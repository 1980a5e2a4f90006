"""Decode slots in use over the slots there are, mean over the engine
steps of the window that decoded."""


def read(ctx):
    b = ctx["bench"]
    act = [s["active"] for s in b["steps"] if s["active"]]
    if not act:
        return None
    return 100.0 * sum(act) / len(act) / b["max_slots"]
