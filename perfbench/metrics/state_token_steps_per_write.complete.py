"""Token-steps of a live lane that one write of its state row covers:
the live lanes' token-steps times the layers decoded in the window, over
the state rows written in it. From the program's `step` spans, which
carry `state_row_writes` (live lanes x layers x writes a dispatch) where
a step decodes, and the `decode` spans under them (one a live lane and
dispatch, `chunk` token-steps each). 4.0 where a chunk of 4 writes its
rows once, 1.0 where every token-step writes them. A program without
the field (the parent of the PR that added it) gives None."""
import bisect


def read(ctx):
    b = ctx["bench"]
    spans = b.get("spans", [])
    steps = sorted((ev["t0"], ev["t1"], ev["state_row_writes"])
                   for ev in spans
                   if ev.get("comp") == "step" and "state_row_writes" in ev
                   and b["t_open"] <= ev["t0"] <= b["t_close"])
    writes = sum(w for _, _, w in steps)
    if not writes:
        return None
    starts = [t0 for t0, _, _ in steps]
    lane_steps = 0
    for ev in spans:
        if ev.get("comp") != "decode":
            continue
        i = bisect.bisect_right(starts, ev["t0"]) - 1
        if i >= 0 and ev["t0"] <= steps[i][1]:
            lane_steps += ev.get("chunk", 0)
    return lane_steps * ctx["config"]["num_hidden_layers"] / writes
