"""True prompt tokens over the tokens the prefill programs computed
(admit width x bucket), over the window's prefill dispatches."""


def read(ctx):
    b = ctx["bench"]
    plen = {r.rid: len(r.item.ids) for r in b["records"]}
    true = {}
    padded = {}
    for ev in b.get("spans", []):
        if ev.get("comp") != "prefill" or ev["rid"] not in plen:
            continue
        key = (ev.get("tick"), ev["t0"])
        true[key] = true.get(key, 0) + plen[ev["rid"]]
        padded[key] = ev["bucket"] * ev["width"]
    if not padded:
        return None
    return 100.0 * sum(true.values()) / sum(padded.values())
