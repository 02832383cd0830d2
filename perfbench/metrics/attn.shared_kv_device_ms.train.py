"""Device time per train step of the cross-attention layers, which have
W_q and W_o alone and attend with the keys and values another layer
made: ops whose name stack holds both the layer's own name (the net
text's `L<i>.attn` of the layers the configuration's reference calls
`cross`) and the program's scope `attn` (harness/scopes.py), forward,
recomputation and backward.  None for a configuration without such a
layer or a program without the scope."""

import importlib

from perfbench.harness import opmeta, scopes


def read(run):
    if not run.get("trace") or not run.get("steps"):
        return None
    cfg = run["ctx"]["config"]
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    kinds = model.dims(cfg).get("kinds", ())
    layers = {f"L{i}.attn" for i, k in enumerate(kinds) if k == "cross"}
    ops, window = scopes.ops_of_run(run)
    if not layers or not ops:
        return None
    lo, hi = window
    total, found = 0.0, False
    for tf_op, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        tokens = set(opmeta._SPLIT.split(tf_op.split(":", 1)[0]))
        if "attn" in tokens and tokens & layers:
            total += d
            found = True
    return 1e3 * total / run["steps"] if found else None
