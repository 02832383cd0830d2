"""Device time per train step of the attention layers (projections, rotary turns, the attention kernels, forward, recomputation and backward): ops whose name stack carries the
program's scope (harness/opmeta.py), summed inside the traced window."""

from perfbench.harness import opmeta


def read(run):
    if not run.get("trace") or not run["steps"]:
        return None
    s = opmeta.of_run(run).get("attn")
    return None if s is None else 1e3 * s / run["steps"]
