"""Device time per train step of the optimizer's update (clip, Adam over every blob): ops whose name stack carries the
program's scope (harness/opmeta.py), summed inside the traced window."""

from perfbench.harness import opmeta


def read(run):
    if not run.get("trace") or not run["steps"]:
        return None
    s = opmeta.of_run(run).get("update")
    return None if s is None else 1e3 * s / run["steps"]
