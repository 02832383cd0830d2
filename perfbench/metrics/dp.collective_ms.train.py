"""Time per train step during which a collective is under way on a
device (mean over the devices; harness/trace.py)."""


def read(run):
    t = run.get("trace")
    if not t or not run["steps"] or "collective_s" not in t:
        return None
    return 1e3 * t["collective_s"] / run["steps"]
