"""Device-busy time (union of op intervals on a device's plane, mean
over the devices) per train step executed in the traced window."""


def read(run):
    t = run.get("trace")
    if not t or not run["steps"]:
        return None
    return 1e3 * t["busy_s"] / run["steps"]
