"""1 - device busy / traced window, mean over the devices."""


def read(run):
    t = run.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
