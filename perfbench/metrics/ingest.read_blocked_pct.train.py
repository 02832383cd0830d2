"""Series `read_blocked` over the window: the share of the window the
feeder stood before a full feed queue."""

from perfbench.harness.series import delta


def read(run):
    d = delta(run, "read_blocked", witness="read")
    return None if d is None else 100.0 * d[0] / run["window_s"]
