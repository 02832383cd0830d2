"""Series `read` over the window: the feeder's own busy time (LMDB cursor,
Datum parse, shuffle buffer), one sample per batch of records, per image."""

from perfbench.harness.series import delta


def read(run):
    d = delta(run, "read")
    if not d or not d[1]:
        return None
    return 1e3 * d[0] / (d[1] * run["batch"])
