"""Series `step` over the window: what the solver thread spends
enqueueing one step (not the device's time for it)."""

from perfbench.harness.series import delta


def read(run):
    d = delta(run, "step")
    return 1e3 * d[0] / d[1] if d and d[1] else None
