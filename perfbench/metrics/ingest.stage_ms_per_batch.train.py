"""Series `stage` over the window: the stager's host copy, `device_put`
and device-transform dispatch, per staged batch."""

from perfbench.harness.series import delta


def read(run):
    d = delta(run, "stage")
    return 1e3 * d[0] / d[1] if d and d[1] else None
