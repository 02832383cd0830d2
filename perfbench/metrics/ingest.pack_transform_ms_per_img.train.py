"""Series `pack_transform` over the window: the half of a pack that
crops, mirrors and subtracts the mean, per image packed."""

from perfbench.harness.series import delta


def read(run):
    half, pack = delta(run, "pack_transform"), delta(run, "pack")
    if not half or not pack or not pack[1]:
        return None
    return 1e3 * half[0] / (pack[1] * run["batch"])
