"""Series `pack_decode` over the window: the half of a pack that fills
the batch from the records (JPEG decode, or the raw per-record loop), per
image packed."""

from perfbench.harness.series import delta


def read(run):
    half, pack = delta(run, "pack_decode"), delta(run, "pack")
    if not half or not pack or not pack[1]:
        return None
    return 1e3 * half[0] / (pack[1] * run["batch"])
