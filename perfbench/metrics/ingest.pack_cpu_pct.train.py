"""Series `pack_cpu` / `pack` over the window: of the wall time of the
packs, the share their threads were on a CPU (`time.thread_time()`); the
rest they waited for the GIL or the scheduler."""

from perfbench.harness.series import delta


def read(run):
    cpu, pack = delta(run, "pack_cpu"), delta(run, "pack")
    if not cpu or not pack or pack[0] <= 0:
        return None
    return 100.0 * cpu[0] / pack[0]
