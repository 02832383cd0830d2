"""Device time per train step of the selective scan itself (softplus,
the recurrence in whichever form the program lowers it to: the two
Mosaic kernels on the TPU and what joins them, the laying of B and C
over lanes among it; the skip D u): ops under the program's scope
`ssm.scan` (harness/scopes.py), which lies inside `ssm`.  None for a
program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"ssm\.scan")
