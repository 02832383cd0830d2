"""Device time per train step of the Mamba-2 scan itself (softplus, the
decays, the recurrence in whichever form the program lowers it to, the
skip D u): ops under the program's scope `ssd.scan` (harness/scopes.py),
which lies inside `ssd`.  None for a program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"ssd\.scan")
