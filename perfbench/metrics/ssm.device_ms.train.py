"""Device time per train step of the Mamba mixers (the four products,
the taps with their bias and SiLU, softplus, the selective scan, the
skip and the gate), forward, recomputation and backward: ops under the
program's scope `ssm` (harness/scopes.py).  None for a program without
the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"ssm")
