"""Device time per train step of the Gated Memory Units (the product in,
SiLU, the gate with the memory, the product out), forward, recomputation
and backward: ops under the program's scope `gmu` (harness/scopes.py).
None for a program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"gmu")
