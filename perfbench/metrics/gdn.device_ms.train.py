"""Device time per train step of the Gated DeltaNet operators (the two
products in, the taps and their SiLU, the gated delta rule, the gated
norm, the product out), forward, recomputation and backward: ops under
the program's scope `gdn` (harness/scopes.py).  None for a program
without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"gdn")
