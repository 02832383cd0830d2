"""Device time per train step of the Mamba-2 mixers (the products, the
taps with their bias and SiLU, softplus, the decays, the chunked scan,
the skip, the gate and the grouped norm), forward, recomputation and
backward: ops under the program's scope `ssd` (harness/scopes.py).  None
for a program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"ssd")
