"""Device time per train step of the gated delta rule alone (decay and
write strength, the normalisation of q and k, the chunked scan over the
sequence), forward, recomputation and backward, whatever implements it:
ops under the program's scope `gdn.scan` (harness/scopes.py)."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"gdn\.scan")
