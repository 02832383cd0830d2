"""Device time per train step of the short convolution's gates and taps
alone (gate, shifted sums over time, gate: the part between the layer's
two products), forward, recomputation and backward: ops under the
program's scope `sconv.mix` (harness/scopes.py)."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"sconv\.mix")
