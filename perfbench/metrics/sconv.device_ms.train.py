"""Device time per train step of the gated short-convolution layers (both
products, the gates and the taps; forward, recomputation and backward):
ops under the program's scope `sconv` (harness/scopes.py), summed inside
the traced window."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"sconv")
