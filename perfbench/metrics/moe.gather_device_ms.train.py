"""Device time per train step of the expert layers' row gather (the
slice of the sorted order, the group sizes, `xf[tok]`; transposed, a
scatter-add into dx), forward, recomputation and backward alike: ops
under the program's scope `moe.gather`, nested in a pass of
`moe.experts` (harness/scopes.py).  None for a program without the
scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"moe\.gather")
