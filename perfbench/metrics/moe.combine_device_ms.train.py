"""Device time per train step of the expert layers' combine (the mask
of rows past the last group, the gate product, the scatter-add into the
running sum; transposed, a gather of its rows), forward, recomputation
and backward alike: ops under the program's scope `moe.combine`, nested
in a pass of `moe.experts` (harness/scopes.py).  None for a program
without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"moe\.combine")
