"""Device time per train step of the expert layers' sort (the held
mask, the argsort of the k N assignments, the counts' scatter-add and
their running sums), forward and recomputation (integers carry no
gradient): ops under the program's scope `moe.sort`, nested in
`moe.route` (harness/scopes.py).  None for a program without the
scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"moe\.sort")
