"""Device time per train step of the expert layers' routing (the
router's product at HIGHEST, the scoring, `top_k`, the weights, and the
sort of `moe.sort` inside it), forward, recomputation and backward
alike: ops under the program's scope `moe.route` (harness/scopes.py).
None for a program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"moe\.route")
