"""Device time per train step of the expert layers' grouped products
(`lax.ragged_dot`) and the activation between them, forward,
recomputation and backward alike: ops under the program's scope
`moe.products`, nested in a pass of `moe.experts` (harness/scopes.py),
and the compiler's own calls for the products: on the TPU they become
Mosaic calls named `ragged-dot-*` (`ragged-dot-none` the product,
`ragged-dot-metadata` its groups' tiles) that carry no name stack of the
program's, so the scope alone reads only the activation (my chip run,
PR 38: `tests/data/moe_small.xplane.pb`).  None for a program without
the scope."""

from perfbench.harness import scopes


def read(run):
    if scopes.ms_per_step(run, r"moe\.products") is None:
        return None
    return scopes.ms_per_step(run, r"moe\.products|ragged-dot-.*")
