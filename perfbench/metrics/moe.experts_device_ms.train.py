"""Device time per train step of the expert layers' scan over the
passes of sorted rows (the row gather, the grouped products, the gate
product and scatter-add, and the loop's and the conditionals' own ops),
forward, recomputation and backward alike: ops under the program's scope
`moe.experts` (harness/scopes.py), and the compiler's own calls for
`lax.ragged_dot`, which only this scan makes: on the TPU the grouped
products become Mosaic calls named `ragged-dot-*` that carry no name
stack of the program's, so no scope and no prototxt layer reaches them
(my chip run, PR 38: `tests/data/moe_small.xplane.pb`).  None for a
program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"moe\.experts|ragged-dot-.*")
