"""Device time per train step of what `_attention_dispatch` runs (the
flash kernels, or the einsum path), forward, recomputation and backward,
apart from the products, norms and rotary turns of the attention layer
around it: ops under the program's scope `attn.core` (harness/scopes.py).
Every attention layer type ends in that one dispatch."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"attn\.core")
