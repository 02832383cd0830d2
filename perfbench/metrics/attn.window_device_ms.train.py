"""Device time per train step of the attention layers that run under a
window (the flash kernels that skip what the window hides, or the masked
einsum path), forward, recomputation and backward: ops under the
program's scope `attn.window` (harness/scopes.py), which lies inside
`attn` and around `attn.core`, so that `attn.core` less this is the
global layers'.  None for a program without the scope."""

from perfbench.harness import scopes


def read(run):
    return scopes.ms_per_step(run, r"attn\.window")
