"""Which form of an operator is lowered, and the record of what was.

An operator with a Mosaic kernel has two forms, chosen here by what can
be observed while the program is traced, no option: the kernel on the
TPU (in interpret mode under COS_FLASH_INTERPRET=1, the CPU suite's way
in) when the caller's shape tiles, float32 comes in where the kernel
is written for float32, and no mesh of several devices is installed (a
bare Mosaic call cannot be partitioned; an operator that is parallel
over the batch asks for the mesh instead and goes through `shard_map`);
else the XLA form, which is also what the kernels' tests are held to.
`kernel` is that rule, once.  A caller keeps what is its own: the
predicate that says its shape tiles, and the two forms to call.

What a trace chose is written down with `lowered` and read with `plans`;
a `-train` job puts every kind into its metrics as `info.<kind>` after
its first step (`processor._note_lowering_plans`).  What a kind's facts
mean is documented where they are written.
"""
from __future__ import annotations

import contextlib
import copy
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

_SUPPRESS = 0        # > 0 inside `suppress_flash`
_MESH: list = []     # (mesh, batch_axes, head_axes, time_axes), innermost last

# {kind: {key: facts}}: static, written while a program is traced
_PLANS: Dict[str, Dict[str, dict]] = {}
# a kind whose `info.<kind>` is computed from its entries
_SUMMARIES: Dict[str, Callable[[Dict[str, dict]], dict]] = {}


@contextlib.contextmanager
def suppress_flash():
    """No flash attention kernel for the duration: the opt-out of a
    caller (the autotuner's `attention: reference` variant, tests) that
    needs the einsum form whatever the backend."""
    global _SUPPRESS
    _SUPPRESS += 1
    try:
        yield
    finally:
        _SUPPRESS -= 1


@contextlib.contextmanager
def flash_mesh(mesh, batch_axes=("dp",), head_axes=("tp",),
               time_axes=("sp",)):
    """`mesh` is installed for the duration of a trace
    (`MeshLayout.install_flash`, meshes of several devices).  Attention
    is parallel over batch x heads and LRN over the batch, so their
    kernels run on each device's block through `shard_map`; where the
    mesh also shards TIME (sp axis), attention's body is the
    differentiable fused ring (`parallel.sp._ring_attention_local`:
    K/V shards rotate on ppermute while flash kernels accumulate).
    Every other kernel gives way to its XLA form."""
    _MESH.append((mesh, tuple(batch_axes), tuple(head_axes),
                  tuple(time_axes)))
    try:
        yield
    finally:
        _MESH.pop()


class Route(NamedTuple):
    """The kernel form is to be lowered: in interpret mode or not, and
    (for a caller that asked for `shard_map`) under which installed
    (mesh, batch axes, head axes, time axes), None without one."""
    interpret: bool
    mesh: Optional[Tuple] = None


def on_tpu() -> bool:
    """The Mosaic kernels compile for this backend: the TPU, unless
    COS_DISABLE_PALLAS opts out (CPU tests run them in interpret mode
    instead).  A backend that fails to initialise raises here — it must
    not quietly become "no Pallas"."""
    if os.environ.get("COS_DISABLE_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


def kernel(tiles, *operands, mesh: str = "refuse",
           attention: bool = False) -> Optional[Route]:
    """The rule of the module's docstring: a `Route` when the kernel
    form is to be lowered, None for the XLA form.  `tiles`: the
    caller's own answer to "does this shape fit the kernel" (anything
    falsy, a missing plan included, is no); `operands`: the arrays that
    have to arrive in float32; `mesh`: "refuse" an installed mesh, or
    hand it to a caller that wraps its kernel in "shard_map";
    `attention`: `suppress_flash` and COS_DISABLE_FLASH, the vetoes of
    the flash kernels alone, apply.

    The one place that reads the backend and the three environment
    names, while a program is traced: the one exemption from the
    repo's COS003 rule (no host read under a trace).  The suite sets
    the names per test, so they are read per call, here and in
    `on_tpu`."""
    interpret = os.environ.get("COS_FLASH_INTERPRET") == "1"
    if not (on_tpu() or interpret):
        return None
    if attention and (_SUPPRESS or os.environ.get("COS_DISABLE_FLASH")):
        return None
    if mesh == "refuse" and _MESH:
        return None
    if not tiles or any(a.dtype != jnp.float32 for a in operands):
        return None
    return Route(interpret, _MESH[-1] if _MESH else None)


def entries(kind: str) -> Dict[str, dict]:
    """{key: facts} of `kind` as `lowered` writes them: the record
    itself, not a copy."""
    return _PLANS.setdefault(kind, {})


def lowered(kind: str, key: str, **facts) -> dict:
    """The entry of `key` among the `kind` plans, made on first sight;
    `facts`, where given, are now all it holds.  Without facts it is
    the caller's to add to: what is gathered over several traces (the
    layers that took a shape, a call's three kernels) stays in it."""
    entry = entries(kind).setdefault(key, {})
    if facts:
        entry.clear()
        entry.update(facts)
    return entry


def summarized(kind: str, fn: Callable[[Dict[str, dict]], dict]) -> None:
    """`plans()[kind]` is `fn(the kind's entries)`, not the entries."""
    _SUMMARIES[kind] = fn


def plans() -> Dict[str, dict]:
    """{kind: {key: facts}} of everything this process has lowered:
    copies, a kind with nothing to say left out."""
    out = {}
    for kind, entries in _PLANS.items():
        got = (_SUMMARIES[kind](entries) if kind in _SUMMARIES
               else copy.deepcopy(entries))
        if got:
            out[kind] = got
    return out


def forget(*kinds: str) -> None:
    """Drop the record of `kinds` (all without one): a test's way to
    read one trace alone."""
    for kind in kinds or list(_PLANS):
        entries(kind).clear()
