"""What a `recompute_block` keeps between its forward and its backward.

`Net.apply` runs a block under `jax.checkpoint(policy=BLOCK_POLICY)`:
the backward pass computes the block again from the blobs that enter
it, except the values named here, which the forward pass hands over as
it made them.  One rule chooses them: the outputs of a Mosaic forward
kernel that its own backward reads, and the router's result.  A layer
names such a value where it makes it (`keep`); the block keeps what was
named.  Everywhere else (a TEST pass, a net without blocks,
COS_REMAT=1) a name is an identity.

A block computes nothing a third time.  A layer that would recompute an
elementwise stage between its products by itself (`stage`: the Gated
DeltaNet's and Mamba's convolution, the preparation of the delta rule's
operands) does so only outside a block: there `stage` is
`jax.checkpoint`.  Inside one the block's recomputation is the second
and last time the stage runs, and its intermediates live from there to
the stage's transpose; a checkpoint of its own would keep only its
inputs from that second run and compute the stage once more before it
could pull back.  (A stage that the compiler fuses worse bare than its
third run costs, as those that read a kernel's kept output do, stays a
plain `jax.checkpoint` at its call site.)
"""
from __future__ import annotations

import contextlib

import jax
from jax.ad_checkpoint import checkpoint_name

from . import route

KEPT = (
    # `pallas_kernels.flash_attention`: cos_flash_fwd's output (read by
    # delta and by W_o's backward) and its log-sum-exp a row (read by
    # both backward kernels), after the join of the chunk pairs' parts
    "flash.out", "flash.lse",
    # `pallas_kernels._gdn_rule`: cos_gdn_fwd's output (read by the
    # gated norm's backward) and the states at the groups' edges (where
    # the backward's recomputation of a group starts)
    "gdn.o", "gdn.edges",
    # `pallas_kernels._ssm_scan`: cos_ssm_fwd's output (read by the
    # gate's and the skip's backward) and the states at the chunks'
    # edges (where the backward's recomputation of a chunk starts)
    "ssm.y", "ssm.edges",
    # `pallas_kernels._ssd_rule` / `layers._ssd_groups`: the Mamba-2
    # scan's output (read by the gated norm's backward) and the states
    # its backward starts from: before every chunk (cos_ssd_fwd's, read
    # by cos_ssd_bwd) or at the groups' edges (the XLA form's, where the
    # recomputation of a group starts)
    "ssd.y", "ssd.edges",
    # `layers._moe_dropless`, scope moe.route: what the router's own
    # backward reads (its product at HIGHEST, top_k's choice) ...
    "moe.logits", "moe.topi",
    # ... and what the expert loops read: the k N weights, the argsort
    # of the assignments padded to whole passes, each held expert's
    # first and last sorted row, the held assignments
    "moe.gates", "moe.order", "moe.starts", "moe.ends", "moe.total",
)

# the `jax.checkpoint` policy of every `recompute_block`
BLOCK_POLICY = jax.checkpoint_policies.save_only_these_names(*KEPT)

# the entries of the blocks being traced, innermost last: what a block
# keeps ({name: bytes}, a name's bytes summed over the block's layers)
# and the stages that ran in it without a checkpoint of their own
_TRACING: list = []


def keep(x: jax.Array, name: str) -> jax.Array:
    """`x` under `name`: kept by a `recompute_block` that holds this
    call, itself anywhere else."""
    if name not in KEPT:
        raise KeyError(f"{name!r} is not a value a recompute_block keeps")
    if _TRACING:
        kept = _TRACING[-1]["kept"]
        kept[name] = kept.get(name, 0) + x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


def stage(fn):
    """`fn`, an elementwise stage of a layer whose intermediates are not
    worth keeping from the forward pass to the backward pass: inside a
    `recompute_block` itself (the block computes it again, once), under
    a `jax.checkpoint` of its own anywhere else."""
    if not _TRACING:
        return jax.checkpoint(fn)
    _TRACING[-1]["stages"] += 1
    return fn


@contextlib.contextmanager
def block_trace(tag: str):
    """Around the trace of block `tag`'s layers: what they name is the
    block's entry (a later trace of the block writes it anew)."""
    _TRACING.append(route.lowered("recompute", tag, kept={}, stages=0))
    try:
        yield
    finally:
        _TRACING.pop()


def note_shared(blobs: dict) -> None:
    """A net whose blocks are being traced says which of their blobs
    blocks further on than the next read (`Net.shared_blobs`: per blob
    the layer that makes it, its readers and bytes): the job's
    `info.shared`."""
    for blob, facts in blobs.items():
        route.lowered("shared", blob, **facts)


def _summary(blocks: dict) -> dict:
    """`route.plans()["recompute"]`, the job's `info.recompute`:
    {"blocks": {block: {name: bytes}}, "bytes_a_step": their sum,
    "keep_nothing": the blocks whose layers named nothing,
    "stages_unwrapped": {block: the stages that ran in it without a
    checkpoint of their own, where any did}} for the blocks traced by
    this process; {} without one."""
    if not blocks:
        return {}
    kept = {t: e["kept"] for t, e in blocks.items()}
    return {"blocks": {t: dict(e) for t, e in kept.items() if e},
            "bytes_a_step": sum(sum(e.values()) for e in kept.values()),
            "keep_nothing": [t for t, e in kept.items() if not e],
            "stages_unwrapped": {t: e["stages"] for t, e in blocks.items()
                                 if e["stages"]}}


route.summarized("recompute", _summary)
