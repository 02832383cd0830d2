"""What a `recompute_block` keeps between its forward and its backward.

`Net.apply` runs a block under `jax.checkpoint(policy=BLOCK_POLICY)`:
the backward pass computes the block again from the blobs that enter
it, except the values named here, which the forward pass hands over as
it made them.  One rule chooses them: the outputs of a Mosaic forward
kernel that its own backward reads, and the router's result.  A layer
names such a value where it makes it (`keep`); the block keeps what was
named.  Everywhere else (a TEST pass, a net without blocks,
COS_REMAT=1) a name is an identity.
"""
from __future__ import annotations

import contextlib

import jax
from jax.ad_checkpoint import checkpoint_name

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

# What the blocks traced by this process keep: {block: {name: bytes}},
# a name's bytes summed over the block's layers.  Static, written while
# a program is traced; the -train job puts it into its metrics as
# `info.recompute`.
_BLOCKS: dict = {}
_TRACING: list = []     # the entries of the blocks being traced
# The blobs one block makes and blocks further on than the next read
# (`Net.shared_blobs`), of the nets whose blocks this process traced:
# the job's `info.shared`.
_SHARED: dict = {}


def keep(x: jax.Array, name: str) -> jax.Array:
    """`x` under `name`: kept by a `recompute_block` that holds this
    call, itself anywhere else."""
    if name not in KEPT:
        raise KeyError(f"{name!r} is not a value a recompute_block keeps")
    if _TRACING:
        entry = _TRACING[-1]
        entry[name] = entry.get(name, 0) + x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


@contextlib.contextmanager
def block_trace(tag: str):
    """Around the trace of block `tag`'s layers: what they name is the
    block's entry (a later trace of the block writes it anew)."""
    entry = _BLOCKS[tag] = {}
    _TRACING.append(entry)
    try:
        yield
    finally:
        _TRACING.pop()


def note_shared(blobs: dict) -> None:
    """A net whose blocks are being traced says which of their blobs
    cross blocks."""
    _SHARED.update(blobs)


def shared_plans() -> dict:
    return {k: dict(v) for k, v in _SHARED.items()}


def recompute_plans() -> dict:
    """{"blocks": {block: {name: bytes}}, "bytes_a_step": their sum,
    "keep_nothing": the blocks whose layers named nothing} for the
    blocks traced by this process; {} without one."""
    if not _BLOCKS:
        return {}
    return {"blocks": {t: dict(e) for t, e in _BLOCKS.items() if e},
            "bytes_a_step": sum(sum(e.values()) for e in _BLOCKS.values()),
            "keep_nothing": [t for t, e in _BLOCKS.items() if not e]}
