"""Pallas TPU kernels for ops XLA doesn't fuse optimally.

LRN ACROSS_CHANNELS (CaffeNet norm1/norm2 hot path): XLA lowers the
reduce_window over channels to a separate pass over HBM; the Pallas
kernel keeps each (C, spatial-tile) block resident in VMEM and computes
square → 5-wide channel-window sum (static shifted adds on the VPU) →
pow → divide in one fused pass, one HBM read + one write per element.

`lrn_across_channels(x, ...)` pads the flattened spatial dim to the
128-lane grid, runs the kernel per (batch, tile), and is used by
`ops.layers._lrn` when running on TPU (fallback: the XLA reduce_window
path — numerically identical, see tests/test_pallas.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import route
from .recompute import keep

TILE = 512  # spatial lanes per block (4 × 128)


# `pl.pallas_call` hands back a function under an inlined `jax.jit`.
# Made anew at every call site, as a kernel with many sites in a step
# is (an attention's chunk pairs in every layer, the convolution stage
# and the scans once a layer forward, again in a block's recomputation
# and backward, the expert layers' 8 to 12 products a layer), the body,
# the index maps and the grid are traced again each time, in Python, at
# every job start, compile cache or none: half of a smallthinker job's
# 14 s of building and tracing here, 1.9 s of an lfm2 step's for the 72
# sites of the grouped products alone.  Built once an argument list,
# every site after a shape's first is that jit's cache lookup and binds
# the same equation under its own name stack; what is lowered is what it
# was.  PERF.md section 7, "what a Mosaic call site costs a job's start".
_BUILDERS: list = []


def _built_once(build):
    """`build(*shapes, **statics)` -> what `pl.pallas_call` returns, as
    a function of the operands: called with arrays (and the statics by
    name) it hands `build` their `jax.ShapeDtypeStruct`s, runs it once
    an argument list (`functools.lru_cache`: a static that cannot be
    hashed is an error) and applies the call to the arrays.  All that
    `build` can read of the site is its arguments, so they are the
    whole key."""
    cached = functools.lru_cache(maxsize=256)(build)

    @functools.wraps(build)
    def call(*operands, **statics):
        return cached(*(jax.ShapeDtypeStruct(x.shape, x.dtype)
                        for x in operands), **statics)(*operands)

    _BUILDERS.append(cached)
    return call


def forget_calls() -> None:
    """Drop every call `_built_once` holds (tests that count traces)."""
    for cached in _BUILDERS:
        cached.cache_clear()


def _window_sum(v: jax.Array, pad: int) -> jax.Array:
    """Σ over the symmetric channel window via static shifted adds (VPU)."""
    acc = v
    for off in range(1, pad + 1):
        down = jnp.concatenate(
            [jnp.zeros((off, v.shape[1]), v.dtype), v[:-off]], axis=0)
        up = jnp.concatenate(
            [v[off:], jnp.zeros((off, v.shape[1]), v.dtype)], axis=0)
        acc = acc + down + up
    return acc


def _lrn_kernel_fwd_only(x_ref, o_ref, *, local_size: int, alpha: float,
                         beta: float, k: float, fuse_relu: bool):
    """The one forward kernel (train AND eval): no scale residual.
    The backward kernel recomputes the denominators from x — a few VPU
    ops on a block already resident in VMEM — instead of storing an
    activation-sized scale tensor (round-5 perf pass: dropping the
    residual removes one full-size HBM write on the forward and one
    read on the backward, ~2/7 of the LRN stage's training traffic).

    Math runs in f32 regardless of the I/O dtype: in mixed (bf16)
    training, scale = 1 + (α/n)·Σx² computed in bf16 (eps ≈ 8e-3)
    rounds away most of the normalizer's significant digits.  The
    upcast lives in VMEM, so HBM traffic is unchanged.

    fuse_relu computes lrn(max(x, 0)) on the pre-activation input:
    XLA cannot fuse a producer into an opaque pallas call, so a
    separate ReLU→LRN chain materializes BOTH the relu output (the
    kernel's residual) and — for the relu mask — keeps the
    pre-activation live too.  Fused, the only residual is the
    pre-activation x and the mask is recomputed in VMEM (net.py's
    relu+lrn peephole, COS_FUSE_RELU_LRN)."""
    x = x_ref[0].astype(jnp.float32)
    if fuse_relu:
        x = jnp.maximum(x, 0.0)
    pad = local_size // 2
    scale = k + (alpha / local_size) * _window_sum(x * x, pad)
    o_ref[0] = (x * jnp.exp(-beta * jnp.log(scale))).astype(o_ref.dtype)


def _lrn_bwd_kernel(x_ref, dy_ref, dx_ref, *, local_size: int,
                    alpha: float, beta: float, k: float,
                    fuse_relu: bool):
    """dx = dy·s^{-β} − (2αβ/n)·x·Σ_{i∈W} dy_i·x_i·s_i^{-β-1}, with
    s recomputed in-VMEM from x in f32 (bit-identical to the
    forward's: same block, same op order, same upcast).  With
    fuse_relu the LRN gradient flows through max(x,0) and the mask
    zeroes dx where x < 0 — also recomputed in VMEM."""
    xr = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    x = jnp.maximum(xr, 0.0) if fuse_relu else xr
    pad = local_size // 2
    s = k + (alpha / local_size) * _window_sum(x * x, pad)
    s_nb = jnp.exp(-beta * jnp.log(s))        # s^{-β}
    u = dy * x * s_nb / s                      # dy·x·s^{-β-1}
    dx = dy * s_nb - (2.0 * alpha * beta / local_size) * x \
        * _window_sum(u, pad)
    if fuse_relu:
        dx = jnp.where(xr > 0.0, dx, 0.0)
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _pad_flat(x):
    n, c, h, w = x.shape
    hw = h * w
    padded = (hw + TILE - 1) // TILE * TILE
    xf = x.reshape(n, c, hw)
    if padded != hw:
        xf = jnp.pad(xf, ((0, 0), (0, 0), (0, padded - hw)))
    return xf, hw, padded


def _block_spec(c):
    return pl.BlockSpec((1, c, TILE), lambda i, j: (i, 0, j),
                        memory_space=pltpu.VMEM)


def _lrn_fwd_call(x, local_size, alpha, beta, k, interpret, fuse_relu):
    n, c, h, w = x.shape
    xf, hw, padded = _pad_flat(x)
    kern = functools.partial(_lrn_kernel_fwd_only, local_size=local_size,
                             alpha=alpha, beta=beta, k=k,
                             fuse_relu=fuse_relu)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n, c, padded), x.dtype),
        grid=(n, padded // TILE),
        in_specs=[_block_spec(c)],
        out_specs=_block_spec(c),
        interpret=interpret,
    )(xf)
    return out[:, :, :hw].reshape(n, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def lrn_across_channels(x: jax.Array, local_size: int = 5,
                        alpha: float = 1e-4, beta: float = 0.75,
                        k: float = 1.0,
                        interpret: bool = False,
                        fuse_relu: bool = False) -> jax.Array:
    """(N, C, H, W) → LRN, Caffe semantics (alpha/local_size); with
    fuse_relu, lrn(relu(x)) in one pass (see the kernel docstring).
    Differentiable: a second fused kernel computes the exact VJP,
    recomputing the denominators (and relu mask) in VMEM from the
    saved input — the only residual is x itself, so training adds
    zero extra HBM traffic over inference."""
    return _lrn_fwd_call(x, local_size, alpha, beta, k, interpret,
                         fuse_relu)


def _lrn_vjp_fwd(x, local_size, alpha, beta, k, interpret, fuse_relu):
    out = _lrn_fwd_call(x, local_size, alpha, beta, k, interpret,
                        fuse_relu)
    return out, x


def _lrn_vjp_bwd(local_size, alpha, beta, k, interpret, fuse_relu, res,
                 dy):
    x = res
    n, c, h, w = x.shape
    xf, hw, padded = _pad_flat(x)
    dyf, _, _ = _pad_flat(dy)
    kern = functools.partial(_lrn_bwd_kernel, local_size=local_size,
                             alpha=alpha, beta=beta, k=k,
                             fuse_relu=fuse_relu)
    dx = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], c, padded), x.dtype),
        grid=(x.shape[0], padded // TILE),
        in_specs=[_block_spec(c), _block_spec(c)],
        out_specs=_block_spec(c),
        interpret=interpret,
    )(xf, dyf)
    return (dx[:, :, :hw].reshape(n, c, h, w),)


lrn_across_channels.defvjp(_lrn_vjp_fwd, _lrn_vjp_bwd)


# ---------------------------------------------------------------------------
# Fused conv-stem epilogue: bias + ReLU + LRN in one VMEM pass
# ---------------------------------------------------------------------------
# Generalizes the fuse_relu LRN kernel one producer further: the conv's
# per-channel bias add joins relu+lrn in the epilogue, so the conv can
# emit its RAW matmul output and the stem chain conv→(+bias)→relu→lrn
# costs one HBM read + one write per element instead of materializing
# the biased pre-activation as the kernel's residual.  Backward parity
# follows the existing kernel's design: the VJP kernel recomputes the
# biased input, the relu mask, and the normalizers in VMEM from the
# saved RAW x + bias; d_bias is the channel-sum of d_x (exact — the
# bias add is an affine shift), reduced in XLA where it fuses.

def _lrn_kernel_fwd_bias(x_ref, b_ref, o_ref, *, local_size: int,
                         alpha: float, beta: float, k: float):
    """lrn(relu(x + bias)) — the bias_relu epilogue forward.  Math in
    f32 in VMEM regardless of I/O dtype (see _lrn_kernel_fwd_only)."""
    x = x_ref[0].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    x = jnp.maximum(x, 0.0)
    pad = local_size // 2
    scale = k + (alpha / local_size) * _window_sum(x * x, pad)
    o_ref[0] = (x * jnp.exp(-beta * jnp.log(scale))).astype(o_ref.dtype)


def _lrn_bwd_kernel_bias(x_ref, b_ref, dy_ref, dx_ref, *,
                         local_size: int, alpha: float, beta: float,
                         k: float):
    """d/d(x) of lrn(relu(x + bias)): the _lrn_bwd_kernel math on the
    recomputed biased input, masked where x + bias < 0.  The returned
    dx is ALSO d/d(x + bias), so the caller derives d_bias as its
    (N, H, W) channel sum."""
    xr = x_ref[0].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    x = jnp.maximum(xr, 0.0)
    pad = local_size // 2
    s = k + (alpha / local_size) * _window_sum(x * x, pad)
    s_nb = jnp.exp(-beta * jnp.log(s))        # s^{-β}
    u = dy * x * s_nb / s                      # dy·x·s^{-β-1}
    dx = dy * s_nb - (2.0 * alpha * beta / local_size) * x \
        * _window_sum(u, pad)
    dx = jnp.where(xr > 0.0, dx, 0.0)
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _bias_spec(c):
    return pl.BlockSpec((c, 1), lambda i, j: (0, 0),
                        memory_space=pltpu.VMEM)


def _bias_col(bias):
    # (C,) → (C, 1) f32 column: broadcasts against the (C, TILE) block
    return bias.astype(jnp.float32).reshape(-1, 1)


def _bias_lrn_fwd_call(x, bias, local_size, alpha, beta, k, interpret):
    n, c, h, w = x.shape
    xf, hw, padded = _pad_flat(x)
    kern = functools.partial(_lrn_kernel_fwd_bias, local_size=local_size,
                             alpha=alpha, beta=beta, k=k)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n, c, padded), x.dtype),
        grid=(n, padded // TILE),
        in_specs=[_block_spec(c), _bias_spec(c)],
        out_specs=_block_spec(c),
        interpret=interpret,
    )(xf, _bias_col(bias))
    return out[:, :, :hw].reshape(n, c, h, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def bias_relu_lrn_across_channels(x: jax.Array, bias: jax.Array,
                                  local_size: int = 5,
                                  alpha: float = 1e-4,
                                  beta: float = 0.75, k: float = 1.0,
                                  interpret: bool = False) -> jax.Array:
    """(N, C, H, W) raw conv output + (C,) bias → lrn(relu(x + bias)),
    Caffe LRN semantics, one fused pass.  Differentiable in x AND bias:
    the VJP kernel recomputes bias-add, relu mask and normalizers in
    VMEM (residuals: the raw x and the (C,) bias — no biased
    pre-activation is ever materialized in HBM)."""
    return _bias_lrn_fwd_call(x, bias, local_size, alpha, beta, k,
                              interpret)


def _bias_lrn_vjp_fwd(x, bias, local_size, alpha, beta, k, interpret):
    out = _bias_lrn_fwd_call(x, bias, local_size, alpha, beta, k,
                             interpret)
    return out, (x, bias)


def _bias_lrn_vjp_bwd(local_size, alpha, beta, k, interpret, res, dy):
    x, bias = res
    n, c, h, w = x.shape
    xf, hw, padded = _pad_flat(x)
    dyf, _, _ = _pad_flat(dy)
    kern = functools.partial(_lrn_bwd_kernel_bias, local_size=local_size,
                             alpha=alpha, beta=beta, k=k)
    dx = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((n, c, padded), x.dtype),
        grid=(n, padded // TILE),
        in_specs=[_block_spec(c), _bias_spec(c), _block_spec(c)],
        out_specs=_block_spec(c),
        interpret=interpret,
    )(xf, _bias_col(bias), dyf)
    dx = dx[:, :, :hw].reshape(n, c, h, w)
    # the padded tail lanes of dx are exact zeros (dy padding), so the
    # channel sum over the CROPPED dx is the exact d_bias
    db = jnp.sum(dx.astype(jnp.float32), axis=(0, 2, 3)).astype(
        bias.dtype)
    return dx, db


bias_relu_lrn_across_channels.defvjp(_bias_lrn_vjp_fwd,
                                     _bias_lrn_vjp_bwd)


def xla_lrn_across_channels(x, local_size, alpha, beta, k):
    """THE XLA across-channels LRN fallback chain (square → channel
    reduce_window → scale → divide) — one copy shared by
    ops.layers._lrn's off-TPU path and the fused-epilogue fallback
    below, so a numerics fix can never land in one and miss the
    other."""
    from jax import lax
    sq = x * x
    pad = local_size // 2
    sqp = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    s = lax.reduce_window(sqp, 0.0, lax.add, (1, local_size, 1, 1),
                          (1, 1, 1, 1), "VALID")
    scale = k + (alpha / local_size) * s
    return x / jnp.power(scale, beta)


def xla_bias_relu_lrn(x, bias, local_size, alpha, beta, k):
    """Reference/fallback path for the fused stem epilogue — identical
    semantics on every backend (ops.layers._lrn routes here off-TPU)."""
    x = jnp.maximum(x + bias.reshape(1, -1, 1, 1).astype(x.dtype), 0)
    return xla_lrn_across_channels(x, local_size, alpha, beta, k)


# ---------------------------------------------------------------------------
# int8 forward matmul (serving InnerProduct)
# ---------------------------------------------------------------------------
# The quantized-serving down payment (ROADMAP item 3): InnerProduct
# forward as an int8×int8 MXU matmul with int32 accumulation, weights
# and activations on per-blob max-abs scales — the exact scale
# machinery gradsync's int8 wire uses (parallel/gradsync.quantize_int8,
# round-to-nearest here: inference wants determinism, not unbiased
# accumulation).  int8 quarters the weight HBM read and doubles MXU
# issue rate on chips with int8 MXU paths; accuracy drift is gated by
# the autotuner's pinned parity tolerance before the variant is chosen.

INT8_BLOCK_M = 32          # int8 min sublane tile
INT8_BLOCK_N = 128
INT8_BLOCK_LANE = 128      # K must tile the 128-lane dimension


def _int8_matmul_kernel(x_ref, w_ref, o_ref):
    # (bm, K) int8 · (bn, K) int8 → (bm, bn) int32 on the MXU
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def int8_matmul(xq: jax.Array, wq: jax.Array) -> jax.Array:
    """(M, K) int8 @ (N, K) int8ᵀ → (M, N) int32.  Pallas-tiled where
    `route.kernel` says so and the shapes tile (grid over M/N blocks, K
    resident per block — the flash kernels' layout); XLA int8
    dot_general otherwise (same int32-accumulated math on every
    backend, incl. CPU)."""
    m, kk = xq.shape
    n = wq.shape[0]
    kernel = route.kernel(m % INT8_BLOCK_M == 0 and n % INT8_BLOCK_N == 0
                          and kk % INT8_BLOCK_LANE == 0)
    if kernel:
        xspec = pl.BlockSpec((INT8_BLOCK_M, kk), lambda i, j: (i, 0),
                             memory_space=pltpu.VMEM)
        wspec = pl.BlockSpec((INT8_BLOCK_N, kk), lambda i, j: (j, 0),
                             memory_space=pltpu.VMEM)
        ospec = pl.BlockSpec((INT8_BLOCK_M, INT8_BLOCK_N),
                             lambda i, j: (i, j),
                             memory_space=pltpu.VMEM)
        return pl.pallas_call(
            _int8_matmul_kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
            grid=(m // INT8_BLOCK_M, n // INT8_BLOCK_N),
            in_specs=[xspec, wspec],
            out_specs=ospec,
            interpret=kernel.interpret,
        )(xq, wq)
    return jax.lax.dot_general(xq, wq, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def int8_inner_product(x: jax.Array, w: jax.Array, *,
                       transpose: bool = False,
                       w_scale: Optional[jax.Array] = None
                       ) -> jax.Array:
    """Quantized InnerProduct forward: y ≈ x @ wᵀ (Caffe layout; or
    x @ w when `transpose`), both operands on per-blob max-abs int8
    scales, int32 accumulation, output in x's dtype.  Forward-only —
    the serving path; training never routes here.

    Two weight regimes:

      * `w` float, `w_scale` None — the autotune-variant path: the
        weight quantizes INSIDE the traced forward, an O(N·K)
        abs-max+round paid on every flush.  The autotuner's A/B
        measures the variant WITH this cost, so a net where
        re-quantization eats the matmul win never selects int8.
      * `w` already int8 with its publish-time `w_scale` — the
        quantized-RESIDENT path (serving/quant.py): the model was
        quantized ONCE at ModelRegistry.publish and the resident blob
        IS the MXU operand, so the per-call weight quantization above
        disappears; only the activation still quantizes per call
        (it must — its values change per request)."""
    from ..parallel.gradsync import quantize_int8
    wn = w.T if transpose else w              # (N, K)
    xq, sx = quantize_int8(x, None)
    if wn.dtype == jnp.int8:
        if w_scale is None:
            raise ValueError(
                "int8_inner_product: pre-quantized int8 weight needs "
                "its publish-time w_scale (serving/quant.py)")
        wqn, sw = wn, w_scale
    else:
        wqn, sw = quantize_int8(wn, None)
    acc = int8_matmul(xq, wqn)
    return (acc.astype(jnp.float32) * (sx * sw)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention (blockwise online-softmax), fwd + bwd kernels
# ---------------------------------------------------------------------------
# The MultiHeadAttention hot path: XLA materializes the (T, T) score
# matrix in HBM for both passes; these kernels keep one (block_q, T)
# strip of scores in VMEM and stream K/V blocks past it (the standard
# flash decomposition: running max m, normalizer l, f32 accumulator).
# Memory: O(block·T) VMEM instead of O(T²) HBM — within a device this
# is the same trick ring attention plays across devices (parallel/sp.py
# accumulate(), same m/l/corr algebra), so the two compose: ring over
# device shards, flash within a shard.
#
# Layout: q,k,v (B, H, T, D) flattened to (B·H, T, D); grid =
# (B·H, T/block).  K/V block specs expose the full (T, D) per head, in
# the operand type of the products (cast once, before the call: 3 MB a
# head at T=4k, 192/128-wide bf16); rows whose blocks and tiles do not
# fit the default VMEM window are cut into chunks that do and run pair
# by pair (`_flash_chunk`: the same composition the ring makes across
# devices).  A score tile does only what it needs: the tiles wholly
# under the causal diagonal run a body with no mask at all, the tiles
# on it the masked one, and the tile sizes follow from the shape
# (`_flash_tiles`).  Under a window (`window` keys a row, its own among
# them) there is a second edge: the tiles before it are skipped like the
# ones past the diagonal, the ones on it masked by it, pair of chunks by
# pair of chunks (`_window_span`, `_chunk_pairs`).

BLOCK_Q = 128             # the floor of a tile, and the public default
BLOCK_K = 128
_NEG_INF = -1e30          # finite mask value: -inf NaNs the m-corr path

_NT = (((1,), (1,)), ((), ()))      # a @ b.T, contracted in place


def _dot_nt(a, b):
    """a @ b.T in float32, the transpose folded into the product (no
    XLU pass over b)."""
    return jax.lax.dot_general(a, b, _NT,
                               preferred_element_type=jnp.float32)


def _online_softmax_step(q, kb, vb, m, l, acc, *, sm_scale: float,
                         visible=None):
    """One online-softmax accumulation (the flash/ring shared algebra):
    scores for (q, kb) fold into the (m, l, acc) carry; `visible`
    (None = every score) is the causal mask of a tile that holds
    masked scores.  The m_safe
    guard makes fully-masked-so-far rows accumulate exact zeros (a
    no-op for rows that have seen the causal diagonal).  m and l are
    (block_q, 1) column vectors — Mosaic's block-shape rule wants the
    per-row stats rank-2, and the column form broadcasts against the
    (block_q, block_k) score strip with no reshapes.  The products
    take their operands as they come (the caller casts them once, to
    the type the MXU is to see); p is cast to v's."""
    s = _dot_nt(q, kb) * sm_scale
    if visible is not None:
        s = jnp.where(visible, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.dot(
        p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _rows_at(i, block):
    return pl.ds(pl.multiple_of(i * block, block), block)


def _row_minus_col(rows: int, cols: int):
    """(rows, cols) int32 of row index - column index: one tile's
    causal mask is this against a scalar."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _diagonal_span(i, block, other):
    """[lo, hi): the tiles of `other` rows along one axis that tile i of
    `block` rows along the other axis meets on the causal diagonal (the
    axes are equally long).  Before lo and from hi on a tile is whole or
    empty: a q tile sees its k tiles before lo whole and none from hi
    on; a k tile is seen by no q tile before lo and whole from hi on."""
    return (i * block) // other, ((i + 1) * block + other - 1) // other


def _clip(x, lo, hi):
    """x held to [lo, hi]: Python numbers where a plan is counted,
    traced scalars inside a kernel."""
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _window_span(i, block, other, n_other, offset: int, window: int,
                 rows: bool):
    """(lo, e1, e2, hi): the tiles of `other` rows along one axis that
    tile i of `block` rows along the other axis meets under a causal
    mask with a window, row t seeing the columns s with
    t - window < s <= t, the call's rows lying `offset` past its columns
    (a pair of chunks).  [lo, hi) hold a visible score and are visited;
    [e1, e2) hold no masked one and take the body without a mask;
    [lo, e1) straddle the first edge the loop meets and [e2, hi) the
    second.  `rows` = tile i is one of q rows and the loop walks k
    tiles (the window's edge first, then the diagonal); else tile i is
    one of k columns and the loop walks q tiles (the diagonal first).
    Where window >= block + other - 1 no tile lies on both edges and
    e1 <= e2 before the clips; below that every masked tile takes both
    masks, and [e1, max(e1, e2)) is what is whole."""
    big = (n_other + 1) * other

    def tiles(x):           # x // other, of an x held inside the call
        return _clip(x, 0, big) // other

    a = i * block + (offset if rows else -offset)
    b = a + block           # tile i is [a, b) on the loop's axis
    if rows:
        lo = tiles(a - window + 1)              # last column > a - window
        e1 = tiles(b - 1 - window + other)      # first column > b-1-window
        e2, hi = tiles(a), tiles(b + other - 1)
    else:
        lo, e1 = tiles(a), tiles(b + other - 1)
        e2 = tiles(a + window)                  # last row < a + window
        hi = tiles(b + window + other - 2)      # first row < b-1+window
    lo = _clip(lo, 0, n_other)
    hi = _clip(hi, lo, n_other)
    e1 = _clip(e1, lo, hi)
    return lo, e1, _clip(e2, e1, hi), hi


def _window_visible(ahead, shift, window: int, edge: str, both: bool):
    """A masked tile's visible scores: `ahead` is row - column inside
    the tile, `shift` what the tile's place adds to it; the tile lies
    on the window's `edge` or on the "diagonal", or, where `both`, maybe
    on the two."""
    if edge == "diagonal" and not both:
        return ahead >= -shift                  # column <= row
    inside = ahead < window - shift             # column > row - window
    return inside & (ahead >= -shift) if both else inside


def _window_loops(tile, carry, i, block, other, n_other, offset: int,
                  window: int, rows: bool):
    """`tile(j, carry[, visible])` folded over the tiles j that tile i
    meets under the window (`_window_span`): the ones on the first edge
    masked by it, the ones between the edges without a mask, the ones
    on the second edge masked by that; the rest are not visited.  The
    scores of a tile are (block, other), rows x columns where `rows`
    and columns x rows (the dK/dV kernel's transposed scores) else."""
    lo, e1, e2, hi = _window_span(i, block, other, n_other, offset, window,
                                  rows)
    ahead = _row_minus_col(block, other)
    both = window < block + other - 1
    first, second = (("window", "diagonal") if rows
                     else ("diagonal", "window"))

    def on(edge):
        # the call's row offset + r sees column c: what tile (i, j)'s
        # place adds to r - c
        def masked(j, carry):
            place = i * block - j * other
            return tile(j, carry, _window_visible(
                ahead if rows else -ahead,
                offset + (place if rows else -place), window, edge, both))
        return masked

    carry = jax.lax.fori_loop(lo, e1, on(first), carry)
    carry = jax.lax.fori_loop(e1, e2, tile, carry)
    return jax.lax.fori_loop(e2, hi, on(second), carry)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool, block_k: int,
                      window: int = 0, offset: int = 0):
    # operands of every product arrive in the type the MXU is to see
    # (float32: exact, several passes; bfloat16: one pass, float32
    # accumulation, what XLA's default precision gives the einsum path
    # on the TPU); no tile is cast here but p, which is made here
    q = q_ref[0]                                # (block_q, D)
    block_q = q.shape[0]
    qi = pl.program_id(1)

    def tile(i, carry, visible=None):
        at = _rows_at(i, block_k)
        return _online_softmax_step(q, k_ref[0, at, :], v_ref[0, at, :],
                                    *carry, sm_scale=sm_scale,
                                    visible=visible)

    carry = (jnp.full((block_q, 1), _NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32))
    if window:
        # the k tiles before the window's edge and past the diagonal are
        # skipped, the ones on either edge masked, each by its own edge
        carry = _window_loops(tile, carry, qi, block_q, block_k,
                              k_ref.shape[1] // block_k, offset, window,
                              True)
    elif causal:
        # K/V tiles starting past this q tile's last row are fully
        # masked and skipped, the ones ending at or before its first row
        # hold no masked score and take the body without a mask
        n_whole, n_k = _diagonal_span(qi, block_q, block_k)
        carry = jax.lax.fori_loop(0, n_whole, tile, carry)
        ahead = _row_minus_col(block_q, block_k)
        # row qi*block_q + r sees column i*block_k + c
        carry = jax.lax.fori_loop(
            n_whole, n_k,
            lambda i, carry: tile(
                i, carry, ahead >= i * block_k - qi * block_q), carry)
    else:
        carry = jax.lax.fori_loop(0, k_ref.shape[1] // block_k, tile,
                                  carry)
    m, l, acc = carry
    if window and offset:
        # a row of a later chunk may see no column of this one: its part
        # is nothing, weighted by nothing when the parts are added up
        seen = l > 0.0
        l = jnp.where(seen, l, 1.0)
        o_ref[0] = jnp.where(seen, acc / l, 0.0).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(seen, m + jnp.log(l), _NEG_INF)
    else:
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, *, sm_scale: float,
                          causal: bool, block_q: int, window: int = 0,
                          offset: int = 0):
    # the scores of this kernel are transposed, (block_k, block_q): both
    # accumulations are then plain products of them (pT dO, dsT q), and
    # the two per-row statistics broadcast as (1, block_q) rows
    kb = k_ref[0]                               # (block_k, D)
    vb = v_ref[0]
    block_k = kb.shape[0]
    ki = pl.program_id(1)
    n_q = q_ref.shape[1] // block_q

    def tile(i, carry, visible=None):
        dk, dv = carry
        at = _rows_at(i, block_q)
        qb = q_ref[0, at, :]
        dob = do_ref[0, at, :]
        st = _dot_nt(kb, qb) * sm_scale
        if visible is not None:
            st = jnp.where(visible, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, :, at])    # exact probabilities
        dv = dv + jnp.dot(pt.astype(dob.dtype), dob,
                          preferred_element_type=jnp.float32)
        dst = pt * (_dot_nt(vb, dob) - delta_ref[0, :, at])
        dk = dk + jnp.dot(dst.astype(qb.dtype), qb,
                          preferred_element_type=jnp.float32)
        return dk, dv

    carry = (jnp.zeros((block_k, kb.shape[-1]), jnp.float32),
             jnp.zeros((block_k, vb.shape[-1]), jnp.float32))
    # causal: q tiles ending before this k tile's first row see only
    # masked scores and are skipped; the ones up to its last row lie on
    # the diagonal; the rest hold no masked score
    i_whole = 0
    if causal and not window:
        i0, i_whole = _diagonal_span(ki, block_k, block_q)
        behind = -_row_minus_col(block_k, block_q)
        # row i*block_q + r sees column ki*block_k + c
        carry = jax.lax.fori_loop(
            i0, i_whole,
            lambda i, carry: tile(
                i, carry, behind >= ki * block_k - i * block_q), carry)
    if window:
        # under a window the loop also ends early, past the last q row
        # that sees this k tile, and the q tiles on that edge are masked
        dk, dv = _window_loops(tile, carry, ki, block_k, block_q, n_q,
                               offset, window, False)
    else:
        dk, dv = jax.lax.fori_loop(i_whole, n_q, tile, carry)
    # d(scores) = ds * sm_scale: the scale once, on the accumulator
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, *, sm_scale: float,
                         causal: bool, block_k: int, window: int = 0,
                         offset: int = 0):
    qb = q_ref[0]                                # (block_q, D)
    dob = do_ref[0]
    lse = lse_ref[0]                             # (block_q, 1)
    dlt = delta_ref[0]
    block_q = qb.shape[0]
    qi = pl.program_id(1)

    def tile(i, dq, visible=None):
        at = _rows_at(i, block_k)
        kb = k_ref[0, at, :]
        s = _dot_nt(qb, kb) * sm_scale
        if visible is not None:
            s = jnp.where(visible, s, _NEG_INF)
        ds = jnp.exp(s - lse) * (_dot_nt(dob, v_ref[0, at, :]) - dlt)
        return dq + jnp.dot(ds.astype(kb.dtype), kb,
                            preferred_element_type=jnp.float32)

    dq = jnp.zeros((block_q, qb.shape[-1]), jnp.float32)
    if window:
        dq = _window_loops(tile, dq, qi, block_q, block_k,
                           k_ref.shape[1] // block_k, offset, window, True)
    elif causal:
        n_whole, n_k = _diagonal_span(qi, block_q, block_k)
        dq = jax.lax.fori_loop(0, n_whole, tile, dq)
        ahead = _row_minus_col(block_q, block_k)
        dq = jax.lax.fori_loop(
            n_whole, n_k,
            lambda i, dq: tile(
                i, dq, ahead >= i * block_k - qi * block_q), dq)
    else:
        dq = jax.lax.fori_loop(0, k_ref.shape[1] // block_k, tile, dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_specs(block, d, t):
    # `*_` absorbs the scalar-prefetch refs appended to index-map args
    # when these specs are used under a PrefetchScalarGridSpec.
    # Per-row stats of a block of rows (m/l/lse/delta) travel as
    # (bh, t, 1) column vectors: Mosaic requires the last two block
    # dims divisible by (8, 128) OR equal to the array dims — (block, 1)
    # satisfies that ((1, block) from a rank-2 (bh, t) layout does not,
    # and fails to lower).  All t rows' stats travel as one (1, t) row
    # of a (bh, 1, t) array: lane-dense, t x 4 bytes a head.
    qspec = pl.BlockSpec((1, block, d), lambda b, i, *_: (b, i, 0))
    kvspec = pl.BlockSpec((1, t, d), lambda b, i, *_: (b, 0, 0))
    vec = pl.BlockSpec((1, block, 1), lambda b, i, *_: (b, i, 0))
    row_full = pl.BlockSpec((1, 1, t), lambda b, i, *_: (b, 0, 0))
    return qspec, kvspec, vec, row_full


def _flash_kv_specs(block, d, t, g):
    """(one block of `block` rows, all t rows) of a k / v array that
    holds one head for every `g` heads of the grid's first axis: program
    b*H + h reads head b*(H/g) + h // g = (b*H + h) // g, so grouped
    queries read their shared keys and values in place.  g = 1 gives
    `_flash_specs`' own maps."""
    if g == 1:
        blk, full, _, _ = _flash_specs(block, d, t)
        return blk, full
    return (pl.BlockSpec((1, block, d), lambda b, i, *_: (b // g, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i, *_: (b // g, 0, 0)))


def _lanes(width: int) -> int:
    """What a block's last dimension takes in VMEM: whole tiles of 128
    lanes (64-wide heads cost what 128-wide ones do, 192-wide what
    256-wide ones do)."""
    return -(-width // 128) * 128


# What XLA's memory-space assignment leaves every op on the v5e, a
# Mosaic call included, whatever window the call itself asks for: its
# own VMEM buffers that live across the call lie from here up (PR 33).
# No flash call asks for more: a shape whose blocks and tiles do not
# fit is cut into chunks that do (`_flash_chunk`).
_SCOPED_VMEM = 16 << 20
_MOSAIC_ROOM = 1 << 19    # Mosaic's own scratch beside what is counted


def _flash_window(call_bytes: int) -> int:
    """VMEM a call takes: what `_fwd/_dq/_dkv_block_bytes` count, and
    room for Mosaic's own scratch."""
    return call_bytes + _MOSAIC_ROOM


def _fwd_block_bytes(d, dv, isz, block_q, block_k=BLOCK_K):
    """VMEM bytes of a forward call over t rows at these tiles: the
    blocks the pipeline double-buffers (q, all of k and v in operands of
    `isz` bytes; the float32 output and the lse column, which VMEM pads
    to 128 lanes) and the float32 tiles live in a step (s, p and the
    mask; p again as an operand; two accumulators)."""
    ld, ldv = _lanes(d), _lanes(dv)
    return lambda t: (
        2 * (t * (ld + ldv) * isz
             + block_q * (ld * isz + (ldv + 128) * 4))
        + block_q * (block_k * (12 + isz) + 2 * ldv * 4))


def _dq_block_bytes(d, dv, isz, block_q, block_k=BLOCK_K):
    """The dq call's: blocks of q and dO, all of k and v, the float32
    dq block and two statistic columns; live s, dp, ds and the mask, ds
    again as an operand, two accumulators."""
    ld, ldv = _lanes(d), _lanes(dv)
    return lambda t: (
        2 * (t * (ld + ldv) * isz
             + block_q * ((ld + ldv) * isz + (ld + 256) * 4))
        + block_q * (block_k * (16 + isz) + 2 * ld * 4))


def _dkv_block_bytes(d, dv, isz, block_q, block_k=BLOCK_K):
    """The dk/dv call's: blocks of k and v, all of q and dO, the two
    float32 output blocks, and the two statistics as (1, t) rows (16
    bytes a row's value as Mosaic tiles them); live sT, dpT and the
    mask, pT and dsT as operands, two pairs of accumulators."""
    ld, ldv = _lanes(d), _lanes(dv)
    return lambda t: (
        2 * (t * (ld + ldv) * isz + 2 * 16 * t
             + block_k * (ld + ldv) * (isz + 4))
        + block_k * (block_q * (12 + 2 * isz) + 2 * (ld + ldv) * 4))


# The three counts are upper estimates (Mosaic reuses the room of tiles
# that are dead), held against the compiler for a described v5e over a
# grid of tiles at both language models' shapes (PR 34): every pair of
# tiles it refused counts over the window here, and every pair counted
# inside the window compiled.
_BLOCK_BYTES = {"fwd": _fwd_block_bytes, "dq": _dq_block_bytes,
                "dkv": _dkv_block_bytes}
# Past 512 rows or columns a tile buys nothing on the v5e (my chip run,
# PR 34, call 1: each kernel alone over a grid of tiles at both language
# models' shapes): the loop's fixed costs are spread thin by then, and
# the half of every diagonal tile that lies above the diagonal grows
# with the tile.
_MAX_TILE = 512


def _flash_tiles(kernel: str, t: int, d: int, dv: int, isz: int,
                 floor=(BLOCK_Q, BLOCK_K)):
    """(block_q, block_k) of one flash kernel ("fwd", "dq", "dkv") over
    t rows of d / dv-wide heads in operands of `isz` bytes: the largest
    tiles, multiples of the floor that divide t, whose call fits the
    default VMEM window.  A wider tile of the streamed side (k for the
    forward and dq, q for dk/dv) amortises the accumulator's rescale,
    the statistics and the loop's fixed cost; a taller tile of the
    resident side feeds the MXU more rows a weight load.  None where
    even the floor does not fit (the caller cuts the rows first)."""
    need = _BLOCK_BYTES[kernel]

    def sizes(lo):
        return [n for n in range(lo, min(t, max(lo, _MAX_TILE)) + 1, lo)
                if t % n == 0]

    fit = [(bq, bk) for bq in sizes(floor[0]) for bk in sizes(floor[1])
           if _flash_window(need(d, dv, isz, bq, bk)(t)) <= _SCOPED_VMEM]
    # the most scores a tile, then the wider streamed side
    streamed = 0 if kernel == "dkv" else 1
    return max(fit, key=lambda s: (s[0] * s[1], s[streamed]),
               default=None)


def _flash_chunk(t: int, block: int, *block_bytes) -> int:
    """Rows of q and of k / v a single call takes.  A window above the
    default is not safe: XLA places the VMEM buffers it keeps across a
    Mosaic call as if the call took the default 16 MiB, so a call that
    uses more writes over them (the sorted token ids of the embedding's
    scatter-add among them: a step of lfm2 at one row of 8,192 tokens
    never ended, PR 33).  So the rows are halved until every one of
    `block_bytes` (functions of the rows: one for each kernel of the
    call, at its smallest tiles) fits the default window; the caller
    runs the pairs of chunks and adds them up.  t itself where it fits;
    a length no halving brings inside is an error, not a call that may
    never end."""
    def fits(n):
        return all(_flash_window(f(n)) <= _SCOPED_VMEM
                   for f in block_bytes)
    c = t
    while not fits(c):
        if c % 2 or (c // 2) % block:
            raise ValueError(
                f"flash_attention: {t} rows cannot be halved into "
                f"chunks of whole {block}-row blocks that fit "
                f"{_SCOPED_VMEM >> 20} MiB of VMEM (stopped at {c})")
        c //= 2
    return c


def _chunk_pairs(n: int, causal: bool, window: int = 0, chunk: int = 0):
    """(q chunk, k / v chunk, causal) of every pair that holds a score:
    under the causal mask the pairs below the diagonal are whole, the
    diagonal ones masked by their local positions (the chunks are
    equally long), the ones above it empty.  Under a window of `window`
    keys over chunks of `chunk` rows the pairs whose nearest row and
    column lie a window apart or more are empty too, and the others
    below the diagonal are masked by the window's edge, (i - j) chunk
    rows past their columns."""
    return [(i, j, causal and i == j) for i in range(n)
            for j in range(i + 1 if causal else n)
            if not window or (i - j - 1) * chunk + 1 < window]


def _rows(x, i, c):
    return jax.lax.slice_in_dim(x, i * c, (i + 1) * c, axis=1)


def _masked_tiles(kernel: str, t: int, block_q: int, block_k: int,
                  causal: bool, window: int = 0, offset: int = 0):
    """(score tiles that run the masked body, score tiles visited) of
    one call: what the kernels' loop bounds come to, summed over the
    grid's second axis."""
    n_q, n_k = t // block_q, t // block_k
    if window:
        if kernel == "dkv":
            spans = [_window_span(i, block_k, block_q, n_q, offset, window,
                                  False) for i in range(n_k)]
        else:
            spans = [_window_span(i, block_q, block_k, n_k, offset, window,
                                  True) for i in range(n_q)]
        return (sum(e1 - lo + hi - e2 for lo, e1, e2, hi in spans),
                sum(hi - lo for lo, _, _, hi in spans))
    if not causal:
        return 0, n_q * n_k
    if kernel == "dkv":     # a k tile's program: q tiles from lo on
        spans = [_diagonal_span(i, block_k, block_q) for i in range(n_k)]
        visited = sum(n_q - lo for lo, _ in spans)
    else:                   # a q tile's program: k tiles before hi
        spans = [_diagonal_span(i, block_q, block_k) for i in range(n_q)]
        visited = sum(hi for _, hi in spans)
    return sum(hi - lo for lo, hi in spans), visited


def _note_plan(kernel, shape, causal, chunk, tiles, window=0):
    """`route.plans()["flash"]` (the job's `info.flash`), by call shape
    and kernel: the tiles chosen, the calls an attention takes and the
    share of score tiles under the masked body; under a window also the
    window, the calls a causal attention over the same chunks takes and
    the share of its tiles that are visited."""
    bh, t, d, dv, dtype, g = shape
    pairs = _chunk_pairs(t // chunk, causal, window, chunk)
    counts = [_masked_tiles(kernel, chunk, *tiles, cz, window,
                            (i - j) * chunk) for i, j, cz in pairs]
    masked, visited = (sum(c[i] for c in counts) for i in (0, 1))
    key = (f"{bh}x{t}x{d}/{dv} {jnp.dtype(dtype).name} g{g}"
           f"{' causal' if causal else ''}"
           f"{f' window {window}' if window else ''}")
    plan = route.lowered("flash", key)[kernel] = {
        "block_q": tiles[0], "block_k": tiles[1], "calls": len(pairs),
        "masked_tile_share": round(masked / visited, 4)}
    if window:
        # beside what a causal call over the same chunks and tiles runs
        whole = _chunk_pairs(t // chunk, True)
        plan.update(
            window=window, causal_calls=len(whole),
            visited_tile_share=round(visited / sum(
                _masked_tiles(kernel, chunk, *tiles, cz)[1]
                for _, _, cz in whole), 4))


def _operands(mxu_dtype, *xs):
    """The arrays in the type the MXU is to see, cast once, here: HBM,
    the DMAs and VMEM then carry that type, and no kernel casts a block
    it is handed.  None = as they come."""
    if mxu_dtype is None:
        return xs
    return tuple(x.astype(mxu_dtype) for x in xs)


def _check_blocks(t, block_q, block_k):
    if t % block_q or t % block_k:
        # a truncated grid would leave the output/lse tail rows
        # uninitialized garbage — fail loudly (mirrors
        # flash_block_update; in-repo callers pre-check and fall back
        # to the XLA path, this guards direct calls)
        raise ValueError(
            f"flash_attention needs T divisible by the blocks: "
            f"t={t} % block_q={block_q}, t={t} % block_k={block_k}")


@_built_once
def _flash_fwd_built(q, k, v, *, causal, offset, sm_scale, tiles,
                     interpret, out_dtype, window):
    bh, t, d = q.shape
    dv = v.shape[-1]
    block_q, block_k = tiles
    g = bh // k.shape[0]            # query heads a key/value head
    qspec, _, vec, _ = _flash_specs(block_q, d, t)
    ospec, _, _, _ = _flash_specs(block_q, dv, t)
    _, kspec = _flash_kv_specs(block_q, d, t, g)
    _, vspec = _flash_kv_specs(block_q, dv, t, g)
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                          causal=causal, block_k=block_k, window=window,
                          offset=offset),
        out_shape=(jax.ShapeDtypeStruct((bh, t, dv), out_dtype),
                   jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)),
        grid=(bh, t // block_q),
        in_specs=[qspec, kspec, vspec],
        out_specs=(ospec, vec),
        interpret=interpret,
        name="cos_flash_fwd",
    )


def _flash_fwd_one(q, k, v, causal, offset=0, *, sm_scale, tiles,
                   interpret, out_dtype, window=0):
    """One forward call: (out, lse) of q over all of k, v."""
    out, lse = _flash_fwd_built(
        q, k, v, causal=causal, offset=offset, sm_scale=sm_scale,
        tiles=tiles, interpret=interpret, out_dtype=jnp.dtype(out_dtype),
        window=window)
    return out, lse[:, :, 0]


def _check_window(causal, window, t):
    """The window a call runs under: 0 where every row sees its whole
    causal past anyway."""
    if window and not causal:
        raise ValueError("flash_attention: a window needs causal=True "
                         "(row t sees the `window` keys up to its own)")
    return 0 if window >= t else window


def _flash_fwd_call(q, k, v, sm_scale, causal, block_q, block_k,
                    interpret, mxu_dtype=None, window=0):
    bh, t, d = q.shape
    dv = v.shape[-1]
    _check_blocks(t, block_q, block_k)
    window = _check_window(causal, window, t)
    out_dtype = q.dtype
    q, k, v = _operands(mxu_dtype, q, k, v)
    isz = q.dtype.itemsize
    floor = (block_q, block_k)
    c = _flash_chunk(t, max(floor), _fwd_block_bytes(d, dv, isz, *floor))
    tiles = _flash_tiles("fwd", c, d, dv, isz, floor)
    _note_plan("fwd", (bh, t, d, dv, q.dtype, bh // k.shape[0]), causal,
               c, tiles, window)
    one = functools.partial(_flash_fwd_one, sm_scale=sm_scale,
                            tiles=tiles, interpret=interpret,
                            out_dtype=out_dtype, window=window)
    if c == t:
        return one(q, k, v, causal)
    # each chunk of q against the chunks of k / v it sees, one call a
    # pair; a row's parts are weighted by their share of its softmax sum
    outs, lses = [], []
    for i in range(t // c):
        parts = [one(_rows(q, i, c), _rows(k, j, c), _rows(v, j, c), cz,
                     (i - j) * c)
                 for qi, j, cz in _chunk_pairs(t // c, causal, window, c)
                 if qi == i]
        lse = functools.reduce(jnp.logaddexp, [p[1] for p in parts])
        outs.append(sum(o * jnp.exp(l - lse)[:, :, None].astype(
            o.dtype) for o, l in parts))
        lses.append(lse)
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1)


def _flash_flatten(q, k, v):
    """(B, heads, T, ·) -> (B·heads, T, ·), each with its own heads."""
    if q.shape[1] % k.shape[1] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads over "
            f"{k.shape[1]} key / {v.shape[1]} value heads")
    return tuple(x.reshape((-1,) + x.shape[2:]) for x in (q, k, v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K,
                    interpret: bool = False,
                    mxu_dtype=None, window: int = 0) -> jax.Array:
    """Fused blockwise attention, q (B, H, T, D), k (B, H/g, T, D),
    v (B, H/g, T, Dv) → (B, H, T, Dv); Dv may differ from D (latent
    attention: 192-wide q/k, 128-wide v), and with g > 1 query head h
    reads key/value head h // g through the kernels' block index maps
    (no repeated copy of k or v; dK/dV come out a query head and are
    summed over each group).

    Same math as parallel.sp.attention (softmax(QKᵀ/√D)V, optional
    causal mask); O(block·T) VMEM instead of an O(T²) HBM score
    matrix, exact (not approximate) via online softmax.  Requires T
    divisible by `block_q` and `block_k` — callers fall back to the XLA
    path otherwise (ops.layers._mha); they are the smallest tiles a
    kernel may take, the tiles it does take follow from the shape
    (`_flash_tiles`).  `mxu_dtype` (None = the inputs' own type) is
    the operand type of the products: q, k, v and dO are cast to it
    once, before the kernels; the scores, the softmax statistics and
    the accumulators stay float32, the outputs the inputs' type.
    `window` > 0 (with `causal`): row t sees the `window` columns
    t - window < s <= t and no others; the kernels visit the score
    tiles that hold a visible score and mask the ones on either edge
    (`_window_span`), pair of chunks by pair of chunks; 0, or a window
    of t rows or more, is the causal mask alone."""
    return _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret,
                          mxu_dtype, window)[0]


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret,
                   mxu_dtype, window):
    b, h, t, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flash_flatten(q, k, v)
    out, lse = _flash_fwd_call(qf, kf, vf, sm_scale, causal, block_q,
                               block_k, interpret, mxu_dtype, window)
    # a recompute_block keeps these two (the named values themselves go
    # to the backward: a sibling would be kept AND the kernel run again);
    # q, k, v are computed again
    out, lse = keep(out, "flash.out"), keep(lse, "flash.lse")
    return out.reshape(b, h, t, v.shape[-1]), (qf, kf, vf, out, lse)


@_built_once
def _flash_dq_built(qf, kf, vf, dof, lse, delta, *, causal, offset, tiles,
                    interpret, out_dtype, window):
    bh, t, d = qf.shape
    dv_w = vf.shape[-1]
    block_q, block_k = tiles
    g = bh // kf.shape[0]           # query heads a key/value head
    qspec, _, vec, _ = _flash_specs(block_q, d, t)
    dospec, _, _, _ = _flash_specs(block_q, dv_w, t)
    _, kfull = _flash_kv_specs(block_q, d, t, g)
    _, vfull = _flash_kv_specs(block_q, dv_w, t, g)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel,
                          sm_scale=1.0 / math.sqrt(d), causal=causal,
                          block_k=block_k, window=window, offset=offset),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), out_dtype),
        grid=(bh, t // block_q),
        in_specs=[qspec, kfull, vfull, dospec, vec, vec],
        out_specs=qspec,
        interpret=interpret,
        name="cos_flash_bwd_dq",
    )


def _flash_dq_one(qf, kf, vf, dof, lse, delta, causal, offset=0, *, tiles,
                  interpret, out_dtype, window=0):
    """One pair's dq: a program a block of q, all of k and v past it;
    the statistics as (block_q, 1) columns beside the scores' rows."""
    return _flash_dq_built(
        qf, kf, vf, dof, lse[:, :, None], delta[:, :, None], causal=causal,
        offset=offset, tiles=tiles, interpret=interpret,
        out_dtype=jnp.dtype(out_dtype), window=window)


@_built_once
def _flash_dkv_built(qf, kf, vf, dof, lse, delta, *, causal, offset, tiles,
                     interpret, out_dtypes, window):
    bh, t, d = qf.shape
    dv_w = vf.shape[-1]
    block_q, block_k = tiles
    g = bh // kf.shape[0]
    dkspec, qfull, _, row_full = _flash_specs(block_k, d, t)
    dvspec, dofull, _, _ = _flash_specs(block_k, dv_w, t)
    kspec, _ = _flash_kv_specs(block_k, d, t, g)
    vspec, _ = _flash_kv_specs(block_k, dv_w, t, g)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel,
                          sm_scale=1.0 / math.sqrt(d), causal=causal,
                          block_q=block_q, window=window, offset=offset),
        out_shape=(jax.ShapeDtypeStruct((bh, t, d), out_dtypes[0]),
                   jax.ShapeDtypeStruct((bh, t, dv_w), out_dtypes[1])),
        grid=(bh, t // block_k),
        in_specs=[qfull, kspec, vspec, dofull, row_full, row_full],
        out_specs=(dkspec, dvspec),
        interpret=interpret,
        name="cos_flash_bwd_dkv",
    )


def _flash_dkv_one(qf, kf, vf, dof, lse, delta, causal, offset=0, *,
                   tiles, interpret, out_dtypes, window=0):
    """One pair's dk, dv: a program a block of k and v, all of q and dO
    past it; the statistics as (1, t) rows beside the transposed
    scores' columns."""
    bh, t, d = qf.shape
    dv_w = vf.shape[-1]
    g = bh // kf.shape[0]
    dk, dv = _flash_dkv_built(
        qf, kf, vf, dof, lse[:, None, :], delta[:, None, :], causal=causal,
        offset=offset, tiles=tiles, interpret=interpret,
        out_dtypes=tuple(jnp.dtype(x) for x in out_dtypes), window=window)
    if g > 1:
        # one dk, dv a query head: the group's sum is its key/value
        # head's gradient
        dk = dk.reshape(bh // g, g, t, d).sum(axis=1)
        dv = dv.reshape(bh // g, g, t, dv_w).sum(axis=1)
    return dk, dv


def flash_bwd_block(qf, kf, vf, dof, lse, delta, *, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    out_dtype=None, mxu_dtype=None, window: int = 0):
    """dq, dk, dv for one (q-group, kv-block) attention pair from the
    saved stats — the flash backward building block.  All operands
    flattened (B·H, T, D) / (B·H, T), v and dO (B·H, T, Dv); k and v
    may hold B·H/g heads (grouped queries), and dk, dv then come back
    summed over each group, in k's and v's shapes; `causal`
    masks with LOCAL
    positions, so callers composing cross-shard pairs (ring backward,
    parallel/sp.py) pass causal=True only for the diagonal pair and
    causal=False for fully-visible ones.  `out_dtype` overrides the
    gradient dtype — accumulating callers pass float32 so bf16 inputs
    don't round each per-hop partial before the sum.  `block_q` /
    `block_k` are the smallest tiles, `mxu_dtype` the operand type and
    `window` the window, as in `flash_attention`."""
    bh, t, d = qf.shape
    dv_w = vf.shape[-1]
    _check_blocks(t, block_q, block_k)
    window = _check_window(causal, window, t)
    out_dtypes = tuple(out_dtype or x.dtype for x in (qf, kf, vf))
    qf, kf, vf, dof = _operands(mxu_dtype, qf, kf, vf, dof)
    isz = qf.dtype.itemsize
    floor = (block_q, block_k)
    c = _flash_chunk(t, max(floor), _dq_block_bytes(d, dv_w, isz, *floor),
                     _dkv_block_bytes(d, dv_w, isz, *floor))
    shape = (bh, t, d, dv_w, qf.dtype, bh // kf.shape[0])
    tiles = {}
    for kern in ("dq", "dkv"):
        tiles[kern] = _flash_tiles(kern, c, d, dv_w, isz, floor)
        _note_plan(kern, shape, causal, c, tiles[kern], window)

    def one(*pair):
        dq = _flash_dq_one(*pair, tiles=tiles["dq"], interpret=interpret,
                           out_dtype=out_dtypes[0], window=window)
        return (dq,) + _flash_dkv_one(
            *pair, tiles=tiles["dkv"], interpret=interpret,
            out_dtypes=out_dtypes[1:], window=window)

    if c == t:
        return one(qf, kf, vf, dof, lse, delta, causal)
    # lse and delta are whole rows' statistics, so the pairs of chunks
    # add up: dq over a q chunk's pairs, dk and dv over a k / v chunk's
    n = t // c
    dqs, dks, dvs = [None] * n, [None] * n, [None] * n
    for i, j, cz in _chunk_pairs(n, causal, window, c):
        part = one(_rows(qf, i, c), _rows(kf, j, c), _rows(vf, j, c),
                   _rows(dof, i, c), _rows(lse, i, c), _rows(delta, i, c),
                   cz, (i - j) * c)
        for acc, at, x in zip((dqs, dks, dvs), (i, j, j), part):
            acc[at] = x if acc[at] is None else acc[at] + x
    return tuple(jnp.concatenate(a, axis=1) for a in (dqs, dks, dvs))


def _flash_vjp_bwd(causal, block_q, block_k, interpret, mxu_dtype, window,
                   res, do):
    qf, kf, vf, out, lse = res
    bh, t, d = qf.shape
    dof = do.reshape(bh, t, vf.shape[-1])
    # delta = rowsum(dO ∘ O): cheap elementwise+reduce, XLA fuses it
    delta = jnp.sum(dof.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq, dk, dv = flash_bwd_block(qf, kf, vf, dof, lse, delta,
                                 causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 mxu_dtype=mxu_dtype, window=window)
    lead = do.shape[:3]
    kv_lead = (lead[0], kf.shape[0] // lead[0], t)
    return (dq.reshape(lead + (d,)), dk.reshape(kv_lead + (d,)),
            dv.reshape(kv_lead + (vf.shape[-1],)))


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Flash block-update: the ring-attention inner step as a fused kernel
# ---------------------------------------------------------------------------
# parallel/sp.py's ring rotates K/V shards around the ICI ring and
# accumulates each incoming block with the same online-softmax algebra
# the flash kernels use (m/l/corr).  Inside shard_map the code is
# per-device, so a pallas_call is legal (no GSPMD partitioning of an
# opaque call) — this kernel fuses one accumulate() step: VMEM-resident
# score strip instead of a (T_local, T_local) HBM matrix per ring hop.
# The ring is differentiable end to end: parallel/sp.py's
# _make_ring_flash wraps this forward with a custom VJP whose backward
# is a second ring pass over flash_bwd_block.

def _flash_carry_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                        m_ref, l_ref, a_ref, mo_ref, lo_ref, ao_ref, *,
                        sm_scale: float, causal: bool, block_k: int):
    q = q_ref[0].astype(jnp.float32)             # (block_q, D)
    m = m_ref[0]
    l = l_ref[0]
    acc = a_ref[0].astype(jnp.float32)
    t_k = k_ref.shape[1]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    q_pos = (qoff_ref[0] + qi * block_q
             + jax.lax.broadcasted_iota(jnp.int32,
                                        (block_q, block_k), 0))

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        k_pos = (koff_ref[0] + i * block_k
                 + jax.lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
        # the offsets are the ring's, known only on the device: every
        # tile of a causal hop keeps the masked body
        return _online_softmax_step(
            q, kb, vb, m, l, acc, sm_scale=sm_scale,
            visible=q_pos >= k_pos if causal else None)

    m, l, acc = jax.lax.fori_loop(0, t_k // block_k, body, (m, l, acc))
    mo_ref[0] = m
    lo_ref[0] = l
    ao_ref[0] = acc.astype(ao_ref.dtype)


def flash_block_update(q: jax.Array, k_blk: jax.Array,
                       v_blk: jax.Array, m: jax.Array, l: jax.Array,
                       acc: jax.Array, q_off, k_off, *, causal: bool,
                       block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                       interpret: bool = False):
    """One ring-attention accumulate step, fused.

    q (BH, Tq, D) stays fixed; (k_blk, v_blk) (BH, Tk, D) is the block
    rotating past; (m, l, acc) is the online-softmax carry, updated and
    returned.  q_off/k_off are the blocks' global time offsets (traced
    int32 scalars — ring step index math), used for causal masking.
    Same algebra as parallel/sp.py accumulate()."""
    bh, t_q, d = q.shape
    t_k = k_blk.shape[1]
    if t_q % block_q or t_k % block_k:
        # a truncated grid would return partly-uninitialized carries
        raise ValueError(
            f"flash_block_update needs T divisible by the blocks: "
            f"t_q={t_q} % {block_q}, t_k={t_k} % {block_k}")
    sm_scale = 1.0 / math.sqrt(d)
    kern = functools.partial(_flash_carry_kernel, sm_scale=sm_scale,
                             causal=causal, block_k=block_k)
    qspec, kvspec, vec, _ = _flash_specs(block_q, d, t_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, t_q // block_q),
        in_specs=[qspec, kvspec, kvspec, vec, vec, qspec],
        out_specs=(vec, vec, qspec),
    )
    offs = (jnp.asarray([q_off], jnp.int32),
            jnp.asarray([k_off], jnp.int32))
    mo, lo, ao = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((bh, t_q, 1), jnp.float32),
                   jax.ShapeDtypeStruct((bh, t_q, 1), jnp.float32),
                   jax.ShapeDtypeStruct((bh, t_q, d), acc.dtype)),
        interpret=interpret,
    )(*offs, q, k_blk, v_blk, m[:, :, None], l[:, :, None], acc)
    return mo[:, :, 0], lo[:, :, 0], ao


# ---------------------------------------------------------------------------
# The gated delta rule (Gated DeltaNet's scan), fwd + bwd kernels
# ---------------------------------------------------------------------------
# `ops.layers.gated_delta_rule` as Mosaic calls: a program is one key
# head with its R value heads, the grid's second axis walks the chunks
# in order, and the (dk, dv) states of the R heads stay in a VMEM
# scratch from the first chunk to the last ((dk, R dv) float32: the
# heads side by side along the lanes).  The R heads of a chunk are
# PACKED along the rows, N = R c of them (128 at the family's chunk of
# 64 and two value heads a key head): their unit lower triangular
# systems are the diagonal blocks of one (N, N) system, so the blockwise
# inverse, the decays and every score matrix fill whole 128-lane tiles.
# A chunk, with gam the running sum of g inside it and S = S_r the state
# before it (the XLA form's algebra, `layers._delta_group`, in the
# three-product order):
#
#     A  = tril(beta k k^T e^(gam_i - gam_j), -1),  T = (I + A)^-1
#     vn = T (beta (v - e^gam (k S)))             what the tokens write
#     o  = e^gam (q S) + tril(q k^T e^(gam_i - gam_j)) vn
#     S' = e^(gam_c) S + (e^(gam_c - gam) k)^T vn
#
# Every product is float32 at HIGHEST, as outside (`_GDN_PRECISION`).
# The backward pass goes a group of chunks at a time, last group first,
# in one loop: the forward kernel runs again from the state the forward
# pass kept at the group's edge and writes the state before every chunk,
# T and vn (transient, a group's worth), and the backward kernel sweeps
# the group's chunks in reverse with dS in VMEM, writing its rows of
# the five gradients into arrays the loop carries.  No call asks for a
# VMEM window (PR 33's hang: `_SCOPED_VMEM`).  What sets a chunk's time
# on the v5e is the MXU's weight loads: a (128, 128) float32 product at
# HIGHEST is six loads of its right-hand tile, 768 cycles on one of the
# four MXUs, whether 64 or 128 rows stream past it (PR 37: the static
# schedule, 2,448 / 2,117 / 3,121 bundles a chunk for the forward, the
# recomputation and the sweep, matched the chip's time to 3%).

_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NN = (((1,), (0,)), ((), ()))

# Chunks a grid step (one block of rows) and between two kept states.
# On the chip at qwen3next's shape (PR 37, call 1: 16 / 32 heads, 8,192
# tokens, 128 / 128) 4 and 8 a step take the same forward (5.25 / 5.33
# ms), 4 the shorter backward; 16 between states keeps the backward
# pass's transient at 64 MB a layer and the step's memory at the XLA
# form's; the chunk loop unrolled gains 2-5% for four times the code.
GDN_STEP_CHUNKS = 4
GDN_GROUP_CHUNKS = 16


def _gdn_dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _stack_heads(x, r: int, width: int):
    """(c, r width), the heads side by side -> (r c, width), the heads
    one under the other."""
    return jnp.concatenate(
        [x[:, i * width:(i + 1) * width] for i in range(r)], axis=0)


def _stacked_rows(ref, at, r: int):
    """Rows `at` of the r heads of a (1, r, rows, width) block, one
    head under the other."""
    return jnp.concatenate([ref[0, i, at, :] for i in range(r)], axis=0)


def _beside_heads(x, r: int, c: int):
    """(r c, width), the heads one under the other -> (c, r width)."""
    return jnp.concatenate(
        [x[i * c:(i + 1) * c] for i in range(r)], axis=1)


class _GdnChunk:
    """What a chunk's forward and backward passes share: the masks of
    the packed (N, N) matrices, gam and beta as columns, the decays, the
    scores, the state's products with k and q."""

    def __init__(self, qc, kc, gam_row, beta_row, s_cat, r: int, c: int,
                 with_q: bool = True):
        n = r * c
        self.r, self.c, self.n = r, c, n
        self.dv = s_cat.shape[1] // r
        ri = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        ci = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        self.ri, self.ci = ri, ci
        lg = c.bit_length() - 1
        self.eye = ri == ci
        same = (ri >> lg) == (ci >> lg)         # one head's block
        self.low = same & (ri >= ci)
        self.strict = same & (ri > ci)
        self.gam = self.col(gam_row)
        self.beta = self.col(beta_row)
        # e^(gam_i - gam_j) at j <= i of one head, 0 elsewhere
        self.dm = jnp.exp(jnp.where(self.low, self.gam - gam_row,
                                    _NEG_INF))
        self.k_big = jnp.concatenate([kc] * r, axis=0)
        self.q_big = jnp.concatenate([qc] * r, axis=0)
        self.kq = jnp.concatenate([kc, qc], axis=0)
        # k k^T and q k^T: the heads share k and q, so c rows each go
        # through the MXU, against every head's columns at once
        both = _gdn_dot(self.kq if with_q else kc, self.k_big, _NT)
        self.kk = jnp.concatenate([both[:c]] * r, axis=0)
        if with_q:
            self.qk = jnp.concatenate([both[c:]] * r, axis=0)
        ss = _gdn_dot(self.kq if with_q else kc, s_cat)     # (2c, r dv)
        self.ks = _stack_heads(ss[:c], r, self.dv)
        if with_q:
            self.qs = _stack_heads(ss[c:], r, self.dv)
        self.eg = jnp.exp(self.gam)
        # gam at a head's last token, on every row of the head
        self.glast = _rowsum(jnp.where(
            same & ((ci & (c - 1)) == c - 1), gam_row, 0.0))
        self.ed = jnp.exp(self.glast - self.gam)
        self.kd = self.ed * self.k_big
        self.s_cat = s_cat

    def col(self, row):
        return _rowsum(jnp.where(self.eye, row, 0.0))

    def row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0,
                       keepdims=True)

    def head_rows(self, x, i):
        return x[i * self.c:(i + 1) * self.c]

    def head_state(self, s, i):
        return s[:, i * self.dv:(i + 1) * self.dv]

    def egl(self, i):
        """e^(gam_c) of head i, (1, 1)."""
        return jnp.exp(self.glast[i * self.c:i * self.c + 1])

    def scores(self):
        """P = tril(q k^T e^(gam_i - gam_j)) of every head."""
        return self.qk * self.dm

    def fold(self, x):
        """(N, N), nothing outside the heads' own blocks -> (c, N): the
        heads' blocks side by side (the sum of the heads' rows)."""
        return sum(self.head_rows(x, i) for i in range(self.r))

    def inverse(self):
        """(I + A)^-1 by blocks (`layers._unit_lower_inverse`): the
        inverse of [[X, 0], [C, Y]] from those of X and Y, 1 x 1 blocks
        up to a head's c x c; the blocks of different heads never
        meet."""
        a = jnp.where(self.strict, self.beta * self.kk * self.dm, 0.0)
        ri, ci = self.ri, self.ci

        def below(s):       # the block C of every 2s x 2s block
            k = s.bit_length()
            return (((ri >> k) == (ci >> k)) & ((ri & s) != 0)
                    & ((ci & s) == 0))

        if self.c >= 8:
            # the 8 x 8 blocks on the diagonal (a sublane tile each) by
            # forward substitution on the VPU, which has the room (the
            # MXU's weight loads set a chunk's time): column j of every
            # block, spread along the lanes, times row j of the block's
            # inverse
            n = self.n
            in8 = (ri >> 3) == (ci >> 3)
            a8 = jnp.where(in8, a, 0.0)
            x = jnp.where(self.eye, 1.0, 0.0)
            for j in range(7):
                col = _rowsum(jnp.where((ci & 7) == j, a8, 0.0))
                row = jnp.sum(
                    jnp.where((ri & 7) == j, x, 0.0).reshape(n // 8, 8, n),
                    axis=1, keepdims=True)
                x = x - (col.reshape(n // 8, 8, 1) * row).reshape(n, n)
            s = 8
        else:
            x = jnp.where(self.eye, 1.0, 0.0) - jnp.where(below(1), a, 0.0)
            s = 2
        while s < self.c:
            off = jnp.where(below(s), a, 0.0)
            if s % 8:
                x = x - _gdn_dot(_gdn_dot(x, off), x)
            else:
                # X C X has rows in the lower half of each 2s x 2s
                # block only: those rows alone go through the MXU
                # (whole sublane tiles from s = 8 on)
                blocks = range(0, self.n, 2 * s)
                half = jnp.concatenate(
                    [x[b + s:b + 2 * s] for b in blocks], axis=0)
                half = half - _gdn_dot(_gdn_dot(half, off), x)
                x = jnp.concatenate(
                    [p for i, b in enumerate(blocks)
                     for p in (x[b:b + s], half[i * s:(i + 1) * s])],
                    axis=0)
            s *= 2
        return x


def _gdn_fwd_kernel(group_ref, q_ref, k_ref, v_ref, gam_ref, beta_ref,
                    s0_ref, *refs, r: int, c: int, steps: int,
                    group_steps: int, save: bool):
    """`steps` chunks of one key head (`group_ref` is for the index
    maps: which group of the row the grid walks).  save=False: o and the
    state at each group's edge; save=True (the backward pass's
    recomputation of a group): the state before each chunk, T and vn."""
    del group_ref
    if save:
        s_all_ref, t_ref, vn_ref, s_ref = refs
    else:
        o_ref, edge_ref, s_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        s_ref[...] = s0_ref[0, 0]

    if not save:
        @pl.when(j % group_steps == 0)
        def _():
            edge_ref[0, 0] = s_ref[...]

    def chunk(jj, carry):
        at = pl.ds(pl.multiple_of(jj * c, c), c)
        s_cat = s_ref[...]
        ch = _GdnChunk(q_ref[0, at, :], k_ref[0, at, :], gam_ref[0, jj],
                       beta_ref[0, jj], s_cat, r, c, with_q=not save)
        v_big = _stacked_rows(v_ref, at, r)
        t = ch.inverse()
        vn = _gdn_dot(t, ch.beta * (v_big - ch.eg * ch.ks))
        if save:
            s_all_ref[0, jj] = s_cat
            t_ref[0, jj] = t
            vn_ref[0, jj] = vn
        else:
            o = ch.eg * ch.qs + _gdn_dot(ch.scores(), vn)
            for i in range(r):
                o_ref[0, i, at, :] = ch.head_rows(o, i)
        s_ref[...] = jnp.concatenate(
            [ch.egl(i) * ch.head_state(s_cat, i)
             + _gdn_dot(ch.head_rows(ch.kd, i), ch.head_rows(vn, i), _TN)
             for i in range(r)], axis=1)
        return carry

    jax.lax.fori_loop(0, steps, chunk, 0)


def _gdn_bwd_kernel(group_ref, q_ref, k_ref, v_ref, gam_ref, beta_ref,
                    do_ref, s_all_ref, t_ref, vn_ref, ds_in_ref, *refs,
                    r: int, c: int, steps: int):
    """The reverse sweep over `steps` chunks of a group (the grid walks
    the group's blocks last first), dS of the R heads in VMEM.  The
    gradients' arrays are whole rows': the later groups' calls wrote
    their blocks, this one is handed the arrays (five operands never
    read here, aliased to the outputs) and writes its own."""
    del group_ref
    (dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref, ds_out_ref,
     ds_ref) = refs[-7:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        ds_ref[...] = ds_in_ref[0, 0]

    def chunk(it, carry):
        jj = steps - 1 - it
        at = pl.ds(pl.multiple_of(jj * c, c), c)
        ds_cat = ds_ref[...]
        ch = _GdnChunk(q_ref[0, at, :], k_ref[0, at, :], gam_ref[0, jj],
                       beta_ref[0, jj], s_all_ref[0, jj], r, c)
        n = ch.n
        v_big, do = _stacked_rows(v_ref, at, r), _stacked_rows(do_ref, at, r)
        t, vn = t_ref[0, jj], vn_ref[0, jj]
        p = ch.scores()
        pre = v_big - ch.eg * ch.ks
        # what the later chunks' states hand back to this chunk's writes
        kdds = jnp.concatenate(
            [_gdn_dot(ch.head_rows(ch.kd, i), ch.head_state(ds_cat, i))
             for i in range(r)], axis=0)
        dvn = _gdn_dot(p, do, _TN) + kdds
        drhs = _gdn_dot(t, dvn, _TN)
        both = _gdn_dot(jnp.concatenate([drhs, do], axis=0), vn, _NT)
        da = jnp.where(ch.strict, -both[:n], 0.0)       # d A
        dp = both[n:]                                   # d P, in dm's mask
        dpre = ch.beta * drhs
        dks, dqs = -ch.eg * dpre, ch.eg * do
        kkd = ch.kk * ch.dm
        e = da * (ch.beta * kkd) + dp * p               # d(decay) x decay
        z = _rowsum(vn * kdds)
        dbeta = _rowsum(drhs * pre) + _rowsum(da * kkd)
        dgam = (_rowsum(e) - z
                + ch.eg * (_rowsum(do * ch.qs) - _rowsum(dpre * ch.ks)))
        dgam_row = ch.row(dgam) - jnp.sum(e, axis=0, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        for i in range(r):      # through gam at each head's last token
            sds = ch.head_state(ch.s_cat, i) * ch.head_state(ds_cat, i)
            tot = (jnp.sum(ch.head_rows(z, i), axis=0, keepdims=True)
                   + ch.egl(i) * jnp.sum(_rowsum(sds), axis=0,
                                         keepdims=True))
            dgam_row = dgam_row + jnp.where(lane == i * c + c - 1, tot,
                                            0.0)
        dgam_ref[0, jj] = dgam_row
        dbeta_ref[0, jj] = ch.row(dbeta)
        # through the state: k S and q S
        st = jnp.concatenate(
            [jnp.concatenate([ch.head_rows(dks, i), ch.head_rows(dqs, i)],
                             axis=0) for i in range(r)], axis=1)
        dkq = _gdn_dot(st, ch.s_cat, _NT)               # (2c, dk)
        ds_ref[...] = jnp.concatenate(
            [ch.egl(i) * ch.head_state(ds_cat, i) for i in range(r)],
            axis=1) + _gdn_dot(ch.kq, st, _TN)
        dk_kd = _gdn_dot(_beside_heads(ch.ed * vn, r, c), ds_cat, _NT)
        # through the scores
        dkk = da * ch.beta * ch.dm
        dqk = dp * ch.dm
        on_k = _gdn_dot(jnp.concatenate(
            [ch.fold(dkk + dkk.T), ch.fold(dqk)], axis=0), ch.k_big)
        dq_ref[0, at, :] = dkq[c:] + on_k[c:]
        dk_ref[0, at, :] = (dkq[:c] + dk_kd + on_k[:c]
                            + _gdn_dot(ch.fold(dqk.T), ch.q_big))
        for i in range(r):
            dv_ref[0, i, at, :] = ch.head_rows(dpre, i)
        return carry

    jax.lax.fori_loop(0, steps, chunk, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        ds_out_ref[0, 0] = ds_ref[...]


def _gdn_specs(r, c, steps, dk, dv, at):
    """Block specs of one grid step's rows: q / k, v-shaped, gam-shaped
    arrays; `at(j, group)` is the block of grid step j."""
    rows = steps * c
    return (pl.BlockSpec((1, rows, dk), lambda b, j, g: (b, at(j, g), 0)),
            pl.BlockSpec((1, r, rows, dv),
                         lambda b, j, g: (b, 0, at(j, g), 0)),
            pl.BlockSpec((1, steps, 1, r * c),
                         lambda b, j, g: (b, at(j, g), 0, 0)))


def _gdn_call(kernel, name, group, operands, *, grid, in_specs, out_specs,
              out_shape, state_shape, interpret, aliases=None):
    """One Mosaic call of the rule: `group` ((1,) int32, which group of
    the row) is prefetched for the index maps; the grid is (key heads,
    the group's steps), the second in order with the states in a VMEM
    scratch; no VMEM window is asked."""
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(state_shape, jnp.float32)]),
        out_shape=out_shape,
        input_output_aliases=aliases or {},
        interpret=interpret, name=name, **params,
    )(group, *operands)


def _gdn_fwd_call(group, q, k, v, gam, beta, s0, *, c, steps, count,
                  group_steps=None, interpret=False):
    """The forward kernel over the `count` grid steps of group `group`
    of the rows.  group_steps given (count = the whole row, group 0):
    -> o and the states at the groups' edges; else (the backward's
    recomputation) -> the state before every chunk, T and vn.  s0 (BH,
    groups, dk, R dv): the state before each group."""
    bh, _, dk = q.shape
    r, dv = v.shape[1], v.shape[-1]
    n = r * c
    qspec, vspec, gspec = _gdn_specs(r, c, steps, dk, dv,
                                     lambda j, g: g[0] * count + j)
    state = pl.BlockSpec((1, 1, dk, r * dv),
                         lambda b, j, g: (b, g[0], 0, 0))
    f32 = jnp.float32
    if group_steps is None:
        chunks = count * steps
        out_shape = (jax.ShapeDtypeStruct((bh, chunks, dk, r * dv), f32),
                     jax.ShapeDtypeStruct((bh, chunks, n, n), f32),
                     jax.ShapeDtypeStruct((bh, chunks, n, dv), f32))
        out_specs = tuple(
            pl.BlockSpec((1, steps) + a.shape[2:],
                         lambda b, j, g: (b, j, 0, 0)) for a in out_shape)
    else:
        out_shape = (
            jax.ShapeDtypeStruct(v.shape, f32),
            jax.ShapeDtypeStruct((bh, count // group_steps, dk, r * dv),
                                 f32))
        out_specs = (
            vspec,
            pl.BlockSpec((1, 1, dk, r * dv),
                         lambda b, j, g: (b, j // group_steps, 0, 0)))
    return _gdn_call(
        functools.partial(_gdn_fwd_kernel, r=r, c=c, steps=steps,
                          group_steps=group_steps or 1,
                          save=group_steps is None),
        "cos_gdn_save" if group_steps is None else "cos_gdn_fwd",
        group, (q, k, v, gam, beta, s0), grid=(bh, count),
        in_specs=[qspec, qspec, vspec, gspec, gspec, state],
        out_specs=out_specs, out_shape=out_shape,
        state_shape=(dk, r * dv), interpret=interpret)


def _gdn_bwd_call(group, q, k, v, gam, beta, do, s_all, t_all, vn_all, ds,
                  grads, *, c, steps, count, interpret=False):
    """The backward kernel over the `count` grid steps of group `group`
    of the rows, last first -> (dq, dk, dv, dgam, dbeta) with those rows
    written, and dS before them; s_all, t_all, vn_all are the group's
    own, ds (BH, 1, dk, R dv) is dS after the group, `grads` the five
    arrays as the later groups' calls left them."""
    bh, _, dk = q.shape
    r, dv = v.shape[1], v.shape[-1]
    qspec, vspec, gspec = _gdn_specs(
        r, c, steps, dk, dv, lambda j, g: g[0] * count + count - 1 - j)
    saved = [pl.BlockSpec((1, steps) + a.shape[2:],
                          lambda b, j, g: (b, count - 1 - j, 0, 0))
             for a in (s_all, t_all, vn_all)]
    state = pl.BlockSpec((1, 1, dk, r * dv), lambda b, j, g: (b, 0, 0, 0))
    operands = (q, k, v, gam, beta, do, s_all, t_all, vn_all, ds, *grads)
    return _gdn_call(
        functools.partial(_gdn_bwd_kernel, r=r, c=c, steps=steps),
        "cos_gdn_bwd", group, operands, grid=(bh, count),
        in_specs=[qspec, qspec, vspec, gspec, gspec, vspec] + saved
        + [state] + [pl.BlockSpec(memory_space=pl.ANY)] * len(grads),
        out_specs=(qspec, qspec, vspec, gspec, gspec, state),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, jnp.float32)
                        for a in (*grads, ds)),
        # operand 0 is the group's index
        aliases={len(operands) - len(grads) + 1 + i: i
                 for i in range(len(grads))},
        state_shape=(dk, r * dv), interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gdn_rule(q, k, v, gam, beta, c, steps, group_steps, interpret):
    return _gdn_rule_fwd(q, k, v, gam, beta, c, steps, group_steps,
                         interpret)[0]


def _gdn_rule_fwd(q, k, v, gam, beta, c, steps, group_steps, interpret):
    bh, full, dk = q.shape
    s0 = jnp.zeros((bh, 1, dk, v.shape[1] * v.shape[-1]), jnp.float32)
    o, edges = _gdn_fwd_call(
        jnp.zeros((1,), jnp.int32), q, k, v, gam, beta, s0, c=c,
        steps=steps, count=full // (steps * c), group_steps=group_steps,
        interpret=interpret)
    # what a recompute_block keeps of the rule: the gated norm's
    # backward reads o, the groups' recomputation starts from the edges
    o, edges = keep(o, "gdn.o"), keep(edges, "gdn.edges")
    return o, (q, k, v, gam, beta, edges)


def _gdn_rule_bwd(c, steps, group_steps, interpret, res, do):
    """A group at a time, last first, ONE loop whatever the row's
    length: a call site costs its tracing and lowering at every job's
    start, cached program or not (PR 37: 48 sites a step for 8 groups
    unrolled made `setup_s` 17 s longer)."""
    q, k, v, gam, beta, edges = res
    groups = edges.shape[1]
    at = {"c": c, "steps": steps, "count": group_steps,
          "interpret": interpret}

    def group(i, carry):
        ds, grads = carry
        g = jnp.full((1,), groups - 1 - i, jnp.int32)
        saved = _gdn_fwd_call(g, q, k, v, gam, beta, edges, **at)
        *grads, ds = _gdn_bwd_call(g, q, k, v, gam, beta, do, *saved, ds,
                                   grads, **at)
        return ds, tuple(grads)

    return jax.lax.fori_loop(
        0, groups, group,
        (jnp.zeros_like(edges[:, :1]),
         tuple(jnp.zeros_like(a) for a in (q, k, v, gam, beta))))[1]


_gdn_rule.defvjp(_gdn_rule_fwd, _gdn_rule_bwd)


def gdn_rule_tiles(r: int, c: int, dk: int, dv: int) -> bool:
    """Whether the kernels take a rule of R value heads a key head at
    chunk c: the packed rows fill whole 128-lane tiles, the state's
    sides too."""
    return (c & (c - 1) == 0 and (r * c) % 128 == 0 and r * c <= 512
            and dk % 128 == 0 and dv % 128 == 0)


def gdn_rule_steps(chunks: int):
    """(chunks a grid step, grid steps a group, chunks the row is padded
    to): whole grid steps, and whole groups once there is more than
    one."""
    steps = min(GDN_STEP_CHUNKS, chunks)
    count = -(-chunks // steps)
    group_steps = min(max(GDN_GROUP_CHUNKS // steps, 1), count)
    return steps, group_steps, -(-count // group_steps) * group_steps * steps


def gated_delta_rule_kernels(q, k, v, g, beta, chunk: int,
                             interpret: bool = False):
    """`ops.layers.gated_delta_rule` through the kernels above: q, k (B,
    Hk, T, dk), v (B, Hk, R, T, dv), g, beta (B, Hk, R, T) -> o (B, Hk,
    R, T, dv), differentiable in all five.  The running sum of g inside
    each chunk is taken here, outside the kernels (one pass over (B, Hv,
    T)); T is padded to whole grid steps and groups (`gdn_rule_steps`)
    with tokens that neither write (beta 0) nor decay (g 0)."""
    b, hk, t, dk = q.shape
    r, dv = v.shape[2], v.shape[-1]
    c = int(chunk)
    steps, group_steps, n = gdn_rule_steps(-(-t // c))
    full = n * c

    def rows(a, axis):      # time on `axis`, padded to n chunks
        if full == t:
            return a
        w = [(0, 0)] * a.ndim
        w[axis] = (0, full - t)
        return jnp.pad(a, w)

    def packed(a):      # (B, Hk, R, T) -> (B Hk, chunks, 1, R c)
        a = a.reshape(b * hk, r, n, c)
        return jnp.swapaxes(a, 1, 2).reshape(b * hk, n, 1, r * c)

    g, beta = rows(g, 3), rows(beta, 3)
    gam = jnp.cumsum(g.reshape(b, hk, r, n, c), axis=-1)
    o = _gdn_rule(rows(q, 2).reshape(b * hk, full, dk),
                  rows(k, 2).reshape(b * hk, full, dk),
                  rows(v, 3).reshape(b * hk, r, full, dv),
                  packed(gam), packed(beta), c, steps, group_steps,
                  interpret)
    return o.reshape(b, hk, r, full, dv)[..., :t, :]


# ---------------------------------------------------------------------------
# The selective state-space scan (Mamba), fwd + bwd kernels
# ---------------------------------------------------------------------------
# s_t = exp(dt_t A) s_(t-1) + dt_t u_t B_t,  y_t = sum_n s_t C_t: all
# elementwise, nothing for the MXU.  Channels lie on lanes and the N
# states of a channel on sublanes, so the state of `SSM_CHANNELS`
# channels is N / 8 rows of vregs that stay in registers through a
# chunk's steps and in a VMEM scratch from a row's first chunk to its
# last.  The grid is (batch row, chunk, channel block), the channel
# block innermost: B_t and C_t, which every channel reads, arrive
# already laid over 128 lanes ((T, N, 128), made once outside) and are
# fetched once a chunk, their block index unchanged while the channel
# blocks go by.  The forward pass writes y and the state before every
# chunk; the backward pass goes over the chunks last first, computes a
# chunk's states again from its edge into a scratch and sweeps them in
# reverse, dB and dC summed over the channel blocks in their resident
# output block (their 128 lanes are summed outside).

SSM_CHANNELS = 512        # lanes a program holds the state of
SSM_UNROLL = 8            # steps of a chunk unrolled together


def ssm_scan_plan(t: int, channels: int, states: int, chunk: int):
    """{chunk, channels, vmem_bytes} the kernels take a scan of `t`
    steps at, or None where they do not take it: the channels fill
    whole 128-lane tiles, the states whole sublanes, the chunk whole
    unrolled groups."""
    if channels % 128 or states % 8 or chunk % SSM_UNROLL:
        return None
    block = next(c for c in (SSM_CHANNELS, 256, 128) if channels % c == 0)
    rows = 2 * chunk * block * 4            # a (chunk, block) f32, twice
    wide = 2 * chunk * states * 128 * 4     # a (chunk, N, 128) f32, twice
    state = states * channels * 4
    # the backward call, the larger: u, dt, dy in and du, ddt out; B, C
    # in and dB, dC out; A and the edge in; the chunk's states; g and dA
    vmem = (5 * rows + 4 * wide + 4 * states * block * 4
            + (chunk + 1) * states * block * 4 + 3 * state)
    if _flash_window(vmem) > _SCOPED_VMEM:
        return None
    return {"chunk": chunk, "channels": block, "vmem_bytes": vmem}


def _over_lanes(x, width: int):
    """(N, 128) -> (N, width): the same 128 lanes side by side."""
    return x if width == 128 else jnp.concatenate([x] * (width // 128),
                                                  axis=1)


def _unrolled(chunk: int, step, carry):
    """`step(i, carry)` for i in 0..chunk-1, `SSM_UNROLL` steps a loop
    iteration (Mosaic unrolls a loop whole or not at all)."""
    def group(k, carry):
        for j in range(SSM_UNROLL):
            carry = step(k * SSM_UNROLL + j, carry)
        return carry
    return jax.lax.fori_loop(0, chunk // SSM_UNROLL, group, carry)


def _fold_lanes(x):
    """(N, width) -> (N, 128): the 128-lane groups summed."""
    return sum(x[:, i:i + 128] for i in range(0, x.shape[1], 128))


def _ssm_fwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, edge_ref,
                    s_ref, *, chunk: int):
    j, cb = pl.program_id(1), pl.program_id(2)
    width = u_ref.shape[-1]

    @pl.when(j == 0)
    def _():
        s_ref[cb] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    a = a_ref[0]
    s = s_ref[cb]
    edge_ref[0, 0, 0] = s

    def step(i, s):
        dt = dt_ref[0, pl.ds(i, 1), :]                      # (1, width)
        w = dt * u_ref[0, pl.ds(i, 1), :]
        s = jnp.exp(dt * a) * s + w * _over_lanes(b_ref[0, i], width)
        y_ref[0, pl.ds(i, 1), :] = jnp.sum(
            s * _over_lanes(c_ref[0, i], width), axis=0, keepdims=True)
        return s

    s_ref[cb] = _unrolled(chunk, step, s)


def _ssm_bwd_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, edge_ref, dy_ref,
                    du_ref, ddt_ref, db_ref, dc_ref, da_ref,
                    g_ref, st_ref, *, chunk: int):
    j, cb = pl.program_id(1), pl.program_id(2)
    width = u_ref.shape[-1]

    @pl.when(j == 0)            # the row's last chunk
    def _():
        g_ref[cb] = jnp.zeros(g_ref.shape[1:], jnp.float32)
        da_ref[0, cb] = jnp.zeros(da_ref.shape[2:], jnp.float32)

    @pl.when(cb == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    a = a_ref[0]

    def again(i, s):            # st[i] = the state before step i
        st_ref[i] = s
        dt = dt_ref[0, pl.ds(i, 1), :]
        w = dt * u_ref[0, pl.ds(i, 1), :]
        return jnp.exp(dt * a) * s + w * _over_lanes(b_ref[0, i], width)

    st_ref[chunk] = _unrolled(chunk, again, edge_ref[0, 0, 0])

    def step(k, carry):
        g, da = carry           # dL/ds_i from the steps after i; dA
        i = chunk - 1 - k
        dt = dt_ref[0, pl.ds(i, 1), :]
        u = u_ref[0, pl.ds(i, 1), :]
        dy = dy_ref[0, pl.ds(i, 1), :]
        b = _over_lanes(b_ref[0, i], width)
        g = g + dy * _over_lanes(c_ref[0, i], width)
        dc_ref[0, i] += _fold_lanes(dy * st_ref[i + 1])
        db_ref[0, i] += _fold_lanes(g * (dt * u))
        decay = jnp.exp(dt * a)
        gs = g * st_ref[i] * decay          # dL/d(dt A), elementwise
        gb = jnp.sum(g * b, axis=0, keepdims=True)
        du_ref[0, pl.ds(i, 1), :] = dt * gb
        ddt_ref[0, pl.ds(i, 1), :] = u * gb + jnp.sum(
            gs * a, axis=0, keepdims=True)
        return g * decay, da + gs * dt

    g, da = _unrolled(chunk, step, (g_ref[cb], da_ref[0, cb]))
    g_ref[cb] = g
    da_ref[0, cb] = da


def _mosaic_call(kernel, name, *, grid, in_specs, out_specs, out_shape,
                 scratch, interpret,
                 semantics=("parallel", "arbitrary", "arbitrary")):
    """One Mosaic call over a grid of three axes with float32 VMEM
    scratch (the scan's: batch rows, chunks, channel blocks, the last
    two in order with the states in the scratch); no VMEM window is
    asked.  The call, for a `_built_once` builder to hand back."""
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=semantics)}
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        interpret=interpret, name=name, **params)


def _ssm_specs(chunk, block, n, blocks, at):
    """Block specs of one grid step: a (B, T, C) array, a (B, T, N,
    128) one, A as (blocks, N, block), the edges (B, chunks, blocks, N,
    block), dA (B, blocks, N, block); `at(j)` is the chunk of step j."""
    return (pl.BlockSpec((1, chunk, block), lambda b, j, c: (b, at(j), c)),
            pl.BlockSpec((1, chunk, n, 128),
                         lambda b, j, c: (b, at(j), 0, 0)),
            pl.BlockSpec((1, n, block), lambda b, j, c: (c, 0, 0)),
            pl.BlockSpec((1, 1, 1, n, block),
                         lambda b, j, c: (b, at(j), c, 0, 0)),
            pl.BlockSpec((1, blocks, n, block),
                         lambda b, j, c: (b, 0, 0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssm_scan(u, dt, bx, cx, a, chunk, block, interpret):
    return _ssm_scan_fwd(u, dt, bx, cx, a, chunk, block, interpret)[0]


@_built_once
def _ssm_fwd_call(u, dt, bx, cx, a, *, chunk, block, interpret):
    bsz, t, ch = u.shape
    blocks, n = a.shape[0], a.shape[1]
    chunks = t // chunk
    rows, wide, aspec, edge, _ = _ssm_specs(chunk, block, n, blocks,
                                            lambda j: j)
    f32 = jnp.float32
    return _mosaic_call(
        functools.partial(_ssm_fwd_kernel, chunk=chunk), "cos_ssm_fwd",
        grid=(bsz, chunks, blocks),
        in_specs=[rows, rows, wide, wide, aspec], out_specs=(rows, edge),
        out_shape=(jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct((bsz, chunks, blocks, n, block),
                                        f32)),
        scratch=[(blocks, n, block)], interpret=interpret)


def _ssm_scan_fwd(u, dt, bx, cx, a, chunk, block, interpret):
    y, edges = _ssm_fwd_call(u, dt, bx, cx, a, chunk=chunk, block=block,
                             interpret=interpret)
    # what a recompute_block keeps of the scan: the gate's backward
    # reads y, the chunks' recomputation starts from the edges
    y, edges = keep(y, "ssm.y"), keep(edges, "ssm.edges")
    return y, (u, dt, bx, cx, a, edges)


@_built_once
def _ssm_bwd_call(u, dt, bx, cx, a, edges, dy, *, chunk, block, interpret):
    bsz, t, ch = u.shape
    blocks, n = a.shape[0], a.shape[1]
    chunks = t // chunk
    rows, wide, aspec, edge, da = _ssm_specs(
        chunk, block, n, blocks, lambda j: chunks - 1 - j)
    f32 = jnp.float32
    return _mosaic_call(
        functools.partial(_ssm_bwd_kernel, chunk=chunk), "cos_ssm_bwd",
        grid=(bsz, chunks, blocks),
        in_specs=[rows, rows, wide, wide, aspec, edge, rows],
        out_specs=(rows, rows, wide, wide, da),
        out_shape=(jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct(u.shape, f32),
                   jax.ShapeDtypeStruct(bx.shape, f32),
                   jax.ShapeDtypeStruct(cx.shape, f32),
                   jax.ShapeDtypeStruct((bsz,) + a.shape, f32)),
        scratch=[(blocks, n, block), (chunk + 1, n, block)],
        interpret=interpret)


def _ssm_scan_bwd(chunk, block, interpret, res, dy):
    du, ddt, dbx, dcx, dax = _ssm_bwd_call(
        *res, dy, chunk=chunk, block=block, interpret=interpret)
    return du, ddt, dbx, dcx, jnp.sum(dax, axis=0)


_ssm_scan.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)


def selective_scan_kernels(u, dt, a, b, c, plan: dict,
                           interpret: bool = False):
    """`ops.layers.selective_scan` through the kernels above: u, dt (B,
    T, C), a (C, N), b, c (B, T, N) -> y (B, T, C), differentiable in
    all five.  T is padded to whole chunks with steps that neither
    decay nor write (dt 0); B and C are laid over 128 lanes and A is
    turned channel block by channel block here, outside the kernels."""
    bsz, t, ch = u.shape
    n = a.shape[1]
    chunk, block = plan["chunk"], plan["channels"]
    full = -(-t // chunk) * chunk

    def rows(x):
        return x if full == t else jnp.pad(
            x, ((0, 0), (0, full - t), (0, 0)))

    def wide(x):        # (B, T, N) -> (B, T, N, 128)
        return jnp.broadcast_to(rows(x)[..., None], (bsz, full, n, 128))

    a_blocks = jnp.swapaxes(a.T.reshape(n, ch // block, block), 0, 1)
    y = _ssm_scan(rows(u), rows(dt), wide(b), wide(c), a_blocks, chunk,
                  block, interpret)
    return y[:, :t]


# ---------------------------------------------------------------------------
# The short causal convolution + SiLU of the Gated DeltaNet and Mamba layers
# ---------------------------------------------------------------------------
# silu(sum_j taps[:, j] * a[t - (L - 1) + j] + bias) over time-major (T,
# B, W) whose first C channels are a: the free view (T, B W) puts time on
# the sublanes and the channels on the lanes, so a step back in time is
# one row up and the taps and the bias repeat every W lanes.  The grid is
# (channel tile, batch column, time tile); a program reads its (time
# tile, channel tile) block of the wide array where it lies (no slice is
# copied out first) and, through a second block spec on the same
# operand, the 8 rows above it (zero at t = 0), and writes the block of
# the output: every element is read from HBM once, plus the halo, and
# written once.  The shifted reads are static slices, 0 to L - 1 rows
# off the tile's rows; only a tile's first rows reach into the halo, and
# only they go through a small scratch that holds the halo above them.
# The backward call computes the pre-activation again from a, then
# du = dy silu'(pre) on the tile and on the 8 rows after it (zero past
# the last row), da[t] = sum_j taps[:, j] du[t + (L - 1) - j], and the
# sums over time of du a[t - (L - 1) + j] and of du, eight partial sums a
# channel in an output block that the batch and time axes revisit.

TAPS_TIME = 512           # rows of a time tile, at most
TAPS_CHANNELS = 512       # lanes of a channel tile, at most
_TAPS_HALO = 8            # rows of the blocks above and below a tile
_TAPS_ROWS = 64           # rows computed together


def taps_plan(t: int, channels: int, width: int, n_taps: int,
              first: int = 0):
    """{time_tile, channel_tile} the kernels take a convolution of `t`
    steps over `channels` of `width` channels (from channel `first` on)
    at, or None where they do not take it: the taps reach no further
    back than the halo, T is whole sublane groups and either one tile
    or whole tiles of at least 128 rows (a grid of shorter ones costs
    more than the XLA form), and the channels, where they start and the
    width are whole 128-lane tiles."""
    tile = t if t <= TAPS_TIME else math.gcd(t, TAPS_TIME)
    if not 1 <= n_taps <= _TAPS_HALO + 1 or t % _TAPS_HALO \
            or tile < min(t, 128) or channels % 128 or width % 128 \
            or first % 128 or first + channels > width:
        return None
    return {"time_tile": tile,
            "channel_tile": math.gcd(
                math.gcd(math.gcd(channels, width), first),
                TAPS_CHANNELS)}


def _taps_chunks(tile: int):
    """(first row, rows) of the row groups a time tile is computed in."""
    return [(r, min(_TAPS_ROWS, tile - r))
            for r in range(0, tile, _TAPS_ROWS)]


def _taps_edge(a_ref, up_ref, edge_ref):
    """The halo above the tile (zero at t = 0) and the tile's first row
    group under it, in `edge_ref`."""
    first = edge_ref.shape[0] - _TAPS_HALO
    edge_ref[0:_TAPS_HALO] = jnp.where(pl.program_id(2) == 0, 0.0,
                                       up_ref[...])
    edge_ref[_TAPS_HALO:] = a_ref[0:first]


def _taps_back(a_ref, edge_ref, r0: int, rows: int, k: int):
    """The input k steps before rows [r0, r0 + rows) of the tile."""
    if r0 == 0:
        return edge_ref[_TAPS_HALO - k:_TAPS_HALO - k + rows]
    return a_ref[r0 - k:r0 - k + rows]


def _taps_pre(xs, taps_ref, bias_ref):
    """bias + sum_j taps[j] xs[j]: xs[j] the input L - 1 - j steps back."""
    pre = bias_ref[...] + taps_ref[0:1] * xs[0]
    for j in range(1, len(xs)):
        pre = pre + taps_ref[j:j + 1] * xs[j]
    return pre


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _taps_fwd_kernel(a_ref, up_ref, taps_ref, bias_ref, y_ref, edge_ref):
    n = taps_ref.shape[0]
    _taps_edge(a_ref, up_ref, edge_ref)
    for r0, rows in _taps_chunks(a_ref.shape[0]):
        pre = _taps_pre([_taps_back(a_ref, edge_ref, r0, rows, n - 1 - j)
                         for j in range(n)], taps_ref, bias_ref)
        y_ref[r0:r0 + rows] = pre * _sigmoid(pre)


def _sum8(x):
    """(rows, lanes) -> (8, lanes): the sublane groups summed."""
    return sum(x[g:g + 8] for g in range(0, x.shape[0], 8))


def _taps_bwd_kernel(a_ref, up_ref, down_ref, dy_ref, dy_down_ref,
                     taps_ref, bias_ref, da_ref, sums_ref, edge_ref,
                     tail_ref, du_ref):
    n, tile = taps_ref.shape[0], a_ref.shape[0]
    t = pl.program_id(2)
    halo = _TAPS_HALO

    @pl.when((pl.program_id(1) == 0) & (t == 0))
    def _():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)

    def du_of(pre, dy):         # dy silu'(pre)
        s = _sigmoid(pre)
        return dy * (s * (1.0 + pre * (1.0 - s)))

    _taps_edge(a_ref, up_ref, edge_ref)
    for r0, rows in _taps_chunks(tile):
        xs = [_taps_back(a_ref, edge_ref, r0, rows, n - 1 - j)
              for j in range(n)]
        du = du_of(_taps_pre(xs, taps_ref, bias_ref), dy_ref[r0:r0 + rows])
        du_ref[r0:r0 + rows] = du
        for j in range(n):
            sums_ref[j] += _sum8(du * xs[j])
        sums_ref[n] += _sum8(du)
    # du of the 8 rows after the tile, which its last rows' da reads:
    # zero past the sequence's end
    tail_ref[0:halo] = a_ref[tile - halo:tile]
    tail_ref[halo:] = down_ref[...]
    pre = _taps_pre([tail_ref[halo - (n - 1 - j):2 * halo - (n - 1 - j)]
                     for j in range(n)], taps_ref, bias_ref)
    du_ref[tile:] = jnp.where(t == pl.num_programs(2) - 1, 0.0,
                              du_of(pre, dy_down_ref[...]))
    for r0, rows in _taps_chunks(tile):
        da = taps_ref[0:1] * du_ref[r0 + n - 1:r0 + n - 1 + rows]
        for j in range(1, n):
            da = da + taps_ref[j:j + 1] * du_ref[r0 + n - 1 - j:
                                                 r0 + n - 1 - j + rows]
        da_ref[r0:r0 + rows] = da


def _taps_grid(z, taps, cols, tile, block, first=0):
    """The grid (channel tiles, batch columns, time tiles) of a call on
    z (T, cols W) and taps (L, C), and its block specs: for an array of
    W-wide (the C channels from `first` on) and of C-wide columns each
    (a (tile, block) of it, the 8 rows above, the 8 rows below), then
    the taps (L, C) and the bias (1, C)."""
    n, channels = taps.shape
    steps, groups = z.shape[0] // tile, tile // _TAPS_HALO

    def rows(width, off=0):
        per = width // block
        # (a call on the first channels keeps the index maps it had)
        lane = ((lambda c, b: b * per + c + off) if off
                else (lambda c, b: b * per + c))
        return (pl.BlockSpec((tile, block),
                             lambda c, b, t: (t, lane(c, b))),
                pl.BlockSpec((_TAPS_HALO, block), lambda c, b, t: (
                    jnp.maximum(t * groups - 1, 0), lane(c, b))),
                pl.BlockSpec((_TAPS_HALO, block), lambda c, b, t: (
                    jnp.minimum((t + 1) * groups, steps * groups - 1),
                    lane(c, b))))

    return ((channels // block, cols, steps),
            rows(z.shape[1] // cols, first // block), rows(channels),
            (pl.BlockSpec((n, block), lambda c, b, t: (0, c)),
             pl.BlockSpec((1, block), lambda c, b, t: (0, c))))


# (The two calls are plain functions, not a `jax.jit` each: under a jit
# of their own the -train job started 6 s later warm and 16 s later with
# an empty compile cache, PERF.md, section 7, PR 44.  Since PR 50 the
# unrolled bodies are traced once a shape all the same: `_built_once`.)
@_built_once
def _taps_fwd_built(z, above, taps, bias, *, cols, tile, block, interpret,
                    first):
    grid, (wide, up, _), (narrow, _, _), consts = _taps_grid(
        z, taps, cols, tile, block, first)
    return _mosaic_call(
        _taps_fwd_kernel, "cos_taps_fwd", grid=grid,
        in_specs=[wide, up, *consts], out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct(
            (z.shape[0], cols * taps.shape[1]), jnp.float32),
        scratch=[(_TAPS_HALO + min(_TAPS_ROWS, tile), block)],
        semantics=("parallel",) * 3, interpret=interpret)


def _taps_fwd_call(z, taps, bias, cols, tile, block, interpret, first=0):
    """z (T, cols W), taps (L, C), bias (1, C) -> y (T, cols C)."""
    return _taps_fwd_built(z, z, taps, bias, cols=cols, tile=tile,
                           block=block, interpret=interpret, first=first)


@_built_once
def _taps_bwd_built(z, above, below, dy, dy_below, taps, bias, *, cols,
                    tile, block, interpret, first):
    n, channels = taps.shape
    f32 = jnp.float32
    grid, (wide, up, down), (narrow, _, narrow_down), consts = _taps_grid(
        z, taps, cols, tile, block, first)
    return _mosaic_call(
        _taps_bwd_kernel, "cos_taps_bwd", grid=grid,
        in_specs=[wide, up, down, narrow, narrow_down, *consts],
        out_specs=(narrow, pl.BlockSpec((n + 1, 8, block),
                                        lambda c, b, t: (0, 0, c))),
        out_shape=(jax.ShapeDtypeStruct(dy.shape, f32),
                   jax.ShapeDtypeStruct((n + 1, 8, channels), f32)),
        scratch=[(_TAPS_HALO + min(_TAPS_ROWS, tile), block),
                 (2 * _TAPS_HALO, block), (tile + _TAPS_HALO, block)],
        interpret=interpret)


def _taps_bwd_call(z, taps, bias, dy, cols, tile, block, interpret,
                   first=0):
    """-> da (T, cols C), the sums over time (L + 1, 8, C): the taps'
    gradient row by row, then the bias's, eight partial sums each."""
    return _taps_bwd_built(z, z, z, dy, dy, taps, bias, cols=cols,
                           tile=tile, block=block, interpret=interpret,
                           first=first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _taps_silu(z, taps, bias, cols, tile, block, interpret, first=0):
    return _taps_fwd_call(z, taps, bias, cols, tile, block, interpret,
                          first)


def _taps_silu_fwd(z, taps, bias, cols, tile, block, interpret, first):
    return (_taps_fwd_call(z, taps, bias, cols, tile, block, interpret,
                           first), (z, taps, bias))


def _taps_silu_bwd(cols, tile, block, interpret, first, res, dy):
    z, taps, bias = res
    n, channels = taps.shape
    da, sums = _taps_bwd_call(z, taps, bias, dy, cols, tile, block,
                              interpret, first)
    sums = jnp.sum(sums, axis=1)
    dz = jnp.pad(da.reshape(z.shape[0], cols, channels),
                 ((0, 0), (0, 0),
                  (first, z.shape[1] // cols - first - channels)))
    return dz.reshape(z.shape), sums[:n], sums[n:]


_taps_silu.defvjp(_taps_silu_fwd, _taps_silu_bwd)


def causal_taps_silu_kernels(z, taps, bias, plan: dict,
                             interpret: bool = False, first: int = 0):
    """`ops.layers.causal_taps_silu` through the kernels above: z (T, B,
    W) float32 whose C channels from `first` on are convolved, taps (C,
    L), bias (C,) or None -> (T, B, C), differentiable in all three.
    What a backward pass keeps is the inputs alone."""
    t, cols, _ = z.shape
    channels = taps.shape[0]
    bias = jnp.zeros((channels,), jnp.float32) if bias is None else bias
    y = _taps_silu(z.reshape(t, -1), taps.T, bias.reshape(1, channels),
                   cols, plan["time_tile"], plan["channel_tile"], interpret,
                   first)
    return y.reshape(t, cols, channels)


# ---------------------------------------------------------------------------
# The Mamba-2 scan (state-space duality), fwd + bwd kernels
# ---------------------------------------------------------------------------
# `ops.layers.ssd_scan` as Mosaic calls.  A program is one batch column's
# group of R heads (the heads that share B and C); the grid's second axis
# walks the row's chunks in order, and the group's state (R P, N)
# float32, the heads' channels one under the other, stays in a VMEM
# scratch from the row's first chunk to its last.  The kernels read u, B
# and C WHERE THEY LIE, in the time-major array the convolution stage
# wrote ((T, B W) with [u | B | C] along W: three block specs on one
# operand, a group's R P lanes of u and its N lanes of B and of C), and
# write y time-major with the skip D u added: no slice, no swap and no
# pass of its own between the two stages.  Time lies on the sublanes and
# the channels on the lanes, so what a head has a token (dt, the running
# sum `cum` of dt A inside the chunk) arrives as columns, the heads side
# by side ((L, 2 R), made outside on arrays of T x H floats), and the
# running sum a second time as rows ((R, L)): a head's (L, L) decay
# matrix is  exp(cum as a column - cum as a row)  masked BEFORE the
# exponential, every decay the exponential of a difference of running
# sums (`layers._ssd_group`'s contract).  A chunk, with S the state
# before it (`layers._ssd_group`'s algebra):
#
#     y  = (decay o (C B^T)) (dt u)  +  e^cum o (C S^T)  +  D u
#     S' = e^(cum_L) S + ((dt u) o e^(cum_L - cum))^T B
#
# C B^T is made once a group; the read C S^T and the update are one
# product each over the group's R P channels; the R products with the
# decay matrices take a head's lanes of dt u, turned where a head is
# narrower than a 128-lane tile (`_SsdChunk.own`: the tile transposed
# once, a head's P rows streamed past its matrix).  Every product is
# float32 at HIGHEST, as outside (`_SSD_PRECISION`).  The forward pass
# writes y and the state BEFORE every chunk (`ssd.edges`: R P x N floats
# a chunk and group, 134 MB a layer at nemotron3nano's shape); the
# backward pass goes over the chunks last to first with dS in the
# scratch, reads a chunk's state instead of computing a group again, and
# writes du (dt's and the skip's part included), dB, dC, the sum over
# time of dy u a lane (D's gradient), and what reaches dt and cum a
# token and head, as columns (`_ssd_bwd_kernel` says how cum's is had
# from y and d(dt u) with no (L, L) matrix summed).  The running sum's,
# the softplus's and A's backward are XLA's, outside, on those T x H
# arrays, and so is the pass that lays du, dB, dC side by side as x
# lies (three outputs cannot share an array).  No call asks for a VMEM
# window.
#
# What sets a chunk's time is the MXU's rows: a float32 product at
# HIGHEST is six passes, each streams its left operand's rows eight at a
# time (`vmatmul`) past a (128, 128) tile, and the forward pass of a
# chunk and group streams 208 vregs of rows (C B^T 16, the read 64, the
# heads' own 64, the update 64), the backward 496.  The compiler's
# static schedule of the chunk loop (`--xla_jf_dump_llo_text`, offline)
# counts 3,003 bundles forward and 6,521 backward at the cell's shape
# (R = 8 heads of 64, N = 128, L = 128), and ranked every variant as
# the chip then did; on the chip (PR 48, call 1, 512 chunks and groups
# a layer) `cos_ssd_fwd` takes 1.11 ms and `cos_ssd_bwd` 2.45 ms, one
# and two chunks a grid step alike (4.69 | 4.47 ms forward + backward
# with the passes around them; four do not fit the window).  What cut
# the schedule from 3,520 + 8,027: the turned products (128 -> 64 rows a
# head), and cum's gradient from y (no C S^T in the backward pass, no
# row and column sums of R (L, L) matrices).

# chunks a grid step (one block of rows)
SSD_STEP_CHUNKS = 2


def ssd_scan_plan(t: int, bsz: int, heads: int, head_dim: int, groups: int,
                  states: int, chunk: int):
    """{chunk, steps, chunks, vmem_bytes} the kernels take a scan at (a
    row of `t` tokens as `chunks` chunks, whole grid steps of `steps`),
    or None where they do not take it.  They take: a chunk that is a
    power of two and whole 128-lane tiles; N and a group's R P channels
    in whole tiles; heads of whole sublanes that either share a tile
    evenly or fill tiles; [u | B | C] a batch column laid so that a
    group's u, B and C are whole blocks of the (T, B W) view; the
    backward call, the larger, inside the default VMEM window."""
    h, p, g, n, c = heads, head_dim, groups, states, int(chunk)
    if min(h, p, g, n, c, t, bsz) < 1 or h % g:
        return None
    r = h // g
    rp, di, w = r * p, h * p, h * p + 2 * g * n
    if (c & (c - 1) or c % 128 or n % 128 or rp % 128 or p % 8
            or (128 % p and p % 128) or 2 * r > 128):
        return None
    if di % n or (bsz > 1 and w % rp):
        return None
    chunks = -(-t // c)
    steps = min(SSD_STEP_CHUNKS, chunks)
    rows = steps * c
    # the backward call: u, y, dy in and du out; B, C in and dB, dC out;
    # the columns in and out, 128 lanes in VMEM; the rows; the states;
    # D and its gradient; each twice (the pipeline's two buffers); dS;
    # and what a chunk's body keeps alive beside them
    blocks = 2 * (4 * rows * rp + 4 * rows * n + 2 * rows * 128
                  + steps * max(r, 8) * c + steps * rp * n + 2 * rp)
    live = 8 * c * rp + 6 * c * c + 4 * rp * n
    vmem = 4 * (blocks + rp * n + live)
    if _flash_window(vmem) > _SCOPED_VMEM:
        return None
    return {"chunk": c, "steps": steps,
            "chunks": -(-chunks // steps) * steps, "vmem_bytes": vmem}


def _ssd_head_lanes(r: int, p: int):
    """[(head, first lane, last lane + 1, the head's place in that span
    or None)]: the lanes of (L, R P) that a head's products take.  A
    head of whole tiles takes its own; narrower heads take the tile they
    share, each with the others' lanes zeroed."""
    if p % 128 == 0:
        return [(i, i * p, (i + 1) * p, None) for i in range(r)]
    per = 128 // p
    return [(i, i // per * 128, (i // per + 1) * 128, i % per)
            for i in range(r)]


def _ssd_only(x, j, p: int):
    """A 128-lane tile with every lane but those of its head j zeroed
    (x itself where a head has its lanes to itself)."""
    if j is None:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= j * p) & (lane < (j + 1) * p), x, 0.0)


class _SsdChunk:
    """What a chunk's forward and backward passes share: C B^T, a
    head's columns and row and its decay matrix, the heads' columns laid
    over their lanes."""

    def __init__(self, bm, cm, cols, rows, r: int, p: int):
        n = cols.shape[0]
        self.r, self.p, self.n = r, p, n
        self.low = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
        self.cb = _gdn_dot(cm, bm, _NT)                 # C B^T, (L, L)
        self.dt = [cols[:, i:i + 1] for i in range(r)]
        self.cum = [cols[:, r + i:r + i + 1] for i in range(r)]
        self.cum_row = [rows[i:i + 1, :] for i in range(r)]
        # cum_L, (1, 1): from the row, whose lanes a sum spreads (a
        # corner of the columns would have to be spread both ways)
        end = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) == n - 1
        self.last = [_rowsum(jnp.where(end, x, 0.0)) for x in self.cum_row]
        self.heads = _ssd_head_lanes(r, p)

    def decay(self, i):
        """e^(cum_t - cum_s) at s <= t of head i, 0 elsewhere."""
        return jnp.exp(jnp.where(self.low, self.cum[i] - self.cum_row[i],
                                 _NEG_INF))

    def lanes(self, cols):
        """The heads' columns [(L, 1)] -> (L, R P): each over the lanes
        of its head."""
        p, n = self.p, self.n
        if p % 128 == 0:
            return jnp.concatenate(
                [jnp.broadcast_to(c, (n, p)) for c in cols], axis=1)
        per = 128 // p
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
        tiles = []
        for k in range(0, self.r, per):
            x = jnp.broadcast_to(cols[k], (n, 128))
            for j in range(1, per):
                x = jnp.where(lane >= j * p, cols[k + j], x)
            tiles.append(x)
        return jnp.concatenate(tiles, axis=1)

    def own(self, mats, x, dims=_NN):
        """(L, R P) whose lanes of head i are mats(i) (L, L) times (dims
        `_NN`; its transpose times, `_TN`) x's lanes of head i.  Heads
        narrower than a tile go through the MXU turned: a tile of x as
        (128, L), each head's P rows of it against its matrix, so that P
        rows stream past a matrix and not L with the other heads' lanes
        zeroed."""
        p = self.p
        if p % 128 == 0:
            return jnp.concatenate(
                [_gdn_dot(mats(i), x[:, i * p:(i + 1) * p], dims)
                 for i in range(self.r)], axis=1)
        per = 128 // p
        turned = _NT if dims == _NN else _NN
        tiles = []
        for k in range(0, self.r, per):
            xt = x[:, k * p:k * p + 128].T
            tiles.append(jnp.concatenate(
                [_gdn_dot(xt[j * p:(j + 1) * p], mats(k + j), turned)
                 for j in range(per)], axis=0).T)
        return jnp.concatenate(tiles, axis=1)

    def by_head(self, scale, s):
        """(R P, N), the rows of head i times scale[i], a (1, 1)."""
        p = self.p
        return jnp.concatenate(
            [scale[i] * s[i * p:(i + 1) * p] for i in range(self.r)],
            axis=0)

    def head_sum(self, x, i):
        """The sum over head i's lanes of (L, R P) -> (L, 1)."""
        _, a, b, j = self.heads[i]
        return _rowsum(_ssd_only(x[:, a:b], j, self.p))


# (A chunk's arithmetic is a jitted function of values that the kernel
# bodies call: a body is traced at every call site and again by every
# transformation that meets it, 16 + 4 times at a nemotron3nano job's
# start, and the jit's cache makes of all but the first a lookup; Mosaic
# lowers the call in line, so the kernels are what they would be
# without it.  PERF.md section 6, PR 48: the bodies written out cost the
# job 5 s of `setup_s`.)
@functools.partial(jax.jit, static_argnames=("r", "p"))
def _ssd_chunk_fwd(u, bm, cm, cols, rows, d, s, *, r: int, p: int):
    """A chunk of a group forward: u (L, R P), B, C (L, N), the heads'
    columns (L, 2 R) and rows (R, L), D over the lanes (1, R P), the
    state before the chunk (R P, N) -> y (L, R P), the state after."""
    c = u.shape[0]
    ch = _SsdChunk(bm, cm, cols, rows, r, p)
    cum = ch.lanes(ch.cum)
    du = ch.lanes(ch.dt) * u
    y = (ch.own(lambda i: ch.decay(i) * ch.cb, du)
         + jnp.exp(cum) * _gdn_dot(cm, s, _NT) + d * u)
    return y, (ch.by_head([jnp.exp(x) for x in ch.last], s)
               + _gdn_dot(du * jnp.exp(cum[c - 1:c, :] - cum), bm, _TN))


@functools.partial(jax.jit, static_argnames=("r", "p"))
def _ssd_chunk_bwd(u, bm, cm, cols, rows, d, y, dy, s, ds, *, r: int,
                   p: int):
    """A chunk of a group backward: the forward's operands, its y, dy,
    the state before the chunk and dS after it -> du, dB, dC, the heads'
    columns [d dt | d cum] (L, 128), the sum over the chunk of dy u (1,
    R P), dS before the chunk.  With ddu the gradient of dt u, what
    reaches a head's cum at token t is

        dy_t . (y_t - D u_t)  -  dt_t (u_t . ddu_t)

    (cum_t as the row of its decay matrix and in the read of the state:
    every term of y_t but the skip carries e^(cum_t); as the column, and
    in e^(cum_L - cum_t): every term that dt_t u_t enters carries
    e^(-cum_t)), and at the chunk's last token e^(cum_L) S . dS and every
    token's e^(cum_L - cum) besides: no (L, L) matrix is summed, and y
    is the forward kernel's own output."""
    c = u.shape[0]
    ch = _SsdChunk(bm, cm, cols, rows, r, p)
    dt, cum = ch.lanes(ch.dt), ch.lanes(ch.cum)
    du = dt * u
    dye = jnp.exp(cum) * dy
    decay_out = jnp.exp(cum[c - 1:c, :] - cum)          # e^(cum_L - cum)
    # d(dt u) from the state the chunk leaves, (L, R P)
    back = decay_out * _gdn_dot(bm, ds, _NT)
    # the heads' own products: d(decay o C B^T) = dy (dt u)^T
    dcb = jnp.zeros((c, c), jnp.float32)
    for i, a, b, j in ch.heads:
        dcb = dcb + ch.decay(i) * _gdn_dot(
            _ssd_only(dy[:, a:b], j, p), du[:, a:b], _NT)
    ddu = ch.own(lambda i: ch.decay(i) * ch.cb, dy, _TN) + back
    # what reaches dt and cum a token and head, as columns
    through_dt = ddu * u
    through_cum = dy * (y - d * u)
    through_end = back * du
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 128), 1)
    dcols = jnp.zeros((c, 128), jnp.float32)
    for i in range(r):
        ddt = ch.head_sum(through_dt, i)
        end = (jnp.exp(ch.last[i]) * jnp.sum(_rowsum(
            s[i * p:(i + 1) * p] * ds[i * p:(i + 1) * p]), axis=0,
            keepdims=True) + jnp.sum(ch.head_sum(through_end, i), axis=0,
                                     keepdims=True))
        dcum = (ch.head_sum(through_cum, i) - ch.dt[i] * ddt
                + jnp.where(row == c - 1, end, 0.0))
        dcols = jnp.where(lane == i, ddt, dcols)
        dcols = jnp.where(lane == r + i, dcum, dcols)
    return (dt * ddu + d * dy,
            _gdn_dot(dcb, cm, _TN) + _gdn_dot(decay_out * du, ds),
            _gdn_dot(dcb, bm) + _gdn_dot(dye, s), dcols,
            jnp.sum(dy * u, axis=0, keepdims=True),
            ch.by_head([jnp.exp(x) for x in ch.last], ds)
            + _gdn_dot(dye, cm, _TN))


def _ssd_fwd_kernel(u_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref,
                    y_ref, edge_ref, s_ref, *, r: int, p: int, c: int,
                    steps: int):
    """`steps` chunks of one batch column's group: y and the state
    before every chunk."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    def chunk(jj, carry):
        at = pl.ds(pl.multiple_of(jj * c, c), c)
        edge_ref[0, jj] = s_ref[...]
        y_ref[at, :], s_ref[...] = _ssd_chunk_fwd(
            u_ref[at, :], b_ref[at, :], c_ref[at, :], cols_ref[0, at, :],
            rows_ref[0, jj], d_ref[0], s_ref[...], r=r, p=p)
        return carry

    jax.lax.fori_loop(0, steps, chunk, 0)


def _ssd_bwd_kernel(u_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref,
                    y_ref, dy_ref, edge_ref, du_ref, db_ref, dc_ref,
                    dcols_ref, dd_ref, ds_ref, *, r: int, p: int, c: int,
                    steps: int):
    """The reverse sweep over `steps` chunks (the grid walks the row's
    blocks last first), dS of the group in VMEM."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, jnp.float32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

    def chunk(it, carry):
        jj = steps - 1 - it
        at = pl.ds(pl.multiple_of(jj * c, c), c)
        (du_ref[at, :], db_ref[at, :], dc_ref[at, :], dcols_ref[0, at, :],
         dd, ds_ref[...]) = _ssd_chunk_bwd(
            u_ref[at, :], b_ref[at, :], c_ref[at, :], cols_ref[0, at, :],
            rows_ref[0, jj], d_ref[0], y_ref[at, :], dy_ref[at, :],
            edge_ref[0, jj], ds_ref[...], r=r, p=p)
        dd_ref[0] += dd
        return carry

    jax.lax.fori_loop(0, steps, chunk, 0)


def _ssd_specs(dims, at):
    """Block specs of one grid step of program i = (batch column, group)
    for: u, B and C in the (T, B W) view of [u | B | C]; an array of R P
    lanes a group ((T, B G R P): y, dy, du) and one of N lanes ((T, B G
    N): dB, dC); the heads' columns (B G, T, lanes) and rows (B G,
    chunks, R, L); a row of R P lanes a group or a program; the states
    (B G, chunks, R P, N).  `at(j)` is the row block of grid step j."""
    _, r, p, g, n, c, steps, _ = dims
    rows, rp = steps * c, r * p
    w = g * rp + 2 * g * n

    def lanes(width, first):
        per, off = w // width, first // width
        return pl.BlockSpec((rows, width),
                            lambda i, j: (at(j), i // g * per + off + i % g))

    def cols(width):
        return pl.BlockSpec((1, rows, width), lambda i, j: (i, at(j), 0))

    return {
        "u": lanes(rp, 0), "b": lanes(n, g * rp),
        "c": lanes(n, g * rp + g * n),
        "wide": pl.BlockSpec((rows, rp), lambda i, j: (at(j), i)),
        "narrow": pl.BlockSpec((rows, n), lambda i, j: (at(j), i)),
        "cols": cols(2 * r), "dcols": cols(128),
        "rows": pl.BlockSpec((1, steps, r, c),
                             lambda i, j: (i, at(j), 0, 0)),
        "d": pl.BlockSpec((1, 1, rp), lambda i, j: (i % g, 0, 0)),
        "dd": pl.BlockSpec((1, 1, rp), lambda i, j: (i, 0, 0)),
        "edges": pl.BlockSpec((1, steps, rp, n),
                              lambda i, j: (i, at(j), 0, 0))}


def _ssd_rows(cols, dims):
    """The running sums once more, as rows: the cum half of the columns
    (B G, T, 2 R) -> (B G, chunks, R, L)."""
    _, r, _, _, _, c, _, chunks = dims
    return jnp.swapaxes(cols[..., r:].reshape(-1, chunks, c, r), 2, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ssd_rule(x, cols, d, dims, interpret):
    """x (T, B W), the heads' columns (B G, T, 2 R) = [dt | cum], D over
    the lanes of a group (G, 1, R P) -> y (T, B H P); dims = (B, R, P,
    G, N, chunk, chunks a grid step, chunks a row)."""
    return _ssd_rule_fwd(x, cols, d, dims, interpret)[0]


@_built_once
def _ssd_fwd_call(u, b, c, cols, rows, d, *, dims, interpret):
    bsz, r, p, g, n, lc, steps, chunks = dims
    spec = _ssd_specs(dims, lambda j: j)
    f32 = jnp.float32
    return _mosaic_call(
        functools.partial(_ssd_fwd_kernel, r=r, p=p, c=lc, steps=steps),
        "cos_ssd_fwd", grid=(bsz * g, chunks // steps),
        in_specs=[spec[k] for k in ("u", "b", "c", "cols", "rows", "d")],
        out_specs=(spec["wide"], spec["edges"]),
        out_shape=(jax.ShapeDtypeStruct((u.shape[0], bsz * g * r * p),
                                        f32),
                   jax.ShapeDtypeStruct((bsz * g, chunks, r * p, n), f32)),
        scratch=[(r * p, n)], semantics=("parallel", "arbitrary"),
        interpret=interpret)


def _ssd_rule_fwd(x, cols, d, dims, interpret):
    y, edges = _ssd_fwd_call(x, x, x, cols, _ssd_rows(cols, dims), d,
                             dims=dims, interpret=interpret)
    # what a recompute_block keeps of the scan: the gated norm's
    # backward and the backward kernel read y, the kernel a chunk's
    # state
    y, edges = keep(y, "ssd.y"), keep(edges, "ssd.edges")
    return y, (x, cols, d, y, edges)


@_built_once
def _ssd_bwd_call(u, b, c, cols, rows, d, y, dy, edges, *, dims,
                  interpret):
    bsz, r, p, g, n, lc, steps, chunks = dims
    full, count = u.shape[0], chunks // steps
    spec = _ssd_specs(dims, lambda j: count - 1 - j)
    f32 = jnp.float32
    return _mosaic_call(
        functools.partial(_ssd_bwd_kernel, r=r, p=p, c=lc, steps=steps),
        "cos_ssd_bwd", grid=(bsz * g, count),
        in_specs=[spec[k] for k in ("u", "b", "c", "cols", "rows", "d",
                                    "wide", "wide", "edges")],
        out_specs=tuple(spec[k] for k in ("wide", "narrow", "narrow",
                                          "dcols", "dd")),
        out_shape=(jax.ShapeDtypeStruct(dy.shape, f32),
                   jax.ShapeDtypeStruct((full, bsz * g * n), f32),
                   jax.ShapeDtypeStruct((full, bsz * g * n), f32),
                   jax.ShapeDtypeStruct((bsz * g, full, 128), f32),
                   jax.ShapeDtypeStruct((bsz * g, 1, r * p), f32)),
        scratch=[(r * p, n)], semantics=("parallel", "arbitrary"),
        interpret=interpret)


def _ssd_rule_bwd(dims, interpret, res, dy):
    x, cols, d, y, edges = res
    bsz, r, p, g = dims[:4]
    full = x.shape[0]
    du, db, dc, dcols, dd = _ssd_bwd_call(
        x, x, x, cols, _ssd_rows(cols, dims), d, y, dy, edges, dims=dims,
        interpret=interpret)
    # [du | dB | dC] a batch column, as x lies
    dx = jnp.concatenate(
        [a.reshape(full, bsz, -1) for a in (du, db, dc)], axis=-1)
    return (dx.reshape(x.shape), dcols[..., :2 * r],
            jnp.sum(dd.reshape(bsz, g, 1, r * p), axis=0))


_ssd_rule.defvjp(_ssd_rule_fwd, _ssd_rule_bwd)


def ssd_scan_kernels(x, dt, a, d, plan: dict, *, groups: int, states: int,
                     interpret: bool = False):
    """`ops.layers.ssd_scan` through the kernels above: x (T, B, W) =
    [u (H P) | B (G N) | C (G N)] time-major, dt (T, B, H), a = A and d
    = D (H,) -> y (T, B, H P), differentiable in all four.  Made here,
    outside the kernels, on arrays of T x H floats: dt A and its running
    sum inside each chunk, and the two as the columns and rows a
    program reads.  T is padded to whole grid steps with steps that
    neither decay nor write (dt 0)."""
    t, bsz, w = x.shape
    h, g, n = dt.shape[-1], groups, states
    r, p = h // g, (w - 2 * g * n) // h
    c, chunks = plan["chunk"], plan["chunks"]
    full = chunks * c
    if full != t:
        x, dt = (jnp.pad(v, ((0, full - t), (0, 0), (0, 0)))
                 for v in (x, dt))
    cum = jnp.cumsum((dt * a).reshape(chunks, c, bsz, g, r), axis=1)

    def by_group(v):        # (T, B, G, R) -> (B G, T, R)
        return jnp.moveaxis(v, 0, 2).reshape(bsz * g, full, r)

    cols = jnp.concatenate(
        [by_group(dt.reshape(full, bsz, g, r)),
         by_group(cum.reshape(full, bsz, g, r))], axis=-1)
    y = _ssd_rule(x.reshape(full, bsz * w), cols,
                  jnp.repeat(d, p).reshape(g, 1, r * p),
                  (bsz, r, p, g, n, c, plan["steps"], chunks), interpret)
    return y.reshape(full, bsz, h * p)[:t]


# ---------------------------------------------------------------------------
# The expert layers' grouped products
# ---------------------------------------------------------------------------
# `ops.layers._moe_pass`'s products as Mosaic calls.  The rows of a pass
# are sorted by expert: group g of the G experts held owns `sizes[g]`
# consecutive rows, the rows past the last group belong to nobody.  Three
# kinds of product, one call each:
#
#     rows      out[r] = a[r] W[g(r)]          a (M, K), W (G, K, N) -> (M, N)
#     rows^T    out[r] = a[r] W[g(r)]^T        a (M, N)              -> (M, K)
#     weights   dW[g]  = a[rows of g]^T b[rows of g]   (M, K), (M, N) -> (G, K, N)
#
# The stated precision and no more: every kernel reads float32 tiles
# where they lie (W and W^T through the block specs' index maps, no
# copy, cast or transpose of a weight outside), rounds them to bfloat16
# in VMEM and accumulates in float32, one MXU pass, as `jnp.matmul` of the
# same operands runs at JAX's default precision.
#
# The rows go in tiles of `GMM_ROW_TILE`; a VISIT is a (row tile, group)
# pair whose rows intersect, in the order of the groups (`gmm_visits`,
# made once a pass on G integers; at most tiles + G - 1 of them, the
# grid's last axis, the unused ones at the end standing still on the
# last visit's blocks).  The grid is (block of output lanes, visit) for
# the two row products: the group's (K, lanes) block of W stays in VMEM
# while the group's row tiles go past it, is fetched once a group (the
# pipeline skips a block whose index does not change) and rounded to
# bfloat16 once, into a scratch, when the group changes; a tile that two
# groups share is visited by each, which writes its own rows.  For the
# weights' gradient the grid is (block of K, block of N, visit): the
# (K block, N block) of dW[g] stays in VMEM and sums the group's row
# tiles, every other group's rows in a shared tile masked out; an empty
# group is visited once, to be zeroed.  A hidden width that is no
# multiple of 128 (nemotron3nano's 1856) is a whole block where it is
# contracted and a masked last block where it is the output's lanes;
# nothing is padded outside.
#
# The rows that no group owns (those past the last group) are left
# undefined in every output: zeros or whatever the buffer held;
# `_moe_pass` cuts those rows out before it sums.

_BF16 = jnp.bfloat16


class GmmTiles(NamedTuple):
    """Tiles of the three products against one weight (G, K, N), beside
    the row tile `GMM_ROW_TILE`: the lanes of N a `rows` call holds of
    W, the rows of K a `rows^T` call holds, the (K, N) block of dW."""
    lanes: int
    lanes_t: int
    grad: Tuple[int, int]


# What the blocks of one call may take of the default 16 MiB window by
# the counts below, which are generous (the calls compile for the v5e at
# 15 MiB so counted; `tests/test_tpu_compile.py` holds the cells'
# shapes): at 14 the five cells' passes read 2-5% shorter than at 12 (my
# chip run, PR 50, call 1).  The row tile is 128 whatever the rows an
# even router sends an expert (160 to 1,536 in the five cells): 256 read
# level to 3% slower in all five and 512 17-49% slower (a tile that two
# groups share is multiplied once for each), and at 128 the blocks of W
# can be widest.
_GMM_VMEM = 14 << 20
GMM_ROW_TILE = 128


def _gmm_rows_bytes(k: int, lanes: int, transposed: bool) -> int:
    """VMEM of a `rows` call: the (K, lanes) block of W twice (the
    pipeline's buffers) and once in bfloat16, the row tile twice and its
    bfloat16 copy, the output tile twice and the product."""
    w = _lanes(k) * lanes if transposed else k * _lanes(lanes)
    tm = GMM_ROW_TILE
    return 10 * w + 10 * tm * _lanes(k) + 12 * tm * _lanes(lanes)


def _gmm_grad_bytes(bk: int, bn: int) -> int:
    """VMEM of a `weights` call: the block of dW twice and the product,
    the two row tiles twice and in bfloat16."""
    return (12 * bk * _lanes(bn)
            + 10 * GMM_ROW_TILE * (_lanes(bk) + _lanes(bn)))


def _gmm_fit(width: int, most: int, fits) -> int:
    """The block of `width` lanes to take: the fewest blocks that `fits`
    allows (blocks are multiples of 128, at most `most`), and of those
    the narrowest, so that the masked last block wastes least; `width`
    itself where it fits whole; 0 where nothing does."""
    if width <= most and fits(width):
        return width
    best = 0
    for lanes in range(128, min(most, _lanes(width)) + 1, 128):
        if fits(lanes):
            best = lanes
    if not best:
        return 0
    count = -(-width // best)
    return _lanes(-(-width // count))


@functools.lru_cache(maxsize=None)
def gmm_plan(m: int, k: int, n: int, groups: int) -> Optional[GmmTiles]:
    """The tiles of the products of (M, K) rows against (G, K, N)
    weights, or None where the kernels do not take the shape: M in whole
    row tiles, K and N in whole sublane tiles of bfloat16.  A function
    of the shape alone: the widest blocks that leave the calls inside
    the default VMEM window, so that the rows are read again (once a
    block of W) as few times as can be."""
    if min(m, k, n, groups) < 1 or m % GMM_ROW_TILE or k % 16 or n % 16:
        return None
    lanes = _gmm_fit(n, 1024, lambda b: _gmm_rows_bytes(
        k, b, False) <= _GMM_VMEM)
    lanes_t = _gmm_fit(k, 1024, lambda b: _gmm_rows_bytes(
        n, b, True) <= _GMM_VMEM)
    # dW's block: the widest N first (its rows are the lanes of both
    # the block and b's tile), then the most of K beside it
    bn = _gmm_fit(n, 1024, lambda b: _gmm_grad_bytes(256, b) <= _GMM_VMEM)
    bk = bn and _gmm_fit(k, 1024, lambda b: _gmm_grad_bytes(
        b, bn) <= _GMM_VMEM)
    if not (lanes and lanes_t and bn and bk):
        return None
    return GmmTiles(lanes, lanes_t, (bk, bn))


@functools.partial(jax.jit, static_argnames=("m",), inline=True)
def gmm_visits(sizes, m: int):
    """The visits of a pass of `m` rows whose group g owns `sizes[g]`
    consecutive rows from the first row on: int32 (4 V,), V = m / tm + G
    - 1 (tm = `GMM_ROW_TILE`), laid [row tile | group | first row | end
    row] with the rows counted from the tile's first.  Every (tile,
    group) whose rows intersect, the groups in order and a group's tiles
    in order; an empty group once, with no rows, at the tile its first
    row would lie in; what is left of V repeats the last visit with no
    rows."""
    g, tm = sizes.shape[0], GMM_ROW_TILE
    tiles = m // tm
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles - 1)
    last = jnp.where(sizes > 0, (ends - 1) // tm, first)
    count = last - first + 1
    upto = jnp.cumsum(count)
    v = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    live = v < upto[-1]
    grp = jnp.minimum(jnp.sum(v[:, None] >= upto[None, :], axis=1),
                      g - 1).astype(jnp.int32)
    tile = jnp.where(live, first[grp] + v - (upto - count)[grp],
                     last[g - 1])
    lo = jnp.where(live, jnp.maximum(starts[grp] - tile * tm, 0), 0)
    hi = jnp.where(live, jnp.minimum(ends[grp] - tile * tm, tm), 0)
    return jnp.concatenate([tile, grp, lo, hi]).astype(jnp.int32)


def _gmm_mine(s_ref, v, visits: int, tm: int):
    """(tm, 1) bool: the rows of visit v's tile that its group owns."""
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= s_ref[2 * visits + v]) & (row < s_ref[3 * visits + v])


def _gmm_rows_kernel(s_ref, a_ref, w_ref, o_ref, wb_ref, *, visits: int,
                     transposed: bool):
    """A visit of `rows` / `rows^T`: the group's block of W to bfloat16
    when the group changes, the tile's product with it, and of that the
    group's rows into the output tile.  The tile's other rows keep what
    the earlier visits of the tile wrote, so every row that a group owns
    holds that group's product at the end; the rows nobody owns are
    undefined (zeros where the tile's first visit had rows; a visit with
    none, an empty group's, writes nothing, and the next visit of its
    tile then keeps what the buffer held)."""
    v = pl.program_id(1)
    prev = jnp.maximum(v - 1, 0)

    @pl.when((v == 0) | (s_ref[visits + v] != s_ref[visits + prev]))
    def _():
        wb_ref[...] = w_ref[0].astype(_BF16)

    @pl.when(s_ref[3 * visits + v] > s_ref[2 * visits + v])
    def _():
        acc = jax.lax.dot_general(
            a_ref[...].astype(_BF16), wb_ref[...],
            _NT if transposed else _NN, preferred_element_type=jnp.float32)
        fresh = (v == 0) | (s_ref[v] != s_ref[prev])
        o_ref[...] = jnp.where(
            _gmm_mine(s_ref, v, visits, o_ref.shape[0]), acc,
            jnp.where(fresh, 0.0, o_ref[...]))


def _gmm_weights_kernel(s_ref, a_ref, b_ref, o_ref, *, visits: int):
    """A visit of `weights`: a^T b over the group's rows of the tile,
    added to the group's block, which starts from zeros."""
    v = pl.program_id(2)
    prev = jnp.maximum(v - 1, 0)

    @pl.when((v == 0) | (s_ref[visits + v] != s_ref[visits + prev]))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(s_ref[3 * visits + v] > s_ref[2 * visits + v])
    def _():
        mine = _gmm_mine(s_ref, v, visits, a_ref.shape[0])
        o_ref[0] += jax.lax.dot_general(
            jnp.where(mine, a_ref[...], 0.0).astype(_BF16),
            jnp.where(mine, b_ref[...], 0.0).astype(_BF16), _TN,
            preferred_element_type=jnp.float32)


def _gmm_call(kernel, name, *, grid, in_specs, out_specs, out_shape,
              scratch, interpret):
    """One grouped-product call on (visits, operands): the visits
    prefetched into SMEM for the index maps, and the grid's last axis,
    in order.  The call, for a `_built_once` builder to hand back (a
    job's step holds 8 to 12 sites a layer)."""
    semantics = ("parallel",) * (len(grid) - 1) + ("arbitrary",)
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=semantics)}
    return pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        **params)


@_built_once
def _gmm_rows_call(visits, a, w, *, tiles: GmmTiles, transposed: bool,
                   interpret: bool):
    """rows (a (M, K) -> (M, N)) or rows^T (a (M, N) -> (M, K)) against
    w (G, K, N)."""
    m, (_, k, n) = a.shape[0], w.shape
    tm, count = GMM_ROW_TILE, visits.shape[0] // 4
    if transposed:
        block, width = tiles.lanes_t, k
        w_spec = pl.BlockSpec((1, block, n),
                              lambda j, v, s: (s[count + v], j, 0))
        held = (block, n)
    else:
        block, width = tiles.lanes, n
        w_spec = pl.BlockSpec((1, k, block),
                              lambda j, v, s: (s[count + v], 0, j))
        held = (k, block)
    return _gmm_call(
        functools.partial(_gmm_rows_kernel, visits=count,
                          transposed=transposed),
        "cos_gmm_rows_t" if transposed else "cos_gmm_rows",
        grid=(-(-width // block), count),
        in_specs=[pl.BlockSpec((tm, a.shape[1]),
                               lambda j, v, s: (s[v], 0)), w_spec],
        out_specs=pl.BlockSpec((tm, block), lambda j, v, s: (s[v], j)),
        out_shape=jax.ShapeDtypeStruct((m, width), jnp.float32),
        scratch=[pltpu.VMEM(held, _BF16)], interpret=interpret)


@_built_once
def _gmm_weights_call(visits, a, b, *, groups: int, tiles: GmmTiles,
                      interpret: bool):
    """dW (G, K, N) of a (M, K) and b (M, N)."""
    k, n = a.shape[1], b.shape[1]
    tm, (bk, bn), count = GMM_ROW_TILE, tiles.grad, visits.shape[0] // 4
    return _gmm_call(
        functools.partial(_gmm_weights_kernel, visits=count),
        "cos_gmm_weights", grid=(-(-k // bk), -(-n // bn), count),
        in_specs=[pl.BlockSpec((tm, bk), lambda i, j, v, s: (s[v], i)),
                  pl.BlockSpec((tm, bn), lambda i, j, v, s: (s[v], j))],
        out_specs=pl.BlockSpec((1, bk, bn),
                               lambda i, j, v, s: (s[count + v], i, j)),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        scratch=[], interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_product(a, w, visits, tiles: GmmTiles, interpret: bool = False):
    """a (M, K) float32 rows sorted by group times their group's weight
    of w (G, K, N) float32 -> (M, N), one bfloat16 pass accumulated in
    float32; `visits` = `gmm_visits(sizes, M)`.  Differentiable in a
    and w: da is the rows^T product of the cotangent, dw the weights
    product of a and the cotangent."""
    return _gmm_rows_call(visits, a, w, tiles=tiles, transposed=False,
                          interpret=interpret)


def _grouped_product_fwd(a, w, visits, tiles, interpret):
    return grouped_product(a, w, visits, tiles, interpret), (a, w, visits)


def _grouped_product_bwd(tiles, interpret, res, dy):
    a, w, visits = res
    return (_gmm_rows_call(visits, dy, w, tiles=tiles, transposed=True,
                           interpret=interpret),
            _gmm_weights_call(visits, a, dy, groups=w.shape[0], tiles=tiles,
                              interpret=interpret),
            None)


grouped_product.defvjp(_grouped_product_fwd, _grouped_product_bwd)
