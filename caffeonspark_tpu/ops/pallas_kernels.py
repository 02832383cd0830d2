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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512  # spatial lanes per block (4 × 128)


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


def int8_matmul(xq: jax.Array, wq: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """(M, K) int8 @ (N, K) int8ᵀ → (M, N) int32.  Pallas-tiled when
    the shapes tile (grid over M/N blocks, K resident per block — the
    flash kernels' layout); XLA int8 dot_general otherwise (same
    int32-accumulated math on every backend, incl. CPU)."""
    m, kk = xq.shape
    n = wq.shape[0]
    tiles = (m % INT8_BLOCK_M == 0 and n % INT8_BLOCK_N == 0
             and kk % INT8_BLOCK_LANE == 0)
    if tiles and (interpret or pallas_enabled()):
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
            interpret=interpret,
        )(xq, wq)
    return jax.lax.dot_general(xq, wq, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def int8_inner_product(x: jax.Array, w: jax.Array, *,
                       transpose: bool = False,
                       interpret: bool = False,
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
    acc = int8_matmul(xq, wqn, interpret=interpret)
    return (acc.astype(jnp.float32) * (sx * sw)).astype(x.dtype)


def pallas_enabled() -> bool:
    """Pallas kernels activate on the TPU backend only (CPU tests use
    interpret=True explicitly).  A backend that fails to initialise
    raises here — it must not quietly become "no Pallas"."""
    import os
    if os.environ.get("COS_DISABLE_PALLAS"):
        return False
    return jax.default_backend() == "tpu"


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
# (B·H, T/block).  K/V block specs expose the full (T, D) per head —
# VMEM-bounded at T·D·4 bytes ≈ 4 MB at T=8k, D=128 f32; rows beyond
# 4,096 are cut into chunks whose calls fit the default VMEM window and
# run pair by pair (`_flash_chunk`: the same composition the ring makes
# across devices).

BLOCK_Q = 128
BLOCK_K = 128
_NEG_INF = -1e30          # finite mask value: -inf NaNs the m-corr path


def _online_softmax_step(q, kb, vb, m, l, acc, *, sm_scale: float,
                         causal: bool, q_pos, k_pos, mxu_dtype=None):
    """One online-softmax accumulation (the flash/ring shared algebra):
    scores for (q, kb) fold into the (m, l, acc) carry.  The m_safe
    guard makes fully-masked-so-far rows accumulate exact zeros (a
    no-op for rows that have seen the causal diagonal).  m and l are
    (block_q, 1) column vectors — Mosaic's block-shape rule wants the
    per-row stats rank-2, and the column form broadcasts against the
    (block_q, block_k) score strip with no reshapes."""
    s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * sm_scale
    if causal:
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(m - m_safe)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    if mxu_dtype is not None:
        p = p.astype(mxu_dtype)
    acc_new = acc * corr + jnp.dot(
        p, vb, preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      sm_scale: float, causal: bool, block_k: int,
                      mxu_dtype=None):
    # operands of every product: float32 (exact, several MXU passes)
    # unless the caller asks for one bfloat16 pass with float32
    # accumulation, which is what XLA's default precision gives the
    # einsum path on the TPU
    cd = mxu_dtype or jnp.float32
    q = q_ref[0].astype(cd)                     # (block_q, D)
    t = k_ref.shape[1]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(cd)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(cd)
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return _online_softmax_step(q, kb, vb, m, l, acc,
                                    sm_scale=sm_scale, causal=causal,
                                    q_pos=q_pos, k_pos=k_pos,
                                    mxu_dtype=mxu_dtype)

    if causal:
        # K/V blocks starting past this q block's last row are fully
        # masked — skipping them halves the causal pass's work
        n_k = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        n_k = t // block_k
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, a0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, *, sm_scale: float,
                          causal: bool, block_q: int, mxu_dtype=None):
    cd = mxu_dtype or jnp.float32
    kb = k_ref[0].astype(cd)                    # (block_k, D)
    vb = v_ref[0].astype(cd)
    t = q_ref.shape[1]
    block_k = kb.shape[0]
    ki = pl.program_id(1)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(cd)
        dob = do_ref[0, pl.ds(i * block_q, block_q), :].astype(cd)
        lse = lse_ref[0, pl.ds(i * block_q, block_q)]   # (block_q, 1)
        dlt = delta_ref[0, pl.ds(i * block_q, block_q)]
        s = jnp.dot(qb, kb.T,
                    preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                     # exact probabilities
        dv_new = dv + jnp.dot(p.astype(cd).T, dob,
                              preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * sm_scale
        dk_new = dk + jnp.dot(ds.astype(cd).T, qb,
                              preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zk = jnp.zeros((block_k, kb.shape[-1]), jnp.float32)
    zv = jnp.zeros((block_k, vb.shape[-1]), jnp.float32)
    # causal: q blocks ending before this k block's first row see only
    # masked scores — start at the diagonal
    i0 = (ki * block_k) // block_q if causal else 0
    dk, dv = jax.lax.fori_loop(i0, t // block_q, body, (zk, zv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, *, sm_scale: float,
                         causal: bool, block_k: int, mxu_dtype=None):
    cd = mxu_dtype or jnp.float32
    qb = q_ref[0].astype(cd)                     # (block_q, D)
    dob = do_ref[0].astype(cd)
    lse = lse_ref[0]                             # (block_q, 1)
    dlt = delta_ref[0]
    t = k_ref.shape[1]
    block_q = qb.shape[0]
    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, dq):
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(cd)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(cd)
        s = jnp.dot(qb, kb.T,
                    preferred_element_type=jnp.float32) * sm_scale
        if causal:
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * sm_scale
        return dq + jnp.dot(ds.astype(cd), kb,
                            preferred_element_type=jnp.float32)

    if causal:
        n_k = ((qi + 1) * block_q + block_k - 1) // block_k
    else:
        n_k = t // block_k
    dq = jax.lax.fori_loop(0, n_k, body,
                           jnp.zeros((block_q, qb.shape[-1]),
                                     jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_specs(block, d, t):
    # `*_` absorbs the scalar-prefetch refs appended to index-map args
    # when these specs are used under a PrefetchScalarGridSpec.
    # Per-row stats (m/l/lse/delta) travel as (bh, t, 1) column vectors:
    # Mosaic requires the last two block dims divisible by (8, 128) OR
    # equal to the array dims — (block, 1) satisfies that ((1, block)
    # from a rank-2 (bh, t) layout does not, and fails to lower).
    qspec = pl.BlockSpec((1, block, d), lambda b, i, *_: (b, i, 0))
    kvspec = pl.BlockSpec((1, t, d), lambda b, i, *_: (b, 0, 0))
    vec = pl.BlockSpec((1, block, 1), lambda b, i, *_: (b, i, 0))
    vec_full = pl.BlockSpec((1, t, 1), lambda b, i, *_: (b, 0, 0))
    return qspec, kvspec, vec, vec_full


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
    """What a block's last dimension takes in VMEM: a head narrower
    than the 128 lanes of a tile is padded to them (64-wide heads cost
    what 128-wide ones do).  Widths from 128 up count as they are, as
    they always have here, so the calls of the shapes the tree already
    ran ask for what they asked."""
    return max(width, 128)


# What XLA's memory-space assignment leaves every op on the v5e, a
# Mosaic call included, whatever window the call itself asks for: its
# own VMEM buffers that live across the call lie from here up (PR 33).
_SCOPED_VMEM = 16 << 20
# Rows up to which a call may still ask for a larger window: the calls
# the tree already ran on the chip (kanana2's at 4,096) keep their
# lowering.
_ASK_UP_TO_T = 4096


def _flash_window(block_bytes: int) -> int:
    """VMEM a call takes: its blocks double-buffered, and room for
    Mosaic's own scratch."""
    return 2 * block_bytes + (4 << 20)


def _flash_compiler_params(block_bytes: int, interpret: bool):
    """Mosaic's default scoped VMEM (16 MiB on the v5e) holds the
    double-buffered blocks of a call up to T ~ 2k at 128-wide heads;
    beyond that the call asks for what its blocks need (the chip has
    128 MiB).  {} below the default, so small calls compile as before.
    Rows beyond `_ASK_UP_TO_T` never come here over the default:
    `_flash_chunk` cuts them first."""
    need = _flash_window(block_bytes)
    if interpret or need <= _SCOPED_VMEM:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(need, 100 << 20))}


def _flash_chunk(t: int, block: int, *block_bytes) -> int:
    """Rows of q and of k / v a single call takes.  A window above the
    default is not safe: XLA places the VMEM buffers it keeps across a
    Mosaic call as if the call took the default 16 MiB, so a call that
    uses more writes over them (the sorted token ids of the embedding's
    scatter-add among them: a step of lfm2 at one row of 8,192 tokens
    never ended, PR 33).  So rows beyond `_ASK_UP_TO_T` are halved
    until every one of `block_bytes` (functions of the rows: one for
    each kernel of the call) fits the default window; the caller runs
    the pairs of chunks and adds them up.  t itself where it fits or
    is short enough to ask; a length no halving brings inside is an
    error, not a call that may never end."""
    def fits(n):
        return all(_flash_window(f(n)) <= _SCOPED_VMEM
                   for f in block_bytes)
    if t <= _ASK_UP_TO_T or fits(t):
        return t
    c = t
    while not fits(c):
        if c % 2 or (c // 2) % block:
            raise ValueError(
                f"flash_attention: {t} rows cannot be halved into "
                f"chunks of whole {block}-row blocks that fit "
                f"{_SCOPED_VMEM >> 20} MiB of VMEM (stopped at {c})")
        c //= 2
    return c


def _chunk_pairs(n: int, causal: bool):
    """(q chunk, k / v chunk, causal) of every pair that holds a score:
    under the causal mask the pairs below the diagonal are whole, the
    diagonal ones masked by their local positions (the chunks are
    equally long), the ones above it empty."""
    return [(i, j, causal and i == j) for i in range(n)
            for j in range(i + 1 if causal else n)]


def _rows(x, i, c):
    return jax.lax.slice_in_dim(x, i * c, (i + 1) * c, axis=1)


def _fwd_block_bytes(d, dv, isz, block_q):
    return lambda t: (t * (_lanes(d) + _lanes(dv)) * isz
                      + block_q * (_lanes(d) + _lanes(dv) + 128) * 4)


def _dq_block_bytes(d, dv, isz, block_q):
    return lambda t: (t * (_lanes(d) + _lanes(dv)) * isz
                      + block_q * (2 * _lanes(d) + _lanes(dv) + 256) * 4)


def _dkv_block_bytes(d, dv, isz, block_k):
    # the two per-row statistics travel as (T, 1) columns, which VMEM
    # pads to 128 lanes
    return lambda t: (t * (_lanes(d) + _lanes(dv)) * isz + 2 * t * 128 * 4
                      + block_k * 2 * (_lanes(d) + _lanes(dv)) * 4)


def _flash_fwd_call(q, k, v, sm_scale, causal, block_q, block_k,
                    interpret, mxu_dtype=None):
    bh, t, d = q.shape
    dv = v.shape[-1]
    if t % block_q or t % block_k:
        # a truncated grid would leave the output/lse tail rows
        # uninitialized garbage — fail loudly (mirrors
        # flash_block_update; in-repo callers pre-check and fall back
        # to the XLA path, this guards direct calls)
        raise ValueError(
            f"flash_attention needs T divisible by the blocks: "
            f"t={t} % block_q={block_q}, t={t} % block_k={block_k}")
    isz = q.dtype.itemsize
    block_bytes = _fwd_block_bytes(d, dv, isz, block_q)
    c = _flash_chunk(t, max(block_q, block_k), block_bytes)
    if c < t:
        # each chunk of q against the chunks of k / v it sees, one call
        # a pair; a row's parts are weighted by their share of its
        # softmax sum
        outs, lses = [], []
        for i in range(t // c):
            parts = [_flash_fwd_call(_rows(q, i, c), _rows(k, j, c),
                                     _rows(v, j, c), sm_scale, cz,
                                     block_q, block_k, interpret,
                                     mxu_dtype)
                     for qi, j, cz in _chunk_pairs(t // c, causal)
                     if qi == i]
            lse = functools.reduce(jnp.logaddexp, [p[1] for p in parts])
            outs.append(sum(o * jnp.exp(l - lse)[:, :, None].astype(
                o.dtype) for o, l in parts))
            lses.append(lse)
        return (jnp.concatenate(outs, axis=1),
                jnp.concatenate(lses, axis=1))
    kern = functools.partial(_flash_fwd_kernel, sm_scale=sm_scale,
                             causal=causal, block_k=block_k,
                             mxu_dtype=mxu_dtype)
    g = bh // k.shape[0]            # query heads a key/value head
    qspec, _, vec, _ = _flash_specs(block_q, d, t)
    ospec, _, _, _ = _flash_specs(block_q, dv, t)
    _, kspec = _flash_kv_specs(block_q, d, t, g)
    _, vspec = _flash_kv_specs(block_q, dv, t, g)
    out, lse = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)),
        grid=(bh, t // block_q),
        in_specs=[qspec, kspec, vspec],
        out_specs=(ospec, vec),
        interpret=interpret,
        name="cos_flash_fwd",
        **_flash_compiler_params(block_bytes(t), interpret),
    )(q, k, v)
    return out, lse[:, :, 0]


def _flash_flatten(q, k, v):
    """(B, heads, T, ·) -> (B·heads, T, ·), each with its own heads."""
    if q.shape[1] % k.shape[1] or k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention: {q.shape[1]} query heads over "
            f"{k.shape[1]} key / {v.shape[1]} value heads")
    return tuple(x.reshape((-1,) + x.shape[2:]) for x in (q, k, v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, block_q: int = BLOCK_Q,
                    block_k: int = BLOCK_K,
                    interpret: bool = False,
                    mxu_dtype=None) -> jax.Array:
    """Fused blockwise attention, q (B, H, T, D), k (B, H/g, T, D),
    v (B, H/g, T, Dv) → (B, H, T, Dv); Dv may differ from D (latent
    attention: 192-wide q/k, 128-wide v), and with g > 1 query head h
    reads key/value head h // g through the kernels' block index maps
    (no repeated copy of k or v; dK/dV come out a query head and are
    summed over each group).

    Same math as parallel.sp.attention (softmax(QKᵀ/√D)V, optional
    causal mask); O(block·T) VMEM instead of an O(T²) HBM score
    matrix, exact (not approximate) via online softmax.  Requires T
    divisible by the block sizes — callers fall back to the XLA path
    otherwise (ops.layers._mha).  `mxu_dtype` (None = float32
    operands) is the operand type of the products; the accumulators,
    the softmax statistics and the outputs stay float32/input dtype."""
    b, h, t, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flash_flatten(q, k, v)
    out, _ = _flash_fwd_call(qf, kf, vf, sm_scale, causal, block_q,
                             block_k, interpret, mxu_dtype)
    return out.reshape(b, h, t, v.shape[-1])


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret,
                   mxu_dtype):
    b, h, t, d = q.shape
    sm_scale = 1.0 / math.sqrt(d)
    qf, kf, vf = _flash_flatten(q, k, v)
    out, lse = _flash_fwd_call(qf, kf, vf, sm_scale, causal, block_q,
                               block_k, interpret, mxu_dtype)
    return out.reshape(b, h, t, v.shape[-1]), (qf, kf, vf, out, lse)


def flash_bwd_block(qf, kf, vf, dof, lse, delta, *, causal: bool,
                    block_q: int, block_k: int, interpret: bool,
                    out_dtype=None, mxu_dtype=None):
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
    don't round each per-hop partial before the sum."""
    bh, t, d = qf.shape
    dv_w = vf.shape[-1]
    isz = qf.dtype.itemsize
    dq_bytes = _dq_block_bytes(d, dv_w, isz, block_q)
    dkv_bytes = _dkv_block_bytes(d, dv_w, isz, block_k)
    c = _flash_chunk(t, max(block_q, block_k), dq_bytes, dkv_bytes)
    if c < t:
        # lse and delta are whole rows' statistics, so the pairs of
        # chunks add up: dq over a q chunk's pairs, dk and dv over a
        # k / v chunk's
        n = t // c
        dqs, dks, dvs = [None] * n, [None] * n, [None] * n
        for i, j, cz in _chunk_pairs(n, causal):
            part = flash_bwd_block(
                _rows(qf, i, c), _rows(kf, j, c), _rows(vf, j, c),
                _rows(dof, i, c), _rows(lse, i, c), _rows(delta, i, c),
                causal=cz, block_q=block_q, block_k=block_k,
                interpret=interpret, out_dtype=out_dtype,
                mxu_dtype=mxu_dtype)
            for acc, at, x in zip((dqs, dks, dvs), (i, j, j), part):
                acc[at] = x if acc[at] is None else acc[at] + x
        return tuple(jnp.concatenate(a, axis=1) for a in (dqs, dks, dvs))
    sm_scale = 1.0 / math.sqrt(d)
    lse = lse[:, :, None]          # (bh, t, 1): see _flash_specs
    delta = delta[:, :, None]
    g = bh // kf.shape[0]           # query heads a key/value head
    qspec, qfull, vec, vec_full = _flash_specs(block_q, d, t)
    dospec, dofull, _, _ = _flash_specs(block_q, dv_w, t)
    dkspec, _, _, _ = _flash_specs(block_k, d, t)
    dvspec, _, _, _ = _flash_specs(block_k, dv_w, t)
    kspec_b, kfull = _flash_kv_specs(block_k, d, t, g)
    vspec_b, vfull = _flash_kv_specs(block_k, dv_w, t, g)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_k=block_k,
                          mxu_dtype=mxu_dtype),
        out_shape=jax.ShapeDtypeStruct((bh, t, d),
                                       out_dtype or qf.dtype),
        grid=(bh, t // block_q),
        in_specs=[qspec, kfull, vfull, dospec, vec, vec],
        out_specs=qspec,
        interpret=interpret,
        name="cos_flash_bwd_dq",
        **_flash_compiler_params(dq_bytes(t), interpret),
    )(qf, kf, vf, dof, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          mxu_dtype=mxu_dtype),
        out_shape=(jax.ShapeDtypeStruct((bh, t, d),
                                        out_dtype or kf.dtype),
                   jax.ShapeDtypeStruct((bh, t, dv_w),
                                        out_dtype or vf.dtype)),
        grid=(bh, t // block_k),
        in_specs=[qfull, kspec_b, vspec_b, dofull, vec_full, vec_full],
        out_specs=(dkspec, dvspec),
        interpret=interpret,
        name="cos_flash_bwd_dkv",
        **_flash_compiler_params(dkv_bytes(t), interpret),
    )(qf, kf, vf, dof, lse, delta)
    if g > 1:
        # one dk, dv a query head: the group's sum is its key/value
        # head's gradient
        dk = dk.reshape(bh // g, g, t, d).sum(axis=1)
        dv = dv.reshape(bh // g, g, t, dv_w).sum(axis=1)
    return dq, dk, dv


def _flash_vjp_bwd(causal, block_q, block_k, interpret, mxu_dtype, res,
                   do):
    qf, kf, vf, out, lse = res
    bh, t, d = qf.shape
    dof = do.reshape(bh, t, vf.shape[-1])
    # delta = rowsum(dO ∘ O): cheap elementwise+reduce, XLA fuses it
    delta = jnp.sum(dof.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq, dk, dv = flash_bwd_block(qf, kf, vf, dof, lse, delta,
                                 causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret,
                                 mxu_dtype=mxu_dtype)
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
        return _online_softmax_step(q, kb, vb, m, l, acc,
                                    sm_scale=sm_scale, causal=causal,
                                    q_pos=q_pos, k_pos=k_pos)

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
