"""Pallas kernel parity tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.ops import route
from caffeonspark_tpu.ops.pallas_kernels import lrn_across_channels


def _xla_lrn(x, n=5, alpha=1e-4, beta=0.75, k=1.0):
    from jax import lax
    sq = x * x
    pad = n // 2
    sqp = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    s = lax.reduce_window(sqp, 0.0, lax.add, (1, n, 1, 1),
                          (1, 1, 1, 1), "VALID")
    return x / jnp.power(k + (alpha / n) * s, beta)


@pytest.mark.parametrize("shape", [(2, 8, 4, 4), (1, 96, 55, 55),
                                   (2, 5, 7, 9)])
def test_lrn_pallas_matches_xla(shape):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32) * 3)
    ref = _xla_lrn(x)
    got = lrn_across_channels(x, 5, 1e-4, 0.75, 1.0, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_lrn_pallas_alpha_beta_k():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(1, 6, 3, 3).astype(np.float32))
    ref = _xla_lrn(x, n=3, alpha=0.01, beta=0.5, k=2.0)
    got = lrn_across_channels(x, 3, 0.01, 0.5, 2.0, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("shape", [(2, 8, 4, 4), (1, 12, 9, 11)])
def test_lrn_pallas_grad_matches_xla(shape):
    """The fused VJP kernel must match autodiff through the XLA path
    (uses larger alpha so the scale term contributes meaningfully)."""
    import jax
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    dy = jnp.asarray(rng.randn(*shape).astype(np.float32))

    def f_ref(x):
        return jnp.sum(_xla_lrn(x, n=5, alpha=0.05, beta=0.75) * dy)

    def f_pallas(x):
        return jnp.sum(
            lrn_across_channels(x, 5, 0.05, 0.75, 1.0, True) * dy)

    g_ref = jax.grad(f_ref)(x)
    g_pal = jax.grad(f_pallas)(x)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=3e-4, atol=3e-5)


def test_lrn_pallas_fused_relu_matches_unfused():
    """fuse_relu=True must equal relu → lrn, forward AND grad (the
    grad includes the relu mask recomputed in the bwd kernel)."""
    import jax
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 8, 5, 7).astype(np.float32) * 2)
    dy = jnp.asarray(rng.randn(2, 8, 5, 7).astype(np.float32))

    def f_ref(x):
        return jnp.sum(_xla_lrn(jax.nn.relu(x), alpha=0.05) * dy)

    def f_fused(x):
        return jnp.sum(
            lrn_across_channels(x, 5, 0.05, 0.75, 1.0, True, True) * dy)

    np.testing.assert_allclose(
        np.asarray(lrn_across_channels(x, 5, 0.05, 0.75, 1.0, True,
                                       True)),
        np.asarray(_xla_lrn(jax.nn.relu(x), alpha=0.05)),
        rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(jax.grad(f_fused)(x)), np.asarray(jax.grad(f_ref)(x)),
        rtol=3e-4, atol=3e-5)


def test_bias_relu_lrn_matches_chain():
    """The generalized stem epilogue: bias_relu_lrn(x, b) must equal
    lrn(relu(x + b)) — forward, dx AND d_bias (the bias gradient is
    recovered as the channel sum of the kernel's dx)."""
    from caffeonspark_tpu.ops.pallas_kernels import (
        bias_relu_lrn_across_channels)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 8, 5, 7).astype(np.float32) * 2)
    b = jnp.asarray(rng.randn(8).astype(np.float32))
    dy = jnp.asarray(rng.randn(2, 8, 5, 7).astype(np.float32))

    def chain(x, b):
        xb = jax.nn.relu(x + b.reshape(1, -1, 1, 1))
        return _xla_lrn(xb, alpha=0.05)

    def f_ref(x, b):
        return jnp.sum(chain(x, b) * dy)

    def f_fused(x, b):
        return jnp.sum(bias_relu_lrn_across_channels(
            x, b, 5, 0.05, 0.75, 1.0, True) * dy)

    np.testing.assert_allclose(
        np.asarray(bias_relu_lrn_across_channels(x, b, 5, 0.05, 0.75,
                                                 1.0, True)),
        np.asarray(chain(x, b)), rtol=2e-5, atol=2e-6)
    g_ref = jax.grad(f_ref, argnums=(0, 1))(x, b)
    g_fus = jax.grad(f_fused, argnums=(0, 1))(x, b)
    np.testing.assert_allclose(np.asarray(g_fus[0]),
                               np.asarray(g_ref[0]),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(g_fus[1]),
                               np.asarray(g_ref[1]),
                               rtol=3e-4, atol=3e-5)


def test_bias_relu_lrn_xla_fallback_matches_kernel():
    """The off-TPU fallback (ops.layers routes through it) and the
    pallas kernel are the same math."""
    from caffeonspark_tpu.ops.pallas_kernels import (
        bias_relu_lrn_across_channels, xla_bias_relu_lrn)
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(1, 6, 4, 5).astype(np.float32))
    b = jnp.asarray(rng.randn(6).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(bias_relu_lrn_across_channels(x, b, 5, 1e-4, 0.75,
                                                 1.0, True)),
        np.asarray(xla_bias_relu_lrn(x, b, 5, 1e-4, 0.75, 1.0)),
        rtol=2e-5, atol=2e-6)


def test_int8_matmul_pallas_matches_xla(monkeypatch):
    """The tiled int8 kernel is EXACT vs the XLA int8 dot_general
    (int32 accumulation both ways)."""
    from caffeonspark_tpu.ops.pallas_kernels import int8_matmul
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    rng = np.random.RandomState(9)
    xq = jnp.asarray(rng.randint(-127, 128, (64, 256)).astype(np.int8))
    wq = jnp.asarray(rng.randint(-127, 128, (128, 256)).astype(np.int8))
    got = int8_matmul(xq, wq)
    ref = jax.lax.dot_general(xq, wq, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # non-tiling shapes take the XLA fallback — same result contract
    got2 = int8_matmul(xq[:50], wq[:100])
    np.testing.assert_array_equal(np.asarray(got2),
                                  np.asarray(ref[:50, :100]))


def test_int8_inner_product_tolerance():
    """Per-blob max-abs int8 forward: bounded relative error vs f32,
    and output dtype follows the activation."""
    from caffeonspark_tpu.ops.pallas_kernels import int8_inner_product
    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(32, 64).astype(np.float32) * 0.1)
    y8 = int8_inner_product(x, w)
    yf = x @ w.T
    assert y8.dtype == x.dtype
    rel = float(jnp.max(jnp.abs(y8 - yf)) / jnp.max(jnp.abs(yf)))
    assert 0 < rel < 0.05, rel
    # transpose layout (ip.transpose weights are (K, N))
    y8t = int8_inner_product(x, w.T, transpose=True)
    np.testing.assert_allclose(np.asarray(y8t), np.asarray(y8),
                               rtol=1e-6, atol=1e-6)


def test_lrn_pallas_bf16_io_f32_normalizer():
    """Mixed-precision training feeds the kernel bf16 activations; the
    normalizer must still be computed in f32.  In bf16 (eps ~ 8e-3)
    scale = 1 + (alpha/n)*sum(x^2) rounds away its significant digits
    and LRN silently degrades toward identity — so the kernel upcasts
    in VMEM.  Pin: bf16-in/bf16-out output matches the f32 reference
    within bf16 OUTPUT rounding (2^-8), far tighter than the identity
    gap this alpha produces."""
    rng = np.random.RandomState(3)
    xf = rng.randn(2, 8, 6, 6).astype(np.float32) * 3
    x16 = jnp.asarray(xf, jnp.bfloat16)
    ref = _xla_lrn(jnp.asarray(x16, jnp.float32))  # same rounded input
    got = lrn_across_channels(x16, 5, 1e-4, 0.75, 1.0, True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), rtol=1e-2, atol=1e-2)
    # and the normalization actually happened (output != identity)
    gap = np.max(np.abs(np.asarray(got, np.float32)
                        - np.asarray(x16, np.float32)))
    assert gap > 1e-2, "LRN degenerated to identity"


def _force_tiles(monkeypatch, tiles):
    """Run the flash kernels at given (block_q, block_k) instead of the
    ones `_flash_tiles` picks (which at test lengths is one tile a row):
    one pair for all three kernels, or one a kernel."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    if tiles is not None:
        monkeypatch.setattr(
            pk, "_flash_tiles", lambda kernel, *a, **kw: (
                tiles[kernel] if isinstance(tiles, dict) else tiles))


def _qkv(seed, b, h, g, t, d, dv, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, t, d), dtype),
            jnp.asarray(rng.randn(b, h // g, t, d), dtype),
            jnp.asarray(rng.randn(b, h // g, t, dv), dtype))


# the paths the tile-wise kernels added: a q tile taller than a k tile
# (several diagonal tiles a q tile) and the reverse, tiles chosen from
# the shape, 192 / 128-wide heads, g = 4 at 64-wide heads, operands
# cast once to bfloat16, bfloat16 inputs
_TILED = [
    # t, block, h, g, d, dv, tiles, mxu, dtype
    (512, 128, 2, 1, 32, 32, (256, 128), None, jnp.float32),
    (512, 128, 2, 1, 32, 32, (128, 256), None, jnp.float32),
    (512, 128, 2, 1, 32, 32, None, None, jnp.float32),
    (512, 128, 2, 1, 192, 128, (256, 128), None, jnp.float32),
    (512, 128, 8, 4, 64, 64, (128, 256), None, jnp.float32),
    (512, 128, 8, 4, 64, 64, (256, 128), jnp.bfloat16, jnp.float32),
    (512, 128, 2, 1, 192, 128, (128, 256), jnp.bfloat16, jnp.float32),
    (512, 128, 2, 1, 32, 32, (256, 128), None, jnp.bfloat16),
    (384, 128, 2, 1, 32, 32, {"fwd": (128, 384), "dq": (384, 128),
                              "dkv": (128, 384)}, None, jnp.float32),
]


def _tolerance(mxu, dtype):
    """float32 operands: the exact mode; one bfloat16 pass (operands
    or inputs) rounds the scores to 2^-9."""
    exact = mxu is None and dtype == jnp.float32
    return dict(rtol=2e-5, atol=2e-5) if exact else dict(rtol=3e-2,
                                                         atol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,block,h,g,d,dv,tiles,mxu,dtype", [
    (256, 128, 3, 1, 32, 32, None, None, jnp.float32),
    (64, 64, 3, 1, 32, 32, None, None, jnp.float32),
    (384, 128, 3, 1, 32, 32, None, None, jnp.float32),
    # grouped queries: h heads over h / g key/value heads of 64
    (256, 128, 8, 4, 64, 64, None, None, jnp.float32),
    (1024, 128, 8, 4, 64, 64, None, None, jnp.float32)] + _TILED)
def test_flash_attention_matches_reference(monkeypatch, causal, t, block,
                                           h, g, d, dv, tiles, mxu,
                                           dtype):
    """Flash fwd parity vs the einsum reference (interpret mode)."""
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    _force_tiles(monkeypatch, tiles)
    q, k, v = _qkv(0, 2, h, g, t, d, dv, dtype)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = attention(*f32, causal=causal)
    if g > 1:       # the einsum path itself against repeated heads
        rep = attention(f32[0], jnp.repeat(f32[1], g, axis=1),
                        jnp.repeat(f32[2], g, axis=1), causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(rep),
                                   rtol=2e-5, atol=2e-5)
    out = flash_attention(q, k, v, causal, block, block, True, mxu)
    assert out.dtype == dtype and out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), **_tolerance(mxu, dtype))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,block,h,g,d,dv,tiles,mxu,dtype", [
    (256, 128, 2, 1, 16, 16, None, None, jnp.float32),
    (256, 128, 8, 4, 64, 64, None, None, jnp.float32),
    (1024, 128, 8, 4, 64, 64, None, None, jnp.float32)] + _TILED)
def test_flash_attention_grads_match_reference(monkeypatch, causal, t,
                                               block, h, g, d, dv, tiles,
                                               mxu, dtype):
    """Flash bwd kernels (dq/dk/dv) vs jax.grad of the reference; with
    g > 1 dk and dv are the sums over each group of query heads."""
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    _force_tiles(monkeypatch, tiles)
    q, k, v = _qkv(1, 2, h, g, t, d, dv, dtype)

    def scal(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    gr = jax.grad(scal(lambda q, k, v: attention(
        q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1),
        causal=causal)), argnums=(0, 1, 2))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    gf = jax.grad(scal(lambda q, k, v: flash_attention(
        q, k, v, causal, block, block, True, mxu).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    tol = (dict(rtol=2e-4, atol=1e-5 * g)
           if mxu is None and dtype == jnp.float32
           else dict(rtol=5e-2, atol=5e-2 * g))
    for name, a, b_ in zip("qkv", gr, gf):
        assert a.shape == b_.shape and b_.dtype == dtype
        np.testing.assert_allclose(np.asarray(b_, np.float32),
                                   np.asarray(a), err_msg=f"d{name}",
                                   **tol)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,g,d,dv,room", [
    (8, 4, 64, 64, 1_500_000), (4, 1, 192, 128, 2_100_000)])
def test_flash_attention_in_chunks_matches_reference(monkeypatch, causal,
                                                     h, g, d, dv, room):
    """Rows too long for the default VMEM window go as pairs of chunks
    (`_flash_chunk`): the forward parts weighted by their share of the
    softmax sum, dq / dk / dv added over the pairs.  The window is made
    small here (`room` bytes over Mosaic's own scratch) so that 512
    rows are cut, forward in 2 chunks of 256 and backward in 4 of 128,
    as 8,192 rows of float32 operands are on the chip."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    from caffeonspark_tpu.parallel.sp import attention
    monkeypatch.setattr(pk, "_SCOPED_VMEM", pk._MOSAIC_ROOM + room)
    t, b = 512, 2
    fwd = pk._flash_chunk(t, 128, pk._fwd_block_bytes(d, dv, 4, 128))
    bwd = pk._flash_chunk(t, 128, pk._dq_block_bytes(d, dv, 4, 128),
                          pk._dkv_block_bytes(d, dv, 4, 128))
    assert (fwd, bwd) == (256, 128)
    q, k, v = _qkv(3, b, h, g, t, d, dv)

    def scal(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    ref = lambda q, k, v: attention(q, k, v, causal=causal)  # noqa: E731
    fl = lambda q, k, v: pk.flash_attention(                  # noqa: E731
        q, k, v, causal, 128, 128, True)
    np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gr = jax.grad(scal(ref), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(scal(fl), argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", gr, gf):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=2e-4, atol=1e-5 * g,
                                   err_msg=f"d{name}")
    # the counter: three pairs of chunks under the mask, four without
    plan = route.plans()["flash"][
        f"{b * h}x{t}x{d}/{dv} float32 g{g}{' causal' if causal else ''}"]
    assert plan["fwd"]["calls"] == (3 if causal else 4)
    assert plan["dq"]["calls"] == plan["dkv"]["calls"] == (
        10 if causal else 16)


# windows over one call's tiles: g = 7 (the first group that is no
# power of two), W smaller than, equal to and no multiple of the tile,
# a tile on both edges at once, W a row short of T
_WINDOWED = [
    # t, w, h, g, d, tiles
    (512, 64, 7, 7, 32, (128, 128)),
    (512, 128, 7, 7, 32, (128, 128)),
    (512, 100, 7, 7, 32, (128, 128)),
    (512, 300, 7, 7, 32, (128, 256)),
    (512, 300, 14, 7, 32, (256, 128)),
    (768, 200, 2, 1, 32, {"fwd": (128, 384), "dq": (384, 128),
                          "dkv": (128, 384)}),
    (512, 1, 2, 2, 32, (128, 128)),
    (512, 511, 2, 2, 32, (128, 128)),
    (256, 40, 7, 7, 64, None),
]


def _windowed_pair(t, w, h, g, d, seed=11):
    from caffeonspark_tpu.ops import pallas_kernels as pk
    from caffeonspark_tpu.parallel.sp import attention
    q, k, v = _qkv(seed, 1, h, g, t, d, d)
    ref = lambda q, k, v: attention(q, k, v, causal=True,   # noqa: E731
                                    window=w)
    fl = lambda q, k, v: pk.flash_attention(                 # noqa: E731
        q, k, v, True, 128, 128, True, None, w)
    return (q, k, v), ref, fl


def _assert_same_values_and_grads(args, ref, fl, g):
    def scal(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    np.testing.assert_allclose(np.asarray(fl(*args)),
                               np.asarray(ref(*args)),
                               rtol=2e-5, atol=2e-5)
    gr = jax.grad(scal(ref), argnums=(0, 1, 2))(*args)
    gf = jax.grad(scal(fl), argnums=(0, 1, 2))(*args)
    for name, a, b_ in zip("qkv", gr, gf):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=2e-4, atol=1e-5 * g,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("t,w,h,g,d,tiles", _WINDOWED)
def test_windowed_flash_matches_the_masked_einsum_path(monkeypatch, t, w,
                                                       h, g, d, tiles):
    """The three kernels under a window of w keys (row t sees
    t - w < s <= t) against `parallel/sp.attention(window=)`, values and
    gradients: the tiles before the window's edge and past the diagonal
    are skipped, the ones on either edge masked."""
    _force_tiles(monkeypatch, tiles)
    args, ref, fl = _windowed_pair(t, w, h, g, d)
    _assert_same_values_and_grads(args, ref, fl, g)


@pytest.mark.parametrize("w,fwd_calls,bwd_calls", [
    (128, 3, 7),    # half a forward chunk (the cell's case), one backward
    (256, 3, 9),    # a forward chunk; two backward chunks
    (384, 3, 10),   # one and a half forward chunks
    (100, 3, 7), (1, 2, 4), (129, 3, 7), (130, 3, 9)])
def test_windowed_flash_in_chunks_matches_reference(monkeypatch, w,
                                                    fwd_calls, bwd_calls):
    """T cut into chunks (forward 2 of 256, backward 4 of 128, as
    `test_flash_attention_in_chunks_matches_reference` cuts them) with W
    half a chunk, a chunk, one and a half: every pair carries its row
    offset in both bounds, a row of a later chunk that sees no column of
    an earlier one adds nothing, and the pairs a window apart or more
    are not run."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_SCOPED_VMEM", pk._MOSAIC_ROOM + 1_500_000)
    t, h, g, d = 512, 7, 7, 64
    assert pk._flash_chunk(t, 128, pk._fwd_block_bytes(d, d, 4, 128)) == 256
    assert pk._flash_chunk(t, 128, pk._dq_block_bytes(d, d, 4, 128),
                           pk._dkv_block_bytes(d, d, 4, 128)) == 128
    args, ref, fl = _windowed_pair(t, w, h, g, d, seed=12)
    route.forget("flash")
    _assert_same_values_and_grads(args, ref, fl, g)
    plan = route.plans()["flash"][f"{h}x{t}x{d}/{d} float32 g{g} causal "
                            f"window {w}"]
    assert plan["fwd"]["calls"] == fwd_calls
    assert plan["fwd"]["causal_calls"] == 3
    assert plan["dq"]["calls"] == plan["dkv"]["calls"] == bwd_calls
    assert plan["dq"]["causal_calls"] == 10


@pytest.mark.parametrize("w", [512, 513, 4096])
def test_a_window_of_all_the_rows_is_plain_causal_to_the_last_bit(w):
    """W >= T: the call is the causal call, kernels, plan and all."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    q, k, v = _qkv(13, 1, 7, 7, 512, 32, 32)

    def both(window):
        fn = lambda q, k, v: pk.flash_attention(             # noqa: E731
            q, k, v, True, 128, 128, True, None, window)
        return (fn(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)

    route.forget("flash")
    for a, b_ in zip(both(w), both(0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    assert list(route.plans()["flash"]) == ["7x512x32/32 float32 g7 causal"]
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, k, v, False, 128, 128, True, None, 64)


def test_window_spans_count_what_a_brute_force_counts():
    """`_window_span` / `_masked_tiles` / `_chunk_pairs` against a walk
    over every (row, column): a tile is visited iff it holds a visible
    score, masked iff it also holds a hidden one; at the cell's shape
    (16,384 rows as 2 chunks of 8,192, W 4,096, tiles 512 x 512) 3 pairs
    of 3 and 252 tiles of the causal triangle's 528 a head; at chunks of
    W the pairs two apart are dropped."""
    from caffeonspark_tpu.ops import pallas_kernels as pk

    def brute(t, bq, bk, w, off):
        r = np.arange(t)[:, None] + off
        c = np.arange(t)[None, :]
        seen = (c <= r) & (c > r - w)
        tiles = seen.reshape(t // bq, bq, t // bk, bk)
        some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
        return int((some & ~every).sum()), int(some.sum())

    for t, bq, bk in ((768, 128, 128), (768, 128, 384), (768, 384, 128),
                      (768, 256, 256)):
        for w in (1, 2, 100, 128, 129, 255, 256, 300, 511, 767):
            for off in (0, t, 2 * t):
                want = brute(t, bq, bk, w, off)
                for kernel in ("fwd", "dq", "dkv"):
                    assert pk._masked_tiles(kernel, t, bq, bk, off == 0, w,
                                            off) == want, (
                        kernel, t, bq, bk, w, off)
    assert pk._chunk_pairs(2, True, 4096, 8192) == pk._chunk_pairs(2, True)
    counts = [pk._masked_tiles("fwd", 8192, 512, 512, cz, 4096,
                               (i - j) * 8192)
              for i, j, cz in pk._chunk_pairs(2, True, 4096, 8192)]
    assert counts == [(24, 108), (8, 36), (24, 108)]
    assert sum(pk._masked_tiles("fwd", 8192, 512, 512, cz)[1]
               for _, _, cz in pk._chunk_pairs(2, True)) == 528
    # chunks of W: in the pair next to the diagonal row r sees the
    # columns above r; the pairs further off hold nothing
    assert pk._chunk_pairs(4, True, 4096, 4096) == [
        (0, 0, True), (1, 0, False), (1, 1, True), (2, 1, False),
        (2, 2, True), (3, 2, False), (3, 3, True)]
    assert len(pk._chunk_pairs(4, True, 4097, 4096)) == 7
    assert len(pk._chunk_pairs(4, True, 4098, 4096)) == 9
    assert pk._chunk_pairs(2, True, 1, 256) == [(0, 0, True), (1, 1, True)]


def test_flash_chunk_keeps_long_rows_inside_the_default_window():
    """No flash call asks for a VMEM window: rows whose blocks do not
    fit the default one are cut.  In bfloat16 operands (what both
    language-model cells run) 8,192 rows of 64-wide heads and 4,096 of
    192 / 128-wide ones are one call; float32 operands take twice the
    room and are cut where bfloat16 ones are not."""
    from caffeonspark_tpu.ops import pallas_kernels as pk

    def chunks(t, d, dv, isz):
        return (pk._flash_chunk(t, 128, pk._fwd_block_bytes(d, dv, isz, 128)),
                pk._flash_chunk(t, 128, pk._dq_block_bytes(d, dv, isz, 128),
                                pk._dkv_block_bytes(d, dv, isz, 128)))

    assert chunks(8192, 64, 64, 2) == (8192, 8192)     # lfm2's
    assert chunks(4096, 64, 64, 2) == (4096, 4096)
    assert chunks(4096, 192, 128, 2) == (4096, 4096)   # kanana2's
    assert chunks(1024, 64, 64, 4) == (1024, 1024)
    assert chunks(8192, 64, 64, 4) == (4096, 4096)
    assert chunks(8192, 192, 128, 4) == (4096, 4096)
    assert chunks(16384, 192, 128, 2) == (8192, 8192)
    assert chunks(8192, 256, 256, 2) == (4096, 4096)   # qwen3next's
    # a length no halving brings inside is refused, not sent to a
    # window XLA does not keep free
    with pytest.raises(ValueError, match="cannot be halved"):
        pk._flash_chunk(8192 + 128, 128,
                        pk._fwd_block_bytes(64, 64, 4, 128))
    assert pk._chunk_pairs(2, True) == [(0, 0, True), (1, 0, False),
                                        (1, 1, True)]
    assert len(pk._chunk_pairs(2, False)) == 4


@pytest.mark.parametrize("b,h,hkv,t,d,dv", [
    (2, 32, 32, 4096, 192, 128),    # kanana2.train_packed4k
    (2, 32, 8, 4096, 64, 64),
    (1, 32, 8, 8192, 64, 64),       # lfm2.train_packed8k
    (1, 16, 2, 8192, 256, 256),     # qwen3next.train_packed8k
    (2, 4, 4, 256, 32, 32), (1, 2, 2, 384, 64, 64),
    (1, 2, 2, 3072, 128, 128)])
@pytest.mark.parametrize("isz", [2, 4])
def test_flash_tiles_follow_from_the_shape(b, h, hkv, t, d, dv, isz):
    """`_flash_tiles` is a pure function of (kernel, rows, widths,
    operand size): its tiles divide the rows, are multiples of the
    floor and never under it, and the call they make fits the default
    VMEM window by the same arithmetic `_flash_chunk` cuts rows by."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    floor = (pk.BLOCK_Q, pk.BLOCK_K)
    for kernel, need in pk._BLOCK_BYTES.items():
        c = pk._flash_chunk(t, 128, need(d, dv, isz, *floor))
        tiles = pk._flash_tiles(kernel, c, d, dv, isz)
        assert tiles == pk._flash_tiles(kernel, c, d, dv, isz, floor)
        for n, lo in zip(tiles, floor):
            assert n >= lo and n % lo == 0 and c % n == 0, (kernel, tiles)
        assert pk._flash_window(
            need(d, dv, isz, *tiles)(c)) <= pk._SCOPED_VMEM
        # wider than the floor wherever the rows allow it: the point
        assert max(tiles) > 128 or c == 128, (kernel, tiles)
        if isz == 2 and t >= 4096 and d < 256:  # kanana2, lfm2: one call
            assert c == t
    # a floor above the rows' own tiles is kept (the ring's whole-shard
    # blocks), and where not even the floor fits there is no answer
    assert pk._flash_tiles("fwd", 64, 32, 32, 4, (64, 64)) == (64, 64)
    assert pk._flash_tiles("dkv", 1 << 16, 128, 128, 4) is None


@pytest.mark.parametrize("t,d,dv,chunk,fwd,dq,dkv", [
    # what the parent of PR 36 plans for the two accepted cells: one
    # call a kernel, 512 x 512 tiles, no window
    (4096, 192, 128, 4096, (512, 512), (512, 512), (512, 512)),  # kanana2
    (8192, 64, 64, 8192, (512, 512), (512, 512), (512, 512)),    # lfm2
    # qwen3next.train_packed8k: k and v of one key/value head are 2 x 4
    # MiB of bfloat16, so the rows go as pairs of chunks of 4,096
    (8192, 256, 256, 4096, (512, 512), (256, 512), (512, 256)),
])
def test_flash_plans_of_the_language_model_cells(t, d, dv, chunk, fwd, dq,
                                                 dkv):
    """The plan of each language-model cell's attention shape, letter
    for letter: a change to `_flash_tiles`, `_lanes` or `_flash_chunk`
    for one model's sake shows here before it shows as another cell's
    rate.  Every call fits the default window (no call asks for one)."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    floor = (pk.BLOCK_Q, pk.BLOCK_K)
    need = {k: f(d, dv, 2, *floor) for k, f in pk._BLOCK_BYTES.items()}
    assert pk._flash_chunk(t, 128, need["fwd"]) == chunk
    assert pk._flash_chunk(t, 128, need["dq"], need["dkv"]) == chunk
    for kernel, tiles in (("fwd", fwd), ("dq", dq), ("dkv", dkv)):
        assert pk._flash_tiles(kernel, chunk, d, dv, 2) == tiles, kernel
        assert pk._flash_window(pk._BLOCK_BYTES[kernel](
            d, dv, 2, *tiles)(chunk)) <= pk._SCOPED_VMEM


def test_flash_plans_count_the_masked_tiles():
    """The counter: per call shape lowered, each kernel's tiles, the
    calls an attention takes, and the share of the score tiles it
    visits that run the masked body = diagonal tiles / tiles visited.
    Static, written while the program is traced."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    # 4 q tiles of 128 over 2 k tiles of 256: q tiles 0, 1 see k tile 0
    # (on the diagonal), 2 and 3 see tile 0 whole and tile 1 on it
    assert pk._masked_tiles("fwd", 512, 128, 256, True) == (4, 6)
    assert pk._masked_tiles("dq", 512, 128, 256, True) == (4, 6)
    # the same tiles seen from the k tiles' programs
    assert pk._masked_tiles("dkv", 512, 128, 256, True) == (4, 6)
    # a q tile of 256 over k tiles of 128 lies on two diagonal tiles
    assert pk._masked_tiles("fwd", 512, 256, 128, True) == (4, 6)
    assert pk._masked_tiles("dkv", 512, 256, 128, True) == (4, 6)
    assert pk._masked_tiles("fwd", 512, 128, 128, True) == (4, 10)
    assert pk._masked_tiles("fwd", 512, 128, 128, False) == (0, 16)
    # brute force: a tile is masked iff it holds a score above the
    # diagonal and one at or under it
    for bq, bk in ((128, 384), (384, 128), (256, 256), (128, 128)):
        t = 768
        masked = visited = 0
        for i in range(t // bq):
            for j in range(t // bk):
                lowest_row, top_row = (i + 1) * bq - 1, i * bq
                if j * bk <= lowest_row:
                    visited += 1
                    masked += (j + 1) * bk - 1 > top_row
        for kernel in ("fwd", "dq", "dkv"):
            assert pk._masked_tiles(kernel, t, bq, bk, True) == (
                masked, visited), (kernel, bq, bk)
    q, k, v = _qkv(7, 1, 2, 1, 512, 32, 32)
    route.forget("flash")
    jax.grad(lambda q: jnp.sum(pk.flash_attention(
        q, k, v, True, 128, 128, True)))(q)
    (shape, plan), = route.plans()["flash"].items()
    assert shape == "2x512x32/32 float32 g1 causal"
    assert set(plan) == {"fwd", "dq", "dkv"}
    for kernel, p in plan.items():
        tiles = pk._flash_tiles(kernel, 512, 32, 32, 4)
        assert (p["block_q"], p["block_k"]) == tiles and p["calls"] == 1
        m, n = pk._masked_tiles(kernel, 512, *tiles, True)
        assert p["masked_tile_share"] == round(m / n, 4)


def test_train_job_reports_flash_plans():
    """What the first step's flash calls were lowered to rides in the
    metrics the -train job prints at shutdown, as `info.flash`."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.ops import pallas_kernels as pk
    from caffeonspark_tpu.processor import CaffeProcessor

    class Job:
        metrics = PipelineMetrics()

    route.forget()      # every `info.<kind>` rides the same route
    CaffeProcessor._note_lowering_plans(Job)       # no attention: nothing
    assert "info" not in Job.metrics.summary()
    q, k, v = _qkv(8, 1, 4, 2, 256, 64, 64)
    pk.flash_attention(q, k, v, True, interpret=True,
                       mxu_dtype=jnp.bfloat16)
    CaffeProcessor._note_lowering_plans(Job)
    assert Job.metrics.summary()["info"]["flash"] == {
        "4x256x64/64 bfloat16 g2 causal": {"fwd": {
            "block_q": 256, "block_k": 256, "calls": 1,
            "masked_tile_share": 1.0}}}


def test_flash_attention_rejects_indivisible_t():
    """T not divisible by the blocks must fail LOUDLY: a truncated
    pallas grid would silently return uninitialized tail rows
    (round-4 advisor).  Both the forward and the grad path hit the
    guard (they share _flash_fwd_call)."""
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 192, 16), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, q, q, False, 128, 128, True)
    with pytest.raises(ValueError, match="divisible"):
        jax.grad(lambda x: jnp.sum(
            flash_attention(x, x, x, False, 128, 128, True)))(q)


def test_flash_attention_bf16_inputs():
    """bf16 activations (the mixed-precision path): f32 accumulation
    inside the kernel keeps error at bf16 resolution."""
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    rng = np.random.RandomState(2)
    b, h, t, d = 1, 2, 128, 32
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
    ref = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=True)
    out = flash_attention(q, k, v, True, 128, 128, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_flash_suppressed_under_multi_device_mesh(monkeypatch):
    """The flash dispatch must stay off while tracing multi-device
    steps (a pallas_call is opaque to the GSPMD partitioner) and on
    for single-device ones."""
    from caffeonspark_tpu.ops import layers as L
    import caffeonspark_tpu.ops.pallas_kernels as pk
    calls = []
    monkeypatch.setattr(route, "on_tpu", lambda: True)
    monkeypatch.setattr(pk, "flash_attention",
                        lambda q, *a, **k: calls.append(1) or q)
    monkeypatch.delenv("COS_DISABLE_FLASH", raising=False)
    q = jnp.zeros((1, 1, 128, 8), jnp.float32)
    L._attention_dispatch(q, q, q, causal=True)
    assert calls, "flash must engage when allowed"
    calls.clear()
    with route.suppress_flash():
        L._attention_dispatch(q, q, q, causal=True)
    assert not calls, "flash must be suppressed inside the guard"

    # ParallelSolver routes every multi-device mesh through flash_mesh:
    # dp/tp meshes get the per-block kernel, sp meshes the fused ring
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = NetParameter.from_text("""
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 4 width: 4 } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 2 } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }""")
    for mesh_kw in ({"dp": 8}, {"dp": 2, "sp": 4}):
        s = Solver(SolverParameter.from_text(
            "base_lr: 0.01 random_seed: 1"), npm)
        ps = ParallelSolver(s, build_mesh(**mesh_kw))
        probe = ps._install_flash_mesh(
            lambda: (route._SUPPRESS, len(route._MESH)))
        assert probe() == (0, 1), (
            f"{mesh_kw}: mesh must install the shard_map route")
    assert route._SUPPRESS == 0 and not route._MESH


def test_flash_mesh_dispatch_fallbacks(monkeypatch):
    """Mesh-route tiling guards: shapes that don't tile the mesh
    (heads % tp != 0, batch % dp != 0, T % sp != 0, or an ineligible
    local extent) fall back to einsum attention — no crash, no kernel
    dispatch, same values."""
    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.parallel import build_mesh
    from caffeonspark_tpu.parallel.sp import attention
    import caffeonspark_tpu.ops.pallas_kernels as pk
    import caffeonspark_tpu.parallel.sp as sp_mod

    kernel_calls = []
    monkeypatch.setattr(route, "on_tpu", lambda: True)
    monkeypatch.setattr(pk, "flash_attention",
                        lambda *a, **k: kernel_calls.append(1) or a[0])
    monkeypatch.setattr(sp_mod, "_ring_attention_local",
                        lambda *a, **k: kernel_calls.append(1) or a[0])
    monkeypatch.delenv("COS_DISABLE_FLASH", raising=False)

    rng = np.random.RandomState(0)
    cases = [
        # (mesh, q shape (B, H, T, D)) — each violates EXACTLY one guard
        (build_mesh(dp=4, tp=2), (4, 3, 128, 8)),    # H=3 % tp=2 only
        (build_mesh(dp=8), (3, 2, 128, 8)),          # B=3 % dp=8
        (build_mesh(dp=2, sp=4), (2, 2, 102, 8)),    # T=102 % sp=4
        (build_mesh(dp=2, sp=4), (2, 2, 52, 8)),     # t_local=13 % 8
    ]
    for mesh, shape in cases:
        q = jnp.asarray(rng.randn(*shape), jnp.float32)
        with route.flash_mesh(mesh):
            out = L._attention_dispatch(q, q, q, causal=True)
        assert not kernel_calls, (mesh.shape, shape)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(attention(q, q, q, causal=True)),
            rtol=2e-4, atol=2e-5, err_msg=str((dict(mesh.shape), shape)))


# ------------------------------------------------ the grouped products

def _rounded(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _rounded_straight_through(x):
    return _rounded(x)


_rounded_straight_through.defvjp(lambda x: (_rounded(x), None),
                                 lambda _, g: (g,))


@jax.custom_vjp
def _cotangent_rounded(y):
    return y


_cotangent_rounded.defvjp(lambda y: (y, None), lambda _, g: (_rounded(g),))


def _ragged_dot_of_rounded_operands(kernels, sizes, rows, prec):
    """`layers._moe_products`' contract in XLA: every product one
    bfloat16 pass of its operands (the cotangent is an operand of the
    two transposed products) accumulated in float32."""
    from jax import lax
    return lambda a, w, out=False: _cotangent_rounded(lax.ragged_dot(
        _rounded_straight_through(a), _rounded_straight_through(w), sizes))


# scaled-down expert passes of the five language cells: (tokens, top k,
# D, hidden, gated, assignments a held group, assignments held elsewhere,
# rows a pass, the pass's first row, the tiles forced on the kernels or
# None for `gmm_plan`'s)
_GROUPED = {
    # a hidden width that is no multiple of 128 under blocks of 128 lanes
    # (the masked last block, both as W's lanes and as dW's), ungated
    # relu2, groups that end inside a tile, rows past the last group
    "nemotron3nano": (128, 3, 256, 208, "relu2", (70, 45, 100, 61), 108,
                      384, 0, (128, 128, (128, 128))),
    # gated SiLU, every group inside one tile or across two
    "lfm2": (128, 2, 128, 96, "silu", (30, 34, 29, 35, 31, 33, 28, 36), 0,
             256, 0, None),
    # an empty group among 16, its neighbours sharing a tile
    "kanana2": (128, 3, 128, 48, "silu",
                (20, 0, 25, 18, 22, 0, 0, 30, 17, 23, 19, 21, 24, 16, 26, 15),
                108, 384, 0, None),
    # 32 small groups, many to a tile, the last ones empty
    "qwen3next": (64, 4, 128, 32, "silu", (7,) * 28 + (0,) * 4, 60, 256, 0,
                  (32, 128, (128, 32))),
    # gated by ReLU, groups longer than a tile; the second pass starts
    # inside group 1 (`lo` 256 > its first row 200) and ends past the
    # last held row
    "smallthinker": (256, 2, 160, 64, "relu", (200, 150, 100), 62, 256, 256,
                     None),
    # a pass that holds no held row at all: nothing is added, every
    # gradient is zero, and what the kernels left is in no sum
    "no held row": (64, 2, 128, 32, "silu", (40, 30), 58, 128, 128, None),
}


@pytest.mark.parametrize("cell", sorted(_GROUPED))
def test_grouped_products_match_ragged_dot_of_rounded_operands(monkeypatch,
                                                               cell):
    """The kernel form (`cos_gmm_rows`, `cos_gmm_rows_t`,
    `cos_gmm_weights`, interpret mode) against `lax.ragged_dot` on
    operands rounded to bfloat16: a product alone, value and both
    cotangents, to float32 summation order; and a pass of `_moe_pass`,
    value and the cotangents of the rows, the gates and every weight."""
    from jax import lax
    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.ops import pallas_kernels as pk
    n, k, d, h, gated, held, absent, rows, lo, forced = _GROUPED[cell]
    groups = len(held)
    assert sum(held) + absent == k * n
    keys = jax.random.split(jax.random.key(len(cell)), 8)
    group = jax.random.permutation(keys[0], jnp.repeat(
        jnp.arange(groups + 1), jnp.asarray(held + (absent,)),
        total_repeat_length=k * n))
    order = jnp.argsort(group, stable=True)
    ends = jnp.cumsum(jnp.asarray(held, jnp.int32))
    starts, total = ends - jnp.asarray(held, jnp.int32), ends[-1]
    n_pass = -(-k * n // rows)
    order = jnp.pad(order, (0, n_pass * rows - k * n))
    xf = jax.random.normal(keys[1], (n, d), jnp.float32)
    gates = jax.random.uniform(keys[2], (k * n,), jnp.float32, 0.1, 1.0)
    w_in = tuple(jax.random.normal(kk, (groups, d, h)) * d ** -0.5
                 for kk in keys[3:3 + (1 if gated == "relu2" else 2)])
    w_out = jax.random.normal(keys[5], (groups, h, d)) * h ** -0.5
    acc = jax.random.normal(keys[6], (n, d), jnp.float32)
    tiles = (pk.GmmTiles(*forced), pk.GmmTiles(*forced)) if forced else (
        pk.gmm_plan(rows, d, h, groups), pk.gmm_plan(rows, h, d, groups))
    assert all(tiles) and pk.GMM_ROW_TILE == 128

    # a product alone, rows past the last group left out of the cotangent
    sizes = jnp.clip(ends - lo, 0, rows) - jnp.clip(starts - lo, 0, rows)
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    a = jnp.where(live, _rounded(jax.random.normal(keys[7], (rows, d))), 0)
    dy = jnp.where(live, _rounded(jax.random.normal(keys[6], (rows, h))), 0)
    w = _rounded(w_in[0])
    visits = pk.gmm_visits(sizes, rows)
    y, pull = jax.vjp(lambda a, w: pk.grouped_product(
        a, w, visits, tiles[0], True), a, w)
    y_ref, pull_ref = jax.vjp(lambda a, w: lax.ragged_dot(a, w, sizes), a, w)
    for name, got, ref in zip(("y", "da", "dw"),
                              (jnp.where(live, y, 0),) + tuple(
                                  jnp.where(live, g, 0) if g.ndim == 2 else g
                                  for g in pull(dy)),
                              (y_ref,) + pull_ref(dy)):
        np.testing.assert_allclose(got, ref, rtol=0, err_msg=name,
                                   atol=1e-5 * float(jnp.abs(ref).max() + 1))

    # a pass
    def a_pass(kernels):
        def f(acc, xf, gates, w_in, w_out):
            return L._moe_pass(acc, lo, xf, gates, w_in, w_out, order,
                               starts, ends, total, rows, k, gated, None,
                               kernels)
        out, pull = jax.vjp(f, acc, xf, gates, w_in, w_out)
        return (out,) + tuple(jax.tree.leaves(pull(jnp.cos(out))))

    got = a_pass(L._MoeKernels(*tiles, True))
    monkeypatch.setattr(L, "_moe_products", _ragged_dot_of_rounded_operands)
    ref = a_pass(None)
    names = ["y", "dacc", "dx", "dgates"] + [f"dw{i}" for i in range(3)]
    moved = False
    for name, g, r in zip(names, got, ref):
        assert np.isfinite(g).all(), name
        # a bfloat16 rounding of the hidden activation may fall the
        # other way: 2^-9 of an element, far under one wrong row
        np.testing.assert_allclose(g, r, rtol=0, err_msg=name,
                                   atol=4e-3 * float(jnp.abs(r).max() + 1))
        moved |= name.startswith("dw") and bool(jnp.any(g != 0))
    assert moved == (cell != "no held row")
