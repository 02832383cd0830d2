"""On-chip Pallas kernel parity: every kernel of ops/pallas_kernels.py
compiled by Mosaic (interpret=False) at a shape the zoo uses, against
its XLA reference.  Interpret mode (tests/test_pallas.py) pins the
exact f32 semantics; this proves the lowering.

    COS_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py \
        tests/test_tpu_train.py

One plain process.  Without COS_TPU_TESTS=1 the suite skips (conftest
holds JAX to the CPU); with it, a machine without a TPU fails.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.usefixtures("tpu")


def _xla_lrn(x, n=5, alpha=1e-4, beta=0.75, k=1.0):
    import jax.numpy as jnp
    from jax import lax
    sq = x * x
    pad = n // 2
    sqp = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    s = lax.reduce_window(sqp, 0.0, lax.add, (1, n, 1, 1),
                          (1, 1, 1, 1), "VALID")
    return x / jnp.power(k + (alpha / n) * s, beta)


@pytest.mark.parametrize("shape", [(2, 96, 13, 13),   # CaffeNet norm1-ish
                                   (1, 7, 5, 9)])     # ragged, pad path
def test_lrn_forward_parity_on_tpu(shape):
    import jax
    from caffeonspark_tpu.ops.pallas_kernels import lrn_across_channels
    rng = np.random.RandomState(0)
    x = rng.randn(*shape).astype(np.float32)
    got = np.asarray(
        jax.jit(lambda a: lrn_across_channels(a, 5, 1e-4, 0.75, 1.0))(x))
    want = np.asarray(jax.device_get(jax.jit(_xla_lrn)(x)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_lrn_vjp_parity_on_tpu():
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import lrn_across_channels
    rng = np.random.RandomState(1)
    x = rng.randn(2, 16, 9, 11).astype(np.float32)
    w = rng.randn(*x.shape).astype(np.float32)  # non-uniform cotangent

    def loss_pallas(a):
        return jnp.sum(lrn_across_channels(a, 5, 1e-4, 0.75, 1.0) * w)

    def loss_xla(a):
        return jnp.sum(_xla_lrn(a) * w)

    gp = np.asarray(jax.device_get(jax.jit(jax.grad(loss_pallas))(x)))
    gx = np.asarray(jax.device_get(jax.jit(jax.grad(loss_xla))(x)))
    np.testing.assert_allclose(gp, gx, rtol=2e-4, atol=2e-5)


def test_flash_attention_parity_on_tpu():
    """Flash attention fwd on the REAL compiler vs the einsum path
    (interpret mode only proves semantics; this proves the Mosaic
    lowering)."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 4, 512, 64
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    for causal in (False, True):
        got = np.asarray(jax.device_get(jax.jit(
            lambda a, b_, c: flash_attention(a, b_, c, causal))(q, k, v)))
        want = np.asarray(jax.device_get(jax.jit(
            lambda a, b_, c: attention(a, b_, c, causal=causal))(q, k, v)))
        # tolerance is the MXU default-precision floor: on the real
        # chip both paths multiply f32 operands in bf16 MXU passes and
        # round differently.  Measured on TPU v5 lite at this shape:
        # non-causal — XLA default-vs-highest spread 3.5e-3,
        # flash-vs-xla-default 9.3e-4; causal — flash-vs-xla-default
        # violations up to 6.5e-3 (sharper softmax rows amplify the
        # score rounding).  1e-2 is ~1.5x headroom over the worst
        # observed causal spread.  Exact f32 semantics are pinned by
        # the interpret-mode tests (tests/test_pallas.py).
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_flash_attention_vjp_parity_on_tpu():
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    rng = np.random.RandomState(1)
    b, h, t, d = 1, 2, 256, 32
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def scal(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    gf = jax.jit(jax.grad(scal(
        lambda a, b_, c: flash_attention(a, b_, c, True)),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(scal(
        lambda a, b_, c: attention(a, b_, c, causal=True)),
        argnums=(0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("qkv", gr, gf):
        # MXU default-precision floor (see the fwd parity test's
        # measured spreads); empirically the grads at this smaller
        # shape stay within 5e-3 on chip
        np.testing.assert_allclose(
            np.asarray(jax.device_get(b_)),
            np.asarray(jax.device_get(a)), rtol=5e-3, atol=5e-3,
            err_msg=f"d{name}")


@pytest.mark.parametrize("b,h,hkv,t,d,dv,sub,window,f32", [
    (2, 32, 32, 4096, 192, 128, 4, 0, False),   # kanana2.train_packed4k
    (1, 32, 8, 8192, 64, 64, 4, 0, False),      # lfm2.train_packed8k: g = 4
    (1, 16, 2, 8192, 256, 256, 4, 0, False),    # qwen3next...8k: g = 8
    # smallthinker.train_packed16k: g = 7, two chunks of 8,192; the
    # window layers (W half a chunk) and the global layer, as the
    # dispatch calls them, and at float32 operands against an einsum
    # path at "highest" (values and all three gradients)
    (1, 28, 4, 16384, 128, 128, 1, 4096, False),
    (1, 28, 4, 16384, 128, 128, 1, 0, False),
    (1, 28, 4, 16384, 128, 128, 1, 4096, True),
    (1, 28, 4, 16384, 128, 128, 1, 0, True),
])
def test_flash_attention_real_shapes_parity_on_tpu(b, h, hkv, t, d, dv,
                                                   sub, window, f32):
    """Forward and VJP of the flash kernels at the language-model
    cells' real shapes, as `_attention_dispatch` calls them (float32
    blobs, bfloat16 operands, causal, tiles from the shape; `f32`:
    float32 operands, the einsum path at "highest"), against
    the einsum path.  The (T, T) scores of all heads do not fit the
    chip beside their gradients, so the einsum path runs the first
    `sub` query heads (and the key/value heads they read; a whole group
    where g > `sub`, `sub` heads a call): heads are
    independent, and the loss is a sum over them.  A new lowering runs
    under a watchdog (PERF.md section 7): a call that never ends
    kills the process instead of holding the machine."""
    import faulthandler
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention
    g = h // hkv
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, hkv, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, t, dv), jnp.float32)

    def scal(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    fl = lambda q, k, v: flash_attention(              # noqa: E731
        q, k, v, True, mxu_dtype=None if f32 else jnp.bfloat16,
        window=window)

    def ref(q, k, v):
        with jax.default_matmul_precision(
                "highest" if f32 else "default"):
            return attention(q, k, v, causal=True, window=window)

    faulthandler.dump_traceback_later(240, exit=True)
    try:
        out = jax.jit(fl)(q, k, v)
        gf = jax.jit(jax.grad(scal(fl), argnums=(0, 1, 2)))(q, k, v)
        n_q = max(sub, g)               # whole groups of query heads
        n_kv = n_q // g
        ks, vs = k[:, :n_kv], v[:, :n_kv]
        want, gr = [], None
        for i in range(0, n_q, sub):    # `sub` query heads a call
            kv = slice(i // g, max(i // g + 1, (i + sub) // g))
            qs = q[:, i:i + sub]
            want.append(jax.jit(ref)(qs, ks[:, kv], vs[:, kv]))
            gq, gk, gv = jax.jit(jax.grad(scal(ref), argnums=(0, 1, 2)))(
                qs, ks[:, kv], vs[:, kv])
            zk, zv = jnp.zeros_like(ks), jnp.zeros_like(vs)
            part = [gq, zk.at[:, kv].set(gk), zv.at[:, kv].set(gv)]
            gr = part if gr is None else [
                jnp.concatenate([gr[0], part[0]], axis=1),
                gr[1] + part[1], gr[2] + part[2]]
        want = jnp.concatenate(want, axis=1)
        got = [np.asarray(jax.device_get(x)) for x in (
            out[:, :n_q], gf[0][:, :n_q], gf[1][:, :n_kv],
            gf[2][:, :n_kv])]
        want = [np.asarray(jax.device_get(x)) for x in [want] + gr]
    finally:
        faulthandler.cancel_dump_traceback_later()
    assert all(np.isfinite(x).all() for x in got)
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        # both sides multiply in one bfloat16 pass and round
        # differently (the fwd parity test above has the measured
        # spreads); with g > 1 a key/value head's gradient sums the
        # heads of its group, of which the einsum side ran them all
        err = np.abs(a - w).max() / max(np.abs(w).max(), 1e-6)
        print(f"flash real shape {b}x{h}/{hkv}x{t}x{d}/{dv} window "
              f"{window}{' float32 operands' if f32 else ''} {name}: "
              f"max gap / max {err:.3e}")
        # float32 operands are no exact mode on the chip: Mosaic
        # multiplies them in bfloat16 passes at the default precision
        # too (out read 3.6e-3 against the einsum path at "highest",
        # my chip run, PR 40, call 1: the spread the forward parity
        # test above measured between XLA's default and highest)
        assert err < 2e-2, (name, err)


def test_gated_delta_rule_kernels_real_shape_on_tpu():
    """`qwen3next.train_packed8k`'s rule (1, 16 / 32 heads, 8,192,
    128 / 128, chunk 64): the Mosaic kernels, forward and all five
    gradients, against the XLA form, both float32 at HIGHEST (another
    order of the same products).  Under a watchdog, as every new
    lowering."""
    import faulthandler
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.ops import pallas_kernels as pk
    b, hk, r, t, dk, dv, c = 1, 16, 2, 8192, 128, 128, 64
    rng = np.random.RandomState(7)
    q, k = (rng.randn(b, hk, t, dk) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(b, hk, r, t, dv)
    # A = uniform(1e-3, 16) in the logarithm, as the layer's filler
    rate = np.exp(rng.uniform(np.log(1e-3), np.log(16.0), (1, hk, r, 1)))
    g = -rate * np.log1p(np.exp(rng.randn(b, hk, r, t) + 1.0)) * 0.1
    beta = 1.0 / (1.0 + np.exp(-rng.randn(b, hk, r, t)))
    args = [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]
    w = jnp.asarray(rng.randn(b, hk, r, t, dv), jnp.float32)

    def both(rule):
        return (jax.jit(lambda *a: rule(*a, c))(*args),
                jax.jit(jax.grad(lambda *a: jnp.sum(rule(*a, c) * w),
                                 argnums=(0, 1, 2, 3, 4)))(*args))

    assert pk.gdn_rule_tiles(r, c, dk, dv)
    faulthandler.dump_traceback_later(300, exit=True)
    try:
        got, got_grads = jax.device_get(both(pk.gated_delta_rule_kernels))
        want, want_grads = jax.device_get(both(L.gated_delta_rule_xla))
    finally:
        faulthandler.cancel_dump_traceback_later()
    for name, a, x in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                          [got] + list(got_grads),
                          [want] + list(want_grads)):
        assert a.shape == x.shape and np.isfinite(a).all(), name
        err = np.abs(a - x).max() / max(np.abs(x).max(), 1e-12)
        print(f"gated delta rule {b}x{hk}/{hk * r}x{t}x{dk}/{dv} "
              f"{name}: max gap / max {err:.3e}")
        assert err < 1e-4, (name, err)


# CaffeNet's two LRN inputs at a reduced batch: pool1 -> norm1 and
# pool2 -> norm2 (zoo.caffenet); hw 729 and 169 both take the pad path
_NORM_SHAPES = [(32, 96, 27, 27), (32, 256, 13, 13)]


def test_mamba2_scan_kernels_real_shape_on_tpu():
    """`nemotron3nano.train_packed8k`'s scan (1 x 8,192, 64 heads of 64
    over 8 groups of 128 states, chunk 128, [u | B | C] 6,144 wide with
    the skip): the Mosaic kernels, forward and the four gradients,
    against the XLA form, both float32 at HIGHEST (another order of the
    same products; A's and dt's gradients sum terms of both signs over
    the row).  dt and A as the layer's fillers draw them.  Under a
    watchdog, as every new lowering."""
    import faulthandler
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.ops import pallas_kernels as pk
    t, b, h, p, g, n, c = 8192, 1, 64, 64, 8, 128, 128
    rng = np.random.RandomState(11)
    x = rng.randn(t, h * p + 2 * g * n)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, h))) \
        * np.exp(0.5 * rng.randn(t, h))
    a = -np.arange(1.0, h + 1)
    d = rng.randn(h)
    args = [jnp.asarray(v, jnp.float32) for v in (x, dt, a, d)]
    w = jnp.asarray(rng.randn(t, h * p), jnp.float32)
    plan = pk.ssd_scan_plan(t, b, h, p, g, n, c)
    assert plan

    def kernels(x, dt, a, d):      # 2-D arguments: no relayout at a call
        return pk.ssd_scan_kernels(x[:, None], dt[:, None], a, d, plan,
                                   groups=g, states=n)[:, 0]

    def xla(x, dt, a, d):
        return L.ssd_scan_xla(x[:, None], dt[:, None], a, d, g, n, c)[:, 0]

    def both(scan):
        return (jax.jit(scan)(*args),
                jax.jit(jax.grad(lambda *v: jnp.sum(scan(*v) * w),
                                 argnums=(0, 1, 2, 3)))(*args))

    faulthandler.dump_traceback_later(300, exit=True)
    try:
        got, got_grads = jax.device_get(both(kernels))
        want, want_grads = jax.device_get(both(xla))
    finally:
        faulthandler.cancel_dump_traceback_later()
    for name, v, r in zip(("y", "dx", "ddt", "dA", "dD"),
                          [got] + list(got_grads),
                          [want] + list(want_grads)):
        assert v.shape == r.shape and np.isfinite(v).all(), name
        err = np.abs(v - r).max() / max(np.abs(r).max(), 1e-12)
        print(f"mamba-2 scan {b}x{t} {h} heads of {p} over {g} groups of "
              f"{n} {name}: max gap / max {err:.3e}")
        assert err < 1e-4, (name, err)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", _NORM_SHAPES)
def test_lrn_fuse_relu_parity_on_tpu(shape, dtype):
    """lrn(relu(x)) in one kernel, forward + VJP, against the XLA chain
    on the f32 upcast (the kernel computes in f32 whatever the I/O
    dtype, so bf16 differs from the reference by output rounding)."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import (
        lrn_across_channels, xla_lrn_across_channels)
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.randn(*shape), jnp.float32)

    def pallas(a):
        return lrn_across_channels(a, 5, 1e-4, 0.75, 1.0, False,
                                   True).astype(jnp.float32)

    def xla(a):
        return xla_lrn_across_channels(
            jnp.maximum(a.astype(jnp.float32), 0), 5, 1e-4, 0.75, 1.0)

    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)          # bf16 eps = 7.8e-3
    np.testing.assert_allclose(np.asarray(jax.jit(pallas)(x)),
                               np.asarray(jax.jit(xla)(x)), **tol)
    gp = jax.jit(jax.grad(lambda a: jnp.sum(pallas(a) * w)))(x)
    gx = jax.jit(jax.grad(lambda a: jnp.sum(xla(a) * w)))(x)
    assert gp.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(gp, np.float32),
                               np.asarray(gx, np.float32), **tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_bias_relu_lrn_parity_on_tpu(dtype):
    """The fused stem epilogue lrn(relu(x + bias)) at AlexNet's
    conv1 -> norm1 shape (zoo.alexnet: norm before pool), forward and
    the VJP in x and bias."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import (
        bias_relu_lrn_across_channels, xla_bias_relu_lrn)
    rng = np.random.RandomState(3)
    shape = (16, 96, 55, 55)
    x = jnp.asarray(rng.randn(*shape), dtype)
    b = jnp.asarray(rng.randn(shape[1]), jnp.float32)
    w = jnp.asarray(rng.randn(*shape), jnp.float32)

    def pallas(a, bb):
        return bias_relu_lrn_across_channels(
            a, bb, 5, 1e-4, 0.75, 1.0).astype(jnp.float32)

    def xla(a, bb):
        return xla_bias_relu_lrn(a.astype(jnp.float32), bb, 5, 1e-4,
                                 0.75, 1.0)

    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=1e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(jax.jit(pallas)(x, b)),
                               np.asarray(jax.jit(xla)(x, b)), **tol)
    gp = jax.jit(jax.grad(lambda a, bb: jnp.sum(pallas(a, bb) * w),
                          argnums=(0, 1)))(x, b)
    gx = jax.jit(jax.grad(lambda a, bb: jnp.sum(xla(a, bb) * w),
                          argnums=(0, 1)))(x, b)
    np.testing.assert_allclose(np.asarray(gp[0], np.float32),
                               np.asarray(gx[0], np.float32), **tol)
    # d_bias sums 16*55*55 = 48,400 dx values per channel: compare on
    # the scale of the sum, not per element
    db, db_ref = np.asarray(gp[1]), np.asarray(gx[1])
    scale = np.max(np.abs(db_ref))
    assert np.max(np.abs(db - db_ref)) <= \
        (1e-4 if dtype == "float32" else 1e-2) * scale


def test_int8_matmul_fc6_on_tpu():
    """fc6's serving matmul (M 256, K 9216, N 4096): the Pallas route
    is the one chosen (Mosaic custom call in the compiled program) and
    its int32 accumulation is exact against XLA's dot_general."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import int8_matmul
    rng = np.random.RandomState(4)
    xq = jnp.asarray(rng.randint(-127, 128, (256, 9216)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (4096, 9216)), jnp.int8)
    fn = jax.jit(int8_matmul)
    assert "tpu_custom_call" in fn.lower(xq, wq).compile().as_text()
    want = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32))(xq, wq)
    np.testing.assert_array_equal(np.asarray(fn(xq, wq)),
                                  np.asarray(want))
