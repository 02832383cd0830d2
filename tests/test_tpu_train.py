"""On-chip train-step checks for what the CPU suite cannot compile for
a TPU: the cont-gated LSTM (lax.scan), the causal transformer LM
(MultiHeadAttention, flash kernels through the MHA VJP), the ring
attention kernels, the NHWC layout and uint8-infeed levers, and the
fused K-step loop against K single steps.

Run: COS_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py \\
         tests/test_tpu_train.py     (one process; no TPU = failure)
"""

import numpy as np
import pytest

pytestmark = pytest.mark.usefixtures("tpu")


def test_lstm_train_step_on_tpu():
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = NetParameter.from_text("""
name: "lstm_smoke"
layer { name: "data" type: "Input" top: "seq" top: "cont" top: "tgt"
  input_param { shape { dim: 6 dim: 4 dim: 8 }
                shape { dim: 6 dim: 4 }
                shape { dim: 6 dim: 4 } } }
layer { name: "lstm" type: "LSTM" bottom: "seq" bottom: "cont"
  top: "lstm"
  recurrent_param { num_output: 16
    weight_filler { type: "xavier" } } }
layer { name: "ip" type: "InnerProduct" bottom: "lstm" top: "ip"
  inner_product_param { num_output: 5 axis: 2
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "tgt" top: "loss"
  softmax_param { axis: 2 } }""")
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.1 momentum: 0.9 lr_policy: 'fixed' random_seed: 2"),
        npm)
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    cont = np.ones((6, 4), np.float32)
    cont[0] = 0.0
    inputs = {"seq": rng.randn(6, 4, 8).astype(np.float32),
              "cont": cont,
              "tgt": rng.randint(0, 5, (6, 4)).astype(np.float32)}
    losses = []
    for i in range(3):
        params, st, out = step(params, st, inputs, s.step_rng(i))
        losses.append(float(out["loss"]))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0]


def test_transformer_train_step_on_tpu():
    from caffeonspark_tpu.models.zoo import transformer_lm
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = transformer_lm(vocab=16, d_model=32, heads=2, layers=1,
                         seq=8, batch=4)
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' type: 'ADAM' "
        "random_seed: 1"), npm)
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    seqs = rng.randint(0, 10, (4, 8))
    inputs = {"input_sentence": seqs.T.astype(np.float32),
              "target_sentence": ((seqs + 1) % 10).T.astype(np.float32)}
    losses = []
    for i in range(5):
        params, st, out = step(params, st, inputs, s.step_rng(i))
        losses.append(float(out["loss"]))
    assert np.isfinite(losses).all(), losses


def test_transformer_flash_train_parity_on_tpu(monkeypatch):
    """seq=128 engages the Pallas flash dispatch in MultiHeadAttention
    on single-device TPU runs; the train step (flash fwd + dq/dk/dv
    bwd kernels through the MHA VJP) must match COS_DISABLE_FLASH=1
    losses — the on-chip proof of the whole flash train path."""
    from caffeonspark_tpu.models.zoo import transformer_lm
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver

    def run(disable_flash):
        if disable_flash:
            monkeypatch.setenv("COS_DISABLE_FLASH", "1")
        else:
            monkeypatch.delenv("COS_DISABLE_FLASH", raising=False)
        npm = transformer_lm(vocab=16, d_model=64, heads=2, layers=1,
                             seq=128, batch=2)
        s = Solver(SolverParameter.from_text(
            "base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' "
            "type: 'ADAM' random_seed: 1"), npm)
        params, st = s.init()
        step = s.jit_train_step()
        rng = np.random.RandomState(0)
        seqs = rng.randint(0, 10, (2, 128))
        inputs = {"input_sentence": seqs.T.astype(np.float32),
                  "target_sentence": ((seqs + 1) % 10).T.astype(
                      np.float32)}
        losses = []
        for i in range(4):
            params, st, out = step(params, st, inputs, s.step_rng(i))
            losses.append(float(out["loss"]))
        return losses

    flash = run(disable_flash=False)
    xla = run(disable_flash=True)
    assert np.isfinite(flash).all() and np.isfinite(xla).all()
    # tolerance is the MXU default-precision floor, not the f32 one the
    # interpret-mode tests use: both paths multiply f32 operands in
    # bf16 MXU passes and round differently (~1e-3 relative).  Exact
    # f32 semantics are pinned on CPU (tests/test_pallas.py).
    np.testing.assert_allclose(flash, xla, rtol=5e-3, atol=5e-4)


def test_ring_attention_cross_extent_on_tpu():
    """The round-5 cross-attention fused ring (unequal q/kv extents:
    fused Pallas forward via flash_block_update, custom-VJP einsum-ring
    backward) lowers through the REAL Mosaic compiler and matches
    reference attention fwd + grads.  Single chip = sp mesh of 1: the
    ring degenerates to one hop but every kernel and the VJP wiring
    still run on hardware (the CPU-suite analog is
    test_ring_attention_flash_cross_extent_grads_match)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from caffeonspark_tpu.parallel.sp import attention, ring_attention

    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    rng = np.random.RandomState(12)
    b, h, d = 2, 2, 32
    t_q, t_k = 128, 256
    q = jnp.asarray(rng.randn(b, h, t_q, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t_k, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t_k, d), jnp.float32)
    for causal in (False, True):
        ref = attention(q, k, v, causal=causal)
        got = ring_attention(q, k, v, mesh, causal=causal, flash=True)
        # MXU default-precision floor (bf16 multiply passes; measured
        # band in tests/test_pallas_tpu.py's fwd parity test)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-2,
                                   atol=1e-2, err_msg=f"fwd {causal}")

        def loss(fn):
            # bounded cotangent (|dO| <= 1), matching the equal-extent
            # methodology in test_flash_attention_vjp_parity_on_tpu: an
            # unbounded dO (e.g. sum(out**2) -> dO = 2*out) multiplies
            # the irreducible kernel-forward rounding of `out` inside
            # delta = sum(dO*out) and breaks the analytic dp==delta
            # cancellation on fully-peaked causal rows whose true dq
            # is exactly 0 (measured 0.031 abs there vs 0.007 with
            # sin; the exact-f32 semantics of those rows are pinned by
            # interpret mode in test_parallel.py)
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

        gr = jax.grad(loss(lambda q, k, v: attention(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, flash=True)),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b_ in zip("qkv", gr, gf):
            # measured on-chip band at this shape (TPU v5 lite,
            # HIGHEST-precision backward einsums): max|d| 0.0136 (dk,
            # causal); atol 2e-2 is ~1.5x headroom.  Errors are
            # absolute-scale (softmax rounding), not relative — small
            # |ref| entries carry the same abs noise as large ones.
            np.testing.assert_allclose(
                np.asarray(b_), np.asarray(a), rtol=1e-2, atol=2e-2,
                err_msg=f"d{name} causal={causal}")


_CONV_NET = """
name: "conv_smoke"
layer { name: "data" type: "Input" top: "data" top: "label"
  input_param { shape { dim: 8 dim: 3 dim: 24 dim: 24 }
                shape { dim: 8 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 16 kernel_size: 5 stride: 2
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "ip" type: "InnerProduct" bottom: "conv1" top: "ip"
  inner_product_param { num_output: 5
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }"""


def _conv_losses(n_steps=3, device_batch=None):
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.05 momentum: 0.9 lr_policy: 'fixed' random_seed: 4"),
        NetParameter.from_text(_CONV_NET))
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(1)
    base = {"data": rng.randint(0, 256, (8, 3, 24, 24)).astype(np.float32),
            "label": rng.randint(0, 5, (8,)).astype(np.float32)}
    losses = []
    for i in range(n_steps):
        inputs = device_batch(base) if device_batch else base
        params, st, out = step(params, st, inputs, s.step_rng(i))
        losses.append(float(out["loss"]))
    return losses


def test_nhwc_conv_layout_on_tpu(monkeypatch):
    """COS_CONV_LAYOUT=NHWC lowers through Mosaic/XLA-TPU and matches
    the default layout's training losses on the real compiler (the
    CPU-suite analog is test_nhwc_conv_layout_parity)."""
    # pin s2d off so both runs use the plain conv — a pure layout A/B
    # (the NCHW default would otherwise take the space-to-depth stem)
    monkeypatch.setenv("COS_CONV_S2D", "0")
    monkeypatch.setenv("COS_CONV_LAYOUT", "NCHW")
    ref = _conv_losses()
    monkeypatch.setenv("COS_CONV_LAYOUT", "NHWC")
    got = _conv_losses()
    assert np.isfinite(got).all(), got
    np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-5)


def test_device_transform_train_on_tpu():
    """The uint8-infeed split's device stage (u8 cast + mean/scale with
    vmapped dynamic_slice mean windows) compiles and trains on chip
    with losses equal to the host-transformed feed."""
    import jax
    from caffeonspark_tpu.data.transformer import Transformer
    from caffeonspark_tpu.proto.caffe import TransformationParameter

    tp = TransformationParameter(crop_size=24, mirror=True,
                                 scale=0.00390625,
                                 mean_value=[104.0, 117.0, 123.0])
    rng = np.random.RandomState(7)
    raw = rng.randint(0, 256, (8, 3, 28, 28)).astype(np.float32)

    host_t = Transformer(tp, phase_train=True, seed=9)
    split_t = Transformer(tp, phase_train=True, seed=9)
    fn = jax.jit(split_t.device_stage_fn())

    def host_batch(base):
        return dict(base, data=host_t(raw))

    def dev_batch(base):
        u8, aux = split_t.host_stage(raw)
        return dict(base, data=fn(u8, aux))

    ref = _conv_losses(device_batch=host_batch)
    got = _conv_losses(device_batch=dev_batch)
    assert np.isfinite(got).all(), got
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_fused_loop_matches_single_steps_on_tpu():
    """One train_step_many(8) chunk (the COS_STEPS_PER_LOOP=8 program:
    lax.scan over the step, LR/iter/rng advanced on device) against
    eight single dispatches on the same batches, over every local
    device (dp = device count).  The net has an LRN layer, so the
    Pallas kernel runs inside the scan body."""
    import jax
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver

    batch = 8 * len(jax.devices())
    lrn = ('layer { name: "norm1" type: "LRN" bottom: "conv1" '
           'top: "norm1"\n  lrn_param { local_size: 5 alpha: 0.0001 '
           'beta: 0.75 } }\n')
    net = _CONV_NET.replace("dim: 8 dim: 3", f"dim: {batch} dim: 3") \
        .replace("shape { dim: 8 }", f"shape {{ dim: {batch} }}") \
        .replace('layer { name: "ip"', lrn + 'layer { name: "ip"') \
        .replace('bottom: "conv1" top: "ip"', 'bottom: "norm1" top: "ip"')
    sp = SolverParameter.from_text(
        "base_lr: 0.05 momentum: 0.9 lr_policy: 'step' gamma: 0.5 "
        "stepsize: 3 max_iter: 100 random_seed: 4")
    rng = np.random.RandomState(5)
    batches = [{"data": rng.randint(0, 256, (batch, 3, 24, 24))
                .astype(np.float32),
                "label": rng.randint(0, 5, (batch,)).astype(np.float32)}
               for _ in range(8)]

    def fresh():
        s = Solver(sp, NetParameter.from_text(net))
        ps = ParallelSolver(s, build_mesh())
        return s, ps, ps.init()

    s, ps, (p1, st1) = fresh()
    step = ps.train_step()
    single = []
    for i, b in enumerate(batches):
        p1, st1, out = step(p1, st1, ps.shard_batch(b), s.step_rng(i))
        single.append(float(out["loss"]))

    s, ps, (p8, st8) = fresh()
    block = {k: jax.device_put(np.stack([b[k] for b in batches]), sh)
             for k, sh in ps.chunk_input_shardings().items()}
    p8, st8, outs = ps.train_step_many(8)(p8, st8, block)
    fused = [float(x) for x in np.asarray(outs["loss"])]

    assert np.isfinite(single).all() and np.isfinite(fused).all()
    assert int(st8.iter) == int(st1.iter) == 8
    np.testing.assert_allclose(fused, single, rtol=1e-5, atol=1e-6)
    for ln in p1:
        for bn in p1[ln]:
            np.testing.assert_allclose(
                np.asarray(p8[ln][bn]), np.asarray(p1[ln][bn]),
                rtol=1e-4, atol=1e-6, err_msg=f"{ln}/{bn}")


def test_buffer_pool_memory_outlives_its_transfer_on_tpu():
    """`device_put` returns before the host buffer is read.  A pooled
    buffer comes back only when nothing refers to it, and the runtime
    refers to it until its transfer is complete: thirty 64 MB batches,
    each staged and dropped at once while the next is written into
    whatever memory has come back, all arrive as they were written."""
    import jax
    from caffeonspark_tpu.native import BufferPool
    pool = BufferPool()
    shape = (16, 1024, 1024)
    staged, addrs = [], set()
    for k in range(30):
        a = pool.take(shape, np.float32)
        addrs.add(a.ctypes.data)
        a[:] = k
        staged.append(jax.device_put(a))
        del a
    for k, d in enumerate(staged):
        got = np.asarray(d[:, ::257, ::129])
        assert (got == k).all(), (k, np.unique(got))
    assert len(addrs) < 30
