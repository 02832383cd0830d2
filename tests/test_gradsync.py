"""Gradient-exchange layer (parallel/gradsync.py, COS_GRAD_SYNC).

Parity contract, in order of strictness:
  * `default` is INERT — trajectories byte-identical to an unset env
    across 100+ steps, including under TP, ZeRO-1 and the fused K>1
    loop (the mode adds zero ops to the traced program);
  * `bucket` is the same math through flat buffers — bit-exact on one
    device, numeric-tolerance on dp meshes (collective placement may
    reorder reductions);
  * `quant` changes the wire dtype only — gated by convergence on real
    handwritten digits, not assumed;
  * `hier` re-decomposes the collective — numeric-tolerance parity,
    including the non-divisible-bucket padding path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.data.synthetic import batches
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
from caffeonspark_tpu.parallel.gradsync import (build_plan,
                                                dequantize_int8,
                                                quantize_int8)
from caffeonspark_tpu.proto import (NetParameter, NetState, Phase,
                                    SolverParameter)
from caffeonspark_tpu.solver import Solver

NET = """
name: "tiny"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 4 channels: 1 height: 28 width: 28 } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 5 stride: 2
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "fc_big" type: "InnerProduct" bottom: "conv1" top: "fc_big"
  inner_product_param { num_output: 2048
    weight_filler { type: "xavier" } } }
layer { name: "relu2" type: "ReLU" bottom: "fc_big" top: "fc_big" }
layer { name: "ip2" type: "InnerProduct" bottom: "fc_big" top: "ip2"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }
"""

SOLVER = """
base_lr: 0.01
momentum: 0.9
lr_policy: "fixed"
max_iter: 200
random_seed: 11
"""


def _batch(n=32):
    gen = batches(256, n, seed=3, scale=1.0 / 256.0)
    data, label = next(gen)
    return {"data": jnp.asarray(data), "label": jnp.asarray(label)}


def _make_solver(monkeypatch, mode=None, bucket_mb="0.5", wire=None,
                 solver_text=SOLVER, net_text=NET, **env):
    if mode is None:
        monkeypatch.delenv("COS_GRAD_SYNC", raising=False)
    else:
        monkeypatch.setenv("COS_GRAD_SYNC", mode)
    monkeypatch.setenv("COS_GRAD_BUCKET_MB", bucket_mb)
    if wire is None:
        monkeypatch.delenv("COS_GRAD_WIRE_DTYPE", raising=False)
    else:
        monkeypatch.setenv("COS_GRAD_WIRE_DTYPE", wire)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    return Solver(SolverParameter.from_text(solver_text),
                  NetParameter.from_text(net_text))


def _leaves(tree):
    return [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(tree)]


def _assert_bytes_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


def _assert_close(a, b, atol, rtol=1e-5):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   atol=atol, rtol=rtol)


# -- plan ------------------------------------------------------------------
def test_plan_reverse_backward_order_and_caps():
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    plan = build_plan(net, "bucket", bucket_mb=0.5)
    # grads finalize last-layer-first: ip2 blobs lead, conv1 trails
    assert plan.buckets[0].entries[0][0] == "ip2"
    assert plan.buckets[-1].entries[-1][0] == "conv1"
    order = [e for b in plan.buckets for e in b.entries]
    assert order.index(("ip2", "weight")) < order.index(
        ("fc_big", "weight")) < order.index(("conv1", "weight"))
    cap = int(0.5 * (1 << 20))
    for b in plan.buckets:
        # a bucket only exceeds the cap when a single blob does
        assert b.bytes_grad <= cap or len(b.entries) == 1
    assert plan.total_numel == net.num_params()
    assert plan.total_bytes_wire == plan.total_numel * 4


def test_plan_wire_dtype_bytes():
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    bf16 = build_plan(net, "quant", bucket_mb=1.0)
    assert bf16.wire_dtype == "bfloat16"
    assert bf16.total_bytes_wire == bf16.total_numel * 2
    i8 = build_plan(net, "quant", bucket_mb=1.0, wire_dtype="int8")
    assert i8.total_bytes_wire == i8.total_numel + 4 * i8.n_buckets


def test_plan_skips_requested_blobs():
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    plan = build_plan(net, "bucket", bucket_mb=1.0,
                      skip_blobs=frozenset({("fc_big", "weight")}))
    entries = [e for b in plan.buckets for e in b.entries]
    assert ("fc_big", "weight") not in entries
    assert ("fc_big", "weight") in plan.skipped


def test_exposed_wire_bytes_model():
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    plan = build_plan(net, "bucket", bucket_mb=0.5)
    total, last = plan.total_bytes_wire, plan.buckets[-1].bytes_wire
    # default serializes everything; overlap exposes the tail bucket
    assert plan._replace(mode="default").exposed_wire_bytes() == total
    assert plan.exposed_wire_bytes() == last
    # finite hide capacity: exposed grows back toward total
    assert plan.exposed_wire_bytes(hide_bytes=0) == max(last, total)
    assert plan.exposed_wire_bytes(
        hide_bytes=total - last - 100) == last + 100
    hier = build_plan(net, "hier", bucket_mb=0.5)
    assert hier.exposed_wire_bytes(local_size=4) == -(-last // 4)


# -- default: inert --------------------------------------------------------
def test_default_byte_identical_100_steps(monkeypatch):
    batch = _batch()
    runs = []
    for mode in (None, "default"):
        s = _make_solver(monkeypatch, mode)
        assert not s.grad_sync.enabled
        p, st = s.init()
        step = s.jit_train_step()
        for i in range(100):
            p, st, _ = step(p, st, batch, s.step_rng(i))
        runs.append((p, st))
    _assert_bytes_equal(runs[0][0], runs[1][0])
    _assert_bytes_equal(runs[0][1].history, runs[1][1].history)
    _assert_bytes_equal(runs[0][1].history2, runs[1][1].history2)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_default_byte_identical_tp_zero_fused(monkeypatch):
    """The acceptance pin: default == unset under TP + ZeRO-1 + K>1,
    params AND opt state, across 100+ fused steps."""
    gen = batches(512, 64, seed=3, scale=1.0 / 256.0)
    ds, ls = [], []
    for _ in range(4):
        d, l = next(gen)
        ds.append(d)
        ls.append(l)
    stacked = {"data": jnp.asarray(np.stack(ds)),
               "label": jnp.asarray(np.stack(ls))}
    runs = []
    for mode in (None, "default"):
        s = _make_solver(monkeypatch, mode)
        ps = ParallelSolver(s, build_mesh(dp=4, tp=2), zero_dp=True)
        p, st = ps.init()
        fused = ps.train_step_many(4)
        sh = ps.chunk_input_shardings()
        b = {k: jax.device_put(v, sh[k]) for k, v in stacked.items()}
        for _ in range(26):             # 104 solver iterations
            # one launch in flight: the CPU backend runs a launch's
            # eight device programs on one shared thread pool, and with
            # several launches queued on few cores the threads of a
            # later launch wait in an all-gather for a peer that has no
            # thread left to run on; XLA aborts the process after 40 s
            # (rendezvous.cc "Termination timeout"; the tier-1 run's
            # lost xdist worker, PRs 23-44)
            p, st, _ = jax.block_until_ready(fused(p, st, b))
        runs.append((p, st))
    _assert_bytes_equal(runs[0][0], runs[1][0])
    _assert_bytes_equal(runs[0][1].history, runs[1][1].history)
    assert int(jax.device_get(runs[1][1].iter)) == 104


# -- bucket ----------------------------------------------------------------
def test_bucket_single_device_bit_exact(monkeypatch):
    batch = _batch()
    runs = []
    for mode in ("default", "bucket"):
        s = _make_solver(monkeypatch, mode)
        p, st = s.init()
        step = s.jit_train_step()
        for i in range(20):
            p, st, _ = step(p, st, batch, s.step_rng(i))
        runs.append(p)
    # concat/split through the flat wire buffer moves bytes, not math
    _assert_bytes_equal(runs[0], runs[1])


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_bucket_dp8_numeric_parity(monkeypatch):
    batch = _batch()
    runs = []
    for mode in ("default", "bucket"):
        s = _make_solver(monkeypatch, mode)
        ps = ParallelSolver(s, build_mesh(dp=8))
        p, st = ps.init()
        step = ps.train_step()
        b = ps.shard_batch(batch)
        for i in range(10):
            p, st, _ = step(p, st, b, s.step_rng(i))
        runs.append(p)
    _assert_close(runs[0], runs[1], atol=1e-6)


def test_bucket_iter_size_accumulation_parity(monkeypatch):
    """iter_size > 1 routes through the finished-grad exchange (one
    exchange per optimizer step, after accumulation) — still exact."""
    text = SOLVER + "iter_size: 2\n"
    batch = _batch()
    runs = []
    for mode in ("default", "bucket"):
        s = _make_solver(monkeypatch, mode, solver_text=text)
        if mode == "bucket":
            assert not s.grad_sync.use_hooks(2)
        p, st = s.init()
        step = s.jit_train_step()
        for i in range(10):
            p, st, _ = step(p, st, batch, s.step_rng(i))
        runs.append(p)
    _assert_bytes_equal(runs[0], runs[1])


# -- quant -----------------------------------------------------------------
def test_quant_bf16_short_horizon_parity(monkeypatch):
    batch = _batch()
    runs = []
    for mode in ("default", "quant"):
        s = _make_solver(monkeypatch, mode)
        if mode == "quant":
            assert s.grad_sync.plan.wire_dtype == "bfloat16"
        p, st = s.init()
        step = s.jit_train_step()
        for i in range(10):
            p, st, _ = step(p, st, batch, s.step_rng(i))
        runs.append(p)
    _assert_close(runs[0], runs[1], atol=2e-3, rtol=1e-2)


def test_quant_int8_stochastic_rounding_unbiased():
    x = jnp.asarray(np.linspace(-0.011, 0.013, 257), jnp.float32)
    # round-to-nearest without an rng
    q, scale = quantize_int8(x, None)
    deq = dequantize_int8(q, scale, jnp.float32)
    assert float(jnp.max(jnp.abs(deq - x))) <= float(scale) / 2 + 1e-9
    # stochastic rounding averages back to the input
    keys = jax.random.split(jax.random.key(0), 512)
    qs = jax.vmap(lambda k: dequantize_int8(
        *quantize_int8(x, k)[:1], quantize_int8(x, k)[1],
        jnp.float32))(keys)
    err = np.asarray(jnp.mean(qs, 0) - x)
    assert float(np.max(np.abs(err))) < float(scale) / 6


def _digits_problem():
    from sklearn.datasets import load_digits
    X, y = load_digits(return_X_y=True)
    X = (X / 16.0).astype(np.float32).reshape(-1, 1, 8, 8)
    return X, y.astype(np.int32)


DIGITS_NET = """
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 64 channels: 1 height: 8 width: 8 } }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
  inner_product_param { num_output: 64
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }
"""

DIGITS_SOLVER = """
base_lr: 0.1
momentum: 0.9
lr_policy: "fixed"
max_iter: 300
random_seed: 7
"""


def _train_digits(monkeypatch, mode, wire=None, steps=300):
    X, y = _digits_problem()
    s = _make_solver(monkeypatch, mode, bucket_mb="0.02", wire=wire,
                     solver_text=DIGITS_SOLVER, net_text=DIGITS_NET)
    p, st = s.init()
    step = s.jit_train_step()
    n = X.shape[0]
    rng = np.random.RandomState(0)
    for i in range(steps):
        idx = rng.randint(0, n, 64)
        b = {"data": jnp.asarray(X[idx]), "label": jnp.asarray(y[idx])}
        p, st, _ = step(p, st, b, s.step_rng(i))
    logits, _ = s.train_net.apply(
        p, {"data": jnp.asarray(X), "label": jnp.asarray(y)},
        train=False)
    acc = float(np.mean(np.argmax(
        np.asarray(logits["ip2"], np.float32), 1) == y))
    return acc


def test_quant_convergence_on_real_digits(monkeypatch):
    """The convergence gate for the lossy wire: real handwritten
    digits (sklearn's UCI scans — same data test_real_digits drives
    the reference LeNet configs with) must reach reference accuracy
    under a quantized exchange, bf16 AND int8+stochastic-rounding."""
    ref = _train_digits(monkeypatch, "default")
    assert ref >= 0.93
    for wire in (None, "int8"):
        acc = _train_digits(monkeypatch, "quant", wire=wire)
        assert acc >= ref - 0.03, (wire, acc, ref)
        assert acc >= 0.90, (wire, acc)


# -- hier ------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_hier_dp8_parity_including_padding(monkeypatch):
    batch = _batch()
    runs = []
    for mode in ("default", "hier"):
        s = _make_solver(monkeypatch, mode, bucket_mb="0.5")
        ps = ParallelSolver(s, build_mesh(dp=8))
        if mode == "hier":
            # at least one bucket's numel must NOT divide dp=8 so the
            # two-phase pad/unpad path is actually exercised
            assert any(b.numel % 8 for b in s.grad_sync.plan.buckets)
        p, st = ps.init()
        step = ps.train_step()
        b = ps.shard_batch(batch)
        for i in range(10):
            p, st, _ = step(p, st, b, s.step_rng(i))
        runs.append(p)
    _assert_close(runs[0], runs[1], atol=1e-6)


# -- composition -----------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
@pytest.mark.parametrize("mode", ["bucket", "quant", "hier"])
def test_modes_compose_with_zero_and_fused_loop(monkeypatch, mode):
    gen = batches(512, 64, seed=3, scale=1.0 / 256.0)
    ds, ls = [], []
    for _ in range(4):
        d, l = next(gen)
        ds.append(d)
        ls.append(l)
    stacked = {"data": jnp.asarray(np.stack(ds)),
               "label": jnp.asarray(np.stack(ls))}
    runs = []
    for m in ("default", mode):
        s = _make_solver(monkeypatch, m)
        ps = ParallelSolver(s, build_mesh(dp=8), zero_dp=True)
        p, st = ps.init()
        fused = ps.train_step_many(4)
        sh = ps.chunk_input_shardings()
        b = {k: jax.device_put(v, sh[k]) for k, v in stacked.items()}
        for _ in range(3):
            p, st, outs = fused(p, st, b)
        assert np.all(np.isfinite(
            np.asarray(jax.device_get(outs["loss"]))))
        runs.append(p)
    _assert_close(runs[0], runs[1],
                  atol=1e-6 if mode in ("bucket", "hier") else 2e-3,
                  rtol=1e-2 if mode == "quant" else 1e-5)


def test_auto_mode_resolution(monkeypatch):
    s = _make_solver(monkeypatch, "auto")
    # unbound (single-process, no mesh): numerics-safe default
    assert s.grad_sync.mode == "default"
    if len(jax.devices()) >= 8:
        ParallelSolver(s, build_mesh(dp=8))
        assert s.grad_sync.mode == "bucket"   # dp>1, single process
        assert s.grad_sync.plan.mode == "bucket"


def test_hook_gating(monkeypatch):
    s = _make_solver(monkeypatch, "bucket")
    assert s.grad_sync.use_hooks(1)
    assert not s.grad_sync.use_hooks(2)          # iter_size: post-grad
    s2 = _make_solver(monkeypatch, "quant", wire="int8")
    assert not s2.grad_sync.use_hooks(1)         # rng-consuming bwd
    s3 = _make_solver(monkeypatch, "bucket", COS_GRAD_OVERLAP="0")
    assert not s3.grad_sync.use_hooks(1)
    # hookless bucket still runs and stays exact
    p, st = s3.init()
    step = s3.jit_train_step()
    batch = _batch()
    p, st, out = step(p, st, batch, s3.step_rng(0))
    assert np.isfinite(float(out["loss"]))


# -- satellites ------------------------------------------------------------
def test_zero_state_specs_prefers_largest_divisible_dim():
    from jax.sharding import PartitionSpec as P

    from caffeonspark_tpu.parallel.dp import zero_state_specs
    specs = {"fc6": {"weight": P(), "bias": P()},
             "fc7": {"weight": P()},
             "tpw": {"weight": P("tp", None)},
             "odd": {"weight": P()}}
    shapes = {"fc6": {"weight": (4096, 25088), "bias": (4096,)},
              "fc7": {"weight": (2048, 1152)},
              "tpw": {"weight": (4096, 25088)},
              "odd": {"weight": (4097, 129)}}
    out = zero_state_specs(specs, shapes, 8)
    # the fc6-style blob shards its LARGE axis, not the first divisible
    assert out["fc6"]["weight"] == P(None, "dp")
    # below ZERO_MIN_NUMEL: not worth sharding
    assert out["fc6"]["bias"] == P()
    assert out["fc7"]["weight"] == P("dp", None)
    # composes with an existing tp axis on the other dim
    assert out["tpw"]["weight"] == P("tp", "dp")
    # nothing divisible: stays replicated
    assert out["odd"]["weight"] == P()


def test_comm_info_in_pipeline_metrics():
    from caffeonspark_tpu.metrics import PipelineMetrics
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    plan = build_plan(net, "quant", bucket_mb=0.5)
    m = PipelineMetrics()
    m.set_info("comm", plan.comm_info())
    assert m.has_samples()
    s = m.summary()
    assert s["info"]["comm"]["wire_dtype"] == "bfloat16"
    assert s["info"]["comm"]["buckets"] == plan.n_buckets
    assert (s["info"]["comm"]["bytes_per_step_wire"]
            == plan.total_bytes_wire)
    import json
    json.dumps(s)   # must stay JSON-serializable end to end
