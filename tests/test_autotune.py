"""Roofline-guided per-layer autotuner (ops/autotune.py, COS_AUTOTUNE).

Contract, in order of strictness:
  * COS_AUTOTUNE unset is INERT — Net construction resolves no plan,
    threads no variants, and training trajectories are byte-identical
    to an explicit "0", including under TP + ZeRO-1 + the fused K>1
    loop (the PR 6/10 parity-pin pattern);
  * an applied plan changes numerics only within the plan's pinned
    tolerance — bias/relu+LRN fusion is exact, layout flips are
    float-rounding, dtype flips are bounded by the tuner's parity gate;
  * plans are JSON artifacts keyed by (net digest, device_kind, batch,
    dtype policy): cache roundtrip works, a digest-mismatched plan is
    refused;
  * the tuner itself (measured greedy over roofline-ranked offenders)
    produces a valid, reloadable plan on a real net.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.analysis import roofline as rl
from caffeonspark_tpu.data.synthetic import batches
from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import autotune as at
from caffeonspark_tpu.proto import (NetParameter, NetState, Phase,
                                    SolverParameter)
from caffeonspark_tpu.solver import Solver

# conv → in-place relu → LRN stem (the fusable chain) + an fc torso:
# every variant family is enumerable on one tiny net
NET = """
name: "tinystem"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 4 channels: 3 height: 24 width: 24 } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 5 stride: 2
    weight_filler { type: "xavier" }
    bias_filler { type: "constant" value: 0.1 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
  lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 } }
layer { name: "ip1" type: "InnerProduct" bottom: "norm1" top: "ip1"
  inner_product_param { num_output: 32
    weight_filler { type: "xavier" } } }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
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


def _net(monkeypatch=None, autotune=None, phase=Phase.TRAIN,
         text=NET):
    return Net(NetParameter.from_text(text), NetState(phase=phase),
               autotune=autotune)


def _batch(n=4):
    gen = batches(64, n, seed=3, scale=1.0 / 256.0)
    data, label = next(gen)
    data = np.repeat(data.reshape(n, 1, 28, 28)[:, :, :24, :24], 3, 1)
    return {"data": jnp.asarray(data), "label": jnp.asarray(label)}


def _leaves(tree):
    return [np.asarray(jax.device_get(x))
            for x in jax.tree_util.tree_leaves(tree)]


def _assert_bytes_equal(a, b):
    for x, y in zip(_leaves(a), _leaves(b)):
        np.testing.assert_array_equal(x, y)


def _clear_env(monkeypatch):
    for k in ("COS_AUTOTUNE", "COS_AUTOTUNE_CACHE",
              "COS_FUSE_RELU_LRN", "COS_FUSE_BIAS_RELU_LRN"):
        monkeypatch.delenv(k, raising=False)


# -- inertness -------------------------------------------------------------

def test_unset_is_inert(monkeypatch):
    _clear_env(monkeypatch)
    n = _net()
    assert n.autotune_plan is None
    assert n.layer_variants == {}
    assert n.autotune_info() == {"active": False}
    assert n.fused_relu_lrn == frozenset()
    assert n.fused_bias_lrn == {}


def test_unset_vs_zero_byte_identical(monkeypatch):
    """The inertness pin: unset and COS_AUTOTUNE=0 trajectories are
    byte-identical, params AND opt state, across 20 steps."""
    batch = _batch()
    runs = []
    for env in (None, "0"):
        _clear_env(monkeypatch)
        if env is not None:
            monkeypatch.setenv("COS_AUTOTUNE", env)
        s = Solver(SolverParameter.from_text(SOLVER),
                   NetParameter.from_text(NET))
        assert s.train_net.autotune_plan is None
        p, st = s.init()
        step = s.jit_train_step()
        for i in range(20):
            p, st, _ = step(p, st, batch, s.step_rng(i))
        runs.append((p, st))
    _assert_bytes_equal(runs[0][0], runs[1][0])
    _assert_bytes_equal(runs[0][1].history, runs[1][1].history)
    _assert_bytes_equal(runs[0][1].history2, runs[1][1].history2)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_unset_vs_zero_tp_zero_fused(monkeypatch):
    """The acceptance pin (PR 6/10 pattern): unset == COS_AUTOTUNE=0
    under TP + ZeRO-1 + fused K>1, params AND opt state."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    gen = batches(256, 32, seed=3, scale=1.0 / 256.0)
    ds, ls = [], []
    for _ in range(4):
        d, lb = next(gen)
        d = np.repeat(d.reshape(32, 1, 28, 28)[:, :, :24, :24], 3, 1)
        ds.append(d)
        ls.append(lb)
    stacked = {"data": jnp.asarray(np.stack(ds)),
               "label": jnp.asarray(np.stack(ls))}
    big = NET.replace("batch_size: 4", "batch_size: 32")
    runs = []
    for env in (None, "0"):
        _clear_env(monkeypatch)
        if env is not None:
            monkeypatch.setenv("COS_AUTOTUNE", env)
        s = Solver(SolverParameter.from_text(SOLVER),
                   NetParameter.from_text(big))
        ps = ParallelSolver(s, build_mesh(dp=4, tp=2), zero_dp=True)
        p, st = ps.init()
        fused = ps.train_step_many(4)
        sh = ps.chunk_input_shardings()
        b = {k: jax.device_put(v, sh[k]) for k, v in stacked.items()}
        for _ in range(6):              # 24 solver iterations
            p, st, _ = fused(p, st, b)
        runs.append((p, st))
    _assert_bytes_equal(runs[0][0], runs[1][0])
    _assert_bytes_equal(runs[0][1].history, runs[1][1].history)
    assert int(jax.device_get(runs[1][1].iter)) == 24


# -- plan resolution + cache ----------------------------------------------

def _tiny_plan(npm, layers=None):
    return {"schema": at.PLAN_SCHEMA, "version": at.PLAN_VERSION,
            "source": "tuned",
            "key": {"net_digest": at.net_digest(npm),
                    "device_kind": at.device_kind()},
            "layers": layers or {"ip1": {"dtype": "bfloat16"}}}


def test_cache_roundtrip(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    monkeypatch.setenv("COS_AUTOTUNE_CACHE", str(tmp_path))
    npm = NetParameter.from_text(NET)
    path = at.save_plan(_tiny_plan(npm))
    assert path.startswith(str(tmp_path))
    assert json.load(open(path))["schema"] == at.PLAN_SCHEMA
    monkeypatch.setenv("COS_AUTOTUNE", "1")
    n = _net()
    assert n.layer_variants == {"ip1": {"dtype": "bfloat16"}}
    info = n.autotune_info()
    assert info["active"] and info["source"].startswith("cache:")


def test_cache_slots_separate_mode_and_policy(monkeypatch, tmp_path):
    """A serve-tuned plan and a train-tuned plan of the same prototxt
    live in different cache slots — COS_AUTOTUNE=1 on a TRAIN net
    must never pick up forward-only serve measurements (and f32- vs
    bf16-policy tunes must not collide either)."""
    _clear_env(monkeypatch)
    monkeypatch.setenv("COS_AUTOTUNE_CACHE", str(tmp_path))
    npm = NetParameter.from_text(NET)
    serve_plan = _tiny_plan(npm, {"ip1": {"int8": True}})
    serve_plan["key"]["mode"] = "serve"
    p_serve = at.save_plan(serve_plan)
    train_slot = at.cache_path(at.net_digest(npm))
    assert p_serve != train_slot
    assert at.cache_path("d", "cpu", dtype_policy="f32/bf16") != \
        at.cache_path("d", "cpu", dtype_policy="f32/f32")
    monkeypatch.setenv("COS_AUTOTUNE", "1")
    n = _net()                     # TRAIN net: serve slot is invisible
    assert n.autotune_plan is None and n.layer_variants == {}
    n2 = _net(phase=Phase.TEST)    # TEST net reads the serve slot
    assert n2.layer_variants == {"ip1": {"int8": True}}
    # Net(autotune=True) behaves like COS_AUTOTUNE=1
    monkeypatch.delenv("COS_AUTOTUNE")
    n3 = _net(autotune=True, phase=Phase.TEST)
    assert n3.layer_variants == {"ip1": {"int8": True}}


def test_cache_miss_is_untuned(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    monkeypatch.setenv("COS_AUTOTUNE_CACHE", str(tmp_path))
    monkeypatch.setenv("COS_AUTOTUNE", "1")
    n = _net()
    assert n.autotune_plan is None and n.layer_variants == {}


def test_digest_mismatch_refused(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    npm = NetParameter.from_text(NET)
    plan = _tiny_plan(npm)
    plan["key"]["net_digest"] = "0" * 16
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan))
    monkeypatch.setenv("COS_AUTOTUNE", str(p))
    n = _net()
    assert n.autotune_plan is None and n.layer_variants == {}
    # force=true applies it anyway (explicit operator override)
    plan["force"] = True
    p.write_text(json.dumps(plan))
    n2 = _net()
    assert n2.layer_variants == {"ip1": {"dtype": "bfloat16"}}


def test_plan_file_env(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    npm = NetParameter.from_text(NET)
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(_tiny_plan(npm)))
    monkeypatch.setenv("COS_AUTOTUNE", str(p))
    n = _net()
    assert n.layer_variants == {"ip1": {"dtype": "bfloat16"}}


# -- variant validation + enumeration -------------------------------------

def test_validate_drops_illegal(monkeypatch):
    _clear_env(monkeypatch)
    plan = {"schema": at.PLAN_SCHEMA, "layers": {
        "ghost": {"dtype": "bfloat16"},           # unknown layer
        "ip1": {"int8": True},                    # int8 on TRAIN net
        "norm1": {"layout": "nhwc"},              # layout on non-conv
        "conv1": {"layout": "nhwc"},              # legal
    }}
    n = _net(autotune=plan)
    assert n.layer_variants == {"conv1": {"layout": "nhwc"}}
    # the same int8 variant IS legal on the TEST-phase net
    n2 = _net(autotune={"schema": at.PLAN_SCHEMA,
                        "layers": {"ip1": {"int8": True}}},
              phase=Phase.TEST)
    assert n2.layer_variants == {"ip1": {"int8": True}}


def test_legal_variants_enumeration(monkeypatch):
    _clear_env(monkeypatch)
    n = _net()
    by_name = {lp.name: lp for lp in n.compute_layers}
    conv = at.legal_variants(n, by_name["conv1"])
    assert {"layout": "nhwc"} in conv
    assert {"layout": "s2d"} in conv          # 3ch stride-2 stem
    assert {"dtype": "bfloat16"} in conv
    lrn = at.legal_variants(n, by_name["norm1"])
    assert {"fuse": "relu"} in lrn
    assert {"fuse": "bias_relu"} in lrn       # conv1 has bias_term
    ip = at.legal_variants(n, by_name["ip1"])
    assert {"dtype": "bfloat16"} in ip
    assert {"int8": True} not in ip           # train mode
    ip_s = at.legal_variants(n, by_name["ip1"], mode="serve")
    assert {"int8": True} in ip_s
    # dtype flips go AGAINST the net-wide policy: a bf16-policy net
    # enumerates the f32 precision pin (Ctx.precision() → HIGHEST)
    n16 = Net(NetParameter.from_text(NET),
              NetState(phase=Phase.TRAIN), compute_dtype=jnp.bfloat16)
    by16 = {lp.name: lp for lp in n16.compute_layers}
    assert {"dtype": "float32"} in at.legal_variants(n16, by16["conv1"])
    assert {"dtype": "float32"} in at.legal_variants(n16, by16["ip1"])


def test_conv_layout_enumeration_tracks_ambient(monkeypatch):
    """Layout candidates are the ones that DIFFER from the env-resolved
    ambient path: under COS_CONV_LAYOUT=NHWC the tuner offers the nchw
    pin-back instead of A/B-ing nhwc against itself."""
    _clear_env(monkeypatch)
    monkeypatch.delenv("COS_CONV_LAYOUT", raising=False)
    monkeypatch.setenv("COS_CONV_S2D", "0")
    n = _net()
    by_name = {lp.name: lp for lp in n.compute_layers}
    plain = at.legal_variants(n, by_name["conv1"])
    assert {"layout": "nhwc"} in plain and {"layout": "nchw"} not in plain
    monkeypatch.setenv("COS_CONV_LAYOUT", "NHWC")
    nhwc = at.legal_variants(n, by_name["conv1"])
    assert {"layout": "nchw"} in nhwc and {"layout": "nhwc"} not in nhwc
    monkeypatch.delenv("COS_CONV_LAYOUT")
    monkeypatch.setenv("COS_CONV_S2D", "1")   # ambient = s2d (eligible)
    s2d = at.legal_variants(n, by_name["conv1"])
    assert {"layout": "s2d"} not in s2d and {"layout": "nchw"} in s2d


def test_plan_records_and_checks_ambient_env(monkeypatch, tmp_path,
                                             caplog):
    """The plan key carries the ambient env knobs it was measured
    under; applying it under a different regime warns (the measured
    uplift/parity described a net nobody is running now)."""
    import logging
    _clear_env(monkeypatch)
    monkeypatch.setenv("COS_AUTOTUNE_CACHE", str(tmp_path))
    npm = NetParameter.from_text(NET)
    plan = at.autotune_net(npm, top_layers=1, measure_iters=1,
                           warmup=0, floor_gbs=0, generalize=False)
    assert plan["key"]["env"] == {}           # tuned in a bare env
    monkeypatch.setenv("COS_AUTOTUNE", "1")
    monkeypatch.setenv("COS_FUSE_RELU_LRN", "1")
    with caplog.at_level(logging.WARNING,
                         logger="caffeonspark_tpu.ops.autotune"):
        n = _net()
    assert n.autotune_plan is not None        # still applies
    assert any("measured under env" in r.message for r in caplog.records)


def test_info_reports_applied_fusion_not_requested(monkeypatch):
    """A force-applied fuse=bias_relu the peephole refuses must not be
    published as applied: info.autotune downgrades it to the fusion
    that actually landed (the self-describing-artifact contract)."""
    _clear_env(monkeypatch)
    shared = """
name: "fuse2"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 2 dim: 6 dim: 5 dim: 5 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "c1"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "c1" top: "r1" }
layer { name: "norm1" type: "LRN" bottom: "r1" top: "norm1"
  lrn_param { local_size: 3 alpha: 0.05 beta: 0.75 } }
layer { name: "pool_extra" type: "Pooling" bottom: "c1"
  top: "pool_extra"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "ip" type: "InnerProduct" bottom: "norm1" top: "ip"
  inner_product_param { num_output: 4
    weight_filler { type: "xavier" } } }"""
    n = _net(text=shared,
             autotune={"schema": at.PLAN_SCHEMA,
                       "layers": {"norm1": {"fuse": "bias_relu"}}})
    assert n.fused_relu_lrn == {"norm1"}      # relu landed
    assert n.fused_bias_lrn == {}             # bias refused
    assert n.layer_variants == {"norm1": {"fuse": "relu"}}
    assert n.autotune_info()["layers"] == {"norm1": {"fuse": "relu"}}


def test_lrn_variants_respect_peephole_eligibility(monkeypatch):
    """A relu top with a second consumer is refused by net.py's
    peephole — the tuner must not enumerate it (and the roofline model
    must not credit it): an inert variant that still earned a modeled
    byte saving would fake an uplift under the injected-floor regime."""
    _clear_env(monkeypatch)
    shared = NET + """
layer { name: "ip_extra" type: "InnerProduct" bottom: "conv1"
  top: "ip_extra" inner_product_param { num_output: 3
    weight_filler { type: "xavier" } } }"""
    n = _net(text=shared)
    by_name = {lp.name: lp for lp in n.compute_layers}
    assert at.legal_variants(n, by_name["norm1"]) == []
    # the candidate build indeed refuses it...
    nf = _net(text=shared,
              autotune={"schema": at.PLAN_SCHEMA,
                        "layers": {"norm1": {"fuse": "relu"}}})
    assert nf.fused_relu_lrn == frozenset()
    # ...and the byte model credits NOTHING for the refused variant
    base = rl.step_bytes_total(n, act_bytes=4, param_bytes=4)
    credited = rl.step_bytes_total(
        n, act_bytes=4, param_bytes=4,
        variants={"norm1": {"fuse": "relu"}})
    assert credited == base


MHA_NET = """
name: "tinyattn"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 8 dim: 2 dim: 16 } } }
layer { name: "attn" type: "MultiHeadAttention" bottom: "data"
  top: "attn" attention_param { num_heads: 2 head_dim: 8 } }
layer { name: "loss" type: "EuclideanLoss" bottom: "attn"
  bottom: "data" top: "loss" }
"""


def test_attention_variant(monkeypatch):
    """MHA enumerates the reference-path variant, and applying it is
    output-identical on CPU (both routes hit the einsum math; on TPU
    the variant pins the A/B partner of the flash dispatch)."""
    _clear_env(monkeypatch)
    n0 = _net(text=MHA_NET)
    by_name = {lp.name: lp for lp in n0.compute_layers}
    assert at.legal_variants(n0, by_name["attn"]) == \
        [{"attention": "reference"}]
    n1 = _net(text=MHA_NET,
              autotune={"schema": at.PLAN_SCHEMA,
                        "layers": {"attn": {"attention": "reference"}}})
    assert n1.layer_variants == {"attn": {"attention": "reference"}}
    p0 = n0.init(jax.random.key(0))
    x = {"data": jnp.asarray(
        np.random.RandomState(0).randn(8, 2, 16).astype(np.float32))}
    b0, _ = n0.apply(p0, x, train=False)
    b1, _ = n1.apply(p0, x, train=False)
    np.testing.assert_array_equal(np.asarray(b0["attn"]),
                                  np.asarray(b1["attn"]))


# -- plan application parity ----------------------------------------------

def _loss_and_grads(net, params, x):
    loss, _ = net.loss(params, x, train=True, rng=jax.random.key(1))
    g = jax.grad(lambda p: net.loss(p, x, train=True,
                                    rng=jax.random.key(1))[0])(params)
    return float(loss), g


def test_fusion_plan_parity(monkeypatch):
    """fuse=relu and fuse=bias_relu plans reproduce the unfused loss
    AND grads (the fused kernels are exact on the XLA fallback path;
    d_bias flows back to the conv through the fused VJP)."""
    _clear_env(monkeypatch)
    n0 = _net()
    p0 = n0.init(jax.random.key(0))
    x = _batch()
    l0, g0 = _loss_and_grads(n0, p0, x)
    for fuse in ("relu", "bias_relu"):
        n1 = _net(autotune={"schema": at.PLAN_SCHEMA,
                            "layers": {"norm1": {"fuse": fuse}}})
        assert "norm1" in n1.fused_relu_lrn
        assert (n1.fused_bias_lrn == {"norm1": "conv1"}) \
            == (fuse == "bias_relu")
        l1, g1 = _loss_and_grads(n1, p0, x)
        np.testing.assert_allclose(l1, l0, rtol=1e-5)
        for a, b in zip(_leaves(g0), _leaves(g1)):
            np.testing.assert_allclose(b, a, rtol=2e-4, atol=1e-5)


def test_layout_and_dtype_plan_parity(monkeypatch):
    _clear_env(monkeypatch)
    n0 = _net()
    p0 = n0.init(jax.random.key(0))
    x = _batch()
    l0, _ = _loss_and_grads(n0, p0, x)
    n1 = _net(autotune={"schema": at.PLAN_SCHEMA, "layers": {
        "conv1": {"layout": "s2d"},
        "ip1": {"dtype": "bfloat16"}}})
    l1, _ = _loss_and_grads(n1, p0, x)
    # s2d is float-rounding; the bf16 fc bounds the drift
    np.testing.assert_allclose(l1, l0, rtol=2e-2)


def test_int8_serving_forward(monkeypatch):
    """int8 InnerProduct on the TEST net: output within the quantized
    tolerance of the f32 forward (per-blob max-abs scales)."""
    _clear_env(monkeypatch)
    n0 = _net(phase=Phase.TEST)
    n1 = _net(autotune={"schema": at.PLAN_SCHEMA,
                        "layers": {"ip1": {"int8": True},
                                   "ip2": {"int8": True}}},
              phase=Phase.TEST)
    p0 = n0.init(jax.random.key(0))
    x = _batch()
    b0, _ = n0.apply(p0, x, train=False)
    b1, _ = n1.apply(p0, x, train=False)
    ref = np.asarray(b0["ip2"], np.float32)
    got = np.asarray(b1["ip2"], np.float32)
    assert not np.array_equal(ref, got)       # it actually quantized
    rel = np.max(np.abs(ref - got)) / (np.max(np.abs(ref)) + 1e-6)
    assert rel < 0.08, rel


# -- roofline model --------------------------------------------------------

def test_roofline_rows_and_bounds(monkeypatch):
    _clear_env(monkeypatch)
    n = _net()
    rows = rl.classify(rl.analyze_net(n, act_bytes=4, param_bytes=4))
    assert rows[0]["t_us"] >= rows[-1]["t_us"]
    by = {r["layer"]: r for r in rows}
    assert by["norm1"]["bound"] == "hbm"      # LRN: no FLOPs modeled
    assert all(r["t_us"] == max(r["t_flop_us"], r["t_mem_us"])
               for r in rows)


def test_roofline_variant_costing(monkeypatch):
    """The plan-aware byte model: bf16 halves a layer's act+param
    read, int8 quarters the param read, fusion drops the relu row —
    all without building the variant net."""
    _clear_env(monkeypatch)
    n = _net()
    base = rl.step_bytes_total(n, act_bytes=4, param_bytes=4)
    bf16 = rl.step_bytes_total(
        n, act_bytes=4, param_bytes=4,
        variants={"ip1": {"dtype": "bfloat16"}})
    assert bf16 < base
    i8 = rl.step_bytes_total(n, act_bytes=4, param_bytes=4,
                             variants={"ip1": {"int8": True}})
    # ip1 is param-dominated: the 1-byte param read undercuts even the
    # bf16 variant (which also halves the smaller activation traffic)
    assert i8 < bf16 < base
    # a fuse variant costed on the UNFUSED net drops the feeding relu
    # row — the tuner can price a fusion candidate without building it
    fuse_cost = rl.step_bytes_total(
        n, act_bytes=4, param_bytes=4,
        variants={"norm1": {"fuse": "relu"}})
    assert fuse_cost < base
    # ...and a net BUILT with the fusion (relu removed from
    # compute_layers) agrees with that costing exactly
    nf = _net(autotune={"schema": at.PLAN_SCHEMA,
                        "layers": {"norm1": {"fuse": "relu"}}})
    fused = rl.step_bytes_total(nf, act_bytes=4, param_bytes=4,
                                variants=nf.layer_variants)
    assert fused == fuse_cost


def test_peak_table(monkeypatch):
    peak, src = rl.peak_tflops_for_kind("TPU v5e")
    assert peak == 197.0 and src.startswith("device_kind:")
    with pytest.raises(ValueError, match="weird chip"):
        rl.peak_tflops_for_kind("weird chip")
    assert rl.SCHEMA == "cos-roofline" and rl.MODEL_VERSION >= 2


# -- the tuner end to end --------------------------------------------------

def test_autotune_net_produces_reloadable_plan(monkeypatch, tmp_path):
    _clear_env(monkeypatch)
    monkeypatch.setenv("COS_AUTOTUNE_CACHE", str(tmp_path))
    npm = NetParameter.from_text(NET)
    plan = at.autotune_net(npm, top_layers=2, measure_iters=1,
                           warmup=1, floor_gbs=2.0)
    assert plan["schema"] == at.PLAN_SCHEMA
    assert plan["key"]["net_digest"] == at.net_digest(npm)
    m = plan["measured"]
    assert m["baseline_steps_per_sec"] > 0
    assert m["per_layer"], "no variants were measured"
    for r in m["per_layer"]:
        assert r["layer"] and r["variant"]
        if "error" not in r:
            assert r["parity_max_rel_diff"] >= 0
    # every accepted variant held the pinned tolerance
    for r in m["per_layer"]:
        if r.get("accepted"):
            assert r["parity_max_rel_diff"] <= plan["tolerance"]
    # the cache slot reloads through COS_AUTOTUNE=1
    monkeypatch.setenv("COS_AUTOTUNE", "1")
    n = _net()
    assert (n.layer_variants == plan["layers"])
    info = n.autotune_info()
    assert info["active"] and info["measured"]["uplift"] == \
        plan["measured"]["uplift"]


def test_autotune_info_shape(monkeypatch):
    """info.autotune (metrics set_info payload) is JSON-serializable
    and carries key/layers — the self-describing artifact contract."""
    _clear_env(monkeypatch)
    npm = NetParameter.from_text(NET)
    n = _net(autotune=_tiny_plan(npm))
    info = n.autotune_info()
    json.dumps(info)
    assert info["active"] is True
    assert info["layers"] == {"ip1": {"dtype": "bfloat16"}}
    assert info["key"]["net_digest"] == at.net_digest(npm)
