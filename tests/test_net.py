"""Net compiler tests: shape inference, phase filtering, forward pass on
the reference model zoo configs (LeNet, CIFAR-10 quick, CaffeNet, LRCN)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.net import Net
from caffeonspark_tpu.proto import NetParameter, NetState, Phase, read_net

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REF_DATA = "/root/reference/data"
HAS_REF = os.path.isdir(REF_DATA)


def test_deconvolution_fcn_upsample():
    """FCN-style deconv k=4 s=2 p=1 doubles spatial dims; bilinear
    upsampling of a constant field is constant (grouped, no bias)."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx, _deconv_params
    from caffeonspark_tpu.ops.fillers import fill
    lp = LayerParameter.from_text(
        'name: "up" type: "Deconvolution" bottom: "x" top: "y" '
        'convolution_param { num_output: 2 kernel_size: 4 stride: 2 pad: 1 '
        'group: 2 bias_term: false weight_filler { type: "bilinear" } }')
    specs = _deconv_params(lp, [(1, 2, 8, 8)])
    w = fill(jax.random.key(0), specs[0][2], specs[0][1])
    y = get_op("Deconvolution").apply(Ctx(), lp, [w],
                                      [jnp.ones((1, 2, 8, 8))])[0]
    assert y.shape == (1, 2, 16, 16)
    assert float(y[0, 0, 8, 8]) == pytest.approx(1.0)


def test_scale_two_bottom_bias():
    """Two-bottom Scale: multiplier is bottom[1]; only bias is learnable."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx, _scale_params
    lp = LayerParameter.from_text(
        'name: "s" type: "Scale" bottom: "x" bottom: "g" top: "y" '
        'scale_param { axis: 1 bias_term: true }')
    specs = _scale_params(lp, [(2, 3, 4, 4), (3,)])
    assert [s[0] for s in specs] == ["bias"]
    bias = jnp.array([1.0, 2.0, 3.0])
    x = jnp.ones((2, 3, 4, 4))
    g = jnp.array([2.0, 2.0, 2.0])
    y = get_op("Scale").apply(Ctx(), lp, [bias], [x, g])[0]
    assert float(y[0, 0, 0, 0]) == pytest.approx(3.0)  # 1*2 + 1
    assert float(y[0, 2, 0, 0]) == pytest.approx(5.0)  # 1*2 + 3


def test_init_deterministic_across_runs():
    """Same seed → identical init (stable_hash, not randomized hash())."""
    import subprocess, sys
    code = (
        f"import sys; sys.path.insert(0, {REPO!r});"
        "import os; os.environ['JAX_PLATFORMS']='cpu';"
        "import jax;"
        "from caffeonspark_tpu.net import Net;"
        "from caffeonspark_tpu.proto import NetParameter;"
        "n = Net(NetParameter.from_text('''"
        "layer { name: \"d\" type: \"MemoryData\" top: \"data\" "
        "memory_data_param { batch_size: 1 channels: 1 height: 4 width: 4 } }"
        "layer { name: \"ip\" type: \"InnerProduct\" bottom: \"data\" "
        "top: \"y\" inner_product_param { num_output: 2 "
        "weight_filler { type: \"gaussian\" std: 1.0 } } }'''));"
        "p = n.init(jax.random.key(7));"
        "print(float(p['ip']['weight'][0, 0]))")
    outs = set()
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={**os.environ, "PYTHONHASHSEED": "random"})
        assert r.returncode == 0, r.stderr[-500:]
        outs.add(r.stdout.strip().splitlines()[-1])
    assert len(outs) == 1, f"nondeterministic init: {outs}"


def test_slice_indivisible_raises():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "s" type: "Slice" bottom: "x" top: "a" top: "b" top: "c" '
        'slice_param { axis: 1 }')
    with pytest.raises(ValueError, match="not divisible"):
        get_op("Slice").apply(Ctx(), lp, [], [jnp.ones((2, 10))])


def test_fcn_deconv_segmentation_trains():
    """FCN-style dense prediction: conv encoder → Deconvolution
    upsample → Crop to input size → per-pixel SoftmaxWithLoss; the
    Deconvolution/Crop backward path trains end-to-end."""
    npm = NetParameter.from_text("""
name: "mini_fcn"
layer { name: "data" type: "Input" top: "data" top: "label"
  input_param { shape { dim: 2 dim: 1 dim: 16 dim: 16 }
                shape { dim: 2 dim: 16 dim: 16 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1 stride: 2
    weight_filler { type: "msra" } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "score" type: "Convolution" bottom: "conv1" top: "score"
  convolution_param { num_output: 3 kernel_size: 1
    weight_filler { type: "xavier" } } }
layer { name: "upscore" type: "Deconvolution" bottom: "score"
  top: "upscore"
  convolution_param { num_output: 3 kernel_size: 4 stride: 2 pad: 1
    bias_term: false weight_filler { type: "bilinear" } } }
layer { name: "crop" type: "Crop" bottom: "upscore" bottom: "data"
  top: "cropped" crop_param { axis: 2 } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "cropped"
  bottom: "label" top: "loss"
  loss_param { ignore_label: -1 } softmax_param { axis: 1 } }
""")
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.3 momentum: 0.9 lr_policy: 'fixed' random_seed: 2"),
        npm)
    assert s.train_net.blob_shapes["upscore"] == (2, 3, 16, 16)
    assert s.train_net.blob_shapes["cropped"] == (2, 3, 16, 16)
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(2, 1, 16, 16), jnp.float32)
    # per-pixel labels: left half class 0, right half class 1
    lab = np.zeros((2, 16, 16), np.float32)
    lab[:, :, 8:] = 1.0
    lab_j = jnp.asarray(lab)
    losses = []
    for i in range(120):
        params, st, out = step(params, st,
                               {"data": x, "label": lab_j},
                               s.step_rng(i))
        losses.append(float(out["loss"]))
    assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])


def test_infogain_and_mll_losses():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    probs = jnp.asarray([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]])
    labels = jnp.asarray([0.0, 1.0])
    mll = get_op("MultinomialLogisticLoss").apply(
        Ctx(), LayerParameter.from_text(
            'name: "l" type: "MultinomialLogisticLoss" bottom: "p" '
            'bottom: "y" top: "loss"'), [], [probs, labels])[0]
    expect = -(np.log(0.7) + np.log(0.8)) / 2
    assert float(mll) == pytest.approx(expect, rel=1e-6)
    # identity infogain == MLL
    lp = LayerParameter.from_text(
        'name: "l" type: "InfogainLoss" bottom: "p" bottom: "y" '
        'top: "loss"')
    ig = get_op("InfogainLoss").apply(Ctx(), lp, [], [probs, labels])[0]
    assert float(ig) == pytest.approx(expect, rel=1e-6)
    # off-diagonal H penalizes confusing class 0 with class 1
    h = jnp.asarray([[1.0, 0.5, 0.0], [0.0, 1.0, 0.0], [0.0, 0.0, 1.0]])
    ig2 = get_op("InfogainLoss").apply(Ctx(), lp, [],
                                       [probs, labels, h])[0]
    expect2 = -((np.log(0.7) + 0.5 * np.log(0.2)) + np.log(0.8)) / 2
    assert float(ig2) == pytest.approx(expect2, rel=1e-6)


def test_loss_normalize_legacy():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    base = ('name: "l" type: "SoftmaxWithLoss" bottom: "x" bottom: "lab" '
            'top: "loss" ')
    x = jnp.zeros((4, 3, 2))  # (N, C, spatial): FULL count 8, batch 4
    lab = jnp.zeros((4, 2))
    loss_valid = get_op("SoftmaxWithLoss").apply(
        Ctx(), LayerParameter.from_text(base), [], [x, lab])[0]
    loss_bs = get_op("SoftmaxWithLoss").apply(
        Ctx(), LayerParameter.from_text(
            base + 'loss_param { normalize: false }'), [], [x, lab])[0]
    assert float(loss_bs) == pytest.approx(2 * float(loss_valid), rel=1e-6)

LENET = """
name: "LeNet"
layer {
  name: "data" type: "MemoryData" top: "data" top: "label"
  include { phase: TRAIN }
  memory_data_param { batch_size: 8 channels: 1 height: 28 width: 28 }
}
layer {
  name: "data" type: "MemoryData" top: "data" top: "label"
  include { phase: TEST }
  memory_data_param { batch_size: 4 channels: 1 height: 28 width: 28 }
}
layer {
  name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 20 kernel_size: 5 stride: 1
    weight_filler { type: "xavier" } }
}
layer {
  name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 }
}
layer {
  name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
  inner_product_param { num_output: 500 weight_filler { type: "xavier" } }
}
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer {
  name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param { num_output: 10 weight_filler { type: "xavier" } }
}
layer {
  name: "accuracy" type: "Accuracy" bottom: "ip2" bottom: "label"
  top: "accuracy" include { phase: TEST }
}
layer {
  name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label"
  top: "loss"
}
"""


def test_phase_filtering():
    np_ = NetParameter.from_text(LENET)
    train = Net(np_, NetState(phase=Phase.TRAIN))
    test = Net(np_, NetState(phase=Phase.TEST))
    train_names = [lp.name for lp in train.compute_layers]
    test_names = [lp.name for lp in test.compute_layers]
    assert "accuracy" not in train_names
    assert "accuracy" in test_names
    # batch size comes from the phase's own data layer
    assert dict((n, s) for n, s, _ in train.input_specs)["data"][0] == 8
    assert dict((n, s) for n, s, _ in test.input_specs)["data"][0] == 4


def test_shape_inference_and_forward():
    np_ = NetParameter.from_text(LENET)
    net = Net(np_, NetState(phase=Phase.TRAIN))
    assert net.blob_shapes["conv1"] == (8, 20, 24, 24)
    assert net.blob_shapes["pool1"] == (8, 20, 12, 12)
    assert net.blob_shapes["ip1"] == (8, 500)
    assert net.blob_shapes["ip2"] == (8, 10)
    assert net.blob_shapes["loss"] == ()
    params = net.init(jax.random.key(0))
    assert params["conv1"]["weight"].shape == (20, 1, 5, 5)
    assert params["conv1"]["bias"].shape == (20,)
    inputs = {"data": jnp.ones((8, 1, 28, 28)),
              "label": jnp.zeros((8,))}
    blobs, _ = net.apply(params, inputs)
    assert blobs["loss"].shape == ()
    assert np.isfinite(float(blobs["loss"]))
    # loss ≈ log(10) at init for 10-way uniform-ish outputs
    assert 0.5 < float(blobs["loss"]) < 5.0


def test_loss_and_grad():
    np_ = NetParameter.from_text(LENET)
    net = Net(np_, NetState(phase=Phase.TRAIN))
    params = net.init(jax.random.key(0))
    inputs = {"data": jnp.ones((8, 1, 28, 28)), "label": jnp.zeros((8,))}
    (loss, _), grads = jax.value_and_grad(net.loss, has_aux=True)(
        params, inputs)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(g * g)) for lb in grads.values()
                for g in lb.values())
    assert gnorm > 0


def test_net_outputs():
    np_ = NetParameter.from_text(LENET)
    net = Net(np_, NetState(phase=Phase.TEST))
    assert set(net.output_blobs) == {"accuracy", "loss"}


def test_pooling_ceil_mode():
    # CIFAR pool: 32→ceil((32-3)/2)+1 = 16 (+1 if tail window)
    from caffeonspark_tpu.ops.layers import pool_output_dim
    assert pool_output_dim(32, 3, 2, 0) == 16
    assert pool_output_dim(28, 2, 2, 0) == 14
    # AlexNet: 55 →  pool 3 stride 2 → 27 (caffe ceil mode: 27.0 → 27+1=28?
    # ceil((55-3)/2)+1 = 27
    assert pool_output_dim(55, 3, 2, 0) == 27
    # with padding, tail clip: size 6, k 3, s 2, pad 1 → ceil(6/2)+1=4
    # but (4-1)*2=6 >= 6+1? no → stays 4
    assert pool_output_dim(6, 3, 2, 1) == 4


def test_ave_pooling_divisor():
    """Caffe AVE divisor counts window ∩ padded region."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "p" type: "Pooling" bottom: "x" top: "y" '
        'pooling_param { pool: AVE kernel_size: 3 stride: 2 pad: 1 }')
    x = jnp.ones((1, 1, 4, 4))
    tops = get_op("Pooling").apply(Ctx(), lp, [], [x])
    y = np.asarray(tops[0])
    # out = ceil((4+2-3)/2)+1 = 3; corner window covers 2x2 real pixels,
    # divisor = 3x3 (fully inside the padded region) → 4/9
    assert y.shape == (1, 1, 3, 3)
    assert y[0, 0, 0, 0] == pytest.approx(4.0 / 9.0)
    assert y[0, 0, 1, 1] == pytest.approx(1.0)


def test_contrastive_loss():
    """Caffe contrastive_loss_layer semantics, modern + legacy."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    rs = np.random.RandomState(1)
    a = rs.randn(6, 4).astype(np.float32)
    b = rs.randn(6, 4).astype(np.float32)
    y = np.array([1, 0, 1, 0, 1, 0], np.float32)
    lp = LayerParameter.from_text(
        'name: "cl" type: "ContrastiveLoss" bottom: "a" bottom: "b" '
        'bottom: "y" top: "l" contrastive_loss_param { margin: 2.0 }')
    got = float(get_op("ContrastiveLoss").apply(
        Ctx(), lp, [], [jnp.asarray(a), jnp.asarray(b),
                        jnp.asarray(y)])[0])
    d = np.linalg.norm(a - b, axis=1)
    want = np.mean(y * d ** 2
                   + (1 - y) * np.maximum(2.0 - d, 0) ** 2) / 2.0
    assert got == pytest.approx(want, rel=1e-5)
    lp2 = LayerParameter.from_text(
        'name: "cl" type: "ContrastiveLoss" bottom: "a" bottom: "b" '
        'bottom: "y" top: "l" contrastive_loss_param { margin: 2.0 '
        'legacy_version: true }')
    got2 = float(get_op("ContrastiveLoss").apply(
        Ctx(), lp2, [], [jnp.asarray(a), jnp.asarray(b),
                         jnp.asarray(y)])[0])
    want2 = np.mean(y * d ** 2
                    + (1 - y) * np.maximum(2.0 - d ** 2, 0)) / 2.0
    assert got2 == pytest.approx(want2, rel=1e-5)


def test_parameter_and_batch_reindex_and_spp():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    # Parameter: top is the learnable blob itself
    lp = LayerParameter.from_text(
        'name: "w" type: "Parameter" top: "w" '
        'parameter_param { shape { dim: 3 dim: 2 } } ')
    specs = get_op("Parameter").param_specs(lp, [])
    assert specs[0][1] == (3, 2)
    w = jnp.arange(6.0).reshape(3, 2)
    assert get_op("Parameter").apply(Ctx(), lp, [w], [])[0] is w
    # BatchReindex: gather along batch
    lp = LayerParameter.from_text(
        'name: "r" type: "BatchReindex" bottom: "x" bottom: "i" top: "y"')
    x = jnp.arange(12.0).reshape(4, 3)
    idx = jnp.asarray([2.0, 0.0, 2.0])
    y = np.asarray(get_op("BatchReindex").apply(Ctx(), lp, [], [x, idx])[0])
    np.testing.assert_allclose(y, np.asarray(x)[[2, 0, 2]])
    # SPP: pyramid_height 3 → 1+4+16 bins per channel; level 0 = global
    lp = LayerParameter.from_text(
        'name: "s" type: "SPP" bottom: "x" top: "y" '
        'spp_param { pyramid_height: 3 }')
    rs = np.random.RandomState(0)
    xi = jnp.asarray(rs.rand(2, 5, 9, 7).astype(np.float32))
    out = np.asarray(get_op("SPP").apply(Ctx(), lp, [], [xi])[0])
    assert out.shape == (2, 5 * (1 + 4 + 16))
    np.testing.assert_allclose(out[:, :5],
                               np.asarray(xi).max(axis=(2, 3)), rtol=1e-6)
    # level 1 (2x2 bins) on 9x7: kernel (5,4), SYMMETRIC pad
    # (rem+1)/2 = (1,1) both sides like Caffe spp_layer.cpp
    # GetPoolingParam — windows start at -pad, not 0
    xa = np.asarray(xi)
    want = np.empty((2, 5, 2, 2), np.float32)
    for ph in range(2):
        for pw in range(2):
            hs, ws = ph * 5 - 1, pw * 4 - 1
            want[:, :, ph, pw] = xa[:, :, max(hs, 0):min(hs + 5, 9),
                                    max(ws, 0):min(ws + 4, 7)
                                    ].max(axis=(2, 3))
    np.testing.assert_allclose(out[:, 5:25].reshape(2, 5, 2, 2), want,
                               rtol=1e-6)


def test_moe_capacity_drop_and_aux_loss():
    """Capacity-factor dispatch (Switch-style): with every token routed
    to one expert and capacity_factor 1.0, only C = k*N/E tokens fit;
    overflow tokens produce ZERO output (dropped, not densely
    computed), and the balance aux loss reads ~E for total skew vs ~1
    for uniform routing."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "m" type: "MixtureOfExperts" bottom: "x" top: "y" '
        'top: "aux" loss_weight: 0 loss_weight: 0.01 '
        'moe_param { num_experts: 4 hidden_dim: 8 capacity_factor: 1.0 }')
    rs = np.random.RandomState(0)
    n, d, e = 32, 6, 4
    x = jnp.asarray(rs.rand(n, d).astype(np.float32) + 0.1)
    # router forces every token to expert 1
    router = np.zeros((d, e), np.float32)
    router[:, 1] = 5.0
    w1 = jnp.asarray(rs.randn(e, d, 8).astype(np.float32) * 0.3)
    w2 = jnp.asarray(rs.randn(e, 8, d).astype(np.float32) * 0.3)
    out, aux = get_op("MixtureOfExperts").apply(
        Ctx(), lp, [jnp.asarray(router), w1, w2], [x])
    out = np.asarray(out)
    cap = 8                                  # ceil(1*32/4*1.0)
    assert np.abs(out[:cap]).sum() > 0
    np.testing.assert_array_equal(out[cap:], 0.0)
    assert float(aux) > 2.0                  # ~E at total skew
    # uniform-ish routing: aux near 1
    router2 = rs.randn(d, e).astype(np.float32) * 0.01
    _, aux2 = get_op("MixtureOfExperts").apply(
        Ctx(), lp, [jnp.asarray(router2), w1, w2], [x])
    assert 0.8 < float(aux2) < 1.5


def test_moe_top2_matches_dense_reference():
    """top_k=2 with ample capacity == the dense per-token computation:
    normalized top-2 gates over each chosen expert's FFN."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "m" type: "MixtureOfExperts" bottom: "x" top: "y" '
        'moe_param { num_experts: 4 hidden_dim: 8 top_k: 2 '
        'capacity_factor: 4.0 }')
    rs = np.random.RandomState(1)
    n, d, e = 16, 5, 4
    x = rs.rand(n, d).astype(np.float32)
    router = rs.randn(d, e).astype(np.float32)
    w1 = rs.randn(e, d, 8).astype(np.float32) * 0.3
    w2 = rs.randn(e, 8, d).astype(np.float32) * 0.3
    (out,) = get_op("MixtureOfExperts").apply(
        Ctx(), lp, [jnp.asarray(router), jnp.asarray(w1),
                    jnp.asarray(w2)], [jnp.asarray(x)])
    out = np.asarray(out)

    logits = x @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for i in range(n):
        top2 = np.argsort(p[i])[::-1][:2]
        gsum = p[i][top2].sum()
        for ex in top2:
            h = np.maximum(x[i] @ w1[ex], 0.0)
            want[i] += (p[i][ex] / gsum) * (h @ w2[ex])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


def test_space_to_depth_stem_conv():
    """_s2d_conv must equal the direct strided conv exactly (same
    arithmetic reordered): AlexNet conv1 (11x11s4 no pad) and ResNet
    stem (7x7s2 pad 3) geometries, fwd and grads."""
    from caffeonspark_tpu.ops.layers import _s2d_conv
    rs = np.random.RandomState(3)
    for (cin, cout, k, s, p, hw) in [(3, 96, 11, 4, 0, 227),
                                     (3, 64, 7, 2, 3, 56),
                                     (4, 32, 5, 3, 1, 30)]:
        x = jnp.asarray(rs.randn(2, cin, hw, hw).astype(np.float32))
        w = jnp.asarray(rs.randn(cout, cin, k, k).astype(np.float32) * 0.1)
        ref = jax.lax.conv_general_dilated(
            x, w, (s, s), [(p, p), (p, p)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        got = _s2d_conv(x, w, s, k, k, p, p)
        assert got.shape == ref.shape, (got.shape, ref.shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-4)
        # gradients agree too (the transform is linear in both args)
        g_ref = jax.grad(lambda a, b: jnp.sum(jax.lax.conv_general_dilated(
            a, b, (s, s), [(p, p), (p, p)],
            dimension_numbers=("NCHW", "OIHW", "NCHW")) ** 2),
            argnums=(0, 1))(x, w)
        g_got = jax.grad(
            lambda a, b: jnp.sum(_s2d_conv(a, b, s, k, k, p, p) ** 2),
            argnums=(0, 1))(x, w)
        for a, b in zip(g_ref, g_got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=2e-4, atol=2e-2)


def test_s2d_conv_layer_path(monkeypatch):
    """The Convolution layer takes the s2d path when forced on and
    matches the direct path on the real conv1 layer parameters."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "conv1" type: "Convolution" bottom: "data" top: "conv1" '
        'convolution_param { num_output: 16 kernel_size: 11 stride: 4 }')
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(2, 3, 67, 67).astype(np.float32))
    w = jnp.asarray(rs.randn(16, 3, 11, 11).astype(np.float32) * 0.05)
    b = jnp.asarray(rs.randn(16).astype(np.float32))
    monkeypatch.setenv("COS_CONV_S2D", "0")
    y0 = get_op("Convolution").apply(Ctx(), lp, [w, b], [x])[0]
    monkeypatch.setenv("COS_CONV_S2D", "1")
    y1 = get_op("Convolution").apply(Ctx(), lp, [w, b], [x])[0]
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=2e-5, atol=2e-4)


def test_s2d_conv_layer_grad_parity(monkeypatch):
    """Backward parity pin for the s2d stem rewrite through the layer
    op: input AND weight gradients match the direct strided conv —
    the autotuner composes this variant, so it is pinned individually
    (forward parity is test_s2d_conv_layer_path)."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "conv1" type: "Convolution" bottom: "data" top: "conv1" '
        'convolution_param { num_output: 16 kernel_size: 11 stride: 4 }')
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.rand(2, 3, 67, 67).astype(np.float32))
    w = jnp.asarray(rs.randn(16, 3, 11, 11).astype(np.float32) * 0.05)
    b = jnp.asarray(rs.randn(16).astype(np.float32))
    op = get_op("Convolution")

    def loss(a, p):
        return jnp.sum(op.apply(Ctx(), lp, [p, b], [a])[0] ** 2)

    monkeypatch.setenv("COS_CONV_S2D", "0")
    g0 = jax.grad(loss, argnums=(0, 1))(x, w)
    monkeypatch.setenv("COS_CONV_S2D", "1")
    g1 = jax.grad(loss, argnums=(0, 1))(x, w)
    for a, bb in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                   rtol=2e-4, atol=2e-3)


def test_nhwc_conv_layout_parity(monkeypatch):
    """COS_CONV_LAYOUT=NHWC (layout A/B lever) matches the default NCHW
    path — forward and grads — across plain/strided/grouped/dilated
    convs.  The NHWC wrapper only re-expresses the conv's dimension
    numbers; XLA folds the boundary transposes."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    cases = [
        ("num_output: 12 kernel_size: 5 stride: 3", (2, 3, 31, 31),
         (12, 3, 5, 5)),
        ("num_output: 8 kernel_size: 3 pad: 1 group: 2", (2, 4, 9, 9),
         (8, 2, 3, 3)),
        ("num_output: 6 kernel_size: 3 dilation: 2", (1, 5, 13, 13),
         (6, 5, 3, 3)),
    ]
    op = get_op("Convolution")
    for txt, xs, ws in cases:
        lp = LayerParameter.from_text(
            'name: "c" type: "Convolution" bottom: "d" top: "c" '
            "convolution_param { %s }" % txt)
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.rand(*xs).astype(np.float32))
        w = jnp.asarray(rs.randn(*ws).astype(np.float32) * 0.1)
        b = jnp.asarray(rs.randn(ws[0]).astype(np.float32))

        def loss(a, p):
            return jnp.sum(op.apply(Ctx(), lp, [p, b], [a])[0] ** 2)

        monkeypatch.setenv("COS_CONV_LAYOUT", "NCHW")
        y0, g0 = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        monkeypatch.setenv("COS_CONV_LAYOUT", "NHWC")
        y1, g1 = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(float(y1), float(y0), rtol=1e-4)
        for a, bb in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(bb), np.asarray(a),
                                       rtol=2e-4, atol=2e-3)


def test_stochastic_pooling():
    """Caffe PoolForward{Test,Train}: test = sum(a^2)/sum(a); train samples
    one in-window activation with probability proportional to its value."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "p" type: "Pooling" bottom: "x" top: "y" '
        'pooling_param { pool: STOCHASTIC kernel_size: 2 stride: 2 }')
    x = jnp.asarray(np.random.RandomState(0).rand(2, 3, 4, 4).astype(
        np.float32))
    # TEST phase: weighted mean, checked against a direct loop
    y = np.asarray(get_op("Pooling").apply(Ctx(train=False), lp, [], [x])[0])
    xn = np.asarray(x)
    for n in range(2):
        for c in range(3):
            for i in range(2):
                for j in range(2):
                    w = xn[n, c, 2 * i:2 * i + 2, 2 * j:2 * j + 2]
                    assert y[n, c, i, j] == pytest.approx(
                        (w * w).sum() / w.sum(), rel=1e-5)
    # all-zero window must produce 0, not NaN
    z = get_op("Pooling").apply(Ctx(train=False), lp, [],
                                [jnp.zeros((1, 1, 2, 2))])[0]
    assert float(z[0, 0, 0, 0]) == 0.0
    # TRAIN phase: every output is an element of its window, and the
    # empirical sampling frequency tracks value/sum(window)
    key = jax.random.PRNGKey(7)
    lp2 = LayerParameter.from_text(
        'name: "p" type: "Pooling" bottom: "x" top: "y" '
        'pooling_param { pool: STOCHASTIC kernel_size: 2 stride: 2 }')
    win = jnp.asarray([[1.0, 3.0], [2.0, 4.0]]).reshape(1, 1, 2, 2)
    picks = []
    for s in range(400):
        ctx = Ctx(train=True, rng=jax.random.fold_in(key, s),
                  layer_name="p")
        out = get_op("Pooling").apply(ctx, lp2, [], [win])[0]
        v = float(out[0, 0, 0, 0])
        assert v in (1.0, 2.0, 3.0, 4.0)
        picks.append(v)
    freq4 = picks.count(4.0) / len(picks)
    assert 0.3 < freq4 < 0.5  # p=0.4
    # gradient routes to the sampled element only (one-hot)
    g = jax.grad(lambda t: get_op("Pooling").apply(
        Ctx(train=True, rng=key, layer_name="p"), lp2, [], [t])[0].sum())(win)
    gn = np.asarray(g).ravel()
    assert sorted(gn) == [0.0, 0.0, 0.0, 1.0]


def test_lrn_across_channels():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "n" type: "LRN" bottom: "x" top: "y" '
        'lrn_param { local_size: 5 alpha: 0.0001 beta: 0.75 }')
    x = jnp.ones((2, 8, 3, 3))
    y = get_op("LRN").apply(Ctx(), lp, [], [x])[0]
    # center channels: scale = 1 + alpha/5*5 = 1.0001
    expect = 1.0 / (1 + 0.0001) ** 0.75
    assert float(y[0, 4, 0, 0]) == pytest.approx(expect, rel=1e-5)


def test_dropout_train_vs_test():
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx
    lp = LayerParameter.from_text(
        'name: "d" type: "Dropout" bottom: "x" top: "y" '
        'dropout_param { dropout_ratio: 0.5 }')
    x = jnp.ones((4, 100))
    y_test = get_op("Dropout").apply(Ctx(train=False), lp, [], [x])[0]
    assert np.allclose(np.asarray(y_test), 1.0)
    ctx = Ctx(train=True, rng=jax.random.key(1), layer_name="d")
    y_train = np.asarray(get_op("Dropout").apply(ctx, lp, [], [x])[0])
    assert set(np.unique(y_train)).issubset({0.0, 2.0})
    assert 0.3 < (y_train == 0).mean() < 0.7


def test_lstm_cont_gating():
    """cont=0 at t must reset state: output at t equals output of a fresh
    sequence start."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx, _lstm_params
    lp = LayerParameter.from_text(
        'name: "l" type: "LSTM" bottom: "x" bottom: "cont" top: "h" '
        'recurrent_param { num_output: 4 weight_filler { type: "uniform" '
        'min: -0.1 max: 0.1 } } ')
    from caffeonspark_tpu.ops.fillers import fill
    specs = _lstm_params(lp, [(6, 2, 3), (6, 2)])
    key = jax.random.key(0)
    params = [fill(jax.random.fold_in(key, i), f, s)
              for i, (_, s, f) in enumerate(specs)]
    x = jax.random.normal(jax.random.key(1), (6, 2, 3))
    cont = jnp.ones((6, 2)).at[0].set(0.0).at[3].set(0.0)
    h = get_op("LSTM").apply(Ctx(), lp, params, [x, cont])[0]
    assert h.shape == (6, 2, 4)
    # restart at t=3 ≡ fresh run starting from x[3:]
    h2 = get_op("LSTM").apply(Ctx(), lp, params,
                              [x[3:], jnp.ones((3, 2)).at[0].set(0.0)])[0]
    np.testing.assert_allclose(np.asarray(h[3:]), np.asarray(h2),
                               rtol=1e-5)


def test_lstm_expose_hidden_chunked_equals_full():
    """Running T=8 in one pass must equal two T=4 chunks with the
    exposed (c,h) state handed across (expose_hidden parity)."""
    from caffeonspark_tpu.proto.caffe import LayerParameter
    from caffeonspark_tpu.ops.layers import get_op, Ctx, _lstm_params
    from caffeonspark_tpu.ops.fillers import fill
    lp_full = LayerParameter.from_text(
        'name: "l" type: "LSTM" bottom: "x" bottom: "cont" top: "h" '
        'recurrent_param { num_output: 4 weight_filler { type: "uniform"'
        ' min: -0.2 max: 0.2 } }')
    lp_exp = LayerParameter.from_text(
        'name: "l" type: "LSTM" bottom: "x" bottom: "cont" '
        'bottom: "h0" bottom: "c0" top: "h" top: "hT" top: "cT" '
        'recurrent_param { num_output: 4 expose_hidden: true '
        'weight_filler { type: "uniform" min: -0.2 max: 0.2 } }')
    specs = _lstm_params(lp_full, [(8, 2, 3), (8, 2)])
    key = jax.random.key(5)
    params = [fill(jax.random.fold_in(key, i), f, s)
              for i, (_, s, f) in enumerate(specs)]
    x = jax.random.normal(jax.random.key(6), (8, 2, 3))
    cont = jnp.ones((8, 2)).at[0].set(0.0)
    h_full = get_op("LSTM").apply(Ctx(), lp_full, params, [x, cont])[0]
    z = jnp.zeros((1, 2, 4))
    h1, hT1, cT1 = get_op("LSTM").apply(
        Ctx(), lp_exp, params, [x[:4], cont[:4], z, z])
    # continuation chunk: cont=1 at the boundary carries the state in
    h2, _, _ = get_op("LSTM").apply(
        Ctx(), lp_exp, params, [x[4:], jnp.ones((4, 2)), hT1, cT1])
    np.testing.assert_allclose(np.asarray(h_full),
                               np.concatenate([h1, h2]), rtol=1e-5)


@pytest.mark.skipif(not HAS_REF, reason="reference configs not mounted")
@pytest.mark.parametrize("fname,phase", [
    ("lenet_memory_train_test.prototxt", Phase.TRAIN),
    ("lenet_memory_train_test.prototxt", Phase.TEST),
    ("cifar10_quick_train_test.prototxt", Phase.TRAIN),
])
def test_reference_nets_forward(fname, phase):
    np_ = read_net(os.path.join(REF_DATA, fname))
    net = Net(np_, NetState(phase=phase))
    params = net.init(jax.random.key(0))
    blobs, _ = net.apply(params, net.make_dummy_inputs(),
                         rng=jax.random.key(1))
    for out in net.output_blobs:
        assert np.all(np.isfinite(np.asarray(blobs[out]))), out


@pytest.mark.skipif(not HAS_REF, reason="reference configs not mounted")
def test_all_reference_nets_construct():
    """Every net prototxt shipped with the reference compiles (shape
    inference + param specs) in both phases, under the solver's stages
    where one exists — the full parity surface, construction-level."""
    import glob
    stages_by_net = {
        "lrcn_cos.prototxt": ["freeze-convnet", "factored", "2-layer"],
    }
    count = 0
    for path in sorted(glob.glob(os.path.join(REF_DATA, "*.prototxt"))):
        name = os.path.basename(path)
        if "solver" in name:
            continue
        npm = read_net(path)
        for phase in (Phase.TRAIN, Phase.TEST):
            stages = list(stages_by_net.get(name, []))
            if name == "lrcn_cos.prototxt" and phase == Phase.TEST:
                stages.append("test-on-train")
            net = Net(npm, NetState(phase=phase, stage=stages))
            if net.compute_layers:
                assert net.blob_shapes
                net.init(jax.random.key(0))   # fillers resolve
                count += 1
    assert count >= 16  # 9 nets × 2 phases, minus empty filtered combos


@pytest.mark.skipif(not HAS_REF, reason="reference configs not mounted")
def test_caffenet_shapes():
    """bvlc_reference (AlexNet-style) shape parity checkpoints."""
    np_ = read_net(os.path.join(REF_DATA, "bvlc_reference_net.prototxt"))
    net = Net(np_, NetState(phase=Phase.TRAIN))
    bs = net.blob_shapes
    b = bs["data"][0]
    assert bs["conv1"] == (b, 96, 55, 55)
    assert bs["pool1"] == (b, 96, 27, 27)
    assert bs["conv2"] == (b, 256, 27, 27)
    assert bs["pool2"] == (b, 256, 13, 13)
    assert bs["conv3"] == (b, 384, 13, 13)
    assert bs["conv5"] == (b, 256, 13, 13)
    assert bs["pool5"] == (b, 256, 6, 6)
    assert bs["fc6"] == (b, 4096)
    assert bs["fc8"] == (b, 1000)


_FUSE_NET = """
name: "fuse"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 2 dim: 6 dim: 5 dim: 5 } } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3 pad: 1
    weight_filler { type: "xavier" } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "norm1" type: "LRN" bottom: "conv1" top: "norm1"
  lrn_param { local_size: 3 alpha: 0.05 beta: 0.75 } }
layer { name: "ip" type: "InnerProduct" bottom: "norm1" top: "ip"
  inner_product_param { num_output: 4
    weight_filler { type: "xavier" } } }"""


def test_relu_lrn_peephole_matches_unfused(monkeypatch):
    """COS_FUSE_RELU_LRN=1 drops the eligible ReLU and routes the
    pre-activation into the fused LRN op — identical outputs and
    gradients on the XLA fallback path (the interpret-mode kernel
    parity is test_lrn_pallas_fused_relu_matches_unfused)."""
    np_ = NetParameter.from_text(_FUSE_NET)
    key = jax.random.key(7)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 5, 5),
                    jnp.float32)

    net_ref = Net(np_, NetState(phase=Phase.TRAIN))
    p_ref = net_ref.init(key)
    monkeypatch.setenv("COS_FUSE_RELU_LRN", "1")
    net_fu = Net(np_, NetState(phase=Phase.TRAIN))
    assert net_fu.fused_relu_lrn == {"norm1"}
    assert [lp.name for lp in net_fu.compute_layers] == \
        ["conv1", "norm1", "ip"]
    # the source NetParameter must be untouched (other Nets build
    # from it): the unfused net still has its relu
    assert [lp.name for lp in net_ref.compute_layers] == \
        ["conv1", "relu1", "norm1", "ip"]
    p_fu = net_fu.init(key)

    def out_sum(net, p):
        blobs, _ = net.apply(p, {"data": x}, train=True,
                             rng=jax.random.key(1))
        return jnp.sum(blobs["ip"] ** 2)

    np.testing.assert_allclose(float(out_sum(net_fu, p_fu)),
                               float(out_sum(net_ref, p_ref)),
                               rtol=1e-6)
    g_ref = jax.grad(lambda p: out_sum(net_ref, p))(p_ref)
    g_fu = jax.grad(lambda p: out_sum(net_fu, p))(p_fu)
    for ln in g_ref:
        for br, bf in zip(g_ref[ln].values(), g_fu[ln].values()):
            np.testing.assert_allclose(np.asarray(bf), np.asarray(br),
                                       rtol=1e-5, atol=1e-6)


def test_relu_lrn_peephole_skips_shared_relu(monkeypatch):
    """A relu top with a second consumer must NOT fuse."""
    txt = _FUSE_NET + """
layer { name: "ip2" type: "InnerProduct" bottom: "conv1" top: "ip2"
  inner_product_param { num_output: 3
    weight_filler { type: "xavier" } } }"""
    monkeypatch.setenv("COS_FUSE_RELU_LRN", "1")
    net = Net(NetParameter.from_text(txt), NetState(phase=Phase.TRAIN))
    assert net.fused_relu_lrn == set()
    assert any(lp.name == "relu1" for lp in net.compute_layers)


def test_bias_relu_lrn_peephole_matches_unfused(monkeypatch):
    """COS_FUSE_BIAS_RELU_LRN=1 additionally defers the conv's bias
    add into the fused LRN epilogue: the conv emits its raw matmul
    output, the LRN kernel applies bias+relu+lrn, and EVERY gradient
    — including the conv's bias — matches the unfused net."""
    np_ = NetParameter.from_text(_FUSE_NET)
    key = jax.random.key(7)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 5, 5),
                    jnp.float32)

    monkeypatch.delenv("COS_FUSE_RELU_LRN", raising=False)
    net_ref = Net(np_, NetState(phase=Phase.TRAIN))
    p_ref = net_ref.init(key)
    monkeypatch.setenv("COS_FUSE_BIAS_RELU_LRN", "1")
    net_fu = Net(np_, NetState(phase=Phase.TRAIN))
    assert net_fu.fused_relu_lrn == {"norm1"}
    assert net_fu.fused_bias_lrn == {"norm1": "conv1"}
    p_fu = net_fu.init(key)

    def out_sum(net, p):
        blobs, _ = net.apply(p, {"data": x}, train=True,
                             rng=jax.random.key(1))
        return jnp.sum(blobs["ip"] ** 2)

    np.testing.assert_allclose(float(out_sum(net_fu, p_fu)),
                               float(out_sum(net_ref, p_ref)),
                               rtol=1e-6)
    g_ref = jax.grad(lambda p: out_sum(net_ref, p))(p_ref)
    g_fu = jax.grad(lambda p: out_sum(net_fu, p))(p_fu)
    for ln in g_ref:
        for bn in g_ref[ln]:
            np.testing.assert_allclose(
                np.asarray(g_fu[ln][bn]), np.asarray(g_ref[ln][bn]),
                rtol=1e-5, atol=1e-6, err_msg=f"{ln}/{bn}")


def test_bias_fusion_skips_shared_conv_top(monkeypatch):
    """If another layer consumes the conv's top, the bias must stay in
    the conv (only relu fuses); the consumer needs the biased value."""
    txt = _FUSE_NET + """
layer { name: "ip2" type: "InnerProduct" bottom: "norm1" top: "ip2"
  inner_product_param { num_output: 3
    weight_filler { type: "xavier" } } }"""
    # non-in-place relu so a second consumer can reach the conv top
    # directly: relu still fuses (its own top has one consumer), but
    # the bias must NOT defer — pool_extra needs the biased conv1
    txt2 = """
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
    monkeypatch.setenv("COS_FUSE_BIAS_RELU_LRN", "1")
    ok = Net(NetParameter.from_text(txt), NetState(phase=Phase.TRAIN))
    assert ok.fused_bias_lrn == {"norm1": "conv1"}
    shared = Net(NetParameter.from_text(txt2),
                 NetState(phase=Phase.TRAIN))
    assert shared.fused_relu_lrn == {"norm1"}     # relu still fuses
    assert shared.fused_bias_lrn == {}            # bias must not
