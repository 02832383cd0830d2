"""Model zoo construction tests: shape inference + parameter counts for
the ImageNet-class families (shape-only — forwards at these sizes are
bench/TPU territory)."""

import pytest

from caffeonspark_tpu.models import (caffenet, googlenet, lenet,
                                     resnet50, transformer_lm, vgg16)
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.proto import NetState, Phase


def test_lenet_params():
    net = Net(lenet(batch_size=8))
    assert net.num_params() == 431_080


def test_caffenet_params():
    net = Net(caffenet(batch_size=8))
    # AlexNet/CaffeNet published parameter count
    assert net.num_params() == 60_965_224
    assert net.blob_shapes["fc8"] == (8, 1000)


def test_vgg16_params():
    net = Net(vgg16(batch_size=2))
    # VGG-16 published parameter count
    assert net.num_params() == 138_357_544
    assert net.blob_shapes["pool5"] == (2, 512, 7, 7)
    assert net.blob_shapes["fc8"] == (2, 1000)


def test_vgg16_train_step():
    """One real fwd+bwd+update step (mirrors the ResNet-50 check; the
    conv stack runs at reduced spatial size to fit the CI budget —
    downsized fc6 keeps the 7x7 pool5 contract via num_output surgery
    is NOT done: the net is rebuilt at 64px so fc shapes re-infer)."""
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = vgg16(batch_size=2, num_classes=10, image_size=64)
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.001 momentum: 0.9 lr_policy: 'fixed' random_seed: 1"),
        npm)
    params, st = s.init()
    step = s.jit_train_step()
    inp = {"data": jnp.asarray(
        np.random.RandomState(0).rand(2, 3, 64, 64), jnp.float32),
        "label": jnp.zeros((2,))}
    params, st, out = step(params, st, inp, s.step_rng(0))
    assert np.isfinite(float(out["loss"]))


@pytest.mark.slow  # ~30 s CPU compile+step: keep tier-1 inside its budget
def test_resnet50_shapes():
    import jax.numpy as jnp
    import numpy as np
    net = Net(resnet50(batch_size=2))
    bs = net.blob_shapes
    assert bs["res2c"] == (2, 256, 56, 56)
    assert bs["res3d"] == (2, 512, 28, 28)
    assert bs["res4f"] == (2, 1024, 14, 14)
    assert bs["res5c"] == (2, 2048, 7, 7)
    assert bs["pool5"] == (2, 2048, 1, 1)
    # ResNet-50 published parameter count (conv+fc 25.55M) + BN stats
    stat_layers = set(net.stat_param_layers())
    n_weights = sum(
        int(np.prod(s))
        for ln, specs in net.param_layout.items()
        for bn_, s, _ in specs
        if ln not in stat_layers)
    assert 25_500_000 < n_weights < 25_700_000
    # one training step end-to-end at tiny spatial size (BN+Scale+
    # Eltwise backward path)
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = resnet50(batch_size=2, num_classes=10)
    for lyr in npm.layer:
        if lyr.type == "MemoryData":
            lyr.memory_data_param.height = 64
            lyr.memory_data_param.width = 64
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' random_seed: 1"),
        npm)
    params, st = s.init()
    step = s.jit_train_step()
    inp = {"data": jnp.asarray(
        np.random.RandomState(0).rand(2, 3, 64, 64), jnp.float32),
        "label": jnp.zeros((2,))}
    params, st, out = step(params, st, inp, s.step_rng(0))
    assert np.isfinite(float(out["loss"]))


def test_transformer_lm_trains_and_is_causal():
    """MultiHeadAttention from a prototxt: the tiny causal LM learns a
    deterministic next-token rule, and causality holds (future tokens
    cannot influence earlier predictions)."""
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = transformer_lm(vocab=12, d_model=32, heads=2, layers=1,
                         seq=8, batch=4)
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' type: 'ADAM' "
        "random_seed: 1"), npm)
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    # rule: next token = (token + 1) % 10, starting 2..9
    seqs = np.stack([(np.arange(8) + rng.randint(2, 10)) % 10
                     for _ in range(4)])
    inp = {"input_sentence": jnp.asarray(seqs.T, jnp.float32),
           "target_sentence": jnp.asarray(
               ((seqs + 1) % 10).T, jnp.float32)}
    losses = []
    for i in range(150):
        params, st, out = step(params, st, inp, s.step_rng(i))
        losses.append(float(out["loss"]))
    assert losses[-1] < 0.2 * losses[0], (losses[0], losses[-1])
    # causality: changing the LAST input token must not change the
    # logits at earlier positions
    net = s.train_net
    blobs1, _ = net.apply(params, inp, train=False)
    inp2 = dict(inp)
    mod = np.asarray(inp["input_sentence"]).copy()
    mod[-1, :] = 11.0
    inp2["input_sentence"] = jnp.asarray(mod)
    blobs2, _ = net.apply(params, inp2, train=False)
    np.testing.assert_allclose(
        np.asarray(blobs1["logits"][:-1]),
        np.asarray(blobs2["logits"][:-1]), atol=1e-5)
    assert not np.allclose(np.asarray(blobs1["logits"][-1]),
                           np.asarray(blobs2["logits"][-1]))


def test_googlenet_shapes():
    net = Net(googlenet(batch_size=2), NetState(phase=Phase.TEST))
    bs = net.blob_shapes
    assert bs["inception_3a/output"] == (2, 256, 28, 28)
    assert bs["inception_4e/output"] == (2, 832, 14, 14)
    assert bs["inception_5b/output"] == (2, 1024, 7, 7)
    assert bs["pool5"] == (2, 1024, 1, 1)
    assert bs["loss3/classifier"] == (2, 1000)
    # bvlc_googlenet main-trunk parameter count is ~6.99M
    assert 6_500_000 < net.num_params() < 7_500_000
    # layer names follow the published bvlc_googlenet.caffemodel naming
    # so copy_layers-based finetuning matches by name
    assert "conv1/7x7_s2" in net.param_layout
    assert "inception_3a/1x1" in net.param_layout
    assert "loss3/classifier" in net.param_layout


@pytest.mark.slow  # ~47 s CPU compile+step: keep tier-1 inside its budget
def test_googlenet_train_step():
    """One real fwd+bwd+update step through the TRAIN phase incl. the
    aux loss heads (loss1/loss2 weighted 0.3, loss3 1.0 — the published
    bvlc_googlenet training config)."""
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = googlenet(batch_size=2, num_classes=10, image_size=64)
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' random_seed: 1"),
        npm)
    params, st = s.init()
    step = s.jit_train_step()
    inp = {"data": jnp.asarray(
        np.random.RandomState(0).rand(2, 3, 64, 64), jnp.float32),
        "label": jnp.zeros((2,))}
    params, st, out = step(params, st, inp, s.step_rng(0))
    assert np.isfinite(float(out["loss"]))

def test_lstm_lm_trains():
    """The benchmark recurrent family (zoo.lstm_lm, LRCN-shaped
    Embed->cont-gated LSTM->per-step logits): learns a deterministic
    next-token rule from caption-style time-major tops."""
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.models.zoo import lstm_lm
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    npm = lstm_lm(vocab=20, d_model=32, seq=8, batch_size=4)
    s = Solver(SolverParameter.from_text(
        "base_lr: 0.05 momentum: 0.9 lr_policy: 'fixed' type: 'ADAM' "
        "random_seed: 2"), npm)
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    seqs = np.stack([(np.arange(8) + rng.randint(2, 10)) % 10
                     for _ in range(4)])
    cont = np.ones((8, 4), np.float32)
    cont[0] = 0.0
    inp = {"input_sentence": jnp.asarray(seqs.T, jnp.float32),
           "cont_sentence": jnp.asarray(cont),
           "target_sentence": jnp.asarray(((seqs + 1) % 10).T,
                                          jnp.float32)}
    losses = []
    for i in range(120):
        params, st, out = step(params, st, inp, s.step_rng(i))
        losses.append(float(out["loss"]))
    assert losses[-1] < 0.25 * losses[0], (losses[0], losses[-1])


def test_alexnet_params_and_fusion(monkeypatch):
    """Original-order AlexNet: same published parameter count as
    CaffeNet (the two differ only in norm/pool order), norm runs at
    the PRE-pool extents, and the ReLU→LRN peephole fires on exactly
    norm1/norm2 when enabled."""
    from caffeonspark_tpu.models import alexnet
    net = Net(alexnet(batch_size=8))
    assert net.num_params() == 60_965_224
    assert net.blob_shapes["norm1"] == (8, 96, 55, 55)
    assert net.blob_shapes["norm2"] == (8, 256, 27, 27)
    assert net.blob_shapes["fc8"] == (8, 1000)
    assert net.fused_relu_lrn == frozenset()
    monkeypatch.setenv("COS_FUSE_RELU_LRN", "1")
    fused = Net(alexnet(batch_size=8))
    assert fused.fused_relu_lrn == {"norm1", "norm2"}
    assert not any(lp.name in ("relu_conv1", "relu_conv2")
                   for lp in fused.compute_layers)
    assert fused.blob_shapes["fc8"] == (8, 1000)


def test_phi4flash_counts_kinds_and_round_trip():
    """`zoo.phi4flash`: the cut of `perfbench/configs/phi4flash_mini.json`
    and the whole model count what the published model does (3.85 B),
    the layers' kinds follow the published index, the tied head owns no
    blob, the net text round-trips, and a cut that reads a memory or
    keys no layer of it makes is refused."""
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.proto import NetParameter
    kinds = zoo.phi4flash_kinds(32)
    assert kinds[14:20] == ("mamba", "window", "mamba_memory", "full_kv",
                            "gmu", "cross")
    assert [kinds.count(k) for k in ("mamba", "window", "gmu", "cross")] \
        == [8, 8, 7, 7]
    cut = zoo.phi4flash()
    assert NetParameter.from_text(cut.to_text()) == cut
    assert NetParameter.from_binary(cut.to_binary()) == cut
    net = Net(cut, NetState(phase=Phase.TRAIN))
    assert net.num_params() == 697_073_792
    assert "head.logits" not in net.param_layout
    assert len(net.recompute_blocks) == 12
    assert set(net.shared_blobs()) == {"L2.memory", "L3.k", "L3.v"}
    types = [lp.type for lp in net.compute_layers]
    assert [types.count(t) for t in ("Mamba", "GatedMemoryUnit",
                                     "GroupedQueryAttention", "LayerNorm")] \
        == [2, 1, 3, 13]
    whole = Net(zoo.phi4flash(vocab=200064, first_layer=0, layers=32,
                              seq=128), NetState(phase=Phase.TRAIN))
    assert whole.num_params() == 3_852_457_984
    with pytest.raises(ValueError, match="no layer of the cut"):
        zoo.phi4flash(first_layer=18, layers=2)


def test_nemotron_h_counts_pattern_and_round_trip():
    """`zoo.nemotron_h`: the cut of `perfbench/configs/
    nemotron3_nano_30b_a3b.json` and the whole model count what the
    published model does (31.6 B), a block is ONE operator by the
    published pattern's letter (one norm, one residual a block), the
    net text round-trips, and a cut past the pattern's end is refused."""
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.proto import NetParameter
    pattern = zoo.NEMOTRON_H_PATTERN
    assert len(pattern) == 52 and pattern[34:43] == "EMEMEMEM*"
    assert [pattern.count(k) for k in "ME*"] == [23, 23, 6]
    cut = zoo.nemotron_h()
    assert NetParameter.from_text(cut.to_text()) == cut
    assert NetParameter.from_binary(cut.to_binary()) == cut
    net = Net(cut, NetState(phase=Phase.TRAIN))
    assert net.num_params() == 666_963_456
    assert len(net.recompute_blocks) == 9
    types = [lp.type for lp in net.compute_layers]
    assert [types.count(t) for t in (
        "Mamba2", "MixtureOfExperts", "GroupedQueryAttention", "RMSNorm",
        "Eltwise")] == [4, 4, 1, 10, 9]
    names = [lp.name for lp in net.compute_layers]
    assert "L0.norm1" not in names and "L0.norm2" in names      # E
    assert "L1.norm1" in names and "L1.norm2" not in names      # M
    moe = next(lp for lp in net.compute_layers if lp.name == "L0.moe")
    assert (moe.moe_param.activation, moe.moe_param.gated) == ("relu2",
                                                               False)
    assert [n for n, _ in net.layer_param_specs("L0.moe")] == [
        "router", "bias", "W1", "W2", "S_up", "S_down"]
    whole = Net(zoo.nemotron_h(vocab=131072, first_layer=0, layers=52,
                               experts_held=128, seq=128),
                NetState(phase=Phase.TRAIN))
    assert whole.num_params() == 31_577_940_288
    with pytest.raises(ValueError, match=r"blocks \[50, 59\) of 52"):
        zoo.nemotron_h(first_layer=50)
