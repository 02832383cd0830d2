"""LFM2-24B-A2B through the system against the plain reference
(`perfbench/reference/lfm2_24b_a2b.py`, float32, "highest"), at a
small size with the model's structure: the published layers 1-5 (a
dense conv layer, then attention, conv, conv, conv over experts), 8
sigmoid-routed experts, top-2, no shared one, 4 query heads over 2
key/value heads with q/k norms and rotary positions on the whole head.

Tolerances as `tests/test_kanana2.py` gives them: both sides are float32
with exact products, what differs is the order of sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.proto import LayerParameter, SolverParameter
from caffeonspark_tpu.solver import Solver
from perfbench.reference import lfm2_24b_a2b as ref

SMALL = dict(vocab=64, hidden=32, heads=4, kv_heads=2, head_dim=8,
             dense_width=48, expert_width=12, experts=8, top_k=2,
             layers=5, seq=16, batch=2)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "num_key_value_heads": z["kv_heads"],
            "intermediate_size": z["dense_width"],
            "moe_intermediate_size": z["expert_width"],
            "num_experts": z["experts"],
            "num_experts_per_tok": z["top_k"],
            "experts_held": z.get("experts_held", z["experts"]),
            "first_expert": z.get("first_expert", 0),
            "vocab_size": z["vocab"], "num_hidden_layers": z["layers"],
            "first_layer": 1, "num_dense_layers": 2,
            "layer_types": list(zoo.LFM2_LAYER_TYPES), "conv_L_cache": 3,
            "routed_scaling_factor": 1, "norm_eps": 1e-5,
            "rope_parameters": {"rope_theta": 1e6},
            "assumed": {"init_std": 0.02, "route_norm_epsilon": 1e-6},
            "solver": dict(SOLVER)}


def small_net(**over):
    z = dict(SMALL, **over)
    z.setdefault("experts_held", z["experts"])
    return zoo.lfm2(**z)


def flat(tree):
    return {f"{ln}/{bn}": np.asarray(a) for ln, bl in tree.items()
            for bn, a in bl.items()}


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, SMALL["vocab"],
                        (n, SMALL["batch"], SMALL["seq"] + 1))
    return [(r[:, :-1], r[:, 1:]) for r in rows]


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_init_logits_loss_gradients_and_three_adam_steps():
    cfg = small_cfg()
    sp = SolverParameter.from_text(
        'type: "Adam" lr_policy: "fixed" random_seed: 5 '
        + " ".join(f"{k}: {v}" for k, v in SOLVER.items()))
    solver = Solver(sp, small_net())
    params, st = solver.init()
    kept = {}
    data = batches(3)
    out = ref.train_steps(cfg, 5, data,
                          lambda name, tree: kept.setdefault(
                              name, {k: np.array(v) for k, v in tree.items()}))
    p0 = flat(params)
    assert set(p0) == set(kept["p0"])
    for k, v in kept["p0"].items():            # same seeded draws: exact
        np.testing.assert_array_equal(p0[k], v, err_msg=k)
    # both operators and both feed-forwards are in the net
    assert {"L0.conv/taps", "L1.attn/k_norm", "L0.gate/weight",
            "L1.moe/router"} <= set(p0)

    # logits of the first sequence
    net = solver.train_net
    ids, tgt = data[0]
    ins = {"input_ids": jnp.asarray(ids.T, jnp.float32),
           "target_ids": jnp.asarray(tgt.T, jnp.float32)}
    blobs, _ = net.apply(params, ins, train=True, rng=jax.random.key(0))
    want, counts = ref.forward(ref.init_params(cfg, 5), jnp.asarray(ids[0]),
                               ref.dims(cfg))
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]), want,
                               rtol=2e-5, atol=2e-6)
    # every expert held: nothing falls outside, nothing is dropped
    stats = np.asarray(blobs["L1.moe_stats"])
    assert stats[1] == 1.0 and stats[2] == 0.0

    step = jax.jit(solver.train_step_fn())
    for it, (ids, tgt) in enumerate(data):
        ins = {"input_ids": jnp.asarray(ids.T, jnp.float32),
               "target_ids": jnp.asarray(tgt.T, jnp.float32)}
        params, st, o = step(params, st, ins, jax.random.key(it))
        np.testing.assert_allclose(float(o["loss"]), out["losses"][it],
                                   rtol=2e-5)
        if it == 0:
            for k, v in kept["m1"].items():     # (1 - b1) x clipped gradient
                got = flat(st.history)[k]
                assert np.linalg.norm(got - v) <= 2e-4 * max(
                    np.linalg.norm(v), 1e-12), k
            for k, v in kept["v1"].items():
                got = flat(st.history2)[k]
                assert np.linalg.norm(got - v) <= 4e-4 * max(
                    np.linalg.norm(v), 1e-20), k
    last = flat(params)
    for k, v in kept["p_last"].items():
        moved = np.linalg.norm(v - kept["p0"][k])
        assert np.linalg.norm(last[k] - v) <= 5e-4 * moved + 1e-9, k
    # the selection bias is frozen
    np.testing.assert_array_equal(last["L1.moe/bias"], 0.0)


def _moe_layer(cfg, held, first, x, p, pre="L1.moe", eps=1e-6):
    """The program's expert layer on (N, d) rows with the given share of
    the reference's weights."""
    lp = LayerParameter.from_text(f'''
      name: "moe" type: "MixtureOfExperts" bottom: "x" top: "y" top: "stats"
      top: "counts"
      moe_param {{ num_experts: {cfg["num_experts"]}
        hidden_dim: {cfg["moe_intermediate_size"]}
        top_k: {cfg["num_experts_per_tok"]} dispatch: "dropless"
        scoring: "sigmoid" selection_bias: true norm_epsilon: {eps}
        gated: true experts_held: {held} first_expert: {first} }}''')
    sl = slice(first, first + held)
    blobs = [p[f"{pre}/router"], p[f"{pre}/bias"], p[f"{pre}/W_gate"][sl],
             p[f"{pre}/W_up"][sl], p[f"{pre}/W_down"][sl]]
    return L.get_op("MixtureOfExperts").apply(L.Ctx(train=True), lp, blobs,
                                              [x])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The cell's split at a small size: 64 experts, top 4, run as each
    of the eight shares of 8 experts (first_expert 0, 8, ..., 56).  The
    parts sum to what the uncut reference gives for the whole layer;
    there is no shared expert to count once."""
    cfg = small_cfg(experts=64, top_k=4)
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 3)
    x = jax.random.normal(jax.random.key(1), (40, m["d"]))
    whole, whole_counts = ref.moe(p, "L1.moe", x, m)
    assert int(whole_counts.sum()) == 40 * 4
    parts_ref, parts_prog, rows = 0.0, 0.0, 0
    for first in range(0, 64, 8):
        ms = ref.dims(small_cfg(experts=64, top_k=4, experts_held=8,
                                first_expert=first))
        ps = dict(p, **{f"L1.moe/{b}": p[f"L1.moe/{b}"][first:first + 8]
                        for b in ("W_gate", "W_up", "W_down")})
        part, counts = ref.moe(ps, "L1.moe", x, ms)
        parts_ref = parts_ref + part
        y, stats, got_counts = _moe_layer(cfg, 8, first, x, p)
        parts_prog = parts_prog + y
        np.testing.assert_array_equal(np.asarray(got_counts), counts)
        np.testing.assert_array_equal(counts,
                                      whole_counts[first:first + 8])
        assert float(stats[2]) == 0.0
        rows += int(counts.sum())
    assert rows == 40 * 4
    np.testing.assert_allclose(parts_ref, whole, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(parts_prog, whole, rtol=2e-5, atol=1e-7)


def test_weight_normalisation_takes_its_epsilon():
    """w_i = s_i / (sum + eps): with a large epsilon the layer's output
    shrinks by sum / (sum + eps) a token, and 0 is the bare sum that
    older prototxts keep."""
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 4)
    x = jax.random.normal(jax.random.key(2), (24, m["d"]))
    bare, _, _ = _moe_layer(cfg, 8, 0, x, p, eps=0)
    half, _, _ = _moe_layer(cfg, 8, 0, x, p, eps=0.5)
    topi, w = ref.route(p, "L1.moe", x, dict(m, route_eps=0.0))
    s = jax.nn.sigmoid(x @ p["L1.moe/router"])
    total = jnp.take_along_axis(s, topi, axis=1).sum(-1, keepdims=True)
    np.testing.assert_allclose(half, bare * total / (total + 0.5),
                               rtol=2e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


def _short_conv_layer(x, w_in, taps, w_out, bias=None):
    lp = LayerParameter.from_text(
        'name: "c" type: "ShortConv" bottom: "x" top: "y" '
        f'short_conv_param {{ taps: {taps.shape[1]} '
        f'bias_term: {"false" if bias is None else "true"} }}')
    op = L.get_op("ShortConv")
    blobs = [w_in, taps, w_out] + ([] if bias is None else [bias])
    assert [s[1] for s in op.param_specs(lp, [x.shape])] == [
        a.shape for a in blobs]
    return op.apply(L.Ctx(train=True), lp, blobs, [x])[0]


@pytest.mark.parametrize("with_bias", [False, True])
def test_short_convolution_is_causal_and_equals_a_direct_loop(with_bias):
    """`conv_bias` is false in the published configuration; the option
    stands for the family's other members, and its branch is held to
    the same loop (the bias joins the taps' sum, before the gate)."""
    t, b, d, n = 11, 2, 6, 3
    ks = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(ks[0], (t, b, d))
    w_in = jax.random.normal(ks[1], (3 * d, d))
    taps = jax.random.normal(ks[2], (d, n))
    w_out = jax.random.normal(ks[3], (d, d))
    bias = jax.random.normal(ks[4], (d,)) if with_bias else None
    want_b = np.asarray(bias, np.float64) if with_bias else 0.0
    got = np.asarray(_short_conv_layer(x, w_in, taps, w_out, bias))
    # a direct loop, in numpy
    xn, wi, kn, wo = (np.asarray(a, np.float64)
                      for a in (x, w_in, taps, w_out))
    want = np.zeros((t, b, d))
    for bi in range(b):
        bcu = xn[:, bi] @ wi.T
        z = bcu[:, :d] * bcu[:, 2 * d:]
        for ti in range(t):
            v = np.zeros(d)
            for j in range(n):
                src = ti - (n - 1) + j
                if src >= 0:
                    v += kn[:, j] * z[src]
            want[ti, bi] = (bcu[ti, d:2 * d] * (v + want_b)) @ wo.T
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    if not with_bias:
        # the reference's own (it has no bias), one column at a time
        p = {"c/W_in": w_in, "c/taps": taps, "c/W_out": w_out}
        for bi in range(b):
            np.testing.assert_allclose(
                got[:, bi], ref.short_conv(p, "c", x[:, bi], {}),
                rtol=2e-5, atol=1e-5)
    # causal: a change at t = 7 leaves every output before it untouched,
    # and reaches t = 7, 8, 9 (three taps) of its own column only
    x2 = x.at[7, 0].add(1.0)
    got2 = np.asarray(_short_conv_layer(x2, w_in, taps, w_out, bias))
    np.testing.assert_array_equal(got2[:7], got[:7])
    np.testing.assert_array_equal(got2[:, 1], got[:, 1])
    np.testing.assert_array_equal(got2[10, 0], got[10, 0])
    assert all(np.abs(got2[ti, 0] - got[ti, 0]).max() > 0
               for ti in (7, 8, 9))


def _gqa_layer(x, blobs, h, hkv, hd, **flags):
    opts = " ".join(f"{k}: {str(v).lower()}" for k, v in flags.items())
    lp = LayerParameter.from_text(
        'name: "a" type: "GroupedQueryAttention" bottom: "x" top: "y" '
        f'attention_param {{ num_heads: {h} num_kv_heads: {hkv} '
        f'head_dim: {hd} causal: true rope_theta: 1e6 rms_norm_eps: 1e-5 '
        f'{opts} }}')
    return L.get_op("GroupedQueryAttention").apply(
        L.Ctx(train=True), lp, blobs, [x])[0]


def test_query_head_h_reads_key_value_head_h_over_g():
    """Changing key/value head j moves exactly the query heads
    [j g, (j + 1) g), and the layer equals the reference's attention."""
    t, b, d, h, hkv, hd = 12, 2, 16, 4, 2, 8
    ks = jax.random.split(jax.random.key(3), 7)
    x = jax.random.normal(ks[0], (t, b, d))
    w_q = jax.random.normal(ks[1], (h * hd, d)) * 0.3
    w_k = jax.random.normal(ks[2], (hkv * hd, d)) * 0.3
    w_v = jax.random.normal(ks[3], (hkv * hd, d)) * 0.3
    w_o = jax.random.normal(ks[4], (d, h * hd)) * 0.3
    qn = 1.0 + 0.1 * jax.random.normal(ks[5], (hd,))
    kn = 1.0 + 0.1 * jax.random.normal(ks[6], (hd,))
    blobs = [w_q, w_k, w_v, w_o, qn, kn]
    got = _gqa_layer(x, blobs, h, hkv, hd, qk_norm=True, rotary=True)
    m = {"h": h, "hkv": hkv, "hd": hd, "eps": 1e-5, "theta": 1e6}
    p = dict(zip(("a/W_q", "a/W_k", "a/W_v", "a/W_o", "a/q_norm",
                  "a/k_norm"), blobs))
    for bi in range(b):
        np.testing.assert_allclose(got[:, bi],
                                   ref.attention(p, "a", x[:, bi], m),
                                   rtol=2e-5, atol=2e-6)
    # the heads' outputs before W_o: an identity W_o of the right shape
    eye = jnp.eye(h * hd)
    heads = lambda wv: np.asarray(_gqa_layer(                 # noqa: E731
        x, [w_q, w_k, wv, eye, qn, kn], h, hkv, hd, qk_norm=True,
        rotary=True)).reshape(t, b, h, hd)
    base = heads(w_v)
    moved = heads(w_v.at[hd:].multiply(2.0))    # key/value head 1 alone
    np.testing.assert_array_equal(moved[:, :, :2], base[:, :, :2])
    np.testing.assert_allclose(moved[:, :, 2:], 2.0 * base[:, :, 2:],
                               rtol=1e-5, atol=1e-6)
    # num_kv_heads 0 means num_heads: W_k as wide as W_q
    lp = LayerParameter.from_text(
        'name: "a" type: "GroupedQueryAttention" bottom: "x" top: "y" '
        'attention_param { num_heads: 4 head_dim: 8 }')
    shapes = {n: s for n, s, _ in L.get_op(
        "GroupedQueryAttention").param_specs(lp, [(t, b, d)])}
    assert shapes == {"W_q": (32, d), "W_k": (32, d), "W_v": (32, d),
                      "W_o": (d, 32)}


@pytest.mark.parametrize("path", ["einsum", "flash"])
def test_grouped_heads_through_the_one_dispatch(monkeypatch, path):
    """8 query heads of 64 over 2 key/value heads through
    `_attention_dispatch`: the XLA einsum path and the flash kernels
    (interpret mode), forward and gradients, against the reference's
    attention over repeated keys and values."""
    t, h, hkv, hd = 256, 8, 2, 64
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (t, h, hd))
    k, v = (jax.random.normal(ks[i], (t, hkv, hd)) for i in (1, 2))
    w = jax.random.normal(ks[3], (t, h, hd))
    if path == "flash":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    else:
        monkeypatch.setenv("COS_DISABLE_FLASH", "1")

    def prog(q, k, v):
        bh = lambda a: jnp.transpose(a, (1, 0, 2))[None]      # noqa: E731
        o = L._attention_dispatch(bh(q), bh(k), bh(v), causal=True)
        return jnp.sum(jnp.transpose(o[0], (1, 0, 2)) * w)

    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(ref.grouped_attention(q, k, v) * w),
        argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(prog, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_recompute_block_changes_no_value():
    ins = {"input_ids": jnp.ones((16, 2)) * 3,
           "target_ids": jnp.ones((16, 2)) * 5}
    outs = []
    for recompute in (True, False):
        net = Net(small_net(recompute=recompute))
        assert bool(net.recompute_blocks) == recompute
        params = net.init(jax.random.key(0))
        (loss, _), g = jax.value_and_grad(
            lambda p: net.loss(p, ins, train=True, rng=jax.random.key(1)),
            has_aux=True)(params)
        outs.append((float(loss), flat(g)))
    assert outs[0][0] == outs[1][0]
    for k, v in outs[1][1].items():
        np.testing.assert_allclose(outs[0][1][k], v, rtol=1e-5, atol=1e-8,
                                   err_msg=k)


def test_full_width_net_text_parses_and_counts_664_6_million():
    """The cell's net: published widths, 8 of 64 experts a layer, an
    eighth of the vocabulary, the published layers 1-7."""
    from caffeonspark_tpu.proto import NetParameter
    npm = zoo.lfm2()
    assert NetParameter.from_text(npm.to_text()) == npm
    net = Net(npm)
    assert net.num_params() == 664_597_120
    layout = {ln: {bn: s for bn, s, _ in bl}
              for ln, bl in net.param_layout.items()}
    kinds = ["conv", "attn", "conv", "conv", "conv", "attn", "conv"]
    for i, kind in enumerate(kinds):
        assert f"L{i}.{kind}" in layout
        assert (f"L{i}.moe" in layout) == (i > 0)
    assert layout["L0.conv"] == {"W_in": (6144, 2048), "taps": (2048, 3),
                                 "W_out": (2048, 2048)}
    assert layout["L0.gate"]["weight"] == (11776, 2048)
    assert layout["L1.attn"]["W_q"] == (2048, 2048)
    assert layout["L1.attn"]["W_k"] == (512, 2048)
    assert layout["L1.attn"]["q_norm"] == (64,)
    assert layout["L1.moe"]["router"] == (2048, 64)
    assert layout["L1.moe"]["W_gate"] == (8, 2048, 1536)
    assert "S_gate" not in layout["L1.moe"]
    assert net.blob_shapes["logits"] == (8192, 1, 8192)
    assert len(net.recompute_blocks) == 7
    # the whole model is the same function
    whole = zoo.lfm2(experts_held=64, vocab=65536, first_layer=0,
                     layers=40, seq=128)
    types = [ly.type for ly in whole.layer]
    assert types.count("ShortConv") == 30
    assert types.count("GroupedQueryAttention") == 10
    assert types.count("MixtureOfExperts") == 38


def test_flops_and_param_specs_know_the_new_operators():
    """`utils/flops.py` (and through it `analysis/roofline.py`) count
    the two operators as the reference does; `tp_param_specs` gives
    every blob of theirs a spec (replicated) and the held experts the
    expert axis."""
    from caffeonspark_tpu.analysis.roofline import analyze_net
    from caffeonspark_tpu.parallel.mesh import tp_param_specs
    from caffeonspark_tpu.utils.flops import (forward_flops,
                                              layer_forward_flops)
    net = Net(small_net())
    assert forward_flops(net) == ref.forward_flops(
        small_cfg(), SMALL["seq"], SMALL["batch"])
    per = layer_forward_flops(net)
    n = SMALL["seq"] * SMALL["batch"]
    assert per["L0.conv"] == 2 * n * 4 * 32 * 32
    assert per["L1.attn"] == (2 * n * (2 * 32 * 32 + 2 * 16 * 32)
                              + 2 * 2 * 4 * 16 * 16 // 2 * 2 * 8)
    rows = {r["layer"]: r for r in analyze_net(net, act_bytes=4,
                                               param_bytes=4)}
    assert rows["L0.conv"]["flops"] == 3 * per["L0.conv"]
    assert rows["L0.conv"]["params"] == 3 * 32 * 32 + 32 * 3 + 32 * 32
    specs = tp_param_specs(net)
    assert set(specs["L0.conv"]) == {"W_in", "taps", "W_out"}
    assert all(tuple(s) == () for s in specs["L1.attn"].values())
    assert tuple(specs["L1.moe"]["W_up"]) == ("ep", None, None)

