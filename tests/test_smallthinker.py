"""SmallThinker-21BA3B-Instruct through the system against the plain
reference (`perfbench/reference/smallthinker_21b_a3b.py`, float32,
"highest"), at a small size with the model's structure: the published
layers 0-3 (one global layer without positions, three rotary layers
under a window), 6 query heads over 2 key/value heads, 8 softmax-routed
ReLU-gated experts, top 2, none shared, the router fed from the block's
normed input.

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
from perfbench.reference import smallthinker_21b_a3b as ref

SMALL = dict(vocab=64, hidden=32, heads=6, kv_heads=2, head_dim=8,
             expert_width=12, experts=8, top_k=2, window=8, layers=4,
             seq=24, batch=2)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "num_key_value_heads": z["kv_heads"], "head_dim": z["head_dim"],
            "moe_ffn_hidden_size": z["expert_width"],
            "moe_num_primary_experts": z["experts"],
            "moe_num_active_primary_experts": z["top_k"],
            "experts_held": z.get("experts_held", z["experts"]),
            "first_expert": z.get("first_expert", 0),
            "vocab_size": z["vocab"], "num_hidden_layers": z["layers"],
            "sliding_window_layout": list(zoo.SMALLTHINKER_LAYOUT),
            "rope_layout": list(zoo.SMALLTHINKER_LAYOUT),
            "sliding_window_size": z["window"], "rms_norm_eps": 1e-6,
            "rope_theta": 1.5e6,
            "assumed": {"init_std": 0.02,
                        "router_reads": z.get("router_reads", "n1")},
            "solver": dict(SOLVER)}


def small_net(**over):
    z = dict(SMALL, **over)
    z.setdefault("experts_held", z["experts"])
    return zoo.smallthinker(**z)


def flat(tree):
    return {f"{ln}/{bn}": np.asarray(a) for ln, bl in tree.items()
            for bn, a in bl.items()}


def batches(n, seed=0, seq=SMALL["seq"]):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, SMALL["vocab"], (n, SMALL["batch"], seq + 1))
    return [(r[:, :-1], r[:, 1:]) for r in rows]


def inputs(ids, tgt):
    return {"input_ids": jnp.asarray(ids.T, jnp.float32),
            "target_ids": jnp.asarray(tgt.T, jnp.float32)}


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
    assert {"L0.attn/W_k", "L3.moe/router", "L3.moe/W_gate",
            "head.logits/weight"} <= set(p0)
    assert not any(k.endswith(("q_norm", "k_norm", "bias")) for k in p0)

    # logits of the first sequence
    net = solver.train_net
    ids, tgt = data[0]
    blobs, _ = net.apply(params, inputs(ids, tgt), train=True,
                         rng=jax.random.key(0))
    want, counts = ref.forward(ref.init_params(cfg, 5), jnp.asarray(ids[0]),
                               ref.dims(cfg))
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]), want,
                               rtol=2e-5, atol=2e-6)
    # every expert held: nothing falls outside, nothing is dropped
    stats = np.asarray(blobs["L1.moe_stats"])
    assert stats[1] == 1.0 and stats[2] == 0.0

    step = jax.jit(solver.train_step_fn())
    for it, (ids, tgt) in enumerate(data):
        params, st, o = step(params, st, inputs(ids, tgt),
                             jax.random.key(it))
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


def _gqa_layer(x, blobs, h, hkv, hd, **flags):
    opts = " ".join(f"{k}: {str(v).lower()}" for k, v in flags.items())
    lp = LayerParameter.from_text(
        'name: "a" type: "GroupedQueryAttention" bottom: "x" top: "y" '
        f'attention_param {{ num_heads: {h} num_kv_heads: {hkv} '
        f'head_dim: {hd} causal: true rope_theta: 1.5e6 {opts} }}')
    return L.get_op("GroupedQueryAttention").apply(
        L.Ctx(train=True), lp, blobs, [x])[0]


def _attn_weights(seed, d, h, hkv, hd):
    ks = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(k, s) * 0.3 for k, s in zip(ks, (
        (h * hd, d), (hkv * hd, d), (hkv * hd, d), (d, h * hd)))]


@pytest.mark.parametrize("path", ["einsum", "flash"])
def test_the_window_is_a_window(monkeypatch, path):
    """T > W: changing token s moves the window layer's outputs at
    s ... s + W - 1 and no others (none before s: causal; none from
    s + W on: W keys, the row's own among them), on the einsum route and
    through the kernels; the layer equals the reference's."""
    t, b, d, h, hkv, hd, w = (128, 1, 16, 6, 2, 8, 40) if path == "flash" \
        else (30, 2, 16, 6, 2, 8, 7)
    if path == "flash":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    blobs = _attn_weights(0, d, h, hkv, hd)
    x = jax.random.normal(jax.random.key(1), (t, b, d))
    got = _gqa_layer(x, blobs, h, hkv, hd, rotary=True, window=w)
    m = {"h": h, "hkv": hkv, "hd": hd, "theta": 1.5e6}
    p = dict(zip(("a/W_q", "a/W_k", "a/W_v", "a/W_o"), blobs))
    for bi in range(b):
        np.testing.assert_allclose(
            got[:, bi], ref.attention(p, "a", x[:, bi], m, w, True),
            rtol=2e-5, atol=2e-6)
    s = 5
    moved = np.abs(np.asarray(_gqa_layer(
        x.at[s, 0].add(1.0), blobs, h, hkv, hd, rotary=True, window=w)
        - got)).max(axis=-1)
    assert (moved[:s] == 0).all() and (moved[s + w:] == 0).all()
    assert (moved[s:s + w, 0] > 0).all()
    assert (moved[:, 1:] == 0).all()        # no other sequence
    # without the window the change reaches every later row
    plain = _gqa_layer(x, blobs, h, hkv, hd, rotary=True)
    moved = np.abs(np.asarray(_gqa_layer(
        x.at[s, 0].add(1.0), blobs, h, hkv, hd, rotary=True)
        - plain)).max(axis=-1)
    assert (moved[s:, 0] > 0).all() and (moved[:s] == 0).all()
    # a window of T keys or more is the causal mask
    np.testing.assert_array_equal(
        np.asarray(_gqa_layer(x, blobs, h, hkv, hd, rotary=True,
                              window=t)), np.asarray(plain))


def test_the_global_layer_tells_an_order_through_its_mask_alone():
    """No rotary turn, no bias: a permutation of the tokens before t
    leaves row t's output as it was (nothing carries a position), and
    yet the layer is not a bag of tokens: a token moved from before t
    to after it changes row t."""
    t, d, h, hkv, hd = 12, 16, 6, 2, 8
    blobs = _attn_weights(2, d, h, hkv, hd)
    x = jax.random.normal(jax.random.key(3), (t, 1, d))
    base = np.asarray(_gqa_layer(x, blobs, h, hkv, hd, rotary=False))
    perm = np.r_[3, 0, 2, 1, 4:t]
    mixed = np.asarray(_gqa_layer(x[perm], blobs, h, hkv, hd,
                                  rotary=False))
    np.testing.assert_allclose(mixed[4:], base[4:], rtol=1e-5, atol=1e-6)
    assert np.abs(mixed[1] - base[1]).max() > 1e-3
    # with rotary turns the same permutation moves every later row
    turned = np.asarray(_gqa_layer(x, blobs, h, hkv, hd, rotary=True))
    turned_mixed = np.asarray(_gqa_layer(x[perm], blobs, h, hkv, hd,
                                         rotary=True))
    assert np.abs(turned_mixed[6:] - turned[6:]).max() > 1e-3
    swap = np.r_[0:5, 8, 6, 7, 5, 9:t]      # token 5 goes behind row 6
    assert np.abs(np.asarray(_gqa_layer(
        x[swap], blobs, h, hkv, hd, rotary=False))[6] - base[6]).max() > 1e-3


def test_reference_attention_in_blocks_is_the_dense_mask(monkeypatch):
    """The reference's blocks of query rows over the columns they can
    see give what one dense masked softmax gives, values and
    gradients."""
    from caffeonspark_tpu.parallel.sp import attention as dense
    t, h, hkv, hd = 48, 6, 2, 8
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (t, h, hd))
    k, v = (jax.random.normal(ks[i], (t, hkv, hd)) for i in (1, 2))
    wt = jax.random.normal(ks[3], (t, h, hd))
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    bh = lambda a: jnp.transpose(a, (1, 0, 2))[None]          # noqa: E731
    for w in (0, 5, 8, 13, 48):
        want = jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.transpose(
            dense(bh(q), bh(k), bh(v), causal=True, window=w)[0],
            (1, 0, 2)) * wt), argnums=(0, 1, 2))(q, k, v)
        got = jax.value_and_grad(lambda q, k, v: jnp.sum(
            ref.grouped_attention(q, k, v, w) * wt),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def _moe_layer(cfg, held, first, x, p, routed_from=None, pre="L1.moe",
               act="relu"):
    """The program's expert layer on (N, d) rows with the given share of
    the reference's weights; the router reads `routed_from` if given."""
    second = ' bottom: "r"' if routed_from is not None else ""
    lp = LayerParameter.from_text(f'''
      name: "moe" type: "MixtureOfExperts" bottom: "x"{second} top: "y"
      top: "stats" top: "counts"
      moe_param {{ num_experts: {cfg["moe_num_primary_experts"]}
        hidden_dim: {cfg["moe_ffn_hidden_size"]}
        top_k: {cfg["moe_num_active_primary_experts"]} dispatch: "dropless"
        scoring: "softmax" gated: true gate_activation: "{act}"
        experts_held: {held} first_expert: {first} }}''')
    sl = slice(first, first + held)
    blobs = [p[f"{pre}/router"], p[f"{pre}/W_gate"][sl],
             p[f"{pre}/W_up"][sl], p[f"{pre}/W_down"][sl]]
    return L.get_op("MixtureOfExperts").apply(
        L.Ctx(train=True), lp, blobs,
        [x] + ([] if routed_from is None else [routed_from]))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The cell's split at a small size: 64 experts, top 6, run as each
    of the eight shares of 8 experts (first_expert 0, 8, ..., 56), the
    router reading another tensor than the experts.  The parts sum to
    what the uncut reference gives for the whole layer."""
    cfg = small_cfg(experts=64, top_k=6)
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 3)
    x = jax.random.normal(jax.random.key(1), (40, m["d"]))
    r = jax.random.normal(jax.random.key(2), (40, m["d"])) * 8
    whole, whole_counts = ref.moe(p, "L1.moe", r, x, m)
    assert int(whole_counts.sum()) == 40 * 6
    parts_ref, parts_prog, rows = 0.0, 0.0, 0
    for first in range(0, 64, 8):
        ms = ref.dims(small_cfg(experts=64, top_k=6, experts_held=8,
                                first_expert=first))
        ps = dict(p, **{f"L1.moe/{b}": p[f"L1.moe/{b}"][first:first + 8]
                        for b in ("W_gate", "W_up", "W_down")})
        part, counts = ref.moe(ps, "L1.moe", r, x, ms)
        parts_ref = parts_ref + part
        y, stats, got_counts = _moe_layer(cfg, 8, first, x, p, r)
        parts_prog = parts_prog + y
        np.testing.assert_array_equal(np.asarray(got_counts), counts)
        np.testing.assert_array_equal(counts,
                                      whole_counts[first:first + 8])
        assert float(stats[2]) == 0.0
        rows += int(counts.sum())
    assert rows == 40 * 6
    np.testing.assert_allclose(parts_ref, whole, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(parts_prog, whole, rtol=2e-5, atol=1e-7)


def test_router_weights_are_a_softmax_over_the_chosen_logits():
    """`scoring: "softmax"` over all the experts, the k largest,
    renormalised, is exp(l_i) / sum over the chosen exp(l_j): the
    family's softmax over the top-k logits."""
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 4)
    r = jax.random.normal(jax.random.key(2), (24, m["d"])) * 8
    topi, w = ref.route(p, "L1.moe", r, m)
    logits = np.asarray(r @ p["L1.moe/router"], np.float64)
    want = np.sort(logits, axis=1)[:, ::-1][:, :m["k"]]
    want = np.exp(want) / np.exp(want).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(topi), np.argsort(-logits, axis=1)[:, :m["k"]])


def test_relu_gate_against_silu_gate():
    """`gate_activation: "relu"` is (relu(u W_gate) * (u W_up)) W_down
    in the forward pass and in both backward loops; the default stays
    the SiLU gate; anything else is refused."""
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 6)
    p = {k: v * 8 if "/W_" in k else v for k, v in p.items()}
    x = jax.random.normal(jax.random.key(5), (24, m["d"]))

    def dense(act):
        topi, w = ref.route(p, "L1.moe", x, m)
        y = 0.0
        for j in range(m["held"]):
            wj = jnp.sum(jnp.where(topi == j, w, 0.0), axis=-1)
            e = (act(x @ p["L1.moe/W_gate"][j])
                 * (x @ p["L1.moe/W_up"][j])) @ p["L1.moe/W_down"][j]
            y = y + wj[:, None] * e
        return y

    relu = _moe_layer(cfg, 8, 0, x, p)[0]
    silu = _moe_layer(cfg, 8, 0, x, p, act="silu")[0]
    np.testing.assert_allclose(relu, dense(jax.nn.relu), rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(silu, dense(jax.nn.silu), rtol=2e-5,
                               atol=1e-7)
    assert np.abs(np.asarray(relu - silu)).max() > 1e-3
    # the backward loop recomputes the pass with the same gate
    g_prog = jax.grad(lambda x: jnp.sum(jnp.sin(
        _moe_layer(cfg, 8, 0, x, p)[0])))(x)
    g_want = jax.grad(lambda x: jnp.sum(jnp.sin(ref.moe(
        p, "L1.moe", x, x, m)[0])))(x)
    np.testing.assert_allclose(g_prog, g_want, rtol=2e-4, atol=1e-6)
    with pytest.raises(ValueError, match="gate_activation"):
        _moe_layer(cfg, 8, 0, x, p, act="gelu")


def test_router_gradient_reaches_the_first_norm_through_the_second_bottom():
    """The expert layer's second bottom is what the router reads: its
    cotangent flows into that tensor (and in the net into the block's
    first norm), the experts' into the first bottom; a net whose router
    reads n2 gives other logits."""
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 7)
    p = {k: v * 8 if "/W_" in k else v for k, v in p.items()}
    x = jax.random.normal(jax.random.key(5), (24, m["d"]))
    r = jax.random.normal(jax.random.key(6), (24, m["d"])) * 8
    gx, gr = jax.grad(lambda x, r: jnp.sum(jnp.sin(
        _moe_layer(cfg, 8, 0, x, p, r)[0])), argnums=(0, 1))(x, r)
    wx, wr = jax.grad(lambda x, r: jnp.sum(jnp.sin(
        ref.moe(p, "L1.moe", r, x, m)[0])), argnums=(0, 1))(x, r)
    assert np.abs(np.asarray(wr)).max() > 1e-4
    np.testing.assert_allclose(gx, wx, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(gr, wr, rtol=2e-4, atol=1e-7)
    # one bottom: the router reads what the experts read
    one = _moe_layer(cfg, 8, 0, x, p)[0]
    np.testing.assert_allclose(one, ref.moe(p, "L1.moe", x, x, m)[0],
                               rtol=2e-5, atol=1e-7)
    assert np.abs(np.asarray(one - _moe_layer(cfg, 8, 0, x, p, r)[0])
                  ).max() > 1e-3

    # in the net: the logits differ between the two routers, each net
    # equals its reference, and L0.norm1's gradient holds the router's
    ids, tgt = batches(1, seed=3)[0]
    logits = {}
    for reads in ("n1", "n2"):
        net = Net(small_net(router_reads=reads))
        params = net.init(jax.random.key(0))
        # routers that matter: fresh ones give near-equal weights
        params = {ln: {bn: a * 40 if bn == "router" else a
                       for bn, a in bl.items()}
                  for ln, bl in params.items()}
        blobs, _ = net.apply(params, inputs(ids, tgt), train=True,
                             rng=jax.random.key(0))
        c = small_cfg(router_reads=reads)
        want, _ = ref.forward(
            {k: jnp.asarray(v) for k, v in flat(params).items()},
            jnp.asarray(ids[0]), ref.dims(c))
        logits[reads] = np.asarray(blobs["logits"][:, 0])
        np.testing.assert_allclose(logits[reads], want, rtol=5e-5,
                                   atol=5e-6)
    assert np.abs(logits["n1"] - logits["n2"]).max() > 1e-4
    moe = [ly for ly in small_net().layer if ly.name == "L2.moe"][0]
    assert list(moe.bottom) == ["L2.n2", "L2.n1"]
    moe = [ly for ly in small_net(router_reads="n2").layer
           if ly.name == "L2.moe"][0]
    assert list(moe.bottom) == ["L2.n2"]


def test_recompute_block_changes_no_value():
    ins = {"input_ids": jnp.ones((24, 2)) * 3,
           "target_ids": jnp.ones((24, 2)) * 5}
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


def test_full_width_net_text_parses_and_counts_370_5_million():
    """The cell's net: published widths, 8 of 64 experts a layer, an
    eighth of the vocabulary, the published layers 0-3."""
    from caffeonspark_tpu.proto import NetParameter
    npm = zoo.smallthinker()
    assert NetParameter.from_text(npm.to_text()) == npm
    assert NetParameter.from_binary(npm.to_binary()) == npm
    net = Net(npm)
    assert net.num_params() == 370_547_200
    layout = {ln: {bn: s for bn, s, _ in bl}
              for ln, bl in net.param_layout.items()}
    assert layout["L0.attn"] == {
        "W_q": (3584, 2560), "W_k": (512, 2560), "W_v": (512, 2560),
        "W_o": (2560, 3584)}
    assert sum(int(np.prod(s)) for s in layout["L0.attn"].values()) \
        == 20_971_520
    assert layout["L1.moe"] == {
        "router": (2560, 64), "W_gate": (8, 2560, 768),
        "W_up": (8, 2560, 768), "W_down": (8, 768, 2560)}
    assert sum(int(np.prod(s)) for ln in ("L1.norm1", "L1.attn", "L1.norm2",
                                          "L1.moe")
               for s in layout[ln].values()) == 68_326_400
    assert layout["embed"]["weight"] == (18992, 2560)
    assert layout["head.logits"]["weight"] == (18992, 2560)
    assert net.blob_shapes["logits"] == (16384, 1, 18992)
    assert len(net.recompute_blocks) == 4
    by_name = {ly.name: ly for ly in npm.layer}
    for i in range(4):
        ap = by_name[f"L{i}.attn"].attention_param
        assert int(ap.window) == (4096 if i else 0)
        assert bool(ap.rotary) == bool(i) and not ap.qk_norm
        assert (int(ap.num_heads), int(ap.num_kv_heads),
                int(ap.head_dim)) == (28, 4, 128)
        mp = by_name[f"L{i}.moe"].moe_param
        assert mp.gate_activation == "relu" and mp.scoring == "softmax"
        assert int(mp.top_k) == 6 and int(mp.num_experts) == 64
        assert not mp.selection_bias and not int(mp.shared_hidden_dim)
    # the whole model is the same function
    whole = zoo.smallthinker(experts_held=64, vocab=151936, layers=52,
                             seq=128)
    windows = [int(ly.attention_param.window) for ly in whole.layer
               if ly.type == "GroupedQueryAttention"]
    assert len(windows) == 52 and windows.count(4096) == 39
    assert [w == 0 for w in windows] == [i % 4 == 0 for i in range(52)]


def test_flops_count_the_scores_a_window_lets_a_row_see():
    """`utils/flops.py` (and through it `analysis/roofline.py`) count a
    windowed attention's visible scores and no others, as the reference
    does; `tp_param_specs` gives the held experts the expert axis."""
    from caffeonspark_tpu.analysis.roofline import analyze_net
    from caffeonspark_tpu.parallel.mesh import tp_param_specs
    from caffeonspark_tpu.utils.flops import (forward_flops,
                                              layer_forward_flops,
                                              visible_scores)
    net = Net(small_net())
    t, b, w = SMALL["seq"], SMALL["batch"], SMALL["window"]
    assert forward_flops(net) == ref.forward_flops(small_cfg(), t, b)
    per = layer_forward_flops(net)
    proj = 2 * t * b * (2 * 48 * 32 + 2 * 16 * 32)
    assert visible_scores(t, True, w) == w * (w + 1) // 2 + (t - w) * w \
        == ref.visible_pairs(t, w) == sum(min(r + 1, w) for r in range(t))
    assert visible_scores(t, True) == t * t // 2 == ref.visible_pairs(t, 0)
    assert visible_scores(t, True, t) == t * t // 2
    assert per["L0.attn"] == proj + b * 6 * (t * t // 2) * 4 * 8
    assert per["L1.attn"] == proj + b * 6 * ref.visible_pairs(t, w) * 4 * 8
    assert per["L1.attn"] < per["L0.attn"]
    rows = {r["layer"]: r for r in analyze_net(net, act_bytes=4,
                                               param_bytes=4)}
    assert rows["L1.attn"]["flops"] == 3 * per["L1.attn"]
    specs = tp_param_specs(net)
    assert all(tuple(s) == () for s in specs["L1.attn"].values())
    assert tuple(specs["L1.moe"]["W_up"]) == ("ep", None, None)
    # at the cell's shape
    assert ref.visible_pairs(16384, 4096) == 58_722_304


def test_a_windowed_layer_is_refused_under_a_mesh_that_shards_time():
    """No silent full attention: the ring masks by the diagonal alone."""
    from caffeonspark_tpu.parallel.sp import refuse_time_sharding
    with pytest.raises(ValueError, match="window"):
        refuse_time_sharding(Net(small_net()))
    refuse_time_sharding(Net(small_net(
        sliding_window_layout=(0,) * 52)))      # all global: nothing

