"""kanana-2-30b-a3b through the system against the plain reference
(`perfbench/reference/kanana2_30b_a3b.py`, float32, "highest"),
at a small size with the model's structure: 1 dense + 2 expert layers,
8 sigmoid-routed experts, top-2, 2 shared, latent attention with q/k
wider than v.

Tolerances.  Both sides are float32 on the CPU with exact products; what
differs is the order of sums (grouped products over sorted rows against
a dense loop over experts, fused against unfused reductions), so values
agree to a few float32 roundings of their largest intermediate: 2e-5
relative on logits and losses, 2e-4 of a leaf's norm on gradients
(sums over 32 tokens of products of ~6 layers), 5e-4 of the update's
size on parameters after three Adam steps (Adam divides by sqrt(v): a
gradient rounding is amplified where |g| is near delta)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.proto import SolverParameter
from caffeonspark_tpu.solver import Solver
from perfbench.reference import kanana2_30b_a3b as ref

SMALL = dict(vocab=64, hidden=32, heads=2, qk_nope=8, qk_rope=4, v_head=6,
             kv_lora_rank=16, dense_width=48, expert_width=12, experts=8,
             top_k=2, shared_experts=2, expert_layers=2, seq=16, batch=2)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "qk_nope_head_dim": z["qk_nope"],
            "qk_rope_head_dim": z["qk_rope"], "v_head_dim": z["v_head"],
            "kv_lora_rank": z["kv_lora_rank"],
            "intermediate_size": z["dense_width"],
            "moe_intermediate_size": z["expert_width"],
            "n_routed_experts": z["experts"],
            "num_experts_per_tok": z["top_k"],
            "n_shared_experts": z["shared_experts"],
            "experts_held": z.get("experts_held", z["experts"]),
            "first_expert": z.get("first_expert", 0),
            "vocab_size": z["vocab"],
            "num_hidden_layers": 1 + z["expert_layers"],
            "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
            "rms_norm_eps": 1e-6, "rope_theta": 1e6,
            "assumed": {"init_std": 0.02}, "solver": dict(SOLVER)}


def small_net(**over):
    z = dict(SMALL, **over)
    z.setdefault("experts_held", z["experts"])
    return zoo.kanana2(**z)


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


def _moe_layer(cfg, held, first, x, p, pre="L1.moe", tile=None):
    """The program's expert layer on (N, d) rows with the given share of
    the reference's weights."""
    from caffeonspark_tpu.proto import LayerParameter
    lp = LayerParameter.from_text(f'''
      name: "moe" type: "MixtureOfExperts" bottom: "x" top: "y" top: "stats"
      top: "counts"
      moe_param {{ num_experts: {cfg["n_routed_experts"]}
        hidden_dim: {cfg["moe_intermediate_size"]}
        top_k: {cfg["num_experts_per_tok"]} dispatch: "dropless"
        scoring: "sigmoid" selection_bias: true
        routed_scaling_factor: 2.448 gated: true
        shared_hidden_dim: {2 * cfg["moe_intermediate_size"]}
        experts_held: {held} first_expert: {first} }}''')
    sl = slice(first, first + held)
    blobs = [p[f"{pre}/router"], p[f"{pre}/bias"], p[f"{pre}/W_gate"][sl],
             p[f"{pre}/W_up"][sl], p[f"{pre}/W_down"][sl],
             p[f"{pre}/S_gate"], p[f"{pre}/S_up"], p[f"{pre}/S_down"]]
    return L.get_op("MixtureOfExperts").apply(L.Ctx(train=True), lp, blobs,
                                              [x])


def test_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares, with the shared experts
    counted once, are the whole layer's output: reference against
    itself, and the program's held-experts layer against the uncut
    reference."""
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 3)
    x = jax.random.normal(jax.random.key(1), (40, m["d"]))
    whole, _ = ref.moe(p, "L1.moe", x, m)
    shared_only, _ = ref.moe(p, "L1.moe", x, m, routed=False)
    parts_ref, parts_prog = 0.0, 0.0
    for first in range(0, 8, 2):
        ms = ref.dims(small_cfg(experts_held=2, first_expert=first))
        ps = dict(p, **{f"L1.moe/{b}": p[f"L1.moe/{b}"][first:first + 2]
                        for b in ("W_gate", "W_up", "W_down")})
        part, counts = ref.moe(ps, "L1.moe", x, ms, shared=False)
        parts_ref = parts_ref + part
        y, stats, got_counts = _moe_layer(cfg, 2, first, x, p)
        parts_prog = parts_prog + (y - shared_only)
        np.testing.assert_array_equal(np.asarray(got_counts), counts)
        assert float(stats[2]) == 0.0
        np.testing.assert_allclose(float(stats[1]),
                                   float(counts.sum()) / (40 * 2), rtol=1e-6)
    np.testing.assert_allclose(parts_ref + shared_only, whole, rtol=2e-5,
                               atol=1e-6)
    np.testing.assert_allclose(parts_prog + shared_only, whole, rtol=2e-5,
                               atol=1e-6)


@pytest.mark.parametrize("tile", [512, 8])
def test_every_token_on_one_expert_drops_nothing(monkeypatch, tile):
    """A selection bias that sends every token to experts 0 and 1: the
    held share [0, 2) gets all k N assignments (4x what an even router
    sends it), in several passes at the small tile, and the output and
    its gradient are the reference's."""
    monkeypatch.setattr(L, "_MOE_ROW_TILE", tile)
    cfg = small_cfg(experts_held=2)
    m = ref.dims(cfg)
    p = ref.init_params(small_cfg(), 4)
    p["L1.moe/bias"] = jnp.zeros(8).at[:2].set(10.0)
    x = jax.random.normal(jax.random.key(2), (48, m["d"]))
    if tile == 8:
        assert L._moe_chunk_rows(48, 2, 2, 8) == 32      # 3 passes of 96
    ps = dict(p, **{f"L1.moe/{b}": p[f"L1.moe/{b}"][:2]
                    for b in ("W_gate", "W_up", "W_down")})
    want, counts = ref.moe(ps, "L1.moe", x, m)
    y, stats, got = _moe_layer(cfg, 2, 0, x, p)
    np.testing.assert_array_equal(np.asarray(got), [48, 48])
    assert float(stats[1]) == 1.0 and float(stats[2]) == 0.0
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=1e-6)
    g_ref = jax.grad(lambda a: jnp.sum(jnp.sin(
        ref.moe(ps, "L1.moe", a, m)[0])))(x)
    g = jax.grad(lambda a: jnp.sum(jnp.sin(
        _moe_layer(cfg, 2, 0, a, p)[0])))(x)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-6)


def test_rope_turns_adjacent_pairs_by_position():
    x = jax.random.normal(jax.random.key(0), (9, 3, 8))
    got = L.rope_adjacent(x, 1e6)
    np.testing.assert_allclose(got, ref.rope(x, 1e6), rtol=1e-6, atol=1e-6)
    # by hand: pair i of position t turns by t * theta^(-2i/w)
    t, i = 5, 2
    ang = t * 1e6 ** (-2 * i / 8)
    a, b = np.asarray(x[t, 1, 2 * i]), np.asarray(x[t, 1, 2 * i + 1])
    np.testing.assert_allclose(got[t, 1, 2 * i],
                               a * np.cos(ang) - b * np.sin(ang), rtol=1e-5)
    np.testing.assert_allclose(got[t, 1, 2 * i + 1],
                               b * np.cos(ang) + a * np.sin(ang), rtol=1e-5)
    # a turn keeps lengths, and position 0 is the identity
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(got[0], x[0])


@pytest.mark.parametrize("path", ["einsum", "flash"])
def test_attention_with_wider_keys_than_values(monkeypatch, path):
    """192-wide q/k against 128-wide v through the one dispatch: the XLA
    einsum path, and the flash kernel (interpret mode), forward and
    gradients, against the reference's plain attention."""
    t, h = 256, 2
    ks = jax.random.split(jax.random.key(0), 4)
    q, k = (jax.random.normal(ks[i], (t, h, 192)) for i in (0, 1))
    v = jax.random.normal(ks[2], (t, h, 128))
    w = jax.random.normal(ks[3], (t, h, 128))
    if path == "flash":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    else:
        monkeypatch.setenv("COS_DISABLE_FLASH", "1")

    def prog(q, k, v):
        bh = lambda a: jnp.transpose(a, (1, 0, 2))[None]      # noqa: E731
        o = L._attention_dispatch(bh(q), bh(k), bh(v), causal=True)
        return jnp.sum(jnp.transpose(o[0], (1, 0, 2)) * w)

    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(ref._heads_attention(q, k, v) * w),
        argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(prog, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b in zip(got[1], want[1]):
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


def test_full_width_net_text_parses_and_counts_687_5_million():
    """The cell's net: published widths, 16 of 128 experts a layer, an
    eighth of the vocabulary, 1 + 5 layers."""
    from caffeonspark_tpu.proto import NetParameter
    npm = zoo.kanana2()
    assert NetParameter.from_text(npm.to_text()) == npm
    net = Net(npm)
    assert net.num_params() == 687_502_976
    layout = {ln: {bn: s for bn, s, _ in bl}
              for ln, bl in net.param_layout.items()}
    assert layout["L1.moe"]["router"] == (2048, 128)
    assert layout["L1.moe"]["W_gate"] == (16, 2048, 768)
    assert layout["L0.attn"]["W_q"] == (32 * 192, 2048)
    assert layout["L0.attn"]["W_kva"] == (512 + 64, 2048)
    assert layout["L0.attn"]["W_kvb"] == (32 * 256, 512)
    assert net.blob_shapes["logits"] == (4096, 2, 16032)
    assert len(net.recompute_blocks) == 6

