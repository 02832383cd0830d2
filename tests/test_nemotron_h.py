"""NVIDIA-Nemotron-3-Nano-30B-A3B through the system against the plain
reference (`perfbench/reference/nemotron3_nano_30b_a3b.py`, float32,
"highest"), at a small size with the model's structure: the published
blocks 34-42 (`EMEMEMEM*`, one operator a block), Mamba-2 of 4 heads of
8 over 2 groups of 16 states in chunks of 8, 16 sigmoid-routed ungated
squared-ReLU experts top 3 with an ungated shared expert, 16 query
heads over one key/value head (g = 16) without positions.

Tolerances: both sides are float32 with exact products; what differs is
the order of sums and, in the scan, the chunked form against one token
a step (decays as exponentials of differences of running sums against a
product of per-step exponentials): a few float32 roundings, relative
2e-5 on values and 2e-4 on gradient leaves' norms.  A bfloat16 path
reads 1e-2 and more on either."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import route
from caffeonspark_tpu.proto import (LayerParameter, NetState, Phase,
                                    SolverParameter)
from caffeonspark_tpu.solver import Solver
from perfbench.reference import nemotron3_nano_30b_a3b as ref

SMALL = dict(vocab=64, hidden=32, heads=16, kv_heads=1, head_dim=8,
             mamba_heads=4, mamba_head_dim=8, n_groups=2, d_state=16,
             chunk=8, expert_width=12, shared_width=20, experts=16,
             top_k=3, experts_held=16, seq=20, batch=2)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "num_key_value_heads": z["kv_heads"], "head_dim": z["head_dim"],
            "mamba_num_heads": z["mamba_heads"],
            "mamba_head_dim": z["mamba_head_dim"], "n_groups": z["n_groups"],
            "ssm_state_size": z["d_state"], "conv_kernel": 4,
            "moe_intermediate_size": z["expert_width"],
            "moe_shared_expert_intermediate_size": z["shared_width"],
            "n_shared_experts": 1, "n_routed_experts": z["experts"],
            "num_experts_per_tok": z["top_k"], "routed_scaling_factor": 2.5,
            "experts_held": z["experts_held"],
            "first_expert": z.get("first_expert", 0),
            "vocab_size": z["vocab"],
            "num_hidden_layers": z.get("layers", 9),
            "first_layer": z.get("first_layer", 34),
            "hybrid_override_pattern": zoo.NEMOTRON_H_PATTERN,
            "layer_norm_epsilon": 1e-5, "time_step_min": 1e-3,
            "time_step_max": 1e-1,
            "assumed": {"init_std": 0.02, "conv_bound": 0.5},
            "solver": dict(SOLVER)}


def small_net(**over):
    return zoo.nemotron_h(**dict(SMALL, **over))


def flat(tree):
    return {f"{ln}/{bn}": np.asarray(a) for ln, bl in tree.items()
            for bn, a in bl.items()}


def unflat(p):
    out = {}
    for k, v in p.items():
        ln, bn = k.split("/")
        out.setdefault(ln, {})[bn] = v
    return out


def batches(n, seed=0, seq=SMALL["seq"]):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, SMALL["vocab"], (n, SMALL["batch"], seq + 1))
    return [(r[:, :-1], r[:, 1:]) for r in rows]


def inputs(ids, tgt):
    return {"input_ids": jnp.asarray(ids.T, jnp.float32),
            "target_ids": jnp.asarray(tgt.T, jnp.float32)}


def close(got, want, rel, what=""):
    got, want = np.asarray(got), np.asarray(want)
    assert np.linalg.norm(got - want) <= rel * max(
        np.linalg.norm(want), 1e-12), what


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# ------------------------------------------------------------- the scan

def _scan_inputs(bsz, t, h, p, g, n, dt_scale=1.0, seed=0):
    """u, dt, A, B, C batch-major as the recurrence reads them, D, and a
    weight for y."""
    ks = jax.random.split(jax.random.key(seed), 7)
    return ((jax.random.normal(ks[0], (bsz, t, h, p)),
             dt_scale * jax.nn.softplus(
                 jax.random.normal(ks[1], (bsz, t, h)) - 2),
             -jnp.exp(jax.random.normal(ks[2], (h,))),
             jax.random.normal(ks[3], (bsz, t, g, n)),
             jax.random.normal(ks[4], (bsz, t, g, n)),
             jax.random.normal(ks[6], (h,))),
            jax.random.normal(ks[5], (bsz, t, h, p)))


def _recurrence(u, dt, a, b, c, d):
    """The reference's token-by-token scan, a row at a time, and the
    skip."""
    return jnp.stack([ref.ssd_recurrence(u[i], dt[i], a, b[i], c[i])
                      for i in range(u.shape[0])]) + d[:, None] * u


def _scan(u, dt, a, b, c, d, chunk):
    """`ssd_scan` on the time-major [u | B | C] that the layer hands it,
    back as the recurrence's (B, T, H, P)."""
    bsz, t, h, p = u.shape
    g, n = b.shape[2:]
    x = jnp.concatenate([v.reshape(bsz, t, -1) for v in (u, b, c)], axis=-1)
    y = L.ssd_scan(jnp.swapaxes(x, 0, 1), jnp.swapaxes(dt, 0, 1), a, d,
                   g, n, chunk)
    return jnp.swapaxes(y, 0, 1).reshape(bsz, t, h, p)


# the XLA form at the small net's sizes (chunks of 8, groups of 2
# chunks), and the kernels in interpret mode at sizes that tile (chunks
# of 128, 128 states, a group's heads 128 channels wide).  The last
# column is the gap allowed against the recurrence: where a chunk of
# 128 tokens forgets its start the running sum of dt A reaches -1,000
# inside it, an ulp of which is 6e-5 of a decay (either form reads
# 3e-5 on dt's gradient there).  A's gradient is a sum over all tokens
# of terms of both signs, and a token's own term (decay 1, no
# derivative) cancels in it only as far as float32 goes: the kernels,
# which take what reaches cum from y and d(dt u) instead of summing
# (L, L) matrices, are allowed five times the gap there (they read
# 3e-4 where nothing but the token itself survives, the XLA form 5e-5;
# 2e-6 on both at decays of common size)
SCANS = [
    ("xla", 1, 8, 4, 4, 1.0, 2e-5),     # one chunk
    ("xla", 2, 40, 4, 2, 1.0, 2e-5),    # groups of chunks, B > 1, G < H
    ("xla", 2, 37, 4, 1, 1.0, 2e-5),    # a T that is no multiple of the chunk
    ("xla", 1, 40, 4, 2, 1e-4, 2e-5),   # decays near 1: the state hardly fades
    ("xla", 1, 40, 4, 2, 60.0, 2e-5),   # near 0: a chunk forgets its start
    ("kernel", 1, 128, 8, 1, 1.0, 2e-5),    # one chunk, R = 8
    ("kernel", 2, 600, 16, 2, 1.0, 2e-5),   # grid steps, ragged, B = 2, G = 2
    ("kernel", 1, 300, 2, 2, 1.0, 2e-5),    # R = 1: a head fills a tile
    ("kernel", 1, 400, 8, 1, 1e-4, 2e-5),   # decays near 1
    ("kernel", 1, 400, 8, 1, 60.0, 1e-4),   # decays near 0
]


@pytest.mark.parametrize("form,bsz,t,h,g,dt_scale,rel", SCANS)
def test_chunked_scan_against_the_token_recurrence(monkeypatch, form, bsz,
                                                   t, h, g, dt_scale, rel):
    """Value and every gradient (u, dt, A, B, C, D) of `ssd_scan` equal
    the time-step recurrence's, in both forms: XLA's with groups of 2
    chunks of 8 so that the kept edges, the padding and the carry are
    all exercised, and the kernels' (interpret mode), which are held to
    the XLA form at the same operands besides, closer (the two share
    the chunked algebra; A's gradient is a sum over all tokens of terms
    of both signs)."""
    monkeypatch.setattr(L, "_SSD_GROUP", 2)
    route.forget("ssd")
    if form == "kernel":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
        r = h // g
        p, n, chunk = 128 // r, 128, 128
    else:
        p, n, chunk = 8, 16, 8
    x, w = _scan_inputs(bsz, t, h, p, g, n, dt_scale)
    names = "u dt A B C D".split()

    def both(fn):
        return fn(*x), jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                                argnums=tuple(range(6)))(*x)

    got, grads = both(lambda *a: _scan(*a, chunk))
    assert [e["form"] for e in route.plans()["ssd"].values()] == [form]
    want, wants = both(_recurrence)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))
    loose = {"A": 5 * rel} if form == "kernel" else {}
    for name, a, b in zip(names, grads, wants):
        close(a, b, loose.get(name, rel), name)
    if form == "kernel":
        monkeypatch.delenv("COS_FLASH_INTERPRET")
        xla, xlas = both(lambda *a: _scan(*a, chunk))
        assert route.plans()["ssd"].popitem()[1]["form"] == "xla"
        close(got, xla, 2e-6, "y")
        loose["dt"] = 5e-5
        for name, a, b in zip(names, grads, xlas):
            close(a, b, loose.get(name, 5e-6), name)


def test_scan_records_what_was_lowered_and_keeps_no_token_state(monkeypatch):
    """`info.ssd` at the cell's shape.  On the CPU: the XLA form, the
    chunk, the chunks a row and a group, the kept states' bytes; the
    backward's residuals hold the operands, y's sibling is not among
    them, and the only state kept is one a group of chunks.  Where the
    kernels are lowered: the state before every chunk (134 MB), two
    chunks a grid step, the backward call's VMEM inside the default
    window."""
    route.forget("ssd")
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (8192, 1, 6144), (8192, 1, 64), (64,), (64,))]
    key = "1x8192 64 heads of 64 over 8 groups of 128 states"
    jax.eval_shape(lambda *a: L.ssd_scan(*a, 8, 128, 128), *shapes)
    assert route.plans()["ssd"] == {key: {
        "form": "xla", "chunk": 128, "chunks": 64,
        "chunks_a_group": L._SSD_GROUP,
        "edges_bytes": 64 // L._SSD_GROUP * 64 * 64 * 128 * 4}}
    _, res = jax.eval_shape(
        lambda *a: L._ssd_groups_fwd(a[:4], a[4]),
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (4, 1, 16, 128, 8, 8, 64), (4, 1, 16, 128, 8, 8),
            (4, 1, 16, 128, 8, 128), (4, 1, 16, 128, 8, 128), (8, 8))))
    assert [r.shape for r in jax.tree.leaves(res)][-1] == (4, 1, 8, 8, 64,
                                                            128)
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    jax.eval_shape(lambda *a: L.ssd_scan(*a, 8, 128, 128), *shapes)
    entry = route.plans()["ssd"][key]
    assert entry == {
        "form": "kernel", "chunk": 128, "chunks": 64, "chunks_a_group": 1,
        "edges_bytes": 64 * 64 * 64 * 128 * 4, "chunks_a_step": 2,
        "vmem_bytes": entry["vmem_bytes"]}
    assert entry["vmem_bytes"] + (1 << 19) <= 16 << 20


# ------------------------------------------------------- layer by layer

def _layer(kind, text, blobs, bottoms):
    lp = LayerParameter.from_text(
        f'name: "a" type: "{kind}" bottom: "x" top: "y" {text}')
    return L.get_op(kind).apply(L.Ctx(train=True), lp, blobs, bottoms)


def _cfg_params(cfg, lname, seed=3, scale=None):
    """The reference's blobs of layer `lname` (gaussian ones scaled up
    so that every term of the layer weighs in), program order."""
    p = {k: v for k, v in ref.init_params(cfg, seed).items()
         if k.startswith(lname + "/")}
    if scale:
        p = {k: v * (scale if k.split("/")[1].startswith(("W", "S_",
                                                           "router"))
                     else 1.0) for k, v in p.items()}
    return p


MAMBA2 = ('mamba2_param { num_heads: 4 head_dim: 8 n_groups: 2 d_state: 16 '
          'd_conv: 4 chunk: 8 rms_norm_eps: 1e-5 }')
# a Mamba-2 layer that the scan's kernels take (interpret mode): two
# heads of 64 over one group of 128 states, chunks of 128, two chunks
TILED = dict(mamba_heads=2, mamba_head_dim=64, n_groups=1, d_state=128,
             chunk=128, seq=256)
MAMBA2_TILED = ('mamba2_param { num_heads: 2 head_dim: 64 n_groups: 1 '
                'd_state: 128 d_conv: 4 chunk: 128 rms_norm_eps: 1e-5 }')
MOE = ('moe_param { num_experts: 16 hidden_dim: 12 top_k: 3 '
       'dispatch: "dropless" scoring: "sigmoid" selection_bias: true '
       'routed_scaling_factor: 2.5 norm_epsilon: 1e-20 gated: false '
       'activation: "relu2" shared_hidden_dim: 20 experts_held: 16 }')
GQA = ('attention_param { num_heads: 16 num_kv_heads: 1 head_dim: 8 '
       'causal: true rotary: false }')


@pytest.mark.parametrize("layer", ["mamba2", "mamba2_kernels", "moe",
                                   "attn", "attn_flash"])
def test_every_operator_output_and_parameter_gradients(monkeypatch, layer):
    """Each of the three operators alone against the reference's, value
    and the gradient of every blob and of the input; the g = 16
    attention on the einsum route and through the flash kernels, the
    Mamba-2 mixer with its scan in XLA's form and (two batch columns of
    two chunks, at sizes that tile) on the scan's and the convolution's
    kernels, which read [u | B | C] where the stage before wrote it."""
    cfg = small_cfg(**(TILED if layer == "mamba2_kernels" else {}))
    m = ref.dims(cfg)
    t, bsz = {"attn_flash": (128, 1), "attn": (20, 2),
              "mamba2_kernels": (256, 2)}.get(layer, (20, 1))
    if layer in ("attn_flash", "mamba2_kernels"):
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
        route.forget("flash", "ssd")
    kind, text, lname, fn = {
        "mamba2": ("Mamba2", MAMBA2, "L1.mamba2", ref.mamba2),
        "mamba2_kernels": ("Mamba2", MAMBA2_TILED, "L1.mamba2", ref.mamba2),
        "moe": ("MixtureOfExperts", MOE, "L0.moe",
                lambda *a: ref.moe(*a)[0]),
        "attn": ("GroupedQueryAttention", GQA, "L8.attn", ref.attention),
        "attn_flash": ("GroupedQueryAttention", GQA, "L8.attn",
                       ref.attention)}[layer]
    p = _cfg_params(cfg, lname, scale=8.0)
    blobs = list(p.values())
    x = jax.random.normal(jax.random.key(1), (t, bsz, 32))
    w = jax.random.normal(jax.random.key(2), (t, bsz, 32))

    def system(x, *blobs):
        return jnp.sum(_layer(kind, text, list(blobs), [x])[0] * w)

    def plain(x, *blobs):
        q = dict(zip(p, blobs))
        return sum(jnp.sum(fn(q, lname, x[:, i], m) * w[:, i])
                   for i in range(bsz))

    got = _layer(kind, text, blobs, [x])[0]
    for i in range(bsz):
        np.testing.assert_allclose(got[:, i], fn(p, lname, x[:, i], m),
                                   rtol=2e-4, atol=2e-5)
    every = tuple(range(len(blobs) + 1))
    grads = jax.grad(system, argnums=every)(x, *blobs)
    wants = jax.grad(plain, argnums=every)(x, *blobs)
    for name, a, b in zip(["x"] + list(p), grads, wants):
        if name.endswith("/bias"):          # moves the choice alone
            assert not np.asarray(a).any() and not np.asarray(b).any()
            continue
        close(a, b, 2e-4, name)
    if layer == "attn_flash":
        plan = route.plans()["flash"]
        assert list(plan) == ["16x128x8/8 float32 g16 causal"]
    if layer == "mamba2_kernels":
        assert [e["form"] for e in route.plans()["ssd"].values()] == [
            "kernel"]


def test_relu2_is_not_relu_and_the_default_is_todays_layer():
    """`activation: "relu2"` squares; a layer that does not set the
    field traces to the jaxpr it had (ReLU, ungated)."""
    cfg = small_cfg()
    p = _cfg_params(cfg, "L0.moe", scale=8.0)
    x = jax.random.normal(jax.random.key(1), (20, 2, 32))
    plain = MOE.replace('activation: "relu2" shared_hidden_dim: 20', "")
    blobs = list(p.values())[:4]
    relu = _layer("MixtureOfExperts", plain, blobs, [x])[0]
    square = _layer("MixtureOfExperts", plain.replace(
        "gated: false", 'gated: false activation: "relu2"'), blobs, [x])[0]
    assert float(jnp.abs(relu - square).max()) > 1e-2
    text = str(jax.make_jaxpr(lambda x, *b: _layer(
        "MixtureOfExperts", plain, list(b), [x])[0])(x, *blobs))
    assert "integer_pow" not in text and "square" not in text
    with pytest.raises(ValueError, match="relu or relu2"):
        _layer("MixtureOfExperts", plain.replace(
            "gated: false", 'gated: false activation: "gelu"'), blobs, [x])


# ---------------------------------------------------------- the whole net

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
    assert {"L1.mamba2/A_log", "L0.moe/S_down", "L8.attn/W_k",
            "head.logits/weight"} <= set(p0)
    np.testing.assert_allclose(p0["L1.mamba2/A_log"], np.log([1, 2, 3, 4]),
                               rtol=1e-6)

    net = solver.train_net
    ids, tgt = data[0]
    blobs, _ = net.apply(params, inputs(ids, tgt), train=True,
                         rng=jax.random.key(0))
    want, counts = ref.forward(ref.init_params(cfg, 5), jnp.asarray(ids[0]),
                               ref.dims(cfg))
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]), want,
                               rtol=2e-5, atol=2e-6)
    stats = np.asarray(blobs["L0.moe_stats"])
    assert stats[1] == 1.0 and stats[2] == 0.0  # every expert held
    assert counts.shape == (9, 16) and not np.asarray(counts[1]).any()

    step = jax.jit(solver.train_step_fn())
    for it, (ids, tgt) in enumerate(data):
        params, st, o = step(params, st, inputs(ids, tgt),
                             jax.random.key(it))
        np.testing.assert_allclose(float(o["loss"]), out["losses"][it],
                                   rtol=2e-5)
        rows = np.stack([np.asarray(o[f"L{i}.moe_rows"])
                         for i in (0, 2, 4, 6)])
        np.testing.assert_array_equal(rows, out["counts"][it][::2][:4])
        if it == 0:
            for k, v in kept["m1"].items():     # (1 - b1) x clipped gradient
                close(flat(st.history)[k], v, 2e-4, k)
            for k, v in kept["v1"].items():
                close(flat(st.history2)[k], v, 4e-4, k)
    last = flat(params)
    for k, v in kept["p_last"].items():
        moved = np.linalg.norm(v - kept["p0"][k])
        assert np.linalg.norm(last[k] - v) <= 5e-4 * moved + 1e-9, k


def _loss_and_grads(net_param, p, data):
    net = Net(net_param, NetState(phase=Phase.TRAIN))
    ids, tgt = data
    loss, grads = jax.value_and_grad(
        lambda q: net.loss(q, inputs(ids, tgt), train=True)[0])(p)
    return float(loss), flat(grads)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_recompute_blocks_on_and_off_give_equal_gradients(monkeypatch, form):
    """The published blocks 40-42 (`EM*`: every operator once), with the
    scan in XLA's form and on its kernels (where the attention and the
    convolution stage are on theirs too)."""
    cut = dict(first_layer=40, layers=3)
    if form == "kernel":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
        cut.update(TILED)
    seq = cut.get("seq", SMALL["seq"])
    p = unflat(ref.init_params(small_cfg(**cut), 5))
    data = batches(1, seq=seq)[0]
    on = small_net(**cut)
    assert len(Net(on, NetState(phase=Phase.TRAIN)).recompute_blocks) == 3
    route.forget("recompute", "ssd")
    l_on, g_on = _loss_and_grads(on, p, data)
    l_off, g_off = _loss_and_grads(small_net(recompute=False, **cut), p,
                                   data)
    assert l_on == pytest.approx(l_off, rel=1e-6)
    for k, v in g_off.items():
        close(g_on[k], v, 1e-5, k)
    assert [e["form"] for e in route.plans()["ssd"].values()] == [form]
    kept = route.plans()["recompute"]["blocks"]
    assert set(kept["L1"]) == {"ssd.y", "ssd.edges"}
    if form == "kernel":        # y time-major, a state a chunk
        assert kept["L1"] == {"ssd.y": 256 * 2 * 128 * 4,
                              "ssd.edges": 2 * 2 * 128 * 128 * 4}
    else:
        assert kept["L1"]["ssd.y"] == 2 * 24 * 32 * 4   # padded to 3 chunks


def test_refused_by_name_under_a_time_sharding_mesh():
    from caffeonspark_tpu.parallel.sp import refuse_time_sharding
    with pytest.raises(ValueError, match=r"Mamba2.*'L1.mamba2'"):
        refuse_time_sharding(Net(small_net()))


# -------------------------------------------------------------- the share

def test_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """The parts that all 16 shares' routed experts give, with the
    shared expert counted once, are the uncut reference's expert layer;
    the program's share equals the reference's share."""
    whole = small_cfg()
    m = ref.dims(whole)
    p = _cfg_params(whole, "L0.moe", scale=8.0)
    x = jax.random.normal(jax.random.key(4), (20, 32))
    want, counts = ref.moe(p, "L0.moe", x, m)
    assert int(counts.sum()) == 20 * 3
    total = ref.moe(p, "L0.moe", x, m, routed=False)[0]      # shared, once
    for first in range(16):
        share = small_cfg(experts_held=1, first_expert=first)
        q = dict(p)
        q["L0.moe/W1"] = p["L0.moe/W1"][first:first + 1]
        q["L0.moe/W2"] = p["L0.moe/W2"][first:first + 1]
        part, c = ref.moe(q, "L0.moe", x, ref.dims(share), shared=False)
        assert int(c[0]) == int(counts[first])
        total = total + part
        if first in (0, 7):
            got = _layer("MixtureOfExperts", MOE.replace(
                "experts_held: 16",
                f"experts_held: 1 first_expert: {first}"),
                list(q.values()), [x[:, None]])[0][:, 0]
            np.testing.assert_allclose(
                got, part + ref.moe(p, "L0.moe", x, m, routed=False)[0],
                rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------- the inventory

def test_inventory_at_published_depth_and_for_the_cell():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = json.load(open(os.path.join(
        root, "perfbench", "configs", "nemotron3_nano_30b_a3b.json")))
    assert ref.num_params(cfg) == 666_963_456
    assert Net(zoo.nemotron_h()).num_params() == 666_963_456
    whole = dict(cfg, num_hidden_layers=52, first_layer=0,
                 vocab_size=131072, experts_held=128)
    assert ref.num_params(whole) == 31_577_940_288
    assert ref.dims(cfg)["kinds"] == "EMEMEMEM*"
    # a token's forward pass: 717 MFLOP, 45% of them the Mamba-2 layers
    flops = ref.forward_flops(cfg, 8192, 1) / 8192
    assert round(flops / 1e6) == 717
    from caffeonspark_tpu.utils.flops import forward_flops
    assert forward_flops(Net(zoo.nemotron_h(), NetState(phase=Phase.TRAIN))) \
        == pytest.approx(ref.forward_flops(cfg, 8192, 1), rel=0.002)
