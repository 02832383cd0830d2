"""Phi-4-mini-flash-reasoning through the system against the plain
reference (`perfbench/reference/phi4flash_mini.py`, float32,
"highest"), at a small size with the model's structure: hidden 64, 4
query heads of 16 over 2 key/value heads (read as pairs), d_inner 128,
16 states, 4 taps, a window of 8 keys, a whole model of 8 layers
(first_layer 0) and the middle cut of a 32-layer layout.

Tolerances as `tests/test_kanana2.py` gives them: both sides are float32
with exact products, what differs is the order of sums."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import pallas_kernels as pk
from caffeonspark_tpu.ops import route
from caffeonspark_tpu.proto import NetState, Phase, SolverParameter
from caffeonspark_tpu.solver import Solver
from perfbench.reference import phi4flash_mini as ref

SMALL = dict(vocab=96, hidden=64, heads=4, kv_heads=2, head_dim=16,
             intermediate=96, d_inner=128, d_state=16, d_conv=4, dt_rank=4,
             window=8, total_layers=8, first_layer=0, layers=8, seq=64,
             batch=2, chunk=16)
MIDDLE = dict(total_layers=32, first_layer=14, layers=6)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "num_key_value_heads": z["kv_heads"],
            "intermediate_size": z["intermediate"],
            "sliding_window": z["window"], "layer_norm_eps": 1e-5,
            "vocab_size": z["vocab"], "num_hidden_layers": z["layers"],
            "first_layer": z["first_layer"],
            "tie_word_embeddings": z.get("tie", True),
            "published": {"num_hidden_layers": z["total_layers"]},
            "assumed": {"init_std": 0.02, "lambda_std": 0.1,
                        "conv_bound": 0.5, "dt_min": 1e-3, "dt_max": 1e-1,
                        "mamba_expand": z["d_inner"] // z["hidden"],
                        "mamba_d_state": z["d_state"],
                        "mamba_d_conv": z["d_conv"],
                        "mamba_dt_rank": z["dt_rank"]},
            "solver": dict(SOLVER)}


def small_net(**over):
    return zoo.phi4flash(**dict(SMALL, **over))


def flat(tree):
    return {f"{ln}/{bn}": np.asarray(a) for ln, bl in tree.items()
            for bn, a in bl.items()}


def unflat(p):
    out = {}
    for k, v in p.items():
        ln, bn = k.split("/")
        out.setdefault(ln, {})[bn] = jnp.asarray(v)
    return out


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


@pytest.fixture(autouse=True)
def one_plain_scan(monkeypatch):
    """The benchmark's reference puts 256 steps of its scan under one
    `jax.checkpoint` (`SCAN_BLOCK`: a row of 8,192 has to fit the chip
    beside the system's step).  A checkpoint boundary changes no value,
    only what the backward pass holds; this suite compares values, on
    rows shorter than a block, so it runs the one plain scan (as the
    copy of the reference that the package kept until PR 45 did)."""
    monkeypatch.setattr(ref, "SCAN_BLOCK", 0)


def close(got, want, rel, what=""):
    got, want = np.asarray(got), np.asarray(want)
    assert np.linalg.norm(got - want) <= rel * max(
        np.linalg.norm(want), 1e-12), what


# ------------------------------------------------ the whole net, both cuts

@pytest.mark.parametrize("over,seq", [({}, 64), (MIDDLE, 64), (MIDDLE, 40)],
                         ids=["whole8", "middle_of_32", "middle_T40"])
def test_init_logits_loss_gradients_and_three_adam_steps(over, seq):
    """`first_layer` 0 with N = 8, the middle cut of an N = 32 layout,
    and a T that is no multiple of the scan's chunk."""
    over = dict(over, seq=seq)
    cfg = small_cfg(**over)
    sp = SolverParameter.from_text(
        'type: "Adam" lr_policy: "fixed" random_seed: 5 '
        + " ".join(f"{k}: {v}" for k, v in SOLVER.items()))
    solver = Solver(sp, small_net(**over))
    params, st = solver.init()
    kept = {}
    data = batches(3, seq=seq)
    out = ref.train_steps(cfg, 5, data,
                          lambda name, tree: kept.setdefault(
                              name, {k: np.array(v) for k, v in tree.items()}))
    p0 = flat(params)
    assert set(p0) == set(kept["p0"])
    for k, v in kept["p0"].items():            # same seeded draws: exact
        np.testing.assert_array_equal(p0[k], v, err_msg=k)
    assert "head.logits/weight" not in p0       # tied: the embedding's
    assert ref.num_params(cfg) == solver.train_net.num_params()

    net = solver.train_net
    ids, tgt = data[0]
    blobs, _ = net.apply(params, inputs(ids, tgt), train=True,
                         rng=jax.random.key(0))
    want = ref.forward(ref.init_params(cfg, 5), jnp.asarray(ids[0]),
                       ref.dims(cfg))
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]), want,
                               rtol=2e-5, atol=2e-6)

    step = jax.jit(solver.train_step_fn())
    for it, (ids, tgt) in enumerate(data):
        params, st, o = step(params, st, inputs(ids, tgt),
                             jax.random.key(it))
        np.testing.assert_allclose(float(o["loss"]), out["losses"][it],
                                   rtol=2e-5)
        if it == 0:
            for k, v in kept["m1"].items():     # (1 - b1) x clipped gradient
                close(flat(st.history)[k], v, 3e-4, k)
            for k, v in kept["v1"].items():
                close(flat(st.history2)[k], v, 6e-4, k)
    last = flat(params)
    for k, v in kept["p_last"].items():
        moved = np.linalg.norm(v - kept["p0"][k])
        assert np.linalg.norm(last[k] - v) <= 5e-4 * moved + 1e-9, k


# ----------------------------------------------------- every kind of layer

def _mixers(cfg, seed=3):
    """The reference's parameters, one sequence's stream into every
    layer, and what each mixer half gives: [(kind, x, x + Mixer(LN x),
    hands, shared before)]."""
    m = ref.dims(cfg)
    p = ref.init_params(cfg, seed)
    x = jax.random.normal(jax.random.key(seed), (SMALL["seq"], m["d"]))
    shared, out = {}, []
    for i, kind in enumerate(m["kinds"]):
        y, hands = ref.mixer(p, i, x, shared, m)
        out.append((kind, x, y, hands, dict(shared)))
        shared.update(hands)
        x = ref.feed_forward(p, i, y, m)
    return m, p, out


@pytest.mark.parametrize("layer", range(6), ids=[
    "mamba", "window", "mamba_memory", "full_kv", "gmu", "cross"])
def test_every_layer_kind_output_and_parameter_gradients(layer):
    """Published layers 14-19 one at a time: the program's layers of
    block L<i> on the reference's input to it give the reference's
    output, and the same parameter gradients."""
    cfg = small_cfg(**MIDDLE)
    m, p, halves = _mixers(cfg)
    kind, x, want, hands, shared = halves[layer]
    assert kind == ("mamba", "window", "mamba_memory", "full_kv", "gmu",
                    "cross")[layer]
    net = Net(small_net(**MIDDLE, batch=1, recompute=False),
              NetState(phase=Phase.TRAIN))
    pre = f"L{layer}"
    names = [lp.name for lp in net.compute_layers
             if lp.name.split(".")[0] == pre
             and lp.name.split(".")[1] in ("norm1", "mamba", "attn", "gmu",
                                           "res1")]
    feeds = {"h0" if layer == 0 else f"L{layer - 1}.out": x[:, None]}
    # what earlier layers handed on, as the program lays it out
    if "memory" in shared:
        feeds["L2.memory"] = shared["memory"][:, None]
    if "kv" in shared:
        k, v = shared["kv"]
        t, hkv, hd = k.shape
        feeds["L3.k"] = jnp.transpose(k, (1, 0, 2))[None]
        v = jnp.repeat(v.reshape(t, hkv // 2, 1, 2 * hd), 2, axis=2)
        feeds["L3.v"] = jnp.transpose(v.reshape(t, hkv, 2 * hd),
                                      (1, 0, 2))[None]

    def prog(params):
        blobs, _ = net.apply(params, feeds, train=True, layers=names)
        return blobs

    params = unflat(p)
    blobs = prog(params)
    np.testing.assert_allclose(np.asarray(blobs[f"{pre}.h1"][:, 0]), want,
                               rtol=2e-5, atol=2e-6)
    if kind == "mamba_memory":
        np.testing.assert_allclose(
            np.asarray(blobs["L2.memory"][:, 0]), hands["memory"],
            rtol=2e-5, atol=2e-6)
    if kind == "full_kv":
        np.testing.assert_allclose(
            np.asarray(blobs["L3.k"][0]),
            jnp.transpose(hands["kv"][0], (1, 0, 2)), rtol=2e-5, atol=2e-6)
    w = jax.random.normal(jax.random.key(9), want.shape)
    got = flat(jax.grad(lambda q: jnp.sum(prog(q)[f"{pre}.h1"][:, 0] * w))(
        params))
    ref_g = jax.grad(lambda q: jnp.sum(
        ref.mixer(q, layer, x, shared, m)[0] * w))(p)
    moved = [k for k in p if k.split(".")[0] == pre
             and k.split(".")[1].split("/")[0] in ("norm1", "mamba", "attn",
                                                   "gmu")]
    assert moved
    for k in moved:
        close(got[k], ref_g[k], 2e-4, k)
        assert np.linalg.norm(ref_g[k]) > 0, k


# --------------------------------------------------------- the scan's forms

def _scan_inputs(bsz=2, t=72, ch=256, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (bsz, t, ch)),
            jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, ch)) - 2),
            -jnp.exp(jax.random.normal(ks[2], (ch, n)) * 0.5),
            jax.random.normal(ks[3], (bsz, t, n)),
            jax.random.normal(ks[4], (bsz, t, n))), \
        jax.random.normal(ks[5], (bsz, t, ch))


def _plain_scan(u, dt, a, b, c):
    """`lax.scan` over time on the (C, N) state, a row at a time."""
    return jnp.stack([ref.scan_steps(jnp.zeros(a.shape), x, a)[1]
                      for x in zip(u, dt, b, c)])


def _forms(chunk):
    def kernels(*x):
        plan = pk.ssm_scan_plan(x[0].shape[1], x[0].shape[2], x[2].shape[1],
                                chunk)
        assert plan is not None
        return pk.selective_scan_kernels(*x, plan, interpret=True)
    return {"kernels": kernels,
            "xla": lambda *x: L.selective_scan_xla(*x, chunk)}


@pytest.mark.parametrize("form", ["kernels", "xla"])
@pytest.mark.parametrize("t", [64, 72])
def test_selective_scan_forms_against_lax_scan(form, t):
    """Values and all five gradients of the recurrence (D's skip is the
    layer's: the sixth), a row of several chunks, T whole chunks or
    not: the kernels in interpret mode and the XLA form against a scan
    over time."""
    x, w = _scan_inputs(t=t)
    f = _forms(16)[form]
    np.testing.assert_allclose(f(*x), _plain_scan(*x), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=range(5))(*x)
    want = jax.grad(lambda *a: jnp.sum(_plain_scan(*a) * w),
                    argnums=range(5))(*x)
    for name, g, r in zip(("du", "ddt", "dA", "dB", "dC"), got, want):
        close(g, r, 1e-5, name)


@pytest.mark.parametrize("form", ["kernels", "xla"])
def test_state_carries_across_a_chunk_edge(form):
    """Only the first step writes (u 0 after it) and the state hardly
    decays: every later chunk's output is what the carried state gives.
    A form that reset the state at a chunk's edge would read 0 there."""
    (u, dt, a, b, c), _ = _scan_inputs(bsz=1, t=64, ch=128)
    u = u.at[:, 1:].set(0.0)
    dt = jnp.full_like(dt, 0.01)
    y = _forms(16)[form](u, dt, a, b, c)
    np.testing.assert_allclose(y, _plain_scan(u, dt, a, b, c), rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.min(jnp.max(jnp.abs(y[0, 16:]), axis=-1))) > 1e-6


def test_the_layer_takes_the_kernels_under_interpret(monkeypatch):
    """COS_FLASH_INTERPRET=1 is the CPU suite's way into the kernel
    form: `selective_scan` and the convolution stage before it lower to
    it, say so in `info.ssm` and `info.taps`, and the Mamba
    layer's output and gradients are the XLA form's."""
    cfg = small_cfg(**MIDDLE)
    net = Net(small_net(**MIDDLE, batch=1, recompute=False),
              NetState(phase=Phase.TRAIN))
    params = unflat(ref.init_params(cfg, 2))
    x = jax.random.normal(jax.random.key(1), (64, 1, 64))

    def f(q):
        return net.apply(q, {"h0": x}, train=True,
                         layers=["L0.norm1", "L0.mamba"])[0]["L0.a"]

    conv = "1x64 128 of 256 channels 4 taps float32 bias"
    route.forget("taps")
    want, gw = jax.value_and_grad(lambda q: jnp.sum(f(q) ** 2))(params)
    assert route.plans()["ssm"]["1x64 128 channels 16 states"]["form"] == "xla"
    assert route.plans()["taps"][conv] == {"form": "xla", "sites": ["L0.mamba"]}
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    got, gg = jax.value_and_grad(lambda q: jnp.sum(f(q) ** 2))(params)
    plan = route.plans()["ssm"]["1x64 128 channels 16 states"]
    assert plan["form"] == "kernel" and plan["chunks_a_row"] == 4
    # and the convolution before it to `cos_taps_fwd` / `cos_taps_bwd`
    assert route.plans()["taps"][conv] == {
        "form": "kernel", "time_tile": 64, "channel_tile": 128,
        "sites": ["L0.mamba"]}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for k, v in flat(gw).items():
        if k.startswith("L0.mamba"):
            close(flat(gg)[k], v, 1e-4, k)


@pytest.mark.parametrize("recompute", [True, False],
                         ids=["in_blocks", "no_block"])
def test_the_convolution_kernels_change_no_value_in_or_out_of_a_block(
        monkeypatch, recompute):
    """The middle cut's loss and every gradient with the two Mamba
    layers' convolution on its kernels (interpret mode) against a build
    whose convolution keeps the XLA form, the scan's kernels on both
    sides: inside `recompute_block`s, where the stage runs bare, and
    outside, where it carries a checkpoint of its own."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    cfg = small_cfg(**MIDDLE)
    params = unflat(ref.init_params(cfg, 3))
    data = batches(1, seed=3)[0]
    net_param = small_net(**MIDDLE, recompute=recompute)
    key = "2x64 128 of 256 channels 4 taps float32 bias"
    route.forget("taps")
    loss, grads = _loss_and_grads(net_param, params, data)
    assert route.plans()["taps"][key] == {
        "form": "kernel", "time_tile": 64, "channel_tile": 128,
        "sites": ["L0.mamba", "L2.mamba"]}
    monkeypatch.setattr(pk, "taps_plan", lambda *a: None)
    want, want_grads = _loss_and_grads(net_param, params, data)
    assert route.plans()["taps"][key]["form"] == "xla"
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for k in ("L0.mamba/taps", "L2.mamba/conv_bias", "L0.mamba/W_in"):
        assert np.abs(want_grads[k]).max() > 0, k
    for k, v in want_grads.items():
        close(grads[k], v, 1e-4, k)


# ------------------------------------------------------------ planted faults

def _loss_and_grads(net_param, params, data, **more):
    net = Net(net_param, NetState(phase=Phase.TRAIN))
    ids, tgt = data
    (loss, _), g = jax.value_and_grad(
        lambda q: net.loss(q, dict(inputs(ids, tgt), **more), train=True),
        has_aux=True)(params)
    return float(loss), flat(g)


def _faulty(fault):
    """(net text of the program with the fault planted, parameters)."""
    cfg = small_cfg(**MIDDLE)
    p = ref.init_params(cfg, 5)
    text = small_net(**MIDDLE).to_text()
    if fault == "lambda_zero":
        # plain attention: lambda_init 0 and lambda vectors that cancel
        import re
        text = re.sub(r"lambda_init: [0-9.e-]+", "lambda_init: 0.0", text)
        for k in p:
            if "/lambda_" in k:
                p[k] = jnp.zeros_like(p[k])
    elif fault == "no_window":
        assert "window: 8" in text
        text = text.replace("window: 8", "window: 0")
    elif fault == "untied":
        text = text.replace('param {\n    name: "E"\n  }', "")
        assert 'name: "E"' not in text
        p["head.logits/weight"] = 0.02 * jax.random.normal(
            jax.random.key(77), p["embed/weight"].shape)
    return text, p


@pytest.mark.parametrize("fault", ["sound", "lambda_zero", "no_window",
                                   "memory_of_ones", "own_kv", "untied"])
def test_planted_faults_fail_against_the_reference(fault):
    """The comparison that passes for the program as written fails for:
    lambda forced to 0 (plain attention), the window dropped, the
    memory replaced by ones, layer 19 reading its own k / v of a fresh
    W_kv, an untied head."""
    from caffeonspark_tpu.proto import parse_net_prototxt
    cfg = small_cfg(**MIDDLE)
    data = batches(1, seed=4)[0]
    m = ref.dims(cfg)
    p_ref = ref.init_params(cfg, 5)
    want = sum(float(ref.loss_sum(p_ref, jnp.asarray(i), jnp.asarray(t), m))
               for i, t in zip(*data)) / data[0].size
    want_g = jax.grad(lambda q: sum(
        ref.loss_sum(q, jnp.asarray(i), jnp.asarray(t), m)
        for i, t in zip(*data)) / data[0].size)(p_ref)

    text, p = _faulty(fault)
    net_param = parse_net_prototxt(text)
    more = {}
    if fault == "memory_of_ones":
        more["ones"] = jnp.ones((64, 2, 128))
        # the GMU reads ones instead of layer 16's scan output
        for lp in net_param.layer:
            if lp.type == "GatedMemoryUnit":
                lp.bottom[1] = "ones"
        net_param.layer.insert(2, type(net_param.layer[0]).from_text(
            'name: "ones" type: "DummyData" top: "ones" dummy_data_param '
            '{ shape { dim: 64 dim: 2 dim: 128 } data_filler '
            '{ type: "constant" value: 1 } }'))
    if fault == "own_kv":
        # layer 19 makes its own k / v from a fresh W_k / W_v
        for lp in net_param.layer:
            if lp.name == "L5.attn":
                del lp.bottom[1:]
                lp.attention_param.shared_kv = False
        for j, blob in ((1, "W_k"), (2, "W_v")):
            p[f"L5.attn/{blob}"] = 0.02 * jax.random.normal(
                jax.random.key(70 + j), p["L3.attn/" + blob].shape)
    loss, g = _loss_and_grads(net_param, unflat(p), data, **more)
    worst = max(
        np.linalg.norm(g[k] - np.asarray(v)) / max(np.linalg.norm(v), 1e-12)
        for k, v in want_g.items())
    ok = abs(loss - want) <= 2e-5 * abs(want) and worst <= 3e-4
    assert ok == (fault == "sound"), (fault, loss, want, worst)


# --------------------------------------------- the cut tied to the model

def test_middle_cut_is_the_whole_models_layers_14_to_19():
    """Layers 14-19 built with `first_layer=14` give, from layer 13's
    output of the whole 32-layer small model, that model's layer-19
    output; logits over a vocabulary slice equal the whole model's
    logits at those ids."""
    whole = dict(total_layers=32, first_layer=0, layers=32, seq=32, batch=1)
    cfg_w = small_cfg(**whole)
    m_w = ref.dims(cfg_w)
    p_w = ref.init_params(cfg_w, 11)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 48, 32))
    # the whole model by the reference, stopping at layers 13 and 19
    x, shared, after = p_w["embed/weight"][ids], {}, {}
    for i in range(32):
        x, hands = ref.mixer(p_w, i, x, shared, m_w)
        shared.update(hands)
        x = ref.feed_forward(p_w, i, x, m_w)
        after[i] = x
    # the cut by the program, holding the whole model's layers 14-19
    cut = dict(MIDDLE, seq=32, batch=1, vocab=48)
    net = Net(small_net(**cut, recompute=False), NetState(phase=Phase.TRAIN))
    p_cut = {}
    for k, v in p_w.items():
        ln, bn = k.split("/")
        head, _, rest = ln.partition(".")
        if head[0] == "L" and 14 <= int(head[1:]) < 20:
            p_cut.setdefault(f"L{int(head[1:]) - 14}.{rest}", {})[bn] = v
    p_cut["embed"] = {"weight": p_w["embed/weight"][:48]}   # the slice
    p_cut["head.norm"] = {"scale": p_w["head.norm/scale"],
                          "bias": p_w["head.norm/bias"]}
    assert set(p_cut) == set(net.param_layout)
    names = [lp.name for lp in net.compute_layers
             if lp.name.startswith("L")]
    blobs, _ = net.apply(p_cut, {"h0": after[13][:, None]}, train=True,
                         layers=names)
    np.testing.assert_allclose(np.asarray(blobs["L5.out"][:, 0]), after[19],
                               rtol=2e-5, atol=2e-6)
    # the whole model's logits at the slice's ids
    logits_w = ref.logits_of(p_w, after[31], m_w)
    blobs, _ = net.apply(p_cut, {"L5.out": after[31][:, None]}, train=True,
                         layers=["head.norm", "head.logits"])
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]),
                               logits_w[:, :48], rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------ the tied blob

def test_tied_blob_one_entry_summed_gradient_and_snapshot(tmp_path):
    from caffeonspark_tpu import checkpoint
    net = Net(small_net(**MIDDLE), NetState(phase=Phase.TRAIN))
    assert "head.logits" not in net.param_layout
    assert net.shared_params == {("head.logits", "weight"):
                                 ("embed", "weight")}
    untied = Net(small_net(**MIDDLE, tie=False), NetState(phase=Phase.TRAIN))
    assert net.num_params() == untied.num_params() - 96 * 64
    cfg = small_cfg(**MIDDLE)
    p = unflat(ref.init_params(cfg, 5))
    ids, tgt = batches(1)[0]
    g = jax.grad(lambda q: net.loss(q, inputs(ids, tgt), train=True)[0])(p)
    # untied with the head a copy of the embedding: the tied gradient is
    # the embedding's plus the head's
    pu = dict(p, **{"head.logits": {"weight": p["embed"]["weight"]}})
    gu = jax.grad(lambda q: untied.loss(q, inputs(ids, tgt),
                                        train=True)[0])(pu)
    close(g["embed"]["weight"],
          gu["embed"]["weight"] + gu["head.logits"]["weight"], 1e-5)
    assert np.linalg.norm(gu["head.logits"]["weight"]) > 0
    assert np.linalg.norm(gu["embed"]["weight"]) > 0

    # a snapshot holds it once and restores it
    sp = SolverParameter.from_text(
        'type: "Adam" lr_policy: "fixed" random_seed: 5 '
        + " ".join(f"{k}: {v}" for k, v in SOLVER.items()))
    solver = Solver(sp, small_net(**MIDDLE))
    params, st = solver.init()
    params, st, _ = jax.jit(solver.train_step_fn())(
        params, st, inputs(ids, tgt), jax.random.key(0))
    prefix = os.path.join(str(tmp_path), "snap")
    model_path, state_path = checkpoint.snapshot(
        solver.train_net, params, st, prefix, solver_type="Adam")
    from caffeonspark_tpu.proto import NetParameter
    with open(model_path, "rb") as f:
        saved = NetParameter.from_binary(f.read())
    by_name = {lp.name: lp for lp in saved.layer}
    assert len(by_name["embed"].blobs) == 1
    assert len(by_name["head.logits"].blobs) == 0
    fresh, fresh_st = solver.init()
    back, back_st = checkpoint.restore(solver.train_net, fresh, fresh_st,
                                       state_path)
    assert "head.logits" not in back and "head.logits" not in back_st.history
    for tree, want in ((back, params), (back_st.history, st.history),
                       (back_st.history2, st.history2)):
        np.testing.assert_array_equal(tree["embed"]["weight"],
                                      want["embed"]["weight"])
        assert np.linalg.norm(tree["embed"]["weight"]) > 0


@pytest.mark.parametrize("blocks", [1, 2])
def test_recompute_blocks_on_and_off_give_equal_gradients(blocks):
    """With k / v / m exported from their blocks and read by blocks
    further on."""
    cfg = small_cfg(**MIDDLE)
    p = unflat(ref.init_params(cfg, 5))
    data = batches(1)[0]
    on = small_net(**MIDDLE, blocks_a_layer=blocks)
    net = Net(on, NetState(phase=Phase.TRAIN))
    assert len(net.recompute_blocks) == 6 * blocks
    shared = net.shared_blobs()
    assert set(shared) == {"L2.memory", "L3.k", "L3.v"}
    assert shared["L2.memory"]["to"] == ["L4.gmu"]
    assert shared["L3.k"]["to"] == ["L5.attn"]
    l_on, g_on = _loss_and_grads(on, p, data)
    l_off, g_off = _loss_and_grads(small_net(**MIDDLE, recompute=False), p,
                                   data)
    assert l_on == pytest.approx(l_off, rel=1e-6)
    for k, v in g_off.items():
        close(g_on[k], v, 1e-5, k)


# ------------------------------------------------------------- under a mesh

def test_refused_by_name_under_a_time_sharding_mesh():
    from caffeonspark_tpu.parallel.sp import refuse_time_sharding
    with pytest.raises(ValueError, match=r"Mamba.*'L0.mamba'"):
        refuse_time_sharding(Net(small_net(**MIDDLE)))
    # the differential layers alone (no scan in the net) are named too
    text = small_net(**MIDDLE).to_text()
    from caffeonspark_tpu.proto import parse_net_prototxt
    net = parse_net_prototxt(text)
    keep = [lp for lp in net.layer
            if lp.name.split(".")[0] in ("data", "embed", "L1")]
    del net.layer[:]
    net.layer.extend(keep)
    net.layer[2].bottom[0] = "h0"           # L1.norm1 reads the embedding
    net.layer[4].bottom[0] = "h0"           # L1.res1
    with pytest.raises(ValueError, match=r"differential.*'L1.attn'"):
        refuse_time_sharding(Net(net))


def test_tp_specs_replicate_the_new_layers_and_a_tied_blob_has_one():
    from jax.sharding import PartitionSpec as P
    from caffeonspark_tpu.parallel.mesh import tp_param_specs
    net = Net(small_net(**MIDDLE))
    specs = tp_param_specs(net, min_features=32)
    for lname in ("L0.mamba", "L4.gmu", "L1.attn", "L5.attn", "L0.norm1"):
        assert set(specs[lname].values()) == {P()}, lname
    assert "head.logits" not in specs       # the embedding's blob, once
    assert specs["embed"] == {"weight": P(None, "tp")}
    assert specs["L0.gate"]["weight"] == P("tp", None)


def test_train_job_reports_info_ssm_and_info_shared(monkeypatch):
    """What the first step's scans were lowered to and which blobs cross
    blocks ride in the metrics the -train job prints at shutdown, as
    `info.ssm` and `info.shared`, beside `info.gdn` / `info.recompute`
    and through the same route."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.processor import CaffeProcessor

    class Job:
        metrics = PipelineMetrics()

    # tracing a TRAIN pass of a net with blocks notes what crosses them
    route.forget("shared")
    net = Net(small_net(**MIDDLE), NetState(phase=Phase.TRAIN))
    ids, tgt = batches(1)[0]
    jax.eval_shape(lambda q: net.loss(q, inputs(ids, tgt), train=True)[0],
                   unflat(ref.init_params(small_cfg(**MIDDLE), 5)))
    route.forget("ssm")
    x, _ = _scan_inputs(bsz=1, t=72, ch=128)
    L.selective_scan(*x, 16)
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    jax.eval_shape(lambda *a: L.selective_scan(*a, 64), *(
        jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (1, 8192, 5120), (1, 8192, 5120), (5120, 16), (1, 8192, 16),
            (1, 8192, 16))))
    CaffeProcessor._note_lowering_plans(Job)
    info = Job.metrics.summary()["info"]
    assert info["ssm"] == {
        "1x72 128 channels 16 states": {
            "form": "xla", "chunk": 16, "chunks_a_row": 5,
            "edge_bytes": 5 * 128 * 16 * 4},
        "1x8192 5120 channels 16 states": {
            "form": "kernel", "chunk": 64, "chunks_a_row": 128,
            "edge_bytes": 128 * 5120 * 16 * 4, "channels_a_program": 512,
            "vmem_bytes": 8749056}}
    assert info["shared"] == {
        "L2.memory": {"from": "L2.mamba", "to": ["L4.gmu"],
                      "bytes": 64 * 2 * 128 * 4},
        "L3.k": {"from": "L3.attn", "to": ["L5.attn"],
                 "bytes": 2 * 2 * 64 * 16 * 4},
        "L3.v": {"from": "L3.attn", "to": ["L5.attn"],
                 "bytes": 2 * 2 * 64 * 32 * 4}}
