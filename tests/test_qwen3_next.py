"""Qwen3-Next-80B-A3B-Instruct through the system against the plain
reference (`perfbench/reference/qwen3_next_80b_a3b.py`, float32,
"highest"), at a small size with the model's structure: one period of
the published schedule (Gated DeltaNet, Gated DeltaNet, Gated DeltaNet,
gated full attention), 2 key heads serving 4 value heads in the linear
layers, 4 query heads over 2 key/value heads with q/k norms, rotary
positions on the first quarter of a head and an output gate, 16
softmax-routed experts, top-3, one sigmoid-gated shared expert.

Tolerances as `tests/test_kanana2.py` gives them: both sides are float32
with exact products, what differs is the order of sums (and, for the
recurrence, chunks against single tokens)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import route
from caffeonspark_tpu.proto import LayerParameter, SolverParameter
from caffeonspark_tpu.solver import Solver
from perfbench.reference import qwen3_next_80b_a3b as ref

SMALL = dict(vocab=64, hidden=32, heads=4, kv_heads=2, head_dim=16,
             rotary_dim=4, linear_k_heads=2, linear_v_heads=4,
             linear_k_dim=8, linear_v_dim=8, conv_taps=4, chunk=8,
             expert_width=12, shared_width=12, experts=16, top_k=3,
             layers=4, seq=20, batch=2, init_std=0.1)
SOLVER = dict(base_lr=1e-3, momentum=0.9, momentum2=0.95, delta=1e-8,
              clip_gradients=1.0)


def small_cfg(**over):
    z = dict(SMALL, **over)
    return {"hidden_size": z["hidden"], "num_attention_heads": z["heads"],
            "num_key_value_heads": z["kv_heads"], "head_dim": z["head_dim"],
            "partial_rotary_factor": z["rotary_dim"] / z["head_dim"],
            "rope_theta": 1e7, "rms_norm_eps": 1e-6,
            "full_attention_interval": 4,
            "linear_num_key_heads": z["linear_k_heads"],
            "linear_num_value_heads": z["linear_v_heads"],
            "linear_key_head_dim": z["linear_k_dim"],
            "linear_value_head_dim": z["linear_v_dim"],
            "linear_conv_kernel_dim": z["conv_taps"],
            "moe_intermediate_size": z["expert_width"],
            "shared_expert_intermediate_size": z["shared_width"],
            "num_experts": z["experts"],
            "num_experts_per_tok": z["top_k"],
            "experts_held": z.get("experts_held", z["experts"]),
            "first_expert": z.get("first_expert", 0),
            "vocab_size": z["vocab"], "num_hidden_layers": z["layers"],
            "first_layer": z.get("first_layer", 0),
            "assumed": {"init_std": z["init_std"], "A_log_uniform": [1e-3, 16.0],
                        "dt_bias": 1.0},
            "solver": dict(SOLVER)}


def small_net(**over):
    z = dict(SMALL, **over)
    z.setdefault("experts_held", z["experts"])
    return zoo.qwen3_next(**z)


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
    # both operators, the routed and the gated shared expert are there
    assert {"L0.gdn/A_log", "L2.gdn/taps", "L3.attn/k_norm",
            "L0.moe/router", "L3.moe/S_sgate"} <= set(p0)
    a_log = p0["L1.gdn/A_log"]
    assert np.all(np.isfinite(a_log)) and np.all(a_log < np.log(16.0))
    assert len(set(a_log.tolist())) == a_log.size

    # logits of the first sequence
    net = solver.train_net
    ids, tgt = data[0]
    ins = {"input_ids": jnp.asarray(ids.T, jnp.float32),
           "target_ids": jnp.asarray(tgt.T, jnp.float32)}
    blobs, _ = net.apply(params, ins, train=True, rng=jax.random.key(0))
    want, counts = ref.forward(ref.init_params(cfg, 5), jnp.asarray(ids[0]),
                               ref.dims(cfg))
    np.testing.assert_allclose(np.asarray(blobs["logits"][:, 0]), want,
                               rtol=2e-5, atol=1e-5)     # logits of ~1
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
        # Adam divides by sqrt(v): an element whose gradient is of the
        # size of its rounding moves by lr all the same, where the noise
        # points.  20 tokens hardly feel a head's decay, so the leaves
        # that only set it (W_ba, A_log, dt_bias) hold such elements
        loose = k.rsplit("/", 1)[1] in ("W_ba", "A_log", "dt_bias")
        assert np.linalg.norm(last[k] - v) <= (
            5e-2 if loose else 5e-4) * moved + 1e-9, k


# ----------------------------------------------------- the gated delta rule

def _rule_inputs(t, b=2, hk=2, r=2, dk=8, dv=4, seed=0, alike=False):
    """q, k L2-normalised as the layer hands them over; two value heads
    a key head, one decaying slowly and one fast; `alike` gives keys
    that resemble each other (a common direction)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, hk, t, dk))
    k = jax.random.normal(ks[1], (b, hk, t, dk))
    if alike:
        k = k + 3.0
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, hk, r, t, dv))
    rate = jnp.asarray([0.02, 3.0])[:r]
    g = -jax.random.uniform(ks[3], (b, hk, r, t)) * rate[None, None, :, None]
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (b, hk, r, t)))
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence on the program's layout, one batch
    column at a time."""
    b, hk, t, dk = q.shape
    r, dv = v.shape[2], v.shape[-1]
    outs = []
    for bi in range(b):
        # (T, H, .), value head h = key head h // r
        qq, kk = (jnp.repeat(jnp.transpose(a[bi], (1, 0, 2)), r, axis=1)
                  for a in (q, k))
        vv = jnp.transpose(v[bi].reshape(hk * r, t, dv), (1, 0, 2))
        gg, bb = (jnp.transpose(a[bi].reshape(hk * r, t), (1, 0))
                  for a in (g, beta))
        o = ref.delta_rule(qq, kk, vv, gg, bb)               # (T, H, dv)
        outs.append(jnp.transpose(o, (1, 0, 2)).reshape(hk, r, t, dv))
    return jnp.stack(outs)


@pytest.mark.parametrize("t,chunk", [(64, 16), (50, 16), (37, 64), (130, 64),
                                     (9, 1)])
@pytest.mark.parametrize("alike", [False, True])
def test_chunked_rule_equals_the_token_by_token_recurrence(t, chunk, alike):
    """Values and every gradient, at lengths that are and are not whole
    chunks, one chunk longer than the sequence, and chunks of one token;
    with keys that resemble each other the triangular system is far
    from the identity."""
    args = _rule_inputs(t, alike=alike)
    w = jax.random.normal(jax.random.key(9), args[2].shape)
    got = jax.value_and_grad(
        lambda *a: jnp.sum(L.gated_delta_rule(*a, chunk) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.value_and_grad(
        lambda *a: jnp.sum(_token_by_token(*a) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    np.testing.assert_allclose(
        L.gated_delta_rule(*args, chunk), _token_by_token(*args),
        rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for name, a, b in zip("q k v g beta".split(), got[1], want[1]):
        assert a.shape == b.shape
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)


def test_chunked_rule_is_causal_and_refuses_a_chunk_that_is_no_power_of_two():
    """Changing token 21 moves no output before 21 (a chunk edge lies
    at 16, another at 32), in the values and through every input."""
    q, k, v, g, beta = _rule_inputs(40, seed=2)
    base = np.asarray(L.gated_delta_rule(q, k, v, g, beta, 16))
    moved = (q.at[:, :, 21].multiply(1.5), k.at[:, :, 21].multiply(-1.0),
             v.at[:, :, :, 21].add(1.0), g.at[:, :, :, 21].add(-0.7),
             beta.at[:, :, :, 21].multiply(0.5))
    for i, name in enumerate("q k v g beta".split()):
        args = [q, k, v, g, beta]
        args[i] = moved[i]
        got = np.asarray(L.gated_delta_rule(*args, 16))
        np.testing.assert_array_equal(got[..., :21, :], base[..., :21, :],
                                      err_msg=name)
        assert np.abs(got[..., 21, :] - base[..., 21, :]).max() > 0, name
        if name != "q":         # a query reads, it writes nothing
            assert np.abs(got[..., 22:, :] - base[..., 22:, :]).max() > 0
    with pytest.raises(ValueError, match="power of two"):
        L.gated_delta_rule(q, k, v, g, beta, 24)


def _float64_recurrence(args, w):
    """The recurrence of `gated_delta_rule`'s docstring, a token at a
    time in float64 on the program's layout -> o and the gradients of
    sum(o w) with respect to q, k, v, g, beta."""
    with jax.enable_x64(True):
        q, k, v, g, beta, w = (jnp.asarray(np.asarray(a, np.float64))
                               for a in args + (w,))

        def rule(q, k, v, g, beta):
            def token(s, x):        # s (B, Hk, R, dk, dv)
                qt, kt, vt, gt, bt = x
                s = s * jnp.exp(gt)[..., None, None]
                write = bt[..., None] * (
                    vt - jnp.einsum("bhd,bhrde->bhre", kt, s))
                s = s + jnp.einsum("bhd,bhre->bhrde", kt, write)
                return s, jnp.einsum("bhd,bhrde->bhre", qt, s)

            s0 = jnp.zeros(v.shape[:3] + (q.shape[-1], v.shape[-1]),
                           jnp.float64)
            _, o = jax.lax.scan(token, s0, (
                jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                jnp.moveaxis(v, 3, 0), jnp.moveaxis(g, 3, 0),
                jnp.moveaxis(beta, 3, 0)))
            return jnp.moveaxis(o, 0, 3)

        grads = jax.grad(lambda *a: jnp.sum(rule(*a) * w),
                         argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
        return (np.asarray(rule(q, k, v, g, beta)),
                [np.asarray(a) for a in grads])


@pytest.fixture
def kernel_route(monkeypatch):
    """The Mosaic kernels in interpret mode, two chunks a grid step and
    four between two kept states, so that a few hundred tokens cross
    grid steps and groups."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    monkeypatch.setattr(pk, "GDN_STEP_CHUNKS", 2)
    monkeypatch.setattr(pk, "GDN_GROUP_CHUNKS", 4)
    route.forget("gdn")


# (T, R, chunk): 5 chunks = two groups, the second's last grid step and
# a half padding; one chunk, not full; one value head a key head at a
# chunk of 128 (one group); two groups and no padding
@pytest.mark.parametrize("t,r,chunk", [(300, 2, 64), (40, 2, 64),
                                       (200, 1, 128), (512, 2, 64)])
@pytest.mark.parametrize("alike", [False, True])
def test_kernel_rule_equals_the_recurrence_and_the_xla_form(
        kernel_route, t, r, chunk, alike):
    """The kernel route (`pallas_kernels.gated_delta_rule_kernels`, in
    interpret mode) against the token-by-token recurrence in float64
    and against the XLA form: values and all five gradients, at the
    family's head sizes (128 / 128) and one key head."""
    args = _rule_inputs(t, b=1, hk=1, r=r, dk=128, dv=128, alike=alike,
                        seed=t)
    w = jax.random.normal(jax.random.key(9), args[2].shape)

    def both(rule):
        return (rule(*args, chunk), jax.grad(
            lambda *a: jnp.sum(rule(*a, chunk) * w),
            argnums=(0, 1, 2, 3, 4))(*args))

    got, got_grads = both(L.gated_delta_rule)
    assert route.plans()["gdn"][f"1x{t} 1/{r} heads 128/128"]["rule"] == "kernel"
    xla, xla_grads = both(L.gated_delta_rule_xla)
    want, want_grads = _float64_recurrence(args, w)
    top = np.abs(want).max()
    # float32 against float64: what the XLA form leaves, and no more
    assert np.abs(got - want).max() <= max(
        2.0 * np.abs(xla - want).max(), 2e-6 * top)
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-6 * top)
    for name, a, b, c in zip("q k v g beta".split(), got_grads,
                             xla_grads, want_grads):
        assert a.shape == c.shape, name
        scale = np.abs(c).max()
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)


def test_kernel_rule_is_causal(kernel_route):
    """Changing token 150 (chunk 2 of 5, the second grid step) moves no
    output before it, through every input."""
    q, k, v, g, beta = _rule_inputs(300, b=1, hk=1, dk=128, dv=128, seed=4)
    base = np.asarray(L.gated_delta_rule(q, k, v, g, beta, 64))
    moved = (q.at[:, :, 150].multiply(1.5), k.at[:, :, 150].multiply(-1.0),
             v.at[:, :, :, 150].add(1.0), g.at[:, :, :, 150].add(-0.7),
             beta.at[:, :, :, 150].multiply(0.5))
    for i, name in enumerate("q k v g beta".split()):
        args = [q, k, v, g, beta]
        args[i] = moved[i]
        got = np.asarray(L.gated_delta_rule(*args, 64))
        np.testing.assert_array_equal(got[..., :150, :], base[..., :150, :],
                                      err_msg=name)
        assert np.abs(got[..., 150, :] - base[..., 150, :]).max() > 0, name
        if name != "q":
            assert np.abs(got[..., 151:, :] - base[..., 151:, :]).max() > 0
    assert route.plans()["gdn"]["1x300 1/2 heads 128/128"]["rule"] == "kernel"


@pytest.mark.parametrize("why", ["dk", "r_chunk", "bfloat16", "disabled",
                                 "mesh"])
def test_rule_falls_back_to_the_xla_form(monkeypatch, why):
    """What sends a rule to the XLA form although kernels could run: a
    head size or R x chunk that does not fill 128-lane tiles, operands
    that are not float32, COS_DISABLE_PALLAS on a TPU backend, a mesh
    of several devices (a bare Mosaic call cannot be partitioned).
    Each time the values are the XLA form's and `info.gdn` says so."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    dims = dict(b=2, hk=1, dk=128, dv=128)
    chunk, ctx = 64, None
    if why == "dk":
        dims["dk"] = 64
    elif why == "r_chunk":
        chunk = 32
    elif why == "disabled":
        # no interpret mode: the backend says TPU, the switch says no
        monkeypatch.delenv("COS_FLASH_INTERPRET")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert route.on_tpu()
        monkeypatch.setenv("COS_DISABLE_PALLAS", "1")
    elif why == "mesh":
        from caffeonspark_tpu.parallel.mesh import build_mesh
        ctx = route.flash_mesh(build_mesh(dp=2, devices=jax.devices()[:2]))
    args = _rule_inputs(70, **dims)
    if why == "bfloat16":
        args = tuple(a.astype(jnp.bfloat16) for a in args)
    route.forget("gdn")
    if ctx is None:
        got = L.gated_delta_rule(*args, chunk)
    else:
        with ctx:
            got = L.gated_delta_rule(*args, chunk)
    (plan,) = route.plans()["gdn"].values()
    assert plan["rule"] == "xla" and plan["chunks_a_call"] == 70 // chunk + 1
    np.testing.assert_array_equal(
        got, L.gated_delta_rule_xla(*args, chunk))


def test_unit_lower_inverse_inverts():
    c = 64
    m = jnp.tril(jax.random.normal(jax.random.key(1), (3, c, c)), -1) \
        + jnp.eye(c)
    inv = L._unit_lower_inverse(m, jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(inv, np.linalg.inv(np.asarray(m, np.float64)),
                               rtol=1e-3, atol=1e-3 * float(
                                   np.abs(np.linalg.inv(np.asarray(
                                       m, np.float64))).max()))
    np.testing.assert_array_equal(np.triu(np.asarray(inv), 1), 0.0)


def _gdn_layer(x, blobs, z=SMALL):
    lp = LayerParameter.from_text(
        'name: "g" type: "GatedDeltaNet" bottom: "x" top: "y" '
        f'gated_delta_net_param {{ num_k_heads: {z["linear_k_heads"]} '
        f'num_v_heads: {z["linear_v_heads"]} '
        f'head_k_dim: {z["linear_k_dim"]} head_v_dim: {z["linear_v_dim"]} '
        f'conv_taps: {z["conv_taps"]} chunk: {z["chunk"]} }}')
    op = L.get_op("GatedDeltaNet")
    assert [s[1] for s in op.param_specs(lp, [x.shape])] == [
        a.shape for a in blobs]
    return op.apply(L.Ctx(train=True), lp, blobs, [x])[0]


def test_gated_delta_net_layer_equals_the_reference_and_is_causal():
    cfg = small_cfg()
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 7)
    names = ("W_qkvz", "W_ba", "taps", "A_log", "dt_bias", "norm", "W_out")
    # larger taps than the filler's, so that the convolution matters
    blobs = [p[f"L0.gdn/{n}"] * (20.0 if n == "taps" else 1.0)
             for n in names]
    pp = dict(zip((f"g/{n}" for n in names), blobs))
    t, b = 21, 2
    x = jax.random.normal(jax.random.key(3), (t, b, m["d"]))
    got = np.asarray(_gdn_layer(x, blobs))
    for bi in range(b):
        np.testing.assert_allclose(
            got[:, bi], ref.gated_delta_net(pp, "g", x[:, bi], m),
            rtol=2e-5, atol=2e-6)
    assert np.abs(got).max() > 1e-4
    # causal, and no state crosses a batch column
    got2 = np.asarray(_gdn_layer(x.at[13, 0].add(1.0), blobs))
    np.testing.assert_array_equal(got2[:13], got[:13])
    np.testing.assert_array_equal(got2[:, 1], got[:, 1])
    assert all(np.abs(got2[ti, 0] - got[ti, 0]).max() > 0
               for ti in (13, 14, 16, 20))
    # the counter says what was lowered
    plan = route.plans()["gdn"][f"{b}x{t} 2/4 heads 8/8"]
    assert plan == {"rule": "xla", "chunk": 8, "chunks_a_row": 3,
                    "chunks_a_group": 3, "chunks_a_call": 3, "heads": 4,
                    "state_bytes": b * 4 * 8 * 8 * 4}


def test_train_job_reports_the_lowered_scan_as_info_gdn(monkeypatch):
    """What the first step's Gated DeltaNet operators were lowered to
    rides in the metrics the -train job prints at shutdown, as
    `info.gdn`, beside `info.flash` and through the same route."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.processor import CaffeProcessor

    class Job:
        metrics = PipelineMetrics()

    route.forget("gdn")
    L.gated_delta_rule(*_rule_inputs(300, b=1), 64)
    CaffeProcessor._note_lowering_plans(Job)
    assert Job.metrics.summary()["info"]["gdn"] == {
        "1x300 2/4 heads 8/4": {"rule": "xla", "chunk": 64,
                                "chunks_a_row": 5, "chunks_a_group": 5,
                                "chunks_a_call": 5, "heads": 4,
                                "state_bytes": 4 * 8 * 4 * 4}}
    L.gated_delta_rule(*_rule_inputs(64 * 40, b=1), 64)
    assert route.plans()["gdn"]["1x2560 2/4 heads 8/4"]["chunks_a_group"] == 32
    # the form that was lowered is part of the line: the kernels where
    # the shape fills the tiles (here in interpret mode), with the
    # chunks a call walks with the states in VMEM: a row of 5 chunks in
    # two grid steps of 4 (one group), one of 40 in 12 (three groups of
    # 16 chunks), one of 128 in 32
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    for t, row, group, call in ((300, 5, 8, 8), (64 * 40, 40, 16, 48),
                                (8192, 128, 16, 128)):
        jax.eval_shape(
            lambda *a: L.gated_delta_rule(*a, 64),
            *_rule_inputs(t, b=1, hk=1, dk=128, dv=128))
        assert route.plans()["gdn"][f"1x{t} 1/2 heads 128/128"] == {
            "rule": "kernel", "chunk": 64, "chunks_a_row": row,
            "chunks_a_group": group, "chunks_a_call": call,
            "heads": 2, "state_bytes": 2 * 128 * 128 * 4}


# ------------------------------------------------------ the gated attention

def _gqa_layer(x, blobs, h, hkv, hd, **flags):
    opts = " ".join(f"{k}: {str(v).lower()}" for k, v in flags.items())
    lp = LayerParameter.from_text(
        'name: "a" type: "GroupedQueryAttention" bottom: "x" top: "y" '
        f'attention_param {{ num_heads: {h} num_kv_heads: {hkv} '
        f'head_dim: {hd} causal: true rope_theta: 1e7 rms_norm_eps: 1e-6 '
        f'{opts} }}')
    op = L.get_op("GroupedQueryAttention")
    assert [s[1] for s in op.param_specs(lp, [x.shape])] == [
        a.shape for a in blobs]
    return op.apply(L.Ctx(train=True), lp, blobs, [x])[0]


def _attention_blobs(d, h, hkv, hd, gate=True, seed=3):
    ks = jax.random.split(jax.random.key(seed), 6)
    return [jax.random.normal(ks[0], (h * (2 if gate else 1) * hd, d)) * 0.3,
            jax.random.normal(ks[1], (hkv * hd, d)) * 0.3,
            jax.random.normal(ks[2], (hkv * hd, d)) * 0.3,
            jax.random.normal(ks[3], (d, h * hd)) * 0.3,
            1.0 + 0.1 * jax.random.normal(ks[4], (hd,)),
            1.0 + 0.1 * jax.random.normal(ks[5], (hd,))]


def test_gated_attention_equals_the_reference():
    t, b, d, h, hkv, hd, rd = 12, 2, 16, 4, 2, 8, 2
    x = jax.random.normal(jax.random.key(0), (t, b, d))
    blobs = _attention_blobs(d, h, hkv, hd)
    got = _gqa_layer(x, blobs, h, hkv, hd, qk_norm=True, rotary=True,
                     rotary_dim=rd, output_gate=True)
    m = {"h": h, "hkv": hkv, "hd": hd, "rd": rd, "eps": 1e-6, "theta": 1e7}
    p = dict(zip(("a/W_q", "a/W_k", "a/W_v", "a/W_o", "a/q_norm",
                  "a/k_norm"), blobs))
    for bi in range(b):
        np.testing.assert_allclose(got[:, bi],
                                   ref.attention(p, "a", x[:, bi], m),
                                   rtol=2e-5, atol=2e-6)


def test_output_gate_multiplies_each_head_by_its_own_sigmoid():
    """With the gate's rows of W_q zeroed every gate is sigmoid(0) = 1/2
    and the layer is half the ungated one over the query rows alone;
    a large gate row on one head opens that head and no other."""
    t, b, d, h, hkv, hd = 10, 1, 32, 4, 2, 8
    x = jax.random.normal(jax.random.key(1), (t, b, d))
    blobs = _attention_blobs(d, h, hkv, hd)
    w_q = blobs[0].reshape(h, 2, hd, d)
    plain = [w_q[:, 0].reshape(h * hd, d)] + blobs[1:]
    flags = dict(qk_norm=True, rotary=True, rotary_dim=4)
    ungated = _gqa_layer(x, plain, h, hkv, hd, **flags)
    half = _gqa_layer(x, [w_q.at[:, 1].set(0.0).reshape(2 * h * hd, d)]
                      + blobs[1:], h, hkv, hd, output_gate=True, **flags)
    np.testing.assert_allclose(half, 0.5 * ungated, rtol=1e-5, atol=1e-7)
    # the heads before W_o (an identity of the right shape)
    eye = jnp.eye(h * hd)
    heads = lambda wq: np.asarray(_gqa_layer(                 # noqa: E731
        x, [wq.reshape(2 * h * hd, d)] + blobs[1:3] + [eye] + blobs[4:],
        h, hkv, hd, output_gate=True, **flags)).reshape(t, b, h, hd)
    base = heads(w_q.at[:, 1].set(0.0))
    # a gate row that reads +30 at every token: x . w = 30
    big = 30.0 * x[:, 0] / jnp.sum(x[:, 0] ** 2, axis=-1, keepdims=True)
    one_token = heads(w_q.at[:, 1].set(0.0).at[2, 1].set(
        jnp.broadcast_to(big[5], (hd, d))))
    np.testing.assert_array_equal(one_token[:, :, [0, 1, 3]],
                                  base[:, :, [0, 1, 3]])
    np.testing.assert_allclose(one_token[5, :, 2], 2.0 * base[5, :, 2],
                               rtol=1e-5)


def test_rotary_turns_touch_the_first_rotary_dim_dims_only():
    """q k^T of rotated vectors depends on the positions through the
    first rotary_dim dims alone: with W_q and W_k reading nothing into
    those dims the layer forgets the order of its keys' positions, with
    the whole head rotated it does not; and `rope_adjacent` on a slice
    leaves the rest bit for bit."""
    x = jax.random.normal(jax.random.key(2), (9, 3, 16))
    turned = jnp.concatenate(
        [L.rope_adjacent(x[..., :4], 1e7), x[..., 4:]], axis=-1)
    np.testing.assert_array_equal(turned[..., 4:], x[..., 4:])
    assert np.abs(np.asarray(turned[1:, :, :4] - x[1:, :, :4])).max() > 1e-3
    np.testing.assert_array_equal(turned[0], x[0])          # angle 0
    np.testing.assert_allclose(turned, ref.partial_rope(x, 1e7, 4),
                               rtol=1e-6, atol=1e-7)
    # through the layer: scores with the turned dims zeroed are those
    # of a layer without rotary positions
    t, b, d, h, hkv, hd, rd = 12, 1, 16, 2, 1, 8, 4
    xx = jax.random.normal(jax.random.key(4), (t, b, d))
    blobs = _attention_blobs(d, h, hkv, hd, gate=False)
    blobs[4] = blobs[4].at[:rd].set(0.0)     # q's norm scale: dims < rd -> 0
    on = _gqa_layer(xx, blobs, h, hkv, hd, qk_norm=True, rotary=True,
                    rotary_dim=rd)
    off = _gqa_layer(xx, blobs, h, hkv, hd, qk_norm=True)
    whole = _gqa_layer(xx, blobs, h, hkv, hd, qk_norm=True, rotary=True)
    np.testing.assert_allclose(on, off, rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(whole - off)).max() > 1e-3
    with pytest.raises(ValueError, match="rotary"):
        _gqa_layer(xx, blobs, h, hkv, hd, rotary=True, rotary_dim=10)


# ----------------------------------------------------------- the expert layer

def _moe_layer(cfg, held, first, x, p, pre="L0.moe", shared_gate=True):
    """The program's expert layer on (N, d) rows with the given share of
    the reference's weights."""
    lp = LayerParameter.from_text(f'''
      name: "moe" type: "MixtureOfExperts" bottom: "x" top: "y" top: "stats"
      top: "counts"
      moe_param {{ num_experts: {cfg["num_experts"]}
        hidden_dim: {cfg["moe_intermediate_size"]}
        top_k: {cfg["num_experts_per_tok"]} dispatch: "dropless"
        scoring: "softmax" gated: true
        shared_hidden_dim: {cfg["shared_expert_intermediate_size"]}
        shared_gate: {"true" if shared_gate else "false"}
        experts_held: {held} first_expert: {first} }}''')
    sl = slice(first, first + held)
    blobs = [p[f"{pre}/router"], p[f"{pre}/W_gate"][sl],
             p[f"{pre}/W_up"][sl], p[f"{pre}/W_down"][sl],
             p[f"{pre}/S_gate"], p[f"{pre}/S_up"], p[f"{pre}/S_down"]]
    if shared_gate:
        blobs.append(p[f"{pre}/S_sgate"])
    op = L.get_op("MixtureOfExperts")
    assert [s[1] for s in op.param_specs(lp, [x.shape])] == [
        a.shape for a in blobs]
    return op.apply(L.Ctx(train=True), lp, blobs, [x])


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The cell's split at a small size: 128 experts, top 10, run as each
    of the sixteen shares of 8 experts (first_expert 0, 8, ..., 120).
    The routed parts sum to what the uncut reference gives for the
    routed experts, and the gated shared expert, which every chip
    computes alike, is counted once."""
    cfg = small_cfg(experts=128, top_k=10)
    m = ref.dims(cfg)
    p = ref.init_params(cfg, 3)
    # a router that decides: the filler's 0.02 leaves softmax near flat
    p["L0.moe/router"] = p["L0.moe/router"] * 50.0
    p["L0.moe/S_sgate"] = p["L0.moe/S_sgate"] * 50.0
    x = jax.random.normal(jax.random.key(1), (40, m["d"]))
    whole, whole_counts = ref.moe(p, "L0.moe", x, m)
    assert int(whole_counts.sum()) == 40 * 10
    shared = jax.nn.sigmoid(x @ p["L0.moe/S_sgate"]) * ref.swiglu(
        x, p["L0.moe/S_gate"], p["L0.moe/S_up"], p["L0.moe/S_down"])
    gates = np.asarray(jax.nn.sigmoid(x @ p["L0.moe/S_sgate"]))
    assert gates.min() < 0.3 and gates.max() > 0.7
    parts_ref, parts_prog, rows = 0.0, 0.0, 0
    for first in range(0, 128, 8):
        ms = ref.dims(small_cfg(experts=128, top_k=10, experts_held=8,
                                first_expert=first))
        ps = dict(p, **{f"L0.moe/{b}": p[f"L0.moe/{b}"][first:first + 8]
                        for b in ("W_gate", "W_up", "W_down")})
        part, counts = ref.moe(ps, "L0.moe", x, ms)
        parts_ref = parts_ref + (part - shared)
        y, stats, got_counts = _moe_layer(cfg, 8, first, x, p)
        parts_prog = parts_prog + (y - shared)
        np.testing.assert_array_equal(np.asarray(got_counts), counts)
        np.testing.assert_array_equal(counts,
                                      whole_counts[first:first + 8])
        assert float(stats[2]) == 0.0
        rows += int(counts.sum())
    assert rows == 40 * 10
    np.testing.assert_allclose(parts_ref + shared, whole, rtol=2e-5,
                               atol=2e-7)
    np.testing.assert_allclose(parts_prog + shared, whole, rtol=2e-5,
                               atol=2e-7)
    # the ten chosen weights are renormalised to 1
    _, w = ref.route(p, "L0.moe", x, m)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    # without the gate the shared expert comes in whole
    y_gated = _moe_layer(cfg, 8, 0, x, p)[0]
    y_bare = _moe_layer(cfg, 8, 0, x, p, shared_gate=False)[0]
    np.testing.assert_allclose(
        y_bare - y_gated, (1.0 - gates) * np.asarray(ref.swiglu(
            x, p["L0.moe/S_gate"], p["L0.moe/S_up"], p["L0.moe/S_down"])),
        rtol=2e-4, atol=2e-7)


# one key head of 128 serving two value heads of 128: 512 convolved
# channels of a 768-wide product, whole 128-lane tiles (SMALL's 64 of 96
# are not: its layers keep the XLA form under interpret mode too)
TILED = dict(linear_k_heads=1, linear_v_heads=2, linear_k_dim=128,
             linear_v_dim=128, chunk=64, seq=64, layers=2,
             layer_types=("linear_attention", "full_attention"))


@pytest.mark.parametrize("recompute", [True, False],
                         ids=["in_a_block", "no_block"])
def test_the_layer_takes_the_convolution_kernels_under_interpret(
        monkeypatch, recompute):
    """COS_FLASH_INTERPRET=1 is the CPU suite's way into the kernel form
    of the convolution stage: the Gated DeltaNet layer lowers to
    `cos_taps_fwd` / `cos_taps_bwd`, says so in `info.taps` with the
    layer's name, and the net's loss and every gradient are those of a
    build whose convolution keeps the XLA form (the rule's kernels in
    interpret mode on both sides), inside a `recompute_block` and
    outside one."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    net = Net(small_net(**TILED, recompute=recompute))
    assert bool(net.recompute_blocks) == recompute
    params = net.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (64, 2), 0, SMALL["vocab"])
    ins = {"input_ids": ids.astype(jnp.float32),
           "target_ids": jnp.roll(ids, 1, 0).astype(jnp.float32)}

    def run():
        route.forget("taps")
        fn = jax.value_and_grad(
            lambda p: net.loss(p, ins, train=True, rng=jax.random.key(1)),
            has_aux=True)
        calls = str(jax.make_jaxpr(fn)(params)).count("cos_taps_")
        (loss, _), g = fn(params)
        return float(loss), flat(g), route.plans()["taps"], calls

    loss, grads, plans, calls = run()
    assert plans == {"2x64 512 of 768 channels 4 taps float32": {
        "form": "kernel", "time_tile": 64, "channel_tile": 256,
        "sites": ["L0.gdn"]}}
    assert calls >= 2
    monkeypatch.setattr(pk, "taps_plan", lambda *a: None)
    want, want_grads, plans, calls = run()
    assert plans == {"2x64 512 of 768 channels 4 taps float32": {
        "form": "xla", "sites": ["L0.gdn"]}}
    assert calls == 0
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert np.abs(want_grads["L0.gdn/taps"]).max() > 0
    for k, v in want_grads.items():
        np.testing.assert_allclose(grads[k], v, rtol=2e-4,
                                   atol=2e-6 * np.abs(v).max(), err_msg=k)


def test_under_a_time_sharding_mesh_the_convolution_keeps_the_xla_form(
        monkeypatch):
    """A bare Mosaic call cannot be partitioned: while a mesh of several
    devices is installed (here one that shards time) the layer's
    convolution is the XLA form, interpret mode or not."""
    from caffeonspark_tpu.parallel.mesh import build_mesh
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    route.forget("taps")
    route.forget("gdn")
    z = dict(SMALL, **TILED)
    lp = LayerParameter.from_text(
        'name: "g" type: "GatedDeltaNet" bottom: "x" top: "y" '
        'gated_delta_net_param { num_k_heads: 1 num_v_heads: 2 '
        'head_k_dim: 128 head_v_dim: 128 conv_taps: 4 chunk: 64 }')
    op = L.get_op("GatedDeltaNet")
    x = jax.ShapeDtypeStruct((64, 1, z["hidden"]), jnp.float32)
    blobs = [jax.ShapeDtypeStruct(s[1], jnp.float32)
             for s in op.param_specs(lp, [x.shape])]
    with route.flash_mesh(build_mesh(dp=1, sp=2, devices=jax.devices()[:2])):
        jax.eval_shape(lambda x, *b: op.apply(L.Ctx(train=True), lp,
                                              list(b), [x])[0], x, *blobs)
    assert route.plans()["taps"] == {"1x64 512 of 768 channels 4 taps float32": {
        "form": "xla", "sites": ["g"]}}
    assert [p["rule"] for p in route.plans()["gdn"].values()] == ["xla"]


# ------------------------------------------------------------------ the net

def test_recompute_block_changes_no_value():
    ins = {"input_ids": jnp.ones((20, 2)) * 3,
           "target_ids": jnp.ones((20, 2)) * 5}
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


def test_full_width_net_text_parses_and_counts_625_7_million():
    """The cell's net: published widths, 32 of 512 experts a layer, an
    eighth of the vocabulary, the published layers 0-3."""
    from caffeonspark_tpu.proto import NetParameter
    npm = zoo.qwen3_next()
    assert NetParameter.from_text(npm.to_text()) == npm
    assert NetParameter.from_binary(npm.to_binary()) == npm
    net = Net(npm)
    assert net.num_params() == 625_667_136
    layout = {ln: {bn: s for bn, s, _ in bl}
              for ln, bl in net.param_layout.items()}
    count = lambda ln: sum(int(np.prod(s))                    # noqa: E731
                           for s in layout[ln].values())
    for i, kind in enumerate(["gdn", "gdn", "gdn", "attn"]):
        assert f"L{i}.{kind}" in layout and f"L{i}.moe" in layout
    assert layout["L0.gdn"] == {
        "W_qkvz": (12288, 2048), "W_ba": (64, 2048), "taps": (8192, 4),
        "A_log": (32,), "dt_bias": (32,), "norm": (128,),
        "W_out": (2048, 4096)}
    assert count("L0.gdn") == 33_718_464
    assert layout["L3.attn"] == {
        "W_q": (8192, 2048), "W_k": (512, 2048), "W_v": (512, 2048),
        "W_o": (2048, 4096), "q_norm": (256,), "k_norm": (256,)}
    assert count("L3.attn") == 27_263_488
    assert layout["L3.moe"]["router"] == (2048, 512)
    assert layout["L3.moe"]["W_gate"] == (32, 2048, 512)
    assert layout["L3.moe"]["S_down"] == (512, 2048)
    assert layout["L3.moe"]["S_sgate"] == (2048, 1)
    assert count("L3.moe") == 104_859_648
    assert layout["embed"]["weight"] == layout["head.logits"]["weight"] \
        == (18992, 2048)
    assert net.blob_shapes["logits"] == (8192, 1, 18992)
    assert len(net.recompute_blocks) == 4
    # the whole model is the same function
    whole = zoo.qwen3_next(experts_held=512, vocab=151936, layers=48,
                           seq=128)
    types = [ly.type for ly in whole.layer]
    assert types.count("GatedDeltaNet") == 36
    assert types.count("GroupedQueryAttention") == 12
    assert types.count("MixtureOfExperts") == 48
    assert [ly.type for ly in whole.layer if ly.name.startswith("L7.")][1] \
        == "GroupedQueryAttention"
    with pytest.raises(ValueError, match="layers"):
        zoo.qwen3_next(first_layer=46, layers=4)


def test_flops_and_param_specs_know_the_new_operator():
    """`utils/flops.py` (and through it `analysis/roofline.py`) count
    the operator, the gated attention and the gated shared expert as
    the reference does; `tp_param_specs` gives every blob of theirs a
    spec (replicated) and the held experts the expert axis; a mesh that
    shards time refuses the net by name."""
    from caffeonspark_tpu.analysis.roofline import analyze_net
    from caffeonspark_tpu.parallel.mesh import (MeshLayout, build_mesh,
                                                tp_param_specs)
    from caffeonspark_tpu.utils.flops import (forward_flops,
                                              layer_forward_flops)
    net = Net(small_net())
    cfg = small_cfg()
    assert forward_flops(net) == ref.forward_flops(
        cfg, SMALL["seq"], SMALL["batch"])
    per = layer_forward_flops(net)
    n = SMALL["seq"] * SMALL["batch"]
    kw, vw = 2 * 8, 4 * 8
    assert per["L0.gdn"] == (2 * n * ((2 * kw + 2 * vw) * 32 + 8 * 32
                                      + 32 * vw) + n * 4 * 6 * 8 * 8)
    assert 3 * per["L0.gdn"] - 3 * 2 * n * (
        (2 * kw + 2 * vw) * 32 + 8 * 32 + 32 * vw) == ref.scan_flops(
            cfg, SMALL["seq"], SMALL["batch"])
    assert ref.scan_bytes(cfg, SMALL["seq"], SMALL["batch"]) == (
        3 * n * (2 * kw + 2 * vw + 2 * 4) * 4)
    assert per["L3.attn"] == (2 * n * (3 * 64 * 32 + 2 * 32 * 32)
                              + 2 * 2 * 4 * 20 * 20 // 2 * 2 * 16)
    rows = {r["layer"]: r for r in analyze_net(net, act_bytes=4,
                                               param_bytes=4)}
    assert rows["L0.gdn"]["flops"] == 3 * per["L0.gdn"]
    specs = tp_param_specs(net)
    assert set(specs["L0.gdn"]) == {"W_qkvz", "W_ba", "taps", "A_log",
                                    "dt_bias", "norm", "W_out"}
    assert all(tuple(s) == () for s in specs["L0.gdn"].values())
    assert all(tuple(s) == () for s in specs["L3.attn"].values())
    assert tuple(specs["L1.moe"]["W_up"]) == ("ep", None, None)
    assert tuple(specs["L1.moe"]["S_sgate"]) == ()
    MeshLayout(net, build_mesh(dp=2, devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="sequence parallelism.*L0.gdn"):
        MeshLayout(net, build_mesh(sp=2, devices=jax.devices()[:2]))


def test_full_width_counts_at_the_cell_shape():
    """The operations and bytes the benchmark's roofline reader divides
    by, at the cell's shape: 3 layers, 32 heads of 128 x 128, 8,192
    tokens."""
    import json
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perfbench", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        cfg = json.load(f)
    assert ref.num_params(cfg) == 625_667_136
    assert ref.scan_flops(cfg, 8192, 1) == 3 * 8192 * 32 * 6 * 128 * 128
    assert ref.scan_bytes(cfg, 8192, 1) == 3 * 8192 * (
        2 * 2048 + 2 * 4096 + 64) * 4
    fwd = ref.forward_flops(cfg, 8192, 1)
    assert 11.2e12 < 3 * fwd < 11.4e12
    from caffeonspark_tpu.utils.flops import forward_flops
    assert forward_flops(Net(zoo.qwen3_next())) == fwd

