"""CPU rehearsals of `smallthinker.train_packed16k` at tiny shapes with
the model's structure (one period: a global layer without positions,
three rotary layers under a window of 24 keys over rows of 96; 6 query
heads over 2 key/value heads; 16 softmax-routed ReLU-gated experts of
which this share holds 8, top 3, the router fed from the block's normed
input): records to result object, the plain reference against the system
through the timed path, and timed paths that are broken, the window
layers run unwindowed among them.  No device metric is printed."""

import json
import os

import pytest

from conftest import ROOT
from perfbench import run as R

CELL = "smallthinker.train_packed16k"
TINY = dict(vocab=96, hidden=32, heads=6, kv_heads=2, head_dim=8,
            expert_width=12, experts=16, top_k=3, experts_held=8,
            window=24, layers=4, seq=96, batch=2)


def tiny():
    from caffeonspark_tpu.models import zoo
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        embed_std = json.load(f)["assumed"]["embed_std"]
    net = zoo.smallthinker(embed_std=embed_std, **TINY)
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_smallthinker.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    return {"entry": {"chips": 1},
            "config": {"net": rel, "hidden_size": 32,
                       "num_attention_heads": 6, "num_key_value_heads": 2,
                       "head_dim": 8, "moe_ffn_hidden_size": 12,
                       "moe_num_primary_experts": 16,
                       "moe_num_active_primary_experts": 3,
                       "experts_held": 8, "vocab_size": 96,
                       "num_hidden_layers": 4, "sliding_window_size": 24,
                       "sequence_length": 96, "per_device_batch": 2},
            "traffic": {"rows": 16, "doc_length_median": 40,
                        "doc_length_max": 300},
            "cell": {"warmup_steps": 3, "trace_seconds": 1}}


def unchanged(real, params, st, batch, rng):
    """A step that returns its state unchanged."""
    import jax
    keep = jax.tree.map(lambda a: a.copy(), (params, st))
    _, _, out = real(params, st, batch, rng)
    return keep[0], keep[1], out


def no_routed_experts(real, params, st, batch, rng):
    """A step whose routed experts are left out (their out-products read
    as zero): attention, router and head intact."""
    import jax.numpy as jnp
    p = {ln: {bn: (jnp.zeros_like(a) if bn == "W_down" else a)
              for bn, a in bl.items()} for ln, bl in params.items()}
    return real(p, st, batch, rng)


def unwindowed(real, params, st, batch, rng):
    """A step whose window layers attend to their whole causal past: the
    same net text without its `window:` lines (`control_window.py`)."""
    from perfbench import control_window
    if "step" not in _UNWINDOWED:
        _UNWINDOWED["step"] = control_window.unwindowed_step(os.path.join(
            ROOT, ".perfbench_work", CELL, "solver.prototxt"))
    return _UNWINDOWED["step"](params, st, batch, rng)


_UNWINDOWED: dict = {}


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (no_routed_experts, False),
    (unwindowed, False)])
def test_smallthinker_window_rehearsal(broken, correct):
    _UNWINDOWED.clear()
    res = R.run_cell(ROOT, CELL, 2147484040, 1.0, False,
                     overrides=tiny(), device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())
    assert res["checks"]["init_gap"]["value"] == 0.0
    assert res["checks"]["ingest_token_gap"]["value"] == 0.0
    assert res["checks"]["dropped_assignments"]["value"] == 0.0
    if broken is unwindowed:
        # the number found on the chip: the first gradient, not the loss
        assert (res["checks"]["first_grad_norm_gap"]["value"]
                > res["checks"]["first_grad_norm_gap"]["limit"])


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_rehearsal_leaves_the_window_metrics_out_on_the_cpu():
    """On the CPU there is no device plane: the two readers this cell
    adds find nothing, return None and raise nothing."""
    res = R.run_cell(ROOT, CELL, 5, 1.0, True, overrides=tiny(),
                     device=None)
    assert res["correct"] is True
    for name in ("attn.window_device_ms.train",
                 "attn.window_roofline_pct.train"):
        assert name not in res["metrics"]


def test_window_readers_on_recorded_and_hand_made_traces():
    """A trace recorded on the chip from a program without the scope (a
    parent from before it): both readers return None.  A window and ops
    given by hand: `attn.window` lies inside `attn` and around
    `attn.core`, and the roofline share is the time the peaks allow over
    the scope's time.  The two counting functions at the cell's shape."""
    from perfbench.harness import scopes
    path = os.path.join(ROOT, "perfbench", "tests", "data")
    run = {"trace_dir": path, "steps": 4, "batch": 1,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"devices": {"/device:TPU:0": {"window": (0.0, 1e9)}}}}
    orig = scopes.tr.find_xplane
    scopes.tr.find_xplane = lambda d: os.path.join(d, "cos_small.xplane.pb")
    try:
        for name in ("attn.window_device_ms.train",
                     "attn.window_roofline_pct.train"):
            assert _reader(name).read(run) is None
    finally:
        scopes.tr.find_xplane = orig
    cfg = R.resolve(ROOT, CELL)["config"]
    run = {"steps": 2, "batch": 1, "trace": {"x": 1},
           "device": {"kind": "TPU v5 lite"},
           "ctx": {"config": cfg, "chips": 1},
           "device_ops": ([
               ("jit(step)/jvp(L0.attn)/attn/attn.core/cos_flash_fwd:",
                0.0, 0.1),
               ("jit(step)/jvp(L1.attn)/attn/attn.window/attn.core/"
                "cos_flash_fwd:", 0.1, 0.2),
               ("jit(step)/transpose(jvp(L1.attn))/attn/attn.window/"
                "attn.core/cos_flash_bwd_dq:", 0.2, 0.4),
               ("jit(step)/jvp(L1.attn)/attn/dot_general:", 0.4, 0.5)],
               (0.0, 1.0))}
    assert _reader("attn.window_device_ms.train").read(run) == \
        pytest.approx(150.0)
    assert scopes.ms_per_step(run, r"attn\.core") == pytest.approx(200.0)
    assert scopes.ms_per_step(run, r"attn") == pytest.approx(250.0)
    roof = _reader("attn.window_roofline_pct.train")
    ms, bound = roof.allowed_ms(run)
    from perfbench.reference import smallthinker_21b_a3b as model
    pairs = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert pairs == 58_722_304 == model.visible_pairs(16384, 4096)
    assert model.window_attn_flops(cfg, 16384, 1) == 3 * 4 * pairs * 128 * 28
    assert model.window_attn_bytes(cfg, 16384, 1) == \
        3 * 16384 * 128 * 2 * (2 * 28 + 2 * 4)
    # 11.36 TFLOP a step at 197 TFLOP/s: the operations set the bound,
    # not the 3.6 GB at 819 GB/s
    assert bound == "operations"
    assert ms == pytest.approx(4.5 * 3 * 4 * pairs * 128 * 28 / 197e12 * 1e3)
    assert 57.6 < ms < 57.8
    assert roof.read(run) == pytest.approx(100.0 * ms / 150.0)
    assert roof.read(dict(run, trace=None)) is None


def test_manifest_resolves_the_smallthinker_cell():
    res = R.resolve(ROOT, CELL)
    assert res["chips"] == 1 and res["traffic"]["kind"] == "train_tokens"
    assert res["entry"]["traffic"] == "packed_tokens_parquet"
    cfg = res["config"]
    assert cfg["per_device_batch"] * cfg["sequence_length"] == 16384 \
        == cfg["max_position_embeddings"]
    names = R.metric_names(res["manifest"], "per_layer", CELL)
    assert sorted(names) == sorted([
        "attn.window_device_ms.train", "attn.window_roofline_pct.train",
        "step.device_ms.train", "step.mfu_pct.train",
        "device.idle_pct.train", "ingest.pack_ms_per_img.train",
        "ingest.queue_wait_pct.train"])
    for other in ("kanana2.train_packed4k", "lfm2.train_packed8k",
                  "qwen3next.train_packed8k"):
        assert not any(n.startswith("attn.window") for n in R.metric_names(
            res["manifest"], "per_layer", other))
    # the configuration's own count of what it holds, and its text
    from perfbench.reference import smallthinker_21b_a3b as model
    assert model.num_params(cfg) == 370_547_200
    flops = model.forward_flops(cfg, 16384, 1)
    assert 573.2e6 < flops / 16384 < 573.4e6     # a token's forward pass
    assert 28.1e12 < 3 * flops < 28.3e12
    # the scores a row can see and no others: unwindowed, the three
    # window layers would cost 198 MFLOP a token more
    unwindowed = dict(cfg, sliding_window_layout=[0] * 52)
    extra = (model.forward_flops(unwindowed, 16384, 1) - flops) / 16384
    assert 197e6 < extra < 199e6
    assert model.dims(cfg)["kinds"] == (
        (0, False), (4096, True), (4096, True), (4096, True))
    from caffeonspark_tpu.models import zoo
    net = zoo.smallthinker(embed_std=cfg["assumed"]["embed_std"])
    del net.layer[0]
    with open(os.path.join(ROOT, cfg["net"])) as f:
        assert f.read() == net.to_text()
    # no width differs from the source: the catalog row's numbers, but
    # for the keys the file lists as reduced
    layout = [int(i % 4 != 0) for i in range(52)]
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_layout": layout,
        "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False,
        "model_name": "smallthinker_21b_instruct"}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["dataset", "experts_held",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["experts_held"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (8, 18992, 4)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 151936
    assert cfg["published"]["num_hidden_layers"] == 52
    for name, limit in res["cell"]["limits"].items():
        assert name in res["cell"]["readings"] or limit == 0, name


def test_controls_read_worse_than_the_sound_program():
    """The program's own bfloat16-activation path and the unwindowed
    program beside the program as stated, each against the reference,
    at tiny size on the CPU, as the two control scripts run them at
    full size on the chip."""
    from perfbench import control_tokens, control_window
    res = R.resolve(ROOT, CELL)
    for part, patch in tiny().items():
        res[part].update(patch)
    both = control_tokens.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control.smallthinker"))
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "update_norm_gap": 1e-4, "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]
    both = control_window.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control_window.smallthinker"),
        sound=True)
    assert control_window.fails(both["unwindowed"], limits)
    assert not control_window.fails(both["sound"], limits), both["sound"]
    assert both["unwindowed"]["window_kv_first_grad_norm_gap"] > 0.01
    assert both["sound"]["window_kv_first_grad_norm_gap"] < 1e-5
    assert set(both["sound"]["window_kv_first_grad_norm_gaps"]) == {
        f"L{i}.attn/{b}" for i in (1, 2, 3) for b in ("W_k", "W_v")}
    assert both["unwindowed"]["init_gap"] == 0.0
