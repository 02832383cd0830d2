"""CPU rehearsals of `lfm2.train_packed8k` at tiny shapes with the
model's structure (the published layers 1-5: a dense conv layer, then
attention, conv, conv, conv over experts; 8 experts of which this share
holds 4, top 2, none shared; 4 query heads over 2 key/value heads):
records to result object, the plain reference against the system through
the timed path, and timed paths that are broken.  No device metric is
printed."""

import os

import pytest

from conftest import ROOT
from perfbench import run as R

CELL = "lfm2.train_packed8k"
TINY = dict(vocab=96, hidden=32, heads=4, kv_heads=2, head_dim=8,
            dense_width=48, expert_width=12, experts=8, top_k=2,
            experts_held=4, layers=5, seq=128, batch=2)


def tiny():
    from caffeonspark_tpu.models import zoo
    net = zoo.lfm2(**TINY)
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_lfm2.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    return {"entry": {"chips": 1},
            "config": {"net": rel, "hidden_size": 32,
                       "num_attention_heads": 4, "num_key_value_heads": 2,
                       "intermediate_size": 48, "moe_intermediate_size": 12,
                       "num_experts": 8, "num_experts_per_tok": 2,
                       "experts_held": 4, "vocab_size": 96,
                       "num_hidden_layers": 5, "sequence_length": 128,
                       "per_device_batch": 2},
            "traffic": {"rows": 16, "doc_length_median": 40,
                        "doc_length_max": 300},
            "cell": {"warmup_steps": 3, "trace_seconds": 1}}


def unchanged(real, params, st, batch, rng):
    """A step that returns its state unchanged."""
    import jax
    keep = jax.tree.map(lambda a: a.copy(), (params, st))
    _, _, out = real(params, st, batch, rng)
    return keep[0], keep[1], out


def no_short_convolution(real, params, st, batch, rng):
    """A step whose short-convolution layers leave their output out (the
    out-products read as zero): attention, experts and head intact."""
    import jax.numpy as jnp
    p = {ln: {bn: (jnp.zeros_like(a) if ln.endswith(".conv")
                   and bn == "W_out" else a)
              for bn, a in bl.items()} for ln, bl in params.items()}
    return real(p, st, batch, rng)


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (no_short_convolution, False)])
def test_lfm2_window_rehearsal(broken, correct):
    res = R.run_cell(ROOT, CELL, 2147484033, 1.0, False,
                     overrides=tiny(), device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())
    assert res["checks"]["init_gap"]["value"] == 0.0
    assert res["checks"]["ingest_token_gap"]["value"] == 0.0
    assert res["checks"]["dropped_assignments"]["value"] == 0.0


def test_traced_rehearsal_leaves_the_scope_metrics_out_on_the_cpu():
    """On the CPU there is no device plane: the three readers this cell
    adds find nothing, return None and raise nothing."""
    res = R.run_cell(ROOT, CELL, 5, 1.0, True, overrides=tiny(),
                     device=None)
    assert res["correct"] is True
    for name in ("sconv.device_ms.train", "sconv.mix_device_ms.train",
                 "attn.core_device_ms.train"):
        assert name not in res["metrics"]


def test_scope_readers_on_recorded_traces():
    """A trace recorded on the chip from a program without the scopes:
    every reader returns None.  A window and ops given by hand: the
    time inside the window of the ops whose name stack holds the scope,
    a nested scope counted in its parent too."""
    from perfbench.harness import scopes
    path = os.path.join(ROOT, "perfbench", "tests", "data")
    run = {"trace_dir": path, "steps": 4,
           "trace": {"devices": {"/device:TPU:0": {"window": (0.0, 1e9)}}}}
    orig = scopes.tr.find_xplane
    scopes.tr.find_xplane = lambda d: os.path.join(d, "cos_small.xplane.pb")
    try:
        assert len(scopes.ops_of_run(run)[0]) == 1650
        for pat in (r"sconv", r"sconv\.mix", r"attn\.core"):
            assert scopes.seconds(run, pat) is None
            assert scopes.ms_per_step(run, pat) is None
    finally:
        scopes.tr.find_xplane = orig
    run = {"steps": 2, "trace": {}, "device_ops": ([
        ("jit(step)/jvp(L0.conv)/sconv/dot_general:", 0.0, 1.0),
        ("jit(step)/transpose(jvp(L0.conv))/sconv/sconv.mix/mul:", 1.0, 1.5),
        ("jit(step)/jvp(L1.attn)/attn/attn.core/cos_flash_fwd:", 1.5, 3.5),
        ("jit(step)/jvp(L1.attn)/attn/dot_general:", 3.5, 4.0),
        ("jit(step)/update/sqrt:", 4.0, 9.0)], (0.5, 3.0))}
    assert scopes.seconds(run, r"sconv") == 1.0          # 0.5 + 0.5
    assert scopes.seconds(run, r"sconv\.mix") == 0.5
    assert scopes.seconds(run, r"attn\.core") == 1.5     # cut at 3.0
    assert scopes.seconds(run, r"attn") == 1.5           # 3.5-4.0: outside
    assert scopes.seconds(run, r"nothing") is None
    assert scopes.ms_per_step(dict(run, trace={"x": 1}), r"sconv") == 500.0
    assert scopes.ms_per_step(run, r"sconv") is None     # no trace


def test_manifest_resolves_the_lfm2_cell():
    res = R.resolve(ROOT, CELL)
    assert res["chips"] == 1 and res["traffic"]["kind"] == "train_tokens"
    cfg = res["config"]
    assert cfg["per_device_batch"] * cfg["sequence_length"] == 8192
    names = R.metric_names(res["manifest"], "per_layer", CELL)
    for name in ("sconv.device_ms.train", "sconv.mix_device_ms.train",
                 "attn.core_device_ms.train", "step.device_ms.train",
                 "step.mfu_pct.train", "device.idle_pct.train",
                 "ingest.pack_ms_per_img.train",
                 "ingest.queue_wait_pct.train"):
        assert name in names
    assert "attn.device_ms.train" not in names      # kanana2's list
    kan = R.metric_names(res["manifest"], "per_layer",
                         "kanana2.train_packed4k")
    assert "attn.core_device_ms.train" in kan
    assert "sconv.device_ms.train" not in kan
    # the configuration's own count of what it holds, and its text
    from perfbench.reference import lfm2_24b_a2b as model
    assert model.num_params(cfg) == 664_597_120
    assert 12.5e12 < 3 * model.forward_flops(cfg, 8192, 1) < 12.7e12
    assert model.dims(cfg)["kinds"] == (
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False), ("full_attention", False),
        ("conv", False))
    from caffeonspark_tpu.models import zoo
    net = zoo.lfm2()
    del net.layer[0]
    with open(os.path.join(ROOT, cfg["net"])) as f:
        assert f.read() == net.to_text()
    assert len(cfg["layer_types"]) == 40 and cfg["num_dense_layers"] == 2
    for name, limit in res["cell"]["limits"].items():
        assert name in res["cell"]["readings"] or limit == 0, name


def test_lower_precision_control_reads_worse_than_the_sound_program():
    """The program's own bfloat16-activation path beside the program as
    stated, each against the reference, at tiny size on the CPU."""
    from perfbench import control_tokens
    res = R.resolve(ROOT, CELL)
    for part, patch in tiny().items():
        res[part].update(patch)
    both = control_tokens.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control.lfm2"))
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "update_norm_gap": 1e-4, "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]
