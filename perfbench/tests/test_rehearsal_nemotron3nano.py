"""CPU rehearsals of `nemotron3nano.train_packed8k` at tiny shapes with
the model's structure (the published blocks 34-42, `EMEMEMEM*`, one
operator a block: Mamba-2 of 4 heads of 16 over 2 groups of 16 states in
chunks of 16, 16 sigmoid-routed ungated squared-ReLU experts top 3 of
which 4 are held, an ungated shared expert, 4 query heads over one
key/value head without positions): records to result object, the plain
reference against the system through the timed path, a timed path that
is broken, and the program's own bfloat16-activation path.  No device
metric is printed."""

import os

import pytest

from conftest import ROOT
from perfbench import run as R

CELL = "nemotron3nano.train_packed8k"
TINY = dict(vocab=96, hidden=64, heads=4, kv_heads=1, head_dim=16,
            mamba_heads=4, mamba_head_dim=16, n_groups=2, d_state=16,
            chunk=16, expert_width=24, shared_width=48, experts=16,
            top_k=3, experts_held=4, seq=64, batch=2)


def tiny():
    from caffeonspark_tpu.models import zoo
    net = zoo.nemotron_h(**TINY)        # blocks 34-42 of 52, as the cell
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_nemotron3nano.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    return {"entry": {"chips": 1},
            "config": {"net": rel, "hidden_size": 64,
                       "num_attention_heads": 4, "num_key_value_heads": 1,
                       "head_dim": 16, "mamba_num_heads": 4,
                       "mamba_head_dim": 16, "n_groups": 2,
                       "ssm_state_size": 16, "moe_intermediate_size": 24,
                       "moe_shared_expert_intermediate_size": 48,
                       "n_routed_experts": 16, "num_experts_per_tok": 3,
                       "experts_held": 4, "vocab_size": 96,
                       "sequence_length": 64, "per_device_batch": 2,
                       "solver": {"type": "Adam", "base_lr": 1e-4,
                                  "lr_policy": "fixed", "momentum": 0.9,
                                  "momentum2": 0.95, "delta": 1e-8,
                                  "clip_gradients": 1.0}},
            "traffic": {"rows": 16, "doc_length_median": 40,
                        "doc_length_max": 300},
            "cell": {"warmup_steps": 3, "trace_seconds": 1}}


def unchanged(real, params, st, batch, rng):
    """A step that returns its state unchanged."""
    import jax
    keep = jax.tree.map(lambda a: a.copy(), (params, st))
    _, _, out = real(params, st, batch, rng)
    return keep[0], keep[1], out


@pytest.mark.parametrize("broken,correct", [(None, True),
                                            (unchanged, False)])
def test_nemotron3nano_window_rehearsal(broken, correct):
    res = R.run_cell(ROOT, CELL, 2147484047, 1.0, False,
                     overrides=tiny(), device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())
    assert res["checks"]["init_gap"]["value"] == 0.0
    assert res["checks"]["ingest_token_gap"]["value"] == 0.0
    assert res["checks"]["dropped_assignments"]["value"] == 0.0


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NEW = ("ssd.device_ms.train", "ssd.scan_device_ms.train",
       "ssd.scan_roofline_pct.train")


def test_traced_rehearsal_leaves_the_new_metrics_out_on_the_cpu():
    """On the CPU there is no device plane: the three readers this cell
    adds find nothing, return None and raise nothing."""
    res = R.run_cell(ROOT, CELL, 5, 1.0, True, overrides=tiny(),
                     device=None)
    assert res["correct"] is True
    for name in NEW:
        assert name not in res["metrics"]


def test_new_readers_on_recorded_and_hand_made_traces():
    """A trace recorded on the chip from a program without the scopes (a
    parent from before them): every reader returns None.  A window and
    ops given by hand: `ssd.scan` lies inside `ssd`, and the roofline
    share is the time the bandwidth allows over the scope's time, a
    forward and a backward pass and no second forward."""
    from perfbench.harness import scopes
    cfg = R.resolve(ROOT, CELL)["config"]
    path = os.path.join(ROOT, "perfbench", "tests", "data")
    run = {"trace_dir": path, "steps": 4, "batch": 1,
           "device": {"kind": "TPU v5 lite"},
           "ctx": {"config": cfg, "chips": 1},
           "trace": {"devices": {"/device:TPU:0": {"window": (0.0, 1e9)}}}}
    orig = scopes.tr.find_xplane
    scopes.tr.find_xplane = lambda d: os.path.join(d, "cos_small.xplane.pb")
    try:
        for name in NEW:
            assert _reader(name).read(run) is None, name
    finally:
        scopes.tr.find_xplane = orig
    run = {"steps": 2, "batch": 1, "trace": {"x": 1},
           "device": {"kind": "TPU v5 lite"},
           "ctx": {"config": cfg, "chips": 1},
           "device_ops": ([
               ("jit(step)/jvp(checkpoint)/L1.mamba2/ssd/ssd.proj/"
                "dot_general:", 0.0, 0.1),
               ("jit(step)/jvp(checkpoint)/L1.mamba2/ssd/ssd.scan/"
                "dot_general:", 0.1, 0.2),
               ("jit(step)/transpose(jvp(checkpoint))/L3.mamba2/ssd/"
                "ssd.scan/while:", 0.2, 0.5),
               ("jit(step)/jvp(checkpoint)/L3.mamba2/ssd/ssd.norm/mul:",
                0.5, 0.54),
               ("jit(step)/jvp(checkpoint)/L8.attn/attn/attn.core/"
                "cos_flash_fwd:", 0.54, 0.6)],
               (0.0, 1.0))}
    assert _reader("ssd.device_ms.train").read(run) == pytest.approx(270.0)
    assert _reader("ssd.scan_device_ms.train").read(run) == \
        pytest.approx(200.0)
    roof = _reader("ssd.scan_roofline_pct.train")
    t, c, h, gn = 8192, 4096, 64, 1024
    ops = t * (c + h + 2 * gn)
    assert roof.scan_bytes(cfg, t, 1) == 4 * 4 * (
        (ops + t * c) + (ops + t * c + ops + 2 * h))
    assert roof.scan_operations(cfg, t, 1) == 4 * 3 * t * h * 6 * 64 * 128
    ms = roof.allowed_ms(run)
    assert 4.28 < ms < 4.30         # 3.51 GB at 819 GB/s
    assert roof.read(run) == pytest.approx(100.0 * ms / 200.0)
    assert roof.read(dict(run, trace=None)) is None


def test_manifest_resolves_the_nemotron3nano_cell():
    res = R.resolve(ROOT, CELL)
    assert res["chips"] == 1 and res["traffic"]["kind"] == "train_tokens"
    assert res["entry"]["traffic"] == "packed_tokens_parquet"
    cfg = res["config"]
    assert cfg["per_device_batch"] * cfg["sequence_length"] == 8192
    names = R.metric_names(res["manifest"], "per_layer", CELL)
    assert sorted(names) == sorted(NEW + (
        "step.device_ms.train", "step.mfu_pct.train",
        "device.idle_pct.train", "ingest.pack_ms_per_img.train",
        "ingest.queue_wait_pct.train"))
    for other in ("kanana2.train_packed4k", "lfm2.train_packed8k",
                  "qwen3next.train_packed8k", "smallthinker.train_packed16k",
                  "phi4flash.train_packed8k"):
        assert not set(NEW) & set(R.metric_names(
            res["manifest"], "per_layer", other))
    # the configuration's own count of what it holds, and its text
    from perfbench.reference import nemotron3_nano_30b_a3b as model
    assert model.num_params(cfg) == 666_963_456
    flops = model.forward_flops(cfg, 8192, 1)
    assert 5.8e12 < flops < 5.9e12              # ISSUE 47: 717 MFLOP a token
    assert model.dims(cfg)["kinds"] == "EMEMEMEM*"
    whole = dict(cfg, first_layer=0, num_hidden_layers=52,
                 vocab_size=131072, experts_held=128)
    assert model.num_params(whole) == 31_577_940_288    # published: 31.6 B
    from caffeonspark_tpu.models import zoo
    net = zoo.nemotron_h()
    del net.layer[0]
    with open(os.path.join(ROOT, cfg["net"])) as f:
        assert f.read() == net.to_text()
    # no width differs from the source: every number of the catalog
    # row's config, but for the keys the file lists as reduced
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_num_heads": 64,
        "max_position_embeddings": 262144, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_conv_bias": True,
        "hybrid_override_pattern": zoo.NEMOTRON_H_PATTERN}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["dataset", "experts_held",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["vocab_size"], cfg["num_hidden_layers"], cfg["first_layer"],
            cfg["experts_held"]) == (16384, 9, 34, 8)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 131072
    assert cfg["published"]["num_hidden_layers"] == 52
    for name, limit in res["cell"]["limits"].items():
        assert name in res["cell"]["readings"] or limit == 0, name


def test_control_reads_worse_than_the_sound_program():
    """The program's own bfloat16-activation path beside the program as
    stated, each against the reference, at tiny size on the CPU, as the
    control script runs them at full size on the chip."""
    from perfbench import control_tokens
    res = R.resolve(ROOT, CELL)
    for part, patch in tiny().items():
        res[part].update(patch)
    both = control_tokens.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control.nemotron3nano"))
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "update_norm_gap": 1e-4, "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]
