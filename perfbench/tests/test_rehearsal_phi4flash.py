"""CPU rehearsals of `phi4flash.train_packed8k` at tiny shapes with the
model's structure (the published layers 14-19 of a 32-layer layout:
Mamba, differential attention under a window of 8 keys over rows of 64,
the Mamba that emits the memory, the full attention that emits k / v, a
Gated Memory Unit, a cross-attention layer; 4 query heads over 2
key/value heads of 16; tied embedding): records to result object, the
plain reference against the system through the timed path, and timed
paths that are broken, the memory replaced and the window dropped among
them.  No device metric is printed."""

import json
import os

import pytest

from conftest import ROOT
from perfbench import run as R

CELL = "phi4flash.train_packed8k"
TINY = dict(vocab=96, hidden=64, heads=4, kv_heads=2, head_dim=16,
            intermediate=96, d_inner=128, d_state=16, d_conv=4, dt_rank=4,
            window=8, seq=64, batch=2, chunk=16)


def tiny():
    from caffeonspark_tpu.models import zoo
    net = zoo.phi4flash(**TINY)         # layers 14-19 of 32, as the cell
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_phi4flash.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "phi4flash_mini.json")) as f:
        assumed = json.load(f)["assumed"]
    return {"entry": {"chips": 1},
            "config": {"net": rel, "hidden_size": 64,
                       "num_attention_heads": 4, "num_key_value_heads": 2,
                       "intermediate_size": 96, "sliding_window": 8,
                       "vocab_size": 96, "sequence_length": 64,
                       "per_device_batch": 2,
                       "assumed": dict(assumed, mamba_dt_rank=4),
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


def _faulty(fault):
    def step(real, params, st, batch, rng):
        from perfbench import control_shared
        if fault not in _STEPS:
            _STEPS[fault] = control_shared.faulty_step(os.path.join(
                ROOT, ".perfbench_work", CELL, "solver.prototxt"), fault)
        return _STEPS[fault](params, st, batch, rng)
    step.__name__ = fault
    return step


_STEPS: dict = {}
wrong_memory, unwindowed = _faulty("memory"), _faulty("window")


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (wrong_memory, False),
    (unwindowed, False)])
def test_phi4flash_window_rehearsal(broken, correct):
    _STEPS.clear()
    res = R.run_cell(ROOT, CELL, 2147484041, 1.0, False,
                     overrides=tiny(), device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())
    assert res["checks"]["init_gap"]["value"] == 0.0
    assert res["checks"]["ingest_token_gap"]["value"] == 0.0
    assert "dropped_assignments" not in res["checks"]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NEW = ("ssm.device_ms.train", "ssm.scan_device_ms.train",
       "ssm.scan_roofline_pct.train", "gmu.device_ms.train",
       "attn.shared_kv_device_ms.train")


def test_traced_rehearsal_leaves_the_new_metrics_out_on_the_cpu():
    """On the CPU there is no device plane: the five readers this cell
    adds find nothing, return None and raise nothing."""
    res = R.run_cell(ROOT, CELL, 5, 1.0, True, overrides=tiny(),
                     device=None)
    assert res["correct"] is True
    for name in NEW:
        assert name not in res["metrics"]


def test_new_readers_on_recorded_and_hand_made_traces():
    """A trace recorded on the chip from a program without the scopes (a
    parent from before them): every reader returns None.  A window and
    ops given by hand: `ssm.scan` lies inside `ssm`, the cross layer is
    told from the layer that makes k / v by its name, and the roofline
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
               ("jit(step)/jvp(checkpoint)/L0.mamba/ssm/ssm.proj/"
                "dot_general:", 0.0, 0.1),
               ("jit(step)/jvp(checkpoint)/L0.mamba/ssm/ssm.scan/"
                "cos_ssm_fwd:", 0.1, 0.2),
               ("jit(step)/transpose(jvp(checkpoint))/L2.mamba/ssm/"
                "ssm.scan/cos_ssm_bwd:", 0.2, 0.5),
               ("jit(step)/jvp(checkpoint)/L4.gmu/gmu/dot_general:",
                0.5, 0.54),
               ("jit(step)/jvp(checkpoint)/L3.attn/attn/attn.core/"
                "cos_flash_fwd:", 0.54, 0.6),
               ("jit(step)/jvp(checkpoint)/L5.attn/attn/attn.core/"
                "cos_flash_fwd:", 0.6, 0.7),
               ("jit(step)/transpose(jvp(checkpoint))/L5.attn/attn/"
                "attn.diff/mul:", 0.7, 0.72)],
               (0.0, 1.0))}
    assert _reader("ssm.device_ms.train").read(run) == pytest.approx(250.0)
    assert _reader("ssm.scan_device_ms.train").read(run) == \
        pytest.approx(200.0)
    assert _reader("gmu.device_ms.train").read(run) == pytest.approx(20.0)
    assert _reader("attn.shared_kv_device_ms.train").read(run) == \
        pytest.approx(60.0)
    assert scopes.ms_per_step(run, r"attn") == pytest.approx(90.0)
    roof = _reader("ssm.scan_roofline_pct.train")
    t, c, n = 8192, 5120, 16
    assert roof.scan_bytes(cfg, t, 1) == 2 * 4 * (
        (3 * t * c + 2 * t * n) + (5 * t * c + 4 * t * n + c * n))
    assert roof.scan_operations(cfg, t, 1) == 2 * 27 * t * c * n
    ms = roof.allowed_ms(run)
    assert 3.28 < ms < 3.30         # 2.70 GB at 819 GB/s
    assert roof.read(run) == pytest.approx(100.0 * ms / 200.0)
    assert roof.read(dict(run, trace=None)) is None


def test_manifest_resolves_the_phi4flash_cell():
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
                  "qwen3next.train_packed8k", "smallthinker.train_packed16k"):
        assert not set(NEW) & set(R.metric_names(
            res["manifest"], "per_layer", other))
    # the configuration's own count of what it holds, and its text
    from perfbench.reference import phi4flash_mini as model
    assert model.num_params(cfg) == 697_073_792
    flops = model.forward_flops(cfg, 8192, 1)
    assert 12.3e12 < flops < 12.7e12            # ISSUE 42: about 12.4
    assert model.dims(cfg)["kinds"] == (
        "mamba", "window", "mamba_memory", "full_kv", "gmu", "cross")
    whole = dict(cfg, first_layer=0, num_hidden_layers=32,
                 vocab_size=200064)
    assert model.num_params(whole) == 3_852_457_984     # published: 3.8 B
    from caffeonspark_tpu.models import zoo
    net = zoo.phi4flash()
    del net.layer[0]
    with open(os.path.join(ROOT, cfg["net"])) as f:
        assert f.read() == net.to_text()
    # no width differs from the source: the catalog row's numbers, but
    # for the keys the file lists as reduced
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["dataset", "num_hidden_layers",
                                      "vocab_size"]
    assert (cfg["vocab_size"], cfg["num_hidden_layers"],
            cfg["first_layer"]) == (25008, 6, 14)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 200064
    assert cfg["published"]["num_hidden_layers"] == 32
    for name, limit in res["cell"]["limits"].items():
        assert name in res["cell"]["readings"] or limit == 0, name
    assert "dropped_assignments" not in res["cell"]["limits"]


def test_controls_read_worse_than_the_sound_program():
    """The program's own bfloat16-activation path and the two planted
    faults beside the program as stated, each against the reference, at
    tiny size on the CPU, as the two control scripts run them at full
    size on the chip."""
    from perfbench import control_shared, control_tokens
    res = R.resolve(ROOT, CELL)
    for part, patch in tiny().items():
        res[part].update(patch)
    both = control_tokens.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control.phi4flash"))
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "update_norm_gap": 1e-4, "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]
    sides = control_shared.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control_shared.phi4flash"),
        sound=True)
    assert set(sides) == {"memory", "window", "sound"}
    assert control_shared.fails(sides["memory"], limits), sides["memory"]
    assert control_shared.fails(sides["window"], limits), sides["window"]
    assert not control_shared.fails(sides["sound"], limits), sides["sound"]
    assert sides["memory"]["init_gap"] == 0.0
