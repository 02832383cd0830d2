"""CPU rehearsals of `qwen3next.train_packed8k` at tiny shapes with the
model's structure (one period: Gated DeltaNet, Gated DeltaNet, Gated
DeltaNet, gated full attention; 2 key heads serving 4 value heads in
the linear layers, chunks of 8 tokens over rows of 72, so a last partial
group; 4 query heads over 2 key/value heads with partial rotary turns
and an output gate; 16 softmax-routed experts of which this share holds
8, top 3, one sigmoid-gated shared expert): records to result object,
the plain reference against the system through the timed path, and timed
paths that are broken.  No device metric is printed."""

import os

import pytest

from conftest import ROOT
from perfbench import run as R

CELL = "qwen3next.train_packed8k"
TINY = dict(vocab=96, hidden=32, heads=4, kv_heads=2, head_dim=16,
            rotary_dim=4, linear_k_heads=2, linear_v_heads=4,
            linear_k_dim=8, linear_v_dim=8, chunk=8, expert_width=12,
            shared_width=12, experts=16, top_k=3, experts_held=8,
            layers=4, seq=72, batch=2)


def tiny():
    from caffeonspark_tpu.models import zoo
    net = zoo.qwen3_next(**TINY)
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_qwen3next.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    return {"entry": {"chips": 1},
            "config": {"net": rel, "hidden_size": 32,
                       "num_attention_heads": 4, "num_key_value_heads": 2,
                       "head_dim": 16, "linear_num_key_heads": 2,
                       "linear_num_value_heads": 4,
                       "linear_key_head_dim": 8, "linear_value_head_dim": 8,
                       "moe_intermediate_size": 12,
                       "shared_expert_intermediate_size": 12,
                       "num_experts": 16, "num_experts_per_tok": 3,
                       "experts_held": 8, "vocab_size": 96,
                       "num_hidden_layers": 4, "sequence_length": 72,
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


def no_recurrence(real, params, st, batch, rng):
    """A step whose Gated DeltaNet layers leave the recurrence out: the
    taps of the v channels read as zero, so v = silu(0) = 0, nothing is
    ever written to the state and o = 0 before the gated norm, which so
    gives 0.  Projections, decays, attention, experts and head intact."""
    def cut(blobs):
        vw = blobs["W_out"].shape[1]
        return dict(blobs, taps=blobs["taps"].at[-vw:].set(0.0))
    p = {ln: (cut(bl) if ln.endswith(".gdn") else bl)
         for ln, bl in params.items()}
    return real(p, st, batch, rng)


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (no_recurrence, False)])
def test_qwen3next_window_rehearsal(broken, correct):
    res = R.run_cell(ROOT, CELL, 2147484036, 1.0, False,
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
    for name in ("gdn.device_ms.train", "gdn.scan_device_ms.train",
                 "gdn.scan_roofline_pct.train"):
        assert name not in res["metrics"]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gdn_readers_on_recorded_and_hand_made_traces():
    """A trace recorded on the chip from a program without the scopes
    (a parent from before them): every reader returns None.  A window
    and ops given by hand: the nested scope is counted in its parent
    too, and the roofline share is the time the peaks allow over the
    scope's time."""
    from perfbench.harness import scopes
    path = os.path.join(ROOT, "perfbench", "tests", "data")
    run = {"trace_dir": path, "steps": 4, "batch": 1,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"devices": {"/device:TPU:0": {"window": (0.0, 1e9)}}}}
    orig = scopes.tr.find_xplane
    scopes.tr.find_xplane = lambda d: os.path.join(d, "cos_small.xplane.pb")
    try:
        for name in ("gdn.device_ms.train", "gdn.scan_device_ms.train",
                     "gdn.scan_roofline_pct.train"):
            assert _reader(name).read(run) is None
    finally:
        scopes.tr.find_xplane = orig
    cfg = R.resolve(ROOT, CELL)["config"]
    run = {"steps": 2, "batch": 1, "trace": {"x": 1},
           "device": {"kind": "TPU v5 lite"},
           "ctx": {"config": cfg, "chips": 1},
           "device_ops": ([
               ("jit(step)/jvp(L0.gdn)/gdn/dot_general:", 0.0, 0.010),
               ("jit(step)/jvp(L0.gdn)/gdn/gdn.conv/mul:", 0.010, 0.014),
               ("jit(step)/transpose(jvp(L0.gdn))/gdn/gdn.scan/while:",
                0.014, 0.114),
               ("jit(step)/jvp(L3.attn)/attn/attn.core/cos_flash_fwd:",
                0.114, 0.2)], (0.0, 1.0))}
    assert _reader("gdn.device_ms.train").read(run) == pytest.approx(57.0)
    assert _reader("gdn.scan_device_ms.train").read(run) == \
        pytest.approx(50.0)
    roof = _reader("gdn.scan_roofline_pct.train")
    ms, bound = roof.allowed_ms(run)
    # 3 layers x 8,192 tokens x (2 x 2,048 + 2 x 4,096 + 64) floats, four
    # passes, at 819 GB/s: the bytes set the bound, not the 0.39 ms a
    # pass of operations
    assert bound == "bytes"
    assert ms == pytest.approx(4 * 3 * 8192 * 12352 * 4 / 819e9 * 1e3)
    assert roof.read(run) == pytest.approx(100.0 * ms / 50.0)
    assert roof.read(dict(run, trace=None)) is None


def test_manifest_resolves_the_qwen3next_cell():
    res = R.resolve(ROOT, CELL)
    assert res["chips"] == 1 and res["traffic"]["kind"] == "train_tokens"
    assert res["entry"]["traffic"] == "packed_tokens_parquet"
    cfg = res["config"]
    assert cfg["per_device_batch"] * cfg["sequence_length"] == 8192
    names = R.metric_names(res["manifest"], "per_layer", CELL)
    for name in ("gdn.device_ms.train", "gdn.scan_device_ms.train",
                 "gdn.scan_roofline_pct.train", "step.device_ms.train",
                 "step.mfu_pct.train", "device.idle_pct.train",
                 "ingest.pack_ms_per_img.train",
                 "ingest.queue_wait_pct.train"):
        assert name in names
    assert len(names) == 8              # the accepted lists are not its own
    for other in ("kanana2.train_packed4k", "lfm2.train_packed8k"):
        assert not any(n.startswith("gdn.") for n in R.metric_names(
            res["manifest"], "per_layer", other))
    # the configuration's own count of what it holds, and its text
    from perfbench.reference import qwen3_next_80b_a3b as model
    assert model.num_params(cfg) == 625_667_136
    assert 11.2e12 < 3 * model.forward_flops(cfg, 8192, 1) < 11.4e12
    assert model.dims(cfg)["kinds"] == (
        "linear_attention", "linear_attention", "linear_attention",
        "full_attention")
    from caffeonspark_tpu.models import zoo
    net = zoo.qwen3_next()
    del net.layer[0]
    with open(os.path.join(ROOT, cfg["net"])) as f:
        assert f.read() == net.to_text()
    # no width differs from the source: the catalog row's numbers, but
    # for the keys the file lists as reduced
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512}
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["dataset", "experts_held",
                                      "num_hidden_layers", "vocab_size"]
    assert (cfg["experts_held"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (32, 18992, 4)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"] == 151936
    assert cfg["num_experts_published"] == 512
    assert cfg["num_hidden_layers_published"] == 48
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
        ROOT, ".perfbench_work", "test_control.qwen3next"))
    # no limit on the parameters' change here: at this size the leaves
    # that only set a head's decay (W_ba, A_log) have gradients of the
    # size of their rounding, and Adam moves such an element by lr where
    # the noise points, on the sound side too (tests/test_qwen3_next.py)
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]
    assert both["control"]["loss_gap_step1"] > 10 * both["sound"][
        "loss_gap_step1"]
