"""CPU rehearsals of the `train_tokens` window at tiny shapes with the
model's structure (1 dense + 2 expert layers, 8 experts of which this
share holds 4, top 2, 2 shared, q/k wider than v): records to result
object, the plain reference against the system through the timed path,
and timed paths that are broken.  No device metric is printed."""

import os

import pytest

from conftest import ROOT
from perfbench import run as R

TINY = dict(vocab=96, hidden=32, heads=2, qk_nope=8, qk_rope=4, v_head=6,
            kv_lora_rank=16, dense_width=48, expert_width=12, experts=8,
            top_k=2, shared_experts=2, experts_held=4, expert_layers=2,
            seq=128, batch=2)


def tiny(chips=1):
    from caffeonspark_tpu.models import zoo
    net = zoo.kanana2(**TINY)
    del net.layer[0]                    # the window writes the data layer
    rel = os.path.join(".perfbench_work", "tiny_kanana2.net.prototxt")
    os.makedirs(os.path.join(ROOT, ".perfbench_work"), exist_ok=True)
    with open(os.path.join(ROOT, rel), "w") as f:
        f.write(net.to_text())
    return {"entry": {"chips": chips},
            "config": {"net": rel, "hidden_size": 32,
                       "num_attention_heads": 2, "qk_nope_head_dim": 8,
                       "qk_rope_head_dim": 4, "v_head_dim": 6,
                       "kv_lora_rank": 16, "intermediate_size": 48,
                       "moe_intermediate_size": 12, "n_routed_experts": 8,
                       "num_experts_per_tok": 2, "experts_held": 4,
                       "vocab_size": 96, "num_hidden_layers": 3,
                       "sequence_length": 128, "per_device_batch": 2},
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
    """A step whose expert layers leave the routed experts out (their
    down-projections read as zero): shared experts and router intact."""
    import jax.numpy as jnp
    p = {ln: {bn: (jnp.zeros_like(a) if bn == "W_down" else a)
              for bn, a in bl.items()} for ln, bl in params.items()}
    return real(p, st, batch, rng)


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (no_routed_experts, False)])
def test_token_window_rehearsal(broken, correct):
    res = R.run_cell(ROOT, "kanana2.train_packed4k", 2147484001, 1.0, False,
                     overrides=tiny(), device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())


def test_traced_rehearsal_reads_every_per_layer_metric_it_can():
    """On the CPU there is no device plane: the trace-reading metrics
    leave their lines out and the counters print."""
    res = R.run_cell(ROOT, "kanana2.train_packed4k", 5, 1.0, True,
                     overrides=tiny(), device=None)
    assert res["correct"] is True
    assert "moe.dropped_assignments.train" in res["metrics"]
    assert "moe.rows_max_over_mean.train" in res["metrics"]


def test_rows_are_seeded_packed_and_shifted():
    import json
    import numpy as np
    from perfbench.windows import train_tokens as tt
    traffic = json.load(open(os.path.join(
        ROOT, "perfbench", "traffic", "packed_tokens_parquet.json")))
    a = tt.make_rows(traffic, 16032, 4096, 8, 4294967297)
    b = tt.make_rows(traffic, 16032, 4096, 8, 4294967297)
    assert a.shape == (8, 4097) and (a == b).all()
    assert a.min() == 0 and 0 < a.max() < 16032
    assert (a != tt.make_rows(traffic, 16032, 4096, 8, 3)).any()
    # documents end about every 600-1,200 tokens; id 1 is the commonest
    ends = int((a == 0).sum())
    assert 8 * 4097 / 3000 < ends < 8 * 4097 / 150
    ids, counts = np.unique(a[a > 0], return_counts=True)
    assert ids[np.argmax(counts)] == 1
    found, gap = tt.match_rows(a[[5, 2], :-1].T.astype(np.float32),
                               a[[5, 2], 1:].T.astype(np.float32), a)
    assert found == [5, 2] and gap == 0.0


def test_scopes_are_read_from_the_op_metadata():
    from perfbench.harness import opmeta
    assert opmeta.part_of(
        "jit(step)/jit(main)/transpose(jvp(L3.attn))/attn/dot_general:") \
        == "attn"
    assert opmeta.part_of("jit(step)/jvp(L2.moe)/moe.experts/checkpoint/"
                          "ragged_dot:") == "moe"
    assert opmeta.part_of("jit(step)/update/sqrt:") == "update"
    assert opmeta.part_of("jit(step)/jvp(head.logits)/dot_general:") == "head"
    assert opmeta.part_of("jit(step)/jvp(conv1)/conv_general_dilated:") is None
    # a trace recorded on the chip from a program without such scopes:
    # every op is read with its name stack, and no part is found
    path = os.path.join(ROOT, "perfbench", "tests", "data",
                        "cos_small.xplane.pb")
    ops = opmeta.device_ops(path)["/device:TPU:0"]
    assert len(ops) == 1650
    assert any("dot_general" in o[0] for o in ops)
    assert opmeta.scope_seconds(path) == {}


def test_lower_precision_control_reads_worse_than_the_sound_program():
    """The program's own bfloat16-activation path beside the program as
    stated, each against the reference: at this size on the CPU the
    stated program agrees to float32 rounding and the control to
    bfloat16's, three orders apart, on the loss and on the first
    gradient."""
    from perfbench import control_tokens
    res = R.resolve(ROOT, "kanana2.train_packed4k")
    for part, patch in tiny().items():
        res[part].update(patch)
    both = control_tokens.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control.kanana2"))
    limits = {"loss_gap_step1": 1e-5, "first_grad_norm_gap": 1e-4,
              "update_norm_gap": 1e-4, "init_gap": 1e-6}
    assert control_tokens.fails(both["control"], limits), both["control"]
    assert not control_tokens.fails(both["sound"], limits), both["sound"]


def test_manifest_resolves_both_new_cells():
    dp4 = R.resolve(ROOT, "resnet50.train_raw_dp4")
    assert dp4["chips"] == 4 and dp4["traffic"]["encoding"] == "raw"
    assert dp4["config"]["per_device_batch"] == 64
    assert dp4["cell"]["limits"]["ingest_pixel_gap"] == 0
    # the same 1,024 records as the one-chip cell of the configuration
    one = R.resolve(ROOT, "resnet50.train_raw")
    assert (dp4["cell"]["records_per_global_batch"] * 4
            == one["cell"]["records_per_global_batch"])
    assert {k: v for k, v in dp4["traffic"].items() if k != "who"} \
        == {k: v for k, v in one["traffic"].items() if k != "who"}
    names = R.metric_names(dp4["manifest"], "per_layer",
                           "resnet50.train_raw_dp4")
    for name in ("dp.collective_ms.train", "dp.collective_exposed_ms.train",
                 "ingest.read_ms_per_img.train", "step.dispatch_ms.train",
                 "step.mfu_pct.train", "device.idle_pct.train"):
        assert name in names
    tok = R.resolve(ROOT, "kanana2.train_packed4k")
    assert tok["chips"] == 1 and tok["traffic"]["kind"] == "train_tokens"
    cfg = tok["config"]
    assert (cfg["per_device_batch"] * cfg["sequence_length"]) == 8192
    names = R.metric_names(tok["manifest"], "per_layer",
                           "kanana2.train_packed4k")
    for name in ("attn.device_ms.train", "moe.device_ms.train",
                 "update.device_ms.train", "moe.rows_max_over_mean.train",
                 "moe.dropped_assignments.train", "step.mfu_pct.train",
                 "device.idle_pct.train", "ingest.pack_ms_per_img.train"):
        assert name in names
    assert "dp.collective_ms.train" not in names
    # the configuration's own count of what it holds
    from perfbench.reference import kanana2_30b_a3b as model
    assert model.num_params(cfg) == 687_502_976
    assert 20.6e12 < 3 * model.forward_flops(cfg, 4096, 2) < 20.8e12
