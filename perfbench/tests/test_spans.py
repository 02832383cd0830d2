"""The program's own spans (`cos.*`) and series, as the benchmark reads
them: the loader and the shared-clock metric over a trace recorded on the
chip, and every new reader through a traced CPU rehearsal."""

import json
import os
import shutil

import pytest

from conftest import ROOT
from perfbench import run as R
from perfbench.harness import spans as S
from perfbench.harness import trace as tr
from test_rehearsal import tiny

COS_SMALL = os.path.join(os.path.dirname(__file__), "data",
                         "cos_small.xplane.pb")
NEW = ["ingest.read_ms_per_img.train", "ingest.read_blocked_pct.train",
       "ingest.pack_starved_pct.train",
       "ingest.pack_decode_ms_per_img.train",
       "ingest.pack_transform_ms_per_img.train",
       "ingest.pack_cpu_pct.train", "ingest.stage_ms_per_batch.train",
       "step.dispatch_ms.train", "device.idle_in_queue_wait_pct.train",
       "setup.compile_s.train", "setup.cache_load_s.train",
       "setup.init_params_s.train"]


def test_manifest_lists_the_new_readers_for_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        # both one-chip CNN cells, and of the four-chip cell those that say
        # what one process feeding four chips costs
        assert per_layer[name]["workloads"][:2] == [
            "caffenet.train_jpeg", "resnet50.train_raw"], name
        assert per_layer[name]["workloads"][2:] in (
            [], ["resnet50.train_raw_dp4"]), name
    assert per_layer["device.idle_in_queue_wait_pct.train"][
        "source"] == "program_span"


def test_idle_share_inside_spans():
    busy = [(1.0, 2.0), (5.0, 6.0)]                  # idle: 0-1, 2-5, 6-8
    spans = [("queue_wait", "t#0", 2.0, 4.0, {"n": 0}),
             ("queue_wait", "t#0", 6.5, 9.0, {"n": 1}),
             ("step", "t#0", 4.0, 4.5, {"it": 0})]
    assert S.intervals(spans, "queue_wait") == [(2.0, 4.0), (6.5, 9.0)]
    share = S.idle_share_inside(busy, (0.0, 8.0), spans, "queue_wait")
    assert share == pytest.approx((2.0 + 1.5) / 6.0)
    assert S.idle_share_inside(busy, (0.0, 8.0), spans, "stage") is None
    assert S.idle_share_inside([(0.0, 8.0)], (0.0, 8.0), spans,
                               "queue_wait") is None


def test_series_between_the_edges():
    from perfbench.harness import series
    run = {"pipeline": ({"pack": (1.0, 2), "read": (0.1, 2)},
                        {"pack": (4.0, 5), "read": (0.4, 5)})}
    assert series.delta(run, "pack") == (3.0, 3)
    assert series.delta(run, "read_blocked") is None
    assert series.delta(run, "read_blocked", witness="read") == (0.0, 0)
    assert series.at_b(run, "pack") == (4.0, 5)
    assert series.delta({"pipeline": ({}, {})}, "pack",
                        witness="read") is None        # the parent


def test_traced_rehearsal_reports_every_new_metric_but_the_device_one():
    res = R.run_cell(ROOT, "caffenet.train_jpeg", 2147483999, 1.0, True,
                     overrides=tiny(67), device=None)
    assert res["correct"] is True and res["rehearsal"] is True
    for name in NEW:
        if name.startswith("device."):
            assert name not in res["metrics"]     # no device on the CPU
        else:
            assert name in res["metrics"], name
    assert all(v is None for v in res["metrics"].values())


@pytest.mark.skipif(not os.path.exists(COS_SMALL),
                    reason="no recorded trace")
def test_spans_and_device_ops_of_a_chip_trace_share_a_clock(tmp_path):
    """Recorded on a TPU v5e by tools/record_cos_trace.py: about a second
    of a small -train job, device ops and the program's `cos.*` spans."""
    spans = S.load(COS_SMALL)
    names = {s[0] for s in spans}
    for name in ("pack", "pack_decode", "pack_transform", "stage",
                 "stage_put", "queue_wait", "step"):
        assert name in names, (name, sorted(names))
    trace = tr.load(COS_SMALL)
    ops = tr.op_events(trace["devices"]["/device:TPU:0"])
    d_lo, d_hi = min(e[1] for e in ops), max(e[2] for e in ops)
    steps = [s for s in spans if s[0] == "step"]
    s_lo, s_hi = min(s[2] for s in steps), max(s[3] for s in steps)
    # one clock: the job is held to a dispatch every 40 ms and more, and
    # the device's work for each begins while or just after the solver
    # thread enqueues it, never before
    assert max(d_lo, s_lo) < min(d_hi, s_hi)
    starts = sorted(e[1] for e in ops)
    for _, _, begin, end, _ in steps:
        assert any(begin <= t <= end + 0.02 for t in starts), (begin, end)
    # the metric's own reader, over the capture laid out as a run's trace
    run_dir = tmp_path / "plugins" / "profile" / "recorded"
    run_dir.mkdir(parents=True)
    shutil.copy(COS_SMALL, run_dir / "cos_small.xplane.pb")
    run = {"trace": tr.reduce(trace, window=(s_lo, s_hi)),
           "trace_dir": str(tmp_path)}
    share = R.read_metric("device.idle_in_queue_wait_pct.train", run)
    assert share is not None and 0.0 <= share <= 100.0
    # the job is held to 25 steps a second by a sleep between dispatches:
    # the device idles, and mostly outside the waits for a batch
    assert R.read_metric("device.idle_pct.train", run) > 50
    run["trace_dir"] = None
    assert R.read_metric("device.idle_in_queue_wait_pct.train",
                         run) is None
