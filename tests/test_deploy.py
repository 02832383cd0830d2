"""Continuous deployment (caffeonspark_tpu/deploy/): streaming
source, fine-tune rounds with bad-pair fallback, canary verdict
logic, chaos knob parsing, and the subprocess chaos drills (accept /
reject / canary-kill-aborted / mid-roll rollback — slow+chaos
markers, `make chaos-deploy`)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from caffeonspark_tpu import checkpoint
from caffeonspark_tpu.config import Config
from caffeonspark_tpu.data.streaming import (StreamingDirSource,
                                             append_stream_part,
                                             datum_records)
from caffeonspark_tpu.data.lmdb_io import LmdbWriter
from caffeonspark_tpu.data.source import get_source
from caffeonspark_tpu.data.synthetic import make_images
from caffeonspark_tpu.deploy import DeployController, FineTuner
from caffeonspark_tpu.deploy.canary import (ABORTED, ACCEPT, REJECT,
                                            decide_verdict,
                                            eval_outcome)
from caffeonspark_tpu.tools import chaos
from caffeonspark_tpu.tools.supervisor import pick_snapshot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET_TMPL = """
name: "deploynet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "StreamingDir"
  include {{ phase: TRAIN }}
  memory_data_param {{ source: "{stream}" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "data_test" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  include {{ phase: TEST }}
  memory_data_param {{ source: "{evaldb}" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 8 kernel_size: 5 stride: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "conv1" top: "ip1"
  inner_product_param {{ num_output: 32
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu2" type: "ReLU" bottom: "ip1" top: "ip1" }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}
"""

SOLVER_TMPL = """net: "{net}"
base_lr: 0.01
momentum: 0.9
lr_policy: "fixed"
display: 100
max_iter: 100000
snapshot_prefix: "deploy"
random_seed: 3
"""


def _make_job(tmp_path, n_seed=128, n_eval=64):
    """Stream dir (one seed part), eval LMDB, solver/net prototxts."""
    stream = str(tmp_path / "stream")
    evaldb = str(tmp_path / "eval_lmdb")
    out = str(tmp_path / "out")
    os.makedirs(out, exist_ok=True)
    imgs, labels = make_images(n_seed, seed=7)
    append_stream_part(stream, datum_records(imgs, labels))
    ev_imgs, ev_labels = make_images(n_eval, seed=99)
    LmdbWriter(evaldb).write(datum_records(ev_imgs, ev_labels))
    net = tmp_path / "net.prototxt"
    net.write_text(NET_TMPL.format(stream=stream, evaldb=evaldb))
    solver = tmp_path / "solver.prototxt"
    solver.write_text(SOLVER_TMPL.format(net=net))
    return str(solver), stream, out


def _conf(solver, out, extra=()):
    return Config(["-conf", solver, "-output", out,
                   "-features", "ip2", "-deploy", *extra])


def _grow(stream, n=64, seed=1000, start_id=100000):
    imgs, labels = make_images(n, seed=seed)
    return append_stream_part(stream,
                              datum_records(imgs, labels, start_id))


# ----------------------------------------------------- chaos knobs

def test_chaos_deploy_knob_parsing(monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_CANARY_KILL", f"5:{tmp_path}/ck")
    monkeypatch.setenv("COS_FAULT_SNAPSHOT_TRUNCATE",
                       f"{tmp_path}/st")
    monkeypatch.setenv("COS_FAULT_RELOAD_FAIL_RANK",
                       f"1:{tmp_path}/rf")
    plan = chaos.resolve()
    assert plan.active
    assert plan.canary_kill == (5, f"{tmp_path}/ck")
    assert plan.snapshot_truncate == f"{tmp_path}/st"
    assert plan.reload_fail_rank == (1, f"{tmp_path}/rf")
    d = plan.describe()
    assert d["canary_kill"] == {"after_requests": 5}
    assert d["snapshot_truncate"] is True
    assert d["reload_fail_rank"] == 1


def test_chaos_deploy_knob_validation(monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_CANARY_KILL", "-1:m")
    with pytest.raises(ValueError):
        chaos.resolve()
    monkeypatch.setenv("COS_FAULT_CANARY_KILL", "5:")
    with pytest.raises(ValueError):
        chaos.resolve()


def test_chaos_canary_kill_one_shot(monkeypatch, tmp_path):
    marker = str(tmp_path / "ck.marker")
    monkeypatch.setenv("COS_FAULT_CANARY_KILL", f"3:{marker}")
    inj = chaos.make_injector()
    assert not inj.canary_kill_due(0)
    assert not inj.canary_kill_due(2)
    assert inj.canary_kill_due(3)            # fires exactly once
    assert os.path.exists(marker)
    assert not inj.canary_kill_due(10)       # marker suppresses
    assert chaos.make_injector().canary_kill_due(10) is False


def test_chaos_truncate_snapshot_one_shot(monkeypatch, tmp_path):
    marker = str(tmp_path / "st.marker")
    monkeypatch.setenv("COS_FAULT_SNAPSHOT_TRUNCATE", marker)
    f1 = tmp_path / "m.caffemodel"
    f1.write_bytes(b"x" * 300)
    f2 = tmp_path / "m.solverstate"
    f2.write_bytes(b"y" * 90)
    inj = chaos.make_injector()
    assert inj.truncate_snapshot(str(f1), str(f2))
    assert f1.stat().st_size == 100 and f2.stat().st_size == 30
    f1.write_bytes(b"x" * 300)
    assert not inj.truncate_snapshot(str(f1))   # one-shot
    assert f1.stat().st_size == 300


def test_chaos_reload_fail_rank_one_shot(monkeypatch, tmp_path):
    marker = str(tmp_path / "rf.marker")
    monkeypatch.setenv("COS_FAULT_RELOAD_FAIL_RANK", f"1:{marker}")
    inj = chaos.make_injector()
    assert not inj.reload_fail_due(0)
    assert inj.reload_fail_due(1)
    assert not inj.reload_fail_due(1)


# ----------------------------------------------------- streaming source

def _stream_source(stream):
    from caffeonspark_tpu.proto.caffe import LayerParameter
    lp = LayerParameter.from_text(f'''
        name: "data" type: "MemoryData" top: "data" top: "label"
        source_class: "StreamingDir"
        memory_data_param {{ source: "{stream}" batch_size: 4
          channels: 1 height: 28 width: 28 }}''')
    return get_source(lp, phase_train=True, rank=0, num_ranks=1)


def test_streaming_source_follows_growth(tmp_path):
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(12, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    src = _stream_source(stream)
    assert isinstance(src, StreamingDirSource)
    assert src.part_count == 1 and src.total_records == 12
    assert len(list(src.records())) == 12
    # growth is invisible until a poll absorbs it
    _grow(stream, 8, seed=1)
    assert src.total_records == 12
    assert src.poll() == 8
    assert src.total_records == 20
    recs = list(src.records())
    assert len(recs) == 20
    # epoch = data seen so far: the shuffled pass covers everything
    shuffled = list(src.shuffled_records(epoch=3))
    assert sorted(r[0] for r in shuffled) == sorted(r[0] for r in recs)


def test_streaming_ignores_uncommitted_parts(tmp_path):
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(6, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    # an in-flight writer's temp dir and an underscore marker must
    # not be absorbed (the rename-commit contract)
    os.makedirs(os.path.join(stream, ".tmp-part-xyz-1"))
    open(os.path.join(stream, "_SUCCESS"), "w").close()
    src = _stream_source(stream)
    assert src.part_count == 1 and src.total_records == 6


def test_streaming_wait_for_records_times_out(tmp_path):
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(4, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    src = _stream_source(stream)
    t0 = time.monotonic()
    got = src.wait_for_records(1, timeout_s=0.3)
    assert got == 0                    # nothing new, bounded wait
    assert time.monotonic() - t0 < 5.0


def test_streaming_poll_absorbs_flaky_storage(tmp_path):
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(4, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    src = _stream_source(stream)
    _grow(stream, 4, seed=1)

    class _FlakyInjector:
        """First 3 listings raise — the bounded re-poll must absorb."""
        def __init__(self):
            self.calls = 0

        def storage_fault(self):
            self.calls += 1
            if self.calls <= 3:
                raise OSError("injected flaky storage")

    inj = _FlakyInjector()
    assert src.poll(injector=inj) == 4       # absorbed within one poll
    assert src.poll_faults == 3


def test_streaming_poll_keeps_counts_across_mid_loop_fault(
        tmp_path, monkeypatch):
    """A fault that lands AFTER some parts were already absorbed in
    the same poll() must not lose their record count — the fine-tune
    trigger's min_new growth check reads the return value."""
    from caffeonspark_tpu.data import streaming as streaming_mod
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(4, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    src = _stream_source(stream)
    _grow(stream, 5, seed=1)                      # part-00001
    _grow(stream, 7, seed=2, start_id=200000)     # part-00002

    real_part = streaming_mod._Part
    fired = []

    class _FaultOnPart2(real_part):
        def __init__(self, path):
            if path.endswith("part-00002") and not fired:
                fired.append(path)
                raise OSError("injected mid-poll storage fault")
            super().__init__(path)

    monkeypatch.setattr(streaming_mod, "_Part", _FaultOnPart2)
    # ONE poll: part-00001 (5 recs) absorbs, part-00002 faults once,
    # the in-call retry re-lists and absorbs it — the return value
    # must carry BOTH parts' records
    assert src.poll() == 12
    assert fired and src.total_records == 16


def test_finetuner_trains_when_stream_smaller_than_batch(tmp_path):
    """batch_size 8 but only 3 records visible: the batch buffer
    carries across reshuffled passes instead of spinning forever."""
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    small = str(tmp_path / "small_stream")
    imgs, labels = make_images(3, seed=0)
    append_stream_part(small, datum_records(imgs, labels))
    conf = _conf(solver, out)
    src = _stream_source(small)      # batch_size 4 in the test layer
    ft = FineTuner(conf, src, str(tmp_path / "small_out"), steps=2)
    r = ft.round()
    assert r.end_iter == 2 and os.path.exists(r.model_path)


def test_streaming_quarantines_unreadable_entry(tmp_path):
    """One permanently unreadable committed entry must not block the
    parts sorted after it: it collects strikes, is quarantined, and
    later parts keep absorbing."""
    stream = str(tmp_path / "stream")
    imgs, labels = make_images(4, seed=0)
    append_stream_part(stream, datum_records(imgs, labels))
    src = _stream_source(stream)
    # a stray committed non-part file that sorts BEFORE the next part
    with open(os.path.join(stream, "manifest.json"), "w") as f:
        f.write("{}")
    _grow(stream, 6, seed=1)                 # part-00001 sorts after
    assert src.poll() == 6                   # absorbed despite the junk
    assert src.total_records == 10
    assert "manifest.json" in src.describe().get("quarantined", [])
    # quarantine is sticky: later polls skip it without strikes
    faults_before = src.poll_faults
    _grow(stream, 3, seed=2, start_id=300000)
    assert src.poll() == 3
    assert src.poll_faults == faults_before


def test_append_part_names_sequence(tmp_path):
    stream = str(tmp_path / "s")
    imgs, labels = make_images(2, seed=0)
    p0 = append_stream_part(stream, datum_records(imgs, labels))
    p1 = append_stream_part(stream, datum_records(imgs, labels, 2))
    assert os.path.basename(p0) == "part-00000"
    assert os.path.basename(p1) == "part-00001"


# ----------------------------------------------------- verdict logic

def test_decide_verdict_matrix():
    kw = dict(acc_tol=0.02, p99_ratio=2.0, p99_slack_ms=10.0)
    assert decide_verdict(0.9, 5.0, 0.9, 5.0, **kw)[0] == ACCEPT
    assert decide_verdict(0.89, 5.0, 0.9, 5.0, **kw)[0] == ACCEPT
    v, reason = decide_verdict(0.8, 5.0, 0.9, 5.0, **kw)
    assert v == REJECT and "accuracy" in reason
    v, reason = decide_verdict(0.95, 25.0, 0.9, 5.0, **kw)
    assert v == REJECT and "p99" in reason
    # bootstrap: no incumbent numbers = accept
    assert decide_verdict(0.5, 5.0, None, None, **kw)[0] == ACCEPT
    # no latency numbers: accuracy alone decides
    assert decide_verdict(0.9, None, 0.9, 5.0, **kw)[0] == ACCEPT


def test_eval_outcome_argmax():
    rows = [[0.1, 0.9], [0.8, 0.2], [0.3, 0.7], [0.6, 0.4]]
    assert eval_outcome(rows, [1, 0, 1, 1]) == 0.75


# ----------------------------------------------------- fine-tuner

def test_finetuner_rounds_resume_lineage(tmp_path):
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    conf = _conf(solver, out)
    src = _stream_source(stream)
    ft = FineTuner(conf, src, out, steps=4)
    r0 = ft.round()
    assert r0.start_iter == 0 and r0.end_iter == 4
    assert r0.resumed_from is None
    assert os.path.exists(r0.model_path)
    assert os.path.exists(r0.state_path)
    r1 = ft.round()
    assert r1.start_iter == 4 and r1.end_iter == 8
    assert r1.resumed_from == r0.state_path
    assert r1.mean_loss == r1.mean_loss      # finite


def test_finetuner_bad_pair_fallback(tmp_path):
    """A truncated newest pair is marked bad on the spot and the
    previous pair seeds the round — pick_snapshot fallback, in
    process."""
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    conf = _conf(solver, out)
    ft = FineTuner(conf, _stream_source(stream), out, steps=4)
    r0 = ft.round()
    r1 = ft.round()
    # corrupt the NEWEST pair the way flaky storage would
    with open(r1.model_path, "r+b") as f:
        f.truncate(50)
    with open(r1.state_path, "r+b") as f:
        f.truncate(20)
    r2 = ft.round()
    assert r2.skipped_pairs == 1
    assert r2.resumed_from == r0.state_path
    assert r1.state_path in ft.bad
    # supervisor-side view agrees: pick_snapshot skips the bad pair
    assert pick_snapshot(out, ft.prefix, frozenset(ft.bad)) is not None


def test_finetuner_mark_bad_skips_rejected_candidate(tmp_path):
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    conf = _conf(solver, out)
    ft = FineTuner(conf, _stream_source(stream), out, steps=4)
    r0 = ft.round()
    r1 = ft.round(label_shuffle=True)
    assert r1.label_shuffled
    ft.mark_bad(r1.state_path)               # the gate rejected it
    r2 = ft.round()
    assert r2.resumed_from == r0.state_path  # incumbent lineage


def test_finetuner_rejected_round_never_overwrites_snapshots(tmp_path):
    """After a reject, the next round resumes from the OLDER pair but
    fast-forwards its clock past every iteration already written —
    snapshot paths stay unique, the published incumbent's file is
    never overwritten by an unjudged candidate, and the iteration
    counter keeps advancing instead of wedging."""
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    conf = _conf(solver, out)
    ft = FineTuner(conf, _stream_source(stream), out, steps=4)
    r0 = ft.round()                          # iters 0-4 (incumbent)
    r1 = ft.round()                          # iters 4-8 (candidate)
    ft.mark_bad(r1.state_path)               # the gate rejected r1
    incumbent_bytes = open(r0.model_path, "rb").read()
    rejected_bytes = open(r1.model_path, "rb").read()
    r2 = ft.round()
    assert r2.resumed_from == r0.state_path
    assert r2.start_iter == 8 and r2.end_iter == 12   # clock advanced
    assert r2.model_path not in (r0.model_path, r1.model_path)
    # neither existing pair was overwritten
    assert open(r0.model_path, "rb").read() == incumbent_bytes
    assert open(r1.model_path, "rb").read() == rejected_bytes
    r3 = ft.round()                          # lineage keeps moving
    assert r3.start_iter == 12
    assert r3.resumed_from == r2.state_path


def test_finetuner_iter_floor_survives_restart(tmp_path):
    """A FRESH FineTuner over an existing output dir seeds its clock
    from the newest pair on disk — a restarted controller that falls
    back past a bad pair still cannot overwrite it."""
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    conf = _conf(solver, out)
    ft = FineTuner(conf, _stream_source(stream), out, steps=4)
    ft.round()
    r1 = ft.round()                          # iter 8 pair on disk
    ft2 = FineTuner(conf, _stream_source(stream), out, steps=4)
    ft2.mark_bad(r1.state_path)              # fall back past newest
    r2 = ft2.round()
    assert r2.start_iter == 8 and r2.end_iter == 12


def test_finetuner_truncate_injection(tmp_path, monkeypatch):
    solver, stream, out = _make_job(tmp_path, n_seed=64)
    marker = str(tmp_path / "st.marker")
    monkeypatch.setenv("COS_FAULT_SNAPSHOT_TRUNCATE", marker)
    conf = _conf(solver, out)
    ft = FineTuner(conf, _stream_source(stream), out, steps=4)
    r0 = ft.round(injector=chaos.make_injector())
    assert r0.truncated and os.path.exists(marker)
    with pytest.raises(Exception):
        checkpoint.load_caffemodel_blobs(r0.model_path)


# ----------------------------------------------------- config / CLI

def test_config_deploy_validation(tmp_path):
    solver, stream, out = _make_job(tmp_path, n_seed=4)
    _conf(solver, out).validate()            # well-formed passes
    with pytest.raises(ValueError, match="-features"):
        Config(["-conf", solver, "-output", out,
                "-deploy"]).validate()
    with pytest.raises(ValueError, match="-output"):
        Config(["-conf", solver, "-features", "ip2",
                "-deploy"]).validate()
    with pytest.raises(ValueError, match="-conf"):
        Config(["-deploy", "-output", out,
                "-features", "ip2"]).validate()


def test_controller_requires_streaming_source(tmp_path):
    solver, stream, out = _make_job(tmp_path, n_seed=8)
    conf = _conf(solver, out)
    lmdb_src = get_source(conf.test_data_layer(), phase_train=True,
                          rank=0, num_ranks=1)
    with pytest.raises(ValueError, match="streaming source"):
        DeployController(conf, stream_source=lmdb_src)


# ----------------------------------------------------- subprocess drills

def _procs_serving(needle: str):
    """PIDs of live -serve processes whose cmdline mentions needle."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().decode(errors="replace")
        except OSError:
            continue
        if "-serve" in cmd and needle in cmd:
            out.append(int(pid))
    return out


class _LoadThread:
    """Background client load through the LIVE fleet router — the
    drills pin its failure count at zero."""

    def __init__(self, router, payload):
        self.router = router
        self.payload = payload
        self.ok = 0
        self.failures = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                self.router.predict(self.payload)
                self.ok += 1
            except Exception:     # noqa: BLE001 — counted
                self.failures += 1
            time.sleep(0.05)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *a):
        self._stop.set()
        self._t.join(timeout=10)


def _controller(tmp_path, solver, out, replicas=1, steps=20,
                monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv("COS_AOT_CACHE_DIR",
                           str(tmp_path / "aot"))
        monkeypatch.setenv("COS_DEPLOY_POLL_S", "5")
        monkeypatch.setenv("COS_DEPLOY_EVAL_N", "48")
        monkeypatch.setenv("COS_TRANSFORM_THREADS", "0")
    conf = _conf(solver, out)
    conf.validate()
    return DeployController(conf, replicas=replicas, steps=steps)


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_accept_then_reject(tmp_path, monkeypatch):
    """E2E: growth → fine-tune → canary accepts → rolling reload
    publishes (zero failed client requests); a label-shuffled round
    is rejected — fleet argv/incumbent unchanged, canary reaped."""
    solver, stream, out = _make_job(tmp_path, n_seed=192)
    ctl = _controller(tmp_path, solver, out, replicas=1, steps=30,
                      monkeypatch=monkeypatch)
    ctl.start()
    try:
        payload = ctl.eval_records[0][0]
        with _LoadThread(ctl.fleet.router, payload) as load:
            incumbent0 = ctl.incumbent
            _grow(stream, 96, seed=1)
            r0 = ctl.run_round()
            assert r0["verdict"] == ACCEPT, r0
            assert ctl.incumbent != incumbent0
            accepted = ctl.incumbent
            # respawn args follow the published version
            rep = ctl.fleet.replicas["replica0"]
            assert accepted in rep.serve_args
            _grow(stream, 96, seed=2, start_id=200000)
            r1 = ctl.run_round(label_shuffle=True)
            assert r1["verdict"] == REJECT, r1
            assert ctl.incumbent == accepted          # untouched
            assert accepted in rep.serve_args
            cand = r1["canary"]["model_path"]
            # the rejected candidate's canary process is reaped
            assert _procs_serving(cand) == []
            # a rejected candidate never seeds the next resume
            assert r1["finetune"]["resumed_from"] is not None
        assert load.failures == 0 and load.ok > 0
        assert ctl.mirror_failures == 0
        info = ctl.metrics.summary()["info"]["deploy"]
        assert info["counts"][ACCEPT] == 1
        assert info["counts"][REJECT] == 1
    finally:
        ctl.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_canary_kill_aborts_incumbent_untouched(tmp_path,
                                                      monkeypatch):
    """SIGKILL the canary mid-eval (COS_FAULT_CANARY_KILL): verdict
    `aborted`, incumbent untouched, zero failed client requests on
    the live fleet."""
    solver, stream, out = _make_job(tmp_path, n_seed=192)
    ctl = _controller(tmp_path, solver, out, replicas=1, steps=20,
                      monkeypatch=monkeypatch)
    ctl.start()
    try:
        monkeypatch.setenv("COS_FAULT_CANARY_KILL",
                           f"5:{tmp_path}/ck.marker")
        ctl.refresh_faults()
        incumbent0 = ctl.incumbent
        payload = ctl.eval_records[0][0]
        with _LoadThread(ctl.fleet.router, payload) as load:
            _grow(stream, 64, seed=3)
            r = ctl.run_round()
        assert r["verdict"] == ABORTED, r
        assert "died mid-eval" in r["reason"]
        assert ctl.incumbent == incumbent0
        assert load.failures == 0 and load.ok > 0
        assert ctl.mirror_failures == 0
        assert ctl.metrics.summary()["info"]["faults"]["canary_kill"] \
            == {"after_requests": 5}
    finally:
        ctl.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_truncated_snapshot_aborts_then_falls_back(tmp_path,
                                                         monkeypatch):
    """COS_FAULT_SNAPSHOT_TRUNCATE corrupts the candidate pair after
    the write: the canary refuses to load it (aborted), and the NEXT
    round's resume marks the pair bad and falls back to the incumbent
    lineage (pick_snapshot posture, in-process)."""
    solver, stream, out = _make_job(tmp_path, n_seed=192)
    ctl = _controller(tmp_path, solver, out, replicas=1, steps=20,
                      monkeypatch=monkeypatch)
    ctl.start()
    try:
        monkeypatch.setenv("COS_FAULT_SNAPSHOT_TRUNCATE",
                           f"{tmp_path}/st.marker")
        ctl.refresh_faults()
        incumbent0 = ctl.incumbent
        _grow(stream, 64, seed=4)
        r = ctl.run_round()
        assert r["verdict"] == ABORTED, r
        assert r["finetune"]["truncated"]
        assert ctl.incumbent == incumbent0
        # next round: resume skips the truncated pair
        monkeypatch.delenv("COS_FAULT_SNAPSHOT_TRUNCATE")
        ctl.refresh_faults()
        _grow(stream, 64, seed=5, start_id=300000)
        r2 = ctl.run_round()
        assert r2["verdict"] in (ACCEPT, REJECT)
        assert r2["finetune"]["resumed_from"] != \
            r["canary"]["model_path"].replace(".caffemodel",
                                              ".solverstate")
    finally:
        ctl.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_mid_roll_failure_rolls_back(tmp_path, monkeypatch):
    """COS_FAULT_RELOAD_FAIL_RANK kills replica 1 mid-roll after
    replica 0 swapped: the roll aborts, rollback() re-rolls replica 0
    back to the incumbent, the killed replica's respawn args follow
    the roll's FINAL verdict (incumbent, not the abandoned candidate),
    and the live fleet keeps answering byte-identically."""
    solver, stream, out = _make_job(tmp_path, n_seed=192)
    ctl = _controller(tmp_path, solver, out, replicas=2, steps=20,
                      monkeypatch=monkeypatch)
    ctl.start()
    try:
        incumbent0 = ctl.incumbent
        baseline = ctl.fleet.router.predict(ctl.eval_records[0][0])
        monkeypatch.setenv("COS_FAULT_RELOAD_FAIL_RANK",
                           f"1:{tmp_path}/rf.marker")
        ctl.refresh_faults()
        payload = ctl.eval_records[1][0]
        with _LoadThread(ctl.fleet.router, payload) as load:
            _grow(stream, 96, seed=6)
            r = ctl.run_round()
        assert r["verdict"] == "rolled_back", r
        assert r["canary"]["verdict"] == ACCEPT    # gate said yes...
        assert ctl.incumbent == incumbent0         # ...roll failed
        # EVERY replica's respawn args follow the final verdict
        cand = r["canary"]["model_path"]
        for rep in ctl.fleet.replicas.values():
            assert incumbent0 in rep.serve_args
            assert cand not in rep.serve_args
        assert load.failures == 0
        assert ctl.mirror_failures == 0
        # the incumbent still answers byte-identically
        after = ctl.fleet.router.predict(ctl.eval_records[0][0])
        assert after["rows"] == baseline["rows"]
        info = ctl.metrics.summary()["info"]["deploy"]
        assert info["counts"]["rolled_back"] == 1
    finally:
        ctl.stop()


# ----------------------------------------------------- -deploy CLI

@pytest.mark.slow
@pytest.mark.chaos
def test_deploy_cli_runs_rounds(tmp_path):
    solver, stream, out = _make_job(tmp_path, n_seed=192)
    _grow(stream, 64, seed=8)
    metrics_path = str(tmp_path / "deploy_metrics.json")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "COS_TRANSFORM_THREADS": "0",
           "COS_AOT_CACHE_DIR": str(tmp_path / "aot"),
           "COS_DEPLOY_ROUNDS": "1", "COS_DEPLOY_STEPS": "10",
           "COS_DEPLOY_POLL_S": "5", "COS_DEPLOY_EVAL_N": "32",
           "COS_SERVE_METRICS": metrics_path,
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    p = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.caffe_on_spark",
         "-deploy", "-conf", solver, "-output", out,
         "-features", "ip2"],
        capture_output=True, text=True, env=env, cwd=REPO,
        timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[0]["deploying"] is True
    rounds = [ln for ln in lines if "deploy_round" in ln]
    assert len(rounds) == 1
    assert rounds[0]["verdict"] in (ACCEPT, REJECT, "skipped")
    with open(metrics_path) as f:
        dumped = json.load(f)
    assert "deploy" in dumped["info"]
    assert dumped["info"]["deploy"]["rounds"] == 1
