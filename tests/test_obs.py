"""Observability layer (caffeonspark_tpu/obs): distributed tracing,
flight recorder, Prometheus exposition, profiler capture, and the
periodic metrics flush.

The pins that matter:
  * COS_TRACE_SAMPLE=0 is INERT — the span API returns the null span
    and nothing lands in the ring (the serving hot path is
    byte-identical with tracing off);
  * e2e trace propagation client → router → 2 replicas → forward:
    every span's parent exists in the trace, the router's spans cover
    >= 95% of the client-observed wall, and a RETRIED request is one
    trace with N attempt spans;
  * prom exposition round-trips the validity parser, never emits a
    duplicate family, and counters are monotonic across scrapes;
  * a SIGTERMed -serve replica under load leaves a valid
    flight-recorder artifact (drill, slow);
  * a SIGKILLed training run leaves <output>/metrics.json no older
    than COS_METRICS_FLUSH_S (drill, slow).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from caffeonspark_tpu import checkpoint
from caffeonspark_tpu.config import Config
from caffeonspark_tpu.metrics import (MetricsFlusher, PipelineMetrics,
                                      metrics_flush_s)
from caffeonspark_tpu.obs.prom import (counter_values,
                                       parse_exposition,
                                       render_summary)
from caffeonspark_tpu.obs.recorder import (FlightRecorder,
                                           get_recorder)
from caffeonspark_tpu.obs.trace import (TRACE_HEADER, Tracer,
                                        get_tracer, parse_header)
from caffeonspark_tpu.proto import NetParameter, SolverParameter
from caffeonspark_tpu.serving import (InferenceService, Router,
                                      RouterHTTPServer,
                                      ServingHTTPServer)
from caffeonspark_tpu.solver import Solver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET_TMPL = """
name: "tiny"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{root}/unused_lmdb" batch_size: 8
    channels: 1 height: 12 width: 12 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}
"""

SOLVER_TMPL = """
net: "{net}"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 5
random_seed: 5
"""


@pytest.fixture()
def tiny_model(tmp_path):
    net_path = tmp_path / "net.prototxt"
    net_path.write_text(NET_TMPL.format(root=tmp_path))
    solver_path = tmp_path / "solver.prototxt"
    solver_path.write_text(SOLVER_TMPL.format(net=net_path))
    s = Solver(SolverParameter.from_text(
        SOLVER_TMPL.format(net=net_path)),
        NetParameter.from_text(NET_TMPL.format(root=tmp_path)))
    params, _ = s.init()
    model = str(tmp_path / "m.caffemodel")
    checkpoint.save_caffemodel(model, s.train_net, params)
    return str(solver_path), model


@pytest.fixture()
def sampled_tracer(tmp_path):
    """The process tracer flipped to sample=1.0 for the test, restored
    after (the serving/router modules all hold the singleton)."""
    t = get_tracer("test")
    old_sample, old_spool = t.sample, t.spool_dir
    t.reconfigure(sample=1.0, spool_dir=str(tmp_path / "spool"))
    yield t
    t.reconfigure(sample=old_sample, spool_dir=old_spool)


def _post_json(url, payload, headers=None):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=hdrs)
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _record(seed=0):
    return {"id": f"r{seed}",
            "data": np.random.RandomState(seed)
            .rand(1, 12, 12).astype(np.float32).tolist()}


# ===================================================================== units

def test_tracer_inert_by_default():
    """A fresh tracer at sample 0 (the COS_TRACE_SAMPLE default): no
    root draw, no spans recorded, the null span propagates None —
    the hot path's inertness contract."""
    t = Tracer("inert", sample=0.0, spool_dir="")
    assert not t.enabled()
    assert t.sample_root() is False
    with t.span("a", root=t.sample_root()) as sp:
        assert not sp
        assert sp.ctx is None
        assert sp.header() is None
        with t.span("b") as child:      # no parent, no root -> null
            assert not child
    assert t.recent() == []
    t.record_span("x", None, 0.5)       # parent None -> no-op
    assert t.recent() == []


def test_tracer_parentage_and_header():
    t = Tracer("unit", sample=1.0, spool_dir="")
    with t.span("root", root=True) as root:
        hdr = root.header()
        with t.span("child") as c:       # parent from thread-local
            c.set("k", "v")
    ctx = parse_header(hdr)
    assert ctx is not None and ctx.span_id == root.ctx.span_id
    spans = t.recent()
    assert [s["name"] for s in spans] == ["child", "root"]
    child, rootrec = spans
    assert child["trace_id"] == rootrec["trace_id"]
    assert child["parent_id"] == rootrec["span_id"]
    assert rootrec["parent_id"] is None
    assert child["attrs"] == {"k": "v"}
    # garbage headers never raise
    assert parse_header(None) is None
    assert parse_header("") is None
    assert parse_header("nocolon") is None
    assert parse_header("a:b:c") is None


def test_tracer_cross_thread_activation():
    """The batcher idiom: a request's ctx carried to another thread,
    activated there so spans nest under it."""
    t = Tracer("xthread", sample=1.0, spool_dir="")
    with t.span("req", root=True) as sp:
        ctx = sp.ctx

    def work():
        with t.activate(ctx):
            with t.span("inner"):
                pass

    th = threading.Thread(target=work)
    th.start()
    th.join()
    inner = [s for s in t.recent() if s["name"] == "inner"][0]
    assert inner["parent_id"] == ctx.span_id
    assert inner["trace_id"] == ctx.trace_id


def test_tracer_record_span_backdates():
    t = Tracer("back", sample=1.0, spool_dir="")
    with t.span("root", root=True) as sp:
        ctx = sp.ctx
    t.record_span("waited", ctx, 0.25, bucket=8)
    rec = [s for s in t.recent() if s["name"] == "waited"][0]
    assert rec["dur_ms"] == pytest.approx(250.0)
    assert rec["attrs"]["bucket"] == 8
    assert rec["ts"] <= time.time() - 0.2


def test_tracer_spool_jsonl(tmp_path):
    t = Tracer("spool", sample=1.0, spool_dir=str(tmp_path))
    for i in range(3):
        with t.span(f"s{i}", root=True):
            pass
    path = t.flush_spool()
    assert path and os.path.exists(path)
    lines = [json.loads(ln) for ln in open(path)]
    assert [r["name"] for r in lines] == ["s0", "s1", "s2"]


def test_recorder_ring_bounds_and_dump(tmp_path):
    r = FlightRecorder(capacity=4)
    for i in range(10):
        r.record("unit", "tick", i=i)
    ev = r.events()
    assert len(ev) == 4
    assert [e["i"] for e in ev] == [6, 7, 8, 9]     # oldest dropped
    assert ev[0]["seq"] == 7                        # seq keeps counting
    path = r.dump(str(tmp_path / "rec.json"), reason="unit")
    doc = json.load(open(path))
    assert doc["schema"] == "cos-flight-recorder-v1"
    assert doc["reason"] == "unit"
    assert doc["dropped"] == 6
    assert [e["event"] for e in doc["events"]] == ["tick"] * 4


def test_recorder_disabled():
    r = FlightRecorder(capacity=0)
    assert not r.enabled
    r.record("unit", "tick")
    assert r.events() == []


def test_router_state_transitions_recorded():
    """The drill's key property in unit form: the router's recorder
    timeline carries the drain/down transitions it observed."""
    router = Router({"obs_unit_r0": "http://127.0.0.1:1"})
    router.set_state("obs_unit_r0", "ok")
    router.set_state("obs_unit_r0", "draining")
    router.set_state("obs_unit_r0", "down")
    ev = [e for e in get_recorder().events()
          if e["source"] == "router"
          and e.get("replica") == "obs_unit_r0"]
    states = [e["state"] for e in ev if e["event"] == "state"]
    assert states == ["ok", "draining", "down"]


# ===================================================================== prom

def _sample_metrics():
    m = PipelineMetrics()
    for v in (0.01, 0.02, 0.05):
        m.add("latency", v)
    m.incr("served_rows", 12)
    m.incr("flush_bucket_8", 2)
    m.gauge("queue_depth", 3)
    m.mark_step(4)
    m.set_info("comm", {"mode": "default"})
    return m


def test_prom_render_roundtrips_validity_parser():
    text = render_summary(_sample_metrics().summary(),
                          {"role": "replica"})
    fams = parse_exposition(text)
    assert "cos_served_rows_total" in fams
    assert fams["cos_served_rows_total"]["type"] == "counter"
    (labels, value), = fams["cos_served_rows_total"]["samples"]
    assert labels == {"role": "replica"} and value == 12
    lat = [s for s in fams["cos_stage_ms"]["samples"]
           if s[0].get("stage") == "latency"
           and s[0].get("quantile") == "0.99"]
    assert len(lat) == 1 and lat[0][1] > 0
    # counter family names end in _total (the convention scrapers
    # and recording rules assume)
    for name, fam in fams.items():
        if fam["type"] == "counter":
            assert name.endswith("_total"), name


def test_prom_no_duplicate_families_when_merging():
    """The router's fleet aggregation: N summaries into one writer —
    one family header each, N labeled samples."""
    from caffeonspark_tpu.obs.prom import PromWriter
    w = PromWriter()
    for name in ("replica0", "replica1"):
        w.add_summary(_sample_metrics().summary(), {"replica": name})
    text = w.render()
    fams = parse_exposition(text)           # raises on duplicates
    assert len(fams["cos_served_rows_total"]["samples"]) == 2
    assert text.count("# TYPE cos_served_rows_total") == 1


def test_prom_validity_parser_rejects_garbage():
    with pytest.raises(ValueError, match="duplicate TYPE"):
        parse_exposition("# TYPE cos_x counter\n"
                         "# TYPE cos_x counter\ncos_x 1\n")
    with pytest.raises(ValueError, match="undeclared"):
        parse_exposition("cos_never_declared 1\n")
    with pytest.raises(ValueError, match="unparseable"):
        parse_exposition("# TYPE cos_x counter\ncos_x one\n")


def test_prom_counters_monotonic_across_scrapes():
    m = _sample_metrics()
    c1 = counter_values(parse_exposition(render_summary(m.summary())))
    m.incr("served_rows", 3)
    m.mark_step()
    c2 = counter_values(parse_exposition(render_summary(m.summary())))
    assert set(c1) <= set(c2)
    for k, v in c1.items():
        assert c2[k] >= v, k


# ============================================================= metrics flush

def test_metrics_flush_knob(monkeypatch):
    monkeypatch.delenv("COS_METRICS_FLUSH_S", raising=False)
    assert metrics_flush_s() == 0.0
    monkeypatch.setenv("COS_METRICS_FLUSH_S", "2.5")
    assert metrics_flush_s() == 2.5
    monkeypatch.setenv("COS_METRICS_FLUSH_S", "junk")
    assert metrics_flush_s() == 0.0     # lenient: never kills a run


def test_metrics_flusher_periodic_and_final(tmp_path):
    m = PipelineMetrics()
    m.incr("steps_done", 1)
    path = str(tmp_path / "metrics.json")
    f = MetricsFlusher(m, path, 0.05).start()
    deadline = time.monotonic() + 5
    while not os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert os.path.exists(path)
    first = json.load(open(path))
    assert first["counters"]["steps_done"] == 1
    m.incr("steps_done", 41)
    f.stop()                             # final flush lands the 42
    final = json.load(open(path))
    assert final["counters"]["steps_done"] == 42
    assert f.flushes >= 2
    # no orphan tmp files (atomic-write path)
    assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]


# ============================================================ serving e2e

@pytest.fixture()
def traced_fleet(tiny_model, sampled_tracer):
    """Two in-process replicas behind a real Router + RouterHTTPServer
    (in-process so the spans of every hop land in one ring the test
    can read synchronously)."""
    solver_path, model = tiny_model
    svcs, https = [], []
    for _ in range(2):
        svc = InferenceService(
            Config(["-conf", solver_path, "-model", model]),
            blob_names=("ip",), max_wait_ms=120, max_batch=8)
        svc.start()
        https.append(ServingHTTPServer(svc).start_background())
        svcs.append(svc)
    router = Router({f"replica{i}": f"http://127.0.0.1:{h.port}"
                     for i, h in enumerate(https)})
    for n in router.names():
        router.set_state(n, "ok")
    rhttp = RouterHTTPServer(router).start_background()
    yield router, rhttp, https, sampled_tracer
    rhttp.stop()
    router.stop()
    for h in https:
        h.stop()
    for s in svcs:
        s.stop()


def test_e2e_trace_propagation_and_coverage(traced_fleet):
    """Client -> router -> 2 replicas -> forward: one trace whose
    spans parent correctly across every hop, whose router span covers
    >= 95% of the client-observed wall (the queueing/batching wait is
    INSIDE the spans, not invisible between them), and whose attempt
    attrs show both replicas taking traffic."""
    router, rhttp, https, tracer = traced_fleet
    url = f"http://127.0.0.1:{rhttp.port}/v1/predict"
    for i in range(4):                       # warm connections+buckets
        _post_json(url, {"records": [_record(i)]})
    # the CLIENT mints the trace id (the header contract): the whole
    # request tree is then findable under a known id.  Best-of-3 on
    # the wall measurement: the coverage bound compares an in-span
    # wait (~120 ms flush window) against per-request localhost HTTP
    # overhead, and one slow accept on a loaded CI box would fail an
    # otherwise-correct trace.
    wall_ms = float("inf")
    for i in range(3):
        client_ctx = f"cafe0123deadbee{i}:c11e87"
        t0 = time.monotonic()
        out = _post_json(url, {"records": [_record(9)]},
                         headers={TRACE_HEADER: client_ctx})
        wall_ms = min(wall_ms, (time.monotonic() - t0) * 1e3)
        assert out["rows"]
    spans = _get_json(f"http://127.0.0.1:{rhttp.port}"
                      f"/v1/traces?trace=cafe0123deadbee{i}")["spans"]
    names = {s["name"] for s in spans}
    assert {"router.request", "router.attempt", "replica.request",
            "serve.queue_wait", "serve.pack", "serve.fwd",
            "serve.exec"} <= names
    # parentage: every span's parent is the client's span or a span
    # in the trace — no orphans
    ids = {s["span_id"] for s in spans}
    for s in spans:
        assert s["parent_id"] in ids | {"c11e87"}, s
    root = [s for s in spans if s["parent_id"] == "c11e87"]
    assert len(root) == 1 and root[0]["name"] == "router.request"
    # coverage: the router span accounts for >= 95% of what the
    # client saw (localhost HTTP overhead is the only thing outside)
    assert root[0]["dur_ms"] >= 0.95 * wall_ms, \
        (root[0]["dur_ms"], wall_ms)
    # the replica-side decomposition nests under the attempt
    attempt = [s for s in spans if s["name"] == "router.attempt"][0]
    rreq = [s for s in spans if s["name"] == "replica.request"][0]
    assert rreq["parent_id"] == attempt["span_id"]
    qw = [s for s in spans if s["name"] == "serve.queue_wait"][0]
    assert qw["parent_id"] == rreq["span_id"]
    # both replicas appear across the warmup+measured requests
    all_spans = _get_json(f"http://127.0.0.1:{rhttp.port}"
                          "/v1/traces?limit=4096")["spans"]
    hit = {s["attrs"]["replica"] for s in all_spans
           if s["name"] == "router.attempt"
           and "replica" in s.get("attrs", {})}
    assert hit == {"replica0", "replica1"}


class _Always429(BaseHTTPRequestHandler):
    def do_POST(self):
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        body = b'{"error": "queue full"}'
        self.send_response(429)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        body = b'{"status": "ok"}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_retry_is_one_trace_with_n_attempts(tiny_model,
                                            sampled_tracer):
    """Trace-context hardening: a request that bounces off a 429ing
    replica and retries onto a healthy one is ONE trace with multiple
    attempt spans (same trace id), not N orphan traces."""
    solver_path, model = tiny_model
    svc = InferenceService(
        Config(["-conf", solver_path, "-model", model]),
        blob_names=("ip",), max_wait_ms=5)
    svc.start()
    httpd = ServingHTTPServer(svc).start_background()
    bouncer = ThreadingHTTPServer(("127.0.0.1", 0), _Always429)
    threading.Thread(target=bouncer.serve_forever,
                     daemon=True).start()
    router = Router({
        "bouncer": f"http://127.0.0.1:{bouncer.server_address[1]}",
        "healthy": f"http://127.0.0.1:{httpd.port}"})
    router.set_state("bouncer", "ok")
    router.set_state("healthy", "ok")
    try:
        # pin the first pick onto the bouncer: both idle -> round-robin
        # tie-break; drive until a trace shows a 429 attempt
        found = None
        for i in range(8):
            with sampled_tracer.span("client", root=True) as sp:
                router.predict({"records": [_record(i)]},
                               trace=sp.ctx)
            spans = sampled_tracer.recent(sp.ctx.trace_id)
            outcomes = [s["attrs"].get("outcome") for s in spans
                        if s["name"] == "router.attempt"]
            if "429" in outcomes:
                found = (spans, outcomes)
                break
        assert found, "no request ever hit the bouncer"
        spans, outcomes = found
        attempts = [s for s in spans if s["name"] == "router.attempt"]
        assert len(attempts) >= 2                 # bounced + retried
        assert len({s["trace_id"] for s in attempts}) == 1
        assert outcomes[-1] == "ok"               # the retry landed
        nums = [s["attrs"]["attempt"] for s in attempts]
        assert nums == sorted(nums)
    finally:
        bouncer.shutdown()
        router.stop()
        httpd.stop()
        svc.stop()


def test_trace_off_is_inert_through_serving(tiny_model):
    """COS_TRACE_SAMPLE=0 (the default tracer state in this process
    outside the sampled fixture): a full HTTP predict leaves ZERO new
    spans and no trace slot on any request — the off-config hot path."""
    t = get_tracer()
    assert t.sample == 0.0, "test requires the default-off tracer"
    solver_path, model = tiny_model
    svc = InferenceService(
        Config(["-conf", solver_path, "-model", model]),
        blob_names=("ip",), max_wait_ms=5)
    svc.start()
    httpd = ServingHTTPServer(svc).start_background()
    try:
        before = len(t.recent())
        out = _post_json(f"http://127.0.0.1:{httpd.port}/v1/predict",
                         {"records": [_record(1)]})
        assert out["rows"]
        assert len(t.recent()) == before
    finally:
        httpd.stop()
        svc.stop()


def test_prom_endpoints_replica_and_router(traced_fleet):
    """`/metrics?format=prom` on replica and router: parseable
    exposition, no duplicate families, counters monotonic across two
    scrapes, fleet aggregation carries per-replica labels."""
    router, rhttp, https, _ = traced_fleet
    url = f"http://127.0.0.1:{rhttp.port}/v1/predict"
    for i in range(6):      # round-robin ties spread over both
        _post_json(url, {"records": [_record(i)]})

    def scrape(u):
        with urllib.request.urlopen(u, timeout=30) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            return parse_exposition(r.read().decode())

    rep = scrape(f"http://127.0.0.1:{https[0].port}"
                 "/metrics?format=prom")
    assert "cos_stage_seconds_total" in rep
    assert any(lbl.get("role") == "replica"
               for lbl, _v in rep["cos_uptime_seconds"]["samples"])
    agg1 = scrape(f"http://127.0.0.1:{rhttp.port}"
                  "/metrics?format=prom")
    # fleet aggregation: the router's own families plus both
    # replicas' samples labeled by replica name
    routed = agg1["cos_routed_total"]["samples"]
    assert any(lbl.get("role") == "router" for lbl, _v in routed)
    reps = {lbl.get("replica")
            for lbl, _v in agg1["cos_served_rows_total"]["samples"]}
    assert {"replica0", "replica1"} <= reps
    _post_json(url, {"records": [_record(1)]})
    agg2 = scrape(f"http://127.0.0.1:{rhttp.port}"
                  "/metrics?format=prom")
    c1, c2 = counter_values(agg1), counter_values(agg2)
    for k, v in c1.items():
        assert c2.get(k, v) >= v, k
    # the JSON route is unchanged
    assert "counters" in _get_json(
        f"http://127.0.0.1:{https[0].port}/metrics")


def test_profile_endpoint_live_capture(traced_fleet):
    """POST /v1/profile on a live replica: returns a TensorBoard-
    loadable trace directory while concurrent predicts keep landing;
    a second capture during the first answers 409."""
    router, rhttp, https, _ = traced_fleet
    url = f"http://127.0.0.1:{rhttp.port}/v1/predict"
    stop = threading.Event()
    failures = []

    def client():
        i = 0
        while not stop.is_set():
            try:
                _post_json(url, {"records": [_record(i % 7)]})
            except Exception as e:    # noqa: BLE001
                failures.append(e)
            i += 1

    th = threading.Thread(target=client, daemon=True)
    th.start()
    try:
        out = _post_json(
            f"http://127.0.0.1:{https[0].port}/v1/profile",
            {"duration_ms": 300})
    finally:
        stop.set()
        th.join(timeout=10)
    assert out["ok"] and os.path.isdir(out["trace_dir"])
    # jax writes plugins/profile/<run>/... — TensorBoard's layout
    walked = [os.path.join(dp, f)
              for dp, _dn, fn in os.walk(out["trace_dir"])
              for f in fn]
    assert walked, "profiler capture produced no trace files"
    assert any("plugins" in p for p in walked)
    assert not failures, failures[:3]


def test_profile_endpoint_busy_409(traced_fleet):
    router, rhttp, https, _ = traced_fleet
    url = f"http://127.0.0.1:{https[0].port}/v1/profile"
    results = {}

    def first():
        results["first"] = _post_json(url, {"duration_ms": 600})

    th = threading.Thread(target=first)
    th.start()
    time.sleep(0.15)
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post_json(url, {"duration_ms": 50})
    assert ei.value.code == 409
    th.join(timeout=10)
    assert results["first"]["ok"]


def test_router_traces_aggregate_dedupes(traced_fleet):
    """collect_traces merges router + replica rings without
    duplicating spans (in-process replicas share one ring — the
    degenerate worst case for the dedupe)."""
    router, rhttp, https, _ = traced_fleet
    _post_json(f"http://127.0.0.1:{rhttp.port}/v1/predict",
               {"records": [_record(3)]})
    spans = router.collect_traces(limit=4096)
    ids = [s["span_id"] for s in spans]
    assert len(ids) == len(set(ids))


# ============================================================ drills (slow)

def _drill_env(**extra):
    return {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
            "PYTHONPATH": REPO + os.pathsep
            + os.environ.get("PYTHONPATH", ""), **extra}


@pytest.mark.slow
def test_drill_sigterm_leaves_flight_recorder_artifact(tiny_model,
                                                       tmp_path):
    """Kill-under-load: SIGTERM a -serve replica mid-traffic; the
    process must leave a valid flight-recorder artifact whose
    timeline includes the drain-path events, plus a flushed trace
    spool (COS_TRACE_DIR)."""
    solver_path, model = tiny_model
    dump_dir = tmp_path / "recdump"
    dump_dir.mkdir()
    env = _drill_env(COS_RECORDER_DUMP=str(dump_dir),
                     COS_TRACE_DIR=str(tmp_path / "spool"),
                     COS_TRACE_SAMPLE="1.0")
    proc = subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.caffe_on_spark",
         "-serve", "-servePort", "0", "-conf", solver_path,
         "-model", model, "-features", "ip"],
        stdout=subprocess.PIPE, text=True, env=env, cwd=REPO)
    try:
        line = proc.stdout.readline()
        port = json.loads(line)["port"]
        url = f"http://127.0.0.1:{port}/v1/predict"
        stop = threading.Event()

        def load():
            i = 0
            while not stop.is_set():
                try:
                    _post_json(url, {"records": [_record(i % 5)]})
                except Exception:     # noqa: BLE001 — the kill window
                    return
                i += 1

        th = threading.Thread(target=load, daemon=True)
        th.start()
        time.sleep(1.0)               # traffic flowing
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        stop.set()
        th.join(timeout=10)
        assert rc == 0                # the drain path ran
    finally:
        if proc.poll() is None:
            proc.kill()
    arts = [p for p in os.listdir(dump_dir) if p.endswith(".json")]
    assert len(arts) == 1, arts
    doc = json.load(open(dump_dir / arts[0]))
    assert doc["schema"] == "cos-flight-recorder-v1"
    events = {(e["source"], e["event"]) for e in doc["events"]}
    assert ("serve", "signal") in events          # the SIGTERM itself
    assert ("batcher", "stop") in events          # the drain ran
    assert ("registry", "published") in events    # boot-time history
    # sampled spans survived in the JSONL spool
    spool = os.listdir(tmp_path / "spool")
    assert spool, "no trace spool written"
    lines = [json.loads(ln)
             for ln in open(tmp_path / "spool" / spool[0])]
    assert any(r["name"] == "serve.exec" for r in lines)


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_sigkill_training_leaves_fresh_metrics(tmp_path):
    """SIGKILL a training run mid-flight with COS_METRICS_FLUSH_S
    set: <output>/metrics.json must exist, parse, and be no older
    than the flush interval (plus scheduling slack) at the moment of
    death — the periodic-flush satellite's whole point."""
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(64, seed=3)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(64)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.01\nlr_policy: "fixed"\n'
        'display: 50\nmax_iter: 100000\nrandom_seed: 3\n'
        'snapshot_prefix: "m"\n')
    out = tmp_path / "out"
    flush_s = 0.3
    env = _drill_env(COS_METRICS_FLUSH_S=str(flush_s),
                     COS_TRANSFORM_THREADS="0",
                     COS_FAULT_STEP_DELAY_MS="20")
    proc = subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(out)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO)
    try:
        mpath = out / "metrics.json"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if mpath.exists() and proc.poll() is None:
                break
            time.sleep(0.05)
        assert mpath.exists(), "flusher never wrote metrics.json"
        time.sleep(3 * flush_s)       # let the run make progress
        t_kill = time.time()
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    age_at_kill = t_kill - os.path.getmtime(mpath)
    assert age_at_kill <= flush_s + 2.0, age_at_kill
    doc = json.load(open(mpath))      # complete (atomic write), fresh
    assert doc["steps"] > 0
    assert "step" in doc["stages"]
    assert doc["info"]["faults"]["active"] is True
    assert not [p for p in os.listdir(out) if ".tmp." in p]


def test_profile_of_live_trainer_shows_host_stages(tmp_path, monkeypatch):
    """POST /v1/profile on a live trainer's metrics port: the capture
    holds the job's own `cos.*` stages (metrics.py's vocabulary) from
    every kind of thread of the feed -> pack -> stage -> step chain."""
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.processor import CaffeProcessor
    from perfbench.harness import spans as S
    from perfbench.harness.trace import find_xplane
    from tests.test_ingest_pipeline import _timeline_job

    monkeypatch.setenv("COS_METRICS_PORT", "0")
    conf = _timeline_job(tmp_path, monkeypatch, max_iter=100000,
                         step_delay_ms=10)
    proc = CaffeProcessor.instance(conf)
    for q in proc.queues:
        q._q.maxsize = 24                # the feeder fills them
    started = threading.Event()
    proc.step_observer = lambda it, *rest: it == 3 and started.set()
    job = threading.Thread(
        target=lambda: CaffeOnSpark().train(get_source(
            conf.train_data_layer(), phase_train=True), conf),
        daemon=True)
    job.start()
    try:
        assert started.wait(60)
        out = _post_json(
            f"http://127.0.0.1:{proc._obs_server.port}/v1/profile",
            {"duration_ms": 500})
    finally:
        proc.stop()
        job.join(timeout=30)
    names = {s[0] for s in S.load(find_xplane(out["trace_dir"]))}
    for thread, stage in (("feeder", "read_blocked"),
                          ("dispatcher", "group_blocked"),
                          ("worker", "pack"), ("stager", "stage"),
                          ("solver", "queue_wait"), ("solver", "step")):
        assert stage in names, (thread, stage, sorted(names))
