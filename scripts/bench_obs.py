#!/usr/bin/env python
"""Observability overhead benchmark (ALWAYS exits
0 with one JSON document on stdout; --out writes the same document).

The obs layer's promise is that it is cheap enough to leave on: with
tracing sampled at 1.0 (every request spanned, JSONL-spooled) and the
flight recorder armed, serving rows/s and training steps/s must
regress < 3% vs the off-config.

Measurement integrity: this box's CPU share swings tens of percent on
neighbor-tenant contention (the bench_syncmode/bench_steploop floor
recipes pin against the same problem), so an off-then-on sequence
measures the BOX, not the layer.  Here every trial is a PAIR of
adjacent cells — off/on order alternating per pair so neither config
systematically lands on the quiet half — and the headline overhead is
the MEDIAN of the per-pair on/off ratios: pairs share a contention
regime, the median discards the pairs a regime shift split.

  serving   4 closed-loop client threads driving the REAL stack
            (InferenceService -> MicroBatcher -> jitted forward) with
            8-record requests (one trace per request, the wire shape);
            off = COS_TRACE_SAMPLE=0 (the default null-span path),
            on = sample 1.0 + JSONL spool + per-hop spans.
  training  the jitted train-step loop with PipelineMetrics; on adds
            the armed flight recorder (an event per display cadence)
            and the COS_METRICS_FLUSH_S-style periodic atomic flusher
            at 0.25 s.  (Tracing does not touch the training path —
            recorder + flusher ARE its on-config.)

Gates (recorded, not exit-coded): overhead_serving_pct < 3,
overhead_training_pct < 3, spans_were_recorded (the on-config really
traced — a gate that passes because tracing silently never ran is no
gate).

Usage: python scripts/bench_obs.py [--quick] [--out PATH]
"""

import argparse
import json
import os
import platform
import statistics
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_cpu_multi_thread_eigen=false"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FLAG).strip()
# the off-config must be the true default: no ambient sampling/flush
os.environ.pop("COS_TRACE_SAMPLE", None)
os.environ.pop("COS_METRICS_FLUSH_S", None)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

# Moderately-sized net ON PURPOSE: the overhead gate divides a fixed
# per-request tracing cost by the request's compute; a micro-forward
# of ~0.1 ms/row measures GIL scheduling, not the layer.  This stem
# (2 convs + fc-256) runs ~0.2-0.3 ms/row on the CI box — the small
# end of real serving models, and still seconds to compile.
NET_TMPL = """
name: "obsnet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{root}/unused_lmdb" batch_size: 32
    channels: 3 height: 32 width: 32 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 32 kernel_size: 5 stride: 1
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param {{ pool: MAX kernel_size: 2 stride: 2 }} }}
layer {{ name: "conv2" type: "Convolution" bottom: "pool1" top: "conv2"
  convolution_param {{ num_output: 32 kernel_size: 3
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu2" type: "ReLU" bottom: "conv2" top: "conv2" }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "conv2" top: "ip1"
  inner_product_param {{ num_output: 256
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu3" type: "ReLU" bottom: "ip1" top: "ip1" }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}
"""

SOLVER_TMPL = """
net: "{net}"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 10
random_seed: 7
"""


def build_model(td: str):
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    net_path = os.path.join(td, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(NET_TMPL.format(root=td))
    solver_path = os.path.join(td, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(SOLVER_TMPL.format(net=net_path))
    s = Solver(SolverParameter.from_text(
        SOLVER_TMPL.format(net=net_path)),
        NetParameter.from_text(NET_TMPL.format(root=td)))
    params, _ = s.init()
    model = os.path.join(td, "serve.caffemodel")
    checkpoint.save_caffemodel(model, s.train_net, params)
    return solver_path, model


# ---------------------------------------------------------------------------
# serving leg
# ---------------------------------------------------------------------------

def serve_leg(solver_path: str, model: str, pairs: int,
              window_s: float, spool_dir: str) -> dict:
    """ONE warm service, saturated by 12 closed-loop client threads
    (8-record requests — the wire shape — with ~3 buckets of backlog,
    so throughput is executor-bound, not latency-coupled), measured in
    adjacent timed WINDOWS that flip the process tracer between the
    off-config (sample 0: every span call is the null fast path,
    requests carry trace=None) and full-fire tracing (sample 1.0 +
    JSONL spool: client root span per request, queue_wait/exec per
    request, pack/fwd per flush).  The service, its compiled
    programs, and the client threads persist across every window —
    the ONLY thing a pair compares is the tracing config."""
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.obs.trace import get_tracer
    from caffeonspark_tpu.serving import InferenceService
    tracer = get_tracer("bench")
    tracer.reconfigure(sample=0.0, spool_dir=spool_dir)
    conf = Config(["-conf", solver_path, "-model", model])
    svc = InferenceService(conf, blob_names=("ip2",), max_batch=32,
                           max_wait_ms=1.0, queue_depth=512)
    svc.start(warmup=True)
    rec = ("r", 0.0, 3, 32, 32, False,
           (np.random.RandomState(0).rand(3, 32, 32)
            .astype(np.float32) * 255.0))
    stop = threading.Event()
    lock = threading.Lock()
    total = [0]
    k, clients = 8, 12

    def client():
        while not stop.is_set():
            try:
                with tracer.span("client.request",
                                 root=tracer.sample_root()) as sp:
                    pend = svc.submit_many([rec] * k, trace=sp.ctx)
                    for p in pend:
                        p.wait(60.0)
                with lock:
                    total[0] += k
            except Exception:    # noqa: BLE001 — queue-full backoff
                time.sleep(0.001)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    time.sleep(1.0)                      # ramp out of the window

    def window(sample: float) -> float:
        tracer.sample = sample
        time.sleep(0.1)                  # config settle
        with lock:
            n0 = total[0]
        t0 = time.monotonic()
        time.sleep(window_s)
        with lock:
            n1 = total[0]
        return (n1 - n0) / (time.monotonic() - t0)

    rows, ratios = [], []
    for p in range(pairs):
        if p % 2 == 0:
            off, on = window(0.0), window(1.0)
        else:
            on, off = window(1.0), window(0.0)
        rows.append({"pair": p, "off_rows_per_sec": round(off, 1),
                     "on_rows_per_sec": round(on, 1),
                     "ratio": round(on / off, 4)})
        ratios.append(on / off)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    svc.stop(drain=True)
    m = svc.metrics_summary()
    lat = m["stages"].get("latency", {})
    spans = len(tracer.recent(limit=10 ** 9))
    tracer.flush_spool()
    tracer.reconfigure(sample=0.0, spool_dir="")
    med = statistics.median(ratios)
    return {"pairs": rows, "median_ratio": round(med, 4),
            "overhead_pct": round(max(0.0, 1.0 - med) * 100.0, 2),
            "clients": clients, "records_per_request": k,
            "p50_ms": lat.get("p50_ms"), "p99_ms": lat.get("p99_ms"),
            "mean_batch_fill": m["queue_depths"]
            .get("batch_fill", {}).get("mean"),
            "spans_in_ring": spans}


# ---------------------------------------------------------------------------
# training leg
# ---------------------------------------------------------------------------

def train_leg(solver_path: str, pairs: int, steps: int,
              out_dir: str) -> dict:
    """ONE jitted train-step loop, measured in adjacent windows of
    `steps` steps with the on-config extras toggled — armed flight
    recorder (an event per display cadence, the realistic event rate)
    and the periodic atomic metrics flusher at 0.25 s.  The compiled
    program, device buffers, and the PipelineMetrics bookkeeping both
    configs share persist across every window."""
    from caffeonspark_tpu.metrics import MetricsFlusher, PipelineMetrics
    from caffeonspark_tpu.obs.recorder import FlightRecorder
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    net_path = os.path.join(os.path.dirname(solver_path),
                            "net.prototxt")
    s = Solver(SolverParameter.from_text(open(solver_path).read()),
               NetParameter.from_text(open(net_path).read()))
    params, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(3)
    import jax
    import jax.numpy as jnp
    batch = {"data": jnp.asarray(rng.rand(32, 3, 32, 32)
                                 .astype(np.float32) * 255.0),
             "label": jnp.asarray(rng.randint(0, 10, 32)
                                  .astype(np.float32))}
    metrics = PipelineMetrics()
    recorder = FlightRecorder(capacity=512)
    it = [0]
    flush_total = [0]

    def window(observed: bool) -> float:
        flusher = MetricsFlusher(
            metrics, os.path.join(out_dir, "metrics.json"),
            0.25).start() if observed else None
        nonlocal params, st
        out = None
        t0 = time.monotonic()
        for _ in range(steps):
            it[0] += 1
            t_step = time.monotonic()
            params, st, out = step(params, st, batch,
                                   s.step_rng(it[0]))
            metrics.add("step", time.monotonic() - t_step)
            metrics.mark_step()
            if observed and it[0] % 20 == 0:
                recorder.record("bench", "display", iter=it[0])
        jax.block_until_ready(out["loss"])
        elapsed = time.monotonic() - t0
        if flusher is not None:
            flusher.stop()
            flush_total[0] += flusher.flushes
        return steps / elapsed

    # warmup (compile) outside every window
    params, st, out = step(params, st, batch, s.step_rng(0))
    jax.block_until_ready(out["loss"])
    rows, ratios = [], []
    for p in range(pairs):
        if p % 2 == 0:
            off, on = window(False), window(True)
        else:
            on, off = window(True), window(False)
        rows.append({"pair": p, "off_steps_per_sec": round(off, 2),
                     "on_steps_per_sec": round(on, 2),
                     "ratio": round(on / off, 4)})
        ratios.append(on / off)
    med = statistics.median(ratios)
    return {"pairs": rows, "median_ratio": round(med, 4),
            "overhead_pct": round(max(0.0, 1.0 - med) * 100.0, 2),
            "steps_per_window": steps, "flushes": flush_total[0],
            "recorder_events": len(recorder.events())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--pairs", type=int, default=0)
    args = ap.parse_args()
    pairs = args.pairs or (4 if args.quick else 7)
    window_s = 1.5 if args.quick else 2.5
    steps = 150 if args.quick else 300
    doc = {"bench": "obs_overhead", "schema": 2,
           "host": platform.node(), "python": sys.version.split()[0],
           "quick": bool(args.quick), "pairs": pairs,
           "method": "one warm stack; adjacent off/on windows, order "
                     "alternating per pair; overhead = 1 - "
                     "median(on/off ratio)",
           "knobs": {"serving_on": "COS_TRACE_SAMPLE=1.0 + "
                                   "COS_TRACE_DIR spool + recorder",
                     "training_on": "flight recorder + periodic "
                                    "atomic flush @0.25s"}}
    try:
        td = tempfile.mkdtemp(prefix="bench_obs_")
        solver_path, model = build_model(td)
        spool = os.path.join(td, "spool")

        serving = serve_leg(solver_path, model, pairs, window_s,
                            spool)
        training = train_leg(solver_path, pairs, steps, td)

        spool_files = os.listdir(spool) if os.path.isdir(spool) else []
        doc.update({
            "serving": dict(serving, spool_files=spool_files),
            "training": training,
            "gates": {
                "overhead_serving_lt_3pct":
                    serving["overhead_pct"] < 3.0,
                "overhead_training_lt_3pct":
                    training["overhead_pct"] < 3.0,
                "spans_were_recorded":
                    serving["spans_in_ring"] > 0
                    and bool(spool_files),
                "metrics_flushed": training["flushes"] > 0,
            },
        })
    except BaseException as e:     # noqa: BLE001 — always-exit-0
        doc["error"] = f"{type(e).__name__}: {e}"
        import traceback
        doc["traceback"] = traceback.format_exc()
    text = json.dumps(doc, indent=2, sort_keys=False)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
