#!/usr/bin/env python
"""Production-day replay bench: checked-in scenarios through the
prodday harness against the REAL process tree, verdicts from the
observability substrate alone.

Two legs:

  day   scenarios/prodday.json — a compressed production day (ramp
        with straggler + flaky storage, diurnal midday with a replica
        SIGKILL and a canary-killed deploy round, evening flash
        crowd) against the full PR 13 loop (streaming ingest thread →
        fine-tune → canary → fleet) with hedging + response cache
        live.  Gate: the day survives — every phase inside its SLO
        error budget, every injected fault explained in the merged
        flight-recorder timeline, no leaks, clean scrapes.
  a/b   scenarios/flash_straggler.json (zipfian flash crowd + one
        120x straggler) run twice: hedging/cache DISABLED must go
        red (p99 SLO blown), hedging/cache ENABLED must go green —
        the harness distinguishes system versions, which is the whole
        point of a replay harness.
  a/b2  scenarios/autoscale_day.json (flash crowd + batch-lane
        backlog + one slowed replica) run twice from a ONE-replica
        fleet with hedging/cache off in both cells: the static fleet
        must blow the p99 budget (red), the SLO-driven control plane
        (COS_AS_ENABLE + COS_LANES) must hold it (green) with its
        scale-up decisions visible in the flight recorder.

`--quick` runs scenarios/prodday_smoke.json only (no deploy faults,
no a/b cells) and stays tier-1-safe (<60s).

ALWAYS exits 0 with ONE JSON document on stdout;
the full artifact lands in bench_evidence/bench_prodday.json.

Usage:
  python scripts/bench_prodday.py [--quick] [--out PATH]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("COS_TRANSFORM_THREADS", "0")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NET_TMPL = """
name: "proddaynet"
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
  inner_product_param {{ num_output: 64
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
snapshot_prefix: "prodday"
random_seed: 3
"""

# the green system version: PR 8/12/16 tail-latency stack live
GREEN = {"COS_HEDGE_PCT": "95", "COS_HEDGE_MIN_MS": "25",
         "COS_HEDGE_MAX_PCT": "30", "COS_CACHE_CAP": "64"}
# the red system version: same code, hedging + cache disabled
RED = {"COS_HEDGE_PCT": "0", "COS_CACHE_CAP": "0"}

# autoscale a/b: hedging/cache off in BOTH cells so the only
# difference is the control plane — static one-replica fleet (red)
# vs autoscaler + admission lanes over the same fleet (green)
AS_RED = {"COS_HEDGE_PCT": "0", "COS_CACHE_CAP": "0"}
AS_GREEN = dict(AS_RED,
                COS_AS_ENABLE="1", COS_SLO_P99_MS="600",
                COS_SLO_QDEPTH="24", COS_AS_MIN="1", COS_AS_MAX="4",
                COS_AS_INTERVAL_S="0.5", COS_AS_WINDOW_S="8",
                COS_AS_UP_BREACHES="2",
                COS_AS_UP_COOLDOWN_S="3", COS_AS_DOWN_MARGIN="0.4",
                COS_AS_DOWN_INTERVALS="8", COS_AS_DOWN_COOLDOWN_S="8",
                COS_LANES="1", COS_LANE_BATCH_DEPTH="64")


class IngestThread:
    """The streaming-ingest leg of the PR 13 loop: keeps the training
    stream growing during the day so scheduled deploy rounds always
    find fresh records."""

    def __init__(self, stream, every_s=3.0, part=64):
        self.stream = stream
        self.every_s = every_s
        self.part = part
        self.parts = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True,
                                   name="cos-prodday-ingest")

    def _run(self):
        from caffeonspark_tpu.data.streaming import (append_stream_part,
                                                     datum_records)
        from caffeonspark_tpu.data.synthetic import make_images
        while not self._stop.wait(self.every_s):
            self.parts += 1
            imgs, labels = make_images(self.part,
                                       seed=1000 + self.parts)
            append_stream_part(
                self.stream,
                datum_records(imgs, labels, 100000 * self.parts))

    def start(self):
        self._t.start()
        return self

    def stop(self):
        self._stop.set()
        self._t.join(timeout=15)


def _payload_pools(eval_records, n=8):
    """Pre-serialized request bodies: `n` distinct well-formed
    payloads for the zipfian mix, plus adversarial bodies that must
    come back 4xx (never 5xx, never a crash)."""
    pool = [json.dumps(p).encode()
            for p, _label in eval_records[:n]]
    malformed = [b'{"records": "not-a-list"}',
                 b'{"truncated": ',
                 b"\x00\x81 not json at all"]
    return pool, malformed


def _set_env(env):
    old = {}
    for k, v in env.items():
        old[k] = os.environ.get(k)
        os.environ[k] = v
    return old


def _restore_env(old):
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    # scheduled chaos must never leak into the next leg
    for k in list(os.environ):
        if k.startswith("COS_FAULT_"):
            del os.environ[k]


def _recorder_events(dump_dir, source, event):
    """Count `source.event` occurrences across a leg's recorder dump
    files — how the bench proves a control-plane decision actually
    fired (vs the verdict merely coming out green)."""
    n = 0
    needle_src = f'"{source}"'
    needle_evt = f'"{event}"'
    for root, _dirs, files in os.walk(dump_dir):
        for fname in files:
            if not fname.endswith(".json"):
                continue
            try:
                with open(os.path.join(root, fname)) as f:
                    doc = json.load(f)
                evs = doc.get("events") if isinstance(doc, dict) \
                    else doc
                for ev in evs or []:
                    if (isinstance(ev, dict)
                            and ev.get("source") == source
                            and ev.get("event") == event):
                        n += 1
            except (OSError, ValueError):
                # a half-written dump shouldn't kill the bench; the
                # raw-string fallback still counts the event
                try:
                    text = open(os.path.join(root, fname),
                                errors="replace").read()
                    if needle_src in text and needle_evt in text:
                        n += 1
                except OSError:
                    pass
    return n


def run_day(tag, scenario_path, knobs, conf, pools, dump_root,
            steps, replicas=2):
    """One compressed day under one set of system knobs; returns the
    harness verdict document (plus run metadata)."""
    from caffeonspark_tpu.deploy import DeployController
    from caffeonspark_tpu.prodday import (FleetStack, ProdDay,
                                          load_scenario)

    scenario = load_scenario(scenario_path)
    dump_dir = os.path.join(dump_root, tag)
    os.makedirs(dump_dir, exist_ok=True)
    old = _set_env(dict(knobs, COS_RECORDER_DUMP=dump_dir))
    print(f"[{tag}] scenario={scenario.name} "
          f"duration={scenario.duration_s:g}s knobs={knobs}",
          file=sys.stderr, flush=True)
    stack = None
    t0 = time.monotonic()
    try:
        ctl = DeployController(conf, replicas=replicas, steps=steps)
        stack = FleetStack(controller=ctl)
        day = ProdDay(scenario, stack,
                      payload_pool=pools[0], malformed_pool=pools[1],
                      dump_dir=dump_dir)
        doc = day.run()
        stack = None                 # run() stopped it
    finally:
        if stack is not None:        # run() died mid-day
            try:
                stack.stop()
            except Exception:        # noqa: BLE001 — best-effort
                pass
        _restore_env(old)
    doc["tag"] = tag
    doc["knobs"] = dict(knobs)
    doc["wall_s"] = round(time.monotonic() - t0, 2)
    print(f"[{tag}] ok={doc['ok']} gates={doc['gates']} "
          f"({doc['wall_s']}s)", file=sys.stderr, flush=True)
    return doc


def run(args, record):
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data.lmdb_io import LmdbWriter
    from caffeonspark_tpu.data.streaming import (append_stream_part,
                                                 datum_records)
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.deploy import DeployController  # noqa: F401

    steps = 10 if args.quick else 25
    eval_n = 24 if args.quick else 48
    with tempfile.TemporaryDirectory(prefix="bench_prodday_") as tmp:
        stream = os.path.join(tmp, "stream")
        evaldb = os.path.join(tmp, "eval_lmdb")
        out = os.path.join(tmp, "out")
        dump_root = os.path.join(tmp, "recorder")
        os.makedirs(out)
        imgs, labels = make_images(384, seed=7)
        append_stream_part(stream, datum_records(imgs[:192],
                                                 labels[:192]))
        ev_imgs, ev_labels = make_images(eval_n, seed=99)
        LmdbWriter(evaldb).write(datum_records(ev_imgs, ev_labels))
        net_path = os.path.join(tmp, "net.prototxt")
        with open(net_path, "w") as f:
            f.write(NET_TMPL.format(stream=stream, evaldb=evaldb))
        solver_path = os.path.join(tmp, "solver.prototxt")
        with open(solver_path, "w") as f:
            f.write(SOLVER_TMPL.format(net=net_path))
        os.environ["COS_AOT_CACHE_DIR"] = os.path.join(tmp, "aot")
        os.environ["COS_DEPLOY_POLL_S"] = "15"
        os.environ["COS_DEPLOY_EVAL_N"] = str(eval_n)
        os.environ["COS_PRODDAY_RECOVERY_S"] = "150"

        conf = Config(["-conf", solver_path, "-output", out,
                       "-features", "ip2", "-deploy"])
        conf.validate()
        # the eval set doubles as the client payload pool — RAW
        # records, exactly what a real client would post
        ctl_probe = DeployController(conf, replicas=2, steps=steps)
        pools = _payload_pools(ctl_probe.eval_records)
        del ctl_probe

        day_path = os.path.join(
            REPO, "scenarios",
            "prodday_smoke.json" if args.quick else "prodday.json")
        ingest = IngestThread(stream).start()
        try:
            record["day"] = run_day("day", day_path, GREEN, conf,
                                    pools, dump_root, steps)
        finally:
            ingest.stop()
        record["day_survived"] = bool(record["day"]["ok"])

        if not args.quick:
            ab_path = os.path.join(REPO, "scenarios",
                                   "flash_straggler.json")
            red = run_day("red", ab_path, RED, conf, pools,
                          dump_root, steps)
            green = run_day("green", ab_path, GREEN, conf, pools,
                            dump_root, steps)
            record["ab"] = {"red": red, "green": green}
            # red must be red for the RIGHT reason: the SLO gate (the
            # straggler blowing p99), not a harness failure
            record["ab_red_detects"] = bool(
                not red["gates"]["slo"]
                and red["gates"]["incidents_explained"]
                and red["gates"]["leaks"])
            record["ab_green_passes"] = bool(green["ok"])

            # a/b2: SLO-driven control plane vs static fleet, from a
            # deliberately under-provisioned single replica
            as_path = os.path.join(REPO, "scenarios",
                                   "autoscale_day.json")
            as_red = run_day("as_red", as_path, AS_RED, conf, pools,
                             dump_root, steps, replicas=1)
            as_green = run_day("as_green", as_path, AS_GREEN, conf,
                               pools, dump_root, steps, replicas=1)
            scale_ups = _recorder_events(
                os.path.join(dump_root, "as_green"),
                "fleet", "scale_up")
            decisions = _recorder_events(
                os.path.join(dump_root, "as_green"),
                "autoscale", "decision")
            record["autoscale_ab"] = {
                "red": as_red, "green": as_green,
                "green_scale_ups": scale_ups,
                "green_decisions": decisions}
            record["as_red_detects"] = bool(
                not as_red["gates"]["slo"]
                and as_red["gates"]["leaks"])
            record["as_green_passes"] = bool(as_green["ok"]
                                             and scale_ups > 0)
            record["ok"] = bool(record["day_survived"]
                                and record["ab_red_detects"]
                                and record["ab_green_passes"]
                                and record["as_red_detects"]
                                and record["as_green_passes"])
        else:
            record["ab"] = "skipped (--quick)"
            record["ok"] = record["day_survived"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(
        REPO, "bench_evidence",
        "bench_prodday_quick.json" if args.quick
        else "bench_prodday.json")
    record = {
        "bench": "prodday",
        "backend": "cpu",
        "cpus": os.cpu_count(),
        "config": {"quick": bool(args.quick), "replicas": 2,
                   "green_knobs": GREEN, "red_knobs": RED,
                   "autoscale_green_knobs": AS_GREEN,
                   "autoscale_red_knobs": AS_RED},
        "harness_semantics": (
            "Scenario data files replayed by caffeonspark_tpu.prodday "
            "against a real DeployController process tree (2 fleet "
            "replicas + canary subprocesses).  Verdicts come from the "
            "observability substrate only: per-phase SLO error "
            "budgets from periodic router prom scrapes, incident "
            "reconstruction over merged flight-recorder dumps (every "
            "injected fault needs evidence + a recovery event), "
            "slowest-request trace exemplars, and end-of-day leak "
            "gates (fds/children/threads/residency vs start)."),
        "ts": time.time(),
    }
    try:
        run(args, record)
    except Exception as e:   # noqa: BLE001 — always-exit-0 contract
        import traceback
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=12)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    day = record.get("day") or {}
    print(json.dumps({"bench": "prodday",
                      "day_survived": record.get("day_survived"),
                      "day_gates": day.get("gates"),
                      "ab_red_detects": record.get("ab_red_detects"),
                      "ab_green_passes":
                          record.get("ab_green_passes"),
                      "as_red_detects": record.get("as_red_detects"),
                      "as_green_passes":
                          record.get("as_green_passes"),
                      "ok": record.get("ok"),
                      "error": record.get("error"),
                      "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
