#!/usr/bin/env python
"""Continuous-deployment drill bench: N fine-tune rounds through the
canary gate, with one injected-regression round and one
injected-crash round — both must leave the incumbent serving.

One in-process DeployController drives the REAL process tree (fleet
replica subprocesses + one canary subprocess per round, AOT-warm):

  round 1, 2   clean fine-tunes — the canary must ACCEPT and each
               rolling reload must publish with zero failed client
               requests (background load runs the whole time);
  round 3      label-shuffled fine-tune (the injected regression) —
               the canary must REJECT it, incumbent untouched;
  round 4      COS_FAULT_RELOAD_FAIL_RANK kills replica 1 mid-roll
               after replica 0 swapped (the injected crash) — the
               fleet must auto-ROLLBACK to the incumbent, which must
               answer byte-identically to its pre-round outputs;
  round 5      clean again — the loop must recover and ACCEPT.

Gates: `gate_accepts` (clean rounds accepted), `regression_rejected`,
`rollback_proven` (crash round rolled back + byte-identical
incumbent), `accepted_improves` (final incumbent beats the bootstrap
on the held-out eval), `zero_failed_client_requests`.

ALWAYS exits 0 with ONE JSON document on stdout;
the full artifact lands in bench_evidence/bench_deploy.json.

Usage:
  python scripts/bench_deploy.py [--quick] [--out PATH]
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
snapshot_prefix: "deploy"
random_seed: 3
"""


class LoadThread:
    """Constant background client load through the live fleet router;
    its failure count is the zero-failed-client-requests gate."""

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
            except Exception:        # noqa: BLE001 — counted
                self.failures += 1
            time.sleep(0.05)

    def start(self):
        self._t.start()
        return self

    def stop(self):
        self._stop.set()
        self._t.join(timeout=15)


def run(args, record):
    import numpy as np

    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data.lmdb_io import LmdbWriter
    from caffeonspark_tpu.data.streaming import (append_stream_part,
                                                 datum_records)
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.deploy import DeployController

    steps = 15 if args.quick else 40
    eval_n = 32 if args.quick else 96
    with tempfile.TemporaryDirectory(prefix="bench_deploy_") as tmp:
        stream = os.path.join(tmp, "stream")
        evaldb = os.path.join(tmp, "eval_lmdb")
        out = os.path.join(tmp, "out")
        os.makedirs(out)
        imgs, labels = make_images(768, seed=7)
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
        os.environ["COS_DEPLOY_POLL_S"] = "10"
        os.environ["COS_DEPLOY_EVAL_N"] = str(eval_n)

        conf = Config(["-conf", solver_path, "-output", out,
                       "-features", "ip2", "-deploy"])
        conf.validate()
        print("bootstrapping incumbent + starting fleet "
              "(2 replicas)...", file=sys.stderr, flush=True)
        ctl = DeployController(conf, replicas=2, steps=steps)
        t0 = time.monotonic()
        ctl.start()
        record["fleet_start_s"] = round(time.monotonic() - t0, 2)
        load = LoadThread(ctl.fleet.router,
                          ctl.eval_records[0][0]).start()
        rounds = []
        try:
            bootstrap_acc = ctl.mirror_incumbent()[0]
            record["bootstrap_accuracy"] = bootstrap_acc

            def one(tag, grow_seed, grow_from, label_shuffle=False,
                    fault_env=None):
                if fault_env:
                    for k, v in fault_env.items():
                        os.environ[k] = v
                    ctl.refresh_faults()
                gi, gl = make_images(128, seed=grow_seed)
                append_stream_part(
                    stream, datum_records(gi, gl, grow_from))
                t = time.monotonic()
                r = ctl.run_round(label_shuffle=label_shuffle)
                r["tag"] = tag
                r["faults"] = ctl.injector.plan.describe()
                if fault_env:
                    for k in fault_env:
                        os.environ.pop(k, None)
                    ctl.refresh_faults()
                rounds.append(r)
                print(f"  {tag:>12}: verdict={r['verdict']} "
                      f"acc={(r.get('canary') or {}).get('accuracy')} "
                      f"({time.monotonic() - t:.1f}s)",
                      file=sys.stderr, flush=True)
                return r

            one("clean-1", 1, 100000)
            one("clean-2", 2, 200000)
            one("regression", 3, 300000, label_shuffle=True)
            # byte-identical incumbent proof brackets the crash round
            probe = ctl.eval_records[1][0]
            before = ctl.fleet.router.predict(probe)["rows"]
            crash = one("crash-midroll", 4, 400000, fault_env={
                "COS_FAULT_RELOAD_FAIL_RANK":
                    f"1:{os.path.join(tmp, 'rf.marker')}"})
            after = ctl.fleet.router.predict(probe)["rows"]
            byte_identical = \
                json.dumps(before, sort_keys=True) == \
                json.dumps(after, sort_keys=True)
            one("clean-3", 5, 500000)

            final_acc = ctl.mirror_incumbent()[0]
            record["final_accuracy"] = final_acc
            record["rounds"] = rounds
            record["info_deploy"] = \
                ctl.metrics.summary()["info"]["deploy"]
            verdicts = {r["tag"]: r["verdict"] for r in rounds}
            record["verdicts"] = verdicts
            record["gate_accepts"] = all(
                verdicts[t] == "accept"
                for t in ("clean-1", "clean-2", "clean-3"))
            record["regression_rejected"] = \
                verdicts["regression"] == "reject"
            record["rollback_proven"] = bool(
                verdicts["crash-midroll"] == "rolled_back"
                and crash["incumbent"] == rounds[1]["incumbent"]
                and byte_identical)
            record["crash_round_byte_identical"] = byte_identical
            record["accepted_improves"] = bool(
                bootstrap_acc is not None and final_acc is not None
                and final_acc > bootstrap_acc)
        finally:
            load.stop()
            record["client_load"] = {"ok": load.ok,
                                     "failures": load.failures}
            record["zero_failed_client_requests"] = \
                load.failures == 0 and ctl.mirror_failures == 0
            ctl.stop()
        record["canary_warm_s"] = [
            (r.get("canary") or {}).get("warm_s") for r in rounds]
        record["ok"] = all(record.get(g) for g in (
            "gate_accepts", "regression_rejected", "rollback_proven",
            "accepted_improves", "zero_failed_client_requests"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(
        REPO, "bench_evidence",
        "bench_deploy_quick.json" if args.quick
        else "bench_deploy.json")
    record = {
        "bench": "deploy",
        "backend": "cpu",
        "cpus": os.cpu_count(),
        "config": {"quick": bool(args.quick), "replicas": 2},
        "drill_semantics": (
            "One DeployController drives the real process tree "
            "(2 fleet replicas + 1 canary subprocess per round, AOT "
            "warm start).  Rounds: 2 clean fine-tunes (must accept "
            "and publish via rolling reload), 1 label-shuffled "
            "regression (must reject), 1 mid-roll replica kill via "
            "COS_FAULT_RELOAD_FAIL_RANK (must auto-rollback, "
            "incumbent byte-identical), 1 clean recovery round.  "
            "Background client load runs throughout; the "
            "zero-failed-client-requests gate counts its errors."),
        "ts": time.time(),
    }
    try:
        run(args, record)
    except Exception as e:   # noqa: BLE001 — always-exit-0 contract
        record["error"] = f"{type(e).__name__}: {e}"
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"bench": "deploy",
                      "verdicts": record.get("verdicts"),
                      "rollback_proven": record.get("rollback_proven"),
                      "zero_failed_client_requests":
                          record.get("zero_failed_client_requests"),
                      "accepted_improves":
                          record.get("accepted_improves"),
                      "ok": record.get("ok"),
                      "error": record.get("error"),
                      "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
