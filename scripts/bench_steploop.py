#!/usr/bin/env python
"""Fused multi-step loop benchmark: K=1 vs K>1 steps-per-loop
(BENCH-style JSON artifact).

Builds a synthetic encoded-JPEG LMDB and drives the REAL standalone
trainer (`mini_cluster.MiniCluster.train`) once per configured K
(`COS_STEPS_PER_LOOP`), identical data and solver config:

  K=1   legacy per-step dispatch: every solver iteration is its own
        Python→XLA call (plus staging handoff) and pays the fixed
        per-dispatch cost.
  K>1   fused chunks: K packed batches stack into one (K, batch…)
        block, `jax.lax.scan` runs K solver iterations in ONE XLA
        program (Solver.build_train_step_many), and the loop returns
        to Python once per chunk.

THE FLOOR MODELS PER-DISPATCH COST, NOT PER-STEP DEVICE TIME.
`COS_FAULT_STEP_DELAY_MS` (--step-floor-ms, default 45) sleeps once
per *dispatch* in the mini_cluster loop — a stand-in for a fixed
host→device round-trip (an assumed cost: the real gap between
launches on the chip is not measured, ROADMAP queue 1 item 6).
K=1 pays the floor every step, K=8 once per 8 steps —
exactly the overhead SparkNet-style iterations-per-loop amortizes.
The artifact also carries a floor=0 control run so the raw
CPU-backend ratio (dispatch savings only, expect ~1x on an idle box)
is committed next to the modeled one.

Environment pins (same recipe as bench_ingest.py, see
box-cpu-contention notes): XLA CPU limited to one intra-op thread,
COS_NATIVE=0 single-threaded decode, best-of-N alternating trials to
damp neighbor-tenant CPU-share swings.

Steady-state steps/s comes from each run's step-timeline metrics
(PipelineMetrics.mark_step — chunk-aware: K marks land per dispatch
and the rate counts marks after the measurement window opens), so
one-time jit compilation does not pollute the comparison.  Per-stage
series (queue-wait / pack / stack / stage / step / scan_step) of every
best run are embedded in the artifact.

Usage:
  python scripts/bench_steploop.py [--quick] [--out PATH]
"""

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("COS_NATIVE", "0")
_FLAG = "--xla_cpu_multi_thread_eigen=false"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FLAG).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_SCRIPTS = os.path.dirname(os.path.abspath(__file__))
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

from bench_ingest import build_lmdb, write_configs  # noqa: E402


def run_mode(k: int, solver: str, outdir: str,
             step_floor_ms: float, threads: int) -> dict:
    """One full MiniCluster.train run at COS_STEPS_PER_LOOP=k; returns
    throughput + metrics read back from the -pipeline_metrics
    artifact."""
    from caffeonspark_tpu.mini_cluster import MiniCluster, \
        build_argparser

    os.environ["COS_STEPS_PER_LOOP"] = str(k)
    os.environ["COS_TRANSFORM_THREADS"] = str(threads)
    if step_floor_ms > 0:
        os.environ["COS_FAULT_STEP_DELAY_MS"] = str(step_floor_ms)
    else:
        os.environ.pop("COS_FAULT_STEP_DELAY_MS", None)
    pm_path = os.path.join(outdir, f"pm_k{k}_{time.monotonic()}.json")
    args = build_argparser().parse_args(
        ["-solver", solver, "-output", outdir,
         "-model", os.path.join(outdir, f"k{k}.caffemodel"),
         "-pipeline_metrics", pm_path])
    t0 = time.perf_counter()
    MiniCluster(args).train()
    wall = time.perf_counter() - t0
    with open(pm_path) as f:
        metrics = json.load(f)
    out = {
        "steps_per_loop": k,
        "wall_s": round(wall, 3),
        "steady_steps_per_sec": metrics.get("steady_steps_per_sec"),
        "metrics": metrics,
    }
    print(f"  K={k}: {out['steady_steps_per_sec']} steps/s "
          f"steady-state ({wall:.1f}s wall, "
          f"floor {step_floor_ms:.0f}ms/dispatch)", flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller run for CI (fewer iters)")
    ap.add_argument("--out", default=None,
                    help="JSON artifact path (default bench_evidence/"
                    "bench_steploop[_quick].json)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--hw", type=int, default=48,
                    help="source image height=width (small: this bench "
                    "must be dispatch-bound, not ingest-bound)")
    ap.add_argument("--ks", default="1,8,32",
                    help="comma-separated steps-per-loop values "
                    "(first must be 1, the baseline)")
    ap.add_argument("--threads", type=int,
                    default=max(1, (os.cpu_count() or 2) - 1),
                    help="transformer-pool width (both modes)")
    ap.add_argument("--step-floor-ms", type=float, default=45.0,
                    help="per-DISPATCH wall-time floor modeling the "
                    "fixed host->device round-trip (an assumed cost, "
                    "not measured on a chip); 0 = off")
    ap.add_argument("--repeats", type=int, default=3,
                    help="trials per K (alternating); best-of wins — "
                    "damps CPU-share throttling noise on shared boxes")
    ap.add_argument("--cooldown", type=float, default=1.0)
    ap.add_argument("--no-floor0-control", action="store_true",
                    help="skip the floor=0 control pass")
    args = ap.parse_args(argv)

    ks = [int(x) for x in args.ks.split(",")]
    if ks[0] != 1:
        ap.error("--ks must start with 1 (the baseline)")
    iters = args.iters or (64 if args.quick else 160)
    # every K must divide into full chunks of the iteration budget
    # often enough to measure; iters is padded to a multiple of max K
    kmax = max(ks)
    iters = ((iters + kmax - 1) // kmax) * kmax
    crop = args.hw - 8
    out_path = args.out or os.path.join(
        REPO, "bench_evidence",
        "bench_steploop_quick.json" if args.quick
        else "bench_steploop.json")

    with tempfile.TemporaryDirectory() as tmp:
        n = max(4 * args.batch, 128)
        print(f"building synthetic JPEG LMDB: {n} x 3x{args.hw}x"
              f"{args.hw} ...", flush=True)
        lmdb = build_lmdb(tmp, n, 3, args.hw, args.hw)
        solver = write_configs(tmp, lmdb, args.batch, 3, args.hw,
                               args.hw, crop, iters)
        print(f"running {iters} iters, batch {args.batch}, "
              f"K in {ks}, floor {args.step_floor_ms}ms/dispatch, "
              f"{args.repeats} trial(s)/K ...", flush=True)
        trials = {k: [] for k in ks}
        for r in range(max(1, args.repeats)):
            for k in ks:
                if args.cooldown and (r or k != ks[0]):
                    time.sleep(args.cooldown)
                trials[k].append(run_mode(k, solver, tmp,
                                          args.step_floor_ms,
                                          args.threads))
        floor0 = None
        if not args.no_floor0_control and args.step_floor_ms > 0:
            print("floor=0 control (raw dispatch savings) ...",
                  flush=True)
            floor0 = {k: run_mode(k, solver, tmp, 0.0, args.threads)
                      for k in (1, ks[-1])}

    def best(k):
        return max(trials[k],
                   key=lambda t: t["steady_steps_per_sec"] or 0.0)

    bests = {k: best(k) for k in ks}
    base = bests[1]["steady_steps_per_sec"]
    speedups = {}
    for k in ks[1:]:
        b = bests[k]["steady_steps_per_sec"]
        speedups[f"k{k}_vs_k1"] = (round(b / base, 3)
                                   if base and b else None)
    record = {
        "bench": "steploop_fused",
        "backend": os.environ.get("JAX_PLATFORMS", ""),
        "cpus": os.cpu_count(),
        "config": {"iters": iters, "batch": args.batch, "hw": args.hw,
                   "crop": crop, "ks": ks, "threads": args.threads,
                   "step_floor_ms": args.step_floor_ms,
                   "repeats": args.repeats, "quick": bool(args.quick)},
        "floor_semantics": (
            "COS_FAULT_STEP_DELAY_MS sleeps once per DISPATCH in the "
            "mini_cluster loop: it models the fixed host->device "
            "round-trip (an assumed cost, not measured), which a "
            "fused K-chunk pays once per K steps. The floor0_control "
            "rows show the raw CPU-backend ratio without that model."),
        "results": {f"k{k}": bests[k] for k in ks},
        "all_trials": {f"k{k}": [t["steady_steps_per_sec"]
                                 for t in trials[k]] for k in ks},
        "speedups": speedups,
        "floor0_control": ({f"k{k}": {
            "steady_steps_per_sec": v["steady_steps_per_sec"],
            "wall_s": v["wall_s"]} for k, v in floor0.items()}
            if floor0 else None),
        "ts": time.time(),
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"bench": "steploop_fused", "speedups": speedups,
                      "k1_sps": base,
                      "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
