#!/usr/bin/env python
"""Straggler-tolerance benchmark: COS_SYNC_MODE=lockstep vs
local_sgd vs async under one injected 5x-slow rank.

Two REAL `mini_cluster` rank processes train the same tiny job; rank 1
carries `COS_FAULT_SLOW_RANK=1:<factor>` (tools/chaos.py — every step
is followed by a sleep of (factor-1)x the measured step time, so the
rank runs factor× slower end to end).  The measured quantity is RANK
0's steady steps/s:

  lockstep   both ranks join one jax.distributed mesh; the per-step
             gradient all-reduce couples them, so rank 0 is dragged to
             the straggler's rate — the baseline this repo had;
  local_sgd  no global mesh; K local steps then a soft-barrier round
             average (parallel/syncmode.py).  The straggler detaches
             after falling a round behind and rank 0 runs free;
  async      no barrier at all; rank 0 merges into the versioned
             global state every S steps and never waits for rank 1.

The slow factor is the controlled variable, exactly like the 45 ms
dispatch floor in bench_steploop and the comm floor in bench_gradsync:
this box is CPU-only and homogeneous, so heterogeneity is injected.
A factor=1 control (healthy pack, no injection) rides in the artifact
so the no-straggler overhead of the relaxed modes is committed next to
the headline ratio.

ALWAYS exits 0 with ONE JSON document on stdout;
the full artifact lands in bench_evidence/bench_syncmode.json.

Usage:
  python scripts/bench_syncmode.py [--quick] [--out PATH]
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

MODES = ("lockstep", "local_sgd", "async")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def write_configs(tmpdir: str, batch: int, iters: int,
                  display: int) -> str:
    """Tiny conv+fc job over a synthetic raw LMDB (the exchange cost
    is not the variable here — the straggler coupling is)."""
    import numpy as np
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    n = 256
    imgs, labels = make_images(n, seed=5)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(n)]
    lmdb = os.path.join(tmpdir, "lmdb")
    LmdbWriter(lmdb).write(recs)
    net = os.path.join(tmpdir, "net.prototxt")
    with open(net, "w") as f:
        f.write(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{lmdb}" batch_size: {batch}
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
  bottom: "label" top: "loss" }}''')
    solver = os.path.join(tmpdir, "solver.prototxt")
    with open(solver, "w") as f:
        f.write(f'net: "{net}"\nbase_lr: 0.01\nmomentum: 0.9\n'
                f'lr_policy: "fixed"\ndisplay: {display}\n'
                f'max_iter: {iters}\nsnapshot_prefix: "bench"\n'
                'random_seed: 3\n')
    return solver


def run_mode(mode: str, solver: str, tmpdir: str, *, iters: int,
             k: int, slow_factor: float, tag: str) -> dict:
    """One 2-rank run; returns rank 0's steady steps/s + sync info."""
    outdir = os.path.join(tmpdir, f"out_{mode}_{tag}")
    os.makedirs(outdir, exist_ok=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "COS_TRANSFORM_THREADS": "0",
           "COS_SYNC_MODE": mode,
           "COS_SYNC_K": str(k), "COS_SYNC_STALENESS": str(k),
           "COS_SYNC_HEARTBEAT_TIMEOUT_S": "4",
           # short round patience: the straggler costs the pack ONE
           # timeout, then sticky detachment frees it (syncmode.py)
           "COS_SYNC_ROUND_TIMEOUT_S": "1.0",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    if slow_factor > 1:
        env["COS_FAULT_SLOW_RANK"] = f"1:{slow_factor}"
    port = _free_port()
    pm0 = os.path.join(outdir, "pm_rank0.json")
    procs = []
    for rank in (0, 1):
        cmd = [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
               "-solver", solver, "-output", outdir,
               "-server", f"127.0.0.1:{port}",
               "-cluster", "2", "-rank", str(rank),
               "-iterations", str(iters)]
        if rank == 0:
            cmd += ["-pipeline_metrics", pm0]
        procs.append(subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=REPO))
    t0 = time.perf_counter()
    try:
        out0, _ = procs[0].communicate(timeout=900)
        wall0 = time.perf_counter() - t0
        # rank 1 (the straggler) finishes on its own in every mode —
        # lockstep couples it to rank 0, the relaxed modes
        # fast-forward it to the pack's clock at its next exchange
        try:
            procs[1].communicate(timeout=300)
        except subprocess.TimeoutExpired:
            procs[1].kill()
            procs[1].communicate()
        if procs[0].returncode != 0:
            raise RuntimeError(
                f"{mode}: rank 0 failed:\n{out0[-2000:]}")
    except BaseException:
        # never leak a rank past the always-exit-0 bench: an orphaned
        # jax process poisons every later run on this box
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
        raise
    with open(pm0) as f:
        metrics = json.load(f)
    sps = metrics.get("steady_steps_per_sec")
    res = {
        "mode": mode,
        "rank0_steady_steps_per_sec": sps,
        "rank0_wall_s": round(wall0, 2),
        "sync": metrics.get("info", {}).get("sync"),
        "faults": metrics.get("info", {}).get("faults"),
    }
    print(f"  {mode:>9} (slow x{slow_factor:g}): "
          f"{sps} steps/s rank0 steady ({wall0:.1f}s wall)",
          file=sys.stderr, flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--k", type=int, default=8,
                    help="COS_SYNC_K / COS_SYNC_STALENESS")
    ap.add_argument("--slow-factor", type=float, default=5.0)
    ap.add_argument("--repeats", type=int, default=2,
                    help="trials per mode (alternating); best-of wins")
    ap.add_argument("--modes", default=",".join(MODES))
    ap.add_argument("--no-control", action="store_true",
                    help="skip the factor=1 healthy-pack control")
    args = ap.parse_args(argv)

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    if modes[0] != "lockstep":
        ap.error("--modes must start with lockstep (the baseline)")
    # long enough that the one-time detachment transient (local_sgd
    # pays ONE first-round patience before the straggler detaches)
    # amortizes out of the steady rate
    iters = args.iters or (96 if args.quick else 160)
    repeats = 1 if args.quick else max(1, args.repeats)
    out_path = args.out or os.path.join(
        REPO, "bench_evidence",
        "bench_syncmode_quick.json" if args.quick
        else "bench_syncmode.json")

    record = {
        "bench": "syncmode",
        "backend": "cpu",
        "cpus": os.cpu_count(),
        "config": {"iters": iters, "batch": args.batch, "k": args.k,
                   "slow_factor": args.slow_factor, "modes": modes,
                   "repeats": repeats, "quick": bool(args.quick)},
        "floor_semantics": (
            "COS_FAULT_SLOW_RANK=1:<factor> makes rank 1 factor-x "
            "slower (post-step sleep of (factor-1)x the measured step "
            "time, tools/chaos.py).  This box is CPU-only and "
            "homogeneous, so the straggler is the injected controlled "
            "variable — same technique as bench_steploop's dispatch "
            "floor and bench_gradsync's comm floor.  Measured: rank "
            "0's steady steps/s.  lockstep couples rank 0 to the "
            "straggler through the per-step all-reduce; local_sgd "
            "detaches it after one round; async never waits at all.  "
            "The control block repeats the sweep with NO slow rank "
            "(relaxed-mode overhead check)."),
        "ts": time.time(),
    }
    try:
        with tempfile.TemporaryDirectory() as tmp:
            print(f"building job: {iters} iters, batch {args.batch}, "
                  f"K={args.k}, slow x{args.slow_factor}, "
                  f"{repeats} trial(s)/mode ...",
                  file=sys.stderr, flush=True)
            solver = write_configs(tmp, args.batch, iters,
                                   display=max(2, args.k // 2))
            trials = {m: [] for m in modes}
            for r in range(repeats):
                for m in modes:
                    trials[m].append(run_mode(
                        m, solver, tmp, iters=iters, k=args.k,
                        slow_factor=args.slow_factor,
                        tag=f"t{r}"))

            def best(ts):
                return max(ts, key=lambda t:
                           t["rank0_steady_steps_per_sec"] or 0.0)

            bests = {m: best(trials[m]) for m in modes}
            base = bests["lockstep"]["rank0_steady_steps_per_sec"]
            speedups = {}
            for m in modes[1:]:
                b = bests[m]["rank0_steady_steps_per_sec"]
                speedups[f"{m}_vs_lockstep"] = (
                    round(b / base, 3) if base and b else None)
            record["results"] = bests
            record["all_trials"] = {
                m: [t["rank0_steady_steps_per_sec"]
                    for t in trials[m]] for m in modes}
            record["speedups"] = speedups
            record["gate_3x"] = all(
                (speedups.get(f"{m}_vs_lockstep") or 0) >= 3.0
                for m in modes[1:]) if len(modes) > 1 else None

            if not args.no_control:
                print("factor=1 control (healthy pack) ...",
                      file=sys.stderr, flush=True)
                control = {}
                for m in modes:
                    c = run_mode(m, solver, tmp, iters=iters,
                                 k=args.k, slow_factor=1.0,
                                 tag="ctl")
                    control[m] = c["rank0_steady_steps_per_sec"]
                c0 = control.get("lockstep")
                record["control_no_straggler"] = {
                    m: {"steady_steps_per_sec": v,
                        "vs_lockstep": (round(v / c0, 3)
                                        if c0 and v else None)}
                    for m, v in control.items()}
    except Exception as e:   # noqa: BLE001 — always-exit-0 contract
        record["error"] = f"{type(e).__name__}: {e}"

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"bench": "syncmode",
                      "speedups": record.get("speedups"),
                      "gate_3x": record.get("gate_3x"),
                      "error": record.get("error"),
                      "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
