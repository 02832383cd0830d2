#!/usr/bin/env python
"""Multi-host scaling bench: two-tier `hier` vs flat `bucket`
gradient exchange across 4 emulated hosts under an asymmetric
comm floor.

Four REAL NodeAgent daemons (tools/nodeagent.py) stand in for four
hosts on this one box; each agent spawns TWO `mini_cluster` ranks of
an 8-process gloo cluster (1 CPU device per rank -> dp=8, "2 chips
per host", COS_FAULT_COMM_LOCAL=2), and every rank resolves the
jax.distributed coordinator through the LEAD agent's rendezvous
(`-server agent://...`) — the full host-spanning launch path, not a
local fork.

The controlled variable is the injected asymmetric comm floor
(tools/chaos.py).  The floor is CALIBRATED, not hard-coded: the
floor=0 control runs first and measures the emulated base step time,
which on one oversubscribed CPU is orders of magnitude slower than
the sub-ms accelerator step the gigabit regime actually feeds (the
fused multi-step loop reaches that on nets this size).  The gigabit
prices (8 ns/byte inter-host = 1 Gbit/s, 0.05 ns/byte intra-host)
are then time-dilated by that measured factor so the modeled
comm:compute RATIO — the thing the hierarchy argument is about — is
the real gigabit regime's, reproduced faithfully on slow hardware.
Under that floor the flat `bucket` exchange pays the full dense wire
per step on the slow link; the two-tier `hier` exchange (intra-host
reduce-scatter/all-gather + 1/local-sized inter-host leg,
`GradSyncPlan.tier_wire_bytes`) pays half the inter-host bytes plus
a near-free intra term, so its steps/s must come out >= 1.5x — the
FireCaffe-style hierarchy argument, priced end to end.  The floor=0
control doubles as the reality check: with no injected asymmetry the
two modes must be rate-equal (0.95-1.05x), proving the win comes
from the floor model and nothing else.

ALWAYS exits 0 with ONE JSON document on stdout;
the full artifact (gates embedded) lands in
bench_evidence/bench_scaling.json.

Usage:
  python scripts/bench_scaling.py [--quick] [--out PATH]
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_HOSTS = 4
RANKS_PER_HOST = 2          # intra-host group size (2 "chips"/host)
WORLD = N_HOSTS * RANKS_PER_HOST
MODES = ("bucket", "hier")

# The modeled fabric: 1 Gbit/s inter-host (8 ns/byte), ~100x faster
# intra-host links, feeding accelerator hosts that step this small
# net in ~0.4 ms (sub-ms per-step cost is exactly what the fused
# multi-step loop buys on tiny nets — see bench_steploop).  The
# injected floor scales these prices by measured_base_step /
# REF_STEP_S so the comm:compute ratio survives CPU emulation.
REF_INTER_NS_PER_BYTE = 8.0
REF_INTRA_NS_PER_BYTE = 0.05
REF_STEP_S = 0.0004
MAX_DILATION = 20000.0   # safety valve only: base/REF on one
                         # timeshared CPU legitimately reaches 10^3+


def write_configs(tmpdir: str, batch: int, iters: int,
                  display: int) -> str:
    """One small mlp job over a synthetic raw LMDB: ~51k params
    (~0.2 MB f32 wire).  Deliberately SMALL: the REAL gloo exchange
    cost is proportional to the wire and differs between bucket's
    one all-reduce and hier's two-phase decomposition, so a small
    wire keeps the floor=0 control mode-neutral on one CPU — the
    priced regime rides entirely on the injected (dilated) floor."""
    import numpy as np
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    n = 256
    imgs, labels = make_images(n, seed=11)
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
layer {{ name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
  inner_product_param {{ num_output: 64
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }}
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


def _start_agents(tmpdir: str):
    """Four NodeAgent subprocesses (= four emulated hosts); each
    prints its boot JSON line with the ephemeral API port."""
    agents = []
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    for i in range(N_HOSTS):
        p = subprocess.Popen(
            [sys.executable, "-m", "caffeonspark_tpu.tools.nodeagent",
             "-host", f"host{i}",
             "-blobDir", os.path.join(tmpdir, f"blobs{i}")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=REPO)
        boot = json.loads(p.stdout.readline())
        agents.append({"proc": p, "host": boot["agent"],
                       "url": boot["url"]})
    return agents


def _stop_agents(agents) -> None:
    """SIGTERM first (the agent's handler TERMs its child trees),
    SIGKILL stragglers — never leak a rank past the bench."""
    for a in agents:
        if a["proc"].poll() is None:
            a["proc"].terminate()
    deadline = time.monotonic() + 10
    for a in agents:
        while a["proc"].poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        if a["proc"].poll() is None:
            a["proc"].kill()
        a["proc"].communicate()


def run_mode(mode: str, solver: str, tmpdir: str, agents, *,
             iters: int, inter_ns: float, intra_ns: float,
             tag: str) -> dict:
    """One 4-host x 2-rank run: every rank spawned THROUGH its home
    agent (rank r lives on agents[r // RANKS_PER_HOST], so ranks
    sharing an emulated host are consecutive — the grouping
    COS_FAULT_COMM_LOCAL=2 prices).  Coordinator resolved via the
    lead agent.  Returns rank 0's steady steps/s + published info."""
    from caffeonspark_tpu.tools.nodeagent import AgentProc, agent_call

    floor = inter_ns > 0
    outdir = os.path.join(tmpdir, f"out_{mode}_{tag}")
    os.makedirs(outdir, exist_ok=True)
    pm0 = os.path.join(outdir, "pm_rank0.json")
    lead = agents[0]["url"]
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "",
           "COS_TRANSFORM_THREADS": "0",
           "COS_GRAD_SYNC": mode,
           "COS_FAULT_COMM_NS_PER_BYTE": str(inter_ns),
           "COS_FAULT_COMM_INTRA_NS_PER_BYTE": str(intra_ns),
           "COS_FAULT_COMM_LOCAL": str(RANKS_PER_HOST),
           "COS_FAULT_COMM_HIDE_BYTES": "0",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    coordinator = "agent://" + lead.split("://", 1)[1]
    procs = []
    for rank in range(WORLD):
        cmd = [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
               "-solver", solver, "-output", outdir,
               "-server", coordinator,
               "-cluster", str(WORLD), "-rank", str(rank),
               "-iterations", str(iters)]
        if rank == 0:
            cmd += ["-pipeline_metrics", pm0]
        home = agents[rank // RANKS_PER_HOST]
        doc = agent_call(home["url"], "/v1/spawn",
                         data={"argv": cmd, "env": env,
                               "name": f"{mode}-{tag}-rank{rank}"},
                         timeout=30.0)
        procs.append(AgentProc(home["url"], doc["proc"],
                               pid=doc["pid"]))
    t0 = time.perf_counter()
    try:
        rc0 = procs[0].wait(timeout=900)
        wall0 = time.perf_counter() - t0
        for p in procs[1:]:
            try:
                p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
        if rc0 != 0:
            tail = procs[0].info().get("tail") or []
            raise RuntimeError(f"{mode}/{tag}: rank 0 rc={rc0}:\n"
                               + "\n".join(tail[-25:]))
    except BaseException:
        for p in procs:
            p.kill()
        raise
    with open(pm0) as f:
        metrics = json.load(f)
    sps = metrics.get("steady_steps_per_sec")
    res = {"mode": mode, "floor": floor,
           "rank0_steady_steps_per_sec": sps,
           "rank0_wall_s": round(wall0, 2),
           "comm": metrics.get("info", {}).get("comm"),
           "faults": metrics.get("info", {}).get("faults")}
    print(f"  {mode:>6} ({'floor' if floor else 'ctl  '}, {tag}): "
          f"{sps} steps/s rank0 steady ({wall0:.1f}s wall)",
          file=sys.stderr, flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=None,
                    help="override control iters (floor cells run "
                         "a quarter, min 12: sleeps are "
                         "deterministic)")
    ap.add_argument("--batch", type=int, default=2048,
                    help="global batch (dp=8 shards it; BIG on "
                         "purpose: real compute must dwarf the "
                         "~20ms fixed cost of hier's extra gloo "
                         "collective wave on one oversubscribed "
                         "CPU, or the floor=0 control can never "
                         "be rate-equal)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="trials per cell (alternating order); gate "
                         "ratios pair same-repeat trials, per-cell "
                         "reporting is best-of")
    args = ap.parse_args(argv)

    ctl_iters = args.iters or (24 if args.quick else 40)
    floor_iters = max(12, ctl_iters // 4)
    repeats = 1 if args.quick else max(1, args.repeats)
    out_path = args.out or os.path.join(
        REPO, "bench_evidence",
        "bench_scaling_quick.json" if args.quick
        else "bench_scaling.json")

    record = {
        "bench": "scaling",
        "backend": "cpu",
        "cpus": os.cpu_count(),
        "config": {"hosts": N_HOSTS,
                   "ranks_per_host": RANKS_PER_HOST,
                   "world": WORLD,
                   "control_iters": ctl_iters,
                   "floor_iters": floor_iters,
                   "batch": args.batch,
                   "ref_inter_ns_per_byte": REF_INTER_NS_PER_BYTE,
                   "ref_intra_ns_per_byte": REF_INTRA_NS_PER_BYTE,
                   "ref_step_s": REF_STEP_S,
                   "repeats": repeats, "quick": bool(args.quick)},
        "floor_semantics": (
            "Four NodeAgent daemons emulate four hosts; each spawns "
            "two mini_cluster ranks (dp=8, COS_FAULT_COMM_LOCAL=2 = "
            "ranks per host) and the coordinator comes from the lead "
            "agent's rendezvous.  This box is one machine, so the "
            "cross-host asymmetry is INJECTED and CALIBRATED: the "
            "floor=0 control measures the emulated base step time, "
            "and the gigabit prices (8 ns/byte inter-host, 0.05 "
            "ns/byte intra-host) are time-dilated by base_step/"
            f"{REF_STEP_S}s — one CPU timesharing 8 ranks steps far "
            "slower than the sub-ms accelerator step a real gigabit "
            "fabric feeds on a net this size (the fused multi-step "
            "loop's regime), and an undilated floor would vanish "
            "into that slowdown, testing nothing.  Dilation "
            "preserves the modeled comm:compute RATIO, which is "
            "what the hierarchy "
            "argument is about (GradSyncPlan.tier_wire_bytes x "
            "CommFloor.sleep_seconds, tools/chaos.py) — the same "
            "controlled-variable technique as bench_gradsync's flat "
            "floor.  bucket pays the full dense wire on the slow "
            "link; hier pays the 1/local inter-host slice plus a "
            "near-free intra term.  The floor=0 control doubles as "
            "the reality check: any rate gap there would be model "
            "error, not hierarchy win.  Gate ratios are medians of "
            "same-repeat hier/bucket pairs (mode order alternating "
            "per repeat) because this box's CPU share drifts over a "
            "multi-minute run — the bench_obs adjacent-window "
            "technique."),
        "ts": time.time(),
    }
    try:
        with tempfile.TemporaryDirectory() as tmp:
            print(f"building job: {N_HOSTS} hosts x "
                  f"{RANKS_PER_HOST} ranks, ctl {ctl_iters} / floor "
                  f"{floor_iters} iters, batch {args.batch}, "
                  f"{repeats} trial(s)/cell ...",
                  file=sys.stderr, flush=True)
            solver = write_configs(tmp, args.batch,
                                   max(ctl_iters, floor_iters),
                                   display=8)
            agents = _start_agents(tmp)
            trials = {(m, fl): [] for m in MODES
                      for fl in (True, False)}
            try:
                # Throwaway warmup: the first cluster after agent
                # boot pays import-storm and page-cache contention
                # its successors do not — measuring it would bias
                # whichever mode runs first.
                run_mode("bucket", solver, tmp, agents, iters=6,
                         inter_ns=0.0, intra_ns=0.0, tag="warmup")

                # Phase 1 — floor=0 controls: rate-equality gate AND
                # the calibration measurement for the floor prices.
                # Mode order alternates per repeat so best-of cancels
                # any residual first-runner handicap.
                for r in range(repeats):
                    order = MODES if r % 2 == 0 \
                        else tuple(reversed(MODES))
                    for m in order:
                        trials[(m, False)].append(run_mode(
                            m, solver, tmp, agents, iters=ctl_iters,
                            inter_ns=0.0, intra_ns=0.0, tag=f"t{r}"))

                base = max((t["rank0_steady_steps_per_sec"] or 0.0)
                           for t in trials[("bucket", False)])
                if base <= 0:
                    raise RuntimeError(
                        "control run produced no steady rate; "
                        "cannot calibrate the floor")
                base_step_s = 1.0 / base
                dilation = min(MAX_DILATION,
                               max(1.0, base_step_s / REF_STEP_S))
                inter_ns = REF_INTER_NS_PER_BYTE * dilation
                intra_ns = REF_INTRA_NS_PER_BYTE * dilation
                record["calibration"] = {
                    "base_steps_per_sec": round(base, 3),
                    "base_step_s": round(base_step_s, 4),
                    "dilation": round(dilation, 2),
                    "inter_ns_per_byte": round(inter_ns, 2),
                    "intra_ns_per_byte": round(intra_ns, 3)}
                print(f"calibration: base {base:.2f} steps/s -> "
                      f"dilation {dilation:.1f}x, floor "
                      f"{inter_ns:.0f}/{intra_ns:.2f} ns/B",
                      file=sys.stderr, flush=True)

                # Phase 2 — the priced cells (same alternation).
                for r in range(repeats):
                    order = MODES if r % 2 == 0 \
                        else tuple(reversed(MODES))
                    for m in order:
                        trials[(m, True)].append(run_mode(
                            m, solver, tmp, agents, iters=floor_iters,
                            inter_ns=inter_ns, intra_ns=intra_ns,
                            tag=f"t{r}"))
            finally:
                _stop_agents(agents)

            def best(ts):
                return max(ts, key=lambda t:
                           t["rank0_steady_steps_per_sec"] or 0.0)

            results = {}
            for (m, fl), ts in trials.items():
                if ts:
                    results[f"{m}_{'floor' if fl else 'control'}"] \
                        = best(ts)
            record["results"] = results
            record["all_trials"] = {
                f"{m}_{'floor' if fl else 'control'}":
                    [t["rank0_steady_steps_per_sec"] for t in ts]
                for (m, fl), ts in trials.items() if ts}

            # Gate ratios are the MEDIAN of per-repeat adjacent-pair
            # ratios (hier[r]/bucket[r]) — the bench_obs technique:
            # this box's CPU share drifts over a multi-minute run,
            # so comparing each mode against its own-repeat partner
            # cancels the drift that a cross-session best-of cannot.
            def pair_ratios(fl):
                hs = [t["rank0_steady_steps_per_sec"]
                      for t in trials[("hier", fl)]]
                bs = [t["rank0_steady_steps_per_sec"]
                      for t in trials[("bucket", fl)]]
                return [round(h / b, 3)
                        for h, b in zip(hs, bs) if h and b]

            def median(xs):
                if not xs:
                    return None
                s = sorted(xs)
                n = len(s)
                return round(s[n // 2] if n % 2
                             else (s[n // 2 - 1] + s[n // 2]) / 2, 3)

            fpairs, cpairs = pair_ratios(True), pair_ratios(False)
            record["floor_pair_ratios"] = fpairs
            record["control_pair_ratios"] = cpairs
            ratio = median(fpairs)
            record["hier_vs_bucket_at_floor"] = ratio
            record["gate_hier_1_5x"] = (ratio is not None
                                        and ratio >= 1.5)
            cratio = median(cpairs)
            record["hier_vs_bucket_control"] = cratio
            record["gate_control_rate_equal"] = (
                None if cratio is None else 0.95 <= cratio <= 1.05)
    except Exception as e:   # noqa: BLE001 — always-exit-0 contract
        record["error"] = f"{type(e).__name__}: {e}"

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({
        "bench": "scaling",
        "hier_vs_bucket_at_floor":
            record.get("hier_vs_bucket_at_floor"),
        "gate_hier_1_5x": record.get("gate_hier_1_5x"),
        "hier_vs_bucket_control":
            record.get("hier_vs_bucket_control"),
        "gate_control_rate_equal":
            record.get("gate_control_rate_equal"),
        "error": record.get("error"),
        "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
