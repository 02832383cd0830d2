#!/usr/bin/env python
"""Online serving benchmark: dynamic micro-batching vs batch=1
dispatch (BENCH-style JSON artifact).

Drives the REAL serving stack (InferenceService → MicroBatcher →
bucketed jitted forward) with closed-loop client threads at several
offered-load levels, once per bucket configuration:

  serve_b1    max_batch=1 — every request is its own dispatch; the
              per-request cost is the full fixed pack+dispatch+fetch
              overhead ("RPC Considered Harmful" worst case)
  serve_b8    max_batch=8 — micro-batching amortizes the fixed cost
              over up to 8 coalesced requests
  serve_b64   max_batch=64 — deeper amortization (quick mode: b32)

Per (config, offered-load) cell: sustained throughput (rows/s
completed over the measurement window) and client-observed p50/p99
latency from the service's own metrics (the same PipelineMetrics
JSON the trainer dumps).  The headline `speedup_at_saturation` is
max-load batched throughput / max-load batch=1 throughput — the
dynamic-batching win the serving subsystem exists to capture.

Environment pins (box-cpu-contention recipe, same as
bench_steploop.py): XLA CPU single intra-op thread, best-of-N trials
per cell to damp neighbor-tenant CPU-share swings.

Multi-replica mode (`--fleet N`, `make bench-serving-fleet`): drives
the REAL fleet stack (N `-serve` subprocesses behind the
least-outstanding router) and reports, in one always-exit-0 JSON
document (`bench_evidence/bench_serving_fleet.json`):
  * AOT warm start — replica 1 cold (fills the persistent compilation
    cache), the fleet's replicas warm (cache hits); both warmup wall
    times plus the cache-entry delta (0 added = pure hits), with
    COS_RECOMPILE_GUARD=1 armed inside every replica;
  * offered-load sweep — rows/s + client-observed p50/p99 per load
    level, with per-replica utilization (request share);
  * fault injection — one replica SIGKILLed under load: failed client
    requests (target 0 — router retries absorb it), restart count and
    warm-rejoin wall time.

Usage:
  python scripts/bench_serving.py [--quick] [--out PATH] [--fleet N]
"""

import argparse
import json
import os
import platform
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_cpu_multi_thread_eigen=false"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FLAG).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

NET_TMPL = """
name: "servenet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{root}/unused_lmdb" batch_size: 64
    channels: 3 height: 24 width: 24 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 16 kernel_size: 5 stride: 2
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

SOLVER_TMPL = """
net: "{net}"
base_lr: 0.01
lr_policy: "fixed"
max_iter: 10
random_seed: 7
"""


def build_model(td: str):
    """Write prototxts + a filler-initialized caffemodel (throughput
    does not care about trained weights)."""
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    net_path = os.path.join(td, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(NET_TMPL.format(root=td))
    solver_path = os.path.join(td, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(SOLVER_TMPL.format(net=net_path))
    s = Solver(SolverParameter.from_text(SOLVER_TMPL.format(net=net_path)),
               NetParameter.from_text(NET_TMPL.format(root=td)))
    params, _ = s.init()
    model = os.path.join(td, "serve.caffemodel")
    checkpoint.save_caffemodel(model, s.train_net, params)
    return solver_path, model


def run_cell(solver_path: str, model: str, max_batch: int,
             clients: int, duration_s: float, max_wait_ms: float
             ) -> dict:
    """One (bucket config, offered load) measurement: `clients`
    closed-loop threads submit-and-wait for `duration_s`."""
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.serving import InferenceService
    conf = Config(["-conf", solver_path, "-model", model])
    svc = InferenceService(conf, blob_names=("ip2",),
                           max_batch=max_batch,
                           max_wait_ms=max_wait_ms,
                           queue_depth=max(64, 4 * max_batch))
    svc.start(warmup=True)
    rec = ("r", 0.0, 3, 24, 24, False,
           (np.random.RandomState(0).rand(3, 24, 24)
            .astype(np.float32) * 255.0))
    stop = threading.Event()
    counts = [0] * clients
    rejects = [0] * clients

    def client(i):
        while not stop.is_set():
            try:
                svc.submit(rec).wait(60.0)
                counts[i] += 1
            except Exception:      # noqa: BLE001 — queue-full backoff
                rejects[i] += 1
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=90)
    elapsed = time.monotonic() - t0
    svc.stop(drain=True)
    m = svc.metrics_summary()
    lat = m["stages"].get("latency", {})
    served = sum(counts)
    return {
        "max_batch": max_batch, "clients": clients,
        "duration_s": round(elapsed, 3),
        "rows_per_sec": round(served / elapsed, 2),
        "served": served, "rejected": sum(rejects),
        "p50_ms": lat.get("p50_ms"), "p95_ms": lat.get("p95_ms"),
        "p99_ms": lat.get("p99_ms"),
        "flushes": m["counters"].get("flushes", 0),
        "mean_batch_fill": m["queue_depths"]
        .get("batch_fill", {}).get("mean"),
        "buckets": m["buckets"],
    }


# ---------------------------------------------------------------------------
# sharded serving (--tp N): zero-gather swap vs host-gather baseline
# ---------------------------------------------------------------------------

BIG_NET_TMPL = """
name: "shardservenet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{root}/unused_lmdb" batch_size: 16
    channels: 3 height: 24 width: 24 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 16 kernel_size: 5 stride: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "fc1" type: "InnerProduct" bottom: "conv1" top: "fc1"
  inner_product_param {{ num_output: {fc}
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu2" type: "ReLU" bottom: "fc1" top: "fc1" }}
layer {{ name: "fc2" type: "InnerProduct" bottom: "fc1" top: "fc2"
  inner_product_param {{ num_output: {fc}
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu3" type: "ReLU" bottom: "fc2" top: "fc2" }}
layer {{ name: "ip" type: "InnerProduct" bottom: "fc2" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}
"""


def build_big_model(td: str, fc: int):
    """An fc-heavy net (the tp-shardable regime: two fc x fc
    InnerProducts dominate the parameter bytes, the vgg/alexnet fc6/7
    shape class) + a filler-initialized dense caffemodel."""
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    net_path = os.path.join(td, "net.prototxt")
    with open(net_path, "w") as f:
        f.write(BIG_NET_TMPL.format(root=td, fc=fc))
    solver_path = os.path.join(td, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(SOLVER_TMPL.format(net=net_path))
    s = Solver(SolverParameter.from_text(SOLVER_TMPL.format(net=net_path)),
               NetParameter.from_text(BIG_NET_TMPL.format(root=td,
                                                          fc=fc)))
    params, _ = s.init()
    model = os.path.join(td, "serve.caffemodel")
    checkpoint.save_caffemodel(model, s.train_net, params)
    n_params = sum(
        int(np.prod(shape)) for specs in s.train_net.param_layout.values()
        for _, shape, _ in specs)
    return solver_path, model, n_params


def main_tp_worker(args) -> int:
    """Subprocess body for one swap-path measurement: `--tp-worker
    write` shards the dense model onto the mesh once; `gather` repeats
    the host-gather swap (dense parse + full host copy + placement —
    the pre-mesh route); `streamed` repeats the zero-gather mesh load
    (with the dense-host helpers poisoned, so the artifact re-proves
    the path never touches them).  Each mode runs in its OWN process
    so ru_maxrss is a clean per-path peak-RSS measurement."""
    import resource
    import jax
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.parallel import MeshLayout, build_mesh
    from caffeonspark_tpu.serving.registry import build_serving_net

    conf = Config(["-conf", args.solver])
    net = build_serving_net(conf.netParam, conf.solverParameter)
    layout = MeshLayout(net, build_mesh(tp=args.tp))
    mode = args.tp_worker
    if mode == "write":
        params = checkpoint.load_serving_params(net, args.model,
                                                layout=layout)
        checkpoint.save_sharded_caffemodel(
            args.model_sharded, net, params, force_shards=True)
        print(json.dumps({"mode": "write", "ok": True}))
        return 0

    if mode == "streamed":
        def boom(*a, **k):
            raise AssertionError("dense-host path touched on the "
                                 "streamed load path")
        checkpoint.gather_params_if_sharded = boom
        checkpoint._dense_host_param = boom
        checkpoint.load_caffemodel_blobs = boom

    walls = []
    current = None
    for _ in range(args.swaps):
        t0 = time.monotonic()
        if mode == "gather":
            host = checkpoint.load_serving_params(net, args.model)
            new = layout.place_params(host)
        else:
            new = checkpoint.load_serving_params(
                net, args.model_sharded, layout=layout)
        jax.block_until_ready(new)
        walls.append(time.monotonic() - t0)
        # hot-swap reality: the OLD version stays referenced (serving
        # in-flight flushes) until the new one is live
        current = new                                    # noqa: F841
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(json.dumps({
        "mode": mode, "tp": args.tp, "swaps": args.swaps,
        "swap_wall_s": [round(w, 4) for w in walls],
        "swap_wall_s_mean": round(sum(walls) / len(walls), 4),
        "swap_wall_s_min": round(min(walls), 4),
        "peak_rss_mb": round(peak_kb / 1024.0, 1),
        "dense_path_poisoned": mode == "streamed",
    }))
    return 0


def main_sharded(args) -> int:
    """--tp N: sharded-serving swap bench — ALWAYS exits 0 with ONE
    JSON document on stdout.  Headline: hot-swap
    wall time + peak host RSS, host-gather baseline vs zero-gather
    shard streaming, on the largest fc-heavy model the budget
    allows."""
    import subprocess
    import tempfile
    fc = 1024 if args.quick else 4096
    swaps = 2 if args.quick else 3
    out = {"bench": "serving_sharded", "tp": args.tp,
           "quick": args.quick,
           "env": {"platform": platform.platform(),
                   "python": sys.version.split()[0],
                   "cpu_count": os.cpu_count()},
           "notes": "CPU box: devices are XLA host-platform virtual "
                    "chips, so 'device' placement is host RAM — the "
                    "wall-time and transient-buffer comparison (full "
                    "dense parse+copy vs per-shard slab streaming) is "
                    "the signal; on real HBM the gather baseline "
                    "additionally pays a full-size host staging "
                    "buffer the streamed path never allocates",
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime())}
    try:
        td = tempfile.mkdtemp(prefix="cos_shard_bench_")
        solver_path, model, n_params = build_big_model(td, fc)
        sharded = os.path.join(td, "serve_sharded.caffemodel")
        out["model"] = {"fc": fc, "params": n_params,
                        "param_mb": round(n_params * 4 / 2**20, 1),
                        "caffemodel_mb": round(
                            os.path.getsize(model) / 2**20, 1)}
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS":
               f"{_FLAG} --xla_force_host_platform_device_count"
               f"={args.tp}"}

        def run_worker(mode):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--tp-worker", mode, "--tp", str(args.tp),
                   "--swaps", str(swaps), "--solver", solver_path,
                   "--model", model, "--model-sharded", sharded]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env=env, timeout=900)
            if r.returncode != 0:
                raise RuntimeError(
                    f"{mode} worker rc={r.returncode}: "
                    f"{r.stderr[-800:]}")
            cell = json.loads(r.stdout.strip().splitlines()[-1])
            print(json.dumps(cell), file=sys.stderr, flush=True)
            return cell

        run_worker("write")
        out["sidecar_mb"] = round(sum(
            os.path.getsize(os.path.join(td, n)) / 2**20
            for n in os.listdir(td) if ".shard" in n), 1)
        gather = run_worker("gather")
        streamed = run_worker("streamed")
        out["cells"] = {"gather": gather, "streamed": streamed}
        out["headline"] = {
            "metric": "hot_swap_wall_s_and_peak_rss",
            "gather_swap_wall_s": gather["swap_wall_s_mean"],
            "streamed_swap_wall_s": streamed["swap_wall_s_mean"],
            "swap_speedup": round(
                gather["swap_wall_s_mean"]
                / streamed["swap_wall_s_mean"], 2)
            if streamed["swap_wall_s_mean"] else None,
            # steady-state (best-of): excludes the gather path's
            # once-per-process filler-init compile — the repeated-
            # hot-swap regime both paths settle into
            "swap_speedup_steady": round(
                gather["swap_wall_s_min"]
                / streamed["swap_wall_s_min"], 2)
            if streamed["swap_wall_s_min"] else None,
            "gather_peak_rss_mb": gather["peak_rss_mb"],
            "streamed_peak_rss_mb": streamed["peak_rss_mb"],
            "rss_saving_mb": round(gather["peak_rss_mb"]
                                   - streamed["peak_rss_mb"], 1),
            "zero_gather_proven": streamed["dense_path_poisoned"],
        }
    except Exception as e:      # noqa: BLE001 — artifact over rc
        out["error"] = f"{type(e).__name__}: {e}"
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


# ---------------------------------------------------------------------------
# multi-model mode (--multimodel): quantized residency + LRU HBM paging
# ---------------------------------------------------------------------------

MM_NET_TMPL = """
name: "mmnet"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{root}/unused_lmdb" batch_size: 8
    channels: 1 height: 12 width: 12 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 8 kernel_size: 3
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "ip" type: "InnerProduct" bottom: "conv1" top: "ip"
  inner_product_param {{ num_output: {fc}
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}
"""


def build_model_family(td: str, n: int, fc: int):
    """One prototxt (one net digest → ONE compiled program set shared
    by every model, the fact that keeps paging recompile-free), n
    caffemodels with differently-seeded weights (n tenants/arms)."""
    import jax
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.proto import NetParameter
    from caffeonspark_tpu.serving.registry import build_serving_net
    net_path = os.path.join(td, "mmnet.prototxt")
    with open(net_path, "w") as f:
        f.write(MM_NET_TMPL.format(root=td, fc=fc))
    solver_path = os.path.join(td, "mmsolver.prototxt")
    with open(solver_path, "w") as f:
        f.write(SOLVER_TMPL.format(net=net_path))
    net = build_serving_net(
        NetParameter.from_text(MM_NET_TMPL.format(root=td, fc=fc)))
    models = []
    for i in range(n):
        params = net.init(jax.random.key(1000 + i))
        path = os.path.join(td, f"tenant{i}.caffemodel")
        checkpoint.save_caffemodel(path, net, params)
        models.append(path)
    return solver_path, net_path, models, net


def mm_build_service(solver_path, models, weight_dtype, budget_mb,
                     max_batch, env_extra=None):
    """A fresh multi-model InferenceService: tenant0 is the default
    model, tenant1..k ride as named models (one flush lane each)."""
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.serving import InferenceService
    env = {"COS_SERVE_WEIGHT_DTYPE": weight_dtype,
           "COS_SERVE_HBM_BUDGET_MB": str(budget_mb),
           "COS_RECOMPILE_GUARD": "1"}
    env.update(env_extra or {})
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        svc = InferenceService(
            Config(["-conf", solver_path, "-model", models[0]]),
            blob_names=("ip",), max_batch=max_batch, max_wait_ms=1.0,
            queue_depth=max(64, 4 * max_batch))
        for i, path in enumerate(models[1:], start=1):
            svc.add_model(f"tenant{i}",
                          Config(["-conf", solver_path,
                                  "-model", path]),
                          blob_names=("ip",))
        svc.start(warmup=True)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return svc


def mm_load_cell(svc, names, clients, duration_s) -> dict:
    """Closed-loop round-robin traffic ACROSS the model set — the
    multi-tenant access pattern that makes an over-budget resident set
    thrash.  Client-observed latency includes any page-in the request
    triggered (that IS the tenant experience)."""
    rec = ("r", 0.0, 1, 12, 12, False,
           (np.random.RandomState(0).rand(1, 12, 12)
            .astype(np.float32) * 255.0))
    stop = threading.Event()
    lats = [[] for _ in range(clients)]
    errors = [0] * clients

    def client(ci):
        i = ci                       # stagger the round-robin phase
        while not stop.is_set():
            name = names[i % len(names)]
            i += 1
            t0 = time.monotonic()
            try:
                svc.submit(rec, model=name).wait(60.0)
                lats[ci].append(time.monotonic() - t0)
            except Exception:        # noqa: BLE001 — counted
                errors[ci] += 1
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    elapsed = time.monotonic() - t0
    all_lats = sorted(x for ls in lats for x in ls)

    def pct(p):
        return round(1e3 * all_lats[min(len(all_lats) - 1,
                                        int(p * len(all_lats)))], 3) \
            if all_lats else None

    stats = svc.registry.model_stats()
    page = svc.metrics.summary()["stages"].get("page_in", {})
    return {
        "models": len(names), "clients": clients,
        "duration_s": round(elapsed, 3),
        "rows_per_sec": round(len(all_lats) / elapsed, 2),
        "served": len(all_lats), "failed": sum(errors),
        "p50_ms": pct(0.50), "p99_ms": pct(0.99),
        "evictions": sum(s["evictions"] for s in stats.values()),
        "page_ins": sum(s["page_ins"] for s in stats.values()),
        "page_in_mean_ms": page.get("mean_ms"),
        "page_in_p99_ms": page.get("p99_ms"),
    }


def mm_drift_table(nets_and_params, tol) -> list:
    """Per-(net, weight_dtype) accuracy drift vs the f32 forward on
    seeded inputs — the publish gate's own measurement, reported per
    zoo net so the artifact carries the evidence."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.serving import ModelRegistry
    rows = []
    for label, net, params in nets_and_params:
        regf = ModelRegistry(net, weight_dtype="f32",
                             hbm_budget_bytes=0)
        mvf = regf.publish(params, "f32")
        outs = tuple(net.output_blobs)
        rng = np.random.RandomState(0)
        inputs = {}
        for name, shape, kind in net.input_specs:
            inputs[name] = (jnp.zeros(shape, jnp.float32)
                            if kind.startswith("label") else
                            jnp.asarray(rng.rand(*shape)
                                        .astype(np.float32)))
        ref = regf.forward(outs)(mvf.params, inputs)
        for wd in ("bf16", "int8"):
            regq = ModelRegistry(net, weight_dtype=wd,
                                 hbm_budget_bytes=0)
            mvq = regq.publish(params, wd)
            got = regq.forward(outs, weight_dtype=mvq.weight_dtype)
            got = (got(mvq.params, inputs)
                   if mvq.weight_dtype == "f32" else
                   got(mvq.params, mvq.scales or {}, inputs))
            worst = 0.0
            for bn in outs:
                r = np.asarray(jax.device_get(ref[bn]), np.float32)
                g = np.asarray(jax.device_get(got[bn]), np.float32)
                worst = max(worst, float(np.max(np.abs(g - r)))
                            / (float(np.max(np.abs(r))) + 1e-9))
            rows.append({
                "net": label, "weight_dtype": wd,
                "published_as": mvq.weight_dtype,
                "max_rel_drift": round(worst, 6),
                "tolerance": tol,
                "within_tolerance": worst <= tol,
            })
    return rows


def mm_prequant_ab(fc: int, iters: int) -> dict:
    """Satellite A/B: the per-call weight quantization PR 11 documented
    inside int8_inner_product vs the publish-time prequantized path —
    same shapes, same int8 matmul, the only delta is the O(N*K)
    abs-max+round on the weight per call."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import int8_inner_product
    from caffeonspark_tpu.parallel.gradsync import quantize_int8
    k = 8 * 10 * 10
    x = jnp.asarray(np.random.RandomState(0)
                    .rand(64, k).astype(np.float32))
    w = jnp.asarray(np.random.RandomState(1)
                    .rand(fc, k).astype(np.float32) - 0.5)
    wq, sw = quantize_int8(w, None)

    percall = jax.jit(lambda x, w: int8_inner_product(x, w))
    prequant = jax.jit(
        lambda x, wq, sw: int8_inner_product(x, wq, w_scale=sw))
    jax.block_until_ready(percall(x, w))
    jax.block_until_ready(prequant(x, wq, sw))

    def timeit(fn, *args):
        t0 = time.monotonic()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / iters

    t_percall = timeit(percall, x, w)
    t_prequant = timeit(prequant, x, wq, sw)
    return {
        "shape": {"m": 64, "k": k, "n": fc},
        "iters": iters,
        "per_call_quant_ms": round(t_percall * 1e3, 4),
        "prequant_ms": round(t_prequant * 1e3, 4),
        "speedup": round(t_percall / t_prequant, 3)
        if t_prequant else None,
    }


def main_multimodel(args) -> int:
    """--multimodel: models-per-chip × rows/s under a pinned HBM
    budget — quantized+paged serving vs the f32 resident baseline.
    ALWAYS exits 0 with ONE JSON document on stdout (bench.py
    contract).  Headline: under the same budget, int8 residency holds
    >= 2x the models of f32 at equal p99 (gate_2x_models), page-ins
    stream from the compressed host cache with ZERO fresh compiles
    (COS_RECOMPILE_GUARD armed through every cell), and every tested
    net's quantized drift sits inside the publish gate's tolerance."""
    import tempfile
    import jax
    from caffeonspark_tpu.serving import quant

    fc = 1024 if args.quick else 4096
    duration = 1.0 if args.quick else 2.5
    clients = 4
    max_batch = 8
    n_models = 4 if args.quick else 8
    out = {"bench": "serving_multimodel", "quick": args.quick,
           "env": {"platform": platform.platform(),
                   "python": sys.version.split()[0],
                   "jax": jax.__version__,
                   "cpu_count": os.cpu_count()},
           "notes": "CPU box: 'HBM' is host RAM, so the budget is the "
                    "registry's byte-accounted resident set and the "
                    "paging cost is the host->device placement wall — "
                    "the mechanism (LRU eviction, compressed host "
                    "cache, per-shard streamed page-in, zero fresh "
                    "compiles) is identical on real chips, where the "
                    "f32 baseline additionally pays HBM it does not "
                    "have",
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime())}
    svc = None
    try:
        td = tempfile.mkdtemp(prefix="cos_mm_bench_")
        solver_path, _net_path, models, net = build_model_family(
            td, n_models, fc)
        spec8 = quant.quant_spec(net, "int8")
        nb_f32 = quant.spec_nbytes(net, {})
        nb_int8 = quant.spec_nbytes(net, spec8)
        # budget = one f32 model (rounded up to the MB knob's grain):
        # the fits-only-one regime for f32, fits-several for int8
        budget_mb = max(1, -(-nb_f32 // 2**20))
        cap_f32 = max(1, (budget_mb * 2**20) // nb_f32)
        cap_int8 = max(1, (budget_mb * 2**20) // nb_int8)
        out["model"] = {
            "fc": fc, "count": n_models,
            "f32_mb": round(nb_f32 / 2**20, 3),
            "int8_mb": round(nb_int8 / 2**20, 3),
            "budget_mb": budget_mb,
            "capacity_f32": int(cap_f32),
            "capacity_int8": int(cap_int8),
        }
        aot_dir = os.path.join(td, "aot")
        ks = sorted({1, min(int(cap_int8), n_models), n_models})
        cells = {}
        guard_ok = True
        for wd in ("f32", "int8"):
            rows = []
            for k in ks:
                svc = mm_build_service(
                    solver_path, models[:k], wd, budget_mb, max_batch,
                    env_extra={"COS_AOT_CACHE_DIR": aot_dir})
                names = [None] + [f"tenant{i}" for i in range(1, k)]
                try:
                    cell = mm_load_cell(svc, names, clients, duration)
                    if svc._recompile_guard is not None:
                        try:
                            svc._recompile_guard.check()
                        except Exception as e:  # noqa: BLE001
                            guard_ok = False
                            cell["recompile_violation"] = str(e)
                finally:
                    svc.stop()
                    svc = None
                cell["weight_dtype"] = wd
                print(json.dumps(cell), file=sys.stderr, flush=True)
                rows.append(cell)
            cells[wd] = rows

        def cell_at(wd, k):
            return next(c for c in cells[wd] if c["models"] == k)

        # "holds k models at equal p99": p99 at k within 2x of the
        # same dtype's single-model p99 AND it never paged (the
        # resident set truly fits)
        def holds(wd, k):
            base = cell_at(wd, 1)["p99_ms"] or 0.0
            c = cell_at(wd, k)
            return (c["page_ins"] == 0 and c["failed"] == 0
                    and (c["p99_ms"] or 1e9) <= 2.0 * base + 5.0)

        held_f32 = max((k for k in ks if holds("f32", k)), default=0)
        held_int8 = max((k for k in ks if holds("int8", k)), default=0)
        tol = quant.serve_quant_tol()
        drift = mm_drift_table(
            [("mmnet_fc%d" % fc, net,
              net.init(jax.random.key(1000)))]
            + mm_zoo_nets(), tol)
        ab = mm_prequant_ab(fc, iters=5 if args.quick else 20)
        # page-in wall evidence comes from whichever cell actually
        # thrashed (the over-budget f32 sweep always does)
        page = max((c for rows in cells.values() for c in rows),
                   key=lambda c: c["page_ins"])
        out["cells"] = cells
        out["drift_table"] = drift
        out["prequant_ab"] = ab
        out["headline"] = {
            "metric": "models_per_chip_at_pinned_hbm_budget",
            "budget_mb": budget_mb,
            "models_held_f32": held_f32,
            "models_held_int8": held_int8,
            "capacity_ratio": round(cap_int8 / cap_f32, 2),
            "gate_2x_models": (held_f32 > 0
                               and held_int8 >= 2 * held_f32
                               and cap_int8 >= 2 * cap_f32),
            "page_in_mean_ms": page["page_in_mean_ms"],
            "page_in_p99_ms": page["page_in_p99_ms"],
            "page_in_from_cell": {"weight_dtype": page["weight_dtype"],
                                  "models": page["models"]},
            "page_in_fresh_compiles": 0 if guard_ok else "VIOLATED",
            "recompile_guard_armed": True,
            "drift_all_within_tolerance": all(
                r["within_tolerance"] for r in drift),
            "prequant_speedup": ab["speedup"],
        }
    except Exception as e:      # noqa: BLE001 — artifact over rc
        out["error"] = f"{type(e).__name__}: {e}"
        if svc is not None:
            try:
                svc.stop()
            except Exception:   # noqa: BLE001 — already reported
                pass
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


def mm_zoo_nets():
    """Zoo nets for the drift table (small enough for the CI box):
    LeNet — the repo's canonical convnet — with filler weights."""
    import jax
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.serving.registry import build_serving_net
    rows = []
    for label, np_ in (("lenet", zoo.lenet(batch_size=8)),):
        net = build_serving_net(np_)
        rows.append((label, net, net.init(jax.random.key(7))))
    return rows


# ---------------------------------------------------------------------------
# multi-replica (fleet) mode
# ---------------------------------------------------------------------------

def _fleet_record():
    return {"id": "r0", "label": 0.0,
            "data": (np.random.RandomState(0)
                     .rand(3, 24, 24).astype(np.float32) * 255.0)
            .tolist()}


def _replica_metrics(router, name):
    from caffeonspark_tpu.serving.router import http_json
    code, body = http_json(router.replica_url(name) + "/metrics",
                           timeout=10.0)
    return body if code == 200 else {}


def fleet_load_cell(router, clients: int, duration_s: float,
                    kill=None) -> dict:
    """Closed-loop offered load against the router; client-observed
    latency measured at the caller (retries included — that IS the
    client experience).  `kill` = (fleet, replica_name, at_s) injects
    a SIGKILL mid-window."""
    rec = _fleet_record()
    req_share_before = {
        n: r["requests"] for n, r
        in router.metrics_summary()["replicas"].items()}
    stop = threading.Event()
    lats = [[] for _ in range(clients)]
    errors = [0] * clients

    def client(i):
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                out = router.predict({"records": [rec]})
                assert out["rows"], "empty response"
                lats[i].append(time.monotonic() - t0)
            except Exception:      # noqa: BLE001 — counted as failed
                errors[i] += 1
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    if kill is not None:
        fleet, name, at_s = kill
        time.sleep(at_s)
        fleet.kill_replica(name)
        print(json.dumps({"fault": f"SIGKILL {name}"}),
              file=sys.stderr, flush=True)
        time.sleep(max(0.0, duration_s - at_s))
    else:
        time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=90)
    elapsed = time.monotonic() - t0
    all_lats = sorted(x for ls in lats for x in ls)

    def pct(p):
        return round(1e3 * all_lats[min(len(all_lats) - 1,
                                        int(p * len(all_lats)))], 3) \
            if all_lats else None

    share_after = {n: r["requests"] for n, r
                   in router.metrics_summary()["replicas"].items()}
    served = len(all_lats)
    util = {n: share_after[n] - req_share_before.get(n, 0)
            for n in share_after}
    return {
        "clients": clients, "duration_s": round(elapsed, 3),
        "rows_per_sec": round(served / elapsed, 2),
        "served": served, "failed": sum(errors),
        "p50_ms": pct(0.50), "p99_ms": pct(0.99),
        "per_replica_requests": util,
    }


def main_fleet(args) -> int:
    """Fleet bench: ALWAYS exits 0 with ONE JSON document on stdout
    (progress/faults go to stderr)."""
    import tempfile
    import jax
    from caffeonspark_tpu.serving import Fleet, aot

    replicas = args.fleet
    duration = 1.2 if args.quick else 3.0
    loads = [1, 8] if args.quick else [1, 8, 32]
    max_batch = 16 if args.quick else 32
    out = {"bench": "serving_fleet", "replicas": replicas,
           "quick": args.quick,
           "env": {"platform": platform.platform(),
                   "python": sys.version.split()[0],
                   "jax": jax.__version__,
                   "cpu_count": os.cpu_count()},
           "notes": "CPU box: replicas CONTEND for the same few "
                    "cores, so fleet rows/s ~matches one replica — "
                    "the throughput scaleup belongs to one-device-"
                    "per-replica deployments; what this box proves "
                    "is the fleet mechanics (balancing, zero-failure "
                    "kill absorption, warm AOT restart)",
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime())}
    fleet = None
    cold = None
    try:
        td = tempfile.mkdtemp(prefix="cos_fleet_bench_")
        solver_path, model = build_model(td)
        aot_dir = os.path.join(td, "aot")
        env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": _FLAG,
               "COS_AOT_CACHE_DIR": aot_dir,
               "COS_RECOMPILE_GUARD": "1",
               "COS_SERVE_MAX_BATCH": str(max_batch),
               "COS_SERVE_MAX_WAIT_MS": "2"}
        serve_args = ["-conf", solver_path, "-model", model,
                      "-features", "ip2"]

        # -- phase A: one COLD replica fills the AOT cache -----------
        t0 = time.monotonic()
        cold = Fleet(serve_args, replicas=1, env=env)
        cold.start()
        cold_start_s = time.monotonic() - t0
        cold_warmup = _replica_metrics(cold.router,
                                       "replica0").get("warmup_s")
        ns = os.listdir(aot_dir)
        cache = os.path.join(aot_dir, ns[0]) if ns else aot_dir
        entries_cold = aot.cache_entries(cache)
        single_peak = max(
            fleet_load_cell(cold.router, nc, duration)["rows_per_sec"]
            for nc in loads)
        cold.stop()
        cold = None

        # -- phase B: the fleet WARM-starts from the cache -----------
        t0 = time.monotonic()
        fleet = Fleet(serve_args, replicas=replicas, env=env,
                      poll_interval_s=0.1)
        fleet.start()
        warm_start_s = time.monotonic() - t0
        warm_warmups = [
            _replica_metrics(fleet.router, n).get("warmup_s")
            for n in fleet.router.names()]
        out["aot_warm_start"] = {
            "cold_warmup_s": cold_warmup,
            "cold_spawn_to_healthy_s": round(cold_start_s, 3),
            "warm_warmup_s_per_replica": warm_warmups,
            "warm_spawn_to_healthy_s": round(warm_start_s, 3),
            "cache_entries_after_cold": entries_cold,
            "entries_added_by_warm_fleet":
                aot.cache_entries(cache) - entries_cold,
            "recompile_guard_armed": True,
        }

        # -- offered-load sweep --------------------------------------
        cells = []
        for nc in loads:
            cell = fleet_load_cell(fleet.router, nc, duration)
            print(json.dumps(cell), file=sys.stderr, flush=True)
            cells.append(cell)
        out["cells"] = cells
        fleet_peak = max(c["rows_per_sec"] for c in cells)

        # -- fault injection under load ------------------------------
        fault = fleet_load_cell(
            fleet.router, max(loads), duration + 1.5,
            kill=(fleet, "replica0", 0.8))
        deadline = time.monotonic() + 120
        while fleet.router.states()["replica0"] != "ok" \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        rejoin = fleet.metrics_summary()["stages"] \
            .get("replica_rejoin", {})
        out["fault_injection"] = {
            "cell": fault,
            "failed_client_requests": fault["failed"],
            "zero_failures": fault["failed"] == 0,
            "replica_restarts": fleet.restarts(),
            "rejoin_wall_s": rejoin.get("mean_ms", 0) / 1e3 or None,
            "rejoined_warm_entries_added":
                aot.cache_entries(cache) - entries_cold,
        }

        out["headline"] = {
            "metric": "fleet_rows_per_sec",
            "single_replica_peak": single_peak,
            "fleet_peak": fleet_peak,
            "scaleup": round(fleet_peak / single_peak, 2)
            if single_peak else None,
            "kill_under_load_failed_requests": fault["failed"],
            "warm_vs_cold_warmup":
                [warm_warmups, cold_warmup],
        }
    except Exception as e:      # noqa: BLE001 — artifact over rc
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        # the cold phase-A replica too: an exception between
        # cold.start() and cold.stop() must not leave a -serve
        # subprocess contending for the box
        for fl in (fleet, cold):
            if fl is not None:
                try:
                    fl.stop()
                except Exception:  # noqa: BLE001 — already reported
                    pass
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


# ---------------------------------------------------------------------------
# pipeline-parallel mode (--pp N): stage-granular HBM paging
# ---------------------------------------------------------------------------

def pp_build_net(td: str, fc: int):
    """The --tp big net reused for --pp: two fc x fc InnerProducts
    dominate the parameter bytes, so the roofline partition puts them
    in different stages and the stage page-in cost is real."""
    from caffeonspark_tpu.proto import NetParameter
    from caffeonspark_tpu.serving.registry import build_serving_net
    solver_path, model, n_params = build_big_model(td, fc)
    net = build_serving_net(
        NetParameter.from_text(BIG_NET_TMPL.format(root=td, fc=fc)))
    return solver_path, model, n_params, net


def pp_feed(bs: int):
    rng = np.random.RandomState(0)
    return {"data": rng.rand(bs, 3, 24, 24).astype(np.float32),
            "label": np.zeros(bs, np.float32)}


def pp_ttfr(net, model, pp: int) -> dict:
    """Cold-start time-to-first-result, programs pre-compiled so the
    timed window is pure paging + execution: whole-model baseline
    (stream EVERY byte, then answer) vs stage-granular (answer while
    the tail still pages).  Both paths stream the same caffemodel
    from disk through the same streamed loader."""
    import jax
    from caffeonspark_tpu.parallel import MeshLayout, build_mesh
    from caffeonspark_tpu.serving.registry import ModelRegistry
    feed = pp_feed(16)
    rows = {}
    for mode in ("whole_model", "staged"):
        lay = (MeshLayout(net, build_mesh(pp=pp,
                                          devices=jax.devices()[:pp]))
               if mode == "staged" else None)
        reg = ModelRegistry(net, lay)
        # dress rehearsal: compile every program variant + fault in
        # the file cache, so the timed run measures paging, not XLA
        reg.load(model)
        e = reg._entry(None)
        if e.pager is not None:
            e.pager.join(60)
        mv, w = reg.staged_view()
        kw = {"stage_wait": w} if w is not None else {}
        fwd = reg.forward(("ip",))
        jax.block_until_ready(fwd(mv.params, feed, **kw)["ip"])
        if mode == "staged":
            # the timed cold run serves THROUGH the waiter (m=1
            # program) — compile it now by superseding mid-page
            reg.load(model)
            mv, w = reg.staged_view()
            if w is not None:
                jax.block_until_ready(
                    fwd(mv.params, feed, stage_wait=w)["ip"])
            e.pager.join(60)
        # timed: version-bumping load() drops residency + host cache,
        # so every byte re-streams from the file
        t0 = time.monotonic()
        reg.load(model)
        t_load = time.monotonic() - t0
        mv, w = reg.staged_view()
        kw = {"stage_wait": w} if w is not None else {}
        jax.block_until_ready(fwd(mv.params, feed, **kw)["ip"])
        t_first = time.monotonic() - t0
        if e.pager is not None:
            e.pager.join(60)
        rows[mode] = {"load_return_ms": round(t_load * 1e3, 3),
                      "ttfr_ms": round(t_first * 1e3, 3)}
    rows["ttfr_improvement"] = round(
        rows["whole_model"]["ttfr_ms"] / rows["staged"]["ttfr_ms"], 3)
    rows["gate_staged_strictly_faster"] = (
        rows["staged"]["ttfr_ms"] < rows["whole_model"]["ttfr_ms"])
    return rows


def pp_build_service(solver_path, model, pp, budget_mb, max_batch):
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.serving import InferenceService
    env = {"COS_RECOMPILE_GUARD": "1"}
    if budget_mb:
        env["COS_SERVE_HBM_BUDGET_MB"] = str(budget_mb)
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        svc = InferenceService(
            Config(["-conf", solver_path, "-model", model,
                    "-serveMesh", f"pp={pp}", "-devices", str(2 * pp)]),
            blob_names=("ip",), max_batch=max_batch, max_wait_ms=1.0,
            queue_depth=max(64, 4 * max_batch))
        svc.start(warmup=True)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return svc


def pp_load_cell(svc, clients, duration_s) -> dict:
    """Closed-loop offered load against one staged service; client-
    observed latency includes any stage page-in the flush triggered
    (under a fits-one-stage budget every flush pages — that IS the
    over-budget tenant experience)."""
    rec = ("r", 0.0, 3, 24, 24, False,
           (np.random.RandomState(0).rand(3, 24, 24)
            .astype(np.float32) * 255.0))
    stop = threading.Event()
    lats = [[] for _ in range(clients)]
    errors = [0] * clients

    def client(ci):
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                svc.submit(rec).wait(60.0)
                lats[ci].append(time.monotonic() - t0)
            except Exception:        # noqa: BLE001 — counted
                errors[ci] += 1
                time.sleep(0.001)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    elapsed = time.monotonic() - t0
    all_lats = sorted(x for ls in lats for x in ls)

    def pct(p):
        return round(1e3 * all_lats[min(len(all_lats) - 1,
                                        int(p * len(all_lats)))], 3) \
            if all_lats else None

    stats = svc.registry.model_stats()["default"]
    guard_violation = None
    if svc._recompile_guard is not None:
        try:
            svc._recompile_guard.check()
        except Exception as ex:      # noqa: BLE001
            guard_violation = str(ex)
    return {
        "clients": clients, "duration_s": round(elapsed, 3),
        "rows_per_sec": round(len(all_lats) / elapsed, 2),
        "served": len(all_lats), "failed": sum(errors),
        "p50_ms": pct(0.50), "p99_ms": pct(0.99),
        "page_ins": stats["page_ins"], "evictions": stats["evictions"],
        "stages": stats.get("stages"),
        "recompile_violation": guard_violation,
    }


def pp_churn(net, workers: int, target_page_ins: int,
             timeout_s: float) -> dict:
    """Never-mixed + RecompileGuard integrity under concurrent stage
    page-ins: a fits-one-stage budget makes every flush page (each
    one evicting the sibling stage), `workers` flush threads race a
    publisher flipping two versions, and every output must byte-equal
    one of the pure versions.  Runs until `target_page_ins` stage
    page-ins completed (the 500+ concurrency evidence)."""
    import jax
    from caffeonspark_tpu.analysis.runtime import RecompileGuard
    from caffeonspark_tpu.parallel import MeshLayout, build_mesh
    from caffeonspark_tpu.serving.registry import (ModelRegistry,
                                                   StaleVersionError)
    # pin the microbatch split: byte-equality against the unstaged
    # reference holds per PROGRAM, and a publisher making all stages
    # briefly resident would otherwise let some flushes pick the
    # measured no-waiter m — a different (still correct) program
    # whose float noise this harness would miscount as mixing
    os.environ["COS_SERVE_PP_MB"] = "1"
    feed = pp_feed(16)
    p1 = net.init(jax.random.key(1))
    p2 = {ln: {bn: a * 1.25 for bn, a in bl.items()}
          for ln, bl in p1.items()}
    reg0 = ModelRegistry(net)
    f0 = reg0.forward(("ip",))
    ref1 = np.asarray(f0(p1, feed)["ip"])
    ref2 = np.asarray(f0(p2, feed)["ip"])

    lay = MeshLayout(net, build_mesh(pp=2, devices=jax.devices()[:4]))
    probe = ModelRegistry(net, lay)
    probe.publish(p1)
    budget = max(st.nbytes
                 for st in probe._entry(None).stage_state) + 65536
    reg = ModelRegistry(net, lay, hbm_budget_bytes=budget)
    reg.publish(p1)
    fwd = reg.forward(("ip",))
    e = reg._entry(None)
    # warm the waiter-path program, then pin the guard: every page-in
    # cycle after this point must be placement-only
    mv, w = reg.staged_view()
    fwd(mv.params, feed, **({"stage_wait": w} if w is not None else {}))
    guard = RecompileGuard("bench-pp-churn")
    guard.watch("pp-churn", fwd)
    guard.mark_steady()

    stop = threading.Event()
    mixed = [0] * workers
    flushes = [0] * workers
    stale = [0] * workers
    failed = [0] * workers
    flips = [0]

    def worker(i):
        while not stop.is_set():
            try:
                for attempt in range(4):
                    mv, w = reg.staged_view()
                    kw = ({"stage_wait": w} if w is not None else {})
                    try:
                        got = np.asarray(
                            fwd(mv.params, feed, **kw)["ip"])
                        break
                    except StaleVersionError:
                        stale[i] += 1
                else:
                    failed[i] += 1
                    continue
                flushes[i] += 1
                if not (np.array_equal(got, ref1)
                        or np.array_equal(got, ref2)):
                    mixed[i] += 1
            except Exception:        # noqa: BLE001 — counted
                failed[i] += 1

    def publisher():
        flip = False
        while not stop.is_set():
            time.sleep(0.25)
            try:
                reg.publish(p2 if flip else p1)
                flips[0] += 1
                flip = not flip
            except Exception:        # noqa: BLE001 — next tick
                pass

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(workers)]
    pub = threading.Thread(target=publisher, daemon=True)
    t0 = time.monotonic()
    for t in threads:
        t.start()
    pub.start()
    while (e.page_ins < target_page_ins
           and time.monotonic() - t0 < timeout_s):
        time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    pub.join(timeout=60)
    guard_violation = None
    try:
        guard.check()
    except Exception as ex:          # noqa: BLE001
        guard_violation = str(ex)
    os.environ.pop("COS_SERVE_PP_MB", None)
    return {
        "workers": workers,
        "duration_s": round(time.monotonic() - t0, 3),
        "page_ins": e.page_ins, "evictions": e.evictions,
        "target_page_ins": target_page_ins,
        "flushes": sum(flushes), "publish_flips": flips[0],
        "stale_retries": sum(stale), "failed": sum(failed),
        "mixed_outputs": sum(mixed),
        "recompile_violation": guard_violation,
        "gate_integrity": (sum(mixed) == 0 and sum(failed) == 0
                           and guard_violation is None
                           and e.page_ins >= target_page_ins),
    }


def main_pp(args) -> int:
    """--pp N: pipeline-parallel serving over stage-granular HBM
    paging.  ALWAYS exits 0 with ONE JSON document (bench.py
    contract).  Three claims, one artifact:

      * over-budget serving — a net whose stages together exceed the
        HBM budget (fits-one-stage) still serves, p99 within
        `gate_p99_ratio` of the unconstrained control;
      * cold start — stage-granular page-in (answer while the tail
        still pages) strictly beats the whole-model-paging baseline
        (stream every byte, then answer) on time-to-first-result;
      * integrity — 500+ concurrent stage page-ins racing a
        version-flipping publisher: never-mixed violations 0,
        RecompileGuard violations 0.
    """
    _flag = "--xla_force_host_platform_device_count=8"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + _flag).strip()
    import tempfile
    import jax
    from caffeonspark_tpu.parallel import MeshLayout, build_mesh
    from caffeonspark_tpu.serving.registry import ModelRegistry

    pp = args.pp
    fc = 1024 if args.quick else 2048
    duration = 1.2 if args.quick else 3.0
    clients = 4
    target_page_ins = 120 if args.quick else 520
    gate_p99_ratio = 60.0
    out = {"bench": "serving_pp", "quick": args.quick, "pp": pp,
           "env": {"platform": platform.platform(),
                   "python": sys.version.split()[0],
                   "jax": jax.__version__,
                   "cpu_count": os.cpu_count()},
           "notes": "CPU box: 'HBM' is host RAM, stages live on "
                    "xla_force_host_platform devices — the mechanism "
                    "(roofline-balanced stage cut, per-stage LRU, "
                    "streamed stage page-in, device-resident "
                    "inter-stage activations, never-mixed flush "
                    "snapshot) is identical on real chips",
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                      time.gmtime())}
    svc = None
    try:
        td = tempfile.mkdtemp(prefix="cos_pp_bench_")
        solver_path, model, n_params, net = pp_build_net(td, fc)
        lay = MeshLayout(net, build_mesh(pp=pp,
                                         devices=jax.devices()[:2 * pp]))
        probe = ModelRegistry(net, lay)
        probe.load(model)
        pe = probe._entry(None)
        if pe.pager is not None:
            pe.pager.join(60)
        stage_bytes = [st.nbytes for st in pe.stage_state]
        budget_mb = max(1, -(-max(stage_bytes) // 2**20))
        assert budget_mb * 2**20 < sum(stage_bytes), \
            "fits-one-stage budget must not fit the whole net"
        out["model"] = {
            "fc": fc, "params": n_params,
            "stages": [len(s) for s in lay.stages],
            "stage_mb": [round(b / 2**20, 3) for b in stage_bytes],
            "total_mb": round(sum(stage_bytes) / 2**20, 3),
            "budget_mb": budget_mb,
            "mesh": lay.signature(),
        }

        out["cold_start"] = pp_ttfr(net, model, pp)
        print(json.dumps({"cold_start": out["cold_start"]}),
              file=sys.stderr, flush=True)

        cells = {}
        for label, budget in (("control", 0),
                              ("over_budget", budget_mb)):
            svc = pp_build_service(solver_path, model, pp, budget,
                                   max_batch=8)
            try:
                cells[label] = pp_load_cell(svc, clients, duration)
            finally:
                svc.stop()
                svc = None
            print(json.dumps({label: cells[label]}),
                  file=sys.stderr, flush=True)
        ratio = (cells["over_budget"]["p99_ms"]
                 / cells["control"]["p99_ms"]
                 if cells["control"]["p99_ms"] else None)
        out["over_budget"] = {
            "control": cells["control"],
            "over_budget": cells["over_budget"],
            "p99_ratio": round(ratio, 3) if ratio else None,
            "gate_p99_ratio": gate_p99_ratio,
            "gate_within_ratio": (
                ratio is not None and ratio <= gate_p99_ratio
                and cells["over_budget"]["failed"] == 0
                and cells["over_budget"]["page_ins"] > 0
                and cells["over_budget"]["recompile_violation"] is None),
        }

        out["churn"] = pp_churn(net, workers=8,
                                target_page_ins=target_page_ins,
                                timeout_s=300.0)
        print(json.dumps({"churn": out["churn"]}),
              file=sys.stderr, flush=True)

        out["headline"] = {
            "metric": "over_budget_p99_ratio_vs_unconstrained",
            "p99_ratio": out["over_budget"]["p99_ratio"],
            "gate_within_ratio": out["over_budget"]["gate_within_ratio"],
            "cold_start_ttfr_improvement":
                out["cold_start"]["ttfr_improvement"],
            "gate_staged_strictly_faster":
                out["cold_start"]["gate_staged_strictly_faster"],
            "churn_page_ins": out["churn"]["page_ins"],
            "never_mixed_violations": out["churn"]["mixed_outputs"],
            "recompile_guard_violations": (
                0 if (out["churn"]["recompile_violation"] is None
                      and cells["over_budget"]["recompile_violation"]
                      is None
                      and cells["control"]["recompile_violation"]
                      is None) else "VIOLATED"),
            "gate_integrity": out["churn"]["gate_integrity"],
        }
    except Exception as e:      # noqa: BLE001 — artifact over rc
        out["error"] = f"{type(e).__name__}: {e}"
        if svc is not None:
            try:
                svc.stop()
            except Exception:   # noqa: BLE001 — already reported
                pass
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small configs + short windows (CI smoke)")
    ap.add_argument("--out", default="bench_evidence/bench_serving.json")
    ap.add_argument("--trials", type=int, default=0,
                    help="best-of-N per cell (default 2, quick 1)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="multi-replica mode: N replica subprocesses "
                         "behind the router (always exits 0, one JSON "
                         "document on stdout)")
    ap.add_argument("--tp", type=int, default=0, metavar="N",
                    help="sharded-serving mode: hot-swap wall + peak "
                         "host RSS, host-gather baseline vs zero-"
                         "gather shard streaming under a tp=N mesh "
                         "(always exits 0, one JSON document)")
    ap.add_argument("--tp-worker", default="", metavar="MODE",
                    help="internal: subprocess body for --tp "
                         "(write | gather | streamed)")
    ap.add_argument("--swaps", type=int, default=3)
    ap.add_argument("--solver", default="")
    ap.add_argument("--model", default="")
    ap.add_argument("--model-sharded", dest="model_sharded", default="")
    ap.add_argument("--multimodel", action="store_true",
                    help="multi-model mode: models-per-chip x rows/s "
                         "under a pinned HBM budget, quantized+paged "
                         "residency vs the f32 resident baseline "
                         "(always exits 0, one JSON document)")
    ap.add_argument("--pp", type=int, default=0, metavar="N",
                    help="pipeline-parallel mode: stage-granular HBM "
                         "paging under a pp=N mesh — over-budget p99 "
                         "vs unconstrained control, cold-start TTFR "
                         "vs whole-model paging, never-mixed + "
                         "recompile integrity under 500+ concurrent "
                         "stage page-ins (always exits 0, one JSON "
                         "document)")
    args = ap.parse_args()
    if args.tp_worker:
        return main_tp_worker(args)
    if args.tp:
        if args.out == "bench_evidence/bench_serving.json":
            args.out = "bench_evidence/bench_serving_sharded.json"
        return main_sharded(args)
    if args.multimodel:
        if args.out == "bench_evidence/bench_serving.json":
            args.out = "bench_evidence/bench_serving_multimodel.json"
        return main_multimodel(args)
    if args.pp:
        if args.out == "bench_evidence/bench_serving.json":
            args.out = "bench_evidence/bench_serving_pp.json"
        return main_pp(args)
    if args.fleet:
        return main_fleet(args)

    import tempfile
    import jax
    td = tempfile.mkdtemp(prefix="cos_serve_bench_")
    solver_path, model = build_model(td)

    # saturation needs offered load >= the largest bucket (a closed
    # loop with N clients can never fill a bucket past N)
    duration = 1.2 if args.quick else 3.0
    trials = args.trials or (1 if args.quick else 2)
    configs = [1, 8, 32] if args.quick else [1, 8, 64]
    loads = [1, 32] if args.quick else [1, 16, 64]

    cells = []
    for mb in configs:
        # max_wait short enough that batch=1-equivalent idle latency
        # stays bounded, long enough that a saturated window coalesces
        wait_ms = 0.0 if mb == 1 else 2.0
        for nc in loads:
            best = None
            for _ in range(trials):
                cell = run_cell(solver_path, model, mb, nc, duration,
                                wait_ms)
                if best is None or cell["rows_per_sec"] > \
                        best["rows_per_sec"]:
                    best = cell
            print(json.dumps(best), flush=True)
            cells.append(best)

    def peak(mb):
        return max(c["rows_per_sec"] for c in cells
                   if c["max_batch"] == mb)

    batched_peak = max(peak(mb) for mb in configs if mb > 1)
    headline = {
        "metric": "serving_rows_per_sec",
        "batch1_rows_per_sec_at_saturation": peak(1),
        "batched_rows_per_sec_at_saturation": batched_peak,
        "speedup_at_saturation": round(batched_peak / peak(1), 2),
        "quick": args.quick,
    }
    out = {
        "bench": "serving",
        "headline": headline,
        "cells": cells,
        "recipe": {
            "trials_per_cell_best_of": trials,
            "duration_s_per_cell": duration,
            "closed_loop_clients": loads,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "notes": "single intra-op XLA thread; best-of-N damps "
                     "neighbor-tenant CPU swings (box-cpu-contention "
                     "recipe); CPU backend — the fixed per-dispatch "
                     "cost being amortized is host-side "
                     "pack+dispatch+fetch",
        },
        "env": {
            "platform": platform.platform(),
            "python": sys.version.split()[0],
            "jax": jax.__version__,
            "backend": jax.devices()[0].platform,
            "cpu_count": os.cpu_count(),
        },
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"headline": headline}), flush=True)
    if headline["speedup_at_saturation"] < 3.0 and not args.quick:
        print("WARNING: speedup below the 3x acceptance gate",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
