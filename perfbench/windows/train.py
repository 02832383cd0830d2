"""Window kind `train`: the job a CaffeOnSpark user runs.

`caffe_on_spark.main -train` with the cell's LMDB, `max_iter` out of
reach, no validation, no snapshot, every program option at its default.
The harness changes nothing the train path computes.  It observes it: the
callable `ParallelSolver.train_step()` hands to `CaffeProcessor._run_train`
is wrapped by a pass-through that

  * on the job's first CHECK_STEPS steps copies out what the comparison
    needs (state before step 1, momentum after it, parameters after the
    last, each loss, the first pixel row of every packed image);
  * at the last warm-up step (the job's step CHECK_STEPS + warmup_steps)
    blocks on the step's loss and stamps the clock: set-up ends there,
    at a fixed point of the job;
  * from that step on, once no stock of packed batches is left, blocks
    on the step's loss (window edge a, a value fetched from the device),
    counts steps, and at the first step that ends `seconds` later, a
    whole number of pool rounds after edge a, blocks again (edge b).
    The steps between the stamp and edge a (the drain of the stock) are
    the benchmark's own and belong to neither `setup_s` nor the window;
  * with tracing on, starts and stops the profiler at the edges and marks
    `dispatch` / `wait_for_batch` spans on the profiler's clock.

The job is then ended through the processor's own `stop()`.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import shutil
import threading
import time

import numpy as np

CHECK_STEPS = 3
SAMPLE_ROWS = 32
MAX_WARMUP_STEPS = 64


class Observer:
    def __init__(self, *, seconds, warmup_steps, trace_dir, t_process0,
                 break_step=None):
        self.seconds = seconds
        self.warmup_steps = warmup_steps
        self.trace_dir = trace_dir
        self.t0 = t_process0
        self.break_step = break_step      # tests only: a broken timed path
        self.calls = 0
        self.round = 1                    # pack workers feeding the loop
        self.stamps = {}
        self.overhead = 0.0               # the comparison's own copying
        self.cap = {"losses": [], "strips": [], "labels": [], "rows": [],
                    "row_idx": []}
        self.window_losses = []
        self.t_setup = None               # the last warm-up step is done
        self.t_a = self.t_b = None
        self.n_a = self.n_b = None
        self.metrics_a = self.metrics_b = None
        self.compiles_in_window = 0
        self.in_window = False
        self.step_ends = []               # host clock, each window step
        self.memory_peak = 0
        self.done = threading.Event()
        self._wait_span = None
        self._window_span = None

    # ---------------------------------------------------------- helpers
    def _pipeline_summary(self):
        from caffeonspark_tpu.processor import CaffeProcessor
        proc = CaffeProcessor._instance
        if proc is None:
            return {}
        stages = proc.metrics.summary().get("stages", {})
        return {k: (v["total_s"], v["count"]) for k, v in stages.items()}

    def _stock(self, steps_taken):
        packed = self._pipeline_summary().get("pack", (0, steps_taken))[1]
        return packed - steps_taken

    def _sample_memory(self):
        from ..harness import devices
        self.memory_peak = max(self.memory_peak, devices.memory_now())

    def setup_and_drain(self):
        """-> (setup_s, drain seconds, drain steps): process start to the
        stamp of the last warm-up step, less the comparison's own copying;
        then what the benchmark's own stock rule added before edge a."""
        return ((self.t_setup - self.t0) - self.overhead,
                self.t_a - self.t_setup,
                self.n_a - (CHECK_STEPS + self.warmup_steps))

    def _on_compile(self, event, duration, **kw):
        if self.in_window and "compile" in event:
            self.compiles_in_window += 1

    def _capture(self, n, batch, result):
        import jax
        p2, st2, out = result
        self.cap["losses"].append(float(out["loss"]))   # the step's own time
        t = time.perf_counter()
        data, label = batch["data"], batch["label"]
        rows = data.shape[0]
        idx = np.sort(np.random.default_rng(n).choice(
            rows, min(SAMPLE_ROWS, rows), replace=False))
        self.cap["strips"].append(np.asarray(data[:, :, 0, :]))
        self.cap["labels"].append(np.asarray(label))
        self.cap["row_idx"].append(idx)
        self.cap["rows"].append(np.asarray(data[idx]))
        if n == 0:
            self.cap["v1"] = jax.device_get(st2.history)
            self.cap["p1"] = jax.device_get(p2)
        if n == CHECK_STEPS - 1:
            self.cap["p_last"] = jax.device_get(p2)
        self.overhead += time.perf_counter() - t

    # ------------------------------------------------------------ wrapper
    def wrap(self, real):
        import jax
        from jax.profiler import TraceAnnotation

        def step(params, st, batch, rng):
            n = self.calls
            self.calls += 1
            if self.done.is_set():
                return real(params, st, batch, rng)
            if n < CHECK_STEPS:
                if n == 0:
                    self.stamps["first_step_called"] = time.perf_counter()
                    t = time.perf_counter()
                    self.cap["p0"] = jax.device_get(params)
                    self.overhead += time.perf_counter() - t
                if self.break_step is not None:
                    result = self.break_step(real, params, st, batch, rng)
                else:
                    result = real(params, st, batch, rng)
                self._capture(n, batch, result)
                if n == 0:
                    self.stamps["first_step_done"] = time.perf_counter()
                return result
            if self._wait_span is not None:
                self._wait_span.__exit__(None, None, None)
                self._wait_span = None
            if self.in_window and self.trace_dir:
                with TraceAnnotation("perfbench.dispatch"):
                    result = real(params, st, batch, rng)
            else:
                result = real(params, st, batch, rng)
            out = result[2]
            if n + 1 == CHECK_STEPS + self.warmup_steps:
                # set-up ends at a fixed point of the job: how long the
                # stock then takes to drain follows the step time and the
                # feed, and a PR that makes either faster must not read
                # as a slower or a faster set-up
                jax.block_until_ready(out["loss"])
                self.t_setup = self.stamps["setup_end"] = time.perf_counter()
            if self.t_a is None:
                # the window opens once the warm-up steps are done AND the
                # stock of packed batches that set-up built is gone: packed
                # so far less steps taken is under one pool round, as it is
                # whenever the loop lives from hand to mouth.  A run
                # that compiles leaves the pool and the stager half a
                # minute to pack ahead, up to 11 batches; run down inside
                # the window that read as up to 25% more img/s, and a
                # fixed warm-up long enough to drain it (16 steps) costs
                # every warm run of an ingest-bound cell ten seconds more
                # (PERF.md section 6).  A feed that always keeps a stock
                # (a device-bound cell) starts after MAX_WARMUP_STEPS.
                if n + 1 >= CHECK_STEPS + self.warmup_steps and (
                        self._stock(n + 1) < self.round
                        or n + 1 >= CHECK_STEPS + MAX_WARMUP_STEPS):
                    if self.trace_dir:
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0    # host spans only
                        jax.profiler.start_trace(self.trace_dir,
                                                 profiler_options=opts)
                    self.metrics_a = self._pipeline_summary()
                    jax.block_until_ready(out["loss"])      # edge a
                    self.t_a, self.n_a = time.perf_counter(), n + 1
                    self.in_window = True
                    self._sample_memory()
                    if self.trace_dir:
                        self._window_span = TraceAnnotation(
                            "perfbench.window")
                        self._window_span.__enter__()
            else:
                self.window_losses.append(out["loss"])
                self.step_ends.append(time.perf_counter())
                self._sample_memory()
                # the window closes on a whole number of pool rounds: the
                # pack workers deliver in turn, so steps come in bursts of
                # `round` and a window cut inside a burst would count a
                # step without the time its batch took to pack
                if time.perf_counter() >= self.t_a + self.seconds \
                        and (n + 1 - self.n_a) % self.round == 0:
                    jax.block_until_ready(out["loss"])      # edge b
                    self.t_b, self.n_b = time.perf_counter(), n + 1
                    self.in_window = False
                    if self._window_span is not None:
                        self._window_span.__exit__(None, None, None)
                    self.metrics_b = self._pipeline_summary()
                    if self.trace_dir:
                        jax.profiler.stop_trace()
                    self.done.set()
                    return result
            if self.in_window and self.trace_dir:
                self._wait_span = TraceAnnotation("perfbench.wait_for_batch")
                self._wait_span.__enter__()
            return result

        return step


def write_inputs(ctx, work):
    """LMDB + net + solver from the cell's files and the seed."""
    from caffeonspark_tpu.data import LmdbWriter
    from ..harness import records
    cfg, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    chips = ctx["chips"]
    batch = int(cfg["per_device_batch"]) * chips
    n = int(cell["records_per_global_batch"] * batch)
    t0 = time.perf_counter()
    recs, pixels, labels, facts = records.generate(
        traffic, n, ctx["seed"], int(cfg["num_classes"]))
    lmdb = os.path.join(work, "train_lmdb")
    LmdbWriter(lmdb).write(recs)
    del recs
    facts["inputs_s"] = time.perf_counter() - t0
    means = " ".join(f"mean_value: {m}" for m in cfg["mean_values"])
    mirror = "true" if traffic.get("mirror") else "false"
    with open(os.path.join(ctx["root"], cfg["net"])) as f:
        body = f.read()
    net_path = os.path.join(work, "train_val.prototxt")
    with open(net_path, "w") as f:
        head, rest = body.split("\n", 1)      # the `name:` line stays first
        f.write(f'''{head}
layer {{
  name: "data" type: "MemoryData" top: "data" top: "label"
  include {{ phase: TRAIN }} source_class: "{traffic["source_class"]}"
  memory_data_param {{ source: "{lmdb}" batch_size: {batch}
    channels: 3 height: {traffic["side"]} width: {traffic["side"]} }}
  transform_param {{ crop_size: {cfg["crop"]} mirror: {mirror} {means} }}
}}
{rest}''')
    solver_path = os.path.join(work, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(f'net: "{net_path}"\n')
        for k, v in cfg["solver"].items():
            f.write(f"{k}: {json_scalar(v)}\n")
        # steady state: nothing but train steps in the window
        f.write("max_iter: 100000000\ntest_interval: 0\nsnapshot: 0\n"
                "snapshot_after_train: false\ndisplay: 0\n"
                f"random_seed: {ctx['seed']}\n")
    return solver_path, pixels, labels, facts, batch


def json_scalar(v):
    return f'"{v}"' if isinstance(v, str) else repr(v)


def run(ctx: dict) -> dict:
    """One run of a train cell.  ctx: root, work, cell, config, traffic,
    chips, seed, seconds, trace, t_process0; `break_step` is for the test
    that breaks the timed path."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from caffeonspark_tpu.caffe_on_spark import main as cos_main
    from caffeonspark_tpu.parallel import ParallelSolver
    from caffeonspark_tpu.processor import CaffeProcessor
    from ..harness import check, devices

    cfg, cell = ctx["config"], ctx["cell"]
    work = ctx["work"]
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    solver_path, pixels, rec_labels, facts, batch = write_inputs(ctx, work)
    print(f"[perfbench] inputs {facts}", flush=True)

    trace_dir = os.path.join(work, "trace") if ctx["trace"] else None
    seconds = (min(ctx["seconds"], float(cell.get("trace_seconds", 6)))
               if ctx["trace"] else ctx["seconds"])
    obs = Observer(seconds=seconds,
                   warmup_steps=int(cell["warmup_steps"]),
                   trace_dir=trace_dir, t_process0=ctx["t_process0"],
                   break_step=ctx.get("break_step"))
    from caffeonspark_tpu.data.queue_runner import transform_threads
    obs.round = max(1, transform_threads())
    jax.monitoring.register_event_duration_secs_listener(obs._on_compile)
    orig = ParallelSolver.train_step
    ParallelSolver.train_step = lambda ps: obs.wrap(orig(ps))

    def end_job():
        obs.done.wait()
        proc = CaffeProcessor._instance
        if proc is not None:
            proc.stop()             # the processor's own way to end a job
    ender = threading.Thread(target=end_job, daemon=True)
    ender.start()
    obs.stamps["job_started"] = time.perf_counter()
    try:
        rc = cos_main(["-conf", solver_path, "-train", "-output", work,
                       "-devices", str(ctx["chips"])])
    finally:
        ParallelSolver.train_step = orig
        obs.done.set()
        ender.join(120)
    if rc != 0 or obs.t_b is None:
        raise RuntimeError(f"-train returned {rc}; window "
                           f"{'closed' if obs.t_b else 'never closed'}")
    window_s = obs.t_b - obs.t_a
    steps = obs.n_b - obs.n_a
    setup_s, drain_s, drain_steps = obs.setup_and_drain()
    peak = obs.memory_peak
    obs.stamps["window_start"] = obs.t_a
    print("[perfbench] set-up, seconds from process start: "
          + ", ".join(f"{k} {v - obs.t0:.2f}" for k, v in obs.stamps.items()),
          flush=True)
    print(f"[perfbench] device memory {devices.memory_report()}", flush=True)
    losses = [float(v) for v in jax.device_get(obs.window_losses)]
    if steps >= 8:      # drift of the rate inside the window, for a reader
        ends = [obs.step_ends[steps * k // 8 - 1] - obs.t_a
                for k in range(1, 9)]
        print("[perfbench] eighths of the window's steps were dispatched "
              "by " + " ".join(f"{t:.2f}" for t in ends) + " s", flush=True)
    print(f"[perfbench] window {window_s:.4f} s, {steps} steps of {batch} "
          f"images (pool rounds of {obs.round}); peak HBM {peak} bytes; "
          f"set-up {setup_s:.2f} s "
          f"(+{obs.overhead:.2f} s copying for the comparison); "
          f"warm-up drain {drain_s:.2f} s, {drain_steps} steps; "
          f"compiles in window {obs.compiles_in_window}", flush=True)
    gc.collect()

    # ---- the comparison, after the window and outside setup_s ---------
    t_ref = time.perf_counter()
    model = importlib.import_module(
        "perfbench.reference." + cfg["reference"])
    crop = int(cfg["crop"])
    nums = {"nonfinite_window_losses":
            sum(1 for v in losses if not math.isfinite(v)),
            "compiles_in_window": obs.compiles_in_window}
    batches, pixel_gap = [], 0.0
    for k in range(CHECK_STEPS):
        found, gap = check.match_rows(
            obs.cap["strips"][k], obs.cap["labels"][k], pixels, rec_labels,
            cfg["mean_values"], crop, bool(ctx["traffic"].get("mirror")))
        data = check.rebuild_batch(found, pixels, cfg["mean_values"], crop)
        rows = obs.cap["row_idx"][k]
        gap = max(gap, float(np.max(np.abs(data[rows]
                                           - obs.cap["rows"][k]))))
        pixel_gap = max(pixel_gap, gap)
        batches.append((data, obs.cap["labels"][k]))
    nums["ingest_pixel_gap"] = pixel_gap
    t_match = time.perf_counter() - t_ref
    from ..reference import common
    lr_mults = check.lr_mults_of(model.layers(cfg, crop))
    ref = common.train_steps(model, cfg, ctx["seed"], batches,
                             jax.local_devices()[:ctx["chips"]])
    prog = {k: check.by_index(obs.cap[k])
            for k in ("p0", "p1", "v1", "p_last")}
    prog["losses"] = obs.cap["losses"]
    nums.update(check.compare(prog, ref, lr_mults,
                              cfg["solver"]["base_lr"]))
    correct = check.verdict(nums, cell["limits"])
    print(f"[perfbench] comparison took {time.perf_counter() - t_ref:.2f} s "
          f"({t_match:.2f} s of it finding the crops and rebuilding the "
          "batches)", flush=True)
    return {
        "correct": correct, "nums": nums,
        "attempted": steps, "failed": nums["nonfinite_window_losses"],
        "window_s": window_s, "steps": steps, "images": steps * batch,
        "setup_s": setup_s, "warmup_drain_s": drain_s,
        "warmup_drain_steps": drain_steps, "memory_peak_bytes": peak,
        "pipeline": (obs.metrics_a, obs.metrics_b), "batch": batch,
        "trace_dir": trace_dir, "facts": facts, "losses": losses,
        "flops_per_step": 3 * common.forward_flops(model, cfg, crop, batch),
    }
