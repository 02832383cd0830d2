"""Per-stage ingest/step timeline metrics for the pipelined runtime.

The reference executor has no visibility into where a training step's
wall-time goes (queue wait vs transform vs H2D vs solver); this module
gives the TPU pipeline that visibility cheaply: lock-guarded ring
buffers per stage, O(1) per sample, summarized on demand.

The serving subsystem records its stages (latency / assemble / pack /
fwd / exec_wait / time_to_first_flush series, queue_depth /
batch_fill gauges, served/rejected/expired and per-bucket
flush_bucket_<n> counters) through the same classes, so serving
metrics dump in this exact JSON format.  The fleet layer adds its own
series in the same shape: `route` (router-observed request time,
retries included), `replica_startup` / `replica_rejoin` (spawn →
healthy wall time, cold vs restart-on-death), counters `routed` /
`retries` / `retry_429` / `retry_503` / `retry_conn` /
`replica_restarts` / `rolling_reloads` (one per fleet-wide swap
operation; `replica_reloads` counts per-replica swaps), and a
per-replica
state/outstanding/requests table under `replicas` in the router
summary.

The train path's vocabulary — THE one place it is written down.  Every
thread of the feed -> pack -> stage -> step chain reports what it does
through `PipelineMetrics.span(stage, **attrs)`: on exit the span adds
its wall time to the series `stage` (what `add()` does) and it brackets
the same interval in a `jax.profiler.TraceAnnotation("cos.<stage>")`.
With no profiler session the annotation is inert (about half a
microsecond); with one (`POST /v1/profile`, `mini_cluster -profile`,
`perfbench --trace 1`) the span lands on the host plane of the same
`.xplane.pb` as the device ops, on the same clock.  "Tracing on" means
"a profiler session is running": there is no other switch.

*busy* spans are recorded once per unit of work.  *starved* (waiting for
upstream) and *blocked* (waiting for downstream) spans are recorded only
for an actual wait: the non-blocking get/put is tried first and the span
opens around the polling loop only when that fails, so one sample is one
real episode.  Every span carries `n=` the ordinal of the batch at that
stage (the pool emits in feed order, so ordinal k at `pack` is ordinal k
at `stage` and step k while iter_size is 1 and nothing was dropped: a
dropped batch keeps its `n` at `pack` and never reaches `stage`); worker
spans also carry `w=` the worker index.  A span opened with no attrs
inside another span on the same thread inherits the outer one's.

  thread      series / span   kind     covers
  feeder      read            busy     source iteration (LMDB cursor,
                                       Datum parse, shuffle buffer):
                                       timed per record, summed, ONE
                                       sample per batch_size records
                                       (`timed_records`); series only
              read_blocked    blocked  the feed queue is full
                                       (FeedQueue.offer: every feeder,
                                       Spark's through feed_queue)
  dispatcher  group_starved   starved  no record to take
              group_blocked   blocked  the pool's work queue is full
  worker      pack            busy     whole pack of one batch; ONE
                                       sample per packed batch
              pack_decode     busy     child of pack: records -> pixels
                                       (JPEG decode into uint8 planes;
                                       for raw records, gathering the
                                       payloads; on the general path
                                       the float32 `data` array)
              pack_transform  busy     child of pack: crop/mirror/mean/
                                       scale into the batch (one native
                                       pass from the uint8 pixels;
                                       Transformer.__call__ on the
                                       general path; host_stage under
                                       COS_DEVICE_TRANSFORM)
              pack_cpu        series   time.thread_time() delta over the
                                       same interval as pack: seconds the
                                       WORKER's own thread was on a CPU
                                       (the rest is GIL or scheduler
                                       wait).  A native call runs one
                                       share of its work on the worker
                                       and the rest on helper threads
                                       this does not count: CPUs a pack
                                       kept busy ~ pack_cpu x
                                       pack_threads
              pack_threads    gauge    one sample per pack: threads its
                                       native calls were given (0 =
                                       nobody chose, every core; a pool
                                       gives each worker its share of
                                       the cores, tune_decode_threads)
              pack_starved    starved  no work queued
              pack_blocked    blocked  results window full (_deposit)
  (any)       stack           busy     np.stack of K packed batches into
                                       one (K, batch…) block (fused
                                       multi-step path only)
  stager      stage_starved   starved  waiting for a packed batch
              stage           busy     whole staging of one batch
              stage_copy      busy     child of stage: host copy
                                       (copy-on-CPU aliasing defense)
              stage_put       busy     child of stage: device_put /
                                       make_array_from_process_local_data
                                       + device-transform dispatch
              stage_blocked   blocked  hand-off queue full
  solver      queue_wait      starved  next(gen): waiting for a staged
                                       batch
              step            busy     ENQUEUE of one dispatch, attrs
                                       it= first iteration, k= steps in
                                       it.  On an accelerator the async
                                       runtime returns before compute
                                       finishes: this is dispatch wall
                                       time, not device time (throughput
                                       comes from mark_step()); for a
                                       fused chunk the series holds
                                       chunk_time/K per step
              scan_step       busy     one fused K-step dispatch (whole
                                       chunk; the profiler span of a
                                       fused chunk is cos.step, k=K)
              validation      busy     the train loop is held by a
                                       validation round
              snapshot        busy     ... by a snapshot
              init_params     busy     parameter + optimizer-state init
  (compiler)  compile         series   programs the backend compiled
              cache_load      series   programs fetched from the
                                       persistent cache (`CompileWatch`,
                                       from jax.monitoring; counters
                                       cache_hits / cache_misses)
  solver      comm            series   injected gradient-exchange floor
                                       sleeps (bench drills:
                                       COS_FAULT_COMM_NS_PER_BYTE)
              sync_exchange   busy     relaxed sync modes: host-side
                                       round-average / global merge

Static run facts ride in the same JSON via `set_info`: the trainer
publishes the gradient-exchange plan as `info.comm` (per-step wire
bytes, bucket count and sizes, wire dtype, mode), the resolved
fault-injection plan as `info.faults` (tools/chaos.py — {"active":
false} on clean runs, the exact injectors otherwise, so every drill
and bench artifact is self-describing), and the sync-mode policy +
final exchange counts as `info.sync` (COS_SYNC_MODE, K/staleness,
exchanges / skipped / adopted / timeouts / max_gap), and after the
first step what its operators were lowered to, one `info.<kind>` for
every kind in `ops.route.plans()` (`info.flash`, `info.moe`,
`info.recompute` and their like: what a kind's facts mean is written
where `ops.route.lowered` is called for it).
Where the
expert layers return their
stats, the summary's `experts` says what they did over the last steps
(`moe.passes_run`: `experts.passes_run`, a layer's mean and max of the
passes that ran, beside `held_share`; read from the steps' outputs when
the summary is read, `processor.ExpertWindow`).  The relaxed
sync modes also record a `sync_exchange` stage series (host-side
round-average / global-merge wall time).  The continuous-deployment
controller publishes `info.deploy` the same way (incumbent, verdict
history, per-state counts, knobs) plus a `deploy_round` wall series
and `deploy_<verdict>` counters.

Stages are NOT disjoint when staging (and, on the inline path, packing)
runs synchronously inside next(gen): there queue_wait SUBSUMES the pack
and stage samples recorded for the same batch, so per-stage totals can
legitimately exceed wall-time.  They are disjoint in the fully
pipelined configuration (pool + background stager), where queue_wait
measures pure starvation.

Counters (dropped batches, ragged-tail records) and gauges (queue
depths, sampled each step) ride along in the same summary.  `pack_fused`
and `pack_general` count batches by the way their pack took
(`DataSource.next_batch` has the rule): the one-pass kernel over uint8
pixels, or float32 `data` through `Transformer.__call__`; the
device-transform split's batches count as neither.

The observability layer (caffeonspark_tpu/obs) builds on this format
without a second bookkeeping path: `obs/prom.py` renders the same
summary dict as Prometheus exposition (`/metrics?format=prom`), and
`MetricsFlusher` (COS_METRICS_FLUSH_S) background-flushes it to
`<output>/metrics.json` through the fsync'd atomic-write path so a
SIGKILLed run keeps telemetry no older than one interval.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Callable, Dict, List, Optional

_DEFAULT_CAPACITY = 8192


class _Series:
    """Total/count plus a bounded sample ring for percentiles."""

    __slots__ = ("total", "count", "max", "_ring", "_cap", "_i")

    def __init__(self, capacity: int):
        self.total = 0.0
        self.count = 0
        self.max = 0.0
        self._ring: List[float] = []
        self._cap = capacity
        self._i = 0

    def add(self, v: float):
        self.total += v
        self.count += 1
        if v > self.max:
            self.max = v
        if len(self._ring) < self._cap:
            self._ring.append(v)
        else:
            self._ring[self._i] = v
            self._i = (self._i + 1) % self._cap

    def summary(self) -> Dict[str, float]:
        s = sorted(self._ring)
        n = len(s)

        def pct(p):
            return s[min(n - 1, int(p * n))] if n else 0.0

        return {
            "count": self.count,
            "total_s": round(self.total, 6),
            "mean_ms": round(1e3 * self.total / self.count, 4)
            if self.count else 0.0,
            "p50_ms": round(1e3 * pct(0.50), 4),
            "p95_ms": round(1e3 * pct(0.95), 4),
            "p99_ms": round(1e3 * pct(0.99), 4),
            "p99_9_ms": round(1e3 * pct(0.999), 4),
            "max_ms": round(1e3 * self.max, 4),
        }


class _Gauge:
    """Sampled depth/level: count, mean, max."""

    __slots__ = ("total", "count", "max")

    def __init__(self):
        self.total = 0.0
        self.count = 0
        self.max = 0.0

    def observe(self, v: float):
        self.total += v
        self.count += 1
        if v > self.max:
            self.max = v

    def summary(self) -> Dict[str, float]:
        return {
            "samples": self.count,
            "mean": round(self.total / self.count, 3) if self.count else 0.0,
            "max": self.max,
        }


_ANNOTATION = None          # jax.profiler.TraceAnnotation, resolved once


class _NullAnnotation:
    """Stands in where jax cannot be imported: a span is then add()."""

    def __init__(self, name, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:
            _ANNOTATION = _NullAnnotation
    return _ANNOTATION


class _Span:
    """One interval of one thread: series sample + profiler annotation
    (PipelineMetrics.span).  Nothing is added to the series when the
    body raises — a pack that failed is a drop, not a pack."""

    __slots__ = ("_m", "_stage", "_attrs", "_outer", "_ann", "_t0",
                 "seconds")

    def __init__(self, m: "PipelineMetrics", stage: str, attrs: dict):
        self._m, self._stage, self._attrs = m, stage, attrs

    def __enter__(self):
        tls = self._m._tls
        self._outer = getattr(tls, "attrs", None)
        if not self._attrs and self._outer:
            self._attrs = self._outer
        tls.attrs = self._attrs
        self._ann = _annotation()("cos." + self._stage, **self._attrs)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(et, ev, tb)
        self._m._tls.attrs = self._outer
        if et is None:
            self._record(self.seconds)
        return False

    def _record(self, seconds: float):
        self._m.add(self._stage, seconds)


class _StepSpan(_Span):
    """cos.step: one dispatch of k solver steps (PipelineMetrics.step_span)."""

    __slots__ = ()

    def _record(self, seconds: float):
        k = self._attrs["k"]
        if k == 1:
            self._m.add("step", seconds)
            self._m.mark_step()
        else:
            self._m.add_chunk(k, seconds)


class PipelineMetrics:
    """Thread-safe per-stage timeline: durations, counters, gauges, and
    step timestamps for steady-state throughput."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._series: Dict[str, _Series] = {}
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, _Gauge] = {}
        self._steps: List[float] = []
        self._info: Dict[str, object] = {}
        self._sections: Dict[str, Callable[[], object]] = {}
        self._cap = capacity
        self._step_i = 0
        self._created = time.monotonic()
        self._tls = threading.local()   # span attrs, per thread

    # -- recording (hot path: one lock, O(1)) ---------------------------
    def add(self, stage: str, seconds: float):
        with self._lock:
            s = self._series.get(stage)
            if s is None:
                s = self._series[stage] = _Series(self._cap)
            s.add(seconds)

    def span(self, stage: str, **attrs) -> _Span:
        """`with m.span(stage, n=k):` — on exit what add(stage, seconds)
        does, and the same interval as a `cos.<stage>` annotation on
        the profiler's clock (inert unless a profiler session runs).
        add() stays for back-dated intervals."""
        return _Span(self, stage, attrs)

    def step_span(self, it: int, k: int) -> _Span:
        """The solver thread's dispatch of `k` steps from iteration
        `it`: `step` + mark_step() for k == 1, add_chunk() for a fused
        chunk; either way one `cos.step` annotation."""
        return _StepSpan(self, "step", {"it": it, "k": k})

    def incr(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge(self, name: str, value: float):
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = _Gauge()
            g.observe(value)

    def set_info(self, name: str, value) -> None:
        """Attach a static (JSON-serializable) fact to the summary —
        e.g. the gradient-exchange plan under "comm"."""
        with self._lock:
            self._info[name] = value

    def set_section(self, name: str, read: Callable[[], object]) -> None:
        """A top-level entry of the summary that is computed when the
        summary is read (`read()`, JSON-serializable, None = left out)
        — e.g. "experts", from device values nobody waits for while the
        job steps."""
        with self._lock:
            self._sections[name] = read

    def mark_step(self, n: int = 1):
        """Timestamp `n` completed solver steps (throughput series).
        A fused K-step chunk lands K marks at the same instant — the
        steady-rate computation only cares about mark COUNT between
        first and last timestamp, so chunked and per-step runs report
        comparable steps/sec."""
        with self._lock:
            now = time.monotonic()
            for _ in range(max(1, n)):
                if len(self._steps) < self._cap:
                    self._steps.append(now)
                else:
                    self._steps[self._step_i] = now
                    self._step_i = (self._step_i + 1) % self._cap

    def add_chunk(self, n: int, seconds: float):
        """Fused-chunk accounting: one `scan_step` sample for the whole
        K-step dispatch, the recovered per-step device time (chunk/K)
        into the `step` series so per-step percentiles stay comparable
        with K=1 runs, and K step marks."""
        self.add("scan_step", seconds)
        per = seconds / max(1, n)
        for _ in range(max(1, n)):
            self.add("step", per)
        self.mark_step(n)

    # -- reading --------------------------------------------------------
    def get_counter(self, name: str) -> int:
        """One counter's current value (0 if never incremented) — the
        cheap point read for pollers (fleet bench, tests) that a full
        summary() would make O(all series)."""
        with self._lock:
            return self._counters.get(name, 0)

    def has_samples(self) -> bool:
        with self._lock:
            return bool(self._series or self._counters or self._steps
                        or self._info)

    def steady_steps_per_sec(self, skip: int = 5) -> Optional[float]:
        """Throughput over the step timestamps with the first `skip`
        (compile + cache warmup) steps discarded; None if too few."""
        with self._lock:
            if self._step_i:     # ring wrapped: chronological order
                ts = self._steps[self._step_i:] + self._steps[:self._step_i]
            else:
                ts = list(self._steps)
        ts = ts[skip:]
        if len(ts) < 2 or ts[-1] <= ts[0]:
            return None
        # count only marks strictly after the window start: a fused
        # chunk lands K marks at ONE timestamp, so (len-1)/span would
        # count the first chunk's remaining marks as work done inside
        # the window and overstate the rate; for per-step runs
        # (distinct timestamps) this is exactly (len-1)/span
        t0 = ts[0]
        n_after = sum(1 for t in ts if t > t0)
        return n_after / (ts[-1] - t0)

    def summary(self) -> dict:
        with self._lock:
            stages = {k: v.summary() for k, v in self._series.items()}
            counters = dict(self._counters)
            gauges = {k: v.summary() for k, v in self._gauges.items()}
            nsteps = len(self._steps)
            info = dict(self._info)
            sections = dict(self._sections)
        out = {
            "stages": stages,
            "counters": counters,
            "queue_depths": gauges,
            "steps": nsteps,
            "uptime_s": round(time.monotonic() - self._created, 3),
        }
        if info:
            out["info"] = info
        for name, read in sections.items():
            value = read()
            if value is not None:
                out[name] = value
        sps = self.steady_steps_per_sec()
        if sps is not None:
            out["steady_steps_per_sec"] = round(sps, 3)
        return out

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.summary(), f, indent=2, sort_keys=True)
            f.write("\n")
        return path

    def dump_atomic(self, path: str) -> str:
        """Summary via the fsync'd atomic-write path — readers (and a
        post-mortem after SIGKILL) only ever see a complete document."""
        from .utils.fsutils import atomic_write_local
        summary = self.summary()

        def _write(tmp):
            with open(tmp, "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True)
                f.write("\n")

        atomic_write_local(path, _write)
        return path


_NO_SPAN = contextlib.nullcontext()


def span_of(metrics: Optional[PipelineMetrics], stage: str, **attrs):
    """`metrics.span(...)`, or nothing where a stage was handed no
    PipelineMetrics (-features, serving, bare library use)."""
    return _NO_SPAN if metrics is None else metrics.span(stage, **attrs)


def timed_records(records, metrics: Optional[PipelineMetrics],
                  batch_size: int):
    """Yield from `records`, timing the source's own iteration (LMDB
    cursor, Datum parse, shuffle buffer) per record and recording the
    sum as ONE `read` sample per `batch_size` records — the feeder's
    busy time.  Series only: a profiler span per record would cost more
    than the read; on the timeline the feeder's busy time is the
    complement of `read_blocked`."""
    if metrics is None:
        yield from records
        return
    it = iter(records)
    acc, k = 0.0, 0
    while True:
        t0 = time.perf_counter()
        try:
            rec = next(it)
        except StopIteration:
            return
        acc += time.perf_counter() - t0
        k += 1
        if k == batch_size:
            metrics.add("read", acc)
            acc, k = 0.0, 0
        yield rec


class CompileWatch:
    """jax.monitoring -> the `compile` and `cache_load` series (and the
    `cache_hits` / `cache_misses` counters) of one trainer.

    On JAX 0.9.0 `/jax/core/compile/backend_compile_duration` fires for
    EVERY program, fetched or compiled: it wraps compile_or_get_cached.
    A fetch from the persistent cache first fires
    `/jax/compilation_cache/cache_retrieval_time_sec` (lookup +
    deserialize + load) on the same thread, so that one is the
    `cache_load` sample and the backend-compile event right behind it on
    that thread is skipped.  `iteration()` is the trainer's current
    iteration, None before its first step: a compile after that also
    goes to the flight recorder — "which step recompiled".  stop()
    unregisters; jax's other listeners are never touched."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
    COUNTERS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self, metrics: PipelineMetrics, iteration=lambda: None):
        self.metrics = metrics
        self.iteration = iteration
        self._fetched = threading.local()
        self._on = False

    def _on_duration(self, event: str, seconds: float, **kw):
        if event == self.CACHE_RETRIEVAL:
            self._fetched.flag = True
            self.metrics.add("cache_load", seconds)
        elif event == self.BACKEND_COMPILE:
            if getattr(self._fetched, "flag", False):
                self._fetched.flag = False
                return
            self.metrics.add("compile", seconds)
            it = self.iteration()
            if it is not None:
                from .obs.recorder import record
                record("trainer", "compile", it=it,
                       seconds=round(seconds, 4))

    def _on_event(self, event: str, **kw):
        name = self.COUNTERS.get(event)
        if name is not None:
            self.metrics.incr(name)

    def start(self) -> "CompileWatch":
        if not self._on:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                self._on_duration)
            monitoring.register_event_listener(self._on_event)
            self._on = True
        return self

    def stop(self) -> None:
        if self._on:
            from jax import monitoring
            monitoring.unregister_event_duration_listener(
                self._on_duration)
            monitoring.unregister_event_listener(self._on_event)
            self._on = False


def metrics_flush_s() -> float:
    """COS_METRICS_FLUSH_S: background-flush interval for the summary
    artifact; 0/unset = the historical dump-only-at-stop behavior."""
    from .utils.envutils import env_num
    return max(0.0, env_num("COS_METRICS_FLUSH_S", 0.0, strict=False))


class MetricsFlusher:
    """Background thread flushing a PipelineMetrics summary to disk
    every `interval_s` (the atomic-write path), so a SIGKILLed run
    leaves telemetry no older than one interval instead of nothing.
    `stop()` lands one final flush."""

    def __init__(self, metrics: PipelineMetrics, path: str,
                 interval_s: float):
        self.metrics = metrics
        self.path = path
        self.interval_s = max(0.05, float(interval_s))
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.flushes = 0
        self.errors = 0

    def _flush_once(self) -> None:
        try:
            self.metrics.dump_atomic(self.path)
            self.flushes += 1
        except OSError:
            # a bad path/full disk must never take the run down; the
            # final stop() flush surfaces persistent failure via count
            self.errors += 1

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._flush_once()

    def start(self) -> "MetricsFlusher":
        assert self._thread is None, "flusher already started"
        self._thread = threading.Thread(target=self._loop,
                                        name="cos-metrics-flush",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        self._flush_once()


def maybe_start_flusher(metrics: PipelineMetrics,
                        output_dir: Optional[str],
                        filename: str = "metrics.json"
                        ) -> Optional[MetricsFlusher]:
    """Start the periodic flusher when COS_METRICS_FLUSH_S > 0 and an
    output directory exists to land `<output>/metrics.json` in."""
    interval = metrics_flush_s()
    if interval <= 0 or not output_dir:
        return None
    import os
    path = os.path.join(output_dir, filename)
    return MetricsFlusher(metrics, path, interval).start()
