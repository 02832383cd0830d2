"""CaffeProcessor: per-executor training/inference engine.

Mirror of `caffe-grid/.../CaffeProcessor.scala` re-designed for a TPU
process: a per-process singleton (`instance()`, :20-30) that owns the
compiled Solver + mesh step, bounded feed queues with STOP_MARK /
backpressure semantics (:192-198, :205), transformer threads feeding a
device-prefetch pipe (:254-383 doTransform), a solver loop (:413-471
doTrain) with interleaved validation (queue 1, :388-411
updateValidationReport) and rank-0 snapshotting (:454-458), and a
feature-extraction path (:473-523 doFeatures).

The sync() barrier (:180-189) is retained for API parity; under SPMD it
only needs to order host-side epochs — collectives themselves are the
barrier.
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import os
import queue
import threading
from typing import (Any, Dict, Iterator, List, Optional,
                    Sequence, Tuple)

import numpy as np

_LOG = logging.getLogger(__name__)

from . import checkpoint
from .config import Config
from .data.queue_runner import (DROP_LIMIT_DEFAULT, DROPPED, FeedQueue,
                                TransformerPool, chunked_feed,
                                device_prefetch, stage_background,
                                stage_depth, steps_per_loop,
                                transform_threads, tune_decode_threads)
from .data.source import STOP_MARK, DataSource
from .metrics import CompileWatch, PipelineMetrics
from .parallel import ParallelSolver, build_mesh, parse_mesh_spec
from .solver import Solver

# historical alias: the parser now lives with the mesh machinery
# (parallel.mesh.parse_mesh_spec — shared with the serving CLI)
_parse_mesh_spec = parse_mesh_spec


class ValidationReport:
    """Accumulates per-output means over batch × test_iter
    (updateValidationReport analog)."""

    def __init__(self, names: Sequence[str]):
        self.names = list(names)
        self.rounds: List[Dict[str, float]] = []
        self._acc: Dict[str, float] = {}
        self._n = 0

    def add_batch(self, outputs: Dict[str, Any]):
        for n in self.names:
            v = float(np.mean(np.asarray(outputs[n])))
            self._acc[n] = self._acc.get(n, 0.0) + v
        self._n += 1

    def finish_round(self):
        if self._n:
            self.rounds.append({n: self._acc[n] / self._n
                                for n in self.names})
        self._acc, self._n = {}, 0


class ExpertWindow:
    """What the dropless expert layers did over the last `STEPS` steps,
    from the `moe_stats` the step returns anyway: nothing is fetched
    while the job steps, the record is computed when the metrics are
    read (`PipelineMetrics.set_section`).  `passes_run` is how often
    the layer's loop over the passes ran, forward and backward: held
    assignments over the rows a pass takes, rounded up
    (`layers._moe_passes_run`)."""

    STEPS = 64

    def __init__(self, layers: Dict[str, Tuple[str, dict]]):
        # stats top -> (layer, its entry of `moe_plans()`)
        self.layers = layers
        self._steps: collections.deque = collections.deque(
            maxlen=self.STEPS)
        self._lock = threading.Lock()

    def add(self, out: Dict[str, Any]):
        with self._lock:
            self._steps.append([out[top] for top in self.layers])

    def record(self) -> Optional[dict]:
        import jax
        with self._lock:
            steps = list(self._steps)
        if not steps:
            return None
        # a fused chunk's stats are (K, 3), a step's (3,)
        per_layer = [np.concatenate([np.reshape(v, (-1, 3)) for v in vs],
                                    dtype=np.float64)
                     for vs in zip(*jax.device_get(steps))]
        passes_run = {}
        for (layer, plan), vals in zip(self.layers.values(), per_layer):
            held = np.rint(vals[:, 1] * plan["assignments"])
            ran = np.minimum(plan["passes"],
                             np.ceil(held / plan["rows"]))
            passes_run[layer] = {"mean": float(ran.mean()),
                                 "max": int(ran.max())}
        return {"steps": len(per_layer[0]),
                "held_share": float(np.mean([v[:, 1].mean()
                                             for v in per_layer])),
                "passes_run": passes_run}


class CaffeProcessor:
    _instance: Optional["CaffeProcessor"] = None

    # -- singleton protocol (CaffeProcessor.scala:20-30) -----------------
    @classmethod
    def instance(cls, conf: Optional[Config] = None, rank: int = 0
                 ) -> "CaffeProcessor":
        if conf is not None:
            # same Config object → same processor (so train() followed by
            # features()/test() keeps the in-memory trained params)
            if cls._instance is not None and cls._instance.conf is conf:
                return cls._instance
            if cls._instance is not None:
                cls._instance.stop()
            cls._instance = cls(conf, rank)
        assert cls._instance is not None, "processor not started"
        return cls._instance

    def __init__(self, conf: Config, rank: int = 0):
        from .data.source import get_source
        self.conf = conf
        self.rank = rank
        import jax
        devices = (jax.local_devices()[:conf.devices]
                   if conf.devices > 0
                   else None)  # -devices limits THIS host's devices
        if conf.mesh:
            mesh = build_mesh(devices=devices,
                              **_parse_mesh_spec(conf.mesh))
        else:
            mesh = build_mesh(devices=devices)
        # data sharding + rng seeding follow the mesh's DP coordinate
        # when processes form a jax.distributed cluster: tp/sp ranks
        # share replicated activations, so their augmentation/dropout
        # streams must match and every rank must feed the SAME records
        # (mini_cluster has the identical rule).  Outside a cluster
        # (Spark local engine, tests) the conf rank/clusterSize
        # semantics stand.
        if jax.process_count() > 1:
            from .parallel import dp_data_rank
            data_rank, data_ranks = dp_data_rank(mesh)
        else:
            data_rank, data_ranks = rank, max(1, conf.clusterSize)
        self.solver = Solver(conf.solverParameter, conf.netParam,
                             rank=data_rank)
        self.psolver = ParallelSolver(self.solver, mesh)
        self.queues = [FeedQueue(), FeedQueue()]   # 0 train, 1 validation
        self.results: List[Dict[str, Any]] = []
        self.validation: Optional[ValidationReport] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._stopped = False
        # set by trainWithValidation: only then does anyone feed queue 1
        self.interleave_validation = False
        self.dropped_batches = 0      # driver reads this to re-sync feeds
        self.dropped_val_batches = 0  # informational (round shrinks)
        self._consecutive_drops = 0
        self._consecutive_val_drops = 0
        # pack runs on pool worker threads while validation packs on
        # the solver thread: all drop accounting shares one lock
        self._drop_lock = threading.Lock()
        self.metrics = PipelineMetrics()  # step-timeline (stop() dumps)
        # called on the solver thread after each dispatch with
        # (it, n, batch, params, opt_state, outputs): `it` the first
        # iteration of the dispatch, `n` its steps.  None = one test
        # per step; the loop computes nothing for it.
        self.step_observer = None
        self._it_now: Optional[int] = None   # None until the first step
        self._compile_watch = CompileWatch(self.metrics,
                                           lambda: self._it_now)
        self._flusher = None          # COS_METRICS_FLUSH_S (start())
        self._obs_server = None       # COS_METRICS_PORT (start())
        self._train_pool: Optional[TransformerPool] = None
        self._val_pool: Optional[TransformerPool] = None
        self._snapshotter = None      # lazy AsyncSnapshotter (-async_snapshot)
        self._val_shardings = None    # set when the val feed splits
        self.params = None
        self.opt_state = None

        seed = int(conf.solverParameter.random_seed) \
            if conf.solverParameter.random_seed >= 0 else 0
        self._source_kw = dict(rank=data_rank, num_ranks=data_ranks,
                               seed=seed, resize=conf.resize)
        tl = conf.train_data_layer()
        self.train_source: Optional[DataSource] = (
            get_source(tl, phase_train=True, **self._source_kw)
            if tl is not None and conf.isTraining else None)
        vl = conf.test_data_layer()
        self.val_source: Optional[DataSource] = (
            get_source(vl, phase_train=False, **self._source_kw)
            if vl is not None else None)
        # the stages with no handle on the job's metrics get this one:
        # next_batch reports its halves, a full feed queue the feeder's
        # wait (FeedQueue.offer: Spark feeders come through feed_queue)
        for src, q in zip((self.train_source, self.val_source),
                          self.queues):
            q.metrics = self.metrics
            if src is not None:
                src.metrics, q.batch_size = self.metrics, src.batch_size

    # -- queue API (feedQueue backpressure, :192-198) --------------------
    def feed_queue(self, idx: int, sample) -> bool:
        return self.queues[idx].offer(sample)

    def mark_epoch_end(self, idx: int = 0):
        self.queues[idx].mark_epoch_end()

    def sync(self):
        """Cluster barrier analog — host-side ordering only."""
        return True

    # -- lifecycle -------------------------------------------------------
    def start(self):
        self._it_now = None
        self._compile_watch.start()
        self._init_params()
        for q in self.queues:       # re-arm after a previous run stopped
            q.reset()
        self._train_pool = None     # _run_train builds fresh pools
        self._val_pool = None
        # observability: periodic summary flush to <output>/metrics.json
        # (COS_METRICS_FLUSH_S — a SIGKILLed run keeps telemetry) and
        # the live metrics port (COS_METRICS_PORT)
        if self._flusher is None and self.rank == 0:
            from .metrics import maybe_start_flusher
            self._flusher = maybe_start_flusher(
                self.metrics, getattr(self.conf, "outputPath", ""))
        if self._obs_server is None and self.rank == 0:
            from .obs.http import maybe_start_obs_server
            self._obs_server = maybe_start_obs_server(
                self.metrics.summary, role="trainer")
        self._thread = threading.Thread(target=self._run_train,
                                        daemon=True)
        self._thread.start()

    def _init_params(self):
        if self.params is not None:
            return
        with self.metrics.span("init_params"):
            params, st = self.psolver.init()
            conf = self.conf
            if conf.snapshotStateFile:
                params, st = checkpoint.restore(
                    self.solver.train_net, params, st,
                    conf.snapshotStateFile,
                    weights_path=conf.snapshotModelFile or None)
                params = self.psolver.shard_params(params)
                st = self.psolver.shard_opt_state(st)
            elif conf.snapshotModelFile:
                params = checkpoint.copy_layers(
                    self.solver.train_net, params, conf.snapshotModelFile)
                params = self.psolver.shard_params(params)
            self.params, self.opt_state = params, st

    def stop(self):
        self._stopped = True
        for q in self.queues:
            q.stop()
        if self._thread is not None:
            self._thread.join(timeout=600)
            self._thread = None
        snap_err = None
        if self._snapshotter is not None:   # pending write-behind lands
            try:
                self._snapshotter.wait(timeout=600)
            except BaseException as e:      # noqa: BLE001
                snap_err = e                # must not mask train error
        self._compile_watch.stop()
        if self._obs_server is not None:
            self._obs_server.stop()
            self._obs_server = None
        if self._flusher is not None:       # final flush at stop
            self._flusher.stop()
            self._flusher = None
        self._dump_metrics()
        CaffeProcessor._instance = None
        if self._error is not None:
            raise self._error
        if snap_err is not None:
            raise snap_err

    def _dump_metrics(self):
        """Step-timeline dump at shutdown: one INFO line always, plus a
        JSON artifact when COS_PIPELINE_METRICS names a path."""
        m = self.metrics
        if not m.has_samples():
            return
        summary = m.summary()
        _LOG.info("pipeline metrics: %s",
                  json.dumps(summary, sort_keys=True))
        path = os.environ.get("COS_PIPELINE_METRICS")
        if path:
            try:
                m.dump(path)
            except OSError as e:
                _LOG.warning("could not write pipeline metrics to "
                             "%s: %s", path, e)

    def join(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # -- training loop (doTrain, :413-471) -------------------------------
    def _train_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        assert self.train_source is not None
        src = self.train_source
        buf: List = []
        while not self._stopped:
            try:
                item = self.queues[0].take(timeout=1.0)
            except queue.Empty:
                continue
            if item is STOP_MARK:
                buf = []       # epoch boundary: drop ragged tail
                continue
            if item is None:
                return         # terminal sentinel
            buf.append(item)
            if len(buf) == src.batch_size:
                batch = self._pack_or_drop(src, buf)
                if batch is not None:
                    yield batch
                buf = []

    MAX_CONSECUTIVE_DROPS = DROP_LIMIT_DEFAULT

    def _note_pack_ok(self, *, val: bool = False):
        with self._drop_lock:
            if val:
                self._consecutive_val_drops = 0
            else:
                self._consecutive_drops = 0

    def _note_pack_drop(self, e: Exception, *, val: bool = False):
        """Thread-safe drop accounting shared by the transformer pool's
        workers and the inline validation pack — the reference's
        per-iteration failure tolerance (CaffeProcessor.scala:449-451).
        A run of consecutive failures means a systematic config error
        and aborts (raises) instead of spinning forever.  Train and
        validation keep SEPARATE consecutive counters: the pools pack
        concurrently, and a healthy train feed must not keep resetting
        the streak of a systematically failing validation source (or
        vice versa).  Drop totals are also separate: only TRAIN drops
        make the driver top up the train feed (a dropped validation
        batch already advanced the round counter, so topping up train
        records for it would skew the cadence)."""
        with self._drop_lock:
            if val:
                self._consecutive_val_drops += 1
                consecutive = self._consecutive_val_drops
                self.dropped_val_batches += 1
            else:
                self._consecutive_drops += 1
                consecutive = self._consecutive_drops
                self.dropped_batches += 1
        self.metrics.incr("dropped_val_batches" if val
                          else "dropped_batches")
        _LOG.warning("dropping batch after record error: %s", e)
        if consecutive >= self.MAX_CONSECUTIVE_DROPS:
            raise RuntimeError(
                f"{consecutive} consecutive batch failures — "
                f"systematic data/config error; last: {e}") from e

    def _pack_or_drop(self, src, buf, *, val: bool = False):
        """Inline pack with the drop policy (validation rounds and the
        COS_TRANSFORM_THREADS=0 legacy train path)."""
        try:
            with self.metrics.span("pack"):
                batch = src.next_batch(buf)
        except Exception as e:
            self._note_pack_drop(e, val=val)   # raises at the limit
            return None
        self._note_pack_ok(val=val)
        return batch

    def _run_train(self):
        gen = None
        try:
            import jax
            solver, ps = self.solver, self.psolver
            # gradient-exchange plan (COS_GRAD_SYNC) into the
            # step-timeline artifact: every pipeline-metrics JSON
            # states the per-step wire bytes / buckets / wire dtype
            gs = getattr(solver, "grad_sync", None)
            if gs is not None:
                self.metrics.set_info("comm", gs.plan.comm_info())
            # autotune plan (COS_AUTOTUNE) into the artifact exactly
            # like info.comm/info.sync: {"active": false} when unset,
            # else the plan key + per-layer variants applied
            self.metrics.set_info(
                "autotune", solver.train_net.autotune_info())
            # unified chaos layer (tools/chaos.py): the driver path
            # honors the step-delay / die-once / slow-rank injectors
            # too, and publishes the resolved plan so every metrics
            # artifact states what was injected.  The sync-mode policy
            # rides along (the driver is one process — the relaxed
            # modes' cross-rank exchange lives in mini_cluster; here
            # lockstep IS the only shape, but the artifact says so).
            from .tools.chaos import make_injector
            inj = make_injector(self.rank)
            self.metrics.set_info("faults", inj.plan.describe())
            self.metrics.set_info(
                "sync", getattr(solver, "sync_policy").describe())
            step = ps.train_step()
            eval_step = (ps.eval_step()
                         if solver.test_net is not None else None)
            sp = solver.param
            test_interval = sp.test_interval
            test_iter = solver.test_iter
            snap = sp.snapshot or 0
            max_iter = sp.max_iter
            if eval_step is not None and solver.test_net is not None:
                self.validation = ValidationReport(
                    solver.test_net.output_blobs)
            it = int(jax.device_get(self.opt_state.iter))
            from .data.queue_runner import combine_batches
            tmajor = frozenset(
                n for n, _, kind in solver.train_net.input_specs
                if kind.endswith(":T"))
            dxf = (self.train_source.enable_device_transform(
                       solver.train_net.dtype)
                   if self.train_source is not None else None)
            # validation feed takes the same split (center crop on
            # uint8 host-side, mean/scale on device before eval_step);
            # the stage output must carry eval_step's input shardings
            self._val_shardings = None
            if self.val_source is not None and solver.test_net is not None:
                if self.val_source.enable_device_transform(
                        solver.test_net.dtype):
                    self._val_shardings = ps.input_shardings(
                        solver.test_net)
            # pipelined ingest (the tentpole): a threaded transformer
            # pool packs batches off the solver thread, and the device
            # stager (H2D + jitted device-transform dispatch) runs on
            # its own background thread — the solver thread only ever
            # waits on ready, staged batches.  COS_TRANSFORM_THREADS=0
            # keeps the legacy inline path (pack + stage on the solver
            # thread).
            nthreads = transform_threads()
            src = self.train_source
            if nthreads > 0 and src is not None:
                tune_decode_threads(src, nthreads)
                self._train_pool = TransformerPool(
                    self.queues[0], src.batch_size,
                    pack=src.pack_batch, draw_fn=src.make_draw_fn(),
                    num_threads=nthreads,
                    on_pack_ok=self._note_pack_ok,
                    on_pack_error=lambda e: self._note_pack_drop(e),
                    metrics=self.metrics,
                    should_stop=lambda: self._stopped).start()
                batches = iter(self._train_pool)
            else:
                batches = self._train_batches()
            if (nthreads > 0 and self.interleave_validation
                    and self.val_source is not None
                    and eval_step is not None):
                vsrc = self.val_source
                # one pack worker: validation packs ahead between
                # rounds and is off the latency-critical path — extra
                # workers would only pressure the train pool, and its
                # native calls get one train worker's share of the cores
                tune_decode_threads(vsrc, nthreads)
                self._val_pool = TransformerPool(
                    self.queues[1], vsrc.batch_size,
                    pack=vsrc.pack_batch, draw_fn=vsrc.make_draw_fn(),
                    num_threads=1,
                    on_pack_ok=lambda: self._note_pack_ok(val=True),
                    on_pack_error=lambda e: self._note_pack_drop(
                        e, val=True),
                    metrics=self.metrics,
                    should_stop=lambda: self._stopped).start()
            # fused multi-step loop (COS_STEPS_PER_LOOP=K>1): K packed
            # batches stack into one (K, batch…) block and one XLA
            # dispatch runs K solver iterations (LR schedule, iter
            # counter and rng advance on-device).  chunk_schedule keeps
            # every chunk inside the boundaries this loop ACTS on —
            # the interleaved-validation interval and the snapshot
            # cadence (single-step remainders otherwise), so both keep
            # their exact iterations; an interval with no action here
            # (display — this loop never logs it; test_interval with
            # validation off) must NOT throttle fusion.  K=1 is the
            # legacy per-step path.
            k_loop = steps_per_loop()
            fused_step = (ps.train_step_many(k_loop)
                          if k_loop > 1 else None)
            will_validate = (self.interleave_validation and test_interval
                             and eval_step is not None and test_iter)
            feed = chunked_feed(
                combine_batches(batches, max(1, sp.iter_size), tmajor),
                start_iter=it, max_iter=max_iter, k=k_loop,
                boundaries=(test_interval if will_validate else 0,
                            snap),
                metrics=self.metrics)
            gen = device_prefetch(
                feed, depth=stage_depth(),
                sharding=ps.input_shardings(),
                chunked=True,
                chunk_sharding=(ps.chunk_input_shardings()
                                if k_loop > 1 else None),
                device_transforms=dxf,
                background=nthreads > 0 and stage_background(),
                metrics=self.metrics)
            params, st = self.params, self.opt_state
            m = self.metrics
            experts = None      # the expert layers' window, if any
            for nd in itertools.count():        # dispatches of this run
                inj.step_delay()
                inj.maybe_die(it)
                try:
                    with m.span("queue_wait", n=nd):
                        n, batch = next(gen)
                except StopIteration:
                    break
                m.gauge("feed_depth", len(self.queues[0]))
                with m.step_span(it, n) as dispatch:
                    if n == 1:
                        params, st, out = step(params, st, batch,
                                               solver.step_rng(it))
                    else:
                        params, st, out = fused_step(params, st, batch)
                if nd == 0:
                    self._note_lowering_plans()
                    experts = self._expert_window(out)
                if experts is not None:
                    experts.add(out)
                if self.step_observer is not None:
                    self.step_observer(it, n, batch, params, st, out)
                it += n
                self._it_now = it
                inj.slow_sleep(dispatch.seconds)
                # interleaved validation: rank-0 records, all ranks step
                if self.interleave_validation and test_interval \
                        and it % test_interval == 0 \
                        and eval_step is not None and test_iter:
                    with m.span("validation", it=it):
                        self._run_validation(eval_step, params,
                                             test_iter)
                if snap and it % snap == 0:
                    # the multi-host tp/ep param gather is a COLLECTIVE
                    # — every rank runs it at this lockstep boundary
                    # (no-op otherwise); non-rank0 then participates
                    # only to write its ZeRO state-shard sidecar
                    export_p = checkpoint.gather_params_if_sharded(
                        params)
                    if self.rank == 0 \
                            or checkpoint.state_is_sharded(st):
                        self.params, self.opt_state = params, st
                        with m.span("snapshot", it=it):
                            self._snapshot(export_params=export_p)
                if it >= max_iter:
                    break
            self.params, self.opt_state = params, st
            if sp.snapshot_after_train:
                export_p = checkpoint.gather_params_if_sharded(params)
                if self.rank == 0 \
                        or checkpoint.state_is_sharded(st):
                    with m.span("snapshot", it=it):
                        self._snapshot(final=True, export_params=export_p)
        except BaseException as e:     # surfaced on stop()/join()
            from .obs.recorder import maybe_dump, record
            record("trainer", "fatal",
                   error=f"{type(e).__name__}: {e}")
            maybe_dump("fatal_exception")
            self._error = e
        finally:
            # tear the pipeline down in dependency order: close the
            # stager generator first (its finally unblocks a stager
            # thread stuck on a full handoff queue), then flag the
            # pools down, then unblock feeders spinning in offer()
            # (backpressure release)
            if gen is not None:
                try:
                    gen.close()
                except Exception:       # noqa: BLE001
                    pass
            for pool in (self._train_pool, self._val_pool):
                if pool is not None:
                    pool.stop(join_timeout=2.0)
            for q in self.queues:
                q.stop()

    def _note_lowering_plans(self):
        """The first step is lowered: what its operators were lowered to
        goes into the metrics (`info.<kind>`) and into the log, once.
        Static facts, nothing a step on the device; each kind's are
        documented where they are written (`ops.route.lowered`'s
        callers)."""
        from .ops import route
        for kind, plans in sorted(route.plans().items()):
            self.metrics.set_info(kind, plans)
            _LOG.info("%s as lowered: %s", kind, plans)

    def _expert_window(self, out) -> Optional[ExpertWindow]:
        """The window over the dropless expert layers whose stats the
        step returns, as the summary's `experts`; None without one."""
        from .ops.layers import moe_plans
        plan_of = {layer: plan for plan in moe_plans().values()
                   for layer in plan["layers"]}
        layers = {lp.top[1]: (lp.name, plan_of[lp.name])
                  for lp in self.solver.train_net.layers
                  if lp.name in plan_of and len(lp.top) > 1
                  and getattr(out.get(lp.top[1]), "is_fully_addressable",
                              False)}
        if not layers:
            return None
        window = ExpertWindow(layers)
        self.metrics.set_section("experts", window.record)
        return window

    VALIDATION_STALL_TIMEOUT = 30.0

    def _run_validation(self, eval_step, params, test_iter: int):
        assert self.val_source is not None
        src = self.val_source
        if self._val_pool is not None:
            self._run_validation_pooled(eval_step, params, test_iter)
            return
        buf: List = []
        done = 0
        while done < test_iter and not self._stopped:
            try:
                item = self.queues[1].take(
                    timeout=self.VALIDATION_STALL_TIMEOUT)
            except queue.Empty:
                if self._stopped or self.queues[1].stopped:
                    break          # ordinary shutdown mid-validation
                # a stalled validation feeder must not silently shrink
                # the round (round-1 VERDICT weak spot 5): fail loudly —
                # the solver thread surfaces this on stop()/join()
                raise RuntimeError(
                    f"validation feed stalled: {done}/{test_iter} "
                    "batches after 30s — feeder dead or test source "
                    "exhausted (check test_iter x batch_size vs "
                    "dataset size)")
            if item is STOP_MARK or item is None:
                continue
            buf.append(item)
            if len(buf) == src.batch_size:
                batch = self._pack_or_drop(src, buf, val=True)
                if batch is not None:
                    batch = src.apply_device_stage(
                        batch, self._val_shardings)
                    out = eval_step(params, batch)
                    self.validation.add_batch(out)
                buf = []
                done += 1
        self.validation.finish_round()

    def _run_validation_pooled(self, eval_step, params,
                               test_iter: int):
        """Validation round over the queue-1 transformer pool: batches
        arrive packed (and in feed order), the solver thread only runs
        eval steps.  A DROPPED slot still advances the round counter —
        the old inline loop's semantics (the feeder already spent the
        records)."""
        src = self.val_source
        done = 0
        while done < test_iter and not self._stopped:
            try:
                batch = self._val_pool.take(
                    timeout=self.VALIDATION_STALL_TIMEOUT,
                    skip_dropped=False)
            except queue.Empty:
                if self._stopped or self.queues[1].stopped:
                    break          # ordinary shutdown mid-validation
                raise RuntimeError(
                    f"validation feed stalled: {done}/{test_iter} "
                    "batches after "
                    f"{self.VALIDATION_STALL_TIMEOUT:.0f}s — feeder "
                    "dead or test source exhausted (check test_iter x "
                    "batch_size vs dataset size)")
            if batch is None:
                break              # pool terminal (stop/exhausted)
            if batch is not DROPPED:
                batch = src.apply_device_stage(
                    batch, self._val_shardings)
                out = eval_step(params, batch)
                self.validation.add_batch(out)
            done += 1
        self.validation.finish_round()

    def _snapshot(self, final: bool = False, export_params=None):
        conf = self.conf
        from .utils import fsutils
        prefix = fsutils.join(conf.outputPath or ".",
                              conf.solverParameter.snapshot_prefix
                              or "model")
        fmt = conf.solverParameter.snapshot_format
        write_main = self.rank == 0
        params = (export_params if export_params is not None
                  else self.params)
        if getattr(conf, "asyncSnapshot", False):
            if self._snapshotter is None:
                self._snapshotter = checkpoint.AsyncSnapshotter()
            self._snapshotter.submit(
                self.solver.train_net, params, self.opt_state,
                prefix, fmt=fmt, solver_type=self.solver.solver_type,
                write_main=write_main)
            if final:
                self._snapshotter.wait()
        else:
            checkpoint.snapshot(
                self.solver.train_net, params, self.opt_state,
                prefix, fmt=fmt, solver_type=self.solver.solver_type,
                write_main=write_main)
        if final and conf.modelPath and self.rank == 0:
            checkpoint.save_caffemodel(conf.modelPath,
                                       self.solver.train_net,
                                       params)

    # -- feature extraction (doFeatures, :473-523) ------------------------
    def extract_features(self, source: DataSource,
                         blob_names: Sequence[str]
                         ) -> List[Dict[str, Any]]:
        return self.extract_rows(source.records(), blob_names,
                                 source=source)

    def default_feature_blobs(self) -> List[str]:
        net = self.solver.test_net or self.solver.train_net
        names = list(net.output_blobs)
        label = getattr(self.conf, "label", "")
        if label and label not in names:
            names.append(label)     # -label column rides along
        return names

    def feature_source(self) -> Optional[DataSource]:
        """Record decoder for feature extraction, ALWAYS test-phase:
        the val source when the net has a TEST data layer, else one
        built in phase_train=False from whatever data layer exists —
        never train_source, whose transformer applies random
        crop/mirror and would make predictions nondeterministic."""
        src = self.val_source or getattr(self, "_feature_src", None)
        if src is None:
            lp = (self.conf.test_data_layer()
                  or self.conf.train_data_layer())
            if lp is not None:
                from .data.source import get_source
                src = get_source(lp, phase_train=False,
                                 **self._source_kw)
                self._feature_src = src
        return src

    def _feature_fwd(self, blob_names: Tuple[str, ...]):
        """Jitted predict(blobNames) closure, cached per blob set — the
        daemon's chunked EXTRACT requests must not retrace per chunk.
        The builder lives in serving/forward.py (shared with the online
        serving subsystem, which needs it without a training run).
        With an explicit -mesh the extract forward runs under the SAME
        MeshLayout the training step uses (mesh-parallel forward: tp/ep
        params stay sharded, batch over dp); the implicit all-dp
        default keeps the single-program path — one program on ONE
        device (_extract_params) — so extract output stays
        byte-identical to the pre-mesh behavior."""
        from .serving.forward import BlobForward
        net = self.solver.test_net or self.solver.train_net
        layout = self._extract_layout()
        fwd = getattr(self, "_blob_forward", None)
        if fwd is None or fwd.net is not net or fwd.layout is not layout:
            fwd = self._blob_forward = BlobForward(net, layout=layout)
        return fwd(blob_names)

    def _extract_layout(self):
        return (self.psolver.layout
                if (getattr(self.conf, "mesh", "")
                    and self.psolver.mesh.devices.size > 1)
                else None)

    def _extract_params(self):
        """Params for the extract forward.  Without an explicit -mesh
        the forward is one un-partitioned program, but the trainer
        leaves the params replicated over every device of the default
        all-dp mesh — and a program spanning several devices would have
        to partition the Pallas LRN kernels, which Mosaic refuses
        outside shard_map (the four-chip -features run, PR 21).  So the
        single-program path reads the params from the mesh's first
        local device and runs there, as serving without -serveMesh does."""
        if self._extract_layout() is not None \
                or self.psolver.mesh.devices.size == 1:
            return self.params
        held = getattr(self, "_extract_held", None)
        if held is None or held[0] is not self.params:
            import jax
            held = self._extract_held = (self.params, jax.device_put(
                self.params, self.psolver.mesh.local_devices[0]))
        return held[1]

    def extract_rows(self, records, blob_names: Sequence[str],
                     source: Optional[DataSource] = None
                     ) -> List[Dict[str, Any]]:
        """features()/predict core over an arbitrary record stream —
        the Spark path hands partition records in over the feed daemon
        (OP_EXTRACT) while the local path streams source.records()."""
        self._init_params()
        source = source or self.feature_source()
        assert source is not None, "no data layer to decode records with"
        fwd = self._feature_fwd(tuple(blob_names))
        params = self._extract_params()
        feat_shardings = None
        if getattr(source, "_device_transform", False) \
                and params is self.params:
            feat_shardings = self.psolver.input_shardings(
                self.solver.test_net or self.solver.train_net)
        rows: List[Dict[str, Any]] = []
        buf: List = []
        ids: List[str] = []

        from .serving.forward import fetch_rows

        def flush(real: int):
            """Run one batch and emit `real` rows (row extraction
            shared with serving via fetch_rows — one device_get per
            blob, not per row)."""
            nonlocal buf, ids
            bs = len(buf)
            # a split-enabled source (train-then-features on the same
            # processor) emits uint8+aux: finish the transform here,
            # placed on the mesh so mesh-sharded params and the input
            # agree on devices
            out = fwd(params,
                      source.apply_device_stage(source.next_batch(buf),
                                                feat_shardings))
            rows.extend(fetch_rows(out, blob_names, ids, real, bs))
            buf, ids = [], []

        for rec in records:
            buf.append(rec)
            ids.append(str(rec[0]) if isinstance(rec, tuple)
                       else str(rec.get("id", len(ids))))
            if len(buf) == source.batch_size:
                flush(real=len(buf))
        if buf:
            # ragged tail: pad to full batch (static shapes), trim rows
            real = len(buf)
            pad = source.batch_size - real
            buf += [buf[-1]] * pad
            ids += [ids[-1]] * pad
            flush(real=real)
        return rows
