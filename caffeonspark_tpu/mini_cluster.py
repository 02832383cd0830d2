"""Standalone cluster trainer — no Spark, pure CLI.

TPU-native analog of `caffe-distri/.../tools/caffe_mini_cluster.cpp`
(:31-293) + `util/mini_cluster.cpp`: the reference's bring-up harness
that runs distributed `caffe train` with `-cluster N -server host` rank
assignment over raw TCP.  Here the rank/address machinery is
`jax.distributed.initialize` and the sync is the SPMD mesh; the CLI
surface mirrors the reference's flags:

    python -m caffeonspark_tpu.mini_cluster \
        -solver lenet_memory_solver.prototxt \
        [-train /path/override_source] [-net net.prototxt] \
        [-weights model.caffemodel] [-snapshot state.solverstate] \
        [-iterations N] [-devices dp[,tp[,sp[,ep]]]] \
        [-server host:port -cluster N -rank I]   # multi-host

Signal actions match the reference (`caffe_mini_cluster.cpp:55-60`):
SIGINT → "stop" (snapshot + exit), SIGHUP → "snapshot" (snapshot +
continue).
"""

from __future__ import annotations

import argparse
import itertools
import os
import signal
import sys
import time


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mini_cluster",
        description="standalone (non-Spark) distributed trainer")
    p.add_argument("-solver", "-conf", dest="solver", required=True,
                   help="solver prototxt")
    p.add_argument("-net", dest="net", default=None,
                   help="net prototxt (overrides solver's `net:` path)")
    p.add_argument("-train", dest="train", default=None,
                   help="override train data source path")
    p.add_argument("-test", dest="test", default=None,
                   help="override test data source path")
    p.add_argument("-weights", dest="weights", default=None,
                   help=".caffemodel[.h5] to finetune from")
    p.add_argument("-snapshot", dest="snapshot", default=None,
                   help=".solverstate[.h5] to resume from")
    p.add_argument("-iterations", dest="iterations", type=int,
                   default=None, help="override max_iter")
    p.add_argument("-devices", dest="devices", default=None,
                   help="device count N (N-way data-parallel, the "
                   "reference's GPUs-per-node semantics) or mesh spec "
                   "dp[,tp[,sp[,ep]]] (default: all devices dp)")
    p.add_argument("-mesh", dest="mesh", default=None,
                   help="mesh spec dp[,tp[,sp[,ep]]] (same as the "
                   "driver CLI's -mesh; wins over -devices)")
    p.add_argument("-model", dest="model", default=None,
                   help="final model output path")
    p.add_argument("-output", dest="output", default=".",
                   help="snapshot output dir")
    # multi-host (the -server/-cluster flags of the reference tool)
    p.add_argument("-server", dest="server", default=None,
                   help="coordinator host:port for multi-host")
    p.add_argument("-cluster", dest="cluster", type=int, default=None,
                   help="number of processes")
    p.add_argument("-rank", dest="rank", type=int, default=None,
                   help="this process's rank")
    p.add_argument("-display_every", type=int, default=None,
                   help="override solver display interval")
    p.add_argument("-profile", dest="profile", default=None,
                   help="write a jax.profiler trace to this directory")
    p.add_argument("-metrics", dest="metrics", default=None,
                   help="append per-display-step JSONL records "
                   "(iter, loss, lr, steps/s, records/s) to this file")
    p.add_argument("-pipeline_metrics", dest="pipeline_metrics",
                   default=None,
                   help="write the per-stage ingest timeline "
                   "(queue-wait / pack / stage / step, queue depths) "
                   "as JSON to this file at exit")
    p.add_argument("-dtype", dest="dtype", default="float32",
                   choices=["float32", "bfloat16", "mixed"],
                   help="float32 | bfloat16 (params+compute bf16) | "
                   "mixed (f32 master weights, bf16 compute)")
    return p


def local_ranks(args) -> int:
    """How many of the job's ranks run on this host, as far as a rank
    can tell from its own arguments: all `-cluster` of them when the
    rendezvous is on the loopback (the supervisor's single-host mode)
    or there is none (elastic ranks on a shared output), else 1 —
    a pod's `-local_ranks` never reaches the rank."""
    n = args.cluster or 1
    host = (args.server or "").rsplit("/", 1)[-1].rsplit(":", 1)[0]
    return n if host in ("", "localhost", "127.0.0.1", "[::1]") else 1


class MiniCluster:
    def __init__(self, args):
        from .parallel import ParallelSolver, build_mesh, distributed_init
        from .proto import read_net, read_solver
        from .solver import Solver
        from .utils.compile_cache import enable_compile_cache

        enable_compile_cache()   # resumes/retrains skip the compile

        # sync-mode policy (COS_SYNC_MODE, parallel/syncmode.py):
        # lockstep joins the global jax.distributed mesh as always;
        # the relaxed modes (local_sgd/async) deliberately DO NOT —
        # each rank trains on its own local devices and exchanges
        # parameters host-side through the shared-filesystem store,
        # which is what makes the fleet elastic (no collective to hang
        # when a rank dies, no rendezvous to block a rejoiner)
        from .parallel.syncmode import resolve_policy
        self.sync_policy = resolve_policy()
        self.elastic = (self.sync_policy.elastic
                        and (args.cluster or 1) > 1)
        if not self.elastic:
            distributed_init(args.server, args.cluster, args.rank)

        from .config import resolve_net_path
        self.sp = read_solver(args.solver)
        self.net_param = read_net(
            resolve_net_path(args.solver, args.net or self.sp.net))
        if args.train or args.test:
            for lyr in self.net_param.layer:
                if lyr.type not in ("MemoryData", "CoSData"):
                    continue
                is_test = any(r.phase == 1 for r in lyr.include)
                override = args.test if is_test else args.train
                if override:
                    if lyr.has("memory_data_param"):
                        lyr.memory_data_param.source = override
                    else:
                        lyr.cos_data_param.source = override
        if args.iterations is not None:
            self.sp.max_iter = args.iterations
        if args.display_every is not None:
            self.sp.display = args.display_every

        import jax.numpy as jnp
        dtype = (jnp.bfloat16 if args.dtype == "bfloat16"
                 else jnp.float32)
        compute = jnp.bfloat16 if args.dtype == "mixed" else None
        spec = getattr(args, "mesh", None) or args.devices
        if spec:
            from .processor import _parse_mesh_spec
            spec = str(spec)
            kw = _parse_mesh_spec(spec)
            devices = None
            if "," not in spec:
                # bare count N: use N local devices data-parallel (the
                # reference's GPUs-per-node -devices semantics)
                import jax
                devices = jax.devices()[:kw["dp"]]
            mesh = build_mesh(devices=devices, **kw)
        else:
            mesh = build_mesh()
        self.mesh = mesh
        # the solver's rng rank follows the mesh's DP coordinate, not
        # the process rank: tp/sp ranks share replicated activations,
        # so their dropout masks / augmentation streams must be
        # identical, while dp ranks decorrelate (CaffeNet.cpp:614-618
        # seed = seed + device semantics, mesh-aware).  Elastic modes
        # have no global mesh — the process rank IS the dp coordinate
        # there, so augment/dropout streams decorrelate across ranks.
        from .parallel import dp_data_rank
        rng_rank = (args.rank or 0) if self.elastic \
            else dp_data_rank(mesh)[0]
        self.solver = Solver(self.sp, self.net_param,
                             rank=rng_rank, dtype=dtype,
                             compute_dtype=compute)
        self.psolver = ParallelSolver(self.solver, mesh)
        self.args = args
        self._is_rank0 = (args.rank or 0) == 0
        self.prefix = os.path.join(
            args.output, self.sp.snapshot_prefix or "model")
        self._stop = False
        self._want_snapshot = False

    # ------------------------------------------------------------------
    def _install_signals(self):
        from .obs.recorder import maybe_dump, record

        def on_int(sig, frame):
            # an operator Ctrl-C mid-drill must not lose the ring:
            # the recorder dumps on SIGINT exactly like SIGTERM, then
            # the normal drain (snapshot + exit) runs
            print("\nSIGINT → stop (snapshot + exit)", file=sys.stderr)
            record("trainer", "signal", signal="SIGINT")
            maybe_dump("sigint")
            self._stop = True

        def on_hup(sig, frame):
            print("SIGHUP → snapshot", file=sys.stderr)
            record("trainer", "signal", signal="SIGHUP")
            self._want_snapshot = True

        def on_term(sig, frame):
            # supervisor teardown sends SIGTERM first (drain window
            # before SIGKILL): exit the step loop cleanly so atexit
            # drains any in-flight async snapshot upload.  The flight
            # recorder dumps HERE — if the grace window closes and
            # SIGKILL lands, the timeline is already on disk.
            print("SIGTERM → teardown (drain snapshots + exit)",
                  file=sys.stderr)
            record("trainer", "signal", signal="SIGTERM")
            maybe_dump("sigterm")
            self._stop = True

        signal.signal(signal.SIGINT, on_int)
        signal.signal(signal.SIGTERM, on_term)
        if hasattr(signal, "SIGHUP"):
            signal.signal(signal.SIGHUP, on_hup)

    # ------------------------------------------------------------------
    def train(self) -> str:
        import jax
        import jax.numpy as jnp
        from . import checkpoint
        from .data import get_source
        from .data.queue_runner import device_prefetch

        solver, ps = self.solver, self.psolver
        params, st = ps.init()
        if self.args.snapshot:
            params = {ln: dict(bl) for ln, bl in params.items()}
            params, st = checkpoint.restore(
                solver.train_net, params, st, self.args.snapshot,
                weights_path=self.args.weights)
            params = ps.shard_params(params)
            st = ps.shard_opt_state(st)
            print(f"resumed from iter {int(jax.device_get(st.iter))}")
        elif self.args.weights:
            params = checkpoint.copy_layers(solver.train_net, params,
                                            self.args.weights)
            params = ps.shard_params(params)
            print(f"finetuning from {self.args.weights}")

        # unified chaos layer (tools/chaos.py): every COS_FAULT_* knob
        # resolves here, once, host-side; the active plan rides in the
        # metrics artifact as info.faults so drills self-describe
        from .tools.chaos import make_injector
        inj = make_injector(self.args.rank or 0)
        # elastic sync modes (COS_SYNC_MODE=local_sgd|async): the
        # host-side exchange object over the shared store.  A
        # (re)joining rank adopts the newest AVERAGED state — it wins
        # over -snapshot (which may be a full round older): this is
        # how a relaunched rank re-admits at the next round instead of
        # rewinding the fleet
        from .parallel.syncmode import make_sync
        sync = make_sync(self.sync_policy, self.args.output,
                         self.args.rank or 0, chaos=inj) \
            if self.elastic else None
        if sync is not None:
            g = sync.adopt_latest(int(jax.device_get(st.iter)))
            if g is not None:
                params = ps.place_host_params(g["params"], params)
                st = ps.set_iter(st, g["iter"])
                print(f"rejoined pack at iter {g['iter']} from "
                      f"averaged state v{g['version']}", flush=True)

        data_layers = solver.train_net.data_layers
        if not data_layers:
            raise ValueError("train net has no data layer")
        # data sharding follows the mesh's dp axis, not the process
        # rank: on a tp/sp-only multi-host mesh every process feeds
        # the SAME records (parallel.mesh.dp_data_rank) — process-rank
        # sharding would hand each model shard different data.
        # Elastic modes have no global mesh: the process rank shards
        # the data (a permanently-departed rank's slice is simply not
        # revisited this run — the epoch-level cost of elasticity).
        from .parallel import dp_data_rank
        if self.elastic:
            data_rank, data_ranks = (self.args.rank or 0,
                                     self.args.cluster or 1)
        else:
            data_rank, data_ranks = dp_data_rank(self.mesh)
        src = get_source(data_layers[0], phase_train=True,
                         rank=data_rank, num_ranks=data_ranks,
                         seed=int(self.sp.random_seed)
                         if self.sp.random_seed >= 0 else 0)
        step = ps.train_step()
        self._install_signals()

        from .utils import StepTimer, profile_trace
        max_iter = self.sp.max_iter
        display = self.sp.display or 0
        snap_every = self.sp.snapshot or 0
        # interleaved validation on the pod path (the driver CLI's
        # trainWithValidation semantics, here for supervisor-launched
        # standalone clusters): every test_interval steps run test_iter
        # eval batches on the SAME replicated validation stream on
        # every rank (the eval step is a collective on meshes), rank 0
        # records the per-round output means
        test_interval = int(self.sp.test_interval or 0)
        test_iter = int(self.sp.test_iter[0]) if self.sp.test_iter else 0
        interleave = bool(test_interval and test_iter
                          and solver.test_net is not None
                          and solver.test_net.data_layers)
        if interleave:
            from .data.transformer import DEVICE_AUX_SUFFIX
            from .processor import ValidationReport
            eval_step = ps.eval_step()
            val_names = list(solver.test_net.output_blobs)
            val_report = ValidationReport(val_names)
            val_src = get_source(
                solver.test_net.data_layers[0], phase_train=False,
                rank=0, num_ranks=1,   # replicated validation data
                seed=int(self.sp.random_seed)
                if self.sp.random_seed >= 0 else 0)
            # uint8-infeed split for the validation feed too (the
            # driver CLI's processor does the same)
            val_src.enable_device_transform(solver.test_net.dtype)
            val_gen = val_src.batches(loop=True, shuffle=False)
            vsh = ps.input_shardings(solver.test_net)
            val_multiproc = jax.process_count() > 1

            def _vsh_for(k):
                if k.endswith(DEVICE_AUX_SUFFIX):
                    return vsh[k[:-len(DEVICE_AUX_SUFFIX)]]
                return vsh[k]

            def _stage_val(b):
                # multi-process: numpy can't carry a non-trivial
                # sharding — build the global array from each
                # process's IDENTICAL local batch.  global_shape MUST
                # be the local shape: without it jax scales every
                # process-spanning sharded dim (concatenating the
                # duplicate copies — and on sp meshes corrupting the
                # TIME axis); with it the local data IS the full
                # replicated-batch value
                if not val_multiproc:
                    return b
                return {k: jax.make_array_from_process_local_data(
                            _vsh_for(k), v, global_shape=v.shape)
                        for k, v in b.items()}
        it = int(jax.device_get(st.iter))
        from .data.queue_runner import (PipelinedFeed, chunked_feed,
                                        combine_batches,
                                        stage_background, stage_depth,
                                        steps_per_loop,
                                        transform_threads)
        from .metrics import CompileWatch, PipelineMetrics, span_of
        tmajor = frozenset(
            n for n, _, kind in solver.train_net.input_specs
            if kind.endswith(":T"))
        dxf = src.enable_device_transform(solver.train_net.dtype)
        # pipelined ingest: reader thread -> transformer pool packs off
        # the step loop; COS_TRANSFORM_THREADS=0 restores the inline
        # generator path
        pmetrics = PipelineMetrics()
        src.metrics = pmetrics      # next_batch reports its halves
        # a compile after this run's first step is a recompile: it goes
        # to the flight recorder with the iteration (None until then)
        first_it = it
        compile_watch = CompileWatch(
            pmetrics, lambda: None if it == first_it else it)
        # observability (caffeonspark_tpu/obs): COS_METRICS_FLUSH_S
        # background-flushes the summary to <output>/metrics.json via
        # the atomic-write path (a SIGKILLed run keeps telemetry no
        # older than one interval), and COS_METRICS_PORT exposes the
        # live summary + prom exposition + /v1/profile over HTTP
        from .metrics import maybe_start_flusher
        from .obs.http import maybe_start_obs_server
        flusher = maybe_start_flusher(pmetrics, self.args.output) \
            if self._is_rank0 else None
        obs_server = maybe_start_obs_server(pmetrics.summary,
                                            role="trainer") \
            if self._is_rank0 else None
        nthreads = transform_threads()
        feed = None
        if nthreads > 0:
            feed = PipelinedFeed(src, loop=True, num_threads=nthreads,
                                 metrics=pmetrics,
                                 should_stop=lambda: self._stop,
                                 local_procs=local_ranks(self.args))
            raw_batches = iter(feed)
        else:
            def _timed_batches():
                # inline path: record read + decode + transform all
                # happen right here, serial with the step loop
                it_ = src.batches(loop=True)
                for nb in itertools.count():
                    try:
                        with pmetrics.span("pack", n=nb):
                            b = next(it_)
                    except StopIteration:
                        return
                    yield b

            raw_batches = _timed_batches()
        batches_it = combine_batches(raw_batches,
                                     max(1, self.sp.iter_size), tmajor)
        if solver.train_net.dtype != jnp.float32:
            import ml_dtypes
            import numpy as np
            np_dtype = ml_dtypes.bfloat16
            # only pixel/feature tops narrow: label and id tops keep
            # f32 — bf16's 8 significant bits would round class ids
            # above 256 to other ids (LayerOp.index_bottoms)
            narrow = {n for n, _, kind in solver.train_net.input_specs
                      if kind.startswith("data")}

            def _cast(bs):
                # uint8 pixels / int32 aux of the device-transform split
                # keep their wire dtype; the device stage emits bf16
                for b in bs:
                    yield {k: v.astype(np_dtype)
                           if k in narrow and v.dtype not in (np.uint8,
                                                              np.int32)
                           else v for k, v in b.items()}

            batches_it = _cast(batches_it)
        # fused multi-step loop (COS_STEPS_PER_LOOP=K>1): stack K
        # batches per dispatch and scan K solver steps on-device;
        # chunk_schedule falls back to single-step chunks around the
        # boundaries this loop ACTS on (display log, interleaved
        # validation, snapshot, max_iter) so every host-side action
        # keeps its exact iteration — a test_interval with validation
        # off has no action and must not throttle fusion.  Pick K to
        # divide the display interval or the display cadence caps the
        # effective chunk size.
        k_loop = steps_per_loop()
        fused_step = ps.train_step_many(k_loop) if k_loop > 1 else None
        # sync-mode exchanges are loop boundaries too: a fused chunk
        # must never cross an averaging round / staleness sync point
        # (local_sgd with COS_STEPS_PER_LOOP=K IS "K local steps in
        # one dispatch, then one exchange")
        sync_boundary = (self.sync_policy.boundary
                         if sync is not None else 0)
        batches_it = chunked_feed(
            batches_it, start_iter=it, max_iter=max_iter, k=k_loop,
            boundaries=(display, test_interval if interleave else 0,
                        snap_every, sync_boundary),
            metrics=pmetrics)
        gen = device_prefetch(batches_it, depth=stage_depth(),
                              sharding=ps.input_shardings(),
                              chunked=True,
                              chunk_sharding=(ps.chunk_input_shardings()
                                              if k_loop > 1 else None),
                              device_transforms=dxf,
                              background=nthreads > 0
                              and stage_background(),
                              metrics=pmetrics)
        # each step consumes exactly one source batch (device_prefetch
        # shards it across dp; it does not multiply the record count)
        timer = StepTimer(batch_size=src.batch_size)
        timer.start()
        smoothed = None
        # fault injection for drills and benches is fully resolved in
        # `inj` (tools/chaos.py): step delay widens kill windows,
        # die-once kills a rank at an iter exactly once, slow-rank is
        # the straggler injector, and the comm floor sleeps the
        # gradsync plan's modeled EXPOSED wire time per step (same
        # technique as bench_steploop's 45 ms dispatch floor: on a
        # CPU-only box the floor IS the controlled variable).  The
        # resolved plan is published so every artifact states what was
        # injected.
        pmetrics.set_info("faults", inj.plan.describe())
        pmetrics.set_info("sync", self.sync_policy.describe())
        pmetrics.set_info("autotune", solver.train_net.autotune_info())
        gs = getattr(solver, "grad_sync", None)
        comm_sleep = 0.0
        if gs is not None:
            pmetrics.set_info("comm", gs.plan.comm_info())
            comm_sleep = inj.plan.comm.sleep_seconds(gs.plan)

        # host-side param exchange callbacks for the sync modes (the
        # rebinding closure: an adopted/averaged state replaces the
        # live pytree between dispatches)
        def _sync_get():
            return ps.host_params(params)

        def _sync_put(flat):
            nonlocal params
            params = ps.place_host_params(flat, params)

        if sync is not None:
            sync.on_start(it)
        # two clocks: `it` is the PACK clock (LR schedule, sync
        # boundaries, logging — a re-admission jump moves it), while
        # `sched_it` advances exactly with consumed chunks and drives
        # the display/validation/snapshot conditions — chunked_feed
        # ends chunks on ITS counter's boundaries, so the conditions
        # must use the same arithmetic or a jump would silently
        # disable every boundary action for the rest of the run.
        # Lockstep never jumps: the clocks are identical there and the
        # conditions compute exactly what they always did.  Jumps are
        # multiples of the sync boundary k, so `it` and `sched_it`
        # stay congruent mod k and exchange boundaries keep firing.
        sched_it = it
        compile_watch.start()
        try:
            with profile_trace(self.args.profile):
                for nd in itertools.count():    # dispatches of this run
                    if it >= max_iter or self._stop:
                        break
                    inj.step_delay()
                    inj.maybe_die(it)
                    with pmetrics.span("queue_wait", n=nd):
                        n, batch = next(gen)
                    with pmetrics.step_span(it, n) as dispatch:
                        if n == 1:
                            params, st, out = step(params, st, batch,
                                                   solver.step_rng(it))
                        else:
                            params, st, out = fused_step(params, st,
                                                         batch)
                    it += n
                    sched_it += n
                    # straggler injector: this rank runs factor× slower
                    inj.slow_sleep(dispatch.seconds)
                    if comm_sleep:
                        # one exchange per solver step, fused or not;
                        # n per-step samples so the series stays
                        # per-step comparable across K settings
                        time.sleep(comm_sleep * n)
                        for _ in range(n):
                            pmetrics.add("comm", comm_sleep)
                    if sync is not None:
                        # a sample only at an exchange boundary (a
                        # re-admission jump happens on one too): the
                        # heartbeat-only calls in between are not one
                        at_exchange = (sync_boundary
                                       and it % sync_boundary == 0)
                        with span_of(pmetrics if at_exchange else None,
                                     "sync_exchange", it=it):
                            new_it = sync.maybe_exchange(it, _sync_get,
                                                         _sync_put)
                        if new_it != it:
                            # re-admission: the exchange fast-forwarded
                            # us to the pack's clock — the LR schedule
                            # follows via the opt-state counter
                            print(f"sync: re-admitted at iter {new_it}"
                                  f" (was {it})", flush=True)
                            from .obs.recorder import record
                            record("trainer", "sync_readmitted",
                                   iter_from=it, iter_to=new_it)
                            it = new_it
                            st = ps.set_iter(st, it)
                    timer.tick(n)
                    # boundary actions fire on the SCHEDULE clock (see
                    # the sched_it note above) — identical to `it` in
                    # lockstep, chunk-aligned after an elastic jump
                    if display and sched_it % display == 0:
                        # fused chunks stack outputs (K, …); the chunk
                        # schedule ends chunks ON display boundaries,
                        # so the last slice is this iteration's value
                        loss = float(jax.device_get(
                            out["loss"] if n == 1 else out["loss"][-1]))
                        lr_now = float(jax.device_get(
                            out["lr"] if n == 1 else out["lr"][-1]))
                        smoothed = loss if smoothed is None else (
                            0.9 * smoothed + 0.1 * loss)
                        print(
                            f"iter {it}/{max_iter} loss={loss:.4f} "
                            f"(smoothed {smoothed:.4f}) "
                            f"lr={lr_now:.6f} "
                            f"[{timer.steps_per_sec:.1f} it/s, "
                            f"{timer.records_per_sec:.0f} img/s]")
                        if self.args.metrics and self._is_rank0:
                            import json
                            with open(self.args.metrics, "a") as mf:
                                mf.write(json.dumps(
                                    {"iter": it, "loss": round(loss, 6),
                                     "smoothed": round(smoothed, 6),
                                     "lr": lr_now,
                                     "steps_per_sec": round(
                                         timer.steps_per_sec, 2),
                                     "records_per_sec": round(
                                         timer.records_per_sec, 1),
                                     "ts": time.time()}) + "\n")
                    if interleave and sched_it % test_interval == 0:
                        with pmetrics.span("validation", it=it):
                            for _ in range(test_iter):
                                vb = val_src.apply_device_stage(
                                    _stage_val(next(val_gen)),
                                    None if val_multiproc else vsh)
                                vout = eval_step(params, vb)
                                # pre-reduce each output to a REPLICATED
                                # scalar (jnp.mean all-reduces a dp-sharded
                                # blob): a per-example top spanning other
                                # hosts' devices cannot be device_get
                                # directly
                                val_report.add_batch(
                                    {n: jnp.mean(vout[n])
                                     for n in val_names})
                            val_report.finish_round()
                        if self._is_rank0:
                            row = val_report.rounds[-1]
                            print("validation iter %d: %s" % (
                                it, " ".join(f"{n}={v:.4f}"
                                             for n, v in row.items())),
                                flush=True)
                    if (snap_every and sched_it % snap_every == 0) \
                            or self._want_snapshot:
                        signalled = self._want_snapshot
                        self._want_snapshot = False
                        # ZeRO multi-host: every rank writes its own state
                        # shard sidecar (checkpoint.py sharded-state notes);
                        # rank 0 also writes the model + solverstate.  The
                        # snap_every path hits the same `it` on every rank
                        # (lockstep), so the sidecar set is consistent; a
                        # SIGNAL-triggered snapshot is only consistent if
                        # the operator signalled ALL ranks in the same
                        # iteration window — restore fails loudly on a
                        # partial sidecar set either way.
                        sharded = checkpoint.state_is_sharded(st)
                        if signalled and sharded:
                            print("WARNING: signal-triggered snapshot with "
                                  "sharded (ZeRO) state — deliver the "
                                  "signal to every rank promptly or the "
                                  "sidecar set will be incomplete",
                                  file=sys.stderr)
                        lockstep = bool(snap_every
                                        and sched_it % snap_every == 0)
                        if not lockstep \
                                and checkpoint.params_partitioned(params):
                            # signal-only snapshot with cross-host tp/ep
                            # params: the dense-export gather is a
                            # COLLECTIVE — running it on just the
                            # signalled rank would deadlock the cluster.
                            # Skip; the next interval boundary snapshots
                            # in lockstep.
                            print("WARNING: signal-triggered snapshot "
                                  "skipped: params are partitioned across "
                                  "hosts and an unsynchronized gather "
                                  "would hang — wait for the next "
                                  "snapshot interval", file=sys.stderr)
                            continue
                        # multi-host tp/ep params: COLLECTIVE gather on
                        # every rank (lockstep boundary) so rank 0 can
                        # write the dense model; no-op otherwise
                        export_p = checkpoint.gather_params_if_sharded(
                            params)
                        if self._is_rank0 or sharded:
                            with pmetrics.span("snapshot", it=it):
                                m, s = checkpoint.snapshot(
                                    solver.train_net, export_p, st,
                                    self.prefix,
                                    fmt=self.sp.snapshot_format,
                                    solver_type=solver.solver_type,
                                    write_main=self._is_rank0)
                            if self._is_rank0:
                                print(f"snapshot → {m}")
                                from .obs.recorder import record
                                record("trainer", "snapshot",
                                       iter=it, path=m)
        except BaseException as e:
            # fatal training error: land the flight recorder before
            # the exception unwinds the process
            from .obs.recorder import maybe_dump, record
            record("trainer", "fatal",
                   error=f"{type(e).__name__}: {e}")
            maybe_dump("fatal_exception")
            raise
        finally:
            # stop the ingest threads whatever happens (a step failure
            # must not leak a reader/pool/stager still decoding at full
            # speed), then land the step-timeline artifact — partial
            # runs are exactly when it matters
            compile_watch.stop()
            try:
                gen.close()
            except Exception:           # noqa: BLE001
                pass
            if feed is not None:
                feed.close()
            if sync is not None:
                # mark done so peers' soft barriers stop expecting us,
                # and land the final exchange counts in the artifact
                sync.finalize(it)
                pmetrics.set_info("sync", sync.info())
            if obs_server is not None:
                obs_server.stop()
            if flusher is not None:
                # final flush so <output>/metrics.json carries the
                # complete run (including the sync/faults info blocks
                # finalized just above)
                flusher.stop()
            if self._is_rank0 and self.args.pipeline_metrics \
                    and pmetrics.has_samples():
                try:
                    pmetrics.dump(self.args.pipeline_metrics)
                    print(f"pipeline metrics → "
                          f"{self.args.pipeline_metrics}")
                except OSError as e:
                    # a bad -pipeline_metrics path must not mask the
                    # real training error propagating through here
                    print(f"WARNING: could not write pipeline "
                          f"metrics: {e}", file=sys.stderr)
        if self._is_rank0:
            print(timer.summary())
            if interleave and val_report.rounds:
                # same artifact the driver CLI writes (validation.json:
                # one row of per-output means per validation round)
                import json
                vpath = os.path.join(self.args.output,
                                     "validation.json")
                os.makedirs(self.args.output, exist_ok=True)
                with open(vpath, "w") as vf:
                    for row in val_report.rounds:
                        vf.write(json.dumps(
                            {k: round(v, 6) for k, v in row.items()})
                            + "\n")
                print(f"validation rounds → {vpath}")

        model_path = self.args.model or checkpoint.snapshot_filename(
            self.prefix, it, is_state=False,
            h5=self.sp.snapshot_format == 0)
        # every rank reaches this point AT THE SAME it after a full run
        # (max_iter is lockstep), so the multi-host tp/ep param gather
        # (collective, no-op otherwise) is safe — EXCEPT on a signal
        # stop, where ranks may exit at different iterations and an
        # unsynchronized collective would hang; export the params
        # as-is there (the dense write then fails with the actionable
        # gather-params-first error instead of deadlocking)
        export_p = (params if self._stop
                    and checkpoint.params_partitioned(params)
                    else checkpoint.gather_params_if_sharded(params))
        if self._stop and not self._is_rank0 \
                and checkpoint.state_is_sharded(st):
            # interrupted with ZeRO state: this rank's sidecar is part
            # of the resumable snapshot
            checkpoint.snapshot(solver.train_net, export_p, st,
                                self.prefix, fmt=self.sp.snapshot_format,
                                solver_type=solver.solver_type,
                                write_main=False)
        if self._is_rank0:  # main files are rank-0-only (SURVEY §5.4)
            if self._stop:
                # interrupted: write model + state so -snapshot resumes
                m, s = checkpoint.snapshot(solver.train_net, export_p,
                                           st, self.prefix,
                                           fmt=self.sp.snapshot_format,
                                           solver_type=solver.solver_type)
                print(f"stopped at iter {it}; resume with -snapshot {s}")
            if model_path.endswith(".h5"):
                from .checkpoint import _save_h5_blobs
                _save_h5_blobs(model_path, solver.train_net, export_p)
            else:
                checkpoint.save_caffemodel(model_path, solver.train_net,
                                           export_p)
            print(f"final model → {model_path}")
        self.final_params = params
        self.final_state = st
        # only rank 0 wrote the file; other ranks must not hand out a
        # path that does not exist
        return model_path if self._is_rank0 else None


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    MiniCluster(args).train()
    return 0


if __name__ == "__main__":
    sys.exit(main())
