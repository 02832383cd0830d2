"""CaffeOnSpark: the driver API facade + CLI.

Public surface parity with `caffe-grid/.../CaffeOnSpark.scala`:
  * `main` CLI dispatch (-train / -test / -features, :27-84)
  * `train(source)` (:164-231)
  * `trainWithValidation(sourceTrain, sourceValidation)` (:239-358) —
    interleaved validation with fixed-size rounds, results as a
    DataFrame of per-round output means
  * `test(source)` (:396-418) — per-blob mean vectors (VectorMean UDAF)
  * `features(source)` / `features2` (:427-506) — SampleID + blob
    columns DataFrame

Engine: runs on the local process group by default (each process = one
"executor" owning the mesh).  When pyspark is importable and a
SparkContext is passed, the same driver logic dispatches partitions to
executors via `spark_backend` (optional; this environment ships no
pyspark, so that path is import-gated)."""

from __future__ import annotations

import itertools
import json
import os
import signal
import sys
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .config import Config
from .data.source import DataSource, get_source
from .metrics import timed_records
from .processor import CaffeProcessor
from .utils import fsutils


class DataFrame:
    """Minimal columnar result set (stand-in for Spark's DataFrame in
    local mode): list-of-dict rows + schema, json/parquet writers."""

    def __init__(self, rows: List[Dict[str, Any]],
                 columns: Optional[Sequence[str]] = None):
        self.rows = rows
        self.columns = (list(columns) if columns is not None
                        else (list(rows[0].keys()) if rows else []))

    def __len__(self):
        return len(self.rows)

    def select(self, *cols) -> "DataFrame":
        return DataFrame([{c: r[c] for c in cols} for r in self.rows],
                         cols)

    def collect(self) -> List[Dict[str, Any]]:
        return self.rows

    def to_arrow(self):
        import pyarrow as pa
        return pa.table({c: [r.get(c) for r in self.rows]
                         for c in self.columns})

    def write(self, path: str, fmt: str = "json") -> None:
        if fmt == "json":
            with fsutils.open_file(path, "w") as f:
                for r in self.rows:
                    f.write(json.dumps(r) + "\n")
        elif fmt == "parquet":
            import pyarrow.parquet as pq
            with fsutils.open_file(path, "wb") as f:
                pq.write_table(self.to_arrow(), f)
        else:
            raise ValueError(f"outputFormat {fmt!r}")


def vector_mean(df: DataFrame, column: str) -> List[float]:
    """Element-wise mean of a float-array column (VectorMean.scala
    UDAF analog, used by test())."""
    arrs = [np.asarray(r[column], np.float64) for r in df.rows]
    if not arrs:
        return []
    return [float(x) for x in np.mean(np.stack(arrs), axis=0)]


class CaffeOnSpark:
    """Driver facade.  `sc` is accepted for API parity; local engine
    when None or pyspark is unavailable."""

    def __init__(self, sc=None):
        self.sc = sc

    # ------------------------------------------------------------------
    def _engine(self, conf: Config):
        """SparkEngine when `sc` is a usable SparkContext, else None
        (local engine).  The reference has no such fork — Spark IS its
        runtime; here local mode is first-class (TPU pods don't need a
        JVM) and a real `sc` upgrades train/trainWithValidation/features
        to the barrier-stage executor choreography transparently."""
        from . import spark as spark_mod
        if self.sc is None or not hasattr(self.sc, "parallelize"):
            return None
        # no spark_available() gate here: a live SparkContext proves a
        # working JVM gateway however it was launched (spark-submit
        # with a bundled JRE has no `java` on PATH) — the which-java
        # heuristic belongs to the pre-construction path in
        # _cli_spark_context only (round-4 advisor)
        return spark_mod.SparkEngine(self.sc, conf, require=False)

    def _engine_run(self, engine, make_feed) -> dict:
        """The driver re-feed loop (:204-227): feed, poll, repeat until
        the executor solvers reach max_iter; then join + shutdown.
        `make_feed` builds the per-round feed closure INSIDE the
        try/finally so a failure materializing sources still tears the
        executors down (orphaned daemons would hijack the app_id's next
        run).  Raises unless training verifiably completed."""
        rep = None
        try:
            feed_rounds = make_feed()
            for _ in range(1000):
                feed_rounds()
                rep = engine.collect_report()
                if rep is not None and not rep["alive"]:
                    break
            rep = engine.wait_done()
        finally:
            engine.shutdown()
        if rep is not None and rep.get("error"):
            raise RuntimeError(
                f"executor solver failed: {rep['error']}")
        if rep is None or rep.get("alive"):
            raise RuntimeError(
                "training did not complete: executor solver still "
                "running (or unreachable) after the re-feed loop — "
                "check executor logs / max_iter vs records fed")
        return rep

    # ------------------------------------------------------------------
    def train(self, source: DataSource, conf: Optional[Config] = None
              ) -> None:
        """Synchronous training over the mesh (CaffeOnSpark.train).
        The re-feed loop of the reference (:204-227, feeding the RDD
        until max_iter) is the processor's looping source feed; with a
        real SparkContext the records stream through the barrier-stage
        executors instead."""
        conf = conf or source_conf(source)
        engine = self._engine(conf)
        if engine is not None:
            engine.setup()

            def make_feed():
                # executor-side reads: each feed round = one epoch of
                # every rank's own shard, opened inside the task (the
                # records never pass through the driver)
                epochs = itertools.count()
                return lambda: engine.feed_source(source, 0,
                                                  next(epochs))

            self._engine_run(engine, make_feed)
            return
        proc = CaffeProcessor.instance(conf, rank=conf.rank)
        proc.start()
        try:
            self._feed_until_done(proc, source)
        finally:
            proc.queues[0].offer(None)
            proc.join()

    def trainWithValidation(self, source_train: DataSource,
                            source_validation: DataSource,
                            conf: Optional[Config] = None) -> DataFrame:
        """Interleaved train+validation (:239-358): every executor feeds
        test_interval×batch training records then test_iter×batch
        validation records, in lockstep; rank 0 records metrics."""
        conf = conf or source_conf(source_train)
        sp = conf.solverParameter
        test_interval = sp.test_interval
        test_iter = sp.test_iter[0] if sp.test_iter else 0
        if not test_interval or not test_iter:
            raise ValueError("trainWithValidation needs test_interval "
                             "and test_iter in the solver prototxt")
        engine = self._engine(conf)
        if engine is not None:
            engine.setup(interleave_validation=True)

            def make_feed():
                # train records: executor-side shard reads per round.
                # validation: one ROUND per feed round, sized exactly
                # test_iter x batch (the fixed-size validation
                # partition, CaffeOnSpark.scala:266,279-282) — feeding
                # the whole validation set each round would outrun the
                # solver's per-interval drain and deadlock on queue-1
                # backpressure.  The bounded val slice is the one
                # driver-materialized piece, by design.
                epochs = itertools.count()
                need = test_iter * source_validation.batch_size
                val_round = list(itertools.islice(
                    _record_loop(source_validation), need))
                val_rdd = self.sc.parallelize(val_round, 1)

                def rounds():
                    engine.feed_source(source_train, 0, next(epochs))
                    engine.feed_partitions(val_rdd, 1)
                return rounds

            rep = self._engine_run(engine, make_feed)
            val = (rep or {}).get("validation") or {}
            return DataFrame(val.get("rounds", []),
                             val.get("names", []))
        proc = CaffeProcessor.instance(conf, rank=conf.rank)
        proc.interleave_validation = True
        proc.start()
        try:
            train_bs = source_train.batch_size
            val_bs = source_validation.batch_size
            persistent = bool(getattr(conf, "isPersistent", False))
            train_gen = timed_records(
                _record_loop(source_train, persistent=persistent),
                proc.metrics, train_bs)
            val_gen = _record_loop(source_validation,
                                   persistent=persistent)
            max_iter = sp.max_iter
            fed = 0
            drops_seen = 0
            while fed < max_iter and proc._thread.is_alive():
                # top up for batches the processor dropped (bad records)
                # so its iteration count stays in lockstep with the plan
                extra = proc.dropped_batches - drops_seen
                drops_seen = proc.dropped_batches
                for _ in range((test_interval + extra) * train_bs):
                    if not proc.feed_queue(0, next(train_gen)):
                        break
                fed += test_interval
                for _ in range(test_iter * val_bs):
                    if not proc.feed_queue(1, next(val_gen)):
                        break
        finally:
            proc.queues[0].offer(None)
            proc.join()
        report = proc.validation
        rows = report.rounds if report else []
        return DataFrame(rows, report.names if report else [])

    # ------------------------------------------------------------------
    def test(self, source: DataSource,
             conf: Optional[Config] = None) -> Dict[str, List[float]]:
        """Forward over the test set; per-output mean vectors
        (:396-418)."""
        df = self.features2(source, conf)
        names = [c for c in df.columns if c != "SampleID"]
        return {n: vector_mean(df, n) for n in names}

    def features(self, source: DataSource,
                 conf: Optional[Config] = None) -> DataFrame:
        """Feature extraction → DataFrame(SampleID, blobs...)
        (:427-438)."""
        return self.features2(source, conf)

    def features2(self, source: DataSource,
                  conf: Optional[Config] = None) -> DataFrame:
        conf = conf or source_conf(source)
        blob_names = [b.strip() for b in conf.features.split(",")
                      if b.strip()] if conf.features else None
        if blob_names and conf.label and conf.label not in blob_names:
            blob_names.append(conf.label)
        engine = self._engine(conf)
        if engine is not None:
            # executor-resident extraction (featureRDD, :483-505):
            # params come from -weights/-snapshot, no solver thread;
            # blob_names=None resolves daemon-side (net outputs +
            # -label, default_feature_blobs)
            engine.setup(start_training=False)
            try:
                rows = engine.features_source(source, blob_names)
            finally:
                engine.shutdown()
            names = (blob_names if blob_names else
                     [c for c in (rows[0] if rows else {})
                      if c != "SampleID"])
            return DataFrame(rows, ["SampleID"] + list(names))
        proc = CaffeProcessor.instance(conf, rank=conf.rank)
        if blob_names is None:
            blob_names = proc.default_feature_blobs()
        rows = proc.extract_features(source, blob_names)
        return DataFrame(rows, ["SampleID"] + blob_names)

    # ------------------------------------------------------------------
    def _feed_until_done(self, proc: CaffeProcessor,
                         source: DataSource) -> None:
        gen = timed_records(
            _record_loop(source, persistent=bool(
                getattr(proc.conf, "isPersistent", False))),
            proc.metrics, source.batch_size)
        while proc._thread is not None and proc._thread.is_alive():
            if not proc.feed_queue(0, next(gen)):
                break


def _record_loop(source: DataSource, persistent: bool = False):
    """Endless record generator (the repeated RDD re-feed, :204-227);
    train-phase sources emit a per-epoch shuffled order.  With
    `persistent` (the -persistent flag, sourceRDD.persist analog,
    CaffeOnSpark.scala:206) epoch 0 materializes the decoded records in
    memory and later epochs re-serve them (seeded per-epoch reshuffle)
    instead of re-reading the backing store."""
    epoch = 0
    cache: Optional[List] = [] if persistent else None
    while True:
        n = 0
        if cache and epoch > 0:
            if source.phase_train:
                rng = np.random.RandomState(source.epoch_seed(epoch))
                order = rng.permutation(len(cache))
            else:
                order = range(len(cache))
            for i in order:
                n += 1
                yield cache[i]
        else:
            records = (source.shuffled_records(epoch)
                       if source.phase_train else source.records())
            for rec in records:
                n += 1
                if cache is not None:
                    cache.append(rec)
                yield rec
        if n == 0:
            raise ValueError("data source produced no records")
        epoch += 1


def source_conf(source: DataSource) -> Config:
    conf = getattr(source, "_conf", None)
    if conf is None:
        raise ValueError("pass conf= explicitly (source has none)")
    return conf


def validation_source(conf: Config) -> Optional[DataSource]:
    """Interleaved-validation source, or None if the config doesn't
    interleave.  Every rank feeds the SAME validation data in lockstep
    — the reference replicates the one validation partition to every
    executor (CaffeOnSpark.scala:293-302 via UnionRDDWLocsSpecified
    + Util.executorLocations); rank-sharding it would validate each
    rank on different data, so rank/num_ranks are pinned to 0/1."""
    test_layer = conf.test_data_layer()
    sp = conf.solverParameter
    if test_layer is None or not sp.test_interval \
            or not (sp.test_iter and sp.test_iter[0]):
        return None
    return get_source(test_layer, phase_train=False, rank=0,
                      num_ranks=1, resize=conf.resize)


# ---------------------------------------------------------------------------
# CLI (CaffeOnSpark.main, :27-84)
# ---------------------------------------------------------------------------

def _cli_spark_context(conf: Config):
    """Under spark-submit the reference's main always runs with a
    SparkContext; mirror that when a cluster is requested AND pyspark
    exists.  Local/TPU-pod runs (clusterSize <= 1, or no pyspark) stay
    on the first-class local engine — no JVM required."""
    if conf.clusterSize <= 1:
        return None
    from . import spark as spark_mod
    if not spark_mod.spark_available():
        return None
    from pyspark import SparkContext
    return SparkContext.getOrCreate()


def _serve_sigterm_drains() -> None:
    """Route SIGTERM — and an operator's Ctrl-C — onto the same
    drain-then-exit path.  The fleet/supervisor teardown
    (tools/supervisor.terminate_processes) sends SIGTERM with a grace
    window precisely so accepted serving work can flush; without a
    handler Python's default disposition kills the process instantly
    and the drain never runs.  The flight recorder dumps FIRST — if
    the grace window closes and SIGKILL lands mid-drain, the event
    timeline is already on disk (COS_RECORDER_DUMP).  SIGINT gets the
    same treatment: Python's default KeyboardInterrupt would run the
    drain but dump the ring only at the very end of the finally block
    — a second Ctrl-C mid-drain would lose it, so the dump lands
    before the drain here too."""
    def handler(signum, frame):
        from .obs.recorder import maybe_dump, record
        name = "SIGINT" if signum == signal.SIGINT else "SIGTERM"
        record("serve", "signal", signal=name)
        maybe_dump(name.lower())
        raise KeyboardInterrupt
    try:
        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)
    except ValueError:
        pass                  # not the main thread (embedded): skip


def _dump_serve_metrics(summary: dict) -> None:
    """COS_SERVE_METRICS=path: one JSON document at shutdown (same
    shape for single-process and fleet mode).  The flight-recorder
    artifact (COS_RECORDER_DUMP) and the trace spool flush land here
    too — the clean-shutdown counterpart of the SIGTERM dump."""
    path = os.environ.get("COS_SERVE_METRICS")
    if path:
        with open(path, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
    from .obs.recorder import maybe_dump
    from .obs.trace import get_tracer
    maybe_dump("shutdown")
    get_tracer().flush_spool()


def serve_fleet_main(conf: Config, replicas: int) -> int:
    """-serve -serveReplicas N: fleet mode.  N replica processes (each
    the unchanged single-process stack on an ephemeral loopback port)
    behind the least-outstanding router; the client-facing port is the
    ROUTER's.  Replica death is absorbed: the router retries onto
    healthy peers while the fleet monitor restarts the dead process
    (warm via COS_AOT_CACHE_DIR when set)."""
    from .serving.fleet import Fleet
    from .serving.router import RouterHTTPServer
    _serve_sigterm_drains()
    serve_args = ["-conf", conf.protoFile]
    if conf.modelPath:
        serve_args += ["-model", conf.modelPath]
    if conf.snapshotModelFile:
        serve_args += ["-weights", conf.snapshotModelFile]
    if conf.snapshotStateFile:
        serve_args += ["-snapshot", conf.snapshotStateFile]
    # the served-blob selection must reach the replicas, or they fall
    # back to the net's output blobs and answer the wrong columns
    if conf.features:
        serve_args += ["-features", conf.features]
    if conf.label:
        serve_args += ["-label", conf.label]
    if getattr(conf, "resize", False):
        serve_args += ["-resize"]
    # sharded serving: each replica builds the same mesh layout
    if getattr(conf, "serveMesh", ""):
        serve_args += ["-serveMesh", conf.serveMesh]
    fleet = Fleet(serve_args, replicas)
    fleet.start()
    try:
        # inside the guard: a bind failure (port in use) must not
        # orphan N freshly-warmed replica subprocesses
        httpd = RouterHTTPServer(fleet.router, host=conf.serveHost,
                                 port=conf.servePort,
                                 reload_fn=fleet.rolling_reload,
                                 publish_fn=fleet.publish_model)
    except BaseException:
        fleet.stop()
        raise
    try:
        # inside the guard: a signal (or BrokenPipeError on a closed
        # stdout) landing during the boot print must still tear the
        # warmed replicas down
        print(json.dumps({"serving": True, "port": httpd.port,
                          "replicas": replicas,
                          "replica_urls": {n: r.url for n, r
                                           in fleet.replicas.items()}}),
              flush=True)
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.stop()
        fleet.stop()
        _dump_serve_metrics(fleet.metrics_summary())
    return 0


def deploy_main(conf: Config) -> int:
    """-deploy mode: the continuous-deployment loop (deploy/).  Runs
    `-deployRounds` (or COS_DEPLOY_ROUNDS) rounds of stream-follow →
    fine-tune → canary → fleet roll/rollback, printing one JSON line
    per round verdict, then dumps the fleet+deploy metrics (info.deploy
    included) to COS_SERVE_METRICS when set."""
    from .deploy import DeployController, deploy_rounds
    _serve_sigterm_drains()
    ctl = DeployController(conf)
    ctl.start()
    try:
        print(json.dumps({"deploying": True,
                          "incumbent": ctl.incumbent,
                          "replicas": ctl.replicas,
                          "stream": ctl.source.describe()}),
              flush=True)
        for r in range(conf.deployRounds or deploy_rounds()):
            rec = ctl.run_round()
            print(json.dumps({"deploy_round": rec["round"],
                              "verdict": rec["verdict"],
                              "reason": rec.get("reason"),
                              "incumbent": rec["incumbent"]}),
                  flush=True)
    except KeyboardInterrupt:
        pass
    finally:
        ctl.stop()
        _dump_serve_metrics(ctl.metrics_summary())
    return 0


def serve_main(conf: Config) -> int:
    """-serve mode: online inference over the serving subsystem.  Runs
    until interrupted; drains in-flight requests on shutdown and dumps
    serving metrics to COS_SERVE_METRICS (same JSON format as the
    pipeline metrics) when set.  `-serveReplicas N` (or
    COS_SERVE_REPLICAS) > 1 switches to fleet mode."""
    from .serving import InferenceService, ServingHTTPServer
    from .serving.fleet import serve_replicas
    n = conf.serveReplicas if getattr(conf, "serveReplicas", 0) > 0 \
        else serve_replicas()
    if n > 1:
        return serve_fleet_main(conf, n)
    _serve_sigterm_drains()
    svc = InferenceService(conf)   # loads -weights, else -model
    svc.start()
    httpd = ServingHTTPServer(svc, host=conf.serveHost,
                              port=conf.servePort)
    try:
        print(json.dumps({"serving": True, "port": httpd.port,
                          "model_version": svc.registry.version,
                          "buckets": list(svc.batcher.buckets)}),
              flush=True)
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        svc.stop(drain=True)
        _dump_serve_metrics(svc.metrics_summary())
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    conf = Config(argv if argv is not None else sys.argv[1:])
    conf.validate()
    from .utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if getattr(conf, "serve", False):
        return serve_main(conf)
    if getattr(conf, "deploy", False):
        return deploy_main(conf)
    cos = CaffeOnSpark(_cli_spark_context(conf))

    if conf.isTraining:
        # the trained model is handed to a later -test/-features phase
        # through the model file, as the reference does via -model
        if not conf.modelPath:
            conf.modelPath = fsutils.join(conf.outputPath or ".",
                                          "model.caffemodel")
        train_layer = conf.train_data_layer()
        src = get_source(train_layer, phase_train=True, rank=conf.rank,
                         num_ranks=max(1, conf.clusterSize),
                         resize=conf.resize)
        src._conf = conf
        val_src = validation_source(conf)
        if val_src is not None:
            df = cos.trainWithValidation(src, val_src, conf)
            if conf.outputPath:
                df.write(fsutils.join(conf.outputPath,
                                      "validation." + conf.outputFormat),
                         conf.outputFormat)
        else:
            cos.train(src, conf)

    if conf.isTest or conf.features:
        # load trained weights: after a training phase the JUST-trained
        # model wins (even over a -weights finetune source); in
        # test/features-only runs, -model supplies the weights
        if conf.isTraining and conf.modelPath \
                and fsutils.exists(conf.modelPath):
            conf.snapshotModelFile = conf.modelPath
            conf.snapshotStateFile = ""
        elif conf.modelPath and fsutils.exists(conf.modelPath) \
                and not conf.snapshotModelFile:
            conf.snapshotModelFile = conf.modelPath
        layer = conf.test_data_layer() or conf.train_data_layer()
        src = get_source(layer, phase_train=False, rank=conf.rank,
                         num_ranks=max(1, conf.clusterSize),
                         resize=conf.resize)
        src._conf = conf
        if conf.isTest:
            result = cos.test(src, conf)
            out = json.dumps(result)
            print(out)
            if conf.outputPath:
                with fsutils.open_file(
                        fsutils.join(conf.outputPath, "test_result"),
                        "w") as f:
                    f.write(out + "\n")
        else:
            df = cos.features(src, conf)
            if conf.outputPath:
                df.write(fsutils.join(conf.outputPath,
                                      "features." + conf.outputFormat),
                         conf.outputFormat)
    return 0


if __name__ == "__main__":
    sys.exit(main())
