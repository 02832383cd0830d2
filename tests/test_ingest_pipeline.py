"""Pipelined ingest runtime tests: FeedQueue timeout semantics, the
ordered TransformerPool (multi-thread ordering, epoch boundaries, one
terminal per pool, drop-abort), the background device stager's CPU
aliasing defense, combine_batches remainder logging, PipelineMetrics,
and the end-to-end pipelined CaffeProcessor train path."""

import os
import queue
import threading
import time

import numpy as np
import pytest

from caffeonspark_tpu.data.queue_runner import (DROPPED, FeedQueue,
                                                PipelinedFeed,
                                                TransformerPool,
                                                combine_batches,
                                                device_prefetch)
from caffeonspark_tpu.metrics import PipelineMetrics


# -- FeedQueue timeout semantics (satellite fix) -----------------------

def test_feed_queue_take_timeout_zero():
    """A falsy timeout must NOT fall into the forever-blocking branch."""
    q = FeedQueue(capacity=4)
    with pytest.raises(queue.Empty):
        q.take(timeout=0)
    q.offer(1)
    assert q.take(timeout=0) == 1


def test_feed_queue_offer_deadline():
    """offer() honors a real deadline instead of one 0.1s slice."""
    q = FeedQueue(capacity=2)
    assert q.offer(1) and q.offer(2)
    t0 = time.monotonic()
    assert q.offer(3, timeout=0.5) is False
    dt = time.monotonic() - t0
    assert 0.4 < dt < 2.0, dt
    # timeout=0: single non-blocking attempt
    t0 = time.monotonic()
    assert q.offer(3, timeout=0) is False
    assert time.monotonic() - t0 < 0.2
    q.take()
    assert q.offer(3, timeout=0) is True


def test_feed_queue_offer_unblocks_on_stop():
    q = FeedQueue(capacity=1)
    q.offer(1)
    done = []

    def blocked():
        done.append(q.offer(2))        # no timeout: spins until stop

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.3)
    q.stop()
    t.join(timeout=5)
    assert done == [False]


# -- TransformerPool ---------------------------------------------------

def _ids_pack(buf, draw):
    return {"ids": np.asarray(buf)}


def test_transformer_pool_ordered_output_multithread():
    """Output order == feed order even when workers finish shuffled."""
    feed = FeedQueue()

    def jittery_pack(buf, draw):
        time.sleep(0.002 * (buf[0] % 4))
        return {"ids": np.asarray(buf)}

    pool = TransformerPool(feed, 4, jittery_pack, num_threads=4).start()
    for i in range(64):
        feed.offer(i)
    feed.offer(None)
    got = [b["ids"].tolist() for b in pool]
    assert got == [list(range(i, i + 4)) for i in range(0, 64, 4)]
    pool.join(timeout=5)


def test_transformer_pool_epoch_boundary_drops_ragged_tail():
    m = PipelineMetrics()
    feed = FeedQueue()
    pool = TransformerPool(feed, 4, _ids_pack, num_threads=2,
                           metrics=m).start()
    for i in range(10):                # 2 full batches + ragged 2
        feed.offer(i)
    feed.mark_epoch_end()
    for i in range(20, 24):            # next epoch: 1 full batch
        feed.offer(i)
    feed.offer(None)
    got = [b["ids"].tolist() for b in pool]
    assert got == [[0, 1, 2, 3], [4, 5, 6, 7], [20, 21, 22, 23]]
    assert m.summary()["counters"]["ragged_tail_records"] == 2


def test_transformer_pool_single_terminal():
    """Exactly one terminal condition per pool: iteration ends once,
    further take() keeps returning None, threads exit."""
    feed = FeedQueue()
    pool = TransformerPool(feed, 2, _ids_pack, num_threads=3).start()
    for i in range(6):
        feed.offer(i)
    feed.offer(None)
    assert len(list(pool)) == 3
    assert pool.take() is None
    assert pool.take(timeout=0.1) is None
    pool.join(timeout=5)
    assert all(not t.is_alive() for t in pool._threads)


def test_transformer_pool_drop_skip_and_abort():
    """Pack failures drop the slot (train consumers skip, validation
    counts) and a consecutive run aborts via take()."""
    feed = FeedQueue()

    def pack(buf, draw):
        if buf[0] % 8 == 0:
            raise ValueError(f"bad {buf[0]}")
        return {"ids": np.asarray(buf)}

    pool = TransformerPool(feed, 4, pack, num_threads=2,
                           drop_limit=50).start()
    for i in range(32):
        feed.offer(i)
    feed.offer(None)
    got = [b["ids"][0] for b in pool]
    assert got == [4, 12, 20, 28]      # slots 0,8,16,24 dropped
    assert pool.drops == 4

    # skip_dropped=False exposes the DROPPED slot (validation rounds)
    feed2 = FeedQueue()
    pool2 = TransformerPool(feed2, 4, pack, num_threads=2,
                            drop_limit=50).start()
    for i in range(8):
        feed2.offer(i)
    feed2.offer(None)
    assert pool2.take(timeout=5, skip_dropped=False) is DROPPED
    assert pool2.take(timeout=5, skip_dropped=False)["ids"][0] == 4

    # consecutive failures abort the pipeline
    feed3 = FeedQueue()

    def bad_pack(buf, draw):
        raise ValueError("always")

    pool3 = TransformerPool(feed3, 2, bad_pack, num_threads=2,
                            drop_limit=3).start()
    for i in range(12):
        feed3.offer(i)
    feed3.offer(None)
    with pytest.raises(RuntimeError, match="consecutive batch"):
        for _ in pool3:
            pass
    for p in (pool, pool2, pool3):
        p.stop(join_timeout=5)


@pytest.mark.parametrize(
    "cores,quota,pool_width,explicit,local_procs,want", [
        (13, None, 2, 0, 1, 5),         # the one-chip host: 10 in all
        (13, "max 100000", 2, 0, 1, 5),  # a cgroup with no quota
        (13, "800000 100000", 2, 0, 1, 3),
        (13, "400000 100000", 2, 0, 1, 1),
        (13, "50000 100000", 2, 0, 1, 1),   # half a CPU
        (13, "garbage", 2, 0, 1, 5),
        (2, None, 2, 0, 1, 1),          # the 2-core box keeps its 1
        (1, None, 2, 0, 1, 1),
        (13, None, 1, 0, 1, 11),        # a validation-style pool of one
        (13, None, 4, 0, 1, 2),
        (112, None, 2, 0, 1, 55),
        (13, None, 2, 4, 1, 4),         # the caller's choice stands
        (13, None, 2, 1, 1, 1),
        (2, None, 2, 7, 1, 7),
        (13, None, 2, 0, 2, 2),         # two ranks of a job on the host
        (112, None, 2, 0, 4, 13),
        (8, None, 2, 0, 8, 1),
        (None, None, 2, 0, 1, 2),       # no affinity call: cpu_count()
    ])
def test_pooled_pack_thread_share(tmp_path, monkeypatch, cores, quota,
                                  pool_width, explicit, local_procs,
                                  want):
    """tune_decode_threads: a pooled source nobody chose threads for
    gets (cores the process may use - 2) // pool width, cores cut by a
    cgroup quota and by the job's other processes on the host, never
    under 1; a caller's own num_threads stands."""
    import os
    from types import SimpleNamespace
    from caffeonspark_tpu.data import queue_runner as qr

    if cores is None:
        def no_affinity(pid):
            raise AttributeError("sched_getaffinity")
        monkeypatch.setattr(os, "sched_getaffinity", no_affinity,
                            raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 6)
    else:
        monkeypatch.setattr(os, "sched_getaffinity",
                            lambda pid: set(range(cores)), raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 999)
    cpu_max = tmp_path / "cpu.max"
    if quota is not None:
        cpu_max.write_text(quota + "\n")
    monkeypatch.setattr(qr, "_CGROUP_CPU_MAX", str(cpu_max))
    src = SimpleNamespace(num_threads=explicit)
    qr.tune_decode_threads(src, pool_width, local_procs)
    assert src.num_threads == want


def test_mini_cluster_counts_its_local_ranks():
    """A rank divides the cores by the job's ranks on its host when its
    own arguments say they are all there."""
    from types import SimpleNamespace as A
    from caffeonspark_tpu.mini_cluster import local_ranks
    assert local_ranks(A(cluster=None, server=None)) == 1
    assert local_ranks(A(cluster=4, server="127.0.0.1:47788")) == 4
    assert local_ranks(A(cluster=4, server="localhost:1")) == 4
    assert local_ranks(A(cluster=2, server="agent://127.0.0.1:9")) == 2
    assert local_ranks(A(cluster=2, server=None)) == 2     # elastic
    assert local_ranks(A(cluster=16, server="10.0.0.5:47788")) == 1


@pytest.mark.parametrize("num_threads", [1, 3])
@pytest.mark.parametrize("kind", ["encoded", "raw"])
def test_transformer_pool_ordered_draw_parity(tmp_path, kind,
                                              num_threads):
    """A pool of several workers, each pack's native calls on
    `num_threads` threads, reproduces the inline path's augmentation
    stream exactly (crop offsets + mirror flips pre-drawn in feed order
    by the dispatcher), for JPEG and for raw records."""
    import cv2
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum, LayerParameter

    imgs, labels = make_images(48, seed=4)
    recs = []
    for i in range(48):
        u8 = (imgs[i, 0] * 255).astype(np.uint8)
        if kind == "encoded":
            ok, buf = cv2.imencode(".jpg", u8)
            d = Datum(encoded=True, data=bytes(buf), label=int(labels[i]))
        else:
            d = Datum(channels=1, height=28, width=28, data=u8.tobytes(),
                      label=int(labels[i]))
        recs.append((b"%06d" % i, d.to_binary()))
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    lp = LayerParameter.from_text(f'''
        name: "data" type: "MemoryData" top: "data" top: "label"
        source_class: "LMDB"
        transform_param {{ crop_size: 24 mirror: true scale: 0.0039 }}
        memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
          channels: 1 height: 28 width: 28 }}''')
    ref_src = get_source(lp, phase_train=True, seed=9, resize=True)
    ref = list(ref_src.batches(loop=False, shuffle=False))
    src = get_source(lp, phase_train=True, seed=9, resize=True,
                     num_threads=num_threads)
    src.metrics = PipelineMetrics()
    feed = PipelinedFeed(src, loop=False, shuffle=False, num_threads=3)
    got = list(feed)
    feed.close()
    assert len(got) == len(ref) == 6
    assert src.num_threads == num_threads       # the caller chose
    gauge = src.metrics.summary()["queue_depths"]["pack_threads"]
    assert gauge == {"samples": 6, "mean": num_threads,
                     "max": num_threads}
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a["data"], b["data"])
        np.testing.assert_array_equal(a["label"], b["label"])
    # and both are what Transformer.__call__ makes of the same records
    # on the same stream of draws
    oracle = get_source(lp, phase_train=True, seed=9, resize=True)
    records = list(oracle.records())
    for k, b in enumerate(got):
        data = oracle._records_to_data(records[8 * k:8 * k + 8], 1, 28, 28)
        np.testing.assert_array_equal(b["data"], oracle.transformer(data))


def test_pipelined_feed_small_shard_carries_tail(tmp_path):
    """A looping feed whose shard is smaller than batch_size must still
    form batches — epochs stream continuously (batches(loop=True)
    carry-over semantics), they don't drop the tail per epoch."""
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum, LayerParameter

    imgs, labels = make_images(5, seed=2)       # 5 records, batch 8
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary()) for i in range(5)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    lp = LayerParameter.from_text(f'''
        name: "data" type: "MemoryData" top: "data" top: "label"
        source_class: "LMDB"
        memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
          channels: 1 height: 28 width: 28 }}''')
    src = get_source(lp, phase_train=True, seed=0)
    feed = PipelinedFeed(src, loop=True, shuffle=False, num_threads=2)
    it = iter(feed)
    try:
        batches = [next(it) for _ in range(3)]
    finally:
        feed.close()
    labels_seen = np.concatenate([b["label"] for b in batches])
    want = np.tile([float(r) for r in labels[:5]], 5)[:24]
    np.testing.assert_array_equal(labels_seen, want)


# -- device stager -----------------------------------------------------

def test_stager_cpu_aliasing_regression():
    """Reused/pooled pack buffers must survive staging on the CPU
    backend, where jax.device_put aliases aligned host numpy buffers:
    the stager's host copy freezes each batch's value at stage time."""
    buf = np.zeros(8, np.float32)      # one reused pack buffer

    def gen():
        for i in range(6):
            buf[:] = i
            yield {"x": buf}

    staged = list(device_prefetch(gen(), depth=2, background=True))
    vals = [float(np.asarray(b["x"])[0]) for b in staged]
    assert vals == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0], vals

    # foreground staging applies the same defense
    buf[:] = 0
    staged = list(device_prefetch(gen(), depth=2, background=False))
    vals = [float(np.asarray(b["x"])[0]) for b in staged]
    assert vals == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0], vals


def test_background_stager_propagates_errors():
    def gen():
        yield {"x": np.zeros(2, np.float32)}
        raise RuntimeError("upstream died")

    g = device_prefetch(gen(), depth=2, background=True)
    next(g)
    with pytest.raises(RuntimeError, match="upstream died"):
        for _ in g:
            pass


def test_background_stager_stops_on_close():
    def gen():
        i = 0
        while True:
            yield {"x": np.full(2, i, np.float32)}
            i += 1

    g = device_prefetch(gen(), depth=2, background=True)
    next(g)
    g.close()            # must not hang; stager thread winds down


# -- combine_batches remainder logging (satellite) ---------------------

def test_combine_batches_logs_dropped_remainder(caplog):
    batches = [{"x": np.full(2, i, np.float32)} for i in range(5)]
    with caplog.at_level("INFO",
                        logger="caffeonspark_tpu.data.queue_runner"):
        out = list(combine_batches(iter(batches), 2))
    assert len(out) == 2
    assert any("dropping 1 trailing" in r.message for r in caplog.records)


# -- metrics -----------------------------------------------------------

def test_pipeline_metrics_summary_and_dump(tmp_path):
    m = PipelineMetrics(capacity=64)
    for i in range(10):
        m.add("pack", 0.01 * (i + 1))
        m.mark_step()
        m.gauge("feed_depth", i)
    m.incr("dropped_batches")
    s = m.summary()
    assert s["stages"]["pack"]["count"] == 10
    assert s["stages"]["pack"]["p50_ms"] > 0
    assert s["stages"]["pack"]["max_ms"] >= s["stages"]["pack"]["p50_ms"]
    assert s["counters"]["dropped_batches"] == 1
    assert s["queue_depths"]["feed_depth"]["max"] == 9
    assert s["steps"] == 10
    p = m.dump(str(tmp_path / "m.json"))
    import json
    loaded = json.load(open(p))
    assert loaded["stages"]["pack"]["count"] == 10


def test_pipeline_metrics_thread_safety():
    m = PipelineMetrics(capacity=128)

    def pound():
        for i in range(500):
            m.add("pack", 0.001)
            m.incr("n")
            m.gauge("d", i)
            m.mark_step()

    ts = [threading.Thread(target=pound) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    s = m.summary()
    assert s["stages"]["pack"]["count"] == 2000
    assert s["counters"]["n"] == 2000


def test_drop_counters_per_phase():
    """Concurrent train-pool successes must not reset a systematically
    failing validation source's consecutive-drop streak (and vice
    versa) — the abort fires per phase."""
    proc = CaffeProcessorShim()
    for i in range(25):
        proc._note_pack_ok()                 # healthy train feed
        if i < 19:
            proc._note_pack_drop(ValueError("bad val"), val=True)
    with pytest.raises(RuntimeError, match="consecutive"):
        proc._note_pack_drop(ValueError("bad val"), val=True)
    assert proc.dropped_val_batches == 20
    assert proc.dropped_batches == 0


class CaffeProcessorShim:
    """Just the drop-accounting mixin surface of CaffeProcessor,
    avoiding solver/mesh construction."""

    def __init__(self):
        import threading
        from caffeonspark_tpu.metrics import PipelineMetrics
        self.dropped_batches = 0
        self.dropped_val_batches = 0
        self._consecutive_drops = 0
        self._consecutive_val_drops = 0
        self._drop_lock = threading.Lock()
        self.metrics = PipelineMetrics()

    from caffeonspark_tpu.processor import CaffeProcessor
    MAX_CONSECUTIVE_DROPS = CaffeProcessor.MAX_CONSECUTIVE_DROPS
    _note_pack_ok = CaffeProcessor._note_pack_ok
    _note_pack_drop = CaffeProcessor._note_pack_drop
    del CaffeProcessor


# -- end-to-end: pipelined processor train -----------------------------

def test_processor_pipelined_train_end_to_end(tmp_path, monkeypatch):
    """CaffeOnSpark.train with the pipelined runtime (pool + stager):
    completes, tolerates a corrupt record via the thread-safe drop
    path, and the step-timeline metrics carry non-zero queue-wait /
    pack / stage / step samples."""
    import cv2
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.processor import CaffeProcessor
    from caffeonspark_tpu.proto.caffe import Datum

    monkeypatch.setenv("COS_TRANSFORM_THREADS", "2")
    imgs, labels = make_images(48, seed=6)
    recs = []
    for i in range(48):
        ok, buf = cv2.imencode(".jpg", (imgs[i, 0] * 255).astype(np.uint8))
        data = b"CORRUPT!" if i == 5 else bytes(buf)
        recs.append((b"%06d" % i,
                     Datum(encoded=True, data=data,
                           label=int(labels[i])).to_binary()))
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 16
    channels: 1 height: 28 width: 28 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\n'
                      'lr_policy: "fixed"\nmax_iter: 5\n'
                      'snapshot_prefix: "x"\nrandom_seed: 2\n')
    conf = Config(["-conf", str(solver), "-train",
                   "-output", str(tmp_path), "-resize"])
    cos = CaffeOnSpark()
    src = get_source(conf.train_data_layer(), phase_train=True,
                     resize=True)
    metrics_path = tmp_path / "pipeline_metrics.json"
    monkeypatch.setenv("COS_PIPELINE_METRICS", str(metrics_path))
    cos.train(src, conf)
    proc = CaffeProcessor.instance()
    assert proc._train_pool is not None, "pool not engaged"
    assert proc.dropped_batches >= 1
    s = proc.metrics.summary()
    for stage in ("queue_wait", "pack", "stage", "step"):
        assert s["stages"][stage]["count"] > 0, stage
        assert s["stages"][stage]["total_s"] > 0, stage
    proc.stop()
    import json
    dumped = json.load(open(metrics_path))
    assert dumped["stages"]["step"]["count"] >= 5


def test_processor_inline_fallback(tmp_path, monkeypatch):
    """COS_TRANSFORM_THREADS=0 keeps the legacy inline path working."""
    import cv2
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.processor import CaffeProcessor
    from caffeonspark_tpu.proto.caffe import Datum

    monkeypatch.setenv("COS_TRANSFORM_THREADS", "0")
    imgs, labels = make_images(32, seed=6)
    recs = []
    for i in range(32):
        ok, buf = cv2.imencode(".jpg", (imgs[i, 0] * 255).astype(np.uint8))
        recs.append((b"%06d" % i,
                     Datum(encoded=True, data=bytes(buf),
                           label=int(labels[i])).to_binary()))
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 16
    channels: 1 height: 28 width: 28 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\n'
                      'lr_policy: "fixed"\nmax_iter: 3\n'
                      'snapshot_prefix: "x"\nrandom_seed: 2\n')
    conf = Config(["-conf", str(solver), "-train",
                   "-output", str(tmp_path), "-resize"])
    cos = CaffeOnSpark()
    src = get_source(conf.train_data_layer(), phase_train=True,
                     resize=True)
    cos.train(src, conf)
    proc = CaffeProcessor.instance()
    assert proc._train_pool is None
    assert proc.metrics.summary()["stages"]["step"]["count"] == 3
    proc.stop()


# -- the host timeline: spans, series, observer ------------------------

SERIES_ALWAYS = ("read", "pack", "pack_decode", "pack_transform",
                 "pack_cpu", "stage_starved", "stage", "stage_copy",
                 "stage_put", "queue_wait", "step", "init_params")
# a feeder that runs ahead of a solver held to 20 ms a step fills every
# queue on the way (blocked); an interleaved job's feeder hands over one
# round at a time, and the pool runs dry between rounds (starved)
SERIES_BLOCKED = ("read_blocked", "group_blocked", "pack_blocked",
                  "stage_blocked")
SERIES_STARVED = ("group_starved", "pack_starved")


def _timeline_job(tmp_path, monkeypatch, *, max_iter, extra_solver="",
                  step_delay_ms=20):
    """A tiny two-phase job: raw 1x28x28 records, batch 16, the full
    pipelined runtime (2 pool workers + background stager) on the CPU."""
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    monkeypatch.setenv("COS_TRANSFORM_THREADS", "2")
    monkeypatch.setenv("COS_STAGE_BG", "1")
    monkeypatch.setenv("COS_FAULT_STEP_DELAY_MS", str(step_delay_ms))
    imgs, labels = make_images(64, seed=6)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(64)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    data = "\n".join(f"""
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  include {{ phase: {phase} }} source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 16
    channels: 1 height: 28 width: 28 }}
  transform_param {{ crop_size: 24 mirror: {mirror} }} }}"""
                     for phase, mirror in (("TRAIN", "true"),
                                           ("TEST", "false")))
    net = tmp_path / "net.prototxt"
    net.write_text(data + """
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }""")
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\n'
                      f'lr_policy: "fixed"\nmax_iter: {max_iter}\n'
                      'snapshot_prefix: "x"\nrandom_seed: 2\n'
                      'snapshot_after_train: false\n' + extra_solver)
    return Config(["-conf", str(solver), "-train",
                   "-output", str(tmp_path)])


def test_span_adds_what_add_would():
    """span() == add() of the same interval, plus an annotation that is
    inert with no profiler session; a body that raises adds nothing; an
    inner span with no attrs carries the outer one's."""
    m = PipelineMetrics()
    t0 = time.perf_counter()
    with m.span("pack", n=3, w=1) as outer:
        time.sleep(0.02)
        with m.span("pack_decode") as inner:
            assert inner._attrs == {"n": 3, "w": 1}
    took = time.perf_counter() - t0
    s = m.summary()["stages"]
    assert s["pack"]["count"] == 1 and s["pack_decode"]["count"] == 1
    assert 0.02 <= outer.seconds <= took
    assert s["pack"]["total_s"] == pytest.approx(outer.seconds, abs=1e-6)
    with pytest.raises(KeyError):
        with m.span("pack", n=4):
            raise KeyError("a failed pack is a drop, not a pack")
    assert m.summary()["stages"]["pack"]["count"] == 1
    assert getattr(m._tls, "attrs", None) is None
    # the solver thread's dispatch: one step, then a fused chunk of 4
    with m.step_span(0, 1):
        pass
    with m.step_span(1, 4):
        pass
    s = m.summary()
    assert s["stages"]["step"]["count"] == 5 and s["steps"] == 5
    assert s["stages"]["scan_step"]["count"] == 1


def test_train_job_reports_every_series(tmp_path, monkeypatch):
    """One -train job with validation and snapshots: every series of the
    vocabulary (metrics.py) has samples, and `pack` still counts packed
    batches, one sample each."""
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.processor import CaffeProcessor

    # a round of 20 batches is more than pool, stager and feed queue hold
    conf = _timeline_job(tmp_path, monkeypatch, max_iter=40,
                         extra_solver="test_interval: 20\ntest_iter: 2\n"
                                      "snapshot: 20\n")
    proc = CaffeProcessor.instance(conf)
    for q in proc.queues:
        q._q.maxsize = 24
    packed = []
    for src in (proc.train_source, proc.val_source):
        real = src.pack_batch
        src.pack_batch = (lambda recs, draw=None, real=real:
                          (packed.append(1), real(recs, draw))[1])
    train_src = get_source(conf.train_data_layer(), phase_train=True)
    val_src = get_source(conf.test_data_layer(), phase_train=False)
    df = CaffeOnSpark().trainWithValidation(train_src, val_src, conf)
    assert len(df) == 2
    for pool in (proc._train_pool, proc._val_pool):
        pool.join(timeout=5)
    s = proc.metrics.summary()
    stages = s["stages"]
    for name in SERIES_ALWAYS + SERIES_BLOCKED + SERIES_STARVED + (
            "validation", "snapshot"):
        assert stages.get(name, {}).get("count", 0) > 0, name
    assert stages["compile"]["count"] + stages.get(
        "cache_load", {"count": 0})["count"] > 0
    assert stages["pack"]["count"] == len(packed)
    assert stages["pack_cpu"]["count"] == len(packed)
    assert stages["step"]["count"] == 40
    assert stages["validation"]["count"] == 2
    assert stages["snapshot"]["count"] == 2
    halves = (stages["pack_decode"]["total_s"]
              + stages["pack_transform"]["total_s"])
    assert halves <= stages["pack"]["total_s"]
    proc.stop()


def test_train_job_packs_every_batch_in_one_pass(tmp_path, monkeypatch):
    """An LMDB -train run through TransformerPool: every batch the pool
    packs, train and validation, takes the one-pass kernel
    (`pack_fused` = `pack` samples, `pack_general` absent), and the
    counters reach the -pipeline_metrics dump."""
    import json
    from caffeonspark_tpu import native
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.processor import CaffeProcessor
    if not native.available():
        pytest.skip("native toolchain/libjpeg unavailable")

    conf = _timeline_job(tmp_path, monkeypatch, max_iter=8,
                         extra_solver="test_interval: 4\ntest_iter: 2\n",
                         step_delay_ms=0)
    dump = tmp_path / "pipeline_metrics.json"
    monkeypatch.setenv("COS_PIPELINE_METRICS", str(dump))
    monkeypatch.setenv("COS_METRICS_FLUSH_S", "0.2")
    # a host of 9 cores: (9 - 2) // 2 pool workers = 3 threads a pack,
    # for the train pool's workers and the validation pool's alike
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(9)), raising=False)
    proc = CaffeProcessor.instance(conf)
    train_src = get_source(conf.train_data_layer(), phase_train=True)
    val_src = get_source(conf.test_data_layer(), phase_train=False)
    CaffeOnSpark().trainWithValidation(train_src, val_src, conf)
    for pool in (proc._train_pool, proc._val_pool):
        pool.join(timeout=5)
    s = proc.metrics.summary()
    assert s["stages"]["step"]["count"] == 8
    assert s["counters"]["pack_fused"] == s["stages"]["pack"]["count"] >= 12
    assert "pack_general" not in s["counters"]
    assert (proc.train_source.num_threads, proc.val_source.num_threads
            ) == (3, 3)
    proc.stop()
    assert json.load(open(dump))["counters"]["pack_fused"] >= 12
    # the flusher's metrics.json: one pack_threads sample a packed batch
    flushed = json.load(open(tmp_path / "metrics.json"))
    packs = flushed["stages"]["pack"]["count"]
    assert flushed["counters"]["pack_fused"] == packs >= 12
    assert flushed["queue_depths"]["pack_threads"] == {
        "samples": packs, "mean": 3.0, "max": 3}


def test_pool_workers_account_for_their_lifetime():
    """pack + pack_starved + pack_blocked is what a worker does from
    start to exit: no more than threads x pool lifetime, and (loosely,
    for a busy CI host) at least 0.7 of it."""
    m = PipelineMetrics()
    feed = FeedQueue()

    def pack(buf, draw):
        time.sleep(0.01)
        return {"ids": np.asarray(buf)}

    t0 = time.perf_counter()
    pool = TransformerPool(feed, 4, pack, num_threads=2,
                           metrics=m).start()
    time.sleep(0.1)                       # starved: nothing fed yet
    for i in range(4 * 24):
        feed.offer(i)
    time.sleep(0.15)                      # blocked: nobody takes
    got = [b for b in iter(lambda: pool.take(timeout=5), None)
           if feed.offer(None) or True][:24]
    assert len(got) == 24
    pool.join(timeout=5)
    lifetime = 2 * (time.perf_counter() - t0)
    stages = m.summary()["stages"]
    assert stages["pack"]["count"] == 24
    for name in ("pack_starved", "pack_blocked", "group_starved",
                 "group_blocked"):
        assert stages[name]["count"] > 0, name
    accounted = sum(stages[k]["total_s"]
                    for k in ("pack", "pack_starved", "pack_blocked"))
    assert 0.7 * lifetime <= accounted <= lifetime, (accounted, lifetime)


def test_profile_of_a_train_job_holds_the_host_timeline(tmp_path,
                                                         monkeypatch):
    """A profiler capture of a live -train job, read through the
    benchmark's own span loader: the chain's stages are there on one
    clock, a pack's halves lie inside it, and batch n is packed before it
    is staged before its step is dispatched."""
    import jax
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.processor import CaffeProcessor
    from perfbench.harness import spans as S
    from perfbench.harness.trace import find_xplane

    conf = _timeline_job(tmp_path, monkeypatch, max_iter=40)
    proc = CaffeProcessor.instance(conf)
    trace_dir = str(tmp_path / "trace")
    seen = []

    def observer(it, n, batch, params, st, out):
        seen.append((it, n))
        if it == 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        elif it == 38:          # the pool packs a dozen batches ahead
            jax.profiler.stop_trace()

    proc.step_observer = observer
    CaffeOnSpark().train(get_source(conf.train_data_layer(),
                                    phase_train=True), conf)
    stages = proc.metrics.summary()["stages"]
    proc.stop()
    assert seen == [(i, 1) for i in range(40)]
    for name in SERIES_ALWAYS + SERIES_BLOCKED:
        assert stages.get(name, {}).get("count", 0) > 0, name
    spans = S.load(find_xplane(trace_dir))
    by = {}
    for name, line, s, e, stats in spans:
        by.setdefault(name, []).append((line, s, e, stats))
    for name in ("pack", "pack_decode", "pack_transform", "stage",
                 "stage_put", "queue_wait", "step"):
        assert by.get(name), name
    packs = {p[3]["n"]: p for p in by["pack"]}
    for half in by["pack_decode"] + by["pack_transform"]:
        line, s, e, stats = half
        outer = packs.get(stats["n"])
        if outer is None:
            continue                    # its pack began before the trace
        assert outer[0] == line and outer[3]["w"] == stats["w"]
        assert outer[1] <= s and e <= outer[2]
    stages = {p[3]["n"]: p for p in by["stage"]}
    steps = {p[3]["it"]: p for p in by["step"]}
    chained = [n for n in sorted(packs) if n in stages and n in steps]
    assert len(chained) >= 3, (sorted(packs), sorted(stages),
                               sorted(steps))
    for n in chained:
        assert packs[n][2] <= stages[n][1] <= stages[n][2] \
            <= steps[n][1], n
    # five kinds of thread minus the feeder, whose busy time is no span
    assert len({p[0] for name in ("pack", "stage", "step")
                for p in by[name]}) >= 3


def test_step_observer_error_surfaces_on_stop(tmp_path, monkeypatch):
    """The hook computes nothing itself, and an observer that raises ends
    the job like any train error: on stop()."""
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.processor import CaffeProcessor

    conf = _timeline_job(tmp_path, monkeypatch, max_iter=10,
                         step_delay_ms=0)
    proc = CaffeProcessor.instance(conf)
    calls = []

    def observer(it, n, batch, params, st, out):
        calls.append(it)
        assert set(batch) == {"data", "label"} and "loss" in out
        if it == 3:
            raise RuntimeError("observer gave up")

    proc.step_observer = observer
    with pytest.raises(RuntimeError, match="observer gave up"):
        CaffeOnSpark().train(get_source(conf.train_data_layer(),
                                        phase_train=True), conf)
    assert calls == [0, 1, 2, 3]
    assert proc.metrics.summary()["stages"]["step"]["count"] == 4


def test_compile_events_reach_series_and_recorder(tmp_path,
                                                  monkeypatch):
    """With a processor live, jax's backend-compile event lands in the
    `compile` series and, once a step is done, in the flight recorder
    with the iteration; a fetch from the persistent cache is a
    `cache_load` and no compile; after stop() nothing is written."""
    import jax
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.metrics import CompileWatch
    from caffeonspark_tpu.obs.recorder import get_recorder
    from caffeonspark_tpu.processor import CaffeProcessor

    conf = _timeline_job(tmp_path, monkeypatch, max_iter=6,
                         step_delay_ms=0)
    proc = CaffeProcessor.instance(conf)

    def observer(it, n, batch, params, st, out):
        if it == 3:
            jax.monitoring.record_event_duration_secs(
                CompileWatch.BACKEND_COMPILE, 1.25)
            jax.monitoring.record_event_duration_secs(
                CompileWatch.CACHE_RETRIEVAL, 0.75)
            jax.monitoring.record_event_duration_secs(
                CompileWatch.BACKEND_COMPILE, 0.76)    # the fetch's own
            jax.monitoring.record_event(
                "/jax/compilation_cache/cache_hits")

    proc.step_observer = observer
    hits0 = proc.metrics.get_counter("cache_hits")
    CaffeOnSpark().train(get_source(conf.train_data_layer(),
                                    phase_train=True), conf)
    stages = proc.metrics.summary()["stages"]
    assert stages["compile"]["max_ms"] == 1250.0
    assert stages["cache_load"]["max_ms"] == 750.0
    assert proc.metrics.get_counter("cache_hits") >= hits0 + 1
    mine = [e for e in get_recorder().events()
            if e["source"] == "trainer" and e["event"] == "compile"
            and e["seconds"] == 1.25]
    assert [e["it"] for e in mine] == [3]
    proc.stop()
    before = proc.metrics.summary()["stages"]["compile"]["count"]
    jax.monitoring.record_event_duration_secs(
        CompileWatch.BACKEND_COMPILE, 2.5)
    assert proc.metrics.summary()["stages"]["compile"]["count"] == before
    assert not [e for e in get_recorder().events()
                if e["event"] == "compile" and e.get("seconds") == 2.5]


def test_timed_records_one_read_sample_per_batch():
    from caffeonspark_tpu.metrics import timed_records
    m = PipelineMetrics()

    def slow():
        for i in range(10):
            time.sleep(0.002)
            yield i

    assert list(timed_records(slow(), m, 4)) == list(range(10))
    read = m.summary()["stages"]["read"]
    assert read["count"] == 2                    # the ragged tail: none
    assert 0.016 <= read["total_s"] < 0.1
    assert list(timed_records(iter(range(3)), None, 4)) == [0, 1, 2]


@pytest.mark.slow
@pytest.mark.bench
def test_bench_ingest_smoke(tmp_path):
    """scripts/bench_ingest.py --quick runs end to end and emits a
    well-formed artifact with per-stage metrics."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "bench.json"
    r = subprocess.run(
        [sys.executable, "scripts/bench_ingest.py", "--quick",
         "--iters", "8", "--repeats", "1", "--cooldown", "0",
         "--hw", "96", "--out", str(out)],
        capture_output=True, text=True, timeout=600, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(out))
    assert rec["bench"] == "ingest_pipeline"
    for mode in ("inline", "pipelined"):
        stages = rec[mode]["metrics"]["stages"]
        for stage in ("queue_wait", "pack", "stage", "step"):
            assert stages[stage]["count"] > 0, (mode, stage)
