"""Bounded-queue feed runtime: the QueuePair / backpressure protocol of
the reference executor, re-expressed for a host→TPU pipeline.

Reference semantics preserved (SURVEY §2.2):
  * bounded source queue, capacity 1024 (`DataSource.scala:67-76`);
  * STOP_MARK sentinel ends an epoch (`CaffeProcessor.scala:205`);
  * `feedQueue` spins `offer` until the solver completes — device→task
    backpressure (`CaffeProcessor.scala:192-198`);
  * transformer threads decode/augment while the device computes
    (`transform_thread_per_device`, `CaffeProcessor.scala:54-55`) —
    here `TransformerPool`, an ORDERED multi-threaded pack pool;
  * double-buffered transformer→solver handoff (QueuePair depth 2,
    `CaffeProcessor.scala:32-35`) — here `device_prefetch`, optionally
    with a background stager thread so the H2D transfer and the jitted
    device-transform dispatch also leave the solver thread.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import queue
import threading
import time
from typing import Callable, Dict, Iterator, Optional

import jax
import numpy as np

from ..metrics import span_of, timed_records
from .source import STOP_MARK

_LOG = logging.getLogger(__name__)

SOURCE_QUEUE_CAPACITY = 1024

# consecutive pack failures that abort the pipeline (systematic
# data/config error) — one constant for both the standalone pool's
# default policy and CaffeProcessor.MAX_CONSECUTIVE_DROPS
DROP_LIMIT_DEFAULT = 20

# ordered-slot marker for a batch the pool dropped after a pack error
# (corrupt record): the slot still advances the sequence so validation
# rounds can count it, train consumers skip it
DROPPED = object()

_END = object()          # worker/stager shutdown sentinel


def transform_threads(default: int = 2) -> int:
    """Transformer-pool width per processor (COS_TRANSFORM_THREADS;
    0 = inline legacy path: pack on the solver thread)."""
    try:
        return max(0, int(os.environ.get("COS_TRANSFORM_THREADS",
                                         str(default))))
    except ValueError:
        return default


def steps_per_loop(default: int = 1) -> int:
    """Fused multi-step chunk size K (COS_STEPS_PER_LOOP; 1 = legacy
    per-step dispatch).  K solver iterations compile into one XLA
    program (Solver.build_train_step_many) fed by a stacked (K, batch…)
    block, amortizing the host→device dispatch round-trip — the
    SparkNet/FireCaffe iterations-per-loop lever."""
    try:
        return max(1, int(os.environ.get("COS_STEPS_PER_LOOP",
                                         str(default))))
    except ValueError:
        return default


def stage_depth(default: int = 2) -> int:
    """Background-stager handoff depth (COS_STAGE_DEPTH)."""
    try:
        return max(1, int(os.environ.get("COS_STAGE_DEPTH",
                                         str(default))))
    except ValueError:
        return default


def stage_background(default: Optional[bool] = None) -> bool:
    """Run the device stager on its own thread?  Default: only on
    accelerator backends, where H2D rides a DMA engine and host cores
    are free to run the stager.  On the CPU backend every device op
    (device_put included) funnels through jax's single async dispatch
    executor, so a stager thread adds scheduler/handoff latency without
    adding bandwidth — staging stays on the consumer thread there.
    COS_STAGE_BG=0/1 overrides."""
    env = os.environ.get("COS_STAGE_BG")
    if env is not None:
        return env.lower() not in ("0", "", "false", "no")
    if default is not None:
        return default
    return jax.default_backend() != "cpu"


# cores a pool leaves alone: the solver thread's and the stager's
RESERVED_CORES = 2
_CGROUP_CPU_MAX = "/sys/fs/cgroup/cpu.max"


def usable_cores() -> int:
    """CPUs this process may run on, as far as it can observe: its
    affinity mask (`os.cpu_count()` where the platform has none), cut
    by a cgroup-v2 CPU quota (`cpu.max`: "quota period", whole CPUs of
    it) where one is set."""
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        cores = os.cpu_count() or 1
    try:
        with open(_CGROUP_CPU_MAX) as f:
            quota, period = f.read().split()[:2]
        if quota != "max":
            cores = min(cores, int(quota) // int(period))
    except (OSError, ValueError, ZeroDivisionError):
        pass
    return max(1, cores)


def pack_thread_share(cores: int, pool_width: int,
                      local_procs: int = 1) -> int:
    """Threads one pool worker's native calls run on: the process's
    part of `cores` (`local_procs` processes of the job share the
    host), less RESERVED_CORES, split over the pool's workers; never
    under 1."""
    mine = cores // local_procs
    return max(1, (mine - RESERVED_CORES) // pool_width)


def tune_decode_threads(src, pool_width: int, local_procs: int = 1):
    """Give a pooled source its share of the host's cores.  A pool
    parallelises across batches (`pool_width` workers, a whole batch
    each) and every native call parallelises inside one (`num_threads`
    over the batch's images).  At `num_threads` 0, "nobody chose", each
    call would take every core, and N workers doing that oversubscribe
    the host (measured 2.6x slower packs on a 2-core box).  So each
    worker gets `pack_thread_share` of what `usable_cores` sees: 5 of a
    13-core host's under a pool of 2, and still 1 on the 2-core box,
    which runs on the calling thread.  A caller that set `num_threads`
    keeps it (Spark's source spec passes its own).  Every pooled source
    comes through here; a validation pool's gets one train worker's
    share.  The thread count cannot change a value: images are
    independent and the draw is made before the pack."""
    if getattr(src, "num_threads", None) == 0:
        src.num_threads = pack_thread_share(usable_cores(), pool_width,
                                            local_procs)


class FeedQueue:
    """Bounded record queue with STOP_MARK epoch protocol.  Handed the
    job's PipelineMetrics (`metrics`, with `batch_size` for the span's
    ordinal), a feeder that really has to wait in a blocking offer()
    reports the episode as `read_blocked`."""

    def __init__(self, capacity: int = SOURCE_QUEUE_CAPACITY):
        self._q: queue.Queue = queue.Queue(maxsize=capacity)
        self._stopped = False
        self.metrics = None
        self.batch_size = 1
        self._offered = 0

    def offer(self, item, timeout: Optional[float] = None) -> bool:
        """Put with backpressure; returns False if stopped or the
        deadline expires.  timeout=None blocks until space (polling in
        short slices so stop() stays responsive); a numeric timeout is
        a real deadline for the WHOLE call — including timeout=0, a
        single non-blocking attempt."""
        if self._stopped:
            return False
        if timeout is not None:
            ok = self._offer_until(item, time.monotonic() + timeout)
        else:
            try:
                self._q.put_nowait(item)
                ok = True
            except queue.Full:
                with span_of(self.metrics, "read_blocked",
                             n=self._offered // self.batch_size):
                    ok = self._offer_until(item, None)
        self._offered += ok
        return ok

    def _offer_until(self, item, deadline: Optional[float]) -> bool:
        while True:
            if self._stopped:
                return False
            if deadline is None:
                wait = 0.1
            else:
                wait = deadline - time.monotonic()
                if wait <= 0:
                    try:
                        self._q.put_nowait(item)
                        return True
                    except queue.Full:
                        return False
                wait = min(0.1, wait)
            try:
                self._q.put(item, timeout=wait)
                return True
            except queue.Full:
                continue

    def reset(self):
        """Re-arm a stopped queue (processor restart) and drop leftovers."""
        self._stopped = False
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                return

    def mark_epoch_end(self):
        self.offer(STOP_MARK)

    def take(self, timeout: Optional[float] = None):
        """Blocking get; a numeric timeout (INCLUDING 0) raises
        queue.Empty on expiry instead of falling into the forever-
        blocking branch."""
        if timeout is None:
            return self._q.get()
        return self._q.get(timeout=timeout)

    def stop(self):
        self._stopped = True
        try:                     # wake a consumer blocked in take()
            self._q.put_nowait(STOP_MARK)
        except queue.Full:
            pass

    @property
    def stopped(self) -> bool:
        return self._stopped

    def __len__(self):
        return self._q.qsize()


class TransformerPool:
    """Ordered multi-threaded decode/augment/pack pool — the
    transform_thread_per_device analog (`CaffeProcessor.scala:54-55`)
    that takes host transform work off the solver thread.

    One dispatcher thread drains `feed`, groups records into
    batch-sized buffers (STOP_MARK drops the ragged epoch tail, a
    `None` record terminates the pool), pre-draws the per-batch
    augmentation via `draw_fn` IN FEED ORDER (so on clean data the
    pool reproduces the inline path's RNG stream exactly), and hands
    (seq, buffer, draw) to `num_threads` workers calling
    `pack(buffer, draw)`.  Output is re-sequenced: `take()`/iteration
    yields batches in feed order regardless of worker scheduling, with
    exactly one terminal condition per pool.  The pre-draw happens at
    dispatch, so a batch whose pack later FAILS has still consumed the
    RNG — on dirty data the pooled stream diverges from the inline
    path after the first drop (deliberate: drawing after decode would
    serialize the workers, and the reference's per-thread transformer
    RNGs never had cross-path parity at all).

    Pack failures follow the reference's per-iteration tolerance: the
    slot becomes DROPPED (skipped by train consumers, countable by
    validation), drop accounting is thread-safe, and `drop_limit`
    consecutive failures abort the pipeline (the error re-raises from
    `take()`).  `on_pack_ok`/`on_pack_error` externalize the counters
    (CaffeProcessor shares one counter across train + validation);
    an `on_pack_error` that raises aborts the pool the same way.
    """

    def __init__(self, feed: FeedQueue, batch_size: int,
                 pack: Callable, *, num_threads: int = 2,
                 draw_fn: Optional[Callable] = None,
                 on_pack_ok: Optional[Callable] = None,
                 on_pack_error: Optional[Callable] = None,
                 drop_limit: int = DROP_LIMIT_DEFAULT,
                 depth: Optional[int] = None,
                 metrics=None,
                 should_stop: Optional[Callable[[], bool]] = None):
        self.feed = feed
        self.batch_size = int(batch_size)
        self.pack = pack
        self.num_threads = max(1, int(num_threads))
        self.draw_fn = draw_fn
        self.on_pack_ok = on_pack_ok
        self.on_pack_error = on_pack_error
        self.drop_limit = drop_limit
        self.depth = depth if depth is not None else 2 * self.num_threads
        self.metrics = metrics
        self._ext_stop = should_stop or (lambda: False)
        self._stopped = False
        self._work: queue.Queue = queue.Queue(maxsize=max(1, self.depth))
        # results window: bounded by construction (a worker blocks
        # depositing seq >= next_emit + window), so a stalled consumer
        # backpressures the whole pool instead of growing the dict
        self._window = self.depth + self.num_threads
        self._cond = threading.Condition()
        self._results: Dict[int, object] = {}
        self._next_emit = 0
        self._in_seq: Optional[int] = None   # total batches dispatched
        self._dispatched = 0    # so far (dispatcher writes, spans read)
        self._error: Optional[BaseException] = None
        self._consecutive = 0
        self.drops = 0
        self._threads: list = []
        self._started = False

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "TransformerPool":
        assert not self._started, "pool already started"
        self._started = True
        d = threading.Thread(target=self._dispatch, daemon=True,
                             name="cos-xform-dispatch")
        self._threads.append(d)
        for i in range(self.num_threads):
            t = threading.Thread(target=self._worker, args=(i,),
                                 daemon=True, name=f"cos-xform-{i}")
            self._threads.append(t)
        for t in self._threads:
            t.start()
        return self

    def stop(self, join_timeout: Optional[float] = None):
        """Flag every pool thread down; optionally reap them."""
        self._stopped = True
        with self._cond:
            self._cond.notify_all()
        if join_timeout is not None:
            self.join(timeout=join_timeout)

    def join(self, timeout: Optional[float] = None):
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        for t in self._threads:
            t.join(timeout=None if deadline is None
                   else max(0.0, deadline - time.monotonic()))

    def _should_stop(self) -> bool:
        # an abort (_error) halts the whole pipeline too: without it
        # the dispatcher would keep draining records and workers would
        # keep decoding doomed batches until the consumer reaches its
        # teardown
        return (self._stopped or self._error is not None
                or self._ext_stop())

    def _fail(self, exc: BaseException):
        with self._cond:
            if self._error is None:
                self._error = exc
            self._cond.notify_all()

    # -- dispatcher: feed order, epoch boundaries, ordered draws --------
    def _dispatch(self):
        buf: list = []
        seq = 0
        try:
            while not self._should_stop():
                item = self._next_record()
                if item is None or item is _END:
                    break               # terminal sentinel / winding down
                if item is STOP_MARK:
                    # epoch boundary: drop the ragged tail
                    if buf and self.metrics is not None:
                        self.metrics.incr("ragged_tail_records",
                                          len(buf))
                    buf = []
                    if self.feed.stopped:
                        break           # stop()-wake, not an epoch
                    continue
                buf.append(item)
                if len(buf) == self.batch_size:
                    draw = (self.draw_fn(len(buf))
                            if self.draw_fn is not None else None)
                    if not self._put_work((seq, buf, draw)):
                        return
                    seq += 1
                    self._dispatched = seq
                    buf = []
        except BaseException as e:      # noqa: BLE001 — surfaced on take()
            self._fail(e)
        finally:
            with self._cond:
                self._in_seq = seq
                self._cond.notify_all()
            for _ in range(self.num_threads):
                self._put_work(_END, force=True)

    def _next_record(self):
        """One item off the feed; _END when the pool winds down.  The
        wait for the feeder, when there is one, is `group_starved`."""
        try:
            return self.feed.take(timeout=0)
        except queue.Empty:
            pass
        with span_of(self.metrics, "group_starved", n=self._dispatched):
            while not self._should_stop():
                try:
                    return self.feed.take(timeout=0.2)
                except queue.Empty:
                    if self.feed.stopped:
                        break
        return _END

    def _put_work(self, item, force: bool = False) -> bool:
        if not force and self._should_stop():
            return False
        try:
            self._work.put_nowait(item)
            return True
        except queue.Full:
            pass
        with span_of(self.metrics, "group_blocked", n=self._dispatched):
            while True:
                if not force and self._should_stop():
                    return False
                try:
                    self._work.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    if force and self._should_stop():
                        # workers are exiting on their own stop checks;
                        # don't spin on a full queue forever
                        return False

    # -- workers: pack + thread-safe drop accounting --------------------
    def _record_ok(self):
        cb = self.on_pack_ok
        if cb is not None:
            cb()
            return
        with self._cond:
            self._consecutive = 0

    def _record_drop(self, exc: Exception):
        with self._cond:
            self.drops += 1
        cb = self.on_pack_error
        if cb is not None:
            cb(exc)                     # may raise to abort the pool
            return
        if self.metrics is not None:
            self.metrics.incr("dropped_batches")
        _LOG.warning("dropping batch after record error: %s", exc)
        with self._cond:
            self._consecutive += 1
            n = self._consecutive
        if n >= self.drop_limit:
            raise RuntimeError(
                f"{n} consecutive batch failures — systematic "
                f"data/config error; last: {exc}") from exc

    def _next_work(self, w: int):
        """The next (seq, buf, draw), or _END.  With the work queue
        empty every dispatched batch is taken, so the one this worker
        waits for (`pack_starved`) is ordinal `_dispatched`."""
        try:
            return self._work.get_nowait()
        except queue.Empty:
            pass
        with span_of(self.metrics, "pack_starved", n=self._dispatched,
                     w=w):
            while True:
                try:
                    return self._work.get(timeout=0.2)
                except queue.Empty:
                    if self._should_stop():
                        return _END

    def _worker(self, w: int):
        m = self.metrics
        while True:
            item = self._next_work(w)
            if item is _END:
                return
            seq, buf, draw = item
            cpu0 = time.thread_time()
            try:
                with span_of(m, "pack", n=seq, w=w):
                    batch = self.pack(buf, draw)
            except Exception as e:      # pack failure → DROPPED slot
                batch = DROPPED
                try:
                    self._record_drop(e)
                except BaseException as abort:  # noqa: BLE001
                    self._fail(abort)
            else:
                if m is not None:
                    m.add("pack_cpu", time.thread_time() - cpu0)
                try:
                    self._record_ok()
                except BaseException as abort:  # noqa: BLE001
                    self._fail(abort)
            self._deposit(seq, batch, w)

    def _window_full(self, seq: int) -> bool:
        return (self._error is None and not self._should_stop()
                and seq - self._next_emit >= self._window)

    def _deposit(self, seq: int, batch, w: int):
        with self._cond:
            if self._window_full(seq):
                with span_of(self.metrics, "pack_blocked", n=seq, w=w):
                    while self._window_full(seq):
                        self._cond.wait(0.2)
            self._results[seq] = batch
            self._cond.notify_all()

    # -- consumer -------------------------------------------------------
    def take(self, timeout: Optional[float] = None, *,
             skip_dropped: bool = True):
        """Next packed batch in feed order.  Raises queue.Empty when
        `timeout` expires, re-raises a pipeline abort, returns None
        when the input is exhausted or the pool is stopping.  With
        skip_dropped=False a pack-failed slot returns DROPPED (the
        validation round counter needs the slot)."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while True:
                if self._error is not None:
                    raise self._error
                if self._next_emit in self._results:
                    batch = self._results.pop(self._next_emit)
                    self._next_emit += 1
                    self._cond.notify_all()
                    if batch is DROPPED and skip_dropped:
                        continue
                    return batch
                if (self._in_seq is not None
                        and self._next_emit >= self._in_seq):
                    return None          # input exhausted, all emitted
                if self._should_stop():
                    return None
                if deadline is None:
                    wait = 0.2
                else:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        raise queue.Empty
                    wait = min(0.2, wait)
                self._cond.wait(wait)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            batch = self.take()
            if batch is None:
                return
            yield batch


class PipelinedFeed:
    """records → FeedQueue → TransformerPool for generator-based
    callers (mini_cluster): a reader thread streams `src` records into
    a bounded feed queue (one mark_epoch_end per epoch, shuffled at
    TRAIN like DataSource.batches), the pool packs them off-thread.
    Iterate for ordered batches; close() tears the threads down.
    `local_procs`: processes of this job on this host, which share its
    cores (`tune_decode_threads`)."""

    def __init__(self, src, *, loop: bool = True,
                 shuffle: Optional[bool] = None, num_threads: int = 2,
                 metrics=None,
                 should_stop: Optional[Callable[[], bool]] = None,
                 capacity: int = SOURCE_QUEUE_CAPACITY,
                 local_procs: int = 1):
        self._closed = False
        ext = should_stop or (lambda: False)
        self.feed = FeedQueue(capacity)
        self.feed.metrics, self.feed.batch_size = metrics, src.batch_size
        self._reader_error: dict = {}
        do_shuffle = src.phase_train if shuffle is None else shuffle
        tune_decode_threads(src, num_threads, local_procs)

        def read():
            # NOTE: mirrors DataSource.batches()'s record loop (shuffle
            # selection, empty-source guard, epoch counting, loop-True
            # tail carry-over) — the pooled-vs-inline parity tests pin
            # the two together; change them in lockstep.  Divergence is
            # loop=False only: batches() yields the ragged tail as a
            # short batch, the pool (fixed batch shapes) drops it.
            epoch = 0
            try:
                while not self._closed and not ext():
                    got_any = False
                    records = (src.shuffled_records(epoch) if do_shuffle
                               else src.records())
                    for rec in timed_records(records, metrics,
                                             src.batch_size):
                        got_any = True
                        if not self.feed.offer(rec):
                            return
                    if not got_any:
                        return
                    if not loop:
                        # single pass: the ragged tail can't form a
                        # fixed-shape batch — drop it explicitly
                        self.feed.mark_epoch_end()
                        return
                    # looping epochs stream CONTINUOUSLY, matching
                    # DataSource.batches(loop=True): a partial tail
                    # carries into the next epoch's records (no
                    # STOP_MARK — with one, a rank whose shard is
                    # smaller than batch_size would never form a batch
                    # and the consumer would hang)
                    epoch += 1
            except BaseException as e:  # noqa: BLE001 — surfaced below
                self._reader_error["e"] = e
            finally:
                self.feed.offer(None)   # terminal sentinel
                self.feed.stop()

        self.pool = TransformerPool(
            self.feed, src.batch_size,
            pack=src.pack_batch, draw_fn=src.make_draw_fn(),
            num_threads=num_threads, metrics=metrics,
            should_stop=lambda: self._closed or ext())
        self.pool.start()
        self._reader = threading.Thread(target=read, daemon=True,
                                        name="cos-feed-reader")
        self._reader.start()

    def __iter__(self):
        for batch in self.pool:
            yield batch
        err = self._reader_error.get("e")
        if err is not None:
            raise err

    def close(self, join_timeout: Optional[float] = 2.0):
        self._closed = True
        self.feed.stop()
        self.pool.stop(join_timeout=join_timeout)

    def __del__(self):
        # safety net for consumers that abandon iteration without
        # close(): flag the reader/pool threads down so they don't
        # busy-poll for the process lifetime (no join at GC time)
        try:
            self._closed = True
            self.feed.stop()
            self.pool.stop()
        except Exception:               # noqa: BLE001 — interpreter exit
            pass


def combine_batches(batches: Iterator[Dict[str, np.ndarray]], k: int,
                    time_major: frozenset = frozenset()
                    ) -> Iterator[Dict[str, np.ndarray]]:
    """Concatenate k consecutive batches along the batch axis (axis 1
    for time-major keys) — feeds iter_size>1 steps, which consume
    (iter_size·B, ...) per call and split internally
    (solver.train_step_fn)."""
    if k <= 1:
        yield from batches
        return
    buf: list = []
    for b in batches:
        buf.append(b)
        if len(buf) == k:
            yield {key: np.concatenate(
                [x[key] for x in buf],
                axis=1 if key in time_major else 0)
                for key in buf[0]}
            buf = []
    if buf:
        # a short epoch's trailing partial group is discarded by design
        # (static iter_size·B step shapes) — but say so, or it reads as
        # lost data
        _LOG.info(
            "combine_batches: dropping %d trailing sub-batch(es) short "
            "of an iter_size=%d group", len(buf), k)


def chunk_schedule(start_iter: int, max_iter: int, k: int,
                   boundaries=()) -> Iterator[int]:
    """Per-dispatch step counts for the fused multi-step loop: yields
    `k` while the next `k` iterations stay inside every configured
    interval, and falls back to single-step (1) chunks when a boundary
    (`test_interval`, `snapshot`, `display` — zeros are ignored) or
    `max_iter` is closer than `k`.  A chunk may END exactly on a
    boundary (the host-side action runs between dispatches), it never
    spans one — interleaved validation, snapshot cadence and the
    display log keep their exact iterations.

    The schedule is a pure function of (start_iter, config), so a run
    resumed from a snapshot mid-training re-derives the identical
    chunking from the restored iteration.

    Configured-vs-effective visibility: entering a forced-single
    region logs ONCE per boundary (not per chunk)."""
    if k < 1:
        raise ValueError(f"steps-per-loop k must be >= 1, got {k}")
    bset = sorted({int(b) for b in boundaries if b and int(b) > 0})
    it = int(start_iter)
    in_single_run = False
    while max_iter <= 0 or it < max_iter:
        dist = min((b - it % b) for b in bset) if bset else k
        if max_iter > 0:
            dist = min(dist, max_iter - it)
        if dist >= k:
            in_single_run = False
            yield k
            it += k
        else:
            if k > 1 and not in_single_run:
                _LOG.info(
                    "steps_per_loop: boundary at iter %d forces %d "
                    "single-step remainder chunk(s) (configured "
                    "chunk size %d)", it + dist, dist, k)
                in_single_run = True
            yield 1
            it += 1


def stack_chunks(batches: Iterator[Dict[str, np.ndarray]],
                 schedule: Iterator[int], *, metrics=None
                 ) -> Iterator[tuple]:
    """Group per-step batches into `(n, block)` chunks following
    `schedule` (chunk_schedule): n == 1 passes the batch through
    unstacked (the plain-step path), n > 1 stacks n batches along a
    new axis 0 into the (K, batch…) block the fused scan step
    consumes.  `np.stack` copies into a fresh buffer, so chunks are
    immune to the CPU-backend `device_put` host-buffer aliasing
    hazard by construction; single-step chunks keep relying on
    device_prefetch's copy-on-CPU rule.  A stream that ends mid-chunk
    flushes the leftovers as single-step chunks — the single-step
    program is already compiled, odd remainder sizes never are."""
    it = iter(batches)
    for n in schedule:
        if n <= 1:
            try:
                b = next(it)
            except StopIteration:
                return
            yield 1, b
            continue
        buf = []
        for _ in range(n):
            try:
                buf.append(next(it))
            except StopIteration:
                break
        if len(buf) == n:
            with span_of(metrics, "stack"):
                block = {key: np.stack([b[key] for b in buf])
                         for key in buf[0]}
            yield n, block
        else:
            for b in buf:
                yield 1, b
            return


def chunked_feed(batches: Iterator[Dict[str, np.ndarray]], *,
                 start_iter: int, max_iter: int, k: int,
                 boundaries=(), metrics=None) -> Iterator[tuple]:
    """The (n, batch) stream both train loops consume: K > 1 routes
    through chunk_schedule + stack_chunks, K == 1 passes singles
    through — one place for the schedule construction so the
    CaffeProcessor and mini_cluster trainers cannot drift."""
    if k > 1:
        return stack_chunks(
            batches,
            chunk_schedule(start_iter, max_iter, k, boundaries),
            metrics=metrics)
    return ((1, b) for b in batches)


def _resolve_host_copy(host_copy: Optional[bool]) -> bool:
    """Copy numpy buffers before device_put?  On the CPU backend
    jax.device_put ALIASES aligned host buffers (zero-copy) for good,
    so a pooled/reused pack buffer mutated after staging would corrupt
    the staged batch.  On a TPU device_put returns BEFORE the host
    buffer has been read (measured, PR 21: a 40 MB buffer overwritten
    right after the call corrupted the device value 10 times of 10),
    so there the buffer must stay untouched until the transfer is done
    — which every in-repo producer guarantees by allocating per batch
    (pack_batch, np.stack); a caller that reuses buffers sets
    COS_STAGE_COPY=1.  Default: copy on CPU only; COS_STAGE_COPY=0/1
    overrides."""
    if host_copy is not None:
        return bool(host_copy)
    env = os.environ.get("COS_STAGE_COPY")
    if env is not None:
        return env.lower() not in ("0", "", "false", "no")
    return jax.default_backend() == "cpu"


def device_prefetch(batches: Iterator[Dict[str, np.ndarray]], *,
                    depth: int = 2, sharding=None,
                    device_transforms=None, background: bool = False,
                    metrics=None, host_copy: Optional[bool] = None,
                    chunked: bool = False, chunk_sharding=None
                    ) -> Iterator[Dict[str, jax.Array]]:
    """Asynchronously stage `depth` batches onto the device (the
    double-buffered QueuePair analog). jax transfers are async: calling
    device_put for batch N+1 while N computes overlaps H2D with compute.

    `device_transforms` ({top: fn(u8, aux) -> float}, from
    Source.enable_device_transform) finishes the transform split: the
    uint8 pixels + aux offsets cross the host->device link (4x fewer
    bytes than float32) and the jitted mean/scale stage runs on device,
    dispatched right behind the transfer so it overlaps like the
    transfer itself.  Tops without an aux key pass through untouched.

    With `background=True` the staging itself (device_put dispatch +
    jitted transform dispatch) runs on a dedicated stager thread with a
    bounded handoff queue — the H2D path overlaps compute even when the
    upstream producer (host pack) is slow, and the solver thread only
    ever blocks on a ready-batch queue.  Closing the returned generator
    stops the thread.

    `host_copy` (see _resolve_host_copy) defends staged batches against
    pack-buffer reuse on the aliasing CPU backend.

    With `chunked=True` the upstream yields `(n, batch)` pairs
    (stack_chunks): n == 1 batches stage exactly as before under
    `sharding`, n > 1 blocks stage under `chunk_sharding` (the same
    per-step specs with an unsharded leading chunk axis) and their
    device transforms run vmapped over the chunk axis; the generator
    then yields `(n, staged)`.  Stacked blocks are fresh `np.stack`
    copies, so the copy-on-CPU aliasing defense applies only to the
    n == 1 path.

    Multi-host: when the mesh spans processes, each process's batch is
    its LOCAL shard of the global batch (per-device batch semantics —
    'batch sizes in prototxt files are per device'); the global array is
    assembled with make_array_from_process_local_data."""
    from .transformer import DEVICE_AUX_SUFFIX
    multiproc = jax.process_count() > 1
    jitted = {k: jax.jit(fn)
              for k, fn in (device_transforms or {}).items()}
    vjitted = ({k: jax.jit(jax.vmap(fn))
                for k, fn in (device_transforms or {}).items()}
               if chunked else {})
    copy_host = _resolve_host_copy(host_copy)

    def put_one(v, sh):
        if sh is None:
            return jax.device_put(v)
        if multiproc:
            return jax.make_array_from_process_local_data(sh, v)
        return jax.device_put(v, sh)

    def stage_dict(b, sh, fns, copy):
        def sh_for(k):
            if not isinstance(sh, dict):
                return sh
            if k.endswith(DEVICE_AUX_SUFFIX):
                # aux rides its top's batch-dim sharding (P("dp") specs)
                return sh.get(k[:-len(DEVICE_AUX_SUFFIX)])
            return sh[k]  # unknown top = config error: fail fast

        if copy:
            with span_of(metrics, "stage_copy"):
                b = {k: np.array(v, copy=True)
                     if isinstance(v, np.ndarray) else v
                     for k, v in b.items()}
        with span_of(metrics, "stage_put"):
            staged = {k: put_one(v, sh_for(k)) for k, v in b.items()}
            if not fns:
                return staged
            out = {}
            for k, v in staged.items():
                if k.endswith(DEVICE_AUX_SUFFIX):
                    continue
                aux = staged.get(k + DEVICE_AUX_SUFFIX)
                fn = fns.get(k)
                out[k] = fn(v, aux) if (fn is not None
                                        and aux is not None) else v
            return out

    def put(item):
        if not chunked:
            return stage_dict(item, sharding, jitted, copy_host)
        n, b = item
        if n == 1:
            return 1, stage_dict(b, sharding, jitted, copy_host)
        return n, stage_dict(b, chunk_sharding, vjitted, False)

    ordinal = itertools.count()

    def staged_batches():
        """batches -> staged, on whichever thread stages.  Upstream is a
        generator, so `stage_starved` brackets every next() on it, wait
        or not (as `queue_wait` does on the solver thread)."""
        it = iter(batches)
        for n in ordinal:
            try:
                with span_of(metrics, "stage_starved", n=n):
                    b = next(it)
            except StopIteration:
                return
            with span_of(metrics, "stage", n=n):
                staged = put(b)
            yield n, staged

    if background:
        return _background_stage(staged_batches(), depth, metrics)
    return _foreground_stage(staged_batches(), depth)


def _foreground_stage(staged_batches, depth):
    buf = collections.deque()
    for _, staged in staged_batches:
        buf.append(staged)
        if len(buf) > depth:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def _background_stage(staged_batches, depth, metrics):
    outq: queue.Queue = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    state: dict = {}

    def hand_over(staged):
        while not stop.is_set():
            try:
                outq.put(staged, timeout=0.2)
                return
            except queue.Full:
                continue

    def run():
        try:
            for n, staged in staged_batches:
                if metrics is not None:
                    metrics.gauge("stage_depth", outq.qsize())
                try:
                    outq.put_nowait(staged)
                except queue.Full:
                    with span_of(metrics, "stage_blocked", n=n):
                        hand_over(staged)
                if stop.is_set():
                    return
        except BaseException as e:      # noqa: BLE001 — re-raised below
            state["err"] = e
        finally:
            hand_over(_END)

    def gen():
        # lazy start: the thread exists only once the consumer actually
        # iterates — a generator that is built but never driven (early
        # exit between construction and the first next()) must not leak
        # a stager spinning on a full handoff queue
        t = threading.Thread(target=run, daemon=True, name="cos-stager")
        t.start()
        try:
            while True:
                item = outq.get()
                if item is _END:
                    err = state.get("err")
                    if err is not None:
                        raise err
                    return
                yield item
        finally:
            stop.set()

    return gen()
