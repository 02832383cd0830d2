"""Data sources: the DataSource SPI of the reference re-expressed for a
host→TPU feed pipeline.

Reference: `caffe-grid/.../DataSource.scala:27-128` (SPI: init /
makeRDD / nextBatch / STOP_MARK queue protocol) with concrete sources
LMDB (`LMDB.scala`), SeqImageDataSource (`SeqImageDataSource.scala`),
ImageDataFrame (`ImageDataFrame.scala`), DataFrameSource
(`DataFrameSource.scala`) — all instantiated reflectively from the
prototxt `source_class` field (`DataSource.scala:133-166`).

Here each source yields **record tuples** `(id, label, C, H, W, encoded,
bytes)` — the reference's 7-tuple RDD element — and `next_batch` packs
them through the `Transformer` into the data layer's named blobs, ready
for `jax.device_put`.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..metrics import span_of
from ..proto.caffe import Datum, LayerParameter
from .lmdb_io import LmdbReader
from .sequencefile import SequenceFileReader
from .transformer import AugDraw, DEVICE_AUX_SUFFIX, Transformer

ImageRecord = Tuple[str, float, int, int, int, bool, bytes]

STOP_MARK = object()


def _strip_scheme(uri: str) -> str:
    for scheme in ("file:", "hdfs:"):
        if uri.startswith(scheme):
            uri = uri[len(scheme):]
    return uri


def decode_image(data: bytes, *, channels: int,
                 resize_hw: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """JPEG/PNG bytes → (C, H, W) float32, BGR channel order like OpenCV
    (`jcaffe/Mat.java decode` semantics)."""
    import cv2
    flag = cv2.IMREAD_GRAYSCALE if channels == 1 else cv2.IMREAD_COLOR
    img = cv2.imdecode(np.frombuffer(data, np.uint8), flag)
    if img is None:
        raise ValueError("image decode failed")
    if resize_hw is not None and (img.shape[0], img.shape[1]) != resize_hw:
        img = cv2.resize(img, (resize_hw[1], resize_hw[0]))
    if img.ndim == 2:
        img = img[:, :, None]
    return img.transpose(2, 0, 1).astype(np.float32)


def datum_to_record(key: bytes, raw: bytes) -> ImageRecord:
    """LMDB value (serialized Datum) → 7-tuple record
    (`LmdbRDD.scala:136-151` + CHW ordering :270-281)."""
    d = Datum.from_binary(raw)
    if not d.encoded and not d.has("data") and d.float_data:
        # float-payload Datum (e.g. feature LMDBs): raw float32 planes,
        # not image bytes — pass through as an ndarray payload
        arr = np.asarray(list(d.float_data), np.float32).reshape(
            d.channels, d.height, d.width)
        return (key.decode("latin-1"), float(d.label), d.channels,
                d.height, d.width, False, arr)
    if d.encoded or not d.has("data"):
        data = d.data if d.has("data") else b""
        return (key.decode("latin-1"), float(d.label), d.channels,
                d.height, d.width, True, data)
    return (key.decode("latin-1"), float(d.label), d.channels, d.height,
            d.width, False, d.data)


class DataSource:
    """SPI base: concrete sources implement `records()`."""

    def __init__(self, layer: LayerParameter, *, phase_train: bool,
                 rank: int = 0, num_ranks: int = 1, seed: int = 0,
                 resize: bool = False, num_threads: int = 0):
        self.layer = layer
        self.phase_train = phase_train
        self.rank = rank
        self.num_ranks = num_ranks
        self.seed = seed
        self.resize = resize
        # threads a native call spreads a batch over; 0 = every core,
        # until a pool gives its workers their share (tune_decode_threads)
        self.num_threads = num_threads
        self.batch_size = self._batch_size()
        self.transformer = Transformer(
            layer.transform_param if layer.has("transform_param") else None,
            phase_train=phase_train, seed=seed + rank,
            mean_dir=os.path.dirname(self.source_uri()) or None)
        self._device_transform = False
        self._device_fns = None
        # the job's PipelineMetrics, handed over by a trainer: next_batch
        # then reports pack_decode / pack_transform (None: no report)
        self.metrics = None

    # -- config ------------------------------------------------------------
    def _batch_size(self) -> int:
        if self.layer.has("memory_data_param"):
            return int(self.layer.memory_data_param.batch_size)
        if self.layer.has("cos_data_param"):
            return int(self.layer.cos_data_param.batch_size)
        raise ValueError("data layer has no batch size")

    def source_uri(self) -> str:
        if self.layer.has("memory_data_param"):
            return _strip_scheme(self.layer.memory_data_param.source)
        if self.layer.has("cos_data_param"):
            return _strip_scheme(self.layer.cos_data_param.source)
        return ""

    def image_dims(self) -> Tuple[int, int, int]:
        p = self.layer.memory_data_param
        return int(p.channels), int(p.height), int(p.width)

    # -- SPI ---------------------------------------------------------------
    def records(self) -> Iterator[ImageRecord]:
        raise NotImplementedError

    def record_partitions(self, n: int) -> List[Any]:
        """Opaque partition descriptors for sharded reads (rank i of n)."""
        return list(range(n))

    def next_batch(self, records: Sequence[ImageRecord],
                   draw: Optional[AugDraw] = None
                   ) -> Dict[str, np.ndarray]:
        """Pack + transform records into the data layer's blobs
        (ImageDataSource.nextBatch analog, `ImageDataSource.scala:99-163`).
        `draw` replays a pre-drawn augmentation (TransformerPool's
        ordered-draw protocol) instead of consuming the RNG here.

        Which way a batch is packed follows from what its records
        hold.  Every record carrying uint8 pixels (all encoded, or all
        raw `bytes`/uint8 payloads at the layer's geometry): the pixels
        stay uint8 (`pack_decode`) until one native pass writes the
        float32 batch (`pack_transform`; counter `pack_fused`).  Float
        payloads, mixed batches, a corrupt image, a mean the kernel
        does not do, no native library: float32 `data`, then
        `Transformer.__call__` (counter `pack_general`), which defines
        the values of both.  The counters count batches by the way they
        took, a batch that then fails included."""
        c, h, w = self.image_dims()
        labels = np.asarray([r[1] for r in records], np.float32)
        m = self.metrics
        if m is not None:
            m.gauge("pack_threads", self.num_threads)
        with span_of(m, "pack_decode"):
            pixels = self._records_to_pixels(records, c, h, w)
            if m is not None and not self._device_transform:
                m.incr("pack_general" if pixels is None else "pack_fused")
            data = (self._records_to_data(records, c, h, w)
                    if pixels is None else None)
        out_names = list(self.layer.top)
        if pixels is not None:
            with span_of(m, "pack_transform"):
                batch = {out_names[0]: self.transformer.fused(
                    pixels, (c, h, w), draw, self.num_threads)}
        # device-transform split: ships uint8 + per-sample crop/flip aux.
        # Requires pixel payloads (encoded image or uint8 buffer) — a
        # float payload can't be losslessly narrowed, and a silent
        # per-batch fallback would emit inconsistent key sets that
        # combine_batches/iter_size would mis-merge, so fail fast.
        elif self._device_transform:
            bad = next((r for r in records
                        if not r[5] and isinstance(r[6], np.ndarray)
                        and r[6].dtype != np.uint8), None)
            if bad is not None:
                raise ValueError(
                    f"COS_DEVICE_TRANSFORM=1 needs uint8/encoded pixel "
                    f"payloads, but record {bad[0]!r} carries "
                    f"{bad[6].dtype} data — unset COS_DEVICE_TRANSFORM "
                    "for float-valued sources")
            with span_of(m, "pack_transform"):
                u8, aux = self.transformer.host_stage(data, draw=draw)
            batch = {out_names[0]: u8,
                     out_names[0] + DEVICE_AUX_SUFFIX: aux}
        else:
            with span_of(m, "pack_transform"):
                batch = {out_names[0]: self.transformer(data, draw=draw)}
        if len(out_names) > 1:
            batch[out_names[1]] = labels
        return batch

    # -- transformer-pool protocol ------------------------------------
    def pack_batch(self, records: Sequence[ImageRecord],
                   draw: Optional[AugDraw] = None
                   ) -> Dict[str, np.ndarray]:
        """next_batch with an optional ordered pre-draw — the callable
        TransformerPool workers run.  Sources that override next_batch
        (HDF5/DataFrame blob packing) never get a draw (make_draw_fn
        returns None for them), so their signature stays untouched."""
        if draw is None:
            return self.next_batch(records)
        return self.next_batch(records, draw=draw)

    def make_draw_fn(self):
        """Per-batch augmentation pre-draw `fn(n) -> AugDraw` for the
        pool dispatcher, consuming the transformer RNG in FEED ORDER on
        one thread so `num_threads > 1` packing reproduces the inline
        path's augmentation stream.  None when this source packs its
        own blobs or has no static image geometry — those pack without
        a pre-draw (transformer draws under its own lock)."""
        if type(self).next_batch is not DataSource.next_batch:
            return None
        try:
            c, h, w = self.image_dims()
        except Exception:       # noqa: BLE001 — geometry-less source
            return None
        t = self.transformer
        return lambda n: t.draw(n, h, w)

    def enable_device_transform(self, net_dtype=None):
        """Opt in to the uint8-infeed transform split: when
        COS_DEVICE_TRANSFORM=1 and this source supports it, next_batch
        emits uint8 pixels + aux offsets and the returned {top: jit-able
        fn} runs mean/scale on the device (Transformer.device_stage_fn).
        The whole policy lives here — env gate, out-dtype rule (bf16
        nets get device-side cast, f32 nets stay f32), and the
        host-path fallbacks: returns None for sources that override
        next_batch with their own blob packing (HDF5/DataFrame), have
        no image geometry, or use an unsupported mean shape."""
        import os
        if os.environ.get("COS_DEVICE_TRANSFORM") != "1":
            return None
        if type(self).next_batch is not DataSource.next_batch:
            return None
        try:
            c, h, w = self.image_dims()
        except (NotImplementedError, ValueError):
            return None
        if not self.transformer.device_eligible(h, w):
            return None
        import jax
        import jax.numpy as jnp
        out_dtype = None if net_dtype in (None, jnp.float32) else net_dtype
        self._device_transform = True
        fns = {self.layer.top[0]:
               self.transformer.device_stage_fn(out_dtype)}
        # jitted copies for direct consumers (apply_device_stage);
        # device_prefetch jits the raw fns itself
        self._device_fns = {k: jax.jit(f) for k, f in fns.items()}
        return fns

    def apply_device_stage(self, batch, shardings=None):
        """Finish the split for consumers that call next_batch directly
        (validation rounds, feature extraction) instead of feeding
        through device_prefetch: run the jitted device stage on any
        uint8+aux tops.  `shardings` ({top: NamedSharding}) places the
        uint8/aux arrays BEFORE the stage so the output matches a
        sharded step's in_shardings.  No-op when the split is off."""
        if not self._device_transform \
                or not getattr(self, "_device_fns", None):
            return batch
        import jax
        out = dict(batch)
        for k, f in self._device_fns.items():
            aux = out.pop(k + DEVICE_AUX_SUFFIX, None)
            if aux is None:
                continue
            v = out[k]
            if shardings is not None and k in shardings:
                sh = shardings[k]
                if jax.process_count() > 1:
                    # multi-host: assemble the global array from this
                    # process's local shard (device_put can't target
                    # non-addressable devices) — same rule as
                    # queue_runner.device_prefetch's put_one
                    v = jax.make_array_from_process_local_data(sh, v)
                    aux = jax.make_array_from_process_local_data(sh, aux)
                else:
                    v = jax.device_put(v, sh)
                    aux = jax.device_put(aux, sh)
            out[k] = f(v, aux)
        return out

    def _records_to_pixels(self, records, c, h, w):
        """The records' pixels for `Transformer.fused`, or None where
        the batch takes `_records_to_data` + `Transformer.__call__`
        (`next_batch` has the rule).  Encoded records: one uint8
        (n, c, h, w) array — float32 if an image is of another size and
        was resampled, whose fractions a uint8 store would drop.  Raw
        records: the payloads themselves, which the kernel reads in
        place."""
        if (self._device_transform or not records
                or not self.transformer.fusable(c, h, w)):
            return None
        if all(r[5] for r in records):
            from .. import native
            jpegs = [r[6] for r in records]
            kw = dict(channels=c, out_h=h, out_w=w,
                      num_threads=self.num_threads)
            try:
                px = native.decode_batch(jpegs, out_dtype=np.uint8,
                                         exact=True, **kw)
                return px if px is not None else \
                    native.decode_batch(jpegs, **kw)
            except ValueError:
                return None  # corrupt image: per-image path reports it
        size = c * h * w

        def raw_u8(r):
            p = r[6]
            return not r[5] and tuple(r[2:5]) == (c, h, w) and (
                len(p) == size if isinstance(p, bytes) else
                isinstance(p, np.ndarray) and p.dtype == np.uint8
                and p.size == size)
        return ([r[6] for r in records] if all(map(raw_u8, records))
                else None)

    def _records_to_data(self, records, c, h, w) -> np.ndarray:
        """The records' pixels as one float32 (n, c, h, w) array (uint8
        under the device-transform split), undecoded payloads decoded:
        the general path's `pack_decode`."""
        if all(r[5] for r in records):
            return self._decode_encoded_batch(records, c, h, w)
        data = np.zeros((len(records), c, h, w), np.float32)
        for i, (rid, label, rc, rh, rw, encoded, payload) in \
                enumerate(records):
            if encoded:
                data[i] = decode_image(
                    payload, channels=c,
                    resize_hw=(h, w) if (self.resize
                                         or (rh, rw) != (h, w))
                    else None)
            else:
                if (rh, rw) != (h, w):
                    raise ValueError(
                        f"record {rid}: {rh}x{rw} != layer {h}x{w} "
                        "(set -resize for encoded sources)")
                if isinstance(payload, np.ndarray):
                    data[i] = payload.reshape(rc, rh, rw)
                else:
                    data[i] = np.frombuffer(payload, np.uint8).astype(
                        np.float32).reshape(rc, rh, rw)
        return data

    def _decode_encoded_batch(self, records, c, h, w) -> np.ndarray:
        from .. import native
        # under the device-transform split the native decoder writes
        # uint8 planes directly — no float buffer, no host cast pass
        dt = np.uint8 if self._device_transform else np.float32
        if native.available():
            try:
                return native.decode_batch(
                    [r[6] for r in records], channels=c, out_h=h,
                    out_w=w, num_threads=self.num_threads,
                    out_dtype=dt)
            except ValueError:
                pass  # corrupt image somewhere: per-image path reports it
        n = len(records)
        data = np.zeros((n, c, h, w), np.float32)
        for i, r in enumerate(records):
            data[i] = decode_image(r[6], channels=c, resize_hw=(h, w))
        return data

    SHUFFLE_BUFFER = 4096

    def epoch_seed(self, epoch: int) -> int:
        """Deterministic per-(seed, rank, epoch) shuffle seed — shared
        by the streaming shuffle and the -persistent cache reshuffle so
        both modes see the same epoch orders."""
        return (self.seed + self.rank * 9973
                + epoch * 131071) & 0x7FFFFFFF

    def shuffled_records(self, epoch: int) -> Iterator[ImageRecord]:
        """Streaming shuffle over records(): a bounded reservoir buffer
        (capacity SHUFFLE_BUFFER) emits a random resident element as
        each new record arrives — order varies per epoch and per rank
        but is fully determined by (seed, rank, epoch).  The reference
        gets its shuffling from randomized LMDB keys + Spark partition
        order; a streaming buffer is the TPU-feed equivalent."""
        rng = np.random.RandomState(self.epoch_seed(epoch))
        buf: List[ImageRecord] = []
        for rec in self.records():
            if len(buf) < self.SHUFFLE_BUFFER:
                buf.append(rec)
                continue
            j = rng.randint(0, len(buf))
            out, buf[j] = buf[j], rec
            yield out
        rng.shuffle(buf)
        yield from buf

    def batches(self, *, loop: bool = True,
                shuffle: Optional[bool] = None
                ) -> Iterator[Dict[str, np.ndarray]]:
        """Convenience: records → transformed batches, epoch-looping.
        Shuffles by default in the TRAIN phase."""
        if shuffle is None:
            shuffle = self.phase_train
        buf: List[ImageRecord] = []
        epoch = 0
        while True:
            got_any = False
            records = (self.shuffled_records(epoch) if shuffle
                       else self.records())
            for rec in records:
                got_any = True
                buf.append(rec)
                if len(buf) == self.batch_size:
                    yield self.next_batch(buf)
                    buf = []
            if not got_any:
                return
            if not loop:
                if buf:
                    yield self.next_batch(buf)
                return
            epoch += 1


class _DBSource(DataSource):
    """Shared rank-sharded read loop for key-value databases of Datum
    records; subclasses provide `_reader()`."""

    def _reader(self):
        raise NotImplementedError

    def records(self) -> Iterator[ImageRecord]:
        with self._reader() as r:
            ranges = r.partition_ranges(self.num_ranks)
            lo, hi = ranges[self.rank % len(ranges)]
            for k, v in r.items(lo, hi):
                yield datum_to_record(k, v)


class LMDB(_DBSource):
    """LMDB of Caffe Datum records (source_class com.yahoo.ml.caffe.LMDB)."""

    def _reader(self):
        return LmdbReader(self.source_uri())


class CaffeDataSource(_DBSource):
    """Caffe's own `Data` layer (`data_param { source backend }`):
    LMDB or LEVELDB databases of serialized Datum records — the
    db_lmdb.cpp / db_leveldb.cpp pair.  Geometry comes from the first
    record (Caffe infers shapes from the database the same way)."""

    def _batch_size(self) -> int:
        return int(self.layer.data_param.batch_size)

    def source_uri(self) -> str:
        return _strip_scheme(self.layer.data_param.source)

    def _reader(self):
        from ..proto.caffe import DBBackend
        if self.layer.data_param.backend == DBBackend.LEVELDB:
            from .leveldb_io import LevelDBReader
            return LevelDBReader(self.source_uri())
        return LmdbReader(self.source_uri())

    def image_dims(self) -> Tuple[int, int, int]:
        dims = getattr(self, "_dims", None)
        if dims is None:
            with self._reader() as r:
                for k, v in r.items(None, None):
                    d = Datum.from_binary(v)
                    dims = (int(d.channels), int(d.height),
                            int(d.width))
                    break
            if dims is None:
                raise ValueError(
                    f"{self.source_uri()!r}: empty database")
            self._dims = dims
        return dims


class SeqImageDataSource(DataSource):
    """SequenceFile of (id, Datum) records
    (source_class com.yahoo.ml.caffe.SeqImageDataSource)."""

    def records(self) -> Iterator[ImageRecord]:
        path = self.source_uri()
        files = sorted(
            os.path.join(path, f) for f in os.listdir(path)
            if not f.startswith((".", "_"))) if os.path.isdir(path) \
            else [path]
        for i, f in enumerate(files):
            if i % self.num_ranks != self.rank and len(files) > 1:
                continue
            for key, val in SequenceFileReader(f):
                yield datum_to_record(key.encode("latin-1"), val)


class ImageDataFrame(DataSource):
    """Parquet DataFrame of images (source_class
    com.yahoo.ml.caffe.ImageDataFrame): optional columns id/label/
    channels/height/width/encoded + data (ImageDataFrame.scala:31-73)."""

    def records(self) -> Iterator[ImageRecord]:
        import pyarrow.parquet as pq
        c, h, w = self.image_dims()
        encoded_default = self.layer.memory_data_param.image_encoded
        table = pq.read_table(self.source_uri())
        cols = set(table.column_names)
        sel = list(self.layer.memory_data_param.dataframe_column_select)
        n = table.num_rows
        lo = self.rank * n // self.num_ranks
        hi = (self.rank + 1) * n // self.num_ranks
        tbl = table.slice(lo, hi - lo).to_pydict()
        for i in range(hi - lo):
            def col(name, default):
                return tbl[name][i] if name in cols else default
            data = col("data", b"") or b""
            if isinstance(data, list):
                data = bytes(data)
            yield (str(col("id", i)), float(col("label", 0.0) or 0.0),
                   int(col("channels", c)), int(col("height", h)),
                   int(col("width", w)),
                   bool(col("encoded", encoded_default)), data)


class ImageListSource(DataSource):
    """Caffe's ImageData layer (image_data_layer.cpp): a text list of
    `<path> <label>` lines, images loaded from disk (optionally under
    root_folder), resized to new_height x new_width.  rand_skip and
    shuffle follow the Caffe fields; rank striping shards the list."""

    def __init__(self, layer: LayerParameter, **kw):
        # Caffe's ImageData always resizes to new_height/new_width
        kw["resize"] = True
        super().__init__(layer, **kw)
        self._epoch = 0

    def _batch_size(self) -> int:
        return int(self.layer.image_data_param.batch_size)

    def source_uri(self) -> str:
        return _strip_scheme(self.layer.image_data_param.source)

    def image_dims(self) -> Tuple[int, int, int]:
        p = self.layer.image_data_param
        c = 3 if p.is_color else 1
        h, w = int(p.new_height), int(p.new_width)
        if not h or not w:
            cs = int(self.layer.transform_param.crop_size or 0)
            h = h or cs
            w = w or cs
        return c, h, w

    def _entries(self) -> List[Tuple[str, float]]:
        p = self.layer.image_data_param
        root = p.root_folder or ""
        out = []
        with open(self.source_uri()) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                path, _, lbl = ln.rpartition(" ")
                if not path:      # no label column
                    path, lbl = lbl, "0"
                out.append((os.path.join(root, path), float(lbl)))
        return out

    def records(self) -> Iterator[ImageRecord]:
        """Caffe image_data_layer.cpp order: shuffle first (fresh
        permutation every epoch — ShuffleImages() on each wrap), then
        rand_skip once at startup only."""
        c, h, w = self.image_dims()
        p = self.layer.image_data_param
        entries = self._entries()
        epoch, self._epoch = self._epoch, self._epoch + 1
        if p.shuffle:
            # rank-INdependent seed: every rank must apply the same
            # permutation so the i % num_ranks striping below still
            # partitions the list disjointly
            seed = (self.seed + epoch * 131071) & 0x7FFFFFFF
            np.random.RandomState(seed).shuffle(entries)
        if int(p.rand_skip) and epoch == 0:
            skip = np.random.RandomState(self.seed).randint(
                0, int(p.rand_skip))
            entries = entries[skip:] + entries[:skip]
        for i, (path, lbl) in enumerate(entries):
            if i % self.num_ranks != self.rank:
                continue
            with open(path, "rb") as f:
                yield (os.path.basename(path), lbl, c, h, w, True,
                       f.read())


_CLASS_MAP = {
    "com.yahoo.ml.caffe.LMDB": LMDB,
    "com.yahoo.ml.caffe.SeqImageDataSource": SeqImageDataSource,
    "com.yahoo.ml.caffe.ImageDataFrame": ImageDataFrame,
    "LMDB": LMDB,
    "SeqImageDataSource": SeqImageDataSource,
    "ImageDataFrame": ImageDataFrame,
}


def get_source(layer: LayerParameter, **kw) -> DataSource:
    """Reflective factory keyed on prototxt `source_class`
    (DataSource.scala:130-167 analog)."""
    if layer.type == "HDF5Data":
        # Caffe layer type with no CoS source_class: route directly
        from .hdf5 import HDF5Source
        return HDF5Source(layer, **kw)
    if layer.type == "ImageData":
        return ImageListSource(layer, **kw)
    if layer.type == "Data" and not layer.source_class:
        # source_class-less Data layer: Caffe's own LMDB/LevelDB path;
        # WITH a source_class the CoS dispatch below takes precedence
        return CaffeDataSource(layer, **kw)
    cls_name = layer.source_class
    if not cls_name:
        raise ValueError(f"data layer {layer.name!r} has no source_class")
    if cls_name in _CLASS_MAP:
        return _CLASS_MAP[cls_name](layer, **kw)
    if cls_name == "com.yahoo.ml.caffe.DataFrameSource" \
            or cls_name.endswith("DataFrameSource"):
        from .dataframe import DataFrameSource
        return DataFrameSource(layer, **kw)
    if cls_name in ("StreamingDir", "com.yahoo.ml.caffe.StreamingDir"):
        # growing part-directory stream (continuous deployment,
        # data/streaming.py) — lazy import keeps the common sources
        # free of the deploy machinery
        from .streaming import StreamingDirSource
        return StreamingDirSource(layer, **kw)
    # user-provided "module:Class" extension point
    if ":" in cls_name:
        import importlib
        mod, cls = cls_name.rsplit(":", 1)
        return getattr(importlib.import_module(mod), cls)(layer, **kw)
    raise ValueError(f"unknown source_class {cls_name!r}")


def register_source(name: str, cls) -> None:
    _CLASS_MAP[name] = cls
