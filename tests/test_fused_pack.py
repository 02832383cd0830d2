"""The one-pass pack: uint8 pixels -> cropped float32 batch in a single
native pass (`Transformer.fused`), against its definition,
`Transformer.__call__` on float32 `data`, bit for bit; which records
take which way; and the decoder's equal-size branch."""

import itertools

import numpy as np
import pytest

from caffeonspark_tpu import native
from caffeonspark_tpu.data.queue_runner import (DROPPED, FeedQueue,
                                                TransformerPool)
from caffeonspark_tpu.data.source import get_source
from caffeonspark_tpu.metrics import PipelineMetrics
from caffeonspark_tpu.proto.caffe import (BlobProto, BlobShape,
                                          LayerParameter)


@pytest.fixture(scope="module", autouse=True)
def lib():
    if not native.available():
        pytest.skip("native toolchain/libjpeg unavailable")


H, W, CROP, N = 20, 24, 16, 5


def _images(c, n=N, h=H, w=W, seed=0):
    """(n, h, w, c) uint8, smooth enough for JPEG to keep some of."""
    import cv2
    rng = np.random.RandomState(seed)
    return [cv2.GaussianBlur(
        rng.randint(0, 256, (h, w, c)).astype(np.uint8), (5, 5), 0
    ).reshape(h, w, c) for _ in range(n)]


def _jpeg(img):
    import cv2
    ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
    assert ok
    return bytes(buf)


def _records(kind, c, n=N, h=H, w=W, seed=0):
    """`encoded`: Datum(encoded) as convert_imageset writes it (no
    geometry on the record); `raw`: CHW uint8 payloads (BGR order is the
    writer's business: any bytes do)."""
    imgs = _images(c, n, h, w, seed)
    if kind == "encoded":
        return [(f"{i:06d}", float(i), 0, 0, 0, True, _jpeg(im))
                for i, im in enumerate(imgs)]
    return [(f"{i:06d}", float(i), c, h, w, False,
             im.transpose(2, 0, 1).tobytes()) for i, im in enumerate(imgs)]


def _source(tmp_path, c, transform, *, train=True, seed=3, h=H, w=W,
            **kw):
    lp = LayerParameter.from_text(f'''
        name: "data" type: "MemoryData" top: "data" top: "label"
        source_class: "LMDB"
        transform_param {{ {transform} }}
        memory_data_param {{ source: "{tmp_path}/none" batch_size: {N}
          channels: {c} height: {h} width: {w} }}''')
    src = get_source(lp, phase_train=train, seed=seed, **kw)
    src.metrics = PipelineMetrics()
    return src


def _counters(src):
    return src.metrics.summary()["counters"]


def _general(src, records, draw, c, h=H, w=W):
    """The definition: float32 `data`, then Transformer.__call__."""
    return src.transformer(src._records_to_data(records, c, h, w),
                           draw=draw)


def _mean_file(tmp_path, shape, name):
    mean = (np.random.RandomState(7).rand(*shape) * 200).astype(np.float32)
    path = tmp_path / name
    path.write_bytes(BlobProto(
        shape=BlobShape(dim=[1, *shape]),
        data=[float(v) for v in mean.ravel()]).to_binary())
    return str(path)


MEANS = ("none", "value1", "value3", "file_full", "file_out")
GRID = list(itertools.product(
    ("TRAIN", "TEST"), ("crop", "nocrop"), MEANS, (1.0, 1 / 256),
    (1, 3), ("encoded", "raw"), (1, 3)))


@pytest.mark.parametrize("phase,crop,mean,scale,c,kind,num_threads", GRID)
def test_fused_pack_equals_transformer_call(tmp_path, phase, crop, mean,
                                            scale, c, kind, num_threads):
    """Max abs gap 0.0 against Transformer.__call__ on the same AugDraw,
    over the whole grid of what a transform_param can ask for, with the
    native calls on the caller's thread alone and on three."""
    oh, ow = (CROP, CROP) if crop == "crop" else (H, W)
    parts = [f"scale: {scale!r}", "mirror: true"]
    if crop == "crop":
        parts.append(f"crop_size: {CROP}")
    if mean == "value1":
        parts.append("mean_value: 117.5")
    elif mean == "value3":
        parts.append("mean_value: 104 mean_value: 116.75 mean_value: 123")
    elif mean == "file_full":
        parts.append('mean_file: "%s"' % _mean_file(
            tmp_path, (c, H, W), "full.binaryproto"))
    elif mean == "file_out":
        parts.append('mean_file: "%s"' % _mean_file(
            tmp_path, (c, oh, ow), "out.binaryproto"))
    src = _source(tmp_path, c, " ".join(parts), train=phase == "TRAIN",
                  num_threads=num_threads)
    records = _records(kind, c)
    draw = src.transformer.draw(N, H, W)
    if mean == "value3" and c == 1:
        # three values for one channel: Transformer.__call__ refuses,
        # and the refusal stays its own
        with pytest.raises(ValueError, match="mean_values"):
            src.next_batch(records, draw=draw)
        assert _counters(src) == {"pack_general": 1}
        return
    got = src.next_batch(records, draw=draw)
    assert _counters(src) == {"pack_fused": 1}
    want = _general(src, records, draw, c)
    assert got["data"].dtype == np.float32
    assert got["data"].flags["C_CONTIGUOUS"]
    assert got["data"].shape == want.shape == (N, c, oh, ow)
    assert np.abs(got["data"] - want).max() == 0.0
    np.testing.assert_array_equal(got["label"], np.arange(N, dtype="f4"))
    if phase == "TRAIN":
        assert draw.flip.any() and not draw.flip.all()


def test_fused_pack_draws_like_the_general_path(tmp_path):
    """With no pre-drawn AugDraw the fused pack consumes the
    transformer's RNG exactly as __call__ does (the inline path), and a
    scale that float32 does not hold exactly is rounded the same way."""
    tp = f"crop_size: {CROP} mirror: true scale: 0.017 mean_value: 110"
    records = _records("raw", 3)
    a = _source(tmp_path, 3, tp)
    b = _source(tmp_path, 3, tp)
    for _ in range(3):
        got = a.next_batch(records)["data"]
        want = b.transformer(b._records_to_data(records, 3, H, W))
        assert np.abs(got - want).max() == 0.0
    assert _counters(a) == {"pack_fused": 3}


@pytest.mark.parametrize("kind", ["encoded", "raw"])
def test_fused_pack_writes_into_memory_that_came_back(tmp_path, kind,
                                                      monkeypatch):
    """A batch nobody refers to any more gives its memory to the next
    pack; one somebody still holds (the stager, the device runtime
    reading it) keeps its values whatever is packed after it."""
    src = _source(tmp_path, 3, f"crop_size: {CROP} mirror: true")
    from caffeonspark_tpu import native
    monkeypatch.setattr(native, "POOL_MIN_BYTES", 1024)
    records, other = _records(kind, 3), _records(kind, 3, seed=8)
    draw = src.transformer.draw(N, H, W)
    want = _general(src, records, draw, 3)
    held = src.next_batch(records, draw=draw)["data"]
    addr = held.ctypes.data
    for _ in range(3):                  # `held` is alive: never its memory
        nxt = src.next_batch(other, draw=draw)["data"]
        assert nxt.ctypes.data != addr
    assert np.abs(held - want).max() == 0.0
    seen = {nxt.ctypes.data}
    del held, nxt
    for _ in range(3):                  # nobody holds any: memory returns
        seen.add(src.next_batch(records, draw=draw)["data"].ctypes.data)
    assert len(seen) <= 3
    assert np.abs(src.next_batch(records, draw=draw)["data"]
                  - want).max() == 0.0


def test_fused_pack_resampled_images_stay_float(tmp_path):
    """Encoded records of another size than the layer's are resampled;
    a uint8 store would drop the fractions, so that batch goes through
    the one-pass kernel as float32 — still bit-identical."""
    src = _source(tmp_path, 3, f"crop_size: {CROP} mirror: true",
                  resize=True)
    records = _records("encoded", 3, h=30, w=28)
    draw = src.transformer.draw(N, H, W)
    got = src.next_batch(records, draw=draw)["data"]
    want = _general(src, records, draw, 3)
    assert np.abs(got - want).max() == 0.0
    assert (want != np.floor(want)).any(), "no fraction: not resampled"
    assert _counters(src) == {"pack_fused": 1}


def _float_records():
    rng = np.random.RandomState(1)
    return [(f"{i}", 0.0, 3, H, W, False,
             rng.rand(3, H, W).astype(np.float32)) for i in range(N)]


def _mixed_records():
    recs = _records("raw", 3)
    img = recs[2][6]
    recs[2] = ("2", 2.0, 3, H, W, True, _jpeg(
        np.frombuffer(img, np.uint8).reshape(3, H, W).transpose(1, 2, 0)))
    return recs


def _corrupt_records():
    recs = _records("encoded", 3)
    recs[3] = recs[3][:6] + (b"CORRUPT!",)
    return recs


@pytest.mark.parametrize("make,ok", [(_float_records, True),
                                     (_mixed_records, True),
                                     (_corrupt_records, False)],
                         ids=["float_payload", "mixed", "corrupt_jpeg"])
def test_other_batches_take_the_general_path(tmp_path, make, ok):
    """Float payloads (feature LMDBs), a batch of raw and encoded
    records, a batch with a corrupt JPEG: `Transformer.__call__`, as
    before; through a pool the corrupt batch is a DROPPED slot and the
    next batch is packed."""
    src = _source(tmp_path, 3, f"crop_size: {CROP} mirror: true")
    ref = _source(tmp_path, 3, f"crop_size: {CROP} mirror: true")
    records, good = make(), _records("raw", 3, seed=5)
    feed = FeedQueue()
    pool = TransformerPool(feed, N, src.pack_batch, num_threads=2,
                           draw_fn=src.make_draw_fn(),
                           metrics=src.metrics).start()
    for r in records + good + [None]:
        feed.offer(r)
    first = pool.take(timeout=20, skip_dropped=False)
    second = pool.take(timeout=20, skip_dropped=False)
    pool.stop(join_timeout=5)
    draws = [ref.transformer.draw(N, H, W) for _ in range(2)]
    if ok:
        want = _general(ref, records, draws[0], 3)
        assert np.abs(first["data"] - want).max() == 0.0
    else:
        assert first is DROPPED
        assert pool.drops == 1
    assert np.abs(second["data"]
                  - _general(ref, good, draws[1], 3)).max() == 0.0
    assert _counters(src)["pack_general"] == 1
    assert _counters(src)["pack_fused"] == 1


def test_raw_records_of_another_geometry_are_refused_as_before(tmp_path):
    src = _source(tmp_path, 3, "")
    with pytest.raises(ValueError, match="!= layer"):
        src.next_batch(_records("raw", 3, h=H + 2))
    assert _counters(src) == {"pack_general": 1}


def test_no_native_library_means_the_general_path(tmp_path, monkeypatch):
    src = _source(tmp_path, 3, f"crop_size: {CROP}")
    monkeypatch.setenv("COS_NATIVE", "0")
    records = _records("encoded", 3)
    draw = src.transformer.draw(N, H, W)
    got = src.next_batch(records, draw=draw)["data"]
    assert _counters(src) == {"pack_general": 1}
    monkeypatch.delenv("COS_NATIVE")
    assert np.abs(got - src.next_batch(records, draw=draw)["data"]
                  ).max() <= 2.0      # cv2's decoder, to a level or two
    assert _counters(src) == {"pack_general": 1, "pack_fused": 1}


def test_device_transform_keeps_host_stage(tmp_path, monkeypatch):
    """COS_DEVICE_TRANSFORM=1 ships uint8 + aux as before and counts as
    neither way."""
    monkeypatch.setenv("COS_DEVICE_TRANSFORM", "1")
    src = _source(tmp_path, 3, f"crop_size: {CROP} mirror: true")
    assert src.enable_device_transform() is not None
    batch = src.next_batch(_records("encoded", 3))
    assert batch["data"].dtype == np.uint8
    assert batch["data"].shape == (N, 3, CROP, CROP)
    assert _counters(src) == {}


# -- native.transform_batch: what it refuses ----------------------------

@pytest.mark.parametrize("kw,msg", [
    (dict(crop=8, h_off=[0, 5], w_off=[0, 0]), "h_off"),
    (dict(crop=8, h_off=[0, 0], w_off=[-1, 0]), "w_off"),
    (dict(crop=13), "crop"),
    (dict(mean=np.zeros(2, np.float32)), "mean channels"),
    (dict(crop=8, mean=np.zeros((3, 6, 6), np.float32)), "under output"),
    (dict(mirror=[1]), "mirror"),
], ids=["h_off", "w_off", "crop", "mean_count", "mean_small", "mirror"])
def test_transform_batch_checks_before_it_passes_pointers(kw, msg):
    batch = np.zeros((2, 3, 12, 12), np.uint8)
    with pytest.raises(ValueError, match=msg):
        native.transform_batch(batch, **kw)


def test_transform_batch_checks_payload_sizes():
    with pytest.raises(ValueError, match="bytes, not"):
        native.transform_batch([b"\0" * 10], chw=(1, 4, 4))
    with pytest.raises(ValueError, match="not uint8"):
        native.transform_batch([np.zeros(16, np.float32)], chw=(1, 4, 4))


def test_transform_batch_reads_uint8_arrays_in_place():
    """Raw payloads may be uint8 arrays as well as bytes, and `mean`
    planes are indexed at the source pixel, before the mirror."""
    rng = np.random.RandomState(2)
    imgs = [rng.randint(0, 256, (2, 6, 8)).astype(np.uint8)
            for _ in range(3)]
    mean = rng.rand(2, 6, 8).astype(np.float32)
    flip = np.asarray([1, 0, 1], bool)
    hs, ws = np.asarray([0, 1, 2]), np.asarray([3, 0, 4])
    got = native.transform_batch(
        [imgs[0], imgs[1].tobytes(), imgs[2]], chw=(2, 6, 8), crop=4,
        h_off=hs, w_off=ws, mirror=flip, mean=mean, scale=0.5)
    for i in range(3):
        want = (imgs[i].astype(np.float32) - mean)[
            :, hs[i]:hs[i] + 4, ws[i]:ws[i] + 4]
        if flip[i]:
            want = want[:, :, ::-1]
        np.testing.assert_array_equal(got[i], want * np.float32(0.5))


# -- the decoder's equal-size branch ------------------------------------

def _bilinear_chw(rgb, dh, dw):
    """resize_to_chw's bilinear branch in numpy: the same float32
    operations in the same order, RGB HWC -> BGR CHW."""
    sh, sw, _ = rgb.shape
    f = np.float32
    ys = f(sh - 1) / f(dh - 1) if dh > 1 else f(0)
    xs = f(sw - 1) / f(dw - 1) if dw > 1 else f(0)
    fy = np.arange(dh, dtype=f) * ys
    fx = np.arange(dw, dtype=f) * xs
    y0, x0 = fy.astype(np.int32), fx.astype(np.int32)
    y1, x1 = np.minimum(y0 + 1, sh - 1), np.minimum(x0 + 1, sw - 1)
    wy = (fy - y0.astype(f))[:, None, None]
    wx = (fx - x0.astype(f))[None, :, None]
    p = rgb.astype(f)
    one = f(1)
    v = (p[y0][:, x0] * (one - wy) * (one - wx)
         + p[y0][:, x1] * (one - wy) * wx
         + p[y1][:, x0] * wy * (one - wx)
         + p[y1][:, x1] * wy * wx)
    return v[:, :, ::-1].transpose(2, 0, 1)


def test_equal_size_decode_is_the_bilinear_branch_bit_for_bit():
    """A 300x280 JPEG still resizes, to exactly what the numpy copy of
    the bilinear branch gives on its decoded pixels; and on the image's
    own size that same copy is the identity the equal-size branch
    writes, as float32 and as uint8."""
    jpeg = _jpeg(_images(3, n=1, h=300, w=280)[0])
    own = native.decode_batch([jpeg], channels=3, out_h=300, out_w=280,
                              out_dtype=np.uint8, exact=True)
    assert own is not None and own.shape == (1, 3, 300, 280)
    rgb = own[0][::-1].transpose(1, 2, 0)
    resized = native.decode_batch([jpeg], channels=3, out_h=256,
                                  out_w=256)
    np.testing.assert_array_equal(resized[0], _bilinear_chw(rgb, 256, 256))
    assert (resized != np.floor(resized)).any()
    same = native.decode_batch([jpeg], channels=3, out_h=300, out_w=280)
    np.testing.assert_array_equal(same[0], _bilinear_chw(rgb, 300, 280))
    np.testing.assert_array_equal(same[0], own[0].astype(np.float32))
    # uint8 of a resampled image truncates, and `exact` declines it
    u8 = native.decode_batch([jpeg], channels=3, out_h=256, out_w=256,
                             out_dtype=np.uint8)
    np.testing.assert_array_equal(u8, resized.astype(np.uint8))
    assert native.decode_batch([jpeg, jpeg], channels=3, out_h=256,
                               out_w=256, out_dtype=np.uint8,
                               exact=True) is None


def test_equal_size_decode_grayscale_matches_cv2():
    import cv2
    jpeg = _jpeg(_images(1, n=1, h=40, w=36)[0])
    got = native.decode_batch([jpeg], channels=1, out_h=40, out_w=36,
                              out_dtype=np.uint8, exact=True)
    ref = cv2.imdecode(np.frombuffer(jpeg, np.uint8), cv2.IMREAD_GRAYSCALE)
    assert np.abs(got[0, 0].astype(int) - ref.astype(int)).max() <= 2
