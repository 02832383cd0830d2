"""Native library tests: build, decode parity vs cv2, transform parity
vs the numpy Transformer (TransformTest.java analog for the native
path)."""

import numpy as np
import pytest

from caffeonspark_tpu import native


@pytest.fixture(scope="module")
def lib():
    if not native.available():
        pytest.skip("native toolchain/libjpeg unavailable")
    return native.get_lib()


def _jpegs(n=6, h=32, w=32):
    import cv2
    from caffeonspark_tpu.data.synthetic import make_images
    imgs, _ = make_images(n, channels=3, height=h, width=w, seed=9)
    out = []
    for i in range(n):
        ok, buf = cv2.imencode(
            ".jpg", (imgs[i].transpose(1, 2, 0) * 255).astype(np.uint8),
            [cv2.IMWRITE_JPEG_QUALITY, 95])
        assert ok
        out.append(bytes(buf))
    return out


def test_version(lib):
    assert lib.cos_native_version() == 2


def test_decode_batch_matches_cv2(lib):
    import cv2
    jpegs = _jpegs()
    got = native.decode_batch(jpegs, channels=3, out_h=32, out_w=32)
    assert got.shape == (6, 3, 32, 32)
    for i, buf in enumerate(jpegs):
        ref = cv2.imdecode(np.frombuffer(buf, np.uint8),
                           cv2.IMREAD_COLOR)  # BGR HWC
        ref = ref.transpose(2, 0, 1).astype(np.float32)
        # decoders differ slightly (IDCT implementations); tolerance 3/255
        assert np.mean(np.abs(got[i] - ref)) < 3.0, i


def test_decode_grayscale(lib):
    jpegs = _jpegs()
    got = native.decode_batch(jpegs, channels=1, out_h=16, out_w=16)
    assert got.shape == (6, 1, 16, 16)
    assert got.min() >= 0 and got.max() <= 255


def test_decode_corrupt_raises(lib):
    with pytest.raises(ValueError, match="failed to decode"):
        native.decode_batch([b"not a jpeg"], channels=3, out_h=8,
                            out_w=8)


@pytest.mark.parametrize("n,num_threads", [(1, 1), (1, 4), (3, 8),
                                           (6, 3), (6, 0), (24, 32)])
def test_threaded_calls_agree_with_one_thread(lib, n, num_threads):
    """run_workers: the caller's thread takes a share and spawns
    num_threads - 1 helpers, never more than n in all; one image, fewer
    images than threads, more threads than cores, and 0 = a thread a
    hardware thread all give the values one thread gives, for each of
    the three kernels."""
    jpegs = _jpegs(n)
    one = native.decode_batch(jpegs, channels=3, out_h=32, out_w=32,
                              out_dtype=np.uint8, exact=True,
                              num_threads=1)
    got = native.decode_batch(jpegs, channels=3, out_h=32, out_w=32,
                              out_dtype=np.uint8, exact=True,
                              num_threads=num_threads)
    np.testing.assert_array_equal(got, one)
    rng = np.random.RandomState(n)
    hs, ws = rng.randint(0, 9, n), rng.randint(0, 9, n)
    flip = rng.randint(0, 2, n).astype(np.uint8)
    kw = dict(crop=24, h_off=hs, w_off=ws, mirror=flip,
              mean=np.asarray([104.0, 117.0, 123.0], np.float32))
    np.testing.assert_array_equal(
        native.transform_batch(one, num_threads=num_threads, **kw),
        native.transform_batch(one, num_threads=1, **kw))
    np.testing.assert_array_equal(
        native.crop_mirror_u8(one, hs, ws, flip, crop=24,
                              num_threads=num_threads),
        native.crop_mirror_u8(one, hs, ws, flip, crop=24, num_threads=1))


@pytest.mark.parametrize("num_threads", [1, 3, 0])
def test_corrupt_jpeg_inside_a_threaded_decode_raises(lib, num_threads):
    """One corrupt image among good ones, wherever a thread meets it:
    the call still raises, and says how many failed."""
    jpegs = _jpegs(6)
    jpegs[4] = jpegs[4][:40]
    for exact, dt in ((False, np.float32), (True, np.uint8)):
        with pytest.raises(ValueError, match="1/6 images failed"):
            native.decode_batch(jpegs, channels=3, out_h=32, out_w=32,
                                out_dtype=dt, exact=exact,
                                num_threads=num_threads)


def test_transform_matches_numpy(lib):
    rng = np.random.RandomState(0)
    batch = rng.rand(4, 3, 12, 12).astype(np.float32) * 255
    h_off = np.asarray([0, 2, 4, 1], np.int32)
    w_off = np.asarray([3, 0, 2, 4], np.int32)
    mirror = np.asarray([0, 1, 0, 1], np.uint8)
    mean = np.asarray([10.0, 20.0, 30.0], np.float32)
    got = native.transform_batch(batch, crop=8, h_off=h_off, w_off=w_off,
                                 mirror=mirror, mean=mean, scale=0.5)
    for i in range(4):
        ref = batch[i, :, h_off[i]:h_off[i] + 8, w_off[i]:w_off[i] + 8]
        if mirror[i]:
            ref = ref[:, :, ::-1]
        ref = (ref - mean.reshape(3, 1, 1)) * 0.5
        np.testing.assert_allclose(got[i], ref, rtol=1e-6)


def test_transform_mean_plane(lib):
    rng = np.random.RandomState(1)
    batch = rng.rand(2, 1, 6, 6).astype(np.float32)
    meanp = rng.rand(1, 6, 6).astype(np.float32)
    got = native.transform_batch(batch, mean=meanp, scale=2.0)
    np.testing.assert_allclose(got, (batch - meanp[None]) * 2.0,
                               rtol=1e-6)


def test_decode_batch_uint8_equals_float_cast(lib):
    """The uint8 decode path (device-transform split) must equal the
    float path truncated to uint8 — same pixels on the wire, no float
    buffer in between.  Resized output exercises the fractional
    bilinear values where truncation actually matters."""
    jpegs = _jpegs()
    f32 = native.decode_batch(jpegs, channels=3, out_h=24, out_w=24)
    u8 = native.decode_batch(jpegs, channels=3, out_h=24, out_w=24,
                             out_dtype=np.uint8)
    assert u8.dtype == np.uint8
    np.testing.assert_array_equal(u8, f32.astype(np.uint8))


def test_source_ships_uint8_from_native_decode(lib, tmp_path, monkeypatch):
    """Encoded-image sources under COS_DEVICE_TRANSFORM pack uint8
    straight from the native decoder (no float round trip)."""
    monkeypatch.setenv("COS_DEVICE_TRANSFORM", "1")
    import cv2
    from caffeonspark_tpu.data.lmdb_io import LmdbWriter
    from caffeonspark_tpu.data.source import get_source
    from caffeonspark_tpu.proto.caffe import Datum, LayerParameter

    rng = np.random.RandomState(0)
    recs = []
    for i in range(8):
        img = rng.randint(0, 255, (20, 20, 3), np.uint8)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        d = Datum(channels=3, height=20, width=20, label=i % 3,
                  data=bytes(buf.tobytes()), encoded=True)
        recs.append((b"%08d" % i, d.to_binary()))
    LmdbWriter(str(tmp_path / "data.mdb")).write(recs)
    lp = LayerParameter.from_text(f'''
        name: "data" type: "MemoryData" top: "data" top: "label"
        source_class: "com.yahoo.ml.caffe.LMDB"
        transform_param {{ scale: 0.00390625 }}
        memory_data_param {{
          source: "file:{tmp_path}"
          batch_size: 4 channels: 3 height: 16 width: 16 }}''')
    src = get_source(lp, phase_train=True, seed=0, resize=True)
    assert src.enable_device_transform() is not None
    batch = next(src.batches(loop=False, shuffle=False))
    assert batch["data"].dtype == np.uint8
    assert batch["data"].shape == (4, 3, 16, 16)


def test_crop_mirror_u8_matches_numpy(lib):
    """The threaded native host-half kernel == the numpy slicing path
    bit for bit (random per-image offsets and mirror flags)."""
    rng = np.random.RandomState(4)
    n, c, h, w, crop = 6, 3, 14, 12, 8
    batch = rng.randint(0, 256, (n, c, h, w)).astype(np.uint8)
    hs = rng.randint(0, h - crop + 1, n)
    ws = rng.randint(0, w - crop + 1, n)
    flip = rng.randint(0, 2, n).astype(bool)
    got = native.crop_mirror_u8(batch, hs, ws, flip, crop=crop)
    want = np.stack([batch[i, :, hs[i]:hs[i] + crop,
                           ws[i]:ws[i] + crop] for i in range(n)])
    want[flip] = want[flip, :, :, ::-1]
    np.testing.assert_array_equal(got, want)
    # no-crop mode: mirror only
    got2 = native.crop_mirror_u8(batch, np.zeros(n, int),
                                 np.zeros(n, int), flip, crop=0)
    want2 = batch.copy()
    want2[flip] = want2[flip, :, :, ::-1]
    np.testing.assert_array_equal(got2, want2)


def test_host_stage_native_equals_numpy(lib, monkeypatch):
    """Transformer.host_stage produces identical bytes through the
    native kernel and the numpy fallback (same RNG draws)."""
    from caffeonspark_tpu import native as native_mod
    from caffeonspark_tpu.data.transformer import Transformer
    from caffeonspark_tpu.proto.caffe import TransformationParameter
    tp = TransformationParameter(crop_size=10, mirror=True)
    x = np.random.RandomState(5).randint(
        0, 256, (4, 3, 16, 16)).astype(np.float32)
    a_u8, a_aux = Transformer(tp, phase_train=True, seed=3).host_stage(x)
    monkeypatch.setattr(native_mod, "available", lambda: False)
    b_u8, b_aux = Transformer(tp, phase_train=True, seed=3).host_stage(x)
    np.testing.assert_array_equal(a_u8, b_u8)
    np.testing.assert_array_equal(a_aux, b_aux)


# -- BufferPool: batch-sized arrays that come back -----------------------

@pytest.fixture
def small_pool(monkeypatch):
    """A pool that keeps blocks from 1 KiB, two a size."""
    monkeypatch.setattr(native, "POOL_MIN_BYTES", 1024)
    monkeypatch.setattr(native, "POOL_KEEP", 2)
    return native.BufferPool()


def test_buffer_pool_reuses_memory_nobody_refers_to(small_pool):
    pool = small_pool
    a = pool.take((4, 256), np.float32)
    assert a.shape == (4, 256) and a.dtype == np.float32
    assert a.flags["C_CONTIGUOUS"] and a.flags["WRITEABLE"]
    addr = a.ctypes.data
    del a
    b = pool.take((256, 4), np.float32)     # same bytes, another shape
    assert b.ctypes.data == addr
    u = pool.take((4096,), np.uint8)        # b is alive: not its memory
    assert u.ctypes.data != addr
    # under min_bytes: plain np.empty, nothing kept
    small = pool.take((8,), np.float32)
    assert small.base is None
    del small
    assert list(pool._free) == [4096]


def test_buffer_pool_leaves_memory_a_view_still_reaches(small_pool):
    """The array handed out dies but a slice of it lives: that memory is
    the slice's, as with the allocator's own free, and is never handed
    out again."""
    pool = small_pool
    a = pool.take((4, 256), np.float32)
    a[:] = 7.0
    addr, row = a.ctypes.data, a[2]
    del a
    b = pool.take((4, 256), np.float32)
    assert b.ctypes.data != addr
    b[:] = 9.0
    assert (row == 7.0).all()
    del row, b
    assert pool.take((4, 256), np.float32) is not None


@pytest.mark.parametrize("shift", [1, -1])
def test_buffer_pool_is_np_empty_where_counts_differ(small_pool,
                                                     monkeypatch, shift):
    """Reuse rests on CPython's reference counts.  On an interpreter
    that reports one more (nothing would ever come back) or one fewer
    (memory a view still reaches would be handed out), a new pool finds
    out at once and every take is np.empty's."""
    import sys
    real = sys.getrefcount
    monkeypatch.setattr(native.sys, "getrefcount",
                        lambda o: real(o) - 1 + shift)
    pool = native.BufferPool()
    a = pool.take((4, 256), np.float32)
    assert a.base is None and not pool._free
    assert small_pool.take((4, 256), np.float32).base is not None


def test_buffer_pool_keeps_at_most_keep_a_size(small_pool):
    pool = small_pool
    arrays = [pool.take((2048,), np.uint8) for _ in range(5)]
    del arrays
    assert [len(v) for v in pool._free.values()] == [2]


def test_buffer_pool_under_threads(small_pool):
    """More takers than cores, each filling its array with its own value
    and reading it back after a pause: an array handed to two holders at
    once would show the other's value."""
    import sys
    import threading
    import time
    pool = small_pool
    bad, addrs, takes = [], set(), []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def taker(k):
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            a = pool.take((64, 64), np.float32)
            addrs.add(a.ctypes.data)
            takes.append(k)
            a[:] = k
            time.sleep(0)
            if not (a == k).all():
                bad.append(k)
            del a

    try:
        threads = [threading.Thread(target=taker, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not bad and len(addrs) < len(takes) / 4
