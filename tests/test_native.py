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
