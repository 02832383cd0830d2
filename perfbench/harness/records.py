"""The one general traffic generator: seeded image records -> an LMDB.

Reads a traffic file's parameters (side, encoding, JPEG quality, noise)
and makes `n` records from the seed: smooth structure (a coarse random
field, interpolated) plus mild per-pixel noise, so a JPEG of it has a
realistic size and no two crops of it look alike.  Every record keeps
its decoded uint8 pixels (C, H, W; BGR as OpenCV and Caffe order them)
for the benchmark's own transform — decoded here with cv2, not with the
program's decoder.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def datum(channels, height, width, data: bytes, label: int,
          encoded: bool) -> bytes:
    """caffe.proto Datum: channels=1 height=2 width=3 data=4 label=5
    encoded=7."""
    out = (b"\x08" + _varint(channels) + b"\x10" + _varint(height)
           + b"\x18" + _varint(width) + b"\x22" + _varint(len(data)) + data
           + b"\x28" + _varint(label))
    if encoded:
        out += b"\x38\x01"
    return out


def make_pixels(n: int, side: int, seed: int, *, coarse: int = 8,
                noise: int = 12) -> np.ndarray:
    """(n, side, side, 3) uint8, HWC BGR."""
    import cv2
    rng = np.random.default_rng(seed)
    # the coarse field keeps clear of 0 and 255, so that neither the cubic
    # overshoot nor the noise saturates: no flat patch, every crop distinct
    low = rng.integers(48, 208, (n, coarse, coarse, 3), dtype=np.uint8)
    jitter = rng.integers(0, 2 * noise + 1, (n, side, side, 3),
                          dtype=np.uint8)
    centre = np.full((side, side, 3), noise, np.uint8)
    for i in range(n):          # saturating uint8 arithmetic, in place
        smooth = cv2.resize(low[i], (side, side),
                            interpolation=cv2.INTER_CUBIC)
        cv2.subtract(cv2.add(smooth, jitter[i]), centre, dst=jitter[i])
    return jitter


def generate(traffic: dict, n: int, seed: int, classes: int):
    """-> (records [(key, value bytes)], pixels (n, 3, side, side) uint8 as
    a reader of the record should see them, labels (n,), facts dict)."""
    import cv2
    side = int(traffic["side"])
    hwc = make_pixels(n, side, seed, coarse=int(traffic.get("coarse", 8)),
                      noise=int(traffic.get("noise", 12)))
    labels = (np.random.default_rng(seed + 1).permutation(n)
              % classes).astype(np.int64)
    facts = {"records": n, "side": side}
    if traffic["encoding"] == "jpeg":
        quality = int(traffic["jpeg_quality"])

        def enc(img):
            ok, buf = cv2.imencode(".jpg", img,
                                   [cv2.IMWRITE_JPEG_QUALITY, quality])
            if not ok:
                raise RuntimeError("cv2.imencode failed")
            return buf.tobytes()

        with ThreadPoolExecutor(4) as ex:
            payloads = list(ex.map(enc, hwc))
        t0 = time.perf_counter()
        with ThreadPoolExecutor(4) as ex:
            dec = list(ex.map(lambda b: cv2.imdecode(
                np.frombuffer(b, np.uint8), cv2.IMREAD_COLOR), payloads))
        facts["reference_decode_ms_per_img"] = (
            1e3 * (time.perf_counter() - t0) * 4 / n)   # 4 threads
        pixels = np.stack(dec).transpose(0, 3, 1, 2)
        encoded = True
    elif traffic["encoding"] == "raw":
        pixels = hwc.transpose(0, 3, 1, 2)
        payloads = [np.ascontiguousarray(p).tobytes() for p in pixels]
        encoded = False
    else:
        raise ValueError(f"traffic encoding {traffic['encoding']!r}")
    records = [(b"%08d" % i,
                datum(3, side, side, payloads[i], int(labels[i]), encoded))
               for i in range(n)]
    facts["mean_record_bytes"] = sum(len(p) for p in payloads) / n
    return records, np.ascontiguousarray(pixels), labels, facts
