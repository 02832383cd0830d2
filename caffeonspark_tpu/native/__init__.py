"""ctypes binding + build for the native data-pipeline library.

The reference's native image path (jcaffe Mat → cv::imdecode,
FloatDataTransformer → caffe::DataTransformer, SURVEY §2.4) lives here
as `libcos_native.so` (libjpeg decode + threaded NCHW transform).  The
library builds on demand with g++ (Makefile equivalent: `make -C
caffeonspark_tpu/native`); when the toolchain or libjpeg is missing,
callers fall back to the cv2/numpy path in `data.transformer` /
`data.source` — same semantics.  It links the system's libjpeg, which
here is libjpeg-turbo (`libjpeg.so.62`), the SIMD decoder cv2 bundles
too: the two decode a JPEG to the same pixels at about the same speed.
What the native path adds is what happens around the decode: pixels
stay uint8 in BGR planes, and one pass (`transform_batch`) crops,
mirrors, subtracts the mean, scales and writes the float32 batch.
Every call takes `num_threads` and spreads the batch's images over
that many threads, the calling thread one of them (so 1 spawns
nothing): 0 means one per hardware thread, and a transformer pool gives
each of its workers a share of the cores the process may use
(`data/queue_runner.py:tune_decode_threads`; the share is never under
1, which is what a 2-core box gets: two workers that each took both
cores packed 2.6x slower there).  Images are independent and each
output pixel is written once, so the thread count changes no value.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libcos_native.so")
_SRC = os.path.join(_DIR, "cos_native.cpp")
_LOG = logging.getLogger(__name__)
_ABI_VERSION = 2        # cos_native_version() of the source beside this
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def build(force: bool = False) -> bool:
    """Compile the shared library; returns True on success.  A shipped
    .so without the source (pruned deployment) is accepted as-is."""
    global _build_failed
    if os.path.exists(_SO) and not force:
        try:
            if (not os.path.exists(_SRC)
                    or os.path.getmtime(_SO) >= os.path.getmtime(_SRC)):
                return True
        except OSError:
            return True       # can't stat: trust the shipped .so
    if not os.path.exists(_SRC):
        return os.path.exists(_SO)
    # to a name of this process's own, then renamed: several processes
    # (test workers, executors on one host) may build at once, and none
    # may load a half-written file
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", tmp, "-ljpeg"]
    # callers fall back to cv2 with the same semantics, but say so: a
    # silent None would hide which decoder a measurement ran on
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _LOG.warning("native decoder not built (%s); using cv2", e)
        _build_failed = True
        return False
    if r.returncode != 0:
        _LOG.warning("native decoder not built (g++ rc %d: %s); using "
                     "cv2", r.returncode, r.stderr.strip()[-400:])
        _build_failed = True
        return False
    os.replace(tmp, _SO)
    return True


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed); None when unavailable."""
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        # build() is a no-op when the .so is current; a source edit
        # (newer mtime) triggers a rebuild so new symbols exist
        if not build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
            _bind(lib)
        except OSError:
            _build_failed = True
            return None
        except AttributeError:
            # stale .so lacking newer symbols (mtime-preserving copy):
            # one forced rebuild if the source is around, else give up
            # and let callers fall back to the cv2 path
            if not (os.path.exists(_SRC) and build(force=True)):
                _build_failed = True
                return None
            try:
                lib = ctypes.CDLL(_SO)
                _bind(lib)
            except (OSError, AttributeError):
                _build_failed = True
                return None
        _lib = lib
        return _lib


def _bind(lib) -> None:
    lib.cos_decode_batch.restype = ctypes.c_int
    lib.cos_decode_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_long), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.cos_decode_batch_u8.restype = ctypes.c_int
    lib.cos_decode_batch_u8.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_long), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int, ctypes.c_int]
    lib.cos_transform_batch.restype = None
    lib.cos_transform_batch.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ctypes.c_float, ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.cos_crop_mirror_u8.restype = None
    lib.cos_crop_mirror_u8.argtypes = [
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int]
    lib.cos_native_version.restype = ctypes.c_int
    if lib.cos_native_version() != _ABI_VERSION:
        # same symbols, other signatures: as stale as a missing symbol
        raise AttributeError(
            f"libcos_native.so is version {lib.cos_native_version()}, "
            f"not {_ABI_VERSION}")


def available() -> bool:
    """COS_NATIVE=0 forces the cv2/numpy fallback (same semantics; the
    way to A/B the two on one host)."""
    if os.environ.get("COS_NATIVE", "").lower() in ("0", "false", "no"):
        return False
    return get_lib() is not None


# a pack's batch-sized arrays (`BufferPool`): a block under POOL_MIN_BYTES
# is malloc's own business (it is not mapped and unmapped on every use),
# and at most POOL_KEEP free blocks a size wait for the next pack
POOL_KEEP = 4
POOL_MIN_BYTES = 32 << 20


class BufferPool:
    """Batch-sized arrays whose memory comes back instead of going to
    the OS.  `take(shape, dtype)` is `np.empty` for the caller; when the
    array it returned is garbage — when today's allocator would free it
    — its memory goes on a free list and the next `take` of that size
    writes into pages already touched.  A fresh 475 MB batch costs 4 us a
    page in first touches (PERF.md section 5), and a feed that maps and
    unmaps 2.5 GB/s of them outran the chip host's reclaim until the
    machine's 40 GiB were gone (PR 27).

    As safe as the free it replaces: whoever still reads the array (the
    TPU runtime holds a reference until its transfer is complete) keeps
    it alive, and memory still reachable through another view of it is
    recognised by its reference count and left to the allocator.  That
    rests on CPython's counting (an array dies when its last reference
    goes, `sys.getrefcount` of a block only the pool holds is 2), so a
    new pool tries it once, and on an interpreter that counts another
    way every `take` is `np.empty`."""

    def __init__(self):
        self._free: Dict[int, List[np.ndarray]] = {}
        self._reuses = self._counts_as_expected()
        if not self._reuses:
            _LOG.warning("cos_native: reference counts are not CPython's; "
                        "pack buffers are not reused")

    def take(self, shape, dtype) -> np.ndarray:
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        if nbytes < POOL_MIN_BYTES or not self._reuses:
            return np.empty(shape, dtype)
        return self._lend(nbytes, shape, dtype)

    def _lend(self, nbytes: int, shape, dtype) -> np.ndarray:
        free = self._free.setdefault(nbytes, [])
        while True:
            try:                # list.pop/append are atomic: no lock
                base = free.pop()       # for a finalizer to deadlock on
            except IndexError:
                base = np.empty(nbytes, np.uint8)
                break
            # ours alone? (`base` here + getrefcount's argument); a view
            # someone still holds counts one more: that memory is theirs
            if sys.getrefcount(base) == 2:
                break
        out = base.view(dtype).reshape(shape)
        weakref.finalize(out, self._give_back, base).atexit = False
        return out

    def _give_back(self, base: np.ndarray) -> None:
        free = self._free.get(base.nbytes)
        if free is not None and len(free) < POOL_KEEP:
            free.append(base)

    def _counts_as_expected(self) -> bool:
        """Take, drop, take: the same memory.  Drop it while a slice
        lives, take: other memory."""
        a = self._lend(64, (64,), np.uint8)
        addr = a.ctypes.data
        del a
        b = self._lend(64, (64,), np.uint8)
        came_back = b.ctypes.data == addr
        part = b[:1]
        del b
        c = self._lend(64, (64,), np.uint8)
        left_alone = c.ctypes.data != addr
        del part, c
        self._free.clear()
        return came_back and left_alone


_buffers = BufferPool()


def decode_batch(images: Sequence[bytes], *, channels: int, out_h: int,
                 out_w: int, num_threads: int = 0,
                 out_dtype=np.float32,
                 exact: bool = False) -> Optional[np.ndarray]:
    """JPEG bytes → (N, C, out_h, out_w) BGR planes, in an array of
    its own (batch-sized ones come from the `BufferPool`).

    An image already out_h x out_w is only deinterleaved; any other is
    resized bilinearly.  out_dtype float32 (default) or uint8.  The
    uint8 store of a resized image truncates (it equals
    `float_output.astype(uint8)` exactly, which is what the
    device-transform split ships); a caller that wants the decoded
    pixels themselves passes `exact=True` with uint8 and gets None as
    soon as one image is of another size."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(images)
    blob = b"".join(images)
    offsets = np.zeros(n, np.int64)
    sizes = np.asarray([len(b) for b in images], np.int64)
    np.cumsum(sizes[:-1], out=offsets[1:]) if n > 1 else None
    args = [blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            n, channels, out_h, out_w]
    if np.dtype(out_dtype) == np.uint8:
        out = _buffers.take((n, channels, out_h, out_w), np.uint8)
        ok = lib.cos_decode_batch_u8(
            *args, out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            num_threads, int(exact))
        if ok < 0:
            return None
    else:
        out = _buffers.take((n, channels, out_h, out_w), np.float32)
        ok = lib.cos_decode_batch(
            *args, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            num_threads)
    if ok != n:
        raise ValueError(f"{n - ok}/{n} images failed to decode")
    return out


def _int32_within(name: str, v, n: int, hi: int) -> np.ndarray:
    """Per-image crop origins as the kernel reads them: n int32 values
    in [0, hi]."""
    a = np.ascontiguousarray(v if v is not None else np.zeros(n), np.int32)
    if a.shape != (n,) or (n and (a.min() < 0 or a.max() > hi)):
        raise ValueError(f"{name}: need {n} offsets in [0, {hi}]")
    return a


def transform_batch(batch, *, chw: Optional[Tuple[int, int, int]] = None,
                    crop: int = 0,
                    h_off: Optional[np.ndarray] = None,
                    w_off: Optional[np.ndarray] = None,
                    mirror: Optional[np.ndarray] = None,
                    mean: Optional[np.ndarray] = None,
                    scale: float = 1.0,
                    num_threads: int = 0) -> np.ndarray:
    """Caffe transform in one pass: crop + mirror + mean + scale from
    the source pixels to an (N, C, oh, ow) float32 batch that nobody
    else refers to (a batch-sized one from the `BufferPool`), each
    output pixel written once.

    `batch` is an (N, C, H, W) array, uint8 or float32 (anything else
    is cast to float32), or a sequence of N raw uint8 images of `chw` =
    (C, H, W) each (`bytes`, or uint8 arrays: Datum payloads), which
    the kernel reads in place.  `mean`: 1-D values (one, or one a
    channel) or a (1 or C, mh, mw) plane, subtracted at the SOURCE
    pixel as Caffe does: a full-size plane at each image's own crop
    window, any other (mh >= oh, mw >= ow) at its centre window.  The
    float operations are those of `Transformer.__call__` in the same
    order, so the two agree bit for bit."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    keep = []          # buffers the pointer array points into
    if isinstance(batch, np.ndarray):
        dt = np.uint8 if batch.dtype == np.uint8 else np.float32
        batch = np.ascontiguousarray(batch, dt)
        n, c, h, w = batch.shape
        in_ptr, ptrs = batch.ctypes.data, None
    else:
        dt = np.uint8
        c, h, w = chw
        n = len(batch)
        in_ptr, ptrs = None, (ctypes.c_void_p * n)()
        for i, p in enumerate(batch):
            if isinstance(p, np.ndarray):
                if p.dtype != np.uint8:
                    raise ValueError(f"image {i}: {p.dtype}, not uint8")
                p = np.ascontiguousarray(p)
                keep.append(p)
                size, ptrs[i] = p.size, p.ctypes.data
            else:
                size = len(p)
                ptrs[i] = ctypes.cast(ctypes.c_char_p(p), ctypes.c_void_p)
            if size != c * h * w:
                raise ValueError(
                    f"image {i}: {size} bytes, not {c}x{h}x{w}")
    crop = int(crop)
    if crop < 0 or crop > h or crop > w:
        raise ValueError(f"crop {crop} outside input {h}x{w}")
    oh = crop or h
    ow = crop or w
    h_off = _int32_within("h_off", h_off, n, h - oh)
    w_off = _int32_within("w_off", w_off, n, w - ow)
    mir = np.ascontiguousarray(
        mirror if mirror is not None else np.zeros(n, np.uint8), np.uint8)
    if mir.shape != (n,):
        raise ValueError(f"mirror: need {n} flags")
    mode, mc, mh, mw = 0, 0, 0, 0
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32)
        if mean.ndim == 1:
            mode, mc = 1, mean.shape[0]
        elif mean.ndim == 3:
            mode, (mc, mh, mw) = 2, mean.shape
            if (mh, mw) != (h, w) and (mh < oh or mw < ow):
                raise ValueError(f"mean {mh}x{mw} under output {oh}x{ow}")
        else:
            raise ValueError(f"mean of {mean.ndim} dimensions")
        if mc not in (1, c):
            raise ValueError(f"{mc} mean channels for {c} channels")
    out = _buffers.take((n, c, oh, ow), np.float32)
    float_p = ctypes.POINTER(ctypes.c_float)
    lib.cos_transform_batch(
        in_ptr, ptrs, int(dt == np.uint8), n, c, h, w, crop,
        h_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        w_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        mir.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        None if mean is None else mean.ctypes.data_as(float_p),
        mode, mc, mh, mw, ctypes.c_float(scale),
        out.ctypes.data_as(float_p), num_threads)
    return out


def crop_mirror_u8(batch: np.ndarray, h_off: np.ndarray,
                   w_off: np.ndarray, mirror: np.ndarray, *,
                   crop: int = 0, num_threads: int = 0) -> np.ndarray:
    """Threaded uint8 crop(+mirror) — the device-transform split's host
    half (Transformer.host_stage's hot loop).  The RNG draws stay with
    the caller; this only moves bytes."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    batch = np.ascontiguousarray(batch, np.uint8)
    n, c, h, w = batch.shape
    oh = crop if crop else h
    ow = crop if crop else w
    ho = np.ascontiguousarray(h_off, np.int32)
    wo = np.ascontiguousarray(w_off, np.int32)
    mi = np.ascontiguousarray(mirror, np.uint8)
    out = np.empty((n, c, oh, ow), np.uint8)
    lib.cos_crop_mirror_u8(
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        n, c, h, w, crop,
        ho.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        wo.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        mi.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)), num_threads)
    return out
