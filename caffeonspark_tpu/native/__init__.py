"""ctypes binding + build for the native data-pipeline library.

The reference's native image path (jcaffe Mat → cv::imdecode,
FloatDataTransformer → caffe::DataTransformer, SURVEY §2.4) lives here
as `libcos_native.so` (libjpeg decode + threaded NCHW transform).  The
library builds on demand with g++ (Makefile equivalent: `make -C
caffeonspark_tpu/native`); when the toolchain or libjpeg is missing,
callers fall back to the cv2/numpy path in `data.transformer` /
`data.source` — same semantics.  Measured (tools/simulator.py): on a
single core the cv2 fallback is competitive (its SIMD decode beats
plain libjpeg); the native path's win is its thread pool on multi-core
executor hosts and independence from cv2.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libcos_native.so")
_SRC = os.path.join(_DIR, "cos_native.cpp")
_LOG = logging.getLogger(__name__)
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
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", _SO, "-ljpeg"]
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
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int]
    lib.cos_transform_batch.restype = None
    lib.cos_transform_batch.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ctypes.c_float, ctypes.POINTER(ctypes.c_float), ctypes.c_int]
    lib.cos_crop_mirror_u8.restype = None
    lib.cos_crop_mirror_u8.argtypes = [
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int]
    lib.cos_native_version.restype = ctypes.c_int


def available() -> bool:
    """COS_NATIVE=0 forces the cv2/numpy fallback — on few-core hosts
    cv2's SIMD decode beats libjpeg (see module docstring), and an
    ingest pool supplies its own inter-batch parallelism."""
    if os.environ.get("COS_NATIVE", "").lower() in ("0", "false", "no"):
        return False
    return get_lib() is not None


def decode_batch(images: Sequence[bytes], *, channels: int, out_h: int,
                 out_w: int, num_threads: int = 0,
                 out_dtype=np.float32) -> np.ndarray:
    """JPEG bytes → (N, C, out_h, out_w) BGR planes.

    out_dtype float32 (default) or uint8 — the uint8 path decodes
    straight into byte planes for the device-transform split
    (COS_DEVICE_TRANSFORM): no float buffer, no host cast pass, and
    its truncating store equals `float_output.astype(uint8)` exactly."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    n = len(images)
    blob = b"".join(images)
    offsets = np.zeros(n, np.int64)
    sizes = np.asarray([len(b) for b in images], np.int64)
    np.cumsum(sizes[:-1], out=offsets[1:]) if n > 1 else None
    if np.dtype(out_dtype) == np.uint8:
        out = np.empty((n, channels, out_h, out_w), np.uint8)
        fn, ptr = lib.cos_decode_batch_u8, ctypes.c_ubyte
    else:
        out = np.empty((n, channels, out_h, out_w), np.float32)
        fn, ptr = lib.cos_decode_batch, ctypes.c_float
    ok = fn(
        blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        n, channels, out_h, out_w,
        out.ctypes.data_as(ctypes.POINTER(ptr)), num_threads)
    if ok != n:
        raise ValueError(f"{n - ok}/{n} images failed to decode")
    return out


def transform_batch(batch: np.ndarray, *, crop: int = 0,
                    h_off: Optional[np.ndarray] = None,
                    w_off: Optional[np.ndarray] = None,
                    mirror: Optional[np.ndarray] = None,
                    mean: Optional[np.ndarray] = None,
                    scale: float = 1.0,
                    num_threads: int = 0) -> np.ndarray:
    """Caffe transform on an (N, C, H, W) float32 batch (native)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    batch = np.ascontiguousarray(batch, np.float32)
    n, c, h, w = batch.shape
    oh = crop or h
    ow = crop or w
    out = np.empty((n, c, oh, ow), np.float32)
    zeros = np.zeros(n, np.int32)
    h_off = np.ascontiguousarray(h_off if h_off is not None else zeros,
                                 np.int32)
    w_off = np.ascontiguousarray(w_off if w_off is not None else zeros,
                                 np.int32)
    mir = np.ascontiguousarray(
        mirror if mirror is not None else np.zeros(n, np.uint8), np.uint8)
    if mean is None:
        mean_ptr, mode = None, 0
    elif mean.ndim == 1:
        mean = np.ascontiguousarray(mean, np.float32)
        mean_ptr, mode = mean.ctypes.data_as(
            ctypes.POINTER(ctypes.c_float)), 1
    else:
        mean = np.ascontiguousarray(mean, np.float32)
        assert mean.shape == (c, oh, ow), (mean.shape, (c, oh, ow))
        mean_ptr, mode = mean.ctypes.data_as(
            ctypes.POINTER(ctypes.c_float)), 2
    lib.cos_transform_batch(
        batch.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n, c, h, w, crop,
        h_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        w_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        mir.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        mean_ptr, mode, ctypes.c_float(scale),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), num_threads)
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
