"""Host-side data transformer: Caffe `transform_param` semantics.

Equivalent of caffe::DataTransformer<float> consumed through the JNI
wrapper `jcaffe/FloatDataTransformer.java:9-40` (scale / mirror / crop /
mean-subtract per batch, SURVEY §2.4).  Runs on the host CPU over numpy
batches (the TPU analog of the reference's transformer threads feeding
preallocated blobs), so the jitted step receives ready NCHW tensors.

Order of operations (matches Caffe Transform, data_transformer.cpp):
  1. crop (random at TRAIN, center at TEST)
  2. mean_file subtraction at the SOURCE pixel — the mean is cropped at
     the same per-sample (h_off, w_off) as the image, before mirroring
  3. mirror (random horizontal flip at TRAIN)
  4. mean_value per-channel subtraction (commutes with the flip)
  5. scale multiplication
"""

from __future__ import annotations

import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

from ..proto.caffe import BlobProto, TransformationParameter

# batch-dict key suffix carrying the (N, 3) int32 [h_off, w_off, flip]
# aux array of the device-transform split (see Transformer.host_stage)
DEVICE_AUX_SUFFIX = "__devxf"


class AugDraw(NamedTuple):
    """One batch's pre-drawn augmentation: `offs` is (hs, ws) per-sample
    crop offsets or None when no crop applies, `flip` the per-sample
    mirror flags.  Produced by Transformer.draw() so a multi-threaded
    pack pool can consume the RNG in feed order on ONE thread and hand
    workers a fixed draw — the pooled pipeline then reproduces the
    inline path's augmentation stream exactly."""
    offs: Optional[Tuple[np.ndarray, np.ndarray]]
    flip: np.ndarray


def load_mean_file(path: str) -> np.ndarray:
    """mean.binaryproto → (C, H, W) float32 (BlobProto wire format)."""
    with open(path, "rb") as f:
        bp = BlobProto.from_binary(f.read())
    if bp.shape.dim:
        shape = tuple(int(d) for d in bp.shape.dim)
    else:
        shape = (int(bp.channels), int(bp.height), int(bp.width))
    arr = np.asarray(bp.data, np.float32).reshape(shape)
    if arr.ndim == 4:
        arr = arr[0]
    return arr


class Transformer:
    """Batched NCHW transformer with Caffe RNG discipline: one stream per
    transformer instance, seeded per rank (CaffeNet.cpp:614-618 analog)."""

    def __init__(self, tp: Optional[TransformationParameter], *,
                 phase_train: bool, seed: int = 0,
                 mean_dir: Optional[str] = None):
        self.tp = tp or TransformationParameter()
        self.train = phase_train
        self.rng = np.random.RandomState(seed & 0x7FFFFFFF)
        # np.RandomState is not safe under concurrent draws; draw()
        # serializes consumers (pool dispatcher vs inline callers)
        self._rng_lock = threading.Lock()
        self.mean: Optional[np.ndarray] = None
        if self.tp.has("mean_file") and self.tp.mean_file:
            import os
            p = self.tp.mean_file
            if mean_dir is not None and not os.path.isabs(p):
                p = os.path.join(mean_dir, p)
            self.mean = load_mean_file(p)
        if self.tp.mean_value and self.mean is not None:
            raise ValueError("specify either mean_file or mean_value, "
                             "not both")

    # -- the RNG-bearing draws, shared verbatim by the host-only path and
    # the device-transform split so both consume self.rng identically
    # (trajectory parity between the two pipelines depends on it) -------

    def _draw_crop(self, n: int, h: int, w: int):
        """Per-sample crop offsets, or None when no crop applies.
        Draws from self.rng ONLY at TRAIN with an active crop."""
        crop = int(self.tp.crop_size)
        if not (crop and (crop != h or crop != w)):
            return None
        if crop > h or crop > w:
            raise ValueError(f"crop_size {crop} exceeds input {h}x{w}")
        if self.train:
            hs = self.rng.randint(0, h - crop + 1, size=n)
            ws = self.rng.randint(0, w - crop + 1, size=n)
        else:
            hs = np.full(n, (h - crop) // 2)
            ws = np.full(n, (w - crop) // 2)
        return hs, ws

    def _draw_flip(self, n: int):
        """Per-sample mirror flags (TRAIN with mirror), else all-False."""
        if self.tp.mirror and self.train:
            return self.rng.randint(0, 2, size=n).astype(bool)
        return np.zeros(n, bool)

    def draw(self, n: int, h: int, w: int) -> AugDraw:
        """Consume the RNG for one n-sample batch — crop offsets then
        mirror flags, the exact order __call__/host_stage use — under a
        lock, so a transformer-pool dispatcher can pre-draw batches in
        feed order while workers pack concurrently."""
        with self._rng_lock:
            offs = self._draw_crop(n, h, w)
            flip = self._draw_flip(n)
        return AugDraw(offs, flip)

    def __call__(self, batch: np.ndarray,
                 draw: Optional[AugDraw] = None) -> np.ndarray:
        """batch: (N, C, H, W) float32 (raw 0..255 pixel scale);
        `draw` replays a pre-drawn augmentation instead of consuming
        the RNG here (TransformerPool ordered-draw protocol)."""
        tp = self.tp
        n, c, h, w = batch.shape
        crop = int(tp.crop_size)
        out = batch
        if draw is None:
            draw = self.draw(n, h, w)

        # Caffe subtracts mean_file at the SOURCE index (data_index uses
        # h_off/w_off, mirror only remaps the destination) — equivalent
        # to subtracting the full-size mean BEFORE crop+flip.
        if self.mean is not None:
            m = self.mean
            if m.shape[1] == h and m.shape[2] == w:
                out = out - m[None]
                mean_done = True
            else:
                mean_done = False  # crop-sized mean: subtract post-crop
        else:
            mean_done = True

        offs = draw.offs
        if offs is not None:
            hs, ws = offs
            crop = int(tp.crop_size)
            if self.train:
                out = (np.stack([out[i, :, hs[i]:hs[i] + crop,
                                     ws[i]:ws[i] + crop]
                                 for i in range(n)])
                       if n else
                       np.empty((0, c, crop, crop), out.dtype))
            else:  # center crop: one slice for the whole batch —
                #      scalar offsets, not hs[0] (an empty batch has
                #      no element 0 but still a valid cropped shape)
                h0, w0 = (h - crop) // 2, (w - crop) // 2
                out = out[:, :, h0:h0 + crop, w0:w0 + crop]
        else:
            out = out.copy()

        if not mean_done:
            m = self.mean
            if (m.shape[1] != out.shape[2]
                    or m.shape[2] != out.shape[3]):
                hs0 = (m.shape[1] - out.shape[2]) // 2
                ws0 = (m.shape[2] - out.shape[3]) // 2
                m = m[:, hs0:hs0 + out.shape[2], ws0:ws0 + out.shape[3]]
            out = out - m[None]

        flip = draw.flip
        if flip.any():
            out[flip] = out[flip, :, :, ::-1]

        # mean_file and mean_value are mutually exclusive (checked in
        # __init__); mean_file was already subtracted pre-flip above
        if tp.mean_value:
            mv = np.asarray(list(tp.mean_value), np.float32)
            if len(mv) == 1:
                out = out - mv[0]
            else:
                if len(mv) != c:
                    raise ValueError(
                        f"{len(mv)} mean_values for {c} channels")
                out = out - mv.reshape(1, c, 1, 1)

        if tp.scale != 1.0:
            out = out * tp.scale
        return np.ascontiguousarray(out, np.float32)

    def output_hw(self, h: int, w: int) -> Tuple[int, int]:
        crop = int(self.tp.crop_size)
        return (crop, crop) if crop else (h, w)

    # -- the one-pass pack ------------------------------------------------
    # __call__ above is the definition (and the tests' oracle); it writes
    # the batch as float32 three or four times.  Where the records carry
    # uint8 pixels, `fused` gets the same values, bit for bit, in one
    # native pass from those pixels to the float32 batch.

    def fusable(self, c: int, h: int, w: int) -> bool:
        """Whether `fused` serves this configuration on (c, h, w)
        inputs.  It leaves to __call__ what the kernel does not do —
        a mean plane that is neither full-size nor large enough to
        centre-crop — and what __call__ refuses (a mean_value count or
        a crop that does not fit), so that the refusal stays its own."""
        from .. import native
        if not native.available():
            return False
        if len(self.tp.mean_value) not in (0, 1, c):
            return False
        oh, ow = self.output_hw(h, w)
        if oh > h or ow > w:
            return False
        m = self.mean
        return m is None or (
            m.ndim == 3 and m.shape[0] in (1, c)
            and (m.shape[1:] == (h, w)
                 or (m.shape[1] >= oh and m.shape[2] >= ow)))

    def fused(self, pixels, chw: Tuple[int, int, int],
              draw: Optional[AugDraw] = None,
              num_threads: int = 0) -> np.ndarray:
        """`self(pixels as float32, draw)` in one pass
        (`native.transform_batch`): `pixels` is an (N, C, H, W) uint8 or
        float32 array, or N raw uint8 images of `chw`.  Only where
        `fusable(*chw)`.  The output is an array nobody else refers to
        (a staged batch is read by the device after `device_put`
        returns)."""
        from .. import native
        c, h, w = chw
        if draw is None:
            draw = self.draw(len(pixels), h, w)
        hs, ws = draw.offs if draw.offs is not None else (None, None)
        tp = self.tp
        mean = (np.asarray(list(tp.mean_value), np.float32)
                if tp.mean_value else self.mean)
        return native.transform_batch(
            pixels, chw=chw,
            crop=int(tp.crop_size) if draw.offs is not None else 0,
            h_off=hs, w_off=ws, mirror=draw.flip, mean=mean,
            scale=float(tp.scale), num_threads=num_threads)

    # -- device-side transform (COS_DEVICE_TRANSFORM) ----------------------
    # TPU-first split of the Caffe transform: the host keeps only the
    # RNG-bearing byte moves (crop + mirror, on uint8), and the float
    # work (mean subtraction, scale, dtype) runs inside a jitted stage on
    # the device.  The infeed then carries 1 byte/pixel instead of 4 —
    # 4x less host->device traffic (158 MB -> 40 MB per CaffeNet b256
    # step).  The reference instead transforms to float on CPU and
    # ships float blobs to the GPU (FloatDataTransformer.java:9-40).
    #
    # RNG discipline: host_stage draws crop offsets then mirror flips
    # from self.rng in the SAME order as __call__, so a run with the
    # split enabled consumes the stream identically and the (host crop/
    # mirror, device mean/scale) pipeline reproduces the host-only
    # trajectory exactly (test_device_transform_parity).

    def device_eligible(self, in_h: int, in_w: int) -> bool:
        """The split supports the two mean geometries Caffe produces:
        full-size (subtract-then-crop == per-sample window) and
        output-size (plain broadcast).  Any other mean shape keeps the
        host path (center-crop-the-mean semantics need the pre-crop
        size the device stage doesn't see)."""
        if self.mean is None:
            return True
        oh, ow = self.output_hw(in_h, in_w)
        return tuple(self.mean.shape[1:]) in {(in_h, in_w), (oh, ow)}

    def host_stage(self, batch: np.ndarray,
                   draw: Optional[AugDraw] = None):
        """(N,C,H,W) integral-valued pixels -> (uint8 batch cropped +
        mirrored, aux int32 (N,3) of [h_off, w_off, flip]).  Crop and
        flip come from the same draw() the host-only path uses (or a
        pre-drawn AugDraw in the pooled pipeline), so the two pipelines
        consume self.rng identically.  The byte moves run in the
        threaded native kernel (cos_crop_mirror_u8) when built; numpy
        otherwise — identical output either way (test_native.py
        parity)."""
        n, c, h, w = batch.shape
        crop = int(self.tp.crop_size)
        u8 = batch.astype(np.uint8) if batch.dtype != np.uint8 else batch
        if draw is None:
            draw = self.draw(n, h, w)
        offs = draw.offs
        if offs is not None:
            hs, ws = offs
        else:
            hs = np.zeros(n, np.int64)
            ws = np.zeros(n, np.int64)
        flip = draw.flip
        aux = np.stack([hs, ws, flip.astype(np.int64)],
                       axis=1).astype(np.int32)

        from .. import native
        if native.available():
            out = native.crop_mirror_u8(
                u8, hs, ws, flip,
                crop=crop if offs is not None else 0)
            return out, aux

        if offs is not None:
            u8 = np.stack([u8[i, :, hs[i]:hs[i] + crop,
                              ws[i]:ws[i] + crop] for i in range(n)])
        else:
            u8 = u8.copy()
        if flip.any():
            u8[flip] = u8[flip, :, :, ::-1]
        return np.ascontiguousarray(u8), aux

    def device_stage_fn(self, out_dtype=None):
        """Jittable (x_uint8, aux) -> transformed float batch, closing
        over the mean/scale constants.  Subtracting the per-sample
        (h_off, w_off) window of the full-size mean, flipped where the
        image was flipped, is algebraically identical to Caffe's
        subtract-at-source-pixel-then-crop-and-mirror order
        (data_transformer.cpp; see __call__'s comments)."""
        import jax
        import jax.numpy as jnp

        tp = self.tp
        mean = self.mean
        mv = np.asarray(list(tp.mean_value), np.float32) \
            if tp.mean_value else None
        scale = float(tp.scale)

        def apply(x, aux):
            out = x.astype(jnp.float32)
            n, c, ch, cw = x.shape
            if mean is not None:
                m = jnp.asarray(mean, jnp.float32)
                if m.shape[1] == ch and m.shape[2] == cw:
                    win = jnp.broadcast_to(m[None], (n,) + m.shape)
                else:
                    # full-size mean (device_eligible guarantees it):
                    # per-sample window at the image's own crop offset
                    def window(a):
                        return jax.lax.dynamic_slice(
                            m, (0, a[0], a[1]), (m.shape[0], ch, cw))
                    win = jax.vmap(window)(aux)
                flip = aux[:, 2].astype(bool)[:, None, None, None]
                win = jnp.where(flip, win[..., ::-1], win)
                out = out - win
            if mv is not None:
                if len(mv) == 1:
                    out = out - mv[0]
                else:
                    out = out - mv.reshape(1, c, 1, 1)
            if scale != 1.0:
                out = out * scale
            if out_dtype is not None:
                out = out.astype(out_dtype)
            return out

        return apply
