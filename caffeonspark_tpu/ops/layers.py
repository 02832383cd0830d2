"""Caffe layer semantics as pure JAX functions (TPU-first).

Each layer type registers:
  * ``param_specs(lp, bottom_shapes)`` → list of (blob_name, shape, filler)
    for its learnable blobs (order == Caffe blob order, so `.caffemodel`
    import/export maps 1:1), and
  * ``apply(ctx, lp, params, bottoms)`` → list of top arrays.

Layout is Caffe-logical NCHW at layer boundaries; XLA's TPU layout
assignment maps convs/matmuls onto the MXU, so no manual NHWC plumbing is
needed for correctness, and compute-heavy paths stay fused under one jit.

Caffe behaviors reproduced (the "hard parts" of SURVEY.md §7):
  * pooling ceil-mode output sizing with tail-window clipping,
  * AVE pooling divisor = window ∩ padded region (not kernel area),
  * LRN ACROSS_CHANNELS uses alpha/local_size,
  * SoftmaxWithLoss VALID normalization + ignore_label,
  * Dropout inverted scaling at train time,
  * LSTM cont-gated recurrence (gate order i,f,o,g), time-major (T,B,·).

Reference equivalents: caffe-public layer implementations consumed via
`CaffeNet.cpp` (see SURVEY.md §2.5, §2.9 layer list).
"""

from __future__ import annotations

import contextlib
import copy
import functools
import math
import os
import zlib
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..proto.caffe import (EltwiseOp, FillerParameter, LayerParameter,
                           NormalizationMode, NormRegion, PoolMethod)
from ..utils.flops import visible_scores
from . import route
from .recompute import keep, stage

Array = jax.Array


@dataclass
class Ctx:
    """Per-call context threaded through layer application."""
    train: bool = False
    rng: Optional[Array] = None          # folded per-layer inside Net.apply
    state_in: Dict[str, List[Array]] = field(default_factory=dict)
    state_out: Dict[str, List[Array]] = field(default_factory=dict)
    layer_name: str = ""
    # LRN layer names whose op applies relu in-kernel (net.py's
    # COS_FUSE_RELU_LRN peephole)
    fused_relu_lrn: frozenset = frozenset()
    # this layer's autotune variant (per-layer precision/layout/fusion
    # plan entry, resolved ONCE at Net construction — ops must never
    # read env for these; None = no override, the inert default)
    variant: Optional[Dict] = None
    # conv-stem bias fusion (net.py peephole, generalized): conv layer
    # names whose bias add is deferred into the consuming LRN kernel,
    # and the LRN layer names that receive the bias as params[0]
    defer_bias: frozenset = frozenset()
    bias_lrn: frozenset = frozenset()
    # per-blob dequant scales for quantized-resident serving weights
    # ({layer: {blob: f32 scalar}}, serving/quant.py): an int8 weight
    # arriving at an op finds its max-abs scale here and runs the
    # dequant-free kernel path instead of quantizing per call
    qscales: Optional[Dict] = None

    def qscale(self, bname: str):
        if not self.qscales:
            return None
        return self.qscales.get(self.layer_name, {}).get(bname)

    def take_rng(self) -> Array:
        assert self.rng is not None, "layer needs rng but none provided"
        return jax.random.fold_in(self.rng, stable_hash(self.layer_name))

    def precision(self):
        """MXU precision pin for this layer's contractions: a layer the
        autotune plan holds at float32 computes at HIGHEST precision
        (the COS002 precision-floor discipline — an f32 variant that
        still multiplied in bf16 passes would be a lie); None
        otherwise (jax default)."""
        if self.variant and self.variant.get("dtype") == "float32":
            return jax.lax.Precision.HIGHEST
        return None


def stable_hash(name: str) -> int:
    """Process-independent name hash (Python's hash() is randomized per
    interpreter, which would break random_seed reproducibility)."""
    return zlib.crc32(name.encode("utf-8"))


_REGISTRY: Dict[str, "LayerOp"] = {}


def _no_params(lp, shapes):
    return []


@dataclass
class LayerOp:
    name: str
    apply: Callable
    param_specs: Callable = _no_params
    is_loss: bool = False
    is_data: bool = False
    # layer updates running statistics in the forward pass and must run
    # in f32 (exempt from compute-dtype casts and rematerialization)
    f32_stats: bool = False
    # positions of bottoms that carry class / row ids as floats (Caffe's
    # convention): exempt from compute-dtype casts — bf16 keeps 8
    # significant bits, so an id above 256 would be rounded to another
    # id (and 999 to 1000, out of range: a NaN loss on the chip, PR 21)
    index_bottoms: Tuple[int, ...] = ()
    # (lp, specs, tops) -> the layer's forward FLOPs, from the blobs it
    # computes with [(name, shape, filler)] and its tops {name: shape};
    # None: the count of `utils.flops` holds (every weight of two or
    # more axes once a position of the first top)
    flops: Optional[Callable] = None
    # (lp) -> None where the layer's time axis may be cut over the `sp`
    # mesh axis, else (what kind of layer this is, why it may not): the
    # two halves of `parallel.sp.refuse_time_sharding`'s message; None:
    # the type has no time axis of its own, any cut is the bottoms'
    time_sharding: Optional[Callable] = None


def register(name: str, *, params=_no_params, is_loss=False, is_data=False,
             f32_stats=False, index_bottoms=(), flops=None,
             time_sharding=None):
    """A layer type is declared here and nowhere else: what other
    modules have to know of it (`utils.flops`, `parallel.sp`) they ask
    its `LayerOp`.  A type with blobs of its own that is not one of
    Caffe's states both `flops` and `time_sharding`
    (`tests/test_layer_facts.py` holds every registered type to it)."""
    def deco(fn):
        _REGISTRY[name] = LayerOp(name, fn, params,
                                  is_loss=is_loss, is_data=is_data,
                                  f32_stats=f32_stats,
                                  index_bottoms=tuple(index_bottoms),
                                  flops=flops, time_sharding=time_sharding)
        return fn
    return deco


def _rows(tops) -> int:
    """Positions of a time-major layer: the first top's (T, B, ...)
    without its feature axis."""
    return math.prod(next(iter(tops.values()))[:-1])


def _products_flops(lp, specs, tops) -> int:
    """Every product per position: the blobs called W_..., each applied
    whole to every row; gates, taps and norms are elementwise passes
    (HBM-bound), not counted."""
    return 2 * _rows(tops) * sum(math.prod(ps) for nm, ps, _ in specs
                                 if nm.startswith("W_"))


def _no_flops(lp, specs, tops) -> int:
    """A gather or an elementwise pass: nothing for the MXU."""
    return 0


def _cut_anywhere(lp):
    """Every row is computed by itself: the time axis may be cut."""
    return None


def get_op(type_name: str) -> LayerOp:
    if type_name not in _REGISTRY:
        raise NotImplementedError(f"layer type {type_name!r} not supported")
    return _REGISTRY[type_name]


def supported_types() -> List[str]:
    return sorted(_REGISTRY)


def _filler(msg, default_type="constant") -> FillerParameter:
    if isinstance(msg, FillerParameter):
        return msg
    return FillerParameter(type=default_type)


# ---------------------------------------------------------------------------
# data layers — net inputs; shapes resolved by the net compiler
# ---------------------------------------------------------------------------

def _net_input(ctx, lp, params, bottoms):
    raise RuntimeError("data layers are net inputs; never applied")


for _type in ("MemoryData", "CoSData", "Input", "Data", "HDF5Data",
              "DummyData", "ImageData"):
    register(_type, is_data=True)(_net_input)


@register("HDF5Output")
def _hdf5_output(ctx, lp, params, bottoms):
    """hdf5_output_layer.cpp: an output sink — file I/O cannot live
    inside a jitted forward, so the bottoms are recorded in the forward
    state under 'hdf5_output:<name>' and the runtime writes them with
    `data.hdf5.write_hdf5_outputs` (see Net.apply's second return)."""
    ctx.state_out["hdf5_output:" + ctx.layer_name] = list(bottoms)
    return []


# ---------------------------------------------------------------------------
# Convolution / Deconvolution / InnerProduct / Embed
# ---------------------------------------------------------------------------

def _conv_geometry(cp):
    def pair(rep, h, w, default):
        if cp.has(h) or cp.has(w):
            if not (cp.has(h) and cp.has(w)):
                raise ValueError(f"{h} and {w} must be set together")
            return (int(getattr(cp, h)), int(getattr(cp, w)))
        v = getattr(cp, rep)
        if isinstance(v, list):
            if len(v) == 0:
                return (default, default)
            if len(v) == 1:
                return (int(v[0]), int(v[0]))
            return (int(v[0]), int(v[1]))
        return (int(v), int(v))

    kernel = pair("kernel_size", "kernel_h", "kernel_w", None)
    if kernel[0] is None:
        raise ValueError("convolution_param needs kernel_size or "
                         "kernel_h/kernel_w")
    stride = pair("stride", "stride_h", "stride_w", 1)
    pad = pair("pad", "pad_h", "pad_w", 0)
    dil = cp.dilation
    dilation = ((int(dil[0]), int(dil[-1] if len(dil) > 1 else dil[0]))
                if dil else (1, 1))
    return kernel, stride, pad, dilation


def _conv_params(lp, shapes):
    cp = lp.convolution_param
    (kh, kw), _, _, _ = _conv_geometry(cp)
    c_in = shapes[0][1]
    group = max(1, cp.group)
    specs = [("weight", (cp.num_output, c_in // group, kh, kw),
              _filler(cp.weight_filler if lp.convolution_param.has(
                  "weight_filler") else None))]
    if cp.bias_term:
        specs.append(("bias", (cp.num_output,),
                      _filler(cp.bias_filler if cp.has("bias_filler")
                              else None)))
    return specs


def _s2d_geometry_ok(c_in, cp, kh, kw, sh, sw, dh, dw) -> bool:
    """Geometric eligibility for the space-to-depth stem rewrite:
    C_in<=4, square stride>=2, no dilation, no groups.  Separated from
    the enable decision so the autotuner can both force the rewrite on
    a layer and enumerate it from blob shapes — ONE copy of the rule."""
    return (c_in <= 4 and sh == sw and sh >= 2
            and dh == dw == 1 and max(1, cp.group) == 1)


def _s2d_eligible(x, cp, kh, kw, sh, sw, dh, dw) -> bool:
    """Stem convs (C_in<=4, stride>=2) hit the MXU badly: the 8-lane
    channel padding and the strided 11x11/7x7 window waste most of the
    systolic array.  Space-to-depth by the stride factor rewrites them
    as dense stride-1 convs over C_in*s^2 channels — the standard TPU
    stem transform (MLPerf ResNet).  Same multiply-adds in a different
    summation order, so results match the direct conv to float-rounding
    tolerance, not bitwise (like any XLA layout change).  On by default
    on TPU; COS_CONV_S2D=0 forces the direct conv everywhere."""
    env = os.environ.get("COS_CONV_S2D")
    if env is not None:
        enabled = env == "1"
    else:
        enabled = route.on_tpu()
    return enabled and _s2d_geometry_ok(x.shape[1], cp, kh, kw, sh, sw,
                                        dh, dw)


def _conv_layout() -> str:
    """COS_CONV_LAYOUT=NHWC requests NHWC-internal convolutions: the
    logical NCHW operands are transposed around an NHWC/HWIO conv.  XLA's
    transpose-folding absorbs the wrappers into the conv's dimension
    numbers, so the net effect is a layout *hint* — channels land on the
    minormost (lane) dimension without a layout-assignment round trip.
    A/B lever for the roofline experiments (docs/benchmarks.md); numerics
    are identical to float rounding.  Default NCHW."""
    return os.environ.get("COS_CONV_LAYOUT", "NCHW").upper()


def _nhwc_conv(x, w, strides, padding, rhs_dilation, groups,
               precision=None):
    """x (N,C,H,W), w (O,I/g,kh,kw) → NHWC-internal conv → (N,O,oh,ow)."""
    xt = x.transpose(0, 2, 3, 1)
    wt = w.transpose(2, 3, 1, 0)  # OIHW → HWIO
    out = lax.conv_general_dilated(
        xt, wt, window_strides=strides, padding=padding,
        rhs_dilation=rhs_dilation, feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)
    return out.transpose(0, 3, 1, 2)


def _s2d_conv(x, w, s, kh, kw, ph, pw, precision=None):
    """stride-s conv as a stride-1 conv over s x s space-to-depth blocks.

    x: (N, C, H, W) already conceptually padded by (ph, pw) — padding is
    applied here together with the tail pad/crop to the block grid.
    w: (O, C, kh, kw).  Output identical to
    conv(x, w, stride=s, pad=(ph, pw))."""
    n, c, h, wd = x.shape
    o_h = (h + 2 * ph - kh) // s + 1
    o_w = (wd + 2 * pw - kw) // s + 1
    kb_h = (kh - 1) // s + 1
    kb_w = (kw - 1) // s + 1
    gh, gw = o_h + kb_h - 1, o_w + kb_w - 1
    # pad left with conv padding, right up/down to the block grid
    xt = jnp.pad(x, ((0, 0), (0, 0),
                     (ph, max(0, gh * s - h - ph)),
                     (pw, max(0, gw * s - wd - pw))))
    xt = xt[:, :, :gh * s, :gw * s]
    xt = xt.reshape(n, c, gh, s, gw, s).transpose(0, 1, 3, 5, 2, 4)
    xt = xt.reshape(n, c * s * s, gh, gw)
    oc = w.shape[0]
    wp = jnp.pad(w, ((0, 0), (0, 0),
                     (0, kb_h * s - kh), (0, kb_w * s - kw)))
    wp = wp.reshape(oc, c, kb_h, s, kb_w, s).transpose(0, 1, 3, 5, 2, 4)
    wp = wp.reshape(oc, c * s * s, kb_h, kb_w)
    return lax.conv_general_dilated(
        xt, wp, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=precision)


@register("Convolution", params=_conv_params)
def _conv(ctx, lp, params, bottoms):
    cp = lp.convolution_param
    (kh, kw), (sh, sw), (ph, pw), (dh, dw) = _conv_geometry(cp)
    x = bottoms[0]
    w = params[0]
    # per-layer autotune variant (resolved at Net construction) beats
    # the global env knobs; absent a variant the env behavior is
    # byte-identical to pre-autotune
    v = ctx.variant or {}
    layout = (v.get("layout") or "").lower()
    prec = ctx.precision()
    # no preferred_element_type: the TPU MXU accumulates in f32
    # internally either way, and forcing an f32 output breaks the
    # conv transpose (backward) for bf16 nets with a dtype mismatch
    if layout == "nhwc" or (not layout and _conv_layout() == "NHWC"):
        # NHWC experiment measures the plain conv, not the s2d rewrite —
        # one variable at a time (s2d is itself a layout transform).
        out = _nhwc_conv(x, w, (sh, sw), [(ph, ph), (pw, pw)],
                         (dh, dw), max(1, cp.group), precision=prec)
    elif (layout == "s2d"
          and _s2d_geometry_ok(x.shape[1], cp, kh, kw, sh, sw, dh, dw)) \
            or (not layout
                and _s2d_eligible(x, cp, kh, kw, sh, sw, dh, dw)):
        out = _s2d_conv(x, w, sh, kh, kw, ph, pw, precision=prec)
    else:
        out = lax.conv_general_dilated(
            x, w, window_strides=(sh, sw), padding=[(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw), feature_group_count=max(1, cp.group),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=prec)
    if cp.bias_term and ctx.layer_name not in ctx.defer_bias:
        # defer_bias: the bias add (and relu+LRN) runs in the consuming
        # LRN layer's fused epilogue (net.py stem peephole)
        out = out + params[1].reshape(1, -1, 1, 1)
    return [out]


def _deconv_params(lp, shapes):
    cp = lp.convolution_param
    (kh, kw), _, _, _ = _conv_geometry(cp)
    c_in = shapes[0][1]
    group = max(1, cp.group)
    # Caffe Deconvolution weight blob: (C_in, N/group, kh, kw)
    specs = [("weight", (c_in, cp.num_output // group, kh, kw),
              _filler(cp.weight_filler if cp.has("weight_filler") else None))]
    if cp.bias_term:
        specs.append(("bias", (cp.num_output,),
                      _filler(cp.bias_filler if cp.has("bias_filler")
                              else None)))
    return specs


@register("Deconvolution", params=_deconv_params)
def _deconv(ctx, lp, params, bottoms):
    """Caffe deconv = gradient of conv wrt its input: output size
    s·(i−1) + k − 2p.  Expressed as an input-dilated convolution with a
    spatially flipped kernel and per-side padding (k−1−p), which XLA maps
    onto the MXU like any conv."""
    cp = lp.convolution_param
    (kh, kw), (sh, sw), (ph, pw), (dh, dw) = _conv_geometry(cp)
    x = bottoms[0]
    w = params[0]  # (C_in, C_out/g, kh, kw)
    g = max(1, cp.group)
    c_in = w.shape[0]
    c_out = w.shape[1] * g
    # (C_in, C_out/g, kh, kw) → (C_out, C_in/g, kh, kw), spatially flipped
    wk = w.reshape(g, c_in // g, c_out // g, kh, kw)
    wk = wk.transpose(0, 2, 1, 3, 4).reshape(c_out, c_in // g, kh, kw)
    wk = wk[:, :, ::-1, ::-1]
    ekh = (kh - 1) * dh + 1  # effective (dilated) kernel extent
    ekw = (kw - 1) * dw + 1
    out = lax.conv_general_dilated(
        x, wk, window_strides=(1, 1),
        padding=[(ekh - 1 - ph, ekh - 1 - ph), (ekw - 1 - pw, ekw - 1 - pw)],
        lhs_dilation=(sh, sw), rhs_dilation=(dh, dw),
        feature_group_count=g,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    if cp.bias_term:
        out = out + params[1].reshape(1, -1, 1, 1)
    return [out]


def _ip_params(lp, shapes):
    ip = lp.inner_product_param
    axis = ip.axis
    k = math.prod(shapes[0][axis:])
    shape = (k, ip.num_output) if ip.transpose else (ip.num_output, k)
    specs = [("weight", shape,
              _filler(ip.weight_filler if ip.has("weight_filler") else None))]
    if ip.bias_term:
        specs.append(("bias", (ip.num_output,),
                      _filler(ip.bias_filler if ip.has("bias_filler")
                              else None)))
    return specs


@register("InnerProduct", params=_ip_params)
def _inner_product(ctx, lp, params, bottoms):
    ip = lp.inner_product_param
    axis = ip.axis
    x = bottoms[0]
    lead = x.shape[:axis]
    x2 = x.reshape((math.prod(lead), -1))
    w = params[0]
    v = ctx.variant or {}
    if not ctx.train and w.dtype == jnp.int8:
        # quantized-RESIDENT serving weight (serving/quant.py): the
        # blob was quantized once at ModelRegistry.publish and lives
        # in HBM as the int8 operand itself — the kernel consumes it
        # with its cached max-abs scale, no per-call re-quantization
        from .pallas_kernels import int8_inner_product
        y = int8_inner_product(x2, w, transpose=bool(ip.transpose),
                               w_scale=ctx.qscale("weight"))
    elif v.get("int8") and not ctx.train:
        # quantized serving forward (autotune variant; TEST-phase nets
        # only — net.py refuses int8 on a TRAIN net): int8×int8 MXU
        # matmul on per-blob max-abs scales, int32 accumulation
        from .pallas_kernels import int8_inner_product
        y = int8_inner_product(x2, w, transpose=bool(ip.transpose))
    else:
        prec = ctx.precision()
        y = (jnp.matmul(x2, w, precision=prec) if ip.transpose
             else jnp.matmul(x2, w.T, precision=prec))
    if ip.bias_term:
        y = y + params[1]
    return [y.reshape(lead + (ip.num_output,))]


def _embed_params(lp, shapes):
    ep = lp.embed_param
    specs = [("weight", (ep.input_dim, ep.num_output),
              _filler(ep.weight_filler if ep.has("weight_filler") else None))]
    if ep.bias_term:
        specs.append(("bias", (ep.num_output,),
                      _filler(ep.bias_filler if ep.has("bias_filler")
                              else None)))
    return specs


@register("Embed", params=_embed_params, index_bottoms=(0,),
          flops=_no_flops)
def _embed(ctx, lp, params, bottoms):
    ep = lp.embed_param
    idx = bottoms[0].astype(jnp.int32)
    out = jnp.take(params[0], idx, axis=0)
    if ep.bias_term:
        out = out + params[1]
    return [out]


# ---------------------------------------------------------------------------
# Pooling (Caffe ceil-mode + divisor semantics)
# ---------------------------------------------------------------------------

def pool_output_dim(size: int, kernel: int, stride: int, pad: int) -> int:
    out = int(math.ceil((size + 2 * pad - kernel) / stride)) + 1
    if pad > 0 and (out - 1) * stride >= size + pad:
        out -= 1
    return out


@register("Pooling")
def _pooling(ctx, lp, params, bottoms):
    pp = lp.pooling_param
    x = bottoms[0]
    n, c, h, w = x.shape
    if pp.global_pooling:
        kh, kw = h, w
        sh = sw = 1
        ph = pw = 0
    else:
        for a, b in (("kernel_h", "kernel_w"), ("stride_h", "stride_w"),
                     ("pad_h", "pad_w")):
            if pp.has(a) != pp.has(b):
                raise ValueError(f"pooling_param: {a} and {b} must be set "
                                 "together")
        kh = int(pp.kernel_h) if pp.has("kernel_h") else int(pp.kernel_size)
        kw = int(pp.kernel_w) if pp.has("kernel_w") else int(pp.kernel_size)
        if kh == 0 or kw == 0:
            raise ValueError("pooling_param needs kernel_size or "
                             "kernel_h/kernel_w")
        sh = int(pp.stride_h) if pp.has("stride_h") else int(pp.stride)
        sw = int(pp.stride_w) if pp.has("stride_w") else int(pp.stride)
        ph = int(pp.pad_h) if pp.has("pad_h") else int(pp.pad)
        pw = int(pp.pad_w) if pp.has("pad_w") else int(pp.pad)
    oh = pool_output_dim(h, kh, sh, ph)
    ow = pool_output_dim(w, kw, sw, pw)
    # explicit asymmetric padding so the ceil-mode tail window exists
    eh = max(0, (oh - 1) * sh + kh - h - ph)
    ew = max(0, (ow - 1) * sw + kw - w - pw)
    if pp.pool == PoolMethod.MAX:
        xp = jnp.pad(x, ((0, 0), (0, 0), (ph, eh), (pw, ew)),
                     constant_values=-jnp.inf)
        out = lax.reduce_window(xp, -jnp.inf, lax.max,
                                (1, 1, kh, kw), (1, 1, sh, sw), "VALID")
    elif pp.pool == PoolMethod.AVE:
        xp = jnp.pad(x, ((0, 0), (0, 0), (ph, eh), (pw, ew)))
        s = lax.reduce_window(xp, 0.0, lax.add,
                              (1, 1, kh, kw), (1, 1, sh, sw), "VALID")
        # Caffe divisor: overlap of each window with the symmetric padded
        # region [0, size + 2*pad), NOT the raw kernel area
        ones_h = jnp.ones((1, 1, h + 2 * ph, 1), x.dtype)
        ones_w = jnp.ones((1, 1, 1, w + 2 * pw), x.dtype)
        ones_h = jnp.pad(ones_h, ((0, 0), (0, 0), (0, max(0, eh - ph)),
                                  (0, 0)))
        ones_w = jnp.pad(ones_w, ((0, 0), (0, 0), (0, 0),
                                  (0, max(0, ew - pw))))
        div_h = lax.reduce_window(ones_h, 0.0, lax.add, (1, 1, kh, 1),
                                  (1, 1, sh, 1), "VALID")
        div_w = lax.reduce_window(ones_w, 0.0, lax.add, (1, 1, 1, kw),
                                  (1, 1, 1, sw), "VALID")
        out = s / (div_h * div_w)
    elif pp.pool == PoolMethod.STOCHASTIC:
        # Caffe pooling_layer.cu PoolForward{Train,Test}: activations are
        # assumed non-negative (post-ReLU).  TRAIN samples one element per
        # window with probability value/sum(window); TEST outputs the
        # activation-weighted mean sum(a^2)/sum(a) (0 when the window sums
        # to 0).  Caffe forbids padding for STOCHASTIC (pooling_layer.cpp
        # SetUp check); zero padding is harmless here (zeros are never
        # sampled unless the whole window is zero).
        if ctx.train:
            patches = lax.conv_general_dilated_patches(
                x, (kh, kw), (sh, sw), [(ph, eh), (pw, ew)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            p = patches.reshape(n, c, kh * kw, oh, ow)
            # selection math in f32: in bf16 `u` can be exactly 0
            # (~2^-8) or cumsum can round below u*total, degenerating
            # argmax to index 0 and biasing sampling toward the
            # window's top-left element
            cum = jnp.cumsum(p.astype(jnp.float32), axis=2)
            total = cum[:, :, -1]        # Caffe accumulates, not re-sums
            u = jax.random.uniform(ctx.take_rng(), total.shape,
                                   dtype=jnp.float32, minval=1e-7,
                                   maxval=1.0)
            # first window index whose running sum crosses u * total
            idx = jnp.argmax(cum >= (u * total)[:, :, None], axis=2)
            out = jnp.take_along_axis(p, idx[:, :, None], axis=2)[:, :, 0]
        else:
            # weighted mean sum(a^2)/sum(a) via two reduce_windows — no
            # kh*kw patch materialization on the eval path
            xf = x.astype(jnp.float32)
            xp = jnp.pad(xf, ((0, 0), (0, 0), (ph, eh), (pw, ew)))
            total = lax.reduce_window(xp, 0.0, lax.add,
                                      (1, 1, kh, kw), (1, 1, sh, sw),
                                      "VALID")
            sq = lax.reduce_window(xp * xp, 0.0, lax.add,
                                   (1, 1, kh, kw), (1, 1, sh, sw),
                                   "VALID")
            out = jnp.where(total > 0, sq / jnp.where(total > 0, total, 1),
                            0.0).astype(x.dtype)
    else:
        raise NotImplementedError(f"pooling method {pp.pool}")
    return [out]


# ---------------------------------------------------------------------------
# elementwise activations
# ---------------------------------------------------------------------------

@register("ReLU")
def _relu(ctx, lp, params, bottoms):
    slope = lp.relu_param.negative_slope
    x = bottoms[0]
    if slope:
        return [jnp.where(x > 0, x, slope * x)]
    return [jax.nn.relu(x)]


def _prelu_params(lp, shapes):
    n = 1 if lp.prelu_param.channel_shared else shapes[0][1]
    f = (lp.prelu_param.filler if lp.prelu_param.has("filler")
         else FillerParameter(type="constant", value=0.25))
    return [("slope", (n,), f)]


@register("PReLU", params=_prelu_params)
def _prelu(ctx, lp, params, bottoms):
    x = bottoms[0]
    a = params[0].reshape((1, -1) + (1,) * (x.ndim - 2))
    return [jnp.where(x > 0, x, a * x)]


@register("ELU")
def _elu(ctx, lp, params, bottoms):
    a = lp.elu_param.alpha
    x = bottoms[0]
    return [jnp.where(x > 0, x, a * (jnp.exp(x) - 1.0))]


@register("Sigmoid")
def _sigmoid(ctx, lp, params, bottoms):
    return [jax.nn.sigmoid(bottoms[0])]


@register("TanH")
def _tanh(ctx, lp, params, bottoms):
    return [jnp.tanh(bottoms[0])]


@register("AbsVal")
def _absval(ctx, lp, params, bottoms):
    return [jnp.abs(bottoms[0])]


@register("BNLL")
def _bnll(ctx, lp, params, bottoms):
    x = bottoms[0]
    return [jnp.where(x > 0, x + jnp.log1p(jnp.exp(-x)),
                      jnp.log1p(jnp.exp(x)))]


@register("Power")
def _power(ctx, lp, params, bottoms):
    p = lp.power_param
    y = p.shift + p.scale * bottoms[0]
    if p.power != 1.0:
        y = jnp.power(y, p.power)
    return [y]


@register("Exp")
def _exp(ctx, lp, params, bottoms):
    p = lp.exp_param
    x = p.shift + p.scale * bottoms[0]
    if p.base > 0:
        return [jnp.power(p.base, x)]
    return [jnp.exp(x)]


@register("Log")
def _log(ctx, lp, params, bottoms):
    p = lp.log_param
    x = p.shift + p.scale * bottoms[0]
    y = jnp.log(x)
    if p.base > 0:
        y = y / math.log(p.base)
    return [y]


@register("Threshold")
def _threshold(ctx, lp, params, bottoms):
    t = lp.threshold_param.threshold
    return [(bottoms[0] > t).astype(bottoms[0].dtype)]


@register("Dropout")
def _dropout(ctx, lp, params, bottoms):
    ratio = lp.dropout_param.dropout_ratio
    x = bottoms[0]
    if not ctx.train or ratio == 0.0:
        return [x]
    keep = 1.0 - ratio
    mask = jax.random.bernoulli(ctx.take_rng(), keep, x.shape)
    return [jnp.where(mask, x / keep, 0.0)]


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@register("LRN")
def _lrn(ctx, lp, params, bottoms):
    p = lp.lrn_param
    x = bottoms[0]
    n = int(p.local_size)
    alpha, beta, k = p.alpha, p.beta, p.k
    # net.py's ReLU→LRN peephole routed the pre-activation here: apply
    # relu in-kernel (pallas) or inline (XLA fallback) — identical
    # semantics on every backend
    fuse_relu = lp.name in ctx.fused_relu_lrn
    kernel = route.kernel(x.ndim == 4, mesh="shard_map")
    if lp.name in ctx.bias_lrn:
        # generalized stem epilogue (net.py bias peephole): the
        # producing conv's bias arrives as params[0] and bias-add +
        # relu + LRN run in one fused pass (pallas on TPU, the
        # identical-semantics XLA chain elsewhere)
        from .pallas_kernels import (bias_relu_lrn_across_channels,
                                     xla_bias_relu_lrn)
        bias = params[0]
        if kernel:
            return [_on_batch_shards(
                kernel.mesh, lambda a, b: bias_relu_lrn_across_channels(
                    a, b, n, alpha, beta, k, kernel.interpret), x, bias)]
        return [xla_bias_relu_lrn(x, bias, n, alpha, beta, k)]
    if p.norm_region == NormRegion.ACROSS_CHANNELS:
        from .pallas_kernels import lrn_across_channels
        if kernel:
            # fused VMEM-resident kernel on TPU, with a matching fused
            # VJP kernel so the training path stays on Pallas
            return [_on_batch_shards(
                kernel.mesh, lambda a: lrn_across_channels(
                    a, n, alpha, beta, k, kernel.interpret, fuse_relu), x)]
        if fuse_relu:
            x = jnp.maximum(x, 0)
        # one shared XLA fallback chain (pallas_kernels owns it so the
        # fused-epilogue fallback can never drift from this path)
        from .pallas_kernels import xla_lrn_across_channels
        return [xla_lrn_across_channels(x, n, alpha, beta, k)]
    else:  # WITHIN_CHANNEL: spatial window average of squares
        sq = x * x
        pad = n // 2
        sqp = jnp.pad(sq, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        s = lax.reduce_window(sqp, 0.0, lax.add, (1, 1, n, n),
                              (1, 1, 1, 1), "VALID")
        scale = k + (alpha / (n * n)) * s
    return [x / jnp.power(scale, beta)]


@register("MVN")
def _mvn(ctx, lp, params, bottoms):
    p = lp.mvn_param
    x = bottoms[0]
    axes = (1, 2, 3) if p.across_channels else (2, 3)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    y = x - mean
    if p.normalize_variance:
        var = jnp.mean(y * y, axis=axes, keepdims=True)
        y = y / (jnp.sqrt(var) + p.eps)
    return [y]


def _bn_params(lp, shapes):
    c = shapes[0][1]
    zero = FillerParameter(type="constant", value=0.0)
    return [("mean", (c,), zero), ("variance", (c,), zero),
            ("count", (1,), zero)]


@register("BatchNorm", params=_bn_params, f32_stats=True)
def _batch_norm(ctx, lp, params, bottoms):
    p = lp.batch_norm_param
    x = bottoms[0]
    eps = p.eps
    use_global = (p.use_global_stats if p.has("use_global_stats")
                  else not ctx.train)
    mean_b, var_b, count = params
    if use_global:
        scale = jnp.where(count[0] == 0, 1.0, 1.0 / count[0])
        mean = mean_b * scale
        var = var_b * scale
    else:
        axes = (0,) + tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes)
        var = jnp.mean(jnp.square(x), axis=axes) - jnp.square(mean)
        maf = p.moving_average_fraction
        # Caffe accumulates the UNBIASED variance into blobs_[1]
        # (batch_norm_layer.cpp bias_correction_factor m/(m-1),
        # m = elements per channel)
        m = x.shape[0] * math.prod(x.shape[2:])
        bias_corr = m / (m - 1.0) if m > 1 else 1.0
        ctx.state_out[ctx.layer_name] = [
            mean_b * maf + mean, var_b * maf + var * bias_corr,
            count * maf + 1.0]
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return [(x - mean.reshape(shape))
            / jnp.sqrt(var.reshape(shape) + eps)]


def _scale_params(lp, shapes):
    p = lp.scale_param
    if len(shapes) > 1:
        # two-bottom Scale: the multiplier IS bottom[1]; only an optional
        # bias blob is learnable (its shape follows bottom[1])
        if not p.bias_term:
            return []
        bf = (p.bias_filler if p.has("bias_filler")
              else FillerParameter(type="constant", value=0.0))
        return [("bias", tuple(shapes[1]), bf)]
    axis = p.axis if p.axis >= 0 else len(shapes[0]) + p.axis
    num_axes = p.num_axes
    if num_axes == -1:
        shape = shapes[0][axis:]
    else:
        shape = shapes[0][axis:axis + num_axes]
    f = p.filler if p.has("filler") else FillerParameter(type="constant",
                                                        value=1.0)
    specs = [("scale", tuple(shape), f)]
    if p.bias_term:
        bf = (p.bias_filler if p.has("bias_filler")
              else FillerParameter(type="constant", value=0.0))
        specs.append(("bias", tuple(shape), bf))
    return specs


@register("Scale", params=_scale_params)
def _scale(ctx, lp, params, bottoms):
    p = lp.scale_param
    x = bottoms[0]
    g = bottoms[1] if len(bottoms) > 1 else params[0]
    bias = None
    if p.bias_term:
        bias = params[0] if len(bottoms) > 1 else params[1]
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    shape = [1] * x.ndim
    for i, d in enumerate(g.shape):
        shape[axis + i] = d
    y = x * g.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return [y]


def _bias_params(lp, shapes):
    p = lp.bias_param
    axis = p.axis if p.axis >= 0 else len(shapes[0]) + p.axis
    if p.num_axes == -1:
        shape = shapes[0][axis:]
    else:
        shape = shapes[0][axis:axis + p.num_axes]
    f = p.filler if p.has("filler") else FillerParameter(type="constant")
    return [("bias", tuple(shape), f)]


@register("Bias", params=_bias_params)
def _bias(ctx, lp, params, bottoms):
    p = lp.bias_param
    x = bottoms[0]
    b = bottoms[1] if len(bottoms) > 1 else params[0]
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    shape = [1] * x.ndim
    for i, d in enumerate(b.shape):
        shape[axis + i] = d
    return [x + b.reshape(shape)]


def _parameter_params(lp, shapes):
    shape = tuple(int(d) for d in lp.parameter_param.shape.dim)
    return [("param", shape, FillerParameter(type="constant"))]


@register("Parameter", params=_parameter_params)
def _parameter(ctx, lp, params, bottoms):
    """parameter_layer.hpp: the top IS a learnable blob of the given
    shape (lets arbitrary tensors be optimized, e.g. input embeddings)."""
    return [params[0]]


@register("BatchReindex", index_bottoms=(1,))
def _batch_reindex(ctx, lp, params, bottoms):
    """batch_reindex_layer.cpp: top = bottom[0][bottom[1]] along axis 0
    (gather; gradients scatter-add back through the first bottom)."""
    x, idx = bottoms[0], bottoms[1]
    return [jnp.take(x, idx.astype(jnp.int32).reshape(-1), axis=0)]


@register("SPP")
def _spp(ctx, lp, params, bottoms):
    """Spatial pyramid pooling (spp_layer.cpp): for level i in
    [0, pyramid_height), pool into 2^i x 2^i bins, flatten each level
    and concat channel-wise → fixed-size vector regardless of input
    H, W.  Caffe's GetPoolingParam builds a per-level pooling layer
    with kernel = ceil(dim/bins), stride = kernel, and SYMMETRIC pad
    (remainder+1)/2 on both sides — delegated here to the Pooling
    layer so bin windows and the pooled-dim clip match bit-for-bit
    (weights ported from Caffe SPP nets reproduce)."""
    p = lp.spp_param
    x = bottoms[0]
    n, c, h, w = x.shape
    if not p.has("pyramid_height") or p.pyramid_height < 1:
        raise ValueError("spp_param.pyramid_height must be >= 1")
    if p.pool not in (PoolMethod.MAX, PoolMethod.AVE):
        raise NotImplementedError("SPP: MAX and AVE pooling only")
    outs = []
    for i in range(int(p.pyramid_height)):
        bins = 2 ** i
        kh = -(-h // bins)
        kw = -(-w // bins)
        pool_lp = LayerParameter(name=f"{lp.name}_level{i}",
                                 type="Pooling")
        pool_lp.pooling_param.pool = p.pool
        pool_lp.pooling_param.kernel_h = kh
        pool_lp.pooling_param.kernel_w = kw
        pool_lp.pooling_param.stride_h = kh
        pool_lp.pooling_param.stride_w = kw
        pool_lp.pooling_param.pad_h = (kh * bins - h + 1) // 2
        pool_lp.pooling_param.pad_w = (kw * bins - w + 1) // 2
        pooled = _pooling(ctx, pool_lp, [], [x])[0]
        outs.append(pooled.reshape(n, -1))
    return [jnp.concatenate(outs, axis=1)]


# ---------------------------------------------------------------------------
# shape ops
# ---------------------------------------------------------------------------

@register("Flatten")
def _flatten(ctx, lp, params, bottoms):
    p = lp.flatten_param
    x = bottoms[0]
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    end = p.end_axis if p.end_axis >= 0 else x.ndim + p.end_axis
    shape = x.shape[:axis] + (-1,) + x.shape[end + 1:]
    return [x.reshape(shape)]


@register("Reshape")
def _reshape(ctx, lp, params, bottoms):
    p = lp.reshape_param
    x = bottoms[0]
    dims = list(p.shape.dim)
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    num_axes = p.num_axes
    end = x.ndim if num_axes == -1 else axis + num_axes
    mid = []
    for i, d in enumerate(dims):
        if d == 0:
            mid.append(x.shape[axis + i])
        else:
            mid.append(int(d))
    shape = list(x.shape[:axis]) + mid + list(x.shape[end:])
    return [x.reshape(shape)]


@register("Concat")
def _concat(ctx, lp, params, bottoms):
    p = lp.concat_param
    axis = p.axis if p.has("axis") or not p.has("concat_dim") \
        else int(p.concat_dim)
    return [jnp.concatenate(bottoms, axis=axis)]


@register("Slice")
def _slice(ctx, lp, params, bottoms):
    p = lp.slice_param
    x = bottoms[0]
    axis = p.axis
    n_top = len(lp.top)
    if p.slice_point:
        points = [0] + [int(q) for q in p.slice_point] + [x.shape[axis]]
    else:
        if x.shape[axis] % n_top != 0:
            raise ValueError(
                f"Slice: axis size {x.shape[axis]} not divisible by "
                f"{n_top} tops (set slice_point explicitly)")
        step = x.shape[axis] // n_top
        points = [i * step for i in range(n_top + 1)]
    return [lax.slice_in_dim(x, points[i], points[i + 1], axis=axis)
            for i in range(n_top)]


@register("Eltwise")
def _eltwise(ctx, lp, params, bottoms):
    p = lp.eltwise_param
    op = p.operation
    if op == EltwiseOp.PROD:
        y = bottoms[0]
        for b in bottoms[1:]:
            y = y * b
    elif op == EltwiseOp.SUM:
        coeffs = p.coeff if p.coeff else [1.0] * len(bottoms)
        if len(coeffs) != len(bottoms):
            raise ValueError(
                f"Eltwise SUM: {len(coeffs)} coeffs for "
                f"{len(bottoms)} bottoms (must match)")
        y = coeffs[0] * bottoms[0]
        for c, b in zip(coeffs[1:], bottoms[1:]):
            y = y + c * b
    else:  # MAX
        y = bottoms[0]
        for b in bottoms[1:]:
            y = jnp.maximum(y, b)
    return [y]


@register("Tile")
def _tile(ctx, lp, params, bottoms):
    p = lp.tile_param
    x = bottoms[0]
    reps = [1] * x.ndim
    reps[p.axis] = int(p.tiles)
    return [jnp.tile(x, reps)]


@register("Reduction")
def _reduction(ctx, lp, params, bottoms):
    p = lp.reduction_param
    x = bottoms[0]
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    flat = x.reshape(x.shape[:axis] + (-1,))
    op = p.operation
    if op == 1:
        y = jnp.sum(flat, axis=-1)
    elif op == 2:
        y = jnp.sum(jnp.abs(flat), axis=-1)
    elif op == 3:
        y = jnp.sum(flat * flat, axis=-1)
    else:
        y = jnp.mean(flat, axis=-1)
    return [p.coeff * y]


@register("Crop")
def _crop(ctx, lp, params, bottoms):
    p = lp.crop_param
    x, ref = bottoms
    axis = p.axis if p.axis >= 0 else x.ndim + p.axis
    offsets = list(p.offset) or [0]
    starts = [0] * x.ndim
    sizes = list(x.shape)
    for i in range(axis, x.ndim):
        off = offsets[i - axis] if i - axis < len(offsets) else offsets[-1]
        starts[i] = off
        sizes[i] = ref.shape[i]
    return [lax.dynamic_slice(x, starts, sizes)]


@register("Split")
def _split(ctx, lp, params, bottoms):
    return [bottoms[0] for _ in lp.top]


@register("Silence")
def _silence(ctx, lp, params, bottoms):
    return []


@register("ArgMax")
def _argmax(ctx, lp, params, bottoms):
    p = lp.argmax_param
    x = bottoms[0]
    k = int(p.top_k)
    if p.has("axis"):
        # keep the axis with size top_k; out_max_val selects values
        axis = p.axis if p.axis >= 0 else x.ndim + p.axis
        moved = jnp.moveaxis(x, axis, -1)
        vals, idxs = lax.top_k(moved, k)
        out = vals if p.out_max_val else idxs.astype(jnp.float32)
        return [jnp.moveaxis(out, -1, axis)]
    flat = x.reshape(x.shape[0], -1)
    vals, idxs = lax.top_k(flat, k)
    if p.out_max_val:
        return [jnp.stack([idxs.astype(jnp.float32), vals], axis=1)]
    return [idxs.astype(jnp.float32).reshape(x.shape[0], 1, k)]


# ---------------------------------------------------------------------------
# softmax / losses / metrics
# ---------------------------------------------------------------------------

@register("Softmax")
def _softmax(ctx, lp, params, bottoms):
    axis = lp.softmax_param.axis
    return [jax.nn.softmax(bottoms[0], axis=axis)]


def _loss_normalizer(norm_mode, valid_count, batch, full):
    if norm_mode == NormalizationMode.FULL:
        return full
    if norm_mode == NormalizationMode.BATCH_SIZE:
        return batch
    if norm_mode == NormalizationMode.NONE:
        return 1.0
    return jnp.maximum(valid_count, 1.0)  # VALID


@register("SoftmaxWithLoss", is_loss=True, index_bottoms=(1,))
def _softmax_loss(ctx, lp, params, bottoms):
    axis = lp.softmax_param.axis if lp.has("softmax_param") else 1
    scores, labels = bottoms[0], bottoms[1]
    logp = jax.nn.log_softmax(scores, axis=axis)
    lbl = labels.astype(jnp.int32)
    # reshape labels to scores-without-class-axis
    outer = scores.shape[:axis]
    inner = scores.shape[axis + 1:]
    lbl = lbl.reshape(outer + inner)
    lp_msg = lp.loss_param
    has_ignore = lp.has("loss_param") and lp_msg.has("ignore_label")
    ignore = lp_msg.ignore_label if has_ignore else -1
    safe_lbl = jnp.where(lbl == ignore, 0, lbl) if has_ignore else lbl
    picked = jnp.take_along_axis(
        logp, jnp.expand_dims(safe_lbl, axis), axis=axis)
    nll = -jnp.squeeze(picked, axis)
    if has_ignore:
        mask = (lbl != ignore).astype(scores.dtype)
        nll = nll * mask
        valid = jnp.sum(mask)
    else:
        valid = float(math.prod(outer + inner))
    # legacy loss_param.normalize: true → VALID, false → BATCH_SIZE
    # (only consulted when 'normalization' itself is unset)
    if lp.has("loss_param") and not lp_msg.has("normalization") \
            and lp_msg.has("normalize"):
        norm_mode = (NormalizationMode.VALID if lp_msg.normalize
                     else NormalizationMode.BATCH_SIZE)
    elif lp.has("loss_param"):
        norm_mode = lp_msg.normalization
    else:
        norm_mode = NormalizationMode.VALID
    denom = _loss_normalizer(norm_mode, valid, scores.shape[0],
                             math.prod(outer + inner))
    return [jnp.sum(nll) / denom]


@register("EuclideanLoss", is_loss=True)
def _euclidean_loss(ctx, lp, params, bottoms):
    a, b = bottoms[0], bottoms[1]
    diff = a - b
    return [jnp.sum(diff * diff) / (2.0 * a.shape[0])]


@register("SigmoidCrossEntropyLoss", is_loss=True)
def _sce_loss(ctx, lp, params, bottoms):
    x, t = bottoms[0], bottoms[1]
    # stable: max(x,0) - x*t + log(1+exp(-|x|))
    loss = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return [jnp.sum(loss) / x.shape[0]]


@register("ContrastiveLoss", is_loss=True)
def _contrastive_loss(ctx, lp, params, bottoms):
    """Siamese-net loss (contrastive_loss_layer.cpp): bottoms are two
    feature batches a, b (N, C) and a pair label y (1 = similar).
    loss = 1/(2N) Σ [ y·d² + (1−y)·max(margin − d, 0)² ], d = ‖a−b‖;
    legacy_version uses max(margin − d², 0) instead."""
    p = lp.contrastive_loss_param
    a, b, y = bottoms[0], bottoms[1], bottoms[2]
    n = a.shape[0]
    y = y.reshape(n).astype(a.dtype)
    diff = (a - b).reshape(n, -1)
    dist_sq = jnp.sum(diff * diff, axis=1)
    if p.legacy_version:
        mismatch = jnp.maximum(p.margin - dist_sq, 0.0)
    else:
        # sqrt guard: d=0 has zero gradient through maximum anyway
        d = jnp.sqrt(jnp.maximum(dist_sq, 1e-12))
        m = jnp.maximum(p.margin - d, 0.0)
        mismatch = m * m
    return [jnp.sum(y * dist_sq + (1.0 - y) * mismatch) / (2.0 * n)]


@register("HingeLoss", is_loss=True, index_bottoms=(1,))
def _hinge_loss(ctx, lp, params, bottoms):
    x, y = bottoms[0], bottoms[1]
    n = x.shape[0]
    lbl = y.astype(jnp.int32).reshape(n)
    sign = jnp.ones_like(x).at[jnp.arange(n), lbl].set(-1.0)
    margin = jnp.maximum(0.0, 1.0 + sign * x)
    if lp.hinge_loss_param.norm == 2:
        return [jnp.sum(margin * margin) / n]
    return [jnp.sum(margin) / n]


@register("MultinomialLogisticLoss", is_loss=True,
          index_bottoms=(1,))
def _mll_loss(ctx, lp, params, bottoms):
    """-log(p[label]) on an already-softmaxed bottom (legacy pairing of
    Softmax + MultinomialLogisticLoss)."""
    probs, labels = bottoms[0], bottoms[1]
    n = probs.shape[0]
    lbl = labels.astype(jnp.int32).reshape(n)
    p = probs.reshape(n, -1)[jnp.arange(n), lbl]
    return [-jnp.sum(jnp.log(jnp.maximum(p, 1e-20))) / n]


@register("InfogainLoss", is_loss=True, index_bottoms=(1,))
def _infogain_loss(ctx, lp, params, bottoms):
    """Infogain-weighted multinomial loss: -(1/N) Σ_n Σ_k H[label_n, k]
    · log(p_nk).  The infogain matrix H arrives as bottom[2] (or, in
    Caffe, from infogain_loss_param.source — supply it as a bottom
    here; H = identity degenerates to MultinomialLogisticLoss)."""
    probs, labels = bottoms[0], bottoms[1]
    n, k = probs.shape[0], probs.reshape(probs.shape[0], -1).shape[1]
    if len(bottoms) > 2:
        h = bottoms[2].reshape(k, k)
    elif lp.has("infogain_loss_param") \
            and lp.infogain_loss_param.source:
        # load H from the binaryproto at trace time (constant in the
        # compiled program) — the standard Caffe configuration
        import numpy as _np
        from ..proto.caffe import BlobProto
        with open(lp.infogain_loss_param.source, "rb") as f:
            bp = BlobProto.from_binary(f.read())
        h = jnp.asarray(_np.asarray(bp.data, _np.float32).reshape(k, k))
    else:
        h = jnp.eye(k, dtype=probs.dtype)
    lbl = labels.astype(jnp.int32).reshape(n)
    logp = jnp.log(jnp.maximum(probs.reshape(n, k), 1e-20))
    rows = h[lbl]                       # (N, K) infogain row per sample
    return [-jnp.sum(rows * logp) / n]


@register("Accuracy", index_bottoms=(1,))
def _accuracy(ctx, lp, params, bottoms):
    p = lp.accuracy_param
    axis = p.axis
    k = int(p.top_k)
    scores, labels = bottoms[0], bottoms[1]
    outer = scores.shape[:axis]
    inner = scores.shape[axis + 1:]
    lbl = labels.astype(jnp.int32).reshape(outer + inner)
    has_ignore = lp.has("accuracy_param") and p.has("ignore_label")
    moved = jnp.moveaxis(scores, axis, -1)
    if k == 1:
        correct = (jnp.argmax(moved, axis=-1) == lbl)
    else:
        _, topi = lax.top_k(moved, k)
        correct = jnp.any(topi == lbl[..., None], axis=-1)
    correct = correct.astype(scores.dtype)
    if has_ignore:
        mask = (lbl != p.ignore_label).astype(scores.dtype)
        return [jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)]
    return [jnp.mean(correct)]


# ---------------------------------------------------------------------------
# attention (extension: long-context, time-major like the recurrent layers)
# ---------------------------------------------------------------------------

def _mha_params(lp, shapes):
    ap = lp.attention_param
    d_model = math.prod(shapes[0][2:]) if len(shapes[0]) > 2 else 1
    h = int(ap.num_heads)
    hd = int(ap.head_dim)
    wf = _filler(ap.weight_filler if ap.has("weight_filler") else None,
                 "xavier")
    return [("W_qkv", (3 * h * hd, d_model), wf),
            ("W_o", (d_model, h * hd), wf)]


def _on_batch_shards(installed, kernel, x, *whole):
    """Run a batch-major Pallas kernel on each device's batch shard.

    A bare pallas_call cannot be partitioned: inside a dp-sharded
    program JAX refuses to lower it ("Mosaic kernels cannot be
    automatically partitioned", the seed's four-chip train step,
    PR 21).  While a mesh is `installed` (`route.Route.mesh`, the route
    attention already takes) the call goes through shard_map over the
    batch axes instead; `whole` operands (a bias) reach every shard
    unsplit.  Without a mesh, or with batch axes of extent 1, the
    kernel is called directly."""
    if installed:
        mesh, b_axes, _, _ = installed
        b_axes = tuple(a for a in b_axes if mesh.shape.get(a, 1) > 1)
        if b_axes:
            from jax.sharding import PartitionSpec as P
            from ..parallel.sp import shard_map_nocheck
            spec = P(b_axes, *([None] * (x.ndim - 1)))
            return shard_map_nocheck(
                kernel, mesh, (spec,) + (P(),) * len(whole),
                spec)(x, *whole)
    return kernel(x, *whole)


def _attention_dispatch(q, k, v, *, causal: bool, mxu_dtype=None,
                        window: int = 0):
    """Flash (Pallas, O(block·T) VMEM) on TPU when the shape tiles;
    under a multi-device mesh the kernel runs per-device via shard_map
    over (batch, heads); XLA einsum attention otherwise — numerically
    the same math (tests/test_pallas.py flash parity).  q (B, H, T, D),
    k (B, H/g, T, D), v (B, H/g, T, Dv): Dv need not equal D, and with
    g > 1 query head h reads key/value head h // g (the kernels' block
    index maps and the einsum path's reshape: k and v are never
    repeated, except before the time-sharded ring, which assumes equal
    heads).  `mxu_dtype` is the operand type of the kernel's products
    (None = float32 operands, the kernel's exact mode); the einsum path
    takes XLA's precision.  `window` > 0: row t sees the keys
    t - window < s <= t alone, on the kernel route (which skips what
    the window hides) and on the einsum route alike; a mesh that shards
    time refuses such a layer before anything is traced
    (`parallel.sp.refuse_time_sharding`).  Everything here runs under
    the scope `attn.core`: the kernels' (or the einsums') device time
    apart from the products, norms and rotary turns of the layer around
    them; a windowed layer's under `attn.window` around that."""
    from .pallas_kernels import flash_attention
    t = q.shape[2]
    # whether the shape tiles depends on the branch below: only
    # 128-aligned sequence lengths take the kernel (Mosaic block shapes
    # must tile (8, 128), and at small T the O(T²) XLA path is cheap
    # anyway), and under a mesh batch and heads have to divide as well
    kernel = route.kernel(True, mesh="shard_map", attention=True)
    windowed = (jax.named_scope("attn.window") if window
                else contextlib.nullcontext())
    with windowed, jax.named_scope("attn.core"):
        if kernel and kernel.mesh:
            from jax.sharding import PartitionSpec as P
            from ..parallel.sp import shard_map_nocheck
            mesh, b_axes, h_axes, t_axes = kernel.mesh
            shape = dict(mesh.shape)
            b_axes = tuple(a for a in b_axes if shape.get(a, 1) > 1)
            h_axes = tuple(a for a in h_axes if shape.get(a, 1) > 1)
            t_axes = tuple(a for a in t_axes if shape.get(a, 1) > 1)
            nb = math.prod(shape[a] for a in b_axes) if b_axes else 1
            nh = math.prod(shape[a] for a in h_axes) if h_axes else 1
            tiles = (q.shape[0] % nb == 0 and q.shape[1] % nh == 0
                     and k.shape[1] % nh == 0)
            if t_axes and window:
                raise ValueError(
                    "an attention layer with a window under a mesh that "
                    "shards time: the ring masks by the diagonal alone")
            if (t_axes and len(t_axes) == 1 and tiles
                    and t % shape[t_axes[0]] == 0):
                # TIME sharded: differentiable fused ring per (b, h) block
                nt = shape[t_axes[0]]
                from ..parallel.sp import flash_block_size
                if flash_block_size(t // nt) is not None:
                    from ..parallel.sp import _ring_attention_local
                    g = q.shape[1] // k.shape[1]
                    if g > 1:       # the ring rotates equal heads
                        k, v = (jnp.repeat(a, g, axis=1) for a in (k, v))
                    spec = P(b_axes or None, h_axes or None, t_axes, None)
                    fl = shard_map_nocheck(
                        functools.partial(
                            _ring_attention_local, axis_name=t_axes[0],
                            causal=causal,
                            flash=("interpret" if kernel.interpret
                                   else True)),
                        mesh, (spec, spec, spec), spec)
                    return fl(q, k, v)
                # local T unsuited to the kernel: einsum path below
            elif not t_axes and tiles and t % 128 == 0:
                spec = P(b_axes or None, h_axes or None, None, None)
                fl = shard_map_nocheck(
                    # the kernels size their own tiles from the
                    # shard's shape (`pallas_kernels._flash_tiles`)
                    functools.partial(flash_attention, causal=causal,
                                      interpret=kernel.interpret,
                                      mxu_dtype=mxu_dtype, window=window),
                    mesh, (spec, spec, spec), spec)
                return fl(q, k, v)
            # shapes don't tile the mesh: einsum path below
        elif kernel and t % 128 == 0:
            return flash_attention(q, k, v, causal,
                                   interpret=kernel.interpret,
                                   mxu_dtype=mxu_dtype, window=window)
        from ..parallel.sp import attention as _plain_attention
        return _plain_attention(q, k, v, causal=causal, window=window)


def _kernel_operand_dtype(prec, q):
    """Operand type of the flash kernels' products for a layer at
    precision `prec`: one bfloat16 pass with float32 accumulation where
    XLA's default gives the projections around them the same (float32
    blobs on the TPU, no precision pinned), else None = float32."""
    return (jnp.bfloat16 if prec is None and q.dtype == jnp.float32
            and jax.default_backend() == "tpu" else None)


def _attention_time_sharding(lp):
    ap = lp.attention_param
    if ap.differential or ap.shared_kv or ap.emit_kv:
        return ("a differential attention layer or one that shares its "
                "keys and values",
                "the ring rotates equal heads of one width and knows no "
                "second layer's keys")
    if ap.window:
        return ("an attention layer with a window",
                "the ring's hops mask by the causal diagonal alone, so "
                "the layer would attend to its whole past")
    return None


def _mha_flops(lp, specs, tops):
    """The projections apply the FULL weight per (t, b) position (top
    is (T, B, D), not (T, B, 3D)), plus the two attention einsums (QK^T
    and PV: 2 * 2*B*H*T^2*hd): every score, as this type always counted
    them, unless a window hides some."""
    t_s, b_s = next(iter(tops.values()))[:2]
    ap = lp.attention_param
    scores = (visible_scores(t_s, True, int(ap.window))
              if ap.causal and ap.window else t_s * t_s)
    return (2 * t_s * b_s * sum(math.prod(ps) for _, ps, _ in specs)
            + 4 * b_s * int(ap.num_heads) * scores * int(ap.head_dim))


def _causal_attention_flops(lp, specs, tops, wide):
    """Every two-axis blob per (t, b) position, plus causal attention
    over `wide` lanes a query head (the score product's and the
    weighted value's): the masked half of QK^T and PV is not work, and
    under a window the scores a row can see and no others."""
    t_s, b_s = next(iter(tops.values()))[:2]
    ap = lp.attention_param
    return (2 * t_s * b_s * sum(math.prod(ps) for _, ps, _ in specs
                                if len(ps) == 2)
            + 2 * b_s * int(ap.num_heads)
            * visible_scores(t_s, True, int(ap.window)) * wide)


@register("MultiHeadAttention", params=_mha_params, flops=_mha_flops,
          time_sharding=_attention_time_sharding)
def _mha(ctx, lp, params, bottoms):
    """Multi-head self-attention on time-major (T, B, D) input: one
    fused `W_qkv` of equal heads, no positions, no norm (the latent
    variant with rotary positions is `LatentAttention` below, the one
    with fewer key/value heads and q/k norms `GroupedQueryAttention`;
    all end in `_attention_dispatch`) —
    extension beyond the reference (SURVEY §5.7: it has no attention at
    all).  Under jit on a mesh, GSPMD partitions the attention einsums
    along whatever axes the activations carry; for explicit
    sequence-parallel ring execution use `parallel.sp.ring_attention`
    (same math, shard_map + ppermute) in a hand-rolled step."""
    ap = lp.attention_param
    x = bottoms[0]
    t_steps, batch = x.shape[0], x.shape[1]
    h, hd = int(ap.num_heads), int(ap.head_dim)
    xf = x.reshape(t_steps, batch, -1)
    qkv = jnp.einsum("tbd,ed->tbe", xf, params[0])
    qkv = qkv.reshape(t_steps, batch, 3, h, hd)
    # (B, H, T, hd)
    q, k, v = (jnp.moveaxis(qkv[:, :, i], (0, 1, 2), (2, 0, 1))
               for i in range(3))
    # autotune variant "reference": pin the einsum reference path (A/B
    # partner of the flash dispatch; same math, see tests/test_pallas.py)
    pinned = (ctx.variant or {}).get("attention") == "reference"
    with route.suppress_flash() if pinned else contextlib.nullcontext():
        o = _attention_dispatch(q, k, v, causal=bool(ap.causal),
                                window=int(ap.window))
    # back to (T, B, H*hd)
    o = jnp.moveaxis(o, (0, 1, 2), (1, 2, 0)).reshape(t_steps, batch,
                                                      h * hd)
    return [jnp.einsum("tbe,de->tbd", o, params[1])]


def rms_norm(x, scale, eps):
    """x / sqrt(mean(x², last axis) + eps) · scale, statistics in
    float32 whatever the compute dtype."""
    x32 = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * scale


def _rms_norm_params(lp, shapes):
    rp = lp.rms_norm_param
    f = (rp.scale_filler if rp.has("scale_filler")
         else FillerParameter(type="constant", value=1.0))
    return [("scale", (int(shapes[0][-1]),), f)]


@register("RMSNorm", params=_rms_norm_params, flops=_no_flops,
          time_sharding=_cut_anywhere)
def _rms_norm(ctx, lp, params, bottoms):
    return [rms_norm(bottoms[0], params[0], float(lp.rms_norm_param.eps))]


@register("SiLU")
def _silu(ctx, lp, params, bottoms):
    return [jax.nn.silu(bottoms[0])]


def rope_adjacent(x, theta: float):
    """Rotary positions on adjacent pairs of the last axis; x is
    (T, ...) with the position on axis 0.  Pair i of width-w x turns
    by the angle t · theta^(-2i/w)."""
    w = x.shape[-1]
    t = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, w, 2, dtype=jnp.float32) / w))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (t,) + (1,) * (x.ndim - 2) + (w // 2,)
    cos = jnp.cos(ang).reshape(shape).astype(x.dtype)
    sin = jnp.sin(ang).reshape(shape).astype(x.dtype)
    xe, xo = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin], axis=-1)
    return out.reshape(x.shape)


def _mla_params(lp, shapes):
    ap = lp.attention_param
    d = math.prod(shapes[0][2:]) if len(shapes[0]) > 2 else 1
    h = int(ap.num_heads)
    nope, rope = int(ap.qk_nope_head_dim), int(ap.qk_rope_head_dim)
    vd, r = int(ap.v_head_dim), int(ap.kv_lora_rank)
    if not (h and nope and rope and vd and r) or rope % 2:
        raise ValueError(
            f"LatentAttention {lp.name!r} needs num_heads, "
            "qk_nope_head_dim, an even qk_rope_head_dim, v_head_dim "
            "and kv_lora_rank")
    wf = _filler(ap.weight_filler if ap.has("weight_filler") else None,
                 "xavier")
    one = FillerParameter(type="constant", value=1.0)
    return [("W_q", (h * (nope + rope), d), wf),
            ("W_kva", (r + rope, d), wf),
            ("kv_norm", (r,), one),
            ("W_kvb", (h * (nope + vd), r), wf),
            ("W_o", (d, h * vd), wf)]


def _mla_flops(lp, specs, tops):
    """The five projections, and nope + rope wide q/k with v_head_dim
    wide v."""
    ap = lp.attention_param
    return _causal_attention_flops(
        lp, specs, tops, int(ap.qk_nope_head_dim)
        + int(ap.qk_rope_head_dim) + int(ap.v_head_dim))


@register("LatentAttention", params=_mla_params, flops=_mla_flops,
          time_sharding=_attention_time_sharding)
def _mla(ctx, lp, params, bottoms):
    """Multi-head latent attention without q compression (deepseek_v3
    with `q_lora_rank: null`) on time-major (T, B, D) input:

        q = x W_q                 -> H x (nope + rope)
        [c_kv, k_rope] = x W_kva  -> kv_lora_rank + rope (k_rope shared
                                     by all heads)
        RMSNorm(c_kv) W_kvb       -> H x (k_nope + v)
        k = [k_nope, RoPE(k_rope)],  q = [q_nope, RoPE(q_rope)]
        o = softmax(q kᵀ / sqrt(nope + rope), causal) v  -> H x v
        y = o W_o

    RoPE turns adjacent pairs by position t (axis 0) with base
    `rope_theta`.  q/k are nope + rope wide and v is v_head_dim wide;
    the attention itself is `_attention_dispatch`, the one MultiHead-
    Attention takes.  On the TPU its kernel multiplies in one bfloat16
    pass with float32 accumulation — XLA's default precision for the
    projections around it — unless the layer is pinned to float32 by
    an autotune variant."""
    ap = lp.attention_param
    w_q, w_kva, kv_norm, w_kvb, w_o = params
    x = bottoms[0]
    t, b = x.shape[0], x.shape[1]
    h = int(ap.num_heads)
    nope, rope = int(ap.qk_nope_head_dim), int(ap.qk_rope_head_dim)
    vd, r = int(ap.v_head_dim), int(ap.kv_lora_rank)
    theta = float(ap.rope_theta)
    prec = ctx.precision()
    xf = x.reshape(t, b, -1)
    with jax.named_scope("attn"):
        q = jnp.einsum("tbd,ed->tbe", xf, w_q,
                       precision=prec).reshape(t, b, h, nope + rope)
        kva = jnp.einsum("tbd,ed->tbe", xf, w_kva, precision=prec)
        c_kv = rms_norm(kva[..., :r], kv_norm, float(ap.rms_norm_eps))
        k_rope = rope_adjacent(kva[..., r:], theta)         # (T, B, rope)
        kvb = jnp.einsum("tbr,er->tbe", c_kv, w_kvb,
                         precision=prec).reshape(t, b, h, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], rope_adjacent(q[..., nope:], theta)], axis=-1)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (t, b, h, rope))],
            axis=-1)
        v = kvb[..., nope:]
        # (T, B, H, ·) -> (B, H, T, ·)
        q, k, v = (jnp.transpose(a, (1, 2, 0, 3)) for a in (q, k, v))
        o = _attention_dispatch(q, k, v, causal=bool(ap.causal),
                                mxu_dtype=_kernel_operand_dtype(prec, q),
                                window=int(ap.window))
        o = jnp.transpose(o, (2, 0, 1, 3)).reshape(t, b, h * vd)
        return [jnp.einsum("tbe,de->tbd", o, w_o, precision=prec)]


def _gqa_heads(ap):
    h, hd = int(ap.num_heads), int(ap.head_dim)
    hkv = int(ap.num_kv_heads) or h
    rd = int(ap.rotary_dim) or hd
    if not (h and hd) or h % hkv or (ap.rotary and (rd % 2 or rd > hd)):
        raise ValueError(
            f"GroupedQueryAttention: {h} heads of {hd} over {hkv} "
            "key/value heads (num_heads must be a multiple of "
            "num_kv_heads, a rotary head_dim or rotary_dim even and "
            "inside the head)")
    if ap.differential and (hkv % 2 or ap.output_gate):
        raise ValueError(
            f"GroupedQueryAttention: differential over {hkv} key/value "
            "heads (they pair, so their number is even; no output gate)")
    if ap.shared_kv and (ap.emit_kv or ap.qk_norm or ap.rotary):
        raise ValueError(
            "GroupedQueryAttention: shared_kv reads another layer's keys "
            "and values as they are (no emit_kv, qk_norm or rotary)")
    return h, hkv, hd


def _gqa_params(lp, shapes):
    ap = lp.attention_param
    d = math.prod(shapes[0][2:]) if len(shapes[0]) > 2 else 1
    h, hkv, hd = _gqa_heads(ap)
    wf = _filler(ap.weight_filler if ap.has("weight_filler") else None,
                 "xavier")
    one = FillerParameter(type="constant", value=1.0)
    # with an output gate W_q gives a head's query and gate side by side
    qw = 2 * hd if ap.output_gate else hd
    specs = [("W_q", (h * qw, d), wf)]
    if not ap.shared_kv:
        specs += [("W_k", (hkv * hd, d), wf), ("W_v", (hkv * hd, d), wf)]
    specs.append(("W_o", (d, h * hd), wf))
    if ap.qk_norm:
        specs += [("q_norm", (hd,), one), ("k_norm", (hd,), one)]
    if ap.differential:
        lf = _filler(ap.lambda_filler if ap.has("lambda_filler") else None,
                     "constant")
        specs += [(n, (hd,), lf) for n in ("lambda_q1", "lambda_k1",
                                           "lambda_q2", "lambda_k2")]
        specs.append(("sub_norm", (2 * hd,), one))
    return specs


def _gqa_flops(lp, specs, tops):
    """The four projections (W_k and W_v at their own fewer heads),
    and head_dim wide q/k and v for every QUERY head; a differential
    layer's values are two heads wide: the score head_dim, the weighted
    value 2 x head_dim a pair."""
    ap = lp.attention_param
    return _causal_attention_flops(
        lp, specs, tops, (3 if ap.differential else 2) * int(ap.head_dim))


@register("GroupedQueryAttention", params=_gqa_params, flops=_gqa_flops,
          time_sharding=_attention_time_sharding)
def _gqa(ctx, lp, params, bottoms):
    """Self-attention with fewer key/value heads than query heads
    (lfm2, and most dense decoders since) on time-major (T, B, D) input:

        q = x W_q -> H x head_dim;  k = x W_k, v = x W_v -> H/g x head_dim
        qk_norm: q <- RMSNorm(q), k <- RMSNorm(k) over each head, one
                 head_dim-wide scale each, shared by the heads
        rotary:  adjacent pairs of the WHOLE head (rotary_dim > 0: of
                 its first rotary_dim dims, the rest left as they are)
                 turn by position t with base rope_theta, after the norms
        o = softmax(q k^T / sqrt(head_dim), causal) v, query head h
            reading key/value head h // g;  y = o W_o
        output_gate: [q, gate] = x W_q, a head's 2 x head_dim side by
                 side; y = (o * sigmoid(gate)) W_o (qwen3_next)
        differential: heads pair (phi4flash; `_differential`): key pair
                 j = key heads 2j, 2j + 1, its value the two heads' side
                 by side; query pairs g j .. g j + g - 1 read it, first
                 queries at query heads 2 g j + r, second at 2 g j + g
                 + r; o_p = map1 v - lambda map2 v, RMSNorm over its
                 2 x head_dim, x (1 - lambda_init)
        emit_kv: tops 1, 2 = k (B, H/g, T, head_dim) and v as the
                 dispatch takes them; shared_kv: bottoms 1, 2 = another
                 layer's, and W_q, W_o are the only matrices

    The attention itself is `_attention_dispatch`, the one the other
    two attention types take; products are as `LatentAttention`'s (one
    bfloat16 pass on the TPU at the default precision)."""
    ap = lp.attention_param
    blobs = dict(zip((n for n, _, _ in _gqa_params(
        lp, [bt.shape for bt in bottoms])), params))
    w_q, w_o = blobs["W_q"], blobs["W_o"]
    x = bottoms[0]
    t, b = x.shape[0], x.shape[1]
    h, hkv, hd = _gqa_heads(ap)
    prec = ctx.precision()
    xf = x.reshape(t, b, -1)

    def heads(w, n):
        return jnp.einsum("tbd,ed->tbe", xf, w, precision=prec
                          ).reshape(t, b, n, -1)

    with jax.named_scope("attn"):
        q = heads(w_q, h)
        if ap.shared_kv:
            k, v = bottoms[1], bottoms[2]       # (B, Hkv, T, .) as emitted
        else:
            k, v = heads(blobs["W_k"], hkv), heads(blobs["W_v"], hkv)
        if ap.output_gate:
            q, gate = q[..., :hd], q[..., hd:].reshape(t, b, h * hd)
        if ap.qk_norm:
            eps = float(ap.rms_norm_eps)
            q = rms_norm(q, blobs["q_norm"], eps)
            k = rms_norm(k, blobs["k_norm"], eps)
        if ap.rotary:
            theta, rd = float(ap.rope_theta), int(ap.rotary_dim)
            if rd and rd < hd:
                q, k = (jnp.concatenate(
                    [rope_adjacent(a[..., :rd], theta), a[..., rd:]],
                    axis=-1) for a in (q, k))
            else:
                q, k = rope_adjacent(q, theta), rope_adjacent(k, theta)
        # (T, B, heads, hd) -> (B, heads, T, hd)
        q = jnp.transpose(q, (1, 2, 0, 3))
        if not ap.shared_kv:
            k, v = (jnp.transpose(a, (1, 2, 0, 3)) for a in (k, v))
            if ap.differential:
                # the value heads pair: both key heads of a pair read
                # the pair's two values side by side, 2 x hd wide
                v = v.reshape(b, hkv // 2, 1, 2, t, hd)
                v = jnp.moveaxis(v, 3, 4).reshape(b, hkv // 2, 1, t, 2 * hd)
                v = jnp.broadcast_to(
                    v, (b, hkv // 2, 2, t, 2 * hd)).reshape(
                        b, hkv, t, 2 * hd)
        o = _attention_dispatch(q, k, v, causal=bool(ap.causal),
                                mxu_dtype=_kernel_operand_dtype(prec, q),
                                window=int(ap.window))
        if ap.differential:
            o = _differential(o, blobs, ap, h // hkv)
        o = jnp.transpose(o, (2, 0, 1, 3)).reshape(t, b, h * hd)
        if ap.output_gate:
            o = o * jax.nn.sigmoid(gate)
        y = jnp.einsum("tbe,de->tbd", o, w_o, precision=prec)
        return [y, k, v] if ap.emit_kv else [y]


def _differential(o, blobs, ap, g: int):
    """The two maps of every head pair, subtracted and normed: o (B, H,
    T, 2 hd), the dispatch's output, whose 2g heads a key pair are the
    g first queries of its pairs and then their g second queries -> (B,
    H/2, T, 2 hd), pair p's o1 - lambda o2 under an RMSNorm over its
    2 hd, times 1 - lambda_init.  Scope `attn.diff`."""
    b, h, t, w = o.shape
    f32 = jnp.float32
    with jax.named_scope("attn.diff"):
        lam_init = float(ap.lambda_init)
        lam = (jnp.exp(jnp.sum(blobs["lambda_q1"].astype(f32)
                               * blobs["lambda_k1"].astype(f32)))
               - jnp.exp(jnp.sum(blobs["lambda_q2"].astype(f32)
                                 * blobs["lambda_k2"].astype(f32)))
               + lam_init)
        o = o.reshape(b, h // (2 * g), 2, g, t, w)
        o = (o[:, :, 0] - lam.astype(o.dtype) * o[:, :, 1]).reshape(
            b, h // 2, t, w)
        return rms_norm(o, blobs["sub_norm"], float(ap.rms_norm_eps)) \
            * (1.0 - lam_init)


def _short_conv_params(lp, shapes):
    cp = lp.short_conv_param
    d = int(shapes[0][-1])
    taps = int(cp.taps)
    if taps < 1:
        raise ValueError(f"ShortConv {lp.name!r}: taps {taps}")
    wf = _filler(cp.weight_filler if cp.has("weight_filler") else None,
                 "xavier")
    specs = [("W_in", (3 * d, d), wf), ("taps", (d, taps), wf),
             ("W_out", (d, d), wf)]
    if cp.bias_term:
        specs.append(("bias", (d,),
                      FillerParameter(type="constant", value=0.0)))
    return specs


def causal_taps(z, taps):
    """Depthwise causal convolution over time (axis 0) of z (T, ..., D):
    taps (D, L); tap j multiplies the input at t - (L - 1) + j, zero
    before t = 0.  Shifted slices of one padded array.  What XLA makes
    of them on the v5e depends on the array's size, not on what stands
    around them (PERF.md, section 7, PR 44): `short_conv_mix`'s 2,048
    channels (67 MB a copy at T = 8,192) are staged in the chip's second
    memory space and the shifted reads run at 690 GB/s; the 8,192 or
    5,120 channels before a Gated DeltaNet's or Mamba's SiLU (268 / 168
    MB) are copied out to HBM first and read back at 265 GB/s, which is
    why `causal_taps_silu` has kernels of its own."""
    n_taps, t = taps.shape[1], z.shape[0]
    zp = jnp.pad(z, ((n_taps - 1, 0),) + ((0, 0),) * (z.ndim - 1))
    return sum(zp[j:j + t] * taps[:, j].astype(z.dtype)
               for j in range(n_taps))


def causal_taps_silu(z, taps, bias=None, *, site: str = "",
                     first: int = 0):
    """silu(`causal_taps`(z[..., first:first + C], taps) [+ bias]) for
    time-major z (T, B, W), taps (C, L), bias (C,): the convolution
    stage of the Gated DeltaNet and Mamba layers, over C channels of
    the product's W-wide output, read where they lie (from channel
    `first` on: the first C unless the caller says otherwise).

    The Mosaic kernels (`pallas_kernels.causal_taps_silu_kernels`: one
    pass forward and one backward, each element read where it lies in
    z) where `route.kernel` says so: C and W fill whole 128-lane tiles,
    T whole sublane groups (`taps_plan`); else `causal_taps_silu_xla`.
    `route.plans()["taps"]` (the job's `info.taps`) says by call shape
    which form it was lowered to (`form`: "kernel" with the kernels'
    tiles, or "xla") and for which layers (`sites`)."""
    from .pallas_kernels import causal_taps_silu_kernels, taps_plan
    t, b, w = z.shape
    c, n = taps.shape
    plan = taps_plan(t, c, w, n, first)
    kernel = route.kernel(plan, z, taps, *(() if bias is None else (bias,)))
    entry = route.lowered(
        "taps", f"{b}x{t} {c} of {w} channels"
        f"{f' from {first}' if first else ''} {n} taps {z.dtype.name}"
        f"{'' if bias is None else ' bias'}")
    entry.setdefault("sites", [])
    entry.update({"form": "kernel", **plan} if kernel else {"form": "xla"})
    if site and site not in entry["sites"]:
        entry["sites"].append(site)
    if kernel:
        return causal_taps_silu_kernels(z, taps, bias, plan,
                                        interpret=kernel.interpret,
                                        first=first)
    return causal_taps_silu_xla(z, taps, bias, first)


def causal_taps_silu_xla(z, taps, bias=None, first: int = 0):
    """`causal_taps_silu` as plain XLA: the fallback (CPU, shapes that
    do not tile, a mesh) and the parity reference of the kernels'
    tests."""
    pre = causal_taps(z[..., first:first + taps.shape[0]], taps)
    if bias is not None:
        pre = pre + bias.astype(pre.dtype)
    return jax.nn.silu(pre)


def short_conv_mix(b, c, u, taps, bias=None):
    """c * conv(b * u): the gates and the depthwise causal convolution
    over time (axis 0) of the gated short convolution (`causal_taps`:
    XLA fuses gate, shifts and gate into one pass)."""
    conv = causal_taps(b * u, taps)
    if bias is not None:
        conv = conv + bias.astype(conv.dtype)
    return c * conv


@register("ShortConv", params=_short_conv_params, flops=_products_flops,
          time_sharding=_cut_anywhere)
def _short_conv(ctx, lp, params, bottoms):
    """The gated short convolution (lfm2's `conv` operator) on
    time-major (T, B, D) input:

        [b, c, u] = split3(x W_in)      three D-wide parts, in this order
        z = b * u
        v[t] = sum_j taps[:, j] * z[t - (L - 1) + j]   per channel, causal
        y = (c * v) W_out

    No state crosses a batch column, and nothing marks a document's
    start inside a packed row (L - 1 tokens of the document before
    reach over a boundary)."""
    w_in, taps, w_out = params[:3]
    x = bottoms[0]
    d = x.shape[-1]
    prec = ctx.precision()
    with jax.named_scope("sconv"):
        bcu = jnp.einsum("...d,ed->...e", x, w_in, precision=prec)
        with jax.named_scope("sconv.mix"):
            y = short_conv_mix(bcu[..., :d], bcu[..., d:2 * d],
                               bcu[..., 2 * d:], taps,
                               params[3] if len(params) > 3 else None)
        return [jnp.einsum("...d,ed->...e", y, w_out, precision=prec)]


def _gdn_dims(gp):
    hk, hv = int(gp.num_k_heads), int(gp.num_v_heads)
    dk, dv = int(gp.head_k_dim), int(gp.head_v_dim)
    if not (hk and hv and dk and dv) or hv % hk or int(gp.chunk) < 1 \
            or int(gp.conv_taps) < 1:
        raise ValueError(
            f"GatedDeltaNet: {hv} value heads of {dv} over {hk} key "
            f"heads of {dk}, chunk {int(gp.chunk)}, taps "
            f"{int(gp.conv_taps)} (num_v_heads must be a multiple of "
            "num_k_heads)")
    return hk, hv, dk, dv


def _gdn_params(lp, shapes):
    gp = lp.gated_delta_net_param
    d = int(shapes[0][-1])
    hk, hv, dk, dv = _gdn_dims(gp)
    wf = _filler(gp.weight_filler if gp.has("weight_filler") else None,
                 "xavier")
    one = FillerParameter(type="constant", value=1.0)
    kw, vw = hk * dk, hv * dv
    return [("W_qkvz", (2 * kw + 2 * vw, d), wf), ("W_ba", (2 * hv, d), wf),
            ("taps", (2 * kw + vw, int(gp.conv_taps)), wf),
            # the family's A = uniform(0, 16), kept off log(0)
            ("A_log", (hv,), FillerParameter(type="log_uniform", min=1e-3,
                                             max=16.0)),
            ("dt_bias", (hv,), one), ("norm", (dv,), one),
            ("W_out", (d, vw), wf)]


def _unit_lower_inverse(m, precision):
    """Inverse of unit lower triangular matrices m (..., c, c), c a
    power of two: the inverse of [[A, 0], [C, D]] is X - X [[0, 0],
    [C, 0]] X with X = diag(A^-1, D^-1), from 1 x 1 blocks (the
    identity) up, every level past the first two products of whole
    c x c matrices under a mask.  No entry ever exceeds what the true
    inverses of the diagonal blocks hold (a power series of the strict
    part would, for keys that resemble each other)."""
    c = m.shape[-1]
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]

    def below(s):
        """The block C of every 2s x 2s block on the diagonal."""
        return ((row // (2 * s) == col // (2 * s))
                & (row % (2 * s) >= s) & (col % (2 * s) < s))

    # 2 x 2 blocks: [[1, 0], [c, 1]]^-1 = [[1, 0], [-c, 1]]
    x = jnp.eye(c, dtype=m.dtype) - jnp.where(below(1), m, 0)
    sizes = [s for s in (2 ** j for j in range(1, c.bit_length()))
             if s < c]
    if not sizes:
        return x

    def level(x, off):      # one loop, so that the levels compile once
        xc = jnp.matmul(x, jnp.where(off, m, 0), precision=precision)
        return x - jnp.matmul(xc, x, precision=precision), None

    return lax.scan(level, x, jnp.stack([below(s) for s in sizes]))[0]


# the state's products keep float32 (HIGHEST: a state carried along
# 8,192 tokens is not rounded to bfloat16 once a chunk), as the router's
_GDN_PRECISION = lax.Precision.HIGHEST

# chunks between two states that the forward pass keeps (the residual
# of either form: (chunks a row / group) states of (dk, dv) a head), the
# backward pass computing a group again from the state at its edge.  In
# the XLA form, this number, it also bounds what is alive together: the
# triangular systems, products and states of a group of chunks.  In the
# kernel form (`pallas_kernels.GDN_GROUP_CHUNKS`, 16) it bounds the
# backward pass's transient: the state before every chunk of a group,
# its T and its vn in HBM between the recomputation and the sweep
# (4 MB a chunk at 16 key heads of 128 / 2 x 128)
_GDN_GROUP = 32


def _delta_group(state, x, *, c: int):
    """One group of G chunks of the XLA form of `gated_delta_rule`:
    state (B, Hk, R, dk, dv) before it, x = q, k (B, Hk, 1, G, c, dk),
    v (B, Hk, R, G, c, dv), g, beta (B, Hk, R, G, c) -> the state after
    it, o (B, Hk, R, G, c, dv).  (The kernel form computes the same
    chunk in the three-product order, `pallas_kernels._GdnChunk`; this
    one is what its tests are held to.)

    With gam the running sum of g inside a chunk, A = tril(beta k k^T
    e^(gam_i - gam_j), -1) and T = (I + A)^-1, a chunk's tokens write
    u - w S where u = T (beta v), w = T (beta e^gam k), and S is the
    state before the chunk.  So a chunk is a linear map of the state,

        S' = M S + B,    M = e^(gam_c) I - kd^T w,   B = kd^T u
        o  = Q S + O,    Q = e^gam q - P w,          O = P u

    (kd = e^(gam_c - gam) k, P = tril(q k^T e^(gam_i - gam_j))).  M, B,
    Q and O of the G chunks are products over all of them at once; what
    is left to go from chunk to chunk is one (dk, dk) x (dk, dv) product
    a head, and the outputs follow from the G states together."""
    prec = _GDN_PRECISION
    qc, kc, vc, gc, bc = x
    gam = jnp.cumsum(gc, axis=-1)
    i = jnp.arange(c)
    low = i[:, None] >= i[None, :]
    # e^(gam_i - gam_j) for j <= i, 0 above the diagonal
    decay = jnp.exp(jnp.where(low, gam[..., :, None] - gam[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bhxnid,bhxnjd->bhxnij", kc, kc, precision=prec)
    qk = jnp.einsum("bhxnid,bhxnjd->bhxnij", qc, kc, precision=prec)
    strict = jnp.where(i[:, None] > i[None, :],
                       bc[..., None] * kk * decay, 0)
    tinv = _unit_lower_inverse(strict + jnp.eye(c, dtype=strict.dtype),
                               prec)
    egam = jnp.exp(gam)[..., None]
    u = jnp.matmul(tinv, vc * bc[..., None], precision=prec)
    w = jnp.matmul(tinv, kc * (bc[..., None] * egam), precision=prec)
    p = qk * decay
    last = gam[..., -1:]
    kd_t = jnp.swapaxes(kc * jnp.exp(last - gam)[..., None], -1, -2)
    dk = kc.shape[-1]
    m = (jnp.exp(last)[..., None] * jnp.eye(dk, dtype=w.dtype)
         - jnp.matmul(kd_t, w, precision=prec))
    b_in = jnp.matmul(kd_t, u, precision=prec)
    q_s = qc * egam - jnp.matmul(p, w, precision=prec)
    o_own = jnp.matmul(p, u, precision=prec)

    def step(state, x):     # -> the state after a chunk; emits the one before
        m_n, b_n = x
        return jnp.matmul(m_n, state, precision=prec) + b_n, state

    state, before = lax.scan(
        step, state, (jnp.moveaxis(m, 3, 0), jnp.moveaxis(b_in, 3, 0)))
    return state, o_own + jnp.matmul(q_s, jnp.moveaxis(before, 0, 3),
                                     precision=prec)


def gated_delta_rule(q, k, v, g, beta, chunk: int):
    """The gated delta rule over the sequence, in chunks.

        S_t = e^(g_t) S_(t-1) + k_t (beta_t (v_t - (e^(g_t) S_(t-1))^T k_t))^T
        o_t = S_t^T q_t,  S_0 = 0

    q, k (B, Hk, T, dk); v (B, Hk, R, T, dv); g (<= 0), beta (B, Hk, R,
    T): key head h serves the R value heads (h, .)  -> o (B, Hk, R, T,
    dv).  Within a chunk of `chunk` tokens (a power of two) the rule is
    a unit lower triangular system and products, which make of the
    chunk a linear map of the (dk, dv) state; across chunks the states
    are carried; every decay is the exponential of a difference of
    running sums that is <= 0.  What the forward pass keeps is the
    state every group of chunks (`_GDN_GROUP`), nothing a token and
    nothing a chunk; the backward pass computes a group again from
    there.

    The Mosaic kernels (`pallas_kernels.gated_delta_rule_kernels`: the
    states in VMEM from the first chunk to the last) where
    `route.kernel` says so: R chunk and both head sizes fill whole
    128-lane tiles (`gdn_rule_tiles`); else `gated_delta_rule_xla`.
    `route.plans()["gdn"]` (the job's `info.gdn`) says by operator
    shape which form it was lowered to (`rule`: "kernel" or "xla"), the
    chunk, the chunks a row, between two kept states and a call (or
    loop), the heads and the state's bytes."""
    from .pallas_kernels import (gated_delta_rule_kernels, gdn_rule_steps,
                                 gdn_rule_tiles)
    b, hk, t, dk = q.shape
    r, dv = v.shape[2], v.shape[-1]
    c = int(chunk)
    if c & (c - 1):
        raise ValueError(f"gated_delta_rule: chunk {c} is not a power "
                         "of two")
    kernel = route.kernel(gdn_rule_tiles(r, c, dk, dv), q, k, v, g, beta)
    n = -(-t // c)
    # chunks between two kept states, and chunks the states pass through
    # in one call: a row, padded to whole grid steps and groups (the
    # kernels' forward call, the states in VMEM all along), or one
    # iteration of the scan over groups (XLA)
    if kernel:
        steps, group_steps, a_call = gdn_rule_steps(n)
        group = steps * group_steps
    else:
        group = a_call = min(_GDN_GROUP, n)
    route.lowered(
        "gdn", f"{b}x{t} {hk}/{hk * r} heads {dk}/{dv}",
        rule="kernel" if kernel else "xla",
        chunk=c, chunks_a_row=n, chunks_a_group=group,
        chunks_a_call=a_call, heads=hk * r,
        state_bytes=b * hk * r * dk * dv * 4)
    if kernel:
        return gated_delta_rule_kernels(q, k, v, g, beta, c,
                                        interpret=kernel.interpret)
    return gated_delta_rule_xla(q, k, v, g, beta, c)


def gated_delta_rule_xla(q, k, v, g, beta, c: int):
    """`gated_delta_rule` as XLA products at `_GDN_PRECISION`: the
    fallback (CPU, shapes that do not tile, a mesh) and the parity
    reference of the kernels' tests.  The chunks go `_GDN_GROUP` at a
    time (`_delta_group`: `_unit_lower_inverse`, the chunk as a linear
    map) under a `lax.scan` whose body is recomputed in the backward
    pass.  T is padded to whole groups with tokens that neither write
    (beta 0) nor decay (g 0)."""
    b, hk, t, dk = q.shape
    r, dv = v.shape[2], v.shape[-1]
    n = -(-t // c)
    grp = min(_GDN_GROUP, n)
    ng = -(-n // grp)
    full = ng * grp * c

    def groups(a, axis):
        """Time on `axis` -> (groups, ..., chunks of a group, c, ...)."""
        if full != t:
            w = [(0, 0)] * a.ndim
            w[axis] = (0, full - t)
            a = jnp.pad(a, w)
        a = a.reshape(a.shape[:axis] + (ng, grp, c) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    xs = (groups(q, 2)[:, :, :, None], groups(k, 2)[:, :, :, None],
          groups(v, 3), groups(g, 3), groups(beta, 3))
    _, o = lax.scan(
        jax.checkpoint(functools.partial(_delta_group, c=c)),
        jnp.zeros((b, hk, r, dk, dv), v.dtype), xs)
    # (groups, B, Hk, R, chunks, c, dv) -> (B, Hk, R, T, dv)
    return jnp.moveaxis(o, 0, 3).reshape(b, hk, r, full, dv)[..., :t, :]


def _whole_sequence(lp):
    """A layer whose state runs along the whole sequence on one device
    (or, a Gated Memory Unit, that reads such a layer's output row for
    row): no exchange of the state between time shards is written."""
    return ("GatedDeltaNet/Mamba/Mamba2/GatedMemoryUnit layers",
            "the chunked scan carries its state along the whole sequence "
            "on one device")


def _gdn_flops(lp, specs, tops):
    """The three products per position, plus the recurrence as
    written: per token and value head the read S^T k, the rank-one
    write and the read S^T q, 2 x dk x dv each (the decay of the state
    is an elementwise pass; taps, gates and norms are not counted)."""
    gp = lp.gated_delta_net_param
    return _products_flops(lp, specs, tops) + _rows(tops) * (
        int(gp.num_v_heads) * 3 * 2 * int(gp.head_k_dim)
        * int(gp.head_v_dim))


@register("GatedDeltaNet", params=_gdn_params, flops=_gdn_flops,
          time_sharding=_whole_sequence)
def _gdn(ctx, lp, params, bottoms):
    """The Gated DeltaNet operator (qwen3_next's linear-attention
    layer) on time-major (T, B, D) input:

        [q, k, v, z] = x W_qkvz;  [b, a] = x W_ba
        [q, k, v] <- silu(taps over time of concat(q, k, v)), causal
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q <- q / |q| / sqrt(head_k_dim);  k <- k / |k|
        o = `gated_delta_rule`(q, k, v, g, beta), key head h // R
            serving value head h, in float32
        y = (RMSNorm(o) * norm * silu(z)) W_out

    No state crosses a batch column, and nothing marks a document's
    start inside a packed row (the state and the taps reach over a
    boundary).  Scopes: `gdn`, inside it `gdn.conv` (taps + SiLU,
    `causal_taps_silu`: the Mosaic calls `cos_taps_fwd` / `cos_taps_bwd`
    where the rule's kernels run, else the XLA form) and
    `gdn.scan` (decay, strengths, normalisation, and the rule in
    whichever form `gated_delta_rule` lowers here: the Mosaic kernels on
    the TPU at the family's shapes, forward, recomputation and backward
    calls alike, else the XLA form, the reference of the kernels'
    tests)."""
    gp = lp.gated_delta_net_param
    w_qkvz, w_ba, taps, a_log, dt_bias, norm, w_out = params
    x = bottoms[0]
    t, b = x.shape[0], x.shape[1]
    hk, hv, dk, dv = _gdn_dims(gp)
    r, kw, vw = hv // hk, hk * dk, hv * dv
    prec = ctx.precision()
    f32 = jnp.float32

    conv = functools.partial(causal_taps_silu, site=lp.name)

    def heads(qkv, ba, a_log, dt_bias):
        """-> q, k (B, Hk, T, dk), v (B, Hk, R, T, dv), g, beta (B, Hk,
        R, T), float32."""
        beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:].astype(f32) + dt_bias.astype(f32))
        q, k = (qkv[..., j * kw:(j + 1) * kw].astype(f32).reshape(
            t, b, hk, dk) for j in (0, 1))
        q, k = (a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                              + 1e-6) for a in (q, k))
        v = qkv[..., 2 * kw:].astype(f32).reshape(t, b, hk, r, dv)
        return (jnp.transpose(q * (dk ** -0.5), (1, 2, 0, 3)),
                jnp.transpose(k, (1, 2, 0, 3)),
                jnp.transpose(v, (1, 2, 3, 0, 4)),
                jnp.transpose(g.reshape(t, b, hk, r), (1, 2, 3, 0)),
                jnp.transpose(beta.reshape(t, b, hk, r), (1, 2, 3, 0)))

    def gate(o, z, norm):
        # (B, Hk, R, T, dv) -> (T, B, Hv, dv), normed and gated
        o = jnp.transpose(o, (3, 0, 1, 2, 4)).reshape(t, b, hv, dv)
        return (rms_norm(o, norm.astype(f32), float(gp.rms_norm_eps))
                * jax.nn.silu(z.astype(f32))).astype(x.dtype)

    # the elementwise passes between the products are computed again in
    # the backward pass, so that of a layer's 8,192-channel activations
    # only the products' own outputs are kept.  What feeds the rule is
    # a `stage`: inside a recompute_block the block's recomputation is
    # its second and last run.  The gate, which reads the rule's kept
    # output, has its own checkpoint there too: bare, the v5e's
    # compiler fuses it into W_out's backward, which at qwen3_next's
    # shapes costs 1 ms a step more than the gate's third run
    with jax.named_scope("gdn"):
        qkvz = jnp.einsum("tbd,ed->tbe", x, w_qkvz, precision=prec)
        ba = jnp.einsum("tbd,ed->tbe", x, w_ba, precision=prec)
        with jax.named_scope("gdn.conv"):
            qkv = stage(conv)(qkvz, taps)
        with jax.named_scope("gdn.scan"):
            o = gated_delta_rule(
                *stage(heads)(qkv, ba, a_log, dt_bias),
                int(gp.chunk))
        o = jax.checkpoint(gate)(
            o, qkvz[..., 2 * kw + vw:].reshape(t, b, hv, dv), norm)
        return [jnp.einsum("tbe,de->tbd", o.reshape(t, b, vw), w_out,
                           precision=prec)]


def layer_norm(x, scale, bias, eps):
    """(x - mean) / sqrt(var + eps) * scale + bias over the last axis,
    statistics in float32 whatever the compute dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    inv = lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (xc * inv).astype(x.dtype) * scale + bias


def _layer_norm_params(lp, shapes):
    np_ = lp.layer_norm_param
    d = int(shapes[0][-1])
    return [("scale", (d,), np_.scale_filler if np_.has("scale_filler")
             else FillerParameter(type="constant", value=1.0)),
            ("bias", (d,), np_.bias_filler if np_.has("bias_filler")
             else FillerParameter(type="constant", value=0.0))]


@register("LayerNorm", params=_layer_norm_params, flops=_no_flops,
          time_sharding=_cut_anywhere)
def _layer_norm(ctx, lp, params, bottoms):
    return [layer_norm(bottoms[0], params[0], params[1],
                       float(lp.layer_norm_param.eps))]


def _mamba_dims(mp, d):
    di, n = int(mp.d_inner) or 2 * d, int(mp.d_state)
    taps, rank = int(mp.d_conv), int(mp.dt_rank) or -(-d // 16)
    if n < 1 or taps < 1 or int(mp.chunk) < 1:
        raise ValueError(f"Mamba: state {n}, taps {taps}, chunk "
                         f"{int(mp.chunk)}")
    return di, n, taps, rank


def _mamba_params(lp, shapes):
    mp = lp.mamba_param
    d = int(shapes[0][-1])
    di, n, taps, rank = _mamba_dims(mp, d)
    wf = _filler(mp.weight_filler if mp.has("weight_filler") else None,
                 "xavier")
    cf = _filler(mp.conv_filler if mp.has("conv_filler") else None,
                 "xavier")
    return [("W_in", (2 * di, d), wf), ("taps", (di, taps), cf),
            ("conv_bias", (di,), cf), ("W_x", (rank + 2 * n, di), wf),
            ("W_dt", (di, rank), wf),
            ("dt_bias", (di,), FillerParameter(
                type="inv_softplus_log_uniform", min=float(mp.dt_min),
                max=float(mp.dt_max))),
            ("A_log", (di, n), FillerParameter(type="log_arange")),
            ("D", (di,), FillerParameter(type="constant", value=1.0)),
            ("W_out", (d, di), wf)]


def selective_scan(u, dt, a, b, c, chunk: int = 64):
    """The selective state-space recurrence over the sequence,

        s_t[c, n] = exp(dt_t[c] A[c, n]) s_(t-1)[c, n] + dt_t[c] u_t[c] B_t[n]
        y_t[c]    = sum_n s_t[c, n] C_t[n],      s_(-1) = 0

    u, dt (B, T, C); a = A (C, N), < 0; b, c (B, T, N) -> y (B, T, C),
    all float32 (the skip D u is the caller's).  No state crosses a
    batch row.  What the forward pass keeps is y and the state at every
    chunk's edge, nothing a token; the backward pass computes a chunk's
    states again from there.

    The Mosaic kernels (`pallas_kernels.selective_scan_kernels`:
    channels on lanes, the (N, channels) state in VMEM from a row's
    first chunk to its last) where `route.kernel` says so: the channels
    fill whole 128-lane tiles, the states whole sublanes
    (`ssm_scan_plan`); else `selective_scan_xla`.
    `route.plans()["ssm"]` (the job's `info.ssm`) says by operator shape
    which form it was lowered to (`form`: "kernel" or "xla"), the chunk,
    the chunks a row, the kept edges' bytes and, of the kernels, the
    channels a program and the VMEM a call takes."""
    from .pallas_kernels import selective_scan_kernels, ssm_scan_plan
    bsz, t, ch = u.shape
    n = a.shape[1]
    plan = ssm_scan_plan(t, ch, n, int(chunk))
    kernel = route.kernel(plan, u, dt, a, b, c)
    length = plan["chunk"] if kernel else min(int(chunk), t)
    route.lowered(
        "ssm", f"{bsz}x{t} {ch} channels {n} states",
        form="kernel" if kernel else "xla", chunk=length,
        chunks_a_row=-(-t // length),
        edge_bytes=bsz * -(-t // length) * ch * n * 4,
        **({"channels_a_program": plan["channels"],
            "vmem_bytes": plan["vmem_bytes"]} if kernel else {}))
    if kernel:
        return selective_scan_kernels(u, dt, a, b, c, plan,
                                      interpret=kernel.interpret)
    return selective_scan_xla(u, dt, a, b, c, length)


def _ssm_chunk(state, x, *, a):
    """One chunk of the XLA form: state (B, C, N) before it, x = u, dt
    (B, L, C), b, c (B, L, N) -> the state after it, y (B, L, C).  The
    steps s <- decay s + write compose associatively, so a chunk is one
    `lax.associative_scan` over its L steps."""
    u, dt, b, c = x
    decay = jnp.exp(dt[..., None] * a)                    # (B, L, C, N)
    write = (dt * u)[..., None] * b[:, :, None, :]

    def then(first, second):
        return (first[0] * second[0], second[0] * first[1] + second[1])

    decays, states = lax.associative_scan(then, (decay, write), axis=1)
    states = states + decays * state[:, None]
    return states[:, -1], jnp.sum(states * c[:, :, None, :], axis=-1)


def selective_scan_xla(u, dt, a, b, c, chunk: int):
    """`selective_scan` as plain XLA in float32: the fallback (CPU,
    shapes that do not tile, a mesh) and the parity reference of the
    kernels' tests.  The chunks go one at a time under a `lax.scan`
    whose body is recomputed in the backward pass; T is padded to whole
    chunks with steps that neither decay nor write (dt 0)."""
    bsz, t, ch = u.shape
    length = min(int(chunk), t)
    n = -(-t // length)

    def chunks(x):      # (B, T, w) -> (chunks, B, L, w)
        x = jnp.pad(x, ((0, 0), (0, n * length - t), (0, 0)))
        return jnp.moveaxis(x.reshape(bsz, n, length, x.shape[-1]), 1, 0)

    _, y = lax.scan(
        jax.checkpoint(functools.partial(_ssm_chunk, a=a)),
        jnp.zeros((bsz, ch, a.shape[1]), jnp.float32),
        tuple(chunks(x) for x in (u, dt, b, c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, n * length, ch)[:, :t]


def _mamba_flops(lp, specs, tops):
    """Every product per position, and the recurrence as written
    besides: per token, channel and state the decay's product and its
    exponential, the write, the update and the read, 9 elementwise
    operations (vector-unit work, counted as operations, not as MXU
    work); taps, gates and softplus are not counted."""
    a_log = dict((nm, ps) for nm, ps, _ in specs)["A_log"]
    return (_products_flops(lp, specs, tops)
            + 9 * _rows(tops) * math.prod(a_log))


@register("Mamba", params=_mamba_params, flops=_mamba_flops,
          time_sharding=_whole_sequence)
def _mamba(ctx, lp, params, bottoms):
    """The selective state-space mixer (Mamba, arXiv:2312.00752) on
    time-major (T, B, D) input:

        [a, z] = x W_in;  u = silu(taps over time of a + conv_bias)
        [r, B, C] = u W_x;  dt = softplus(r W_dt + dt_bias)
        y = `selective_scan`(u, dt, -exp(A_log), B, C) + D u, float32
        out = (y * silu(z)) W_out

    A second top is y itself, before the gate: the memory that
    `GatedMemoryUnit` layers further on read.  No state crosses a batch
    column, and nothing marks a document's start inside a packed row
    (the state and the taps reach over a boundary).  Scopes: `ssm`,
    inside it `ssm.proj` (the four products), `ssm.conv` (taps, bias,
    SiLU: `causal_taps_silu`, in whichever form it lowers here) and
    `ssm.scan` (softplus, the recurrence in whichever form
    `selective_scan` lowers here, the skip)."""
    mp = lp.mamba_param
    (w_in, taps, conv_bias, w_x, w_dt, dt_bias, a_log, d_skip,
     w_out) = params
    x = bottoms[0]
    di, n, _, rank = _mamba_dims(mp, x.shape[-1])
    prec = ctx.precision()
    f32 = jnp.float32

    conv = functools.partial(causal_taps_silu, site=lp.name)

    def rows(u, r, bc, dt_bias, a_log):
        """-> u, dt (B, T, C), A (C, N), B, C (B, T, N), float32."""
        u, r, bc = (jnp.swapaxes(v.astype(f32), 0, 1) for v in (u, r, bc))
        return (u, jax.nn.softplus(r + dt_bias.astype(f32)),
                -jnp.exp(a_log.astype(f32)), bc[..., :n], bc[..., n:])

    def skip(y, u, d_skip):     # (B, T, C) -> (T, B, C), + D u
        return (jnp.swapaxes(y, 0, 1)
                + d_skip.astype(f32) * u.astype(f32)).astype(x.dtype)

    # the elementwise passes between the products are computed again in
    # the backward pass, as `GatedDeltaNet`'s are: the convolution is a
    # `stage`, `rows` and `skip` have their own checkpoint inside a
    # recompute_block too (bare, at phi4flash's shapes on the v5e, they
    # cost 1.3 and 1.8 ms a step more than their third run)
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm.proj"):
            az = jnp.einsum("tbd,ed->tbe", x, w_in, precision=prec)
        with jax.named_scope("ssm.conv"):
            u = stage(conv)(az, taps, conv_bias)
        with jax.named_scope("ssm.proj"):
            rbc = jnp.einsum("tbe,re->tbr", u, w_x, precision=prec)
            r = jnp.einsum("tbr,er->tbe", rbc[..., :rank], w_dt,
                           precision=prec)
        with jax.named_scope("ssm.scan"):
            y = selective_scan(
                *jax.checkpoint(rows)(u, r, rbc[..., rank:], dt_bias,
                                      a_log), int(mp.chunk))
            y = jax.checkpoint(skip)(y, u, d_skip)
        with jax.named_scope("ssm.proj"):
            out = jnp.einsum(
                "tbe,de->tbd", y * jax.nn.silu(az[..., di:]), w_out,
                precision=prec)
        return [out, y][:max(1, len(lp.top))]


def _mamba2_dims(mp):
    h, p, g = int(mp.num_heads), int(mp.head_dim), int(mp.n_groups)
    n, taps, chunk = int(mp.d_state), int(mp.d_conv), int(mp.chunk)
    if not (h and p and g and n) or h % g or taps < 1 or chunk < 1:
        raise ValueError(
            f"Mamba2: {h} heads of {p} over {g} groups of {n} states, "
            f"taps {taps}, chunk {chunk} (num_heads must be a multiple "
            "of n_groups)")
    return h, p, g, n


def _mamba2_params(lp, shapes):
    mp = lp.mamba2_param
    d = int(shapes[0][-1])
    h, p, g, n = _mamba2_dims(mp)
    di, conv = h * p, h * p + 2 * g * n
    wf = _filler(mp.weight_filler if mp.has("weight_filler") else None,
                 "xavier")
    cf = _filler(mp.conv_filler if mp.has("conv_filler") else None,
                 "xavier")
    one = FillerParameter(type="constant", value=1.0)
    return [("W_in", (di + conv + h, d), wf),
            ("taps", (conv, int(mp.d_conv)), cf), ("conv_bias", (conv,), cf),
            ("dt_bias", (h,), FillerParameter(
                type="inv_softplus_log_uniform", min=float(mp.dt_min),
                max=float(mp.dt_max))),
            ("A_log", (h,), FillerParameter(type="log_arange")),
            ("D", (h,), one), ("norm", (di,), one), ("W_out", (d, di), wf)]


# the scan's products keep float32 (HIGHEST), as the gated delta rule's:
# a state carried along 8,192 tokens is not rounded to bfloat16 once a
# chunk, and neither is what is read from it
_SSD_PRECISION = lax.Precision.HIGHEST

# chunks between two states that the forward pass keeps; the backward
# pass computes a group again from the state at its edge.  It also
# bounds what is alive together: a group's (heads, chunk, chunk) decay
# matrices (4 MB a chunk at 64 heads and chunks of 128) and products
_SSD_GROUP = 16


def _ssd_group(state, x, a):
    """One group of n chunks of `ssd_scan`: state (B, G, R, P, N) before
    it, x = u (B, n, L, G, R, P), dt (B, n, L, G, R), b, c (B, n, L, G,
    N), a (G, R) -> the state after it, y (B, n, L, G, R, P).

    With cum the running sum of dt A inside a chunk (<= 0), a chunk's
    own tokens give  y_t = sum_(s <= t) e^(cum_t - cum_s) (C_t . B_s)
    dt_s u_s  and leave  sum_s e^(cum_L - cum_s) dt_s u_s B_s^T  in the
    state; the state before the chunk decays into it by e^(cum_L) and
    is read by  e^(cum_t) C_t S.  All four are products over the n
    chunks at once; what goes from chunk to chunk is one elementwise
    step a chunk.  Every decay is the exponential of a difference of
    running sums, never a ratio of exponentials."""
    prec = _SSD_PRECISION
    u, dt, b, c = x
    cum = jnp.cumsum(dt * a, axis=2)                # (B, n, L, G, R)
    i = jnp.arange(u.shape[2])
    # e^(cum_t - cum_s) for s <= t, 0 above the diagonal: (B, n, G, R,
    # t, s), the (L, L) matrices innermost
    by_head = jnp.moveaxis(cum, 2, -1)
    decay = jnp.exp(jnp.where(
        i[:, None] >= i[None, :],
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    cb = jnp.einsum("bntgk,bnsgk->bngts", c, b, precision=prec)
    du = u * dt[..., None]
    y = jnp.einsum("bngrts,bnsgrp->bntgrp", decay * cb[:, :, :, None], du,
                   precision=prec)
    last = cum[:, :, -1:]
    own = jnp.einsum("bnsgrp,bnsgk->bngrpk",
                     du * jnp.exp(last - cum)[..., None], b, precision=prec)

    def step(state, x):     # -> the state after a chunk; emits the one before
        through, own = x
        return through[..., None, None] * state + own, state

    state, before = lax.scan(
        step, state, (jnp.moveaxis(jnp.exp(last[:, :, 0]), 1, 0),
                      jnp.moveaxis(own, 1, 0)))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bntgk,bngrpk->bntgrp", c, jnp.moveaxis(before, 0, 1),
        precision=prec)
    return state, y


@jax.custom_vjp
def _ssd_groups(xs, a):
    """The groups of `ssd_scan` in order: xs = u, dt, b, c with the
    groups on axis 0 (`_ssd_group`'s operands behind it) -> y likewise.
    Its own backward: what is kept is y and the state before every
    group, nothing a token and nothing a chunk."""
    return _ssd_groups_fwd(xs, a)[0]


def _ssd_groups_fwd(xs, a):
    u, b = xs[0], xs[2]
    zero = jnp.zeros(u.shape[1:2] + u.shape[4:] + b.shape[-1:], u.dtype)

    def group(state, x):
        after, y = _ssd_group(state, x, a)
        return after, (y, state)

    _, (y, edges) = lax.scan(group, zero, xs)
    # what a recompute_block keeps of the scan: the gated norm's and the
    # skip's backward read y, a group's recomputation starts from its
    # edge
    y, edges = keep(y, "ssd.y"), keep(edges, "ssd.edges")
    return y, (xs, a, edges)


def _ssd_groups_bwd(res, dy):
    """The groups last to first: a group is computed again from the
    state kept at its edge and pulled back against its outputs'
    cotangent and the cotangent of the state it leaves."""
    xs, a, edges = res

    def group(carry, x):
        dstate, da = carry
        edge, x, dy = x
        _, pull = jax.vjp(_ssd_group, edge, x, a)
        dstate, dx, da_own = pull((dstate, dy))
        return (dstate, da + da_own), dx

    (_, da), dxs = lax.scan(
        group, (jnp.zeros_like(edges[0]), jnp.zeros_like(a)),
        (edges, xs, dy), reverse=True)
    return dxs, da


_ssd_groups.defvjp(_ssd_groups_fwd, _ssd_groups_bwd)


def ssd_scan(x, dt, a, d, groups: int, states: int, chunk: int = 128):
    """The Mamba-2 recurrence (state-space duality, arXiv:2405.21060)
    over the sequence, in chunks, with the skip: a (P, N) matrix state a
    head under one scalar decay a head and token,

        S_t[h] = exp(dt_t[h] A[h]) S_(t-1)[h] + dt_t[h] u_t[h] B_t[g]^T
        y_t[h] = S_t[h] C_t[g] + D[h] u_t[h],  S_(-1) = 0,  g = h // (H / G)

    on time-major operands where the convolution stage leaves them: x
    (T, B, W) = [u (H P) | B (G N) | C (G N)] along W; dt (T, B, H), > 0;
    a = A (H,), < 0; d = D (H,) -> y (T, B, H P), all float32.  No state
    crosses a batch column.  Inside a chunk of `chunk` tokens the rule
    is products of (chunk, chunk) and (chunk, N) matrices at
    `_SSD_PRECISION`; the states are carried from chunk to chunk; every
    decay is the exponential of a difference of running sums.

    Two forms, one algorithm.  The Mosaic kernels
    (`pallas_kernels.ssd_scan_kernels`: a group's u, B and C read from x
    in place, its (R P, N) state in VMEM from a row's first chunk to its
    last, y written time-major with the skip added, the state before
    every chunk kept for the backward kernel) where `route.kernel` says
    so: the chunk, N and a group's R P channels fill whole 128-lane
    tiles and the calls fit the default VMEM window (`ssd_scan_plan`);
    else `ssd_scan_xla` (the CPU, a mesh, a shape that does not tile),
    which keeps the state every `_SSD_GROUP` chunks and computes a group
    again in its backward pass.  `route.plans()["ssd"]` (the job's
    `info.ssd`) says by operator shape which form was lowered (`form`:
    "kernel" or "xla"), the chunk, the chunks a row and between two
    kept states, the kept states' bytes and, of the kernels, the chunks
    a grid step and the VMEM the larger call takes."""
    from .pallas_kernels import ssd_scan_kernels, ssd_scan_plan
    t, bsz, w = x.shape
    h, g, n = dt.shape[-1], int(groups), int(states)
    p = (w - 2 * g * n) // h
    plan = ssd_scan_plan(t, bsz, h, p, g, n, int(chunk))
    kernel = route.kernel(plan, x, dt, a, d)
    if kernel:
        length, chunks, grp = plan["chunk"], plan["chunks"], 1
    else:
        length = min(int(chunk), t)
        chunks = -(-t // length)
        grp = min(_SSD_GROUP, chunks)
    route.lowered(
        "ssd", f"{bsz}x{t} {h} heads of {p} over {g} groups of {n} states",
        form="kernel" if kernel else "xla", chunk=length, chunks=chunks,
        chunks_a_group=grp,
        edges_bytes=bsz * -(-chunks // grp) * h * p * n * 4,
        **({"chunks_a_step": plan["steps"],
            "vmem_bytes": plan["vmem_bytes"]} if kernel else {}))
    if kernel:
        return ssd_scan_kernels(x, dt, a, d, plan, groups=g, states=n,
                                interpret=kernel.interpret)
    return ssd_scan_xla(x, dt, a, d, g, n, length)


def ssd_scan_xla(x, dt, a, d, g: int, n: int, length: int):
    """`ssd_scan` as XLA products at `_SSD_PRECISION`: the fallback (CPU,
    a shape that does not tile, a mesh) and the parity reference of the
    kernels' tests.  u, B and C are sliced out of x and turned
    batch-major, the chunks go `_SSD_GROUP` at a time (`_ssd_group`)
    under `_ssd_groups`' own backward, which keeps y and the state every
    group, nothing a token; T is padded to whole groups with steps that
    neither decay nor write (dt 0); the skip and the swap back are a
    pass of their own.  The passes around the scan are computed again
    in the backward pass (`jax.checkpoint`, inside a recompute_block
    too: bare they were slower on the chip, PR 43)."""
    t, bsz, w = x.shape
    h = dt.shape[-1]
    di, bc = w - 2 * g * n, g * n
    p, r = di // h, h // g
    f32 = jnp.float32
    chunks = -(-t // length)
    grp = min(_SSD_GROUP, chunks)
    groups = -(-chunks // grp)
    full = groups * grp * length

    def rows(x, dt):
        """-> u (B, T, H, P), dt (B, T, H), B, C (B, T, G, N), float32."""
        x, dt = (jnp.swapaxes(v.astype(f32), 0, 1) for v in (x, dt))
        return (x[..., :di].reshape(bsz, t, h, p), dt,
                x[..., di:di + bc].reshape(bsz, t, g, n),
                x[..., di + bc:].reshape(bsz, t, g, n))

    def skip(y, x, d):      # (B, T, H, P) -> (T, B, H P), + D u
        u = x[..., :di].astype(f32).reshape(t, bsz, h, p)
        return (jnp.swapaxes(y, 0, 1)
                + d.astype(f32)[:, None] * u).reshape(t, bsz, di)

    def grouped(x, *tail):
        """(B, T, ...) -> (groups, B, chunks of a group, L, *tail)."""
        x = jnp.pad(x, ((0, 0), (0, full - t)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(bsz, groups, grp, length, *tail), 1, 0)

    u, dt, b, c = jax.checkpoint(rows)(x, dt)
    y = _ssd_groups((grouped(u, g, r, p), grouped(dt, g, r),
                     grouped(b, g, n), grouped(c, g, n)),
                    a.astype(f32).reshape(g, r))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, full, h, p)[:, :t]
    return jax.checkpoint(skip)(y, x, d)


def _mamba2_flops(lp, specs, tops):
    """The two products per position, and the recurrence as written
    besides: per token and head the decay of the state, the rank-one
    write and the read, P x N multiply-adds each (not the chunked
    form's products); taps, gates, softplus and the norm are not
    counted."""
    h, p, _, n = _mamba2_dims(lp.mamba2_param)
    return _products_flops(lp, specs, tops) + _rows(tops) * h * 3 * 2 * p * n


@register("Mamba2", params=_mamba2_params, flops=_mamba2_flops,
          time_sharding=_whole_sequence)
def _mamba2(ctx, lp, params, bottoms):
    """The Mamba-2 mixer (nemotron_h's `M` operator) on time-major (T,
    B, D) input, H heads of P channels, G groups of N states:

        [z | xBC | dt] = x W_in
        xBC = silu(taps over time of xBC + conv_bias), causal
        [u | B | C] = xBC;  dt = softplus(dt + dt_bias)
        y = `ssd_scan`([u | B | C], dt, -exp(A_log), D), float32: the
            recurrence and the skip D u
        y = RMSNorm(y * silu(z)) over each of the G groups of channels,
            times the H P-wide `norm`
        out = y W_out

    No state crosses a batch column, and nothing marks a document's
    start inside a packed row (the state and the taps reach over a
    boundary).  W_in's product is made in two parts, [z | xBC] and dt,
    so that the convolution reads its channels where they lie in a
    wide array of whole 128-lane tiles (`causal_taps_silu(first=)`).
    Scopes: `ssd`, inside it `ssd.proj` (the products), `ssd.conv`
    (taps, bias, SiLU: `causal_taps_silu`, in whichever form it lowers
    here), `ssd.scan` (softplus, the decays, the chunked scan and the
    skip: `ssd_scan`, in whichever form it lowers here, reads xBC where
    the convolution wrote it) and `ssd.norm` (the gate and the grouped
    norm)."""
    mp = lp.mamba2_param
    w_in, taps, conv_bias, dt_bias, a_log, d_skip, norm, w_out = params
    x = bottoms[0]
    t, bsz = x.shape[0], x.shape[1]
    h, p, g, n = _mamba2_dims(mp)
    di, bc = h * p, g * n
    wide = 2 * di + 2 * bc
    prec = ctx.precision()
    f32 = jnp.float32
    eps = float(mp.rms_norm_eps)

    conv = functools.partial(causal_taps_silu, site=lp.name, first=di)

    def gate(y, z, norm):       # -> (T, B, H P), gated, normed by group
        y = (y.reshape(t, bsz, di) * jax.nn.silu(z.astype(f32))).reshape(
            t, bsz, g, di // g)
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        return (y.reshape(t, bsz, di) * norm.astype(f32)).astype(x.dtype)

    # the elementwise passes between the products are computed again in
    # the backward pass, as `Mamba`'s are: the convolution is a `stage`;
    # `gate`, which reads the scan's kept output, has its own checkpoint
    # inside a recompute_block too (and so have the passes around the
    # scan's XLA form: `ssd_scan_xla`)
    with jax.named_scope("ssd"):
        with jax.named_scope("ssd.proj"):
            zx = jnp.einsum("tbd,ed->tbe", x, w_in[:wide], precision=prec)
            dt = jnp.einsum("tbd,ed->tbe", x, w_in[wide:], precision=prec)
        with jax.named_scope("ssd.conv"):
            xbc = stage(conv)(zx, taps, conv_bias)
        with jax.named_scope("ssd.scan"):
            y = ssd_scan(
                xbc, jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
                -jnp.exp(a_log.astype(f32)), d_skip.astype(f32), g, n,
                int(mp.chunk))
        with jax.named_scope("ssd.norm"):
            y = jax.checkpoint(gate)(y, zx[..., :di], norm)
        with jax.named_scope("ssd.proj"):
            return [jnp.einsum("tbe,de->tbd", y, w_out, precision=prec)]


def _gmu_params(lp, shapes):
    gp = lp.gated_memory_unit_param
    if len(shapes) != 2:
        raise ValueError(f"GatedMemoryUnit {lp.name!r}: bottoms are the "
                         "stream and the memory")
    d, m = int(shapes[0][-1]), int(shapes[1][-1])
    wf = _filler(gp.weight_filler if gp.has("weight_filler") else None,
                 "xavier")
    return [("W_in", (m, d), wf), ("W_out", (d, m), wf)]


@register("GatedMemoryUnit", params=_gmu_params, flops=_products_flops,
          time_sharding=_whole_sequence)
def _gmu(ctx, lp, params, bottoms):
    """The Gated Memory Unit (arXiv:2507.06607) on time-major input:
    y = (m * silu(x W_in)) W_out, x (T, B, D) the normed stream, m (T,
    B, M) the memory an earlier layer made (a `Mamba` layer's second
    top).  Scope `gmu`."""
    w_in, w_out = params
    x, mem = bottoms
    prec = ctx.precision()
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(jnp.einsum("tbd,ed->tbe", x, w_in,
                                      precision=prec))
        return [jnp.einsum("tbe,de->tbd", mem.astype(gate.dtype) * gate,
                           w_out, precision=prec)]


def _moe_held(mp):
    """(first expert, experts held) of a layer: all of them unless the
    layer is told its share."""
    e = int(mp.num_experts)
    held = int(mp.experts_held) or e
    first = int(mp.first_expert)
    if first + held > e:
        raise ValueError(
            f"moe_param: experts [{first}, {first + held}) of {e}")
    return first, held


def _moe_gate_activation(mp) -> str:
    if mp.gate_activation not in ("silu", "relu"):
        raise ValueError(f"moe_param.gate_activation "
                         f"{mp.gate_activation!r}: expected silu or relu")
    return mp.gate_activation


def _moe_activation(mp):
    """What `_moe_pass` and the shared expert are handed as `gated`:
    the gate's activation for gated experts (a `jax.nn` name: "silu" |
    "relu"), else False for today's ungated ReLU experts and "relu2"
    for ungated experts with a squared ReLU (`_moe_hidden`)."""
    if mp.gated:
        return _moe_gate_activation(mp)
    if mp.activation not in ("relu", "relu2"):
        raise ValueError(f"moe_param.activation {mp.activation!r}: "
                         "expected relu or relu2")
    return "relu2" if mp.activation == "relu2" else False


def _moe_hidden(gated, first, second):
    """An expert's hidden activation from its first product and (gated
    experts: a thunk of) its second."""
    if gated == "relu2":
        return jnp.square(jax.nn.relu(first))
    if gated:
        # `gated` names the gate's activation: silu | relu
        return getattr(jax.nn, gated)(first) * second()
    return jax.nn.relu(first)


def _moe_params(lp, shapes):
    mp = lp.moe_param
    d = int(shapes[0][-1])
    e = int(mp.num_experts)
    h = int(mp.hidden_dim)
    if mp.dispatch == "dropless":
        wf = _filler(mp.weight_filler if mp.has("weight_filler")
                     else None, "xavier")
        _, held = _moe_held(mp)
        specs = [("router", (d, e), wf)]
        if mp.selection_bias:
            specs.append(("bias", (e,),
                          FillerParameter(type="constant", value=0.0)))
        if mp.gated:
            specs += [("W_gate", (held, d, h), wf),
                      ("W_up", (held, d, h), wf),
                      ("W_down", (held, h, d), wf)]
        else:
            specs += [("W1", (held, d, h), wf), ("W2", (held, h, d), wf)]
        hs = int(mp.shared_hidden_dim)
        if hs:
            if mp.gated:
                specs.append(("S_gate", (d, hs), wf))
            specs += [("S_up", (d, hs), wf), ("S_down", (hs, d), wf)]
            if mp.shared_gate:
                specs.append(("S_sgate", (d, 1), wf))
        return specs
    if mp.dispatch != "capacity":
        raise ValueError(f"moe_param.dispatch {mp.dispatch!r}: "
                         "expected capacity or dropless")
    if mp.has("weight_filler"):
        wf = _filler(mp.weight_filler)
        return [("router", (d, e), wf), ("W1", (e, d, h), wf),
                ("W2", (e, h, d), wf)]
    # explicit xavier-equivalent uniform bounds: the generic fan
    # heuristic (fan_in = count/shape[0]) misreads these layouts —
    # router is (in, out) and W1/W2 carry a leading expert dim
    def unif(fan_in):
        s = math.sqrt(3.0 / fan_in)
        return FillerParameter(type="uniform", min=-s, max=s)

    return [("router", (d, e), unif(d)), ("W1", (e, d, h), unif(d)),
            ("W2", (e, h, d), unif(h))]


def _moe_flops(lp, specs, tops):
    """Router over all experts for every token, plus the expert
    products a token's k assignments touch.  `capacity` dispatch runs
    every expert on its full (C, D) buffer; `dropless` runs, for an
    even router, the k x held / experts of the assignments that fall on
    the experts this layer holds, plus the shared experts on every
    token."""
    shapes = dict((nm, ps) for nm, ps, _ in specs)
    n = _rows(tops)
    mp = lp.moe_param
    e, k = int(mp.num_experts), max(1, int(mp.top_k))
    total = 2 * n * math.prod(shapes["router"])
    if mp.dispatch == "dropless":
        held = int(mp.experts_held) or e
        per_expert = sum(math.prod(ps[1:]) for nm, ps in shapes.items()
                         if nm.startswith("W"))
        total += int(2 * n * k * held / e * per_expert)
        total += 2 * n * sum(math.prod(ps) for nm, ps in shapes.items()
                             if nm.startswith("S_"))
        return total
    cap = max(1, int(math.ceil(k * n / e * float(mp.capacity_factor))))
    return total + 2 * cap * (math.prod(shapes["W1"])
                              + math.prod(shapes["W2"]))


@register("MixtureOfExperts", params=_moe_params, flops=_moe_flops,
          time_sharding=_cut_anywhere)
def _moe(ctx, lp, params, bottoms):
    """Top-k routed expert FFN on (..., D) input — extension beyond the
    reference.  `moe_param.dispatch: "dropless"` is `_moe_dropless`
    below (sorted assignments, grouped products over the experts held,
    sigmoid or softmax scoring, gated and shared experts).  The default,
    `"capacity"`, is what this docstring describes from here on, built
    the way TPU MoE stacks were (Switch/GShard-style fixed expert
    capacity):

    * each token's top-k experts get it IF the expert still has room;
      capacity C = ceil(k·N/E · capacity_factor) is a static shape, so
      the dispatch is a scatter into a dense (E, C, D) buffer (mode
      'drop' discards overflow) and the expert FFN is two expert-major
      batched matmuls that shard over the `ep` mesh axis under GSPMD
      (`parallel.dp.tp_param_specs`) — memory O(E·C·D), not O(E·N·D);
    * gates come from the softmax router (normalized over the chosen k
      for k>1), so routing stays differentiable through the combine;
    * if the layer declares a second top it emits the load-balancing
      auxiliary loss  E · Σ_e f_e·P_e  (f = realized assignment
      fraction, P = mean router probability) — weight it with the
      layer's second `loss_weight`.
    """
    mp = lp.moe_param
    if mp.dispatch == "dropless":
        return _moe_dropless(ctx, lp, params, bottoms)
    router, w1, w2 = params
    x = bottoms[0]
    lead = x.shape[:-1]
    d = x.shape[-1]
    e = int(mp.num_experts)
    k = max(1, int(mp.top_k))
    xf = x.reshape(-1, d)                       # (N, D) tokens
    n = xf.shape[0]
    cap = max(1, int(math.ceil(k * n / e * float(mp.capacity_factor))))

    logits = xf @ router                        # (N, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(probs, k)            # (N, k)
    gates = topv / topv.sum(-1, keepdims=True) if k > 1 else topv

    # slot-major flattening: every token's 1st choice claims capacity
    # before any token's 2nd choice (GShard dispatch order)
    flat_e = topi.T.reshape(-1)                 # (k·N,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.float32)
    # position of each assignment within its expert's buffer — int32
    # accumulation: a float32 cumsum is exact only to 2^24, beyond
    # which positions silently collide and corrupt capacity accounting
    ionehot = onehot.astype(jnp.int32)
    pos = jnp.cumsum(ionehot, axis=0) - 1
    pos = jnp.sum(pos * ionehot, axis=-1)       # (k·N,) int32
    keep = pos < cap

    tokens = jnp.tile(xf, (k, 1))               # (k·N, D) slot-major
    disp = jnp.zeros((e, cap, d), x.dtype).at[flat_e, pos].set(
        tokens, mode="drop")                    # overflow dropped
    hidden = jax.nn.relu(jnp.einsum("ecd,edh->ech", disp, w1))
    out = jnp.einsum("ech,ehd->ecd", hidden, w2)

    gathered = out[flat_e, jnp.minimum(pos, cap - 1)]       # (k·N, D)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    gf = (gates.T.reshape(-1)[:, None].astype(x.dtype) * gathered)
    combined = gf.reshape(k, n, d).sum(axis=0)
    tops = [combined.reshape(lead + (d,))]

    if len(lp.top) > 1:
        # Switch-Transformer balance loss: realized assignment
        # fraction × mean router prob, scaled by E (=1 at uniform)
        frac = onehot.mean(axis=0)              # (E,)
        mean_p = probs.mean(axis=0)
        tops.append((e * jnp.sum(frac * mean_p)).astype(jnp.float32))
    return tops


# A pass's rows come in whole multiples of this (`_moe_chunk_rows`): four
# row tiles of the grouped-product kernels (`pallas_kernels.GMM_ROW_TILE`,
# which divides it), and what `lax.ragged_dot`'s calls tile their rows by
# where the XLA form runs.
_MOE_ROW_TILE = 512


def _moe_chunk_rows(n: int, k: int, held: int, e: int) -> int:
    """Rows of sorted assignments one pass of the grouped products
    takes: 4/3 of the k·N·held/E an even router sends to the held
    experts, in whole tiles of 512, at most all k·N.  Not a capacity:
    a step whose held assignments outnumber it takes further passes."""
    want = -(-4 * k * n * held // (3 * e))
    return min(k * n, -(-want // _MOE_ROW_TILE) * _MOE_ROW_TILE)


# `route.plans()["moe"]`, under the two names that the benchmark's
# reader of `moe.products_mfu_pct.train` and its tests know
_MOE_PLANS = route.entries("moe")


def moe_plans() -> dict:
    return copy.deepcopy(_MOE_PLANS)


class _MoeKernels(NamedTuple):
    """The kernel form of a layer shape's grouped products: the tiles
    against the first weights (G, D, hidden) and against the last (G,
    hidden, D), and whether the calls run in interpret mode."""
    into: "GmmTiles"
    out: "GmmTiles"
    interpret: bool


def _moe_products(kernels, sizes, rows, prec):
    """The grouped product of a pass whose group g owns `sizes[g]` of
    its `rows` sorted rows: `product(a, w, out=False)`, a (rows, K)
    against w (G, K, N), `out` for the experts' last weights.  The
    Mosaic kernels (`pallas_kernels.grouped_product`: float32 tiles of a
    and of w rounded to bfloat16 in VMEM, accumulated in float32, w and,
    in the backward pass, its transpose read where they lie) where the
    layer took that form, else `lax.ragged_dot` at `prec`."""
    if not kernels:
        return lambda a, w, out=False: lax.ragged_dot(
            a, w.astype(a.dtype), sizes, precision=prec)
    from .pallas_kernels import gmm_visits, grouped_product
    visits = gmm_visits(sizes, rows)
    return lambda a, w, out=False: grouped_product(
        a, w, visits, kernels.out if out else kernels.into,
        kernels.interpret)


def _moe_pass(acc, lo, xf, gates, w_in, w_out, order, starts, ends, total,
              rows, k, gated, prec, kernels=None):
    """`acc` plus what the sorted rows [lo, lo + rows) add to it: linear
    in `acc`, so a pass's gradients need no running sum."""
    with jax.named_scope("moe.gather"):
        idx = lax.dynamic_slice(order, (lo,), (rows,))
        valid = (lo + jnp.arange(rows)) < total
        idx = jnp.where(valid, idx, 0)
        tok = idx // k
        sizes = (jnp.clip(ends - lo, 0, rows)
                 - jnp.clip(starts - lo, 0, rows))
        xs = jnp.where(valid[:, None], xf[tok], 0)
    with jax.named_scope("moe.products"):
        product = _moe_products(kernels, sizes, rows, prec)
        hid = _moe_hidden(gated, product(xs, w_in[0]),
                          lambda: product(xs, w_in[1]))
        ys = product(hid, w_out, out=True)
    with jax.named_scope("moe.combine"):
        # rows past the last group hold whatever the kernels left (NaN
        # bit patterns included, in either form): they are cut out
        # BEFORE the product, so that neither the sum nor the gates'
        # gradient (d/dg = ys) ever sees them
        ys = jnp.where(valid[:, None], ys, 0) \
            * gates[idx][:, None].astype(ys.dtype)
        return acc.at[tok].add(ys)


def _moe_passes_run(total, rows, n_pass):
    """Held rows sort first: the passes that hold one are the first
    ceil(total / rows)."""
    return jnp.minimum(n_pass, (total + rows - 1) // rows)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(8, 9, 10, 11, 12, 13))
def _moe_passes(xf, gates, w_in, w_out, order, starts, ends, total, rows,
                n_pass, k, gated, prec, kernels=None):
    """The routed sum over the passes that run, (N, D).  The trip count
    is the step's own, forward and backward; the integer operands are
    arguments (a tracer closed over would leak under the block's
    `jax.checkpoint`) and take no cotangent."""
    return _moe_passes_fwd(xf, gates, w_in, w_out, order, starts, ends,
                           total, rows, n_pass, k, gated, prec, kernels)[0]


def _moe_passes_fwd(xf, gates, w_in, w_out, order, starts, ends, total,
                    rows, n_pass, k, gated, prec, kernels):
    res = (xf, gates, w_in, w_out, order, starts, ends, total)
    routed = lax.fori_loop(
        0, _moe_passes_run(total, rows, n_pass),
        lambda i, acc: _moe_pass(acc, i * rows, *res, rows, k, gated, prec,
                                 kernels),
        jnp.zeros_like(xf))
    return routed, res


def _moe_passes_bwd(rows, n_pass, k, gated, prec, kernels, res, g):
    """The passes that ran, last to first (the order in which a scan's
    transpose sums, so the gradients round as its would): a pass is
    computed again, pulled back against `g` (every pass's output
    cotangent, the pass being linear in the running sum) and added into
    one accumulator a differentiable operand."""
    *diff, order, starts, ends, total = res
    n_run = _moe_passes_run(total, rows, n_pass)

    def body(i, sums):
        lo = (n_run - 1 - i) * rows
        # any running sum will do (here `g`, for its shape): the pass's
        # value is not used, and the gradients do not depend on it
        _, pull = jax.vjp(
            lambda *a: _moe_pass(g, lo, *a, order, starts, ends, total,
                                 rows, k, gated, prec, kernels), *diff)
        return jax.tree.map(jnp.add, sums, pull(g))

    sums = lax.fori_loop(0, n_run, body,
                         jax.tree.map(jnp.zeros_like, tuple(diff)))
    return (*sums, None, None, None, None)


_moe_passes.defvjp(_moe_passes_fwd, _moe_passes_bwd)


def _moe_dropless(ctx, lp, params, bottoms):
    """Routed experts without a capacity, for a layer that may hold
    only some of the experts.

    Router: s = sigmoid(x W_g) (or softmax) over ALL `num_experts`, in
    float32 at HIGHEST precision (a routing choice must not turn on a
    bfloat16 rounding); the k experts with the largest s + bias are
    chosen; weights s_i / (sum(s chosen) + norm_epsilon) x
    routed_scaling_factor.  With a second bottom the router reads that
    (a block's normed input, before its attention) and the experts the
    first; the router's cotangent then flows into the second.

    Dispatch: the k·N assignments are sorted by expert, those of
    experts held elsewhere last.  The sorted rows are taken in passes
    of `_moe_chunk_rows` rows: gather the tokens, three grouped
    products (two for ungated experts; `_moe_products`: on the TPU the
    repo's own Mosaic kernels, `pallas_kernels.grouped_product`, one
    bfloat16 pass of float32 tiles with float32 accumulation, in row
    tiles of 128 that visit only the tiles a group covers; under a mesh,
    at HIGHEST, off the TPU and for shapes that do not tile
    `lax.ragged_dot`, the compiler's grouped matmul), weight,
    scatter-add.
    Only the passes that hold a held assignment run: held rows sort
    first, so they are the first ceil(held / rows) (`_moe_passes_run`), a
    number known on the device before the loop starts.  An even router
    needs one, every token on one held expert needs them all, and
    nothing is ever dropped.  The loop has its own backward
    (`_moe_passes`, a `jax.custom_vjp`): a second loop of the same trip
    count that computes each pass again, last first, pulls it back
    against the output's cotangent and adds the pass's gradients (tokens,
    gates, every expert weight) into one accumulator each.  So the layer
    keeps no k·N-row activation and, a pass being linear in the running
    sum, no running sum either: the backward keeps the loop's inputs and
    nothing else, and a block's `recompute_block` has no forward loop to
    run again.  A pass that does not run costs nothing, forward or
    backward; a step whose router fills one pass fills each accumulator
    with zeros once and adds into it once.  `moe_plans()` has the passes
    a shape takes and the accumulators' bytes.

    Scopes on the device's ops (forward, recomputation and transpose
    carry the same token): `moe.route` holds the router's product, the
    scoring, `top_k` and the weights, and inside it `moe.sort` the held
    mask, the argsort, the counts and their running sums; `moe.experts`
    holds both loops over the passes, and inside a pass `moe.gather`
    (the slice of the order, the row gather; transposed, a scatter-add
    into dx), `moe.products` (the grouped products and the activation
    between them) and `moe.combine` (the mask, the gate product, the
    scatter-add; transposed, a gather).  What `moe.experts` holds
    outside those three is the loops' own: the trip count, the carries,
    the accumulators' zero fill and the sums into them.  `moe.shared`
    holds the shared experts.

    Experts: gated (`W_gate`, `W_up`, `W_down`; the gate through SiLU
    or `gate_activation`) or, `gated: false`, two matrices around
    `activation` (ReLU, or "relu2" its square): `_moe_hidden`, the
    routed and the shared experts alike.

    What the absent experts would add is left out: the result is this
    share's part of the routed sum plus the shared experts (with
    `shared_gate` times sigmoid(x w_sg), one gate a token)."""
    mp = lp.moe_param
    names = [n for n, _, _ in _moe_params(lp, [bottoms[0].shape])]
    pd = dict(zip(names, params))
    x = bottoms[0]
    lead, d = x.shape[:-1], x.shape[-1]
    e, k = int(mp.num_experts), max(1, int(mp.top_k))
    first, held = _moe_held(mp)
    gated = _moe_activation(mp)
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    routed_from = bottoms[1].reshape(-1, d) if len(bottoms) > 1 else xf

    # what a recompute_block keeps of the routing (`recompute.KEPT`):
    # the values the router's own backward and the expert loops read, so
    # that the recomputation has no reader for the product at HIGHEST,
    # top_k or the argsort
    with jax.named_scope("moe.route"):
        logits = keep(jnp.matmul(routed_from.astype(jnp.float32),
                                 pd["router"].astype(jnp.float32),
                                 precision=lax.Precision.HIGHEST),
                      "moe.logits")
        if mp.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif mp.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"moe_param.scoring {mp.scoring!r}")
        sel = scores
        if "bias" in pd:
            sel = scores + lax.stop_gradient(
                pd["bias"].astype(jnp.float32))[None, :]
        topi = keep(lax.top_k(sel, k)[1], "moe.topi")        # (N, k)
        topv = jnp.take_along_axis(scores, topi, axis=1)
        if k > 1:
            total_s = jnp.sum(topv, axis=-1, keepdims=True)
            if float(mp.norm_epsilon):
                total_s = total_s + float(mp.norm_epsilon)
            topv = topv / total_s
        gates = keep((topv * float(mp.routed_scaling_factor)).reshape(
            -1).astype(xf.dtype), "moe.gates")
        with jax.named_scope("moe.sort"):
            # token-major flattening: assignment a belongs to token
            # a // k
            local = topi.reshape(-1) - first
            on_held = (local >= 0) & (local < held)
            group = jnp.where(on_held, local, held)          # absent last
            order = jnp.argsort(group, stable=True)
            counts = jnp.zeros((held + 1,),
                               jnp.int32).at[group].add(1)[:held]
            ends = jnp.cumsum(counts)
            starts = keep(ends - counts, "moe.starts")
            ends = keep(ends, "moe.ends")
            total = keep(ends[-1], "moe.total")

    rows = _moe_chunk_rows(n, k, held, e)
    n_pass = -(-(k * n) // rows)
    order = keep(jnp.pad(order, (0, n_pass * rows - k * n)), "moe.order")
    prec = ctx.precision()
    w_in = (pd["W_gate"], pd["W_up"]) if mp.gated else (pd["W1"],)
    w_out = pd["W_down"] if mp.gated else pd["W2"]
    hidden, products = int(w_out.shape[1]), len(w_in) + 1
    # the form of the grouped products: the kernels where the shape
    # tiles, float32 comes in, no mesh is installed and the layer is not
    # pinned to float32 products (an autotune plan's HIGHEST)
    from .pallas_kernels import GMM_ROW_TILE, gmm_plan
    tiles = (gmm_plan(rows, d, hidden, held), gmm_plan(rows, hidden, d, held))
    kernel = route.kernel(
        all(tiles) and prec != lax.Precision.HIGHEST, xf, gates, *w_in,
        w_out)
    kernels = _MoeKernels(*tiles, kernel.interpret) if kernel else None
    # `info.moe`, by layer shape: the layers that took it, the k N
    # assignments, the rows and the number of passes, the passes an even
    # router fills, the rows' quantum, the forward operations a held row
    # costs, the bytes of expert weight gradient that the backward
    # loop carries, added into once a pass that runs, the form of the
    # grouped products ("kernel" | "xla") and, of the kernels, the tiles
    # (`pallas_kernels.GmmTiles` against the first and the last weights)
    # and the call sites a layer's step holds: a product's forward call,
    # and in the backward loop the call again, its rows^T and its weights
    plan = route.lowered(
        "moe", f"{n}x{d} top {k} of {e}, {held} held x {hidden}"
        f"{' gated' if mp.gated else ''}"
        f"{' by relu' if gated == 'relu' else ''}"
        f"{' relu2' if gated == 'relu2' else ''}, "
        f"shared {int(mp.shared_hidden_dim)}")
    plan.setdefault("layers", [])
    plan.update(
        assignments=k * n, rows=rows, passes=n_pass,
        passes_even_router=-(-(k * n * held) // (e * rows)),
        row_tile=_MOE_ROW_TILE, row_flops=2 * d * hidden * products,
        carry_bytes=(held * products * d * hidden
                     * jnp.dtype(w_out.dtype).itemsize),
        form="kernel" if kernels else "xla",
        calls=4 * products if kernels else 0,
        **({"tiles": {side: {"rows": GMM_ROW_TILE, **t._asdict()}
                      for side, t in zip(("into", "out"), tiles)}}
           if kernels else {}))
    if lp.name not in plan["layers"]:
        plan["layers"].append(lp.name)

    with jax.named_scope("moe.experts"):
        routed = _moe_passes(xf, gates, w_in, w_out, order, starts, ends,
                             total, rows, n_pass, k, gated, prec, kernels)

    out = routed
    if "S_up" in pd:
        with jax.named_scope("moe.shared"):
            if mp.gated:        # SiLU-gated whatever gates the routed ones
                hs = jax.nn.silu(jnp.matmul(xf, pd["S_gate"],
                                            precision=prec)) \
                    * jnp.matmul(xf, pd["S_up"], precision=prec)
            else:
                hs = _moe_hidden(gated, jnp.matmul(xf, pd["S_up"],
                                                   precision=prec), None)
            shared = jnp.matmul(hs, pd["S_down"], precision=prec)
            if "S_sgate" in pd:     # one sigmoid gate a token
                shared = shared * jax.nn.sigmoid(
                    jnp.matmul(xf, pd["S_sgate"], precision=prec))
            out = out + shared
    tops = [out.reshape(lead + (d,))]
    if len(lp.top) > 1:
        cf = counts.astype(jnp.float32)
        # dropped = held assignments less the rows the passes cover;
        # the passes cover all k·N sorted rows, so this reads 0 unless
        # a later change to the pass arithmetic breaks that
        covered = jnp.minimum(total, n_pass * rows)
        tops.append(jnp.stack([
            jnp.max(cf) / jnp.maximum(jnp.mean(cf), 1.0),
            total.astype(jnp.float32) / (k * n),
            (total - covered).astype(jnp.float32)]))
    if len(lp.top) > 2:
        tops.append(counts.astype(jnp.float32))
    return tops


# ---------------------------------------------------------------------------
# recurrent layers (time-major (T, B, ·), cont-gated — Caffe RecurrentLayer)
# ---------------------------------------------------------------------------

def _lstm_params(lp, shapes):
    rp = lp.recurrent_param
    n = int(rp.num_output)
    d = math.prod(shapes[0][2:]) if len(shapes[0]) > 2 else 1
    wf = _filler(rp.weight_filler if rp.has("weight_filler") else None)
    bf = _filler(rp.bias_filler if rp.has("bias_filler") else None)
    specs = [("W_xc", (4 * n, d), wf), ("b_c", (4 * n,), bf),
             ("W_hc", (4 * n, n), wf)]
    # bottoms: x, cont[, x_static][, c_0, h_0 (expose_hidden)]
    n_state = 2 if rp.expose_hidden else 0
    if len(shapes) - n_state > 2:  # static input bottom present
        ds = math.prod(shapes[2][1:])
        specs.append(("W_xc_static", (4 * n, ds), wf))
    return specs


@register("LSTM", params=_lstm_params)
def _lstm(ctx, lp, params, bottoms):
    """Caffe LSTMLayer: x (T,B,D), cont (T,B) in {0,1}; gate order i,f,o,g;
    cont gates both h_{t-1} and c_{t-1} (sequence restart ⇒ zero state).
    Time loop is a `lax.scan` — XLA compiles one fused step, the MXU sees
    a (B,D)x(D,4N) matmul per step; the big x-projection for ALL steps is
    hoisted out of the scan as one (T*B,D)x(D,4N) matmul.

    expose_hidden: bottoms gain [h_0, c_0] ((1,B,N) or (B,N)) after any
    static input; tops gain [h_T, c_T] — Caffe's LSTMLayer orders the
    recurrent blobs h-first (RecurrentInputBlobNames) — enabling chunked
    sequences and O(T) incremental decoding."""
    rp = lp.recurrent_param
    n = int(rp.num_output)
    expose = bool(rp.expose_hidden)
    x, cont = bottoms[0], bottoms[1]
    t_steps, batch = x.shape[0], x.shape[1]
    xf = x.reshape(t_steps, batch, -1)
    w_xc, b_c, w_hc = params[0], params[1], params[2]
    has_static = len(params) > 3
    # hoisted input projection: one big MXU matmul over all timesteps
    xproj = jnp.einsum("tbd,gd->tbg", xf, w_xc) + b_c
    if has_static:
        xproj = xproj + (bottoms[2].reshape(batch, -1) @ params[3].T)

    cont_f = cont.reshape(t_steps, batch, 1).astype(xf.dtype)

    def step(carry, inp):
        h_prev, c_prev = carry
        xp_t, cont_t = inp
        h_g = h_prev * cont_t
        c_g = c_prev * cont_t
        gates = xp_t + h_g @ w_hc.T
        i, f, o, g = jnp.split(gates, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        o = jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * c_g + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    if expose:
        si = 2 + (1 if has_static else 0)
        h0 = bottoms[si].reshape(batch, n).astype(xf.dtype)
        c0 = bottoms[si + 1].reshape(batch, n).astype(xf.dtype)
    else:
        h0 = jnp.zeros((batch, n), xf.dtype)
        c0 = jnp.zeros((batch, n), xf.dtype)
    (h_t, c_t), hs = lax.scan(step, (h0, c0), (xproj, cont_f))
    if expose:
        return [hs, h_t.reshape(1, batch, n), c_t.reshape(1, batch, n)]
    return [hs]


def _rnn_params(lp, shapes):
    rp = lp.recurrent_param
    n = int(rp.num_output)
    d = math.prod(shapes[0][2:]) if len(shapes[0]) > 2 else 1
    wf = _filler(rp.weight_filler if rp.has("weight_filler") else None)
    bf = _filler(rp.bias_filler if rp.has("bias_filler") else None)
    return [("W_xh", (n, d), wf), ("b_h", (n,), bf), ("W_hh", (n, n), wf),
            ("W_ho", (n, n), wf), ("b_o", (n,), bf)]


@register("RNN", params=_rnn_params)
def _rnn(ctx, lp, params, bottoms):
    """Caffe RNNLayer: h_t = tanh(W_hh h'_{t-1} + W_xh x_t + b_h);
    o_t = tanh(W_ho h_t + b_o)."""
    rp = lp.recurrent_param
    n = int(rp.num_output)
    x, cont = bottoms[0], bottoms[1]
    t_steps, batch = x.shape[0], x.shape[1]
    xf = x.reshape(t_steps, batch, -1)
    w_xh, b_h, w_hh, w_ho, b_o = params
    xproj = jnp.einsum("tbd,nd->tbn", xf, w_xh) + b_h
    cont_f = cont.reshape(t_steps, batch, 1).astype(xf.dtype)

    def step(h_prev, inp):
        xp_t, cont_t = inp
        h = jnp.tanh(xp_t + (h_prev * cont_t) @ w_hh.T)
        o = jnp.tanh(h @ w_ho.T + b_o)
        return h, o

    h0 = jnp.zeros((batch, n), xf.dtype)
    _, os = lax.scan(step, h0, (xproj, cont_f))
    return [os]
