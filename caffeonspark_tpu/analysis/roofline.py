"""Importable per-layer roofline model (lifted out of scripts/roofline.py).

For every compute layer of a constructed Net, bounds one train step's
time by max(FLOPs / MXU peak, HBM bytes / bandwidth) and classifies the
layer as MXU-bound or HBM-bound — the ranking the per-layer autotuner
(`ops/autotune.py`) prunes its variant search with, and the model the
CLI report (`scripts/roofline.py`, now a shim over this module) prints.

Model (estimate-grade, stated so the numbers are auditable):
  * forward bytes/layer = in + out activations + params read;
  * backward ≈ 2x forward traffic (dL/dx needs weights + stashed
    activations; dL/dW needs activations + writes grads) and 2x
    forward FLOPs for weighted layers;
  * optimizer: read param+momentum, write param+momentum in f32
    (16 bytes/param) regardless of compute dtype;
  * fused=True drops elementwise layers' activation traffic (XLA fuses
    ReLU/Dropout/eltwise into the producing matmul/conv) — the fused
    and unfused totals bracket reality;
  * a per-layer `variants` map (the autotuner's plan shape) adjusts the
    accounting: a bf16 dtype variant halves that layer's activation and
    param-read bytes, an int8 variant quarters the param read, and an
    LRN fusion variant drops the fused ReLU's (and deferred bias-add's)
    separate round trip — so a candidate plan can be costed without
    building it.

MODEL_VERSION bumps whenever the accounting above changes; JSON
emitters carry it (plus SCHEMA) so downstream consumers can detect
model changes instead of silently comparing incompatible estimates.
"""

from __future__ import annotations

from math import prod
from typing import Dict, List, Optional

SCHEMA = "cos-roofline"
MODEL_VERSION = 2          # v1: scripts/roofline.py inline model;
#                            v2: importable + per-layer variant costing

ELEMENTWISE = {"ReLU", "Dropout", "Eltwise", "Scale", "Bias", "PReLU",
               "Sigmoid", "TanH", "ELU", "AbsVal", "Power", "Exp",
               "Log", "BNLL", "SiLU"}
MEMBOUND = {"Pooling", "LRN", "Softmax", "SoftmaxWithLoss", "Concat",
            "Slice", "Flatten", "Reshape", "BatchNorm", "Accuracy",
            "RMSNorm"}

# bf16 peak TFLOP/s per chip by device_kind substring (public spec
# sheets); MFU is reported against the RUNNING chip's peak.  One copy:
# bench.py and scripts/bench_attention.py both resolve through here.
# A device_kind that matches no row is an error, not a default.
PEAK_BF16_TFLOPS = (
    ("v6e", 918.0), ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_tflops_for_kind(device_kind: str) -> tuple:
    """(peak_bf16_tflops, source) for a device_kind string; raises
    ValueError when it matches no known chip."""
    kind = str(device_kind or "").lower()
    for sub, peak in PEAK_BF16_TFLOPS:
        if sub in kind:
            return peak, f"device_kind:{kind}"
    raise ValueError(
        f"no bf16 peak known for device_kind {device_kind!r}: add it to "
        "analysis/roofline.PEAK_BF16_TFLOPS with its source")


def peak_tflops(device) -> tuple:
    """(peak_bf16_tflops, source) for a jax device object (reads its
    device_kind attribute)."""
    return peak_tflops_for_kind(getattr(device, "device_kind", ""))


def _variant_bytes(variant: Optional[dict], act_bytes: int,
                   param_bytes: int) -> tuple:
    """(act_bytes, param_bytes) under a layer's autotune variant."""
    if not variant:
        return act_bytes, param_bytes
    dt = variant.get("dtype")
    if dt == "bfloat16":
        act_bytes, param_bytes = 2, 2
    elif dt == "float32":
        act_bytes, param_bytes = 4, 4
    if variant.get("int8"):
        param_bytes = 1
    return act_bytes, param_bytes


def analyze_net(net, *, act_bytes: int, param_bytes: int,
                fused: bool = False,
                variants: Optional[Dict[str, dict]] = None
                ) -> List[dict]:
    """Per-layer {layer, type, flops, bytes, params} rows for one TRAIN
    step of a constructed Net (see module docstring for the model).
    `variants` is an autotune-plan-shaped {layer: variant} map used to
    cost a candidate plan without building it."""
    from ..utils.flops import layer_forward_flops
    variants = variants or {}
    per_layer = layer_forward_flops(net)
    # an LRN fuse variant on an UNFUSED net absorbs the feeding ReLU
    # into the LRN's epilogue: that relu row's traffic disappears.  (On
    # a net already built with the fusion the relu layer is gone from
    # compute_layers, so the saving shows up with no variant at all —
    # both costings agree.)  Eligibility is net.py's OWN peephole
    # predicate — crediting a fusion the build would refuse would let
    # an inert variant fake an uplift under the injected-floor regime.
    from ..net import fusable_relu_for_lrn
    fused_relus = set()
    layers = list(net.compute_layers)
    for lp in layers:
        if lp.type != "LRN":
            continue
        if (variants.get(lp.name) or {}).get("fuse") not in (
                "relu", "bias_relu"):
            continue
        relu = fusable_relu_for_lrn(layers, lp)
        if relu is not None:
            fused_relus.add(relu.name)
    rows = []
    for lp in layers:
        tops = net._top_shapes.get(lp.name, {})
        out_elems = sum(prod(s) for s in tops.values())
        in_elems = sum(prod(net.blob_shapes[b]) for b in lp.bottom
                       if b in net.blob_shapes)
        p_elems = sum(prod(s) for _, s, _ in
                      net.param_layout.get(lp.name, []))
        flops = per_layer.get(lp.name, 0)
        ab, pb = _variant_bytes(variants.get(lp.name), act_bytes,
                                param_bytes)
        fwd_bytes = (in_elems + out_elems) * ab + p_elems * pb
        if lp.type in ELEMENTWISE and (fused
                                       or lp.name in fused_relus):
            fwd_bytes = 0          # fused into the producer's epilogue
        step_bytes = 3 * fwd_bytes + 16 * p_elems
        step_flops = 3 * flops
        rows.append({"layer": lp.name, "type": lp.type,
                     "flops": step_flops, "bytes": step_bytes,
                     "params": p_elems})
    return rows


def classify(rows: List[dict], *, peak_tflops: float = 197.0,
             hbm_gbs: float = 819.0) -> List[dict]:
    """Adds t_flop_us / t_mem_us / bound / t_us to each row (in place)
    and returns the rows sorted DESCENDING by roofline time — the
    autotuner's pruning order.  The defaults are the model's chip, a
    TPU v5e (197 bf16 TFLOP/s, 819 GB/s), not a guess about the
    running device."""
    peak = peak_tflops * 1e12
    bw = hbm_gbs * 1e9
    for r in rows:
        r["t_flop_us"] = r["flops"] / peak * 1e6
        r["t_mem_us"] = r["bytes"] / bw * 1e6
        r["bound"] = ("mxu" if r["t_flop_us"] >= r["t_mem_us"]
                      else "hbm")
        r["t_us"] = max(r["t_flop_us"], r["t_mem_us"])
    return sorted(rows, key=lambda r: r["t_us"], reverse=True)


def step_bytes_total(net, *, act_bytes: int = 2, param_bytes: int = 2,
                     variants: Optional[Dict[str, dict]] = None) -> int:
    """Total modeled HBM bytes of one train step under a (possibly
    empty) variant plan — the quantity the autotune bench's injected
    HBM-floor regime sleeps proportionally to."""
    rows = analyze_net(net, act_bytes=act_bytes, param_bytes=param_bytes,
                       fused=False, variants=variants)
    return sum(r["bytes"] for r in rows)
