"""Analytic FLOP estimates for a constructed Net.

Counts the multiply-accumulate work of the parametrised layers from the
weight blob shapes and inferred top shapes — the >99% of CaffeNet's
arithmetic that lands on the MXU.  Elementwise layers (ReLU, LRN,
Pooling, Softmax) are ignored; they are HBM-bound, not FLOP-bound.  A
layer type whose work is not weight x positions says what it is where
it is declared (`ops.layers.register(flops=...)`).

Used by bench.py for MFU: images/sec alone can't be sanity-checked
against chip peak without a FLOP count (reference analog: the
throughput harnesses in `caffe-distri/.../PerfTest.java:69-118` report
rates only — no roofline; this is the TPU-native upgrade).
"""

from __future__ import annotations

from math import prod


def visible_scores(t: int, causal: bool, window: int = 0) -> int:
    """Scores a head computes over t rows: all t x t, the causal half
    (counted as t x t / 2), or under a window of `window` keys the
    pairs a row can see and no others: row r sees min(r + 1, window)
    columns, window (window + 1) / 2 + (t - window) window in all."""
    if not causal:
        return t * t
    if 0 < window < t:
        return window * (window + 1) // 2 + (t - window) * window
    return t * t // 2


def forward_flops(net) -> int:
    """Estimated forward-pass FLOPs for one batch through `net`.

    2 * (output elements) * (MACs per output element), where MACs per
    output element = prod(weight.shape[1:]) for every weighted layer:
      Convolution  weight (K, C/g, kh, kw), top (N, K, Ho, Wo)
      InnerProduct weight (K, I),           top (N, K)
      LSTM/RNN     weight (4H, I) etc.      top (T, N, H)
    Deconvolution scatters from the bottom instead: weight
    (C, K/g, kh, kw) applied per bottom element.
    """
    return sum(layer_forward_flops(net).values())


def layer_forward_flops(net) -> dict:
    """{layer name: forward FLOPs} — the one copy of the per-layer
    accounting (scripts/roofline.py consumes this too): the type's own
    count where it declares one, else every weight of two or more axes
    once a position of the first top."""
    from ..ops.layers import get_op    # lazy: layers imports this module
    out: dict = {}
    for lp in net.compute_layers:
        # every blob the layer computes with, one it reads under a
        # shared name (a tied head) included
        specs = [(n, ps, None) for n, ps in net.layer_param_specs(lp.name)]
        if not specs:
            continue
        tops = net._top_shapes[lp.name]
        if not tops:
            continue
        op = get_op(lp.type)
        if op.flops is not None:
            out[lp.name] = op.flops(lp, specs, tops)
            continue
        first_top = next(iter(tops.values()))
        total = 0
        for (pname, pshape, _) in specs:
            if len(pshape) < 2 or "bias" in pname:
                continue
            if lp.type == "Deconvolution":
                # one MAC per bottom element per kernel tap
                n, c = first_top[0], pshape[0]
                # bottom spatial size = prod(top)/N/K * ... — recover
                # from blob_shapes via the bottom name when available
                bshape = net.blob_shapes.get(lp.bottom[0])
                ref = prod(bshape) if bshape else prod(first_top)
                total += 2 * ref * prod(pshape[1:])
            elif lp.type in ("LSTM", "RNN"):
                # gate weights (4H, I)/(4H, H) apply FULLY per
                # (t, b) step — the top (T, B, H) only exposes H, so
                # the generic rule would undercount 4x
                total += 2 * prod(first_top[:2]) * prod(pshape)
            else:
                total += 2 * prod(first_top) * prod(pshape[1:])
        out[lp.name] = total
    return out


def train_step_flops(net) -> int:
    """Forward + backward + update ≈ 3x forward (dL/dW and dL/dx are
    each another pass of the same matmuls; the elementwise optimizer
    update is negligible)."""
    return 3 * forward_flops(net)
