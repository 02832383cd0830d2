"""Analytic FLOP estimates for a constructed Net.

Counts the multiply-accumulate work of the parametrised layers
(Convolution / Deconvolution / InnerProduct / LSTM-style weights,
the three attention types, ShortConv, GatedDeltaNet, MixtureOfExperts)
from the weight blob shapes and inferred top shapes — the >99% of
CaffeNet's arithmetic that lands on the MXU.  Elementwise layers (ReLU, LRN,
Pooling, Softmax) are ignored; they are HBM-bound, not FLOP-bound.

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
    accounting (scripts/roofline.py consumes this too)."""
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
        first_top = next(iter(tops.values()))
        total = 0
        if lp.type == "Embed":
            out[lp.name] = 0     # gather, not a matmul: ~0 FLOPs
            continue
        if lp.type == "MultiHeadAttention":
            # projections apply the FULL weight per (t, b) position
            # (top is (T, B, D), not (T, B, 3D)), plus the two
            # attention einsums (QK^T and PV: 2 * 2*B*H*T^2*hd)
            t_s, b_s = first_top[0], first_top[1]
            for (pname, pshape, _) in specs:
                total += 2 * t_s * b_s * prod(pshape)
            ap = lp.attention_param
            # every score, as this type always counted them, unless a
            # window hides some
            scores = (visible_scores(t_s, True, int(ap.window))
                      if ap.causal and ap.window else t_s * t_s)
            total += 4 * b_s * int(ap.num_heads) * scores \
                * int(ap.head_dim)
            out[lp.name] = total
            continue
        if lp.type == "LatentAttention":
            # the five projections per (t, b) position, plus causal
            # attention with nope + rope wide q/k and v_head_dim wide
            # v: the masked half of QK^T and PV is not work
            t_s, b_s = first_top[0], first_top[1]
            ap = lp.attention_param
            total = 2 * t_s * b_s * sum(
                prod(ps) for (_, ps, _) in specs if len(ps) == 2)
            total += (2 * b_s * int(ap.num_heads)
                      * visible_scores(t_s, True, int(ap.window))
                      * (int(ap.qk_nope_head_dim)
                         + int(ap.qk_rope_head_dim)
                         + int(ap.v_head_dim)))
            out[lp.name] = total
            continue
        if lp.type == "GroupedQueryAttention":
            # the four projections per (t, b) position (W_k and W_v at
            # their own fewer heads), plus causal attention over
            # head_dim wide q/k and v for every QUERY head: under a
            # window the scores a row can see and no others
            t_s, b_s = first_top[0], first_top[1]
            ap = lp.attention_param
            total = 2 * t_s * b_s * sum(
                prod(ps) for (_, ps, _) in specs if len(ps) == 2)
            # a differential layer's values are two heads wide: the
            # score head_dim, the weighted value 2 x head_dim a pair
            wide = 3 if ap.differential else 2
            total += (2 * b_s * int(ap.num_heads)
                      * visible_scores(t_s, True, int(ap.window))
                      * wide * int(ap.head_dim))
            out[lp.name] = total
            continue
        if lp.type in ("Mamba", "GatedMemoryUnit"):
            # every product per position; Mamba's recurrence as written
            # besides: per token, channel and state the decay's product
            # and its exponential, the write, the update and the read,
            # 9 elementwise operations (vector-unit work, counted as
            # operations, not as MXU work); taps, gates and softplus are
            # not counted
            n = prod(first_top[:-1])
            out[lp.name] = 2 * n * sum(
                prod(ps) for (nm, ps, _) in specs if nm.startswith("W_"))
            if lp.type == "Mamba":
                out[lp.name] += 9 * n * prod(dict(
                    (nm, ps) for nm, ps, _ in specs)["A_log"])
            continue
        if lp.type == "ShortConv":
            # W_in and W_out per position; the taps and the two gates
            # are elementwise passes (HBM-bound), not counted
            out[lp.name] = 2 * prod(first_top[:-1]) * sum(
                prod(ps) for (n, ps, _) in specs if n in ("W_in", "W_out"))
            continue
        if lp.type == "GatedDeltaNet":
            # the three products per position, plus the recurrence as
            # written: per token and value head the read S^T k, the
            # rank-one write and the read S^T q, 2 x dk x dv each (the
            # decay of the state is an elementwise pass; taps, gates
            # and norms are not counted)
            gp = lp.gated_delta_net_param
            n = prod(first_top[:-1])
            out[lp.name] = 2 * n * sum(
                prod(ps) for (nm, ps, _) in specs if nm.startswith("W_"))
            out[lp.name] += (n * int(gp.num_v_heads) * 3 * 2
                             * int(gp.head_k_dim) * int(gp.head_v_dim))
            continue
        if lp.type == "MixtureOfExperts":
            out[lp.name] = _moe_forward_flops(lp, dict(
                (n, ps) for (n, ps, _) in specs), prod(first_top[:-1]))
            continue
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


def _moe_forward_flops(lp, shapes: dict, n: int) -> int:
    """Router over all experts for every token, plus the expert
    products a token's k assignments touch.  `capacity` dispatch runs
    every expert on its full (C, D) buffer; `dropless` runs, for an
    even router, the k x held / experts of the assignments that fall on
    the experts this layer holds, plus the shared experts on every
    token."""
    from math import ceil
    mp = lp.moe_param
    e, k = int(mp.num_experts), max(1, int(mp.top_k))
    total = 2 * n * prod(shapes["router"])
    if mp.dispatch == "dropless":
        held = int(mp.experts_held) or e
        per_expert = sum(prod(ps[1:]) for nm, ps in shapes.items()
                         if nm.startswith("W"))
        total += int(2 * n * k * held / e * per_expert)
        total += 2 * n * sum(prod(ps) for nm, ps in shapes.items()
                             if nm.startswith("S_"))
        return total
    cap = max(1, int(ceil(k * n / e * float(mp.capacity_factor))))
    return total + 2 * cap * (prod(shapes["W1"]) + prod(shapes["W2"]))


def train_step_flops(net) -> int:
    """Forward + backward + update ≈ 3x forward (dL/dW and dL/dx are
    each another pass of the same matmuls; the elementwise optimizer
    update is negligible)."""
    return 3 * forward_flops(net)
