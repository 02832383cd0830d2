"""The share of their roofline at which the windowed attention layers
run, whatever implements them: the time the chip's peaks allow for the
work the window AS WRITTEN needs for one step, over the device time of
the program's scope `attn.window`.

The work: per windowed layer, head and visible (row, column) pair
(row t sees t - W < s <= t: W (W + 1) / 2 + (T - W) W pairs) the score
and the weighted value, 2 x 2 x head width operations a forward pass; a
step is the forward pass, the block's recomputed forward pass and a
backward pass of five such products to the forward's two: 4.5 forward
passes.  Bytes: q read and o written once a query head, k and v once a
key/value head, at the operands' width, counted 4.5 times as well (the
operations set the bound by two orders of magnitude).  Both counts are
the reference's (`window_attn_flops`, `window_attn_bytes`), from shapes;
the time allowed is the larger of operations / bf16 peak and bytes / HBM
bandwidth (peaks.json).  A kernel that masks the window without skipping
it, tiles that reach over the window's edges, the softmax's own passes:
all of that is time, not work, and lowers the share."""

import importlib

from perfbench.harness import scopes
from perfbench.harness.devices import peaks

PASSES = 4.5    # forward + recomputed forward + backward (= 2.5 forwards)


def allowed_ms(run):
    """(ms a step the peaks allow, which bound set it)."""
    cfg = run["ctx"]["config"]
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    seq, batch = int(cfg["sequence_length"]), run["batch"]
    peak = peaks(run["device"]["kind"])
    by_ops = PASSES * model.window_attn_flops(cfg, seq, batch) / (
        peak["bf16_tflops"] * 1e12)
    by_bytes = PASSES * model.window_attn_bytes(cfg, seq, batch) / (
        peak["hbm_gbytes_per_s"] * 1e9)
    per_chip = 1e3 * max(by_ops, by_bytes) / run["ctx"]["chips"]
    return per_chip, "operations" if by_ops >= by_bytes else "bytes"


def read(run):
    ms = scopes.ms_per_step(run, r"attn\.window")
    if ms is None or not run.get("device"):
        return None
    return 100.0 * allowed_ms(run)[0] / ms
