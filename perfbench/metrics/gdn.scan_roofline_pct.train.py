"""The share of its roofline at which the gated delta rule runs,
whatever implements it: the time the chip's peaks allow for the work the
recurrence AS WRITTEN needs for one step, over the device time of the
program's scope `gdn.scan`.

The work: one forward pass of the recurrence counted four times (the
forward pass, the recomputed forward pass of the block, and a backward
pass of twice a forward's operations and bytes).  Operations and bytes of
a forward pass come from the shapes (the reference's `scan_flops` and
`scan_bytes`: per token and value head three products with the 128 x 128
state; q, k, v, g, beta read and o written once, float32 as stored); the
time allowed is the larger of operations / bf16 peak and bytes / HBM
bandwidth (peaks.json).  What the program adds to that work (chunked
products, triangular systems, further recomputation) is time, not work:
it lowers the share."""

import importlib

from perfbench.harness import scopes
from perfbench.harness.devices import peaks

PASSES = 4      # forward + recomputed forward + backward (= 2 forwards)


def allowed_ms(run):
    """(ms a step the peaks allow, which bound set it)."""
    cfg = run["ctx"]["config"]
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    seq, batch = int(cfg["sequence_length"]), run["batch"]
    peak = peaks(run["device"]["kind"])
    by_ops = PASSES * model.scan_flops(cfg, seq, batch) / (
        peak["bf16_tflops"] * 1e12)
    by_bytes = PASSES * model.scan_bytes(cfg, seq, batch) / (
        peak["hbm_gbytes_per_s"] * 1e9)
    per_chip = 1e3 * max(by_ops, by_bytes) / run["ctx"]["chips"]
    return per_chip, "operations" if by_ops >= by_bytes else "bytes"


def read(run):
    ms = scopes.ms_per_step(run, r"gdn\.scan")
    if ms is None or not run.get("device"):
        return None
    return 100.0 * allowed_ms(run)[0] / ms
