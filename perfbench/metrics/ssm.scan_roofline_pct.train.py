"""The share of its roofline at which the selective scan runs, whatever
implements it: the time the chip's HBM bandwidth allows for the bytes
the recurrence AS WRITTEN moves in one step, over the device time of the
program's scope `ssm.scan`.

The recurrence s_t = exp(dt_t A) s_(t-1) + dt_t u_t B_t, y_t = s_t . C_t
is elementwise: nothing for the MXU, and peaks.json holds no vector-unit
peak, so the bound is the bytes alone (`scan_operations` says what the
vector unit does beside them, for a reader; it sets no bound here).
Bytes, float32 as stored, per Mamba layer and sequence: the forward pass
reads u and dt (T x C each), B and C (T x N each) and writes y (T x C);
the backward pass reads those and dy and writes the five gradients (du,
ddt: T x C; dB, dC: T x N; dA: C x N).  ONLY THE PASSES THAT RUN are
counted: a recompute_block keeps the forward kernel's outputs (`ssm.y`,
`ssm.edges`), so a step is one forward pass and one backward pass, not
a forward pass twice.  What the program adds (B and C laid over 128
lanes, the states at the chunks' edges, a chunk's states computed again
in the backward pass) is time, not work: it lowers the share."""

import importlib

from perfbench.harness import scopes
from perfbench.harness.devices import peaks


def _sizes(cfg):
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    m = model.dims(cfg)
    layers = sum(1 for k in m["kinds"] if k.startswith("mamba"))
    return layers, m["di"], m["n"]


def scan_bytes(cfg, seq: int, seqs: int) -> int:
    """Bytes one step's scans move as written: a forward and a backward
    pass of every Mamba layer."""
    layers, c, n = _sizes(cfg)
    forward = 4 * (3 * seq * c + 2 * seq * n)
    backward = 4 * (3 * seq * c + 2 * seq * n          # u, dt, dy, B, C
                    + 2 * seq * c + 2 * seq * n + c * n)    # 5 gradients
    return seqs * layers * (forward + backward)


def scan_operations(cfg, seq: int, seqs: int) -> int:
    """Elementwise operations of the same two passes: 9 a token, channel
    and state forward (the reference's `SCAN_OPS`), twice that backward."""
    layers, c, n = _sizes(cfg)
    return seqs * layers * 3 * 9 * seq * c * n


def allowed_ms(run):
    cfg = run["ctx"]["config"]
    seq, batch = int(cfg["sequence_length"]), run["batch"]
    peak = peaks(run["device"]["kind"])
    return 1e3 * scan_bytes(cfg, seq, batch) / (
        peak["hbm_gbytes_per_s"] * 1e9) / run["ctx"]["chips"]


def read(run):
    ms = scopes.ms_per_step(run, r"ssm\.scan")
    if ms is None or not run.get("device"):
        return None
    return 100.0 * allowed_ms(run) / ms
