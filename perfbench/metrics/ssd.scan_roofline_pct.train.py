"""The share of its roofline at which the Mamba-2 scan runs, whatever
implements it: the time the chip's HBM bandwidth allows for the bytes
the recurrence AS WRITTEN moves in one step, over the device time of the
program's scope `ssd.scan`.

The recurrence S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T, y_t = S_t C_t
+ D u_t keeps its (P, N) state a head on the chip; what it has to move
is its operands and results, so the bound is the bytes (`scan_operations`
says what it computes beside them, as written: per token and head the
decay of the state, the rank-one write and the read, P N multiply-adds
each, a forward pass; twice that backward.  At the v5e's peaks the
bytes take longer than the operations would at bf16; the share is taken
against the longer time of the two).  Bytes, float32 as stored,
per Mamba-2 layer and sequence: the forward pass reads u (T x H P), dt
(T x H), B and C (T x G N each) and writes y (T x H P); the backward pass
reads those and dy and writes du, ddt, dB, dC, dA and dD (H each).  ONLY
THE PASSES THAT RUN are counted: a recompute_block keeps the scan's
outputs (`ssd.y`, `ssd.edges`), so a step is one forward pass and one
backward pass, not a forward pass twice.  What the program adds (the
chunked form's (chunk, chunk) decay matrices and products, the states at
the groups' edges, a group computed again in the backward pass) is time,
not work: it lowers the share.  The same count holds a later kernel."""

import importlib

from perfbench.harness import scopes
from perfbench.harness.devices import peaks


def _sizes(cfg):
    """(Mamba-2 layers, channels H P, heads H, group channels G N,
    a head's state P N)."""
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    m = model.dims(cfg)
    return (sum(1 for k in m["kinds"] if k == "M"), m["mh"] * m["mp"],
            m["mh"], m["g"] * m["n"], m["mp"] * m["n"])


def scan_bytes(cfg, seq: int, seqs: int) -> int:
    """Bytes one step's scans move as written: a forward and a backward
    pass of every Mamba-2 layer."""
    layers, c, h, gn, _ = _sizes(cfg)
    operands = seq * (c + h + 2 * gn)               # u, dt, B, C
    forward = 4 * (operands + seq * c)              # ... and y
    backward = 4 * (operands + seq * c              # ... and dy
                    + operands + 2 * h)             # du, ddt, dB, dC; dA, dD
    return seqs * layers * (forward + backward)


def scan_operations(cfg, seq: int, seqs: int) -> int:
    """Operations of the same two passes as written: 3 x 2 P N a token
    and head forward, twice that backward."""
    layers, _, h, _, pn = _sizes(cfg)
    return seqs * layers * 3 * seq * h * 3 * 2 * pn


def allowed_ms(run):
    cfg = run["ctx"]["config"]
    seq, batch = int(cfg["sequence_length"]), run["batch"]
    peak = peaks(run["device"]["kind"])
    by_bytes = scan_bytes(cfg, seq, batch) / (peak["hbm_gbytes_per_s"] * 1e9)
    by_ops = scan_operations(cfg, seq, batch) / (peak["bf16_tflops"] * 1e12)
    return 1e3 * max(by_bytes, by_ops) / run["ctx"]["chips"]


def read(run):
    ms = scopes.ms_per_step(run, r"ssd\.scan")
    if ms is None or not run.get("device"):
        return None
    return 100.0 * allowed_ms(run) / ms
