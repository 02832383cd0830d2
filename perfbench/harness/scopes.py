"""Device time of one named scope of the program, for the readers of the
per-layer metrics that PR 33 adds.

`opmeta.device_ops` gives every "XLA Ops" event of a trace with its name
stack (`jit(step)/transpose(jvp(L3.conv))/sconv/sconv.mix/mul`); its
table of parts is closed, so a reader of another scope comes here with
the scope's pattern.  The parsed events are kept on the run: three
readers, one walk over the file.

A program without the scope (a parent from before it), a run without a
trace, or a trace without a device plane gives None: the metric's line
is left out.
"""

from __future__ import annotations

import re

from . import opmeta
from . import trace as tr


def ops_of_run(run: dict):
    """(ops of the first device plane that has events, the traced
    window) kept on the run; ([], None) where there is nothing to read."""
    if "device_ops" not in run:
        devs = (run.get("trace") or {}).get("devices") or {}
        ops, window = [], None
        if run.get("trace_dir") and devs:
            window = tuple(devs[sorted(devs)[0]]["window"])
            planes = opmeta.device_ops(tr.find_xplane(run["trace_dir"]))
            ops = next((o for _, o in sorted(planes.items()) if o), [])
        run["device_ops"] = (ops, window)
    return run["device_ops"]


def seconds(run: dict, pattern: str):
    """Device seconds inside the traced window of the ops whose name
    stack holds a token that matches `pattern` whole; None where no op
    does."""
    ops, window = ops_of_run(run)
    if not ops:
        return None
    pat = re.compile(pattern)
    lo, hi = window
    total, found = 0.0, False
    for tf_op, s, e in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0 and any(pat.fullmatch(t) for t in
                         opmeta._SPLIT.split(tf_op.split(":", 1)[0]) if t):
            total += d
            found = True
    return total if found else None


def ms_per_step(run: dict, pattern: str):
    """`seconds` as milliseconds a traced step, or None."""
    if not run.get("trace") or not run.get("steps"):
        return None
    s = seconds(run, pattern)
    return None if s is None else 1e3 * s / run["steps"]
