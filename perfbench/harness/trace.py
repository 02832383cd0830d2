"""Reduction of a profiler trace (.xplane.pb) to the numbers the
per-layer metrics read.  Needs nothing but JAX's own reader.

A device plane is named "/device:TPU:<n>"; its "XLA Ops" line holds one
event per executed HLO op (start and duration in ns), its "XLA Modules"
line one per executed program.  Host threads are lines of "/host:CPU";
the harness's own `jax.profiler.TraceAnnotation` spans are events there,
on the same clock.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute|all-to-all)")
SPAN_PREFIX = "perfbench."


def op_name(text: str) -> str:
    """The trace names an op by its whole HLO line; keep the op's name."""
    return text.split(" = ", 1)[0].lstrip("%")[:96]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """-> {"devices": {plane name: {line name: [(name, start, end)]}},
           "spans": [(name, start, end)]} in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (op_name(ev.name), ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def union(intervals):
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of merged intervals `a` not covered by merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def op_events(lines: dict):
    """The per-op events of one device plane."""
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    skip = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
            "Framework Name Scope", "Source code")
    return [ev for name, evs in lines.items() if name not in skip
            for ev in evs]


def reduce(trace: dict, window=None) -> dict:
    """Numbers per device plane and over all of them.

    window: (start, end) on the trace's clock, default the span named
    `window` the harness recorded, else first to last device event."""
    spans = trace["spans"]
    if window is None:
        win = [s for s in spans if s[0] == "window"]
        if win:
            window = (win[0][1], win[-1][2])
    per_dev = {}
    for plane, lines in sorted(trace["devices"].items()):
        evs = op_events(lines)
        if not evs:
            continue
        lo, hi = window or (min(e[1] for e in evs), max(e[2] for e in evs))
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
               if min(e, hi) > max(s, lo)]
        coll = union([(s, e) for n, s, e in evs if COLLECTIVE.match(n)])
        # an async collective is a -start and a -done op: the exchange is
        # under way between them
        starts = [(n, s, e) for n, s, e in evs
                  if COLLECTIVE.match(n) and "-start" in n]
        dones = [(n, s, e) for n, s, e in evs
                 if COLLECTIVE.match(n) and "-done" in n]
        if starts and len(starts) == len(dones):
            coll = union(coll + [(a[1], b[2]) for a, b in zip(starts, dones)])
        other = union([(s, e) for n, s, e in evs if not COLLECTIVE.match(n)])
        busy = union([(s, e) for _, s, e in evs])
        ops = {}
        for n, s, e in evs:
            ops[n] = ops.get(n, 0.0) + (e - s)
        per_dev[plane] = {
            "window": (lo, hi), "busy_s": total(busy),
            "busy": busy, "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, other)),
            "ops": ops,
            "modules": len(clip([(s, e) for _, s, e in
                                 lines.get("XLA Modules", [])], lo, hi))}
    if not per_dev:
        return {"devices": {}, "busy_s": 0.0, "window_s": 0.0}
    n = len(per_dev)
    first = per_dev[sorted(per_dev)[0]]
    lo, hi = first["window"]
    return {
        "devices": per_dev, "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "collective_s": sum(d["collective_s"] for d in per_dev.values()) / n,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_dev.values()) / n,
        "device_ops": sorted(first["ops"].items(),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": idle_gaps(first["busy"], lo, hi, spans)}


def idle_gaps(busy, lo, hi, spans, top=10, small=5e-6):
    """Idle time of one device, attributed to what the host was doing:
    each gap goes to the harness span that covers most of it; gaps under
    `small` seconds are the device's own turn-round between ops."""
    gaps = subtract([(lo, hi)], busy)
    spans = [s for s in spans if s[0] != "window"]
    by_name, j = {}, 0
    for s, e in gaps:
        if e - s < small:
            by_name["between_ops"] = by_name.get("between_ops", 0.0) + e - s
            continue
        while j < len(spans) and spans[j][2] <= s:
            j += 1
        best, cover, k = "unattributed", 0.0, j
        while k < len(spans) and spans[k][1] < e:
            c = min(e, spans[k][2]) - max(s, spans[k][1])
            if c > cover:
                best, cover = spans[k][0], c
            k += 1
        by_name[best] = by_name.get(best, 0.0) + (e - s)
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
