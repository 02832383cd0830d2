"""Device time by the program's named scopes, from the .xplane.pb itself.

JAX's `ProfileData` gives an "XLA Ops" event its HLO text, start and
duration, but not the op's metadata.  The file holds more: every event
points at an XEventMetadata entry whose stats carry `tf_op`, the op's
name stack as the program built it (`jit(step)/jvp(L1.attn)/attn/
dot_general`), so a `jax.named_scope` of the program is readable there
(checked on the traces recorded on the chip under tests/data).  This
module reads just that: a minimal protobuf wire-format walk over the
fields of tsl's xplane.proto that it needs, nothing but the standard
library.

    XSpace.planes=1;  XPlane: name=2 lines=3 event_metadata=4 (map)
    stat_metadata=5 (map);  XLine: name=2 timestamp_ns=3 events=4;
    XEvent: metadata_id=1 offset_ps=2 duration_ps=3;
    XEventMetadata: id=1 name=2 stats=5;  XStat: metadata_id=1 str_value=5
    bytes_value=6 ref_value=7;  XStatMetadata: id=1 name=2

A program without such scopes (a parent from before them) yields no
part: `scope_seconds` returns {} and the metrics that read it leave
their line out.
"""

from __future__ import annotations

import re

# block part -> the name-stack tokens that mean it: the scopes inside the
# new ops, and the prototxt layer names Net.apply puts on every op
PARTS = (
    ("update", re.compile(r"^update$")),
    ("moe", re.compile(r"^(moe\.(route|experts|shared)|.*\.moe)$")),
    ("attn", re.compile(r"^(attn|.*\.attn)$")),
    ("head", re.compile(r"^(head\..*|logits|loss)$")),
)
_SPLIT = re.compile(r"[/()]+")


def _varint(b, i):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one message; length-delimited values as
    memoryview slices, varints as ints, fixed-width fields skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif w == 1:
            v, i = None, i + 8
        elif w == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {w}")
        yield f, v


def _map_entry(b):
    key = val = None
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def part_of(tf_op: str):
    """The block part an op's name stack puts it in, or None."""
    tokens = [t for t in _SPLIT.split(tf_op.split(":", 1)[0]) if t]
    for part, pat in PARTS:
        if any(pat.match(t) for t in tokens):
            return part
    return None


def device_ops(path: str):
    """{plane name: [(tf_op, start_s, end_s)]} of every device plane's
    "XLA Ops" line, on the clock `ProfileData` reports (line timestamp +
    event offset)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                k, val = _map_entry(v)
                emeta[k] = val
            elif f2 == 5:
                k, val = _map_entry(v)
                smeta[k] = next((bytes(x).decode() for g, x in _fields(val)
                                 if g == 2), "")
        if not name.startswith("/device:"):
            continue
        tf_id = next((k for k, n in smeta.items() if n == "tf_op"), None)
        if tf_id is None:
            continue
        # a stat's string is stored inline or as a reference to a stat
        # metadata's name
        tf_op = {}
        for mid, msg in emeta.items():
            for f3, stat in _fields(msg):
                if f3 != 5:
                    continue
                sid = text = None
                for f4, x in _fields(stat):
                    if f4 == 1:
                        sid = x
                    elif f4 in (5, 6):
                        text = bytes(x).decode("utf-8", "replace")
                    elif f4 == 7:
                        text = smeta.get(x, "")
                if sid == tf_id and text:
                    tf_op[mid] = text
        for line in lines:
            lname, t0, events = "", 0, []
            for f3, v in _fields(line):
                if f3 == 2:
                    lname = bytes(v).decode()
                elif f3 == 3:
                    t0 = v
                elif f3 == 4:
                    events.append(v)
            if lname != "XLA Ops":
                continue
            ops = []
            for ev in events:
                mid = off = dur = 0
                for f4, x in _fields(ev):
                    if f4 == 1:
                        mid = x
                    elif f4 == 2:
                        off = x
                    elif f4 == 3:
                        dur = x
                start = t0 * 1e-9 + off * 1e-12
                ops.append((tf_op.get(mid, ""), start, start + dur * 1e-12))
            out[name] = ops
    return out


def scope_seconds(path: str, window=None) -> dict:
    """{part: device seconds inside `window`} on the first device plane
    that has events; {} where no op carries a known scope."""
    for _, ops in sorted(device_ops(path).items()):
        if not ops:
            continue
        lo, hi = window or (min(o[1] for o in ops), max(o[2] for o in ops))
        out = {}
        for tf_op, s, e in ops:
            d = min(e, hi) - max(s, lo)
            part = part_of(tf_op) if d > 0 else None
            if part:
                out[part] = out.get(part, 0.0) + d
        return out
    return {}


def of_run(run: dict) -> dict:
    """The traced run's parts, read once and kept on the run."""
    if "scope_seconds" not in run:
        from . import trace as tr
        t = run.get("trace") or {}
        devs = t.get("devices") or {}
        if not run.get("trace_dir") or not devs:
            run["scope_seconds"] = {}
        else:
            run["scope_seconds"] = scope_seconds(
                tr.find_xplane(run["trace_dir"]),
                devs[sorted(devs)[0]]["window"])
    return run["scope_seconds"]
