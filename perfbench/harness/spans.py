"""The program's own host spans out of a run's profiler trace.

`PipelineMetrics.span(stage)` (caffeonspark_tpu/metrics.py holds the
vocabulary) brackets each interval it adds to a series in a
`jax.profiler.TraceAnnotation("cos.<stage>")`.  With a profiler session
running those land as events on the lines (one per thread) of the
"/host:CPU" plane of the same .xplane.pb as the device ops, on the same
clock.  A program from before the spans has none: every reader here then
finds nothing and returns nothing.
"""

from __future__ import annotations

from .trace import clip, find_xplane, subtract, total, union

PREFIX = "cos."


def load(path: str, prefix: str = PREFIX):
    """-> [(name, line, start_s, end_s, stats)] by start time: `name`
    without the prefix, `line` one host thread of the trace, `stats` the
    span's attrs (n=, w=, it=, k=)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            key = f"{line.name}#{i}"
            for ev in line.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name[len(prefix):], key,
                                ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[2])


def of_run(run: dict):
    """The spans of a traced run; [] for an untraced one."""
    if not run.get("trace_dir"):
        return []
    try:
        return load(find_xplane(run["trace_dir"]))
    except FileNotFoundError:
        return []


def intervals(spans, name: str):
    """Merged [(start, end)] of every span called `name`."""
    return union([(s, e) for n, _, s, e, _ in spans if n == name])


def idle_share_inside(busy, window, spans, name: str):
    """Of one device's idle time in `window`, the share that lies inside
    the spans called `name`; None without idle time or such spans."""
    lo, hi = window
    idle = subtract([(lo, hi)], busy)
    inside = clip(intervals(spans, name), lo, hi)
    if not inside or total(idle) <= 0:
        return None
    outside = subtract(idle, inside)
    return 1.0 - total(outside) / total(idle)


def by_thread(spans):
    """{line: {name: [count, seconds, first span's stats]}}: what each
    host thread of a capture spent in which stage."""
    out = {}
    for name, line, s, e, stats in spans:
        d = out.setdefault(line, {}).setdefault(name, [0, 0.0, stats])
        d[0] += 1
        d[1] += e - s
    return out


def print_by_thread(spans, indent="  "):
    for line, names in sorted(by_thread(spans).items()):
        print(f"{indent}thread {line}")
        for name, (count, secs, stats) in sorted(names.items()):
            print(f"{indent}  cos.{name:<15} {count:5d} spans "
                  f"{secs:9.4f} s   first attrs {stats}")
