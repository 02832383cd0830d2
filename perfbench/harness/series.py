"""`PipelineMetrics` series between the window's edges.

The train window's observer snapshots `(total_s, count)` of every series
the program keeps at edge a and at edge b into `run["pipeline"]`.  A
series the program does not keep (a program from before it) reads None;
one it keeps but that had no sample yet (a wait that never happened)
reads zero, which `witness`, a series every instrumented run has, tells
apart from the first case.
"""

from __future__ import annotations


def at_b(run: dict, name: str, witness: str | None = None):
    """(total_s, count) at edge b."""
    b = run["pipeline"][1] or {}
    if name in b:
        return tuple(b[name])
    return (0.0, 0) if witness and witness in b else None


def delta(run: dict, name: str, witness: str | None = None):
    """(total_s, count) gained between the edges."""
    end = at_b(run, name, witness)
    if end is None:
        return None
    start = (run["pipeline"][0] or {}).get(name, (0.0, 0))
    return end[0] - start[0], end[1] - start[1]
