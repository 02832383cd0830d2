"""The chip: found or the run fails; its peaks come from one table."""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def require_chip(chips: int) -> dict:
    """Exit non-zero (no result line) unless JAX holds `chips` TPU chips
    of a kind the peak table knows."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu" or dev["count"] < chips:
        sys.exit(f"perfbench: this cell needs {chips} TPU chip(s); JAX "
                 f"found {dev}.  No result is printed for another device.")
    peaks(dev["kind"])
    return dev


def peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        sys.exit(f"perfbench: device kind {kind!r} has no row in "
                 "perfbench/peaks.json — add it with its source; a peak is "
                 "never defaulted")
    return table[kind]


def memory_now() -> int:
    """Bytes the fullest local chip holds at this instant (0 where the
    backend reports none): live buffers plus the scratch its loaded
    programs have reserved.

    The TPU runtime keeps two books.  `bytes_in_use` are live buffers
    (parameters, optimizer state, staged batches); `bytes_reserved` is the
    temporary memory of the loaded train step, which `bytes_in_use` does
    not count: it reads 4.02 GB for CaffeNet at batch 768 and 5.07 GB for
    ResNet-50 at 64 (my chip runs, PR 23) where the compiler's
    memory_analysis() gives the step 3.94 and 5.16 GB of temporaries
    (compile rehearsal, PR 23).  The window samples this sum at every
    step and reports the largest, so no two peaks of different moments
    are added."""
    import jax
    now = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        now = max(now, int(stats.get("bytes_in_use", 0))
                  + int(stats.get("bytes_reserved", 0)))
    return now


def memory_report() -> list:
    """Every local device's memory statistics, as the backend gives them."""
    import jax
    return [{"id": d.id, **{k: int(v) for k, v in
                            (d.memory_stats() or {}).items()}}
            for d in jax.local_devices()]
