"""Of the pool workers' time in the window (`pack` + `pack_starved` +
`pack_blocked`), the share spent waiting for work."""

from perfbench.harness.series import delta


def read(run):
    parts = [delta(run, name, witness="pack_cpu")
             for name in ("pack_starved", "pack", "pack_blocked")]
    if None in parts:
        return None
    whole = sum(p[0] for p in parts)
    return 100.0 * parts[0][0] / whole if whole > 0 else None
