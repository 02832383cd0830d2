"""Rows per held expert, largest over mean, the worst expert layer and
step of the window: the expert layers' own per-step tops."""


def read(run):
    return (run.get("experts") or {}).get("rows_max_over_mean")
