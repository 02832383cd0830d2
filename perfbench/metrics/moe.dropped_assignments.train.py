"""Assignments the expert layers dropped, summed over the window's steps
and layers (the layers' own per-step tops).  Must be 0."""


def read(run):
    return (run.get("experts") or {}).get("dropped_assignments")
