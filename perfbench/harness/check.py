"""The comparison that decides `correct` for a training cell.

Program side: what the observer copied out of the timed path's own
first steps (state before step 1, parameters and momentum after it,
parameters after the last check step, each step's loss, each step's
batch as the program packed it).  Reference side:
reference/common.train_steps on batches the benchmark rebuilds from its
own records.
"""

from __future__ import annotations

import math

import numpy as np

# the program names a layer's blobs; Caffe numbers them
BLOB_INDEX = {"weight": 0, "mean": 0, "scale": 0, "bias": 1,
              "variance": 1, "count": 2}


def by_index(tree) -> dict:
    """{layer: {blob name: array}} -> {"layer/i": float64 array}."""
    return {f"{ln}/{BLOB_INDEX[bn]}": np.asarray(a, np.float64)
            for ln, blobs in tree.items() for bn, a in blobs.items()}


def match_rows(strips, labels, pixels, rec_labels, means, crop, mirror):
    """Find, for each packed row, the record and the crop the program
    drew: `strips` is the first pixel row of every packed image
    (B, C, crop), mean-subtracted.  -> [(record, h, w, flip)], worst
    absolute pixel gap over the strips."""
    side = pixels.shape[-1]
    span = side - crop + 1
    means = np.asarray(means, np.float32)
    by_label = {}
    for i, lab in enumerate(rec_labels):
        by_label.setdefault(int(lab), []).append(i)
    probe_w = 16
    found, worst, bad = [], 0.0, []
    win = np.lib.stride_tricks.sliding_window_view
    for row in range(strips.shape[0]):
        strip = strips[row] + means[:, None]
        best = None
        for rec in by_label.get(int(labels[row]), []):
            plane = pixels[rec, 0, :span].astype(np.float32)
            for flip in ((False, True) if mirror else (False,)):
                if flip:    # strip[j] = R[h, w + crop - 1 - j]
                    cand = win(plane[:, crop - probe_w:
                                     crop - probe_w + span + probe_w - 1],
                               probe_w, axis=1)[:, :, ::-1]
                else:
                    cand = win(plane[:, :span + probe_w - 1], probe_w,
                               axis=1)
                err = np.abs(cand - strip[0, :probe_w]).sum(-1)
                # the probe can tie (flat patches): the whole strip decides
                for h, w in zip(*np.nonzero(err <= err.min() + 1e-3)):
                    own = pixels[rec, :, h, w:w + crop].astype(np.float32)
                    gap = float(np.max(np.abs(
                        (own[:, ::-1] if flip else own) - strip)))
                    if best is None or gap < best[0]:
                        best = (gap, rec, int(h), int(w), flip)
                    if gap == 0:
                        break
        if best is None:
            raise ValueError(f"row {row}: no record has label "
                             f"{labels[row]}")
        gap, rec, h, w, flip = best
        if gap > 8:
            bad.append((row, int(labels[row]), rec, h, w, flip, gap))
        worst = max(worst, gap)
        found.append((rec, h, w, flip))
    if bad:
        print(f"[perfbench] ingest: {len(bad)} of {strips.shape[0]} packed "
              f"rows match no crop of the record their label names; first "
              f"(row, label, record, h, w, flip, gap): {bad[:4]}", flush=True)
    return found, worst


def rebuild_batch(found, pixels, means, crop):
    """The benchmark's own crop / mirror / mean-subtract of its own
    records (data_transformer.cpp), float32 NCHW."""
    means = np.asarray(means, np.float32)[:, None, None]
    out = np.empty((len(found), pixels.shape[1], crop, crop), np.float32)
    for i, (rec, h, w, flip) in enumerate(found):
        img = pixels[rec, :, h:h + crop, w:w + crop]
        out[i] = (img[:, :, ::-1] if flip else img)
        out[i] -= means
    return out


def leaf_gaps(prog: dict, ref: dict):
    """Per leaf | ||prog|| - ||ref|| | / max(||ref||, median ||ref||);
    -> (worst gap, its leaf, median gap)."""
    positive = [v for v in ref.values() if v > 0]
    med = float(np.median(positive)) if positive else 1.0
    gaps = {k: abs(prog[k] - r) / max(r, med) for k, r in ref.items()}
    if not all(math.isfinite(g) for g in gaps.values()):
        return float("inf"), "", float("inf")
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf, float(np.median(list(gaps.values())))


def lr_mults_of(layers) -> dict:
    """{"layer/i": lr_mult} from a reference's layer table."""
    return {f"{ln}/{i}": b[2] for ln, bl in layers for i, b in enumerate(bl)}


def forward_stats_gap(prog_p1: dict, ref_p1: dict, lr_mults: dict):
    """Geometric mean, over the leaves the optimizer never touches and
    the forward pass writes (BatchNorm's accumulated mean and variance),
    of ||prog - ref|| / ||ref|| after step 1: forward quantities at the
    seeded weights, from the shallowest layer to the deepest.  A gap
    under one float32 rounding counts as one.  None for a net that keeps
    no such statistics."""
    logs = []
    for k, r in ref_p1.items():
        if lr_mults[k] or r.size < 2:       # BatchNorm's count is one number
            continue
        r = r.astype(np.float64)
        gap = np.linalg.norm(prog_p1[k] - r) / np.linalg.norm(r)
        logs.append(math.log(max(float(gap), 2.0 ** -24))
                    if math.isfinite(gap) else math.inf)
    return math.exp(sum(logs) / len(logs)) if logs else None


def norms(tree: dict, scale=None) -> dict:
    """Per leaf ||v|| (times scale[leaf], over scale's leaves, if given)."""
    return {k: float(np.linalg.norm(np.asarray(tree[k], np.float64).ravel()))
            * (scale[k] if scale else 1.0) for k in (scale or tree)}


def compare(prog: dict, ref: dict, lr_mults: dict, lr: float) -> dict:
    """The numbers compared, by name.  prog and ref hold the same things
    under "layer/i": parameters before step 1 (p0), after it (p1) and
    after the last check step (p_last), the momentum after step 1 (v1),
    and each step's loss -- the program's copied out of the timed path by
    the observer, the reference's from reference/common.train_steps."""
    nums = {}
    for k, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        nums[f"loss_gap_step{k + 1}"] = (
            abs(a - b) / abs(b) if math.isfinite(a) else float("inf"))
    # over the leaves the optimizer moves: the first gradient as it got
    # it, V1 / (lr lr_mult), and the parameters' change over the steps
    per_lr = {k: 1.0 / (lr * m) for k, m in lr_mults.items() if m}
    (nums["first_grad_norm_gap"], nums["first_grad_norm_gap_leaf"],
     nums["first_grad_norm_gap_median"]) = leaf_gaps(
        norms(prog["v1"], per_lr), norms(ref["v1"], per_lr))
    (nums["update_norm_gap"], nums["update_norm_gap_leaf"],
     nums["update_norm_gap_median"]) = leaf_gaps(
        norms({k: prog["p_last"][k] - prog["p0"][k] for k in per_lr}),
        norms({k: ref["p_last"][k] - ref["p0"][k] for k in per_lr}))
    nums["init_gap"] = max(
        float(np.max(np.abs(prog["p0"][k] - a))) if a.size else 0.0
        for k, a in ref["p0"].items())
    stats = forward_stats_gap(prog["p1"], ref["p1"], lr_mults)
    if stats is not None:
        nums["forward_stats_gap"] = stats
    return nums


def verdict(nums: dict, limits: dict) -> bool:
    """Every limited number at or under its limit; prints each beside it,
    then the numbers that were read and are held to no limit in this cell."""
    ok = True
    for name, limit in limits.items():
        value = nums.get(name)
        leaf = nums.get(name + "_leaf")
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        print(f"[perfbench] check {name} = {value!r} limit {limit!r}"
              + (f" (worst leaf {leaf})" if leaf else "")
              + ("" if good else "  <-- FAILS"), flush=True)
        ok = ok and good
    for name, value in nums.items():
        if name not in limits and isinstance(value, float):
            print(f"[perfbench] recorded {name} = {value!r}", flush=True)
    return ok
