"""CPU rehearsals of the `train` window at tiny shapes: the control flow
of a run from records to the result object, the plain references against
the system, a broken timed path, and the lower-precision control.  No
device metric is printed: a rehearsal withholds every value."""

import json
import os

import pytest

from conftest import ROOT
from perfbench import run as R

SOLVER = {"base_lr": 0.0001, "lr_policy": "step", "gamma": 0.1,
          "stepsize": 100000, "momentum": 0.9, "weight_decay": 0.0005}


def tiny(crop, batch=4, chips=1):
    return {"entry": {"chips": chips},
            "config": {"crop": crop, "per_device_batch": batch,
                       "solver": SOLVER},
            "traffic": {"side": crop + 5},
            "cell": {"warmup_steps": 3, "records_per_global_batch": 4.0,
                     "trace_seconds": 1}}


@pytest.mark.parametrize("chips,trace", [(1, False), (1, True), (4, False)])
def test_train_window_rehearsal(chips, trace):
    """One device, traced, and dp=4 on four virtual devices (no cell takes
    four chips yet; the path is rehearsed for the one that will)."""
    res = R.run_cell(ROOT, "caffenet.train_jpeg", 2147483999, 1.0, trace,
                     overrides=tiny(67, chips=chips), device=None)
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] and all(v is None for v in res["metrics"].values())
    assert "device" not in res


def test_resnet50_reference_against_system():
    """Batch 16 at crop 96: BatchNorm over a handful of values is too
    ill-conditioned below that for a float32 comparison to say much."""
    ov = tiny(96, batch=16)
    ov["cell"]["limits"] = {"init_gap": 1e-6, "ingest_pixel_gap": 0,
                            "loss_gap_step1": 1e-4,
                            "first_grad_norm_gap_median": 0.015,
                            "forward_stats_gap": 3e-4}
    res = R.run_cell(ROOT, "resnet50.train_raw", 31337, 1.0, False,
                     overrides=ov, device=None)
    assert res["correct"] is True


def test_broken_timed_path_is_not_correct():
    """A step that returns its state unchanged."""
    def broken(real, params, st, batch, rng):
        import jax
        keep = jax.tree.map(lambda a: a.copy(), (params, st))
        _, _, out = real(params, st, batch, rng)
        return keep[0], keep[1], out
    res = R.run_cell(ROOT, "caffenet.train_jpeg", 7, 1.0, False,
                     overrides=tiny(67), device=None,
                     extra={"break_step": broken})
    assert res["correct"] is False


@pytest.mark.parametrize("cell,crop,batch", [
    ("caffenet.train_jpeg", 67, 4), ("resnet50.train_raw", 96, 16)])
def test_lower_precision_control_fails_the_limits(cell, crop, batch):
    """The program's own bfloat16-activation path against the cell's real
    limits, and the program as the configuration states it beside it."""
    from perfbench import control
    res = R.resolve(ROOT, cell)
    for part, patch in tiny(crop, batch=batch).items():
        res[part].update(patch)
    both = control.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control." + cell))
    limits = json.load(open(os.path.join(
        ROOT, "perfbench", "cells", cell + ".json")))["limits"]
    assert control.fails(both["control"], limits), both["control"]
    assert not control.fails(both["sound"], limits), both["sound"]


def test_run_refuses_without_a_chip():
    import subprocess
    import sys
    p = subprocess.run([sys.executable, os.path.join(
        ROOT, "perfbench", "run.py"), "--workload", "caffenet.train_jpeg",
        "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")
