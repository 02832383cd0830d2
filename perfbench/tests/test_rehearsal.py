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
    ov["cell"]["limits"] = RESNET_LIMITS
    res = R.run_cell(ROOT, "resnet50.train_raw", 31337, 1.0, False,
                     overrides=ov, device=None)
    assert res["correct"] is True


RESNET_LIMITS = {"init_gap": 1e-6, "ingest_pixel_gap": 0,
                 "loss_gap_step1": 1e-4,
                 "first_grad_norm_gap_median": 0.015,
                 "forward_stats_gap": 3e-4}


def unchanged(real, params, st, batch, rng):
    """A step that returns its state unchanged."""
    import jax
    keep = jax.tree.map(lambda a: a.copy(), (params, st))
    _, _, out = real(params, st, batch, rng)
    return keep[0], keep[1], out


def first_rows_only(parts):
    """A step fed the first 1/parts of its rows, `parts` times over: with
    2, half of the batch left out and the mean taken over the rest; with
    the number of chips, every chip given the first chip's rows, which is
    what a chip computes when nothing is exchanged."""
    def broken(real, params, st, batch, rng):
        import jax
        import jax.numpy as jnp
        fed = {k: jax.device_put(
            jnp.concatenate([v[:v.shape[0] // parts]] * parts), v.sharding)
            for k, v in batch.items()}
        return real(params, st, fed, rng)
    return broken


def test_broken_timed_path_is_not_correct():
    res = R.run_cell(ROOT, "caffenet.train_jpeg", 7, 1.0, False,
                     overrides=tiny(67), device=None,
                     extra={"break_step": unchanged})
    assert res["correct"] is False


@pytest.mark.parametrize("broken,correct", [
    (None, True), (unchanged, False), (first_rows_only(2), False),
    (first_rows_only(4), False)],
    ids=["sound", "state_unchanged", "half_batch", "no_exchange"])
def test_resnet50_on_four_devices(broken, correct):
    """The four-chip cell at tiny size on four virtual devices, 4 rows a
    device: BatchNorm's statistics are over the 16 rows of all four, in
    the program and in the reference laid over the same devices; and each
    fault the cell can have reads `correct` false."""
    ov = tiny(96, batch=4, chips=4)
    ov["cell"]["limits"] = RESNET_LIMITS
    res = R.run_cell(ROOT, "resnet50.train_raw_dp4", 31338, 1.0, False,
                     overrides=ov, device=None,
                     extra={"break_step": broken} if broken else None)
    assert res["correct"] is correct, res["checks"]


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


@pytest.mark.parametrize("stock,drain_steps", [(0, 0), (100, 59)])
def test_setup_ends_at_the_last_warmup_step(monkeypatch, stock,
                                            drain_steps):
    """`setup_s` is the clock at the job's 8th step whatever the feed: a
    feed that keeps a stock forces the drain to MAX_WARMUP_STEPS, the
    window opens 59 steps later, and only the printed drain moves."""
    import jax.numpy as jnp
    from perfbench.windows import train
    clock = FakeClock()
    monkeypatch.setattr(train, "time", clock)
    obs = train.Observer(seconds=3.0, warmup_steps=5, trace_dir=None,
                         t_process0=0.0)
    monkeypatch.setattr(obs, "_capture", lambda n, batch, result: None)
    monkeypatch.setattr(obs, "_pipeline_summary", lambda: {})
    monkeypatch.setattr(obs, "_stock", lambda steps: stock)
    monkeypatch.setattr(obs, "_sample_memory", lambda: None)

    def real(params, st, batch, rng):
        clock.now += 1.0                        # a step takes a second
        return params, st, {"loss": jnp.float32(1.0)}

    step = obs.wrap(real)
    while not obs.done.is_set():
        step({}, {}, {}, None)
    setup_s, drain_s, steps = obs.setup_and_drain()
    assert setup_s == 8.0
    assert (drain_s, steps) == (float(drain_steps), drain_steps)
    assert obs.n_a == 8 + drain_steps and obs.n_b - obs.n_a == 3


@pytest.mark.parametrize("cell,crop,batch", [
    ("caffenet.train_jpeg", 67, 4), ("resnet50.train_raw", 96, 16),
    ("resnet50.train_raw_dp4", 96, 4)])
def test_lower_precision_control_fails_the_limits(cell, crop, batch):
    """The program's own bfloat16-activation path and every planted fault
    against the cell's real limits, and the program as the configuration
    states it beside them, over as many devices as the cell has chips."""
    from perfbench import control
    res = R.resolve(ROOT, cell)
    for part, patch in tiny(crop, batch=batch, chips=res["chips"]).items():
        res[part].update(patch)
    each = control.readings(res, 11, os.path.join(
        ROOT, ".perfbench_work", "test_control." + cell))
    limits = json.load(open(os.path.join(
        ROOT, "perfbench", "cells", cell + ".json")))["limits"]
    assert ("no_exchange" in each) == (res["chips"] > 1)
    assert not control.fails(each.pop("sound"), limits)
    for name, nums in each.items():
        assert control.fails(nums, limits), (name, nums)


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
