"""4-process cluster + mid-run rank-failure → resume-from-snapshot
drill (round-1 VERDICT item 8; the recovery story the reference only
documents, `Config.scala:461-467` — a failed executor means the job is
relaunched with -snapshot/-weights pointing at the last good state).

Choreography:
  1. 4 OS processes (1 CPU device each) train in lockstep via
     jax.distributed; rank 0 snapshots every `snap` iters.
  2. Once the iter-`snap` snapshot lands, rank 3 is SIGKILLed mid-run
     (a per-step fault-injection delay keeps the window open).  The
     survivors block in the gradient all-reduce — the same hang a dead
     NCCL/MPI peer causes — and are terminated, as a cluster manager
     would.
  3. The full cluster relaunches with -snapshot/-weights from the last
     good state and trains to completion; the final model exists and
     all ranks report lockstep completion.
"""

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

# slow/e2e: 2-4 OS processes per test joining a jax.distributed
# cluster, with kill/relaunch choreography — tens of seconds each on
# the CI box.  Run with `-m slow`; these are the LOCKSTEP legs of the
# chaos drill suite (`make chaos`) — the elastic sync-mode legs live
# in tests/test_syncmode.py.
pytestmark = [pytest.mark.slow, pytest.mark.chaos]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


N_PROCS = 4
SNAP = 6
MAX_ITER = 40


def _launch(solver, lmdb, out, port, rank, env, extra=(),
            cluster=N_PROCS):
    return subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(lmdb),
         "-output", str(out),
         "-server", f"127.0.0.1:{port}",
         "-cluster", str(cluster), "-rank", str(rank), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO)


def test_four_process_rank_failure_resume(tmp_path):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    imgs, labels = make_images(256, seed=4)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(256)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
  inner_product_param {{ num_output: 24
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu" type: "ReLU" bottom: "ip1" top: "ip1" }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        f'lr_policy: "fixed"\ndisplay: {SNAP}\nmax_iter: {MAX_ITER}\n'
        f'snapshot: {SNAP}\nsnapshot_prefix: "mh"\nrandom_seed: 9\n')

    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "",
           "COS_FAULT_STEP_DELAY_MS": "150",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}

    # ---- run 1: kill rank 3 after the first snapshot lands -----------
    port = _free_port()
    procs = [_launch(solver, tmp_path / "lmdb", out, port, r, env)
             for r in range(N_PROCS)]
    state = out / f"mh_iter_{SNAP}.solverstate"
    model = out / f"mh_iter_{SNAP}.caffemodel"
    deadline = time.time() + 240
    while time.time() < deadline and not (
            state.exists() and model.exists()):
        assert all(p.poll() is None or p.returncode == 0
                   for p in procs), "a rank died before the snapshot"
        time.sleep(0.1)
    assert state.exists() and model.exists(), "snapshot never appeared"

    procs[3].send_signal(signal.SIGKILL)
    procs[3].wait(timeout=30)
    assert procs[3].returncode == -9

    # survivors block in the all-reduce (dead-peer hang) or exit on a
    # distributed error; give them a moment, then terminate — the
    # cluster-manager role
    time.sleep(2.0)
    unfinished = [p for p in procs[:3] if p.poll() is None]
    for p in unfinished:
        p.kill()
    for p in procs[:3]:
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
    # the drill is only meaningful if the failure was mid-run
    assert not (out / f"mh_iter_{MAX_ITER}.caffemodel").exists(), \
        "run finished before the kill — fault window too small"

    # ---- run 2: full relaunch resuming from the last good state ------
    env2 = {**env, "COS_FAULT_STEP_DELAY_MS": "0"}
    port2 = _free_port()
    procs2 = [_launch(solver, tmp_path / "lmdb", out, port2, r, env2,
                      extra=("-snapshot", str(state),
                             "-weights", str(model)))
              for r in range(N_PROCS)]
    outs = []
    for p in procs2:
        o, _ = p.communicate(timeout=520)
        outs.append(o)
    for r, (p, o) in enumerate(zip(procs2, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-2000:]}"
    last_display = (MAX_ITER // SNAP) * SNAP
    for r, o in enumerate(outs):
        assert f"resumed from iter {SNAP}" in o, f"rank {r}:\n{o[-800:]}"
        # lockstep: every rank reached the last display boundary
        assert f"iter {last_display}/{MAX_ITER}" in o, \
            f"rank {r}:\n{o[-800:]}"
    assert "final model" in outs[0]
    assert (out / f"mh_iter_{MAX_ITER}.caffemodel").exists()
    for o in outs[1:]:
        assert "final model" not in o     # rank-0-only snapshots


def test_two_process_zero_sharded_snapshot_resume(tmp_path):
    """ZeRO-1 across REAL processes: a 2-proc dp2 cluster with
    COS_ZERO=1 shards the optimizer state between the processes, so
    no single rank can write a full .solverstate — each rank writes
    its shard SIDECAR, rank 1 is killed mid-run, and the relaunch
    reassembles the full state from both sidecars (the per-host
    checkpoint write of checkpoint.py's sharded-state design, proven
    over a real jax.distributed cluster)."""
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    N, snap, max_iter = 2, 6, 30
    imgs, labels = make_images(128, seed=7)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(128)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
  inner_product_param {{ num_output: 32
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu" type: "ReLU" bottom: "ip1" top: "ip1" }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        f'lr_policy: "fixed"\ndisplay: {snap}\nmax_iter: {max_iter}\n'
        f'snapshot: {snap}\nsnapshot_prefix: "zs"\nrandom_seed: 9\n')

    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "",
           "COS_ZERO": "1",
           "COS_FAULT_STEP_DELAY_MS": "150",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}

    port = _free_port()
    procs = [_launch(solver, tmp_path / "lmdb", out, port, r, env,
                     cluster=N) for r in range(N)]
    state = out / f"zs_iter_{snap}.solverstate"
    model = out / f"zs_iter_{snap}.caffemodel"
    shards = [out / f"zs_iter_{snap}.solverstate.shard{r}"
              for r in range(N)]
    deadline = time.time() + 240
    while time.time() < deadline and not (
            state.exists() and model.exists()
            and all(s.exists() for s in shards)):
        assert all(p.poll() is None or p.returncode == 0
                   for p in procs), "a rank died before the snapshot"
        time.sleep(0.1)
    assert all(s.exists() for s in shards), (
        "every rank must write its ZeRO state sidecar "
        f"(have: {[s.name for s in shards if s.exists()]})")

    procs[1].send_signal(signal.SIGKILL)
    procs[1].wait(timeout=30)
    time.sleep(2.0)
    for p in procs[:1]:
        if p.poll() is None:
            p.kill()
        try:
            p.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
    assert not (out / f"zs_iter_{max_iter}.caffemodel").exists(), \
        "run finished before the kill — fault window too small"

    env2 = {**env, "COS_FAULT_STEP_DELAY_MS": "0"}
    port2 = _free_port()
    procs2 = [_launch(solver, tmp_path / "lmdb", out, port2, r, env2,
                      extra=("-snapshot", str(state),
                             "-weights", str(model)), cluster=N)
              for r in range(N)]
    outs = []
    for p in procs2:
        o, _ = p.communicate(timeout=520)
        outs.append(o)
    for r, (p, o) in enumerate(zip(procs2, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-2000:]}"
        assert f"resumed from iter {snap}" in o, f"rank {r}:\n{o[-800:]}"
    assert "final model" in outs[0]
    assert (out / f"zs_iter_{max_iter}.caffemodel").exists()
