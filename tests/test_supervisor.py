"""Elastic recovery supervisor: a rank dies mid-run (injected fault),
the supervisor tears the cluster down and relaunches every rank from
the newest snapshot, and the job completes — the automated form of the
recovery the reference documents as a manual resubmit
(`Config.scala:461-467`)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from caffeonspark_tpu.tools.supervisor import find_latest_snapshot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 2
SNAP = 8
MAX_ITER = 24


def test_find_latest_snapshot(tmp_path):
    assert find_latest_snapshot(str(tmp_path), "m") is None
    for it in (8, 16):
        (tmp_path / f"m_iter_{it}.solverstate").touch()
        (tmp_path / f"m_iter_{it}.caffemodel").touch()
    (tmp_path / "m_iter_24.solverstate").touch()   # state without model
    s, m = find_latest_snapshot(str(tmp_path), "m")
    assert s.endswith("m_iter_16.solverstate")
    assert m.endswith("m_iter_16.caffemodel")


@pytest.mark.slow  # spawns a mini-cluster subprocess fleet (12-24 s)
@pytest.mark.chaos
def test_supervisor_recovers_from_rank_death(tmp_path):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    imgs, labels = make_images(128, seed=6)
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
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        f'lr_policy: "fixed"\ndisplay: {SNAP}\nmax_iter: {MAX_ITER}\n'
        f'snapshot: {SNAP}\nsnapshot_prefix: "sv"\nrandom_seed: 11\n')

    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           # rank 1 exits(3) at iter 12 — after the iter-8 snapshot —
           # exactly once (marker suppresses it post-relaunch)
           "COS_FAULT_DIE_ONCE": f"1:12:{tmp_path}/died.marker",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-cluster", str(N),
         "-max_restarts", "2", "-poll_interval", "0.3"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-1000:])
    assert "attempt 1 ranks [0, 1] from scratch" in r.stdout
    assert "tearing down for relaunch" in r.stdout
    assert (f"attempt 2 ranks [0, 1] from "
            f"{out}/sv_iter_{SNAP}.solverstate") in r.stdout
    assert "run complete" in r.stdout
    assert os.path.exists(tmp_path / "died.marker")
    assert (out / f"sv_iter_{MAX_ITER}.caffemodel").exists()


def _tiny_job(tmp_path, max_iter=12, snap=100):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(64, seed=9)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(64)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.01\nlr_policy: "fixed"\n'
        f'display: 100\nmax_iter: {max_iter}\nsnapshot: {snap}\n'
        'snapshot_prefix: "sv"\nrandom_seed: 11\n')
    return solver


@pytest.mark.slow  # spawns a mini-cluster subprocess fleet (12-24 s)
def test_per_host_supervisors_complete_pod_job(tmp_path):
    """The multi-host shape from docs/deploy.md on localhost: TWO
    supervisor processes, each hosting ONE rank of a cluster=2 job,
    rendezvousing through a shared coordinator — both must exit 0 and
    rank 0 writes the final model."""
    import socket
    solver = _tiny_job(tmp_path)
    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    procs = []
    for host_id in (0, 1):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
             "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
             "-output", str(out), "-cluster", "2",
             "-server", f"127.0.0.1:{port}",
             "-rank_base", str(host_id), "-local_ranks", "1",
             "-max_restarts", "0", "-poll_interval", "0.3"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=REPO))
    outs = []
    for p in procs:
        o, _ = p.communicate(timeout=560)
        outs.append(o)
    assert all(p.returncode == 0 for p in procs), outs
    assert "ranks [0] from scratch" in outs[0]
    assert "ranks [1] from scratch" in outs[1]
    assert (out / "sv_iter_12.caffemodel").exists()


@pytest.mark.slow  # spawns a mini-cluster subprocess fleet (12-24 s)
@pytest.mark.chaos
def test_stall_timeout_detects_remote_death(tmp_path):
    """cluster=2 but only rank 0 exists (the 'remote host died before
    joining' case): rank 0 blocks in the rendezvous, no snapshots
    appear, and the stall timeout must tear down instead of hanging
    forever."""
    solver = _tiny_job(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path / "out"), "-cluster", "2",
         "-rank_base", "0", "-local_ranks", "1",
         "-stall_timeout", "12", "-max_restarts", "0",
         "-poll_interval", "0.3"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=REPO)
    assert r.returncode == 1, r.stdout[-1500:]
    assert "no progress for 12s" in r.stdout
    assert "max_restarts exceeded" in r.stdout
