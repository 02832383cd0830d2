"""fsutils against a REAL remote scheme: gs:// over a live HTTP server.

VERDICT r3 #9: the remote-FS plumbing had only ever round-tripped
through fsspec's in-process memory:// backend.  Here the snapshot
upload / resume / supervisor-discovery paths run against gcsfs — the
actual backend the deploy docs prescribe (`-output gs://bucket/run`) —
talking to an in-process fake GCS JSON-API server (tests/fake_gcs.py)
over a real socket via STORAGE_EMULATOR_HOST.  Every byte crosses HTTP;
nothing is monkeypatched.  Reference analog: FSUtils.scala:21-89
(CopyFileToHDFS/GenModelOrState against real HDFS).
"""

import os

import numpy as np
import pytest

gcsfs = pytest.importorskip("gcsfs")

# slow/e2e: every byte crosses a real HTTP socket, and in an offline
# container gcsfs's credential/retry machinery can stall for minutes
# (measured: the FIRST test alone exceeds 120 s on the CI box, which
# used to eat the entire tier-1 870 s budget and starve every test
# file after this one alphabetically).  Run with `-m slow`.
pytestmark = pytest.mark.slow

from caffeonspark_tpu.utils import fsutils  # noqa: E402

from fake_gcs import FakeGCS  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def gcs(monkeypatch):
    server = FakeGCS()
    monkeypatch.setenv("STORAGE_EMULATOR_HOST", server.endpoint)
    gcsfs.GCSFileSystem.clear_instance_cache()
    yield server
    server.close()
    gcsfs.GCSFileSystem.clear_instance_cache()


def test_bytes_and_upload_roundtrip(gcs, tmp_path):
    fsutils.write_bytes("gs://bkt/run/a.bin", b"over-http")
    assert fsutils.exists("gs://bkt/run/a.bin")
    assert fsutils.read_bytes("gs://bkt/run/a.bin") == b"over-http"
    local = tmp_path / "up.bin"
    local.write_bytes(b"uploaded")
    fsutils.upload(str(local), "gs://bkt/run/up.bin")
    back = fsutils.download("gs://bkt/run/up.bin",
                            str(tmp_path / "down.bin"))
    assert open(back, "rb").read() == b"uploaded"
    assert sorted(fsutils.listdir("gs://bkt/run")) == ["a.bin", "up.bin"]
    # dircache must not freeze: a file created after the first listing
    # (here by the server, in reality by another rank) shows up
    gcs.store[("bkt", "run/late.bin")] = b"x"
    assert "late.bin" in fsutils.listdir("gs://bkt/run")


def test_snapshot_and_resume_over_gcs(gcs):
    """GenModelOrState analog: snapshot straight to gs://, then resume
    from it — the write-local-then-upload path + remote restore."""
    import jax
    from caffeonspark_tpu import checkpoint
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver

    npm = NetParameter.from_text("""
name: "t"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 4 channels: 1 height: 8 width: 8 } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 3
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }""")
    sp = SolverParameter.from_text(
        "base_lr: 0.01 max_iter: 4 random_seed: 3")
    solver = Solver(sp, npm)
    params, st = solver.init()
    model, state = checkpoint.snapshot(
        solver.train_net, params, st, "gs://bkt/run1/model")
    assert model.startswith("gs://bkt/run1/") and fsutils.exists(model)
    assert fsutils.exists(state)

    p2, st2 = solver.init()
    p2, st2 = checkpoint.restore(solver.train_net, p2, st2, state,
                                 weights_path=model)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(params["ip"]["weight"])),
        np.asarray(jax.device_get(p2["ip"]["weight"])))


def test_supervisor_rank_death_drill_over_gcs(gcs, tmp_path):
    """Full pod-shaped elastic-recovery drill over the remote FS
    (VERDICT r4 #8): a cluster=2 supervisor job with `-output gs://`,
    rank 1 dies mid-run AFTER the iter-8 snapshot (injected fault),
    the supervisor relaunches every rank FROM the gs:// snapshot, and
    the completed model lands in the bucket.  Composes
    test_supervisor_recovers_from_rank_death with the fake GCS server:
    every snapshot write, discovery listing, and resume read is an
    HTTP round trip from real separate rank processes."""
    import subprocess
    import sys

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
        'lr_policy: "fixed"\ndisplay: 8\nmax_iter: 24\n'
        'snapshot: 8\nsnapshot_prefix: "sv"\nrandom_seed: 11\n')

    out = "gs://bkt/drill"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "STORAGE_EMULATOR_HOST": gcs.endpoint,
           "COS_FAULT_DIE_ONCE": f"1:12:{tmp_path}/died.marker",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", out, "-cluster", "2",
         "-max_restarts", "2", "-poll_interval", "0.3"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-1000:])
    assert "attempt 1 ranks [0, 1] from scratch" in r.stdout
    assert os.path.exists(tmp_path / "died.marker")
    assert (f"attempt 2 ranks [0, 1] from "
            f"{out}/sv_iter_8.solverstate") in r.stdout
    assert "run complete" in r.stdout
    assert ("bkt", "drill/sv_iter_24.caffemodel") in gcs.store
    assert ("bkt", "drill/sv_iter_24.solverstate") in gcs.store


def test_supervisor_discovery_over_gcs(gcs):
    """The multi-host recovery path (ADVICE r3 high): snapshot
    discovery + content-derived progress stamps on a gs:// output dir,
    every call an HTTP round trip."""
    import argparse

    from caffeonspark_tpu.tools.supervisor import (Supervisor,
                                                   find_latest_snapshot)

    out = "gs://bkt/run2"
    assert find_latest_snapshot(out, "m") is None
    for it in (10, 25):
        fsutils.write_bytes(f"{out}/m_iter_{it}.solverstate", b"s")
        fsutils.write_bytes(f"{out}/m_iter_{it}.caffemodel", b"m")
    fsutils.write_bytes(f"{out}/m_iter_40.solverstate", b"s")  # no model
    assert find_latest_snapshot(out, "m") == (
        f"{out}/m_iter_25.solverstate", f"{out}/m_iter_25.caffemodel")

    sup = Supervisor(argparse.Namespace(output=out), [])
    st1 = sup._progress_stamp("m")
    assert st1 == (40, 5)
    # another rank writes a newer snapshot: the stamp must advance
    # (the healthy-run stall-timer bug this fixes)
    gcs.store[("bkt", "run2/m_iter_55.solverstate")] = b"s"
    assert sup._progress_stamp("m") > st1
