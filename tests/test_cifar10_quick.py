"""CIFAR-10 quick workload (BASELINE.md shipped-config matrix): the
reference's cifar10_quick solver+net train end to end through the CLI
on synthetic CIFAR-shaped LMDBs, exercising the mean_file path (the
config subtracts mean.binaryproto) and the conv/pool/LRN-free quick
topology.  Sources are redirected the same way the reference's CI
does (its paths point at a Yahoo-internal HDFS)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REF = "/root/reference/data"

pytestmark = pytest.mark.skipif(
    not os.path.exists(os.path.join(REF, "cifar10_quick_solver.prototxt")),
    reason="reference configs not present")


def test_cifar10_quick_cli(tmp_path):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto import Phase, read_net, read_solver
    from caffeonspark_tpu.proto.caffe import BlobProto, Datum

    rng = np.random.RandomState(0)
    for split, n, seed in (("train", 600, 1), ("test", 200, 2)):
        imgs, labels = make_images(n, channels=3, height=32, width=32,
                                   seed=seed)
        recs = [(b"%08d" % i,
                 Datum(channels=3, height=32, width=32,
                       data=(imgs[i] * 255).astype(np.uint8).tobytes(),
                       label=int(labels[i])).to_binary())
                for i in range(n)]
        LmdbWriter(str(tmp_path / f"cifar10_{split}_lmdb")).write(recs)
    # mean.binaryproto like compute_image_mean
    mean = rng.rand(3, 32, 32).astype(np.float32) * 60
    bp = BlobProto(channels=3, height=32, width=32, num=1,
                   data=[float(v) for v in mean.ravel()])
    (tmp_path / "mean.binaryproto").write_bytes(bp.to_binary())

    npm = read_net(os.path.join(REF, "cifar10_quick_train_test.prototxt"))
    for lp in npm.layer:
        if lp.type != "MemoryData":
            continue
        is_train = any(r.has("phase") and r.phase == Phase.TRAIN
                       for r in lp.include)
        lp.memory_data_param.source = str(
            tmp_path / ("cifar10_train_lmdb" if is_train
                        else "cifar10_test_lmdb"))
    net_path = tmp_path / "net.prototxt"
    net_path.write_text(npm.to_text())

    sp = read_solver(os.path.join(REF, "cifar10_quick_solver.prototxt"))
    sp.net = str(net_path)
    sp.max_iter = 60            # CI budget; shipped config runs 4000
    sp.test_interval = 30
    if sp.test_iter:
        sp.test_iter[0] = 2
    solver_path = tmp_path / "solver.prototxt"
    solver_path.write_text(sp.to_text())

    out = tmp_path / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.caffe_on_spark",
         "-conf", str(solver_path), "-train", "-test",
         "-output", str(out)],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(tmp_path))
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    res = json.loads(open(out / "test_result").read())
    assert "accuracy" in res and np.isfinite(res["accuracy"][0])
    # synthetic separable patterns at 60 iters: should beat chance (0.1)
    assert res["accuracy"][0] > 0.3, res