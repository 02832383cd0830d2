"""Real-data convergence gate: the REFERENCE LeNet configs trained on
real handwritten digits, end to end through the CLI.

Reference analogs: `InterleaveTest.scala:36-57` (real MNIST LMDB built
by `scripts/setup-mnist.sh` + `Makefile:23`) and
`PythonApiTest.py:45` (accuracy > 0.9 gate after full train + test).

This image is airgapped, so the data is scikit-learn's bundled real
digit scans (UCI optical digits) packed into MNIST-geometry LMDBs by
`tools/datasets.py::build_digits` — real handwriting, not the
synthetic separable patterns the other driver tests use.  The solver
and net prototxts are the reference's own files with only the LMDB
`source:` paths redirected (the reference hardcodes a developer's
laptop path — its CI rewrites sources the same way) and max_iter
trimmed for the 1-core CI budget.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REF = "/root/reference/data"

pytestmark = pytest.mark.skipif(
    not os.path.exists(os.path.join(REF, "lenet_memory_solver.prototxt")),
    reason="reference configs not present")


def test_reference_lenet_on_real_digits(tmp_path):
    from caffeonspark_tpu.proto import Phase, read_net, read_solver
    from caffeonspark_tpu.tools.datasets import build_digits

    build_digits(str(tmp_path))

    npm = read_net(os.path.join(REF, "lenet_memory_train_test.prototxt"))
    for lp in npm.layer:
        if lp.type != "MemoryData":
            continue
        is_train = any(r.has("phase") and r.phase == Phase.TRAIN
                       for r in lp.include)
        lp.memory_data_param.source = str(
            tmp_path / ("mnist_train_lmdb" if is_train
                        else "mnist_test_lmdb"))
    net_path = tmp_path / "lenet_memory_train_test.prototxt"
    net_path.write_text(npm.to_text())

    sp = read_solver(os.path.join(REF, "lenet_memory_solver.prototxt"))
    sp.net = str(net_path)
    sp.max_iter = 400          # 1-core budget; ref trains 2000
    sp.test_interval = 200
    solver_path = tmp_path / "lenet_memory_solver.prototxt"
    solver_path.write_text(sp.to_text())

    out = tmp_path / "out"
    # single device: the reference's TEST batch (100) doesn't divide
    # over the suite's 8 virtual devices, and the sharding guard
    # correctly rejects that
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
    assert res["accuracy"][0] > 0.9, res
