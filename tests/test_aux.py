"""Aux subsystem tests: tracing, spark gating, examples, CIFAR-10 quick
workload (BASELINE.md parity), and the -profile flag."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from caffeonspark_tpu.utils import StepTimer, profile_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_step_timer():
    t = StepTimer(batch_size=32)
    t.start()
    for _ in range(5):
        time.sleep(0.01)
        t.tick()
    assert t.steps == 5
    assert 0.005 < t.step_time < 0.2
    assert t.records_per_sec > 100
    assert "steps in" in t.summary()


def test_profile_trace_writes(tmp_path):
    import jax.numpy as jnp
    d = str(tmp_path / "trace")
    with profile_trace(d):
        jnp.sum(jnp.ones((100, 100))).block_until_ready()
    assert os.path.isdir(d)
    assert any(os.scandir(d)), "trace directory is empty"
    # no-op path
    with profile_trace(None):
        pass


def test_spark_gating():
    from caffeonspark_tpu import spark
    if spark.spark_available():
        pytest.skip("pyspark installed; gating paths not applicable")
    with pytest.raises(RuntimeError, match="pyspark is not installed"):
        spark.require_spark()
    port = spark.coordinator_port("app-123")
    assert 1024 < port < 65536
    assert port == spark.coordinator_port("app-123")   # deterministic
    # conf pickling round trip (the broadcast analog)
    from caffeonspark_tpu.config import Config
    conf = Config(["-clusterSize", "3", "-devices", "2",
                   "-outputFormat", "parquet"])
    blob = spark._pickle_conf(conf)
    conf2 = spark._unpickle_conf(blob)
    assert conf2.clusterSize == 3
    assert conf2.devices == 2
    assert conf2.outputFormat == "parquet"


def _cifar_fixture(tmp_path):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(256, channels=3, height=32, width=32,
                               seed=8)
    recs = [(b"%06d" % i,
             Datum(channels=3, height=32, width=32,
                   data=(imgs[i] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(256)]
    LmdbWriter(str(tmp_path / "cifar_lmdb")).write(recs)


def test_cifar10_quick_workload(tmp_path):
    """The CIFAR-10 quick benchmark config (BASELINE.md) trains on
    synthetic 32x32x3 data through the unmodified reference net."""
    ref = "/root/reference/data/cifar10_quick_train_test.prototxt"
    if not os.path.exists(ref):
        pytest.skip("reference configs not mounted")
    import jax.numpy as jnp
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.proto import SolverParameter, read_net
    from caffeonspark_tpu.solver import Solver
    _cifar_fixture(tmp_path)
    npm = read_net(ref)
    for lyr in npm.layer:
        if lyr.type == "MemoryData":
            lyr.memory_data_param.source = str(tmp_path / "cifar_lmdb")
            lyr.memory_data_param.batch_size = 32
            lyr.clear("transform_param")   # no mean.binaryproto here
    # cifar10_quick's gaussian std=0.0001 init plateaus ~400 iters while
    # symmetry breaks (the reference trains it 4000 iters); by 700 the
    # loss collapses (measured: 2.30 → 0.04 with shuffled feeding)
    sp = SolverParameter.from_text(
        "base_lr: 0.01 momentum: 0.9 weight_decay: 0.004 "
        "lr_policy: 'fixed' max_iter: 700 random_seed: 4")
    s = Solver(sp, npm)
    src = get_source(s.train_net.data_layers[0], phase_train=True,
                     seed=1)
    params, st = s.init()
    step = s.jit_train_step()
    losses = []
    gen = src.batches(loop=True)
    for i in range(700):
        b = next(gen)
        b = {k: jnp.asarray(v) * (1 / 256.0 if k == "data" else 1.0)
             for k, v in b.items()}
        params, st, out = step(params, st, b, s.step_rng(i))
        losses.append(float(out["loss"]))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_mini_cluster_iter_size(tmp_path):
    """iter_size: 2 through the standalone CLI: feeds 2×batch records
    per optimizer step and completes max_iter steps."""
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(64, seed=13)
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
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\n'
                      'lr_policy: "fixed"\ndisplay: 2\nmax_iter: 6\n'
                      'iter_size: 2\nsnapshot_prefix: "i"\n'
                      'random_seed: 3\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "iter 6/6" in r.stdout
    assert os.path.exists(tmp_path / "i_iter_6.caffemodel")


def test_logistic_regression_example(tmp_path):
    """examples/multiclass_logistic_regression.py end-to-end."""
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(128, seed=12)
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
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 16
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 8 kernel_size: 5 stride: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "conv1" top: "ip1"
  inner_product_param {{ num_output: 32
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\nmomentum: 0.9\n'
                      'lr_policy: "fixed"\nmax_iter: 40\n'
                      'snapshot_prefix: "m"\nrandom_seed: 6\n')
    sys.path.insert(0, os.path.join(REPO, "examples"))
    try:
        import multiclass_logistic_regression as ex
        acc = ex.main(["-conf", str(solver), "-features", "ip1",
                       "-label", "label"])
    finally:
        sys.path.pop(0)
    # untrained conv features of the synthetic gratings still beat
    # 10-class chance (0.1) by a wide margin
    assert acc > 0.25, acc


def test_long_context_example(tmp_path):
    """examples/long_context.py end-to-end on the virtual mesh:
    sequence-parallel transformer training, parity line asserted
    inside the script."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "examples/long_context.py", "16"],
        capture_output=True, text=True, timeout=520, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-800:])
    assert "matches the single-device step" in r.stdout
    assert "fused ring attention trains end to end" in r.stdout
