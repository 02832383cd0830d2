"""Driver API tests: Config flag parity, CaffeOnSpark facade
(train / trainWithValidation / test / features), CLI — the
InterleaveTest / PythonApiTest analogs (SURVEY §4.2, §4.3) on synthetic
MNIST-shaped LMDB data."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from caffeonspark_tpu.caffe_on_spark import (CaffeOnSpark, DataFrame,
                                             vector_mean)
from caffeonspark_tpu.config import Config
from caffeonspark_tpu.data import LmdbWriter, get_source
from caffeonspark_tpu.data.synthetic import make_images
from caffeonspark_tpu.proto.caffe import Datum

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_lmdb(path, n=256, seed=5):
    imgs, labels = make_images(n, seed=seed)
    recs = [(b"%08d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary()) for i in range(n)]
    LmdbWriter(str(path)).write(recs)


NET_TMPL = """
name: "LeNetish"
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  include {{ phase: TRAIN }}
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{train}" batch_size: 16
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  include {{ phase: TEST }}
  source_class: "com.yahoo.ml.caffe.LMDB"
  memory_data_param {{ source: "{test}" batch_size: 16
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param {{ num_output: 12 kernel_size: 5 stride: 2
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "conv1" top: "ip1"
  inner_product_param {{ num_output: 64
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "relu2" type: "ReLU" bottom: "ip1" top: "ip1" }}
layer {{ name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "accuracy" type: "Accuracy" bottom: "ip2" bottom: "label"
  top: "accuracy" include {{ phase: TEST }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }}
"""

SOLVER_TMPL = """
net: "{net}"
test_iter: 4
test_interval: 25
base_lr: 0.01
momentum: 0.9
weight_decay: 0.0005
lr_policy: "inv"
gamma: 0.0001
power: 0.75
display: 25
max_iter: {max_iter}
snapshot: 1000
snapshot_prefix: "lenetish"
random_seed: 42
"""


@pytest.fixture()
def setup(tmp_path):
    _write_lmdb(tmp_path / "train_lmdb", 512, seed=5)
    _write_lmdb(tmp_path / "test_lmdb", 128, seed=99)
    net = tmp_path / "net.prototxt"
    net.write_text(NET_TMPL.format(train=tmp_path / "train_lmdb",
                                   test=tmp_path / "test_lmdb"))
    solver = tmp_path / "solver.prototxt"
    solver.write_text(SOLVER_TMPL.format(net=net, max_iter=150))
    return tmp_path, solver


def test_config_flag_parity(setup):
    tmp, solver = setup
    conf = Config(["-conf", str(solver), "-train", "-persistent",
                   "-devices", "1", "-clusterSize", "1",
                   "-outputFormat", "parquet",
                   "-connection", "ethernet"])
    assert conf.isTraining and conf.isPersistent
    assert conf.outputFormat == "parquet"
    assert conf.solverParameter.max_iter == 150
    assert conf.train_data_layer().memory_data_param.batch_size == 16
    assert conf.test_data_layer() is not None
    assert conf.train_data_layer_id != conf.test_data_layer_id
    conf.validate()


def test_config_state_without_model(setup):
    tmp, solver = setup
    conf = Config(["-conf", str(solver), "-train",
                   "-snapshot", "s.solverstate"])
    with pytest.raises(ValueError, match="state without model"):
        conf.validate()


def test_train_with_validation_interleave(setup):
    """InterleaveTest.scala analog: validation DF columns == (accuracy,
    loss); final accuracy above the reference's own 0.8 bar."""
    tmp, solver = setup
    conf = Config(["-conf", str(solver), "-train",
                   "-output", str(tmp)])
    cos = CaffeOnSpark()
    train_src = get_source(conf.train_data_layer(), phase_train=True,
                           seed=1)
    val_src = get_source(conf.test_data_layer(), phase_train=False,
                         seed=1)
    df = cos.trainWithValidation(train_src, val_src, conf)
    assert set(df.columns) == {"accuracy", "loss"}
    assert len(df) >= 3                      # 100 iters / 25 interval
    final = df.rows[-1]
    assert final["accuracy"] > 0.8, df.rows
    assert final["loss"] < 0.5, df.rows


def test_train_with_validation_interleave_device_transform(
        setup, monkeypatch):
    """The full trainWithValidation choreography under the uint8-infeed
    split — BOTH feeds (train batches through device_prefetch, the
    validation round through eval_step) run the device-side mean/scale
    stage — and clears the same InterleaveTest quality bars."""
    monkeypatch.setenv("COS_DEVICE_TRANSFORM", "1")
    # the processor packs with ITS OWN source objects — spy on the
    # split's host stage to prove BOTH feeds engaged: the train feed
    # (TRAIN-phase transformer) and the validation feed (TEST-phase)
    from caffeonspark_tpu.data.transformer import Transformer
    phases = set()
    orig = Transformer.host_stage

    def spy(self, batch, draw=None):
        phases.add(self.train)
        return orig(self, batch, draw=draw)

    monkeypatch.setattr(Transformer, "host_stage", spy)
    tmp, solver = setup
    conf = Config(["-conf", str(solver), "-train",
                   "-output", str(tmp)])
    cos = CaffeOnSpark()
    train_src = get_source(conf.train_data_layer(), phase_train=True,
                           seed=1)
    val_src = get_source(conf.test_data_layer(), phase_train=False,
                         seed=1)
    df = cos.trainWithValidation(train_src, val_src, conf)
    assert phases == {True, False}, (
        f"both feeds must take the split, saw phases={phases}")
    final = df.rows[-1]
    assert final["accuracy"] > 0.8, df.rows
    assert final["loss"] < 0.5, df.rows


def test_validation_source_identical_across_ranks(setup):
    """The reference feeds every rank the SAME validation data in
    lockstep (CaffeOnSpark.scala:293-302: the one validation partition
    is replicated to every executor via UnionRDDWLocsSpecified).
    validation_source() must therefore yield bit-identical batches for
    every rank of a multi-rank config, while the TRAIN source shards."""
    from caffeonspark_tpu.caffe_on_spark import validation_source
    tmp, solver = setup
    batches = {}
    train_first = {}
    for rank in (0, 1):
        conf = Config(["-conf", str(solver), "-train",
                       "-clusterSize", "2", "-rank", str(rank)])
        vsrc = validation_source(conf)
        assert vsrc is not None
        gen = vsrc.batches(loop=False, shuffle=False)
        batches[rank] = [next(gen) for _ in range(4)]   # test_iter
        tsrc = get_source(conf.train_data_layer(), phase_train=True,
                          rank=rank, num_ranks=2, seed=1)
        train_first[rank] = next(tsrc.batches(loop=False,
                                              shuffle=False))
    for b0, b1 in zip(batches[0], batches[1]):
        assert set(b0) == set(b1)
        for k in b0:
            np.testing.assert_array_equal(b0[k], b1[k])
    # train shards ARE rank-disjoint (different data per rank)
    assert not np.array_equal(train_first[0]["data"],
                              train_first[1]["data"])


def test_features_and_test(setup):
    """PythonApiTest analog: features → SampleID + blob columns;
    test() → accuracy mean > 0.9 after training."""
    tmp, solver = setup
    conf = Config(["-conf", str(solver), "-train",
                   "-output", str(tmp)])
    cos = CaffeOnSpark()
    train_src = get_source(conf.train_data_layer(), phase_train=True,
                           seed=1)
    cos.train(train_src, conf)

    fconf = Config(["-conf", str(solver),
                    "-features", "ip1,ip2", "-label", "label"])
    from caffeonspark_tpu.processor import CaffeProcessor
    proc = CaffeProcessor.instance(fconf)
    # reuse trained weights: load from the final snapshot
    snaps = sorted(os.path.join(str(tmp), p)
                   for p in os.listdir(str(tmp))
                   if p.startswith("lenetish_iter_")
                   and p.endswith(".caffemodel"))
    src = get_source(fconf.test_data_layer(), phase_train=False, seed=1)
    if snaps:
        from caffeonspark_tpu import checkpoint
        proc._init_params()
        proc.params = checkpoint.copy_layers(proc.solver.train_net,
                                             proc.params, snaps[-1])
    df = cos.features2(src, fconf)
    assert df.columns[0] == "SampleID"
    assert "ip1" in df.columns and "ip2" in df.columns
    assert len(df) == 128
    assert df.rows[0]["SampleID"] == "00000000"
    assert len(df.rows[0]["ip1"]) == 64
    assert len(df.rows[0]["ip2"]) == 10



def test_features_with_device_transform(setup, monkeypatch):
    """features2 over a split-enabled source: extract_rows finishes
    the device stage (apply_device_stage), producing features equal to
    the host-transform run."""
    import numpy as np
    tmp, solver = setup
    fconf = Config(["-conf", str(solver),
                    "-features", "ip2", "-label", "label"])
    cos = CaffeOnSpark()
    monkeypatch.delenv("COS_DEVICE_TRANSFORM", raising=False)
    src = get_source(fconf.test_data_layer(), phase_train=False, seed=1)
    df_ref = cos.features2(src, fconf)

    monkeypatch.setenv("COS_DEVICE_TRANSFORM", "1")
    src2 = get_source(fconf.test_data_layer(), phase_train=False, seed=1)
    assert src2.enable_device_transform() is not None
    df = cos.features2(src2, fconf)   # same singleton => same params
    assert len(df) == len(df_ref) and len(df) > 0
    for a, b in zip(df_ref.rows, df.rows):
        assert a["SampleID"] == b["SampleID"]
        np.testing.assert_allclose(b["ip2"], a["ip2"], rtol=1e-6)


def test_vector_mean():
    df = DataFrame([{"v": [1.0, 2.0]}, {"v": [3.0, 4.0]}])
    assert vector_mean(df, "v") == [2.0, 3.0]


def test_dataframe_write_parquet_and_select(tmp_path):
    df = DataFrame([{"SampleID": "a", "f": [1.0, 2.0], "label": 0.0},
                    {"SampleID": "b", "f": [3.0, 4.0], "label": 1.0}])
    p = str(tmp_path / "out.parquet")
    df.write(p, "parquet")
    import pyarrow.parquet as pq
    t = pq.read_table(p)
    assert t.num_rows == 2
    assert set(t.column_names) == {"SampleID", "f", "label"}
    assert t.column("f").to_pylist()[1] == [3.0, 4.0]
    sel = df.select("SampleID", "label")
    assert sel.columns == ["SampleID", "label"]
    assert sel.rows[0] == {"SampleID": "a", "label": 0.0}
    import pytest as _pt
    with _pt.raises(ValueError, match="outputFormat"):
        df.write(str(tmp_path / "x.bad"), "xml")


def test_cli_end_to_end(setup):
    """spark-submit-style CLI: -train + -test in one invocation."""
    tmp, solver = setup
    out = tmp / "out"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.caffe_on_spark",
         "-conf", str(solver), "-train", "-test",
         "-output", str(out)],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=str(tmp))
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    res = json.loads(open(out / "test_result").read())
    assert "accuracy" in res
    assert res["accuracy"][0] > 0.8, res
    vdf = [json.loads(l) for l in
           open(out / "validation.json").read().splitlines()]
    assert vdf and set(vdf[0]) == {"accuracy", "loss"}
