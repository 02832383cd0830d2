"""Checkpoint/resume/finetune tests + the mini_cluster CLI end-to-end."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu import checkpoint
from caffeonspark_tpu.data.synthetic import batches, make_images
from caffeonspark_tpu.proto import (NetParameter, SolverParameter)
from caffeonspark_tpu.proto.caffe import Datum, SnapshotFormat
from caffeonspark_tpu.solver import Solver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NET = """
name: "tiny"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 8 channels: 1 height: 12 width: 12 } }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 4 kernel_size: 3
    weight_filler { type: "xavier" } } }
layer { name: "relu" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "ip" type: "InnerProduct" bottom: "conv1" top: "ip"
  inner_product_param { num_output: 10 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label"
  top: "loss" }
"""

SOLVER = """
base_lr: 0.01
momentum: 0.9
lr_policy: "fixed"
max_iter: 50
random_seed: 5
"""


def _trained(iters=5):
    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(NET))
    params, st = s.init()
    step = s.jit_train_step()
    gen = batches(64, 8, seed=1, scale=1 / 256.0, height=12, width=12)
    for i in range(iters):
        d, l = next(gen)
        params, st, _ = step(params, st,
                             {"data": jnp.asarray(d),
                              "label": jnp.asarray(l)}, s.step_rng(i))
    return s, params, st


@pytest.mark.parametrize("fmt", [SnapshotFormat.BINARYPROTO,
                                 SnapshotFormat.HDF5])
def test_snapshot_restore_round_trip(tmp_path, fmt):
    s, params, st = _trained()
    prefix = str(tmp_path / "snap")
    model_path, state_path = checkpoint.snapshot(
        s.train_net, params, st, prefix, fmt=fmt)
    assert f"_iter_5." in model_path
    assert os.path.exists(model_path) and os.path.exists(state_path)

    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(NET))
    p2, st2 = s2.init()
    p2, st2 = checkpoint.restore(s2.train_net, p2, st2, state_path)
    assert int(jax.device_get(st2.iter)) == 5
    for ln in params:
        for bn in params[ln]:
            np.testing.assert_allclose(
                np.asarray(jax.device_get(params[ln][bn])),
                np.asarray(jax.device_get(p2[ln][bn])), rtol=1e-6)
            np.testing.assert_allclose(
                np.asarray(jax.device_get(st.history[ln][bn])),
                np.asarray(jax.device_get(st2.history[ln][bn])),
                rtol=1e-6)
    # training continues identically after resume
    step1 = s.jit_train_step()
    step2 = s2.jit_train_step()
    gen = batches(64, 8, seed=2, scale=1 / 256.0, height=12, width=12)
    d, l = next(gen)
    b = {"data": jnp.asarray(d), "label": jnp.asarray(l)}
    pa, _, o1 = step1(params, st, b, s.step_rng(5))
    pb, _, o2 = step2(p2, st2, b, s2.step_rng(5))
    assert float(o1["loss"]) == pytest.approx(float(o2["loss"]), rel=1e-6)


def test_async_snapshotter(tmp_path):
    """Write-behind snapshot: submit returns before the write, wait()
    lands it, the on-disk state equals a synchronous snapshot, and a
    failing write surfaces on wait()."""
    s, params, st = _trained()
    snapper = checkpoint.AsyncSnapshotter()
    done = snapper.submit(s.train_net, params, st,
                          str(tmp_path / "async_snap"))
    snapper.wait()
    assert done.is_set()
    state_path = str(tmp_path / "async_snap_iter_5.solverstate")
    assert os.path.exists(state_path)
    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(NET))
    p2, st2 = s2.init()
    p2, st2 = checkpoint.restore(s2.train_net, p2, st2, state_path)
    for ln in params:
        for bn in params[ln]:
            np.testing.assert_allclose(
                np.asarray(jax.device_get(params[ln][bn])),
                np.asarray(p2[ln][bn]), rtol=1e-6)
    # the submitted copy is decoupled from later in-place training
    done2 = snapper.submit(s.train_net, params, st,
                           str(tmp_path / "snap2"))
    snapper.wait()
    assert done2.is_set()
    # error path: unwritable destination surfaces on wait, not silently
    snapper.submit(s.train_net, params, st,
                   "/proc/definitely/not/writable/snap")
    with pytest.raises(RuntimeError, match="async snapshot failed"):
        snapper.wait()


def test_async_snapshot_cli_flag(tmp_path):
    """-async_snapshot through the driver trains, snapshots land, and
    resume from the async-written state works."""
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter
    imgs, labels = make_images(64, height=12, width=12, seed=3)
    recs = [(b"%08d" % i,
             Datum(channels=1, height=12, width=12,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary()) for i in range(64)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = NET.replace(
        'memory_data_param { batch_size: 8',
        f'source_class: "LMDB" memory_data_param {{ '
        f'source: "{tmp_path / "lmdb"}" batch_size: 8')
    (tmp_path / "net.prototxt").write_text(net)
    (tmp_path / "solver.prototxt").write_text(
        SOLVER + f'net: "{tmp_path / "net.prototxt"}"\n'
        'snapshot: 20\nsnapshot_prefix: "m"\nmax_iter: 40\n')
    conf = Config(["-conf", str(tmp_path / "solver.prototxt"), "-train",
                   "-async_snapshot", "-output", str(tmp_path)])
    assert conf.asyncSnapshot
    from caffeonspark_tpu.data import get_source
    src = get_source(conf.train_data_layer(), phase_train=True, seed=5)
    CaffeOnSpark().train(src, conf)
    state = tmp_path / "m_iter_40.solverstate"
    assert state.exists() and (tmp_path / "m_iter_20.solverstate").exists()
    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(NET))
    p2, st2 = s2.init()
    _, st2 = checkpoint.restore(s2.train_net, p2, st2, str(state))
    assert int(jax.device_get(st2.iter)) == 40


BIG_NET = """
name: "bigip"
layer { name: "data" type: "MemoryData" top: "data" top: "label"
  memory_data_param { batch_size: 16 channels: 1 height: 16 width: 16 } }
layer { name: "flat" type: "Flatten" bottom: "data" top: "flat" }
layer { name: "fc_big" type: "InnerProduct" bottom: "flat" top: "fc_big"
  inner_product_param { num_output: 128
    weight_filler { type: "xavier" } } }
layer { name: "r" type: "ReLU" bottom: "fc_big" top: "fc_big" }
layer { name: "ip" type: "InnerProduct" bottom: "fc_big" top: "ip"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label"
  top: "loss" }
"""


def test_sharded_state_snapshot_roundtrip(tmp_path):
    """ZeRO/multi-host sharded-state checkpointing: state blobs that
    would not be addressable from one host write per-process shard
    SIDECARS (npz slabs) next to a marker-carrying .solverstate, and
    restore() reassembles the full state bit-for-bit.  force_shards
    exercises the exact multi-host format on this single process
    (where the 8 dp shards are all local); the real 2-process leg is
    tests/test_multihost_recovery.py's COS_ZERO drill."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.proto.caffe import SolverState

    mesh = build_mesh(dp=8)
    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(BIG_NET))
    ps = ParallelSolver(s, mesh, zero_dp=True)
    assert "dp" in tuple(ps.state_specs["fc_big"]["weight"])
    params, st = ps.init()
    step = ps.train_step()
    gen = batches(64, 16, seed=1, scale=1 / 256.0, height=16, width=16)
    for i in range(3):
        d, l = next(gen)
        params, st, _ = step(params, st,
                             ps.shard_batch({"data": jnp.asarray(d),
                                             "label": jnp.asarray(l)}),
                             s.step_rng(i))
    want_m = np.asarray(jax.device_get(st.history["fc_big"]["weight"]),
                        np.float32)

    prefix = str(tmp_path / "z")
    m, spath = checkpoint.snapshot(s.train_net, params, st, prefix,
                                   solver_type=s.solver_type,
                                   force_shards=True)
    # marker blobs in the solverstate, slabs in the sidecar
    raw = SolverState.from_binary(open(spath, "rb").read())
    assert any(bp.shape.dim and not len(bp.data) for bp in raw.history)
    assert os.path.exists(spath + ".shard0")

    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(BIG_NET))
    p2, st2 = s2.init()
    p2, st2 = checkpoint.restore(s2.train_net, p2, st2, spath,
                                 weights_path=m)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(st2.history["fc_big"]["weight"]),
                   np.float32), want_m, rtol=0, atol=0)
    assert int(jax.device_get(st2.iter)) == 3

    # resumed trajectory continues identically to the unsharded resume
    p2 = ps.shard_params(p2)
    st2 = ps.shard_opt_state(st2)
    d, l = next(gen)
    batch = ps.shard_batch({"data": jnp.asarray(d),
                            "label": jnp.asarray(l)})
    pa, sta, outa = step(params, st, batch, s.step_rng(3))
    pb, stb, outb = step(p2, st2, batch, s.step_rng(3))
    assert float(outa["loss"]) == pytest.approx(float(outb["loss"]),
                                                rel=1e-5)

    # a missing sidecar must fail loudly, not restore zeros
    os.unlink(spath + ".shard0")
    p3, st3 = s2.init()
    with pytest.raises(FileNotFoundError, match="sidecar"):
        checkpoint.restore(s2.train_net, p3, st3, spath, weights_path=m)


def test_sharded_snapshot_elastic_reshard_resume(tmp_path):
    """Elastic resume: a ZeRO snapshot taken on one mesh size restores
    onto a DIFFERENT mesh size (dp8 → dp4) — restore() reassembles the
    full state from the sidecars and ParallelSolver re-shards it for
    the new mesh; the resumed trajectory matches the original run
    continued on its own mesh."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh

    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(BIG_NET))
    ps8 = ParallelSolver(s, build_mesh(dp=8), zero_dp=True)
    params, st = ps8.init()
    step8 = ps8.train_step()
    gen = batches(64, 16, seed=2, scale=1 / 256.0, height=16, width=16)
    for i in range(3):
        d, l = next(gen)
        batch = {"data": jnp.asarray(d), "label": jnp.asarray(l)}
        params, st, _ = step8(params, st, ps8.shard_batch(batch),
                              s.step_rng(i))
    prefix = str(tmp_path / "el")
    m, spath = checkpoint.snapshot(s.train_net, params, st, prefix,
                                   solver_type=s.solver_type,
                                   force_shards=True)

    d, l = next(gen)
    nxt = {"data": jnp.asarray(d), "label": jnp.asarray(l)}
    _, _, out8 = step8(params, st, ps8.shard_batch(nxt), s.step_rng(3))

    # resume on HALF the data-parallel width
    s4 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(BIG_NET))
    ps4 = ParallelSolver(s4, build_mesh(dp=4, devices=jax.devices()[:4]),
                         zero_dp=True)
    p4, st4 = s4.init()
    p4, st4 = checkpoint.restore(s4.train_net, p4, st4, spath,
                                 weights_path=m)
    p4 = ps4.shard_params(p4)
    st4 = ps4.shard_opt_state(st4)
    assert "dp" in tuple(st4.history["fc_big"]["weight"].sharding.spec)
    _, _, out4 = ps4.train_step()(p4, st4, ps4.shard_batch(nxt),
                                  s4.step_rng(3))
    assert float(out8["loss"]) == pytest.approx(float(out4["loss"]),
                                                rel=2e-4)


def test_sharded_state_async_snapshot_roundtrip(tmp_path):
    """AsyncSnapshotter × ZeRO through the REAL submit() API: the
    host copy (incl. per-process shard slabs, force_shards) must
    materialize eagerly at submit time — the train loop donates the
    live buffers on its next step while the worker thread is still
    writing — and the write-behind snapshot must produce the same
    reassemblable sidecar format as the sync path."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh

    mesh = build_mesh(dp=8)
    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(BIG_NET))
    ps = ParallelSolver(s, mesh, zero_dp=True)
    params, st = ps.init()
    step = ps.train_step()
    gen = batches(64, 16, seed=3, scale=1 / 256.0, height=16, width=16)
    d, l = next(gen)
    params, st, _ = step(params, st,
                         ps.shard_batch({"data": jnp.asarray(d),
                                         "label": jnp.asarray(l)}),
                         s.step_rng(0))
    want = np.asarray(jax.device_get(st.history["fc_big"]["weight"]),
                      np.float32)
    prefix = str(tmp_path / "az")
    snapper = checkpoint.AsyncSnapshotter()
    snapper.submit(s.train_net, params, st, prefix,
                   solver_type=s.solver_type, force_shards=True)
    # donate the ORIGINAL buffers immediately — the submit-time host
    # copy is what protects the in-flight write
    d, l = next(gen)
    step(params, st, ps.shard_batch({"data": jnp.asarray(d),
                                     "label": jnp.asarray(l)}),
         s.step_rng(1))
    snapper.wait()
    spath = checkpoint.snapshot_filename(prefix, 1, is_state=True)
    m = checkpoint.snapshot_filename(prefix, 1, is_state=False)
    assert os.path.exists(spath + ".shard0"), "sidecar from submit()"
    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(BIG_NET))
    p2, st2 = s2.init()
    p2, st2 = checkpoint.restore(s2.train_net, p2, st2, spath,
                                 weights_path=m)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(st2.history["fc_big"]["weight"]),
                   np.float32), want, rtol=0, atol=0)


def test_zero1_composes_with_iter_size():
    """ZeRO × gradient accumulation: iter_size>1 accumulates inside
    the jitted step while the state stays dp-sharded — the trajectory
    must match the single-device iter_size step."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh

    sp_txt = SOLVER + "iter_size: 2\n"
    s1 = Solver(SolverParameter.from_text(sp_txt),
                NetParameter.from_text(BIG_NET))
    p1, st1 = s1.init()
    step1 = s1.jit_train_step()

    sz = Solver(SolverParameter.from_text(sp_txt),
                NetParameter.from_text(BIG_NET))
    ps = ParallelSolver(sz, build_mesh(dp=8), zero_dp=True)
    pz, stz = ps.init()
    stepz = ps.train_step()
    gen = batches(64, 32, seed=5, scale=1 / 256.0, height=16, width=16)
    for i in range(2):
        d, l = next(gen)
        batch = {"data": jnp.asarray(d), "label": jnp.asarray(l)}
        p1, st1, out1 = step1(p1, st1, batch, s1.step_rng(i))
        pz, stz, outz = stepz(pz, stz, ps.shard_batch(batch),
                              sz.step_rng(i))
        assert float(out1["loss"]) == pytest.approx(
            float(outz["loss"]), rel=2e-4), i
    assert "dp" in tuple(stz.history["fc_big"]["weight"].sharding.spec)


def test_sharded_state_write_main_false_writes_only_sidecar(tmp_path):
    """The non-rank-0 multi-host call: write_main=False leaves no
    model/solverstate (rank 0 owns those), only this process's shard
    sidecar."""
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh

    mesh = build_mesh(dp=8)
    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(BIG_NET))
    ps = ParallelSolver(s, mesh, zero_dp=True)
    params, st = ps.init()
    prefix = str(tmp_path / "nr")
    m, spath = checkpoint.snapshot(s.train_net, params, st, prefix,
                                   solver_type=s.solver_type,
                                   write_main=False, force_shards=True)
    assert not os.path.exists(m) and not os.path.exists(spath)
    assert os.path.exists(spath + ".shard0")


def test_finetune_copy_layers(tmp_path):
    s, params, st = _trained()
    mp = str(tmp_path / "weights.caffemodel")
    checkpoint.save_caffemodel(mp, s.train_net, params)
    # a DIFFERENT net sharing conv1 but with a new head
    net2 = NET.replace('num_output: 10', 'num_output: 3').replace(
        '"tiny"', '"tiny2"')
    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(net2))
    p2, _ = s2.init()
    p3 = checkpoint.copy_layers(s2.train_net, p2, mp)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(params["conv1"]["weight"])),
        np.asarray(jax.device_get(p3["conv1"]["weight"])), rtol=1e-6)
    # mismatched head untouched
    np.testing.assert_allclose(
        np.asarray(jax.device_get(p2["ip"]["weight"])),
        np.asarray(jax.device_get(p3["ip"]["weight"])))


def test_v1_legacy_caffemodel_import(tmp_path):
    """Published legacy models use the deprecated V1 `layers` field;
    copy_layers must import their blobs by name."""
    from caffeonspark_tpu.proto.caffe import (BlobProto, BlobShape,
                                              NetParameter as NP,
                                              V1LayerParameter)
    s, params, st = _trained()
    w = np.asarray(jax.device_get(params["conv1"]["weight"]))
    legacy = NP(name="legacy")
    v1 = V1LayerParameter(name="conv1", type=4)   # 4 = Convolution
    v1.blobs.append(BlobProto(
        shape=BlobShape(dim=list(w.shape)), data=w.ravel()))
    legacy.layers.append(v1)
    mp = tmp_path / "legacy.caffemodel"
    mp.write_bytes(legacy.to_binary())

    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(NET))
    p2, _ = s2.init()
    p3 = checkpoint.copy_layers(s2.train_net, p2, str(mp))
    np.testing.assert_allclose(
        np.asarray(jax.device_get(p3["conv1"]["weight"])), w, rtol=1e-6)
    assert V1LayerParameter(type=4).type_name() == "Convolution"


def test_state_without_model_errors(tmp_path):
    s, params, st = _trained()
    prefix = str(tmp_path / "x")
    model_path, state_path = checkpoint.snapshot(s.train_net, params, st,
                                                prefix)
    os.unlink(model_path)
    s2 = Solver(SolverParameter.from_text(SOLVER),
                NetParameter.from_text(NET))
    p2, st2 = s2.init()
    with pytest.raises(ValueError, match="state without model"):
        checkpoint.restore(s2.train_net, p2, st2, state_path)


def test_kill9_recovery_from_snapshot(tmp_path):
    """Failure recovery (SURVEY §5.3): SIGKILL a trainer mid-run, resume
    from the last periodic snapshot, training completes."""
    from caffeonspark_tpu.data import LmdbWriter
    imgs, labels = make_images(64, seed=21)
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
                      'lr_policy: "fixed"\ndisplay: 100\n'
                      'max_iter: 100000\nsnapshot: 20\n'
                      'snapshot_prefix: "k"\nrandom_seed: 4\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO}
    import signal, time
    p = subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO)
    # wait for at least one periodic snapshot, then hard-kill
    deadline = time.time() + 240
    snap = None
    while time.time() < deadline:
        snaps = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("k_iter_")
                       and f.endswith(".solverstate"))
        if snaps:
            snap = snaps[-1]
            break
        time.sleep(0.5)
    assert snap, "no periodic snapshot appeared"
    time.sleep(1.0)
    p.send_signal(signal.SIGKILL)
    p.wait(timeout=60)
    assert p.returncode != 0          # died hard, no graceful shutdown

    # resume from the surviving snapshot and finish a short run
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(tmp_path),
         "-snapshot", str(tmp_path / snap), "-iterations", "60"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=REPO)
    assert r.returncode == 0, r.stdout[-1500:]
    it0 = int(snap.split("_iter_")[1].split(".")[0])
    assert f"resumed from iter {it0}" in r.stdout
    assert "final model" in r.stdout


def test_mini_cluster_cli(tmp_path):
    """The standalone CLI trainer end-to-end on an LMDB."""
    from caffeonspark_tpu.data import LmdbWriter
    imgs, labels = make_images(64, seed=3)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(64)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)

    solver_txt = tmp_path / "solver.prototxt"
    net_txt = tmp_path / "net.prototxt"
    # the reference's lenet_memory_train_test.prototxt: LeNet fed from
    # an LMDB whose path the command line gives
    from caffeonspark_tpu.models import zoo
    lenet = zoo.lenet(batch_size=8)
    lenet.layer[0].source_class = "com.yahoo.ml.caffe.LMDB"
    net_txt.write_text(lenet.to_text())
    solver_txt.write_text(f"""
net: "{net_txt}"
base_lr: 0.01
momentum: 0.9
lr_policy: "inv"
gamma: 0.0001
power: 0.75
display: 5
max_iter: 12
snapshot: 10
snapshot_prefix: "m"
random_seed: 7
""")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver_txt), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "iter 10/12" in r.stdout or "iter 5/12" in r.stdout
    assert os.path.exists(tmp_path / "m_iter_10.caffemodel")
    assert "final model" in r.stdout
    # resume from the snapshot
    r2 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver_txt), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path),
         "-snapshot", str(tmp_path / "m_iter_10.solverstate"),
         "-iterations", "15"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=REPO)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from iter 10" in r2.stdout


# ---------------------------------------------------------------------------
# atomic snapshot writes (deploy canary / pick_snapshot safety)
# ---------------------------------------------------------------------------

def test_atomic_write_local_crash_keeps_previous(tmp_path):
    """A write that dies mid-tmp leaves the previous complete file in
    place and no target mutation — the local-snapshot atomicity the
    canary and pick_snapshot lean on."""
    from caffeonspark_tpu.utils import fsutils
    target = tmp_path / "m.caffemodel"
    fsutils.write_bytes(str(target), b"v1" * 100)

    def crash_mid_write(tmp):
        with open(tmp, "wb") as f:
            f.write(b"v2")                # partial
        raise KeyboardInterrupt("writer died mid-save")

    with pytest.raises(KeyboardInterrupt):
        fsutils.atomic_write_local(str(target), crash_mid_write)
    assert target.read_bytes() == b"v1" * 100
    # the failed tmp is cleaned up, and snapshot discovery would have
    # ignored it anyway (`.tmp.` never matches the pair patterns)
    assert [p.name for p in tmp_path.iterdir()] == ["m.caffemodel"]


_KILL_WRITER = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax.numpy as jnp
from caffeonspark_tpu import checkpoint
from caffeonspark_tpu.proto import NetParameter, SolverParameter
from caffeonspark_tpu.solver import Solver

# a deliberately fat ip blob so each snapshot write has a real kill
# window (~8 MB model + same-size momentum state)
net = NetParameter.from_text({net!r}.replace(
    "num_output: 10", "num_output: 4096", 1))
s = Solver(SolverParameter.from_text({solver!r}), net)
params, st = s.init()
out = {out!r}
print("WRITER READY", flush=True)
for i in range(200):
    st = st._replace(iter=jnp.asarray(i + 1, jnp.int32))
    checkpoint.snapshot(s.train_net, params, st, out + "/model")
    print("WROTE", i + 1, flush=True)
"""


@pytest.mark.slow
@pytest.mark.chaos
def test_snapshot_kill_mid_save_previous_pair_survives(tmp_path):
    """SIGKILL a snapshot writer while a pair write is in flight: no
    discovered pair may ever be truncated — pick_snapshot's newest
    pair must restore cleanly (the deploy fine-tune/canary contract).
    The kill is aimed at the tmp-file window (the only window that
    exists now that every file lands via tmp+rename)."""
    import re
    import signal
    import time
    from caffeonspark_tpu.tools.supervisor import (find_snapshots,
                                                   pick_snapshot)
    out = tmp_path / "snaps"
    out.mkdir()
    script = tmp_path / "writer.py"
    script.write_text(_KILL_WRITER.format(
        repo=REPO, net=NET, solver=SOLVER, out=str(out)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    p = subprocess.Popen([sys.executable, str(script)],
                         stdout=subprocess.PIPE, text=True, env=env)
    try:
        # wait until at least one complete pair landed, then kill the
        # instant a NEW in-flight tmp file appears (mid-write window)
        deadline = time.time() + 240
        killed = False
        while time.time() < deadline and p.poll() is None:
            names = os.listdir(out)
            pairs = find_snapshots(str(out), "model")
            tmps = [n for n in names if ".tmp." in n]
            if len(pairs) >= 1 and tmps:
                p.send_signal(signal.SIGKILL)
                killed = True
                break
            time.sleep(0.001)
        assert killed, "never caught an in-flight tmp write"
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    pairs = find_snapshots(str(out), "model")
    assert pairs, "no complete pair survived"
    # every DISCOVERED pair parses and restores end to end — a
    # truncated file may exist only under a .tmp. name
    s = Solver(SolverParameter.from_text(SOLVER),
               NetParameter.from_text(NET.replace(
                   "num_output: 10", "num_output: 4096", 1)))
    params, st = s.init()
    for state_path, model_path in pairs:
        checkpoint.load_caffemodel_blobs(model_path)
        checkpoint.restore(s.train_net, params, st, state_path,
                           weights_path=model_path)
    picked = pick_snapshot(str(out), "model")
    assert picked == pairs[0]
    leftovers = [n for n in os.listdir(out) if ".tmp." in n]
    # the killed write's orphan tmp (if any) is invisible to discovery
    assert all(not re.match(r"model_iter_\d+\.(caffemodel|solverstate)$",
                            n) for n in leftovers)
