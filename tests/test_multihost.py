"""Real two-process multi-host training over localhost — the distributed
coverage the reference never had in CI (SURVEY §4: 'no real multi-node
CI test').  Two OS processes, each with one CPU device, join a
jax.distributed cluster through mini_cluster's -server/-cluster/-rank
flags (the caffe_mini_cluster bring-up path) and train data-parallel in
lockstep; rank 0 writes the model."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# slow/e2e: each test boots a 2-process jax.distributed cluster over
# localhost (subprocess spawn + backend init + lockstep train) — tens
# of seconds per test on the CI box.  Run with `-m slow`.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def test_two_process_mini_cluster(tmp_path):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    imgs, labels = make_images(128, seed=3)
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
    solver.write_text(f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
                      'lr_policy: "fixed"\ndisplay: 5\nmax_iter: 10\n'
                      'snapshot_prefix: "mh"\nrandom_seed: 9\n')

    def run_cluster(outdir, extra_env):
        port = _free_port()
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               # baseline runs must NOT inherit the split from the
               # outer shell — parity would compare split vs split
               "COS_DEVICE_TRANSFORM": "",
               "PYTHONPATH": REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""), **extra_env}
        procs = []
        for rank in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
                 "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
                 "-output", str(outdir),
                 "-server", f"127.0.0.1:{port}",
                 "-cluster", "2", "-rank", str(rank)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=REPO))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=520)
            outs.append(out)
        for rank, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {rank}:\n{out[-2000:]}"
        return outs

    outs = run_cluster(tmp_path / "out", {})
    # rank 0 wrote the final model; rank 1 did not
    assert "final model" in outs[0]
    assert "final model" not in outs[1]
    assert os.path.exists(tmp_path / "out" / "mh_iter_10.caffemodel")
    # both ranks trained in lockstep to max_iter
    assert "iter 10/10" in outs[0] and "iter 10/10" in outs[1]

    # same cluster under the uint8-infeed split: the multi-process
    # make_array_from_process_local_data branch carries uint8+aux and
    # the trained model must match the host-transform run
    outs2 = run_cluster(tmp_path / "out2",
                        {"COS_DEVICE_TRANSFORM": "1"})
    assert "iter 10/10" in outs2[0] and "iter 10/10" in outs2[1]
    from caffeonspark_tpu.checkpoint import load_caffemodel_blobs
    a = load_caffemodel_blobs(str(tmp_path / "out" / "mh_iter_10.caffemodel"))
    b = load_caffemodel_blobs(str(tmp_path / "out2" / "mh_iter_10.caffemodel"))
    for k in a:
        for pa, pb in zip(a[k], b[k]):
            np.testing.assert_allclose(np.asarray(pb), np.asarray(pa),
                                       rtol=1e-5, atol=1e-6)


RING_WORKER = r'''
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
rank = int(sys.argv[1])
jax.distributed.initialize(sys.argv[2], 2, rank)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from caffeonspark_tpu.parallel.sp import attention, ring_attention
mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
rng = np.random.RandomState(0)
b, h, t, d = 2, 2, 32, 16
q = rng.randn(b, h, t, d).astype(np.float32)
sh = NamedSharding(mesh, P(None, None, "sp", None))
local = q[:, :, (t // 2) * rank:(t // 2) * (rank + 1), :]
qd = jax.make_array_from_process_local_data(sh, local)
rep = NamedSharding(mesh, P())
out = jax.jit(lambda a: a, out_shardings=rep)(
    ring_attention(qd, qd, qd, mesh, causal=True))
ref = attention(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q),
                causal=True)
fd = float(np.max(np.abs(np.asarray(jax.device_get(out))
                         - np.asarray(ref))))
assert fd < 1e-4, fd
g = jax.grad(lambda a: jnp.sum(
    ring_attention(a, a, a, mesh, causal=True) ** 2))(qd)
gout = jax.jit(lambda a: a, out_shardings=rep)(g)
gref = jax.grad(lambda a: jnp.sum(
    attention(a, a, a, causal=True) ** 2))(jnp.asarray(q))
gd = float(np.max(np.abs(np.asarray(jax.device_get(gout))
                         - np.asarray(gref))))
assert gd < 1e-3, gd
print(f"rank {{rank}} ring fwd-delta {{fd:.2e}} grad-delta {{gd:.2e}} OK")
'''


def test_two_process_ring_attention(tmp_path):
    """Sequence parallelism across REAL process boundaries: a 2-proc
    jax.distributed cluster builds an sp=2 mesh spanning both
    processes and runs ring attention — the K/V ppermute rotation and
    the backward's visitor rotation ride the inter-process transport
    (gloo here, ICI/DCN on a pod).  Forward AND grads must match the
    single-process reference; this is the cross-host long-context
    proof the virtual-mesh tests cannot give."""
    script = tmp_path / "ring_worker.py"
    script.write_text(RING_WORKER.format(repo=REPO))
    port = _free_port()
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), f"127.0.0.1:{port}"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        # a rank that died early leaves its peer blocked in the
        # rendezvous — never orphan it past the test
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-1500:]}"
        assert "OK" in o, f"rank {r}:\n{o[-500:]}"


def test_two_process_tensor_parallel_training(tmp_path):
    """Tensor parallelism across REAL process boundaries: a 2-proc
    cluster with `-mesh 1,2` column-shards the big InnerProduct across
    the processes.  Both ranks must feed IDENTICAL records (the mesh-
    aware dp_data_rank — process-rank sharding would train the model
    shards on inconsistent data), the tp-sharded optimizer state
    writes per-process sidecars, rank 0's collective-gathered dense
    .caffemodel must match a single-process run bit-for-tolerance, and
    resume from the sharded snapshot works."""
    from caffeonspark_tpu.checkpoint import load_caffemodel_blobs
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
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "fc_big" type: "InnerProduct" bottom: "data"
  top: "fc_big"
  inner_product_param {{ num_output: 1024
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "r" type: "ReLU" bottom: "fc_big" top: "fc_big" }}
layer {{ name: "ip" type: "InnerProduct" bottom: "fc_big" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        'lr_policy: "fixed"\nmax_iter: 8\nsnapshot: 4\n'
        'snapshot_prefix: "t"\nrandom_seed: 7\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}

    port = _free_port()
    out = tmp_path / "out"
    procs = [subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-server", f"127.0.0.1:{port}",
         "-cluster", "2", "-rank", str(r), "-mesh", "1,2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-1500:]}"
    # tp-sharded momentum wrote BOTH ranks' sidecars
    assert (out / "t_iter_8.solverstate.shard0").exists()
    assert (out / "t_iter_8.solverstate.shard1").exists()

    # single-process reference: same records, same seeds
    r1 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path / "out1")],
        capture_output=True, text=True, timeout=240, env=env)
    assert r1.returncode == 0, r1.stdout[-800:]
    a = load_caffemodel_blobs(str(out / "t_iter_8.caffemodel"))
    b = load_caffemodel_blobs(str(tmp_path / "out1" /
                                  "t_iter_8.caffemodel"))
    assert a and a.keys() == b.keys(), (sorted(a), sorted(b))
    assert any(len(v) for v in a.values()), "export carried no blobs"
    for k in a:
        assert len(a[k]) == len(b[k]), k
        for pa, pb in zip(a[k], b[k]):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=2e-3, atol=2e-5)

    # resume from the sharded tp snapshot (single process reassembles)
    r2 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path / "out2"),
         "-snapshot", str(out / "t_iter_4.solverstate"),
         "-weights", str(out / "t_iter_4.caffemodel")],
        capture_output=True, text=True, timeout=240, env=env)
    assert r2.returncode == 0 and "resumed from iter 4" in r2.stdout, \
        r2.stdout[-800:]


def test_two_process_expert_parallel_training(tmp_path):
    """Expert parallelism across REAL process boundaries: `-mesh
    1,1,1,2` shards the MoE expert dimension over 2 processes; both
    feed identical records (dp_data_rank), the expert-sharded params
    gather for rank 0's dense export, and the final model matches a
    single-process run."""
    from caffeonspark_tpu.checkpoint import load_caffemodel_blobs
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    imgs, labels = make_images(64, seed=12)
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
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "flat" type: "Flatten" bottom: "data" top: "flat" }}
layer {{ name: "moe" type: "MixtureOfExperts" bottom: "flat" top: "moe"
  moe_param {{ num_experts: 4 hidden_dim: 64 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "moe" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        'lr_policy: "fixed"\nmax_iter: 8\nsnapshot: 100\n'
        'snapshot_prefix: "e"\nrandom_seed: 7\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    port = _free_port()
    out = tmp_path / "out"
    procs = [subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-server", f"127.0.0.1:{port}",
         "-cluster", "2", "-rank", str(r), "-mesh", "1,1,1,2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-1500:]}"

    r1 = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(tmp_path / "out1")],
        capture_output=True, text=True, timeout=240, env=env)
    assert r1.returncode == 0, r1.stdout[-800:]
    a = load_caffemodel_blobs(str(out / "e_iter_8.caffemodel"))
    b = load_caffemodel_blobs(str(tmp_path / "out1" /
                                  "e_iter_8.caffemodel"))
    assert a and a.keys() == b.keys(), (sorted(a), sorted(b))
    assert any(len(v) for v in a.values()), "export carried no blobs"
    for k in a:
        assert len(a[k]) == len(b[k]), k
        for pa, pb in zip(a[k], b[k]):
            np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                       rtol=2e-3, atol=2e-5)


def test_two_process_interleaved_validation(tmp_path):
    """Interleaved validation on the pod path: a 2-proc dp cluster
    whose solver sets test_interval/test_iter runs the eval step in
    LOCKSTEP on both ranks (it is a collective on the mesh) over the
    same replicated validation stream; rank 0 prints the rounds and
    writes validation.json — the driver CLI's trainWithValidation
    artifact, now from supervisor-launched standalone clusters."""
    import json

    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum

    imgs, labels = make_images(96, seed=5)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(96)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  include {{ phase: TRAIN }} source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "tdata" type: "MemoryData" top: "data" top: "label"
  include {{ phase: TEST }} source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 8
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}
layer {{ name: "accuracy" type: "Accuracy" bottom: "ip" bottom: "label"
  top: "accuracy" include {{ phase: TEST }} }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        'lr_policy: "fixed"\nmax_iter: 8\ntest_interval: 4\n'
        'test_iter: 2\nsnapshot: 100\nsnapshot_prefix: "v"\n'
        'random_seed: 5\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
           "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    port = _free_port()
    out = tmp_path / "out"
    procs = [subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-server", f"127.0.0.1:{port}",
         "-cluster", "2", "-rank", str(r)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    try:
        outs = [p.communicate(timeout=240)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r}:\n{o[-1500:]}"
    assert "validation iter 4" in outs[0] and \
        "validation iter 8" in outs[0]
    assert "validation iter" not in outs[1]   # rank-0-only reporting
    rows = [json.loads(l)
            for l in (out / "validation.json").read_text().splitlines()]
    assert len(rows) == 2
    assert set(rows[0]) == {"accuracy", "loss"}
