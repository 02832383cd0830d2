"""Device mesh construction + multi-host bootstrap.

TPU-native replacement for the reference's entire connection machinery:
RDMA/socket server address exchange via Spark collect
(`CaffeOnSpark.scala:113-142`), `SocketChannel::Connect` retries
(`socket.cpp:242-281`), and TCP `MiniCluster::AllGather` rank assignment
(`mini_cluster.cpp:22-66`) all collapse into `jax.distributed.initialize`
(coordinator address = the "server" flag) plus a named `Mesh`.  The
cluster barrier (`CaffeNet::sync`, `socket_sync.cpp:156-183`) is implicit
in every SPMD collective.

Mesh axes:
  dp — data parallel (batch sharding, gradient pmean)
  tp — tensor parallel (weight sharding on large InnerProducts)
  sp — sequence parallel (ring attention / long-context)
  pp — pipeline parallel (stage-partitioned nets)
  ep — expert parallel (MixtureOfExperts expert-dim sharding)
Axes of size 1 cost nothing; lay dp innermost-last so its collectives
ride ICI neighbors first.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("pp", "ep", "sp", "tp", "dp")


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """'dp[,tp[,sp[,ep]]]' → build_mesh kwargs; rejects extra dims
    instead of silently dropping them.  Any token may instead be a
    named 'axis=N' dim ('pp=4', 'tp=2,pp=2', '2,2,pp=2') — the only
    spelling for the pp axis, which has no positional slot.  Shared by
    the training CLI (-mesh) and the serving CLI (-serveMesh)."""
    names = ["dp", "tp", "sp", "ep"]
    out: Dict[str, int] = {}
    pos = 0
    for tok in spec.split(","):
        tok = tok.strip()
        if "=" in tok:
            name, _, val = tok.partition("=")
            name = name.strip()
            if name not in AXES:
                raise ValueError(
                    f"mesh spec {spec!r}: unknown axis {name!r} "
                    f"(axes: {','.join(AXES)})")
            dim = int(val)
        else:
            if pos >= len(names):
                raise ValueError(
                    f"mesh spec {spec!r} has more than {len(names)} "
                    f"positional dims ({','.join(names)})")
            name = names[pos]
            pos += 1
            dim = int(tok)
        if name in out:
            raise ValueError(
                f"mesh spec {spec!r}: axis {name!r} given twice")
        if dim < 1:
            raise ValueError(
                f"mesh spec {spec!r}: axis {name!r} must be >= 1, "
                f"got {dim}")
        out[name] = dim
    return out


def distributed_init(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bootstrap (the address-exchange / rank-assignment
    analog).  No-op for single-process runs.

    `coordinator` is normally `host:port`; the `agent://host:port`
    form instead asks the NodeAgent at that address for the rendezvous
    (GET /v1/coordinator) — the LEAD agent allocates one coordinator
    port and hands every rank the same answer, so a cross-host job
    needs no hand-picked port, only the lead agent's address."""
    if coordinator is None:
        return
    if coordinator.startswith("agent://"):
        from ..tools.nodeagent import resolve_coordinator
        coordinator = resolve_coordinator(coordinator)
    # CPU backends need the gloo collectives implementation for real
    # cross-process collectives (the default CPU client rejects
    # "multiprocess computations"): the multihost failure drills and
    # the lockstep leg of scripts/bench_syncmode.py run 2-4 CPU ranks
    # through here.  Must be set BEFORE the backend initializes; inert
    # on accelerator backends.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def build_mesh(*, dp: Optional[int] = None, tp: int = 1, sp: int = 1,
               pp: int = 1, ep: int = 1, devices=None) -> Mesh:
    """Mesh over all devices with named axes (pp, ep, sp, tp, dp); dp is
    inferred as the remainder when unset."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = tp * sp * pp * ep
    if n % fixed != 0:
        raise ValueError(
            f"{n} devices not divisible by tp*sp*pp*ep={fixed}")
    if dp is None:
        dp = n // fixed
    if dp * fixed != n:
        raise ValueError(f"dp*tp*sp*pp*ep={dp * fixed} != {n} devices")
    arr = np.asarray(devices).reshape(pp, ep, sp, tp, dp)
    return Mesh(arr, AXES)


def data_sharding(mesh: Mesh, batch_axis: int = 0) -> NamedSharding:
    """Shard the batch dimension across dp AND sp together — for pure
    data parallelism on a mesh that also carries an sp axis, both axes
    consume the global batch so no devices idle."""
    spec = [None] * (batch_axis + 1)
    spec[batch_axis] = ("dp", "sp") if mesh.shape.get("sp", 1) > 1 \
        else "dp"
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def dp_data_rank(mesh: Mesh) -> tuple:
    """(data_rank, data_num_ranks) for THIS process: which shard of
    the record stream it must feed.

    Derived from the mesh coordinates of the local devices, NOT the
    process rank — on a tp/sp-only mesh every process sits at dp
    index 0 and must feed IDENTICAL records (its model shard consumes
    the same replicated batch), while the process-rank sharding the
    cluster flags imply would feed each rank different data and
    silently train on inconsistent replicas.  Single-process meshes
    feed the whole stream (device_prefetch shards locally)."""
    if jax.process_count() <= 1:
        return 0, 1
    dp_total = mesh.shape.get("dp", 1)
    if dp_total <= 1:
        return 0, 1
    axes = list(mesh.axis_names)
    dp_axis = axes.index("dp")
    local_ids = {d.id for d in jax.local_devices()}
    rows = sorted({idx[dp_axis]
                   for idx in np.ndindex(mesh.devices.shape)
                   if mesh.devices[idx].id in local_ids})
    k = len(rows)
    if (k and rows == list(range(rows[0], rows[0] + k))
            and dp_total % k == 0 and rows[0] % k == 0):
        return rows[0] // k, dp_total // k
    # non-contiguous local dp rows (exotic device order): feed the
    # whole stream rather than misalign the local shard
    return 0, 1


# ---------------------------------------------------------------------------
# named-axis layouts (param/input spec construction)
#
# THE one spec-construction path: ParallelSolver (training) and
# BlobForward (serving / batch extract / validation) both consume
# MeshLayout, so a net's tp/ep partitioning can never diverge between
# the step that trains the weights and the forward that serves them.
# ---------------------------------------------------------------------------

TP_MIN_FEATURES = 1024  # shard only matmuls big enough to matter


def tp_param_specs(net, *, min_features: int = TP_MIN_FEATURES
                   ) -> Dict[str, Dict[str, P]]:
    """PartitionSpec per param blob: column-shard large IP/Embed weights
    over 'tp', replicate the rest (Megatron-style split on num_output)."""
    specs: Dict[str, Dict[str, P]] = {}
    by_name = {lp.name: lp for lp in net.compute_layers}
    for lname, blobs in net.param_layout.items():
        lp = by_name[lname]
        specs[lname] = {}
        for bname, shape, _ in blobs:
            spec = P()
            if lp.type == "InnerProduct" and bname == "weight":
                ipp = lp.inner_product_param
                n_out = int(ipp.num_output)
                if n_out >= min_features and not ipp.transpose:
                    spec = P("tp", None)     # (num_output, K) column split
                elif n_out >= min_features:
                    spec = P(None, "tp")
            elif lp.type == "InnerProduct" and bname == "bias":
                if int(lp.inner_product_param.num_output) >= min_features:
                    spec = P("tp")
            elif lp.type == "Embed" and bname == "weight":
                if int(lp.embed_param.num_output) >= min_features:
                    spec = P(None, "tp")     # (vocab, dim) dim split
            elif lp.type in ("LSTM", "RNN") and bname.startswith("W_x"):
                rp = lp.recurrent_param
                if int(rp.num_output) * 4 >= min_features:
                    spec = P("tp", None)     # (4N, D) gate split
            elif lp.type == "MixtureOfExperts" and bname in (
                    "W1", "W2", "W_gate", "W_up", "W_down"):
                # expert-dim split: the capacity dispatch's W1/W2 and
                # the dropless dispatch's gated experts alike (router,
                # selection bias and shared experts stay replicated)
                spec = P("ep", None, None)
            # every other type's blobs stay replicated: a head or
            # channel split is written for none of them, and a new type
            # needs no entry here to be right.  A blob shared under a
            # name (`param { name: .. }`, a tied embedding) is its
            # owner's entry of `param_layout` and has that one spec;
            # the layers that read it take it as laid out
            specs[lname][bname] = spec
    return specs


def validate_param_specs(specs: Dict[str, Dict[str, P]],
                         shapes: Dict[str, Dict[str, tuple]],
                         mesh: Mesh) -> None:
    """Divisibility guard: every sharded param dim must divide by its
    mesh axis (an opaque XLA partition error otherwise)."""
    for ln, blobs in specs.items():
        for bn, spec in blobs.items():
            for dim_i, ax in enumerate(spec):
                if ax is None:
                    continue
                size = mesh.shape.get(ax, 1)
                dim = shapes[ln][bn][dim_i]
                if size > 1 and dim % size != 0:
                    raise ValueError(
                        f"layer {ln!r} blob {bn!r}: dim {dim_i} "
                        f"(size {dim}) not divisible by mesh axis "
                        f"{ax!r} (size {size}) — adjust "
                        f"num_experts/num_output or the mesh")


class MeshLayout:
    """Named-axis parameter + input layouts for one Net under one Mesh.

    Holds the PartitionSpecs/NamedShardings a forward or train step
    needs: tp/ep-sharded param layouts (with the divisibility guard),
    dp(+sp)-sharded input layouts, the replicated sharding, and a
    stable topology signature (the AOT cache namespace key).  Built
    once and shared — ParallelSolver derives its training shardings
    from it, and serving's BlobForward jits against the SAME object,
    which is what lets a net bigger than one device's HBM serve across
    the mesh with the exact layout training produced."""

    def __init__(self, net, mesh: Mesh, *, tensor_parallel: bool = True,
                 min_features: int = TP_MIN_FEATURES):
        self.net = net
        self.mesh = mesh
        self.tp_on = tensor_parallel and (
            mesh.shape.get("tp", 1) > 1 or mesh.shape.get("ep", 1) > 1)
        self.param_specs = (
            tp_param_specs(net, min_features=min_features) if self.tp_on
            else {ln: {bn: P() for bn, _, _ in blobs}
                  for ln, blobs in net.param_layout.items()})
        self.shapes = {ln: {bn: s for bn, s, _ in blobs}
                       for ln, blobs in net.param_layout.items()}
        validate_param_specs(self.param_specs, self.shapes, mesh)
        if mesh.shape.get("sp", 1) > 1:
            from .sp import refuse_time_sharding   # lazy: avoids cycle
            refuse_time_sharding(net)
        # -- pipeline stages (pp axis) ---------------------------------
        # pp > 1 cuts the net into contiguous stages (the roofline-
        # balanced partitioner shared with PipelineSolver) and pins
        # each stage's params to the submesh of its pp row: every
        # downstream consumer of param_sharding — place_params, the
        # zero-gather streaming loader, the serving registry — then
        # places or pages a stage's blobs straight onto that stage's
        # devices with no further routing logic.
        self.pp = 1
        self.stages: List[List[str]] = [
            [lp.name for lp in net.compute_layers]]
        self.stage_of_layer: Dict[str, int] = {}
        self.stage_meshes: List[Mesh] = [mesh]
        if int(mesh.shape.get("pp", 1)) > 1:
            from .pp import partition_layers   # lazy: avoids cycle
            self.stages = partition_layers(
                net, int(mesh.shape.get("pp", 1)))
            self.pp = len(self.stages)
            self.stage_meshes = [Mesh(mesh.devices[k], AXES[1:])
                                 for k in range(self.pp)]
        for k, names in enumerate(self.stages):
            for nme in names:
                self.stage_of_layer[nme] = k

        def _owner(ln: str) -> Mesh:
            return self.stage_meshes[self.stage_of_layer.get(ln, 0)] \
                if self.pp > 1 else mesh

        self.param_sharding = {
            ln: {bn: NamedSharding(_owner(ln), spec)
                 for bn, spec in blobs.items()}
            for ln, blobs in self.param_specs.items()}
        self.repl = replicated(mesh)
        self.stage_repl = ([replicated(m) for m in self.stage_meshes]
                           if self.pp > 1 else [self.repl])

    # -- inputs ---------------------------------------------------------
    def input_specs(self, net=None) -> Dict[str, P]:
        """Per-input PartitionSpec: batch sharded over dp; time-major
        (T, B, ·) tops shard batch on axis 1 and — when the mesh has an
        sp axis — their TIME axis over sp (sequence parallelism).  The
        optional `net` override serves forwards whose input geometry
        differs from the layout net (TEST-phase vs TRAIN-phase)."""
        net = net or self.net
        has_sp = dict(self.mesh.shape).get("sp", 1) > 1
        out = {}
        for name, shape, kind in net.input_specs:
            if kind.endswith(":T"):
                out[name] = P("sp", "dp") if has_sp else P(None, "dp")
            else:
                out[name] = P("dp")
        return out

    def input_shardings(self, net=None) -> Dict[str, NamedSharding]:
        # staged layouts feed inputs to stage 0's devices only — the
        # remaining stages receive activations, never inputs
        m = self.stage_meshes[0] if self.pp > 1 else self.mesh
        return {name: NamedSharding(m, spec)
                for name, spec in self.input_specs(net).items()}

    # -- placement ------------------------------------------------------
    def place_params(self, params) -> Dict:
        """device_put every param blob onto its layout sharding."""
        return {ln: {bn: jax.device_put(arr, self.param_sharding[ln][bn])
                     for bn, arr in blobs.items()}
                for ln, blobs in params.items()}

    def install_flash(self, fn):
        """A bare pallas_call cannot be GSPMD-partitioned, but attention
        is embarrassingly parallel over batch x heads and LRN over
        batch — on meshes the Pallas dispatches are routed through
        shard_map (ops.route.flash_mesh) and each device runs the
        kernel on its local block.  Single-device meshes call the
        kernel directly."""
        if self.mesh.devices.size <= 1:
            return fn

        def wrapped(*args, _f=fn):
            from ..ops.route import flash_mesh
            with flash_mesh(self.mesh):  # active during TRACING
                return _f(*args)
        return wrapped

    # -- identity -------------------------------------------------------
    @property
    def dp(self) -> int:
        return self.mesh.shape.get("dp", 1)

    def describe(self) -> Dict[str, object]:
        """JSON-serializable layout summary (PipelineMetrics set_info,
        /healthz) — axes with extent > 1 plus the sharded blobs."""
        axes = {ax: int(n) for ax, n in self.mesh.shape.items() if n > 1}
        sharded = sorted(
            f"{ln}/{bn}:{','.join(str(a) for a in spec)}"
            for ln, blobs in self.param_specs.items()
            for bn, spec in blobs.items()
            if any(ax is not None for ax in spec))
        out = {"axes": axes or {"dp": 1},
               "devices": int(self.mesh.devices.size),
               "sharded_params": sharded}
        if self.pp > 1:
            out["pp_stages"] = [len(s) for s in self.stages]
        return out

    def signature(self) -> str:
        """Stable topology+layout signature: distinct meshes (or
        distinct param layouts under one mesh) must never share a
        compiled-program cache namespace (serving/aot.py).  Staged
        layouts append the pp stage boundaries — a staged and an
        unstaged program of the same net (or two cuts of it) compile
        to different executables and must never collide."""
        axes = ",".join(f"{ax}{self.mesh.shape.get(ax, 1)}"
                        for ax in self.mesh.axis_names)
        specs = ";".join(
            f"{ln}/{bn}={spec}"
            for ln in sorted(self.param_specs)
            for bn, spec in sorted(self.param_specs[ln].items())
            if any(ax is not None for ax in spec))
        sig = f"mesh({axes})|{specs}"
        if self.pp > 1:
            cuts = ",".join(str(len(s)) for s in self.stages)
            sig += f"|pp[{cuts}]"
        return sig


def lockstep_steps(total_records: int, batch_per_step: int,
                   num_ranks: int) -> int:
    """The minPartSize equalization invariant
    (`CaffeOnSpark.scala:185-200`): every rank must execute the SAME
    number of steps or a collective deadlocks the slice.  Returns the
    per-epoch step count = floor(min records per rank / batch)."""
    per_rank = total_records // num_ranks
    return max(0, per_rank // batch_per_step)
