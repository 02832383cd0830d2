"""Blob-forward builder: the predict(blobNames) closure factory.

Lifted out of `CaffeProcessor._feature_fwd` so an online service can
build the jitted forward from a Net + params WITHOUT a training run
(no Solver thread, no feed queues).  The processor's feature path,
the validation round, and the serving subsystem share this one
implementation, which is what makes the serving-vs-extract parity
gate (tests/test_serving.py) hold by construction: same program,
same row extraction.

Mesh-parallel forward: pass a `parallel.mesh.MeshLayout` and every
program is jitted under the layout's mesh — params laid out on tp/ep
exactly as `ParallelSolver` trains them (the SAME MeshLayout object
builds both), the input batch sharded on dp, outputs replicated so
row extraction stays a plain device_get.  A net bigger than one
device's HBM serves across the mesh with no second spec derivation
anywhere.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..net import Net

_LOG = logging.getLogger(__name__)


def make_forward_fn(net: Net, blob_names: Tuple[str, ...]):
    """The one un-jitted forward body every consumer traces:
    predict(blobNames) semantics (CaffeNet.cpp:677-697) — forward,
    then read ANY named blob, not just net outputs."""
    def fwd(params, inputs):
        blobs, _ = net.apply(params, inputs, train=False)
        return {bn: blobs[bn] for bn in blob_names}
    return fwd


def _dequant_entry(params, scales, spec):
    """The quant-forward entry preamble: storage params → compute
    params.  bf16 upcasts, int8 dequantizes by its per-blob scale,
    int8 InnerProduct weights pass through untouched with their scale
    routed to the kernel via the qscales side channel.  Shared by the
    whole-net quant forward and every per-stage staged body (restricted
    there to the stage's layer subset simply by what `params`
    contains)."""
    import jax.numpy as jnp
    from .quant import BF16, INT8, INT8_IP
    p2 = {}
    qscales: Dict[str, dict] = {}
    for ln, bl in params.items():
        sp = spec.get(ln) or {}
        out = {}
        for bn, arr in bl.items():
            kind = sp.get(bn)
            if kind == BF16:
                out[bn] = arr.astype(jnp.float32)
            elif kind == INT8:
                out[bn] = (arr.astype(jnp.float32)
                           * scales[ln][bn])
            elif kind == INT8_IP:
                out[bn] = arr              # kernel consumes int8
                qscales.setdefault(ln, {})[bn] = scales[ln][bn]
            else:
                out[bn] = arr
        p2[ln] = out
    return p2, qscales


def make_quant_forward_fn(net: Net, blob_names: Tuple[str, ...],
                          spec: Dict[str, Dict[str, str]]):
    """Forward body over COMPRESSED resident params (serving/quant.py
    storage spec): bf16 blobs upcast to f32 at entry (storage-only
    compression — compute stays the f32 program), int8 blobs
    dequantize by their per-blob scale, and int8 InnerProduct weights
    pass straight through to the PR 11 int8 MXU kernel (dequant-free;
    the scale rides to the op via Net.apply's qscales side channel).
    Signature is (params, scales, inputs) — scales are traced f32
    scalars so every model version shares one compiled program."""
    def fwd(params, scales, inputs):
        p2, qscales = _dequant_entry(params, scales, spec)
        blobs, _ = net.apply(p2, inputs, train=False, qscales=qscales)
        return {bn: blobs[bn] for bn in blob_names}
    return fwd


class StagedForward:
    """Pipeline-staged predict closure for one (blob set, storage
    dtype) under a pp>1 MeshLayout — the staged twin of the closures
    BlobForward hands out, same call signature (`fwd(params, inputs)`
    / `fwd(params, scales, inputs)`) so warmup, the batcher flush and
    the recompile guard treat it like any jitted forward.

    Execution contract ("RPC Considered Harmful": the hop, not the
    math, is the bottleneck):

      * each stage is its own jitted program over `net.apply(layers=
        stage)` — params pinned to the stage's submesh, outputs
        replicated over that submesh;
      * inter-stage activations move with ONE `jax.device_put` to the
        next stage's devices (ICI on real hardware) — they are never
        fetched to the host between stages;
      * the flush may split into microbatches dispatched `for mb: for
        stage` — under JAX's per-device FIFO async dispatch that order
        IS a 1F1B-style forward pipeline (stage s runs microbatch m
        while stage s-1 runs m+1).  Whether >1 microbatch actually
        beats single-shot is MEASURED per batch shape at first call
        (compile both, time both, keep the winner) — never assumed;
        COS_SERVE_PP_MB pins the count and skips the measurement.

    `stage_wait` (optional kwarg) is the cold-start overlap hook: a
    `waiter(k) -> (stage_params, stage_scales)` provider that blocks
    until stage k is HBM-resident (the registry pages stages in
    order), so the first resident stages execute while later stages
    are still streaming in."""

    def __init__(self, net: Net, layout, blob_names: Tuple[str, ...],
                 weight_dtype: str = "f32"):
        from ..parallel.pp import stage_blob_routing
        from ..utils.envutils import env_int
        self.net = net
        self.layout = layout
        self.blob_names = tuple(blob_names)
        self.weight_dtype = weight_dtype
        self.spec = None
        if weight_dtype != "f32":
            from .quant import quant_spec
            self.spec = quant_spec(net, weight_dtype)
        self.stages = layout.stages
        self.stage_in, self.stage_out = stage_blob_routing(
            net, self.stages, extra_outputs=self.blob_names)
        # COS003: knob read once at construction. 0 = measure.
        self._mb_forced = max(0, env_int("COS_SERVE_PP_MB", 0,
                                         strict=False))
        self._mb_choice: Dict[Tuple, int] = {}
        self._stage_fns: List[Any] = []
        self._tmajor = {n for n, _, kind in net.input_specs
                        if kind.endswith(":T")}
        from ..obs.trace import get_tracer
        self._tracer = get_tracer()
        self._build()

    # -- program construction ------------------------------------------
    def _build(self):
        import jax
        net, lay, spec = self.net, self.layout, self.spec
        input_sh = lay.input_shardings(net)
        for s, names in enumerate(self.stages):
            outs = tuple(sorted(self.stage_out[s]))
            sm = lay.stage_meshes[s]
            repl = lay.stage_repl[s]
            if spec is None:
                def sfwd(sparams, acts, *, _names=tuple(names),
                         _outs=outs):
                    blobs, _ = net.apply(sparams, acts, train=False,
                                         layers=_names)
                    return {b: blobs[b] for b in _outs}
            else:
                def sfwd(sparams, sscales, acts, *,
                         _names=tuple(names), _outs=outs):
                    p2, qs = _dequant_entry(sparams, sscales, spec)
                    blobs, _ = net.apply(p2, acts, train=False,
                                         qscales=qs, layers=_names)
                    return {b: blobs[b] for b in _outs}
            if sm.devices.size > 1:
                def sfwd(*args, _f=sfwd, _m=sm):
                    from ..ops.route import flash_mesh
                    with flash_mesh(_m):   # active during TRACING
                        return _f(*args)
            param_sh = {ln: lay.param_sharding[ln]
                        for ln in names if ln in lay.param_sharding}
            # stage 0 consumes net inputs on their dp-sharded layout;
            # activations (and any input a later stage reads directly,
            # e.g. a label fed to a tail loss) arrive replicated over
            # the stage's submesh
            acts_sh = {b: input_sh.get(b, repl)
                       for b in sorted(self.stage_in[s])} \
                if s == 0 else {b: repl
                                for b in sorted(self.stage_in[s])}
            if spec is None:
                shardings = (param_sh, acts_sh)
            else:
                spec_sh = {
                    ln: {bn: repl for bn, k in bl.items()
                         if k in ("int8", "int8_ip")}
                    for ln, bl in spec.items() if ln in set(names)}
                spec_sh = {ln: bl for ln, bl in spec_sh.items() if bl}
                shardings = (param_sh, spec_sh, acts_sh)
            self._stage_fns.append(jax.jit(
                sfwd, in_shardings=shardings,
                out_shardings={b: repl for b in outs}))

    # -- helpers -------------------------------------------------------
    def stage_params(self, params, s: int):
        return {ln: params[ln] for ln in self.stages[s]
                if ln in params}

    def _stage_scales(self, scales, s: int):
        keep = set(self.stages[s])
        return {ln: bl for ln, bl in (scales or {}).items()
                if ln in keep and ln in (self.spec or {})}

    def _split(self, inputs, m: int):
        """inputs → m equal microbatches (list of dicts); time-major
        ':T' tops carry batch on axis 1."""
        out = [dict() for _ in range(m)]
        for k, v in inputs.items():
            v = np.asarray(v)
            ax = 1 if k in self._tmajor else 0
            b = v.shape[ax]
            step = b // m
            for i in range(m):
                sl = [slice(None)] * v.ndim
                sl[ax] = slice(i * step, (i + 1) * step)
                out[i][k] = v[tuple(sl)]
        return out

    def _batch_of(self, inputs) -> Tuple:
        key = []
        for k in sorted(inputs):
            v = inputs[k]
            key.append((k, tuple(np.shape(v))))
        return tuple(key)

    def _run(self, params, scales, inputs, m: int, stage_wait=None):
        """Dispatch the staged forward over m microbatches; returns
        {blob: array} with requested blobs concatenated over
        microbatches (scalar outputs averaged)."""
        import jax
        import jax.numpy as jnp
        S = len(self.stages)
        lay = self.layout
        mbs = self._split(inputs, m) if m > 1 else [inputs]
        per_mb: List[Dict[str, Any]] = []
        for mb in mbs:
            pool: Dict[str, Any] = dict(mb)
            got: Dict[str, Any] = {}
            for s in range(S):
                if stage_wait is not None:
                    sp, ss = stage_wait(s)
                else:
                    sp = self.stage_params(params, s)
                    ss = self._stage_scales(scales, s)
                acts = {}
                for b in sorted(self.stage_in[s]):
                    v = pool[b]
                    if s > 0 and isinstance(v, jax.Array):
                        # the stage hop: device → device, never host
                        v = jax.device_put(v, lay.stage_repl[s])
                    acts[b] = v
                with self._tracer.span(f"serve.stage{s}") as span:
                    span.set("stage", s).set("layers",
                                             len(self.stages[s]))
                    if self.spec is None:
                        outs = self._stage_fns[s](sp, acts)
                    else:
                        outs = self._stage_fns[s](sp, ss, acts)
                pool.update(outs)
                for b in self.blob_names:
                    if b in outs:
                        got[b] = outs[b]
            per_mb.append(got)
        if m == 1:
            return per_mb[0]
        out: Dict[str, Any] = {}
        for b in self.blob_names:
            vals = [g[b] for g in per_mb]
            if getattr(vals[0], "ndim", 0) == 0:
                # aggregated scalars (Accuracy): equal-sized
                # microbatches, so the flat mean is exact
                out[b] = jnp.mean(jnp.stack(vals))
            else:
                out[b] = jnp.concatenate(vals, axis=0)
        return out

    def _choose_m(self, params, scales, inputs) -> int:
        """Microbatch count for this batch shape: the forced knob, or
        the measured winner of {1, pp} (compile both, time both) —
        'microbatched 1F1B when it beats single-shot, measured not
        assumed'."""
        import jax
        key = self._batch_of(inputs)
        if key in self._mb_choice:
            return self._mb_choice[key]
        first = next(iter(inputs.values()))
        ax = 1 if sorted(inputs)[0] in self._tmajor else 0
        bs = int(np.shape(first)[ax])
        S = len(self.stages)
        # each microbatch must still split evenly over stage 0's dp
        # extent (the batcher's bucket rule, applied post-split)
        dp = max(1, getattr(self.layout, "dp", 1))

        def _ok(m: int) -> bool:
            return m > 0 and bs % m == 0 and (bs // m) % dp == 0
        if self._mb_forced:
            m = self._mb_forced if _ok(self._mb_forced) else 1
            self._mb_choice[key] = m
            return m
        import time as _time
        cands = [1] + ([S] if S > 1 and _ok(S) else [])
        best, best_t = 1, None
        for m in cands:
            # compile pass, then one timed pass
            jax.block_until_ready(
                self._run(params, scales, inputs, m))
            t0 = _time.perf_counter()
            jax.block_until_ready(
                self._run(params, scales, inputs, m))
            dt = _time.perf_counter() - t0
            if best_t is None or dt < best_t:
                best, best_t = m, dt
        self._mb_choice[key] = best
        _LOG.info("staged forward: batch=%d stages=%d -> "
                  "microbatches=%d (measured)", bs, S, best)
        return best

    # -- the closure surface -------------------------------------------
    def __call__(self, params, *rest, stage_wait=None):
        if self.spec is None:
            (inputs,) = rest
            scales = None
        else:
            scales, inputs = rest
        m = self._choose_m(params, scales, inputs) \
            if stage_wait is None else 1
        return self._run(params, scales, inputs, m,
                         stage_wait=stage_wait)

    def _cache_size(self) -> int:
        """RecompileGuard probe: total compiled-program count across
        the per-stage jitted functions."""
        total = 0
        for fn in self._stage_fns:
            cs = getattr(fn, "_cache_size", None)
            if callable(cs):
                total += int(cs())
        return total


class BlobForward:
    """Jitted predict(blobNames) closures for one Net, cached per blob
    set — chunked EXTRACT requests and per-bucket serving flushes must
    not retrace per call.  Programs are params-agnostic, so a model
    hot-swap reuses every compiled bucket program.

    `layout` (a MeshLayout) switches every closure to mesh execution:
    in_shardings pin params to the layout's tp/ep placement and the
    batch to dp, out_shardings replicate the fetched blobs.  jit does
    the input device_put itself, so callers keep handing in host
    arrays."""

    def __init__(self, net: Net, layout=None):
        self.net = net
        self.layout = layout
        self._cache: Dict[Tuple, Any] = {}

    def __call__(self, blob_names: Tuple[str, ...],
                 weight_dtype: str = "f32"):
        """The jitted closure for (blob set, resident storage dtype).
        "f32" is the unchanged pre-quantization program —
        fwd(params, inputs); compressed dtypes get
        fwd(params, scales, inputs) over make_quant_forward_fn (one
        program per dtype, shared by every version of the net)."""
        import jax
        key = (tuple(blob_names), weight_dtype)
        if key not in self._cache:
            if getattr(self.layout, "pp", 1) > 1:
                # staged twin: same signature, per-stage programs,
                # device-resident inter-stage activations
                self._cache[key] = StagedForward(
                    self.net, self.layout, tuple(blob_names),
                    weight_dtype)
                return self._cache[key]
            if weight_dtype == "f32":
                fwd = make_forward_fn(self.net, tuple(blob_names))
            else:
                from .quant import quant_spec
                spec = quant_spec(self.net, weight_dtype)
                fwd = make_quant_forward_fn(self.net,
                                            tuple(blob_names), spec)
            if self.layout is None:
                fwd = jax.jit(fwd)
            else:
                lay = self.layout
                if weight_dtype == "f32":
                    shardings = (lay.param_sharding,
                                 lay.input_shardings(self.net))
                else:
                    # scales are scalars: replicated everywhere; the
                    # compressed params reuse the layout's placement
                    # (shardings are dtype-agnostic)
                    spec_sh = {
                        ln: {bn: lay.repl for bn, k in bl.items()
                             if k in ("int8", "int8_ip")}
                        for ln, bl in spec.items()}
                    spec_sh = {ln: bl for ln, bl in spec_sh.items()
                               if bl}
                    shardings = (lay.param_sharding, spec_sh,
                                 lay.input_shardings(self.net))
                fwd = jax.jit(
                    lay.install_flash(fwd),
                    in_shardings=shardings,
                    out_shardings={bn: lay.repl for bn in blob_names})
            self._cache[key] = fwd
        return self._cache[key]


def fetch_rows(out: Dict[str, Any], blob_names: Sequence[str],
               ids: Sequence[str], real: int, bs: int
               ) -> List[Dict[str, Any]]:
    """Forward outputs → `real` SampleID rows (one device_get per blob,
    not per row — aggregated scalar outputs like Accuracy repeat per
    row, CaffeOnSpark.scala:499-507).  `bs` is the executed batch
    size; rows past `real` are padding and are dropped."""
    import jax
    fetched = {bn: np.asarray(jax.device_get(out[bn]))
               for bn in blob_names}
    rows: List[Dict[str, Any]] = []
    for i in range(real):
        row: Dict[str, Any] = {"SampleID": ids[i]}
        for bn, arr in fetched.items():
            if arr.ndim == 0:
                row[bn] = [float(arr)]
            else:
                per = arr.reshape(bs, -1) if arr.shape[0] == bs \
                    else np.repeat(arr.reshape(1, -1), bs, 0)
                row[bn] = [float(x) for x in per[i]]
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# serving mesh resolution (-serveMesh / COS_SERVE_TP)
# ---------------------------------------------------------------------------

def serve_mesh_spec(conf=None) -> Optional[Dict[str, int]]:
    """Resolve the serving mesh request: `-serveMesh dp[,tp[,sp[,ep]]]`
    (same grammar as the training `-mesh` flag), else the COS_SERVE_TP
    shorthand (tp=N, dp = device remainder).  None = single-device
    serving, exactly the pre-mesh behavior."""
    spec = getattr(conf, "serveMesh", "") if conf is not None else ""
    if not spec:
        spec = os.environ.get("COS_SERVE_MESH", "")
    if spec:
        from ..parallel.mesh import parse_mesh_spec
        return parse_mesh_spec(spec)
    try:
        tp = int(os.environ.get("COS_SERVE_TP", "0"))
    except ValueError:
        _LOG.warning("ignoring non-integer COS_SERVE_TP=%r",
                     os.environ.get("COS_SERVE_TP"))
        tp = 0
    if tp > 1:
        return {"tp": tp}
    return None


def build_serving_layout(net: Net, conf=None, *, devices=None):
    """MeshLayout for serving, or None when no mesh was requested.
    Spec construction is `parallel.mesh.MeshLayout` — the identical
    path ParallelSolver uses for training, so serving params land on
    the same shards the trainer would put them on.  `-devices N`
    limits the mesh to this host's first N devices (the trainer's
    rule), so a replica can own a sub-slice."""
    kwargs = serve_mesh_spec(conf)
    if kwargs is None:
        return None
    import jax
    from ..parallel.mesh import MeshLayout, build_mesh
    if devices is None and getattr(conf, "devices", 0) > 0:
        devices = jax.local_devices()[:conf.devices]
    mesh = build_mesh(devices=devices, **kwargs)
    layout = MeshLayout(net, mesh)
    _LOG.info("serving mesh: %s", layout.describe())
    return layout
