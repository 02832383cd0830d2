"""Net compiler: NetParameter (+ NetState) → a functional JAX net.

TPU-native equivalent of caffe::Net construction inside
`CaffeNet<Dtype>::CaffeNet` (reference `caffe-distri/src/main/cpp/
CaffeNet.cpp:101-205`) and the per-phase layer filtering the driver does in
`Config.scala:73-86`.  Instead of a mutable layer graph, compilation
produces:

  * ``Net.init(key)``      → params pytree {layer: {blob: array}}
  * ``Net.apply(params, inputs, train, rng, state)`` → (blobs, new_state)
  * ``Net.loss(...)``      → weighted total loss + blobs (for jax.grad)

Everything in apply is traceable: one `jax.jit` covers the whole forward
(+backward via grad), letting XLA fuse elementwise chains into MXU matmul/
conv ops.  Layer inclusion rules (phase/stage/not_stage/level) follow
caffe's NetState::StateMeetsRule semantics used by lrcn_solver.prototxt's
train_state/test_state stages.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from .ops import layers as L
from .ops.recompute import BLOCK_POLICY, block_trace, note_shared
from .proto.caffe import (LayerParameter, NetParameter, NetState,
                          NetStateRule, Phase, TopBlobType)

Array = jax.Array
Params = Dict[str, Dict[str, Array]]


def state_meets_rule(rule: NetStateRule, state: NetState) -> bool:
    if rule.has("phase") and rule.phase != state.phase:
        return False
    if rule.has("min_level") and state.level < rule.min_level:
        return False
    if rule.has("max_level") and state.level > rule.max_level:
        return False
    stages = set(state.stage)
    for s in rule.stage:
        if s not in stages:
            return False
    for s in rule.not_stage:
        if s in stages:
            return False
    return True


def layer_included(lp: LayerParameter, state: NetState) -> bool:
    if lp.include:
        return any(state_meets_rule(r, state) for r in lp.include)
    if lp.exclude:
        return not any(state_meets_rule(r, state) for r in lp.exclude)
    return True


def _cos_top_shape(top, batch: int) -> Tuple[int, ...]:
    """Shape of one CoSData top (cos_data_layer.cpp:10-47 semantics)."""
    if top.transpose:
        # time-major (T, B) layout for RNN inputs
        return (int(top.channels), batch)
    axes = top.sample_num_axes
    t = top.type
    if t in (TopBlobType.ENCODED_IMAGE_WITH_DIM, TopBlobType.ENCODED_IMAGE,
             TopBlobType.RAW_IMAGE):
        c = int(top.out_channels or top.channels)
        h = int(top.out_height or top.height)
        w = int(top.out_width or top.width)
        if top.transform_param.crop_size:
            h = w = int(top.transform_param.crop_size)
        return (batch, c, h, w)
    if axes == 1:
        return (batch, int(top.channels))
    if axes == 0:
        return (batch,)
    return (batch, int(top.channels), int(top.height), int(top.width))


def _peek_db_dims(lp: LayerParameter) -> Tuple[int, int, int]:
    """First-record (C, H, W) of a Data layer's LMDB/LevelDB database;
    (3, 0, 0) when the database isn't readable at graph-build time
    (deploy nets parsed away from the data)."""
    from .proto.caffe import DBBackend, Datum
    try:
        from .data.source import _strip_scheme
        source = _strip_scheme(lp.data_param.source)
        if lp.data_param.backend == DBBackend.LEVELDB:
            from .data.leveldb_io import LevelDBReader as _Reader
        else:
            from .data.lmdb_io import LmdbReader as _Reader
        with _Reader(source) as r:
            for _k, v in r.items(None, None):
                d = Datum.from_binary(v)
                return int(d.channels), int(d.height), int(d.width)
    except Exception:
        pass
    return 3, 0, 0


def data_layer_input_specs(lp: LayerParameter) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(blob_name, shape, kind) for each top of a data layer.
    kind ∈ {'data','label','int'} guides dtype selection downstream."""
    t = lp.type
    if t == "MemoryData":
        p = lp.memory_data_param
        b = int(p.batch_size)
        shape = (b, int(p.channels), int(p.height), int(p.width))
        if lp.transform_param.crop_size:
            cs = int(lp.transform_param.crop_size)
            shape = (b, int(p.channels), cs, cs)
        specs = [(lp.top[0], shape, "data")]
        if len(lp.top) > 1:
            specs.append((lp.top[1], (b,), "label"))
        return specs
    if t == "CoSData":
        p = lp.cos_data_param
        b = int(p.batch_size)
        # transpose tops are time-major (T, B): batch axis is 1
        return [(top.name, _cos_top_shape(top, b),
                 ("int" if top.type in (TopBlobType.INT,
                                        TopBlobType.INT_ARRAY) else "data")
                 + (":T" if top.transpose else ""))
                for top in p.top]
    if t == "Input":
        shapes = list(lp.input_param.shape)
        if len(shapes) == 1 and len(lp.top) > 1:
            shapes = shapes * len(lp.top)  # one shape shared by all tops
        if len(shapes) != len(lp.top):
            raise ValueError(f"Input layer {lp.name!r}: {len(shapes)} "
                             f"shapes for {len(lp.top)} tops")
        return [(name, tuple(int(d) for d in shp.dim), "data")
                for name, shp in zip(lp.top, shapes)]
    if t == "HDF5Data":
        # shapes live in the HDF5 files (hdf5_data_layer.cpp reads the
        # first listed file to size the tops) — probe it when the
        # source list is readable, else the caller must pass
        # input_shapes overrides (Net(..., input_shapes=...))
        import os
        p = lp.hdf5_data_param
        src = p.source
        if src and os.path.exists(src):
            from .data.hdf5 import hdf5_top_shapes
            shapes = hdf5_top_shapes(src, list(lp.top),
                                     int(p.batch_size))
            return [(name, shapes[name],
                     "label" if name == "label" else "data")
                    for name in lp.top]
        return [(name, (), "data") for name in lp.top]
    if t == "Data":
        p = lp.data_param
        b = int(p.batch_size)
        cs = int(p.crop_size or lp.transform_param.crop_size or 0)
        # Caffe's DataLayer reads the first Datum at LayerSetUp to size
        # its tops (data_layer.cpp); do the same so downstream layers
        # compile against the real geometry
        c, h, w = _peek_db_dims(lp)
        if cs:
            h = w = cs
        shape = (b, c, h or 1, w or 1)
        specs = [(lp.top[0], shape, "data")]
        if len(lp.top) > 1:
            specs.append((lp.top[1], (b,), "label"))
        return specs
    if t == "ImageData":
        # image_data_layer.cpp: (path label) list file; static TPU
        # shapes need new_height/new_width (or a crop) declared
        p = lp.image_data_param
        b = int(p.batch_size)
        c = 3 if p.is_color else 1
        cs = int(lp.transform_param.crop_size or 0)
        h = cs or int(p.new_height)
        w = cs or int(p.new_width)
        if not h or not w:
            raise ValueError(
                f"ImageData layer {lp.name!r}: set new_height/new_width "
                "(or transform_param.crop_size) — static shapes required")
        specs = [(lp.top[0], (b, c, h, w), "data")]
        if len(lp.top) > 1:
            specs.append((lp.top[1], (b,), "label"))
        return specs
    if t == "DummyData":
        p = lp.dummy_data_param
        out = []
        for i, name in enumerate(lp.top):
            if p.shape:
                shp = p.shape[min(i, len(p.shape) - 1)]
                out.append((name, tuple(int(d) for d in shp.dim), "data"))
            else:
                idx = min(i, len(p.num) - 1) if p.num else 0
                out.append((name, (int(p.num[idx]), int(p.channels[idx]),
                                   int(p.height[idx]), int(p.width[idx])),
                            "data"))
        return out
    raise NotImplementedError(f"data layer {t}")


def fusable_relu_for_lrn(layers: Sequence[LayerParameter],
                         lrn_lp: LayerParameter
                         ) -> Optional[LayerParameter]:
    """THE ReLU→LRN fusion-eligibility rule, as a predicate: the ReLU
    layer `_fuse_relu_lrn` would absorb into `lrn_lp`, or None.  One
    copy — the peephole applies it, and the autotuner's variant
    enumeration (`ops/autotune.py`) and the roofline byte model
    (`analysis/roofline.py`) consult the SAME rule, so neither can
    enumerate or credit a fusion the build refuses.

    Eligible: `lrn_lp` is a 1-bottom ACROSS_CHANNELS LRN whose
    bottom's last producer is a plain ReLU (negative_slope 0, no loss
    weight, 1 bottom / 1 top) consumed by nothing but the LRN."""
    from .proto.caffe import NormRegion
    if (lrn_lp.type != "LRN" or len(lrn_lp.bottom) != 1
            or lrn_lp.lrn_param.norm_region
            != NormRegion.ACROSS_CHANNELS):
        return None
    prod, pi = None, -1
    found = False
    for j, l2 in enumerate(layers):
        if l2 is lrn_lp:
            found = True
            break
        if lrn_lp.bottom[0] in l2.top:
            prod, pi = l2, j
    if not found or prod is None or prod.type != "ReLU":
        return None
    if len(prod.bottom) != 1 or len(prod.top) != 1:
        return None
    if float(getattr(prod.relu_param, "negative_slope", 0.0) or 0.0):
        return None
    if any(float(w) for w in prod.loss_weight):
        return None
    consumers = [l2 for j, l2 in enumerate(layers)
                 if j > pi and prod.top[0] in l2.bottom]
    if consumers != [lrn_lp]:
        return None
    return prod


def prefuse_conv_bias_eligible(layers: Sequence[LayerParameter],
                               lrn_lp: LayerParameter,
                               relu_lp: LayerParameter) -> bool:
    """PRE-fuse mirror of `_fuse_conv_bias`'s rule (which runs on the
    post-fuse layer list): would the conv feeding `relu_lp` get its
    bias deferred into `lrn_lp` once the relu is fused away?  True
    when that producer is a bias_term Convolution whose top feeds
    nothing but the relu chain (for an in-place relu, the LRN also
    reads the name — that IS the chain)."""
    conv, ci = None, -1
    for j, l2 in enumerate(layers):
        if l2 is relu_lp:
            break
        if relu_lp.bottom[0] in l2.top:
            conv, ci = l2, j
    if (conv is None or conv.type != "Convolution"
            or not conv.convolution_param.bias_term):
        return False
    others = [l2 for j, l2 in enumerate(layers)
              if j > ci and conv.top[0] in l2.bottom
              and l2 is not relu_lp]
    return others in ([], [lrn_lp])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _constant_blobs(spec, dtype):
    """{key: blob of `shape` filled with `value`} for `spec`'s (key,
    (shape, value)) pairs, as one program (`Net.init`)."""
    return {k: jnp.full(shape, value, dtype) for k, (shape, value) in spec}


class Net:
    """A compiled, phase-filtered network."""

    def __init__(self, net_param: NetParameter, state: Optional[NetState] = None,
                 input_shapes: Optional[Dict[str, Sequence[int]]] = None,
                 dtype=jnp.float32,
                 remat: Optional[Union[bool, str]] = None,
                 compute_dtype=None,
                 autotune: Union[None, bool, str, dict] = None):
        self.net_param = net_param
        self.state = state or NetState(phase=Phase.TRAIN)
        self.name = net_param.name
        self.dtype = dtype
        # mixed precision: params stay `dtype` (f32 master weights for
        # optimizer updates) while the forward casts params+inputs to
        # `compute_dtype` (bf16 on the MXU); grads come back f32 via the
        # cast's transpose
        self.compute_dtype = compute_dtype or dtype
        # rematerialization: recompute layer activations in the backward
        # pass instead of storing them — trades MXU FLOPs for HBM
        # (jax.checkpoint per layer).  COS_REMAT=1 full per-layer remat
        # (max HBM savings, measured -21% on CaffeNet b256);
        # COS_REMAT=mxu keeps matmul/conv OUTPUTS and recomputes only
        # the cheap elementwise work — most of the memory win at a
        # fraction of the recompute tax, since the expensive MXU ops
        # never re-run
        if remat is None:
            remat = os.environ.get("COS_REMAT", "")
        if isinstance(remat, str):
            # env values and string args share one mapping; an unknown
            # value must error, not silently enable the WRONG remat
            # flavor (a truthy typo string used to read as full remat)
            try:
                remat = {"": False, "0": False, "false": False,
                         "off": False, "1": True, "full": True,
                         "true": True, "mxu": "mxu"}[remat.lower()]
            except KeyError:
                raise ValueError(
                    f"COS_REMAT={remat!r}: expected 0/1/full/mxu") \
                    from None
        self.remat = remat
        self.remat_policy = None
        if self.remat == "mxu":
            # save every MXU-op result (matmul AND conv — jax's
            # built-in checkpoint_dots covers only dot_general, which
            # misses convs entirely on a CNN), recompute just the
            # cheap VPU elementwise work
            def _mxu_saveable(prim, *_, **__):
                return prim.name in ("dot_general",
                                     "conv_general_dilated")
            self.remat_policy = _mxu_saveable

        self.layers: List[LayerParameter] = [
            lp for lp in net_param.layer if layer_included(lp, self.state)]

        # --- resolve net inputs ------------------------------------------
        self.input_specs: List[Tuple[str, Tuple[int, ...], str]] = []
        self.data_layers: List[LayerParameter] = []
        # legacy net-level inputs (deploy prototxts)
        if net_param.input:
            for i, name in enumerate(net_param.input):
                if net_param.input_shape:
                    shp = tuple(int(d)
                                for d in net_param.input_shape[i].dim)
                else:
                    shp = tuple(int(d)
                                for d in net_param.input_dim[4 * i:4 * i + 4])
                self.input_specs.append((name, shp, "data"))
        for lp in self.layers:
            if L.get_op(lp.type).is_data:
                self.data_layers.append(lp)
                specs = data_layer_input_specs(lp)
                if input_shapes:
                    specs = [(n, tuple(input_shapes.get(n, s)), k)
                             for (n, s, k) in specs]
                for n, s, _ in specs:
                    if len(s) == 0:
                        raise ValueError(
                            f"data layer {lp.name!r} ({lp.type}) top "
                            f"{n!r} has no shape in the prototxt — pass "
                            f"input_shapes={{'{n}': (...)}} to Net")
                self.input_specs.extend(specs)
        self.compute_layers = [lp for lp in self.layers
                               if not L.get_op(lp.type).is_data]

        # --- autotune plan (COS_AUTOTUNE, resolved ONCE here — never at
        # trace time; COS003 discipline).  None/unset/"0" is INERT:
        # no plan, no per-layer variants, byte-identical construction.
        # `autotune` arg: False forces inert (the tuner's candidate
        # nets), a dict is an explicit plan, a str a plan path, None
        # defers to the env.
        self.autotune_plan: Optional[dict] = None
        self.layer_variants: Dict[str, dict] = {}
        if autotune is not False:
            from .ops.autotune import dtype_policy_str, resolve_plan
            self.autotune_plan, self.layer_variants = resolve_plan(
                net_param, self.state, autotune,
                dtype_policy=dtype_policy_str(self.dtype,
                                              self.compute_dtype))

        # --- ReLU→LRN peephole (COS_FUSE_RELU_LRN=1, opt-in; also
        # requested per-layer by the autotune plan) -----------------------
        # XLA cannot fuse a producer into an opaque pallas call, so a
        # ReLU feeding the Pallas LRN kernel materializes its output as
        # the kernel's residual AND keeps the pre-activation live for
        # its own mask — one extra activation-sized HBM round trip per
        # stage in training.  Fused, the LRN kernel applies relu (and
        # its mask, in the VJP) in VMEM and the only residual is the
        # pre-activation.  Caveat (why opt-in): the relu top is no
        # longer a materialized blob — for an in-place relu the name
        # then holds the PRE-activation, so feature extraction of that
        # blob changes meaning.
        # COS_FUSE_BIAS_RELU_LRN=1 (or a plan variant fuse=bias_relu)
        # generalizes the epilogue one producer further: the conv's
        # bias add joins relu+lrn in the kernel, the conv emits its RAW
        # matmul output, and d_bias is recovered exactly from the
        # kernel's dx (ops/pallas_kernels.bias_relu_lrn_across_channels).
        self.fused_relu_lrn: frozenset = frozenset()
        self.fused_bias_lrn: Dict[str, str] = {}      # lrn → conv
        plan_fuse = {n for n, v in self.layer_variants.items()
                     if v.get("fuse") in ("relu", "bias_relu")}
        plan_deny = frozenset(n for n, v in self.layer_variants.items()
                              if v.get("fuse") == "none")
        env_fuse_all = os.environ.get("COS_FUSE_RELU_LRN") == "1"
        env_bias = os.environ.get("COS_FUSE_BIAS_RELU_LRN") == "1"
        if env_fuse_all or env_bias or plan_fuse:
            fused: set = set()
            self.compute_layers = self._fuse_relu_lrn(
                self.compute_layers, fused,
                want=None if (env_fuse_all or env_bias) else plan_fuse,
                deny=plan_deny)
            self.fused_relu_lrn = frozenset(fused)
            bias_want = (None if env_bias else
                         {n for n, v in self.layer_variants.items()
                          if v.get("fuse") == "bias_relu"})
            if env_bias or bias_want:
                self.fused_bias_lrn = self._fuse_conv_bias(bias_want)
        self._bias_lrn_set = frozenset(self.fused_bias_lrn)
        self._defer_bias = frozenset(self.fused_bias_lrn.values())
        self._validate_variants()

        # --- shape inference + param spec construction -------------------
        blob_shapes: Dict[str, Tuple[int, ...]] = {
            name: tuple(shape) for name, shape, _ in self.input_specs}
        self.param_layout: Dict[str, List[Tuple[str, Tuple[int, ...], object]]] = {}
        # named parameter sharing (`param { name: "E" }`, Caffe's own
        # mechanism): the first layer that names a blob owns it, and it
        # is that layer's entry of `param_layout` (one gradient, one
        # optimizer state, one blob of a snapshot); a later layer that
        # gives the same name reads the owner's.  `param_sources` is
        # every parameterized layer's blobs in its op's order as
        # (its name there, owning layer, blob, shape); `shared_params`
        # {(reader, blob): (owner, blob)} the ones read from elsewhere
        self.param_sources: Dict[str, List[Tuple[str, str, str, Tuple[int, ...]]]] = {}
        self.shared_params: Dict[Tuple[str, str], Tuple[str, str]] = {}
        named: Dict[str, Tuple[str, str, Tuple[int, ...]]] = {}
        self._top_shapes: Dict[str, Dict[str, Tuple[int, ...]]] = {}
        for lp in self.compute_layers:
            op = L.get_op(lp.type)
            for b in lp.bottom:
                if b not in blob_shapes:
                    raise ValueError(
                        f"layer {lp.name!r} ({lp.type}) consumes unknown "
                        f"blob {b!r}; produced so far: "
                        f"{sorted(blob_shapes)}")
            bshapes = [blob_shapes[b] for b in lp.bottom]
            specs = [(n, tuple(int(x) for x in s), f)
                     for (n, s, f) in op.param_specs(lp, bshapes)]
            if specs:
                sources, owned = [], []
                for i, (bname, shape, filler) in enumerate(specs):
                    share = lp.param[i].name if i < len(lp.param) else ""
                    if share and share in named:
                        oln, obn, oshape = named[share]
                        if oshape != shape:
                            raise ValueError(
                                f"layer {lp.name!r}: blob {bname!r} "
                                f"{shape} shares {share!r} with "
                                f"{oln}/{obn} {oshape}")
                        self.shared_params[lp.name, bname] = (oln, obn)
                        sources.append((bname, oln, obn, shape))
                        continue
                    if share:
                        named[share] = (lp.name, bname, shape)
                    sources.append((bname, lp.name, bname, shape))
                    owned.append((bname, shape, filler))
                self.param_sources[lp.name] = sources
                if owned:
                    self.param_layout[lp.name] = owned
            # abstract evaluation for top shapes
            dummy_params = [jax.ShapeDtypeStruct(s, dtype)
                            for (_, s, _) in specs]
            if lp.name in self.fused_bias_lrn:
                # the bias-fused LRN consumes the producing conv's bias
                # as params[0] (the conv is earlier in topo order, so
                # its layout is already known)
                conv = self.fused_bias_lrn[lp.name]
                bshape = next(s for (n2, s, _) in
                              self.param_layout[conv] if n2 == "bias")
                dummy_params = [jax.ShapeDtypeStruct(bshape, dtype)] \
                    + dummy_params
            dummy_bottoms = [jax.ShapeDtypeStruct(s, dtype) for s in bshapes]
            ctx = L.Ctx(train=self.state.phase == Phase.TRAIN,
                        rng=jax.random.key(0), layer_name=lp.name,
                        fused_relu_lrn=self.fused_relu_lrn,
                        variant=self.layer_variants.get(lp.name),
                        defer_bias=self._defer_bias,
                        bias_lrn=self._bias_lrn_set)
            tops = jax.eval_shape(
                lambda p, b, lp=lp, op=op, ctx=ctx: op.apply(ctx, lp, p, b),
                dummy_params, dummy_bottoms)
            shaped = {}
            for name, tshape in zip(lp.top, tops):
                blob_shapes[name] = tuple(tshape.shape)
                shaped[name] = tuple(tshape.shape)
            self._top_shapes[lp.name] = shaped
        self.blob_shapes = blob_shapes

        # --- net outputs: tops never consumed ----------------------------
        consumed = {b for lp in self.compute_layers for b in lp.bottom}
        produced: List[str] = [n for n, _, _ in self.input_specs]
        for lp in self.compute_layers:
            for t in lp.top:
                if t not in produced:
                    produced.append(t)
        # in-place layers re-produce their bottom; a blob is an output if no
        # layer consumes it — approximate Caffe: top not in consumed
        self.output_blobs = [n for n in produced if n not in consumed]
        # loss weights per top
        self.loss_weights: Dict[str, float] = {}
        for lp in self.compute_layers:
            op = L.get_op(lp.type)
            for i, t in enumerate(lp.top):
                if i < len(lp.loss_weight):
                    w = float(lp.loss_weight[i])
                elif op.is_loss:
                    w = 1.0
                else:
                    w = 0.0
                if w:
                    self.loss_weights[t] = w
        self.recompute_blocks = self._recompute_blocks()

    def _recompute_blocks(self) -> Dict[str, dict]:
        """`recompute_block: "<name>"` on consecutive layers makes them
        one block: in a TRAIN pass `apply` runs it under one
        jax.checkpoint, so the blobs that enter it are kept for the
        backward pass and what is inside is computed again there, all
        but the few values its layers name as they make them (a Mosaic
        forward kernel's outputs, the router's result:
        `ops/recompute.py`).  -> {first layer's name: {layers, inputs,
        exports}}."""
        blocks: Dict[str, dict] = {}
        seen: set = set()
        run: List[LayerParameter] = []

        def close():
            if not run:
                return
            tag = run[0].recompute_block
            names = {lp.name for lp in run}
            made: set = set()
            inputs: List[str] = []
            for lp in run:
                if L.get_op(lp.type).f32_stats:
                    raise ValueError(
                        f"recompute_block {tag!r}: layer {lp.name!r} "
                        f"({lp.type}) updates running statistics in its "
                        "forward pass and cannot be recomputed")
                for b in lp.bottom:
                    if b not in made and b not in inputs:
                        inputs.append(b)
                made.update(lp.top)
            outside = {b for lp in self.compute_layers
                       if lp.name not in names for b in lp.bottom}
            keep = outside | set(self.output_blobs) | set(self.loss_weights)
            blocks[run[0].name] = {
                "layers": list(run), "inputs": inputs,
                "exports": [t for t in dict.fromkeys(
                    t for lp in run for t in lp.top) if t in keep]}
            run.clear()

        for lp in self.compute_layers:
            tag = lp.recompute_block
            if run and tag != run[0].recompute_block:
                close()
            if tag:
                if not run and tag in seen:
                    raise ValueError(
                        f"recompute_block {tag!r} is not contiguous "
                        f"(again at layer {lp.name!r})")
                seen.add(tag)
                run.append(lp)
        close()
        return blocks

    # ------------------------------------------------------------------
    def _fuse_relu_lrn(self, layers: List[LayerParameter], fused: set,
                       want: Optional[set] = None,
                       deny: frozenset = frozenset()
                       ) -> List[LayerParameter]:
        """Replace eligible [ReLU, LRN] pairs with one LRN layer whose
        op applies relu in-kernel (see __init__).  Eligibility is the
        module-level `fusable_relu_for_lrn` predicate — the ONE copy
        the autotuner and roofline model also consult.  The LRN entry
        is a deep copy (the source NetParameter may build other Nets);
        its name is added to `fused` (becomes self.fused_relu_lrn,
        which Net.apply threads to the op through Ctx).  `want`
        restricts fusion to the named LRN layers (the autotune plan's
        per-layer request; None = every eligible pair, the env-knob
        behavior); `deny` always blocks the named LRNs (a plan
        fuse=none beats the env knob)."""
        out: List[LayerParameter] = list(layers)
        i = 0
        while i < len(out):
            nl = out[i]
            if nl.type != "LRN" or nl.name in deny \
                    or (want is not None and nl.name not in want):
                i += 1
                continue
            r = fusable_relu_for_lrn(out, nl)
            if r is None:
                i += 1
                continue
            fused_lp = LayerParameter.from_binary(nl.to_binary())
            fused_lp.bottom = [r.bottom[0]]
            out[i] = fused_lp
            ri = next(j for j, l2 in enumerate(out) if l2 is r)
            del out[ri]            # ri < i: the producer sits earlier,
            #                        so out[i-1] is now the fused LRN
            #                        and out[i] the next layer to scan
            fused.add(nl.name)
        return out

    # ------------------------------------------------------------------
    def _fuse_conv_bias(self, want: Optional[set]) -> Dict[str, str]:
        """Second stem-peephole pass: for relu-fused LRN layers (their
        bottom is now the conv's raw top), defer the producing conv's
        bias add into the LRN kernel's epilogue.  Eligible: the LRN's
        single bottom is produced by a bias_term Convolution whose top
        is consumed by NO other layer.  Returns {lrn_name: conv_name};
        Net.apply routes the conv's bias blob to the LRN as params[0]
        and tells the conv op to skip its own add (Ctx.defer_bias) —
        gradients still land on the conv's bias through the fused
        kernel's VJP.  `want` restricts to the named LRNs (autotune
        plan); None = every eligible fused pair (the env knob).

        Caveat (the relu peephole's, one producer deeper — why this
        too is opt-in): the conv's top name now holds the RAW matmul
        output, so feature-extracting that blob returns UNBIASED
        activations.  The layer-consumer check above cannot see the
        extraction surface (-features names arbitrary blobs at run
        time); don't enable bias fusion on nets whose conv stems feed
        feature extraction."""
        out: Dict[str, str] = {}
        by_top: Dict[str, LayerParameter] = {}
        for lp in self.compute_layers:
            for t in lp.top:
                by_top[t] = lp
        for lp in self.compute_layers:
            if lp.name not in self.fused_relu_lrn:
                continue
            if want is not None and lp.name not in want:
                continue
            src = by_top.get(lp.bottom[0])
            if (src is None or src.type != "Convolution"
                    or not src.convolution_param.bias_term):
                continue
            consumers = [o for o in self.compute_layers
                         if o is not lp and src.top[0] in o.bottom]
            if consumers:
                continue     # someone else needs the biased activation
            out[lp.name] = src.name
        return out

    # ------------------------------------------------------------------
    def _validate_variants(self) -> None:
        """Drop plan entries that cannot apply to THIS net: unknown
        layer names (pruned relus, other phases), int8 on a TRAIN-phase
        net (the quantized matmul is forward-only serving), and
        type-mismatched knobs.  Dropping with a log line — never
        erroring — keeps one plan applicable to the train/test net pair
        it was tuned against."""
        self._variant_dtype: Dict[str, object] = {}
        if not self.layer_variants:
            return
        import logging
        log = logging.getLogger(__name__)
        by_name = {lp.name: lp.type for lp in self.compute_layers}
        train = self.state.phase == Phase.TRAIN
        keep: Dict[str, dict] = {}
        for name, v in self.layer_variants.items():
            t = by_name.get(name)
            if t is None:
                continue                 # fused-away or other-phase layer
            v = dict(v)
            if v.get("int8") and (train or t != "InnerProduct"):
                log.warning("autotune: dropping int8 variant on %s "
                            "(%s, train=%s) — serving InnerProduct only",
                            name, t, train)
                v.pop("int8")
            if v.get("layout") and t != "Convolution":
                v.pop("layout")
            if v.get("attention") and t != "MultiHeadAttention":
                v.pop("attention")
            if v.get("fuse") and t != "LRN":
                v.pop("fuse")
            # reconcile fuse with what the peephole ACTUALLY did:
            # info.autotune publishes "the variants applied to THIS
            # net", so a refused fusion must not be reported as
            # applied (a bias_relu the bias pass refused downgrades
            # to the relu fusion that did land, or disappears)
            fuse = v.get("fuse")
            if fuse == "bias_relu" and name not in self.fused_bias_lrn:
                fuse = "relu" if name in self.fused_relu_lrn else None
            elif fuse == "relu" and name not in self.fused_relu_lrn:
                fuse = None
            if fuse != v.get("fuse") and v.get("fuse") != "none":
                log.warning(
                    "autotune: fuse=%s on %s not applied (peephole "
                    "eligibility) — reporting %s", v.get("fuse"), name,
                    fuse or "unfused")
                if fuse is None:
                    v.pop("fuse")
                else:
                    v["fuse"] = fuse
            if v:
                keep[name] = v
        self.layer_variants = keep
        self._variant_dtype = {
            n: jnp.dtype(v["dtype"]) for n, v in keep.items()
            if v.get("dtype")}

    def autotune_info(self) -> dict:
        """The self-describing `info.autotune` block every metrics
        artifact carries (like info.comm / info.sync): {"active":
        False} when COS_AUTOTUNE is unset, else the plan's key, source,
        and the per-layer variants actually applied to THIS net."""
        if not self.autotune_plan:
            return {"active": False}
        p = self.autotune_plan
        return {"active": True,
                "source": p.get("source", "explicit"),
                "key": p.get("key", {}),
                "tolerance": p.get("tolerance"),
                "measured": p.get("measured"),
                "layers": {n: dict(v)
                           for n, v in self.layer_variants.items()}}

    # ------------------------------------------------------------------
    def init(self, key: Array) -> Params:
        """Initialize all learnable blobs (filler semantics).  Filled
        blob by blob from Python, every distinct fill is a program of
        its own to compile or to fetch from the persistent cache (74
        for ResNet-50, 3.7 s of every warm start).  The constant
        fillers, most of the blobs, have no arithmetic a compiler could
        order another way and are filled by one program; a random
        filler inside a larger program rounds differently in the last
        bit, so those stay one program a shape."""
        from .ops.fillers import fill
        from .ops.layers import stable_hash
        params: Params = {}
        constants = {}
        for lname, specs in self.param_layout.items():
            lkey = jax.random.fold_in(key, stable_hash(lname))
            blobs = {}
            for i, (bname, shape, filler) in enumerate(specs):
                if (filler.type or "constant") == "constant":
                    blobs[bname] = None         # keeps its place
                    constants[lname, bname] = (
                        tuple(int(d) for d in shape), float(filler.value))
                else:
                    blobs[bname] = fill(jax.random.fold_in(lkey, i),
                                        filler, shape, self.dtype)
            params[lname] = blobs
        filled = _constant_blobs(tuple(constants.items()), self.dtype)
        for (lname, bname), blob in filled.items():
            params[lname][bname] = blob
        return params

    def input_names(self) -> List[str]:
        return [n for n, _, _ in self.input_specs]

    def make_dummy_inputs(self, batch_override: Optional[int] = None
                          ) -> Dict[str, Array]:
        out = {}
        for name, shape, kind in self.input_specs:
            if batch_override is not None:
                # time-major (":T") tops carry batch on axis 1, not 0
                ax = 1 if kind.endswith(":T") else 0
                shape = tuple(batch_override if i == ax else d
                              for i, d in enumerate(shape))
            out[name] = jnp.zeros(shape, self.dtype)
        return out

    # ------------------------------------------------------------------
    def apply(self, params: Params, inputs: Dict[str, Array], *,
              train: Optional[bool] = None, rng: Optional[Array] = None,
              net_state: Optional[Dict] = None,
              qscales: Optional[Dict] = None,
              layers: Optional[Sequence[str]] = None
              ) -> Tuple[Dict[str, Array], Dict]:
        """Forward pass. Returns (all blobs, updated_param_blobs).

        The second value maps layer name → [new blob arrays] for layers
        that update their own param blobs during the forward pass
        (BatchNorm running stats).  `Solver.train_step` merges it back
        into params with `merge_forward_state`; stat blobs are pinned to
        lr_mult = decay_mult = 0 so the optimizer never touches them.

        `qscales` ({layer: {blob: f32 scalar}}) carries the publish-
        time max-abs scales for quantized-resident serving weights
        (serving/quant.py): an op receiving an int8 param finds its
        dequant scale via Ctx.qscale and runs the dequant-free kernel
        path.  None (every training/eval caller) is inert.

        `layers` restricts the pass to a subset of compute_layers (run
        in net order) — the pipeline-stage body used by parallel/pp.py
        and serving/forward.py.  The caller supplies the stage's
        boundary blobs via `inputs` and must keep any layer named by
        `fused_bias_lrn` together with its producing conv (one stage),
        since the fused kernel pulls the conv's bias out of `params`."""
        if train is None:
            train = self.state.phase == Phase.TRAIN
        blobs: Dict[str, Array] = dict(inputs)
        ctx = L.Ctx(train=train, rng=rng,
                    state_in=net_state or {}, state_out={},
                    fused_relu_lrn=self.fused_relu_lrn,
                    defer_bias=self._defer_bias,
                    bias_lrn=self._bias_lrn_set,
                    qscales=qscales)
        cast = (self.compute_dtype != self.dtype)
        subset = None if layers is None else set(layers)
        compute = (self.compute_layers if subset is None else
                   [lp for lp in self.compute_layers
                    if lp.name in subset])
        def run_layer(lp, blobs, params):
            op = L.get_op(lp.type)
            ctx.layer_name = lp.name
            ctx.variant = self.layer_variants.get(lp.name)
            # per-layer compute dtype: the autotune plan's dtype variant
            # beats the net-wide compute_dtype (stat layers stay exempt
            # — see the f32_stats comment below); with no variant this
            # is exactly the pre-autotune cast, op for op
            vdt = (None if op.f32_stats
                   else self._variant_dtype.get(lp.name))
            target = (self.dtype if op.f32_stats
                      else (vdt or self.compute_dtype))
            # any per-layer dtype variant makes EVERY layer normalize
            # its floating bottoms to its own target (a bf16 layer's
            # output must cast back up entering its f32 consumer);
            # with no variants this reduces to the pre-autotune gate
            docast = cast or bool(self._variant_dtype)
            lparams = [params[ln][bname] for _, ln, bname, _ in
                       self.param_sources.get(lp.name, ())]
            if lp.name in self.fused_bias_lrn:
                # bias-fused stem LRN: the producing conv's bias rides
                # in as params[0]; its gradient flows back to the conv
                # blob through the fused kernel's VJP
                lparams = [params[self.fused_bias_lrn[lp.name]]["bias"]] \
                    + lparams
            if docast and not op.f32_stats and lparams:
                # non-floating params (int8 quantized-resident serving
                # weights) must pass through untouched — a dtype-policy
                # cast would silently dequantize without the scale
                lparams = [p.astype(target)
                           if jnp.issubdtype(p.dtype, jnp.floating)
                           else p for p in lparams]
            bottoms = [blobs[b] for b in lp.bottom]
            if docast:
                # stat layers (BatchNorm) keep their INPUT at full
                # precision: E[x²]−E[x]² cancels catastrophically in
                # bf16 for unnormalized activations — their target is
                # self.dtype above
                # — and id-carrying bottoms (labels, token ids) are
                # never narrowed: see LayerOp.index_bottoms
                bottoms = [b.astype(target)
                           if jnp.issubdtype(b.dtype, jnp.floating)
                           and b.dtype != target
                           and i not in op.index_bottoms else b
                           for i, b in enumerate(bottoms)]
            if self.remat and train and lparams \
                    and not op.f32_stats:
                # only parameterized layers are checkpointed — wrapping
                # elementwise ops would just block XLA fusion; BatchNorm
                # is excluded because its running-stat side channel
                # (ctx.state_out) must not cross the remat boundary
                kw = ({"policy": self.remat_policy}
                      if self.remat_policy is not None else {})
                fn = jax.checkpoint(
                    lambda p, b, op=op, lp=lp, ctx=ctx:
                    op.apply(ctx, lp, p, b), **kw)
                tops = fn(lparams, bottoms)
            else:
                # the layer's name on every op it emits: device time can
                # be summed by prototxt layer from a profiler trace
                with jax.named_scope(lp.name):
                    tops = op.apply(ctx, lp, lparams, bottoms)
            for name, val in zip(lp.top, tops):
                blobs[name] = val

        blocks = (self.recompute_blocks
                  if train and subset is None and not self.remat else {})
        if blocks:
            note_shared(self.shared_blobs())
        skip: set = set()
        for lp in compute:
            if lp.name in skip:
                continue
            blk = blocks.get(lp.name)
            if blk is None:
                run_layer(lp, blobs, params)
                continue

            def block_fn(bparams, ins, blk=blk):
                local, merged = dict(ins), {**params, **bparams}
                with block_trace(blk["layers"][0].recompute_block):
                    for blp in blk["layers"]:
                        run_layer(blp, local, merged)
                return {n: local[n] for n in blk["exports"]}

            own = {ln: params[ln] for blp in blk["layers"]
                   for _, ln, _, _ in self.param_sources.get(blp.name, ())}
            # computed again in the backward pass, all but what the
            # layers named as they made it (`ops/recompute.py`)
            blobs.update(jax.checkpoint(block_fn, policy=BLOCK_POLICY)(
                own, {n: blobs[n] for n in blk["inputs"]}))
            skip.update(blp.name for blp in blk["layers"])
        return blobs, ctx.state_out

    def loss(self, params: Params, inputs: Dict[str, Array], *,
             train: bool = True, rng: Optional[Array] = None,
             net_state: Optional[Dict] = None
             ) -> Tuple[Array, Tuple[Dict[str, Array], Dict]]:
        """Total weighted loss (for jax.value_and_grad(has_aux=True))."""
        blobs, new_state = self.apply(params, inputs, train=train, rng=rng,
                                      net_state=net_state)
        # the scalar loss ACCUMULATES in f32 regardless of compute dtype
        # (a bf16 running sum over a large blob drops addends)
        total = jnp.zeros((), jnp.float32)
        for name, w in self.loss_weights.items():
            total = total + w * jnp.sum(blobs[name],
                                        dtype=jnp.float32)
        return total, (blobs, new_state)

    def merge_forward_state(self, params: Params,
                            forward_state: Dict[str, List[Array]]) -> Params:
        """Overwrite self-updating param blobs (BatchNorm stats) with the
        values produced by the last forward pass."""
        if not forward_state:
            return params
        out = {ln: dict(bl) for ln, bl in params.items()}
        for lname, blobs in forward_state.items():
            if lname not in self.param_layout:
                continue   # side-channel keys (LSTM hidden, HDF5Output)
            for (bname, _, _), arr in zip(self.param_layout[lname], blobs):
                out[lname][bname] = arr
        return out

    def layer_param_specs(self, lname: str
                          ) -> List[Tuple[str, Tuple[int, ...]]]:
        """(blob, shape) of every blob layer `lname` computes with, its
        own and the ones it reads under a shared name, in its op's
        order; [] for a layer without parameters."""
        return [(name, shape) for name, _, _, shape in
                self.param_sources.get(lname, ())]

    def shared_blobs(self) -> Dict[str, dict]:
        """The blobs one `recompute_block` makes and a block further on
        than the next reads (another layer's keys and values, a scan's
        output as memory): {blob: {"from": layer, "to": [layers],
        "bytes": n}}.  They leave their block as `exports` and enter
        every reader's as `inputs`: kept, not recomputed."""
        order = list(self.recompute_blocks)
        block_of = {lp.name: i for i, first in enumerate(order)
                    for lp in self.recompute_blocks[first]["layers"]}
        out: Dict[str, dict] = {}
        for i, first in enumerate(order):
            blk = self.recompute_blocks[first]
            for blob in blk["exports"]:
                readers = [lp.name for lp in self.compute_layers
                           if blob in lp.bottom
                           and block_of.get(lp.name, i + 1) > i + 1]
                if readers:
                    maker = next(lp.name for lp in blk["layers"]
                                 if blob in lp.top)
                    out[blob] = {
                        "from": maker, "to": readers,
                        "bytes": math.prod(self.blob_shapes[blob])
                        * jnp.dtype(self.compute_dtype).itemsize}
        return out

    def stat_param_layers(self) -> List[str]:
        """Layers whose param blobs are running statistics, not weights
        (op-level f32_stats flag, e.g. BatchNorm)."""
        return [lp.name for lp in self.compute_layers
                if L.get_op(lp.type).f32_stats]

    def num_params(self, params: Optional[Params] = None) -> int:
        if params is not None:
            return sum(int(x.size) for lb in params.values()
                       for x in lb.values())
        return sum(math.prod(s) for specs in self.param_layout.values()
                   for (_, s, _) in specs)
