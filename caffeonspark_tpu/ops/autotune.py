"""Roofline-guided per-layer precision/layout/fusion autotuner.

MFU across the zoo sits at 0.19–0.51 (BENCH_r05) and the knobs that
close the gap — conv layout (NCHW/NHWC/space-to-depth), per-layer
compute dtype, the fused ReLU(+bias)+LRN stem epilogue, flash vs
reference attention, int8 serving matmuls — were global, opt-in and
hand-picked.  This module picks them PER LAYER, by measurement:

  1. rank layers with the roofline model (analysis/roofline.py):
     MXU-bound layers are precision candidates, HBM-bound layers are
     layout/fusion candidates; only the top offenders get measured
     (the tail can't move the step time, so it stays default);
  2. for each ranked layer, enumerate the LEGAL variants (dtype flips
     never touch f32_stats layers — the COS002 precision-floor
     discipline; int8 is serving-forward-only; fusion only where the
     net's peephole proves the producer chain eligible);
  3. A/B each variant by MEASURED steps/s at a pinned numerics
     tolerance against the untuned net — a variant that drifts past
     the tolerance is rejected no matter how fast it is;
  4. the winning plan is a JSON artifact cached per (net digest,
     device_kind, batch, dtype policy), applied at net-build time
     through the layer-op context (`Net(..., autotune=...)` /
     `COS_AUTOTUNE`), and published as `info.autotune` in
     PipelineMetrics so every bench artifact is self-describing.

COS_AUTOTUNE semantics (resolved ONCE at Net construction — never at
trace time, the COS003 discipline):
  * unset / "0"  — INERT: no plan, no variants, training byte-identical;
  * "1"          — apply the cached plan for this net's digest (no
                   cached plan: log and run untuned — tuning is an
                   explicit act, `autotune_net` / `make bench-autotune`,
                   never a construction-time surprise);
  * <path>       — apply that plan file.

Injected floor (CPU benches): COS_AUTOTUNE_FLOOR_GBS (or the
`floor_gbs` argument) models an HBM-bandwidth regime by sleeping
modeled_step_bytes/floor after every measured step — the same
floor-model technique bench_steploop's per-dispatch floor and
bench_gradsync's comm floor use, so byte-reducing variants show their
uplift on hardware whose own memory system isn't the bottleneck.  The
floor applies identically to baseline and candidates and is recorded
in the plan.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from typing import Dict, List, Optional, Tuple

_LOG = logging.getLogger(__name__)

PLAN_SCHEMA = "cos-autotune-plan"
PLAN_VERSION = 1

# layer types the tuner knows variants for
TUNABLE_TYPES = ("Convolution", "InnerProduct", "LRN",
                 "MultiHeadAttention")

# the ambient env knobs that shape the MEASURED baseline and every
# non-variant layer: recorded in the plan key at tune time, compared
# (warn-only) at apply time — a plan measured under COS_CONV_LAYOUT=
# NHWC applied in a bare shell runs its non-variant convs in a regime
# nobody measured.  COS_SERVE_WEIGHT_DTYPE matters the same way for
# serve-mode plans resolved per model: under int8/bf16 RESIDENCY
# (serving/quant.py) the InnerProduct weight arrives pre-quantized and
# the int8 variant's per-call weight-quantization cost — which the
# tuner's A/B measured — is gone, so a plan tuned in one regime and
# applied in the other states the mismatch instead of silently
# reporting stale numbers
AMBIENT_ENV_KNOBS = ("COS_CONV_LAYOUT", "COS_CONV_S2D",
                     "COS_FUSE_RELU_LRN", "COS_FUSE_BIAS_RELU_LRN",
                     "COS_SERVE_WEIGHT_DTYPE")


def ambient_env() -> Dict[str, str]:
    return {k: os.environ[k] for k in AMBIENT_ENV_KNOBS
            if os.environ.get(k) is not None}


# ---------------------------------------------------------------------------
# plan identity + cache
# ---------------------------------------------------------------------------

def net_digest(net_param) -> str:
    """Digest of the net topology (the aot.py idiom): the prototxt
    carries layer geometry AND data-layer batch sizes, so one digest
    identifies the tuned program shape."""
    return hashlib.sha256(str(net_param).encode()).hexdigest()[:16]


def dtype_policy_str(dtype, compute_dtype=None) -> str:
    """THE one grammar for the plan key's dtype-policy term — net.py's
    resolve hook and the tuner's plan key must agree or COS_AUTOTUNE=1
    silently fails open to an untuned run (cache filename mismatch)."""
    import jax.numpy as jnp
    return (f"{jnp.dtype(dtype).name}/"
            f"{jnp.dtype(compute_dtype if compute_dtype is not None else dtype).name}")


def device_kind() -> str:
    try:
        import jax
        return str(getattr(jax.devices()[0], "device_kind",
                           jax.default_backend()))
    except Exception:  # noqa: BLE001 — identity probe must never raise
        return "unknown"


def cache_root() -> str:
    return os.environ.get("COS_AUTOTUNE_CACHE", "artifacts/autotune")


def _slug(s: str) -> str:
    return "".join(c if c.isalnum() else "-" for c in str(s).lower())


def cache_path(digest: str, dev_kind: Optional[str] = None,
               root: Optional[str] = None, mode: str = "train",
               dtype_policy: str = "float32/float32") -> str:
    """One cache slot per (digest, device, mode, dtype policy): a
    serve-tuned plan (forward-only measurements, int8 variants) and a
    train-tuned plan of the same prototxt must never overwrite or
    cross-apply, and neither must f32- and bf16-policy tunes."""
    dev = _slug(dev_kind if dev_kind is not None else device_kind())
    return os.path.join(
        root or cache_root(),
        f"plan-{digest}-{dev}-{_slug(mode)}-{_slug(dtype_policy)}.json")


def plan_cache_path(plan: dict, root: Optional[str] = None) -> str:
    """The cache slot a plan's own key addresses."""
    key = plan.get("key", {})
    return cache_path(key["net_digest"], key.get("device_kind"),
                      root=root, mode=key.get("mode", "train"),
                      dtype_policy=key.get("dtype_policy",
                                           "float32/float32"))


def save_plan(plan: dict, path: Optional[str] = None) -> str:
    """Write the plan artifact (atomic tmp+rename) to `path` or its
    cache slot; returns the path."""
    if path is None:
        path = plan_cache_path(plan)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(plan, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_plan(path: str) -> dict:
    with open(path) as f:
        plan = json.load(f)
    if plan.get("schema") != PLAN_SCHEMA:
        raise ValueError(f"{path}: not a {PLAN_SCHEMA} artifact "
                         f"(schema={plan.get('schema')!r})")
    return plan


def resolve_plan(net_param, state, autotune,
                 dtype_policy: str = "float32/float32"
                 ) -> Tuple[Optional[dict], Dict[str, dict]]:
    """Net-construction hook: (plan, {layer: variant}) for this net.
    `autotune`: None defers to COS_AUTOTUNE (unset/"0" = inert), True
    behaves like COS_AUTOTUNE=1, a str is a plan path, a dict an
    explicit plan.  The cache lookup is keyed by (digest, device,
    mode, dtype policy) — mode from `state.phase` (TRAIN nets read
    train-tuned plans, TEST nets serve-tuned ones).  A plan whose key
    names a DIFFERENT net digest is ignored with a warning
    (force=true in the plan overrides — cross-net application is a
    measured risk the operator takes explicitly)."""
    from ..proto.caffe import Phase

    def _from_cache():
        mode = ("train" if state is None or state.phase == Phase.TRAIN
                else "serve")
        path = cache_path(net_digest(net_param), mode=mode,
                          dtype_policy=dtype_policy)
        if not os.path.exists(path):
            _LOG.info(
                "COS_AUTOTUNE=1: no cached plan at %s — run "
                "scripts/bench_autotune.py (or ops.autotune."
                "autotune_net) to tune this net; running untuned",
                path)
            return None, None
        return load_plan(path), f"cache:{path}"

    plan = None
    source = None
    if isinstance(autotune, dict):
        plan, source = autotune, autotune.get("source", "explicit")
    elif isinstance(autotune, str):
        plan, source = load_plan(autotune), f"file:{autotune}"
    elif autotune is True:
        plan, source = _from_cache()
        if plan is None:
            return None, {}
    else:
        env = os.environ.get("COS_AUTOTUNE", "")
        if env in ("", "0"):
            return None, {}
        if env == "1":
            plan, source = _from_cache()
            if plan is None:
                return None, {}
        else:
            plan, source = load_plan(env), f"file:{env}"
    key = plan.get("key", {})
    want = key.get("net_digest")
    have = net_digest(net_param)
    if want and want != have and not plan.get("force"):
        _LOG.warning(
            "autotune plan is for net digest %s, this net is %s — "
            "ignoring the plan (set force=true in the plan to apply "
            "anyway)", want, have)
        return None, {}
    tuned_env = key.get("env")
    if tuned_env is not None and tuned_env != ambient_env():
        # warn-only: the plan still applies, but its measured uplift /
        # parity described a DIFFERENT ambient regime for the
        # non-variant layers — the operator should re-tune or align
        _LOG.warning(
            "autotune plan was measured under env %s but the current "
            "regime is %s — non-variant layers run an unmeasured "
            "configuration; re-tune or align the knobs",
            tuned_env, ambient_env())
    if source:
        # the RESOLUTION route (cache:/file:/explicit) — the plan's own
        # provenance ("tuned") stays inside the artifact on disk
        plan = dict(plan, source=source)
    return plan, {n: dict(v) for n, v in plan.get("layers", {}).items()}


# ---------------------------------------------------------------------------
# variant enumeration
# ---------------------------------------------------------------------------

def _conv_variants(net, lp, *, dtype_flip: Optional[str]) -> List[dict]:
    from .layers import _conv_geometry, _s2d_geometry_ok
    cp = lp.convolution_param
    s2d_ok = False
    try:
        (kh, kw), (sh, sw), _, (dh, dw) = _conv_geometry(cp)
        c_in = net.blob_shapes[lp.bottom[0]][1]
        s2d_ok = _s2d_geometry_ok(c_in, cp, kh, kw, sh, sw, dh, dw)
    except Exception:  # noqa: BLE001 — geometry probe only prunes
        pass
    # enumerate the layouts that DIFFER from this layer's ambient
    # (env-resolved) path: under COS_CONV_LAYOUT=NHWC the useful
    # candidate is pinning BACK to nchw, and A/B-ing nhwc against
    # itself would just be a wasted compile that noise can accept
    if os.environ.get("COS_CONV_LAYOUT", "NCHW").upper() == "NHWC":
        amb = "nhwc"
    else:
        env_s2d = os.environ.get("COS_CONV_S2D")
        if env_s2d is not None:
            s2d_on = env_s2d == "1"
        else:
            from .route import on_tpu
            s2d_on = on_tpu()
        amb = "s2d" if (s2d_on and s2d_ok) else "nchw"
    candidates = ["nchw", "nhwc"] + (["s2d"] if s2d_ok else [])
    out: List[dict] = [{"layout": lo} for lo in candidates if lo != amb]
    if dtype_flip:
        out.append({"dtype": dtype_flip})
    return out


def _lrn_variants(net, lp) -> List[dict]:
    # eligibility IS net.py's peephole rule — the shared predicates,
    # not a re-implementation.  A looser probe would enumerate
    # variants the candidate build then silently refuses, and under
    # the injected-floor regime the byte model would credit the no-op
    # with a fake uplift.
    from ..net import fusable_relu_for_lrn, prefuse_conv_bias_eligible
    relu = fusable_relu_for_lrn(net.compute_layers, lp)
    if relu is None:
        return []
    out: List[dict] = [{"fuse": "relu"}]
    if prefuse_conv_bias_eligible(net.compute_layers, lp, relu):
        out.append({"fuse": "bias_relu"})
    return out


def legal_variants(net, lp, *, mode: str = "train",
                   allow_dtype: bool = True) -> List[dict]:
    """The legal variant dicts for one layer of `net` (excluding the
    implicit default {}).  `mode` 'serve' additionally admits the int8
    forward matmul for InnerProduct.  The dtype flip goes AGAINST the
    net-wide policy: bf16 candidates on an f32 net (HBM relief), f32
    candidates on a bf16 net (the precision pin — Ctx.precision()
    computes such layers at HIGHEST, so a sensitive layer can buy
    accuracy back if the measured A/B tolerates the cost)."""
    import jax.numpy as jnp
    t = lp.type
    f32_net = jnp.dtype(net.compute_dtype) == jnp.dtype(jnp.float32)
    dtype_flip = (("bfloat16" if f32_net else "float32")
                  if allow_dtype else None)
    if t == "Convolution":
        return _conv_variants(net, lp, dtype_flip=dtype_flip)
    if t == "InnerProduct":
        out = [{"dtype": dtype_flip}] if dtype_flip else []
        if mode == "serve":
            out.append({"int8": True})
        return out
    if t == "LRN":
        return _lrn_variants(net, lp)
    if t == "MultiHeadAttention":
        return [{"attention": "reference"}]
    return []


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

def _rand_inputs(net, seed: int = 0):
    import numpy as np
    import jax.numpy as jnp
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape, kind in net.input_specs:
        if kind.startswith(("label", "int")):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jnp.asarray(
                rs.randn(*shape).astype(np.float32))
    return out


def _build_step(net, mode: str):
    """One jitted measurement step for a candidate net: train =
    loss+grads (the training hot path without the optimizer — the
    tuner must not recurse into Solver, which builds Nets); serve =
    the blob forward."""
    import jax

    if mode == "serve":
        names = tuple(net.output_blobs)

        def fwd(params, inputs):
            blobs, _ = net.apply(params, inputs, train=False)
            return {n: blobs[n] for n in names}
        return jax.jit(fwd)

    rng = jax.random.key(0)

    def step(params, inputs):
        (loss, (blobs, _)), grads = jax.value_and_grad(
            lambda p: net.loss(p, inputs, train=True, rng=rng),
            has_aux=True)(params)
        return loss, {n: blobs[n] for n in net.output_blobs}, grads
    return jax.jit(step)


def _pull(out):
    import jax
    leaf = jax.tree_util.tree_leaves(out)[0]
    jax.device_get(leaf)


def _measure(step, args, *, iters: int, warmup: int,
             sleep_s: float = 0.0):
    for _ in range(max(0, warmup)):
        _pull(step(*args))
        if sleep_s:
            time.sleep(sleep_s)
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = step(*args)
        _pull(out)
        if sleep_s:
            time.sleep(sleep_s)
    dt = time.perf_counter() - t0
    return iters / dt, out


def _ref_values(out):
    """f32 host copies of a step's comparable outputs (loss + output
    blobs; grads excluded — grad drift is bounded through the loss)."""
    import numpy as np
    import jax
    if isinstance(out, tuple):          # train: (loss, blobs, grads)
        loss, blobs = out[0], out[1]
        vals = {"loss": np.asarray(jax.device_get(loss), np.float32)}
    else:                               # serve: blobs
        blobs, vals = out, {}
    for n, v in blobs.items():
        vals[n] = np.asarray(jax.device_get(v), np.float32)
    return vals


def _parity(ref: dict, got: dict) -> float:
    """max over compared tensors of max|a−b| / (max|a| + 1e-6) — the
    pinned relative tolerance metric recorded in the plan."""
    import numpy as np
    worst = 0.0
    for n, a in ref.items():
        b = got.get(n)
        if b is None or a.shape != b.shape:
            return float("inf")
        denom = float(np.max(np.abs(a))) + 1e-6
        worst = max(worst, float(np.max(np.abs(a - b))) / denom)
    return worst


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

def autotune_net(net_param, *, state=None, dtype=None,
                 compute_dtype=None, mode: str = "train",
                 top_layers: int = 6, measure_iters: int = 3,
                 warmup: int = 1, tolerance: float = 5e-2,
                 min_uplift: float = 1.02,
                 floor_gbs: Optional[float] = None,
                 generalize: bool = True, save: bool = True,
                 cache_dir: Optional[str] = None, seed: int = 0) -> dict:
    """Tune one net; returns (and by default caches) the plan dict.

    Greedy coordinate descent over the roofline top offenders: each
    candidate plan is a real Net build + jit + measured steps/s, gated
    on `_parity(...) <= tolerance` against the untuned baseline.  With
    `generalize`, a layer's winning variant is propagated to its
    (type, roofline-bound) class and the composed plan re-measured —
    falling back to the measured-only plan if the propagation regresses
    or breaks parity."""
    import jax
    import jax.numpy as jnp
    from ..analysis import roofline as rl
    from ..net import Net
    from ..proto.caffe import NetState, Phase

    state = state or NetState(phase=Phase.TRAIN
                              if mode == "train" else Phase.TEST)
    dtype = dtype or jnp.float32
    if floor_gbs is None:
        env = os.environ.get("COS_AUTOTUNE_FLOOR_GBS", "")
        floor_gbs = float(env) if env else 0.0

    def build(layers_plan):
        at = ({"schema": PLAN_SCHEMA, "layers": layers_plan}
              if layers_plan else False)
        return Net(net_param, state, dtype=dtype,
                   compute_dtype=compute_dtype, autotune=at)

    # bytes/layer follow the NET-WIDE dtype policy; per-layer variants
    # then override per layer inside the model
    act_b = 2 if (compute_dtype is not None
                  and jnp.dtype(compute_dtype) != jnp.dtype(dtype)) else 4

    def sleep_for(net, layers_plan):
        if not floor_gbs:
            return 0.0
        return rl.step_bytes_total(net, act_bytes=act_b,
                                   param_bytes=act_b,
                                   variants=layers_plan) \
            / (floor_gbs * 1e9)

    base_net = build({})
    params = base_net.init(jax.random.key(seed))
    inputs = _rand_inputs(base_net, seed)
    args = (params, inputs)
    step = _build_step(base_net, mode)
    base_sps, base_out = _measure(step, args, iters=measure_iters,
                                  warmup=warmup,
                                  sleep_s=sleep_for(base_net, {}))
    ref = _ref_values(base_out)

    # roofline ranking: only the top offenders are worth a compile
    rows = rl.classify(rl.analyze_net(base_net, act_bytes=act_b,
                                      param_bytes=act_b))
    by_name = {lp.name: lp for lp in base_net.compute_layers}
    ranked = [r for r in rows if r["type"] in TUNABLE_TYPES
              and r["layer"] in by_name][:max(1, top_layers)]

    plan_layers: Dict[str, dict] = {}
    per_layer: List[dict] = []
    best_sps = base_sps
    # best parity-passing variant per (type, bound) class, accepted or
    # not: a single layer's uplift (~1-2%) sits at the noise floor of
    # a short measurement, but composed across its whole class it can
    # be decisive — the generalize pass re-measures and gates the
    # composition, so seeding it from near-miss candidates is safe
    cand_win: Dict[Tuple[str, str], Tuple[float, dict]] = {}
    for row in ranked:
        lp = by_name[row["layer"]]
        for variant in legal_variants(base_net, lp, mode=mode):
            cand = dict(plan_layers)
            cand[lp.name] = variant
            try:
                net_v = build(cand)
                step_v = _build_step(net_v, mode)
                sps, out_v = _measure(
                    step_v, args, iters=measure_iters, warmup=warmup,
                    sleep_s=sleep_for(net_v, cand))
                par = _parity(ref, _ref_values(out_v))
            except Exception as e:  # noqa: BLE001 — an unbuildable
                #   variant loses the A/B, it must not kill the tune
                _LOG.warning("autotune: variant %s on %s failed: %s",
                             variant, lp.name, e)
                per_layer.append({"layer": lp.name, "type": lp.type,
                                  "bound": row["bound"],
                                  "variant": variant, "error": str(e),
                                  "accepted": False})
                continue
            accepted = (par <= tolerance
                        and sps >= best_sps * min_uplift)
            if par <= tolerance and sps > base_sps:
                ckey = (lp.type, row["bound"])
                if ckey not in cand_win or sps > cand_win[ckey][0]:
                    cand_win[ckey] = (sps, variant)
            per_layer.append({"layer": lp.name, "type": lp.type,
                              "bound": row["bound"], "variant": variant,
                              "steps_per_sec": round(sps, 4),
                              "uplift_vs_base": round(sps / base_sps, 4),
                              "parity_max_rel_diff": round(par, 6),
                              "accepted": accepted})
            if accepted:
                plan_layers[lp.name] = variant
                best_sps = sps

    # generalize winners across each (type, bound) class, then gate the
    # composed plan on one more measured A/B — never ship an unmeasured
    # composition.  Per-layer accepted winners take precedence; classes
    # with only near-miss candidates still get a shot, because the
    # composed measurement (not the noisy per-layer one) is the gate.
    generalized_from: Dict[str, str] = {}
    cls_win: Dict[Tuple[str, str], dict] = {}
    for row in ranked:
        v = plan_layers.get(row["layer"])
        if v:
            cls_win.setdefault((row["type"], row["bound"]), v)
    for ckey, (_, v) in cand_win.items():
        cls_win.setdefault(ckey, v)
    if generalize and cls_win:
        cand = dict(plan_layers)
        for r in rows:
            key = (r["type"], r["bound"])
            if key in cls_win and r["layer"] not in cand \
                    and r["layer"] in by_name:
                lp2 = by_name[r["layer"]]
                if cls_win[key] in legal_variants(base_net, lp2,
                                                 mode=mode):
                    cand[r["layer"]] = dict(cls_win[key])
                    generalized_from[r["layer"]] = "class"
        if len(cand) > len(plan_layers):
            try:
                net_g = build(cand)
                step_g = _build_step(net_g, mode)
                sps_g, out_g = _measure(
                    step_g, args, iters=measure_iters, warmup=warmup,
                    sleep_s=sleep_for(net_g, cand))
                par_g = _parity(ref, _ref_values(out_g))
                if par_g <= tolerance and sps_g >= max(
                        best_sps, base_sps * min_uplift):
                    plan_layers, best_sps = cand, sps_g
                else:
                    generalized_from = {}
            except Exception as e:  # noqa: BLE001 — see above
                _LOG.warning("autotune: generalized plan failed: %s", e)
                generalized_from = {}

    dg = net_digest(net_param)
    dk = device_kind()
    batch = base_net.input_specs[0][1][0] if base_net.input_specs else 0
    plan = {
        "schema": PLAN_SCHEMA,
        "version": PLAN_VERSION,
        "model_version": rl.MODEL_VERSION,
        "source": "tuned",
        "key": {
            "net_digest": dg,
            "device_kind": dk,
            "batch": int(batch),
            "dtype_policy": dtype_policy_str(dtype, compute_dtype),
            "mode": mode,
            "env": ambient_env(),
        },
        "tolerance": tolerance,
        "layers": plan_layers,
        "generalized": sorted(generalized_from),
        "measured": {
            "baseline_steps_per_sec": round(base_sps, 4),
            "tuned_steps_per_sec": round(best_sps, 4),
            "uplift": round(best_sps / base_sps, 4),
            "floor_gbs": floor_gbs,
            "measure_iters": measure_iters,
            "per_layer": per_layer,
        },
    }
    if save:
        path = save_plan(plan, None if cache_dir is None
                         else plan_cache_path(plan, cache_dir))
        _LOG.info("autotune: plan cached at %s (uplift %.2fx, %d "
                  "layer variants)", path, best_sps / base_sps,
                  len(plan_layers))
    return plan
