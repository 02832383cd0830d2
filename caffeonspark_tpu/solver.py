"""Solver: Caffe SolverParameter semantics as a jitted JAX train step.

TPU-native equivalent of caffe::Solver/SGDSolver consumed through
`CaffeNet<Dtype>::train` (`caffe-distri/src/main/cpp/CaffeNet.cpp:707-729`,
`solver->Step(1)`), re-designed as a pure function:

    (params, opt_state, inputs, rng) --train_step--> (params', opt_state',
                                                      outputs)

with `jax.jit(..., donate_argnums=(0, 1))` so parameter and momentum
buffers update in place in HBM.  Reproduced Caffe behaviors:

  * learning-rate policies fixed/step/exp/inv/multistep/poly/sigmoid
    (sgd_solver.cpp GetLearningRate), computed with jnp ops so the
    iteration counter stays a traced scalar — no recompiles per step;
  * per-blob lr_mult/decay_mult from layer `param {}` specs;
  * L2/L1 regularization (weight_decay × decay_mult);
  * clip_gradients by global L2 norm;
  * iter_size gradient accumulation;
  * solver types SGD / Nesterov / AdaGrad / RMSProp / AdaDelta / Adam
    (update rules follow the corresponding caffe solver .cpp files);
  * rank/device seeding: seed = random_seed + rank
    (`CaffeNet.cpp:614-618`).

Gradient averaging across devices (the 1/solver_count scaling in
`parallel_cpu.cpp:120-122` + SocketSync shard exchange) is NOT here — it
is a `jax.lax.pmean` inserted by `parallel.dp` when the step is wrapped
for a mesh.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .net import Net, Params
from .proto.caffe import (NetParameter, NetState, Phase, SolverParameter)

Array = jax.Array


class OptState(NamedTuple):
    """Optimizer state: iteration counter + per-param history pytrees."""
    iter: Array                 # int32 scalar
    history: Params             # momentum / accumulated squared grads
    history2: Params            # second moment (Adam) / delta accum (AdaDelta)


@functools.partial(jax.jit, static_argnames="dtype")
def _zeros_like_params(params: Params, dtype=None) -> Params:
    # one program for the whole tree, like Net.init
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros_like(p, dtype=dtype), params)


def learning_rate(sp: SolverParameter, it: Array) -> Array:
    """Caffe GetLearningRate — traced-friendly."""
    policy = sp.lr_policy or "fixed"
    base = sp.base_lr
    itf = it.astype(jnp.float32)
    if policy == "fixed":
        return jnp.asarray(base, jnp.float32)
    if policy == "step":
        step = jnp.floor(itf / max(1, sp.stepsize))
        return base * jnp.power(sp.gamma, step)
    if policy == "exp":
        return base * jnp.power(sp.gamma, itf)
    if policy == "inv":
        return base * jnp.power(1.0 + sp.gamma * itf, -sp.power)
    if policy == "multistep":
        steps = jnp.asarray(list(sp.stepvalue) or [1 << 30], jnp.int32)
        current = jnp.sum((it >= steps).astype(jnp.int32))
        return base * jnp.power(sp.gamma, current.astype(jnp.float32))
    if policy == "poly":
        frac = jnp.clip(itf / max(1, sp.max_iter), 0.0, 1.0)
        return base * jnp.power(1.0 - frac, sp.power)
    if policy == "sigmoid":
        return base / (1.0 + jnp.exp(-sp.gamma * (itf - sp.stepsize)))
    raise ValueError(f"unknown lr_policy {policy!r}")


class Solver:
    """Owns the train/test Nets compiled from a SolverParameter and builds
    the jitted train/eval steps."""

    def __init__(self, solver_param: SolverParameter,
                 net_param: Optional[NetParameter] = None, *,
                 rank: int = 0, dtype=jnp.float32, compute_dtype=None,
                 state_dtype=None, grad_sync=None):
        self.param = solver_param
        self.rank = rank
        # optimizer-history dtype (default: match each param blob).
        # bfloat16 halves the optimizer's HBM round trip — on CaffeNet
        # b256 that is ~300 MB/step, the single biggest remaining lever
        # per scripts/roofline.py (fc6/fc7 are optimizer-traffic-bound,
        # not matmul-bound).  _apply_update already preserves history
        # dtype (h_n.astype(h.dtype)): arithmetic upcasts to f32, only
        # the STORED momentum is rounded.  COS_STATE_DTYPE=bfloat16
        # flips it globally.
        if state_dtype is None:
            env = os.environ.get("COS_STATE_DTYPE", "")
            state_dtype = jnp.dtype(env).type if env else None
        stype = (solver_param.type or "SGD").upper()
        if (state_dtype is not None
                and jnp.dtype(state_dtype).itemsize < 4
                and stype not in ("SGD", "NESTEROV")):
            # second-moment accumulators (Adam/AdaGrad/RMSProp/AdaDelta
            # keep them in `history`/`history2`) change by ~1e-3
            # relative per step — below bf16 ulp, so a reduced state
            # dtype would freeze them after warm-up.  Only the
            # momentum-style first moments tolerate it.
            import logging
            logging.getLogger(__name__).warning(
                "COS_STATE_DTYPE=%s ignored for solver type %s "
                "(second-moment accumulators need >=f32)",
                jnp.dtype(state_dtype).name, stype)
            state_dtype = None
        self.state_dtype = state_dtype
        if net_param is None:
            raise ValueError("net_param required (driver resolves "
                             "solver.net path → NetParameter)")
        self.net_param = net_param

        train_state = NetState(phase=Phase.TRAIN)
        if solver_param.has("train_state"):
            train_state = solver_param.train_state.clone()
            train_state.phase = Phase.TRAIN
        self.train_net = Net(net_param, train_state, dtype=dtype,
                             compute_dtype=compute_dtype)

        test_state = NetState(phase=Phase.TEST)
        if solver_param.test_state:
            test_state = solver_param.test_state[0].clone()
            test_state.phase = Phase.TEST
        try:
            self.test_net: Optional[Net] = Net(net_param, test_state,
                                               dtype=dtype,
                                               compute_dtype=compute_dtype)
            if not self.test_net.compute_layers:
                self.test_net = None
        except Exception:
            self.test_net = None

        seed = solver_param.random_seed
        if seed < 0:
            seed = 1701  # caffe uses a clock seed; fixed default for replay
        # weight init must be IDENTICAL on every rank (the reference
        # syncs weights at start via the on_start exchange; with SPMD
        # replication, identical init IS the sync) — only the
        # per-iteration dropout/augment stream is rank-decorrelated
        # (seed = random_seed + rank, CaffeNet.cpp:614-618)
        self.init_key = jax.random.key(int(seed))
        self.key = jax.random.key(int(seed) + rank)
        self.solver_type = (solver_param.type or "SGD").upper()

        self._lr_mults, self._decay_mults = self._collect_mults()
        # explicit gradient-exchange layer (COS_GRAD_SYNC): inert in
        # `default` mode; ParallelSolver binds the mesh before any step
        # is traced.  Runtime import — parallel.dp imports this module.
        if grad_sync is None:
            from .parallel.gradsync import make_gradsync
            grad_sync = make_gradsync(self.train_net)
        self.grad_sync = grad_sync
        # sync-mode policy (COS_SYNC_MODE): resolved HERE, once, like
        # grad_sync — lockstep (the default) constructs nothing and
        # changes nothing; the relaxed modes are driven by the runtime
        # (mini_cluster) through parallel/syncmode.py, the traced step
        # itself is identical in every mode
        from .parallel.syncmode import resolve_policy
        self.sync_policy = resolve_policy()
        # COS_RECOMPILE_GUARD=1: every jitted step is watched and a
        # steady-state recompile (shape drift, trace-time host read)
        # raises instead of silently storming XLA (analysis/runtime.py)
        from .analysis.runtime import maybe_recompile_guard
        self._recompile_guard = maybe_recompile_guard("solver")
        self._jit_train_step = None
        self._jit_train_step_many: Dict[int, object] = {}
        self._jit_eval_step = None

    # ------------------------------------------------------------------
    def _collect_mults(self) -> Tuple[Params, Params]:
        """Per-blob lr/decay multipliers from layer `param {}` specs."""
        lr_m: Dict[str, Dict[str, float]] = {}
        dc_m: Dict[str, Dict[str, float]] = {}
        net = self.train_net
        by_name = {lp.name: lp for lp in net.compute_layers}
        for lname in net.param_layout:
            lp = by_name[lname]
            lr_m[lname] = {}
            dc_m[lname] = {}
            # `param {}` i is the layer's blob i, one it reads under a
            # shared name included: the owner's spec is the blob's
            for i, (bname, owner, _, _) in enumerate(
                    net.param_sources[lname]):
                if owner != lname:
                    continue
                if i < len(lp.param):
                    ps = lp.param[i]
                    lr_m[lname][bname] = (ps.lr_mult
                                          if ps.has("lr_mult") else 1.0)
                    dc_m[lname][bname] = (ps.decay_mult
                                          if ps.has("decay_mult") else 1.0)
                else:
                    lr_m[lname][bname] = 1.0
                    dc_m[lname][bname] = 1.0
        # BatchNorm stat blobs are updated by the forward pass, never by
        # the optimizer (Caffe forces lr_mult 0 on them)
        for lname in net.stat_param_layers():
            for bname in lr_m.get(lname, {}):
                lr_m[lname][bname] = 0.0
                dc_m[lname][bname] = 0.0
        return lr_m, dc_m

    # ------------------------------------------------------------------
    def init(self) -> Tuple[Params, OptState]:
        params = self.train_net.init(self.init_key)
        return params, self.init_state(params)

    def init_state(self, params: Params) -> OptState:
        return OptState(
            iter=jnp.zeros((), jnp.int32),
            history=_zeros_like_params(params, self.state_dtype),
            history2=_zeros_like_params(params, self.state_dtype))

    # ------------------------------------------------------------------
    def _apply_update(self, params: Params, grads: Params, state: OptState,
                      lr: Array) -> Tuple[Params, OptState]:
        sp = self.param
        momentum = sp.momentum
        wd = sp.weight_decay
        l1 = sp.regularization_type == "L1"
        t = self.solver_type
        it1 = (state.iter + 1).astype(jnp.float32)

        # Caffe order (SGDSolver::ApplyUpdate): ClipGradients on the raw
        # accumulated diffs FIRST, then Normalize (1/iter_size), then
        # Regularize.  Our grads arrive already normalized (sum/iter_size),
        # and ||sum|| = iter_size*||mean||, so clipping the mean against
        # threshold/iter_size is exactly Caffe's clip-the-sum
        if sp.clip_gradients > 0:
            thresh = sp.clip_gradients / max(1, int(sp.iter_size))
            leaves = jax.tree_util.tree_leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
            scale = jnp.where(gnorm > thresh, thresh / gnorm, 1.0)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

        def reg(g, w, dm):
            if wd == 0.0 or dm == 0.0:
                return g
            if l1:
                return g + wd * dm * jnp.sign(w)
            return g + wd * dm * w

        grads = {ln: {bn: reg(g, params[ln][bn],
                              self._decay_mults[ln][bn])
                      for bn, g in bl.items()}
                 for ln, bl in grads.items()}

        new_p: Params = {}
        new_h: Params = {}
        new_h2: Params = {}
        for ln, bl in params.items():
            new_p[ln] = {}
            new_h[ln] = {}
            new_h2[ln] = {}
            for bn, w in bl.items():
                g = grads[ln][bn]
                h = state.history[ln][bn]
                h2 = state.history2[ln][bn]
                local_lr = lr * self._lr_mults[ln][bn]
                if t == "SGD":
                    upd = local_lr * g + momentum * h
                    w2, h_n, h2_n = w - upd, upd, h2
                elif t == "NESTEROV":
                    h_n = local_lr * g + momentum * h
                    upd = (1 + momentum) * h_n - momentum * h
                    w2, h2_n = w - upd, h2
                elif t == "ADAGRAD":
                    h_n = h + g * g
                    w2 = w - local_lr * g / (jnp.sqrt(h_n) + sp.delta)
                    h2_n = h2
                elif t == "RMSPROP":
                    h_n = sp.rms_decay * h + (1 - sp.rms_decay) * g * g
                    w2 = w - local_lr * g / (jnp.sqrt(h_n) + sp.delta)
                    h2_n = h2
                elif t == "ADADELTA":
                    h_n = momentum * h + (1 - momentum) * g * g
                    upd = g * jnp.sqrt((h2 + sp.delta) / (h_n + sp.delta))
                    h2_n = momentum * h2 + (1 - momentum) * upd * upd
                    w2 = w - local_lr * upd
                elif t == "ADAM":
                    b1, b2 = momentum, sp.momentum2
                    h_n = b1 * h + (1 - b1) * g
                    h2_n = b2 * h2 + (1 - b2) * g * g
                    corr = (jnp.sqrt(1.0 - jnp.power(b2, it1))
                            / (1.0 - jnp.power(b1, it1)))
                    w2 = w - local_lr * corr * h_n / (jnp.sqrt(h2_n)
                                                      + sp.delta)
                else:
                    raise ValueError(f"unknown solver type {t!r}")
                # keep each blob's dtype (the f32 lr scalar would
                # silently upcast bf16 nets to f32 after one update)
                new_p[ln][bn] = w2.astype(w.dtype)
                new_h[ln][bn] = h_n.astype(h.dtype)
                new_h2[ln][bn] = h2_n.astype(h2.dtype)
        return new_p, OptState(iter=state.iter + 1, history=new_h,
                               history2=new_h2)

    # ------------------------------------------------------------------
    def train_step_fn(self):
        """The pure (params, opt_state, inputs, rng) step — wrap with jit
        or hand to parallel.dp for mesh execution.

        With `iter_size > 1` (gradient accumulation, solver prototxt),
        the incoming batch is reshaped to (iter_size, B/iter_size, ...)
        INSIDE the step (so every caller's (B, ...) contract still
        holds) and a `lax.scan` accumulates gradients over the
        sub-batches before ONE optimizer update — Caffe's
        Normalize-by-iter_size semantics.  BatchNorm running stats are
        threaded through the scan carry so each forward compounds them
        (Caffe updates per forward); reported output blobs are the mean
        over sub-batches."""
        net = self.train_net
        iter_size = max(1, int(self.param.iter_size))
        tmajor = {n for n, _, kind in net.input_specs
                  if kind.endswith(":T")}
        stat_layers = net.stat_param_layers()
        # explicit gradient exchange (parallel/gradsync.py): backward
        # hooks emit each bucket's collective mid-backward when
        # eligible; otherwise the finished grad pytree is transformed
        # below.  Both trace-time booleans — `default` mode adds no ops
        # and the step stays byte-identical to the implicit exchange.
        gs = self.grad_sync
        hooks_on = gs is not None and gs.use_hooks(iter_size)
        exchange_on = (gs is not None and gs.enabled and not hooks_on)

        def loss_and_grads(params, inputs, rng):
            def loss_fn(p):
                if hooks_on:
                    p = gs.attach(p)
                total, (blobs, fwd_state) = net.loss(p, inputs,
                                                     train=True, rng=rng)
                return total, (blobs, fwd_state)
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def _split(inputs):
            out = {}
            for k, v in inputs.items():
                ax = 1 if k in tmajor else 0
                b = v.shape[ax]
                if b % iter_size:
                    raise ValueError(
                        f"batch {b} not divisible by iter_size "
                        f"{iter_size} (input {k!r})")
                if ax == 0:
                    out[k] = v.reshape((iter_size, b // iter_size)
                                       + v.shape[1:])
                else:
                    t = v.shape[0]
                    r = v.reshape((t, iter_size, b // iter_size)
                                  + v.shape[2:])
                    out[k] = jnp.moveaxis(r, 1, 0)
            return out

        def step(params: Params, state: OptState,
                 inputs: Dict[str, Array], rng: Array):
            if iter_size == 1:
                (loss, (blobs, fwd_state)), grads = loss_and_grads(
                    params, inputs, rng)
                if exchange_on:
                    grads = gs.exchange(grads, rng)
                outputs = {name: blobs[name]
                           for name in net.output_blobs}
            else:
                subs = _split(inputs)

                def body(carry, xs):
                    stats, gacc, oacc = carry
                    sub, sub_rng = xs
                    p = {**params, **stats}
                    (l, (blobs, fwd)), g = loss_and_grads(p, sub,
                                                          sub_rng)
                    gacc = jax.tree_util.tree_map(jnp.add, gacc, g)
                    oacc = {name: oacc[name] + blobs[name]
                            for name in oacc}
                    merged = net.merge_forward_state(
                        {ln: stats[ln] for ln in stats}, fwd)
                    return (merged, gacc, oacc), None

                zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
                # output shapes for the RUNTIME sub-batch (construction
                # shapes in net.blob_shapes carry the config batch size)
                sub0 = jax.tree_util.tree_map(lambda v: v[0], subs)
                out_abs = jax.eval_shape(
                    lambda p, s: {n: net.apply(p, s, train=True,
                                               rng=rng)[0][n]
                                  for n in net.output_blobs},
                    params, sub0)
                zero_o = {n: jnp.zeros(a.shape, a.dtype)
                          for n, a in out_abs.items()}
                stats0 = {ln: params[ln] for ln in stat_layers}
                rngs = jax.random.split(rng, iter_size)
                (stats, gsum, osum), _ = jax.lax.scan(
                    body, (stats0, zero_g, zero_o), (subs, rngs))
                grads = jax.tree_util.tree_map(
                    lambda g: g / iter_size, gsum)
                if exchange_on:
                    # ONE exchange per optimizer step, after the
                    # iter_size accumulation (Caffe's Normalize-then-
                    # exchange order)
                    grads = gs.exchange(grads, rng)
                outputs = {name: v / iter_size
                           for name, v in osum.items()}
                fwd_state = {ln: [stats[ln][bn] for bn, _, _ in
                                  net.param_layout[ln]]
                             for ln in stat_layers}
            lr = learning_rate(self.param, state.iter)
            with jax.named_scope("update"):
                params2, state2 = self._apply_update(params, grads, state,
                                                     lr)
            # BatchNorm running stats updated by the forward pass(es)
            params2 = net.merge_forward_state(params2, fwd_state)
            outputs["lr"] = lr
            return params2, state2, outputs

        return step

    def jit_train_step(self):
        if self._jit_train_step is None:
            from .analysis.runtime import (maybe_guard_jit,
                                           maybe_poison_donation)
            fn = jax.jit(self.train_step_fn(), donate_argnums=(0, 1))
            fn = maybe_guard_jit(self._recompile_guard,
                                 "solver.train_step", fn, allow=1)
            self._jit_train_step = maybe_poison_donation(fn, (0, 1))
        return self._jit_train_step

    # ------------------------------------------------------------------
    def build_train_step_many(self, k: int):
        """Fused K-step train step: `jax.lax.scan` over a stacked
        `(K, batch…)` input block (axis 0 = the chunk axis, prepended
        to every input's per-step shape — time-major tops become
        (K, T, B, …)).

            (params, opt_state, stacked_inputs) -->
                (params', opt_state', stacked_outputs)

        One XLA program runs K solver iterations without returning to
        Python: the LR schedule, the iteration counter, gradient
        clipping and iter_size accumulation are already traced-friendly
        and advance on-device through the scan carry.  The per-step
        dropout/augment rng is derived INSIDE the scan as
        `fold_in(self.key, opt_state.iter)` — bit-identical to the
        host-side `step_rng(it)` stream, so a fused chunk reproduces K
        inline steps exactly (tests/test_steploop.py pins byte parity).
        Outputs come back stacked (K, …) per blob; `outputs['lr'][i]`
        is iteration i's learning rate."""
        if k < 1:
            raise ValueError(f"steps-per-loop k must be >= 1, got {k}")
        step = self.train_step_fn()
        key = self.key

        def fused(params: Params, state: OptState,
                  stacked: Dict[str, Array]):
            def body(carry, xs):
                p, s = carry
                rng = jax.random.fold_in(key, s.iter)
                p2, s2, out = step(p, s, xs, rng)
                return (p2, s2), out

            (p, s), outs = jax.lax.scan(body, (params, state), stacked,
                                        length=k)
            return p, s, outs

        return fused

    def jit_train_step_many(self, k: int):
        """Jitted fused K-step program, cached per k (the runtime only
        ever compiles the configured K; boundary remainders reuse the
        single-step program instead of compiling odd sizes)."""
        if k not in self._jit_train_step_many:
            from .analysis.runtime import (maybe_guard_jit,
                                           maybe_poison_donation)
            fn = jax.jit(self.build_train_step_many(k),
                         donate_argnums=(0, 1))
            fn = maybe_guard_jit(self._recompile_guard,
                                 f"solver.train_step_many[k={k}]",
                                 fn, allow=1)
            self._jit_train_step_many[k] = maybe_poison_donation(
                fn, (0, 1))
        return self._jit_train_step_many[k]

    # ------------------------------------------------------------------
    def eval_step_fn(self):
        """Validation forward — constructed by the shared blob-forward
        builder (serving/forward.py), so serving, batch extract, and
        validation trace one implementation."""
        net = self.test_net
        assert net is not None, "no TEST-phase net in this config"
        from .serving.forward import make_forward_fn
        return make_forward_fn(net, tuple(net.output_blobs))

    def jit_eval_step(self):
        if self._jit_eval_step is None:
            from .analysis.runtime import maybe_guard_jit
            self._jit_eval_step = maybe_guard_jit(
                self._recompile_guard, "solver.eval_step",
                jax.jit(self.eval_step_fn()), allow=1)
        return self._jit_eval_step

    # ------------------------------------------------------------------
    def step_rng(self, it: int) -> Array:
        """Per-iteration dropout/augment key, decorrelated by rank."""
        return jax.random.fold_in(self.key, it)

    @property
    def max_iter(self) -> int:
        return self.param.max_iter

    @property
    def test_interval(self) -> int:
        return self.param.test_interval

    @property
    def test_iter(self) -> int:
        return self.param.test_iter[0] if self.param.test_iter else 0
