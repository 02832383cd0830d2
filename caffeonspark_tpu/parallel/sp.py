"""Sequence/context parallelism: ring attention over the `sp` mesh axis.

The reference handles sequences only by single-device time-unrolled LSTM
(SURVEY §5.7 — no SP/CP of any kind).  Long-context support is
first-class here: sequences are sharded along time across the `sp` axis,
and attention runs as a **ring**: each step every device computes a
partial (flash-style, numerically stable online-softmax) attention
against its resident K/V block, then rotates K/V to its ring neighbor
with `lax.ppermute` — ICI traffic overlapping MXU compute, total memory
O(T/S) per device (Ring Attention, Liu et al. 2023; blockwise parallel
transformers).

`ring_attention` is the shard_map-ready collective op; `attention` is
the single-device reference implementation (also the parity oracle in
tests).  The LSTM path gets sequence scaling separately via its hoisted
(T·B, D)×(D, 4N) input projection, which XLA shards on `sp` when the
time axis carries a sharding.
"""

from __future__ import annotations

import math
from functools import partial
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-mesh-axes checker disabled: a
    pallas_call's outputs carry no such metadata, which the default
    checker rejects (used by ring attention's flash mode and
    ops.layers' per-device kernel routes)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def refuse_time_sharding(net) -> None:
    """A net with a layer whose type says its time axis cannot be cut
    (`ops.layers.LayerOp.time_sharding`) is refused on a mesh that
    shards time, by the first such layer's name and reason, before
    anything is traced."""
    from ..ops.layers import get_op    # lazy: layers imports this module
    bad = []
    for lp in net.compute_layers:
        ask = get_op(lp.type).time_sharding
        reason = ask(lp) if ask is not None else None
        if reason:
            bad.append((lp.name, reason))
    if bad:
        name, (what, why) = bad[0]
        more = sum(reason == bad[0][1] for _, reason in bad) - 1
        raise ValueError(
            f"sequence parallelism (mesh axis sp > 1) is not written for "
            f"{what} ({name!r} and {more} more): {why}; use dp / ep / pp "
            "axes for this net")


def attention(q: Array, k: Array, v: Array, *, causal: bool = False,
              q_offset: int = 0, k_offset: int = 0,
              window: int = 0) -> Array:
    """Reference softmax attention. q,k,v: (B, H, T, D); k and v may
    hold H / g heads, query head h then reads key/value head h // g.
    `window` > 0 (with `causal`): row t sees the `window` columns
    t - window < s <= t alone."""
    if window and not causal:
        raise ValueError("attention: a window needs causal=True")
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, tq, _ = q.shape
    g = h // k.shape[1]
    if g > 1:       # the group as rows of q: no repeated k or v
        q = q.reshape(b, h // g, g * tq, q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        qpos = q_offset + jnp.arange(tq)[:, None]
        kpos = k_offset + jnp.arange(k.shape[2])[None, :]
        mask = qpos >= kpos
        if window:
            mask &= qpos - kpos < window
        s = jnp.where(jnp.tile(mask, (g, 1)) if g > 1 else mask, s,
                      -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o.reshape(b, h, tq, -1) if g > 1 else o


def flash_block_size(t: int):
    """Flash kernel block for a local sequence extent t: 128 when it
    tiles; whole-shard for small shards; None = shape unsuited (a
    whole-shard block would blow VMEM) — callers fall back to the
    einsum accumulate.  The ONE place that knows the eligibility rule
    (used by the ring body here and ops.layers' mesh dispatch)."""
    if t % 128 == 0:
        return 128
    if t <= 256 and t % 8 == 0:
        return t
    return None


def _ring_attention_local(q: Array, k: Array, v: Array, *, axis_name: str,
                          causal: bool, flash=False) -> Array:
    """Per-shard body (inside shard_map): q,k,v are the LOCAL time blocks
    (B, H, T_local, D)."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    qpos = idx * t_q + jnp.arange(t_q)           # global query positions
    bq, bk = flash_block_size(t_q), flash_block_size(t_k)
    use_flash = bool(flash) and bq is not None and bk is not None
    if use_flash:
        interp = flash == "interpret"
        if t_q == t_k:
            # fused + differentiable custom-VJP ring
            return _make_ring_flash(axis_name, causal, bq, bk,
                                    interp)(q, k, v)
        # unequal shard extents (cross-attention): fused Pallas
        # forward + einsum-ring backward (see _make_ring_flash_cross)
        return _make_ring_flash_cross(axis_name, causal, bq, bk,
                                      interp)(q, k, v)

    def accumulate(m, l, o, k_blk, v_blk, src):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            kpos = src * t_k + jnp.arange(t_k)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows: exp against a finite max
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(m - m_safe)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p,
                                                 v_blk)
        return m_new, l_new, o_new

    def body(step, carry):
        m, l, o, k_blk, v_blk = carry
        # rotate K/V around the ring (neighbor exchange on ICI), then
        # accumulate — block 0 is handled before the loop, so no
        # superfluous rotation happens after the last accumulation
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        m, l, o = accumulate(m, l, o, k_blk, v_blk, (idx - step) % n)
        return m, l, o, k_blk, v_blk

    # derive from q so the carry is device-varying like the loop outputs
    # (shard_map VMA typing requires carry in/out types to match)
    m0 = jnp.full(q.shape[:-1], -jnp.inf, q.dtype) + q[..., 0] * 0
    l0 = jnp.zeros(q.shape[:-1], q.dtype) + q[..., 0] * 0
    o0 = jnp.zeros(q.shape, q.dtype) + q * 0
    m, l, o = accumulate(m0, l0, o0, k, v, idx)
    m, l, o, _, _ = lax.fori_loop(1, n, body, (m, l, o, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _flash_ring_forward(q: Array, k: Array, v: Array, *, axis_name: str,
                        causal: bool, bq: int, bk: int, interpret: bool):
    """Fused flash ring forward (the ONE copy of the ring loop): K/V
    shards rotate on ICI ppermute, each hop folds into the
    online-softmax (m, l, acc) carry via flash_block_update.  Returns
    (out, lse); lse = m + log(l) is the VJP residual for the
    differentiable wrapper (unused by the forward-only caller).
    Causal runs skip fully-masked hops (K entirely after Q) — on
    average (n-1)/2 kernel launches saved per device per pass."""
    from ..ops.pallas_kernels import flash_block_update
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    bh = b * h

    def hop(m, l, o, k_blk, v_blk, src):
        mf, lf, of = flash_block_update(
            q.reshape(bh, t_q, d), k_blk.reshape(bh, t_k, d),
            v_blk.reshape(bh, t_k, d), m.reshape(bh, t_q),
            l.reshape(bh, t_q), o.reshape(bh, t_q, d),
            idx * t_q, src * t_k, causal=causal, block_q=bq,
            block_k=bk, interpret=interpret)
        return (mf.reshape(b, h, t_q), lf.reshape(b, h, t_q),
                of.reshape(b, h, t_q, d))

    def maybe_hop(m, l, o, k_blk, v_blk, src):
        if not causal:
            return hop(m, l, o, k_blk, v_blk, src)
        # contributes iff the last q row can see the first k row
        return lax.cond((idx + 1) * t_q > src * t_k,
                        lambda m_, l_, o_: hop(m_, l_, o_, k_blk,
                                               v_blk, src),
                        lambda m_, l_, o_: (m_, l_, o_),
                        m, l, o)

    def body(step, carry):
        m, l, o, k_blk, v_blk = carry
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        m, l, o = maybe_hop(m, l, o, k_blk, v_blk, (idx - step) % n)
        return m, l, o, k_blk, v_blk

    # device-varying carry init (shard_map VMA typing), f32 stats
    m0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32) \
        + q[..., 0].astype(jnp.float32) * 0
    l0 = jnp.zeros(q.shape[:-1], jnp.float32) \
        + q[..., 0].astype(jnp.float32) * 0
    o0 = jnp.zeros(q.shape, jnp.float32) + q.astype(jnp.float32) * 0
    m, l, o = maybe_hop(m0, l0, o0, k, v, idx)
    m, l, o, _, _ = lax.fori_loop(1, n, body, (m, l, o, k, v))
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)                        # (B, H, t_q) f32
    return out, lse


def _make_ring_flash(axis_name: str, causal: bool, bq: int, bk: int,
                     interpret: bool):
    """Differentiable fused ring attention (equal shard extents).

    Forward: _flash_ring_forward, keeping the log-sum-exp residual.

    Backward: a second ring pass.  Each device keeps its K/V shard
    resident and the (q, dO, lse, delta, dq-accumulator) tuple rotates;
    at each hop the resident shard contributes via the flash backward
    kernels (flash_bwd_block) — causal kernels for the diagonal pair,
    unmasked for fully-visible pairs (visitor origin j > idx), skipped
    when fully masked (j < idx).  dk/dv accumulate at home in f32; dq
    co-rotates with its q-group and one final ppermute returns it.
    This is the standard ring-attention backward (the memory-efficient
    counterpart of differentiating the einsum accumulate, which would
    rematerialize (T_local, T_local) score blocks per hop)."""
    from ..ops.pallas_kernels import flash_bwd_block

    def _fwd_pass(q, k, v):
        return _flash_ring_forward(q, k, v, axis_name=axis_name,
                                   causal=causal, bq=bq, bk=bk,
                                   interpret=interpret)

    @jax.custom_vjp
    def rf(q, k, v):
        return _fwd_pass(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = _fwd_pass(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        n = lax.psum(1, axis_name)
        idx = lax.axis_index(axis_name)
        b, h, t, d = q.shape
        bh = b * h
        qf = q.reshape(bh, t, d)
        kf = k.reshape(bh, t, d)
        vf = v.reshape(bh, t, d)
        dof = do.reshape(bh, t, d).astype(qf.dtype)
        lsef = lse.reshape(bh, t)
        delta = jnp.sum(dof.astype(jnp.float32)
                        * out.reshape(bh, t, d).astype(jnp.float32),
                        axis=-1)                      # (bh, t) f32

        def block(vq, vdo, vlse, vdelta, diag):
            # f32 outputs straight from the kernels: per-hop partials
            # must not round to bf16 before the ring accumulation
            return flash_bwd_block(
                vq, kf, vf, vdo, vlse, vdelta, causal=diag,
                block_q=bq, block_k=bk, interpret=interpret,
                out_dtype=jnp.float32)

        # s = 0: the diagonal pair (visitor == home shard)
        dq0, dk0, dv0 = block(qf, dof, lsef, delta, diag=causal)

        def body(s, carry):
            vq, vdo, vlse, vdelta, dqv, dk, dv = carry
            prm = [(i, (i + 1) % n) for i in range(n)]
            vq, vdo, vlse, vdelta, dqv = (
                lax.ppermute(x, axis_name, prm)
                for x in (vq, vdo, vlse, vdelta, dqv))
            j = (idx - s) % n          # visiting q-group's home shard

            def contribute(_):
                return block(vq, vdo, vlse, vdelta, diag=False)

            def skip(_):
                return (jnp.zeros((bh, t, d), jnp.float32),
                        jnp.zeros((bh, t, d), jnp.float32),
                        jnp.zeros((bh, t, d), jnp.float32))

            if causal:
                # visitor attends this shard's K/V iff it sits later in
                # the global sequence (diagonal already done at s=0)
                dqh, dkh, dvh = lax.cond(j > idx, contribute, skip,
                                         None)
            else:
                dqh, dkh, dvh = contribute(None)
            return (vq, vdo, vlse, vdelta, dqv + dqh, dk + dkh,
                    dv + dvh)

        carry = (qf, dof, lsef, delta, dq0, dk0, dv0)
        _, _, _, _, dqv, dk32, dv32 = lax.fori_loop(1, n, body, carry)
        # dq co-rotated n-1 times with its q-group: one more hop home
        prm = [(i, (i + 1) % n) for i in range(n)]
        dqv = lax.ppermute(dqv, axis_name, prm)
        return (dqv.reshape(b, h, t, d).astype(q.dtype),
                dk32.reshape(b, h, t, d).astype(k.dtype),
                dv32.reshape(b, h, t, d).astype(v.dtype))

    rf.defvjp(fwd, bwd)
    return rf


def _make_ring_flash_cross(axis_name: str, causal: bool, bq: int,
                           bk: int, interpret: bool):
    """Differentiable fused ring attention for UNEQUAL shard extents
    (cross-attention: T_q ≠ T_k per shard).

    Forward: the same fused Pallas ring as the equal-extent path
    (_flash_ring_forward handles t_q ≠ t_k), keeping the lse residual.

    Backward: an einsum ring pass, NOT the flash backward kernels —
    those assume square (T, T) block geometry (flash_bwd_block derives
    the K/V specs from q's extent).  Each hop rematerializes one
    (t_q_local, t_k_local) score block from the saved lse, which is
    exactly the memory the fused path saves on the forward; for
    cross-attention the K/V extent is typically the short encoder side,
    so the block stays small.  Ring choreography matches
    _make_ring_flash's backward: K/V stay home, (q, dO, lse, delta, dq)
    rotate, dk/dv accumulate at home in f32, one final ppermute sends
    dq home.  Causal masking uses GLOBAL positions (visitor q-group j's
    offset j·t_q vs home K offset idx·t_k) — the equal-extent path can
    reason per-pair, unequal extents cannot."""

    def _fwd_pass(q, k, v):
        return _flash_ring_forward(q, k, v, axis_name=axis_name,
                                   causal=causal, bq=bq, bk=bk,
                                   interpret=interpret)

    @jax.custom_vjp
    def rf(q, k, v):
        return _fwd_pass(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = _fwd_pass(q, k, v)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        n = lax.psum(1, axis_name)
        idx = lax.axis_index(axis_name)
        b, h, t_q, d = q.shape
        t_k = k.shape[2]
        scale = 1.0 / math.sqrt(d)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        do32 = do.astype(jnp.float32)
        delta = jnp.sum(do32 * out.astype(jnp.float32),
                        axis=-1)                     # (B, H, t_q) f32
        kpos = idx * t_k + jnp.arange(t_k)           # home K positions

        # HIGHEST precision: on TPU a DEFAULT-precision f32 einsum is a
        # single bf16 MXU pass — measured max score error 1.2e-2 at the
        # test shape, which exp() turns into an 8e-4 p-inconsistency
        # against the kernel's lse and a >1e-2 dq violation on sharp
        # causal rows.  HIGHEST (multi-pass f32) recovers the kernel's
        # accuracy (p error 2e-4 measured on chip).  The
        # lossless-re-round argument (bf16 activations upcast to f32
        # round-trip exactly through a DEFAULT bf16 pass) applies ONLY
        # to einsums whose f32 operands are such upcasts — the score
        # and dp products below.  `p` (exp of shifted scores) and `ds`
        # are GENUINELY f32-valued intermediates with no bf16
        # preimage, so every einsum consuming them runs HIGHEST
        # unconditionally; rounding them through a bf16 MXU pass would
        # leave the bf16-input backward less accurate than the forward
        # kernel it must match (ADVICE r05).
        hi = (jax.lax.Precision.HIGHEST
              if any(a.dtype == jnp.float32 for a in (q, k, v))
              else jax.lax.Precision.DEFAULT)
        hi_pd = jax.lax.Precision.HIGHEST   # p/ds-consuming einsums

        def pair(vq, vdo, vlse, vdelta, j):
            """Visitor q-group (home shard j) against the resident K/V:
            p from the saved lse, then ds → (dq, dk, dv) partials."""
            s = jnp.einsum("bhqd,bhkd->bhqk", vq.astype(jnp.float32),
                           kf, precision=hi) * scale
            p = jnp.exp(s - vlse[..., None])
            if causal:
                qpos = j * t_q + jnp.arange(t_q)
                p = jnp.where((qpos[:, None] >= kpos[None, :])
                              [None, None], p, 0.0)
            dp = jnp.einsum("bhqd,bhkd->bhqk", vdo, vf, precision=hi)
            ds = p * (dp - vdelta[..., None])
            dqh = jnp.einsum("bhqk,bhkd->bhqd", ds, kf,
                             precision=hi_pd) * scale
            dkh = jnp.einsum("bhqk,bhqd->bhkd", ds,
                             vq.astype(jnp.float32),
                             precision=hi_pd) * scale
            dvh = jnp.einsum("bhqk,bhqd->bhkd", p, vdo,
                             precision=hi_pd)
            return dqh, dkh, dvh

        def maybe_pair(vq, vdo, vlse, vdelta, j):
            if not causal:
                return pair(vq, vdo, vlse, vdelta, j)
            # visitor contributes iff its last q row can see the home
            # shard's first k row (mirror of the forward's hop skip)
            return lax.cond(
                (j + 1) * t_q > idx * t_k,
                lambda _: pair(vq, vdo, vlse, vdelta, j),
                lambda _: (jnp.zeros((b, h, t_q, d), jnp.float32),
                           jnp.zeros((b, h, t_k, d), jnp.float32),
                           jnp.zeros((b, h, t_k, d), jnp.float32)),
                None)

        dq0, dk0, dv0 = maybe_pair(q, do32, lse, delta, idx)

        def body(s, carry):
            vq, vdo, vlse, vdelta, dqv, dk, dv = carry
            prm = [(i, (i + 1) % n) for i in range(n)]
            vq, vdo, vlse, vdelta, dqv = (
                lax.ppermute(x, axis_name, prm)
                for x in (vq, vdo, vlse, vdelta, dqv))
            j = (idx - s) % n         # visiting q-group's home shard
            dqh, dkh, dvh = maybe_pair(vq, vdo, vlse, vdelta, j)
            return (vq, vdo, vlse, vdelta, dqv + dqh, dk + dkh,
                    dv + dvh)

        carry = (q, do32, lse, delta, dq0, dk0, dv0)
        _, _, _, _, dqv, dk32, dv32 = lax.fori_loop(1, n, body, carry)
        prm = [(i, (i + 1) % n) for i in range(n)]
        dqv = lax.ppermute(dqv, axis_name, prm)
        return (dqv.astype(q.dtype), dk32.astype(k.dtype),
                dv32.astype(v.dtype))

    rf.defvjp(fwd, bwd)
    return rf


def ring_attention(q: Array, k: Array, v: Array, mesh: Mesh, *,
                   causal: bool = False, axis_name: str = "sp",
                   flash=False) -> Array:
    """Sequence-parallel attention: (B, H, T, D) with T sharded on
    `axis_name`.  Returns output with the same sharding.

    flash: False (default, einsum accumulate) | True (fused Pallas
    ring, now DIFFERENTIABLE for equal shard extents — custom-VJP
    second ring pass with the flash backward kernels, see
    _make_ring_flash) | "interpret" (same, on CPU for tests)."""
    spec = P(None, None, axis_name, None)
    local = partial(_ring_attention_local, axis_name=axis_name,
                    causal=causal, flash=flash)
    if flash:
        fn = shard_map_nocheck(local, mesh, (spec, spec, spec), spec)
    else:
        fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def sp_shard_time(x: Array, mesh: Mesh, *, time_axis: int = 2,
                  axis_name: str = "sp") -> Array:
    """Place an activation with its time axis sharded over sp."""
    spec = [None] * (time_axis + 1)
    spec[time_axis] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
