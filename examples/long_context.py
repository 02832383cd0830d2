"""Long-context training demo: sequence parallelism end to end.

    python examples/long_context.py [seq_len]

Trains the zoo's causal transformer LM on synthetic token streams with
the TIME axis sharded over an `sp` mesh (the capability the reference
lacks entirely, SURVEY §2.7/§5.7) and prints the loss curve plus a
parity check against the unsharded step.  With no accelerator the
script builds a virtual 8-device CPU mesh itself; on a TPU pod slice
the same code shards over real chips.  On TPU meshes the attention
dispatch routes through shard_map automatically: dp/tp meshes run the
Pallas flash kernel per (batch, heads) block, and sp meshes run the
DIFFERENTIABLE fused ring (K/V rotating on ICI, flash kernels per
hop) when the local sequence extent is kernel-eligible — einsum
otherwise.  `parallel.sp.ring_attention(flash=True)` exposes the same
fused ring for hand-rolled steps (demoed below).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))      # run in-repo without install

# no accelerator → virtual 8-device CPU mesh, BEFORE jax initializes
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", "") and not os.environ.get("COS_REAL_DEVICES"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device"
                                 "_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(seq_len: int = 32):
    import jax
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from caffeonspark_tpu.models import transformer_lm
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver

    n_dev = len(jax.devices())
    sp_n = max(s for s in (1, 2, 4)
               if n_dev % s == 0 and seq_len % s == 0)
    dp_n = max(1, n_dev // sp_n)
    batch = 2 * dp_n
    print(f"devices={n_dev}  mesh dp={dp_n} x sp={sp_n}  "
          f"seq={seq_len}  batch={batch}")

    npm = transformer_lm(vocab=64, d_model=32, heads=2, layers=2,
                         seq=seq_len, batch=batch)
    sp_txt = ("base_lr: 0.01 momentum: 0.9 lr_policy: 'fixed' "
              "type: 'ADAM' random_seed: 5")

    rng = np.random.RandomState(0)
    seqs = rng.randint(0, 60, (seq_len, batch)).astype(np.float32)
    data = {"input_sentence": jnp.asarray(seqs),
            "target_sentence": jnp.asarray((seqs + 1) % 60)}

    # sequence-parallel step: ParallelSolver shards time-major inputs
    # (T, B, ·) as P("sp", "dp") on an sp mesh — no hand-rolled jit
    solver = Solver(SolverParameter.from_text(sp_txt), npm)
    ps = ParallelSolver(solver, build_mesh(dp=dp_n, sp=sp_n))
    params, st = ps.init()
    step = ps.train_step()

    # unsharded reference for the parity line
    ref = Solver(SolverParameter.from_text(sp_txt), npm)
    p_ref, st_ref = ref.init()
    step_ref = ref.jit_train_step()

    for i in range(10):
        r = solver.step_rng(i)
        params, st, out = step(params, st, ps.shard_batch(data), r)
        p_ref, st_ref, out_ref = step_ref(p_ref, st_ref, data, r)
        loss = float(jax.device_get(out["loss"]))
        delta = abs(loss - float(jax.device_get(out_ref["loss"])))
        print(f"iter {i:2d}  loss {loss:.4f}  "
              f"|sp - single-device| = {delta:.2e}")
        assert delta < 1e-3 * max(1.0, abs(loss))
    print("sequence-parallel training matches the single-device step")

    if sp_n > 1:
        _fused_ring_demo(sp_n, dp_n, seq_len)


def _fused_ring_demo(sp_n: int, dp_n: int, seq_len: int):
    """Hand-rolled long-context step on the DIFFERENTIABLE fused ring:
    ring_attention(flash=...) trains with per-hop Pallas kernels (the
    custom-VJP second ring pass) — interpret-mode on CPU meshes, the
    compiled Mosaic kernels on a real pod."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from caffeonspark_tpu.parallel import build_mesh
    from caffeonspark_tpu.parallel.sp import ring_attention

    mesh = build_mesh(dp=dp_n, sp=sp_n)
    flash = "interpret" if jax.default_backend() == "cpu" else True
    rng = np.random.RandomState(1)
    b, h, d = 2, 2, 16
    t = max(seq_len, 8 * sp_n)
    x = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    tgt = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    w = jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)

    @jax.jit
    def ring_step(w):
        def loss(w):
            qkv = jnp.einsum("bhtd,de->bhte", x, w)
            out = ring_attention(qkv, qkv, qkv, mesh, causal=True,
                                 flash=flash)
            return jnp.mean((out - tgt) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 0.5 * g, l

    losses = []
    for _ in range(5):
        w, l = ring_step(w)
        losses.append(float(jax.device_get(l)))
    print("fused-ring (differentiable flash) losses: "
          + "  ".join(f"{l:.4f}" for l in losses))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    print("fused ring attention trains end to end")

    # cross-attention at long context: K/V twice as long as Q (e.g. a
    # decoder attending a long encoder memory) — unequal per-shard
    # extents route through the cross-extent fused ring (fused Pallas
    # forward, einsum-ring backward) and still train
    t_kv = 2 * t
    mem = jnp.asarray(rng.randn(b, h, t_kv, d), jnp.float32)
    wq = jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)

    @jax.jit
    def cross_step(wq):
        def loss(wq):
            q = jnp.einsum("bhtd,de->bhte", x, wq)
            out = ring_attention(q, mem, mem, mesh, flash=flash)
            return jnp.mean((out - tgt) ** 2)
        l, g = jax.value_and_grad(loss)(wq)
        return wq - 0.5 * g, l

    closses = []
    for _ in range(5):
        wq, l = cross_step(wq)
        closses.append(float(jax.device_get(l)))
    print(f"cross-attention fused ring (T_q={t}, T_kv={t_kv}) losses: "
          + "  ".join(f"{l:.4f}" for l in closses))
    assert closses[-1] < closses[0] and np.isfinite(closses).all()
    print("cross-extent fused ring trains end to end")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 32)
