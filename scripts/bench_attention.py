"""On-chip long-context attention microbench: flash kernel vs XLA.

The long-context story (SURVEY §5.7: ring + flash attention) has
throughput claims only from interpret-mode semantics so far.  This
script measures, on the real chip, causal self-attention fwd+bwd at
long sequence lengths:

  - xla:   the einsum reference (`parallel.sp.attention`) — what a
           user gets without the Pallas path
  - flash: `ops.pallas_kernels.flash_attention` (tiled online-softmax,
           O(T) memory, the kernel the ring path runs per hop)

and prints one JSON record per (T, impl).

The metric is attention-FLOPs/s: 4·B·H·T²·D multiply-adds fwd (×3.5
fwd+bwd, causal ×0.5) — the standard flash-attention accounting — so
MFU here is attention-math utilization, comparable across T.

Run (one process holds the chip):
    python scripts/bench_attention.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

# bf16 peak TFLOP/s by device_kind: ONE copy, in analysis/roofline.py
# (bench.py resolves through it too); an unknown chip is an error
from caffeonspark_tpu.analysis.roofline import peak_tflops  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention
    from caffeonspark_tpu.parallel.sp import attention

    jax.config.update("jax_default_matmul_precision", "bfloat16")
    dev = jax.devices()[0]
    chip = f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    print("backend:", chip)

    # BENCH_ATTN_SMOKE=1: tiny-shape CPU harness check (interpret-mode
    # flash, no MFU) — validates the script end-to-end before chip time
    # is spent on it
    smoke = os.environ.get("BENCH_ATTN_SMOKE") == "1"
    interpret = smoke and dev.platform != "tpu"
    peak, peak_src = (None, None) if interpret else peak_tflops(dev)

    b, h, d = (1, 2, 64) if smoke else (4, 16, 64)
    iters = 2 if smoke else 20
    results = []
    for t in ((256,) if smoke else (1024, 2048, 4096)):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, h, t, d), jnp.bfloat16)
        # causal attention FLOPs: 2 matmuls x 2 FLOP/MAC x B H T^2 D,
        # x0.5 causal, x3.5 fwd+bwd (standard flash accounting)
        flops_step = 3.5 * 0.5 * 4 * b * h * t * t * d

        def make(fn):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

            grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

            def step(q, k, v):
                def body(c, _):
                    l, gs = grad(q + c.astype(q.dtype) * 1e-9, k, v)
                    return (l * 1e-20).astype(jnp.float32), None
                return jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    None, length=iters)[0]
            return jax.jit(step)

        impls = {
            "xla": lambda q, k, v: attention(q, k, v, causal=True),
            "flash": lambda q, k, v: flash_attention(
                q, k, v, True, interpret=interpret),
        }
        row = {"t": t}
        for name, fn in impls.items():
            # per-leg isolation: the XLA leg materializes the full
            # (B,H,T,T) score/softmax tensors — at T=4096 that is
            # multi-GB and may OOM where flash's O(block·T) does not.
            # A dead reference leg must not kill the flash rows.
            try:
                stepj = make(fn)
                tc = time.perf_counter()
                np.asarray(jax.device_get(stepj(q, k, v)))  # compile+warm
                compile_s = time.perf_counter() - tc
                t0 = time.perf_counter()
                np.asarray(jax.device_get(stepj(q, k, v)))
                dt = (time.perf_counter() - t0) / iters
            except Exception as e:  # noqa: BLE001
                row[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                print(json.dumps({"metric":
                                  f"attention_causal_t{t}_{name}",
                                  "error": row[name]["error"]}),
                      flush=True)
                continue
            tflops = flops_step / dt / 1e12
            mfu_fields = {} if peak is None else {
                "mfu": round(tflops / peak, 4),
                "peak_tflops_per_sec": peak, "peak_source": peak_src}
            rec = {
                "metric": f"attention_causal_t{t}_{name}",
                "value": round(b * t / dt, 1),
                "unit": "sequences*T/sec(tokens/sec)",
                **mfu_fields,
                "model_tflops_per_sec": round(tflops, 2),
                "flops_per_step": flops_step,
                "batch": b, "heads": h, "head_dim": d, "iters": iters,
                "precision": "bfloat16", "act_dtype": "bfloat16",
                "chip": chip,
            }
            rec["timing"] = {"sec_per_iter": dt, "compile_s": compile_s}
            row[name] = {"ms": round(dt * 1e3, 3),
                         "tflops": round(tflops, 2)}
            print(json.dumps(rec), flush=True)
        if ("ms" in row.get("xla", {})) and ("ms" in row.get("flash", {})):
            row["speedup"] = round(row["xla"]["ms"] / row["flash"]["ms"], 3)
        results.append(row)
    print(json.dumps({"summary": results}), flush=True)
    # the flash legs are the point; a missing flash row is a failure
    if not all("ms" in r.get("flash", {}) for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
