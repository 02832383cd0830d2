"""Benchmark: train-step / forward throughput of one zoo model on one
TPU chip (images/sec, or sentences/sec for the LSTM), with MFU against
the running chip's bf16 peak.

One plain process.  It names the device first and exits non-zero when
the platform is not `tpu`, when the chip's `device_kind` has no row in
the peak table (analysis/roofline.PEAK_BF16_TFLOPS), and when any loss
it reads back is not finite.  It prints one JSON record on stdout and
nothing it did not measure in this run.

What this measures (a private `lax.scan` over the solver's step on one
replayed random batch; BENCH_PIPELINE=1 for the host-fed path) is due
to be replaced by cells over the system's own loops — ROADMAP queue 1
item 1.  Timing: the host clock around `jax.block_until_ready`, which
waits for the device on this backend (50 chained 4096^3 bf16 matmuls:
36.5 ms either way, 188 TFLOP/s — chip run, PR 21, CHANGES.md).

Env knobs:
  BENCH_MODEL        'caffenet' (default) | 'alexnet' | 'resnet50' |
                     'vgg16' | 'googlenet' | 'lstm' (zoo.lstm_lm)
  BENCH_BATCH        per-step batch (default 256; resnet50/vgg16
                     default 64, googlenet 128, lstm 64)
  BENCH_ITERS        timed iterations (default 50)
  BENCH_PRECISION    jax default_matmul_precision (default 'bfloat16'
                     — one MXU pass; 'highest' for f32 parity runs)
  BENCH_DTYPE        'mixed' (default: f32 master weights, bf16
                     activations/compute) | 'float32' | 'bfloat16'
  COS_STATE_DTYPE    optimizer-history dtype (read by Solver directly)
  BENCH_PIPELINE=1   feed through the data pipeline (JPEG LMDB ->
                     decode -> transform -> device prefetch),
                     host-dispatched per step; also reports host
                     decode+transform scaling vs thread count.
                     + COS_DEVICE_TRANSFORM=1 ships uint8 + on-device
                     mean/scale
  BENCH_FORWARD=1    forward-only throughput (the features/test path)

Reference perf harness analog:
caffe-distri/src/test/java/com/yahoo/ml/jcaffe/PerfTest.java:69-118
"""

import functools
import json
import os
import sys
import tempfile
import time

import numpy as np


def _dataset_tag(model: str) -> str:
    """Dataset half of the metric name: CNNs bench the ImageNet
    workload shape, the recurrent family the COCO-caption shape."""
    return "coco" if model == "lstm" else "imagenet"


def _pipeline_inputs(batch, dshape, tmpdir, net_dtype=None):
    """Build a JPEG LMDB once and stream it through the full source
    pipeline (decode -> transform -> prefetch)."""
    from caffeonspark_tpu.data import get_source
    from caffeonspark_tpu.data.queue_runner import device_prefetch
    lp = _pipeline_layer(batch, dshape, tmpdir)
    src = get_source(lp, phase_train=True, seed=0, resize=True)
    # COS_DEVICE_TRANSFORM=1 engages the uint8-infeed split here too,
    # with the same out-dtype rule production uses (bf16 nets get the
    # device-side cast).  Returns the engaged flag for the record.
    dxf = src.enable_device_transform(net_dtype)
    return device_prefetch(src.batches(loop=True), depth=2,
                           device_transforms=dxf), dxf is not None


def _pipeline_layer(batch, dshape, tmpdir):
    import cv2
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum, LayerParameter

    c, h, w = dshape[1], 256, 256
    n = max(4 * batch, 256)
    imgs, labels = make_images(n, channels=c, height=h, width=w, seed=0)
    recs = []
    for i in range(n):
        ok, buf = cv2.imencode(
            ".jpg", (imgs[i].transpose(1, 2, 0) * 255).astype(np.uint8))
        if not ok:
            raise RuntimeError("cv2.imencode failed (JPEG support?)")
        recs.append((b"%08d" % i,
                     Datum(encoded=True, data=bytes(buf),
                           label=int(labels[i])).to_binary()))
    LmdbWriter(os.path.join(tmpdir, "bench_lmdb")).write(recs)
    return LayerParameter.from_text(f'''
      name: "data" type: "MemoryData" top: "data" top: "label"
      source_class: "LMDB"
      memory_data_param {{ source: "{tmpdir}/bench_lmdb"
        batch_size: {batch} channels: {c} height: {h} width: {w} }}
      transform_param {{ crop_size: {dshape[2]} mirror: true
        mean_value: 104 mean_value: 117 mean_value: 123 }}''')


def _host_pipeline_scaling(batch, dshape, tmpdir, threads_list,
                           n_batches=4, budget_s=120.0):
    """Decode+transform throughput at several thread counts — the
    host-feed half of the reference's decode-threads-overlap-solver
    design (CaffeProcessor.scala:254-383).  Returns {threads: img/s} on
    this host's cores; thread counts past the time budget are skipped."""
    from caffeonspark_tpu.data import get_source
    lp = _pipeline_layer(batch, dshape, tmpdir)
    out = {}
    t_begin = time.monotonic()
    for nt in threads_list:
        if time.monotonic() - t_begin > budget_s:
            break
        src = get_source(lp, phase_train=True, seed=0, resize=True,
                         num_threads=nt)
        # under COS_DEVICE_TRANSFORM the sweep must measure the same
        # (lighter: uint8 crop/mirror only) host path the bench feeds
        src.enable_device_transform()
        gen = src.batches(loop=True)
        next(gen)                       # warm caches/threads
        t0 = time.perf_counter()
        for _ in range(n_batches):
            next(gen)
        dt = time.perf_counter() - t0
        out[nt] = round(batch * n_batches / dt, 1)
    return out


def _require_finite(what, values):
    values = np.asarray(values, np.float64)
    if not np.all(np.isfinite(values)):
        sys.exit(f"bench: non-finite {what}: {values.ravel()[-3:]} — "
                 "a throughput over a diverged run is not a result")


def main():
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.analysis.roofline import peak_tflops
    from caffeonspark_tpu.utils.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"bench: device {device}", file=sys.stderr)
    if device["platform"] != "tpu":
        sys.exit(f"bench: needs a TPU, found {device} — a CPU timing is "
                 "not a device metric")
    peak, peak_source = peak_tflops(devs[0])   # unknown kind raises

    precision = os.environ.get("BENCH_PRECISION", "bfloat16")
    jax.config.update("jax_default_matmul_precision", precision)
    enable_compile_cache()

    model = os.environ.get("BENCH_MODEL", "caffenet")
    default_batch = {"caffenet": 256, "alexnet": 256, "resnet50": 64,
                     "vgg16": 64, "googlenet": 128,
                     "lstm": 64}.get(model, 64)
    batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))
    iters = int(os.environ.get("BENCH_ITERS", "50"))
    pipeline = os.environ.get("BENCH_PIPELINE") == "1"
    forward_only = os.environ.get("BENCH_FORWARD") == "1"
    if pipeline and model == "lstm":
        sys.exit("bench: BENCH_PIPELINE measures the image decode "
                 "pipeline; not applicable to BENCH_MODEL=lstm")

    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    from caffeonspark_tpu.utils.flops import train_step_flops

    zoo_name = {"lstm": "lstm_lm"}.get(model, model)
    npm = getattr(zoo, zoo_name)(batch_size=batch)

    # base_lr 1e-4 + clip_gradients (not the reference's 0.01/unclipped):
    # a FIXED random batch is replayed for the warmup + 3 timed repeats
    # (200 steps), and the clip bounds the update so the losses stay
    # finite; the global-norm reduce is ~1e-4 of the step FLOPs.  (The
    # NaN losses this config was first blamed for were bf16-rounded
    # labels — LayerOp.index_bottoms, PR 21; whether 0.01 unclipped
    # diverges on a replayed batch has not been re-measured.)
    sp = SolverParameter.from_text(
        "base_lr: 0.0001 momentum: 0.9 weight_decay: 0.0005 "
        "clip_gradients: 1.0 "
        "lr_policy: 'step' gamma: 0.1 stepsize: 100000 max_iter: 450000 "
        "random_seed: 1")
    dts = os.environ.get("BENCH_DTYPE", "mixed")
    dtype_kw = {}
    if dts == "mixed":
        dtype_kw = dict(dtype=jnp.float32, compute_dtype=jnp.bfloat16)
    elif dts == "bfloat16":
        dtype_kw = dict(dtype=jnp.bfloat16)
    solver = Solver(sp, npm, **dtype_kw)
    params, st = solver.init()
    flops_step = train_step_flops(solver.train_net)

    specs = dict((n, s) for n, s, _ in solver.train_net.input_specs)
    rng = np.random.RandomState(0)
    if "data" in specs:
        dshape = (batch,) + tuple(specs["data"][1:])
        fixed = {"data": jnp.asarray(rng.rand(*dshape).astype(np.float32)),
                 "label": jnp.asarray(
                     rng.randint(0, 1000, batch).astype(np.float32))}
    else:
        # recurrent LM family (BENCH_MODEL=lstm): time-major caption
        # tops — tokens, cont gates (0 starts a sequence), targets
        dshape = None
        t_steps = specs["input_sentence"][0]
        toks = rng.randint(0, 4000, (t_steps, batch))
        cont = np.ones((t_steps, batch), np.float32)
        cont[0] = 0.0
        fixed = {"input_sentence": jnp.asarray(toks, jnp.float32),
                 "cont_sentence": jnp.asarray(cont),
                 "target_sentence": jnp.asarray(
                     (toks + 1) % 4000, jnp.float32)}
    extra = {}
    timing = {}
    metric = f"{model}_{_dataset_tag(model)}"

    if forward_only:
        # the features()/test() path: jitted forward, batches chained
        # on device via scan (inputs reused; outputs data-dependent)
        net = solver.train_net

        def run_fwd(params, inputs, n):
            def body(carry, _):
                # tie each step's input to the previous loss: a scalar
                # broadcast-add that makes the body loop-VARIANT, so
                # XLA cannot hoist the forward out of the scan
                inp = dict(inputs)
                k0 = "data" if "data" in inp else "input_sentence"
                inp[k0] = inp[k0] + carry * 1e-9
                blobs, _st = net.apply(params, inp, train=False)
                loss = blobs["loss"].astype(jnp.float32)
                return loss, loss
            return jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                None, length=n)

        runf = jax.jit(functools.partial(run_fwd, n=iters))
        t0 = time.perf_counter()
        jax.block_until_ready(runf(params, fixed))
        timing["warmup_compile_seconds"] = round(
            time.perf_counter() - t0, 3)
        t0 = time.perf_counter()
        _, losses = jax.block_until_ready(runf(params, fixed))
        dt = time.perf_counter() - t0
        _require_finite("forward losses", losses)
        flops_step = flops_step // 3     # fwd-only
        metric += "_forward_images_per_sec_per_chip"
    elif pipeline:
        # host-dispatched loop fed by the real decode/transform pipeline
        step = solver.jit_train_step()
        losses = []
        with tempfile.TemporaryDirectory(prefix="cos_bench_") as td:
            gen, devxf = _pipeline_inputs(batch, dshape, td,
                                          solver.train_net.dtype)
            for i in range(5):
                params, st, out = step(params, st, next(gen),
                                       solver.step_rng(i))
            jax.block_until_ready(out["loss"])
            t0 = time.perf_counter()
            for i in range(iters):
                params, st, out = step(params, st, next(gen),
                                       solver.step_rng(5 + i))
                losses.append(out["loss"])
            jax.block_until_ready(losses)
            dt = time.perf_counter() - t0
            _require_finite("train losses", jax.device_get(losses))
            metric += ("_train_images_per_sec_per_chip_pipeline"
                       + ("_devxf" if devxf else ""))
            extra["device_transform"] = devxf
            # host-side decode+transform scaling: how many cores does
            # it take to feed the chip at the on-chip rate?
            ncpu = os.cpu_count() or 1
            with tempfile.TemporaryDirectory(prefix="cos_scale_") as td2:
                scaling = _host_pipeline_scaling(
                    batch, dshape, td2, sorted({1, 2, 4, 8, ncpu}))
            extra["pipeline"] = {
                "host_cores": ncpu,
                "decode_transform_img_per_sec_by_threads": scaling}
    else:
        # ON-DEVICE loop: lax.scan over the chained train step, one
        # dispatch per repeat
        step_fn = solver.train_step_fn()

        def run(p, s, inputs, rngs):
            def body(carry, r):
                p, s = carry
                p, s, out = step_fn(p, s, inputs, r)
                return (p, s), out["loss"]
            (p, s), losses = jax.lax.scan(body, (p, s), rngs)
            return p, s, losses

        runj = jax.jit(run, donate_argnums=(0, 1))
        rngs = jnp.stack([solver.step_rng(i) for i in range(iters)])
        t0 = time.perf_counter()
        params, st, losses = jax.block_until_ready(
            runj(params, st, fixed, rngs))
        timing["warmup_compile_seconds"] = round(
            time.perf_counter() - t0, 3)
        # 3 timed repeats; the first is the reported one, all are kept
        repeats = []
        for _ in range(3):
            t0 = time.perf_counter()
            params, st, losses = jax.block_until_ready(
                runj(params, st, fixed, rngs))
            repeats.append(time.perf_counter() - t0)
            _require_finite("train losses", losses)
        dt = repeats[0]
        timing["timed_repeat_seconds"] = [round(r, 4) for r in repeats]
        timing["losses_tail"] = [float(x) for x in np.asarray(losses)[-3:]]
        metric += "_train_images_per_sec_per_chip"

    tflops = flops_step * iters / dt / 1e12
    if tflops > peak:
        sys.exit(f"bench: implied {tflops:.0f} TFLOP/s exceeds the chip "
                 f"peak {peak:.0f} — the timing is broken")
    timing["timed_seconds"] = round(dt, 4)
    print(json.dumps({
        "metric": metric,
        "value": round(batch * iters / dt, 2),
        "unit": "sentences/sec" if model == "lstm" else "images/sec",
        "device": device,
        "mfu": round(tflops / peak, 4),
        "peak_tflops_per_sec": peak,
        "peak_source": peak_source,
        "model_tflops_per_sec": round(tflops, 2),
        "flops_per_step": flops_step,
        "batch": batch, "iters": iters,
        # precision = MXU matmul precision; act_dtype = activation
        # storage dtype (BENCH_DTYPE)
        "precision": precision,
        "act_dtype": dts,
        "timing": timing,
        **extra,
    }), flush=True)


if __name__ == "__main__":
    main()
