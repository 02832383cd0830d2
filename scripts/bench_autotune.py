#!/usr/bin/env python
"""Per-layer autotuner benchmark: untuned vs COS_AUTOTUNE plan on the
worst-MFU zoo net (googlenet, 0.192 in BENCH_r05).

Runs `ops.autotune.autotune_net` — the real tuner: roofline-ranked
per-layer variant enumeration, greedy measured A/B at a pinned parity
tolerance — and commits the chosen plan plus the measured uplift as a
single JSON artifact.

THE FLOOR MODELS AN HBM-BANDWIDTH-STARVED REGIME, NOT DEVICE MATH.
This box is CPU-only, so — exactly like bench_steploop's 45 ms
per-dispatch floor and bench_gradsync's gigabit comm floor — the
controlled variable is an injected sleep: every measured step is
charged modeled_step_bytes/floor seconds, where the bytes come from
the SAME roofline model the tuner ranks with
(`analysis.roofline.step_bytes_total`, per-layer variant aware).
Variants that cut modeled HBM traffic (per-layer bf16, the fused
ReLU+LRN stem epilogue) therefore show their uplift in measured
steps/s; variants that only rearrange layout (NHWC/s2d) are judged by
their raw compute time and typically stay inert on CPU.  The artifact
carries a floor=0 control A/B so the raw ratio without the model is
committed next to the modeled one.

ALWAYS exits 0 with ONE JSON document on stdout;
--out also writes the full artifact (bench_evidence/bench_autotune.json
via `make bench-autotune`).

Usage:
  python scripts/bench_autotune.py [--quick] [--out PATH]
      [--net googlenet] [--batch 2] [--image-size 64]
      [--floor-gbs 0.125] [--top-layers 6] [--iters 3]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_FLAG = "--xla_cpu_multi_thread_eigen=false"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FLAG).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _build_net_param(args):
    from caffeonspark_tpu.models import zoo
    if args.net == "googlenet":
        return zoo.googlenet(batch_size=args.batch, num_classes=10,
                             image_size=args.image_size,
                             aux_heads=False)
    if args.net == "alexnet":
        return zoo.alexnet(batch_size=args.batch, num_classes=10,
                           crop=args.image_size)
    if args.net == "caffenet":
        return zoo.caffenet(batch_size=args.batch, num_classes=10,
                            crop=args.image_size)
    raise SystemExit(f"--net {args.net!r}: googlenet/alexnet/caffenet")


def _ab(net_param, plan_layers, *, iters, floor_gbs, seed=0):
    """Measured A/B of {} vs `plan_layers` under the given floor —
    the control leg, reusing the tuner's own measurement harness."""
    from caffeonspark_tpu.analysis import roofline as rl
    from caffeonspark_tpu.net import Net
    from caffeonspark_tpu.ops import autotune as at
    from caffeonspark_tpu.proto.caffe import NetState, Phase
    import jax
    out = {}
    for name, layers in (("baseline", {}), ("tuned", plan_layers)):
        net = Net(net_param, NetState(phase=Phase.TRAIN),
                  autotune={"schema": at.PLAN_SCHEMA, "layers": layers}
                  if layers else False)
        params = net.init(jax.random.key(seed))
        inputs = at._rand_inputs(net, seed)
        step = at._build_step(net, "train")
        sleep = (rl.step_bytes_total(net, act_bytes=4, param_bytes=4,
                                     variants=layers)
                 / (floor_gbs * 1e9) if floor_gbs else 0.0)
        sps, _ = at._measure(step, (params, inputs), iters=iters,
                             warmup=1, sleep_s=sleep)
        out[name] = round(sps, 4)
    out["ratio"] = round(out["tuned"] / out["baseline"], 4)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="googlenet")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--floor-gbs", type=float, default=0.125,
                    help="injected HBM-floor bandwidth (GB/s); the "
                    "gigabit-regime default matches bench_gradsync")
    ap.add_argument("--top-layers", type=int, default=6)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="alexnet, fewer layers/iters (CI smoke)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.quick:
        args.net = "alexnet"
        args.image_size = min(args.image_size, 67)
        args.top_layers = min(args.top_layers, 3)
        args.iters = 2

    out_path = args.out or os.path.join(
        REPO, "bench_evidence", "bench_autotune.json")
    record = {
        "bench": "autotune",
        "net": args.net, "batch": args.batch,
        "image_size": args.image_size,
        "floor_gbs": args.floor_gbs,
        "floor_note": (
            "injected HBM-bandwidth floor: every measured step sleeps "
            "modeled_step_bytes/floor (analysis.roofline model, "
            "per-layer variant aware) — same controlled-variable "
            "technique as bench_steploop's dispatch floor and "
            "bench_gradsync's comm floor; floor0_control shows the "
            "raw CPU ratio without the model."),
        "ts": time.time(),
    }
    t0 = time.time()
    try:
        from caffeonspark_tpu.ops import autotune as at
        net_param = _build_net_param(args)
        plan = at.autotune_net(
            net_param, top_layers=args.top_layers,
            measure_iters=args.iters, warmup=1,
            floor_gbs=args.floor_gbs, save=True)
        m = plan["measured"]
        record["plan"] = {k: plan[k] for k in
                          ("key", "layers", "generalized", "tolerance")}
        record["plan_path"] = at.plan_cache_path(plan)
        record["per_layer"] = m["per_layer"]
        record["baseline_steps_per_sec"] = m["baseline_steps_per_sec"]
        record["tuned_steps_per_sec"] = m["tuned_steps_per_sec"]
        record["uplift"] = m["uplift"]
        record["parity_max_rel_diff"] = max(
            [r.get("parity_max_rel_diff", 0.0)
             for r in m["per_layer"] if r.get("accepted")] or [0.0])
        record["gate_1p2x"] = m["uplift"] >= 1.2
        # floor=0 control: the same final plan, no injected floor
        record["floor0_control"] = _ab(
            net_param, plan["layers"], iters=args.iters, floor_gbs=0.0)
        # the applied plan as every metrics artifact would carry it:
        # COS_AUTOTUNE=<plan_path> → Net → info.autotune
        os.environ["COS_AUTOTUNE"] = record["plan_path"]
        from caffeonspark_tpu.net import Net
        from caffeonspark_tpu.proto.caffe import NetState, Phase
        net = Net(net_param, NetState(phase=Phase.TRAIN))
        record["info"] = {"autotune": net.autotune_info()}
    except Exception as e:   # noqa: BLE001 — always-exit-0 contract
        import traceback
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()
    record["wall_s"] = round(time.time() - t0, 1)

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"bench": "autotune",
                      "uplift": record.get("uplift"),
                      "gate_1p2x": record.get("gate_1p2x"),
                      "layers": list(record.get("plan", {})
                                     .get("layers", {})),
                      "error": record.get("error"),
                      "artifact": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
