#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the paper's own path once, through the driver entry point
`caffeonspark_tpu.caffe_on_spark.main`, at the full width of CaffeNet
(zoo.caffenet: 227x227x3, 1000 classes, per-device batch 256), with
random weights from a seed and generated inputs:

    JPEG LMDB + prototxt  ->  -train with interleaved validation and
    snapshots  ->  -features fc8 from the written model  ->  -serve
    (InferenceService + HTTP) answering POST /v1/predict

One process — the one that trained also serves; the chip belongs to
one process at a time.  Needs no network, starts no other process.

It FAILS (non-zero exit, no result line) when JAX finds no TPU.  The
last stdout line of a passing run is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

    python chip_smoke.py                # on a machine with a chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal
        # tiny shapes, Pallas in interpret mode: checks the control
        # flow before chip time is spent; prints no device result

What it checks: validation losses read back from the device are finite
and the first is near ln(1000) (gaussian fillers, hardly trained — a
wrong layout or a dead kernel shows here); the written model is finite;
feature rows == records fed; serving rows for a full bucket equal the
-features rows for the same records; norm1/norm2 were lowered by
Mosaic on each device's own batch shard; every device reports non-zero
peak memory; with several devices, the dp=N first-step loss matches a
one-device step on the same global batch.  Any failure raises.

Times are host-clock spans that end in a value fetched from the device
(a validation round); compile/warm-up is reported apart from the steady
window.  They describe this run, not the system's speed.
"""

from __future__ import annotations

import argparse
import base64
import json
import math
import os
import re
import shutil
import signal
import socket
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
CLASSES = 1000
# reference CaffeNet means (bvlc_reference_net transform_param, BGR)
MEANS = (104, 117, 123)


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, **kv):
    print(f"[chip_smoke] {phase}: " + json.dumps(kv, sort_keys=True),
          flush=True)


# ---------------------------------------------------------------- inputs

def write_lmdb(path, n, side, seed):
    """n seeded JPEG records -> LMDB; returns [(key, label, jpeg)]."""
    import cv2
    import numpy as np
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(n, channels=3, height=side, width=side,
                               num_classes=CLASSES, seed=seed)
    recs = []
    for i in range(n):
        ok, buf = cv2.imencode(
            ".jpg", (imgs[i].transpose(1, 2, 0) * 255).astype(np.uint8))
        require(ok, "cv2.imencode failed")
        recs.append((b"%08d" % i, int(labels[i]), bytes(buf)))
    LmdbWriter(path).write(
        [(key, Datum(encoded=True, data=jpeg, label=label).to_binary())
         for key, label, jpeg in recs])
    return recs


def write_configs(work, *, crop, side, train_batch, val_batch, max_iter,
                  test_interval, snapshot):
    """zoo.caffenet with LMDB data layers and the reference transform;
    a solver with the reference CaffeNet hyper-parameters."""
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.proto.caffe import LayerParameter

    def data_layer(phase, lmdb, batch, mirror):
        means = " ".join(f"mean_value: {m}" for m in MEANS)
        return LayerParameter.from_text(f'''
          name: "data" type: "MemoryData" top: "data" top: "label"
          include {{ phase: {phase} }} source_class: "LMDB"
          memory_data_param {{ source: "{work}/{lmdb}" batch_size: {batch}
            channels: 3 height: {side} width: {side} }}
          transform_param {{ crop_size: {crop} mirror: {mirror}
            {means} }}''')

    npm = zoo.caffenet(batch_size=train_batch, num_classes=CLASSES,
                       crop=crop)
    require(npm.layer[0].type == "MemoryData", "zoo.caffenet changed")
    npm.layer[0:1] = [
        data_layer("TRAIN", "train_lmdb", train_batch, "true"),
        data_layer("TEST", "val_lmdb", val_batch, "false")]
    net_path = os.path.join(work, "caffenet_train_val.prototxt")
    with open(net_path, "w") as f:
        f.write(npm.to_text())
    solver_path = os.path.join(work, "caffenet_solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(f'''net: "{net_path}"
test_iter: 1
test_interval: {test_interval}
base_lr: 0.01
lr_policy: "step"
gamma: 0.1
stepsize: 100000
display: 20
max_iter: {max_iter}
momentum: 0.9
weight_decay: 0.0005
snapshot: {snapshot}
snapshot_prefix: "caffenet"
random_seed: 1
''')
    return solver_path


# ------------------------------------------------------------------ train

def watch_validation(stamps, stop):
    """Host-clock stamp at each finished validation round — the points
    where the train loop fetched a loss from the device.  Observes the
    live processor; touches nothing."""
    from caffeonspark_tpu.processor import CaffeProcessor
    while not stop.wait(0.005):
        proc = CaffeProcessor._instance
        report = proc.validation if proc is not None else None
        if report is not None and len(report.rounds) > len(stamps):
            stamps.append(time.perf_counter())


def check_compiled_step(proc, ndev, on_chip):
    """The step that just trained: its input sharding, its Pallas
    lowering (read from the compiled program), the devices' memory."""
    import jax
    from caffeonspark_tpu.ops import pallas_kernels as pk
    ps, solver = proc.psolver, proc.solver
    net = solver.train_net
    in_sh = ps.input_shardings()
    specs = {n: s for n, s, _ in net.input_specs}
    shard = in_sh["data"].shard_shape(tuple(specs["data"]))
    holders = in_sh["data"].devices_indices_map(tuple(specs["data"]))
    require(len(holders) == ndev
            and len({str(ix) for ix in holders.values()}) == ndev,
            f"batch not split over {ndev} devices: {holders}")
    require(shard[0] * ndev == specs["data"][0], f"uneven shard {shard}")
    report = {"dp": ps.num_dp_ranks, "per_device_batch": shard[0],
              "global_batch": specs["data"][0]}

    if on_chip:
        def abstract(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
        import jax.numpy as jnp
        batch = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32,
                                         sharding=in_sh[n])
                 for n, s in specs.items()}
        hlo = ps.train_step().lower(
            jax.tree.map(abstract, proc.params),
            jax.tree.map(abstract, proc.opt_state), batch,
            solver.step_rng(0)).compile().as_text()
        calls = re.findall(
            r"= \w+\[([\d,]+)\][^\n]*custom_call_target=\"tpu_custom_call\"",
            hlo)
        lowered = {}
        for norm in ("norm1", "norm2"):
            _, c, h, w = net.blob_shapes[norm]
            padded = -(-h * w // pk.TILE) * pk.TILE
            want = f"{shard[0]},{c},{padded}"      # this device's shard
            lowered[norm] = calls.count(want)
            require(lowered[norm] >= 2,
                    f"{norm}: no Mosaic forward+backward kernel of shape "
                    f"[{want}] in the compiled step (custom calls: "
                    f"{sorted(set(calls))}) — the LRN ran as the XLA "
                    "chain, or on a gathered batch")
        report["mosaic_kernels"] = lowered
    else:
        report["mosaic_kernels"] = "rehearsal: Pallas interpret mode"

    peaks = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        peaks[str(d.id)] = stats["peak_bytes_in_use"] if stats else None
    if on_chip:
        require(all(p for p in peaks.values()),
                f"a device reports no peak memory: {peaks}")
    report["peak_bytes_in_use"] = peaks
    return report


def check_dp_parity(solver_path, ndev):
    """First-step loss over all devices against one device, same global
    batch and rng (the check __graft_entry__.dryrun_multichip makes on
    virtual devices)."""
    import jax
    import numpy as np
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.solver import Solver
    conf = Config(["-conf", solver_path])
    rng = np.random.RandomState(7)
    losses = {}
    for n in (ndev, 1):
        solver = Solver(conf.solverParameter, conf.netParam)
        ps = ParallelSolver(solver,
                            build_mesh(devices=jax.devices()[:n]))
        params, st = ps.init()
        if n == ndev:
            specs = {k: tuple(s) for k, s, _ in
                     solver.train_net.input_specs}
            batch = {"data": (rng.rand(*specs["data"]) * 255 - 117)
                     .astype(np.float32),
                     "label": rng.randint(0, CLASSES, specs["label"])
                     .astype(np.float32)}
        _, _, out = ps.train_step()(params, st, ps.shard_batch(batch),
                                    solver.step_rng(0))
        losses[n] = float(out["loss"])
    rel = abs(losses[ndev] - losses[1]) / abs(losses[1])
    require(math.isfinite(rel) and rel < 2e-3,
            f"dp={ndev} loss {losses[ndev]} vs one device {losses[1]}")
    return {"loss_dp": losses[ndev], "loss_one_device": losses[1],
            "rel_delta": rel}


# ------------------------------------------------------------------ serve

def http_json(url, payload=None, timeout=300.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def serve_client(port, sent, feature_rows, bucket, result):
    """Runs beside caffe_on_spark.main(-serve): waits for the server,
    posts the records the -features phase extracted, compares, then
    asks the serving process (this one) to drain and exit."""
    import numpy as np
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 900
        health = None
        while health is None:
            try:
                health = http_json(base + "/healthz", timeout=5)
            except OSError:
                require(time.monotonic() < deadline,
                        "server never became healthy")
                time.sleep(0.2)
        want = {r["SampleID"]: r for r in feature_rows}

        def predict(recs):
            rows = http_json(base + "/v1/predict",
                             {"records": recs})["rows"]
            require([r["SampleID"] for r in rows] ==
                    [r["id"] for r in recs], "rows out of order")
            return rows

        full = predict(sent[:bucket])
        ref = [want[r["SampleID"]] for r in full]
        worst = max(float(np.max(np.abs(np.asarray(a["fc8"])
                                        - np.asarray(b["fc8"]))))
                    for a, b in zip(full, ref))
        scale = max(float(np.max(np.abs(np.asarray(b["fc8"]))))
                    for b in ref)
        require(all(np.isfinite(r["fc8"]).all() for r in full),
                "non-finite serving rows")
        require(full == ref, "serving rows for a full bucket differ from "
                f"the -features rows (max abs {worst})")
        require(predict(sent[:bucket]) == full, "same request, other rows")
        # partial buckets run another batch shape: equal up to rounding
        for recs in (sent[:1], sent[3:8]):
            for row in predict(recs):
                d = np.max(np.abs(np.asarray(row["fc8"])
                                  - np.asarray(want[row["SampleID"]]["fc8"])))
                require(d <= 1e-2 * scale,
                        f"partial bucket row off by {d} (scale {scale})")
                require(row["label"] == want[row["SampleID"]]["label"],
                        "label column lost")
        result.update(full_bucket=bucket, full_bucket_bit_equal=True,
                      mesh=health.get("mesh"))
    except BaseException as e:      # noqa: BLE001 — re-raised by main()
        result["error"] = e
    finally:
        os.kill(os.getpid(), signal.SIGINT)     # drain, then exit 0


# ------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at tiny shapes (needs "
                    "JAX_PLATFORMS=cpu); prints no device result")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="report directory (bulky inputs/models are removed)")
    args = ap.parse_args()
    t_start = time.perf_counter()

    if args.rehearsal:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit("chip_smoke: --rehearsal runs on the CPU only; set "
                     "JAX_PLATFORMS=cpu")
        os.environ["COS_FLASH_INTERPRET"] = "1"   # Pallas interpret mode
    crop, side, per_dev, val_batch = ((67, 72, 4, 8) if args.rehearsal
                                      else (227, 256, 256, 32))
    # largest serving bucket = the -features batch: same program shape
    os.environ["COS_SERVE_MAX_BATCH"] = str(val_batch)

    import jax
    from caffeonspark_tpu import native
    from caffeonspark_tpu.caffe_on_spark import main as cos_main
    from caffeonspark_tpu.processor import CaffeProcessor
    from caffeonspark_tpu.serving.aot import cache_entries
    from caffeonspark_tpu.utils.compile_cache import enable_compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("device", **device)
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearsal:
        sys.exit(f"chip_smoke: no TPU — JAX found {device}.  This smoke "
                 "proves the program on the chip; for a CPU rehearsal "
                 "run JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal")
    if args.rehearsal:
        require(not on_chip, "--rehearsal is the CPU run")
        say("rehearsal", note="CPU, tiny shapes, Pallas interpret mode; "
            "no device number is printed")
    ndev = len(devs)

    # every program this run compiles is persisted, whatever its compile
    # time, so a second run's entry count is exact
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = enable_compile_cache()
    cache_before = cache_entries(cache_dir)

    train_batch = per_dev * ndev
    test_interval, max_iter, snapshot = 4, 12, 8
    work = os.path.join(args.out, "work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    report = {"device": device, "rehearsal": args.rehearsal,
              "compile_cache": {"dir": cache_dir,
                                "entries_before": cache_before}}
    try:
        t0 = time.perf_counter()
        write_lmdb(os.path.join(work, "train_lmdb"), 2 * train_batch,
                   side, seed=0)
        # the validation records double as the serving requests
        sent = [{"id": key.decode(), "label": float(label),
                 "image_b64": base64.b64encode(jpeg).decode()}
                for key, label, jpeg in write_lmdb(
                    os.path.join(work, "val_lmdb"), 2 * val_batch, side,
                    seed=1)]
        solver_path = write_configs(
            work, crop=crop, side=side, train_batch=train_batch,
            val_batch=val_batch, max_iter=max_iter,
            test_interval=test_interval, snapshot=snapshot)
        say("inputs", seconds=round(time.perf_counter() - t0, 1),
            train_records=2 * train_batch, val_records=2 * val_batch)

        # ---- train, with interleaved validation and snapshots ----------
        model = os.path.join(work, "caffenet.caffemodel")
        stamps, stop = [], threading.Event()
        watcher = threading.Thread(target=watch_validation,
                                   args=(stamps, stop), daemon=True)
        watcher.start()
        t_train = time.perf_counter()
        rc = cos_main(["-conf", solver_path, "-train", "-output", work,
                       "-model", model])
        t_done = time.perf_counter()
        stop.set()
        watcher.join()
        require(rc == 0, f"-train returned {rc}")
        with open(os.path.join(work, "validation.json")) as f:
            rounds = [json.loads(line) for line in f]
        losses = [r["loss"] for r in rounds]
        require(len(rounds) == max_iter // test_interval,
                f"validation rounds: {rounds}")
        require(all(math.isfinite(v) for r in rounds for v in r.values()),
                f"non-finite validation output: {rounds}")
        # (the rehearsal's 67-pixel net is not CaffeNet: at the reference
        # learning rate its loss wanders, so only the chip run is held
        # to the bound)
        require(args.rehearsal
                or abs(losses[0] - math.log(CLASSES)) < 0.7,
                f"first validation loss {losses[0]} is not near "
                f"ln({CLASSES}) = {math.log(CLASSES):.2f}")
        from caffeonspark_tpu.checkpoint import load_caffemodel_blobs
        import numpy as np
        blobs = load_caffemodel_blobs(model)
        require(all(np.isfinite(b).all() for bl in blobs.values()
                    for b in bl), "the written model is not finite")
        n_params = sum(b.size for bl in blobs.values() for b in bl)
        if not args.rehearsal:
            require(n_params == 60_965_224, f"CaffeNet has {n_params} "
                    "parameters, not 60,965,224 — not the full width")
        snaps = sorted(f for f in os.listdir(work)
                       if f.startswith("caffenet_iter_"))
        require(any(f.endswith(".solverstate") for f in snaps),
                f"no snapshot written: {snaps}")
        require(len(stamps) == len(rounds), "validation watcher lagged")
        report["train"] = {
            "steps": max_iter, "validation_loss": losses,
            "validation_accuracy": [r["accuracy"] for r in rounds],
            "parameters": int(n_params), "snapshots": snaps,
            "decoder": "native" if native.available() else "cv2",
            "seconds": {
                # start -> first fetched validation loss: compiles the
                # train and eval steps, runs test_interval fed steps
                "warmup_to_first_validation":
                    round(stamps[0] - t_train, 2),
                # fetched loss -> next fetched loss: test_interval fed
                # steps + one validation round, nothing compiling
                "steady_windows": [round(b - a, 2) for a, b
                                   in zip(stamps, stamps[1:])],
                "steps_per_window": test_interval,
                "total_with_snapshots": round(t_done - t_train, 2)}}
        say("train", **report["train"])

        report["step"] = check_compiled_step(CaffeProcessor._instance,
                                             ndev, on_chip)
        say("step", **report["step"])
        if ndev > 1:
            report["dp_parity"] = check_dp_parity(solver_path, ndev)
            say("dp_parity", **report["dp_parity"])

        # ---- features from the written model ---------------------------
        t0 = time.perf_counter()
        rc = cos_main(["-conf", solver_path, "-features", "fc8",
                       "-label", "label", "-model", model,
                       "-output", work])
        require(rc == 0, f"-features returned {rc}")
        with open(os.path.join(work, "features.json")) as f:
            feature_rows = [json.loads(line) for line in f]
        require([r["SampleID"] for r in feature_rows] ==
                [r["id"] for r in sent],
                f"{len(feature_rows)} feature rows for {len(sent)} records")
        require(all(len(r["fc8"]) == CLASSES
                    and np.isfinite(r["fc8"]).all()
                    and r["label"] == [s["label"]]
                    for r, s in zip(feature_rows, sent)),
                "feature rows: wrong width, label, or not finite")
        report["features"] = {
            "rows": len(feature_rows), "records_fed": len(sent),
            "seconds": round(time.perf_counter() - t0, 2),
            "placement": "one device" if ndev == 1 else
            f"one program on device 0; the other {ndev - 1} devices "
            "idle while extracting (no -mesh)"}
        say("features", **report["features"])

        # ---- serve in this process, over HTTP --------------------------
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        os.environ["COS_SERVE_METRICS"] = os.path.join(
            args.out, "serve_metrics.json")
        served: dict = {}
        client = threading.Thread(
            target=serve_client, daemon=True,
            args=(port, sent, feature_rows, val_batch, served))
        t0 = time.perf_counter()
        client.start()
        rc = cos_main(["-conf", solver_path, "-serve", "-features", "fc8",
                       "-label", "label", "-model", model,
                       "-servePort", str(port)])
        client.join(60)
        if "error" in served:
            raise served["error"]
        require(rc == 0 and not client.is_alive() and served,
                f"-serve returned {rc}, client state {served}")
        with open(os.environ["COS_SERVE_METRICS"]) as f:
            sm = json.load(f)
        require(sm["counters"].get("served_rows", 0) >= 2 * val_batch + 6,
                f"served rows: {sm['counters']}")
        served.update(
            buckets=sm["buckets"], warmup_seconds=sm.get("warmup_s"),
            seconds=round(time.perf_counter() - t0, 2),
            placement="serving mesh" if served.pop("mesh") is not None
            else "one device" if ndev == 1 else
            f"single-device forward on device 0; the other {ndev - 1} "
            "devices idle while serving (no -serveMesh)")
        report["serve"] = served
        say("serve", **served)
    finally:
        shutil.rmtree(work, ignore_errors=True)   # GBs of models + LMDBs

    report["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    report["compile_cache"]["entries_added"] = (
        report["compile_cache"]["entries_after"] - cache_before)
    report["seconds_total"] = round(time.perf_counter() - t_start, 1)
    say("compile_cache", **report["compile_cache"])
    say("done", seconds_total=report["seconds_total"])
    with open(os.path.join(args.out, "report.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    if args.rehearsal:
        print("[chip_smoke] rehearsal passed (CPU): all phases ran; this "
              "is not a chip result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
