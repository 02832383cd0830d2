#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by its name in BENCHMARK.json:

    configs/<config>.json (+ its net text), reference/<reference>.py
    traffic/<traffic>.json          -> its "kind" picks windows/<kind>.py
    cells/<cell>.json               -> warm-up, records, limits of `correct`
    metrics/<metric name>.py        -> read(run) -> number or None

The run fails, printing no result line, without a TPU of a kind that
peaks.json knows, or with fewer chips than the cell asks for.  The last
line of a run's standard output is the result object; its last key,
`checks`, holds every number `correct` compared beside its limit, and the
same are the last lines of standard error.
"""

from __future__ import annotations

import time
T_PROCESS0 = time.perf_counter()

import argparse                                     # noqa: E402
import importlib                                    # noqa: E402
import importlib.util                               # noqa: E402
import json                                         # noqa: E402
import math                                         # noqa: E402
import os                                           # noqa: E402
import sys                                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(root: str, workload: str) -> dict:
    """The cell's files, found by name from the manifest."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        sys.exit(f"perfbench: no workload {workload!r} in BENCHMARK.json "
                 f"(have {sorted(cells)})")
    entry = cells[workload]
    conf_entry = next(c for c in manifest["configs"]
                      if c["name"] == entry["config"])
    return {"manifest": manifest, "entry": entry,
            "config": load_json(root, conf_entry["file"]),
            "traffic": load_json(HERE, "traffic", entry["traffic"] + ".json"),
            "cell": load_json(HERE, "cells", workload + ".json"),
            "chips": int(entry["chips"])}


def metric_names(manifest: dict, section: str, workload: str):
    return [m["name"] for m in manifest[section]
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, run: dict):
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, overrides: dict | None = None,
             device: dict | None = None, extra: dict | None = None) -> dict:
    """Resolve the cell, drive its window, reduce to the result object.
    `overrides` (tests: tiny sizes) is merged into config / cell / traffic;
    `device` None means a rehearsal: no device is named and every metric
    that is a time, a rate or a share is withheld."""
    res = resolve(root, workload)
    for part, patch in (overrides or {}).items():
        res[part].update(patch)
    res["chips"] = int(res["entry"]["chips"])
    kind = res["traffic"]["kind"]
    window = importlib.import_module(f"perfbench.windows.{kind}")
    ctx = dict(res, root=root, seed=int(seed), seconds=float(seconds),
               trace=bool(trace), t_process0=T_PROCESS0,
               work=os.path.join(root, ".perfbench_work", workload))
    ctx.update(extra or {})
    run = window.run(ctx)
    run["ctx"] = ctx
    run["device"] = device
    units = {m["name"]: m["unit"] for sec in ("end_to_end", "per_layer")
             for m in res["manifest"][sec]}
    if trace:
        from perfbench.harness import trace as tr
        run["trace"] = tr.reduce(tr.load(tr.find_xplane(run["trace_dir"])))
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name in metric_names(res["manifest"], section, workload):
        value = read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics}
    # every number compared, beside its limit; the key comes last
    checks = {name: {"value": plain(run["nums"].get(name)), "limit": limit}
              for name, limit in res["cell"]["limits"].items()}
    if device is None:
        result["metrics"] = {k: None for k in metrics}   # a CPU rehearsal
        result["rehearsal"] = True
        result["checks"] = checks
        return result
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    if trace:
        t = run["trace"]
        if t["busy_s"] <= 0:
            sys.exit("perfbench: the traced window holds no device "
                     "operation")
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in t["device_ops"]],
            "idle_gaps": [[n, s] for n, s in t["idle_gaps"]]}
    result["device"] = dev
    for name, m in metrics.items():
        if (name.endswith("_roofline") or "mfu" in name) \
                and m["value"] > 100:
            sys.exit(f"perfbench: {name} = {m['value']} is over 100% of the "
                     "peak: the operations are counted too high or the "
                     "time leaves out work")
    result["checks"] = checks
    return result


def plain(value):
    """A compared number as JSON can hold it."""
    if isinstance(value, float) and not math.isfinite(value):
        return repr(value)
    return value


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import caffeonspark_tpu  # noqa: F401
    except ImportError:
        sys.exit("perfbench: the system under test (caffeonspark_tpu) is "
                 "not in this directory")
    # every program option at its default: a later PR that changes a
    # default must show here
    for k in [k for k in os.environ if k.startswith("COS_")]:
        del os.environ[k]
    res = resolve(ROOT, args.workload)
    from perfbench.harness.devices import require_chip
    device = require_chip(res["chips"])
    print(f"[perfbench] {args.workload} seed {args.seed} on {device}",
          flush=True)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), device=device)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"perfbench: {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
