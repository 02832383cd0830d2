#!/usr/bin/env python3
"""The control of `correct`: the program's own lower-precision path put in
the program's place and held to the cell's limits.  It has to FAIL.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3

The path is the system's own `Solver(..., compute_dtype=bfloat16)`
(`-dtype mixed`: bfloat16 activations over float32 master weights), the
step a later PR would be tempted to make the default.  Per seed, in one
process and at the cell's own batch and image size: the benchmark's own
crops of its own records, the plain reference over them, then the
program's train step at the precision the configuration states (sound:
has to pass) and at the lower one (control: has to fail), each through
the same comparison as a run.  Prints every compared number of both
beside its limit; exits 0 only if, on every seed, the sound program
passed and the control failed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEPS = 3


def program_steps(solver_path: str, batches, **precision) -> dict:
    """The program's own Solver and train step over `batches`, copied out
    as the window's observer copies a run."""
    import jax
    import jax.numpy as jnp
    from caffeonspark_tpu.proto import read_net, read_solver
    from caffeonspark_tpu.solver import Solver
    from perfbench.harness.check import by_index
    net_path = os.path.join(os.path.dirname(solver_path),
                            "train_val.prototxt")
    solver = Solver(read_solver(solver_path), read_net(net_path), rank=0,
                    **precision)
    params, st = solver.init()
    step = jax.jit(solver.train_step_fn(), donate_argnums=(0, 1))
    out = {"p0": by_index(jax.device_get(params)), "losses": []}
    for it, (data, labels) in enumerate(batches):
        params, st, res = step(params, st, {"data": jnp.asarray(data),
                                            "label": jnp.asarray(labels)},
                               solver.step_rng(it))
        out["losses"].append(float(res["loss"]))
        if it == 0:
            out["v1"] = by_index(jax.device_get(st.history))
            out["p1"] = by_index(jax.device_get(params))
    out["p_last"] = by_index(jax.device_get(params))
    return out


def readings(res: dict, seed: int, work: str) -> dict:
    """{"sound": numbers, "control": numbers} for one seed."""
    import jax.numpy as jnp
    import numpy as np
    from perfbench.harness import check
    from perfbench.reference import common
    from perfbench.windows import train
    cfg = res["config"]
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    crop, side = int(cfg["crop"]), int(res["traffic"]["side"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    solver_path, pixels, labels, _, batch = train.write_inputs(
        dict(res, root=ROOT, seed=seed, work=work), work)
    rng = np.random.default_rng(seed + 2)
    batches = []
    for _ in range(STEPS):
        recs = rng.permutation(len(pixels))[:batch]
        found = [(int(r), int(rng.integers(0, side - crop + 1)),
                  int(rng.integers(0, side - crop + 1)),
                  bool(rng.integers(0, 2))) for r in recs]
        batches.append((check.rebuild_batch(found, pixels,
                                            cfg["mean_values"], crop),
                        labels[recs].astype(np.float32)))
    ref = common.train_steps(model, cfg, seed, batches)
    lr_mults = check.lr_mults_of(model.layers(cfg, crop))
    lr = cfg["solver"]["base_lr"]
    return {name: check.compare(program_steps(solver_path, batches, **kw),
                                ref, lr_mults, lr)
            for name, kw in (("sound", {}),
                             ("control", {"compute_dtype": jnp.bfloat16}))}


def fails(nums: dict, limits: dict) -> list:
    return [k for k, limit in limits.items()
            if k in nums and not nums[k] <= limit]


def main(argv=None) -> int:
    from perfbench.run import resolve
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    res = resolve(ROOT, args.workload)
    limits = res["cell"]["limits"]
    work = os.path.join(ROOT, ".perfbench_work", "control." + args.workload)
    as_it_must = True
    for seed in (int(s) for s in args.seeds.split(",")):
        both = readings(res, seed, work)
        for name, nums in both.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": name, "numbers": nums,
                              "limits": {k: v for k, v in limits.items()
                                         if k in nums},
                              "fails": fails(nums, limits)}), flush=True)
        as_it_must = (as_it_must and not fails(both["sound"], limits)
                      and bool(fails(both["control"], limits)))
    print("control: the sound program passed and the lower precision failed "
          "on every seed" if as_it_must else
          "control: NOT as it must be; read the lines above")
    return 0 if as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
