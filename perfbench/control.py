#!/usr/bin/env python3
"""The control of `correct`: the program's own lower-precision path put in
the program's place and held to the cell's limits.  It has to FAIL, and so
has each fault the cell can have.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3

The path is the system's own `Solver(..., compute_dtype=bfloat16)`
(`-dtype mixed`: bfloat16 activations over float32 master weights), the
step a later PR would be tempted to make the default.  Per seed, in one
process, at the cell's own batch and image size and over the cell's own
chips: the benchmark's own crops of its own records, the plain reference
over them, then the program's train step at the precision the
configuration states (sound: has to pass) and at the lower one (control:
has to fail), each as `ParallelSolver` lays it over the cell's mesh and
through the same comparison as a run.  Then the faults, planted in what
the stated program is fed: `half_batch` (half of the batch left out, the
mean taken over the rest: the first half of the rows, twice) and, over
several chips, `no_exchange` (every chip given the first chip's rows: what
a chip computes whose statistics and gradients are not exchanged).  Prints
every compared number of each beside its limit; exits 0 only if, on every
seed, the sound program passed and the control and every fault failed.
The benchmark's own runs never run this.
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


def program_steps(solver_path: str, feeds: dict, chips: int,
                  **precision) -> dict:
    """The program's own Solver and its step over the cell's mesh, as
    `CaffeProcessor` builds them for `-devices <chips>`, built once and run
    from the seeded state over each of `feeds` ({name: batches}); each
    copied out as the window's observer copies a run."""
    import jax
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from caffeonspark_tpu.proto import read_net, read_solver
    from caffeonspark_tpu.solver import Solver
    from perfbench.harness.check import by_index
    net_path = os.path.join(os.path.dirname(solver_path),
                            "train_val.prototxt")
    solver = Solver(read_solver(solver_path), read_net(net_path), rank=0,
                    **precision)
    psolver = ParallelSolver(solver, build_mesh(
        devices=jax.local_devices()[:chips]))
    step = psolver.train_step()
    outs = {}
    for name, batches in feeds.items():
        params, st = psolver.init()
        out = {"p0": by_index(jax.device_get(params)), "losses": []}
        for it, (data, labels) in enumerate(batches):
            params, st, res = step(
                params, st,
                psolver.shard_batch({"data": data, "label": labels}),
                solver.step_rng(it))
            out["losses"].append(float(res["loss"]))
            if it == 0:
                out["v1"] = by_index(jax.device_get(st.history))
                out["p1"] = by_index(jax.device_get(params))
        out["p_last"] = by_index(jax.device_get(params))
        outs[name] = out
    return outs


def repeat_first(batches, parts: int):
    """Every batch's first 1/parts of the rows, `parts` times over."""
    import numpy as np
    return [tuple(np.concatenate([a[:len(a) // parts]] * parts)
                  for a in batch) for batch in batches]


def readings(res: dict, seed: int, work: str) -> dict:
    """{"sound": numbers, "control": numbers, and a fault's name: numbers
    for each planted fault} for one seed."""
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
    import jax
    chips = int(res["chips"])
    ref = common.train_steps(model, cfg, seed, batches,
                             jax.local_devices()[:chips])
    lr_mults = check.lr_mults_of(model.layers(cfg, crop))
    lr = cfg["solver"]["base_lr"]
    stated = {"sound": batches, "half_batch": repeat_first(batches, 2)}
    if chips > 1:
        stated["no_exchange"] = repeat_first(batches, chips)
    outs = program_steps(solver_path, stated, chips)
    outs.update(program_steps(solver_path, {"control": batches}, chips,
                              compute_dtype=jnp.bfloat16))
    return {name: check.compare(out, ref, lr_mults, lr)
            for name, out in outs.items()}


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
        each = readings(res, seed, work)
        for name, nums in each.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": name, "numbers": nums,
                              "limits": {k: v for k, v in limits.items()
                                         if k in nums},
                              "fails": fails(nums, limits)}), flush=True)
        as_it_must = (as_it_must and not fails(each.pop("sound"), limits)
                      and all(fails(nums, limits) for nums in each.values()))
    print("control: the sound program passed, the lower precision and every "
          "planted fault failed, on every seed" if as_it_must else
          "control: NOT as it must be; read the lines above")
    return 0 if as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
