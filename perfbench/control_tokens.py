#!/usr/bin/env python3
"""The control of `correct` for a `train_tokens` cell: the program's own
lower-precision path in the program's place, held to the cell's limits.
It has to FAIL.

    python3 perfbench/control_tokens.py --workload <cell> --seeds 1,2,3

`control.py` is tied to the image window (crops, pixel rows); this is the
same check for packed token rows.  The path is the system's own
`Solver(..., compute_dtype=bfloat16)` (`-dtype mixed`: bfloat16
activations over float32 master weights).  Per seed, in one process and at
the cell's own size: three batches of the benchmark's own rows, the
program's train step at the lower precision (control: has to fail) and,
with `--sound 1`, at the precision the configuration states (sound: has
to pass; the cell's own runs already read that through the timed path),
then the plain reference over the same batches, each through the
comparison a run makes (`windows/train_tokens.numbers`).  One model is on
the device at a time.  Exits 0 only if the control failed on every seed
and no sound reading did.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.control import fails          # noqa: E402,F401

STEPS = 3


def program_steps(solver_path: str, batches, columns, **precision):
    """The program's own Solver and train step over `batches`, reduced as
    the window's observer reduces a run -> (Kept, losses)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.proto import read_net, read_solver
    from caffeonspark_tpu.solver import Solver
    from perfbench.windows.train_tokens import Kept, flat
    net_path = os.path.join(os.path.dirname(solver_path),
                            "train_val.prototxt")
    solver = Solver(read_solver(solver_path), read_net(net_path), rank=0,
                    **precision)
    params, st = solver.init()
    step = jax.jit(solver.train_step_fn(), donate_argnums=(0, 1))
    kept, losses = Kept(), []
    kept("p0", flat(jax.device_get(params)))
    for it, (ids, targets) in enumerate(batches):
        ins = {columns[0]: jnp.asarray(ids.T, jnp.float32),
               columns[1]: jnp.asarray(targets.T, jnp.float32)}
        params, st, res = step(params, st, ins, solver.step_rng(it))
        losses.append(float(res["loss"]))
        if it == 0:
            kept("m1", flat(jax.device_get(st.history)))
            kept("v1", flat(jax.device_get(st.history2)))
            kept("p1", flat(jax.device_get(params)))
    kept("p_last", flat(jax.device_get(params)))
    del params, st, step, solver
    gc.collect()
    return kept, [float(np.float64(v)) for v in losses]


def readings(res: dict, seed: int, work: str, sound: bool = True) -> dict:
    """{"control": numbers[, "sound": numbers]} for one seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from perfbench.windows import train_tokens as tt
    cfg = res["config"]
    model = importlib.import_module("perfbench.reference." + cfg["reference"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    solver_path, rows, _, batch = tt.write_inputs(
        dict(res, root=ROOT, seed=seed, work=work, chips=1), work)
    rng = np.random.default_rng(seed + 2)
    batches = []
    for _ in range(STEPS):
        pick = rng.permutation(len(rows))[:batch]
        batches.append((rows[pick, :-1], rows[pick, 1:]))
    cols = res["traffic"]["columns"]
    sides = {"control": program_steps(solver_path, batches, cols,
                                      compute_dtype=jnp.bfloat16)}
    if sound:
        sides["sound"] = program_steps(solver_path, batches, cols)
    ref_kept = tt.Kept()
    with jax.default_device(jax.local_devices()[0]):
        ref = model.train_steps(cfg, seed, batches, ref_kept)
    mults = model.lr_mults(cfg)
    return {name: tt.numbers(kept, losses, ref_kept, ref["losses"], mults)
            for name, (kept, losses) in sides.items()}


def main(argv=None) -> int:
    from perfbench.run import resolve
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--sound", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    for k in [k for k in os.environ if k.startswith("COS_")]:
        del os.environ[k]
    res = resolve(ROOT, args.workload)
    limits = res["cell"]["limits"]
    work = os.path.join(ROOT, ".perfbench_work", "control." + args.workload)
    as_it_must = True
    for seed in (int(s) for s in args.seeds.split(",")):
        both = readings(res, seed, work, bool(args.sound))
        for name, nums in both.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": name, "numbers": nums,
                              "limits": {k: v for k, v in limits.items()
                                         if k in nums},
                              "fails": fails(nums, limits)}), flush=True)
        as_it_must = (as_it_must and bool(fails(both["control"], limits))
                      and not fails(both.get("sound", {}), limits))
    print("control: the lower precision failed on every seed and no sound "
          "reading did" if as_it_must else
          "control: NOT as it must be; read the lines above")
    return 0 if as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
