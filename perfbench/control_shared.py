#!/usr/bin/env python3
"""The planted faults of a cell whose layers read what other layers
made (a scan output as memory, another layer's keys and values) and
whose attention runs under a window: the same program with one of them
planted in its net text, held to the cell's limits.  Each has to FAIL.

    python3 perfbench/control_shared.py --workload <cell> --seeds 1,2 \
        --faults memory,window

`memory`: every Gated Memory Unit reads the scan output of the FIRST
Mamba layer of the net and not that of the layer the model names (the
memory replaced by another tensor of its shape).  `window`: every
`window:` line taken out (`control_window.unwindowed`: plain causal
attention in the layers that should see `window` keys).  Per seed, in one
process and at the cell's own size, as `control_tokens.py` runs the lower
precision: three batches of the benchmark's own rows, each faulty
program's train step, the plain reference (as the configuration states)
over the same batches, the comparison a run makes
(`windows/train_tokens.numbers`).  Exits 0 only if every fault failed a
limit on every seed (and, with `--sound 1`, the program as stated none).
The benchmark's own runs never run this;
`perfbench/tests/test_rehearsal_phi4flash.py` plants the same faults
under the timed path at tiny size.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.control import fails          # noqa: E402
from perfbench.control_tokens import STEPS, program_steps   # noqa: E402
from perfbench.control_window import unwindowed     # noqa: E402

_MAMBA = re.compile(r'layer \{\n  name: "(L\d+)\.mamba"\n  type: "Mamba"\n'
                    r'  bottom: "[^"]+"\n  top: "[^"]+"\n(  top: "[^"]+"\n)?')


def wrong_memory(solver_path: str) -> str:
    """Beside the run's inputs, the same solver over the same net text
    with the first Mamba layer given a second top and every Gated Memory
    Unit made to read it -> that solver's path."""
    src = os.path.dirname(solver_path)
    dst = os.path.join(src, "wrong_memory")
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(src, "train_val.prototxt")) as f:
        text = f.read()
    first = _MAMBA.search(text)
    made = re.search(r'top: "(L\d+\.memory)"', text)
    if not first or not made or first.group(2):
        raise ValueError(f"{src}: no Mamba layer before the one that "
                         "makes the memory")
    wrong = f"{first.group(1)}.wrong_memory"
    text = (text[:first.end()] + f'  top: "{wrong}"\n'
            + text[first.end():].replace(f'bottom: "{made.group(1)}"',
                                         f'bottom: "{wrong}"'))
    net_path = os.path.join(dst, "train_val.prototxt")
    with open(net_path, "w") as f:
        f.write(text)
    with open(solver_path) as f:
        solver = f.read()
    out = os.path.join(dst, "solver.prototxt")
    with open(out, "w") as f:
        f.write(re.sub(r'^net: ".*"$', f'net: "{net_path}"', solver,
                       count=1, flags=re.M))
    return out


FAULTS = {"memory": wrong_memory, "window": unwindowed}


def faulty_step(solver_path: str, fault: str):
    """The faulty program's jitted train step, for a test that puts it
    under the timed path."""
    import jax
    from caffeonspark_tpu.proto import read_net, read_solver
    from caffeonspark_tpu.solver import Solver
    path = FAULTS[fault](solver_path)
    solver = Solver(read_solver(path), read_net(os.path.join(
        os.path.dirname(path), "train_val.prototxt")), rank=0)
    return jax.jit(solver.train_step_fn(), donate_argnums=(0, 1))


def readings(res: dict, seed: int, work: str, faults=tuple(FAULTS),
             sound: bool = False) -> dict:
    """{fault: numbers[, "sound": numbers]} for one seed."""
    import jax
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
    sides = {f: program_steps(FAULTS[f](solver_path), batches, cols)
             for f in faults}
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
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--sound", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for k in [k for k in os.environ if k.startswith("COS_")]:
        del os.environ[k]
    res = resolve(ROOT, args.workload)
    limits = res["cell"]["limits"]
    work = os.path.join(ROOT, ".perfbench_work",
                        "control_shared." + args.workload)
    as_it_must = True
    for seed in (int(s) for s in args.seeds.split(",")):
        sides = readings(res, seed, work, tuple(args.faults.split(",")),
                         bool(args.sound))
        for name, nums in sides.items():
            failed = fails(nums, limits)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": name, "numbers": nums,
                              "limits": {k: v for k, v in limits.items()
                                         if k in nums},
                              "fails": failed}), flush=True)
            as_it_must = as_it_must and bool(failed) == (name != "sound")
    print("control_shared: every fault failed on every seed"
          if as_it_must else
          "control_shared: NOT as it must be; read the lines above")
    return 0 if as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
