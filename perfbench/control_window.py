#!/usr/bin/env python3
"""The planted fault of a cell whose attention layers run under a window:
the same program with every window taken out of its net text (plain causal
attention in the layers that should see `window` keys), held to the cell's
limits.  It has to FAIL.

    python3 perfbench/control_window.py --workload <cell> --seeds 1,2,3

What a fresh model's loss moves by when a window layer sees its whole past
is little, so the loss gaps need not show it; the first gradient does (the
keys and values of a window layer get gradient from every later row, not
from `window` of them).  Per seed, in one process and at the cell's own
size, as `control_tokens.py` runs the lower precision: three batches of the
benchmark's own rows, the unwindowed program's train step, the plain
reference (windowed, as the configuration states) over the same batches,
the comparison a run makes (`windows/train_tokens.numbers`), and beside it
the per-leaf gaps of the windowed layers' W_k and W_v.  Exits 0 only if the
fault failed a limit on every seed.  The benchmark's own runs never run
this; `perfbench/tests/test_rehearsal_smallthinker.py` plants the same
fault under the timed path at tiny size.
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

_WINDOW = re.compile(r"^\s*window: \d+\n", re.M)


def unwindowed(solver_path: str) -> str:
    """Beside the run's inputs, the same solver over the same net text
    with every `window:` line taken out -> that solver's path."""
    src = os.path.dirname(solver_path)
    dst = os.path.join(src, "unwindowed")
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(src, "train_val.prototxt")) as f:
        text = f.read()
    cut, n = _WINDOW.subn("", text)
    if not n:
        raise ValueError(f"{src}: the net text has no windowed layer")
    net_path = os.path.join(dst, "train_val.prototxt")
    with open(net_path, "w") as f:
        f.write(cut)
    with open(solver_path) as f:
        solver = f.read()
    out = os.path.join(dst, "solver.prototxt")
    with open(out, "w") as f:
        f.write(re.sub(r'^net: ".*"$', f'net: "{net_path}"', solver,
                       count=1, flags=re.M))
    return out


def unwindowed_step(solver_path: str):
    """The unwindowed program's jitted train step, for a test that puts
    it under the timed path."""
    import jax
    from caffeonspark_tpu.proto import read_net, read_solver
    from caffeonspark_tpu.solver import Solver
    path = unwindowed(solver_path)
    solver = Solver(read_solver(path), read_net(os.path.join(
        os.path.dirname(path), "train_val.prototxt")), rank=0)
    return jax.jit(solver.train_step_fn(), donate_argnums=(0, 1))


def window_leaf_gaps(prog, ref, cfg: dict) -> dict:
    """|‖program‖ - ‖reference‖| / ‖reference‖ of Adam's first moment
    after step 1 on W_k and W_v of the layers the configuration puts
    under a window: the worst, and each."""
    first = int(cfg.get("first_layer", 0))
    gaps = {}
    for i in range(int(cfg["num_hidden_layers"])):
        if not cfg["sliding_window_layout"][first + i]:
            continue
        for blob in ("W_k", "W_v"):
            k = f"L{i}.attn/{blob}"
            a, b = prog.norms["m1"][k], ref.norms["m1"][k]
            gaps[k] = abs(a - b) / b
    return {"window_kv_first_grad_norm_gap": max(gaps.values()),
            "window_kv_first_grad_norm_gaps": gaps}


def readings(res: dict, seed: int, work: str, sound: bool = False) -> dict:
    """{"unwindowed": numbers[, "sound": numbers]} for one seed."""
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
    sides = {"unwindowed": program_steps(unwindowed(solver_path), batches,
                                         cols)}
    if sound:
        sides["sound"] = program_steps(solver_path, batches, cols)
    ref_kept = tt.Kept()
    with jax.default_device(jax.local_devices()[0]):
        ref = model.train_steps(cfg, seed, batches, ref_kept)
    mults = model.lr_mults(cfg)
    return {name: dict(tt.numbers(kept, losses, ref_kept, ref["losses"],
                                  mults),
                       **window_leaf_gaps(kept, ref_kept, cfg))
            for name, (kept, losses) in sides.items()}


def main(argv=None) -> int:
    from perfbench.run import resolve
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--sound", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for k in [k for k in os.environ if k.startswith("COS_")]:
        del os.environ[k]
    res = resolve(ROOT, args.workload)
    limits = res["cell"]["limits"]
    work = os.path.join(ROOT, ".perfbench_work",
                        "control_window." + args.workload)
    as_it_must = True
    for seed in (int(s) for s in args.seeds.split(",")):
        both = readings(res, seed, work, bool(args.sound))
        for name, nums in both.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "program": name, "numbers": nums,
                              "limits": {k: v for k, v in limits.items()
                                         if k in nums},
                              "fails": fails(nums, limits)}), flush=True)
        as_it_must = (as_it_must and bool(fails(both["unwindowed"], limits))
                      and not fails(both.get("sound", {}), limits))
    print("control_window: the unwindowed program failed on every seed"
          if as_it_must else
          "control_window: NOT as it must be; read the lines above")
    return 0 if as_it_must else 1


if __name__ == "__main__":
    sys.exit(main())
