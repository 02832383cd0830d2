"""One traced run of a cell as `perfbench/run.py` makes it, and beside
its result line the device time a step of every part the harness can
name: `opmeta`'s parts (update, moe, attn, head), further scopes and
prototxt layer names through `harness/scopes.py`, and what is left of
`step.device_ms.train` (ops run one at a time on a chip, so the parts
add up to the step).

    python3 perfbench/tools/parts.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 1

For PERF.md's tables: a cell that is in no list of `attn.device_ms.train`
and its like is read here.  The driver runs `perfbench/run.py`, never
this.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as R                        # noqa: E402

# inside a part
INNER = {"sconv.mix": r"sconv\.mix", "attn.core": r"attn\.core",
         "moe.route": r"moe\.route", "moe.experts": r"moe\.experts"}
# beside opmeta's parts: scopes and prototxt layer names
OTHER = {"sconv": r"sconv", "dense_ffn": r"L\d+\.(gate|up|down|act|prod)",
         "norms_residuals": r"L\d+\.(norm|res)[12]", "embed": r"embed"}


def report(run: dict) -> None:
    from perfbench.harness import opmeta, scopes
    t, steps = run.get("trace"), run.get("steps")
    if not t or not steps:
        return
    per = lambda s: None if s is None else 1e3 * s / steps   # noqa: E731
    parts = {k: per(v) for k, v in opmeta.of_run(run).items()}
    parts.update((k, per(scopes.seconds(run, p))) for k, p in OTHER.items())
    inner = {k: per(scopes.seconds(run, p)) for k, p in INNER.items()}
    step = 1e3 * t["busy_s"] / steps
    print("[parts] " + json.dumps({
        "steps": steps, "step.device_ms": step, "parts_ms": parts,
        "inside_ms": inner,
        "rest_ms": step - sum(v for v in parts.values() if v)}))


def main(argv=None) -> int:
    seen = {}
    read_metric = R.read_metric

    def keep_run(name, run):
        seen["run"] = run
        return read_metric(name, run)

    R.read_metric = keep_run
    rc = R.main(argv)
    if "run" in seen:
        report(seen["run"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
