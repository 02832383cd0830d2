"""One traced run of a cell as `perfbench/run.py` makes it, and beside
its result line the device time a step of every node of the program's
own name stacks: prototxt layer -> program scope -> scope inside it, each
split by phase (forward, recomputation, backward), with no table of
patterns to keep.

    python3 perfbench/tools/scope_tree.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 1

An "XLA Ops" event's name stack (`tf_op`, harness/opmeta.py) reads
`jit(step)/transpose(jvp(L3.moe))/moe.experts/while/body/closed_call/
checkpoint/rematted_computation/cond/branch_1_fun/moe.gather/gather`.
Its nodes are `L3.moe > moe.experts > moe.gather`: what is left when the
transformations (`jit(..)` with what it wraps, `jvp`, `transpose`), the
control flow (`while`, `body`, `cond`, `branch_*`, `closed_call`,
`checkpoint`, `remat*`, `pallas_call`) and the trailing primitive are
taken out.  Its phase is recomputation if the stack holds
`rematted_computation`, backward if it holds `transpose(`, forward
otherwise.  A node's time is the sum over the events under it, as
`harness/scopes.py` sums a scope; where the union of their intervals is
shorter, events overlap (a loop counted beside its body) and the line
says so.  What a node's events take that lie under none of its children
is printed beneath it by primitive (`moe.experts`' own: the scan's and
the conditionals' ops).

After the tree: the time under no node at all, and how the loops and
conditionals lie on the line (events named `while*` / `cond*`, how many
carry a name stack, their time beside that of the other events inside
them).  The driver runs `perfbench/run.py`, never this.
"""

import bisect
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as R                        # noqa: E402
from perfbench.harness import trace as tr             # noqa: E402

PHASES = ("forward", "recomputation", "backward")
CONTROL = re.compile(r"^(while|body|cond|branch_\d+_fun|closed_call|"
                     r"checkpoint|remat.*|pallas_call|custom_[jv][vj]p_call"
                     r"|core_call)$")
WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
LOOP_EVENT = re.compile(r"^(while|cond|conditional)[.\d]*$")


def path_of(tf_op: str):
    """(nodes, phase) of one event's name stack."""
    parts = tf_op.split(":", 1)[0].split("/")
    nodes, backward = [], False
    for part in parts[:-1]:                 # the last one is the primitive
        wrappers = []
        while (m := WRAPPED.match(part)):
            wrappers.append(m.group(1))
            part = m.group(2)
        backward = backward or "transpose" in wrappers
        if part and not any(w.endswith("jit") for w in wrappers) \
                and not CONTROL.match(part):
            nodes.append(part)
    phase = ("recomputation" if "rematted_computation" in parts
             else "backward" if backward else "forward")
    return tuple(nodes), phase


def tree(ops, window):
    """{node path: {phase: seconds, "union": seconds, "own": {primitive:
    seconds}}} over every prefix of every event's path inside `window`;
    the empty path is the line.  "own" holds the events that lie at the
    node itself, under none of its children, by their trailing
    primitive."""
    lo, hi = window
    sums, spans = {}, {}
    for tf_op, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        nodes, phase = path_of(tf_op)
        for i in range(len(nodes) + 1):
            at = sums.setdefault(nodes[:i], dict.fromkeys(PHASES, 0.0))
            at[phase] += e - s
            spans.setdefault(nodes[:i], []).append((s, e))
        own = sums[nodes].setdefault("own", {})
        prim = tf_op.split(":", 1)[0].rsplit("/", 1)[-1] or "(no stack)"
        own[prim] = own.get(prim, 0.0) + e - s
    for path, at in sums.items():
        at["union"] = tr.total(tr.union(spans[path]))
        at.setdefault("own", {})
    return sums


def render(sums, steps, min_ms=0.005, own_ms=0.5):
    """The tree as lines, a node's children by time; ms a step.  A node
    with children whose own events take `own_ms` or more gets a second
    line: that time by primitive, the five largest."""
    per = 1e3 / steps
    lines = [f"{'node':<58}{'ms/step':>10}{'forward':>10}{'recomp.':>10}"
             f"{'backward':>10}"]

    def walk(path):
        at = sums[path]
        whole = sum(at[p] for p in PHASES)
        if path and whole * per < min_ms:
            return
        name = "  " * (len(path) - 1) + path[-1] if path else "(the line)"
        line = f"{name:<58}{whole * per:>10.3f}" + "".join(
            f"{at[p] * per:>10.3f}" for p in PHASES)
        if at["union"] < 0.995 * whole:
            line += f"  (union {at['union'] * per:.3f}: events overlap)"
        lines.append(line)
        kids = [p for p in sums if len(p) == len(path) + 1
                and p[:len(path)] == path]
        own = sum(at["own"].values())
        if path and kids and own * per >= own_ms:
            top = sorted(at["own"].items(), key=lambda kv: -kv[1])[:5]
            lines.append("  " * len(path) + f"(its own {own * per:.3f}: "
                         + ", ".join(f"{k} {v * per:.3f}" for k, v in top)
                         + ")")
        for kid in sorted(kids, key=lambda p: -sum(sums[p][q]
                                                   for q in PHASES)):
            walk(kid)

    walk(())
    return lines


def loops(named, ops, window):
    """How the loops and conditionals lie on the line.  `named` is the
    same line as `trace.load` gives it ((HLO name, start, end), the same
    events in the same order as `ops`): per kind of event the count, how
    many carry a name stack, how many of those stacks hold a node, their
    seconds, and the seconds of the other events that lie inside them."""
    lo, hi = window
    if len(named) != len(ops) or any(
            abs(a[1] - b[1]) > 1e-6 for a, b in zip(named[::997],
                                                    ops[::997])):
        return {"error": f"{len(named)} named events, {len(ops)} stacks, "
                         "or not in one order"}
    # ops run one at a time: the other events inside a loop's interval
    # are a run of the events sorted by start
    leaves = sorted((s, e) for n, s, e in named if not LOOP_EVENT.match(n))
    starts = [s for s, _ in leaves]
    upto = [0.0]
    for s, e in leaves:
        upto.append(upto[-1] + e - s)
    out = {}
    for (name, s, e), (tf_op, _, _) in zip(named, ops):
        m = LOOP_EVENT.match(name)
        s, e = max(s, lo), min(e, hi)
        if not m or e <= s:
            continue
        at = out.setdefault(m.group(1), {
            "events": 0, "with_stack": 0, "with_node": 0, "seconds": 0.0,
            "inside_seconds": 0.0, "a_stack": ""})
        at["events"] += 1
        at["with_stack"] += bool(tf_op)
        at["with_node"] += bool(path_of(tf_op)[0])
        at["a_stack"] = at["a_stack"] or tf_op
        at["seconds"] += e - s
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        at["inside_seconds"] += upto[j] - upto[i]
    return out


def report(run: dict) -> None:
    from perfbench.harness import scopes
    t, steps = run.get("trace"), run.get("steps")
    if not t or not steps:
        return
    ops, window = scopes.ops_of_run(run)
    if not ops:
        print("[scope_tree] the trace holds no device event")
        return
    sums = tree(ops, window)
    for line in render(sums, steps):
        print("[scope_tree] " + line)
    line = sums[()]
    facts = {
        "steps": steps, "events": len(ops),
        "step.device_ms": 1e3 * t["busy_s"] / steps,
        "sum_of_events_ms": 1e3 * sum(line[p] for p in PHASES) / steps,
        "union_of_events_ms": 1e3 * line["union"] / steps,
        "no_stack_events": sum(1 for o in ops if not o[0]),
        # events under no node, by primitive: the compiler's own names
        # (`ragged-dot-none`) and those without a stack (loops,
        # conditionals, some fusions)
        "no_node_ms": {k: round(1e3 * v / steps, 4) for k, v in sorted(
            line["own"].items(), key=lambda kv: -kv[1])[:8]}}
    print("[scope_tree] " + json.dumps(facts))
    path = tr.find_xplane(run["trace_dir"])
    planes = tr.load(path)["devices"]
    named = next((tr.op_events(planes[p]) for p in sorted(planes)
                  if tr.op_events(planes[p])), [])
    found = loops(named, ops, window)
    for at in found.values():
        if "seconds" in at:
            at["ms_per_step"] = 1e3 * at.pop("seconds") / steps
            at["inside_ms_per_step"] = 1e3 * at.pop("inside_seconds") / steps
    print("[scope_tree] loops " + json.dumps(found))
    print("[scope_tree] xplane " + json.dumps(
        {"bytes": os.path.getsize(path)}))


def main(argv=None) -> int:
    seen = {}
    read_metric = R.read_metric

    def keep_run(name, run):
        seen["run"] = run
        return read_metric(name, run)

    R.read_metric = keep_run
    try:
        return R.main(argv)
    finally:            # a run that a reader ended is read all the same
        if "run" in seen:
            report(seen["run"])


if __name__ == "__main__":
    sys.exit(main())
