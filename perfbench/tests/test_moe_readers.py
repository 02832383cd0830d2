"""The eight readers of the expert layer's scopes and `tools/scope_tree.py`
on an op list as `opmeta.device_ops` gives it, its name stacks copied
from the optimised HLO of the tiny layer's gradient (the CPU compile of
`tests/test_moe_scopes.py`), and on the small trace recorded on the chip
(`data/moe_small.xplane.pb`, `tools/record_moe_trace.py`), which settles
how loops and conditionals lie on the "XLA Ops" line."""

import os

import pytest

from perfbench import run as R
from perfbench.harness import opmeta, scopes
from perfbench.tools import scope_tree

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "moe_small.xplane.pb")

FWD = "jit(step)/jvp(L1.moe)/"
PASS = "moe.experts/closed_call/while/body/closed_call/checkpoint/"
BWD = "jit(step)/transpose(jvp(jvp()))/checkpoint/"
BPASS = BWD + "L1.moe/moe.experts/while/body/closed_call/checkpoint/"
RUN = "cond/branch_1_fun/"
# (name stack, milliseconds): two steps' worth on the line, one after
# another
STACKS = [
    (FWD + "moe.route/dot_general", 1.0),
    (FWD + "moe.route/moe.sort/jit(argsort)/sort", 0.5),
    (FWD + "moe.experts/closed_call/while/body/dynamic_slice", 0.1),
    (FWD + PASS + RUN + "moe.gather/gather", 2.0),
    (FWD + PASS + RUN + "moe.products/dot_general", 3.0),
    (FWD + PASS + RUN + "moe.products/jit(silu)/mul", 0.25),
    (FWD + PASS + RUN + "moe.combine/scatter-add", 4.0),
    (FWD + "moe.shared/dot_general", 0.7),
    (BWD + "rematted_computation/L1.moe/moe.route/moe.sort/"
     "jit(argsort)/sort", 0.5),
    (BPASS + "rematted_computation/" + RUN + "moe.gather/gather", 2.0),
    (BPASS + "rematted_computation/" + RUN + "moe.products/dot_general",
     3.0),
    (BPASS + RUN + "moe.combine/gather", 1.0),
    (BPASS + RUN + "moe.products/dot_general", 6.0),
    (BPASS + RUN + "moe.gather/scatter-add", 5.0),
    (BWD + "L1.moe/moe.experts/while/body/closed_call/add_any", 8.0),
    (BWD + "L1.moe/moe.route/dot_general", 2.0),
    ("jit(step)/jvp(L1.attn)/attn/attn.core/cos_flash_fwd/pallas_call",
     9.0),
    ("jit(step)/update/mul:mul", 1.5),
    # the grouped products themselves, as the TPU's compiler names the
    # Mosaic calls it makes of `lax.ragged_dot`: no stack of the program's
    ("ragged-dot-metadata:", 0.5),
    ("ragged-dot-none:", 7.0),
    ("", 0.4),
]
# per step (the list is two steps): what each reader must say
WANT = {"moe.route": 2.0, "moe.sort": 0.5, "moe.experts": 17.175 + 3.75,
        "moe.gather": 4.5, "moe.products": 6.125 + 3.75, "moe.combine": 2.5}
# a parent's stacks: the two scopes it has, nothing inside them
PARENT = [(t.replace("moe.sort/", "").replace(RUN + "moe.gather/", RUN)
           .replace(RUN + "moe.products/", RUN)
           .replace(RUN + "moe.combine/", RUN), ms) for t, ms in STACKS]


def fake_run(stacks, steps=2, **more):
    ops, t = [], 10.0
    for tf_op, ms in stacks:
        ops.append((tf_op, t, t + ms * 1e-3))
        t += ms * 1e-3 + 1e-6
    run = {"device_ops": (ops, (10.0, t)), "steps": steps,
           "trace": {"busy_s": sum(ms for _, ms in stacks) * 1e-3},
           "trace_dir": None, "batch": 1}
    run.update(more)
    return run


@pytest.mark.parametrize("scope", sorted(WANT))
def test_each_scope_reader_reads_its_scope(scope):
    got = R.read_metric(scope + "_device_ms.train", fake_run(STACKS))
    assert got == pytest.approx(WANT[scope])


def test_unscoped_is_the_whole_less_the_three_parts():
    got = R.read_metric("moe.experts_unscoped_device_ms.train",
                        fake_run(STACKS))
    # the scan's own slice and the sum into the carried gradients
    assert got == pytest.approx((0.1 + 8.0) / 2)
    assert got == pytest.approx(
        WANT["moe.experts"] - WANT["moe.gather"] - WANT["moe.products"]
        - WANT["moe.combine"])


@pytest.mark.parametrize("name", [
    "moe.sort_device_ms.train", "moe.gather_device_ms.train",
    "moe.products_device_ms.train", "moe.combine_device_ms.train",
    "moe.experts_unscoped_device_ms.train", "moe.products_mfu_pct.train"])
def test_a_parents_stacks_leave_the_new_lines_out(name):
    run = fake_run(PARENT, device={"kind": "TPU v5 lite"},
                   experts={"held_share": 0.06}, ctx={"chips": 1})
    assert R.read_metric(name, run) is None
    # the two scopes it has read what they read
    assert R.read_metric("moe.route_device_ms.train", run) \
        == pytest.approx(WANT["moe.route"])
    assert R.read_metric("moe.experts_device_ms.train", run) \
        == pytest.approx(WANT["moe.experts"])


def test_no_trace_no_line():
    run = {"steps": 4, "trace": None}
    for scope in WANT:
        assert R.read_metric(scope + "_device_ms.train", run) is None
    assert R.read_metric("moe.experts_unscoped_device_ms.train", run) is None
    assert R.read_metric("moe.products_mfu_pct.train", run) is None


def test_a_loop_event_that_carries_the_scope_is_counted_beside_its_body():
    """What `scopes.seconds` does with an enclosing `while` event whose
    own name stack holds the scope: it sums every event whose stack
    matches, without a union, so the loop's time lands on `moe.experts`
    (and on the unscoped remainder) a second time.  The recorded trace
    below says whether the profiler writes such events; this says what
    the readers would then read, so that a change of the profiler's
    shows."""
    inside = sum(ms for t, ms in STACKS if "moe.experts/" in t
                 and t.startswith(FWD))
    loop = (FWD + "moe.experts/closed_call/while", inside)
    run = fake_run(STACKS + [loop])
    assert R.read_metric("moe.experts_device_ms.train", run) \
        == pytest.approx(WANT["moe.experts"] + inside / 2)
    assert R.read_metric("moe.experts_unscoped_device_ms.train", run) \
        == pytest.approx((0.1 + 8.0 + inside) / 2)
    # the inner scopes are not the loop's: they read what they read
    assert R.read_metric("moe.products_device_ms.train", run) \
        == pytest.approx(WANT["moe.products"])


# ------------------------------------------------------------------ mfu

PLAN = {"8192x2048 top 10 of 512, 32 held x 512 gated, shared 512": {
    "layers": ["L0.moe", "L1.moe", "L2.moe", "L3.moe"],
    "assignments": 81920, "rows": 7168, "passes": 12,
    "passes_even_router": 1, "row_tile": 512, "row_flops": 6291456,
    "carry_bytes": 402653184}}


def mfu_run(products_ms, **more):
    stacks = [(FWD + PASS + RUN + "moe.products/dot_general", products_ms)]
    return fake_run(stacks, steps=1, **dict(
        dict(device={"kind": "TPU v5 lite"}, ctx={"chips": 1},
             experts={"held_share": 0.0625}), **more))


def test_products_mfu_counts_the_held_rows_three_times(monkeypatch):
    from caffeonspark_tpu.ops import layers as L
    monkeypatch.setattr(L, "_MOE_PLANS", PLAN)
    # 4 layers x 5,120 held rows x 6.29 MFLOP x 3 = 386.5 GFLOP a step:
    # 1.962 ms at 197 TFLOP/s
    flops = 3 * 0.0625 * 4 * 81920 * 6291456
    got = R.read_metric("moe.products_mfu_pct.train", mfu_run(40.0))
    assert got == pytest.approx(100 * flops / 40e-3 / 197e12)
    assert 4.8 < got < 5.0


def test_products_mfu_over_100_reaches_the_rule_that_ends_the_run(
        monkeypatch):
    """No `min(..., 100)`: products attributed elsewhere read over 100,
    and `run.py` ends a run on any metric with `mfu` in its name that
    does."""
    from caffeonspark_tpu.ops import layers as L
    monkeypatch.setattr(L, "_MOE_PLANS", PLAN)
    assert R.read_metric("moe.products_mfu_pct.train", mfu_run(1.5)) > 130
    rule = open(R.__file__).read()
    assert '"mfu" in name' in rule and "over 100% of the" in rule


def test_products_mfu_needs_the_programs_plan_and_the_windows_share(
        monkeypatch):
    from caffeonspark_tpu.ops import layers as L
    monkeypatch.setattr(L, "_MOE_PLANS", {})
    assert R.read_metric("moe.products_mfu_pct.train", mfu_run(40.0)) is None
    monkeypatch.setattr(L, "_MOE_PLANS", PLAN)
    assert R.read_metric("moe.products_mfu_pct.train",
                         mfu_run(40.0, experts={})) is None
    assert R.read_metric("moe.products_mfu_pct.train",
                         mfu_run(40.0, device=None)) is None


# ----------------------------------------------------------- scope_tree

def test_path_of_takes_transformations_and_control_flow_out():
    p = scope_tree.path_of
    assert p(FWD + PASS + RUN + "moe.products/jit(silu)/mul") == (
        ("L1.moe", "moe.experts", "moe.products"), "forward")
    assert p(BPASS + "rematted_computation/" + RUN + "moe.gather/gather") \
        == (("L1.moe", "moe.experts", "moe.gather"), "recomputation")
    assert p(BPASS + RUN + "moe.gather/scatter-add") == (
        ("L1.moe", "moe.experts", "moe.gather"), "backward")
    assert p("jit(step)/transpose(jvp(L3.moe))/moe.experts/while") == (
        ("L3.moe", "moe.experts"), "backward")
    # a kernel's name is a node, the call is not
    assert p("jit(step)/jvp(L0.gdn)/gdn/gdn.scan/while/body/closed_call/"
             "cos_gdn_bwd/pallas_call")[0] == (
        "L0.gdn", "gdn", "gdn.scan", "cos_gdn_bwd")
    assert p("jit(step)/update/mul:mul") == (("update",), "forward")
    assert p("") == ((), "forward") and p("fusion.12") == ((), "forward")


def test_tree_sums_every_prefix_by_phase():
    ops, window = fake_run(STACKS)["device_ops"]
    sums = scope_tree.tree(ops, window)
    ms = lambda path: {k: round(1e3 * v, 6)       # noqa: E731
                       for k, v in sums[path].items() if k != "own"}
    assert ms(("L1.moe", "moe.experts", "moe.gather")) == {
        "forward": 2.0, "recomputation": 2.0, "backward": 5.0, "union": 9.0}
    assert ms(("L1.moe", "moe.route", "moe.sort")) == {
        "forward": 0.5, "recomputation": 0.5, "backward": 0.0, "union": 1.0}
    whole = ms(("L1.moe", "moe.experts"))     # the scope: no ragged-dot
    assert whole["forward"] + whole["recomputation"] + whole["backward"] \
        == pytest.approx(2 * WANT["moe.experts"] - 7.5)
    layer = ms(("L1.moe",))
    assert layer["forward"] == pytest.approx(1.0 + 0.5 + 0.1 + 9.25 + 0.7)
    line = sums[()]
    assert sum(line[p] for p in scope_tree.PHASES) == pytest.approx(
        sum(m for _, m in STACKS) * 1e-3)
    assert line["own"] == pytest.approx({
        "(no stack)": 0.4e-3, "ragged-dot-none": 7e-3,
        "ragged-dot-metadata": 0.5e-3})
    lines = scope_tree.render(sums, steps=2)
    at = {l.split()[0]: l for l in lines[1:]}
    assert list(at)[:2] == ["(the", "L1.moe"]
    assert lines.index(at["moe.experts"]) < lines.index(at["moe.gather"])
    assert at["moe.gather"].split()[1:5] == ["4.500", "1.000", "1.000",
                                             "2.500"]
    assert not any("overlap" in l for l in lines)
    # what lies at `moe.experts` itself, under none of its three scopes
    own = sums[("L1.moe", "moe.experts")]["own"]
    assert own == pytest.approx({"dynamic_slice": 0.1e-3, "add_any": 8e-3})
    assert lines[lines.index(at["moe.experts"]) + 1].strip() == (
        "(its own 4.050: add_any 4.000, dynamic_slice 0.050)")


def test_tree_says_where_events_overlap():
    loop = (FWD + "moe.experts/closed_call/while", 0.0)
    ops, window = fake_run(STACKS)["device_ops"]
    inside = [o for o in ops if o[0].startswith(FWD + "moe.experts/")]
    ops = ops + [(loop[0], inside[0][1], inside[-1][2])]
    lines = scope_tree.render(scope_tree.tree(ops, window), steps=2)
    flagged = [l.split()[0] for l in lines if "events overlap" in l]
    assert flagged == ["(the", "L1.moe", "moe.experts"]


def test_loops_counts_the_loop_events_and_what_lies_inside_them():
    ops, window = fake_run(STACKS)["device_ops"]
    named = [(f"fusion.{i}", s, e) for i, (_, s, e) in enumerate(ops)]
    inside = [o for o in ops if o[0].startswith(FWD + "moe.experts/")]
    s, e = inside[0][1], inside[-1][2]
    # one loop with a stack that holds a scope, one conditional without
    named += [("while.62", s, e), ("cond.1098", inside[1][1], inside[-1][2])]
    ops = ops + [(FWD + "moe.experts/closed_call/while", s, e),
                 ("", inside[1][1], inside[-1][2])]
    got = scope_tree.loops(named, ops, window)
    assert got["while"]["events"] == 1 and got["while"]["with_node"] == 1
    assert got["while"]["inside_seconds"] == pytest.approx(9.35e-3)
    assert got["while"]["seconds"] == pytest.approx(e - s)
    assert got["cond"]["with_stack"] == 0
    assert got["cond"]["inside_seconds"] == pytest.approx(9.25e-3)
    assert "error" in scope_tree.loops(named[:-1], ops, window)


# ------------------------------------------- the trace recorded on the chip

@pytest.fixture(scope="module")
def recorded():
    """(ops with their stacks, the same events with their HLO names, the
    window) of `data/moe_small.xplane.pb`: two gradient steps through one
    dropless layer of three passes on a TPU v5e (my chip run, PR 38)."""
    from perfbench.harness import trace as tr
    if not os.path.exists(SMALL):
        pytest.skip("no recorded trace")
    ops = next(o for _, o in sorted(opmeta.device_ops(SMALL).items()) if o)
    planes = tr.load(SMALL)["devices"]
    named = next(tr.op_events(planes[p]) for p in sorted(planes)
                 if tr.op_events(planes[p]))
    return ops, named, (min(o[1] for o in ops), max(o[2] for o in ops))


def test_recorded_loops_lie_beside_their_bodies_and_carry_no_stack(
        recorded):
    """How the profiler lays a `while` and a `cond` on the "XLA Ops"
    line: each is an event of its own, its body's ops are events too
    (inside its interval), and the loop's event has NO name stack, so no
    scope matches it and `scopes.seconds` counts a scope's time once.
    The day a loop's event carries its scope this fails, and
    `moe.experts_device_ms.train` would count the scan twice."""
    ops, named, window = recorded
    assert len(ops) == len(named) == 826
    got = scope_tree.loops(named, ops, window)
    assert got["while"]["events"] == 4          # forward + backward, twice
    assert got["cond"]["events"] >= 12          # three passes each
    for kind in ("while", "cond"):
        assert got[kind]["with_stack"] == 0 and got[kind]["with_node"] == 0
        assert got[kind]["inside_seconds"] > 0.5 * got[kind]["seconds"]
    sums = scope_tree.tree(ops, window)
    line = sums[()]
    # the loops overlap their bodies on the line; no node's events do
    assert line["union"] < 0.7 * sum(line[p] for p in scope_tree.PHASES)
    for path, at in sums.items():
        if path:
            assert at["union"] == pytest.approx(
                sum(at[p] for p in scope_tree.PHASES), rel=1e-6), path


def test_recorded_trace_holds_every_scope_in_every_phase(recorded):
    ops, _, window = recorded
    sums = scope_tree.tree(ops, window)
    experts = ("L1.moe", "moe.experts")
    for scope in ("moe.gather", "moe.products", "moe.combine"):
        at = sums[experts + (scope,)]
        assert all(at[p] > 0 for p in scope_tree.PHASES), (scope, at)
    sort = sums[("L1.moe", "moe.route", "moe.sort")]
    assert sort["forward"] > 0 and sort["recomputation"] > 0
    assert sort["backward"] == 0
    assert {p[:2] for p in sums if len(p) >= 2} == {
        ("L1.moe", "moe.route"), experts, ("L1.moe", "moe.shared")}


def test_recorded_grouped_products_carry_no_stack_of_the_programs(recorded):
    """The Mosaic calls the TPU's compiler makes of `lax.ragged_dot` are
    named `ragged-dot-none` / `ragged-dot-metadata` and nothing else: no
    scope, no prototxt layer.  Twelve products a step (three forward,
    three recomputed, three for dx, three for dW) and four group
    tables."""
    ops, named, window = recorded
    calls = [(t, n) for (t, _, _), (n, _, _) in zip(ops, named)
             if "ragged" in t or "ragged" in n]
    assert sorted({t for t, _ in calls}) == ["ragged-dot-metadata:",
                                             "ragged-dot-none:"]
    assert sum(t == "ragged-dot-none:" for t, _ in calls) == 24
    assert sum(t == "ragged-dot-metadata:" for t, _ in calls) == 8
    assert set(scope_tree.tree(ops, window)[()]["own"]) >= {
        "ragged-dot-none", "ragged-dot-metadata", "(no stack)"}
    run = {"device_ops": (ops, window), "steps": 2, "trace": {"busy_s": 1}}
    scope_only = scopes.ms_per_step(run, r"moe\.products")
    products = R.read_metric("moe.products_device_ms.train", run)
    calls_ms = scopes.ms_per_step(run, r"ragged-dot-.*")
    assert products == pytest.approx(scope_only + calls_ms)
    assert calls_ms > 5 * scope_only        # the scope alone reads the SiLU


def test_recorded_readers_nest(recorded):
    ops, _, window = recorded
    run = {"device_ops": (ops, window), "steps": 2, "trace": {"busy_s": 1}}
    read = lambda n: R.read_metric(n + ".train", run)     # noqa: E731
    got = {n: read(f"moe.{n}_device_ms") for n in (
        "route", "sort", "experts", "gather", "products", "combine",
        "experts_unscoped")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["sort"] < got["route"]
    assert got["experts_unscoped"] == pytest.approx(
        got["experts"] - got["gather"] - got["products"] - got["combine"])
    # the recorder wrote no window of `moe_stats`: no share, no line
    assert read("moe.products_mfu_pct") is None
