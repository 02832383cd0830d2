"""The dropless expert layer's device scopes, its loops over the passes
that run, and its pass plan.

`moe.sort` inside `moe.route`, and `moe.gather` / `moe.products` /
`moe.combine` inside a pass of `moe.experts`, are `jax.named_scope`s:
metadata on the lowered ops, read back by the benchmark from a device
trace's name stacks (`perfbench/harness/scopes.py`).  What is held here,
on the CPU: the tokens reach the optimised HLO's `op_name`s through
the loops over the passes that run (`_moe_passes`: a `custom_vjp`, its
forward loop and its backward loop), the block's recomputation and the
transpose; the work each names sits under it and under no other; the
values are PR 38's to the last bit; and `moe_plans()` is the three
language cells' plan, published as `info.moe`.  And of the loops
themselves (PR 39): gradients equal to a scan over every pass bit for
bit whatever the router fills, no work on a weight gradient outside
the backward loop, and the passes that ran in the job's metrics."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.models import zoo
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import route
from caffeonspark_tpu.proto import LayerParameter

INNER = ("moe.gather", "moe.products", "moe.combine")
# 48 tokens x top 2 of 8 experts, 2 held, row tile 8: passes of 32 rows,
# 3 of them, of which an even router fills one
N, D, H, E, K, HELD = 48, 32, 12, 8, 2, 2


@pytest.fixture
def tile8(monkeypatch):
    monkeypatch.setattr(L, "_MOE_ROW_TILE", 8)
    route.forget("moe")


def layer_param(scoring="sigmoid", gated=True, shared=2 * H, held=HELD,
                e=E, k=K, hidden=H, bias=False):
    return LayerParameter.from_text(f'''
      name: "L1.moe" type: "MixtureOfExperts" bottom: "x" top: "y"
      top: "stats"
      moe_param {{ num_experts: {e} hidden_dim: {hidden} top_k: {k}
        dispatch: "dropless" scoring: "{scoring}"
        routed_scaling_factor: 2.448 gated: {str(gated).lower()}
        selection_bias: {str(bias).lower()}
        shared_hidden_dim: {shared} experts_held: {held} }}''')


def blobs(lp, d=D, seed=7):
    keys = jax.random.split(jax.random.key(seed), 16)
    return [0.3 * jax.random.normal(k, shape, jnp.float32)
            for k, (_, shape, _) in zip(keys, L._moe_params(lp, [(N, d)]))]


def apply(lp, params, x):
    return L.get_op("MixtureOfExperts").apply(L.Ctx(train=True), lp,
                                              params, [x])


# --------------------------------------------------------------- scopes

@pytest.fixture
def stacks(tile8):
    """`op_name`s of the optimised HLO of a gradient through one block
    of the small kanana2 net (its expert layer under `recompute_block`),
    with the stats of the same step: the assignments held."""
    small = dict(vocab=64, hidden=D, heads=2, qk_nope=8, qk_rope=4,
                 v_head=6, kv_lora_rank=16, dense_width=48,
                 expert_width=H, experts=E, top_k=K, shared_experts=2,
                 expert_layers=1, seq=N // 2, batch=2, experts_held=HELD)
    net = Net(zoo.kanana2(**small, recompute=True))
    assert net.recompute_blocks
    params = net.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    ins = {k: jnp.asarray(rng.integers(0, 64, (N // 2, 2)), jnp.float32)
           for k in ("input_ids", "target_ids")}

    def loss(p):
        value, (tops, _) = net.loss(p, ins, train=True,
                                    rng=jax.random.key(1))
        return value, tops["L1.moe_stats"]

    fn = jax.jit(jax.grad(loss, has_aux=True))
    _, stats = fn(params)
    text = fn.lower(params).compile().as_text()
    names = sorted(set(re.findall(r'op_name="([^"]*)"', text)))
    return [n for n in names if "moe." in n], stats


def wrapped(name, scope):
    """How a scope's token stands in a name stack: plain where the
    forward pass wrote it, `jvp(...)` where the backward loop computes a
    pass again, `transpose(jvp(...))` where it pulls the pass back; None
    where the stack has no such token."""
    for form, ph in ((f"/transpose(jvp({scope}))/", "backward"),
                     (f"/jvp({scope})/", "recomputation"),
                     (f"/{scope}/", "forward")):
        if form in name:
            return ph
    return None


def phase(name, scope):
    """The phase of an op of `scope`.  What the block's `jax.checkpoint`
    runs again (the router and the sort; not the forward loop, whose
    result no gradient needs) is a recomputation too."""
    ph = wrapped(name, scope)
    if ph == "forward" and "rematted_computation" in name:
        return "recomputation"
    return ph


def under(names, scope, ph):
    return [n for n in names if phase(n, scope) == ph]


def test_a_pass_is_skipped_in_the_traced_step(stacks):
    _, stats = stacks
    held = float(stats[1]) * K * N
    assert 0 < held <= 64       # of 3 passes of 32 rows at least one idles
    assert float(stats[2]) == 0.0
    plan, = L.moe_plans().values()
    assert (plan["rows"], plan["passes"], plan["passes_even_router"]) \
        == (32, 3, 1)


def test_sort_lies_inside_route_and_is_not_computed_again(stacks):
    names, _ = stacks
    got = under(names, "moe.sort", "forward")
    assert got
    assert all("/moe.route/moe.sort/" in n for n in got)
    # the argsort and the counts' scatter-add are the scope's, the
    # router's product and top_k are not
    assert any(n.endswith("jit(argsort)/sort") for n in got)
    assert any(n.endswith("moe.sort/scatter-add") for n in got)
    route = under(names, "moe.route", "forward")
    for prim in ("dot_general", "top_k"):
        at = [n for n in route if n.endswith("/" + prim)]
        assert at and not any("moe.sort" in n for n in at), prim
    # the block keeps the router's result (`ops/recompute.py`): what it
    # computes again of the routing is the scoring, from the kept
    # logits; no sort, no top_k, no product
    assert not under(names, "moe.sort", "recomputation")
    again = under(names, "moe.route", "recomputation")
    assert again
    assert not [n for n in again
                if n.endswith(("/dot_general", "/top_k", "/sort"))]
    # integers carry no gradient: the sort has no transpose
    assert not under(names, "moe.sort", "backward")


@pytest.mark.parametrize("scope", INNER)
def test_inner_scope_in_forward_recomputation_and_transpose(stacks, scope):
    names, _ = stacks
    for ph in ("forward", "recomputation", "backward"):
        got = [n for n in under(names, scope, ph) if n.startswith("jit(")]
        assert got, (scope, ph)
        for n in got:
            # inside a pass of a loop of moe.experts (the forward's, or
            # the backward's for the pass computed again and pulled
            # back), under the prototxt layer's name and under no second
            # inner scope
            assert re.search(r"[/(]L1\.moe[/)]+moe\.experts/while/body/"
                             r"[a-z(]*" + re.escape(scope) + r"\)*/", n), n
            assert sum(wrapped(n, s) is not None for s in INNER) == 1, n
            assert ("transpose(" in n) == (ph != "forward"), n


def test_each_kind_of_work_sits_under_its_own_scope(stacks):
    names, _ = stacks
    experts = [n for n in names if "/moe.experts/" in n]

    def scopes_of(prim, ph):
        return {s for n in experts if n.endswith("/" + prim)
                for s in INNER if phase(n, s) == ph}

    for ph in ("forward", "recomputation"):
        # the slice of the sorted order and the row gather; the gates'
        # gather is the combine's
        assert scopes_of("dynamic_slice", ph) >= {"moe.gather"}
        assert scopes_of("gather", ph) == {"moe.gather", "moe.combine"}
        # the grouped products (on the CPU, their expansion)
        assert scopes_of("dot_general", ph) == {"moe.products"}
    assert scopes_of("scatter-add", "forward") == {"moe.combine"}
    # a pass computed again adds into no running sum: none is needed
    assert scopes_of("scatter-add", "recomputation") == set()
    # transposed: the row gather becomes a scatter-add into dx (and the
    # gates' into dgates), the scatter-add a gather of d(acc)'s rows,
    # the products stay products
    assert scopes_of("scatter-add", "backward") == {"moe.gather",
                                                    "moe.combine"}
    assert scopes_of("gather", "backward") == {"moe.combine"}
    assert scopes_of("dot_general", "backward") == {"moe.products"}
    # the loops and the sums into the backward's accumulators carry
    # `moe.experts` and no inner scope (what the unscoped metric reads);
    # no conditional is left, a pass that does not run is not visited
    own = [n for n in experts
           if all(wrapped(n, s) is None for s in INNER)]
    for ph, stack in (("forward", "jvp(L1.moe)"),
                      ("backward", "transpose(")):
        assert any(stack in n and n.endswith("/moe.experts/while")
                   for n in own), ph
    assert any("transpose(" in n and n.endswith("/while/body/add")
               for n in own)
    assert not any(n.endswith("/cond") or "/branch_" in n for n in experts)


# --------------------------------------------------------------- values

# the values of the tree before PR 38 (commit ed92fd8, this machine's CPU
# backend, float32 at the default precision), as `float.hex`: a named
# scope is metadata and may change no bit of them, and PR 39's backward
# loop leaves out only sums of zeros
PARENT = {
    "y": "0x1.e5212c0000000p+7", "dx": "-0x1.dc05ee0000000p+6",
    "dW_gate": "-0x1.4baea00000000p+8", "dW_down": "0x1.b7b4360000000p+6",
    "drouter": "-0x1.f3e7400000000p+2", "y_abs": "0x1.067fb00000000p+12",
    "dx_abs": "0x1.f1c5f80000000p+11",
}


def values():
    lp = layer_param()
    params = blobs(lp)
    x = jax.random.normal(jax.random.key(11), (N, D), jnp.float32)

    def f(x, params):
        y = jax.checkpoint(lambda a, p: apply(lp, p, a)[0])(x, params)
        return jnp.sum(jnp.sin(y)), y

    (_, y), (dx, dp) = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True))(x, params)
    names = [n for n, _, _ in L._moe_params(lp, [(N, D)])]
    dp = dict(zip(names, dp))
    out = {"y": y.sum(), "dx": dx.sum(), "dW_gate": dp["W_gate"].sum(),
           "dW_down": dp["W_down"].sum(), "drouter": dp["router"].sum(),
           "y_abs": jnp.abs(y).sum(), "dx_abs": jnp.abs(dx).sum()}
    return {k: float(v).hex() for k, v in out.items()}


def test_outputs_and_gradients_are_the_parents_to_the_last_bit(tile8):
    assert values() == PARENT


# ------------------------------------------------- the passes that run

def every_pass(xf, gates, w_in, w_out, order, starts, ends, total, rows,
               n_pass, k, gated, prec, kernels=None):
    """The reference for `_moe_passes`: a scan over all `n_pass` passes,
    those that hold no held row too, differentiated by JAX (PR 38's
    loop without its conditional)."""
    def one(acc, lo):
        return L._moe_pass(acc, lo, xf, gates, w_in, w_out, order, starts,
                           ends, total, rows, k, gated, prec, kernels), None

    return jax.lax.scan(one, jnp.zeros_like(xf),
                        jnp.arange(n_pass, dtype=jnp.int32) * rows)[0]


# the selection bias that makes every token choose these experts (0 and
# 1 are held): the passes of 32 rows that the 96 assignments then fill
FILLS = {0: (6, 7), 1: (), 2: (0,), 3: (0, 1)}


def steered(fill, gated):
    lp = layer_param(gated=gated, bias=True)
    names = [n for n, _, _ in L._moe_params(lp, [(N, D)])]
    params = blobs(lp)
    params[names.index("bias")] = jnp.zeros((E,), jnp.float32).at[
        jnp.asarray(FILLS[fill], jnp.int32)].set(10.0)
    return lp, names, params


def value_and_gradients(lp, params, x, remat):
    def f(x, params):
        def layer(a, p):
            return tuple(apply(lp, p, a))

        y, stats = (jax.checkpoint(layer) if remat else layer)(x, params)
        return jnp.sum(jnp.sin(y)), (y, stats)

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
        x, params)


@pytest.mark.parametrize("remat", [False, True],
                         ids=["plain", "recompute_block"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu"])
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_gradients_equal_those_of_every_pass_run(tile8, monkeypatch, fill,
                                                 gated, remat):
    """x, the router and every expert weight: the loops over the passes
    that run give what `n_pass` passes run unconditionally give, bit for
    bit (a sum of zeros left out may turn a -0.0 into 0.0, no more)."""
    lp, names, params = steered(fill, gated)
    x = jax.random.normal(jax.random.key(11), (N, D), jnp.float32)
    (_, (y, stats)), (dx, dp) = value_and_gradients(lp, params, x, remat)
    held = round(float(stats[1]) * K * N)
    assert -(-held // 32) == fill and float(stats[2]) == 0.0
    monkeypatch.setattr(L, "_moe_passes", every_pass)
    (_, (y_ref, _)), (dx_ref, dp_ref) = value_and_gradients(lp, params, x,
                                                            remat)
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(dx, dx_ref)
    for name, got, ref in zip(names, dp, dp_ref):
        assert np.isfinite(got).all(), name
        np.testing.assert_array_equal(got, ref, err_msg=name)
        if name.startswith("W") or name == "router":
            # no held assignment, no pass: the accumulators' zeros
            assert bool(jnp.any(got != 0)) == (fill > 0), name


def computations(text):
    """{name: its instructions' lines} of an HLO module's text, and
    {name: the computations it calls}."""
    lines, calls, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(1)
            lines[name], calls[name] = [], set()
        elif line.strip() == "}":
            name = None
        elif name and line.strip():
            lines[name].append(line)
            calls[name].update(re.findall(
                r"(?:calls|body|condition|to_apply)=%([\w.\-]+)", line))
    return lines, calls


def reachable(calls, name):
    seen, todo = set(), [name]
    while todo:
        at = todo.pop()
        if at not in seen:
            seen.add(at)
            todo.extend(calls[at])
    return seen


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu"])
def test_weight_gradients_are_touched_in_the_backward_loop_alone(tile8,
                                                                 gated):
    """The optimised HLO of a gradient: outside the bodies of the two
    loops of `moe.experts` the only work on an array of an expert
    weight's shape is the zero fill of the backward loop's accumulators,
    one a weight.  (What a skipped pass used to add, a zero cotangent a
    weight and its sum into the carry, is in no computation: a pass
    that does not run is an iteration the loop does not make.)"""
    lp = layer_param(gated=gated)
    params = blobs(lp)
    x = jax.random.normal(jax.random.key(11), (N, D), jnp.float32)

    def f(x, params):
        y = jax.checkpoint(lambda a, p: apply(lp, p, a)[0])(x, params)
        return jnp.sum(jnp.sin(y))

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(
        x, params).compile().as_text()
    lines, calls = computations(text)
    loops = [(line, re.search(r"body=%([\w.\-]+)", line).group(1))
             for ls in lines.values() for line in ls
             if re.search(r" while\(.*moe\.experts\)?/while\"", line)]
    assert len(loops) == 2          # forward and backward, no third
    assert sum("transpose(" in line for line, _ in loops) == 1
    inside = set().union(*(reachable(calls, body) for _, body in loops))

    weight = re.compile(rf"%(\S+) = f32\[{HELD},(?:{D},{H}|{H},{D})\]\S* "
                        r"([\w\-]+)\((.*?)\)(?:.* calls=%([\w.\-]+))?")
    fused = {c for ls in lines.values() for line in ls
             for c in re.findall(r" calls=%([\w.\-]+)", line)}
    fills, work = {}, []            # a zero fill -> its computation
    for comp in sorted(set(lines) - inside):
        for line in lines[comp]:
            m = weight.search(line)
            if not m or m.group(2) in ("parameter", "get-tuple-element"):
                continue
            name, op, args, called = m.groups()
            if (op == "broadcast" and "," not in args) \
                    or (op == "copy" and args.lstrip("%") in fills) \
                    or (op == "fusion" and all(      # a broadcast, wrapped
                        re.search(r" (parameter|broadcast|constant)\(", ln)
                        for ln in lines[called])):
                fills[name] = comp
            else:
                work.append(line.strip()[:160])
    assert not work, work
    # at most one fill a weight
    assert 1 <= sum(c not in fused for c in fills.values()) \
        <= (3 if gated else 2), fills


# ----------------------------------------------------------------- plan

CELLS = [
    # (zoo net, N, the plan's key, layers, rows, passes, carry bytes)
    ("kanana2", 8192, "8192x2048 top 6 of 128, 16 held x 768 gated, "
     "shared 1536", 5, 8192, 6, 16 * 3 * 2048 * 768 * 4),
    ("lfm2", 8192, "8192x2048 top 4 of 64, 8 held x 1536 gated, shared 0",
     6, 5632, 6, 8 * 3 * 2048 * 1536 * 4),
    ("qwen3_next", 8192, "8192x2048 top 10 of 512, 32 held x 512 gated, "
     "shared 512", 4, 7168, 12, 32 * 3 * 2048 * 512 * 4),
]


@pytest.mark.parametrize("name,n,key,layers,rows,passes,carry", CELLS,
                         ids=[c[0] for c in CELLS])
def test_moe_plans_of_the_language_model_cells(monkeypatch, name, n, key,
                                               layers, rows, passes,
                                               carry):
    """Every expert layer of the cell's net as `zoo` writes it, traced
    at the cell's tokens a step (shapes only: nothing runs)."""
    route.forget("moe")
    npm = getattr(zoo, name)()
    moe = [lp for lp in npm.layer if lp.type == "MixtureOfExperts"]
    assert len(moe) == layers
    for lp in moe:
        mp = lp.moe_param
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32)
                  for _, s, _ in L._moe_params(lp, [(n, 2048)])]
        jax.eval_shape(lambda p, x, lp=lp: apply(lp, p, x), shapes,
                       jax.ShapeDtypeStruct((n, 2048), jnp.float32))
    k, e = int(mp.top_k), int(mp.num_experts)
    held, hidden = int(mp.experts_held), int(mp.hidden_dim)
    assert L.moe_plans() == {key: {
        "layers": [lp.name for lp in moe], "assignments": k * n,
        "rows": rows, "passes": passes,
        "passes_even_router": 1, "row_tile": 512,
        "row_flops": 2 * 2048 * hidden * 3, "carry_bytes": carry,
        # off the TPU and out of interpret mode: `lax.ragged_dot`
        "form": "xla", "calls": 0}}
    assert passes == -(-k * n // rows)
    assert rows >= k * n * held / e           # an even router fits one


def test_plan_of_a_plain_layer_and_a_copy_that_cannot_be_edited(tile8):
    """ReLU experts (two products), no shared expert, every expert held:
    all k N rows are one pass; `moe_plans()` hands out copies."""
    lp = layer_param(gated=False, shared=0, held=0)
    x = jnp.ones((N, D), jnp.float32)
    apply(lp, blobs(lp), x)
    apply(lp, blobs(lp), x)                     # the same layer again
    key = f"{N}x{D} top {K} of {E}, {E} held x {H}, shared 0"
    assert L.moe_plans() == {key: {
        "layers": ["L1.moe"], "assignments": K * N, "rows": K * N,
        "passes": 1, "passes_even_router": 1, "row_tile": 8,
        "row_flops": 2 * D * H * 2, "carry_bytes": E * 2 * D * H * 4,
        "form": "xla", "calls": 0}}
    L.moe_plans()[key]["layers"].append("x")
    assert L.moe_plans()[key]["layers"] == ["L1.moe"]


def test_kernel_form_is_in_the_plan_and_under_the_scopes(monkeypatch):
    """Two expert layers of one shape on the grouped-product kernels
    (interpret mode): `route.plans()["moe"]` holds the form, the tiles
    and the call sites a layer's step holds beside the keys the
    benchmark's reader of `moe.products_mfu_pct.train` uses; and every
    `pallas_call` of the traced gradient, the second layer's too (whose
    calls are the first's, kept a shape), carries its own layer's name,
    `moe.experts` and inside it `moe.products`: what
    `moe.products_device_ms.train` and `moe.experts_unscoped_device_ms.
    train` read the kernels' time by."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    route.forget("moe")
    net = Net(zoo.kanana2(
        vocab=64, hidden=D, heads=2, qk_nope=8, qk_rope=4, v_head=6,
        kv_lora_rank=16, dense_width=48, expert_width=48, experts=E,
        top_k=K, shared_experts=0, expert_layers=2, seq=32, batch=2,
        experts_held=4, recompute=True))
    params = jax.eval_shape(net.init, jax.random.key(0))
    ins = {k: jax.ShapeDtypeStruct((32, 2), jnp.float32)
           for k in ("input_ids", "target_ids")}
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, i: net.loss(
        p, i, train=True, rng=jax.random.key(1))[0]))(params, ins)

    def calls(jaxpr, outer=""):
        # (an equation's name stack starts at the jaxpr that holds it)
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call" \
                    and eqn.params["name"].startswith("cos_gmm_"):
                yield eqn.params["name"], stack
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from calls(sub, stack)

    found = list(calls(jaxpr.jaxpr))
    plan, = route.plans()["moe"].values()
    assert plan["form"] == "kernel" and plan["calls"] == 12
    assert set(plan["tiles"]) == {"into", "out"}
    assert plan["tiles"]["into"] == {"rows": 128, "lanes": 48,
                                     "lanes_t": D, "grad": (D, 48)}
    assert {"layers", "assignments", "row_flops"} <= set(
        L.moe_plans()[next(iter(L.moe_plans()))])
    assert plan["layers"] == ["L1.moe", "L2.moe"]
    for layer in plan["layers"]:
        mine = [(name, stack) for name, stack in found if layer in stack]
        # the forward loop is not run again in the block's backward
        assert len(mine) == plan["calls"], (layer, mine)
        for name, stack in mine:
            assert re.search(r"moe\.experts.*moe\.products", stack), stack
            for scope in ("moe.gather", "moe.combine", "moe.route"):
                assert scope not in stack, stack
    assert len(found) == 2 * plan["calls"]


def test_capacity_dispatch_writes_no_plan(tile8):
    lp = LayerParameter.from_text(f'''
      name: "moe" type: "MixtureOfExperts" bottom: "x" top: "y"
      moe_param {{ num_experts: {E} hidden_dim: {H} top_k: {K} }}''')
    keys = jax.random.split(jax.random.key(0), 3)
    params = [jax.random.normal(k, s) for k, (_, s, _) in
              zip(keys, L._moe_params(lp, [(N, D)]))]
    apply(lp, params, jnp.ones((N, D), jnp.float32))
    assert L.moe_plans() == {}


# ------------------------------------------------------------- info.moe

EXPERTS = '''
layer { name: "experts" type: "MixtureOfExperts" bottom: "ip1" top: "ip1"
  %s
  moe_param { num_experts: %d hidden_dim: 8 top_k: %d dispatch: "dropless"
    scoring: "softmax" gated: true experts_held: %d } }'''


def train_job(tmp_path, monkeypatch, experts, steps=2, observer=None):
    """A tiny -train job of `steps` steps over 16 x 32 features, its
    net with the expert layer `experts` (prototxt, may be empty) between
    two inner products.  -> the metrics' summary after the last step."""
    from caffeonspark_tpu.caffe_on_spark import CaffeOnSpark
    from caffeonspark_tpu.config import Config
    from caffeonspark_tpu.data import LmdbWriter, get_source
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.processor import CaffeProcessor
    from caffeonspark_tpu.proto.caffe import Datum

    route.forget("moe")
    monkeypatch.setenv("COS_TRANSFORM_THREADS", "0")
    imgs, labels = make_images(32, seed=6)
    LmdbWriter(str(tmp_path / "lmdb")).write([
        (b"%06d" % i,
         Datum(channels=1, height=28, width=28,
               data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
               label=int(labels[i])).to_binary()) for i in range(32)])
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: 16
    channels: 1 height: 28 width: 28 }} }}
layer {{ name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
  inner_product_param {{ num_output: 32
    weight_filler {{ type: "xavier" }} }} }}''' + experts + '''
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
  inner_product_param { num_output: 10
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2"
  bottom: "label" top: "loss" }''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(f'net: "{net}"\nbase_lr: 0.01\n'
                      f'lr_policy: "fixed"\nmax_iter: {steps}\n'
                      'snapshot_prefix: "x"\nrandom_seed: 2\n'
                      'snapshot_after_train: false\n')
    conf = Config(["-conf", str(solver), "-train", "-output",
                   str(tmp_path)])
    proc = CaffeProcessor.instance(conf)
    proc.step_observer = observer
    CaffeOnSpark().train(
        get_source(conf.train_data_layer(), phase_train=True), conf)
    assert CaffeProcessor.instance() is proc
    summary = proc.metrics.summary()
    proc.stop()
    return summary


@pytest.mark.parametrize("experts", [True, False],
                         ids=["expert_layer", "no_expert_layer"])
def test_train_job_reports_the_pass_plan_as_info_moe(tmp_path, monkeypatch,
                                                     caplog, experts):
    """After its first step the summary that `/metrics`, `metrics.json`
    and the shutdown line print holds `info.moe` if the net has a
    dropless expert layer, and no such key if it has none."""
    import logging

    with caplog.at_level(logging.INFO, "caffeonspark_tpu"):
        summary = train_job(tmp_path, monkeypatch,
                            EXPERTS % ("", 4, 2, 2) if experts else "")
    info = summary.get("info", {})
    said = [r for r in caplog.records
            if "moe as lowered" in r.getMessage()]
    # the layer returns no stats: nothing to say of its passes
    assert "experts" not in summary
    if not experts:
        assert "moe" not in info and not said
        return
    assert info["moe"] == {"16x32 top 2 of 4, 2 held x 8 gated, shared 0": {
        "layers": ["experts"], "assignments": 32, "rows": 32, "passes": 1,
        "passes_even_router": 1, "row_tile": 512,
        "row_flops": 2 * 32 * 8 * 3, "carry_bytes": 2 * 3 * 32 * 8 * 4,
        "form": "xla", "calls": 0}}
    assert len(said) == 1


def test_train_job_counts_the_passes_that_ran(tmp_path, monkeypatch, tile8):
    """`experts.passes_run` of a job whose expert layer returns its
    stats: a layer's mean and max over the steps of ceil(held
    assignments / the rows a pass takes), from the stats the steps
    returned (here 64 assignments in 3 passes of 24 rows; the router
    collapses as it trains, the passes that run change)."""
    seen = []
    summary = train_job(
        tmp_path, monkeypatch, EXPERTS % ('top: "experts_stats"', 16, 4, 4),
        steps=5,
        observer=lambda it, n, batch, p, st, out: seen.append(
            np.asarray(out["experts_stats"])))
    plan, = summary["info"]["moe"].values()
    assert (plan["assignments"], plan["rows"], plan["passes"]) == (64, 24, 3)
    held = [round(float(s[1]) * 64) for s in seen]
    ran = [-(-h // 24) for h in held]
    assert len(ran) == 5 and 1 < len(set(ran))
    assert summary["experts"] == {
        "steps": 5, "held_share": pytest.approx(np.mean(held) / 64),
        "passes_run": {"experts": {"mean": pytest.approx(np.mean(ran)),
                                   "max": max(ran)}}}
