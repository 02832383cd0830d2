"""`ops/route.py`: the one rule that says which form of an operator is
lowered, held against every operator that asks it (LRN, flash attention,
the convolution + SiLU stage, the gated delta rule, the selective scan,
the Mamba-2 scan, the expert layers' grouped products):
what a traced program holds (a `pallas_call`, a `shard_map` around it, or
neither) and, where the kind keeps one, what `route.plans()` says.  And
the two properties the module exists for: nothing else under `ops/`
reads the three environment names, and `parallel/sp.py` and
`utils/flops.py` know no layer type by name."""

import ast
import os
import re

import jax
import jax.numpy as jnp
import pytest

from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import route
from caffeonspark_tpu.proto import LayerParameter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _lrn(tiles):
    lp = LayerParameter.from_text(
        'name: "norm" type: "LRN" lrn_param { local_size: 5 }')
    return (lambda x: L.get_op("LRN").apply(L.Ctx(), lp, [], [x])[0],
            [f32(2, 8, 4, 4)], None)


def _flash(tiles):
    t = 128 if tiles else 96
    return (lambda q, k, v: L._attention_dispatch(q, k, v, causal=True),
            [f32(2, 2, t, 32)] * 3, ("flash", f"4x{t}x32/32 float32 g1 causal"))


def _taps(tiles):
    c = 128 if tiles else 96
    return (lambda z, w: L.causal_taps_silu(z, w, site="L0.op"),
            [f32(16, 1, c), f32(c, 4)],
            ("taps", f"1x16 {c} of {c} channels 4 taps float32"))


def _gdn(tiles):
    dk = 128 if tiles else 64
    return (lambda *a: L.gated_delta_rule(*a, 64),
            [f32(1, 1, 70, dk), f32(1, 1, 70, dk), f32(1, 1, 2, 70, 128),
             f32(1, 1, 2, 70), f32(1, 1, 2, 70)],
            ("gdn", f"1x70 1/2 heads {dk}/128"))


def _ssm(tiles):
    ch = 128 if tiles else 96
    return (lambda *a: L.selective_scan(*a, 16),
            [f32(1, 64, ch), f32(1, 64, ch), f32(ch, 16), f32(1, 64, 16),
             f32(1, 64, 16)], ("ssm", f"1x64 {ch} channels 16 states"))


def _ssd(tiles):
    p = 128 if tiles else 96
    return (lambda *a: L.ssd_scan(*a, 1, 128, 128),
            [f32(140, 1, 2 * p + 256), f32(140, 1, 2), f32(2), f32(2)],
            ("ssd", f"1x140 2 heads of {p} over 1 groups of 128 states"))


def _moe_layer(hidden, ctx=None):
    """A dropless expert layer over (128, 32): passes of 256 rows (two
    row tiles of the kernels), 4 of 8 experts held."""
    lp = LayerParameter.from_text(f'''
      name: "L0.moe" type: "MixtureOfExperts" bottom: "x" top: "y"
      moe_param {{ num_experts: 8 hidden_dim: {hidden} top_k: 2
        dispatch: "dropless" scoring: "sigmoid" experts_held: 4 }}''')
    return (lambda x, *p: L.get_op("MixtureOfExperts").apply(
                ctx or L.Ctx(train=True), lp, list(p), [x])[0],
            [f32(128, 32)] + [f32(*s) for _, s, _ in
                              L._moe_params(lp, [(128, 32)])],
            ("moe", f"128x32 top 2 of 8, 4 held x {hidden}, shared 0"))


def _moe(tiles):
    return _moe_layer(48 if tiles else 40)     # whole bfloat16 sublanes


KINDS = {"lrn": _lrn, "flash": _flash, "taps": _taps, "gdn": _gdn,
         "ssm": _ssm, "ssd": _ssd, "moe": _moe}
# the word a kind's plan holds the form under
FORM = {"taps": "form", "gdn": "rule", "ssm": "form", "ssd": "form",
        "moe": "form"}
# parallel over the batch (and heads): under a mesh the kernel stays, on
# each device's block
OVER_SHARDS = ("lrn", "flash")


def primitives(jaxpr, inside=()):
    """[(primitive, the primitives it is nested in)] of a jaxpr and of
    every jaxpr in its equations' parameters."""
    out = []
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, inside))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += primitives(sub, inside + (eqn.primitive.name,))
    return out


@pytest.mark.parametrize("kind,where", [
    (k, w) for k in KINDS for w in ("cpu", "interpret", "mesh", "untiled")
    if (k, w) != ("lrn", "untiled")])    # LRN's XLA form is 4-D as well
def test_which_form_is_lowered(monkeypatch, kind, where):
    """(cpu) no TPU, no interpret mode: the XLA form; (interpret)
    COS_FLASH_INTERPRET=1 at a shape that tiles: the kernel; (mesh) the
    same under a mesh of several devices: the scans give way to XLA,
    LRN and attention keep the kernel inside a `shard_map`; (untiled)
    interpret mode at a shape that does not tile: the XLA form."""
    from caffeonspark_tpu.parallel.mesh import build_mesh
    for name in ("COS_FLASH_INTERPRET", "COS_DISABLE_FLASH",
                 "COS_DISABLE_PALLAS"):
        monkeypatch.delenv(name, raising=False)
    if where != "cpu":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    fn, args, plan = KINDS[kind](where != "untiled")
    route.forget()
    if where == "mesh":
        with route.flash_mesh(build_mesh(dp=2, devices=jax.devices()[:2])):
            prims = primitives(jax.make_jaxpr(fn)(*args).jaxpr)
    else:
        prims = primitives(jax.make_jaxpr(fn)(*args).jaxpr)
    calls = [inside for name, inside in prims if name == "pallas_call"]
    kernel = where == "interpret" or (where == "mesh"
                                      and kind in OVER_SHARDS)
    assert bool(calls) is kernel
    if where == "mesh" and kernel:
        assert all("shard_map" in inside for inside in calls)
    else:
        assert "shard_map" not in [name for name, _ in prims]
    if kind in FORM:
        assert route.plans()[plan[0]][plan[1]][FORM[kind]] == (
            "kernel" if kernel else "xla")
    elif kind == "flash":       # a plan is a kernel call's, by its shape
        assert list(route.plans().get("flash", {})) == (
            ["2x128x32/32 float32 g1 causal" if where == "mesh"
             else plan[1]] if kernel else [])
    else:                       # LRN keeps no record: no `info.lrn`
        assert route.plans() == {}


def test_expert_products_pinned_to_float32_keep_the_xla_form(monkeypatch):
    """An autotune plan that holds a layer at float32 (`ctx.precision()`
    HIGHEST) keeps `lax.ragged_dot`: the kernels are one bfloat16 pass
    and no more; the plan says which form, the tiles and the call sites
    a layer's step holds."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    for variant, form, calls in (({"dtype": "float32"}, "xla", 0),
                                 (None, "kernel", 8)):
        fn, args, (kind, key) = _moe_layer(
            48, L.Ctx(train=True, variant=variant))
        route.forget()
        prims = primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        assert ("pallas_call" in [n for n, _ in prims]) is (form == "kernel")
        assert ("ragged_dot_general" in [n for n, _ in prims]) is (
            form == "xla")
        plan = route.plans()[kind][key]
        assert (plan["form"], plan["calls"]) == (form, calls)
        assert ("tiles" in plan) is (form == "kernel")
        if form == "kernel":
            assert plan["tiles"]["into"] == {
                "rows": 128, "lanes": 48, "lanes_t": 32, "grad": (32, 48)}
            assert plan["tiles"]["out"] == {
                "rows": 128, "lanes": 32, "lanes_t": 48, "grad": (48, 32)}


def test_the_attention_vetoes_are_attentions_alone(monkeypatch):
    """`suppress_flash` and COS_DISABLE_FLASH send attention to its
    einsum form and no other operator anywhere."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    assert route.kernel(True, attention=True) == route.Route(True, None)
    with route.suppress_flash():
        assert route.kernel(True, attention=True) is None
        assert route.kernel(True) == route.Route(True, None)
    monkeypatch.setenv("COS_DISABLE_FLASH", "1")
    assert route.kernel(True, attention=True) is None
    assert route.kernel(True) == route.Route(True, None)
    assert route.kernel(True, jax.ShapeDtypeStruct((2,), jnp.bfloat16)) \
        is None


def _environment_reads(path):
    """The COS_* names a module reads from the environment, by `ast`."""
    tree = ast.parse(open(path).read())
    return {n.value for n in ast.walk(tree)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)
            and re.fullmatch(r"COS_(FLASH_INTERPRET|DISABLE_FLASH|"
                             r"DISABLE_PALLAS)", n.value)}


def test_one_module_reads_the_three_names_and_two_know_no_layer_type():
    ops = os.path.join(ROOT, "caffeonspark_tpu", "ops")
    readers = {f for f in sorted(os.listdir(ops)) if f.endswith(".py")
               and _environment_reads(os.path.join(ops, f))}
    assert readers == {"route.py"}
    language = [t for t in L.supported_types()
                if L.get_op(t).time_sharding is not None]
    assert "GatedDeltaNet" in language and "MixtureOfExperts" in language
    for path in ("parallel/sp.py", "utils/flops.py"):
        tree = ast.parse(open(os.path.join(
            ROOT, "caffeonspark_tpu", path)).read())
        named = {n.value for n in ast.walk(tree)
                 if isinstance(n, ast.Constant) and n.value in language}
        assert not named, (path, named)
