"""What a `recompute_block` keeps between its forward and its backward.

`Net.apply` runs a block under `jax.checkpoint` with the policy of
`ops/recompute.py`: the block is computed again in the backward pass,
all but the values its layers name as they make them: a Mosaic forward
kernel's outputs that its own backward reads (flash attention, the gated
delta rule) and the router's result.  Held here, on the CPU with the
kernels in interpret mode, a layer type a case: the recomputation holds
no forward kernel, `top_k`, sort or router product and the saved
residuals are the list's names; values and gradients are a plain
`jax.checkpoint`'s to the last bit; outside a block the names are
identities; `info.recompute` counts the bytes the shapes give."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu import net as net_mod
from caffeonspark_tpu.proto import parse_net_prototxt
from caffeonspark_tpu.net import Net
from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import pallas_kernels as pk
from caffeonspark_tpu.ops import recompute as R

T, B, D = 128, 1, 32
N = T * B
E, K, HELD, HID = 8, 2, 4, 12

GQA = ('type: "GroupedQueryAttention" attention_param { num_heads: 4 '
       'num_kv_heads: 2 head_dim: 8 causal: true rotary: true %s }')
MOE = ('type: "MixtureOfExperts" top: "moe.stats" top: "moe.rows" '
       'moe_param { num_experts: %d hidden_dim: %d top_k: %d '
       'dispatch: "dropless" scoring: "%%s" gated: true '
       'experts_held: %d %%s }' % (E, HID, K, HELD))


def flash_bytes(heads, dv):
    return {"flash.out": B * heads * T * dv * 4,
            "flash.lse": B * heads * T * 4}


def gdn_bytes(hk=1, r=2, dk=128, dv=128, c=64):
    steps, group_steps, chunks = pk.gdn_rule_steps(-(-T // c))
    groups = chunks // (steps * group_steps)
    return {"gdn.o": B * hk * r * chunks * c * dv * 4,
            "gdn.edges": B * hk * groups * dk * r * dv * 4}


def moe_bytes():
    rows = L._moe_chunk_rows(N, K, HELD, E)
    return {"moe.logits": N * E * 4, "moe.topi": N * K * 4,
            "moe.gates": N * K * 4,
            "moe.order": -(-(K * N) // rows) * rows * 4,
            "moe.starts": HELD * 4, "moe.ends": HELD * 4, "moe.total": 4}


# case -> (the layer under the block, the bytes its block keeps,
#          what the recomputation must not hold: kernels, primitives)
CASES = {
    "gqa": (GQA % "", lambda: flash_bytes(4, 8), ("cos_flash_fwd",)),
    "gqa_window": (GQA % "window: 32", lambda: flash_bytes(4, 8),
                   ("cos_flash_fwd",)),
    "latent": ('type: "LatentAttention" attention_param { num_heads: 2 '
               'causal: true qk_nope_head_dim: 8 qk_rope_head_dim: 4 '
               'v_head_dim: 8 kv_lora_rank: 16 }',
               lambda: flash_bytes(2, 8), ("cos_flash_fwd",)),
    "gated": (GQA % "qk_norm: true rotary_dim: 4 output_gate: true",
              lambda: flash_bytes(4, 8), ("cos_flash_fwd",)),
    "gdn": ('type: "GatedDeltaNet" gated_delta_net_param { num_k_heads: 1 '
            'num_v_heads: 2 head_k_dim: 128 head_v_dim: 128 conv_taps: 4 '
            'chunk: 64 }', gdn_bytes, ("cos_gdn_fwd",)),
    "moe_sigmoid": (MOE % ("sigmoid", "selection_bias: true "
                           "routed_scaling_factor: 2.5 "
                           "shared_hidden_dim: 12"),
                    moe_bytes, ("top_k", "sort", "router")),
    "moe_softmax": (MOE % ("softmax", "norm_epsilon: 1e-6"),
                    moe_bytes, ("top_k", "sort", "router")),
    # a block whose layers have nothing on the list
    "dense": ('type: "InnerProduct" inner_product_param { num_output: %d '
              'axis: 2 weight_filler { type: "xavier" } }' % D,
              dict, ()),
}
KEEPING = [c for c in CASES if c != "dense"]


def build(cases, tag=True, **net_kw):
    """A net of one pre-norm residual block a case (norm, the case's
    layer, the residual sum), each block one `recompute_block` named
    after its case if `tag`, under a Euclidean loss."""
    text = ('layer { name: "data" type: "Input" top: "x" top: "want" '
            'input_param { shape { dim: %d dim: %d dim: %d } '
            'shape { dim: %d dim: %d dim: %d } } }' % ((T, B, D) * 2))
    h = "x"
    for c in cases:
        blk = f'recompute_block: "{c}"' if tag else ""
        body = CASES[c][0]
        text += f'''
layer {{ name: "{c}.norm" type: "RMSNorm" bottom: "{h}" top: "{c}.n" {blk} }}
layer {{ name: "{c}.op" bottom: "{c}.n" top: "{c}.a" {blk} {body} }}
layer {{ name: "{c}.res" type: "Eltwise" bottom: "{h}" bottom: "{c}.a"
  top: "{c}.out" {blk} }}'''
        h = f"{c}.out"
    text += ('\nlayer { name: "loss" type: "EuclideanLoss" bottom: "%s" '
             'bottom: "want" top: "loss" }' % h)
    return Net(parse_net_prototxt(text), **net_kw)


@pytest.fixture
def interpret(monkeypatch):
    """The kernels' route, in interpret mode; fresh counters."""
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    monkeypatch.setattr(R, "_BLOCKS", {})


def plain(monkeypatch):
    """The parent's block: `jax.checkpoint` with no policy."""
    monkeypatch.setattr(net_mod, "BLOCK_POLICY", None)


def problem(net, seed=0):
    """-> (loss(params, x) with the tops as aux, params, x)."""
    kp, kx, kw = jax.random.split(jax.random.key(seed), 3)
    params = net.init(kp)
    x = jax.random.normal(kx, (T, B, D), jnp.float32)
    want = jax.random.normal(kw, (T, B, D), jnp.float32)

    def loss(p, x):
        value, (tops, _) = net.loss(p, {"x": x, "want": want}, train=True,
                                    rng=jax.random.key(1))
        return value, tops

    return loss, params, x


def eqns(jaxpr):
    """Every equation under `jaxpr`, sub-jaxprs included (a kernel's
    body is none of them: `pallas_call` is a leaf)."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from eqns(sub)


def recomputations(jaxpr):
    """The `jax.checkpoint`s at the top of a gradient's program, in
    order: those a layer wraps its own parts in, as the forward pass
    runs them, then the blocks' in the backward pass, last block first
    (the layer's own are inside them there)."""
    return [e for e in jaxpr.eqns
            if e.primitive.name in ("checkpoint", "remat2")]


def held(body, what):
    """Whether a recomputation's body holds `what`: a kernel by name, a
    primitive, or the router's (N, E) product."""
    for e in eqns(body):
        name = e.primitive.name
        if what == "router":
            if name == "dot_general" and e.outvars[0].aval.shape == (N, E):
                return True
        elif name == what or (name == "pallas_call"
                              and what in e.params["name"]):
            return True
    return False


def handed_over(jaxpr):
    """What the one block's checkpoint reads in the backward pass that
    the forward pass made -> ({name: bytes} of the values that are a named value:
    as named, behind the full-precision `reduce_precision` jax puts on
    a float residual, or handed through a jitted function that returns
    its argument; the shapes of the others).  This is what
    `jax.ad_checkpoint.print_saved_residuals` lists, less the arguments,
    with each value traced to its name."""
    made = {v: e for e in jaxpr.eqns for v in e.outvars}
    remat = recomputations(jaxpr)[-1]
    named, other = {}, []
    for v in remat.invars:
        if v not in made:
            continue        # an argument: a blob, the block's input
        src = v
        while src in made:
            e = made[src]
            if e.primitive.name == "reduce_precision":
                src = e.invars[0]
                continue
            if e.primitive.name in ("jit", "pjit"):
                inner = e.params["jaxpr"].jaxpr
                out = inner.outvars[e.outvars.index(src)]
                if out in inner.invars:
                    src = e.invars[inner.invars.index(out)]
                    continue
            break
        e = made.get(src)
        if e is not None and e.primitive.name == "name":
            assert e.params["name"] not in named
            named[e.params["name"]] = v.aval.size * v.aval.dtype.itemsize
        else:
            other.append(v.aval.shape)
    return named, other


# ------------------------------------------ (a) what is computed again

@pytest.mark.parametrize("case", KEEPING)
def test_recomputation_holds_no_kept_forward(interpret, monkeypatch, case):
    """In a gradient through one block the checkpoint's body holds no
    forward kernel call, no `top_k`, no sort and no router product (a
    plain `jax.checkpoint` holds each: the control), and what the
    forward pass hands it is exactly the case's names, with the bytes of
    `info.recompute`."""
    _, kept, gone = CASES[case]
    net = build([case])
    loss, params, x = problem(net)

    def program():      # traced anew at every call
        return jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x)[0],
                                       argnums=(0, 1)))(params, x).jaxpr

    jaxpr = program()
    body = recomputations(jaxpr)[-1].params["jaxpr"]
    assert not [w for w in gone if held(body, w)]
    # the backward's own work is there: this is the right body
    assert any(e.primitive.name in ("pallas_call", "while")
               for e in eqns(body))

    named, other = handed_over(jaxpr)
    assert named == kept() and set(named) <= set(R.KEPT)
    # and nothing else of the block's making: the cotangent alone
    assert other == [(T, B, D)]
    assert R.recompute_plans()["blocks"] == {case: named}

    plain(monkeypatch)
    body = recomputations(program())[-1].params["jaxpr"]
    assert [w for w in gone if held(body, w)] == list(gone)


# ----------------------------------------------------- (b) the values

@pytest.mark.parametrize("case", KEEPING)
def test_values_equal_a_plain_checkpoint_bit_for_bit(interpret, monkeypatch,
                                                     case):
    """Loss, every top and every gradient (the block's blobs and its
    input) of a block that keeps its names equal those of the same
    block under a plain `jax.checkpoint` to the last bit."""
    net = build([case])
    loss, params, x = problem(net, seed=3)

    def run():
        fn = jax.jit(jax.value_and_grad(
            lambda p, x: loss(p, x), argnums=(0, 1), has_aux=True))
        return fn(params, x), fn.lower(params, x).as_text()

    got, program = run()
    plain(monkeypatch)
    want, parent = run()
    assert program != parent
    assert jax.tree.structure(got) == jax.tree.structure(want)
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert any(np.abs(np.asarray(a)).max() > 0 for _, a in flat)
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(path))


# ------------------------------------- (c) a name outside a block

def shape_of(jaxpr):
    """The program but for its naming identities: every equation's
    primitive and result types, in order."""
    return [(e.primitive.name, tuple(str(v.aval) for v in e.outvars))
            for e in eqns(jaxpr) if e.primitive.name != "name"]


@pytest.mark.parametrize("where", ["no_block", "test_pass", "COS_REMAT"])
def test_names_are_identities_outside_a_block(interpret, monkeypatch, where):
    """A net without blocks, a TEST pass and COS_REMAT=1 (a checkpoint a
    layer, no policy) trace to the program they were before any value
    had a name, but for the `name` identities themselves; no checkpoint
    there has a policy, and nothing is counted as kept."""
    cases = ["gqa_window", "gdn", "moe_softmax"]
    if where == "COS_REMAT":
        monkeypatch.setenv("COS_REMAT", "1")
    net = build(cases, tag=where != "no_block")
    assert net.remat is (where == "COS_REMAT")
    assert bool(net.recompute_blocks) is (where != "no_block")
    kp, kx = jax.random.split(jax.random.key(0))
    params = net.init(kp)
    x = jax.random.normal(kx, (T, B, D), jnp.float32)

    def program():
        def loss(p, x):
            return net.loss(p, {"x": x, "want": x},
                            train=where != "test_pass",
                            rng=jax.random.key(1))[0]
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params,
                                                              x).jaxpr

    named = program()
    names = [e.params["name"] for e in eqns(named)
             if e.primitive.name == "name"]
    assert {"flash.out", "gdn.edges", "moe.order"} <= set(names)
    assert all(e.params["policy"] is None for e in eqns(named)
               if e.primitive.name in ("checkpoint", "remat2"))
    assert R.recompute_plans() == {}
    for mod in (L, pk):
        monkeypatch.setattr(mod, "keep", lambda x, name: x)
    bare = program()
    assert not [e for e in eqns(bare) if e.primitive.name == "name"]
    assert shape_of(named) == shape_of(bare)


def test_mxu_policy_does_not_save_a_name():
    """COS_REMAT=mxu keeps matmul and convolution results and nothing
    else: not what a layer names."""
    from jax._src.ad_checkpoint import name_p
    net = build(["dense"], remat="mxu")
    assert net.remat_policy(name_p, name="flash.out") is False
    assert net.remat_policy(jax.lax.dot_general_p) is True


def test_keep_refuses_a_name_off_the_list():
    with pytest.raises(KeyError, match="flash.scores"):
        R.keep(jnp.zeros(3), "flash.scores")


# ------------------------------------------------- (d) info.recompute

@pytest.mark.parametrize("case", list(CASES))
def test_info_recompute_counts_the_bytes_the_shapes_give(interpret, case):
    """`info.recompute` after a traced gradient: the block's names with
    the bytes reckoned from the shapes, their sum a step, and the block
    that keeps nothing by name; a second trace counts nothing twice."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.processor import CaffeProcessor

    net = build([case, "dense"] if case != "dense" else ["dense"])
    loss, params, x = problem(net)
    want = CASES[case][1]()
    for _ in range(2):
        jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x)[0]))(params, x)
        assert R.recompute_plans() == {
            "blocks": {case: want} if want else {},
            "bytes_a_step": sum(want.values()),
            "keep_nothing": ["dense"]}

    class Job:
        metrics = PipelineMetrics()

    CaffeProcessor._note_lowering_plans(Job)
    assert Job.metrics.summary()["info"]["recompute"] == R.recompute_plans()
