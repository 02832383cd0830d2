"""What a `recompute_block` keeps between its forward and its backward.

`Net.apply` runs a block under `jax.checkpoint` with the policy of
`ops/recompute.py`: the block is computed again in the backward pass,
all but the values its layers name as they make them: a Mosaic forward
kernel's outputs that its own backward reads (flash attention, the gated
delta rule, the selective scan, the Mamba-2 scan) and the router's
result.  Held here, on the CPU with the kernels in interpret mode, a
layer type a case: the recomputation holds
no forward kernel, `top_k`, sort or router product and the saved
residuals are the list's names; values and gradients are a plain
`jax.checkpoint`'s to the last bit; outside a block the names are
identities; `info.recompute` counts the bytes the shapes give.

And a block computes nothing a third time: the elementwise stages that
`GatedDeltaNet` and `Mamba` hand to `recompute.stage` (the convolution
of both, the preparation of the delta rule's operands) carry no
checkpoint of their own inside a block, where they run twice a step;
outside one the layers' programs are what they were; values and
gradients equal a build with the wrappers on to the last bit;
`info.recompute` counts the stages a block.  The convolution stage is
counted in both its forms (`layers.causal_taps_silu`): the XLA form by
its SiLU, the kernel form by its calls, `cos_taps_fwd` twice and
`cos_taps_bwd` once in a block."""

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
from caffeonspark_tpu.ops import route

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


def ssm_bytes(ch=128, n=16, c=64):
    c = pk.ssm_scan_plan(T, ch, n, c)["chunk"]
    return {"ssm.y": B * T * ch * 4, "ssm.edges": B * -(-T // c) * ch * n * 4}


def ssd_bytes(h=2, p=64, n=128, c=64):
    """The XLA form (a chunk of 64 does not tile): y padded to whole
    groups of chunks, the state before every group."""
    groups = -(-(-(-T // c)) // L._SSD_GROUP)
    full = groups * min(L._SSD_GROUP, -(-T // c)) * c
    return {"ssd.y": B * full * h * p * 4,
            "ssd.edges": groups * B * h * p * n * 4}


def ssd_kernel_bytes(h=2, p=64, g=1, n=128, c=128):
    """The kernels: y time-major, padded to whole grid steps, and the
    state before every chunk."""
    chunks = pk.ssd_scan_plan(T, B, h, p, g, n, c)["chunks"]
    return {"ssd.y": chunks * c * B * h * p * 4,
            "ssd.edges": B * chunks * h * p * n * 4}


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
    "mamba": ('type: "Mamba" mamba_param { d_inner: 128 d_state: 16 '
              'd_conv: 4 dt_rank: 4 chunk: 64 }', ssm_bytes, ("cos_ssm_fwd",)),
    # the XLA form (a chunk of 64 does not tile): what must not run
    # again is the forward scan over the groups of chunks (`held`:
    # "ssd_forward"); and the kernels at a chunk of 128
    "mamba2": ('type: "Mamba2" mamba2_param { num_heads: 2 head_dim: 64 '
               'n_groups: 1 d_state: 128 d_conv: 4 chunk: 64 }', ssd_bytes,
               ("ssd_forward",)),
    "mamba2_kernel": ('type: "Mamba2" mamba2_param { num_heads: 2 '
                      'head_dim: 64 n_groups: 1 d_state: 128 d_conv: 4 '
                      'chunk: 128 }', ssd_kernel_bytes, ("cos_ssd_fwd",)),
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
# the layers with three stages each -> (the shape of the stages'
# marker, the SiLU over the convolution's channels; the SiLUs of that
# shape a gradient holds outside the stages: Mamba's gate on z, in the
# forward pass and in the block's recomputation; how many of the three
# run bare in a block: all but the Gated DeltaNet's gate, all but
# Mamba's `rows` and `skip`, which keep a checkpoint of their own; how
# many stages the layer has)
STAGED = {"gdn": ((T, B, 2 * 128 + 2 * 128), 0, 2, 3),
          "mamba": ((T, B, 128), 2, 1, 3),
          # Mamba-2's gate is on z, 128 wide: no SiLU of the
          # convolution's 384 channels outside its stage; `gate` keeps
          # a checkpoint of its own (two stages), and so do the scan's
          # XLA form's `rows` and `skip` (four)
          "mamba2": ((T, B, 128 + 2 * 128), 0, 1, 4),
          "mamba2_kernel": ((T, B, 128 + 2 * 128), 0, 1, 2)}


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
    route.forget("recompute")


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
        elif what == "ssd_forward":
            # `_ssd_groups_fwd`'s scan: forward in time, a group's
            # running sums in its body (the backward's scan runs in
            # reverse; the chunk-to-chunk carries hold no running sum)
            if name == "scan" and not e.params["reverse"] and any(
                    i.primitive.name == "cumsum"
                    for i in eqns(e.params["jaxpr"].jaxpr)):
                return True
        elif name == what or (name == "pallas_call"
                              and what in e.params["name"]):
            return True
    return False


def handed_over(jaxpr):
    """What the one block's checkpoint reads in the backward pass that
    the forward pass made -> ({name: bytes} of the values that are a named value:
    as named, behind the full-precision `reduce_precision` jax puts on
    a float residual, or handed through a jitted function or a layer's
    own checkpoint that returns its argument; the shapes of the others).
    This is what `jax.ad_checkpoint.print_saved_residuals` lists, less
    the arguments, with each value traced to its name."""
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
            if e.primitive.name in ("jit", "pjit", "checkpoint", "remat2"):
                inner = e.params["jaxpr"]
                inner = getattr(inner, "jaxpr", inner)
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
    assert any(e.primitive.name in ("pallas_call", "while", "scan")
               for e in eqns(body))

    named, other = handed_over(jaxpr)
    assert named == kept() and set(named) <= set(R.KEPT)
    # and nothing else of the block's making: the cotangent alone
    assert other == [(T, B, D)]
    assert route.plans()["recompute"]["blocks"] == {case: named}

    plain(monkeypatch)
    body = recomputations(program())[-1].params["jaxpr"]
    assert [w for w in gone if held(body, w)] == list(gone)


# ----------------------------------------------------- (b) the values

def assert_same_leaves(got, want, ulps=False):
    """Two runs' losses, tops and gradients, leaf by leaf: to the last
    bit, or with `ulps` every array within 1e-7 of its largest entry
    (the scalars still to the last bit); not all zero."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert any(np.abs(np.asarray(a)).max() > 0 for _, a in flat)
    for (path, a), b in zip(flat, jax.tree.leaves(want)):
        a, b, name = np.asarray(a), np.asarray(b), jax.tree_util.keystr(path)
        if ulps and a.ndim:
            np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                       atol=1e-7 * np.abs(b).max())
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


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
    assert_same_leaves(got, want)


# ------------------------------------- (c) a name outside a block

def gradient_program(net, train=True):
    """The gradient of the net's loss in its parameters and its input,
    traced anew at every call."""
    kp, kx = jax.random.split(jax.random.key(0))
    params = net.init(kp)
    x = jax.random.normal(kx, (T, B, D), jnp.float32)

    def loss(p, x):
        return net.loss(p, {"x": x, "want": x}, train=train,
                        rng=jax.random.key(1))[0]

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr


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
    train = where != "test_pass"
    named = gradient_program(net, train)
    names = [e.params["name"] for e in eqns(named)
             if e.primitive.name == "name"]
    assert {"flash.out", "gdn.edges", "moe.order"} <= set(names)
    assert all(e.params["policy"] is None for e in eqns(named)
               if e.primitive.name in ("checkpoint", "remat2"))
    assert route.plans().get("recompute", {}) == {}
    for mod in (L, pk):
        monkeypatch.setattr(mod, "keep", lambda x, name: x)
    bare = gradient_program(net, train)
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
    the bytes reckoned from the shapes, their sum a step, the block
    that keeps nothing by name, and the stages that ran in a block
    without a checkpoint of their own; a second trace counts nothing
    twice."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.processor import CaffeProcessor

    net = build([case, "dense"] if case != "dense" else ["dense"])
    loss, params, x = problem(net)
    want = CASES[case][1]()
    for _ in range(2):
        jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x)[0]))(params, x)
        assert route.plans().get("recompute", {}) == {
            "blocks": {case: want} if want else {},
            "bytes_a_step": sum(want.values()),
            "keep_nothing": ["dense"],
            "stages_unwrapped": ({case: STAGED[case][2]}
                                 if case in STAGED else {})}

    class Job:
        metrics = PipelineMetrics()

    CaffeProcessor._note_lowering_plans(Job)
    assert Job.metrics.summary()["info"]["recompute"] == route.plans().get("recompute", {})


# ------------------------- (e) a block computes nothing a third time

def wrapped(monkeypatch):
    """The parent's layers: every stage under its own `jax.checkpoint`,
    inside a block too."""
    monkeypatch.setattr(L, "stage", jax.checkpoint)


@pytest.fixture(params=["xla", "kernel"])
def form(request, monkeypatch):
    """The form the convolution stage is lowered to under the
    `interpret` fixture: the kernels', or (no shape tiles) XLA's."""
    if request.param == "xla":
        monkeypatch.setattr(pk, "taps_plan", lambda *a: None)
    return request.param


def is_call(e, name):
    return e.primitive.name == "pallas_call" and name in e.params["name"]


def is_marker(e, case, form):
    """The convolution stage's forward: the `logistic` over its channels
    (XLA form) or the forward kernel call."""
    if form == "kernel":
        return is_call(e, "cos_taps_fwd")
    return (e.primitive.name == "logistic"
            and e.outvars[0].aval.shape == STAGED[case][0])


def times_run(jaxpr, case, form):
    """How often the program computes the convolution stage forward:
    the `logistic` equations over its channels, wherever they stand,
    less those outside the stages, or the forward kernel's calls."""
    return (sum(is_marker(e, case, form) for e in eqns(jaxpr))
            - (STAGED[case][1] if form == "xla" else 0))


def nested(jaxpr):
    """The checkpoints inside the last checkpoint at the top (a
    block's, in a net that has blocks)."""
    return [e for e in eqns(recomputations(jaxpr)[-1].params["jaxpr"])
            if e.primitive.name in ("checkpoint", "remat2")]


@pytest.mark.parametrize("case", list(STAGED))
def test_a_stage_runs_twice_inside_a_block_not_three_times(
        interpret, monkeypatch, case, form):
    """The gradient through one block holds, inside the block's
    checkpoint, those of the stages that keep one and no other, and the
    convolution's forward twice: the forward pass and the block's
    recomputation (the XLA form's SiLU; the kernel form's forward call,
    beside one backward call).  With the wrappers on (the control) every
    stage's checkpoint is nested there and the XLA form's SiLU stands a
    third time, in the inner checkpoint's own backward (the kernel
    form's backward call computes it inside: what its forward keeps is
    its inputs, so the inner checkpoint has no call to make again)."""
    bare = STAGED[case][2]
    net = build([case])
    jaxpr = gradient_program(net)
    assert len(recomputations(jaxpr)) - 1 == len(nested(jaxpr)) \
        == STAGED[case][3] - bare
    assert times_run(jaxpr, case, form) == 2
    assert sum(is_call(e, "cos_taps_bwd") for e in eqns(jaxpr)) == (
        form == "kernel")
    assert route.plans()["recompute"]["stages_unwrapped"] == {case: bare}

    wrapped(monkeypatch)
    jaxpr = gradient_program(net)
    assert len(recomputations(jaxpr)) - 1 == len(nested(jaxpr)) \
        == STAGED[case][3]
    assert times_run(jaxpr, case, form) == (3 if form == "xla" else 2)
    assert route.plans()["recompute"]["stages_unwrapped"] == {}


@pytest.mark.parametrize("where", ["no_block", "test_pass", "COS_REMAT"])
@pytest.mark.parametrize("case", list(STAGED))
def test_outside_a_block_a_stage_keeps_its_own_checkpoint(
        interpret, monkeypatch, case, where, form):
    """A net without blocks, a TEST pass and COS_REMAT=1 trace to the
    program of the layers with `jax.checkpoint` at the call sites: the
    convolution stage's checkpoint is there (around the SiLU's second
    run, or around the backward kernel call, which runs it inside),
    nothing is counted."""
    if where == "COS_REMAT":
        monkeypatch.setenv("COS_REMAT", "1")
    net = build([case], tag=where != "no_block")
    train = where != "test_pass"
    jaxpr = gradient_program(net, train)
    own = [e for e in eqns(jaxpr)
           if e.primitive.name in ("checkpoint", "remat2")
           and any(is_call(s, "cos_taps_bwd") if form == "kernel"
                   else is_marker(s, case, form)
                   for s in eqns(e.params["jaxpr"]))]
    assert own and route.plans().get("recompute", {}) == {}
    wrapped(monkeypatch)
    assert shape_of(gradient_program(net, train)) == shape_of(jaxpr)


@pytest.mark.parametrize("how", ["op_by_op", "compiled"])
@pytest.mark.parametrize("case", list(STAGED))
def test_values_equal_the_wrapped_stages_bit_for_bit(interpret, monkeypatch,
                                                     case, how, form):
    """Loss, every top and every gradient of a block whose stages run
    bare equal those of the same block with each stage under its own
    checkpoint: the same arithmetic, once less.  To the last bit where
    each primitive runs by itself; compiled, XLA fuses what the inner
    checkpoints' barriers kept apart, and a sum over the rows (the taps'
    gradient) may come out an ulp away."""
    net = build([case])
    loss, params, x = problem(net, seed=5)

    def run():
        fn = jax.value_and_grad(
            lambda p, x: loss(p, x), argnums=(0, 1), has_aux=True)
        if how == "compiled":
            fn = jax.jit(fn)
        return fn(params, x), jax.jit(fn).lower(params, x).as_text()

    got, program = run()
    wrapped(monkeypatch)
    want, parent = run()
    assert program != parent
    assert_same_leaves(got, want, ulps=how == "compiled")


def test_stages_are_counted_by_block(interpret):
    """The staged layers in blocks of their own beside a block without
    one: each of them with its stages, the last block not listed, a
    second trace counting nothing twice."""
    net = build(list(STAGED) + ["dense"])
    for _ in range(2):
        gradient_program(net)
        assert route.plans()["recompute"]["stages_unwrapped"] == {
            c: STAGED[c][2] for c in STAGED}
