"""The flash kernels at the real shapes of the benchmark's language
models, the gated delta rule's kernels at qwen3next's, the selective
scan's at phi4flash's, the Mamba-2 scan's at nemotron3nano's and the
convolution stage's at all three, compiled for
a described (not attached) TPU v5e: what interpret
mode cannot see — VMEM, tiling, the grouped block index maps.  PR 33
found here, before any chip time, that a 64-wide head is padded to 128
lanes in VMEM.  About two seconds a case; nothing runs.

The topology is described inside a fixture, never at import (only one
process may hold the TPU's library: `on-chip-measurement` guide,
section 2), and the compile is made in the test's own process.

One test here runs on the chip and is skipped without one (the `tpu`
fixture, COS_TPU_TESTS=1): the convolution stage's compiled kernels
against the XLA form at the two cells' shapes."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from caffeonspark_tpu.ops import route


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,h,hkv,t,d,dv,mxu,calls,window", [
    (2, 32, 8, 4096, 64, 64, jnp.bfloat16, 3, 0),   # lfm2 at 2 x 4,096
    (1, 32, 8, 8192, 64, 64, jnp.bfloat16, 3, 0),   # lfm2.train_packed8k
    (2, 32, 32, 4096, 192, 128, jnp.bfloat16, 3, 0),    # kanana2...4k
    # qwen3next.train_packed8k: 256-wide heads, g = 8; k and v of one
    # key/value head are 2 x 4 MiB resident, so 8,192 rows go as pairs
    # of chunks of 4,096 (3 calls a kernel), none with a window
    (1, 16, 2, 8192, 256, 256, jnp.bfloat16, 9, 0),
    # float32 operands (`_mha`'s exact mode) take twice the room: 8,192
    # rows go as pairs of chunks of 4,096, three forward and three for
    # each backward kernel
    (1, 4, 4, 8192, 64, 64, None, 9, 0),
    (1, 4, 4, 2048, 128, 128, None, 3, 0),
    # smallthinker.train_packed16k: g = 7, 128-wide heads, 16,384 rows
    # as two chunks of 8,192: 3 pairs a kernel in the global layer, and
    # under the window of 4,096 keys (half a chunk) 3 pairs too, the
    # off-diagonal one visited in an eighth of its tiles
    (1, 28, 4, 16384, 128, 128, jnp.bfloat16, 9, 0),
    (1, 28, 4, 16384, 128, 128, jnp.bfloat16, 9, 4096),
    # a window of one chunk drops the pairs two chunks apart (float32
    # operands: 4 chunks of 4,096, 7 of the 10 causal pairs a kernel)
    (1, 28, 4, 16384, 128, 128, None, 21, 4096),
    # phi4flash.train_packed8k: differential attention, 40 query heads of
    # 64 over 20 key heads of 64 with the values of a pair side by side,
    # 128 wide; one chunk, whole past and under a window of 512 keys (one
    # tile: every visited tile is masked)
    (1, 40, 20, 8192, 64, 128, jnp.bfloat16, 3, 0),
    (1, 40, 20, 8192, 64, 128, jnp.bfloat16, 3, 512),
    # nemotron3nano.train_packed8k: g = 16, the most query heads a
    # key/value head of any cell (32 over 2 heads of 128); one chunk
    (1, 32, 2, 8192, 128, 128, jnp.bfloat16, 3, 0),
])
def test_flash_forward_and_backward_compile_for_v5e(one_chip, b, h, hkv,
                                                    t, d, dv, mxu, calls,
                                                    window):
    """The three kernels at the tiles `_flash_tiles` picks lower for the
    v5e, and NO call carries a VMEM window of its own: XLA lays the
    buffers it keeps across a Mosaic call as if the call took the
    default 16 MiB, and a step of lfm2 whose kernels took 20-36 never
    ended on the chip (PR 33).  Since PR 34 that holds at every length,
    kanana2's 4,096 rows included (its dk/dv call asked for 22.6 MiB):
    operands arrive in bfloat16, the statistics of the dk/dv call as
    rows, and what still does not fit is cut (`_flash_chunk`)."""
    from caffeonspark_tpu.ops import pallas_kernels as pk

    def loss(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, True, interpret=False,
                                          mxu_dtype=mxu, window=window))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((b, h, t, d), (b, hkv, t, d), (b, hkv, t, dv))]
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    text = compiled.as_text()
    for name in ("cos_flash_fwd", "cos_flash_bwd_dq", "cos_flash_bwd_dkv"):
        assert name in text
    # dk, dv come back in k's and v's own shapes
    out = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), *shapes)
    assert [o.shape for o in out] == [s.shape for s in shapes]
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == calls
    # a call at the default carries no window
    windows = [int(n) for line in lines for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"\d+","size":"(\d+)"', line)]
    assert not windows, windows
    # the counter says what was lowered: tiles above the floor, and
    # the calls an attention takes
    plan = route.plans()["flash"][
        f"{b * h}x{t}x{d}/{dv} "
        f"{jnp.dtype(mxu or jnp.float32).name} g{h // hkv} causal"
        f"{f' window {window}' if window else ''}"]
    assert sum(p["calls"] for p in plan.values()) == calls
    assert all(max(p["block_q"], p["block_k"]) > 128
               for p in plan.values())
    if window == 512:
        assert all((p["block_q"], p["block_k"], p["visited_tile_share"],
                    p["masked_tile_share"]) == (512, 512, 0.2279, 1.0)
                   for p in plan.values())
    elif window and mxu is not None:
        # the cell's windowed layers: 252 of the causal triangle's 528
        # tiles of 512 x 512 a head, the first and the last of a q
        # tile's 9 k tiles masked
        assert all((p["block_q"], p["block_k"], p["causal_calls"],
                    p["visited_tile_share"], p["masked_tile_share"])
                   == (512, 512, 3, 0.4773, 0.2222) for p in plan.values())
    elif window:
        assert all(p["causal_calls"] == 10 for p in plan.values())


@pytest.mark.parametrize("shape,plan", [
    ("64x4096x192/128 bfloat16 g1 causal",          # kanana2
     {"fwd": (512, 512, 1, 0.2222), "dq": (512, 512, 1, 0.2222),
      "dkv": (512, 512, 1, 0.2222)}),
    ("32x8192x64/64 bfloat16 g4 causal",            # lfm2
     {"fwd": (512, 512, 1, 0.1176), "dq": (512, 512, 1, 0.1176),
      "dkv": (512, 512, 1, 0.1176)}),
    ("16x8192x256/256 bfloat16 g8 causal",          # qwen3next
     {"fwd": (512, 512, 3, 0.1176), "dq": (256, 512, 3, 0.1176),
      "dkv": (512, 256, 3, 0.1176)}),
])
def test_accepted_cells_flash_plans_are_what_they_were(shape, plan):
    """`info.flash` of the three accepted language cells, letter for
    letter as the parent of the PR that brought windows wrote them (tiles,
    calls an attention, masked share; no new key): a call without a
    window is planned, counted and lowered as before."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    head, dtype, g = shape.split()[:3]
    bh, t, widths = head.split("x")
    d, dv = (int(x) for x in widths.split("/"))
    hkv = int(bh) // int(g[1:])
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, int(bh), int(t), d), (1, hkv, int(t), d), (1, hkv, int(t), dv)))
    route.entries("flash").pop(shape, None)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(pk.flash_attention(
        q, k, v, True, interpret=True, mxu_dtype=jnp.bfloat16)),
        argnums=(0, 1, 2)), q, k, v)
    assert route.plans()["flash"][shape] == {
        kern: {"block_q": bq, "block_k": bk, "calls": calls,
               "masked_tile_share": share}
        for kern, (bq, bk, calls, share) in plan.items()}


def test_gated_delta_rule_kernels_compile_for_v5e(one_chip):
    """The rule's forward and backward calls at `qwen3next.train_
    packed8k`'s shape (1, 16 / 32 heads, 8,192, 128 / 128, chunk 64)
    lower for the v5e: one forward call over the row, and in ONE loop
    over the 8 groups of 16 chunks a recomputation and a sweep (three
    call sites: what a job traces and lowers at every start), none
    with a VMEM window of its own."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    b, hk, r, t, dk, dv, c = 1, 16, 2, 8192, 128, 128, 64
    assert pk.gdn_rule_tiles(r, c, dk, dv)

    def loss(*a):
        return jnp.sum(pk.gated_delta_rule_kernels(*a, c))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((b, hk, t, dk), (b, hk, t, dk), (b, hk, r, t, dv),
                        (b, hk, r, t), (b, hk, r, t))]
    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grad).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert [o.shape for o in jax.eval_shape(grad, *shapes)] == [
        s.shape for s in shapes]
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("cos_gdn_fwd", "cos_gdn_save", "cos_gdn_bwd"):
        assert sum(name in line for line in lines) == 1, name
    assert len(lines) == 3
    assert all('"scoped_memory_configs":[]' in line for line in lines)
    assert pk.gdn_rule_steps(t // c) == (4, 4, 128)     # 8 groups


def _expert_layer(net: str):
    """The first expert layer of a zoo net as `zoo` writes it, and the
    float32 shapes it is applied to: its blobs, then its bottoms."""
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.ops import layers as L
    npm = getattr(zoo, net)()
    lp = next(lp for lp in npm.layer if lp.type == "MixtureOfExperts")
    n, d = 8192, {"nemotron_h": 2688}.get(net, 2048)
    blobs = [s for _, s, _ in L._moe_params(lp, [(n, d)])]
    return lp, blobs, [(n, d)] * len(lp.bottom)


def _expert_layers_loss(lps):
    """sum(sin(layer(x))) over `lps`, each with its own blobs."""
    from caffeonspark_tpu.ops import layers as L

    def loss(blobs, *bottoms):
        return sum(jnp.sum(jnp.sin(L.get_op("MixtureOfExperts").apply(
            L.Ctx(train=True), lp, p, list(bottoms))[0]))
            for lp, p in zip(lps, blobs))
    return loss


@pytest.mark.parametrize("net,key,tiles", [
    ("lfm2", "8192x2048 top 4 of 64, 8 held x 1536 gated, shared 0",
     {"into": {"rows": 128, "lanes": 512, "lanes_t": 512,
               "grad": (1024, 768)},
      "out": {"rows": 128, "lanes": 512, "lanes_t": 512,
              "grad": (768, 1024)}}),
    # a hidden width of 14.5 x 128: whole where it is contracted, a
    # masked last block where it is the lanes (5 x 384, 2 x 1024)
    ("nemotron_h", "8192x2688 top 6 of 128, 8 held x 1856 relu2, "
     "shared 3712",
     {"into": {"rows": 128, "lanes": 384, "lanes_t": 512,
               "grad": (896, 1024)},
      "out": {"rows": 128, "lanes": 512, "lanes_t": 384,
              "grad": (1024, 896)}}),
])
def test_expert_pass_kernels_compile_for_v5e(one_chip, monkeypatch, net,
                                             key, tiles):
    """An expert layer of `lfm2.train_packed8k` and of `nemotron3nano.
    train_packed8k`, value and gradients, lowers for the v5e with exactly
    the `calls` its plan records: a product's forward call in the
    forward loop, and in the backward loop the call again, its rows^T
    and its weights; none with a VMEM window of its own."""
    monkeypatch.setattr(route, "on_tpu", lambda: True)
    route.forget("moe")
    lp, blobs, bottoms = _expert_layer(net)
    shapes = [[jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
               for s in blobs]] + [jax.ShapeDtypeStruct(
                   s, jnp.float32, sharding=one_chip) for s in bottoms]
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(jax.value_and_grad(
            lambda p, *xs: _expert_layers_loss([lp])([p], *xs),
            argnums=(0, 1))).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    plan = route.plans()["moe"][key]
    assert plan["form"] == "kernel" and plan["tiles"] == tiles
    products = plan["calls"] // 4
    assert plan["calls"] == (8 if net == "nemotron_h" else 12)
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == plan["calls"]
    for name, calls in (("cos_gmm_rows", 2 * products),
                        ("cos_gmm_rows_t", products),
                        ("cos_gmm_weights", products)):
        assert sum(f'/{name}/pallas_call"' in line
                   for line in lines) == calls, name
    # under `moe.products` inside a loop of `moe.experts`, whatever
    # transformation wrapped the two tokens
    assert all(re.search(r"moe\.experts\)*/while/body/[a-z(]*moe\.products"
                         r"\)*/cos_gmm_", line) for line in lines)
    assert all('"scoped_memory_configs":[]' in line for line in lines)


def test_expert_layers_of_one_shape_trace_each_kernel_once(monkeypatch):
    """What a job's start pays for the expert layers' kernels (CPU,
    interpret mode): one layer's forward + backward holds the 12
    `pallas_call` equations its plan's `calls` says (8 ungated), and
    two layers of one shape trace each kernel body no more often than
    one does: once (`rows`: twice) a kind of product and weight shape,
    whatever the number of layers and call sites (a `pallas_call` is
    built once an argument list, `pallas_kernels._built_once`).  PRs 37,
    44, 48 and 49 each found a kernel body's Python in `setup_s` only on
    the chip."""
    import collections
    from caffeonspark_tpu.ops import pallas_kernels as pk
    from caffeonspark_tpu.proto import LayerParameter
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    seen = collections.Counter()
    for name in ("_gmm_rows_kernel", "_gmm_weights_kernel"):
        def counted(*refs, _fn=getattr(pk, name), _name=name, **static):
            seen[_name, static.get("transposed"), refs[1].shape] += 1
            return _fn(*refs, **static)
        monkeypatch.setattr(pk, name, counted)
    n, d, h = 128, 32, 48
    x = jax.ShapeDtypeStruct((n, d + 16), jnp.float32)

    def layers(count, gated):
        lps = [LayerParameter.from_text(f"""
          name: "L{i}.moe" type: "MixtureOfExperts" bottom: "x" top: "y"
          moe_param {{ num_experts: 8 hidden_dim: {h + 16 + 16 * gated}
            top_k: 2 dispatch: "dropless" scoring: "sigmoid"
            gated: {str(gated).lower()} experts_held: 4 }}""")
               for i in range(count)]
        from caffeonspark_tpu.ops import layers as L
        blobs = [[jax.ShapeDtypeStruct(s, jnp.float32) for _, s, _ in
                  L._moe_params(lp, [x.shape])] for lp in lps]
        return jax.make_jaxpr(jax.value_and_grad(
            _expert_layers_loss(lps), argnums=(0, 1)))(blobs, x)

    def calls(jaxpr):       # nested equations are printed too
        return str(jaxpr).count(" pallas_call[")

    for gated, sites in ((True, 12), (False, 8)):
        route.forget("moe")
        pk.forget_calls()
        seen.clear()
        assert calls(layers(1, gated)) == sites
        plan, = route.plans()["moe"].values()
        assert plan["form"] == "kernel" and plan["calls"] == sites
        one = dict(seen)
        # rows and rows^T against the first and the last weights, and
        # the weights' product of each; `rows` once more where it is
        # differentiated (the inlined jit keeps a trace a context)
        assert len(one) == 6 and max(one.values()) <= 2
        assert calls(layers(2, gated)) == 2 * sites
        assert dict(seen) == one


def test_selective_scan_kernels_compile_for_v5e(one_chip):
    """The scan's forward and backward calls at `phi4flash.train_
    packed8k`'s shape (1, 8,192, 5,120 channels, 16 states, chunk 64)
    lower for the v5e: one call each over the row's 128 chunks and 10
    channel blocks, neither with a VMEM window of its own (8.7 MB
    counted for the backward call, the larger)."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    b, t, ch, n = 1, 8192, 5120, 16
    plan = pk.ssm_scan_plan(t, ch, n, 64)
    assert plan == {"chunk": 64, "channels": 512, "vmem_bytes": 8749056}
    assert pk._flash_window(plan["vmem_bytes"]) <= pk._SCOPED_VMEM
    # what does not tile falls to the XLA form
    assert pk.ssm_scan_plan(t, 5000, n, 64) is None
    assert pk.ssm_scan_plan(t, ch, 4, 64) is None
    assert pk.ssm_scan_plan(t, ch, n, 1024) is None     # over the window

    def loss(*a):
        return jnp.sum(pk.selective_scan_kernels(*a, plan))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((b, t, ch), (b, t, ch), (ch, n), (b, t, n),
                        (b, t, n))]
    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grad).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert [o.shape for o in jax.eval_shape(grad, *shapes)] == [
        s.shape for s in shapes]
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("cos_ssm_fwd", "cos_ssm_bwd"):
        assert sum(name in line for line in lines) == 1, name
    assert len(lines) == 2
    assert all('"scoped_memory_configs":[]' in line for line in lines)


def test_mamba2_scan_kernels_compile_for_v5e(one_chip):
    """The scan's forward and backward calls at `nemotron3nano.train_
    packed8k`'s shape (1 x 8,192, 64 heads of 64 over 8 groups of 128
    states, chunk 128, [u | B | C] 6,144 wide) lower for the v5e: one
    call each over the 8 groups' 32 grid steps of two chunks, neither
    with a VMEM window of its own (10.6 MB counted for the backward call,
    the larger).  What does not tile falls to the XLA form."""
    from caffeonspark_tpu.ops import pallas_kernels as pk
    t, b, h, p, g, n = 8192, 1, 64, 64, 8, 128
    plan = pk.ssd_scan_plan(t, b, h, p, g, n, 128)
    assert plan == {"chunk": 128, "steps": 2, "chunks": 64,
                    "vmem_bytes": 10641408}
    assert pk._flash_window(plan["vmem_bytes"]) <= pk._SCOPED_VMEM
    assert pk.ssd_scan_plan(t, b, h, p, g, n, 64) is None    # the chunk
    assert pk.ssd_scan_plan(t, b, h, p, g, 64, 128) is None     # N
    assert pk.ssd_scan_plan(t, b, h, 8, g, n, 128) is None      # R P = 64
    assert pk.ssd_scan_plan(t, b, h, 96, g, n, 128) is None     # heads of 96
    assert pk.ssd_scan_plan(t, b, 60, p, g, n, 128) is None     # H / G
    assert pk.ssd_scan_plan(t, b, 256, 256, g, 256, 256) is None    # VMEM
    # two batch columns: 6,144 = 12 blocks of R P = 512 a column
    assert pk.ssd_scan_plan(t, 2, h, p, g, n, 128) == plan

    def loss(*a):
        return jnp.sum(pk.ssd_scan_kernels(*a, plan, groups=g, states=n))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((t, b, h * p + 2 * g * n), (t, b, h), (h,), (h,))]
    grad = jax.grad(loss, argnums=(0, 1, 2, 3))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(grad).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert [o.shape for o in jax.eval_shape(grad, *shapes)] == [
        s.shape for s in shapes]
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("cos_ssd_fwd", "cos_ssd_bwd"):
        assert sum(name in line for line in lines) == 1, name
    assert len(lines) == 2
    assert all('"scoped_memory_configs":[]' in line for line in lines)


# the convolution stage of `qwen3next.train_packed8k` (the first 8,192
# of W_qkvz's 12,288 channels) and of `phi4flash.train_packed8k` (the
# first 5,120 of W_in's 10,240, with a bias), and of `nemotron3nano.
# train_packed8k` (the 6,144 channels of xBC behind z's 4,096 in the
# 10,240-wide [z | xBC], with a bias)
TAPS_SHAPES = [(8192, 1, 12288, 8192, False, 0),
               (8192, 1, 10240, 5120, True, 0),
               (8192, 1, 10240, 6144, True, 4096)]


def _taps_case(t, b, w, c, bias, first=0):
    """The stage's kernel and XLA forms as functions of 2-D arrays (so
    that a test's own arguments carry the layout the step's product
    gives them), each -> (y, (dz, dtaps[, dbias]))."""
    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.ops import pallas_kernels as pk
    plan = pk.taps_plan(t, c, w, 4, first)

    def both(stage):
        def run(z2, taps, bv, dy2):
            args = (z2, taps) + ((bv,) if bias else ())
            y, vjp = jax.vjp(lambda z2, taps, bv=None: stage(
                z2.reshape(t, b, w), taps, bv).reshape(t, b * c), *args)
            return y, vjp(dy2)
        return run

    return plan, both(lambda z, taps, bv: pk.causal_taps_silu_kernels(
        z, taps, bv, plan, first=first)), both(
            lambda z, taps, bv: L.causal_taps_silu_xla(z, taps, bv, first))


@pytest.mark.parametrize("t,b,w,c,bias,first", TAPS_SHAPES)
def test_convolution_stage_kernels_compile_for_v5e(one_chip, t, b, w, c,
                                                   bias, first):
    """`cos_taps_fwd` and `cos_taps_bwd` at the two cells' shapes lower
    for the v5e at the tiles `taps_plan` picks, one call each, neither
    with a VMEM window of its own, and read the wide array where it
    lies: no copy of it or of its slice stands before a call."""
    plan, kernel, _ = _taps_case(t, b, w, c, bias, first)
    assert plan == {"time_tile": 512, "channel_tile": 512}
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
              for s in ((t, b * w), (c, 4), (c,), (t, b * c))]
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(kernel).lower(*shapes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    lines = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("cos_taps_fwd", "cos_taps_bwd"):
        assert sum(name in line for line in lines) == 1, name
    assert len(lines) == 2
    assert all('"scoped_memory_configs":[]' in line for line in lines)
    # the calls' first operand is the test's own argument
    assert all(re.search(r"custom-call\(f32\[%d,%d\][^ ]* %%z2" % (t, b * w),
                         line) or re.search(r"custom-call\(%%?z2", line)
               for line in lines), lines


@pytest.mark.parametrize("t,b,w,c,bias,first", TAPS_SHAPES)
def test_convolution_stage_kernels_equal_the_xla_form_on_the_chip(
        tpu, t, b, w, c, bias, first):
    """On the chip (skipped elsewhere): the compiled kernels against the
    XLA form at the two cells' shapes.  The bound is float32 rounding of
    a sum of four products and a bias under a SiLU: y and dz within 8 ulp
    of the array's largest entry (8 x 2^-23 = 9.5e-7 of it; measured
    0 to 2.9e-7, PR 44's lab), the sums over 8,192 rows (the taps' and
    the bias's gradients) within 2^-17 = 7.6e-6 of theirs (measured
    4.4e-7)."""
    _, kernel, xla = _taps_case(t, b, w, c, bias, first)
    k = jax.random.split(jax.random.key(t + c), 4)
    args = (jax.random.normal(k[0], (t, b * w)),
            0.5 * jax.random.normal(k[1], (c, 4)),
            0.5 * jax.random.normal(k[2], (c,)),
            jax.random.normal(k[3], (t, b * c)))
    got, got_grads = jax.jit(kernel)(*args)
    want, want_grads = jax.jit(xla)(*args)
    assert len(got_grads) == 2 + bias

    def gap(a, r):
        return float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))

    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert gap(got, want) <= 8 * 2.0 ** -23
    assert gap(got_grads[0], want_grads[0]) <= 8 * 2.0 ** -23
    dz = got_grads[0].reshape(t, b, w)
    assert not bool(jnp.any(dz[..., :first]))
    assert not bool(jnp.any(dz[..., first + c:]))
    for a, r in zip(got_grads[1:], want_grads[1:]):
        assert a.shape == r.shape and gap(a, r) <= 2.0 ** -17


def test_nemotron3nano_step_compiles_for_v5e(one_chip, monkeypatch):
    """`nemotron3nano.train_packed8k`'s whole train step (the net of
    `zoo.nemotron_h()`, Adam, one row of 8,192) compiles for the v5e
    with the operators on the forms the cell is to run: the g = 16
    attention on the three flash kernels, the four convolution stages
    on the taps kernels reading xBC from lane 4,096 of [z | xBC], the
    four Mamba-2 scans on their kernels reading u, B and C from the
    taps kernels' output in place (67 Mosaic calls); no Mosaic call asks
    for a VMEM window; arguments + temporaries stand under the chip's
    15.75 GB (8,003,682,816 + 5,131,206,144 = 13.13 GB as compiled
    here: the states before every chunk are kept, 134 MB a layer)."""
    from caffeonspark_tpu.models import zoo
    from caffeonspark_tpu.proto import SolverParameter
    from caffeonspark_tpu.solver import Solver
    monkeypatch.setattr(route, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    route.forget()
    solver = Solver(SolverParameter.from_text(
        'type: "Adam" base_lr: 1e-6 lr_policy: "fixed" momentum: 0.9 '
        'momentum2: 0.95 delta: 1e-8 clip_gradients: 1.0 random_seed: 1'),
        zoo.nemotron_h())

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params, state = jax.eval_shape(solver.init)
    inputs = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
              for n, s, _ in solver.train_net.input_specs}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(solver.train_step_fn(),
                           donate_argnums=(0, 1)).lower(
            shaped(params), shaped(state), shaped(inputs),
            shaped(jax.eval_shape(lambda: jax.random.key(0)))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 8.1e9       # 3 x 4 B x 667 M
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 15.75e9
    lines = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(lines) == 23 + 4 * 8
    # (by the call's own name: a scan's line names the taps call whose
    # output it reads)
    for name, calls in (("cos_flash_fwd", 1), ("cos_flash_bwd_dq", 1),
                        ("cos_flash_bwd_dkv", 1), ("cos_taps_fwd", 8),
                        ("cos_taps_bwd", 4), ("cos_ssd_fwd", 4),
                        ("cos_ssd_bwd", 4), ("cos_gmm_rows", 16),
                        ("cos_gmm_rows_t", 8), ("cos_gmm_weights", 8)):
        assert sum(f'/{name}/pallas_call"' in line
                   for line in lines) == calls, name
    windows = [int(n) for line in lines for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"\d+","size":"(\d+)"', line)]
    assert not windows, windows
    plans = route.plans()
    assert list(plans["flash"]) == ["32x8192x128/128 bfloat16 g16 causal"]
    taps = plans["taps"][
        "1x8192 6144 of 10240 channels from 4096 4 taps float32 bias"]
    assert taps["form"] == "kernel" and taps["sites"] == [
        f"L{i}.mamba2" for i in (1, 3, 5, 7)]
    assert plans["ssd"] == {
        "1x8192 64 heads of 64 over 8 groups of 128 states": {
            "form": "kernel", "chunk": 128, "chunks": 64,
            "chunks_a_group": 1, "edges_bytes": 64 * 64 * 64 * 128 * 4,
            "chunks_a_step": 2, "vmem_bytes": 10641408}}
    assert plans["recompute"]["blocks"]["L1"] == {
        "ssd.y": 8192 * 4096 * 4, "ssd.edges": 64 * 64 * 64 * 128 * 4}
    assert sorted(plans["recompute"]["blocks"]) == [
        f"L{i}" for i in range(9)]
    moe, = plans["moe"].items()
    assert "relu2" in moe[0] and moe[1]["form"] == "kernel" \
        and moe[1]["calls"] == 8 and len(moe[1]["layers"]) == 4
