"""The convolution stage of the Gated DeltaNet and Mamba layers,
`layers.causal_taps_silu` = silu(causal_taps(z[..., :C], taps) [+ bias]),
in its two forms: the Mosaic kernels `cos_taps_fwd` / `cos_taps_bwd`
(here in interpret mode) against the XLA form, value and every
gradient; the halo between two time tiles, forward and backward; zero
before t = 0; what sends a shape to the XLA form; what `info.taps`
and the job's `info.taps` say."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from caffeonspark_tpu.ops import layers as L
from caffeonspark_tpu.ops import pallas_kernels as pk
from caffeonspark_tpu.ops import route

TAPS = 4


def inputs(t, b, w, c, bias, seed=0, taps=TAPS):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (t, b, w), jnp.float32),
            0.5 * jax.random.normal(k[1], (c, taps), jnp.float32),
            0.5 * jax.random.normal(k[2], (c,), jnp.float32) if bias
            else None,
            jax.random.normal(k[3], (t, b, c), jnp.float32))


def kernels(z, taps, bias, **tiles):
    plan = dict(pk.taps_plan(z.shape[0], taps.shape[0], z.shape[2],
                             taps.shape[1]), **tiles)
    return pk.causal_taps_silu_kernels(z, taps, bias, plan, interpret=True)


def value_and_grads(f, z, taps, bias, dy):
    """f's value and its gradients in z, taps and (if any) bias under
    the cotangent dy."""
    args = (z, taps) + (() if bias is None else (bias,))

    def loss(*a):
        y = f(*a) if bias is not None else f(*a, None)
        return jnp.sum(y * dy), y

    grads, y = jax.grad(loss, argnums=tuple(range(len(args))),
                        has_aux=True)(*args)
    return y, grads


def close(got, want, name, rtol=2e-6):
    """Within float32 rounding of sums of products of this size."""
    np.testing.assert_allclose(got, want, rtol=0, err_msg=name,
                               atol=rtol * max(1.0, np.abs(want).max()))


# T = 16: one time tile; 48 in three tiles of 16; 136: one tile of four
# row groups and a short fifth; 1024: two of the tiles the cells run
@pytest.mark.parametrize("t,tiles", [(16, {}), (48, {"time_tile": 16}),
                                     (136, {}), (1024, {})],
                         ids=["T16", "T48", "T136", "T1024"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("bias", [False, True], ids=["plain", "bias"])
@pytest.mark.parametrize("w,c", [(128, 128), (384, 256)],
                         ids=["whole", "slice"])
def test_kernels_equal_the_xla_form(t, tiles, b, bias, w, c):
    """Value and the gradients in the input, the taps and the bias, the
    input the first C channels of a wider array or all of it."""
    z, taps, bv, dy = inputs(t, b, w, c, bias, seed=t + b)
    got, gg = value_and_grads(
        lambda *a: kernels(*a, **tiles), z, taps, bv, dy)
    want, gw = value_and_grads(L.causal_taps_silu_xla, z, taps, bv, dy)
    assert got.shape == (t, b, c)
    close(got, want, "y")
    assert len(gg) == 2 + bias
    for name, a, r in zip(("dz", "dtaps", "dbias"), gg, gw):
        assert a.shape == r.shape, name
        close(a, r, name, rtol=2e-6 * (1 if name == "dz" else t ** 0.5))
    # nothing flows into the channels the stage does not read
    assert not np.asarray(gg[0])[..., c:].any()
    assert np.abs(np.asarray(gg[0])[..., :c]).min() > 0


def test_channel_tiles_follow_the_batch_column():
    """Two batch columns of three channel tiles each, out of a wider
    array of five: every (column, tile) reads its own lanes."""
    z, taps, bv, dy = inputs(32, 2, 640, 384, True, seed=9)
    plan = pk.taps_plan(32, 384, 640, TAPS)
    assert plan == {"time_tile": 32, "channel_tile": 128}
    got, gg = value_and_grads(kernels, z, taps, bv, dy)
    want, gw = value_and_grads(L.causal_taps_silu_xla, z, taps, bv, dy)
    close(got, want, "y")
    for name, a, r in zip(("dz", "dtaps", "dbias"), gg, gw):
        close(a, r, name, rtol=2e-5)


@pytest.mark.parametrize("t,b", [(48, 1), (32, 2)])
def test_channels_read_from_an_offset_where_they_lie(t, b):
    """`first`: the C channels start at a whole tile inside the wider
    array (a Mamba-2 layer's [z | xBC]: xBC behind z): every (column,
    tile) reads its own lanes, value and gradients equal the XLA form's,
    and nothing flows into the channels before or after."""
    w, c, first = 640, 256, 128
    z, taps, bv, dy = inputs(t, b, w, c, True, seed=t)
    plan = pk.taps_plan(t, c, w, TAPS, first)
    assert plan == {"time_tile": t, "channel_tile": 128}
    got, gg = value_and_grads(
        lambda *a: pk.causal_taps_silu_kernels(
            *a, dict(plan, time_tile=16), interpret=True, first=first),
        z, taps, bv, dy)
    want, gw = value_and_grads(
        lambda *a: L.causal_taps_silu_xla(*a, first), z, taps, bv, dy)
    close(got, want, "y")
    close(want, L.causal_taps_silu_xla(z[..., first:], taps, bv), "slice")
    for name, a, r in zip(("dz", "dtaps", "dbias"), gg, gw):
        close(a, r, name, rtol=2e-5)
    dz = np.asarray(gg[0])
    assert not dz[..., :first].any() and not dz[..., first + c:].any()
    assert np.abs(dz[..., first:first + c]).min() > 0
    # where the channels start has to be a whole tile, and they have to fit
    assert pk.taps_plan(t, c, w, TAPS, 64) is None
    assert pk.taps_plan(t, c, w, TAPS, 512) is None
    assert pk.taps_plan(8192, 6144, 10240, 4, 4096) == {
        "time_tile": 512, "channel_tile": 512}


@pytest.mark.parametrize("tile", [8, 16, 64])
def test_an_impulse_crosses_the_tile_edge_both_ways(tile):
    """A unit impulse in the last row of a time tile shows in that row
    and the next tile's first L - 1 rows, tap by tap; a unit cotangent in
    the first row of a tile reaches the L - 1 rows above it, in the tile
    before: the mirror image."""
    t, c = 4 * tile, 128
    taps = jnp.arange(1.0, 1.0 + c * TAPS).reshape(c, TAPS) / (c * TAPS)
    edge = 2 * tile             # the first row of the third tile
    z = jnp.zeros((t, 1, c)).at[edge - 1].set(1.0)
    pre = np.zeros((t, c), np.float32)
    for k in range(TAPS):       # the row k steps later reads tap L - 1 - k
        pre[edge - 1 + k] = np.asarray(taps[:, TAPS - 1 - k])
    got = np.asarray(kernels(z, taps, None, time_tile=tile))[:, 0]
    np.testing.assert_allclose(got, pre / (1.0 + np.exp(-pre)), rtol=1e-6)
    assert (got[edge:edge + TAPS - 1] > 0).all() and not got[:edge - 1].any()
    # backward: at z = 0 the pre-activation is 0 and silu'(0) = 1/2
    dy = jnp.zeros((t, 1, c)).at[edge].set(1.0)
    dz = np.asarray(jax.vjp(lambda z: kernels(z, taps, None,
                                              time_tile=tile),
                            jnp.zeros((t, 1, c)))[1](dy)[0])[:, 0]
    want = np.zeros((t, c), np.float32)
    for k in range(TAPS):       # the row k steps earlier, through tap L-1-k
        want[edge - k] = 0.5 * np.asarray(taps[:, TAPS - 1 - k])
    np.testing.assert_allclose(dz, want, rtol=1e-6)
    assert (dz[edge - TAPS + 1:edge] > 0).all() and not dz[edge + 1:].any()


def test_rows_before_the_first_read_as_zero():
    """The first L - 1 rows see only the taps that reach a row >= 0 (the
    block above the first tile is the tile's own first rows: masked),
    and the last rows' gradient only the rows that exist."""
    t, c = 32, 128
    z, taps, _, _ = inputs(t, 1, c, c, False, seed=5)
    z = z + 3.0         # nothing near zero
    got = np.asarray(kernels(z, taps, None, time_tile=16))[:, 0]
    a, w = np.asarray(z)[:, 0], np.asarray(taps)
    for row in range(TAPS - 1):
        pre = sum(w[:, TAPS - 1 - k] * a[row - k] for k in range(row + 1))
        np.testing.assert_allclose(got[row], pre / (1 + np.exp(-pre)),
                                   rtol=2e-6, atol=1e-6)
    dz = jax.grad(lambda z: jnp.sum(kernels(z, taps, None, time_tile=16)))
    want = jax.grad(lambda z: jnp.sum(L.causal_taps_silu_xla(z, taps)))
    close(dz(z)[-TAPS:], want(z)[-TAPS:], "dz, last rows")


@pytest.mark.parametrize("taps", [1, 2, 3])
def test_fewer_taps(taps):
    z, w, bv, dy = inputs(24, 1, 128, 128, True, seed=taps, taps=taps)
    got, gg = value_and_grads(kernels, z, w, bv, dy)
    want, gw = value_and_grads(L.causal_taps_silu_xla, z, w, bv, dy)
    close(got, want, "y")
    for name, a, r in zip(("dz", "dtaps", "dbias"), gg, gw):
        close(a, r, name, rtol=1e-5)


def test_the_cells_shapes_plan_to_whole_tiles():
    assert pk.taps_plan(8192, 8192, 12288, 4) == {
        "time_tile": 512, "channel_tile": 512}      # qwen3next
    assert pk.taps_plan(8192, 5120, 10240, 4) == {
        "time_tile": 512, "channel_tile": 512}      # phi4flash
    assert pk.taps_plan(640, 128, 128, 4)["time_tile"] == 128
    assert pk.taps_plan(520, 128, 128, 4) is None       # tiles of 8 rows
    # a call's blocks, twice each, and its scratch, inside the default
    # VMEM window (the backward call, the larger)
    tile = 512 * 512 * 4
    assert pk._flash_window(2 * 3 * tile + tile + 8 * tile // 16) \
        <= pk._SCOPED_VMEM


@pytest.mark.parametrize("why,form", [
    ("tiles", "kernel"), ("channels", "xla"), ("width", "xla"),
    ("time", "xla"), ("short_tiles", "xla"), ("bfloat16", "xla"),
    ("taps", "xla"), ("mesh", "xla"), ("cpu", "xla")])
def test_the_form_follows_what_can_be_observed(monkeypatch, why, form):
    """`causal_taps_silu` under COS_FLASH_INTERPRET=1: the kernels where
    the shape tiles; the XLA form where the channels or the wide array
    are not whole 128-lane tiles, T is not whole sublane groups or only
    in tiles of a few rows, the input is not float32, the taps reach further back than the halo, a
    mesh of several devices is installed, or neither a TPU nor interpret
    mode is there.  `info.taps` says which, with the tiles and the
    call site, and the value is the XLA form's either way."""
    t, w, c, n, dtype, ctx = 32, 256, 128, TAPS, jnp.float32, None
    if why != "cpu":
        monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    if why == "channels":
        c = 96
    elif why == "width":
        w = 200
    elif why == "time":
        t = 30
    elif why == "short_tiles":
        t = 1000            # 125 tiles of 8 rows
    elif why == "bfloat16":
        dtype = jnp.bfloat16
    elif why == "taps":
        n = 10
    elif why == "mesh":
        from caffeonspark_tpu.parallel.mesh import build_mesh
        ctx = route.flash_mesh(build_mesh(dp=1, sp=2,
                                      devices=jax.devices()[:2]))
    z, taps, bias, _ = inputs(t, 1, w, c, True, taps=n)
    z = z.astype(dtype)
    route.forget("taps")
    if ctx is None:
        got = L.causal_taps_silu(z, taps, bias, site="L0.op")
    else:
        with ctx:
            got = L.causal_taps_silu(z, taps, bias, site="L0.op")
    (key, plan), = route.plans()["taps"].items()
    assert key == (f"1x{t} {c} of {w} channels {n} taps "
                   f"{jnp.dtype(dtype).name} bias")
    tiles = {"time_tile": 32, "channel_tile": 128} if form == "kernel" \
        else {}
    assert plan == {"form": form, "sites": ["L0.op"], **tiles}
    want = L.causal_taps_silu_xla(z, taps, bias)
    assert got.dtype == want.dtype
    if form == "xla":
        np.testing.assert_array_equal(got, want)
    else:
        close(got, want, "y")
    # a second site of the same shape joins the entry; a second trace of
    # the first adds nothing
    L.causal_taps_silu(z, taps, bias, site="L1.op")
    L.causal_taps_silu(z, taps, bias, site="L0.op")
    assert route.plans()["taps"][key]["sites"] == ["L0.op", "L1.op"]


def test_train_job_reports_info_taps(monkeypatch):
    """What the convolution stages were lowered to rides in the metrics
    the -train job prints at shutdown, as `info.taps`, through the route
    of `info.ssm` and `info.gdn`."""
    from caffeonspark_tpu.metrics import PipelineMetrics
    from caffeonspark_tpu.processor import CaffeProcessor

    class Job:
        metrics = PipelineMetrics()

    route.forget("taps")
    CaffeProcessor._note_lowering_plans(Job)
    assert "taps" not in Job.metrics.summary().get("info", {})
    monkeypatch.setenv("COS_FLASH_INTERPRET", "1")
    z, taps, _, _ = inputs(1024, 1, 384, 256, False)
    jax.eval_shape(lambda z, w: L.causal_taps_silu(z, w, site="L0.gdn"),
                   z, taps)
    CaffeProcessor._note_lowering_plans(Job)
    assert Job.metrics.summary()["info"]["taps"] == {
        "1x1024 256 of 384 channels 4 taps float32": {
            "form": "kernel", "time_tile": 512, "channel_tile": 128,
            "sites": ["L0.gdn"]}}
