"""The flash kernels at the real shapes of the benchmark's two language
models, compiled for a described (not attached) TPU v5e: what interpret
mode cannot see — VMEM, tiling, the grouped block index maps.  PR 33
found here, before any chip time, that a 64-wide head is padded to 128
lanes in VMEM.  About two seconds a case; nothing runs.

The topology is described inside a fixture, never at import (only one
process may hold the TPU's library: `on-chip-measurement` guide,
section 2), and the compile is made in the test's own process."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


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


@pytest.mark.parametrize("b,h,hkv,t,d,dv", [
    (2, 32, 8, 4096, 64, 64),       # lfm2.train_packed8k: g = 4
    (1, 32, 8, 8192, 64, 64),       # the same at T = 8,192: VMEM (_lanes)
    (2, 32, 32, 4096, 192, 128),    # kanana2.train_packed4k: g = 1
])
def test_flash_forward_and_backward_compile_for_v5e(one_chip, b, h, hkv,
                                                    t, d, dv):
    from caffeonspark_tpu.ops.pallas_kernels import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 128, 128, False,
                                       jnp.bfloat16))

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
    # beyond 4,096 rows no call asks for more VMEM than the default
    # window: XLA lays the buffers it keeps across a Mosaic call as if
    # the call took 16 MiB, and a step of lfm2 whose kernels took 20-36
    # never ended on the chip (PR 33)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    windows = [int(n) for line in calls for n in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1",'
        r'"offset":"\d+","size":"(\d+)"', line)]
    # one call a kernel up to 4,096 rows; at 8,192 three pairs of
    # 4,096 forward and ten of 2,048 for each backward kernel
    assert len(calls) == (3 if t <= 4096 else 23)
    if t > 4096:        # a call at the default carries no window
        assert max(windows, default=0) <= 16 << 20, windows
    else:               # and these still ask, as they did
        assert windows
