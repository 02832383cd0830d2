"""Test harness config: force CPU with 8 virtual devices so multi-chip
sharding tests (Mesh/pjit/shard_map) run without TPU hardware, mirroring
SURVEY.md §4.4's guidance for the rebuild's CI."""

import os

# COS_TPU_TESTS=1 opts OUT of the CPU force so the on-chip suite
# (tests/test_pallas_tpu.py, tests/test_tpu_train.py) reaches the TPU.
if os.environ.get("COS_TPU_TESTS") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tpu():
    """Gate of the on-chip suite (test_pallas_tpu.py, test_tpu_train.py):
    skipped unless COS_TPU_TESTS=1; with it set, a missing TPU is a
    failure — an on-chip run that quietly skips proves nothing."""
    if os.environ.get("COS_TPU_TESTS") != "1":
        pytest.skip("on-chip suite: COS_TPU_TESTS=1 on a TPU machine")
    import jax
    if jax.default_backend() != "tpu":
        pytest.fail(f"COS_TPU_TESTS=1 but the backend is "
                    f"{jax.default_backend()!r}: this suite needs a TPU")
    return jax.devices()[0]


@pytest.fixture()
def recompile_guard():
    """A fresh RecompileGuard (analysis/runtime.py): watch jitted
    callables, mark_steady() once warm, and any further XLA compile
    raises RecompileError.  Teardown runs a final pull-style check so
    a recompile on the last call of a test still fails it."""
    from caffeonspark_tpu.analysis.runtime import RecompileGuard

    guard = RecompileGuard("pytest")
    yield guard
    guard.check()
