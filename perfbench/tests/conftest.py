"""The benchmark's own tests run on the CPU: four virtual devices, Pallas
kernels in interpret mode.  They print no device metric."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("COS_FLASH_INTERPRET", "1")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the rehearsals persist every program they compile; kept out of the
# program's own <repo>/.jax_cache, which the repo's tier-1 tests share (a
# tier-1 run over a cache the rehearsals had filled hung twice, PR 23)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    ROOT, ".perfbench_work", "jax_cache_tests"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
