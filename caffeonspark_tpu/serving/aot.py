"""AOT warm start: persistent compilation cache for serving warmup.

Elastic scale-up is only real if a fresh replica is serving in
seconds, and the dominant cost of a cold replica is XLA compiling the
bucket programs (`InferenceService.warmup` compiles
log2(max_batch)+1 of them; a big net on TPU pays tens of seconds
each).  The fix is the persistent compilation cache every entry
point already enables (`utils/compile_cache.py`): on shared storage, a
replica whose (program, compile options) were compiled by ANY earlier
replica warms up on deserialized executables — cache hits, zero fresh
compiles (`RecompileGuard`-verifiable).

Cache layout: one subdirectory per serving identity, named by a
digest of (net topology, bucket set, served blobs) —
``<COS_AOT_CACHE_DIR>/aot-<digest>``.  JAX's own cache key (HLO +
compile options + backend) already guarantees correctness; the
namespace exists so operators can prune per-model and so the tests
can count one model's entries in isolation.  The digest deliberately
EXCLUDES the param values and the model version: forward programs are
params-agnostic (`BlobForward`), so every version of one net shares
one program set — that sharing is what makes rolling hot-swap free
and it would be thrown away by a version-keyed cache.

Knob: COS_AOT_CACHE_DIR (unset = the process-wide cache of
`utils/compile_cache.py`, no per-model namespace).  Where
`JAX_COMPILATION_CACHE_DIR` is set the environment has placed the
cache: the namespace is not used, nothing is re-pointed or reset.
"""

from __future__ import annotations

import hashlib
import logging
import os
from typing import Optional, Sequence

_LOG = logging.getLogger(__name__)


def aot_cache_root() -> str:
    """COS_AOT_CACHE_DIR: root under which per-model namespaces live
    ('' = AOT warm start disabled)."""
    return os.environ.get("COS_AOT_CACHE_DIR", "")


def aot_cache_key(net_param, buckets: Sequence[int],
                  blob_names: Sequence[str],
                  mesh_sig: Optional[str] = None,
                  weight_dtype: Optional[str] = None) -> str:
    """Digest of the serving identity that determines the compiled
    program set: net topology + bucket shapes + served blobs + mesh
    topology/sharding layout (`MeshLayout.signature()`; None =
    single-device) + quantized-residency storage dtype
    (COS_SERVE_WEIGHT_DTYPE; None/"f32" adds nothing, so every
    pre-quantization namespace digest is unchanged).  A bf16/int8
    resident program traces a DIFFERENT body (dequant at entry /
    int8 MXU kernel) over extra scale operands — sharing the f32
    namespace would make each regime count the other's entries as its
    own.  Params and model version stay excluded on purpose (see
    module docstring): every VERSION of one (net, dtype) regime still
    shares one program set — that sharing is what keeps hot-swap and
    LRU page-in recompile-free."""
    h = hashlib.sha256()
    h.update(str(net_param).encode())
    h.update(repr(tuple(buckets)).encode())
    h.update(repr(tuple(blob_names)).encode())
    h.update(repr(mesh_sig).encode())
    if weight_dtype not in (None, "f32"):
        h.update(repr(weight_dtype).encode())
    return h.hexdigest()[:16]


def resolve_cache_dir(net_param, buckets: Sequence[int],
                      blob_names: Sequence[str],
                      root: Optional[str] = None,
                      mesh_sig: Optional[str] = None,
                      weight_dtype: Optional[str] = None
                      ) -> Optional[str]:
    root = aot_cache_root() if root is None else root
    if not root:
        return None
    return os.path.join(root,
                        "aot-" + aot_cache_key(net_param, buckets,
                                               blob_names, mesh_sig,
                                               weight_dtype))


def enable_aot_cache(cache_dir: str) -> bool:
    """Point JAX's persistent compilation cache at `cache_dir`; False
    (and nothing touched) when the environment placed the cache.  Must
    run before the first trace of the programs it should capture (the
    serving path calls it before warmup).  min_compile_time 0 /
    min_entry_size -1 persist even the fast CPU compiles — the CI box
    is where the warm-start tests prove the mechanism the TPU path
    relies on."""
    from ..utils.compile_cache import CACHE_ENV, placed_by_env
    if placed_by_env():
        _LOG.info("%s is set: serving compiles into it, the AOT "
                  "namespace %s is not used", CACHE_ENV, cache_dir)
        return False
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache binds its directory lazily at the FIRST compile and
    # then never re-reads the config — and model/param loading already
    # compiled small host programs by the time serving configures the
    # dir, so without a reset the warmup programs silently skip the
    # cache (observed: zero entries written)
    compilation_cache.reset_cache()
    _LOG.info("AOT compilation cache at %s", cache_dir)
    return True


def cache_entries(cache_dir: str) -> int:
    """Number of serialized executables in the namespace (the
    `*-cache` files jax writes; `-atime` sidecars excluded).  A warm
    replica's warmup adds ZERO entries — every program deserializes —
    which is the timing-free cache-hit proof the fleet tests use."""
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if n.endswith("-cache"))
    except OSError:
        return 0
