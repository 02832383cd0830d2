#!/bin/bash
# Local TPU-host environment bring-up — the analog of the reference's
# scripts/local-setup-hadoop.sh + local-setup-spark.sh (which download
# and configure the single-node runtime the driver needs).  A TPU-VM
# needs no Hadoop/Spark daemons: this script prepares the pieces the
# trainer actually uses — the persistent XLA compilation cache, the
# native decode library, and (optionally) a virtual-device CPU mesh for
# development boxes without a chip.
#
# Usage:  source scripts/local-setup-tpu.sh [ndev]
#   ndev   optional: set up an ndev-device *virtual CPU* mesh instead
#          of real TPU devices (for laptops/CI; e.g. `source ... 8`)

# No `set -e`: this script is sourced, and errexit would persist into
# (and can abort) the user's interactive shell.  Failures are handled
# per-command below instead.

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"

# 1. persistent XLA compilation cache: JAX reads this variable itself
#    (unset, the package keeps its cache in <repo>/.jax_cache)
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$HOME/.cache/cos_tpu_xla}"
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

# 2. native decode/transform library (threaded libjpeg pipeline)
if [ ! -f "$REPO/caffeonspark_tpu/native/libcos_native.so" ]; then
    (cd "$REPO" && make -s native 2>/dev/null) \
        && echo "built libcos_native.so" \
        || echo "WARN: native build failed — cv2 fallback will be used"
fi

# 3. virtual mesh for development without a chip
if [ -n "$1" ]; then
    export JAX_PLATFORMS=cpu
    export XLA_FLAGS="--xla_force_host_platform_device_count=$1 ${XLA_FLAGS}"
    echo "virtual CPU mesh: $1 devices (JAX_PLATFORMS=cpu)"
fi

export PYTHONPATH="$REPO:${PYTHONPATH}"
echo "caffeonspark_tpu env ready (repo: $REPO, cache: $JAX_COMPILATION_CACHE_DIR)"
echo "try: python -m caffeonspark_tpu.mini_cluster -conf <solver.prototxt>"
