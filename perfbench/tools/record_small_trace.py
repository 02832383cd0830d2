"""Record the small trace that tests/test_trace.py reduces: a few jitted
matmuls with idle gaps between them and the harness's span names around
them.  Run on the chip; writes tests/data-style output under the given
directory."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation


def main(out):
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = os.path.join(out, "tmp_trace")
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("perfbench.window"):
        for _ in range(4):
            with TraceAnnotation("perfbench.dispatch"):
                y = f(x)
            with TraceAnnotation("perfbench.wait_for_batch"):
                y.block_until_ready()
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    shutil.rmtree(tmp)
    print("recorded", os.path.getsize(os.path.join(out, "small.xplane.pb")),
          "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
