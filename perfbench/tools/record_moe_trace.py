"""Record the small trace that tests/test_moe_readers.py reads: two
gradient steps through one dropless expert layer of the program (2,048
tokens, top 2 of 8 experts, 2 held: three passes of 1,536 sorted rows,
of which an even router fills one) under a block's recomputation, so
that the file holds the scan's `while`, the passes' conditionals and
the ops inside them as the device's "XLA Ops" line lays them out.  Run
on the chip; writes `moe_small.xplane.pb` under the given directory and
prints what `tools/scope_tree.py` makes of it."""

import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

N, D, HIDDEN = 2048, 128, 128


def main(out):
    import jax
    import jax.numpy as jnp

    from caffeonspark_tpu.ops import layers as L
    from caffeonspark_tpu.proto import LayerParameter
    from perfbench.harness import opmeta, trace as tr
    from perfbench.tools import scope_tree

    lp = LayerParameter.from_text(f'''
      name: "L1.moe" type: "MixtureOfExperts" bottom: "x" top: "y"
      moe_param {{ num_experts: 8 hidden_dim: {HIDDEN} top_k: 2
        dispatch: "dropless" scoring: "sigmoid" gated: true
        shared_hidden_dim: {HIDDEN} experts_held: 2 }}''')
    keys = jax.random.split(jax.random.key(0), 16)
    params = [0.05 * jax.random.normal(k, s, jnp.float32) for k, (_, s, _)
              in zip(keys, L._moe_params(lp, [(N, D)]))]
    x = jax.random.normal(jax.random.key(1), (N, D), jnp.float32)
    op = L.get_op("MixtureOfExperts")

    @jax.checkpoint
    def block(a, p):
        with jax.named_scope(lp.name):
            return op.apply(L.Ctx(train=True), lp, p, [a])[0]

    step = jax.jit(jax.grad(lambda a, p: jnp.sum(jnp.sin(block(a, p))),
                            argnums=(0, 1)))
    jax.block_until_ready(step(x, params))
    tmp = os.path.join(out, "tmp_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # the device's lines are wanted
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(2):
        jax.block_until_ready(step(x, params))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "moe_small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print("recorded", os.path.getsize(dst), "bytes;", L.moe_plans())
    ops = next((o for _, o in sorted(opmeta.device_ops(dst).items()) if o),
               None)
    if ops is None:
        sys.exit("the trace holds no device plane: not recorded on a chip")
    window = (min(o[1] for o in ops), max(o[2] for o in ops))
    for line in scope_tree.render(scope_tree.tree(ops, window), 2,
                                  min_ms=0.0):
        print(line)
    planes = tr.load(dst)["devices"]
    named = next(tr.op_events(planes[p]) for p in sorted(planes)
                 if tr.op_events(planes[p]))
    print("loops", scope_tree.loops(named, ops, window))


if __name__ == "__main__":
    main(sys.argv[1])
