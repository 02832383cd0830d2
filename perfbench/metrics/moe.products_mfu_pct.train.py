"""The share of the chip's bf16 peak at which the expert layers' grouped
products run: the operations the rows actually held need, over the
device time of the program's scope `moe.products` and of the compiler's
`ragged-dot-*` calls (what `moe.products_device_ms.train` reads).

The work, counted as `step.mfu_pct.train` counts a step (forward once,
backward twice; the recomputed forward is time, not work): 3 x the mean
share of the k N assignments that fell on held experts (the window's
`moe_stats`) x k N x layers x the forward operations a held row costs.
Layers, k N and operations a row are the program's own plan
(`ops.layers.moe_plans`, written when the step was traced in this
process); None for a program without the scope or the plan.  The share
cannot pass 100 unless ops of the products are attributed elsewhere;
`run.py` ends a run whose `mfu` reads over 100 with an error, and this
reader hands it the number as it is."""

from perfbench.harness import scopes
from perfbench.harness.devices import peaks


def work_flops(run):
    """Operations a step's held rows need, or None."""
    share = (run.get("experts") or {}).get("held_share")
    try:
        from caffeonspark_tpu.ops.layers import moe_plans
    except ImportError:
        return None
    plans = moe_plans()
    if share is None or not plans:
        return None
    return 3 * share * sum(len(p["layers"]) * p["assignments"]
                           * p["row_flops"] for p in plans.values())


def read(run):
    if scopes.ms_per_step(run, r"moe\.products") is None \
            or not run.get("device"):
        return None
    ms = scopes.ms_per_step(run, r"moe\.products|ragged-dot-.*")
    flops = work_flops(run)
    if flops is None:
        return None
    peak = peaks(run["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * flops / run["ctx"]["chips"] / (ms * 1e-3) / peak
