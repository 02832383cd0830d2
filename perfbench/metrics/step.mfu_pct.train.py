"""One device's share of the step's FLOPs over its busy time, against the
chip's bf16 peak: utilisation while the device is busy, not the job's.
FLOPs: reference/common.forward_flops x 3, from shapes."""

from perfbench.harness.devices import peaks


def read(run):
    t = run.get("trace")
    if not t or not run["steps"] or not run["device"]:
        return None
    per_device = run["flops_per_step"] / run["ctx"]["chips"]
    busy_per_step = t["busy_s"] / run["steps"]
    peak = peaks(run["device"]["kind"])["bf16_tflops"] * 1e12
    return 100.0 * per_device / busy_per_step / peak
