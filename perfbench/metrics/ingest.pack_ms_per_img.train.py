"""PipelineMetrics series `pack` over the window: pool-thread seconds
spent decoding / cropping / packing, per image packed."""


def read(run):
    a, b = run["pipeline"]
    if "pack" not in (b or {}):
        return None
    total = b["pack"][0] - a.get("pack", (0, 0))[0]
    count = b["pack"][1] - a.get("pack", (0, 0))[1]
    return 1e3 * total / (count * run["batch"]) if count else None
