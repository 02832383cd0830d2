"""PipelineMetrics series `queue_wait` over the window: the share of the
window the solver thread waited for a staged batch."""


def read(run):
    a, b = run["pipeline"]
    if "queue_wait" not in (b or {}):
        return None
    return 100.0 * (b["queue_wait"][0] - a.get("queue_wait", (0, 0))[0]) \
        / run["window_s"]
