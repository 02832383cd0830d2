"""Of the first device's idle time in the traced window, the share that
lies inside the solver thread's own `cos.queue_wait` spans: the program's
spans put against the device's clock."""

from perfbench.harness import spans


def read(run):
    t = run.get("trace")
    if not t or not t.get("devices"):
        return None
    first = t["devices"][sorted(t["devices"])[0]]
    share = spans.idle_share_inside(first["busy"], first["window"],
                                    spans.of_run(run), "queue_wait")
    return None if share is None else 100.0 * share
