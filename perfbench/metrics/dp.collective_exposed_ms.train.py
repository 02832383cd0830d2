"""The part of dp.collective_ms.train during which no other op runs on
that device: what the exchange adds to the step."""


def read(run):
    t = run.get("trace")
    if not t or not run["steps"] or "collective_exposed_s" not in t:
        return None
    return 1e3 * t["collective_exposed_s"] / run["steps"]
