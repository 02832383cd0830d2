"""One run of a cell as `perfbench/run.py` makes it, and beside its
result line what the per-layer readers are computed from: every
`PipelineMetrics` series between the window's edges and, for a traced
run, the program's `cos.*` spans thread by thread.

    python3 perfbench/tools/timeline.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

For looking at a cell by hand and for PERF.md's tables; the driver runs
`perfbench/run.py`, never this.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as R                        # noqa: E402


def report(run: dict) -> None:
    a, b = run["pipeline"]
    rows = {k: (round(b[k][0] - a.get(k, (0, 0))[0], 6),
                b[k][1] - a.get(k, (0, 0))[1], b[k][0], b[k][1])
            for k in sorted(b or {})}
    print(f"[timeline] window {run['window_s']:.4f} s, {run['steps']} "
          f"steps of {run['batch']}; series: seconds and samples inside "
          "the window, then since the job started")
    for k, (ds, dn, ts, tn) in rows.items():
        print(f"[timeline]   {k:<15} {ds:10.4f} s {dn:6d}   "
              f"{ts:10.4f} s {tn:6d}")
    print("[timeline] " + json.dumps({"series_in_window": {
        k: v[:2] for k, v in rows.items()}}))
    if not run.get("trace_dir"):
        return
    from perfbench.harness import spans as S
    devs = (run.get("trace") or {}).get("devices") or {}
    lo, hi = (devs[sorted(devs)[0]]["window"] if devs
              else (float("-inf"), float("inf")))
    spans = [s for s in S.of_run(run) if s[3] > lo and s[2] < hi]
    print("[timeline] cos.* spans that touch the traced window:")
    S.print_by_thread(spans, indent="[timeline]   ")
    if devs and spans:
        # the breakdown's own rule (each idle gap goes whole to the span
        # that covers most of it), over the solver thread's cos.* spans:
        # comparable with its wait_for_batch / dispatch; the metric
        # device.idle_in_queue_wait_pct is the exact overlap instead
        from perfbench.harness import trace as tr
        solver = {s[1] for s in spans if s[0] == "queue_wait"}
        gaps = tr.idle_gaps(devs[sorted(devs)[0]]["busy"], lo, hi, sorted(
            ((n, b, e) for n, line, b, e, _ in spans if line in solver),
            key=lambda t: t[1]))
        print("[timeline] idle gaps by the solver thread's cos.* span "
              f"covering most of each: {json.dumps(gaps)}")


def main(argv=None) -> int:
    seen = {}
    read_metric = R.read_metric

    def keep_run(name, run):
        seen["run"] = run
        return read_metric(name, run)

    R.read_metric = keep_run
    rc = R.main(argv)
    if "run" in seen:
        report(seen["run"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
