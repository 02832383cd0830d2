"""The reduction from a trace to busy time, collectives, ops and gaps."""

import os

import pytest

from perfbench.harness import trace as tr

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def synthetic():
    ops = [("fusion.1", 0.0, 1.0), ("all-reduce-start.1", 1.0, 1.1),
           ("convolution.2", 1.1, 2.0), ("all-reduce-done.1", 2.0, 2.5),
           ("fusion.1", 4.0, 5.0)]
    spans = [("window", 0.0, 6.0), ("dispatch", 0.0, 0.2),
             ("wait_for_batch", 0.2, 3.9), ("dispatch", 3.9, 4.1),
             ("wait_for_batch", 4.1, 6.0)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules":
                                          [("jit_step", 0.0, 2.5),
                                           ("jit_step", 4.0, 5.0)]}},
            "spans": sorted(spans, key=lambda s: s[1])}


def test_interval_arithmetic():
    assert tr.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    assert tr.total(tr.subtract([(0, 10)], [(1, 2), (3, 5)])) == 7
    assert tr.subtract([(0, 2), (3, 4)], [(1, 3.5)]) == [(0, 1), (3.5, 4)]


def test_reduce_synthetic():
    r = tr.reduce(synthetic())
    assert r["window_s"] == 6.0
    assert r["busy_s"] == pytest.approx(3.5)
    # the exchange runs from 1.0 to 2.5; the convolution hides 0.9 s of it
    assert r["collective_s"] == pytest.approx(1.5)
    assert r["collective_exposed_s"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ("fusion.1", pytest.approx(2.0))
    gaps = dict(r["idle_gaps"])
    assert gaps["wait_for_batch"] == pytest.approx(2.5)
    assert r["devices"]["/device:TPU:0"]["modules"] == 2


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_reduce_recorded_trace():
    """Recorded on a TPU v5e by tools/record_small_trace.py: four
    1024^3 bf16 matmuls, 10 ms of host sleep after each."""
    r = tr.reduce(tr.load(SMALL))
    assert len(r["devices"]) == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] > 0.04                  # four sleeps of 10 ms
    assert r["busy_s"] < 0.01                    # four ~11 us matmuls
    assert dict(r["idle_gaps"]).get("wait_for_batch", 0) > 0.03
    assert r["collective_s"] == 0
