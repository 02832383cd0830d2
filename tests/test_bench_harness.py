"""bench.py contract: one plain process that measures on a TPU or fails.

No chip -> non-zero exit and no record; an unknown device_kind or a
non-finite loss is an error; nothing is printed that this run did not
measure.  The subprocess tests drive `python bench.py`, the surface the
driver runs.
Reference perf-harness analog:
caffe-distri/src/test/java/com/yahoo/ml/jcaffe/PerfTest.java:69-118
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture()
def bench():
    sys.path.insert(0, REPO)
    try:
        import bench as mod
        yield mod
    finally:
        sys.path.remove(REPO)


def test_no_chip_exits_nonzero_and_prints_no_record():
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_unknown_device_kind_is_an_error(bench, monkeypatch):
    """A chip the peak table does not know must not be measured
    against somebody else's peak."""
    import jax

    class Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(ValueError, match="TPU v99"):
        bench.main()


def test_non_finite_loss_exits_nonzero(bench):
    bench._require_finite("losses", [6.9, 6.8])
    with pytest.raises(SystemExit) as e:
        bench._require_finite("losses", [6.9, float("nan")])
    assert e.value.code not in (0, None)
    assert "non-finite" in str(e.value.code)


def test_one_process_and_nothing_claimed():
    """The harness spawns nothing (a second process cannot get the
    chip) and carries no number from an earlier run."""
    src = open(BENCH).read()
    for word in ("subprocess", "multiprocessing", "--worker", "claimed",
                 "vs_baseline", "bench_evidence"):
        assert word not in src, word


def test_spark_tests_runner_always_writes_artifact(tmp_path):
    """spark_tests.py on the environment-gated legs: an artifact JSON
    is ALWAYS written, with per-test outcomes and the env facts that
    decide the gates (here: no pyspark -> the spark leg records honest
    skips, rc 1)."""
    out = tmp_path / "SPARK_TESTS_test.json"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "SPARK_TESTS_OUT": str(out),
                "SPARK_TESTS_LEGS": "spark",
                # roomy: in pyspark+JVM environments the real local[4]
                # leg (JVM startup + both analogs) far exceeds the
                # seconds the skip path needs here
                "SPARK_TESTS_TIMEOUT": "600"})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "spark_tests.py")],
        capture_output=True, text=True, timeout=640, env=env, cwd=REPO)
    assert out.exists(), (
        "runner died without writing the artifact:\n"
        f"stdout: {proc.stdout[-1500:]}\nstderr: {proc.stderr[-1500:]}")
    rec = json.loads(out.read_text())
    assert "spark" in rec["legs"]
    leg = rec["legs"]["spark"]
    assert leg.get("tests"), (
        "junit outcomes must be recorded; leg record: "
        f"{ {k: v for k, v in leg.items() if k != 'tail'} }\n"
        f"tail: {leg.get('tail', '')[-600:]}")
    assert "pyspark" in rec["env"] and "java" in rec["env"]
    has_spark = rec["env"]["pyspark"] and rec["env"]["java"]
    if not has_spark:       # this dev box: honest skip, nonzero exit
        assert proc.returncode == 1
        assert rec["ok"] is False
        assert all(t["outcome"] == "skipped" for t in leg["tests"])
    else:                   # docker/CI: the real proof must pass
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert rec["ok"] is True
