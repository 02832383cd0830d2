# Build + test pipeline (reference `Makefile:19-27` analog: build ->
# native lib -> tests -> python tests; here the "build" is the native
# decode library plus an editable install).

PY ?= python
CPU_ENV = env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: build native install lint test test-slow spark-test bench \
  chip-smoke onchip-suite bench-ingest bench-steploop \
  bench-serving bench-serving-sharded bench-serving-multimodel \
  bench-serving-pp \
  bench-gradsync bench-syncmode bench-scaling bench-autotune \
  bench-deploy \
  bench-obs bench-tail bench-prodday prodday-smoke chaos \
  bench-autoscale \
  chaos-deploy docs clean

build: native install

native:
	$(MAKE) -C caffeonspark_tpu/native

install:
	$(PY) -m pip install -e . --no-deps --no-build-isolation

# coslint (JAX/concurrency rules COS001..COS005, see
# docs/architecture.md "Correctness tooling") against the checked-in
# zero-findings baseline, then ruff (pyflakes + import hygiene,
# [tool.ruff] in pyproject.toml) when the container has it — the
# minimal test image does not, and the tier-1 gate must not depend on
# an installer
lint:
	$(PY) -m caffeonspark_tpu.analysis \
	  --baseline artifacts/coslint_baseline.json
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check caffeonspark_tpu tests scripts; \
	else \
	  echo "lint: ruff not installed — coslint only (ruff config" \
	       "lives in pyproject.toml [tool.ruff])"; \
	fi

# tier-1 shape: slow/e2e tests (subprocess fleets, offline-hanging
# gcsfs, minute-long zoo compiles) run via `make test-slow`, not here
test:
	$(CPU_ENV) $(PY) -m pytest tests/ -x -q -m "not slow"

test-slow:
	$(CPU_ENV) $(PY) -m pytest tests/ -q -m "slow"

# real-SparkContext leg (needs pyspark + a JVM) + the multicore 1F1B
# wall-clock leg (needs >=4 cores): InterleaveTest / PythonApiTest
# analogs at local[4].  ALWAYS writes SPARK_TESTS_r05.json with
# per-test outcomes + env fingerprint so runs
# in docker/CI leave committable proof
spark-test:
	$(CPU_ENV) $(PY) spark_tests.py

# on a machine with a TPU (one process holds the chip; the compile
# cache goes where JAX_COMPILATION_CACHE_DIR says, else <repo>/.jax_cache)
bench:
	$(PY) bench.py

chip-smoke:
	$(PY) chip_smoke.py

onchip-suite:
	COS_TPU_TESTS=1 $(PY) -m pytest tests/test_pallas_tpu.py \
	  tests/test_tpu_train.py

# inline vs pipelined ingest comparison on CPU; JSON artifact with
# per-stage (queue-wait / pack / stage / step) timings
bench-ingest:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_ingest.py --quick \
	  --out bench_evidence/bench_ingest_quick.json

# fused multi-step loop (COS_STEPS_PER_LOOP): K=1 vs K=8/32 with the
# 45 ms per-dispatch floor recipe (best-of-N, pinned single-thread);
# JSON artifact embeds the per-stage chunk timeline + floor=0 control
bench-steploop:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_steploop.py \
	  --out bench_evidence/bench_steploop.json

# gradient exchange: COS_GRAD_SYNC default vs bucket/quant/hier under
# the injected per-byte cross-host comm floor (best-of-N, pinned
# single-thread); JSON artifact embeds the comm plan + floor=0 control
bench-gradsync:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_gradsync.py \
	  --out bench_evidence/bench_gradsync.json

# sync modes under an injected 5x-slow rank: rank-0 steps/s for
# lockstep vs local_sgd vs async (straggler-tolerance sweep), with a
# no-straggler control; ALWAYS exits 0 with one JSON document on
# stdout
bench-syncmode:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_syncmode.py \
	  --out bench_evidence/bench_syncmode.json

# multi-host scaling: 4 NodeAgent daemons each spawning 2 ranks of an
# 8-process cluster (coordinator via agent:// rendezvous), two-tier
# hier vs flat bucket exchange under the calibrated asymmetric comm
# floor (gigabit prices time-dilated to this box's base step), with a
# floor=0 rate-equality control; ALWAYS exits 0 with one JSON document
# on stdout
bench-scaling:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_scaling.py \
	  --out bench_evidence/bench_scaling.json

# per-layer autotuner: untuned vs COS_AUTOTUNE plan on the worst-MFU
# zoo net (googlenet) under the injected HBM-bandwidth floor; the
# chosen plan is cached under artifacts/autotune and embedded in the
# artifact (with a floor=0 control); ALWAYS exits 0 with one JSON
# document on stdout
bench-autotune:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_autotune.py \
	  --out bench_evidence/bench_autotune.json

# chaos drills: the fault-injection test suite (kill-rank / slow-rank
# / flaky-exchange / flaky-storage under each sync mode, supervisor
# elastic relaunch + bad-snapshot fallback) — subprocess-heavy, so
# they carry the `chaos` marker and stay out of tier-1
chaos:
	$(CPU_ENV) $(PY) -m pytest tests/ -q -m "chaos"

# continuous-deployment chaos drills only: canary accept/reject e2e,
# canary SIGKILL mid-eval -> aborted, truncated-snapshot fallback,
# mid-roll replica kill -> auto-rollback, kill-mid-save atomicity
chaos-deploy:
	$(CPU_ENV) $(PY) -m pytest tests/test_deploy.py \
	  tests/test_checkpoint.py -q -m "chaos"

# continuous deployment: N fine-tune rounds through the canary gate
# with one injected-regression round (label-shuffled -> rejected) and
# one injected-crash round (mid-roll replica kill -> auto-rollback,
# incumbent byte-identical) under constant background client load;
# ALWAYS exits 0 with one JSON document on stdout
bench-deploy:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_deploy.py \
	  --out bench_evidence/bench_deploy.json

# observability overhead: tracing at sample 1.0 + JSONL spool +
# armed flight recorder + periodic metrics flush vs the off-config,
# measured as adjacent alternating windows on ONE warm stack (median
# of per-pair ratios — this box's CPU share swings would swamp an
# off-then-on sequence); gate <3% on serving rows/s AND training
# steps/s; ALWAYS exits 0 with one JSON document on stdout
bench-obs:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_obs.py \
	  --out bench_evidence/bench_obs.json

# tail latency: the straggler drill (no-straggler control vs
# COS_FAULT_REPLICA_SLOW cliff vs hedged-requests recovery, measured
# at client p99.9) and the zipf cache replay (content-hash response
# cache + in-flight coalescing vs the cache-off wire at ~0.8 hit
# rate); ALWAYS exits 0 with one JSON document on stdout
bench-tail:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_tail.py \
	  --out bench_evidence/bench_tail.json

# production-day replay: checked-in scenarios (scenarios/*.json)
# through the prodday harness — compressed day with scheduled chaos
# against the full deploy loop, plus the red/green flash-crowd +
# straggler A/B (hedging/cache off must go red, on must go green);
# ALWAYS exits 0 with one JSON document on stdout
bench-prodday:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_prodday.py \
	  --out bench_evidence/bench_prodday.json

# fleet control plane: offered-load staircase over a real 1-replica
# fleet, static vs SLO-driven AutoScaler (scale decisions read back
# from the flight recorder), plus the admission-lane starvation
# drill (interactive p99 alone vs under a batch-lane flood); ALWAYS
# exits 0 with one JSON document on stdout
bench-autoscale:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_autoscale.py \
	  --out bench_evidence/bench_autoscale.json

# tier-1-safe smoke day (<60s): scenarios/prodday_smoke.json only,
# no deploy faults, no A/B cell
prodday-smoke:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_prodday.py --quick \
	  --out bench_evidence/bench_prodday_quick.json

# online serving: dynamic micro-batching vs batch=1 dispatch across
# offered loads; JSON artifact with p50/p99 latency + rows/s per cell
bench-serving:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_serving.py \
	  --out bench_evidence/bench_serving.json

# fleet serving: N replica subprocesses behind the least-outstanding
# router — offered-load sweep with per-replica utilization, AOT
# warm-start timings (cold fill vs cache-hit warmup), and the
# kill-under-load fault drill (zero failed client requests); ALWAYS
# exits 0 with one JSON document on stdout
bench-serving-fleet:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_serving.py --fleet 2 \
	  --out bench_evidence/bench_serving_fleet.json

# sharded serving: hot-swap wall time + peak host RSS under a tp=2
# mesh — zero-gather shard streaming vs the host-gather baseline
# (dense-host path poisoned in the streamed worker, so the artifact
# re-proves no full-size host buffer); ALWAYS exits 0 with one JSON
# document on stdout
bench-serving-sharded:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_serving.py --tp 2 \
	  --out bench_evidence/bench_serving_sharded.json

# multi-model serving: models-per-chip x rows/s under a pinned HBM
# budget — int8 quantized residency + LRU paging vs the f32 resident
# baseline (gate: >=2x models at equal p99), per-net accuracy-drift
# table, publish-time-vs-per-call weight-quantization A/B, zero fresh
# compiles across every page-in (COS_RECOMPILE_GUARD armed); ALWAYS
# exits 0 with one JSON document on stdout
bench-serving-multimodel:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_serving.py --multimodel \
	  --out bench_evidence/bench_serving_multimodel.json

# pipeline-parallel serving: stage-granular HBM paging under a pp=2
# mesh — over-budget p99 vs the unconstrained control, cold-start
# TTFR vs whole-model paging, never-mixed + recompile integrity
# under 500+ concurrent stage page-ins
bench-serving-pp:
	mkdir -p bench_evidence
	$(CPU_ENV) $(PY) scripts/bench_serving.py --pp 2 \
	  --out bench_evidence/bench_serving_pp.json

docs:
	$(PY) docs/gen_html.py

clean:
	rm -rf build *.egg-info docs/_html
	$(MAKE) -C caffeonspark_tpu/native clean 2>/dev/null || true
