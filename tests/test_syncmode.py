"""Sync-mode layer (parallel/syncmode.py, COS_SYNC_MODE) + unified
chaos layer (tools/chaos.py, COS_FAULT_*).

Parity contract, in order of strictness:
  * `lockstep` (the default) is INERT — no sync object is constructed
    and trajectories stay byte-identical to an unset env, including
    under ZeRO-1 and the fused K>1 loop;
  * `local_sgd` and `async` gate on CONVERGENCE (real handwritten
    digits to reference accuracy, the test_gradsync precedent), not
    parity — relaxed sync changes the trajectory by design;
  * `async` must honor its staleness bound: a rank never runs more
    than S local steps between global merges;
  * the chaos drills (slow/kill/flaky injection under each mode) are
    subprocess-heavy and carry the slow+chaos markers (`make chaos`).
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from caffeonspark_tpu.parallel import syncmode
from caffeonspark_tpu.parallel.syncmode import (
    AsyncSync, LocalSGDSync, ParamStore, average_flats, make_sync,
    resolve_policy)
from caffeonspark_tpu.tools import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# =========================================================================
# policy / env resolution
# =========================================================================
def test_policy_defaults_and_modes(monkeypatch):
    monkeypatch.delenv("COS_SYNC_MODE", raising=False)
    p = resolve_policy()
    assert p.mode == "lockstep" and not p.elastic and p.boundary == 0
    monkeypatch.setenv("COS_SYNC_MODE", "local_sgd")
    monkeypatch.setenv("COS_SYNC_K", "16")
    p = resolve_policy()
    assert p.mode == "local_sgd" and p.elastic and p.boundary == 16
    monkeypatch.setenv("COS_SYNC_MODE", "async")
    monkeypatch.setenv("COS_SYNC_STALENESS", "5")
    p = resolve_policy()
    assert p.boundary == 5
    assert p.describe()["staleness"] == 5


def test_policy_rejects_bad_values(monkeypatch):
    monkeypatch.setenv("COS_SYNC_MODE", "bsp")
    with pytest.raises(ValueError, match="COS_SYNC_MODE"):
        resolve_policy()
    monkeypatch.setenv("COS_SYNC_MODE", "local_sgd")
    monkeypatch.setenv("COS_SYNC_K", "0")
    with pytest.raises(ValueError, match="COS_SYNC_K"):
        resolve_policy()
    monkeypatch.delenv("COS_SYNC_K", raising=False)
    monkeypatch.setenv("COS_SYNC_WIRE_DTYPE", "int4")
    with pytest.raises(ValueError, match="COS_SYNC_WIRE_DTYPE"):
        resolve_policy()


def test_make_sync_lockstep_constructs_nothing(monkeypatch, tmp_path):
    monkeypatch.delenv("COS_SYNC_MODE", raising=False)
    assert make_sync(resolve_policy(), str(tmp_path), 0) is None
    assert not (tmp_path / ".sync").exists()


# =========================================================================
# chaos plan / injectors
# =========================================================================
def test_chaos_plan_resolution(monkeypatch, tmp_path):
    for k in list(os.environ):
        if k.startswith("COS_FAULT_"):
            monkeypatch.delenv(k, raising=False)
    plan = chaos.resolve(rank=2)
    assert not plan.active and plan.slow_factor == 1.0
    assert plan.describe() == {"active": False}

    monkeypatch.setenv("COS_FAULT_STEP_DELAY_MS", "150")
    monkeypatch.setenv("COS_FAULT_DIE_ONCE", f"1:12:{tmp_path}/m")
    monkeypatch.setenv("COS_FAULT_SLOW_RANK", "2:5")
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "0.25")
    monkeypatch.setenv("COS_FAULT_FLAKY_STORAGE", "0.1")
    monkeypatch.setenv("COS_FAULT_COMM_NS_PER_BYTE", "20")
    monkeypatch.setenv("COS_FAULT_COMM_LAT_US", "200")
    monkeypatch.setenv("COS_FAULT_COMM_LOCAL", "4")
    plan = chaos.resolve(rank=2)
    assert plan.active
    assert plan.step_delay_s == pytest.approx(0.15)
    assert plan.die_once == (1, 12, f"{tmp_path}/m")
    assert plan.slow_factor == 5.0          # rank 2 IS the slow rank
    assert chaos.resolve(rank=0).slow_factor == 1.0
    d = plan.describe()
    assert d["slow_rank"] == {"rank": 2, "factor": 5.0}
    assert d["flaky_exchange_p"] == 0.25
    assert d["comm_floor"]["ns_per_byte"] == 20.0
    json.dumps(d)                            # info.faults must be JSON


def test_chaos_rejects_bad_values(monkeypatch):
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "1.5")
    with pytest.raises(ValueError, match="COS_FAULT_FLAKY_EXCHANGE"):
        chaos.resolve()
    monkeypatch.delenv("COS_FAULT_FLAKY_EXCHANGE")
    monkeypatch.setenv("COS_FAULT_SLOW_RANK", "0:0.5")
    with pytest.raises(ValueError, match="SLOW_RANK"):
        chaos.resolve()


def test_chaos_injectors_deterministic(monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "0.5")
    monkeypatch.setenv("COS_FAULT_SEED", "42")
    a = chaos.ChaosInjector(chaos.resolve(0))
    b = chaos.ChaosInjector(chaos.resolve(0))
    seq_a = [a.exchange_fault() for _ in range(64)]
    seq_b = [b.exchange_fault() for _ in range(64)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)
    assert a.injected["exchange_faults"] == sum(seq_a)

    monkeypatch.setenv("COS_FAULT_FLAKY_STORAGE", "0.9")
    c = chaos.ChaosInjector(chaos.resolve(0))
    with pytest.raises(OSError, match="flaky-storage"):
        for _ in range(64):
            c.storage_fault()

    # die-once: marker suppresses, wrong rank/early iter never fires
    marker = tmp_path / "died"
    monkeypatch.setenv("COS_FAULT_DIE_ONCE", f"1:10:{marker}")
    inj0 = chaos.ChaosInjector(chaos.resolve(0))
    inj0.maybe_die(50)                       # not our rank: no exit
    inj1 = chaos.ChaosInjector(chaos.resolve(1))
    inj1.maybe_die(9)                        # before the iter: no exit
    marker.touch()
    inj1.maybe_die(10)                       # marker set: no exit
    assert marker.exists()


def test_chaos_slow_sleep_factor(monkeypatch):
    monkeypatch.setenv("COS_FAULT_SLOW_RANK", "0:3")
    inj = chaos.ChaosInjector(chaos.resolve(0))
    t0 = time.perf_counter()
    inj.slow_sleep(0.05)                     # sleeps (3-1) x 0.05
    dt = time.perf_counter() - t0
    assert 0.08 <= dt <= 0.5
    healthy = chaos.ChaosInjector(chaos.resolve(1))
    t0 = time.perf_counter()
    healthy.slow_sleep(0.05)
    assert time.perf_counter() - t0 < 0.02


def test_chaos_comm_floor_model(monkeypatch):
    """The comm floor moved behind CommFloor.sleep_seconds — same
    numbers the inline mini_cluster computation produced."""
    from caffeonspark_tpu.net import Net
    from caffeonspark_tpu.parallel.gradsync import build_plan
    from caffeonspark_tpu.proto import NetParameter, NetState, Phase
    from tests.test_gradsync import NET
    net = Net(NetParameter.from_text(NET), NetState(phase=Phase.TRAIN))
    plan = build_plan(net, "default")
    monkeypatch.setenv("COS_FAULT_COMM_NS_PER_BYTE", "20")
    monkeypatch.setenv("COS_FAULT_COMM_LAT_US", "200")
    floor = chaos.resolve(0).comm
    assert floor.active
    want = (plan.total_bytes_wire * 20 + 1 * 200e3) / 1e9
    assert floor.sleep_seconds(plan) == pytest.approx(want)
    monkeypatch.delenv("COS_FAULT_COMM_NS_PER_BYTE")
    assert not chaos.resolve(0).comm.active
    assert chaos.resolve(0).comm.sleep_seconds(plan) == 0.0


# =========================================================================
# flat codec + store
# =========================================================================
def test_flatten_roundtrip():
    from caffeonspark_tpu.checkpoint import (flatten_host_params,
                                             unflatten_host_params)
    params = {"conv1": {"weight": np.arange(6, dtype=np.float32)
                        .reshape(2, 3), "bias": np.zeros(2)},
              "ip": {"weight": np.ones((3, 2), np.float32)}}
    flat = flatten_host_params(params)
    assert set(flat) == {"conv1::weight", "conv1::bias", "ip::weight"}
    back = unflatten_host_params(flat)
    np.testing.assert_array_equal(back["conv1"]["weight"],
                                  params["conv1"]["weight"])
    with pytest.raises(ValueError, match="flat sync-store key"):
        flatten_host_params({"a::b": {"w": np.zeros(1)}})


def _store(tmp_path, rank, mode="local_sgd", chaos_inj=None, **env):
    os.environ.update({"COS_SYNC_MODE": mode, **env})
    try:
        pol = resolve_policy()
    finally:
        for k in ("COS_SYNC_MODE", *env):
            os.environ.pop(k, None)
    return ParamStore(str(tmp_path / "sync"), rank, pol,
                      chaos=chaos_inj)


def test_param_store_rounds_and_global(tmp_path):
    s0 = _store(tmp_path, 0)
    s1 = _store(tmp_path, 1)
    f0 = {"ip::weight": np.ones((4,), np.float32)}
    f1 = {"ip::weight": 3 * np.ones((4,), np.float32)}
    s0.publish_round(2, f0)
    s1.publish_round(2, f1)
    assert s0.round_ranks(2) == [0, 1]
    conts = s0.read_round(2)
    np.testing.assert_allclose(
        average_flats(list(conts.values()))["ip::weight"], 2.0)
    assert s0.latest_global_meta() is None
    s0.publish_global(2, 8, [0, 1], conts[0])
    g = s1.load_global()
    assert g["iter"] == 8 and g["version"] == 2
    assert g["members"] == [0, 1]
    np.testing.assert_array_equal(g["params"]["ip::weight"],
                                  f0["ip::weight"])
    # gc: publishing far-later versions drops old globals + rounds
    s0.publish_global(7, 28, [0], f0)
    s0.publish_global(8, 32, [0], f0)
    names = os.listdir(s0.root)
    assert not any(n.startswith("global_v00000002") for n in names)
    assert not any(n.startswith("round_00000002") for n in names)


def test_param_store_bf16_wire(tmp_path):
    s = _store(tmp_path, 0, COS_SYNC_WIRE_DTYPE="bfloat16")
    x = {"ip::weight": np.asarray([1.0, 2.5, -3.25], np.float32)}
    s.publish_round(1, x)
    back = s.read_round(1)[0]
    # bf16 wire: values survive at bf16 resolution, read back as f32
    assert back["ip::weight"].dtype == np.float32
    np.testing.assert_allclose(back["ip::weight"],
                               x["ip::weight"], rtol=1e-2)


def test_param_store_heartbeats_membership(tmp_path):
    s0 = _store(tmp_path, 0, COS_SYNC_HEARTBEAT_TIMEOUT_S="0.4")
    s1 = _store(tmp_path, 1, COS_SYNC_HEARTBEAT_TIMEOUT_S="0.4")
    s0.heartbeat(5, force=True)
    s1.heartbeat(3, force=True)
    assert s0.live_ranks() == {0: 5, 1: 3}
    s1.heartbeat(9, done=True)               # done: no longer expected
    assert s0.live_ranks() == {0: 5}
    assert s0.members()[1]["done"]
    time.sleep(0.5)                          # rank 0 goes silent
    assert s1.live_ranks() == {}


def test_param_store_retries_flaky_storage(monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_FLAKY_STORAGE", "0.4")
    monkeypatch.setenv("COS_FAULT_SEED", "7")
    inj = chaos.ChaosInjector(chaos.resolve(0))
    s = _store(tmp_path, 0, chaos_inj=inj)
    x = {"ip::weight": np.ones((8,), np.float32)}
    for rnd in range(6):                     # plenty of I/O under p=.4
        s.publish_round(rnd, x)
        got = s.read_round(rnd)[0]
        np.testing.assert_array_equal(got["ip::weight"],
                                      x["ip::weight"])
    assert inj.injected["storage_faults"] > 0


def test_average_flats_key_mismatch():
    with pytest.raises(ValueError, match="key mismatch"):
        average_flats([{"a": np.zeros(1)}, {"b": np.zeros(1)}])
    with pytest.raises(ValueError, match="no contributions"):
        average_flats([])


# =========================================================================
# local_sgd semantics
# =========================================================================
def _mk_sync(tmp_path, rank, mode, chaos_inj=None, **env):
    os.environ.update({"COS_SYNC_MODE": mode, **env})
    try:
        pol = resolve_policy()
    finally:
        for k in ("COS_SYNC_MODE", *env):
            os.environ.pop(k, None)
    return make_sync(pol, str(tmp_path), rank, chaos=chaos_inj)


def test_local_sgd_two_ranks_average(tmp_path):
    """Two concurrent ranks at the same round boundary: both end up
    with the exact mean, and the round leader publishes the global."""
    s0 = _mk_sync(tmp_path, 0, "local_sgd", COS_SYNC_K="4")
    s1 = _mk_sync(tmp_path, 1, "local_sgd", COS_SYNC_K="4")
    p = {0: {"ip::w": np.full((3,), 2.0, np.float32)},
         1: {"ip::w": np.full((3,), 6.0, np.float32)}}
    out, its = {}, {}
    # first heartbeats land BEFORE either thread runs: if rank 0's
    # whole exchange outran rank 1's on_start, rank 0's live_ranks()
    # saw only itself and solo-averaged (the known cross-run flake —
    # real trainers heartbeat from iter 0, long before a boundary)
    s0.on_start(0)
    s1.on_start(0)

    def run(sync, r):
        its[r] = sync.maybe_exchange(
            4, lambda: p[r], lambda f: out.__setitem__(r, f))

    ts = [threading.Thread(target=run, args=(s, r))
          for r, s in ((0, s0), (1, s1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for r in (0, 1):
        np.testing.assert_allclose(out[r]["ip::w"], 4.0)
        assert its[r] == 4
    g = s0.store.load_global()
    assert g["iter"] == 4 and g["members"] == [0, 1]
    np.testing.assert_allclose(g["params"]["ip::w"], 4.0)
    assert s0.counts["exchanges"] == 1 and s0.counts["timeouts"] == 0


def test_local_sgd_non_boundary_is_noop(tmp_path):
    s0 = _mk_sync(tmp_path, 0, "local_sgd", COS_SYNC_K="4")
    s0.on_start(0)
    called = []
    assert s0.maybe_exchange(3, lambda: called.append(1) or {},
                             lambda f: called.append(2)) == 3
    assert not called and s0.counts["exchanges"] == 0


def test_local_sgd_dead_rank_timeout_and_sticky_detach(tmp_path):
    """A rank that never contributes costs ONE round timeout, then is
    sticky-detached: the next round releases immediately."""
    s0 = _mk_sync(tmp_path, 0, "local_sgd", COS_SYNC_K="4",
                  COS_SYNC_ROUND_TIMEOUT_S="0.3",
                  COS_SYNC_HEARTBEAT_TIMEOUT_S="30")
    # rank 1 heartbeats (live, within one round) but never publishes
    s1_store = _store(tmp_path / ".", 1,
                      COS_SYNC_ROUND_TIMEOUT_S="0.3",
                      COS_SYNC_HEARTBEAT_TIMEOUT_S="30")
    s1_store.root = s0.store.root
    s1_store.heartbeat(2, force=True)
    p = {"ip::w": np.ones((2,), np.float32)}
    s0.on_start(0)
    t0 = time.monotonic()
    s0.maybe_exchange(4, lambda: p, lambda f: None)
    assert time.monotonic() - t0 >= 0.3      # waited the full patience
    assert s0.counts["timeouts"] == 1
    assert 1 in s0._detached
    s1_store.heartbeat(5, force=True)        # still "close" — but detached
    t0 = time.monotonic()
    s0.maybe_exchange(8, lambda: p, lambda f: None)
    assert time.monotonic() - t0 < 0.25      # no second wait
    assert s0.counts["exchanges"] == 2


def test_local_sgd_straggler_adopts_and_jumps(tmp_path):
    """A rank that reaches its boundary after the pack moved on drops
    its stale round, adopts the average, and fast-forwards."""
    s0 = _mk_sync(tmp_path, 0, "local_sgd", COS_SYNC_K="4",
                  COS_SYNC_ROUND_TIMEOUT_S="0.2")
    s1 = _mk_sync(tmp_path, 1, "local_sgd", COS_SYNC_K="4",
                  COS_SYNC_ROUND_TIMEOUT_S="0.2")
    pack = {"ip::w": np.full((2,), 8.0, np.float32)}
    s0.on_start(0)
    for it in (4, 8, 12):                    # rank 1 absent: averages solo
        s0.maybe_exchange(it, lambda: pack, lambda f: None)
    stale = {"ip::w": np.zeros((2,), np.float32)}
    got = {}
    s1.on_start(0)
    new_it = s1.maybe_exchange(4, lambda: stale,
                               lambda f: got.update(f))
    assert new_it == 12                      # jumped to the pack clock
    np.testing.assert_allclose(got["ip::w"], 8.0)
    assert s1.counts["adopted"] == 1 and s1.counts["exchanges"] == 0


def test_local_sgd_flaky_exchange_skips_round(monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "1.0")
    # probability 1 would be rejected; use 0.999… practical certainty
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "0.999")
    inj = chaos.ChaosInjector(chaos.resolve(0))
    s0 = _mk_sync(tmp_path, 0, "local_sgd", chaos_inj=inj,
                  COS_SYNC_K="4", COS_SYNC_ROUND_TIMEOUT_S="0.2")
    s0.on_start(0)
    p = {"ip::w": np.ones((2,), np.float32)}
    assert s0.maybe_exchange(4, lambda: p, lambda f: None) == 4
    assert s0.counts["skipped"] == 1 and s0.counts["exchanges"] == 0
    assert s0.store.round_ranks(1) == []     # nothing published


# =========================================================================
# async semantics
# =========================================================================
def test_async_merge_math_and_bound(tmp_path):
    a0 = _mk_sync(tmp_path, 0, "async", COS_SYNC_STALENESS="8")
    a1 = _mk_sync(tmp_path, 1, "async", COS_SYNC_STALENESS="8")
    a0.on_start(0)
    a1.on_start(0)
    p0 = {"ip::w": np.full((3,), 1.0, np.float32)}
    p1 = {"ip::w": np.full((3,), 3.0, np.float32)}
    out = {}
    a0.maybe_exchange(8, lambda: p0, lambda f: out.__setitem__(0, f))
    np.testing.assert_allclose(out[0]["ip::w"], 1.0)   # first merge
    a1.maybe_exchange(8, lambda: p1, lambda f: out.__setitem__(1, f))
    # two live ranks -> alpha = 1/2: (1-.5)*1 + .5*3 = 2
    np.testing.assert_allclose(out[1]["ip::w"], 2.0)
    g = a0.store.load_global()
    assert g["version"] == 2 and g["members"] == [0, 1]
    # boundary cadence == the staleness bound, and it is never exceeded
    for it in (16, 24, 32):
        a0.maybe_exchange(it, lambda: p0,
                          lambda f: out.__setitem__(0, f))
    assert a0.max_gap <= 8
    assert a0.counts["exchanges"] == 4


def test_async_stale_contribution_downweighted(tmp_path):
    a0 = _mk_sync(tmp_path, 0, "async", COS_SYNC_STALENESS="8",
                  COS_SYNC_ALPHA="0.5")
    a1 = _mk_sync(tmp_path, 1, "async", COS_SYNC_STALENESS="8",
                  COS_SYNC_ALPHA="0.5")
    a0.on_start(0)
    a1.on_start(0)
    zeros = {"ip::w": np.zeros((2,), np.float32)}
    tens = {"ip::w": np.full((2,), 10.0, np.float32)}
    a0.maybe_exchange(8, lambda: zeros, lambda f: None)   # global v1 @8
    a0.maybe_exchange(16, lambda: zeros, lambda f: None)  # global v2 @16
    out = {}
    # rank 1 merges at it=8, lag = 16-8 = 8 = one bound:
    # alpha_eff = 0.5 / (1 + 8/8) = 0.25 -> 0.25 * 10 = 2.5
    a1.maybe_exchange(8, lambda: tens, lambda f: out.update(f))
    np.testing.assert_allclose(out["ip::w"], 2.5)
    assert a1.store.load_global()["iter"] == 16   # clock never rewinds


def test_async_flaky_exchange_retries_until_bound_honored(
        monkeypatch, tmp_path):
    monkeypatch.setenv("COS_FAULT_FLAKY_EXCHANGE", "0.5")
    monkeypatch.setenv("COS_FAULT_SEED", "3")
    inj = chaos.ChaosInjector(chaos.resolve(0))
    a0 = _mk_sync(tmp_path, 0, "async", chaos_inj=inj,
                  COS_SYNC_STALENESS="4")
    a0.on_start(0)
    p = {"ip::w": np.ones((2,), np.float32)}
    for it in (4, 8, 12, 16):
        assert a0.maybe_exchange(it, lambda: p, lambda f: None) == it
    # every boundary merged despite injected faults (retried, not
    # skipped: async's bound is a promise) and the bound held
    assert a0.counts["exchanges"] == 4
    assert inj.injected["exchange_faults"] > 0
    assert a0.max_gap <= 4


def test_async_hopelessly_stale_readmits(tmp_path):
    a0 = _mk_sync(tmp_path, 0, "async", COS_SYNC_STALENESS="2")
    a1 = _mk_sync(tmp_path, 1, "async", COS_SYNC_STALENESS="2")
    a0.on_start(0)
    pack = {"ip::w": np.full((2,), 5.0, np.float32)}
    for it in range(2, 22, 2):
        a0.maybe_exchange(it, lambda: pack, lambda f: None)
    got = {}
    a1.on_start(0)
    new_it = a1.maybe_exchange(2, lambda: {"ip::w": np.zeros(
        (2,), np.float32)}, lambda f: got.update(f))
    assert new_it == 20                      # lag 18 > 4*2: re-admit
    np.testing.assert_allclose(got["ip::w"], 5.0)
    assert a1.counts["adopted"] == 1


# =========================================================================
# lockstep inertness (byte parity) + convergence gates
# =========================================================================
def _tiny_solver(monkeypatch, sync_env, net_text, solver_text):
    import jax
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    if sync_env is None:
        monkeypatch.delenv("COS_SYNC_MODE", raising=False)
    else:
        monkeypatch.setenv("COS_SYNC_MODE", sync_env)
    s = Solver(SolverParameter.from_text(solver_text),
               NetParameter.from_text(net_text))
    return jax, s


def test_lockstep_env_is_byte_identical(monkeypatch):
    """COS_SYNC_MODE=lockstep vs unset: identical trajectories over
    the fused K>1 loop (the mode constructs nothing)."""
    import jax.numpy as jnp
    from tests.test_gradsync import (NET, SOLVER, _assert_bytes_equal,
                                     _batch)
    runs = []
    for env in (None, "lockstep"):
        jax, s = _tiny_solver(monkeypatch, env, NET, SOLVER)
        assert s.sync_policy.mode == "lockstep"
        p, st = s.init()
        fused = s.jit_train_step_many(4)
        b = _batch(8)
        stacked = {k: jnp.stack([v] * 4) for k, v in b.items()}
        for _ in range(3):
            p, st, _ = fused(p, st, stacked)
        runs.append(p)
    _assert_bytes_equal(runs[0], runs[1])


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_lockstep_byte_identical_under_tp_zero_fused(monkeypatch):
    """The acceptance pin: lockstep under TP + ZeRO-1 + the fused K>1
    loop on a dp4,tp2 mesh is byte-identical to an unset env, params
    AND opt state (mirrors gradsync's default-inertness pin)."""
    import jax.numpy as jnp
    from caffeonspark_tpu.parallel import ParallelSolver, build_mesh
    from tests.test_gradsync import (NET, SOLVER, _assert_bytes_equal,
                                     _batch)
    runs = []
    for env in (None, "lockstep"):
        _, s = _tiny_solver(monkeypatch, env, NET, SOLVER)
        assert s.sync_policy.mode == "lockstep"
        ps = ParallelSolver(s, build_mesh(dp=4, tp=2), zero_dp=True)
        p, st = ps.init()
        fused = ps.train_step_many(4)
        sh = ps.chunk_input_shardings()
        b = _batch(32)
        stacked = {k: jax.device_put(jnp.stack([v] * 4), sh[k])
                   for k, v in b.items()}
        for _ in range(3):
            p, st, _ = fused(p, st, stacked)
        runs.append((p, st))
    _assert_bytes_equal(runs[0][0], runs[1][0])
    _assert_bytes_equal(runs[0][1].history, runs[1][1].history)


def _digits_accuracy(params, net, X, y):
    import jax.numpy as jnp
    logits, _ = net.apply(params, {"data": jnp.asarray(X),
                                   "label": jnp.asarray(y)},
                          train=False)
    return float(np.mean(np.argmax(
        np.asarray(logits["ip2"], np.float32), 1) == y))


def _digits_worker(rank, sync, X, y, steps, k, out, err):
    """One local-SGD/async worker: its own Solver, its own data
    stream, exchanging through the shared store every k steps."""
    try:
        import jax.numpy as jnp
        from caffeonspark_tpu.proto import (NetParameter,
                                            SolverParameter)
        from caffeonspark_tpu.solver import Solver
        from tests.test_gradsync import DIGITS_NET, DIGITS_SOLVER
        s = Solver(SolverParameter.from_text(DIGITS_SOLVER),
                   NetParameter.from_text(DIGITS_NET), rank=rank)
        p, st = s.init()
        step = s.jit_train_step()
        ps_like = None     # single-device: host exchange is device_get
        rng = np.random.RandomState(100 + rank)
        from caffeonspark_tpu.checkpoint import (flatten_host_params,
                                                 unflatten_host_params)
        import jax

        def get():
            return {kk: np.asarray(v, np.float32)
                    for kk, v in flatten_host_params(p).items()}

        def put(flat):
            nonlocal p
            host = unflatten_host_params(flat)
            p = {ln: {bn: jnp.asarray(np.asarray(
                arr, np.dtype(p[ln][bn].dtype)))
                for bn, arr in bl.items()}
                for ln, bl in host.items()}

        del ps_like, jax
        sync.on_start(0)
        it = 0
        n = X.shape[0]
        while it < steps:
            idx = rng.randint(0, n, 64)
            b = {"data": jnp.asarray(X[idx]),
                 "label": jnp.asarray(y[idx])}
            p, st, _ = step(p, st, b, s.step_rng(it))
            it += 1
            it = sync.maybe_exchange(it, get, put)
        sync.finalize(it)
        out[rank] = (p, s.train_net)
    except BaseException as e:               # noqa: BLE001
        err[rank] = e
        raise


@pytest.mark.parametrize("mode", ["local_sgd", "async"])
def test_relaxed_modes_convergence_on_real_digits(tmp_path, mode):
    """The convergence gate (test_gradsync precedent): two workers
    exchanging through the real store must reach reference accuracy
    on real handwritten digits — relaxed sync changes the trajectory,
    it must not change the destination."""
    pytest.importorskip("sklearn")
    from tests.test_gradsync import (DIGITS_NET, DIGITS_SOLVER,
                                     _digits_problem)
    from caffeonspark_tpu.proto import NetParameter, SolverParameter
    from caffeonspark_tpu.solver import Solver
    import jax.numpy as jnp
    X, y = _digits_problem()

    # reference: one worker, 240 plain steps
    s = Solver(SolverParameter.from_text(DIGITS_SOLVER),
               NetParameter.from_text(DIGITS_NET))
    p, st = s.init()
    step = s.jit_train_step()
    rng = np.random.RandomState(0)
    for i in range(240):
        idx = rng.randint(0, X.shape[0], 64)
        p, st, _ = step(p, st, {"data": jnp.asarray(X[idx]),
                                "label": jnp.asarray(y[idx])},
                        s.step_rng(i))
    ref = _digits_accuracy(p, s.train_net, X, y)
    assert ref >= 0.93

    syncs = [_mk_sync(tmp_path / mode, r, mode, COS_SYNC_K="10",
                      COS_SYNC_STALENESS="10",
                      COS_SYNC_ROUND_TIMEOUT_S="20")
             for r in (0, 1)]
    out, err = {}, {}
    ts = [threading.Thread(target=_digits_worker,
                           args=(r, syncs[r], X, y, 240, 10, out, err))
          for r in (0, 1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert not err, err
    assert syncs[0].counts["exchanges"] >= 10
    if mode == "async":
        assert max(sy.max_gap for sy in syncs) <= 10
    acc = _digits_accuracy(*out[0], X, y)
    assert acc >= ref - 0.03, (mode, acc, ref)
    assert acc >= 0.90, (mode, acc)


# =========================================================================
# supervisor units (backoff + snapshot fallback)
# =========================================================================
def test_relaunch_backoff_shape():
    import random as _random
    from caffeonspark_tpu.tools.supervisor import relaunch_backoff
    rng = _random.Random(0)
    assert relaunch_backoff(0) == 0.0
    for attempt in range(1, 12):
        d = relaunch_backoff(attempt, base_s=1.0, cap_s=30.0, rng=rng)
        assert 0.0 <= d <= min(30.0, 2 ** (attempt - 1))
    # jitter: two seeds disagree
    a = relaunch_backoff(5, rng=_random.Random(1))
    b = relaunch_backoff(5, rng=_random.Random(2))
    assert a != b


def test_pick_snapshot_skips_bad(tmp_path):
    from caffeonspark_tpu.tools.supervisor import (find_snapshots,
                                                   pick_snapshot)
    for it in (8, 16, 24):
        (tmp_path / f"m_iter_{it}.solverstate").touch()
        (tmp_path / f"m_iter_{it}.caffemodel").touch()
    (tmp_path / "m_iter_32.solverstate").touch()  # incomplete pair
    pairs = find_snapshots(str(tmp_path), "m")
    assert [p[0].endswith(f"m_iter_{i}.solverstate")
            for p, i in zip(pairs, (24, 16, 8))] == [True] * 3
    newest = pick_snapshot(str(tmp_path), "m")
    assert newest[0].endswith("m_iter_24.solverstate")
    fb = pick_snapshot(str(tmp_path), "m", frozenset({newest[0]}))
    assert fb[0].endswith("m_iter_16.solverstate")
    allbad = frozenset(p[0] for p in pairs)
    assert pick_snapshot(str(tmp_path), "m", allbad) is None


# =========================================================================
# chaos drills: subprocess fleets (slow + chaos markers, `make chaos`)
# =========================================================================
def _drill_job(tmp_path, max_iter=32, snap=8, batch=8):
    from caffeonspark_tpu.data import LmdbWriter
    from caffeonspark_tpu.data.synthetic import make_images
    from caffeonspark_tpu.proto.caffe import Datum
    imgs, labels = make_images(128, seed=6)
    recs = [(b"%06d" % i,
             Datum(channels=1, height=28, width=28,
                   data=(imgs[i, 0] * 255).astype(np.uint8).tobytes(),
                   label=int(labels[i])).to_binary())
            for i in range(128)]
    LmdbWriter(str(tmp_path / "lmdb")).write(recs)
    net = tmp_path / "net.prototxt"
    net.write_text(f'''
layer {{ name: "data" type: "MemoryData" top: "data" top: "label"
  source_class: "LMDB"
  memory_data_param {{ source: "{tmp_path}/lmdb" batch_size: {batch}
    channels: 1 height: 28 width: 28 }}
  transform_param {{ scale: 0.00390625 }} }}
layer {{ name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param {{ num_output: 10
    weight_filler {{ type: "xavier" }} }} }}
layer {{ name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }}''')
    solver = tmp_path / "solver.prototxt"
    solver.write_text(
        f'net: "{net}"\nbase_lr: 0.05\nmomentum: 0.9\n'
        f'lr_policy: "fixed"\ndisplay: {snap}\nmax_iter: {max_iter}\n'
        f'snapshot: {snap}\nsnapshot_prefix: "cd"\nrandom_seed: 11\n')
    return solver


def _drill_env(**extra):
    return {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
            "COS_TRANSFORM_THREADS": "0",
            "PYTHONPATH": REPO + os.pathsep
            + os.environ.get("PYTHONPATH", ""), **extra}


def _launch_rank(solver, out, rank, env, extra=()):
    return subprocess.Popen(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(out),
         "-cluster", "2", "-rank", str(rank), *extra],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO)


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_local_sgd_kill_loses_at_most_k(tmp_path):
    """SIGKILL a rank mid-run under local_sgd: the survivor keeps
    training (no teardown), the supervisor relaunches ONLY the dead
    rank with backoff, the relaunched rank rejoins from the averaged
    state, and the fleet loses at most K steps of the victim's work
    (rejoin iter >= death iter - K)."""
    solver = _drill_job(tmp_path, max_iter=40)
    out = tmp_path / "out"
    env = _drill_env(
        COS_SYNC_MODE="local_sgd", COS_SYNC_K="4",
        COS_SYNC_HEARTBEAT_TIMEOUT_S="4",
        COS_FAULT_DIE_ONCE=f"1:14:{tmp_path}/died.marker",
        COS_FAULT_STEP_DELAY_MS="40")
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-cluster", "2",
         "-max_restarts", "2", "-poll_interval", "0.3",
         "-backoff_base", "0.3", "-backoff_cap", "1.0"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-800:])
    assert "supervisor[elastic:local_sgd]" in r.stdout
    # per-rank relaunch, never a fleet teardown
    assert "tearing down" not in r.stdout
    assert "rank 1 died (exit 3)" in r.stdout
    assert "survivors keep training" in r.stdout
    assert "launching rank 1 (attempt 2)" in r.stdout
    assert "launching rank 0 (attempt 2)" not in r.stdout
    assert (out / "cd_iter_40.caffemodel").exists()
    # the elastic guarantee: whatever the victim lost, the averaged
    # state it rejoined from is within one round of its death point
    import re as _re
    died = int(_re.search(r"dying at iter (\d+)", r.stdout).group(1))
    rejoin = _re.search(r"rejoined pack at iter (\d+)", r.stdout)
    assert rejoin, r.stdout[-3000:]
    assert int(rejoin.group(1)) >= died - 4


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_async_slow_rank_never_stalls_and_bound_holds(tmp_path):
    """A 4x-slow rank under async: rank 0 never waits for it (wall
    ratio >> 1), the staleness bound is honored (info.sync.max_gap),
    and the straggler re-admits itself at the pack's clock."""
    solver = _drill_job(tmp_path, max_iter=32)
    out = tmp_path / "out"
    pm0 = tmp_path / "pm0.json"
    env = _drill_env(
        COS_SYNC_MODE="async", COS_SYNC_STALENESS="4",
        COS_SYNC_HEARTBEAT_TIMEOUT_S="4",
        COS_FAULT_STEP_DELAY_MS="30",
        COS_FAULT_SLOW_RANK="1:4")
    p1 = _launch_rank(solver, out, 1, env)
    t0 = time.monotonic()
    p0 = _launch_rank(solver, out, 0, env,
                      extra=("-pipeline_metrics", str(pm0)))
    o0, _ = p0.communicate(timeout=520)
    wall0 = time.monotonic() - t0
    o1, _ = p1.communicate(timeout=520)
    assert p0.returncode == 0, o0[-2000:]
    assert p1.returncode == 0, o1[-2000:]
    info = json.load(open(pm0))["info"]
    assert info["sync"]["mode"] == "async"
    assert info["sync"]["max_gap"] <= 4
    assert info["sync"]["exchanges"] >= 4
    assert info["faults"]["slow_rank"] == {"rank": 1, "factor": 4.0}
    # the straggler adopted the pack clock instead of stalling anyone
    assert "re-admitted at iter" in o1 or "rejoined pack" in o1
    # rank 0's wall is step-delay bound (~32*30ms + overhead), nowhere
    # near the straggler's 4x rate
    assert wall0 < 4 * 32 * 0.030 + 60


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_lockstep_unchanged_with_chaos_disabled(tmp_path):
    """Chaos off, lockstep: single-rank training is byte-identical
    with and without the chaos/sync layers importable — pinned by
    comparing final models across two runs of the same seed."""
    solver = _drill_job(tmp_path, max_iter=12, snap=100)
    env = _drill_env()
    models = []
    for tag in ("a", "b"):
        out = tmp_path / f"out_{tag}"
        p = subprocess.run(
            [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
             "-solver", str(solver), "-output", str(out),
             "-model", str(out / "final.caffemodel")],
            capture_output=True, text=True, timeout=520, env=env,
            cwd=REPO)
        assert p.returncode == 0, p.stdout[-2000:]
        models.append((out / "final.caffemodel").read_bytes())
    assert models[0] == models[1]


@pytest.mark.slow
@pytest.mark.chaos
def test_drill_supervisor_falls_back_past_bad_snapshot(tmp_path):
    """A corrupt newest snapshot pair on shared storage must not burn
    every restart attempt: the supervisor blames it after one instant
    no-progress death and falls back to the previous good pair."""
    solver = _drill_job(tmp_path, max_iter=16, snap=8)
    out = tmp_path / "out"
    env = _drill_env()
    # produce a GOOD iter-8 snapshot by running rank 0 solo to 8
    p = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.mini_cluster",
         "-solver", str(solver), "-output", str(out),
         "-iterations", "8"],
        capture_output=True, text=True, timeout=520, env=env,
        cwd=REPO)
    assert p.returncode == 0, p.stdout[-2000:]
    assert (out / "cd_iter_8.solverstate").exists()
    # plant a CORRUPT newer pair (a partial write on shared storage)
    (out / "cd_iter_12.solverstate").write_bytes(b"garbage")
    (out / "cd_iter_12.caffemodel").write_bytes(b"garbage")
    r = subprocess.run(
        [sys.executable, "-m", "caffeonspark_tpu.tools.supervisor",
         "-solver", str(solver), "-train", str(tmp_path / "lmdb"),
         "-output", str(out), "-cluster", "1",
         "-max_restarts", "3", "-poll_interval", "0.3",
         "-backoff_base", "0.2", "-backoff_cap", "0.5",
         "-min_uptime", "15"],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=REPO)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-800:])
    assert "from " + str(out / "cd_iter_12.solverstate") in r.stdout
    assert ("marking snapshot " + str(out / "cd_iter_12.solverstate")
            + " bad") in r.stdout
    assert "from " + str(out / "cd_iter_8.solverstate") in r.stdout
    assert "run complete" in r.stdout
    assert (out / "cd_iter_16.caffemodel").exists()
