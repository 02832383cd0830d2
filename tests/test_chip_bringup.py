"""What PR 21 (chip bring-up) added: the chip_smoke.py rehearsal and its
armed platform check, the one compile-cache helper, route.on_tpu()
without a fallback, and the fleet's refusal to outnumber the chips."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(args, env, tmp_path, timeout=600):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                         "JAX_COMPILATION_CACHE_DIR")}
    full.update(env)
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=timeout, env=full,
        cwd=str(tmp_path))


# ------------------------------------------------------------ chip_smoke

def test_chip_smoke_rehearsal_runs_every_phase(tmp_path):
    """train -> features -> serve through caffe_on_spark.main on the
    CPU at tiny shapes, Pallas in interpret mode: exit 0, labelled a
    rehearsal, and no device result line."""
    cache = tmp_path / "cache"
    p = _smoke(["--rehearsal"],
               {"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(cache)}, tmp_path)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    phases = [ln.split(":")[0] for ln in p.stdout.splitlines()
              if ln.startswith("[chip_smoke] ")]
    for phase in ("device", "rehearsal", "train", "step", "features",
                  "serve", "compile_cache"):
        assert f"[chip_smoke] {phase}" in phases, (phase, phases)
    assert "rehearsal passed" in p.stdout.splitlines()[-1]
    assert '"ok"' not in p.stdout          # no device result
    assert "Pallas interpret mode" in p.stdout
    # the environment placed the cache: everything went there, nothing
    # into the checkout-local default next to the script
    assert any(n.endswith("-cache") for n in os.listdir(cache))
    assert not (tmp_path / "out" / "work").exists()   # GBs cleaned up


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"}, {}],
                         ids=["held-to-cpu", "platform-unset"])
def test_chip_smoke_without_a_chip_fails(env, tmp_path):
    """The platform check is armed unless --rehearsal is given: no TPU
    is a non-zero exit naming what JAX found, and no result line —
    also where JAX_PLATFORMS is unset and libtpu finds no chip."""
    from caffeonspark_tpu.utils.chips import local_tpu_chips
    if not env and local_tpu_chips():
        pytest.skip("this host has a TPU")
    p = _smoke([], env, tmp_path, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def test_chip_smoke_rehearsal_is_cpu_only(tmp_path):
    p = _smoke(["--rehearsal"], {}, tmp_path, timeout=120)
    assert p.returncode != 0 and "JAX_PLATFORMS=cpu" in p.stderr


# ---------------------------------------------------------- compile cache

@pytest.fixture()
def jax_cache_config():
    """Restore the process-wide cache config a test re-points."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cache_placed_by_env_is_left_alone(monkeypatch, tmp_path,
                                           jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: code sets no directory, and the
    AOT namespace neither re-points nor resets."""
    import jax
    from caffeonspark_tpu.serving import aot
    from caffeonspark_tpu.utils import compile_cache as cc
    monkeypatch.setenv(cc.CACHE_ENV, str(tmp_path / "placed"))
    monkeypatch.setenv("COS_AOT_CACHE_DIR", str(tmp_path / "aot"))
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert cc.enable_compile_cache() == str(tmp_path / "placed")
    ns = aot.resolve_cache_dir("net", (1,), ("ip",))
    assert aot.enable_aot_cache(ns) is False
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    assert not os.path.exists(ns)


def test_cache_defaults_to_one_path_in_the_checkout(monkeypatch,
                                                    jax_cache_config):
    import jax
    from caffeonspark_tpu.utils import compile_cache as cc
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    assert cc.enable_compile_cache() == cc.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_CACHE_DIR
    assert cc.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_aot_namespace_kept_when_env_unset(monkeypatch, tmp_path,
                                           jax_cache_config):
    """Unset + COS_AOT_CACHE_DIR: today's per-model namespace."""
    import jax
    from caffeonspark_tpu.serving import aot
    from caffeonspark_tpu.utils import compile_cache as cc
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    monkeypatch.setenv("COS_AOT_CACHE_DIR", str(tmp_path))
    ns = aot.resolve_cache_dir("net", (1,), ("ip",))
    assert os.path.dirname(ns) == str(tmp_path)
    assert os.path.basename(ns).startswith("aot-")
    assert aot.enable_aot_cache(ns) is True
    assert jax.config.jax_compilation_cache_dir == ns
    assert os.path.isdir(ns)


# ------------------------------------------------------------ route.on_tpu

def test_pallas_enabled_only_on_tpu_and_never_swallows(monkeypatch):
    import jax
    from caffeonspark_tpu.ops.route import on_tpu
    monkeypatch.delenv("COS_DISABLE_PALLAS", raising=False)
    for backend, want in (("tpu", True), ("cpu", False), ("gpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert on_tpu() is want, backend

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        on_tpu()


# ------------------------------------------------------- one process/chip

def test_chip_count_needs_no_backend(monkeypatch):
    from caffeonspark_tpu.utils import chips
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    assert chips.local_tpu_chips() == 0       # held to another platform
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert chips.local_tpu_chips() == 2
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "3")
    assert chips.local_tpu_chips() == 1
    chips.require_chips(1, "x")
    with pytest.raises(RuntimeError, match="one process at a time"):
        chips.require_chips(2, "x")
    # a parent that has claimed the chips itself may start no child
    import jax
    from jax._src import xla_bridge
    assert not chips.this_process_holds_chips()     # CPU backend here
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="has not touched JAX"):
        chips.require_chips(1, "x")


def test_fleet_and_deploy_refuse_to_outnumber_the_chips(monkeypatch):
    """On a TPU host a fleet larger than the chip count — and -deploy,
    whose parent trains on the chips its children would need — say so
    in one sentence before spawning anything; replicas of a fleet that
    fits each get their own chip through their environment."""
    from caffeonspark_tpu.serving import fleet as fleet_mod
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    spawned = []
    monkeypatch.setattr(fleet_mod.ReplicaProcess, "spawn",
                        lambda self: spawned.append(self) or self)

    too_many = fleet_mod.Fleet(["-conf", "x", "-model", "m"], replicas=3)
    with pytest.raises(RuntimeError, match=r"fleet of 3 replicas needs 3 "
                       r"processes that each hold a TPU chip, but this "
                       r"host has 2"):
        too_many.start()
    assert spawned == [] and too_many.replicas == {}

    fits = fleet_mod.Fleet(["-conf", "x", "-model", "m"], replicas=2,
                           startup_timeout_s=0.01)
    with pytest.raises(RuntimeError, match="failed to become healthy"):
        fits.start()              # the stub never serves; env is the point
    assert [r.env["TPU_VISIBLE_CHIPS"] for r in spawned] == ["0", "1"]
    assert all(r.env["TPU_PROCESS_BOUNDS"] == "1,1,1" for r in spawned)

    from caffeonspark_tpu.deploy import DeployController

    class Conf:
        netParam = object()
        outputPath = "/tmp/x"
    with pytest.raises(RuntimeError, match="-deploy fine-tunes in this "
                       "process, which holds every TPU chip"):
        DeployController(Conf())


def test_bf16_compute_never_narrows_class_ids():
    """Mixed precision casts activations to bf16, which keeps 8
    significant bits: a float label above 256 would be rounded to
    another class and 999 to 1000 — out of range, a NaN loss (seen on
    the chip at CaffeNet's 1000 classes).  Id-carrying bottoms stay
    f32, so the bf16 loss tracks the f32 one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from caffeonspark_tpu.net import Net
    from caffeonspark_tpu.proto import NetParameter, NetState, Phase
    npm = NetParameter.from_text("""
layer { name: "data" type: "Input" top: "data" top: "label"
  input_param { shape { dim: 4 dim: 16 } shape { dim: 4 } } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "ip"
  inner_product_param { num_output: 1000
    weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip"
  bottom: "label" top: "loss" }""")
    x = {"data": jnp.asarray(np.random.RandomState(0).randn(4, 16),
                             jnp.float32),
         "label": jnp.asarray([999., 997., 301., 5.])}
    loss = {}
    for name, cd in (("f32", None), ("bf16", jnp.bfloat16)):
        net = Net(npm, NetState(phase=Phase.TRAIN), compute_dtype=cd)
        blobs, _ = net.apply(net.init(jax.random.key(0)), x, train=True)
        loss[name] = float(blobs["loss"])
    assert np.isfinite(loss["bf16"])
    assert abs(loss["bf16"] - loss["f32"]) < 0.05, loss
