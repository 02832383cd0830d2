"""Window kind `train_tokens`: a language-model job fed packed token rows.

`caffe_on_spark.main -train` over a Parquet DataFrame of packed rows
(columns `input_ids` / `target_ids`, INT_ARRAY, time-major) through
`CoSData` / `DataFrameSource`, `max_iter` out of reach, no validation, no
snapshot, every program option at its default.  The end of set-up, the
window's edges, its pool rounds and its stock rule are `windows/train.py`'s
`Observer`; what
is this file's own is what writes the inputs, what the observer copies
out of the first steps, and the comparison that decides `correct`.

`images` in the result are sequences: `train_img_per_s` counts records,
here packed rows of `sequence_length` tokens.
"""

from __future__ import annotations

import gc
import importlib
import math
import os
import shutil
import threading
import time

import numpy as np

from .train import CHECK_STEPS, Observer, json_scalar


# ------------------------------------------------------------------ inputs

def make_rows(traffic: dict, vocab: int, seq: int, n_rows: int,
              seed: int) -> np.ndarray:
    """(n_rows, seq + 1) int32: rows cut without padding from one seeded
    stream of documents.  Lengths are lognormal (median and sigma from the
    traffic file, clipped), ids Zipf over [1, vocab) with id = rank, and
    `eod_id` ends every document.  A row's inputs are its first `seq`
    tokens and its targets the same row shifted by one."""
    rng = np.random.default_rng([int(seed), 26])
    need = n_rows * (seq + 1)
    med, sig = float(traffic["doc_length_median"]), \
        float(traffic["doc_length_sigma"])
    lengths = []
    while sum(lengths) + len(lengths) < need:
        more = np.exp(rng.normal(math.log(med), sig, 4096))
        lengths += list(np.clip(more, traffic["doc_length_min"],
                                traffic["doc_length_max"]).astype(np.int64))
    ranks = np.arange(1, vocab, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(traffic["zipf_exponent"]))
    cdf /= cdf[-1]
    stream = 1 + np.searchsorted(cdf, rng.random(need)).astype(np.int32)
    stream = np.minimum(stream, vocab - 1)
    ends = np.cumsum(np.asarray(lengths) + 1) - 1   # one EOD a document
    stream[ends[ends < need]] = int(traffic["eod_id"])
    return stream.reshape(n_rows, seq + 1)


def write_inputs(ctx: dict, work: str):
    """Parquet rows + net + solver from the cell's files and the seed."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    cfg, traffic = ctx["config"], ctx["traffic"]
    seq = int(cfg["sequence_length"])
    batch = int(cfg["per_device_batch"]) * ctx["chips"]
    t0 = time.perf_counter()
    rows = make_rows(traffic, int(cfg["vocab_size"]), seq,
                     int(traffic["rows"]), ctx["seed"])
    cols = traffic["columns"]
    path = os.path.join(work, "train.parquet")
    pq.write_table(pa.table({
        cols[0]: pa.array(list(rows[:, :-1]), pa.list_(pa.int32())),
        cols[1]: pa.array(list(rows[:, 1:]), pa.list_(pa.int32()))}), path)
    facts = {"rows": int(rows.shape[0]), "tokens_per_row": seq,
             "documents_ended": int((rows == traffic["eod_id"]).sum()),
             "parquet_bytes": os.path.getsize(path),
             "inputs_s": time.perf_counter() - t0}
    with open(os.path.join(ctx["root"], cfg["net"])) as f:
        head, rest = f.read().split("\n", 1)    # the `name:` line first
    tops = "\n".join(
        f'    top {{ name: "{c}" type: INT_ARRAY channels: {seq} '
        "sample_num_axes: 1 transpose: true }" for c in cols)
    net_path = os.path.join(work, "train_val.prototxt")
    with open(net_path, "w") as f:
        f.write(f'''{head}
layer {{
  name: "data" type: "CoSData" top: "{cols[0]}" top: "{cols[1]}"
  include {{ phase: TRAIN }} source_class: "{traffic["source_class"]}"
  cos_data_param {{ source: "{path}" batch_size: {batch}
    dataframe_format: "{traffic["dataframe_format"]}"
{tops} }}
}}
{rest}''')
    solver_path = os.path.join(work, "solver.prototxt")
    with open(solver_path, "w") as f:
        f.write(f'net: "{net_path}"\n')
        for k, v in cfg["solver"].items():
            f.write(f"{k}: {json_scalar(v)}\n")
        # steady state: nothing but train steps in the window
        f.write("max_iter: 100000000\ntest_interval: 0\nsnapshot: 0\n"
                "snapshot_after_train: false\ndisplay: 0\n"
                f"random_seed: {ctx['seed']}\n")
    return solver_path, rows, facts, batch


# ---------------------------------------------------- what is kept of a state

def flat(tree) -> dict:
    """{layer: {blob: array}} -> {"layer/blob": host array}."""
    return {f"{ln}/{bn}": np.asarray(a) for ln, bl in tree.items()
            for bn, a in bl.items()}


def norm(a) -> float:
    """||a||: float32 dot products over a few million elements at a time
    (BLAS sums them blockwise), added up in float64."""
    a = np.asarray(a, np.float32).ravel()
    return math.sqrt(sum(float(np.dot(a[i:i + (1 << 22)],
                                      a[i:i + (1 << 22)]))
                         for i in range(0, a.size, 1 << 22)))


class Kept:
    """One side's compared states, reduced as they arrive: the parameters
    before step 1 whole (`p0`), then per leaf the norms of both Adam
    moments after step 1 (`m1`, `v1`) and of the parameters' change over
    step 1 (`p1`) and over the check steps (`p_last`).  Both the
    observer (the program's states) and the reference hand their states
    to one of these, so neither side holds five copies of the model."""

    def __init__(self):
        self.p0 = None
        self.norms = {}
        self.at = []            # (state, host clock) as each arrived

    def __call__(self, name: str, tree: dict):
        self.at.append((name, time.perf_counter()))
        if name == "p0":
            self.p0 = {k: np.array(v, np.float32) for k, v in tree.items()}
        elif name in ("m1", "v1"):
            self.norms[name] = {k: norm(v) for k, v in tree.items()}
        else:
            self.norms[name] = {k: norm(np.asarray(v) - self.p0[k])
                                for k, v in tree.items()}
        return name


def numbers(prog: Kept, prog_losses, ref: Kept, ref_losses,
            lr_mults: dict) -> dict:
    """The compared numbers by name; the norm gaps as harness/check.py
    defines them (worst leaf over the leaves the optimizer moves, against
    max(the leaf's, the median leaf's) reference norm).  The first
    gradient is Adam's first moment after step 1, (1 - b1) x the clipped
    gradient: a common factor, which a relative gap does not see."""
    from ..harness.check import leaf_gaps
    nums = {"init_gap": max(
        float(np.max(np.abs(prog.p0[k] - v))) if v.size else 0.0
        for k, v in ref.p0.items())}
    for i, (a, b) in enumerate(zip(prog_losses, ref_losses)):
        nums[f"loss_gap_step{i + 1}"] = (
            abs(a - b) / abs(b) if math.isfinite(a) else float("inf"))
    moved = [k for k, m in lr_mults.items() if m]
    for out, name in (("first_grad_norm_gap", "m1"),
                      ("second_moment_norm_gap", "v1"),
                      ("step1_update_norm_gap", "p1"),
                      ("update_norm_gap", "p_last")):
        (nums[out], nums[out + "_leaf"], nums[out + "_median"]) = leaf_gaps(
            {k: prog.norms[name][k] for k in moved},
            {k: ref.norms[name][k] for k in moved})
    return nums


def match_rows(ids, targets, rows):
    """For every packed sequence of one step, the row of the benchmark's
    own table it is: ids, targets (T, B) as the program packed them ->
    (row indices, largest |packed - own| over both columns)."""
    first = {}
    for r, row in enumerate(rows):
        first.setdefault(row[:32].tobytes(), []).append(r)
    found, worst = [], 0.0
    for b in range(ids.shape[1]):
        col = ids[:, b]
        key = np.rint(col[:32]).astype(np.int32).tobytes()
        cands = first.get(key) or range(len(rows))
        gaps = [(max(float(np.max(np.abs(col - rows[r, :-1]))),
                     float(np.max(np.abs(targets[:, b] - rows[r, 1:])))), r)
                for r in cands]
        gap, r = min(gaps)
        found.append(r)
        worst = max(worst, gap)
    return found, worst


# ---------------------------------------------------------------- observer

class TokenObserver(Observer):
    """`train.Observer` with a token batch's capture: each check step's
    packed ids whole, the states reduced by `Kept`, and through the
    window the expert layers' per-step values (scalar tops the step
    returns anyway; fetched after the window)."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.kept = Kept()
        self.cap = {"losses": [], "ids": [], "targets": [], "rows": []}
        self.window_tops = []
        self.columns = ("input_ids", "target_ids")

    def _capture(self, n, batch, result):
        import jax
        p2, st2, out = result
        self.cap["losses"].append(float(out["loss"]))   # the step's own time
        t = time.perf_counter()
        if n == 0:
            self.kept("p0", flat(self.cap.pop("p0")))
        self.cap["ids"].append(np.asarray(batch[self.columns[0]]))
        self.cap["targets"].append(np.asarray(batch[self.columns[1]]))
        self.cap["rows"].append({k: np.asarray(v) for k, v in out.items()
                                 if k.endswith(".moe_rows")})
        if n == 0:
            self.kept("m1", flat(jax.device_get(st2.history)))
            self.kept("v1", flat(jax.device_get(st2.history2)))
            self.kept("p1", flat(jax.device_get(p2)))
        if n == CHECK_STEPS - 1:
            self.kept("p_last", flat(jax.device_get(p2)))
        self.overhead += time.perf_counter() - t

    def wrap(self, real):
        inner = super().wrap(real)

        def step(params, st, batch, rng):
            was_in = self.in_window
            result = inner(params, st, batch, rng)
            if was_in:          # a step of the window, the closing one too
                self.window_tops.append(
                    {k: v for k, v in result[2].items()
                     if k.endswith(".moe_stats")})
            return result

        return step


def moe_stats(window_tops) -> dict:
    """Per-step values of the expert layers over the window: the largest
    rows-per-held-expert max/mean of any layer and step, the mean share
    of the k N assignments that fell on held experts, and the dropped
    assignments summed.  {} for a program whose steps return none."""
    import jax
    vals = [np.asarray(v) for d in jax.device_get(window_tops)
            for v in d.values()]
    if not vals:
        return {}
    vals = np.stack(vals)
    return {"rows_max_over_mean": float(vals[:, 0].max()),
            "rows_max_over_mean_mean": float(vals[:, 0].mean()),
            "held_share": float(vals[:, 1].mean()),
            "dropped_assignments": float(vals[:, 2].sum())}


# --------------------------------------------------------------------- run

def run(ctx: dict) -> dict:
    """One run of a token-training cell.  ctx as `windows/train.run`."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from caffeonspark_tpu.caffe_on_spark import main as cos_main
    from caffeonspark_tpu.parallel import ParallelSolver
    from caffeonspark_tpu.processor import CaffeProcessor
    from ..harness import check, devices

    cfg, cell = ctx["config"], ctx["cell"]
    work = ctx["work"]
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    solver_path, rows, facts, batch = write_inputs(ctx, work)
    print(f"[perfbench] inputs {facts}", flush=True)
    seq = int(cfg["sequence_length"])

    trace_dir = os.path.join(work, "trace") if ctx["trace"] else None
    seconds = (min(ctx["seconds"], float(cell.get("trace_seconds", 6)))
               if ctx["trace"] else ctx["seconds"])
    obs = TokenObserver(seconds=seconds,
                        warmup_steps=int(cell["warmup_steps"]),
                        trace_dir=trace_dir, t_process0=ctx["t_process0"],
                        break_step=ctx.get("break_step"))
    obs.columns = tuple(ctx["traffic"]["columns"])
    from caffeonspark_tpu.data.queue_runner import transform_threads
    obs.round = max(1, transform_threads())
    jax.monitoring.register_event_duration_secs_listener(obs._on_compile)
    orig = ParallelSolver.train_step
    ParallelSolver.train_step = lambda ps: obs.wrap(orig(ps))

    def end_job():
        obs.done.wait()
        proc = CaffeProcessor._instance
        if proc is not None:
            proc.stop()             # the processor's own way to end a job
    ender = threading.Thread(target=end_job, daemon=True)
    ender.start()
    obs.stamps["job_started"] = time.perf_counter()
    try:
        rc = cos_main(["-conf", solver_path, "-train", "-output", work,
                       "-devices", str(ctx["chips"])])
    finally:
        ParallelSolver.train_step = orig
        obs.done.set()
        ender.join(120)
    if rc != 0 or obs.t_b is None:
        raise RuntimeError(f"-train returned {rc}; window "
                           f"{'closed' if obs.t_b else 'never closed'}")
    window_s = obs.t_b - obs.t_a
    steps = obs.n_b - obs.n_a
    setup_s, drain_s, drain_steps = obs.setup_and_drain()
    peak = obs.memory_peak
    obs.stamps["window_start"] = obs.t_a
    print("[perfbench] set-up, seconds from process start: "
          + ", ".join(f"{k} {v - obs.t0:.2f}" for k, v in obs.stamps.items()),
          flush=True)
    print(f"[perfbench] device memory {devices.memory_report()}", flush=True)
    losses = [float(v) for v in jax.device_get(obs.window_losses)]
    experts = moe_stats(obs.window_tops)
    obs.window_losses, obs.window_tops = [], []
    if steps >= 8:      # drift of the rate inside the window, for a reader
        ends = [obs.step_ends[steps * k // 8 - 1] - obs.t_a
                for k in range(1, 9)]
        print("[perfbench] eighths of the window's steps were dispatched "
              "by " + " ".join(f"{t:.2f}" for t in ends) + " s", flush=True)
    print(f"[perfbench] window {window_s:.4f} s, {steps} steps of {batch} "
          f"sequences of {seq} tokens (pool rounds of {obs.round}); peak "
          f"HBM {peak} bytes; set-up {setup_s:.2f} s "
          f"(+{obs.overhead:.2f} s copying for the comparison); "
          f"warm-up drain {drain_s:.2f} s, {drain_steps} steps; "
          f"compiles in window {obs.compiles_in_window}; window losses "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}; experts {experts}",
          flush=True)
    gc.collect()
    print(f"[perfbench] device memory before the reference "
          f"{devices.memory_now()} bytes", flush=True)

    # ---- the comparison, after the window and outside setup_s ---------
    t_ref = time.perf_counter()
    model = importlib.import_module(
        "perfbench.reference." + cfg["reference"])
    nums = {"nonfinite_window_losses":
            sum(1 for v in losses if not math.isfinite(v)),
            "compiles_in_window": obs.compiles_in_window,
            "dropped_assignments": experts.get("dropped_assignments", 0.0)}
    batches, token_gap = [], 0.0
    for k in range(CHECK_STEPS):
        found, gap = match_rows(obs.cap["ids"][k], obs.cap["targets"][k],
                                rows)
        token_gap = max(token_gap, gap)
        batches.append((rows[found, :-1], rows[found, 1:]))
    nums["ingest_token_gap"] = token_gap
    ref_kept = Kept()
    with jax.default_device(jax.local_devices()[0]):
        ref = model.train_steps(cfg, ctx["seed"], batches, ref_kept)
    nums.update(numbers(obs.kept, obs.cap["losses"], ref_kept,
                        ref["losses"], model.lr_mults(cfg)))
    # the routing, as far as the step's own tops show it: rows per held
    # expert of every layer against the reference's, half the summed
    # difference over the assignments held (a lower bound on the share of
    # assignments routed otherwise)
    diff = held = 0.0
    for k, per_layer in enumerate(obs.cap["rows"]):
        want = np.asarray(ref["counts"][k], np.float64)
        for name, got in per_layer.items():
            layer = int(name.split(".")[0][1:])
            diff += float(np.abs(got - want[layer]).sum())
            held += float(want[layer].sum())
    if held:
        nums["router_choice_mismatch_pct"] = 100.0 * diff / 2 / held
    correct = check.verdict(nums, cell["limits"])
    print(f"[perfbench] comparison took {time.perf_counter() - t_ref:.2f} s"
          " (the reference's states arrived at "
          + ", ".join(f"{n} +{t - t_ref:.1f}" for n, t in ref_kept.at)
          + " s)", flush=True)
    return {
        "correct": correct, "nums": nums,
        "attempted": steps, "failed": nums["nonfinite_window_losses"],
        "window_s": window_s, "steps": steps, "images": steps * batch,
        "setup_s": setup_s, "warmup_drain_s": drain_s,
        "warmup_drain_steps": drain_steps, "memory_peak_bytes": peak,
        "pipeline": (obs.metrics_a, obs.metrics_b), "batch": batch,
        "trace_dir": trace_dir, "facts": facts, "losses": losses,
        "experts": experts,
        "flops_per_step": 3 * model.forward_flops(cfg, seq, batch),
    }
