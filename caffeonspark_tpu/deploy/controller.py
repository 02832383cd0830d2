"""DeployController: the continuous-deployment loop on one box.

    stream grows ──▶ fine-tune K steps ──▶ canary gate ──▶ fleet roll
         ▲  (bounded   (resume newest      (accept /        (rolling_
         │   re-poll)   good snapshot)      reject /          reload)
         │                                  aborted)            │
         └──────────────── incumbent keeps serving ◀── rollback ┘
                                                       on mid-roll
                                                       failure

One process tree exercises ingest → train → snapshot → canary →
fleet end to end: the controller owns the streaming source, the
in-process fine-tuner, the canary gate (one replica subprocess per
round), and the serving fleet (N replica subprocesses behind the
router).  The rollback invariant: the fleet only ever serves the
incumbent or a canary-accepted candidate — a rejected/aborted
candidate is reaped without touching the fleet, and a roll that
fails mid-way is rolled back to the incumbent before the round ends.

Verdict history, per-state counters, and the knobs publish as
`info.deploy` in PipelineMetrics (beside `info.comm` / `info.sync` /
`info.autotune` / `info.faults`), so every drill and bench artifact
states exactly what the loop decided and why.

Knobs (see docs/tuning.md):
  COS_DEPLOY_STEPS        fine-tune steps per round (default 20)
  COS_DEPLOY_MIN_NEW      new records required to trigger a round
  COS_DEPLOY_POLL_S       stream growth wait deadline per round
  COS_DEPLOY_EVAL_N       held-out eval records per canary round
  COS_DEPLOY_ACC_TOL      accuracy tolerance vs incumbent
  COS_DEPLOY_P99_RATIO    p99 budget: incumbent x ratio + slack
  COS_DEPLOY_P99_SLACK_MS
  COS_DEPLOY_CANARY_TIMEOUT_S  canary spawn→healthy deadline
  COS_DEPLOY_ROUNDS       rounds the -deploy CLI runs (default 3)
"""

from __future__ import annotations

import logging
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..data.source import get_source
from ..metrics import PipelineMetrics
from ..serving.fleet import Fleet
from ..tools import chaos
from ..tools.supervisor import pick_snapshot
from ..utils.chips import local_tpu_chips
from ..utils.envutils import env_int, env_num
from .canary import ACCEPT, CanaryGate, EvalRecord
from .finetune import FineTuner

_LOG = logging.getLogger(__name__)

ROLLED_BACK = "rolled_back"
SKIPPED = "skipped"


def deploy_rounds(default: int = 3) -> int:
    """COS_DEPLOY_ROUNDS: rounds the -deploy CLI runs."""
    return max(1, env_int("COS_DEPLOY_ROUNDS", default))


class DeployController:
    """Owns the loop; one instance per deployment."""

    def __init__(self, conf, *, stream_source=None,
                 eval_records: Optional[List[EvalRecord]] = None,
                 replicas: int = 0, steps: Optional[int] = None,
                 env: Optional[Dict[str, str]] = None,
                 metrics: Optional[PipelineMetrics] = None):
        if conf.netParam is None:
            raise ValueError("-deploy needs -conf resolving a solver "
                             "+ net prototxt")
        if not conf.outputPath:
            raise ValueError("-deploy needs -output (snapshot + "
                             "lineage directory)")
        if local_tpu_chips():
            # checked first: building the fine-tuner below already
            # initialises the JAX backend, which claims the chips
            raise RuntimeError(
                "-deploy fine-tunes in this process, which holds every "
                "TPU chip of the host, and then starts serving replicas "
                "and a canary as child processes that each need one: a "
                "chip belongs to one process at a time, so they would "
                "fail or hang in warm-up (ROADMAP queue 3 item 4)")
        self.conf = conf
        self.outdir = conf.outputPath
        self.metrics = metrics or PipelineMetrics()
        self.env = dict(env) if env else {}
        # the serving blob the gate argmaxes: first -features entry
        self.blob = next((b.strip() for b in
                          (conf.features or "").split(",")
                          if b.strip()), None)
        if not self.blob:
            raise ValueError("-deploy needs -features naming the "
                             "logits blob the canary gate scores "
                             "(e.g. -features ip)")
        # stream source: the TRAIN data layer must be a streaming-
        # capable source (poll/wait_for_records) unless injected
        if stream_source is None:
            layer = conf.train_data_layer()
            if layer is None:
                raise ValueError("-deploy needs a TRAIN-phase data "
                                 "layer (the stream)")
            stream_source = get_source(layer, phase_train=True,
                                       rank=0, num_ranks=1,
                                       resize=conf.resize)
        if not hasattr(stream_source, "wait_for_records"):
            raise ValueError(
                f"-deploy needs a streaming source (source_class "
                f"\"StreamingDir\"), got "
                f"{type(stream_source).__name__}")
        self.source = stream_source
        self.finetuner = FineTuner(conf, stream_source, self.outdir,
                                   steps=steps)
        self.eval_n = env_int("COS_DEPLOY_EVAL_N", 64)
        self.eval_records = (eval_records
                             if eval_records is not None
                             else self._eval_from_test_layer())
        if not self.eval_records:
            raise ValueError("-deploy needs a held-out eval set: a "
                             "TEST-phase data layer in the net "
                             "prototxt, or eval_records=")
        serve_args = ["-conf", conf.protoFile,
                      "-features", conf.features]
        if conf.label:
            serve_args += ["-label", conf.label]
        if getattr(conf, "resize", False):
            serve_args += ["-resize"]
        self._serve_args = serve_args
        self.gate = CanaryGate(serve_args, self.blob, env=self.env)
        self.replicas = (replicas or conf.serveReplicas
                         or env_int("COS_SERVE_REPLICAS", 1))
        self.fleet: Optional[Fleet] = None
        self.incumbent: Optional[str] = None
        # knobs (resolved once, host-side — COS003 discipline;
        # eval_n above, before the eval set is read)
        self.min_new = env_int("COS_DEPLOY_MIN_NEW", 1)
        self.poll_timeout_s = env_num("COS_DEPLOY_POLL_S", 30.0)
        self.injector = chaos.make_injector()
        self.history: List[dict] = []
        self.counts = {ACCEPT: 0, "reject": 0, "aborted": 0,
                       ROLLED_BACK: 0, SKIPPED: 0}
        self.mirror_failures = 0     # failed LIVE-fleet requests: 0
        self._round_i = 0
        self._publish_info()

    # -- setup --------------------------------------------------------
    def _eval_from_test_layer(self) -> List[EvalRecord]:
        """Held-out eval = the solver prototxt's TEST data layer (the
        CaffeOnSpark place a validation set lives), read once."""
        layer = self.conf.test_data_layer()
        if layer is None:
            return []
        src = get_source(layer, phase_train=False, rank=0,
                         num_ranks=1, resize=self.conf.resize)
        n = self.eval_n
        out: List[EvalRecord] = []
        for rec in src.records():
            rid, label, c, h, w, encoded, payload = rec
            # RAW pixels only — the serving replica applies the
            # test-phase transform itself, so the payload must be the
            # untransformed record (a pre-scaled payload would be
            # double-transformed)
            if encoded:
                import base64
                payload_json = {"id": rid, "image_b64":
                                base64.b64encode(payload).decode()}
            else:
                if isinstance(payload, np.ndarray):
                    data = payload.reshape(c, h, w)
                else:
                    data = np.frombuffer(payload, np.uint8).astype(
                        np.float32).reshape(c, h, w)
                payload_json = {"id": rid, "data": data.tolist()}
            out.append((payload_json, int(label)))
            if len(out) >= n:
                break
        return out

    def ensure_incumbent(self) -> str:
        """The model the fleet boots on: newest good snapshot if one
        exists, else a bootstrap fine-tune round (the initial deploy
        is unvetted by construction — there is nothing to canary
        against yet)."""
        if self.incumbent:
            return self.incumbent
        pair = pick_snapshot(self.outdir,
                             self.finetuner.prefix,
                             frozenset(self.finetuner.bad))
        if pair is not None:
            self.incumbent = pair[1]
        else:
            # the bootstrap needs records to EXIST, not to grow — a
            # pre-seeded quiet stream (absorbed by the source's
            # construction-time poll) must train immediately instead
            # of sleeping the whole growth deadline
            if self.source.total_records == 0:
                self.source.wait_for_records(
                    1, timeout_s=self.poll_timeout_s,
                    injector=self.injector)
            ft = self.finetuner.round(injector=self.injector)
            self.incumbent = ft.model_path
        return self.incumbent

    def start(self) -> "DeployController":
        model = self.ensure_incumbent()
        self.fleet = Fleet(
            self._serve_args + ["-model", model],
            self.replicas, env=self.env, metrics=self.metrics)
        self.fleet.start()
        self._publish_info()
        return self

    def stop(self) -> None:
        if self.fleet is not None:
            self.fleet.stop()
            self.fleet = None

    # -- chaos --------------------------------------------------------
    def refresh_faults(self, env: Optional[dict] = None) -> None:
        """Re-resolve COS_FAULT_* (host-side) — drills/bench flip the
        deploy knobs between rounds; a long-lived controller picks
        them up here instead of re-reading env anywhere else.  `env`
        optionally applies `{COS_FAULT_*: value|None}` updates first
        (chaos.apply_fault_env — the prodday scenario engine's
        scheduled-chaos hook; None clears a knob)."""
        if env:
            chaos.apply_fault_env(env)
        self.injector = chaos.make_injector()
        self._publish_info()

    # -- live-fleet mirror --------------------------------------------
    def mirror_incumbent(self) -> Tuple[Optional[float],
                                        Optional[float]]:
        """The incumbent's numbers, measured by mirroring the held-out
        eval through the LIVE fleet (router → replicas — the same path
        client traffic takes, so p99 is comparable with the canary's).
        Router retries absorb replica churn; anything that still
        surfaces counts as a failed client request (the drills pin
        this at zero)."""
        assert self.fleet is not None, "controller not started"
        lats: List[float] = []
        rows: List[List[float]] = []
        labels: List[int] = []
        for payload, label in self.eval_records[:self.eval_n]:
            try:
                t0 = time.monotonic()
                out = self.fleet.router.predict(payload)
                lat_ms = (time.monotonic() - t0) * 1e3
            except Exception as e:    # noqa: BLE001 — counted, not raised
                self.mirror_failures += 1
                _LOG.error("deploy mirror: LIVE fleet request "
                           "failed: %s", e)
                continue
            row = out["rows"][0]
            if self.blob in row:
                # accuracy and p99 cover the SAME request set — a row
                # without the scored blob contributes to neither
                rows.append(row[self.blob])
                labels.append(int(label))
                lats.append(lat_ms)
        if not rows:
            return None, None
        from .canary import _p99, eval_outcome
        return eval_outcome(rows, labels), _p99(lats)

    # -- the loop -----------------------------------------------------
    def run_round(self, *, label_shuffle: bool = False) -> dict:
        """One round: wait for growth → fine-tune → canary → roll or
        rollback.  Returns the round record (also appended to
        `history` and published in info.deploy)."""
        assert self.fleet is not None, "call start() first"
        i = self._round_i
        self._round_i += 1
        t0 = time.monotonic()
        rec: dict = {"round": i}
        grew = self.source.wait_for_records(
            self.min_new, timeout_s=self.poll_timeout_s,
            injector=self.injector)
        rec["new_records"] = grew
        rec["stream"] = self.source.describe()
        if grew < self.min_new:
            rec.update(verdict=SKIPPED,
                       reason=f"stream grew {grew} < {self.min_new} "
                              f"records within {self.poll_timeout_s}s")
            return self._finish_round(rec, t0)
        try:
            ft = self.finetuner.round(label_shuffle=label_shuffle,
                                      injector=self.injector)
        except Exception as e:       # noqa: BLE001 — skip, don't die
            _LOG.error("deploy: fine-tune round failed: %s", e)
            rec.update(verdict=SKIPPED,
                       reason=f"fine-tune failed: {e}")
            return self._finish_round(rec, t0)
        rec["finetune"] = {
            "start_iter": ft.start_iter, "end_iter": ft.end_iter,
            "mean_loss": (None if ft.mean_loss != ft.mean_loss
                          else round(ft.mean_loss, 5)),
            "resumed_from": ft.resumed_from,
            "skipped_pairs": ft.skipped_pairs,
            "label_shuffled": ft.label_shuffled,
            "truncated": ft.truncated,
        }
        incumbent_stats = self.mirror_incumbent()
        if self.incumbent is not None and incumbent_stats[0] is None:
            # an incumbent EXISTS but the live fleet could not be
            # measured (unreachable mid-churn): decide_verdict would
            # read (None, None) as "bootstrap — accept", so a
            # transient fleet outage must skip the round, never
            # auto-publish an unjudged candidate
            self.finetuner.mark_bad(ft.state_path)
            rec.update(verdict=SKIPPED,
                       reason="live-fleet mirror produced no "
                              "incumbent numbers — candidate held")
            return self._finish_round(rec, t0)
        verdict = self.gate.evaluate(
            ft.model_path, self.eval_records[:self.eval_n],
            incumbent_stats, injector=self.injector)
        rec["canary"] = verdict.describe()
        final = verdict.verdict
        if final == ACCEPT:
            try:
                self.fleet.rolling_reload(
                    ft.model_path,
                    before_reload=self._chaos_before_reload)
                self.incumbent = ft.model_path
            except Exception as e:   # noqa: BLE001 — roll failed
                _LOG.error("deploy: rolling reload failed mid-way "
                           "(%s) — rolling back to incumbent", e)
                rec["roll_error"] = f"{type(e).__name__}: {e}"
                rollback_versions = self.fleet.rollback()
                rec["rollback_versions"] = rollback_versions
                self.finetuner.mark_bad(ft.state_path)
                final = ROLLED_BACK
                rec["reason"] = ("accepted by the canary but the "
                                 f"roll failed mid-way ({e}) — "
                                 "rolled back to the incumbent")
        else:
            # rejected/aborted candidates must not seed the next
            # round's resume — fall back to the incumbent lineage
            self.finetuner.mark_bad(ft.state_path)
        rec["verdict"] = final
        rec.setdefault("reason", verdict.reason)
        return self._finish_round(rec, t0)

    def _chaos_before_reload(self, name: str, index: int) -> None:
        """COS_FAULT_RELOAD_FAIL_RANK: kill replica `index` right
        before its swap — the mid-roll failure the rollback drill
        injects."""
        if self.injector.reload_fail_due(index):
            assert self.fleet is not None
            self.fleet.kill_replica(name)

    def _finish_round(self, rec: dict, t0: float) -> dict:
        rec["wall_s"] = round(time.monotonic() - t0, 3)
        rec["incumbent"] = self.incumbent
        from ..obs.recorder import record as record_event
        record_event("deploy", "round", round=rec["round"],
                     verdict=rec["verdict"],
                     reason=rec.get("reason"),
                     incumbent=self.incumbent)
        self.counts[rec["verdict"]] = \
            self.counts.get(rec["verdict"], 0) + 1
        self.metrics.incr("deploy_rounds")
        self.metrics.incr(f"deploy_{rec['verdict']}")
        self.metrics.add("deploy_round", rec["wall_s"])
        self.history.append(rec)
        self._publish_info()
        return rec

    def run(self, rounds: int) -> List[dict]:
        return [self.run_round() for _ in range(rounds)]

    # -- reporting ----------------------------------------------------
    def _publish_info(self) -> None:
        """info.deploy: the loop's state machine, self-described the
        way info.comm/info.sync/info.autotune are."""
        self.metrics.set_info("deploy", {
            "incumbent": self.incumbent,
            "rounds": self._round_i,
            "counts": dict(self.counts),
            "mirror_failures": self.mirror_failures,
            "replicas": self.replicas,
            "blob": self.blob,
            "knobs": {
                "steps": self.finetuner.steps,
                "min_new": self.min_new,
                "poll_timeout_s": self.poll_timeout_s,
                "eval_n": self.eval_n,
                "acc_tol": self.gate.acc_tol,
                "p99_ratio": self.gate.p99_ratio,
                "p99_slack_ms": self.gate.p99_slack_ms,
            },
            # bounded verdict history (the full record set lives in
            # the controller / bench artifact)
            "verdicts": [
                {"round": r["round"], "verdict": r["verdict"],
                 "accuracy": (r.get("canary") or {}).get("accuracy"),
                 "incumbent_accuracy":
                     (r.get("canary") or {}).get(
                         "incumbent_accuracy")}
                for r in self.history[-32:]],
        })
        self.metrics.set_info("faults",
                              self.injector.plan.describe())

    def metrics_summary(self) -> dict:
        out = (self.fleet.metrics_summary()
               if self.fleet is not None else self.metrics.summary())
        if self.fleet is not None:
            # fleet summary is router-rooted; graft the deploy info
            out.setdefault("info", {}).update(
                self.metrics.summary().get("info", {}))
        return out
