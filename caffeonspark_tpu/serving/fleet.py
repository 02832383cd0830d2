"""Fleet: N serving replica processes behind one router.

The process plumbing is the training supervisor's
(`tools/supervisor.py`): spawn one process per replica, watch them,
and when one dies relaunch it — but where the supervisor tears the
WHOLE cluster down (a dead training rank wedges the survivors inside
the gradient collective), serving replicas share nothing, so the
fleet restarts exactly the dead one while the router keeps routing
around it.  With COS_AOT_CACHE_DIR set, every replica warms from the
shared persistent compilation cache (serving/aot.py), so a restarted
or scaled-up replica is serving again in seconds — its warmup is
cache hits, not fresh XLA compiles.

Each replica is the UNCHANGED single-process stack: one
`caffe_on_spark.py -serve` process (InferenceService + HTTP) on an
ephemeral port, discovered from the startup JSON line the serve CLI
prints.  The fleet layer never reaches into a replica — everything
goes over the same HTTP surface operators script against.

    fleet = Fleet(["-conf", solver, "-model", m], replicas=4)
    fleet.start()                       # spawn, wait healthy, route
    fleet.router.predict({...})
    fleet.rolling_reload(new_model)     # drain+reload one at a time
    fleet.stop()

Knob: COS_SERVE_REPLICAS (the `-serveReplicas` CLI default).

Multi-host: with `agents=[...]` (or COS_AGENTS=url,url,...) the fleet
becomes a host-aware scheduler — replicas are spawned through NodeAgent
daemons (`tools/nodeagent.py`) instead of forked locally, replica i's
home is agents[i % n], a dead replica respawns on the first LIVE agent
(failover after COS_FAULT_HOST_KILL), and agent heartbeats feed the
`hosts` block of metrics_summary (the `cos_host_up` gauge).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..metrics import PipelineMetrics
from ..obs.recorder import record as record_event
from ..tools.nodeagent import (AGENT_ERRORS, AgentProc, agent_call,
                               agent_env_overlay, agent_urls_from_env)
from ..tools.supervisor import terminate_processes
from ..utils.chips import chip_env, local_chip_ids, require_chips
from .batcher import _env_int
from .retry import RetryPolicy
from .router import (DOWN, OK, STARTING, TRANSPORT_ERRORS, Router,
                     RouterRequestError, http_json)

_LOG = logging.getLogger(__name__)


def serve_replicas(default: int = 1) -> int:
    """COS_SERVE_REPLICAS: fleet size when the CLI flag is absent."""
    return max(1, _env_int("COS_SERVE_REPLICAS", default))


def _args_with_model(args: List[str], model_path: str) -> List[str]:
    """Respawn args after a rolling reload: the new model supersedes
    whatever weights source (-model/-weights/-snapshot) the fleet was
    launched with, so a replica that dies AFTER the swap rejoins on
    the NEW version instead of silently reintroducing the old one."""
    out, skip = [], False
    for a in args:
        if skip:
            skip = False
        elif a in ("-model", "-weights", "-snapshot"):
            skip = True
        else:
            out.append(a)
    return out + ["-model", model_path]


def _model_from_args(args: List[str]) -> Optional[str]:
    """The default-model weights source named by serve args (`-model`
    wins, then `-weights`, then `-snapshot` — a .solverstate is a
    valid reload target too, its learned_net pointer resolves the
    model) — the fleet's initial 'incumbent' for pre-roll
    bookkeeping.  Every validly-launched serve fleet names one of the
    three, so the abandoned-roll repoint and rollback() always have a
    lineage to return to."""
    found: Dict[str, str] = {}
    for i, a in enumerate(args):
        if a in ("-model", "-weights", "-snapshot") \
                and i + 1 < len(args):
            found[a] = args[i + 1]
    return (found.get("-model") or found.get("-weights")
            or found.get("-snapshot"))


class ReplicaProcess:
    """One `-serve` subprocess: spawn, discover the ephemeral port
    from the startup JSON line, wait until /healthz answers."""

    def __init__(self, name: str, serve_args: List[str],
                 env: Optional[Dict[str, str]] = None,
                 host: str = "127.0.0.1"):
        self.name = name
        self.serve_args = list(serve_args)
        self.env = dict(env) if env else None
        self.host = host
        self.host_name = ""         # NodeAgent host name ("" = local)
        self.proc: Optional[subprocess.Popen] = None
        self.port: Optional[int] = None
        self._port_ready = threading.Event()
        self.t_spawn: Optional[float] = None
        self.t_ready: Optional[float] = None
        self.restart_count = 0      # lifetime restarts of THIS replica
        # scale-down marks the replica retired BEFORE draining it, so
        # the death monitor never resurrects a replica the fleet is
        # deliberately retiring (terminate looks exactly like a death)
        self.retired = False
        # what the RUNNING process actually booted with (captured at
        # spawn — serve_args may be repointed after the fork, e.g. by
        # an abandoned roll's verdict repoint racing a respawn)
        self.booted_model: Optional[str] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def spawn(self) -> "ReplicaProcess":
        cmd = [sys.executable, "-m", "caffeonspark_tpu.caffe_on_spark",
               "-serve", "-serveHost", self.host, "-servePort", "0",
               "-serveReplicas", "1"] + self.serve_args
        env = dict(os.environ)
        # the child must import THIS checkout whether or not the
        # package is pip-installed (tests/bench run from the repo)
        pkg_parent = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = pkg_parent + (
            os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else "")
        if self.env:
            env.update(self.env)
        # a FRESH event per spawn: the previous process's stdout
        # reader still holds the old one, so its EOF set() (which can
        # land after a respawn's clear under contention) cannot spoof
        # readiness for the new process
        evt = threading.Event()
        self._port_ready = evt
        self.port = None
        self.t_spawn = time.monotonic()
        self.t_ready = None
        self.booted_model = _model_from_args(self.serve_args)
        self.proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                     env=env, text=True)
        threading.Thread(target=self._read_stdout,
                         args=(self.proc, evt),
                         name=f"cos-fleet-{self.name}-stdout",
                         daemon=True).start()
        return self

    def _read_stdout(self, proc, evt):
        """First JSON line carries the bound port; keep draining after
        that so the child never blocks on a full pipe.  `proc`/`evt`
        are this spawn's own — a stale reader never touches the
        replica's current port."""
        try:
            for line in proc.stdout:
                if self.port is None and self.proc is proc:
                    try:
                        msg = json.loads(line)
                        if msg.get("serving"):
                            self.port = int(msg["port"])
                            evt.set()
                    except (ValueError, KeyError, TypeError):
                        pass
        except (OSError, ValueError):
            pass
        finally:
            evt.set()                   # EOF: unblock waiters (death)

    def wait_ready(self, timeout_s: float = 180.0,
                   stop_evt: Optional[threading.Event] = None) -> bool:
        """True once /healthz answers 200 (model loaded, warmup done —
        the serve CLI prints its startup line only after start()).
        `stop_evt` aborts the wait early (the fleet monitor passes its
        stop event so Fleet.stop() is not held behind a warmup)."""
        deadline = time.monotonic() + timeout_s
        self._port_ready.wait(timeout_s)
        if self.port is None:
            return False
        while time.monotonic() < deadline:
            if stop_evt is not None and stop_evt.is_set():
                return False
            if self.proc is None or self.proc.poll() is not None:
                return False
            try:
                code, body = http_json(self.url + "/healthz",
                                       timeout=5.0)
                if code == 200:
                    self.t_ready = time.monotonic()
                    return True
            except TRANSPORT_ERRORS + (OSError, ValueError):
                pass
            time.sleep(0.05)
        return False

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self) -> None:
        """Hard-kill (fault injection: the tests' and bench's replica
        failure is this, not a graceful stop)."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait(timeout=30)

    def terminate(self, grace: float = 10.0) -> None:
        if self.proc is not None:
            terminate_processes([self.proc], grace=grace)


class AgentReplicaProcess(ReplicaProcess):
    """A replica scheduled onto a NodeAgent instead of forked locally.
    Only `spawn()` changes: the serve argv goes to an agent's POST
    /v1/spawn (trying the agent list round-robin from this replica's
    home index — the failover that lands a respawn on a SURVIVING
    host after COS_FAULT_HOST_KILL), `self.proc` becomes the
    Popen-mimicking `AgentProc`, and the boot port is discovered by
    polling the agent's proc record (the agent tails the child's
    stdout) instead of reading a local pipe.  Everything else —
    wait_ready, alive, kill, terminate, the monitor's restart
    bookkeeping — is inherited untouched."""

    def __init__(self, name: str, serve_args: List[str],
                 env: Optional[Dict[str, str]] = None,
                 agents: Optional[List[str]] = None,
                 agent_index: int = 0):
        super().__init__(name, serve_args, env=env)
        self.agents = [u.rstrip("/") for u in (agents or [])]
        if not self.agents:
            raise ValueError(f"{name}: AgentReplicaProcess needs at "
                             "least one agent URL")
        self._agent_i = agent_index % len(self.agents)
        self.agent_url: Optional[str] = None

    def spawn(self) -> "AgentReplicaProcess":
        from urllib.parse import urlsplit
        evt = threading.Event()
        self._port_ready = evt
        self.port = None
        self.t_spawn = time.monotonic()
        self.t_ready = None
        self.booted_model = _model_from_args(self.serve_args)
        overlay = agent_env_overlay(self.env)
        last: Optional[BaseException] = None
        for k in range(len(self.agents)):
            url = self.agents[(self._agent_i + k) % len(self.agents)]
            bind = urlsplit(url).hostname or "127.0.0.1"
            cmd = [sys.executable, "-m",
                   "caffeonspark_tpu.caffe_on_spark", "-serve",
                   "-serveHost", bind, "-servePort", "0",
                   "-serveReplicas", "1"] + self.serve_args
            try:
                doc = agent_call(url, "/v1/spawn",
                                 data={"argv": cmd, "env": overlay,
                                       "name": self.name},
                                 timeout=15.0)
            except AGENT_ERRORS as e:
                last = e
                continue
            self._agent_i = (self._agent_i + k) % len(self.agents)
            self.agent_url = url
            self.host_name = str(doc.get("host") or "")
            self.host = bind
            proc = AgentProc(url, doc["proc"], pid=doc.get("pid"))
            self.proc = proc
            threading.Thread(target=self._poll_agent_port,
                             args=(proc, evt),
                             name=f"cos-fleet-{self.name}-agentport",
                             daemon=True).start()
            return self
        # every agent unreachable: raise rather than fabricate a dead
        # proc — Fleet.start tears down, and the monitor's try/except
        # retries next pass (hosts may be coming back)
        raise RuntimeError(f"{self.name}: no live NodeAgent among "
                           f"{self.agents}") from last

    def _poll_agent_port(self, proc: AgentProc,
                         evt: threading.Event) -> None:
        """The agent's stdout tail discovers the replica's boot line;
        surface the port here with the same staleness guard as the
        local pipe reader (`proc`/`evt` are this spawn's own)."""
        try:
            while self.proc is proc:
                info = proc.info()
                port = info.get("port")
                if port and self.proc is proc:
                    self.port = int(port)
                    break
                if not info.get("alive"):
                    break
                time.sleep(0.05)
        except AGENT_ERRORS:
            pass
        finally:
            evt.set()


class Fleet:
    """Replica processes + router + restart-on-death monitor."""

    def __init__(self, serve_args: List[str], replicas: int = 0, *,
                 env: Optional[Dict[str, str]] = None,
                 policy: Optional[RetryPolicy] = None,
                 startup_timeout_s: float = 180.0,
                 poll_interval_s: float = 0.25,
                 max_restarts: int = 10,
                 metrics: Optional[PipelineMetrics] = None,
                 agents: Optional[List[str]] = None):
        self.serve_args = list(serve_args)
        self.n = replicas or serve_replicas()
        self.env = dict(env) if env else {}
        # multi-host: NodeAgent endpoints to schedule replicas onto
        # (explicit arg > COS_AGENTS env; empty = fork locally).
        # Replica i's HOME agent is agents[i % n] — spread by default,
        # failover handled inside AgentReplicaProcess.spawn
        self.agents = ([u.rstrip("/") for u in agents] if agents
                       else agent_urls_from_env())
        self._agent_state: Dict[str, dict] = {}   # url -> host/up/ts
        self._agents_next_poll = 0.0
        self.startup_timeout_s = startup_timeout_s
        self.poll_interval_s = poll_interval_s
        self.max_restarts = max_restarts
        self.metrics = metrics or PipelineMetrics()
        self.router = Router(policy=policy, metrics=self.metrics)
        self.replicas: Dict[str, ReplicaProcess] = {}
        self._monitor: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._restarts = 0
        # named models published fleet-wide (publish_model): specs are
        # remembered so a replica that dies and respawns — which boots
        # with only the DEFAULT model from its argv — gets every named
        # model re-published by the monitor before it rejoins
        self._published_models: Dict[str, dict] = {}
        self._published_lock = threading.Lock()
        # default-model lineage for rolling reloads: the LAST model the
        # fleet committed to (argv at start; advanced only when a roll
        # COMPLETES).  A roll that fails mid-way leaves this at the
        # incumbent — rollback() re-rolls survivors to it, and respawn
        # args follow the roll's final verdict, not its high-water mark
        self._default_model: Optional[str] = _model_from_args(
            self.serve_args)
        self.pre_roll_model: Optional[str] = None
        self._roll_active = False
        # monotonic replica-name/index counter: scale-up never reuses
        # an index (COS_REPLICA_INDEX targets per-replica chaos, and a
        # recycled name would alias recorder timelines)
        self._next_index = self.n

    # -- lifecycle ----------------------------------------------------
    def start(self) -> "Fleet":
        """Spawn every replica, wait until each is healthy, then open
        routing and start the death monitor.  Spawns overlap (the
        expensive part of a cold start is each process's own warmup
        compile — with the AOT cache, replica 0 fills it and the rest
        mostly hit it)."""
        # a chip belongs to one process at a time: on a TPU host each
        # local replica of a fleet of several gets its OWN chip through
        # its environment (a fleet of one keeps every chip, for
        # -serveMesh), and a fleet larger than the host is refused
        # here — before anything is spawned, and without this process
        # touching a JAX backend (which would claim the chips itself)
        chips = [] if self.agents else require_chips(
            self.n, f"a fleet of {self.n} replicas")
        try:
            for i in range(self.n):
                name = f"replica{i}"
                # the fleet-assigned index rides into the subprocess
                # so per-replica chaos (COS_FAULT_REPLICA_SLOW) can
                # target one replica; respawns reuse this env dict,
                # keeping the index (and the chip) stable across
                # restarts
                renv = dict(self.env, COS_REPLICA_INDEX=str(i))
                if chips and self.n > 1:
                    renv.update(chip_env(chips[i]))
                if self.agents:
                    rep: ReplicaProcess = AgentReplicaProcess(
                        name, self.serve_args, env=renv,
                        agents=self.agents, agent_index=i)
                else:
                    rep = ReplicaProcess(name, self.serve_args,
                                         env=renv)
                self.replicas[name] = rep.spawn()
                self.router.add_replica(name, "http://unbound",
                                        state=STARTING,
                                        host=rep.host_name)
            for name, rep in self.replicas.items():
                if not rep.wait_ready(self.startup_timeout_s):
                    raise RuntimeError(
                        f"fleet: {name} failed to become healthy "
                        f"within {self.startup_timeout_s}s")
                self.router.update_url(name, rep.url,
                                       host=rep.host_name or None)
                self.router.set_state(name, OK)
                if rep.t_ready and rep.t_spawn:
                    self.metrics.add("replica_startup",
                                     rep.t_ready - rep.t_spawn)
        except BaseException:
            # a failed spawn or warmup must not orphan the replicas
            # that DID come up (stale -serve processes pin the box)
            self.stop()
            raise
        self.router.start_health()
        self._stop_evt.clear()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="cos-fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        return self

    def stop(self, grace: float = 10.0) -> None:
        self._stop_evt.set()
        if self._monitor is not None:
            self._monitor.join(timeout=30)
            self._monitor = None
        self.router.stop()
        terminate_processes(
            [r.proc for r in self.replicas.values()
             if r.proc is not None], grace=grace)

    # -- restart-on-death ---------------------------------------------
    def _monitor_loop(self):
        while not self._stop_evt.wait(self.poll_interval_s):
            try:
                self._agents_once()
                self._monitor_once()
            except Exception as e:   # noqa: BLE001 — keep monitoring
                # a failed spawn (fork pressure, vanished binary) must
                # not kill the only restart path for the whole fleet
                _LOG.warning("fleet monitor pass failed: %s", e)

    def _agents_once(self):
        """Throttled NodeAgent heartbeat poll: tracks each agent's
        host name + liveness (what `cos_host_up` renders) and records
        host up/down transitions on the flight recorder — the
        host-level half of a kill-a-host incident timeline."""
        if not self.agents:
            return
        now = time.monotonic()
        if now < self._agents_next_poll:
            return
        self._agents_next_poll = now + 1.0
        for url in self.agents:
            prev = self._agent_state.get(url) or {}
            try:
                doc = agent_call(url, "/healthz", timeout=2.0)
                host = str(doc.get("host") or url)
                up = True
            except AGENT_ERRORS:
                host = prev.get("host") or url
                up = False
            if prev.get("up") != up:
                record_event("fleet", "host_up" if up else "host_down",
                             host=host, agent=url)
                if not up:
                    self.metrics.incr("host_down_events")
            self._agent_state[url] = {"host": host, "up": up,
                                      "ts": round(time.time(), 3)}

    def _monitor_once(self):
        for name, rep in list(self.replicas.items()):
            if rep.retired or rep.alive() or self._stop_evt.is_set():
                continue
            self.router.set_state(name, DOWN)
            # the budget is PER REPLICA: one crash-looping replica
            # must not spend the allowance of its healthy peers (nor
            # may sporadic recoverable deaths across a long-lived
            # fleet add up to a permanent no-restart state)
            if rep.restart_count >= self.max_restarts:
                _LOG.error("fleet: %s died; max_restarts (%d) "
                           "exhausted — leaving it down", name,
                           self.max_restarts)
                continue
            rep.restart_count += 1
            self._restarts += 1
            _LOG.warning("fleet: %s died (rc=%s) — restarting "
                         "(%d/%d)", name, rep.proc.returncode,
                         rep.restart_count, self.max_restarts)
            record_event("fleet", "replica_died", replica=name,
                         rc=rep.proc.returncode,
                         restart=rep.restart_count,
                         **({"host": rep.host_name}
                            if rep.host_name else {}))
            self.metrics.incr("replica_restarts")
            self.router.note_restart(name)
            t0 = time.monotonic()
            rep.spawn()
            # restarts serialize deliberately (one warmup at a
            # time); meanwhile the health poller keeps marking any
            # OTHER dead replica down, so routing stays correct
            if rep.wait_ready(self.startup_timeout_s,
                              stop_evt=self._stop_evt):
                # a respawn boots with only the argv default model:
                # re-publish every fleet-wide named model BEFORE the
                # replica rejoins rotation, or name-routed requests
                # would 404 on it until an operator noticed
                self._republish_models(rep)
                # heal a respawn that BOOTED on a model the fleet has
                # since moved away from — e.g. it was spawned with an
                # abandoned roll's candidate argv in the instant
                # before the abandonment repoint landed.  Outside a
                # live roll the committed default is the only version
                # a rejoining replica may serve.
                self._heal_respawn_model(rep)
                # new ephemeral port (and possibly a new HOST, after
                # a host kill): point the router at it BEFORE
                # reopening routing
                self.router.update_url(name, rep.url,
                                       host=rep.host_name or None)
                self.router.set_state(name, OK)
                self.metrics.add("replica_rejoin",
                                 time.monotonic() - t0)
                record_event("fleet", "replica_rejoined",
                             replica=name, url=rep.url,
                             wall_s=round(time.monotonic() - t0, 3),
                             **({"host": rep.host_name}
                                if rep.host_name else {}))
            else:
                _LOG.error("fleet: restarted %s failed to become "
                           "healthy", name)
                record_event("fleet", "restart_unhealthy",
                             replica=name)

    def _heal_respawn_model(self, rep: ReplicaProcess) -> None:
        """Reload a freshly-respawned replica onto the fleet's
        committed default model when what it BOOTED with differs —
        before it rejoins rotation.  No-op during a live roll (the
        per-replica repoint semantics govern there) and when the
        lineage is unknown."""
        desired = self._default_model
        if (self._roll_active or desired is None
                or rep.booted_model == desired):
            return
        try:
            code, body = http_json(
                rep.url + "/v1/reload",
                data=json.dumps({"model": desired}).encode(),
                timeout=120.0)
            if code != 200:
                _LOG.error("fleet: healing respawned %s onto %s "
                           "failed: %s", rep.name, desired, body)
                return
            _LOG.warning("fleet: respawned %s booted on %s — "
                         "reloaded onto the committed default %s "
                         "before rejoining", rep.name,
                         rep.booted_model, desired)
            rep.booted_model = desired
            rep.serve_args = _args_with_model(rep.serve_args, desired)
        except TRANSPORT_ERRORS + (OSError, ValueError) as e:
            _LOG.error("fleet: healing respawned %s onto %s "
                       "failed: %s", rep.name, desired, e)

    def _republish_models(self, rep: ReplicaProcess) -> None:
        with self._published_lock:
            specs = list(self._published_models.values())
        for spec in specs:
            try:
                code, body = http_json(
                    rep.url + "/v1/models",
                    data=json.dumps(spec).encode(), timeout=120.0)
                if code != 200:
                    _LOG.error("fleet: re-publishing model %r on "
                               "restarted %s failed: %s",
                               spec.get("name"), rep.name, body)
            except TRANSPORT_ERRORS + (ValueError,) as e:
                _LOG.error("fleet: re-publishing model %r on "
                           "restarted %s failed: %s",
                           spec.get("name"), rep.name, e)

    # -- operations ---------------------------------------------------
    def rolling_reload(self, model_path: str,
                       model_name: Optional[str] = None,
                       before_reload=None
                       ) -> Dict[str, int]:
        """Fleet-wide rolling swap.  Records the pre-roll default
        model (`pre_roll_model`) so an abandoned roll can be undone
        with `rollback()`.  Respawn args follow the roll's FINAL
        verdict: while the roll is live, a replica that dies after
        its own swap rejoins on the new version (repoint fires per
        replica), but if the roll fails mid-way the already-swapped
        replicas' respawn args are pointed BACK at the incumbent —
        the abandoned version must never be reintroduced by a
        restart-on-death respawn."""
        # serve_args repoint PER replica as each one's reload lands:
        # a replica that dies mid-roll after ITS swap must rejoin on
        # the NEW version (fresh list assignment — the monitor reads
        # serve_args only at spawn).  A NAMED model's reload instead
        # updates the remembered publish spec (argv only carries the
        # default model).
        swapped: List[str] = []

        def repoint(name: str) -> None:
            if model_name is not None:
                return
            swapped.append(name)
            rep = self.replicas.get(name)
            if rep is not None:
                rep.serve_args = _args_with_model(rep.serve_args,
                                                  model_path)
        if model_name is not None:
            with self._published_lock:
                spec = self._published_models.get(model_name)
                if spec is not None:
                    spec["model"] = model_path
        else:
            self.pre_roll_model = self._default_model
        self._roll_active = True
        try:
            out = self.router.rolling_reload(
                model_path, on_reloaded=repoint,
                model_name=model_name, before_reload=before_reload)
        except BaseException:
            if model_name is None:
                # roll abandoned: the verdict is the INCUMBENT.  Any
                # replica already repointed at the new model (swapped,
                # or swapped-then-died) must respawn on the incumbent;
                # rollback() re-rolls the live survivors.
                old = self.pre_roll_model
                if old is not None:
                    for name in swapped:
                        rep = self.replicas.get(name)
                        if rep is not None:
                            rep.serve_args = _args_with_model(
                                rep.serve_args, old)
            self._roll_active = False
            raise
        self._roll_active = False
        if model_name is None:
            self._default_model = model_path
        return out

    def rollback(self, wait_idle_s: float = 60.0) -> Dict[str, int]:
        """Re-roll every live replica back to the pre-roll default
        model (the incumbent a failed rolling_reload left recorded).
        Dead/unreachable replicas are skipped — their respawn args
        already point at the incumbent, so the monitor brings them
        back on the right version.  Returns {replica: version} for
        the replicas actually re-rolled."""
        target = self._default_model
        if target is None:
            raise RuntimeError(
                "rollback: no recorded default model (fleet launched "
                "without -model/-weights and never rolled)")
        record_event("fleet", "rollback_start", model=target)
        versions: Dict[str, int] = {}
        fail_kinds = TRANSPORT_ERRORS + (RouterRequestError,
                                         TimeoutError, OSError,
                                         ValueError)
        for name in self.router.names():
            rep = self.replicas.get(name)
            if rep is not None:
                rep.serve_args = _args_with_model(rep.serve_args,
                                                  target)
            try:
                self.router.drain_replica(name,
                                          wait_idle_s=wait_idle_s)
            except fail_kinds as e:
                # unreachable for the drain: if it is dead, the
                # monitor respawns it on `target` (argv above, plus
                # the respawn heal); if it is alive-but-wedged the
                # health poller re-admits it once it answers — and
                # the heal path cannot cover that, so say so loudly
                _LOG.error("fleet rollback: %s unreachable for "
                           "drain (%s) — skipped; a dead replica "
                           "respawns on the incumbent, a wedged "
                           "live one needs operator attention",
                           name, e)
                continue
            try:
                code, body = http_json(
                    self.router.replica_url(name) + "/v1/reload",
                    data=json.dumps({"model": target}).encode(),
                    timeout=120.0)
                if code != 200:
                    _LOG.error("fleet rollback: replica %s refused "
                               "the reload: %s — leaving it DRAINED "
                               "(serves nothing) rather than "
                               "re-admitting the abandoned version",
                               name, body)
                    continue
                self.router.undrain_replica(name)
                versions[name] = body.get("model_version", -1)
            except fail_kinds as e:
                # drained but the reload/undrain failed: keep it
                # DRAINED — capacity loss an operator can see beats
                # silently serving the abandoned version
                _LOG.error("fleet rollback: %s drained but its "
                           "reload failed (%s) — left drained",
                           name, e)
                continue
        self.metrics.incr("rollbacks")
        record_event("fleet", "rollback_done", model=target,
                     rerolled=sorted(versions))
        return versions

    def publish_model(self, spec: dict) -> Dict[str, dict]:
        """Publish a named model fleet-wide: POST the /v1/models spec
        ({"name", "solver", "model", ...}) to every live replica and
        REMEMBER it, so restart-on-death respawns (which boot with
        only the argv default) get it re-published before rejoining."""
        name = spec.get("name")
        if not name:
            raise ValueError("publish_model spec needs 'name'")
        out = self.router.broadcast_post("/v1/models", spec)
        with self._published_lock:
            self._published_models[name] = dict(spec)
        return out

    def kill_replica(self, name: str) -> None:
        self.replicas[name].kill()

    # -- elastic fleet size (the autoscaler's verbs) -------------------
    @staticmethod
    def _index_of(name: str) -> int:
        try:
            return int(name.replace("replica", "") or 0)
        except ValueError:
            return 0

    def scale_up(self, count: int = 1) -> List[str]:
        """Spawn `count` additional replicas and admit each once
        healthy.  Indexes are monotonic (never recycled), host-aware
        placement rides the agents round-robin exactly like start(),
        and the spawn args follow the fleet's COMMITTED default model
        — a scale-up mid-lineage must serve what the fleet serves,
        not what the launch argv named.  With COS_AOT_CACHE_DIR the
        new replica warms on cache hits and serves in seconds."""
        added: List[str] = []
        for _ in range(max(1, int(count))):
            i = self._next_index
            self._next_index += 1
            name = f"replica{i}"
            renv = dict(self.env, COS_REPLICA_INDEX=str(i))
            chips = [] if self.agents else local_chip_ids()
            if chips:
                # a replica started without a chip of its own (a fleet
                # of one) holds them all
                held = [(r.env or {}).get("TPU_VISIBLE_CHIPS")
                        for r in self.replicas.values() if not r.retired]
                free = [c for c in chips if c not in held]
                if None in held or not free:
                    raise RuntimeError(
                        f"fleet: cannot add {name}: every TPU chip of "
                        f"this host ({len(chips)}) is held by a running "
                        "replica, and a chip belongs to one process at "
                        "a time (ROADMAP queue 3 item 4)")
                renv.update(chip_env(free[0]))
            args = self.serve_args
            if self._default_model is not None:
                args = _args_with_model(self.serve_args,
                                        self._default_model)
            if self.agents:
                rep: ReplicaProcess = AgentReplicaProcess(
                    name, args, env=renv, agents=self.agents,
                    agent_index=i)
            else:
                rep = ReplicaProcess(name, args, env=renv)
            t0 = time.monotonic()
            rep.spawn()
            self.router.add_replica(name, "http://unbound",
                                    state=STARTING,
                                    host=rep.host_name)
            if not rep.wait_ready(self.startup_timeout_s,
                                  stop_evt=self._stop_evt):
                # never admit (or monitor) a replica that failed to
                # boot: it was not yet in self.replicas, so cleanup
                # is just the router entry and the process
                self.router.remove_replica(name)
                rep.terminate()
                record_event("fleet", "scale_up_failed", replica=name)
                raise RuntimeError(
                    f"fleet: scale-up {name} failed to become "
                    f"healthy within {self.startup_timeout_s}s")
            self._republish_models(rep)
            # registered only now: the monitor must never see a
            # replica the scale-up might still abandon
            self.replicas[name] = rep
            self.router.update_url(name, rep.url,
                                   host=rep.host_name or None)
            self.router.set_state(name, OK)
            self.n += 1
            wall = time.monotonic() - t0
            self.metrics.incr("scale_ups")
            self.metrics.add("replica_startup", wall)
            record_event("fleet", "scale_up", replica=name,
                         url=rep.url, wall_s=round(wall, 3),
                         replicas=self.n,
                         **({"host": rep.host_name}
                            if rep.host_name else {}))
            added.append(name)
        return added

    def scale_down(self, name: Optional[str] = None,
                   wait_idle_s: float = 60.0) -> str:
        """Retire one replica WITHOUT losing a request:
        drain → wait-idle → terminate (the rolling_reload drain path)
        — never a SIGTERM with in-flight work.  `name` None retires
        the highest-index routable replica (LIFO: the most recent
        scale-up goes first).  The replica is flagged retired before
        the drain so the death monitor cannot resurrect it, and
        un-flagged if the drain fails — drain_replica has already put
        it back in rotation (timeout) or marked it down
        (unreachable), so the fleet keeps its capacity either way."""
        if name is None:
            states = self.router.states()
            cands = [n for n, r in self.replicas.items()
                     if not r.retired and states.get(n) == OK]
            if len(cands) <= 1:
                raise RuntimeError(
                    "scale_down: need more than one routable replica "
                    f"to retire one (routable: {sorted(cands)})")
            name = max(cands, key=self._index_of)
        rep = self.replicas.get(name)
        if rep is None:
            raise KeyError(f"scale_down: unknown replica {name!r}")
        rep.retired = True
        record_event("fleet", "scale_down_start", replica=name)
        try:
            self.router.drain_replica(name, wait_idle_s=wait_idle_s)
        except BaseException:
            rep.retired = False
            record_event("fleet", "scale_down_aborted", replica=name)
            raise
        rep.terminate()
        self.router.remove_replica(name)
        self.replicas.pop(name, None)
        self.n = max(0, self.n - 1)
        self.metrics.incr("scale_downs")
        record_event("fleet", "scale_down", replica=name,
                     replicas=self.n)
        return name

    def set_replica_fault(self, name: str, env: Dict[str, Optional[str]]
                          ) -> dict:
        """Scripted-chaos hook: flip COS_FAULT_* knobs inside ONE live
        replica via its POST /v1/faults route (prodday stages a
        straggler mid-phase and lifts it later without a respawn).
        The env rides into the replica's respawn env too, so a
        restart-on-death respawn keeps the scenario's intent until the
        scenario clears it."""
        rep = self.replicas[name]
        for k, v in env.items():
            if v is None or v == "":
                rep.env = rep.env or {}
                rep.env.pop(k, None)
            else:
                rep.env = dict(rep.env or {}, **{k: str(v)})
        code, body = http_json(
            rep.url + "/v1/faults",
            data=json.dumps({"env": env}).encode(), timeout=30.0)
        if code != 200:
            raise RuntimeError(f"set_replica_fault({name}): {body}")
        record_event("fleet", "replica_fault_set", replica=name,
                     env={k: (None if v in (None, "") else str(v))
                          for k, v in env.items()})
        return body

    def restarts(self) -> int:
        return self._restarts

    def metrics_summary(self) -> dict:
        out = self.router.metrics_summary()
        out["fleet"] = dict(out.get("fleet") or {},
                            replicas=self.n,
                            restarts=self._restarts,
                            scale_ups=self.metrics.get_counter(
                                "scale_ups"),
                            scale_downs=self.metrics.get_counter(
                                "scale_downs"))
        if self.agents:
            # the agent-heartbeat view: host -> up?, what the prom
            # writer renders as cos_host_up{host=...}
            out["hosts"] = {st["host"]: {"up": st["up"],
                                         "agent": url,
                                         "ts": st["ts"]}
                            for url, st in self._agent_state.items()}
        return out
