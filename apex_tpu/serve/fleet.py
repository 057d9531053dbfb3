"""Fault-tolerant serving fleet — replica health, failover re-dispatch,
hedged requests, rolling drain.

One :class:`~apex_tpu.serve.scheduler.ServeScheduler` is a single point
of failure: PR 8's warm restart survives a fatal *tick*, but a dead
*replica* (process gone, host gone, network gone) still takes every
in-flight request with it. This module is the control plane above N
single-chip engine replicas — thread-backed, ``ThreadProcessGroup``-style,
so CPU tier-1 can fake a pod — composing the pieces the repo already
owns:

- **Replica registry + heartbeat health model**
  (:class:`ReplicaRegistry`) — each replica's worker thread beats a
  monotonic-clock heartbeat (``perf_counter`` deltas only, apexlint
  APX005); the router's sweep escalates watchdog-style on missed beats:
  ``healthy → suspect`` at ``suspect_misses`` heartbeat intervals of
  silence (``serve_replica_suspect``), ``→ dead`` at ``dead_misses``
  (``serve_replica_dead``). A beat heals a *suspect* back to healthy; a
  *dead* replica never self-revives — the router has already re-dispatched
  its requests, and a partition that heals must rejoin through an
  explicit :meth:`FleetController.restart_replica`, never by quietly
  beating again (the double-complete door stays closed).
- **Router** (:class:`FleetController`) — least-loaded dispatch over
  healthy replicas (suspects only as a fallback pool), bounded retry
  with exponential backoff for retriable replica-side rejections, and
  optional **hedged dispatch**: a request with no terminal status after
  ``hedge_ms`` fires one copy on a second replica
  (``serve_hedge_fired``); the first terminal status wins, the loser is
  aborted, and exactly-once is enforced by request id — a terminal
  record is accepted only for the request's *currently live* attempt
  object, so a superseded or duplicate completion can never settle
  twice. Routing also sheds on PR-10 burn rates: a replica whose SLO
  short-window burn is at or above ``shed_burn_factor`` receives new
  load only when every alternative is burning too.
- **Failover re-dispatch** — a dead replica's live requests are
  re-submitted to survivors (``serve_failover``, with the span the
  request lost on the dead replica as a timed goodput cause) and
  re-prefilled through the existing bucketed prefill — bit-exact by the
  PR-5 prefill/decode invariant, so greedy outputs are bit-identical to
  a no-fault run, and a prefix-cached survivor pays only the unshared
  tail. Sampled streams restart their (per-replica, seeded) PRNG path —
  the per-replica ``sampling_state`` journal (PR 8) still covers
  same-replica warm restarts bit-for-bit.
- **Draining / rolling restart** — :meth:`FleetController.drain` marks a
  replica draining (no new admissions), migrates its still-queued
  requests to peers through the scheduler's :meth:`pop_queued` hook
  (no bogus terminal status — the fleet record stays exactly-once),
  lets in-flight requests finish, then ``serve_replica_drained``;
  :meth:`restart_replica` resets the engine (compiled artifacts kept —
  zero recompiles) and rejoins it (``serve_replica_restarted``).
  :meth:`rolling_restart` does this one replica at a time, so admitting
  capacity never drops below N-1 (tier-1 asserts the recorded minimum).
- **Fleet chaos** — :class:`~apex_tpu.resilience.fault_injection.FaultInjector`
  grows ``kill_replica`` (the worker dies mid-loop, heartbeats stop),
  ``partition_replica`` (heartbeats AND results stop crossing, the
  replica keeps decoding — the no-double-complete case when it heals),
  and ``straggler_replica`` (per-tick stalls — what drives hedging).
  The tier-1 smoke runs all three in one seeded schedule and asserts
  every submitted request reaches exactly one terminal status
  fleet-wide, greedy completions bit-identical to the no-fault fleet,
  and zero decode retraces on every surviving replica.

**Request journeys** (PR 13). With a :class:`~apex_tpu.monitor.trace.
Tracer` armed (``tracer=``), the controller opens ONE fleet-level trace
per request — ``journey`` root with ``fleet_queue → attempt[replica=k]
→ backoff → hedge → failover → terminal`` children — and propagates the
trace id + attempt span id into each replica attempt
(:attr:`~apex_tpu.serve.scheduler.Request.trace_id` /
``trace_parent``), so the replica scheduler's existing
``queue/prefill/decode`` spans nest as children of the attempt. Every
fleet span is stamped from the SAME clock reads the summary and the
``serve_failover`` events use, and carries the rounded
``seconds``/``ttft_s``/``latency_s`` values as attrs — span durations
reconcile EXACTLY with TTFT/latency/failover accounting
(``tools/trace_explain.py`` exits 1 when they don't), and decode still
compiles once per replica with tracing armed. The journey root closes
LAST, after every bus event for the request — the tail-capture router's
fallback decision point. :class:`FleetTraceHarness` wires the whole
surface for the CLIs: per-replica Chrome-trace files at ``PATH.rK``,
the fleet-plane file at ``PATH``, and the
:class:`~apex_tpu.monitor.trace.TailCaptureRouter` head-sampling +
tail-capture policy across them.

**Threading contract.** Each replica's worker thread touches only its
own scheduler (which serializes under its own lock) and the registry
(every row mutation under the registry lock — apexlint APX002 keeps the
discipline). All :class:`FleetController` methods — ``submit``, ``run``,
``pump``, ``drain``, ``restart_replica`` — are driven from ONE control
thread; the controller's own tables need no lock because no worker ever
writes them (workers signal through the registry and their scheduler's
``done`` list, which the control thread harvests under the scheduler
lock). The pump's per-iteration probes are **lock-free**: each worker
publishes a ``(load, done_count)`` snapshot after every tick (one tuple
rebind — the ``partitioned``/``crashed`` APX002-legal snapshot idiom,
PR 11's documented follow-up), and the control thread refreshes it
itself after its own submits/pops, so routing and the harvest gate
never contend with the scheduler lock ``step()`` holds across a tick —
the hedge/failover reaction latency no longer waits out the slowest
replica's in-flight tick. Only an actual harvest (new terminal records
exist) or an explicit drain/restart takes a scheduler lock from the
control thread.

**Metrics.** Give each :class:`EngineReplica` its own
:class:`~apex_tpu.serve.metrics.ServeMetrics`: per-replica snapshots fold
through ``tools/metrics_merge.py`` (the PR-10 exact merge) into one
fleet view whose counters reconcile exactly with the fleet summary's
``attempts`` section (tier-1 asserts). See docs/serving.md "Fleet
failover and draining".
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Set

from apex_tpu.monitor.export import percentile
from apex_tpu.monitor.flight import FlightRecorder
# module-level on purpose (flight too): a function-local import inside
# FleetTraceHarness would RE-import monitor.trace after a sys.modules
# purge of apex_tpu.*, binding a fresh module whose bus the
# already-imported scheduler modules never publish to — the
# tail-capture router would then miss every lifecycle event (the
# test_serve_resilience subscribe-at-collection precedent)
from apex_tpu.monitor.trace import (ChromeTraceWriter, TailCaptureRouter,
                                    Tracer)
from apex_tpu.serve.scheduler import Request, ServeScheduler
from apex_tpu.utils.logging import publish_event

# replica lifecycle states (docs/serving.md has the state diagram):
# healthy -> suspect -> dead on missed heartbeats (suspect heals on a
# beat; dead is absorbing until restart_replica); healthy -> draining ->
# drained -> healthy is the rolling-restart path
REPLICA_HEALTHY = "healthy"
REPLICA_SUSPECT = "suspect"
REPLICA_DRAINING = "draining"
REPLICA_DRAINED = "drained"
REPLICA_DEAD = "dead"

# states the heartbeat sweep may escalate (drained replicas idle-beat;
# dead ones are already as escalated as it gets)
_SWEEPABLE = (REPLICA_HEALTHY, REPLICA_SUSPECT, REPLICA_DRAINING)
# states the router will send NEW admissions to (healthy preferred;
# suspect only as the fallback pool)
ADMITTING_STATES = (REPLICA_HEALTHY, REPLICA_SUSPECT)


class ReplicaRegistry:
    """Heartbeat-driven replica health: monotonic beats in, watchdog-style
    escalation events out.

    ``heartbeat`` is called from every replica's worker thread;
    ``sweep``/``set_state`` from the fleet's control thread — every row
    mutation holds the registry lock (APX002). Events are published
    OUTSIDE the lock (the bus delivers to arbitrary subscribers; the
    same snapshot-then-deliver rule the bus itself follows)."""

    def __init__(self, heartbeat_s: float = 0.05, *,
                 suspect_misses: float = 2.0, dead_misses: float = 4.0,
                 clock=time.perf_counter):
        if heartbeat_s <= 0:
            raise ValueError(f"heartbeat_s must be > 0: {heartbeat_s}")
        if not 0 < suspect_misses < dead_misses:
            raise ValueError(
                f"need 0 < suspect_misses < dead_misses, got "
                f"{suspect_misses} / {dead_misses}")
        self.heartbeat_s = float(heartbeat_s)
        self.suspect_misses = float(suspect_misses)
        self.dead_misses = float(dead_misses)
        self.clock = clock
        self._lock = threading.Lock()
        self._rows: Dict[str, Dict[str, Any]] = {}

    def register(self, replica_id: str) -> None:
        with self._lock:
            self._rows[str(replica_id)] = {
                "state": REPLICA_HEALTHY, "last_beat": self.clock(),
                "beats": 0}

    def heartbeat(self, replica_id: str) -> None:
        """One beat from the replica's worker thread. Heals a *suspect*
        back to healthy; a *dead* row keeps its state — a healed
        partition's beats must not quietly re-admit a replica whose
        requests were already re-dispatched (restart_replica is the only
        way back in)."""
        with self._lock:
            row = self._rows[str(replica_id)]
            row["last_beat"] = self.clock()
            row["beats"] += 1
            if row["state"] == REPLICA_SUSPECT:
                row["state"] = REPLICA_HEALTHY

    def touch_all(self) -> None:
        """Refresh every row's beat stamp (fleet start: the gap between
        construction and the first worker beat must not read as misses)."""
        with self._lock:
            now = self.clock()
            for row in self._rows.values():
                row["last_beat"] = now

    def sweep(self, now: Optional[float] = None
              ) -> List[Dict[str, Any]]:
        """Escalate silent replicas; returns (and publishes) the
        transition records. Exactly one ``serve_replica_suspect`` /
        ``serve_replica_dead`` per transition — dead is absorbing, so a
        storm of sweeps cannot re-announce a death."""
        now = self.clock() if now is None else now
        transitions: List[Dict[str, Any]] = []
        with self._lock:
            for rid, row in self._rows.items():
                if row["state"] not in _SWEEPABLE:
                    continue
                age = now - row["last_beat"]
                misses = age / self.heartbeat_s
                if misses >= self.dead_misses:
                    transitions.append({
                        "replica": rid, "old": row["state"],
                        "new": REPLICA_DEAD,
                        "misses": round(misses, 2),
                        "age_s": round(age, 6)})
                    row["state"] = REPLICA_DEAD
                elif misses >= self.suspect_misses \
                        and row["state"] == REPLICA_HEALTHY:
                    transitions.append({
                        "replica": rid, "old": REPLICA_HEALTHY,
                        "new": REPLICA_SUSPECT,
                        "misses": round(misses, 2),
                        "age_s": round(age, 6)})
                    row["state"] = REPLICA_SUSPECT
        for t in transitions:
            event = ("serve_replica_dead" if t["new"] == REPLICA_DEAD
                     else "serve_replica_suspect")
            publish_event(event, level="warning", replica=t["replica"],
                          misses=t["misses"], age_s=t["age_s"])
        return transitions

    def state(self, replica_id: str) -> str:
        with self._lock:
            return self._rows[str(replica_id)]["state"]

    def states(self) -> Dict[str, str]:
        with self._lock:
            return {rid: row["state"]
                    for rid, row in self._rows.items()}

    def row(self, replica_id: str) -> Dict[str, Any]:
        """A copy of one replica's registry row plus its beat age — the
        context a per-replica flight recorder stamps into a death
        postmortem (state, last heartbeat, how long it was silent)."""
        with self._lock:
            row = dict(self._rows[str(replica_id)])
        row["replica"] = str(replica_id)
        row["age_s"] = round(self.clock() - row["last_beat"], 6)
        return row

    def set_state(self, replica_id: str, state: str, *,
                  beat: bool = False) -> None:
        """Explicit lifecycle transition (drain / drained / restart) from
        the control thread; ``beat=True`` also refreshes the stamp so a
        just-restarted replica is not instantly re-suspected."""
        with self._lock:
            row = self._rows[str(replica_id)]
            row["state"] = state
            if beat:
                row["last_beat"] = self.clock()


class EngineReplica:
    """One engine + scheduler + worker thread: a fake pod member.

    The worker loop per tick: consult the fault injector (kill /
    partition / straggle), heartbeat the registry (unless partitioned),
    run one scheduler tick, sleep briefly when idle. ``partitioned`` and
    ``crashed`` are plain boolean rebinds (worker writes, control thread
    reads — the snapshot idiom, no read-modify-write); everything else
    the worker touches is behind the scheduler or registry lock."""

    ROLES = ("unified", "prefill", "decode")

    def __init__(self, replica_id: str, engine, *, admission=None,
                 metrics=None, tracer=None, idle_sleep_s: float = 0.002,
                 role: str = "unified"):
        self.replica_id = str(replica_id)
        self.engine = engine
        self.metrics = metrics
        self.scheduler = ServeScheduler(engine, admission=admission,
                                        metrics=metrics, tracer=tracer)
        self.idle_sleep_s = float(idle_sleep_s)
        if role not in self.ROLES:
            raise ValueError(
                f"role={role!r} must be one of {self.ROLES}")
        # disaggregated serving role (docs/serving.md "Disaggregated
        # prefill/decode"): "prefill" replicas run prompt prefill and
        # stream committed KV pages out, "decode" replicas receive pages
        # and serve the client stream, "unified" does both (the
        # non-disaggregated default — FleetController ignores roles)
        self.role = role
        # committed-but-undelivered page handoffs sourced at this
        # replica — control-thread-only bookkeeping (the disaggregation
        # controller is single-threaded by the FleetController contract);
        # a draining prefill replica may not report drained while > 0
        self.pending_handoffs = 0
        self.index = 0              # assigned by the controller (tiebreak)
        self.done_seen = 0          # harvest cursor into scheduler.done
        self.tick = 0
        self.partitioned = False
        self.crashed = False
        # lock-free (load, done_count) snapshot: the worker rebinds it
        # after every tick, the control thread after its own submits and
        # pops — one tuple rebind, the APX002-legal snapshot idiom — so
        # the pump's routing/harvest probes never contend with the
        # scheduler lock step() holds across a whole tick
        self._progress = (0, 0)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._registry: Optional[ReplicaRegistry] = None
        self._injector = None

    @property
    def reachable(self) -> bool:
        """Results can cross to the router: not crashed (memory gone)
        and not behind a partition (nothing crosses until it heals)."""
        return not self.crashed and not self.partitioned

    def start(self, registry: ReplicaRegistry, injector=None) -> None:
        self._registry = registry
        self._injector = injector
        self.publish_progress()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._worker, name=f"replica-{self.replica_id}",
            daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=10.0)
        self._thread = None

    def restart(self) -> None:
        """Clean restart after drain (or death): stop the worker, drop
        any stale live requests WITHOUT touching the engine
        (their fleet copies were already migrated or re-dispatched; the
        router's attempt-identity dedup drops the stale records), reset
        the engine state — compiled artifacts kept, zero recompiles —
        and start a fresh worker."""
        self.stop()
        if self.scheduler.load() > 0:
            # only a dead replica restarts non-empty; a drained one is
            # idle by definition
            self.scheduler.drain_and_reject("engine_failure")
        self.engine.reset()
        self.tick = 0
        self.partitioned = False
        self.crashed = False
        self.publish_progress()
        if self._registry is not None:
            self.start(self._registry, self._injector)

    def publish_progress(self) -> None:
        """Refresh the lock-free progress snapshot (one scheduler-lock
        acquisition, one tuple rebind). The worker calls it each tick;
        the control thread calls it right after its own scheduler
        mutations (submit / pop_queued / abort), so :meth:`load` is
        exact whenever the controller just changed it and at most one
        tick stale otherwise."""
        self._progress = self.scheduler.progress()

    def load(self) -> int:
        """Queued + in-slot requests — the router's load signal. Reads
        the published snapshot, never the scheduler lock."""
        return self._progress[0]

    @property
    def done_count(self) -> int:
        """Published terminal-record count — the harvest gate: the
        controller takes the scheduler lock only when this moved past
        its cursor."""
        return self._progress[1]

    def burn_short_max(self) -> float:
        """The replica's worst SLO short-window burn rate (0.0 with no
        SLO armed) — the PR-10 routing signal: a replica burning its
        error budget at or above the fleet's shed factor receives new
        load only when every alternative burns too."""
        m = self.metrics
        if m is None or m.slo is None:
            return 0.0
        with self.scheduler._lock:  # the SLO windows move under it
            summary = m.slo.summary()
        return max((s["burn_short"] for s in summary.values()),
                   default=0.0)

    # ------------------------------------------------------- worker loop
    def _worker(self) -> None:
        from apex_tpu.resilience.fault_injection import SimulatedCrash

        try:
            while not self._stop.is_set():
                self.tick += 1
                inj = self._injector
                if inj is not None:
                    if inj.replica_kill_due(self.replica_id, self.tick):
                        raise SimulatedCrash(
                            f"replica {self.replica_id} killed at tick "
                            f"{self.tick}")
                    stall = inj.replica_straggle_due(self.replica_id,
                                                     self.tick)
                    if stall:
                        time.sleep(stall)
                    self.partitioned = inj.replica_partitioned(
                        self.replica_id, self.tick)
                if not self.partitioned:
                    self._registry.heartbeat(self.replica_id)
                busy = self.scheduler.step()
                self.publish_progress()
                if not busy:
                    time.sleep(self.idle_sleep_s)
        except SimulatedCrash:
            # the process is gone: heartbeats stop, the registry sweep
            # escalates, and the router re-dispatches the live requests.
            # Unharvested results die with the memory (`reachable`).
            self.crashed = True


class _FleetRequest:
    """Router-side bookkeeping for one client request: the immutable
    spec, the live attempt per replica, and the exactly-once terminal
    record (first terminal of a live attempt wins)."""

    __slots__ = ("spec", "attempts", "attempt_t", "record", "dispatch_t",
                 "hedged", "retries", "next_dispatch_t", "spans",
                 "attempt_seq")

    def __init__(self, spec: Request):
        self.spec = spec
        self.attempts: Dict[str, Request] = {}
        self.attempt_t: Dict[str, float] = {}
        self.record: Optional[Dict[str, Any]] = None
        self.dispatch_t: Optional[float] = None
        self.hedged = False
        self.retries = 0
        self.next_dispatch_t = 0.0
        # journey spans (tracer armed only): "root", "fleet_queue",
        # "backoff", and ("attempt", replica_id) entries
        self.spans: Optional[Dict[Any, Any]] = None
        self.attempt_seq = 0


@dataclasses.dataclass
class FleetStats:
    """Fleet-wide accounting: exactly one record per submitted request,
    plus the attempt-level counters the per-replica metrics snapshots
    must reconcile with after ``tools/metrics_merge.py``."""

    requests: List[Dict[str, Any]]
    replicas: int
    failovers: int
    hedge_fired: int
    migrations: int
    retries: int
    replica_dead: int
    replica_restarted: int
    attempts: Dict[str, int]
    per_replica: Dict[str, Dict[str, Any]]
    decode_step_s: List[float]
    wall_s: float

    def summary(self) -> Dict[str, Any]:
        new_tokens = sum(r["new_tokens"] for r in self.requests)
        ttfts = [r["ttft_s"] for r in self.requests if "ttft_s" in r]
        lat = list(self.decode_step_s)
        return {
            "requests": len(self.requests),
            "completed": sum(r["state"] == "completed"
                             for r in self.requests),
            "evicted": sum(r["state"] == "evicted"
                           for r in self.requests),
            "rejected": sum(r["state"] == "rejected"
                            for r in self.requests),
            "deadline_exceeded": sum(
                r.get("finish_reason") == "deadline"
                for r in self.requests),
            "shed_rate": round(
                sum(r["state"] == "rejected" for r in self.requests)
                / len(self.requests), 4) if self.requests else 0.0,
            # fleet resilience counters (all lower-is-better; the
            # regression gate knows failover/hedge_fired/replica_dead)
            "failovers": self.failovers,
            "hedge_fired": self.hedge_fired,
            "migrations": self.migrations,
            "retries": self.retries,
            "replica_dead": self.replica_dead,
            "replica_restarted": self.replica_restarted,
            "replicas": self.replicas,
            # attempt-level counters: what the merged per-replica
            # metrics snapshots must equal, family by family
            "attempts": dict(self.attempts),
            "decode_steps": len(lat),     # pooled over every replica
            "new_tokens": new_tokens,
            # fleet throughput is wall-clock rate (replicas decode in
            # parallel — summing per-replica decode-time rates would
            # overstate a straggling fleet)
            "tokens_per_s": round(new_tokens / self.wall_s, 3)
            if self.wall_s else 0.0,
            "p50_step_ms": round(percentile(lat, 0.50) * 1e3, 3),
            "p99_step_ms": round(percentile(lat, 0.99) * 1e3, 3),
            "ttft_p50_ms": round(percentile(ttfts, 0.50) * 1e3, 3),
            "ttft_p99_ms": round(percentile(ttfts, 0.99) * 1e3, 3),
            "wall_s": round(self.wall_s, 6),
        }


class FleetController:
    """Route a request stream over N engine replicas with health-driven
    failover, optional hedging, and rolling drain.

    Drive it from one control thread: :meth:`submit` the workload, then
    :meth:`run` (which starts the replica workers, pumps the control
    loop until every request has its terminal record, and stops the
    workers). :meth:`pump` is public for embeddings that interleave
    control actions (drain, chaos healing) with the loop — the tier-1
    tests do exactly that."""

    def __init__(self, replicas: Sequence[EngineReplica], *,
                 heartbeat_ms: float = 50.0,
                 suspect_misses: float = 2.0, dead_misses: float = 4.0,
                 hedge_ms: Optional[float] = None,
                 max_retries: int = 2, retry_backoff_s: float = 0.01,
                 retry_backoff_factor: float = 2.0,
                 max_retry_backoff_s: float = 0.5,
                 shed_burn_factor: float = 2.0,
                 fault_injector=None, tracer=None,
                 clock=time.perf_counter):
        if not replicas:
            raise ValueError("FleetController needs at least one replica")
        ids = [h.replica_id for h in replicas]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate replica ids: {ids}")
        if hedge_ms is not None and len(replicas) < 2:
            raise ValueError(
                "hedged dispatch needs >= 2 replicas: a hedge fired at "
                "the only replica would race itself")
        if hedge_ms is not None and hedge_ms <= 0:
            raise ValueError(f"hedge_ms must be > 0: {hedge_ms}")
        self.handles = list(replicas)
        for i, h in enumerate(self.handles):
            h.index = i
        self._by_id = {h.replica_id: h for h in self.handles}
        self.registry = ReplicaRegistry(
            heartbeat_ms / 1e3, suspect_misses=suspect_misses,
            dead_misses=dead_misses, clock=clock)
        for h in self.handles:
            self.registry.register(h.replica_id)
        self.hedge_ms = hedge_ms
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_factor = float(retry_backoff_factor)
        self.max_retry_backoff_s = float(max_retry_backoff_s)
        self.shed_burn_factor = float(shed_burn_factor)
        self.injector = fault_injector
        # fleet-level request journeys: one trace per submitted request,
        # stamped from the same clock reads the accounting uses
        self.tracer = tracer if tracer is not None and tracer.enabled \
            else None
        self._clock = clock
        self._pump_interval_s = min(0.003, heartbeat_ms / 4e3)
        self._requests: Dict[Any, _FleetRequest] = {}
        self._pending: List[_FleetRequest] = []
        self._started = False
        self._draining_all = False
        self._drain_shed_done = False
        self._drain_migrated: Dict[str, int] = {}
        self._t0: Optional[float] = None
        # fleet counters (the summary + bench entry carry them)
        self.dispatches = 0
        self.failovers = 0
        self.hedges_fired = 0
        self.migrations = 0
        self.retries = 0
        self.replica_deaths = 0
        self.replica_restarts = 0
        self._min_admitting = len(self.handles)

    # ----------------------------------------------------------- intake
    def submit(self, spec: Request) -> bool:
        """Accept one client request (the object is the immutable SPEC —
        per-replica attempts are fresh copies, so a hedge or failover
        can never alias scheduler state across replicas) and dispatch it
        to the least-loaded admitting replica. Returns ``False`` when
        the fleet is draining (SIGTERM drain: no new admissions).
        Malformed requests raise — caller errors, not load."""
        if self._draining_all:
            return False
        if spec.request_id in self._requests:
            raise ValueError(
                f"request id {spec.request_id!r} already submitted "
                f"fleet-wide (exactly-once needs unique ids)")
        if not len(spec.tokens):
            raise ValueError(f"request {spec.request_id!r}: empty prompt")
        max_len = self.handles[0].engine.max_len
        if len(spec.tokens) >= max_len:
            raise ValueError(
                f"request {spec.request_id!r}: prompt of "
                f"{len(spec.tokens)} tokens leaves no room to generate "
                f"under max_len={max_len}")
        freq = _FleetRequest(spec)
        self._requests[spec.request_id] = freq
        now = self._clock()
        if self.tracer is not None:
            # the journey roots at the controller's OWN submit stamp —
            # the same `now` every dispatch/backoff computation below
            # measures from, so fleet span durations and the routing
            # accounting are the same numbers
            root = self.tracer.begin(
                "journey", trace_id=f"journey:{spec.request_id}",
                t0=now, request_id=str(spec.request_id),
                prompt_tokens=len(spec.tokens))
            freq.spans = {
                "root": root,
                "fleet_queue": self.tracer.begin("fleet_queue",
                                                 parent=root, t0=now)}
        self._dispatch_new(freq, now)
        return True

    def _dispatch_new(self, freq: _FleetRequest, now: float) -> None:
        """First dispatch of a fresh request: route or pend. The
        disaggregation controller overrides this seam to interpose a
        prefill→decode page handoff before the real dispatch."""
        handle = self._route()
        if handle is None:
            freq.next_dispatch_t = now
            self._pending.append(freq)
        else:
            self._submit_attempt(freq, handle, now)

    def begin_drain(self) -> None:
        """Fleet-wide drain (the ``--drain-on SIGTERM`` contract): stop
        accepting new work; the next :meth:`pump` sheds every
        still-QUEUED (never admitted) request as a terminal retriable
        rejection (``finish_reason="draining"`` — a healthy fleet can
        serve it), in-flight requests finish, then :meth:`run` returns
        normally. Safe at signal depth: this is one flag write — the
        control thread does the actual shedding."""
        self._draining_all = True

    # ---------------------------------------------------------- routing
    def _route(self, exclude: Sequence[str] = ()
               ) -> Optional[EngineReplica]:
        """Least-loaded admitting replica: healthy before suspect,
        burn-rate-quiet before shedding, then load, then index (a
        deterministic tiebreak)."""
        states = self.registry.states()
        cands = [h for h in self.handles
                 if h.replica_id not in exclude and not h.crashed
                 and states.get(h.replica_id) in ADMITTING_STATES]
        if not cands:
            return None
        healthy = [h for h in cands
                   if states[h.replica_id] == REPLICA_HEALTHY]
        pool = healthy or cands
        quiet = [h for h in pool
                 if h.burn_short_max() < self.shed_burn_factor]
        pool = quiet or pool
        return min(pool, key=lambda h: (h.load(), h.index))

    def _submit_attempt(self, freq: _FleetRequest,
                        handle: EngineReplica, now: float) -> None:
        spec = freq.spec
        att = Request(request_id=spec.request_id,
                      tokens=list(spec.tokens),
                      max_new_tokens=spec.max_new_tokens,
                      eos_id=spec.eos_id, deadline_ms=spec.deadline_ms,
                      priority=spec.priority, tenant=spec.tenant)
        sp = freq.spans
        if sp is not None:
            # whichever wait preceded this dispatch ends now (first
            # dispatch: fleet_queue; a retry: its backoff span)
            for key in ("fleet_queue", "backoff"):
                waited = sp.pop(key, None)
                if waited is not None:
                    self.tracer.end(waited, t1=now)
            freq.attempt_seq += 1
            att_span = self.tracer.begin(
                "attempt", parent=sp["root"], t0=now,
                replica=handle.replica_id, attempt=freq.attempt_seq)
            sp[("attempt", handle.replica_id)] = att_span
            # propagate: the replica scheduler's request trace nests
            # under this attempt span, in the SAME journey trace
            att.trace_id = sp["root"].trace_id
            att.trace_parent = att_span.span_id
        freq.attempts[handle.replica_id] = att
        freq.attempt_t[handle.replica_id] = now
        freq.dispatch_t = now
        self.dispatches += 1
        # a False return (admission reject) leaves a terminal rejected
        # record in the replica's done list — the harvest/retry path
        # owns it from there
        handle.scheduler.submit(att)
        handle.publish_progress()

    # ------------------------------------------------------ control loop
    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._t0 = self._clock()
        # the gap between construction (engine builds, test setup) and
        # this point must not count as missed beats
        self.registry.touch_all()
        for h in self.handles:
            h.start(self.registry, self.injector)

    def stop(self) -> None:
        for h in self.handles:
            h.stop()
        self._started = False

    def pump(self) -> None:
        """One control iteration: sweep heartbeats (failover on a death
        transition), harvest reachable replicas' terminal records,
        dispatch pending/retrying requests, fire due hedges. Public so
        embeddings (and the chaos tests) can interleave control actions
        with the loop."""
        now = self._clock()
        for t in self.registry.sweep(now):
            if t["new"] == REPLICA_DEAD:
                self.replica_deaths += 1
                self._failover(t["replica"], now)
        if self._draining_all and not self._drain_shed_done:
            self._drain_shed_done = True
            self._shed_queued_for_drain(now)
        self._harvest(now)
        self._dispatch_pending(now)
        self._fire_hedges(now)
        states = self.registry.states()
        for h in self.handles:
            # a draining replica whose last in-flight request just left
            # becomes drained HERE, whichever loop is pumping — so a
            # drain(wait=False) can never wedge it in draining forever
            if states.get(h.replica_id) == REPLICA_DRAINING:
                self._maybe_mark_drained(h)
        admitting = sum(s in ADMITTING_STATES for s in states.values())
        self._min_admitting = min(self._min_admitting, admitting)

    def run(self, *, max_wall_s: float = 60.0) -> FleetStats:
        """Start the workers (if not already), pump until every
        submitted request has exactly one terminal record, stop the
        workers, return the stats. ``max_wall_s`` is a loud liveness
        bound — a wedged fleet raises instead of hanging tier-1."""
        self.start()
        t0 = self._clock()
        try:
            while not self.all_terminal():
                self.pump()
                if self._clock() - t0 > max_wall_s:
                    live = [rid for rid, f in self._requests.items()
                            if f.record is None]
                    raise TimeoutError(
                        f"fleet did not settle {len(live)} request(s) "
                        f"within {max_wall_s}s: {live[:8]}")
                time.sleep(self._pump_interval_s)
        finally:
            self.stop()
        return self.stats()

    def all_terminal(self) -> bool:
        return all(f.record is not None
                   for f in self._requests.values())

    # ---------------------------------------------------------- harvest
    def _harvest(self, now: float) -> None:
        for handle in self.handles:
            if not handle.reachable:
                # a crashed replica's unharvested results died with its
                # memory; a partitioned one's cannot cross until it
                # heals (and then lose first-terminal-wins if the
                # router already settled the request elsewhere)
                continue
            if handle.done_count == handle.done_seen:
                # lock-free gate: the published snapshot says nothing
                # new is terminal — skip the scheduler lock entirely
                # (it may be held across a multi-second contended tick)
                continue
            done, handle.done_seen = handle.scheduler.done_since(
                handle.done_seen)
            for req in done:
                self._settle(handle, req, now)

    def _settle(self, handle: EngineReplica, req: Request,
                now: float) -> None:
        freq = self._requests.get(req.request_id)
        if freq is None:
            return      # replica-local traffic (e.g. an injector storm)
        if freq.record is not None:
            return      # hedge/partition duplicate: first terminal won
        if freq.attempts.get(handle.replica_id) is not req:
            # a superseded attempt (failed over, migrated, or drained
            # after death) — its record must never settle the request
            return
        del freq.attempts[handle.replica_id]
        done_t = req.done_t if req.done_t is not None else now
        if req.state == "rejected":
            # a shed copy must never settle a request another replica
            # is actively serving: with a hedge copy still live, that
            # copy IS the retry — drop this rejection outright (if the
            # live copy is later rejected too, attempts is empty and
            # the normal retry/terminal path below owns it)
            if freq.attempts:
                self._end_attempt(freq, handle.replica_id, t1=done_t,
                                  status="cancelled", reason="rejected")
                return
            if self._retryable(freq):
                freq.retries += 1
                self.retries += 1
                backoff = min(
                    self.retry_backoff_s
                    * self.retry_backoff_factor ** (freq.retries - 1),
                    self.max_retry_backoff_s)
                freq.next_dispatch_t = now + backoff
                self._end_attempt(freq, handle.replica_id, t1=done_t,
                                  status="cancelled", reason="rejected")
                if freq.spans is not None:
                    # the wait until re-dispatch: closed by the next
                    # _submit_attempt (its `now` — the same stamp
                    # attempt_t records)
                    freq.spans["backoff"] = self.tracer.begin(
                        "backoff", parent=freq.spans["root"], t0=now,
                        retry=freq.retries,
                        backoff_s=round(backoff, 6))
                self._pending.append(freq)
                return
        self._accept(freq, handle.replica_id, req, now)

    def _retryable(self, freq: _FleetRequest) -> bool:
        return freq.retries < self.max_retries \
            and self._route() is not None

    def _accept(self, freq: _FleetRequest, replica_id: str,
                req: Request, now: float) -> None:
        """First terminal of a live attempt wins: record it, abort every
        other live attempt (reachable replicas only — an unreachable
        one's duplicate is dropped at harvest by the attempt-identity
        rule), then close the journey — terminal + root spans last,
        after every lifecycle event the settle published."""
        record = dict(req.record())
        record["replica"] = replica_id
        freq.record = record
        for rid, att in list(freq.attempts.items()):
            h = self._by_id[rid]
            if h.reachable:
                h.scheduler.abort(att.request_id)
                h.publish_progress()
            self._end_attempt(freq, rid, t1=now, status="cancelled",
                              reason="superseded")
        freq.attempts.clear()
        done_t = req.done_t if req.done_t is not None else now
        self._end_attempt(
            freq, replica_id, t1=done_t,
            status="ok" if req.state == "completed" else "cancelled")
        self._close_journey(freq, t1=done_t, record=record)

    # ------------------------------------------------------ journey spans
    def _end_attempt(self, freq: _FleetRequest, replica_id: str, *,
                     t1: float, status: str, **attrs: Any) -> None:
        sp = freq.spans
        if sp is None:
            return
        span = sp.pop(("attempt", replica_id), None)
        if span is not None:
            self.tracer.end(span, t1=t1, status=status, **attrs)

    def _close_journey(self, freq: _FleetRequest, *, t1: float,
                       record: Dict[str, Any]) -> None:
        """Terminal marker + root close, carrying the record's EXACT
        rounded ttft/latency values as attrs (what trace_explain
        reconciles bit-for-bit against the summary). Runs exactly once,
        LAST — after every bus event for this request — so the
        tail-capture router's fallback decision sees a settled world."""
        sp = freq.spans
        if sp is None:
            return
        freq.spans = None
        # anything still open (a dead replica's attempt that never
        # settled, a backoff that never re-dispatched) ends here
        for key, span in list(sp.items()):
            if key != "root":
                self.tracer.end(span, t1=t1, status="cancelled")
        attrs = {"state": record["state"],
                 "finish_reason": record.get("finish_reason"),
                 "replica": record.get("replica"),
                 "new_tokens": record.get("new_tokens", 0)}
        for key in ("ttft_s", "latency_s"):
            if record.get(key) is not None:
                attrs[key] = record[key]
        term = self.tracer.begin("terminal", parent=sp["root"], t0=t1,
                                 **attrs)
        self.tracer.end(term, t1=t1)
        self.tracer.end(
            sp["root"], t1=t1,
            status="ok" if record["state"] == "completed"
            else "cancelled", **attrs)

    # --------------------------------------------------------- failover
    def _failover(self, replica_id: str, now: float) -> None:
        """A replica was declared dead: every one of its live requests
        with no other live attempt is re-dispatched to a survivor
        (``serve_failover``; the span the request already spent on the
        dead replica is the timed loss — the survivor redoes that
        work, bit-identically under greedy decoding)."""
        for freq in self._requests.values():
            att = freq.attempts.pop(replica_id, None)
            if att is None or freq.record is not None:
                continue
            lost_t0 = freq.attempt_t.get(replica_id, now)
            lost_s = max(now - lost_t0, 0.0)
            seconds = round(lost_s, 6)
            self._end_attempt(freq, replica_id, t1=now, status="error",
                              cause="replica_dead", seconds=seconds)
            if freq.attempts:
                continue    # a hedge copy already runs elsewhere
            self.failovers += 1
            target = self._route(exclude=(replica_id,))
            publish_event(
                "serve_failover", level="warning",
                request_id=freq.spec.request_id,
                from_replica=replica_id,
                to_replica=target.replica_id if target else None,
                cause="replica_dead", seconds=seconds)
            if freq.spans is not None:
                # the failover gap span covers EXACTLY the lost attempt
                # window, and its ``seconds`` attr is the SAME rounded
                # value the event (and so the goodput ledger) carries —
                # the reconciliation in tools/trace_explain.py is exact
                fo = self.tracer.begin(
                    "failover", parent=freq.spans["root"], t0=lost_t0,
                    from_replica=replica_id,
                    to_replica=target.replica_id if target else None,
                    cause="replica_dead", seconds=seconds)
                self.tracer.end(fo, t1=now)
            if target is not None:
                self._submit_attempt(freq, target, now)
            else:
                freq.next_dispatch_t = now
                self._pending.append(freq)

    def _dispatch_pending(self, now: float) -> None:
        still: List[_FleetRequest] = []
        for freq in self._pending:
            if freq.record is not None:
                continue    # settled while waiting (a late duplicate)
            if freq.next_dispatch_t > now:
                still.append(freq)
                continue
            handle = self._route()
            if handle is None:
                if all(s == REPLICA_DEAD
                       for s in self.registry.states().values()):
                    # total fleet loss: exactly-once still stands — a
                    # synthetic terminal eviction, never a silent drop
                    self._fail_terminal(freq, now)
                else:
                    still.append(freq)   # draining/restarting: wait
                continue
            self._submit_attempt(freq, handle, now)
        self._pending = still

    def _fail_terminal(self, freq: _FleetRequest, now: float) -> None:
        freq.record = {
            "request_id": freq.spec.request_id, "state": "evicted",
            "finish_reason": "engine_failure",
            "prompt_tokens": len(freq.spec.tokens), "new_tokens": 0,
            "generated": [], "replica": None}
        freq.attempts.clear()
        # total fleet loss publishes no lifecycle event — the journey
        # root close below IS the tail-capture router's decision point
        self._close_journey(freq, t1=now, record=freq.record)

    def _shed_queued_for_drain(self, now: float) -> None:
        """The fleet-wide drain sweep (one per :meth:`begin_drain`):
        every request with no ADMITTED copy anywhere — still queued at
        its replica(s), or pending (re)dispatch — becomes a terminal
        retriable rejection; requests already in a slot finish in
        place. Queue waits were published by ``pop_queued``; the
        rejection itself rides ``serve_request_rejected`` like every
        other shed."""
        for freq in self._requests.values():
            if freq.record is not None:
                continue
            for rid, att in list(freq.attempts.items()):
                h = self._by_id[rid]
                if h.reachable and \
                        h.scheduler.pop_queued(att.request_id) is not None:
                    h.publish_progress()
                    del freq.attempts[rid]
                    self._end_attempt(freq, rid, t1=now,
                                      status="cancelled",
                                      reason="draining")
            if freq.attempts:
                continue    # admitted (or unreachable): finishes there
            freq.record = {
                "request_id": freq.spec.request_id, "state": "rejected",
                "finish_reason": "draining", "retriable": True,
                "prompt_tokens": len(freq.spec.tokens), "new_tokens": 0,
                "generated": [], "replica": None}
            publish_event("serve_request_rejected", level="warning",
                          request_id=freq.spec.request_id,
                          reason="draining", retriable=True,
                          seconds=0.0, queue_depth=0)
            self._close_journey(freq, t1=now, record=freq.record)
        self._pending = [f for f in self._pending if f.record is None]

    # ---------------------------------------------------------- hedging
    def _fire_hedges(self, now: float) -> None:
        if self.hedge_ms is None:
            return
        for freq in self._requests.values():
            if freq.record is not None or freq.hedged \
                    or len(freq.attempts) != 1 \
                    or freq.dispatch_t is None \
                    or now - freq.dispatch_t < self.hedge_ms / 1e3:
                continue
            primary = next(iter(freq.attempts))
            target = self._route(exclude=(primary,))
            if target is None:
                continue
            freq.hedged = True      # at most ONE hedge per request
            self.hedges_fired += 1
            waited_ms = round((now - freq.dispatch_t) * 1e3, 3)
            publish_event("serve_hedge_fired",
                          request_id=freq.spec.request_id,
                          primary=primary, hedge=target.replica_id,
                          waited_ms=waited_ms)
            if freq.spans is not None:
                # instant marker: the race opens here; the two attempt
                # spans racing after it ARE the hedge margin
                h = self.tracer.begin(
                    "hedge", parent=freq.spans["root"], t0=now,
                    primary=primary, hedge=target.replica_id,
                    waited_ms=waited_ms)
                self.tracer.end(h, t1=now)
            self._submit_attempt(freq, target, now)

    # --------------------------------------------- drain / rolling restart
    def drain(self, replica_id: str, *, wait: bool = True,
              max_wall_s: float = 30.0) -> int:
        """Mark a replica draining: no new admissions route to it, its
        still-queued requests migrate to peers (the scheduler's
        ``pop_queued`` hook — no terminal status, the fleet record stays
        exactly-once), in-flight requests finish in place. With
        ``wait=True`` pumps until the replica is idle, then publishes
        ``serve_replica_drained``. Returns the migration count."""
        handle = self._by_id[str(replica_id)]
        self.registry.set_state(handle.replica_id, REPLICA_DRAINING)
        now = self._clock()
        migrated = 0
        for freq in self._requests.values():
            att = freq.attempts.get(handle.replica_id)
            if att is None or freq.record is not None:
                continue
            popped = handle.scheduler.pop_queued(att.request_id)
            if popped is None:
                continue    # already in a slot: finishes where it is
            handle.publish_progress()
            del freq.attempts[handle.replica_id]
            migrated += 1
            self.migrations += 1
            lost_t0 = freq.attempt_t.get(handle.replica_id, now)
            seconds = round(max(now - lost_t0, 0.0), 6)
            self._end_attempt(freq, handle.replica_id, t1=now,
                              status="cancelled", cause="drain",
                              seconds=seconds)
            target = self._route(exclude=(handle.replica_id,))
            publish_event(
                "serve_failover", request_id=freq.spec.request_id,
                from_replica=handle.replica_id,
                to_replica=target.replica_id if target else None,
                cause="drain", seconds=seconds)
            if freq.spans is not None:
                # same contract as the death path: span window == the
                # migrated wait, seconds attr == the event's value
                fo = self.tracer.begin(
                    "failover", parent=freq.spans["root"], t0=lost_t0,
                    from_replica=handle.replica_id,
                    to_replica=target.replica_id if target else None,
                    cause="drain", seconds=seconds)
                self.tracer.end(fo, t1=now)
            if target is not None:
                self._submit_attempt(freq, target, now)
            else:
                freq.next_dispatch_t = now
                self._pending.append(freq)
        self._drain_migrated[handle.replica_id] = migrated
        if wait:
            t0 = self._clock()
            while self.registry.state(handle.replica_id) \
                    == REPLICA_DRAINING:
                self.pump()     # pump marks it drained at load 0
                if self._clock() - t0 > max_wall_s:
                    raise TimeoutError(
                        f"replica {replica_id} did not drain within "
                        f"{max_wall_s}s (load={handle.load()})")
                time.sleep(self._pump_interval_s)
        else:
            # already idle? mark now — otherwise every later pump()
            # checks, so wait=False can never wedge it in draining
            self._maybe_mark_drained(handle)
        return migrated

    def _maybe_mark_drained(self, handle: EngineReplica) -> None:
        """Draining → drained the moment the replica is idle (exactly
        one ``serve_replica_drained`` per drain — the state transition
        is the guard). A draining PREFILL replica must first flush its
        committed-but-undelivered page handoffs (``pending_handoffs``)
        — declaring it drained with pages in flight would strand KV
        state its decode targets are counting on; the disaggregation
        controller's pump delivers them and drops the count to zero."""
        if self.registry.state(handle.replica_id) == REPLICA_DRAINING \
                and handle.load() == 0 and handle.pending_handoffs == 0:
            self.registry.set_state(handle.replica_id, REPLICA_DRAINED)
            publish_event(
                "serve_replica_drained", replica=handle.replica_id,
                migrated=self._drain_migrated.get(handle.replica_id, 0))

    def restart_replica(self, replica_id: str) -> None:
        """Clean restart of a drained (or dead) replica: engine state
        reset with every compiled artifact kept — zero recompiles — and
        the registry re-admits it (``serve_replica_restarted``). The
        ONLY way back in for a dead replica: a healed partition's
        heartbeats alone never re-admit it."""
        handle = self._by_id[str(replica_id)]
        state = self.registry.state(handle.replica_id)
        if state not in (REPLICA_DRAINED, REPLICA_DEAD):
            raise ValueError(
                f"replica {replica_id!r} is {state!r}: drain it (or let "
                f"the sweep declare it dead) before restarting")
        if self._started:
            handle.restart()
        else:
            # not running yet (pre-start lifecycle tests): reset only
            if handle.scheduler.load():
                handle.scheduler.drain_and_reject("engine_failure")
            handle.engine.reset()
            handle.crashed = False
            handle.partitioned = False
            handle.publish_progress()
        self.registry.set_state(handle.replica_id, REPLICA_HEALTHY,
                                beat=True)
        self.replica_restarts += 1
        publish_event("serve_replica_restarted",
                      replica=handle.replica_id)

    def add_replica(self, handle: EngineReplica) -> None:
        """Admit a freshly-built replica into a running fleet (the
        autoscaler's cold-spawn path — warm restarts of a DRAINED
        standby go through :meth:`restart_replica` instead and cost
        zero recompiles). The handle registers healthy with a fresh
        heartbeat stamp and, if the fleet is started, its worker starts
        immediately; ``serve_replica_spawned`` records the spawn."""
        rid = handle.replica_id
        if rid in self._by_id:
            raise ValueError(
                f"replica id {rid!r} already in the fleet (spawn needs "
                f"a unique id; restart the existing one instead)")
        handle.index = len(self.handles)
        self.handles.append(handle)
        self._by_id[rid] = handle
        self.registry.register(rid)
        if self._started:
            handle.start(self.registry, self.injector)
        publish_event("serve_replica_spawned", replica=rid,
                      role=handle.role, replicas=len(self.handles))

    def rolling_restart(self, *, max_wall_s: float = 30.0
                        ) -> Dict[str, int]:
        """Drain → restart every non-dead replica, one at a time, so
        admitting capacity never drops below N-1 (the returned
        ``min_admitting`` proves it — tier-1 asserts) and zero in-flight
        requests are lost (queued ones migrate, running ones finish)."""
        self._min_admitting = len(self.handles)
        restarted = 0
        for handle in self.handles:
            if self.registry.state(handle.replica_id) == REPLICA_DEAD:
                continue
            self.drain(handle.replica_id, wait=True,
                       max_wall_s=max_wall_s)
            self.restart_replica(handle.replica_id)
            restarted += 1
        return {"restarted": restarted,
                "min_admitting": self._min_admitting}

    # ------------------------------------------------------------- stats
    def stats(self) -> FleetStats:
        records = [dict(f.record) for f in self._requests.values()
                   if f.record is not None]
        # attempt-level counters, classified exactly the way the
        # per-replica ServeMetrics hooks count them (state rejected →
        # on_reject, deadline eviction → on_deadline, other evictions →
        # on_evict, completed → on_complete) — so the merged snapshot's
        # family totals must equal these, counter for counter
        attempts = {"submitted": self.dispatches, "completed": 0,
                    "evicted": 0, "deadline_exceeded": 0, "rejected": 0}
        pooled_steps: List[float] = []
        per_replica: Dict[str, Dict[str, Any]] = {}
        for h in self.handles:
            done, _ = h.scheduler.done_since(0)
            for r in done:
                if r.state == "completed":
                    attempts["completed"] += 1
                elif r.state == "rejected":
                    attempts["rejected"] += 1
                elif r.finish_reason == "deadline":
                    attempts["deadline_exceeded"] += 1
                else:
                    attempts["evicted"] += 1
            pooled_steps.extend(h.scheduler.decode_step_s)
            per_replica[h.replica_id] = {
                "state": self.registry.state(h.replica_id),
                "decode_steps": h.scheduler.decode_steps,
                "attempts_done": len(done),
                "crashed": h.crashed,
            }
        wall = (self._clock() - self._t0) if self._t0 is not None else 0.0
        return FleetStats(
            requests=records, replicas=len(self.handles),
            failovers=self.failovers, hedge_fired=self.hedges_fired,
            migrations=self.migrations, retries=self.retries,
            replica_dead=self.replica_deaths,
            replica_restarted=self.replica_restarts,
            attempts=attempts, per_replica=per_replica,
            decode_step_s=pooled_steps, wall_s=wall)


# --------------------------------------------------------------------------
# --trace-jsonl fleet wiring (shared by apex-tpu-serve and apex-tpu-bench)
# --------------------------------------------------------------------------

class FleetTraceHarness:
    """One object owning the whole fleet tracing surface: a fleet-plane
    :class:`~apex_tpu.monitor.trace.Tracer` (track ``fleet``) streaming
    to ``PATH``, one tracer per replica (track ``rK``) streaming to
    ``PATH.rK``, and a :class:`~apex_tpu.monitor.trace.TailCaptureRouter`
    applying the seeded head-sampling + tail-capture policy across all of
    them (``sample_rate=1`` — the default — streams every journey, the
    pre-PR-13 behavior).

    Usage::

        harness = FleetTraceHarness(path, [h.replica_id for h in handles],
                                    sample_rate=0.1, sample_seed=seed)
        fleet = FleetController(handles, tracer=harness.fleet_tracer, ...)
        # EngineReplica(..., tracer=harness.tracer_for(rid)) per replica
        try:
            fleet.run()
        finally:
            harness.close()    # finalize every trace file

    ``tools/trace_explain.py PATH PATH.r0 ...`` merges the files back
    into per-request attribution and verifies the reconciliation.
    """

    def __init__(self, path: str, replica_ids: Sequence[str], *,
                 sample_rate: float = 1.0, sample_seed: int = 0,
                 ring_spans: int = 256):
        self.path = path
        self.fleet_tracer = Tracer(tags={"track": "fleet"})
        self.replica_tracers = {
            str(rid): Tracer(tags={"track": str(rid)})
            for rid in replica_ids}
        # dict order matters: the fleet writer is first, so untracked
        # spans (none in practice) default to the fleet file
        writers = {"fleet": ChromeTraceWriter(path, subscribe=False)}
        for rid in self.replica_tracers:
            writers[rid] = ChromeTraceWriter(f"{path}.{rid}",
                                             subscribe=False)
        self.router = TailCaptureRouter(writers, sample_rate=sample_rate,
                                        sample_seed=sample_seed,
                                        ring_spans=ring_spans)

    def tracer_for(self, replica_id: str):
        return self.replica_tracers[str(replica_id)]

    @property
    def paths(self) -> List[str]:
        return [self.path] + [f"{self.path}.{rid}"
                              for rid in self.replica_tracers]

    def stats(self) -> Dict[str, Any]:
        """Sampling/promotion provenance for the CLI summary and the
        bench entry (``trace_promoted`` gates lower-is-better)."""
        return {"sample_rate": self.router.sampler.rate,
                "sample_seed": self.router.sampler.seed,
                **self.router.stats()}

    def close(self) -> None:
        self.router.close()

    def __enter__(self) -> "FleetTraceHarness":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def attach_fleet_recorders(fleet: FleetController, path: str,
                           harness: Optional[FleetTraceHarness] = None
                           ) -> List[FlightRecorder]:
    """``--flight-recorder`` fleet wiring, shared by ``apex-tpu-serve``
    and ``apex-tpu-bench --serve`` (one spelling — the two CLIs'
    postmortems can never diverge): one recorder per replica at
    ``PATH.rK``, auto-dump scoped (``trigger_filter``) to THAT replica's
    death/suspect transition and carrying its registry row
    (``context_fn``) plus its tracer's open spans; plus the fleet-plane
    recorder at ``PATH``, returned LAST — wrap the control loop in its
    ``guard()`` (a fatal controller error has no bus record to trigger
    on). The caller detaches every returned recorder in its teardown."""
    recorders: List[FlightRecorder] = []
    for h in fleet.handles:
        rid = h.replica_id
        recorders.append(FlightRecorder(
            f"{path}.{rid}",
            tracer=harness.tracer_for(rid) if harness is not None
            else None,
            trigger_filter=lambda rec, rid=rid:
            rec.get("replica") in (None, rid),
            context_fn=lambda rid=rid:
            fleet.registry.row(rid)).attach())
    recorders.append(FlightRecorder(
        path,
        tracer=harness.fleet_tracer if harness is not None
        else None).attach())
    return recorders
