"""Disaggregated prefill/decode serving — fleet-of-meshes role routing,
chain-hash-certified KV page streaming, and the SLO-driven autoscaler.

apex's NCCL p2p/IPC machinery exists so KV state can move between
devices without a correctness gap; the TPU-native analog is **page**
streaming between replica pools, built from invariants this repo
already pinned: page indices are rank-invariant (one index addresses
every mesh rank's shard of a page), the page table is replicated data,
``copy_page``/``install_page`` are single jitted ops, and the prefix
index's chained chunk hashes commit to an entire prompt prefix. This
module composes them into a disaggregated fleet:

- **Roles.** Each :class:`~apex_tpu.serve.fleet.EngineReplica` carries a
  role: ``prefill`` replicas run the bucketed prefill and stream the
  committed prompt pages out; ``decode`` replicas receive pages and
  serve the client stream; ``unified`` does both (a fleet with no
  prefill replicas behaves exactly like the base
  :class:`~apex_tpu.serve.fleet.FleetController`). Every replica owns
  its own engine — and with ``EngineConfig(tp=N)`` its own
  ``NamedSharding`` mesh (the fleet-of-meshes: one compile per mesh
  shape, per-rank metrics folding through ``merge_snapshots``
  unchanged).
- **The handoff.** A disaggregation-eligible request (>= one full page
  of prompt) is NOT dispatched on arrival. The controller submits a
  *prefill job* — a replica-local clone request (id
  ``"<id>#prefill"``, ``max_new_tokens=1``) — to the least-loaded
  prefill replica; the clone never enters the fleet's request table, so
  the settlement door (:meth:`FleetController._settle` drops unknown
  ids) cannot confuse it with the real request. When the clone
  completes, the prompt's full pages sit committed in the prefill
  engine's prefix index; the controller exports them
  (:meth:`Engine.export_prefix_pages` — each payload stamped with a
  transport digest), certifies each on arrival, installs the accepted
  chain prefix into ONE decode replica's pool
  (:meth:`Engine.import_prefix_pages`), and only then dispatches the
  real request to that same replica — whose admission finds the pages
  as ordinary prefix hits and scans only the tail.
- **Certification.** The receiver derives the expected chain hashes
  from the request's own prompt (:func:`~apex_tpu.serve.paging.
  chunk_hashes`) — a payload claiming any other hash is the wrong
  prefix — and recomputes the payload digest over the bytes that
  actually arrived (:func:`~apex_tpu.serve.paging.page_payload_digest`)
  — a bit flip or torn copy in flight fails it. A failed page REFUSES
  the handoff at that point in the chain (``serve_handoff_refused``);
  pages before it stay usable, and the request's admission simply finds
  a shorter prefix and re-prefills the rest locally — **bit-exact by
  the PR-5 prefill/decode invariant**, never a silent wrong token.
- **Exactly-once across the handoff.** The real request settles through
  the fleet's unchanged attempt-identity door. A prefill replica dying
  with handoffs in flight abandons them (the request dispatches without
  pages — local re-prefill); a duplicate stream after failover is
  dropped by the prefix-index insert no-op (a chain hash already
  indexed installs nothing); a handoff racing a drain is flushed before
  the source may report drained (``pending_handoffs`` gates
  ``serve_replica_drained``). Every path ends in exactly one terminal
  record per request and at most one ``serve_handoff_wait`` stall
  record per handoff.
- **Autoscaler.** :class:`Autoscaler` runs on the control thread
  (``tick()`` from the pump loop — the fleet threading contract means
  it needs no lock), scaling one role between ``min_replicas`` and
  ``max_replicas`` on two pressure signals: the role's worst
  short-window SLO burn rate (PR 10) and its tightest free-page
  fraction. Hysteresis is structural — distinct up/down thresholds, a
  consecutive-evaluation streak requirement, and a post-action cooldown
  — so one noisy sample can never flap the fleet. Scale-up prefers
  warm-restarting a DRAINED standby (zero recompiles) over the cold
  ``factory`` spawn; scale-down is a rolling drain (queued work
  migrates, in-flight work finishes), never a kill.
- **Diurnal traffic.** :class:`DiurnalTraffic` generates the seeded
  millions-of-users load curve the autoscaler is proven under: a
  sinusoidal requests-per-second profile scaled from a modeled user
  population, integrated against an injectable clock so chaos tests
  replay bit-for-bit.

Chaos coverage (:class:`~apex_tpu.resilience.fault_injection.
FaultInjector`): ``kill_prefill_replica`` (handoffs abandoned, local
re-prefill fallback), ``corrupt_page_in_flight`` (certification refusal
path), ``stall_handoff`` (deferred delivery — charged to
``serve_handoff_wait``, never a wedged control thread). The tier-1
smoke mixes all three in one seeded schedule and holds greedy streams
bit-identical to a no-fault unified fleet with ``decode_traces`` delta
0 on every survivor. See docs/serving.md "Disaggregated
prefill/decode".
"""

from __future__ import annotations

import dataclasses
import math
import random
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from apex_tpu.serve import paging
from apex_tpu.serve.fleet import (ADMITTING_STATES, REPLICA_DEAD,
                                  REPLICA_DRAINED, REPLICA_DRAINING,
                                  REPLICA_HEALTHY, EngineReplica,
                                  FleetController, FleetStats)
from apex_tpu.serve.scheduler import Request
# module-level on purpose (the fleet/scheduler precedent): a
# function-local import would re-import utils.logging after a
# sys.modules purge and publish to a bus no collection-time subscriber
# sees
from apex_tpu.utils.logging import publish_event

CLONE_SUFFIX = "#prefill"

# handoff lifecycle (control-thread-only transitions):
#   prefilling -> committed -> delivered | refused
#   prefilling | committed -> abandoned (source died / clone rejected)
HANDOFF_PREFILLING = "prefilling"
HANDOFF_COMMITTED = "committed"


class _Handoff:
    """Control-thread bookkeeping for one prefill→decode page handoff."""

    __slots__ = ("freq", "clone_id", "source_id", "state", "t0",
                 "deliver_at")

    def __init__(self, freq, clone_id: str, source_id: str, t0: float):
        self.freq = freq
        self.clone_id = clone_id
        self.source_id = source_id
        self.state = HANDOFF_PREFILLING
        self.t0 = t0
        self.deliver_at = t0


@dataclasses.dataclass
class DisaggStats(FleetStats):
    """Fleet stats plus the handoff ledger. Note ``attempts`` /
    ``per_replica`` counters on PREFILL replicas count their prefill
    jobs (the replica-local clones) — ``prefill_jobs`` carries the
    total so the two views reconcile: real-request completions =
    attempts completed − prefill jobs completed."""

    handoffs: int = 0
    handoffs_delivered: int = 0
    handoffs_refused: int = 0
    handoffs_abandoned: int = 0
    pages_migrated: int = 0
    prefill_jobs: int = 0

    def summary(self) -> Dict[str, Any]:
        out = super().summary()
        out.update({
            "handoffs": self.handoffs,
            "handoffs_delivered": self.handoffs_delivered,
            "handoffs_refused": self.handoffs_refused,
            "handoffs_abandoned": self.handoffs_abandoned,
            "pages_migrated": self.pages_migrated,
            "prefill_jobs": self.prefill_jobs,
        })
        return out


class DisaggController(FleetController):
    """:class:`~apex_tpu.serve.fleet.FleetController` with role-aware
    routing and the prefill→decode page handoff.

    With no ``prefill``-role replicas the controller degrades to the
    base router exactly (every override is gated on :attr:`disagg`).
    With them: real requests route only to ``decode``/``unified``
    replicas; disaggregation-eligible requests (>= one full page of
    prompt, a prefill replica admitting) go through the handoff state
    machine in :meth:`pump` before their first real dispatch. All
    handoff state lives on the control thread — the fleet threading
    contract — so none of it needs a lock."""

    def __init__(self, replicas: Sequence[EngineReplica], **kwargs: Any):
        super().__init__(replicas, **kwargs)
        prefills = [h for h in self.handles if h.role == "prefill"]
        self.disagg = bool(prefills)
        if self.disagg:
            if not any(h.role in ("decode", "unified")
                       for h in self.handles):
                raise ValueError(
                    "disaggregation needs at least one decode (or "
                    "unified) replica to stream pages into — a fleet "
                    "of only prefill replicas serves nobody")
            for h in self.handles:
                if h.engine.prefix is None:
                    raise ValueError(
                        f"replica {h.replica_id!r} ({h.role}) has no "
                        f"prefix index: disaggregation streams pages "
                        f"through it — build every replica's engine "
                        f"with page_size + prefix_cache=True")
            sizes = {int(h.engine.page_size)
                     for h in self.handles}
            if len(sizes) != 1:
                raise ValueError(
                    f"page_size must agree across the fleet (got "
                    f"{sorted(sizes)}): a migrated page must mean the "
                    f"same token span on both sides of the handoff")
            self.page_size: Optional[int] = sizes.pop()
        else:
            self.page_size = None
        # handoff tables (control-thread-only; keyed by REAL request id)
        self._handoffs: Dict[Any, _Handoff] = {}
        self._clone_to_req: Dict[str, Any] = {}
        self._clone_cursor: Dict[str, int] = {}
        # optional control-thread autoscaler, ticked from pump()
        self.autoscaler: Optional["Autoscaler"] = None
        # handoff counters (DisaggStats / bench entries carry them)
        self.handoffs = 0
        self.handoffs_delivered = 0
        self.handoffs_refused = 0
        self.handoffs_abandoned = 0
        self.pages_migrated = 0

    # ---------------------------------------------------------- routing
    def _route(self, exclude: Sequence[str] = ()
               ) -> Optional[EngineReplica]:
        """Real requests never land on a prefill replica — its whole
        pool budget belongs to prompt pages in transit."""
        exclude = tuple(exclude) + tuple(
            h.replica_id for h in self.handles if h.role == "prefill")
        return super()._route(exclude)

    def _route_prefill(self) -> Optional[EngineReplica]:
        """Least-loaded admitting prefill replica (healthy preferred,
        index tiebreak — the same policy shape as the real router)."""
        states = self.registry.states()
        cands = [h for h in self.handles
                 if h.role == "prefill" and not h.crashed
                 and states.get(h.replica_id) in ADMITTING_STATES]
        if not cands:
            return None
        healthy = [h for h in cands
                   if states[h.replica_id] == REPLICA_HEALTHY]
        pool = healthy or cands
        return min(pool, key=lambda h: (h.load(), h.index))

    def _dispatch_new(self, freq, now: float) -> None:
        """Interpose the handoff: an eligible fresh request prefills
        remotely first; everything else (short prompts, no prefill
        capacity, unified fleets) takes the base route-or-pend path."""
        if self.disagg and freq.spec.request_id not in self._handoffs:
            if len(freq.spec.tokens) >= self.page_size:
                source = self._route_prefill()
                if source is not None:
                    self._begin_handoff(freq, source, now)
                    return
        super()._dispatch_new(freq, now)

    # ---------------------------------------------------------- handoff
    def _begin_handoff(self, freq, source: EngineReplica,
                       now: float) -> None:
        spec = freq.spec
        clone_id = f"{spec.request_id}{CLONE_SUFFIX}"
        # the clone is a replica-LOCAL prefill job: one sampled token
        # (prefill's own epilogue — zero decode steps), no deadline (the
        # real request's deadline governs the real attempt; an expiring
        # handoff resolves through abandonment, not eviction racing)
        clone = Request(request_id=clone_id, tokens=list(spec.tokens),
                        max_new_tokens=1, priority=spec.priority,
                        tenant=spec.tenant)
        ho = _Handoff(freq, clone_id, source.replica_id, now)
        self._handoffs[spec.request_id] = ho
        self._clone_to_req[clone_id] = spec.request_id
        self.handoffs += 1
        source.pending_handoffs += 1
        # a rejected submit leaves a terminal rejected clone record —
        # the clone scan abandons the handoff from there
        source.scheduler.submit(clone)
        source.publish_progress()

    def pump(self) -> None:
        if self.disagg:
            self._pump_handoffs(self._clock())
        super().pump()
        if self.autoscaler is not None:
            self.autoscaler.tick()

    def _pump_handoffs(self, now: float) -> None:
        # 1) clone completions: committed (stall consulted once, at
        #    commit) or abandoned (the prefill side shed/evicted it)
        for h in self.handles:
            if h.role != "prefill" or not h.reachable:
                continue
            cursor = self._clone_cursor.get(h.replica_id, 0)
            if h.done_count == cursor:
                continue        # lock-free gate, as in _harvest
            done, self._clone_cursor[h.replica_id] = \
                h.scheduler.done_since(cursor)
            for req in done:
                rid = self._clone_to_req.get(req.request_id)
                ho = self._handoffs.get(rid) if rid is not None else None
                if ho is None or ho.state != HANDOFF_PREFILLING \
                        or ho.source_id != h.replica_id:
                    continue    # stale clone of an already-resolved handoff
                if req.state == "completed":
                    ho.state = HANDOFF_COMMITTED
                    stall = self.injector.handoff_stall_due() \
                        if self.injector is not None else 0.0
                    ho.deliver_at = now + stall
                else:
                    self._abandon(ho, now)
        # 2) sweep every live handoff: cancelled requests, dead sources,
        #    due deliveries (a DRAINING source flushes immediately — its
        #    committed pages must land before it may report drained)
        for rid in list(self._handoffs):
            ho = self._handoffs.get(rid)
            if ho is None:
                continue
            if ho.freq.record is not None:
                # the request settled without us (fleet-wide drain shed,
                # total-loss synthetic record): cancel the handoff
                self._cancel(ho, now)
                continue
            source = self._by_id[ho.source_id]
            src_state = self.registry.state(ho.source_id)
            if source.crashed or src_state == REPLICA_DEAD:
                # prefill completed (or not) on a dying replica: its
                # memory is gone either way — abandon, dispatch without
                # pages, re-prefill locally (bit-exact)
                self._abandon(ho, now)
                continue
            if ho.state == HANDOFF_COMMITTED and \
                    (now >= ho.deliver_at
                     or src_state == REPLICA_DRAINING):
                self._deliver(ho, source, now)

    def _deliver(self, ho: _Handoff, source: EngineReplica,
                 now: float) -> None:
        target = self._route()
        if target is None:
            return      # no decode replica admitting: retry next pump
        spec = ho.freq.spec
        payloads = source.scheduler.export_prefix_pages(
            list(spec.tokens))
        # in-flight corruption (chaos): flip one bit of the K payload
        # AFTER the digest was stamped — exactly what a real transport
        # fault looks like to the receiver
        if self.injector is not None:
            for p in payloads:
                if self.injector.page_corrupt_due():
                    k = np.array(p["k"], copy=True)
                    raw = bytearray(k.tobytes())
                    raw[0] ^= 0x01
                    p["k"] = np.frombuffer(
                        bytes(raw), dtype=k.dtype).reshape(k.shape)
        # certification: expected chain hashes derive from the
        # request's OWN prompt — the receiver trusts nothing the wire
        # claims; the first failed page truncates the accepted chain
        expected = paging.chunk_hashes(list(spec.tokens),
                                       int(self.page_size))
        target_codec = getattr(target.engine, "kv_quant", None)
        accepted: List[Dict[str, Any]] = []
        refused_at = None
        refused_reason = None
        for i, p in enumerate(payloads):
            k_np = np.asarray(p["k"])
            v_np = np.asarray(p["v"])
            if p.get("codec") != target_codec:
                # quantization provenance mismatch: the bytes may be
                # pristine, but the target pool would misread them
                # (codec bytes as fp32 or vice versa) — refuse the whole
                # chain and fall back to local re-prefill, which is
                # exact for the target's OWN codec by construction
                refused_at, refused_reason = i, "quant_codec"
                break
            if i >= len(expected) or p["chain_hash"] != expected[i]:
                refused_at, refused_reason = i, "chain_hash"
                break
            # quantized pages certify codes ‖ scales in ONE digest: a
            # flipped scale bit is refused exactly like a payload bit
            scale_bytes = ()
            if p.get("codec") is not None:
                scale_bytes = (np.asarray(p["k_scale"]).tobytes(),
                               np.asarray(p["v_scale"]).tobytes())
            if paging.page_payload_digest(
                    p["chain_hash"], k_np.tobytes(), v_np.tobytes(),
                    *scale_bytes) != p["digest"]:
                refused_at, refused_reason = i, "digest"
                break
            accepted.append(p)
        installed = {"installed": 0, "duplicate": 0, "no_capacity": 0}
        if accepted:
            installed = target.scheduler.import_prefix_pages(accepted)
        self.pages_migrated += installed["installed"]
        for i in range(installed["installed"]):
            publish_event(
                "serve_page_migrated", request_id=spec.request_id,
                from_replica=source.replica_id,
                to_replica=target.replica_id, page_index=i)
        if refused_at is not None:
            self.handoffs_refused += 1
            publish_event(
                "serve_handoff_refused", level="warning",
                request_id=spec.request_id, page_index=refused_at,
                reason=refused_reason, from_replica=source.replica_id,
                to_replica=target.replica_id)
            if refused_reason == "quant_codec":
                publish_event(
                    "serve_quant_fallback", level="warning",
                    request_id=spec.request_id,
                    source_codec=payloads[refused_at].get("codec"),
                    target_codec=target_codec,
                    from_replica=source.replica_id,
                    to_replica=target.replica_id)
            self._resolve(ho, "refused", now)
        else:
            self.handoffs_delivered += 1
            self._resolve(ho, "delivered", now)
        # the real dispatch goes to the SAME replica the pages landed
        # in — its admission finds them as prefix hits; a refused
        # (or duplicate-truncated) chain just means a longer local tail
        self._submit_attempt(ho.freq, target, now)

    def _abandon(self, ho: _Handoff, now: float) -> None:
        self.handoffs_abandoned += 1
        self._resolve(ho, "abandoned", now)
        # dispatch with no pages: the decode replica re-prefills the
        # whole prompt locally — bit-exact by the PR-5 invariant
        super()._dispatch_new(ho.freq, now)

    def _cancel(self, ho: _Handoff, now: float) -> None:
        """The request settled elsewhere: tear the handoff down without
        dispatching (exactly-once: a settled request never re-enters)."""
        source = self._by_id[ho.source_id]
        if ho.state == HANDOFF_PREFILLING and source.reachable:
            source.scheduler.abort(ho.clone_id)
            source.publish_progress()
        self._resolve(ho, "cancelled", now)

    def _resolve(self, ho: _Handoff, outcome: str, now: float) -> None:
        """Exactly one resolution per handoff: pop the tables, release
        the source's drain gate, charge the wait."""
        self._handoffs.pop(ho.freq.spec.request_id, None)
        self._clone_to_req.pop(ho.clone_id, None)
        source = self._by_id[ho.source_id]
        source.pending_handoffs = max(0, source.pending_handoffs - 1)
        publish_event(
            "serve_handoff_wait",
            seconds=round(max(now - ho.t0, 0.0), 6),
            request_id=ho.freq.spec.request_id, outcome=outcome,
            source=ho.source_id)

    # ------------------------------------------------------------- stats
    def stats(self) -> DisaggStats:
        base = super().stats()
        kw = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(FleetStats)}
        return DisaggStats(handoffs=self.handoffs,
                           handoffs_delivered=self.handoffs_delivered,
                           handoffs_refused=self.handoffs_refused,
                           handoffs_abandoned=self.handoffs_abandoned,
                           pages_migrated=self.pages_migrated,
                           prefill_jobs=self.handoffs, **kw)


class Autoscaler:
    """SLO-driven per-role replica autoscaling on the control thread.

    ``tick()`` evaluates two pressure signals over the role's admitting
    replicas — the worst short-window SLO burn rate
    (:meth:`EngineReplica.burn_short_max`, PR 10) and the tightest
    free-page fraction (:attr:`Engine.free_page_frac`) — and scales
    between ``min_replicas`` and ``max_replicas``:

    - **up** when burn >= ``up_burn`` OR free pages <= ``up_free_frac``:
      prefer warm-restarting a DRAINED standby of the role
      (:meth:`FleetController.restart_replica` — zero recompiles), else
      cold-spawn via ``factory`` (a zero-arg callable returning a
      started-ready :class:`EngineReplica`;
      :meth:`FleetController.add_replica` admits it).
    - **down** when burn <= ``down_burn`` AND free pages >=
      ``down_free_frac``: rolling drain of the least-loaded replica
      (``drain(wait=False)`` — queued work migrates, in-flight work
      finishes, the drained standby becomes the next scale-up's warm
      restart).

    **Hysteresis, structurally.** Three independent guards keep it from
    flapping: (1) the up and down thresholds are disjoint bands — a
    signal between them scales nothing; (2) a direction must hold for
    ``evals`` CONSECUTIVE ticks (one noisy sample resets the streak);
    (3) after any action the ``cooldown_s`` window rejects further
    actions entirely. Total actions over a window W are therefore
    bounded by ``W / cooldown_s`` whatever the traffic does — the
    tier-1 diurnal test asserts exactly this bound. Capacity can never
    leave ``[min_replicas, max_replicas]``: down is refused at min, up
    at max.

    Runs strictly on the fleet's control thread (tick it from the pump
    loop, or attach as ``DisaggController.autoscaler``), so its tables
    need no lock — the same contract every controller table relies on.
    """

    def __init__(self, fleet: FleetController, *, role: str = "decode",
                 min_replicas: int = 1, max_replicas: int = 4,
                 factory=None, up_burn: float = 1.0,
                 down_burn: float = 0.25, up_free_frac: float = 0.1,
                 down_free_frac: float = 0.5, evals: int = 2,
                 cooldown_s: float = 0.25, clock=None):
        if role not in EngineReplica.ROLES:
            raise ValueError(
                f"role={role!r} must be one of {EngineReplica.ROLES}")
        if not 1 <= int(min_replicas) <= int(max_replicas):
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{min_replicas} / {max_replicas}")
        if not 0 <= down_burn < up_burn:
            raise ValueError(
                f"need 0 <= down_burn < up_burn (disjoint hysteresis "
                f"bands), got {down_burn} / {up_burn}")
        if not 0 <= up_free_frac < down_free_frac <= 1:
            raise ValueError(
                f"need 0 <= up_free_frac < down_free_frac <= 1, got "
                f"{up_free_frac} / {down_free_frac}")
        self.fleet = fleet
        self.role = role
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.factory = factory
        self.up_burn = float(up_burn)
        self.down_burn = float(down_burn)
        self.up_free_frac = float(up_free_frac)
        self.down_free_frac = float(down_free_frac)
        self.evals = max(1, int(evals))
        self.cooldown_s = float(cooldown_s)
        self._clock = clock or fleet._clock
        self._up_streak = 0
        self._down_streak = 0
        self._last_action_t: Optional[float] = None
        self.scale_ups = 0
        self.scale_downs = 0
        self.spawned = 0

    # ------------------------------------------------------------ signals
    def _role_handles(self) -> List[EngineReplica]:
        return [h for h in self.fleet.handles if h.role == self.role]

    def active(self) -> List[EngineReplica]:
        states = self.fleet.registry.states()
        return [h for h in self._role_handles()
                if not h.crashed
                and states.get(h.replica_id) in ADMITTING_STATES]

    def standbys(self) -> List[EngineReplica]:
        states = self.fleet.registry.states()
        return [h for h in self._role_handles()
                if states.get(h.replica_id) == REPLICA_DRAINED]

    def signals(self) -> Dict[str, float]:
        active = self.active()
        return {
            "burn": max((h.burn_short_max() for h in active),
                        default=0.0),
            "free_page_frac": min(
                (h.engine.free_page_frac for h in active), default=1.0),
            "active": float(len(active)),
        }

    # --------------------------------------------------------------- tick
    def tick(self) -> Optional[str]:
        """One control-loop evaluation; returns ``"up"``/``"down"`` when
        an action fired, else ``None``."""
        now = self._clock()
        sig = self.signals()
        n = int(sig["active"])
        pressure = sig["burn"] >= self.up_burn \
            or sig["free_page_frac"] <= self.up_free_frac
        quiet = sig["burn"] <= self.down_burn \
            and sig["free_page_frac"] >= self.down_free_frac
        self._up_streak = self._up_streak + 1 if pressure else 0
        self._down_streak = self._down_streak + 1 if quiet else 0
        if self._last_action_t is not None \
                and now - self._last_action_t < self.cooldown_s:
            return None
        if pressure and self._up_streak >= self.evals \
                and n < self.max_replicas:
            return self._scale_up(now, sig)
        if quiet and self._down_streak >= self.evals \
                and n > self.min_replicas:
            return self._scale_down(now, sig)
        return None

    def _scale_up(self, now: float, sig: Dict[str, float]
                  ) -> Optional[str]:
        standby = self.standbys()
        if standby:
            handle = min(standby, key=lambda h: h.index)
            self.fleet.restart_replica(handle.replica_id)   # warm: zero
            #                                                 recompiles
        elif self.factory is not None:
            handle = self.factory()
            if handle.role != self.role:
                raise ValueError(
                    f"factory built a {handle.role!r} replica; this "
                    f"autoscaler scales {self.role!r}")
            self.fleet.add_replica(handle)
            self.spawned += 1
        else:
            return None     # nothing to scale with: not an action
        self.scale_ups += 1
        self._last_action_t = now
        self._up_streak = 0
        self._down_streak = 0
        publish_event(
            "serve_autoscale_up", role=self.role,
            replica=handle.replica_id, replicas=len(self.active()),
            burn=round(sig["burn"], 4),
            free_page_frac=round(sig["free_page_frac"], 4))
        return "up"

    def _scale_down(self, now: float, sig: Dict[str, float]) -> str:
        handle = min(self.active(), key=lambda h: (h.load(), h.index))
        self.fleet.drain(handle.replica_id, wait=False)
        self.scale_downs += 1
        self._last_action_t = now
        self._up_streak = 0
        self._down_streak = 0
        publish_event(
            "serve_autoscale_down", role=self.role,
            replica=handle.replica_id, replicas=len(self.active()),
            burn=round(sig["burn"], 4),
            free_page_frac=round(sig["free_page_frac"], 4))
        return "down"


class DiurnalTraffic:
    """Seeded diurnal request generator — the millions-of-users load
    curve compressed onto a test clock.

    The modeled fleet serves ``users`` users issuing
    ``requests_per_user_per_day`` requests over a (wall-clock) day;
    this harness replays that curve over ``day_s`` seconds at
    ``capacity_scale`` of the modeled volume (the CPU fleet under test
    is a thin slice of the modeled one). The instantaneous rate is
    sinusoidal with ``peak_to_trough`` ratio, trough at phase 0:

    ``rate(x) = trough + (peak - trough) * (1 - cos(2*pi*x)) / 2``

    :meth:`due` integrates the rate between consecutive calls against
    the injected ``clock`` and emits whole requests (fractional
    residue carries over), each with a seeded prompt — same seed +
    same clock readings = the identical request stream, which is what
    lets the autoscaler chaos test replay bit-for-bit."""

    def __init__(self, *, users: int = 2_000_000,
                 requests_per_user_per_day: float = 8.0,
                 peak_to_trough: float = 4.0, day_s: float = 86400.0,
                 capacity_scale: float = 1e-4, seed: int = 0,
                 prompt_lens: Sequence[int] = (8,),
                 max_new_tokens: int = 4, vocab: int = 61,
                 id_prefix: str = "diurnal",
                 clock=time.perf_counter):
        if peak_to_trough < 1:
            raise ValueError(
                f"peak_to_trough={peak_to_trough} must be >= 1")
        mean_rps = float(users) * float(requests_per_user_per_day) \
            / 86400.0 * float(capacity_scale)
        r = float(peak_to_trough)
        self.trough_rps = 2.0 * mean_rps / (1.0 + r)
        self.peak_rps = r * self.trough_rps
        self.day_s = float(day_s)
        self.prompt_lens = list(prompt_lens)
        self.max_new_tokens = int(max_new_tokens)
        self.vocab = int(vocab)
        self.id_prefix = id_prefix
        self.rng = random.Random(seed)
        self.clock = clock
        self._t0: Optional[float] = None
        self._last_t: Optional[float] = None
        self._accum = 0.0
        self.emitted = 0

    def rate_at(self, now: float) -> float:
        """Requests per second at wall time ``now`` (0 before start)."""
        if self._t0 is None:
            return 0.0
        x = ((now - self._t0) % self.day_s) / self.day_s
        return self.trough_rps + (self.peak_rps - self.trough_rps) \
            * (1.0 - math.cos(2.0 * math.pi * x)) / 2.0

    def start(self, t0: Optional[float] = None) -> "DiurnalTraffic":
        self._t0 = self.clock() if t0 is None else float(t0)
        self._last_t = self._t0
        self._accum = 0.0
        return self

    def due(self, now: Optional[float] = None) -> List[Request]:
        """Requests that became due since the previous call (consumed).
        Trapezoidal integration of the rate curve over the elapsed
        window; sub-request residue accumulates, so long-run volume
        matches the curve whatever the polling cadence."""
        if self._t0 is None:
            raise RuntimeError("DiurnalTraffic.due() before start()")
        now = self.clock() if now is None else float(now)
        dt = max(now - self._last_t, 0.0)
        self._accum += dt * (self.rate_at(self._last_t)
                             + self.rate_at(now)) / 2.0
        self._last_t = now
        n = int(self._accum)
        self._accum -= n
        out: List[Request] = []
        for _ in range(n):
            self.emitted += 1
            plen = self.rng.choice(self.prompt_lens)
            out.append(Request(
                request_id=f"{self.id_prefix}-{self.emitted}",
                tokens=[self.rng.randrange(self.vocab)
                        for _ in range(plen)],
                max_new_tokens=self.max_new_tokens))
        return out
