"""Continuous-batching request scheduler.

The serving loop between decode steps, in pure host python (everything
device-side is the engine's fixed-shape compiled calls):

admission queue -> slot assignment (batched prefill) -> decode -> per-slot
termination (EOS / max new tokens / context full) -> eviction -> backfill
from the queue -> next decode step.

The scheduler is **mesh-agnostic**: a tensor-parallel engine
(``EngineConfig(tp=N)``, docs/serving.md "Tensor-parallel decode")
exposes the identical prefill/decode/evict surface — slot state, the
queue, page tables, and the tick journal are all replicated host data,
sharding lives entirely behind the engine's compiled calls — so
everything here (admission control, deadlines, warm restart, metrics,
tracing) runs unchanged over a mesh.

Lifecycle events ride the PR-2 telemetry bus
(:func:`apex_tpu.utils.logging.publish_event`) so a
:class:`~apex_tpu.monitor.goodput.GoodputLedger` or Telemetry JSONL mirror
picks them up with zero wiring:

- ``serve_request_admitted``  {request_id, slot, queue_wait_s}
- ``serve_queue_wait``        {seconds} — a timed goodput cause: time a
  request sat in the queue because no slot was free (published at
  admission and at abort of a still-queued request, always the
  INCREMENT not yet charged — a warm-restart re-admission can never
  double-count a wait; a shed request's wait rides
  ``serve_request_rejected`` and a deadline expiry charges its whole
  span under ``serve_deadline_exceeded`` instead)
- ``serve_request_completed`` {request_id, slot, new_tokens, ttft_s,
  latency_s, finish_reason}
- ``serve_request_evicted``   {request_id, slot, reason} — mid-stream
  abort or shutdown; completed requests publish completed, not evicted
- ``serve_decode_step``       {seconds, active} — per-step decode latency
- ``serve_request_rejected``  {request_id, reason, retriable, seconds} —
  admission control: the backlog was full (``max_queue``) and the shed
  policy chose this request; ``seconds`` (time already queued, 0 for a
  reject-at-submit) is a timed loss cause
- ``serve_deadline_exceeded`` {request_id, slot, seconds, deadline_ms,
  admitted} — the per-request deadline expired (queued-but-never-admitted
  requests time out too); ``seconds`` — the whole submit-to-expiry span
  was lost serving time — is a timed loss cause
- ``serve_degraded_mode``     {entered, queue_depth, clamp} — sustained
  overload flipped graceful degradation on/off
- ``serve_engine_restart``    {restarts, resumed_slots, requeued, error}
  — a warm restart recovered the fleet after a fatal tick exception
- ``serve_prefix_hit``        {request_id, slot, hit_tokens, hit_pages,
  scanned_tokens} — an admission reused resident read-only prefix pages
  and skipped prefilling them (engines with ``prefix_cache``)
- ``serve_page_alloc_fail``   {seconds, queue_depth, free_page_frac} —
  admission stalled because the KV pool had no free pages;
  ``seconds`` (the whole head-of-queue stall window) is a timed loss
  cause distinct from plain ``serve_queue_wait`` — capacity lost to KV
  bytes, not to slot count

Aborts can be driven deterministically by the resilience
:class:`~apex_tpu.resilience.fault_injection.FaultInjector`
(``abort_request(request_id, at_step)``): the scheduler polls
``serve_aborts_due`` before each decode step, which is how tier-1 proves a
mid-stream abort leaves every other slot's output stream bit-identical
under greedy decoding. (The engine's slot *arithmetic* is always
isolated — logits never depend on other slots' bytes — but under
``temperature > 0`` an abort changes backfill timing and with it the
shared PRNG stream, so surviving requests' *sampled* tokens may differ.)

**Tracing** (``tracer=``, a :class:`~apex_tpu.monitor.trace.Tracer`):
every request becomes ONE trace — ``queue → prefill → decode →
complete|evict|abort`` spans stamped from the scheduler's own
``perf_counter`` reads, so span durations reconcile EXACTLY with the
TTFT/latency accounting (``queue.dur == queue_wait``, ``queue + prefill
== ttft``, ``root.dur == latency``) — plus a scheduler-level trace of
per-tick ``decode_tick`` spans. With ``tracer=None`` (the default) no
span code runs at all, and tracing never touches the device either way:
the one-compile invariant holds with it on (asserted in tier-1).
``flight_recorder=`` arms a crash dump around :meth:`run`;
``memory_accountant=`` samples HBM per decode tick.

**Host spans** (:func:`apex_tpu.utils.prof.annotate`, no switch): a tick
is ``apex.sched.step`` (``queued``), holding ``apex.sched.admit`` (the
page probes to the batch's last first token, around the engine's
``apex.prefill``), the engine's ``apex.decode_step``, and
``apex.sched.accept`` (accept loop, eviction flush, metrics and journal
tick). Under a profiler session they land on the host plane of the device
trace, on its clock; with a process tracer installed they nest in its
span tree; with neither each costs an inactive annotation.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Sequence

import numpy as np

from apex_tpu.monitor.export import percentile
from apex_tpu.serve.engine import Engine
from apex_tpu.serve.spec import NGramDrafter
from apex_tpu.utils.logging import publish_event
from apex_tpu.utils.prof import annotate

# a request in one of these states has reached its exactly-one terminal
# status; recovery and the drain path must never touch it again
TERMINAL_STATES = ("completed", "evicted", "rejected")


# eq=False: the queue holds request objects, not values — a resubmitted
# identical prompt must not alias an existing request in `in`/`remove`
@dataclasses.dataclass(eq=False)
class Request:
    """One generation request and its accounting."""

    request_id: Any
    tokens: Sequence[int]                  # prompt token ids
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # total latency budget from submit (monotonic sweep in step()); a
    # queued-but-never-admitted request times out against it too
    deadline_ms: Optional[float] = None
    priority: int = 0         # higher wins under the "priority" shed policy
    # optional tenant label for per-tenant accounting (ServeMetrics):
    # admission/latency/SLO series are recorded per tenant with bounded
    # cardinality; None lands under the "default" tenant
    tenant: Optional[str] = None
    # cross-replica trace propagation (serve.fleet): the controller's
    # journey trace id + the attempt span id this request should nest
    # under, so the replica's queue/prefill/decode spans link as children
    # of the fleet-level attempt. None (the default) keeps the PR-6
    # behavior: one standalone "request:<id>" trace per request
    trace_id: Optional[str] = None
    trace_parent: Optional[int] = None
    # per-request decode policy (the DecodePolicy seam,
    # apex_tpu.serve.spec): a policy spelling installed on the slot at
    # admission, so one batch mixes greedy/top_p/min_p requests on one
    # trace. None = the engine's default policy; needs
    # EngineConfig(decode_policy=...).
    policy: Optional[str] = None

    # filled in by the scheduler
    generated: List[int] = dataclasses.field(default_factory=list)
    state: str = "queued"     # queued|running|completed|evicted|rejected
    # eos|length|context|aborted|deadline|queue_full|shed|engine_failure
    finish_reason: Optional[str] = None
    slot: Optional[int] = None
    # effective token budget granted at admission (max_new_tokens, or the
    # degraded-mode clamp of it). A separate field — never a mutation of
    # max_new_tokens — so a warm-restart rollback re-admits against the
    # CURRENT overload state, not a stale clamp from the torn tick
    budget: Optional[int] = None
    # queue-wait seconds already charged to the ledger: a request
    # re-admitted after a warm-restart rollback charges only the
    # increment, so the cause totals its true final wait
    wait_charged: float = 0.0
    submit_t: Optional[float] = None
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None or self.submit_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def latency_s(self) -> Optional[float]:
        if self.done_t is None or self.submit_t is None:
            return None
        return self.done_t - self.submit_t

    def record(self) -> Dict[str, Any]:
        out = {
            "request_id": self.request_id, "state": self.state,
            "finish_reason": self.finish_reason,
            "prompt_tokens": len(self.tokens),
            "new_tokens": len(self.generated),
            "generated": list(self.generated),
        }
        if self.tenant is not None:
            out["tenant"] = self.tenant
        if self.state == "rejected":
            # load shedding is a server condition, not a request defect —
            # the CLI surfaces the retriable status so clients back off
            # and resubmit instead of treating it as a hard failure
            out["retriable"] = True
        for k in ("ttft_s", "latency_s"):
            v = getattr(self, k)
            if v is not None:
                out[k] = round(v, 6)
        lat = self.latency_s
        if lat and self.generated:
            out["tokens_per_s"] = round(len(self.generated) / lat, 3)
        return out


@dataclasses.dataclass
class ServeStats:
    """Aggregate accounting over a scheduler run."""

    requests: List[Dict[str, Any]]
    decode_steps: int
    decode_step_s: List[float]
    decode_tokens: int          # tokens produced BY decode steps
    total_new_tokens: int       # includes each request's prefill-sampled
    wall_s: float               # first token
    restarts: int = 0           # warm restarts survived (recover() calls)
    admitted: int = 0           # requests that reached a slot
    prefix_hits: int = 0        # admissions that reused resident pages
    peak_resident_tokens: int = 0  # max cache tokens live at once
    # speculative decoding: active slot-steps (one slot taking one
    # decode/verify step), drafts proposed, drafts accepted — the
    # acceptance accounting behind accepted_tokens_per_step (exactly 1.0
    # on the one-token path, > 1 when speculation earns its keep)
    decode_slot_steps: int = 0
    spec_proposed: int = 0
    spec_accepted: int = 0

    def summary(self) -> Dict[str, Any]:
        # ONE percentile rule for every field: the exact nearest-rank
        # helper shared with the histogram-quantile tests (the seed used
        # len//2 indexing for TTFT but round-half-even linear indexing
        # for the step fields — two answers for "the median");
        # percentile() sorts internally, nothing here needs order
        lat = list(self.decode_step_s)
        ttfts = [r["ttft_s"] for r in self.requests if "ttft_s" in r]
        decode_s = sum(lat)
        rejected = sum(r["state"] == "rejected" for r in self.requests)
        return {
            "requests": len(self.requests),
            "completed": sum(r["state"] == "completed"
                             for r in self.requests),
            "evicted": sum(r["state"] == "evicted"
                           for r in self.requests),
            # SLO accounting: load shed + deadline misses + restarts are
            # first-class summary fields (the bench entry and the CLI
            # summary both carry them; shed_rate gates lower-is-better)
            "rejected": rejected,
            "deadline_exceeded": sum(
                r.get("finish_reason") == "deadline"
                for r in self.requests),
            "shed_rate": round(rejected / len(self.requests), 4)
            if self.requests else 0.0,
            "restarts": self.restarts,
            "decode_steps": self.decode_steps,
            "new_tokens": self.total_new_tokens,
            # pool effectiveness: what fraction of admissions were
            # served partly from shared prefix pages, and the densest the
            # cache ever got (the capacity number small pages
            # multiply; divide by the engine's kv_cache_bytes for the
            # bench's resident_tokens_per_hbm_byte)
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": round(self.prefix_hits / self.admitted, 4)
            if self.admitted else 0.0,
            "peak_resident_tokens": self.peak_resident_tokens,
            # decode throughput: decode-produced tokens over decode-step
            # time ONLY — prefill-sampled first tokens ride TTFT, not this
            # rate, so the bench headline tracks the decode hot path and
            # not the run's admission pattern
            "tokens_per_s": round(
                self.decode_tokens / decode_s, 3) if decode_s else 0.0,
            # speculative throughput: committed tokens per SLOT-step —
            # 1.0 exactly on the one-token path (and for a drafter that
            # never guesses right), > 1 when verified drafts multiply
            # each compiled step. check_regression gates it
            # higher-is-better; the spec workload axes make speculative
            # captures refuse to gate against one-token baselines.
            "accepted_tokens_per_step": round(
                self.decode_tokens / self.decode_slot_steps, 4)
            if self.decode_slot_steps else 0.0,
            "spec_accept_rate": round(
                self.spec_accepted / self.spec_proposed, 4)
            if self.spec_proposed else 0.0,
            "p50_step_ms": round(percentile(lat, 0.50) * 1e3, 3),
            "p99_step_ms": round(percentile(lat, 0.99) * 1e3, 3),
            "ttft_p50_ms": round(percentile(ttfts, 0.50) * 1e3, 3),
            # the tail the ttft_p99_ms SLO objective watches live — the
            # exact end-of-run value is the oracle the histogram estimate
            # is held against in tier-1
            "ttft_p99_ms": round(percentile(ttfts, 0.99) * 1e3, 3),
            "wall_s": round(self.wall_s, 6),
        }


class ServeScheduler:
    """Drive an :class:`Engine` over a request stream with continuous
    batching. ``fault_injector`` (optional) supplies scripted mid-stream
    aborts, decode-step crashes, latency spikes, and queue storms; a real
    deployment calls :meth:`abort` directly — :meth:`submit` and
    :meth:`abort` are safe from other threads while :meth:`run` drives
    the loop (one reentrant lock serializes every queue/slot mutation; a
    cross-thread call lands between ticks).

    Resilience seams (all optional, see
    :mod:`apex_tpu.serve.resilience`): ``admission=`` an
    :class:`~apex_tpu.serve.resilience.AdmissionController` bounds the
    backlog with an explicit shed policy and drives graceful
    degradation; ``journal=`` a
    :class:`~apex_tpu.serve.resilience.TickJournal` snapshots request
    metadata per tick so :meth:`recover` can warm-restart after a fatal
    tick exception without losing a single request's terminal status.
    Per-request ``deadline_ms`` is swept every tick (monotonic clocks)
    whether or not the request was ever admitted."""

    def __init__(self, engine: Engine, *, fault_injector=None,
                 tracer=None, flight_recorder=None, memory_accountant=None,
                 admission=None, journal=None, metrics=None, drafter=None):
        self.engine = engine
        self.injector = fault_injector
        self.admission = admission
        self.journal = journal
        self.restarts = 0
        # speculative decoding: the host-side drafter proposes each
        # tick's draft tokens (injectable — tests script pathological
        # drafters; correctness never depends on it, the engine's verify
        # step accepts exactly). Defaults to the n-gram prompt-lookup
        # drafter whenever the engine is built with spec_draft_len >= 1.
        self.drafter = drafter
        if self.drafter is None and engine.spec_draft_len:
            self.drafter = NGramDrafter()
        # observability seams (all optional; None = zero work per tick)
        self.tracer = tracer if tracer is not None and tracer.enabled \
            else None
        self.flight = flight_recorder
        self.memory = memory_accountant
        # live per-tenant accounting + SLO evaluation (serve.metrics
        # ServeMetrics): hooks fire at the same points the bus events
        # publish, all host-side — decode still compiles exactly once
        # with metrics armed (tier-1 scrapes a live loop and asserts)
        self.metrics = metrics
        self._req_spans: Dict[Request, Dict[str, Any]] = {}
        self._sched_span = None    # root of the scheduler's tick trace
        # submit()/abort() are documented entry points for OTHER threads
        # (a serving frontend feeding the loop, a deployment cancelling a
        # request) while step() runs — every queue/slot/accounting
        # mutation takes this lock (apexlint APX002 keeps the
        # discipline). Reentrant: step()'s injector path calls abort().
        self._lock = threading.RLock()
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[Request]] = \
            [None] * engine.config.num_slots
        self.done: List[Request] = []
        self.decode_steps = 0
        self.decode_step_s: List[float] = []
        self.decode_tokens = 0
        self.decode_slot_steps = 0    # active slots × decode steps
        self.spec_proposed = 0        # draft tokens offered to verify
        self.spec_accepted = 0        # draft tokens the oracle accepted
        self.admitted = 0             # requests that reached a slot
        self.prefix_hits = 0          # admissions served partly from the
        #                               prefix index
        self.peak_resident_tokens = 0
        # head-of-queue page-allocation stall window:
        # opened when admission is blocked on pool pages, closed + charged
        # to serve_page_alloc_fail when pages free up (or at drain)
        self._alloc_stall_t0: Optional[float] = None
        self._alloc_stall_req: Optional[Request] = None
        self._to_evict: set = set()   # slots freed, device reset pending
        self._t0: Optional[float] = None

    # --------------------------------------------------------- admission
    def submit(self, req: Request) -> bool:
        """Enqueue ``req``. Returns ``True`` when it entered the backlog,
        ``False`` when admission control rejected it (terminal state
        ``rejected``, retriable — the record and bus event carry it);
        malformed requests (empty/oversized prompt) still raise, they are
        caller errors, not load."""
        if not len(req.tokens):
            raise ValueError(f"request {req.request_id!r}: empty prompt")
        if len(req.tokens) >= self.engine.max_len:
            raise ValueError(
                f"request {req.request_id!r}: prompt of {len(req.tokens)} "
                f"tokens leaves no room to generate under max_len="
                f"{self.engine.max_len}")
        req.submit_t = time.perf_counter()
        req.state = "queued"
        with self._lock:
            if self.metrics is not None:
                # counted BEFORE the admission verdict: shed_frac is
                # rejected over everything that ASKED, so a
                # reject-at-submit must land in the submitted total too
                self.metrics.on_submit(req)
            if self.tracer is not None:
                # one trace per request, rooted at submit; span stamps
                # reuse the scheduler's own clock reads so trace durations
                # and the TTFT/latency accounting are the same numbers.
                # A fleet-dispatched request carries the controller's
                # journey trace id + attempt span id: this root becomes a
                # child in the cross-replica journey instead of a
                # standalone trace. Opened BEFORE the admission verdict:
                # a reject-at-submit is a bad outcome the tail-capture
                # router must be able to promote — a journey with zero
                # spans would be invisible to the trace file
                root = self.tracer.begin(
                    "request",
                    trace_id=req.trace_id or f"request:{req.request_id}",
                    parent_id=req.trace_parent,
                    t0=req.submit_t, request_id=str(req.request_id),
                    prompt_tokens=len(req.tokens))
                self._req_spans[req] = {
                    "root": root,
                    "queue": self.tracer.begin("queue", parent=root,
                                               t0=req.submit_t)}
            if self.admission is not None:
                verdict, victim = self.admission.on_submit(self.queue, req)
                if verdict == "reject":
                    reason = ("priority" if self.admission.shed_policy
                              == "priority" else "queue_full")
                    self._reject(req, reason, seconds=0.0)
                    return False
                if victim is not None:
                    # shed a queued request to make room: its (not yet
                    # charged) wait so far is lost time and the
                    # rejection says so
                    self.queue.remove(victim)
                    self._stall_head_removed(victim)
                    self._reject(victim, "shed",
                                 seconds=max(req.submit_t
                                             - victim.submit_t
                                             - victim.wait_charged, 0.0))
            self.queue.append(req)
        return True

    def _reject(self, req: Request, reason: str, *, seconds: float) -> None:
        """Terminal rejection (admission control / drain): accounted
        exactly once, retriable, with the wasted queue time as a timed
        loss cause."""
        # caller holds self._lock (submit()/drain_and_reject())
        req.state = "rejected"
        req.finish_reason = reason
        req.done_t = time.perf_counter()
        self.done.append(req)
        self._close_trace(req, "reject", reason)
        if self.metrics is not None:
            self.metrics.on_reject(req, reason)
        publish_event("serve_request_rejected", level="warning",
                      request_id=req.request_id, reason=reason,
                      retriable=True, seconds=round(seconds, 6),
                      queue_depth=len(self.queue))

    def _admit(self) -> None:
        """Fill free slots from the queue with ONE batched prefill call
        (per shared pow2 bucket) and record each admitted request's first
        sampled token.

        The engine is probed FIRST (``Engine.admission_page_cost``):
        a request whose page reservation does not fit stays at the head
        of the queue — FIFO order holds, the stall is charged to
        ``serve_page_alloc_fail`` once pages free up, and the batched
        prefill below can never fail allocation mid-batch."""
        # caller holds self._lock (step())
        free = [i for i, r in enumerate(self.slots) if r is None]
        if free and self.queue:
            with annotate("apex.sched.admit"):
                # a slot freed since the last flush (an abort, a deadline)
                # still holds its pages: give them back before the probe,
                # so a free slot is never short of the pages it held
                if self._to_evict:
                    self._flush_evictions()
                self._admit_into(free)

    def _admit_into(self, free: List[int]) -> None:
        """:meth:`_admit` once there is a free slot and a queued request:
        the page probes, the batched prefill, the first tokens."""
        # caller holds self._lock (_admit())
        prior_stall = self._alloc_stall_t0
        batch: Dict[int, Request] = {}
        pending_pages = 0
        # prefix-hit pages promised to earlier batch members: a later
        # probe must not count them as evictable headroom (the engine's
        # batched prefill protects the whole batch's hits)
        pending_protect: set = set()
        stalled = False
        while free and self.queue:
            head = self.queue[0]
            # the admitted budget (degradation clamp included) sizes the
            # page reservation, so probe with the value admission grants
            budget = (self.admission.clamp(head.max_new_tokens)
                      if self.admission is not None
                      else head.max_new_tokens)
            cost = self.engine.admission_page_cost(head.tokens, budget,
                                                   pending_pages,
                                                   protect=pending_protect)
            if cost is None:
                # head-of-line page stall: no slot membership change, the
                # request waits for completions to free pages
                stalled = True
                break
            pending_pages += cost
            slot = free.pop(0)
            req = self.queue.popleft()
            req.slot = slot
            req.budget = budget
            self.slots[slot] = req
            batch[slot] = req
        if batch and prior_stall is not None:
            # the head that opened the window was admitted: charge its
            # whole blocked span (an admission that merely rides along
            # while the head STAYS blocked must not close — or reset —
            # the window, so the true start is never lost)
            self._end_alloc_stall()
        if stalled and self._alloc_stall_t0 is None:
            self._alloc_stall_t0 = time.perf_counter()
            self._alloc_stall_req = self.queue[0]
        if not batch:
            return
        now = time.perf_counter()
        for slot, req in batch.items():
            req.admit_t = now
            req.state = "running"
            if self.drafter is not None and \
                    hasattr(self.drafter, "observe"):
                # cross-request prompt lookup: admitted prompts feed the
                # drafter's corpus (host state only — admission order is
                # deterministic, so drafts are too)
                self.drafter.observe(req.tokens)
            if self.engine.policy_armed:
                # per-request policy mixing: the slot's knobs are DATA
                # on the compiled calls — installing them never retraces
                self.engine.set_slot_policy(slot, req.policy)
            wait = max(now - req.submit_t - req.wait_charged, 0.0)
            req.wait_charged += wait
            self.admitted += 1
            publish_event("serve_queue_wait", seconds=wait,
                          request_id=req.request_id)
            publish_event("serve_request_admitted",
                          request_id=req.request_id, slot=slot,
                          queue_wait_s=round(wait, 6))
            if self.metrics is not None:
                self.metrics.on_admit(req, wait)
            sp = self._req_spans.get(req)
            if sp is not None:
                self.tracer.end(sp["queue"], t1=now,
                                queue_wait_s=round(wait, 6))
                sp["prefill"] = self.tracer.begin(
                    "prefill", parent=sp["root"], t0=now, slot=slot)
        first, _last_logits, _all = self.engine.prefill(
            {slot: req.tokens for slot, req in batch.items()},
            budgets={slot: req.budget for slot, req in batch.items()})
        t_first = time.perf_counter()
        for slot, req in batch.items():
            hit = self.engine.last_prefill_stats.get(slot, {})
            if hit.get("hit_tokens"):
                # the shared-prefix win, per request: these tokens were
                # served from resident read-only pages instead of being
                # re-prefilled (the counted event the hit-rate audits)
                self.prefix_hits += 1
                publish_event("serve_prefix_hit",
                              request_id=req.request_id, slot=slot,
                              hit_tokens=hit["hit_tokens"],
                              hit_pages=hit["hit_pages"],
                              scanned_tokens=hit["scanned"])
                if self.metrics is not None:
                    self.metrics.on_prefix_hit(req, hit["hit_tokens"])
            req.first_token_t = t_first
            sp = self._req_spans.get(req)
            if sp is not None:
                self.tracer.end(sp["prefill"], t1=t_first)
                # opened BEFORE _accept_token: a request finishing on its
                # prefill-sampled token still closes a decode span
                sp["decode"] = self.tracer.begin(
                    "decode", parent=sp["root"], t0=t_first, slot=slot)
            self._accept_token(req, int(first[slot]))

    def _end_alloc_stall(self) -> None:
        """Close an open page-allocation stall window: the whole span the
        queue head spent blocked on pool pages is lost serving time, and
        the cause says so (a plain ``serve_queue_wait`` would blame slot
        scarcity for what is a KV-capacity shortage)."""
        # caller holds self._lock (_admit()/drain_and_reject()/run())
        if self._alloc_stall_t0 is None:
            return
        stalled = max(time.perf_counter() - self._alloc_stall_t0, 0.0)
        self._alloc_stall_t0 = None
        self._alloc_stall_req = None
        publish_event("serve_page_alloc_fail", level="warning",
                      seconds=round(stalled, 6),
                      queue_depth=len(self.queue),
                      free_page_frac=round(self.engine.free_page_frac, 4))

    def _stall_head_removed(self, req: Request) -> None:
        """A queued request left the queue by a NON-admission path (shed,
        abort, deadline expiry): when it is the head whose page stall
        opened the window, close-and-charge the window now — the span it
        spent blocked on pages is real lost capacity, but the idle span
        after its departure is not, and a window left open here would
        charge that whole idle span to ``serve_page_alloc_fail`` at the
        next admission."""
        # caller holds self._lock (submit()/abort()/_sweep_deadlines())
        if req is self._alloc_stall_req:
            self._end_alloc_stall()

    # -------------------------------------------------------- lifecycle
    def _accept_token(self, req: Request, tok: int) -> None:
        # caller holds self._lock (step()/_admit())
        req.generated.append(tok)
        budget = req.budget if req.budget is not None \
            else req.max_new_tokens
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(req, "eos")
        elif len(req.generated) >= budget:
            self._finish(req, "length")
        elif len(req.tokens) + len(req.generated) >= self.engine.max_len:
            self._finish(req, "context")

    # ------------------------------------------------------- speculation
    def _build_drafts(self, spec_k: int):
        """Each active slot's host draft for this tick, clamped so the
        verify commit (up to ``draft_len + 1`` tokens) can never overrun
        the request's token budget, the model context, or the slot's
        admitted cache capacity — a fully clamped slot runs a plain
        one-token step on the SAME verify trace (``draft_len`` is
        data)."""
        # caller holds self._lock (step())
        b = self.engine.config.num_slots
        drafts = np.zeros((b, spec_k), np.int32)
        draft_lens = np.zeros((b,), np.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            budget = req.budget if req.budget is not None \
                else req.max_new_tokens
            room = min(budget - len(req.generated),
                       self.engine.max_len - len(req.tokens)
                       - len(req.generated),
                       self.engine.spec_headroom(slot))
            k = max(min(spec_k, room - 1), 0)
            if k:
                d = self.drafter.draft(
                    list(req.tokens) + req.generated, k)[:k]
                draft_lens[slot] = len(d)
                drafts[slot, :len(d)] = np.asarray(d, np.int32)
        return drafts, draft_lens

    def _accept_spec(self, committed, counts, draft_lens) -> int:
        """Commit each slot's verified token run through the one-token
        acceptance path — EOS/budget/context checks run per TOKEN in
        commit order, so deadline/evict/journey accounting counts
        tokens, not steps. Tokens the engine committed after a terminal
        state are discarded (the slot is released and its cache rows
        evicted regardless). Publishes the per-step draft acceptance
        aggregates and feeds the metrics hooks; returns the number of
        tokens that actually entered streams."""
        # caller holds self._lock (step())
        appended = 0
        acc_total = 0
        rej_total = 0
        tenant_tokens: Dict[Any, int] = {}
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            n = int(counts[slot])
            proposed = int(draft_lens[slot])
            accepted = max(n - 1, 0)   # committed minus the bonus token
            acc_total += accepted
            rej_total += max(proposed - accepted, 0)
            self.spec_proposed += proposed
            self.spec_accepted += accepted
            if self.metrics is not None:
                self.metrics.on_spec(req, proposed=proposed,
                                     accepted=accepted)
            took = 0
            for tok in committed[slot][:n]:
                took += 1
                self._accept_token(req, int(tok))
                if req.state != "running":
                    break
            appended += took
            tenant_tokens[req.tenant] = \
                tenant_tokens.get(req.tenant, 0) + took
        if self.metrics is not None and tenant_tokens:
            self.metrics.on_spec_step(tenant_tokens)
        if acc_total:
            publish_event("serve_spec_draft_accepted", tokens=acc_total,
                          step=self.decode_steps)
        if rej_total:
            publish_event("serve_spec_draft_rejected", tokens=rej_total,
                          step=self.decode_steps)
        return appended

    def _close_trace(self, req: Request, marker: str, reason: str) -> None:
        """End a request's trace: close any still-open lifecycle spans at
        ``done_t``, drop a terminal marker span, close the root."""
        # caller holds self._lock (_finish/_evict)
        sp = self._req_spans.pop(req, None)
        if sp is None or self.tracer is None:
            return
        t1 = req.done_t if req.done_t is not None else time.perf_counter()
        status = "ok" if marker == "complete" else "cancelled"
        for key in ("queue", "prefill", "decode"):
            span = sp.get(key)
            if span is not None:
                self.tracer.end(span, t1=t1, status=status)
        mark = self.tracer.begin(marker, parent=sp["root"], t0=t1,
                                 reason=reason)
        self.tracer.end(mark, t1=t1)
        # the EXACT rounded accounting values ride the root close as
        # attrs (the same numbers record()/summary() carry), so
        # tools/trace_explain.py reconciles bit-for-bit instead of
        # re-deriving them from microsecond-rounded stamps
        extra: Dict[str, Any] = {}
        if req.ttft_s is not None:
            extra["ttft_s"] = round(req.ttft_s, 6)
        if req.latency_s is not None:
            extra["latency_s"] = round(req.latency_s, 6)
        self.tracer.end(sp["root"], t1=t1, status=status,
                        state=req.state, finish_reason=reason,
                        new_tokens=len(req.generated), **extra)

    def _finish(self, req: Request, reason: str) -> None:
        # caller holds self._lock (_accept_token)
        req.state = "completed"
        req.finish_reason = reason
        req.done_t = time.perf_counter()
        self.done.append(req)
        self._release(req)
        self._close_trace(req, "complete", reason)
        if self.metrics is not None:
            self.metrics.on_complete(req)
        publish_event("serve_request_completed",
                      request_id=req.request_id, slot=req.slot,
                      new_tokens=len(req.generated), finish_reason=reason,
                      ttft_s=round(req.ttft_s or 0.0, 6),
                      latency_s=round(req.latency_s or 0.0, 6))

    def _release(self, req: Request) -> None:
        # caller holds self._lock (_finish/_evict)
        # the device-side length reset is deferred and batched: several
        # requests finishing on one tick cost ONE evict_slots call, and a
        # slot backfilled on the next tick needs no eviction at all
        # (prefill resets admitted slots itself)
        if req.slot is not None and self.slots[req.slot] is req:
            self.slots[req.slot] = None
            self._to_evict.add(req.slot)

    def _flush_evictions(self) -> None:
        """One mask-shaped engine.evict for every slot freed since the
        last flush, skipping slots a prefill already reclaimed."""
        # caller holds self._lock (step()/run())
        pending = {s for s in self._to_evict if self.slots[s] is None}
        if pending:
            self.engine.evict(sorted(pending))
        self._to_evict.clear()

    # ------------------------------------------------- fleet hooks
    def load(self) -> int:
        """Queued + in-slot requests — the fleet router's load signal
        (and its drain-completion probe). Safe from any thread."""
        with self._lock:
            return len(self.queue) + sum(r is not None
                                         for r in self.slots)

    def progress(self):
        """``(load, done_count)`` under ONE lock acquisition — the fleet
        worker reads this between ticks and publishes it as a lock-free
        snapshot (:attr:`EngineReplica` plain-rebind), so the
        controller's per-pump probes never contend with the scheduler
        lock :meth:`step` holds across a whole tick."""
        with self._lock:
            return (len(self.queue) + sum(r is not None
                                          for r in self.slots),
                    len(self.done))

    def done_since(self, cursor: int):
        """Terminal requests appended to :attr:`done` since ``cursor``,
        plus the new cursor — the fleet router's harvest hook. Read
        under the scheduler lock; the returned :class:`Request` objects
        are terminal and never mutate again, so the caller may inspect
        them lock-free."""
        with self._lock:
            return list(self.done[cursor:]), len(self.done)

    def pop_queued(self, request_id) -> Optional[Request]:
        """Remove and return a still-queued request WITHOUT a terminal
        status — the fleet drain/migrate hook: the request is about to
        be re-submitted to another replica, so terminal-accounting it
        here (the way :meth:`abort` does) would give it two records
        fleet-wide. Its wasted queue time still lands on the ledger
        (``serve_queue_wait`` — the wait was real whichever replica
        finally serves it). Returns ``None`` when the request is not
        queued (already admitted — the caller lets it finish in place —
        or already terminal)."""
        with self._lock:
            req = self._remove_queued(request_id)
            if req is not None:
                self._close_trace(req, "evict", "migrated")
            return req

    def export_prefix_pages(self, tokens):
        """Thread-safe export of the engine's indexed prefix pages for
        ``tokens`` (:meth:`Engine.export_prefix_pages`) — the
        disaggregation controller calls this on a PREFILL replica from
        the control thread while the replica's worker may be mid-tick,
        so the read takes the scheduler lock the tick holds."""
        with self._lock:
            return self.engine.export_prefix_pages(tokens)

    def import_prefix_pages(self, payloads):
        """Thread-safe install of certified migrated pages into the
        engine's pool (:meth:`Engine.import_prefix_pages`) — the
        disaggregation controller calls this on a DECODE replica from
        the control thread; the lock serializes the pool/index/cache
        mutation against the worker's own admissions."""
        with self._lock:
            return self.engine.import_prefix_pages(payloads)

    def _remove_queued(self, request_id) -> Optional[Request]:
        """Take a request out of the queue and publish its uncharged
        wait — the ONE queue-exit bookkeeping (abort and pop_queued
        share it, so migration accounting can never diverge from abort
        accounting); the caller owns the terminal/trace handling."""
        # caller holds self._lock (abort()/pop_queued())
        for req in list(self.queue):
            if req.request_id == request_id:
                self.queue.remove(req)
                self._stall_head_removed(req)
                publish_event(
                    "serve_queue_wait",
                    seconds=max(time.perf_counter() - req.submit_t
                                - req.wait_charged, 0.0),
                    request_id=req.request_id)
                return req
        return None

    def abort(self, request_id) -> bool:
        """Mid-stream abort: evict a running request (or drop it from the
        queue). Other slots are untouched — bit-identical, by the static
        shapes of the engine. Safe to call from another thread while
        :meth:`run` is mid-tick.

        A still-queued (never-admitted) request is removed from the
        queue, accounted exactly once, and publishes the same abort
        event as an in-slot one — plus a ``serve_queue_wait`` record for
        the time it sat waiting, which was lost either way and must land
        under a goodput cause (admission publishes it for admitted
        requests; before this, an aborted queued request's wait simply
        vanished from the ledger)."""
        with self._lock:
            req = self._remove_queued(request_id)
            if req is not None:
                self._evict(req, "aborted")
                return True
            for req in self.slots:
                if req is not None and req.request_id == request_id:
                    self._evict(req, "aborted")
                    return True
            return False

    def _sweep_deadlines(self, now: float) -> None:
        """Expire every request whose ``deadline_ms`` has elapsed —
        queued-but-never-admitted requests time out too (a client that
        stopped waiting must not be prefilled). Monotonic clock deltas
        only (apexlint APX005): ``submit_t`` is a ``perf_counter``
        stamp."""
        # caller holds self._lock (step())
        for req in list(self.queue):
            if req.deadline_ms is not None and \
                    (now - req.submit_t) * 1e3 > req.deadline_ms:
                self.queue.remove(req)
                self._stall_head_removed(req)
                self._expire(req, now)
        for req in list(self.slots):
            if req is not None and req.deadline_ms is not None and \
                    (now - req.submit_t) * 1e3 > req.deadline_ms:
                self._expire(req, now)

    def _expire(self, req: Request, now: float) -> None:
        # caller holds self._lock (_sweep_deadlines())
        waited = max(now - req.submit_t, 0.0)
        req.state = "evicted"
        req.finish_reason = "deadline"
        req.done_t = now
        self.done.append(req)
        self._release(req)
        self._close_trace(req, "deadline", "deadline")
        if self.metrics is not None:
            self.metrics.on_deadline(req)
        # the whole submit-to-expiry span is lost serving time: the
        # client gave up, whatever was computed is discarded
        publish_event("serve_deadline_exceeded", level="warning",
                      request_id=req.request_id, slot=req.slot,
                      seconds=round(waited, 6),
                      deadline_ms=req.deadline_ms,
                      new_tokens=len(req.generated),
                      admitted=req.admit_t is not None)

    def _evict(self, req: Request, reason: str) -> None:
        # caller holds self._lock (abort()/run())
        req.state = "evicted"
        req.finish_reason = reason
        req.done_t = time.perf_counter()
        self.done.append(req)
        self._release(req)
        self._close_trace(req, "abort" if reason == "aborted" else "evict",
                          reason)
        if self.metrics is not None:
            self.metrics.on_evict(req, reason)
        publish_event("serve_request_evicted", level="warning",
                      request_id=req.request_id, slot=req.slot,
                      reason=reason)

    # ------------------------------------------------------------- steps
    def step(self) -> bool:
        """One scheduler tick: scripted faults -> deadline sweep ->
        backfill -> one decode step -> per-slot termination -> journal.
        Returns False when idle (no running or queued work). Holds the
        scheduler lock for the whole tick — a cross-thread submit/abort
        lands between ticks, never mid-tick."""
        with self._lock, annotate("apex.sched.step",
                                  queued=len(self.queue)):
            if self._t0 is None:
                self._t0 = time.perf_counter()
            if self.journal is not None and self.journal.snapshot is None:
                # pre-traffic baseline: a crash on the very first decode
                # step still has a consistent state to recover to
                self._journal_tick()
            if self.injector is not None:
                for rid in self.injector.serve_aborts_due(
                        self.decode_steps):
                    self.abort(rid)
                for spec in self.injector.serve_storm_due(
                        self.decode_steps):
                    # a scripted client burst: storms go through the
                    # normal submit path so admission control is what is
                    # actually under test
                    self.submit(Request(**spec))
            self._sweep_deadlines(time.perf_counter())
            if self.admission is not None:
                if self.memory is not None:
                    self.admission.note_hbm(self.memory.last)
                if self.engine.page_size < self.engine.max_len:
                    # pool occupancy is the serving-side memory-pressure
                    # signal (the allocator stats above are process-wide):
                    # a drained free list degrades admitted budgets just
                    # like a deep queue does. Not where a page is a
                    # slot's whole context: a smaller budget frees no
                    # page there, and a drained pool is only busy slots
                    self.admission.note_pool(self.engine.free_page_frac)
                flip = self.admission.on_tick(len(self.queue))
                if flip is not None:
                    publish_event(
                        "serve_degraded_mode", level="warning",
                        entered=flip, queue_depth=len(self.queue),
                        clamp=self.admission.degraded_max_new_tokens)
            self._admit()
            self.peak_resident_tokens = max(
                self.peak_resident_tokens, self.engine.resident_tokens)
            active = np.array([r is not None for r in self.slots], bool)
            if not active.any():
                # no decode step will run this tick, so the end-of-tick
                # eviction flush below is unreachable — flush HERE or
                # the engine livelocks: pages of slots freed by the
                # deadline sweep / an abort stay refcounted, the queue
                # head's page probe keeps failing, and no decode step
                # ever advances decode_steps toward max_steps
                self._flush_evictions()
                # idle ticks still move the occupancy gauges and the SLO
                # windows: a deadline storm expiring queued-only requests
                # must be able to breach (and later recover) with zero
                # decode steps run
                self._metrics_tick(None, 0)
                if self.journal is not None:
                    self._journal_tick()
                return bool(self.queue)
            t0 = time.perf_counter()
            if self.injector is not None:
                spike = self.injector.latency_spike_due(self.decode_steps)
                if spike:
                    time.sleep(spike)  # a stalled device/host hiccup
                self.injector.maybe_crash_decode(self.decode_steps)
            spec_k = self.engine.spec_draft_len
            if spec_k and self.drafter is not None:
                # speculative tick: host drafts -> ONE compiled verify
                # step for every slot (the multi-token analog of
                # decode_step — same trace under any churn)
                drafts, draft_lens = self._build_drafts(spec_k)
                committed, counts = self.engine.spec_decode_step(
                    self.engine.last_tokens, drafts, draft_lens, active)
            else:
                next_tokens, _logits = self.engine.decode_step(
                    self.engine.last_tokens, active)
            dt = time.perf_counter() - t0
            self.decode_steps += 1
            self.decode_step_s.append(dt)
            self.decode_slot_steps += int(active.sum())
            # second residency sample, AFTER the append: a completing
            # slot's final token is resident right now and gone before
            # the next tick's sample — without this the true peak is
            # systematically one token per completion low
            self.peak_resident_tokens = max(
                self.peak_resident_tokens, self.engine.resident_tokens)
            if self.tracer is not None:
                if self._sched_span is None:
                    self._sched_span = self.tracer.begin(
                        "serve", trace_id="serve:scheduler", t0=t0,
                        num_slots=self.engine.config.num_slots)
                tick = self.tracer.begin("decode_tick",
                                         parent=self._sched_span, t0=t0,
                                         step=self.decode_steps,
                                         active=int(active.sum()))
                self.tracer.end(tick, t1=t0 + dt)
            if self.memory is not None:
                self.memory.tick("serve_decode", step=self.decode_steps)
            publish_event("serve_decode_step", seconds=dt,
                          active=int(active.sum()))
            with annotate("apex.sched.accept"):
                if spec_k and self.drafter is not None:
                    self.decode_tokens += self._accept_spec(
                        committed, counts, draft_lens)
                else:
                    self.decode_tokens += int(active.sum())
                    for slot, req in enumerate(self.slots):
                        if req is not None:
                            self._accept_token(req, int(next_tokens[slot]))
                self._flush_evictions()
                # AFTER the accept loop: completions landing on this tick
                # feed the SLO windows before this tick's evaluate() — a
                # breach crossed by the final tick's events must publish
                # before run() exits, and the exit snapshot's burn gauges
                # must reflect this tick, not the previous one
                self._metrics_tick(dt, int(active.sum()))
                if self.journal is not None:
                    # end-of-tick: the state is consistent again — this
                    # is the snapshot a crash in the NEXT tick rolls back
                    # to
                    self._journal_tick()
            return any(r is not None
                       for r in self.slots) or bool(self.queue)

    def _metrics_tick(self, dt_s: Optional[float], active: int) -> None:
        """Feed the live-metrics layer one tick: the decode-step sample
        (None on idle ticks), occupancy gauges, and the SLO evaluation —
        all host-side, nothing touches the device."""
        # caller holds self._lock (step())
        if self.metrics is None:
            return
        self.metrics.on_tick(
            dt_s=dt_s, active=active, queue_depth=len(self.queue),
            resident_tokens=self.engine.resident_tokens,
            free_page_frac=self.engine.free_page_frac)

    # --------------------------------------------- journal / warm restart
    def _journal_tick(self) -> None:
        """Record the current consistent state into the journal: request
        metadata copies (a half-applied crashing tick can never poison
        them) plus the engine's sampling state and PRNG key."""
        # caller holds self._lock (step())
        self.journal.record({
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "decode_slot_steps": self.decode_slot_steps,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "engine": self.engine.sampling_state(),
            # page accounting: page tables + refcounts, for the
            # postmortem journal and the recovery integrity story —
            # recovery itself re-derives allocation by re-prefilling,
            # sharing whatever prefix pages survived
            "paging": self.engine.paging_state(),
            "slots": [None if r is None else {
                "req": r, "request_id": r.request_id,
                # the prompt is immutable for the request's lifetime —
                # a reference is crash-safe; only `generated` changes
                # between ticks and needs the per-tick copy
                "prompt": r.tokens,
                "generated": list(r.generated),
            } for r in self.slots],
            "queued": list(self.queue),
        })

    def recover(self, error: Optional[str] = None) -> int:
        """Warm restart after a fatal tick exception: roll back to the
        journal's last consistent snapshot without losing any request.

        Device state is rebuilt by re-prefilling each surviving slot's
        accepted prefix (prompt + all but the last generated token)
        through the existing bucketed prefill — bit-exact by the PR-5
        prefill/decode invariant — then restoring the journaled sampling
        state (PRNG key, last tokens), so surviving streams continue
        exactly where the snapshot left them. Compiled executables are
        reused: ``Engine.decode_traces`` does not grow (tier-1 asserts).
        Requests that reached a terminal status during the crashing tick
        keep it (their events already published — exactly-once); every
        other in-flight request resumes, and queued ones (including
        arrivals after the snapshot) are requeued in order. Returns the
        number of slots re-prefilled."""
        with self._lock:
            if self.journal is None or self.journal.snapshot is None:
                raise RuntimeError(
                    "recover() needs ServeScheduler(journal=TickJournal"
                    "(...)) — there is no snapshot to roll back to")
            snap = self.journal.snapshot
            self.restarts += 1
            # state drop; compiled artifacts kept. Engines with a
            # prefix index keep the pool bytes + index too: shared prefix
            # pages are read-only (a crash cannot have torn them), so
            # recovery re-prefills ONLY the unshared pages of each
            # surviving slot — the re-prefill below hits the index for
            # the prompt portion and pays just the generated tail
            self.engine.reset(keep_prefix_cache=True)
            snap_ids = {id(ent["req"]) for ent in snap["slots"]
                        if ent is not None}
            # requeue: journaled order first, then post-snapshot arrivals
            # that got ADMITTED during the crashing tick (popped from the
            # live queue into a slot the snapshot never saw — they must
            # roll back to queued, not vanish), then the rest of the live
            # queue — nothing is dropped, and relative submit order holds
            requeue: List[Request] = []
            seen = set()
            for req in snap["queued"]:
                seen.add(id(req))
                if req.state in TERMINAL_STATES:
                    continue
                self._rollback_to_queued(req)
                requeue.append(req)
            for req in list(self.slots):
                if req is None or id(req) in snap_ids \
                        or id(req) in seen \
                        or req.state in TERMINAL_STATES:
                    continue
                seen.add(id(req))
                self._rollback_to_queued(req)
                requeue.append(req)
            for req in list(self.queue):
                if id(req) in seen or req.state in TERMINAL_STATES:
                    continue
                self._rollback_to_queued(req)
                requeue.append(req)
            self.queue = collections.deque(requeue)
            self.slots = [None] * self.engine.config.num_slots
            self._to_evict.clear()
            # an open page-stall window is void: the rollback re-derives
            # allocation, and the requeued head's wait is charged as
            # queue time at its (re-)admission
            self._alloc_stall_t0 = None
            self._alloc_stall_req = None
            prefixes: Dict[int, List[int]] = {}
            budgets: Dict[int, int] = {}
            cacheable: Dict[int, int] = {}
            for slot, ent in enumerate(snap["slots"]):
                if ent is None:
                    continue
                req = ent["req"]
                if req.state in TERMINAL_STATES:
                    continue  # finished mid-crash-tick: status stands
                req.state = "running"
                req.slot = slot
                req.generated = list(ent["generated"])
                self.slots[slot] = req
                # the cache must hold prompt + generated[:-1]: the last
                # generated token is the NEXT decode input, not resident
                prefixes[slot] = list(ent["prompt"]) + req.generated[:-1]
                # page reservation for the REMAINING stream: the admitted
                # budget minus tokens already generated (the re-prefilled
                # tail counts as resident, not budget)
                budget = req.budget if req.budget is not None \
                    else req.max_new_tokens
                budgets[slot] = max(budget - len(req.generated) + 1, 1)
                # only the original prompt may enter the prefix index —
                # generated-token pages are one stream's state, not a
                # shareable prefix, and must not pin the index
                cacheable[slot] = len(ent["prompt"])
            if prefixes:
                # ONE prefill call, exactly like _admit: the engine pads
                # every prefix to the shared pow2 bucket itself, so a
                # mixed-length recovery pays at most one fresh bucket
                # trace, never one per length class
                self.engine.prefill(prefixes, budgets=budgets,
                                    cacheable=cacheable)
            self.engine.restore_sampling_state(snap["engine"],
                                               slots=sorted(prefixes))
            self.decode_steps = snap["decode_steps"]
            del self.decode_step_s[self.decode_steps:]
            self.decode_tokens = snap["decode_tokens"]
            # spec counters ride the same snapshot (PR-18); .get keeps
            # journals from pre-spec builds replayable
            self.decode_slot_steps = snap.get("decode_slot_steps", 0)
            self.spec_proposed = snap.get("spec_proposed", 0)
            self.spec_accepted = snap.get("spec_accepted", 0)
            publish_event("serve_engine_restart", level="warning",
                          restarts=self.restarts,
                          resumed_slots=len(prefixes),
                          requeued=len(self.queue),
                          error=error or "")
            return len(prefixes)

    def _rollback_to_queued(self, req: Request) -> None:
        """Return a (possibly mid-crash-tick admitted) request to the
        queue: progress from the torn tick is discarded — under greedy
        decoding the replay regenerates it bit-for-bit."""
        # caller holds self._lock (recover())
        req.state = "queued"
        req.slot = None
        req.generated.clear()
        req.admit_t = None
        req.first_token_t = None
        # the torn tick's admitted budget is void: re-admission grants a
        # fresh one against the CURRENT degradation state, so a clamp
        # from a past overload never outlives the overload
        req.budget = None
        sp = self._req_spans.get(req)
        if sp is not None:
            prefill = sp.pop("prefill", None)
            if prefill is not None:
                # it was admitted during the crashing tick: close the
                # torn lifecycle spans and reopen the queue wait
                self.tracer.end(prefill, status="cancelled", restart=True)
                decode = sp.pop("decode", None)
                if decode is not None:
                    self.tracer.end(decode, status="cancelled",
                                    restart=True)
                sp["queue"] = self.tracer.begin("queue", parent=sp["root"],
                                                restart=True)

    def drain_and_reject(self, reason: str = "engine_failure") -> int:
        """Terminal-status every still-live request WITHOUT touching the
        (presumed dead) engine: queued requests are rejected (retriable
        — a healthy replica can serve them), in-flight ones evicted.
        The supervisor calls this when the restart budget is exhausted;
        after it, every submitted request has exactly one terminal
        status. Returns the number drained."""
        n = 0
        with self._lock:
            self._end_alloc_stall()
            now = time.perf_counter()
            while self.queue:
                req = self.queue.popleft()
                self._reject(req, reason,
                             seconds=max(now - req.submit_t
                                         - req.wait_charged, 0.0))
                n += 1
            for slot, req in enumerate(self.slots):
                if req is None:
                    continue
                # clear the slot FIRST so _release never schedules a
                # device-side eviction on the dead engine
                self.slots[slot] = None
                self._evict(req, reason)
                n += 1
            self._to_evict.clear()
        return n

    def run(self, max_steps: Optional[int] = None) -> ServeStats:
        """Run until idle (or ``max_steps`` decode steps); returns stats.
        Unfinished requests are evicted with reason ``shutdown``. A fatal
        exception anywhere in the loop leaves a flight-recorder dump
        (when one is attached) before propagating."""
        try:
            with (self.flight.guard("serve") if self.flight is not None
                  else contextlib.nullcontext()):
                while self.step():
                    if max_steps is not None and \
                            self.decode_steps >= max_steps:
                        break
                with self._lock:
                    self._end_alloc_stall()
                    for req in list(self.queue) + [r for r in self.slots
                                                   if r is not None]:
                        if req in self.queue:
                            self.queue.remove(req)
                        self._evict(req, "shutdown")
                    self._flush_evictions()
                    # the shutdown drain's evictions observed SLO events
                    # with no tick left to evaluate them — one final
                    # tick keeps the exit snapshot's gauges and breach
                    # state current with everything above
                    self._metrics_tick(None, 0)
        finally:
            if self.tracer is not None and self._sched_span is not None:
                self.tracer.end(self._sched_span,
                                ticks=self.decode_steps)
                self._sched_span = None
        return self.stats()

    def stats(self) -> ServeStats:
        wall = (time.perf_counter() - self._t0) if self._t0 else 0.0
        records = [r.record() for r in self.done]
        return ServeStats(requests=records,
                          decode_steps=self.decode_steps,
                          decode_step_s=list(self.decode_step_s),
                          decode_tokens=self.decode_tokens,
                          total_new_tokens=sum(r["new_tokens"]
                                               for r in records),
                          wall_s=wall,
                          restarts=self.restarts,
                          admitted=self.admitted,
                          prefix_hits=self.prefix_hits,
                          peak_resident_tokens=self.peak_resident_tokens,
                          decode_slot_steps=self.decode_slot_steps,
                          spec_proposed=self.spec_proposed,
                          spec_accepted=self.spec_accepted)
