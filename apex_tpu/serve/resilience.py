"""Production failure semantics for the serving stack — admission
control, load shedding, graceful degradation, and crash-recovering warm
restart.

The PR-5 scheduler is fair-weather: an unbounded FIFO queue, no deadline
anywhere, and a fatal exception in the jitted step kills every in-flight
request (the flight recorder dumps a postmortem and the process dies).
This module composes the pieces PRs 1-7 already landed into the four
contracts a production front line needs — all of them mesh-shape-agnostic
(nothing here knows the engine's device layout, so the coming
tensor-parallel engine inherits every one for free):

- **Admission control & load shedding** — :class:`AdmissionController`
  bounds the scheduler's backlog (``max_queue``) and picks who pays when
  it overflows: ``reject-newest`` (classic tail drop), ``shed-oldest``
  (drop the request that has already waited longest — its deadline is the
  most doomed), or ``priority`` (shed the lowest-priority queued request
  strictly below the newcomer). Every shed/reject is a *terminal*,
  accounted, retriable status (``serve_request_rejected`` on the bus) —
  never a hang.
- **Graceful degradation** — under *sustained* overload (queue depth at
  the high watermark, or HBM allocator pressure from the PR-6
  ``hbm_snapshot`` sampling, for ``sustain_ticks`` consecutive ticks) the
  controller clamps admitted requests' ``max_new_tokens`` so the server
  sheds work before it sheds requests; ``serve_degraded_mode`` records
  each transition.
- **Warm restart** — :class:`TickJournal` keeps the last consistent
  end-of-tick snapshot of all scheduler request metadata (prompt ids,
  generated tokens, per-slot progress, the engine's sampling state and
  PRNG key path). ``ServeScheduler.recover()`` rebuilds device state by
  re-prefilling each surviving slot's accepted prefix through the
  existing bucketed prefill — bit-exact by the PR-5 prefill/decode
  invariant — and restores the journaled PRNG key, so surviving streams
  continue exactly where they left off. ``Engine.decode_traces`` must not
  grow across a recovery (tier-1 asserts).
- **Supervision** — :class:`ServeSupervisor` wraps ``scheduler.run()``
  with bounded retry + exponential backoff; when the budget is exhausted
  it drains every in-flight/queued request to a terminal rejected/evicted
  status (without touching the dead engine) and re-raises. Under any
  seeded :class:`~apex_tpu.resilience.fault_injection.FaultInjector`
  schedule, every submitted request reaches exactly one terminal status —
  the chaos invariant tier-1 proves.

Deadlines themselves live on :class:`~apex_tpu.serve.scheduler.Request`
(``deadline_ms``) and are swept by the scheduler every tick with
monotonic clocks (apexlint APX005); the journal's on-disk form commits
via ``.tmp`` + ``os.replace`` (APX004). See docs/serving.md "Overload
and failure semantics".
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict, List, Optional, Tuple

# shed policies: who pays when the admission queue is full
REJECT_NEWEST = "reject-newest"
SHED_OLDEST = "shed-oldest"
PRIORITY = "priority"
SHED_POLICIES = (REJECT_NEWEST, SHED_OLDEST, PRIORITY)

JOURNAL_SCHEMA_VERSION = 1


class AdmissionController:
    """Bounded-queue admission, shed policy, and degraded-mode tracking.

    Pure policy: every method is called by the scheduler under its own
    lock (submit-time decisions from :meth:`on_submit`, per-tick
    bookkeeping from :meth:`on_tick`), so the controller holds no lock
    and no thread ever races it. ``max_queue`` bounds the *backlog* (the
    admission queue the scheduler drains into free slots); a workload
    that submits its whole request list before ``run()`` should size it
    at least as large as the burst it wants queued.

    Degradation fires only when ``degraded_max_new_tokens`` is set: once
    the overload signal — ``queue_depth >= queue_high`` (default
    ``ceil(queue_high_frac * max_queue)``), HBM allocator usage at
    ``hbm_frac_high`` of the device limit (fed from the PR-6
    ``hbm_snapshot`` sampling via :meth:`note_hbm`), or the KV
    pool's free-page fraction at or below ``pool_frac_low`` (fed from
    the scheduler via :meth:`note_pool`) — holds for ``sustain_ticks``
    consecutive ticks, newly admitted requests have ``max_new_tokens``
    clamped until the signal clears for the same number of ticks. A
    one-tick spike never flips the mode. Clamping admitted budgets is
    doubly effective where pages are smaller than ``max_len``: the
    budget sizes the page reservation, so degradation directly relieves
    the pool pressure that triggered it.
    """

    def __init__(self, max_queue: Optional[int] = None,
                 shed_policy: str = REJECT_NEWEST, *,
                 degraded_max_new_tokens: Optional[int] = None,
                 queue_high: Optional[int] = None,
                 queue_high_frac: float = 0.75,
                 sustain_ticks: int = 4,
                 hbm_frac_high: float = 0.92,
                 pool_frac_low: float = 0.05):
        if shed_policy not in SHED_POLICIES:
            raise ValueError(
                f"shed_policy {shed_policy!r} not in {SHED_POLICIES}")
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if degraded_max_new_tokens is not None \
                and degraded_max_new_tokens < 1:
            raise ValueError("degraded_max_new_tokens must be >= 1")
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.degraded_max_new_tokens = degraded_max_new_tokens
        if queue_high is None and max_queue is not None:
            queue_high = max(1, math.ceil(queue_high_frac * max_queue))
        self.queue_high = queue_high
        self.sustain_ticks = max(1, int(sustain_ticks))
        self.hbm_frac_high = float(hbm_frac_high)
        self.pool_frac_low = float(pool_frac_low)
        self.degraded = False
        self._hot_ticks = 0
        self._cool_ticks = 0
        self._hbm_frac: Optional[float] = None
        self._pool_free_frac: Optional[float] = None

    # ---- submit-time decisions -----------------------------------------
    def on_submit(self, queue, req) -> Tuple[str, Optional[Any]]:
        """Admission verdict for ``req`` against the current backlog:
        ``("admit", None)``, ``("admit", victim)`` (shed ``victim`` from
        the queue to make room), or ``("reject", None)``."""
        if self.max_queue is None or len(queue) < self.max_queue:
            return ("admit", None)
        if self.shed_policy == SHED_OLDEST:
            return ("admit", queue[0])
        if self.shed_policy == PRIORITY:
            # oldest of the lowest-priority queued requests (min() keeps
            # the first minimal element; deque order is submit order)
            victim = min(queue, key=lambda r: r.priority)
            if victim.priority < req.priority:
                return ("admit", victim)
        return ("reject", None)

    # ---- degraded mode --------------------------------------------------
    def note_hbm(self, stats: Optional[Dict[str, int]]) -> None:
        """Feed the latest sampled ``hbm_snapshot`` allocator stats (the
        scheduler forwards its MemoryAccountant's last sample)."""
        if not stats:
            return
        limit = stats.get("bytes_limit")
        if limit:
            self._hbm_frac = stats.get("bytes_in_use", 0) / float(limit)

    def note_pool(self, free_frac: Optional[float]) -> None:
        """Feed the KV pool's free-page fraction (the scheduler
        forwards ``Engine.free_page_frac`` per tick where a page is
        smaller than a slot's context) —
        the low-watermark overload signal for KV capacity."""
        if free_frac is not None:
            self._pool_free_frac = float(free_frac)

    def overloaded(self, queue_depth: int) -> bool:
        if self.queue_high is not None and queue_depth >= self.queue_high:
            return True
        if (self._pool_free_frac is not None
                and self._pool_free_frac <= self.pool_frac_low):
            return True
        return (self._hbm_frac is not None
                and self._hbm_frac >= self.hbm_frac_high)

    def on_tick(self, queue_depth: int) -> Optional[bool]:
        """Per-tick degraded-mode bookkeeping. Returns ``True`` on the
        tick the mode is entered, ``False`` on the tick it clears, and
        ``None`` when nothing changed (the common case)."""
        if self.degraded_max_new_tokens is None:
            return None
        if self.overloaded(queue_depth):
            self._hot_ticks += 1
            self._cool_ticks = 0
        else:
            self._cool_ticks += 1
            self._hot_ticks = 0
        if not self.degraded and self._hot_ticks >= self.sustain_ticks:
            self.degraded = True
            return True
        if self.degraded and self._cool_ticks >= self.sustain_ticks:
            self.degraded = False
            return False
        return None

    def clamp(self, max_new_tokens: int) -> int:
        """The admitted token budget under the current mode."""
        if self.degraded and self.degraded_max_new_tokens is not None:
            return min(max_new_tokens, self.degraded_max_new_tokens)
        return max_new_tokens


class TickJournal:
    """The last consistent end-of-tick serving snapshot, host-side.

    The scheduler records a snapshot at the top of the first tick (the
    pre-traffic baseline a crash on the very first decode step recovers
    to) and at the end of every successful tick thereafter: per-slot
    request metadata (prompt ids, generated tokens — *copies*, so a
    half-applied crashing tick can never poison recovery), the queued
    request list, and the engine's sampling state (host lengths, last
    tokens, and the PRNG key — the key path that makes a sampled stream
    replay bit-for-bit). Only the latest snapshot is kept: recovery is a
    rollback to the last consistent tick, not a history replay.

    ``path=`` additionally persists a serializable view every ``every``
    ticks for postmortem analysis (atomic ``.tmp`` + ``os.replace``, the
    repo-wide APX004 durability contract). Warm restart reads the
    in-memory snapshot — it survives the exception, not the process; a
    cross-process cold restart from the on-disk journal is ROADMAP work.
    """

    def __init__(self, path: Optional[str] = None, *, every: int = 1):
        self.path = path
        self.every = max(1, int(every))
        self.snapshot: Optional[Dict[str, Any]] = None
        self.ticks_recorded = 0

    def record(self, snap: Dict[str, Any]) -> None:
        """Install a new consistent snapshot (built by the scheduler,
        under its lock); persist on the configured cadence."""
        self.snapshot = snap
        self.ticks_recorded += 1
        if self.path is not None and self.ticks_recorded % self.every == 0:
            self.save()

    def to_payload(self) -> Dict[str, Any]:
        """The serializable (object-ref-free) view of the snapshot."""
        snap = self.snapshot
        if snap is None:
            return {"schema": JOURNAL_SCHEMA_VERSION, "empty": True}
        slots: List[Optional[Dict[str, Any]]] = []
        for ent in snap["slots"]:
            if ent is None:
                slots.append(None)
            else:
                slots.append({"request_id": str(ent["request_id"]),
                              "prompt": list(ent["prompt"]),
                              "generated": list(ent["generated"])})
        out = {
            "schema": JOURNAL_SCHEMA_VERSION,
            "decode_steps": snap["decode_steps"],
            "decode_tokens": snap["decode_tokens"],
            "engine": snap["engine"],
            "slots": slots,
            "queued": [{"request_id": str(r.request_id),
                        "prompt_tokens": len(r.tokens)}
                       for r in snap["queued"]],
        }
        # page tables + pool refcounts + prefix-index size
        # (docs/serving.md "Paged KV pool" — the postmortem answer to
        # "where did the HBM go")
        out["paging"] = snap["paging"]
        return out

    def save(self, path: Optional[str] = None) -> str:
        """Persist the journal atomically: stage to ``.tmp``, publish
        with one ``os.replace`` — a crash mid-save leaves the previous
        complete journal, never a torn one (apexlint APX004)."""
        path = path or self.path
        if path is None:
            raise ValueError("TickJournal has no path to save to")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_payload(), f, sort_keys=True, default=str)
        os.replace(tmp, path)
        return path


class ServeSupervisor:
    """Bounded-retry warm-restart loop around ``scheduler.run()``.

    A fatal exception anywhere in a tick (the jitted decode step, the
    prefill, scheduler host code) no longer loses the fleet: the
    supervisor backs off, calls :meth:`ServeScheduler.recover` (rollback
    to the journal's last consistent tick; compiled executables are
    reused — zero decode retraces), and resumes. After ``max_restarts``
    failed recoveries it stops pretending: every still-live request is
    drained to a terminal rejected/evicted status — the engine is never
    touched again — and the last exception propagates (with a flight
    recorder attached, its postmortem dump already landed).
    """

    def __init__(self, scheduler, *, max_restarts: int = 2,
                 backoff_s: float = 0.05, backoff_factor: float = 2.0,
                 max_backoff_s: float = 2.0, sleep=time.sleep):
        if scheduler.journal is None:
            raise ValueError(
                "ServeSupervisor needs ServeScheduler(journal=TickJournal"
                "(...)): recovery replays the journal's last snapshot")
        self.scheduler = scheduler
        self.max_restarts = max(0, int(max_restarts))
        self.backoff_s = float(backoff_s)
        self.backoff_factor = float(backoff_factor)
        self.max_backoff_s = float(max_backoff_s)
        self.sleep = sleep

    def run(self, max_steps: Optional[int] = None):
        """Run to completion across at most ``max_restarts`` warm
        restarts; returns the scheduler's :class:`ServeStats`."""
        restarts = 0
        while True:
            try:
                return self.scheduler.run(max_steps=max_steps)
            except Exception as e:
                if restarts >= self.max_restarts:
                    self.scheduler.drain_and_reject("engine_failure")
                    raise
                restarts += 1
                self.sleep(min(
                    self.backoff_s * self.backoff_factor ** (restarts - 1),
                    self.max_backoff_s))
                try:
                    self.scheduler.recover(
                        error=f"{type(e).__name__}: {e}")
                except Exception:
                    # recovery itself failed (the likeliest way: the
                    # re-prefill hit the same dead runtime). The
                    # exactly-once contract still stands: drain every
                    # live request to a terminal status — engine
                    # untouched — before propagating.
                    self.scheduler.drain_and_reject("engine_failure")
                    raise
