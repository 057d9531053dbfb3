"""Per-tenant serving accounting — the live-metrics adapter between the
scheduler and :mod:`apex_tpu.monitor.export` / :mod:`apex_tpu.monitor.slo`.

One :class:`ServeMetrics` object owns the serving metric families and is
called by :class:`~apex_tpu.serve.scheduler.ServeScheduler` at exactly
the points where the matching bus events publish (``metrics=None``, the
default, keeps the scheduler at zero extra work per tick — the tracer
pattern). Every hook is host python under the scheduler's lock, off the
traced path (apexlint APX001 flags a registry mutation reachable from
traced code; tier-1 scrapes a live loop and asserts ``decode_traces ==
1``).

Requests carry an optional ``tenant`` label
(:class:`~apex_tpu.serve.scheduler.Request`); unlabeled requests land
under ``default``. Cardinality is bounded at ``max_tenants`` — overflow
tenants fold into the registry's ``__other__`` series, so a tenant-id
explosion cannot grow a scrape.

The family catalog (all ``serve_*``; seconds-valued histograms):

========================================  =========  ==================
name                                      type       labels
========================================  =========  ==================
serve_requests_submitted_total            counter    tenant
serve_requests_admitted_total             counter    tenant
serve_requests_completed_total            counter    tenant
serve_requests_rejected_total             counter    tenant
serve_requests_evicted_total              counter    tenant
serve_deadline_exceeded_total             counter    tenant
serve_prefix_hits_total                   counter    tenant
serve_generated_tokens_total              counter    tenant
serve_spec_accept_rate                    histogram  tenant
serve_tokens_per_decode_step              gauge      tenant (merge: max)
serve_ttft_seconds                        histogram  tenant
serve_latency_seconds                     histogram  tenant
serve_queue_wait_seconds                  histogram  tenant
serve_decode_step_seconds                 histogram  —
serve_queue_depth                         gauge      — (merge: sum)
serve_active_slots                        gauge      — (merge: sum)
serve_resident_tokens                     gauge      — (merge: sum)
serve_free_page_frac                      gauge      — (merge: min)
serve_slo_burn_short / _long / _breached  gauge      objective (max)
========================================  =========  ==================

Tier-1 holds the per-tenant counters against the scheduler's exact
end-of-run ``summary()`` (the sums must agree) and the TTFT/latency
histogram quantiles against the exact sorted-list percentiles within the
documented bucket error. See docs/observability.md "Live metrics, SLOs,
and fleet aggregation".
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

from apex_tpu.monitor.export import MetricsRegistry

DEFAULT_TENANT = "default"


class ServeMetrics:
    """Record serving lifecycle + latency into a
    :class:`~apex_tpu.monitor.export.MetricsRegistry`, optionally feeding
    an :class:`~apex_tpu.monitor.slo.SLOTracker` whose burn rates are
    mirrored into gauges each tick."""

    def __init__(self, registry: Optional[MetricsRegistry] = None, *,
                 slo=None, max_tenants: int = 32):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.slo = slo
        r = self.registry
        t = ("tenant",)
        n = int(max_tenants)
        self.submitted = r.counter(
            "serve_requests_submitted_total",
            "requests entering the admission backlog", t, n)
        self.admitted = r.counter(
            "serve_requests_admitted_total",
            "requests that reached a cache slot", t, n)
        self.completed = r.counter(
            "serve_requests_completed_total",
            "requests finishing with eos/length/context", t, n)
        self.rejected = r.counter(
            "serve_requests_rejected_total",
            "requests shed by admission control (retriable)", t, n)
        self.evicted = r.counter(
            "serve_requests_evicted_total",
            "mid-stream evictions (abort/shutdown/engine_failure)", t, n)
        self.deadline = r.counter(
            "serve_deadline_exceeded_total",
            "requests expiring on their deadline_ms budget", t, n)
        self.prefix_hits = r.counter(
            "serve_prefix_hits_total",
            "admissions served partly from resident prefix pages", t, n)
        self.generated = r.counter(
            "serve_generated_tokens_total",
            "tokens generated for terminal requests", t, n)
        # speculative decoding (PR-18): accept rate is a per-(slot, step)
        # sample in [0, 1] — quantiles answer "how often do drafts land
        # for THIS tenant", which a run-total ratio hides; the gauge is
        # the live tokens-per-step multiplier the autoscaler reads
        self.spec_accept = r.histogram(
            "serve_spec_accept_rate",
            "per-step draft acceptance fraction (speculative decode)",
            t, n)
        self.tokens_per_step = r.gauge(
            "serve_tokens_per_decode_step",
            "tokens committed per decode step, last tick", t, n,
            agg="max")
        self.ttft = r.histogram(
            "serve_ttft_seconds", "submit to first token", t, n)
        self.latency = r.histogram(
            "serve_latency_seconds", "submit to terminal status", t, n)
        self.queue_wait = r.histogram(
            "serve_queue_wait_seconds", "time queued before a slot", t, n)
        self.decode_step = r.histogram(
            "serve_decode_step_seconds", "one batched decode step")
        self.queue_depth = r.gauge(
            "serve_queue_depth", "requests waiting for admission")
        self.active_slots = r.gauge(
            "serve_active_slots", "slots decoding this tick")
        self.resident_tokens = r.gauge(
            "serve_resident_tokens", "KV tokens resident across slots")
        self.free_page_frac = r.gauge(
            "serve_free_page_frac",
            "KV pool free-page fraction", agg="min")
        obj = ("objective",)
        self.slo_burn_short = r.gauge(
            "serve_slo_burn_short",
            "short-window error-budget burn rate", obj, agg="max")
        self.slo_burn_long = r.gauge(
            "serve_slo_burn_long",
            "long-window error-budget burn rate", obj, agg="max")
        self.slo_breached = r.gauge(
            "serve_slo_breached", "1 while the objective is breached",
            obj, agg="max")

    # ---- per-request lifecycle (caller: scheduler, under its lock) -----
    @staticmethod
    def _tenant(req) -> str:
        tenant = getattr(req, "tenant", None)
        return str(tenant) if tenant else DEFAULT_TENANT

    def on_submit(self, req) -> None:
        self.submitted.inc(tenant=self._tenant(req))

    def on_admit(self, req, wait_s: float) -> None:
        tenant = self._tenant(req)
        self.admitted.inc(tenant=tenant)
        self.queue_wait.record(wait_s, tenant=tenant)

    def on_prefix_hit(self, req, hit_tokens: int) -> None:
        self.prefix_hits.inc(tenant=self._tenant(req))

    def on_spec(self, req, *, proposed: int, accepted: int) -> None:
        """One slot's draft outcome for one verify step (speculative
        decode only; ticks where the scheduler clamped the draft to zero
        contribute no sample — there was nothing to accept)."""
        if proposed > 0:
            self.spec_accept.record(accepted / proposed,
                                    tenant=self._tenant(req))

    def on_spec_step(self, tenant_tokens: Mapping[Any, int]) -> None:
        """Tokens committed per tenant in one verify step — sets the live
        ``serve_tokens_per_decode_step`` gauge (1.0 is the one-token
        floor; > 1 is speculation paying off)."""
        for tenant, tokens in tenant_tokens.items():
            self.tokens_per_step.set(
                float(tokens),
                tenant=str(tenant) if tenant else DEFAULT_TENANT)

    def on_complete(self, req) -> None:
        tenant = self._tenant(req)
        self.completed.inc(tenant=tenant)
        self.generated.inc(len(req.generated), tenant=tenant)
        if req.ttft_s is not None:
            self.ttft.record(req.ttft_s, tenant=tenant)
        if req.latency_s is not None:
            self.latency.record(req.latency_s, tenant=tenant)
        if self.slo is not None:
            if req.ttft_s is not None:
                self.slo.observe("ttft", value=req.ttft_s)
            self.slo.observe("deadline", bad=False)
            self.slo.observe("shed", bad=False)

    def on_reject(self, req, reason: str) -> None:
        tenant = self._tenant(req)
        self.rejected.inc(tenant=tenant)
        if req.latency_s is not None:
            self.latency.record(req.latency_s, tenant=tenant)
        if self.slo is not None:
            self.slo.observe("shed", bad=True)
            # EVERY terminal status feeds every fraction window exactly
            # once, or the live denominators diverge from the documented
            # objectives (deadline_miss_frac is over TERMINAL requests;
            # check_regression derives it over submitted): a rejected
            # request is terminal and did not miss a deadline
            self.slo.observe("deadline", bad=False)

    def on_deadline(self, req) -> None:
        tenant = self._tenant(req)
        self.deadline.inc(tenant=tenant)
        self.generated.inc(len(req.generated), tenant=tenant)
        # a request that reached its first token and THEN expired still
        # witnessed a TTFT — the exact summary counts it, and under
        # deadline pressure the worst TTFTs are exactly the requests
        # that die by deadline: dropping them would make the histogram
        # (and the ttft SLO) read systematically better than the oracle
        if req.ttft_s is not None:
            self.ttft.record(req.ttft_s, tenant=tenant)
        if req.latency_s is not None:
            self.latency.record(req.latency_s, tenant=tenant)
        if self.slo is not None:
            if req.ttft_s is not None:
                self.slo.observe("ttft", value=req.ttft_s)
            self.slo.observe("deadline", bad=True)
            self.slo.observe("shed", bad=False)

    def on_evict(self, req, reason: str) -> None:
        tenant = self._tenant(req)
        self.evicted.inc(tenant=tenant)
        self.generated.inc(len(req.generated), tenant=tenant)
        # same survivorship rule as on_deadline: an evicted request that
        # got a first token is a TTFT witness the summary also counts
        if req.ttft_s is not None:
            self.ttft.record(req.ttft_s, tenant=tenant)
        if req.latency_s is not None:
            self.latency.record(req.latency_s, tenant=tenant)
        if self.slo is not None:
            if req.ttft_s is not None:
                self.slo.observe("ttft", value=req.ttft_s)
            # eviction is terminal: one good event in each fraction
            # window keeps the live denominators == terminal requests
            # (see on_reject) — an evicted request was neither shed by
            # admission nor expired on its deadline
            self.slo.observe("deadline", bad=False)
            self.slo.observe("shed", bad=False)

    # ---- per-tick ------------------------------------------------------
    def on_tick(self, *, dt_s: Optional[float], active: int,
                queue_depth: int, resident_tokens: int,
                free_page_frac: float) -> None:
        """End of one scheduler tick (``dt_s=None`` on idle ticks: no
        decode step ran, but occupancy gauges and the SLO windows must
        still move — a deadline storm can breach with zero decode
        steps)."""
        if dt_s is not None:
            self.decode_step.record(dt_s)
        self.queue_depth.set(queue_depth)
        self.active_slots.set(active)
        self.resident_tokens.set(resident_tokens)
        self.free_page_frac.set(free_page_frac)
        if self.slo is not None:
            self.slo.evaluate()
            for name, state in self.slo.summary().items():
                self.slo_burn_short.set(state["burn_short"],
                                        objective=name)
                self.slo_burn_long.set(state["burn_long"], objective=name)
                self.slo_breached.set(float(state["breached"]),
                                      objective=name)

    def summary(self) -> Dict[str, Any]:
        """A compact live view (the CLI's final summary carries it when
        metrics are armed): per-family totals plus the SLO state."""
        totals: Dict[str, float] = {}
        for fam in self.registry.families():
            if fam.kind == "counter":
                totals[fam.name] = sum(s.value for s in fam.series())
        out: Dict[str, Any] = {"totals": totals}
        if self.slo is not None:
            out["slo"] = self.slo.summary()
        return out
