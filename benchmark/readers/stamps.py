"""Request stamps -> what a caller sees. All times are the host's monotonic
clock, in seconds; a token counts when the scheduler has fetched it.

A request is a dict with ``submit_t``, ``admit_t``, ``first_token_t``,
``token_t`` (one stamp per emitted token), ``done_t`` and ``failed``.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest rank: the smallest value with at least ``q`` of the sample
    at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def _inside(t, window) -> bool:
    return t is not None and window[0] <= t < window[1]


def tokens_per_s(requests, window) -> float:
    """Every token emitted inside the window, whatever request it belongs
    to, over the window's seconds."""
    n = sum(_inside(t, window) for r in requests for t in r["token_t"])
    return n / (window[1] - window[0])


def ttft_ms(requests, window, ramp_too: bool = False) -> list:
    """First token minus submission of every request that was submitted
    inside the window and whose first token fell inside it; one that was
    submitted inside it and failed inside it counts as the whole window.
    A request whose wait began in the ramp does not count: its wait holds
    the first execution of both programs, which is set-up. ``ramp_too``
    takes those in as well: the sample of the former ``ttft_p90_ms``."""
    out = []
    for r in requests:
        if not (ramp_too or _inside(r["submit_t"], window)):
            continue
        if r["failed"] and _inside(r.get("done_t"), window):
            out.append((window[1] - window[0]) * 1e3)
        elif not r["failed"] and _inside(r["first_token_t"], window):
            out.append((r["first_token_t"] - r["submit_t"]) * 1e3)
    return out


def ttft_shape(requests, window) -> dict:
    """The sample ``ttft_mean_ms`` is taken over: how many, their mean,
    and (nearest rank) median, 90th percentile and longest; beside it the
    former ``ttft_p90_ms`` with its count, over every request whose first
    token fell inside the window. With nothing to take a mean of it
    raises, naming the counts."""
    waits, former = ttft_ms(requests, window), ttft_ms(requests, window, True)
    if not waits:
        raise ValueError(
            f"no request was submitted and first served inside the window "
            f"{window}: of {len(requests)} requests, "
            f"{sum(_inside(r['submit_t'], window) for r in requests)} were "
            f"submitted inside it and {len(former)} got their first token "
            f"inside it")
    return {"count": len(waits), "mean_ms": sum(waits) / len(waits),
            "median_ms": percentile(waits, 0.5),
            "p90_ms": percentile(waits, 0.90), "longest_ms": max(waits),
            "former_count": len(former),
            "former_p90_ms": percentile(former, 0.90)}


def gaps_ms(requests, window) -> list:
    """Gaps between consecutive tokens of one request, pooled over the
    requests, each counted where its later token fell."""
    return [(b - a) * 1e3 for r in requests
            for a, b in zip(r["token_t"], r["token_t"][1:])
            if _inside(b, window)]


def end_to_end(requests, window) -> dict:
    return {"tokens_per_s": tokens_per_s(requests, window),
            "ttft_mean_ms": ttft_shape(requests, window)["mean_ms"],
            "itl_p99_ms": percentile(gaps_ms(requests, window), 0.99)}


def read(spec: dict, obs: dict):
    """``queue_wait_p50``: admission minus submission, over the requests
    submitted and admitted inside the window (the sample of
    ``ttft_mean_ms``, taken one prefill call earlier)."""
    if spec["args"]["quantity"] != "queue_wait_p50":
        raise ValueError(f"stamps cannot read {spec['args']}")
    waits = [(r["admit_t"] - r["submit_t"]) * 1e3 for r in obs["requests"]
             if _inside(r["submit_t"], obs["window"])
             and _inside(r["admit_t"], obs["window"])]
    return percentile(waits, 0.5) if waits else None
