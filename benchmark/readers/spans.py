"""Benchmark-side spans -> a layer's time per call. A span is ``(name, t0,
t1, info)`` on the host's monotonic clock, taken from outside by wrapping
the bound methods of the objects the driver built. Only spans that end
inside the window count.
"""

from __future__ import annotations

from readers import stamps


def _in_window(obs, name):
    lo, hi = obs["window"]
    return [s for s in obs["spans"] if s[0] == name and lo <= s[2] < hi]


def read(spec: dict, obs: dict):
    args = spec["args"]
    if "p50_of" in args:
        durs = [(s[2] - s[1]) * 1e3 for s in _in_window(obs, args["p50_of"])]
        return stamps.percentile(durs, 0.5) if durs else None
    if "self_of" in args:
        # self time: the span's seconds minus those of the named spans
        # inside it, per span
        own = _in_window(obs, args["self_of"])
        if not own:
            return None
        inner = sum(s[2] - s[1] for name in args["minus"]
                    for s in _in_window(obs, name))
        return (sum(s[2] - s[1] for s in own) - inner) * 1e3 / len(own)
    raise ValueError(f"spans cannot read {args}")
