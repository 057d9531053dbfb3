"""Device time of one program's run, split by the ``jax.named_scope`` its
operations were traced under. The model step names its phases (``ln_qkv``,
``attention`` with ``kv_write`` and ``attn_proj`` inside it, ``mlp``, and
``sampling`` or ``verify`` around the final norm, the logits product and
the sampler); each operation's own time (nested ones taken out) goes to
the innermost of those names on its path:

- ``dense``: ``ln_qkv``, ``attn_proj``, ``mlp`` and the logits product
  (the ``dot_general`` under ``sampling`` / ``verify``): the weights;
- ``attention``: ``attention`` less ``kv_write`` and ``attn_proj``;
- ``kv_write``: the cache append;
- ``other``: the rest of the run's device time (the sampler, the cache's
  copies, parameters, the ``while`` of a scan, the chip between two
  operations), so that the four sum to the run.

Per whole run of the program in the traced slice. A program compiled
before a scope existed, or served from a compile cache that holds it from
then (the cache's key leaves scope names out), has no operation under that
name: the metric is left out, never 0.
"""

from __future__ import annotations

import bisect

from readers import device_trace, program_spans

PARTS = {"kv_write": "kv_write", "attn_proj": "dense", "ln_qkv": "dense",
         "mlp": "dense", "attention": "attention"}
FINAL = ("sampling", "verify")         # dense only for the logits product


def part_of(path) -> str:
    """The part an operation's time goes to, from its scope path
    (``jit(_decode_fn)/attention/kv_write/scatter``): the innermost known
    name wins; no path or no known name is ``other``."""
    segments = (path or "").split("/")
    for segment in reversed(segments[:-1]):
        if segment in PARTS:
            return PARTS[segment]
        if segment in FINAL:
            return "dense" if segments[-1] == "dot_general" else "other"
    return "other"


def split(obs: dict, module: str):
    """``({part: seconds over the whole runs}, number of runs)``."""
    tr = program_spans.trace(obs)
    runs = program_spans.whole_runs(tr, module)
    if not runs or not tr["scopes"]:
        return None
    starts = [r[0] for r in runs]
    part = {key: part_of(path) for key, path in tr["scopes"].items()}
    events = []
    for name, start, end in tr["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < runs[i][1]:
            events.append((part.get((runs[i][2], name), "other"),
                           start, end))
    parts = device_trace.self_seconds(events)
    named = sum(v for k, v in parts.items() if k != "other")
    parts["other"] = sum(e - s for s, e, _ in runs) - named
    return parts, len(runs)


def read(spec: dict, obs: dict):
    args = spec["args"]
    got = obs.setdefault("_scope_split", {})
    if args["module"] not in got:
        got[args["module"]] = split(obs, args["module"])
    if got[args["module"]] is None:
        return None
    parts, runs = got[args["module"]]
    if args["part"] not in parts:
        return None
    return parts[args["part"]] * 1e3 / runs
