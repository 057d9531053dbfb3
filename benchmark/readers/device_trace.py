"""The profiler's ``.xplane.pb`` -> device time. Nothing in the program is
asked: the planes named ``/device:TPU:<n>`` are the chips, their line ``XLA
Ops`` holds one event per operation run and ``XLA Modules`` one per
compiled program run; the host's lines hold the ``bench.*`` annotations the
driver's wrappers open. The traced slice is the end of the window; it
starts and ends between scheduler steps, which each end in a blocking
fetch, so a call is never cut. The chip's trace buffer is finite (about 2 M
events, some 3.5 s of GPT-2 XL's programs): a trace that fills stops early.
So the traced window is what the device's events cover, first start to last
end, and a program's calls are the first as many of the slice's spans as the
trace holds whole runs of it.

    python3 benchmark/readers/device_trace.py <dir>   # what a trace holds
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys

OPS_LINE, MODULES_LINE, DEVICE_PLANE = "XLA Ops", "XLA Modules", "/device:TPU:"
SHORT_GAP_S = 10e-6


def union_seconds(intervals) -> float:
    """Seconds covered by ``(start, end)`` intervals, overlaps once."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total, reach = total + (end - start), end
        elif end > reach:
            total, reach = total + (end - reach), end
    return total


def gaps(intervals):
    """The ``(start, end)`` stretches between the first start and the last
    end that no interval covers."""
    out, reach = [], None
    for start, end in sorted(intervals):
        if reach is not None and start > reach:
            out.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return out


def self_seconds(events) -> dict:
    """``{name: seconds}`` with each event's time less that of the events
    nested in it (a ``while`` holds its body's operations)."""
    out: dict = collections.defaultdict(float)
    stack: list = []             # [name, start, end, seconds of children]

    def close():
        name, start, end, inner = stack.pop()
        out[name] += (end - start) - inner
        if stack:
            stack[-1][3] += end - start

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][2]:
            close()
        stack.append([name, start, end, 0.0])
    while stack:
        close()
    return dict(out)


def kind(name: str) -> str:
    """An operation's kind: on the chip an event is named by its whole HLO
    instruction (``%copy.117 = bf16[48,17,64,25,64]{...} copy(...)``); what
    is kept is the result's name without its number and its first shape
    (``copy bf16[48,17,64,25,64]``), so that the layers' copies of one
    operation add up."""
    head, _, rest = name.partition(" = ")
    head = re.sub(r"[.\d]+$", "", head.lstrip("%")) or head
    shape = re.match(r"\(?([a-z]+\d*\[[\d,]*\])", rest)
    return f"{head} {shape.group(1)}" if shape else head


def program(name: str) -> str:
    """``jit__decode_fn(9015977658400354697)`` -> ``decode_fn``."""
    return re.sub(r"^jit_+|\(\d+\)$", "", name)


def by_program(ops, modules):
    """``(program: kind, start, end)`` for each operation: the program is
    the compiled module whose run the operation starts in."""
    runs = sorted((s, e, program(n)) for n, s, e in modules)
    starts = [r[0] for r in runs]
    out = []
    for name, start, end in ops:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < runs[i][1]
        out.append(((runs[i][2] + ": " if inside else "") + kind(name),
                    start, end))
    return out


def parse(trace_dir: str) -> dict:
    """``{"chips": [{"ops": [...], "modules": [...]}], "host": [...]}``,
    events as ``(name, start_s, end_s)``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return {"chips": [], "host": []}
    data = ProfileData.from_file(files[-1])

    def events(line):
        return [(e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
                for e in line.events]

    chips, host = [], []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PLANE) and OPS_LINE in lines:
            chips.append({"ops": events(lines[OPS_LINE]),
                          "modules": events(lines[MODULES_LINE])
                          if MODULES_LINE in lines else []})
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                host += [e for e in events(line) if e[0].startswith("bench.")]
    return {"chips": [c for c in chips if c["ops"]], "host": host}


def _trace(obs: dict) -> dict:
    if "_trace" not in obs:
        obs["_trace"] = (parse(obs["trace_dir"]) if obs.get("trace_dir")
                         else {"chips": [], "host": []})
    return obs["_trace"]


def busy(obs: dict):
    """``(busy_s, window_s)``: seconds in which an operation ran, averaged
    over the chips, and the length of the traced window; None untraced."""
    chips = _trace(obs)["chips"]
    if not chips or not obs.get("slice"):
        return None
    each = [union_seconds((s, e) for _, s, e in c["ops"]) for c in chips]
    covered = (max(e for c in chips for _, _, e in c["ops"])
               - min(s for c in chips for _, s, _ in c["ops"]))
    return sum(each) / len(each), covered


def breakdown(obs: dict):
    """The ten kinds of operation, by program, with most time of their own
    (a kind is a name and a result shape: see ``kind``), and the idle
    stretches summed by the innermost benchmark span open on the host."""
    chips = _trace(obs)["chips"]
    if not chips:
        return None
    ops = chips[0]["ops"]
    top = sorted(self_seconds(by_program(ops, chips[0]["modules"])).items(),
                 key=lambda kv: -kv[1])[:10]
    idle: dict = collections.defaultdict(float)
    for start, end in gaps((s, e) for _, s, e in ops):
        if end - start < SHORT_GAP_S:     # the chip between two operations
            idle["between_ops_under_10us"] += end - start
            continue
        mid = (start + end) / 2
        open_ = [h for h in _trace(obs)["host"] if h[1] <= mid < h[2]]
        name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ \
            else "outside_spans"
        idle[name] += end - start
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}


def _runs(obs, program: dict):
    """Seconds of each whole run of a program that the trace holds."""
    return [e - s for c in _trace(obs)["chips"][:1]
            for n, s, e in c["modules"] if program["module"] in n]


def _work(obs, program: dict):
    """``[(flops, bytes)]`` of the program's calls that the trace holds:
    the slice's spans in order, as many as there are whole runs.
    ``program`` names the span, the module and the count in ``work.py``."""
    from readers import work

    lo, hi = obs["slice"]
    calls = [s[3] for s in obs["spans"]
             if s[0] == program["span"] and lo <= s[1] and s[2] <= hi]
    count = getattr(work, program["work"])
    return [count(obs["config"], **c)
            for c in calls[:len(_runs(obs, program))]]


def read(spec: dict, obs: dict):
    from readers import work

    args = spec["args"]
    measured = busy(obs)
    if measured is None:
        return None
    if args["quantity"] == "idle_share":
        return 100.0 * (1.0 - measured[0] / measured[1])
    peaks = obs["peaks"]
    if args["quantity"] == "mfu":
        flops = sum(f for program in args["programs"]
                    for f, _ in _work(obs, program))
        return 100.0 * flops / (measured[1] * peaks["bf16_flops_per_s"])
    if args["quantity"] == "roofline":
        calls = _work(obs, args)
        took = sum(_runs(obs, args)[:len(calls)])
        if not calls or not took:
            return None
        least = sum(work.least_seconds(f, b, peaks) for f, b in calls)
        return 100.0 * least / took
    raise ValueError(f"device_trace cannot read {args}")


if __name__ == "__main__":
    from jax.profiler import ProfileData

    for path in sorted(glob.glob(os.path.join(sys.argv[1], "**",
                                              "*.xplane.pb"), recursive=True)):
        print(path, os.path.getsize(path))
        for plane in ProfileData.from_file(path).planes:
            print(" plane", plane.name)
            for line in plane.lines:
                evs = list(line.events)
                names = collections.Counter(e.name for e in evs)
                print("   line", repr(line.name), len(evs),
                      names.most_common(6))
