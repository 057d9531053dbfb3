"""The program's own ``apex.*`` ranges -> where the host's time goes around
each engine call, and what each call carried. ``apex_tpu`` opens them
through ``utils/prof.annotate`` inside ``ServeScheduler.step``,
``Engine.decode_step`` and ``Engine.prefill``; under the traced run's
profiler session they land on the host planes of the same ``.xplane.pb``
as the device's operations, with their attributes as the event's stats.
Nothing here reads the benchmark's own ``bench.*`` wrappers.

A device run is matched to the span that launched it by order: the n-th
whole run of ``decode_fn`` to the n-th ``apex.decode_step``. The slice is
what the device's events cover, so a span counts only if its run is whole
(a trace that filled cuts the last run and holds none of the later ones),
a scheduler tick only if every engine span inside it counts, and a run
with no accelerator plane counts nothing. A parent without these spans
has nothing to read either: every metric is then left out, never 0.

Host and device stand on one timeline in the file, but the chip's clock is
mapped onto the host's only to within a millisecond, which is the size of
the lags: ``clock_shift`` finds the map's error from the runtime's own
enqueue events and every comparison of a span with a run allows for it.

The trace is read once for both this reader and ``device_scopes``
(``trace(obs)``, kept on ``obs``): the host's events and the program runs
through ``jax.profiler.ProfileData``, the device's operations as
``device_trace`` already parsed them, and, for ``device_scopes``, the
scope each instruction was traced under, which ``ProfileData`` does not
show, from the file's own bytes.
"""

from __future__ import annotations

import glob
import os

from readers import device_trace

PREFIX = "apex."
ENQUEUE = "DoEnqueueProgram"      # the TPU runtime's own event, with run_id
SCOPE_STAT, PROGRAM_STAT = "tf_op", "program_id"    # on an op's metadata
_POINTS = ("span_start", "launch_end", "fetch_end", "run_start", "run_end")


# ------------------------------------------------------- the file's bytes

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf, start: int, end: int):
    """``(field number, value)`` of one protobuf message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` for a
    length-delimited field, the bytes of a fixed one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (i, i + size), i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(buf) -> dict:
    """``{(program id, instruction text): scope path}`` for the operations
    of the ``/device:TPU:0`` plane. The chip's profiler keeps one event
    metadata per instruction of each program, and on it, as stats, the
    program's id (the number in the module's name) and ``tf_op``: the
    ``op_name`` JAX gave the instruction and a colon
    (``jit(_decode_fn)/attention/kv_write/scatter:``). Two programs may
    hold instructions of one text, so the id is part of the key.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name =
    2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .uint64_value = 3, .int64_value = 4, .str_value = 5, .ref_value = 7."""
    for number, plane in fields(buf, 0, len(buf)):
        if number != 1:
            continue
        parts = list(fields(buf, *plane))
        name = next((_text(buf, v) for n, v in parts if n == 2), "")
        if name != device_trace.DEVICE_PLANE + "0":
            continue

        def entries(field):
            for n, entry in parts:
                if n == field:
                    pair = dict(fields(buf, *entry))
                    if 1 in pair and 2 in pair:
                        yield pair[1], pair[2]

        stat_names = {}
        for key, value in entries(5):
            for n, v in fields(buf, *value):
                if n == 2:
                    stat_names[key] = _text(buf, v)

        def value_of(stat):
            if 5 in stat:
                return _text(buf, stat[5])
            if 7 in stat:
                return stat_names.get(stat[7])
            return stat.get(3, stat.get(4))

        out = {}
        for _, value in entries(4):
            text, found = None, {}
            for n, v in fields(buf, *value):
                if n == 2:
                    text = _text(buf, v)
                elif n == 5:
                    stat = dict(fields(buf, *v))
                    found[stat_names.get(stat.get(1))] = value_of(stat)
            path = found.get(SCOPE_STAT)
            if text is not None and path:
                out[str(found.get(PROGRAM_STAT)), text] = \
                    path[:-1] if path.endswith(":") else path
        return out
    return {}


# --------------------------------------------------------------- the trace

def _read(path: str) -> dict:
    """From one pass over the file: ``spans``, ``(name, start_s, end_s,
    stats)`` of every ``apex.*`` event on a host plane, by start;
    ``modules``, ``(name, start_s, end_s, run id)`` of the first chip's
    program runs; ``enqueued``, ``{run id: second}`` at which the host's
    runtime began to put that run on the device's queue."""
    from jax.profiler import ProfileData

    def seconds(e):
        return e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9

    spans, modules, enqueued = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == device_trace.DEVICE_PLANE + "0":
            for line in plane.lines:
                if line.name == device_trace.MODULES_LINE:
                    modules = [(e.name, *seconds(e),
                                dict(e.stats).get("run_id"))
                               for e in line.events]
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, *seconds(e), dict(e.stats)))
                    elif e.name == ENQUEUE:
                        enqueued[dict(e.stats).get("run_id")] = \
                            seconds(e)[0]
    return {"spans": sorted(spans, key=lambda s: (s[1], -s[2])),
            "modules": modules, "enqueued": enqueued}


def clock_shift(modules, enqueued) -> float:
    """Seconds by which the device's events have to be moved later to
    stand on the host's clock. The profiler maps the chip's clock onto the
    host's with an error of some tenths of a millisecond, another in each
    session (0.35 and 1.3 ms early in two sessions of PR 28), which is the
    size of the lags read here: a run then starts before the host has
    enqueued it. The shift is the least that lets no run start before
    ``DoEnqueueProgram`` of its ``run_id`` began; the chip is idle when a
    step is enqueued, so the run that started soonest started at once, and
    the shift is right to that run's few tens of microseconds. Without
    such events, or where the chip's clock is the later one, 0."""
    early = [enqueued[run] - start for _, start, _, run in modules
             if run is not None and run in enqueued]
    return max(early + [0.0])


def trace(obs: dict) -> dict:
    """``{"spans", "modules", "enqueued", "shift", "ops", "scopes"}`` of
    the traced run, read once and kept on ``obs``; empty where the run
    was not traced or ran on no chip."""
    if "_program_trace" not in obs:
        files = sorted(glob.glob(os.path.join(
            obs["trace_dir"], "**", "*.xplane.pb"), recursive=True)) \
            if obs.get("trace_dir") else []
        chips = device_trace._trace(obs)["chips"]   # parsed once, there
        out = {"spans": [], "modules": [], "enqueued": {}, "scopes": {},
               "ops": chips[0]["ops"] if chips else []}
        if files:
            out.update(_read(files[-1]))
            if out["ops"]:
                with open(files[-1], "rb") as f:
                    out["scopes"] = op_scopes(memoryview(f.read()))
        out["shift"] = clock_shift(out["modules"], out["enqueued"])
        obs["_program_trace"] = out
    return obs["_program_trace"]


def program_id(module_name: str) -> str:
    """``jit__decode_fn(9015977658400354697)`` -> the number."""
    return module_name.rpartition("(")[2].rstrip(")")


def whole_runs(tr: dict, module: str) -> list:
    """``(start, end, program id)`` of the module's runs that the
    device's operations cover whole, by start, on the device's clock."""
    if not tr["ops"]:
        return []
    last = max(e for _, _, e in tr["ops"])
    return sorted((s, e, program_id(n)) for n, s, e, _ in tr["modules"]
                  if device_trace.program(n) == module and e <= last + 1e-6)


def _named(tr: dict, name: str) -> list:
    return [s for s in tr["spans"] if s[0] == name]


def _inside(tr: dict, outer, name: str) -> list:
    return [s for s in _named(tr, name)
            if outer[1] <= s[1] and s[2] <= outer[2]]


def pairs(tr: dict, span: str, module: str) -> list:
    """``[(span, (run start, run end))]``: the n-th whole run of
    ``module``, moved onto the host's clock, with the n-th ``span``; a run
    that does not lie between its span's start and a little past its end
    means the order was lost, and nothing is read."""
    out = [(s, (run[0] + tr["shift"], run[1] + tr["shift"]))
           for s, run in zip(_named(tr, span), whole_runs(tr, module))]
    if any(not (s[1] - 5e-3 <= run[0] and run[1] <= s[2] + 5e-3)
           for s, run in out):
        return []
    return out


def _mean_ms(seconds):
    seconds = list(seconds)
    return 1e3 * sum(seconds) / len(seconds) if seconds else None


def _point(tr, which: str, span, run):
    if which == "span_start":
        return span[1]
    if which in ("run_start", "run_end"):
        return run[which == "run_end"]
    child = _inside(tr, span, span[0] + "." + which.split("_")[0])
    return child[0][2] if child else None


def read(spec: dict, obs: dict):
    args, tr = spec["args"], trace(obs)
    quantity = args["quantity"]
    if quantity == "self_time":
        # a tick counts if it made an engine call and all it made count
        counted = {id(s) for name, module in args["calls"]
                   for s, _ in pairs(tr, name, module)}
        own = []
        for tick in _named(tr, args["span"]):
            inner = [s for name, _ in args["calls"]
                     for s in _inside(tr, tick, name)]
            if inner and all(id(s) in counted for s in inner):
                own.append((tick[2] - tick[1])
                           - sum(s[2] - s[1] for s in inner))
        return _mean_ms(own)
    matched = pairs(tr, args["span"], args["module"])
    if not matched:
        return None
    if quantity == "lag":
        assert args["from"] in _POINTS and args["to"] in _POINTS, args
        lags = []
        for span, run in matched:
            a, b = (_point(tr, args[k], span, run) for k in ("from", "to"))
            if a is None or b is None:
                return None
            lags.append(max(b - a, 0.0))
        return _mean_ms(lags)
    if quantity == "host_time":
        return _mean_ms((s[2] - s[1]) - (run[1] - run[0])
                        for s, run in matched)
    if quantity == "between":
        counted = {id(s) for s, _ in matched}
        same = _named(tr, args["span"])
        breaks = _named(tr, args["not_across"])
        waits = [b[1] - a[2] for a, b in zip(same, same[1:])
                 if id(a) in counted and id(b) in counted
                 and not any(a[2] <= x[1] < b[1] for x in breaks)]
        return _mean_ms(waits)
    if quantity == "share":
        over = under = 0
        for span, _ in matched:
            holder = span
            if "child" in args:
                child = _inside(tr, span, span[0] + args["child"])
                holder = child[0] if child else None
            if holder is None or not all(
                    k in holder[3] for k in args["over"] + args["under"]):
                continue
            a = b = 1
            for k in args["over"]:
                a *= holder[3][k]
            for k in args["under"]:
                b *= holder[3][k]
            over, under = over + a, under + b
        return 100.0 * over / under if under else None
    raise ValueError(f"program_spans cannot read {args}")
