"""The serving driver: ``ServeScheduler.step()`` over ``serve.Engine`` under
one traffic mix, a closed loop of ``callers`` that each wait for their
reply, in one thread.

Set-up makes the weights from the seed (``reference/<name>.py``, one jitted
call), builds the engine and its paged pool, loads the ``decode`` and
``prefill_<bucket>`` programs the mix needs, and runs the load until
``ramp_requests`` have completed: by then every program has run and the
slots no longer move in step. Then the window opens and stays open for
``--seconds``; a traced run traces its last ``trace_seconds``, so that the
profiler writes its file once the window has closed. The engine's bound
methods are wrapped from outside: each call leaves a span, each token a
stamp at the moment the blocking fetch returned it, and the logits the call
returned are kept where they are, on the device. Once the window has closed,
the peak memory is read, the engine is dropped, and the reference scores a
sample of the finished requests: the logits the program computed against
its own, and each served token against the logits it was drawn from.
"""

from __future__ import annotations

import gc
import importlib
import os
import shutil
import time

import numpy as np

import generator
from readers import stamps

now = time.perf_counter


def _pow2_ceil(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def build(cell, seed: int):
    """``(engine, params, reference module)`` for one cell and seed."""
    import jax.numpy as jnp
    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.serve.engine import Engine, EngineConfig

    cfg, geo, mix = cell.config, cell.config["serve"], cell.traffic
    reference = importlib.import_module(f"reference.{cfg['reference']}")
    model = GPT2Config(
        vocab_size=cfg["vocab_size"], n_positions=cfg["n_positions"],
        n_embd=cfg["n_embd"], n_layer=cfg["n_layer"], n_head=cfg["n_head"],
        compute_dtype=getattr(jnp, cfg["compute_dtype"]))
    params = reference.make_params(cfg, seed)
    engine = Engine(model, params, EngineConfig(
        num_slots=geo["num_slots"], max_len=geo["max_len"], temperature=0.0,
        page_size=geo["page_size"], num_pages=geo["num_pages"],
        prefix_cache=geo["prefix_cache"]))
    lo, hi = mix["prompt_tokens"]
    engine.aot_compile(sorted({_pow2_ceil(lo), _pow2_ceil(hi)}))
    return engine, params, reference


class Recorder:
    """Spans around ``step``, ``prefill`` and ``decode_step``, and for
    every token a stamp and the ``(logits of the call, slot)`` it was drawn
    from, taken by wrapping the bound methods."""

    def __init__(self, engine, sched):
        from jax.profiler import TraceAnnotation

        self.engine, self.sched, self._note = engine, sched, TraceAnnotation
        self.spans: list = []
        self.token_t: dict = {}
        self.logits: dict = {}
        self._inner = {"prefill": engine.prefill,
                       "decode_step": engine.decode_step, "step": sched.step}
        engine.prefill, engine.decode_step = self._prefill, self._decode
        sched.step = self.step

    def restore(self):
        self.engine.prefill = self._inner["prefill"]
        self.engine.decode_step = self._inner["decode_step"]
        self.sched.step = self._inner["step"]

    def _timed(self, name, owners, info, *args, **kw):
        """``owners``: ``{slot: the request that gets a token}``."""
        with self._note(f"bench.{name}"):
            t0 = now()
            out = self._inner[name](*args, **kw)
            t1 = now()
        self.spans.append((name, t0, t1, info))
        for slot, req in owners.items():
            self.token_t.setdefault(req.request_id, []).append(t1)
            self.logits.setdefault(req.request_id, []).append((out[1], slot))
        return out

    def _prefill(self, prompts, **kw):
        owners = {slot: self.sched.slots[slot] for slot in prompts}
        info: dict = {}
        out = self._timed("prefill", owners, info, prompts, **kw)
        stats = [self.engine.last_prefill_stats[slot] for slot in prompts]
        info.update(prompts=[s["scanned"] for s in stats],
                    hits=[s["hit_tokens"] for s in stats])
        return out

    def _decode(self, last_tokens, active):
        owners = {slot: r for slot, r in enumerate(self.sched.slots)
                  if r is not None}
        info = {"active": len(owners),
                "resident": self.engine.resident_tokens + len(owners)}
        return self._timed("decode_step", owners, info, last_tokens, active)

    def step(self):
        return self._timed("step", {}, {})


def drive(cell, seed, seconds, trace, engine, t_start):
    """Run the load: ramp, then the window. Returns the observations."""
    import jax
    from apex_tpu.serve.scheduler import Request, ServeScheduler

    mix, vocab = cell.traffic, cell.config["vocab_size"]
    sched = ServeScheduler(engine)
    rec = Recorder(engine, sched)
    compiled = engine.decode_traces + engine.prefill_traces
    stream = generator.requests(mix, seed, vocab)
    limit = float(mix["ramp_limit_s"])
    sent: list = []

    def submit():                         # a caller sends its next request
        tokens, answer = next(stream)
        sent.append(Request(request_id=len(sent), tokens=tokens,
                            max_new_tokens=answer))
        sched.submit(sent[-1])

    t_loop = now()
    for _ in range(int(mix["callers"])):
        submit()
    opened = cut = None
    cursor = completed = 0
    trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
    while True:
        t = now()
        if opened is None and completed >= mix["ramp_requests"]:
            opened = t
        elif opened is None and t - t_loop > limit:
            raise RuntimeError(f"{completed} requests done after {limit} s: "
                               f"the ramp needs {mix['ramp_requests']}")
        if opened is not None:
            if t >= opened + seconds:
                break
            if trace and cut is None and \
                    t >= opened + seconds - mix["trace_seconds"]:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0   # no per-call Python events
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                cut = [now(), None]
        sched.step()
        finished, cursor = sched.done_since(cursor)
        for _ in finished:                # its caller got the reply
            completed += 1
            submit()
    if cut:                               # writing the trace takes tens of
        cut[1] = now()                    # seconds: the window has closed
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    rec.restore()
    requests = [{
        "id": r.request_id, "prompt": list(r.tokens),
        "generated": list(r.generated), "submit_t": r.submit_t,
        "admit_t": r.admit_t,
        "first_token_t": r.first_token_t, "done_t": r.done_t,
        "token_t": rec.token_t.get(r.request_id, []),
        "logits": rec.logits.get(r.request_id, []),
        "ended": r.state not in ("queued", "running"),
        "failed": (r.state in ("evicted", "rejected")
                   or (r.state == "completed"
                       and (r.finish_reason != "length"
                            or len(r.generated) != r.max_new_tokens
                            or not all(0 <= t < vocab
                                       for t in r.generated)))),
    } for r in sent]
    for r in requests:                    # a stamp for every served token
        if r["ended"] and len(r["token_t"]) != len(r["generated"]):
            raise RuntimeError(f"request {r['id']}: {len(r['token_t'])} "
                               f"stamps for {len(r['generated'])} tokens")
    return {
        "requests": requests, "spans": rec.spans,
        "window": (opened, opened + seconds), "setup_s": opened - t_start,
        "slice": tuple(cut) if cut else None,
        "trace_dir": trace_dir if cut else None, "config": cell.config,
        "compiles_in_window": (engine.decode_traces + engine.prefill_traces
                               - compiled),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
        "calls": {"prefill": engine.prefill_calls,
                  "decode": engine.decode_calls,
                  "prefix_hit_tokens": engine.prefix_hit_tokens},
    }


def sample(obs, seed: int, mix: dict) -> list:
    """``check_requests`` of the requests that finished inside the window,
    drawn from the seed, the longest among them."""
    lo, hi = obs["window"]
    done = [r for r in obs["requests"]
            if r["ended"] and not r["failed"] and lo <= r["done_t"] < hi]
    if not done:
        raise RuntimeError("no request finished inside the window")
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["generated"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 2])
    picks = rng.permutation(len(rest))[: int(mix["check_requests"]) - 1]
    return [longest] + [rest[i] for i in picks]


def layout(mix, chosen):
    """The chosen requests as the reference takes them: ``tokens [n,
    width]`` (each prompt with its served tokens), the ``(sequence,
    position)`` whose logits give each served token, the served tokens, and
    1.0 for each real row. Shapes are the mix's largest, whatever was
    drawn, so the reference compiles once a cell."""
    n, longest = int(mix["check_requests"]), int(mix["answer_tokens"][1])
    tokens = np.zeros((n, int(mix["prompt_tokens"][1]) + longest), np.int64)
    rows, served = [], []
    for i, r in enumerate(chosen):
        seq = r["prompt"] + r["generated"]
        tokens[i, :len(seq)] = seq
        rows += [(i, len(r["prompt"]) - 1 + j)
                 for j in range(len(r["generated"]))]
        served += r["generated"]
    pad = n * longest - len(rows)
    return (tokens, rows + [(0, 0)] * pad,
            np.asarray(served + [0] * pad, np.int32),
            np.asarray([1.0] * len(rows) + [0.0] * pad, np.float32))


def program_logits(chosen, rows: int) -> np.ndarray:
    """The logits each served token of the chosen requests was drawn from,
    as the timed calls returned them, padded to ``rows``."""
    got = [np.asarray(call[slot], np.float32)
           for r in chosen for call, slot in r["logits"]]
    return np.concatenate(
        [np.stack(got), np.zeros((rows - len(got), got[0].shape[-1]),
                                 np.float32)])


def compare(ref, got, served, counts) -> dict:
    """What is compared, over the real rows. ``logit_noise_share``: the
    squared distance between the logits ``got`` and the reference's, over
    the squared size of the reference's, each row taken about its own mean
    (a shift of a whole row changes no choice). ``served_below_own_best``:
    how many served tokens were not the best of the logits they were drawn
    from. ``mean_logit_gap`` and ``widest_logit_gap``: by how much a served
    token's logit lies below the reference's best."""
    import jax.numpy as jnp

    def centred(x):
        return x - x.mean(-1, keepdims=True)

    def at_served(x):
        return jnp.take_along_axis(x, jnp.asarray(served)[:, None], 1)[:, 0]

    ref, got, counts = jnp.asarray(ref), jnp.asarray(got), jnp.asarray(counts)
    off = jnp.square(centred(got) - centred(ref)).sum(-1) * counts
    size = jnp.square(centred(ref)).sum(-1) * counts
    gaps = (ref.max(-1) - at_served(ref)) * counts
    return {"logit_noise_share": float(off.sum() / size.sum()),
            "served_below_own_best": int(
                ((at_served(got) < got.max(-1)) * counts).sum()),
            "mean_logit_gap": float(gaps.sum() / counts.sum()),
            "widest_logit_gap": float(gaps.max())}


def score(cfg, mix, params, reference, chosen, control=None) -> dict:
    """``compare`` for the chosen requests: the reference runs once over
    each prompt with its served tokens. With a ``control`` (a mode of the
    reference below the stated precision) that forward stands in the
    program's place: its logits at the same positions, and as served tokens
    the ones it puts first."""
    tokens, rows, served, counts = layout(mix, chosen)
    ref = reference.forward_logits(cfg, params, tokens, rows)
    if control:
        got = reference.forward_logits(cfg, params, tokens, rows, control)
        served = np.asarray(got.argmax(-1), np.int32)
    else:
        got = program_logits(chosen, len(rows))
    return dict(compare(ref, got, served, counts), tokens=int(counts.sum()))


def longest_stalls(obs) -> str:
    """The window's longest scheduler step, prefill call, decode step and
    stretch between two steps, each with the second of the window it began
    in: a run that reads far off says here where it stood still."""
    lo, hi = obs["window"]
    groups = {name: [s for s in obs["spans"]
                     if s[0] == name and lo <= s[1] < hi]
              for name in ("step", "prefill", "decode_step")}
    groups["between steps"] = [
        ("between steps", a[2], b[1], None)
        for a, b in zip(groups["step"], groups["step"][1:])]
    return ", ".join(
        f"{name} {(t1 - t0) * 1e3:.1f} ms at {t0 - lo:.1f} s"
        for name, group in groups.items() if group
        for _, t0, t1, _ in [max(group, key=lambda s: s[2] - s[1])])


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        control=None) -> dict:
    engine, params, reference = build(cell, seed)
    obs = drive(cell, seed, seconds, trace, engine, t_start)
    chosen = sample(obs, seed, cell.traffic)
    del engine
    gc.collect()                          # the pool goes, the weights stay
    t0 = now()
    scored = score(cell.config, cell.traffic, params, reference, chosen,
                   control)
    for r in obs["requests"]:
        del r["logits"]
    ended = [r for r in obs["requests"] if r["ended"]]
    shape = stamps.ttft_shape(obs["requests"], obs["window"])
    failed = sum(r["failed"] for r in ended)
    obs.update(
        end_to_end={"setup_s": obs["setup_s"],
                    **stamps.end_to_end(obs["requests"], obs["window"])},
        attempted=len(ended), failed=failed,
        checks={**{name: {"value": scored[name], "limit": spec["limit"]}
                   for name, spec in cell.limits.items()},
                "served_below_own_best": {
                    "value": scored["served_below_own_best"], "limit": 0},
                "failed_requests": {"value": failed, "limit": 0},
                "compiles_in_window": {"value": obs["compiles_in_window"],
                                       "limit": 0}},
        note=(f"requests ended {len(ended)} (in flight at the close "
              f"{len(obs['requests']) - len(ended)}), failed {failed}; "
              f"compilations inside the window {obs['compiles_in_window']}; "
              f"allocator peak_bytes_in_use {obs['memory_peak_bytes']}; "
              f"engine calls {obs['calls']}; time to first token over the "
              f"{shape['count']} requests submitted and first served inside "
              f"the window: mean {shape['mean_ms']:.1f}, median "
              f"{shape['median_ms']:.1f}, 90th {shape['p90_ms']:.1f}, longest "
              f"{shape['longest_ms']:.1f} ms (the former ttft_p90_ms, over "
              f"the {shape['former_count']} first served inside it: "
              f"{shape['former_p90_ms']:.1f}); reference scored "
              f"{scored['tokens']} tokens of {len(chosen)} requests in "
              f"{now() - t0:.1f} s"
              + (f" WITH THE CONTROL {control} IN THE PROGRAM'S PLACE"
                 if control else "")
              + f": served tokens lie {scored['mean_logit_gap']:.5f} in the "
              f"mean and {scored['widest_logit_gap']:.4f} at the widest "
              f"below the reference's best; longest in the window: "
              f"{longest_stalls(obs)}"))
    return obs
