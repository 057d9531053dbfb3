"""The serving driver for ``deepseek_v3`` configurations: the loop, the
recorder, the sample, the layout and the comparison are ``drivers/serve.py``'s
own, by import; what differs is the engine that is built and which logits
stay on the device.

**The engine.** ``models.deepseek_v3.DeepseekV3Config`` from the
configuration's published keys and its share (the experts held, their
offset, the vocabulary slice), through the same ``serve.Engine``; weights
from the seed, in the stated dtype (``reference/deepseek_v3.py``).

**The logits.** ``drivers/serve.py`` keeps every call's ``[slots, vocab]``
logits until the run is scored: 4 MB a call here, several GB a window. This
driver keeps only the rows it may score, and they are still the logits the
timed calls returned. Which requests those are is fixed by the seed before
the window opens: the requests whose prompt's CRC-32 is ``seed`` modulo
``kept_share``. The engine's two calls are wrapped before the recorder
wraps them in turn: after a call returns, the rows of the kept requests'
slots are copied, in one small device program that writes in place, into a ring of ``kept_rows``
rows on the device, and the recorder is handed, in the logits' place, the
call's index into the ring. A request can be scored if none of its rows was
overwritten before the window closed; the sample is drawn among those.
"""

from __future__ import annotations

import gc
import importlib
import zlib

import numpy as np

from drivers import serve
from readers import stamps

now = serve.now
GATHER = 32           # rows a copy into the ring takes at most, padded to


class Ring:
    """``rows`` rows of ``[vocab]`` float32 on the device; position ``p``
    (counted from the first row ever kept) lives at ``p % rows``."""

    def __init__(self, rows: int, vocab: int):
        import jax
        import jax.numpy as jnp

        def keep_rows(buf, logits, block):
            # ``block``: the slots, then the row the block starts at; one
            # contiguous block into the donated buffer, in place
            return jax.lax.dynamic_update_slice(
                buf, logits[block[:GATHER]], (block[GATHER], 0))

        self.rows, self.head = int(rows), 0
        self.buf = jnp.zeros((self.rows, vocab), jnp.float32)
        self._put = jax.jit(keep_rows, donate_argnums=0)

    def keep(self, logits, slots) -> dict:
        """Copy ``logits[slot]`` for the slots into the ring;
        ``{slot: position}``. A copy is a block of ``GATHER`` rows: the
        padding repeats the last slot onto positions not yet given out,
        and a block that would run over the ring's end starts at its
        beginning instead (the positions skipped are given to no row)."""
        at = {}
        for i in range(0, len(slots), GATHER):
            part = slots[i:i + GATHER]
            if self.head % self.rows + GATHER > self.rows:
                self.head += self.rows - self.head % self.rows
            block = np.asarray(part + [part[-1]] * (GATHER - len(part))
                               + [self.head % self.rows], np.int32)
            self.buf = self._put(self.buf, logits, block)
            at.update((slot, self.head + j) for j, slot in enumerate(part))
            self.head += len(part)
        return at

    def holds(self, position: int) -> bool:
        """Whether the row at ``position`` is still its own: not yet
        overwritten, by a kept row or by a block's padding."""
        return position >= self.head + GATHER - self.rows

    def fetch(self, positions) -> np.ndarray:
        import jax.numpy as jnp

        at = np.asarray(positions, np.int64) % self.rows
        return np.asarray(self.buf[jnp.asarray(at.astype(np.int32))])


class Kept:
    """What the recorder keeps in a call's logits' place: where the
    call's kept rows lie in the ring, by slot."""

    __slots__ = ("at",)

    def __init__(self, at: dict):
        self.at = at


class KeptLogits:
    """Wraps ``engine.prefill`` and ``engine.decode_step``: a slot is
    flagged when a kept request is admitted into it, and every call's
    rows of flagged slots that got a token go into the ring."""

    def __init__(self, engine, ring: Ring, seed: int, share: int):
        self.engine, self.ring = engine, ring
        self.pick, self.share = int(seed) % int(share), int(share)
        self.flagged = np.zeros((engine.config.num_slots,), bool)
        self._prefill, self._decode = engine.prefill, engine.decode_step
        engine.prefill, engine.decode_step = self.prefill, self.decode_step

    def restore(self):
        self.engine.prefill, self.engine.decode_step = (self._prefill,
                                                        self._decode)

    def kept(self, prompt) -> bool:
        ids = np.asarray(prompt, np.int64)
        return zlib.crc32(ids.tobytes()) % self.share == self.pick

    def _keep(self, logits, slots) -> Kept:
        slots = [int(s) for s in slots if self.flagged[s]]
        return Kept(self.ring.keep(logits, slots) if slots else {})

    def prefill(self, prompts, **kw):
        first, last_logits, all_logits = self._prefill(prompts, **kw)
        for slot, prompt in prompts.items():
            self.flagged[slot] = self.kept(prompt)
        return first, self._keep(last_logits, sorted(prompts)), all_logits

    def decode_step(self, last_tokens, active):
        tokens, logits = self._decode(last_tokens, active)
        return tokens, self._keep(logits, np.flatnonzero(np.asarray(active)))


def build(cell, seed: int):
    """``(engine, params, reference module)`` for one cell and seed."""
    from apex_tpu.models.deepseek_v3 import DeepseekV3Config
    from apex_tpu.serve.engine import Engine, EngineConfig

    cfg, geo, mix = cell.config, cell.config["serve"], cell.traffic
    reference = importlib.import_module(f"reference.{cfg['reference']}")
    model = DeepseekV3Config.from_dict(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["deployment"]["expert_offset"],
        vocab_held=cfg["vocab_size"])
    params = reference.make_params(cfg, seed)
    engine = Engine(model, params, EngineConfig(
        num_slots=geo["num_slots"], max_len=geo["max_len"], temperature=0.0,
        page_size=geo["page_size"], num_pages=geo["num_pages"],
        prefix_cache=geo["prefix_cache"]))
    lo, hi = mix["prompt_tokens"]
    engine.aot_compile(sorted({serve._pow2_ceil(lo), serve._pow2_ceil(hi)}))
    return engine, params, reference


def scorable(obs, ring: Ring) -> dict:
    """``obs`` with only the requests every one of whose tokens has its
    logits row in the ring still."""
    def whole(r):
        return r["logits"] and all(
            slot in call.at and ring.holds(call.at[slot])
            for call, slot in r["logits"])

    return dict(obs, requests=[r for r in obs["requests"] if whole(r)])


def program_rows(chosen, ring: Ring, rows: int) -> np.ndarray:
    """The logits each served token of the chosen requests was drawn
    from, off the ring, padded to ``rows``; the ring's buffer goes."""
    got = ring.fetch([call.at[slot] for r in chosen
                      for call, slot in r["logits"]])
    ring.buf = None
    gc.collect()
    return np.concatenate([got, np.zeros((rows - len(got), got.shape[1]),
                                         np.float32)])


def score(cfg, mix, params, reference, chosen, ring, control=None) -> dict:
    """``serve.compare`` for the chosen requests; the program's rows come
    off the ring, and the ring goes, before the reference runs."""
    tokens, rows, served, counts = serve.layout(mix, chosen)
    got = program_rows(chosen, ring, len(rows))
    ref = reference.forward_logits(cfg, params, tokens, rows)
    if control:
        got = reference.forward_logits(cfg, params, tokens, rows, control)
        served = np.asarray(got.argmax(-1), np.int32)
    return dict(serve.compare(ref, got, served, counts),
                tokens=int(counts.sum()))


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        control=None) -> dict:
    engine, params, reference = build(cell, seed)
    mix = cell.traffic
    ring = Ring(mix["kept_rows"], cell.config["vocab_size"])
    kept = KeptLogits(engine, ring, seed, mix["kept_share"])
    obs = serve.drive(cell, seed, seconds, trace, engine, t_start)
    chosen = serve.sample(scorable(obs, ring), seed, mix)
    del engine, kept
    gc.collect()                          # the pool goes, the weights stay
    t0 = now()
    scored = score(cell.config, mix, params, reference, chosen, ring, control)
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
              f"{shape['median_ms']:.1f}, longest {shape['longest_ms']:.1f} "
              f"ms; logits kept of {ring.head} rows, the ring holds "
              f"{ring.rows}; reference scored {scored['tokens']} tokens of "
              f"{len(chosen)} requests in {now() - t0:.1f} s"
              + (f" WITH THE CONTROL {control} IN THE PROGRAM'S PLACE"
                 if control else "")
              + f": served tokens lie {scored['mean_logit_gap']:.5f} in the "
              f"mean and {scored['widest_logit_gap']:.4f} at the widest "
              f"below the reference's best; longest in the window: "
              f"{serve.longest_stalls(obs)}"))
    return obs
