"""The serving driver for ``ouro`` configurations (a looped decoder: the
layers run several times a token, each pass over cache planes of its own).
What differs from the other two drivers is the engine that is built; the
rest is theirs, by import:

- the loop, the recorder, the sample, the layout and the comparison are
  ``drivers/serve.py``'s (through ``drivers/serve_deepseek_v3.py``);
- the ring of kept logits and ``KeptLogits`` are
  ``drivers/serve_deepseek_v3.py``'s: a call's ``[16, 49 152]`` float32
  logits are 3.1 MB, a window of them about 4 GB, so only the rows of the
  requests that may be scored stay on the device, and they are still the
  rows the timed calls returned;
- ``run`` is ``serve_deepseek_v3.run`` itself, which asks its own module for
  ``build``: for the length of the call that name is this module's.
"""

from __future__ import annotations

import importlib
from unittest import mock

from drivers import serve, serve_deepseek_v3


def build(cell, seed: int):
    """``(engine, params, reference module)`` for one cell and seed."""
    from apex_tpu.models.ouro import OuroConfig   # a parent has none: at once
    from apex_tpu.serve.engine import Engine, EngineConfig

    cfg, geo, mix = cell.config, cell.config["serve"], cell.traffic
    reference = importlib.import_module(f"reference.{cfg['reference']}")
    params = reference.make_params(cfg, seed)
    engine = Engine(OuroConfig.from_dict(cfg), params, EngineConfig(
        num_slots=geo["num_slots"], max_len=geo["max_len"], temperature=0.0,
        page_size=geo["page_size"], num_pages=geo["num_pages"],
        prefix_cache=geo["prefix_cache"]))
    lo, hi = mix["prompt_tokens"]
    engine.aot_compile(sorted({serve._pow2_ceil(lo), serve._pow2_ceil(hi)}))
    return engine, params, reference


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        control=None) -> dict:
    with mock.patch.object(serve_deepseek_v3, "build", build):
        return serve_deepseek_v3.run(cell, seed, seconds, trace, t_start,
                                     control)
