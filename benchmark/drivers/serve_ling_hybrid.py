"""The serving driver for ``ling_hybrid`` configurations (layers of two
kinds: latent attention over pages, and a recurrent state a slot; routed
experts as one rank's share). What differs from the other drivers is the
engine that is built; the rest is theirs, by import, as in
``drivers/serve_ouro.py``:

- the loop, the recorder, the sample, the layout and the comparison are
  ``drivers/serve.py``'s (through ``drivers/serve_deepseek_v3.py``);
- the ring of kept logits and ``KeptLogits`` are
  ``drivers/serve_deepseek_v3.py``'s: a call's ``[64, 39 296]`` float32
  logits are 10 MB, a window of them about 13 GB, so only the rows of the
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
    # a parent has no such module: it fails here, at once
    from apex_tpu.models.ling_hybrid import LingHybridConfig
    from apex_tpu.serve.engine import Engine, EngineConfig

    cfg, geo, mix = cell.config, cell.config["serve"], cell.traffic
    reference = importlib.import_module(f"reference.{cfg['reference']}")
    model = LingHybridConfig.from_dict(
        cfg, num_experts=cfg["published"]["num_experts"],
        experts_held=cfg["num_experts"],
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


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        control=None) -> dict:
    with mock.patch.object(serve_deepseek_v3, "build", build):
        return serve_deepseek_v3.run(cell, seed, seconds, trace, t_start,
                                     control)
