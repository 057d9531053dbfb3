"""Work from shapes: the operations and bytes the algorithm needs, never
what today's program does. Weights count once a call at the configuration's
compute type, keys and values only for tokens that are resident, prompts
without their padding. So a share of these cannot pass 100% because the
program changed. ``cfg`` is a configuration file's dict.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float32": 4}


def parameters(cfg: dict) -> dict:
    """Parameter counts: one layer, the embedding table (also the output
    head), the positions, and all of them."""
    e = cfg["n_embd"]
    layer = 12 * e * e + 13 * e          # qkv, out, two MLP halves, 2 norms
    table, pos = cfg["vocab_size"] * e, cfg["n_positions"] * e
    return {"layer": layer, "table": table, "positions": pos,
            "total": cfg["n_layer"] * layer + table + pos + 2 * e}


def kv_bytes_per_token(cfg: dict) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * _BYTES[cfg["compute_dtype"]]


def _forward(cfg, positions, attended, logit_rows):
    """Operations and bytes of one forward call over ``positions`` new
    tokens that together attend over ``attended`` (query, key) pairs and
    need ``logit_rows`` rows of logits."""
    n = parameters(cfg)
    layers = cfg["n_layer"] * n["layer"]
    flops = (2 * layers * positions + 2 * n["table"] * logit_rows
             + 4 * cfg["n_layer"] * cfg["n_embd"] * attended)
    weight_bytes = (layers + n["table"]) * _BYTES[cfg["compute_dtype"]]
    return flops, weight_bytes


def decode_step(cfg: dict, active: int, resident: int):
    """One token for each of ``active`` slots whose caches hold
    ``resident`` tokens together: every weight once, the resident keys and
    values once, the logits written in float32."""
    flops, weight_bytes = _forward(cfg, active, resident, active)
    return flops, (weight_bytes + resident * kv_bytes_per_token(cfg)
                   + active * cfg["vocab_size"] * 4)


def prefill_call(cfg: dict, prompts, hits=None):
    """The prompts of one call (``prompts``: their lengths without what
    the prefix cache served, ``hits``) as one batched causal forward would
    need them: real positions only, logits for each prompt's last row,
    every weight once, the prompts' keys and values written once."""
    hits = hits or [0] * len(prompts)
    positions = sum(prompts)
    attended = sum(n * h + n * (n + 1) // 2 for n, h in zip(prompts, hits))
    flops, weight_bytes = _forward(cfg, positions, attended, len(prompts))
    return flops, (weight_bytes + positions * kv_bytes_per_token(cfg)
                   + len(prompts) * cfg["vocab_size"] * 4)


def least_seconds(flops, nbytes, peaks: dict) -> float:
    """The roofline: the larger of operations over peak operations a
    second and bytes over peak bytes a second."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
