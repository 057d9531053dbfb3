"""Work from shapes for an ``ouro`` configuration (a looped decoder:
``total_ut_steps`` passes through the same ``num_hidden_layers`` layers, a
cache plane for every layer of every pass), and the per-layer metrics that
need it: what the algorithm needs, never what today's program does, as
``readers/work.py`` counts GPT-2.

**Weights count once a PASS**, in the configuration's compute type: a pass
reads every layer's weights, 4.93 GB at the published widths, and no chip
holds that between one pass and the next, so a call cannot do with fewer
than ``total_ut_steps`` streams of them. The untied head counts once a call,
the embedding a row a position. Keys and values (``2 x heads x head_dim`` a
token a plane) count once for every resident token and once for every token
written, prompts without their padding, logits in float32. A position's
products are ``2 x`` the layers' matrix parameters ``x`` the passes; a
(query, key) pair is a score and a weighted value a head a plane.

The metrics (``read``): a whole program's roofline share and the step's
share of the peak operations a second, over the device runs the trace holds
whole, each matched with the program's own span (``program_spans.pairs``)
and the counters it left on ``apex.<call>.loop``; the attention scope's
roofline share against the planes' bytes alone; the passes a token took. A
program with no such spans, or a configuration that is no ``ouro``, reads
nothing.
"""

from __future__ import annotations

from readers import device_trace, program_spans, work, work_deepseek_v3

_BYTES = {"bfloat16": 2, "float32": 4}


def parameters(cfg: dict) -> dict:
    """Parameter counts: one layer's matrices, one layer whole (its four
    norms with them), an embedding-sized table, and all of them."""
    e, width = cfg["hidden_size"], cfg["intermediate_size"]
    attn = cfg["num_attention_heads"] * cfg["head_dim"]
    matrices = 4 * e * attn + 3 * e * width
    layer, table = matrices + 4 * e, cfg["vocab_size"] * e
    return {"matrices": matrices, "layer": layer, "table": table,
            "total": (cfg["num_hidden_layers"] * layer + 2 * table
                      + 2 * e + 1)}          # final norm, the gate


def planes(cfg: dict) -> int:
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def kv_bytes_per_token(cfg: dict) -> int:
    return (planes(cfg) * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
            * _BYTES[cfg["compute_dtype"]])


def weight_bytes(cfg: dict) -> int:
    """What one call streams of the weights: the layers once a pass, the
    head once."""
    n = parameters(cfg)
    return (cfg["total_ut_steps"] * cfg["num_hidden_layers"] * n["layer"]
            + n["table"]) * _BYTES[cfg["compute_dtype"]]


def plane_attention(cfg: dict, attended: int, resident: int):
    """Attention over the planes: a score and a weighted value a head a
    plane for each of ``attended`` (query, key) pairs; the keys and values
    of ``resident`` tokens read once."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return (4 * planes(cfg) * width * attended,
            resident * kv_bytes_per_token(cfg))


def _forward(cfg, positions, attended, cached, logit_rows):
    """One call over ``positions`` new tokens that together attend over
    ``attended`` pairs, ``cached`` of whose keys lay in the pool before the
    call, and need ``logit_rows`` rows of logits."""
    n, item = parameters(cfg), _BYTES[cfg["compute_dtype"]]
    passes_layers = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    attn_flops, attn_bytes = plane_attention(cfg, attended, cached)
    flops = (2 * passes_layers * n["matrices"] * positions
             + 2 * n["table"] * logit_rows + attn_flops)
    nbytes = (weight_bytes(cfg) + positions * cfg["hidden_size"] * item
              + attn_bytes + positions * kv_bytes_per_token(cfg)
              + logit_rows * cfg["vocab_size"] * 4)
    return flops, nbytes


def decode_step(cfg: dict, active: int, resident: int):
    """One token for each of ``active`` slots whose caches hold
    ``resident`` tokens together before the step."""
    return _forward(cfg, active, resident + active, resident, active)


def prefill_call(cfg: dict, admitted: int, real_positions: int,
                 hit_tokens: int):
    """One call's prompts as one batched causal forward would need them:
    real positions only (the spans give the call's totals, so the prompts
    are taken as equally long, the fewest pairs those totals allow),
    logits for each prompt's last row."""
    each = real_positions / max(admitted, 1)
    pairs = admitted * each * (each + 1) / 2 \
        + real_positions * hit_tokens / max(admitted, 1)
    return _forward(cfg, real_positions, pairs, hit_tokens, admitted)


# ----------------------------------------------------- reading the trace

PROGRAMS = work_deepseek_v3.PROGRAMS


def calls(obs: dict, program: str) -> list:
    """``[(attributes, run seconds)]`` of the program's whole runs in the
    traced slice, each with what its own spans carried: the call's
    occupancy and, from ``<span>.loop``, the program's counters. Empty
    where a span lacks them (a model with no pass loop, a parent commit)."""
    spec, tr = PROGRAMS[program], program_spans.trace(obs)
    out = []
    for span, run in program_spans.pairs(tr, spec["span"], spec["module"]):
        loop = program_spans._inside(tr, span, spec["span"] + ".loop")
        holder = span if spec["holder"] is None else next(iter(
            program_spans._inside(tr, span, spec["span"] + spec["holder"])),
            None)
        if not loop or holder is None or not all(
                k in holder[3] for k in spec["keys"]):
            return []
        attrs = {k: holder[3][k] for k in spec["keys"]}
        if program == "prefill":
            attrs["admitted"] = span[3]["admitted"]
        out.append((attrs, run[1] - run[0], loop[0][3]))
    return out


def read(spec: dict, obs: dict):
    args, cfg = spec["args"], obs["config"]
    if cfg.get("reference") != "ouro":
        return None
    quantity = args["quantity"]
    if quantity == "passes_per_row":
        loops = [loop for _, _, loop in calls(obs, args["program"])]
        rows = sum(loop["rows"] for loop in loops)
        return sum(loop["passes"] for loop in loops) / rows if rows else None
    peaks = obs["peaks"]
    if quantity == "mfu":
        measured = device_trace.busy(obs)
        each = [calls(obs, p) for p in ("decode", "prefill")]
        if measured is None or not each[0]:
            return None
        flops = sum(decode_step(cfg, **a)[0] for a, _, _ in each[0]) \
            + sum(prefill_call(cfg, **a)[0] for a, _, _ in each[1])
        return 100.0 * flops / (measured[1] * peaks["bf16_flops_per_s"])
    matched = calls(obs, args["program"])
    if not matched:
        return None
    if quantity == "roofline":
        count = decode_step if args["program"] == "decode" else prefill_call
        least = sum(work.least_seconds(*count(cfg, **a), peaks)
                    for a, _, _ in matched)
        return 100.0 * least / sum(took for _, took, _ in matched)
    if quantity == "attention_roofline":
        got = work_deepseek_v3.scope_seconds(
            obs, PROGRAMS[args["program"]]["module"])
        if got is None:
            return None
        took = got[0].get("attention", 0.0) * len(matched)
        least = sum(work.least_seconds(*plane_attention(
            cfg, a["resident"] + a["active"], a["resident"]), peaks)
            for a, _, _ in matched)
        return 100.0 * least / took if took else None
    raise ValueError(f"work_ouro cannot read {args}")
