"""Work from shapes for a ``deepseek_v3`` configuration, and the per-layer
metrics that need it: what the algorithm needs, never what today's program
does, as ``readers/work.py`` counts GPT-2.

Every weight outside the routed experts counts once a call in the
configuration's compute type, but the embedding, of which a call reads a row
a token. **A held expert's weights count only in a call that routed a row to
it**: the number comes from the program's own counter (``experts_hit`` on
``apex.decode_step.routing`` / ``apex.prefill.routing``), as does the number
of picks that landed here, whose rows are the experts' operations. Latent
rows (``kv_lora_rank + qk_rope_head_dim`` wide, one a token a layer) count
once for every resident token, prompts without their padding, logits in
float32. ``cfg`` is a configuration file's dict: ``n_routed_experts`` is the
experts held here, ``published.n_routed_experts`` the router's width.

The metrics (``read``): a whole program's roofline share and the step's
share of the peak operations a second, over the device runs that the trace
holds whole, each matched with the program's own span that launched it
(``program_spans.pairs``); a scope's device time a run, and its roofline
share against the work of that scope alone. A program with no such spans or
scopes, or a configuration that is no ``deepseek_v3``, reads nothing.
"""

from __future__ import annotations

import bisect

from readers import device_trace, program_spans, work

_BYTES = {"bfloat16": 2, "float32": 4}
# the innermost of these names on an operation's scope path takes its time
SCOPES = ("experts", "router", "shared_expert", "kv_write", "attn_proj",
          "attention", "ln_qkv", "mlp")
# the TPU compiler expands ``ragged_dot`` into a grouped-matmul kernel whose
# instructions it names anew (``%ragged-dot-none.3``, ``op_name`` the same):
# the scope they were traced under is gone. The routed experts' are the
# program's only grouped products, so the name stands for the scope
GROUPED = "ragged-dot"


def parameters(cfg: dict) -> dict:
    """Parameter counts by part; ``layer_*`` are whole layers as held
    here, ``total`` all that is held."""
    e, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    mla = (e * q_rank + q_rank * heads * (nope + rope) + e * (kv_rank + rope)
           + kv_rank * heads * (nope + v) + heads * v * e)
    norms = 2 * e + q_rank + kv_rank       # both of a layer, and MLA's two
    dense = 3 * e * cfg["intermediate_size"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    routed = cfg["published"]["n_routed_experts"]
    router = e * routed + routed
    held = cfg["n_routed_experts"]
    dense_layers = cfg["first_k_dense_replace"]
    expert_layers = cfg["num_hidden_layers"] - dense_layers
    table = cfg["vocab_size"] * e
    layer_dense = mla + norms + dense
    layer_expert = mla + norms + router + expert * (1 + held)
    return {"mla": mla, "norms": norms, "dense": dense, "expert": expert,
            "router": router, "table": table, "layer_dense": layer_dense,
            "layer_expert": layer_expert, "expert_layers": expert_layers,
            "dense_layers": dense_layers,
            "total": (dense_layers * layer_dense
                      + expert_layers * layer_expert + 2 * table + e)}


def latent_bytes_per_token(cfg: dict) -> int:
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * _BYTES[cfg["compute_dtype"]])


def experts(cfg: dict, experts_hit: int, picks_here: int):
    """The routed experts of one call: each hit expert's three matrices
    once, each landed pick a row through them."""
    n = parameters(cfg)
    return (2 * n["expert"] * picks_here,
            n["expert"] * experts_hit * _BYTES[cfg["compute_dtype"]])


def latent_attention(cfg: dict, active: int, resident: int):
    """Decode's attention in the absorbed form, every layer: a query a
    slot and head into the latent space (``nope x kv_rank``) and its
    result out of it (``kv_rank x v``), a score (``kv_rank + rope``) and a
    weighted sum (``kv_rank``) a head a resident token; the resident
    latent rows read once."""
    heads, kv_rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    per_query = heads * kv_rank * (cfg["qk_nope_head_dim"]
                                   + cfg["v_head_dim"])
    per_token = heads * (2 * kv_rank + cfg["qk_rope_head_dim"])
    flops = 2 * cfg["num_hidden_layers"] * (active * per_query
                                            + resident * per_token)
    return flops, resident * latent_bytes_per_token(cfg)


def _outside_experts(cfg: dict, rows: int):
    """Operations and weight bytes of ``rows`` positions through all that
    is not a routed expert or attention's products with the cache: MLA's
    projections, the dense and shared MLPs, the router; the up-projection
    ``kv_b`` counts here once (it is what the absorbed products are made
    of, and ``latent_attention`` / the prefill's pairs count those)."""
    n = parameters(cfg)
    kv_b = (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))
    per_row = (cfg["num_hidden_layers"] * (n["mla"] - kv_b)
               + n["dense_layers"] * n["dense"]
               + n["expert_layers"] * (n["expert"] + n["router"]))
    weights = n["total"] - 2 * n["table"] \
        - n["expert_layers"] * cfg["n_routed_experts"] * n["expert"]
    return 2 * per_row * rows, weights * _BYTES[cfg["compute_dtype"]]


def _head_and_rows(cfg: dict, rows: int, logit_rows: int):
    """The embedding rows read, the head once, the latent rows written,
    the logits written in float32."""
    e, item = cfg["hidden_size"], _BYTES[cfg["compute_dtype"]]
    n = parameters(cfg)
    return (2 * n["table"] * logit_rows,
            rows * e * item + n["table"] * item
            + rows * latent_bytes_per_token(cfg)
            + logit_rows * cfg["vocab_size"] * 4)


def decode_step(cfg: dict, active: int, resident: int, experts_hit: int,
                picks_here: int):
    """One token for each of ``active`` slots whose caches hold
    ``resident`` tokens together."""
    parts = (_outside_experts(cfg, active),
             _head_and_rows(cfg, active, active),
             experts(cfg, experts_hit, picks_here),
             latent_attention(cfg, active, resident))
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def prefill_call(cfg: dict, admitted: int, real_positions: int,
                 hit_tokens: int, experts_hit: int, picks_here: int):
    """One call's prompts as one batched causal forward would need them:
    real positions only, attention in the plain form over the pairs (the
    spans give the call's totals, so the prompts are taken as equally
    long, which is the fewest pairs those totals allow), logits for each
    prompt's last row."""
    each = real_positions / max(admitted, 1)
    pairs = admitted * each * (each + 1) / 2 \
        + real_positions * hit_tokens / max(admitted, 1)
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]               # a score and a weighted value
    kv_b = (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))
    attention = 2 * cfg["num_hidden_layers"] * (
        cfg["num_attention_heads"] * width * pairs + kv_b * real_positions)
    parts = (_outside_experts(cfg, real_positions),
             _head_and_rows(cfg, real_positions, admitted),
             experts(cfg, experts_hit, picks_here))
    return (sum(p[0] for p in parts) + attention,
            sum(p[1] for p in parts)
            + hit_tokens * latent_bytes_per_token(cfg))


# ----------------------------------------------------- reading the trace

PROGRAMS = {
    "decode": {"span": "apex.decode_step", "module": "decode_fn",
               "holder": None, "keys": ("active", "resident")},
    "prefill": {"span": "apex.prefill", "module": "prefill_fn",
                "holder": ".launch", "keys": ("real_positions",
                                              "hit_tokens")},
}


def calls(obs: dict, program: str) -> list:
    """``[(attributes, run seconds)]`` of the program's whole runs in the
    traced slice, each with what its own spans carried: the call's
    occupancy and, from ``<span>.routing``, the program's counters. Empty
    where a span lacks them (a model with no routing, a parent commit)."""
    spec, tr = PROGRAMS[program], program_spans.trace(obs)
    out = []
    for span, run in program_spans.pairs(tr, spec["span"], spec["module"]):
        routing = program_spans._inside(tr, span, spec["span"] + ".routing")
        holder = span if spec["holder"] is None else next(iter(
            program_spans._inside(tr, span, spec["span"] + spec["holder"])),
            None)
        if not routing or holder is None or not all(
                k in holder[3] for k in spec["keys"]):
            return []
        attrs = {k: holder[3][k] for k in spec["keys"]}
        attrs.update({k: routing[0][3][k]
                      for k in ("experts_hit", "picks_here")})
        if program == "prefill":
            attrs["admitted"] = span[3]["admitted"]
        out.append((attrs, run[1] - run[0]))
    return out


def scope_seconds(obs: dict, module: str):
    """``({scope: device seconds a whole run}, runs)``: each operation's
    own time to the innermost of ``SCOPES`` on its path."""
    kept = obs.setdefault("_deepseek_scopes", {})
    if module not in kept:
        tr = program_spans.trace(obs)
        runs = program_spans.whole_runs(tr, module)
        kept[module] = None
        if runs and tr["scopes"]:
            starts = [r[0] for r in runs]

            def scope(path):
                return next((seg for seg in reversed(
                    (path or "").split("/")[:-1]) if seg in SCOPES), "other")

            named = {key: scope(path) for key, path in tr["scopes"].items()}
            events = []
            for name, start, end in tr["ops"]:
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start < runs[i][1]:
                    events.append((
                        "experts" if name.lstrip("%").startswith(GROUPED)
                        else named.get((runs[i][2], name), "other"),
                        start, end))
            total = device_trace.self_seconds(events)
            kept[module] = ({k: v / len(runs) for k, v in total.items()},
                            len(runs))
    return kept[module]


def _least(cfg, count, attrs, peaks, keys=None):
    chosen = attrs if keys is None else {k: attrs[k] for k in keys}
    return work.least_seconds(*count(cfg, **chosen), peaks)


def read(spec: dict, obs: dict):
    args, cfg = spec["args"], obs["config"]
    if cfg.get("reference") != "deepseek_v3":
        return None
    quantity = args["quantity"]
    if quantity == "scope_ms":
        got = scope_seconds(obs, PROGRAMS[args["program"]]["module"])
        if got is None or args["scope"] not in got[0]:
            return None
        return got[0][args["scope"]] * 1e3
    peaks = obs["peaks"]
    if quantity == "mfu":
        measured = device_trace.busy(obs)
        each = [calls(obs, p) for p in ("decode", "prefill")]
        if measured is None or not each[0]:
            return None
        flops = sum(decode_step(cfg, **a)[0] for a, _ in each[0]) \
            + sum(prefill_call(cfg, **a)[0] for a, _ in each[1])
        return 100.0 * flops / (measured[1] * peaks["bf16_flops_per_s"])
    matched = calls(obs, args["program"])
    if not matched:
        return None
    if quantity == "roofline":
        count = decode_step if args["program"] == "decode" else prefill_call
        least = sum(_least(cfg, count, a, peaks) for a, _ in matched)
        return 100.0 * least / sum(took for _, took in matched)
    if quantity == "scope_roofline":
        got = scope_seconds(obs, PROGRAMS[args["program"]]["module"])
        if got is None:
            return None
        took = sum(got[0].get(s, 0.0) for s in args["scopes"]) * len(matched)
        count, keys = {
            "experts": (experts, ("experts_hit", "picks_here")),
            "latent_attention": (latent_attention, ("active", "resident")),
        }[args["work"]]
        least = sum(_least(cfg, count, a, peaks, keys) for a, _ in matched)
        return 100.0 * least / took if took else None
    raise ValueError(f"work_deepseek_v3 cannot read {args}")
