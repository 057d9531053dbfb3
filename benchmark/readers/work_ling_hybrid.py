"""Work from shapes for a ``ling_hybrid`` configuration (KDA layers over a
recurrent state a slot, one MLA layer in six over latent pages, routed
experts as one rank's share), and the per-layer metrics that need it: what
the algorithm needs, never what today's program does, as ``readers/work.py``
counts GPT-2 and ``readers/work_deepseek_v3.py`` the expert layer.

Every weight outside the routed experts counts once a call in the
configuration's compute type, but the embedding, of which a call reads a
row a token. **A held expert's 11.8 MB count only in a call that routed a
row to it** (``experts_hit`` on ``apex.<call>.routing``; the landed picks
are the experts' operations). **The KDA state and convolution tail of the
call's slots count once a layer each way**: read and written by a decode
step, written by a prefill call, which starts from zero (2 097 152 + 73 728
B a slot a layer). Latent rows of the one MLA plane (1 152 B a token) count
once for every resident token and once for every token written, prompts
without their padding; logits in float32. ``cfg`` is a configuration file's
dict: ``num_experts`` is the experts held here, ``published.num_experts``
the router's width.

The metrics (``read``): a whole program's roofline share and the step's
share of the peak operations a second, over the device runs the trace holds
whole, each matched with the program's own span (``program_spans.pairs``)
and the counters on ``apex.<call>.routing``; the device time under the
scope ``kda_state`` a run, its roofline share against the state's bytes
(decode) or the chunkwise recurrence's operations and bytes (prefill), and
the routed experts' share against the hit experts' bytes. The state's bytes
counted here have to be the ``state_bytes`` the span carries for its
``state_slots``, or nothing is read. A program with no such spans or scopes
(a parent commit, another model), or a configuration that is no
``ling_hybrid``, reads nothing.
"""

from __future__ import annotations

from unittest import mock

from readers import device_trace, program_spans, work, work_deepseek_v3

_BYTES = {"bfloat16": 2, "float32": 4}
CHUNK = 64           # positions a step of the chunkwise recurrence
# the innermost of these names on an operation's scope path takes its time
SCOPES = ("kda_state",) + work_deepseek_v3.SCOPES
PROGRAMS = work_deepseek_v3.PROGRAMS


def layers(cfg: dict) -> dict:
    """How many layers of each kind the configuration keeps."""
    n, group = cfg["num_hidden_layers"], cfg["layer_group_size"]
    dense = cfg["first_k_dense_replace"]
    return {"mla": n // group, "kda": n - n // group, "dense": dense,
            "expert": n - dense}


def parameters(cfg: dict) -> dict:
    """Parameter counts by part; ``total`` is all that is held."""
    e, h, d = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["head_dim"])
    rank, nope, rope, v = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                           cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    a = h * d
    # q, k, v, o and the decay; the two head-wise maps; three convolutions
    kda = 5 * e * a + 2 * e * h + 3 * cfg["short_conv_kernel_size"] * a
    kda_small = h + a + d                 # A_log, dt_bias, the output norm
    kv_b = rank * h * (nope + v)
    mla = e * h * (nope + rope) + e * (rank + rope) + kv_b + h * v * e + e * h
    dense = 3 * e * cfg["intermediate_size"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    shared = 3 * e * cfg["moe_shared_expert_intermediate_size"]
    routed = cfg["published"]["num_experts"]
    router = e * routed + routed
    n, table = layers(cfg), cfg["vocab_size"] * e
    outside = (n["kda"] * (kda + kda_small) + n["mla"] * (mla + rank)
               + cfg["num_hidden_layers"] * 2 * e + n["dense"] * dense
               + n["expert"] * (shared + router) + e)
    return {"kda": kda, "mla": mla, "kv_b": kv_b, "dense": dense,
            "expert": expert, "shared": shared, "router": router,
            "table": table, "outside_experts": outside,
            "total": (outside + 2 * table
                      + n["expert"] * cfg["num_experts"] * expert)}


def state_bytes_per_slot(cfg: dict) -> int:
    """One KDA layer's float32 state and its convolution's tail, a slot."""
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    return (4 * a * cfg["head_dim"]
            + (cfg["short_conv_kernel_size"] - 1) * 3 * a
            * _BYTES[cfg["compute_dtype"]])


def latent_bytes_per_token(cfg: dict) -> int:
    return (layers(cfg)["mla"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * _BYTES[cfg["compute_dtype"]])


def experts(cfg: dict, experts_hit: int, picks_here: int):
    """The routed experts of one call: each hit expert's three matrices
    once, each landed pick a row through them."""
    n = parameters(cfg)
    return (2 * n["expert"] * picks_here,
            n["expert"] * experts_hit * _BYTES[cfg["compute_dtype"]])


def kda_state_step(cfg: dict, state_slots: int):
    """A decode step's recurrence, every KDA layer: a slot's state decayed,
    read for ``k^T S`` and ``q^T S`` and updated (7 operations an element),
    the state and the convolution's tail read once and written once."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    each = layers(cfg)["kda"] * state_slots
    return 7 * h * d * d * each, 2 * state_bytes_per_slot(cfg) * each


def kda_scan_call(cfg: dict, state_slots: int, real_positions: int):
    """A prefill call's recurrence in the chunkwise form, every KDA layer,
    real positions only. A position and head, at ``C = CHUNK``: the two
    decayed pair blocks ``4 C D``, the three products with the chunk's
    start state and the update of it ``6 D Dv``, the pairs' product with
    the solved values ``2 C Dv`` and the triangular solve ``C Dv``. The
    bytes: a position's convolved inputs in and its output out in the
    compute type, its decay in float32; the state and tail of each of the
    ``state_slots`` admitted written once."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    item, n = _BYTES[cfg["compute_dtype"]], layers(cfg)["kda"]
    per_position = h * (4 * CHUNK * d + 6 * d * d + 3 * CHUNK * d)
    return (n * per_position * real_positions,
            n * (real_positions * h * d * (4 * item + 4)
                 + state_slots * state_bytes_per_slot(cfg)))


def latent_attention(cfg: dict, active: int, resident: int):
    """Decode's attention in the absorbed form over the MLA planes
    (``work_deepseek_v3.latent_attention`` over these planes alone)."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    per_query = heads * rank * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    per_token = heads * (2 * rank + cfg["qk_rope_head_dim"])
    return (2 * layers(cfg)["mla"] * (active * per_query
                                      + resident * per_token),
            resident * latent_bytes_per_token(cfg))


def _outside_experts(cfg: dict, rows: int):
    """``rows`` positions through every product with a weight that is no
    routed expert's and not the head's (``kv_b`` counts with attention's
    own products), and those weights once."""
    n, k = parameters(cfg), layers(cfg)
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    a = h * cfg["head_dim"]
    per_row = (k["kda"] * (5 * e * a + 2 * e * h)
               + k["mla"] * (n["mla"] - n["kv_b"]) + k["dense"] * n["dense"]
               + k["expert"] * (n["shared"] + n["router"]))
    return (2 * per_row * rows,
            n["outside_experts"] * _BYTES[cfg["compute_dtype"]])


def _head_and_rows(cfg: dict, rows: int, logit_rows: int):
    """The embedding rows read, the head once, the latent rows written,
    the logits written in float32."""
    e, item = cfg["hidden_size"], _BYTES[cfg["compute_dtype"]]
    table = parameters(cfg)["table"]
    return (2 * table * logit_rows,
            rows * e * item + table * item
            + rows * latent_bytes_per_token(cfg)
            + logit_rows * cfg["vocab_size"] * 4)


def _sum(parts):
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def decode_step(cfg: dict, active: int, resident: int, experts_hit: int,
                picks_here: int, state_slots: int):
    """One token for each of ``active`` slots whose latent pages hold
    ``resident`` tokens together."""
    return _sum((_outside_experts(cfg, active),
                 _head_and_rows(cfg, active, active),
                 experts(cfg, experts_hit, picks_here),
                 latent_attention(cfg, active, resident),
                 kda_state_step(cfg, state_slots)))


def prefill_call(cfg: dict, admitted: int, real_positions: int,
                 hit_tokens: int, experts_hit: int, picks_here: int,
                 state_slots: int):
    """One call's prompts as one batched causal forward would need them:
    real positions only, the MLA layers' attention in the plain form over
    the pairs (the spans give the call's totals, so the prompts are taken
    as equally long: the fewest pairs those totals allow), the KDA layers'
    recurrence in the chunkwise form, logits for each prompt's last row.
    ``hit_tokens`` is 0: the model refuses the prefix cache."""
    each = real_positions / max(admitted, 1)
    pairs = admitted * each * (each + 1) / 2
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]               # a score and a weighted value
    attention = 2 * layers(cfg)["mla"] * (
        cfg["num_attention_heads"] * width * pairs
        + parameters(cfg)["kv_b"] * real_positions)
    return _sum((_outside_experts(cfg, real_positions),
                 _head_and_rows(cfg, real_positions, admitted),
                 experts(cfg, experts_hit, picks_here),
                 kda_scan_call(cfg, state_slots, real_positions),
                 (attention, 0)))


# ----------------------------------------------------- reading the trace


def calls(obs: dict, program: str) -> list:
    """``work_deepseek_v3.calls`` (``[(attributes, run seconds)]`` of the
    program's whole runs in the traced slice, each with its occupancy and
    the routing counters of ``<span>.routing``) with the ``state_slots``
    that span carries. Empty where a span lacks them (a model with no
    recurrent state, a parent commit) or where the state's bytes counted
    here are not the ``state_bytes`` the span says the call moved."""
    spec, tr = PROGRAMS[program], program_spans.trace(obs)
    each = (2 if program == "decode" else 1) \
        * layers(obs["config"])["kda"] * state_bytes_per_slot(obs["config"])
    out = []
    for (attrs, took), (span, _) in zip(
            work_deepseek_v3.calls(obs, program),
            program_spans.pairs(tr, spec["span"], spec["module"])):
        moved = program_spans._inside(tr, span,
                                      spec["span"] + ".routing")[0][3]
        if moved.get("state_bytes", -1) != moved.get("state_slots", 0) * each:
            return []
        out.append((dict(attrs, state_slots=moved["state_slots"]), took))
    return out


def scope_seconds(obs: dict, module: str):
    """``({scope: device seconds a whole run}, runs)``:
    ``work_deepseek_v3.scope_seconds`` with ``kda_state`` among the scopes
    it splits by (for the length of the call that module's ``SCOPES`` is
    this one's; it reads no ``ling_hybrid`` trace on its own account)."""
    with mock.patch.object(work_deepseek_v3, "SCOPES", SCOPES):
        return work_deepseek_v3.scope_seconds(obs, module)


# what a scope's roofline share is counted against: the work and the
# attributes of a call it needs
_SCOPE_WORK = {
    "experts": (experts, ("experts_hit", "picks_here")),
    "kda_state_step": (kda_state_step, ("state_slots",)),
    "kda_scan_call": (kda_scan_call, ("state_slots", "real_positions")),
}


def read(spec: dict, obs: dict):
    args, cfg = spec["args"], obs["config"]
    if cfg.get("reference") != "ling_hybrid":
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
        least = sum(work.least_seconds(*count(cfg, **a), peaks)
                    for a, _ in matched)
        return 100.0 * least / sum(took for _, took in matched)
    if quantity == "scope_roofline":
        got = scope_seconds(obs, PROGRAMS[args["program"]]["module"])
        if got is None:
            return None
        took = got[0].get(args["scope"], 0.0) * len(matched)
        count, keys = _SCOPE_WORK[args["work"]]
        least = sum(work.least_seconds(
            *count(cfg, **{k: a[k] for k in keys}), peaks)
            for a, _ in matched)
        return 100.0 * least / took if took else None
    raise ValueError(f"work_ling_hybrid cannot read {args}")
