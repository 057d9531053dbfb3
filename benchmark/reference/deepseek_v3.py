"""DeepSeek-V3 (``model_type: deepseek_v3``), plainly: seeded weights and a
float32 forward, for the benchmark. Imports nothing of ``apex_tpu``.

It follows the published ``modeling_deepseek_v3`` (Hugging Face): RMSNorm,
latent attention in its plain, non-absorbed form with no cache (queries
through the ``q_a``/``q_b`` pair with a norm between, one ``kv_a`` product
whose first ``kv_lora_rank`` outputs are normalised and expanded by
``kv_b`` into every head's ``nope`` key and value, and whose last
``qk_rope_head_dim`` outputs are the one rotated key all heads share),
YaRN rotary frequencies, ``first_k_dense_replace`` dense SwiGLU layers and
then expert layers behind the ``noaux_tc`` router with one shared expert,
a final norm and an untied head. ``cfg`` is a configuration file's dict.

The departures from the published code, each marked ``DEPARTURE`` below:

1. **A rank's share.** ``cfg["n_routed_experts"]`` is how many routed
   experts are HELD (``deployment.expert_offset`` on), while the router
   keeps ``published.n_routed_experts`` columns; a chosen expert that is
   not held adds nothing. ``vocab_size`` is the held slice of the
   vocabulary: embedding, head and logits are over it.
2. The multi-token-prediction layer is not computed (the published
   inference code does not load it).
3. The mixed-precision recipe is replaced by one statement: every weight
   holds a ``compute_dtype`` value (bfloat16 in the configurations), every
   product and every activation is float32 at ``highest`` precision.

``mode`` says how the matrix products with weights are computed: ``fp32``
(the reference) or ``int8`` (weights per output column and activations per
row rounded to 127 levels): the control, one step below the bfloat16 that
the configurations state.

**Initialisation** (``param_spec``), chosen so that activations, sigmoid
scores and logits are O(1) at any width: a linear weight is N(0, 1 /
fan_in), so a unit-RMS input gives a unit-RMS output (queries, keys and
values have unit entries; a score is ``q . k * softmax_scale`` with
standard deviation about 2); the embedding is N(0, 1); a norm's weight is
N(1, 0.1); the router's columns are N(0, 1 / hidden), so its logits are
N(0, 1) and the sigmoid scores spread over 0.27-0.73; its bias is N(0,
0.002), the size a trained model's load-balancing bias is left with once the
experts are evenly used: the eight chosen of 128 candidates score 0.82-0.92,
about a hundredth apart, so such a bias decides near-ties and gives no expert
a following of its own (at N(0, 0.1) the same few experts were chosen for
every token, half the held experts were hit a step where even use hits 87 %;
at N(0, 0.02) the share, and with it a decode step's time, still moved by 2 %
from seed to seed, more than `tokens_per_s` may spread: my chip run,
PR 30); the head is N(0, 1 / hidden), so logits are N(0, 1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def shape_of(cfg: dict) -> dict:
    """The sizes the forward needs, from a configuration's dict."""
    published = cfg.get("published", {})
    return {
        "hidden": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "dense_width": cfg["intermediate_size"],
        "moe_width": cfg["moe_intermediate_size"],
        # DEPARTURE 1: held here, against the router's published width
        "held": cfg["n_routed_experts"],
        "routed": published.get("n_routed_experts", cfg["n_routed_experts"]),
        "offset": cfg.get("deployment", {}).get("expert_offset", 0),
        "vocab": cfg["vocab_size"],
    }


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, mean, std)}`` for every leaf, in a fixed order."""
    s = shape_of(cfg)
    e, h = s["hidden"], s["heads"]
    spec = {("embed",): ((s["vocab"], e), 0.0, 1.0),
            ("head",): ((s["vocab"], e), 0.0, e ** -0.5),
            ("norm",): ((e,), 1.0, 0.1)}

    def linear(path, fan_in, fan_out, lead=()):
        spec[path] = (lead + (fan_in, fan_out), 0.0, fan_in ** -0.5)

    for i in range(s["layers"]):
        name = f"l_{i}"
        spec[(name, "attn_norm")] = ((e,), 1.0, 0.1)
        linear((name, "q_a"), e, s["q_rank"])
        spec[(name, "q_norm")] = ((s["q_rank"],), 1.0, 0.1)
        linear((name, "q_b"), s["q_rank"], h * (s["nope"] + s["rope"]))
        linear((name, "kv_a"), e, s["kv_rank"] + s["rope"])
        spec[(name, "kv_norm")] = ((s["kv_rank"],), 1.0, 0.1)
        linear((name, "kv_b"), s["kv_rank"], h * (s["nope"] + s["v"]))
        linear((name, "o"), h * s["v"], e)
        spec[(name, "ffn_norm")] = ((e,), 1.0, 0.1)
        if i < s["dense_layers"]:
            linear((name, "gate"), e, s["dense_width"])
            linear((name, "up"), e, s["dense_width"])
            linear((name, "down"), s["dense_width"], e)
        else:
            w = s["moe_width"]
            linear((name, "router"), e, s["routed"])
            spec[(name, "router_bias")] = ((s["routed"],), 0.0, 0.002)
            linear((name, "shared_gate"), e, w)
            linear((name, "shared_up"), e, w)
            linear((name, "shared_down"), w, e)
            linear((name, "w_gate"), e, w, (s["held"],))
            linear((name, "w_up"), e, w, (s["held"],))
            linear((name, "w_down"), w, e, (s["held"],))
    return spec


def seed_key(seed: int):
    """A raw threefry key from any whole number up to 64 bits."""
    seed = int(seed)
    return jnp.asarray(np.array([(seed >> 32) & 0xFFFFFFFF,
                                 seed & 0xFFFFFFFF], np.uint32))


@functools.lru_cache(maxsize=4)
def _jitted_params(cfg_json: str):
    import json

    cfg = json.loads(cfg_json)
    spec = param_spec(cfg)
    dtype = getattr(jnp, cfg["compute_dtype"])

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree: dict = {}
        for i, (path, (shape, mean, std)) in enumerate(spec.items()):
            leaf = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, _F32)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            # drawn in float32, held in the stated dtype; the router's
            # bias stays float32, as published
            node[path[-1]] = leaf if path[-1] == "router_bias" \
                else leaf.astype(dtype)
        return {"params": tree}

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    """Every weight from the seed, in one jitted call: each leaf is drawn
    in float32 and rounded to ``compute_dtype`` inside it, so no float32
    tree ever exists."""
    import json

    keys = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "vocab_size", "compute_dtype")
    slim = {k: cfg[k] for k in keys}
    slim["published"] = {"n_routed_experts": shape_of(cfg)["routed"]}
    return _jitted_params(json.dumps(slim, sort_keys=True))(seed_key(seed))


# ------------------------------------------------------------ the forward


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, mode):
    """``x [.., in] @ w [in, out]`` in float32."""
    x, w = x.astype(_F32), w.astype(_F32)
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode != "fp32":
        raise ValueError(f"no mode {mode!r}")
    return jnp.einsum("...i,io->...o", x, w, precision=_HI)


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(_F32)


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """``_compute_yarn_parameters`` of the published code: the blend of
    ``theta ** (-2i / d)`` and the same over ``factor`` by the linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scaling = cfg.get("rope_scaling")
    if not scaling:
        return base
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    extrapolation_share = 1.0 - ramp
    return (base / scaling["factor"] * (1 - extrapolation_share)
            + base * extrapolation_share)


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return scale


def _rope(x, positions, inv_freq):
    """``apply_rotary_pos_emb_interleave``: de-interleave the pairs, then
    rotate halves. ``x [t, ..., d]``, ``positions [t]``. (The factor on cos
    and sin is ``mscale / mscale_all_dim``-derived and 1 here.)"""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    freqs = positions.astype(_F32)[:, None] * jnp.asarray(inv_freq, _F32)
    emb = jnp.concatenate([freqs, freqs], -1)
    emb = emb.reshape((emb.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _attention(x, blk, cfg, s, mode):
    """Plain MLA over one sequence ``x [t, hidden]``."""
    t, h = x.shape[0], s["heads"]
    eps, inv_freq = cfg["rms_norm_eps"], yarn_inv_freq(cfg)
    positions = jnp.arange(t)
    q = _linear(_rms_norm(_linear(x, blk["q_a"], mode), blk["q_norm"], eps),
                blk["q_b"], mode).reshape(t, h, s["nope"] + s["rope"])
    q_nope, q_rope = q[..., :s["nope"]], q[..., s["nope"]:]
    kv = _linear(x, blk["kv_a"], mode)
    c_kv = _rms_norm(kv[:, :s["kv_rank"]], blk["kv_norm"], eps)
    k_rope = _rope(kv[:, s["kv_rank"]:], positions, inv_freq)   # [t, rope]
    q_rope = _rope(q_rope, positions, inv_freq)
    kvx = _linear(c_kv, blk["kv_b"], mode).reshape(t, h, s["nope"] + s["v"])
    k_nope, v = kvx[..., :s["nope"]], kvx[..., s["nope"]:]
    score = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope, precision=_HI)
             ) * softmax_scale(cfg)
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v,
                   precision=_HI).reshape(t, h * s["v"])
    return _linear(o, blk["o"], mode)


def _swiglu(u, gate, up, down, mode):
    return _linear(jax.nn.silu(_linear(u, gate, mode))
                   * _linear(u, up, mode), down, mode)


def route(u, w_router, bias, cfg, mode="fp32"):
    """``DeepseekV3TopkRouter`` (``noaux_tc``): ``(experts [t, k], weights
    [t, k])``, float32."""
    groups, experts = cfg["n_group"], w_router.shape[-1]
    scores = jax.nn.sigmoid(_linear(u, w_router, mode))
    for_choice = scores + bias.astype(_F32)
    group_scores = jax.lax.top_k(
        for_choice.reshape(-1, groups, experts // groups), 2)[0].sum(-1)
    kept = jax.lax.top_k(group_scores, cfg["topk_group"])[1]
    group_mask = jnp.zeros(group_scores.shape, bool).at[
        jnp.arange(u.shape[0])[:, None], kept].set(True)
    for_choice = jnp.where(
        jnp.repeat(group_mask, experts // groups, axis=1), for_choice, 0.0)
    chosen = jax.lax.top_k(for_choice, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights * cfg["routed_scaling_factor"]


def _experts(u, blk, cfg, s, mode):
    """The shared expert plus, expert by expert, each HELD expert's
    output weighted by the router's weight for the rows that chose it."""
    chosen, weights = route(u, blk["router"], blk["router_bias"], cfg, mode)
    out = _swiglu(u, blk["shared_gate"], blk["shared_up"],
                  blk["shared_down"], mode)

    def one(out, expert):
        number, gate, up, down = expert
        # DEPARTURE 1: only held experts are in this loop; a row's weight
        # for an expert it did not choose is 0
        weight = jnp.where(chosen == number, weights, 0.0).sum(-1)
        return out + weight[:, None] * _swiglu(u, gate, up, down, mode), None

    numbers = s["offset"] + jnp.arange(s["held"])
    return jax.lax.scan(one, out, (numbers, blk["w_gate"], blk["w_up"],
                                   blk["w_down"]))[0]


@functools.partial(jax.jit, static_argnames=("cfg_json", "dense", "mode"))
def _block(x, blk, *, cfg_json, dense, mode):
    """One layer over ``x [n, t, hidden]``, a sequence at a time, so that
    one sequence's scores and one layer's float32 weights are all that is
    live."""
    import json

    cfg = json.loads(cfg_json)
    s, eps = shape_of(cfg), cfg["rms_norm_eps"]

    def one(seq):
        h = seq + _attention(_rms_norm(seq, blk["attn_norm"], eps), blk,
                             cfg, s, mode)
        u = _rms_norm(h, blk["ffn_norm"], eps)
        if dense:
            return h + _swiglu(u, blk["gate"], blk["up"], blk["down"], mode)
        return h + _experts(u, blk, cfg, s, mode)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(h, norm, head, *, eps, mode):
    return _linear(_rms_norm(h, norm, eps), head.T, mode)


def forward_logits(cfg: dict, params, tokens, rows, mode: str = None):
    """Logits ``[len(rows), vocab]`` at the ``(sequence, position)`` pairs
    in ``rows``, for ``tokens [n, t]`` (causal, so padding at the end of a
    sequence changes nothing before it): the full causal forward, no
    cache, layer by layer."""
    import json

    mode = mode or "fp32"
    p = params["params"] if "params" in params else params
    cfg_json = json.dumps({k: v for k, v in cfg.items()
                           if k not in ("note", "assumed", "serve")},
                          sort_keys=True)
    x = p["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, p[f"l_{i}"], cfg_json=cfg_json,
                   dense=i < cfg["first_k_dense_replace"], mode=mode)
    seq, pos = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    return _head(x[seq, pos], p["norm"], p["head"],
                 eps=cfg["rms_norm_eps"], mode=mode)
