"""Ling-3.0-flash (``model_type: bailing_hybrid``: inclusionAI's hybrid of
linear and latent attention over sparse experts), plainly: seeded weights
and a float32 forward, for the benchmark. Imports nothing of ``apex_tpu``.
``cfg`` is a configuration file's dict.

Layer ``i`` of ``num_hidden_layers`` is a latent-attention (MLA) layer when
``(i + 1) % layer_group_size == 0`` and a **KDA** layer (Kimi Delta
Attention: a gated delta rule with a decay a channel) otherwise; the first
``first_k_dense_replace`` layers have a dense SwiGLU, the rest routed
experts behind the ``noaux_tc`` router with one shared expert. ``x = x +
mix(rms(x))``, ``x = x + ffn(rms(x))``, a final RMSNorm, an untied head.

**A KDA layer** over ``u = rms(x)``, heads of ``head_dim`` (``D``):

- ``q~, k~, v~ = u W_q, u W_k, u W_v``; each channel through a causal
  convolution over its last ``short_conv_kernel_size`` steps (zeros before
  the sequence), then SiLU; per head ``q = l2norm(q) / sqrt(D)``, ``k =
  l2norm(k)``; no rotary;
- the decay, a head ``h`` and a channel: ``g_t = kda_lower_bound *
  sigmoid(exp(A_log_h) * (u W_f + dt_bias))``, ``a_t = exp(g_t)``;
  ``b_t = sigmoid(u W_b)`` a head;
- the state ``S`` ``[D, D]`` a head, from zero: ``S_t = (I - b_t k_t
  k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``, one
  token at a time (``lax.scan``);
- ``rms(o_t)`` a head with a gain ``[D]``, times ``sigmoid(u W_g)_h`` (one
  gate a head), the heads concatenated, ``W_o``.

**An MLA layer**: ``modeling_deepseek_v3``'s latent attention in its plain
form with no cache, ``q = u W_q`` directly (``q_lora_rank`` null), plain
rotary frequencies ``rope_theta ** (-2j / 64)`` on the interleaved pairs of
the 64-wide slice, ``softmax_scale = 192 ** -0.5``, and the same head-wise
sigmoid gate on the heads' outputs before ``W_o``.

What the source's ``config.json`` has no key for (the convolution's form,
the norms' placement, the l2norm's epsilon, the rotary pairing, one shared
expert, no biases) is listed under ``assumed`` in the configuration's
file, from arXiv:2510.26692 and its published module, as remembered: there
is no network here.

The departures, each marked ``DEPARTURE`` below:

1. **A rank's share.** ``cfg["num_experts"]`` is how many routed experts
   are HELD (``deployment.expert_offset`` on), while the router keeps
   ``published.num_experts`` columns; a chosen expert that is not held
   adds nothing. ``vocab_size`` is the held slice of the vocabulary.
2. The vision tower and the multi-token-prediction head are not computed:
   the language model over text ids.
3. The mixed-precision recipe is one statement: every weight holds a
   ``compute_dtype`` value (bfloat16 in the configuration; the decay's
   ``A_log`` and ``dt_bias`` and the router's bias float32), every product
   and activation is float32 at ``highest``.
4. A non-zero entry of ``expert_swiglu_limit_list`` /
   ``share_expert_swiglu_limit_list`` among the layers kept is refused:
   the clamp's form is not in the config.

``mode``: ``fp32`` (the reference) or ``int8`` (the products with weights
rounded to 127 levels, weights per output column and activations per row):
the control, one step below the stated bfloat16.

**Initialisation** (``param_spec``): a linear weight N(0, 1 / fan_in), the
embedding N(0, 1), a norm's gain N(1, 0.1), the router's bias N(0, 0.002)
(``reference/deepseek_v3.py`` says why), ``A_log`` N(0, 0.3) and ``dt_bias``
N(-3, 1.5): the decay's sigmoid reads a median of 0.05, so half the
channels forget by less than a fifth a token (``a`` over 0.79) and one in
twenty by more than ``exp(-3.6)``: slow and fast channels side by side, as
a trained layer has them. Two leaves are shaped after they are drawn, so
that **the experts are evenly used, as a trained router's bias leaves
them**: SiLU leaves a mean of 0.2 on every channel of ``q``, ``k`` and
``v``, so ``q . k`` is positive on average and every token's ``o`` holds a
running average of values, all along the one all-positive direction of a
head; written into the residual stream that is a direction every token
shares (9 % of the normed state's energy), and it gives every token the
same favourite experts: a step of 64 rows hit 53 % of the held experts
where even use hits 63 %, the picks an expert got spread by 11 about a mean
of 12, and the picks that landed here moved by 4 % with the seed (CPU, full
width). So (a) **a convolution's taps** are N(0, 1) scaled to unit norm a
channel (every channel's output then has its input's variance, and SiLU
leaves the same mean on each), and (b) **a KDA layer's ``o``** has the rows
of each head centred (the head's all-positive direction maps to zero):
the shared direction falls to 0.5 % and the picks spread by 4.0 (3.5 is
Poisson's).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_FLOAT32_LEAVES = ("router_bias", "a_log", "dt_bias")
_KEYS = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
         "layer_group_size", "num_attention_heads", "head_dim",
         "short_conv_kernel_size", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "intermediate_size",
         "moe_intermediate_size", "moe_shared_expert_intermediate_size",
         "num_experts", "vocab_size", "compute_dtype")


def is_mla(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["layer_group_size"] == 0


def shape_of(cfg: dict) -> dict:
    """The sizes the forward needs, from a configuration's dict."""
    published = cfg.get("published", {})
    return {
        "hidden": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "dense_layers": cfg["first_k_dense_replace"],
        "heads": cfg["num_attention_heads"], "head": cfg["head_dim"],
        "taps": cfg["short_conv_kernel_size"],
        "kv_rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "dense_width": cfg["intermediate_size"],
        "moe_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["moe_shared_expert_intermediate_size"],
        # DEPARTURE 1: held here, against the router's published width
        "held": cfg["num_experts"],
        "routed": published.get("num_experts", cfg["num_experts"]),
        "offset": cfg.get("deployment", {}).get("expert_offset", 0),
        "vocab": cfg["vocab_size"],
    }


def refuse_swiglu_clamp(cfg: dict) -> None:
    """DEPARTURE 4."""
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        kept = list(cfg.get(key) or [])[:cfg["num_hidden_layers"]]
        if any(kept):
            raise ValueError(
                f"{key}[:{cfg['num_hidden_layers']}] = {kept}: a non-zero "
                f"entry clamps the SwiGLU of that layer, and the form of "
                f"the clamp is not in the config")


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, mean, std)}`` for every leaf, in a fixed order."""
    s = shape_of(cfg)
    e, h, d = s["hidden"], s["heads"], s["head"]
    spec = {("embed",): ((s["vocab"], e), 0.0, 1.0),
            ("head",): ((s["vocab"], e), 0.0, e ** -0.5),
            ("norm",): ((e,), 1.0, 0.1)}

    def linear(path, fan_in, fan_out, lead=()):
        spec[path] = (lead + (fan_in, fan_out), 0.0, fan_in ** -0.5)

    for i in range(s["layers"]):
        name = f"l_{i}"
        spec[(name, "attn_norm")] = ((e,), 1.0, 0.1)
        if is_mla(cfg, i):
            linear((name, "q"), e, h * (s["nope"] + s["rope"]))
            linear((name, "kv_a"), e, s["kv_rank"] + s["rope"])
            spec[(name, "kv_norm")] = ((s["kv_rank"],), 1.0, 0.1)
            linear((name, "kv_b"), s["kv_rank"], h * (s["nope"] + s["v"]))
            linear((name, "head_gate"), e, h)
            linear((name, "o"), h * s["v"], e)
        else:
            for proj in ("q", "k", "v", "f"):
                linear((name, proj), e, h * d)
            for proj in ("conv_q", "conv_k", "conv_v"):
                spec[(name, proj)] = ((s["taps"], h * d), 0.0, 1.0)
            linear((name, "beta"), e, h)
            linear((name, "head_gate"), e, h)
            spec[(name, "a_log")] = ((h,), 0.0, 0.3)
            spec[(name, "dt_bias")] = ((h * d,), -3.0, 1.5)
            spec[(name, "o_norm")] = ((d,), 1.0, 0.1)
            linear((name, "o"), h * d, e)
        spec[(name, "ffn_norm")] = ((e,), 1.0, 0.1)
        if i < s["dense_layers"]:
            linear((name, "gate"), e, s["dense_width"])
            linear((name, "up"), e, s["dense_width"])
            linear((name, "down"), s["dense_width"], e)
        else:
            w = s["moe_width"]
            linear((name, "router"), e, s["routed"])
            spec[(name, "router_bias")] = ((s["routed"],), 0.0, 0.002)
            linear((name, "shared_gate"), e, s["shared_width"])
            linear((name, "shared_up"), e, s["shared_width"])
            linear((name, "shared_down"), s["shared_width"], e)
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
    cfg = json.loads(cfg_json)
    spec = param_spec(cfg)
    dtype = getattr(jnp, cfg["compute_dtype"])

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree: dict = {}
        for i, (path, (shape, mean, std)) in enumerate(spec.items()):
            leaf = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, _F32)
            if path[-1].startswith("conv_"):     # unit norm a channel
                leaf = leaf * jax.lax.rsqrt(
                    jnp.sum(jnp.square(leaf), 0, keepdims=True))
            elif path[-1] == "o" and not is_mla(cfg, int(path[0][2:])):
                heads = leaf.reshape(cfg["num_attention_heads"], -1,
                                     shape[-1])     # each head's rows centred
                leaf = (heads - heads.mean(1, keepdims=True)).reshape(shape)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            # drawn in float32, held in the stated dtype (DEPARTURE 3)
            node[path[-1]] = leaf if path[-1] in _FLOAT32_LEAVES \
                else leaf.astype(dtype)
        return {"params": tree}

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    """Every weight from the seed, in one jitted call: each leaf is drawn
    in float32 and rounded to ``compute_dtype`` inside it, so no float32
    tree ever exists."""
    slim = {k: cfg[k] for k in _KEYS}
    slim["published"] = {"num_experts": shape_of(cfg)["routed"]}
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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _short_conv(x, taps):
    """``y_t = silu(sum_j taps[j] * x_{t - (K-1) + j})`` a channel, zeros
    before the sequence. ``x [t, channels]``, ``taps [K, channels]``."""
    t, k = x.shape[0], taps.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + t] * taps[j].astype(_F32)
                           for j in range(k)))


def kda_recurrence(q, k, v, g, beta, state=None):
    """The delta rule with a decay a channel, a token at a time. ``q, k,
    g [t, heads, D]``, ``v [t, heads, Dv]``, ``beta [t, heads]``; returns
    ``(o [t, heads, Dv], the state after the last token [heads, D, Dv])``."""
    if state is None:
        state = jnp.zeros(q.shape[1:] + v.shape[-1:], _F32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("hk,hkv->hv", k_t, s, precision=_HI)
        s = s + (b_t[:, None] * k_t)[..., None] * (v_t - seen)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=_HI)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _kda(u, blk, cfg, s, mode):
    """One KDA layer's mixing over one sequence ``u [t, hidden]``."""
    t, h, d = u.shape[0], s["heads"], s["head"]
    q, k, v = (_short_conv(_linear(u, blk[name], mode),
                           blk["conv_" + name]).reshape(t, h, d)
               for name in ("q", "k", "v"))
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    rate = jnp.exp(blk["a_log"].astype(_F32))[None, :, None]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * (
        _linear(u, blk["f"], mode) + blk["dt_bias"].astype(_F32)
    ).reshape(t, h, d))
    beta = jax.nn.sigmoid(_linear(u, blk["beta"], mode))
    o, _ = kda_recurrence(q, k, v, g, beta)
    o = _rms_norm(o, blk["o_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(_linear(u, blk["head_gate"], mode))[..., None]
    return _linear(o.reshape(t, h * d), blk["o"], mode)


def _rope(x, positions, inv_freq):
    """The interleaved pairing: de-interleave the pairs, then rotate
    halves. ``x [t, ..., d]``, ``positions [t]``."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    freqs = positions.astype(_F32)[:, None] * jnp.asarray(inv_freq, _F32)
    emb = jnp.concatenate([freqs, freqs], -1)
    emb = emb.reshape((emb.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def _mla(u, blk, cfg, s, mode):
    """Plain MLA over one sequence ``u [t, hidden]``, gated a head."""
    t, h = u.shape[0], s["heads"]
    rope = s["rope"]
    inv_freq = float(cfg["rope_theta"]) ** (
        -np.arange(0, rope, 2, dtype=np.float64) / rope)
    positions = jnp.arange(t)
    q = _linear(u, blk["q"], mode).reshape(t, h, s["nope"] + rope)
    q_nope, q_rope = q[..., :s["nope"]], q[..., s["nope"]:]
    kv = _linear(u, blk["kv_a"], mode)
    c_kv = _rms_norm(kv[:, :s["kv_rank"]], blk["kv_norm"],
                     cfg["rms_norm_eps"])
    k_rope = _rope(kv[:, s["kv_rank"]:], positions, inv_freq)   # [t, rope]
    q_rope = _rope(q_rope, positions, inv_freq)
    kvx = _linear(c_kv, blk["kv_b"], mode).reshape(t, h, s["nope"] + s["v"])
    k_nope, v = kvx[..., :s["nope"]], kvx[..., s["nope"]:]
    score = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope, precision=_HI)
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope, precision=_HI)
             ) * (s["nope"] + rope) ** -0.5
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v,
                   precision=_HI)
    o = o * jax.nn.sigmoid(_linear(u, blk["head_gate"], mode))[..., None]
    return _linear(o.reshape(t, h * s["v"]), blk["o"], mode)


def _swiglu(u, gate, up, down, mode):
    return _linear(jax.nn.silu(_linear(u, gate, mode))
                   * _linear(u, up, mode), down, mode)


def route(u, w_router, bias, cfg, mode="fp32"):
    """The ``noaux_tc`` router (sigmoid scores, a bias for the choice and
    not for the weight, ``topk_group`` of ``n_group`` groups): ``(experts
    [t, k], weights [t, k])``, float32."""
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


@functools.partial(jax.jit, static_argnames=("cfg_json", "mla", "dense",
                                             "mode"))
def _block(x, blk, *, cfg_json, mla, dense, mode):
    """One layer over ``x [n, t, hidden]``, a sequence at a time, so that
    one sequence's scores or states and one layer's float32 weights are
    all that is live."""
    cfg = json.loads(cfg_json)
    s, eps = shape_of(cfg), cfg["rms_norm_eps"]

    def one(seq):
        u = _rms_norm(seq, blk["attn_norm"], eps)
        h = seq + (_mla if mla else _kda)(u, blk, cfg, s, mode)
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
    cache and no state kept, layer by layer."""
    mode = mode or "fp32"
    refuse_swiglu_clamp(cfg)
    p = params["params"] if "params" in params else params
    cfg_json = json.dumps({k: v for k, v in cfg.items()
                           if k not in ("note", "assumed", "serve",
                                        "expert_swiglu_limit_list",
                                        "share_expert_swiglu_limit_list")},
                          sort_keys=True)
    x = p["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
    for i in range(cfg["num_hidden_layers"]):
        x = _block(x, p[f"l_{i}"], cfg_json=cfg_json, mla=is_mla(cfg, i),
                   dense=i < cfg["first_k_dense_replace"], mode=mode)
    seq, pos = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    return _head(x[seq, pos], p["norm"], p["head"],
                 eps=cfg["rms_norm_eps"], mode=mode)
