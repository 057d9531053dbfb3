"""The model seam: what a model gives ``serve.Engine``.

The engine keeps the two compiled programs (``decode_fn``, ``prefill_fn``),
the sampler, the page planner, the scheduler's contract and the spans. A
model gives it, through one object:

- ``name``, ``cfg``, ``max_positions``, ``n_layer``, ``vocab_size``,
  ``compute_dtype``, and ``workload()``: what a cost ledger says of it;
- ``serving_weights(params)``: the tree the engine holds and hands its
  programs, in the form the forward consumes it, made once at build
  (GPT-2's matrices cast to ``compute_dtype``; a model whose weights are
  made in ``compute_dtype`` returns its tree itself);
- ``cache_planes``: the planes of its cache, the leading axis of every
  pool array and of a migrated page's payload. ``n_layer`` counts layers
  of WEIGHTS; the two are equal unless the model runs its layers more
  than once a token, each pass with planes of its own (``ouro``: more
  planes than layers), or keeps rows a token in only some of its layers
  (``ling_hybrid``: fewer, one a latent-attention layer);
- ``refuse(config)``: a build-time ``ValueError`` for every engine
  mode the model has no mechanism for, naming the mechanism;
- ``block_k(max_len, page_size, config, tp)``: the decode-attention
  chunk, resolved once at build (``page_size`` is the engine's resolved
  one: ``max_len`` where the config names none);
- ``attended_chunks(lengths, active, block_k, key_chunks)``: of a
  slot's ``key_chunks`` chunks of ``block_k`` keys, how many the decode
  attention will visit in a step over these host lengths and this active
  mask (the span ``apex.decode_step`` carries both);
- ``init_cache(num_slots, max_len, page_size, num_pages, kv_quant, tp)``:
  its cache pytree, a paged pool with ``lengths`` and ``page_table`` as
  ``serve/kv_cache.py`` and ``serve/paging.py`` expect them, allocated
  for ``tp`` ranks. A cache may hold, beside its pages, a fixed-size
  state a slot for layers that keep no rows a token
  (``kv_cache.HybridCache``): the engine's row view then carries the
  slots' ids, every call that returns the cache updates the state in
  place, and admission starts a slot from a zero state inside the
  prefill program (eviction moves ``lengths`` alone);
- ``forward(weights, cache, tokens, positions, mask, logits_at=None, *,
  block_k, kv_quant, final_scope)``: its token forward in the two shapes
  (``[slots]``: decode and the verify scan's body; ``[slots, T]`` with
  ``logits_at``: prefill). Returns ``(logits, cache)``, or ``(logits,
  cache, counters)`` with ``counters`` a small int32 array the programs
  hand back beside their results; a model that returns them also gives
  ``counters_span`` and ``call_counters(counters, real_rows, slots,
  prefill)``: the name and the attributes of the span
  ``apex.<call>.<counters_span>`` the engine leaves them on (``routing``,
  ``loop``), for a call over ``real_rows`` real positions of ``slots``
  slots (a decode step's two are equal), ``prefill`` or decode.

``serving_model(cfg)`` finds the object: a ``GPT2Config`` gets
:class:`GPT2Serving`; any other config provides ``cfg.serving_model()``.
The tensor-parallel per-rank forward is not behind the seam: it is
GPT-2's (``serve/tp.py``), and a model without one refuses ``tp > 1``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import numpy as np

from apex_tpu.models.gpt2 import GPT2Config, gpt2_token_forward
from apex_tpu.serve import kv_cache
from apex_tpu.serve.attention import attended_chunks, resolve_block_k


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    """Every leaf to ``dtype``, in one jitted call."""
    return [x.astype(dtype) for x in leaves]


class GPT2Serving:
    """GPT-2 of any size: every engine mode."""

    name = "gpt2"

    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg
        self.max_positions = cfg.n_positions
        self.n_layer, self.vocab_size = cfg.n_layer, cfg.vocab_size
        self.cache_planes = cfg.n_layer
        self.compute_dtype = cfg.compute_dtype
        self.heads, self.head_dim = cfg.n_head, cfg.n_embd // cfg.n_head

    def refuse(self, config) -> None:
        return None

    def serving_weights(self, params):
        """``params`` with every matrix (the 2-D leaves: ``wte``,
        ``wpe``, each block's ``attn_qkv`` / ``attn_out`` kernels,
        ``mlp_fc_w``, ``mlp_proj_w``) in ``compute_dtype``: the forward
        only ever reads a matrix as ``.astype(compute_dtype)``, so the
        products take the same bits, and a call streams the weights at
        the width it computes in. Biases and layer-norm leaves keep their
        dtype (the tensor-parallel MLP adds its biases in float32). A
        leaf already in ``compute_dtype`` is kept, not copied; where none
        needs the cast (a float32 engine) ``params`` itself is
        returned."""
        dt = np.dtype(self.compute_dtype)
        leaves, tree = jax.tree_util.tree_flatten(params)
        todo = [i for i, x in enumerate(leaves)
                if np.ndim(x) == 2 and x.dtype != dt]
        if not todo:
            return params
        for i, x in zip(todo, _cast([leaves[i] for i in todo], dt)):
            leaves[i] = x
        return jax.tree_util.tree_unflatten(tree, leaves)

    def block_k(self, max_len: int, page_size: int, config, tp: int) -> int:
        return resolve_block_k(max_len, self.heads // tp, self.head_dim,
                               self.compute_dtype, config.block_k,
                               page_size=page_size, tp_shards=tp)

    def attended_chunks(self, lengths, active, block_k: int,
                        key_chunks: int) -> int:
        # the decode attention's loop runs to the longest active slot
        return int(attended_chunks(lengths, active, block_k, key_chunks,
                                   xp=np))

    def init_cache(self, num_slots, max_len, page_size, num_pages, kv_quant,
                   tp=1):
        return kv_cache.init_paged_cache(
            self.cache_planes, num_slots, max_len, page_size, num_pages,
            self.heads, self.head_dim, self.compute_dtype, kv_quant=kv_quant,
            shards=tp)

    def forward(self, weights, cache, *data, **kw):
        return gpt2_token_forward(self.cfg, weights, cache, *data, **kw)

    def workload(self) -> Dict[str, Any]:
        return {"n_layer": int(self.cfg.n_layer),
                "n_embd": int(self.cfg.n_embd),
                "n_head": int(self.cfg.n_head),
                "vocab_size": int(self.cfg.vocab_size)}


class DeepseekV3Serving:
    """``deepseek_v3`` (``models/deepseek_v3.py``) as one rank of an
    expert-parallel deployment: the paged latent cache, one chip."""

    name = "deepseek_v3"
    counters_span = "routing"

    def __init__(self, cfg):
        self.cfg = cfg
        self.max_positions = cfg.max_position_embeddings
        self.n_layer, self.vocab_size = cfg.num_hidden_layers, cfg.vocab
        self.cache_planes = cfg.num_hidden_layers
        self.compute_dtype = cfg.compute_dtype
        self.heads, self.head_dim = (cfg.num_attention_heads,
                                     cfg.latent_width)

    def refuse(self, config) -> None:
        missing = []
        if config.tp > 1:
            missing.append(f"tp={config.tp}: there is no per-rank forward "
                           f"that shards MLA's heads and no head axis in "
                           f"the latent cache to shard (serve/tp.py is "
                           f"GPT-2's)")
        if config.spec_draft_len:
            missing.append(f"spec_draft_len={config.spec_draft_len}: the "
                           f"verify scan's acceptance oracle is unproven "
                           f"for a routed model, whose logits move with "
                           f"the batch's rounding at a router near-tie")
        if config.kv_quant is not None:
            missing.append(f"kv_quant={config.kv_quant!r}: the block-scale "
                           f"codec scales one (token, head) vector, and a "
                           f"latent row has no head axis")
        if missing:
            raise ValueError(
                "deepseek_v3 is not served with " + "; ".join(missing))

    def serving_weights(self, params):
        """``params`` itself: the weights are made in
        ``compute_dtype``."""
        return params

    def block_k(self, max_len: int, page_size: int, config, tp: int) -> int:
        # latent attention reads whole pages; the knob is accepted where
        # it names one
        if config.block_k not in (None, page_size):
            raise ValueError(
                f"block_k={config.block_k}: deepseek_v3's latent attention "
                f"reads a page at a time (page_size={page_size})")
        return int(page_size)

    def attended_chunks(self, lengths, active, block_k: int,
                        key_chunks: int) -> int:
        # latent_decode_attention gathers the slot's whole key axis
        return key_chunks

    def init_cache(self, num_slots, max_len, page_size, num_pages, kv_quant,
                   tp=1):
        return kv_cache.init_paged_latent_cache(
            self.cache_planes, num_slots, max_len, page_size, num_pages,
            self.cfg.latent_width, self.compute_dtype)

    def forward(self, weights, cache, *data, block_k=None, kv_quant=None,
                final_scope="sampling"):
        from apex_tpu.models.deepseek_v3 import deepseek_v3_token_forward

        return deepseek_v3_token_forward(self.cfg, weights, cache, *data,
                                         final_scope=final_scope)

    def call_counters(self, counters, real_rows: int, slots: int,
                      prefill: bool) -> Dict[str, int]:
        """What an engine call's span says of its routing: the two
        counters the program returned, and what they are shares of."""
        c = self.cfg
        layers = c.num_hidden_layers - c.first_k_dense_replace
        picks_here, experts_hit = (int(v) for v in counters)
        return {"picks_here": picks_here, "experts_hit": experts_hit,
                "experts_held": c.held * layers,
                "picks": real_rows * c.num_experts_per_tok * layers}

    def workload(self) -> Dict[str, Any]:
        c = self.cfg
        return {"n_layer": int(c.num_hidden_layers),
                "n_embd": int(c.hidden_size),
                "n_head": int(c.num_attention_heads),
                "vocab_size": int(c.vocab),
                "experts_held": int(c.held),
                "n_routed_experts": int(c.n_routed_experts)}


class OuroServing(GPT2Serving):
    """``ouro`` (``models/ouro.py``), whole on one chip: GPT-2's paged
    key-value pool, chunk and trip count, with a plane for every layer of
    every pass."""

    name = "ouro"
    counters_span = "loop"

    def __init__(self, cfg):
        self.cfg = cfg
        self.max_positions = cfg.max_position_embeddings
        self.n_layer, self.vocab_size = cfg.num_hidden_layers, cfg.vocab_size
        self.cache_planes = cfg.cache_planes
        self.compute_dtype = cfg.compute_dtype
        self.heads, self.head_dim = cfg.num_attention_heads, cfg.head_dim

    def refuse(self, config) -> None:
        missing = []
        if config.tp > 1:
            missing.append(f"tp={config.tp}: there is no per-rank forward "
                           f"for it (serve/tp.py is GPT-2's)")
        if config.spec_draft_len:
            missing.append(f"spec_draft_len={config.spec_draft_len}: the "
                           f"verify scan's rollback of rejected rows is "
                           f"unproven over planes that every pass of a "
                           f"later token reads")
        if config.kv_quant is not None:
            missing.append(f"kv_quant={config.kv_quant!r}: the codec's "
                           f"tolerance is calibrated for one trip through "
                           f"the layers, and no test holds it over "
                           f"{self.cfg.total_ut_steps} passes that each "
                           f"read what the one before wrote")
        if missing:
            raise ValueError("ouro is not served with " + "; ".join(missing))

    serving_weights = DeepseekV3Serving.serving_weights

    def forward(self, weights, cache, *data, **kw):
        from apex_tpu.models.ouro import ouro_token_forward

        return ouro_token_forward(self.cfg, weights, cache, *data, **kw)

    def call_counters(self, counters, real_rows: int, slots: int,
                      prefill: bool) -> Dict[str, int]:
        """What an engine call's span says of its pass loop: the two
        counters the program returned, beside the rows they are over and
        the planes the call wrote."""
        passes, early_exits = (int(v) for v in counters)
        return {"passes": passes, "rows": real_rows,
                "planes": self.cache_planes, "early_exits": early_exits}

    def workload(self) -> Dict[str, Any]:
        c = self.cfg
        return {"n_layer": int(c.num_hidden_layers),
                "n_embd": int(c.hidden_size),
                "n_head": int(c.num_attention_heads),
                "vocab_size": int(c.vocab_size),
                "total_ut_steps": int(c.total_ut_steps),
                "cache_planes": int(c.cache_planes)}


class LingHybridServing(DeepseekV3Serving):
    """``ling_hybrid`` (``models/ling_hybrid.py``) as one rank of an
    expert-parallel deployment, one chip: latent pages for its
    latent-attention layers alone, and a recurrent state a slot for every
    other layer (``kv_cache.HybridCache``)."""

    name = "ling_hybrid"

    def __init__(self, cfg):
        super().__init__(cfg)
        self.cache_planes = cfg.mla_layers

    def refuse(self, config) -> None:
        missing = []
        if config.prefix_cache:
            missing.append("prefix_cache=True: a shared page is of no use "
                           "without the recurrent state at its boundary, "
                           "and no state is kept but a slot's newest")
        if config.tp > 1:
            missing.append(f"tp={config.tp}: there is no per-rank forward "
                           f"that shards the recurrent state's heads "
                           f"(serve/tp.py is GPT-2's)")
        if config.spec_draft_len:
            missing.append(f"spec_draft_len={config.spec_draft_len}: a "
                           f"rejected draft is rolled back by set_lengths, "
                           f"and a token folded into a recurrent state "
                           f"cannot be taken out of it")
        if config.kv_quant is not None:
            missing.append(f"kv_quant={config.kv_quant!r}: the block-scale "
                           f"codec scales one (token, head) vector; a "
                           f"latent row has no head axis and a recurrent "
                           f"state is no token")
        if missing:
            raise ValueError(
                "ling_hybrid is not served with " + "; ".join(missing))

    def init_cache(self, num_slots, max_len, page_size, num_pages, kv_quant,
                   tp=1):
        c = self.cfg
        return kv_cache.init_hybrid_cache(
            self.cache_planes, c.kda_layers, num_slots, max_len, page_size,
            num_pages, c.latent_width, c.num_attention_heads, c.head_dim,
            c.head_dim, (c.short_conv_kernel_size - 1) * c.conv_channels,
            self.compute_dtype)

    def forward(self, weights, cache, *data, block_k=None, kv_quant=None,
                final_scope="sampling"):
        from apex_tpu.models.ling_hybrid import ling_hybrid_token_forward

        return ling_hybrid_token_forward(self.cfg, weights, cache, *data,
                                         final_scope=final_scope)

    def call_counters(self, counters, real_rows: int, slots: int,
                      prefill: bool) -> Dict[str, int]:
        """``DeepseekV3Serving``'s routing counters, and the recurrent
        state the call moved: ``state_slots`` slots in each of the
        recurrent layers, and the bytes of state and convolution tail it
        read and wrote (a decode step reads and writes each once; a
        prefill call starts from zero and only writes)."""
        c = self.cfg
        return dict(
            super().call_counters(counters, real_rows, slots, prefill),
            state_slots=slots,
            state_bytes=(1 if prefill else 2) * slots * c.kda_layers
            * c.state_bytes_per_slot)

    def workload(self) -> Dict[str, Any]:
        c = self.cfg
        return {"n_layer": int(c.num_hidden_layers),
                "n_embd": int(c.hidden_size),
                "n_head": int(c.num_attention_heads),
                "vocab_size": int(c.vocab),
                "experts_held": int(c.held),
                "num_experts": int(c.num_experts),
                "kda_layers": int(c.kda_layers),
                "mla_layers": int(c.mla_layers)}


def serving_model(model_cfg):
    """The seam object for a model config (see the module docstring)."""
    if isinstance(model_cfg, GPT2Config):
        return GPT2Serving(model_cfg)
    if hasattr(model_cfg, "serving_model"):
        return model_cfg.serving_model()
    raise TypeError(
        f"{type(model_cfg).__name__} is no servable model config: it is "
        f"neither a GPT2Config nor provides serving_model()")


__all__ = ["GPT2Serving", "DeepseekV3Serving", "OuroServing",
           "LingHybridServing", "serving_model"]
