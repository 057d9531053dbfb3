"""GPT-2 family — the flagship benchmark model (BASELINE.md config 5:
GPT-2 1.5B + megatron scaled_masked_softmax + fused MHA).

Built entirely from the framework's fused components: FusedLayerNorm (Pallas),
flash attention (Pallas, = fused MHA + causal megatron softmax),
dense_gelu_dense (fused MLP), fused xentropy loss. bf16-first compute with
fp32 params by default (amp O1 shape).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.normalization.fused_layer_norm import FusedLayerNorm
from apex_tpu.ops.pallas.flash_attention import flash_attention
from apex_tpu.transformer.fused_dense import dense_gelu_dense


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    compute_dtype: Any = jnp.bfloat16

    @classmethod
    def tiny(cls):
        return cls(vocab_size=1024, n_positions=256, n_embd=256, n_layer=2,
                   n_head=4)

    @classmethod
    def small(cls):
        return cls()

    @classmethod
    def xl(cls):  # GPT-2 1.5B
        return cls(n_embd=1600, n_layer=48, n_head=25)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = c.n_head
        d = c.n_embd // h
        b, s, e = x.shape

        y = FusedLayerNorm(e, name="ln_1")(x)
        qkv = nn.Dense(3 * e, dtype=c.compute_dtype, name="attn_qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(b, s, h, d).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        # ragged lengths are padded inside the kernel — no unfused fallback
        o = flash_attention(q, k, v, True)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
        x = x + nn.Dense(e, dtype=c.compute_dtype, name="attn_out")(o)

        y = FusedLayerNorm(e, name="ln_2")(x)
        w1 = self.param("mlp_fc_w", nn.initializers.normal(0.02),
                        (4 * e, e), jnp.float32)
        b1 = self.param("mlp_fc_b", nn.initializers.zeros, (4 * e,),
                        jnp.float32)
        w2 = self.param("mlp_proj_w", nn.initializers.normal(0.02),
                        (e, 4 * e), jnp.float32)
        b2 = self.param("mlp_proj_b", nn.initializers.zeros, (e,),
                        jnp.float32)
        x = x + dense_gelu_dense(y, w1.astype(c.compute_dtype),
                                 b1.astype(c.compute_dtype),
                                 w2.astype(c.compute_dtype),
                                 b2.astype(c.compute_dtype))
        return x


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False,
                 position_offset=0):
        """``position_offset`` shifts the learned positional embeddings:
        token column ``j`` reads ``wpe[position_offset + j]`` — the same
        offset contract as ``transformer.rope.fused_rope`` so a suffix of
        a sequence (a serving decode window) sees the rotations/embeddings
        of its absolute positions. Accepts a python int or a traced int32
        scalar; caller guarantees ``position_offset + s <= n_positions``.
        """
        c = self.cfg
        b, s = tokens.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (c.vocab_size, c.n_embd), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (c.n_positions, c.n_embd), jnp.float32)
        from apex_tpu.transformer.rope import _offset_slice

        pos = _offset_slice(wpe, position_offset, s)
        x = wte[tokens].astype(c.compute_dtype) \
            + pos[None].astype(c.compute_dtype)
        for i in range(c.n_layer):
            x = Block(c, name=f"h_{i}")(x)
        x = FusedLayerNorm(c.n_embd, name="ln_f")(x)
        if return_hidden:
            # pre-logits hidden states, for the chunked-vocab fused head
            # (transformer.linear_cross_entropy) — the logits matmul is
            # then fused into the loss and never materialized
            return x
        logits = jax.lax.dot_general(
            x, wte.astype(c.compute_dtype), (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits


def lm_loss(model: GPT2, params, tokens):
    """Next-token xentropy over the fused loss (contrib.xentropy)."""
    logits = model.apply(params, tokens)
    loss = softmax_cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
    return jnp.mean(loss)


# --------------------------------------------------------------- serving
#
# The cache-aware forward used by apex_tpu.serve, written over ROWS: one
# token per slot per call (decode, the verify scan's body: [num_slots]
# rows) or one chunk of a prompt per slot (the batched prefill:
# [num_slots * bucket] rows through the same dense code, every weight read
# once a call). Learned positional embeddings are indexed by each row's
# absolute position. It is a pure function over the SAME param pytree
# GPT2.init/flax produce (no separate serving weights), with every array
# shape fixed by the engine's geometry — the serve engine's
# one-compile-per-program invariant rests on that. Only the cache append
# and the attention differ between the two forms (_append_and_attend):
# at one query row per slot the MXU work is a [1, L] matvec, so decode
# attention is the chunked-softmax XLA path in serve.attention; a chunk
# attends over its own fresh K/V in one plain causal [T, T] block a slot
# and head (serve.attention.chunk_attention; at GPT-2 XL's bucket 64 the
# scores are [4, 25, 64, 64] and the plain form beats a kernel launch).


def _affine_layer_norm(x, scale, bias, eps: float = 1e-5):
    """Row LayerNorm for the decode path: the repo's jnp reference LN
    (the same normalization FusedLayerNorm computes — at num_slots rows
    there is no tile to amortize a Pallas launch over)."""
    from apex_tpu.normalization.fused_layer_norm import manual_layer_norm

    return manual_layer_norm(x, scale, bias, (x.shape[-1],), eps)


def _chunk_geometry(cache, heads, head_dim, dtype, block_k, pos, write_mask,
                    tp=1):
    """Once a forward: the resolved key chunk ``block_k`` and, for the
    one-token form, the trip count of every layer's decode attention
    (:func:`~apex_tpu.serve.attention.attended_chunks`: the chunks the
    longest slot the step writes can reach; a chunk a slot has none, its
    cached head sets its own)."""
    from apex_tpu.serve.attention import attended_chunks, resolve_block_k

    bk = resolve_block_k(cache.max_len, heads, head_dim, dtype, block_k,
                         page_size=cache.page_size, tp_shards=tp)
    if pos.ndim == 2:
        return bk, None
    return bk, attended_chunks(pos, write_mask, bk, cache.max_len // bk)


def _append_and_attend(cache, layer, q, k, v, pos, write_mask, block_k,
                       trips, kv_quant):
    """One layer's cache append and attention, for either form of the
    forward. ``q``/``k``/``v``: ``[rows, heads, head_dim]`` with
    ``rows = pos.size``; ``pos``/``write_mask``:
    ``[num_slots]`` (one token a slot: appended at ``pos``, attending
    over cached ``0..pos`` in ``trips`` chunks of ``block_k``) or
    ``[num_slots, T]`` (a chunk a slot). Both read the STACKED pool at
    ``layer`` inside their loop: no layer's pool is sliced out here.
    Returns ``(o [rows, heads, head_dim], cache)``. The cache's head
    axis is allocated in whole tiles (``kv_cache.padded_heads``): the
    queries are padded with zero heads to meet it, attention runs over
    all of them (the same tiles either way), and the padding's outputs
    are dropped."""
    from apex_tpu.serve.attention import chunk_attention, paged_attention
    from apex_tpu.serve.kv_cache import (pad_heads, paged_write_token,
                                         write_rows)

    heads = q.shape[-2]
    q = pad_heads(q, cache.k.shape[-2])
    if pos.ndim == 2:
        rows = pos.shape + k.shape[1:]
        cache, k_read, v_read = write_rows(
            cache, layer, k.reshape(rows), v.reshape(rows), pos,
            write_mask, codec=kv_quant)
        o = chunk_attention(q.reshape(pos.shape + q.shape[1:]), k_read,
                            v_read, cache, layer, pos[:, 0], block_k=block_k)
        return o.reshape(q.shape)[:, :heads], cache
    cache = paged_write_token(cache, layer, k, v, pos, write_mask,
                              codec=kv_quant)
    o = paged_attention(q, cache, layer, pos, trips, block_k=block_k)
    return o[:, :heads], cache


def _final_logits(x, p, dt, shape, logits_at):
    """Final norm and the logits product over the rows that are asked
    for: every row (``[*shape, vocab]``), or in the chunk form row
    ``logits_at[b]`` of each slot's chunk (``[num_slots, vocab]``)."""
    x = x.reshape(shape + x.shape[-1:])
    if logits_at is not None:
        x = x[jnp.arange(shape[0]), logits_at.astype(jnp.int32)]
    x = _affine_layer_norm(x, p["ln_f"]["weight"], p["ln_f"]["bias"])
    return jax.lax.dot_general(
        x, p["wte"].astype(dt), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def gpt2_token_forward(cfg: GPT2Config, params, cache, tokens, positions,
                       write_mask, logits_at=None, *, block_k=None,
                       kv_quant=None, final_scope: str = "sampling"):
    """One decode token per slot — or one chunk of a prompt per slot —
    through GPT-2 with the serving KV cache.

    ``tokens``/``positions``/``write_mask``: ``[num_slots]`` (int32, int32,
    bool). Each masked slot's token K/V is appended to the cache at
    ``positions[slot]`` and the slot attends over cached positions
    ``0..positions[slot]``; masked-off slots compute garbage that is
    discarded and write nothing. Returns ``(logits [num_slots, vocab]
    fp32, cache)``. ``block_k`` is the decode-attention KV chunk
    (autotuned via ``apex_tpu.tune`` when None). ``final_scope`` names
    the phase of the final LN + logits projection for the cost ledger:
    decode/prefill feed the sampler ("sampling"); the speculative
    verify step passes "verify" so its per-position logits work — the
    verify step's own cost — is attributed to the verify phase and
    phase reconciliation stays exact (monitor/costs.py).

    ``cache`` is a :class:`~apex_tpu.serve.kv_cache.PagedKVCache`;
    where its pages lie does not enter the arithmetic, so two page
    sizes are bit-identical in fp32 on identical resident tokens at
    equal ``block_k`` (the chunk size orders the softmax partial sums).

    ``kv_quant`` (``"int8"``/``"mxfp8"``, static trace-time string) arms
    the block-scale KV codec: each appended token's K/V is encoded with
    one fp32 scale per head inside the write, and attention dequantizes
    per streamed chunk from the cache's scale planes. Encode is
    deterministic, so the same token at the same position gets the same
    codes and scales whichever program appends it.

    **Chunk form (the batched prefill).** With ``tokens``/``positions``/
    ``write_mask`` of shape ``[num_slots, T]`` the ``T`` consecutive
    positions of every slot go through the layers TOGETHER: the dense
    parts run once over ``num_slots * T`` rows (every weight is read once
    a call), the chunk's K/V take one masked scatter
    (:func:`~apex_tpu.serve.kv_cache.write_rows`), and a row attends
    causally over its slot's chunk and over the cached positions before
    ``positions[:, 0]`` (:func:`~apex_tpu.serve.attention.
    chunk_attention`). ``logits_at`` (``[num_slots]`` int32 chunk index)
    picks the ONE row a slot whose logits are computed and returned as
    ``[num_slots, vocab]``; ``None`` returns every row's, ``[num_slots,
    T, vocab]``. A batched product and a one-row product may round
    differently in the last place, so the chunk form matches the
    one-token form to a tolerance, not to the bit (docs/serving.md).
    """
    c = cfg
    dt = c.compute_dtype
    h, d = c.n_head, c.n_embd // c.n_head
    p = params["params"] if "params" in params else params
    pos = positions.astype(jnp.int32)
    block_k, trips = _chunk_geometry(cache, h, d, dt, block_k, pos,
                                     write_mask)

    x = (p["wte"][tokens].astype(dt)
         + p["wpe"][jnp.clip(pos, 0, c.n_positions - 1)].astype(dt)
         ).reshape(-1, c.n_embd)
    # phase markers: trace-safe jax.named_scope only (scope names ride
    # the MLIR loc(...) metadata — monitor/costs.py attributes the cost
    # ledger per phase on them; no traced effect, APX001-quiet). Inside
    # "attention", "kv_write" (serve/kv_cache.py) and "attn_proj" name
    # the cache append and the output projection for a device trace's
    # reader; the ledger knows neither name, so both stay "attention"
    # there
    for i in range(c.n_layer):
        blk = p[f"h_{i}"]
        with jax.named_scope("ln_qkv"):
            y = _affine_layer_norm(x, blk["ln_1"]["weight"],
                                   blk["ln_1"]["bias"])
            qkv = (y.astype(dt) @ blk["attn_qkv"]["kernel"].astype(dt)
                   + blk["attn_qkv"]["bias"].astype(dt))
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(-1, h, d)
            k = k.reshape(-1, h, d)
            v = v.reshape(-1, h, d)
        with jax.named_scope("attention"):
            o, cache = _append_and_attend(cache, i, q, k, v, pos,
                                          write_mask, block_k, trips,
                                          kv_quant)
            with jax.named_scope("attn_proj"):
                o = o.reshape(-1, c.n_embd)
                x = x + (o.astype(dt)
                         @ blk["attn_out"]["kernel"].astype(dt)
                         + blk["attn_out"]["bias"].astype(dt))
        with jax.named_scope("mlp"):
            y = _affine_layer_norm(x, blk["ln_2"]["weight"],
                                   blk["ln_2"]["bias"])
            x = x + dense_gelu_dense(y, blk["mlp_fc_w"].astype(dt),
                                     blk["mlp_fc_b"].astype(dt),
                                     blk["mlp_proj_w"].astype(dt),
                                     blk["mlp_proj_b"].astype(dt))
    with jax.named_scope(final_scope):
        logits = _final_logits(x, p, dt, pos.shape, logits_at)
    return logits, cache


def _psum_halves_into(part, resid, bias, axis_name, ln=None):
    """TokenWeave overlap seam: one logical all-reduce of ``part``
    ``[num_slots, e]`` split into two slot-half psums, each half's
    residual add (+ optional row layer-norm) interleaved so the OTHER
    half's collective can fly behind it under XLA's async-collective
    scheduling. Row-wise ops make the halved compute bit-identical to
    the full-width spelling, and an elementwise psum split along rows is
    bit-identical to the unsplit psum — so "overlap" differs from plain
    Megatron row-parallel only in schedule, never in value. Returns
    ``(x, ln_x | None)``."""
    half = part.shape[0] // 2
    with jax.named_scope("collective"):
        r1 = jax.lax.psum(part[:half], axis_name)
    x1 = resid[:half] + r1 + bias
    y1 = ln(x1) if ln is not None else None
    with jax.named_scope("collective"):
        r2 = jax.lax.psum(part[half:], axis_name)
    x2 = resid[half:] + r2 + bias
    y2 = ln(x2) if ln is not None else None
    x = jnp.concatenate([x1, x2], axis=0)
    return x, (jnp.concatenate([y1, y2], axis=0) if ln is not None
               else None)


def gpt2_token_forward_tp(cfg: GPT2Config, tp: int, sync: str, params,
                          cache, tokens, positions, write_mask,
                          logits_at=None, *, block_k=None, kv_quant=None,
                          axis_name: str = "tp",
                          final_scope: str = "sampling"):
    """The PER-RANK body of the tensor-parallel token forward, in either
    of :func:`gpt2_token_forward`'s forms (one token or one chunk a slot:
    the gathers run over heads and hidden columns and the psums over
    rows, so neither cares how many rows there are) —
    run under ``shard_map`` over the serving mesh (``apex_tpu.serve.tp``
    owns the param layout and specs). Heads are sharded: this rank sees
    ``n_head // tp`` heads' qkv columns, its slice of the KV cache's
    head axis, and the replicated residual stream.

    The rank-local arithmetic is :func:`gpt2_token_forward`'s, op for
    op, on column slices (per-column matmul determinism is what the
    bit-exactness claim rides on); the modes differ ONLY in how ranks
    combine:

    - ``sync="exact"``: ``all_gather`` (concatenation — no cross-rank
      float add) of the attention heads and the MLP hidden slices, then
      the full projection matmuls replicated. Bit-identical in fp32 to
      the single-chip forward at equal ``block_k``.
    - ``sync="overlap"``: Megatron row-parallel projections; each of the
      two per-layer all-reduces is split into two slot-half psums
      interleaved with the adjacent residual/norm compute (TokenWeave).
      ±ulp vs exact (partial sums reorder float adds).
    - ``sync="relaxed"``: the post-attention all-reduce is deferred —
      ``ln_2``/MLP run on the rank's partially-synchronized residual and
      ONE combined psum per layer lands attention + MLP together
      (partially-synchronized activations; opt-in approximation).

    Every mode re-synchronizes the residual stream by the end of each
    layer, so ``ln_f`` and the logits matmul run replicated and the
    returned logits are identical on every rank (the caller's
    ``out_specs`` treat them as replicated).
    """
    c = cfg
    dt = c.compute_dtype
    h_loc = c.n_head // tp
    d = c.n_embd // c.n_head
    p = params
    pos = positions.astype(jnp.int32)
    # positions and the mask are replicated, so every rank runs the same
    # trips
    block_k, trips = _chunk_geometry(cache, h_loc, d, dt, block_k, pos,
                                     write_mask, tp)

    x = (p["wte"][tokens].astype(dt)
         + p["wpe"][jnp.clip(pos, 0, c.n_positions - 1)].astype(dt)
         ).reshape(-1, c.n_embd)
    # phase markers mirror gpt2_token_forward's; collective sites carry
    # their own nested "collective" scope (innermost scope wins in the
    # ledger walk, so a gather inside attention attributes to collective)
    for i in range(c.n_layer):
        blk = p[f"h_{i}"]
        with jax.named_scope("ln_qkv"):
            y = _affine_layer_norm(x, blk["ln_1"]["weight"],
                                   blk["ln_1"]["bias"])
            # local heads' q/k/v: the permuted kernel slice is exactly
            # this rank's columns of the full projection, so each output
            # column's dot product is the single-chip one
            qkv = (y.astype(dt) @ blk["attn_qkv"]["kernel"].astype(dt)
                   + blk["attn_qkv"]["bias"].astype(dt))
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(-1, h_loc, d)
            k = k.reshape(-1, h_loc, d)
            v = v.reshape(-1, h_loc, d)
        with jax.named_scope("attention"):
            # per-head encode is rank-local (a head's scale reduces only
            # over that head's head_dim), so this rank's shard of the
            # quantized pool is bit-identical to the single-chip
            # engine's same head slice
            o, cache = _append_and_attend(cache, i, q, k, v, pos,
                                          write_mask, block_k, trips,
                                          kv_quant)
            out_b = blk["attn_out"]["bias"].astype(dt)
            if sync == "exact":
                # concatenate the heads across ranks, then the FULL
                # output projection replicated: no float add crosses a
                # rank
                with jax.named_scope("collective"):
                    o_full = jax.lax.all_gather(o, axis_name, axis=1,
                                                tiled=True)
                with jax.named_scope("attn_proj"):
                    o_full = o_full.reshape(-1, c.n_embd)
                    x = x + (o_full.astype(dt)
                             @ blk["attn_out"]["kernel"].astype(dt) + out_b)
            else:
                # row-parallel output projection: this rank's heads hit
                # its rows of the kernel — a PARTIAL [num_slots, e] sum
                with jax.named_scope("attn_proj"):
                    attn_part = (o.reshape(-1, h_loc * d).astype(dt)
                                 @ blk["attn_out"]["kernel"].astype(dt))
        with jax.named_scope("mlp"):
            if sync == "exact":
                y = _affine_layer_norm(x, blk["ln_2"]["weight"],
                                       blk["ln_2"]["bias"])
            elif sync == "overlap":
                x, y = _psum_halves_into(
                    attn_part, x, out_b, axis_name,
                    ln=lambda v_: _affine_layer_norm(
                        v_, blk["ln_2"]["weight"], blk["ln_2"]["bias"]))
            else:  # relaxed: defer the attention psum across the norm
                y = _affine_layer_norm(x + attn_part + out_b,
                                       blk["ln_2"]["weight"],
                                       blk["ln_2"]["bias"])
            # MLP, column-parallel fc (this rank's 4e/tp rows),
            # mirroring fused_dense.dense_gelu_dense's primal ops exactly
            h = jax.lax.dot_general(
                y.astype(dt), blk["mlp_fc_w"].astype(dt),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            h = h + blk["mlp_fc_b"].astype(jnp.float32)
            a = jax.nn.gelu(h, approximate=False)
            proj_b = blk["mlp_proj_b"].astype(jnp.float32).astype(dt)
            if sync == "exact":
                with jax.named_scope("collective"):
                    a_full = jax.lax.all_gather(a.astype(dt), axis_name,
                                                axis=1, tiled=True)
                m = jax.lax.dot_general(
                    a_full, blk["mlp_proj_w"].astype(dt),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                x = x + (m
                         + blk["mlp_proj_b"].astype(jnp.float32)
                         ).astype(dt)
            else:
                mlp_part = jax.lax.dot_general(
                    a.astype(dt), blk["mlp_proj_w"].astype(dt),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(dt)
                if sync == "overlap":
                    x, _ = _psum_halves_into(mlp_part, x, proj_b,
                                             axis_name)
                else:
                    # relaxed: ONE all-reduce lands the deferred
                    # attention partial and the MLP partial together;
                    # the residual stream is fully synchronized again at
                    # layer exit
                    x, _ = _psum_halves_into(attn_part + mlp_part, x,
                                             out_b + proj_b, axis_name)
    with jax.named_scope(final_scope):
        logits = _final_logits(x, p, dt, pos.shape, logits_at)
    return logits, cache
