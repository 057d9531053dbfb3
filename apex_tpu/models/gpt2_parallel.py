"""Manually-parallel GPT-2 — the multi-chip training step: DP × TP × SP over a
``jax.sharding.Mesh`` via ``shard_map``.

Composition (the 'How to Scale Your Model' recipe, hand-annotated):
- **DP**: batch sharded over ``dp``; grads of every param psum over dp
  (the bucketed-psum DDP capability, apex_tpu.parallel.ddp).
- **TP**: Megatron column/row parallel linears over ``tp`` — q/k/v projections
  column-sharded (heads split), attention output row-sharded with a psum;
  MLP fc column-sharded, proj row-sharded with a psum. The wgrad-accum
  primitive semantics (fp32 grads for low-precision params) ride on
  preferred_element_type.
- **SP**: sequence sharded over ``sp``; attention runs the ring
  (apex_tpu.parallel.ring_attention) so K/V shards rotate over ICI while Q
  stays resident; positional embeddings sharded with the sequence.

Round 2 composes the remaining two axes (VERDICT item 5):
- **PP**: ``make_train_step_pp`` runs the block stack through the 1F1B
  pipeline (apex_tpu.parallel.pipeline.pipeline_train_1f1b) over the ``pp``
  axis — blocks stacked with a leading layer dim sharded over pp, embeddings
  and final-LN shared (replicated over pp, grads psum'd), the last stage
  computing the loss so cotangents enter the reverse pipeline on-device.
- **EP**: ``moe_experts > 0`` replaces the dense FFN with the
  expert-parallel MoE FFN (apex_tpu.parallel.moe.moe_ffn_ep) over the ``ep``
  axis, expert weights sharded (pp, ep, ...).

All five axes compose in one mesh (dp, pp, tp, sp, ep); degenerate (size-1)
axes cost nothing, so one train step covers every combination.

All params/optimizer state live in fp32; compute in bf16 (amp O1 shape);
optimizer is the fused Adam tree update (optimizers/functional.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
from apex_tpu.normalization.fused_layer_norm import (fused_layer_norm_affine)
from apex_tpu.optimizers.functional import adam_update
from apex_tpu.parallel.ring_attention import ring_self_attention
from apex_tpu.parallel.ulysses import ulysses_self_attention

_f32 = jnp.float32


def choose_mesh_shape(n: int) -> Tuple[int, int, int]:
    """Factor n devices into (dp, tp, sp), preferring dp ≥ tp ≥ sp."""
    dp = tp = sp = 1
    for axis in ("dp", "tp", "sp", "dp", "tp", "sp"):
        if n % 2 != 0 or n == 1:
            break
        n //= 2
        if axis == "dp":
            dp *= 2
        elif axis == "tp":
            tp *= 2
        else:
            sp *= 2
    dp *= n  # leftover odd factor onto dp
    return dp, tp, sp


def init_params(cfg: GPT2Config, key) -> Dict[str, Any]:
    """Full (unsharded) param dict; shard_map slices per the specs below."""
    ks = jax.random.split(key, 4 + cfg.n_layer)
    e = cfg.n_embd
    p = {
        "wte": jax.random.normal(ks[0], (cfg.vocab_size, e), _f32) * 0.02,
        "wpe": jax.random.normal(ks[1], (cfg.n_positions, e), _f32) * 0.01,
        "lnf_w": jnp.ones((e,), _f32),
        "lnf_b": jnp.zeros((e,), _f32),
        "blocks": [],
    }
    for i in range(cfg.n_layer):
        bk = jax.random.split(ks[4 + i], 6)
        std = 0.02
        p["blocks"].append({
            "ln1_w": jnp.ones((e,), _f32), "ln1_b": jnp.zeros((e,), _f32),
            "wq": jax.random.normal(bk[0], (e, e), _f32) * std,
            "wk": jax.random.normal(bk[1], (e, e), _f32) * std,
            "wv": jax.random.normal(bk[2], (e, e), _f32) * std,
            "wo": jax.random.normal(bk[3], (e, e), _f32) * std
                  / math.sqrt(2 * cfg.n_layer),
            "ln2_w": jnp.ones((e,), _f32), "ln2_b": jnp.zeros((e,), _f32),
            "fc_w": jax.random.normal(bk[4], (e, 4 * e), _f32) * std,
            "fc_b": jnp.zeros((4 * e,), _f32),
            "proj_w": jax.random.normal(bk[5], (4 * e, e), _f32) * std
                      / math.sqrt(2 * cfg.n_layer),
            "proj_b": jnp.zeros((e,), _f32),
        })
    return p


def param_specs(cfg: GPT2Config) -> Dict[str, Any]:
    """PartitionSpecs: TP-sharded projections, SP-sharded positions."""
    col = P(None, "tp")   # column parallel (output dim sharded)
    row = P("tp", None)   # row parallel (input dim sharded)
    rep = P()
    block = {
        "ln1_w": rep, "ln1_b": rep,
        "wq": col, "wk": col, "wv": col, "wo": row,
        "ln2_w": rep, "ln2_b": rep,
        "fc_w": col, "fc_b": P("tp"), "proj_w": row, "proj_b": rep,
    }
    return {
        "wte": rep,
        "wpe": P("sp", None),
        "lnf_w": rep, "lnf_b": rep,
        "blocks": [dict(block) for _ in range(cfg.n_layer)],
    }


def _grad_sync_specs(cfg: GPT2Config) -> Dict[str, Any]:
    """Axes each param's grad must be psum'd over = axes it is replicated on.
    Encoded as '|'-joined strings so the spec tree has leaf-for-leaf structure
    with the grad tree."""
    tp_sharded = "dp|sp"          # grads of tp-sharded params
    replicated = "dp|sp|tp"
    block = {
        "ln1_w": replicated, "ln1_b": replicated,
        "wq": tp_sharded, "wk": tp_sharded, "wv": tp_sharded,
        "wo": tp_sharded,
        "ln2_w": replicated, "ln2_b": replicated,
        "fc_w": tp_sharded, "fc_b": tp_sharded, "proj_w": tp_sharded,
        "proj_b": replicated,
    }
    return {
        "wte": replicated,
        "wpe": "dp|tp",           # sp-sharded: sum over dp and tp only
        "lnf_w": replicated, "lnf_b": replicated,
        "blocks": [dict(block) for _ in range(cfg.n_layer)],
    }


def _block_apply(cfg: GPT2Config, blk, x, sp_strategy: str = "ring"):
    """One transformer block on a local activation shard (b, s_local, e).

    TP: column-parallel q/k/v + row-parallel output with psum over tp;
    SP: sequence parallelism over sp — ``sp_strategy="ring"`` rotates K/V
    around the ICI ring (any head count), ``"ulysses"`` re-shards
    head↔sequence with two all-to-alls (needs local heads divisible by sp;
    see parallel/ulysses.py for the trade-off); EP: when the block carries
    expert weights ("gate_w"/"w1"/"w2"), the FFN is the expert-parallel MoE
    over ep.
    """
    cd = cfg.compute_dtype
    e = cfg.n_embd
    tp = axis_size("tp")
    h_local = cfg.n_head // tp
    d = e // cfg.n_head
    b, s_local, _ = x.shape

    y = fused_layer_norm_affine(x, blk["ln1_w"], blk["ln1_b"], e)
    q = (y @ blk["wq"].astype(cd))
    k = (y @ blk["wk"].astype(cd))
    v = (y @ blk["wv"].astype(cd))

    def heads(t):
        return t.reshape(b, s_local, h_local, d).transpose(0, 2, 1, 3)

    if sp_strategy == "ulysses":
        o = ulysses_self_attention(heads(q), heads(k), heads(v), "sp",
                                   causal=True)
    else:
        o = ring_self_attention(heads(q), heads(k), heads(v), "sp",
                                causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(b, s_local, h_local * d)
    # row-parallel output projection: partial matmul + psum over tp
    attn = jax.lax.psum(o @ blk["wo"].astype(cd), "tp")
    x = x + attn

    y = fused_layer_norm_affine(x, blk["ln2_w"], blk["ln2_b"], e)
    if "gate_w" in blk:
        # expert-parallel MoE FFN over ep (parallel/moe.py)
        from apex_tpu.parallel.moe import moe_ffn_ep

        y2 = y.reshape(b * s_local, e).astype(jnp.float32)
        mlp = moe_ffn_ep(y2, blk["gate_w"], blk["w1"], blk["w2"], "ep")
        x = x + mlp.reshape(b, s_local, e).astype(x.dtype)
    else:
        hmid = jax.nn.gelu(y @ blk["fc_w"].astype(cd)
                           + blk["fc_b"].astype(cd), approximate=False)
        mlp = jax.lax.psum(hmid @ blk["proj_w"].astype(cd), "tp")
        x = x + (mlp + blk["proj_b"].astype(cd))
    return x


def _forward_local(cfg: GPT2Config, params, tokens, targets, mask,
                   sp_strategy: str = "ring"):
    """Per-shard forward: tokens (b_local, s_local) on a (dp, tp, sp) mesh."""
    cd = cfg.compute_dtype
    e = cfg.n_embd
    tp = axis_size("tp")
    h_local = cfg.n_head // tp
    d = e // cfg.n_head

    # wpe is sp-sharded over positions; the parallel path trains at full
    # context length (seq == n_positions) so position shards align with
    # sequence shards
    sp = axis_size("sp")
    assert tokens.shape[1] * sp == cfg.n_positions, (
        f"parallel GPT-2 requires seq == n_positions "
        f"({tokens.shape[1]}*{sp} != {cfg.n_positions})")
    x = params["wte"][tokens].astype(cd) + params["wpe"][None].astype(cd)
    b, s_local, _ = x.shape

    for blk in params["blocks"]:
        x = _block_apply(cfg, blk, x, sp_strategy)

    x = fused_layer_norm_affine(x, params["lnf_w"], params["lnf_b"], e)
    logits = jax.lax.dot_general(x, params["wte"].astype(cd),
                                 (((2,), (1,)), ((), ())),
                                 preferred_element_type=_f32)
    loss_tok = softmax_cross_entropy_loss(logits, targets)
    # global masked mean over the dp × sp data shards
    tot = jax.lax.psum(jax.lax.psum(jnp.sum(loss_tok * mask), "dp"), "sp")
    cnt = jax.lax.psum(jax.lax.psum(jnp.sum(mask), "dp"), "sp")
    return tot / jnp.maximum(cnt, 1.0)


def make_train_step(cfg: GPT2Config, mesh: Mesh, lr: float = 1e-4,
                    sp_strategy: str = "ring"):
    """Returns jitted train_step(params, opt_state, tokens, targets, mask, step)
    → (params, opt_state, loss). Inputs are FULL arrays; sharding via specs.
    ``sp_strategy``: "ring" or "ulysses" (see _block_apply)."""
    if sp_strategy not in ("ring", "ulysses"):
        raise ValueError(
            f"sp_strategy must be 'ring' or 'ulysses', got {sp_strategy!r}")
    pspecs = param_specs(cfg)
    sync_axes = _grad_sync_specs(cfg)

    def local_step(params, m, v, tokens, targets, mask, step):
        def loss_fn(p):
            return _forward_local(cfg, p, tokens, targets, mask,
                                  sp_strategy)

        loss, grads = jax.value_and_grad(loss_fn)(params)

        # gradient sync: psum over every axis the param is replicated on.
        # With check_vma=False shard_map does not track replication, so the
        # replicated loss seeds a cotangent on EVERY device and each psum
        # transpose re-broadcasts it — after the sync psums the result is
        # exactly (dp·tp·sp)× the true gradient, for every param class
        # (verified empirically across (2,1,1)...(8,1,1),(1,8,1),(4,2,1),
        # (1,2,4) meshes). Normalize by the total mesh size.
        n_total = (axis_size("dp") * axis_size("tp")
                   * axis_size("sp"))

        def sync(g, axes):
            for ax in axes.split("|"):
                g = jax.lax.psum(g, ax)
            return g / n_total

        grads = jax.tree_util.tree_map(sync, grads, sync_axes)

        params, m, v = adam_update(params, grads, m, v, step=step, lr=lr,
                                   weight_decay=0.01)
        return params, m, v, loss

    state_specs = pspecs  # optimizer state sharded exactly like its params

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, state_specs, state_specs,
                  P("dp", "sp"), P("dp", "sp"), P("dp", "sp"), P()),
        out_specs=(pspecs, state_specs, state_specs, P()),
        check_vma=False)

    @jax.jit
    def train_step(params, opt_state, tokens, targets, mask, step):
        m, v = opt_state
        params, m, v, loss = sharded(params, m, v, tokens, targets, mask,
                                     step)
        return params, (m, v), loss

    return train_step


def init_opt_state(params):
    z = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, _f32), params)
    z2 = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, _f32), params)
    return (z, z2)


# ------------------------------------------------------------ pp/ep (round 2)


def init_params_pp(cfg: GPT2Config, key, moe_experts: int = 0):
    """Params for the pipelined model: blocks STACKED (leading n_layer dim,
    sharded over pp), embeddings/final-LN shared. ``moe_experts > 0`` builds
    expert-parallel FFNs (gate + per-expert w1/w2) instead of dense fc/proj."""
    p = init_params(cfg, key)
    blocks = p.pop("blocks")
    if moe_experts:
        e = cfg.n_embd
        ks = jax.random.split(jax.random.fold_in(key, 17),
                              3 * cfg.n_layer)
        for i, blk in enumerate(blocks):
            for k_ in ("fc_w", "fc_b", "proj_w", "proj_b"):
                del blk[k_]
            std = 0.02
            blk["gate_w"] = jax.random.normal(
                ks[3 * i], (e, moe_experts), _f32) * std
            blk["w1"] = jax.random.normal(
                ks[3 * i + 1], (moe_experts, e, 4 * e), _f32) * std
            blk["w2"] = jax.random.normal(
                ks[3 * i + 2], (moe_experts, 4 * e, e), _f32) * std \
                / math.sqrt(2 * cfg.n_layer)
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *blocks)
    shared = {"wte": p["wte"], "wpe": p["wpe"],
              "lnf_w": p["lnf_w"], "lnf_b": p["lnf_b"]}
    return {"blocks": stacked, "shared": shared}


def param_specs_pp(cfg: GPT2Config, moe_experts: int = 0):
    """PartitionSpecs for the pipelined layout: leading layer dim over pp,
    TP/EP dims inside, shared params replicated over pp."""
    col = P("pp", None, "tp")
    row = P("pp", "tp", None)
    rep = P("pp")
    block = {
        "ln1_w": rep, "ln1_b": rep,
        "wq": col, "wk": col, "wv": col, "wo": row,
        "ln2_w": rep, "ln2_b": rep,
    }
    if moe_experts:
        block.update({
            "gate_w": P("pp", None, None),
            "w1": P("pp", "ep", None, None),
            "w2": P("pp", "ep", None, None),
        })
    else:
        block.update({
            "fc_w": col, "fc_b": P("pp", "tp"),
            "proj_w": row, "proj_b": rep,
        })
    shared = {"wte": P(), "wpe": P("sp", None), "lnf_w": P(), "lnf_b": P()}
    return {"blocks": block, "shared": shared}


def _grad_sync_specs_pp(cfg: GPT2Config, moe_experts: int = 0):
    """Axes (|-joined) each grad must be psum'd over in the pp layout.
    Blocks are pp-sharded so never synced over pp; the pipeline already
    psums shared grads over pp internally."""
    tp_sharded = "dp|sp|ep" if moe_experts else "dp|sp"
    replicated = "dp|sp|tp|ep" if moe_experts else "dp|sp|tp"
    block = {
        "ln1_w": replicated, "ln1_b": replicated,
        "wq": tp_sharded, "wk": tp_sharded, "wv": tp_sharded,
        "wo": tp_sharded,
        "ln2_w": replicated, "ln2_b": replicated,
    }
    if moe_experts:
        block.update({"gate_w": replicated,
                      "w1": "dp|sp|tp", "w2": "dp|sp|tp"})
    else:
        block.update({"fc_w": tp_sharded, "fc_b": tp_sharded,
                      "proj_w": tp_sharded, "proj_b": replicated})
    shared = {"wte": replicated, "wpe": "dp|tp|ep" if moe_experts
              else "dp|tp", "lnf_w": replicated, "lnf_b": replicated}
    return {"blocks": block, "shared": shared}


def make_train_step_pp(cfg: GPT2Config, mesh: Mesh, lr: float = 1e-4,
                       num_microbatches: int = 4, moe_experts: int = 0):
    """Composed 5-axis (dp, pp, tp, sp, ep) train step: 1F1B pipeline over
    pp wrapping the dp×tp×sp(×ep) block stack. Returns jitted
    train_step(params, opt_state, tokens, targets, mask, step) →
    (params, opt_state, loss)."""
    from apex_tpu.parallel.pipeline import pipeline_train_1f1b

    pspecs = param_specs_pp(cfg, moe_experts)
    sync_axes = _grad_sync_specs_pp(cfg, moe_experts)
    pp = mesh.shape["pp"]
    assert cfg.n_layer % pp == 0, \
        "pp (pipeline stages) must divide n_layer evenly"
    cd = cfg.compute_dtype
    e = cfg.n_embd
    M = num_microbatches

    def local_step(blocks, shared, m, v, tokens, targets, mask, step):
        b_local, s_local = tokens.shape
        assert b_local % M == 0, "num_microbatches must divide local batch"
        mb = b_local // M
        micro = tuple(a.reshape(M, mb, s_local)
                      for a in (tokens, targets, mask))
        x_template = jnp.zeros((mb, s_local, e), cd)
        # GLOBAL valid-token count (all microbatches): per-microbatch losses
        # are tot_i / cnt_total so their sum is the exact global token mean
        # (per-microbatch normalization would overweight sparse microbatches)
        cnt_total = jnp.maximum(jax.lax.psum(jax.lax.psum(
            jnp.sum(mask), "dp"), "sp"), 1.0)

        def stage_fn(stage_blocks, shared_, x_act, tok, tgt, msk):
            my_pp = jax.lax.axis_index("pp")
            last = my_pp == axis_size("pp") - 1
            # cond (not where): only stage 0 pays the (vocab, e) embedding
            # gather — and its scatter-add cotangent — per tick; mirrors the
            # lax.cond gating of the vocab-logits loss on the last stage
            x = jax.lax.cond(
                my_pp == 0,
                lambda: (shared_["wte"][tok].astype(cd)
                         + shared_["wpe"][None].astype(cd)),
                lambda: x_act)
            lps = cfg.n_layer // pp
            for i in range(lps):
                blk = jax.tree_util.tree_map(lambda l: l[i], stage_blocks)
                x = _block_apply(cfg, blk, x)

            def loss_of(xv):
                y = fused_layer_norm_affine(xv, shared_["lnf_w"],
                                            shared_["lnf_b"], e)
                logits = jax.lax.dot_general(
                    y, shared_["wte"].astype(cd), (((2,), (1,)), ((), ())),
                    preferred_element_type=_f32)
                loss_tok = softmax_cross_entropy_loss(logits, tgt)
                tot = jax.lax.psum(jax.lax.psum(
                    jnp.sum(loss_tok * msk), "dp"), "sp")
                return tot / cnt_total

            # only the last stage pays the vocab matmul (lax.cond: 1 branch)
            loss_i = jax.lax.cond(last, loss_of,
                                  lambda _: jnp.float32(0.0), x)
            return x, loss_i

        loss_sum, g_blocks, g_shared = pipeline_train_1f1b(
            stage_fn, blocks, shared, x_template, micro, M, "pp")
        loss = loss_sum  # already the global token mean (see cnt_total)

        # grad sync + replication-factor normalization (see make_train_step:
        # with check_vma=False each sync psum re-broadcasts the seed
        # cotangent, giving n_total× the true grad; pp is handled inside the
        # pipeline for shared params and absent for block params)
        n_total = (axis_size("dp") * axis_size("tp")
                   * axis_size("sp") * axis_size("ep"))

        def sync(g, axes):
            for ax in axes.split("|"):
                g = jax.lax.psum(g, ax)
            return g / n_total

        g_blocks = {k_: sync(g_blocks[k_], sync_axes["blocks"][k_])
                    for k_ in g_blocks}
        g_shared = {k_: sync(g_shared[k_], sync_axes["shared"][k_])
                    for k_ in g_shared}

        params = {"blocks": blocks, "shared": shared}
        grads = {"blocks": g_blocks, "shared": g_shared}
        params, m, v = adam_update(params, grads, m, v, step=step, lr=lr,
                                   weight_decay=0.01)
        return params["blocks"], params["shared"], m, v, loss

    bspec = pspecs["blocks"]
    sspec = pspecs["shared"]
    state_spec = {"blocks": bspec, "shared": sspec}

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(bspec, sspec, state_spec, state_spec,
                  P("dp", "sp"), P("dp", "sp"), P("dp", "sp"), P()),
        out_specs=(bspec, sspec, state_spec, state_spec, P()),
        check_vma=False)

    @jax.jit
    def train_step(params, opt_state, tokens, targets, mask, step):
        m, v = opt_state
        blocks, shared, m, v, loss = sharded(
            params["blocks"], params["shared"], m, v, tokens, targets,
            mask, step)
        return {"blocks": blocks, "shared": shared}, (m, v), loss

    return train_step
