"""Tensor-parallel serving — the mesh/sharding layer of the engine.

The serving engine goes multi-chip by sharding on the **head axis** over
a 1-D ``NamedSharding`` mesh (axis ``"tp"``):

- **model params** — the q/k/v projection columns, the attention output
  projection, and the MLP weights are sharded per head block (the qkv
  kernel is re-laid head-major first, see :func:`permute_qkv`, so a
  ``tp``-slice of the last axis is one rank's whole local q|k|v block);
  embeddings, layer norms, and biases added after a collective stay
  replicated;
- **KV cache** — the pool shards its ``heads`` axis (axis 3 of
  ``[n_layer, num_pages, page_size, heads, head_dim]``;
  allocated for the mesh, ``kv_cache.padded_heads``);
  ``lengths`` and the **page table stay replicated data** — page indices
  address every rank's shard simultaneously, so the host-side allocator,
  prefix index, and scheduler need zero changes;
- **the decode step** (and each pow2 prefill bucket) lowers the per-rank
  body under ``shard_map`` — admission/eviction/backfill still move only
  values, so the one-compile invariant becomes one compile **per mesh
  shape**.

Three per-layer synchronization modes (``EngineConfig.tp_sync``), all
sharing the per-rank arithmetic:

- ``"exact"`` (default, THE oracle): the cross-rank combine is pure
  **concatenation** — ``all_gather`` the per-head attention outputs (and
  the MLP hidden slices), then run the full projection matmul replicated.
  No float add ever crosses a rank boundary and column-sliced matmuls
  are per-column deterministic under XLA, so a ``tp=N`` engine is
  **bit-identical in fp32** to the single-chip engine at equal
  ``block_k`` (tier-1 asserts, greedy AND sampled). 2 all-gathers/layer.
- ``"overlap"`` (TokenWeave): Megatron row-parallel projections with the
  post-attention and post-MLP all-reduces each **split into two slot
  halves**, each half's psum interleaved with the adjacent residual-add
  + layer-norm compute so XLA's async collectives can hide it behind
  compute on real hardware. 4 half-psums/layer; partial sums reorder
  float adds, so ±ulp vs exact (never bit-claimed).
- ``"relaxed"`` (partially-synchronized activations, opt-in): the
  post-attention all-reduce is **deferred across the norm** — each rank's
  MLP runs on its partially-synchronized residual (local attention
  partial only) and ONE combined all-reduce per layer lands attention +
  MLP contributions together. Halves the collective count again
  (2 half-psums/layer); an approximation by construction — quality is
  checkpoint-dependent, which is why it is opt-in and the exact mode
  stays the oracle.

:func:`expected_collectives` states the per-decode-step collective
contract per mode and :func:`count_collectives` verifies it against the
actual lowered StableHLO — the tier-1 overlap-seam unit holds the two
together.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

TP_AXIS = "tp"
SYNC_MODES = ("exact", "overlap", "relaxed")


def serving_mesh(tp: int, devices=None):
    """The 1-D serving mesh: the first ``tp`` devices on axis ``"tp"``.

    Tier-1 runs this on the conftest-forced multi-device CPU host (the
    ``xla_force_host_platform_device_count`` early-env hook), so sharded
    tests never depend on real chips; a real deployment passes its ICI
    slice. Raises a clear ``ValueError`` when the host has fewer devices
    than the mesh needs."""
    import jax

    from apex_tpu.parallel.mesh import make_mesh

    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, have {len(devices)} "
            f"(on CPU force a multi-device host with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp})")
    return make_mesh([tp], [TP_AXIS], devices[:tp])


def permute_qkv(kernel, bias, n_head: int, head_dim: int, tp: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Re-lay the fused qkv projection head-major for tp slicing.

    The stock kernel is ``[e, 3e] = [Wq | Wk | Wv]``: a plain tp-slice of
    the last axis would cut across the q/k/v boundary (for tp=2, rank 0
    would get all of q plus half of k). Emit instead the concatenation
    over ranks ``r`` of ``(Wq_r | Wk_r | Wv_r)`` — rank ``r``'s contiguous
    head block of each projection — so a ``P(None, "tp")`` shard IS one
    rank's local qkv and an in-rank ``split(3)`` recovers q/k/v. Pure
    column permutation: every output column's dot product is unchanged,
    which is what keeps the sharded projection bit-exact per column."""
    kernel = np.asarray(kernel)
    bias = np.asarray(bias)
    wq, wk, wv = np.split(kernel, 3, axis=1)
    bq, bk, bv = np.split(bias, 3)
    loc = (n_head // tp) * head_dim
    ks: List[np.ndarray] = []
    bs: List[np.ndarray] = []
    for r in range(tp):
        sl = slice(r * loc, (r + 1) * loc)
        ks += [wq[:, sl], wk[:, sl], wv[:, sl]]
        bs += [bq[sl], bk[sl], bv[sl]]
    return np.concatenate(ks, axis=1), np.concatenate(bs)


def unpermute_qkv(kernel, bias, n_head: int, head_dim: int, tp: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact inverse of :func:`permute_qkv`: gather each projection's
    per-rank head blocks back into contiguous ``[Wq | Wk | Wv]`` —
    what turns a TP-serving checkpoint back into the dense training
    layout. A pure column permutation both ways, so the round trip is
    byte-identical; the storage layer restates both directions jax-free
    (:mod:`apex_tpu.resilience.topology`), and tier-1 holds the two
    implementations bit-identical."""
    kernel = np.asarray(kernel)
    bias = np.asarray(bias)
    loc = (n_head // tp) * head_dim
    qs, ks, vs, bqs, bks, bvs = [], [], [], [], [], []
    for r in range(tp):
        base = r * 3 * loc
        qs.append(kernel[:, base:base + loc])
        ks.append(kernel[:, base + loc:base + 2 * loc])
        vs.append(kernel[:, base + 2 * loc:base + 3 * loc])
        bqs.append(bias[base:base + loc])
        bks.append(bias[base + loc:base + 2 * loc])
        bvs.append(bias[base + 2 * loc:base + 3 * loc])
    return (np.concatenate(qs + ks + vs, axis=1),
            np.concatenate(bqs + bks + bvs))


def tp_param_specs(cfg, sync: str) -> Dict[str, Any]:
    """``PartitionSpec`` tree for the TP param layout of
    :func:`build_tp_params` (same dict structure, spec leaves).

    The head-sharded leaves: qkv kernel/bias (permuted layout), the MLP
    fc rows. The attention output projection and the MLP proj are
    sharded only in the psum modes — the exact mode gathers activations
    and runs those matmuls replicated-full, which is what makes its
    combine pure concatenation."""
    from jax.sharding import PartitionSpec as P

    rep1, rep2 = P(), P(None, None)
    gathered = sync == "exact"
    block = {
        "ln_1": {"weight": rep1, "bias": rep1},
        "ln_2": {"weight": rep1, "bias": rep1},
        "attn_qkv": {"kernel": P(None, TP_AXIS), "bias": P(TP_AXIS)},
        "attn_out": {"kernel": rep2 if gathered else P(TP_AXIS, None),
                     "bias": rep1},
        "mlp_fc_w": P(TP_AXIS, None),
        "mlp_fc_b": P(TP_AXIS),
        "mlp_proj_w": rep2 if gathered else P(None, TP_AXIS),
        "mlp_proj_b": rep1,
    }
    specs: Dict[str, Any] = {
        "wte": rep2, "wpe": rep2,
        "ln_f": {"weight": rep1, "bias": rep1},
    }
    for i in range(cfg.n_layer):
        specs[f"h_{i}"] = block
    return specs


def build_tp_params(cfg, params, tp: int, sync: str, mesh):
    """The sharded serving param tree: the standard flax GPT-2 pytree
    re-laid for head-axis tp and ``device_put`` onto the mesh per
    :func:`tp_param_specs`. Returns ``(tp_params, specs)``.

    Only the qkv projection changes LAYOUT (head-major permutation);
    every other leaf keeps its bytes and is merely placed — sharded
    where a rank owns a head block, replicated otherwise."""
    import jax
    from jax.sharding import NamedSharding

    p = params["params"] if "params" in params else params
    h = cfg.n_head
    d = cfg.n_embd // h
    tree: Dict[str, Any] = {
        "wte": np.asarray(p["wte"]), "wpe": np.asarray(p["wpe"]),
        "ln_f": {k: np.asarray(v) for k, v in p["ln_f"].items()},
    }
    for i in range(cfg.n_layer):
        blk = p[f"h_{i}"]
        qkv_k, qkv_b = permute_qkv(blk["attn_qkv"]["kernel"],
                                   blk["attn_qkv"]["bias"], h, d, tp)
        tree[f"h_{i}"] = {
            "ln_1": {k: np.asarray(v) for k, v in blk["ln_1"].items()},
            "ln_2": {k: np.asarray(v) for k, v in blk["ln_2"].items()},
            "attn_qkv": {"kernel": qkv_k, "bias": qkv_b},
            "attn_out": {"kernel": np.asarray(blk["attn_out"]["kernel"]),
                         "bias": np.asarray(blk["attn_out"]["bias"])},
            "mlp_fc_w": np.asarray(blk["mlp_fc_w"]),
            "mlp_fc_b": np.asarray(blk["mlp_fc_b"]),
            "mlp_proj_w": np.asarray(blk["mlp_proj_w"]),
            "mlp_proj_b": np.asarray(blk["mlp_proj_b"]),
        }
    specs = tp_param_specs(cfg, sync)

    def place(leaf, spec):
        # explicit recursion, not jax.tree.map: PartitionSpec flattens
        # as a pytree on some jax versions, which would tear the spec
        # tree's structure out from under a joint map
        if isinstance(leaf, dict):
            return {k: place(v, spec[k]) for k, v in leaf.items()}
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return place(tree, specs), specs


def expected_collectives(n_layer: int, sync: str) -> Dict[str, int]:
    """The per-decode-step collective CONTRACT per sync mode — what the
    lowered step must contain (tier-1 holds this against
    :func:`count_collectives` of the actual StableHLO):

    - ``exact``: 2 all-gathers per layer (post-attention heads, MLP
      hidden), zero all-reduces — the combine is concatenation.
    - ``overlap``: 2 logical all-reduces per layer, each split into two
      slot-half psums (TokenWeave) = 4 all-reduces, zero gathers.
    - ``relaxed``: ONE deferred all-reduce per layer (attention partial +
      MLP partial land together), split in two halves = 2 all-reduces.

    Delegates to ``monitor/costs.py:expected_collective_ops`` — the cost
    ledger prices collective bytes from the SAME contract, so the two
    spellings can never diverge.
    """
    from apex_tpu.monitor import costs

    return costs.expected_collective_ops(n_layer, sync)


def count_collectives(stablehlo_text: str) -> Dict[str, int]:
    """Count collective ops in a lowered module's StableHLO text — the
    verifier side of :func:`expected_collectives` (pre-XLA-pass text, so
    only the shard_map-explicit collectives count, never a compiler
    resharding). Delegates to ``monitor/costs.py:collective_counts``
    (the generalized ledger walk owns the spelling)."""
    from apex_tpu.monitor import costs

    return costs.collective_counts(stablehlo_text)


def rank_snapshots(engine, meta: Optional[Dict[str, Any]] = None
                   ) -> List[Dict[str, Any]]:
    """One mergeable metrics snapshot per TP rank — the PR-10
    ``merge_snapshots`` seam used for its designed purpose: each rank
    reports its OWN shard (local KV bytes, local heads, its collective
    traffic), and the fleet view is the exact fold:

    - ``serve_tp_ranks`` gauge (agg sum, 1 per rank) → mesh size,
    - ``serve_tp_rank_heads`` gauge (agg sum) → the model's ``n_head``,
    - ``serve_tp_rank_kv_bytes`` gauge (agg sum) → the engine's total
      ``kv_cache_bytes``,
    - ``serve_tp_rank_collectives_total`` counter → fleet-wide collective
      ops executed (decode calls × the per-step contract, per rank).

    In a real multi-host deployment each host writes its own rank file;
    the fake-multihost tier-1 writes all of them from one process and
    folds them through ``tools/metrics_merge.py`` identically."""
    from apex_tpu.monitor.export import MetricsRegistry

    tp = engine.tp
    per_step = sum(expected_collectives(engine.model_cfg.n_layer,
                                        engine.config.tp_sync).values())
    docs = []
    for r in range(tp):
        reg = MetricsRegistry()
        reg.gauge("serve_tp_ranks",
                  "TP mesh ranks reporting (fleet view: mesh size)").set(1)
        reg.gauge("serve_tp_rank_heads",
                  "attention heads resident on this rank").set(
            engine.model_cfg.n_head // tp)
        reg.gauge("serve_tp_rank_kv_bytes",
                  "KV cache bytes resident on this rank").set(
            engine.kv_cache_bytes // tp)
        reg.counter(
            "serve_tp_rank_collectives_total",
            "collective ops this rank executed in decode steps").inc(
            engine.decode_calls * per_step)
        docs.append(reg.snapshot(
            meta={**(meta or {}), "tp_rank": r, "tp": tp,
                  "tp_sync": engine.config.tp_sync}))
    return docs
