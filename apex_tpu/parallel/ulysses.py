"""Ulysses-style sequence parallelism — all-to-all head↔sequence re-sharding.

The reference has no sequence parallelism (SURVEY §2.5: PP/EP/Ulysses/ring
absent from apex); this module and :mod:`apex_tpu.parallel.ring_attention`
are the framework's two first-class long-context strategies:

- **Ring** (ring_attention.py): K/V rotate over the ICI ring; O(s_local·d)
  memory; comm scales with the shard size × (n−1) steps. Best when s is
  huge and heads are few.
- **Ulysses** (this module, after DeepSpeed-Ulysses): inputs arrive
  sequence-sharded ``(b, h, s/n, d)``; ONE ``all_to_all`` re-shards to
  head-sharded ``(b, h/n, s, d)``, each device runs ordinary full-sequence
  flash attention over its head group, and a second ``all_to_all`` restores
  sequence sharding. Comm is two all-to-alls of the activation (independent
  of n on a ring/torus), and the attention itself needs NO cross-device
  softmax merging — the numerics are exactly single-device flash. Requires
  ``h % n == 0``; best when h ≥ n (the usual transformer regime).

Composition rule of thumb (scaling playbook): Ulysses inside a slice where
all_to_all rides ICI; ring across the slower axis when h < n forces it.

Layout convention matches the rest of the package: q/k/v ``(b, h, s_local,
d)`` per device under ``shard_map`` with the sequence axis sharded on
``axis_name``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from apex_tpu.ops.pallas.flash_attention import flash_attention


def _seq_to_heads(x, axis_name: str, n: int):
    """(b, h, s/n, d) seq-sharded → (b, h/n, s, d) head-sharded.

    ``all_to_all`` splits the head axis n-ways and concatenates the
    gathered pieces along the sequence axis."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _heads_to_seq(x, axis_name: str, n: int):
    """Inverse of :func:`_seq_to_heads`."""
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           axis_name: str, causal: bool = False,
                           scale: Optional[float] = None,
                           dropout_p: float = 0.0, dropout_seed=None):
    """Full-sequence self-attention over sequence-sharded q/k/v.

    Inside ``shard_map``: q/k/v are the local ``(b, h, s_local, d)`` shards
    of a globally ``(b, h, s, d)`` array sharded on ``axis_name``. Returns
    the local shard of the attention output with the same sharding.
    Differentiable (all_to_all is its own transpose, so the backward is two
    all-to-alls around the flash backward — no custom VJP needed).
    """
    n = axis_size(axis_name)
    b, h, s_local, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the sequence-parallel "
            f"axis size ({n}); use ring_attention when h < n")
    qh = _seq_to_heads(q, axis_name, n)
    kh = _seq_to_heads(k, axis_name, n)
    vh = _seq_to_heads(v, axis_name, n)
    oh = flash_attention(qh, kh, vh, causal, scale,
                         dropout_p=dropout_p, dropout_seed=dropout_seed)
    return _heads_to_seq(oh.astype(q.dtype), axis_name, n)
