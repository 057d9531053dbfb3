"""Fused rotary positional embedding — TPU equivalent of
``fused_rotary_positional_embedding`` (csrc/megatron/fused_rotary_positional_embedding.{h,cu,cpp}).

Variants mirrored (fused_rotary_positional_embedding.cpp:176-193):
- ``fused_rope(t, freqs)``            sbhd layout (s, b, h, d)
- ``fused_rope_cached(t, cos, sin)``  precomputed cos/sin tables
- ``fused_rope_thd(t, cu_seqlens, freqs)``  packed variable-length batches
- ``fused_rope_2d(t, freqs_h, freqs_w)``    image (2D) rotary

Rotation rule (fused_rope_block_forward, .h:28-61): only the first ``d2 =
freqs.shape[-1]`` channels rotate; NeoX rotate-half pairing
``out[d] = in[d]·cos(f[d]) + rot_half(in)[d]·sin(f[d])`` with
``rot_half(x)[d] = -x[d+d2/2]`` for d < d2/2 else ``x[d-d2/2]``; trailing
``d-d2`` channels pass through. Backward = rotation by -f (the reference's
separate backward kernel, .h:63-97) — expressed here via custom_vjp so autodiff
never materializes intermediate products.

All math fp32; IO dtype preserved. XLA fuses the elementwise chain; there is
no launch overhead to amortize, so no Pallas kernel is needed for this op.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_f32 = jnp.float32


def _rot_half(x):
    d2 = x.shape[-1]
    a, b = x[..., : d2 // 2], x[..., d2 // 2:]
    return jnp.concatenate([-b, a], axis=-1)


def _apply_rope(x, cos, sin):
    """x: (..., d); cos/sin broadcastable (..., d2) with d2 <= d."""
    d = x.shape[-1]
    d2 = cos.shape[-1]
    x32 = x.astype(_f32)
    head, tail = x32[..., :d2], x32[..., d2:]
    out = head * cos + _rot_half(head) * sin
    if d2 < d:
        out = jnp.concatenate([out, tail], axis=-1)
    return out.astype(x.dtype)


@jax.custom_vjp
def _rope_cached(x, cos, sin):
    return _apply_rope(x, cos, sin)


def _rope_cached_fwd(x, cos, sin):
    return _apply_rope(x, cos, sin), (cos, sin)


def _rope_cached_bwd(res, dy):
    cos, sin = res
    # inverse rotation: R(-f) == transpose of R(f)
    dx = _apply_rope(dy, cos, -sin)
    return dx, None, None


_rope_cached.defvjp(_rope_cached_fwd, _rope_cached_bwd)


def _offset_slice(table: jax.Array, position_offset, s: int) -> jax.Array:
    """Rows ``position_offset .. position_offset+s`` of a positional table
    whose axis 0 is the position axis. ``position_offset`` may be a python
    int or a traced int32 scalar (a serving decode step's position); the
    table must cover ``position_offset + s`` rows. The static-zero case
    stays a plain slice so existing jaxprs are unchanged."""
    if isinstance(position_offset, int) and position_offset == 0:
        return table[:s]
    return jax.lax.dynamic_slice_in_dim(table, position_offset, s, 0)


def fused_rope(t: jax.Array, freqs: jax.Array,
               transpose_output_memory: bool = False, *,
               position_offset=0) -> jax.Array:
    """sbhd variant: t (s, b, h, d), freqs (s_max, 1, 1, d2) or (s_max, d2).

    ``transpose_output_memory`` is a CUDA memory-layout knob; XLA owns layout
    on TPU — accepted for parity, ignored.

    ``position_offset`` rotates token row ``j`` of ``t`` by frequency row
    ``position_offset + j`` — a single decode token at absolute position
    ``p`` (``t`` of shape (1, b, h, d), ``position_offset=p``) gets exactly
    the rotation token ``p`` of a full-sequence call gets. Accepts a traced
    scalar, so a serving decode step can pass the slot's current length.
    """
    if freqs.ndim == 2:
        freqs = freqs[:, None, None, :]
    freqs = _offset_slice(freqs, position_offset, t.shape[0])
    cos = jnp.cos(freqs.astype(_f32))
    sin = jnp.sin(freqs.astype(_f32))
    return _rope_cached(t, cos, sin)


def fused_rope_cached(t: jax.Array, cos: jax.Array, sin: jax.Array, *,
                      position_offset=0) -> jax.Array:
    """Cached-freqs variant (``fused_rope_forward_cached``).

    ``position_offset`` indexes the cos/sin tables at the tokens' absolute
    positions (axis 0 = position), same contract as :func:`fused_rope`.
    """
    while cos.ndim < t.ndim:
        cos = jnp.expand_dims(cos, 1)
        sin = jnp.expand_dims(sin, 1)
    cos = _offset_slice(cos, position_offset, t.shape[0])
    sin = _offset_slice(sin, position_offset, t.shape[0])
    return _rope_cached(t, cos.astype(_f32), sin.astype(_f32))


def fused_rope_thd(t: jax.Array, cu_seqlens: jax.Array,
                   freqs: jax.Array) -> jax.Array:
    """Packed thd variant (``fused_rope_forward_thd``): t (total_t, h, d);
    ``cu_seqlens`` (b+1,) cumulative sequence starts; each token rotates by its
    position WITHIN its own sequence.

    TPU note: implemented with a vectorized searchsorted over the static token
    axis (no dynamic shapes), so it stays jittable.
    """
    total = t.shape[0]
    tok = jnp.arange(total, dtype=jnp.int32)
    # sequence id of each token, then its in-sequence position
    seq_id = jnp.searchsorted(cu_seqlens.astype(jnp.int32), tok,
                              side="right") - 1
    seq_id = jnp.clip(seq_id, 0, cu_seqlens.shape[0] - 2)
    pos = tok - cu_seqlens.astype(jnp.int32)[seq_id]
    if freqs.ndim > 2:
        freqs = freqs.reshape(freqs.shape[0], freqs.shape[-1])
    f = freqs.astype(_f32)[pos]            # (total_t, d2)
    cos = jnp.cos(f)[:, None, :]           # broadcast over heads
    sin = jnp.sin(f)[:, None, :]
    return _rope_cached(t, cos, sin)


def fused_rope_2d(t: jax.Array, img_h: int, img_w: int,
                  freqs_h: jax.Array, freqs_w: jax.Array) -> jax.Array:
    """2D (image) variant (``fused_rope_forward_2d``): t (b, img_h*img_w, h, d);
    first half of channels rotates by the row frequency, second half by the
    column frequency."""
    b, s, h, d = t.shape
    assert s == img_h * img_w, "sequence must equal img_h*img_w"
    if freqs_h.ndim > 2:
        freqs_h = freqs_h.reshape(freqs_h.shape[-2], freqs_h.shape[-1])
        freqs_w = freqs_w.reshape(freqs_w.shape[-2], freqs_w.shape[-1])
    d2h = freqs_h.shape[-1]
    d2w = freqs_w.shape[-1]
    fh = jnp.repeat(freqs_h.astype(_f32)[:img_h], img_w, axis=0)  # (s, d2h)
    fw = jnp.tile(freqs_w.astype(_f32)[:img_w], (img_h, 1))       # (s, d2w)
    t_h, t_w = t[..., :d2h], t[..., d2h:d2h + d2w]
    rest = t[..., d2h + d2w:]
    out_h = _rope_cached(t_h, jnp.cos(fh)[None, :, None, :],
                         jnp.sin(fh)[None, :, None, :])
    out_w = _rope_cached(t_w, jnp.cos(fw)[None, :, None, :],
                         jnp.sin(fw)[None, :, None, :])
    return jnp.concatenate([out_h, out_w, rest], axis=-1)


# --------------------------------------------------- YaRN, interleaved pairs


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies under YaRN scaling (Peng et al.,
    arXiv:2309.00071, as the published ``rope_type: yarn`` computes them),
    float64 on the host: a constant of the traced program.

    Pair ``i`` turns ``theta ** (-2i / dim)`` radians a position unscaled.
    A pair that makes more than ``beta_fast`` turns over the original
    context keeps that frequency; one that makes fewer than ``beta_slow``
    has it divided by ``factor`` (positions interpolated); between the two
    correction dimensions, ``dim * ln(original / (2 pi beta)) / (2 ln
    theta)`` floored and ceiled, the two are blended by a linear ramp.
    """
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return (dim * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     inv_freq) -> jax.Array:
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x``'s last axis by
    ``positions * inv_freq[i]`` radians. ``x``: ``[..., d]`` with ``d == 2
    * len(inv_freq)``; ``positions`` has ``x``'s leading shape up to
    broadcasting (``[rows]`` against ``[rows, heads, d]`` takes a
    ``[:, None]``). Float32 inside, ``x``'s dtype out. The published
    DeepSeek-V3 code de-interleaves the pairs and then rotates halves:
    the same rotation in another order of the channels, so every
    query-key product is the same."""
    angle = positions.astype(_f32)[..., None] * jnp.asarray(inv_freq, _f32)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(_f32).reshape(x.shape[:-1] + (-1, 2))
    even, odd = x32[..., 0], x32[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_rotate_half(x: jax.Array, positions: jax.Array,
                     inv_freq) -> jax.Array:
    """Rotate the pairs ``(i, i + d / 2)`` of ``x``'s last axis by
    ``positions * inv_freq[i]`` radians: the NeoX rotate-half pairing of
    :func:`fused_rope` over the whole of ``d == 2 * len(inv_freq)``, each
    row at its own position (``positions`` has ``x``'s leading shape up to
    broadcasting, as in :func:`rope_interleaved`). Float32 inside, ``x``'s
    dtype out."""
    angle = positions.astype(_f32)[..., None] * jnp.asarray(inv_freq, _f32)
    angle = jnp.concatenate([angle, angle], axis=-1)
    return _apply_rope(x, jnp.cos(angle), jnp.sin(angle))
