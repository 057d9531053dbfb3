"""FusedLayerNorm / FusedRMSNorm — TPU equivalent of
``apex/normalization/fused_layer_norm.py`` (module :724 / :841, functional
wrappers :670-721, CPU fallback :815-833, Mixed* variants :959-1031).

Public surface (functional, differentiable, jittable):
- ``fused_layer_norm_affine(x, weight, bias, normalized_shape, eps, memory_efficient)``
- ``fused_layer_norm(x, normalized_shape, eps, memory_efficient)``
- ``fused_rms_norm_affine(x, weight, normalized_shape, eps, memory_efficient)``
- ``fused_rms_norm(x, normalized_shape, eps, memory_efficient)``
- ``manual_rms_norm`` — pure-jnp reference (≈ fused_layer_norm.py:22)

plus flax modules ``FusedLayerNorm``, ``FusedRMSNorm``, ``MixedFusedLayerNorm``,
``MixedFusedRMSNorm``.

The hot path is the Pallas kernel pair in ops/pallas/layer_norm_kernel.py; a
pure-jnp path handles lane-unfriendly hidden sizes and serves as the parity
reference in tests (mirroring the reference's fallback to ``F.layer_norm``).
"""

from __future__ import annotations

import functools
import numbers
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.ops.pallas.layer_norm_kernel import ln_bwd_pallas, ln_fwd_pallas
from apex_tpu.utils.logging import one_time_warning

_f32 = jnp.float32


def _norm_size(normalized_shape) -> int:
    if isinstance(normalized_shape, numbers.Integral):
        return int(normalized_shape)
    out = 1
    for d in normalized_shape:
        out *= int(d)
    return out


def pallas_route(hidden: int) -> Optional[str]:
    """None when the Pallas kernel takes this hidden size, else why the
    pure-jnp path runs instead (chip_smoke.py prints it per kernel)."""
    if hidden % 128:
        return f"hidden {hidden} is not a multiple of the 128-lane tile"
    if hidden > 65536:
        return f"hidden {hidden} exceeds the kernel's 65536 row budget"
    return None


def _pallas_ok(hidden: int) -> bool:
    reason = pallas_route(hidden)
    if reason is not None:
        # the route is kept, but never silently: GPT-2 XL (hidden 1600)
        # was "fused Pallas" in name only
        one_time_warning(
            f"fused layer/rms norm takes the jnp path: {reason}")
    return reason is None


# ----------------------------------------------------------- jnp reference


def manual_layer_norm(x, weight, bias, normalized_shape, eps):
    h = _norm_size(normalized_shape)
    shape = x.shape
    x2 = x.reshape(-1, h).astype(_f32)
    mu = jnp.mean(x2, axis=1, keepdims=True)
    xc = x2 - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.reshape(1, h).astype(_f32)
    if bias is not None:
        y = y + bias.reshape(1, h).astype(_f32)
    return y.reshape(shape).astype(x.dtype)


def manual_rms_norm(x, weight, normalized_shape, eps):
    """Pure-jnp RMSNorm (ref fused_layer_norm.py:22 ``manual_rms_norm``)."""
    h = _norm_size(normalized_shape)
    shape = x.shape
    x2 = x.reshape(-1, h).astype(_f32)
    var = jnp.mean(x2 * x2, axis=1, keepdims=True)
    y = x2 * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.reshape(1, h).astype(_f32)
    return y.reshape(shape).astype(x.dtype)


# ------------------------------------------------------- pallas custom_vjp


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_norm(x, weight, bias, hidden: int, eps: float, rms: bool,
                affine: bool, memory_efficient: bool):
    y, _, _ = _fwd_impl(x, weight, bias, hidden, eps, rms, affine)
    return y.reshape(x.shape)


def _fwd_impl(x, weight, bias, hidden, eps, rms, affine):
    x2 = x.reshape(-1, hidden)
    return ln_fwd_pallas(x2, weight if affine else None,
                         bias if (affine and bias is not None) else None,
                         eps=eps, rms=rms)


def _fused_norm_fwd(x, weight, bias, hidden, eps, rms, affine,
                    memory_efficient):
    y2, mean, invvar = _fwd_impl(x, weight, bias, hidden, eps, rms, affine)
    if memory_efficient:
        # save output instead of input (fused_layer_norm.py:53-56)
        saved = y2
        res = (saved, weight, bias, mean if not rms else None, invvar, x.shape)
    else:
        res = (x.reshape(-1, hidden), weight, bias, mean if not rms else None,
               invvar, x.shape)
    return y2.reshape(x.shape), res


def _fused_norm_bwd(hidden, eps, rms, affine, memory_efficient, res, dy):
    saved2, weight, bias, mean, invvar, xshape = res
    dy2 = dy.reshape(-1, hidden)
    if mean is None:
        mean = jnp.zeros_like(invvar)
    dx2, dgamma, dbeta = ln_bwd_pallas(
        dy2, saved2, weight if affine else None,
        bias if (affine and bias is not None) else None, mean, invvar,
        rms=rms, memory_efficient=memory_efficient)
    dx = dx2.reshape(xshape)
    dw = dgamma.astype(weight.dtype).reshape(weight.shape) if affine else None
    db = (dbeta.astype(bias.dtype).reshape(bias.shape)
          if (affine and bias is not None) else None)
    return dx, dw, db


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


# ------------------------------------------------------------- public API


def fused_layer_norm_affine(x, weight, bias, normalized_shape,
                            eps: float = 1e-5, memory_efficient: bool = False):
    """≈ apex fused_layer_norm_affine (fused_layer_norm.py:670)."""
    h = _norm_size(normalized_shape)
    if not _pallas_ok(h):
        return manual_layer_norm(x, weight, bias, normalized_shape, eps)
    return _fused_norm(x, weight, bias, h, eps, False, True, memory_efficient)


def fused_layer_norm(x, normalized_shape, eps: float = 1e-5,
                     memory_efficient: bool = False):
    """≈ apex fused_layer_norm (no affine)."""
    h = _norm_size(normalized_shape)
    if not _pallas_ok(h):
        return manual_layer_norm(x, None, None, normalized_shape, eps)
    return _fused_norm(x, None, None, h, eps, False, False, memory_efficient)


def fused_rms_norm_affine(x, weight, normalized_shape, eps: float = 1e-5,
                          memory_efficient: bool = False):
    """≈ apex fused_rms_norm_affine (fused_layer_norm.py:695)."""
    h = _norm_size(normalized_shape)
    if not _pallas_ok(h):
        return manual_rms_norm(x, weight, normalized_shape, eps)
    return _fused_norm(x, weight, None, h, eps, True, True, memory_efficient)


def fused_rms_norm(x, normalized_shape, eps: float = 1e-5,
                   memory_efficient: bool = False):
    h = _norm_size(normalized_shape)
    if not _pallas_ok(h):
        return manual_rms_norm(x, None, normalized_shape, eps)
    return _fused_norm(x, None, None, h, eps, True, False, memory_efficient)


# ------------------------------------------------------------ flax modules


class FusedLayerNorm(nn.Module):
    """flax module ≈ apex.normalization.FusedLayerNorm (fused_layer_norm.py:724)."""

    normalized_shape: int | Sequence[int]
    eps: float = 1e-5
    elementwise_affine: bool = True
    memory_efficient: bool = False
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = _norm_size(self.normalized_shape)
        if self.elementwise_affine:
            weight = self.param("weight", nn.initializers.ones, (h,),
                                self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros, (h,),
                              self.param_dtype)
            return fused_layer_norm_affine(
                x, weight, bias, h, self.eps, self.memory_efficient)
        return fused_layer_norm(x, h, self.eps, self.memory_efficient)


class FusedRMSNorm(nn.Module):
    """flax module ≈ apex.normalization.FusedRMSNorm (fused_layer_norm.py:841)."""

    normalized_shape: int | Sequence[int]
    eps: float = 1e-5
    elementwise_affine: bool = True
    memory_efficient: bool = False
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = _norm_size(self.normalized_shape)
        if self.elementwise_affine:
            weight = self.param("weight", nn.initializers.ones, (h,),
                                self.param_dtype)
            return fused_rms_norm_affine(
                x, weight, h, self.eps, self.memory_efficient)
        return fused_rms_norm(x, h, self.eps, self.memory_efficient)


class MixedFusedLayerNorm(FusedLayerNorm):
    """Params kept in the IO dtype (≈ MixedFusedLayerNorm :959-1031)."""

    param_dtype: jnp.dtype = jnp.bfloat16


class MixedFusedRMSNorm(FusedRMSNorm):
    param_dtype: jnp.dtype = jnp.bfloat16
