"""KV-cache codec glue consumed by the serve engine.

Block granularity for KV is fixed at ONE (token, head) vector of
``head_dim`` elements. That is the only block shape compatible with
incremental decode: each step appends exactly one token row per head,
so its scale can be computed and written in the same masked
read-modify-write as the payload, and scales inherit every page
behaviour (prefix sharing, COW, LRU eviction, export/import streaming)
by living in arrays shaped like the payload minus the head_dim axis:

- k/v    ``[n_layer, pages, page_size, heads, head_dim]``
- scales ``[n_layer, pages, page_size, heads]``

Scales are fp32. Per head_dim=D that is ``D * storage + 4`` bytes per
(token, head) vs ``4 * D`` unquantized — e.g. D=64: 68 vs 256 bytes,
a 3.76× capacity win; the ``resident_tokens_per_hbm_byte`` gate in
the bench holds the ≥~2× floor.
"""

from __future__ import annotations

import jax.numpy as jnp

from apex_tpu.quant import blockscale

KV_CODECS = ("int8", "mxfp8")


def check_kv_codec(codec) -> None:
    """Build-time validation; every CLI surfaces this as exit 2."""
    if codec is None:
        return
    if codec not in KV_CODECS:
        raise ValueError(
            f"unknown kv_quant codec {codec!r}; expected one of "
            f"{KV_CODECS} or None")
    if codec == "mxfp8" and not blockscale.has_float8():
        raise ValueError(
            "kv_quant='mxfp8' requires float8_e4m3fn support in this "
            "jax build")


def kv_storage_dtype(codec):
    """Storage dtype for K/V payload arrays under ``codec``."""
    check_kv_codec(codec)
    if codec is None:
        return None
    if codec == "int8":
        return jnp.int8
    return jnp.float8_e4m3fn


def encode_kv(codec: str, x: jnp.ndarray):
    """Encode ``[..., heads, head_dim]`` -> (codes, scales[..., heads])."""
    block = int(x.shape[-1])
    if codec == "int8":
        codes, scales = blockscale.encode_int8(x, block)
    elif codec == "mxfp8":
        codes, scales = blockscale.encode_mxfp8(x, block)
    else:
        raise ValueError(f"unknown kv_quant codec {codec!r}")
    # block == head_dim, so the blocked codec emits exactly one scale
    # per (token, head): drop that singleton block axis — KV scale
    # planes are shaped like the payload minus head_dim
    return codes, scales[..., 0]


def decode_kv(codes: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Dequantize per-(token, head) codes back to fp32."""
    return codes.astype(jnp.float32) * scales[..., None]
