"""Blockwise (flash) attention — Pallas TPU kernels, forward and backward.

Role in the framework: the fused replacement for the reference's attention
stack — megatron ``scaled_(upper_triang_)masked_softmax`` kernels
(csrc/megatron/scaled_masked_softmax.h:106-333) composed with the batched
matmuls around them, and the capability of the removed
``apex.contrib.fast_multihead_attn`` (BASELINE.md config 5 note). Instead of
materializing the (sq, sk) score matrix and running a softmax kernel over it,
the TPU-native design streams K/V blocks through VMEM with an online softmax —
O(sq·d) memory, MXU-resident matmuls, fp32 accumulators.

Round-2 generality (VERDICT item 4), matching the reference capability:

- **Arbitrary additive bias / boolean mask**, block-streamed through the
  kernel (≈ scaled_masked_softmax.h:211's arbitrary mask). ``mask`` follows
  the megatron convention (True = masked) and hard-masks with -inf semantics:
  fully-masked rows produce zero output, like the generic masked softmax.
- **Attention dropout** (the removed fast_multihead_attn capability):
  counter-based in-kernel PRNG seeded per (batch·head, q-block, k-block) so
  the backward pass regenerates the identical mask from the same seed without
  materializing it.
- **Ragged sequence lengths**: inputs are padded to the block grid and padded
  key columns are masked in-kernel; no ``sq % block == 0`` requirement.

Layout: q (b, h, sq, d), k/v (b, h, sk, d). Grid (b·h, q_blocks, k_blocks)
with the k axis innermost: TPU grid iterations are sequential, so the running
max / sum / accumulator live in VMEM scratch across k steps (the standard TPU
flash pattern; double-buffered HBM→VMEM pipelining is generated by Mosaic
from the BlockSpecs).

Backward follows the two-kernel FlashAttention-2 scheme: a dq kernel
(grid over q blocks, streaming k) and a dk/dv kernel (grid over k blocks,
streaming q), both recomputing P from the saved row logsumexp; D = rowsum(dO·O)
is one fused XLA reduction outside. When (and only when) a user-supplied
additive bias is differentiated, the dq kernel additionally emits dlogits
blocks as the bias cotangent; mask-only calls statically skip that output so
the backward stays O(s·d) memory.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas.tpu import CompilerParams as _CompilerParams
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.tune.api import pow2_bucket, tuned_params
from apex_tpu.utils.env import interpret_default
from apex_tpu.utils.tiling import round_up as _round_up

_f32 = jnp.float32
# Block sizes sized for per-step MXU occupancy: at (bq, bk) = (512, 1024)
# and d = 64 a grid step is ~270 MFLOPs with VMEM ~1.5 MB incl. double
# buffering. The only measured datapoint is the OLD (256, 512) defaults:
# ~10% MXU on v5e at s=2048, overhead-bound. (512, 1024) is the sizing
# hypothesis that per-iteration overhead amortizes to noise — it has NOT
# been measured on chip yet; `apex-tpu-tune --kernels flash_attention`
# sweeps it (docs/performance.md tracks measurement status).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024

# grid is (batch·head, outer blocks, inner accumulation blocks): the first
# two dims are independent, only the innermost carries the online-softmax /
# gradient accumulation — declaring that lets Mosaic pipeline freely
_DIMSEM = _CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))
NEG_INF = -1e30
# scores at or below this are "hard masked": excluded from the softmax support
# entirely (p forced to 0) rather than merely dominated
_MASK_EDGE = 0.5 * NEG_INF


def _causal_run(qi, kj, bq, bk):
    # block participates unless it lies entirely above the diagonal
    return kj * bk <= qi * bq + bq - 1


def _kv_index_map(causal, bq, bk):
    """k/v BlockSpec index map for grids (bh, qi, kj). For causal grids,
    kj is clamped to the last block that intersects the lower triangle:
    the skipped (above-diagonal) steps then repeat the previous block
    index and Mosaic elides their HBM->VMEM copies entirely — the k/v
    bytes of skipped blocks are never fetched."""
    if not causal:
        return lambda b_, i, j: (b_, j, 0)

    def imap(b_, i, j):
        limit = (i * bq + bq - 1) // bk
        return (b_, jnp.minimum(j, limit), 0)

    return imap


def _q_index_map_dkv(causal, bq, bk, nq):
    """q-stream index maps for the dkv grid (bh, kj, qi): for causal, qi
    is clamped UP to the first row block at or below the diagonal, so the
    skipped leading steps repeat a block index and skip their copies.
    ``first`` is additionally clamped to the last real q block: causal
    cross-attention with sk > sq has k blocks past every q row, and the
    raw division would index out of bounds (those steps are all
    run=False, but the index map must stay in range regardless)."""
    if not causal:
        return lambda b_, j, i: (b_, i, 0)

    def imap(b_, j, i):
        first = jnp.minimum((j * bk) // bq, nq - 1)
        return (b_, jnp.maximum(i, first), 0)

    return imap


def _block_mask(qi, kj, bq, bk):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + kj * bk
    return cols <= rows


def _pad_axis(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fold_scale(q, scale):
    """Fold the softmax scale into q when EXACT (scale is a power of two —
    true for the 1/√d of d ∈ {16, 64, 256, 1024}): one (sq, d) multiply
    total instead of a (bq, bk) VPU pass per grid step, with zero rounding
    (a power-of-two multiply only shifts the exponent, even in bf16).
    Non-power-of-two scales (d = 32, 128, …) keep the exact post-dot fp32
    multiply. Returns ``(q_maybe_scaled, kernel_scale)`` where
    ``kernel_scale`` is the static residual the kernels must still apply
    (1.0 when folded)."""
    frac, _ = math.frexp(scale)
    if frac == 0.5:  # exactly a power of two
        return (q.astype(_f32) * scale).astype(q.dtype), 1.0
    return q, float(scale)


def _scores(q_ref, k_ref, bias_ref, *, kscale, causal, qi, kj, bq, bk,
            sk_orig, masked=True):
    """(q·kᵀ)·kscale + bias with causal / key-padding hard-masking (fp32).
    ``kscale`` is 1.0 when the softmax scale was folded into q
    (see :func:`_fold_scale`) — the multiply then folds away statically.

    The dot runs in the INPUT dtype (bf16 on the training path) with fp32
    accumulation — upcasting q/k first would force the MXU onto its fp32
    path (~4x slower on v5e) for zero accuracy gain over bf16·bf16→fp32.

    ``masked=False`` statically skips the causal/padding iota+select work —
    the caller proves (via ``_mask_split``) that no element of this block is
    masked, so interior blocks pay only the matmul + bias add."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=_f32)
    if kscale != 1.0:
        s = s * kscale
    if bias_ref is not None:
        s = s + bias_ref[0].astype(_f32)
    if masked and causal:
        s = jnp.where(_block_mask(qi, kj, bq, bk), s, NEG_INF)
    if masked and sk_orig % bk != 0:
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + kj * bk
        s = jnp.where(cols < sk_orig, s, NEG_INF)
    return s


def _mask_split(causal, qi, kj, bq, bk, sk_orig, num_k):
    """(run, needs_mask) for block (qi, kj).

    ``run``: the block contributes at all (False only for causal blocks
    entirely above the diagonal). ``needs_mask``: at least one element of
    the block is causal-masked (straddles the diagonal) or is a padded key
    column (last k block when sk % bk != 0) — everything else can take the
    mask-free fast path. ``needs_mask`` is Python ``False`` (static) when
    neither source of masking exists, collapsing the kernel to one branch.
    """
    run = _causal_run(qi, kj, bq, bk) if causal else True
    needs = False
    if causal:
        needs = kj * bk + bk - 1 > qi * bq
    if sk_orig % bk != 0:
        pad = kj == num_k - 1
        needs = pad if needs is False else jnp.logical_or(needs, pad)
    return run, needs


def _when_split(run, needs, make_step):
    """Run ``make_step(masked)`` under the (run, needs_mask) predicates:
    one static branch when no masking can occur, else a masked and an
    unmasked arm of which exactly one executes per grid step."""
    if needs is False:
        pl.when(run)(make_step(False))
    else:
        pl.when(jnp.logical_and(run, needs))(make_step(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(needs)))(
            make_step(False))


def _dropout_keep(seed_ref, b_, qi, kj, bq, bk, dropout_p):
    """Regenerable keep-mask scaled by 1/(1-p).

    Counter-based: a stateless splitmix/murmur-style integer hash of
    (seed, batch·head, global row, global col). Pure uint32 VPU arithmetic,
    so it lowers identically on compiled TPU and CPU interpret mode, the
    backward kernels regenerate the exact mask from the same seed, and the
    mask is independent of the block decomposition.
    """
    u32 = jnp.uint32
    row = (jax.lax.broadcasted_iota(u32, (bq, bk), 0)
           + jax.lax.convert_element_type(qi * bq, u32))
    col = (jax.lax.broadcasted_iota(u32, (bq, bk), 1)
           + jax.lax.convert_element_type(kj * bk, u32))
    x = (row * u32(0xD2511F53)
         ^ col * u32(0xCD9E8D57)
         ^ jax.lax.convert_element_type(b_, u32) * u32(0x85EBCA6B)
         ^ seed_ref[0].astype(u32) * u32(0x9E3779B9))
    x ^= x >> u32(16)
    x *= u32(0x7FEB352D)
    x ^= x >> u32(15)
    x *= u32(0x846CA68B)
    x ^= x >> u32(16)
    threshold = u32(min(int(dropout_p * (2.0 ** 32)), 2 ** 32 - 1))
    return jnp.where(x >= threshold, 1.0 / (1.0 - dropout_p), 0.0)


# ------------------------------------------------------------------ forward


def _unpack(refs, has_bias, dropout_p):
    """Split *refs into (q, k, v, bias?, seed?, rest) by the fwd input
    layout: q, k, v, [bias], [seed]."""
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = seed_ref = None
    if has_bias:
        bias_ref = refs[idx]
        idx += 1
    if dropout_p > 0.0:
        seed_ref = refs[idx]
        idx += 1
    return q_ref, k_ref, v_ref, bias_ref, seed_ref, refs[idx:]


def _unpack_bwd(refs, has_bias, dropout_p):
    """Split *refs by the bwd input layout: q, k, v, [bias], do, lse, dvec,
    [seed]. Returns (q, k, v, bias?, do, lse, dvec, seed?, rest) — the single
    source of truth for BOTH backward kernels' operand offsets."""
    q_ref, k_ref, v_ref = refs[:3]
    idx = 3
    bias_ref = seed_ref = None
    if has_bias:
        bias_ref = refs[idx]
        idx += 1
    do_ref, lse_ref, dvec_ref = refs[idx:idx + 3]
    idx += 3
    if dropout_p > 0.0:
        seed_ref = refs[idx]
        idx += 1
    return (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref,
            seed_ref, refs[idx:])


def _fa_fwd_kernel(*refs, kscale, causal, bq, bk, sk_orig, has_bias,
                   dropout_p):
    q_ref, k_ref, v_ref, bias_ref, seed_ref, rest = _unpack(
        refs, has_bias, dropout_p)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest

    b_ = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run, needs = _mask_split(causal, qi, kj, bq, bk, sk_orig,
                             pl.num_programs(2))

    def make_step(masked):
        def _step():
            s = _scores(q_ref, k_ref, bias_ref, kscale=kscale,
                        causal=causal, qi=qi, kj=kj, bq=bq, bk=bk,
                        sk_orig=sk_orig, masked=masked)
            m_prev = m_scr[...][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # hard-masked rows keep m == NEG_INF; shift by 0 there so exp()
            # underflows to 0 instead of exp(0) == 1 (zero-output rows)
            m_safe = jnp.where(m_new <= _MASK_EDGE, 0.0, m_new)
            p = jnp.exp(s - m_safe)
            alpha = jnp.exp(jnp.where(m_prev <= _MASK_EDGE, NEG_INF, m_prev)
                            - m_safe)
            l_new = (l_scr[...][:, :1] * alpha
                     + jnp.sum(p, axis=1, keepdims=True))
            if dropout_p > 0.0:
                p_v = p * _dropout_keep(seed_ref, b_, qi, kj, bq, bk,
                                        dropout_p)
            else:
                p_v = p
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
                p_v.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=_f32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return _step

    _when_split(run, needs, make_step)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _fin():
        l = l_scr[...][:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        m = m_scr[...][:, :1]
        lse_ref[0] = jnp.where(m <= _MASK_EDGE, NEG_INF,
                               m + jnp.log(safe_l))


def _pick_blocks(sq, sk, block_q, block_k):
    """Block sizes honoring TPU tiling (8-sublane / 128-lane granularity)."""
    bq = block_q if sq >= block_q else _round_up(sq, 8)
    bk = block_k if sk >= block_k else _round_up(sk, 128)
    return bq, bk


def _blocks_ok(params) -> bool:
    bq, bk = params["block_q"], params["block_k"]
    return (isinstance(bq, int) and isinstance(bk, int)
            and bq > 0 and bq % 8 == 0 and bk > 0 and bk % 128 == 0)


def validate_blocks(block_q: int, block_k: int, sq: int, sk: int) -> None:
    """Reject block sizes that cannot tile the (padded) sequence grid.

    Sequences pad UP to a block multiple, so the only way a user-passed
    block can break the kernel is misalignment: ``block_q`` must be a
    positive multiple of 8 (sublanes) and ``block_k`` a positive multiple
    of 128 (lanes). Anything else used to either silently over-pad or die
    deep inside ``pallas_call`` with a Mosaic layout error — now it is a
    ``ValueError`` at the API boundary.
    """
    if not _blocks_ok({"block_q": block_q, "block_k": block_k}):
        raise ValueError(
            f"flash_attention block_q={block_q!r}/block_k={block_k!r} "
            f"invalid: block_q must be a positive multiple of 8 and "
            f"block_k a positive multiple of 128 (TPU sublane/lane "
            f"granularity) so they tile the padded sequence lengths")
    bq, bk = _pick_blocks(sq, sk, block_q, block_k)
    sq_p, sk_p = _round_up(sq, bq), _round_up(sk, bk)
    if sq_p % bq or sk_p % bk:  # unreachable by construction; belt+braces
        raise ValueError(
            f"flash_attention blocks ({bq}, {bk}) do not divide the padded "
            f"sequence lengths ({sq_p}, {sk_p})")


def _resolve_blocks(sq, sk, d, causal, dtype, block_q, block_k):
    """Public-API block resolution: when the caller passes nothing, use
    the tuned cache entry for this (shape-bucket, dtype, chip) and fall
    back to the committed defaults; an explicit block is validated and
    honored as-is (partial overrides fill the other side from the
    defaults)."""
    if block_q is None and block_k is None:
        p = tuned_params(
            "flash_attention",
            (("sq", pow2_bucket(sq)), ("sk", pow2_bucket(sk)), ("d", d),
             ("causal", causal)),
            {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K},
            dtype=dtype, validate=_blocks_ok)
        return p["block_q"], p["block_k"]
    bq = DEFAULT_BLOCK_Q if block_q is None else block_q
    bk = DEFAULT_BLOCK_K if block_k is None else block_k
    validate_blocks(bq, bk, sq, sk)
    return bq, bk


class _BiasPlan:
    """Blockwise streaming plan for a broadcastable (B,H,SQ,SK) bias.

    Broadcast dims stay UNMATERIALIZED: a (b,1,1,sk) key-padding mask is
    streamed as (b, 1, sk_p) blocks of shape (1, 1, bk) — never expanded to
    the full (b·h, sq, sk) array (which at long sequence would dwarf the
    O(s·d) memory the flash kernel exists to guarantee).
    """

    def __init__(self, bias, b, h, bq, bk, sq, sk, sq_p, sk_p):
        B, H, SQ, SK = bias.shape
        assert B in (1, b) and H in (1, h) and SQ in (1, sq) and SK in (1, sk)
        self.full_q = SQ != 1
        self.full_k = SK != 1
        arr = bias.reshape(B * H, SQ, SK)
        pads = ((0, 0), (0, sq_p - sq if self.full_q else 0),
                (0, sk_p - sk if self.full_k else 0))
        self.arr = jnp.pad(arr, pads)
        self.block = (1, bq if self.full_q else 1, bk if self.full_k else 1)
        if B == 1 and H == 1:
            bidx = lambda b_: 0  # noqa: E731
        elif B == 1:
            bidx = lambda b_: b_ % h  # noqa: E731
        elif H == 1:
            bidx = lambda b_: b_ // h  # noqa: E731
        else:
            bidx = lambda b_: b_  # noqa: E731
        fq, fk = self.full_q, self.full_k

        def imap_qk(b_, i, j):  # grid (bh, q, k)
            return (bidx(b_), i if fq else 0, j if fk else 0)

        def imap_kq(b_, j, i):  # grid (bh, k, q) — dkv kernel
            return (bidx(b_), i if fq else 0, j if fk else 0)

        self.imap_qk = imap_qk
        self.imap_kq = imap_kq

    def spec(self, transposed=False):
        return pl.BlockSpec(self.block,
                            self.imap_kq if transposed else self.imap_qk,
                            memory_space=pltpu.VMEM)


def flash_attention_fwd(q, k, v, *, scale, causal, bias=None,
                        dropout_p=0.0, dropout_seed=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=None):
    if interpret is None:
        interpret = interpret_default()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    validate_blocks(block_q, block_k, sq, sk)
    bq, bk = _pick_blocks(sq, sk, block_q, block_k)
    q_s, kscale = _fold_scale(q, scale)
    q3 = _pad_axis(q_s.reshape(b * h, sq, d), 1, bq)
    k3 = _pad_axis(k.reshape(b * h, sk, d), 1, bk)
    v3 = _pad_axis(v.reshape(b * h, sk, d), 1, bk)
    bh, sq_p, _ = q3.shape
    sk_p = k3.shape[1]
    grid = (bh, sq_p // bq, sk_p // bk)

    kv_map = _kv_index_map(causal, bq, bk)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), kv_map, memory_space=pltpu.VMEM),
    ]
    operands = [q3, k3, v3]
    if bias is not None:
        plan = _BiasPlan(bias, b, h, bq, bk, sq, sk, sq_p, sk_p)
        in_specs.append(plan.spec())
        operands.append(plan.arr)
    if dropout_p > 0.0:
        seed = (jnp.zeros((1,), jnp.int32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)

    o, lse = pl.pallas_call(
        functools.partial(_fa_fwd_kernel, kscale=kscale, causal=causal,
                          bq=bq, bk=bk, sk_orig=sk,
                          has_bias=bias is not None, dropout_p=dropout_p),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_p, 1), _f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), _f32),
            pltpu.VMEM((bq, 128), _f32),
            pltpu.VMEM((bq, d), _f32),
        ],
        compiler_params=_DIMSEM,
        interpret=interpret,
    )(*operands)
    return (o[:, :sq].reshape(b, h, sq, d),
            lse[:, :sq].reshape(b, h, sq))


# ----------------------------------------------------------------- backward


def _bwd_p(s, lse):
    """P = exp(s - lse) with hard-masked entries (and fully-masked or padded
    rows, whose saved lse is NEG_INF) forced to exactly 0."""
    lse_safe = jnp.where(lse <= _MASK_EDGE, 0.0, lse)
    dead = (s <= _MASK_EDGE) | (lse <= _MASK_EDGE)
    return jnp.where(dead, 0.0, jnp.exp(s - lse_safe))


def _fa_dq_kernel(*refs, scale, kscale, causal, bq, bk, sk_orig, has_bias,
                  dropout_p, want_dbias):
    (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref, seed_ref,
     rest) = _unpack_bwd(refs, has_bias, dropout_p)
    if want_dbias:
        dq_ref, dbias_ref, dq_scr = rest
    else:
        dq_ref, dq_scr = rest
        dbias_ref = None

    b_ = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run, needs = _mask_split(causal, qi, kj, bq, bk, sk_orig,
                             pl.num_programs(2))

    def make_step(masked):
        def _step():
            s = _scores(q_ref, k_ref, bias_ref, kscale=kscale,
                        causal=causal, qi=qi, kj=kj, bq=bq, bk=bk,
                        sk_orig=sk_orig, masked=masked)
            p = _bwd_p(s, lse_ref[0])
            # dots stay in the input dtype (MXU bf16 path); see _scores
            dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=_f32)
            if dropout_p > 0.0:
                dp = dp * _dropout_keep(seed_ref, b_, qi, kj, bq, bk,
                                        dropout_p)
            dl = p * (dp - dvec_ref[0])
            if dbias_ref is not None:
                dbias_ref[0] = dl.astype(dbias_ref.dtype)
            dq_scr[...] += jax.lax.dot_general(
                (dl * scale).astype(k_ref.dtype), k_ref[0],
                (((1,), (0,)), ((), ())), preferred_element_type=_f32)
        return _step

    _when_split(run, needs, make_step)

    if want_dbias:
        @pl.when(jnp.logical_not(run))
        def _zero_bias():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(kj == pl.num_programs(2) - 1)
    def _fin():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_dkv_kernel(*refs, kscale, causal, bq, bk, sk_orig, has_bias,
                   dropout_p):
    (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, dvec_ref, seed_ref,
     rest) = _unpack_bwd(refs, has_bias, dropout_p)
    dk_ref, dv_ref, dk_scr, dv_scr = rest

    b_ = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run, needs = _mask_split(causal, qi, kj, bq, bk, sk_orig,
                             pl.num_programs(1))

    def make_step(masked):
        def _step():
            s = _scores(q_ref, k_ref, bias_ref, kscale=kscale,
                        causal=causal, qi=qi, kj=kj, bq=bq, bk=bk,
                        sk_orig=sk_orig, masked=masked)
            p = _bwd_p(s, lse_ref[0])
            if dropout_p > 0.0:
                keep = _dropout_keep(seed_ref, b_, qi, kj, bq, bk, dropout_p)
                p_v = p * keep
            else:
                keep = None
                p_v = p
            # dots stay in the input dtype (MXU bf16 path); see _scores
            dv_scr[...] += jax.lax.dot_general(
                p_v.astype(do_ref.dtype), do_ref[0],
                (((0,), (0,)), ((), ())), preferred_element_type=_f32)
            dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=_f32)
            if keep is not None:
                dp = dp * keep
            # folded (kscale==1): q_ref holds scale·q, so dotting the
            # unscaled ds with it yields dk = p(dp-D)·scale·qᵀ for free;
            # unfolded: apply the scale here (q_ref is raw)
            ds = p * (dp - dvec_ref[0])
            if kscale != 1.0:
                ds = ds * kscale
            dk_scr[...] += jax.lax.dot_general(
                ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=_f32)
        return _step

    _when_split(run, needs, make_step)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _fin():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, scale, causal, bias=None,
                        dropout_p=0.0, dropout_seed=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=None, want_dbias=False):
    if interpret is None:
        interpret = interpret_default()
    b, h, sq, d = q.shape
    sk = k.shape[2]
    validate_blocks(block_q, block_k, sq, sk)
    bq, bk = _pick_blocks(sq, sk, block_q, block_k)
    bh = b * h
    dvec = jnp.sum(do.astype(_f32) * o.astype(_f32), axis=-1)  # (b,h,sq)

    # scale folded into q, mirroring the forward (see _fold_scale)
    q_s, kscale = _fold_scale(q, scale)
    q3 = _pad_axis(q_s.reshape(bh, sq, d), 1, bq)
    k3 = _pad_axis(k.reshape(bh, sk, d), 1, bk)
    v3 = _pad_axis(v.reshape(bh, sk, d), 1, bk)
    do3 = _pad_axis(do.reshape(bh, sq, d), 1, bq)
    # padded q rows: lse pads to NEG_INF so p == 0 there (no dk/dv pollution)
    lse1 = lse.reshape(bh, sq, 1)
    pad_q = (-sq) % bq
    lse2 = jnp.pad(lse1, ((0, 0), (0, pad_q), (0, 0)),
                   constant_values=NEG_INF)
    dvec2 = _pad_axis(dvec.reshape(bh, sq, 1), 1, bq)
    sq_p, sk_p = q3.shape[1], k3.shape[1]

    has_bias = bias is not None
    if has_bias:
        plan = _BiasPlan(bias, b, h, bq, bk, sq, sk, sq_p, sk_p)

    def seed_ops():
        if dropout_p <= 0.0:
            return [], []
        seed = (jnp.zeros((1,), jnp.int32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.int32).reshape(1))
        return [pl.BlockSpec(memory_space=pltpu.SMEM)], [seed]

    want_dbias = want_dbias and has_bias
    kw = dict(kscale=kscale, causal=causal, bq=bq, bk=bk, sk_orig=sk,
              has_bias=has_bias, dropout_p=dropout_p)

    qspec = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                         memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, bk, d), _kv_index_map(causal, bq, bk),
                         memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, bq, 1), lambda b_, i, j: (b_, i, 0),
                           memory_space=pltpu.VMEM)

    in_specs = [qspec, kspec, kspec]
    operands = [q3, k3, v3]
    if has_bias:
        in_specs.append(plan.spec())
        operands.append(plan.arr)
    in_specs += [qspec, rowspec, rowspec]
    operands += [do3, lse2, dvec2]
    sspec, sops = seed_ops()
    in_specs += sspec
    operands += sops

    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype)]
    if want_dbias:
        # dbias requires the materialized (bh, sq_p, sk_p) dlogits — only
        # pay that O(s²) memory when the bias is actually differentiated
        out_specs.append(pl.BlockSpec((1, bq, bk),
                                      lambda b_, i, j: (b_, i, j),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((bh, sq_p, sk_p), _f32))

    dq_out = pl.pallas_call(
        functools.partial(_fa_dq_kernel, scale=scale,
                          want_dbias=want_dbias, **kw),
        grid=(bh, sq_p // bq, sk_p // bk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, d), _f32)],
        compiler_params=_DIMSEM,
        interpret=interpret,
    )(*operands)
    dq = dq_out[0]
    dbias = None
    if want_dbias:
        dlogits = dq_out[1][:, :sq, :sk].reshape(b, h, sq, sk)
        # reduce to the user's (possibly broadcast) bias shape
        bb, bh_, bsq, bsk = bias.shape
        red = tuple(ax for ax, n in zip(range(4), (bb, bh_, bsq, bsk))
                    if n == 1 and dlogits.shape[ax] != 1)
        dbias = jnp.sum(dlogits, axis=red, keepdims=True).astype(bias.dtype)

    # dk/dv: swap the roles — grid (bh, k_blocks, q_blocks)
    qmap2 = _q_index_map_dkv(causal, bq, bk, sq_p // bq)
    qspec2 = pl.BlockSpec((1, bq, d), qmap2, memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, bk, d), lambda b_, j, i: (b_, j, 0),
                          memory_space=pltpu.VMEM)

    def rowmap2(b_, j, i):
        _, iq, _ = qmap2(b_, j, i)
        return (b_, iq, 0)

    rowspec2 = pl.BlockSpec((1, bq, 1), rowmap2, memory_space=pltpu.VMEM)
    in_specs2 = [qspec2, kspec2, kspec2]
    operands2 = [q3, k3, v3]
    if has_bias:
        in_specs2.append(plan.spec(transposed=True))
        operands2.append(plan.arr)
    in_specs2 += [qspec2, rowspec2, rowspec2]
    operands2 += [do3, lse2, dvec2]
    in_specs2 += sspec
    operands2 += sops

    dk, dv = pl.pallas_call(
        functools.partial(_fa_dkv_kernel, **kw),
        grid=(bh, sk_p // bk, sq_p // bq),
        in_specs=in_specs2,
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct((bh, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), _f32),
                        pltpu.VMEM((bk, d), _f32)],
        compiler_params=_DIMSEM,
        interpret=interpret,
    )(*operands2)

    return (dq[:, :sq].reshape(b, h, sq, d),
            dk[:, :sk].reshape(b, h, sk, d),
            dv[:, :sk].reshape(b, h, sk, d), dbias)


# --------------------------------------------------------------- public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention(q, k, v, bias, causal, scale, dropout_p, block_q,
                     block_k, want_dbias):
    o, _ = flash_attention_fwd(
        q, k, v, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=None,
        block_q=block_q, block_k=block_k)
    return o


def _fa_vjp_fwd(q, k, v, bias, causal, scale, dropout_p, block_q, block_k,
                want_dbias):
    o, lse = flash_attention_fwd(
        q, k, v, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=None,
        block_q=block_q, block_k=block_k)
    return o, (q, k, v, bias, o, lse)


def _fa_vjp_bwd(causal, scale, dropout_p, block_q, block_k, want_dbias,
                res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv, dbias = flash_attention_bwd(
        q, k, v, o, lse, do, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=None,
        block_q=block_q, block_k=block_k, want_dbias=want_dbias)
    return dq, dk, dv, dbias


_flash_attention.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)


# dropout variant: the seed is a traced operand (varies per training step
# under one jit), so it is a separate custom_vjp with the seed as an arg
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_attention_dropout(q, k, v, bias, seed, causal, scale, dropout_p,
                             block_q, block_k, want_dbias):
    o, _ = flash_attention_fwd(
        q, k, v, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=seed,
        block_q=block_q, block_k=block_k)
    return o


def _fad_vjp_fwd(q, k, v, bias, seed, causal, scale, dropout_p, block_q,
                 block_k, want_dbias):
    o, lse = flash_attention_fwd(
        q, k, v, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=seed,
        block_q=block_q, block_k=block_k)
    return o, (q, k, v, bias, seed, o, lse)


def _fad_vjp_bwd(causal, scale, dropout_p, block_q, block_k, want_dbias,
                 res, do):
    q, k, v, bias, seed, o, lse = res
    dq, dk, dv, dbias = flash_attention_bwd(
        q, k, v, o, lse, do, scale=scale, causal=causal, bias=bias,
        dropout_p=dropout_p, dropout_seed=seed,
        block_q=block_q, block_k=block_k, want_dbias=want_dbias)
    return dq, dk, dv, dbias, None


_flash_attention_dropout.defvjp(_fad_vjp_fwd, _fad_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None, *, bias=None, mask=None,
                    dropout_p: float = 0.0, dropout_seed=None,
                    bias_requires_grad: bool = True):
    """Fused scaled-dot-product attention. q/k/v: (b, h, s, d).

    ``scale`` defaults to 1/sqrt(d) (the attention convention the megatron
    softmax kernels receive pre-applied). ``block_q``/``block_k`` default
    to the autotuned geometry for this (shape, dtype, chip) when the tune
    cache has one (``apex_tpu.tune``), else the committed
    ``DEFAULT_BLOCK_Q``/``DEFAULT_BLOCK_K``; explicit values are validated
    (positive, 8-sublane / 128-lane aligned) and honored as-is. ``bias``
    is an additive logits bias broadcastable to (b, h, sq, sk) —
    differentiable. ``mask`` is a boolean array of the same
    broadcastability, True = masked (megatron convention,
    scaled_masked_softmax.h:211); fully-masked rows yield zero output like
    the generic masked softmax. ``dropout_p`` applies attention dropout
    with a counter-based in-kernel PRNG; ``dropout_seed`` (int32 array or
    python int) varies the mask across steps.

    Differentiating a bias requires materializing the (sq, sk) dlogits in
    the backward — O(s²) memory. Pass ``bias_requires_grad=False`` for
    constant biases (ALiBi slopes, fixed relative-position tables) to keep
    the backward at the flash O(s·d) footprint; a ``mask`` alone never pays
    the cost.
    """
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _resolve_blocks(
        q.shape[2], k.shape[2], q.shape[3], causal, q.dtype,
        block_q, block_k)
    want_dbias = bias is not None and bias_requires_grad
    if mask is not None:
        if mask.ndim != 4:
            raise ValueError("mask must be rank-4 broadcastable to "
                             "(b, h, sq, sk)")
        mbias = jnp.where(mask, NEG_INF, 0.0).astype(_f32)
        mbias = jax.lax.stop_gradient(mbias)
        bias = mbias if bias is None else bias + mbias
    if dropout_p > 0.0:
        if dropout_seed is None:
            raise ValueError(
                "dropout_p > 0 requires dropout_seed (vary it per training "
                "step — a fixed seed would drop the same attention entries "
                "every step)")
        seed = jnp.asarray(dropout_seed, jnp.int32)
        return _flash_attention_dropout(q, k, v, bias, seed, causal, s,
                                        float(dropout_p), block_q, block_k,
                                        want_dbias)
    return _flash_attention(q, k, v, bias, causal, s, 0.0, block_q, block_k,
                            want_dbias)
