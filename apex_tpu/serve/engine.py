"""AOT batched prefill + one-jit decode over the static, paged KV cache.

The engine owns three compiled artifacts and NOTHING else touches the
device. It owns the cache's buffers too: **the pool is resident**.
Every program below that takes the cache and returns it is given it by
donation, and the pool's shape (whole tiles a token: ``kv_cache``, "the
resident pool") makes the runtime's default layout the one the programs
work in, so a call's writes land in the buffers it was handed and no
call copies a pool; whoever kept ``engine.cache`` across a call holds
deleted arrays, and a call that fails after the donation raises
:class:`PoolLost` over a re-initialised pool (docs/serving.md, "Who owns
the pool"):

- ``decode_step`` — ONE jitted function, ``[num_slots]`` tokens in,
  ``[num_slots]`` sampled tokens out. Admission, completion, eviction, and
  backfill all happen by changing *values* (masks, lengths, page-table
  rows), so the jit cache holds exactly one entry for the life of the
  engine — asserted by tier-1 (``Engine.decode_traces``).
- ``prefill`` — ONE batched forward over the rows a call admits (the
  same ``gpt2_token_forward``, in its chunk form): ``[num_slots,
  bucket]``, or the bucket's small ``[rows, bucket]`` program over a row
  view of the cache where the batch fits it (:func:`prefill_rows`: the
  row count follows from the engine; an engine whose full program is
  small already keeps that one). The prompts' positions go
  through the layers together, so a call reads every weight once; their
  K/V take one masked scatter a layer (rows of non-admitted slots and
  of padding are dropped); a row attends causally over its own chunk
  and, after a prefix hit, over the cached head in a loop whose trip
  count is data; the logits are those of each admitted slot's last real
  position. At most two compiles per pow2 prompt-length bucket, one
  device run a call. Prefill and decode are the same mathematics in another order of
  float32 sums (a batched product against a one-row product, a softmax
  over the chunk against ``block_k`` chunks of the cache), so an
  incrementally decoded token's logits match the same token's logits
  under full-sequence prefill to rounding, not to the bit
  (docs/serving.md has what stays bit-exact).
- ``evict`` — a mask-shaped length reset (kv_cache.evict_slots), one
  compile total; only ``lengths`` goes through it.

**The cache is a paged pool**
(:class:`~apex_tpu.serve.kv_cache.PagedKVCache`): ``page_size`` tokens a
page, a per-slot page table that is DATA threaded through the compiled
calls, host-side allocation in :mod:`apex_tpu.serve.paging`. With no
``EngineConfig.page_size`` a page is ``max_len`` tokens: one page a
slot, ``num_slots + 1`` pages, every slot's whole context reserved and
no admission ever short of pages. A smaller page lets mixed-length
traffic share the pool (``num_pages`` sizes it). Where the pages lie
does not enter the attention arithmetic, so two page sizes are
**bit-exact in fp32 against each other** on identical request traces at
the same ``block_k`` (tier-1 holds several pages a slot against one; the
default chunk follows the page, so pin ``block_k`` for bitwise
comparison). With ``prefix_cache=True`` a hash-based prefix
index shares read-only prompt pages across requests: a request whose
prompt prefix is already resident skips prefill for those pages (the
call covers only the tail; a partially-used boundary page is
copied-on-write first), which is what removes the repeated fleet-wide
system-prompt prefill. Pages for a request's whole admitted budget are
reserved at admission, so decode can never page-fault mid-stream —
conservative, but it keeps admission the single choke point
(``serve_page_alloc_fail`` accounts the stall when the pool is the
bottleneck).

**Tensor-parallel mode** (``EngineConfig(tp=N)``) shards the whole
engine over a 1-D ``NamedSharding`` mesh on the **head axis**: params
(q/k/v columns, output-projection rows, MLP slices — see
:mod:`apex_tpu.serve.tp`) and the pool's K/V bytes shard per
head block, while ``lengths``, the page table, and every scheduler-side
structure stay replicated data — so the allocator, prefix index,
journal, and scheduler are mesh-agnostic and the one-compile invariant
becomes **one compile per mesh shape** (``decode_traces`` still reads
1). The per-rank forward runs under ``shard_map`` inside the SAME
jitted decode step and prefill call; per-layer cross-rank sync is
``tp_sync="exact"`` (all-gather concatenation — **bit-identical in fp32
to the single-chip engine at equal ``block_k``**, greedy and sampled;
the tier-1 oracle), ``"overlap"`` (TokenWeave: the two per-layer
all-reduces each split into slot halves interleaved with norm/residual
compute so async collectives hide behind compute on real chips), or
``"relaxed"`` (partially-synchronized activations: ONE deferred
all-reduce per layer; opt-in approximation). Sampling runs on the full
replicated logits outside ``shard_map``, so the PRNG key path — and
with it sampled-stream replay — is identical to a single chip.

Sampling (temperature / top-k, greedy at ``temperature=0``) runs inside
the jitted step under a threaded PRNG key: the key is part of engine
state, split in-graph, and returned — a fixed seed replays a stream
bit-for-bit.

**Speculative mode** (``EngineConfig(spec_draft_len=K)``) adds a FOURTH
compiled artifact: ``verify`` — a ``lax.scan`` of the one-token decode
forward over ``K + 1`` positions, the only scan of the token forward the
engine has (column 0 re-feeds the slot's last committed token,
columns 1..K are host-side draft guesses from
:class:`~apex_tpu.serve.spec.NGramDrafter`). Acceptance is exact and
in-graph: position ``p``'s logits produce the target policy's own next
token, a draft is committed iff it equals that target, and the leading
match run plus one bonus token advance the slot — ``set_lengths``
truncation rolls back every rejected draft row (the evict mechanism:
K/V beyond ``lengths`` is unreachable because attention reachability is
keyed on the position argument). Draft width is a static shape, the
accepted length is data, so the invariant extends to one decode trace
PLUS one verify trace per mesh shape (``verify_traces``), and a greedy
speculative stream is bit-identical to the one-token engine — at any
page size, tp=1 and tp=2-exact. The ``DecodePolicy`` seam
(``EngineConfig(decode_policy=...)``, :mod:`apex_tpu.serve.spec`)
threads per-slot temperature/top_p/min_p as DATA through the same
compiled calls for per-request policy mixing in one batch.

``aot_compile()`` lowers and compiles decode (and any requested prompt
buckets) ahead of time — the serving analog of the repo's AOT tooling: no
request ever pays a trace.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt2 import GPT2Config, gpt2_token_forward_tp
from apex_tpu.ops.pallas.tiling import pow2_ceil
from apex_tpu.serve import kv_cache, paging
from apex_tpu.serve import spec as serve_spec
from apex_tpu.serve import tp as serve_tp
from apex_tpu.serve.kv_cache import shard_cache, tp_cache_specs
from apex_tpu.serve.model import serving_model
from apex_tpu.serve.paging import PagePool, PrefixIndex
from jax import shard_map
# bound at module import, NOT function-locally (the scheduler's
# precedent): a sys.modules purge-and-reimport mid-process would
# otherwise make engine builds publish to a FRESH event bus that
# collection-time subscribers never see
from apex_tpu.utils.logging import publish_event
from apex_tpu.utils.prof import annotate


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-side knobs; the model's own config (``GPT2Config``,
    ``DeepseekV3Config``) is the engine's first argument."""

    num_slots: int = 4
    max_len: Optional[int] = None      # default: model n_positions
    temperature: float = 1.0           # 0 => greedy argmax
    top_k: int = 0                     # 0 => full vocab
    block_k: Optional[int] = None      # decode-attention KV chunk (tuned)
    # paged KV pool: tokens per page (None => max_len: one page a slot).
    # Must divide max_len; the tuned decode_attention block_k must
    # divide it.
    page_size: Optional[int] = None
    # pool capacity in pages INCLUDING the reserved null page (needs
    # page_size). Default num_slots * (max_len / page_size) + 1: every
    # slot's whole context; size it SMALLER to overcommit (the point of
    # paging: mixed-length traffic shares the pool).
    num_pages: Optional[int] = None
    # hash-based prompt-prefix sharing across requests, a page at a time
    # (needs page_size: a page of max_len tokens is never shared)
    prefix_cache: bool = False
    # keep per-position prefill logits (parity tests / scoring). O(P*B*V)
    # memory — leave False for real vocabularies.
    keep_prefill_logits: bool = False
    # tensor-parallel mesh size (1 = single chip). Must divide n_head:
    # the engine shards params and the KV pool on the HEAD axis over a
    # 1-D NamedSharding mesh and lowers decode/prefill under shard_map —
    # one compile per mesh shape (docs/serving.md "Tensor-parallel
    # decode")
    tp: int = 1
    # per-layer cross-rank synchronization (tp >= 2 only): "exact" (the
    # default and THE oracle — all-gather concatenation, bit-identical
    # in fp32 to the single-chip engine at equal block_k), "overlap"
    # (TokenWeave: row-parallel psums split in slot halves, interleaved
    # with norm/residual compute), or "relaxed" (partially-synchronized
    # activations: ONE deferred all-reduce per layer; opt-in
    # approximation)
    tp_sync: str = "exact"
    # speculative decoding (docs/serving.md "Speculative decoding and
    # the decode-policy zoo"): static draft width per verify step; 0 is
    # the one-token engine. The verify step scores draft_len + 1
    # positions per slot in ONE compiled call; the accepted length is
    # data, so the one-compile invariant survives speculation.
    spec_draft_len: int = 0
    # the DecodePolicy seam (apex_tpu.serve.spec): None keeps the legacy
    # static sampler above (temperature/top_k baked into the trace) and
    # the decode signature unchanged; a policy spelling ("greedy",
    # "top_p[=P]", "min_p[=M]", "spec(POLICY)") arms per-slot policy
    # mixing — per-slot temperature/top_p/min_p ride the compiled calls
    # as [num_slots] f32 DATA, so mixing policies in one batch never
    # retraces. Parse/validation errors are build-time ValueErrors.
    decode_policy: Optional[str] = None
    # block-scale KV quantization (apex_tpu.quant,
    # docs/quantization.md): None stores fp32/compute-dtype K/V; "int8"
    # / "mxfp8" stores codec bytes plus one fp32 scale per (token,
    # head) in the cache pytree — scales are DATA, so the one-compile
    # invariant is untouched and scales ride prefix sharing, COW,
    # eviction, export/import, and tp head sharding with their pages.
    # Requires fp32 compute_dtype (the tolerance oracle is calibrated
    # against the fp32 engine) and spec_draft_len == 0 (the spec
    # acceptance oracle is bit-exact; quant is tolerance-based — the
    # combination is refused until proven, the repo's standing policy).
    kv_quant: Optional[str] = None


# The rows of a bucket's SMALL prefill program (``prefill_rows``). Two
# numbers, neither a knob:
# - a call over fewer positions than PREFILL_WEIGHT_BOUND_POSITIONS is
#   bound by one read of the weights, so fewer rows would buy nothing: a
#   v5e does 197e12 FLOP/s over 819e9 B/s = 240 FLOP a byte, and bf16
#   weights give one FLOP a byte a position (PERF.md §6, PR 36);
# - a steady tick admits a few slots of many (a mean of 1.1 of 16 and 2.7
#   of 64, never over 3 and 5, in the benchmark's closed loops): an eighth
#   of the slots holds all but the ramp's first admission.
PREFILL_WEIGHT_BOUND_POSITIONS = 256
PREFILL_SLOT_SHARE = 8


def prefill_rows(num_slots: int, bucket: int) -> int:
    """Rows of the small ``[rows, bucket]`` prefill program an engine of
    ``num_slots`` slots compiles beside ``[num_slots, bucket]``, worked
    out from what the engine is and never set: ``num_slots`` itself where
    the full program is already within the weight-bound range (that
    engine has ONE program a bucket)."""
    return min(num_slots,
               max(-(-PREFILL_WEIGHT_BOUND_POSITIONS // bucket),
                   num_slots // PREFILL_SLOT_SHARE))


class PoolLost(RuntimeError):
    """A serving call raised after the cache had been donated to it: the
    pool's buffers are gone, the engine has re-initialised the pool
    empty, and every request that was resident must be re-prefilled
    (``ServeScheduler.recover``) or failed (docs/serving.md, "Who owns
    the pool")."""


def _donating_jit(fn, cache_arg: int):
    """``jax.jit`` of a program that takes the cache as positional
    argument ``cache_arg`` and returns it: the cache is donated, so the
    program writes into the buffers it was handed. The caller rebinds its
    cache to the result; the arrays it passed are deleted."""
    return jax.jit(fn, donate_argnums=cache_arg)


class Engine:
    """A servable model: static cache + compiled prefill/decode.

    ``model_cfg`` names the model through the seam of
    :mod:`apex_tpu.serve.model`: a ``GPT2Config`` (``params`` is then the
    standard flax param pytree of ``models.gpt2.GPT2``, ``model.init(...)``
    or a training checkpoint; the engine holds its matrices cast once to
    the model config's ``compute_dtype``, the seam's ``serving_weights``;
    use fp32 configs for bit-exactness claims), or any config that
    provides ``serving_model()``, such as
    ``models.deepseek_v3.DeepseekV3Config`` (its weights are held in
    ``compute_dtype``). A model refuses, at build, the engine modes it has
    no mechanism for.

    ``engine.cache`` is the engine's to rebind: a serving call donates
    it and binds the call's result, so read it after a call, never
    across one.
    """

    def __init__(self, model_cfg, params,
                 config: EngineConfig = EngineConfig(), *, seed: int = 0):
        self.model_cfg = model_cfg
        self.model = serving_model(model_cfg)
        self.config = config
        self.max_len = int(config.max_len or self.model.max_positions)
        if self.max_len > self.model.max_positions:
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's "
                f"n_positions={self.model.max_positions}")
        if config.page_size is None:
            if config.prefix_cache:
                raise ValueError(
                    "prefix_cache=True needs page_size (prefix sharing is "
                    "page-granular, and the default page is a slot's "
                    "whole max_len)")
            if config.num_pages is not None:
                raise ValueError(
                    "num_pages needs page_size (the default pool is one "
                    "max_len page a slot)")
        # tokens a page: with none named, one page holds a slot's whole
        # context (one page a slot, num_slots + 1 pages)
        ps = self.page_size = int(
            self.max_len if config.page_size is None else config.page_size)
        if ps <= 0 or self.max_len % ps:
            raise ValueError(
                f"page_size={config.page_size} must be positive and "
                f"divide max_len={self.max_len}")
        self._max_pages = self.max_len // ps
        self._num_pages = int(
            config.num_pages or config.num_slots * self._max_pages + 1)
        if self._num_pages < self._max_pages + 1:
            raise ValueError(
                f"num_pages={self._num_pages} cannot hold one "
                f"full-context request plus the null page (need "
                f">= {self._max_pages + 1})")
        self.model.refuse(config)
        h = self.model.heads
        # tensor-parallel mesh (docs/serving.md "Tensor-parallel
        # decode"): every geometry error is a build-time ValueError,
        # never a bad lowering
        self._tp = int(config.tp)
        if self._tp < 1:
            raise ValueError(f"tp={config.tp} must be >= 1")
        if config.tp_sync not in serve_tp.SYNC_MODES:
            raise ValueError(
                f"tp_sync={config.tp_sync!r} must be one of "
                f"{serve_tp.SYNC_MODES}")
        if self._tp == 1 and config.tp_sync != "exact":
            raise ValueError(
                f"tp_sync={config.tp_sync!r} relaxes cross-rank "
                f"synchronization; it needs tp >= 2 (a single chip has "
                f"no collectives to overlap or relax)")
        if h % self._tp:
            raise ValueError(
                f"tp={self._tp} must divide n_head={h}: the serving "
                f"mesh shards whole heads")
        if self._tp > 1:
            self.mesh: Optional[Any] = serve_tp.serving_mesh(self._tp)
            self._tp_params, self._tp_param_specs = \
                serve_tp.build_tp_params(
                    model_cfg, self.model.serving_weights(params),
                    self._tp, config.tp_sync, self.mesh)
            # the sharded tree is the ONLY param copy the compiled
            # paths read; keeping the caller's full replicated tree
            # alive too would pin a second whole-model copy for the
            # engine's lifetime — for the model sizes TP exists for,
            # that is the dominant memory cost duplicated
            self.params = None
        else:
            self.mesh = None
            self._tp_params = self._tp_param_specs = None
            self.params = params
        # the bytes of the weights every call reads as its argument, as
        # they are held: the spans of the engine calls carry them
        self.weight_bytes = int(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self._weights)))
        # resolve the tuned geometry ONCE at engine build (cache lookups
        # at trace time inside scan would re-announce per position);
        # block_k is validated against page_size here — a tuned
        # or explicit chunk that does not divide the page is a clear
        # ValueError at build, never a bad gather at trace time. A
        # sharded engine tunes at its PER-SHARD head count with the
        # shard count as its own key axis (winners never leak across
        # mesh shapes)
        self.block_k = self.model.block_k(self.max_len, ps, config,
                                          self._tp)
        # speculative decoding + the DecodePolicy seam: every bad knob is
        # a build-time ValueError (both CLIs surface them as exit 2
        # before any compile)
        self._spec_k = int(config.spec_draft_len or 0)
        if self._spec_k < 0:
            raise ValueError(
                f"spec_draft_len={config.spec_draft_len} must be >= 0 "
                f"(0 disables speculation)")
        if self._spec_k and self._spec_k + 1 > self.max_len:
            raise ValueError(
                f"spec_draft_len={self._spec_k} needs max_len >= "
                f"{self._spec_k + 1}: a verify step scores draft_len + 1 "
                f"positions")
        self._policy = (serve_spec.parse_policy(
            config.decode_policy, spec_draft_len=self._spec_k)
            if config.decode_policy is not None else None)
        # block-scale KV quantization: codec validation is build-time
        # (unknown codec / missing float8 support), and the two
        # incompatible knob combinations are refused loudly rather than
        # served unproven — kv_quant needs the fp32 engine as its
        # tolerance reference, and speculation's acceptance oracle is
        # bit-exact where quant is tolerance-based
        self._kv_quant = config.kv_quant
        if self._kv_quant is not None:
            from apex_tpu.quant.kv import check_kv_codec

            check_kv_codec(self._kv_quant)
            if self.model.compute_dtype != jnp.float32:
                raise ValueError(
                    f"kv_quant={self._kv_quant!r} requires "
                    f"compute_dtype=float32: the quantization quality "
                    f"gate (quant_ppl_delta) is calibrated against the "
                    f"fp32 engine as the exact reference")
            if self._spec_k:
                raise ValueError(
                    f"kv_quant={self._kv_quant!r} is incompatible with "
                    f"spec_draft_len={self._spec_k}: the speculative "
                    f"acceptance oracle is bit-exact, the quantized "
                    f"cache is tolerance-gated — the combination is "
                    f"refused until separately proven")
        self._init_state(seed)

        # trace counters: tier-1 asserts decode_traces == 1 across a full
        # admit/complete/evict/backfill trace (the one-jit invariant —
        # one compile per MESH SHAPE: a tp engine's single decode trace
        # covers every rank, there is no per-rank compile to count).
        # Speculation adds verify_traces with the identical contract:
        # one verify trace per mesh shape, churn-proof.
        self.decode_traces = 0
        self.prefill_traces = 0
        self.verify_traces = 0

        # per program, what its compiled text says of the pool
        # (kv_cache.pool_facts); filled where the engine holds the
        # executable, at aot_compile
        self._pool_facts: Dict[Any, Dict[str, int]] = {}
        self._decode = _donating_jit(self._decode_fn, 1)
        self._decode_aot = None
        self._decode_lowered = None    # kept so collective counting and
        #                                postmortems never re-trace
        self._prefill_jits: Dict[int, Any] = {}
        self._prefill_aot: Dict[int, Any] = {}
        self._prefill_lowered: Dict[int, Any] = {}   # same retention
        #                                contract as _decode_lowered: the
        #                                cost ledger reads prefill costs
        #                                without re-lowering after reset()
        self._verify = self._make_verify() if self._spec_k else None
        self._verify_aot = None
        self._verify_lowered = None    # retention contract shared with
        #                                _decode_lowered: cost_ledger()
        #                                prices verify after reset()
        #                                without ever re-tracing
        # the host-called mutators, given the pool as the programs are
        self._copy_page = _donating_jit(kv_cache.copy_page, 0)
        self._install_page = _donating_jit(kv_cache.install_page, 0)
        if self._tp > 1:
            publish_event(
                "serve_tp_mesh_ready", tp=self._tp,
                tp_sync=config.tp_sync, heads_per_shard=h // self._tp,
                collectives_per_decode_step=sum(
                    self.tp_collectives_per_step().values()))

    # ------------------------------------------------------------ graphs
    def _sample(self, logits, rng, pol=None):
        """Temperature / top-k sampling; greedy when temperature == 0.
        With the DecodePolicy seam armed, ``pol`` carries the per-slot
        temperature/top_p/min_p arrays as data and the branchless
        combined sampler runs instead (greedy rows stay an exact
        argmax)."""
        if pol is not None:
            return serve_spec.sample_with_policy(
                logits, rng, pol, top_k=int(self.config.top_k))
        t = float(self.config.temperature)
        k = int(self.config.top_k)
        if t <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) / jnp.float32(t)
        if k > 0 and k < logits.shape[-1]:
            kth = jax.lax.top_k(scaled, k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, jnp.float32(-1e30), scaled)
        return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)

    @property
    def params(self):
        """The weights a single-chip engine's programs take, as they
        take them (``None`` under tensor parallelism: the sharded tree is
        then the only copy). Assigning a tree casts it with the seam's
        ``serving_weights`` and places it on the device, once: a restored
        checkpoint's numpy leaves would otherwise be re-uploaded every
        call (device arrays in ``compute_dtype`` pass through untouched,
        so fleet replicas given one such tree keep sharing it)."""
        return self._params

    @params.setter
    def params(self, params):
        self._params = None if params is None else jax.tree_util.tree_map(
            jnp.asarray, self.model.serving_weights(params))

    @property
    def _weights(self):
        """The param pytree the compiled calls take as their FIRST jit
        argument (the head-sharded tree under tensor parallelism). Never
        closed over: a closed-over array is lowered as a dense constant,
        which at GPT-2 XL is the whole model baked into every program."""
        return self.params if self.mesh is None else self._tp_params

    def _token_step(self, weights, cache, tokens, positions, mask,
                    logits_at=None, *, final_scope: str = "sampling"):
        """The model forward at this engine's geometry: one token a slot
        (``[num_slots]`` arguments: decode, the verify scan's body) or
        one chunk a slot (``[num_slots, T]``: prefill, with ``logits_at``
        naming the row a slot whose logits are wanted)."""
        kw = dict(block_k=self.block_k, kv_quant=self._kv_quant,
                  final_scope=final_scope)
        data = (tokens, positions, mask) \
            + (() if logits_at is None else (logits_at,))
        if self.mesh is None:
            return self.model.forward(weights, cache, *data, **kw)
        # tensor-parallel: the SAME call sites (decode_fn, prefill_fn,
        # the verify scan body) lower the per-rank forward under
        # shard_map — the cache rides in head-sharded, the page
        # table/lengths replicated, logits come back replicated
        # (identical on every rank by the sync-mode contract), and
        # sampling stays outside on the full replicated logits exactly
        # as on a single chip
        from jax.sharding import PartitionSpec as P

        specs = tp_cache_specs(cache)

        def rank_body(params, cache, *data):
            return gpt2_token_forward_tp(
                self.model_cfg, self._tp, self.config.tp_sync, params,
                cache, *data, **kw)

        fn = shard_map(rank_body, mesh=self.mesh,
                       in_specs=(self._tp_param_specs, specs)
                       + (P(),) * len(data),
                       out_specs=(P(), specs), check_vma=False)
        return fn(weights, cache, *data)

    def _decode_fn(self, weights, cache, last_tokens, active, rng,
                   pol=None):
        self.decode_traces += 1          # python side effect: trace count
        positions = cache.lengths
        # a model's counters, where it returns any, ride out last
        logits, cache, *counters = self._token_step(
            weights, cache, last_tokens, positions, active)
        with jax.named_scope("sampling"):
            rng, sub = jax.random.split(rng)
            next_tokens = self._sample(logits, sub, pol)
        cache = kv_cache.advance(cache, active)
        return next_tokens, logits, cache, rng, tuple(counters)

    def _make_prefill(self, bucket: int, rows: Optional[int] = None):
        """A ``prefill_<bucket>`` program: ONE ``[rows, bucket]`` forward.
        Every admitted prompt's positions go through the layers together,
        their K/V take one masked write a layer at ``start + t``, and
        each admitted slot's first token is drawn in-program from the
        logits of its last real position. The cache is donated.

        ``rows`` of ``None`` or ``num_slots`` is the program over EVERY
        slot (row ``i`` is slot ``i``). With fewer
        (:func:`prefill_rows`) the program takes
        one more argument, ``slots [rows] int32``: row ``i`` is slot
        ``slots[i]`` (a padding row carries ``num_slots`` and ``admit ==
        False``). It runs the same forward over a row view of the cache
        (``kv_cache.slot_view``: those slots' ``lengths`` and page-table
        rows beside the same donated pools; a forward reaches a slot
        only through the two), then closes the view
        (``kv_cache.close_view``) and scatters its rows' logits into
        ``[num_slots, vocab]``, so both programs sample over, and
        return, arrays a slot id indexes, and a sampled stream draws a
        slot's token from the same bits whichever program ran."""
        keep = self.config.keep_prefill_logits
        b = self.config.num_slots

        def chunk(weights, cache, tokens, admit, start, tail_lens):
            t = jnp.arange(bucket, dtype=jnp.int32)[None, :]
            # absolute position = start + chunk index: with a prefix hit
            # the call covers only the tail, attending back over the
            # shared pages (start == 0 and tail == prompt otherwise)
            write = admit[:, None] & (t < tail_lens[:, None])
            last = jnp.maximum(tail_lens - 1, 0)
            logits, cache, *counters = self._token_step(
                weights, cache, tokens, start[:, None] + t, write,
                None if keep else last)
            if keep:
                all_logits = jnp.swapaxes(logits, 0, 1)     # [P, B, V]
                last_logits = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1)[:, 0]
            else:
                all_logits, last_logits = None, logits
            return cache, last_logits, all_logits, tuple(counters)

        def draw(last_logits, rng, pol):
            with jax.named_scope("sampling"):
                rng, sub = jax.random.split(rng)
                return self._sample(last_logits, sub, pol), rng

        if rows in (None, b):
            def prefill_fn(weights, cache, tokens, admit, start, tail_lens,
                           rng, pol=None):
                self.prefill_traces += 1
                cache, last_logits, all_logits, counters = chunk(
                    weights, cache, tokens, admit, start, tail_lens)
                cache = kv_cache.set_lengths(cache, admit,
                                             start + tail_lens)
                first_tokens, rng = draw(last_logits, rng, pol)
                return (cache, first_tokens, last_logits, all_logits, rng,
                        counters)

            return _donating_jit(prefill_fn, 1)

        def prefill_fn(weights, cache, slots, tokens, admit, start,
                       tail_lens, rng, pol=None):
            self.prefill_traces += 1
            view, last_logits, all_logits, counters = chunk(
                weights, kv_cache.slot_view(cache, slots), tokens, admit,
                start, tail_lens)
            cache = kv_cache.close_view(cache, view, slots, admit,
                                        start + tail_lens)
            # a padding row's slot id is out of range: dropped
            last_logits = jnp.zeros(
                (b,) + last_logits.shape[1:], last_logits.dtype
            ).at[slots].set(last_logits, mode="drop")
            if keep:
                all_logits = jnp.zeros(
                    (bucket, b) + all_logits.shape[2:], all_logits.dtype
                ).at[:, slots].set(all_logits, mode="drop")
            first_tokens, rng = draw(last_logits, rng, pol)
            return (cache, first_tokens, last_logits, all_logits, rng,
                    counters)

        return _donating_jit(prefill_fn, 1)

    def _make_verify(self):
        """The speculative verify step: a scan of the one-token decode
        forward over ``draft_len + 1`` positions at decode width. Column 0
        re-feeds each slot's last committed token (exactly what
        ``decode_step`` would feed), columns ``1..K`` are the host
        drafter's guesses; position ``p``'s logits produce the target
        policy's own next token, and a draft is accepted iff it EQUALS
        that target (exact rejection-sampling acceptance for a
        point-mass drafter — no tolerance: the scan's body IS the decode
        step's forward, bit for bit). The accepted run length is data:
        ``set_lengths`` commits ``accepted + 1`` tokens and thereby
        rolls back every rejected draft row (stale K/V beyond
        ``lengths`` is unreachable — attention reachability is keyed on
        the position argument, the same mechanism evict relies on).
        Per-slot ``draft_lens`` is also data, so capacity- or
        budget-clamped slots (down to plain one-token steps at
        ``draft_lens == 0``) ride the same trace. The cache is donated."""
        k = self._spec_k
        width = k + 1

        def verify_fn(weights, cache, last_tokens, drafts, draft_lens,
                      active, rng, pol=None):
            self.verify_traces += 1      # python side effect: trace count
            start = cache.lengths

            def body(carry, p):
                cache = carry
                write = active & (p <= draft_lens)
                positions = jnp.where(write, start + p, cache.lengths)
                tokens = jnp.where(
                    p == 0, last_tokens,
                    drafts[:, jnp.maximum(p - 1, 0)])
                logits, cache = self._token_step(
                    weights, cache, tokens, positions, write,
                    final_scope="verify")
                return cache, logits

            cache, all_logits = jax.lax.scan(
                body, cache, jnp.arange(width, dtype=jnp.int32))
            with jax.named_scope("sampling"):
                # ONE split of the engine key per verify call — the same
                # key-path contract as decode, so sampling_state()
                # journal replay covers speculative streams unchanged
                rng, sub = jax.random.split(rng)
                keys = jax.random.split(sub, width)
                targets = jax.vmap(
                    lambda lg, kk: self._sample(lg, kk, pol))(
                        all_logits, keys)
            targets = jnp.transpose(targets)          # [B, K+1]
            with jax.named_scope("verify"):
                proposed = (jnp.arange(k, dtype=jnp.int32)[None, :]
                            < draft_lens[:, None])
                match = (drafts == targets[:, :k]) & proposed
                # leading run of matches: a rejection truncates the draft
                accepted = jnp.cumprod(
                    match.astype(jnp.int32), axis=1).sum(axis=1)
                committed = jnp.where(active, accepted + 1, 0) \
                    .astype(jnp.int32)
                next_tokens = jnp.take_along_axis(
                    targets, accepted[:, None], axis=1)[:, 0]
                cache = kv_cache.set_lengths(cache, active,
                                             start + committed)
            return targets, committed, next_tokens, cache, rng

        return _donating_jit(verify_fn, 1)

    # -------------------------------------------------------------- AOT
    def _policy_args(self):
        """Per-slot policy knobs as a jit-argument pytree (DATA — new
        values never retrace); None when the seam is unarmed, which
        keeps the unarmed engine's trace signature as it is."""
        if self._policy is None:
            return None
        return {"temps": jnp.asarray(self._pol_temps),
                "top_ps": jnp.asarray(self._pol_top_ps),
                "min_ps": jnp.asarray(self._pol_min_ps)}

    def _decode_args(self):
        args = (self._weights, self.cache,
                jnp.zeros((self.config.num_slots,), jnp.int32),
                jnp.zeros((self.config.num_slots,), bool), self.rng)
        return args + ((self._policy_args(),)
                       if self._policy is not None else ())

    def _prefill_args(self, bucket: int, rows: Optional[int] = None):
        b = self.config.num_slots
        r = rows or b
        args = (self._weights, self.cache) \
            + (() if r == b else (jnp.zeros((r,), jnp.int32),)) \
            + (jnp.zeros((r, bucket), jnp.int32),
               jnp.zeros((r,), bool), jnp.zeros((r,), jnp.int32),
               jnp.zeros((r,), jnp.int32), self.rng)
        return args + ((self._policy_args(),)
                       if self._policy is not None else ())

    def _prefill_key(self, bucket: int, rows: int):
        """Where a bucket's program of ``rows`` rows lives in
        ``_prefill_jits``, ``_prefill_aot``, ``_prefill_lowered`` and
        ``_pool_facts``: under the bucket itself for the one over every
        slot, under ``(bucket, rows)`` for the small one."""
        return bucket if rows == self.config.num_slots else (bucket, rows)

    def _prefill_programs(self, bucket: int) -> Dict[Any, int]:
        """``{key: rows}`` of a bucket's programs: the one over every
        slot and, where :func:`prefill_rows` gives fewer rows than
        slots, the small one."""
        b = self.config.num_slots
        return {self._prefill_key(bucket, rows): rows
                for rows in (b, prefill_rows(b, bucket))}

    def _verify_args(self):
        b = self.config.num_slots
        args = (self._weights, self.cache, jnp.zeros((b,), jnp.int32),
                jnp.zeros((b, self._spec_k), jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
                self.rng)
        return args + ((self._policy_args(),)
                       if self._policy is not None else ())

    def aot_compile(self, prompt_buckets: Sequence[int] = ()) -> "Engine":
        """Lower + compile decode (and the given prompt-length buckets)
        ahead of the first request — startup pays the trace, not traffic.

        Each fresh compile publishes its static XLA memory reservation as
        an ``hbm_snapshot`` event (``apex_tpu.monitor.memory``) — the
        serving AOT points are where the engine's HBM budget is decided,
        and a capacity comparison of two pool geometries reads them —
        together with the lowered module's size and ``main`` argument count
        (``monitor.costs.module_facts``: the weights must be arguments).
        """
        from apex_tpu.monitor.costs import module_facts
        from apex_tpu.monitor.memory import publish_compiled_memory

        if self._decode_aot is None:
            # the lowering is kept: decode_collectives() counts the
            # step's collective ops from it without ever re-tracing
            # (a second .lower() would grow decode_traces)
            self._decode_lowered = self._decode.lower(
                *self._decode_args())
            self._decode_aot = self._decode_lowered.compile()
            self._pool_facts["decode"] = kv_cache.pool_facts(
                self._decode_aot, self.cache)
            publish_compiled_memory(
                "serve_decode", self._decode_aot,
                num_slots=self.config.num_slots, max_len=self.max_len,
                page_size=self.page_size,
                kv_cache_bytes=self.kv_cache_bytes,
                **module_facts(self._decode_lowered.as_text()))
        for bucket in prompt_buckets:
            bucket = pow2_ceil(int(bucket))
            for key, rows in self._prefill_programs(bucket).items():
                if key in self._prefill_aot:
                    continue
                fn = self._prefill_jits.setdefault(
                    key, self._make_prefill(bucket, rows))
                # retained like _decode_lowered: cost_ledger() prices
                # prefill buckets from the saved lowering — after a
                # reset()/warm restart there is nothing to re-trace
                lowered = fn.lower(*self._prefill_args(bucket, rows))
                self._prefill_lowered[key] = lowered
                self._prefill_aot[key] = lowered.compile()
                self._pool_facts[key] = kv_cache.pool_facts(
                    self._prefill_aot[key], self.cache)
                publish_compiled_memory(
                    "serve_prefill", self._prefill_aot[key],
                    bucket=bucket, num_slots=self.config.num_slots,
                    rows=rows, max_len=self.max_len,
                    **module_facts(lowered.as_text()))
        if self._spec_k and self._verify_aot is None:
            # retained like _decode_lowered: cost_ledger() prices the
            # verify step from the saved lowering after reset()
            self._verify_lowered = self._verify.lower(
                *self._verify_args())
            self._verify_aot = self._verify_lowered.compile()
            self._pool_facts["verify"] = kv_cache.pool_facts(
                self._verify_aot, self.cache)
            publish_compiled_memory(
                "serve_verify", self._verify_aot,
                draft_len=self._spec_k,
                num_slots=self.config.num_slots, max_len=self.max_len,
                page_size=self.page_size,
                **module_facts(self._verify_lowered.as_text()))
        return self

    def _init_state(self, seed: int) -> None:
        """ALL mutable serving state lives here (shared by __init__ and
        :meth:`reset` so a drain/restart can never miss a field)."""
        b = self.config.num_slots
        self._init_pool()
        self.rng = jax.random.PRNGKey(seed)
        self.last_tokens = np.zeros((b,), np.int32)
        # prefix-cache accounting (tier-1 asserts a prefix hit SKIPS
        # prefill work via these, not via wall clock)
        self.decode_calls = 0            # decode_step executions
        self.prefill_calls = 0           # host prefill() invocations
        self.prefill_requests = 0        # slot-prompts prefilled
        self.prefill_scanned_tokens = 0  # positions paid: bucket a call
        self.prefix_hits = 0             # prompts that reused >=1 page
        self.prefix_hit_tokens = 0       # tokens served from the index
        if self._policy is not None:
            # per-slot policy knobs (host mirrors of the jit-argument
            # arrays): reset() restores the engine-default policy
            self._pol_temps = np.full((b,), self._policy.temperature,
                                      np.float32)
            self._pol_top_ps = np.full((b,), self._policy.top_p,
                                       np.float32)
            self._pol_min_ps = np.full((b,), self._policy.min_p,
                                       np.float32)

    def _init_pool(self) -> None:
        """The pool and everything that says what it holds: the cache,
        the allocator, the prefix index, the page tables and the host's
        mirror of ``lengths``. What :meth:`_donating` rebuilds when a
        failed call took the buffers with it."""
        b = self.config.num_slots
        self.cache: Any = self.model.init_cache(
            b, self.max_len, self.page_size, self._num_pages,
            self._kv_quant, self._tp)
        if self.mesh is not None:
            # head-sharded K/V pools, replicated bookkeeping — placed at
            # init so the compiled step never pays a layout move
            self.cache = shard_cache(self.cache, self.mesh)
        self.pool = PagePool(self._num_pages, self.page_size)
        self.prefix: Optional[PrefixIndex] = \
            PrefixIndex(self.page_size) if self.config.prefix_cache else None
        self._page_table = np.zeros((b, self._max_pages), np.int32)
        self._slot_pages = [[] for _ in range(b)]
        # per-slot admitted token capacity: pages reserved at admission
        # × page_size
        self._slot_capacity = np.zeros((b,), np.int64)
        # host mirror of cache.lengths (advanced deterministically by
        # prefill/decode/evict) — lets decode_step enforce the context
        # bound without a per-step device fetch
        self._host_lengths = np.zeros((b,), np.int64)
        self.last_prefill_stats: Dict[int, Dict[str, int]] = {}

    def _donating(self, fn, *args):
        """Call a program that is given the pool by donation. A call
        that raises before its arguments are consumed (a refusal, a
        trace or compile error) leaves the engine as it was. One that
        raises AFTER has taken the pool's buffers with it: that is fatal
        for the resident requests, so the pool is re-initialised
        (:meth:`_init_pool`: zero pages, empty allocator and prefix
        index, every slot free; programs, weights, the PRNG key and the
        counters stay) and :class:`PoolLost` is raised from the cause —
        ``self.cache`` never points at deleted buffers."""
        try:
            return fn(*args)
        except Exception as err:
            if not any(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(self.cache)):
                raise
            self._init_pool()
            raise PoolLost(
                f"a serving call failed after the cache was donated to "
                f"it ({type(err).__name__}: {err}); the pool's buffers "
                f"went with it, so every resident request is lost and "
                f"the pool has been re-initialised empty") from err

    def reset(self, seed: int = 0, *,
              keep_prefix_cache: bool = False) -> "Engine":
        """Drop all serving state — empty cache, fresh PRNG stream — while
        keeping every compiled artifact (the weights are a jit argument).
        A server drain/restart costs zero recompiles; tests reuse one
        compiled engine across scenarios.

        The page-pool free list and the prefix index are reset
        too (a leaked refcount would poison the next scenario — tier-1
        regression-tests this). ``keep_prefix_cache=True`` (warm restart)
        instead releases every slot's page references but keeps the pool
        bytes and the index: shared prefix pages are read-only, so a
        crash cannot have corrupted them, and recovery re-prefills only
        the unshared tail of each surviving slot.
        """
        if keep_prefix_cache and self.prefix is not None:
            b = self.config.num_slots
            for slot in range(b):
                self._release_slot_pages(slot)
            self.cache = self.cache.replace(
                lengths=jnp.zeros((b,), jnp.int32))
            self.rng = jax.random.PRNGKey(seed)
            self.last_tokens = np.zeros((b,), np.int32)
            self._host_lengths = np.zeros((b,), np.int64)
            self.last_prefill_stats = {}
            return self
        self._init_state(seed)
        return self

    # --------------------------------------------- warm-restart support
    def sampling_state(self) -> Dict[str, Any]:
        """The host-side sampling state a tick journal snapshots: the
        PRNG key (restoring it is what makes a ``temperature > 0``
        stream replay bit-for-bit across a warm restart — the key path
        is consumed one split per prefill/decode call), the per-slot
        last tokens (the next decode inputs), and the host length
        mirror (an integrity cross-check at restore)."""
        return {"rng": np.asarray(self.rng).tolist(),
                "last_tokens": self.last_tokens.tolist(),
                "lengths": self._host_lengths.tolist()}

    def restore_sampling_state(self, state: Dict[str, Any], *,
                               slots: Sequence[int] = ()) -> None:
        """Install a journaled sampling state after recovery re-prefill.

        ``slots`` names the slot indices the caller re-prefilled; their
        current cache lengths must equal the journaled ones (prompt +
        generated-but-last) or the rebuilt cache does NOT hold the state
        the PRNG/last-token restore assumes — refuse loudly rather than
        continue a stream from the wrong prefix."""
        want = np.asarray(state["lengths"], np.int64)
        for slot in slots:
            if self._host_lengths[slot] != want[slot]:
                raise ValueError(
                    f"recovery integrity: slot {slot} rebuilt to length "
                    f"{int(self._host_lengths[slot])}, journal says "
                    f"{int(want[slot])} — the re-prefilled prefix does "
                    f"not match the journaled stream")
        self.rng = jnp.asarray(np.asarray(state["rng"], np.uint32))
        self.last_tokens = np.asarray(state["last_tokens"], np.int32)

    def paging_state(self) -> Dict[str, Any]:
        """The page-accounting view a tick journal records: per-slot
        page tables, pool refcounts, and the prefix-index size — the
        postmortem answer to "where did the HBM go" and the integrity
        cross-check for recovery."""
        return {
            "page_size": self.page_size,
            "num_pages": self._num_pages,
            "free_pages": self.pool.free_count,
            "refcounts": list(self.pool.refcount),
            "page_table": self._page_table.tolist(),
            "slot_capacity": self._slot_capacity.tolist(),
            "prefix_entries": len(self.prefix) if self.prefix else 0,
        }

    # ---------------------------------------------------- page planning
    def _release_slot_pages(self, slot: int) -> None:
        """Drop the slot's page references (completion, eviction, or the
        re-prefill prologue); index-pinned prefix pages survive."""
        for page in self._slot_pages[slot]:
            self.pool.release(page)
        self._slot_pages[slot] = []
        self._page_table[slot, :] = paging.NULL_PAGE
        self._slot_capacity[slot] = 0

    def admission_page_cost(self, tokens: Sequence[int], budget: int,
                            pending: int = 0,
                            protect: Optional[set] = None) -> Optional[int]:
        """Admission probe: fresh pages admitting ``tokens`` with
        ``budget`` new-token headroom would allocate, or ``None`` when
        the pool (free list + LRU-evictable prefix pages) cannot cover
        them on top of ``pending`` pages already promised to earlier
        members of the same admission batch. ``protect`` (a set the
        scheduler threads through a batch of probes — the only mutation)
        accumulates every probed member's prefix-hit pages: a page one
        member plans to share must not count as evictable headroom for
        a later member, or prefill's eviction (which protects the whole
        batch's hits) would free fewer pages than the probes assumed
        and fail allocation mid-batch. Never touches the pool — the
        scheduler probes before popping a request. With the default
        geometry (one page a slot) a free slot always has its page."""
        plan = paging.plan_admission(
            tokens, budget, self.max_len, self.page_size, self.prefix,
            touch=False)
        hits = {pg for _, pg in plan["hits"]}
        protect_all = hits | (protect or set())
        avail = self.pool.free_count
        if self.prefix is not None:
            avail += self.prefix.evictable(self.pool, protect_all)
        if plan["new_pages"] + pending > avail:
            return None
        if protect is not None:
            protect.update(hits)
        return plan["new_pages"]

    # ------------------------------------------------------------- calls
    def _occupancy(self, act_np: np.ndarray,
                   program: str = "decode") -> Dict[str, int]:
        """What a decode step's span carries: slots fed and held, the
        bytes of the weights the step reads (``weight_bytes``), tokens
        resident before the step, pool pages out of the free
        list, and what the compiled ``program`` says of the pool
        (``pool_aliased_bytes``, ``pool_copies``: there once
        :meth:`aot_compile` holds the executable), and of the slot's
        ``key_chunks`` chunks of ``block_k`` keys how many the step's
        attention visits (``attended_chunks``: the model's own count, a
        verify step's at its first position). Host ints the engine
        already keeps; nothing is read from the device."""
        key_chunks = self.max_len // self.block_k
        return {"active": int(act_np.sum()),
                "slots": self.config.num_slots,
                "weight_bytes": self.weight_bytes,
                "resident": self.resident_tokens,
                **self._pool_facts.get(program, {}),
                "pages_in_use": self.pool.capacity - self.pool.free_count,
                "pages": self.pool.capacity,
                "attended_chunks": self.model.attended_chunks(
                    self._host_lengths, act_np, self.block_k, key_chunks),
                "key_chunks": key_chunks}

    def _note_counters(self, span: str, counters, real_rows: int,
                       slots: int) -> None:
        """Where the model's forward returned counters (an expert
        model's routing, a looped model's passes), fetch them, now that
        the call's tokens are here, and leave them as the attributes of
        ``<span>.<the model's counters_span>``, inside the call's own
        span: a call over ``real_rows`` real positions of ``slots``
        slots."""
        if counters:
            with annotate(f"{span}.{self.model.counters_span}",
                          **self.model.call_counters(
                              np.asarray(counters[0]), real_rows, slots,
                              span == "apex.prefill")):
                pass

    def prefill(self, prompts: Dict[int, Sequence[int]], *,
                budgets: Optional[Dict[int, int]] = None,
                cacheable: Optional[Dict[int, int]] = None):
        """Insert ``{slot: prompt token ids}`` in one compiled call.

        Pads every prompt to the shared pow2 bucket and runs ONE batched
        forward over the rows it admits: the bucket's ``[rows, bucket]``
        program where the batch fits its rows (:func:`prefill_rows`; an
        admission then costs no forward over the slots that go on
        decoding), else ``[num_slots, bucket]`` (rows of non-target
        slots and of padding are computed, discarded, and written
        nowhere in either), and samples each admitted slot's first
        generated token. An engine whose full program is small already
        has only that one. Returns ``(first_tokens [B], last_logits [B,
        vocab], all_logits [P, B, vocab] | None)`` with ``B ==
        num_slots`` whichever program ran, indexed by slot id; only the
        admitted slots' rows are meaningful.

        ``budgets[slot]`` (default: worst case ``max_len -
        len(prompt)``) sizes the page reservation — pages for the whole
        admitted budget are taken here so decode never allocates. With a
        prefix index, the longest indexed prefix is shared read-only and
        the call covers only the tail (a partial boundary page is
        copied-on-write); afterwards the prompt's full pages are inserted
        into the index — ``cacheable[slot]`` caps how many leading tokens
        are indexable (recovery passes the original prompt length so
        generated-token pages never pin the index). Raises
        :class:`~apex_tpu.serve.paging.PagePoolExhausted` when pages run
        out — callers admit through :meth:`admission_page_cost` first.
        """
        if not prompts:
            raise ValueError("prefill needs at least one slot: prompt")
        b = self.config.num_slots
        max_p = max(len(t) for t in prompts.values())
        if max_p < 1:
            raise ValueError("empty prompt")
        for slot, toks in prompts.items():
            if not 0 <= slot < b:
                raise ValueError(f"slot {slot} out of range 0..{b - 1}")
            if len(toks) > self.max_len:
                raise ValueError(
                    f"prompt of {len(toks)} tokens exceeds max_len="
                    f"{self.max_len}")

        with annotate("apex.prefill", admitted=len(prompts), slots=b,
                      weight_bytes=self.weight_bytes):
            with annotate("apex.prefill.plan"):
                starts, tails, new_pages = self._plan_prefill(prompts,
                                                              budgets)
            bucket = pow2_ceil(max(len(t) for t in tails.values()))
            # the smaller of the bucket's programs that holds the batch
            # (an engine with one program has rows == num_slots)
            rows = prefill_rows(b, bucket)
            if len(prompts) > rows:
                rows = b
            key = self._prefill_key(bucket, rows)
            with annotate("apex.prefill.launch", bucket=bucket, slots=rows,
                          real_positions=sum(len(t) for t in tails.values()),
                          hit_tokens=int(starts.sum()), new_pages=new_pages,
                          **self._pool_facts.get(key, {})):
                # row i of the call is slot slots[i]: every slot in the
                # program over all of them, the admitted ones alone in
                # the small one, whose padding rows name no slot
                order = range(b) if rows == b else sorted(tails)
                slots = np.full((rows,), b, np.int32)
                slots[:len(order)] = order
                tokens = np.zeros((rows, bucket), np.int32)
                admit = np.zeros((rows,), bool)
                start = np.zeros((rows,), np.int32)
                lens = np.zeros((rows,), np.int32)
                for row, slot in enumerate(order):
                    toks = tails.get(slot)
                    if toks is not None:
                        tokens[row, :len(toks)] = np.asarray(toks, np.int32)
                        admit[row] = True
                        start[row] = starts[slot]
                        lens[row] = len(toks)

                fn = self._prefill_aot.get(key)
                if fn is None:
                    fn = self._prefill_jits.setdefault(
                        key, self._make_prefill(bucket, rows))
                args = (self._weights, self.cache) \
                    + (() if rows == b else (jnp.asarray(slots),)) \
                    + (jnp.asarray(tokens), jnp.asarray(admit),
                       jnp.asarray(start), jnp.asarray(lens), self.rng)
                if self._policy is not None:
                    args += (self._policy_args(),)
                (self.cache, first, last_logits, all_logits, self.rng,
                 counters) = self._donating(fn, *args)
            self.prefill_calls += 1
            self.prefill_requests += len(prompts)
            self.prefill_scanned_tokens += int(bucket)
            with annotate("apex.prefill.fetch"):
                first_np = np.asarray(first)
            self._note_counters("apex.prefill", counters, int(lens.sum()),
                                len(prompts))
            live = slots[admit]
            last, lengths = self.last_tokens.copy(), self._host_lengths.copy()
            last[live], lengths[live] = first_np[live], (start + lens)[admit]
            self.last_tokens, self._host_lengths = last, lengths
            if self.prefix is not None:
                ps = self.page_size
                with annotate("apex.prefill.index"):
                    for slot, toks in prompts.items():
                        upto = (cacheable or {}).get(slot, len(toks))
                        row = self._slot_pages[slot]
                        for i, h in enumerate(
                                paging.chunk_hashes(list(toks[:upto]), ps)):
                            self.prefix.insert(h, row[i], self.pool)
            if self._kv_quant is not None:
                # quantized-capacity provenance: these pages now hold
                # codec bytes + scales, not fp32 rows — counted so a bench
                # capture can prove its resident_tokens_per_hbm_byte came
                # from a quantized pool, not a mislabeled fp32 one
                publish_event("serve_kv_quantized_pages", pages=new_pages,
                              codec=self._kv_quant)
            return first_np, last_logits, all_logits

    def _plan_prefill(self, prompts: Dict[int, Sequence[int]],
                      budgets: Optional[Dict[int, int]]):
        """The host half of an admission, before anything is launched:
        release, plan, evict, allocate, copy-on-write, and the page-table
        upload. Returns ``(starts [num_slots], {slot: tail to run},
        fresh pages taken)`` and leaves ``last_prefill_stats``."""
        starts = np.zeros((self.config.num_slots,), np.int32)
        tails: Dict[int, Sequence[int]] = dict(prompts)
        self.last_prefill_stats = {}
        new_pages = 0
        ps = self.page_size
        for slot in prompts:
            # the slot may still hold pages (same-tick backfill
            # defers the device-side evict; tests re-prefill
            # directly) — release before re-planning
            self._release_slot_pages(slot)
        # two passes: plan every slot BEFORE any eviction, so one
        # slot's LRU eviction can never free a page another batch
        # member planned to share (the probe counted those hits —
        # evicting them would make its page math wrong mid-batch)
        plans = {}
        for slot in sorted(prompts):
            toks = prompts[slot]
            budget = (budgets or {}).get(slot)
            if budget is None:
                budget = self.max_len - len(toks)
            plans[slot] = paging.plan_admission(
                toks, budget, self.max_len, ps, self.prefix,
                touch=True)
        protect_all = {pg for plan in plans.values()
                       for _, pg in plan["hits"]}
        for slot in sorted(prompts):
            plan = plans[slot]
            shared = [pg for _, pg
                      in plan["hits"][:plan["shared_pages"]]]
            if plan["new_pages"] > self.pool.free_count \
                    and self.prefix is not None:
                self.prefix.evict(
                    self.pool,
                    plan["new_pages"] - self.pool.free_count,
                    protect=protect_all)
            fresh = self.pool.alloc(plan["new_pages"])
            new_pages += len(fresh)
            for pg in shared:
                self.pool.retain(pg)
            if plan["cow_src"] is not None:
                # copy-on-write: the tail starts mid-page, so the
                # slot gets its own writable copy of the boundary
                # page (one compiled op; identical bytes)
                self.cache = self._donating(
                    self._copy_page, self.cache, plan["cow_src"],
                    fresh[0])
            row = shared + fresh
            self._page_table[slot, :] = paging.NULL_PAGE
            self._page_table[slot, :len(row)] = row
            self._slot_pages[slot] = row
            self._slot_capacity[slot] = plan["total_pages"] * ps
            starts[slot] = plan["use"]
            tails[slot] = plan["tail"]
            if plan["use"]:
                self.prefix_hits += 1
                self.prefix_hit_tokens += plan["use"]
            self.last_prefill_stats[slot] = {
                "hit_tokens": plan["use"],
                "hit_pages": plan["shared_pages"],
                "scanned": len(plan["tail"]),
            }
        self.cache = self.cache.replace(
            page_table=jnp.asarray(self._page_table))
        return starts, tails, new_pages

    def decode_step(self, last_tokens, active):
        """One decode step for every slot: feed each active slot its last
        token, get its next. ``last_tokens`` ``[num_slots]`` int,
        ``active`` ``[num_slots]`` bool. Returns ``(next_tokens
        np.ndarray, logits [num_slots, vocab] device array)``."""
        act_np = np.asarray(active, bool)
        with annotate("apex.decode_step", **self._occupancy(act_np)):
            full = act_np & (self._host_lengths >= self._slot_capacity)
            if full.any():
                # the cache write would land in an unreserved page, or be
                # clipped onto the newest K/V row at max_len, and corrupt
                # it — refuse instead; the scheduler terminates at
                # context-full / budget before ever reaching this
                raise ValueError(
                    f"slot(s) {np.flatnonzero(full).tolist()} are at their "
                    f"admitted capacity "
                    f"{self._slot_capacity[full].tolist()} (max_len="
                    f"{self.max_len}); evict or raise max_len before "
                    f"decoding further")
            with annotate("apex.decode_step.launch"):
                fn = self._decode_aot or self._decode
                lt = jnp.asarray(np.asarray(last_tokens, np.int32))
                act = jnp.asarray(act_np)
                args = (self._weights, self.cache, lt, act, self.rng)
                if self._policy is not None:
                    args += (self._policy_args(),)
                next_tokens, logits, self.cache, self.rng, counters = \
                    self._donating(fn, *args)
            self.decode_calls += 1
            with annotate("apex.decode_step.fetch"):
                next_np = np.asarray(next_tokens)
            self._note_counters("apex.decode_step", counters,
                                int(act_np.sum()), int(act_np.sum()))
            self.last_tokens = np.where(act_np, next_np, self.last_tokens)
            self._host_lengths = self._host_lengths + act_np
            return next_np, logits

    # ------------------------------------------------ speculative decode
    @property
    def spec_draft_len(self) -> int:
        """Static draft width K (0 = speculation off)."""
        return self._spec_k

    @property
    def policy_armed(self) -> bool:
        """True when the DecodePolicy seam threads per-slot knobs."""
        return self._policy is not None

    def set_slot_policy(self, slot: int, policy=None) -> None:
        """Install a per-request decode policy on ``slot`` (policy
        mixing in one batch): the knobs are DATA on the compiled calls,
        so this never retraces. ``policy`` is a
        :class:`~apex_tpu.serve.spec.DecodePolicy`, a policy spelling,
        or None to restore the engine default. Needs
        ``EngineConfig(decode_policy=...)`` — the unarmed engine's
        sampler is baked into the trace."""
        if self._policy is None:
            if policy is None:
                return
            raise ValueError(
                "per-slot policies need EngineConfig(decode_policy=...): "
                "the unarmed engine bakes its sampler into the trace")
        pol = policy if policy is not None else self._policy
        if isinstance(pol, str):
            pol = serve_spec.parse_policy(pol,
                                          spec_draft_len=self._spec_k)
        self._pol_temps[slot] = pol.temperature
        self._pol_top_ps[slot] = pol.top_p
        self._pol_min_ps[slot] = pol.min_p

    def spec_headroom(self, slot: int) -> int:
        """Cache rows still writable for ``slot`` (admitted capacity
        minus resident tokens) — the scheduler clamps each tick's draft
        to ``headroom - 1`` so a verify commit can never overrun."""
        return int(self._slot_capacity[slot] - self._host_lengths[slot])

    def spec_decode_step(self, last_tokens, drafts, draft_lens, active):
        """One speculative step for every slot: feed each active slot
        its last committed token plus up to ``spec_draft_len`` host
        draft guesses; the compiled verify step scores all ``K + 1``
        positions and commits the exactly-accepted run plus one bonus
        token. ``drafts`` ``[num_slots, K]`` int, ``draft_lens``
        ``[num_slots]`` int in ``[0, K]`` (data — a 0 row is a plain
        one-token step on the same trace), ``active`` ``[num_slots]``
        bool. Returns ``(committed [num_slots, K + 1] np.ndarray — only
        the first ``counts[slot]`` entries of each row are meaningful —
        and counts [num_slots] np.ndarray)``."""
        if not self._spec_k:
            raise ValueError(
                "spec_decode_step needs EngineConfig(spec_draft_len >= "
                "1); use decode_step on the one-token engine")
        act_np = np.asarray(active, bool)
        with annotate("apex.spec_decode_step",
                      **self._occupancy(act_np, "verify")):
            dl_np = np.asarray(draft_lens, np.int64)
            if ((dl_np < 0) | (dl_np > self._spec_k)).any():
                raise ValueError(
                    f"draft_lens {dl_np.tolist()} must lie in "
                    f"[0, spec_draft_len={self._spec_k}]")
            # capacity backstop, mirroring decode_step's refusal: the
            # verify scan writes positions length..length+draft_len, and
            # commits up to draft_len + 1 tokens — an overrun would land
            # in an unreserved page (or clip at max_len) and corrupt K/V
            # rows
            need = self._host_lengths + np.where(act_np, dl_np + 1, 0)
            over = act_np & (need > self._slot_capacity)
            if over.any():
                raise ValueError(
                    f"slot(s) {np.flatnonzero(over).tolist()} would overrun "
                    f"their admitted capacity "
                    f"{self._slot_capacity[over].tolist()} at draft_lens="
                    f"{dl_np[over].tolist()} (max_len={self.max_len}); "
                    f"clamp the draft or evict before speculating further")
            with annotate("apex.spec_decode_step.launch"):
                fn = self._verify_aot or self._verify
                b = self.config.num_slots
                args = (self._weights, self.cache,
                        jnp.asarray(np.asarray(last_tokens, np.int32)),
                        jnp.asarray(np.asarray(drafts, np.int32).reshape(
                            b, self._spec_k)),
                        jnp.asarray(dl_np.astype(np.int32)),
                        jnp.asarray(act_np), self.rng)
                if self._policy is not None:
                    args += (self._policy_args(),)
                committed, counts, next_tokens, self.cache, self.rng = \
                    self._donating(fn, *args)
            self.decode_calls += 1
            with annotate("apex.spec_decode_step.fetch"):
                committed_np = np.asarray(committed)
                counts_np = np.asarray(counts)
                next_np = np.asarray(next_tokens)
            self.last_tokens = np.where(act_np, next_np, self.last_tokens)
            self._host_lengths = self._host_lengths + counts_np
            return committed_np, counts_np

    def evict(self, slots) -> None:
        """Free the given slot indices (mask-shaped op, compiled once)
        and return the slots' page references to the pool (index-pinned
        prefix pages stay resident)."""
        mask = np.zeros((self.config.num_slots,), bool)
        mask[np.asarray(list(slots), np.int64)] = True
        # only ``lengths`` goes through a program: the pool's arrays are
        # the same buffers before and after
        self.cache = kv_cache.evict_slots(self.cache, jnp.asarray(mask))
        self._host_lengths = np.where(mask, 0, self._host_lengths)
        for slot in np.flatnonzero(mask):
            self._release_slot_pages(int(slot))

    # --------------------- page migration (disaggregated prefill→decode)
    def export_prefix_pages(self, tokens: Sequence[int]):
        """Snapshot the indexed prefix pages of ``tokens`` for streaming
        into another replica's pool: ``[{chain_hash, k, v, digest}, ...]``
        in chain order, one entry per consecutive indexed full chunk.
        Payload arrays are host copies ``[cache_planes, page_size, heads,
        head_dim]`` with the pool's padded head axis (under tensor
        parallelism ``device_get`` gathers the head shards — page indices are rank-invariant, payloads are
        whole pages). The digest is stamped here, over the exact bytes
        exported (:func:`~apex_tpu.serve.paging.page_payload_digest`), so
        the receiver can certify the transfer. ``touch=False``: an
        export is a read, not a use — it must not reorder the donor's
        LRU. Empty when there is no prefix index / no indexed prefix.
        """
        self._refuse_page_migration()
        if self.prefix is None:
            return []
        out = []
        for h, page in self.prefix.lookup(tokens, touch=False):
            k_np = np.asarray(jax.device_get(self.cache.k[:, page]))
            v_np = np.asarray(jax.device_get(self.cache.v[:, page]))
            entry = {"chain_hash": h, "k": k_np, "v": v_np,
                     "codec": self._kv_quant}
            if self._kv_quant is not None:
                # quantized payloads ship their scale planes, and the
                # digest covers codes ‖ scales together: a flipped
                # scale bit fails certification exactly like a flipped
                # payload bit
                ks_np = np.asarray(
                    jax.device_get(self.cache.k_scale[:, page]))
                vs_np = np.asarray(
                    jax.device_get(self.cache.v_scale[:, page]))
                entry["k_scale"] = ks_np
                entry["v_scale"] = vs_np
                entry["digest"] = paging.page_payload_digest(
                    h, k_np.tobytes(), v_np.tobytes(),
                    ks_np.tobytes(), vs_np.tobytes())
            else:
                entry["digest"] = paging.page_payload_digest(
                    h, k_np.tobytes(), v_np.tobytes())
            out.append(entry)
        return out

    def import_prefix_pages(self, payloads) -> Dict[str, int]:
        """Install **certified** migrated pages into this engine's pool
        and prefix index; returns ``{"installed", "duplicate",
        "no_capacity"}`` counts. Certification (chain-hash + payload
        digest) is the CALLER's job — the disaggregation controller
        refuses un-certified pages before they reach here; this method
        enforces only the structural contract (an engine with a prefix
        index, exact payload shape).

        Exactly-once by construction: a payload whose chain hash is
        already indexed is a duplicate stream (failover replay, a second
        handoff of the same prefix) and is skipped — the index insert
        no-op is the same door that makes two requests sharing a prompt
        idempotent. Installed pages are index-only (refcount 1): they
        age out through normal LRU eviction like locally-prefilled
        prefix pages, and the next admission of the migrated prompt
        shares them read-only exactly as a local prefix hit.
        """
        self._refuse_page_migration()
        if self.prefix is None:
            raise ValueError(
                "import_prefix_pages needs an engine with page_size and "
                "prefix_cache=True (page migration lands in the prefix "
                "index)")
        shape = (self.model.cache_planes, self.page_size) \
            + tuple(self.cache.k.shape[3:])
        stats = {"installed": 0, "duplicate": 0, "no_capacity": 0}
        for p in payloads:
            if tuple(np.shape(p["k"])) != shape or \
                    tuple(np.shape(p["v"])) != shape:
                raise ValueError(
                    f"migrated page payload shape {np.shape(p['k'])} != "
                    f"engine page shape {shape} (torn transfer should "
                    f"have been refused at certification)")
            if p.get("codec") != self._kv_quant:
                raise ValueError(
                    f"migrated page codec {p.get('codec')!r} != engine "
                    f"kv_quant {self._kv_quant!r} (a codec mismatch "
                    f"should have been refused at certification — "
                    f"installing it would misread the pool bytes)")
            if p["chain_hash"] in self.prefix:
                stats["duplicate"] += 1
                continue
            if self.pool.free_count < 1:
                self.prefix.evict(self.pool, 1)
            if self.pool.free_count < 1:
                # chain order: a missing page truncates the usable
                # prefix, so later pages would be unreachable anyway
                stats["no_capacity"] += len(payloads) - (
                    stats["installed"] + stats["duplicate"])
                break
            page = self.pool.alloc(1)[0]
            planes = (p["k"], p["v"]) + (
                (p["k_scale"], p["v_scale"])
                if self._kv_quant is not None else ())
            self.cache = self._donating(
                self._install_page, self.cache, page,
                *(jnp.asarray(a) for a in planes))
            self.prefix.insert(p["chain_hash"], page, self.pool)
            # index-only residency (refcount 1): admission shares it
            # read-only like any local prefix hit; LRU can reclaim it
            self.pool.release(page)
            stats["installed"] += 1
        if self._kv_quant is not None and stats["installed"]:
            publish_event("serve_kv_quantized_pages",
                          pages=stats["installed"],
                          codec=self._kv_quant)
        return stats

    def _refuse_page_migration(self) -> None:
        if isinstance(self.cache, kv_cache.HybridCache):
            raise ValueError(
                f"{self.model.name} pages do not migrate: a page is of no "
                f"use without the recurrent state at its boundary, and "
                f"export/import move pages alone")
        if not hasattr(self.cache, "k"):
            raise ValueError(
                f"{self.model.name} pages do not migrate: export/import, "
                f"their payload digest and kv_cache.install_page move a "
                f"page's K and V arrays, and a latent pool has one array "
                f"of rows (prefix sharing inside the engine is unaffected)")

    @property
    def lengths(self) -> np.ndarray:
        return np.asarray(self.cache.lengths)

    # ------------------------------------------------- tensor parallel
    @property
    def tp(self) -> int:
        """Tensor-parallel mesh size (1 = single chip)."""
        return self._tp

    def tp_collectives_per_step(self) -> Dict[str, int]:
        """The per-decode-step collective CONTRACT of this engine's sync
        mode (zeros on a single chip); tier-1 holds it against the
        actual lowering via :meth:`decode_collectives`."""
        if self._tp == 1:
            return {"all_gather": 0, "all_reduce": 0}
        return serve_tp.expected_collectives(self.model.n_layer,
                                             self.config.tp_sync)

    def decode_collectives(self) -> Dict[str, int]:
        """Collective ops in the ACTUAL lowered decode step (StableHLO
        count — the verifier of :meth:`tp_collectives_per_step`). Uses
        the saved AOT lowering, producing it first if needed — on an
        engine already serving through the plain jit path, that
        ``.lower()`` resolves from the jit's trace cache, so
        ``decode_traces`` stays at 1 either way (tier-1 pins exactly
        this ordering)."""
        if self._decode_lowered is None:
            self.aot_compile()
        return serve_tp.count_collectives(self._decode_lowered.as_text())

    def cost_ledger(self, chip: Optional[str] = None,
                    prompt_buckets: Sequence[int] = ()) -> Dict[str, Any]:
        """The engine's compiled-step cost ledger
        (``apex_tpu.monitor.costs``): phase-attributed FLOPs/HBM bytes/
        op histograms walked from the SAVED AOT lowerings plus XLA's own
        cost/memory analyses, with a roofline projection on ``chip``
        (auto-detected; ``"cpu"`` — marked non-gating — off silicon).

        Rides ``_decode_lowered``/``_prefill_lowered`` exactly like
        :meth:`decode_collectives` — producing them first if needed,
        never re-tracing (``decode_traces`` stays at 1), and surviving
        ``reset()``/warm restarts, which keep the compiled artifacts.
        Entries: ``decode`` plus ``prefill_<bucket>`` (and the small
        program's ``prefill_<bucket>_rows<rows>`` where the engine has
        one) for every bucket
        already compiled or requested via ``prompt_buckets``, plus
        ``verify`` when speculation is armed (``spec_draft_len >= 1``;
        a one-token engine's ledger is byte-identical to PR 17's —
        there is no verify artifact to price).
        """
        from apex_tpu.monitor import costs
        from apex_tpu.utils.prof import detect_chip

        if self._decode_lowered is None or any(
                pow2_ceil(int(b)) not in self._prefill_lowered
                for b in prompt_buckets) or (
                    self._spec_k and self._verify_lowered is None):
            self.aot_compile(prompt_buckets)
        execs = {"decode": costs.executable_record(
            self._decode_lowered, self._decode_aot)}
        for bucket in sorted(k for k in self._prefill_lowered
                             if isinstance(k, int)):
            for key, rows in self._prefill_programs(bucket).items():
                name = f"prefill_{bucket}" + (
                    "" if key == bucket else f"_rows{rows}")
                execs[name] = costs.executable_record(
                    self._prefill_lowered[key], self._prefill_aot.get(key))
        if self._spec_k:
            execs["verify"] = costs.executable_record(
                self._verify_lowered, self._verify_aot)
        dtype = jnp.dtype(self.model.compute_dtype)
        workload = {
            "model": self.model.name,
            "num_slots": int(self.config.num_slots),
            "max_len": int(self.max_len),
            "page_size": self.page_size,
            "dtype": dtype.name,
            "dtype_bytes": int(dtype.itemsize),
            "block_k": int(self.block_k),
            "tp": int(self._tp),
            "tp_sync": self.config.tp_sync if self._tp > 1 else None,
            **self.model.workload(),
            "spec_draft_len": int(self._spec_k),
            "decode_policy": self.config.decode_policy,
            "kv_quant": self._kv_quant,
            "quant_block": int(self.quant_block),
        }
        return costs.build_ledger(execs, workload,
                                  chip=chip or detect_chip() or "cpu")

    def tp_rank_snapshots(self, meta: Optional[Dict[str, Any]] = None):
        """Per-rank mergeable metrics snapshots (the PR-10
        ``merge_snapshots`` seam) — see
        :func:`apex_tpu.serve.tp.rank_snapshots`. Empty on a single
        chip (there are no ranks to fold)."""
        if self._tp == 1:
            return []
        return serve_tp.rank_snapshots(self, meta=meta)

    @property
    def resident_tokens(self) -> int:
        """Tokens currently resident in the cache across all slots."""
        return int(self._host_lengths.sum())

    @property
    def free_page_frac(self) -> float:
        """Fraction of the pool allocatable RIGHT NOW: free pages plus
        index-only cached pages an LRU sweep could evict on demand.
        Counting evictable pages matters: a warm prefix cache
        deliberately keeps the free list near empty, so raw free_count
        reads as permanent pressure on an engine that actually has plenty
        of headroom."""
        free = self.pool.free_count
        if self.prefix is not None:
            free += self.prefix.evictable(self.pool)
        return free / max(self.pool.capacity, 1)

    @property
    def kv_quant(self) -> Optional[str]:
        """The armed KV codec (``"int8"``/``"mxfp8"``) or None."""
        return self._kv_quant

    @property
    def quant_block(self) -> int:
        """Quantization block size (elements per scale): the head_dim
        when ``kv_quant`` is armed — one scale per (token, head) vector —
        else 0 (unquantized). A workload-provenance axis: captures at
        different blocks are incomparable."""
        if self._kv_quant is None:
            return 0
        return int(self.model.head_dim)

    @property
    def kv_cache_bytes(self) -> int:
        """Resident bytes of the KV buffers — the pool's
        ``num_pages * page_size`` tokens, INCLUDING the fp32 scale planes when
        ``kv_quant`` is armed (the capacity win must be priced net of
        its scale overhead); stamped into the serving AOT
        ``hbm_snapshot`` and the bench's
        ``resident_tokens_per_hbm_byte`` so captures carry it."""
        return kv_cache.cache_bytes(self.cache)


@functools.lru_cache(maxsize=8)
def _jitted_init(cfg: GPT2Config):
    """One jitted ``model.init`` per config: a second engine's weights
    (a fleet's replicas, a restart, the next test) reuse the compile."""
    from apex_tpu.models.gpt2 import GPT2

    return jax.jit(GPT2(cfg).init)


def init_gpt2_params(cfg: GPT2Config, seed: int = 0):
    """Random GPT-2 params for smoke/bench serving (real deployments load
    a checkpoint). ``model.init`` traces the training forward at a short
    length to discover the param shapes; under ``jit`` only the
    initializers survive (the params do not depend on the forward), so
    a 48-layer model pays one compile, not an eager forward.
    """
    dummy = jnp.zeros((1, min(8, cfg.n_positions)), jnp.int32)
    return _jitted_init(cfg)(jax.random.PRNGKey(seed), dummy)
