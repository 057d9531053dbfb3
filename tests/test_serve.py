"""Serving engine tier-1: static-shape KV cache, one-jit decode,
continuous batching.

The acceptance claims under test:

- **parity** — incremental decode logits match full-sequence prefill
  logits to a float32 rounding tolerance (``BORDER``): prefill is one
  batched ``[num_slots, bucket]`` forward, decode one row a slot, and a
  batched product may round differently from a one-row product in the
  last place; the flax ``GPT2`` module's full-sequence forward is the
  oracle that is not the engine;
- **one compile** — a scripted trace that admits, completes, evicts, and
  backfills requests mid-stream traces ``decode_step`` exactly once
  (``Engine.decode_traces``);
- **isolation** — a FaultInjector-scripted mid-stream abort leaves every
  other request's token stream bit-identical (per-slot reductions cannot
  see other slots' bytes);
- termination (EOS / max-new-tokens / context), greedy + seeded-sampling
  determinism, the serve bench + regression gate, and both CLIs.

Engines are compiled once per geometry and shared across tests via
``Engine.reset()`` (state drop, zero recompiles — itself part of the
serving contract); the one-jit acceptance tests get fresh engines so
their trace counters stay airtight.
"""

import collections
import dataclasses
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor.goodput import GoodputLedger
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.kv_cache import init_paged_cache, paged_write_token
from apex_tpu.serve.scheduler import Request, ServeScheduler
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session, and a function-local re-import after that
# would subscribe to a FRESH bus while the (old) scheduler module keeps
# publishing to the original one
from apex_tpu.utils.logging import subscribe_events

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("temperature", 0.0)
    seed = kw.pop("seed", 0)
    return Engine(CFG, params, EngineConfig(**kw), seed=seed)


@pytest.fixture(scope="module")
def greedy3(params):
    """Shared greedy 3-slot engine; tests reset() it — compiled once."""
    return _engine(params)


@pytest.fixture(scope="module")
def greedy2(params):
    return _engine(params, num_slots=2)


@pytest.fixture(scope="module")
def keeper3(params):
    """3-slot greedy engine that keeps per-position prefill logits."""
    return _engine(params, keep_prefill_logits=True)


# Across the prefill/decode border the mathematics is the same and the
# order of the float32 sums is not: prefill multiplies [num_slots * bucket]
# rows at once and sums its softmax over the chunk, decode multiplies one
# row a slot and sums over block_k chunks of the cache. On this file's
# model (logits up to 0.4) the largest difference read over 8 prompts of
# 24 tokens is 1.8e-7, against decode and against the flax module alike;
# the bound leaves ten times that. Same-path identities (a call repeated,
# paged against slot, tp against one chip, a neighbour's bytes) stay
# array_equal.
BORDER = dict(rtol=1e-5, atol=2e-6)


def _tokens(n, seed=7, vocab=97):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


# ------------------------------------------------------------ kv cache

def _one_page_a_slot(cache):
    """``cache`` with the identity page table of the default geometry:
    slot ``b`` owns page ``b + 1`` (page 0 is the null page)."""
    return cache.replace(page_table=jnp.arange(
        1, cache.num_slots + 1, dtype=jnp.int32)[:, None])


def test_kv_cache_ops_are_static_and_masked():
    cache = _one_page_a_slot(init_paged_cache(
        n_layer=2, num_slots=4, max_len=16, page_size=16, num_pages=5,
        heads=2, head_dim=8))
    # a masked-off slot's page holds bytes a write must not touch
    cache = cache.replace(k=cache.k.at[0, 2].set(7.0))
    k = jnp.ones((4, 2, 8)) * jnp.arange(1, 5)[:, None, None]
    pos = jnp.zeros((4,), jnp.int32)
    mask = jnp.array([True, False, True, False])
    out = jax.jit(paged_write_token, static_argnums=1)(
        cache, 0, k, k, pos, mask)
    assert out.k.shape == cache.k.shape  # static shapes, whatever the mask
    # the head axis is allocated in whole groups of 8; the rest stays zero
    assert cache.k.shape == (2, 5, 16, 8, 8)
    assert not np.asarray(out.v[:, :, :, 2:]).any()
    got = np.asarray(out.k[0, 1:, 0, 0, 0])          # slot b is page b + 1
    np.testing.assert_array_equal(got, [1.0, 7.0, 3.0, 0.0])
    # masked-off slots' bytes are bit-untouched, and so is the null page
    # their writes are routed to
    for page in (0, 2, 4):
        np.testing.assert_array_equal(np.asarray(out.k[:, page]),
                                      np.asarray(cache.k[:, page]))


# -------------------------------------------------------------- parity

def test_prefill_vs_incremental_decode_matches(greedy3, keeper3):
    """THE serving invariant: decode token j's logits == full prefill's
    position-j logits, to float32 rounding (``BORDER``: the batched
    prefill sums in another order than the one-row decode step)."""
    seq = _tokens(12)
    _, _, all_logits = keeper3.reset().prefill({1: seq})
    all_logits = np.asarray(all_logits)          # [P, B, V]

    inc = greedy3.reset()
    inc.prefill({1: seq[:5]})
    for j in range(5, len(seq)):
        forced = np.array([0, seq[j], 0], np.int32)
        _, logits = inc.decode_step(forced, np.array([False, True, False]))
        a, b = all_logits[j, 1], np.asarray(logits)[1]
        assert a.dtype == np.float32
        np.testing.assert_allclose(a, b, err_msg=f"decode pos {j}",
                                   **BORDER)
    assert inc.lengths[1] == len(seq)


def test_prefill_last_logits_match_kept_logits(keeper3):
    seq = _tokens(9, seed=3)
    _, last, all_logits = keeper3.reset().prefill({0: seq})
    np.testing.assert_array_equal(np.asarray(last)[0],
                                  np.asarray(all_logits)[len(seq) - 1, 0])


# ------------------------------------ the batched prefill (one forward)

def _resident(eng, slot, field="k"):
    """``[n_layer, lengths[slot], ...]``: the rows of ``cache.<field>`` a
    slot's attention can reach, read through the page table."""
    n = int(eng.lengths[slot])
    buf = np.asarray(getattr(eng.cache, field))
    ps, table = eng.page_size, eng._page_table[slot]
    return np.stack([buf[:, table[p // ps], p % ps] for p in range(n)], 1)


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_chunk_attention_matches_a_plain_softmax(layout):
    """``chunk_attention`` alone against the definition in float64: row
    ``t`` of slot ``b`` attends over the cached positions ``< start[b]``
    and the chunk's own keys ``0..t``, one softmax over both. One page a
    slot (``slot``: four chunks of 8 inside the one page) and four
    (``paged``); a slot with no cached head beside slots with one (the
    head's loop runs to the longest, 19 rows = 3 chunks of 8, and masks
    the rest); the head read through a shuffled page table."""
    from apex_tpu.serve.attention import chunk_attention
    from apex_tpu.serve.kv_cache import pad_heads

    b, t, h, d, max_len = 3, 4, 2, 8, 32
    ps = max_len if layout == "slot" else 8
    per_slot = max_len // ps
    pages = b * per_slot + 1
    rng = np.random.RandomState(0)
    head_k, head_v = rng.randn(2, b, max_len, h, d).astype(np.float32)
    q, k, v = rng.randn(3, b, t, h, d).astype(np.float32)
    start = np.array([0, 19, 8], np.int32)
    table = rng.permutation(np.arange(1, pages)).reshape(b, per_slot)
    pool = init_paged_cache(2, b, max_len, ps, pages, h, d)

    def paged(rows):              # [b, max_len, ...] -> [pages, ps, ...]
        rows = np.asarray(pad_heads(rows, pool.k.shape[-2]))
        out = np.zeros((pages, ps) + rows.shape[2:], np.float32)
        out[table.reshape(-1)] = rows.reshape((-1, ps) + rows.shape[2:])
        return out

    cache = pool.replace(
        k=pool.k.at[1].set(paged(head_k)),
        v=pool.v.at[1].set(paged(head_v)),
        page_table=jnp.asarray(table, jnp.int32))
    # the cache's head axis is padded: so are the chunk's queries, keys
    # and values, and the padding's outputs are dropped (gpt2.py does so)
    full = cache.k.shape[-2]
    got = np.asarray(jax.jit(
        lambda *a: chunk_attention(*(pad_heads(x, full) for x in a[:3]),
                                   cache, 1, a[3], block_k=8))(
            q, k, v, start))[:, :, :h]
    for i in range(b):
        for j in range(t):
            keys = np.concatenate([head_k[i, :start[i]], k[i, :j + 1]])
            vals = np.concatenate([head_v[i, :start[i]], v[i, :j + 1]])
            sc = np.einsum("hd,khd->hk", q[i, j].astype(np.float64),
                           keys.astype(np.float64)) / np.sqrt(d)
            w = np.exp(sc - sc.max(-1, keepdims=True))
            want = np.einsum("hk,khd->hd", w / w.sum(-1, keepdims=True),
                             vals.astype(np.float64))
            np.testing.assert_allclose(got[i, j], want, rtol=1e-5,
                                       atol=1e-5, err_msg=f"{i},{j}")


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_batched_prefill_matches_the_flax_forward(params, keeper3, layout):
    """An oracle that is not the engine: every real position's logits of
    one ``[num_slots, bucket]`` prefill call (two prompts of different
    lengths, one idle slot) against the flax ``GPT2`` module's
    full-sequence forward on the same parameters, to ``BORDER`` (the
    module's flash kernel sums its softmax in blocks)."""
    from apex_tpu.models.gpt2 import GPT2

    eng = keeper3.reset() if layout == "slot" else _engine(
        params, keep_prefill_logits=True, page_size=8)
    prompts = {0: _tokens(12, seed=11), 2: _tokens(7, seed=12)}
    first, last, all_logits = eng.prefill(prompts)
    all_logits = np.asarray(all_logits)              # [P, B, V]
    assert all_logits.shape == (16, 3, CFG.vocab_size)
    for slot, toks in prompts.items():
        want = np.asarray(GPT2(CFG).apply(
            params, jnp.asarray([toks], jnp.int32)))[0]
        np.testing.assert_allclose(all_logits[:len(toks), slot], want,
                                   err_msg=f"slot {slot}", **BORDER)
        # same program, same rows: the returned last logits ARE the kept
        # ones, and the first token is their argmax
        np.testing.assert_array_equal(np.asarray(last)[slot],
                                      all_logits[len(toks) - 1, slot])
        assert first[slot] == int(np.argmax(want[-1]))
    assert eng.lengths.tolist() == [12, 0, 7]


@pytest.mark.parametrize("kind", ["slot", "paged", "paged-int8"])
def test_batched_prefill_leaves_the_cache_decode_would(params, kind):
    """The K/V one batched prefill call writes against the K/V the same
    tokens leave when fed one by one through ``decode_step``: the same
    values to ``BORDER`` (the rows come out of a batched and a one-row
    product). With a ``kv_quant`` codec the scales match to ``BORDER``
    and a code may sit one step off where a value lay on a rounding
    boundary; the encode itself is the per-token one in both programs."""
    kw = dict(num_slots=2)
    if kind != "slot":
        kw["page_size"] = 8
    if kind == "paged-int8":
        kw["kv_quant"] = "int8"
    seq = _tokens(13, seed=21)
    batched = _engine(params, **kw)
    batched.prefill({1: seq})
    stepped = _engine(params, **kw)
    stepped.prefill({1: seq[:1]})
    for tok in seq[1:]:
        stepped.decode_step(np.array([0, tok], np.int32),
                            np.array([False, True]))
    assert batched.lengths.tolist() == stepped.lengths.tolist() == [0, 13]
    for field in ("k", "v"):
        a, b = _resident(batched, 1, field), _resident(stepped, 1, field)
        # 2 heads in a head axis of 8: the padding is never written
        assert a.shape == b.shape == (CFG.n_layer, 13, 8, 16)
        assert not a[:, :, 2:].any() and not b[:, :, 2:].any()
        if kind != "paged-int8":
            np.testing.assert_allclose(a, b, err_msg=field, **BORDER)
            continue
        np.testing.assert_allclose(
            _resident(batched, 1, field + "_scale"),
            _resident(stepped, 1, field + "_scale"), err_msg=field,
            **BORDER)
        off = np.abs(a.astype(np.int32) - b.astype(np.int32))
        assert off.max() <= 1 and (off > 0).mean() < 0.01, field


@pytest.mark.parametrize("shared, hit, scanned", [(16, 16, 5),
                                                  (16, 15, 1)])
def test_prefill_after_a_prefix_hit_matches_a_cold_prefill(
        params, shared, hit, scanned):
    """A prompt whose head the prefix index serves (two whole shared
    pages and a 5-token tail; or the whole prompt cached, so one page
    shared and the boundary page copied-on-write for the last token):
    the tail's logits, computed over the cached pages and the chunk in
    one softmax, match a cold engine's for the same prompt to ``BORDER``
    (another order of the same sum), and the counters read as before."""
    eng = _engine(params, page_size=8, prefix_cache=True,
                  keep_prefill_logits=True)
    head = _tokens(shared, seed=42)
    prompt = head + _tokens(scanned if hit == shared else 0, seed=2)
    _, cold_last, cold_all = eng.prefill({0: prompt})
    cold_all = np.asarray(cold_all)
    eng.reset()
    eng.prefill({0: head + _tokens(5, seed=1)})      # seeds the index
    assert eng.prefix_hits == 0
    paid = eng.prefill_scanned_tokens
    first, warm_last, warm_all = eng.prefill({1: prompt})
    assert eng.last_prefill_stats[1] == {
        "hit_tokens": hit, "hit_pages": hit // 8, "scanned": scanned}
    assert eng.prefix_hits == 1 and eng.prefix_hit_tokens == hit
    # positions paid: the tail's pow2 bucket, not the prompt's
    assert eng.prefill_scanned_tokens - paid == \
        {5: 8, 1: 1}[scanned]
    np.testing.assert_allclose(
        np.asarray(warm_all)[:scanned, 1], cold_all[hit:len(prompt), 0],
        **BORDER)
    np.testing.assert_allclose(np.asarray(warm_last)[1],
                               np.asarray(cold_last)[0], **BORDER)
    assert first[1] == int(np.argmax(np.asarray(cold_last)[0]))
    assert eng.lengths[1] == len(prompt)


@pytest.mark.parametrize("layout", ["slot", "paged"])
def test_admission_beside_a_decoding_slot_leaves_it_bit_identical(
        params, layout):
    """Two prompts of different lengths admitted in ONE prefill call
    beside a decoding slot: the decoding slot's resident K/V, its length
    and its next token and logits are bit-identical to a run without the
    admission (masked-off rows are dropped by the write, and reductions
    run within a slot)."""
    kw = {} if layout == "slot" else dict(page_size=8)

    def run(admit):
        eng = _engine(params, **kw)
        first, _, _ = eng.prefill({0: _tokens(9, seed=31)})
        tok = first
        for _ in range(2):
            tok, _ = eng.decode_step(tok, np.array([True, False, False]))
        before = (_resident(eng, 0, "k"), _resident(eng, 0, "v"))
        if admit:
            eng.prefill({1: _tokens(11, seed=32), 2: _tokens(3, seed=33)})
            assert eng.lengths.tolist() == [11, 11, 3]
        after = (_resident(eng, 0, "k"), _resident(eng, 0, "v"))
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)
        nxt, logits = eng.decode_step(
            np.where([True, False, False], tok, 0).astype(np.int32),
            np.array([True, False, False]))
        return (int(nxt[0]), np.asarray(logits)[0], int(eng.lengths[0]),
                _resident(eng, 0, "k"), _resident(eng, 0, "v"))

    alone, beside = run(False), run(True)
    assert alone[0] == beside[0] and alone[2] == beside[2] == 12
    for a, b in zip(alone[1:], beside[1:]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("layout", ["slot", "paged", "tp2"])
def test_prefill_program_is_one_forward_not_a_scan(layout, tp_devices):
    """So that a scan over positions cannot come back unseen: the lowered
    ``prefill_<bucket>`` module multiplies each weight once, whatever the
    bucket. ``walk_module`` multiplies a counted ``stablehlo.while``'s
    body by its trip count, so a 16-step scan of the token forward would
    count 16 times the products; the one loop the program has is the
    cached head's (one a layer, inside ``attention``), its trip count is
    data, and it holds no weight."""
    from apex_tpu.monitor.costs import stablehlo_debug_text, walk_module

    eng = Engine(CFG, init_gpt2_params(CFG), EngineConfig(
        num_slots=2, max_len=32, temperature=0.0,
        page_size=None if layout == "slot" else 8,
        tp=2 if layout == "tp2" else 1))
    walks = {}
    for bucket in (8, 16):
        text = stablehlo_debug_text(
            eng._make_prefill(bucket).lower(*eng._prefill_args(bucket)))
        walks[bucket] = walk_module(text)
        # the four weight products a layer and the logits product, and a
        # layer's attention: two products over the chunk, two in the
        # cached head's loop body (counted once: its trip count is data)
        assert walks[bucket]["op_families"]["dot_general"] == \
            4 * CFG.n_layer + 1 + 4 * CFG.n_layer, bucket
        head_loops = [n for n in walks[bucket].get("notes", ())
                      if "trip count not statically resolvable" in n]
        assert len(head_loops) == CFG.n_layer, walks[bucket].get("notes")
        assert walks[bucket]["phases"]["mlp"]["flops"] > 0
    # work follows the rows: twice the bucket, twice the dense flops
    for phase in ("ln_qkv", "mlp"):
        small, big = (walks[b]["phases"][phase]["flops"] for b in (8, 16))
        assert 1.9 < big / small < 2.1, (phase, small, big)
    assert walks[8]["op_families"].get("while") is None   # never priced


# ----------------------------------------------------- one-jit invariant

def test_decode_compiles_once_across_admit_evict_backfill(params):
    """Scripted multi-request trace — staggered admissions, completions,
    a mid-stream abort, and backfill — compiles decode_step exactly once
    and one prefill per prompt bucket. Fresh engine: the trace counters
    are the assertion."""
    eng = _engine(params, num_slots=2)
    inj = FaultInjector(seed=0).abort_request("r2", at_step=4)
    sched = ServeScheduler(eng, fault_injector=inj)
    for i, plen in enumerate((4, 6, 5, 3, 7)):
        sched.submit(Request(request_id=f"r{i}",
                             tokens=_tokens(plen, seed=i),
                             max_new_tokens=4 + i % 3))
    stats = sched.run()
    assert len(stats.requests) == 5
    assert {r["state"] for r in stats.requests} == {"completed", "evicted"}
    assert eng.decode_traces == 1, \
        "slot membership changes must not retrace decode_step"
    # prompts bucket to pow2: {4, 8} at most
    assert eng.prefill_traces <= 2


def test_aot_compile_then_serve_traces_once(params):
    eng = _engine(params, num_slots=2).aot_compile(prompt_buckets=[8])
    assert eng.decode_traces == 1
    sched = ServeScheduler(eng)
    for i in range(3):
        sched.submit(Request(request_id=i, tokens=_tokens(6, seed=i),
                             max_new_tokens=3))
    sched.run()
    assert eng.decode_traces == 1      # served entirely from the AOT exe
    assert eng.prefill_traces == 1
    # reset drops state but keeps the compiled artifacts — including
    # the retained prefill LOWERINGS (PR 17): cost_ledger() on the warm-
    # restarted engine extracts from the saved artifacts, never
    # re-tracing or re-lowering
    assert set(eng._prefill_lowered) == {8}
    eng.reset()
    assert np.asarray(eng.cache.lengths).max() == 0
    assert set(eng._prefill_lowered) == {8}
    ledger = eng.cost_ledger()
    assert set(ledger["executables"]) == {"decode", "prefill_8"}
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    sched = ServeScheduler(eng)
    sched.submit(Request(request_id="again", tokens=_tokens(6),
                         max_new_tokens=2))
    sched.run()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1


# --------------------------------------------------------- termination

def test_eos_terminates_request(greedy2):
    # greedy decode is deterministic: discover the first generated token,
    # then rerun with that token as EOS — must stop after exactly 1 token
    sched = ServeScheduler(greedy2.reset())
    sched.submit(Request(request_id="probe", tokens=_tokens(5),
                         max_new_tokens=4))
    first = sched.run().requests[0]["generated"][0]

    sched2 = ServeScheduler(greedy2.reset())
    sched2.submit(Request(request_id="eos", tokens=_tokens(5),
                          max_new_tokens=16, eos_id=int(first)))
    rec = sched2.run().requests[0]
    assert rec["finish_reason"] == "eos"
    assert rec["new_tokens"] == 1
    assert rec["generated"][-1] == int(first)


def test_max_new_tokens_terminates(greedy3):
    sched = ServeScheduler(greedy3.reset())
    sched.submit(Request(request_id=0, tokens=_tokens(5),
                         max_new_tokens=5))
    rec = sched.run().requests[0]
    assert rec["finish_reason"] == "length"
    assert rec["new_tokens"] == 5


def test_context_full_terminates(greedy2):
    eng = greedy2.reset()
    sched = ServeScheduler(eng)
    sched.submit(Request(request_id=0, tokens=_tokens(28),
                         max_new_tokens=100))
    rec = sched.run().requests[0]
    assert rec["finish_reason"] == "context"
    assert rec["new_tokens"] == 4          # 28 + 4 == max_len == 32
    # slot freed at completion: lengths reset
    assert eng.lengths.max() == 0
    # the RAW engine refuses to decode a context-full slot (a clipped
    # cache write would silently corrupt the newest K/V row)
    eng.reset()
    eng.prefill({0: _tokens(31)})
    eng.decode_step(eng.last_tokens, np.array([True, False]))  # -> 32
    with pytest.raises(ValueError, match="max_len"):
        eng.decode_step(eng.last_tokens, np.array([True, False]))


def test_oversized_prompt_rejected(greedy2):
    sched = ServeScheduler(greedy2.reset())
    with pytest.raises(ValueError, match="no room"):
        sched.submit(Request(request_id=0, tokens=_tokens(32)))
    with pytest.raises(ValueError, match="empty"):
        sched.submit(Request(request_id=1, tokens=[]))


# ----------------------------------------------- eviction isolation

def _run_trace(eng, injector=None, n=4):
    sched = ServeScheduler(eng.reset(), fault_injector=injector)
    for i in range(n):
        sched.submit(Request(request_id=f"r{i}", tokens=_tokens(5, seed=i),
                             max_new_tokens=6))
    sched.run()
    return {r["request_id"]: r for r in sched.stats().requests}


@pytest.mark.fault
def test_mid_stream_abort_leaves_other_slots_bit_identical(greedy2):
    """FaultInjector aborts r1 mid-decode; every other request's token
    stream must match the abort-free run bit-for-bit (static shapes make
    slot arithmetic independent of slot membership)."""
    base = _run_trace(greedy2)
    inj = FaultInjector(seed=0).abort_request("r1", at_step=2)
    with GoodputLedger() as led:
        faulted = _run_trace(greedy2, injector=inj)
    assert faulted["r1"]["state"] == "evicted"
    assert faulted["r1"]["finish_reason"] == "aborted"
    for rid in ("r0", "r2", "r3"):
        assert faulted[rid]["state"] == "completed"
        assert faulted[rid]["generated"] == base[rid]["generated"], rid
    assert led.summary()["events"]["serve_request_evicted"] == 1


@pytest.mark.fault
def test_abort_of_still_queued_request(greedy2):
    """Satellite regression (PR 8): aborting a request that was never
    admitted must remove it from the queue, account it exactly once,
    publish the abort event — and charge its wasted queue time as a
    ``serve_queue_wait`` loss (previously the wait silently vanished).
    Both entry points: a direct cross-thread-style abort() call and the
    FaultInjector-scripted path."""
    # direct call, before any tick: 3 requests, 2 slots -> "c" queued
    sched = ServeScheduler(greedy2.reset())
    for rid in ("a", "b", "c"):
        sched.submit(Request(request_id=rid, tokens=_tokens(5),
                             max_new_tokens=3))
    assert sched.abort("c") is True
    assert all(r.request_id != "c" for r in sched.queue)
    assert sched.abort("c") is False      # terminal: never re-accounted
    stats = sched.run()
    recs = {r["request_id"]: r for r in stats.requests}
    assert len(stats.requests) == 3
    assert recs["c"]["state"] == "evicted"
    assert recs["c"]["finish_reason"] == "aborted"
    assert recs["c"]["new_tokens"] == 0
    assert recs["a"]["state"] == recs["b"]["state"] == "completed"

    # injector path mid-run, with the event + queue-wait accounting
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("request_id") == "r2"
        and r.get("event") in ("serve_request_evicted",
                               "serve_queue_wait") else None)
    try:
        inj = FaultInjector(seed=0).abort_request("r2", at_step=1)
        sched = ServeScheduler(greedy2.reset(), fault_injector=inj)
        for i in range(3):
            sched.submit(Request(request_id=f"r{i}",
                                 tokens=_tokens(5, seed=i),
                                 max_new_tokens=4))
        stats = sched.run()
    finally:
        unsub()
    recs = {r["request_id"]: r for r in stats.requests}
    assert recs["r2"]["state"] == "evicted"
    assert recs["r2"]["finish_reason"] == "aborted"
    evicted = [r for r in seen if r["event"] == "serve_request_evicted"]
    waits = [r for r in seen if r["event"] == "serve_queue_wait"]
    assert len(evicted) == 1 and evicted[0]["reason"] == "aborted"
    assert len(waits) == 1 and waits[0]["seconds"] >= 0.0


# -------------------------------------------------------- determinism

def test_greedy_is_deterministic_and_argmax(greedy3, keeper3):
    seq = _tokens(6)
    first, last_logits, _ = keeper3.reset().prefill({0: seq})
    assert first[0] == int(np.asarray(last_logits)[0].argmax())
    runs = []
    for _ in range(2):
        s = ServeScheduler(greedy3.reset())
        s.submit(Request(request_id=0, tokens=seq, max_new_tokens=8))
        runs.append(s.run().requests[0]["generated"])
    assert runs[0] == runs[1]


def test_sampled_decode_replays_under_fixed_key(params):
    eng = _engine(params, temperature=0.8, top_k=5)

    def run(seed):
        s = ServeScheduler(eng.reset(seed))
        s.submit(Request(request_id=0, tokens=_tokens(6),
                         max_new_tokens=8))
        return s.run().requests[0]["generated"]

    assert run(1) == run(1)          # threaded PRNG: same seed, same stream
    assert run(1) != run(2)          # and the key actually matters


def test_top_k_restricts_to_top_k(params, keeper3):
    seq = _tokens(6)
    _, last_logits, _ = keeper3.reset().prefill({0: seq})
    top5 = set(np.argsort(np.asarray(last_logits)[0])[-5:].tolist())
    eng = _engine(params, temperature=1.5, top_k=5)
    for seed in range(2):
        first, _, _ = eng.reset(seed).prefill({0: seq})
        assert int(first[0]) in top5


# ------------------------------------------------------------ tracing

def test_request_traces_reconcile_with_stats(greedy2):
    """THE tracing acceptance: every completed request is exactly one
    trace with queue/prefill/decode/complete spans whose durations equal
    the scheduler's own TTFT/latency accounting (same clock reads), the
    scheduler trace carries one decode_tick per step, and tracing adds
    ZERO compiles (the one-jit invariant holds with it on)."""
    from apex_tpu.monitor import Tracer, spans_by_trace

    eng = greedy2.reset()
    tracer = Tracer()
    sched = ServeScheduler(eng, tracer=tracer)
    for i in range(4):
        sched.submit(Request(request_id=f"r{i}",
                             tokens=_tokens(5, seed=i), max_new_tokens=4))
    stats = sched.run()
    assert eng.decode_traces == 1          # tracing retraced nothing
    by_trace = spans_by_trace(tracer.completed_records())
    recs = {r["request_id"]: r for r in stats.requests}
    assert len(recs) == 4
    tol = 2e-3  # span stamps round to the microsecond; ttft to 1e-6 s
    for rid, rec in recs.items():
        spans = {s["name"]: s for s in by_trace[f"request:{rid}"]}
        assert set(spans) == {"request", "queue", "prefill", "decode",
                              "complete"}, rid
        q, p, d = spans["queue"], spans["prefill"], spans["decode"]
        root = spans["request"]
        assert abs((q["t1"] - q["t0"]) + (p["t1"] - p["t0"])
                   - rec["ttft_s"]) < tol
        assert abs((root["t1"] - root["t0"]) - rec["latency_s"]) < tol
        assert abs((d["t1"] - d["t0"])
                   - (rec["latency_s"] - rec["ttft_s"])) < tol
        assert root["attrs"]["new_tokens"] == rec["new_tokens"]
        for s in spans.values():
            assert s["status"] == "ok"
    ticks = [s for s in by_trace["serve:scheduler"]
             if s["name"] == "decode_tick"]
    assert len(ticks) == stats.decode_steps
    assert not tracer.open_spans()         # run() closed everything


@pytest.mark.fault
def test_aborted_request_trace_marks_abort(greedy2):
    from apex_tpu.monitor import Tracer, spans_by_trace

    tracer = Tracer()
    inj = FaultInjector(seed=0).abort_request("r1", at_step=2)
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                           tracer=tracer)
    for i in range(2):
        sched.submit(Request(request_id=f"r{i}",
                             tokens=_tokens(5, seed=i), max_new_tokens=6))
    sched.run()
    spans = {s["name"]: s for s in spans_by_trace(
        tracer.completed_records())["request:r1"]}
    assert "abort" in spans and "complete" not in spans
    assert spans["request"]["status"] == "cancelled"
    assert spans["request"]["attrs"]["finish_reason"] == "aborted"
    # the surviving request completed normally
    other = spans_by_trace(tracer.completed_records())["request:r0"]
    assert {s["name"] for s in other} >= {"request", "complete"}


def test_untraced_scheduler_publishes_no_spans(greedy3):
    """Tracing disabled (the default) adds nothing: no span records on
    the bus, no per-request bookkeeping, and — asserted everywhere else
    in this file — no extra compiles."""
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r) if str(r.get("event", "")).startswith(
            "span_") else None)
    try:
        sched = ServeScheduler(greedy3.reset())
        sched.submit(Request(request_id=0, tokens=_tokens(5),
                             max_new_tokens=2))
        sched.run()
    finally:
        unsub()
    assert not seen
    assert sched.tracer is None and not sched._req_spans


# ------------------------------------- host spans and device scopes

def _spanned_run(eng, tracer=None):
    """A short mixed run; with ``tracer`` installed as the process
    tracer for its length. Returns the greedy streams by request."""
    from apex_tpu.monitor.trace import set_tracer

    prev = set_tracer(tracer) if tracer is not None else None
    try:
        sched = ServeScheduler(eng.reset())
        reqs = _mixed_requests()
        for r in reqs:
            sched.submit(r)
        sched.run()
    finally:
        if tracer is not None:
            set_tracer(prev)
    return {r.request_id: list(r.generated) for r in reqs}


def test_apex_spans_count_the_engine_calls_and_hold_their_children(paged3):
    """With a process tracer installed the hot path's ``apex.*`` ranges
    become spans: one ``apex.decode_step`` per decode call and one
    ``apex.prefill`` per prefill call, each with its children in order
    and inside it, under the tick that made the call; the occupancy they
    carry stays inside what the engine has."""
    from apex_tpu.monitor import Tracer

    tracer = Tracer()
    _spanned_run(paged3, tracer)
    recs = [r for r in tracer.completed_records()
            if r["name"].startswith("apex.")]
    by_id = {r["span_id"]: r for r in recs}
    named = collections.defaultdict(list)
    for r in recs:
        named[r["name"]].append(r)
    assert len(named["apex.decode_step"]) == paged3.decode_calls > 0
    assert len(named["apex.prefill"]) == paged3.prefill_calls > 1
    assert len(named["apex.sched.accept"]) == paged3.decode_calls
    assert len(named["apex.sched.admit"]) == paged3.prefill_calls

    def children(parent):
        kids = sorted((r for r in recs
                       if r["parent_id"] == parent["span_id"]),
                      key=lambda r: r["span_id"])
        for a, b in zip([parent] + kids, kids):
            assert a["t0"] <= b["t0"]
        for kid in kids:
            assert parent["t0"] <= kid["t0"] <= kid["t1"] <= parent["t1"]
        for a, b in zip(kids, kids[1:]):
            assert a["t1"] <= b["t0"]
        return [k["name"] for k in kids]

    for step in named["apex.decode_step"]:
        assert children(step) == ["apex.decode_step.launch",
                                  "apex.decode_step.fetch"]
        a = step["attrs"]
        assert 0 < a["active"] <= a["slots"] == 3
        assert 0 < a["pages_in_use"] <= a["pages"] \
            == paged3.pool.capacity
        assert 0 < a["resident"] <= a["pages_in_use"] * 8
        assert by_id[step["parent_id"]]["name"] == "apex.sched.step"
    for call in named["apex.prefill"]:
        assert children(call) == [
            "apex.prefill.plan", "apex.prefill.launch",
            "apex.prefill.fetch", "apex.prefill.index"]
        assert 0 < call["attrs"]["admitted"] <= call["attrs"]["slots"] == 3
        assert by_id[call["parent_id"]]["name"] == "apex.sched.admit"
    for launch in named["apex.prefill.launch"]:
        a = launch["attrs"]
        assert 0 < a["real_positions"] <= a["bucket"] * a["slots"]
        assert a["hit_tokens"] == 0 and a["new_pages"] > 0
    for tick in named["apex.sched.step"]:
        assert tick["parent_id"] is None and tick["attrs"]["queued"] >= 0
        assert set(children(tick)) <= {"apex.sched.admit",
                                       "apex.decode_step",
                                       "apex.sched.accept"}
    assert sum(a["attrs"]["real_positions"]
               for a in named["apex.prefill.launch"]) \
        == sum(len(r.tokens) for r in _mixed_requests())
    assert not tracer.open_spans()


def test_spans_on_change_no_token_and_retrace_nothing(paged3):
    from apex_tpu.monitor import Tracer

    plain = _spanned_run(paged3)
    tracer = Tracer()
    traced = _spanned_run(paged3, tracer)
    assert traced == plain and all(plain.values())
    assert paged3.decode_traces == 1
    assert any(r["name"] == "apex.decode_step"
               for r in tracer.completed_records())


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("layout", ["slot", "paged", "tp2"])
def test_lowered_programs_name_the_cache_write_and_the_projection(
        layout, program, tp_devices):
    """``kv_write`` and ``attn_proj`` ride the lowered program's location
    metadata, nested in ``attention``: what a device trace's reader
    splits attention by. Names only: the cost ledger, which knows
    neither, still files both under ``attention``."""
    eng = Engine(CFG, init_gpt2_params(CFG), EngineConfig(
        num_slots=2, max_len=32, temperature=0.0,
        page_size=None if layout == "slot" else 8,
        tp=2 if layout == "tp2" else 1))
    fn, args = {"decode": (eng._decode, eng._decode_args()),
                "prefill": (eng._make_prefill(8), eng._prefill_args(8))
                }[program]
    text = fn.lower(*args).as_text(debug_info=True)
    paths = [p.split("/") for p in set(re.findall(r'"([^"]*)"', text))]
    for scope in ("kv_write", "attn_proj"):
        inside = [p for p in paths if scope in p[:-1]]
        assert inside, scope
        assert all(p[p.index(scope) - 1] == "attention" for p in inside), \
            inside[:3]
    assert not any("kv_write" in p and "attn_proj" in p for p in paths)


# -------------------------------------------------- scheduler / events

def test_backfill_and_queue_wait_accounting(greedy2):
    with GoodputLedger() as led:
        sched = ServeScheduler(greedy2.reset())
        for i in range(5):
            sched.submit(Request(request_id=i, tokens=_tokens(5, seed=i),
                                 max_new_tokens=3))
        stats = sched.run()
    s = stats.summary()
    assert s["completed"] == 5
    g = led.summary()
    assert g["events"]["serve_request_admitted"] == 5
    assert g["events"]["serve_request_completed"] == 5
    assert g["events"]["serve_decode_step"] == stats.decode_steps
    # 3 of 5 requests waited for a slot: queue-wait is a goodput cause
    assert g["lost_by_cause"].get("serve_queue_wait", 0.0) > 0.0
    assert s["tokens_per_s"] > 0
    assert s["p99_step_ms"] >= s["p50_step_ms"] >= 0


def test_stats_record_shape(greedy3):
    sched = ServeScheduler(greedy3.reset())
    sched.submit(Request(request_id="x", tokens=_tokens(5),
                         max_new_tokens=2))
    rec = sched.run().requests[0]
    for key in ("request_id", "state", "finish_reason", "prompt_tokens",
                "new_tokens", "generated", "ttft_s", "latency_s",
                "tokens_per_s"):
        assert key in rec, key


# --------------------------------------------------- tuned geometry

def test_decode_attention_block_drives_geometry(params):
    """An explicit (valid) block_k changes the decode step's
    partial-reduction order — parity with prefill (to ``BORDER``) must
    survive the non-default geometry; an invalid one must be rejected
    loudly."""
    seq = _tokens(8)
    full = _engine(params, keep_prefill_logits=True, block_k=8)
    _, _, all_logits = full.prefill({1: seq})
    inc = _engine(params, block_k=8)
    inc.prefill({1: seq[:4]})
    for j in range(4, len(seq)):
        forced = np.array([0, seq[j], 0], np.int32)
        _, logits = inc.decode_step(forced,
                                    np.array([False, True, False]))
        # across the prefill/decode border: float32 rounding (BORDER)
        np.testing.assert_allclose(np.asarray(all_logits)[j, 1],
                                   np.asarray(logits)[1], **BORDER)
    with pytest.raises(ValueError, match="divide"):
        _engine(params, block_k=7)


def test_decode_attention_registered_with_tune():
    from apex_tpu.tune import CODE_VERSIONS
    from apex_tpu.tune import registry

    assert "decode_attention" in CODE_VERSIONS
    spec = registry.spec("decode_attention")
    shape = dict(spec.default_shapes[0])
    cands = spec.candidates(shape)
    assert spec.defaults(shape) in cands
    # the build runs the real decode attention at a small geometry
    small = {"b": 2, "max_len": 64, "heads": 2, "d": 8}
    p = spec.defaults(small)
    step, state, consts = spec.build(small, jnp.float32, p)
    out = step(0, state, *consts)
    assert out.shape == state.shape


# ----------------------------------- paged KV pool + prefix caching

@pytest.fixture(scope="module")
def paged3(params):
    """Shared 3-slot greedy engine with 4 pages a slot (page_size 8, a
    pool of every slot's whole context, prefix index on); tests reset()
    it — compiled once. The prefix index only fires on page-aligned
    shared prompts, so parity tests with distinct prompts share no
    page."""
    return _engine(params, page_size=8, prefix_cache=True)


@pytest.fixture(scope="module")
def slot8(params):
    """The default geometry (no ``page_size``: ONE ``max_len`` page a
    slot, several chunks inside the page) pinned
    to block_k=8 — ``paged3``'s chunk geometry. Bit-exactness across
    page sizes holds at EQUAL block_k (only the K/V fetch differs
    then); at different block_k the softmax partial-sum order differs
    by design."""
    return _engine(params, block_k=8)


def _mixed_requests(n=5, seed0=0, max_new=5):
    """Mixed-length prompts on 3 slots: staggered completions force
    eviction + backfill mid-trace."""
    return [Request(request_id=f"r{i}",
                    tokens=_tokens(4 + 3 * (i % 4), seed=seed0 + i),
                    max_new_tokens=max_new) for i in range(n)]


def _trace_outputs(eng, reqs, injector=None):
    sched = ServeScheduler(eng, fault_injector=injector)
    for r in reqs:
        sched.submit(r)
    return {r["request_id"]: r for r in sched.run().requests}


def test_paged_bit_exact_vs_slot_greedy(slot8, paged3):
    """Several pages equal one page a slot: an identical mixed-length
    request trace through an engine built with no ``page_size`` and
    through one with 8-token pages (4 a slot) produces bit-identical greedy
    streams — where the pages lie never enters the chunked-softmax
    arithmetic, only the K/V fetch differs. Both engines run the same
    block_k (paged3's page-sized default): equal chunk geometry is the
    bit-exactness precondition, and the autotuner keys it per page size
    so a deployment pins it the same way."""
    assert paged3.block_k == slot8.block_k == 8
    assert slot8.config.page_size is None
    assert slot8.page_size == slot8.max_len
    assert slot8.cache.page_table.shape == (3, 1)
    base = _trace_outputs(slot8.reset(), _mixed_requests())
    got = _trace_outputs(paged3.reset(), _mixed_requests())
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}
    assert {k: v["finish_reason"] for k, v in got.items()} == \
           {k: v["finish_reason"] for k, v in base.items()}


def test_paged_decode_logits_match_slot_prefill(params, paged3, slot8):
    """Strongest form: the small-page engine's incremental decode logits
    match the one-page-a-slot engine's full-sequence prefill logits —
    crossing both the page size (bit-exact on its own, asserted below on
    the prefill side) and the prefill/decode border (float32 rounding, ``BORDER``),
    at the shared block_k=8 chunk geometry."""
    seq = _tokens(12)
    keeper = _engine(params, keep_prefill_logits=True, block_k=8)
    _, _, all_logits = keeper.prefill({1: seq})
    all_logits = np.asarray(all_logits)          # [P, B, V]
    inc = paged3.reset()
    _, paged_last, _ = inc.prefill({1: seq[:5]})
    # the page size alone, same program shape: the chunk's attention never
    # sees where its rows are stored, so this stays bit-exact (slot8 and
    # not the keeper, whose logits product runs over every row)
    _, slot_last, _ = slot8.reset().prefill({1: seq[:5]})
    np.testing.assert_array_equal(np.asarray(paged_last)[1],
                                  np.asarray(slot_last)[1])
    for j in range(5, len(seq)):
        forced = np.array([0, seq[j], 0], np.int32)
        _, logits = inc.decode_step(forced, np.array([False, True, False]))
        a, b = all_logits[j, 1], np.asarray(logits)[1]
        assert a.dtype == np.float32
        np.testing.assert_allclose(a, b, err_msg=f"paged decode pos {j}",
                                   **BORDER)


@pytest.mark.slow
def test_paged_bit_exact_vs_slot_sampled(params):
    """Seeded sampling: the PRNG key is engine state split once per
    prefill/decode call in BOTH layouts, so identical traces consume
    identical key paths — sampled streams match token-for-token.

    Slow tier: the greedy paged-vs-slot parity above pins the layout
    equivalence in tier-1; this adds the PRNG-path leg."""
    kw = dict(temperature=0.8, top_k=5, block_k=8)
    base = _trace_outputs(_engine(params, **kw), _mixed_requests(max_new=6))
    got = _trace_outputs(_engine(params, page_size=8, **kw),
                         _mixed_requests(max_new=6))
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}


def test_paged_decode_compiles_once_across_admit_evict_backfill(params):
    """The one-compile invariant survives paging: page tables are data,
    so admissions, completions, a scripted mid-stream abort, and
    backfill (page alloc/release/COW churn included) trace decode_step
    exactly once. Fresh engine: the counters are the assertion."""
    eng = _engine(params, num_slots=2, page_size=8, prefix_cache=True)
    inj = FaultInjector(seed=0).abort_request("r2", at_step=4)
    sched = ServeScheduler(eng, fault_injector=inj)
    for i, plen in enumerate((4, 6, 5, 3, 7)):
        sched.submit(Request(request_id=f"r{i}",
                             tokens=_tokens(plen, seed=i),
                             max_new_tokens=4 + i % 3))
    stats = sched.run()
    assert len(stats.requests) == 5
    assert {r["state"] for r in stats.requests} == {"completed", "evicted"}
    assert eng.decode_traces == 1, \
        "page-table churn must not retrace decode_step"
    assert eng.prefill_traces <= 2          # pow2 buckets {4, 8}


def test_prefix_hit_skips_prefill(paged3):
    """A request whose prompt prefix is resident skips prefill for those
    pages: asserted by the engine's scan counters (never wall clock), the
    serve_prefix_hit event fires, and the stream built on shared pages is
    bit-identical to a cold prefill."""
    eng = paged3.reset()
    sysp = _tokens(16, seed=42)              # two full pages
    warm_prompt = sysp + _tokens(5, seed=2)
    # cold baseline for the WARM request (fresh engine state, no index)
    base = _trace_outputs(eng, [Request(request_id="b",
                                        tokens=list(warm_prompt),
                                        max_new_tokens=3)])
    eng.reset()
    # cold run seeds the index (a request can't hit its own admission)
    _trace_outputs(eng, [Request(request_id="a",
                                 tokens=sysp + _tokens(5, seed=1),
                                 max_new_tokens=3)])
    assert eng.prefix_hits == 0
    scanned_cold = eng.prefill_scanned_tokens
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("event") == "serve_prefix_hit" else None)
    try:
        got = _trace_outputs(eng, [Request(request_id="b",
                                           tokens=list(warm_prompt),
                                           max_new_tokens=3)])
    finally:
        unsub()
    assert eng.prefix_hits == 1 and eng.prefix_hit_tokens == 16
    # the warm prefill scanned only the 5-token tail's pow2 bucket (8),
    # not the 21-token prompt's (32): the prefill-work skip, in counters
    assert eng.prefill_scanned_tokens - scanned_cold == 8
    assert len(seen) == 1
    assert seen[0]["hit_tokens"] == 16 and seen[0]["hit_pages"] == 2
    assert seen[0]["scanned_tokens"] == 5
    # shared read-only pages hold the same bytes a cold prefill writes
    assert got["b"]["generated"] == base["b"]["generated"]


def test_prefix_cache_cow_tail_page(paged3):
    """A fully-cached prompt caps its hit one token short (the final
    prompt token must re-run to seed sampling), which copies the
    boundary page (copy-on-write) before appending — and stays
    bit-exact."""
    eng = paged3.reset()
    sysp = _tokens(16, seed=42)              # exactly two pages
    cold = _trace_outputs(eng, [Request(request_id="cold",
                                        tokens=list(sysp),
                                        max_new_tokens=4)])
    warm = _trace_outputs(eng, [Request(request_id="warm",
                                        tokens=list(sysp),
                                        max_new_tokens=4)])
    # 1 full page shared + COW of the second: 15 of 16 tokens reused
    assert eng.prefix_hits == 1 and eng.prefix_hit_tokens == 15
    assert warm["warm"]["generated"] == cold["cold"]["generated"]
    # the index's read-only page survived the COW append untouched: a
    # third identical prompt hits the same 15 tokens again
    warm2 = _trace_outputs(eng, [Request(request_id="w2",
                                         tokens=list(sysp),
                                         max_new_tokens=4)])
    assert eng.prefix_hit_tokens == 30
    assert warm2["w2"]["generated"] == cold["cold"]["generated"]


def test_engine_reset_clears_pool_and_prefix_index(paged3):
    """Satellite regression: reset() must return every page to the free
    list and drop the prefix index — tests share compiled engines across
    scenarios, and a leaked refcount would poison the next one."""
    eng = paged3.reset()
    sysp = _tokens(16, seed=42)
    first = _trace_outputs(eng, [
        Request(request_id=f"r{i}", tokens=sysp + _tokens(3, seed=i),
                max_new_tokens=3) for i in range(2)])
    # completed requests released their pages; the index still pins the
    # shared prefix pages — exactly what reset() must reclaim
    assert len(eng.prefix) == 2
    assert eng.pool.free_count < eng.pool.capacity
    eng.reset()
    assert eng.pool.free_count == eng.pool.capacity
    assert all(rc == 0 for rc in eng.pool.refcount[1:])
    assert len(eng.prefix) == 0
    assert eng.prefix_hits == 0 and eng.prefill_calls == 0
    assert np.asarray(eng.cache.lengths).max() == 0
    # the scenario replays bit-identically on the reset engine
    again = _trace_outputs(eng, [
        Request(request_id=f"r{i}", tokens=sysp + _tokens(3, seed=i),
                max_new_tokens=3) for i in range(2)])
    assert {k: v["generated"] for k, v in again.items()} == \
           {k: v["generated"] for k, v in first.items()}


def test_paged_geometry_validation(params):
    """Bad pool geometry is a clear build-time ValueError, never a bad
    gather at trace time."""
    with pytest.raises(ValueError, match="divide"):
        _engine(params, page_size=5)              # 32 % 5 != 0
    with pytest.raises(ValueError, match="divide page_size"):
        _engine(params, page_size=8, block_k=16)  # chunk spans 2 pages
    with pytest.raises(ValueError, match="null page"):
        _engine(params, page_size=8, num_pages=4)  # < max_pages + 1
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(params, prefix_cache=True)        # needs the pool
    with pytest.raises(ValueError, match="num_pages"):
        _engine(params, num_pages=9)              # needs page_size


def test_overcommitted_pool_stalls_then_completes(slot8, params):
    """An overcommitted pool (the point of paging) admits what fits and
    stalls the queue head until completions free pages — the stall is
    charged to serve_page_alloc_fail (a timed cause distinct from
    queue_wait), every request completes, and outputs still match the
    one-page-a-slot engine's (which never stalls) bit-for-bit."""
    base = _trace_outputs(slot8.reset(), _mixed_requests())
    # 5 allocatable pages against ~2-page reservations: two requests fit,
    # the third stalls on pages while a SLOT sits free — KV-bound, not
    # slot-bound (admission order shifts, per-slot greedy streams don't)
    eng = _engine(params, page_size=8, num_pages=6)
    stalls = []
    unsub = subscribe_events(
        lambda r: stalls.append(r)
        if r.get("event") == "serve_page_alloc_fail" else None)
    try:
        with GoodputLedger() as led:
            got = _trace_outputs(eng, _mixed_requests())
    finally:
        unsub()
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}
    s = led.summary()
    assert s["events"].get("serve_page_alloc_fail", 0) >= 1
    assert s["lost_by_cause"].get("serve_page_alloc_fail", 0.0) > 0.0
    # every published stall is a REAL cross-tick window — an admission
    # that merely rides along while the head stays blocked must not
    # close-and-reopen the window as a spurious ~0s event
    assert all(e["seconds"] > 1e-4 for e in stalls), stalls
    assert eng.decode_traces == 1


def test_idle_tick_releases_pages_for_stalled_admission(params):
    """Review regression: when every running request terminates without
    a decode step following (an abort on an otherwise-idle tick), the
    deferred device-side eviction must still run — before the fix a
    paged engine's pages stayed refcounted, the queue head's page probe
    failed forever, and the scheduler livelocked with a free slot, a
    non-empty queue, and decode_steps pinned below max_steps."""
    eng = _engine(params, num_slots=2, page_size=8, num_pages=6)
    sched = ServeScheduler(eng)
    # the hog reserves 4 of the 5 allocatable pages
    sched.submit(Request(request_id="hog", tokens=_tokens(10),
                         max_new_tokens=20))
    sched.step()
    sched.submit(Request(request_id="r1", tokens=_tokens(9, seed=3),
                         max_new_tokens=4))
    assert sched.abort("hog") is True
    # bounded manual ticks (never run(): the pre-fix failure mode is an
    # unbounded loop) — r1 must get the hog's pages and complete
    for _ in range(40):
        if not sched.step():
            break
    recs = {r["request_id"]: r for r in sched.stats().requests}
    assert recs["r1"]["state"] == "completed", recs
    assert recs["hog"]["state"] == "evicted"


def test_admission_probe_protects_batch_hits(params):
    """Review hardening: the admission probe threads a protect set
    across a batch, so a page one member plans to share is never counted
    as evictable headroom for a later member — otherwise prefill (which
    protects the whole batch's hits from eviction) would free fewer
    pages than the probes assumed and fail allocation mid-batch."""
    eng = _engine(params, num_slots=3, max_len=16, page_size=8,
                  num_pages=5, prefix_cache=True)
    p1, p2 = _tokens(8, seed=21), _tokens(8, seed=22)
    _trace_outputs(eng, [
        Request(request_id="s1", tokens=p1 + [1], max_new_tokens=1),
        Request(request_id="s2", tokens=p2 + [2], max_new_tokens=1)])
    assert len(eng.prefix) == 2 and eng.pool.free_count == 2
    # hold the remaining free pages in a live slot: every further page
    # must now come from evicting an index entry
    eng.prefill({0: _tokens(9, seed=30)}, budgets={0: 1})
    assert eng.pool.free_count == 0
    protect: set = set()
    # member 1 hits p1's page and takes the last evictable (p2's) as
    # its fresh page
    c1 = eng.admission_page_cost(p1 + [5, 6], 1, 0, protect=protect)
    assert c1 == 1 and protect
    # member 2 needs one page; p1's page must NOT count as its headroom
    # (member 1 is sharing it) — before the fix this probe passed and
    # prefill raised PagePoolExhausted mid-batch
    assert eng.admission_page_cost(_tokens(6, seed=31), 1, c1,
                                   protect=protect) is None


def test_stall_window_closes_when_stalled_head_leaves_queue(params):
    """Review regression: a queue head stalled on pages that then leaves
    the queue WITHOUT being admitted (abort here; deadline expiry and
    load shedding share ``_stall_head_removed``) must close-and-charge
    the stall window at its departure — before the fix the window stayed
    open and the NEXT admission charged the whole intervening idle span
    to ``serve_page_alloc_fail`` as phantom lost capacity."""
    eng = _engine(params, num_slots=2, page_size=8, num_pages=6)
    sched = ServeScheduler(eng)
    stalls = []
    unsub = subscribe_events(
        lambda r: stalls.append(r)
        if r.get("event") == "serve_page_alloc_fail" else None)
    try:
        # the hog reserves 4 of the 5 allocatable pages; "big" needs 2
        # pages and stalls at the head
        sched.submit(Request(request_id="hog", tokens=_tokens(10),
                             max_new_tokens=20))
        sched.step()
        sched.submit(Request(request_id="big", tokens=_tokens(9, seed=3),
                             max_new_tokens=4))
        sched.step()
        assert sched._alloc_stall_t0 is not None   # window open
        time.sleep(0.03)                           # real blocked span
        assert sched.abort("big") is True
        # closed AT removal: the blocked span is charged, nothing after
        assert sched._alloc_stall_t0 is None
        assert len(stalls) == 1 and stalls[0]["seconds"] >= 0.03
        time.sleep(0.2)                            # idle, pool unchanged
        # "late" fits the remaining free page and admits instantly: no
        # second stall event, and in particular none spanning the idle
        sched.submit(Request(request_id="late", tokens=_tokens(3, seed=4),
                             max_new_tokens=2))
        for _ in range(60):
            if not sched.step():
                break
    finally:
        unsub()
    recs = {r["request_id"]: r for r in sched.stats().requests}
    assert recs["late"]["state"] == "completed", recs
    assert len(stalls) == 1, stalls
    assert eng.decode_traces == 1


def test_plan_admission_empty_prompt():
    """Review regression: an empty prompt (legal for the planner — only
    ``ServeScheduler.submit`` rejects it) must plan zero shared tokens
    instead of ``use=-1`` whose tail-page remainder indexed ``hits[-1]``
    on an empty hit list."""
    from apex_tpu.serve import paging

    for idx in (None, paging.PrefixIndex(page_size=8)):
        plan = paging.plan_admission([], 4, 32, 8, idx)
        assert plan["use"] == 0 and plan["shared_pages"] == 0
        assert plan["cow_src"] is None and plan["tail"] == []
        assert plan["hits"] == []
        assert plan["new_pages"] == plan["total_pages"] >= 1


def test_decode_attention_page_geometry_registered():
    """Satellite: page_size is a shape-key axis of the decode_attention
    autotuner (one page a slot is keyed at ``max_len``, never 0, and
    winners of two page sizes never collide), candidates must divide the
    page, and CODE_VERSIONS invalidates the entries keyed on the
    slot-contiguous layout that is gone."""
    from apex_tpu.tune import CODE_VERSIONS
    from apex_tpu.tune import registry

    assert CODE_VERSIONS["decode_attention"] >= 4
    spec = registry.spec("decode_attention")
    k_slot = spec.shape_key({"max_len": 64, "heads": 2, "d": 8})
    k_paged = spec.shape_key({"max_len": 64, "page_size": 16,
                              "heads": 2, "d": 8})
    assert k_slot != k_paged
    assert ("page_size", 64) in k_slot and ("page_size", 16) in k_paged
    paged_shape = {"b": 2, "max_len": 64, "page_size": 16,
                   "heads": 2, "d": 8}
    cands = spec.candidates(paged_shape)
    assert cands and all(16 % c["block_k"] == 0 for c in cands)
    assert spec.defaults(paged_shape) in cands
    assert all(s.get("page_size") for s in spec.default_shapes)
    # the build runs the real page-table gather path, at one page a
    # slot too
    for shape in (paged_shape, {"b": 2, "max_len": 64, "heads": 2, "d": 8}):
        p = spec.defaults(shape)
        step, q, consts = spec.build(shape, jnp.float32, p)
        assert step(0, q, *consts).shape == q.shape


# ------------------------------------------------------------ CLIs

@pytest.mark.parametrize("layout", ["slot", "paged", "tp2"])
def test_lowerings_take_the_weights_as_arguments(layout, tp_devices):
    """Decode, prefill and verify take the weights as ``main`` arguments
    and carry no dense literal beyond a few KB: a closed-over param tree
    is lowered as constants — at GPT-2 XL, 6.2 GB of literal in every
    program, in the compile-cache key, and a second model copy in HBM."""
    from apex_tpu.monitor.costs import module_facts

    def lowered_facts(cfg):
        eng = Engine(cfg, init_gpt2_params(cfg), EngineConfig(
            num_slots=2, max_len=32, temperature=0.0, spec_draft_len=2,
            page_size=None if layout == "slot" else 8,
            tp=2 if layout == "tp2" else 1))
        programs = {
            "decode": (eng._decode, eng._decode_args()),
            "prefill": (eng._make_prefill(8), eng._prefill_args(8)),
            "verify": (eng._verify, eng._verify_args()),
        }
        weights = len(jax.tree_util.tree_leaves(eng._weights))
        out = {}
        for name, (fn, args) in programs.items():
            facts = module_facts(fn.lower(*args).as_text())
            assert facts["main_args"] == len(
                jax.tree_util.tree_leaves(args)) > weights, (name, facts)
            assert facts["max_literal_chars"] < 4096, (name, facts)
            out[name] = facts["module_chars"]
        return out

    small = lowered_facts(CFG)
    if layout == "slot":
        # 4x the embedding parameters, the same programs: the module text
        # must not grow with the parameter count
        big = lowered_facts(dataclasses.replace(
            CFG, vocab_size=CFG.vocab_size * 4))
        for name in small:
            assert abs(big[name] - small[name]) < 0.02 * small[name], name


def _cli_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_serve_cli_smoke(tmp_path):
    """The scripted-serve acceptance: one CLI run with --trace-jsonl
    yields a Perfetto-loadable trace where every completed request has
    exactly one trace with queue/prefill/decode/complete spans — and the
    run still compiles decode exactly once."""
    tpath = str(tmp_path / "serve_trace.json")
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.serve.cli", "--config", "tiny",
         "--requests", "3", "--prompt-len", "4", "--max-new-tokens", "4",
         "--num-slots", "2", "--max-len", "32", "--temperature", "0",
         "--aot", "--trace-jsonl", tpath],
        cwd=ROOT, env=_cli_env(), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    recs, summary = lines[:-1], lines[-1]
    assert len(recs) == 3
    assert all(rec["state"] == "completed" for rec in recs)
    assert summary["decode_compiles"] == 1
    assert summary["summary"]["new_tokens"] == 12
    # the record names what ran it: under the harness that is the CPU,
    # with Pallas interpreted — never mistakable for a chip run
    assert summary["device"]["platform"] == "cpu"
    assert summary["device"]["device_kind"] == "cpu"
    assert summary["device"]["device_count"] >= 1
    assert summary["device"]["interpret_mode"] is True

    from apex_tpu.monitor.trace import read_chrome_trace

    events = read_chrome_trace(tpath)           # strict JSON when closed
    xs = [e for e in events if e.get("ph") == "X"]
    per_trace = {}
    for e in xs:
        per_trace.setdefault(e["args"]["trace_id"], set()).add(e["name"])
    for rec in recs:
        spans = per_trace[f"request:{rec['request_id']}"]
        assert spans == {"request", "queue", "prefill", "decode",
                         "complete"}, rec["request_id"]
        # durations reconcile with the CLI's own accounting (±1 tick)
        root = next(e for e in xs
                    if e["args"]["trace_id"]
                    == f"request:{rec['request_id']}"
                    and e["name"] == "request")
        tick_ms = summary["summary"]["p99_step_ms"] + 1.0
        assert abs(root["dur"] / 1e3 - rec["latency_s"] * 1e3) <= tick_ms
    assert "serve:scheduler" in per_trace       # the tick track


@pytest.mark.slow
def test_serve_cli_stdin_stream():
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.serve.cli", "--stdin",
         "--max-new-tokens", "2", "--num-slots", "2", "--max-len", "32",
         "--temperature", "0"],
        input="1 2 3\n7, 8, 9, 10\n", cwd=ROOT, env=_cli_env(),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()]
    assert len(lines) == 3
    assert {rec["prompt_tokens"] for rec in lines[:-1]} == {3, 4}


def test_serve_cli_rejects_bad_tokens():
    # input validation runs BEFORE params/compile: this fails fast
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.serve.cli", "--stdin",
         "--config", "tiny"],
        input="999999\n", cwd=ROOT, env=_cli_env(), capture_output=True,
        text=True, timeout=600)
    assert r.returncode == 2
    assert "vocab" in r.stderr


def test_bench_serve_smoke_and_regression_gate(tmp_path, capsys):
    """``apex-tpu-bench --serve`` emits the BENCH_SUITE shape; the
    regression gate compares it direction-aware (latency lower-is-better,
    throughput higher-is-better). In-process (the CLI smoke above covers
    the subprocess entry; a second jax import would only burn budget)."""
    from apex_tpu.bench_cli import _serve_bench

    _serve_bench(steps=6, num_slots=2)
    suite = json.loads(capsys.readouterr().out)
    entry = suite["serve_decode"]
    assert entry["value"] > 0 and entry["unit"] == "tokens_per_s"
    for k in ("p50_ms", "p99_ms", "ttft_ms"):
        assert entry[k] >= 0
    # capture provenance is stamped (device-kind gate satellite): on this
    # CPU harness the capture must say so
    for k in ("device_kind", "interpret_mode", "git", "captured"):
        assert k in suite, k
    assert suite["interpret_mode"] is True

    base = dict(suite)
    path_cur = tmp_path / "cur.json"
    path_base = tmp_path / "base.json"
    path_cur.write_text(json.dumps(suite))
    path_base.write_text(json.dumps(base))

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_regression
    finally:
        sys.path.pop(0)
    # identical capture: gate passes
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 0
    # direction-aware: higher latency AND lower throughput both regress
    worse = json.loads(json.dumps(suite))
    worse["serve_decode"]["p99_ms"] = entry["p99_ms"] * 10 + 1
    worse["serve_decode"]["value"] = entry["value"] / 10
    path_cur.write_text(json.dumps(worse))
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 1
    # ...and a FASTER capture (lower latency, higher tokens/s) passes
    better = json.loads(json.dumps(suite))
    better["serve_decode"]["p99_ms"] = entry["p99_ms"] / 10
    better["serve_decode"]["value"] = entry["value"] * 10
    path_cur.write_text(json.dumps(better))
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 0
    # SLO counters gate from a ZERO baseline: the healthy default
    # workload ships rejected=0/shed_rate=0, and a capture that starts
    # shedding must regress — a base==0 ratio skip would let it ship
    assert entry["rejected"] == 0 and entry["shed_rate"] == 0.0
    shedding = json.loads(json.dumps(suite))
    shedding["serve_decode"]["rejected"] = 5
    shedding["serve_decode"]["shed_rate"] = 0.31
    path_cur.write_text(json.dumps(shedding))
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 1


def test_serve_cli_paged_usage_errors(capsys):
    """``apex-tpu-serve --page-size --prefix-cache``: bad geometry is a
    clean usage error — exit 2 before anything compiles."""
    from apex_tpu.serve import cli

    # pool geometry that can't exist: exit 2 + the engine's message
    assert cli.main(["--config", "tiny", "--max-len", "32",
                     "--page-size", "7", "--requests", "1"]) == 2
    assert "divide" in capsys.readouterr().err
    # --prefix-cache without --page-size: same clean refusal
    assert cli.main(["--config", "tiny", "--max-len", "32",
                     "--prefix-cache", "--requests", "1"]) == 2
    assert "prefix_cache" in capsys.readouterr().err


@pytest.mark.slow
def test_serve_cli_paged_smoke(capsys, monkeypatch):
    """A shared-prefix stdin stream serves through the paged CLI with
    one decode compile and a real prefix hit. In-process (the subprocess
    smoke above covers the entry point). Slow tier: the paged engine
    compile (~11s) duplicates layout coverage the paged-vs-slot
    bit-exact tests keep in tier-1; the CLI flag plumbing stays tier-1
    via ``test_serve_cli_paged_usage_errors``."""
    import io

    from apex_tpu.serve import cli

    # one slot serializes the two requests, so the second admission sees
    # the first's prompt pages resident: a real end-to-end prefix hit
    prefix = " ".join(str(t) for t in range(1, 9))     # one full page
    monkeypatch.setattr("sys.stdin", io.StringIO(
        f"{prefix} 11\n{prefix} 12\n"))
    rc = cli.main(["--config", "tiny", "--stdin", "--max-len", "32",
                   "--num-slots", "1", "--max-new-tokens", "2",
                   "--temperature", "0", "--page-size", "8",
                   "--prefix-cache"])
    assert rc == 0
    lines = [json.loads(l)
             for l in capsys.readouterr().out.strip().splitlines()]
    recs, summary = lines[:-1], lines[-1]
    assert all(rec["state"] == "completed" for rec in recs)
    assert summary["decode_compiles"] == 1
    assert summary["summary"]["prefix_hits"] == 1
    assert summary["summary"]["prefix_hit_rate"] == 0.5
    assert summary["summary"]["peak_resident_tokens"] > 0


def test_serve_bench_usage_errors_exit_clean():
    """Review regression: bad pool geometry and a malformed
    ``--prompt-len`` spec are usage errors — one clean message via
    SystemExit (like the adjacent shared-prefix check), never a raw
    ValueError traceback."""
    from apex_tpu.bench_cli import _serve_bench

    with pytest.raises(SystemExit, match="page_size=7 must"):
        _serve_bench(steps=2, max_len=32, page_size=7)
    with pytest.raises(SystemExit, match="--prompt-len"):
        _serve_bench(steps=2, prompt_len="0:4")


@pytest.mark.slow
def test_paged_bench_capacity_and_gate(tmp_path, capsys):
    """ISSUE 9 bench acceptance: on a mixed-length shared-prefix
    workload, the paged capture shows >= 2x resident tokens per HBM byte
    vs the slot capture at the same workload, prefix_hit_rate > 0, and
    the capture gates through check_regression with page_size provenance
    (a lower hit rate regresses).

    Slow tier: two full ``_serve_bench`` compiles at max_len=128 are the
    single heaviest tier-1 item (~48s); the regression-gate direction
    coverage stays in tier-1 via
    ``test_bench_serve_smoke_and_regression_gate`` and the paged
    layout's correctness via the paged-vs-slot bit-exact tests."""
    from apex_tpu.bench_cli import _serve_bench

    # mixed 8..24-token prompts + a 16-token fleet-wide system prefix on
    # a max_len=128 context: the slot layout reserves 128 tokens/slot
    # for ~48-token requests — the waste paging reclaims
    kw = dict(steps=16, num_slots=4, max_len=128, prompt_len="8:24",
              shared_prefix=16)
    _serve_bench(**kw)
    slot = json.loads(capsys.readouterr().out)["serve_decode"]
    # equal workload, pool sized to the actual working set: 4 slots x 4
    # own pages + 2 shared prefix pages + the null page
    _serve_bench(**kw, page_size=8, num_pages=19, prefix_cache=True)
    suite = json.loads(capsys.readouterr().out)
    paged = suite["serve_decode"]

    assert paged["prefix_hit_rate"] > 0.0
    assert slot["prefix_hit_rate"] == 0.0
    assert paged["resident_tokens_per_hbm_byte"] >= \
        2.0 * slot["resident_tokens_per_hbm_byte"], \
        "paging must multiply resident-token capacity per HBM byte"
    # provenance: the pool geometry rides the workload record, so SLO/
    # capacity numbers are never gated across incomparable configs
    assert paged["workload"]["page_size"] == 8
    assert paged["workload"]["prefix_cache"] is True
    assert paged["workload"]["shared_prefix"] == 16
    assert slot["workload"]["page_size"] == 128      # one page a slot
    assert slot["workload"]["num_pages"] == 5

    path_cur = tmp_path / "cur.json"
    path_base = tmp_path / "base.json"
    path_base.write_text(json.dumps(suite))
    path_cur.write_text(json.dumps(suite))
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_regression
    finally:
        sys.path.pop(0)
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 0
    # prefix_hit_rate is higher-is-better: losing the hits regresses
    worse = json.loads(json.dumps(suite))
    worse["serve_decode"]["prefix_hit_rate"] = \
        paged["prefix_hit_rate"] * 0.2
    path_cur.write_text(json.dumps(worse))
    assert check_regression.main([str(path_cur), "--suite",
                                  str(path_base),
                                  "--kernels", "serve_decode"]) == 1


# --------------------------------------------- gpt2 position offsets

def test_gpt2_learned_position_offset_parity(params):
    """GPT2(position_offset=k) reads wpe[k:k+s] — proven by rolling the
    embedding table: a model whose wpe is pre-shifted by k at offset 0
    equals the original model at offset k."""
    from apex_tpu.models.gpt2 import GPT2

    model = GPT2(CFG)
    tokens = jnp.asarray(np.array([_tokens(6, seed=5)], np.int32))
    k = 9
    inner = dict(params["params"])
    wpe = np.asarray(params["params"]["wpe"])
    inner["wpe"] = jnp.asarray(np.roll(wpe, -k, axis=0))
    shifted = {"params": inner}
    a = model.apply(params, tokens, position_offset=k)
    b = model.apply(shifted, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # (traced positions are exercised by the serve engine itself: prefill
    # and decode index wpe by each row's absolute position)
