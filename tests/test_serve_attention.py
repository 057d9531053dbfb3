"""The decode attention's chunk loop (``serve/attention.py``), float32 on
the CPU: the loop's trip count is data (the longest slot the step writes),
and what masking promised while every chunk was visited still holds now
that the unreachable ones are not:

(a) a slot's result depends on that slot's bytes only: a short slot beside
    a long one has the bits it has beside another short one;
(b) the result does not depend on the trip count once it covers the slot;
(c) a slot the mask leaves out does not lengthen the loop, whatever
    position it was left at;
(d) several pages a slot and one page a slot agree to the bit at equal
    ``block_k``;
(e) the online softmax is the plain one-shot softmax to float32 rounding.

Every case runs on a plain pool and on an ``int8`` one (scale planes
fetched in the loop's body).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.serve.attention import attended_chunks, paged_attention
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.kv_cache import init_paged_cache, write_rows

pytestmark = pytest.mark.serve

QUANT = pytest.mark.parametrize("kv_quant", [None, "int8"])
B, H, D, L, BK, LAYER = 3, 2, 8, 64, 8, 1


def _filled(kv_quant, page_size, seed=0):
    """A two-layer pool whose layer ``LAYER`` holds ``L`` random tokens a
    slot, written as the prefill writes them (encoded under ``kv_quant``)
    through a shuffled page table; and the rows as a read returns them
    (``[B, L, heads, D]`` float32, the head axis as the pool pads it)."""
    rng = np.random.RandomState(seed)
    per_slot = L // page_size
    pages = B * per_slot + 1
    k, v = rng.randn(2, B, L, H, D).astype(np.float32)
    pool = init_paged_cache(2, B, L, page_size, pages, H, D,
                            kv_quant=kv_quant)
    q = jnp.asarray(rng.randn(B, pool.k.shape[-2], D).astype(np.float32))
    table = rng.permutation(np.arange(1, pages)).reshape(B, per_slot)
    pool = pool.replace(page_table=jnp.asarray(table, jnp.int32))
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    cache, k_read, v_read = write_rows(
        pool, LAYER, jnp.asarray(k), jnp.asarray(v), pos,
        jnp.ones((B, L), bool), codec=kv_quant)
    return cache, q, np.asarray(k_read, np.float32), \
        np.asarray(v_read, np.float32)


def _attend(cache, q, positions, trips=None):
    out = jax.jit(lambda c, q, p, n: paged_attention(
        q, c, LAYER, p, n, block_k=BK))(
            cache, q, jnp.asarray(positions, jnp.int32),
            None if trips is None else jnp.int32(trips))
    return np.asarray(out)


@QUANT
def test_short_slot_beside_a_long_one_keeps_its_bits(kv_quant):
    """(a): slot 0 at position 5 runs one trip beside short neighbours
    and eight beside a neighbour at 63; the seven chunks past its
    position change no bit of its result, and the long neighbour's own
    result is what it is beside anyone."""
    cache, q, _, _ = _filled(kv_quant, 16)
    alone = _attend(cache, q, [5, 2, 7])
    beside = _attend(cache, q, [5, 63, 7])
    np.testing.assert_array_equal(beside[0], alone[0])
    np.testing.assert_array_equal(beside[2], alone[2])
    np.testing.assert_array_equal(
        beside[1], _attend(cache, q, [50, 63, 30])[1])
    assert not np.array_equal(beside[1], alone[1])


@QUANT
def test_result_is_independent_of_the_trip_count_once_covered(kv_quant):
    """(b): positions up to 19 need three chunks of 8; every trip count
    from three to the whole key axis gives the same bits, and the
    default (``trips=None``: every slot counted) is the three."""
    cache, q, _, _ = _filled(kv_quant, 16)
    pos = [19, 3, 11]
    want = _attend(cache, q, pos)
    assert int(attended_chunks(np.asarray(pos), True, BK, L // BK,
                               xp=np)) == 3
    for n in range(3, L // BK + 1):
        np.testing.assert_array_equal(_attend(cache, q, pos, n), want,
                                      err_msg=f"trips={n}")
    # and fewer trips than a slot needs is a different answer for that
    # slot alone
    short = _attend(cache, q, pos, 2)
    assert not np.array_equal(short[0], want[0])
    np.testing.assert_array_equal(short[1:], want[1:])


@QUANT
def test_inactive_slot_does_not_lengthen_the_loop(kv_quant):
    """(c): the count the engine leaves on ``apex.decode_step`` (host
    ints) and the one the program works out (the same function over the
    device's positions and mask) follow the ACTIVE slots: a slot that
    holds 30 tokens and sits the step out costs no trip."""
    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=2, compute_dtype=jnp.float32)
    eng = Engine(cfg, init_gpt2_params(cfg, seed=0),
                 EngineConfig(num_slots=3, max_len=64, temperature=0.0,
                              block_k=8, page_size=16, kv_quant=kv_quant),
                 seed=0)
    rng = np.random.RandomState(1)
    eng.prefill({0: rng.randint(0, 97, 30).tolist(),
                 2: rng.randint(0, 97, 4).tolist()})
    both = np.array([True, False, True])
    short = np.array([False, False, True])
    occ = eng._occupancy(both)
    assert (occ["attended_chunks"], occ["key_chunks"]) == (30 // 8 + 1, 8)
    assert eng._occupancy(short)["attended_chunks"] == 1
    assert eng._occupancy(np.zeros(3, bool))["attended_chunks"] == 1
    for act in (both, short):
        got = attended_chunks(eng.cache.lengths, jnp.asarray(act), 8, 8)
        assert int(got) == eng._occupancy(act)["attended_chunks"]
    # the short slot's tokens are the same whether the long slot decodes
    # beside it or sits the steps out with its 30 tokens resident
    streams = []
    for act in (both, short):
        eng.reset()
        eng.prefill({0: rng.randint(0, 97, 30).tolist(),
                     2: [5, 6, 7, 8]})
        toks = []
        for _ in range(6):
            nxt, _ = eng.decode_step(eng.last_tokens, act)
            toks.append(int(nxt[2]))
        streams.append(toks)
    assert streams[0] == streams[1]
    assert eng.decode_traces == 1


@QUANT
def test_page_sizes_agree_to_the_bit_at_equal_block_k(kv_quant):
    """(d): the same tokens in one 64-row page a slot, in four pages of
    16 and in eight of 8 (one chunk a page): where the pages lie does
    not enter the arithmetic."""
    pos = [5, 63, 17]
    want = None
    for ps in (64, 16, 8):
        cache, q, _, _ = _filled(kv_quant, ps)
        got = _attend(cache, q, pos)
        if want is None:
            want = got
        np.testing.assert_array_equal(got, want, err_msg=f"ps={ps}")


@QUANT
def test_online_softmax_matches_the_one_shot_softmax(kv_quant):
    """(e): against one max-subtracted softmax over the reachable keys,
    in float32, over the rows as a read of the cache returns them (so an
    ``int8`` pool is held to its own decoded values). The tolerance is
    float32 rounding and nothing else: the loop rescales its running
    sums by ``exp(m - m_new)`` once a chunk where the one-shot form
    subtracts one global max, so up to eight partial sums are each one
    rounding (6e-8 relative) off, on outputs of order 1."""
    cache, q, k_read, v_read = _filled(kv_quant, 16)
    pos = [5, 63, 17]
    got = _attend(cache, q, pos)
    for b, p in enumerate(pos):
        sc = np.einsum("hd,khd->hk", np.asarray(q[b]),
                       k_read[b, :p + 1]) / np.float32(np.sqrt(D))
        w = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hk,khd->hd", w / w.sum(-1, keepdims=True),
                         v_read[b, :p + 1])
        np.testing.assert_allclose(got[b], want, rtol=2e-6, atol=2e-6,
                                   err_msg=f"slot {b}")
