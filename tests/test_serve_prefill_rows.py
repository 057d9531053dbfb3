"""A prefill call computes the rows it admits, at a small size on the CPU.

An engine of 8 slots at bucket 64 has two prefill programs
(``engine.prefill_rows``: 4 rows beside 8): the small one runs the model's
unchanged forward over a row view of the cache (``kv_cache.slot_view``).
For all three models: which program a call takes, that a neighbour's bytes
stay untouched, that the two programs agree, what comes back, a prefix hit
through the small program, the spans, and that an engine of 4 slots is
exactly what it was (one program, no ``slots`` argument).
"""

import contextlib
import functools
import os
import re
import sys
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from apex_tpu.models.gpt2 import GPT2Config  # noqa: E402
from apex_tpu.serve import engine as engine_mod  # noqa: E402
from apex_tpu.serve import kv_cache  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   init_gpt2_params, prefill_rows)

MODELS = ("gpt2", "deepseek_v3", "ouro")
SLOTS, ROWS, BUCKET, VOCAB = 8, 4, 64, 512
GEOMETRY = dict(num_slots=SLOTS, max_len=128, temperature=0.0, page_size=16,
                num_pages=SLOTS * 8 + 1, prefix_cache=True)

# The two programs are the same forward over other rows. Rows of a batched
# product are independent, but XLA may tile a [4 * 64]-row product and an
# [8 * 64]-row one differently, so their float32 sums may run in another
# order: the programs are held to each other by a tolerance, not to the
# bit. 1e-5 on logits and cache rows of unit size is a hundred times the
# rounding (3e-8 to 2e-7 read here) and a thousandth of any mistake (a
# row of another slot, a position off by one).
TOLERANCE = 1e-5


@functools.lru_cache(maxsize=None)
def _model(name: str):
    """``(model config, params)`` of a model at a size for the CPU."""
    if name == "gpt2":
        cfg = GPT2Config(vocab_size=VOCAB, n_positions=128, n_embd=32,
                         n_layer=2, n_head=2, compute_dtype=jnp.float32)
        return cfg, init_gpt2_params(cfg)
    if name == "ouro":
        from apex_tpu.models.ouro import OuroConfig
        from reference import ouro as reference

        from test_ouro import tiny
    else:
        from reference import deepseek_v3 as reference

        from test_deepseek_v3 import model_of, tiny
    cfg = tiny()
    assert cfg["vocab_size"] == VOCAB
    model_cfg = OuroConfig.from_dict(cfg) if name == "ouro" else model_of(cfg)
    return model_cfg, reference.make_params(cfg, 7)


def _build(name: str, **knobs) -> Engine:
    model_cfg, params = _model(name)
    return Engine(model_cfg, params, EngineConfig(**{**GEOMETRY, **knobs}))


@functools.lru_cache(maxsize=None)
def _compiled(name: str) -> Engine:
    """One engine a model with both programs compiled ahead; a test
    resets it (``reset`` keeps every compiled artifact)."""
    return _build(name).aot_compile([BUCKET])


@contextlib.contextmanager
def _only_the_full_program():
    """An engine built and called in here has the one program over every
    slot, as every engine had before there were two."""
    with mock.patch.object(engine_mod, "prefill_rows",
                           lambda num_slots, bucket: num_slots):
        yield


def _prompts(seed: int, *slots: int) -> dict:
    """A prompt of 33..64 random ids (bucket 64) for each slot."""
    rng = np.random.default_rng(seed)
    return {s: rng.integers(0, VOCAB, int(rng.integers(33, 65))).tolist()
            for s in slots}


def _pools(eng: Engine) -> dict:
    """The cache's token arrays, off the device (a copy: the next call
    deletes the arrays themselves)."""
    return {name: np.array(getattr(eng.cache, name))
            for name in kv_cache._token_arrays(eng.cache)}


def _spans(monkeypatch) -> list:
    """``[(name, attributes)]`` of every span the engine opens from here
    on, read through ``utils/prof.annotate`` as the engine calls it."""
    seen = []
    real = engine_mod.annotate

    def recording(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(engine_mod, "annotate", recording)
    return seen


def test_the_rule_gives_the_cells_their_row_counts():
    # the benchmark's three engines at bucket 64: gpt2-xl keeps ONE
    # program, ouro-2.6b and gigachat3.1-702b-ep16 get a small one
    assert [prefill_rows(n, 64) for n in (4, 16, 64)] == [4, 4, 8]
    # a bucket of fewer positions needs more rows to leave the
    # weight-bound range; never more rows than slots
    assert prefill_rows(64, 16) == 16 and prefill_rows(8, 16) == 8
    assert prefill_rows(1024, 64) == 128


# ------------------------------------------------- (a) which program runs

@pytest.mark.parametrize("model", MODELS)
def test_the_admitted_count_picks_the_program_and_nothing_compiles(model):
    eng = _compiled(model).reset()
    assert set(eng._prefill_aot) == {BUCKET, (BUCKET, ROWS)}
    traces = eng.prefill_traces
    assert traces == 2
    ran = []
    programs = dict(eng._prefill_aot)
    for key, program in programs.items():
        eng._prefill_aot[key] = (
            lambda *a, _key=key, _p=program: ran.append(_key) or _p(*a))
    try:
        for admitted in (1, 2, 3, ROWS, ROWS + 1):
            eng.reset()
            eng.prefill(_prompts(admitted, *range(admitted)))
    finally:
        eng._prefill_aot.update(programs)
    assert ran == [(BUCKET, ROWS)] * ROWS + [BUCKET]
    assert eng.prefill_traces == traces
    assert [fn._cache_size() for fn in eng._prefill_jits.values()] == [0, 0]


# ------------------------------------- (b) a neighbour's bytes stay as is

@pytest.mark.parametrize("model", MODELS)
def test_the_small_program_leaves_every_neighbours_bytes_untouched(model):
    eng = _compiled(model).reset()
    eng.prefill(_prompts(1, *range(SLOTS)))          # the full program
    active = np.ones((SLOTS,), bool)
    for _ in range(3):
        eng.decode_step(eng.last_tokens, active)
    eng.evict([2, 5])
    before = _pools(eng)
    lengths = np.array(eng.cache.lengths)
    eng.prefill(_prompts(2, 2, 5))                   # the small one
    after = _pools(eng)
    own = sorted({p for s in (2, 5) for p in eng._slot_pages[s]})
    others = [p for p in range(eng._num_pages) if p not in own]
    assert 0 in others                               # the null page too
    for name in before:
        np.testing.assert_array_equal(after[name][:, others],
                                      before[name][:, others], err_msg=name)
        assert not np.array_equal(after[name][:, own], before[name][:, own])
    kept = [s for s in range(SLOTS) if s not in (2, 5)]
    now = np.asarray(eng.cache.lengths)
    np.testing.assert_array_equal(now[kept], lengths[kept])
    assert (now[[2, 5]] == eng._host_lengths[[2, 5]]).all() \
        and (now[[2, 5]] >= 33).all()
    # the page table goes out as it came in: the host's own rows
    np.testing.assert_array_equal(np.asarray(eng.cache.page_table),
                                  eng._page_table)


# ----------------------------------------- (c) the two programs agree, (d)

@pytest.mark.parametrize("model", MODELS)
def test_small_and_full_program_agree_and_a_decode_step_continues_alike(
        model):
    admission = _prompts(3, 1, 6)
    small = _compiled(model).reset()
    first, last_logits, all_logits = small.prefill(admission)
    with _only_the_full_program():
        full = _build(model)
        want_first, want_logits, _ = full.prefill(admission)
    assert set(full._prefill_jits) == {BUCKET}
    # (d) what comes back is indexed by slot id whichever program ran
    assert first.shape == (SLOTS,) and all_logits is None
    assert last_logits.shape == (SLOTS, VOCAB) == want_logits.shape
    got = np.asarray(last_logits)
    for slot in admission:
        assert first[slot] == got[slot].argmax() == want_first[slot]
    idle = [s for s in range(SLOTS) if s not in admission]
    assert not got[idle].any() and not first[idle].any()
    np.testing.assert_allclose(got[[1, 6]], np.asarray(want_logits)[[1, 6]],
                               atol=TOLERANCE, rtol=0)
    # the same rows written: the same allocator gave both the same pages
    assert small._slot_pages == full._slot_pages
    a, b = _pools(small), _pools(full)
    for name in a:
        np.testing.assert_allclose(a[name], b[name], atol=TOLERANCE, rtol=0,
                                   err_msg=name)
    np.testing.assert_array_equal(np.asarray(small.cache.lengths),
                                  np.asarray(full.cache.lengths))
    active = np.zeros((SLOTS,), bool)
    active[[1, 6]] = True
    for _ in range(2):
        nxt, logits = small.decode_step(small.last_tokens, active)
        want_nxt, want = full.decode_step(full.last_tokens, active)
        np.testing.assert_array_equal(nxt[[1, 6]], want_nxt[[1, 6]])
        np.testing.assert_allclose(np.asarray(logits)[[1, 6]],
                                   np.asarray(want)[[1, 6]],
                                   atol=TOLERANCE, rtol=0)


def test_kept_prefill_logits_keep_their_shape_through_the_small_program():
    # the builder's choice (CHANGES.md, PR 36): `keep_prefill_logits`
    # (tests only) is served by the small program too, scattered into
    # `[P, num_slots, V]` as the last logits are into `[num_slots, V]`
    eng = _build("gpt2", keep_prefill_logits=True)
    admission = _prompts(4, 3, 4)
    _, last_logits, all_logits = eng.prefill(admission)
    assert set(eng._prefill_jits) == {(BUCKET, ROWS)}
    assert all_logits.shape == (BUCKET, SLOTS, VOCAB)
    kept = np.asarray(all_logits)
    for slot, prompt in admission.items():
        np.testing.assert_array_equal(kept[len(prompt) - 1, slot],
                                      np.asarray(last_logits)[slot])
    assert not kept[:, [0, 1, 2, 5, 6, 7]].any()


# -------------------------------- (e) a prefix hit through the small program

@pytest.mark.parametrize("model", MODELS)
def test_a_prefix_hit_and_a_copied_page_through_the_small_program(model):
    eng = _compiled(model).reset()
    rng = np.random.default_rng(5)
    head = rng.integers(0, VOCAB, 48).tolist()       # three whole pages
    eng.prefill({0: head})
    # slot 3 shares two pages and brings a tail of 40 (bucket 64); slot 4
    # asks for `head` again: all but its last token is served, the third
    # page copied-on-write. One call, the small program, two starts > 0
    admission = {3: head[:32] + rng.integers(0, VOCAB, 40).tolist(),
                 4: list(head)}
    hits = eng.prefix_hits
    first, last_logits, _ = eng.prefill(admission)
    stats = eng.last_prefill_stats
    assert eng.prefix_hits == hits + 2
    assert (stats[3]["hit_tokens"], stats[3]["scanned"]) == (32, 40)
    assert (stats[4]["hit_tokens"], stats[4]["scanned"]) == (47, 1)
    assert eng._slot_pages[4][:2] == eng._slot_pages[0][:2]
    assert eng._slot_pages[4][2] != eng._slot_pages[0][2]     # the copy
    np.testing.assert_array_equal(np.asarray(eng.cache.lengths)[[3, 4]],
                                  [72, 48])
    # against the same prompts prefilled cold by the full program
    with _only_the_full_program():
        cold = _build(model, prefix_cache=False)
        want_first, want_logits, _ = cold.prefill(admission)
    np.testing.assert_array_equal(first[[3, 4]], want_first[[3, 4]])
    np.testing.assert_allclose(np.asarray(last_logits)[[3, 4]],
                               np.asarray(want_logits)[[3, 4]],
                               atol=TOLERANCE, rtol=0)
    active = np.zeros((SLOTS,), bool)
    active[[3, 4]] = True
    nxt, logits = eng.decode_step(eng.last_tokens, active)
    want_nxt, want = cold.decode_step(cold.last_tokens, active)
    np.testing.assert_array_equal(nxt[[3, 4]], want_nxt[[3, 4]])
    np.testing.assert_allclose(np.asarray(logits)[[3, 4]],
                               np.asarray(want)[[3, 4]],
                               atol=TOLERANCE, rtol=0)


# ------------------------------------------------------------- the spans

@pytest.mark.parametrize("model", MODELS)
def test_the_launch_span_says_the_rows_computed_and_the_call_the_admitted(
        model, monkeypatch):
    eng = _compiled(model).reset()
    seen = _spans(monkeypatch)
    two = _prompts(6, 2, 7)
    eng.prefill(two)
    eng.reset()
    five = _prompts(7, 0, 1, 2, 3, 4)
    eng.prefill(five)
    calls = [a for n, a in seen if n == "apex.prefill"]
    launches = [a for n, a in seen if n == "apex.prefill.launch"]
    assert calls == [{"admitted": 2, "slots": SLOTS,
                      "weight_bytes": eng.weight_bytes},
                     {"admitted": 5, "slots": SLOTS,
                      "weight_bytes": eng.weight_bytes}]
    for launch, rows, prompts in zip(launches, (ROWS, SLOTS), (two, five)):
        assert launch["slots"] == rows and launch["bucket"] == BUCKET
        assert launch["real_positions"] == sum(map(len, prompts.values()))
        # what each compiled program says of the pool rides its own span
        assert launch["pool_copies"] == 0
    names = [n for n, _ in seen]
    assert names.count("apex.prefill.launch") == 2 == names.count(
        "apex.prefill.fetch")
    # a model's counters count the admitted rows, whichever program ran
    spans = [a for n, a in seen if n.startswith("apex.prefill.")
             and n.rsplit(".", 1)[1] in ("loop", "routing")]
    if model == "ouro":
        assert [s["rows"] for s in spans] == [
            sum(map(len, p.values())) for p in (two, five)]
    assert len(spans) == (0 if model == "gpt2" else 2)


# ------------------------- (f) an engine of 4 slots is exactly what it was

def _row_gathers(text: str, rows: int, pages: int):
    """The ``stablehlo.gather``s whose result is ``rows`` whole rows of a
    page table ``pages`` wide: what ``kv_cache.slot_view`` adds."""
    return [line for line in text.splitlines() if "stablehlo.gather" in line
            and line.rstrip().endswith(f"-> tensor<{rows}x{pages}xi32>")]


@pytest.mark.parametrize("model", MODELS)
def test_four_slots_at_bucket_64_keep_their_one_program(model):
    eng = _build(model, num_slots=4, num_pages=33).aot_compile([BUCKET])
    assert eng.prefill_traces == 1
    assert set(eng._prefill_lowered) == {BUCKET} == set(eng._prefill_aot)
    text = eng._prefill_lowered[BUCKET].as_text()
    main = text[text.index("func.func public @main("):]
    args = re.findall(r"%arg\d+: (tensor<[^>]*>)",
                      main[:main.index(") -> ")])
    # weights, cache, then tokens, admit, start, tail lengths, the key:
    # no `slots [rows]` between the cache and the tokens
    assert args[-5:] == ["tensor<4x64xi32>", "tensor<4xi1>",
                         "tensor<4xi32>", "tensor<4xi32>", "tensor<2xui32>"]
    assert args[-6] == "tensor<4x8xi32>"             # the page table
    assert not _row_gathers(text, 4, 8)
    # where there IS a small program it is the one that gathers, once
    # (the lengths' gather beside it), and takes `slots`
    both = _compiled(model)
    small = both._prefill_lowered[(BUCKET, ROWS)].as_text()
    assert len(_row_gathers(small, ROWS, 8)) == 1
    assert not _row_gathers(both._prefill_lowered[BUCKET].as_text(), SLOTS, 8)
    assert "jit_prefill_fn" in small and "jit_prefill_fn" in text
    eng.prefill(_prompts(8, 0, 1))
    assert eng.prefill_traces == 1
