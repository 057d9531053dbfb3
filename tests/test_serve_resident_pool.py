"""The resident pool, read off the compiled programs and off the engine.

Two halves:

- **The compiled text**, for a described v5e (no chip): decode,
  ``prefill_64`` and verify of a 2-layer engine at GPT-2 XL's widths and
  the first cell's geometry, and decode and ``prefill_64`` of a 2-layer
  ``deepseek_v3`` engine at the second cell's, and decode and
  ``prefill_64`` of a 2-layer ``ouro`` engine at the third cell's (8
  planes, the pass loop a loop, the plane data), and the small
  ``[rows, 64]`` prefill program of those two (8 rows of 64 slots, 4 of
  16), hold no ``copy`` of a
  pool's shape and alias every pool array, with every argument in the
  layout the runtime gives it. This is the guard that keeps the
  whole-pool copies from coming back with a later kernel. The GPT-2
  programs also hold one ``while`` a layer under ``attention`` and no
  slice of one layer's whole pool out of the stacked array.
- **The donation contract**, on the CPU: a full admit / complete / evict /
  backfill / prefix-hit trace through an engine whose calls donate the
  cache gives the token streams of a twin that donates nothing; no call
  touches a deleted array; a call that raises after donation leaves the
  engine re-initialised and says so (docs/serving.md, "Who owns the
  pool"). That programs which come out of the persistent compile cache
  serve as the compiled ones do is in ``tests/test_monitor.py``, beside
  the one place that may name the cache's directory.
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from apex_tpu.models.deepseek_v3 import DeepseekV3Config  # noqa: E402
from apex_tpu.models.gpt2 import GPT2, GPT2Config  # noqa: E402
from apex_tpu.serve import engine as engine_mod  # noqa: E402
from apex_tpu.serve import kv_cache  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   PoolLost, init_gpt2_params)
from apex_tpu.serve.scheduler import Request, ServeScheduler  # noqa: E402


# ------------------------------------------- the compiled text, for a v5e

@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip. Described inside the fixture, never at
    import: only one process may hold libtpu, and every xdist worker
    imports this file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    quiet = {"TPU_LOG_DIR": "disabled", "TPU_SKIP_MDS_QUERY": "1",
             "TPU_ACCELERATOR_TYPE": "v5litepod-4",
             "TPU_WORKER_HOSTNAMES": "localhost"}
    with pytest.MonkeyPatch.context() as patch:
        for name, value in quiet.items():
            if name not in os.environ:
                patch.setenv(name, value)
        try:
            topo = topologies.get_topology_desc("v5e:2x2", "tpu")
        except Exception as e:                 # no libtpu, or its lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _cell_config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _cell_engine(model: str, program: str):
    """``(engine, abstract weights)``: the cell's widths and cache
    geometry at 2 layers; the engine holds no weight (they are an
    argument of every program), so nothing of the model's size is made."""
    if model == "gpt2-xl":
        cfg = _cell_config("gpt2-xl")
        geo = cfg["serve"]
        model_cfg = GPT2Config(
            vocab_size=cfg["vocab_size"], n_positions=cfg["n_positions"],
            n_embd=cfg["n_embd"], n_layer=2, n_head=cfg["n_head"],
            compute_dtype=getattr(jnp, cfg["compute_dtype"]))
        weights = jax.eval_shape(GPT2(model_cfg).init, jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))
    elif model == "ouro":
        from apex_tpu.models.ouro import OuroConfig
        from reference import ouro as reference

        cfg = dict(_cell_config("ouro-2.6b"), num_hidden_layers=2)
        geo = cfg["serve"]
        model_cfg = OuroConfig.from_dict(cfg)
        weights = jax.eval_shape(lambda: reference.make_params(cfg, 0))
    else:
        from reference import deepseek_v3 as reference

        cfg = dict(_cell_config("gigachat3.1-702b-ep16"),
                   num_hidden_layers=2)         # 1 dense + 1 expert layer
        geo = cfg["serve"]
        model_cfg = DeepseekV3Config.from_dict(
            cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
            experts_held=cfg["n_routed_experts"],
            expert_offset=cfg["deployment"]["expert_offset"],
            vocab_held=cfg["vocab_size"])
        weights = jax.eval_shape(lambda: reference.make_params(cfg, 0))
    eng = Engine(model_cfg, {}, EngineConfig(
        num_slots=geo["num_slots"], max_len=geo["max_len"], temperature=0.0,
        page_size=geo["page_size"], num_pages=geo["num_pages"],
        prefix_cache=geo["prefix_cache"],
        spec_draft_len=4 if program == "verify" else 0))
    return eng, weights


def _compile_for(eng, weights, program: str, chip):
    """The engine's own ``program``, compiled for ``chip`` with every
    argument in the layout the runtime gives it there."""
    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    jitted, args = {
        "decode": (lambda: eng._decode, eng._decode_args),
        "prefill_64": (lambda: eng._make_prefill(64),
                       lambda: eng._prefill_args(64)),
        # the bucket's small program, over the rows a tick admits
        "prefill_64_rows": (
            lambda: eng._make_prefill(64, _small_rows(eng)),
            lambda: eng._prefill_args(64, _small_rows(eng))),
        "verify": (lambda: eng._verify, eng._verify_args)}[program]
    _, _, *data = args()
    return jitted().lower(on_chip(weights), on_chip(eng.cache),
                          *on_chip(data)).compile()


def _small_rows(eng) -> int:
    rows = engine_mod.prefill_rows(eng.config.num_slots, 64)
    assert rows < eng.config.num_slots      # the cell HAS a small program
    return rows


COMPILED = [("gpt2-xl", "decode"), ("gpt2-xl", "prefill_64"),
            ("gpt2-xl", "verify"), ("deepseek_v3", "decode"),
            ("deepseek_v3", "prefill_64"), ("ouro", "decode"),
            ("ouro", "prefill_64"), ("deepseek_v3", "prefill_64_rows"),
            ("ouro", "prefill_64_rows")]


@pytest.mark.parametrize("model,program", COMPILED)
def test_compiled_programs_copy_no_pool_and_alias_every_pool_array(
        model, program, one_chip):
    eng, weights = _cell_engine(model, program)
    compiled = _compile_for(eng, weights, program, one_chip)
    facts = kv_cache.pool_facts(compiled, eng.cache)
    assert facts["pool_copies"] == 0, facts
    # the v5e's tiles pad a pool array, so the aliased bytes are at least
    # the logical ones
    assert facts["pool_aliased_bytes"] >= eng.kv_cache_bytes, facts
    if program == "prefill_64_rows":
        # the row view gathers two small int32 arrays and hands the
        # forward the SAME pools: the second cell's latent pool (1 089
        # pages) and the third's planes (65) ride it in place, with no
        # asynchronous copy of a pool's shape either, and the program
        # computes `rows` slots, not all of them
        text = compiled.as_text()
        rows = _small_rows(eng)
        assert (model, rows, eng.cache.num_pages) in (
            ("deepseek_v3", 8, 1089), ("ouro", 4, 65))
        for name in kv_cache._token_arrays(eng.cache):
            pool = ",".join(map(str, getattr(eng.cache, name).shape))
            assert not re.search(
                r"\[" + pool + r"\][^ ]* copy-(start|done)\(", text), name
        chunks = set(re.findall(r"s32\[(\d+),64\][^ ]* parameter\(", text))
        assert chunks == {str(rows)}, chunks    # the tokens: `rows` chunks
    if model == "ouro":
        # the pool rides the pass loop and the layer scan in place: no
        # asynchronous copy of a pool's shape either (`pool_copies` does
        # not count those), the loops are loops (passes, layers, and the
        # cached keys' chunks inside), and a layer's weights are read
        # where they lie in the stacked array
        text = compiled.as_text()
        pool = ",".join(map(str, eng.cache.k.shape))
        assert eng.cache.k.shape[:2] == (8, 65)
        assert not re.search(r"\[" + pool + r"\][^ ]* copy-(start|done)\(",
                             text)
        # (a fourth in the small program: its rows' logits go into
        # `[num_slots, vocab]` by a scatter of `rows` trips)
        assert len(re.findall(r" while\(", text)) \
            == 3 + (program == "prefill_64_rows")
        assert "bf16[2,2048,5632]" in text      # stacked, never unstacked
        assert not re.search(r"bf16\[2048,5632\][^ ]* copy\(", text)
    if model != "gpt2-xl":
        return
    # attention over cached keys is one loop a layer (decode and the
    # verify scan's body: the key chunks a slot can reach; prefill: a
    # cached prompt head), and its body fetches from the STACKED pool:
    # no program slices one layer's whole pool out first
    text = compiled.as_text()
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert len([n for n in loops if "/attention/" in n]) \
        == eng.model.n_layer, loops
    layer = ",".join(map(str, eng.cache.k.shape[1:]))
    assert f"[{layer}]" not in text, f"a whole layer's pool [{layer}]"


def test_with_a_head_axis_the_pool_is_relaid_and_the_counter_sees_it(
        one_chip):
    """The control: the pool as it was shaped before it was rows, ``[L,
    pages, page_size, heads, head_dim]``, donated to a program that
    writes four token rows into it. Every byte is aliased and the whole
    pool is still relaid on the way in and on the way out (the runtime's
    default layout puts the heads outside a page's rows), which
    ``pool_copies`` has to be able to count."""
    cfg = _cell_config("gpt2-xl")
    geo = cfg["serve"]
    heads = cfg["n_head"]
    pool = jax.ShapeDtypeStruct(
        (2, geo["num_pages"], geo["page_size"], heads,
         cfg["n_embd"] // heads), jnp.bfloat16, sharding=one_chip)
    small = jax.ShapeDtypeStruct((geo["num_slots"],), jnp.int32,
                                 sharding=one_chip)
    cache = kv_cache.PagedKVCache(k=pool, v=pool, lengths=small,
                                  page_table=small)

    def write(cache, pages, offs):
        new = jnp.ones((pages.shape[0],) + cache.k.shape[3:], cache.k.dtype)
        return cache.replace(k=cache.k.at[0, pages, offs].set(new),
                             v=cache.v.at[1, pages, offs].set(new))

    compiled = jax.jit(write, donate_argnums=0).lower(
        cache, small, small).compile()
    facts = kv_cache.pool_facts(compiled, cache)
    assert facts["pool_aliased_bytes"] >= 2 * 2 * np.prod(pool.shape), facts
    assert facts["pool_copies"] >= 2, facts


# --------------------------------------- the donation contract, on the CPU

GPT2_TINY = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                       n_head=2, compute_dtype=jnp.float32)
SCALING = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
               mscale_all_dim=1, original_max_position_embeddings=64,
               rope_type="yarn")
LATENT_TINY = dict(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
    n_shared_experts=1, n_routed_experts=4, routed_scaling_factor=2.5,
    kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=12,
    qk_nope_head_dim=8, n_group=8, topk_group=4, num_experts_per_tok=8,
    first_k_dense_replace=1, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=100000, max_position_embeddings=64, rope_scaling=SCALING,
    published=dict(n_routed_experts=32), deployment=dict(expert_offset=4),
    compute_dtype="float32")

KINDS = {
    "slot": dict(page_size=None),
    "paged": dict(page_size=8, num_pages=25, prefix_cache=True),
    "latent": dict(page_size=8, num_pages=25, prefix_cache=True),
}


def _tiny_engine(kind: str, **knobs) -> Engine:
    config = EngineConfig(num_slots=3, max_len=64, temperature=0.0,
                          **{**KINDS[kind], **knobs})
    if kind != "latent":
        return Engine(GPT2_TINY, init_gpt2_params(GPT2_TINY), config)
    from reference import deepseek_v3 as reference

    model_cfg = DeepseekV3Config.from_dict(
        LATENT_TINY,
        n_routed_experts=LATENT_TINY["published"]["n_routed_experts"],
        experts_held=LATENT_TINY["n_routed_experts"],
        expert_offset=LATENT_TINY["deployment"]["expert_offset"],
        vocab_held=LATENT_TINY["vocab_size"])
    return Engine(model_cfg, reference.make_params(LATENT_TINY, 0), config)


def _undonated(eng: Engine, monkeypatch) -> Engine:
    """The non-donating twin: the same programs through plain
    ``jax.jit``, which keeps every argument alive. The patch stays for
    the rest of the test: a prefill program is made at its first call."""
    monkeypatch.setattr(engine_mod, "_donating_jit",
                        lambda fn, cache_arg: jax.jit(fn))
    return Engine(eng.model_cfg, eng.params, eng.config)


def _prompt(n: int, seed: int) -> list:
    return np.random.RandomState(seed).randint(1, 128, size=n).tolist()


SHARED = _prompt(16, 99)        # two full pages


def _requests():
    """Six requests on three slots: the first three are admitted
    together, the rest backfill slots as answers of different lengths
    complete and are evicted on different ticks. Three prompts share a
    16-token head: the first indexes its two pages, the head alone comes
    back as a hit whose boundary page is copied-on-write (its last token
    is run again), and a longer one shares both pages read-only."""
    prompts = [SHARED + _prompt(3, 1), _prompt(11, 3), _prompt(5, 4),
               SHARED, SHARED + _prompt(6, 2), _prompt(7, 5)]
    return [Request(request_id=i, tokens=p, max_new_tokens=4 + 2 * (i % 3))
            for i, p in enumerate(prompts)]


def _serve(eng: Engine):
    sched = ServeScheduler(eng)
    for r in _requests():
        sched.submit(r)
    stats = sched.run()
    return {r["request_id"]: r["generated"] for r in stats.requests}


CALLS = ["decode", "prefill", "verify", "evict", "copy_on_write", "import"]


def _drive(eng: Engine, call: str):
    """Exercise ``call`` on a fresh engine and return something that
    depends on the cache it left: tokens, or the pages read back."""
    if call in ("decode", "prefill", "evict", "copy_on_write"):
        return _serve(eng)
    if call == "verify":
        first, _, _ = eng.prefill({0: _prompt(9, 5), 2: _prompt(4, 6)})
        out = []
        active = np.array([True, False, True])
        for _ in range(4):
            drafts = np.tile(eng.last_tokens[:, None], (1, 3))
            committed, counts = eng.spec_decode_step(
                eng.last_tokens, drafts, np.array([3, 0, 2]), active)
            out.append((committed.tolist(), counts.tolist()))
        return first.tolist(), out
    # import: pages exported by a donor land in this engine's pool and a
    # later admission shares them
    donor = _tiny_engine("paged")
    donor.prefill({0: SHARED})
    payloads = donor.export_prefix_pages(SHARED)
    stats = eng.import_prefix_pages(payloads)
    return stats, _serve(eng)


def _supports(kind: str, call: str) -> bool:
    if call == "verify":
        return kind != "latent"             # refused for a routed model
    if call == "copy_on_write":
        return kind != "slot"               # no pages to share
    if call == "import":
        return kind == "paged"              # latent pages do not migrate
    return True


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_donating_calls_serve_what_a_twin_that_donates_nothing_serves(
        kind, call, monkeypatch):
    if not _supports(kind, call):
        with pytest.raises(ValueError):
            if call == "verify":
                _tiny_engine(kind, spec_draft_len=3)
            elif call == "copy_on_write":
                _tiny_engine(kind, prefix_cache=True)
            else:
                _tiny_engine(kind).import_prefix_pages([])
        return
    knobs = dict(spec_draft_len=3) if call == "verify" else {}
    eng = _tiny_engine(kind, **knobs)
    first = eng.cache
    got = _drive(eng, call)
    twin = _undonated(eng, monkeypatch)
    kept = twin.cache
    assert got == _drive(twin, call)
    # this backend honours donation: the cache the engine started from is
    # gone and the whole trace ran clean without it; the twin's is alive
    assert first.lengths.is_deleted(), (
        f"{jax.default_backend()} kept a donated cache")
    assert not kept.lengths.is_deleted()
    for leaf in jax.tree_util.tree_leaves(eng.cache):
        assert not leaf.is_deleted()
    assert eng.decode_traces == (0 if call == "verify" else 1)
    assert eng.verify_traces == (1 if call == "verify" else 0)
    if call == "copy_on_write":
        assert (eng.prefix_hits, eng.prefix_hit_tokens) == (2, 15 + 16)
    if call == "evict":
        assert int(np.asarray(eng.cache.lengths).max()) == 0
        np.testing.assert_array_equal(eng.lengths, twin.lengths)
    # the pool's bytes are those of the twin, call for call
    for name in kv_cache._token_arrays(eng.cache):
        np.testing.assert_array_equal(
            np.asarray(getattr(eng.cache, name)),
            np.asarray(getattr(twin.cache, name)))


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_call_that_raises_after_donation_leaves_a_fresh_pool(kind, call):
    """The failure rule: the program consumed the cache and then the
    call raised. The engine re-initialises the pool (every slot free, the
    allocator and the prefix index empty), raises ``PoolLost`` from the
    cause, and serves the next trace as a fresh engine does."""
    if not _supports(kind, call) or call == "evict":
        # evict passes only ``lengths`` through a program: there is
        # nothing donated to lose
        if call == "evict":
            eng = _tiny_engine(kind)
            eng.prefill({0: _prompt(6, 7)})
            pool = [getattr(eng.cache, n)
                    for n in kv_cache._token_arrays(eng.cache)]
            eng.evict([0])
            assert all(a is b for a, b in zip(pool, (
                getattr(eng.cache, n)
                for n in kv_cache._token_arrays(eng.cache))))
        return
    knobs = dict(spec_draft_len=3) if call == "verify" else {}
    eng = _tiny_engine(kind, **knobs)
    want = _drive(_tiny_engine(kind, **knobs), call)

    def failing(real):
        def fn(*args):
            real(*args)                       # consumes the donated cache
            for a in args:                    # and where the backend kept
                if isinstance(a, type(eng.cache)):     # it, so do we
                    for leaf in jax.tree_util.tree_leaves(a):
                        leaf.is_deleted() or leaf.delete()
            raise RuntimeError("the device fell over")
        return fn

    attr = {"decode": "_decode", "verify": "_verify",
            "copy_on_write": "_copy_page", "import": "_install_page"}
    if call == "prefill":
        eng._prefill_jits = {b: failing(eng._make_prefill(b))
                             for b in (4, 8, 16, 32, 64)}
    else:
        real = getattr(eng, attr[call])
        setattr(eng, attr[call], failing(real))
    calls_before = eng.decode_calls
    with pytest.raises(PoolLost, match="re-initialised") as err:
        _drive(eng, call)
    assert isinstance(err.value.__cause__, RuntimeError)
    # the state docs/serving.md names: live zero pool, nothing resident,
    # nothing allocated or indexed; programs and counters kept
    for leaf in jax.tree_util.tree_leaves(eng.cache):
        assert not leaf.is_deleted()
    assert eng.resident_tokens == 0 and int(eng.lengths.max()) == 0
    assert eng.pool.free_count == eng.pool.capacity
    assert not eng._page_table.any()
    if eng.prefix is not None:
        assert len(eng.prefix) == 0
    assert eng.decode_calls >= calls_before
    # mend the program and the engine serves as a fresh one does
    if call == "prefill":
        eng._prefill_jits = {}
    else:
        setattr(eng, attr[call], real)
    traces = (eng.decode_traces, eng.verify_traces)
    eng.reset()
    assert _drive(eng, call) == want
    assert (eng.decode_traces, eng.verify_traces) <= (
        max(traces[0], 1), max(traces[1], 1))


def test_a_call_that_raises_before_donation_leaves_the_pool_as_it_was():
    eng = _tiny_engine("paged")
    eng.prefill({0: _prompt(6, 7)})
    held = eng.cache
    with pytest.raises(ValueError, match="capacity"):
        eng._slot_capacity[0] = 6
        eng.decode_step(eng.last_tokens, [True, False, False])
    assert eng.cache is held and not held.k.is_deleted()

    def refusing(*args):
        raise RuntimeError("refused before anything ran")

    eng._slot_capacity[0] = 64
    eng._decode = refusing
    with pytest.raises(RuntimeError, match="refused") as err:
        eng.decode_step(eng.last_tokens, [True, False, False])
    assert not isinstance(err.value, PoolLost)
    assert eng.cache is held and eng.resident_tokens == 6


def test_the_spans_carry_what_the_compiled_programs_say_of_the_pool(
        monkeypatch):
    seen = {}

    class Recording:
        def __init__(self, name, **attrs):
            seen.setdefault(name, []).append(attrs)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    eng = _tiny_engine("paged").aot_compile([8])
    monkeypatch.setattr(engine_mod, "annotate", Recording)
    eng.prefill({0: _prompt(6, 7)})
    eng.decode_step(eng.last_tokens, [True, False, False])
    for span in ("apex.prefill.launch", "apex.decode_step"):
        attrs = seen[span][-1]
        assert attrs["pool_copies"] == 0, span
        assert attrs["pool_aliased_bytes"] >= eng.kv_cache_bytes, span
    assert seen["apex.decode_step"][-1]["pages_in_use"] >= 1
