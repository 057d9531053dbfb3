"""Tensor-parallel serving tier-1: mesh-sharded decode, bit-exact vs the
single-chip engine.

The acceptance claims under test (docs/serving.md "Tensor-parallel
decode"):

- **bit-exactness** — a ``tp=2`` engine in the default ``exact`` sync
  mode produces greedy AND sampled token streams (and raw logits)
  bit-identical in fp32 to the single-chip engine at equal ``block_k``,
  on both cache layouts (slot and paged, prefix-hit churn included).
  The mechanism: per-rank compute is the single-chip forward on column
  slices (per-column matmul determinism), and the cross-rank combine is
  pure concatenation (``all_gather``) — no float add ever crosses a
  rank boundary.
- **one compile per mesh shape** — admit/evict/abort/prefix-hit churn
  on the sharded engine traces decode exactly once
  (``Engine.decode_traces``), same as the single-chip invariant.
- **the collective contract** — ``expected_collectives`` (2 gathers per
  layer exact, 4 half-psums overlap/TokenWeave, 2 half-psums relaxed)
  equals the count in the ACTUAL lowered StableHLO
  (``Engine.decode_collectives``), and relaxed < overlap < the naive
  2-per-layer × unsplit baseline in all-reduce pressure.
- **the merge seam** — per-rank metrics snapshots fold through
  ``merge_snapshots`` into the fleet view (ranks/heads/KV bytes sum to
  the engine totals), and ``check_regression`` REFUSES to gate a tp=2
  capture against a single-chip baseline.

Engines are compiled once per geometry and shared via ``Engine.reset()``
(the test_serve precedent); the trace-counter tests build fresh engines.
All of it runs on the conftest-forced multi-device CPU host (the
``tp_devices`` fixture) — sharded tier-1 never depends on real chips.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.scheduler import Request, ServeScheduler
from apex_tpu.serve.tp import (count_collectives, expected_collectives,
                               serving_mesh)
# bound at collection time (a test that purges apex_tpu.* from
# sys.modules mid-session; a function-local re-import would subscribe
# to a FRESH bus the old engine module never publishes to)
from apex_tpu.utils.logging import subscribe_events

pytestmark = pytest.mark.serve

CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=4, compute_dtype=jnp.float32)


def _tokens(n, seed=7, vocab=97):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("block_k", 8)     # equal chunk geometry: the
    #                                 bit-exactness precondition
    seed = kw.pop("seed", 0)
    return Engine(CFG, params, EngineConfig(**kw), seed=seed)


@pytest.fixture(scope="module")
def base8(params, tp_devices):
    """Single-chip engine, one page a slot, at block_k=8."""
    return _engine(params)


@pytest.fixture(scope="module")
def tp2(params, tp_devices):
    """tp=2 engine, one page a slot, exact sync (THE sharded default)."""
    return _engine(params, tp=2)


@pytest.fixture(scope="module")
def paged1(params, tp_devices):
    """Single-chip paged oracle (page_size 8, prefix index on)."""
    return _engine(params, page_size=8, prefix_cache=True)


@pytest.fixture(scope="module")
def tp2_paged(params, tp_devices):
    """tp=2 paged engine: head-sharded pool, replicated page table."""
    return _engine(params, page_size=8, prefix_cache=True, tp=2)


def _mixed_requests(n=5, seed0=0, max_new=5):
    return [Request(request_id=f"r{i}",
                    tokens=_tokens(4 + 3 * (i % 4), seed=seed0 + i),
                    max_new_tokens=max_new) for i in range(n)]


def _trace_outputs(eng, reqs, injector=None):
    sched = ServeScheduler(eng, fault_injector=injector)
    for r in reqs:
        sched.submit(r)
    return {r["request_id"]: r for r in sched.run().requests}


# ------------------------------------------------------------- the mesh


def test_serving_mesh_shape(tp_devices):
    mesh = serving_mesh(2)
    assert mesh.shape == {"tp": 2}
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(10 ** 6)


def test_tp_engine_validation_matrix(params, tp_devices):
    """Every bad mesh geometry is a clear build-time ValueError, never a
    bad lowering (the CLI exit-2 matrix rides these messages)."""
    for kw, msg in (
            (dict(tp=3), "divide n_head"),
            (dict(tp=0), ">= 1"),
            (dict(tp=2, tp_sync="bogus"), "tp_sync"),
            (dict(tp_sync="relaxed"), "tp >= 2"),
            (dict(tp=10 ** 6), None),      # ValueError either way: the
            #   head check fires before the device-pool check for a tp
            #   this large; both are build-time refusals
    ):
        with pytest.raises(ValueError, match=msg):
            _engine(params, **kw)


# ------------------------------------------- bit-exactness (THE oracle)


def test_tp_bit_exact_vs_single_chip_greedy(base8, tp2):
    """THE sharded acceptance: an identical mixed-length request trace
    through the single-chip engine (the oracle) and the tp=2 mesh
    produces bit-identical greedy streams at equal block_k."""
    assert tp2.tp == 2 and tp2.block_k == base8.block_k == 8
    base = _trace_outputs(base8.reset(), _mixed_requests())
    got = _trace_outputs(tp2.reset(), _mixed_requests())
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}
    assert {k: v["finish_reason"] for k, v in got.items()} == \
           {k: v["finish_reason"] for k, v in base.items()}


def test_tp_decode_logits_match_single_prefill(params, tp2, tp_devices):
    """Strongest oracle form: the tp=2 engine's incremental decode
    LOGITS match the single-chip engine's full-sequence prefill logits —
    crossing the mesh boundary (bit-exact on its own: the tp engine's
    batched prefill against the single chip's, same call) AND the
    prefill/decode border (float32 rounding: the batched prefill sums in
    another order than the one-row decode step)."""
    seq = _tokens(12)
    keeper = _engine(params, keep_prefill_logits=True)
    _, _, all_logits = keeper.prefill({1: seq})
    all_logits = np.asarray(all_logits)              # [P, B, V]
    inc = tp2.reset()
    _, tp_last, _ = inc.prefill({1: seq[:5]})
    _, one_last, _ = keeper.reset().prefill({1: seq[:5]})
    np.testing.assert_array_equal(np.asarray(tp_last)[1],
                                  np.asarray(one_last)[1])
    for j in range(5, len(seq)):
        forced = np.array([0, seq[j], 0], np.int32)
        _, logits = inc.decode_step(forced,
                                    np.array([False, True, False]))
        a, b = all_logits[j, 1], np.asarray(logits)[1]
        assert a.dtype == np.float32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                   err_msg=f"tp decode pos {j}")


def test_tp_paged_bit_exact_vs_single_chip(paged1, tp2_paged):
    """The paged pool under the mesh: head-sharded page bytes behind a
    REPLICATED page table, prefix-hit + COW churn included — greedy
    streams bit-identical to the single-chip engine of the same pages (itself
    held bit-exact to one page a slot by test_serve)."""
    sysp = _tokens(16, seed=42)                  # two full shared pages
    reqs = lambda: [Request(request_id=f"p{i}",          # noqa: E731
                            tokens=sysp + _tokens(3 + i, seed=100 + i),
                            max_new_tokens=4) for i in range(4)]
    base = _trace_outputs(paged1.reset(), reqs())
    got = _trace_outputs(tp2_paged.reset(), reqs())
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}
    # the churn was real: later admissions hit the shared prefix pages
    assert tp2_paged.prefix_hits >= 1


@pytest.mark.slow
def test_tp_bit_exact_sampled(params, tp_devices):
    """Seeded sampling crosses the mesh bit-for-bit: logits are
    bit-identical (exact mode) and the PRNG key path is identical (the
    key is engine state split once per call, sampling runs on the full
    replicated logits outside shard_map) — so sampled streams match
    token-for-token.

    Slow tier: greedy tp-vs-single-chip parity stays in tier-1; this
    adds the PRNG-path leg on top of bit-identical logits."""
    kw = dict(temperature=0.8, top_k=5)
    base = _trace_outputs(_engine(params, **kw),
                          _mixed_requests(max_new=6))
    got = _trace_outputs(_engine(params, tp=2, **kw),
                         _mixed_requests(max_new=6))
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}


# ------------------------------------- one compile per mesh shape


@pytest.mark.fault
def test_tp_decode_compiles_once_across_churn(params, tp_devices):
    """The one-compile invariant survives the mesh: admissions,
    completions, a scripted mid-stream abort, prefix-hit admissions,
    and backfill churn on a tp=2 PAGED engine trace decode exactly once
    — one compile per mesh shape, proven by counters, with the
    serve_tp_mesh_ready provenance event published at build."""
    events = []
    unsub = subscribe_events(events.append)
    try:
        eng = _engine(params, num_slots=2, page_size=8,
                      prefix_cache=True, tp=2)
        inj = FaultInjector(seed=0).abort_request("c2", at_step=4)
        sched = ServeScheduler(eng, fault_injector=inj)
        sysp = _tokens(8, seed=9)
        for i, plen in enumerate((4, 6, 5, 3)):
            sched.submit(Request(request_id=f"c{i}",
                                 tokens=sysp + _tokens(plen, seed=i),
                                 max_new_tokens=4 + i % 3))
        stats = sched.run()
    finally:
        unsub()
    assert len(stats.requests) == 4
    assert eng.decode_traces == 1, \
        "mesh-sharded decode must compile once per mesh shape"
    mesh_ev = [e for e in events if e["event"] == "serve_tp_mesh_ready"]
    assert len(mesh_ev) == 1 and mesh_ev[0]["tp"] == 2


# ------------------------------------------- the collective contract


def test_tp_collective_counts_exact_overlap_relaxed(params, tp_devices):
    """The overlap-seam unit: per-mode collective counts in the ACTUAL
    lowered decode step equal the documented contract — exact = 2
    all-gathers/layer (combine by concatenation), overlap = the two
    per-layer all-reduces each split in two slot halves (TokenWeave),
    relaxed = ONE deferred all-reduce per layer — and exact mode's
    logits are bit-identical to the replicated reference."""
    ref = _engine(params, num_slots=2)
    prompt = {0: _tokens(6, seed=1), 1: _tokens(4, seed=2)}
    _, ref_logits, _ = ref.prefill(dict(prompt))

    got = {}
    for sync in ("exact", "overlap", "relaxed"):
        eng = _engine(params, num_slots=2, tp=2, tp_sync=sync)
        # serve FIRST through the plain jit path (no aot_compile), so
        # the collective count below exercises the risky ordering: its
        # internal .lower() must hit the jit's trace cache, never trace
        # decode a second time (the one-compile invariant would read 2)
        _, logits, _ = eng.prefill(dict(prompt))
        eng.decode_step(eng.last_tokens, np.array([True, True]))
        assert eng.decode_traces == 1
        counts = eng.decode_collectives()
        assert eng.decode_traces == 1, \
            "decode_collectives() re-traced a compiled engine"
        want = expected_collectives(CFG.n_layer, sync)
        assert counts["all_gather"] == want["all_gather"], (sync, counts)
        assert counts["all_reduce"] == want["all_reduce"], (sync, counts)
        assert counts["all_to_all"] == counts["permute"] == 0
        assert counts == {**counts, **eng.tp_collectives_per_step()}
        got[sync] = np.asarray(logits)

    # exact IS the replicated reference, bit for bit
    assert np.array_equal(got["exact"], np.asarray(ref_logits))
    # overlap reorders partial sums only: ulp-level, never bit-claimed
    assert np.allclose(got["overlap"], got["exact"], atol=1e-4)
    assert np.isfinite(got["relaxed"]).all()
    # the pressure ordering the two papers buy: TokenWeave splits hide
    # latency at equal volume; relaxed halves the all-reduce count
    assert expected_collectives(CFG.n_layer, "relaxed")["all_reduce"] \
        < expected_collectives(CFG.n_layer, "overlap")["all_reduce"]


def test_count_collectives_text_unit():
    txt = ('stablehlo.all_reduce x stablehlo.all_reduce y '
           'stablehlo.all_gather z collective_permute w')
    assert count_collectives(txt) == {
        "all_gather": 1, "all_reduce": 2, "all_to_all": 0, "permute": 1}
    with pytest.raises(ValueError, match="tp_sync"):
        expected_collectives(2, "bogus")


# ------------------------------------------------- the PR-10 merge seam


def test_tp_rank_snapshots_fold_through_merge(tp2):
    """Per-rank metrics fold through merge_snapshots into the fleet
    view — the PR-10 aggregation seam used for its designed purpose:
    each rank reports its OWN shard and the fold reconstructs the
    engine totals exactly."""
    from apex_tpu.monitor.export import merge_snapshots

    eng = tp2.reset()
    eng.prefill({0: _tokens(5)})
    for _ in range(3):
        eng.decode_step(eng.last_tokens, np.array([True, False, False]))
    docs = eng.tp_rank_snapshots(meta={"device_kind": "cpu"})
    assert len(docs) == 2
    merged = merge_snapshots(docs)
    vals = {name: fam["series"][0]["value"]
            for name, fam in merged["metrics"].items()}
    assert vals["serve_tp_ranks"] == 2
    assert vals["serve_tp_rank_heads"] == CFG.n_head
    assert vals["serve_tp_rank_kv_bytes"] == eng.kv_cache_bytes
    per_step = sum(eng.tp_collectives_per_step().values())
    assert vals["serve_tp_rank_collectives_total"] == \
        eng.decode_calls * per_step * 2
    # mesh-shape provenance survives the fold (the comparability axis
    # check_regression refuses on); per-file rank identity does not
    assert merged["meta"]["tp"] == 2
    assert "tp_rank" not in merged["meta"]


def test_tp_single_chip_has_no_rank_files(base8):
    assert base8.tp_rank_snapshots() == []
    assert base8.tp_collectives_per_step() == {"all_gather": 0,
                                               "all_reduce": 0}


# --------------------------------------------------- tune registry axis


@pytest.mark.tune
def test_decode_attention_tp_shards_axis_registered():
    """The decode_attention shape key carries the tp_shards axis (a
    winner tuned unsharded must never apply to a mesh shard) and
    CODE_VERSIONS bumped so stale v2 entries invalidate cleanly."""
    from apex_tpu.tune import CODE_VERSIONS
    from apex_tpu.tune import registry

    assert CODE_VERSIONS["decode_attention"] >= 3
    spec = registry.spec("decode_attention")
    k1 = spec.shape_key({"max_len": 32, "page_size": 32, "heads": 2,
                         "d": 8})
    k2 = spec.shape_key({"max_len": 32, "page_size": 32, "heads": 2,
                         "d": 8, "tp_shards": 2})
    assert k1 != k2
    assert ("tp_shards", 1) in k1 and ("tp_shards", 2) in k2


def test_tp_engines_resolve_distinct_block_k_keys(base8, tp2):
    """Both engines resolved a block_k under their own key (per-shard
    heads + tp_shards axis); pinning block_k=8 made them EQUAL — the
    bit-exactness precondition the oracle tests above ride."""
    assert base8.block_k == tp2.block_k == 8


# --------------------------------------------------------- CLI + bench


@pytest.mark.slow
def test_serve_cli_tp_smoke_and_rank_snapshots(tmp_path, capsys):
    """In-process ``apex-tpu-serve --tp 2``: bit-identical greedy output
    to the --tp 1 run, decode compiles once, the final line carries the
    mesh provenance, and --metrics-snapshot writes PATH.tpK per rank
    plus the merged PATH.tp fleet view.

    Slow tier: the two full serve runs cost ~10s; the tp engine
    bit-exactness and flag matrix stay in tier-1 via the in-process
    tests above and ``test_serve_cli_tp_exit2_matrix``."""
    from apex_tpu.serve.cli import main

    snap = str(tmp_path / "tp.json")
    argv = ["--config", "tiny", "--dtype", "fp32", "--requests", "3",
            "--max-new-tokens", "4", "--temperature", "0",
            "--max-len", "32", "--seed", "0"]
    assert main(argv) == 0
    single = [json.loads(l) for l in
              capsys.readouterr().out.strip().splitlines()]
    assert main(argv + ["--tp", "2", "--metrics-snapshot", snap]) == 0
    sharded = [json.loads(l) for l in
               capsys.readouterr().out.strip().splitlines()]
    # per-request records bit-identical (drop the timing fields)
    strip = lambda recs: [{k: v for k, v in r.items()          # noqa: E731
                           if k in ("request_id", "generated",
                                    "finish_reason")}
                          for r in recs[:-1]]
    assert strip(sharded) == strip(single)
    final = sharded[-1]
    assert final["decode_compiles"] == 1
    assert final["tp"] == {"tp": 2, "sync": "exact",
                           "collectives_per_decode_step":
                               {"all_gather": 2 * 2, "all_reduce": 0}}
    for suffix in (".tp0", ".tp1", ".tp"):
        assert os.path.exists(snap + suffix), suffix
    merged = json.load(open(snap + ".tp"))
    ranks = merged["metrics"]["serve_tp_ranks"]["series"][0]["value"]
    assert ranks == 2 and merged["meta"]["tp"] == 2


def test_serve_cli_tp_exit2_matrix(capsys):
    """Contradictory/inert tp flag combinations are loud exit-2 usage
    errors BEFORE any params/compile work."""
    from apex_tpu.serve.cli import main

    # --tp 2 --replicas 2 is no longer here: PR 16 made it the
    # fleet-of-meshes configuration (see test_serve_disagg)
    for argv in (["--tp", "3"],                       # 3 ∤ n_head=4
                 ["--tp", "0"],
                 ["--tp-sync", "relaxed"],            # sync without mesh
                 ["--tp-sync", "overlap"]):
        assert main(argv) == 2, argv
    capsys.readouterr()


def test_bench_tp_capture_and_gate_refusal(tmp_path, capsys):
    """A --tp-stamped serve_decode capture: workload provenance records
    the mesh shape, the capture gates cleanly against itself, and
    check_regression REFUSES to gate it against a single-chip baseline
    (exit 2, INCOMPARABLE) — in either direction."""
    from apex_tpu.bench_cli import _serve_bench
    from tools.check_regression import incomparable_entries, main as gate

    cap = str(tmp_path / "tp2.json")
    _serve_bench(6, 2, cap, max_len=32, tp=2, tp_sync="exact")
    capsys.readouterr()
    doc = json.load(open(cap))
    wl = doc["serve_decode"]["workload"]
    assert wl["tp"] == 2 and wl["tp_sync"] == "exact"

    # self-gate: comparable, passes
    assert gate([cap, "--suite", cap, "--kernels", "serve_decode"]) == 0
    out = capsys.readouterr().out
    assert "INCOMPARABLE" not in out

    # synthetic single-chip baseline: same numbers, tp=1 — the refusal
    base = json.loads(json.dumps(doc))
    base["serve_decode"]["workload"]["tp"] = 1
    base["serve_decode"]["workload"]["tp_sync"] = None
    basep = str(tmp_path / "tp1.json")
    json.dump(base, open(basep, "w"))
    assert incomparable_entries(doc, base) == {
        "serve_decode": "workload.tp=2 vs baseline workload.tp=1"}
    # a LEGACY baseline without the key at all is single-chip too
    del base["serve_decode"]["workload"]["tp"]
    assert "serve_decode" in incomparable_entries(doc, base)
    rc = gate([cap, "--suite", basep, "--kernels", "serve_decode"])
    out = capsys.readouterr().out
    assert rc == 2 and "INCOMPARABLE" in out    # nothing left to gate
