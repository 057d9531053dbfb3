"""Compiled-step cost observatory (PR 17) — phase-attributed ledgers.

Layers under test:

1. **The walk itself** — a toy jitted function with ``jax.named_scope``
   markers attributes FLOPs/bytes to the right phases, multiplies scan
   bodies by their trip counts, and reconciles phase sums against the
   executable total EXACTLY (the reconciliation IS the test — PR-13
   trace_explain precedent).
2. **The engine surface** — ``Engine.cost_ledger()`` rides the saved
   AOT artifacts (never re-tracing: ``decode_traces`` stays 1), is
   byte-deterministic across extractions, reconciles for the slot AND
   paged engines, and — at tp=2 exact — its counted collectives equal
   the PR-15 ``expected_collectives`` contract.
3. **The gate + diff tools** — the new ledger metric families are
   direction-aware in check_regression, a doctored +10%-bytes ledger
   FAILS the gate (exit 1), incomparable workload axes are refused
   (exit 2), and ``tools/cost_diff.py`` runs in a jax-poisoned
   subprocess (exit 0 clean / exit 2 on doctored provenance).
4. **The CLI matrix** — the new ``--cost-ledger``/``--chip-spec`` flags
   are loud usage errors when inert or contradictory (PR-10 precedent).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor import costs
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# n_head=4 so the same params serve tp=2 (the test_serve_tp geometry)
CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=4, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("block_k", 8)
    return Engine(CFG, params, EngineConfig(**kw), seed=0)


def _assert_reconciles(rec):
    """Phase sums == executable totals, exactly (no tolerance: both
    sides are integers accumulated by the same deterministic walk, and
    the ledger's contract is EXACT attribution)."""
    for field in ("ops", "flops", "hbm_bytes", "transcendentals"):
        assert sum(p[field] for p in rec["phases"].values()) \
            == rec["total"][field], field


# --------------------------------------------------------- 1. the walk

def test_walk_attributes_phases_and_reconciles():
    def f(x, w):
        with jax.named_scope("ln_qkv"):
            y = x @ w
        with jax.named_scope("mlp"):
            y = jnp.tanh(y)

        def body(c, t):
            with jax.named_scope("attention"):
                return c + t * 2.0, t

        c, _ = jax.lax.scan(body, jnp.zeros_like(y), jnp.stack([y] * 5))
        return c

    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32)
    rec = costs.walk_module(
        costs.stablehlo_debug_text(jax.jit(f).lower(x, w)))
    _assert_reconciles(rec)
    # the matmul lands in ln_qkv: 2*4*8*8 = 512 flops (+ any epilogue)
    assert rec["phases"]["ln_qkv"]["flops"] >= 512
    # tanh is transcendental and lands in mlp
    assert rec["phases"]["mlp"]["transcendentals"] > 0
    # the scan body is outlined into a private func and must be priced
    # once per trip: 5 trips × (4*8 mul + 4*8 add) = 320, in attention
    assert rec["phases"]["attention"]["flops"] >= 320
    assert rec["total"]["arithmetic_intensity"] > 0


def test_walk_multiplies_while_bodies_by_trip_count():
    def body(c, t):
        return c * 1.5 + t, t

    def f(xs):
        c, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return c

    one = costs.walk_module(costs.stablehlo_debug_text(
        jax.jit(f).lower(jnp.ones((1,), jnp.float32))))
    ten = costs.walk_module(costs.stablehlo_debug_text(
        jax.jit(f).lower(jnp.ones((10,), jnp.float32))))
    # same program, 10× the trips: the scanned-body flops scale with
    # the trip count (not the module's static op count)
    assert ten["total"]["flops"] >= 10 * one["total"]["flops"] > 0
    assert "notes" not in ten     # trip count statically resolved


def test_walk_prices_a_data_trip_count_at_the_bound_it_is_clipped_to():
    """The decode attention's convention: a loop whose trip count is
    data is priced at its worst case where the program clips the count
    to a constant, and once, with a note, where it does not."""
    def f(x, n, clip):
        trips = jnp.minimum(n, 7) if clip else n
        return jax.lax.fori_loop(0, trips, lambda i, c: c * 1.5, x)

    x, n = jnp.ones((16,), jnp.float32), jnp.int32(3)
    clipped, free = (costs.walk_module(costs.stablehlo_debug_text(
        jax.jit(f, static_argnums=2).lower(x, n, clip)))
        for clip in (True, False))
    assert clipped["total"]["flops"] == 7 * free["total"]["flops"] == 7 * 16
    assert "notes" not in clipped
    assert any("counted once" in note for note in free["notes"])


def test_walk_ignores_phase_named_source_paths(tmp_path):
    """MLIR loc bodies quote source FILE paths alongside named_scope
    paths — code traced from a directory that happens to be named after
    a phase (here ``verify/``) must not have its ops claimed by that
    phase."""
    import importlib.util

    mod_dir = tmp_path / "verify"
    mod_dir.mkdir()
    src = mod_dir / "user_drive.py"
    src.write_text("import jax.numpy as jnp\n\n\n"
                   "def f(x):\n"
                   "    return jnp.tanh(x) @ x\n")
    spec = importlib.util.spec_from_file_location("_phase_path_mod", src)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rec = costs.walk_module(costs.stablehlo_debug_text(
        jax.jit(mod.f).lower(jnp.ones((4, 4), jnp.float32))))
    _assert_reconciles(rec)
    assert rec["phases"]["verify"]["ops"] == 0
    assert rec["phases"]["other"]["ops"] > 0


def test_expected_collective_ops_contract_and_unknown_mode():
    # the PR-15 contract, spelled once (serve/tp.py delegates here)
    assert costs.expected_collective_ops(12, "exact") \
        == {"all_gather": 24, "all_reduce": 0}
    assert costs.expected_collective_ops(12, "overlap") \
        == {"all_gather": 0, "all_reduce": 48}
    assert costs.expected_collective_ops(12, "relaxed") \
        == {"all_gather": 0, "all_reduce": 24}
    with pytest.raises(ValueError, match="unknown tp_sync"):
        costs.expected_collective_ops(2, "banana")
    with pytest.raises(ValueError, match="unknown chip spec"):
        costs.build_ledger({}, {}, chip="v99x")


# ------------------------------------------------ 2. the engine surface

def test_cost_ledger_deterministic_and_reconciles(params):
    eng = _engine(params)
    led1 = eng.cost_ledger(prompt_buckets=[8])
    assert eng.decode_traces == 1      # rode the saved artifacts
    led2 = eng.cost_ledger(prompt_buckets=[8])
    assert eng.decode_traces == 1
    # byte-identical: no wall clocks, no env reads in the ledger body
    assert json.dumps(led1, sort_keys=True) \
        == json.dumps(led2, sort_keys=True)
    assert led1["schema"] == costs.LEDGER_SCHEMA
    assert set(led1["executables"]) == {"decode", "prefill_8"}
    for rec in led1["executables"].values():
        _assert_reconciles(rec)
        # every annotated phase is populated in the decode/prefill step
        for ph in ("ln_qkv", "attention", "mlp", "sampling"):
            assert rec["phases"][ph]["ops"] > 0, ph
    d = led1["derived"]
    assert d["decode_ops_total"] == \
        led1["executables"]["decode"]["total"]["ops"]
    assert d["decode_flops_per_token"] > 0
    assert d["decode_hbm_bytes_per_token"] > 0
    # cpu chip spec: roofline present but marked non-gating
    assert led1["chip_spec"] == "cpu" and led1["gating"] is False
    gm = costs.ledger_gate_metrics(led1)
    assert "predicted_mfu" not in gm
    assert gm["decode_flops_per_token"] == d["decode_flops_per_token"]
    # ...while a real chip spec gates the roofline families too
    v5p = eng.cost_ledger(chip="v5p")
    gm5 = costs.ledger_gate_metrics(v5p)
    assert 0 < gm5["predicted_mfu"] <= 1
    assert gm5["predicted_step_time_us"] > 0


def test_cost_ledger_paged_reconciles(params):
    eng = _engine(params, page_size=8, prefix_cache=True)
    led = eng.cost_ledger(prompt_buckets=[8])
    for rec in led["executables"].values():
        _assert_reconciles(rec)
    assert led["workload"]["page_size"] == 8
    # paged vs slot is an incomparable axis: the gate must refuse
    slot = _engine(params).cost_ledger()
    assert any("page_size" in r
               for r in costs.provenance_mismatch(led, slot))


def test_cost_ledger_quantized_reconciles(params):
    """PR-20 ride-along: a kv_quant engine's ledger reconciles exactly
    (the in-step encode/dequant arithmetic and the int8 KV traffic are
    walked like any other op), stamps ``kv_quant``/``quant_block``
    provenance so quantized ledgers refuse to gate against fp32 ones,
    and its decode step moves FEWER HBM bytes per token than the fp32
    engine's — the capacity claim, visible in the static byte model."""
    eng = _engine(params, kv_quant="int8")
    led = eng.cost_ledger()
    for rec in led["executables"].values():
        _assert_reconciles(rec)
    assert led["workload"]["kv_quant"] == "int8"
    assert led["workload"]["quant_block"] == 8     # = head_dim
    plain = _engine(params).cost_ledger()
    assert any("kv_quant" in r
               for r in costs.provenance_mismatch(led, plain))
    assert "kv_quant" not in plain["workload"] \
        or plain["workload"]["kv_quant"] is None
    assert led["derived"]["decode_hbm_bytes_per_token"] \
        < plain["derived"]["decode_hbm_bytes_per_token"]


def test_cost_ledger_tp2_exact_matches_pr15_contract(params, tp_devices):
    eng = _engine(params, num_slots=2, tp=2)
    led = eng.cost_ledger()
    dec = led["executables"]["decode"]
    _assert_reconciles(dec)
    # the ledger's counted collectives == the PR-15 contract == the
    # engine's own count_collectives (three independent spellings)
    expect = costs.expected_collective_ops(CFG.n_layer, "exact")
    nonzero = {k: v for k, v in expect.items() if v}
    counted = {k: v for k, v in dec["collectives"].items() if v}
    assert counted == nonzero == {
        k: v for k, v in eng.decode_collectives().items() if v}
    assert led["contract"]["expected"] == expect
    # collective phase carries exactly those ops
    assert dec["phases"]["collective"]["ops"] == sum(expect.values())
    # tp pricing table covers every sync mode, exact's op count agrees
    pricing = led["collective_pricing"]
    assert set(pricing) == set(costs.SYNC_MODES)
    assert pricing["exact"]["ops"] == expect
    assert all(p["bytes_on_wire_per_step"] > 0 for p in pricing.values())


def test_cost_ledger_survives_reset_without_relowering(params):
    """Satellite 6: ``cost_ledger()`` after ``reset()`` (warm restart)
    rides the RETAINED prefill lowerings — no re-trace, no re-lower."""
    eng = _engine(params, num_slots=2).aot_compile(prompt_buckets=[8])
    before = eng.cost_ledger()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    eng.reset()
    after = eng.cost_ledger()
    assert eng.decode_traces == 1 and eng.prefill_traces == 1
    assert json.dumps(before, sort_keys=True) \
        == json.dumps(after, sort_keys=True)
    assert "prefill_8" in after["executables"]


def test_cost_ledger_spec_verify_entry(params):
    """PR-18 ride-along: a spec-armed engine's ledger carries the
    verify executable from the SAME retained lowerings (no re-trace,
    works after reset), its verify phase is populated via the model's
    final_scope threading, and the spec workload axes make spec-off
    ledgers refuse rather than compare."""
    eng = Engine(CFG, params,
                 EngineConfig(num_slots=3, max_len=32, temperature=0.0,
                              block_k=8, spec_draft_len=2), seed=0)
    led = eng.cost_ledger(prompt_buckets=[8])
    assert eng.decode_traces == 1 and eng.verify_traces == 1
    assert set(led["executables"]) == {"decode", "prefill_8", "verify"}
    ver = led["executables"]["verify"]
    _assert_reconciles(ver)
    # the verify phase holds the final LN + logits work of all K+1
    # scanned positions (final_scope="verify"); the inner phases and the
    # acceptance sampler keep their own attribution
    for ph in ("ln_qkv", "attention", "mlp", "sampling", "verify"):
        assert ver["phases"][ph]["ops"] > 0, ph
    # decode/prefill entries keep "verify" EMPTY: their final scope is
    # still "sampling", so the new phase never leaks attribution
    assert led["executables"]["decode"]["phases"]["verify"]["ops"] == 0
    assert led["workload"]["spec_draft_len"] == 2
    # byte-deterministic across reset, still no re-trace (warm restart)
    eng.reset()
    led2 = eng.cost_ledger(prompt_buckets=[8])
    assert eng.decode_traces == 1 and eng.verify_traces == 1
    assert json.dumps(led, sort_keys=True) \
        == json.dumps(led2, sort_keys=True)
    # spec on/off is an incomparable ledger axis (missing key = off)
    plain = _engine(params).cost_ledger()
    assert any("spec_draft_len" in r
               for r in costs.provenance_mismatch(led, plain))
    assert "spec_draft_len" not in plain["workload"] \
        or plain["workload"]["spec_draft_len"] == 0


# --------------------------------------------- 3. the gate + diff tools

def _check_regression():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_regression
    finally:
        sys.path.pop(0)
    return check_regression


def test_gate_directions_for_ledger_families():
    cr = _check_regression()
    for name in ("cost_ledger.decode_flops_per_token",
                 "cost_ledger.decode_hbm_bytes_per_token",
                 "cost_ledger.decode_ops_total",
                 "cost_ledger.decode.attention_flops_per_token",
                 "cost_ledger.predicted_step_time_us"):
        assert cr.lower_is_better(name), name
    assert not cr.lower_is_better("cost_ledger.predicted_mfu")


def test_gate_passes_identical_and_fails_doctored_bytes(params, tmp_path):
    """ISSUE acceptance: a doctored +10% hbm-bytes ledger FAILS the
    gate; identical ledgers pass; a different workload axis is refused
    (exit 2), never silently compared."""
    cr = _check_regression()
    led = _engine(params).cost_ledger()
    cur, base = str(tmp_path / "cur.json"), str(tmp_path / "base.json")
    json.dump(led, open(cur, "w"))
    json.dump(led, open(base, "w"))
    assert cr.main([cur, "--suite", base]) == 0

    worse = json.loads(json.dumps(led))
    worse["derived"]["decode_hbm_bytes_per_token"] = \
        led["derived"]["decode_hbm_bytes_per_token"] * 1.10
    json.dump(worse, open(cur, "w"))
    assert cr.main([cur, "--suite", base]) == 1

    json.dump(led, open(cur, "w"))
    other = json.loads(json.dumps(led))
    other["workload"]["tp"] = 2
    json.dump(other, open(base, "w"))
    assert cr.main([cur, "--suite", base]) == 2


def test_cost_diff_runs_in_jax_free_subprocess(params, tmp_path):
    """tools/cost_diff.py with a poisoned jax shim on PYTHONPATH: exit 0
    on comparable ledgers (rendering the per-phase deltas), exit 2 on
    doctored provenance — jax never imports (the shim raises)."""
    led = _engine(params).cost_ledger()
    cur = str(tmp_path / "cur.json")
    base = str(tmp_path / "base.json")
    moved = json.loads(json.dumps(led))
    moved["derived"]["decode_flops_per_token"] *= 1.5
    moved["executables"]["decode"]["phases"]["mlp"]["flops"] += 1000
    json.dump(led, open(cur, "w"))
    json.dump(moved, open(base, "w"))

    shim = tmp_path / "nojax"
    shim.mkdir()
    (shim / "jax.py").write_text(
        'raise ImportError("jax must not be imported by cost_diff")')
    env = dict(os.environ, PYTHONPATH=str(shim))

    def diff(*extra):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "cost_diff.py"),
             cur, base, *extra],
            capture_output=True, text=True, env=env)

    proc = diff()
    assert proc.returncode == 0, proc.stderr
    assert "decode_flops_per_token" in proc.stdout
    assert "mlp" in proc.stdout
    doc = json.loads(diff("--json").stdout)
    assert doc["derived"]["decode_flops_per_token"]["ratio"] \
        == pytest.approx(1 / 1.5, rel=1e-4)

    doctored = json.loads(json.dumps(led))
    doctored["workload"]["dtype"] = "bf16"
    json.dump(doctored, open(base, "w"))
    proc = diff()
    assert proc.returncode == 2
    assert "INCOMPARABLE" in proc.stderr and "dtype" in proc.stderr


# ------------------------------------------------------ 4. CLI matrix

def test_bench_cli_cost_ledger_flag_matrix(monkeypatch, tmp_path):
    from apex_tpu.bench_cli import _serve_bench
    from apex_tpu.bench_cli import main as bench_main

    with pytest.raises(SystemExit, match="needs --cost-ledger"):
        _serve_bench(2, 2, chip_spec="v5p")          # inert --chip-spec
    with pytest.raises(SystemExit, match="unknown --chip-spec"):
        _serve_bench(2, 2, cost_ledger=str(tmp_path / "l.json"),
                     chip_spec="v99x")
    with pytest.raises(SystemExit, match="pick two paths"):
        _serve_bench(2, 2, cost_ledger=str(tmp_path / "same.json"),
                     metrics_snapshot=str(tmp_path / "same.json"))
    # --cost-ledger without --serve: the pre-parse matrix exits 2
    monkeypatch.setattr(sys, "argv",
                        ["apex-tpu-bench", "--cost-ledger", "x.json"])
    with pytest.raises(SystemExit) as ei:
        bench_main()
    assert ei.value.code == 2


@pytest.mark.slow
def test_bench_cli_emits_provenance_stamped_ledger(tmp_path, capsys):
    """The full surface in-process: ``--serve --cost-ledger`` writes the
    schema'd, provenance-stamped ledger next to the suite capture, and
    the file round-trips through the gate against itself."""
    from apex_tpu.bench_cli import _serve_bench

    path = str(tmp_path / "ledger.json")
    _serve_bench(4, 2, cost_ledger=path, chip_spec="v5p")
    capsys.readouterr()
    doc = json.load(open(path))
    assert doc["schema"] == costs.LEDGER_SCHEMA
    assert doc["chip_spec"] == "v5p" and doc["gating"] is True
    for k in ("device_kind", "git", "captured"):
        assert k in doc["meta"], k
    for rec in doc["executables"].values():
        _assert_reconciles(rec)
    cr = _check_regression()
    assert cr.main([path, "--suite", path]) == 0
