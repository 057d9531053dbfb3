"""Autotuner tests (ISSUE 3): cache round-trip + corruption fallback,
deterministic keys, heuristic fallback, interpret-mode isolation,
empty-cache bit-for-bit tile parity, the apex-tpu-tune CPU smoke, and the
BENCH_BASELINE.json regression gate.

All CPU-only and fast — tier-1; select alone with ``-m tune``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import tune
from apex_tpu.tune.api import pow2_bucket, tuned_params

pytestmark = pytest.mark.tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the process-wide tune cache at a fresh tmp file."""
    path = str(tmp_path / "tune_cache.json")
    monkeypatch.setenv("APEX_TPU_TUNE_CACHE", path)
    tune.invalidate()
    yield path
    tune.invalidate()


# ------------------------------------------------------------------ cache


class TestCache:
    def test_round_trip(self, tmp_cache):
        c = tune.TuneCache(tmp_cache)
        key = tune.cache_key("layer_norm", (("rows", 8192), ("hidden", 4096)),
                             jnp.bfloat16, "v5e")
        c.put(key, {"block_rows": 64}, meta={"ms": 0.1})
        c.save()
        reloaded = tune.TuneCache(tmp_cache)
        assert reloaded.get(key) == {"params": {"block_rows": 64},
                                     "meta": {"ms": 0.1}}
        assert len(reloaded) == 1

    def test_deterministic_keys_across_processes(self, tmp_cache):
        args = ("flash_attention", (("sq", 2048), ("sk", 2048), ("d", 64),
                                    ("causal", True)), "bfloat16", "v5e")
        key = tune.cache_key(*args)
        # key ordering is canonical regardless of pair order
        shuffled = tuple(reversed(args[1]))
        assert tune.cache_key(args[0], shuffled, args[2], args[3]) == key
        # and identical in a fresh interpreter (no per-process state).
        # cache.py is loaded standalone — its module level is stdlib-only
        # by design, so the subprocess skips the jax import entirely
        cache_py = os.path.join(REPO, "apex_tpu", "tune", "cache.py")
        out = subprocess.run(
            [sys.executable, "-c",
             "import importlib.util; "
             f"spec = importlib.util.spec_from_file_location('tc', {cache_py!r}); "
             "m = importlib.util.module_from_spec(spec); "
             "spec.loader.exec_module(m); "
             "print(m.cache_key('flash_attention', (('sq', 2048), "
             "('sk', 2048), ('d', 64), ('causal', True)), 'bfloat16', "
             "'v5e'))"],
            capture_output=True, text=True, check=True)
        assert out.stdout.strip() == key

    def test_dtype_canonicalization(self):
        a = tune.cache_key("softmax", (("sk", 128),), jnp.bfloat16, "cpu")
        b = tune.cache_key("softmax", (("sk", 128),), "bfloat16", "cpu")
        c = tune.cache_key("softmax", (("sk", 128),),
                           jnp.dtype(jnp.bfloat16), "cpu")
        assert a == b == c

    def test_float_key_material_rejected(self):
        with pytest.raises(TypeError):
            tune.cache_key("softmax", (("scale", 0.125),), None, "cpu")

    def test_corrupt_file_falls_back_empty(self, tmp_cache, capsys):
        with open(tmp_cache, "w") as f:
            f.write('{"entries": [truncated...')
        c = tune.TuneCache(tmp_cache)
        assert len(c) == 0
        rec = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
        assert rec["event"] == "tune_cache_corrupt"
        # and lookups with the corrupt file on disk use the heuristics
        got = tuned_params("layer_norm", (("rows", 64),),
                           {"block_rows": 32}, interpret=False)
        assert got == {"block_rows": 32}

    def test_wrong_schema_falls_back_empty(self, tmp_cache):
        with open(tmp_cache, "w") as f:
            json.dump({"schema": 999, "entries": {"k": {"params": {}}}}, f)
        assert len(tune.TuneCache(tmp_cache)) == 0


# ----------------------------------------------------------- tuned_params


class TestTunedParams:
    def test_miss_returns_defaults_unchanged(self, tmp_cache):
        defaults = {"block_rows": 256}
        got = tuned_params("layer_norm", (("rows", 8192), ("hidden", 4096)),
                           defaults, dtype=jnp.bfloat16, interpret=False)
        assert got == defaults and got is not defaults

    def test_hit_merges_known_keys_only(self, tmp_cache):
        shape_key = (("rows", 8192), ("hidden", 4096))
        key = tune.cache_key("layer_norm", shape_key, jnp.bfloat16,
                             tune.device_key())
        c = tune.default_cache()
        c.put(key, {"block_rows": 64, "evil_kwarg": 1})
        c.save()
        got = tuned_params("layer_norm", shape_key, {"block_rows": 256},
                           dtype=jnp.bfloat16, interpret=False)
        assert got == {"block_rows": 64}

    def test_interpret_never_consults_cache(self, tmp_cache, monkeypatch):
        # a lookup in interpret mode must not even touch the cache object
        # (patch the name api.py actually calls, not the defining module)
        import apex_tpu.tune.api as tune_api

        def boom():
            raise AssertionError("interpret-mode lookup touched the cache")

        monkeypatch.setattr(tune_api, "default_cache", boom)
        got = tuned_params("layer_norm", (("rows", 64), ("hidden", 128)),
                           {"block_rows": 8}, interpret=True)
        assert got == {"block_rows": 8}
        # ...and the interpret kernels go through that same short circuit
        from apex_tpu.ops.pallas.layer_norm_kernel import ln_fwd_pallas

        x = jax.random.normal(jax.random.PRNGKey(0), (16, 128))
        y, _, _ = ln_fwd_pallas(x, None, None, eps=1e-5, rms=False,
                                interpret=True)
        assert y.shape == (16, 128)

    def test_no_cache_unless_the_variable_names_one(self, monkeypatch):
        # block sizes must depend only on what the checkout holds: with
        # APEX_TPU_TUNE_CACHE unset no file is consulted (the old default,
        # ~/.cache/apex_tpu/tune_cache.json, is a file outside the
        # checkout that two commits under comparison would share)
        monkeypatch.delenv("APEX_TPU_TUNE_CACHE", raising=False)
        tune.invalidate()
        assert tune.default_cache_path() is None
        assert tune.default_cache() is None
        monkeypatch.setattr(
            os.path, "expanduser",
            lambda p: (_ for _ in ()).throw(AssertionError(p)))
        got = tuned_params("layer_norm", (("rows", 64), ("hidden", 128)),
                           {"block_rows": 8}, interpret=False)
        assert got == {"block_rows": 8}
        with pytest.raises(ValueError, match="APEX_TPU_TUNE_CACHE"):
            tune.record_tuned("layer_norm", (("rows", 64),), {"b": 1})
        with pytest.raises(ValueError, match="APEX_TPU_TUNE_CACHE"):
            tune.TuneCache()

    def test_force_compiled_aot_skips_cache(self, tmp_cache, monkeypatch):
        # deviceless AOT (APEX_TPU_FORCE_COMPILED=1) must not consult the
        # cache: device_key() would name the host, not the compile target,
        # and committed AOT artifacts must not depend on stray cache files
        shape_key = (("rows", 64), ("hidden", 128))
        key = tune.cache_key("layer_norm", shape_key, jnp.float32,
                             tune.device_key())
        c = tune.default_cache()
        c.put(key, {"block_rows": 16})
        c.save()
        monkeypatch.setenv("APEX_TPU_FORCE_COMPILED", "1")
        got = tuned_params("layer_norm", shape_key, {"block_rows": 64},
                           dtype=jnp.float32, interpret=False)
        assert got == {"block_rows": 64}

    def test_validate_rejects_bad_entry(self, tmp_cache):
        # flat optimizer entries are keyed dtype-agnostic (dtype=None)
        shape_key = (("rows", 128),)
        key = tune.cache_key("fused_adam", shape_key, None,
                             tune.device_key())
        c = tune.default_cache()
        c.put(key, {"block_rows": 100})  # not sublane-aligned
        c.save()
        from apex_tpu.ops.pallas.fused_adam_kernel import _flat_block_rows

        assert _flat_block_rows("fused_adam", 128, jnp.float32, False,
                                None) == 128  # heuristic min(512, rows)

    def test_flat_entries_shared_across_dtypes(self, tmp_cache):
        # warm at one dtype; the master-weight (fp32) and bf16 paths must
        # both pick the entry up — flat lookups are keyed dtype=None
        key = tune.cache_key("fused_adam", (("rows", 2048),), None,
                             tune.device_key())
        c = tune.default_cache()
        c.put(key, {"block_rows": 256})
        c.save()
        from apex_tpu.ops.pallas.fused_adam_kernel import _flat_block_rows

        for dt in (jnp.bfloat16, jnp.float32):
            assert _flat_block_rows("fused_adam", 2048, dt, False,
                                    None) == 256

    def test_selection_publishes_kernel_autotune_event(self, tmp_cache):
        from apex_tpu.utils.logging import subscribe_events

        shape_key = (("rows", 4096), ("hidden", 512))
        key = tune.cache_key("layer_norm", shape_key, jnp.float32,
                             tune.device_key())
        c = tune.default_cache()
        c.put(key, {"block_rows": 32})
        c.save()
        events = []
        unsub = subscribe_events(events.append)
        try:
            got = tuned_params("layer_norm", shape_key, {"block_rows": 256},
                              dtype=jnp.float32, interpret=False)
        finally:
            unsub()
        assert got == {"block_rows": 32}
        auto = [e for e in events if e["event"] == "kernel_autotune"]
        assert auto and auto[0]["source"] == "cache"
        assert auto[0]["params"] == {"block_rows": 32}
        assert auto[0]["key"] == key


# --------------------------------------- empty cache == heuristics, exact


class TestEmptyCacheBitForBit:
    """With no cache entry, every kernel must reproduce the pre-autotuner
    tile choices exactly (the shared tiling helpers ARE the old inline
    heuristics, and the compiled-path lookup falls through to them)."""

    def test_layer_norm(self, tmp_cache):
        from apex_tpu.ops.pallas.layer_norm_kernel import (_block_rows,
                                                           _pick_block_rows)
        from apex_tpu.ops.pallas.tiling import norm_block_rows

        for rows, hidden in [(64, 128), (8192, 4096), (8, 65536),
                             (1000, 256), (256, 131072)]:
            legacy = _seed_ln_pick(rows, hidden)
            assert _pick_block_rows(rows, hidden) == legacy
            assert norm_block_rows(rows, hidden) == legacy
            assert _block_rows(rows, hidden, jnp.bfloat16,
                               interpret=False) == legacy

    def test_softmax(self, tmp_cache):
        from apex_tpu.ops.pallas.softmax_kernel import (_block_rows,
                                                        _pick_rows)

        for skp, sq, itemsize, mask in [(128, 64, 2, False),
                                        (1024, 1024, 4, True),
                                        (16384, 8, 2, False),
                                        (2048, 333, 4, False)]:
            legacy = _seed_sm_pick(skp, sq, itemsize, mask)
            assert _pick_rows(skp, sq, itemsize, mask) == legacy
            assert _block_rows(skp, sq, itemsize, mask, jnp.bfloat16,
                               interpret=False) == legacy

    def test_group_norm(self, tmp_cache):
        from apex_tpu.ops.pallas.group_norm_kernel import (_hw_block,
                                                           _pick_hw_block)

        for hw, c in [(64, 64), (4096, 256), (16384, 2048), (1000, 128)]:
            legacy = _seed_gn_pick(hw, c)
            assert _pick_hw_block(hw, c) == legacy
            assert _hw_block(hw, c, jnp.bfloat16, interpret=False) == legacy

    def test_flash_attention_defaults(self, tmp_cache):
        from apex_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                         DEFAULT_BLOCK_Q,
                                                         _resolve_blocks)

        assert _resolve_blocks(2048, 2048, 64, True, jnp.bfloat16,
                               None, None) == (DEFAULT_BLOCK_Q,
                                               DEFAULT_BLOCK_K)

    def test_flat_optimizers(self, tmp_cache):
        from apex_tpu.ops.pallas.fused_adam_kernel import (_flat_block_rows,
                                                           _pick_block_rows)

        for rows in [8, 512, 7813, 7812496]:
            legacy = min(512, rows)
            assert _pick_block_rows(rows) == legacy
            assert _flat_block_rows("fused_adam", rows, jnp.bfloat16,
                                    False, None) == legacy
            # explicit arg always wins
            assert _flat_block_rows("fused_adam", rows, jnp.bfloat16,
                                    False, 128) == 128

    def test_warmed_cache_changes_selection(self, tmp_cache):
        """The inverse control: a valid warmed entry IS picked up."""
        from apex_tpu.ops.pallas.layer_norm_kernel import _block_rows

        rows, hidden = 8192, 4096
        tune.record_tuned("layer_norm",
                          (("rows", pow2_bucket(rows)), ("hidden", hidden)),
                          {"block_rows": 64}, dtype=jnp.bfloat16)
        tune.invalidate()
        assert _block_rows(rows, hidden, jnp.bfloat16,
                           interpret=False) == 64
        # interpret mode still ignores it
        assert _block_rows(rows, hidden, jnp.bfloat16,
                           interpret=True) == _seed_ln_pick(rows, hidden)


# seed-era reference implementations (verbatim from the pre-PR3 kernels),
# kept here as the bit-for-bit oracle the shared helpers must match


def _seed_ln_pick(rows, hidden):
    budget = 2 * 1024 * 1024 // max(hidden * 4, 1)
    br = 256
    while br > budget and br > 8:
        br //= 2
    while rows % br != 0 and br > 8:
        br //= 2
    return max(br, 8)


def _seed_sm_pick(skp, sq, itemsize, has_mask):
    def round_up(n, m):
        return -(-n // m) * m

    bytes_per_elt = 2 * (2 * itemsize + (4 if has_mask else 0)) + 8
    br = (10 << 20) // (skp * bytes_per_elt)
    br = max(8, min(512, round_up(br, 8) if br >= 8 else 8))
    return min(br, round_up(sq, 8))


def _seed_gn_pick(hw, c):
    budget = max((2 * 1024 * 1024) // max(c * 4, 1), 8)
    blk = 1 << (budget.bit_length() - 1)
    blk = min(blk, hw)
    while hw % blk != 0 and blk > 8:
        blk //= 2
    return max(blk, 8)


# ------------------------------------------------- flash block validation


class TestFlashBlockValidation:
    def _qkv(self, s=64, d=64):
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k_, (1, 2, s, d)) * 0.1 for k_ in k)

    def test_misaligned_block_q_raises(self):
        from apex_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="multiple of 8"):
            flash_attention(q, k, v, True, block_q=100)

    def test_misaligned_block_k_raises(self):
        from apex_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, k, v, True, block_k=100)

    def test_nonpositive_raises(self):
        from apex_tpu.ops.pallas.flash_attention import validate_blocks

        with pytest.raises(ValueError):
            validate_blocks(0, 128, 64, 64)
        with pytest.raises(ValueError):
            validate_blocks(8, -128, 64, 64)

    def test_valid_explicit_blocks_accepted(self):
        from apex_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = self._qkv()
        o = flash_attention(q, k, v, True, block_q=16, block_k=128)
        assert o.shape == q.shape
        # parity with the default-block path (same math, different grid)
        o2 = flash_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o2),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------------- search + CLI smoke


class TestSearchAndCli:
    def test_every_consulting_kernel_is_warmable(self):
        # every kernel with a CODE_VERSIONS entry (i.e. whose entry point
        # consults the cache) must have a registry spec — otherwise its
        # lookup path is permanently dead code
        from apex_tpu.tune import registry

        assert set(tune.CODE_VERSIONS) == set(registry.kernels())
        for name in registry.kernels():
            spec = registry.spec(name)
            assert spec.default_shapes, name
            shape = dict(spec.default_shapes[0])
            cands = spec.candidates(shape)
            assert spec.defaults(shape) in cands, name

    def test_flat_optimizer_specs_run(self, tmp_cache):
        from apex_tpu.tune.search import autotune_kernel

        for kernel in ("fused_lamb", "fused_novograd", "fused_adagrad"):
            res = autotune_kernel(kernel, {"numel": 1024}, "float32",
                                  iters=1, max_candidates=1)
            assert "best" in res, res
            assert res["key"].startswith(f"{kernel}|")

    def test_autotune_kernel_writes_winner(self, tmp_cache):
        from apex_tpu.tune.search import autotune_kernel

        res = autotune_kernel("layer_norm", {"rows": 64, "hidden": 256},
                              "float32", iters=1, max_candidates=2)
        assert "best" in res and res["key"].startswith("layer_norm|")
        tune.invalidate()
        assert tune.default_cache().get(res["key"])["params"] == res["best"]
        # the default candidate is always part of the sweep
        tried = [r["params"] for r in res["candidates"]]
        assert res["default"] in tried

    def test_cli_end_to_end_smoke(self, tmp_cache, tmp_path, capsys):
        from apex_tpu.tune.cli import main as tune_main
        from apex_tpu.utils.logging import subscribe_events

        spec = tmp_path / "workload.json"
        spec.write_text(json.dumps([
            {"kernel": "layer_norm", "shape": {"rows": 32, "hidden": 128},
             "dtype": "float32"},
            {"kernel": "fused_sgd", "shape": {"numel": 1024},
             "dtype": "float32"},
        ]))
        events = []
        unsub = subscribe_events(events.append)
        try:
            rc = tune_main(["--spec", str(spec), "--iters", "1",
                            "--max-candidates", "2"])
        finally:
            unsub()
        assert rc == 0
        doc = json.load(open(tmp_cache))
        assert doc["schema"] == 1 and len(doc["entries"]) == 2
        assert any(k.startswith("layer_norm|") for k in doc["entries"])
        assert any(k.startswith("fused_sgd|") for k in doc["entries"])
        auto = [e for e in events if e["event"] == "kernel_autotune"]
        assert {e["kernel"] for e in auto} == {"layer_norm", "fused_sgd"}
        assert all(e["source"] == "search" for e in auto)
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[-1]["tuned"] == 2 and lines[-1]["failed"] == 0

    def test_cli_rejects_unknown_kernel(self, tmp_cache, tmp_path):
        from apex_tpu.tune.cli import main as tune_main

        spec = tmp_path / "workload.json"
        spec.write_text(json.dumps([{"kernel": "nope", "shape": {}}]))
        with pytest.raises((SystemExit, KeyError)):
            tune_main(["--spec", str(spec)])


# ------------------------------------------------------- baseline gate


class TestBaselineGate:
    BASELINE = os.path.join(REPO, "BENCH_BASELINE.json")

    def _run(self, args):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import check_regression
        finally:
            sys.path.pop(0)
        return check_regression.main(args)

    def test_committed_baseline_self_compare_passes(self, capsys):
        assert os.path.exists(self.BASELINE), \
            "BENCH_BASELINE.json must be committed (apex-tpu-bench " \
            "--kernels ... --emit-baseline)"
        rc = self._run([self.BASELINE, "--suite", self.BASELINE])
        assert rc == 0
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["regressions"] == 0 and summary["compared"] > 0
        assert "per_kernel" in summary
        # the committed gate covers at least two kernels (acceptance)
        assert len(summary["per_kernel"]) >= 2

    def test_regression_detected_per_kernel(self, tmp_path, capsys):
        base = json.load(open(self.BASELINE))
        cur = json.loads(json.dumps(base))
        cur["layer_norm"]["value"] = base["layer_norm"]["value"] * 3.0
        cur_path = tmp_path / "cur.json"
        cur_path.write_text(json.dumps(cur))
        rc = self._run([str(cur_path), "--suite", self.BASELINE])
        assert rc == 1
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["per_kernel"]["layer_norm"]["regressions"] >= 1
        # the untouched kernels stay green
        assert summary["per_kernel"]["fused_adam_1b"]["regressions"] == 0

    def test_kernel_subset_filter(self, tmp_path, capsys):
        base = json.load(open(self.BASELINE))
        cur = json.loads(json.dumps(base))
        cur["layer_norm"]["value"] = base["layer_norm"]["value"] * 3.0
        cur_path = tmp_path / "cur.json"
        cur_path.write_text(json.dumps(cur))
        # gating only fused_adam_1b ignores the layer_norm regression
        rc = self._run([str(cur_path), "--suite", self.BASELINE,
                        "--kernels", "fused_adam_1b"])
        assert rc == 0
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert list(summary["per_kernel"]) == ["fused_adam_1b"]

    def test_usage_requires_exactly_one_baseline(self):
        assert self._run([self.BASELINE]) == 2
        assert self._run([self.BASELINE, self.BASELINE,
                          "--suite", self.BASELINE]) == 2


# --------------------------------------------------- bench_cli --kernels


class TestBenchSubset:
    def test_emit_baseline_subset(self, tmp_path, monkeypatch):
        from apex_tpu import bench_cli

        out = tmp_path / "B.json"
        monkeypatch.setattr(sys, "argv",
                            ["apex-tpu-bench", "--kernels", "layer_norm",
                             "--emit-baseline", str(out)])
        bench_cli.main()
        doc = json.load(open(out))
        assert doc["subset"] == ["layer_norm"]
        assert doc["complete"] is False  # a subset is never a full suite
        assert "value" in doc["layer_norm"]
        assert "fused_adam_1b" not in doc

    def test_unknown_kernel_raises(self, monkeypatch):
        from apex_tpu import bench_cli

        monkeypatch.setattr(sys, "argv",
                            ["apex-tpu-bench", "--kernels", "not_a_bench"])
        with pytest.raises(ValueError, match="unknown bench"):
            bench_cli.main()
