"""dp×tp training + topology-portable checkpoints (markers: ``train`` +
``fault``).

The PR-19 acceptance claims, proven deterministically on the
fake-multihost harness + the conftest-forced 8-device CPU mesh:

- **tp composes with dp bit-exactly**: ``TrainConfig(tp=2)`` runs each
  grad micro-shard's forward/backward over the PR-15 head-axis mesh
  (gather-compute-slice — pure concatenation combine, no float add
  crosses a rank), and BOTH identities survive the composition:
  tp=2 ≡ tp=1 on one chip, and world 1 ≡ world 2 with tp armed;
- **THE chaos train-then-serve headline**: the PR-14 chaos schedule
  (preempt ×2, elastic 2→1→2, crash-on-step, crash-mid-save) on a
  dp×tp=2 GPT-2 trainer ends bit-identical to the uninterrupted
  single-chip oracle, the committed checkpoint's manifest carries the
  dp×tp ``layout`` block, and the restored params serve through a tp=2
  ``Engine`` with decode logits matching a single-chip prefill of the
  trained params to float32 rounding (the batched prefill sums in
  another order than the one-row decode step: docs/serving.md);
- **topology-portable restore**: a checkpoint written at tp=2 restores
  onto a tp=1 job automatically (the sharded manager reassembles leaves
  topology-independently), publishing a counted
  ``train_topology_restored`` — and the resumed run stays bit-exact;
- **reshard is a digest-verified pure permutation**: dense → tp_serving
  → dense is byte-identical, and the storage-layer numpy transform is
  bit-identical to the serving stack's ``permute_qkv``/``unpermute_qkv``;
- **storage chaos**: a single bit-flip in one committed blob
  (``corrupt_checkpoint_blob``) quarantines exactly that step and falls
  back to the last good commit bit-exactly; a torn manifest is refused
  loudly (quarantined, never half-restored).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from apex_tpu.resilience import (FaultInjector, ShardedCheckpointManager,
                                 SingleProcessCoordinator)
from apex_tpu.resilience.checkpoint_manager import CheckpointManager
from apex_tpu.resilience.topology import (FORMAT_TP_SERVING, ReshardError,
                                          layout_block, reshard,
                                          tree_digests)
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.tp import permute_qkv, tp_param_specs, unpermute_qkv
from apex_tpu.train import TrainConfig, Trainer, TrainSupervisor
from apex_tpu.train.cli import main as train_cli_main
from apex_tpu.utils.logging import subscribe_events

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.train, pytest.mark.fault]

# the serve-suite GPT-2 (same shape as tests/test_serve_tp.py): 4 heads,
# head_dim 8 — tp=2 gives each rank 2 heads; fp32 so bit-equality is
# meaningful end to end
CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=4, compute_dtype=jnp.float32)
_GPT2 = GPT2(CFG)


def _gpt2_loss(params, tokens):
    return lm_loss(_GPT2, params, tokens)


def _gpt2_batch(step):
    rng = np.random.RandomState(100003 * 23 + int(step))
    return jnp.asarray(rng.randint(0, CFG.vocab_size, (8, 16)), jnp.int32)


def _gcfg(**kw):
    base = dict(steps=12, batch=8, seq=16, vocab=97, hidden=32,
                grad_shards=2, seed=23)
    base.update(kw)
    return TrainConfig(**base)


def _cfg(seed, **kw):
    base = dict(steps=10, batch=8, seq=12, vocab=64, hidden=24,
                grad_shards=2, seed=seed)
    base.update(kw)
    return TrainConfig(**base)


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _tokens(n, seed=7):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, CFG.vocab_size, n)]


@pytest.fixture
def events():
    collected = []
    unsub = subscribe_events(collected.append)
    yield collected
    unsub()


def _named(events, name):
    return [e for e in events if e.get("event") == name]


# --------------------------------------------- dp×tp bit-identity (builtin)

def test_world_sizes_bit_identical_with_tp_armed(tp_devices):
    """Both identities through the composition: tp=2 on the mesh equals
    tp=1 on one chip bit-for-bit, and world 1 equals world 2 with tp=2
    armed — each grad micro-shard's shard_map forward/backward changes
    nothing the dp reduction can see."""
    ref = Trainer(_cfg(seed=33))
    ref.run()
    oracle = jax.tree_util.tree_map(np.asarray, ref.params)
    ref.close()

    t2 = Trainer(_cfg(seed=33, tp=2))
    t2.run()
    try:
        _assert_trees_equal(t2.params, oracle)
    finally:
        t2.close()

    sup = TrainSupervisor(_cfg(seed=33, world=2, tp=2))
    rep = sup.run()
    assert rep["final_step"] == 9 and not rep["preempted"]
    _assert_trees_equal(sup.params(), oracle)
    assert rep["goodput"]["steps"] == 10 and rep["steps_retried"] == 0


# ------------------------------------------------ THE chaos train-then-serve

def test_chaos_dp_tp_train_then_serve_bit_identical(tmp_path, events,
                                                    tp_devices):
    """Headline: THE PR-14 chaos schedule (preempt ×2, elastic 2→1→2,
    crash-on-step, crash-mid-save) on a dp×tp=2 GPT-2 trainer — final
    params bit-identical to the uninterrupted single-chip oracle, the
    committed manifest carries the dp×tp layout block, zero recompiles
    across every leg (the custom-fns cache), and the trained checkpoint
    serves through a tp=2 Engine with decode logits matching a
    single-chip prefill of the same params to float32 rounding."""
    steps = 12
    init = init_gpt2_params(CFG, seed=0)
    spec = {"params": tp_param_specs(CFG, "exact")}

    ref = Trainer(_gcfg(), loss_fn=_gpt2_loss, init_params=init,
                  batch_fn=_gpt2_batch)
    ref.run()
    oracle = jax.tree_util.tree_map(np.asarray, ref.params)
    ref.close()

    inj = (FaultInjector(seed=23)
           .preempt_at_step(3, rank=1)       # drain -> resize 2 -> 1
           .preempt_at_step(7, rank=0)       # drain -> resize 1 -> 2
           .crash_on_train_step(9)           # warm restart, same topology
           .crash_during_checkpoint_save(8))  # death mid-commit
    cfg = _gcfg(world=2, tp=2, checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj, max_restarts=3,
                          backoff_s=0.01, world_schedule=[2, 1, 2],
                          loss_fn=_gpt2_loss, init_params=init,
                          batch_fn=_gpt2_batch, tp_spec=spec)
    rep = sup.run()
    assert not rep["preempted"] and rep["final_step"] == steps - 1
    assert rep["preempt_drains"] == 2 and rep["restarts"] == 2
    _assert_trees_equal(sup.params(), oracle)
    # exactly-once accounting + zero recompiles: every restart / resize
    # leg reused the ONE compiled tp step (the (loss_fn, static_key)
    # cache), so the chaos run never paid a second GPT-2 grad compile
    assert rep["goodput"]["steps"] == steps
    counts = sup.trace_counts()
    assert counts["shard_grads"] == 1 and counts["apply"] == 1, counts
    # same tp throughout: the restores were same-topology, no reshard
    assert not _named(events, "train_topology_restored")

    # the committed manifest records WHO wrote it: the dp×tp layout block
    mgr = ShardedCheckpointManager(
        str(tmp_path), coordinator=SingleProcessCoordinator())
    layout = mgr.validate(mgr.latest_step())["layout"]
    assert layout["storage"] == "sharded"
    assert layout["tp"] == 2 and layout["grad_shards"] == 2
    assert layout["world"] == 2

    # train-then-serve: restore the committed step, load the params into
    # a tp=2 serving Engine (head-major qkv permutation happens at param
    # load), and hold its incremental decode LOGITS to a single-chip
    # prefill of the trained params: across the prefill/decode border,
    # so to float32 rounding (tests/test_serve.py: BORDER), not the bit
    probe = Trainer(cfg, loss_fn=_gpt2_loss, init_params=init,
                    batch_fn=_gpt2_batch, tp_spec=spec)
    restored = mgr.restore_latest(probe._tree(0))
    probe.close()
    assert restored is not None and restored[0] == steps - 1
    dense = jax.tree_util.tree_map(np.asarray, restored[1]["params"])
    _assert_trees_equal(dense, oracle)

    e_kw = dict(num_slots=3, max_len=32, temperature=0.0, block_k=8)
    served = jax.tree_util.tree_map(jnp.asarray, dense)  # device-resident
    keeper = Engine(CFG, served,
                    EngineConfig(keep_prefill_logits=True, **e_kw))
    seq = _tokens(12, seed=9)
    _, _, all_logits = keeper.prefill({1: seq})
    all_logits = np.asarray(all_logits)              # [P, B, V]
    tp_eng = Engine(CFG, served, EngineConfig(tp=2, **e_kw))
    tp_eng.prefill({1: seq[:5]})
    for j in range(5, len(seq)):
        forced = np.array([0, seq[j], 0], np.int32)
        _, logits = tp_eng.decode_step(forced,
                                       np.array([False, True, False]))
        a, b = all_logits[j, 1], np.asarray(logits)[1]
        assert a.dtype == np.float32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                   err_msg=f"served pos {j}")


# --------------------------------------------- topology-portable restore

def test_restore_across_tp_topologies_reshards_bit_exact(tmp_path,
                                                         events,
                                                         tp_devices):
    """A checkpoint written by a tp=2 job restores onto a tp=1 job
    automatically (the sharded manager reassembles leaves topology-
    independently and places them with the restore target's sharding —
    restore onto a different tp IS the reshard), publishes ONE counted
    ``train_topology_restored`` naming both topologies, and the resumed
    run ends bit-identical to the uninterrupted tp=1 oracle."""
    ref = Trainer(_cfg(seed=31))
    ref.run()
    oracle = jax.tree_util.tree_map(np.asarray, ref.params)
    ref.close()

    leg_a = Trainer(_cfg(seed=31, steps=4, tp=2,
                         checkpoint_dir=str(tmp_path), save_every=2))
    leg_a.run()
    leg_a.close()
    mgr = ShardedCheckpointManager(
        str(tmp_path), coordinator=SingleProcessCoordinator())
    assert mgr.latest_step() == 3
    assert mgr.validate(3)["layout"]["tp"] == 2

    leg_b = Trainer(_cfg(seed=31, checkpoint_dir=str(tmp_path),
                         save_every=2))
    rep = leg_b.run()
    try:
        assert rep["restored_from"] == 3 and rep["final_step"] == 9
        _assert_trees_equal(leg_b.params, oracle)
    finally:
        leg_b.close()
    moved = _named(events, "train_topology_restored")
    assert len(moved) == 1
    assert moved[0]["from_tp"] == 2 and moved[0]["to_tp"] == 1


# --------------------------------------------- reshard: pure permutation

def test_reshard_dense_tp_serving_round_trip_byte_identical():
    """``dense → tp_serving → dense`` is byte-identical (digest-verified
    on every call), and the storage-layer numpy permutation is
    bit-identical to the serving stack's permute/unpermute pair."""
    rng = np.random.RandomState(0)
    qkv_k = rng.randn(32, 96).astype(np.float32)
    qkv_b = rng.randn(96).astype(np.float32)
    tree = {"wte": rng.randn(97, 32).astype(np.float32),
            "h_0": {"attn_qkv": {"kernel": qkv_k, "bias": qkv_b},
                    "mlp_fc_w": rng.randn(32, 128).astype(np.float32)}}
    dense_l = layout_block(world=2, grad_shards=2, tp=1)
    serve_l = layout_block(tp=2, fmt=FORMAT_TP_SERVING, n_head=4,
                           head_dim=8)
    served = reshard(tree, dense_l, serve_l)
    # bit-identical to the serving stack's own transform
    pk, pb = permute_qkv(qkv_k, qkv_b, 4, 8, 2)
    np.testing.assert_array_equal(served["h_0"]["attn_qkv"]["kernel"], pk)
    np.testing.assert_array_equal(served["h_0"]["attn_qkv"]["bias"], pb)
    uk, ub = unpermute_qkv(pk, pb, 4, 8, 2)
    np.testing.assert_array_equal(uk, qkv_k)
    np.testing.assert_array_equal(ub, qkv_b)
    # non-qkv leaves pass through untouched
    np.testing.assert_array_equal(served["wte"], tree["wte"])
    # the round trip is byte-identical, proven by digest
    back = reshard(served, serve_l, dense_l)
    assert tree_digests(back) == tree_digests(tree)
    # same-format reshard is a numpy pass-through
    same = reshard(tree, dense_l, dense_l)
    assert tree_digests(same) == tree_digests(tree)


def test_reshard_refuses_bad_layouts():
    with pytest.raises(ReshardError, match="unknown layout format"):
        layout_block(fmt="bogus")
    tree = {"attn_qkv": {"kernel": np.zeros((4, 12), np.float32),
                         "bias": np.zeros(12, np.float32)}}
    with pytest.raises(ReshardError, match="unknown layout format"):
        reshard(tree, {"format": "bogus"}, {"format": "dense"})
    with pytest.raises(ReshardError, match="n_head/head_dim"):
        # a tp_serving target without model geometry cannot permute
        reshard(tree, layout_block(),
                {"world": 1, "grad_shards": 1, "tp": 2,
                 "format": FORMAT_TP_SERVING})


# ------------------------------------------------------- storage chaos

def test_corrupt_blob_quarantines_once_and_falls_back_bit_exact(
        tmp_path, events, tp_devices):
    """A single bit-flip in ONE committed blob: restore quarantines
    exactly that step (one ``checkpoint_quarantined``, republished as a
    counted ``train_ckpt_quarantined``), falls back to the last good
    commit, and the recovered run ends bit-identical to the oracle. A
    torn manifest is likewise refused loudly — quarantined, never
    half-restored."""
    ref = Trainer(_cfg(seed=35))
    ref.run()
    oracle = jax.tree_util.tree_map(np.asarray, ref.params)
    ref.close()

    cfg = _cfg(seed=35, checkpoint_dir=str(tmp_path), save_every=2)
    first = Trainer(cfg)
    first.run()
    first.close()
    mgr = ShardedCheckpointManager(
        str(tmp_path), coordinator=SingleProcessCoordinator())
    latest = mgr.latest_step()
    assert latest == 9

    inj = FaultInjector(seed=35).corrupt_checkpoint_blob(latest, leaf=0)
    second = Trainer(cfg, injector=inj)
    rep = second.run()
    try:
        # the rotted step 9 was refused; step 8 restored; 9 re-ran
        assert rep["restored_from"] == 8 and rep["final_step"] == 9
        _assert_trees_equal(second.params, oracle)
        q = getattr(second.manager, "last_quarantined", None)
        assert q is not None and len(q) == 1 and q[0]["step"] == latest
    finally:
        second.close()
    assert len(_named(events, "checkpoint_quarantined")) == 1
    counted = _named(events, "train_ckpt_quarantined")
    assert len(counted) == 1 and counted[0]["step"] == latest
    assert any(n.endswith(".corrupt") for n in os.listdir(tmp_path))

    # torn manifest: truncated JSON in the newest commit — refused
    # loudly (quarantined), the previous commit restores instead
    newest = mgr.latest_step()
    mpath = os.path.join(mgr.step_path(newest), "manifest.json")
    with open(mpath, "wb") as f:
        f.write(b'{"format_version": 1, "leav')
    probe = Trainer(cfg)
    like = probe._tree(0)
    out = mgr.restore_latest(like)
    probe.close()
    assert out is not None and out[0] < newest
    assert any(q["step"] == newest for q in mgr.last_quarantined)


# ------------------------------------------------- config + CLI matrix

def test_config_validation_refuses_bad_tp_geometry():
    with pytest.raises(ValueError, match=">= 1"):
        TrainConfig(tp=0).validate()
    with pytest.raises(ValueError, match="divide hidden"):
        TrainConfig(tp=3, hidden=32).validate()
    with pytest.raises(ValueError, match="sharded_checkpoint"):
        TrainConfig(tp=2, hidden=32, checkpoint_dir="/x",
                    sharded_checkpoint=False).validate()


@pytest.mark.parametrize("argv,fragment", [
    (["--tp", "0"], ">= 1"),
    (["--tp", "3"], "divide hidden"),
    (["--tp", "2", "--grad-shards", "2", "--checkpoint-dir", "/tmp/x",
      "--elastic", "2x2:1x1"], "tp resize refused"),
    (["--tp", "2", "--grad-shards", "2", "--checkpoint-dir", "/tmp/x",
      "--elastic", "2xbanana"], "colon-separated"),
    (["--tp", "2", "--world", "8", "--grad-shards", "8"], "envelope"),
])
def test_train_cli_tp_exit2_matrix(argv, fragment, capsys):
    """The tp flag matrix refuses loudly (exit 2) before anything
    compiles: bad degree, non-dividing hidden, a live tp resize spelled
    into the world schedule, and a dp×tp envelope larger than the
    host's device pool."""
    rc = train_cli_main(argv)
    assert rc == 2
    err = capsys.readouterr().err
    assert fragment in err, err


# ------------------------------------------------- jax-free inspection

def test_ckpt_inspect_jax_free_dump_and_digest_gate(tmp_path):
    """``tools/ckpt_inspect.py`` dumps a committed step's layout block
    and digests with jax POISONED in the subprocess (importing it would
    explode — proving the forensic tool never touches jax), and exits 2
    on a flipped blob byte or a torn manifest."""
    ck = tmp_path / "ck"
    mgr = CheckpointManager(str(ck))
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.int64(7)}
    mgr.save(3, tree, layout=layout_block(world=1, grad_shards=2, tp=2))

    poison = tmp_path / "poison" / "jax"
    poison.mkdir(parents=True)
    (poison / "__init__.py").write_text(
        "raise ImportError('ckpt_inspect must not import jax')")
    env = dict(os.environ, PYTHONPATH=str(tmp_path / "poison"))
    tool = os.path.join(ROOT, "tools", "ckpt_inspect.py")

    out = subprocess.run([sys.executable, tool, str(ck)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["step"] == 3 and doc["storage"] == "dense"
    assert doc["layout"]["tp"] == 2 and doc["layout"]["grad_shards"] == 2
    assert doc["blobs_verified"] == 2 and doc["all_steps"] == [3]
    assert all(e["blake2b"] for e in doc["leaves"])

    # a missing step is a usage error, loudly
    out = subprocess.run([sys.executable, tool, str(ck), "--step", "7"],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2 and "not committed" in out.stderr

    # flip one bit of one committed blob -> exit 2 naming the file
    step_dir = os.path.join(str(ck), "step_00000003")
    blob = sorted(n for n in os.listdir(step_dir) if n.endswith(".npy"))[0]
    path = os.path.join(step_dir, blob)
    data = bytearray(open(path, "rb").read())
    data[-1] ^= 0x01
    open(path, "wb").write(bytes(data))
    out = subprocess.run([sys.executable, tool, str(ck)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2 and "mismatch" in out.stderr

    # torn manifest -> exit 2, named as torn
    with open(os.path.join(step_dir, "manifest.json"), "wb") as f:
        f.write(b'{"num_leaves": 2, "leaves": [')
    out = subprocess.run([sys.executable, tool, str(ck)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2 and "torn" in out.stderr


# ------------------------------------------------- bench + gate wiring

def test_bench_train_chaos_tp_provenance_and_gate_refusal(capsys,
                                                          monkeypatch,
                                                          tp_devices):
    """``apex-tpu-bench --train-chaos --tp 2`` stamps the tensor axis
    into workload provenance; the regression gate refuses a dp×tp
    capture against a legacy dp-only baseline (missing key = tp 1)
    instead of pretending to compare, and the new counted event names
    gate lower-is-better."""
    import apex_tpu.bench_cli as bench_cli

    tools_path = os.path.join(ROOT, "tools")
    if tools_path not in sys.path:
        sys.path.insert(0, tools_path)
    import check_regression

    monkeypatch.setattr(sys, "argv",
                        ["apex-tpu-bench", "--train-chaos", "--steps",
                         "6", "--tp", "2"])
    bench_cli.main()
    out = capsys.readouterr().out
    suite = json.loads(out[out.index("{"):])
    entry = suite["train_chaos"]
    assert entry["workload"]["tp"] == 2
    assert entry["step_recompiles"] == 1  # zero-recompile under the mesh
    # a healthy chaos run quarantines nothing and never reshards
    assert entry["ckpt_quarantined"] == 0
    assert entry["topology_restored"] == 0

    legacy = {"train_chaos": json.loads(json.dumps(entry))}
    del legacy["train_chaos"]["workload"]["tp"]  # pre-tp-axis baseline
    bad = check_regression.incomparable_entries(suite, legacy)
    assert "train_chaos" in bad and "tp=2" in bad["train_chaos"]

    # a quarantine storm / reshard churn gates as a regression off the
    # healthy 0 baseline (flat counter names, as the bench stamps them)
    assert check_regression.lower_is_better("ckpt_quarantined")
    assert check_regression.lower_is_better("topology_restored")

    # bad tp geometry is a loud exit 2 before anything compiles
    monkeypatch.setattr(sys, "argv",
                        ["apex-tpu-bench", "--train-chaos", "--tp", "3"])
    with pytest.raises(SystemExit) as exc:
        bench_cli.main()
    assert exc.value.code == 2
    assert "divide the bench model's hidden" in capsys.readouterr().err
