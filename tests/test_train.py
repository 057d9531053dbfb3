"""The elastic production trainer + training chaos harness (markers:
``train`` + ``fault``).

The acceptance claims, proven deterministically on the fake-multihost
``ThreadProcessGroup`` harness:

- the Trainer is a **bit-equality oracle** of a hand-rolled loop built
  from the same public primitives — the composition (ResilientStep,
  sharded reduction, accounting) adds nothing to the math;
- updates are **world-size independent** (the canonical shard-indexed
  reduction), which is what elastic 2→1→2 restarts ride;
- a coordinated preemption drains every rank at the same step, commits
  ONE final checkpoint, and accounts exactly-once;
- a crash mid-checkpoint-commit leaves the previous committed step
  restorable (the atomic-commit discipline, injected at the trainer);
- a same-topology supervisor restart adds **zero recompiles** (trace
  counters on every jitted step-path function stay at 1);
- THE chaos smoke: preempt + crash-on-step + crash-during-save +
  elastic resize in one seeded schedule completes with bit-identical
  final params vs the uninterrupted oracle, exactly-once step accounting
  in the goodput ledger, and zero recompiles on the same-topology
  restarts.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.monitor.export import MetricsRegistry
from apex_tpu.optimizers.functional import adam_update
from apex_tpu.resilience import (FaultInjector, ShardedCheckpointManager,
                                 SimulatedCrash, SingleProcessCoordinator)
from apex_tpu.train import (TrainConfig, Trainer, TrainSupervisor,
                            make_scaler, tiny_lm_batch, tiny_lm_params)
from apex_tpu.train.cli import main as train_cli_main
from apex_tpu.utils.logging import subscribe_events

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.train, pytest.mark.fault]


def _cfg(seed, **kw):
    base = dict(steps=10, batch=8, seq=12, vocab=64, hidden=24,
                grad_shards=2, seed=seed)
    base.update(kw)
    return TrainConfig(**base)


def _oracle_params(seed, **kw):
    """Uninterrupted single-rank reference run (params only)."""
    tr = Trainer(_cfg(seed, **kw))
    tr.run()
    try:
        return tr.params
    finally:
        tr.close()


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def events():
    collected = []
    unsub = subscribe_events(collected.append)
    yield collected
    unsub()


def _named(events, name):
    return [e for e in events if e.get("event") == name]


# ------------------------------------------------ hand-rolled oracle

def test_trainer_matches_hand_rolled_loop_bit_exact():
    """The Trainer IS the hand-rolled loop: same public primitives
    (seeded init/batches, scaler, canonical shard-order reduction, fused
    Adam, skip-on-overflow, floor), composed by hand — final params
    bit-identical, and the loss falls."""
    cfg = _cfg(seed=11)
    scaler = make_scaler(cfg)
    G, inv = cfg.grad_shards, 1.0 / cfg.grad_shards

    def loss_fn(p, tokens):
        x = p["emb"][tokens[:, :-1]]
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logp = jax.nn.log_softmax((h @ p["head"]).astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)

    @jax.jit
    def shard_grads(p, sstate, tokens):
        def scaled(p):
            loss = loss_fn(p, tokens)
            return scaler.scale(loss, sstate), loss

        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(p)
        return grads, loss

    @jax.jit
    def apply(p, m, v, sstate, gsum, t):
        grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
        grads, _, found_inf = scaler.unscale_and_norm(grads, sstate)
        new_p, m, v = adam_update(p, grads, m, v, step=t + 1, lr=cfg.lr,
                                  found_inf=found_inf)
        # the ResilientStep post-step, by hand: keep old values on
        # overflow, advance the scale state machine, apply the floor
        kept = jax.tree_util.tree_map(
            lambda n, o: jnp.where(found_inf, o, n), (new_p, m, v),
            (p, m, v))
        sstate = scaler.update(sstate, found_inf)
        sstate = sstate._replace(scale=jnp.maximum(
            sstate.scale, jnp.float32(cfg.scale_floor)))
        return kept, sstate

    params = tiny_lm_params(cfg)
    zeros = lambda x: jnp.zeros_like(x, jnp.float32)  # noqa: E731
    m = jax.tree_util.tree_map(zeros, params)
    v = jax.tree_util.tree_map(zeros, params)
    sstate = scaler.init()
    losses = []
    for t in range(cfg.steps):
        tokens = tiny_lm_batch(cfg, t)
        shards = tokens.reshape((G, cfg.batch // G, cfg.seq))
        parts = [shard_grads(params, sstate, shards[i]) for i in range(G)]
        gsum = functools.reduce(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
            (g for g, _ in parts))
        losses.append(float(
            functools.reduce(jnp.add, (l for _, l in parts)) * inv))
        (params, m, v), sstate = apply(params, m, v, sstate, gsum,
                                       jnp.int32(t))

    trainer = Trainer(_cfg(seed=11))
    step_losses = []
    trainer.run(on_step=lambda t, loss: step_losses.append(loss))
    try:
        _assert_trees_equal(trainer.params, params)
        _assert_trees_equal((trainer.m, trainer.v), (m, v))
        # per-step losses identical too (not just the endpoint), and the
        # run actually trained (params moved; the lm_pretrain example
        # covers loss-falls on real structure — these tokens are random)
        np.testing.assert_allclose(step_losses, losses, rtol=0, atol=0)
        assert len(set(step_losses)) > 1
    finally:
        trainer.close()


def test_world_sizes_produce_bit_identical_updates():
    """The canonical shard-indexed reduction: world 1 and world 2 runs of
    the same config produce bit-identical params — the foundation every
    elastic restore stands on."""
    oracle = _oracle_params(seed=12)
    sup = TrainSupervisor(_cfg(seed=12, world=2))
    rep = sup.run()
    assert rep["final_step"] == 9 and not rep["preempted"]
    _assert_trees_equal(sup.params(), oracle)
    # exactly-once: every step productive, none replayed
    assert rep["goodput"]["steps"] == 10
    assert rep["steps_retried"] == 0


# ------------------------------------------------ preemption drain

def test_coordinated_preemption_drains_once_and_resumes(tmp_path,
                                                        events):
    """A preemption on rank 1 is agreed collectively: both ranks drain at
    the same step, ONE final checkpoint commits, rank 0 publishes exactly
    one timed train_preempt_drain, accounting is exactly-once across the
    drain + resume, and the resumed job finishes bit-identical to the
    uninterrupted oracle."""
    oracle = _oracle_params(seed=13)
    inj = FaultInjector(seed=13).preempt_at_step(4, rank=1)
    cfg = _cfg(seed=13, world=2, checkpoint_dir=str(tmp_path))
    sup = TrainSupervisor(cfg, injector=inj, world_schedule=[2])
    rep = sup.run()
    assert rep["preempted"] and rep["preempt_drains"] == 1
    drained_at = rep["final_step"]
    assert drained_at == 4  # the agreement lands at the SAME boundary
    drains = _named(events, "train_preempt_drain")
    assert len(drains) == 1 and drains[0]["step"] == drained_at
    assert drains[0]["seconds"] > 0  # timed: the ledger charges it
    assert "train_preempt_drain" in rep["goodput"]["lost_by_cause"]
    # ONE final checkpoint at the drain step, atomically committed
    mgr = ShardedCheckpointManager(str(tmp_path),
                                   coordinator=SingleProcessCoordinator())
    assert mgr.latest_step() == drained_at
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
    # exactly-once across drain + resume: the two jobs' ledgers
    # partition the step indices
    assert rep["goodput"]["steps"] == drained_at + 1
    sup2 = TrainSupervisor(cfg, world_schedule=[2])
    rep2 = sup2.run()
    assert not rep2["preempted"] and rep2["final_step"] == 9
    assert rep2["goodput"]["steps"] == 10 - (drained_at + 1)
    _assert_trees_equal(sup2.params(), oracle)


# ------------------------------------------------ crash mid-commit

def test_crash_mid_checkpoint_save_keeps_previous_commit(tmp_path):
    """A death on the first write into a checkpoint's .tmp staging leaves
    the previous committed step fully restorable (nothing half-written is
    ever visible), and the recovered run finishes bit-identical."""
    oracle = _oracle_params(seed=14)
    inj = FaultInjector(seed=14).crash_during_checkpoint_save(6)
    cfg = _cfg(seed=14, checkpoint_dir=str(tmp_path), save_every=2)
    trainer = Trainer(cfg, injector=inj)
    with pytest.raises(SimulatedCrash):
        trainer.run()
    trainer.close()
    # the crashed step 6 never committed; step 4's commit is intact
    mgr = ShardedCheckpointManager(str(tmp_path),
                                   coordinator=SingleProcessCoordinator())
    assert mgr.latest_step() == 4
    # recovery: a fresh attempt restores step 4, replays, and the
    # re-save of step 6 (schedule consumed) commits cleanly
    trainer2 = Trainer(cfg, injector=inj)
    rep = trainer2.run()
    try:
        assert rep["restored_from"] == 4
        assert rep["final_step"] == 9
        _assert_trees_equal(trainer2.params, oracle)
    finally:
        trainer2.close()
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_restart_budget_exhaustion_preserves_last_commit(tmp_path):
    """A fault that outlives the restart budget propagates (the job
    fails loudly) — and the last committed checkpoint is still the
    restore target, not a torn write."""
    inj = FaultInjector(seed=15).crash_on_train_step(5, times=10)
    cfg = _cfg(seed=15, checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj, max_restarts=1,
                          backoff_s=0.01)
    with pytest.raises(SimulatedCrash):
        sup.run()
    assert sup.restarts == 1
    mgr = ShardedCheckpointManager(str(tmp_path),
                                   coordinator=SingleProcessCoordinator())
    assert mgr.latest_step() == 4  # steps 0..4 ran; 4 was the last save
    assert mgr.restore_latest(Trainer(cfg)._tree(0)) is not None


# ------------------------------------------------ elastic restarts

def test_elastic_2_1_2_restore_bit_exact(tmp_path, events):
    """Acceptance: drain at world 2, resume at world 1, finish back at
    world 2 — every leg restores the same sharded checkpoint at a
    different data-parallel world size, publishes train_elastic_resized,
    and the final params are bit-identical to the uninterrupted run."""
    oracle = _oracle_params(seed=16)
    inj = (FaultInjector(seed=16)
           .preempt_at_step(3, rank=1)
           .preempt_at_step(6, rank=0))
    cfg = _cfg(seed=16, world=2, checkpoint_dir=str(tmp_path))
    sup = TrainSupervisor(cfg, injector=inj, world_schedule=[2, 1, 2])
    rep = sup.run()
    assert not rep["preempted"] and rep["final_step"] == 9
    assert rep["preempt_drains"] == 2
    assert rep["worlds"] == [2, 1, 2]
    _assert_trees_equal(sup.params(), oracle)
    resizes = [(e["from_world"], e["to_world"])
               for e in _named(events, "train_elastic_resized")]
    assert (2, 1) in resizes and (1, 2) in resizes
    # exactly-once accounting spans all three legs (one supervisor ledger)
    assert rep["goodput"]["steps"] == 10
    assert rep["goodput"]["skipped_steps"] == 0


# ------------------------------------------------ zero recompiles

def test_same_topology_restart_adds_zero_recompiles(tmp_path, events):
    """A supervisor warm restart reuses every compiled artifact: across a
    crash + restart + replay, each jitted step-path function (per-shard
    grads, post-exchange apply, ResilientStep post) traces exactly once,
    replayed steps charge train_replay (never productive twice), and the
    result is bit-identical."""
    oracle = _oracle_params(seed=17)
    inj = FaultInjector(seed=17).crash_on_train_step(6)
    cfg = _cfg(seed=17, checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj, max_restarts=2,
                          backoff_s=0.01)
    rep = sup.run()
    assert rep["restarts"] == 1 and rep["final_step"] == 9
    counts = sup.trace_counts()
    assert counts == {"shard_grads": 1, "apply": 1, "post": 1}, counts
    _assert_trees_equal(sup.params(), oracle)
    # rollback to step 4's commit replays 5 before reaching the crash
    # point — accounted as train_replay, productive steps exactly-once
    assert rep["steps_retried"] == 1
    assert len(_named(events, "train_step_replayed")) == 1
    assert rep["goodput"]["steps"] == 10
    assert rep["goodput"]["lost_by_cause"]["train_replay"] > 0
    assert len(_named(events, "train_restart")) == 1


# ------------------------------------------------ THE chaos smoke

def test_chaos_schedule_bit_identical_and_exactly_once(tmp_path, events):
    """Acceptance: one seeded schedule mixing coordinated preemption,
    elastic resize (2 -> 1 -> 2), a fatal mid-step crash, and a death
    mid-checkpoint-commit completes with (a) bit-identical final params
    vs the uninterrupted oracle, (b) exactly-once step accounting in the
    goodput ledger, (c) zero recompiles on the same-topology restarts."""
    steps = 12
    oracle = _oracle_params(seed=18, steps=steps)
    inj = (FaultInjector(seed=18)
           .preempt_at_step(3, rank=1)       # drain -> resize 2 -> 1
           .preempt_at_step(7, rank=0)       # drain -> resize 1 -> 2
           .crash_on_train_step(9)           # warm restart, same topology
           .crash_during_checkpoint_save(8))  # death mid-commit
    cfg = _cfg(seed=18, steps=steps, world=2,
               checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj, max_restarts=3,
                          backoff_s=0.01, world_schedule=[2, 1, 2])
    rep = sup.run()
    assert not rep["preempted"] and rep["final_step"] == steps - 1
    assert rep["preempt_drains"] == 2
    assert rep["restarts"] == 2  # crash-step + crash-save, both survived
    # (a) bit-identical to the uninterrupted run
    _assert_trees_equal(sup.params(), oracle)
    # (b) exactly-once: every step index productive once; replays ride
    # the train_replay cause, never the productive count
    good = rep["goodput"]
    assert good["steps"] == steps and good["skipped_steps"] == 0
    assert rep["steps_retried"] >= 1
    assert good["lost_by_cause"]["train_replay"] > 0
    assert good["events"]["train_preempt_drain"] == 2
    assert good["events"]["train_restart"] == 2
    # (c) zero recompiles: the step-path functions traced once for the
    # ENTIRE job — restarts and resizes reused every executable (post is
    # per-trainer: one trace per (world, rank=0..n) trainer, never more)
    counts = sup.trace_counts()
    assert counts["shard_grads"] == 1 and counts["apply"] == 1, counts
    n_trainers = len(sup._trainers)
    assert counts["post"] == n_trainers, (counts, n_trainers)
    # every checkpoint on disk is a committed one (no torn staging)
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


# ------------------------------------------------ overflow storms

def test_overflow_burst_replays_bit_exact_across_restart(tmp_path):
    """Scaler state rides the checkpoint: a NaN burst (skip-on-overflow +
    backoff) followed by a crash restart replays the identical stream —
    final params bit-identical to the same burst without the crash."""
    cfg_plain = _cfg(seed=19)
    inj_a = FaultInjector(seed=19).nan_burst(3, 2)
    ref = Trainer(cfg_plain, injector=inj_a)
    rep_ref = ref.run()
    assert rep_ref["skipped_steps"] == 2
    burst_params = ref.params
    ref.close()

    inj_b = (FaultInjector(seed=19).nan_burst(3, 2)
             .crash_on_train_step(7))
    cfg = _cfg(seed=19, checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj_b, max_restarts=1,
                          backoff_s=0.01)
    rep = sup.run()
    assert rep["restarts"] == 1
    assert rep["skipped_steps"] == 2
    assert rep["goodput"]["skipped_steps"] == 2
    _assert_trees_equal(sup.params(), burst_params)


# ------------------------------------------------ watchdog + registry

def test_watchdog_surfaces_straggler_rank(events):
    """A straggling rank stalls its peers inside the gradient exchange:
    the collective watchdog turns the silent wait into a
    collective_stall event naming the exchange."""
    inj = FaultInjector(seed=20).straggler_rank(1, delay_s=0.4, at_step=2)
    cfg = _cfg(seed=20, steps=4, world=2, watchdog_timeout_s=0.05)
    sup = TrainSupervisor(cfg, injector=inj)
    rep = sup.run()
    assert rep["final_step"] == 3
    stalls = _named(events, "collective_stall")
    assert any(e["name"].startswith("train_allgather") for e in stalls)


def test_metrics_registry_seam_counts_training_ranks(tmp_path):
    """Telemetry(registry=...) is the serving-grade metrics seam: a
    training run lands step counters + the step-seconds histogram in a
    mergeable registry exactly like a serving rank would."""
    reg = MetricsRegistry()
    sup = TrainSupervisor(_cfg(seed=21, steps=5), registry=reg)
    rep = sup.run()
    assert rep["final_step"] == 4
    snap = reg.snapshot()
    series = snap["metrics"]
    assert series["train_steps_total"]["series"][0]["value"] == 5
    hist = series["train_step_seconds"]["series"][0]
    assert hist["count"] == 5


def test_supervisor_status_table_tracks_rank_progress():
    sup = TrainSupervisor(_cfg(seed=22, steps=4, world=2))
    rep = sup.run()
    assert rep["final_step"] == 3
    status = sup.status()
    assert set(status) == {0, 1}
    assert all(v["step"] == 3 for v in status.values())


# ------------------------------------------------ config + CLI matrix

def test_config_validation_refuses_bad_geometry():
    with pytest.raises(ValueError, match="divide grad_shards"):
        TrainConfig(world=3, grad_shards=4).validate()
    with pytest.raises(ValueError, match="divide batch"):
        TrainConfig(batch=6, grad_shards=4).validate()
    with pytest.raises(ValueError, match="needs checkpoint_dir"):
        TrainConfig(save_every=2).validate()
    with pytest.raises(ValueError, match="sharded_checkpoint"):
        TrainConfig(world=2, grad_shards=2, checkpoint_dir="/x",
                    sharded_checkpoint=False).validate()
    with pytest.raises(ValueError, match="amp"):
        TrainConfig(amp="fp8").validate()


@pytest.mark.parametrize("argv,fragment", [
    (["--elastic", "2:1", "--grad-shards", "2"], "--checkpoint-dir"),
    (["--elastic", "2:1", "--grad-shards", "2", "--world", "2",
      "--checkpoint-dir", "/tmp/x"], "replaces --world"),
    (["--chaos", "crash-step:3", "--max-restarts", "0",
      "--checkpoint-dir", "/tmp/x"], "restart budget"),
    (["--chaos", "crash-step:3"], "--checkpoint-dir"),
    (["--chaos", "crash-step:banana", "--checkpoint-dir", "/tmp/x"],
     "malformed"),
    (["--steps", "4", "--chaos", "preempt:9",
      "--checkpoint-dir", "/tmp/x"], "never fire"),
    (["--chaos", "explode:3", "--checkpoint-dir", "/tmp/x"],
     "expected crash-step"),
    (["--steps", "24", "--save-every", "4", "--checkpoint-dir",
      "/tmp/x", "--chaos", "crash-save:9"], "never saved"),
    (["--world", "3", "--grad-shards", "4"], "divide"),
    (["--grad-shards", "3", "--batch", "8"], "divide"),
    (["--save-every", "2"], "checkpoint_dir"),
    (["--steps", "0"], ">= 1"),
    (["--watchdog-timeout", "0"], "> 0"),
    (["--elastic", "2:x", "--checkpoint-dir", "/tmp/x"],
     "colon-separated"),
])
def test_train_cli_exit2_usage_matrix(argv, fragment, capsys):
    """Contradictory or inert flag combinations refuse loudly (exit 2)
    before any params are built or anything compiles — the serve/fleet
    CLI precedent."""
    rc = train_cli_main(argv)
    assert rc == 2
    err = capsys.readouterr().err
    assert fragment in err, err


def test_train_cli_chaos_smoke_end_to_end(tmp_path, capsys):
    """The CLI happy path: a chaos schedule (crash + preempt/relaunch)
    under the supervisor, clean exit 0, and a JSON job report whose
    counters reconcile."""
    rc = train_cli_main([
        "--steps", "8", "--batch", "8", "--seq", "10", "--vocab", "64",
        "--hidden", "16", "--grad-shards", "2",
        "--checkpoint-dir", str(tmp_path), "--save-every", "2",
        "--max-restarts", "2", "--elastic", "1:1",
        "--chaos", "crash-step:3,preempt:5"])
    assert rc == 0
    out = capsys.readouterr().out
    report = json.loads(out.strip().splitlines()[-1])
    assert report["final_step"] == 7 and not report["preempted"]
    assert report["restarts"] == 1 and report["preempt_drains"] == 1
    assert report["goodput"]["steps"] == 8  # exactly-once via the CLI too
    # the report names what ran it: the harness's virtual CPU devices
    assert report["device"] == {
        "platform": "cpu", "device_kind": "cpu",
        "device_count": len(jax.devices()), "interpret_mode": True}


# ------------------------------------------------ bench + gate wiring

def test_bench_train_chaos_mode_and_gate_direction(tmp_path, capsys,
                                                   monkeypatch):
    """`apex-tpu-bench --train-chaos` emits a suite entry whose
    resilience counters the regression gate reads as lower-is-better —
    a 0 -> N restart storm gates as a regression, never a win — with
    trainer workload provenance nested (never lifted into the gated
    metrics)."""
    import sys as _sys

    import apex_tpu.bench_cli as bench_cli

    sys_path = os.path.join(ROOT, "tools")
    if sys_path not in _sys.path:
        _sys.path.insert(0, sys_path)
    import check_regression

    monkeypatch.setattr(_sys, "argv",
                        ["apex-tpu-bench", "--train-chaos", "--steps",
                         "6"])
    bench_cli.main()
    out = capsys.readouterr().out
    suite = json.loads(out[out.index("{"):])
    entry = suite["train_chaos"]
    assert entry["unit"] == "steps_per_s" and entry["value"] > 0
    for key in ("restarts", "preempt_drains", "steps_retried",
                "step_recompiles"):
        assert key in entry
        assert check_regression.lower_is_better(f"train_chaos.{key}")
    assert entry["step_recompiles"] == 1  # the zero-recompile contract
    # provenance: world/parallelism/amp nested under workload — config,
    # not a gated metric
    wl = entry["workload"]
    assert {"world", "grad_shards", "amp_dtype"} <= set(wl)
    metrics = check_regression.metrics_from_suite(suite)
    assert "train_chaos.workload" not in metrics
    assert "train_chaos.restarts" in metrics
    # a healthy 0-restart baseline vs this chaos capture: the counters
    # gate as regressions off the zero baseline (PR-8 precedent)
    baseline = dict(metrics)
    baseline["train_chaos.restarts"] = (0.0, None)
    results, _ = check_regression.compare(metrics, baseline, 0.1)
    row = {r["metric"]: r for r in results}["train_chaos.restarts"]
    assert row["direction"] == "lower" and row["regressed"]


# ------------------------------------------------ slow chaos sweep

@pytest.mark.slow
@pytest.mark.parametrize("seed", [31, 32, 33])
def test_chaos_sweep_seeded_schedules(tmp_path, seed):
    """Sweep: per-seed schedules mixing every trainer fault; each run
    must end bit-identical to its own uninterrupted oracle with
    exactly-once accounting."""
    steps = 12
    oracle = _oracle_params(seed=seed, steps=steps)
    inj = (FaultInjector(seed=seed)
           .preempt_at_step(2 + seed % 3, rank=seed % 2)
           .crash_on_train_step(6 + seed % 2)
           .crash_during_checkpoint_save(8)
           .nan_burst(4, 1))
    oracle_inj = FaultInjector(seed=seed).nan_burst(4, 1)
    ref = Trainer(_cfg(seed=seed, steps=steps), injector=oracle_inj)
    ref.run()
    oracle = ref.params
    ref.close()
    cfg = _cfg(seed=seed, steps=steps, world=2,
               checkpoint_dir=str(tmp_path), save_every=2)
    sup = TrainSupervisor(cfg, injector=inj, max_restarts=3,
                          backoff_s=0.01, world_schedule=[2, 1])
    rep = sup.run()
    assert not rep["preempted"] and rep["final_step"] == steps - 1
    _assert_trees_equal(sup.params(), oracle)
    assert rep["goodput"]["steps"] == steps
