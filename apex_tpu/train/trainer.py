"""``Trainer`` — one elastic, preemption-tolerant data-parallel train loop.

Composes what the repo built but never unified: ``ResilientStep`` +
``DynamicGradScaler`` (AMP with overflow-storm guard rails),
``ShardedCheckpointManager`` (atomic commit, elastic restore),
``PreemptionGuard`` (coordinated save-and-stop), ``CollectiveWatchdog``
(stuck gradient exchanges become events, not hangs), and
``Telemetry(registry=...)`` (training ranks snapshot/merge/SLO-gate
exactly like serving ranks) — behind one :class:`~apex_tpu.train.config.
TrainConfig`.

**The determinism contract** every robustness claim rides on:

- batches are a pure function of ``(config.seed, step)``;
- the global batch is cut into ``grad_shards`` fixed micro-shards, rank
  ``r`` of ``world`` computes shards ``{i : i % world == r}`` with ONE
  compiled per-shard function (shapes are world-independent), and the
  step gradient is the shard gradients summed in **shard-index order** —
  whatever world size computed them. Float addition never reassociates
  across a resize, so a run restored at a different data-parallel degree
  continues **bit-exactly**, and the compiled executables (keyed on the
  workload, not the world) are all reused;
- optimizer moments, scaler state, and the step counter ride the
  checkpoint, so a crash rollback replays the identical tail.

**Threading/collective contract**: with a world > 1 every rank must call
``run()`` with the same config (the ``ThreadProcessGroup`` harness on CPU
tier-1, ``JaxCoordinator`` on a real pod). The per-step gradient exchange
and the every-step ``guard.should_stop()`` poll are collectives — all
ranks reach them at the same cadence by construction of the loop.

**Accounting contract** (rank 0 only — the fake-multihost ranks share one
process bus): each step index lands in the goodput ledger as productive
exactly once per job (the supervisor threads its high-water mark through
restarts); a step re-executed after a crash rollback publishes
``train_step_replayed`` with its wall seconds (ledger cause
``train_replay``) instead. A coordinated preemption finishes the in-flight
step, commits one final checkpoint atomically, publishes
``train_preempt_drain`` with the drain seconds, and returns clean.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.amp.grad_scaler import DynamicGradScaler, ScalerState
from apex_tpu.monitor.metrics import collect_metrics
from apex_tpu.monitor.telemetry import Telemetry
from apex_tpu.optimizers.functional import adam_update
from apex_tpu.resilience.checkpoint_manager import CheckpointManager
from apex_tpu.resilience.distributed import (CollectiveWatchdog,
                                             ShardedCheckpointManager,
                                             SingleProcessCoordinator)
from apex_tpu.resilience.preemption import PreemptionGuard
from apex_tpu.resilience.step import ResilientStep
from apex_tpu.resilience.topology import layout_block
from apex_tpu.train.config import TrainConfig
from apex_tpu.utils.logging import is_rank_zero, publish_event


# --------------------------------------------------------------------------
# The built-in tiny-LM workload (pure functions of the config — the
# hand-rolled-loop bit-equality oracle in tests reuses exactly these)
# --------------------------------------------------------------------------

def make_scaler(config: TrainConfig) -> DynamicGradScaler:
    """The config's AMP policy as a scaler (``amp="off"`` disables it —
    unscaled bf16-first semantics; the floor is ResilientStep's job)."""
    return DynamicGradScaler(init_scale=config.init_scale,
                             enabled=config.amp != "off")


def tiny_lm_params(config: TrainConfig) -> Dict[str, jax.Array]:
    """Seeded fp32 init for the built-in LM (embedding → tanh MLP →
    LM head). Pure function of ``config.seed``."""
    k = jax.random.split(jax.random.PRNGKey(config.seed), 3)
    return {
        "emb": jax.random.normal(k[0], (config.vocab, config.hidden),
                                 jnp.float32) * 0.02,
        "w1": jax.random.normal(k[1], (config.hidden, config.hidden),
                                jnp.float32) * 0.1,
        "b1": jnp.zeros((config.hidden,), jnp.float32),
        "head": jax.random.normal(k[2], (config.hidden, config.vocab),
                                  jnp.float32) * 0.02,
    }


def tiny_lm_batch(config: TrainConfig, step: int) -> jax.Array:
    """The global token batch for ``step`` — a pure function of
    ``(config.seed, step)``, so replays and elastic resizes see the
    identical data stream."""
    key = jax.random.fold_in(jax.random.PRNGKey(config.seed + 0x5EED),
                             step)
    return jax.random.randint(key, (config.batch, config.seq), 0,
                              config.vocab, jnp.int32)


def _make_apply(scaler: DynamicGradScaler, counts: Dict[str, int],
                grad_shards: int, lr: float):
    """The jitted post-exchange step: mean the canonical gradient sum,
    fused unscale + grad-norm + overflow probe, fused Adam, in-graph
    metrics. ``counts["apply"]`` bumps only when jax TRACES it — the
    zero-recompile-restart proof reads it."""
    inv = 1.0 / float(grad_shards)

    def apply(state3, sstate, gsum, loss_sum, t):
        counts["apply"] += 1
        params, m, v = state3
        grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
        grads, grad_norm, found_inf = scaler.unscale_and_norm(grads,
                                                              sstate)
        new_p, m2, v2 = adam_update(params, grads, m, v, step=t + 1,
                                    lr=lr, found_inf=found_inf)
        loss = (loss_sum * inv).astype(jnp.float32)
        # amp off: report loss_scale=1.0 (the sstate scale is inert),
        # keeping the emitted row schema stable across amp on/off
        scale_kw = ({"scaler_state": sstate} if scaler.enabled
                    else {"loss_scale": 1.0})
        tm = collect_metrics(params=new_p, grad_norm=grad_norm,
                             found_inf=found_inf, loss=loss, **scale_kw)
        return (new_p, m2, v2), found_inf, loss, tm

    return jax.jit(apply)


def _make_shard_grads(loss_fn: Callable, scaler: DynamicGradScaler,
                      counts: Dict[str, int]):
    """Jitted per-shard gradient function: scaled-loss grads + the
    unscaled loss as aux. ``loss_fn(params, tokens) -> scalar loss``."""

    def shard_grads(params, sstate, tokens):
        counts["shard_grads"] += 1

        def scaled(p):
            loss = loss_fn(p, tokens)
            return scaler.scale(loss, sstate), loss

        (_, loss), grads = jax.value_and_grad(scaled,
                                              has_aux=True)(params)
        return grads, loss

    return jax.jit(shard_grads)


def _tiny_lm_loss(params, tokens):
    x = params["emb"][tokens[:, :-1]]
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    logp = jax.nn.log_softmax((h @ params["head"]).astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)


# --------------------------------------------------------------------------
# Tensor-parallel gradients (the tp axis of TrainConfig)
#
# The mechanism is gather-compute-slice: params live tp-sharded on the
# PR-15 serving mesh in their RAW axis order (no qkv permutation — the
# logical checkpoint values stay dense-identical), the shard_map body
# all_gathers each sharded leaf by pure concatenation (tiled=True —
# exact reconstruction, no float combine), runs the PRISTINE single-chip
# value_and_grad of the unmodified loss replicated on every rank, and
# slices each sharded leaf's gradient back to its local chunk. No AD
# transpose ever crosses the shard_map boundary and no float add ever
# crosses a rank, so tp=N gradients — and therefore every update — are
# bit-identical to tp=1 (tier-1 asserts through GPT-2 + flash attention).
# --------------------------------------------------------------------------

def builtin_tp_specs() -> Dict[str, P]:
    """PartitionSpecs for the built-in tiny-LM tree: shard the hidden
    axis (requires ``tp | hidden`` — config.validate refuses otherwise);
    a custom workload passes its own spec tree via ``Trainer(tp_spec=)``
    (the GPT-2 one is :func:`apex_tpu.serve.tp.tp_param_specs`)."""
    return {"emb": P(None, "tp"), "w1": P(None, "tp"), "b1": P("tp"),
            "head": P("tp", None)}


def _spec_axis(spec: P) -> Optional[int]:
    for ax, name in enumerate(spec):
        if name == "tp":
            return ax
    return None


def _tp_tree_map(fn, tree, specs):
    return jax.tree_util.tree_map(fn, tree, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def _gather_tree(tree, specs):
    def g(leaf, spec):
        ax = _spec_axis(spec)
        if ax is None:
            return leaf
        return jax.lax.all_gather(leaf, "tp", axis=ax, tiled=True)
    return _tp_tree_map(g, tree, specs)


def _slice_tree(tree, specs, tp: int):
    r = jax.lax.axis_index("tp")

    def s(leaf, spec):
        ax = _spec_axis(spec)
        if ax is None:
            return leaf
        chunk = leaf.shape[ax] // tp
        return jax.lax.dynamic_slice_in_dim(leaf, r * chunk, chunk,
                                            axis=ax)
    return _tp_tree_map(s, tree, specs)


def _make_shard_grads_tp(loss_fn: Callable, scaler: DynamicGradScaler,
                         counts: Dict[str, int], mesh, specs):
    """The tp>1 twin of :func:`_make_shard_grads` — same signature, same
    outputs (sharded grads + replicated unscaled loss), gather-compute-
    slice body under ``shard_map``. The trace counter bumps in the OUTER
    jit wrapper: the shard_map body may legitimately trace more than once
    per executable, so counting there would break the zero-recompile
    proofs."""
    tp = mesh.devices.size
    sstate_spec = jax.tree_util.tree_map(lambda _: P(), scaler.init())

    def body(params_loc, sstate, tokens):
        full = _gather_tree(params_loc, specs)

        def scaled(p):
            loss = loss_fn(p, tokens)
            return scaler.scale(loss, sstate), loss

        (_, loss), grads = jax.value_and_grad(scaled, has_aux=True)(full)
        return _slice_tree(grads, specs, tp), loss

    sm = shard_map(body, mesh=mesh,
                   in_specs=(specs, sstate_spec, P()),
                   out_specs=(specs, P()), check_vma=False)

    def shard_grads(params, sstate, tokens):
        counts["shard_grads"] += 1
        return sm(params, sstate, tokens)

    return jax.jit(shard_grads)


def _place_tree(tree, mesh, specs):
    """Commit a tree onto the tp mesh per its specs (replicated leaves
    get P() so every leaf lands device-committed — eager ops and
    zeros_like then preserve the placement)."""
    def p(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return _tp_tree_map(p, tree, specs)


@functools.lru_cache(maxsize=None)
def _builtin_fns(key):
    """Compiled step functions for the built-in workload, cached on the
    config's :meth:`~TrainConfig.static_key` — a restarted (or
    elastically resized) job with the same workload gets the SAME
    callables back, so jax's jit cache serves every dispatch without a
    retrace. The returned ``counts`` dict is the cache entry's lifetime
    trace counter; the mesh/specs pair is ``(None, None)`` at tp=1 and
    the (cached, shared) serving mesh + builtin spec tree at tp>1."""
    (_shard_batch, _seq, _vocab, _hidden, grad_shards, lr, amp,
     init_scale, _floor, _seed, tp) = key
    counts = {"shard_grads": 0, "apply": 0}
    scaler = DynamicGradScaler(init_scale=init_scale,
                               enabled=amp != "off")
    if tp > 1:
        from apex_tpu.serve.tp import serving_mesh
        mesh, specs = serving_mesh(tp), builtin_tp_specs()
        grads_fn = _make_shard_grads_tp(_tiny_lm_loss, scaler, counts,
                                        mesh, specs)
    else:
        mesh = specs = None
        grads_fn = _make_shard_grads(_tiny_lm_loss, scaler, counts)
    return (grads_fn, _make_apply(scaler, counts, grad_shards, lr),
            counts, mesh, specs)


_CUSTOM_FNS: Dict[Any, tuple] = {}


def _custom_fns(loss_fn, key, tp_spec):
    """The custom-workload twin of :func:`_builtin_fns`: compiled step
    functions cached on ``(loss_fn, static_key, tp_spec)``. The
    supervisor rebuilds a Trainer per restart / elastic-resize leg with
    the SAME loss_fn object, and this cache is what keeps those legs on
    one compiled callable (zero recompiles) instead of re-jitting the
    model's grad per leg."""
    if tp_spec is None:
        token = None
    else:
        leaves, treedef = jax.tree_util.tree_flatten(
            tp_spec, is_leaf=lambda x: isinstance(x, P))
        token = (treedef, tuple(leaves))
    cache_key = (loss_fn, key, token)
    hit = _CUSTOM_FNS.get(cache_key)
    if hit is not None:
        return hit
    (_shard_batch, _seq, _vocab, _hidden, grad_shards, lr, amp,
     init_scale, _floor, _seed, tp) = key
    counts = {"shard_grads": 0, "apply": 0}
    scaler = DynamicGradScaler(init_scale=init_scale,
                               enabled=amp != "off")
    if tp > 1:
        from apex_tpu.serve.tp import serving_mesh
        mesh, specs = serving_mesh(tp), tp_spec
        grads_fn = _make_shard_grads_tp(loss_fn, scaler, counts, mesh,
                                        specs)
    else:
        mesh = specs = None
        grads_fn = _make_shard_grads(loss_fn, scaler, counts)
    out = (grads_fn, _make_apply(scaler, counts, grad_shards, lr),
           counts, mesh, specs)
    _CUSTOM_FNS[cache_key] = out
    return out


# --------------------------------------------------------------------------
# Trainer
# --------------------------------------------------------------------------

class Trainer:
    """One rank's view of the elastic production train loop (see module
    docstring for the determinism / collective / accounting contracts).

    Custom models plug in via ``loss_fn(params, tokens) -> scalar``,
    ``init_params`` (a pytree), and ``batch_fn(step) -> tokens`` — the
    checkpointing, preemption, chaos hooks, and accounting are identical
    (``examples/lm_pretrain`` is the worked example). ``registry`` is the
    serving-grade metrics seam: pass a
    :class:`~apex_tpu.monitor.export.MetricsRegistry` and per-step
    counters/histograms land in a mergeable snapshot exactly like a
    serving rank's.
    """

    def __init__(self, config: TrainConfig, *, coordinator=None,
                 injector=None, loss_fn: Optional[Callable] = None,
                 init_params: Any = None,
                 batch_fn: Optional[Callable[[int], Any]] = None,
                 tp_spec: Any = None,
                 registry=None, hwm: int = 0, telemetry=None,
                 install_signal_handlers: bool = False):
        self.config = config.validate()
        self.coord = (coordinator if coordinator is not None
                      else SingleProcessCoordinator())
        self.rank = self.coord.process_index
        self.world = self.coord.process_count
        if config.grad_shards % self.world:
            raise ValueError(
                f"coordinator world {self.world} must divide grad_shards "
                f"{config.grad_shards}")
        self.G = config.grad_shards
        self.injector = injector
        self._install_signals = install_signal_handlers
        # BOTH gates: the coordinator's fake rank (thread harness — the
        # real process is jax rank 0 there) AND the real jax process
        # index, so a multi-host run without a coordinator (N processes
        # each seeing a SingleProcessCoordinator rank 0) still emits one
        # telemetry stream / one banner set, not N
        self._rank0 = self.rank == 0 and is_rank_zero()

        self.scaler = make_scaler(config)
        self.mesh = self.tp_spec = None
        if loss_fn is not None:
            if init_params is None or batch_fn is None:
                raise ValueError(
                    "a custom loss_fn needs init_params and batch_fn")
            if config.tp > 1 and tp_spec is None:
                raise ValueError(
                    "tp > 1 with a custom loss_fn needs tp_spec (a "
                    "PartitionSpec tree matching init_params; GPT-2 "
                    "uses serve.tp.tp_param_specs)")
            (self._shard_grads, self._apply, self._counts, self.mesh,
             self.tp_spec) = _custom_fns(
                 loss_fn, config.static_key(),
                 tp_spec if config.tp > 1 else None)
            self.params = jax.tree_util.tree_map(jnp.asarray, init_params)
            self._batch_fn = batch_fn
        else:
            (self._shard_grads, self._apply, self._counts, self.mesh,
             self.tp_spec) = _builtin_fns(config.static_key())
            self.params = tiny_lm_params(config)
            self._batch_fn = lambda t: tiny_lm_batch(config, t)
        if self.mesh is not None:
            # commit params onto the tp mesh; moments inherit via
            # zeros_like, grads come back sharded from the shard_map, and
            # _apply's elementwise Adam preserves the placement — so the
            # whole state stays resident in the tp layout step over step
            self.params = _place_tree(self.params, self.mesh,
                                      self.tp_spec)
        zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # noqa: E731
        self.m = jax.tree_util.tree_map(zeros, self.params)
        self.v = jax.tree_util.tree_map(zeros, self.params)
        self.sstate: ScalerState = self._on_mesh(self.scaler.init())

        self._next_step = 0           # the step not yet run
        self.hwm = int(hwm)           # job-scope exactly-once watermark
        self.steps_retried = 0        # replayed executions (rank 0)

        self.watchdog: Optional[CollectiveWatchdog] = None
        if config.watchdog_timeout_s:
            self.watchdog = CollectiveWatchdog(
                timeout_s=config.watchdog_timeout_s,
                coordinator=self.coord)
        self.manager = None
        if config.checkpoint_dir:
            kw: Dict[str, Any] = {"max_to_keep": config.max_to_keep}
            if injector is not None:
                kw["fs"] = injector.filesystem()
            if config.sharded_checkpoint:
                self.manager = ShardedCheckpointManager(
                    config.checkpoint_dir, coordinator=self.coord,
                    watchdog=self.watchdog, **kw)
            else:
                self.manager = CheckpointManager(config.checkpoint_dir,
                                                 **kw)
        # rank 0 owns telemetry + the goodput ledger (the fake-multihost
        # ranks share ONE process bus — a per-rank sink would multiply
        # every record); other ranks compute, rank 0 accounts. A
        # supervisor passes ONE shared sink so the job's accounting spans
        # restarts and elastic resizes (exactly-once needs one ledger).
        self.telemetry: Optional[Telemetry] = None
        self._owns_telemetry = False
        if self._rank0:
            if telemetry is not None:
                self.telemetry = telemetry
            else:
                self.telemetry = Telemetry(
                    config.telemetry_jsonl, rank_zero_only=False,
                    tokens_per_step=float(config.batch
                                          * (config.seq - 1)),
                    trace_jsonl=config.trace_jsonl, registry=registry)
                self._owns_telemetry = True
        # telemetry=None on purpose: the trainer does its own exactly-once
        # logging (ResilientStep would log every execution, replays
        # included); the in-graph metrics ride _apply's collect_metrics.
        # The tracer rides through: with config.trace_jsonl, rank 0's
        # steps emit the train_step/forward_backward/unscale span tree
        # (the hand-rolled lm_pretrain loop's tracing, preserved)
        self._tracer = (self.telemetry.tracer
                        if self.telemetry is not None else None)
        self._resilient = ResilientStep(
            self._apply, self.scaler,
            max_consecutive_overflows=config.max_consecutive_overflows,
            scale_floor=config.scale_floor, tracer=self._tracer)
        self.guard: Optional[PreemptionGuard] = None
        self._last_saved_step: Optional[int] = None

    # ---- lifecycle ------------------------------------------------------
    def rebind(self, coordinator) -> "Trainer":
        """A relaunched attempt re-rendezvouses: same trainer object (every
        compiled executable and the ResilientStep post-step survive — the
        zero-recompile same-topology-restart contract), fresh coordinator;
        the preemption guard is rebuilt per :meth:`run`."""
        if self.config.grad_shards % coordinator.process_count:
            raise ValueError(
                f"coordinator world {coordinator.process_count} must "
                f"divide grad_shards {self.config.grad_shards}")
        self.coord = coordinator
        self.rank = coordinator.process_index
        self.world = coordinator.process_count
        self._rank0 = self.rank == 0 and is_rank_zero()
        if self.manager is not None and hasattr(self.manager,
                                                "coordinator"):
            self.manager.coordinator = coordinator
        if self.watchdog is not None:
            self.watchdog.coordinator = coordinator
        return self

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._owns_telemetry and self.telemetry is not None:
            self.telemetry.close()

    def calibrate(self) -> "Trainer":
        """MFU calibration for rank 0's telemetry: the XLA cost model of
        one gradient shard, scaled by ``grad_shards`` (the step runs one
        such call per shard). Optional — it pays one analysis
        lower+compile of the shard function, which also bumps the trace
        counter once."""
        if self.telemetry is None:
            return self
        tokens = self._batch_fn(0)
        shard = tokens.reshape((self.G, tokens.shape[0] // self.G)
                               + tokens.shape[1:])[0]
        self.telemetry.calibrate(self._shard_grads, self.params,
                                 self.sstate, shard)
        if self.telemetry.flops_per_step:
            self.telemetry.flops_per_step *= self.G
        return self

    def trace_counts(self) -> Dict[str, int]:
        """Lifetime jax trace counts of the three step-path functions —
        flat across warm restarts and elastic resizes of the same
        workload (the tier-1 zero-recompile proofs read this)."""
        return {"shard_grads": self._counts["shard_grads"],
                "apply": self._counts["apply"],
                "post": self._resilient.post_traces}

    # ---- checkpoint tree ------------------------------------------------
    def _tree(self, step: int) -> Dict[str, Any]:
        r = self._resilient
        return {
            "params": self.params, "m": self.m, "v": self.v,
            "scaler": {"scale": self.sstate.scale,
                       "growth": self.sstate.growth_tracker,
                       "hyst": self.sstate.hysteresis_tracker},
            "meta": {"step": np.int64(step),
                     "world": np.int64(self.world),
                     "consec": np.int64(r.consecutive_overflows),
                     "skipped": np.int64(r.skipped_steps),
                     "degraded": np.int64(bool(r.degraded))},
        }

    def _save(self, step: int) -> Optional[str]:
        """Commit ``step`` (idempotent per step: the final/drain save
        after a cadence save of the same step — or a resumed
        already-complete run — must not re-stage or double-publish the
        commit; every rank derives the same decision, so the sharded
        barriers stay aligned)."""
        if step == self._last_saved_step:
            return None
        span = (self._tracer.span("checkpoint", step=step)
                if self._tracer is not None and self._tracer.enabled
                else contextlib.nullcontext())
        with span:
            path = self.manager.save(step, self._tree(step),
                                     layout=self._layout_block())
        self._last_saved_step = step
        if self._rank0:
            publish_event("train_checkpoint_commit", step=int(step),
                          path=path, world=self.world)
        return path

    def _layout_block(self) -> Dict[str, Any]:
        """The manifest ``layout`` block this topology stamps on every
        commit: which (dp world, grad_shards, tp) wrote the step. Values
        are stored in the raw dense format whatever the tp degree — tp
        shards are raw-axis chunks, so the logical tree is
        topology-portable by construction."""
        return layout_block(world=self.world, grad_shards=self.G,
                            tp=self.config.tp)

    def _restore(self) -> Optional[int]:
        out = self.manager.restore_latest(self._tree(0))
        if self._rank0:
            for q in getattr(self.manager, "last_quarantined", ()):
                publish_event("train_ckpt_quarantined", **q)
        if out is None:
            return None
        step, tree = out
        self.params, self.m, self.v = (tree["params"], tree["m"],
                                       tree["v"])
        # restored leaves come back committed to the restore target's
        # devices; params/m/v restore onto the tp mesh (the _tree(0)
        # template is mesh-placed), the scaler scalars are placed as a
        # fresh trainer's are
        sc = self._on_mesh(tree["scaler"])
        self.sstate = ScalerState(sc["scale"], sc["growth"], sc["hyst"])
        meta = tree["meta"]
        r = self._resilient
        r.consecutive_overflows = int(meta["consec"])
        r.skipped_steps = int(meta["skipped"])
        r.degraded = bool(int(meta["degraded"]))
        self._next_step = int(meta["step"]) + 1
        self._last_saved_step = int(meta["step"])  # it IS committed
        saved_world = int(meta["world"])
        if saved_world != self.world and self._rank0:
            publish_event("train_elastic_resized",
                          from_world=saved_world, to_world=self.world,
                          step=int(meta["step"]))
        # topology observability: the manifest's layout block names the
        # topology that WROTE the step. Restoring reassembles leaves
        # topology-independently and re-places them onto THIS config's
        # mesh (the automatic reshard) — when the written tp differs,
        # that crossing is counted, never silently absorbed.
        saved_layout = getattr(self.manager, "last_restored_layout",
                               None)
        if saved_layout and self._rank0:
            saved_tp = int(saved_layout.get("tp", 1))
            if saved_tp != self.config.tp:
                publish_event(
                    "train_topology_restored", step=int(meta["step"]),
                    from_tp=saved_tp, to_tp=self.config.tp,
                    from_world=int(saved_layout.get("world",
                                                    saved_world)),
                    to_world=self.world)
        return step

    def _on_mesh(self, tree):
        """``tree`` (the scaler's scalars) replicated on the tp mesh; as
        it is at tp=1. A fresh trainer's and a restored one's go through
        here, so the jitted step sees ONE argument type for the life of
        a job: an array's mesh is part of its type, and a scalar that is
        on the mesh only after a restore is a second trace of the whole
        model's gradient (and, committed to one device beside
        mesh-committed params, a refusal)."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    # ---- the step -------------------------------------------------------
    def _step(self, t: int):
        tokens = self._batch_fn(t)
        n = tokens.shape[0]
        if n % self.G:
            raise ValueError(
                f"batch_fn returned leading dim {n}, not divisible by "
                f"grad_shards {self.G}")
        shards = tokens.reshape((self.G, n // self.G) + tokens.shape[1:])
        parts = [(i, *self._shard_grads(self.params, self.sstate,
                                        shards[i]))
                 for i in range(self.rank, self.G, self.world)]
        if self.world > 1:
            watch = (self.watchdog.watch(f"train_allgather:{t}")
                     if self.watchdog is not None
                     else contextlib.nullcontext())
            with watch:
                gathered = self.coord.all_gather_object(parts)
            parts = [p for rank_parts in gathered for p in rank_parts]
        # canonical reduction: shard-index order, whatever rank computed
        # each shard — the float-add order (and therefore the update) is
        # identical at every world size
        parts.sort(key=lambda p: p[0])
        gsum = functools.reduce(
            lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
            (g for _, g, _ in parts))
        loss_sum = functools.reduce(jnp.add, (l for _, _, l in parts))
        if self.injector is not None and self.injector.grads_faulty(t):
            # deterministic fill (not the seeded poison_grads draw): every
            # rank and every replay of this step must agree
            gsum = jax.tree_util.tree_map(
                lambda g: jnp.full_like(g, jnp.nan), gsum)
        state3, self.sstate, found_inf, loss, tm = self._resilient(
            (self.params, self.m, self.v), self.sstate, gsum, loss_sum,
            jnp.int32(t))
        self.params, self.m, self.v = state3
        # the loop's ONE host sync — the skip flag it needs anyway
        return loss, tm, bool(found_inf)

    def _account(self, t: int, tm, skipped: bool, seconds: float) -> None:
        if not self._rank0:
            return
        if t >= self.hwm:
            self.telemetry.log_step(t, metrics=tm, skipped=skipped,
                                    step_ms=seconds * 1e3)
            self.hwm = t + 1
        else:
            # a crash rollback re-executed this step: real wall time spent
            # redoing discarded work — charged to train_replay, never
            # double-counted as a productive step
            self.steps_retried += 1
            publish_event("train_step_replayed", step=int(t),
                          seconds=round(seconds, 6))

    # ---- the run loop ---------------------------------------------------
    def run(self, *, on_step=None, on_resume=None, on_preempt=None,
            external_stop: Optional[Callable[[], bool]] = None,
            progress: Optional[Callable[[int, int], None]] = None
            ) -> Dict[str, Any]:
        """Run (or resume) to ``config.steps``; returns a report dict.

        ``on_step(step, loss)`` / ``on_resume(step)`` / ``on_preempt(step)``
        fire on rank 0 (``on_step`` costs one extra scalar fetch).
        ``external_stop()`` polled each step feeds the coordinated
        preemption agreement (the supervisor's signal bridge — thread
        ranks cannot install handlers). ``progress(rank, step)`` fires on
        every rank (the supervisor's live status feed).
        """
        cfg = self.config
        self.guard = PreemptionGuard(coordinator=self.coord)
        if self._install_signals:
            self.guard.install()
        restored = self._restore() if self.manager is not None else None
        if restored is not None and on_resume is not None and self._rank0:
            on_resume(restored)
        preempted = False
        try:
            while self._next_step < cfg.steps:
                t = self._next_step
                if self.injector is not None:
                    delay = self.injector.train_straggle_due(self.rank, t)
                    if delay:
                        time.sleep(delay)
                    if self.injector.train_preempt_due(self.rank, t):
                        self.guard.request_stop()
                if external_stop is not None and external_stop():
                    self.guard.request_stop()
                if self.injector is not None:
                    self.injector.maybe_crash_train(t, self.rank)
                t0 = time.perf_counter()
                loss, tm, skipped = self._step(t)
                self._account(t, tm, skipped,
                              time.perf_counter() - t0)
                if progress is not None:
                    progress(self.rank, t)
                if on_step is not None and self._rank0:
                    on_step(t, float(loss))
                self._next_step = t + 1
                if self.manager is not None and cfg.save_every \
                        and t % cfg.save_every == 0:
                    self._save(t)
                # the every-step preemption poll IS a collective in
                # coordinated mode: every rank flips at the same boundary
                if self.guard.should_stop():
                    preempted = True
                    break
            if preempted:
                # coordinated drain: the in-flight step finished above and
                # the sharded save's barriers drain the collectives; ONE
                # final checkpoint commits atomically, then a clean exit
                t0 = time.perf_counter()
                if self.manager is not None and self._next_step > 0:
                    self._save(self._next_step - 1)
                if self._rank0:
                    publish_event(
                        "train_preempt_drain",
                        seconds=round(time.perf_counter() - t0, 6),
                        step=self._next_step - 1, world=self.world,
                        signal=self.guard.received_signal)
                    if on_preempt is not None:
                        on_preempt(self._next_step - 1)
            elif self.manager is not None:
                self._save(cfg.steps - 1)  # the final commit
        finally:
            self.guard.restore()
        return {"rank": self.rank, "world": self.world,
                "final_step": self._next_step - 1,
                "preempted": preempted, "restored_from": restored,
                "hwm": self.hwm, "steps_retried": self.steps_retried,
                "skipped_steps": self._resilient.skipped_steps}
