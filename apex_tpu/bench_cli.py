"""Console entry point (``apex-tpu-bench``) — runs the repo benchmark suite.

Delegates to the repo-root bench.py when present (the driver's interface),
else runs the packaged headline benchmark inline.

``apex-tpu-bench --telemetry-jsonl PATH [--steps N]`` instead runs the
telemetry-instrumented train bench: a single-jit LM train step (amp dynamic
loss scaling + fused Adam) with in-graph :class:`TrainMetrics`, streamed
through :class:`apex_tpu.monitor.Telemetry` so every step lands in PATH as
``{step, loss, grad_norm, loss_scale, step_ms, tokens_per_s, mfu, ...}``.
Feed the JSONL to ``tools/check_regression.py`` against a committed
baseline to gate perf claims in CI (docs/observability.md).

``apex-tpu-bench --kernels fused_adam_1b,layer_norm [--emit-baseline
[PATH]]`` runs just that subset of the bench suite against the
already-selected backend (the per-kernel path of the perf gate,
docs/performance.md). With
``--emit-baseline`` the capture is written as a suite-format JSON
(default ``BENCH_BASELINE.json``) ready to commit and enforce with
``tools/check_regression.py CURRENT --suite BENCH_BASELINE.json`` —
refreshing the committed gate is one command.

``apex-tpu-bench --serve [--steps N]`` runs the serving micro-bench
(apex_tpu.serve continuous batching on the tiny fp32 GPT-2): decode
tokens/s, p50/p99 per-token latency, and TTFT as a ``serve_decode``
BENCH_SUITE entry — same ``--emit-baseline`` + check_regression suite
workflow as the kernel gate (docs/serving.md). ``--page-size``/
``--num-pages``/``--prefix-cache`` swap in the paged KV pool, and
``--prompt-len MIN:MAX`` + ``--shared-prefix N`` script the
mixed-length multi-tenant workload the pool's
``resident_tokens_per_hbm_byte`` / ``prefix_hit_rate`` capacity claims
are measured on (docs/serving.md "Paged KV pool and prefix caching").
``--replicas N`` runs the same workload over N thread-backed engine
replicas under the fleet controller (``--hedge-ms``/``--heartbeat-ms``
shape routing): the entry gains the fleet resilience counters
(``failovers``/``hedge_fired``/``replica_dead``/``migrations`` — all
lower-is-better, a 0→N failover storm gates as a regression) and the
workload provenance records replicas/hedge_ms/heartbeat_ms so fleet
counters are never gated across incomparable configs
(docs/serving.md "Fleet failover and draining"). ``--trace-jsonl`` (+
``--trace-sample``) arms cross-replica request journeys — the fleet
trace at PATH, one Chrome-trace per replica at PATH.rK, seeded head
sampling with tail capture — ``--flight-recorder`` arms per-replica
postmortems, and the live-metrics flags serve/commit the merged fleet
registry view; the entry stamps ``trace_promoted`` (lower-is-better)
plus traced/trace_sample workload provenance so traced and untraced
captures never gate against each other (docs/observability.md "Fleet
request journeys").
"""

from __future__ import annotations

import os
import runpy
import sys


def _inline_bench() -> None:
    """Packaged fallback: the headline fused-Adam benchmark at wheel-install
    scale (no repo checkout). Same metric semantics and timing methodology
    as bench.py: (rows, 128) native-tiled state (a 1-D arg would pay a
    multi-GB relayout copy at 1B params) and fori_loop+fetch timing via
    ``apex_tpu.utils.benchtime`` (per-dispatch wall clock is unreliable on
    remote/async runtimes)."""
    import json

    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_adam_kernel import LANE, fused_adam_flat
    from apex_tpu.utils.benchtime import measure_fetch_floor, timed_steps

    on_tpu = jax.default_backend() == "tpu"
    n = 999_999_488 if on_tpu else 1_048_576
    rows = n // LANE
    p = jax.random.normal(jax.random.PRNGKey(0), (rows, LANE),
                          jnp.bfloat16) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (rows, LANE), jnp.bfloat16)
    m = jnp.zeros((rows, LANE), jnp.float32)
    v = jnp.zeros((rows, LANE), jnp.float32)

    def step(i, st, g):
        p, m, v = st
        return tuple(fused_adam_flat(p, g, m, v, lr=1e-3, weight_decay=0.01,
                                     step=i + 1, inv_scale=1.0))

    ms = timed_steps(step, (p, m, v), iters=30 if on_tpu else 2,
                     consts=(g,), floor_s=measure_fetch_floor())
    ref_ms = n * 22 / (1555e9 * 0.85) * 1e3
    print(json.dumps({
        "metric": f"fused_adam_step_ms_at_{n // 1_000_000}M_params"
                  f"_bf16p_f32state",
        "value": round(ms, 3), "unit": "ms",
        "vs_baseline": round(ref_ms / ms, 3)}))


def _make_telemetry_step(batch: int = 8, seq: int = 33, vocab: int = 128,
                         hidden: int = 64, init_scale: float = 2.0 ** 12):
    """Build the instrumented LM train step for the telemetry bench.

    Returns ``(step, state, tokens, tokens_per_step)`` where ``step`` is
    ONE jitted callable — ``step(i, state, tokens) -> (state, metrics)``
    with ``state = (params, m, v, scaler_state)``. Loss scaling, gradient
    computation, the fused-Adam update (``found_inf`` no-op flag), the
    scale state machine, and the full :class:`TrainMetrics` collection all
    trace into that single call: there is nothing for the host to sync on
    mid-step, and tests assert no callbacks are traced in.
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp.grad_scaler import DynamicGradScaler
    from apex_tpu.monitor.metrics import collect_metrics
    from apex_tpu.optimizers.functional import adam_update

    scaler = DynamicGradScaler(init_scale=init_scale)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {
        "emb": jax.random.normal(keys[0], (vocab, hidden)) * 0.02,
        "w1": jax.random.normal(keys[1], (hidden, hidden)) * 0.1,
        "b1": jnp.zeros((hidden,)),
        "head": jax.random.normal(keys[2], (hidden, vocab)) * 0.02,
    }
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # noqa: E731
    state = (params, jax.tree_util.tree_map(zeros, params),
             jax.tree_util.tree_map(zeros, params), scaler.init())
    tokens = jax.random.randint(keys[3], (batch, seq), 0, vocab, jnp.int32)

    def step(i, state, tokens):
        params, m, v, sstate = state

        def loss_fn(p):
            x = p["emb"][tokens[:, :-1]]
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            logp = jax.nn.log_softmax((h @ p["head"]).astype(jnp.float32))
            nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
            loss = jnp.mean(nll)
            return scaler.scale(loss, sstate), loss

        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # fused unscale + grad-norm + overflow probe: ONE pass over grads
        grads, grad_norm, found_inf = scaler.unscale_and_norm(grads, sstate)
        new_p, m, v = adam_update(params, grads, m, v, step=i + 1, lr=1e-2,
                                  found_inf=found_inf)
        tm = collect_metrics(
            params=new_p,
            updates=jax.tree_util.tree_map(lambda n, o: n - o, new_p,
                                           params),
            scaler_state=sstate, grad_norm=grad_norm, found_inf=found_inf,
            loss=loss)
        return (new_p, m, v, scaler.update(sstate, found_inf)), tm

    return jax.jit(step), state, tokens, float(batch * (seq - 1))


def _telemetry_bench(jsonl_path: "str | None", steps: int = 8,
                     watchdog_timeout: "float | None" = None,
                     trace_jsonl: "str | None" = None,
                     flight_path: "str | None" = None) -> None:
    """Run the instrumented train loop and stream telemetry to JSONL.

    ``trace_jsonl`` additionally enables span-tree tracing for the run
    (one trace per step: ``train_step`` root over the jitted dispatch and
    the completion fetch) exported as Perfetto-loadable Chrome-trace
    JSON, plus per-step HBM sampling and the calibrated step's static
    memory reservation. ``flight_path`` arms a crash-time flight
    recorder: a preemption or watchdog escalation mid-bench leaves a
    postmortem dump instead of a silent log tail.
    """
    import contextlib
    import json

    import jax

    from apex_tpu.monitor import Telemetry

    step, state, tokens, tokens_per_step = _make_telemetry_step()
    tel = Telemetry(jsonl_path, tokens_per_step=tokens_per_step,
                    trace_jsonl=trace_jsonl)
    mem = None
    if trace_jsonl:
        from apex_tpu.monitor.memory import MemoryAccountant
        # every 16 steps: allocator reads are for trends, not hot loops
        mem = MemoryAccountant(every=16)
    flight = None
    if flight_path:
        from apex_tpu.monitor.flight import FlightRecorder
        flight = FlightRecorder(flight_path, tracer=tel.tracer).attach()
    # optional collective watchdog: a step that wedges (stuck collective,
    # straggler host) becomes a collective_stall event in the JSONL —
    # visible in the capture — instead of a silently hung benchmark
    wd = None
    if watchdog_timeout:
        from apex_tpu.resilience import CollectiveWatchdog
        wd = CollectiveWatchdog(timeout_s=watchdog_timeout)
    try:
        # flight.guard: a fatal step exception (XLA error, OOM) has no
        # bus record — the guard is what turns it into a postmortem dump
        with (flight.guard("telemetry_bench") if flight is not None
              else contextlib.nullcontext()):
            tel.calibrate(step, 0, state, tokens)  # MFU from cost model
            # compile outside the timed window so row 1's step_ms is a
            # step, not the trace+compile
            state, tm = step(0, state, tokens)
            jax.block_until_ready(tm)
            tel.start()
            # per-step spans ONLY under --trace-jsonl: each tel.span
            # publishes a "span" bus event, and the telemetry mirror
            # appends one JSONL line per event — per-step writes are the
            # price of opting into tracing, not of plain telemetry
            # (whose events stay low-rate by design)
            step_span = (tel.span if tel.tracer is not None
                         else lambda name: contextlib.nullcontext())
            for i in range(1, steps + 1):
                with (wd.watch("train_step") if wd is not None
                      else contextlib.nullcontext()):
                    with step_span("train_step"):
                        state, tm = step(i, state, tokens)
                        # the loop's ONE host transfer — the overflow
                        # flag it needs anyway; its data dependency also
                        # makes step_ms honest wall clock (and gives the
                        # watchdog a real completion boundary)
                        skipped = bool(jax.device_get(tm.found_inf))
                if mem is not None:
                    mem.tick("train_step", step=i)
                tel.log_step(i, metrics=tm, skipped=skipped)
            summary = tel.summary()
    finally:
        # teardown runs on the failure path too: the recorder must not
        # stay subscribed, the process tracer must be restored, and the
        # Chrome trace must be terminated
        if wd is not None:
            wd.stop()
        if flight is not None:
            flight.detach()
        tel.close()
    print(json.dumps({
        "metric": "telemetry_train_step_ms_lm_tiny",
        "value": round(summary["metrics"].get("step_ms", -1.0), 3),
        "unit": "ms", "steps": steps, "jsonl": jsonl_path,
        "goodput": summary["goodput"]["goodput_frac"]}))


def _train_chaos_bench(steps: int = 12, world: int = 1,
                       grad_shards: "int | None" = None,
                       emit_baseline: "str | None" = None,
                       tp: int = 1) -> None:
    """Trainer chaos smoke (``--train-chaos``): run the production
    trainer under its supervisor through a seeded crash + mid-save-crash
    + preemption/relaunch schedule, and emit a suite-shaped
    ``train_chaos`` entry.

    The headline value is steps/s (higher-is-better); the resilience
    counters (``restarts``/``preempt_drains``/``steps_retried`` — all
    lower-is-better to the gate) ride the entry so a chaos capture that
    suddenly restarts more gates as a regression. Trainer workload
    provenance (world size, gradient-shard parallelism, amp dtype) nests
    under ``workload`` so elastic captures never gate against
    incomparable configs (the serve-bench precedent)."""
    import json
    import tempfile
    import time

    from apex_tpu.resilience import FaultInjector
    from apex_tpu.train import TrainConfig, TrainSupervisor

    g = grad_shards if grad_shards is not None else max(1, world)
    steps = max(6, int(steps))
    config = TrainConfig(steps=steps, batch=8, seq=16, world=world,
                         grad_shards=g, seed=0, tp=tp)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        import dataclasses

        config = dataclasses.replace(config, checkpoint_dir=ckpt_dir,
                                     save_every=max(1, steps // 4))
        # the seeded schedule: a fatal step error (warm restart), a death
        # mid-checkpoint-commit (previous step must restore), and one
        # coordinated preemption drain + same-topology relaunch
        inj = (FaultInjector(seed=0)
               .crash_on_train_step(steps // 3)
               .crash_during_checkpoint_save(
                   (steps // 2) - (steps // 2) % config.save_every)
               .preempt_at_step(2 * steps // 3))
        supervisor = TrainSupervisor(config, injector=inj,
                                     max_restarts=3,
                                     world_schedule=[world, world])
        t0 = time.perf_counter()
        report = supervisor.run()
        wall = time.perf_counter() - t0
    counts = supervisor.trace_counts()
    suite = {
        "train_chaos": {
            "metric": "train_chaos_steps_per_s",
            "value": round(report["goodput"]["steps"] / wall, 3),
            "unit": "steps_per_s",
            # lower-is-better resilience counters (the gate knows all
            # three; a 0 -> N storm off this baseline is a regression)
            "restarts": report["restarts"],
            "preempt_drains": report["preempt_drains"],
            "steps_retried": report["steps_retried"],
            "goodput_frac": round(report["goodput"]["goodput_frac"], 6),
            # recompiles across the whole chaos run (lower-is-better to
            # the gate via the "recompile" hint; the contract is exactly
            # one trace — >1 means a restart recompiled)
            "step_recompiles": counts["shard_grads"],
            # storage-health counters off the goodput ledger: a healthy
            # run holds both at 0, so a bit-rot quarantine storm or
            # unexpected reshard churn on restore gates as a regression
            "ckpt_quarantined": report["goodput"]["events"].get(
                "train_ckpt_quarantined", 0),
            "topology_restored": report["goodput"]["events"].get(
                "train_topology_restored", 0),
            "bench_wall_s": round(wall, 3),
            "workload": {"steps": steps, "batch": config.batch,
                         "seq": config.seq,
                         "world": world, "grad_shards": g,
                         # tensor-axis provenance: a dp×tp capture is
                         # incomparable with a legacy dp-only baseline
                         # (missing key reads as tp=1), so the gate
                         # refuses instead of pretending to compare
                         "tp": tp,
                         "amp_dtype": config.amp,
                         "save_every": config.save_every,
                         "max_restarts": 3},
            "complete": False,
        },
    }
    if emit_baseline:
        bench = _load_bench_module()
        bench.atomic_write_json(emit_baseline, suite)
        print(json.dumps({"baseline": emit_baseline,
                          "kernels": ["train_chaos"]}))
    else:
        print(json.dumps(suite, indent=1))


def _load_bench_module():
    """Import the repo checkout's bench.py (the suite/baseline machinery
    lives there, not in the wheel). Exits 2 with a clear message on a
    wheel-only install — shared by the kernel-subset and serve modes."""
    import importlib.util

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_path = os.path.join(here, "bench.py")
    if not os.path.exists(bench_path):
        print("apex-tpu-bench: --kernels/--emit-baseline need the repo "
              "checkout's bench.py (wheel installs carry only the inline "
              "headline bench)", file=sys.stderr)
        raise SystemExit(2)
    spec = importlib.util.spec_from_file_location("apex_tpu_bench_suite",
                                                  bench_path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def _parse_prompt_lens(spec: str) -> "tuple[int, int]":
    """``"8"`` -> (8, 8); ``"4:24"`` -> (4, 24) — the mixed-length range
    scripted prompts are drawn from (uniform, seeded)."""
    lo, _, hi = spec.partition(":")
    lo = int(lo)
    hi = int(hi) if hi else lo
    if lo < 1 or hi < lo:
        raise ValueError(f"--prompt-len {spec!r}: need MIN[:MAX] with "
                         f"1 <= MIN <= MAX")
    return lo, hi


def _serve_bench(steps: int, num_slots: int = 4,
                 emit_baseline: "str | None" = None,
                 deadline_ms: "float | None" = None,
                 max_queue: "int | None" = None,
                 shed_policy: str = "reject-newest",
                 max_len: int = 64,
                 prompt_len: str = "8",
                 shared_prefix: int = 0,
                 page_size: "int | None" = None,
                 num_pages: "int | None" = None,
                 prefix_cache: bool = False,
                 metrics_port: "int | None" = None,
                 metrics_snapshot: "str | None" = None,
                 tenants: int = 0,
                 replicas: int = 1,
                 hedge_ms: "float | None" = None,
                 heartbeat_ms: "float | None" = None,
                 trace_jsonl: "str | None" = None,
                 trace_sample: "float | None" = None,
                 flight_recorder: "str | None" = None,
                 tp: int = 1,
                 tp_sync: str = "exact",
                 disagg: bool = False,
                 roles: "str | None" = None,
                 diurnal: bool = False,
                 cost_ledger: "str | None" = None,
                 chip_spec: "str | None" = None,
                 spec_draft_len: "int | None" = None,
                 decode_policy: "str | None" = None,
                 kv_quant: "str | None" = None) -> None:
    """Serving micro-bench: a scripted continuous-batching workload on the
    tiny fp32 GPT-2 — tokens/s, p50/p99 per-token decode latency, and TTFT
    in the BENCH_SUITE entry shape, ready for the check_regression suite
    gate (``tools/check_regression.py CURRENT --suite BASELINE --kernels
    serve_decode``). Latency metrics are lower-is-better; the gate knows —
    as are the overload SLO fields (``rejected``, ``deadline_exceeded``,
    ``shed_rate``) the entry carries when ``--deadline-ms``/``--max-queue``
    shape the workload.

    The paged-pool knobs (``--page-size``/``--num-pages``/
    ``--prefix-cache``) plus the workload shapers (``--prompt-len
    MIN:MAX`` mixed lengths, ``--shared-prefix N`` a fleet-wide system
    prompt every request starts with) are what the capacity claim is
    measured on: ``resident_tokens_per_hbm_byte`` (peak resident tokens
    over the engine's KV reservation — the number paging multiplies at
    equal HBM budget) and ``prefix_hit_rate`` (admissions served partly
    from shared prefix pages) land in the entry, higher-is-better, and
    every pool/workload knob rides the nested ``workload`` provenance so
    the gate never compares incomparable configs (PR-8 precedent).

    ``--metrics-port`` serves live Prometheus/JSON scrapes while the
    bench runs and ``--metrics-snapshot`` commits the mergeable
    per-rank snapshot at exit (``tools/metrics_merge.py`` folds these,
    and ``check_regression`` gates them directly — the live scrape and
    this bench produce comparably gateable artifacts); ``--tenants N``
    labels the scripted workload round-robin for a per-tenant view.

    ``--tp N`` shards the bench engine over an N-device mesh
    (docs/serving.md "Tensor-parallel decode") — the serve_decode entry
    then measures the SHARDED step's tokens/s (the scaling curve), the
    mesh shape rides the ``workload`` provenance, and
    ``check_regression`` refuses to gate across mesh shapes outright.
    ``--tp-sync`` picks the per-layer collective mode (exact = the
    bit-identical oracle; overlap/relaxed trade exactness for less or
    hidden collective pressure).

    ``--cost-ledger PATH`` additionally commits the device-independent
    compiled-step cost ledger (``apex_tpu.cost_ledger/v1``: per-phase
    FLOPs/HBM bytes extracted from the SAME AOT artifacts the bench
    ran, roofline-priced per ``--chip-spec``) — the wall-clock-free
    regression artifact ``check_regression`` gates and
    ``tools/cost_diff.py`` attributes. See docs/performance.md "Cost
    ledgers and roofline gating".
    """
    import dataclasses
    import json
    import time

    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
    from apex_tpu.serve.scheduler import Request, ServeScheduler

    # resolve the baseline writer BEFORE benching: a wheel-only install
    # must fail in milliseconds, not after the engine compiles and runs
    bench = _load_bench_module() if emit_baseline else None

    from apex_tpu.utils.env import capture_provenance

    try:
        plo, phi = _parse_prompt_lens(prompt_len)
    except ValueError as e:
        raise SystemExit(f"apex-tpu-bench: {e}")
    # tensor-parallel + fleet flag matrix (PR-10 precedent:
    # inert/contradictory flags are loud usage errors before any
    # compile, never silent no-ops)
    if tp < 1:
        raise SystemExit(f"apex-tpu-bench: --tp {tp} must be >= 1")
    if tp_sync != "exact" and tp == 1:
        raise SystemExit(
            f"apex-tpu-bench: --tp-sync {tp_sync} relaxes cross-rank "
            f"synchronization; it needs --tp >= 2 (a single chip has "
            f"no collectives to overlap or relax)")
    if replicas < 1:
        raise SystemExit(f"apex-tpu-bench: --replicas {replicas} must "
                         f"be >= 1")
    # speculative-decoding matrix (same discipline, same as
    # apex-tpu-serve): refused in milliseconds, before any compile
    if spec_draft_len is not None and spec_draft_len < 1:
        raise SystemExit(
            f"apex-tpu-bench: --spec-draft-len {spec_draft_len} must "
            f"be >= 1 (it is the drafter's proposal width; omit the "
            f"flag for one-token decode)")
    spec_k = spec_draft_len or 0
    if decode_policy is not None:
        from apex_tpu.serve.spec import parse_policy

        try:
            parse_policy(decode_policy, spec_draft_len=spec_k)
        except ValueError as e:
            raise SystemExit(f"apex-tpu-bench: --decode-policy: {e}")
    # KV-quantization matrix (same discipline): the bench engine is
    # fp32 by construction, so only the codec itself and the spec
    # conflict need refusing before any compile
    if kv_quant is not None:
        if spec_k:
            raise SystemExit(
                f"apex-tpu-bench: --kv-quant {kv_quant} is incompatible "
                f"with --spec-draft-len {spec_k}: the speculative "
                f"acceptance oracle is bit-exact, the quantized cache "
                f"is tolerance-gated (drop one)")
        from apex_tpu.quant.kv import check_kv_codec

        try:
            check_kv_codec(kv_quant)
        except ValueError as e:
            raise SystemExit(f"apex-tpu-bench: --kv-quant: {e}")
    # cost-ledger matrix (same inert/contradictory-flag discipline):
    # validated against the ledger module's own chip-spec table BEFORE
    # any params/compile work
    if chip_spec is not None or cost_ledger:
        import os as _os

        from apex_tpu.monitor import costs

        if chip_spec is not None and not cost_ledger:
            raise SystemExit(
                "apex-tpu-bench: --chip-spec prices the cost ledger's "
                "roofline; it needs --cost-ledger")
        if chip_spec is not None and chip_spec not in costs.CHIP_SPECS:
            raise SystemExit(
                f"apex-tpu-bench: unknown --chip-spec {chip_spec!r}; "
                f"known specs: {', '.join(sorted(costs.CHIP_SPECS))}")
        if cost_ledger and metrics_snapshot and (
                _os.path.abspath(cost_ledger)
                == _os.path.abspath(metrics_snapshot)):
            raise SystemExit(
                f"apex-tpu-bench: --cost-ledger and --metrics-snapshot "
                f"both write {cost_ledger!r} — the second atomic commit "
                f"would clobber the first (pick two paths)")
    # disaggregation matrix (PR-10 precedent, same as apex-tpu-serve)
    role_split = None
    if roles is not None and not disagg:
        raise SystemExit(
            "apex-tpu-bench: --roles splits a DISAGGREGATED fleet; it "
            "needs --disagg")
    if disagg:
        if not page_size or not prefix_cache:
            raise SystemExit(
                "apex-tpu-bench: --disagg streams prompt pages through "
                "the prefix index; it needs --page-size and "
                "--prefix-cache")
        if roles is not None:
            pr, sep, de = str(roles).partition(":")
            try:
                role_split = (int(pr), int(de)) if sep else None
            except ValueError:
                role_split = None
            if role_split is None or min(role_split) < 1:
                raise SystemExit(
                    f"apex-tpu-bench: --roles {roles!r}: want P:D "
                    f"positive integers (e.g. 1:2)")
            if replicas > 1 and replicas != sum(role_split):
                raise SystemExit(
                    f"apex-tpu-bench: --roles {roles} is a "
                    f"{sum(role_split)}-replica fleet; --replicas "
                    f"{replicas} contradicts it (drop one)")
            replicas = sum(role_split)
        else:
            if replicas < 2:
                raise SystemExit(
                    "apex-tpu-bench: --disagg needs --replicas >= 2 "
                    "(one prefill + at least one decode) or an "
                    "explicit --roles P:D")
            role_split = (1, replicas - 1)
    if diurnal and replicas < 2:
        raise SystemExit(
            "apex-tpu-bench: --diurnal drives a FLEET through the "
            "day curve; it needs --replicas >= 2 (or --disagg)")
    if replicas == 1 and (hedge_ms is not None
                          or heartbeat_ms is not None):
        raise SystemExit(
            "apex-tpu-bench: --hedge-ms/--heartbeat-ms are fleet "
            "routing; they need --replicas >= 2 (one replica has "
            "nowhere to hedge or fail over to)")
    if heartbeat_ms is not None and heartbeat_ms <= 0:
        # a falsy-coerced default would be a silent no-op of the exact
        # class this matrix refuses
        raise SystemExit(f"apex-tpu-bench: --heartbeat-ms "
                         f"{heartbeat_ms:g} must be > 0")
    if trace_sample is not None:
        if not trace_jsonl:
            # sampling a file that will never exist is the inert-flag
            # class this matrix refuses
            raise SystemExit(
                "apex-tpu-bench: --trace-sample needs --trace-jsonl "
                "(it decides which journeys reach that file)")
        if not 0.0 < trace_sample <= 1.0:
            raise SystemExit(f"apex-tpu-bench: --trace-sample "
                             f"{trace_sample:g} must be in (0, 1]")
    # live metrics: same wiring as apex-tpu-serve — registries + the
    # optional pull endpoint on a daemon thread, atomic snapshots at
    # exit; the scrape-vs-bench comparability is the point
    # (check_regression gates either artifact with the same direction
    # hints). Fleet captures (PR 13) get one registry per replica, the
    # merged pull endpoint at /metrics, and PATH.rK + merged PATH
    # snapshots. Armed BEFORE the engines pay for params + compiles: an
    # inert --tenants or an unbindable port must fail in milliseconds
    metrics = exporter = registries = per_metrics = None
    if role_split:
        replica_specs = [(f"p{i}", "prefill")
                         for i in range(role_split[0])] \
            + [(f"d{i}", "decode") for i in range(role_split[1])]
    else:
        replica_specs = [(f"r{i}", "unified") for i in range(replicas)]
    replica_ids = [rid for rid, _ in replica_specs]
    if tenants > 0 and metrics_port is None and not metrics_snapshot:
        # the labels would reach no observable output — the armed-but-
        # inert flag class this PR makes a loud usage error everywhere
        raise SystemExit(
            "apex-tpu-bench: --tenants labels the live metrics; it "
            "needs --metrics-port and/or --metrics-snapshot to be "
            "observable")
    if metrics_port is not None or metrics_snapshot:
        from apex_tpu.monitor.export import (FleetMetricsExporter,
                                             MetricsExporter,
                                             MetricsRegistry)
        from apex_tpu.serve.metrics import ServeMetrics

        # provenance rides the snapshot meta: check_regression's
        # device-mismatch guard reads it, so a CPU-smoke snapshot can
        # never silently gate real-chip numbers
        metrics_meta = capture_provenance()
        if replicas > 1:
            registries = {rid: MetricsRegistry() for rid in replica_ids}
            per_metrics = {rid: ServeMetrics(registry=reg)
                           for rid, reg in registries.items()}
            if metrics_port is not None:
                try:
                    exporter = FleetMetricsExporter(
                        registries, port=metrics_port,
                        meta=metrics_meta).start()
                except OSError as e:
                    raise SystemExit(
                        f"apex-tpu-bench: cannot bind --metrics-port "
                        f"{metrics_port}: {e}")
                print(f"apex-tpu-bench: fleet metrics at {exporter.url} "
                      f"(per-replica at /metrics/rK)", file=sys.stderr)
        else:
            metrics = ServeMetrics()
            if metrics_port is not None:
                try:
                    exporter = MetricsExporter(
                        metrics.registry, port=metrics_port,
                        snapshot_path=metrics_snapshot,
                        meta=metrics_meta).start()
                except OSError as e:
                    raise SystemExit(
                        f"apex-tpu-bench: cannot bind --metrics-port "
                        f"{metrics_port}: {e}")
                print(f"apex-tpu-bench: metrics at {exporter.url}",
                      file=sys.stderr)
    # tracing (PR 13): the fleet harness (journeys + PATH.rK files +
    # tail capture) for --replicas N, a single tracer + tail-capture
    # router otherwise — both stream through the same sampling policy
    harness = router = tracer = None
    if trace_jsonl:
        rate = 1.0 if trace_sample is None else trace_sample
        if replicas > 1:
            from apex_tpu.serve.fleet import FleetTraceHarness

            harness = FleetTraceHarness(trace_jsonl, replica_ids,
                                        sample_rate=rate)
        else:
            from apex_tpu.monitor.trace import (ChromeTraceWriter,
                                                TailCaptureRouter,
                                                Tracer)

            tracer = Tracer()
            router = TailCaptureRouter(
                {"": ChromeTraceWriter(trace_jsonl, subscribe=False)},
                sample_rate=rate)
    cfg = GPT2Config.tiny()
    if max_len > cfg.n_positions:
        # the tiny preset caps context at its n_positions; a deeper bench
        # workload (e.g. the 32-1024 mixed sweep) needs longer rope/wpe
        cfg = dataclasses.replace(cfg, n_positions=max_len)
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    if cfg.n_head % tp:
        # before paying for params: the mesh shards whole heads
        raise SystemExit(
            f"apex-tpu-bench: --tp {tp} must divide the bench model's "
            f"n_head={cfg.n_head} (the serving mesh shards whole heads)")
    params = init_gpt2_params(cfg)
    try:
        # one param pytree shared by every replica (read-only): the
        # fleet bit-exactness story needs identical weights everywhere
        engines = [Engine(cfg, params,
                          EngineConfig(num_slots=num_slots,
                                       max_len=max_len,
                                       temperature=0.0,
                                       page_size=page_size,
                                       num_pages=num_pages,
                                       prefix_cache=prefix_cache,
                                       tp=tp, tp_sync=tp_sync,
                                       spec_draft_len=spec_k,
                                       decode_policy=decode_policy,
                                       kv_quant=kv_quant),
                          seed=0)
                   for _ in range(replicas)]
    except ValueError as e:
        # bad pool geometry (page_size not dividing max_len, undersized
        # num_pages, ...) is a usage error, same as the prefix check below
        raise SystemExit(f"apex-tpu-bench: {e}")
    engine = engines[0]
    if shared_prefix + phi >= max_len:
        raise SystemExit(
            f"apex-tpu-bench: --shared-prefix {shared_prefix} + "
            f"--prompt-len max {phi} leaves no room to generate under "
            f"--serve-max-len {max_len}")
    # warm EVERY reachable prefill bucket, not just the longest prompt's:
    # mixed-length batches and prefix-hit tails (the scan covers only the
    # unshared remainder) land on smaller pow2 buckets, and a fresh
    # compile inside the timed region would corrupt the TTFT/p99 the
    # gate compares — log-many buckets, all paid before the clock
    top = shared_prefix + phi
    buckets, b = [], 1
    while b < top:
        buckets.append(b)
        b *= 2
    buckets.append(top)
    for e in engines:
        e.aot_compile(buckets)
    rng = np.random.RandomState(0)

    def _admission():
        if max_queue is None:
            return None
        from apex_tpu.serve.resilience import AdmissionController

        return AdmissionController(max_queue=max_queue,
                                   shed_policy=shed_policy)

    # enough requests to keep every slot busy and exercise backfill
    n_requests = max(2 * num_slots * replicas,
                     (steps * num_slots) // 8 + 1)
    system = [int(t) for t in rng.randint(0, cfg.vocab_size,
                                          shared_prefix)]
    specs = []
    for i in range(n_requests):
        plen = int(rng.randint(plo, phi + 1))
        tail = [int(t) for t in rng.randint(0, cfg.vocab_size, plen)]
        specs.append(Request(
            request_id=f"bench-{i}", tokens=system + tail,
            max_new_tokens=8, deadline_ms=deadline_ms,
            tenant=f"tenant-{i % tenants}" if tenants > 0 else None))
    fleet = None
    recorders = []
    fleet_flight = single_flight = None
    if replicas > 1:
        from apex_tpu.serve.disagg import DisaggController
        from apex_tpu.serve.fleet import EngineReplica, FleetController

        # CPU-tolerant death budget (2s at the default interval): a
        # fabricated death on a healthy bench fleet would stamp nonzero
        # failovers/replica_dead into lower-is-better gated counters —
        # flunking the regression gate off machine noise
        fleet_cls = DisaggController if role_split else FleetController
        fleet = fleet_cls(
            [EngineReplica(
                rid, e, role=role, admission=_admission(),
                metrics=per_metrics[rid] if per_metrics else None,
                tracer=harness.tracer_for(rid) if harness else None)
             for (rid, role), e in zip(replica_specs, engines)],
            heartbeat_ms=50.0 if heartbeat_ms is None else heartbeat_ms,
            suspect_misses=20, dead_misses=40, hedge_ms=hedge_ms,
            tracer=harness.fleet_tracer if harness else None)
        if flight_recorder:
            from apex_tpu.serve.fleet import attach_fleet_recorders

            # per-replica postmortems + the fleet-plane recorder — the
            # ONE wiring shared with apex-tpu-serve --replicas
            recorders = attach_fleet_recorders(fleet, flight_recorder,
                                               harness)
            fleet_flight = recorders[-1]
        if not diurnal:
            for spec in specs:
                fleet.submit(spec)
    else:
        if flight_recorder:
            from apex_tpu.monitor.flight import FlightRecorder

            single_flight = FlightRecorder(flight_recorder,
                                           tracer=tracer).attach()
            recorders.append(single_flight)
        sched = ServeScheduler(engine, admission=_admission(),
                               metrics=metrics, tracer=tracer,
                               flight_recorder=single_flight)
        for spec in specs:
            sched.submit(spec)
    t0 = time.perf_counter()
    try:
        import contextlib

        # the fleet runs the whole request set (its workload bound is
        # n_requests, which --steps sized above); the liveness bound
        # scales with it so a long-but-healthy run never trips a
        # TimeoutError mid-bench
        with (fleet_flight.guard("fleet") if fleet_flight is not None
              else contextlib.nullcontext()):
            if fleet is not None and diurnal:
                # one compressed "day": requests arrive along the
                # seeded sinusoidal curve (trough -> peak -> trough)
                # while the control loop pumps, then the fleet finishes
                # the backlog — total volume sized to the --steps
                # workload so the entry stays comparable in scale
                from apex_tpu.serve.disagg import DiurnalTraffic

                day_s = 2.0
                traffic = DiurnalTraffic(
                    day_s=day_s, seed=0,
                    capacity_scale=(len(specs) / day_s)
                    / (2_000_000 * 8.0 / 86400.0),
                    prompt_lens=list(range(plo, phi + 1)),
                    max_new_tokens=8, vocab=cfg.vocab_size,
                    id_prefix="bench-diurnal")
                fleet.start()
                traffic.start()
                t_end = time.perf_counter() + day_s
                while time.perf_counter() < t_end:
                    for r in traffic.due():
                        if system or deadline_ms is not None \
                                or tenants > 0:
                            r = dataclasses.replace(
                                r, tokens=system + list(r.tokens),
                                deadline_ms=deadline_ms,
                                tenant=f"tenant-{traffic.emitted % tenants}"
                                if tenants > 0 else None)
                        fleet.submit(r)
                    fleet.pump()
                    time.sleep(0.002)
                stats = fleet.run(
                    max_wall_s=max(60.0, 2.0 * max(traffic.emitted, 1)))
            elif fleet is not None:
                stats = fleet.run(max_wall_s=max(60.0, 2.0 * len(specs)))
            else:
                stats = sched.run(max_steps=steps)
        # measured BEFORE the finally teardown: exporter.stop() blocks on
        # the HTTP server's shutdown poll + thread join + snapshot I/O,
        # and bench_wall_s gates lower-is-better — teardown noise must
        # not read as a perf regression of the metrics-armed capture
        wall = time.perf_counter() - t0
    finally:
        if exporter is not None:
            exporter.stop()
        if metrics_snapshot and registries is not None:
            # per-replica mergeable snapshots at PATH.rK plus the
            # metrics_merge fleet view at PATH itself (the serve CLI's
            # contract), all atomic, provenance meta on each
            from apex_tpu.monitor.export import (atomic_write_json,
                                                 merge_snapshots)

            docs = []
            for rid, reg in registries.items():
                doc = reg.snapshot(meta={**(metrics_meta or {}),
                                         "replica": rid})
                atomic_write_json(f"{metrics_snapshot}.{rid}", doc)
                docs.append(doc)
            atomic_write_json(metrics_snapshot, merge_snapshots(docs))
        elif exporter is None and metrics is not None \
                and metrics_snapshot:
            from apex_tpu.monitor.export import write_snapshot

            write_snapshot(metrics.registry, metrics_snapshot,
                           meta=metrics_meta)
        for fr in recorders:
            fr.detach()
        if harness is not None:
            harness.close()
        if router is not None:
            router.close()
    s = stats.summary()
    if fleet is not None:
        # fleet-wide capacity/hit aggregates the single path reads off
        # its one scheduler; summed over replicas here
        peak_resident = sum(h.scheduler.peak_resident_tokens
                            for h in fleet.handles)
        kv_bytes = sum(h.engine.kv_cache_bytes for h in fleet.handles)
        admitted = sum(h.scheduler.admitted for h in fleet.handles)
        prefix_hits = sum(h.scheduler.prefix_hits for h in fleet.handles)
        s["prefix_hit_rate"] = round(prefix_hits / admitted, 4) \
            if admitted else 0.0
        s["peak_resident_tokens"] = peak_resident
        # speculative aggregates the single path reads off its one
        # scheduler summary; pooled over replicas here (fleet-wide
        # tokens over fleet-wide slot-steps, NOT a mean of ratios)
        slot_steps = sum(h.scheduler.decode_slot_steps
                         for h in fleet.handles)
        dec_tokens = sum(h.scheduler.decode_tokens
                         for h in fleet.handles)
        proposed = sum(h.scheduler.spec_proposed for h in fleet.handles)
        accepted = sum(h.scheduler.spec_accepted for h in fleet.handles)
        s["accepted_tokens_per_step"] = round(
            dec_tokens / slot_steps, 4) if slot_steps else 0.0
        s["spec_accept_rate"] = round(
            accepted / proposed, 4) if proposed else 0.0
    else:
        kv_bytes = engine.kv_cache_bytes
    suite = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        # capture provenance: a CPU-smoke capture must be identifiable as
        # one — check_regression flags a device_kind mismatch between
        # capture and baseline instead of gating apples against oranges
        **capture_provenance(),
        "serve_decode": {
            "metric": "serve_decode_tokens_per_s",
            "value": s["tokens_per_s"], "unit": "tokens_per_s",
            "p50_ms": s["p50_step_ms"], "p99_ms": s["p99_step_ms"],
            "ttft_ms": s["ttft_p50_ms"],
            # overload SLO fields (lower-is-better; check_regression
            # knows) — zero on the default unbounded/no-deadline workload
            "rejected": s["rejected"],
            "deadline_exceeded": s["deadline_exceeded"],
            "shed_rate": s["shed_rate"],
            # paged-pool effectiveness (higher-is-better; the gate
            # knows): peak resident tokens per byte of KV reservation —
            # the capacity number paging multiplies at equal HBM budget —
            # and the fraction of admissions served partly from shared
            # prefix pages
            # significant digits, not decimal places: a production-scale
            # pool puts this gate metric near 1e-8, where round(x, 9)
            # would quantize away a real 5-10% capacity regression
            "resident_tokens_per_hbm_byte": float(
                f"{s['peak_resident_tokens'] / max(kv_bytes, 1):.6g}"),
            "prefix_hit_rate": s["prefix_hit_rate"],
            # fleet resilience counters (lower-is-better; the gate
            # knows failover/hedge_fired/replica_dead) — only stamped
            # by fleet captures, so single-replica baselines simply
            # skip them instead of gating a missing field
            **({"failovers": s["failovers"],
                "hedge_fired": s["hedge_fired"],
                "replica_dead": s["replica_dead"],
                "migrations": s["migrations"]}
               if fleet is not None else {}),
            # disaggregated captures only: refused handoffs are
            # certification failures (lower-is-better, the gate knows
            # "handoff_refused"); pages_migrated is the streaming
            # volume the refusal rate is read against
            **({"handoff_refused": s["handoffs_refused"],
                "pages_migrated": s["pages_migrated"]}
               if role_split else {}),
            # traced captures only (lower-is-better; the gate knows):
            # every promoted journey is a bad-outcome request the tail
            # capture had to rescue — untraced baselines simply skip it
            **({"trace_promoted": (harness.stats() if harness is not None
                                   else router.stats())["promoted"]}
               if trace_jsonl else {}),
            # speculative captures only (all higher-is-better; the gate
            # knows tokens/_per_s/accept_rate): tokens committed per
            # verify step (1.0 is the one-token floor), the draft
            # acceptance fraction, and the throughput restated under a
            # spec-specific name so the gate can hold the speculative
            # rate by name — one-token baselines simply skip all three,
            # and the workload axes below make cross-config comparisons
            # a refusal, not a skew
            **({"accepted_tokens_per_step": s["accepted_tokens_per_step"],
                "spec_accept_rate": s["spec_accept_rate"],
                "spec_tokens_per_s": s["tokens_per_s"]}
               if spec_k else {}),
            "bench_wall_s": round(wall, 3),
            # workload config nested as a dict: check_regression lifts
            # only numeric scalars, so a capture with different
            # --steps/--serve-slots than the baseline gates on PERF
            # fields alone, not on its own configuration
            # the overload knobs ride along so a capture whose SLO
            # counters were shaped by a different config is identifiable
            # (nested dict: never lifted into the gated metrics)
            "workload": {"steps": s["decode_steps"],
                         "new_tokens": s["new_tokens"],
                         "slots": num_slots,
                         "deadline_ms": deadline_ms,
                         "max_queue": max_queue,
                         "shed_policy": shed_policy,
                         # pool geometry provenance: a capture whose
                         # capacity/hit-rate numbers were shaped by a
                         # different page_size is identifiable, never
                         # silently gated against (the engine's resolved
                         # geometry: one max_len page a slot by default)
                         "max_len": max_len,
                         "page_size": engine.page_size,
                         "num_pages": engine._num_pages,
                         "prefix_cache": bool(prefix_cache),
                         "prompt_len": prompt_len,
                         "shared_prefix": shared_prefix,
                         "kv_cache_bytes": kv_bytes,
                         # fleet shape provenance: counters shaped by a
                         # different replica count / hedge / heartbeat
                         # config are identifiable, never silently
                         # gated across incomparable configs
                         "replicas": replicas,
                         "hedge_ms": hedge_ms,
                         "heartbeat_ms": heartbeat_ms,
                         # mesh shape provenance: a tp=2 capture's
                         # tokens/s measures a sharded step (collective
                         # latency included) — check_regression REFUSES
                         # to gate it against a different mesh shape
                         # (incomparable_entries), not merely flags it
                         "tp": tp,
                         "tp_sync": tp_sync if tp > 1 else None,
                         # disaggregation provenance: a disaggregated
                         # (or diurnal-arrival) capture measures a
                         # different serving pipeline — the gate
                         # REFUSES to compare across these axes
                         # (incomparable_entries), not merely flags it
                         "disagg": bool(role_split),
                         "roles": f"{role_split[0]}:{role_split[1]}"
                         if role_split else None,
                         "diurnal": bool(diurnal),
                         # trace provenance (PR-8 incomparable-config
                         # precedent): a traced capture pays host-side
                         # span work per request — it must never gate
                         # against an untraced baseline as if the two
                         # measured the same thing
                         "traced": bool(trace_jsonl),
                         "trace_sample": (
                             1.0 if trace_sample is None
                             else trace_sample)
                         if trace_jsonl else None,
                         # speculative provenance: a spec capture's
                         # tokens/s rides draft-acceptance luck and its
                         # step time carries draft_len + 1 positions —
                         # the gate REFUSES to compare across these
                         # axes (missing key = speculation off, the
                         # pre-spec default, so legacy baselines refuse
                         # rather than silently gate)
                         "spec": bool(spec_k),
                         "draft_len": spec_k,
                         "decode_policy": decode_policy,
                         # quantization provenance: a quantized
                         # capture's capacity/latency numbers are a
                         # different workload — the gate refuses to
                         # compare across codec or block (missing key
                         # = unquantized, the pre-quant default)
                         "kv_quant": kv_quant,
                         "quant_block": int(engine.quant_block)},
            # a subset capture, not the full committed suite
            "complete": False,
        },
    }
    if cost_ledger:
        # device-independent companion artifact: the per-executable cost
        # ledger extracted from the SAME AOT artifacts the bench just
        # ran (no re-trace, no re-lower — Engine.cost_ledger resolves
        # from the retained lowerings), provenance-stamped so
        # check_regression can refuse cross-device/cross-workload gates
        from apex_tpu.monitor import costs
        from apex_tpu.monitor.export import atomic_write_json

        ledger = engine.cost_ledger(chip=chip_spec)
        ledger["meta"] = capture_provenance()
        atomic_write_json(cost_ledger, ledger)
        print(f"apex-tpu-bench: cost ledger (chip="
              f"{ledger['chip_spec']}, gating={ledger['gating']}, "
              f"schema={costs.LEDGER_SCHEMA}) at {cost_ledger}",
              file=sys.stderr)
    if bench is not None:
        # same contract as the kernel-subset gate: atomic publish via the
        # repo bench module (loaded up front — a torn gate file is worse
        # than no gate file)
        bench.atomic_write_json(emit_baseline, suite)
        print(json.dumps({"baseline": emit_baseline,
                          "kernels": ["serve_decode"]}))
    else:
        print(json.dumps(suite, indent=1))


def _subset_bench(kernels: str | None, emit_baseline: str | None) -> None:
    """Run a bench-suite subset directly (no worker/cache indirection) and
    optionally write it as a committed-baseline artifact."""
    import json

    bench = _load_bench_module()

    import jax
    import jax.numpy as jnp

    from apex_tpu.utils.logging import subscribe_events

    backend = jax.default_backend()
    only = None
    if kernels:
        only = [k.strip() for k in kernels.split(",") if k.strip()]
    # record which autotuned configs the benched kernels selected (cache
    # hits publish kernel_autotune on the bus) — the baseline artifact then
    # carries its own tuning provenance
    autotune: list = []
    unsub = subscribe_events(
        lambda rec: autotune.append(
            {k: rec[k] for k in ("kernel", "key", "params", "source")
             if k in rec})
        if rec.get("event") == "kernel_autotune" else None)
    try:
        suite = bench.run_suite(jax, jnp, backend, out_path=None, only=only)
    finally:
        unsub()
    if autotune:
        suite["autotune"] = autotune
    if emit_baseline:
        bench.atomic_write_json(emit_baseline, suite)
        print(json.dumps({"baseline": emit_baseline, "backend": backend,
                          "kernels": suite.get("subset") or
                          [n for n, _ in bench.BENCHES]}))
    else:
        print(json.dumps({k: v for k, v in suite.items()
                          if isinstance(v, dict)}, indent=1))


def main() -> None:
    # a preempted bench run (SIGTERM from the scheduler) exits cleanly with
    # a structured record instead of a stack trace mid-measurement; there is
    # no step boundary to poll, so the guard raises to unwind immediately
    from apex_tpu.resilience import PreemptionGuard
    from apex_tpu.utils.env import enable_compile_cache
    from apex_tpu.utils.logging import is_rank_zero, publish_event

    enable_compile_cache()
    with PreemptionGuard(raise_on_signal=True) as guard:
        # --flight-recorder selects this mode too: silently dropping the
        # flag would mean the requested postmortem recorder never armed —
        # the exact silent-death failure it exists to prevent. With
        # --serve, --trace-jsonl/--flight-recorder belong to the SERVE
        # bench (PR 13: fleet journeys + per-replica postmortems), so
        # those two no longer force the telemetry train bench —
        # but --telemetry-jsonl stays a train-bench flag, and with
        # --serve it must keep hitting the loud mode conflict below
        # (the serve bench has no event mirror; swallowing the flag
        # would be the silent-no-op class this matrix refuses)
        has_serve = any(a == "--serve" for a in sys.argv[1:])
        serve_only = [a for a in sys.argv[1:]
                      if a.split("=", 1)[0] in ("--disagg", "--roles",
                                                "--diurnal",
                                                "--cost-ledger",
                                                "--chip-spec",
                                                "--spec-draft-len",
                                                "--decode-policy",
                                                "--kv-quant")]
        if serve_only and not has_serve:
            # without --serve these would silently fall through to the
            # kernel bench — the inert-flag class this matrix refuses
            print(f"apex-tpu-bench: {serve_only[0]} shapes the serving "
                  f"bench; it needs --serve", file=sys.stderr)
            sys.exit(2)
        has_train_chaos = any(a == "--train-chaos" for a in sys.argv[1:])
        has_telemetry = any(
            a.split("=", 1)[0] == "--telemetry-jsonl"
            for a in sys.argv[1:]) or (
            any(a.split("=", 1)[0] in ("--trace-jsonl",
                                       "--flight-recorder")
                for a in sys.argv[1:]) and not has_serve)
        # --emit-baseline is shared by the serve, train-chaos, and
        # kernel-subset modes; --kernels is NOT valid with --serve or
        # --train-chaos and must keep refusing
        has_subset = any(a.split("=", 1)[0] == "--kernels"
                         for a in sys.argv[1:]) or (
            any(a.split("=", 1)[0] == "--emit-baseline"
                for a in sys.argv[1:]) and not has_serve
            and not has_train_chaos)
        if sum((has_telemetry, has_subset, has_serve,
                has_train_chaos)) > 1:
            # parse_known_args would silently swallow the other mode's
            # flags — refuse instead of pretending both ran
            print("apex-tpu-bench: --telemetry-jsonl, --serve, "
                  "--train-chaos, and --kernels/--emit-baseline are "
                  "separate modes; run them as separate invocations",
                  file=sys.stderr)
            sys.exit(2)
        if has_train_chaos:
            import argparse

            ap = argparse.ArgumentParser(prog="apex-tpu-bench")
            ap.add_argument("--train-chaos", action="store_true")
            ap.add_argument("--steps", type=int, default=12,
                            help="train steps the chaos schedule runs "
                                 "over (min 6 so every fault fires)")
            ap.add_argument("--world", type=int, default=1,
                            help="data-parallel degree (thread-faked "
                                 "ranks; must divide --grad-shards)")
            ap.add_argument("--grad-shards", type=int, default=None,
                            help="fixed micro-shard count (default: "
                                 "world)")
            ap.add_argument("--tp", type=int, default=1,
                            help="tensor-parallel degree: each micro-"
                                 "shard's grad runs over the head-axis "
                                 "mesh (bit-identical to --tp 1); "
                                 "stamped into workload provenance")
            ap.add_argument("--emit-baseline", nargs="?",
                            const="BENCH_BASELINE_TRAIN.json",
                            default=None,
                            help="write the capture as a suite JSON "
                                 "(default BENCH_BASELINE_TRAIN.json)")
            args, _ = ap.parse_known_args(sys.argv[1:])
            shards = (args.grad_shards if args.grad_shards is not None
                      else max(1, args.world))
            # the full geometry contract, as a loud exit-2 BEFORE any
            # params/compile work (the TrainConfig would refuse anyway,
            # but as a traceback, not a usage error): world | shards
            # AND shards | the fixed bench batch of 8
            if args.world < 1 or shards % args.world or 8 % shards:
                print(f"apex-tpu-bench: --train-chaos needs --world "
                      f">= 1 dividing --grad-shards (got {args.world}/"
                      f"{shards}), and --grad-shards dividing the "
                      f"bench batch of 8", file=sys.stderr)
                sys.exit(2)
            if args.tp < 1 or 32 % args.tp:
                # the bench model's hidden is the TrainConfig default
                # (32); same loud pre-compile refusal as the trainer CLI
                print(f"apex-tpu-bench: --train-chaos --tp {args.tp} "
                      f"must be >= 1 and divide the bench model's "
                      f"hidden of 32", file=sys.stderr)
                sys.exit(2)
            _train_chaos_bench(args.steps, args.world, args.grad_shards,
                               args.emit_baseline, tp=args.tp)
        elif has_serve:
            import argparse

            ap = argparse.ArgumentParser(prog="apex-tpu-bench")
            ap.add_argument("--serve", action="store_true")
            ap.add_argument("--steps", type=int, default=16,
                            help="decode steps to run (the workload "
                                 "keeps slots busy with backfill)")
            ap.add_argument("--serve-slots", type=int, default=4)
            ap.add_argument("--deadline-ms", type=float, default=None,
                            help="per-request latency budget; misses "
                                 "show up as deadline_exceeded in the "
                                 "serve_decode entry")
            ap.add_argument("--max-queue", type=int, default=None,
                            help="bound the admission backlog; overflow "
                                 "is shed per --shed-policy and counted "
                                 "in rejected/shed_rate")
            ap.add_argument("--shed-policy", default="reject-newest",
                            choices=["reject-newest", "shed-oldest",
                                     "priority"])
            ap.add_argument("--serve-max-len", type=int, default=64,
                            help="per-request context bound (prompt + "
                                 "generated); deep mixed-length "
                                 "workloads need it above the default")
            ap.add_argument("--prompt-len", default="8",
                            help="scripted prompt length: N, or MIN:MAX "
                                 "for a seeded mixed-length workload")
            ap.add_argument("--shared-prefix", type=int, default=0,
                            help="every prompt starts with this many "
                                 "shared tokens (the fleet-wide system "
                                 "prompt --prefix-cache deduplicates)")
            ap.add_argument("--page-size", type=int, default=None,
                            help="tokens per KV page: paged block pool "
                                 "instead of per-slot reservation")
            ap.add_argument("--num-pages", type=int, default=None,
                            help="pool pages incl. the null page "
                                 "(default: slot-cache-equivalent "
                                 "capacity; smaller overcommits)")
            ap.add_argument("--prefix-cache", action="store_true",
                            help="share read-only prompt-prefix pages "
                                 "across requests (needs --page-size)")
            ap.add_argument("--emit-baseline", nargs="?",
                            const="BENCH_BASELINE_SERVE.json",
                            default=None,
                            help="write the capture as a suite JSON "
                                 "(default BENCH_BASELINE_SERVE.json)")
            ap.add_argument("--metrics-port", type=int, default=None,
                            help="serve live Prometheus /metrics + JSON "
                                 "/metrics.json while the bench runs "
                                 "(0 = ephemeral port)")
            ap.add_argument("--metrics-snapshot", default=None,
                            help="commit an atomic mergeable metrics "
                                 "snapshot at exit (gateable by "
                                 "check_regression, mergeable by "
                                 "tools/metrics_merge.py)")
            ap.add_argument("--tenants", type=int, default=0,
                            help="label the scripted workload round-"
                                 "robin across N tenants (per-tenant "
                                 "series in the live metrics)")
            ap.add_argument("--replicas", type=int, default=1,
                            help="run the workload over N thread-backed "
                                 "engine replicas under the fleet "
                                 "controller; the entry gains failovers/"
                                 "hedge_fired/replica_dead/migrations")
            ap.add_argument("--hedge-ms", type=float, default=None,
                            help="hedged dispatch threshold (needs "
                                 "--replicas >= 2)")
            ap.add_argument("--heartbeat-ms", type=float, default=None,
                            help="replica heartbeat interval (needs "
                                 "--replicas >= 2; default 50)")
            ap.add_argument("--trace-jsonl", default=None,
                            help="per-request span traces as Perfetto-"
                                 "loadable Chrome-trace JSON; with "
                                 "--replicas N the fleet journey lands "
                                 "here plus one file per replica at "
                                 "PATH.rK")
            ap.add_argument("--trace-sample", type=float, default=None,
                            help="seeded head-sampling rate over "
                                 "request journeys; bad outcomes are "
                                 "always promoted (needs --trace-jsonl)")
            ap.add_argument("--flight-recorder", default=None,
                            help="crash-time postmortem dump path; with "
                                 "--replicas N one recorder per replica "
                                 "(PATH.rK, auto-dump on that replica's "
                                 "death) plus the fleet-plane PATH")
            ap.add_argument("--tp", type=int, default=1,
                            help="tensor-parallel mesh size: shard the "
                                 "bench engine (params + KV pool on the "
                                 "head axis) over N devices — the "
                                 "serve_decode tokens/s scaling curve; "
                                 "workload provenance records it so the "
                                 "gate never compares mesh shapes")
            ap.add_argument("--tp-sync", default="exact",
                            choices=["exact", "overlap", "relaxed"],
                            help="per-layer cross-rank sync under --tp "
                                 ">= 2 (exact = bit-identical oracle)")
            ap.add_argument("--disagg", action="store_true",
                            help="disaggregated prefill/decode fleet: "
                                 "dedicated prefill replicas stream "
                                 "certified KV pages into the decode "
                                 "pool (needs --page-size + "
                                 "--prefix-cache and --replicas >= 2 "
                                 "or --roles)")
            ap.add_argument("--roles", default=None, metavar="P:D",
                            help="prefill:decode replica split (needs "
                                 "--disagg; default 1:(replicas-1))")
            ap.add_argument("--diurnal", action="store_true",
                            help="drive the fleet through one seeded "
                                 "compressed diurnal day instead of an "
                                 "upfront burst (needs --replicas >= 2 "
                                 "or --disagg)")
            ap.add_argument("--cost-ledger", default=None, metavar="PATH",
                            help="write the device-independent compiled-"
                                 "step cost ledger (per-phase FLOPs/HBM "
                                 "bytes from the benched AOT artifacts, "
                                 "apex_tpu.cost_ledger/v1) — gateable by "
                                 "check_regression, diffable by "
                                 "tools/cost_diff.py")
            ap.add_argument("--chip-spec", default=None,
                            help="price the ledger roofline against this "
                                 "chip generation (e.g. v5p, v6e; "
                                 "default: detected chip, else the non-"
                                 "gating cpu spec; needs --cost-ledger)")
            ap.add_argument("--spec-draft-len", type=int, default=None,
                            metavar="K",
                            help="speculative decoding: host n-gram "
                                 "drafts of K tokens per slot verified "
                                 "by one compiled K+1-position step — "
                                 "the entry gains accepted_tokens_per_"
                                 "step / spec_accept_rate / spec_tokens"
                                 "_per_s (higher-is-better) and spec "
                                 "workload provenance the gate refuses "
                                 "to compare across")
            ap.add_argument("--decode-policy", default=None,
                            metavar="POLICY",
                            help="decode-policy seam: greedy | "
                                 "top_p[=P] | min_p[=M] | spec(POLICY) "
                                 "with optional ',t=T' (beam-like "
                                 "policies are refused — no exact "
                                 "per-token acceptance test exists)")
            ap.add_argument("--kv-quant", default=None,
                            choices=["int8", "mxfp8"],
                            help="block-scale KV-cache quantization: "
                                 "K/V pages as codec bytes + per-"
                                 "(token, head) fp32 scales — the "
                                 "entry's resident_tokens_per_hbm_byte "
                                 "carries the capacity win and the "
                                 "kv_quant/quant_block workload axes "
                                 "refuse fp32 baselines (incompatible "
                                 "with --spec-draft-len)")
            args, _ = ap.parse_known_args(sys.argv[1:])
            _serve_bench(args.steps, args.serve_slots,
                         args.emit_baseline,
                         deadline_ms=args.deadline_ms,
                         max_queue=args.max_queue,
                         shed_policy=args.shed_policy,
                         max_len=args.serve_max_len,
                         prompt_len=args.prompt_len,
                         shared_prefix=args.shared_prefix,
                         page_size=args.page_size,
                         num_pages=args.num_pages,
                         prefix_cache=args.prefix_cache,
                         metrics_port=args.metrics_port,
                         metrics_snapshot=args.metrics_snapshot,
                         tenants=args.tenants,
                         replicas=args.replicas,
                         hedge_ms=args.hedge_ms,
                         heartbeat_ms=args.heartbeat_ms,
                         trace_jsonl=args.trace_jsonl,
                         trace_sample=args.trace_sample,
                         flight_recorder=args.flight_recorder,
                         tp=args.tp, tp_sync=args.tp_sync,
                         disagg=args.disagg, roles=args.roles,
                         diurnal=args.diurnal,
                         cost_ledger=args.cost_ledger,
                         chip_spec=args.chip_spec,
                         spec_draft_len=args.spec_draft_len,
                         decode_policy=args.decode_policy,
                         kv_quant=args.kv_quant)
        elif has_telemetry:
            import argparse

            ap = argparse.ArgumentParser(prog="apex-tpu-bench")
            ap.add_argument("--telemetry-jsonl", default=None)
            ap.add_argument("--trace-jsonl", default=None,
                            help="write per-step span traces as "
                                 "Perfetto-loadable Chrome-trace JSON "
                                 "(usable with or without "
                                 "--telemetry-jsonl)")
            ap.add_argument("--flight-recorder", default=None,
                            help="crash-time flight-recorder dump path")
            ap.add_argument("--steps", type=int, default=8)
            ap.add_argument("--watchdog-timeout", type=float, default=None,
                            help="seconds a train step may block before a "
                                 "collective_stall event lands in the JSONL")
            args, _ = ap.parse_known_args(sys.argv[1:])
            _telemetry_bench(args.telemetry_jsonl, args.steps,
                             watchdog_timeout=args.watchdog_timeout,
                             trace_jsonl=args.trace_jsonl,
                             flight_path=args.flight_recorder)
        elif has_subset:
            import argparse

            ap = argparse.ArgumentParser(prog="apex-tpu-bench")
            ap.add_argument("--kernels", default=None,
                            help="comma-separated bench subset "
                                 "(e.g. fused_adam_1b,layer_norm)")
            ap.add_argument("--emit-baseline", nargs="?",
                            const="BENCH_BASELINE.json", default=None,
                            help="write the capture as a committed-"
                                 "baseline suite JSON (default "
                                 "BENCH_BASELINE.json)")
            args, _ = ap.parse_known_args(sys.argv[1:])
            _subset_bench(args.kernels, args.emit_baseline)
        else:
            here = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))
            bench = os.path.join(here, "bench.py")
            if os.path.exists(bench):
                sys.argv = [bench] + sys.argv[1:]
                runpy.run_path(bench, run_name="__main__")
            else:
                _inline_bench()
    if guard.should_stop():
        # console record on rank 0 only (multi-host bench: one banner);
        # the bus event fires everywhere for per-host consumers
        publish_event("bench_preempted", level="warning",
                      emit=is_rank_zero(),
                      signal=guard.received_signal,
                      action="results above this line are complete")
        # a truncated run must not read as a successful benchmark to the
        # caller's exit-code check; keep the conventional signal status
        sys.exit(128 + guard.received_signal)


if __name__ == "__main__":
    main()
