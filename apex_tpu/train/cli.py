"""``apex-tpu-train`` — the config-driven production trainer entry point.

Runs the elastic, preemption-tolerant trainer under its supervisor::

    apex-tpu-train --steps 32 --world 2 --grad-shards 2 \\
        --checkpoint-dir /ckpt --save-every 4 --max-restarts 2

    # elastic: drain at world 2, resume at 1, finish back at 2 —
    # bit-exactly (the canonical shard reduction)
    apex-tpu-train --steps 32 --elastic 2:1:2 --grad-shards 2 \\
        --checkpoint-dir /ckpt --chaos preempt:8,preempt:16

    # chaos smoke: crash mid-step AND mid-save, survive both
    apex-tpu-train --steps 24 --checkpoint-dir /ckpt --save-every 4 \\
        --max-restarts 2 --chaos crash-step:9,crash-save:12

``--chaos`` is a seeded deterministic schedule (the same harness tier-1
drives): ``crash-step:N`` (fatal error before step N — warm restart),
``crash-save:N`` (process dies mid-commit of checkpoint N — the previous
step stays restorable), ``preempt:N`` (coordinated drain at step N; with
``--elastic`` each drain advances the world schedule), ``nan-burst:N:L``
(L non-finite steps from N — the overflow-storm guard rail).

``--tp N`` arms the tensor axis: each grad micro-shard's forward/backward
runs over the PR-15 head-axis mesh (gather-compute-slice — bit-identical
to ``--tp 1``). Elastic schedules may spell entries ``W`` or ``WxT``, but
every ``T`` must equal ``--tp``: a live tp resize is refused at parse
time (exit 2) — changing tp is an explicit checkpoint reshard across a
restart, never an in-job transition. The device envelope is checked
up front too: ``max(worlds) × tp`` must fit the host's device count.

Contradictory or inert flag combinations are usage errors (exit 2)
refused BEFORE anything compiles — the serve/fleet CLI precedent. A
SIGTERM mid-run triggers the coordinated drain: one final checkpoint
commits, the summary prints, exit is clean.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

PROG = "apex-tpu-train"


def _usage(msg: str) -> int:
    print(f"{PROG}: {msg}", file=sys.stderr)
    return 2


def parse_chaos(spec: str, injector, steps: int,
                save_every: int = 0) -> Optional[str]:
    """Apply a ``--chaos`` schedule to ``injector``; returns an error
    message (the caller exits 2) or None. Inert entries — a step beyond
    ``--steps``, or a ``crash-save`` at a step the save cadence never
    commits — are refused, not silently ignored."""
    parsed = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        kind, _, arg = entry.partition(":")
        try:
            nums = [int(x) for x in arg.split(":")] if arg else []
        except ValueError:
            return f"--chaos entry {entry!r}: malformed step number"
        if kind in ("crash-step", "crash-save", "preempt") \
                and len(nums) == 1:
            if not 0 <= nums[0] < steps:
                return (f"--chaos {entry!r}: step outside the run "
                        f"[0, {steps}) — the fault would never fire")
        elif kind == "nan-burst" and len(nums) == 2:
            if not 0 <= nums[0] < steps or nums[1] < 1:
                return f"--chaos {entry!r}: burst outside the run"
        else:
            return (f"--chaos entry {entry!r}: expected crash-step:N, "
                    f"crash-save:N, preempt:N, or nan-burst:N:L")
        parsed.append((kind, nums, entry))
    # which steps the run will actually commit: the cadence, the final
    # step, and every scheduled preemption drain — a crash-save anywhere
    # else would silently never fire
    saved = {steps - 1} | {n for k, (n, *_), _ in parsed
                           if k == "preempt"}
    if save_every > 0:
        saved |= set(range(0, steps, save_every))
    for kind, nums, entry in parsed:
        if kind == "crash-step":
            injector.crash_on_train_step(nums[0])
        elif kind == "crash-save":
            if nums[0] not in saved:
                return (f"--chaos {entry!r}: step {nums[0]} is never "
                        f"saved (cadence --save-every "
                        f"{save_every or 'off'}, final step "
                        f"{steps - 1}, preempt drains) — the fault "
                        f"would never fire")
            injector.crash_during_checkpoint_save(nums[0])
        elif kind == "preempt":
            injector.preempt_at_step(nums[0])
        else:
            injector.nan_burst(nums[0], nums[1])
    return None


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog=PROG, description="elastic, preemption-tolerant trainer "
                               "(docs/training.md)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--world", type=int, default=1,
                    help="data-parallel degree (thread-faked ranks on "
                         "CPU; must divide --grad-shards)")
    ap.add_argument("--grad-shards", type=int, default=1,
                    help="fixed micro-shard count — the world-"
                         "independent gradient partition that makes "
                         "elastic restarts bit-exact")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: each grad micro-shard "
                         "runs over the head-axis serving mesh, "
                         "bit-identical to --tp 1; fixed for the job "
                         "(a tp change is an explicit reshard)")
    ap.add_argument("--elastic", default=None, metavar="W1:W2:...",
                    help="world schedule: each coordinated preemption "
                         "drain relaunches at the next entry (needs "
                         "--checkpoint-dir; replaces --world). Entries "
                         "may be W or WxT, but T must equal --tp — "
                         "live tp resizes are refused")
    ap.add_argument("--amp", default="dynamic", choices=["off", "dynamic"])
    ap.add_argument("--checkpoint-dir", default=None,
                    help="sharded atomic checkpoints + elastic restore "
                         "land here; resume is automatic")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint cadence in steps (0 = only the "
                         "final/preemption commit; needs "
                         "--checkpoint-dir)")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="bounded warm restarts after fatal step errors "
                         "(exponential backoff between attempts)")
    ap.add_argument("--chaos", default=None,
                    help="seeded fault schedule, e.g. "
                         "crash-step:3,crash-save:4,preempt:6 (needs "
                         "--checkpoint-dir and --max-restarts >= 1)")
    ap.add_argument("--telemetry-jsonl", default=None,
                    help="per-step telemetry rows + mirrored events")
    ap.add_argument("--watchdog-timeout", type=float, default=None,
                    help="seconds a gradient exchange / commit barrier "
                         "may block before a collective_stall event")
    args = ap.parse_args(argv)

    from apex_tpu.utils.env import device_block, enable_compile_cache

    enable_compile_cache()

    # ---- the usage-error matrix: refuse contradictions loudly BEFORE
    # ---- any params are built or anything compiles (fleet precedent).
    # ---- Geometry/range rules live in ONE place — TrainConfig.validate,
    # ---- converted to exit 2 below — only the flag interplay validate
    # ---- cannot see (elastic schedules, chaos) is checked here.
    if args.elastic is not None and args.world != 1:
        return _usage("--elastic is a world schedule; it replaces "
                      "--world — pass exactly one of the two")
    if args.elastic is not None and not args.checkpoint_dir:
        return _usage("--elastic needs --checkpoint-dir: a resize "
                      "crosses a restart, and only a committed sharded "
                      "checkpoint carries the state over")
    worlds = [args.world]
    if args.elastic is not None:
        worlds = []
        for ent in args.elastic.split(":"):
            if not ent:
                continue
            w, _, t = ent.partition("x")
            try:
                world_n = int(w)
                tp_n = int(t) if t else args.tp
            except ValueError:
                return _usage(f"--elastic {args.elastic!r}: expected "
                              f"colon-separated world sizes (W or WxT)")
            if tp_n != args.tp:
                return _usage(
                    f"--elastic entry {ent!r}: live tp resize refused — "
                    f"elastic resizes move the dp axis only (--tp "
                    f"{args.tp} is fixed for the job); a tp change is "
                    f"an explicit checkpoint reshard across a restart")
            worlds.append(world_n)
        if not worlds:
            return _usage("--elastic needs at least one world size")
    for w in worlds:
        # validate() only sees worlds[0] (config.world) — every later
        # schedule entry must hold the same shard-divisibility contract
        if w < 1:
            return _usage(f"world size {w} must be >= 1")
        if args.grad_shards < 1 or args.grad_shards % w:
            return _usage(
                f"world {w} must divide --grad-shards "
                f"{args.grad_shards} (equal shards per rank is what "
                f"makes elastic restarts bit-exact)")
    if args.chaos is not None:
        if args.max_restarts < 1:
            return _usage("--max-restarts 0 with a --chaos schedule: "
                          "an injected crash would simply kill the run "
                          "— give the supervisor a restart budget")
        if not args.checkpoint_dir:
            return _usage("--chaos needs --checkpoint-dir: crash "
                          "recovery restores the last committed step")

    from apex_tpu.train.config import TrainConfig

    try:
        config = TrainConfig(
            steps=args.steps, batch=args.batch, seq=args.seq,
            vocab=args.vocab, hidden=args.hidden, lr=args.lr,
            seed=args.seed, world=worlds[0],
            grad_shards=args.grad_shards, tp=args.tp, amp=args.amp,
            checkpoint_dir=args.checkpoint_dir,
            save_every=args.save_every,
            telemetry_jsonl=args.telemetry_jsonl,
            watchdog_timeout_s=args.watchdog_timeout).validate()
    except ValueError as e:
        return _usage(str(e))

    if args.tp > 1:
        # device-envelope geometry, still before anything compiles: the
        # certified composition is per-rank dp device blocks × the tp
        # mesh, so the PEAK scheduled world must fit alongside the mesh
        import jax

        ndev = len(jax.devices())
        if max(worlds) * args.tp > ndev:
            return _usage(
                f"--tp {args.tp} at world {max(worlds)} needs "
                f"{max(worlds) * args.tp} devices, have {ndev} — the "
                f"dp × tp envelope must fit the host (on CPU force "
                f"more with XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)")

    injector = None
    if args.chaos is not None:
        from apex_tpu.resilience import FaultInjector

        injector = FaultInjector(seed=args.seed)
        err = parse_chaos(args.chaos, injector, args.steps,
                          save_every=args.save_every)
        if err is not None:
            return _usage(err)

    from apex_tpu.train.supervisor import TrainSupervisor

    supervisor = TrainSupervisor(
        config, injector=injector, max_restarts=args.max_restarts,
        world_schedule=worlds).install_signals()
    report = supervisor.run()
    report["device"] = device_block()
    print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
