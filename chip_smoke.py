"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                  # a machine with one TPU chip
    python3 chip_smoke.py --four-chips     # INSTEAD: the four-chip leg alone
    python3 chip_smoke.py --rehearse-cpu   # the same legs at `tiny`, HERE

Run through the chip tool from the root of a checkout. Everything happens
in THIS process, one leg after another — a chip belongs to one process, so
nothing is spawned:

(a) **serving leg** — ``apex_tpu.serve.cli.main`` (the ``apex-tpu-serve``
    entry point) serves GPT-2 XL at full width and depth in bf16 from
    seeded random weights: paged pool + prefix cache, ``max_len`` 1024,
    seeded ~128-token prompts, 32 greedy tokens each, AOT-compiled. Run
    twice with one seed: every request must complete, ``decode_compiles``
    must be 1, every token must be in the vocabulary, and the two runs'
    streams must be identical. The compiled programs' ``memory_analysis()``
    and the allocator's peak are printed.
(b) **kernel leg** — every Pallas kernel, COMPILED (asserted), against its
    plain-jnp reference (``chipcheck.py``).

``--four-chips`` runs, on a four-chip host and in place of (a) and (b),
`small` fp32 ``--tp 4`` against ``--tp 1`` through the same entry point
(equal streams, every device holding shards) and then
``__graft_entry__.dryrun_multichip(4)``.

The platform is pinned to ``tpu`` before any backend use, so a missing or
busy chip raises: nothing falls back to the CPU, nothing is caught and
summarised as ok, and any failed check ends the run with a traceback and a
non-zero exit before the result line. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device.

``--rehearse-cpu`` is for debugging the script in the sandbox: `tiny`
instead of XL, kernels interpreted, on whatever platform the environment
selects. Its output and its result line say it is a rehearsal; it proves
nothing about the chip and is never the default.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import time

# the geometry that fitted one 16 GB v5e with room while the cache was not
# donated, the weights are held in fp32 and re-cast per call, and the page
# pool's tiled layout was 1.97x its data (PERF.md "Where the time goes"):
# the prefill program was the high-water mark, 13.9 GB by memory_analysis
# of the 16.91 GB the allocator offers (PR 21; since PR 31 the pool is
# donated and 1.04x its data: not measured again at this geometry).
# 33 pages = 32 usable pages of 64 tokens; each request here pins 3.
XL = dict(config="xl", dtype="bf16", num_slots=8, max_len=1024,
          page_size=64, num_pages=33, requests=12, prompt_len=128,
          max_new_tokens=32)
TINY = dict(config="tiny", dtype="bf16", num_slots=4, max_len=64,
            page_size=8, num_pages=17, requests=6, prompt_len=16,
            max_new_tokens=8)
# four-chip leg: XL has 25 heads, which 4 does not divide; `small` has 12.
# fp32 because tp_sync="exact" promises bit-identity with one chip there.
SMALL_TP = dict(config="small", dtype="fp32", num_slots=4, max_len=256,
                page_size=64, num_pages=None, requests=6, prompt_len=64,
                max_new_tokens=16)


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def gb(n: float) -> str:
    return f"{n / 1e9:.2f} GB"


def serve_argv(spec: dict, *extra: str) -> list:
    argv = ["--config", spec["config"], "--dtype", spec["dtype"],
            "--num-slots", str(spec["num_slots"]),
            "--max-len", str(spec["max_len"]),
            "--page-size", str(spec["page_size"]), "--prefix-cache",
            "--requests", str(spec["requests"]),
            "--prompt-len", str(spec["prompt_len"]),
            "--max-new-tokens", str(spec["max_new_tokens"]),
            "--temperature", "0", "--seed", "0", "--aot"]
    if spec["num_pages"]:
        argv += ["--num-pages", str(spec["num_pages"])]
    return argv + list(extra)


def serve_once(tag: str, argv: list, cache_events: dict) -> dict:
    """One ``apex-tpu-serve`` invocation in this process. Returns the
    parsed request records, the final line, and what the event bus and
    JAX's compile-cache monitor saw meanwhile."""
    from apex_tpu.serve import cli
    from apex_tpu.utils.logging import subscribe_events

    seen = {"hbm": [], "admitted_at": None}

    def on_event(rec):
        if rec.get("event") == "hbm_snapshot" and rec.get("kind") == "static":
            seen["hbm"].append(rec)
        elif rec.get("event") == "serve_request_admitted" \
                and seen["admitted_at"] is None:
            seen["admitted_at"] = time.perf_counter()

    say(f"[{tag}] apex-tpu-serve {' '.join(argv)}")
    before = dict(cache_events)
    out = io.StringIO()
    unsubscribe = subscribe_events(on_event)
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            rc = cli.main(argv)
    finally:
        unsubscribe()
    t1 = time.perf_counter()
    require(rc == 0, f"{tag}: apex-tpu-serve exited {rc}")
    lines = [json.loads(l) for l in out.getvalue().splitlines() if l]
    records, final = lines[:-1], lines[-1]
    setup_s = (seen["admitted_at"] or t1) - t0
    for rec in seen["hbm"]:
        say(f"[{tag}]   {rec['name']}"
            + (f" bucket {rec['bucket']}" if "bucket" in rec else "")
            + f": memory_analysis args {gb(rec['argument_size_in_bytes'])}"
            f" + out {gb(rec['output_size_in_bytes'])}"
            f" + temp {gb(rec['temp_size_in_bytes'])}"
            f" - alias {gb(rec['alias_size_in_bytes'])}"
            f" | lowering: {rec['module_chars'] / 1e6:.2f} MB of text, "
            f"{rec['main_args']} main arguments, largest literal "
            f"{rec['max_literal_chars']} chars")
    hits = cache_events["hits"] - before["hits"]
    writes = cache_events["writes"] - before["writes"]
    say(f"[{tag}]   set-up {setup_s:.1f} s (weights from the seed, engine, "
        f"AOT compile; persistent compile cache: {hits} hits, {writes} "
        f"entries written), first admission to exit {t1 - t0 - setup_s:.1f} s")
    return {"records": records, "final": final, "hbm": seen["hbm"],
            "cache_hits": hits}


def check_served(tag: str, run: dict, spec: dict, vocab: int,
                 platform: str) -> list:
    """Every request completed at full length with in-range tokens, one
    decode compile, on the expected device. Returns the token streams."""
    records, final = run["records"], run["final"]
    require(len(records) == spec["requests"],
            f"{tag}: {len(records)} records for {spec['requests']} requests")
    streams = []
    for rec in sorted(records, key=lambda r: r["request_id"]):
        require(rec["state"] == "completed",
                f"{tag}: {rec['request_id']} ended {rec['state']} "
                f"({rec.get('finish_reason')})")
        toks = rec["generated"]
        require(len(toks) == spec["max_new_tokens"],
                f"{tag}: {rec['request_id']} produced {len(toks)} tokens, "
                f"wanted {spec['max_new_tokens']}")
        require(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
                f"{tag}: {rec['request_id']} has a token outside "
                f"[0, {vocab})")
        streams.append(toks)
    require(final["decode_compiles"] == 1,
            f"{tag}: decode_compiles == {final['decode_compiles']}, not 1")
    require(final["device"]["platform"] == platform,
            f"{tag}: served on {final['device']}, expected {platform}")
    summary = final["summary"]
    say(f"[{tag}]   {summary['completed']}/{summary['requests']} completed, "
        f"{summary['new_tokens']} new tokens in [0, {vocab}), "
        f"decode_compiles {final['decode_compiles']}, prefill_compiles "
        f"{final['prefill_compiles']}, decode steps "
        f"{summary['decode_steps']}, device {final['device']}")
    if platform == "tpu":
        # one run, no warm-up discipline, no load generator: where the
        # time went in THIS run, for the bring-up record — not a benchmark
        # result, and never printed for a CPU rehearsal
        say(f"[{tag}]   the CLI's own clock, this run only: decode step "
            f"p50 {summary['p50_step_ms']} ms / p99 "
            f"{summary['p99_step_ms']} ms, first token after p50 "
            f"{summary['ttft_p50_ms']} ms")
    return streams


def allocator_line(tag: str, jax) -> None:
    for dev in jax.devices():
        stats = dev.memory_stats()
        if not stats:
            say(f"[{tag}] allocator: {dev} reports no memory_stats()")
            continue
        say(f"[{tag}] allocator {dev}: peak_bytes_in_use "
            f"{gb(stats['peak_bytes_in_use'])}, in use now "
            f"{gb(stats['bytes_in_use'])}, limit "
            f"{gb(stats.get('bytes_limit', 0))}")


def serving_leg(jax, spec: dict, cache_events: dict, platform: str,
                expect_cache_hits: bool) -> None:
    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.normalization.fused_layer_norm import pallas_route
    from apex_tpu.utils.env import interpret_default

    cfg = getattr(GPT2Config, spec["config"])()
    mode = "interpreted" if interpret_default() else "compiled"
    route = pallas_route(cfg.n_embd)
    say(f"[serve] kernels on this path at hidden {cfg.n_embd}: "
        f"FusedLayerNorm (training forward, traced by the weight init) -> "
        + (f"{mode}" if route is None else f"jnp path: {route}")
        + f"; flash_attention (same forward) -> {mode}, but dead code under "
        f"the jitted init; the serving step itself -> LayerNorm jnp path: "
        f"num_slots rows by design, attention and MLP are XLA "
        f"(serve/attention.py), no Pallas kernel")
    streams = []
    for i in (1, 2):
        tag = f"serve run {i}"
        run = serve_once(tag, serve_argv(spec), cache_events)
        require({r["name"] for r in run["hbm"]}
                >= {"serve_decode", "serve_prefill"},
                f"{tag}: no memory_analysis for decode and prefill")
        streams.append(check_served(tag, run, spec, cfg.vocab_size,
                                    platform))
        # the engine is a reference cycle (its jitted bound methods);
        # collect it so the second engine does not share HBM with the first
        gc.collect()
        allocator_line(tag, jax)
        if i == 2 and expect_cache_hits:
            # (a rehearsal's `tiny` compiles are under JAX's 1 s
            # threshold for writing a cache entry at all)
            require(run["cache_hits"] > 0,
                    f"{tag}: the second engine's compiles hit the "
                    f"persistent cache 0 times")
    require(streams[0] == streams[1],
            "the same seed gave different streams on the second run")
    say("[serve] streams identical across the two runs: yes")


def kernel_leg(jax) -> None:
    import jax.numpy as jnp

    import chipcheck

    results = chipcheck.run_checks(jax, jnp)
    bad = [name for name, r in results.items() if not r["pass"]]
    require(not bad, f"kernels out of tolerance vs jnp: {bad}")
    say(f"[kernel] {len(results)}/{len(results)} within tolerance of "
        f"their jnp references")


def four_chip_leg(jax, cache_events: dict, platform: str) -> None:
    """The builder's four-chip check: `small` fp32 tp=4 (exact sync)
    against tp=1 through the same entry point, every device holding
    shards, then the manually-parallel training step over real ICI."""
    from apex_tpu.models.gpt2 import GPT2Config

    require(len(jax.devices()) >= 4,
            f"--four-chips needs 4 devices, JAX has {len(jax.devices())}")
    vocab = GPT2Config.small().vocab_size
    run4 = serve_once("tp=4", serve_argv(SMALL_TP, "--tp", "4"),
                      cache_events)
    s4 = check_served("tp=4", run4, SMALL_TP, vocab, platform)
    gc.collect()
    allocator_line("tp=4", jax)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:4]]
    if platform == "tpu":
        require(all(p for p in peaks),
                f"tp=4: a device never held a shard (peaks {peaks})")
    run1 = serve_once("tp=1", serve_argv(SMALL_TP), cache_events)
    s1 = check_served("tp=1", run1, SMALL_TP, vocab, platform)
    gc.collect()
    diverged = [i for i, (a, b) in enumerate(zip(s4, s1)) if a != b]
    require(not diverged,
            f"tp=4 streams differ from tp=1 for requests {diverged}")
    say("[four-chips] small fp32 --tp 4 streams equal --tp 1: yes")

    import __graft_entry__

    __graft_entry__.dryrun_multichip(4)
    say("[four-chips] dryrun_multichip(4): both training steps finite")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the script at `tiny` without a chip; "
                         "labelled a rehearsal, proves nothing")
    ap.add_argument("--four-chips", action="store_true",
                    help="run the tp=4-vs-tp=1 and multi-axis training "
                         "leg INSTEAD of the two standard legs (needs "
                         "four devices)")
    args = ap.parse_args(argv)

    import jax

    if not args.rehearse_cpu:
        # before any backend use: a missing or busy chip now raises
        # instead of JAX quietly handing back the CPU
        jax.config.update("jax_platforms", "tpu")

    from apex_tpu._native.build import native_status
    from apex_tpu.utils.env import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse_cpu:
        say("[smoke] REHEARSAL: cut-down model, kernels interpreted — this "
            "run says nothing about the chip")
    else:
        require(dev.platform == "tpu", f"JAX found {device}, not a TPU")
    say(f"[smoke] jax {jax.__version__} | platform {dev.platform} | "
        f"device_kind {dev.device_kind} | devices {len(jax.devices())}")
    say(f"[smoke] compile cache: {cache_dir} ("
        + ("from JAX_COMPILATION_CACHE_DIR"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "JAX_COMPILATION_CACHE_DIR unset") + ")")
    say(f"[smoke] native helpers: {native_status()}")

    cache_events = {"hits": 0, "writes": 0}

    def on_jax_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["writes"] += 1

    jax.monitoring.register_event_listener(on_jax_event)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_leg(jax, cache_events, dev.platform)
    else:
        serving_leg(jax, TINY if args.rehearse_cpu else XL, cache_events,
                    dev.platform, expect_cache_hits=not args.rehearse_cpu)
        say(f"[smoke] serving leg {time.perf_counter() - t0:.0f} s")
        kernel_leg(jax)
    say(f"[smoke] total {time.perf_counter() - t0:.0f} s")
    result = {"ok": True, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
