"""Compiled-step cost ledgers — phase-attributed, device-independent.

Every committed bench capture is a CPU-smoke record (ROADMAP standing
caveat): the regression gate has never gated a number that survives a
host swap. This module extracts what IS device-independent from the
engine's saved AOT artifacts: a deterministic per-executable **cost
ledger** — FLOPs, HBM bytes (operand-byte model), arithmetic intensity,
an op-family histogram, and a per-phase attribution keyed on the
``jax.named_scope`` markers the GPT-2 serving forwards carry
(``ln_qkv`` / ``attention`` / ``mlp`` / ``sampling`` / ``collective``).
Phase sums reconcile **exactly** with the executable totals by
construction (one walk accumulates both) — and the reconciliation is
re-derived independently in tier-1, the PR-13 precedent.

The walk generalizes ``serve/tp.py:count_collectives``: instead of
substring-counting collectives it parses every op line of the lowered
StableHLO (with MLIR debug info, so scope paths ride the ``loc(...)``
metadata), prices it with an analytic per-op model, and multiplies
``stablehlo.while`` region bodies by their parsed trip counts. On top
rides a roofline layer (:data:`CHIP_SPECS`): per-phase predicted step
time, a predicted-MFU bound, and — for tensor-parallel engines —
collective bytes per sync mode priced from the PR-15 contract.

**Import-time stdlib only.** Like ``monitor/export.py``, this module
never imports jax (or any ``apex_tpu`` sibling) at import time: the
jax-free consumers — ``tools/cost_diff.py`` and
``tools/check_regression.py`` — load it by file path, so the ONE
spelling of the ledger/gate-metric rules lives here and can never
diverge (the histogram_quantile delegation precedent). Functions that
touch jax objects (``lowered``/``compiled``) only call methods on them.

Entry points: ``Engine.cost_ledger()`` (serve/engine.py — rides the
saved ``_decode_lowered``/``_prefill_lowered``, never re-tracing),
``apex-tpu-bench --serve --cost-ledger PATH``, and the jax-free
``tools/cost_diff.py``. See docs/performance.md "Cost ledgers and
roofline gating".
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Tuple

LEDGER_SCHEMA = "apex_tpu.cost_ledger/v1"

# the phase vocabulary of the annotated GPT-2 serving forwards; "verify"
# is the speculative verify step's own work (the final LN + logits
# projection per verify position plus the in-graph acceptance test —
# PR 18; absent from one-token executables); "other" is the explicit
# remainder bucket (embedding lookup, cache advance, PRNG plumbing) so
# phase sums always equal the executable total
PHASES = ("ln_qkv", "attention", "mlp", "sampling", "verify",
          "collective", "other")

SYNC_MODES = ("exact", "overlap", "relaxed")

# chip-spec table for the roofline layer (bf16 peak TFLOPs, HBM GB/s —
# the same peaks utils/prof.py reports). "cpu" is the off-silicon
# fallback: its roofline projections are shape-checking only, so it is
# marked non-gating and `ledger_gate_metrics` withholds the
# roofline-derived families (the device-independent FLOP/byte/op
# families gate regardless — that is the point of the ledger).
CHIP_SPECS = {
    "v5e": {"tflops": 197.0, "hbm_gbps": 819.0, "gating": True},
    "v6e": {"tflops": 918.0, "hbm_gbps": 1640.0, "gating": True},
    "v5p": {"tflops": 459.0, "hbm_gbps": 2765.0, "gating": True},
    "cpu": {"tflops": 0.5, "hbm_gbps": 40.0, "gating": False},
}

# the device-side fields of CompiledMemoryStats (host_* mirrors skipped:
# they are zero everywhere we run and double the record size) — moved
# here from monitor/memory.py so the ledger and the hbm_snapshot events
# extract through one spelling
MEMORY_STATIC_KEYS = ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3B11FNUZ": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i4": 1, "ui4": 1, "i1": 1,
    "c64": 8, "c128": 16,
}

_FLOAT_PREFIXES = ("f", "bf")

# one scalar-output flop per element; the transcendental subset is also
# tallied separately (mirrors XLA cost_analysis' "transcendentals")
_TRANSCENDENTAL = frozenset({
    "exponential", "exponential_minus_one", "log", "log_plus_one",
    "logistic", "tanh", "sqrt", "rsqrt", "cbrt", "sine", "cosine",
    "tangent", "atan2", "power",
})
_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "negate", "abs", "sign", "floor", "ceil", "round_nearest_afz",
    "round_nearest_even", "remainder", "select", "clamp", "compare",
}) | _TRANSCENDENTAL
_REDUCES = frozenset({"reduce", "reduce_window"})
# control/region scaffolding: never recorded as ops (their region
# bodies are walked line by line instead)
_SKIP_OPS = frozenset({"while", "return", "func", "call", "if", "case"})

_COLLECTIVES = ("all_gather", "all_reduce", "all_to_all",
                "collective_permute")

_OP_RE = re.compile(r'(?:^|\s|=\s|")(?:stablehlo|mhlo|chlo|func)\.'
                    r'([A-Za-z_][A-Za-z0-9_]*)')
_LOC_TAIL_RE = re.compile(r'\s*loc\((?:#(loc[0-9]*))?\)\s*$')
_LOC_DEF_RE = re.compile(r'^#(loc[0-9]*) = loc\((.*)\)\s*$')
_LOC_REF_RE = re.compile(r'#(loc[0-9]*)')
_QUOTED_RE = re.compile(r'"([^"]*)"')
_TENSOR_RE = re.compile(r'tensor<([^>]*)>')
_CONTRACT_RE = re.compile(r'contracting_dims\s*=\s*\[([^\]]*)\]'
                          r'\s*x\s*\[([^\]]*)\]')
_SCALAR_CONST_RE = re.compile(
    r'%(\S+)\s*=\s*stablehlo\.constant\s+dense<(\d+)>\s*:\s*tensor<[su]?i')
_FUNC_RE = re.compile(r'^\s*func\.func\s+(?:[a-z]+\s+)?@([\w$.-]+)\s*\(')
# ``%n = stablehlo.minimum %a, %b : tensor<i32>``: a scalar clipped to a
# bound (a loop's data trip count to the most it can be)
_SCALAR_MIN_RE = re.compile(
    r'%(\S+)\s*=\s*stablehlo\.minimum\s+%(\S+?),\s*%(\S+?)\s*:\s*'
    r'tensor<[su]?i\d+>')
_CALL_RE = re.compile(r'(?<![\w.])(?:func\.)?call\s+@([\w$.-]+)')


def _sig6(x: float) -> float:
    """6 significant digits — stable, readable floats in the ledger."""
    return float(f"{float(x):.6g}")


# --------------------------------------------------------------- parsing

def _tensor_info(spec: str) -> Tuple[int, str, int]:
    """``(elements, dtype, bytes)`` for a ``tensor<...>`` body like
    ``2x256xf32`` (scalar tensors have no dims; dynamic dims count 1)."""
    parts = spec.split("x")
    dtype = parts[-1].strip()
    elems = 1
    for p in parts[:-1]:
        p = p.strip()
        if p.isdigit():
            elems *= int(p)
    return elems, dtype, elems * _DTYPE_BYTES.get(dtype, 4)


def _is_float(dtype: str) -> bool:
    return dtype.startswith(_FLOAT_PREFIXES)


def _signature(body: str) -> Optional[Tuple[Optional[List[str]], List[str]]]:
    """``(operand_tensor_specs | None, result_tensor_specs)`` from the
    trailing type signature of an op line (loc already stripped).
    ``None`` operands means the uniform form (``%r = op %a, %b : T``):
    the caller counts ``%``-refs instead."""
    idx = body.rfind(" : ")
    if idx < 0:
        return None
    sig = body[idx + 3:].strip()
    if "->" in sig:
        lhs, rhs = sig.split("->", 1)
        return _TENSOR_RE.findall(lhs), _TENSOR_RE.findall(rhs)
    return None, _TENSOR_RE.findall(sig)


def _uniform_operand_count(body: str) -> int:
    """Operand count for the uniform type form: ``%``-refs on the RHS of
    the assignment (attributes never contain ``%``)."""
    rhs = body.split(" = ", 1)[-1]
    idx = rhs.rfind(" : ")
    if idx >= 0:
        rhs = rhs[:idx]
    return rhs.count("%")


def _phase_resolver(text: str) -> Callable[[Optional[str]], str]:
    """Map a ``#locN`` id to its phase by walking the MLIR location
    footer: scope paths live in quoted strings
    (``"jit(f)/jit(main)/attention/dot_general"``), possibly behind
    callsite/fused chains of further ``#loc`` refs. Innermost scope
    wins, so a ``collective`` scope nested inside ``mlp`` attributes to
    ``collective``."""
    defs: Dict[str, str] = {}
    for line in text.splitlines():
        if not line.startswith("#loc"):
            continue
        m = _LOC_DEF_RE.match(line)
        if m:
            defs[m.group(1)] = m.group(2)
    memo: Dict[str, str] = {}

    def from_path(name: str) -> Optional[str]:
        # loc bodies quote SOURCE FILE paths too ("/a/verify/drive.py");
        # a directory that happens to be named after a phase must not
        # claim the op — only named_scope paths (last segment is the op
        # primitive, never a filename) are phase evidence
        if name.rsplit("/", 1)[-1].endswith((".py", ".pyi")):
            return None
        for seg in reversed(name.split("/")):
            for ph in PHASES[:-1]:
                if seg == ph or (seg.startswith(ph + "_")
                                 and seg[len(ph) + 1:].isdigit()):
                    return ph
        return None

    def resolve(loc: Optional[str], depth: int = 0) -> str:
        if loc is None or loc not in defs or depth > 25:
            return "other"
        if loc in memo:
            return memo[loc]
        memo[loc] = "other"          # cycle guard
        body = defs[loc]
        for q in _QUOTED_RE.findall(body):
            ph = from_path(q)
            if ph:
                memo[loc] = ph
                return ph
        for ref in _LOC_REF_RE.findall(body):
            if ref != loc:
                ph = resolve(ref, depth + 1)
                if ph != "other":
                    memo[loc] = ph
                    return ph
        return memo[loc]

    return resolve


def _while_spans(lines: List[str], i: int, end: int
                 ) -> Optional[Tuple[int, int, int, int, int]]:
    """Region spans of the ``stablehlo.while`` at line ``i``:
    ``(cond_start, cond_end, body_start, body_end, next_line)`` —
    half-open line ranges found by brace matching from the ``cond {``
    opener (attribute-dict braces are balanced per line at depth >= 1,
    so only region braces cross zero)."""
    j = i
    while j < min(i + 3, end) and "cond" not in lines[j]:
        j += 1
    if j >= min(i + 3, end) or "{" not in lines[j]:
        return None
    depth = 0
    opens: List[int] = []
    closes: List[int] = []
    k = j
    while k < end:
        for ch in lines[k]:
            if ch == "{":
                depth += 1
                if depth == 1:
                    opens.append(k)
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    closes.append(k)
        if len(closes) == 2:
            return (opens[0] + 1, closes[0], opens[1] + 1, closes[1],
                    closes[1] + 1)
        k += 1
    return None


def _trip_count(lines: List[str], start: int, end: int,
                consts: Dict[str, int], header: str) -> Optional[int]:
    """Trip count of a while loop from its cond region: the jax
    counted-loop pattern ``compare LT, %iterArg, %bound`` where
    ``%bound`` is a scalar integer constant (in the region or collected
    earlier at module scope), or a loop-carried value whose initial
    value (named on the ``header`` line) is one: ``consts`` also holds
    every scalar the program clipped to a constant with ``minimum``, at
    that constant. ``None`` when the loop is not provably counted
    (walked with multiplier 1 + a ledger note)."""
    local = dict(consts)
    cmp_line = None
    for k in range(start, end):
        m = _SCALAR_CONST_RE.search(lines[k])
        if m:
            local[m.group(1)] = int(m.group(2))
        if "stablehlo.compare" in lines[k] and "%iterArg" in lines[k]:
            cmp_line = lines[k]
    if cmp_line is None:
        return None
    names = re.findall(r'%(\S+?)[,\s:]', cmp_line)
    for name in names:
        if name in local and not name.startswith("iterArg"):
            return local[name]
    # a carried bound (the compare's last operand): its initial value
    carried = dict(re.findall(r'%(iterArg\w*) = %([^\s,)]+)', header))
    return consts.get(carried.get(names[-1]))


def _flops_for(op: str, operands: List[Tuple[int, str, int]],
               results: List[Tuple[int, str, int]], body: str) -> int:
    if op == "dot_general":
        if not results:
            return 0
        out_elems = results[0][0]
        contract = 1
        m = _CONTRACT_RE.search(body)
        if m:
            # lhs shape from the signature's first operand spec
            sig = _signature(body)
            lhs_shape: List[int] = []
            if sig and sig[0]:
                parts = sig[0][0].split("x")[:-1]
                lhs_shape = [int(p) for p in parts if p.strip().isdigit()]
            for idx in m.group(1).split(","):
                idx = idx.strip()
                if idx.isdigit() and int(idx) < len(lhs_shape):
                    contract *= lhs_shape[int(idx)]
        return 2 * out_elems * contract
    if op in _REDUCES:
        if operands and _is_float(operands[0][1]):
            return operands[0][0]
        return 0
    if op in _ELEMENTWISE:
        if results and _is_float(results[0][1]):
            return results[0][0]
        # compare on floats produces i1 — charge the operand elements
        if op == "compare" and operands and _is_float(operands[0][1]):
            return operands[0][0]
        return 0
    return 0


def walk_module(text: str) -> Dict[str, Any]:
    """Deterministic analytic walk of a lowered StableHLO module (debug-
    info form from :func:`stablehlo_debug_text`). Returns totals, the
    per-phase attribution, the op-family histogram, and collective
    counts/bytes. Phase sums equal totals by construction — one
    accumulation pass feeds both.

    The byte model is XLA's operand-byte convention (every op charges
    operand + result bytes — an HBM upper bound that ignores fusion /
    VMEM reuse; see the ``roofline()`` caveat in utils/prof.py). FLOPs:
    ``dot_general`` = 2·|out|·|contraction|, elementwise float = |out|,
    reduce = |in|; data movement (reshape/convert/slice/...) = 0.
    ``stablehlo.while`` bodies multiply by the parsed trip count, so a
    verify scan prices every scanned position. **A trip count that is
    data** is priced at the WORST case where the program clips it to a
    constant (``minimum(n, K)`` feeding the loop: the decode attention's
    chunk loop, clipped to ``max_len // block_k``, is priced as every
    key chunk of the slot, which is what the unrolled chunks it replaced
    were priced as), and once, with a ledger note, where it does not
    (the prefill's loop over a cached prompt head: no hit, no trip).
    ``func.call`` sites walk
    the callee's body at the caller's multiplicity (jax outlines scan
    bodies into ``func.func private`` functions), so outlined loop
    bodies price once per trip, not once per module."""
    lines = text.splitlines()
    resolve = _phase_resolver(text)
    phases = {ph: {"ops": 0, "flops": 0, "hbm_bytes": 0,
                   "transcendentals": 0} for ph in PHASES}
    families: Dict[str, int] = {}
    collectives = {k: 0 for k in ("all_gather", "all_reduce",
                                  "all_to_all", "permute")}
    collective_bytes = 0
    consts: Dict[str, int] = {}
    notes: List[str] = []

    def record(line: str, op: str, mult: int) -> None:
        nonlocal collective_bytes
        locm = _LOC_TAIL_RE.search(line)
        body = line[:locm.start()] if locm else line
        phase = resolve(locm.group(1) if locm else None)
        m = _SCALAR_CONST_RE.search(body)
        if m:
            consts[m.group(1)] = int(m.group(2))
        m = _SCALAR_MIN_RE.search(body)
        if m and (m.group(2) in consts or m.group(3) in consts):
            consts[m.group(1)] = min(consts[n] for n in m.groups()[1:]
                                     if n in consts)
        sig = _signature(body)
        operands: List[Tuple[int, str, int]] = []
        results: List[Tuple[int, str, int]] = []
        if sig is not None:
            op_specs, res_specs = sig
            results = [_tensor_info(s) for s in res_specs]
            if op_specs is None:
                n = 0 if op == "constant" else _uniform_operand_count(body)
                operands = results[:1] * n
            else:
                operands = [_tensor_info(s) for s in op_specs]
        nbytes = sum(o[2] for o in operands) + sum(r[2] for r in results)
        flops = _flops_for(op, operands, results, body)
        bucket = phases[phase]
        bucket["ops"] += mult
        bucket["flops"] += mult * flops
        bucket["hbm_bytes"] += mult * nbytes
        if op in _TRANSCENDENTAL and flops:
            bucket["transcendentals"] += mult * flops
        families[op] = families.get(op, 0) + mult
        if op in _COLLECTIVES:
            key = "permute" if op == "collective_permute" else op
            collectives[key] += mult
            collective_bytes += mult * sum(r[2] for r in results)

    # function bodies by name: jax outlines scan/cond bodies into
    # private funcs reached via func.call — walked at the call site's
    # multiplicity, never at module scope
    funcs: Dict[str, Tuple[int, int]] = {}
    n = len(lines)
    i = 0
    while i < n:
        fm = _FUNC_RE.match(lines[i])
        if fm is None:
            i += 1
            continue
        # the signature line nets +1 (attribute dicts balance within
        # it; the body brace stays open) — accumulate it whole, then
        # close where cumulative depth first returns to zero
        depth = lines[i].count("{") - lines[i].count("}")
        close = None
        k = i + 1
        while k < n and close is None:
            for ch in lines[k]:
                if ch == "{":
                    depth += 1
                elif ch == "}":
                    depth -= 1
                    if depth == 0:
                        close = k
                        break
            k += 1
        if close is None:
            close = n - 1
        funcs[fm.group(1)] = (i + 1, close)
        i = close + 1

    def walk(start: int, end: int, mult: int,
             stack: Tuple[str, ...]) -> None:
        i = start
        while i < end:
            line = lines[i]
            stripped = line.lstrip()
            if stripped.startswith(("#loc", "module")):
                i += 1          # loc metadata / module-attribute lines
                continue
            cm = _CALL_RE.search(line)
            if cm is not None:
                callee = funcs.get(cm.group(1))
                if (callee is not None and cm.group(1) not in stack
                        and len(stack) < 25):
                    walk(callee[0], callee[1], mult,
                         stack + (cm.group(1),))
                i += 1
                continue
            m = _OP_RE.search(line)
            op = m.group(1) if m else None
            if op == "while":
                spans = _while_spans(lines, i, end)
                if spans is None:
                    i += 1
                    continue
            else:
                spans = None
            if spans is not None:
                c0, c1, b0, b1, nxt = spans
                trip = _trip_count(lines, c0, c1, consts, line)
                if trip is None:
                    trip = 1
                    notes.append(f"while@line{i}: trip count not "
                                 f"statically resolvable; counted once")
                walk(c0, c1, mult, stack)   # cond: ~trip cheap compares
                walk(b0, b1, mult * trip, stack)
                i = nxt
                continue
            if op is not None and op not in _SKIP_OPS:
                record(line, op, mult)
            i += 1

    entry = "main" if "main" in funcs else (next(iter(funcs), None))
    if entry is not None:
        walk(funcs[entry][0], funcs[entry][1], 1, (entry,))
    else:
        walk(0, n, 1, ())
    total = {"ops": sum(p["ops"] for p in phases.values()),
             "flops": sum(p["flops"] for p in phases.values()),
             "hbm_bytes": sum(p["hbm_bytes"] for p in phases.values()),
             "transcendentals": sum(p["transcendentals"]
                                    for p in phases.values())}
    total["arithmetic_intensity"] = _sig6(
        total["flops"] / total["hbm_bytes"]) if total["hbm_bytes"] else 0.0
    out = {"total": total, "phases": phases,
           "op_families": dict(sorted(families.items())),
           "collectives": collectives,
           "collective_bytes": collective_bytes}
    if notes:
        out["notes"] = sorted(set(notes))
    return out


# ------------------------------------------------ jax-object extractors

def stablehlo_debug_text(lowered, large_elements_limit: int = 8) -> str:
    """The lowered module's StableHLO text WITH MLIR debug info — scope
    paths appear only in ``loc(...)`` metadata, which the default
    ``as_text()`` strips. ``large_elements_limit`` elides any large
    constant's payload (the walk never needs one)."""
    try:
        ir = lowered.compiler_ir()
        return ir.operation.get_asm(
            enable_debug_info=True,
            large_elements_limit=large_elements_limit)
    except Exception:
        # no debug info available: the walk still totals correctly,
        # every op just lands in the "other" phase
        return lowered.as_text()


def module_facts(stablehlo_text: str) -> Dict[str, int]:
    """What a lowered module carries by value versus by argument:
    ``module_chars`` (text size), ``main_args`` (arguments of the public
    ``main``) and ``max_literal_chars`` (the longest ``dense<...>``
    payload). A program that closes over its weights shows up as few
    arguments, a literal the size of the model, and a text that grows
    with the parameter count; the serving programs must show none of
    the three (tier-1 asserts on ``tiny``, chip_smoke.py prints XL's)."""
    start = stablehlo_text.index("func.func public @main(")
    sig = stablehlo_text[start:stablehlo_text.index(") -> ", start)]
    return {
        "module_chars": len(stablehlo_text),
        "main_args": len(re.findall(r"%arg\d+:", sig)),
        "max_literal_chars": max(
            (len(m) for m in re.findall(r"dense<([^>]*)>",
                                        stablehlo_text)), default=0),
    }


def collective_counts(stablehlo_text: str) -> Dict[str, int]:
    """Collective-op counts by substring — THE spelling behind
    ``serve/tp.py:count_collectives`` (which delegates here). Pre-XLA-
    pass text, so only shard_map-explicit collectives count, never a
    compiler resharding."""
    return {
        "all_gather": stablehlo_text.count("stablehlo.all_gather"),
        "all_reduce": stablehlo_text.count("stablehlo.all_reduce"),
        "all_to_all": stablehlo_text.count("stablehlo.all_to_all"),
        "permute": stablehlo_text.count("collective_permute"),
    }


def expected_collective_ops(n_layer: int, sync: str) -> Dict[str, int]:
    """The per-decode-step collective CONTRACT per sync mode (the PR-15
    contract; ``serve/tp.py:expected_collectives`` delegates here):
    exact = 2 all-gathers/layer, overlap = 4 half-psum all-reduces/layer
    (TokenWeave), relaxed = 2 (one deferred logical all-reduce split in
    slot halves)."""
    if sync == "exact":
        return {"all_gather": 2 * n_layer, "all_reduce": 0}
    if sync == "overlap":
        return {"all_gather": 0, "all_reduce": 4 * n_layer}
    if sync == "relaxed":
        return {"all_gather": 0, "all_reduce": 2 * n_layer}
    raise ValueError(f"unknown tp_sync mode {sync!r}; "
                     f"pick one of {SYNC_MODES}")


def xla_cost_record(compiled) -> Optional[Dict[str, float]]:
    """``compiled.cost_analysis()`` flattened to the stable keys — THE
    spelling the three pre-existing call sites (monitor/metrics.py,
    utils/prof.py, Telemetry.calibrate) now share. ``None`` when the
    backend reports no analysis."""
    if compiled is None:
        return None
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    if not isinstance(ca, dict) or not ca:
        return None
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    if "transcendentals" in ca:
        out["transcendentals"] = float(ca["transcendentals"])
    return out


def xla_flops(compiled) -> float:
    rec = xla_cost_record(compiled)
    return rec["flops"] if rec else 0.0


def memory_analysis_record(compiled) -> Optional[Dict[str, int]]:
    """``compiled.memory_analysis()`` as a plain int dict (plus the
    derived ``reserved_bytes`` total), or ``None`` when the executable
    doesn't expose one. Moved from monitor/memory.py (which delegates
    here) so the ledger and the hbm_snapshot events can never diverge."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None
    out: Dict[str, int] = {}
    for k in MEMORY_STATIC_KEYS:
        v = getattr(ma, k, None)
        if isinstance(v, (int, float)):
            out[k] = int(v)
    if not out:
        return None
    out["reserved_bytes"] = (out.get("argument_size_in_bytes", 0)
                             + out.get("output_size_in_bytes", 0)
                             + out.get("temp_size_in_bytes", 0))
    return out


def executable_record(lowered, compiled=None) -> Dict[str, Any]:
    """One executable's ledger entry: the deterministic analytic walk
    plus XLA's own cost/memory analyses (kept separately under ``xla`` —
    the analytic model is the gateable one; XLA's numbers are the
    cross-check)."""
    rec = walk_module(stablehlo_debug_text(lowered))
    xla: Dict[str, Any] = {}
    cost = xla_cost_record(compiled)
    if cost is not None:
        xla["cost_analysis"] = cost
    mem = memory_analysis_record(compiled)
    if mem is not None:
        xla["memory_analysis"] = mem
    if xla:
        rec["xla"] = xla
    return rec


# ----------------------------------------------------- roofline pricing

def roofline_record(walk: Dict[str, Any], chip: str) -> Dict[str, Any]:
    """Roofline projection of one walked executable on ``chip``: per-
    phase MXU/HBM times, the binding resource, a predicted step time
    (sum of per-phase maxima — phases serialize; within a phase compute
    and memory overlap), and the predicted-MFU bound."""
    spec = CHIP_SPECS.get(chip)
    if spec is None:
        raise ValueError(f"unknown chip spec {chip!r}; "
                         f"pick one of {sorted(CHIP_SPECS)}")
    peak_flops = spec["tflops"] * 1e12
    peak_bw = spec["hbm_gbps"] * 1e9
    per_phase: Dict[str, Any] = {}
    step_s = 0.0
    for ph, p in walk["phases"].items():
        t_mxu = p["flops"] / peak_flops
        t_hbm = p["hbm_bytes"] / peak_bw
        t = max(t_mxu, t_hbm)
        step_s += t
        if p["ops"]:
            per_phase[ph] = {"t_mxu_us": _sig6(t_mxu * 1e6),
                             "t_hbm_us": _sig6(t_hbm * 1e6),
                             "bound": "mxu" if t_mxu > t_hbm else "hbm",
                             "t_us": _sig6(t * 1e6)}
    flops = walk["total"]["flops"]
    return {"chip": chip, "gating": bool(spec["gating"]),
            "per_phase": per_phase,
            "predicted_step_time_us": _sig6(step_s * 1e6),
            "predicted_mfu": _sig6(flops / (peak_flops * step_s))
            if step_s > 0 else 0.0}


def price_collectives(n_layer: int, n_embd: int, num_slots: int,
                      tp: int, dtype_bytes: int = 4) -> Dict[str, Any]:
    """Predicted per-decode-step collective bytes-on-wire per sync mode,
    priced from the PR-15 contract and the model dims (ring cost:
    all-gather moves (tp-1)/tp of the full payload per device,
    all-reduce 2·(tp-1)/tp of the partial). Payloads per layer: exact
    gathers the attention heads [B, e] and the MLP hidden [B, 4e];
    overlap all-reduces two [B, e] partials split in slot halves;
    relaxed lands ONE combined [B, e] partial in halves."""
    ring_ag = (tp - 1) / tp
    ring_ar = 2 * (tp - 1) / tp
    b, e = num_slots, n_embd
    per_layer = {
        "exact": ring_ag * b * (e + 4 * e) * dtype_bytes,
        "overlap": ring_ar * 2 * b * e * dtype_bytes,
        "relaxed": ring_ar * b * e * dtype_bytes,
    }
    return {mode: {"ops": expected_collective_ops(n_layer, mode),
                   "bytes_on_wire_per_step": int(n_layer
                                                 * per_layer[mode])}
            for mode in SYNC_MODES}


# --------------------------------------------------------- ledger build

def build_ledger(executables: Dict[str, Dict[str, Any]],
                 workload: Dict[str, Any],
                 chip: str = "cpu") -> Dict[str, Any]:
    """Assemble the provenance-stamped ledger document. Deterministic:
    no wall clocks, no environment reads — two builds from the same AOT
    artifacts are byte-identical under ``json.dumps(sort_keys=True)``
    (tier-1 asserts exactly that). Writers that want capture provenance
    (git, device_kind, timestamps) stamp it under ``meta`` at write time
    (``apex-tpu-bench --cost-ledger``) so it never breaks determinism
    of the ledger body."""
    spec = CHIP_SPECS.get(chip)
    if spec is None:
        raise ValueError(f"unknown chip spec {chip!r}; "
                         f"pick one of {sorted(CHIP_SPECS)}")
    executables = {name: dict(rec) for name, rec in executables.items()}
    for rec in executables.values():
        rec["roofline"] = roofline_record(rec, chip)
    ledger: Dict[str, Any] = {
        "schema": LEDGER_SCHEMA,
        "chip_spec": chip,
        "gating": bool(spec["gating"]),
        "workload": dict(workload),
        "executables": executables,
    }
    dec = executables.get("decode")
    if dec is not None:
        slots = max(int(workload.get("num_slots", 1)), 1)
        ledger["derived"] = {
            "decode_flops_per_token": _sig6(dec["total"]["flops"] / slots),
            "decode_hbm_bytes_per_token": _sig6(
                dec["total"]["hbm_bytes"] / slots),
            "decode_ops_total": dec["total"]["ops"],
            "predicted_mfu": dec["roofline"]["predicted_mfu"],
        }
    tp = int(workload.get("tp", 1) or 1)
    if tp > 1 and dec is not None:
        n_layer = int(workload.get("n_layer", 0))
        ledger["collective_pricing"] = price_collectives(
            n_layer, int(workload.get("n_embd", 0)),
            int(workload.get("num_slots", 1)), tp,
            int(workload.get("dtype_bytes", 4)))
        sync = workload.get("tp_sync") or "exact"
        ledger["contract"] = {
            "tp_sync": sync,
            "expected": expected_collective_ops(n_layer, sync),
            "counted": dec["collectives"],
        }
    return ledger


# workload/provenance axes on which two ledgers are INCOMPARABLE (the
# check_regression INCOMPARABLE_WORKLOAD_KEYS discipline, extended with
# the ledger-specific axes: a different dtype/page_size/slot count/chip
# spec prices a different step). Dict value = the default for a missing
# key, mirroring tools/check_regression.py.
LEDGER_INCOMPARABLE_KEYS = {
    "tp": 1, "tp_sync": None, "page_size": 0, "dtype": None,
    "num_slots": None, "max_len": None, "chip_spec": None,
    # speculative decoding (PR 18): a verify-step ledger prices
    # draft_len + 1 positions per step — never gate it against a
    # one-token ledger. Missing keys = speculation off (pre-PR-18
    # ledgers are one-token by construction).
    "spec_draft_len": 0, "decode_policy": None,
    # block-scale KV quantization (apex_tpu.quant): a quantized
    # decode step's HBM bytes are the codec bytes + scale planes — a
    # real win that must never gate against an fp32 ledger as if it
    # were an optimization of the same workload. Missing keys =
    # unquantized (pre-quant ledgers stored full-width K/V).
    "kv_quant": None, "quant_block": 0,
}


def is_ledger(doc: Any) -> bool:
    return isinstance(doc, dict) and doc.get("schema") == LEDGER_SCHEMA


def ledger_workload_axes(ledger: Dict[str, Any]) -> Dict[str, Any]:
    w = ledger.get("workload") or {}
    axes = {k: w.get(k, d) for k, d in LEDGER_INCOMPARABLE_KEYS.items()
            if k != "chip_spec"}
    axes["chip_spec"] = ledger.get("chip_spec")
    return axes


def provenance_mismatch(cur: Dict[str, Any],
                        base: Dict[str, Any]) -> List[str]:
    """Human-readable reasons two ledgers must NOT be compared (empty
    list = comparable). ``tools/cost_diff.py`` exits 2 on any."""
    reasons: List[str] = []
    for doc, tag in ((cur, "current"), (base, "baseline")):
        if not is_ledger(doc):
            reasons.append(f"{tag} is not a {LEDGER_SCHEMA} document")
    if reasons:
        return reasons
    ca, ba = ledger_workload_axes(cur), ledger_workload_axes(base)
    for k in LEDGER_INCOMPARABLE_KEYS:
        if ca.get(k) != ba.get(k):
            reasons.append(f"workload.{k}={ca.get(k)!r} vs baseline "
                           f"workload.{k}={ba.get(k)!r}")
    return reasons


def ledger_gate_metrics(ledger: Dict[str, Any]) -> Dict[str, float]:
    """The flat, gateable metric view of a ledger — THE spelling
    check_regression loads by path. The device-independent families
    (``*_flops_per_token`` / ``*_hbm_bytes_per_token`` / ``*_ops_total``,
    lower-is-better) always gate; the roofline-derived families
    (``predicted_mfu`` higher-is-better, ``predicted_step_time_us``)
    only when the chip spec is a gating one (never the cpu fallback)."""
    out: Dict[str, float] = {}
    gating = bool(ledger.get("gating"))
    for k, v in (ledger.get("derived") or {}).items():
        if not gating and k.startswith("predicted_"):
            continue
        out[k] = float(v)
    slots = max(int((ledger.get("workload") or {}).get("num_slots", 1)
                    or 1), 1)
    dec = (ledger.get("executables") or {}).get("decode")
    if dec is not None:
        for ph, p in dec.get("phases", {}).items():
            if not p.get("ops"):
                continue
            out[f"decode.{ph}_flops_per_token"] = _sig6(
                p["flops"] / slots)
            out[f"decode.{ph}_hbm_bytes_per_token"] = _sig6(
                p["hbm_bytes"] / slots)
        if gating:
            out["predicted_step_time_us"] = float(
                dec["roofline"]["predicted_step_time_us"])
    return out


def diff_ledgers(cur: Dict[str, Any],
                 base: Dict[str, Any]) -> Dict[str, Any]:
    """Per-phase / per-op-family / derived deltas between two
    provenance-compatible ledgers (``tools/cost_diff.py`` renders
    this). Ratios are current/baseline; baseline-zero rows report the
    absolute delta only."""
    def row(c: float, b: float) -> Dict[str, Any]:
        r = {"baseline": b, "current": c, "delta": _sig6(c - b)}
        if b:
            r["ratio"] = _sig6(c / b)
        return r

    out: Dict[str, Any] = {"derived": {}, "executables": {}}
    dc, db = cur.get("derived") or {}, base.get("derived") or {}
    for k in sorted(set(dc) & set(db)):
        out["derived"][k] = row(float(dc[k]), float(db[k]))
    ec, eb = cur.get("executables") or {}, base.get("executables") or {}
    for name in sorted(set(ec) & set(eb)):
        c, b = ec[name], eb[name]
        ex: Dict[str, Any] = {
            "total": {k: row(c["total"][k], b["total"][k])
                      for k in ("ops", "flops", "hbm_bytes")},
            "phases": {}, "op_families": {}}
        for ph in PHASES:
            pc = c["phases"].get(ph, {})
            pb = b["phases"].get(ph, {})
            if not (pc.get("ops") or pb.get("ops")):
                continue
            ex["phases"][ph] = {
                k: row(pc.get(k, 0), pb.get(k, 0))
                for k in ("ops", "flops", "hbm_bytes")}
        for fam in sorted(set(c.get("op_families", {}))
                          | set(b.get("op_families", {}))):
            fc = c.get("op_families", {}).get(fam, 0)
            fb = b.get("op_families", {}).get(fam, 0)
            if fc != fb:
                ex["op_families"][fam] = row(fc, fb)
        out["executables"][name] = ex
    return out
