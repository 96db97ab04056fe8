"""Compiled-program cost registry: roofline attribution + compile observability.

PROFILE.md's roofline rows (histogram attained bandwidth, per-split fixed
costs, "93% of int8 peak") were hand-assembled each round from one-off
probes.  This module makes attained-fraction-of-peak a first-class,
machine-written metric (Williams et al., "Roofline: an insightful visual
performance model", CACM 2009, applied to the histogram-bound cost
structure of LightGBM, Ke et al., NeurIPS 2017):

1. **Program capture.**  ``instrument(name, jax.jit(fn), phase=...)``
   wraps a jitted program.  While the registry is armed, the first call
   of each (shape, dtype, static) signature compiles through the AOT
   path (``fn.lower(...).compile()``) and records the backend's own
   static analysis — ``compiled.cost_analysis()`` (flops, bytes
   accessed), ``compiled.memory_analysis()`` (argument/output/temp
   bytes) — plus the wall-clock compile seconds; subsequent calls run
   the SAME compiled executable directly (identical HLO and compile
   options, so numerics are bit-identical to the plain jit path —
   tests/test_costmodel.py locks this in).  Disabled, the wrapper is a
   flag check and a straight call into the inner jit — zero overhead,
   nothing recorded.

   Contract for instrumented call sites (repo-wide convention already):
   dynamic inputs are POSITIONAL, jit statics are KEYWORD.  A call made
   while JAX is tracing (inner jits inlined into an outer program) or
   under ``jax.disable_jit()`` passes straight through.  Any AOT
   surprise (resharded input, backend quirk) falls back to the inner
   jit and counts ``costmodel/aot_call_fallback`` — capture must never
   break training.

2. **Peak table.**  Per-``device_kind`` hardware ceilings (dense
   flops/sec, int8 ops/sec, HBM bytes/sec) for the TPU generations this
   repo targets.  Unknown kinds (CPU fallback included) degrade to
   ``peaks: "unavailable"`` — attained rates are still reported, the
   fraction-of-peak fields are simply absent.  Never an error.

3. **Roofline join.**  ``roofline(phase_times)`` joins the static
   program costs (flops x calls, bytes x calls per phase label) to the
   telemetry layer's MEASURED phase spans: attained FLOP/s, attained
   HBM GB/s, arithmetic intensity, fraction of peak.  The telemetry
   summary/snapshot and bench.py carry the block; perf_gate.py tracks
   the fractions across BENCH rounds.

   Caveat, stated in the block itself: XLA's cost analysis sees custom
   calls (the Pallas histogram/partition kernels) as opaque — their
   MACs are NOT in ``flops``.  The histogram/partition routing sites
   therefore file ANALYTIC per-pass costs (``note_traced_pass``: the
   dense N*F*B*lanes MAC count PROFILE.md derives by hand) under
   ``traced_passes``, so the Pallas-routed phases keep a machine-written
   cost model too.

4. **Compile observability.**  ``compile_block()``: program count,
   total (cold) compile seconds, warm-program count, plus the telemetry
   counters for true backend compiles, persistent-cache hits and
   mid-run recompiles (telemetry.emit_iteration flags compiles that
   happen after the first iteration record).

Armed/disarmed with the telemetry registry (telemetry.enable/disable/
reset call into here), so every ``metrics_out=`` run gets roofline +
compile blocks with no extra flag.  A program captured in one run stays
usable after ``disable()`` (the wrapper keeps serving the cached
executable — re-compiling it would be strictly worse); ``reset()``
starts a new GENERATION: records re-register lazily on next call,
marked ``warm`` (their compile was paid by a previous run).
"""
from __future__ import annotations

import collections
import os
import re
import subprocess
import time
from typing import Any, Dict, List, Optional, Tuple

_enabled = False
_generation = 0
_records: List[dict] = []            # this generation's programs, in order
_pass_notes: Dict[tuple, dict] = {}  # (phase, static key) -> analytic cost


# ------------------------------------------------------------------ life cycle

def enabled() -> bool:
    return _enabled


def active() -> bool:
    """True when there is anything to report (armed, or a previous run's
    records are still registered)."""
    return _enabled or bool(_records) or bool(_pass_notes)


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    """Stop capturing.  Existing records (and cached executables) are
    kept — snapshot()/reports after disable still serve the run's data."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Start a new generation: clear the report tables.  Wrappers keep
    their compiled executables and lazily re-register (as ``warm``) on
    their next call, so a second run in one process reports fresh call
    counts without paying a second compile."""
    global _generation
    _generation += 1
    del _records[:]
    _pass_notes.clear()


# ------------------------------------------------------------------ peak table

# Per-chip ceilings, flop convention matching XLA cost analysis (one FMA =
# 2 flops; the marketing "TFLOPS" numbers already count it that way).
# ici_bytes_per_sec is the per-chip aggregate inter-chip-interconnect
# egress (one direction, all links), from the public per-chip interchip
# bandwidth specs — the seam-roofline denominator podtrace divides
# measured collective GB/s by.  A logical-payload seam can't exceed it,
# so attained/peak is a conservative (under-)estimate of link saturation.
_PEAK_TABLE: Tuple[Tuple[Tuple[str, ...], Dict[str, float]], ...] = (
    (("v6e", "v6 lite", "trillium"),
     {"flops_per_sec": 918e12, "int8_ops_per_sec": 1836e12,
      "hbm_bytes_per_sec": 1640e9, "ici_bytes_per_sec": 448e9}),
    (("v5p",),
     {"flops_per_sec": 459e12, "int8_ops_per_sec": 918e12,
      "hbm_bytes_per_sec": 2765e9, "ici_bytes_per_sec": 600e9}),
    (("v5e", "v5 lite", "v5lite"),
     {"flops_per_sec": 197e12, "int8_ops_per_sec": 394e12,
      "hbm_bytes_per_sec": 819e9, "ici_bytes_per_sec": 200e9}),
    (("v4",),
     {"flops_per_sec": 275e12, "int8_ops_per_sec": 275e12,
      "hbm_bytes_per_sec": 1228e9, "ici_bytes_per_sec": 300e9}),
    (("v3",),
     {"flops_per_sec": 123e12, "int8_ops_per_sec": 123e12,
      "hbm_bytes_per_sec": 900e9, "ici_bytes_per_sec": 280e9}),
)


def device_kind() -> str:
    """The first local device's kind string (e.g. "TPU v5 lite", "cpu").
    Looked up per call — __graft_entry__ steers backends mid-process."""
    try:
        import jax
        return str(jax.local_devices()[0].device_kind)
    except Exception:
        return "unknown"


def resolve_peaks(kind: str) -> Optional[Dict[str, float]]:
    """Peak table lookup by device-kind substring.  None (not an error)
    for unknown kinds — CPU, simulators, future chips."""
    k = (kind or "").lower()
    for subs, peaks in _PEAK_TABLE:
        if any(s in k for s in subs):
            return dict(peaks)
    return None


def host_fingerprint() -> dict:
    """Self-describing host/run metadata (bench.py's ``host`` block):
    device kind, backend, jax/jaxlib versions, git SHA, process count —
    what perf_gate needs to refuse cross-hardware comparisons."""
    out: Dict[str, Any] = {"device_kind": device_kind()}
    try:
        import jax
        out["backend"] = jax.default_backend()
        out["jax_version"] = jax.__version__
        out["process_count"] = jax.process_count()
        out["local_device_count"] = jax.local_device_count()
    except Exception:
        pass
    try:
        import jaxlib
        out["jaxlib_version"] = jaxlib.__version__
    except Exception:
        pass
    try:
        sha = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5)
        if sha.returncode == 0 and sha.stdout.strip():
            out["git_sha"] = sha.stdout.strip()
    except Exception:
        pass
    return out


# -------------------------------------------------------------- program capture

def _tracing() -> bool:
    # single-homed in telemetry (the span layer's trace/execution split
    # depends on the same check — two copies would drift apart across jax
    # API churn)
    from . import telemetry
    return telemetry._tracing()


def _jit_disabled() -> bool:
    # under jax.disable_jit() the POINT is eager per-op execution;
    # serving a compiled program would defeat it
    try:
        import jax
        return bool(jax.config.jax_disable_jit)
    except Exception:
        return False


def _sig(args, kwargs):
    """Hashable call signature: array leaves by (shape, dtype), everything
    else (jit statics) by value."""
    import jax
    leaves, treedef = jax.tree.flatten(
        (args, tuple(sorted(kwargs.items()))))
    key = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            key.append(("a", tuple(leaf.shape), str(leaf.dtype)))
        else:
            key.append(("v", leaf))
    return (treedef, tuple(key))


def _analyze(compiled) -> dict:
    """Normalize compiled.cost_analysis()/memory_analysis() across
    backends: missing/partial analyses yield None fields, never errors
    (the CPU backend's graceful-degradation contract)."""
    out: Dict[str, Any] = {"flops": None, "bytes_accessed": None,
                           "transcendentals": None, "memory": None}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            for field, key in (("flops", "flops"),
                               ("bytes_accessed", "bytes accessed"),
                               ("transcendentals", "transcendentals")):
                if key in ca:
                    try:
                        out[field] = float(ca[key])
                    except (TypeError, ValueError):
                        pass
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out["memory"] = {
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes",
                                              0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            }
    except Exception:
        pass
    return out


class Instrumented:
    """Cost-capturing wrapper around one jitted program (see module
    docstring for the call-site contract).  One signature-keyed cache of
    (record, compiled executable) per wrapper — wrappers are cached in
    the same program tables (_CHUNK_PROGRAMS etc.) the inner jits were."""
    __slots__ = ("_fn", "name", "phase", "_cache")

    def __init__(self, name: str, fn, phase: Optional[str] = None):
        self._fn = fn
        self.name = name
        self.phase = phase or name
        self._cache: Dict[Any, tuple] = {}

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def _register(self, rec: dict) -> None:
        # a record whose generation is current is already in _records
        # (appended at capture or at a previous re-register); a stale one
        # re-files with fresh call counts, marked warm — its compile was
        # paid by a previous run
        if rec["gen"] != _generation:
            rec["gen"] = _generation
            rec["calls"] = 0
            rec["warm"] = True
            # no capture happened this generation: nothing to subtract
            # from this run's measured spans
            rec["capture_seconds"] = 0.0
            _records.append(rec)

    def _capture(self, sig, args, kwargs):
        from . import telemetry
        # the inner jit holding a compiled entry means a previous
        # (disarmed) call already paid this program's compile: the AOT
        # re-compile below is NOT this run's cold cost (on TPU the
        # persistent cache makes it a disk hit) — mark the record warm so
        # total_compile_seconds stays honest
        try:
            warm_hint = bool(self._fn._cache_size())
        except Exception:
            warm_hint = False
        t0 = time.perf_counter()
        try:
            compiled = self._fn.lower(*args, **kwargs).compile()
        except Exception as e:
            telemetry.count("costmodel/capture_failed")
            rec = {"name": self.name, "phase": self.phase,
                   "compile_seconds": 0.0, "flops": None,
                   "bytes_accessed": None, "transcendentals": None,
                   "memory": None, "calls": 0, "warm": False,
                   "gen": _generation, "error": type(e).__name__}
            _records.append(rec)
            entry = (rec, None)
            self._cache[sig] = entry
            return entry
        dt = round(time.perf_counter() - t0, 3)
        # the capture ran inside the caller's phase span (the program call
        # site is span-wrapped), so roofline() subtracts this wall time
        # from the measured phase seconds — attained rates must price
        # execution, not compilation, or cold-vs-warm-cache rounds would
        # read as kernel regressions (perf_gate false positives)
        # "_compiled": what op_phases() prints and parses at its first call
        # (never here: a capture may lie inside a timed slice)
        rec = {"name": self.name, "phase": self.phase,
               "compile_seconds": dt, "capture_seconds": dt,
               "calls": 0, "warm": warm_hint, "gen": _generation,
               "_compiled": compiled}
        rec.update(_analyze(compiled))
        _records.append(rec)
        try:
            from . import tracing
            if tracing.active():
                # compile captures on the flight-recorder timeline
                # (ISSUE 16): a mid-run capture next to a latency spike
                # is usually the whole explanation
                tracing.event("compile_capture", name=self.name,
                              phase=self.phase, seconds=dt,
                              warm=warm_hint)
        except Exception:
            pass
        entry = (rec, compiled)
        self._cache[sig] = entry
        return entry

    def __call__(self, *args, **kwargs):
        if ((not _enabled and not self._cache)
                or _tracing() or _jit_disabled()):
            return self._fn(*args, **kwargs)
        try:
            sig = _sig(args, kwargs)
            entry = self._cache.get(sig)
        except Exception:
            return self._fn(*args, **kwargs)
        if entry is None:
            if not _enabled:
                # disarmed: no NEW captures, but cached executables above
                # keep serving (re-compiling a program we hold would be
                # strictly worse)
                return self._fn(*args, **kwargs)
            entry = self._capture(sig, args, kwargs)
        rec, compiled = entry
        if _enabled or active():
            self._register(rec)
            rec["calls"] += 1
        if compiled is not None:
            try:
                return compiled(*args)
            except Exception as e:
                from . import telemetry
                from .utils import log
                telemetry.count("costmodel/aot_call_fallback")
                # counted AND said: the retry through plain jit below
                # compiles the program a second time
                log.warning("costmodel: compiled call of %s failed (%s: %s)"
                            "; retrying through jit"
                            % (self.name, type(e).__name__,
                               " ".join(str(e).split())[:300]))
                # poison the executable for this signature (keep the
                # record: the static analysis is still right)
                self._cache[sig] = (rec, None)
        return self._fn(*args, **kwargs)


def instrument(name: str, fn, phase: Optional[str] = None) -> Instrumented:
    """Wrap a jitted program for cost capture.  ``phase`` is the
    telemetry span name whose measured seconds this program's static
    costs join against in ``roofline()``."""
    _keep_out_of_source_locations()
    return Instrumented(name, fn, phase=phase)


def _keep_out_of_source_locations() -> None:
    """This module's frames stay out of the source locations JAX records
    for the operations traced under a wrapper.  Armed, a wrapper traces
    its program from ``_capture``, disarmed from ``__call__``: one frame
    apart.  JAX keeps the innermost ten user frames of an operation, a
    Pallas kernel is serialised with them into its custom call, and that
    is hashed into the compile-cache key; a kernel eight frames under the
    chunk program (ops/route_pallas.py) therefore made a traced run
    (telemetry on) compile a program of its own where it should load the
    timed run's (PERF.md section 6, PR 33)."""
    global _out_of_source_locations
    if not _out_of_source_locations:
        from jax._src import source_info_util
        source_info_util.register_exclusion(__file__)
        _out_of_source_locations = True


_out_of_source_locations = False


# -------------------------------------------------------- analytic pass notes

def note_traced_pass(phase: str, key: tuple, **cost) -> None:
    """File an ANALYTIC per-pass cost at trace time (the hand-derived
    numbers PROFILE.md's roofline used: dense MACs per histogram pass,
    bytes moved per partition call).  XLA cost analysis cannot see into
    Pallas custom calls, so these notes are the cost model for the
    Pallas-routed phases.  Deduped by static ``key``; ``traces`` counts
    how many program traces baked this pass in.

    Mixed-bin packing (ISSUE 6): a histogram level pass over a packed
    dataset is one pass PER bin-width class, and the routing layer files
    one note per class with a trailing ``binclass<width>`` key element
    (ops/histogram._note_hist_pass) — so the roofline block attributes
    narrow-class and wide-class cost separately instead of pricing every
    feature at the uniform worst case, and the modeled MAC total shrinks
    in step with the measured seconds."""
    if not _enabled:
        return
    k = (phase, key)
    note = _pass_notes.get(k)
    if note is None:
        note = {"phase": phase, "key": list(key), "traces": 0}
        note.update({f: float(v) for f, v in cost.items()})
        _pass_notes[k] = note
    note["traces"] += 1


# ------------------------------------------------------------------- reporting

def roofline(phase_times: Dict[str, float],
             kind: Optional[str] = None,
             fenced: Optional[bool] = None) -> dict:
    """Join static program costs to measured phase seconds.

    ``phase_times``: the telemetry layer's cumulative execution spans.
    Per phase: total flops/bytes (cost x calls), attained FLOP/s and HBM
    GB/s over the measured seconds, arithmetic intensity, and — when the
    device kind is in the peak table — fraction-of-peak fields.  Unknown
    kinds report ``peaks: "unavailable"`` and skip only the fractions.

    ``fenced``: whether the spans ran in telemetry fence mode.  On an
    async-dispatch backend (TPU) UNFENCED spans time the dispatch, not
    the execution — the block carries ``fenced_spans`` so consumers
    (perf_gate, PROFILE rounds) know whether the attained rates are
    meaningful; bench.py fences its depthwise runs for exactly this
    reason."""
    kind = kind if kind is not None else device_kind()
    peaks = resolve_peaks(kind)
    agg: Dict[str, dict] = {}
    for rec in _records:
        p = rec.get("phase") or "other"
        a = agg.setdefault(p, {"flops": 0.0, "bytes_accessed": 0.0,
                               "programs": 0, "calls": 0, "capture": 0.0,
                               "flops_unknown": False})
        a["programs"] += 1
        a["calls"] += int(rec.get("calls", 0))
        a["capture"] += float(rec.get("capture_seconds", 0.0))
        for field in ("flops", "bytes_accessed"):
            v = rec.get(field)
            if v is None:
                a["flops_unknown"] = True
            else:
                a[field] += v * int(rec.get("calls", 0))
    phases: Dict[str, dict] = {}
    for p, a in sorted(agg.items()):
        secs = float(phase_times.get(p, 0.0))
        # the first armed call's AOT capture (lower + compile) ran inside
        # this phase's span: attained rates price EXECUTION seconds only,
        # so a cold compile cache cannot read as a kernel regression
        exec_secs = secs - a["capture"] if secs > 0.0 else secs
        blk: Dict[str, Any] = {
            "flops": round(a["flops"], 1),
            "bytes_accessed": round(a["bytes_accessed"], 1),
            "programs": a["programs"], "calls": a["calls"],
            "seconds": round(secs, 6),
        }
        if a["capture"] > 0.0 and secs > 0.0:
            blk["compile_seconds_excluded"] = round(a["capture"], 6)
        if a["flops_unknown"]:
            blk["cost_analysis"] = "partial"
        if exec_secs > 0.0:
            blk["attained_flops_per_sec"] = round(a["flops"] / exec_secs, 1)
            blk["attained_hbm_gbps"] = round(
                a["bytes_accessed"] / exec_secs / 1e9, 4)
            if a["bytes_accessed"] > 0.0:
                blk["arithmetic_intensity"] = round(
                    a["flops"] / a["bytes_accessed"], 4)
            if peaks:
                blk["frac_of_peak_flops"] = round(
                    a["flops"] / exec_secs / peaks["flops_per_sec"], 6)
                blk["frac_of_peak_bw"] = round(
                    a["bytes_accessed"] / exec_secs
                    / peaks["hbm_bytes_per_sec"], 6)
        phases[p] = blk
    out: Dict[str, Any] = {
        "device_kind": kind,
        "peaks": peaks if peaks else "unavailable",
        "phases": phases,
        # honesty marker: Pallas custom calls are opaque to XLA cost
        # analysis — their MACs live in traced_passes, not in flops
        "method": "xla_cost_analysis+measured_spans; custom-call (Pallas) "
                  "flops are analytic (traced_passes), not in phase flops",
    }
    if fenced is not None:
        out["fenced_spans"] = bool(fenced)
        if not fenced:
            out["method"] += ("; spans UNFENCED — on async backends "
                              "attained rates time dispatch, not "
                              "execution (metrics_fence=true to fix)")
    if _pass_notes:
        out["traced_passes"] = [dict(n) for _, n in
                                sorted(_pass_notes.items(),
                                       key=lambda kv: kv[0])]
    return out


def phase_program_records(phase: str) -> List[dict]:
    """This generation's captured-program records filed under one phase
    label (copies).  The serving no-recompile assertion reads this: a
    steady-state bucketed engine must keep a CLOSED program inventory —
    repeated calls at a bucket shape bump ``calls`` on existing records
    and never add a new one (tests/test_serving.py, bench.py
    bench_predict lane)."""
    return [{k: v for k, v in r.items() if not k.startswith("_")}
            for r in _records if r.get("phase") == phase]


# ------------------------------------- phases of the operations no scope holds

XLA = "xla"     # the label of what the compiler put in itself

# what no device runs as an operation of its own (a loop does: one of no
# trips is an event of its own in a trace, the condition's evaluation)
_NO_OPERATION = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier"))
# what only moves or allocates: without metadata, the compiler's own
_MOVES = frozenset((
    "copy", "copy-start", "copy-done", "slice-start", "slice-done",
    "async-start", "async-update", "async-done", "custom-call"))
# a fusion of nothing but these is a move too
_LAYOUT_ONLY = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy",
    "transpose", "reshape"))
# the neighbours of step (d) are not looked for on the far side of these
_BOUNDARY = frozenset(("while", "conditional", "call", "parameter"))
_NEIGHBOUR_STEPS = 8

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_ARRAY = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_ASSIGNED = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"([\w\-]+)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)="
                          r"\{([^}]*)\}")


_Instr = collections.namedtuple(
    "_Instr", "name opcode shape operands op_name called root")


def _closing(text: str, at: int) -> int:
    """Index of the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def _shape_bytes(shape: str, opcode: str = "") -> int:
    """Bytes of a result as the text gives its type: every array of a
    tuple, but of an asynchronous start's ``(destination, source,
    context)`` or ``((operands), result, context)`` the destination alone."""
    if opcode.endswith("-start"):
        if shape.startswith("(("):
            shape = shape[_closing(shape, 1) + 1:]
        arrays = _ARRAY.findall(shape)[:1]
    else:
        arrays = _ARRAY.findall(shape)
    total = 0
    for dtype, dims in arrays:
        n = _DTYPE_BYTES.get(dtype, 1 if dtype.startswith("f8") else 0)
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def _parse_hlo(text: str):
    """({computation: {instruction name: _Instr}}, entry) of an HLO module
    as ``compiled.as_text()`` prints it; instructions in their order."""
    computations, entry, current = {}, None, None
    for line in text.splitlines():
        if current is None:
            head = _COMPUTATION.match(line)
            if head:
                current = computations[head.group(2)] = {}
                if head.group(1):
                    entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        lhs = _ASSIGNED.match(line)
        if not lhs:
            continue
        rest = line[lhs.end():]
        if rest.startswith("("):        # a tuple's type holds spaces
            end = _closing(rest, 0)
            shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
        else:
            shape, _, rest = rest.partition(" ")
        opcode = _OPCODE.match(rest)
        if not opcode:
            continue
        end = _closing(rest, opcode.end() - 1)
        attrs = rest[end + 1:]
        op_name = _OP_NAME.search(attrs)
        called = _CALLED.findall(attrs)
        for listed in _CALLED_LIST.findall(attrs):
            called += [c.strip().lstrip("%") for c in listed.split(",")
                       if c.strip()]
        current[lhs.group(2)] = _Instr(
            lhs.group(2), opcode.group(1), shape,
            _OPERAND.findall(rest[opcode.end():end]),
            op_name.group(1) if op_name else None, called,
            bool(lhs.group(1)))
    return computations, entry


def _phase_pattern():
    from . import telemetry
    return re.compile(r"(^|/)(%s)(/|$)" % "|".join(telemetry.DEVICE_PHASES))


def _produced_phase(body: dict, start: str, named) -> Optional[str]:
    """Step (b): breadth-first from ``start`` towards the parameters of a
    fused computation, the first instruction whose ``op_name`` holds a
    phase."""
    seen, queue = set(), collections.deque([start])
    while queue:
        name = queue.popleft()
        ins = body.get(name)
        if ins is None or name in seen:
            continue
        seen.add(name)
        phase = named(ins.op_name)
        if phase:
            return phase
        queue.extend(ins.operands)
    return None


def _fusion_phase(body: dict, named) -> Optional[str]:
    """A fusion has its root's metadata: carried through a tuple root (the
    phase its outputs agree on, else that of the largest output that has
    one) and through instructions that have none."""
    root = next((i for i in body.values() if i.root), None)
    if root is None:
        return None
    if root.opcode != "tuple" or named(root.op_name):
        return _produced_phase(body, root.name, named)
    outputs = []
    for name in root.operands:
        phase = _produced_phase(body, name, named)
        if phase:
            outputs.append((_shape_bytes(body[name].shape), phase))
    return max(outputs, key=lambda o: o[0])[1] if outputs else None


def _neighbour_phase(body: dict, users: dict, start: str, direct: dict):
    """Step (d): the nearest user's phase, then the nearest producer's,
    through instructions that have none, ``_NEIGHBOUR_STEPS`` at most."""
    for edges in (lambda n: users.get(n, ()), lambda n: body[n].operands):
        frontier, seen = [start], {start}
        for _ in range(_NEIGHBOUR_STEPS):
            reached = []
            for name in frontier:
                for other in edges(name):
                    if other in seen or other not in body:
                        continue
                    seen.add(other)
                    if direct.get(other):
                        return direct[other]
                    if body[other].opcode not in _BOUNDARY:
                        reached.append(other)
            frontier = reached
            if not frontier:
                break
    return None


def label_unscoped_ops(text: str) -> Dict[str, tuple]:
    """{instruction name: (label, opcode, result type, result bytes)} for
    the instructions of one optimised HLO module that the device runs as
    operations of their own and whose own ``op_name`` holds no name of
    ``telemetry.DEVICE_PHASES``; see ``op_phases`` for the rule."""
    pattern = _phase_pattern()

    def named(op_name):
        found = pattern.search(op_name or "")
        return found.group(2) if found else None

    computations, entry = _parse_hlo(text)
    if entry is None:
        return {}
    run, todo = {}, [entry]     # the entry and what it loops over and calls
    while todo:
        comp = todo.pop()
        if comp in run or comp not in computations:
            continue
        run[comp] = True
        for ins in computations[comp].values():
            if ins.opcode in ("while", "conditional", "call", "async-start"):
                todo.extend(ins.called)
    out = {}
    for comp in run:
        body = computations[comp]
        users: Dict[str, list] = {}
        direct, inserted = {}, set()
        for ins in body.values():
            for operand in ins.operands:
                users.setdefault(operand, []).append(ins.name)
            phase = named(ins.op_name)                          # (a)
            fused = (computations.get(ins.called[0])
                     if ins.opcode == "fusion" and ins.called else None)
            if phase is None and fused:                         # (b)
                phase = _fusion_phase(fused, named)
            direct[ins.name] = phase
            # (c) no metadata of any kind, inside it neither, and it only
            # moves or allocates
            if not ins.op_name and (
                    ins.opcode in _MOVES if fused is None else
                    all(not i.op_name and i.opcode in _LAYOUT_ONLY
                        for i in fused.values())):
                inserted.add(ins.name)
        for ins in body.values():
            if ins.opcode in _NO_OPERATION or named(ins.op_name):
                continue
            label = direct[ins.name]
            if label is None and ins.name not in inserted:      # (d)
                label = _neighbour_phase(body, users, ins.name, direct)
            out[ins.name] = (label or XLA, ins.opcode,
                             _LAYOUT.sub("", ins.shape),
                             _shape_bytes(ins.shape, ins.opcode))
    return out


def _unscoped_of(rec: dict) -> Optional[Dict[str, tuple]]:
    """``label_unscoped_ops`` of one record's executable: printed and
    parsed at the first call and kept on the record; None where the text
    cannot be had (no executable, one that will not print)."""
    if "_unscoped" not in rec:
        labels = None
        try:
            text = rec["_compiled"].as_text()
            if text:
                labels = label_unscoped_ops(text)
        except Exception:
            pass
        rec["_unscoped"] = labels
    return rec["_unscoped"]


def op_phases(describe: bool = False) -> Dict[str, Dict[str, Any]]:
    """{program name: {HLO instruction name: label}} for every captured
    program of this generation whose optimised text can be had: the
    instructions the device runs as operations of their own (in the entry
    computation and the bodies of ``while``, ``call`` and ``conditional``)
    **whose own ``op_name`` holds no name of ``telemetry.DEVICE_PHASES``**,
    which is what a device trace shows under no scope.  The label is one
    of ``DEVICE_PHASES`` or ``"xla"``, resolved in this order:

    (a) the instruction's own ``op_name``: with a phase on its scope path
        it is not in the map at all, the trace already has it;
    (b) what it produces: XLA's own rule, "a fusion has its root's
        metadata", carried through a tuple root and through instructions
        that have none, breadth-first from the root of the fused
        computation towards its parameters to the first instruction whose
        ``op_name`` holds a phase; a tuple root whose outputs disagree
        takes the phase of its largest output;
    (c) an instruction that only moves or allocates (``copy``,
        ``copy-start`` / ``-done``, ``slice-start`` / ``-done``, a buffer's
        custom call, a fusion of nothing but copies, bitcasts, transposes
        and reshapes) and has no metadata of any kind, inside it neither:
        what the compiler inserts itself, in front of a conditional,
        between two layouts or two memories.  It is ``"xla"`` at once and
        takes no neighbour's name (the whole-pane copies XLA once put in
        front of every split would else have read ``partition``);
    (d) what came from the program's own code and lost its scope path (a
        cloned fusion; the pieces of a decomposed cumulative sum, which on
        a TPU are bare ``pad``, ``reduce-window``, ``slice`` and
        ``reverse`` instructions with no metadata, elsewhere fusions whose
        inner instructions say ``reduce_window_sum`` and no more) takes its
        neighbours' in the enclosing computation: the nearest user's
        phase, then the nearest producer's, through instructions that have
        no phase either, ``_NEIGHBOUR_STEPS`` steps at most and not
        across a loop, a call or a parameter; else ``"xla"``.

    With ``describe`` a value is ``(label, opcode, result type, result
    bytes)``, so that a whole-buffer copy reads as ``copy
    s8[2,2016,402432]`` and not as ``copy.17``.  Two records of one name
    (two signatures) are kept apart as ``name``, ``name#2``.  A trace's
    operation has a name and a scope but no program: a reader that looks
    names up across programs has to leave out a name that two programs
    label differently (``benchmarks/harness/hidden.py`` does).  The text
    is printed and parsed at the first call and once per record, never at
    capture; a program whose text cannot be had has no entry."""
    out: Dict[str, Dict[str, Any]] = {}
    for rec in _records:
        labels = _unscoped_of(rec)
        if labels is None:
            continue
        name, n = rec["name"], 1
        while name in out:
            n += 1
            name = "%s#%d" % (rec["name"], n)
        out[name] = dict(labels) if describe else {
            op: found[0] for op, found in labels.items()}
    return out


def _unscoped_summary(labels: Dict[str, tuple]) -> dict:
    """Operations per label, and the largest result of the compiler's own:
    ``{"histogram": 37, "xla": 5, "xla_largest": ["copy", 1622605824]}``."""
    out: Dict[str, Any] = {}
    largest = None
    for label, opcode, _shape, nbytes in labels.values():
        out[label] = out.get(label, 0) + 1
        if label == XLA and (largest is None or nbytes > largest[1]):
            largest = [opcode, nbytes]
    if largest is not None:
        out["xla_largest"] = largest
    return out


def compile_block() -> dict:
    """Run-level compile observability: captured-program inventory,
    total cold-compile seconds, and the telemetry compile counters
    (true backend compiles, persistent-cache hits, mid-run recompiles).
    A program whose optimised text can be had carries ``unscoped_ops``:
    how many of its operations lie under no device phase, by the phase
    ``op_phases`` gives them, and under ``xla_largest`` the opcode and the
    bytes of the largest result the compiler put in itself (a copy of a
    whole buffer shows here, without a chip and without reading HLO)."""
    from . import telemetry
    programs = []
    for rec in _records:
        p = {"name": rec["name"], "phase": rec["phase"],
             "compile_seconds": rec["compile_seconds"],
             "calls": rec["calls"]}
        for field in ("flops", "bytes_accessed", "memory", "error"):
            if rec.get(field) is not None:
                p[field] = rec[field]
        if rec.get("warm"):
            p["warm"] = True
        labels = _unscoped_of(rec)
        if labels is not None:
            p["unscoped_ops"] = _unscoped_summary(labels)
        programs.append(p)
    counters = telemetry.counters()
    return {
        "program_count": len(_records),
        "total_compile_seconds": round(
            sum(r["compile_seconds"] for r in _records
                if not r.get("warm")), 3),
        "warm_programs": sum(1 for r in _records if r.get("warm")),
        "backend_compiles": counters.get("jit/backend_compile", 0),
        "persistent_cache_hits": counters.get("jit/persistent_cache_hit",
                                              0),
        "midrun_recompiles": counters.get("jit/midrun_recompile", 0),
        "programs": programs,
    }
