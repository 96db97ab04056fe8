"""Pretty-print a telemetry JSONL file (metrics_out=...) as phase/counter
tables, so BENCH/PROFILE rounds stop hand-assembling them.

Usage:
    python scripts/telemetry_report.py metrics.jsonl
    python scripts/telemetry_report.py --json metrics.jsonl   # machine form

Reads the per-iteration records emitted by lightgbm_tpu/telemetry.py
({"iter", "phase_times", "counters", "eval_metrics", ...} plus an optional
trailing {"summary": true, ...} record) and prints:

  - a per-phase table: total seconds, mean ms/iteration, share of the
    summed phase time (execution spans and trace/compile spans separately),
  - the final kernel-route counter values (cross-host ``allhosts/`` sums
    when the run aggregated them),
  - the training-health table (ISSUE 2 ``health`` blocks: NaN/Inf and
    saturation totals, iterations with anomalies, score watermark),
  - the memory table (ISSUE 2 ``memory`` blocks: peak bytes_in_use,
    per-phase byte deltas, the dataset-residency report),
  - the roofline table (ISSUE 4 ``roofline`` block: per-phase static
    flops/bytes joined to measured seconds — attained FLOP/s, HBM GB/s,
    fraction-of-peak when the device kind is known) and the compile
    table (program inventory, compile seconds, cache hits, mid-run
    recompiles),
  - the flight-recorder table (ISSUE 16 ``trace`` block: ring
    occupancy/drop/dump counts, streaming-sketch latency percentiles per
    family, and the per-component serve attribution — mean share and p99
    share of the request wall time),
  - first/last eval metric values per dataset/metric.

``--monitor monitor.jsonl`` additionally renders the live monitor's
windowed snapshot series (ISSUE 20, monitor_out= JSONL): one row per
closed window with the SLO family's delta-sketch count and p50/p99,
the fast/slow burn rates and breach marks.  Works standalone too
(``--monitor`` with no positional path).  The full contract validator
is ``scripts/monitor_report.py --check``; this is the human render
next to the phase tables.

Malformed or truncated JSONL exits with a one-line error (code 2), not a
stack trace — half-written sinks from crashed runs are an expected input.
"""
from __future__ import annotations

import argparse
import json
import sys


class MalformedJSONL(Exception):
    pass


def load(path: str):
    iters, summary, residency = [], None, None
    try:
        f = open(path)
    except OSError as e:
        raise MalformedJSONL(f"cannot read {path}: {e}")
    with f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise MalformedJSONL(
                    f"{path}:{lineno}: malformed JSONL record ({e}) — "
                    "truncated sink from an aborted run?")
            if not isinstance(rec, dict):
                raise MalformedJSONL(
                    f"{path}:{lineno}: record is not a JSON object")
            if rec.get("summary"):
                summary = rec
            elif "iter" in rec:
                iters.append(rec)
            elif "residency" in rec:
                residency = rec["residency"]
    return iters, summary, residency


def _health_totals(iters, summary):
    """Cumulative health keys: prefer the summary's block (exact totals,
    survives partial files), fall back to summing the iteration blocks."""
    if summary and isinstance(summary.get("health"), dict):
        return dict(summary["health"])
    totals = {}
    for rec in iters:
        for k, v in (rec.get("health") or {}).items():
            if k == "eval_divergence":
                totals["eval_divergence_events"] = (
                    totals.get("eval_divergence_events", 0) + len(v))
            elif k == "score_max_abs":
                totals[k] = max(totals.get(k, 0.0), v)
            else:
                totals[k] = totals.get(k, 0) + v
    return totals


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return ("%.1f %s" % (n, unit)) if unit != "B" else "%d B" % n
        n /= 1024.0
    return "%d" % n


def _sum_phase(iters, key):
    total = {}
    for rec in iters:
        for k, v in rec.get(key, {}).items():
            total[k] = total.get(k, 0.0) + v
    return total


def _table(title, totals, n_iters):
    lines = [title, "-" * len(title)]
    if not totals:
        lines.append("(none recorded)")
        return lines
    grand = sum(totals.values()) or 1.0
    width = max(len(k) for k in totals)
    lines.append(f"{'phase'.ljust(width)}  {'total s':>10}  "
                 f"{'ms/iter':>10}  {'share':>6}")
    for k, v in sorted(totals.items(), key=lambda kv: -kv[1]):
        per = 1000.0 * v / max(n_iters, 1)
        lines.append(f"{k.ljust(width)}  {v:>10.4f}  {per:>10.2f}  "
                     f"{100.0 * v / grand:>5.1f}%")
    return lines


def _roofline_lines(roofline):
    out = ["Roofline (static costs x measured spans)",
           "---------------------------------------"]
    if not roofline:
        out.append("(no roofline block — emitted by metrics_out= runs "
                   "since ISSUE 4)")
        return out
    peaks = roofline.get("peaks")
    out.append("device_kind: %s   peaks: %s"
               % (roofline.get("device_kind", "?"),
                  ("unavailable" if peaks in (None, "unavailable")
                   else ", ".join("%s=%.3g" % kv
                                  for kv in sorted(peaks.items())))))
    phases = roofline.get("phases") or {}
    if phases:
        width = max(len(k) for k in phases)
        out.append(f"{'phase'.ljust(width)}  {'GFLOP':>10}  {'GB':>8}  "
                   f"{'sec':>8}  {'GFLOP/s':>9}  {'GB/s':>7}  "
                   f"{'%peak':>6}  {'AI':>7}")
        for k, b in sorted(phases.items()):
            frac = b.get("frac_of_peak_flops")
            out.append(
                f"{k.ljust(width)}  {b.get('flops', 0) / 1e9:>10.3f}  "
                f"{b.get('bytes_accessed', 0) / 1e9:>8.3f}  "
                f"{b.get('seconds', 0):>8.3f}  "
                + ("%9.2f" % (b["attained_flops_per_sec"] / 1e9)
                   if "attained_flops_per_sec" in b else "%9s" % "-") + "  "
                + ("%7.2f" % b["attained_hbm_gbps"]
                   if "attained_hbm_gbps" in b else "%7s" % "-") + "  "
                + ("%5.1f%%" % (100 * frac) if frac is not None
                   else "%6s" % "-") + "  "
                + ("%7.3f" % b["arithmetic_intensity"]
                   if "arithmetic_intensity" in b else "%7s" % "-"))
    else:
        out.append("(no phases captured)")
    passes = roofline.get("traced_passes") or []
    if passes:
        out.append("analytic traced passes (Pallas/custom-call costs XLA "
                   "analysis cannot see):")
        for n in passes:
            out.append("  %-10s %-42s traces=%-3d TMAC/pass=%.4g"
                       % (n.get("phase", "?"), str(n.get("key")),
                          n.get("traces", 0), n.get("macs", 0.0) / 1e12))
    return out


def _interconnect_lines(ic):
    """Per-collective-site wire-metrics table (ISSUE 5 ``interconnect``
    block): logical payload bytes and attained GB/s per site/phase."""
    out = ["Interconnect (per-collective wire metrics)",
           "------------------------------------------"]
    if not ic or not ic.get("sites"):
        out.append("(no interconnect block — emitted by multi-device "
                   "runs with collective seams traced)")
        return out
    sites = ic["sites"]
    width = max(len(s) for s in sites)
    out.append(f"{'site'.ljust(width)}  {'kind':>12}  {'bytes/call':>12}  "
               f"{'est calls':>9}  {'est bytes':>12}  {'GB/s':>10}")
    for name, blk in sorted(sites.items(),
                            key=lambda kv: -kv[1].get("est_bytes", 0)):
        rate = blk.get("attained_gb_per_s")
        out.append(
            f"{name.ljust(width)}  {blk.get('kind', '?'):>12}  "
            f"{_fmt_bytes(blk.get('bytes_per_call', 0)):>12}  "
            f"{blk.get('est_calls', 0):>9}  "
            f"{_fmt_bytes(blk.get('est_bytes', 0)):>12}  "
            + (f"{rate:>10.4f}" if isinstance(rate, (int, float))
               else f"{'-':>10}"))
    for phase, blk in sorted((ic.get("phases") or {}).items()):
        rate = blk.get("attained_gb_per_s")
        out.append("phase %-12s  %s over %.4fs span -> %s GB/s"
                   % (phase, _fmt_bytes(blk.get("est_bytes", 0)),
                      blk.get("span_seconds", 0.0),
                      ("%.4f" % rate) if isinstance(rate, (int, float))
                      else "-"))
    if ic.get("note"):
        out.append("note: %s" % ic["note"])
    return out


def _ingest_lines(counters, summary_phase_times):
    """The ``ingest/*`` counter family (ISSUE 8, io/streaming.py) with
    derived H2D GB/s: payload bytes over the host time actually blocked
    on transfers, and over the whole ingest span (effective rate).  The
    overlap-hidden estimate is the double buffer's measured win."""
    out = ["Streaming ingestion (ingest/*)",
           "------------------------------"]
    fam = {k: v for k, v in counters.items() if k.startswith("ingest/")}
    if not fam:
        out.append("(no ingest counters — resident load, or telemetry "
                   "was off during ingestion)")
        return out
    width = max(len(k) for k in fam)
    for k, v in sorted(fam.items()):
        val = _fmt_bytes(v) if k.endswith("_bytes") else str(v)
        out.append(f"{k.ljust(width)}  {val}")
    h2d = fam.get("ingest/h2d_bytes", 0)
    wait_s = fam.get("ingest/h2d_wait_us", 0) / 1e6
    hidden_s = fam.get("ingest/overlap_hidden_us", 0) / 1e6
    span_s = (summary_phase_times or {}).get("ingest", 0.0)
    if h2d and wait_s > 0:
        out.append("H2D attained (blocked time)  %.2f GB/s"
                   % (h2d / wait_s / 1e9))
    if h2d and span_s > 0:
        out.append("H2D effective (ingest span)  %.2f GB/s  over %.2f s"
                   % (h2d / span_s / 1e9, span_s))
    if hidden_s > 0:
        out.append("overlap-hidden transfer time  %.2f s" % hidden_s)
    return out


def _serve_lines(counters):
    """The ``serve/*`` counter family (ISSUE 7 engine + ISSUE 13 front)
    with derived coalescing/linger/queue means.  The coalesced batch
    SIZE histogram is the ``serve/bucket_<B>`` rows; the tree-sharded
    wire bytes ride the interconnect block (sites ``serve/tree_*``)."""
    out = ["Serving (serve/*)", "-----------------"]
    fam = {k: v for k, v in counters.items() if k.startswith("serve/")}
    if not fam:
        out.append("(no serve counters — no engine/front activity while "
                   "telemetry was armed)")
        return out
    width = max(len(k) for k in fam)
    for k, v in sorted(fam.items()):
        out.append(f"{k.ljust(width)}  {v}")
    batches = fam.get("serve/coalesced_batches", 0)
    if batches:
        out.append("mean coalesced batch  %.1f rows over %.1f requests"
                   % (fam.get("serve/coalesced_rows", 0) / batches,
                      fam.get("serve/coalesced_requests", 0) / batches))
        out.append("mean linger wait      %.0f us"
                   % (fam.get("serve/linger_wait_us", 0) / batches))
    samples = fam.get("serve/queue_depth_samples", 0)
    if samples:
        # queue_peak_rows is a cumulative counter each front's close()
        # adds its own peak into — a SUM across fronts, not a job peak
        out.append("mean queue depth      %.1f rows "
                   "(per-front peaks summed: %d)"
                   % (fam.get("serve/queue_depth_rows", 0) / samples,
                      fam.get("serve/queue_peak_rows", 0)))
    swaps = fam.get("serve/swaps", 0)
    if swaps:
        out.append("mean swap drain       %.0f us over %d swap(s)"
                   % (fam.get("serve/swap_drain_us", 0) / swaps, swaps))
    return out


def _trace_lines(trace):
    """The flight-recorder block (ISSUE 16, ``trace`` summary key from
    tracing.snapshot()): ring occupancy + exact drop count, per-family
    streaming-sketch percentiles, and the per-component serve-latency
    attribution table.  Component means/p99s come from the same
    fixed-memory log-bucket sketches, so shares are exact to within the
    sketch's bucket resolution."""
    out = ["Flight recorder (trace)", "-----------------------"]
    if not trace:
        out.append("(no trace block — the recorder arms with any "
                   "metrics_out= session; see lightgbm_tpu/tracing.py)")
        return out
    out.append("ring %d/%d events  (appended %d, dropped %d, dumps %d, "
               "sketch growth %g%s)"
               % (trace.get("events", 0), trace.get("ring_events", 0),
                  trace.get("appended", 0), trace.get("dropped", 0),
                  trace.get("dumps", 0), trace.get("sketch_growth", 0.0),
                  ", default ring" if trace.get("default_ring") else ""))
    sketches = trace.get("sketches") or {}
    if not sketches:
        out.append("(no sketch observations)")
        return out

    def _us(x):
        return ("%10.1f" % x) if isinstance(x, (int, float)) else "%10s" % "-"

    width = max(len(k) for k in sketches)
    out.append(f"{'family'.ljust(width)}  {'count':>8}  {'mean us':>10}  "
               f"{'p50 us':>10}  {'p99 us':>10}  {'p999 us':>10}")
    for fam, pc in sorted(sketches.items()):
        out.append(f"{fam.ljust(width)}  {pc.get('count', 0):>8}  "
                   + "  ".join(_us(pc.get(k))
                               for k in ("mean", "p50", "p99", "p999")))
    # per-component serve attribution: where a request's wall time went
    # (component order mirrors tracing.COMPONENTS — the timeline order)
    wall = sketches.get("serve_wall_us") or {}
    comps = [(c, sketches.get("serve_%s_us" % c))
             for c in ("queue", "linger", "coalesce", "dispatch", "walk",
                       "scatter")]
    comps = [(c, pc) for c, pc in comps if pc]
    if wall and comps:
        mean_total = sum(pc.get("mean") or 0.0 for _c, pc in comps)
        wall_p99 = wall.get("p99") or 0.0
        out.append("serve attribution (per component of the exact "
                   "wall-time identity):")
        for c, pc in comps:
            mean = pc.get("mean") or 0.0
            p99 = pc.get("p99") or 0.0
            out.append("  %-9s mean %9.1f us (%5.1f%%)   p99 %9.1f us "
                       "(%5.1f%% of wall p99)"
                       % (c, mean,
                          100.0 * mean / mean_total if mean_total else 0.0,
                          p99,
                          100.0 * p99 / wall_p99 if wall_p99 else 0.0))
    return out


def _compile_lines(comp):
    out = ["Compile observability", "---------------------"]
    if not comp:
        out.append("(no compile block — emitted by metrics_out= runs "
                   "since ISSUE 4)")
        return out
    out.append("programs captured  %d  (cold compile %.2f s, %d warm)"
               % (comp.get("program_count", 0),
                  comp.get("total_compile_seconds", 0.0),
                  comp.get("warm_programs", 0)))
    out.append("backend compiles   %d   persistent-cache hits %d   "
               "MID-RUN recompiles %d%s"
               % (comp.get("backend_compiles", 0),
                  comp.get("persistent_cache_hits", 0),
                  comp.get("midrun_recompiles", 0),
                  "  <-- cache-key leak?"
                  if comp.get("midrun_recompiles", 0) else ""))
    progs = comp.get("programs") or []
    if progs:
        width = max(len(p.get("name", "?")) for p in progs)
        out.append(f"{'program'.ljust(width)}  {'compile s':>9}  "
                   f"{'calls':>5}  {'GFLOP':>9}  {'MB acc':>8}")
        for p in progs:
            fl = p.get("flops")
            by = p.get("bytes_accessed")
            out.append(
                f"{p.get('name', '?').ljust(width)}  "
                f"{p.get('compile_seconds', 0.0):>9.2f}  "
                f"{p.get('calls', 0):>5d}  "
                + ("%9.3f" % (fl / 1e9) if fl is not None
                   else "%9s" % "-") + "  "
                + ("%8.1f" % (by / 1e6) if by is not None
                   else "%8s" % "-")
                + ("  [warm]" if p.get("warm") else "")
                + ("  [%s]" % p["error"] if p.get("error") else ""))
        for p in progs:
            if p.get("unscoped_ops"):
                out.append(_unscoped_line(p.get("name", "?"),
                                          p["unscoped_ops"]))
    return out


def _unscoped_line(name, ops):
    """A program's operations under no device phase, by the phase the
    program's own map gives them, and the largest result the compiler
    inserted itself (costmodel.op_phases)."""
    counts = ", ".join("%s %d" % (label, n) for label, n in sorted(
        ops.items()) if label != "xla_largest")
    largest = ops.get("xla_largest")
    return "%s  under no phase: %s%s" % (
        name, counts, "; largest of xla: %s %.1f MB" % (
            largest[0], largest[1] / 1e6) if largest else "")


def report(path: str, as_json: bool = False) -> int:
    try:
        iters, summary, residency = load(path)
    except MalformedJSONL as e:
        print(f"telemetry_report error: {e}", file=sys.stderr)
        return 2
    if not iters and summary is None:
        print(f"no telemetry records in {path}", file=sys.stderr)
        return 1
    n = len(iters)
    exec_totals = _sum_phase(iters, "phase_times")
    trace_totals = _sum_phase(iters, "trace_times")
    counters = (summary or (iters[-1] if iters else {})).get("counters", {})
    health = _health_totals(iters, summary)
    mem = (summary or {}).get("memory") or (
        iters[-1].get("memory") if iters else None) or {}
    if residency is None:
        residency = mem.get("residency")
    evals = {}
    for rec in iters:
        for k, v in rec.get("eval_metrics", {}).items():
            evals.setdefault(k, []).append(v)

    roofline = (summary or {}).get("roofline")
    comp = (summary or {}).get("compile")
    interconnect = (summary or {}).get("interconnect")
    trace = (summary or {}).get("trace")

    if as_json:
        print(json.dumps({
            "iterations": n,
            "phase_times_total": {k: round(v, 6)
                                  for k, v in sorted(exec_totals.items())},
            "trace_times_total": {k: round(v, 6)
                                  for k, v in sorted(trace_totals.items())},
            "counters": dict(sorted(counters.items())),
            "health": dict(sorted(health.items())),
            "memory": mem,
            "residency": residency or {},
            "roofline": roofline or {},
            "compile": comp or {},
            "interconnect": interconnect or {},
            "trace": trace or {},
            "eval_first_last": {k: [v[0], v[-1]]
                                for k, v in sorted(evals.items())},
        }))
        return 0

    out = [f"telemetry report: {path}  ({n} iteration records"
           + (", summary present)" if summary else ")"), ""]
    out += _table("Execution phases", exec_totals, n)
    out.append("")
    out += _table("Trace/compile attribution", trace_totals, n)
    out.append("")
    out.append("Kernel-route counters")
    out.append("---------------------")
    if counters:
        width = max(len(k) for k in counters)
        for k, v in sorted(counters.items()):
            out.append(f"{k.ljust(width)}  {v}")
    else:
        out.append("(none recorded)")

    out.append("")
    out.append("Training health (totals)")
    out.append("------------------------")
    if health:
        width = max(len(k) for k in health)
        for k, v in sorted(health.items()):
            val = ("%.6g" % v if isinstance(v, float) else str(v))
            out.append(f"{k.ljust(width)}  {val}")
    else:
        out.append("(no health blocks — train with health=true or "
                   "metrics_out=)")

    out.append("")
    out.append("Memory")
    out.append("------")
    if mem:
        out.append("peak bytes_in_use  %s  (source: %s)"
                   % (_fmt_bytes(mem.get("peak_bytes_in_use", 0)),
                      mem.get("source", "?")))
        if "allhosts_peak_bytes_in_use" in mem:
            out.append("all-hosts peak     %s"
                       % _fmt_bytes(mem["allhosts_peak_bytes_in_use"]))
        deltas = mem.get("phase_delta_bytes", {})
        if deltas:
            width = max(len(k) for k in deltas)
            out.append("per-phase cumulative byte deltas:")
            for k, v in sorted(deltas.items(), key=lambda kv: -abs(kv[1])):
                out.append(f"  {k.ljust(width)}  {_fmt_bytes(v):>12}")
    else:
        out.append("(no memory blocks — train with memory_stats=true or "
                   "metrics_out=)")
    if residency:
        out.append("dataset residency:")
        width = max(len(k) for k in residency)
        for k, v in residency.items():
            val = _fmt_bytes(v) if k.endswith("_bytes") else str(v)
            out.append(f"  {k.ljust(width)}  {val:>12}")
    out.append("")
    out += _ingest_lines(counters, (summary or {}).get("phase_times"))
    out.append("")
    out += _serve_lines(counters)
    out.append("")
    out += _roofline_lines(roofline)
    out.append("")
    out += _interconnect_lines(interconnect)
    out.append("")
    out += _trace_lines(trace)
    out.append("")
    out += _compile_lines(comp)
    if evals:
        out.append("")
        out.append("Eval metrics (first -> last)")
        out.append("----------------------------")
        width = max(len(k) for k in evals)
        for k, v in sorted(evals.items()):
            out.append(f"{k.ljust(width)}  {v[0]} -> {v[-1]}")
    print("\n".join(out))
    return 0


def _monitor_lines(path):
    """The live monitor's windowed snapshot series (ISSUE 20,
    ``monitor_out=`` JSONL): per-window SLO-family delta-sketch count
    and p50/p99, burn rates and breach marks.  Percentiles come from
    the emitted window sketches — exact per-bucket deltas of the
    recorder's cumulative sketches, same resolution contract."""
    import math

    def _quantile(sk, q):
        zero = int(sk.get("zero", 0))
        buckets = {int(i): int(c)
                   for i, c in (sk.get("buckets") or {}).items()}
        total = zero + sum(buckets.values())
        if total == 0:
            return None
        rank = min(total - 1, max(0, int(math.ceil(q * total)) - 1))
        if rank < zero:
            return 0.0
        g, seen = float(sk.get("growth", 1.05)), zero
        for i in sorted(buckets):
            seen += buckets[i]
            if rank < seen:
                return g ** (i + 0.5)
        return None

    try:
        f = open(path)
    except OSError as e:
        raise MalformedJSONL(f"cannot read {path}: {e}")
    header, windows, close = None, [], None
    with f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise MalformedJSONL(f"{path}:{lineno}: bad JSONL ({e})")
            if isinstance(rec, dict) and "monitor_header" in rec:
                header = rec["monitor_header"]
            elif isinstance(rec, dict) and "monitor_window" in rec:
                windows.append(rec["monitor_window"])
            elif isinstance(rec, dict) and "monitor_close" in rec:
                close = rec["monitor_close"]
    if header is None:
        raise MalformedJSONL(f"{path}: no monitor_header line")
    slo = header.get("slo")
    fam = (slo or {}).get("family") or "serve_wall_us"
    out = ["Live monitor (windowed, %s)" % fam,
           "-" * (25 + len(fam)),
           "interval %ss  %d window(s)%s"
           % (header.get("interval_s"), len(windows),
              "  slo p99<=%gus/%gs" % (slo["p99_us"], slo["window_s"])
              if slo else "")]

    def _us(x):
        return ("%9.1f" % x) if isinstance(x, (int, float)) else "%9s" % "-"

    out.append("%6s  %7s  %9s  %9s  %8s  %8s  %s"
               % ("window", "count", "p50 us", "p99 us", "fast", "slow",
                  "breach"))
    for w in windows:
        sk = (w.get("sketches") or {}).get(fam)
        ws = w.get("slo") or {}
        out.append("%6s  %7d  %s  %s  %8s  %8s  %s"
                   % (w.get("window"),
                      0 if sk is None else (
                          int(sk.get("zero", 0))
                          + sum(int(c) for c in
                                (sk.get("buckets") or {}).values())),
                      _us(None if sk is None else _quantile(sk, 0.50)),
                      _us(None if sk is None else _quantile(sk, 0.99)),
                      ("%.3f" % ws["fast_burn"])
                      if isinstance(ws.get("fast_burn"),
                                    (int, float)) else "-",
                      ("%.3f" % ws["slow_burn"])
                      if isinstance(ws.get("slow_burn"),
                                    (int, float)) else "-",
                      "BREACH" if ws.get("breach") else ""))
    if not windows:
        out.append("(no closed windows)")
    if close is not None:
        out.append("close: reason=%s windows=%s breaches=%s"
                   % (close.get("reason"), close.get("windows"),
                      close.get("breaches")))
        for key, d in sorted((close.get("drift") or {}).items()):
            out.append("  drift %s: n=%s psi=%s drift=%s aa_psi=%s"
                       % (key, d.get("n"),
                          "-" if d.get("psi") is None
                          else "%.4f" % d["psi"], d.get("drift"),
                          "-" if d.get("aa_psi") is None
                          else "%.4f" % d["aa_psi"]))
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("path", nargs="?", default=None,
                   help="telemetry JSONL file (metrics_out=...)")
    p.add_argument("--monitor", metavar="JSONL", default=None,
                   help="also render a live-monitor windowed series "
                        "(monitor_out= JSONL, ISSUE 20)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable aggregate instead of tables")
    args = p.parse_args()
    if args.path is None and args.monitor is None:
        p.error("need a telemetry JSONL path and/or --monitor")
    rc = 0
    if args.path is not None:
        rc = report(args.path, as_json=args.json)
    if args.monitor is not None:
        try:
            print("\n".join(_monitor_lines(args.monitor)))
        except MalformedJSONL as e:
            print(f"telemetry_report error: {e}", file=sys.stderr)
            return 2
    return rc


if __name__ == "__main__":
    sys.exit(main())
