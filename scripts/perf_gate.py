"""Perf-regression gate over the BENCH/MULTICHIP round trajectory.

Reads the repo's bench history (``BENCH_r*.json`` wrappers with a
``parsed`` bench record, raw ``bench.py`` JSON lines, and
``MULTICHIP_r*.json`` smoke records) and flags regressions in the LATEST
round against the earlier trajectory:

- **throughput**: the headline ``value`` and the satellite rate keys
  (parity/leafwise_int8/maxbin63 rows, ``vs_cuda``) must not drop below
  the prior median by more than the recorded noise band — the
  ``spread``/``parity_spread``-style (max-min)/median markers bench.py
  records for exactly this purpose (sigma = band/2; flagged beyond
  ``--sigma-mult`` sigmas, default 3);
- **serving latency + zero-tolerance contracts** (ISSUE 13): the
  ``bench_serve`` lane's ``serve_p99_us`` must not GROW beyond the wide
  observability band (LATENCY_KEYS), and ``predict_recompiles`` /
  ``serve_recompiles`` / ``serve_dropped`` / ``serve_misscored`` are
  ABSOLUTE findings — any nonzero on the latest round fails the gate
  with no trajectory at all (the closed-program-ladder and
  zero-drop-hot-swap contracts);
- **attained fraction**: the roofline block's ``frac_of_peak_flops`` /
  ``frac_of_peak_bw`` per phase, when present — a throughput number can
  hide a kernel regression behind a faster host, the attained fraction
  cannot;
- **checkpoint contracts** (ISSUE 14): ``ckpt_overhead_pct`` (the
  bench_ckpt lane's checkpointing-on vs off slowdown) rides the
  must-not-grow latency lane, and ``ckpt_restore_exact`` recorded False
  on ANY round — a same-topology restore that was not bit-identical —
  is an absolute finding, as are ``restore_match``/``metrics_complete``
  False in a multichip round's ``MULTICHIP_ELASTIC`` kill-restart row;
- **multichip**: a round whose smoke run went ok -> not-ok, plus the
  ISSUE-5 distributed-observability trajectory: the ``skew`` block's
  ``max_phase_skew`` (cross-host per-phase dispersion must not grow
  beyond the noise band — a growing ratio is a new straggler or an
  unbalanced schedule) and the ``interconnect`` attained GB/s (must not
  drop — a collective-route regression hides behind a healthy ok flag).
  The block is read from the record itself or parsed out of the smoke
  run's ``tail`` (dryrun_multichip prints one ``MULTICHIP_OBS`` JSON
  line).
- **pod-scope observability** (ISSUE 17): the ``MULTICHIP_PODTRACE``
  line's merge bookkeeping.  Three ABSOLUTE findings need no trajectory
  — ``alignment_ok`` False (a host's clock-offset estimates disagree
  beyond the recorded collective-duration bounds, i.e. the alignment
  error exceeded the bound the dump itself recorded),
  ``check_findings``/``unmodeled`` nonzero (the real pod_report --check
  contracts: header bookkeeping, event conservation, attribution
  identity, byte-model coverage), and ``parity`` False (the post-mortem
  straggler verdict diverged from the live StragglerTracker's over the
  same measurements) — plus a must-not-grow lane on the normalized
  merge overhead (``merge_ms_per_kevent``, wide observability floor:
  tiny smokes, timing-noise-dominated).
- **wire bytes** (ISSUE 9): the ``MULTICHIP_WIRE`` line's logical
  ``wire_bytes_per_iter`` per tree learner (data / hybrid / voting at
  the F=28, B=255 schema).  These are DETERMINISTIC — traced shapes x
  loop estimates, no timing noise — so the must-not-grow band is the
  tight rate-key floor, compared only across rounds at the same device
  count; and two ABSOLUTE findings need no trajectory at all: hybrid
  recording >= pure-DP bytes (the 2-D owned-block restriction stopped
  paying) and voting recording >= hybrid bytes (the voted exchange
  stopped paying).

A group whose ``host`` blocks all say ``device_kind: cpu`` is checked
against the absolute contracts only: a timing from XLA's CPU backend is
not a device metric, so no rate or latency trajectory is gated on it.

Entries are grouped by their ``metric`` name (an 11M round is never
compared to a 1M round) and, when the ``host`` block is present
(bench.py records device_kind/jax versions/git SHA since ISSUE 4), the
gate REFUSES to compare rounds measured on different device kinds
(exit 2) — cross-hardware "regressions" are noise.  Rounds without a
host block (the pre-ISSUE-4 history) are assumed comparable.

Usage (the documented pre-merge check):

    python scripts/perf_gate.py --check 'BENCH_r*.json' 'MULTICHIP_r*.json'

Exit codes: 0 = no regression, 1 = regression flagged, 2 = bad input /
cross-hardware mix.  ``--json`` prints the machine-readable report.
Runs as a tier-1 unit test (tests/test_perf_gate.py: must flag an
injected 3-sigma regression, must pass the committed trajectory).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# satellite rate keys checked next to the headline "value", with the
# spread key that prices their noise band
RATE_KEYS: Tuple[Tuple[str, str], ...] = (
    ("value", "spread"),
    ("vs_cuda", "spread"),
    ("parity_leafwise_f32_iters_per_sec", "parity_spread"),
    ("leafwise_int8_iters_per_sec", "leafwise_int8_spread"),
    ("maxbin63_iters_per_sec", "maxbin63_spread"),
    # mixed-bin packed path, pinned explicitly ON (ISSUE 6): guards the
    # per-class histogram schedule even if the headline's auto
    # resolution ever changes
    ("mixedbin_iters_per_sec", "mixedbin_spread"),
    # the COMPOSED configuration (ISSUE 12): block-local mixed-bin
    # packing on the 2-D hybrid mesh, pinned explicitly ON — the lane
    # that proves the speed tiers multiply instead of exclude
    ("mixedbin_hybrid_iters_per_sec", "mixedbin_hybrid_spread"),
    # serving lanes (ISSUE 7, bench.py --bench-predict): predictions/sec
    # off the compiled serving engine at the gated bucket shapes — the
    # 64k throughput bucket (f32 and int8 ensembles) and the 1k
    # latency-tier bucket.  Latency percentiles (p50/p99) and the
    # bfs-vs-scan A/B ratio ride in the record ungated (lower-is-better
    # keys don't fit the drop-gate; the ratio is informational).
    ("predict_b65536_rows_per_sec", "predict_b65536_spread"),
    ("predict_int8_b65536_rows_per_sec", "predict_int8_b65536_spread"),
    ("predict_b1024_rows_per_sec", "predict_b1024_spread"),
    # the 32-row latency-tier bucket: recorded with a spread marker
    # since r06 but never gated — the exact stale-emission drift the
    # graftlint D2 census now fails the gate on (ISSUE 15)
    ("predict_b32_rows_per_sec", "predict_b32_spread"),
    # streaming ingestion (ISSUE 8, bench.py --bench-ingest): rows/sec
    # for the chunked parse->bin->HBM pipeline.  The double-buffer A/B,
    # H2D GB/s and the peak-RSS assertion ride the record ungated
    # (ingest_rss_ok false would be a correctness bug, not a trajectory
    # drift — the bench lane itself surfaces it).
    ("ingest_rows_per_sec", "ingest_spread"),
    # elastic serving (ISSUE 13, bench.py --bench-serve): sustained
    # rows/sec through the coalescing ServingFront under the open-loop
    # load generator.  The p99 lane rides LATENCY_KEYS (must-not-grow);
    # recompiles/dropped/misscored are absolute findings below.
    ("serve_rows_per_sec", "serve_spread"),
)

# lower-is-better keys gated in the GROW direction (ISSUE 13): the p99
# under open-loop load.  Latency tails on a shared host swing far more
# than throughput medians, so the band floor is the wide observability
# floor (like the multichip skew series): the lane catches
# order-of-magnitude breaks — a lost coalescing path, a swap stall in
# the request path — not percent drift.
LATENCY_KEYS: Tuple[Tuple[str, str], ...] = (
    ("serve_p99_us", "serve_spread"),
    # checkpoint cost (ISSUE 14, bench.py --bench-ckpt): percent slowdown
    # of the training loop with async checkpointing ON vs OFF.  Lower is
    # better; gated must-not-grow at the wide observability floor (the
    # overhead is a small difference of two noisy wall times).
    ("ckpt_overhead_pct", "ckpt_spread"),
    # flight-recorder cost (ISSUE 16, bench.py --bench-serve): percent
    # serve throughput lost with the recorder armed, from interleaved
    # recorder-on/off segments of the same open-loop load.  "Always-on"
    # is only honest while this stays flat — gated must-not-grow at the
    # wide observability floor (a small difference of two noisy rates).
    ("trace_overhead_pct", "trace_spread"),
    # live-monitor cost (ISSUE 20, bench.py --bench-serve): percent
    # serve throughput lost with the monitor armed ON TOP of the
    # recorder, from interleaved monitor-on/off segments — same honesty
    # contract as trace_overhead_pct, same wide band.
    ("monitor_overhead_pct", "monitor_spread"),
)

# mirror of lightgbm_tpu.monitor.AA_PSI_BOUND — the documented A/A
# false-positive bound the bench's drift_aa_psi must stay under (kept
# inline: the gate runs on hosts without the package)
AA_PSI_BOUND = 0.05

# absolute zero-tolerance keys (no trajectory needed): any nonzero on
# the LATEST round is a finding.  predict/serve recompiles break the
# closed-program-ladder contract; dropped/misscored requests break the
# hot-swap zero-drop contract (ISSUE 13).
ABSOLUTE_ZERO_KEYS: Tuple[Tuple[str, str], ...] = (
    ("predict_recompiles",
     "serving engine recompiled at a bucketed batch shape (the "
     "compiled-program ladder is no longer closed)"),
    ("serve_recompiles",
     "elastic-serving lane recompiled at a coalesced batch shape (the "
     "compiled-program ladder is no longer closed under load)"),
    ("serve_dropped",
     "request(s) dropped across the mid-load hot swap — the "
     "drain-and-flip zero-drop contract is broken"),
    ("serve_misscored",
     "request(s) misscored across the mid-load hot swap (a result "
     "matched neither the old nor the new engine — a torn swap)"),
    ("trace_dropped_at_default",
     "flight-recorder ring overflowed at the DEFAULT trace_ring_events "
     "during a measured serve window (ISSUE 16) — the last-N-events "
     "crash timeline no longer covers a single load segment"),
)

# absolute must-be-true keys (ISSUE 14): a recorded value of exactly
# False on ANY round in the trajectory is a finding — these are
# correctness contracts, not trajectories.  Absent keys (older rounds)
# are fine.
ABSOLUTE_TRUE_KEYS: Tuple[Tuple[str, str], ...] = (
    ("ckpt_restore_exact",
     "a checkpoint restore was not bit-identical on the same topology "
     "(model text / scores / RNG streams diverged from the "
     "uninterrupted run)"),
)

DEFAULT_FLOOR = 0.02      # minimum relative noise band when none recorded
DEFAULT_SIGMA_MULT = 3.0
# noise-band floor for the multichip skew/interconnect series (no
# recorded spread; tiny smoke runs -> timing-noise-dominated) — also the
# LATENCY_KEYS floor, for the same reason
_OBS_FLOOR = 0.5


class GateError(Exception):
    """Malformed input or an invalid comparison (exit code 2)."""


def _round_of(path: str, data: dict) -> int:
    n = data.get("n") or data.get("round")
    if isinstance(n, int):
        return n
    m = re.search(r"r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else 0


def load_entry(path: str) -> dict:
    """One trajectory entry: {kind: bench|multichip, round, rec, path}."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise GateError(f"{path}: unreadable bench JSON ({e})")
    if not isinstance(data, dict):
        raise GateError(f"{path}: expected a JSON object")
    if isinstance(data.get("parsed"), dict):
        rec, kind = data["parsed"], "bench"
    elif "metric" in data:
        rec, kind = data, "bench"
    elif "n_devices" in data or "ok" in data:
        rec, kind = data, "multichip"
        _attach_multichip_obs(rec)
    else:
        raise GateError(f"{path}: unrecognized bench record "
                        "(no 'parsed', 'metric' or multichip keys)")
    return {"kind": kind, "round": _round_of(path, data), "rec": rec,
            "path": path}


def _attach_multichip_obs(rec: dict) -> None:
    """Surface the distributed-observability block on a multichip record:
    either already present as ``skew``/``interconnect``/``wire`` keys, or
    parsed from the smoke run's captured ``tail`` (dryrun_multichip
    prints one ``MULTICHIP_OBS <json>`` line and, since ISSUE 9, one
    ``MULTICHIP_WIRE <json>`` line).  Malformed/absent lines leave the
    record untouched — earlier rounds simply have no such series."""
    tail = rec.get("tail")
    lines = tail.splitlines() if isinstance(tail, str) else []
    if "skew" not in rec:
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_OBS "):
                continue
            try:
                obs = json.loads(line[len("MULTICHIP_OBS "):])
            except ValueError:
                break
            if isinstance(obs, dict):
                for key in ("skew", "interconnect", "simulated_hosts"):
                    if key in obs:
                        rec[key] = obs[key]
            break
    if "wire" not in rec:
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_WIRE "):
                continue
            try:
                wire = json.loads(line[len("MULTICHIP_WIRE "):])
            except ValueError:
                break
            if isinstance(wire, dict):
                rec["wire"] = wire
            break
    if "elastic" not in rec:
        # ISSUE 14: the kill-a-process-mid-run row prints one
        # MULTICHIP_ELASTIC JSON line (SIGKILL between iterations →
        # restart from the latest checkpoint on a shrunk topology)
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_ELASTIC "):
                continue
            try:
                el = json.loads(line[len("MULTICHIP_ELASTIC "):])
            except ValueError:
                break
            if isinstance(el, dict):
                rec["elastic"] = el
            break
    if "podtrace" not in rec:
        # ISSUE 17: the pod-scope observability row prints one
        # MULTICHIP_PODTRACE JSON line (two real processes -> per-host
        # dumps -> pod_report --check on the merge)
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_PODTRACE "):
                continue
            try:
                pt = json.loads(line[len("MULTICHIP_PODTRACE "):])
            except ValueError:
                break
            if isinstance(pt, dict):
                rec["podtrace"] = pt
            break
    if "monitor" not in rec:
        # ISSUE 20: the live-monitor row prints one MULTICHIP_MONITOR
        # JSON line (induced latency bulge -> SLO burn breach;
        # shifted-score swap -> drift verdict; A/A self-check under its
        # bound; monitor_report/trace_report --check clean)
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_MONITOR "):
                continue
            try:
                mon = json.loads(line[len("MULTICHIP_MONITOR "):])
            except ValueError:
                break
            if isinstance(mon, dict):
                rec["monitor"] = mon
            break
    if "sharded_ingest" not in rec:
        # ISSUE 18: the multi-host sharded-ingest row prints one
        # MULTICHIP_SHARDED_INGEST JSON line (every rank parses only
        # its own row shard's byte ranges — per-host parsed-row counts
        # must tile the dataset exactly, zero overlap)
        for line in reversed(lines):
            line = line.strip()
            if not line.startswith("MULTICHIP_SHARDED_INGEST "):
                continue
            try:
                si = json.loads(line[len("MULTICHIP_SHARDED_INGEST "):])
            except ValueError:
                break
            if isinstance(si, dict):
                rec["sharded_ingest"] = si
            break


def _fractions(rec: dict) -> Dict[str, float]:
    """Flatten the roofline attained fractions into gate keys."""
    out = {}
    phases = (rec.get("roofline") or {}).get("phases") or {}
    for phase, blk in phases.items():
        for f in ("frac_of_peak_flops", "frac_of_peak_bw"):
            v = blk.get(f)
            if isinstance(v, (int, float)):
                out[f"roofline/{phase}/{f}"] = float(v)
    return out


def _series(entries: List[dict], key: str) -> List[Tuple[int, float]]:
    out = []
    for e in entries:
        v = e["rec"].get(key)
        if key.startswith("roofline/"):
            v = _fractions(e["rec"]).get(key)
        if isinstance(v, (int, float)):
            out.append((e["round"], float(v)))
    return out


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _noise_band(entries: List[dict], spread_key: str, floor: float) -> float:
    """Noise band from the PRIOR rounds only (callers pass entries[:-1]):
    a regressed round must not widen its own allowance by also reporting
    a wide spread (self-masking)."""
    spreads = [float(e["rec"][spread_key]) for e in entries
               if isinstance(e["rec"].get(spread_key), (int, float))]
    return max(spreads + [floor])


def _check_group(metric: str, entries: List[dict], floor: float,
                 sigma_mult: float, allow_cross_hardware: bool,
                 findings: List[dict]) -> None:
    entries = sorted(entries, key=lambda e: e["round"])
    kinds = {e["rec"].get("host", {}).get("device_kind")
             for e in entries if isinstance(e["rec"].get("host"), dict)}
    kinds.discard(None)
    if len(kinds) > 1 and not allow_cross_hardware:
        raise GateError(
            f"{metric}: trajectory mixes device kinds {sorted(kinds)} — "
            "cross-hardware comparisons refused "
            "(--allow-cross-hardware to override)")
    # absolute zero-tolerance contracts (ISSUE 7 no-recompile, ISSUE 13
    # zero-drop hot swap): any nonzero on the latest round is a finding,
    # no trajectory needed
    for akey, detail in ABSOLUTE_ZERO_KEYS:
        v = entries[-1]["rec"].get(akey)
        if isinstance(v, (int, float)) and v > 0:
            findings.append({
                "metric": metric, "key": akey,
                "latest_round": entries[-1]["round"],
                "latest": v, "baseline": 0,
                "detail": detail,
            })
    # must-be-true contracts (ISSUE 14): checked on EVERY recorded round
    # — a round that recorded a non-bit-identical checkpoint restore is
    # a finding forever, not only while it is the latest
    for akey, detail in ABSOLUTE_TRUE_KEYS:
        for e in entries:
            if e["rec"].get(akey) is False:
                findings.append({
                    "metric": metric, "key": akey,
                    "latest_round": e["round"],
                    "latest": False, "baseline": True,
                    "detail": detail,
                })
    _check_mixedbin_resolution(metric, entries[-1], findings)
    _check_ingest_workers(metric, entries, findings)
    _check_drift_slo(metric, entries[-1], findings)
    if len(entries) < 2:
        return
    if kinds == {"cpu"}:
        # a series recorded on the CPU backend carries no device metric:
        # its rates and latencies are host timings of XLA's CPU backend
        # (r06+ at 4,096 rows), so they are not held to a trajectory —
        # the absolute contracts above still are
        return
    latest_round = entries[-1]["round"]
    keys = [k for k, _ in RATE_KEYS]
    keys += sorted({k for e in entries for k in _fractions(e["rec"])})
    spread_of = dict(RATE_KEYS)
    for key in keys:
        series = _series(entries, key)
        if len(series) < 2 or series[-1][0] != latest_round:
            continue
        prior = [v for r, v in series[:-1]]
        latest = series[-1][1]
        baseline = _median(prior)
        if baseline <= 0:
            continue
        band = _noise_band(entries[:-1], spread_of.get(key, "spread"),
                           floor)
        sigma = band / 2.0
        threshold = baseline * (1.0 - sigma_mult * sigma)
        if latest < threshold:
            findings.append({
                "metric": metric, "key": key,
                "latest_round": latest_round,
                "latest": latest, "baseline": round(baseline, 6),
                "drop": round(1.0 - latest / baseline, 4),
                "allowed_drop": round(sigma_mult * sigma, 4),
            })
    # lower-is-better latency lanes (ISSUE 13): must not GROW beyond
    # the wide observability band — p99 tails are timing-noise-dominated
    # on shared hosts, so this catches order-of-magnitude breaks
    for key, spread_key in LATENCY_KEYS:
        series = _series(entries, key)
        if len(series) < 2 or series[-1][0] != latest_round:
            continue
        prior = [v for r, v in series[:-1]]
        latest = series[-1][1]
        baseline = _median(prior)
        if baseline <= 0:
            continue
        band = max(_noise_band(entries[:-1], spread_key, floor),
                   _OBS_FLOOR)
        sigma = band / 2.0
        if latest > baseline * (1.0 + sigma_mult * sigma):
            findings.append({
                "metric": metric, "key": key,
                "latest_round": latest_round,
                "latest": latest, "baseline": round(baseline, 6),
                "drop": round(latest / baseline - 1.0, 4),
                "allowed_drop": round(sigma_mult * sigma, 4),
            })


def _check_mixedbin_resolution(metric: str, latest: dict,
                               findings: List[dict]) -> None:
    """ISSUE 12 absolute finding, no trajectory needed: a recorded
    hybrid/voting round whose config requested ``mixed_bin`` auto/true
    on a mixed-cardinality table but whose booster resolved the UNIFORM
    layout — the silent fallback the pre-ISSUE-12 ``needs_uniform_layout``
    gate used to take — must not pass the gate unnoticed.  Reads the
    bench record's resolution keys (``tree_learner`` /
    ``mixed_bin_requested`` / ``mixedbin_expected`` / ``mixed_bin_on``,
    both bare for a headline parallel run and under the
    ``mixedbin_hybrid_`` prefix the composed satellite lane copies).
    ``mixedbin_expected`` guards ``auto``: a genuinely single-class
    table resolving off is a correct resolution, not a regression."""
    rec = latest["rec"]
    for prefix in ("", "mixedbin_hybrid_"):
        learner = rec.get(prefix + "tree_learner")
        requested = rec.get(prefix + "mixed_bin_requested")
        resolved = rec.get(prefix + "mixed_bin_on")
        expected = rec.get(prefix + "mixedbin_expected")
        if learner not in ("hybrid", "voting") or resolved is not False:
            continue
        if requested == "true" or (requested == "auto" and expected):
            findings.append({
                "metric": metric,
                "key": (prefix or "headline_") + "mixed_bin_resolution",
                "latest_round": latest["round"],
                "latest": False, "baseline": True,
                "detail": "%s round requested mixed_bin=%s on a "
                          "mixed-cardinality table but resolved the "
                          "uniform layout (block-local packing silently "
                          "fell back)" % (learner, requested),
            })


def _check_drift_slo(metric: str, latest: dict,
                     findings: List[dict]) -> None:
    """ISSUE 20 absolute findings on the latest bench round, no
    trajectory needed: ``drift_aa_psi`` above the documented A/A bound
    means the score-drift detector's false-positive floor rose past its
    own spec (every production swap would risk a spurious drift page),
    and ``monitor_slo_breaches > 0`` on a round that did NOT declare an
    induced fault means the generous bench SLO (20x the measured
    healthy p99) burned on healthy load — either the serving path
    developed a real bulge or the burn arithmetic broke."""
    rec = latest["rec"]
    aa = rec.get("drift_aa_psi")
    if isinstance(aa, (int, float)) and aa > AA_PSI_BOUND:
        findings.append({
            "metric": metric, "key": "drift_aa_psi",
            "latest_round": latest["round"],
            "latest": aa, "baseline": AA_PSI_BOUND,
            "detail": "A/A self-check PSI %.4g exceeds the documented "
                      "false-positive bound %.2g — same-distribution "
                      "halves look drifted, so every real drift verdict "
                      "is suspect" % (aa, AA_PSI_BOUND),
        })
    breaches = rec.get("monitor_slo_breaches")
    if isinstance(breaches, (int, float)) and breaches > 0 \
            and not rec.get("monitor_induced_fault"):
        findings.append({
            "metric": metric, "key": "monitor_slo_breaches",
            "latest_round": latest["round"],
            "latest": breaches, "baseline": 0,
            "detail": "SLO burn-rate breach(es) fired on a healthy "
                      "bench round with no declared induced fault — the "
                      "20x-generous objective burned on steady load",
        })


def _check_ingest_workers(metric: str, entries: List[dict],
                          findings: List[dict]) -> None:
    """ISSUE 18: the parallel-ingest lanes.  Two contracts, checked on
    EVERY round that recorded ``ingest_workers > 1`` (like the
    mixed-bin resolution check, these are claims about that round, not
    trajectories):

    - must-GROW: a round that ran the byte-range worker pool exists to
      beat the serial tokenizer — its ``ingest_rows_per_sec`` must
      strictly exceed the serial baseline.  The baseline is the
      round's OWN recorded ``ingest_serial_rows_per_sec`` when present
      (the bench lane prices both loaders on the same file, same scale,
      same host — the matched comparison), else the median of all
      strictly-earlier rounds that did NOT record
      ``ingest_workers > 1`` (the r06-r08 serial history).  A parallel
      round at-or-below serial throughput means the fan-out stopped
      paying and must not pass unnoticed.  Skipped when neither
      baseline exists.
    - absolute: a round that REQUESTED workers but recorded
      ``ingest_workers_effective <= 1`` silently resolved to the serial
      loader (fork unavailable, or the dispatch fell through) — the
      lane would then gate serial numbers as if they were parallel."""
    for i, e in enumerate(entries):
        rec = e["rec"]
        workers = rec.get("ingest_workers")
        if not isinstance(workers, (int, float)) or workers <= 1:
            continue
        effective = rec.get("ingest_workers_effective")
        if isinstance(effective, (int, float)) and effective <= 1:
            findings.append({
                "metric": metric, "key": "ingest_workers_effective",
                "latest_round": e["round"],
                "latest": effective, "baseline": workers,
                "detail": "round requested ingest_workers=%d but the "
                          "load resolved to the serial parse silently "
                          "(effective=%d)" % (workers, effective),
            })
        rate = rec.get("ingest_rows_per_sec")
        if not isinstance(rate, (int, float)):
            continue
        own_serial = rec.get("ingest_serial_rows_per_sec")
        if isinstance(own_serial, (int, float)):
            baseline = float(own_serial)
        else:
            serial_prior = [
                float(p["rec"]["ingest_rows_per_sec"])
                for p in entries[:i]
                if isinstance(p["rec"].get("ingest_rows_per_sec"),
                              (int, float))
                and not (isinstance(p["rec"].get("ingest_workers"),
                                    (int, float))
                         and p["rec"]["ingest_workers"] > 1)]
            if not serial_prior:
                continue
            baseline = _median(serial_prior)
        if baseline > 0 and float(rate) <= baseline:
            findings.append({
                "metric": metric, "key": "ingest_rows_per_sec_must_grow",
                "latest_round": e["round"],
                "latest": float(rate), "baseline": round(baseline, 6),
                "detail": "round ran ingest_workers=%d but "
                          "ingest_rows_per_sec did not grow past the "
                          "serial baseline (%s) — the parallel parse "
                          "stopped paying"
                          % (workers,
                             "same-record serial lane"
                             if isinstance(own_serial, (int, float))
                             else "serial-round median"),
            })


def _multichip_obs_value(rec: dict, key: str) -> Optional[float]:
    """The two gated observability series on a multichip record."""
    if key == "skew/max_phase_skew":
        skew = rec.get("skew")
        if isinstance(skew, dict) and isinstance(
                skew.get("max_phase_skew"), (int, float)):
            # a round that compared no iterations has no skew signal
            if skew.get("iterations_compared", 0) > 0 \
                    and skew["max_phase_skew"] > 0:
                return float(skew["max_phase_skew"])
        return None
    if key == "interconnect/attained_gb_per_s":
        ic = rec.get("interconnect")
        if isinstance(ic, dict) and isinstance(
                ic.get("attained_gb_per_s"), (int, float)) \
                and ic["attained_gb_per_s"] > 0:
            return float(ic["attained_gb_per_s"])
    if key.startswith("wire/"):
        wire = rec.get("wire")
        if isinstance(wire, dict):
            v = (wire.get("wire_bytes_per_iter") or {}).get(
                key.split("/", 1)[1])
            if isinstance(v, (int, float)) and v > 0:
                return float(v)
    return None


def _check_multichip(entries: List[dict], findings: List[dict],
                     floor: float = DEFAULT_FLOOR,
                     sigma_mult: float = DEFAULT_SIGMA_MULT) -> None:
    entries = sorted(entries, key=lambda e: e["round"])
    # ISSUE 14 absolute contracts on the kill-restart row, checked on
    # every round that recorded one: a restore that lost finished trees
    # or metric records, or that diverged from the uninterrupted run's
    # budget class, must not pass the gate
    for e in entries:
        el = e["rec"].get("elastic")
        if not isinstance(el, dict):
            continue
        for akey, detail in (
                ("restore_match",
                 "the restarted run's final model diverged from the "
                 "uninterrupted reference beyond the documented budget "
                 "class"),
                ("metrics_complete",
                 "iteration/metric records were lost across the "
                 "kill-restart (coverage of the iteration range has "
                 "gaps)")):
            if el.get(akey) is False:
                findings.append({
                    "metric": "multichip", "key": "elastic/" + akey,
                    "latest_round": e["round"],
                    "latest": False, "baseline": True,
                    "detail": detail,
                })
    if len(entries) < 2:
        return
    latest = entries[-1]
    if not latest["rec"].get("ok", False) and any(
            e["rec"].get("ok") for e in entries[:-1]):
        findings.append({
            "metric": "multichip", "key": "ok",
            "latest_round": latest["round"],
            "latest": False, "baseline": True,
            "detail": "multichip smoke went ok -> not-ok",
        })
    # ISSUE 5: the skew/interconnect trajectory.  No recorded spread for
    # these series, and the smoke runs are tiny (compile warmth and host
    # load dominate — the simulated-host skew legitimately swings ~2x),
    # so the band floor is wide: these series catch ORDER-OF-MAGNITUDE
    # breaks (a collective route regression, a new persistent straggler),
    # not percent drift.  sigma = band/2 like the rate keys.
    sigma = max(floor, _OBS_FLOOR) / 2.0
    for key, direction in (("skew/max_phase_skew", "up"),
                           ("interconnect/attained_gb_per_s", "down")):
        series = [(e["round"], _multichip_obs_value(e["rec"], key))
                  for e in entries]
        series = [(r, v) for r, v in series if v is not None]
        if len(series) < 2 or series[-1][0] != latest["round"]:
            continue
        prior = [v for _, v in series[:-1]]
        latest_v = series[-1][1]
        baseline = _median(prior)
        if baseline <= 0:
            continue
        if direction == "up":
            threshold = baseline * (1.0 + sigma_mult * sigma)
            regressed = latest_v > threshold
            drop = latest_v / baseline - 1.0
        else:
            threshold = baseline * (1.0 - sigma_mult * sigma)
            regressed = latest_v < threshold
            drop = 1.0 - latest_v / baseline
        if regressed:
            findings.append({
                "metric": "multichip", "key": key,
                "latest_round": latest["round"],
                "latest": latest_v, "baseline": round(baseline, 6),
                "drop": round(drop, 4),
                "allowed_drop": round(sigma_mult * sigma, 4),
            })


def _check_podtrace(entries: List[dict], findings: List[dict],
                    floor: float = DEFAULT_FLOOR,
                    sigma_mult: float = DEFAULT_SIGMA_MULT) -> None:
    """ISSUE 17: the pod-merge bookkeeping from the MULTICHIP_PODTRACE
    block.  Absolute contracts checked on EVERY round that recorded one
    (these are correctness claims about that round's merge, not
    trajectories): alignment error exceeding the dump's own recorded
    collective-duration bound, any real pod_report --check finding, a
    measured seam missing from the byte model, and live-vs-post-mortem
    straggler verdict divergence.  The normalized merge overhead
    (``merge_ms_per_kevent``) rides a must-not-grow lane at the wide
    observability floor — the smoke merges a tiny ring, so only
    order-of-magnitude breaks (an accidentally quadratic merge) are
    signal."""
    entries = sorted(entries, key=lambda e: e["round"])
    for e in entries:
        pt = e["rec"].get("podtrace")
        if not isinstance(pt, dict):
            continue
        checks = (
            ("alignment_ok", pt.get("alignment_ok") is False,
             "a host's clock-offset estimates disagree beyond the "
             "recorded collective-duration bounds — the alignment error "
             "exceeded the bound the dumps themselves recorded"),
            ("check_findings",
             isinstance(pt.get("check_findings"), (int, float))
             and pt["check_findings"] > 0,
             "pod_report --check flagged merge-contract violations "
             "(header bookkeeping / event conservation / attribution "
             "identity)"),
            ("unmodeled",
             isinstance(pt.get("unmodeled"), (int, float))
             and pt["unmodeled"] > 0,
             "measured collective seam(s) missing from the wire byte "
             "model — byte-model drift"),
            ("parity", pt.get("parity") is False,
             "the post-mortem straggler verdict diverged from the live "
             "StragglerTracker's over the same measurements — the one-"
             "rule contract is broken"),
        )
        for key, bad, detail in checks:
            if bad:
                findings.append({
                    "metric": "multichip", "key": "podtrace/" + key,
                    "latest_round": e["round"],
                    "latest": pt.get(key), "baseline": None,
                    "detail": detail,
                })
    series = [(e["round"], float(pt["merge_ms_per_kevent"]))
              for e in entries
              for pt in [e["rec"].get("podtrace")]
              if isinstance(pt, dict) and isinstance(
                  pt.get("merge_ms_per_kevent"), (int, float))
              and pt["merge_ms_per_kevent"] > 0]
    if len(series) < 2 or series[-1][0] != entries[-1]["round"]:
        return
    prior = [v for _, v in series[:-1]]
    latest_v = series[-1][1]
    baseline = _median(prior)
    sigma = max(floor, _OBS_FLOOR) / 2.0
    if baseline > 0 and latest_v > baseline * (1.0 + sigma_mult * sigma):
        findings.append({
            "metric": "multichip", "key": "podtrace/merge_ms_per_kevent",
            "latest_round": series[-1][0],
            "latest": latest_v, "baseline": round(baseline, 6),
            "drop": round(latest_v / baseline - 1.0, 4),
            "allowed_drop": round(sigma_mult * sigma, 4),
        })


def _check_sharded_ingest(entries: List[dict],
                          findings: List[dict]) -> None:
    """ISSUE 18c: the multi-host sharded-ingest row from the
    MULTICHIP_SHARDED_INGEST block.  Absolute per-round contracts (no
    trajectory): every rank parses only its own row shard's byte
    ranges, so the per-host parsed-row counts must sum to the dataset
    with zero overlap, tile it exactly (coverage), and bin
    bit-identically to the serial masked load."""
    for e in sorted(entries, key=lambda e: e["round"]):
        si = e["rec"].get("sharded_ingest")
        if not isinstance(si, dict):
            continue
        host_rows = si.get("host_rows")
        total = si.get("total")
        rows_sum = (sum(host_rows) if isinstance(host_rows, list)
                    and all(isinstance(v, (int, float))
                            for v in host_rows) else None)
        checks = (
            ("ok", si.get("ok") is False, si.get("ok"),
             "the sharded-ingest smoke failed outright"),
            ("host_rows_sum",
             rows_sum is not None and isinstance(total, (int, float))
             and rows_sum != total, rows_sum,
             "per-host parsed-row counts do not sum to the dataset "
             "(%s != %s)" % (rows_sum, total)),
            ("overlap",
             isinstance(si.get("overlap"), (int, float))
             and si["overlap"] > 0, si.get("overlap"),
             "hosts parsed overlapping global rows — shard ownership "
             "leaked across ranks"),
            ("coverage_ok", si.get("coverage_ok") is False,
             si.get("coverage_ok"),
             "the union of per-host row shards does not tile the "
             "dataset exactly"),
            ("bit_identical", si.get("bit_identical") is False,
             si.get("bit_identical"),
             "a host's sharded parse binned differently from the "
             "serial masked load"),
        )
        for key, bad, latest, detail in checks:
            if bad:
                findings.append({
                    "metric": "multichip",
                    "key": "sharded_ingest/" + key,
                    "latest_round": e["round"],
                    "latest": latest, "baseline": None,
                    "detail": detail,
                })


def _check_monitor(entries: List[dict], findings: List[dict]) -> None:
    """ISSUE 20: the live-monitor row from the MULTICHIP_MONITOR block.
    Absolute per-round contracts (correctness claims about that round's
    smoke, not trajectories): the induced latency bulge must trip the
    fast+slow burn rule, the shifted-score swap must trip the PSI drift
    verdict, the healthy engine's A/A self-check must hold under its
    bound, and both the monitor_report and trace_report checkers must
    come back clean (delta/total conservation, burn arithmetic,
    re-derived drift verdicts, slo_breach <-> monitor_window linkage)."""
    for e in sorted(entries, key=lambda e: e["round"]):
        mon = e["rec"].get("monitor")
        if not isinstance(mon, dict):
            continue
        checks = (
            ("breaches",
             isinstance(mon.get("breaches"), (int, float))
             and mon["breaches"] < 1, mon.get("breaches"),
             "the induced latency bulge did not trip the fast+slow SLO "
             "burn rule — the monitor missed the exact failure it "
             "exists for"),
            ("drift", mon.get("drift") is False, mon.get("drift"),
             "the shifted-score engine swap did not trip the PSI drift "
             "verdict"),
            ("aa_ok", mon.get("aa_ok") is False, mon.get("aa_psi"),
             "the healthy engine's A/A self-check exceeded its "
             "false-positive bound"),
            ("check_findings",
             isinstance(mon.get("check_findings"), (int, float))
             and mon["check_findings"] > 0, mon.get("check_findings"),
             "monitor_report --check flagged contract violations "
             "(delta/total conservation, burn arithmetic, or a drift "
             "verdict disagreeing with its own buckets)"),
            ("trace_check_findings",
             isinstance(mon.get("trace_check_findings"), (int, float))
             and mon["trace_check_findings"] > 0,
             mon.get("trace_check_findings"),
             "trace_report --check flagged the monitored round's dump "
             "(slo_breach <-> monitor_window linkage or ring "
             "contracts)"),
        )
        for key, bad, latest, detail in checks:
            if bad:
                findings.append({
                    "metric": "multichip", "key": "monitor/" + key,
                    "latest_round": e["round"],
                    "latest": latest, "baseline": None,
                    "detail": detail,
                })


def _check_wire(entries: List[dict], findings: List[dict],
                floor: float = DEFAULT_FLOOR,
                sigma_mult: float = DEFAULT_SIGMA_MULT) -> None:
    """ISSUE 9: the logical wire-bytes-per-iteration series from the
    MULTICHIP_WIRE block.  Two absolute findings on the latest round
    (hybrid >= pure-DP bytes; voting >= hybrid bytes — the 2-D/voted
    restrictions stopped paying), plus a must-not-grow gate per learner
    with the TIGHT rate-key band (the series is deterministic: traced
    shapes x loop estimates, zero timing noise), compared only across
    rounds at the same device count."""
    latest = entries[-1]
    wire = latest["rec"].get("wire")
    if isinstance(wire, dict):
        w = wire.get("wire_bytes_per_iter") or {}
        for a, b in (("hybrid", "data"), ("voting", "hybrid")):
            va, vb = w.get(a), w.get(b)
            if isinstance(va, (int, float)) and isinstance(
                    vb, (int, float)) and va >= vb > 0:
                findings.append({
                    "metric": "multichip", "key": "wire/%s_vs_%s" % (a, b),
                    "latest_round": latest["round"],
                    "latest": va, "baseline": vb,
                    "detail": "%s records >= %s logical wire bytes per "
                              "iteration on the same device count" % (a, b),
                })
    if len(entries) < 2:
        return
    sigma = floor / 2.0
    nd = (wire or {}).get("n_devices")
    for learner in ("data", "hybrid", "voting"):
        key = "wire/" + learner
        series = [(e["round"], _multichip_obs_value(e["rec"], key))
                  for e in entries
                  if (e["rec"].get("wire") or {}).get("n_devices") == nd]
        series = [(r, v) for r, v in series if v is not None]
        if len(series) < 2 or series[-1][0] != latest["round"]:
            continue
        prior = [v for _, v in series[:-1]]
        latest_v = series[-1][1]
        baseline = _median(prior)
        if baseline <= 0:
            continue
        if latest_v > baseline * (1.0 + sigma_mult * sigma):
            findings.append({
                "metric": "multichip", "key": key,
                "latest_round": latest["round"],
                "latest": latest_v, "baseline": round(baseline, 6),
                "drop": round(latest_v / baseline - 1.0, 4),
                "allowed_drop": round(sigma_mult * sigma, 4),
            })


def check_files(paths: List[str], floor: float = DEFAULT_FLOOR,
                sigma_mult: float = DEFAULT_SIGMA_MULT,
                allow_cross_hardware: bool = False) -> dict:
    """Gate a trajectory; returns the report dict (``findings`` empty on
    a clean pass).  Raises GateError on malformed/uncomparable input."""
    if not paths:
        raise GateError("no bench history files matched")
    entries = [load_entry(p) for p in paths]
    groups: Dict[str, List[dict]] = {}
    multichip: List[dict] = []
    for e in entries:
        if e["kind"] == "multichip":
            multichip.append(e)
        else:
            groups.setdefault(str(e["rec"].get("metric", "?")),
                              []).append(e)
    findings: List[dict] = []
    for metric, group in sorted(groups.items()):
        _check_group(metric, group, floor, sigma_mult,
                     allow_cross_hardware, findings)
    _check_multichip(multichip, findings, floor=floor,
                     sigma_mult=sigma_mult)
    if multichip:
        _check_wire(sorted(multichip, key=lambda e: e["round"]), findings,
                    floor=floor, sigma_mult=sigma_mult)
        _check_podtrace(multichip, findings, floor=floor,
                        sigma_mult=sigma_mult)
        _check_sharded_ingest(multichip, findings)
        _check_monitor(multichip, findings)
    return {
        "files": len(entries),
        "groups": {m: len(g) for m, g in sorted(groups.items())},
        "multichip_rounds": len(multichip),
        "sigma_mult": sigma_mult, "floor": floor,
        "findings": findings,
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--check", nargs="+", metavar="GLOB", required=True,
                   help="bench history globs, e.g. 'BENCH_r*.json' "
                        "'MULTICHIP_r*.json'")
    p.add_argument("--floor", type=float, default=DEFAULT_FLOOR,
                   help="minimum relative noise band when no spread is "
                        "recorded (default %(default)s)")
    p.add_argument("--sigma-mult", type=float, default=DEFAULT_SIGMA_MULT,
                   help="flag drops beyond this many sigmas "
                        "(sigma = band/2; default %(default)s)")
    p.add_argument("--allow-cross-hardware", action="store_true",
                   help="compare rounds across device kinds anyway")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    args = p.parse_args(argv)
    paths = sorted({f for g in args.check for f in glob.glob(g)})
    try:
        report = check_files(paths, floor=args.floor,
                             sigma_mult=args.sigma_mult,
                             allow_cross_hardware=args.allow_cross_hardware)
    except GateError as e:
        print(f"perf_gate error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(report))
    else:
        for f in report["findings"]:
            if "drop" in f:
                print("REGRESSION %s %s: round %s at %.4g, %.1f%% below "
                      "the prior median %.4g (allowed %.1f%%)"
                      % (f["metric"], f["key"], f["latest_round"],
                         f["latest"], 100 * f["drop"], f["baseline"],
                         100 * f["allowed_drop"]))
            else:
                print("REGRESSION %s %s: %s"
                      % (f["metric"], f["key"],
                         f.get("detail", "regressed")))
        if not report["findings"]:
            print("perf_gate: %d file(s), %d metric group(s) — no "
                  "regression beyond the noise bands"
                  % (report["files"], len(report["groups"])))
    return 1 if report["findings"] else 0


if __name__ == "__main__":
    sys.exit(main())
