#!/usr/bin/env python
"""graftlint — static AST + jaxpr + concurrency/drift analyzer (gate).

Runs beside ``scripts/perf_gate.py --check`` with the same exit-code
contract (0 clean / 1 findings / 2 tool error):

    python scripts/graftlint.py --check

Layer 1 (AST, no JAX needed) walks the package source for the
review-hardening rule catalog (R1 collective-seam-coverage, R2
cache-key-completeness, R3 span-fencing, R4
banned-patterns-in-traced-code); Layer 2 traces the canonical
small-schema programs (serial/DP/hybrid/voting grow, serving BFS, the
int8 histogram exchange) under ``JAX_PLATFORMS=cpu`` and walks their
closed jaxprs (J1 dtype discipline, J2 collective census vs the declared
telemetry seam inventory).  Layer 3 (ISSUE 15, no JAX needed) covers
the threaded subsystems (C1 thread-lifecycle-registration, C2
future-set-race, C3 blocking-under-lock, C4 env-hatch-discipline) and
the cross-artifact drift censuses (D1 telemetry name families, D2
perf_gate key coverage, D3 the CLI knob inventory).  Findings print
``path:line RULE [symbol] site: message — fix: hint``.

Accepted sites are suppressed EXPLICITLY in ``GRAFTLINT_BASELINE.json``
(each entry carries a written justification; ``--explain-allowlist``
prints them).  A baseline entry that matches nothing is reported as
stale — the baseline can only shrink or be consciously re-justified.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# Layer 2 traces shard_map programs over a simulated multi-device mesh;
# both knobs must land before jax initializes its backend (same as
# tests/conftest.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--check", action="store_true",
                   help="run every layer against the baseline (the "
                        "pre-merge gate; this is also the default)")
    p.add_argument("--ast-only", action="store_true",
                   help="layer 1 only (no JAX import — runs anywhere)")
    p.add_argument("--jaxpr-only", action="store_true",
                   help="layer 2 only (traces the canonical programs)")
    p.add_argument("--concurrency-only", action="store_true",
                   help="layer 3 C-rules only (thread/Future lifecycle "
                        "+ env-hatch discipline; no JAX import)")
    p.add_argument("--drift-only", action="store_true",
                   help="layer 3 D-rules only (telemetry/perf_gate/knob "
                        "cross-artifact censuses; no JAX import)")
    p.add_argument("--baseline", metavar="PATH", default=None,
                   help="baseline/allowlist file (default: "
                        "GRAFTLINT_BASELINE.json at the repo root)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report raw findings, ignoring every suppression")
    p.add_argument("--explain-allowlist", action="store_true",
                   help="print every baseline entry with its written "
                        "justification, then exit 0")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    args = p.parse_args(argv)

    from lightgbm_tpu.analysis import driver
    from lightgbm_tpu.analysis.findings import Baseline

    baseline_path = args.baseline or driver.default_baseline_path()
    baseline = None
    if not args.no_baseline and os.path.exists(baseline_path):
        try:
            baseline = Baseline.load(baseline_path)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print("graftlint error: bad baseline %s: %s"
                  % (baseline_path, e), file=sys.stderr)
            return 2

    if args.explain_allowlist:
        entries = baseline.entries if baseline else []
        if not entries:
            print("graftlint: baseline is empty — no allowlisted sites")
        for e in entries:
            print("%s %s [%s] %s\n    justification: %s"
                  % (e["rule"], e["path"], e["symbol"],
                     e.get("site", "*"), e["justification"]))
        return 0

    selected = [layer for layer, on in (
        ("ast", args.ast_only), ("jaxpr", args.jaxpr_only),
        ("concurrency", args.concurrency_only),
        ("drift", args.drift_only)) if on]
    layers = tuple(selected) or driver.ALL_LAYERS

    try:
        report = driver.run(layers=layers, baseline=baseline)
    except driver.GraftlintError as e:
        print("graftlint error: %s" % e, file=sys.stderr)
        return 2

    findings = report["findings"]
    stale = report["stale_baseline"]
    if args.json:
        print(json.dumps({
            "findings": [f._asdict() for f in findings],
            "suppressed": [f._asdict() for f in report["suppressed"]],
            "stale_baseline": stale,
        }))
    else:
        for f in findings:
            print(f.format())
        for e in stale:
            print("STALE BASELINE %s %s [%s]: matched nothing — remove "
                  "or re-justify" % (e["rule"], e["path"], e["symbol"]))
        if not findings and not stale:
            print("graftlint: %s layer(s) clean (%d suppression(s) "
                  "applied)" % ("+".join(layers),
                                len(report["suppressed"])))
    return 1 if (findings or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
