"""LGBM_TPU_* environment hatches: one loud-reject parser, one inventory.

Every A/B and escape hatch this repo grew (Pallas kill switches, the
ingest double-buffer A/B, fault injection, distributed bootstrap) used
to be a bare ``os.environ.get("LGBM_TPU_...", "") == "1"`` at its point
of use — which meant (a) a typo'd VALUE (``LGBM_TPU_INGEST_SYNC=true``)
silently did nothing instead of rejecting, and (b) there was no single
place that could answer "which hatches exist" (the docstrings
hand-enumerated them, drifting).  This module is both fixes:

- :data:`HATCHES` is the generated hatch inventory — one entry per
  environment variable, with its value shape and one-line purpose.
  graftlint C4 (analysis/concurrency_rules.py) fails the pre-merge gate
  on any ``LGBM_TPU_*`` read that bypasses this module, and on any
  helper call naming a hatch missing from the inventory — so the
  inventory can never drift from the code again.
- The typed readers (:func:`flag`, :func:`choice`, :func:`raw`,
  :func:`int_value`, :func:`float_value`) reject malformed values with
  ``log.fatal`` (naming the variable and the accepted shape) instead of
  silently ignoring them, matching the config system's typed-getter
  contract (config.py ``_get_int``/``_get_bool``).

Readers consult the environment per call — the hatches are flipped
mid-process by the A/B harnesses (__graft_entry__ flips NO_PALLAS
between virtual meshes; bench.py flips INGEST_SYNC around the
double-buffer A/B), so nothing here may cache.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

from .utils import log

# The hatch inventory (graftlint C4's census anchor): every LGBM_TPU_*
# variable the package reads, its value shape, and what it does.
HATCHES = {
    "LGBM_TPU_NO_PALLAS":
        ("flag", "disable EVERY Pallas kernel (histogram + partition + "
                 "level-wise row routing) — the mixed-backend escape hatch "
                 "dryrun_multichip sets"),
    "LGBM_TPU_HIST_EINSUM":
        ("flag", "force the XLA einsum histogram formulation for all "
                 "dtypes (A/B timing hatch)"),
    "LGBM_TPU_PARTITION_NO_OVERLAP":
        ("flag", "serialized partition-kernel DMA schedule (A/B against "
                 "the overlapped default; bit-identical)"),
    "LGBM_TPU_NO_MIXEDBIN":
        ("flag", "force the uniform feature layout — mixed-bin packing "
                 "A/B without touching configs"),
    "LGBM_TPU_INGEST_SYNC":
        ("flag", "depth-0 synchronous ingest transfers — the streaming "
                 "double-buffer A/B (bench.py --bench-ingest)"),
    "LGBM_TPU_HOST_BAGGING":
        ("flag", "host-side bagging draw + full-N mask upload — the "
                 "device-bagging A/B; beats the bagging_device config"),
    "LGBM_TPU_PIPELINE":
        ("choice:off|readback", "pipelined-boosting override — beats the "
                                "pipeline= config for A/B timing"),
    "LGBM_TPU_FAULT_AT":
        ("spec", "'<iter>[,<kind>]' one-shot fault injection at an "
                 "iteration boundary (faults.parse_spec loud-rejects)"),
    "LGBM_TPU_FAULT_PROC":
        ("int", "process index the armed fault fires on (default 0)"),
    "LGBM_TPU_FAULT_STALL_S":
        ("float", "stall duration in seconds for the 'stall' fault kind "
                  "(default 1.0)"),
    "LGBM_TPU_COORDINATOR":
        ("str", "jax.distributed coordinator address — presence engages "
                "multi-host bootstrap"),
    "LGBM_TPU_NUM_PROCS":
        ("int", "process count for jax.distributed bootstrap (default 1)"),
    "LGBM_TPU_PROC_ID":
        ("int", "this process's index for jax.distributed bootstrap "
                "(default 0)"),
}


def _require_registered(name: str) -> None:
    if name not in HATCHES:
        log.fatal("env hatch %s is not in the hatches.HATCHES inventory — "
                  "register it (graftlint C4 gates unregistered reads)"
                  % name)


def flag(name: str) -> bool:
    """Boolean hatch: unset/''/'0' -> False, '1' -> True, anything else
    is a loud reject (a typo'd value must never silently do nothing)."""
    _require_registered(name)
    value = os.environ.get(name, "")
    if value in ("", "0"):
        return False
    if value == "1":
        return True
    log.fatal("env hatch %s must be '1' or '0'/unset, got %r"
              % (name, value))


def choice(name: str, allowed: Sequence[str], default: str = "") -> str:
    """Enumerated hatch: unset -> ``default``; any other value must be in
    ``allowed``."""
    _require_registered(name)
    value = os.environ.get(name)
    if value is None or value == "":
        return default
    if value not in allowed:
        log.fatal("env hatch %s must be one of %s, got %r"
                  % (name, "/".join(allowed), value))
    return value


def raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """Free-form hatch (addresses, fault specs) — registration is still
    required; value validation belongs to the consumer's own
    loud-reject parser (e.g. faults.parse_spec)."""
    _require_registered(name)
    return os.environ.get(name, default)


def int_value(name: str, default: int) -> int:
    _require_registered(name)
    value = os.environ.get(name)
    if value is None or value == "":
        return int(default)
    try:
        return int(value)
    except ValueError:
        log.fatal("env hatch %s must be an int, got %r" % (name, value))


def float_value(name: str, default: float) -> float:
    _require_registered(name)
    value = os.environ.get(name)
    if value is None or value == "":
        return float(default)
    try:
        return float(value)
    except ValueError:
        log.fatal("env hatch %s must be a float, got %r" % (name, value))
