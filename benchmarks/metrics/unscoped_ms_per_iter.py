"""``unscoped_ms_per_iter``: busy device time of the traced slice that none
of ``PHASES`` holds, per traced iteration.  One pattern over all of them,
so an operation under two nested scopes is taken once.

``PHASES`` is the yardstick's own copy of the program's closed set of
device scopes (``lightgbm_tpu.telemetry.DEVICE_PHASES``):
``benchmarks/tests/test_phase_metrics.py`` holds the two equal, so a phase
the program adds or renames shows as an edit here and not as a silent fall
of this number."""
from __future__ import annotations

PHASES = ("gradient", "histogram", "split_find", "row_route", "partition",
          "score_update", "tree_pack", "eval")


def read(state):
    if state.summary is None or not state.traced_iterations:
        return None
    scoped = state.summary.scoped_seconds(
        r"(^|/)(%s)(/|$)" % "|".join(PHASES))
    if scoped is None:
        return None
    return ((state.summary.busy_s - scoped) * 1e3
            / state.traced_iterations)
