"""Per-layer metrics, each read by a file of its own.

``metrics/<name>.json`` names one of the reductions below (``reduce``) and
what it needs (``match``: a pattern over the scope paths and names of the
trace's device operations; ``of``: another metric).  A metric whose
reduction is none of these brings ``metrics/<name>.py`` with a
``read(state)`` of its own instead.  A reader that finds nothing to read
returns None and the metric is left out of the line: never 0 for a share.
"""
from __future__ import annotations

import importlib.util
import json
import os

from harness import roofline

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


class State:
    """What a reader may read: the trace's reduction, the traced span, the
    harness's counters, the peaks and the metrics read before it."""

    def __init__(self, ctx, result, peaks):
        self.summary = ctx.summary
        self.traced = ctx.traced            # (start_s, end_s, iterations)
        self.window_compiles = ctx.window_compiles
        self.counters = ctx.counters
        self.shape = result["shape"]
        self.peaks = peaks
        self.values = {}

    @property
    def traced_iterations(self):
        return self.traced[2] if self.traced else None

    def least_seconds(self) -> float:
        work = roofline.iteration_work(self.shape["rows"],
                                       self.shape["features"],
                                       self.shape["num_leaves"])
        return roofline.least_seconds(work, self.peaks)["seconds"]


def trace_ms_per_iter(spec, state):
    if state.summary is None or not state.traced_iterations:
        return None
    seconds = state.summary.scoped_seconds(spec["match"])
    if not seconds:
        return None
    return seconds * 1e3 / state.traced_iterations


def trace_idle_pct(_spec, state):
    if state.summary is None or state.summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - state.summary.busy_s / state.summary.window_s)


def roofline_pct_of(spec, state):
    """Least time for one iteration's work over the measured device time of
    the kernel that does it (``of``: a ms-per-iteration metric)."""
    base = state.values.get(spec["of"])
    if not base:
        return None
    return 100.0 * state.least_seconds() / (base * 1e-3)


def step_mfu_pct(_spec, state):
    """The same least time over the host-clock seconds per iteration of the
    traced slice: the whole step, idle time included."""
    if not state.traced or not state.traced_iterations:
        return None
    per_iter = (state.traced[1] - state.traced[0]) / state.traced_iterations
    return 100.0 * state.least_seconds() / per_iter if per_iter > 0 else None


def window_compiles(_spec, state):
    return state.window_compiles


def counter(spec, state):
    return state.counters.get(spec["counter"])


REDUCTIONS = {f.__name__: f for f in (
    trace_ms_per_iter, trace_idle_pct, roofline_pct_of, step_mfu_pct,
    window_compiles, counter)}


def read(name: str, state: State):
    py = os.path.join(METRICS, name + ".py")
    if os.path.exists(py):
        mod_spec = importlib.util.spec_from_file_location(
            "metric_" + name.replace(".", "_").replace("-", "_"), py)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read(state)
    with open(os.path.join(METRICS, name + ".json")) as fh:
        spec = json.load(fh)
    return REDUCTIONS[spec["reduce"]](spec, state)
