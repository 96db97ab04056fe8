"""What a per-layer metric may read of the program's own registry
(``lightgbm_tpu.telemetry``: host spans under ``phase_times``, counters,
and the compile listener's seconds under ``trace_times``), read when the
metric is read: after the check, whole process.  The traced run alone has
the registry on.  With telemetry off, or on a program without the name
(the parent of the PR that added it), every reader here returns None."""
from __future__ import annotations


def snapshot():
    from lightgbm_tpu import telemetry
    return telemetry.snapshot() if telemetry.enabled() else None


def span_s(name: str):
    """Seconds under the program's host span ``name``."""
    snap = snapshot()
    return None if snap is None else snap["phase_times"].get(name)


def counter(name: str):
    snap = snapshot()
    return None if snap is None else snap["counters"].get(name)


def over(amount, per):
    """``amount / per``, or None where either was not read or ``per`` is 0."""
    return amount / per if amount is not None and per else None


def build_seconds(state, stages):
    """Seconds of the listener's ``stages`` (of ``jaxpr_trace``, ``lower``,
    ``backend_compile``, ``cache_load``), if all of them are set-up's: the
    events name no program, so the process's sum is set-up's only where
    nothing was built in the window (``window_compiles`` 0) nor after it
    (the ``jit/*`` counters read the same now as when the harness read
    them at the window's close).  Else None, as on a program whose
    listener keeps no stage seconds."""
    snap = snapshot()
    if snap is None or state.window_compiles != 0 \
            or "jaxpr_trace" not in snap["trace_times"]:
        return None
    jit = {k: v for k, v in snap["counters"].items() if k.startswith("jit/")}
    then = {k: v for k, v in state.counters.items() if k.startswith("jit/")}
    if jit != then:
        return None
    return sum(snap["trace_times"].get(stage, 0.0) for stage in stages)
