"""The device time no scope holds, split by the program's own map.

``unscoped_ms_per_iter`` is the busy time of the traced slice under none of
the program's device phases.  The trace cannot say what is in it: an
event's name is the HLO instruction's, and a multi-output fusion, a cloned
fusion and the pieces of a decomposed cumulative sum carry no scope.  The
program can: ``lightgbm_tpu.costmodel.op_phases()`` gives, for every
program it captured, the phase (or ``xla``: what the compiler put in
itself) of each instruction whose own metadata names no phase.  This
module keeps the trace's operations that no pattern of
``metrics/unscoped_ms_per_iter.py``'s ``PHASES`` matches, looks each one's
name up in that map, and sums the seconds by label.  The
``*_hidden_ms_per_iter``, ``xla_inserted`` and ``unnamed`` metrics each
read one group of labels, so together they are ``unscoped_ms_per_iter``
again.

A trace's operation has a name and a scope, no program, so a name that two
captured programs label differently is left out (it counts as ``None``:
no entry).  On a program without the map (the parent of the PR that added
it), with telemetry off, or without a trace, ``split`` returns None and so
does every reader.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")

# the labels each metric sums; ``unnamed`` takes whatever is left: no entry
# in the map (None), a name two programs disagree on, and the phases that
# have no metric of their own here
GROUPS = {
    "histogram_hidden_ms_per_iter": ("histogram", "gradient"),
    "split_find_hidden_ms_per_iter": ("split_find",),
    "partition_hidden_ms_per_iter": ("partition",),
    "row_route_hidden_ms_per_iter": ("row_route",),
    "xla_inserted_ms_per_iter": ("xla",),
}
UNNAMED = "unnamed_ms_per_iter"


def unscoped_pattern():
    """The one pattern of ``unscoped_ms_per_iter``, from its own file."""
    spec = importlib.util.spec_from_file_location(
        "metric_unscoped", os.path.join(METRICS, "unscoped_ms_per_iter.py"))
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    return re.compile(r"(^|/)(%s)(/|$)" % "|".join(metric.PHASES))


def flatten(per_program: dict) -> dict:
    """{instruction name: (label, opcode, result type, result bytes)} over
    all programs; a name whose label differs between two programs is left
    out.  A bare label stands for ``(label, "", "", 0)``."""
    flat, dropped = {}, set()
    for labels in per_program.values():
        for name, value in labels.items():
            value = (value, "", "", 0) if isinstance(value, str) else value
            if flat.setdefault(name, value)[0] != value[0]:
                dropped.add(name)
    for name in dropped:
        del flat[name]
    return flat


def program_map():
    """The flattened map of the running program; None where the program
    has none (no ``op_phases``, nothing captured, no text to be had)."""
    from lightgbm_tpu import costmodel
    op_phases = getattr(costmodel, "op_phases", None)
    per_program = op_phases(describe=True) if op_phases else None
    return flatten(per_program) if per_program else None


def split(state):
    """{label or None: {"ms": ms per traced iteration, "ops": {name: ms}}}
    of the traced slice's unscoped operations, a plane's share averaged as
    ``scoped_seconds`` does; None where there is no trace or no map."""
    if state.summary is None or not state.traced_iterations:
        return None
    if getattr(state, "_hidden", None) is not None:
        return state._hidden
    described = program_map()
    if described is None:
        return None
    scoped = unscoped_pattern()
    planes = state.summary.planes
    scale = 1e3 / (len(planes) * state.traced_iterations)
    out = {}
    for ops in planes.values():
        for op in ops:
            if scoped.search(op.scope) or scoped.search(op.name):
                continue
            label, opcode, result, _bytes = described.get(
                op.name, (None, "", "", 0))
            group = out.setdefault(label, {"ms": 0.0, "ops": {}})
            ms = op.seconds * scale
            group["ms"] += ms
            # the compiler's own under what it is: copy.17 copy s8[2,..]
            what = ("%s %s %s" % (op.name, opcode, result)
                    if label == "xla" else op.name)
            group["ops"][what] = group["ops"].get(what, 0.0) + ms
    state._hidden = out
    note(out)
    return out


def note(out, top: int = 3) -> None:
    """One line on standard error for whoever splits the row by hand: each
    label's milliseconds, its largest operations, and its largest families
    of operations (a name less its number: how many, and their sum)."""
    def largest(pairs):
        return sorted(pairs, key=lambda kv: -kv[-1])[:top]

    lines = {}
    for label, group in out.items():
        families = {}
        for what, ms in group["ops"].items():
            family = families.setdefault(
                re.sub(r"\.\d+\b", "", what), [0, 0.0])
            family[0] += 1
            family[1] += ms
        lines[str(label)] = {
            "ms": group["ms"], "top": largest(group["ops"].items()),
            "families": largest([name, n, ms]
                                for name, (n, ms) in families.items())}
    print("note hidden " + json.dumps(lines), file=sys.stderr)


def read(state, metric: str):
    """Milliseconds per traced iteration of one of the metrics above."""
    found = split(state)
    if found is None:
        return None
    if metric == UNNAMED:
        named = {label for group in GROUPS.values() for label in group}
        return sum(g["ms"] for label, g in found.items()
                   if label not in named)
    return sum(found[label]["ms"] for label in GROUPS[metric]
               if label in found)
