"""From a profiler trace (``.xplane.pb``) to device seconds.

One piece of code for every cell and every trace-pattern metric: find the
device planes, take the line that holds the device's operations, and from
its events compute

- ``busy_s``: the union of the intervals in which an operation ran,
- ``scoped_seconds(pattern)``: the summed durations of the operations whose
  scope path matches: the ``jax.named_scope`` names the program gives its
  phases, which the compiler keeps in each operation's metadata and the
  profiler writes as the ``tf_op`` stat of the event's *metadata*.
  ``ProfileData`` shows an event's own stats only, so ``wire.py`` reads
  the metadata table from the file's bytes,
- ``top_ops``: the operations that took most time, under the trace's names,
- ``idle_gaps``: the longest gaps between operations, each named by the
  host annotation (``jax.profiler.TraceAnnotation``) that covered its
  middle.

Events inside a ``while`` loop are children of the loop's own event and lie
inside it in time; only leaves of that nesting are summed, so nothing is
counted twice.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return found[-1]


class Profile:
    """A trace file: its events (``jax.profiler.ProfileData``) and, per
    plane, the table from an event's name to its metadata (``wire``)."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        from harness import wire
        self._data = ProfileData.from_file(path)
        self.metadata = wire.event_metadata(path)

    @property
    def planes(self):
        # a fresh walk each time: the binding's iterators are single use
        return self._data.planes


def load(path: str) -> Profile:
    return Profile(path)


class Op:
    __slots__ = ("name", "scope", "start", "end")

    def __init__(self, name, scope, start, end):
        self.name, self.scope, self.start, self.end = name, scope, start, end

    @property
    def seconds(self):
        return (self.end - self.start) * 1e-9


def device_ops(profile) -> dict:
    """{plane name: [Op, ...]} for every device plane, leaf events only."""
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            table = profile.metadata.get(plane.name, {})
            ops = []
            for ev in line.events:
                meta = table.get(ev.name, {})
                ops.append(Op(meta.get("display_name") or ev.name,
                              meta.get("tf_op", ""), ev.start_ns,
                              ev.start_ns + ev.duration_ns))
            out[plane.name] = _leaves(ops)
    return out


def _leaves(ops):
    """Drop events that contain other events (loop and call bodies)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    keep = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        is_parent = (nxt is not None and nxt.start < op.end
                     and nxt.end <= op.end
                     and (nxt.start, nxt.end) != (op.start, op.end))
        if not is_parent:
            keep.append(op)
    return keep


def host_spans(profile, prefix: str = "bench/") -> list:
    """[(name, start_ns, end_ns)] of every host event on the thread that
    carries the harness's own annotations (``bench/...``)."""
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events]
            if any(name.startswith(prefix) for name, _a, _b in spans):
                return sorted(spans, key=lambda x: x[1])
    return []


class TraceSummary:
    """The reduction of one traced window over the chips used."""

    def __init__(self, profile):
        self.planes = device_ops(profile)
        if not self.planes or not any(self.planes.values()):
            raise ValueError("the trace holds no device operation")
        self.host = host_spans(profile)
        spans = [a for a in self.host if a[0] == "bench/traced"]
        if spans:
            self.window = (spans[0][1], spans[-1][2])
        else:
            every = [o for ops in self.planes.values() for o in ops]
            self.window = (min(o.start for o in every),
                           max(o.end for o in every))
        lo, hi = self.window
        self.planes = {k: [o for o in v if o.end > lo and o.start < hi]
                       for k, v in self.planes.items()}

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy_ns(self, ops) -> int:
        lo, hi = self.window
        busy, edge = 0, lo
        for op in sorted(ops, key=lambda o: o.start):
            start, end = max(op.start, edge), min(op.end, hi)
            if end > start:
                busy += end - start
                edge = end
        return busy

    @property
    def busy_s(self) -> float:
        """Mean over the device planes of the union of op intervals."""
        per = [self._busy_ns(ops) for ops in self.planes.values()]
        return sum(per) / len(per) * 1e-9

    def scoped_seconds(self, pattern: str):
        """Summed seconds of operations whose scope path or name matches,
        averaged over the planes; None when nothing matches."""
        rx = re.compile(pattern)
        per, hits = [], 0
        for ops in self.planes.values():
            sel = [o for o in ops if rx.search(o.scope) or rx.search(o.name)]
            hits += len(sel)
            per.append(sum(o.seconds for o in sel))
        return sum(per) / len(per) if hits else None

    def top_ops(self, n: int = 10) -> list:
        total = {}
        for ops in self.planes.values():
            for o in ops:
                total[o.name] = total.get(o.name, 0.0) + o.seconds
        k = len(self.planes)
        return [[name, sec / k] for name, sec in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """Longest idle gaps on the first device plane, named by what the
        host was doing in the middle of each, summed by that name."""
        ops = sorted(next(iter(self.planes.values())),
                     key=lambda o: o.start)
        lo, hi = self.window
        gaps, edge = [], lo
        for op in ops:
            if op.start > edge:
                gaps.append((edge, op.start))
            edge = max(edge, op.end)
        if hi > edge:
            gaps.append((edge, hi))
        named = {}
        for a, b in gaps:
            mid = (a + b) // 2
            inner = [x for x in self.host
                     if x[1] <= mid < x[2] and x[0] != "bench/traced"]
            # the harness's span, then the innermost host event under it
            own = [x for x in inner if x[0].startswith("bench/")]
            name = (min(own, key=lambda x: x[2] - x[1])[0] if own
                    else "bench/between_slices")
            rest = [x for x in inner if not x[0].startswith("bench/")]
            if rest:
                name += ">" + min(rest, key=lambda x: x[2] - x[1])[0]
            named[name] = named.get(name, 0.0) + (b - a) * 1e-9
        return [[k, v] for k, v in
                sorted(named.items(), key=lambda kv: -kv[1])[:n]]
