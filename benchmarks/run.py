#!/usr/bin/env python3
"""One process, one cell, once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data, found by the names in ``BENCHMARK.json``:
``cells/<cell>.json`` (configuration, traffic kind, parameters, limits),
``configs/<config>.json`` (sizes and the program's ``key=value``
parameters), ``traffic/<kind>.py`` (the generator: set-up, window, check)
and ``metrics/<metric>.json`` (one per-layer metric each).  The last line of
standard output is the result; a run that finds no TPU, or fewer chips than
the cell asks for, prints none and exits 2.  ``--rows`` cuts the table for a
rehearsal (it runs everything and then still refuses to report off the
chip); it is not a cell.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


class Context:
    """What the harness hands a traffic kind: the cell's data, the clock
    marks, the tracer, and the places where a test can break the timed path
    (``fault``) or ask for the control's readings (``control``)."""

    def __init__(self, args, bench, cell, config, fault=None):
        self.args = args
        self.bench = bench
        self.cell = cell
        self.config = config
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rows = args.rows
        self.control = bool(args.control)
        self.fault = fault
        self.marks = [("start", T_START)]
        self.setup_s = None
        self.compiles = 0
        self.window_compiles = None
        self.memory_peak_bytes = None
        self.memory_stats = []
        self.counters = {}
        self.programs = []
        self.trace_dir = None
        self.traced = None          # (start_s, end_s, iterations)
        self.summary = None
        self._listen()

    # ---------------------------------------------------------- clock marks
    def mark(self, name: str) -> None:
        self.marks.append((name, time.perf_counter()))

    def note(self, text: str) -> None:
        """A line for whoever reads a failed run, before the checks."""
        print("note " + text, file=sys.stderr)

    def window_opens(self) -> None:
        self.mark("window")
        self.setup_s = self.marks[-1][1] - T_START
        self.compiles_at_open = self.compiles

    def window_closes(self) -> None:
        self.mark("closed")
        self.window_compiles = self.compiles - self.compiles_at_open

    def _listen(self) -> None:
        from jax import monitoring

        def on_duration(name, _secs, **_kw):
            if name == COMPILE_EVENT:
                self.compiles += 1
        monitoring.register_event_duration_secs_listener(on_duration)

    # --------------------------------------------------------------- tracer
    def trace_slice(self, index: int) -> bool:
        """Start the profiler if slice ``index`` is the one to trace: the
        window's first (the only one sure to run; warm-up is behind it)."""
        if not self.trace or index != 0:
            return False
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench/traced")
        self._annotation.__enter__()
        return True

    def trace_stop(self, start_s, end_s, iterations) -> None:
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.traced = (start_s, end_s, iterations)

    def reduce_trace(self) -> None:
        from harness import trace_reduce
        try:
            self.summary = trace_reduce.TraceSummary(trace_reduce.load(
                trace_reduce.find_xplane(self.trace_dir)))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    # ------------------------------------------------------- device, counts
    def read_memory_peak(self) -> None:
        """Call it when the window has closed and the program's state is
        still alive.  The allocator's peak leaves out what the loaded
        programs reserve for their temporaries, so the peak is the larger
        of the allocator's own and what is in use now (the programs'
        arguments, which are there whenever they run) plus the most that
        was reserved (PERF.md section 4)."""
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peaks = [max(s_["peak_bytes_in_use"],
                     s_["bytes_in_use"] + s_.get("peak_bytes_reserved", 0))
                 for s_ in stats if "peak_bytes_in_use" in s_]
        self.memory_peak_bytes = max(peaks) if peaks else None
        self.memory_stats = stats

    def read_counters(self) -> None:
        """Traced run only: the program's route counters, and what the
        compiler says each of its programs holds."""
        from lightgbm_tpu import costmodel, telemetry
        if telemetry.enabled():
            self.counters = dict(telemetry.snapshot().get("counters", {}))
            self.programs = [
                dict(p["memory"], name=p["name"])
                for p in costmodel.compile_block()["programs"]
                if p.get("memory")]


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def per_layer_metrics(ctx, result, peaks) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell
    (or lists none), read by its own file under ``metrics/``."""
    from harness import readers
    out = {}
    state = readers.State(ctx, result, peaks)
    for spec in ctx.bench["per_layer"]:
        cells = spec.get("workloads")
        if cells is not None and ctx.args.workload not in cells:
            continue
        value = readers.read(spec["name"], state)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
            state.values[spec["name"]] = value
    return out


def judge(readings: dict, limits: dict, whole: bool = True):
    """({name: {value, limit}}, correct): every number beside its limit; a
    number with no limit is a reading only.  ``whole=False`` is for what
    stands in the program's place (the control, a fault): it is held to the
    limits of the numbers it gives, and gives not all of them."""
    checks, correct = {}, True
    for name, limit in limits.items():
        if not whole and name not in readings:
            continue
        value = readings.get(name)
        ok = value is not None and value == value and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    for name, value in readings.items():
        if name not in checks:
            checks[name] = {"value": value, "limit": None}
    return checks, correct


def execute(args, require_chip: bool = True, fault=None):
    """(result or None, exit code).  ``require_chip=False`` is for the
    tests under ``tests/``: the look for a chip is skipped, nothing else."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print("benchmarks: no cell %r in BENCHMARK.json" % args.workload,
              file=sys.stderr)
        return None, 2
    entry = cells[args.workload]
    cell = load_json("cells", args.workload + ".json")
    config = load_json("configs", entry["config"] + ".json")

    # the package first: it places the compile cache before any compile
    import lightgbm_tpu  # noqa: F401
    from lightgbm_tpu.utils import log
    log.set_stream(sys.stderr)
    device = device_info()
    on_chip = (device["platform"] == "tpu"
               and device["count"] >= int(entry["chips"]))
    if require_chip and not on_chip and args.rows is None:
        print("benchmarks: cell %s needs %d TPU chip(s), JAX reports %s"
              % (args.workload, entry["chips"], device), file=sys.stderr)
        return None, 2
    from harness import roofline
    peaks = roofline.peaks_for(device["kind"]) if on_chip else None

    ctx = Context(args, bench, cell, config, fault=fault)
    if ctx.trace:
        # route counters are read in the traced run only: a task=train
        # user's run has telemetry off, and so has the timed run
        from lightgbm_tpu import telemetry
        telemetry.enable(fence=False)
        telemetry.reset()
    traffic = importlib.import_module("traffic." + entry["traffic"])
    result = traffic.run(ctx)

    checks, correct = judge(result["readings"], cell["limits"])
    correct = correct and result["failed"] == 0
    in_its_place = {}
    for who, readings in result.get("in_its_place", {}).items():
        theirs, in_its_place[who] = judge(readings, cell["limits"],
                                          whole=False)
        checks.update({who + "." + n: c for n, c in theirs.items()})
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    breakdown = None
    if ctx.trace:
        correct = correct and ctx.traced is not None
        if ctx.traced is not None:
            ctx.reduce_trace()
            device["busy_s"] = ctx.summary.busy_s
            device["window_s"] = ctx.summary.window_s
            breakdown = {"device_ops": ctx.summary.top_ops(10),
                         "idle_gaps": ctx.summary.idle_gaps(10)}
        metrics = per_layer_metrics(ctx, result, peaks) if peaks else {}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        values = dict(result["end_to_end"], setup_s=ctx.setup_s)
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in values.items()}
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if ctx.control:
        # the same comparison, of what stood in the program's place: each
        # has to come out false
        line["control_correct"] = in_its_place.pop("control", False)
        line["faults_correct"] = {who.replace("fault_", "", 1): ok
                                  for who, ok in in_its_place.items()}
    line["harness"] = {
        "workload": args.workload, "seed": ctx.seed,
        "seconds": ctx.seconds, "window_s": result["window_s"],
        "slices_s": result["slices"],
        "window_compiles": ctx.window_compiles,
        "setup_marks_s": {n: t - T_START for n, t in ctx.marks[1:]},
        "check_s": time.perf_counter() - ctx.marks[-1][1],
        "memory_stats": [{k: s_.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "bytes_limit")}
            for s_ in ctx.memory_stats],
        "programs": ctx.programs,
        "counters": {k: v for k, v in sorted(ctx.counters.items())
                     if k.split("/")[0] in ("hist", "partition", "costmodel",
                                            "jit")},
    }
    line["checks"] = checks
    for name, c in checks.items():
        print("check %-36s value=%r limit=%r" % (name, c["value"],
                                                 c["limit"]),
              file=sys.stderr)
    for key in ("control_correct", "faults_correct"):
        if key in line:
            print("%s=%s" % (key, line[key]), file=sys.stderr)
    print("correct=%s" % line["correct"], file=sys.stderr, flush=True)
    if require_chip and not on_chip:
        print("benchmarks: rehearsal off the chip (%s): no result line"
              % device, file=sys.stderr)
        return line, 2
    return line, 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal only: cut the table to this many rows")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the control (the reference in the next "
                         "lower precision) and two faults in the program's "
                         "place and judge each by the cell's limits; the "
                         "driver's runs never do")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = json.load(open(os.path.join(
            ROOT, "BENCHMARK.json")))["run_seconds"]
    return args


def main(argv=None) -> int:
    args = parse(argv)
    line, code = execute(args)
    if code == 0:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
