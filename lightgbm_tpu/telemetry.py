"""Process-wide telemetry: phase timers, kernel-route counters, JSONL sink.

The repo's previous observability was three ad-hoc hacks: ``time.time()``
prints in cli.py, hist-stubbed A/B differencing (PROFILE.md; the script
went in PR 27, replaced by the device-trace reduction under benchmarks/),
and hand-assembled counter tables in BENCH rounds.  This module
replaces them with one registry, designed around two JAX realities:

1. **Route decisions are trace-time events.**  Kernel routing (Pallas int8 /
   bf16 / f32 hit, XLA einsum fallback, ``LGBM_TPU_NO_PALLAS`` trips,
   partition-kernel eligibility — ops/histogram.py, ops/compact.py) happens
   while a program is being *traced*; the compiled program then replays the
   chosen route forever.  Counters therefore increment once per traced
   decision — exactly the record of "which route did this program actually
   bake in" that the mixed-backend hardening episodes (commit e7ff0d9)
   lacked.  Compiles are counted via one ``jax.monitoring`` listener:
   ``jit/backend_compile`` (a persistent-cache hit fires none, so the
   count is true compiles), ``jit/persistent_cache_hit`` / ``_miss``, and
   under ``trace_times`` the seconds of each stage of building a program:
   ``jaxpr_trace``, ``lower``, ``backend_compile``, ``cache_load``.

2. **Spans are host-side wall timers, and nothing else.**
   ``span("histogram")`` times the enclosed *host* call with
   ``time.perf_counter`` and puts nothing into a traced program.  A span
   entered while JAX is tracing is recorded under ``trace_times`` (it
   measured tracing, not execution); a span entered with concrete arrays
   (the boosting loop's host phases, set-up's ``dataset_bin`` /
   ``booster_init``, or any op under ``jax.disable_jit()``) is recorded
   under ``phase_times``.  Spans nest: a layer's self time is its span
   less the spans entered inside it (``dataset_bin`` less ``find_bins``
   and ``binarize``).  The optional **fence mode** (``set_fence(True)`` /
   ``enable(fence=True)``) calls ``jax.block_until_ready`` on a value the
   caller hands to ``Span.fence(x)`` before stopping the timer, so async
   dispatch does not attribute device time to the wrong phase.  Fencing
   only *waits* on already-dispatched work — it never issues device
   computation — so it cannot trip the environment's ~60 s per-dispatch
   execution watchdog (BASELINE.md).

One program, traced or not: every public entry checks one module flag and
returns a no-op singleton when disabled, and a span is a HOST object only —
a timer plus a ``jax.profiler.TraceAnnotation`` on the profiler's clock.  A
span never enters ``jax.named_scope``: the ops sites open their spans
inside an unconditional scope of the same name, so a span that entered one
would make a program traced with telemetry on carry ``histogram/histogram``
where the timed run's carries ``histogram`` — another HLO text and, through
the locations serialized into the Pallas kernels, another persistent-cache
key (PERF.md: a second 35 s compile for every traced run).  Names that must
reach the device trace are unconditional ``jax.named_scope``s at the site,
drawn from the closed set ``DEVICE_PHASES`` below.  So enabling/disabling
telemetry perturbs neither numerics, nor the lowered text (debug info
included), nor the compile-cache key (tests/test_trace_scopes.py and
tests/test_telemetry.py lock this in).

JSONL sink: ``enable(jsonl_path)`` (the ``metrics_out=...`` config/CLI
option) arms a per-iteration record stream; the boosting loop emits one
line per iteration::

    {"iter": 3, "phase_times": {...}, "trace_times": {...},
     "counters": {...}, "eval_metrics": {...}}

``phase_times`` are seconds spent per phase *in that iteration* (chunked
training amortizes the fused k-iteration program evenly across its kept
iterations and marks ``"amortized_over": k``); ``counters`` are cumulative.
The canonical phase keys ``histogram``, ``split_find``, ``partition``,
``eval`` are always present.  In multi-process runs only process 0 opens
the sink (decided lazily at first write, after jax.distributed init);
``parallel.learners.aggregate_telemetry`` folds every host's counters into
the leader before the final summary record.  Library users who want the
data without a file call ``snapshot()``.

ISSUE 2 additions — the device-side observability triad:

3. **Memory gauges** (``set_memory(True)`` / ``enable(memory=True)``, the
   ``memory_stats=`` config option): spans additionally sample the device
   allocator (``device.memory_stats()``; host-RSS fallback on backends
   that return None, e.g. CPU) at their boundaries, recording per-phase
   byte deltas and a process-peak ``bytes_in_use`` watermark.  Iteration
   records gain a ``memory`` block (``take_memory_record``), the summary
   and ``snapshot()`` a cumulative one, and ``set_residency`` files the
   one-shot dataset-residency report (bin matrix / metadata / histogram
   scratch) at train start.  Sampling is a host-side stats read — it
   never dispatches device work.

4. **Profiler alignment**: every span body runs under
   ``jax.profiler.TraceAnnotation(name)``, so a Perfetto trace captured
   via ``profile_dir=`` (or the benchmark's ``--trace 1``) carries the
   span on the HOST timeline, on the profiler's own clock, beside the
   device rows.  The DEVICE rows get their phase names from the
   program's unconditional ``jax.named_scope``s (``DEVICE_PHASES``),
   which the compiler keeps in each operation's metadata whether or not
   telemetry is armed: the host span ``histogram`` and the device scope
   ``histogram`` share a name because the site gives both, not because
   the span writes into the program.  Health events (NaN counts,
   saturation, divergence — lightgbm_tpu/health.py) ride the iteration
   records as a ``health`` block via ``emit_iteration``.

ISSUE 4 — roofline attribution and compile observability
(lightgbm_tpu/costmodel.py rides this registry's lifecycle):

5. **Roofline + compile blocks**: enable()/disable()/reset() arm the
   compiled-program cost registry alongside the spans, so the summary
   record and ``snapshot()`` carry a ``roofline`` block (per-phase static
   flops/bytes from ``compiled.cost_analysis()`` joined to the measured
   spans → attained FLOP/s, HBM GB/s, fraction-of-peak) and a ``compile``
   block (program inventory, cold compile seconds, persistent-cache
   hits, mid-run recompiles).  ``emit_iteration`` watches the
   backend-compile counter: a compile AFTER the first iteration record
   is a mid-run recompile — counted (``jit/midrun_recompile``) and
   warned once, because it means a program cache key failed to capture
   something that changed.

ISSUE 5 — distributed observability (per-collective wire metrics,
cross-host span shards, hung-collective flight recorder):

6. **Collective sites** (``collective_span`` / ``record_collective``):
   the parallel learners' collective seams (psum / psum_scatter /
   SplitInfo allgather — parallel/learners.py, and the growers' own
   in-program collectives) are wrapped so every TRACED collective files
   a site record: collective kind, mesh axis, logical payload bytes
   (from the traced shapes/dtypes) and an executed-calls estimate
   (traced occurrences x the caller-supplied loop factor — fori_loop
   bodies trace once but execute per split).  The wrapper calls the
   underlying collective unchanged, so the traced program — and
   therefore scores — are bit-identical with the layer on or off.  The
   summary/``snapshot()`` gain an ``interconnect`` block joining each
   site's estimated bytes to its phase's measured (fenced) span time →
   attained GB/s per collective site, beside the PR 4 HBM roofline.

7. **Per-process span shards** (``timeline=`` config option): with
   timeline mode on, EVERY process opens its own JSONL shard
   (``<metrics_out>.shard-<i>of<n>.jsonl``; atomic deterministic
   naming, line-buffered + per-record flush so a killed process leaves
   at worst one truncated FINAL line) headed by a ``shard`` record
   (host fingerprint, pid, process index, and the clock-offset
   handshake parallel/mesh.clock_handshake records at setup).
   Iteration/summary records gain a local wall-clock ``t``;
   scripts/timeline_report.py merges shards into one job timeline and
   computes per-phase cross-host skew.

8. **Hung-collective flight recorder** (``stall_timeout=`` config
   option): a ring buffer of the last N span/collective/iteration
   events plus a host-side watchdog thread armed around training
   (gbdt.run_training).  If no event lands for ``stall_timeout``
   seconds the watchdog dumps the ring buffer, the in-flight
   phase/iteration/collective and every thread's stack to the sink —
   BEFORE the environment's opaque ~60 s dispatch watchdog kills the
   job with no record of what was in flight.  The clock is injectable
   (tests stall without real waits); the thread only ever reads state
   and writes the dump, never touching device APIs.

ISSUE 8 — streaming ingestion (io/streaming.py):

9. **Ingest spans + the ``ingest/*`` counter family**: a streamed
   dataset load runs under an ``ingest`` span with sub-spans
   ``ingest_count`` (pass-0 raw row count), ``ingest_pass1``
   (label/side-column collection + pinned-index binning sample),
   ``ingest_bin`` (per-chunk parse + quantize) and ``ingest_h2d``
   (final transfer drain).  Counters: ``ingest/chunks`` and
   ``ingest/rows`` (pass-2 progress), ``ingest/h2d_bytes`` (host→device
   payload), ``ingest/h2d_wait_us`` (host time actually BLOCKED on
   transfers) and ``ingest/overlap_hidden_us`` (upper-bound estimate of
   wire time hidden behind host parse/bin work — the double buffer's
   win; ``LGBM_TPU_INGEST_SYNC=1`` forces depth-0 transfers for the
   bench A/B) and ``ingest/worker_wait_us`` (parallel-parse pool time
   the coordinator spent blocked on the bounded in-flight window —
   io/parallel_ingest.py, ISSUE 18).  Routes:
   ``ingest/double_buffer_on|off``.  Device-side
   sampling rides the same registry: ``bagging/device`` vs
   ``bagging/host`` routes (ops/sampling.py draws vs the legacy host
   RNG + full-N upload) and the ``goss/iterations`` counter under a
   ``goss`` span.  scripts/telemetry_report.py renders the family with
   derived H2D GB/s.

ISSUE 14 — preemption-safe elastic training (checkpoint.py, elastic.py):

10. **Checkpoint counters (``ckpt/*``)**: ``ckpt/snapshots`` (raw
    snapshots enqueued at iteration boundaries), ``ckpt/written``
    (atomic files landed — async AND sync), ``ckpt/dropped`` (a pending
    snapshot replaced by a newer one before the writer thread got to it
    — latest-wins backpressure, never a training stall),
    ``ckpt/async_write_us`` (cumulative writer-thread serialize+write
    time, all OFF the hot loop), ``ckpt/pruned`` (old files removed
    past ``checkpoint_keep``), ``ckpt/restored`` (restores executed).

11. **Elastic span + wire sites**: the per-iteration cross-host time
    exchange and the mesh-shrink survivor agreement run under an
    ``elastic`` span and file the ``elastic/times_allgather``
    (all_gather of per-host iteration seconds over the ``data`` axis)
    and ``elastic/survivor_pmin`` (elementwise keep/drop vote minimum)
    collective sites — both censused by graftlint J2
    (analysis/programs.elastic_programs).  ``elastic/shrinks`` counts
    executed drain-at-boundary mesh shrinks.

ISSUE 16 — flight recorder + per-request latency attribution
(lightgbm_tpu/tracing.py rides this registry's lifecycle):

12. **The ``trace/*`` family contract**: the flight recorder mirrors
    exactly two counters into this registry — ``trace/dropped`` (ring
    events overwritten before being read; ANY nonzero at the default
    ``trace_ring_events`` is an absolute perf_gate finding) and
    ``trace/dumps`` (JSONL dump files written: clean close, watchdog
    and fault/crash paths alike).  The dump writer runs under the
    ``trace_dump`` span.  Everything else the recorder knows —
    per-request component attribution (queue/linger/coalesce/dispatch/
    walk/scatter, summing EXACTLY to each request's wall time), the
    event ring, and the fixed-memory log-bucket percentile sketches per
    latency family (``serve_wall_us``, ``serve_<component>_us``,
    ``train_iter_us``) — stays in tracing.py and reaches records as the
    summary's ``trace`` block (``tracing.snapshot()``) and the
    ``trace_dump_dir=`` JSONL dumps (``scripts/trace_report.py``).
    ``disable()`` disarms the recorder (dumping first when configured);
    ``emit_iteration`` files one ``train_iter`` ring event per
    iteration sharing the timeline-shard record keys.

ISSUE 27 — every millisecond of an iteration and every second of set-up
under a name the program gives it (PERF.md section 3 has the table of
which benchmark metric reads which):

13. **Device phases** (``DEVICE_PHASES`` / ``phase_scope``): the closed
    set of unconditional scopes described above.  **Host turn**:
    ``device_wait`` (the host blocked on the dispatched program; entered
    only with telemetry on, where the readback would block anyway),
    ``model_readback`` (the copy alone) and ``tree_build`` (host tree
    construction), with the counters ``train/iterations``,
    ``train/chunks`` and ``train/readback_bytes`` counted where the
    trees are consumed.  **Set-up**: ``dataset_bin`` (opened once per
    loader phase; its own time is the row sample) ⊇ ``find_bins`` (cut
    points, in ``find_bins_for_matrix`` alone) + ``binarize`` (one
    ``searchsorted`` per column), in the loaders' shared internals,
    with ``bin/values`` and ``bin/sample_rows``; ``booster_init`` ⊇ ``h2d`` (the bin table's
    placement, waited for) with ``init/h2d_bytes``; and the compile
    listener's ``trace_times`` keys ``jaxpr_trace`` (nested traces
    counted once), ``lower``, ``backend_compile``, ``cache_load`` with
    ``jit/persistent_cache_hit`` / ``_miss``.  Every one of these
    names has a reader: a per-layer metric of ``BENCHMARK.json``
    (``benchmarks/metrics/``) and a row of README's "Telemetry" table.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import traceback
from typing import Dict, List, Optional

from . import lifecycle

# Canonical per-iteration phase keys — always present in iteration records
# (ISSUE 1 acceptance schema), whether or not the phase ran this iteration.
CANONICAL_PHASES = ("histogram", "split_find", "partition", "eval")

# --------------------------------------------------------------------------
# Telemetry name inventory (ISSUE 15) — THE machine-checked family
# documentation, regenerated from the graftlint D1 census
# (analysis/drift_rules.collect_telemetry_usage; ``python
# scripts/graftlint.py --drift-only`` reports any drift).  The prose
# docstring above explains each family's semantics; THESE tuples are the
# name contract: a counter/span/wire-site the code emits but this
# inventory omits — or an entry here no code emits — fails the pre-merge
# gate.  Entries ending in ``*`` are prefix families whose suffix is
# computed at runtime (bucket sizes, kernel widths, per-host keys).

COUNTER_FAMILIES = (
    "allhosts/*",                 # cross-host sums (aggregate_telemetry)
    "bagging/device",
    "bagging/host",
    "bin/sample_rows",            # rows sampled for the cut points
    "bin/values",                 # rows x used columns quantized
    "ckpt/async_write_us",
    "ckpt/dropped",
    "ckpt/pruned",
    "ckpt/restored",
    "ckpt/snapshots",
    "ckpt/written",
    "costmodel/aot_call_fallback",
    "costmodel/capture_failed",
    "elastic/shrinks",
    "goss/iterations",
    "health/*",                   # per-anomaly-kind counters (health.py)
    "health/anomalous_iterations",
    "hist/accum_ranges",          # int32 accumulation ranges, summed over the int passes
    "hist/env_force_einsum",
    "hist/env_no_pallas",
    "hist/mixedbin_blocked",
    "hist/mixedbin_leafbatch",
    "hist/mixedbin_matmul",
    "hist/mixedbin_off",
    "hist/mixedbin_on",
    "hist/mixedbin_pallas_float",
    "hist/mixedbin_pallas_int",
    "hist/mixedbin_xla_int",
    "hist/pallas_*",              # per-dtype kernel hits
    "hist/pallas_eligible",
    "hist/pallas_fblocks",        # feature blocks of the kernel's grid, summed over Pallas passes
    "hist/pallas_held_onehot",    # int passes contracted with the one-hot held, the value rows streamed
    "hist/pallas_ineligible",
    "hist/pallas_int8",
    "hist/pallas_kernel_*",       # per-width kernel-class hits
    "hist/xla_einsum",
    "hist/xla_int8",
    "hist/xla_int_kernel",
    "hist/xla_matmul",
    "ingest/bin_us",
    "ingest/chunks",
    "ingest/double_buffer_off",
    "ingest/double_buffer_on",
    "ingest/h2d_bytes",
    "ingest/h2d_us",
    "ingest/h2d_wait_us",
    "ingest/overlap_hidden_us",
    "ingest/parse_us",
    "ingest/rows",
    "ingest/worker_wait_us",
    "init/h2d_bytes",             # bin table placed by GBDT.init
    "jit/backend_compile",
    "jit/midrun_recompile",
    "jit/persistent_cache_hit",
    "jit/persistent_cache_miss",
    "learner/fp_*",               # feature-parallel ownership routes
    "monitor/drift_scores",
    "monitor/slo_breaches",
    "monitor/windows",
    "partition/dma_overlap",
    "partition/dma_serial",
    "partition/env_no_pallas",
    "partition/in_pane",          # kernels that read and write the pane
    "partition/pallas",
    "partition/pallas_eligible",
    "partition/pallas_ineligible",
    "partition/pallas_rblocks",   # row blocks of the kernels' grids
    "partition/route_pallas",     # level-wise row routing, once a level
    "partition/route_xla",
    "partition/xla",
    "serve/bucket_*",             # per-ladder-bucket dispatch counts
    "serve/coalesced_batches",
    "serve/coalesced_requests",
    "serve/coalesced_rows",
    "serve/ensemble_flatten",
    "serve/front_requests",
    "serve/front_rows",
    "serve/linger_wait_us",
    "serve/pad_rows",
    "serve/predict_calls",
    "serve/queue_depth_rows",
    "serve/queue_depth_samples",
    "serve/queue_peak_rows",
    "serve/rows",
    "serve/swap_drain_us",
    "serve/swaps",
    "serve/warmups",
    "trace/dropped",
    "trace/dumps",
    "train/chunks",               # fused chunks consumed
    "train/iterations",           # iterations whose trees were consumed
    "train/readback_bytes",       # device -> host bytes of the model readback
)

SPAN_FAMILIES = (
    "bagging",
    "binarize",
    "booster_init",
    "dataset_bin",
    "device_wait",
    "elastic",
    "eval",
    "find_bins",
    "goss",
    "gradient",
    "grow",
    "h2d",
    "histogram",
    "ingest",
    "ingest_bin",
    "ingest_count",
    "ingest_h2d",
    "ingest_pass1",
    "model_readback",
    "partition",
    "predict",
    "predict_encode",
    "predict_warmup",
    "score_update",
    "split_find",
    "trace_dump",
    "train_chunk",
    "tree_build",
    "valid_update",
)

# The closed set of DEVICE phase names: every unconditional
# ``jax.named_scope`` that the fused iteration (models/gbdt.make_chunk_body),
# the per-iteration path and the three grow policies put around device
# work is one of these, so a device trace splits an iteration into these
# rows plus a remainder that is a number (the benchmark's
# ``unscoped_ms_per_iter``), not a guess.  ``level<d>``, ``leafwise_split``
# and ``leafcompact_split`` are OUTER grouping scopes and the objectives'
# ``gradient_<objective>`` nest inside ``gradient``; ``range_sum`` nests
# inside ``histogram`` (ops/hist_pallas.py: the int8 accumulation ranges
# of a table past 16.9M rows added as an integer pair).  XLA gives a fusion
# the metadata of its root operation, so a boundary between two phases is
# exact only where the compiler did not fuse across it.
DEVICE_PHASES = (
    "gradient",       # objective gradients, GOSS selection
    "histogram",      # histogram passes, sibling subtraction, interleave
    "split_find",     # threshold scan, candidate bookkeeping
    "row_route",      # row -> slot / leaf update of the masked growers
    "partition",      # the compacted grower's stream partition
    "score_update",   # leaf lookup + add, valid-score replay
    "tree_pack",      # leaf values, TreeArrays assembly, chunk stacking
    "eval",           # in-program metrics and the health vector
)


def phase_scope(name: str):
    """The unconditional ``jax.named_scope`` of one device phase: the one
    way a phase name reaches the device trace.  Not gated on the enabled
    flag (a scope costs nothing at run time and must not differ between
    the timed and the traced program); a name outside ``DEVICE_PHASES``
    is a programming error."""
    if name not in DEVICE_PHASES:
        raise ValueError("%r is not one of DEVICE_PHASES" % (name,))
    import jax
    return jax.named_scope(name)

WIRE_SITE_FAMILIES = (
    "dp/grad_score_allgather",
    "elastic/survivor_pmin",
    "elastic/times_allgather",
    "health/quant_sat_reduce",
    "health/score_pmax",
    "health/vector_psum",
    "hist/int8_pallas_psum",
    "hist/int8_segsum_psum",
    "hist/int8_xla_psum",
    "hist/quant_scale_pmax",
    "leafcompact/tier_pmax",
    "serve/tree_carry",
    "serve/tree_psum",
)

# Wire sites whose full names are built at RUNTIME (variable site labels
# threaded through the learners' seam wrappers) — documented here, exempt
# from the stale-doc half of the D1 census the static AST pass cannot
# decide.  The J2 census and tests/test_graftlint.EXPECTED_SITES pin the
# concrete (2,2)-mesh instances.
DYNAMIC_WIRE_SITES = (
    "dp_psum/*",                  # pure-DP psum schedule seams
    "dp_rs/*",                    # DP reduce_scatter ownership seams
    "dp/goss_score_allgather",    # fused-chunk GOSS score gather
    "hybrid/*",                   # 2-D mesh owned-block seams
    "voting/*",                   # PV-tree voted-exchange seams
    "fp/*",                       # feature-parallel ownership seams
    "leafwise/*",                 # schedule-policy seam wrap (grower)
    "depthwise/*",
    "leafcompact/*",
)

_enabled = False
_fence = False
_sink_path: Optional[str] = None
_sink_file = None
_sink_error = False

_counters: Dict[str, int] = {}
_phase_times: Dict[str, float] = {}
_phase_counts: Dict[str, int] = {}
_trace_times: Dict[str, float] = {}
# span re-entrancy stack (host-side, single-threaded boosting loop): a span
# whose name is already active is suppressed so recursive helpers
# (histogram_leafbatch's width-grouped self-calls, build_histogram →
# leafbatch) don't double-count wall time under one name
_span_stack: List[str] = []
# marks for per-iteration deltas
_mark_phase: Dict[str, float] = {}
_mark_trace: Dict[str, float] = {}
# last outcome per host-evaluated routing rule (count_route dedup)
_route_state: Dict[str, str] = {}

# memory gauges (ISSUE 2): armed separately from the base registry so hot
# spans pay the allocator-stats read only when asked for
_memory = False
_mem_device = None            # cached jax device handle
_mem_source: Optional[str] = None
_mem_peak = 0                 # this run's bytes_in_use watermark
# the allocator's LIFETIME peak at the first post-reset sample: the device
# stat is monotonic since allocator creation, so a fresh run must baseline
# it or it would report the previous run's (possibly much larger) peak
_mem_dev_peak_base: Optional[int] = None
_mem_phase_delta: Dict[str, int] = {}   # cumulative per-phase byte deltas
_mem_phase_peak: Dict[str, int] = {}    # per-phase bytes_in_use watermark
_mark_mem: Dict[str, int] = {}          # per-iteration delta marks
_residency: Optional[dict] = None       # one-shot dataset-residency report
_allhosts_mem_peak: Optional[int] = None

_compile_listener_installed = False

# mid-run recompile watch (ISSUE 4): backend-compile count at the first
# iteration record; growth past it after that is a mid-run recompile
_compile_base: "Optional[int]" = None
_midrun_warned = False

# ---- distributed observability state (ISSUE 5) ----
# collective-site registry: site -> {kind, axis, bytes_per_call,
# traced_calls, loop, phase} (record_collective)
_collectives: Dict[str, dict] = {}
# timeline mode: per-process JSONL shards + wall-clock "t" on records
_timeline = False
_shard_path_used: Optional[str] = None
# clock-offset handshake result (parallel/mesh.clock_handshake): seconds
# to ADD to this host's time.time() to land on the leader's clock
_clock_offset = 0.0
_clock_rtt: Optional[float] = None
# flight recorder: ring buffer of recent events + stall watchdog thread
_RING_CAP = 256
_ring: "collections.deque" = collections.deque(maxlen=_RING_CAP)
_ring_armed = False           # cheap hot-path gate (timeline or watchdog)
_wd_timeout_cfg = 0.0         # configure_watchdog (config stall_timeout=)
_wd_thread: Optional[threading.Thread] = None
_wd_stop: Optional[threading.Event] = None
_wd_clock = time.monotonic
_wd_timeout = 0.0
_wd_last = 0.0
_wd_context: Dict[str, object] = {}
_wd_dump: Optional[dict] = None   # last flight-recorder dump (tests)


# --------------------------------------------------------------- life cycle

def enabled() -> bool:
    return _enabled


def enable(jsonl_path: Optional[str] = None, fence: bool = False,
           memory: Optional[bool] = None,
           timeline: Optional[bool] = None) -> None:
    """Arm the registry (and optionally a JSONL sink at ``jsonl_path``).

    Idempotent; a second call can attach a sink or toggle fence mode.  The
    sink file is opened lazily at first record — after jax.distributed
    initialization — so only process 0 writes in multi-process runs,
    UNLESS timeline mode is on, in which case every process writes its
    own shard (``<path>.shard-<i>of<n>.jsonl``).  ``memory`` arms/disarms
    the span-boundary memory gauges, ``timeline`` the per-process shard
    mode (None leaves the current mode unchanged).
    """
    global _enabled, _fence, _sink_path, _sink_error, _sink_file, _memory
    _enabled = True
    _fence = bool(fence)
    if memory is not None:
        _memory = bool(memory)
    if timeline is not None:
        set_timeline(timeline)
    if jsonl_path:
        if _sink_file is not None and jsonl_path != _sink_path:
            # re-targeting an open sink: close the old handle or records
            # would keep landing in the previous file
            try:
                _sink_file.close()
            except OSError:
                pass
            _sink_file = None
        _sink_path = jsonl_path
        _sink_error = False
    _install_compile_listener()
    try:
        from . import costmodel
        costmodel.enable()
    except Exception:
        pass


def disable() -> None:
    """Stop recording and close the sink (pending data is flushed).
    Also disarms the stall watchdog, leaves timeline mode and disarms
    the flight recorder (tracing.py — which dumps its ring first when a
    dump dir is configured) — the registry returns to its process-global
    resting state."""
    global _enabled, _fence, _sink_file, _sink_path, _memory
    global _timeline, _shard_path_used, _wd_timeout_cfg
    disarm_watchdog()
    try:
        # flush the live monitor FIRST: its tail window files
        # monitor_window / slo_breach events into the trace ring, so
        # they must land before the recorder's close dump below
        from . import monitor
        monitor.disarm()
    except Exception:
        pass
    try:
        from . import tracing
        # stamp the session's per-site wire byte model into the ring
        # before the close dump: podtrace's seam roofline joins measured
        # collective_sync spans against exactly this model, and a dump
        # that carries it is self-contained on crash-forensics hosts
        snap = interconnect_snapshot()
        if snap and tracing.active():
            tracing.event("wire_model", sites={
                s: {"est_bytes": rec.get("est_bytes", 0),
                    "bytes_per_call": rec.get("bytes_per_call", 0),
                    "est_calls": rec.get("est_calls", 0),
                    "kind": rec.get("kind"), "axis": rec.get("axis")}
                for s, rec in snap.get("sites", {}).items()})
        tracing.disarm()
    except Exception:
        pass
    _timeline = False
    _shard_path_used = None
    _wd_timeout_cfg = 0.0
    set_shard_identity(None)
    _update_ring_armed()
    _enabled = False
    _fence = False
    _memory = False
    if _sink_file is not None:
        try:
            _sink_file.close()
        except OSError:
            pass
    _sink_file = None
    _sink_path = None
    try:
        from . import costmodel
        costmodel.disable()
    except Exception:
        pass


def reset() -> None:
    """Zero all counters/timers/gauges (sink and enabled state are
    untouched)."""
    global _mem_peak, _residency, _allhosts_mem_peak, _mem_dev_peak_base
    global _compile_base, _midrun_warned
    _compile_base = None
    _midrun_warned = False
    try:
        from . import costmodel
        costmodel.reset()
    except Exception:
        pass
    _counters.clear()
    _phase_times.clear()
    _phase_counts.clear()
    _trace_times.clear()
    _mark_phase.clear()
    _mark_trace.clear()
    _route_state.clear()
    _mem_phase_delta.clear()
    _mem_phase_peak.clear()
    _mark_mem.clear()
    _mem_peak = 0
    _mem_dev_peak_base = None    # re-baselined at the next sample
    _residency = None
    _allhosts_mem_peak = None
    _collectives.clear()
    _ring.clear()
    del _span_stack[:]
    del _open_traces[:]


def set_fence(on: bool) -> None:
    global _fence
    _fence = bool(on)


def fence_enabled() -> bool:
    return _fence


def set_memory(on: bool) -> None:
    """Arm/disarm the span-boundary memory gauges."""
    global _memory
    _memory = bool(on)


def memory_enabled() -> bool:
    return _memory


def sink_active() -> bool:
    """True when iteration records have somewhere to go (a sink path is
    configured) — the boosting loop's cheap guard around record assembly."""
    return _enabled and _sink_path is not None


def sink_open() -> bool:
    """True when a sink is configured or a file handle is still open —
    the test-suite leak guard's check (tests/conftest.py)."""
    return _sink_file is not None or (_enabled and _sink_path is not None)


# ---------------------------------------------------------- memory sampling

def _mem_sample() -> int:
    """Current memory footprint in bytes, updating the process watermark.

    Prefers the device allocator (``device.memory_stats()["bytes_in_use"]``
    — real HBM occupancy on TPU/GPU, including its own peak watermark);
    backends that return None (CPU) fall back to the process RSS from
    /proc/self/statm, so CPU runs still carry a meaningful gauge.  A pure
    stats read: never allocates or dispatches device work."""
    global _mem_device, _mem_source, _mem_peak, _mem_dev_peak_base
    try:
        if _mem_device is None:
            import jax
            _mem_device = jax.local_devices()[0]
        ms = _mem_device.memory_stats()
        if ms and "bytes_in_use" in ms:
            b = int(ms["bytes_in_use"])
            # the allocator's peak stat is monotonic over the PROCESS: only
            # growth past the post-reset baseline belongs to this run (it
            # catches transient spikes between our samples); a larger
            # previous run's peak must not leak into this run's watermark
            dev_peak = int(ms.get("peak_bytes_in_use", 0))
            if _mem_dev_peak_base is None:
                _mem_dev_peak_base = dev_peak
            if dev_peak > _mem_dev_peak_base:
                _mem_peak = max(_mem_peak, dev_peak)
            _mem_peak = max(_mem_peak, b)
            _mem_source = "device"
            return b
    except Exception:
        pass
    try:
        with open("/proc/self/statm") as f:
            b = int(f.read().split()[1]) * (os.sysconf("SC_PAGE_SIZE")
                                            if hasattr(os, "sysconf")
                                            else 4096)
        _mem_peak = max(_mem_peak, b)
        _mem_source = "host_rss"
        return b
    except Exception:
        if _mem_source is None:
            _mem_source = "unavailable"
        return 0


def take_memory_record() -> Optional[dict]:
    """Per-iteration ``memory`` block: current and peak bytes plus the
    per-phase byte deltas accumulated since the previous call (re-marks,
    mirroring take_phase_deltas).  None while memory gauges are off."""
    if not _memory:
        return None
    b = _mem_sample()
    deltas = {k: v - _mark_mem.get(k, 0)
              for k, v in _mem_phase_delta.items()
              if v - _mark_mem.get(k, 0) != 0}
    _mark_mem.clear()
    _mark_mem.update(_mem_phase_delta)
    rec = {"bytes_in_use": int(b), "peak_bytes_in_use": int(_mem_peak),
           "source": _mem_source or "unavailable"}
    if deltas:
        rec["phase_delta_bytes"] = {k: int(v)
                                    for k, v in sorted(deltas.items())}
    return rec


def memory_snapshot() -> Optional[dict]:
    """Cumulative memory block (summary record / ``snapshot()``): peak
    watermark, cumulative per-phase deltas and per-phase peaks, the
    dataset-residency report, and the cross-host peak when aggregated."""
    if not (_memory or _mem_phase_delta or _residency is not None):
        return None
    out = {"bytes_in_use": int(_mem_sample()) if _memory else 0,
           "peak_bytes_in_use": int(_mem_peak),
           "source": _mem_source or "unavailable"}
    if _mem_phase_delta:
        out["phase_delta_bytes"] = {k: int(v) for k, v
                                    in sorted(_mem_phase_delta.items())}
        out["phase_peak_bytes"] = {k: int(v) for k, v
                                   in sorted(_mem_phase_peak.items())}
    if _residency is not None:
        out["residency"] = _residency
    if _allhosts_mem_peak is not None:
        out["allhosts_peak_bytes_in_use"] = int(_allhosts_mem_peak)
    return out


def mem_peak_bytes() -> int:
    return int(_mem_peak)


def merge_host_memory(peak: int) -> None:
    """Install the cross-host peak-bytes maximum (parallel.learners.
    aggregate_telemetry) on this process."""
    global _allhosts_mem_peak
    _allhosts_mem_peak = int(peak)


def set_residency(report: dict) -> None:
    """File the one-shot dataset-residency report (bin matrix / metadata /
    histogram scratch footprint, computed at train start by gbdt.init): it
    rides ``memory_snapshot()`` and is written to the sink immediately as
    a standalone ``{"residency": ...}`` record."""
    global _residency
    _residency = dict(report)
    if sink_active():
        write_record({"residency": _residency})


# ----------------------------------------------------- collective sites

def _tree_nbytes(args) -> int:
    """Logical payload bytes of a collective's operands, from the traced
    shapes/dtypes (tracers carry .size/.dtype like concrete arrays)."""
    total = 0
    try:
        import jax
        for leaf in jax.tree.leaves(args):
            size = getattr(leaf, "size", None)
            dt = getattr(leaf, "dtype", None)
            if size is not None and dt is not None:
                total += int(size) * int(getattr(dt, "itemsize", 4))
    except Exception:
        pass
    return total


def record_collective(site: str, kind: str, axis: Optional[str],
                      nbytes: int, loop: int = 1,
                      phase: Optional[str] = None) -> None:
    """File one traced collective occurrence at ``site``.

    Collectives are trace-time events like the kernel-route counters: the
    compiled program replays the traced collective forever, so one record
    per trace occurrence IS the inventory of what the program moves.
    ``loop`` is the caller's executed-calls-per-trace estimate (a seam
    invoked inside a fori_loop body traces once but runs once per split);
    ``phase`` names the telemetry span whose measured time prices this
    site's wire seconds in the ``interconnect`` block."""
    if not _enabled:
        return
    if phase is None and _span_stack:
        # default attribution: the OUTERMOST active span is the host-side
        # phase the compiled program executes under ("grow"/"train_chunk")
        # — inner spans at trace time are trace-time spans
        phase = _span_stack[0]
    rec = _collectives.get(site)
    if rec is None:
        rec = _collectives[site] = {
            "kind": kind, "axis": axis, "bytes_per_call": int(nbytes),
            "traced_calls": 0, "loop": max(int(loop), 1), "phase": phase}
    rec["traced_calls"] += 1
    # shapes can differ between traces (re-trace at a new shape): keep the
    # largest payload as the representative per-call cost
    rec["bytes_per_call"] = max(rec["bytes_per_call"], int(nbytes))
    if _ring_armed:
        _ring_event("collective", site)


def collective_span(site: str, fn, *, kind: str, axis: Optional[str] = None,
                    loop: int = 1, phase: Optional[str] = None):
    """Wrap a collective seam callable so each TRACED invocation files a
    site record (kind, mesh axis, payload bytes from the traced avals).

    The wrapper calls ``fn`` unchanged — nothing is inserted into the
    traced program, so enabling/disabling the layer perturbs neither
    numerics nor jit caching.  ``None`` passes through (optional seams);
    an already-wrapped fn is returned as-is (the first wrap, closest to
    the collective, keeps the most precise kind/loop metadata)."""
    if fn is None:
        return None
    if getattr(fn, "_tl_collective_site", None) is not None:
        return fn

    def wrapped(*args, **kwargs):
        record_collective(site, kind, axis, _tree_nbytes((args, kwargs)),
                          loop=loop, phase=phase)
        return fn(*args, **kwargs)

    wrapped._tl_collective_site = site
    return wrapped


def collectives() -> Dict[str, dict]:
    return {k: dict(v) for k, v in _collectives.items()}


def interconnect_snapshot() -> Optional[dict]:
    """The ``interconnect`` block: per-site estimated bytes moved joined
    to the owning phase's measured span seconds → attained GB/s per
    collective site and per phase.  Estimates: executed calls =
    traced_calls x loop x the phase's span count (the cached program
    replays its collectives on every execution); byte counts are the
    LOGICAL payload (shapes x dtypes) — on-wire bytes depend on the
    collective algorithm (a psum moves ~2x(S-1)/S of the payload per
    hop).  None while no collective site was traced."""
    if not _collectives:
        return None
    sites = {}
    phase_bytes: Dict[str, int] = {}
    for site, rec in sorted(_collectives.items()):
        phase = rec.get("phase")
        # collectives are recorded once per TRACE, but the cached program
        # replays them on every execution of its phase span — scale by
        # the phase's span count so the bytes (and therefore the attained
        # rate against the phase's ACCUMULATED seconds) cover the whole
        # run, mirroring costmodel's per-execution call counter.  A
        # re-trace (new shapes) double-counts both traced_calls and one
        # execution — an estimate, as documented in the block's note.
        execs = max(_phase_counts.get(phase, 1), 1) if phase else 1
        est_calls = rec["traced_calls"] * rec["loop"] * execs
        est_bytes = rec["bytes_per_call"] * est_calls
        entry = {
            "kind": rec["kind"], "axis": rec["axis"],
            "bytes_per_call": int(rec["bytes_per_call"]),
            "traced_calls": int(rec["traced_calls"]),
            "phase_executions": int(execs),
            "est_calls": int(est_calls),
            "est_bytes": int(est_bytes),
        }
        if phase:
            entry["phase"] = phase
            phase_bytes[phase] = phase_bytes.get(phase, 0) + est_bytes
            secs = _phase_times.get(phase, 0.0)
            if secs > 0:
                entry["attained_gb_per_s"] = round(est_bytes / secs / 1e9, 6)
        sites[site] = entry
    phases = {}
    for phase, nbytes in sorted(phase_bytes.items()):
        secs = _phase_times.get(phase, 0.0)
        phases[phase] = {
            "est_bytes": int(nbytes),
            "span_seconds": round(secs, 6),
            "attained_gb_per_s": (round(nbytes / secs / 1e9, 6)
                                  if secs > 0 else None),
        }
    return {"sites": sites, "phases": phases, "fenced_spans": _fence,
            "note": "logical payload bytes; est_calls = traced x loop x "
                    "phase executions"}


# ------------------------------------------------- timeline / clock offset

def set_timeline(on: bool) -> None:
    """Arm/disarm per-process shard mode (the ``timeline=`` option).
    Takes effect at the next sink open; an already-open sink keeps its
    target (retarget via enable(jsonl_path=...))."""
    global _timeline
    _timeline = bool(on)
    _update_ring_armed()


def timeline_enabled() -> bool:
    return _timeline


def set_clock_offset(offset_s: float, rtt_s: Optional[float] = None) -> None:
    """Install the leader-relative clock offset measured by
    parallel/mesh.clock_handshake: seconds to ADD to this host's
    time.time() to land on the leader's clock (recorded in the shard
    header; scripts/timeline_report.py applies it when merging)."""
    global _clock_offset, _clock_rtt
    _clock_offset = float(offset_s)
    _clock_rtt = None if rtt_s is None else float(rtt_s)


def clock_offset() -> float:
    return _clock_offset


_shard_identity: "Optional[tuple[int, int]]" = None


def set_shard_identity(index: Optional[int] = None,
                       count: Optional[int] = None) -> None:
    """Override the (process_index, process_count) shard identity —
    dryrun_multichip and tests use it to exercise the multi-shard merge
    path from a single process (simulated hosts).  ``None`` resets to
    the real jax.process_index()/count()."""
    global _shard_identity
    _shard_identity = (None if index is None or count is None
                       else (int(index), int(count)))
    # keep the flight recorder's pod identity in lockstep — dumps and
    # timeline shards must agree on who "p<i>" is (podtrace merge key)
    try:
        from . import tracing
        if _shard_identity is None:
            tracing.set_identity(process_index=None, process_count=None)
        else:
            tracing.set_identity(process_index=_shard_identity[0],
                                 process_count=_shard_identity[1])
    except Exception:
        pass


def _shard_suffix() -> "tuple[int, int]":
    if _shard_identity is not None:
        return _shard_identity
    try:
        import jax
        return jax.process_index(), jax.process_count()
    except Exception:
        return 0, 1


def shard_path(base: str, index: int, count: int) -> str:
    """Deterministic per-process shard name: each process owns exactly
    one file for the run (no appends to another process's half-written
    shard), and scripts/timeline_report.py can glob
    ``<base>.shard-*.jsonl``."""
    return "%s.shard-%05dof%05d.jsonl" % (base, index, count)


def sink_path() -> Optional[str]:
    """The path records actually land in (the shard path in timeline
    mode) — test/report helper."""
    return _shard_path_used if _timeline else _sink_path


# ------------------------------------------ flight recorder + stall watchdog

def _update_ring_armed() -> None:
    global _ring_armed
    _ring_armed = _timeline or _wd_thread is not None


def _ring_event(kind: str, name: str) -> None:
    """Append one event to the flight-recorder ring (and feed the stall
    watchdog's progress clock).  Hot-path cost: one deque append."""
    global _wd_last
    _ring.append((time.time(), kind, name,
                  _wd_context.get("iteration")))
    if _wd_thread is not None:
        _wd_last = _wd_clock()


def configure_watchdog(timeout_s: float) -> None:
    """Store the ``stall_timeout=`` setting; gbdt.run_training arms the
    watchdog around training when this is > 0."""
    global _wd_timeout_cfg
    _wd_timeout_cfg = max(float(timeout_s), 0.0)


def watchdog_configured() -> float:
    return _wd_timeout_cfg


def watchdog_checkin(phase: Optional[str] = None,
                     iteration: Optional[int] = None,
                     detail: Optional[str] = None) -> None:
    """Mark forward progress (and the in-flight context the dump will
    name).  Called by the boosting loop at phase boundaries; span
    enter/exit events check in implicitly via the ring."""
    global _wd_last
    if phase is not None:
        _wd_context["phase"] = phase
    if iteration is not None:
        _wd_context["iteration"] = int(iteration)
    if detail is not None:
        _wd_context["detail"] = detail
    if _wd_thread is not None:
        _wd_last = _wd_clock()


def arm_watchdog(timeout_s: Optional[float] = None, clock=None,
                 poll_s: float = 0.05) -> bool:
    """Start the stall-watchdog thread (idempotent).  ``clock`` is
    injectable — tests drive a fake clock and never wait out a real
    stall.  The thread polls a monotonic clock and, once no ring
    event/checkin lands for ``timeout_s``, writes a flight-recorder
    dump to the sink (the opaque runtime watchdog is expected to kill a
    truly hung job shortly after; the dump is the record it never
    leaves).  If progress RESUMES after a dump — e.g. the stall was a
    long backend compile, which blocks the host with no events — the
    watchdog re-arms, up to ``_WD_MAX_DUMPS`` dumps per arming."""
    global _wd_thread, _wd_stop, _wd_clock, _wd_timeout, _wd_last, _wd_dump
    timeout = _wd_timeout_cfg if timeout_s is None else float(timeout_s)
    if timeout <= 0 or _wd_thread is not None:
        return False
    _wd_clock = clock or time.monotonic
    _wd_timeout = timeout
    _wd_last = _wd_clock()
    _wd_dump = None
    _wd_stop = threading.Event()
    _wd_thread = threading.Thread(
        target=_wd_run, args=(_wd_stop, poll_s), name="lgbm-tpu-watchdog",
        daemon=True)
    # shared live-object inventory (ISSUE 15): the guard and graftlint C1
    # see the watchdog like every other thread-owning subsystem
    lifecycle.track("watchdog", _wd_thread, disarm_watchdog)
    _wd_thread.start()
    _update_ring_armed()
    return True


def disarm_watchdog(join_s: float = 2.0) -> None:
    global _wd_thread, _wd_stop
    t, ev = _wd_thread, _wd_stop
    _wd_thread, _wd_stop = None, None
    _update_ring_armed()
    if ev is not None:
        ev.set()
    if t is not None:
        if t.is_alive():
            t.join(join_s)
        if not t.is_alive():
            lifecycle.untrack(t)


def watchdog_active() -> bool:
    """True while the watchdog thread is running (tests/conftest.py leak
    guard)."""
    return _wd_thread is not None and _wd_thread.is_alive()


def last_flight_record() -> Optional[dict]:
    return _wd_dump


# a long backend compile blocks the host with no Python events and can
# fire a spurious dump; the watchdog therefore RE-ARMS when progress
# resumes (capped, so a genuinely hung run can't spam the sink) instead
# of retiring on its first dump — a later real hang still gets recorded
_WD_MAX_DUMPS = 3


def _wd_run(stop: "threading.Event", poll_s: float) -> None:
    dumps = 0
    dumped_at: Optional[float] = None   # _wd_last value at the last dump
    while not stop.is_set():
        stop.wait(poll_s)
        try:
            if dumped_at is not None:
                if _wd_last > dumped_at:
                    dumped_at = None    # progress resumed: re-arm
                else:
                    continue
            if _wd_clock() - _wd_last >= _wd_timeout > 0:
                _flight_dump(_wd_clock() - _wd_last, dumps + 1)
                dumps += 1
                dumped_at = _wd_last
                if dumps >= _WD_MAX_DUMPS:
                    return
        except Exception:  # pragma: no cover - never kill the host loop
            return


def _flight_dump(stalled_s: float, dump_index: int = 1) -> None:
    """Assemble and write the flight-recorder dump: in-flight
    phase/iteration/collective, the event ring, and every thread's
    stack.  Pure host-side state reads — never touches device APIs (the
    device is exactly what's presumed hung)."""
    global _wd_dump
    import sys
    events = [{"t": round(t, 6), "kind": k, "name": n,
               "iter": it} for (t, k, n, it) in list(_ring)]
    in_flight_phase = (_span_stack[-1] if _span_stack
                       else _wd_context.get("phase"))
    last_coll = next((e["name"] for e in reversed(events)
                      if e["kind"] == "collective"), None)
    threads = {}
    try:
        names = {t.ident: t.name for t in threading.enumerate()}
        for tid, frame in sys._current_frames().items():
            name = names.get(tid, str(tid))
            if name == "lgbm-tpu-watchdog":
                continue
            threads[name] = [ln.rstrip() for ln in
                             traceback.format_stack(frame)[-8:]]
    except Exception:
        pass
    dump = {
        "flight_recorder": {
            "dump_index": int(dump_index),
            "stalled_for_s": round(float(stalled_s), 3),
            "stall_timeout_s": _wd_timeout,
            "phase": in_flight_phase,
            "iteration": _wd_context.get("iteration"),
            "detail": _wd_context.get("detail"),
            "last_collective": last_coll,
            "open_spans": list(_span_stack),
            "ring": events[-_RING_CAP:],
            "threads": threads,
        }
    }
    _wd_dump = dump
    try:
        from .utils import log
        log.warning(
            "telemetry watchdog: no progress for %.1fs (stall_timeout=%.1fs)"
            " — in-flight phase=%s iter=%s collective=%s; flight-recorder "
            "dump written"
            % (stalled_s, _wd_timeout, in_flight_phase,
               _wd_context.get("iteration"), last_coll))
    except Exception:
        pass
    try:
        write_record(dump)
    except Exception:
        pass


# ------------------------------------------------------------------- spans

class _NullSpan:
    """No-op span returned while telemetry is disabled (or re-entrant)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def fence(self, value):
        return value


_NULL_SPAN = _NullSpan()


def _tracing() -> bool:
    # private in jax 0.9 (jax.core no longer re-exports it).  No except:
    # answering "not tracing" by default made every instrumented program
    # called under a trace compile standalone and fall back (first chip
    # run of PR 24: costmodel/aot_call_fallback = 20)
    from jax._src.core import trace_state_clean
    return not trace_state_clean()


class Span:
    """Context-managed phase timer.  ``fence(x)`` hands the span a value to
    ``jax.block_until_ready`` at exit when fence mode is on (execution-time
    spans only; trace-time spans never block).

    Profiler alignment: the span body runs under
    ``jax.profiler.TraceAnnotation(name)`` (a host-timeline trace event
    on the profiler's clock), so ``profile_dir=`` traces line up with the
    JSONL phase keys.  The span never enters ``jax.named_scope``: what a
    traced program carries must not depend on whether telemetry is armed
    (module docstring); device rows are named by the sites' unconditional
    scopes (``DEVICE_PHASES``).  With memory gauges armed, the span also
    samples the allocator at its boundaries (per-phase byte delta +
    watermark)."""
    __slots__ = ("name", "_t0", "_fence_val", "_is_trace", "_ann", "_mem0")

    def __init__(self, name: str):
        self.name = name
        self._fence_val = None
        self._is_trace = False
        self._t0 = 0.0
        self._ann = None
        self._mem0 = None

    def __enter__(self):
        self._is_trace = _tracing()
        try:
            import jax
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None
        if _memory and not self._is_trace:
            self._mem0 = _mem_sample()
        _span_stack.append(self.name)
        if _ring_armed:
            _ring_event("span_enter", self.name)
        self._t0 = time.perf_counter()
        return self

    def fence(self, value):
        self._fence_val = value
        return value

    def __exit__(self, exc_type, exc, tb):
        if (_fence and not self._is_trace and exc_type is None
                and self._fence_val is not None):
            try:
                import jax
                jax.block_until_ready(self._fence_val)
            except Exception:
                pass
        dt = time.perf_counter() - self._t0
        self._fence_val = None
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
            self._ann = None
        if self._mem0 is not None:
            b1 = _mem_sample()
            _mem_phase_delta[self.name] = (
                _mem_phase_delta.get(self.name, 0) + (b1 - self._mem0))
            _mem_phase_peak[self.name] = max(
                _mem_phase_peak.get(self.name, 0), b1, self._mem0)
            self._mem0 = None
        if _span_stack and _span_stack[-1] == self.name:
            _span_stack.pop()
        if _ring_armed:
            _ring_event("span_exit", self.name)
        if self._is_trace:
            _trace_times[self.name] = _trace_times.get(self.name, 0.0) + dt
        else:
            _phase_times[self.name] = _phase_times.get(self.name, 0.0) + dt
            _phase_counts[self.name] = _phase_counts.get(self.name, 0) + 1
        return False


def span(name: str):
    """Phase timer: ``with telemetry.span("histogram") as sp: ...``.

    Returns a shared no-op when telemetry is disabled or a span of the same
    name is already open (re-entrant helper calls)."""
    if not _enabled or name in _span_stack:
        return _NULL_SPAN
    return Span(name)


# ----------------------------------------------------------------- counters
#
# Mixed-bin packing counters (ISSUE 6): the histogram routing layer files
# ``hist/mixedbin_*`` trace-time counters (``_leafbatch`` = a packed
# leaf-batched dispatch; ``_pallas_int``/``_pallas_float``/``_xla_int``/
# ``_matmul`` = which kernel route ran the per-class passes) and
# gbdt.init records the layout decision once per booster via
# ``count_route("hist_layout", "hist/mixedbin_on"|"hist/mixedbin_off")``
# — the runtime answer to "did this run actually pack, and on which
# kernels".  The BLOCK-LOCAL layout (ISSUE 12, hybrid/voting ownership
# meshes) additionally files ``hist/mixedbin_blocked`` once per booster,
# and in-chunk GOSS bumps ``goss/iterations`` by the chunk length at
# dispatch (the same counter the per-iteration path bumps per draw) —
# the fused DP selection's score allgather records on the
# ``dp/goss_score_allgather`` wire-metrics site.  Pipelined boosting deliberately adds NO counters: it changes
# host wait order only, and the phase spans (model_readback migrating off
# the critical path) are the observable.
#
# Serving counters (ISSUE 7, lightgbm_tpu/serving.py):
# ``serve/ensemble_flatten`` = once per FlatEnsemble build (the
# encode-once contract: predict_file must read 1 for the whole file);
# ``serve/predict_calls`` / ``serve/rows`` / ``serve/pad_rows`` = engine
# call volume and the pad overhead the bucket ladder costs;
# ``serve/bucket_<B>`` = which compiled batch shape served each call.
# The engine's device programs are costmodel-instrumented under phase
# "predict" (span of the same name wraps the device walk;
# "predict_encode" times the host rank-encode), so the roofline and
# compile blocks attribute serving alongside training.
#
# Distributed elastic serving (ISSUE 13) extends the family:
# ``serve/front_requests`` / ``serve/front_rows`` = ServingFront intake;
# ``serve/coalesced_batches`` / ``serve/coalesced_rows`` /
# ``serve/coalesced_requests`` = the cross-request batching outcome (the
# coalesced batch SIZE histogram is the engine's existing
# ``serve/bucket_<B>`` counters — each coalesced batch lands on exactly
# one ladder bucket); ``serve/linger_wait_us`` = cumulative
# first-arrival→dispatch wait (mean = /coalesced_batches);
# ``serve/queue_depth_rows`` + ``serve/queue_depth_samples`` = queue
# depth sampled at each batch formation (mean = rows/samples) with
# ``serve/queue_peak_rows`` filed once at front close; ``serve/swaps`` /
# ``serve/swap_drain_us`` = hot-swap count and drain-and-flip latency;
# ``serve/warmups`` = double-buffered engine warmups (the compile the
# swap keeps OUT of the request path).  The tree-sharded engine's
# cross-shard exchange files wire-metrics sites ``serve/tree_carry``
# (the [C, N] carry-chain ppermute hops, shards-1 per trace) and
# ``serve/tree_psum`` (the final masked broadcast psum), so the
# interconnect block prices tree_psum wire bytes per phase beside the
# training seams — and graftlint J2's census covers the same two sites.

def count(name: str, n: int = 1) -> None:
    """Bump a monotonic counter (kernel-route decisions, env-var trips,
    recompiles).  No-op while disabled."""
    if _enabled:
        _counters[name] = _counters.get(name, 0) + n


def count_route(group: str, name: str) -> None:
    """Record a routing-decision OUTCOME for a rule that host code
    re-evaluates every call (e.g. ops/compact.pallas_partition_ok, once
    per tree): counts once per outcome change within ``group``, so the
    counter reads as decisions, not evaluations — matching the trace-time
    counters' per-decision magnitude."""
    if not _enabled:
        return
    if _route_state.get(group) != name:
        _route_state[group] = name
        count(name)


def counters() -> Dict[str, int]:
    return dict(_counters)


def merge_host_counters(totals: Dict[str, int]) -> None:
    """Install cross-host counter sums (parallel.learners.
    aggregate_telemetry) under ``allhosts/`` keys on this process."""
    for k, v in totals.items():
        _counters["allhosts/" + k] = int(v)


# jax.monitoring event -> ``trace_times`` key: the stages of building one
# program.  ``backend_compile`` is kept for true compiles only — jax fires
# its duration event around the persistent-cache lookup as well, so a load
# from the cache would otherwise count as a compile; a load's seconds go
# under ``cache_load`` (the four keys are disjoint and sum to what the
# process spent building programs)
_BUILD_SECONDS = {
    "jaxpr_to_mlir_module_duration": "lower",
    "cache_retrieval_time_sec": "cache_load",
}
# True between a persistent-cache hit and the backend-compile duration
# event that closes the same lookup
_cache_load_pending = False
# jaxpr traces nest (an inner jit traced inside an outer one fires first
# and lies inside the outer's interval): (start, seconds) of the traces
# not yet found inside another, so each second is counted once
_open_traces: List[tuple] = []


def _on_jaxpr_trace(dur: float) -> None:
    end = time.perf_counter()
    start = end - dur
    inner = 0.0
    while _open_traces and _open_traces[-1][0] >= start:
        inner += _open_traces.pop()[1]
    _open_traces.append((start, dur))
    del _open_traces[:-256]
    _trace_times["jaxpr_trace"] = (
        _trace_times.get("jaxpr_trace", 0.0) + dur - inner)


def _install_compile_listener() -> None:
    """The one ``jax.monitoring`` listener: counts true compiles
    (``jit/backend_compile``: executables the backend built, not those the
    persistent cache served), cache hits and misses, and keeps under
    ``trace_times`` the seconds of each stage of building a program
    (``jaxpr_trace``, ``lower``, ``backend_compile``, ``cache_load``).
    Only the whole process is seen: the events name no program.
    Registered once; increments are gated on the enabled flag
    (jax.monitoring has no unregister)."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    try:
        from jax import monitoring

        def _on_duration(name: str, dur: float, **kw) -> None:
            global _cache_load_pending
            if not _enabled:
                return
            name = name.rsplit("/", 1)[-1]
            if name == "backend_compile_duration":
                if _cache_load_pending:
                    # the lookup hit: its seconds are under cache_load
                    _cache_load_pending = False
                    return
                _counters["jit/backend_compile"] = (
                    _counters.get("jit/backend_compile", 0) + 1)
                _trace_times["backend_compile"] = (
                    _trace_times.get("backend_compile", 0.0) + dur)
                return
            if name == "jaxpr_trace_duration":
                _on_jaxpr_trace(dur)
            elif name in _BUILD_SECONDS:
                key = _BUILD_SECONDS[name]
                _trace_times[key] = _trace_times.get(key, 0.0) + dur

        monitoring.register_event_duration_secs_listener(_on_duration)
        # the duration listener is registered: mark installed NOW —
        # jax.monitoring has no unregister, so a failure in the second
        # (optional) registration below must not cause a later enable()
        # to stack a duplicate _on_duration listener
        _compile_listener_installed = True

        def _on_event(name: str, **kw) -> None:
            # the persistent compilation cache (ISSUE 4): jax records
            # '/jax/compilation_cache/cache_hits' once per executable
            # served from the on-disk cache and '.../cache_misses' once
            # per executable compiled and written to it — together with
            # jit/backend_compile this decomposes "programs built" into
            # paid compiles and cache-served
            global _cache_load_pending
            if not _enabled:
                return
            if name.endswith("/cache_hits"):
                _cache_load_pending = True
                _counters["jit/persistent_cache_hit"] = (
                    _counters.get("jit/persistent_cache_hit", 0) + 1)
            elif name.endswith("/cache_misses"):
                _counters["jit/persistent_cache_miss"] = (
                    _counters.get("jit/persistent_cache_miss", 0) + 1)

        try:
            monitoring.register_event_listener(_on_event)
        except Exception:
            pass
    except Exception:
        pass


def _watch_midrun_recompiles() -> None:
    """Called at each iteration record: backend compiles AFTER the first
    record mean a chunk/grower program cache key missed something that
    changed mid-run (the exact failure mode the PR-3 cache-key hardening
    fixed) — count them and warn once."""
    global _compile_base, _midrun_warned
    n = _counters.get("jit/backend_compile", 0)
    if _compile_base is None:
        _compile_base = n
        return
    if n > _compile_base:
        _counters["jit/midrun_recompile"] = (
            _counters.get("jit/midrun_recompile", 0) + (n - _compile_base))
        _compile_base = n
        if not _midrun_warned:
            _midrun_warned = True
            from .utils import log
            log.warning(
                "telemetry: %d backend compile(s) happened after the first "
                "iteration record (mid-run recompile) — a program cache "
                "key may not capture everything that changed"
                % _counters["jit/midrun_recompile"])


# ---------------------------------------------------------------- snapshots

def snapshot() -> dict:
    """Cumulative registry state for library users (no sink required)."""
    out = {
        "phase_times": dict(_phase_times),
        "phase_counts": dict(_phase_counts),
        "trace_times": dict(_trace_times),
        "counters": dict(_counters),
    }
    mem = memory_snapshot()
    if mem is not None:
        out["memory"] = mem
    ic = interconnect_snapshot()
    if ic is not None:
        out["interconnect"] = ic
    _attach_cost_blocks(out)
    return out


def _attach_cost_blocks(record: dict) -> None:
    """Add the ``roofline`` and ``compile`` blocks (costmodel registry
    joined to the cumulative phase spans) to a summary-shaped record.
    Absent entirely while the cost registry has nothing — disabled-mode
    snapshots stay empty — and never raises (reporting must not crash
    training)."""
    try:
        from . import costmodel
        if costmodel.active():
            record["roofline"] = costmodel.roofline(dict(_phase_times),
                                                    fenced=_fence)
            record["compile"] = costmodel.compile_block()
    except Exception:
        pass


def take_phase_deltas() -> "tuple[Dict[str, float], Dict[str, float]]":
    """(phase_times, trace_times) accumulated since the previous call, and
    re-mark.  The boosting loop calls this once per iteration (or once per
    fused chunk) to scope the per-record timings."""
    dp = {k: v - _mark_phase.get(k, 0.0) for k, v in _phase_times.items()
          if v - _mark_phase.get(k, 0.0) > 0.0}
    dt = {k: v - _mark_trace.get(k, 0.0) for k, v in _trace_times.items()
          if v - _mark_trace.get(k, 0.0) > 0.0}
    _mark_phase.clear()
    _mark_phase.update(_phase_times)
    _mark_trace.clear()
    _mark_trace.update(_trace_times)
    return dp, dt


# -------------------------------------------------------------------- sink

def _ensure_sink():
    """Open the sink on first write.  Deferred so jax.process_index() is
    consulted AFTER distributed init: only the leader writes — unless
    timeline mode is on, in which case EVERY process opens its own shard
    (deterministic per-process name; line-buffered, so a killed process
    leaves at worst one truncated final line) and writes a ``shard``
    header record first."""
    global _sink_file, _sink_error, _shard_path_used
    if _sink_file is not None or _sink_path is None or _sink_error:
        return _sink_file
    path = _sink_path
    header = None
    if _timeline:
        idx, count = _shard_suffix()
        path = _shard_path_used = shard_path(_sink_path, idx, count)
        header = _shard_header(idx, count)
    else:
        try:
            import jax
            if jax.process_count() > 1 and jax.process_index() != 0:
                _sink_error = True   # non-leader: never write
                return None
        except Exception:
            pass
    try:
        # line-buffered: each record reaches the OS at its newline, so a
        # crashed peer's shard is readable up to its last whole record
        _sink_file = open(path, "w", buffering=1)
    except OSError:
        from .utils import log
        log.warning("telemetry: cannot open metrics_out=%s; sink disabled"
                    % path)
        _sink_error = True
        return None
    if header is not None:
        try:
            _sink_file.write(json.dumps(header) + "\n")
            _sink_file.flush()
        except OSError:
            pass
    return _sink_file


def _shard_header(idx: int, count: int) -> dict:
    """The shard's self-describing first record: which host/process wrote
    it, and the clock offset that maps its local ``t`` stamps onto the
    leader's clock."""
    import socket
    info = {
        "process_index": int(idx),
        "process_count": int(count),
        "pid": os.getpid(),
        "clock_offset_s": round(_clock_offset, 6),
        "started_unix": round(time.time(), 6),
    }
    if _clock_rtt is not None:
        info["clock_rtt_s"] = round(_clock_rtt, 6)
    try:
        info["host"] = socket.gethostname()
    except Exception:
        info["host"] = "unknown"
    try:
        from . import costmodel
        info["fingerprint"] = costmodel.host_fingerprint()
    except Exception:
        pass
    return {"shard": info}


def _round_times(d: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v, 6) for k, v in sorted(d.items())}


def write_record(record: dict) -> None:
    """Append one raw JSON line to the sink (no-op without a sink).

    Telemetry must never crash training: an I/O failure (disk full, stale
    mount) disables the sink with a warning, mirroring _ensure_sink's
    open-failure contract."""
    global _sink_error, _sink_file
    f = _ensure_sink()
    if f is None:
        return
    try:
        f.write(json.dumps(record) + "\n")
        f.flush()
    except OSError as e:
        from .utils import log
        log.warning("telemetry: write to metrics_out failed (%s); "
                    "sink disabled" % e)
        _sink_error = True
        try:
            f.close()
        except OSError:
            pass
        _sink_file = None


def emit_iteration(iteration: int, phase_times: Dict[str, float],
                   trace_times: Optional[Dict[str, float]] = None,
                   eval_metrics: Optional[dict] = None,
                   health: Optional[dict] = None,
                   memory: Optional[dict] = None,
                   extra: Optional[dict] = None) -> dict:
    """Build and write one per-iteration record.  Canonical phase keys are
    always present; counters ride cumulatively.  ``health`` is the
    iteration's training-health block (lightgbm_tpu/health.py),
    ``memory`` the per-iteration gauge block (take_memory_record).
    Returns the record."""
    _watch_midrun_recompiles()
    pt = {k: 0.0 for k in CANONICAL_PHASES}
    pt.update(phase_times)
    record = {
        "iter": int(iteration),
        "phase_times": _round_times(pt),
        "counters": dict(sorted(_counters.items())),
        "eval_metrics": eval_metrics or {},
    }
    if _timeline:
        # local wall clock; the shard header's clock_offset_s maps it
        # onto the leader's clock when timeline_report merges shards
        record["t"] = round(time.time(), 6)
    if _ring_armed:
        _ring_event("iteration", str(iteration))
    try:
        from . import tracing
        if tracing.active():
            # the flight recorder's training timeline (ISSUE 16): one
            # train_iter ring event per iteration, same record keys as
            # the timeline shards (iter / phase_times / t)
            tracing.record_train_iteration(iteration,
                                           record["phase_times"])
    except Exception:
        pass
    watchdog_checkin(iteration=iteration)
    if trace_times:
        record["trace_times"] = _round_times(trace_times)
    if health is not None:
        record["health"] = health
    if memory is not None:
        record["memory"] = memory
    if extra:
        record.update(extra)
    write_record(record)
    return record


def emit_summary(extra: Optional[dict] = None) -> dict:
    """Write the end-of-run totals record (cumulative phase/trace times,
    counters and memory gauges — after cross-host aggregation in
    multi-process runs)."""
    record = {
        "summary": True,
        "phase_times": _round_times(_phase_times),
        "phase_counts": dict(sorted(_phase_counts.items())),
        "trace_times": _round_times(_trace_times),
        "counters": dict(sorted(_counters.items())),
    }
    if _timeline:
        record["t"] = round(time.time(), 6)
    mem = memory_snapshot()
    if mem is not None:
        record["memory"] = mem
    ic = interconnect_snapshot()
    if ic is not None:
        record["interconnect"] = ic
    _attach_cost_blocks(record)
    try:
        from . import tracing
        trace = tracing.snapshot()
        if trace:
            # flight-recorder close-out (ISSUE 16): ring occupancy,
            # exact drop count and the live sketch percentiles ride the
            # summary record — percentiles at close without a bench run
            record["trace"] = trace
    except Exception:
        pass
    if extra:
        record.update(extra)
    write_record(record)
    return record
