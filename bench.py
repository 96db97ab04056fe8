"""Benchmark driver: boosting iters/sec on a Higgs-like synthetic dataset.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference LightGBM binary (compiled from /root/reference with
-O2, socket variant) measured on the SAME synthetic data generator and
config (28 features, num_leaves=255, max_bin=255, binary objective) on the
dev host CPU (single core), per BASELINE.md's prescription to measure
locally since the repo publishes no numbers.  Anchors:
  1M rows:  0.433 s/iter → 2.31 iters/sec
  11M rows: 17.9 s/iter → 0.0559 iters/sec  (cache-bound: 41x slower for
            11x the rows — the 308 MB bin matrix falls out of LLC)
Other row counts interpolate the per-row cost log-linearly between anchors.

Usage: python bench.py [--rows N] [--leaves L] [--iters K]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

REFERENCE_CPU_ANCHORS = {1_000_000: 2.31, 11_000_000: 0.0559}

# CUDA-LightGBM anchor (BASELINE.md "CUDA anchor" section): no number can
# be measured here (no GPU, zero egress) and the 2016 reference predates
# the GPU learner, so this is a documented first-principles estimate for a
# V100/A100-class GPU running modern LightGBM's CUDA tree learner on
# Higgs-11M / 255 leaves / 255 bins: ~1.4e9 histogram updates per tree
# (N*F*(1+0.5*(levels-1)) with the smaller-child trick) at the
# ~10-20 G shared-memory-atomic updates/sec such kernels sustain, plus
# roughly equal partition/gather cost -> ~2.5 (V100) to ~5 (A100)
# iters/sec; the anchor below is the midpoint.  1M rows mostly amortizes
# fixed kernel-launch/partition overheads -> ~15 iters/sec.
CUDA_ANCHORS = {1_000_000: 15.0, 11_000_000: 3.0}


def _anchored_iters_per_sec(anchors, rows: int, flat_below: bool) -> float:
    """Log-linear interpolation between the two anchors, linear per-row
    cost beyond the large end.  ``flat_below``: below the small anchor the
    CUDA estimate plateaus (fixed launch/partition overheads dominate),
    while the reference-CPU baseline extrapolates the per-row cost
    linearly (an upper bound — see reference_iters_per_sec)."""
    (r0, v0), (r1, v1) = sorted(anchors.items())
    if rows <= r0:
        return v0 if flat_below else v0 * (r0 / rows)
    if rows >= r1:
        return v1 * (r1 / rows)
    t = (math.log(rows) - math.log(r0)) / (math.log(r1) - math.log(r0))
    return math.exp(math.log(v0) * (1 - t) + math.log(v1) * t)


def cuda_iters_per_sec(rows: int) -> float:
    """CUDA-LightGBM estimate at this scale (CUDA_ANCHORS above)."""
    return _anchored_iters_per_sec(CUDA_ANCHORS, rows, flat_below=True)


def reference_iters_per_sec(rows: int) -> float:
    """Reference-binary baseline at this scale: log-linear between anchors,
    linear per-row cost beyond either end.

    Below the 1M anchor this extrapolates the 1M per-row cost linearly, but
    the reference is FASTER per row at cache-resident scales (the 11M anchor
    is 41x slower for 11x the rows precisely because 1M still partly fits in
    LLC) — so sub-1M ``vs_baseline`` is an upper-bound estimate; the JSON
    carries a ``vs_baseline_bound`` marker there."""
    return _anchored_iters_per_sec(REFERENCE_CPU_ANCHORS, rows,
                                   flat_below=False)


def make_data(rows: int, features: int, seed: int = 42,
              narrow_features: int = 0):
    """Higgs-like synthetic table.

    ``narrow_features`` == 0 (default): every column fully continuous —
    the historical generator, byte-identical output (scripts/auc_parity.py
    pins its recorded reference anchors to a digest of this path).

    ``narrow_features`` > 0 (r06 headline): that many columns are
    low-cardinality (integer counts, binary/ternary flags, coarsely
    quantized detector-style readings; <= 64 distinct values -> the narrow
    bin-width class), the rest stay continuous (num_bin == max_bin).
    Through r05 the bench table was the all-continuous uniform worst case
    (num_bin == max_bin for all 28 features) — a distribution production
    tables don't exhibit: real tabular workloads (the actual HIGGS file
    included, with its discrete b-tag columns) mix counts/flags/quantized
    readings with dense floats, and the reference prices each feature at
    its OWN num_bin (BinMapper.find_bin).  The r06 headline models that
    mix so the mixed-bin packing path is measured on the workload shape it
    exists for.  The reference-CPU/CUDA baselines stay comparable: both
    are per-ROW scatter-add/atomic machines whose per-iteration cost does
    not scale with a feature's bin count, so the anchors price this table
    the same as the all-continuous one.
    """
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, features).astype(np.float32)
    if narrow_features > 0:
        # quantize a deterministic spread of columns (not one contiguous
        # run, so the packed layout is a real permutation) into
        # low-cardinality shapes; the quantized column KEEPS the gaussian
        # signal the logits read — predictive structure survives
        narrow_idx = np.linspace(0, features - 1,
                                 narrow_features).astype(int)
        for j, f in enumerate(narrow_idx):
            card = (2, 3, 5, 9, 17, 33, 61)[j % 7]
            q = np.clip(((x[:, f] + 3.0) * (card / 6.0)).astype(np.int32),
                        0, card - 1)
            x[:, f] = q.astype(np.float32)
        w = rng.randn(features) / np.sqrt(features)
        xs = (x - x.mean(axis=0)) / (x.std(axis=0) + 1e-9)
        logits = (xs @ w + 0.5 * np.sin(xs[:, 0] * 2)
                  + 0.3 * xs[:, 1] * xs[:, 2])
        y = (logits + rng.randn(rows) * 0.5 > 0).astype(np.float32)
        return x.astype(np.float64), y
    w = rng.randn(features) / np.sqrt(features)
    logits = x @ w + 0.5 * np.sin(x[:, 0] * 2) + 0.3 * x[:, 1] * x[:, 2]
    y = (logits + rng.randn(rows) * 0.5 > 0).astype(np.float32)
    return x.astype(np.float64), y


# keys the headline bench copies out of the --bench-predict subprocess
# (scripts/perf_gate.py RATE_KEYS gates the rows/sec entries; latency and
# A/B keys ride along ungated)
PREDICT_COPY_KEYS = (
    "predict_b65536_rows_per_sec", "predict_b65536_spread",
    "predict_b65536_p50_ms", "predict_b65536_p99_ms",
    "predict_b1024_rows_per_sec", "predict_b1024_spread",
    "predict_b32_rows_per_sec", "predict_b32_spread",
    "predict_b1_p50_ms", "predict_b1_p99_ms",
    "predict_int8_b65536_rows_per_sec", "predict_int8_b65536_spread",
    "predict_scan_b65536_rows_per_sec", "predict_bfs_vs_scan_64k",
    "predict_recompiles",
)


def _with_device(out: dict) -> dict:
    """Every result block names the device it ran on, as JAX reports it:
    a number from a CPU run must never read as a device metric."""
    import jax
    devs = jax.devices()
    out["device"] = {"platform": devs[0].platform,
                     "device_kind": devs[0].device_kind,
                     "count": len(devs)}
    return out


def bench_predict(args) -> int:
    """Serving lane: predictions/sec + latency percentiles per bucket.

    Trains a model on min(--rows, 1M) rows (the serving number prices the
    ENGINE, not the trainer — 1M keeps the model-build bounded), then
    times ``ServingEngine.scores`` at each bucket shape.  Every timed
    call is end-to-end serving work: host rank-encode, pad-to-bucket,
    compiled device walk, readback — the number a latency SLO actually
    sees.  The per-tree-scan A/B at the 64k bucket is the acceptance
    number for the breadth-first engine (ISSUE 7)."""
    import jax  # noqa: F401  (device init before timing)
    from lightgbm_tpu import costmodel, telemetry
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.serving import ServingEngine
    from lightgbm_tpu.utils import log

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)
    # armed telemetry = costmodel compile registry on: the lane asserts
    # zero mid-run recompiles at the bucketed shapes (and the JSON gains
    # the predict-phase roofline block).  fence=True: the engine fences
    # its predict spans, so the roofline attained rates price the walk's
    # execution, not its dispatch (PR 4 rule; wall-clock timing below is
    # unaffected — scores() reads back synchronously either way)
    telemetry.enable(fence=True)
    telemetry.reset()

    train_rows = min(args.rows, 1_000_000)
    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    x, y = make_data(train_rows, args.features, narrow_features=narrow)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)
    params = {
        "objective": "binary",
        "num_leaves": str(args.leaves),
        "min_data_in_leaf": "100",
        "min_sum_hessian_in_leaf": "10.0",
        "learning_rate": "0.1",
        "grow_policy": "depthwise",
        "hist_dtype": args.hist_dtype,
        "num_iterations": str(args.iters),
    }
    cfg = OverallConfig()
    cfg.set(params, require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds,
                 create_objective(cfg.objective_type, cfg.objective_config))
    booster.train_chunk(args.iters)
    booster.flush_pipeline()
    T = len(booster.models)

    buckets = (1, 32, 1024, 65536)
    flat = booster.export_flat()
    engines = {
        "f32": ServingEngine(flat, buckets=buckets),
        "int8": ServingEngine(flat, buckets=buckets, quantize="int8"),
        "scan": ServingEngine(flat, buckets=buckets, algo="scan"),
    }
    xe, _ = make_data(buckets[-1], args.features, seed=7,
                      narrow_features=narrow)

    def measure(engine, n):
        """(rows/sec samples, per-call latencies s).  One warm call
        compiles; each repeat times enough calls to fill ~0.5 s wall."""
        batch = xe[:n]
        engine.scores(batch)
        samples, lats = [], []
        for _ in range(max(1, args.repeats)):
            calls, t0 = 0, time.perf_counter()
            while calls < 3 or time.perf_counter() - t0 < 0.5:
                c0 = time.perf_counter()
                engine.scores(batch)
                lats.append(time.perf_counter() - c0)
                calls += 1
                if calls >= 500:
                    break
            samples.append(n * calls / (time.perf_counter() - t0))
        return samples, lats

    out = {
        "metric": f"predict_rows_per_sec_higgs{train_rows // 1000}k_"
                  f"trees{T}_leaves{args.leaves}",
        "unit": "rows/sec",
        "host": costmodel.host_fingerprint(),
        "trees": T,
    }

    def record(prefix, samples, lats):
        med = float(np.median(samples))
        out[f"{prefix}_rows_per_sec"] = round(med, 2)
        out[f"{prefix}_spread"] = round(
            (max(samples) - min(samples)) / med, 4) if med > 0 else 0.0
        out[f"{prefix}_p50_ms"] = round(
            1e3 * float(np.percentile(lats, 50)), 4)
        out[f"{prefix}_p99_ms"] = round(
            1e3 * float(np.percentile(lats, 99)), 4)
        return med

    for b in buckets:
        samples, lats = measure(engines["f32"], b)
        med = record(f"predict_b{b}", samples, lats)
        if b == buckets[-1]:
            out["value"] = round(med, 2)
            out["samples"] = [round(s, 2) for s in samples]
            out["spread"] = out[f"predict_b{b}_spread"]
    # steady-state contract: the f32 bucketed ladder compiled during
    # warmup; everything after (timed loops, the int8/scan lanes, one
    # more full ladder sweep) must not add ONE f32 program signature
    def _f32_programs():
        return len([r for r in costmodel.phase_program_records("predict")
                    if r["name"] == "serve/bfs_scores"])

    base_programs = _f32_programs()
    samples, lats = measure(engines["int8"], buckets[-1])
    record(f"predict_int8_b{buckets[-1]}", samples, lats)
    samples, lats = measure(engines["scan"], buckets[-1])
    record(f"predict_scan_b{buckets[-1]}", samples, lats)
    out["predict_bfs_vs_scan_64k"] = round(
        out[f"predict_b{buckets[-1]}_rows_per_sec"]
        / max(out[f"predict_scan_b{buckets[-1]}_rows_per_sec"], 1e-9), 4)
    for b in buckets:
        engines["f32"].scores(xe[:b])
    out["predict_recompiles"] = _f32_programs() - base_programs
    snap = telemetry.snapshot()
    if "roofline" in snap:
        out["roofline"] = snap["roofline"]
    if "compile" in snap:
        out["compile"] = snap["compile"]
    print(json.dumps(_with_device(out)))
    return 0


# keys the headline bench copies out of the --bench-serve subprocess
# (perf_gate gates serve_rows_per_sec on the rate trajectory and
# serve_p99_us on a must-not-grow lane; serve_recompiles, serve_dropped
# and serve_misscored are ABSOLUTE findings — any nonzero fails the
# gate with no trajectory needed.  ISSUE 16 adds trace_overhead_pct —
# throughput cost of the armed flight recorder, recorder-on vs -off A/B
# on this same lane, must-not-grow with trace_spread as its noise band —
# and trace_dropped_at_default, ring overwrites at the DEFAULT
# trace_ring_events during the measured windows, absolute like
# serve_dropped)
SERVE_COPY_KEYS = (
    "serve_rows_per_sec", "serve_spread", "serve_p50_us", "serve_p99_us",
    "serve_p99_sketch_vs_sorted",
    "serve_offered_rows_per_sec", "serve_requests", "serve_linger_us",
    "serve_recompiles", "serve_dropped", "serve_misscored",
    "serve_swap_drain_ms", "serve_coalesced_batches",
    "serve_mean_batch_rows", "serve_shards_used",
    "trace_overhead_pct", "trace_spread", "trace_dropped_at_default",
    # live-monitor lane (ISSUE 20): monitor_overhead_pct is
    # must-not-grow (band monitor_spread); drift_aa_psi above the A/A
    # bound and monitor_slo_breaches > 0 without monitor_induced_fault
    # are ABSOLUTE findings
    "monitor_overhead_pct", "monitor_spread", "drift_aa_psi",
    "monitor_slo_breaches", "monitor_induced_fault",
)


def bench_serve(args) -> int:
    """Elastic-serving lane (ISSUE 13): p99 latency + rows/sec under a
    CONCURRENT OPEN-LOOP load generator, plus a mid-load hot swap.

    Unlike bench_predict (throughput on pre-formed batches), this lane
    prices the full serving path a latency SLO sees: requests arrive on
    a fixed open-loop schedule (arrivals never wait for completions, so
    queueing delay is measured, not hidden), the ServingFront coalesces
    them onto the bucket ladder under the linger deadline, and
    per-request latency is submit → future completion.  A second phase
    swaps to a DIFFERENT engine mid-load (drain-and-flip, double-
    buffered warmup) and counts dropped and misscored requests — both
    must be zero, and perf_gate flags any nonzero as an absolute
    finding, like serve_recompiles.

    Flight recorder (ISSUE 16): steady-phase segments run interleaved
    recorder-ON / recorder-OFF; the ON segments (the shipped default)
    provide the serve metrics and the OFF controls price the recorder
    (``trace_overhead_pct``).  ``serve_p50_us``/``serve_p99_us`` are
    computed from a streaming LatencySketch fed with the bench's own
    per-request latencies and pinned against the sorted sample within
    bucket resolution.  Each armed window uses a fresh DEFAULT-size
    ring, so ``trace_dropped_at_default`` > 0 means one ~2 s window
    overflowed the default ring — an absolute perf_gate finding.

    Live monitor (ISSUE 20): a third interleave prices the armed
    monitor on top of the recorder (``monitor_overhead_pct``), runs a
    generous SLO that must NOT breach on healthy load
    (``monitor_slo_breaches``) and reports the A/A drift false-positive
    floor (``drift_aa_psi``)."""
    import jax  # noqa: F401  (device init before timing)
    from lightgbm_tpu import costmodel, telemetry, tracing
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.serving import ServingEngine, ServingFront
    from lightgbm_tpu.utils import log

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)
    telemetry.enable(fence=True)
    telemetry.reset()

    train_rows = min(args.rows, 1_000_000)
    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    x, y = make_data(train_rows, args.features, narrow_features=narrow)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)
    cfg = OverallConfig()
    cfg.set({
        "objective": "binary", "num_leaves": str(args.leaves),
        "min_data_in_leaf": "100", "min_sum_hessian_in_leaf": "10.0",
        "learning_rate": "0.1", "grow_policy": "depthwise",
        "hist_dtype": args.hist_dtype,
        "num_iterations": str(args.iters),
    }, require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds,
                 create_objective(cfg.objective_type, cfg.objective_config))
    booster.train_chunk(args.iters)
    booster.flush_pipeline()
    T = len(booster.models)

    shards = max(int(args.serve_shards), 0)
    linger_us = max(int(args.predict_linger_us), 0)
    # a DENSER ladder than the offline default: coalesced batches land
    # between 1k and 64k under open-loop load, and the default ladder's
    # sparse top would pad every ~2k-row batch to 65536 (40x wasted
    # walk).  Still a closed compiled set — this is exactly the
    # predict_buckets knob doing its job for the online profile.
    buckets = (1, 32, 256, 2048, 16384, 65536)
    # the swap pair: engine A serves a PREFIX of the model, engine B the
    # full model — the realistic continued-training hot swap, and their
    # scores differ so a torn request cannot hide
    ta = max(T - 2, 1)
    eng_a = ServingEngine(booster.export_flat(ta), buckets=buckets,
                          shards=shards, linger_us=linger_us)
    eng_b = ServingEngine(booster.export_flat(), buckets=buckets,
                          shards=shards, linger_us=linger_us)

    pool_rows = 65536
    pool, _ = make_data(pool_rows, args.features, seed=7,
                        narrow_features=narrow)
    # per-request references for the misscore check: every request is a
    # contiguous pool slice, so its exact expected scores are a column
    # slice of one of these
    ref_a = eng_a.scores(pool)
    ref_b = eng_b.scores(pool)
    eng_a.warmup()
    eng_b.warmup()             # double-buffer: compiled BEFORE the load
    progs0 = len(costmodel.phase_program_records("predict"))

    # closed-loop capacity estimate prices the offered open-loop rate
    req_rows = 64
    t0 = time.perf_counter()
    calls = 0
    while time.perf_counter() - t0 < 0.5 or calls < 3:
        eng_a.scores(pool[:1024])
        calls += 1
    cap = 1024 * calls / (time.perf_counter() - t0)
    # offer well below the closed-loop estimate: at ~capacity the
    # bounded queue saturates and p99 measures backpressure, not the
    # serving path.  0.3x keeps the generator truly open-loop.
    offered = max(cap * 0.3, req_rows * 10.0)
    interval = req_rows / offered

    def open_loop(front, duration_s, swap_after_s=None, swap_to=None):
        """Submit pool slices on the open-loop schedule; returns
        (records, swap_drain_s).  Arrivals follow the wall clock — a
        slow completion never delays the next submit."""
        import threading
        records = []
        start = time.perf_counter()
        next_t = start
        i = 0
        drain_box = {}
        swap_thread = None
        swapped = swap_after_s is None
        while time.perf_counter() - start < duration_s:
            if not swapped and time.perf_counter() - start >= swap_after_s:
                # the swap blocks until the drain-and-flip completes, so
                # it runs on its OWN thread: the open-loop schedule keeps
                # submitting INTO the drain window — that concurrency is
                # exactly what the zero-drop contract is about
                swap_thread = threading.Thread(
                    target=lambda: drain_box.__setitem__(
                        "drain", front.swap_engine(swap_to, warmup=False)))
                swap_thread.start()
                swapped = True
            now = time.perf_counter()
            if now < next_t:
                time.sleep(next_t - now)
            s = (i * req_rows) % (pool_rows - req_rows)
            rec = {"s": s, "n": req_rows, "t_sub": time.perf_counter()}
            fut = front.submit(pool[s:s + req_rows])
            fut.add_done_callback(
                lambda f, rec=rec: rec.__setitem__(
                    "t_done", time.perf_counter()))
            rec["fut"] = fut
            records.append(rec)
            next_t += interval
            i += 1
        if swap_thread is not None:
            swap_thread.join(60.0)
        return records, drain_box.get("drain")

    # ---- phase 1: steady open-loop load on engine A, interleaved
    # recorder-ON / recorder-OFF segments (ISSUE 16).  ON segments are
    # the shipped default-on state and provide the serve metrics; OFF
    # segments are the control that prices the recorder.  Every ON
    # segment arms a FRESH ring at the default size — a nonzero
    # trace_dropped_at_default therefore means a single ~2 s window
    # overflowed trace_ring_events, never an artifact of accumulation.
    lats, samples, requests = [], [], 0
    off_samples = []
    bench_sk = tracing.LatencySketch()  # bench's own submit→done lats
    wall_sk = None                      # recorder-side serve_wall_us
    dropped_at_default = 0
    for rep in range(2 * max(1, args.repeats)):
        on = rep % 2 == 0
        if on:
            tracing.arm()               # fresh DEFAULT-size ring
        front = ServingFront(eng_a, linger_us=linger_us)
        t0 = time.perf_counter()
        records, _ = open_loop(front, duration_s=2.0)
        front.close()
        wall = time.perf_counter() - t0
        done_rows = sum(r["n"] for r in records if "t_done" in r)
        if not on:
            off_samples.append(done_rows / wall)
            continue
        samples.append(done_rows / wall)
        seg_sk = tracing.LatencySketch()
        for r in records:
            if "t_done" in r:
                lat = r["t_done"] - r["t_sub"]
                lats.append(lat)
                seg_sk.record(1e6 * lat)
        # the cross-segment fold IS the sketch merge operator (the same
        # count addition that folds across threads/hosts)
        bench_sk.merge(seg_sk)
        requests += len(records)
        dropped_at_default += tracing.dropped()
        sk = tracing.sketch("serve_wall_us")
        if sk is not None:
            wall_sk = sk if wall_sk is None else wall_sk.merge(sk)
        tracing.disarm()

    # ---- phase 2: the mid-load hot swap (drain-and-flip, zero drops),
    # recorder armed so the swap/drain events land on the request
    # timeline; --trace-dump flushes this window's ring on disarm for
    # scripts/trace_report.py
    if args.trace_dump:
        os.makedirs(args.trace_dump, exist_ok=True)
    tracing.arm(dump_dir=args.trace_dump)
    front = ServingFront(eng_a, linger_us=linger_us)
    records, drain = open_loop(front, duration_s=2.0, swap_after_s=1.0,
                               swap_to=eng_b)
    front.close()
    dropped_at_default += tracing.dropped()
    sk = tracing.sketch("serve_wall_us")
    if sk is not None:
        wall_sk = sk if wall_sk is None else wall_sk.merge(sk)
    trace_dump_path = tracing.disarm()
    dropped = 0
    misscored = 0
    for r in records:
        fut = r["fut"]
        if not fut.done() or fut.exception() is not None:
            dropped += 1
            continue
        got = np.asarray(fut.result())
        s, n = r["s"], r["n"]
        if not (np.array_equal(got, ref_a[:, s:s + n])
                or np.array_equal(got, ref_b[:, s:s + n])):
            misscored += 1

    # ---- phase 3: live-monitor cost (ISSUE 20), interleaved monitor-ON
    # / monitor-OFF segments with the recorder armed in BOTH (the
    # shipped default) — the delta prices ONLY the monitor: the
    # per-batch score feed, the emitter's windowed differencing and the
    # JSONL append.  The ON segments also run a generous SLO (20x the
    # measured healthy p99) so a breach on a no-fault bench round is an
    # absolute perf_gate finding, and the last segment's A/A PSI rides
    # out as drift_aa_psi — the measured false-positive floor.
    from lightgbm_tpu import monitor
    mon_samples, mon_off_samples = [], []
    mon_breaches = 0
    mon_aa_psi = None
    mon_slo_us = 20.0 * bench_sk.quantile(0.99)
    with tempfile.TemporaryDirectory() as mon_td:
        for rep in range(2 * max(1, args.repeats)):
            on = rep % 2 == 0
            tracing.arm()               # recorder on in BOTH segments
            if on:
                monitor.arm(out_path=os.path.join(
                                mon_td, "monitor-%d.jsonl" % rep),
                            interval_s=0.5, slo_p99_us=mon_slo_us,
                            slo_window_s=6.0)
            front = ServingFront(eng_a, linger_us=linger_us)
            t0 = time.perf_counter()
            records, _ = open_loop(front, duration_s=2.0)
            front.close()
            wall = time.perf_counter() - t0
            done_rows = sum(r["n"] for r in records if "t_done" in r)
            if on:
                mon_samples.append(done_rows / wall)
                aa = monitor.aa_verdict(front._monitor_key)
                if aa["psi"] is not None:
                    mon_aa_psi = aa["psi"]
                mon_breaches += monitor.monitor_snapshot().get(
                    "breaches", 0)
                monitor.disarm()
            else:
                mon_off_samples.append(done_rows / wall)
            tracing.disarm()

    med = float(np.median(samples))
    off_med = float(np.median(off_samples)) if off_samples else med
    mon_med = float(np.median(mon_samples)) if mon_samples else med
    mon_off_med = (float(np.median(mon_off_samples))
                   if mon_off_samples else mon_med)
    # sketch percentiles, A/B-pinned against the sorted sample at the
    # same nearest-rank convention: agreement within the sketch's bucket
    # resolution (a factor sqrt(growth)) is a mathematical guarantee —
    # any violation is a sketch bug and aborts the bench
    lat_us = np.sort(np.asarray(lats)) * 1e6

    def _nearest_rank(q):
        r = min(len(lat_us) - 1, max(0, int(math.ceil(q * len(lat_us))) - 1))
        return float(lat_us[r])

    sk_p50, sk_p99 = bench_sk.quantile(0.50), bench_sk.quantile(0.99)
    tol = math.sqrt(bench_sk.growth) * (1.0 + 1e-9)
    for q, sk_v in ((0.50, sk_p50), (0.99, sk_p99)):
        exact = _nearest_rank(q)
        assert exact > 0 and 1.0 / tol <= sk_v / exact <= tol, (
            "latency sketch p%g %.1fus vs sorted %.1fus — outside bucket "
            "resolution (growth %g)"
            % (100 * q, sk_v, exact, bench_sk.growth))

    def _spread(vals, m):
        return (round((max(vals) - min(vals)) / m, 4)
                if vals and m > 0 else 0.0)

    out = {
        "metric": f"serve_rows_per_sec_higgs{train_rows // 1000}k_"
                  f"trees{T}_leaves{args.leaves}",
        "unit": "rows/sec",
        "host": costmodel.host_fingerprint(),
        "trees": T,
        "value": round(med, 2),
        "samples": [round(s, 2) for s in samples],
        "spread": round((max(samples) - min(samples)) / med, 4)
                  if med > 0 else 0.0,
        "serve_rows_per_sec": round(med, 2),
        "serve_spread": _spread(samples, med),
        "serve_p50_us": round(sk_p50, 1),
        "serve_p99_us": round(sk_p99, 1),
        "serve_p99_sketch_vs_sorted": round(sk_p99 / _nearest_rank(0.99),
                                            4),
        "serve_offered_rows_per_sec": round(offered, 2),
        "serve_requests": requests,
        "serve_linger_us": linger_us,
        "serve_recompiles": len(costmodel.phase_program_records("predict"))
                            - progs0,
        "serve_dropped": dropped,
        "serve_misscored": misscored,
        "serve_swap_drain_ms": round(1e3 * drain, 3)
                               if drain is not None else None,
        "serve_coalesced_batches": telemetry.counters().get(
            "serve/coalesced_batches", 0),
        "serve_mean_batch_rows": round(
            telemetry.counters().get("serve/coalesced_rows", 0)
            / max(telemetry.counters().get("serve/coalesced_batches", 1),
                  1), 1),
        "serve_shards_used": eng_a.shards,
        # recorder cost: throughput lost with the recorder armed, from
        # the interleaved ON/OFF medians (negative = noise; the gate's
        # must-not-grow band absorbs it)
        "trace_overhead_pct": round(100.0 * (off_med - med) / off_med, 2)
                              if off_med > 0 else 0.0,
        "trace_spread": max(_spread(samples, med),
                            _spread(off_samples, off_med)),
        "trace_dropped_at_default": int(dropped_at_default),
        # live-monitor cost (ISSUE 20): throughput lost with the monitor
        # armed on top of the recorder, from the phase-3 interleave —
        # must-not-grow in perf_gate with monitor_spread as its band
        "monitor_overhead_pct": round(
            100.0 * (mon_off_med - mon_med) / mon_off_med, 2)
            if mon_off_med > 0 else 0.0,
        "monitor_spread": max(_spread(mon_samples, mon_med),
                              _spread(mon_off_samples, mon_off_med)),
        # A/A PSI on the last monitored segment's own scores: the
        # measured drift false-positive floor (absolute perf_gate
        # finding above monitor.AA_PSI_BOUND)
        "drift_aa_psi": round(mon_aa_psi, 5)
                        if mon_aa_psi is not None else None,
        # breaches fired under a 20x-generous SLO on healthy load: any
        # nonzero on a round not declaring an induced fault is an
        # absolute perf_gate finding
        "monitor_slo_breaches": int(mon_breaches),
        "monitor_induced_fault": False,
    }
    if wall_sk is not None:
        # recorder-side enqueue→complete wall percentiles (the traced
        # identity's wall, vs the bench's submit→callback lats above)
        out["trace_wall_p99_us"] = round(wall_sk.quantile(0.99), 1)
    if trace_dump_path:
        out["trace_dump"] = trace_dump_path
    snap = telemetry.snapshot()
    if "roofline" in snap:
        out["roofline"] = snap["roofline"]
    if "compile" in snap:
        out["compile"] = snap["compile"]
    print(json.dumps(_with_device(out)))
    return 0


# keys the headline bench copies out of the --bench-ingest subprocess
# (perf_gate gates ingest_rows_per_sec; the A/B, H2D rate and RSS
# assertion ride along ungated)
INGEST_COPY_KEYS = (
    "ingest_rows_per_sec", "ingest_spread",
    "ingest_sync_rows_per_sec", "ingest_overlap_speedup",
    "ingest_h2d_gbps", "ingest_peak_rss_bytes",
    "ingest_rss_bound_bytes", "ingest_rss_ok", "ingest_trained_iters",
    # phase attribution (ISSUE 17): the recorded rounds EXPLAIN an
    # ingest_rows_per_sec move instead of just re-measuring it
    "ingest_parse_pct", "ingest_bin_pct", "ingest_h2d_pct",
    # parallel-parse lane (ISSUE 18): perf_gate turns
    # ingest_rows_per_sec into a must-GROW lane on rounds recording
    # ingest_workers > 1 and flags a silent resolve-to-serial
    "ingest_workers", "ingest_workers_effective",
    "ingest_serial_rows_per_sec", "ingest_serial_parse_pct",
)


def bench_wire(args) -> int:
    """Hybrid/voting wire-bytes lane (ISSUE 9): train the bench schema
    (--features, --max-bin) under ``tree_learner=data`` (pure-DP psum),
    ``hybrid`` and ``voting`` on a simulated (2, 2) mesh and print one
    JSON line with the telemetry interconnect block's LOGICAL
    ``wire_bytes_per_iter`` per learner plus the per-site est-bytes.

    Not a timing lane: the numbers are deterministic (traced shapes x
    loop estimates).  The GATED copy of this series rides the MULTICHIP
    trajectory (__graft_entry__._wire_smoke prints the MULTICHIP_WIRE
    line perf_gate.py checks); this lane reads the same numbers at
    arbitrary schemas, next to the comm-cost model in PROFILE.md
    (F·B·4B DP vs F·B/fs hybrid vs 2k·B voting per split).

    Histograms are pinned to float32 regardless of --hist-dtype: under
    int8 the int accumulators deliberately ride the FULL data-axis psum
    (voting_seams — local caches would break the int-domain bit-identity
    chain), so the voting wire saving the lane prices exists on the
    float paths only."""
    import sys as _sys

    import __graft_entry__ as graft
    device_type = graft._provision_devices(4)

    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.utils import log

    log.set_stream(_sys.stderr)
    log.set_level(log.WARNING)

    rows = min(args.rows, 65536)     # logical bytes don't scale with rows
    x, y = make_data(rows, args.features)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)
    out = graft.measure_wire_bytes(
        ds, device_type,
        {"objective": "binary", "num_leaves": str(args.leaves),
         "min_data_in_leaf": "4", "min_sum_hessian_in_leaf": "0.1",
         "learning_rate": "0.1", "grow_policy": args.grow_policy,
         "hist_dtype": "float32"},
        (("data", {}),
         ("hybrid", {"feature_shards": "2"}),
         # 4k < F/fs — the leaf-wise voting-beats-hybrid regime (the
         # depthwise schedules have no subtraction trick to amortize, so
         # there 2k < F/fs suffices)
         ("voting", {"feature_shards": "2", "top_k": "2"})))
    out.update({"metric": "wire_2x2"})
    out["schema"].update({"rows": rows, "leaves": args.leaves,
                          "hist_dtype": "float32"})
    w = out["wire_bytes_per_iter"]
    out["ok"] = bool(0 < w.get("hybrid", 0) < w.get("data", 0)
                     and 0 < w.get("voting", 0) < w.get("hybrid", 0))
    print(json.dumps(_with_device(out)))
    return 0 if out["ok"] else 1


def bench_ingest(args) -> int:
    """Streaming-ingestion lane (ISSUE 8, io/streaming.py): rows/sec for
    the full chunked parse→bin→HBM pipeline, the double-buffer on/off
    A/B (``LGBM_TPU_INGEST_SYNC=1``), effective H2D GB/s, and the
    peak-host-RSS assertion — a streamed load of a dataset larger than
    one chunk must never approach the resident loader's full [N, F]
    float64 materialization (``ingest_rss_ok``; reported null when the
    scale is too small to discriminate against the interpreter's own
    baseline RSS).  The CSV source is written in bounded row blocks for
    the same reason: the lane prices the LOADER's memory profile, not
    the generator's."""
    import os
    import resource
    import tempfile

    import jax  # noqa: F401  (device init before timing)
    from lightgbm_tpu import costmodel, telemetry
    from lightgbm_tpu.config import IOConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.utils import log

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)
    telemetry.enable()
    telemetry.reset()

    rows = args.rows
    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    tmpdir = tempfile.mkdtemp(prefix="bench_ingest_")
    path = os.path.join(tmpdir, "ingest.csv")
    block = 200_000
    with open(path, "w") as f:
        for s in range(0, rows, block):
            n = min(block, rows - s)
            x, y = make_data(n, args.features, seed=1000 + s // block,
                             narrow_features=narrow)
            f.write("\n".join(
                "%d," % y[i] + ",".join("%.6g" % v for v in x[i])
                for i in range(n)) + "\n")
            del x, y
    csv_bytes = os.path.getsize(path)

    def _rss_bytes() -> int:
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

    rss_after_write = _rss_bytes()

    workers = max(int(getattr(args, "ingest_workers", 0)), 0)

    def load_once(sync: bool, n_workers: int = 0):
        if sync:
            os.environ["LGBM_TPU_INGEST_SYNC"] = "1"
        else:
            os.environ.pop("LGBM_TPU_INGEST_SYNC", None)
        kw = {"ingest_workers": n_workers} if n_workers > 1 else {}
        t0 = time.perf_counter()
        ds = Dataset.load_train(IOConfig(
            data_filename=path, streaming="true",
            ingest_chunk_rows=args.ingest_chunk_rows, **kw))
        return ds, rows / (time.perf_counter() - t0)

    # one warm load compiles the update programs; then timed repeats
    ds, _ = load_once(sync=False, n_workers=workers)
    samples = []
    serial_med = serial_parse_pct = None
    if workers > 1:
        # serial reference lane (ISSUE 18): when the timed lane runs
        # the byte-range worker pool, price the serial loader on the
        # SAME file in the same process — and INTERLEAVE the two lanes'
        # repeats, so minute-scale host drift hits both lanes equally
        # and the within-record speedup ratio (perf_gate's must-GROW
        # baseline) stays honest.  The serial loads never rebind ``ds``:
        # the workers-lane dataset is the one proved below by training.
        phase_us = {k: 0 for k in ("parse", "bin", "h2d")}
        sp = {k: 0 for k in ("parse", "bin", "h2d")}
        h2d = 0
        serial_samples = []
        for _ in range(max(1, args.repeats)):
            c0 = dict(telemetry.counters())
            ds, rps = load_once(sync=False, n_workers=workers)
            c1 = dict(telemetry.counters())
            samples.append(rps)
            h2d += (c1.get("ingest/h2d_bytes", 0)
                    - c0.get("ingest/h2d_bytes", 0))
            for k in phase_us:
                phase_us[k] += (c1.get("ingest/%s_us" % k, 0)
                                - c0.get("ingest/%s_us" % k, 0))
            _, srps = load_once(sync=False)
            s1 = dict(telemetry.counters())
            serial_samples.append(srps)
            for k in sp:
                sp[k] += (s1.get("ingest/%s_us" % k, 0)
                          - c1.get("ingest/%s_us" % k, 0))
        serial_med = float(np.median(serial_samples))
        sp_total = sum(sp.values())
        serial_parse_pct = (round(100.0 * sp["parse"] / sp_total, 2)
                            if sp_total > 0 else None)
    else:
        c0 = dict(telemetry.counters())
        for _ in range(max(1, args.repeats)):
            ds, rps = load_once(sync=False, n_workers=workers)
            samples.append(rps)
        c1 = dict(telemetry.counters())
        h2d = (c1.get("ingest/h2d_bytes", 0)
               - c0.get("ingest/h2d_bytes", 0))
        # tokenizer/bin/H2D attribution over the timed (async) repeats —
        # percentages of the accounted pass-2 time, so the three keys
        # sum to ~100 and a regression names its phase
        phase_us = {k: c1.get("ingest/%s_us" % k, 0)
                    - c0.get("ingest/%s_us" % k, 0)
                    for k in ("parse", "bin", "h2d")}
    phase_total = sum(phase_us.values())
    timed_s = sum(rows / s for s in samples)
    sync_samples = [load_once(sync=True, n_workers=workers)[1]
                    for _ in range(max(1, args.repeats))]
    os.environ.pop("LGBM_TPU_INGEST_SYNC", None)

    # RSS snapshot HERE, before the end-to-end train below: the
    # assertion prices the LOADER's memory profile — trainer
    # allocations (scores, histograms, XLA compile arenas) must not be
    # able to tip ingest_rss_ok over the threshold
    peak_rss = _rss_bytes()

    # end-to-end proof: the streamed (device-resident) dataset trains
    trained = 0
    if args.iters > 0:
        from lightgbm_tpu.config import OverallConfig
        from lightgbm_tpu.models.gbdt import GBDT
        from lightgbm_tpu.objectives import create_objective
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": str(args.leaves),
                 "min_data_in_leaf": "100", "learning_rate": "0.1",
                 "hist_dtype": args.hist_dtype,
                 "grow_policy": args.grow_policy}, require_data=False)
        booster = GBDT()
        booster.init(cfg.boosting_config, ds,
                     create_objective(cfg.objective_type,
                                      cfg.objective_config))
        for _ in range(min(2, args.iters)):
            booster.train_one_iter(is_eval=False)
        trained = len(booster.models)

    rss_bound = rows * args.features * 8   # the resident [N, F] float64
    # the assertion only discriminates when the full matrix would
    # visibly exceed what the process already held (imports + CSV write
    # buffers); tiny lanes report null rather than a vacuous pass.  The
    # threshold is HALF the resident matrix: a regression that
    # re-materializes the full [N, F] float64 lands at about
    # rss_after_write + rss_bound, and allocator reuse of freed write
    # buffers can shave it just under a full-bound threshold — 0.5x
    # still passes every streamed load (one chunk ≪ half the matrix)
    # while failing the exact regression this guards against
    rss_ok = (bool(peak_rss < rss_after_write + 0.5 * rss_bound)
              if rss_bound > max(rss_after_write, 1) else None)

    med = float(np.median(samples))
    sync_med = float(np.median(sync_samples))
    out = {
        "metric": f"ingest_rows_per_sec_{rows // 1000}k_f{args.features}",
        "unit": "rows/sec",
        "host": costmodel.host_fingerprint(),
        "value": round(med, 2),
        "samples": [round(s, 2) for s in samples],
        "spread": round((max(samples) - min(samples)) / med, 4)
        if med > 0 else 0.0,
        "csv_bytes": csv_bytes,
        "ingest_chunk_rows": args.ingest_chunk_rows,
        "ingest_rows_per_sec": round(med, 2),
        "ingest_sync_rows_per_sec": round(sync_med, 2),
        "ingest_overlap_speedup": round(med / max(sync_med, 1e-9), 4),
        "ingest_h2d_gbps": round(h2d / max(timed_s, 1e-9) / 1e9, 4),
        "ingest_peak_rss_bytes": peak_rss,
        "ingest_rss_bound_bytes": rss_bound,
        "ingest_rss_ok": rss_ok,
        "ingest_trained_iters": trained,
        "ingest_parse_pct": (round(100.0 * phase_us["parse"]
                                   / phase_total, 2)
                             if phase_total > 0 else None),
        "ingest_bin_pct": (round(100.0 * phase_us["bin"] / phase_total, 2)
                           if phase_total > 0 else None),
        "ingest_h2d_pct": (round(100.0 * phase_us["h2d"] / phase_total, 2)
                           if phase_total > 0 else None),
    }
    if workers > 1:
        out["ingest_workers"] = workers
        out["ingest_workers_effective"] = int(
            getattr(ds, "ingest_workers_effective", 1))
        out["ingest_serial_rows_per_sec"] = round(serial_med, 2)
        out["ingest_serial_parse_pct"] = serial_parse_pct
    out["ingest_spread"] = out["spread"]
    print(json.dumps(_with_device(out)))
    try:
        os.unlink(path)
        os.rmdir(tmpdir)
    except OSError:
        pass
    return 0


# keys the headline bench copies out of the --bench-ckpt subprocess
# (scripts/perf_gate.py: ckpt_overhead_pct rides the must-not-grow
# latency lane; ckpt_restore_exact recorded False on ANY round is an
# ABSOLUTE finding — the bit-identical same-topology restore contract)
CKPT_COPY_KEYS = (
    "ckpt_overhead_pct", "ckpt_spread", "ckpt_restore_exact",
    "ckpt_writes", "ckpt_dropped", "ckpt_interval",
    "ckpt_off_iters_per_sec", "ckpt_on_iters_per_sec",
)


def bench_ckpt(args) -> int:
    """Checkpoint-cost lane (ISSUE 14): price asynchronous periodic
    checkpointing against the identical run with it off, and pin the
    restore contract.

    Two numbers: ``ckpt_overhead_pct`` — the median percent slowdown of
    ``run_training`` with ``checkpoint_interval=1`` (every iteration, the
    worst case; the async writer thread serializes + writes off the hot
    loop, so this prices exactly the snapshot cost the loop cannot hide)
    — and ``ckpt_restore_exact`` — True iff a kill-free
    train→checkpoint→fresh-booster-restore→finish run reproduces the
    uninterrupted run's model text AND scores bitwise on the same
    topology."""
    import os
    import tempfile

    import jax  # noqa: F401  (device init before timing)
    from lightgbm_tpu import costmodel, telemetry
    from lightgbm_tpu import checkpoint as ckpt_mod
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)
    telemetry.enable()
    telemetry.reset()

    train_rows = min(args.rows, 1_000_000)
    iters = min(args.iters, 64)
    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    x, y = make_data(train_rows, args.features, narrow_features=narrow)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)

    base_params = {
        "objective": "binary",
        "num_leaves": str(args.leaves),
        "min_data_in_leaf": "100",
        "min_sum_hessian_in_leaf": "10.0",
        "learning_rate": "0.1",
        "grow_policy": args.grow_policy,
        "hist_dtype": args.hist_dtype,
    }

    def build(extra=None):
        params = dict(base_params)
        if extra:
            params.update(extra)
        cfg = OverallConfig()
        cfg.set(params, require_data=False)
        b = GBDT()
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config))
        return b

    def timed_run(extra=None):
        b = build(extra)
        t0 = time.perf_counter()
        b.run_training(iters, is_eval=False)
        import jax as _jax
        _jax.block_until_ready(b.score)
        return iters / (time.perf_counter() - t0), b

    # warmup compiles the shared chunk programs for both arms
    timed_run()
    off_samples, on_samples, overheads = [], [], []
    writes = 0
    with tempfile.TemporaryDirectory() as td:
        for r in range(max(1, args.repeats)):
            off, _ = timed_run()
            cdir = os.path.join(td, "r%d" % r)
            on, b_on = timed_run({"checkpoint_interval": "1",
                                  "checkpoint_dir": cdir,
                                  "checkpoint_keep": "2"})
            # checkpoints actually WRITTEN (not the post-prune retained
            # count): the booster records its writer's totals at close
            writes = max(writes,
                         (b_on._ckpt_stats or {}).get("written", 0))
            dropped = (b_on._ckpt_stats or {}).get("dropped", 0)
            off_samples.append(off)
            on_samples.append(on)
            overheads.append(100.0 * (off - on) / on)
        # restore contract: uninterrupted vs checkpoint-resumed, bitwise
        ref, b_ref = timed_run()
        ref_trees = [t.to_string() for t in b_ref.models]
        ref_score = np.asarray(b_ref.score)
        cdir = os.path.join(td, "restore")
        half = max(iters // 2, 1)
        b_half = build({"checkpoint_interval": "1",
                        "checkpoint_dir": cdir})
        b_half.run_training(half, is_eval=False)
        latest = ckpt_mod.latest_checkpoint(cdir)
        b_res = build()
        b_res.restore_checkpoint(ckpt_mod.load_checkpoint(latest))
        b_res.run_training(iters - b_res.iter, is_eval=False)
        exact = (ref_trees == [t.to_string() for t in b_res.models]
                 and np.array_equal(ref_score, np.asarray(b_res.score)))

    med_over = float(np.median(overheads))
    out = {
        "metric": f"ckpt_overhead_higgs{train_rows // 1000}k_"
                  f"leaves{args.leaves}",
        "unit": "pct",
        "host": costmodel.host_fingerprint(),
        "ckpt_interval": 1,
        # clamp at 0: a negative sample is timing noise, and the gated
        # must-not-grow lane wants the cost, not the noise sign
        "ckpt_overhead_pct": round(max(med_over, 0.0), 4),
        # spread in percentage POINTS (the lane's own noise band)
        "ckpt_spread": round(max(overheads) - min(overheads), 4),
        "ckpt_overhead_samples": [round(o, 4) for o in overheads],
        "ckpt_off_iters_per_sec": round(float(np.median(off_samples)), 4),
        "ckpt_on_iters_per_sec": round(float(np.median(on_samples)), 4),
        "ckpt_writes": int(writes),
        "ckpt_dropped": int(dropped),
        "ckpt_restore_exact": bool(exact),
    }
    telemetry.disable()
    print(json.dumps(_with_device(out)))
    return 0


def orchestrate(args) -> int:
    """The full bench: the headline and every satellite lane, each a child
    process run one after another from a parent that stays off JAX (a
    chip belongs to one process at a time — a parent that had trained on
    it would starve its children).  A lane that fails is recorded as
    ``<tag>_error`` and makes the run exit 1."""
    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    failed = []

    def run_lane(tag, cmd_args):
        """One lane = one child process = one owner of the chip, run to
        its end before the next starts.  Returns the lane's JSON record,
        or None after filing ``<tag>_error`` (the run then exits 1)."""
        import subprocess
        cmd = [sys.executable, os.path.abspath(__file__)] + cmd_args
        try:
            res = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                 timeout=2400, check=True)
            return json.loads(res.stdout.strip().splitlines()[-1])
        except Exception as e:
            failed.append(tag)
            out[f"{tag}_error"] = f"{type(e).__name__}: {e}"[:600]
            return None

    # the headline is a lane like any other: this parent never imports
    # JAX, so no child finds the chip already held
    out = {}
    headline = run_lane("headline", sys.argv[1:] + ["--skip-parity"])
    if headline is None:
        print(json.dumps(out))
        return 1
    out = headline

    def sub_bench(tag, extra_args, keys):
        sub = run_lane(tag, [
            "--rows", str(args.rows), "--features", str(args.features),
            "--narrow-features", str(narrow),
            "--leaves", str(args.leaves),
            "--hist-chunk", str(args.hist_chunk),
            "--skip-parity", "--repeats", "3"] + extra_args)
        for out_key, sub_key in keys if sub is not None else ():
            if sub_key in sub:
                out[out_key] = sub[sub_key]

    run_parity = (not args.skip_parity
                  and (args.grow_policy, args.hist_dtype) != ("leafwise",
                                                              "float32"))
    run_maxbin63 = not args.skip_parity and args.max_bin == 255
    # quantized leaf-wise parity mode: the compacted grower with int8
    # histograms — prices whether the per-pass quantize/pack overhead
    # (fixed cost per histogram pass) still binds now that leaf-wise
    # passes run over bucketed segments instead of full sweeps
    run_leafwise_int8 = (not args.skip_parity
                         and (args.grow_policy,
                              args.hist_dtype) != ("leafwise", "int8"))
    run_mixedbin = not args.skip_parity and narrow > 0
    if run_parity:
        # the headline stacks two documented semantic departures from the
        # reference (depthwise level order + int8 quantized gradients,
        # both AUC-gated); price the reference-parity configuration
        # (leafwise, f32) in the same JSON (VERDICT r2 weak #2).
        # median-of-3 + spread: the runtime's dispatch overhead drifts
        # across days on identical code (VERDICT r4 weak #5)
        parity_iters = min(args.iters, 8 if args.rows > 4_000_000 else 16)
        sub_bench("parity",
                  ["--max-bin", str(args.max_bin),
                   "--iters", str(parity_iters),
                   "--grow-policy", "leafwise",
                   "--hist-dtype", "float32"],
                  [("parity_leafwise_f32_iters_per_sec", "value"),
                   ("parity_vs_baseline", "vs_baseline"),
                   ("parity_vs_cuda", "vs_cuda"),
                   ("parity_samples", "samples"),
                   ("parity_spread", "spread")])

    if run_leafwise_int8:
        lw8_iters = min(args.iters, 8 if args.rows > 4_000_000 else 16)
        sub_bench("leafwise_int8",
                  ["--max-bin", str(args.max_bin),
                   "--iters", str(lw8_iters),
                   "--grow-policy", "leafwise",
                   "--hist-dtype", "int8"],
                  [("leafwise_int8_iters_per_sec", "value"),
                   ("leafwise_int8_vs_baseline", "vs_baseline"),
                   ("leafwise_int8_samples", "samples"),
                   ("leafwise_int8_spread", "spread")])

    if run_mixedbin:
        # the packed path pinned explicitly ON (mixed_bin=true): the gated
        # satellite rate guarding the per-class histogram schedule even if
        # the headline's auto resolution ever changes (scripts/perf_gate.py
        # RATE_KEYS)
        sub_bench("mixedbin",
                  ["--max-bin", str(args.max_bin),
                   "--iters", str(args.iters),
                   "--grow-policy", args.grow_policy,
                   "--hist-dtype", args.hist_dtype,
                   "--mixed-bin", "true"],
                  [("mixedbin_iters_per_sec", "value"),
                   ("mixedbin_vs_cuda", "vs_cuda"),
                   ("mixedbin_spread", "spread")])

    if run_mixedbin and args.tree_learner == "serial":
        # the COMPOSED configuration (ISSUE 12): block-local mixed-bin
        # packing ON the 2-D hybrid mesh, pinned explicitly — the gated
        # mixedbin_hybrid_iters_per_sec lane plus the resolution record
        # perf_gate's absolute mixed-bin check reads (a silent fallback
        # to the uniform layout fails the gate, not just the trajectory)
        sub_bench("mixedbin_hybrid",
                  ["--max-bin", str(args.max_bin),
                   "--iters", str(args.iters),
                   "--grow-policy", args.grow_policy,
                   "--hist-dtype", args.hist_dtype,
                   "--mixed-bin", "true",
                   "--tree-learner", "hybrid"],
                  [("mixedbin_hybrid_iters_per_sec", "value"),
                   ("mixedbin_hybrid_spread", "spread"),
                   ("mixedbin_hybrid_tree_learner", "tree_learner"),
                   ("mixedbin_hybrid_mixed_bin_requested",
                    "mixed_bin_requested"),
                   ("mixedbin_hybrid_mixedbin_expected",
                    "mixedbin_expected"),
                   ("mixedbin_hybrid_mixed_bin_on", "mixed_bin_on")])

    run_predict = not args.skip_parity
    if run_predict:
        # serving lane (ISSUE 7): predictions/sec + p50/p99 latency per
        # batch bucket off the compiled serving engine, the int8-ensemble
        # variant, and the legacy per-tree-scan A/B at 64k.  perf_gate
        # gates predict_b65536/predict_int8_b65536/predict_b1024 rows/sec
        # on the BENCH_r* trajectory next to the training rates.
        sub_bench("predict",
                  ["--bench-predict", "--max-bin", str(args.max_bin),
                   "--iters", str(args.iters)],
                  [(k, k) for k in PREDICT_COPY_KEYS])

    run_serve = not args.skip_parity
    if run_serve:
        # elastic-serving lane (ISSUE 13): p99 + rows/sec under the
        # open-loop load generator through the coalescing front, and the
        # mid-load hot swap's dropped/misscored counts.  perf_gate gates
        # serve_rows_per_sec (rate), serve_p99_us (must-not-grow) and
        # flags ANY nonzero recompile/dropped/misscored absolutely.
        sub_bench("serve",
                  ["--bench-serve", "--max-bin", str(args.max_bin),
                   "--iters", str(args.iters)],
                  [(k, k) for k in SERVE_COPY_KEYS])

    run_ckpt = not args.skip_parity
    if run_ckpt:
        # checkpoint-cost lane (ISSUE 14): ckpt_overhead_pct rides the
        # must-not-grow latency lane and ckpt_restore_exact=False is an
        # ABSOLUTE perf_gate finding (a non-bit-identical same-topology
        # restore must never pass a recorded round unnoticed).
        sub_bench("ckpt",
                  ["--bench-ckpt", "--max-bin", str(args.max_bin),
                   "--iters", str(args.iters),
                   "--grow-policy", args.grow_policy,
                   "--hist-dtype", args.hist_dtype],
                  [(k, k) for k in CKPT_COPY_KEYS])

    run_ingest = not args.skip_parity
    if run_ingest:
        # ingestion lane (ISSUE 8): rows/sec for the chunked
        # parse->bin->HBM pipeline at the headline row count, with the
        # double-buffer A/B and the peak-host-RSS assertion.  perf_gate
        # gates ingest_rows_per_sec on the BENCH_r* trajectory.
        ingest_extra = ["--bench-ingest", "--max-bin", str(args.max_bin),
                        "--iters", "2"]
        if args.ingest_workers > 1:
            # the parallel loader's structural win (selective pass 1)
            # only exists past the 50k-row binning sample, and the
            # worker-pool spawn is a fixed cost — price the workers lane
            # at a data-scale row count.  The sub-bench's own serial
            # lane (ingest_serial_rows_per_sec, same record, same
            # scale) is the matched baseline perf_gate's must-GROW
            # check prefers over cross-round medians.
            ingest_extra += ["--rows", str(max(args.rows, 200_000)),
                             "--ingest-workers", str(args.ingest_workers)]
        sub_bench("ingest", ingest_extra,
                  [(k, k) for k in INGEST_COPY_KEYS])

    if run_maxbin63:
        # the reference's own speed configuration (max_bin=63,
        # include/LightGBM/config.h:137): quarter the one-hot MAC cost at
        # a quality cost measured by scripts/auc_parity.py at 11M x 100
        # (BASELINE.md round-5 addendum: AUC delta -0.0023) — the
        # CUDA-anchor comparison at matched bin budget (VERDICT r4 #2)
        sub_bench("maxbin63",
                  ["--max-bin", "63", "--iters", str(args.iters),
                   "--grow-policy", args.grow_policy,
                   "--hist-dtype", args.hist_dtype],
                  [("maxbin63_iters_per_sec", "value"),
                   ("maxbin63_vs_cuda", "vs_cuda"),
                   ("maxbin63_spread", "spread")])
    print(json.dumps(out))
    return 1 if failed else 0


def main() -> int:
    parser = argparse.ArgumentParser()
    # 11M rows is the headline scale (BASELINE.md north star: Higgs-11M,
    # num_leaves=255); pass --rows 1000000 for the quick tuning scale
    parser.add_argument("--rows", type=int, default=11_000_000)
    parser.add_argument("--features", type=int, default=28)
    parser.add_argument("--narrow-features", type=int, default=-1,
                        help="low-cardinality (<=64 distinct) columns in "
                             "the generated table; -1 = 6/7 of the "
                             "features (the r06 mixed-cardinality "
                             "headline schema, see make_data), 0 = the "
                             "historical all-continuous table")
    parser.add_argument("--leaves", type=int, default=255)
    parser.add_argument("--max-bin", type=int, default=255)
    parser.add_argument("--iters", type=int, default=64,
                        help="iterations per chunk; one chunk warms up "
                             "(compiles) and one chunk is timed.  Bigger "
                             "chunks amortize the per-dispatch host "
                             "round-trip (16: 7.2, 32: 7.7, 64: 7.9 "
                             "iters/sec at the 1M default)")
    parser.add_argument("--grow-policy", default="depthwise",
                        choices=["depthwise", "leafwise"],
                        help="depthwise = TPU level-batched histograms "
                             "(headline); leafwise = reference-parity order")
    parser.add_argument("--hist-chunk", type=int, default=0,
                        help="histogram scan row-chunk (0 = policy default)")
    parser.add_argument("--hist-dtype", default="int8",
                        choices=["float32", "bfloat16", "int8"],
                        help="int8 = quantized-gradient Pallas kernel, the "
                             "tuned TPU configuration (held-out AUC within "
                             "0.005 of the reference binary — gated by "
                             "tests/test_auc_parity.py); float32 is the "
                             "reference-exact mode")
    parser.add_argument("--skip-parity", action="store_true",
                        help="skip the additional reference-parity "
                             "(leafwise f32) timing pass")
    parser.add_argument("--repeats", type=int, default=3,
                        help="timed measurement rounds (one dataset build "
                             "+ compile, N timing rounds; applies to both "
                             "grow policies).  The JSON value is the "
                             "median; all samples are reported so drift "
                             "in the runtime's dispatch overhead "
                             "is visible (VERDICT r4 weak #5).  Default 3 "
                             "(r06): the HEADLINE now carries measured "
                             "samples/spread like the satellite lanes, so "
                             "perf_gate's noise band on it is measured "
                             "rather than defaulted")
    parser.add_argument("--mixed-bin", default="auto",
                        choices=["auto", "true", "false"],
                        help="mixed-bin feature packing (per-bin-width-"
                             "class histogram passes); auto = on whenever "
                             "the table mixes narrow and wide features")
    parser.add_argument("--tree-learner", default="serial",
                        choices=["serial", "data", "hybrid", "voting"],
                        help="train the headline on a parallel learner "
                             "over a simulated 4-device CPU mesh "
                             "(hybrid/voting: (2,2) with "
                             "feature_shards=2) — the "
                             "mixedbin_hybrid_iters_per_sec lane runs "
                             "hybrid with mixed_bin=true so the gated "
                             "series carries the composed "
                             "packing-on-the-2-D-mesh configuration")
    parser.add_argument("--pipeline", default="readback",
                        choices=["readback", "off"],
                        help="pipelined boosting: double-buffer the next "
                             "chunk/iteration dispatch against the "
                             "current model readback (bit-identical "
                             "results; 'off' = synchronous A/B)")
    parser.add_argument("--bench-ingest", action="store_true",
                        help="streaming-ingestion benchmark (ISSUE 8): "
                             "write a --rows CSV in bounded blocks, then "
                             "measure the chunked parse->bin->HBM "
                             "pipeline's rows/sec (double-buffer on/off "
                             "A/B, effective H2D GB/s, peak-host-RSS "
                             "assertion, 2-iteration end-to-end train)")
    parser.add_argument("--ingest-chunk-rows", type=int, default=200_000,
                        help="streaming loader chunk length for "
                             "--bench-ingest (the ingest_chunk_rows= "
                             "knob)")
    parser.add_argument("--ingest-workers", type=int, default=0,
                        help="byte-range parse worker processes for "
                             "--bench-ingest (the ingest_workers= knob; "
                             "0/1 = serial loader; >1 additionally "
                             "records the serial reference lane)")
    parser.add_argument("--bench-wire", action="store_true",
                        help="wire-bytes lane (ISSUE 9): tree_learner="
                             "data vs hybrid vs voting on a simulated "
                             "(2,2) mesh at the bench schema; prints the "
                             "per-learner logical wire_bytes_per_iter "
                             "and per-site interconnect est-bytes (the "
                             "gated copy rides the MULTICHIP "
                             "trajectory)")
    parser.add_argument("--bench-predict", action="store_true",
                        help="serving benchmark (ISSUE 7): train a model "
                             "(rows clamped to 1M, --iters trees), then "
                             "measure the compiled serving engine's "
                             "predictions/sec and p50/p99 latency per "
                             "batch bucket (1/32/1k/64k), f32 and int8, "
                             "plus the legacy per-tree-scan A/B at 64k")
    parser.add_argument("--bench-serve", action="store_true",
                        help="elastic-serving benchmark (ISSUE 13): p99 "
                             "latency + rows/sec under a concurrent "
                             "open-loop load generator through the "
                             "coalescing ServingFront, plus a mid-load "
                             "drain-and-flip hot swap with dropped/"
                             "misscored counts (both must be 0)")
    parser.add_argument("--bench-ckpt", action="store_true",
                        help="checkpoint-cost benchmark (ISSUE 14): "
                             "run_training with checkpoint_interval=1 vs "
                             "off (the ckpt_overhead_pct must-not-grow "
                             "lane) plus the bit-identical restore "
                             "contract (ckpt_restore_exact; False fails "
                             "the perf gate absolutely)")
    parser.add_argument("--serve-shards", type=int, default=0,
                        help="tree-shard the --bench-serve engines over "
                             "this many devices (0 = single-device; "
                             "sharded scores are bit-equal by contract)")
    parser.add_argument("--predict-linger-us", type=int, default=500,
                        help="ServingFront max coalescing linger for "
                             "--bench-serve (the predict_linger_us knob)")
    parser.add_argument("--trace-dump", default="",
                        help="flight-recorder dump dir for --bench-serve "
                             "(the swap-phase ring flushes there as JSONL "
                             "on close; render/validate with "
                             "scripts/trace_report.py)")
    args = parser.parse_args()
    if args.bench_ingest:
        return bench_ingest(args)
    if args.bench_predict:
        return bench_predict(args)
    if args.bench_serve:
        if args.serve_shards > 1:
            import __graft_entry__ as graft
            graft._provision_devices(max(args.serve_shards, 4))
        return bench_serve(args)
    if args.bench_wire:
        return bench_wire(args)
    if args.bench_ckpt:
        return bench_ckpt(args)
    if (args.hist_dtype != "int8" and args.rows > 4_000_000
            and args.grow_policy == "depthwise"):
        # one fused dispatch of --iters f32 iterations at this scale would
        # cross the environment's ~60 s per-dispatch execution watchdog
        # (BASELINE.md); clamp to a safe chunk length (coefficient = the
        # measured f32x2 Pallas per-row-per-iteration cost)
        safe = max(1, int(40.0 / (args.rows * 1.8e-7)))
        if args.iters > safe:
            print(f"clamping --iters {args.iters} -> {safe} "
                  f"(f32 dispatch watchdog, see BASELINE.md)",
                  file=sys.stderr)
            args.iters = safe

    if not args.skip_parity:
        return orchestrate(args)

    device_type = ""
    if args.tree_learner != "serial":
        import __graft_entry__ as graft
        device_type = graft._provision_devices(4)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log

    # stdout carries exactly ONE JSON line; all library logs go to stderr
    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)

    # telemetry WITHOUT a sink: kernel-route counters and trace/compile
    # spans are recorded (route decisions fire during the warmup compile),
    # and the only cost inside the timed region is one host perf_counter
    # span per chunk — the JSON gains a phase-breakdown block for free.
    # memory=True adds the span-boundary HBM gauges (a host-side stats
    # read per chunk) so BENCH_*.json rounds carry the memory trajectory;
    # health="auto" follows the record sink, not the bare enabled flag,
    # so the chunked configurations ask for the monitor by name
    # below: the chunk programs accumulate the in-program health vector (a
    # handful of [C,N] reductions per iteration — noise next to the
    # histogram passes).
    # DEPTHWISE runs fence the spans (ISSUE 4): unfenced spans on the
    # async TPU time the chunk DISPATCH, not its execution, and the
    # roofline attained rates would be meaningless.  Total timed wall is
    # unchanged — run_chunks block_until_ready's right after the span
    # either way, the wait just attributes to train_chunk instead of the
    # gap.  Leaf-wise stays unfenced: its per-iteration path overlaps
    # gradient/grow/readback dispatches by design, and fencing would
    # serialize exactly the overlap prior BENCH rounds measured.
    telemetry.enable(memory=True,
                     fence=(args.grow_policy == "depthwise"))

    narrow = (args.narrow_features if args.narrow_features >= 0
              else (args.features * 6) // 7)
    x, y = make_data(args.rows, args.features, narrow_features=narrow)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)

    def run_config(grow_policy: str, hist_dtype: str, iters: int):
        """Train one configuration (fresh booster, shared dataset) and
        return ``(samples, health_summary)``: per-round timed iters/sec
        samples — one warmup round compiles + caches the programs, then
        ``--repeats`` identical rounds are timed (median/spread computed
        by the caller) — plus the booster's cumulative health totals
        (None when the monitor was off, e.g. the leaf-wise path)."""
        params = {
            "objective": "binary",
            "num_leaves": str(args.leaves),
            "min_data_in_leaf": "100",
            "min_sum_hessian_in_leaf": "10.0",
            "learning_rate": "0.1",
            "grow_policy": grow_policy,
            "hist_chunk": str(args.hist_chunk),
            "hist_dtype": hist_dtype,
            "num_iterations": str(2 * iters),
            "mixed_bin": args.mixed_bin,
            "pipeline": args.pipeline,
            "health": "true",
        }
        if grow_policy == "leafwise":
            # leaf-wise times train_one_iter per iteration: the health
            # monitor's separate dispatch + host fetch per iteration is
            # exactly the host round-trip cost this path is
            # dominated by, so it would skew the headline vs prior BENCH
            # rounds — health off here (the chunked path keeps it: its
            # vector rides IN the fused program and the readback)
            params["health"] = "false"
        if args.tree_learner != "serial":
            params.update({"tree_learner": args.tree_learner,
                           "num_machines": "4",
                           "device_type": device_type})
            if args.tree_learner in ("hybrid", "voting"):
                params["feature_shards"] = "2"
        cfg = OverallConfig()
        cfg.set(params, require_data=False)

        booster = GBDT()
        objective = create_objective(cfg.objective_type,
                                     cfg.objective_config)
        learner = None
        if args.tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        booster.init(cfg.boosting_config, ds, objective, learner=learner)
        run_config.mixed_bin_on = booster._pack_spec is not None

        # leaf-wise runs per-iteration: a fused leaf-wise chunk is one
        # dispatch of k x 254 histogram passes, which is both slower than
        # per-iteration dispatch AND crosses the environment's ~60 s
        # per-dispatch execution watchdog at production shapes
        # (BASELINE.md)
        if grow_policy == "leafwise":
            # per-iteration dispatches: warm up (compile) with 2
            # iterations, then time iteration by iteration under a wall
            # budget — the per-dispatch execution watchdog the r01-r05
            # runtime had (~60 s, BASELINE.md) and its variable
            # dispatch overhead make a fixed iteration count fragile
            for _ in range(2):
                if booster.train_one_iter(is_eval=False):
                    raise SystemExit("training stopped during warmup")
            jax.block_until_ready(booster.score)
            samples = []
            for rep in range(max(1, args.repeats)):
                done = 0
                stopped = False
                start = time.perf_counter()
                while done < iters and (done == 0
                                        or time.perf_counter() - start
                                        < 60.0):
                    if booster.train_one_iter(is_eval=False):
                        stopped = True
                        break
                    jax.block_until_ready(booster.score)
                    done += 1
                elapsed = time.perf_counter() - start
                if stopped:
                    # no splittable leaf.  First round: the rate would be
                    # meaningless (and the aborted attempt's wall time
                    # must not count).  Later rounds only ran because
                    # --repeats extended training past the point round 4
                    # benchmarked fine — report the full rounds we have
                    # rather than aborting the whole parity pass.
                    if samples:
                        break
                    raise SystemExit(
                        "training stopped (no splittable leaf) — bench "
                        "numbers would be meaningless; use more rows or "
                        "fewer constraints")
                if done == 0:
                    raise RuntimeError("no leafwise iteration completed")
                samples.append(done / elapsed)
            booster.flush_pipeline()
            return samples, booster.health_summary()

        def run_chunks():
            booster.train_chunk(iters)
            jax.block_until_ready(booster.score)

        run_chunks()
        samples = []
        for _ in range(max(1, args.repeats)):
            start = time.perf_counter()
            run_chunks()
            samples.append(iters / (time.perf_counter() - start))
        # drain the deferred chunk readback (pipeline=readback) so the
        # health/model state below is complete
        booster.flush_pipeline()
        return samples, booster.health_summary()

    run_config.mixed_bin_on = False
    samples, health_summary = run_config(args.grow_policy, args.hist_dtype,
                                         args.iters)
    iters_per_sec = float(np.median(samples))
    snap = telemetry.snapshot()
    from lightgbm_tpu import costmodel
    out = {
        "metric": f"boosting_iters_per_sec_higgs{args.rows // 1000}k_"
                  f"leaves{args.leaves}",
        "value": round(iters_per_sec, 4),
        "unit": "iters/sec",
        # self-describing host metadata (ISSUE 4): BENCH_r*.json trajectory
        # entries carry the hardware/runtime they were measured on, so
        # scripts/perf_gate.py can refuse cross-hardware comparisons
        "host": costmodel.host_fingerprint(),
        "vs_baseline": round(
            iters_per_sec / reference_iters_per_sec(args.rows), 4),
        "vs_cuda": round(iters_per_sec / cuda_iters_per_sec(args.rows), 4),
        "cuda_anchor_iters_per_sec": cuda_iters_per_sec(args.rows),
        # mixed-bin resolution record (ISSUE 12): scripts/perf_gate.py
        # flags a hybrid/voting round whose config requested auto/true
        # but whose booster silently resolved the uniform layout
        "tree_learner": args.tree_learner,
        "mixed_bin_requested": args.mixed_bin,
        "mixedbin_expected": narrow > 0,
        "mixed_bin_on": bool(run_config.mixed_bin_on),
    }
    if len(samples) > 1 or max(1, args.repeats) > 1:
        # emit even when rounds were dropped (no-splittable-leaf early
        # stop): a single-sample result must be distinguishable from a
        # clean multi-round run or the drift record silently vanishes
        out["samples"] = [round(s, 4) for s in samples]
        out["spread"] = round((max(samples) - min(samples))
                              / iters_per_sec, 4)
        if len(samples) < args.repeats:
            out["repeats_dropped"] = args.repeats - len(samples)
    if args.rows < min(REFERENCE_CPU_ANCHORS):
        # sub-anchor scales extrapolate a cache-unfriendly per-row cost the
        # reference doesn't actually pay when the data fits in LLC
        out["vs_baseline_bound"] = "upper"

    # phase breakdown (telemetry): host phase wall times, trace/compile
    # attribution, and the kernel-route counters that record which
    # hist/partition kernels the compiled programs actually bake in —
    # the runtime answer to "did this run silently fall back to XLA?"
    out["phases"] = {
        "phase_times": {k: round(v, 4)
                        for k, v in sorted(snap["phase_times"].items())},
        "trace_times": {k: round(v, 4)
                        for k, v in sorted(snap["trace_times"].items())},
        "counters": dict(sorted(snap["counters"].items())),
    }

    # roofline + compile blocks (ISSUE 4): per-phase static program costs
    # (compiled.cost_analysis) joined to the measured spans — attained
    # FLOP/s, HBM GB/s, fraction-of-peak on TPU (peaks "unavailable"
    # elsewhere) — plus the compiled-program inventory (compile seconds,
    # persistent-cache hits, mid-run recompiles).  perf_gate tracks the
    # attained fractions across rounds next to the raw rates.
    if "roofline" in snap:
        out["roofline"] = snap["roofline"]
    if "compile" in snap:
        out["compile"] = snap["compile"]
    # interconnect block (ISSUE 5): per-collective-site logical bytes and
    # attained GB/s — present when a parallel learner's collective seams
    # were traced (multi-device runs); absent on serial runs
    if "interconnect" in snap:
        out["interconnect"] = snap["interconnect"]

    # memory trajectory (ISSUE 2): peak HBM watermark + dataset residency,
    # so BENCH_*.json rounds stop hand-measuring footprints (PROFILE.md)
    mem = snap.get("memory") or {}
    out["memory"] = {
        "peak_bytes_in_use": mem.get("peak_bytes_in_use", 0),
        "source": mem.get("source", "unavailable"),
        "residency": mem.get("residency", {}),
    }
    # health summary: anomaly count + NaN/saturation totals for the run
    # (health.HealthMonitor; nonzero anomalies invalidate a bench round)
    if health_summary is not None:
        out["health"] = {
            "anomalous_iterations": health_summary.get(
                "anomalous_iterations", 0),
            "grad_nan": health_summary.get("grad_nan", 0),
            "quant_sat": health_summary.get("quant_sat", 0),
            "score_max_abs": round(
                float(health_summary.get("score_max_abs", 0.0)), 4),
            "zero_gain_splits": health_summary.get("zero_gain_splits", 0),
        }

    print(json.dumps(_with_device(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
