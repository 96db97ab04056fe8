"""Configuration system.

Re-designs the reference's layered key=value config
(/root/reference/include/LightGBM/config.h:86-374, src/io/config.cpp:33-331)
as Python dataclasses.  Behavioral parity goals:

- same parameter names, aliases (config.h:301-374) and defaults,
- argv ``key=value`` pairs win over config-file lines (application.cpp:98),
- ``#`` comments in config files,
- the same conflict-resolution rules (config.cpp:133-182),
- typed getters that fail loudly on malformed values (config.h:246-299).

TPU additions: ``tree_learner`` keeps the reference's serial/feature/data
values; ``num_machines``/mesh setup maps to ``jax.sharding.Mesh`` axes rather
than socket/MPI ranks (see lightgbm_tpu/parallel/).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from .utils import log

# Alias table: reference config.h:301-374 (KeyAliasTransform).
ALIAS_TABLE: Dict[str, str] = {
    "config": "config_file",
    "nthread": "num_threads",
    "num_thread": "num_threads",
    "boosting": "boosting_type",
    "boost": "boosting_type",
    "application": "objective",
    "app": "objective",
    "train_data": "data",
    "train": "data",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "init_score": "input_init_score",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "valid": "valid_data",
    "test_data": "valid_data",
    "test": "valid_data",
    "is_sparse": "is_enable_sparse",
    "tranining_metric": "is_training_metric",
    "train_metric": "is_training_metric",
    "ndcg_at": "ndcg_eval_at",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "num_leaf": "num_leaves",
    "sub_feature": "feature_fraction",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_trees": "num_iterations",
    "num_rounds": "num_iterations",
    "sub_row": "bagging_fraction",
    "shrinkage_rate": "learning_rate",
    "tree": "tree_learner",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "two_round_loading": "use_two_round_loading",
    "two_round": "use_two_round_loading",
    "mlist": "machine_list_file",
    "is_save_binary": "is_save_binary_file",
    "save_binary": "is_save_binary_file",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "verbosity": "verbose",
    "header": "has_header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "query": "group_column",
    "query_column": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "topk": "top_k",
}


def apply_aliases(params: Dict[str, str]) -> Dict[str, str]:
    """KeyAliasTransform (config.h:302-373): canonical key wins on conflict."""
    out = dict(params)
    for key, value in params.items():
        canon = ALIAS_TABLE.get(key)
        if canon is not None and canon not in out:
            out[canon] = value
    return out


def _get_int(params, name, default):
    if name in params:
        try:
            return int(params[name])
        except ValueError:
            log.fatal("Parameter %s should be int type, passed is [%s]" % (name, params[name]))
    return default


def _get_float(params, name, default):
    if name in params:
        try:
            return float(params[name])
        except ValueError:
            log.fatal("Parameter %s should be double type, passed is [%s]" % (name, params[name]))
    return default


def _get_bool(params, name, default):
    if name in params:
        value = params[name].lower()
        if value in ("false", "-"):
            return False
        if value in ("true", "+"):
            return True
        log.fatal('Parameter %s should be "true"/"+" or "false"/"-", passed is [%s]'
                  % (name, params[name]))
    return default


def _get_str(params, name, default):
    return params.get(name, default)


@dataclasses.dataclass
class IOConfig:
    """Reference config.h:86-118."""
    max_bin: int = 256
    data_random_seed: int = 1
    data_filename: str = ""
    valid_data_filenames: List[str] = dataclasses.field(default_factory=list)
    output_model: str = "LightGBM_model.txt"
    # TPU extension (SURVEY §5.1): write a jax.profiler trace of the
    # training loop to this directory (view with tensorboard / xprof)
    profile_dir: str = ""
    # Telemetry (ISSUE 1): per-iteration JSONL metrics sink — one record
    # per boosting iteration with phase timings, kernel-route counters and
    # eval metrics (lightgbm_tpu/telemetry.py; pretty-print with
    # scripts/telemetry_report.py).  metrics_fence=true additionally
    # block_until_ready-fences phase spans so async dispatch doesn't
    # attribute device time to the wrong phase (timing-accuracy mode;
    # slows training, never issues extra dispatches)
    metrics_out: str = ""
    metrics_fence: bool = False
    # Memory gauges (ISSUE 2): sample device.memory_stats() at telemetry
    # span boundaries (per-phase byte deltas + peak bytes_in_use
    # watermark) and emit a ``memory`` block in the JSONL records plus a
    # one-shot dataset-residency report at train start.  "auto" (default)
    # = on whenever metrics_out is set; "true"/"false" force it.
    memory_stats: str = "auto"
    # Distributed observability (ISSUE 5): timeline mode writes one JSONL
    # shard PER PROCESS (``<metrics_out>.shard-<i>of<n>.jsonl``, headed
    # by a host/clock record) instead of a leader-only file — merge with
    # scripts/timeline_report.py.  "auto" = on for multi-process runs
    # whenever metrics_out is set; "true"/"false" force it.
    timeline: str = "auto"
    # Hung-collective flight recorder: with stall_timeout > 0 (seconds)
    # a watchdog thread dumps the recent span/collective event ring, the
    # in-flight phase/iteration and all thread stacks to the sink when
    # training makes no progress for that long — before the runtime's
    # own opaque dispatch watchdog kills the job.  0 disables.
    stall_timeout: float = 0.0
    # Flight recorder (ISSUE 16, lightgbm_tpu/tracing.py): the always-on
    # per-event tier under telemetry — per-request serving latency
    # attribution, training timeline events, streaming percentile
    # sketches.  trace_ring_events bounds the preallocated event ring
    # (drops oldest past it, counted ``trace/dropped``); matches
    # tracing.DEFAULT_RING_EVENTS — perf_gate treats drops at THIS
    # default as an absolute finding.
    trace_ring_events: int = 65536
    # trace_dump_dir: where ring dumps land as JSONL (atomic tmp+rename)
    # on clean close AND from the fault/crash paths; "" = no dumps.
    # Render/validate with scripts/trace_report.py.
    trace_dump_dir: str = ""
    # trace_sketch_growth: log-bucket growth factor of the percentile
    # sketches — quantiles are exact to within a factor sqrt(growth)
    trace_sketch_growth: float = 1.05
    # trace_run_id: operator-assigned run tag stamped into every trace
    # dump header.  podtrace/pod_report refuse to merge dumps with
    # mismatched run ids (mixing runs is a loud BadDump, never a
    # silently wrong merge); "" leaves dumps untagged.
    trace_run_id: str = ""
    # Live monitoring (ISSUE 20, lightgbm_tpu/monitor.py): windowed
    # metrics / SLO burn rate / score drift, layered on telemetry +
    # tracing.  monitor_out: JSONL file the emitter thread appends one
    # windowed snapshot per interval to (render/validate with
    # scripts/monitor_report.py); "" = monitor off unless an SLO is
    # declared.
    monitor_out: str = ""
    # monitor_interval_s: window length of the snapshot ring (seconds,
    # > 0) — each window carries exact counter and sketch DELTAS since
    # the previous one.
    monitor_interval_s: float = 1.0
    # slo_p99_us: declarative latency objective for the serving front's
    # serve_wall_us family — a p99 target grants a 1% error budget;
    # breach = fast short-window burn >= 5x AND slow long-window burn
    # >= 1x.  0 disables SLO tracking (predict-task only: there is no
    # serving latency to burn under task=train).
    slo_p99_us: float = 0.0
    # slo_window_s: the SLO error-budget window (seconds, > 0); the
    # fast window is 1/12 of it.
    slo_window_s: float = 60.0
    output_result: str = "LightGBM_predict_result.txt"
    input_model: str = ""
    input_init_score: str = ""
    verbosity: int = 1
    num_model_predict: int = -1
    # Compiled serving engine (ISSUE 7, lightgbm_tpu/serving.py).
    # predict_buckets: the CLOSED ladder of compiled batch shapes —
    # batches pad up to the smallest bucket that holds them (larger
    # inputs chunk at the biggest bucket), so steady-state serving never
    # sees a new program shape and never recompiles.
    predict_buckets: str = "1,32,1024,65536"
    # predict_quantize: "int8" serves an int8-quantized leaf-value table
    # (per-tree symmetric scale; quarter the table traffic — the
    # memory-bound-ensemble mode).  Routing stays exact either way; only
    # leaf VALUES are quantized.  "float32" is bit-equal to the
    # training-side scorer.
    predict_quantize: str = "float32"
    # predict_donate: donate the padded codes buffer to the compiled
    # program so steady-state serving recycles it in place.  "auto" = on
    # for accelerator backends, off on CPU (which ignores donation with a
    # per-call warning).
    predict_donate: str = "auto"
    # predict_algo: "bfs" walks all trees breadth-first in lockstep (one
    # gather-based level step per depth — O(max_depth) fused steps);
    # "scan" keeps the training-side per-tree replay (O(T·L) steps) as
    # the A/B reference bench.py's bench_predict lane prices.
    predict_algo: str = "bfs"
    # Distributed elastic serving (ISSUE 13, lightgbm_tpu/serving.py).
    # serve_shards: shard the flattened ensemble's [T, ...] node tables
    # contiguously along a 1-D ("tree",) device mesh — each device holds
    # ONLY its tree block (the 10k+-tree / multi-GB-ensemble regime one
    # HBM cannot hold); scores stay BIT-equal to the single-device
    # engine (f32 and int8).  0 = single-device; >1 must not exceed the
    # available devices (the engine rejects loudly, never shrinks).
    serve_shards: int = 0
    # predict_linger_us: the ServingFront's max coalescing wait — a
    # queued request is dispatched no later than this many microseconds
    # after the FIRST request of its batch arrived (sooner when a full
    # top-bucket batch is available).  0 = dispatch immediately (still
    # coalesces whatever is queued at pop time).
    predict_linger_us: int = 200
    # predict_queue: bound on in-flight serving work, in TOP-BUCKET
    # batches — the ServingFront's queue holds at most
    # predict_queue * max(predict_buckets) rows (submit blocks when
    # full: backpressure, never load shedding), and predict_file keeps
    # this many parsed chunks in flight ahead of the device.
    predict_queue: int = 4
    is_pre_partition: bool = False
    is_enable_sparse: bool = True
    # Streaming ingestion (ISSUE 8, lightgbm_tpu/io/streaming.py):
    # chunked parse→sample→bin with double-buffered host→device feeds —
    # bit-identical datasets/models to the resident loader, host memory
    # bounded by one chunk instead of the full unbinned matrix.  "auto"
    # (default) engages when the data (or cache) file is at least
    # streaming.AUTO_MIN_BYTES (256 MB); "true"/"false" force.
    # Supersedes use_two_round_loading when both apply.
    streaming: str = "auto"
    # parse/bin/transfer chunk length (rows) of the streaming loader —
    # also the bound on how many raw rows are ever host-resident
    ingest_chunk_rows: int = 200_000
    # parse worker processes of the streaming loader (ISSUE 18,
    # io/parallel_ingest.py): > 1 fans tokenize+bin out over byte-range
    # workers (bit-identical datasets); "auto" = cpu_count; 1 (default)
    # keeps the serial passes
    ingest_workers: int = 1
    use_two_round_loading: bool = False
    is_save_binary_file: bool = False
    # format of the is_save_binary_file cache: "native" (pickle header +
    # raw bin matrix) or "reference" — the reference's own .bin layout
    # (dataset.cpp:653-713), which its binary can train from directly
    save_binary_format: str = "native"
    is_sigmoid: bool = True
    has_header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""

    def predict_bucket_list(self) -> tuple:
        """The ``predict_buckets=`` ladder parsed and validated: sorted
        unique positive ints (the serving engine's compiled batch
        shapes)."""
        try:
            buckets = tuple(sorted({int(b) for b in
                                    self.predict_buckets.split(",") if b}))
        except ValueError:
            log.fatal("predict_buckets should be comma-separated ints, "
                      "passed is [%s]" % self.predict_buckets)
        log.check(bool(buckets) and buckets[0] >= 1,
                  "predict_buckets must contain positive ints")
        return buckets

    def memory_stats_enabled(self) -> bool:
        """The ``memory_stats=`` resolution rule, single-homed (cli.py and
        lgb.train both consult it): "auto" follows the sink — gauges on
        whenever ``metrics_out`` is set; "true"/"false" force it."""
        return (self.memory_stats == "true"
                or (self.memory_stats == "auto" and bool(self.metrics_out)))

    def timeline_enabled(self) -> bool:
        """The ``timeline=`` resolution rule, single-homed: "auto" = per-
        process shards on for TRUE multi-process runs with a sink (the
        exact case where a leader-only file hides every other host);
        "true" forces shard mode even single-process, "false" keeps the
        leader-only sink.  Consulted AFTER distributed init (cli.py), so
        process_count is final."""
        if self.timeline == "true":
            return True
        if self.timeline != "auto" or not self.metrics_out:
            return False
        try:
            import jax
            return jax.process_count() > 1
        except Exception:
            return False

    def set(self, params: Dict[str, str], require_data: bool = True) -> None:
        self.max_bin = _get_int(params, "max_bin", self.max_bin)
        log.check(self.max_bin > 0, "max_bin should be > 0")
        self.data_random_seed = _get_int(params, "data_random_seed", self.data_random_seed)
        if "data" in params:
            self.data_filename = params["data"]
        elif require_data:
            log.fatal("No training/prediction data, application quit")
        self.verbosity = _get_int(params, "verbose", self.verbosity)
        self.profile_dir = _get_str(params, "profile_dir", self.profile_dir)
        self.metrics_out = _get_str(params, "metrics_out", self.metrics_out)
        self.metrics_fence = _get_bool(params, "metrics_fence",
                                       self.metrics_fence)
        if "memory_stats" in params:
            value = params["memory_stats"].lower()
            log.check(value in ("auto", "true", "false"),
                      "memory_stats must be auto, true or false")
            self.memory_stats = value
        if "timeline" in params:
            value = params["timeline"].lower()
            log.check(value in ("auto", "true", "false"),
                      "timeline must be auto, true or false")
            self.timeline = value
        self.stall_timeout = _get_float(params, "stall_timeout",
                                        self.stall_timeout)
        log.check(self.stall_timeout >= 0.0,
                  "stall_timeout should be >= 0")
        self.trace_ring_events = _get_int(params, "trace_ring_events",
                                          self.trace_ring_events)
        log.check(self.trace_ring_events > 0,
                  "trace_ring_events should be > 0 (preallocated "
                  "flight-recorder ring slots)")
        if "trace_dump_dir" in params:
            self.trace_dump_dir = params["trace_dump_dir"]
            if self.trace_dump_dir:
                # loud reject at parse time (ISSUE 16): a dump dir that
                # cannot take writes would otherwise fail silently at
                # the one moment it matters — inside a crash dump
                try:
                    os.makedirs(self.trace_dump_dir, exist_ok=True)
                except OSError:
                    pass
                log.check(os.path.isdir(self.trace_dump_dir)
                          and os.access(self.trace_dump_dir, os.W_OK),
                          "trace_dump_dir must be a writable directory")
        self.trace_sketch_growth = _get_float(params, "trace_sketch_growth",
                                              self.trace_sketch_growth)
        log.check(1.0005 <= self.trace_sketch_growth <= 2.0,
                  "trace_sketch_growth should be in [1.0005, 2.0]")
        if "trace_run_id" in params:
            value = str(params["trace_run_id"])
            log.check(len(value) <= 128
                      and not any(c.isspace() for c in value),
                      "trace_run_id must be <= 128 chars with no "
                      "whitespace (it lands verbatim in dump headers "
                      "and report keys)")
            self.trace_run_id = value
        if "monitor_out" in params:
            self.monitor_out = params["monitor_out"]
            if self.monitor_out:
                # loud reject at parse time (ISSUE 20): an unwritable
                # monitor sink would otherwise fail silently at the one
                # moment it matters — inside a crash flush
                parent = os.path.dirname(self.monitor_out) or "."
                log.check(os.path.isdir(parent)
                          and os.access(parent, os.W_OK),
                          "monitor_out parent must be a writable "
                          "directory")
        self.monitor_interval_s = _get_float(params, "monitor_interval_s",
                                             self.monitor_interval_s)
        log.check(self.monitor_interval_s > 0.0,
                  "monitor_interval_s should be > 0 (the windowed-"
                  "snapshot interval)")
        self.slo_p99_us = _get_float(params, "slo_p99_us", self.slo_p99_us)
        log.check(self.slo_p99_us >= 0.0,
                  "slo_p99_us should be >= 0 (0 disables SLO tracking)")
        self.slo_window_s = _get_float(params, "slo_window_s",
                                       self.slo_window_s)
        log.check(self.slo_window_s > 0.0,
                  "slo_window_s should be > 0 (the error-budget window)")
        self.num_model_predict = _get_int(params, "num_model_predict", self.num_model_predict)
        self.predict_buckets = _get_str(params, "predict_buckets",
                                        self.predict_buckets)
        self.predict_bucket_list()  # validate eagerly: fail at parse time
        if "predict_quantize" in params:
            value = params["predict_quantize"].lower()
            log.check(value in ("float32", "int8"),
                      "predict_quantize must be float32 or int8")
            self.predict_quantize = value
        if "predict_donate" in params:
            value = params["predict_donate"].lower()
            log.check(value in ("auto", "true", "false"),
                      "predict_donate must be auto, true or false")
            self.predict_donate = value
        if "predict_algo" in params:
            value = params["predict_algo"].lower()
            log.check(value in ("bfs", "scan"),
                      "predict_algo must be bfs or scan")
            self.predict_algo = value
        self.serve_shards = _get_int(params, "serve_shards",
                                     self.serve_shards)
        log.check(self.serve_shards >= 0,
                  "serve_shards should be >= 0 (0 = single-device)")
        if self.serve_shards > 1 and self.predict_algo == "scan":
            log.fatal("serve_shards > 1 requires predict_algo=bfs (the "
                      "per-tree scan replay is a single-device A/B path)")
        self.predict_linger_us = _get_int(params, "predict_linger_us",
                                          self.predict_linger_us)
        log.check(self.predict_linger_us >= 0,
                  "predict_linger_us should be >= 0")
        self.predict_queue = _get_int(params, "predict_queue",
                                      self.predict_queue)
        log.check(self.predict_queue >= 1,
                  "predict_queue should be >= 1 (in-flight batches)")
        self.is_pre_partition = _get_bool(params, "is_pre_partition", self.is_pre_partition)
        self.is_enable_sparse = _get_bool(params, "is_enable_sparse", self.is_enable_sparse)
        if "streaming" in params:
            value = params["streaming"].lower()
            log.check(value in ("auto", "true", "false"),
                      "streaming must be auto, true or false")
            self.streaming = value
        self.ingest_chunk_rows = _get_int(params, "ingest_chunk_rows",
                                          self.ingest_chunk_rows)
        log.check(self.ingest_chunk_rows > 0,
                  "ingest_chunk_rows should be > 0")
        if str(params.get("ingest_workers", "")).lower() == "auto":
            self.ingest_workers = os.cpu_count() or 1
        else:
            self.ingest_workers = _get_int(params, "ingest_workers",
                                           self.ingest_workers)
        log.check(self.ingest_workers > 0,
                  "ingest_workers should be > 0 (or auto = cpu_count)")
        self.use_two_round_loading = _get_bool(params, "use_two_round_loading",
                                               self.use_two_round_loading)
        self.is_save_binary_file = _get_bool(params, "is_save_binary_file",
                                             self.is_save_binary_file)
        if "save_binary_format" in params:
            value = params["save_binary_format"].lower()
            log.check(value in ("native", "reference"),
                      "save_binary_format must be native or reference")
            self.save_binary_format = value
        self.is_sigmoid = _get_bool(params, "is_sigmoid", self.is_sigmoid)
        self.output_model = _get_str(params, "output_model", self.output_model)
        self.input_model = _get_str(params, "input_model", self.input_model)
        self.output_result = _get_str(params, "output_result", self.output_result)
        self.input_init_score = _get_str(params, "input_init_score", self.input_init_score)
        if "valid_data" in params:
            self.valid_data_filenames = [s for s in params["valid_data"].split(",") if s]
        self.has_header = _get_bool(params, "has_header", self.has_header)
        self.label_column = _get_str(params, "label_column", self.label_column)
        self.weight_column = _get_str(params, "weight_column", self.weight_column)
        self.group_column = _get_str(params, "group_column", self.group_column)
        self.ignore_column = _get_str(params, "ignore_column", self.ignore_column)


def _default_label_gain() -> List[float]:
    # label_gain = 2^i - 1 up to 31 labels (config.cpp:226-232).
    return [0.0] + [float((1 << i) - 1) for i in range(1, 31)]


@dataclasses.dataclass
class ObjectiveConfig:
    """Reference config.h:120-134."""
    sigmoid: float = 1.0
    label_gain: List[float] = dataclasses.field(default_factory=_default_label_gain)
    max_position: int = 20
    is_unbalance: bool = False
    num_class: int = 1

    def set(self, params: Dict[str, str]) -> None:
        self.is_unbalance = _get_bool(params, "is_unbalance", self.is_unbalance)
        self.sigmoid = _get_float(params, "sigmoid", self.sigmoid)
        self.max_position = _get_int(params, "max_position", self.max_position)
        log.check(self.max_position > 0, "max_position should be > 0")
        self.num_class = _get_int(params, "num_class", self.num_class)
        log.check(self.num_class >= 1, "num_class should be >= 1")
        if "label_gain" in params:
            self.label_gain = _parse_label_gain(params["label_gain"])


def _parse_label_gain(value: str) -> List[float]:
    """Loud-reject parse of the comma-separated label_gain list — a junk
    token used to surface as a bare ValueError traceback instead of the
    typed-getter fatal every other knob gets."""
    try:
        return [float(x) for x in value.split(",") if x]
    except ValueError:
        log.fatal("Parameter label_gain should be comma-separated "
                  "doubles, passed is [%s]" % value)


@dataclasses.dataclass
class MetricConfig:
    """Reference config.h:136-145."""
    num_class: int = 1
    sigmoid: float = 1.0
    label_gain: List[float] = dataclasses.field(default_factory=_default_label_gain)
    eval_at: List[int] = dataclasses.field(default_factory=lambda: [1, 2, 3, 4, 5])

    def set(self, params: Dict[str, str]) -> None:
        self.sigmoid = _get_float(params, "sigmoid", self.sigmoid)
        self.num_class = _get_int(params, "num_class", self.num_class)
        log.check(self.num_class >= 1, "num_class should be >= 1")
        if "label_gain" in params:
            self.label_gain = _parse_label_gain(params["label_gain"])
        if "ndcg_eval_at" in params:
            self.eval_at = sorted(int(x) for x in params["ndcg_eval_at"].split(",") if x)
            for k in self.eval_at:
                log.check(k > 0, "ndcg_eval_at should be > 0")


@dataclasses.dataclass
class TreeConfig:
    """Reference config.h:148-165."""
    min_data_in_leaf: int = 100
    min_sum_hessian_in_leaf: float = 10.0
    num_leaves: int = 127
    feature_fraction_seed: int = 2
    feature_fraction: float = 1.0
    histogram_pool_size: float = -1.0
    max_depth: int = -1
    # TPU-native extension (no reference equivalent): "leafwise" reproduces
    # the reference's strict best-first growth (serial_tree_learner.cpp:119-153);
    # "depthwise" grows level-batched for MXU throughput (grower_depthwise.py)
    grow_policy: str = "leafwise"
    # TPU tuning knobs (no reference equivalent): row-chunk length of the
    # histogram scan (0 = per-policy default) and the one-hot/value operand
    # dtype of the histogram matmul.  On TPU all three dtypes run
    # hand-scheduled Pallas MXU kernels (ops/hist_pallas.py): "float32"
    # rides a two-pass hi/lo bf16 operand split (~16 operand mantissa
    # bits, f32 accumulation — the closest-to-reference mode), "bfloat16"
    # a single pass (grad/hess rounded to 8 mantissa bits; ~2x f32 speed
    # at a fraction of int8's quantization error), "int8" the
    # quantized-gradient kernel on the int8 MXU — fastest, grad/hess
    # rounded to 1/127 of their max over the tree's rows; counts stay
    # exact in every mode.  hist_chunk tunes the XLA scan paths only; the Pallas kernels
    # use their own fixed VMEM block.
    # int8 past ~16.9M GLOBAL rows (one int32 accumulator: 127 x rows can
    # wrap past 2^31 when rows concentrate in one bin) sums every pass in
    # row ranges with an accumulator each, added exactly
    # (ops/hist_pallas.accum_ranges; a route that does not range refuses
    # loudly, check_int8_row_capacity there).
    hist_chunk: int = 0
    hist_dtype: str = "float32"
    # data-parallel histogram reduction schedule (TreeConfig extension):
    # "psum" allreduces the full [C,F,B,3] level histogram and searches
    # splits replicated; "reduce_scatter" is the reference's
    # bandwidth-optimal ownership schedule
    # (data_parallel_tree_learner.cpp:135-235) — psum_scatter the level
    # histograms by contiguous feature block, search only owned features,
    # and allreduce the packed SplitInfo: ~half the collective bytes and
    # 1/S of the split-search compute per level.  Applies to the fused
    # depthwise data-parallel chunk; identical trees either way.
    # "auto" resolves at learner creation: true multi-process runs take
    # reduce_scatter (the reference's N-machine mode IS that schedule);
    # single-process meshes keep psum (parallel/learners.py _schedule)
    dp_schedule: str = "auto"
    # compacted leaf-wise growth (TreeConfig extension, grow_policy=
    # leafwise, serial learner only): keep every leaf's rows physically
    # contiguous (the reference's DataPartition asymptotic,
    # data_partition.hpp:93-139, recast as data movement — see
    # models/grower_leafcompact.py) so each split histograms only the
    # smaller child's rows instead of sweeping all N.  "auto" (default)
    # = on when the backend is TPU, off elsewhere (keeps CPU-golden
    # tests on the masked grower); "true"/"false" force it.
    leafwise_compact: str = "auto"
    # mixed-bin feature packing (TreeConfig extension, ISSUE 6): partition
    # features into bin-width classes at Dataset-attach time (narrow:
    # num_bin <= 64 rides the measured-fast 64-wide kernel class; wide:
    # num_bins_max), reorder the bin matrix by class, and run one
    # histogram pass per class — split outputs are bit-identical to the
    # uniform single-pass path (per-class histograms are reassembled into
    # canonical feature order before split finding).  "auto"/"true" = on
    # whenever the dataset actually mixes narrow and wide features (a
    # single class collapses to the existing path); "false" = off.
    # LGBM_TPU_NO_MIXEDBIN=1 is the env A/B hatch.  The feature-parallel
    # learner keeps the uniform layout (its per-shard ownership slices
    # are arbitrary feature subsets).
    mixed_bin: str = "auto"
    # 2-D hybrid mesh factoring (ISSUE 9, tree_learner=hybrid|voting):
    # num_machines = data_shards x feature_shards.  0 = auto
    # (parallel/mesh.factor_machines: hybrid takes the largest divisor
    # <= sqrt(num_machines) as feature_shards, voting defaults to pure
    # data-parallel); a nonzero value must divide num_machines.
    feature_shards: int = 0
    # voting-parallel top-k (tree_learner=voting; the reference family's
    # ``top_k``/PV-tree parameter, default 20): each data shard proposes
    # its top_k features by local split gain, and full histograms are
    # exchanged only for the <= 2*top_k globally-voted features per
    # owned block.  Voting is exact whenever the voted set covers the
    # true best feature — guaranteed when 2*top_k >= features-per-block,
    # the reference's own accuracy argument otherwise.
    top_k: int = 20
    # int8 rounding mode: "nearest" (default) or "stochastic" — unbiased
    # floor(y+u) with deterministic value-keyed uniform bits
    # (ops/hist_pallas.stochastic_bits); preserves the serial==distributed
    # bit-identity because the key is the row's (grad, hess) values, not
    # its position
    quant_rounding: str = "nearest"

    def set(self, params: Dict[str, str]) -> None:
        self.min_data_in_leaf = _get_int(params, "min_data_in_leaf", self.min_data_in_leaf)
        self.min_sum_hessian_in_leaf = _get_float(params, "min_sum_hessian_in_leaf",
                                                  self.min_sum_hessian_in_leaf)
        log.check(self.min_sum_hessian_in_leaf > 1.0 or self.min_data_in_leaf > 0,
                  "min_sum_hessian_in_leaf/min_data_in_leaf check failed")
        self.num_leaves = _get_int(params, "num_leaves", self.num_leaves)
        log.check(self.num_leaves > 1, "num_leaves should be > 1")
        self.feature_fraction_seed = _get_int(params, "feature_fraction_seed",
                                              self.feature_fraction_seed)
        self.feature_fraction = _get_float(params, "feature_fraction", self.feature_fraction)
        log.check(0.0 < self.feature_fraction <= 1.0,
                  "feature_fraction should be in (0, 1]")
        self.histogram_pool_size = _get_float(params, "histogram_pool_size",
                                              self.histogram_pool_size)
        self.max_depth = _get_int(params, "max_depth", self.max_depth)
        log.check(self.max_depth > 1 or self.max_depth < 0,
                  "max_depth should be > 1 or < 0")
        if "grow_policy" in params:
            value = params["grow_policy"].lower()
            log.check(value in ("leafwise", "depthwise"),
                      "grow_policy must be leafwise or depthwise")
            self.grow_policy = value
        self.hist_chunk = _get_int(params, "hist_chunk", self.hist_chunk)
        log.check(self.hist_chunk >= 0, "hist_chunk should be >= 0")
        if "hist_dtype" in params:
            value = params["hist_dtype"].lower()
            log.check(value in ("float32", "bfloat16", "int8"),
                      "hist_dtype must be float32, bfloat16 or int8")
            self.hist_dtype = value
        if "leafwise_compact" in params:
            value = params["leafwise_compact"].lower()
            log.check(value in ("auto", "true", "false"),
                      "leafwise_compact must be auto, true or false")
            self.leafwise_compact = value
        if "dp_schedule" in params:
            value = params["dp_schedule"].lower()
            log.check(value in ("auto", "psum", "reduce_scatter"),
                      "dp_schedule must be auto, psum or reduce_scatter")
            self.dp_schedule = value
        if "mixed_bin" in params:
            value = params["mixed_bin"].lower()
            log.check(value in ("auto", "true", "false"),
                      "mixed_bin must be auto, true or false")
            self.mixed_bin = value
        self.feature_shards = _get_int(params, "feature_shards",
                                       self.feature_shards)
        log.check(self.feature_shards >= 0,
                  "feature_shards should be >= 0")
        self.top_k = _get_int(params, "top_k", self.top_k)
        log.check(self.top_k >= 1, "top_k should be >= 1")
        if "quant_rounding" in params:
            value = params["quant_rounding"].lower()
            log.check(value in ("nearest", "stochastic"),
                      "quant_rounding must be nearest or stochastic")
            self.quant_rounding = value
            if value == "stochastic" and self.hist_dtype != "int8":
                log.warning("quant_rounding=stochastic only applies to "
                            "hist_dtype=int8; ignored for %s"
                            % self.hist_dtype)


@dataclasses.dataclass
class BoostingConfig:
    """Reference config.h:173-199 (BoostingConfig + GBDTConfig)."""
    output_freq: int = 1
    is_provide_training_metric: bool = False
    num_iterations: int = 10
    learning_rate: float = 0.1
    bagging_fraction: float = 1.0
    bagging_seed: int = 3
    bagging_freq: int = 0
    early_stopping_round: int = 0
    num_class: int = 1
    tree_learner: str = "serial"
    # Training-health monitor (ISSUE 2, lightgbm_tpu/health.py): an
    # in-program health vector (NaN/Inf counts in gradients/hessians/raw
    # scores, int8 quantization saturation, score-magnitude watermark)
    # plus tree-derived counts (zero-gain splits, empty leaves), fetched
    # once per iteration and emitted as a ``health`` block in the JSONL
    # sink.  "auto" (default) = on whenever telemetry is armed
    # (metrics_out=); "true"/"false" force it.
    health: str = "auto"
    # policy on health anomalies (nonzero NaN/Inf counts, eval
    # divergence): "warn" logs once per anomaly kind, "halt" raises a
    # clean TrainingHealthError, "record" only writes the sink block
    on_anomaly: str = "warn"
    # eval-metric divergence detection: k consecutive worsening
    # iterations of any tracked metric flag an anomaly (0 = disabled)
    health_divergence_rounds: int = 0
    # pipelined boosting (ISSUE 6): "readback" double-buffers the next
    # iteration's (or chunk's) gradient/histogram dispatch against the
    # current model readback — the device math is dispatched in exactly
    # the per-iteration order, only HOST WAITS move, so trees/scores/
    # metric values are exact-identical (tests/test_pipeline.py).  "off"
    # keeps the strictly synchronous loop.  "auto" = readback inside
    # run_training for single-process runs without an in-loop checkpoint
    # callback (a save_fn must see every finished tree, so the CLI's
    # incremental output_model saves keep auto synchronous; direct
    # train_one_iter / train_chunk callers keep synchronous semantics
    # unless they opt in explicitly); multi-process runs stay off.
    # LGBM_TPU_PIPELINE overrides for A/B timing.
    pipeline: str = "auto"
    # Device-side bagging (ISSUE 8, lightgbm_tpu/ops/sampling.py): draw
    # the in-bag mask on-device (one threefry key per redraw) instead of
    # a host numpy draw plus a full-N mask upload every bagging_freq
    # iterations.  Exact in-bag count like the host path; the RNG STREAM
    # differs (threefry vs MT19937), so trained trees differ from the
    # host path by the sampling draw only.  "auto" = on for accelerator
    # backends in single-process, no-query runs; "true"/"false" force
    # (true still falls back — with a warning — where the device draw
    # cannot apply: multi-process shards, per-query bagging).
    # LGBM_TPU_HOST_BAGGING=1 is the env A/B hatch back to the host path.
    bagging_device: str = "auto"
    # GOSS — gradient-based one-side sampling (ISSUE 8; the headline
    # trick of the later LightGBM paper): each iteration keeps the
    # top_rate fraction of rows by gradient magnitude plus an other_rate
    # fraction of the remainder sampled uniformly, amplifying the
    # sampled remainder's gradients AND hessians by
    # (1-top_rate)/other_rate.  The selection runs entirely on device
    # and feeds the histogram kernels through the row-mask seam.
    # Incompatible with bagging (the reference family's rule) and with
    # multi-process training in this revision.
    goss: bool = False
    top_rate: float = 0.2
    other_rate: float = 0.1
    # Preemption-safe training (ISSUE 14, lightgbm_tpu/checkpoint.py):
    # checkpoint_interval > 0 makes run_training write an atomic
    # checkpoint file (model + sampler/RNG counters + iteration +
    # best_score/best_iter + config fingerprint) every that-many
    # consumed iterations, on a background writer thread OFF the
    # pipelined readback path — plus one synchronous final checkpoint.
    # A task=train restart with the same checkpoint_dir resumes from the
    # latest checkpoint: bit-identically on the same topology, at the
    # documented cross-schedule budget on a different one (elastic
    # restart re-runs factor_machines on the surviving machine count).
    # 0 disables.  checkpoint_dir must be set when the interval is;
    # checkpoint_keep (>= 1) bounds how many finished checkpoint files
    # are retained (the atomic write-temp+rename discipline means a
    # crash mid-write always leaves the previous one loadable).
    checkpoint_interval: int = 0
    checkpoint_dir: str = ""
    checkpoint_keep: int = 2
    # Live straggler mitigation (ISSUE 14, lightgbm_tpu/elastic.py):
    # elastic_shrink=true arms the drain-at-iteration-boundary mesh
    # shrink — when the persistent-straggler rule (the SAME
    # strictly-slowest->=straggler_k-consecutive-iterations logic
    # scripts/timeline_report.py flags post-mortem) fires, the trainer
    # checkpoints, drops the flagged slot, re-runs factor_machines on
    # the surviving machine count and resumes.  Requires a parallel
    # tree_learner (there is no mesh to shrink under serial).
    elastic_shrink: bool = False
    straggler_k: int = 3
    tree_config: TreeConfig = dataclasses.field(default_factory=TreeConfig)

    def set(self, params: Dict[str, str]) -> None:
        self.num_iterations = _get_int(params, "num_iterations", self.num_iterations)
        log.check(self.num_iterations >= 0, "num_iterations should be >= 0")
        self.bagging_seed = _get_int(params, "bagging_seed", self.bagging_seed)
        self.bagging_freq = _get_int(params, "bagging_freq", self.bagging_freq)
        log.check(self.bagging_freq >= 0, "bagging_freq should be >= 0")
        self.bagging_fraction = _get_float(params, "bagging_fraction", self.bagging_fraction)
        log.check(0.0 < self.bagging_fraction <= 1.0,
                  "bagging_fraction should be in (0, 1]")
        self.learning_rate = _get_float(params, "learning_rate", self.learning_rate)
        log.check(self.learning_rate > 0.0, "learning_rate should be > 0")
        self.early_stopping_round = _get_int(params, "early_stopping_round",
                                             self.early_stopping_round)
        log.check(self.early_stopping_round >= 0, "early_stopping_round should be >= 0")
        self.output_freq = _get_int(params, "metric_freq", self.output_freq)
        log.check(self.output_freq >= 0, "metric_freq should be >= 0")
        self.is_provide_training_metric = _get_bool(params, "is_training_metric",
                                                    self.is_provide_training_metric)
        self.num_class = _get_int(params, "num_class", self.num_class)
        log.check(self.num_class >= 1, "num_class should be >= 1")
        if "health" in params:
            value = params["health"].lower()
            log.check(value in ("auto", "true", "false"),
                      "health must be auto, true or false")
            self.health = value
        if "on_anomaly" in params:
            value = params["on_anomaly"].lower()
            log.check(value in ("warn", "halt", "record"),
                      "on_anomaly must be warn, halt or record")
            self.on_anomaly = value
        self.health_divergence_rounds = _get_int(
            params, "health_divergence_rounds", self.health_divergence_rounds)
        log.check(self.health_divergence_rounds >= 0,
                  "health_divergence_rounds should be >= 0")
        if "pipeline" in params:
            value = params["pipeline"].lower()
            log.check(value in ("auto", "off", "readback"),
                      "pipeline must be auto, off or readback")
            self.pipeline = value
        if "bagging_device" in params:
            value = params["bagging_device"].lower()
            log.check(value in ("auto", "true", "false"),
                      "bagging_device must be auto, true or false")
            self.bagging_device = value
        self.goss = _get_bool(params, "goss", self.goss)
        self.top_rate = _get_float(params, "top_rate", self.top_rate)
        self.other_rate = _get_float(params, "other_rate", self.other_rate)
        if self.goss:
            log.check(0.0 <= self.top_rate < 1.0,
                      "top_rate should be in [0, 1)")
            log.check(0.0 < self.other_rate <= 1.0,
                      "other_rate should be in (0, 1]")
            log.check(self.top_rate + self.other_rate <= 1.0,
                      "top_rate + other_rate should be <= 1")
            if self.bagging_fraction < 1.0 and self.bagging_freq > 0:
                log.fatal("Cannot use bagging in GOSS mode "
                          "(goss=true with bagging_fraction < 1)")
        self.checkpoint_interval = _get_int(params, "checkpoint_interval",
                                            self.checkpoint_interval)
        log.check(self.checkpoint_interval >= 0,
                  "checkpoint_interval should be >= 0 (0 disables)")
        self.checkpoint_dir = _get_str(params, "checkpoint_dir",
                                       self.checkpoint_dir)
        if self.checkpoint_interval > 0 and not self.checkpoint_dir:
            log.fatal("checkpoint_interval > 0 requires checkpoint_dir "
                      "(where should the checkpoints go?)")
        self.checkpoint_keep = _get_int(params, "checkpoint_keep",
                                        self.checkpoint_keep)
        log.check(self.checkpoint_keep >= 1,
                  "checkpoint_keep should be >= 1 (the latest checkpoint "
                  "must survive)")
        self.elastic_shrink = _get_bool(params, "elastic_shrink",
                                        self.elastic_shrink)
        self.straggler_k = _get_int(params, "straggler_k", self.straggler_k)
        log.check(self.straggler_k >= 1, "straggler_k should be >= 1")
        if "tree_learner" in params:
            value = params["tree_learner"].lower()
            if value == "serial":
                self.tree_learner = "serial"
            elif value in ("feature", "feature_parallel"):
                self.tree_learner = "feature"
            elif value in ("data", "data_parallel"):
                self.tree_learner = "data"
            elif value == "hybrid":
                # 2-D (data, feature) mesh: rows sharded on ``data``,
                # feature-block ownership on ``feature`` (ISSUE 9)
                self.tree_learner = "hybrid"
            elif value in ("voting", "voting_parallel"):
                # the reference NAMES voting but Fatals on it
                # (src/io/config.cpp:311-313); here it is realized: top-k
                # per-shard split voting, full histograms exchanged only
                # for the voted features (ISSUE 9)
                self.tree_learner = "voting"
            else:
                log.fatal("Tree learner type error")
        self.tree_config.set(params)


@dataclasses.dataclass
class NetworkConfig:
    """Reference config.h:201-209.

    On TPU the machine list / listen port map to ``jax.distributed`` process
    bootstrap; ``num_machines`` becomes the size of the mesh axis used by the
    parallel tree learners.
    """
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""

    def set(self, params: Dict[str, str]) -> None:
        self.num_machines = _get_int(params, "num_machines", self.num_machines)
        log.check(self.num_machines >= 1, "num_machines should be >= 1")
        self.local_listen_port = _get_int(params, "local_listen_port", self.local_listen_port)
        log.check(self.local_listen_port > 0, "local_listen_port should be > 0")
        self.time_out = _get_int(params, "time_out", self.time_out)
        log.check(self.time_out > 0, "time_out should be > 0")
        self.machine_list_filename = _get_str(params, "machine_list_file",
                                              self.machine_list_filename)


@dataclasses.dataclass
class OverallConfig:
    """Reference config.h:212-243 + config.cpp:33-182."""
    task_type: str = "train"
    num_threads: int = 0
    is_parallel: bool = False
    is_parallel_find_bin: bool = False
    predict_leaf_index: bool = False
    boosting_type: str = "gbdt"
    objective_type: str = "regression"
    metric_types: List[str] = dataclasses.field(default_factory=list)
    network_config: NetworkConfig = dataclasses.field(default_factory=NetworkConfig)
    io_config: IOConfig = dataclasses.field(default_factory=IOConfig)
    boosting_config: BoostingConfig = dataclasses.field(default_factory=BoostingConfig)
    objective_config: ObjectiveConfig = dataclasses.field(default_factory=ObjectiveConfig)
    metric_config: MetricConfig = dataclasses.field(default_factory=MetricConfig)
    # TPU addition: device placement for the tree learner ("tpu"/"cpu"; any
    # value accepted, resolved against jax.devices()).
    device_type: str = ""

    def set(self, params: Dict[str, str], require_data: bool = True) -> None:
        params = apply_aliases(params)
        from .cli import KNOB_INVENTORY
        for key in params:
            # a key nothing reads (a typo, a knob since removed) trains
            # as if it were absent: say so
            if (key not in KNOB_INVENTORY and key not in ALIAS_TABLE
                    and key != "config_file"):
                log.warning("Unknown parameter %s" % key)
        self.num_threads = _get_int(params, "num_threads", self.num_threads)
        if "task" in params:
            value = params["task"].lower()
            if value in ("train", "training"):
                self.task_type = "train"
            elif value in ("predict", "prediction", "test"):
                self.task_type = "predict"
            else:
                log.fatal("Task type error")
        self.predict_leaf_index = _get_bool(params, "predict_leaf_index",
                                            self.predict_leaf_index)
        if "boosting_type" in params:
            value = params["boosting_type"].lower()
            if value in ("gbdt", "gbrt"):
                self.boosting_type = "gbdt"
            else:
                log.fatal("Boosting type %s error" % value)
        if "objective" in params:
            self.objective_type = params["objective"].lower()
        if "metric" in params:
            seen = []
            for m in params["metric"].lower().split(","):
                m = m.strip()
                if m and m not in seen:
                    seen.append(m)
            self.metric_types = seen
        self.device_type = _get_str(params, "device_type", self.device_type)
        self.network_config.set(params)
        self.io_config.set(params, require_data=require_data)
        self.boosting_config.set(params)
        self.objective_config.set(params)
        self.metric_config.set(params)
        self._check_param_conflict()
        # verbosity → log level (config.cpp:59-70); the mapping lives in
        # utils/log so the CLI and library entries share one rule
        log.set_level_from_verbosity(self.io_config.verbosity)

    def _check_param_conflict(self) -> None:
        """Reference config.cpp:133-182."""
        objective_multiclass = self.objective_type == "multiclass"
        num_class = self.boosting_config.num_class
        if objective_multiclass:
            if num_class <= 1:
                log.fatal("You should specify number of class(>=2) for multiclass training.")
        else:
            if self.task_type == "train" and num_class != 1:
                log.fatal("Number of class must be 1 for non-multiclass training.")
        for metric_type in self.metric_types:
            metric_multiclass = metric_type in ("multi_logloss", "multi_error")
            if objective_multiclass != metric_multiclass:
                log.fatal("Objective and metrics don't match.")
        if self.network_config.num_machines > 1:
            self.is_parallel = True
        else:
            self.is_parallel = False
            self.boosting_config.tree_learner = "serial"
        if self.boosting_config.tree_learner == "serial":
            self.is_parallel = False
            self.network_config.num_machines = 1
        if self.boosting_config.elastic_shrink and not self.is_parallel:
            log.fatal("elastic_shrink=true requires a parallel "
                      "tree_learner and num_machines > 1 (there is no "
                      "mesh to shrink under serial training)")
        if self.io_config.slo_p99_us > 0 and self.task_type != "predict":
            log.fatal("slo_p99_us > 0 requires task=predict (the SLO "
                      "watches the serving front's serve_wall_us "
                      "family; a training run has no serving latency "
                      "to burn)")
        if self.boosting_config.tree_learner in ("serial", "feature"):
            self.is_parallel_find_bin = False
        elif self.boosting_config.tree_learner in ("data", "hybrid",
                                                   "voting"):
            # hybrid/voting shard rows over the data axis exactly like
            # tree_learner=data, so they take the same distributed bin
            # finding + LRU-queue-off treatment
            self.is_parallel_find_bin = True
            if self.boosting_config.tree_config.histogram_pool_size >= 0:
                log.warning(
                    "Histogram LRU queue was enabled (histogram_pool_size=%f). "
                    "Will disable this for reducing communication cost."
                    % self.boosting_config.tree_config.histogram_pool_size)
                self.boosting_config.tree_config.histogram_pool_size = -1


def parse_config_file(path: str) -> Dict[str, str]:
    """Parse a .conf file: ``key = value`` lines, ``#`` comments
    (application.cpp:78-113)."""
    params: Dict[str, str] = {}
    with open(path, "r") as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            if "=" not in line:
                continue
            key, value = line.split("=", 1)
            key = key.strip().strip('"').strip("'")
            value = value.strip().strip('"').strip("'")
            if key:
                params[key] = value
    return params


def parse_argv(args: List[str]) -> Dict[str, str]:
    """Parse CLI ``key=value`` tokens (application.cpp:59-76)."""
    params: Dict[str, str] = {}
    for arg in args:
        if "=" not in arg:
            log.warning("Unknown parameter %s" % arg)
            continue
        key, value = arg.split("=", 1)
        key = key.strip().strip('"').strip("'")
        value = value.strip().strip('"').strip("'")
        if key:
            params[key] = value
    return params


def load_config(argv: List[str]) -> OverallConfig:
    """argv pairs + optional config file; argv wins (application.cpp:98)."""
    cli_params = parse_argv(argv)
    cli_params = apply_aliases(cli_params)
    params: Dict[str, str] = {}
    if "config_file" in cli_params:
        params.update(parse_config_file(cli_params["config_file"]))
    # argv has higher priority
    params.update(cli_params)
    config = OverallConfig()
    config.set(params)
    return config
