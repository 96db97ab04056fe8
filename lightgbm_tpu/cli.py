"""CLI application driver: ``lightgbm-tpu key=value ... [config=train.conf]``.

Re-design of /root/reference/src/application/application.cpp:28-302 and
src/main.cpp.  Same surface: ``task=train|predict``, config files from
examples/ run unchanged (only ``device_type`` is TPU-specific and optional).
Distributed runs replace socket/MPI bootstrap (application.cpp:202-205) with
jax.distributed + a device mesh (lightgbm_tpu/parallel/).

TPU-native training knobs beyond the reference surface (all parsed as
ordinary ``key=value`` options, see config.py for semantics):
``grow_policy``, ``hist_dtype``, ``hist_chunk``, ``dp_schedule``,
``leafwise_compact``, ``quant_rounding``,
``mixed_bin`` (per-bin-width-class histogram passes, ISSUE 6) and
``pipeline`` (deferred-readback boosting, ISSUE 6).  ``grow_policy`` and
``hist_dtype`` are documented accuracy/order trades; all the others are
model-invariant — flipping them changes speed, never trees.

Serving knobs (``task=predict``, ISSUE 7 — lightgbm_tpu/serving.py):
``predict_buckets`` (the compiled batch-shape ladder, default
``1,32,1024,65536``; pad-to-bucket keeps steady-state serving at zero
recompiles), ``predict_quantize`` (``float32`` = bit-equal to the
training-side scorer; ``int8`` = quantized leaf values at a quarter of
the table traffic — routing stays exact), ``predict_donate`` (donate the
codes buffer; ``auto`` = accelerators only) and ``predict_algo``
(``bfs`` lockstep breadth-first walk, ``scan`` = legacy per-tree replay
for A/B).  All four are score transforms of the SAME model — only
``predict_quantize=int8`` changes values, by the documented quantization
step.

Distributed elastic serving knobs (ISSUE 13 — same module):
``serve_shards`` shards the flattened ensemble's [T, ...] node tables
contiguously over a 1-D ``("tree",)`` device mesh (0 = single-device;
>1 must not exceed the available devices — loud reject, never a silent
shrink); sharded scores stay BIT-equal to the single-device engine
(f32 and int8) via the canonical-order carry chain + one masked psum
(``serve/tree_psum``).  ``predict_linger_us`` is the cross-request
coalescing front's max linger (a queued request dispatches at latest
this long after its batch's first arrival; 0 = immediately) and
``predict_queue`` bounds in-flight work in top-bucket batches (the
front's queue blocks when full — backpressure, never load shedding —
and ``predict_file`` keeps that many parsed chunks in flight).  All
three are score-invariant: they change latency/placement, never a
result bit (``predict_algo=scan`` composes with none of them beyond
``serve_shards=0`` — the replay is the single-device A/B).

Parallel-training knobs (ISSUE 9 — lightgbm_tpu/parallel/):
``tree_learner`` now spans ``serial|feature|data|hybrid|voting``.
``hybrid`` trains on an explicit 2-D ``(data, feature)`` mesh —
``num_machines = data_shards × feature_shards``, rows sharded on
``data``, feature-block ownership on ``feature``, per-shard histogram
wire bytes cut by ``feature_shards`` — and ``voting`` realizes the
reference's named-but-absent PV-tree mode (top-k per-shard split
voting; full histograms exchanged only for the ≤2·top_k voted
features).  ``feature_shards`` (0 = auto-factor; nonzero must divide
``num_machines``) picks the mesh factoring and ``top_k`` (default 20)
the vote width.  Both learners hold the repo's standing equivalence
bar vs serial (int8 bit-identical; f32 tie-keyed) — voting is exact
whenever 2·top_k covers the owned block, the PV-tree approximation
beyond that.

Streaming ingestion & on-device sampling knobs (ISSUE 8 —
lightgbm_tpu/io/streaming.py + ops/sampling.py): ``streaming``
(``auto`` engages the chunked parse→bin→HBM loader for files ≥256 MB;
``true``/``false`` force — datasets/models are bit-identical either
way), ``ingest_chunk_rows`` (the parse/bin/transfer chunk length, and
the bound on host-resident raw rows; default 200k),
``bagging_device`` (``auto`` draws bagging masks on-device on
accelerator backends — a redraw becomes a threefry key bump instead of
a host full-N draw + upload; the RNG STREAM differs from the host
path, so trees differ by the sampling draw only;
``LGBM_TPU_HOST_BAGGING=1`` is the A/B hatch) and ``goss`` +
``top_rate``/``other_rate`` (gradient-based one-side sampling, run
entirely on device; incompatible with bagging; traced INSIDE the fused
chunk programs since ISSUE 12 — sampled iterations keep the fused-k
dispatch on serial, data/hybrid/voting and feature-parallel learners,
and multi-process GOSS is supported on the chunk path,
grow_policy=depthwise).  ``mixed_bin`` composes with
``tree_learner=hybrid|voting`` via the block-local layout (the class
permutation never crosses an ownership block boundary; degenerates to
uniform, with a warning under ``mixed_bin=true``, when an ownership
block has no narrow feature).
``streaming``/``ingest_chunk_rows``/``bagging_device`` are
model-invariant; ``goss`` changes the trained model by design.

Preemption-safe elastic training knobs (ISSUE 14 —
lightgbm_tpu/checkpoint.py + elastic.py): ``checkpoint_interval``
(iterations between asynchronous atomic checkpoints; 0 = off; > 0
REQUIRES ``checkpoint_dir`` — loud reject otherwise) and
``checkpoint_dir`` (where the ``ckpt-<iter>.json`` files live; a
``task=train`` restart pointing at a dir holding a checkpoint RESUMES
from the latest one: bit-identical continuation — model text, scores,
RNG streams — on the same topology, the documented cross-schedule
budgets on a different ``num_machines``, where ``factor_machines``
re-runs on the surviving count and the binary cache re-shards through
the streaming loader).  ``checkpoint_keep`` (>= 1, loud reject at 0)
bounds retained checkpoint files; the write-temp+rename discipline
guarantees a crash mid-write leaves the previous checkpoint loadable.
``elastic_shrink`` (true/false; requires a parallel ``tree_learner`` —
loud reject under serial) arms the live straggler policy: the
persistent-straggler rule (same implementation as
scripts/timeline_report.py, ``straggler_k`` >= 1 consecutive
strictly-slowest iterations) triggers a drain-at-iteration-boundary
mesh shrink — checkpoint, drop the flagged slot, re-factor, resume.
``checkpoint_*`` knobs are model-invariant (a resumed run reproduces
the uninterrupted one); ``elastic_shrink`` changes topology mid-run and
therefore lands in the same cross-schedule budget class as choosing
that topology at startup.  ``LGBM_TPU_FAULT_AT=<iter>[,<kind>]``
(lightgbm_tpu/faults.py) is the test/harness hatch that kills or stalls
the designated process at an iteration boundary.
"""
from __future__ import annotations

import sys
import time
from typing import List

# --------------------------------------------------------------------------
# THE machine-readable knob inventory (ISSUE 15): one entry per canonical
# ``key=value`` parameter any *Config.set reads (aliases resolve through
# config.ALIAS_TABLE first).  graftlint D3 (analysis/drift_rules.py)
# cross-checks this dict against config.py both ways — a knob parsed but
# undocumented here, or an entry here nothing parses, fails the pre-merge
# gate — so the CLI surface can no longer drift by convention.  Keep the
# values one line: they are the --help-style summary; full semantics live
# on the config.py field comments.

KNOB_INVENTORY = {
    # task / component selection
    "task": "train or predict",
    "boosting_type": "gbdt (gbrt alias)",
    "objective": "objective name (regression/binary/multiclass/lambdarank)",
    "metric": "comma list of eval metric names",
    "device_type": "device selector resolved against jax.devices()",
    "num_threads": "native OpenMP host-path thread count",
    "predict_leaf_index": "predict per-tree leaf indices instead of scores",
    # IO / data
    "data": "training (or predict-input) data file",
    "valid_data": "comma list of validation data files",
    "max_bin": "max bins per feature",
    "data_random_seed": "binning-sample / shard-draw seed",
    "verbose": "log verbosity (-1 fatal .. 2 debug)",
    "has_header": "first data line is a header",
    "label_column": "label column selector",
    "weight_column": "weight column selector",
    "group_column": "query/group column selector",
    "ignore_column": "columns to drop",
    "is_pre_partition": "data files are pre-partitioned per machine",
    "is_enable_sparse": "reference sparse-format toggle (kept for parity)",
    "use_two_round_loading": "reference two-round loader (superseded by "
                             "streaming)",
    "is_save_binary_file": "write a binary dataset cache beside the data",
    "save_binary_format": "native or reference cache layout",
    "streaming": "auto/true/false chunked parse→bin→HBM loader",
    "ingest_chunk_rows": "streaming chunk length (host-resident row bound)",
    "ingest_workers": "byte-range parse worker processes (auto = cpu_count)",
    "output_model": "trained model output path",
    "input_model": "model to continue training from / predict with",
    "input_init_score": "initial-score side file",
    "output_result": "prediction output path",
    "num_model_predict": "how many trees predict uses (-1 = all)",
    "is_sigmoid": "apply sigmoid to binary predict output",
    # observability
    "profile_dir": "jax.profiler trace output directory",
    "metrics_out": "per-iteration JSONL telemetry sink path",
    "metrics_fence": "block_until_ready-fence phase spans",
    "memory_stats": "auto/true/false device-memory gauges",
    "timeline": "auto/true/false per-process JSONL shards",
    "stall_timeout": "hung-collective flight-recorder timeout (seconds)",
    "trace_ring_events": "flight-recorder event-ring slots (drops oldest)",
    "trace_dump_dir": "flight-recorder JSONL dump dir (close + fault)",
    "trace_sketch_growth": "latency-sketch log-bucket growth factor",
    "trace_run_id": "run tag in dump headers (podtrace merge key)",
    "monitor_out": "live-monitor windowed-snapshot JSONL path",
    "monitor_interval_s": "windowed-snapshot interval (seconds, > 0)",
    "slo_p99_us": "serve p99 latency objective (0 = SLO tracking off)",
    "slo_window_s": "SLO error-budget window (seconds, > 0)",
    # serving
    "predict_buckets": "compiled batch-shape ladder (comma ints)",
    "predict_quantize": "float32 or int8 leaf-value serving tables",
    "predict_donate": "auto/true/false codes-buffer donation",
    "predict_algo": "bfs lockstep walk or scan per-tree replay (A/B)",
    "serve_shards": "tree-axis ensemble shards (0 = single device)",
    "predict_linger_us": "coalescing front max linger (microseconds)",
    "predict_queue": "in-flight bound, in top-bucket batches",
    # tree growth
    "min_data_in_leaf": "min rows per leaf",
    "min_sum_hessian_in_leaf": "min hessian mass per leaf",
    "num_leaves": "max leaves per tree",
    "max_depth": "max tree depth (<0 = unlimited)",
    "feature_fraction": "per-tree feature subsample fraction",
    "feature_fraction_seed": "feature-fraction RNG seed",
    "histogram_pool_size": "reference LRU histogram pool (disabled "
                           "distributed)",
    "grow_policy": "leafwise best-first or depthwise level-batched",
    "hist_chunk": "XLA histogram scan row-chunk (0 = per-policy default)",
    "hist_dtype": "float32/bfloat16/int8 histogram operand dtype",
    "dp_schedule": "auto/psum/reduce_scatter DP reduction schedule",
    "leafwise_compact": "auto/true/false contiguous-leaf growth",
    "mixed_bin": "auto/true/false per-bin-width-class histogram passes",
    "feature_shards": "2-D mesh feature-axis factor (0 = auto)",
    "top_k": "voting-parallel per-shard vote width",
    "quant_rounding": "nearest or stochastic int8 gradient rounding",
    # boosting loop
    "num_iterations": "boosting iteration budget",
    "learning_rate": "shrinkage rate",
    "bagging_fraction": "row subsample fraction",
    "bagging_freq": "iterations between bagging redraws (0 = off)",
    "bagging_seed": "bagging RNG seed",
    "bagging_device": "auto/true/false on-device bagging draws",
    "goss": "gradient-based one-side sampling",
    "top_rate": "GOSS top-gradient keep fraction",
    "other_rate": "GOSS remainder sample fraction",
    "early_stopping_round": "rounds without improvement before stop",
    "metric_freq": "iterations between metric output lines",
    "is_training_metric": "also evaluate metrics on the training set",
    "num_class": "number of classes (multiclass)",
    "sigmoid": "sigmoid steepness (binary objective/metric)",
    "is_unbalance": "unbalanced-label weighting (binary)",
    "label_gain": "per-label gain table (lambdarank)",
    "max_position": "NDCG truncation position (lambdarank)",
    "ndcg_eval_at": "NDCG eval positions",
    # health monitor
    "health": "auto/true/false training-health monitor",
    "on_anomaly": "warn/halt/record anomaly policy",
    "health_divergence_rounds": "consecutive worsening rounds that flag "
                                "divergence (0 = off)",
    # pipelining / checkpoints / elasticity
    "pipeline": "auto/off/readback deferred-readback boosting",
    "checkpoint_interval": "iterations between async checkpoints (0 = off)",
    "checkpoint_dir": "checkpoint directory (required when interval > 0)",
    "checkpoint_keep": "retained checkpoint files (>= 1)",
    "elastic_shrink": "live straggler mesh-shrink policy",
    "straggler_k": "consecutive strictly-slowest iterations that flag a "
                   "straggler",
    # distributed
    "tree_learner": "serial/feature/data/hybrid/voting",
    "num_machines": "machine (mesh-slot) count",
    "local_listen_port": "reference networking option (parity)",
    "time_out": "reference networking timeout (parity)",
    "machine_list_file": "reference machine list (parity; TPU bootstrap "
                         "uses env hatches)",
}

from . import config as config_mod
from . import telemetry, tracing
from .config import OverallConfig
from .io.dataset import Dataset
from .metrics import create_metric
from .models.gbdt import GBDT
from .models.predictor import Predictor
from .objectives import create_objective
from .utils import log


class Application:
    def __init__(self, argv: List[str]):
        self.config = config_mod.load_config(argv)
        # set number of threads for the native OpenMP host paths
        # (Application::Application, application.cpp:30-34)
        if self.config.num_threads > 0:
            from .native import lib as native_lib
            native_lib.set_num_threads(self.config.num_threads)
        io = self.config.io_config
        # memory gauges resolve "auto" → on whenever a sink is configured
        # (memory_stats=true arms them standalone, snapshot()-only)
        mem_on = io.memory_stats_enabled()
        if io.metrics_out or mem_on:
            telemetry.enable(io.metrics_out or None,
                             fence=io.metrics_fence, memory=mem_on,
                             # timeline="auto" resolves again after
                             # distributed init (init_train); a forced
                             # "true" arms shard mode immediately
                             timeline=(io.timeline == "true"))
            telemetry.reset()
            # flight recorder (ISSUE 16): always-on under the telemetry
            # session — bounded by the preallocated ring, disarmed (and
            # dumped, when trace_dump_dir is set) by telemetry.disable()
            tracing.set_identity(run_id=io.trace_run_id)
            tracing.arm(ring_events=io.trace_ring_events,
                        dump_dir=io.trace_dump_dir or None,
                        sketch_growth=io.trace_sketch_growth)
            log.debug("telemetry armed: metrics_out=%s fence=%s memory=%s "
                      "timeline=%s trace_ring=%d"
                      % (io.metrics_out, io.metrics_fence, mem_on,
                         io.timeline, io.trace_ring_events))
        if io.monitor_out or io.slo_p99_us > 0:
            # live monitor (ISSUE 20): windowed snapshots / SLO burn /
            # score drift, layered on the recorder armed above (an SLO
            # without a sink still tracks — breaches land in the trace
            # ring).  telemetry.disable() flushes and disarms it.
            if not tracing.active():
                tracing.set_identity(run_id=io.trace_run_id)
                tracing.arm(ring_events=io.trace_ring_events,
                            dump_dir=io.trace_dump_dir or None,
                            sketch_growth=io.trace_sketch_growth)
            from . import monitor
            monitor.arm(out_path=io.monitor_out,
                        interval_s=io.monitor_interval_s,
                        slo_p99_us=io.slo_p99_us,
                        slo_window_s=io.slo_window_s)
            log.debug("monitor armed: out=%s interval=%.3fs slo_p99_us=%g"
                      % (io.monitor_out, io.monitor_interval_s,
                         io.slo_p99_us))
        if io.stall_timeout > 0:
            # hung-collective flight recorder (ISSUE 5): gbdt.run_training
            # arms the watchdog thread around the training loop
            telemetry.configure_watchdog(io.stall_timeout)
        self.boosting: GBDT = None
        self.objective = None
        self.train_data = None
        self.valid_datas = []

    def run(self) -> None:
        if self.config.task_type == "train":
            self.init_train()
            self.train()
        else:
            self.init_predict()
            self.predict()

    # -------------------------------------------------------------- training

    def init_train(self) -> None:
        """Application::InitTrain (application.cpp:201-237)."""
        learner = None
        if self.config.is_parallel:
            from .parallel import create_parallel_learner, sync_up_by_min
            from .parallel.mesh import init_distributed
            init_distributed(self.config)
            # distributed determinism: sync seeds/fractions to global min
            # (application.cpp:207-214, 133-135)
            io, tree = self.config.io_config, self.config.boosting_config.tree_config
            io.data_random_seed = sync_up_by_min(io.data_random_seed)
            tree.feature_fraction_seed = sync_up_by_min(tree.feature_fraction_seed)
            tree.feature_fraction = sync_up_by_min(tree.feature_fraction)
            learner = create_parallel_learner(self.config)
            # timeline="auto" resolves HERE, after distributed init, when
            # process_count is final: multi-process runs get per-process
            # shards (the clock handshake ran inside init_distributed)
            if self.config.io_config.timeline_enabled():
                telemetry.set_timeline(True)
            # pod identity is final here too: trace dumps from every
            # process must carry matching (index, count) or podtrace's
            # merge refuses the set
            try:
                import jax as _jax
                tracing.set_identity(process_index=_jax.process_index(),
                                     process_count=_jax.process_count())
            except Exception:
                pass

        self.boosting = GBDT()
        predict_fun = None
        if self.config.io_config.input_model:
            cont_model = GBDT.from_model_file(self.config.io_config.input_model)
            predict_fun = lambda feats: cont_model.predict_raw(feats)
            self.boosting.models = cont_model.models

        self.objective = create_objective(self.config.objective_type,
                                          self.config.objective_config)
        self.load_data(predict_fun)
        self.boosting.init(self.config.boosting_config, self.train_data,
                           self.objective, self.train_metrics, learner=learner)
        for valid_data, metrics, name in self.valid_datas:
            self.boosting.add_valid_dataset(valid_data, metrics, name=name)

        # preemption-safe restart (ISSUE 14): a checkpoint_dir holding a
        # finished checkpoint resumes training from it — bit-identically
        # on the same topology; on a different num_machines the learner's
        # mesh was already re-factored above (factor_machines over the
        # surviving machine count) and the binary cache re-sharded
        # through the streaming loader, so the restore replays onto the
        # new layout (the documented elastic continuation budgets).
        bc = self.config.boosting_config
        if bc.checkpoint_dir:
            from . import checkpoint as ckpt_mod
            latest = ckpt_mod.latest_checkpoint(bc.checkpoint_dir)
            if latest is not None:
                log.info("resuming from checkpoint %s" % latest)
                self.boosting.restore_checkpoint(latest)
        if bc.elastic_shrink and self.config.is_parallel:
            # live straggler mesh-shrink (ISSUE 14): the factory re-runs
            # factor_machines through create_parallel_learner on the
            # surviving machine count; an explicit feature_shards that no
            # longer divides falls back to auto-factoring (with a note)
            # instead of a mid-run fatal
            from .parallel import create_parallel_learner as _factory_cpl
            cfg = self.config

            def _shrunk_learner(num_machines, _cfg=cfg):
                _cfg.network_config.num_machines = int(num_machines)
                fs = _cfg.boosting_config.tree_config.feature_shards
                if fs and int(num_machines) % fs:
                    log.warning(
                        "elastic shrink: feature_shards=%d does not "
                        "divide the surviving %d machines; re-factoring "
                        "automatically" % (fs, num_machines))
                    _cfg.boosting_config.tree_config.feature_shards = 0
                return _factory_cpl(_cfg)

            self.boosting.enable_elastic(_shrunk_learner)

    def load_data(self, predict_fun=None) -> None:
        """Application::LoadData (application.cpp:119-199)."""
        # perf_counter, not time.time(): wall clock is not monotonic (NTP
        # steps would corrupt the duration); message text keeps reference
        # parity
        start = time.perf_counter()
        rank = 0
        shard_count = 1
        bin_finder = None
        if self.config.is_parallel and self.config.is_parallel_find_bin:
            # Row shards are PER PROCESS: one process hosts every row its
            # mesh devices train on (the data-parallel learner shards them
            # on-device), so the reference's per-machine partition
            # (dataset.cpp:172-216) maps to the process grid — a
            # single-process run over N devices loads ALL rows.  Feature
            # parallel loads full rows everywhere, exactly like the
            # reference (is_parallel_find_bin=false for FP,
            # io/config.cpp:164-172).
            import jax as _jax
            from .parallel import get_rank, distributed_bin_finder
            rank = get_rank()
            shard_count = _jax.process_count()
            bin_finder = distributed_bin_finder(self.config)
        # single-process parallel consumers take the streamed bin matrix
        # committed on the LEARNER's device mesh (explicit NamedSharding
        # placement; parallel.mesh.dataset_row_sharding): row-sharded
        # over the (data,) axis for tree_learner=data when the row count
        # divides the mesh, replicated on that mesh otherwise (a
        # multi-device shard_map rejects a one-device commit) — resident
        # loads and serial training are unaffected
        single_proc_parallel = (self.config.is_parallel
                                and shard_count == 1)
        shard_rows = (single_proc_parallel
                      and self.config.boosting_config.tree_learner
                      == "data")
        self.train_data = Dataset.load_train(
            self.config.io_config, rank=rank, num_machines=shard_count,
            predict_fun=predict_fun, bin_finder=bin_finder,
            shard_rows=shard_rows,
            shard_devices=(self.config.network_config.num_machines
                           if single_proc_parallel else None),
            device_type=self.config.device_type)

        self.train_metrics = []
        if self.config.boosting_config.is_provide_training_metric:
            for metric_type in self.config.metric_types:
                metric = create_metric(metric_type, self.config.metric_config)
                if metric is not None:
                    self.train_metrics.append(metric)

        self.valid_datas = []
        for filename in self.config.io_config.valid_data_filenames:
            valid = Dataset.load_valid(self.train_data, filename,
                                       predict_fun=predict_fun,
                                       io_config=self.config.io_config)
            metrics = []
            for metric_type in self.config.metric_types:
                metric = create_metric(metric_type, self.config.metric_config)
                if metric is not None:
                    metrics.append(metric)
            self.valid_datas.append((valid, metrics, filename))
        log.info("Finish loading data, use %f seconds"
                 % (time.perf_counter() - start))

    def train(self) -> None:
        """Application::Train (application.cpp:239-257).

        ``profile_dir=<dir>`` (SURVEY §5.1) wraps the loop in a
        jax.profiler trace — the device-level phase breakdown the
        reference's wall-clock logs cannot give."""
        log.info("Start train ...")
        is_eval = bool(self.train_metrics) or any(
            m for _, m, _ in self.valid_datas)
        start = time.perf_counter()
        # a checkpoint restore already banked boosting.iter iterations;
        # num_iterations is the TOTAL budget of the run, so train only
        # the remainder (a restart after a clean finish trains nothing
        # and just rewrites the final model file)
        remaining = max(
            self.config.boosting_config.num_iterations - self.boosting.iter,
            0)
        if remaining < self.config.boosting_config.num_iterations:
            log.info("checkpoint restore banked %d iteration(s); training "
                     "%d more" % (self.boosting.iter, remaining))

        def _run():
            self.boosting.run_training(
                remaining, is_eval,
                save_fn=lambda: self.boosting.save_model_to_file(
                    False, self.config.io_config.output_model),
                progress_fn=lambda it: log.info(
                    "%f seconds elapsed, finished %d iteration"
                    % (time.perf_counter() - start, it)))

        if self.config.io_config.profile_dir:
            import jax
            with jax.profiler.trace(self.config.io_config.profile_dir):
                _run()
            log.info("Profiler trace written to %s"
                     % self.config.io_config.profile_dir)
        else:
            _run()
        self.boosting.save_model_to_file(
            True, self.config.io_config.output_model)
        log.info("Finished train")

    # ------------------------------------------------------------ prediction

    def init_predict(self) -> None:
        """Application::InitPredict (application.cpp:269-273)."""
        if not self.config.io_config.input_model:
            log.fatal("Please provide a model file for prediction")
        self.boosting = GBDT.from_model_file(self.config.io_config.input_model)

    def predict(self) -> None:
        from .serving import engine_options_from_config
        predictor = Predictor(self.boosting, self.config.io_config.is_sigmoid,
                              self.config.predict_leaf_index,
                              self.config.io_config.num_model_predict,
                              serving_options=engine_options_from_config(
                                  self.config.io_config))
        predictor.predict_file(self.config.io_config.data_filename,
                               self.config.io_config.output_result,
                               self.config.io_config.has_header)
        if telemetry.enabled():
            # the predict task has no training loop to write the final
            # totals record: emit it here so metrics_out= predict runs
            # carry the serve/* family (and the predict-phase roofline)
            # into the sink telemetry_report.py renders
            telemetry.emit_summary()
        log.info("Finished prediction")


def main(argv: List[str] = None) -> int:
    """src/main.cpp equivalent."""
    argv = argv if argv is not None else sys.argv[1:]
    try:
        app = Application(argv)
        app.run()
    except log.LightGBMError:
        return 1
    finally:
        # close the metrics sink armed in Application.__init__ (flushes
        # pending records; harmless no-op when telemetry was never on)
        telemetry.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
