"""GBDT boosting loop.

Re-design of /root/reference/src/boosting/gbdt.cpp:19-521 (+ gbdt.h,
score_updater.hpp, boosting.cpp factory).  The host drives iterations; each
iteration's compute — gradients, tree growth, score updates — runs as jitted
device programs on the [F, N] bin matrix.  Per-class trees are interleaved
``models_[iter*num_class + k]`` exactly like gbdt.cpp:175-195.

Score maintenance (ScoreUpdater, score_updater.hpp:15-77) is a device
array [num_class, N]; the leaf-id vector returned by the grower covers ALL
rows (in-bag and out-of-bag), so the reference's separate OOB traversal path
(gbdt.cpp:159-165) collapses into one gather.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import faults as faults_mod
from .. import hatches, telemetry, tracing
from ..utils import log
from ..ops.scoring import add_tree_score
from ..ops.lookup import exact_table_lookup as _leaf_lookup
from ..ops.hist_pallas import INT8_HIST_MAX_ROWS, accum_ranges
from .grower import grow_tree
from .tree import Tree


class GBDT:
    def __init__(self, config=None):
        self.config = config
        self.models: List[Tree] = []
        self.num_class = 1
        self.label_idx = 0
        self.max_feature_idx = 0
        self.sigmoid = -1.0
        self.iter = 0
        self.train_data = None
        self.objective = None
        self.training_metrics = []
        self.valid_datasets = []
        self.valid_metrics = []
        self.best_score = []
        self.best_iter = []
        self.early_stopping_round = 0
        # training-time score-distribution reference (ISSUE 20): the
        # serialized monitor.ScoreHistogram captured from the live
        # training scores, saved as the model file's
        # ``score_reference=`` metadata line — the baseline the serving
        # drift detector compares live scores against
        self.score_reference: Optional[dict] = None
        self._saved_model_size = -1
        self._model_file = None
        self._learner_factory: Optional[Callable] = None
        self._mp = False            # multi-process data-parallel mode
        self._mp_fp = False         # multi-process feature-parallel mode
        self._host_inputs = False
        self._row_valid = None
        # latest metric values keyed "dataset/metric" — rides the
        # telemetry iteration records (captured only while a sink is
        # active, _consume_metric_values)
        self._last_eval_values = {}
        # training-health monitor (ISSUE 2, lightgbm_tpu/health.py) —
        # created in init() when the health= setting resolves on
        self._health_monitor = None
        # pipelined boosting (ISSUE 6): deferred-readback queues.  _pipe
        # holds ONE dispatched-but-unconsumed per-iteration entry,
        # _pipe_chunk one dispatched chunk record; _pipeline_auto is set
        # by run_training when pipeline="auto" resolves on (direct
        # train_one_iter/train_chunk callers keep synchronous semantics
        # unless the config forces "readback")
        self._pipe = None
        self._pipe_chunk = None
        self._pipeline_auto = False
        # preemption-safe elastic training (ISSUE 14): the live straggler
        # policy (elastic.StragglerMonitor, armed via enable_elastic),
        # the learner factory a mesh shrink rebuilds with, the active
        # async checkpoint writer (run_training-scoped), and the last
        # checkpointed iteration
        self._straggler_monitor = None
        self._elastic_exchange_on = False
        self._ckpt_writer = None
        self._last_ckpt_iter = 0
        self._boundary_t = None
        # written/dropped totals of the last run's checkpoint writer
        # (recorded at close; the bench ckpt lane reads them)
        self._ckpt_stats = None

    # ------------------------------------------------------------------ init

    def init(self, boosting_config, train_data, objective,
             training_metrics=(), learner=None) -> None:
        """GBDT::Init (gbdt.cpp:41-89).  ``learner`` optionally overrides the
        tree-growing callable (serial default; parallel learners plug in via
        lightgbm_tpu.parallel).  The ``booster_init`` span is set-up's
        share here; its child ``h2d`` is the bin table's placement."""
        with telemetry.span("booster_init"):
            self._init(boosting_config, train_data, objective,
                       training_metrics, learner)

    def _place_bins(self, place, host_bins, **kwargs):
        """The bin table's host → device placement under the ``h2d`` span,
        which waits for the table (a span that only timed the enqueue
        would read nothing) — when telemetry is off nothing waits."""
        with telemetry.span("h2d"):
            placed = place(host_bins, **kwargs)
            if telemetry.enabled():
                telemetry.count("init/h2d_bytes", int(host_bins.nbytes))
                jax.block_until_ready(placed)
        return placed

    def _init(self, boosting_config, train_data, objective,
              training_metrics, learner) -> None:
        self.gbdt_config = boosting_config
        self.tree_config = boosting_config.tree_config
        self.train_data = train_data
        self.objective = objective
        self.num_class = boosting_config.num_class
        self.early_stopping_round = boosting_config.early_stopping_round
        self.training_metrics = list(training_metrics)
        self.max_feature_idx = train_data.num_total_features - 1
        self.label_idx = train_data.label_idx
        self.sigmoid = objective.sigmoid if objective is not None else -1.0
        if learner is None and boosting_config.tree_learner != "serial":
            learner = _configured_learner(boosting_config)
        self._learner = learner or _serial_learner

        N = train_data.num_data
        self.num_bins_max = int(train_data.num_bins.max())
        self.num_features = train_data.num_features
        # [F, B] bin→upper-bound table for vectorized threshold conversion
        self._bin_upper_table = train_data.bin_upper_bounds_matrix()

        # mixed-bin feature packing (ISSUE 6): when the dataset mixes
        # narrow (num_bin <= 64) and wide features, reorder the bin matrix
        # into contiguous bin-width classes so every histogram pass prices
        # each class at ITS width instead of the uniform worst case.  The
        # spec is a static (hashable) layout descriptor threaded through
        # the growers; all histograms are reassembled into canonical
        # feature order before split finding, so trees/splits/ownership
        # are bit-identical to the uniform path.  Feature-parallel keeps
        # the uniform layout — its ownership slices are arbitrary feature
        # subsets that a class-contiguous layout cannot serve.
        mixed_mode = getattr(self.tree_config, "mixed_bin", "auto")
        self._pack_spec = None
        if (learner is not None
                and (type(learner).__name__ == "FeatureParallelLearner"
                     or getattr(learner, "needs_uniform_layout", False))):
            # feature-parallel ownership slices are ARBITRARY (bin-count
            # balanced) feature subsets — no contiguous-block structure a
            # packed layout could commute with
            if mixed_mode == "true":
                log.warning("mixed_bin is not supported by %s; "
                            "keeping the uniform layout"
                            % type(learner).__name__)
        elif (learner is not None
                and getattr(learner, "feature_block_packing", False)):
            # hybrid/voting 2-D mesh (ISSUE 12): the bin-width-class
            # permutation is computed PER owned feature block — it never
            # crosses a block boundary, so packing commutes with block
            # ownership and the owned-block psum / packed-SplitInfo
            # allreduce ride unchanged (io/binning.BlockedPackSpec)
            blk, fs = learner.pack_layout(train_data.num_features)
            self._pack_spec = train_data.plan_packing(
                mode=mixed_mode, block=blk, shards=fs)
            if self._pack_spec is None and mixed_mode == "true":
                log.warning(
                    "mixed_bin=true requested but the block-local plan "
                    "degenerates to the uniform layout (single bin-width "
                    "class, or an ownership block without narrow "
                    "features)")
        else:
            self._pack_spec = train_data.plan_packing(mode=mixed_mode)
        if self._pack_spec is not None:
            blocked = hasattr(self._pack_spec, "block")
            telemetry.count_route("hist_layout", "hist/mixedbin_on")
            if blocked:
                # the block-local variant files an extra marker so the
                # route counters distinguish the layouts (telemetry.py
                # hist/mixedbin_* family)
                telemetry.count("hist/mixedbin_blocked")
            if blocked:
                log.info("mixed-bin packing (block-local, block=%d): %d "
                         "narrow (<=%d bins) + %d wide features PER "
                         "owned block (histogram passes per class: %s)"
                         % (self._pack_spec.block,
                            self._pack_spec.counts[0],
                            self._pack_spec.widths[0],
                            self._pack_spec.counts[1],
                            "x".join(str(w)
                                     for w in self._pack_spec.widths)))
            else:
                log.info("mixed-bin packing: %d narrow (<=%d bins) + %d "
                         "wide features (histogram passes per class: %s)"
                         % (self._pack_spec.counts[0],
                            self._pack_spec.widths[0],
                            self._pack_spec.counts[1],
                            "x".join(str(w)
                                     for w in self._pack_spec.widths)))
        else:
            telemetry.count_route("hist_layout", "hist/mixedbin_off")

        # multi-process data parallelism (the reference's N-machine mode,
        # dataset.cpp:172-216): each process holds a row shard; lift every
        # row-aligned array to a global mesh-sharded jax.Array so the
        # shard_map programs span the whole distributed job.
        self._mp = (jax.process_count() > 1 and learner is not None
                    and type(learner).__name__ == "DataParallelLearner")
        # multi-process feature parallel: every process loads the FULL
        # rows (cli.load_data, matching the reference's FP machines —
        # io/config.cpp:164-172 sets is_parallel_find_bin=false) and the
        # replicated-rows FP chunk program runs over the global mesh with
        # host-side (numpy) inputs.  Only the fused depthwise chunk is
        # lifted; the per-iteration path would push committed local
        # arrays into the global-mesh program, so it fails loudly instead
        # of obscurely (feature_parallel_tree_learner.cpp:9-81 is the
        # reference's N-machine FP).
        self._mp_fp = (jax.process_count() > 1 and learner is not None
                       and type(learner).__name__ == "FeatureParallelLearner")
        if self._mp_fp and self.tree_config.grow_policy != "depthwise":
            log.fatal("multi-process feature-parallel training requires "
                      "grow_policy=depthwise (the fused chunk program); "
                      "leaf-wise feature parallel is single-process only")
        # any multi-process mode keeps replicated inputs host-side (numpy):
        # every process passes identical values into global-mesh programs
        self._host_inputs = self._mp or self._mp_fp
        if self._mp:
            from ..parallel import mesh as _pmesh
            # same mesh the learner's shard_map programs will use
            mesh = _pmesh.get_mesh(
                device_type=getattr(getattr(learner, "config", None),
                                    "device_type", "") or "")
            max_n, counts = _pmesh.global_row_layout(N)
            self._mp_max_n = max_n
            self._mp_local_n = N
            self._mp_mesh = mesh
            self._mp_true_n = int(np.sum(counts))
            # padded-global -> true-global compaction map: process p's true
            # rows live at [p*max_n, p*max_n + counts[p]) of the gathered
            # row axis; metric evaluation slices these out statically
            self._shard_layout = tuple(
                (p * max_n, int(counts[p])) for p in range(len(counts)))
            self._mp_make_global = functools.partial(
                _pmesh.make_global_rows, max_n=max_n, mesh=mesh)
            if objective is not None and not (
                    hasattr(objective, "globalize")
                    or hasattr(objective, "globalize_layout")):
                log.fatal("objective does not support multi-process "
                          "data-parallel training (no row-aligned state "
                          "globalization)")
            self.num_data = max_n * jax.process_count()
            self.bins_device = self._place_bins(
                self._mp_make_global, self._bins_host(train_data),
                row_axis=1)
            # replicated small arrays stay host-side (every process passes
            # identical values into the jitted programs)
            self.num_bins_device = np.asarray(train_data.num_bins)
            valid = np.zeros(max_n, bool)
            valid[:N] = True
            self._row_valid = self._mp_make_global(valid)
            init_score = train_data.metadata.init_score
            score0 = (np.tile(np.asarray(init_score, np.float32),
                              (self.num_class, 1))
                      if init_score is not None
                      else np.zeros((self.num_class, N), np.float32))
            self.score = self._mp_make_global(score0, row_axis=1)
        else:
            self.num_data = N
            # multi-process feature parallel keeps inputs host-side: every
            # process passes identical (replicated) values into the
            # global-mesh chunk program
            _arr0 = np.asarray if self._mp_fp else jnp.asarray
            dev_bins = getattr(train_data, "device_bins", None)
            if dev_bins is not None and not self._host_inputs:
                # streamed dataset (io/streaming.py): the bin matrix is
                # already device-resident with explicit NamedSharding
                # placement — no host copy exists to upload.  Mixed-bin
                # packing reorders by one device-side gather.
                if self._pack_spec is not None:
                    self.bins_device = jnp.take(
                        dev_bins,
                        jnp.asarray(np.asarray(self._pack_spec.perm,
                                               np.int32)), axis=0)
                    # release the unpacked original: keeping both would
                    # DOUBLE peak HBM for the whole run at the 100M-row
                    # scale streaming exists for (the resident path's
                    # duplicate lives on host).  The dataset is consumed
                    # — a second init must re-stream (loud error below).
                    train_data.device_bins = None
                    train_data.device_bins_consumed = True
                else:
                    self.bins_device = dev_bins
            else:
                log.check(
                    not getattr(train_data, "device_bins_consumed", False),
                    "this streamed dataset's device bin matrix was "
                    "consumed by a previous mixed-bin GBDT.init — reload "
                    "the dataset to train another booster on it")
                rows = _row_sharding(learner, N)
                self.bins_device = self._place_bins(
                    _arr0 if rows is None else functools.partial(
                        jax.device_put, device=rows),
                    self._bins_host(train_data))
            self.num_bins_device = _arr0(train_data.num_bins)
            self._row_valid = None
            init_score = train_data.metadata.init_score
            if init_score is not None:
                score0 = np.tile(np.asarray(init_score, np.float32),
                                 (self.num_class, 1))
            else:
                score0 = np.zeros((self.num_class, N), np.float32)
            self.score = _arr0(score0)

        if (self.tree_config.hist_dtype == "int8"
                and accum_ranges(self.num_data) > 1):
            # num_data is the GLOBAL (padded) row count in every mode
            log.info("int8 histograms over %d rows: every pass sums in %d "
                     "accumulation ranges of at most %d rows"
                     % (self.num_data, accum_ranges(self.num_data),
                        INT8_HIST_MAX_ROWS))

        # bagging state (gbdt.cpp:77-88)
        self._bag_rng = np.random.RandomState(boosting_config.bagging_seed)
        self._use_bagging = (boosting_config.bagging_fraction < 1.0
                             and boosting_config.bagging_freq > 0)
        if self._mp:
            # bagging draws over the LOCAL shard (the reference's
            # per-machine Bagging over its partition, gbdt.cpp:106-157);
            # padded phantom rows never enter histograms/root stats
            self._bag_mask = np.ones(N, dtype=bool)
            self._bag_mask_device = self._row_valid
        else:
            self._bag_mask = np.ones(N, dtype=bool)
            # device-side mask caches: uploads pay full link latency, so
            # only re-upload when the host-side mask actually changes
            self._bag_mask_device = jnp.asarray(self._bag_mask)
        # device-side bagging (ISSUE 8, ops/sampling.py): redraws become a
        # threefry key bump + on-device argsort — no host full-N RNG, no
        # mask upload.  The draw counter is the whole rewindable state.
        self._bag_device = self._resolve_bagging_device(boosting_config)
        self._bag_draw_idx = 0
        if self._bag_device:
            from ..ops import sampling as _sampling
            self._bag_base_key = _sampling.bag_key(
                boosting_config.bagging_seed)
            telemetry.count_route("bagging", "bagging/device")
        elif self._use_bagging:
            telemetry.count_route("bagging", "bagging/host")
        self._feat_mask_device = {}
        # per-class feature-fraction RNGs, same seed each
        # (serial_tree_learner.cpp:159-167; one learner per class)
        self._feat_rngs = [np.random.RandomState(self.tree_config.feature_fraction_seed)
                           for _ in range(self.num_class)]

        # GOSS (ISSUE 8): device-side gradient-based one-side sampling —
        # per-iteration top-|grad| rows plus an amplified random
        # remainder, fed through the row-mask seam (ops/sampling.py)
        self._goss_on = bool(getattr(boosting_config, "goss", False))
        if self._goss_on:
            if self._host_inputs and self.tree_config.grow_policy \
                    != "depthwise":
                # multi-process GOSS rides the fused chunk program only
                # (the selection is traced in-program over the gathered
                # global gradient scores); the per-iteration multi-
                # process path would run the device draw over committed
                # local arrays and is not supported
                log.fatal(
                    "goss=true in multi-process training requires the "
                    "fused chunk path: grow_policy=depthwise (and a "
                    "device formulation for every configured metric); "
                    "per-iteration multi-process GOSS is unsupported")
            from ..ops import sampling as _sampling
            self._goss_key = _sampling.bag_key(
                boosting_config.bagging_seed)
            # selection runs over the GLOBAL true rows in every mode
            # (the DP chunk gathers scores and selects on the compacted
            # global layout — identical to the serial draw)
            sel_n = self._mp_true_n if self._mp else N
            (self._goss_top_cnt, self._goss_other_cnt,
             self._goss_amp) = _sampling.goss_counts(
                sel_n, boosting_config.top_rate,
                boosting_config.other_rate)
            log.info("GOSS: keeping top %d rows by |grad| + %d amplified "
                     "(x%.3f) random rows per iteration"
                     % (self._goss_top_cnt, self._goss_other_cnt,
                        self._goss_amp))

        if objective is not None:
            if self._mp and hasattr(objective, "globalize_layout"):
                # global-score objectives (lambdarank) build their
                # per-query tables directly over the padded-global row
                # layout (a local init would be discarded immediately).
                # That layout is only valid when the row shards are
                # query-atomic (dataset.cpp:189-206) — queries from an
                # in-file group column are extracted AFTER sharding and
                # get cut per-record, which would silently mis-train
                if (train_data.metadata.query_boundaries is not None
                        and not getattr(train_data, "shard_query_atomic",
                                        True)):
                    log.fatal(
                        "distributed lambdarank requires query-atomic row "
                        "sharding: supply query ids via a .query side "
                        "file (an in-file group column is extracted after "
                        "sharding and splits queries across machines)")
                objective.globalize_layout(
                    self._mp_global_metadata(), self._shard_layout,
                    self.num_data)
            else:
                objective.init(train_data.metadata, N)
                if self._mp:
                    # lift row-aligned objective state to global sharded
                    # arrays
                    objective.globalize(self._mp_make_global)
        if self._mp and self.training_metrics:
            # training metrics see the GLOBAL rows: rebuild the global
            # metadata on every process (order matches the gathered global
            # score, so values are exactly the serial run's — stronger than
            # the reference's per-machine training metrics, gbdt.cpp:225-259)
            for metric in self.training_metrics:
                metric.init("training", self._mp_global_metadata(),
                            self._mp_true_n)
        else:
            for metric in self.training_metrics:
                metric.init("training", train_data.metadata, N)

        # training-health monitor (ISSUE 2): "auto" follows the telemetry
        # SINK, so metrics_out= runs get health blocks with no extra flag;
        # health=true forces it on for library users without a sink
        from .. import health as _health
        if _health.resolve_enabled(getattr(boosting_config, "health",
                                           "auto")):
            self._health_monitor = _health.HealthMonitor(
                on_anomaly=getattr(boosting_config, "on_anomaly", "warn"),
                divergence_rounds=getattr(boosting_config,
                                          "health_divergence_rounds", 0),
                quantized=self.tree_config.hist_dtype == "int8")
        else:
            self._health_monitor = None

        # one-shot dataset-residency report (memory gauges), filed at
        # train start — after add_valid_dataset calls — by _file_residency
        self._residency_filed = False

    def _bins_host(self, train_data) -> np.ndarray:
        """Host-side bin matrix in the booster's storage layout: canonical
        feature order, or packed bin-width-class order under mixed-bin
        (one row gather, paid once at init)."""
        if self._pack_spec is None:
            return train_data.bins
        perm = np.asarray(self._pack_spec.perm, np.int64)
        return np.ascontiguousarray(train_data.bins[perm])

    def _file_residency(self) -> None:
        """File the one-shot dataset-residency report on the first
        training entry (any path), so BENCH/PROFILE rounds stop
        hand-measuring HBM footprints."""
        if self._residency_filed or not telemetry.memory_enabled():
            return
        self._residency_filed = True
        telemetry.set_residency(self._residency_report())

    def _residency_report(self) -> dict:
        """Static device-memory footprint of this booster's training state:
        the bin matrix, row-aligned score/metadata arrays, and the
        histogram scratch the configured grower will carry."""
        F, B = self.num_features, self.num_bins_max
        L = _effective_num_leaves(self.tree_config)
        md = self.train_data.metadata
        md_bytes = sum(int(np.asarray(a).nbytes) for a in
                       (md.label, md.weights, md.init_score,
                        md.query_boundaries) if a is not None)
        if self.tree_config.grow_policy == "depthwise":
            # widest level: P parent slots, each [F, B, 3] f32, live twice
            # across the subtraction (hists + hist_small)
            from .grower_depthwise import num_levels
            P = 1 << max(num_levels(L, self.tree_config.max_depth) - 1, 0)
            hist_scratch = 2 * P * F * B * 3 * 4
        else:
            # leaf-wise: the [L, F, B, 3] f32 histogram cache
            hist_scratch = L * F * B * 3 * 4
        return {
            "num_rows": int(self.num_data),
            "num_features": int(F),
            "num_bins_max": int(B),
            "bin_matrix_bytes": int(self.bins_device.nbytes),
            "score_bytes": int(self.score.nbytes),
            "metadata_bytes": int(md_bytes),
            "hist_scratch_bytes": int(hist_scratch),
            "valid_bins_bytes": int(sum(e["bins"].nbytes
                                        for e in self.valid_datasets)),
        }

    def health_summary(self):
        """Cumulative health totals (None when the monitor is off) —
        bench.py attaches this to its JSON line."""
        return (self._health_monitor.summary()
                if self._health_monitor is not None else None)

    def _mp_global_metadata(self):
        """Cached all-process Metadata view (labels/weights/query layout in
        process order — the compacted-global row coordinate system)."""
        md = getattr(self, "_mp_global_md", None)
        if md is None:
            from ..parallel.mesh import gather_ragged_rows
            md = self._mp_global_md = self.train_data.metadata.global_view(
                gather_ragged_rows)
        return md

    def add_valid_dataset(self, valid_data, valid_metrics, name=None) -> None:
        """GBDT::AddDataset (gbdt.cpp:92-105).

        Multi-process mode matches the reference's N-machine layout: every
        process loads the FULL validation file (application.cpp:166-177
        LoadValidationData takes no rank partition), so valid bins/scores
        ride replicated — host-side numpy here, every process passing
        identical values into the global-mesh programs."""
        idx = len(self.valid_datasets)
        name = name or f"valid_{idx + 1}"
        _arr = np.asarray if self._host_inputs else jnp.asarray
        entry = {
            "data": valid_data,
            "bins": _arr(valid_data.bins),
            "score": _arr(
                np.tile(valid_data.metadata.init_score, (self.num_class, 1))
                if valid_data.metadata.init_score is not None
                else np.zeros((self.num_class, valid_data.num_data), np.float32)),
            "name": name,
        }
        self.valid_datasets.append(entry)
        for metric in valid_metrics:
            metric.init(name, valid_data.metadata, valid_data.num_data)
        self.valid_metrics.append(list(valid_metrics))
        self.best_score.append([-1.0] * len(valid_metrics))
        self.best_iter.append([0] * len(valid_metrics))

    # ------------------------------------------------------------- iteration

    def _resolve_bagging_device(self, boosting_config) -> bool:
        """The ``bagging_device=`` resolution rule, single-homed: the env
        hatch (LGBM_TPU_HOST_BAGGING=1) beats the config; "auto" is on
        for accelerator backends only (the host path's numpy stream is
        the historical draw — CPU runs keep it so recorded models stay
        stable); explicit "true" forces the device draw anywhere it CAN
        apply.  It cannot apply (warns and falls back on "true"):
        multi-process shards (draws are per-local-shard host state) and
        per-query bagging (the atomic-query draw is a host loop)."""
        if not self._use_bagging:
            return False
        if hatches.flag("LGBM_TPU_HOST_BAGGING"):
            return False
        mode = getattr(boosting_config, "bagging_device", "auto")
        if mode == "false":
            return False
        capable = (not self._host_inputs
                   and self.train_data.metadata.query_boundaries is None
                   and self.train_data.metadata.queries is None)
        if mode == "true":
            if not capable:
                log.warning("bagging_device=true cannot apply here "
                            "(multi-process shard or per-query bagging); "
                            "keeping the host draw")
            return capable
        return capable and jax.default_backend() != "cpu"

    def _draw_bag_mask(self, it: int) -> None:
        """Host-side bagging draw (GBDT::Bagging, gbdt.cpp:106-157):
        per-record, or per-query when query boundaries exist.  Updates
        ``_bag_mask`` only; device upload is the per-iteration path's concern
        (the chunked path ships masks in one batched transfer).

        Called once per (iteration, class) pair like the reference
        (Bagging(iter_, curr_class) inside the per-class loop,
        gbdt.cpp:175-177): on a redraw iteration each class tree gets a
        fresh draw from the single shared RNG stream."""
        if not self._use_bagging or it % self.gbdt_config.bagging_freq != 0:
            return
        if tracing.active():
            # here (not _bagging) so the chunked path's batched draws
            # land on the flight-recorder timeline too — one event per
            # actual RNG advance, replay redraws included
            tracing.event("bagging_draw", iter=int(it))
        frac = self.gbdt_config.bagging_fraction
        if self._bag_device:
            # device draw (ISSUE 8, ops/sampling.py): the redraw is a key
            # bump — fold_in(base_key, draw_idx) — and an on-device exact-
            # count mask; no host RNG advances and nothing crosses the
            # link.  _bag_draw_idx is the WHOLE rewindable stream state
            # (the rollback machinery restores an integer instead of
            # MT19937 state).  Per-query bagging never reaches here
            # (_resolve_bagging_device keeps it on the host path).
            from ..ops import sampling as _sampling
            n = self.num_data
            bag_cnt = int(frac * n)
            self._bag_mask_device = _sampling.bag_mask_for_draw(
                self._bag_base_key, self._bag_draw_idx, n, bag_cnt)
            self._bag_draw_idx += 1
            log.info("re-bagging, using %d data to train" % bag_cnt)
            return
        qb = self.train_data.metadata.query_boundaries
        # multi-process: bag the LOCAL shard, like the reference's
        # per-machine Bagging over its own partition (gbdt.cpp:106-157)
        n = self._mp_local_n if self._mp else self.num_data
        mask = np.zeros(n, dtype=bool)
        if qb is None:
            bag_cnt = int(frac * n)
            idx = self._bag_rng.choice(n, bag_cnt, replace=False)
            mask[idx] = True
        else:
            nq = qb.size - 1
            bag_q = int(nq * frac)
            qidx = self._bag_rng.choice(nq, bag_q, replace=False)
            for q in qidx:
                mask[qb[q]:qb[q + 1]] = True
            bag_cnt = int(mask.sum())
        log.info("re-bagging, using %d data to train" % bag_cnt)
        self._bag_mask = mask
        self._bag_mask_device = None

    def _bagging(self, it: int) -> None:
        with telemetry.span("bagging"):
            self._draw_bag_mask(it)
            if self._bag_mask_device is None:
                if self._mp:
                    self._bag_mask_device = self._mp_make_global(
                        self._bag_mask)
                else:
                    self._bag_mask_device = jnp.asarray(self._bag_mask)

    def _goss_masks(self, grad, hess):
        """Per-iteration GOSS selection (ISSUE 8, ops/sampling.py): keep
        the top_rate fraction of rows by summed |gradient|, sample an
        other_rate fraction of the remainder, amplify the sampled
        remainder's gradients AND hessians by (1-top_rate)/other_rate.
        Runs entirely on device; the returned mask feeds the growers'
        row-mask seam (the same seam bagging uses), so a sampled
        iteration never materializes full-row host intermediates.  The
        draw is a pure function of (seed, iteration) — the pipelined
        rollback machinery needs NO snapshot for it.

        Returns ``(grad, hess, None)`` untouched when GOSS is off."""
        if not self._goss_on:
            return grad, hess, None
        if self._host_inputs:
            # defensive: init() fatals unless the chunk path will serve
            # multi-process GOSS; a direct per-iteration call must not
            # silently run the draw over committed local arrays
            log.fatal("per-iteration multi-process GOSS is unsupported; "
                      "use the fused chunk path (grow_policy=depthwise)")
        from ..ops import sampling as _sampling
        with telemetry.span("goss") as sp:
            g, h, mask = _sampling.goss_select(
                jax.random.fold_in(self._goss_key, self.iter),
                grad, hess, self._goss_top_cnt, self._goss_other_cnt,
                self._goss_amp)
            sp.fence(mask)
        telemetry.count("goss/iterations")
        if tracing.active():
            tracing.event("goss_draw", iter=int(self.iter))
        return g, h, mask

    def _feature_sample(self, cls: int) -> np.ndarray:
        frac = self.tree_config.feature_fraction
        F = self.num_features
        if frac >= 1.0:
            return np.ones(F, dtype=bool)
        used_cnt = max(int(F * frac), 1)
        mask = np.zeros(F, dtype=bool)
        mask[self._feat_rngs[cls].choice(F, used_cnt, replace=False)] = True
        return mask

    # ------------------------------------------------------ pipelined loop

    def _pipeline_on(self) -> bool:
        """The ``pipeline=`` resolution rule, single-homed: the env hatch
        (LGBM_TPU_PIPELINE) beats the config; "auto" is on only inside
        run_training (``_pipeline_auto``); multi-process runs stay
        synchronous (replicated host inputs make deferred consumption a
        cross-host ordering hazard for no measured win)."""
        env = hatches.choice("LGBM_TPU_PIPELINE", ("off", "readback"))
        mode = env or getattr(
            getattr(self, "gbdt_config", None), "pipeline", "off")
        if mode == "off":
            on = False
        elif mode == "readback":
            on = True
        else:
            on = self._pipeline_auto
        return on and not self._host_inputs and jax.process_count() == 1

    def _rng_snapshot(self):
        """Host RNG/mask state needed to rewind a dispatched-but-discarded
        iteration (pipelined rollback): bagging stream + mask caches and
        the per-class feature-fraction streams.  None-components skip the
        copy when the corresponding sampling is off."""
        bag = self._bag_snapshot()
        ff = ([r.get_state() for r in self._feat_rngs]
              if self.tree_config.feature_fraction < 1.0 else None)
        return (bag, ff)

    def _rng_restore(self, snap) -> None:
        if snap is None:
            return
        bag, ff = snap
        self._bag_restore(bag)
        if ff is not None:
            for r, s in zip(self._feat_rngs, ff):
                r.set_state(s)

    def _bag_snapshot(self):
        """The bagging stream's full rewindable state, mode-aware: the
        device stream is (draw counter, current device mask) — an integer
        plus an immutable array reference; the host stream is (MT19937
        state, host mask copy, device mask cache)."""
        if not self._use_bagging:
            return None
        if self._bag_device:
            return ("device", self._bag_draw_idx, self._bag_mask_device)
        return ("host", self._bag_rng.get_state(), self._bag_mask.copy(),
                self._bag_mask_device)

    def _bag_restore(self, snap) -> None:
        if snap is None:
            return
        if snap[0] == "device":
            _, self._bag_draw_idx, self._bag_mask_device = snap
        else:
            _, state, mask, mask_dev = snap
            self._bag_rng.set_state(state)
            self._bag_mask = mask
            self._bag_mask_device = mask_dev

    def flush_pipeline(self) -> bool:
        """Consume every deferred readback (pipelined boosting).  Called
        by run_training at loop end; direct train_one_iter/train_chunk
        callers that force pipeline=readback must call it before reading
        ``models``/scores.  Returns True when the consumed work says
        training stopped (degenerate tree or early stopping)."""
        stop = False
        if self._pipe is not None:
            entry, self._pipe = self._pipe, None
            stop = self._consume_iter_entry(entry, newer=None)
        if self._pipe_chunk is not None:
            rec, self._pipe_chunk = self._pipe_chunk, None
            stop = self._consume_chunk(rec, newer_inflight=False) or stop
        return stop

    # --------------------------------------- checkpoint / elastic (ISSUE 14)

    def _consumed_iteration(self) -> int:
        """The number of fully CONSUMED boosting iterations — the point a
        checkpoint describes.  Pipelined per-iteration mode advances
        ``self.iter`` at dispatch, so the in-flight entry's own iteration
        number is the consumed count; the chunk path advances at
        consumption, so ``self.iter`` is already right."""
        if self._pipe is not None:
            return int(self._pipe["iter_no"])
        return int(self.iter)

    def checkpoint_fingerprint(self) -> dict:
        """The semantic config fields a restored run must match exactly
        (compared field-by-field on load; a mismatch names the field).
        Topology fields (num_machines / tree_learner / feature_shards)
        are deliberately absent — an elastic restart changes them by
        design and the continuation budget is topology's, not the
        model's."""
        bc, tc = self.gbdt_config, self.tree_config
        return {
            "objective": (type(self.objective).__name__
                          if self.objective is not None else None),
            "num_class": int(self.num_class),
            "learning_rate": float(bc.learning_rate),
            "bagging_fraction": float(bc.bagging_fraction),
            "bagging_freq": int(bc.bagging_freq),
            "bagging_seed": int(bc.bagging_seed),
            # the RESOLVED stream, not the knob: "auto" resolving to a
            # different stream on restore would silently fork the draws
            "bagging_stream": ("device" if self._bag_device
                               else "host" if self._use_bagging else "off"),
            "feature_fraction": float(tc.feature_fraction),
            "feature_fraction_seed": int(tc.feature_fraction_seed),
            "goss": bool(getattr(bc, "goss", False)),
            "top_rate": float(getattr(bc, "top_rate", 0.0)),
            "other_rate": float(getattr(bc, "other_rate", 0.0)),
            "num_leaves": int(tc.num_leaves),
            "max_depth": int(tc.max_depth),
            "min_data_in_leaf": int(tc.min_data_in_leaf),
            "min_sum_hessian_in_leaf": float(tc.min_sum_hessian_in_leaf),
            "grow_policy": str(tc.grow_policy),
            "hist_dtype": str(tc.hist_dtype),
            "quant_rounding": str(tc.quant_rounding),
            "early_stopping_round": int(bc.early_stopping_round),
        }

    def _dataset_fingerprint(self) -> dict:
        """Topology-independent dataset identity: true global rows (not
        the padded per-topology layout), feature counts, valid-set
        count."""
        return {
            "num_features": int(self.num_features),
            "num_total_features": int(self.train_data.num_total_features),
            "num_rows": int(self._mp_true_n if self._mp
                            else self.train_data.num_data),
            "num_valid": len(self.valid_datasets),
        }

    def _topology_info(self) -> dict:
        lc = getattr(self._learner, "config", None)
        nm = (int(lc.network_config.num_machines)
              if lc is not None else 1)
        return {
            "tree_learner": (type(self._learner).__name__
                             if self._learner is not _serial_learner
                             else "serial"),
            "num_machines": nm,
            "process_count": int(jax.process_count()),
        }

    def checkpoint_state(self) -> dict:
        """Raw consistent snapshot of the CONSUMED training state, cheap
        enough for the hot loop (list copy + RNG get_state; tree
        serialization happens on the writer thread,
        checkpoint.serialize_state).  Pipelined mode snapshots the state
        as-of the consumed boundary: the in-flight entry's pre-dispatch
        RNG snapshot IS that state (scores are never stored — the
        restore replays the trees, which the rollback machinery already
        proved bitwise-equal to the in-grow updates)."""
        if self._pipe is not None:
            it = int(self._pipe["iter_no"])
            rng = self._pipe["pre_rng"]
            score_ref = self._pipe["score_before"]
            valid_ref = self._pipe["valid_before"]
        elif self._pipe_chunk is not None:
            rec = self._pipe_chunk
            it = int(self.iter)
            rng = (rec["bag_state"], rec["ff_states"])
            score_ref = rec["score_before"]
            valid_ref = tuple(rec["valid_before"])
        else:
            it = int(self.iter)
            rng = self._rng_snapshot()
            score_ref = self.score
            valid_ref = tuple(e["score"] for e in self.valid_datasets)
        if self._mp:
            # compact to TRUE global rows now — the gather is a
            # collective and must run on the main thread; single-process
            # scores stay device references the writer thread reads
            score_ref = self._host_global_score(score_ref)
        return {
            "iteration": it,
            "num_class": int(self.num_class),
            "models": tuple(self.models),
            "best_score": [list(r) for r in self.best_score],
            "best_iter": [list(r) for r in self.best_iter],
            "rng": rng,
            "score": score_ref,
            "valid_scores": list(valid_ref),
            "config": self.checkpoint_fingerprint(),
            "dataset": self._dataset_fingerprint(),
            "topology": self._topology_info(),
        }

    def restore_checkpoint(self, payload) -> None:
        """Continue training from a checkpoint payload (a loaded dict, or
        a path).  Must be called on a FRESHLY initialized booster (after
        ``init`` + ``add_valid_dataset``): the config/dataset
        fingerprints are compared field-by-field (loud reject naming the
        field), trees, RNG streams and the raw f32 scores are restored
        exactly — bit-identical continuation on the same topology; on a
        different one the stored TRUE-row scores re-lift onto the new
        layout and the continuation lands in the documented
        cross-schedule budget class."""
        from .. import checkpoint as ckpt_mod
        if isinstance(payload, str):
            payload = ckpt_mod.load_checkpoint(payload)
        log.check(self.train_data is not None,
                  "restore_checkpoint requires init() first")
        if self.models or self.iter:
            log.fatal("restore_checkpoint requires a freshly initialized "
                      "booster (input_model continuation and checkpoint "
                      "resume are mutually exclusive)")
        try:
            ckpt_mod.check_fingerprint(payload,
                                       self.checkpoint_fingerprint(),
                                       self._dataset_fingerprint())
        except ckpt_mod.CheckpointError as e:
            log.fatal(str(e))
        topo = payload.get("topology", {})
        here = self._topology_info()
        if topo.get("num_machines") not in (None, here["num_machines"]):
            log.info("elastic restart: checkpoint topology "
                     "num_machines=%s -> %s (mesh re-factored on the "
                     "surviving machine count)"
                     % (topo.get("num_machines"), here["num_machines"]))
        self.models = [ckpt_mod.tree_from_json(t)
                       for t in payload["trees"]]
        self.iter = int(payload["iteration"])
        self.best_score = [list(map(float, r))
                           for r in payload["best_score"]]
        self.best_iter = [list(map(int, r)) for r in payload["best_iter"]]
        rng = payload["rng"]
        self._restore_bag_json(rng["bagging"])
        ff = rng["feature_fraction"]
        if ff is not None:
            if len(ff) != len(self._feat_rngs):
                log.fatal("checkpoint rng field 'feature_fraction' has %d "
                          "streams, this run has %d classes"
                          % (len(ff), len(self._feat_rngs)))
            for r, s in zip(self._feat_rngs, ff):
                r.set_state(ckpt_mod._rng_state_from_json(s))
        # install the stored raw f32 scores (true rows), re-lifted onto
        # THIS topology's layout
        stored = ckpt_mod.array_from_json(payload["score"])
        n_true = self._mp_true_n if self._mp else self.train_data.num_data
        if tuple(stored.shape) != (self.num_class, n_true):
            log.fatal("checkpoint field 'score' has shape %s, this run "
                      "needs (%d, %d)" % (tuple(stored.shape),
                                          self.num_class, n_true))
        if self._mp:
            counts = [c for _, c in self._shard_layout]
            off = sum(counts[:jax.process_index()])
            local = stored[:, off:off + self._mp_local_n]
            self.score = self._mp_make_global(local, row_axis=1)
        elif self._host_inputs:
            self.score = np.asarray(stored)
        else:
            self.score = jnp.asarray(stored)
        vs = payload["valid_scores"]
        if len(vs) != len(self.valid_datasets):
            log.fatal("checkpoint field 'valid_scores' has %d sets, this "
                      "run configured %d validation dataset(s)"
                      % (len(vs), len(self.valid_datasets)))
        for entry, sj in zip(self.valid_datasets, vs):
            s = ckpt_mod.array_from_json(sj)
            entry["score"] = (np.asarray(s) if self._host_inputs
                              else jnp.asarray(s))
        # a restarted CLI run rewrites its incremental model file from
        # scratch (fresh header + every tree)
        if self._model_file is not None and not self._model_file.closed:
            self._model_file.close()
        self._saved_model_size = -1
        self._model_file = None
        self._last_ckpt_iter = self.iter
        telemetry.count("ckpt/restored")
        log.info("restored checkpoint at iteration %d (%d trees)"
                 % (self.iter, len(self.models)))

    def _restore_bag_json(self, obj) -> None:
        """Restore the bagging stream from its checkpoint form.  The
        resolved stream mode already matched via the config fingerprint
        (``bagging_stream``); device mode restores the draw counter and
        reconstructs the current mask (a pure function of it), host mode
        restores the MT19937 state + current mask."""
        if obj is None:
            return
        if obj["mode"] == "device":
            self._bag_draw_idx = int(obj["draw_idx"])
            if self._bag_draw_idx > 0:
                from ..ops import sampling as _sampling
                n = self.num_data
                bag_cnt = int(self.gbdt_config.bagging_fraction * n)
                self._bag_mask_device = _sampling.bag_mask_for_draw(
                    self._bag_base_key, self._bag_draw_idx - 1, n, bag_cnt)
            return
        from .. import checkpoint as ckpt_mod
        mask = ckpt_mod._mask_from_json(obj["mask"])
        n_local = self._mp_local_n if self._mp else self.train_data.num_data
        if mask.size != n_local:
            log.fatal("checkpoint rng field 'bagging' mask covers %d rows "
                      "but this process's shard has %d — host-path "
                      "bagging state is per-shard, so an elastic restart "
                      "across a different process layout must use "
                      "bagging_device=true (or bagging off)"
                      % (mask.size, n_local))
        self._bag_rng.set_state(ckpt_mod._rng_state_from_json(obj["state"]))
        self._bag_mask = mask
        self._bag_mask_device = None

    def enable_elastic(self, learner_factory, monitor=None,
                       exchange=None):
        """Arm the live straggler mesh-shrink policy (ISSUE 14):
        ``learner_factory(num_machines)`` builds the learner for a shrunk
        mesh (the CLI passes ``create_parallel_learner`` over a mutated
        config — ``factor_machines`` then re-runs on the surviving
        count).  ``monitor`` defaults to a fresh
        ``elastic.StragglerMonitor(straggler_k)``; feed it observations
        from merged timeline rows or let the per-iteration cross-host
        time exchange drive it (``exchange``: None = auto, on for true
        multi-process runs; True/False force).  Returns the monitor so
        harnesses can inject observations."""
        from .. import elastic as elastic_mod
        self._learner_factory = learner_factory
        if monitor is None:
            monitor = elastic_mod.StragglerMonitor(
                k=int(getattr(self.gbdt_config, "straggler_k", 3)
                      if hasattr(self, "gbdt_config") else 3))
        self._straggler_monitor = monitor
        if exchange is None:
            exchange = jax.process_count() > 1
        self._elastic_exchange_on = bool(exchange)
        return monitor

    def _elastic_step(self) -> bool:
        """One iteration-boundary pass of the live straggler policy:
        exchange per-host iteration times (when armed), consult the
        monitor, and execute the drain-at-boundary mesh shrink when a
        persistent straggler is flagged.  Returns True when draining the
        pipeline surfaced a stop (training must end)."""
        mon = self._straggler_monitor
        if mon is None:
            return False
        now = time.perf_counter()
        if self._elastic_exchange_on and hasattr(self._learner, "_mesh"):
            if self._boundary_t is not None:
                from .. import elastic as elastic_mod
                gathered = elastic_mod.exchange_times(
                    self._learner._mesh(), now - self._boundary_t,
                    iteration=self._consumed_iteration())
                mon.observe(self._consumed_iteration(),
                            elastic_mod.host_times_from_gather(
                                gathered,
                                slots_per_host=jax.local_device_count()))
        self._boundary_t = now
        flagged = mon.take_flagged()
        if flagged is None:
            return False
        return self._elastic_shrink(flagged)

    def _elastic_shrink(self, flagged: str) -> bool:
        """Drain-at-iteration-boundary mesh shrink: checkpoint, drop the
        flagged slot, re-factor the mesh on the surviving machine count,
        restore, resume.  Returns True when the drain surfaced a stop
        (no shrink then — training is over anyway)."""
        from .. import checkpoint as ckpt_mod
        from .. import elastic as elastic_mod
        if self._learner_factory is None or not callable(
                self._learner_factory):
            log.warning("persistent straggler %s flagged but no learner "
                        "factory is registered (enable_elastic); cannot "
                        "shrink the mesh" % flagged)
            self._straggler_monitor = None
            return False
        lc = getattr(self._learner, "config", None)
        cur = (int(lc.network_config.num_machines)
               if lc is not None else 1)
        if cur <= 1:
            log.warning("persistent straggler %s flagged but the mesh is "
                        "already minimal (num_machines=1); cannot shrink"
                        % flagged)
            self._straggler_monitor = None
            return False
        # drain: consume every in-flight pipelined readback so the
        # checkpoint describes a clean iteration boundary
        if self.flush_pipeline():
            return True
        state = self.checkpoint_state()
        if self._ckpt_writer is not None:
            self._ckpt_writer.write_sync(state)
        if jax.process_count() > 1:
            # a live process cannot be evicted from jax.distributed
            # in-process: the shrink IS the checkpoint+restart protocol —
            # drain, persist, and tell the supervisor to restart the
            # survivors (task=train with the same checkpoint_dir re-runs
            # factor_machines on the surviving count).  Without a
            # configured checkpoint writer there is nothing durable to
            # restart FROM — exiting would lose the whole run, so keep
            # training at the degraded pace and say why.
            if self._ckpt_writer is None:
                log.warning(
                    "persistent straggler %s flagged, but no checkpoint "
                    "is configured (checkpoint_interval=0) — a "
                    "multi-process shrink restarts survivors from a "
                    "checkpoint, so none can happen; continuing at the "
                    "straggler's pace.  Arm checkpoint_interval/"
                    "checkpoint_dir to make shrinks recoverable."
                    % flagged)
                self._straggler_monitor = None
                return False
            log.fatal("persistent straggler %s: checkpoint written; "
                      "multi-process mesh shrink requires restarting the "
                      "surviving processes from the checkpoint "
                      "(task=train, same checkpoint_dir)" % flagged)
        # survivor agreement on the OLD mesh before tearing it down: each
        # host votes keep(1)/drop(0) per slot; pmin commits everyone to
        # the most conservative plan (single-process: trivially agreed,
        # but the same seam multi-host supervisors consume)
        try:
            drop_slot = int(str(flagged).lstrip("p").split("@")[0])
        except ValueError:
            drop_slot = cur - 1
        drop_slot = min(max(drop_slot, 0), cur - 1)
        votes = np.ones(cur, np.int32)
        votes[drop_slot] = 0
        if hasattr(self._learner, "_mesh"):
            agreed = elastic_mod.agree_survivors(self._learner._mesh(),
                                                 votes,
                                                 iteration=state["iteration"])
            new_m = int(np.asarray(agreed).sum())
        else:
            new_m = cur - 1
        new_m = max(min(new_m, cur - 1), 1)
        log.warning("elastic mesh shrink: persistent straggler %s — "
                    "draining at iteration %d, re-factoring %d -> %d "
                    "machines" % (flagged, state["iteration"], cur, new_m))
        payload = ckpt_mod.serialize_state(state)
        new_learner = self._learner_factory(new_m)
        valids = [(e["data"], self.valid_metrics[i], e["name"])
                  for i, e in enumerate(self.valid_datasets)]
        # init() rebuilds device state but not the progress bookkeeping
        # __init__ owns — reset it so the restore sees a fresh booster
        # (valid sets re-add below; best_score/best_iter re-append there
        # and are then overwritten by the restore)
        self.models = []
        self.iter = 0
        self.valid_datasets = []
        self.valid_metrics = []
        self.best_score = []
        self.best_iter = []
        self.init(self.gbdt_config, self.train_data, self.objective,
                  self.training_metrics, learner=new_learner)
        for vd, ms, name in valids:
            self.add_valid_dataset(vd, ms, name=name)
        self.restore_checkpoint(payload)
        if self._straggler_monitor is not None:
            self._straggler_monitor.reset()
        telemetry.count("elastic/shrinks")
        if tracing.active():
            tracing.event("elastic_shrink", iter=int(self.iter))
        return False

    def train_one_iter(self, is_eval: bool = True) -> bool:
        """GBDT::TrainOneIter (gbdt.cpp:167-214).  Returns True when
        training must stop (early stopping or no splittable leaf).

        Pipelined mode (pipeline=readback): this call DISPATCHES iteration
        i and consumes iteration i-1's deferred model readback — the
        device work is dispatched in exactly the synchronous order, only
        the host wait moves one iteration later, so trees/scores/metrics
        are exact-identical (stops are discovered one call late and the
        surplus dispatched iteration is rolled back from snapshots)."""
        if self._pipeline_on():
            self._file_residency()
            if self._pipe_chunk is not None:
                # mixing chunked and per-iteration paths mid-pipeline:
                # drain the chunk first (ordering)
                if self.flush_pipeline():
                    return True
            entry = self._dispatch_one_iter(is_eval)
            prev, self._pipe = self._pipe, entry
            if prev is not None and self._consume_iter_entry(prev,
                                                             newer=entry):
                self._pipe = None
                return True
            return False
        if self._pipe is not None or self._pipe_chunk is not None:
            # pipeline turned off with work in flight: drain first
            if self.flush_pipeline():
                return True
        self._file_residency()
        mon = self._health_monitor
        with telemetry.span("gradient") as sp:
            grad, hess = self.objective.get_gradients(
                self.score if self.num_class > 1 else self.score[0])
            sp.fence((grad, hess))
        if self.num_class == 1:
            grad = grad[None]
            hess = hess[None]
        # GOSS selection runs ONCE per iteration over all classes'
        # gradients (the amplified grad/hess feed the growers; health and
        # the next iteration's gradients see the raw arrays)
        g_grow, h_grow, goss_mask = self._goss_masks(grad, hess)

        for cls in range(self.num_class):
            self._bagging(self.iter)
            feature_mask = self._feature_sample(cls)
            row_mask = (goss_mask if goss_mask is not None
                        else self._bag_mask_device)
            key = feature_mask.tobytes()
            if key not in self._feat_mask_device:
                # one live entry suffices: the per-class feature RNGs share
                # one seed and advance in lockstep
                # (serial_tree_learner.cpp:159-167 parity), so every class
                # draws the SAME mask within an iteration — one upload per
                # redraw, hits for classes 1..C-1
                self._feat_mask_device.clear()
                self._feat_mask_device[key] = (
                    np.asarray(feature_mask) if self._mp
                    else jnp.asarray(feature_mask))

            with telemetry.span("grow") as sp:
                tree_arrays = self._learner(
                    self, self.bins_device, g_grow[cls], h_grow[cls],
                    row_mask, self._feat_mask_device[key])
                sp.fence(tree_arrays)

            # ONE host round-trip for everything the host needs (each
            # device_get pays a full host<->device latency, so the 8 small
            # arrays are not fetched separately).  Start
            # the copy asynchronously, dispatch the device-side score update
            # first, and only then block — the link latency overlaps with
            # device compute.
            small = tree_arrays._replace(leaf_ids=None)
            try:
                for arr in jax.tree.leaves(small):
                    arr.copy_to_host_async()
            except Exception:
                pass

            # train score via leaf partition (fast path, gbdt.cpp:216-218 +
            # OOB, 159-165 — unified because leaf_ids cover all rows); the
            # shrinkage (gbdt.cpp:188) is applied on device, so this needs
            # nothing from the host
            lr = jnp.float32(self.gbdt_config.learning_rate)
            # zero the contribution of a degenerate (unsplit) tree on device:
            # the reference rejects such trees before any score update
            # (gbdt.cpp:182-185), and this keeps that invariant without
            # waiting for num_leaves on the host
            with telemetry.span("score_update") as sp:
                shrunk = jnp.where(tree_arrays.num_leaves > 1,
                                   tree_arrays.leaf_value * lr, 0.0)
                self.score = _add_leaf_values(
                    self.score, shrunk, tree_arrays.leaf_ids, cls=cls)
                sp.fence(self.score)
            # valid scores via tree replay (gbdt.cpp:220-222); the grower's
            # arrays are already statically padded to num_leaves-1, so the
            # replay jit compiles once and uses no host data
            if self.valid_datasets:
                max_nodes = len(tree_arrays.split_feature)
                with telemetry.span("valid_update") as sp:
                    for entry in self.valid_datasets:
                        new_cls = add_tree_score(
                            entry["bins"], entry["score"][cls],
                            tree_arrays.split_feature,
                            tree_arrays.threshold_bin,
                            tree_arrays.left_child,
                            tree_arrays.right_child,
                            shrunk,
                            tree_arrays.num_leaves,
                            max_nodes=max_nodes)
                        if self._mp:
                            # valid state stays host-side numpy in
                            # multi-process mode (replicated inputs to the
                            # global programs)
                            entry["score"][cls] = np.asarray(new_cls)
                        else:
                            entry["score"] = entry["score"].at[cls].set(
                                new_cls)
                        sp.fence(new_cls)

            # now block on the (already in-flight) host copy for the model
            host = _read_back(small)
            num_leaves = int(host.num_leaves)
            if mon is not None:
                # tree-derived health counts ride the readback for free
                mon.add_tree(num_leaves, host.split_gain, host.leaf_count)
            if num_leaves <= 1:
                log.info("Can't training anymore, there isn't any leaf meets "
                         "split requirements.")
                if mon is not None:
                    # the iteration produced no tree, but its gradients may
                    # be the REASON (NaN/Inf gains reject every split):
                    # record the health block and apply the policy before
                    # stopping, so the stop is explained, not silent
                    hvec = mon.grad_health_async(grad, hess, self.score)
                    block = mon.assemble(hvec)
                    if telemetry.sink_active():
                        dp, dt = telemetry.take_phase_deltas()
                        telemetry.emit_iteration(
                            self.iter + 1, dp, dt,
                            eval_metrics=self._last_eval_values,
                            health=block,
                            memory=telemetry.take_memory_record(),
                            extra={"stopped": "degenerate_tree"})
                    mon.apply_policy(block, self.iter + 1)
                return True

            with telemetry.span("tree_build"):
                tree = self._to_host_tree(host)
                tree.shrinkage(self.gbdt_config.learning_rate)
                self.models.append(tree)
        telemetry.count("train/iterations")

        # dispatch the health program over this iteration's arrays (async:
        # the host copy overlaps the eval phase; fetched at assemble)
        hvec = (mon.grad_health_async(grad, hess, self.score)
                if mon is not None else None)
        met_early_stopping = False
        if is_eval:
            with telemetry.span("eval"):
                met_early_stopping = self.output_metric(self.iter + 1)
        self.iter += 1
        health_block = mon.assemble(hvec) if mon is not None else None
        if telemetry.sink_active():
            dp, dt = telemetry.take_phase_deltas()
            telemetry.emit_iteration(self.iter, dp, dt,
                                     eval_metrics=self._last_eval_values,
                                     health=health_block,
                                     memory=telemetry.take_memory_record())
        if mon is not None:
            # AFTER the record is written: a halt must not lose the
            # record that explains it
            mon.apply_policy(health_block, self.iter)
        if met_early_stopping:
            log.info("Early stopping at iteration %d, the best iteration "
                     "round is %d"
                     % (self.iter, self.iter - self.early_stopping_round))
            # pop back the last early_stopping_round models (gbdt.cpp:205-210)
            del self.models[len(self.models)
                            - self.early_stopping_round * self.num_class:]
        return met_early_stopping

    def _dispatch_one_iter(self, is_eval: bool) -> dict:
        """Dispatch one boosting iteration's device work (gradients, per-
        class grow + async model copy + score/valid updates) WITHOUT the
        model readback — exactly train_one_iter's dispatch sequence.  The
        returned entry carries everything the deferred consumption needs:
        the in-flight small-array handles, post-update score/valid
        references per class (functional updates make these free), and
        host RNG snapshots for exact rollback when a stop is discovered
        late."""
        mon = self._health_monitor
        pre_rng = self._rng_snapshot()
        with telemetry.span("gradient") as sp:
            grad, hess = self.objective.get_gradients(
                self.score if self.num_class > 1 else self.score[0])
            sp.fence((grad, hess))
        if self.num_class == 1:
            grad = grad[None]
            hess = hess[None]
        entry = {"iter_no": self.iter, "is_eval": is_eval, "cls": [],
                 "grad": grad, "hess": hess, "pre_rng": pre_rng,
                 "mon": mon,
                 # pre-dispatch score references (functional updates make
                 # these free): the CONSUMED-boundary state a checkpoint
                 # taken while this entry is in flight must describe
                 "score_before": self.score,
                 "valid_before": tuple(e["score"]
                                       for e in self.valid_datasets)}
        g_grow, h_grow, goss_mask = self._goss_masks(grad, hess)
        lr = jnp.float32(self.gbdt_config.learning_rate)
        for cls in range(self.num_class):
            cls_pre = self._rng_snapshot()
            self._bagging(self.iter)
            feature_mask = self._feature_sample(cls)
            row_mask = (goss_mask if goss_mask is not None
                        else self._bag_mask_device)
            key = feature_mask.tobytes()
            if key not in self._feat_mask_device:
                self._feat_mask_device.clear()
                self._feat_mask_device[key] = jnp.asarray(feature_mask)
            with telemetry.span("grow") as sp:
                tree_arrays = self._learner(
                    self, self.bins_device, g_grow[cls], h_grow[cls],
                    row_mask, self._feat_mask_device[key])
                sp.fence(tree_arrays)
            small = tree_arrays._replace(leaf_ids=None)
            try:
                for arr in jax.tree.leaves(small):
                    arr.copy_to_host_async()
            except Exception:
                pass
            with telemetry.span("score_update") as sp:
                shrunk = jnp.where(tree_arrays.num_leaves > 1,
                                   tree_arrays.leaf_value * lr, 0.0)
                self.score = _add_leaf_values(
                    self.score, shrunk, tree_arrays.leaf_ids, cls=cls)
                sp.fence(self.score)
            if self.valid_datasets:
                max_nodes = len(tree_arrays.split_feature)
                with telemetry.span("valid_update") as sp:
                    for v_entry in self.valid_datasets:
                        new_cls = add_tree_score(
                            v_entry["bins"], v_entry["score"][cls],
                            tree_arrays.split_feature,
                            tree_arrays.threshold_bin,
                            tree_arrays.left_child,
                            tree_arrays.right_child,
                            shrunk,
                            tree_arrays.num_leaves,
                            max_nodes=max_nodes)
                        v_entry["score"] = v_entry["score"].at[cls].set(
                            new_cls)
                        sp.fence(new_cls)
            entry["cls"].append({
                "small": small,
                "pre_rng": cls_pre,
                "score_after": self.score,
                "valid_after": tuple(e["score"]
                                     for e in self.valid_datasets),
            })
        # dispatch-time increment: the next dispatched iteration's bagging
        # draws key off self.iter; stops discovered at consumption reset it
        self.iter += 1
        return entry

    def _pipe_restore(self, rec, rng_target) -> None:
        """Rewind booster state to exactly ``rec``'s post-update point
        (score/valid refs) and the given RNG snapshot (None = already
        correct)."""
        self.score = rec["score_after"]
        for e, s in zip(self.valid_datasets, rec["valid_after"]):
            e["score"] = s
        self._rng_restore(rng_target)

    def _consume_iter_entry(self, entry, newer) -> bool:
        """Deferred consumption of one dispatched iteration: model
        readback, host tree construction, health/eval/early-stop
        bookkeeping — the synchronous path's tail, verbatim in order.
        ``newer`` is the already-dispatched next iteration (rolled back
        when this one stops) or None on flush."""
        mon = entry["mon"]
        C = self.num_class
        it = entry["iter_no"]
        for cls, rec in enumerate(entry["cls"]):
            host = _read_back(rec["small"])
            num_leaves = int(host.num_leaves)
            if mon is not None:
                mon.add_tree(num_leaves, host.split_gain, host.leaf_count)
            if num_leaves <= 1:
                log.info("Can't training anymore, there isn't any leaf "
                         "meets split requirements.")
                # synchronous semantics: state ends after THIS class's
                # (zero) score update, with later classes' and any newer
                # iteration's dispatched work undone
                if cls + 1 < C:
                    rng_target = entry["cls"][cls + 1]["pre_rng"]
                elif newer is not None:
                    rng_target = newer["pre_rng"]
                else:
                    rng_target = None
                self._pipe_restore(rec, rng_target)
                self.iter = it
                if mon is not None:
                    hvec = mon.grad_health_async(entry["grad"],
                                                 entry["hess"], self.score)
                    block = mon.assemble(hvec)
                    if telemetry.sink_active():
                        dp, dt = telemetry.take_phase_deltas()
                        telemetry.emit_iteration(
                            it + 1, dp, dt,
                            eval_metrics=self._last_eval_values,
                            health=block,
                            memory=telemetry.take_memory_record(),
                            extra={"stopped": "degenerate_tree"})
                    mon.apply_policy(block, it + 1)
                return True
            with telemetry.span("tree_build"):
                tree = self._to_host_tree(host)
                tree.shrinkage(self.gbdt_config.learning_rate)
                self.models.append(tree)
        telemetry.count("train/iterations")

        last = entry["cls"][-1]
        hvec = (mon.grad_health_async(entry["grad"], entry["hess"],
                                      last["score_after"])
                if mon is not None else None)
        met_early_stopping = False
        if entry["is_eval"]:
            with telemetry.span("eval"):
                met_early_stopping = self._output_metric_at(it + 1, last)
        health_block = mon.assemble(hvec) if mon is not None else None
        if telemetry.sink_active():
            dp, dt = telemetry.take_phase_deltas()
            telemetry.emit_iteration(it + 1, dp, dt,
                                     eval_metrics=self._last_eval_values,
                                     health=health_block,
                                     memory=telemetry.take_memory_record())
        if mon is not None:
            from ..health import TrainingHealthError
            try:
                mon.apply_policy(health_block, it + 1)
            except TrainingHealthError:
                # halt must leave the booster at exactly iteration it+1:
                # undo the newer dispatched iteration before re-raising
                if newer is not None:
                    self._pipe_restore(last, newer["pre_rng"])
                self.iter = it + 1
                self._pipe = None
                raise
        if met_early_stopping:
            log.info("Early stopping at iteration %d, the best iteration "
                     "round is %d"
                     % (it + 1, it + 1 - self.early_stopping_round))
            del self.models[len(self.models)
                            - self.early_stopping_round * self.num_class:]
            if newer is not None:
                self._pipe_restore(last, newer["pre_rng"])
            self.iter = it + 1
            return True
        return False

    def _output_metric_at(self, iteration: int, rec) -> bool:
        """output_metric over a pipelined entry's own score snapshot: the
        live ``self.score`` may already carry the NEXT iteration's update,
        so swap the entry's references in for the evaluation and restore
        the newest state after (stop paths re-restore from snapshots
        anyway)."""
        cur_score = self.score
        cur_valid = [e["score"] for e in self.valid_datasets]
        self.score = rec["score_after"]
        for e, s in zip(self.valid_datasets, rec["valid_after"]):
            e["score"] = s
        try:
            return self.output_metric(iteration)
        finally:
            self.score = cur_score
            for e, s in zip(self.valid_datasets, cur_valid):
                e["score"] = s

    def run_training(self, num_iterations: int, is_eval: bool,
                     save_fn: Optional[Callable] = None,
                     chunk_size: int = 8,
                     progress_fn: Optional[Callable] = None) -> None:
        """Drive the full training loop (Application::Train,
        application.cpp:239-257), fusing iterations into device chunks when
        no per-iteration metric output is needed.  Any exception escaping
        the loop (TrainingHealthError halts included) crash-flushes a
        final telemetry summary record before re-raising, so an aborted
        run keeps its tail records."""
        if self._mp_fp and not self.chunkable_for(is_eval):
            # the per-iteration fallback would push committed local arrays
            # into the global-mesh program and fail obscurely mid-train
            log.fatal("multi-process feature-parallel training requires "
                      "the fused chunk path: grow_policy=depthwise and a "
                      "device formulation for every configured metric")
        if self._mp and self._goss_on and not self.chunkable_for(is_eval):
            # multi-process GOSS exists only inside the chunk program
            # (the selection gathers the global gradient scores there)
            log.fatal("goss=true in multi-process training requires the "
                      "fused chunk path: grow_policy=depthwise and a "
                      "device formulation for every configured metric")
        # hung-collective flight recorder (ISSUE 5): with stall_timeout=
        # configured, a watchdog thread records span/collective events in
        # a ring buffer and — if no event lands for the timeout — dumps
        # the ring + in-flight phase/iteration/collective + thread stacks
        # to the sink BEFORE the environment's opaque ~60 s dispatch
        # watchdog kills the job.  Armed here, next to the crash-flush,
        # so both abnormal-end paths leave a record.
        wd_armed = telemetry.arm_watchdog()
        if wd_armed:
            telemetry.watchdog_checkin(phase="run_training",
                                       iteration=self.iter)
        # pipelined boosting (ISSUE 6): pipeline="auto" resolves ON inside
        # this driver — run_training owns the loop AND the flush, so the
        # deferred readbacks can never leak to a caller.  Explicit
        # "readback"/"off" (or LGBM_TPU_PIPELINE) win either way.
        # With a save_fn, auto stays OFF: the in-loop checkpoint must see
        # every finished tree (a deferred readback would persist each
        # snapshot one iteration/chunk stale — callers who accept that
        # lag opt in with pipeline=readback explicitly).
        self._pipeline_auto = save_fn is None
        # asynchronous periodic checkpoints (ISSUE 14): snapshots ride a
        # background writer thread, OFF the pipelined readback path — the
        # hot loop only pays the cheap raw snapshot (checkpoint_state);
        # pipelining stays on, so a checkpoint describes the CONSUMED
        # boundary (at most one iteration/chunk behind the dispatch)
        ckpt_interval = int(getattr(self.gbdt_config,
                                    "checkpoint_interval", 0) or 0)
        ckpt_writer = None
        if ckpt_interval > 0:
            from .. import checkpoint as ckpt_mod
            ckpt_dir = getattr(self.gbdt_config, "checkpoint_dir", "")
            log.check(bool(ckpt_dir),
                      "checkpoint_interval > 0 requires checkpoint_dir")
            ckpt_writer = ckpt_mod.CheckpointWriter(
                ckpt_dir,
                keep=int(getattr(self.gbdt_config, "checkpoint_keep", 2)))
            self._ckpt_writer = ckpt_writer
            self._last_ckpt_iter = self._consumed_iteration()
        self._boundary_t = time.perf_counter()

        def _boundary() -> bool:
            """Iteration-boundary housekeeping: enqueue the async
            checkpoint, run the live straggler policy, and fire the
            fault-injection hatch (faults.maybe_fire — the harness's
            between-iterations kill/stall point).  Returns True when the
            elastic drain surfaced a stop."""
            if ckpt_writer is not None:
                done = self._consumed_iteration()
                if done - self._last_ckpt_iter >= ckpt_interval:
                    ckpt_writer.submit(self.checkpoint_state())
                    self._last_ckpt_iter = done
            stop = False
            if self._straggler_monitor is not None:
                stop = self._elastic_step()
            faults_mod.maybe_fire(self._consumed_iteration())
            return stop
        try:
            if not self.chunkable_for(is_eval) or (num_iterations < chunk_size
                                                   and not self._mp_fp):
                # short runs use the per-iteration path: its grower program
                # is module-jitted (shared across boosters), while a chunk
                # shorter than chunk_size would waste the surplus iterations
                # it computes
                for _ in range(num_iterations):
                    finished = self.train_one_iter(is_eval=is_eval)
                    if wd_armed:
                        telemetry.watchdog_checkin(iteration=self.iter)
                    if save_fn is not None:
                        save_fn()
                    if progress_fn is not None:
                        progress_fn(self.iter)
                    if finished:
                        break
                    if _boundary():
                        break
            else:
                done = 0
                while done < num_iterations:
                    # always run the full-size chunk program (a shorter tail
                    # chunk would re-trace the scan and pay a second multi-
                    # minute compile); surplus iterations are rolled back
                    stop = self.train_chunk(chunk_size,
                                            limit=num_iterations - done,
                                            is_eval=is_eval)
                    if wd_armed:
                        telemetry.watchdog_checkin(iteration=self.iter)
                    if save_fn is not None:
                        save_fn()
                    if progress_fn is not None:
                        progress_fn(self.iter)
                    if stop:
                        break
                    if _boundary():
                        break
                    done += chunk_size
            # drain the deferred readbacks (pipelined mode; no-op
            # otherwise) so callers see fully-consistent models/scores
            if self._pipe is not None or self._pipe_chunk is not None:
                self.flush_pipeline()
                if wd_armed:
                    telemetry.watchdog_checkin(iteration=self.iter)
                if save_fn is not None:
                    save_fn()
                if progress_fn is not None:
                    progress_fn(self.iter)
            if ckpt_writer is not None:
                # final checkpoint, synchronous: a restart after a clean
                # finish sees the complete run
                ckpt_writer.write_sync(self.checkpoint_state())
        except BaseException as e:
            # crash-flush (ISSUE 4): an exception escaping training —
            # TrainingHealthError halts included — must not lose the
            # run's tail records.  Write the final summary (marked with
            # the exception type) and flush the sink before re-raising.
            # No collectives here: a crashed process cannot be assumed
            # able to join the cross-host aggregation, and the peer
            # processes are raising the same (host-replicated) error
            # rather than waiting in an allgather.
            #
            # Pipelined mode: a dispatched-but-unconsumed iteration/chunk
            # may hold a COMPLETED readback whose trees and telemetry
            # record the synchronous path would already have banked —
            # consume it best-effort (the crash may be unrelated to the
            # device) so the crash loses no finished work; if consumption
            # itself fails, drop the queue and keep the original error.
            try:
                if self._pipe is not None or self._pipe_chunk is not None:
                    self.flush_pipeline()
            except BaseException:
                pass
            finally:
                self._pipe = None
                self._pipe_chunk = None
            if ckpt_writer is not None:
                # best-effort final checkpoint: a clean exception
                # (TrainingHealthError halt, injected raise) leaves the
                # consumed state consistent and restartable; if the state
                # is torn, the write fails quietly and the last periodic
                # checkpoint stands
                try:
                    ckpt_writer.write_sync(self.checkpoint_state())
                except Exception:
                    pass
            if telemetry.sink_active():
                try:
                    extra = {"aborted": type(e).__name__,
                             "iterations": self.iter}
                    if self._health_monitor is not None:
                        extra["health"] = self._health_monitor.summary()
                    telemetry.emit_summary(extra=extra)
                except Exception:
                    pass
            # flight-recorder crash dump (ISSUE 16): the ring's last-N
            # events land beside the checkpoint — best-effort, after the
            # summary, never masking the real fault
            tracing.dump_on_fault(type(e).__name__)
            raise
        finally:
            self._pipeline_auto = False
            if ckpt_writer is not None:
                ckpt_writer.close()
                self._ckpt_stats = {"written": ckpt_writer.written,
                                    "dropped": ckpt_writer.dropped}
                self._ckpt_writer = None
            if wd_armed:
                telemetry.disarm_watchdog()
        if self._host_inputs:
            # fold every host's route counters into the leader before the
            # summary.  COLLECTIVE, hence outside any telemetry.enabled()
            # gate: a host whose config lacks metrics_out must still join
            # the allgather or the enabled hosts would hang in it (every
            # process reaches this point — run_training's control flow is
            # host-replicated)
            from ..parallel.learners import aggregate_telemetry
            aggregate_telemetry()
        if telemetry.sink_active():
            extra = {"iterations": self.iter}
            if self._health_monitor is not None:
                extra["health"] = self._health_monitor.summary()
            telemetry.emit_summary(extra=extra)

    # ------------------------------------------------------- chunked training

    @property
    def supports_chunking(self) -> bool:
        """True when fused multi-iteration training applies: serial learner
        (the parallel learners own their shard_map programs), a
        chunk-traceable objective, and device formulations for every
        configured metric (metrics/device.py) — metric values and valid
        scores are then computed INSIDE the chunk program and early
        stopping is applied post-hoc with identical semantics."""
        if (self._learner is not _serial_learner
                or not hasattr(self.objective, "chunk_spec")):
            return False
        return self._metrics_device_capable()

    def _metrics_device_capable(self) -> bool:
        """Every configured metric has a device (pure-JAX) formulation
        (metrics/device.py), so evaluation can run inside chunk programs."""
        from ..metrics import Metric as _MetricBase
        for ms in [self.training_metrics] + self.valid_metrics:
            for m in ms:
                if type(m).device_spec is _MetricBase.device_spec:
                    return False
        return True

    def _needs_eval(self, is_eval: bool) -> bool:
        return bool(is_eval
                    and (self.training_metrics or self.valid_datasets)
                    and (self.gbdt_config.output_freq > 0
                         or self.early_stopping_round > 0))

    def chunk_supported(self, is_eval: bool) -> bool:
        """Whether train_chunk can run at all: serial learner with full
        eval support (supports_chunking), or the data-parallel learner
        with row-shardable objective state — including in-program metric
        evaluation and early stopping (train metrics run on the
        all_gathered global score inside the shard_map chunk; AUC's
        global sort included.  Validation sets ride replicated).

        GOSS (ISSUE 12) runs INSIDE the chunk program on every path:
        the selection is traced into the scan body on each iteration's
        raw in-program gradients (serial/FP: the full replicated rows;
        DP: the |grad| scores all_gathered over the data axis, selected
        on the compacted true rows, sliced back per shard — a pure
        function of the globally-identical gradients, so every shard
        computes the identical selection), so sampled iterations keep
        the fused-k dispatch instead of forcing the per-iteration
        path."""
        if self.supports_chunking:
            return True
        from ..parallel.learners import (DataParallelLearner,
                                         FeatureParallelLearner)
        if (isinstance(self._learner, DataParallelLearner)
                and hasattr(self.objective, "chunk_spec")
                and (getattr(self.objective, "rows_aligned_params", False)
                     or getattr(self.objective, "needs_global_score",
                                False))):
            # eval-free runs never trace metric fns; otherwise every
            # metric needs a device formulation
            return (not self._needs_eval(is_eval)
                    or self._metrics_device_capable())
        if (isinstance(self._learner, FeatureParallelLearner)
                and hasattr(self.objective, "chunk_spec")):
            # rows are replicated under feature ownership, so ANY
            # chunk-traceable objective works (lambdarank included)
            return (not self._needs_eval(is_eval)
                    or self._metrics_device_capable())
        return False

    def chunkable_for(self, is_eval: bool) -> bool:
        """run_training's chunking decision: chunk_supported AND a
        chunk-safe grower/histogram combination.

        Leaf-wise growth stays on the per-iteration loop because the
        fused leaf-wise chunk was the slower of the two when last
        measured, on the r05 runtime (int8 in-scan 2.95 s/iter at 1M
        rows against 0.63 s/iter per-iteration f32: the per-pass
        quantization dominates its one-column passes), and has not been
        measured since; ROADMAP B-W3's cell measures it.  Direct
        train_chunk calls remain available for leaf-wise on CPU (used
        by tests)."""
        return (self.chunk_supported(is_eval)
                and self.tree_config.grow_policy == "depthwise")

    def _metric_spec(self, metric):
        """Cached device_spec per metric instance (NDCG builds large padded
        tables; no reason to rebuild them per chunk)."""
        cache = getattr(self, "_metric_spec_cache", None)
        if cache is None:
            cache = self._metric_spec_cache = {}
        spec = cache.get(id(metric))
        if spec is None:
            spec = cache[id(metric)] = metric.device_spec()
        return spec

    def train_chunk(self, k: int, limit: int = -1,
                    is_eval: bool = False) -> bool:
        """Run ``k`` boosting iterations as ONE device program.

        The reference pays a host round-trip per split; the per-iteration
        path above pays several per iteration (gradient dispatch, grow,
        score update, model readback — each a host<->device round
        trip).  This path lax.scans the whole iteration body —
        gradients → tree growth → score update — over k iterations, so the
        host is touched ONCE per chunk: upload of the per-iteration
        bagging/feature masks, readback of the k stacked tree arrays.

        Semantics match k calls of train_one_iter exactly (same RNG draws
        for bagging/feature sampling, same degenerate-tree stop, same
        per-iteration metric/early-stopping bookkeeping — metric values and
        valid-set scores are computed inside the program and consumed on the
        host post-hoc).  Returns True when training must stop.

        ``limit`` < k keeps only the first ``limit`` iterations and rolls
        the RNG streams and scores back to that point — used by run_training
        to serve a short tail with the full-size compiled program instead of
        re-compiling a second program for the remainder.  An early stop at
        iteration i similarly rolls back to i+1 kept iterations before the
        reference's model pop-back.
        """
        if not self.chunk_supported(is_eval):
            raise RuntimeError(
                "train_chunk requires a chunk-traceable objective and the "
                "serial, data-parallel or feature-parallel learner; any "
                "configured metric "
                "must have a device formulation (metrics/device.py) when "
                "evaluation is consumed, and goss=true is per-iteration "
                "only (see chunk_supported); use "
                "train_one_iter / run_training")
        if self._pipe is not None:
            # per-iteration entries pending (path switch): drain first
            if self.flush_pipeline():
                return True
        if self._pipeline_on():
            # pipelined: dispatch THIS chunk before consuming the previous
            # one, so the previous chunk's stacked-tree transfer (async
            # copy started at its dispatch) overlaps this chunk's device
            # execution.  A stop discovered in the previous chunk discards
            # this dispatch wholesale — the rollback rebuilds score/valid/
            # RNG from snapshots, so nothing of the surplus dispatch
            # survives (exact synchronous semantics).
            rec = self._dispatch_chunk(k, limit, is_eval)
            prev, self._pipe_chunk = self._pipe_chunk, rec
            if prev is not None and self._consume_chunk(
                    prev, newer_inflight=True):
                self._pipe_chunk = None
                return True
            return False
        if self._pipe_chunk is not None:
            # pipeline turned off with a chunk in flight: drain first
            if self.flush_pipeline():
                return True
        rec = self._dispatch_chunk(k, limit, is_eval)
        return self._consume_chunk(rec, newer_inflight=False)

    def _dispatch_chunk(self, k: int, limit: int, is_eval: bool) -> dict:
        """Dispatch one k-iteration chunk program (mask draws, program
        invocation, post-chunk score/valid installation, async readback
        start) and return the consumption record: output handles plus the
        pre-chunk snapshots _consume_chunk's stop paths rebuild from."""
        self._file_residency()
        mon = self._health_monitor
        has_bag = self._use_bagging
        has_ff = self.tree_config.feature_fraction < 1.0
        obj_key, obj_params, grad_fn = self.objective.chunk_spec()
        dp = self._learner is not _serial_learner
        pad = 0
        # no consumer -> no in-program evaluation: with output_freq == 0
        # and no early stopping the per-iteration path evaluates nothing
        # either
        eval_each = self._needs_eval(is_eval)
        train_specs = ([self._metric_spec(m)
                        for m in self.training_metrics]
                       if eval_each else [])
        valid_specs = ([[self._metric_spec(m) for m in ms]
                        for ms in self.valid_metrics] if eval_each else
                       [[] for _ in self.valid_metrics])
        from ..parallel.learners import FeatureParallelLearner
        fp = isinstance(self._learner, FeatureParallelLearner)
        # in-chunk GOSS (ISSUE 12): the static selection parameters ride
        # the program builders (and their cache keys); the per-iteration
        # key stream fold_in(PRNGKey(seed), iteration) matches the
        # per-iteration path's _goss_masks draw exactly
        goss = ((int(self.gbdt_config.bagging_seed), self._goss_top_cnt,
                 self._goss_other_cnt, float(self._goss_amp))
                if self._goss_on else None)
        if dp:
            extra = {} if fp else {
                "needs_global_score": getattr(self.objective,
                                              "needs_global_score", False)}
            if self._mp:
                extra["shard_layout"] = self._shard_layout
            extra["health"] = mon is not None
            extra["goss"] = goss
            fn, num_shards = self._learner.chunk_program(
                self, obj_key, grad_fn, obj_params, has_bag, has_ff,
                train_metric_fns=tuple(s[2] for s in train_specs),
                valid_metric_fns=tuple(tuple(s[2] for s in specs)
                                       for specs in valid_specs),
                n_valid=len(self.valid_datasets), **extra)
            # feature-parallel replicates rows — no shard padding
            pad = 0 if fp else (-self.num_data) % num_shards
        else:
            fn = _get_chunk_program(
                obj_key, grad_fn, self.num_class,
                float(self.gbdt_config.learning_rate),
                self.tree_config.grow_policy,
                num_leaves=_effective_num_leaves(self.tree_config),
                num_bins_max=self.num_bins_max,
                min_data_in_leaf=self.tree_config.min_data_in_leaf,
                min_sum_hessian_in_leaf=(
                    self.tree_config.min_sum_hessian_in_leaf),
                max_depth=self.tree_config.max_depth,
                hist_chunk=self.tree_config.hist_chunk,
                hist_dtype=self.tree_config.hist_dtype,
                quant_rounding=self.tree_config.quant_rounding,
                leafwise_compact=leafwise_compact_on(self.tree_config),
                packing=self._pack_spec,
                has_bag=has_bag, has_ff=has_ff,
                train_metric_fns=tuple(s[2] for s in train_specs),
                valid_metric_fns=tuple(tuple(s[2] for s in specs)
                                       for specs in valid_specs),
                health_fn=(mon.chunk_health_fn(None)
                           if mon is not None else None),
                goss=goss)

        C, N, F = self.num_class, self.num_data, self.num_features
        # snapshots for early/degenerate stops and tail truncation: training
        # must then look exactly like it stopped at that iteration — RNG
        # streams and train/valid scores included
        bag_state = self._bag_snapshot()
        ff_states = ([r.get_state() for r in self._feat_rngs]
                     if has_ff else None)
        score_before = self.score
        valid_before = [e["score"] for e in self.valid_datasets]
        # self.iter advances at CONSUMPTION; a pending pipelined chunk
        # means this dispatch's bagging-freq phase must start past its
        # planned iterations
        prev_rec = self._pipe_chunk
        base_iter = self.iter + (prev_rec["planned"]
                                 if prev_rec is not None else 0)
        if tracing.active():
            # chunk boundary on the flight-recorder timeline (ISSUE 16)
            tracing.event("train_chunk", base_iter=int(base_iter),
                          k=int(k))
        # in-chunk GOSS key stream: global iteration numbers ride the
        # scan xs (fold_in(PRNGKey(seed), iteration) in-program — the
        # rollback machinery needs NO snapshot, the draw is a pure
        # function of the iteration)
        if goss is not None:
            goss_iters = (np.asarray if self._host_inputs else jnp.asarray)(
                np.arange(base_iter, base_iter + k, dtype=np.int32))
            goss_args = (goss_iters,)
            telemetry.count("goss/iterations", k)
        else:
            goss_args = ()

        # multi-process runs keep replicated inputs host-side (every process
        # passes identical values; a committed local jnp array would clash
        # with the global-mesh program)
        _arr = np.asarray if self._host_inputs else jnp.asarray
        if has_bag and self._bag_device:
            # device bagging (ISSUE 8): the chunk's [k, C, N] mask stack
            # is computed ON DEVICE from the draw counter — the host
            # contributes k*C key bumps instead of k*C full-N draws plus
            # one k*C*N bool upload.  Non-redraw iterations carry the
            # previous device mask, exactly like the host stacking loop.
            masks = []
            for i in range(k):
                for cls in range(C):
                    self._draw_bag_mask(base_iter + i)
                    masks.append(self._bag_mask_device)
            rm = jnp.stack(masks).reshape(k, C, N)
            row_masks = (jnp.pad(rm, ((0, 0), (0, 0), (0, pad)))
                         if pad else rm)
        elif has_bag:
            # multi-process: local draws padded to the process block, then
            # lifted to one global row-sharded mask array
            width = self._mp_max_n if self._mp else N + pad
            fill = self._mp_local_n if self._mp else N
            rms = np.zeros((k, C, width), dtype=bool)
            for i in range(k):
                for cls in range(C):
                    self._draw_bag_mask(base_iter + i)
                    rms[i, cls, :fill] = self._bag_mask
            row_masks = (self._mp_make_global(rms, row_axis=2)
                         if self._mp else _arr(rms))
        else:
            row_masks = _arr(np.zeros((k, 1), bool))   # scan driver only
        if has_ff:
            fms = np.empty((k, C, F), dtype=bool)
            for i in range(k):
                for cls in range(C):
                    fms[i, cls] = self._feature_sample(cls)
            feat_masks = _arr(fms)
        else:
            feat_masks = _arr(np.zeros((k, 1), bool))

        if fp:
            own, ownmask = self._learner.chunk_args(self, num_shards)
            # multi-process FP: objective/metric device params were built
            # as process-local jnp arrays; ship them host-side ONCE so
            # every process passes identical replicated values to the
            # global-mesh program (the params are constant across chunks)
            if self._mp_fp:
                ck = (len(train_specs),
                      tuple(len(s) for s in valid_specs))
                cached = getattr(self, "_fp_host_params", None)
                if cached is None or cached[0] != ck:
                    cached = self._fp_host_params = (ck, jax.tree.map(
                        np.asarray,
                        (obj_params,
                         tuple(s[1] for s in train_specs),
                         tuple(tuple(s[1] for s in specs)
                               for specs in valid_specs))))
                obj_in, train_in, valid_in = cached[1]
            else:
                obj_in = obj_params
                train_in = tuple(s[1] for s in train_specs)
                valid_in = tuple(tuple(s[1] for s in specs)
                                 for specs in valid_specs)
            with telemetry.span("train_chunk") as sp:
                new_score, vscores_out, stacked, mvals, hvals = sp.fence(fn(
                    self.score, self.bins_device, self.num_bins_device,
                    own, ownmask, row_masks, feat_masks, obj_in,
                    train_in,
                    tuple(e["bins"] for e in self.valid_datasets),
                    tuple(e["score"] for e in self.valid_datasets),
                    valid_in, *goss_args))
            self.score = new_score
        elif dp:
            # pad rows to the shard grid once per booster; padded rows are
            # masked out of histograms/stats by valid_rows and their score
            # lane is sliced off again below
            cache = getattr(self, "_dp_chunk_inputs", None)
            if cache is None or cache[0] != num_shards:
                bins_p = (jnp.pad(self.bins_device, ((0, 0), (0, pad)))
                          if pad else self.bins_device)
                if getattr(self.objective, "needs_global_score", False):
                    # per-query tables are NOT row-aligned; they ride
                    # replicated and the gradient fn handles the padded
                    # score length itself
                    obj_p = obj_params
                else:
                    obj_p = jax.tree.map(
                        lambda l: (jnp.pad(l, [(0, pad)] + [(0, 0)]
                                           * (l.ndim - 1))
                                   if pad and getattr(l, "ndim", 0) >= 1
                                   else l),
                        obj_params)
                if self._mp:
                    # multi-process: per-process padding is interleaved
                    # (each rank's block ends with phantom rows), and
                    # num_data is already device-aligned (pad == 0)
                    valid_rows = self._row_valid
                else:
                    # commit the row-aligned inputs row-sharded on the
                    # learner's mesh ONCE: a one-device array would
                    # otherwise sit on device 0 and be re-distributed by
                    # every chunk dispatch (a no-op for a table GBDT.init
                    # or the streaming loader already placed so)
                    from jax.sharding import NamedSharding, PartitionSpec
                    from ..parallel.mesh import DATA_AXIS
                    mesh = self._learner._mesh()

                    def by_rows(a, leading=()):
                        return jax.device_put(a, NamedSharding(
                            mesh, PartitionSpec(*leading, DATA_AXIS)))
                    valid_rows = by_rows(jnp.arange(N + pad) < N)
                    bins_p = by_rows(bins_p, (None,))
                    if not getattr(self.objective, "needs_global_score",
                                   False):
                        obj_p = jax.tree.map(
                            lambda l: by_rows(l) if getattr(
                                l, "ndim", 0) == 1 else l, obj_p)
                cache = (num_shards, bins_p, obj_p, valid_rows)
                self._dp_chunk_inputs = cache
            _, bins_p, obj_p, valid_rows = cache
            score_in = (jnp.pad(self.score, ((0, 0), (0, pad)))
                        if pad else self.score)
            with telemetry.span("train_chunk") as sp:
                new_score, vscores_out, stacked, mvals, hvals = sp.fence(fn(
                    score_in, bins_p, self.num_bins_device, valid_rows,
                    row_masks, feat_masks, obj_p,
                    tuple(s[1] for s in train_specs),
                    tuple(e["bins"] for e in self.valid_datasets),
                    tuple(e["score"] for e in self.valid_datasets),
                    tuple(tuple(s[1] for s in specs)
                          for specs in valid_specs), *goss_args))
            self.score = new_score[:, :N] if pad else new_score
        else:
            with telemetry.span("train_chunk") as sp:
                self.score, vscores_out, stacked, mvals, hvals = sp.fence(fn(
                    self.score, self.bins_device, self.num_bins_device,
                    row_masks, feat_masks, obj_params,
                    tuple(s[1] for s in train_specs),
                    tuple(e["bins"] for e in self.valid_datasets),
                    tuple(e["score"] for e in self.valid_datasets),
                    tuple(tuple(s[1] for s in specs)
                          for specs in valid_specs), *goss_args))
        # post-chunk valid scores install NOW (the next dispatch reads
        # them); stop paths rebuild from valid_before absolutely, so the
        # early install is semantics-neutral
        vscores_out = tuple(np.asarray(s) if self._host_inputs else s
                            for s in vscores_out)
        for e, s in zip(self.valid_datasets, vscores_out):
            e["score"] = s
        # start the stacked-tree/metric/health transfers immediately: the
        # copies then overlap whatever the device runs next (pipelined
        # mode: the following chunk)
        try:
            for arr in jax.tree.leaves((stacked, mvals, hvals)):
                arr.copy_to_host_async()
        except Exception:
            pass
        return {
            "k": k, "limit": limit, "eval_each": eval_each, "mon": mon,
            "planned": k if limit < 0 else min(k, limit),
            "stacked": stacked, "mvals": mvals, "hvals": hvals,
            "vscores_out": vscores_out,
            "bag_state": bag_state, "ff_states": ff_states,
            "score_before": score_before, "valid_before": valid_before,
        }

    def _consume_chunk(self, rec: dict, newer_inflight: bool) -> bool:
        """Deferred consumption of one dispatched chunk: model readback,
        host tree construction, per-iteration metric/health/early-stop
        bookkeeping, surplus rollback — the synchronous tail of
        train_chunk, verbatim in order.  ``newer_inflight``: a younger
        chunk was already dispatched, so every stop path must roll back
        through the snapshots (erasing the younger chunk's installed
        score/valid/RNG state) even when this chunk kept all k
        iterations."""
        k, limit, eval_each, mon = (rec["k"], rec["limit"],
                                    rec["eval_each"], rec["mon"])
        stacked, mvals, hvals = rec["stacked"], rec["mvals"], rec["hvals"]
        vscores_out = rec["vscores_out"]
        bag_state, ff_states = rec["bag_state"], rec["ff_states"]
        score_before = rec["score_before"]
        valid_before = rec["valid_before"]
        C = self.num_class
        # stacked trees, metric values when evaluated, and the stacked
        # [k, H] in-program health vectors, one per iteration
        host, mvals_host, hvals_host = _read_back(
            (stacked, mvals if eval_each else None,
             hvals if mon is not None else None))
        telemetry.count("train/chunks")

        # per-iteration telemetry records: the fused program's phases are
        # indivisible from the host, so its wall time is amortized evenly
        # across the chunk's iterations (marked "amortized_over"); the
        # memory gauges are LEVELS, not durations — every record of the
        # chunk carries the same post-chunk sample
        if telemetry.sink_active():
            _chunk_dp, _chunk_dt = telemetry.take_phase_deltas()
            _chunk_mem = telemetry.take_memory_record()
            _scale = 1.0 / max(k, 1)

            def _emit(i: int, health=None, stopped=None) -> None:
                extra = {"amortized_over": k}
                if stopped:
                    extra["stopped"] = stopped
                telemetry.emit_iteration(
                    self.iter + i + 1,
                    {p: v * _scale for p, v in _chunk_dp.items()},
                    {p: v * _scale for p, v in _chunk_dt.items()},
                    eval_metrics=self._last_eval_values,
                    health=health, memory=_chunk_mem,
                    extra=extra)
        else:
            def _emit(i: int, health=None, stopped=None) -> None:
                pass

        keep_iters = k if limit < 0 else min(k, limit)
        esr = self.early_stopping_round
        for i in range(keep_iters):
            for cls in range(C):
                sub = jax.tree.map(lambda a: a[i, cls], host)
                nl = int(sub.num_leaves)
                if mon is not None:
                    mon.add_tree(nl, sub.split_gain, sub.leaf_count)
                if nl <= 1:
                    log.info("Can't training anymore, there isn't any leaf "
                             "meets split requirements.")
                    # the degenerate pair consumed its RNG draws but
                    # produced no tree
                    self._rollback_chunk(i * C + cls + 1, i * C + cls,
                                         bag_state, ff_states, score_before,
                                         valid_before)
                    if mon is not None:
                        # explain the stop (NaN/Inf gains reject every
                        # split): assemble this iteration's in-program
                        # vector and apply the policy before returning —
                        # marked like the per-iteration path so the
                        # rolled-back record is distinguishable from a
                        # trained iteration
                        block = mon.assemble(hvals_host[i])
                        _emit(i, health=block, stopped="degenerate_tree")
                        self.iter += i
                        mon.apply_policy(block, self.iter + 1)
                    else:
                        self.iter += i
                    return True
                with telemetry.span("tree_build"):
                    tree = self._to_host_tree(sub)
                    tree.shrinkage(self.gbdt_config.learning_rate)
                    self.models.append(tree)
            telemetry.count("train/iterations")
            if eval_each:
                train_vals, valid_vals = self._split_metric_values(
                    mvals_host[i])
                if self._consume_metric_values(self.iter + i + 1,
                                               train_vals, valid_vals):
                    kept = i + 1
                    health_i = (mon.assemble(hvals_host[i])
                                if mon is not None else None)
                    _emit(i, health=health_i)
                    log.info("Early stopping at iteration %d, the best "
                             "iteration round is %d"
                             % (self.iter + kept, self.iter + kept - esr))
                    # first restore state to exactly `kept` iterations
                    # (reference semantics: scores keep the popped trees'
                    # contributions, so roll back only the surplus scan
                    # iterations), THEN pop the early-stopping window
                    if kept < k or newer_inflight:
                        self._rollback_chunk(kept * C, kept * C, bag_state,
                                             ff_states, score_before,
                                             valid_before)
                    del self.models[len(self.models) - esr * C:]
                    self.iter += kept
                    if mon is not None:
                        mon.apply_policy(health_i, self.iter)
                    return True
            health_i = (mon.assemble(hvals_host[i])
                        if mon is not None else None)
            _emit(i, health=health_i)
            if mon is not None:
                from ..health import TrainingHealthError
                try:
                    mon.apply_policy(health_i, self.iter + i + 1)
                except TrainingHealthError:
                    # halt must leave the booster CONSISTENT at i+1 kept
                    # iterations, exactly like the early-stop branch: the
                    # scan already applied the whole chunk's score
                    # updates, so roll the surplus back before raising
                    kept = i + 1
                    if kept < k or newer_inflight:
                        self._rollback_chunk(kept * C, kept * C, bag_state,
                                             ff_states, score_before,
                                             valid_before)
                    self.iter += kept
                    self._pipe_chunk = None
                    raise
        if keep_iters < k:
            # tail truncation: only possible on the LAST chunk of a run
            # (limit < k), so no newer chunk can be in flight
            self._rollback_chunk(keep_iters * C, keep_iters * C,
                                 bag_state, ff_states, score_before,
                                 valid_before)
        # else: score/valid already installed at dispatch
        self.iter += keep_iters
        return False

    def _split_metric_values(self, vals: np.ndarray):
        """Unpack one iteration's concatenated device metric vector into
        (train_vals, valid_vals) lists shaped like the host eval path."""
        off = 0

        def take(metric):
            nonlocal off
            n = metric.n_values()
            out = [float(v) for v in vals[off:off + n]]
            off += n
            return out

        train_vals = [take(m) for m in self.training_metrics]
        valid_vals = [[take(m) for m in ms] for ms in self.valid_metrics]
        return train_vals, valid_vals

    def _rollback_chunk(self, replay_pairs: int, kept_trees: int,
                        bag_state, ff_states, score_before,
                        valid_before=()) -> None:
        """Restore exact per-iteration semantics after a chunk that kept
        fewer iterations than it ran (mid-chunk degenerate-tree stop, early
        stop, or a run_training tail served by the full-size program):
        rewind the bagging/feature RNG streams and replay exactly
        ``replay_pairs`` (iteration, class) draws, and rebuild the train and
        valid scores from the pre-chunk scores plus this chunk's
        ``kept_trees`` trees (the scan had already applied the discarded
        iterations' updates on device)."""
        C = self.num_class
        if bag_state is not None:
            self._bag_restore(bag_state)
            for p in range(replay_pairs):
                self._draw_bag_mask(self.iter + p // C)
        if ff_states is not None:
            for r, s in zip(self._feat_rngs, ff_states):
                r.set_state(s)
            for p in range(replay_pairs):
                self._feature_sample(p % C)

        kept = self.models[len(self.models) - kept_trees:] \
            if kept_trees > 0 else []
        max_nodes = max(_effective_num_leaves(self.tree_config) - 1, 1)
        train_fmap = (np.asarray(self._pack_spec.c2p, np.int32)
                      if getattr(self, "_pack_spec", None) is not None
                      else None)
        score = score_before
        vscores = list(valid_before)
        for m, tree in enumerate(kept):
            cls_m = m % C
            score = _replay_tree(score, self.bins_device, tree, cls_m,
                                 max_nodes, feat_map=train_fmap)
            for v, entry in enumerate(self.valid_datasets):
                vscores[v] = _replay_tree(vscores[v], entry["bins"], tree,
                                          cls_m, max_nodes)
        self.score = score
        for entry, s in zip(self.valid_datasets, vscores):
            entry["score"] = s

    def _to_host_tree(self, host) -> Tree:
        """Build the host Tree from an already-device_get'd TreeArrays."""
        n = int(host.num_leaves)
        split_feature = np.asarray(host.split_feature)[:n - 1]
        threshold_bin = np.asarray(host.threshold_bin)[:n - 1]
        # real-valued thresholds from bin upper bounds in float64 on host
        # (serial_tree_learner.cpp:418 BinToValue), via the precomputed
        # [F, B] upper-bound table
        thresholds = self._bin_upper_table[split_feature, threshold_bin]
        real_feature = self.train_data.real_feature_idx[split_feature]
        return Tree(
            num_leaves=n,
            split_feature=split_feature,
            split_feature_real=real_feature,
            threshold_bin=threshold_bin,
            threshold=thresholds,
            split_gain=np.asarray(host.split_gain, np.float64)[:n - 1],
            left_child=np.asarray(host.left_child)[:n - 1],
            right_child=np.asarray(host.right_child)[:n - 1],
            leaf_parent=np.asarray(host.leaf_parent)[:n],
            leaf_value=np.asarray(host.leaf_value, np.float64)[:n],
        )

    # --------------------------------------------------------------- metrics

    def _host_global_score(self, score=None) -> np.ndarray:
        """Training score as a host [C, N_true] array.  Multi-process mode
        replicates the row-sharded global score across the mesh (one
        all_gather) and compacts out the per-process padding blocks.
        ``score`` defaults to the live array (checkpoint_state passes the
        consumed-boundary reference)."""
        if score is None:
            score = self.score
        if not self._mp:
            return np.asarray(score)
        prog = getattr(self, "_mp_replicate_prog", None)
        if prog is None:
            from jax.sharding import NamedSharding, PartitionSpec
            prog = self._mp_replicate_prog = jax.jit(
                lambda s: s,
                out_shardings=NamedSharding(self._mp_mesh, PartitionSpec()))
        full = np.asarray(prog(score))
        return np.concatenate([full[:, s:s + ln]
                               for s, ln in self._shard_layout], axis=1)

    def output_metric(self, iteration: int) -> bool:
        """GBDT::OutputMetric (gbdt.cpp:225-259), host-eval path."""
        freq = self.gbdt_config.output_freq
        eval_now = freq > 0 and iteration % freq == 0
        train_vals = None
        if eval_now and self.training_metrics:
            score_np = self._host_global_score()
            flat = (score_np.reshape(-1) if self.num_class > 1
                    else score_np[0])
            train_vals = [m.eval(flat) for m in self.training_metrics]
        valid_vals = None
        if self.valid_datasets and (eval_now
                                    or self.early_stopping_round > 0):
            valid_vals = []
            for i, entry in enumerate(self.valid_datasets):
                score_np = np.asarray(entry["score"])
                flat = (score_np.reshape(-1) if self.num_class > 1
                        else score_np[0])
                valid_vals.append([m.eval(flat)
                                   for m in self.valid_metrics[i]])
        return self._consume_metric_values(iteration, train_vals, valid_vals)

    def _consume_metric_values(self, iteration: int, train_vals,
                               valid_vals) -> bool:
        """Shared logging + early-stopping bookkeeping over metric VALUES
        (computed on host by output_metric, or on device by train_chunk).
        Mirrors gbdt.cpp:225-259: train metrics print on output_freq
        boundaries; valid metrics additionally drive the best-score /
        early-stop state every iteration."""
        freq = self.gbdt_config.output_freq
        eval_now = freq > 0 and iteration % freq == 0
        ret = False
        if telemetry.sink_active():
            vals = {}
            if train_vals is not None:
                for metric, values in zip(self.training_metrics, train_vals):
                    vals["training/" + metric.name] = list(values)
            if valid_vals is not None:
                for i, entry in enumerate(self.valid_datasets):
                    for j, metric in enumerate(self.valid_metrics[i]):
                        vals[entry["name"] + "/" + metric.name] = list(
                            valid_vals[i][j])
            if vals:
                self._last_eval_values = vals
        if self._health_monitor is not None:
            # eval-divergence tracking (health_divergence_rounds consecutive
            # worsening iterations flag an anomaly; both eval paths — host
            # and in-chunk — land here every iteration)
            mon = self._health_monitor
            if train_vals is not None:
                for metric, values in zip(self.training_metrics, train_vals):
                    mon.observe_eval("training/" + metric.name,
                                     float(values[-1]),
                                     metric.is_bigger_better)
            if valid_vals is not None:
                for i, entry in enumerate(self.valid_datasets):
                    for j, metric in enumerate(self.valid_metrics[i]):
                        mon.observe_eval(
                            entry["name"] + "/" + metric.name,
                            float(valid_vals[i][j][-1]),
                            metric.is_bigger_better)
        if eval_now and train_vals is not None:
            for metric, values in zip(self.training_metrics, train_vals):
                log.info("Iteration:%d, %s : %s"
                         % (iteration, metric.name,
                            " ".join(str(v) for v in values)))
        if valid_vals is not None:
            for i in range(len(self.valid_datasets)):
                for j, metric in enumerate(self.valid_metrics[i]):
                    values = valid_vals[i][j]
                    if eval_now:
                        log.info("Iteration:%d, %s : %s"
                                 % (iteration, metric.name,
                                    " ".join(str(v) for v in values)))
                    if not ret and self.early_stopping_round > 0:
                        bigger_better = metric.is_bigger_better
                        last = values[-1]
                        if (self.best_score[i][j] < 0
                                or (not bigger_better
                                    and last < self.best_score[i][j])
                                or (bigger_better
                                    and last > self.best_score[i][j])):
                            self.best_score[i][j] = last
                            self.best_iter[i][j] = iteration
                        elif (iteration - self.best_iter[i][j]
                                >= self.early_stopping_round):
                            ret = True
        return ret

    # ------------------------------------------------------------ prediction

    # device batch prediction pays ~one dispatch of link latency; below this
    # rows x trees volume the host numpy walk wins
    _DEVICE_PREDICT_THRESHOLD = 20_000_000

    def capture_score_reference(self) -> Optional[dict]:
        """Serialize the live training scores into a
        monitor.ScoreHistogram dict — the drift-detection baseline
        (ISSUE 20).  Recaptured from the CURRENT scores on every call
        while the booster holds score state, so a mid-training
        checkpoint save cannot freeze an early-iteration reference into
        a later final model (the elastic resume path compares final
        model text byte-for-byte).  A booster with no score state
        (fresh load, prediction-only) keeps the reference
        ``models_from_string`` parsed, or returns None."""
        score = getattr(self, "score", None)
        if score is None:
            return self.score_reference
        try:
            from ..monitor import ScoreHistogram
            values = np.asarray(score, dtype=np.float64)
            # true rows only: per-topology padding rows accumulate leaf
            # values too, and two topologies pad differently — the
            # reference must not depend on the mesh shape
            n = int(getattr(self, "num_data", 0)) or values.shape[-1]
            values = values[..., :n].ravel()
            if values.size == 0:
                return None
            hist = ScoreHistogram()
            hist.record_many(values)
            self.score_reference = hist.to_dict()
        except Exception:
            return None
        return self.score_reference

    def export_flat(self, num_models: int = -1):
        """Flatten the first ``num_models`` trees (all when < 0) into a
        serving.FlatEnsemble: stacked per-node tensors + the host-built
        f64 rank-code tables.  This is the once-per-model encode the old
        per-call ``_device_predict_encode`` re-ran on every predict."""
        from ..serving import FlatEnsemble
        models = self.models if num_models < 0 else self.models[:num_models]
        flat = FlatEnsemble.from_models(models, self.num_class)
        # the drift reference rides the flattened ensemble so a
        # ServingFront can register it without ever touching the booster
        flat.score_reference = self.capture_score_reference()
        return flat

    def serving_engine(self, num_models: int = -1, **options):
        """The cached compiled serving engine over the first
        ``num_models`` trees (serving.ServingEngine: bucketed batch
        shapes, donated buffers, breadth-first lockstep scoring).  The
        cache key includes the model count, so continued training (or a
        pipeline rollback popping trees) re-flattens naturally."""
        if num_models < 0:
            num_models = len(self.models)
        key = (len(self.models), num_models, tuple(sorted(options.items())))
        cached = getattr(self, "_serve_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from ..serving import ServingEngine
        engine = ServingEngine(self.export_flat(num_models), **options)
        self._serve_cache = (key, engine)
        return engine

    def _device_predict_encode(self, features: np.ndarray, models):
        """Back-compat shim over serving.FlatEnsemble: rank-encoded codes
        plus the stacked per-tree arrays (the old per-call flatten).  New
        code should use export_flat()/serving_engine() — those cache the
        flatten across calls."""
        from ..serving import FlatEnsemble
        flat = FlatEnsemble.from_models(models, self.num_class)
        codes = flat.encode(features)
        return codes, (flat.split_feature, flat.threshold_rank,
                       flat.left_child, flat.right_child, flat.leaf_value,
                       flat.num_leaves), flat.max_nodes

    def _predict_scores_device(self, features: np.ndarray,
                               models) -> np.ndarray:
        """[num_class, N] raw ensemble sums via the compiled serving
        engine (models must be a prefix of self.models — every caller
        passes self.models[:n])."""
        engine = self.serving_engine(len(models))
        return engine.scores(features)

    def predict_raw(self, features: np.ndarray,
                    num_used_model: int = -1) -> np.ndarray:
        """Batch PredictRaw (gbdt.cpp:470-479); features [N, cols] raw."""
        if num_used_model < 0:
            num_used_model = len(self.models)
        models = self.models[:num_used_model]
        if features.shape[0] * max(len(models), 1) \
                >= self._DEVICE_PREDICT_THRESHOLD:
            return self._predict_scores_device(features, models)[0]
        out = np.zeros(features.shape[0], dtype=np.float64)
        for tree in models:
            out += tree.predict(features)
        return out

    def predict(self, features: np.ndarray,
                num_used_model: int = -1) -> np.ndarray:
        """Predict with sigmoid transform when applicable (gbdt.cpp:481-494)."""
        ret = self.predict_raw(features, num_used_model)
        if self.sigmoid > 0:
            ret = 1.0 / (1.0 + np.exp(-2.0 * self.sigmoid * ret))
        return ret

    def predict_multiclass(self, features: np.ndarray,
                           num_used_model: int = -1) -> np.ndarray:
        """[N, num_class] softmax probabilities (gbdt.cpp:496-508)."""
        if num_used_model < 0:
            num_used_model = len(self.models) // self.num_class
        models = self.models[:num_used_model * self.num_class]
        if features.shape[0] * max(len(models), 1) \
                >= self._DEVICE_PREDICT_THRESHOLD:
            out = self._predict_scores_device(features, models).T
        else:
            out = np.zeros((features.shape[0], self.num_class),
                           dtype=np.float64)
            for i in range(num_used_model):
                for j in range(self.num_class):
                    out[:, j] += self.models[i * self.num_class
                                             + j].predict(features)
        z = out - out.max(axis=1, keepdims=True)
        p = np.exp(z)
        return p / p.sum(axis=1, keepdims=True)

    def predict_leaf_index(self, features: np.ndarray,
                           num_used_model: int = -1) -> np.ndarray:
        """[N, num_models] leaf indices (gbdt.cpp:510-519)."""
        if num_used_model < 0:
            num_used_model = len(self.models)
        models = self.models[:num_used_model]
        if features.shape[0] * max(len(models), 1) \
                >= self._DEVICE_PREDICT_THRESHOLD:
            return self.serving_engine(len(models)).leaf_indices(features)
        cols = []
        for tree in models:
            if tree.num_leaves == 1:
                cols.append(np.zeros(features.shape[0], dtype=np.int32))
            else:
                cols.append(tree.leaf_index_by_replay(features))
        return np.stack(cols, axis=1)

    # -------------------------------------------------------------- model IO

    def save_model_to_file(self, is_finish: bool, filename: str) -> None:
        """Incremental text save (gbdt.cpp:307-348): header once, then newly
        finished trees appended each call, withholding the trailing
        early-stopping window until finish."""
        if self._saved_model_size == -1:
            self._model_file = open(filename, "w")
            self._model_file.write("gbdt\n")
            self._model_file.write("num_class=%d\n" % self.num_class)
            self._model_file.write("label_index=%d\n" % self.label_idx)
            self._model_file.write("max_feature_idx=%d\n" % self.max_feature_idx)
            self._model_file.write("sigmoid=%s\n" % _fmt(self.sigmoid))
            self._model_file.write("\n")
            self._saved_model_size = 0
        if self._model_file is None or self._model_file.closed:
            return
        rest = len(self.models) - self.early_stopping_round * self.num_class
        for i in range(self._saved_model_size, rest):
            self._model_file.write("Tree=%d\n" % i)
            self._model_file.write(self.models[i].to_string() + "\n")
        self._saved_model_size = max(self._saved_model_size, rest)
        self._model_file.flush()
        if is_finish:
            for i in range(max(self._saved_model_size, 0), len(self.models)):
                self._model_file.write("Tree=%d\n" % i)
                self._model_file.write(self.models[i].to_string() + "\n")
            reference = self.capture_score_reference()
            if reference is not None:
                # training-time score distribution, the serving drift
                # detector's comparison baseline (ISSUE 20).  Written at
                # FINISH, not in the header: the header goes out on the
                # first incremental save, which would freeze an
                # early-iteration distribution into the final model
                # (find_value parses it wherever it sits).
                self._model_file.write(
                    "score_reference=%s\n"
                    % json.dumps(reference, separators=(",", ":")))
            self._model_file.write("\n" + self.feature_importance() + "\n")
            self._model_file.close()

    def models_from_string(self, model_str: str) -> None:
        """GBDT::ModelsFromString (gbdt.cpp:350-441)."""
        self.models = []
        lines = model_str.split("\n")

        def find_value(key):
            for line in lines:
                if key in line and "=" in line:
                    return line.split("=", 1)[1].strip()
            return None

        num_class = find_value("num_class=")
        if num_class is None:
            log.fatal("Model file doesn't contain number of class")
        self.num_class = int(num_class)
        label_index = find_value("label_index=")
        if label_index is None:
            log.fatal("Model file doesn't contain label index")
        self.label_idx = int(label_index)
        max_feature_idx = find_value("max_feature_idx=")
        if max_feature_idx is None:
            log.fatal("Model file doesn't contain max_feature_idx")
        self.max_feature_idx = int(max_feature_idx)
        sigmoid = find_value("sigmoid=")
        self.sigmoid = float(sigmoid) if sigmoid is not None else -1.0
        reference = find_value("score_reference=")
        if reference is not None:
            try:
                self.score_reference = json.loads(reference)
            except Exception:
                self.score_reference = None

        i = 0
        while i < len(lines):
            if "Tree=" in lines[i]:
                i += 1
                start = i
                while i < len(lines) and "Tree=" not in lines[i]:
                    i += 1
                self.models.append(Tree.from_string("\n".join(lines[start:i])))
            else:
                i += 1
        log.info("%d models has been loaded" % len(self.models))

    @classmethod
    def from_model_file(cls, filename: str) -> "GBDT":
        """Boosting::CreateBoosting from file (boosting.cpp:6-57)."""
        with open(filename, "r") as f:
            content = f.read()
        first_line = content.split("\n", 1)[0].strip()
        if first_line != "gbdt":
            log.fatal("Unknown boosting type %s" % first_line)
        self = cls()
        self.models_from_string(content)
        return self

    def feature_importance(self) -> str:
        """Split-count importances (gbdt.cpp:443-468)."""
        importances = np.zeros(self.max_feature_idx + 1, dtype=np.int64)
        for tree in self.models:
            for f in tree.split_feature_real:
                importances[f] += 1
        names = (self.train_data.feature_names if self.train_data is not None
                 else [f"Column_{i}" for i in range(self.max_feature_idx + 1)])
        pairs = sorted(zip(importances, names),
                       key=lambda p: -p[0])
        out = ["", "feature importances:"]
        for cnt, name in pairs:
            out.append(f"{name}={cnt}")
        return "\n".join(out) + "\n"


@functools.partial(jax.jit, static_argnames=("cls",))
def _add_leaf_values(score, shrunk, leaf_ids, *, cls):
    """``score`` with each row's leaf value added to class ``cls``: the
    per-tree loop's pass over the rows as ONE program under the
    ``score_update`` device scope, as the chunk body has it (a scope
    around eager operations does not enter their programs).  The shrunk
    [num_leaves] values are made outside: one program with the multiply
    in it rounds the add differently on a CPU, and the per-tree loop and
    the fused chunk grow the same model bit for bit."""
    with telemetry.phase_scope("score_update"):
        return score.at[cls].add(_leaf_lookup(shrunk, leaf_ids))


def _read_back(tree):
    """The model readback, ``jax.device_get(tree)``, in its three parts.
    With telemetry on the host first waits for the device under a span of
    its own (``device_wait``): spans are not fenced by default and the
    readback is where the host first blocks, so without it
    ``model_readback`` would time the program it waits for.  Then the
    copy (``model_readback``), then ``train/readback_bytes``, counted
    where the bytes are consumed."""
    if telemetry.enabled():
        with telemetry.span("device_wait"):
            jax.block_until_ready(tree)
    with telemetry.span("model_readback"):
        host = jax.device_get(tree)
    if telemetry.enabled():
        telemetry.count("train/readback_bytes", sum(
            int(getattr(a, "nbytes", 0)) for a in jax.tree.leaves(host)))
    return host


# Compiled k-iteration chunk programs, shared process-wide.  Keyed ONLY on
# hashable statics — per-dataset arrays (labels, weights, bins) enter as
# runtime inputs via obj_params, so the traced HLO is data-independent and a
# cross-validation loop or repeated lgb.train calls re-use one compile (and
# the persistent XLA cache can hit across processes).
_CHUNK_PROGRAMS: dict = {}


def make_chunk_body(*, grad_fn, obj_params, num_class: int, lrf, grow_fn,
                    has_bag: bool, has_ff: bool, bins, num_bins,
                    base_mask=None, max_nodes: int = 1,
                    valid_bins=(), valid_mparams=(),
                    train_metric_fns=(), train_mparams=(),
                    valid_metric_fns=(), health_fn=None, goss_fn=None):
    """The per-iteration boosting body shared by the serial chunk program
    and the data-parallel shard_map chunk (parallel/learners.py):
    gradients → per-class grow → train-score update (+ valid-score replay
    and in-program metric evaluation when configured).  ``grow_fn`` carries
    the grower statics — and, for the data-parallel case, the psum
    hist/stat reducers; ``base_mask`` is the always-on row validity mask
    (shard padding) and composes with the per-iteration bagging mask.
    ``health_fn`` (health.make_health_fn) accumulates the per-iteration
    training-health vector in-program — the fused chunk is the only place
    those per-iteration values exist; the vector is pure extra reductions
    over the existing arrays, never fed back into them.

    ``goss_fn`` (ISSUE 12): in-program GOSS selection — called as
    ``(iteration, grad, hess) -> (grad', hess', mask)`` on each
    iteration's RAW gradients before the per-class grows, exactly where
    the per-iteration path runs ``gbdt._goss_masks``.  The selection
    mask replaces the bagging row mask (GOSS excludes bagging by config)
    and the amplified grad'/hess' feed the growers; health and the next
    iteration's gradients keep the raw arrays.  When set, the scan xs
    carry a third element: the per-iteration GLOBAL iteration numbers
    (the GOSS key stream is ``fold_in(PRNGKey(seed), iteration)``, same
    as the per-iteration path — fused == per-iteration selection is
    bit-identical)."""
    F, N = bins.shape
    n_valid = len(valid_bins)

    def body(carry, xs):
        score, vscores = carry
        if goss_fn is None:
            rmask, fmask = xs
        else:
            rmask, fmask, goss_it = xs
        # every piece of device work below sits under one scope of
        # telemetry.DEVICE_PHASES, unconditionally: a device trace splits
        # the iteration by these names whether or not telemetry is armed
        with telemetry.phase_scope("gradient"):
            grad, hess = grad_fn(obj_params,
                                 score if num_class > 1 else score[0])
            if num_class == 1:
                grad, hess = grad[None], hess[None]
            if goss_fn is not None:
                g_grow, h_grow, goss_mask = goss_fn(goss_it, grad, hess)
            else:
                g_grow, h_grow, goss_mask = grad, hess, None
        outs = []
        vscores = list(vscores)
        ones = (base_mask if base_mask is not None
                else jnp.ones((N,), jnp.bool_))
        for cls in range(num_class):
            if goss_mask is not None:
                rm = goss_mask & ones
            else:
                rm = (rmask[cls] & ones) if has_bag else ones
            fm = fmask[cls] if has_ff else jnp.ones((F,), jnp.bool_)
            ta = grow_fn(bins, g_grow[cls], h_grow[cls], rm, fm, num_bins)
            with telemetry.phase_scope("score_update"):
                shrunk = jnp.where(ta.num_leaves > 1, ta.leaf_value * lrf,
                                   0.0)
                score = score.at[cls].add(_leaf_lookup(shrunk, ta.leaf_ids))
                # valid scores by tree replay (gbdt.cpp:220-222)
                for v in range(n_valid):
                    vscores[v] = vscores[v].at[cls].set(add_tree_score(
                        valid_bins[v], vscores[v][cls], ta.split_feature,
                        ta.threshold_bin, ta.left_child, ta.right_child,
                        shrunk, ta.num_leaves, max_nodes=max_nodes))
            outs.append(ta._replace(leaf_ids=jnp.zeros((0,), jnp.int32)))
        with telemetry.phase_scope("tree_pack"):
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

        # in-program metric evaluation (Metric::Eval on CPU threads in the
        # reference; here the scores never leave the device)
        with telemetry.phase_scope("eval"):
            mv = []
            for f, p in zip(train_metric_fns, train_mparams):
                mv.append(f(p, score if num_class > 1 else score[0]))
            for v in range(n_valid):
                sv = vscores[v] if num_class > 1 else vscores[v][0]
                for f, p in zip(valid_metric_fns[v], valid_mparams[v]):
                    mv.append(f(p, sv))
            mvals = (jnp.concatenate(mv) if mv
                     else jnp.zeros((0,), jnp.float32))
            hvec = (health_fn(grad, hess, score) if health_fn is not None
                    else jnp.zeros((0,), jnp.float32))
        return (score, tuple(vscores)), (stacked, mvals, hvec)

    return body


def _get_chunk_program(obj_key, grad_fn, num_class: int, lr: float,
                       grow_policy: str, *, num_leaves: int,
                       num_bins_max: int, min_data_in_leaf: int,
                       min_sum_hessian_in_leaf: float, max_depth: int,
                       hist_chunk: int = 0, hist_dtype: str = "float32",
                       quant_rounding: str = "nearest",
                       leafwise_compact: bool = False,
                       packing=None,
                       has_bag: bool, has_ff: bool,
                       train_metric_fns: tuple = (),
                       valid_metric_fns: tuple = (),
                       health_fn=None, goss=None):
    # the RESOLVED pallas-partition/DMA-overlap bits (and the backend
    # identity) are part of the key: __graft_entry__ flips
    # LGBM_TPU_NO_PALLAS mid-process (PROFILE.md's A/B flips
    # LGBM_TPU_PARTITION_NO_OVERLAP), and a stale program would keep the
    # old kernel routing
    from ..ops.compact import pallas_partition_ok, partition_overlap_on
    use_pp = leafwise_compact and grow_policy != "depthwise" \
        and pallas_partition_ok()
    key = (obj_key, id(grad_fn), num_class, lr, grow_policy, num_leaves,
           num_bins_max, min_data_in_leaf, min_sum_hessian_in_leaf,
           max_depth, hist_chunk, hist_dtype, quant_rounding,
           leafwise_compact, use_pp, use_pp and partition_overlap_on(),
           packing, goss,
           jax.default_backend(), has_bag, has_ff,
           tuple(id(f) for f in train_metric_fns),
           tuple(tuple(id(f) for f in fns) for fns in valid_metric_fns),
           id(health_fn) if health_fn is not None else None)
    prog = _CHUNK_PROGRAMS.get(key)
    if prog is not None:
        return prog

    grower_kwargs = dict(
        num_leaves=num_leaves, num_bins_max=num_bins_max,
        min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf, max_depth=max_depth,
        packing=packing,
        **_tuning_kwargs(hist_chunk, hist_dtype, quant_rounding))
    if grow_policy == "depthwise":
        from .grower_depthwise import grow_tree_depthwise as grow
    elif leafwise_compact:
        # the resolved leafwise_compact flag keeps the chunk path (used
        # by direct train_chunk calls — leaf-wise production training is
        # per-iteration) on the SAME grower as the per-iteration path
        import functools as _ft
        from .grower_leafcompact import grow_tree_leafcompact_impl
        grow = _ft.partial(
            grow_tree_leafcompact_impl,
            use_pallas_partition=use_pp,
            partition_overlap=partition_overlap_on())
    else:
        from .grower import grow_tree_impl as grow
    lrf = jnp.float32(lr)
    max_nodes = max(num_leaves - 1, 1)
    goss_fn = make_goss_fn(goss) if goss is not None else None

    def chunk_fn(score, bins, num_bins, row_masks, feat_masks, obj_params,
                 train_mparams, valid_bins, valid_scores, valid_mparams,
                 goss_iters=None):
        body = make_chunk_body(
            grad_fn=grad_fn, obj_params=obj_params, num_class=num_class,
            lrf=lrf,
            grow_fn=lambda *a: grow(*a, **grower_kwargs),
            has_bag=has_bag, has_ff=has_ff, bins=bins, num_bins=num_bins,
            max_nodes=max_nodes, valid_bins=valid_bins,
            valid_mparams=valid_mparams,
            train_metric_fns=train_metric_fns, train_mparams=train_mparams,
            valid_metric_fns=valid_metric_fns, health_fn=health_fn,
            goss_fn=goss_fn)
        xs = ((row_masks, feat_masks) if goss_fn is None
              else (row_masks, feat_masks, goss_iters))
        (score, vscores), (stacked, mvals, hvals) = jax.lax.scan(
            body, (score, tuple(valid_scores)), xs)
        return score, vscores, stacked, mvals, hvals

    from .. import costmodel
    prog = costmodel.instrument("chunk/serial", jax.jit(chunk_fn),
                                phase="train_chunk")
    _CHUNK_PROGRAMS[key] = prog
    return prog


def make_goss_fn(goss):
    """In-program GOSS selection over FULL rows (the serial chunk scan
    and the feature-parallel chunk, whose rows are replicated): the
    per-iteration ``_goss_masks`` draw traced into the chunk body.
    ``goss`` is the static ``(seed, top_cnt, other_cnt, amp)`` tuple;
    the key stream is ``fold_in(PRNGKey(seed), iteration)`` — exactly
    the per-iteration path's, so fused == per-iteration selection is
    bit-identical.  The data-parallel variant (gathered global scores,
    padded-row layouts) lives in parallel/learners.chunk_program."""
    seed, top_cnt, other_cnt, amp = goss
    from ..ops import sampling as _sampling

    def goss_fn(it, grad, hess):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), it)
        mask, w = _sampling.goss_mask_weights(
            key, _sampling.goss_row_scores(grad), top_cnt, other_cnt,
            amp)
        return grad * w, hess * w, mask
    return goss_fn


def _tuning_kwargs(hist_chunk: int, hist_dtype: str,
                   quant_rounding: str = "nearest") -> dict:
    """Grower kwargs for the TPU tuning knobs (TreeConfig extensions)."""
    kwargs = {}
    if hist_chunk > 0:
        kwargs["hist_chunk"] = hist_chunk
    if hist_dtype == "bfloat16":
        kwargs["compute_dtype"] = jnp.bfloat16
    elif hist_dtype == "int8":
        # string sentinel (hashable jit static): quantized-gradient path,
        # dispatched per backend in the histogram ops; the "_sr" variant
        # rounds stochastically (unbiased, value-keyed bits)
        kwargs["compute_dtype"] = ("int8_sr"
                                   if quant_rounding == "stochastic"
                                   else "int8")
    return kwargs


def leafwise_compact_on(tree_config) -> bool:
    """Single home of the leafwise_compact resolution rule: "auto" means
    on for the TPU backend (the compacted grower's Pallas partition is
    TPU-scheduled; CPU keeps the masked grower so golden tests stay on
    the historical path), explicit "true"/"false" win.  Shared by the
    serial learner, both chunk-program builders, and the data-parallel
    learner."""
    c = getattr(tree_config, "leafwise_compact", "auto")
    if c == "auto":
        return jax.default_backend() == "tpu"
    return c == "true"


def _configured_learner(boosting_config):
    """The parallel learner ``tree_learner`` names, built as task=train
    builds it from the same ``key=value`` pairs (cli.Application.
    init_train), for a caller that hands ``GBDT.init`` none."""
    from ..config import OverallConfig
    from ..parallel import create_parallel_learner
    config = OverallConfig(boosting_config=boosting_config,
                           device_type=boosting_config.device_type,
                           is_parallel=True)
    config.network_config.num_machines = boosting_config.num_machines
    return create_parallel_learner(config)


def _row_sharding(learner, rows: int):
    """Where a single-process data-parallel learner's chunk program reads
    the ``[F, rows]`` bin table: cut by rows over the learner's mesh, so
    that each chip holds its own rows and no chip the whole table.  None
    for any other learner, or rows that do not divide evenly (the chunk
    program then pads a copy)."""
    from ..parallel.learners import DataParallelLearner
    if not isinstance(learner, DataParallelLearner):
        return None
    from jax.sharding import NamedSharding, PartitionSpec
    from ..parallel.mesh import DATA_AXIS
    mesh = learner._mesh()
    if rows % mesh.shape[DATA_AXIS]:
        return None
    return NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))


def _serial_learner(gbdt: GBDT, bins, grad, hess, row_mask, feature_mask):
    """Default learner: single-device tree growth, leaf-wise (reference
    parity) or depth-wise (TPU throughput) per ``grow_policy``."""
    kwargs = dict(
        num_leaves=_effective_num_leaves(gbdt.tree_config),
        num_bins_max=gbdt.num_bins_max,
        min_data_in_leaf=gbdt.tree_config.min_data_in_leaf,
        min_sum_hessian_in_leaf=gbdt.tree_config.min_sum_hessian_in_leaf,
        max_depth=gbdt.tree_config.max_depth,
        packing=gbdt._pack_spec,
        **_tuning_kwargs(gbdt.tree_config.hist_chunk,
                         gbdt.tree_config.hist_dtype,
                         gbdt.tree_config.quant_rounding))
    if gbdt.tree_config.grow_policy == "depthwise":
        from .grower_depthwise import grow_tree_depthwise_jit
        return grow_tree_depthwise_jit(bins, grad, hess, row_mask,
                                       feature_mask, gbdt.num_bins_device,
                                       **kwargs)
    if leafwise_compact_on(gbdt.tree_config):
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        from .grower_leafcompact import grow_tree_leafcompact
        # both bits are jit STATICS, so an env flip re-dispatches here
        # (the chunk-program caches carry them in their keys instead)
        return grow_tree_leafcompact(
            bins, grad, hess, row_mask, feature_mask, gbdt.num_bins_device,
            use_pallas_partition=pallas_partition_ok(),
            partition_overlap=partition_overlap_on(),
            **kwargs)
    return grow_tree(
        bins, grad, hess, row_mask, feature_mask, gbdt.num_bins_device,
        **kwargs)


def _replay_tree(score, bins, tree, cls_m: int, max_nodes: int,
                 feat_map=None):
    """Apply one host tree's score contribution to class ``cls_m`` of a
    [C, N] score by replaying the split sequence on the binned matrix —
    the chunk rollback's rebuild rule, factored out of
    ``_rollback_chunk``.  NOT bitwise-equal to the in-grow f32 update:
    the host tree's shrunk leaf values went through an f64
    learning-rate product, which can round 1 ulp away from the device's
    f32 product — both rollback sides share this path, so the rollback
    equivalence pins hold; checkpoints store raw scores instead
    (lightgbm_tpu/checkpoint.py).

    ``feat_map``: canonical inner feature -> row of ``bins``; the TRAIN
    matrix is in packed (mixed-bin) feature order while
    ``tree.split_feature`` is canonical, valid matrices are canonical."""
    pad = lambda a: np.pad(np.asarray(a), (0, max_nodes - len(a)))
    sf = np.asarray(tree.split_feature)
    if feat_map is not None and len(sf):
        sf = feat_map[sf]
    leaf_vals = np.zeros(max_nodes + 1, np.float32)
    leaf_vals[:tree.num_leaves] = tree.leaf_value
    new_cls = add_tree_score(
        bins, score[cls_m],
        pad(sf),
        pad(tree.threshold_bin),
        pad(tree.left_child),
        pad(tree.right_child),
        leaf_vals,
        np.int32(tree.num_leaves),
        max_nodes=max_nodes)
    if isinstance(score, np.ndarray):
        # multi-process valid scores stay host-side numpy
        score = score.copy()
        score[cls_m] = np.asarray(new_cls)
        return score
    return score.at[cls_m].set(new_cls)


def _effective_num_leaves(tree_config) -> int:
    """num_leaves capped by 2^(max_depth-1) (config.h:159-163)."""
    n = tree_config.num_leaves
    if tree_config.max_depth > 0:
        n = min(n, 1 << (tree_config.max_depth - 1))
    return max(n, 2)


def _fmt(x: float) -> str:
    return repr(float(x))
