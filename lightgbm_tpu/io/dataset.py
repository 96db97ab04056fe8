"""Dataset: host-side loading, binning, and the device bin matrix.

Re-design of /root/reference/src/io/dataset.cpp:18-909 for TPU.  The load
pipeline is preserved (column-role resolution by index or ``name:`` prefix,
reservoir sampling ≤50k rows for binning, BinMapper construction, trivial
feature removal, row sharding for distributed training, binary cache), but
the storage layout inverts the reference's per-feature Bin objects: the whole
dataset becomes ONE dense ``[num_features, num_rows]`` integer matrix of bin
indices (uint8 when max_bin ≤ 256), which is exactly the array a TPU histogram
kernel wants in HBM.  Sparse/ordered-bin machinery (sparse_bin.hpp,
ordered_sparse_bin.hpp) is a CPU cache optimization and is deliberately not
reproduced.
"""
from __future__ import annotations

import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from struct import error as struct_error
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import telemetry
from ..utils import log
from . import parser as parser_mod
from .binning import BinMapper, find_bins_for_matrix
from .metadata import Metadata

SAMPLE_CNT = 50000  # dataset.cpp:219 — max rows sampled for bin finding
BINARY_MAGIC = b"LGBM_TPU_BIN_V1"
# binarize: rows a task quantizes of one column, and the threads at most
BINARIZE_BLOCK_ROWS = 1 << 20
BINARIZE_THREADS = 16


def _bin_dtype(max_num_bin: int):
    """uint8/16/32 selection mirrors Bin::CreateDenseBin (bin.cpp:202-210)."""
    if max_num_bin <= 256:
        return np.uint8
    if max_num_bin <= 65536:
        return np.uint16
    return np.uint32


class Dataset:
    """Binned dataset.

    Attributes
    ----------
    bins : np.ndarray [num_features, num_data]
        Bin index per (used feature, row).
    bin_mappers : list[BinMapper]
        Per used feature.
    num_bins : np.ndarray [num_features]
        Bins per used feature.
    real_feature_idx : np.ndarray [num_features]
        Used-feature → original column index (after label removal), i.e. the
        reference's ``split_feature_real`` space (dataset.cpp used_feature_map).
    """

    def __init__(self):
        self.data_filename: str = ""
        self.bins: Optional[np.ndarray] = None
        # streaming ingestion (io/streaming.py): single-process streamed
        # loads land the bin matrix directly in device memory (a
        # jax.Array with explicit NamedSharding placement); ``bins``
        # stays None then — the host never holds the full matrix
        self.device_bins = None
        self.bin_mappers: List[BinMapper] = []
        self.num_bins: np.ndarray = np.zeros(0, dtype=np.int32)
        self.real_feature_idx: np.ndarray = np.zeros(0, dtype=np.int32)
        self.used_feature_map: Dict[int, int] = {}
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata: Metadata = Metadata()
        self.label_idx: int = 0
        self.num_data: int = 0
        self.global_num_data: int = 0
        self.used_data_indices: Optional[np.ndarray] = None
        self.max_bin: int = 256

    # ------------------------------------------------------------------ load

    @classmethod
    def load_train(cls, io_config, rank: int = 0, num_machines: int = 1,
                   predict_fun: Optional[Callable] = None,
                   bin_finder: Optional[Callable] = None,
                   shard_rows: bool = False,
                   shard_devices: Optional[int] = None,
                   device_type: str = "") -> "Dataset":
        """LoadTrainData (dataset.cpp:420-465).

        ``bin_finder(sample_matrix, max_bin) -> List[BinMapper]`` lets the
        distributed path plug in feature-sliced bin finding + allgather
        (dataset.cpp:353-415); default is local bin finding.

        ``shard_rows``: a single-process data-parallel learner will
        consume the dataset — a streamed load then places the device
        matrix row-sharded over the ``(data,)`` mesh axis
        (parallel.mesh.dataset_row_sharding) instead of replicated.
        ``shard_devices`` (with ``device_type``): set for ANY
        single-process parallel consumer to the learner's mesh size —
        the streamed matrix is then committed on the learner's exact
        device mesh (row-sharded under ``shard_rows`` when rows divide
        it, replicated on that mesh otherwise), never on the serial
        one-device placement a multi-device shard_map would reject.
        """
        from . import streaming
        self = cls()
        self.data_filename = io_config.data_filename
        self.max_bin = io_config.max_bin

        # direct columnar-binary input (ISSUE 18b): ``data=`` itself IS a
        # native cache — header-sniffed via BINARY_MAGIC, so repeat jobs
        # skip text entirely (no text sibling required).  A text file
        # classifies "foreign" here and falls through to the normal
        # loaders untouched.
        if os.path.exists(io_config.data_filename):
            kind = self._classify_binary_cache(io_config.data_filename)
            if kind == "ours":
                direct = io_config.data_filename
                if (num_machines <= 1 and streaming.single_process()
                        and streaming.resolve_streaming(io_config,
                                                        direct)):
                    log.info("Loading data set from binary file "
                             "(streamed, direct)")
                    streaming.load_binary_streaming(
                        self, direct, io_config, shard_rows=shard_rows,
                        shard_devices=shard_devices,
                        device_type=device_type)
                else:
                    log.info("Loading data set from binary file (direct)")
                    self._load_binary(direct, rank, num_machines,
                                      io_config.is_pre_partition,
                                      io_config.data_random_seed)
                self._attach_init_score(io_config.input_init_score,
                                        predict_fun)
                return self
            if kind == "corrupt":
                log.fatal("Binary file %s is a corrupt/truncated "
                          "lightgbm_tpu cache — delete it to regenerate"
                          % io_config.data_filename)

        bin_path = io_config.data_filename + ".bin"
        foreign_bin = False
        if os.path.exists(bin_path):
            kind = self._classify_binary_cache(bin_path)
            if kind == "ours":
                if (num_machines <= 1 and streaming.single_process()
                        and streaming.resolve_streaming(io_config,
                                                        bin_path)):
                    log.info("Loading data set from binary file "
                             "(streamed)")
                    streaming.load_binary_streaming(
                        self, bin_path, io_config, shard_rows=shard_rows,
                        shard_devices=shard_devices,
                        device_type=device_type)
                else:
                    log.info("Loading data set from binary file")
                    self._load_binary(bin_path, rank, num_machines,
                                      io_config.is_pre_partition,
                                      io_config.data_random_seed)
                self._attach_init_score(io_config.input_init_score,
                                        predict_fun)
                return self
            if kind == "corrupt":
                log.fatal("Binary file %s is a corrupt/truncated "
                          "lightgbm_tpu cache — delete it to regenerate"
                          % bin_path)
            # a reference-LightGBM cache (dataset.cpp:653-898 layout, no
            # magic) sitting next to the data file: load it natively —
            # same bins, mappers and metadata the reference would see —
            # and never clobber the user's still-valid reference cache
            foreign_bin = True
            try:
                log.info("Loading data set from reference-format binary "
                         "file")
                self._load_reference_binary(bin_path, rank, num_machines,
                                            io_config.is_pre_partition,
                                            io_config.data_random_seed)
            except (ValueError, struct_error) as e:
                self.__dict__.update(cls().__dict__)
                self.data_filename = io_config.data_filename
                self.max_bin = io_config.max_bin
                if not os.path.exists(io_config.data_filename):
                    log.fatal("Binary file %s is neither a lightgbm_tpu "
                              "cache nor a readable reference-LightGBM "
                              "cache (%s), and the text data file %s does "
                              "not exist"
                              % (bin_path, e, io_config.data_filename))
                log.warning("Binary file %s could not be parsed as a "
                            "reference-LightGBM cache (%s) — re-binning "
                            "from the text file (the file is left "
                            "untouched)" % (bin_path, e))
            else:
                # the reference cache stores label DATA, not the label
                # column index — recover a configured label_column (the
                # name: form needs the text header, when still present)
                self.label_idx = _label_idx_without_text_load(io_config)
                self._attach_init_score(io_config.input_init_score,
                                        predict_fun)
                return self
            if io_config.is_save_binary_file:
                log.warning("is_save_binary_file requested but %s is a "
                            "foreign file — NOT overwriting it; delete "
                            "or move it to let lightgbm_tpu write its own"
                            % bin_path)

        label_idx, weight_idx, group_idx, ignore_set, header_names = \
            _resolve_columns(io_config)
        self.label_idx = label_idx

        self.metadata.init_from_files(io_config.data_filename,
                                      io_config.input_init_score)

        parser = parser_mod.create_parser(io_config.data_filename,
                                          io_config.has_header, 0, label_idx)
        if streaming.resolve_streaming(io_config, io_config.data_filename):
            # streaming ingestion (ISSUE 8, io/streaming.py): chunked
            # parse→sample→bin with double-buffered device feeds —
            # bit-identical to the resident load below, and strictly
            # more memory-bound than two-round loading (which it
            # supersedes when both are requested)
            if io_config.use_two_round_loading:
                log.info("streaming supersedes use_two_round_loading")
            streaming.load_train_streaming(
                self, io_config, parser, rank, num_machines, predict_fun,
                bin_finder, weight_idx, group_idx, ignore_set,
                header_names, shard_rows=shard_rows,
                shard_devices=shard_devices, device_type=device_type,
                foreign_bin=foreign_bin)
            self.metadata.finalize(self.num_data)
            return self
        if io_config.use_two_round_loading:
            # streaming two-pass load (dataset.cpp two-round path): never
            # materializes the [N, F] float64 matrix — pass 1 samples rows
            # for binning and collects labels/side columns, pass 2
            # quantizes chunks straight into the bin matrix
            self._load_train_two_round(
                io_config, parser, rank, num_machines, predict_fun,
                bin_finder, weight_idx, group_idx, ignore_set, header_names)
            self.metadata.finalize(self.num_data)
            if io_config.is_save_binary_file and not foreign_bin:
                self._save_binary_as(io_config, bin_path)
            return self
        lines = parser_mod.read_lines(io_config.data_filename,
                                      skip_header=io_config.has_header)
        parsed = parser.parse(lines)
        del lines
        all_features = parsed.features
        all_labels = parsed.labels
        total_rows = all_features.shape[0]
        self.global_num_data = total_rows

        # distributed row sharding at load time (dataset.cpp:172-216):
        # random per-record assignment, query-atomic when queries exist
        self.used_data_indices = self._draw_shard_mask(io_config, rank,
                                                       num_machines,
                                                       total_rows)

        # sample ≤50k global rows for bin finding (dataset.cpp:218-273)
        rng = np.random.RandomState(io_config.data_random_seed)
        if total_rows > SAMPLE_CNT:
            sample_idx = np.sort(rng.choice(total_rows, SAMPLE_CNT, replace=False))
            sample = all_features[sample_idx]
        else:
            sample = all_features

        self.num_total_features = all_features.shape[1]
        self.feature_names = _make_feature_names(header_names, label_idx,
                                                 self.num_total_features)

        # bin mappers + trivial/ignored feature removal (dataset.cpp:334-350)
        self._build_bin_mappers(sample, io_config.max_bin, bin_finder,
                                ignore_set)

        # capture weight/group columns from the data file (overrides side
        # files, ExtractFeaturesFromMemory dataset.cpp:536-545)
        if weight_idx >= 0:
            log.info("using weight in data file, and ignore additional weight file")
            self.metadata.weights = all_features[:, weight_idx].astype(np.float32)
        if group_idx >= 0:
            log.info("using query id in data file, and ignore additional query file")
            self.metadata.query_boundaries = None
            self.metadata.set_queries_from_column(all_features[:, group_idx])

        # shard rows
        if self.used_data_indices is not None:
            features = all_features[self.used_data_indices]
            self.metadata.set_label(all_labels)
            if self.metadata.queries is not None:
                self.metadata.queries = self.metadata.queries[self.used_data_indices]
            self.metadata.partition(self.used_data_indices, total_rows)
        else:
            features = all_features
            self.metadata.set_label(all_labels)
        self.num_data = features.shape[0]

        # the dense bin matrix — THE device array
        self._binarize(features)
        self.metadata.finalize(self.num_data)

        self._attach_init_score_values(features, predict_fun)
        if io_config.is_save_binary_file and not foreign_bin:
            self._save_binary_as(io_config, bin_path)
        return self

    def _save_binary_as(self, io_config, bin_path: str) -> None:
        """save_binary_format dispatch: "native" (default; pickle header +
        raw bin matrix) or "reference" (the reference's own .bin layout —
        its binary trains directly from our cache)."""
        if io_config.save_binary_format == "reference":
            self.save_binary_reference(bin_path)
        else:
            self.save_binary(bin_path)

    def _draw_shard_mask(self, io_config, rank, num_machines, total_rows):
        """Distributed row sharding at load time (dataset.cpp:172-216):
        random per-record assignment, query-atomic when query boundaries
        exist (at this point: from side files — in-file group columns
        override boundaries only AFTER sharding, matching the one-round
        order of operations).  Returns used row indices or None."""
        if num_machines <= 1 or io_config.is_pre_partition:
            return None
        # record whether the draw could honor query atomicity: an in-file
        # group column is only extracted AFTER sharding, so its queries
        # are cut per-record — distributed lambdarank must reject that
        # (gbdt.init guard) rather than silently mis-train
        self.shard_query_atomic = self.metadata.query_boundaries is not None
        rng = np.random.RandomState(io_config.data_random_seed)
        if self.metadata.query_boundaries is not None:
            nq = self.metadata.num_queries
            q_owner = rng.randint(0, num_machines, size=nq)
            row_query = np.searchsorted(self.metadata.query_boundaries,
                                        np.arange(total_rows),
                                        side="right") - 1
            mask = q_owner[row_query] == rank
        else:
            mask = rng.randint(0, num_machines, size=total_rows) == rank
        return np.nonzero(mask)[0].astype(np.int64)

    def _build_bin_mappers(self, sample, max_bin, bin_finder,
                           ignore_set) -> None:
        """Bin mappers for every raw feature column plus trivial/ignored
        feature removal (dataset.cpp:275-350)."""
        with telemetry.span("dataset_bin"):
            if bin_finder is not None:
                # distributed bin finding (parallel/learners)
                raw_mappers = bin_finder(sample, max_bin)
            else:
                raw_mappers = find_bins_for_matrix(
                    sample[:, :self.num_total_features], max_bin,
                    skip=ignore_set)
        for j, mapper in enumerate(raw_mappers):
            if mapper is None or j in ignore_set:
                if j not in ignore_set:
                    log.warning("Ignore Feature %s" % self.feature_names[j])
                continue
            if mapper.is_trivial:
                log.warning("Feature %s only contains one value, will be "
                            "ignored" % self.feature_names[j])
                continue
            self.used_feature_map[j] = len(self.bin_mappers)
            self.bin_mappers.append(mapper)
        self.real_feature_idx = np.array(sorted(self.used_feature_map),
                                         dtype=np.int32)
        self.num_bins = np.array([m.num_bin for m in self.bin_mappers],
                                 dtype=np.int32)

    def _load_train_two_round(self, io_config, parser, rank, num_machines,
                              predict_fun, bin_finder, weight_idx, group_idx,
                              ignore_set, header_names) -> None:
        """Streaming two-pass training load (``use_two_round_loading``,
        dataset.cpp:430-452 / text_reader SampleFromFile): peak host memory
        is one parse chunk plus the ≤50k-row bin-finding sample plus the
        int8/int16 bin matrix — never the full float64 feature matrix."""
        chunk_rows = 200_000
        rng_sample = np.random.RandomState(io_config.data_random_seed)

        # ---- pass 1: count rows, reservoir-sample for binning, collect
        # labels and in-file weight/query columns.  The reservoir is a
        # preallocated matrix COPIED into — retaining views of chunk rows
        # would pin every chunk's full float64 array and defeat the memory
        # bound this path exists for
        reservoir = None          # [SAMPLE_CNT, F] float64
        labels_parts, weight_parts, group_parts = [], [], []
        total_rows = 0
        num_cols = None
        for lines in parser_mod.prefetch_chunks(parser_mod.read_line_chunks(
                io_config.data_filename, skip_header=io_config.has_header,
                chunk_lines=chunk_rows)):
            parsed = parser.parse(lines)
            feats = parsed.features
            num_cols = feats.shape[1]
            if reservoir is None:
                reservoir = np.empty((SAMPLE_CNT, num_cols), np.float64)
            labels_parts.append(parsed.labels)
            if weight_idx >= 0:
                weight_parts.append(feats[:, weight_idx].astype(np.float32))
            if group_idx >= 0:
                group_parts.append(feats[:, group_idx].copy())
            # algorithm-R reservoir, vectorized per chunk (utils/random.h
            # Sample semantics: every row equally likely)
            c = feats.shape[0]
            global_idx = total_rows + np.arange(c)
            if total_rows < SAMPLE_CNT:
                take = min(SAMPLE_CNT - total_rows, c)
                reservoir[total_rows:total_rows + take] = feats[:take]
                start = take
            else:
                start = 0
            if start < c:
                accept = (rng_sample.rand(c - start)
                          < SAMPLE_CNT / (global_idx[start:] + 1.0))
                for i in np.nonzero(accept)[0]:
                    reservoir[rng_sample.randint(SAMPLE_CNT)] = \
                        feats[start + i]
            total_rows += c
        self.global_num_data = total_rows
        sample = (reservoir[:min(total_rows, SAMPLE_CNT)]
                  if reservoir is not None
                  else np.zeros((0, 0), np.float64))

        all_labels = np.concatenate(labels_parts) if labels_parts else \
            np.zeros((0,), np.float32)
        self.num_total_features = num_cols or 0
        self.feature_names = _make_feature_names(header_names,
                                                 self.label_idx,
                                                 self.num_total_features)

        # distributed row sharding mask BEFORE the in-file group column
        # overrides query boundaries — the one-round path's order (side-file
        # boundaries drive query-atomic sharding; the group column is
        # extracted later, dataset.cpp:536-545)
        self.used_data_indices = self._draw_shard_mask(io_config, rank,
                                                       num_machines,
                                                       total_rows)
        mask = None
        if self.used_data_indices is not None:
            mask = np.zeros(total_rows, dtype=bool)
            mask[self.used_data_indices] = True
        if group_idx >= 0:
            log.info("using query id in data file, and ignore additional "
                     "query file")
            self.metadata.query_boundaries = None
            self.metadata.set_queries_from_column(
                np.concatenate(group_parts))

        # bin mappers from the sample (local or distributed)
        self._build_bin_mappers(sample, io_config.max_bin, bin_finder,
                                ignore_set)
        del sample

        if weight_idx >= 0:
            log.info("using weight in data file, and ignore additional "
                     "weight file")
            self.metadata.weights = np.concatenate(weight_parts)

        self.metadata.set_label(all_labels)
        if self.used_data_indices is not None:
            if self.metadata.queries is not None:
                self.metadata.queries = \
                    self.metadata.queries[self.used_data_indices]
            self.metadata.partition(self.used_data_indices, total_rows)
            self.num_data = len(self.used_data_indices)
        else:
            self.num_data = total_rows

        # ---- pass 2: quantize chunks straight into the bin matrix
        dtype = _bin_dtype(int(self.num_bins.max())
                           if len(self.bin_mappers) else 256)
        bins = np.empty((len(self.bin_mappers), self.num_data), dtype=dtype)
        init_scores = [] if predict_fun is not None else None
        cursor = 0
        start = 0
        for lines in parser_mod.prefetch_chunks(parser_mod.read_line_chunks(
                io_config.data_filename, skip_header=io_config.has_header,
                chunk_lines=chunk_rows)):
            feats = parser.parse(lines).features
            c = feats.shape[0]
            if mask is not None:
                feats = feats[mask[start:start + c]]
            n = feats.shape[0]
            for j_raw, j_inner in self.used_feature_map.items():
                bins[j_inner, cursor:cursor + n] = \
                    self.bin_mappers[j_inner].value_to_bin(
                        feats[:, j_raw]).astype(dtype)
            if init_scores is not None:
                init_scores.append(np.asarray(predict_fun(feats),
                                              np.float32).reshape(-1))
            cursor += n
            start += c
        # the file could change between the two streaming passes; a size
        # mismatch must be a hard error, not uninitialized bin memory
        log.check(start == total_rows and cursor == self.num_data,
                  "Input file changed between the two loading passes "
                  f"(pass 1: {total_rows} rows, pass 2: {start})")
        self.bins = bins
        if init_scores is not None:
            self.metadata.init_score = np.concatenate(init_scores)

    @classmethod
    def load_valid(cls, train: "Dataset", filename: str,
                   predict_fun: Optional[Callable] = None,
                   io_config=None) -> "Dataset":
        """LoadValidationData (dataset.cpp:467-511): bin with the TRAIN
        dataset's mappers; honors has_header and in-file weight/group
        columns like the train load (dataset.cpp:474)."""
        self = cls()
        self.data_filename = filename
        self.max_bin = train.max_bin
        self.label_idx = train.label_idx
        self.bin_mappers = train.bin_mappers
        self.num_bins = train.num_bins
        self.real_feature_idx = train.real_feature_idx
        self.used_feature_map = train.used_feature_map
        self.num_total_features = train.num_total_features
        self.feature_names = train.feature_names

        has_header = bool(io_config.has_header) if io_config else False
        weight_idx = group_idx = -1
        if io_config is not None and (io_config.weight_column
                                      or io_config.group_column):
            import dataclasses as _dc
            cfg = _dc.replace(io_config, data_filename=filename)
            _, weight_idx, group_idx, _, _ = _resolve_columns(cfg)

        self.metadata.init_from_files(filename, "")
        parser = parser_mod.create_parser(filename, has_header, 0,
                                          train.label_idx)
        lines = parser_mod.read_lines(filename, skip_header=has_header)
        parsed = parser.parse(lines)
        features = parsed.features
        if weight_idx >= 0 and weight_idx < features.shape[1]:
            self.metadata.weights = features[:, weight_idx].astype(np.float32)
        if group_idx >= 0 and group_idx < features.shape[1]:
            self.metadata.query_boundaries = None
            self.metadata.set_queries_from_column(features[:, group_idx])
        if features.shape[1] < self.num_total_features:
            pad = np.zeros((features.shape[0],
                            self.num_total_features - features.shape[1]))
            features = np.concatenate([features, pad], axis=1)
        self.num_data = features.shape[0]
        self.global_num_data = self.num_data
        self.metadata.set_label(parsed.labels)
        self._binarize(features)
        self.metadata.finalize(self.num_data)
        self._attach_init_score_values(features, predict_fun)
        return self

    @classmethod
    def from_arrays(cls, features: np.ndarray, labels: np.ndarray,
                    max_bin: int = 256,
                    weights: Optional[np.ndarray] = None,
                    query_boundaries: Optional[np.ndarray] = None,
                    sample_cnt: int = SAMPLE_CNT,
                    seed: int = 1,
                    reference: Optional["Dataset"] = None) -> "Dataset":
        """Library entry: build a Dataset from in-memory arrays (no reference
        analog — the reference is file-only; this is the Python-API path).

        ``reference``: an existing (training) Dataset whose bin mappers are
        reused — required for validation sets, which must be quantized with
        the TRAINING distribution's bins (Dataset::LoadValidationData,
        dataset.cpp:467-511)."""
        self = cls()
        features = np.asarray(features, dtype=np.float64)
        self.max_bin = max_bin
        self.num_total_features = features.shape[1]
        self.feature_names = [f"Column_{i}" for i in range(features.shape[1])]
        total_rows = features.shape[0]
        if reference is not None:
            if features.shape[1] != reference.num_total_features:
                log.fatal("valid data has different number of features")
            self.max_bin = reference.max_bin
            self.used_feature_map = dict(reference.used_feature_map)
            self.bin_mappers = reference.bin_mappers
        else:
            # drawing the row sample is dataset_bin's own time
            with telemetry.span("dataset_bin"):
                rng = np.random.RandomState(seed)
                if total_rows > sample_cnt:
                    sample = features[np.sort(rng.choice(
                        total_rows, sample_cnt, replace=False))]
                else:
                    sample = features
                for j, m in enumerate(find_bins_for_matrix(sample, max_bin)):
                    if m.is_trivial:
                        continue
                    self.used_feature_map[j] = len(self.bin_mappers)
                    self.bin_mappers.append(m)
        self.real_feature_idx = np.array(sorted(self.used_feature_map),
                                         dtype=np.int32)
        self.num_bins = np.array([m.num_bin for m in self.bin_mappers],
                                 dtype=np.int32)
        self.num_data = total_rows
        self.global_num_data = total_rows
        self.metadata.set_label(np.asarray(labels, dtype=np.float32))
        if weights is not None:
            self.metadata.weights = np.asarray(weights, dtype=np.float32)
        if query_boundaries is not None:
            self.metadata.query_boundaries = np.asarray(query_boundaries,
                                                        dtype=np.int32)
            self.metadata._load_query_weights()
        self._binarize(features)
        self.metadata.finalize(self.num_data)
        return self

    # ------------------------------------------------------------- internals

    def _binarize(self, features: np.ndarray) -> None:
        """Quantize the dense value matrix into the [F, N] bin matrix:
        one ``searchsorted`` per used column and block of rows, the blocks
        on threads: NumPy's search lets go of the interpreter, and a
        block's temporaries are a few MB whatever the table's rows.  The
        ``binarize`` span; ``bin/values`` counts the values quantized."""
        num_features = len(self.bin_mappers)
        dtype = _bin_dtype(int(self.num_bins.max()) if num_features else 256)
        with telemetry.span("dataset_bin"), telemetry.span("binarize"):
            rows = features.shape[0]
            bins = np.empty((num_features, rows), dtype=dtype)

            def one(task):
                j_raw, j_inner, lo = task
                hi = min(lo + BINARIZE_BLOCK_ROWS, rows)
                bins[j_inner, lo:hi] = self.bin_mappers[j_inner].value_to_bin(
                    features[lo:hi, j_raw])

            tasks = [(j_raw, j_inner, lo)
                     for j_raw, j_inner in self.used_feature_map.items()
                     for lo in range(0, rows, BINARIZE_BLOCK_ROWS)]
            with ThreadPoolExecutor(
                    max(1, min(BINARIZE_THREADS, os.cpu_count() or 1))) as pool:
                list(pool.map(one, tasks))
            telemetry.count("bin/values",
                            int(features.shape[0]) * num_features)
        self.bins = bins

    def _attach_init_score_values(self, features: np.ndarray,
                                  predict_fun) -> None:
        """Continued training: score every row with the old model
        (dataset.cpp:546-581)."""
        if predict_fun is not None:
            self.metadata.init_score = np.asarray(
                predict_fun(features), dtype=np.float32).reshape(-1)

    def _attach_init_score(self, path: str, predict_fun) -> None:
        if path:
            self.metadata._load_init_score(path)

    @property
    def num_features(self) -> int:
        return len(self.bin_mappers)

    def plan_packing(self, mode: str = "auto", block: int = 0,
                     shards: int = 0):
        """Mixed-bin layout plan for THIS dataset's per-feature bin counts
        (io/binning.plan_feature_packing): the bin-width-class partition a
        booster uses to reorder the bin matrix at attach time.  None when
        packing cannot help (single class) or is disabled.  The Dataset
        itself stays canonical — validation sets, tree replay and the
        binary cache all speak canonical feature order; only a training
        booster's device copy of ``bins`` is reordered.

        ``block`` > 0: the BLOCK-LOCAL plan for a contiguous feature-block
        ownership layout (the hybrid/voting 2-D mesh learners,
        io/binning.plan_feature_packing_blocked) — the permutation never
        crosses an ownership block boundary, so packing commutes with
        block ownership."""
        from .binning import (plan_feature_packing,
                              plan_feature_packing_blocked)
        if not len(self.bin_mappers):
            return None
        if block > 0:
            return plan_feature_packing_blocked(
                self.num_bins, int(self.num_bins.max()), block, mode=mode,
                shards=shards)
        return plan_feature_packing(self.num_bins,
                                    int(self.num_bins.max()), mode=mode)

    def bin_upper_bounds_matrix(self) -> np.ndarray:
        """[F, max_bins] float64, padded with +inf; device-side threshold
        real-value lookup."""
        max_b = int(self.num_bins.max()) if self.num_features else 1
        out = np.full((self.num_features, max_b), np.inf, dtype=np.float64)
        for i, m in enumerate(self.bin_mappers):
            out[i, :m.num_bin] = m.bin_upper_bound
        return out

    # ---------------------------------------------------------- binary cache

    def _binary_header(self, bins_dtype, bins_shape) -> dict:
        """The native binary cache's pickled header — shared by the
        resident ``save_binary`` and the streaming loader's pass-2 memmap
        cache writer (io/streaming._CacheWriter), so both produce
        byte-identical files."""
        return {
            "num_data": self.num_data,
            "global_num_data": self.global_num_data,
            "num_total_features": self.num_total_features,
            "label_idx": self.label_idx,
            "feature_names": self.feature_names,
            "used_feature_map": self.used_feature_map,
            "max_bin": self.max_bin,
            "mappers": [m.to_bytes() for m in self.bin_mappers],
            "bins_dtype": str(np.dtype(bins_dtype)),
            "bins_shape": tuple(bins_shape),
            "label": self.metadata.label,
            "weights": self.metadata.weights,
            "query_boundaries": self.metadata.query_boundaries,
        }

    def save_binary(self, path: str) -> None:
        """Binary dataset cache (dataset.cpp:653-713).  Own format: magic +
        pickled header + raw bin matrix."""
        log.check(self.bins is not None,
                  "save_binary needs a host-resident bin matrix (a "
                  "streamed dataset writes its cache during ingestion — "
                  "set is_save_binary_file at load time)")
        header = self._binary_header(self.bins.dtype, self.bins.shape)
        # atomic write (temp + rename): a crash mid-save must not leave a
        # partial cache that a later run would misparse
        tmp = path + ".%d.tmp" % os.getpid()
        try:
            with open(tmp, "wb") as f:
                f.write(BINARY_MAGIC)
                blob = pickle.dumps(header)
                f.write(len(blob).to_bytes(8, "little"))
                f.write(blob)
                f.write(np.ascontiguousarray(self.bins).tobytes())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        log.info("Saved binary data file to %s" % path)

    def save_binary_reference(self, path: str) -> None:
        """Write the REFERENCE's binary cache layout
        (Dataset::SaveBinaryFile, dataset.cpp:653-713) so the reference
        binary can train directly from our cache — the write-side twin of
        the native reader below.  Dense columns only (the reference's
        loader picks DenseBin whenever the file says is_sparse=false,
        bin.cpp:202-210; sparse delta-streams are a CPU cache layout with
        no value in our matrix pipeline).

        Layout quirk inherited from the reference: its own
        Metadata::LoadFromMemory mis-advances past the label block when
        queries are present WITHOUT weights (metadata.cpp:313 advances by
        num_weights, not num_data) — a file we write with that shape is
        byte-faithful to SaveBinaryFile yet unreadable by the reference's
        own loader, exactly like the reference's own caches
        (PARITY.md)."""
        import struct

        md = self.metadata
        n = self.num_data
        weights = md.weights
        qb = md.query_boundaries
        qw = getattr(md, "query_weights", None)
        n_map = self.num_total_features
        fmap = np.full(n_map, -1, dtype=np.int32)
        for real, inner in self.used_feature_map.items():
            fmap[real] = inner
        names = list(self.feature_names)
        if len(names) < n_map:
            names += ["Column_%d" % i for i in range(len(names), n_map)]

        header = b"".join(
            [struct.pack("<Q", int(self.global_num_data or n)),
             struct.pack("<?", False),          # is_enable_sparse
             struct.pack("<iiii", int(self.max_bin), n,
                         self.num_features, n_map),
             struct.pack("<Q", n_map), fmap.tobytes()]
            + [struct.pack("<i", len(s.encode())) + s.encode()
               for s in names])

        meta = [struct.pack("<iii", n,
                            0 if weights is None else len(weights),
                            0 if qb is None else len(qb) - 1),
                np.asarray(md.label, "<f4").tobytes()]
        if weights is not None:
            meta.append(np.asarray(weights, "<f4").tobytes())
        if qb is not None:
            meta.append(np.asarray(qb, "<i4").tobytes())
            if qw is not None:
                meta.append(np.asarray(qw, "<f4").tobytes())
        meta = b"".join(meta)

        # inner features in REAL-index order, like features_ in the
        # reference (construction order = real feature order)
        tmp = path + ".%d.tmp" % os.getpid()
        try:
            with open(tmp, "wb") as f:
                f.write(struct.pack("<Q", len(header)) + header)
                f.write(struct.pack("<Q", len(meta)) + meta)
                for real in self.real_feature_idx:
                    inner = self.used_feature_map[int(real)]
                    m = self.bin_mappers[inner]
                    # single source of the <=256/<=65536 width rule
                    vt = np.dtype(_bin_dtype(m.num_bin)).newbyteorder("<")
                    blob = b"".join([
                        struct.pack("<i?", int(real), False),  # dense
                        struct.pack("<i?d", int(m.num_bin),
                                    bool(m.is_trivial),
                                    float(m.sparse_rate)),
                        np.asarray(m.bin_upper_bound, "<f8").tobytes(),
                        np.ascontiguousarray(
                            self.bins[inner]).astype(vt).tobytes(),
                    ])
                    f.write(struct.pack("<Q", len(blob)) + blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        log.info("Saved binary data file to %s" % path)

    @staticmethod
    def _classify_binary_cache(path: str) -> str:
        """'ours' (magic match) / 'corrupt' (a damaged lightgbm_tpu cache
        — recognizable magic prefix but not the full magic) / 'foreign'
        (anything else: the reference's .bin layout, dataset.cpp:653-898,
        starts with a raw size_t header size and carries no magic, and a
        0-byte crash artifact from any other tool is equally not ours).
        save_binary writes atomically, so 'corrupt' is a best-effort
        diagnosis for caches damaged after the fact; _load_binary's parser
        reports anything that slips through."""
        with open(path, "rb") as f:
            head = f.read(len(BINARY_MAGIC))
        if head == BINARY_MAGIC:
            return "ours"
        if head[:8] == b"LGBM_TPU":
            return "corrupt"
        return "foreign"

    def _load_binary(self, path: str, rank: int, num_machines: int,
                     is_pre_partition: bool, data_random_seed: int = 1) -> None:
        try:
            with open(path, "rb") as f:
                # format already validated by _classify_binary_cache (the
                # only caller gates on it); skip past the magic
                f.read(len(BINARY_MAGIC))
                size = int.from_bytes(f.read(8), "little")
                header = pickle.loads(f.read(size))
                bins = np.frombuffer(f.read(),
                                     dtype=np.dtype(header["bins_dtype"]))
        except log.LightGBMError:
            raise
        except Exception as e:
            log.fatal("Binary file %s is a damaged lightgbm_tpu cache "
                      "(%s) — delete it to regenerate" % (path, e))
        self._apply_binary_header(header)
        self.bins = bins.reshape(header["bins_shape"]).copy()
        self._reshard_rows(rank, num_machines, is_pre_partition,
                           data_random_seed)
        self.metadata.finalize(self.num_data)

    def _apply_binary_header(self, header: dict) -> None:
        """Install every non-bin field of a native cache header — shared
        by the resident loader and the streaming (memmap) cache loader."""
        self.num_data = header["num_data"]
        self.global_num_data = header["global_num_data"]
        self.num_total_features = header["num_total_features"]
        self.label_idx = header["label_idx"]
        self.feature_names = header["feature_names"]
        self.used_feature_map = header["used_feature_map"]
        self.max_bin = header["max_bin"]
        self.bin_mappers = [BinMapper.from_bytes(b) for b in header["mappers"]]
        self.real_feature_idx = np.array(sorted(self.used_feature_map),
                                         dtype=np.int32)
        self.num_bins = np.array([m.num_bin for m in self.bin_mappers],
                                 dtype=np.int32)
        self.metadata.set_label(header["label"])
        self.metadata.weights = header["weights"]
        self.metadata.query_boundaries = header["query_boundaries"]
        if (self.metadata.weights is not None
                and self.metadata.query_boundaries is not None):
            # same recompute as the reference-cache loader: finalize()
            # only derives query weights on the queries-column path
            self.metadata._load_query_weights()

    def _reshard_rows(self, rank: int, num_machines: int,
                      is_pre_partition: bool, data_random_seed: int) -> None:
        """Re-shard cached rows for distributed training
        (dataset.cpp:840-872); query-atomic when query boundaries exist,
        same seed as the fresh-load path so cached and fresh runs shard
        identically."""
        if num_machines <= 1 or is_pre_partition:
            return
        rng = np.random.RandomState(data_random_seed)
        qb = self.metadata.query_boundaries
        if qb is not None:
            q_owner = rng.randint(0, num_machines, size=qb.size - 1)
            row_query = np.searchsorted(qb, np.arange(self.num_data),
                                        side="right") - 1
            mask = q_owner[row_query] == rank
        else:
            mask = rng.randint(0, num_machines, size=self.num_data) == rank
        idx = np.nonzero(mask)[0]
        self.bins = np.ascontiguousarray(self.bins[:, idx])
        self.metadata.partition(idx, self.num_data)
        self.num_data = idx.size

    def _load_reference_binary(self, path: str, rank: int,
                               num_machines: int, is_pre_partition: bool,
                               data_random_seed: int = 1) -> None:
        """Load a binary cache WRITTEN BY THE REFERENCE BINARY
        (Dataset::SaveBinaryFile, dataset.cpp:653-713): little-endian,
        tightly packed —

          size_t header_size; { size_t global_num_data; bool sparse;
          int max_bin; int32 num_data; int num_features;
          int num_total_features; size_t n_map; int map[n_map];
          (int len, char[len]) x num_total_features names }
          size_t metadata_size; { int32 num_data, num_weights,
          num_queries; float label[num_data]; float weights[]?;
          int32 query_boundaries[num_queries+1]?; float query_weights[]? }
          per feature: size_t size; { int feature_index; bool is_sparse;
          BinMapper{int num_bin; bool is_trival; double sparse_rate;
          double upper[num_bin]} ; bin data }

        Dense bin data is a raw uint8/16/32 row (width by num_bin,
        bin.cpp:202-210); sparse is the delta stream of
        sparse_bin.hpp:178-187 (int32 n; uint8 delta[n+1]; VAL_T vals[n])
        whose positions are the running delta sum and whose absent rows
        read back as bin 0 (SparseBinIterator::Get) — gap-filler entries
        carry val 0 and land harmlessly.  NOTE: we parse the layout
        SaveBinaryToFile actually WRITES; the reference's own
        Metadata::LoadFromMemory advances by num_weights (not num_data)
        floats past the label block (metadata.cpp:313), a defect that
        garbles its own caches when a query file is present without
        weights.  Raises ValueError on malformed input (the caller falls
        back to re-binning the text file)."""
        import struct

        def take(buf, fmt, off):
            vals = struct.unpack_from("<" + fmt, buf, off)
            return vals, off + struct.calcsize("<" + fmt)

        with open(path, "rb") as f:
            def read_block(what):
                raw = f.read(8)
                if len(raw) != 8:
                    raise ValueError("truncated at %s size" % what)
                n = struct.unpack("<Q", raw)[0]
                if n > (64 << 30):
                    raise ValueError("implausible %s size %d" % (what, n))
                blob = f.read(n)
                if len(blob) != n:
                    raise ValueError("truncated %s" % what)
                return blob

            head = read_block("header")
            (global_num_data,), off = take(head, "Q", 0)
            off += 1                                  # is_enable_sparse
            (max_bin, num_data, num_features,
             num_total_features), off = take(head, "iiii", off)
            (n_map,), off = take(head, "Q", off)
            if not (0 < num_features <= n_map
                    and num_features <= num_total_features):
                raise ValueError("inconsistent feature counts")
            off += 4 * n_map                          # used_feature_map:
            # rebuilt below from each Feature's own feature_index
            names = []
            for _ in range(num_total_features):
                (ln,), off = take(head, "i", off)
                if ln < 0 or off + ln > len(head):
                    raise ValueError("bad feature-name length")
                names.append(head[off:off + ln].decode("utf-8", "replace"))
                off += ln

            meta = read_block("metadata")
            (md_n, md_w, md_q), off = take(meta, "iii", 0)
            if md_n != num_data:
                raise ValueError("metadata/header row-count mismatch")
            label = np.frombuffer(meta, "<f4", md_n, off).copy()
            off += 4 * md_n
            weights = qb = None
            if md_w > 0:
                weights = np.frombuffer(meta, "<f4", md_w, off).copy()
                off += 4 * md_w
            if md_q > 0:
                qb = np.frombuffer(meta, "<i4", md_q + 1, off).copy()
                off += 4 * (md_q + 1)
            # query_weights (if present) are recomputed by finalize()

            mappers: List[BinMapper] = []
            real_idx: List[int] = []
            cols: List[np.ndarray] = []
            for i in range(num_features):
                blob = read_block("feature %d" % i)
                (fidx,), off = take(blob, "i", 0)
                is_sparse = blob[off] != 0
                off += 1
                (num_bin,), off = take(blob, "i", off)
                is_trivial = blob[off] != 0
                off += 1
                (sparse_rate,), off = take(blob, "d", off)
                if not (0 < num_bin <= (1 << 24)):
                    raise ValueError("bad num_bin %d" % num_bin)
                upper = np.frombuffer(blob, "<f8", num_bin, off).copy()
                off += 8 * num_bin
                vt = ("<u1" if num_bin <= 256
                      else "<u2" if num_bin <= 65536 else "<u4")
                if not is_sparse:
                    # a view into blob is fine: the blob IS the column
                    # (astype/stack below materialize fresh memory)
                    col = np.frombuffer(blob, vt, num_data, off)
                else:
                    (nv,), off = take(blob, "i", off)
                    delta = np.frombuffer(blob, "<u1", nv + 1, off)
                    off += nv + 1
                    vals = np.frombuffer(blob, vt, nv, off)
                    pos = np.cumsum(delta[:nv].astype(np.int64))
                    if nv and pos[-1] >= num_data:
                        raise ValueError("sparse position out of range")
                    col = np.zeros(num_data, dtype=vt)
                    col[pos] = vals
                mappers.append(BinMapper(num_bin=num_bin,
                                         is_trivial=bool(is_trivial),
                                         sparse_rate=float(sparse_rate),
                                         bin_upper_bound=upper))
                real_idx.append(fidx)
                cols.append(col)

        order = np.argsort(np.asarray(real_idx, dtype=np.int64),
                           kind="stable")
        self.num_data = num_data
        self.global_num_data = int(global_num_data) or num_data
        self.num_total_features = num_total_features
        self.feature_names = names
        self.max_bin = max_bin
        self.bin_mappers = [mappers[j] for j in order]
        self.used_feature_map = {int(real_idx[j]): k
                                 for k, j in enumerate(order)}
        self.real_feature_idx = np.array(sorted(self.used_feature_map),
                                         dtype=np.int32)
        self.num_bins = np.array([m.num_bin for m in self.bin_mappers],
                                 dtype=np.int32)
        dtype = _bin_dtype(int(self.num_bins.max()))
        self.bins = np.ascontiguousarray(
            np.stack([cols[j].astype(dtype, copy=False) for j in order],
                     axis=0))
        self.metadata.set_label(label)
        self.metadata.weights = weights
        self.metadata.query_boundaries = qb
        if weights is not None and qb is not None:
            # finalize() only derives query weights on the queries-column
            # path; side-file-style weights+queries need the explicit
            # recompute (metadata.cpp:286-298)
            self.metadata._load_query_weights()
        self._reshard_rows(rank, num_machines, is_pre_partition,
                           data_random_seed)
        self.metadata.finalize(self.num_data)


def _label_idx_without_text_load(io_config) -> int:
    """Resolve label_column to an index for binary-cache loads, where no
    text parse happens: numeric directly; ``name:`` via the text header
    if the file is still on disk (application.cpp resolves names the same
    way before any data read)."""
    lc = io_config.label_column
    if not lc:
        return 0
    if not lc.startswith("name:"):
        try:
            return int(lc)
        except ValueError:
            log.fatal("label_column is not a number, if you want to use "
                      "column name, please add prefix \"name:\" before "
                      "column name")
    name = lc[len("name:"):]
    if io_config.has_header and os.path.exists(io_config.data_filename):
        with open(io_config.data_filename, "r") as f:
            first = f.readline().rstrip("\r\n")
        delim = "\t" if first.count("\t") > first.count(",") else ","
        names = first.split(delim)
        if name in names:
            return names.index(name)
        log.fatal("cannot find label column: %s in data file" % name)
    log.warning("label_column=%s cannot be resolved without the text "
                "file's header; keeping label_index=0 (only the saved "
                "model's label_index field is affected)" % lc)
    return 0


def _resolve_columns(io_config) -> Tuple[int, int, int, set, Optional[List[str]]]:
    """Column-role resolution by index or ``name:`` prefix
    (dataset.cpp:44-146).  Returns (label_idx, weight_idx, group_idx,
    ignore_set, header_names); weight/group/ignore indices are in
    label-removed feature space."""
    header_names: Optional[List[str]] = None
    name2idx: Dict[str, int] = {}
    if io_config.has_header:
        with open(io_config.data_filename, "r") as f:
            first = f.readline().rstrip("\r\n")
        delim = "\t" if first.count("\t") > first.count(",") else ","
        header_names = first.split(delim)
        name2idx = {name: i for i, name in enumerate(header_names)}

    def resolve(column: str, what: str) -> int:
        if column.startswith("name:"):
            name = column[len("name:"):]
            if name in name2idx:
                log.info("use %s column as %s" % (name, what))
                return name2idx[name]
            log.fatal("cannot find %s column: %s in data file" % (what, name))
        try:
            idx = int(column)
        except ValueError:
            log.fatal("%s_column is not a number, if you want to use column "
                      "name, please add prefix \"name:\" before column name"
                      % what)
        log.info("use %d-th column as %s" % (idx, what))
        return idx

    label_idx = 0
    if io_config.label_column:
        label_idx = resolve(io_config.label_column, "label")
    if header_names is not None:
        header_names = list(header_names)
        del header_names[label_idx]

    ignore_set: set = set()
    if io_config.ignore_column:
        spec = io_config.ignore_column
        if spec.startswith("name:"):
            for name in spec[len("name:"):].split(","):
                if name not in name2idx:
                    log.fatal("cannot find column: %s in data file" % name)
                idx = name2idx[name]
                if idx > label_idx:
                    idx -= 1
                ignore_set.add(idx)
        else:
            for token in spec.split(","):
                idx = int(token)
                if idx > label_idx:
                    idx -= 1
                ignore_set.add(idx)

    weight_idx = -1
    if io_config.weight_column:
        weight_idx = resolve(io_config.weight_column, "weight")
        if weight_idx > label_idx:
            weight_idx -= 1
        ignore_set.add(weight_idx)

    group_idx = -1
    if io_config.group_column:
        group_idx = resolve(io_config.group_column, "group/query id")
        if group_idx > label_idx:
            group_idx -= 1
        ignore_set.add(group_idx)

    return label_idx, weight_idx, group_idx, ignore_set, header_names


def _make_feature_names(header_names: Optional[List[str]], label_idx: int,
                        num_total: int) -> List[str]:
    if header_names is not None and len(header_names) >= num_total:
        return header_names[:num_total]
    return [f"Column_{i}" for i in range(num_total)]
