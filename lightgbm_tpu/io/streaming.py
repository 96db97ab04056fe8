"""Streaming ingestion tier (ISSUE 8): sharded out-of-core parse→bin,
double-buffered host→HBM feeds, explicit dataset placement.

The resident loader (``Dataset.load_train``) materializes every line of
the text file as one host ``[N, F]`` float64 matrix before binning — a
~25 GB host allocation at 100M x 28 that caps training around 11M rows.
The reference's own TextReader pipelines 16MB blocks through a bounded
queue (utils/pipeline_reader.h); this module is that idea rebuilt as an
async DEVICE feed:

- **Pass 0** counts data rows with a raw line scan (no parse), sharing
  ``read_line_chunks``'s exact header/blank-line semantics.
- **Pinned-index sample**: the binning sample indices are drawn exactly
  like the resident loader (``RandomState(seed).choice(N, SAMPLE_CNT)``,
  sorted) — an algorithm-R reservoir cannot reproduce those draws, and
  bit-identity with the resident dataset (mappers, bin codes, trained
  model text) is this tier's correctness bar.  ``find_bin`` is
  order-invariant over the sample (np.unique), so gathering the pinned
  rows in file order reproduces the resident mappers bit-for-bit.
- **Pass 1** parses bounded row chunks on a prefetch thread, collecting
  labels/weight/group columns and filling the pinned-index sample
  matrix; bin mappers are fit from the sample (local or distributed
  ``bin_finder``).
- **Pass 2** re-parses chunks, quantizes each against the mappers, and
  lands it straight in device memory through ``DeviceRowWriter``:
  ``jax.device_put`` transfers are dispatched asynchronously and at most
  ``depth`` stay in flight, so the NEXT chunk parses and bins on the
  host while the previous transfer (and its donated
  ``dynamic_update_slice`` into the preallocated ``[F, N]`` HBM matrix)
  is still moving — the double buffer.  ``LGBM_TPU_INGEST_SYNC=1``
  forces depth 0 for the bench lane's A/B.

Placement is explicit: the device matrix carries a ``NamedSharding``
over the ``(data,)`` mesh axis (``parallel.mesh.dataset_row_sharding``).
A single-process PARALLEL consumer gets the matrix committed on the
learner's exact ``get_mesh`` device set — row-sharded for
tree_learner=data when the row count divides the mesh, replicated on
that mesh otherwise (a multi-device shard_map rejects a one-device
commit) — while the serial consumer gets a one-device ``(data,)`` mesh
so serial training computes bit-identically to the resident path.
Multi-PROCESS runs (including feature-parallel, which loads with
num_machines=1 but still runs multi-process — ``single_process()``
gates on the process count, not the shard count) keep the binned LOCAL
shard host-side (bounded by the shard, not the dataset) and ride the
existing ``make_global_rows`` NamedSharding lift in gbdt.init, so
per-host row sharding composes with the DP reduce_scatter ownership
schedule unchanged.

Binary caches stream both ways: a native cache is READ via ``np.memmap``
row-chunks (no full host materialization), and ``is_save_binary_file``
under streaming WRITES the cache through a memmap during pass 2 —
byte-identical to the resident ``save_binary`` output.  Because the
cache is byte-identical and the memmap reader takes the consuming
learner's ``shard_rows``/``shard_devices`` at LOAD time, the cache is
also the elastic-restart re-shard vehicle (ISSUE 14): a ``task=train``
restart on a SHRUNK topology (fewer ``num_machines`` after a
preemption) re-opens the same cache and commits the identical bin
matrix onto the re-factored mesh — the dryrun harness's kill-restart
row and the checkpoint restore's bit-exactness guarantees ride exactly
this property.

Telemetry: the whole load runs under an ``ingest`` span (sub-spans
``ingest_count``/``ingest_pass1``/``ingest_bin``/``ingest_h2d``) and
files the ``ingest/*`` counter family — chunks, rows, h2d_bytes,
h2d_wait_us, overlap_hidden_us (see telemetry.py's docstring;
scripts/telemetry_report.py renders the family with derived GB/s).
"""
from __future__ import annotations

import collections
import os
import pickle
import time
from typing import List, Optional

import numpy as np

from .. import hatches, telemetry, tracing
from ..utils import log
from . import parser as parser_mod

# "auto" engages streaming when the text/cache file is at least this
# large (a resident load of a smaller file is cheap and keeps the
# historical code path); override per-run with streaming=true|false.
AUTO_MIN_BYTES = 256 * 1024 * 1024

# env hatch: force synchronous (depth-0) transfers — the bench lane's
# double-buffer A/B (bench.py --bench-ingest)
SYNC_ENV = "LGBM_TPU_INGEST_SYNC"


def resolve_streaming(io_config, path: str) -> bool:
    """The ``streaming=`` resolution rule, single-homed: "true"/"false"
    force; "auto" engages when ``path`` is at least AUTO_MIN_BYTES."""
    mode = getattr(io_config, "streaming", "auto")
    if mode == "true":
        return True
    if mode == "false":
        return False
    try:
        return os.path.getsize(path) >= AUTO_MIN_BYTES
    except OSError:
        return False


def double_buffer_on() -> bool:
    return not hatches.flag(SYNC_ENV)


def single_process() -> bool:
    """Device residency is single-process only: a multi-process run's
    GBDT paths (_host_inputs) build their global NamedSharding lift from
    HOST arrays — including the feature-parallel learner, which loads
    with num_machines=1 (full rows per process) but still runs
    multi-process."""
    import jax
    return jax.process_count() == 1


# ---------------------------------------------------------------- writers


class HostRowWriter:
    """Row-chunk assembly into a host numpy matrix — the multi-process
    shard target (the global NamedSharding lift happens in gbdt.init via
    make_global_rows, exactly as for a resident dataset)."""

    def __init__(self, num_features: int, num_rows: int, dtype):
        self.bins = np.empty((num_features, num_rows), dtype=dtype)

    def append(self, chunk: np.ndarray, start: int) -> None:
        self.bins[:, start:start + chunk.shape[1]] = chunk

    def finish(self):
        return self.bins


class DeviceRowWriter:
    """Assembles the ``[F, N]`` bin matrix in device memory from host row
    chunks with bounded, double-buffered host→device transfers.

    Each ``append`` dispatches an async ``device_put`` of the binned
    chunk plus a donated ``dynamic_update_slice`` into the preallocated
    device matrix; at most ``depth`` transfers stay in flight (the host
    source buffers of older transfers are released by blocking on them),
    so chunk i+1's parse/bin overlaps chunk i's wire time.  On the CPU
    backend "device" memory IS host RAM and XLA cannot donate, so the
    per-chunk update would copy the whole [F, N] matrix once per chunk
    (O(chunks) full-matrix memcpy for zero memory benefit) — chunks are
    staged into a host matrix instead and committed with ONE sharded
    ``device_put`` in ``finish()``: same values, same placement.

    Counters: ``ingest/h2d_bytes`` (payload), ``ingest/h2d_wait_us``
    (host time actually blocked on transfers) and
    ``ingest/overlap_hidden_us`` (upper-bound estimate of wire time that
    ran behind host parse/bin work: dispatch→wait gaps)."""

    def __init__(self, num_features: int, num_rows: int, dtype, *,
                 sharding=None, depth: int = 2):
        import jax
        import jax.numpy as jnp
        from ..parallel.mesh import dataset_row_sharding
        self._jax = jax
        self.num_rows = int(num_rows)
        self.sharding = (sharding if sharding is not None
                         else dataset_row_sharding(num_rows))
        self._depth = depth if double_buffer_on() else 0
        telemetry.count_route(
            "ingest", "ingest/double_buffer_on" if self._depth
            else "ingest/double_buffer_off")
        dtype = np.dtype(dtype)
        self._pending: "collections.deque" = collections.deque()
        self.h2d_bytes = 0
        self.wait_s = 0.0
        self.hidden_s = 0.0
        if jax.default_backend() == "cpu":
            self._stage = np.empty((num_features, self.num_rows), dtype)
            self._buf = None
            return
        self._stage = None
        self._buf = jax.jit(
            lambda: jnp.zeros((num_features, self.num_rows), dtype.name),
            out_shardings=self.sharding)()
        from jax.sharding import NamedSharding, PartitionSpec
        self._chunk_sharding = NamedSharding(self.sharding.mesh,
                                             PartitionSpec())
        self._update = _update_program(self.sharding)

    def append(self, chunk: np.ndarray, start: int) -> None:
        """Dispatch one ``[F, c]`` chunk landing at column ``start``."""
        if chunk.shape[1] == 0:
            return
        assert start + chunk.shape[1] <= self.num_rows
        if self._stage is not None:
            self._stage[:, start:start + chunk.shape[1]] = chunk
            self.h2d_bytes += chunk.nbytes
            telemetry.count("ingest/h2d_bytes", chunk.nbytes)
            return
        dev = self._jax.device_put(np.ascontiguousarray(chunk),
                                   self._chunk_sharding)
        self._buf = self._update(self._buf, dev, np.int32(start))
        self._pending.append((dev, time.perf_counter()))
        self.h2d_bytes += chunk.nbytes
        telemetry.count("ingest/h2d_bytes", chunk.nbytes)
        while len(self._pending) > self._depth:
            self._drain_one()

    def _drain_one(self) -> None:
        dev, t_dispatch = self._pending.popleft()
        t0 = time.perf_counter()
        self._jax.block_until_ready(dev)
        t1 = time.perf_counter()
        self.wait_s += t1 - t0
        self.hidden_s += max(0.0, t0 - t_dispatch)
        telemetry.count("ingest/h2d_wait_us", int((t1 - t0) * 1e6))
        telemetry.count("ingest/overlap_hidden_us",
                        int(max(0.0, t0 - t_dispatch) * 1e6))

    def finish(self):
        """Drain in-flight transfers and return the device matrix."""
        with telemetry.span("ingest_h2d"):
            if self._stage is not None:
                t0 = time.perf_counter()
                self._buf = self._jax.device_put(self._stage,
                                                 self.sharding)
                self._jax.block_until_ready(self._buf)
                self.wait_s = time.perf_counter() - t0
                telemetry.count("ingest/h2d_wait_us",
                                int(self.wait_s * 1e6))
                # the one-shot staged commit hides nothing behind host
                # work — file the zero explicitly so the derived overlap
                # column (telemetry_report) has its counter on CPU
                # rounds instead of dividing by a missing key
                telemetry.count("ingest/overlap_hidden_us", 0)
                self._stage = None
            else:
                while self._pending:
                    self._drain_one()
                self._jax.block_until_ready(self._buf)
        return self._buf


# one instrumented update program per buffer placement, shared
# process-wide (jit re-traces per chunk shape: full chunks and the ragged
# tail are the only two shapes of a load)
_UPDATE_PROGRAMS: dict = {}


def _update_program(sharding):
    """Donated in-place landing of a replicated ``[F, c]`` chunk at
    column ``start`` of the ``[F, N]`` buffer placed by ``sharding``.

    One device (or a replicated buffer): a ``dynamic_update_slice``.  A
    ROW-SHARDED buffer: under ``shard_map`` every device lands only the
    part of the chunk that falls into its own row block — the same
    ``dynamic_update_slice``, into its block padded by one chunk width on
    either side so that a chunk lying partly or wholly outside the block
    spills into the padding.  Leaving the sharded case to the SPMD
    partitioner (all-gather the buffer, update, re-slice) took 27 s per
    200k-row chunk on four v5e chips (PR 24)."""
    prog = _UPDATE_PROGRAMS.get(sharding)
    if prog is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        def _update(buf, chunk, start):
            return jax.lax.dynamic_update_slice(
                buf, chunk, (jnp.int32(0), start))

        spec = sharding.spec
        row_axis = spec[1] if len(spec) > 1 else None
        fn = _update
        if row_axis is not None and sharding.mesh.shape[row_axis] > 1:
            def _update_block(block, chunk, start):
                per, c = block.shape[1], chunk.shape[1]
                lo = jax.lax.axis_index(row_axis) * per
                at = jnp.clip(start - lo + c, 0, per + c)
                padded = jnp.pad(block, ((0, 0), (c, c)))
                return _update(padded, chunk, at)[:, c:c + per]

            fn = jax.shard_map(
                _update_block, mesh=sharding.mesh,
                in_specs=(spec, PartitionSpec(), PartitionSpec()),
                out_specs=spec)
        jitted = jax.jit(fn, donate_argnums=(0,), out_shardings=sharding)
        from .. import costmodel
        prog = costmodel.instrument("ingest/update", jitted,
                                    phase="ingest")
        _UPDATE_PROGRAMS[sharding] = prog
    return prog


# ------------------------------------------------------- streaming cache


class _CacheWriter:
    """Write the native binary cache during pass 2 through a memmap —
    the streamed twin of ``Dataset.save_binary`` (same magic + pickled
    header + raw ``[F, N]`` bin matrix bytes, written atomically via
    temp + rename), without ever holding the full bin matrix on host."""

    def __init__(self, header: dict, bin_path: str, dtype, shape):
        from .dataset import BINARY_MAGIC
        self._path = bin_path
        self._tmp = bin_path + ".%d.tmp" % os.getpid()
        blob = pickle.dumps(header)
        dtype = np.dtype(dtype)
        total = int(shape[0]) * int(shape[1]) * dtype.itemsize
        with open(self._tmp, "wb") as f:
            f.write(BINARY_MAGIC)
            f.write(len(blob).to_bytes(8, "little"))
            f.write(blob)
            self._offset = f.tell()
            if total:
                f.seek(self._offset + total - 1)
                f.write(b"\0")
        self._mm = (np.memmap(self._tmp, dtype=dtype, mode="r+",
                              offset=self._offset, shape=tuple(shape))
                    if total else None)

    def write(self, chunk: np.ndarray, start: int) -> None:
        if self._mm is not None:
            self._mm[:, start:start + chunk.shape[1]] = chunk

    def finish(self) -> None:
        if self._mm is not None:
            self._mm.flush()
            self._mm = None
        os.replace(self._tmp, self._path)
        log.info("Saved binary data file to %s" % self._path)

    def abort(self) -> None:
        self._mm = None
        try:
            os.unlink(self._tmp)
        except OSError:
            pass


# ------------------------------------------------------------ train load


def pinned_sample_indices(total_rows: int, seed: int,
                          sample_cnt: int) -> Optional[np.ndarray]:
    """The resident loader's binning-sample draw, verbatim
    (dataset.py load_train): sorted ``choice(total_rows, sample_cnt)``
    from a fresh ``RandomState(seed)``, or None when every row is the
    sample.  Single-homed so streaming reproduces the resident mappers
    bit-for-bit (and so the determinism test pins ONE rule)."""
    if total_rows <= sample_cnt:
        return None
    rng = np.random.RandomState(seed)
    return np.sort(rng.choice(total_rows, sample_cnt, replace=False))


def load_train_streaming(ds, io_config, parser, rank: int,
                         num_machines: int, predict_fun, bin_finder,
                         weight_idx: int, group_idx: int, ignore_set,
                         header_names, shard_rows: bool = False,
                         shard_devices: Optional[int] = None,
                         device_type: str = "",
                         foreign_bin: bool = False) -> None:
    """The chunked parse→sample-for-binning→bin→transfer training load.

    Fills ``ds`` (a fresh Dataset) with the exact state the resident
    loader would produce — same mappers, same bin codes, same metadata,
    same shard draw — while holding at most one parse chunk (plus the
    ≤SAMPLE_CNT binning sample and the label/side columns) on the host.
    Single-process loads land the bin matrix directly in device memory
    (``ds.device_bins``; ``ds.bins`` stays None); multi-process loads
    keep the binned LOCAL shard host-side for gbdt's global
    NamedSharding lift."""
    from . import dataset as dataset_mod

    filename = io_config.data_filename
    chunk_rows = getattr(io_config, "ingest_chunk_rows", 200_000)
    device_resident = num_machines <= 1 and single_process()

    # parallel byte-range ingest (ISSUE 18, io/parallel_ingest.py):
    # engaged by ingest_workers > 1, and by ANY multi-process load (the
    # pod-sharded parse: each host tokenizes only its own row shard).
    # Bit-identical to the serial passes below by construction and by
    # test pin (tests/test_parallel_ingest.py).
    workers = int(getattr(io_config, "ingest_workers", 1) or 1)
    ds.ingest_workers_requested = workers
    if workers > 1 or num_machines > 1:
        from . import parallel_ingest
        if parallel_ingest.available():
            return parallel_ingest.load_train_streaming_parallel(
                ds, io_config, parser, rank, num_machines, predict_fun,
                bin_finder, weight_idx, group_idx, ignore_set,
                header_names, shard_rows=shard_rows,
                shard_devices=shard_devices, device_type=device_type,
                foreign_bin=foreign_bin, workers=workers)
        if workers > 1:
            log.warning(
                "ingest_workers=%d requested but no worker interpreter "
                "can be exec'd — parallel parse resolved to the serial "
                "loader" % workers)
    ds.ingest_workers_effective = 1

    with telemetry.span("ingest"):
        # ---- pass 0: count data rows (raw scan, no parse)
        t_pass = time.perf_counter()
        with telemetry.span("ingest_count"):
            total_rows = parser_mod.count_data_rows(
                filename, skip_header=io_config.has_header)
        tracing.record_ingest_pass(0, time.perf_counter() - t_pass,
                                   total_rows)
        ds.global_num_data = total_rows
        sample_cnt = dataset_mod.SAMPLE_CNT
        sample_idx = pinned_sample_indices(
            total_rows, io_config.data_random_seed, sample_cnt)

        # ---- pass 1: labels + side columns + pinned-index sample
        labels_parts: List[np.ndarray] = []
        weight_parts: List[np.ndarray] = []
        group_parts: List[np.ndarray] = []
        sample_parts: List[np.ndarray] = []
        reservoir = None
        num_cols = None
        start = 0
        chunk1_no = 0
        t_pass = time.perf_counter()
        with telemetry.span("ingest_pass1"):
            for lines in parser_mod.prefetch_chunks(
                    parser_mod.read_line_chunks(
                        filename, skip_header=io_config.has_header,
                        chunk_lines=chunk_rows)):
                t0 = time.perf_counter()
                parsed = parser.parse(lines)
                # pass-1 tokenization is parse cost too: without this the
                # ingest/parse_us family under-reports exactly half the
                # tokenizer wall (and the parallel path's selective
                # pass-1 saving would be invisible to the attribution)
                parse_us = (time.perf_counter() - t0) * 1e6
                telemetry.count("ingest/parse_us", int(parse_us))
                tracing.record_ingest_chunk(1, chunk1_no, len(lines),
                                            parse_us, 0.0, 0.0)
                chunk1_no += 1
                feats = parsed.features
                num_cols = feats.shape[1]
                labels_parts.append(parsed.labels)
                if weight_idx >= 0:
                    weight_parts.append(
                        feats[:, weight_idx].astype(np.float32))
                if group_idx >= 0:
                    group_parts.append(feats[:, group_idx].copy())
                c = feats.shape[0]
                if sample_idx is None:
                    # every row is the sample (total <= SAMPLE_CNT); the
                    # concatenation below reproduces the resident
                    # loader's whole-matrix sample in file order
                    sample_parts.append(feats)
                else:
                    if reservoir is None:
                        reservoir = np.empty((sample_idx.size, num_cols),
                                             np.float64)
                    lo = np.searchsorted(sample_idx, start)
                    hi = np.searchsorted(sample_idx, start + c)
                    if hi > lo:
                        reservoir[lo:hi] = feats[sample_idx[lo:hi] - start]
                start += c
        tracing.record_ingest_pass(1, time.perf_counter() - t_pass, start)
        log.check(start == total_rows,
                  "Input file changed between the streaming passes "
                  f"(pass 0: {total_rows} rows, pass 1: {start})")
        if sample_idx is None:
            sample = (np.concatenate(sample_parts) if sample_parts
                      else np.zeros((0, 0), np.float64))
        else:
            sample = reservoir
        del sample_parts, reservoir

        ds.num_total_features = num_cols or 0
        ds.feature_names = dataset_mod._make_feature_names(
            header_names, ds.label_idx, ds.num_total_features)

        # shard mask BEFORE the in-file group column overrides query
        # boundaries — the resident loader's order of operations
        # (side-file boundaries drive query-atomic sharding)
        ds.used_data_indices = ds._draw_shard_mask(io_config, rank,
                                                   num_machines,
                                                   total_rows)
        mask = None
        if ds.used_data_indices is not None:
            mask = np.zeros(total_rows, dtype=bool)
            mask[ds.used_data_indices] = True

        ds._build_bin_mappers(sample, io_config.max_bin, bin_finder,
                              ignore_set)
        del sample

        if weight_idx >= 0:
            log.info("using weight in data file, and ignore additional "
                     "weight file")
            ds.metadata.weights = np.concatenate(weight_parts)
        if group_idx >= 0:
            log.info("using query id in data file, and ignore additional "
                     "query file")
            ds.metadata.query_boundaries = None
            ds.metadata.set_queries_from_column(np.concatenate(group_parts))

        all_labels = (np.concatenate(labels_parts) if labels_parts
                      else np.zeros((0,), np.float32))
        ds.metadata.set_label(all_labels)
        if ds.used_data_indices is not None:
            if ds.metadata.queries is not None:
                ds.metadata.queries = \
                    ds.metadata.queries[ds.used_data_indices]
            ds.metadata.partition(ds.used_data_indices, total_rows)
            ds.num_data = len(ds.used_data_indices)
        else:
            ds.num_data = total_rows
        # finalized BEFORE pass 2: the streamed cache header needs the
        # final query boundaries (finalize is idempotent — the outer
        # loader's second call is a no-op check)
        ds.metadata.finalize(ds.num_data)

        # ---- pass 2: quantize chunks straight into the bin matrix
        F_used = len(ds.bin_mappers)
        dtype = dataset_mod._bin_dtype(
            int(ds.num_bins.max()) if F_used else 256)
        writer = (DeviceRowWriter(
                      F_used, ds.num_data, dtype,
                      sharding=_placement(ds.num_data, shard_rows,
                                          shard_devices, device_type))
                  if device_resident
                  else HostRowWriter(F_used, ds.num_data, dtype))
        cache = _open_cache(ds, io_config, dtype, (F_used, ds.num_data),
                            foreign_bin)
        init_scores = [] if predict_fun is not None else None
        cursor = 0
        start = 0
        chunk_no = 0
        t_pass = time.perf_counter()
        try:
            for lines in parser_mod.prefetch_chunks(
                    parser_mod.read_line_chunks(
                        filename, skip_header=io_config.has_header,
                        chunk_lines=chunk_rows)):
                with telemetry.span("ingest_bin"):
                    # per-chunk tokenizer/bin/H2D split (ISSUE 17): the
                    # attribution that turns an ingest_rows_per_sec
                    # regression into a named phase.  perf_counter pairs
                    # around the three stages; the spans above stay the
                    # coarse (gated) lane.
                    t0 = time.perf_counter()
                    feats = parser.parse(lines).features
                    c0 = feats.shape[0]
                    if mask is not None:
                        feats = feats[mask[start:start + c0]]
                    t1 = time.perf_counter()
                    n = feats.shape[0]
                    t2 = t_h2d = t1
                    if n:
                        binned = np.empty((F_used, n), dtype=dtype)
                        for j_raw, j_inner in ds.used_feature_map.items():
                            binned[j_inner] = \
                                ds.bin_mappers[j_inner].value_to_bin(
                                    feats[:, j_raw]).astype(dtype)
                        if init_scores is not None:
                            init_scores.append(np.asarray(
                                predict_fun(feats),
                                np.float32).reshape(-1))
                        t2 = time.perf_counter()
                        if cache is not None:
                            cache.write(binned, cursor)
                        writer.append(binned, cursor)
                        t_h2d = time.perf_counter()
                parse_us = (t1 - t0) * 1e6
                bin_us = (t2 - t1) * 1e6
                h2d_us = (t_h2d - t2) * 1e6
                telemetry.count("ingest/chunks")
                telemetry.count("ingest/rows", n)
                telemetry.count("ingest/parse_us", int(parse_us))
                telemetry.count("ingest/bin_us", int(bin_us))
                telemetry.count("ingest/h2d_us", int(h2d_us))
                tracing.record_ingest_chunk(2, chunk_no, n, parse_us,
                                            bin_us, h2d_us)
                chunk_no += 1
                cursor += n
                start += c0
            log.check(start == total_rows and cursor == ds.num_data,
                      "Input file changed between the streaming passes "
                      f"(pass 1: {total_rows} rows, pass 2: {start})")
            tracing.record_ingest_pass(2, time.perf_counter() - t_pass,
                                       cursor)
            # the final drain (device_put commit / in-flight transfers)
            # belongs to the H2D phase too — without it the attribution
            # would under-report exactly the part that scales with data
            t_fin = time.perf_counter()
            out = writer.finish()
            telemetry.count("ingest/h2d_us",
                            int((time.perf_counter() - t_fin) * 1e6))
            if device_resident:
                ds.device_bins = out
                ds.bins = None
            else:
                ds.bins = out
            if init_scores is not None:
                ds.metadata.init_score = np.concatenate(init_scores)
            if cache is not None:
                cache.finish()
        except BaseException:
            if cache is not None:
                cache.abort()
            raise


def _placement(num_rows: int, shard_rows: bool,
               shard_devices: Optional[int] = None,
               device_type: str = ""):
    """``shard_devices is not None`` marks a single-process PARALLEL
    consumer (its value = the learner's get_mesh size): the matrix must
    then live on the learner's mesh even when rows aren't sharded, or
    the learner's multi-device shard_map would see an incompatible
    one-device commit."""
    from ..parallel.mesh import dataset_row_sharding
    return dataset_row_sharding(
        num_rows, shard_rows=shard_rows, num_machines=shard_devices,
        device_type=device_type,
        parallel_consumer=shard_devices is not None)


def _open_cache(ds, io_config, dtype, shape,
                foreign_bin: bool = False) -> Optional[_CacheWriter]:
    if not io_config.is_save_binary_file:
        return None
    bin_path = io_config.data_filename + ".bin"
    if foreign_bin:
        # load_train already warned ("NOT overwriting it"): a foreign
        # .bin next to the data file must never be clobbered
        return None
    if io_config.save_binary_format == "reference":
        log.warning("save_binary_format=reference is not supported by "
                    "the streaming loader (the reference layout is "
                    "per-feature-major); skipping the cache write — use "
                    "streaming=false to write a reference cache")
        return None
    return _CacheWriter(ds._binary_header(dtype, shape), bin_path,
                        dtype, shape)


# ---------------------------------------------------- binary-cache load


def load_binary_streaming(ds, path: str, io_config,
                          shard_rows: bool = False,
                          shard_devices: Optional[int] = None,
                          device_type: str = "") -> None:
    """Stream a NATIVE binary cache into device memory: the header is
    parsed as usual, but the ``[F, N]`` bin-matrix region is memmapped
    and fed to the device in row chunks (bounded host RSS) instead of
    being read into one host array.  Single-process only — multi-process
    cache loads reshard rows host-side and keep the resident path."""
    from .dataset import BINARY_MAGIC

    chunk_rows = getattr(io_config, "ingest_chunk_rows", 200_000)
    with telemetry.span("ingest"):
        try:
            with open(path, "rb") as f:
                f.read(len(BINARY_MAGIC))
                size = int.from_bytes(f.read(8), "little")
                header = pickle.loads(f.read(size))
                offset = f.tell()
        except log.LightGBMError:
            raise
        except Exception as e:
            log.fatal("Binary file %s is a damaged lightgbm_tpu cache "
                      "(%s) — delete it to regenerate" % (path, e))
        ds._apply_binary_header(header)
        dtype = np.dtype(header["bins_dtype"])
        shape = tuple(header["bins_shape"])
        mm = np.memmap(path, dtype=dtype, mode="r", offset=offset,
                       shape=shape) if shape[0] * shape[1] else None
        writer = DeviceRowWriter(
            shape[0], shape[1], dtype,
            sharding=_placement(shape[1], shard_rows, shard_devices,
                                device_type))
        # cache loads file the same pass/chunk attribution as the text
        # path (pass 2 only, parse_us=0: there is no tokenizer here), so
        # trace dumps and pod_report ingest attribution aren't blind on
        # the fast path
        t_pass = time.perf_counter()
        chunk_no = 0
        if mm is not None:
            for s in range(0, shape[1], chunk_rows):
                e = min(s + chunk_rows, shape[1])
                with telemetry.span("ingest_bin"):
                    t0 = time.perf_counter()
                    chunk = np.ascontiguousarray(mm[:, s:e])
                    t1 = time.perf_counter()
                    writer.append(chunk, s)
                    t2 = time.perf_counter()
                bin_us = (t1 - t0) * 1e6
                h2d_us = (t2 - t1) * 1e6
                telemetry.count("ingest/chunks")
                telemetry.count("ingest/rows", e - s)
                telemetry.count("ingest/bin_us", int(bin_us))
                telemetry.count("ingest/h2d_us", int(h2d_us))
                tracing.record_ingest_chunk(2, chunk_no, e - s, 0.0,
                                            bin_us, h2d_us)
                chunk_no += 1
        t_fin = time.perf_counter()
        ds.device_bins = writer.finish()
        telemetry.count("ingest/h2d_us",
                        int((time.perf_counter() - t_fin) * 1e6))
        tracing.record_ingest_pass(2, time.perf_counter() - t_pass,
                                   shape[1])
        ds.bins = None
        ds.metadata.finalize(ds.num_data)
