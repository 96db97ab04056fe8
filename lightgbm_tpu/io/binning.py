"""Feature binning: value → bin quantization.

Re-implements the reference BinMapper (/root/reference/src/io/bin.cpp:42-132,
include/LightGBM/bin.h:47-119, 296-309) with NumPy.  The FindBin algorithm is
reproduced step-for-step (distinct-values fast path, dedicated bins for
high-count values, equal-frequency remainder) because differential tests
against the reference depend on identical bin boundaries.

TPU-first difference: there is no per-feature Bin object zoo
(DenseBin/SparseBin/OrderedSparseBin are CPU cache optimizations,
dense_bin.hpp/sparse_bin.hpp) — the whole dataset becomes one dense
``[num_features, num_rows]`` integer matrix living in HBM; see
lightgbm_tpu/io/dataset.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional

import numpy as np

from .. import hatches, telemetry


@dataclass
class BinMapper:
    """Quantization map for one feature (bin.h:47-119)."""
    num_bin: int = 0
    is_trivial: bool = False
    sparse_rate: float = 0.0
    # bin i covers values <= bin_upper_bound[i]; last entry is +inf
    bin_upper_bound: np.ndarray = field(default_factory=lambda: np.zeros(0))

    def find_bin(self, values: np.ndarray, max_bin: int) -> None:
        """BinMapper::FindBin (bin.cpp:42-132), literal algorithm port.

        ``values`` are the sampled values for this feature, zeros included
        (dataset.cpp:278-305 pushes an explicit 0.0 per sampled row).
        """
        values = np.asarray(values, dtype=np.float64)
        sample_size = values.size
        distinct_values, counts = np.unique(values, return_counts=True)
        distinct_values = list(distinct_values)
        counts = [int(c) for c in counts]
        num_values = len(distinct_values)
        cnt_in_bin0 = 0

        if num_values <= max_bin:
            # distinct values are enough: midpoints as boundaries
            self.num_bin = num_values
            upper = np.empty(num_values, dtype=np.float64)
            for i in range(num_values - 1):
                upper[i] = (distinct_values[i] + distinct_values[i + 1]) / 2.0
            if num_values > 0:
                cnt_in_bin0 = counts[0]
                upper[num_values - 1] = np.inf
            self.bin_upper_bound = upper
        else:
            # hybrid: dedicated bins for large-count values, then
            # equal-frequency for the remainder
            mean_bin_size = sample_size / float(max_bin)
            rest_sample_cnt = sample_size
            bin_cnt = 0
            self.num_bin = max_bin
            upper_bounds = [np.inf] * max_bin
            lower_bounds = [np.inf] * max_bin
            # sort by count, descending.  Tie order among equal counts is
            # provably irrelevant to the resulting bounds (dedicated-bin
            # membership is a strict threshold over a contiguous tie run,
            # and both the remainder and the final bins are re-sorted by
            # value) — proven adversarially in tests/test_binning.py.
            # DELIBERATE DIVERGENCE (PARITY.md): the reference's remainder
            # value sort goes through Common::SortForPair
            # (common.h:362-381), whose write-back is off by `start`; with
            # start=bin_cnt>0 (bin.cpp:93) it DROPS the bin_cnt smallest
            # remainder values and leaves a stale std::sort-order-dependent
            # tail, silently losing bin boundaries on features with
            # dedicated bins.  We implement the intended algorithm
            # (tests/test_reference_differential.py::
            # test_binning_count_ties_reference_sortforpair_defect pins
            # both behaviors).
            order = sorted(range(num_values), key=lambda i: -counts[i])
            counts = [counts[i] for i in order]
            distinct_values = [distinct_values[i] for i in order]
            # fetch big slots as dedicated bins
            while bin_cnt < num_values and counts[bin_cnt] > mean_bin_size:
                upper_bounds[bin_cnt] = distinct_values[bin_cnt]
                lower_bounds[bin_cnt] = distinct_values[bin_cnt]
                rest_sample_cnt -= counts[bin_cnt]
                bin_cnt += 1
            # process remainder bins
            if bin_cnt < max_bin:
                # sort rest by value ascending
                rest = sorted(range(bin_cnt, num_values),
                              key=lambda i: distinct_values[i])
                distinct_values[bin_cnt:] = [distinct_values[i] for i in rest]
                counts[bin_cnt:] = [counts[i] for i in rest]
                mean_bin_size = rest_sample_cnt / float(max_bin - bin_cnt)
                lower_bounds[bin_cnt] = distinct_values[bin_cnt]
                cur_cnt_inbin = 0
                for i in range(bin_cnt, num_values - 1):
                    rest_sample_cnt -= counts[i]
                    cur_cnt_inbin += counts[i]
                    if cur_cnt_inbin >= mean_bin_size:
                        upper_bounds[bin_cnt] = distinct_values[i]
                        if bin_cnt == 0:
                            cnt_in_bin0 = cur_cnt_inbin
                        bin_cnt += 1
                        lower_bounds[bin_cnt] = distinct_values[i + 1]
                        if bin_cnt >= max_bin - 1:
                            break
                        cur_cnt_inbin = 0
                        mean_bin_size = rest_sample_cnt / float(max_bin - bin_cnt)
            # sort (lower, upper) pairs by lower bound
            pairs = sorted(zip(lower_bounds, upper_bounds), key=lambda p: p[0])
            lower_bounds = [p[0] for p in pairs]
            upper_bounds = [p[1] for p in pairs]
            self.num_bin = bin_cnt
            upper = np.empty(bin_cnt, dtype=np.float64)
            for i in range(bin_cnt - 1):
                upper[i] = (upper_bounds[i] + lower_bounds[i + 1]) / 2.0
            if bin_cnt > 0:
                upper[bin_cnt - 1] = np.inf
            self.bin_upper_bound = upper

        self.is_trivial = self.num_bin <= 1
        self.sparse_rate = (cnt_in_bin0 / float(sample_size)
                            if sample_size > 0 else 0.0)

    def value_to_bin(self, value):
        """ValueToBin binary search (bin.h:296-309): first bin whose upper
        bound >= value.  Vectorized: accepts scalars or arrays."""
        bounds = self.bin_upper_bound[:-1]  # last is +inf
        return np.searchsorted(bounds, np.asarray(value), side="left").astype(np.int32)

    def bin_to_value(self, bin_idx: int) -> float:
        """Upper bound of a bin; used as the real-valued split threshold
        (serial_tree_learner.cpp:418 BinToValue)."""
        return float(self.bin_upper_bound[bin_idx])

    def bin_representatives(self) -> np.ndarray:
        """One finite real value per bin that ``value_to_bin`` maps back
        to that bin — the decode table for predicting straight from a
        columnar-binary cache (predictor.predict_file on a ``.bin``
        input).  Bin b < num_bin-1 uses its own upper bound: bounds are
        strictly increasing and the searchsorted is side="left", so
        ``value_to_bin(upper[b]) == b`` exactly.  The last bin's bound is
        +inf — any value strictly above the previous bound lands there,
        so ``upper[-2] + 1`` does (single-bin mappers are trivial; 0.0
        keeps them finite)."""
        vals = self.bin_upper_bound.astype(np.float64).copy()
        if vals.size and not np.isfinite(vals[-1]):
            vals[-1] = vals[-2] + 1.0 if vals.size > 1 else 0.0
        return vals

    @property
    def default_bin(self) -> int:
        """Bin of value 0 — the implicit bin for unseen entries
        (bin.h CreateBin default_bin = ValueToBin(0))."""
        return int(self.value_to_bin(0.0))

    # --- serialization (bin.cpp:144-175 fixed layout, used by the binary
    # dataset cache and distributed bin-mapper gathers) ---

    def to_bytes(self) -> bytes:
        import struct
        head = struct.pack("<i?7x d", self.num_bin, self.is_trivial, self.sparse_rate)
        return head + np.asarray(self.bin_upper_bound, dtype=np.float64).tobytes()

    @classmethod
    def from_bytes(cls, buffer: bytes) -> "BinMapper":
        import struct
        num_bin, is_trivial, sparse_rate = struct.unpack_from("<i?7x d", buffer, 0)
        offset = struct.calcsize("<i?7x d")
        upper = np.frombuffer(buffer, dtype=np.float64, count=num_bin,
                              offset=offset).copy()
        return cls(num_bin=num_bin, is_trivial=bool(is_trivial),
                   sparse_rate=sparse_rate, bin_upper_bound=upper)


# ---------------------------------------------------------------------------
# Mixed-bin feature packing (ISSUE 6).
#
# The reference pays per-feature bin counts: BinMapper.find_bin emits
# ``num_bin <= max_bin`` PER FEATURE, and the CPU scatter-add loop touches
# only the bins a feature actually has.  The TPU one-hot-matmul kernels
# instead price every feature at the uniform ``num_bins_max`` histogram
# width — a 3-distinct-value flag column costs the same 255-wide pass as a
# fully continuous one.  The fix is a LAYOUT decision made once at Dataset
# build time: partition features into bin-WIDTH classes (narrow: num_bin
# fits the 64-wide kernel class — the measured-fast ``maxbin63`` shape;
# wide: everything else at the dataset's num_bins_max), reorder the bin
# matrix so each class is a contiguous feature block, and run one histogram
# pass per class.  The per-class histograms are concatenated back into
# CANONICAL feature order before split finding, so feature indices,
# argmax tie-breaks, ownership blocks and trees are exactly the uniform
# path's — a narrow feature's bins beyond its num_bin are all zero in the
# uniform pass too, so the reassembled histogram is value-identical.
#
# The spec is a NamedTuple of plain tuples: hashable, so it rides the
# growers' jit static args and the chunk-program cache keys.

# bin-width classes: features with num_bin <= NARROW_BINS take the narrow
# kernel class (one 64-wide histogram pass — the ``maxbin63`` kernel shape
# measured at 2.6x the 255-wide pass); everything else pays num_bins_max.
# scripts/hist_kernel_bench.py --sweep-classes re-derives this threshold
# from measurement when kernel economics change.
NARROW_BINS = 64


class PackSpec(NamedTuple):
    """Static description of a packed bin-matrix layout.

    widths : per-class histogram width, ascending (e.g. ``(64, 255)``)
    counts : features per class, same order; ``sum(counts) == F``
    perm   : packed position -> canonical inner feature index (stable
             within each class, so the packed order is reproducible)
    """
    widths: tuple
    counts: tuple
    perm: tuple

    @property
    def num_features(self) -> int:
        return len(self.perm)

    @property
    def ranges(self):
        """Per-class ``(start, count, width)`` in packed feature order."""
        out, start = [], 0
        for cnt, width in zip(self.counts, self.widths):
            out.append((start, cnt, width))
            start += cnt
        return tuple(out)

    @property
    def c2p(self) -> tuple:
        """Canonical inner feature index -> packed position (inverse of
        ``perm``)."""
        inv = [0] * len(self.perm)
        for p, f in enumerate(self.perm):
            inv[f] = p
        return tuple(inv)


class BlockedPackSpec(NamedTuple):
    """Block-local mixed-bin layout for feature-block ownership meshes
    (ISSUE 12): the bin-width-class permutation is computed PER owned
    feature block of width ``block`` and never crosses a block boundary,
    so packing COMMUTES with contiguous feature-block ownership — the
    storage positions of ownership block ``b`` are exactly the canonical
    positions ``[b*block, (b+1)*block)``, only the inner order changes.
    The owned-block psum / psum_scatter and the packed-SplitInfo
    allreduce therefore ride unchanged, and the hybrid/voting learners
    no longer force the uniform layout.

    SPMD constraint: every shard traces ONE program, so the per-block
    class counts must be identical across blocks.  ``counts`` is the
    per-block split ``(narrow, block - narrow)`` with ``narrow`` = the
    MINIMUM narrow-feature count over all blocks; each block stores its
    first ``narrow`` narrow features (canonical order, stable) in the
    narrow segment and everything else — surplus narrow features
    included — in the wide segment at the full width (a narrow feature
    histogrammed at the wide width is value-identical: its bins beyond
    ``num_bin`` are zero either way).  The plan degenerates to None
    (uniform layout) when the narrowest block contributes no narrow
    feature — see :func:`plan_feature_packing_blocked`.

    widths : per-class histogram width ``(narrow_bins, num_bins_max)``
    counts : per-BLOCK features per class ``(c_n, block - c_n)``
    block  : the ownership block width ``Fb = ceil(F / feature_shards)``
    perm   : packed storage position -> canonical feature (global, len F;
             a concatenation of within-block permutations)
    """
    widths: tuple
    counts: tuple
    block: int
    perm: tuple

    @property
    def num_features(self) -> int:
        return len(self.perm)

    @property
    def c2p(self) -> tuple:
        """Canonical feature -> packed storage position (global)."""
        inv = [0] * len(self.perm)
        for p, f in enumerate(self.perm):
            inv[f] = p
        return tuple(inv)

    @property
    def ranges(self):
        """Global per-class ``(start, count, width)`` segments in packed
        storage order: per-block interleaved ``narrow`` then ``wide``
        segments (the full-F histogram routes run one pass per segment
        and reassemble via ``c2p`` — the generic PackSpec contract)."""
        F = len(self.perm)
        c_n = self.counts[0]
        out = []
        for start in range(0, F, self.block):
            width = min(self.block, F - start)
            if c_n:
                out.append((start, c_n, self.widths[0]))
            if width > c_n:
                out.append((start + c_n, width - c_n, self.widths[1]))
        return tuple(out)

    @property
    def block_view(self) -> PackSpec:
        """The per-owned-block view the SHARDED histogram passes use: the
        sliced ``[Fb, N]`` owned block is already in packed order and
        STAYS in packed order (identity perm) — feature identity is
        restored at the split finder's storage->canonical remap, so the
        pass structure is shard-uniform (SPMD) even though each block's
        inner permutation differs."""
        return PackSpec(widths=self.widths,
                        counts=(self.counts[0],
                                self.block - self.counts[0]),
                        perm=tuple(range(self.block)))


def plan_feature_packing_blocked(num_bins, num_bins_max: int,
                                 block: int,
                                 mode: str = "auto",
                                 narrow_bins: int = NARROW_BINS,
                                 shards: int = 0
                                 ) -> Optional[BlockedPackSpec]:
    """Block-local mixed-bin plan for a contiguous feature-block
    ownership layout (``block`` = the per-shard block width, ``shards``
    the feature-shard count when known).  Returns None — the uniform
    layout — when packing cannot help or cannot hold: single global
    class (same rule as :func:`plan_feature_packing`), a shard that owns
    ONLY ownership padding (``block * (shards-1) >= F``: its clamped
    duplicate lanes would land a wide feature in the narrow segment —
    garbage outside the masked lanes, but the degenerate mesh isn't
    worth serving), or a narrowest block with no narrow feature (the
    uniform per-block class counts would be ``(0, block)`` — one
    class)."""
    if mode == "false" or hatches.flag("LGBM_TPU_NO_MIXEDBIN"):
        return None
    nb = np.asarray(num_bins)
    F = nb.size
    if F == 0 or num_bins_max <= narrow_bins or block <= 0:
        return None
    if shards > 1 and block * (shards - 1) >= F:
        return None
    narrow = nb <= narrow_bins
    if not narrow.any() or narrow.all():
        return None
    starts = list(range(0, F, block))
    c_n = min(int(narrow[s:s + block].sum()) for s in starts)
    if c_n == 0:
        return None
    perm = []
    for s in starts:
        width = min(block, F - s)
        local = np.arange(s, s + width)
        is_n = narrow[s:s + width]
        first_n = local[is_n][:c_n]
        rest = np.array([f for f in local if f not in set(first_n)],
                        dtype=np.int64)
        perm.extend(int(i) for i in np.concatenate([first_n, rest]))
    return BlockedPackSpec(
        widths=(int(narrow_bins), int(num_bins_max)),
        counts=(int(c_n), int(block - c_n)),
        block=int(block),
        perm=tuple(perm))


def plan_feature_packing(num_bins, num_bins_max: int,
                         mode: str = "auto",
                         narrow_bins: int = NARROW_BINS
                         ) -> Optional[PackSpec]:
    """Decide the packed layout for a dataset's per-feature bin counts.

    Returns None when packing cannot help — a single bin-width class
    (every feature wide, or every feature already within the narrow
    width so ``num_bins_max`` is small anyway) collapses to the existing
    single-pass path with no layout change at all.  ``mode``:
    "auto"/"true" enable (auto and true only differ for callers that log
    the decision), "false" disables.  The ``LGBM_TPU_NO_MIXEDBIN=1`` env
    hatch forces off for A/B timing without touching configs."""
    if mode == "false" or hatches.flag("LGBM_TPU_NO_MIXEDBIN"):
        return None
    nb = np.asarray(num_bins)
    if nb.size == 0 or num_bins_max <= narrow_bins:
        return None
    narrow = nb <= narrow_bins
    if not narrow.any() or narrow.all():
        # degenerate: one class only — the uniform path IS the packed
        # path (all-narrow datasets already ride a small num_bins_max)
        return None
    order = np.concatenate([np.nonzero(narrow)[0], np.nonzero(~narrow)[0]])
    return PackSpec(
        widths=(int(narrow_bins), int(num_bins_max)),
        counts=(int(narrow.sum()), int((~narrow).sum())),
        perm=tuple(int(i) for i in order))


def find_bins_for_matrix(sample: np.ndarray, max_bin: int,
                         skip=()) -> List[Optional[BinMapper]]:
    """Compute a BinMapper per column of a dense sample matrix
    (ConstructBinMappers single-machine path, dataset.cpp:322-350); a
    column in ``skip`` gets None.  The one place cut points are found for
    every loader, so the ``find_bins`` span and ``bin/sample_rows`` live
    here and nowhere else; the caller holds ``dataset_bin`` open."""
    with telemetry.span("find_bins"):
        telemetry.count("bin/sample_rows", int(sample.shape[0]))
        mappers = []
        for j in range(sample.shape[1]):
            if j in skip:
                mappers.append(None)
                continue
            mapper = BinMapper()
            mapper.find_bin(sample[:, j], max_bin)
            mappers.append(mapper)
        return mappers
