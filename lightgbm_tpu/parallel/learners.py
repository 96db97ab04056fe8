"""Parallel tree learners over the device mesh.

Re-designs of /root/reference/src/treelearner/{data,feature}_parallel_tree_learner.cpp
with XLA collectives inside ``shard_map``:

- **data-parallel** (rows sharded over the ``data`` axis): every shard builds
  local histograms, a ``psum`` produces the identical global histograms on
  all shards, and the replicated split search yields bit-identical trees —
  the reference's invariant (data_parallel_tree_learner.cpp:237-243: every
  worker ends each split with the identical global best split) enforced by
  construction.  The reference's ReduceScatter+owned-feature-search+Allgather
  schedule (lines 135-235) is a bandwidth optimization of the same reduction;
  psum is its all-to-all equivalent on ICI.
- **feature-parallel** (feature ownership sharded over the ``feature`` axis,
  rows replicated): each shard histograms and searches ONLY its owned
  feature slice, then a packed SplitInfo argmax-allreduce picks the global
  winner (feature_parallel_tree_learner.cpp:46-79, SplitInfo::MaxReducer
  split_info.hpp:56-72: max gain, ties → smaller feature index); the split
  itself is applied locally on the replicated bin matrix.
- **hybrid** (ISSUE 9: rows sharded over ``data`` AND feature blocks owned
  over ``feature`` on one explicit 2-D mesh, ``num_machines = data_shards
  x feature_shards``): histograms build local-rows x owned-features, the
  reduction is a data-axis psum restricted to the owned block — per-shard
  wire bytes O(F·B / feature_shards) — and the SplitInfo allreduce rides
  the feature axis.
- **voting** (ISSUE 9: the reference NAMES this learner but Fatals on it,
  src/io/config.cpp:311-313 — the PV-tree design realized): per-shard
  top-k split voting, full histograms exchanged only for the <= 2·top_k
  globally-voted features — per-split wire bytes O(min(2k, F/fs)·B).

All four learners drive the ONE schedule-parameterized grower
(models/grower_unified.py): a growth policy (leafwise / depthwise /
leafcompact) plus a declarative SeamSchedule built here.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import telemetry
from ..models.grower_unified import (SeamSchedule, TreeArrays,
                                     grow_tree_unified)
from ..models.gbdt import _effective_num_leaves, _tuning_kwargs
from ..ops.split import (SplitResult, find_best_split,
                         per_feature_best_scores)
from ..io.binning import BinMapper
from ..utils import log
from .mesh import (DATA_AXIS, FEATURE_AXIS, factor_machines, get_mesh,
                   get_mesh2d)


def aggregate_telemetry() -> None:
    """Fold every host's kernel-route counters — and its peak-memory
    watermark — into the leader's registry (``allhosts/<name>`` counter
    keys; ``allhosts_peak_bytes_in_use`` in the memory block) so the
    leader's JSONL summary speaks for the whole job, not just process 0.
    Health anomaly totals ride the counters (``health/*``,
    health.HealthMonitor.apply_policy mirrors every anomaly there), so
    they aggregate with no extra machinery.

    COLLECTIVE: every multi-process run must call it on EVERY process
    (gbdt.run_training does, at end of training) — including processes
    with telemetry disabled, whose counters are simply empty; gating
    participation on local telemetry state would hang the enabled hosts
    in the allgather.  Hosts may also disagree on which counters exist (a
    per-process LGBM_TPU_NO_PALLAS trip, a warm persistent compile cache
    skipping recompiles), so each host ships its payload as a JSON blob
    in a fixed-size byte buffer and counters are summed BY NAME — a
    fixed-order value allgather would silently add other hosts' values to
    the wrong keys whenever key sets differ with equal cardinality.
    Memory peaks reduce by max (a watermark, not a flow).
    Single-process runs return immediately."""
    if jax.process_count() <= 1:
        return
    blob_cap = 1 << 14
    try:
        import json
        from jax.experimental import multihost_utils
        items = sorted(telemetry.counters().items())
        payload = {"c": dict(items),
                   "mem_peak": telemetry.mem_peak_bytes()}
        raw = json.dumps(payload).encode()
        while len(raw) > blob_cap and items:  # pragma: no cover - 100s of keys
            items = items[:len(items) // 2]
            payload["c"] = dict(items)
            raw = json.dumps(payload).encode()
            log.warning("telemetry counters exceed the %d-byte aggregation "
                        "buffer; cross-host sums cover only this host's "
                        "first %d keys" % (blob_cap, len(items)))
        buf = np.zeros(blob_cap, np.uint8)
        buf[:len(raw)] = np.frombuffer(raw, np.uint8)
        gathered = np.asarray(multihost_utils.process_allgather(buf))
        totals: dict = {}
        peak = 0
        for row in gathered:
            blob = json.loads(bytes(row).rstrip(b"\x00").decode() or "{}")
            for k, v in blob.get("c", {}).items():
                totals[k] = totals.get(k, 0) + int(v)
            peak = max(peak, int(blob.get("mem_peak", 0)))
        if telemetry.enabled():
            telemetry.merge_host_counters(totals)
            if peak:
                telemetry.merge_host_memory(peak)
    except Exception as e:  # pragma: no cover - collective failure
        log.warning("telemetry cross-host aggregation failed: %s" % e)

try:
    from jax import shard_map as _shard_map  # JAX >= 0.7 name

    def shard_map(f, mesh, in_specs, out_specs, check_rep=False):
        return _shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_rep)
except ImportError:  # pragma: no cover - older JAX
    from jax.experimental.shard_map import shard_map as _shard_map_old

    def shard_map(f, mesh, in_specs, out_specs, check_rep=False):
        return _shard_map_old(f, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_rep=check_rep)


def allreduce_best_split(res: SplitResult, axis_name: str,
                         site: str = None, loop: int = 1,
                         phase: str = None) -> SplitResult:
    """SplitInfo::MaxReducer as an argmax allreduce (split_info.hpp:56-104):
    max gain wins; ties broken by the smaller (global) feature index.
    ``site`` files the traced collective in the telemetry wire-metrics
    registry (ISSUE 5) — payload is the packed SplitInfo struct."""
    if site is not None:
        telemetry.record_collective(site, "all_gather", axis_name,
                                    telemetry._tree_nbytes(res),
                                    loop=loop, phase=phase)
    stacked = jax.tree.map(lambda x: jax.lax.all_gather(x, axis_name), res)
    gain = stacked.gain
    max_gain = jnp.max(gain)
    is_max = (gain == max_gain) & jnp.isfinite(max_gain)
    feat_key = jnp.where(is_max, stacked.feature, jnp.int32(1 << 30))
    pick = jnp.argmin(feat_key)
    return jax.tree.map(lambda x: x[pick], stacked)


def ownership_finder(own_s, axis_name, site: str = None, loop: int = 1,
                     phase: str = None):
    """Owned-block split finder shared by the feature-parallel learner and
    the data-parallel reduce_scatter schedule: local FindBestThreshold over
    the owned feature block, block-local -> global feature remap, then the
    SplitInfo MaxReducer allreduce (split_info.hpp:56-104)."""
    def finder(hist, sg, sh, cnt, nb, fm, mind, minh):
        local = find_best_split(hist, sg, sh, cnt, nb, fm, mind, minh)
        local = local._replace(
            feature=own_s[local.feature].astype(jnp.int32))
        return allreduce_best_split(local, axis_name, site=site,
                                    loop=loop, phase=phase)
    return finder


def _owned_block(F: int, num_shards: int, axis_name: str):
    """Contiguous-feature-block ownership, the ONE home of the layout
    shared by every ownership schedule (dp reduce_scatter, hybrid,
    voting): ``(Fb, Fpad, ids)`` where ``Fb`` is the per-shard block
    width, ``Fpad`` the padded feature count, and ``ids()`` — called
    inside the traced shard context — returns ``(idx, ownok, own_s)``:
    this shard's global feature ids, their validity (padding blocks
    clamp to duplicates of feature F-1, masked out), and the clamped
    gather indices."""
    Fb = -(-F // num_shards)
    Fpad = Fb * num_shards

    def ids():
        rank = jax.lax.axis_index(axis_name)
        idx = rank * Fb + jnp.arange(Fb, dtype=jnp.int32)
        return idx, idx < F, jnp.minimum(idx, F - 1)
    return Fb, Fpad, ids


def dp_ownership_seams(F: int, num_shards: int, site_prefix: str = "dp_rs",
                       loop: int = 1, phase: str = "grow",
                       root_loop: int = 1):
    """Contiguous-feature-block ownership seams for the data-parallel
    reduce_scatter schedule (data_parallel_tree_learner.cpp:135-235),
    shared by the masked and COMPACTED leaf-wise shard closures: returns
    a traced-context function (fmask, nbins) ->
    (fmask_own, nbins_own, SeamSchedule) — the owned mask/bin slices to
    pass positionally plus the declarative schedule for
    grow_tree_unified (models/grower_unified.py).

    ``site_prefix``/``loop``/``phase`` label the wire-metrics sites
    (telemetry.collective_span, ISSUE 5): per-split seams run inside the
    grower's split loop, so the caller passes its executed-calls-per-
    trace estimate as ``loop`` (e.g. num_leaves-1 for the leaf-wise
    fori_loop, x chunk length on the fused path)."""
    Fb, Fpad, block_ids = _owned_block(F, num_shards, DATA_AXIS)
    _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                           phase=phase)

    def seams(fmask, nbins):
        idx, ownok, own_s = block_ids()
        rank = jax.lax.axis_index(DATA_AXIS)

        def pad_f(x):
            if Fpad == F:
                return x
            widths = [(0, 0)] * x.ndim
            widths[0] = (0, Fpad - F)
            return jnp.pad(x, widths)

        def scatter0(h):
            # per-split [F, B, ...] histogram (f32) or [F, B, lanes]
            # INT accumulator — both carry features on axis 0
            return jax.lax.psum_scatter(
                pad_f(h), DATA_AXIS, scatter_dimension=0, tiled=True)

        def own_slice(h):
            # replicated full root histogram -> this shard's block
            return jax.lax.dynamic_slice_in_dim(
                pad_f(h), rank * Fb, Fb, axis=0)

        scat = _c(site_prefix + "/hist_scatter", scatter0,
                  kind="psum_scatter", loop=loop)
        schedule = SeamSchedule(
            hist_axis=DATA_AXIS,
            hist_reduce=scat, int_hist_reduce=scat,
            stat_reduce=_c(site_prefix + "/root_stats",
                           lambda s: jax.lax.psum(s, DATA_AXIS),
                           kind="psum", loop=root_loop),
            root_hist_reduce=_c(site_prefix + "/root_hist",
                                lambda h: jax.lax.psum(h, DATA_AXIS),
                                kind="psum", loop=root_loop),
            own_slice=own_slice,
            split_finder=ownership_finder(
                own_s, DATA_AXIS, site=site_prefix + "/splitinfo_allreduce",
                loop=loop, phase=phase))
        return fmask[own_s] & ownok, jnp.take(nbins, own_s), schedule
    return seams


def hybrid_ownership_seams(F: int, feature_shards: int, site_prefix: str,
                           loop: int = 1, phase: str = "grow",
                           root_loop: int = 1, slice_hist: bool = False,
                           pack=None):
    """``dp_ownership_seams`` generalized to the 2-D ``(data, feature)``
    mesh (ISSUE 9): contiguous feature-block ownership lives on the
    FEATURE axis and the histogram reduction runs over the DATA axis,
    RESTRICTED to the owned block — per-shard wire bytes drop from
    O(F·B) to O(F·B / feature_shards).  The split search runs on owned
    features and the packed SplitInfo allreduce rides the feature axis.

    ``slice_hist=False``: the caller pre-slices ``bins`` to the owned
    block (local-rows × owned-features histogram compute — the hybrid
    plan's compute saving), so the hist seam is a plain data-axis psum.
    ``slice_hist=True`` (the compact pane keeps all F features): local
    histograms are full-F and the seam cuts the owned block out BEFORE
    the psum, so the wire still carries only the block.

    ``pack`` (io/binning.BlockedPackSpec, masked closures only): the
    block-local mixed-bin layout — the owned slice's histogram rows are
    then in PACKED (bin-width-class) order, and the split finder gathers
    them back to canonical block order before the search, so split
    results, argmax tie-breaks and the packed-SplitInfo allreduce are
    bit-identical to the uniform layout.  The psum seams ride unchanged:
    the permutation never crosses the block boundary, so the reduced
    payload is the same feature set either way.  The compact closures
    (``slice_hist=True``) pass ``pack=None`` — their histograms assemble
    canonically inside the histogram op (global blocked ranges).

    Returns a traced-context fn (fmask, nbins) ->
    (own_s, fmask_own, nbins_own, SeamSchedule)."""
    Fb, Fpad, block_ids = _owned_block(F, feature_shards, FEATURE_AXIS)
    _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                           phase=phase)

    def seams(fmask, nbins):
        idx, ownok, own_s = block_ids()
        rank = jax.lax.axis_index(FEATURE_AXIS)

        def own_block(x):
            if Fpad == F:
                return jax.lax.dynamic_slice_in_dim(x, rank * Fb, Fb,
                                                    axis=0)
            widths = [(0, 0)] * x.ndim
            widths[0] = (0, Fpad - F)
            return jax.lax.dynamic_slice_in_dim(jnp.pad(x, widths),
                                                rank * Fb, Fb, axis=0)

        if slice_hist:
            hist_reduce = _c(site_prefix + "/own_block_allreduce",
                             lambda h: jax.lax.psum(own_block(h),
                                                    DATA_AXIS),
                             kind="psum", loop=loop)
            # int accumulators ([F, B, lanes], features on axis 0) slice
            # identically, keeping the int-domain exactness chain
            int_hist_reduce = _c(site_prefix + "/own_block_int_allreduce",
                                 lambda a: jax.lax.psum(own_block(a),
                                                        DATA_AXIS),
                                 kind="psum", loop=loop)
            root_hist_reduce = _c(site_prefix + "/root_hist",
                                  lambda h: jax.lax.psum(h, DATA_AXIS),
                                  kind="psum", loop=root_loop)
            own_slice = own_block
        else:
            hist_reduce = _c(site_prefix + "/hist_allreduce",
                             lambda h: jax.lax.psum(h, DATA_AXIS),
                             kind="psum", loop=loop)
            # the quantized path's INT accumulators ride build_histogram's
            # internal default data-axis psum (axis_name=DATA_AXIS); the
            # leaf-wise policies' ONE root exchange files at its own
            # root_loop site (wire-metrics accuracy, values identical)
            int_hist_reduce = None
            root_hist_reduce = _c(site_prefix + "/root_hist",
                                  lambda h: jax.lax.psum(h, DATA_AXIS),
                                  kind="psum", loop=root_loop)
            own_slice = None
        schedule = SeamSchedule(
            hist_axis=DATA_AXIS,
            hist_reduce=hist_reduce, int_hist_reduce=int_hist_reduce,
            stat_reduce=_c(site_prefix + "/root_stats",
                           lambda st: jax.lax.psum(st, DATA_AXIS),
                           kind="psum", loop=root_loop),
            root_hist_reduce=root_hist_reduce, own_slice=own_slice,
            hist_feat_gather=_block_feat_gather(pack, own_s, rank, Fb),
            split_finder=ownership_finder(
                own_s, FEATURE_AXIS,
                site=site_prefix + "/splitinfo_allreduce", loop=loop,
                phase=phase))
        return own_s, fmask[own_s] & ownok, jnp.take(nbins, own_s), schedule
    return seams


def _block_feat_gather(pack, own_s, rank, Fb: int):
    """The grower's ``hist_feat_gather`` seam for a block-locally PACKED
    owned slice (io/binning.BlockedPackSpec): TRACED [Fb] indices mapping
    canonical block position -> within-block storage position, handed to
    every histogram build (ops/histogram feat_gather) so the kernels
    restore canonical order IN THE INT DOMAIN (before dequantize/psum)
    — the hist cache, int8-derived root stats, sibling subtraction and
    split search are then all canonical, and the f32 graph downstream is
    shape-identical to the uniform layout's, so packed-vs-uniform stays
    bit-identical including argmax tie-breaks and XLA FMA-contraction
    choices.  Derived from the shard's rank against the global
    canonical->storage map, so the SPMD program is shard-uniform even
    though each block's inner permutation differs.  None when ``pack``
    is None (uniform layout).  Padding lanes clamp; they are masked out
    of the search by fmask_own & ownok either way."""
    if pack is None:
        return None
    c2p = jnp.asarray(pack.c2p, jnp.int32)
    return jnp.clip(jnp.take(c2p, own_s) - rank * Fb, 0, Fb - 1)


def voting_seams(F: int, feature_shards: int, top_k: int, int8: bool,
                 site_prefix: str, loop: int = 1, phase: str = "grow",
                 root_loop: int = 1, lanes: int = 1, pack=None):
    """Voting-parallel seams (ISSUE 9) — the reference NAMES this learner
    but Fatals on it (src/io/config.cpp:311-313); this realizes the
    PV-tree design on the 2-D mesh's data axis:

    1. every data shard histograms ALL its owned-block features over its
       LOCAL rows (caches stay local; parent-minus-smaller subtraction
       is exact locally),
    2. each shard proposes its top-k features by local split gain — the
       vote allgather moves k int32s, not histograms,
    3. full histograms are psum'd over the data axis ONLY for the
       <= 2·top_k globally-voted features (votes desc, feature id asc,
       deterministic), so the per-split exchange drops from
       O(F·B / feature_shards) to O(min(2k, F/fs)·B),
    4. the owned-block winner joins the packed SplitInfo allreduce over
       the feature axis, exactly like the hybrid schedule.

    Voting is exact whenever the voted set covers the true best feature
    — guaranteed when 2·top_k >= the owned block width (the voted set is
    then the whole block and the schedule degenerates to hybrid's),
    PV-tree's accuracy argument otherwise.

    int8: the quantized path's int accumulators ride build_histogram's
    internal data-axis psum UNREDUCED exactness chain (local caches
    would break the int-domain bit-identity guarantee), so int8 voting
    restricts only the SEARCH, not the exchange — the wire saving
    applies to the f32/bfloat16 paths; documented in PROFILE.md.

    Wire accounting: the voted exchange rides the FINDER, which the
    leaf-wise policies run once per CHILD (no subtraction trick is
    possible across distinct voted sets), so the per-split leaf-wise
    exchange is 2·min(2k, Fb)·B·3·4 bytes and voting beats hybrid's
    single Fb-block psum only when 4k < F/fs.  ``loop``/``root_loop``
    are the executed-calls estimates for the body and root finder
    variants; ``lanes`` scales recorded bytes when the caller batches
    the finder with jax.vmap (the compact pair call: the collective
    moves every lane but the tracer only sees one lane's shape —
    depthwise's per-level slot-vmapped finder has no static lane count,
    so its voting est undercounts; the gated smoke rides leaf-wise
    where est == executed)."""
    Fb, Fpad, block_ids = _owned_block(F, feature_shards, FEATURE_AXIS)
    k = min(top_k, Fb)
    V = min(2 * top_k, Fb)
    _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                           phase=phase)

    def seams(fmask, nbins):
        idx, ownok, own_s = block_ids()
        # block-local mixed-bin layout (the masked closures pre-slice
        # ``bins`` in packed storage order): the histogram kernels gather
        # the accumulators back to canonical block order in the int
        # domain (_block_feat_gather), so the vote scoring, tie-breaks
        # and exchanged payloads below match the uniform layout bit for
        # bit
        feat_gather = _block_feat_gather(
            pack, own_s,
            jax.lax.axis_index(FEATURE_AXIS) if pack is not None else 0,
            Fb)

        def make_finder(tag, loop_est, lane_scale):
          # tag distinguishes the root sites: a telemetry site carries ONE
          # executed-calls loop estimate, so the root finder (1 execution)
          # and the per-split body finder cannot share site names
          def finder(hist, sg, sh, cnt, nb, fm, mind, minh):
            # hist: [Fb, B, 3] when the caller pre-sliced ``bins`` to the
            # owned block (the masked policies — histogram compute and
            # cache never touch un-owned features), else [F, B, 3] local
            # full-F (the compact pane keeps all features for the
            # partition; int8: already int-psum'd global) — static
            # shapes, so the slice resolves at trace time
            if hist.shape[0] == Fb:
                hist_own, nb_own, fm_own = hist, nb, fm
            else:
                hist_own = jnp.take(hist, own_s, axis=0)
                nb_own = jnp.take(nb, own_s)
                fm_own = fm[own_s] & ownok
            # 1. local per-feature best gains over the owned block.  The
            # leaf totals for the vote scoring come from the HISTOGRAM
            # ITSELF (any one feature's bins sum to the leaf's rows), not
            # the carried sg/sh/cnt: in f32 the histogram is shard-LOCAL
            # while sg/sh/cnt are global, and mixing them skews every
            # right-child stat by ~the other shards' mass — worse, a leaf
            # whose LOCAL row count falls below min_data_in_leaf would
            # score every feature -inf and the vote would silently
            # degenerate to the lowest feature ids.  PV-tree votes on
            # local evidence: local left/right sums against local totals.
            # (int8: hist is already global, so the bin sums are the
            # global totals and the vote ranking matches a global scorer.)
            tot = jnp.sum(hist_own[0], axis=0)           # [3] g, h, count
            scores = per_feature_best_scores(hist_own, tot[0], tot[1],
                                             tot[2], nb_own, fm_own,
                                             mind, minh)
            # 2. top-k vote (argsort is stable: gain ties resolve to the
            # smaller feature id, matching SplitInfo::MaxReducer)
            order = jnp.argsort(-scores)
            top_local = order[:k]
            top_ids = jnp.where(jnp.isfinite(scores[top_local]),
                                idx[top_local], jnp.int32(Fpad))
            telemetry.record_collective(
                site_prefix + "/%svotes_allgather" % tag, "all_gather",
                DATA_AXIS, telemetry._tree_nbytes(top_ids) * lane_scale,
                loop=loop_est, phase=phase)
            votes = jax.lax.all_gather(top_ids, DATA_AXIS)     # [ds, k]
            # 3. voted set: top-V features by vote count (stable argsort
            # → ties by smaller id), exchanged in ascending feature order
            counts = jnp.sum(votes.reshape(-1)[None, :] == idx[:, None],
                             axis=1)
            voted = jnp.sort(jnp.argsort(-counts)[:V])
            vh = jnp.take(hist_own, voted, axis=0)             # [V, B, 3]
            if not int8:
                telemetry.record_collective(
                    site_prefix + "/%svoted_hist_allreduce" % tag, "psum",
                    DATA_AXIS, telemetry._tree_nbytes(vh) * lane_scale,
                    loop=loop_est, phase=phase)
                vh = jax.lax.psum(vh, DATA_AXIS)
            # 4. owned-block search over the voted set only, then the
            # packed SplitInfo allreduce across feature blocks
            local = find_best_split(vh, sg, sh, cnt,
                                    jnp.take(nb_own, voted),
                                    fm_own[voted], mind, minh)
            gid = jnp.take(own_s, voted)[local.feature]
            local = local._replace(feature=gid.astype(jnp.int32))
            return allreduce_best_split(
                local, FEATURE_AXIS,
                site=site_prefix + "/%ssplitinfo_allreduce" % tag,
                loop=loop_est, phase=phase)
          return finder

        return SeamSchedule(
            hist_axis=DATA_AXIS,
            stat_reduce=_c(site_prefix + "/root_stats",
                           lambda st: jax.lax.psum(st, DATA_AXIS),
                           kind="psum", loop=root_loop),
            hist_feat_gather=feat_gather,
            split_finder=make_finder("", loop, lanes),
            # the ONE root search files its exchange on root_-tagged
            # sites at root_loop (the body finder traces inside the
            # split loop and carries its per-split estimate)
            root_split_finder=make_finder("root_", root_loop, 1),
            # f32/bf16 caches stay local (the voted exchange lives in the
            # finder); int8's internal int-psum makes them global already
            hist_local=not int8)
    return seams


def _tree_out_specs(data_axis=None):
    """TreeArrays out_specs: everything replicated except the row-sharded
    leaf-id vector."""
    return TreeArrays(
        num_leaves=P(), split_feature=P(), threshold_bin=P(), split_gain=P(),
        left_child=P(), right_child=P(), leaf_parent=P(), leaf_value=P(),
        leaf_count=P(), leaf_ids=P(data_axis))


def create_parallel_learner(config) -> Callable:
    """TreeLearner::CreateTreeLearner (tree_learner.cpp:8-17) for the
    parallel variants; returns a callable with the GBDT learner contract."""
    kind = config.boosting_config.tree_learner
    if kind == "data":
        return DataParallelLearner(config)
    if kind == "feature":
        return FeatureParallelLearner(config)
    if kind == "hybrid":
        return HybridLearner(config)
    if kind == "voting":
        return VotingLearner(config)
    log.fatal("Tree learner type error")


class _ParallelLearnerBase:
    def __init__(self, config):
        self.config = config
        self.tree_config = config.boosting_config.tree_config
        self._jitted = None

    def _grow_kwargs(self, gbdt):
        return dict(
            num_leaves=_effective_num_leaves(self.tree_config),
            num_bins_max=gbdt.num_bins_max,
            min_data_in_leaf=self.tree_config.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.tree_config.min_sum_hessian_in_leaf,
            max_depth=self.tree_config.max_depth,
            # mixed-bin layout spec (None for the feature-parallel
            # learner — gbdt.init resolves packing off there).  The
            # per-class histograms reassemble into canonical feature
            # order BEFORE any reduction, so the ownership psum_scatter
            # and owned-slice seams below ride unchanged.
            packing=getattr(gbdt, "_pack_spec", None),
            **_tuning_kwargs(self.tree_config.hist_chunk,
                             self.tree_config.hist_dtype,
                             self.tree_config.quant_rounding))

    @property
    def _depthwise(self) -> bool:
        return self.tree_config.grow_policy == "depthwise"


# Compiled data-parallel k-iteration chunk programs, shared process-wide
# (keyed on static config only, like models/gbdt._CHUNK_PROGRAMS).
_DP_CHUNK_PROGRAMS: dict = {}


class DataParallelLearner(_ParallelLearnerBase):
    """Rows sharded; histograms psum'd (data_parallel_tree_learner.cpp).

    Two histogram-reduction schedules (tree_config.dp_schedule):

    - ``psum`` (default): full-histogram allreduce + replicated split
      search — the all-to-all equivalent of the reference's reduction,
      simplest and proven.
    - ``reduce_scatter``: the reference's bandwidth-optimal ownership
      schedule (data_parallel_tree_learner.cpp:135-235) as XLA
      collectives — psum_scatter the level histograms by contiguous
      feature block, search only owned features, allreduce the packed
      SplitInfo (SplitInfo::MaxReducer semantics).  Halves the collective
      bytes per level and divides split-search compute by the shard
      count; trees are identical (bit-identical under int8)."""

    def _schedule(self) -> str:
        """Resolve dp_schedule: 'auto' (the config default) follows the
        reference — its N-machine data-parallel mode IS the ReduceScatter
        ownership schedule (data_parallel_tree_learner.cpp:135-235) — so
        true multi-process runs default to reduce_scatter, while
        single-process meshes keep psum (simplest, measured equivalent at
        small shard counts, PROFILE.md)."""
        s = getattr(self.tree_config, "dp_schedule", "psum")
        if s == "auto":
            return ("reduce_scatter" if jax.process_count() > 1
                    else "psum")
        return s

    def _mesh(self):
        """The learner's device mesh — the 1-D ``(data,)`` mesh here;
        the 2-D hybrid subclass overrides with ``(data, feature)``."""
        return get_mesh(self.config.network_config.num_machines, DATA_AXIS,
                        getattr(self.config, 'device_type', ''))

    def _key_extra(self) -> tuple:
        """Extra chunk/jit cache-key components (the hybrid subclass adds
        its mesh factoring and voting knobs)."""
        return ()

    def _scatter_grow_fn_leafwise(self, kwargs, F: int, num_shards: int):
        """Per-shard leaf-wise grow closure for the reduce_scatter
        ownership schedule: every histogram (smaller child per split) is
        psum_scatter'd by contiguous feature block — int domain for the
        quantized path — the hist cache holds only the owned block, the
        split search runs on owned features, and the packed SplitInfo
        allreduce picks the global winner.  This is the reference's
        N-machine mode in its native growth order
        (data_parallel_tree_learner.cpp:135-235 driving
        serial_tree_learner.cpp:119-153)."""
        # per-split seams run in the grower's fori_loop: traced once,
        # executed once per split (wire-metrics loop estimate)
        seams = dp_ownership_seams(F, num_shards,
                                   site_prefix="dp_rs/leafwise",
                                   loop=kwargs["num_leaves"] - 1)

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            fmask_own, nbins_own, schedule = seams(fmask, nbins)
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                policy="leafwise", schedule=schedule,
                partition_bins=bins_s, **kwargs)
        return shard_grow

    def _scatter_grow_fn(self, kwargs, F: int, num_shards: int,
                         phase: str = "train_chunk", loop_scale: int = 1):
        """Per-shard DEPTHWISE grow closure for the reduce_scatter
        schedule.  ``loop_scale`` multiplies the wire-metrics
        executed-calls estimate (the fused chunk traces once, executes k
        times)."""
        Fb, Fpad, block_ids = _owned_block(F, num_shards, DATA_AXIS)
        _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                               phase=phase, loop=loop_scale)

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            idx, ownok, own_s = block_ids()
            rank = jax.lax.axis_index(DATA_AXIS)
            fmask_own = fmask[own_s] & ownok
            nbins_own = jnp.take(nbins, own_s)

            def pad_f(x, axis):
                if Fpad == F:
                    return x
                widths = [(0, 0)] * x.ndim
                widths[axis] = (0, Fpad - F)
                return jnp.pad(x, widths)

            def int_reduce(acc):
                # INT accumulators, feature axis 0 — int-domain scatter
                # keeps the serial == distributed bit-exactness chain
                return jax.lax.psum_scatter(
                    pad_f(acc, 0), DATA_AXIS, scatter_dimension=0,
                    tiled=True)

            def hist_scatter(h):
                # f32 [C, F, B, 3] level histogram, feature axis 1
                return jax.lax.psum_scatter(
                    pad_f(h, 1), DATA_AXIS, scatter_dimension=1, tiled=True)

            def own_slice(h):
                # replicated full root histogram -> this shard's block
                return jax.lax.dynamic_slice_in_dim(
                    pad_f(h, 1), rank * Fb, Fb, axis=1)

            schedule = SeamSchedule(
                hist_axis=DATA_AXIS,
                hist_reduce=_c("dp_rs/depthwise/root_hist",
                               lambda h: jax.lax.psum(h, DATA_AXIS),
                               kind="psum"),
                stat_reduce=_c("dp_rs/depthwise/root_stats",
                               lambda s: jax.lax.psum(s, DATA_AXIS),
                               kind="psum"),
                split_finder=ownership_finder(
                    own_s, DATA_AXIS,
                    site="dp_rs/depthwise/splitinfo_allreduce",
                    loop=loop_scale, phase=phase),
                hist_reduce_level=_c("dp_rs/depthwise/level_hist_scatter",
                                     hist_scatter, kind="psum_scatter"),
                int_reduce_level=_c("dp_rs/depthwise/level_int_scatter",
                                    int_reduce, kind="psum_scatter"),
                own_slice=own_slice)
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                policy="depthwise", schedule=schedule, **kwargs)
        return shard_grow

    def chunk_program(self, gbdt, obj_key, grad_fn, obj_params,
                      has_bag: bool, has_ff: bool,
                      train_metric_fns=(), valid_metric_fns=(),
                      n_valid: int = 0, shard_layout=None,
                      needs_global_score: bool = False,
                      health: bool = False, goss=None):
        """Fused k-iteration training program under shard_map: the whole
        gradients → grow(psum'd histograms) → score-update scan runs sharded
        over the mesh, one dispatch per chunk (the data-parallel analog of
        models/gbdt._get_chunk_program), INCLUDING in-program metric
        evaluation: train metrics see the all_gathered global score (the
        reference evaluates metrics every iteration in parallel mode too,
        gbdt.cpp:225-259 — here AUC's global sort runs on the gathered
        scores inside every shard), and validation sets ride replicated
        (each shard replays trees on the full valid bins; identical values
        on all shards).

        Returns (program, num_shards).  The caller pads rows to a multiple
        of num_shards and passes ``valid_rows`` (False on padding) so padded
        rows never enter histograms, root stats or gathered-score metrics
        (metric fns slice to the true row count).  The program's call/return
        contract matches the serial chunk program:
        (score, bins, num_bins, valid_rows, row_masks, feat_masks,
        obj_params, train_mparams, valid_bins, valid_scores, valid_mparams)
        -> (score, vscores, stacked_trees, mvals)."""
        mesh = self._mesh()
        num_shards = mesh.shape[DATA_AXIS]
        num_class = gbdt.num_class
        lr = float(gbdt.gbdt_config.learning_rate)
        kwargs = self._grow_kwargs(gbdt)
        depthwise = self._depthwise
        n_true = gbdt.num_data
        max_nodes = max(_effective_num_leaves(self.tree_config) - 1, 1)
        # reduce_scatter in the fused depthwise chunk; the leaf-wise
        # per-iteration path has its own scatter closure (__call__)
        use_scatter = self._schedule() == "reduce_scatter" and depthwise
        # the compacted grower covers BOTH schedules (_compact_grow_fn
        # dispatches): no masked-grower fall-through under reduce_scatter
        use_compact = not depthwise and self._leafwise_compact_enabled()
        num_features = gbdt.num_features
        # in-program health vector: local reductions + psum/pmax over the
        # data axis, so every shard carries the identical global vector
        # (lightgbm_tpu/health.py; the [8] extra output rides replicated)
        health_fn = None
        if health:
            from ..health import make_health_fn
            health_fn = make_health_fn(
                self.tree_config.hist_dtype == "int8", DATA_AXIS)
        # the RESOLVED pallas-partition and DMA-overlap bits and the
        # backend/device identity are part of the program key:
        # __graft_entry__ flips LGBM_TPU_NO_PALLAS mid-process (and
        # steers onto virtual CPU meshes), PROFILE.md's A/B flips
        # LGBM_TPU_PARTITION_NO_OVERLAP, and a stale program would keep
        # the old kernel routing either way
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        use_pp = use_compact and pallas_partition_ok()
        key = (obj_key, id(grad_fn), num_shards, num_class, lr, depthwise,
               tuple(sorted(kwargs.items())), has_bag, has_ff, n_true,
               shard_layout, needs_global_score, use_scatter, use_compact,
               goss, self._schedule(), use_pp,
               use_pp and partition_overlap_on(), jax.default_backend(),
               getattr(self.config, 'device_type', ''),
               num_features, bool(health), self._key_extra(),
               tuple(id(f) for f in train_metric_fns),
               tuple(tuple(id(f) for f in fns) for fns in valid_metric_fns))
        prog = _DP_CHUNK_PROGRAMS.get(key)
        if prog is not None:
            return prog, num_shards

        lrf = jnp.float32(lr)
        # wire-metrics loop estimate: the scan body traces ONCE but runs k
        # times per chunk; shard_chunk fills in k (row_masks.shape[0])
        # before anything inside the body is traced
        chunk_k = [1]

        def _gather_compact(vec, site):
            """all_gather row-aligned values over the data axis and
            compact out the per-process padding — the ONE home of the
            padded-global -> true-row rule (the in-program train metrics
            AND the in-chunk GOSS row scores both ride it).
            Single-process runs pad only at the tail (slice to n_true);
            multi-process runs pad each process block, so the static
            shard_layout ((start, len) per process) concatenates the
            true row ranges in process order — matching the order the
            global metric metadata was gathered in (gbdt.init).
            Returns ``(compacted, padded_row_count)``."""
            telemetry.record_collective(
                site, "all_gather", DATA_AXIS,
                telemetry._tree_nbytes(vec), loop=chunk_k[0],
                phase="train_chunk")
            full = jax.lax.all_gather(vec, DATA_AXIS, axis=-1, tiled=True)
            if shard_layout is None:
                return full[..., :n_true], full.shape[-1]
            return jnp.concatenate(
                [jax.lax.slice_in_dim(full, st, st + ln, axis=-1)
                 for st, ln in shard_layout], axis=-1), full.shape[-1]

        def gathered(f):
            # train metrics need the GLOBAL score
            def g(p, s):
                comp, _ = _gather_compact(s, "dp/metric_score_allgather")
                return f(p, comp)
            return g

        train_fns = tuple(gathered(f) for f in train_metric_fns)

        goss_fn = None
        if goss is not None:
            # in-chunk GOSS on the data-sharded layout (ISSUE 12): the
            # per-row |grad| scores are all_gathered over the data axis,
            # the draw runs on the COMPACTED true-row layout (exactly
            # the serial/per-iteration selection — same key, same row
            # count, bit-identical), and each shard slices its own
            # rows' mask/weights back out.  Selection is a pure function
            # of the globally-identical gradients, so every shard — and
            # every process in a multi-process job — computes the
            # identical selection.
            g_seed, g_top, g_other, g_amp = goss
            from ..ops import sampling as _sampling

            def goss_fn(it, grad, hess):
                absg = _sampling.goss_row_scores(grad)       # [n_local]
                absg_true, n_pad = _gather_compact(
                    absg, "dp/goss_score_allgather")

                def expand(vec_true, fill):
                    # compacted true-row vector -> padded global layout
                    if shard_layout is None:
                        return jnp.pad(vec_true, (0, n_pad - n_true),
                                       constant_values=fill)
                    pm = np.full(n_pad, n_true, np.int32)
                    off = 0
                    for st, ln in shard_layout:
                        pm[st:st + ln] = off + np.arange(ln)
                        off += ln
                    ext = jnp.concatenate(
                        [vec_true,
                         jnp.full((1,), fill, vec_true.dtype)])
                    return jnp.take(ext, jnp.asarray(pm))

                key = jax.random.fold_in(jax.random.PRNGKey(g_seed), it)
                mask_t, w_t = _sampling.goss_mask_weights(
                    key, absg_true, g_top, g_other, g_amp)
                mask_pad = expand(mask_t, False)
                w_pad = expand(w_t, 1.0)
                rows = grad.shape[-1]
                i = jax.lax.axis_index(DATA_AXIS)
                msl = jax.lax.dynamic_slice_in_dim(mask_pad, i * rows,
                                                   rows)
                wsl = jax.lax.dynamic_slice_in_dim(w_pad, i * rows, rows)
                return grad * wsl, hess * wsl, msl

        if needs_global_score:
            # global-score objectives (lambdarank): pairwise lambdas need
            # every row of every query, and only PROCESS shards are
            # query-atomic — device-level row blocks cut queries.  Gather
            # the score shards (same collective the in-program train
            # metrics ride), compute the full lambda vector replicated,
            # and slice this shard's rows back out.  The reference's
            # per-machine formulation (rank_objective.hpp:68-192) is the
            # compute-distributed special case; this stays exact under any
            # row blocking.
            base_grad_fn = grad_fn

            def grad_fn(params, score):
                telemetry.record_collective(
                    "dp/grad_score_allgather", "all_gather", DATA_AXIS,
                    telemetry._tree_nbytes(score), loop=chunk_k[0],
                    phase="train_chunk")
                full = jax.lax.all_gather(score, DATA_AXIS, axis=-1,
                                          tiled=True)
                g, h = base_grad_fn(params, full)
                rows = score.shape[-1]
                i = jax.lax.axis_index(DATA_AXIS)
                sl = functools.partial(
                    jax.lax.dynamic_slice_in_dim,
                    start_index=i * rows, slice_size=rows, axis=-1)
                return sl(g), sl(h)

        def shard_chunk(score, bins, num_bins, valid_rows, row_masks,
                        feat_masks, obj_params, train_mparams, valid_bins,
                        valid_scores, valid_mparams, goss_iters=None):
            from ..models.gbdt import make_chunk_body
            chunk_k[0] = int(row_masks.shape[0])
            grow_fn = self._chunk_grow_fn(kwargs, num_features, num_shards,
                                          depthwise, use_compact,
                                          use_scatter, chunk_k[0])
            body = make_chunk_body(
                grad_fn=grad_fn, obj_params=obj_params, num_class=num_class,
                lrf=lrf,
                grow_fn=grow_fn,
                has_bag=has_bag, has_ff=has_ff, bins=bins,
                num_bins=num_bins, base_mask=valid_rows,
                max_nodes=max_nodes, valid_bins=valid_bins,
                valid_mparams=valid_mparams,
                train_metric_fns=train_fns, train_mparams=train_mparams,
                valid_metric_fns=valid_metric_fns, health_fn=health_fn,
                goss_fn=goss_fn)
            xs = ((row_masks, feat_masks) if goss_fn is None
                  else (row_masks, feat_masks, goss_iters))
            (score, vscores), (stacked, mvals, hvals) = jax.lax.scan(
                body, (score, tuple(valid_scores)), xs)
            return score, vscores, stacked, mvals, hvals

        def param_spec(leaf):
            # row-aligned arrays ride the data axis; scalars are replicated;
            # global-score objectives' per-query tables ride replicated
            if not needs_global_score and getattr(leaf, "ndim", 0) >= 1:
                return P(DATA_AXIS, *([None] * (leaf.ndim - 1)))
            return P()

        pspecs = jax.tree.map(param_spec, obj_params)
        in_specs = (P(None, DATA_AXIS), P(None, DATA_AXIS), P(),
                    P(DATA_AXIS),
                    P(None, None, DATA_AXIS) if has_bag else P(),
                    P(), pspecs,
                    # metric params / valid sets are replicated (a single
                    # P() broadcasts over the whole subtree)
                    P(), P(), P(), P())
        if goss is not None:
            in_specs = in_specs + (P(),)     # goss_iters, replicated
        from .. import costmodel
        prog = costmodel.instrument("chunk/dp", jax.jit(shard_map(
            shard_chunk, mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(None, DATA_AXIS),
                       tuple(P() for _ in range(n_valid)),
                       _tree_out_specs(None), P(), P()))),
            phase="train_chunk")
        _DP_CHUNK_PROGRAMS[key] = prog
        return prog, num_shards

    def _leafwise_compact_enabled(self) -> bool:
        from ..models.gbdt import leafwise_compact_on
        return leafwise_compact_on(self.tree_config)

    def _compact_grow_fn(self, kwargs, F: int, num_shards: int,
                         phase: str = "grow", loop_scale: int = 1):
        """Per-shard COMPACTED leaf-wise closure for the ACTIVE schedule:
        each shard keeps its local rows physically partitioned
        (grower_leafcompact.py) and the per-split smaller-child
        histograms are reduced globally — distributed parity-mode
        training at the geometric-series cost instead of full sweeps.
        The histogram tier is pmax-synced inside the grower so the
        collectives stay uniform across shards.

        Under ``psum`` the whole histogram is allreduced; under
        ``reduce_scatter`` the reference's ownership schedule
        (data_parallel_tree_learner.cpp:135-235) composes onto the same
        grower: feature-block psum_scatter (int domain for the quantized
        path), owned-slice hist cache and split search, packed SplitInfo
        allreduce — the multi-process default (dp_schedule=auto) no
        longer falls back to the masked N·(L-1)-sweep grower."""
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        use_pallas = pallas_partition_ok()
        overlap = partition_overlap_on()
        # per-split seams run once per split; x the fused-chunk length on
        # the chunk path (wire-metrics executed-calls estimate)
        split_loop = (kwargs["num_leaves"] - 1) * loop_scale

        if self._schedule() == "reduce_scatter":
            seams = dp_ownership_seams(F, num_shards,
                                       site_prefix="dp_rs/leafcompact",
                                       loop=split_loop, phase=phase,
                                       root_loop=loop_scale)

            def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
                fmask_own, nbins_own, schedule = seams(fmask, nbins)
                return grow_tree_unified(
                    bins_s, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                    policy="leafcompact", schedule=schedule,
                    use_pallas_partition=use_pallas,
                    partition_overlap=overlap, **kwargs)
            return shard_grow

        _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                               phase=phase)
        schedule = SeamSchedule(
            hist_axis=DATA_AXIS,
            hist_reduce=_c("dp_psum/leafcompact/hist_allreduce",
                           lambda h: jax.lax.psum(h, DATA_AXIS),
                           kind="psum", loop=split_loop),
            stat_reduce=_c("dp_psum/leafcompact/root_stats",
                           lambda s: jax.lax.psum(s, DATA_AXIS),
                           kind="psum", loop=loop_scale))

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask, nbins,
                policy="leafcompact", schedule=schedule,
                use_pallas_partition=use_pallas,
                partition_overlap=overlap, **kwargs)
        return shard_grow

    def _psum_grow_fn(self, kwargs, F: int, policy: str,
                      phase: str = "grow", loop_scale: int = 1):
        """Per-shard grow closure for the plain-psum schedule, ANY growth
        policy: full-histogram allreduce over the data axis + replicated
        split search.  The one home of the psum seam set — the hybrid
        subclass overrides this with the 2-D owned-block schedule and
        the voting subclass with the top-k voted exchange, so every
        (policy x learner) cell flows through a single dispatch point
        instead of per-policy copies (ISSUE 9)."""
        _c = functools.partial(telemetry.collective_span, axis=DATA_AXIS,
                               phase=phase)
        # depthwise traces its level reduce per (unrolled) level; the
        # leaf-wise/compact fori_loop traces hist_reduce ONCE but runs it
        # once per split (wire-metrics executed-calls estimate)
        hist_loop = loop_scale * (1 if policy == "depthwise"
                                  else kwargs["num_leaves"] - 1)
        schedule = SeamSchedule(
            hist_axis=DATA_AXIS,
            hist_reduce=_c("dp_psum/%s/hist_allreduce" % policy,
                           lambda h: jax.lax.psum(h, DATA_AXIS),
                           kind="psum", loop=hist_loop),
            # the leaf-wise policies' ONE root histogram exchange files
            # at its own loop=loop_scale site (riding hist_reduce would
            # inflate the wire series by the per-split loop factor)
            root_hist_reduce=None if policy == "depthwise" else _c(
                "dp_psum/%s/root_hist" % policy,
                lambda h: jax.lax.psum(h, DATA_AXIS),
                kind="psum", loop=loop_scale),
            stat_reduce=_c("dp_psum/%s/root_stats" % policy,
                           lambda s: jax.lax.psum(s, DATA_AXIS),
                           kind="psum", loop=loop_scale))

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask, nbins,
                policy=policy, schedule=schedule, **kwargs)
        return shard_grow

    def _grow_fn(self, kwargs, F: int, num_shards: int):
        """Per-shard leaf-wise grow closure for the active schedule."""
        if self._schedule() == "reduce_scatter":
            return self._scatter_grow_fn_leafwise(kwargs, F, num_shards)
        return self._psum_grow_fn(kwargs, F, "leafwise")

    def _chunk_grow_fn(self, kwargs, F: int, num_shards: int,
                       depthwise: bool, use_compact: bool,
                       use_scatter: bool, k: int):
        """Policy x schedule dispatch for the fused chunk body — the one
        home of what the chunk builder used to re-derive inline; ``k``
        scales the wire-metrics executed-calls estimates (the scan body
        traces once, executes k times per chunk)."""
        if use_compact:
            # same grower (and the same schedule dispatch) on the chunk
            # path as on __call__'s per-iteration path
            return self._compact_grow_fn(kwargs, F, num_shards,
                                         phase="train_chunk", loop_scale=k)
        if use_scatter:
            return self._scatter_grow_fn(kwargs, F, num_shards,
                                         phase="train_chunk", loop_scale=k)
        return self._psum_grow_fn(kwargs, F,
                                  "depthwise" if depthwise else "leafwise",
                                  phase="train_chunk", loop_scale=k)

    # telemetry route tag ("dp"; the 2-D subclasses say "hybrid"/"voting")
    route_name = "dp"

    def __call__(self, gbdt, bins, grad, hess, row_mask, feature_mask):
        mesh = self._mesh()
        num_shards = mesh.shape[DATA_AXIS]
        F, N = bins.shape
        pad = (-N) % num_shards
        if pad:
            bins = jnp.pad(bins, ((0, 0), (0, pad)))
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            row_mask = jnp.pad(row_mask, (0, pad))

        use_compact = (not self._depthwise
                       and self._leafwise_compact_enabled())
        rt = self.route_name
        telemetry.count_route(
            "learner_" + rt, "learner/%s_" % rt + (
                "depthwise" if self._depthwise
                else ("compact_rs" if self._schedule() == "reduce_scatter"
                      else "compact") if use_compact
                else "leafwise"))

        # the per-iteration program must track the resolved
        # pallas-partition/DMA-overlap bits and backend/device identity,
        # exactly like the chunk-program caches: __graft_entry__ flips
        # LGBM_TPU_NO_PALLAS mid-process (PROFILE.md's A/B flips
        # LGBM_TPU_PARTITION_NO_OVERLAP) and a stale program would keep
        # the old kernel routing
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        use_pp = use_compact and pallas_partition_ok()
        # the resolved mixed-bin layout spec is a cache-key bit exactly
        # like the kernel-routing flags (graftlint R2): the traced
        # program bakes the per-class pass structure AND the canonical
        # reorder gathers in, so a booster with a different ``_pack_spec``
        # must not reuse this learner's jitted program
        jit_key = (use_pp, use_pp and partition_overlap_on(),
                   jax.default_backend(),
                   getattr(self.config, 'device_type', ''),
                   getattr(gbdt, "_pack_spec", None),
                   self._key_extra())
        if self._jitted is None or getattr(self, "_jit_key", None) != jit_key:
            self._jit_key = jit_key
            kwargs = self._grow_kwargs(gbdt)
            if self._depthwise:
                shard_fn = self._psum_grow_fn(kwargs, F, "depthwise")
            elif use_compact:
                shard_fn = self._compact_grow_fn(kwargs, F, num_shards)
            else:
                shard_fn = self._grow_fn(kwargs, F, num_shards)

            from .. import costmodel
            self._jitted = costmodel.instrument("grow/dp", jax.jit(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                          P(DATA_AXIS), P(), P()),
                out_specs=_tree_out_specs(DATA_AXIS))), phase="grow")

        tree = self._jitted(bins, grad, hess, row_mask, feature_mask,
                            gbdt.num_bins_device)
        if pad:
            tree = tree._replace(leaf_ids=tree.leaf_ids[:N])
        return tree


class HybridLearner(DataParallelLearner):
    """Hybrid 2-D ``(data, feature)`` learner (ISSUE 9): rows sharded
    over the ``data`` mesh axis, contiguous feature-block ownership over
    the ``feature`` axis — ``num_machines = data_shards x feature_shards``
    (parallel/mesh.factor_machines; ``feature_shards=0`` auto-factors).

    Histograms build local-rows x owned-features; the histogram
    reduction is a data-axis psum RESTRICTED to the owned block (int
    domain on the quantized path), so per-shard wire bytes drop from
    O(F·B) per split to O(F·B / feature_shards); the split search runs
    on owned features only and the packed SplitInfo argmax-allreduce
    rides the FEATURE axis (hybrid_ownership_seams).  Degenerates to
    pure data parallelism at feature_shards=1.  All per-iteration and
    fused-chunk contracts are inherited from DataParallelLearner — rows
    pad to the DATA-axis size, bins ride replicated over the feature
    axis — so every growth policy x chunk path works unchanged."""

    route_name = "hybrid"
    # mixed-bin packing composes with feature-block ownership via the
    # BLOCK-LOCAL layout (ISSUE 12, io/binning.BlockedPackSpec): the
    # bin-width-class permutation never crosses an ownership block
    # boundary, so the owned-block psum and packed-SplitInfo allreduce
    # ride unchanged.  gbdt.init plans with ``pack_layout(F)``.
    feature_block_packing = True
    voting = False

    def pack_layout(self, num_features: int):
        """``(block, feature_shards)`` the block-local mixed-bin plan
        must respect — ``block`` == _owned_block's Fb for this mesh; the
        shard count lets the plan refuse meshes where a shard owns only
        ownership padding."""
        fs = self._feature_shards()
        return -(-num_features // fs), fs

    @staticmethod
    def _split_pack(kwargs):
        """(grow-call kwargs, pack) for a masked shard closure: under the
        block-local layout the owned slice's histogram passes use the
        shard-uniform ``block_view`` while split application translates
        through the GLOBAL canonical->storage map (partition_packing)."""
        pk = kwargs.get("packing")
        if pk is None or not hasattr(pk, "block_view"):
            return kwargs, None
        kw = dict(kwargs)
        kw["packing"] = pk.block_view
        kw["partition_packing"] = pk
        return kw, pk

    def _mesh(self):
        return get_mesh2d(self.config.network_config.num_machines,
                          getattr(self.tree_config, "feature_shards", 0),
                          getattr(self.config, 'device_type', ''),
                          voting=self.voting)

    def _feature_shards(self) -> int:
        return int(self._mesh().shape[FEATURE_AXIS])

    def _schedule(self) -> str:
        # dp_schedule is a 1-D knob; the 2-D ownership schedule REPLACES
        # the psum/reduce_scatter split (resolving "psum" here keeps the
        # base-class dispatch off the 1-D scatter closures)
        return "psum"

    def _key_extra(self) -> tuple:
        m = self._mesh()
        return (self.route_name, int(m.shape[DATA_AXIS]),
                int(m.shape[FEATURE_AXIS]),
                int(getattr(self.tree_config, "top_k", 0))
                if self.voting else 0)

    def _psum_grow_fn(self, kwargs, F: int, policy: str,
                      phase: str = "grow", loop_scale: int = 1):
        """Masked-policy closure on the 2-D mesh: pre-slice ``bins`` to
        the owned feature block (the histogram pass never touches
        un-owned features — the hybrid compute saving) and apply splits
        on the full-F local rows via ``partition_bins``."""
        fs = self._feature_shards()
        loop = loop_scale * (1 if policy == "depthwise"
                             else kwargs["num_leaves"] - 1)
        kw, pack = self._split_pack(kwargs)
        seams = hybrid_ownership_seams(
            F, fs, site_prefix="hybrid/%s" % policy, loop=loop,
            phase=phase, root_loop=loop_scale, pack=pack)

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            own_s, fmask_own, nbins_own, schedule = seams(fmask, nbins)
            bins_own = jnp.take(bins_s, own_s, axis=0)
            return grow_tree_unified(
                bins_own, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                policy=policy, schedule=schedule, partition_bins=bins_s,
                **kw)
        return shard_grow

    def _compact_grow_fn(self, kwargs, F: int, num_shards: int,
                         phase: str = "grow", loop_scale: int = 1):
        """Compacted leaf-wise on the 2-D mesh: the plane pane packs ALL
        features (the partition needs them), so the seam slices the
        owned block out BEFORE the data-axis psum — the wire still
        carries only the O(F·B / feature_shards) block."""
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        fs = self._feature_shards()
        split_loop = (kwargs["num_leaves"] - 1) * loop_scale
        seams = hybrid_ownership_seams(
            F, fs, site_prefix="hybrid/leafcompact", loop=split_loop,
            phase=phase, root_loop=loop_scale, slice_hist=True)
        use_pallas = pallas_partition_ok()
        overlap = partition_overlap_on()

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            _, fmask_own, nbins_own, schedule = seams(fmask, nbins)
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                policy="leafcompact", schedule=schedule,
                use_pallas_partition=use_pallas,
                partition_overlap=overlap, **kwargs)
        return shard_grow


class VotingLearner(HybridLearner):
    """Voting-parallel learner (ISSUE 9) — realizes the reference's
    named-but-absent ``tree_learner=voting`` (src/io/config.cpp:311-313
    Fatals on it; the PV-tree design): each data shard proposes its
    ``top_k`` features by LOCAL split gain, and full histograms are
    exchanged only for the <= 2·top_k globally-voted features per owned
    block — per-split wire bytes O(min(2·top_k, F/fs)·B) instead of the
    hybrid O(F·B / fs) (voting_seams).

    Pure data-parallel by default (factor_machines(voting=True) ->
    feature_shards=1); 2-D feature sharding composes via the
    feature_shards knob.  Voting is EXACT whenever the voted set covers
    the true best feature — guaranteed when 2·top_k >= the owned block
    width (the schedule then degenerates to a full exchange of the
    block); the PV-tree accuracy argument holds otherwise.  int8 keeps
    the int-domain global exchange (the bit-identity chain) and
    restricts only the search — the wire saving applies to f32/bf16."""

    route_name = "voting"
    voting = True
    def _voting_seams(self, kwargs, F: int, site: str, loop: int,
                      phase: str, root_loop: int, lanes: int = 1,
                      pack=None):
        int8 = str(kwargs.get("compute_dtype", "")).startswith("int8")
        return voting_seams(F, self._feature_shards(),
                            int(getattr(self.tree_config, "top_k", 20)),
                            int8, site_prefix=site, loop=loop,
                            phase=phase, root_loop=root_loop,
                            lanes=lanes, pack=pack)

    def _psum_grow_fn(self, kwargs, F: int, policy: str,
                      phase: str = "grow", loop_scale: int = 1):
        loop = loop_scale * (1 if policy == "depthwise"
                             else kwargs["num_leaves"] - 1)
        kw, pack = self._split_pack(kwargs)
        seams = self._voting_seams(kwargs, F, "voting/%s" % policy, loop,
                                   phase, loop_scale, pack=pack)
        _, _, block_ids = _owned_block(F, self._feature_shards(),
                                       FEATURE_AXIS)

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            # pre-slice ``bins`` to the owned feature block (same as the
            # hybrid masked path): histogram compute and the [L, F, B, 3]
            # cache never touch un-owned features — the local caches and
            # the voted exchange inside the split finder both live on the
            # block — while splits apply on the full-F local rows via
            # ``partition_bins``.  Block-local packing rides the same
            # slice: the permutation never crosses the block boundary,
            # and the finder restores canonical order (voting_seams pack)
            schedule = seams(fmask, nbins)
            _, ownok, own_s = block_ids()
            bins_own = jnp.take(bins_s, own_s, axis=0)
            return grow_tree_unified(
                bins_own, grad_s, hess_s, mask_s,
                fmask[own_s] & ownok, jnp.take(nbins, own_s),
                policy=policy, schedule=schedule, partition_bins=bins_s,
                **kw)
        return shard_grow

    def _compact_grow_fn(self, kwargs, F: int, num_shards: int,
                         phase: str = "grow", loop_scale: int = 1):
        from ..ops.compact import pallas_partition_ok, partition_overlap_on
        split_loop = (kwargs["num_leaves"] - 1) * loop_scale
        # the compact split body batches BOTH children into one vmapped
        # finder call (best_of_pair) — the collective moves 2 lanes per
        # execution while the tracer records one lane's shape
        seams = self._voting_seams(kwargs, F, "voting/leafcompact",
                                   split_loop, phase, loop_scale, lanes=2)
        use_pallas = pallas_partition_ok()
        overlap = partition_overlap_on()

        def shard_grow(bins_s, grad_s, hess_s, mask_s, fmask, nbins):
            schedule = seams(fmask, nbins)
            return grow_tree_unified(
                bins_s, grad_s, hess_s, mask_s, fmask, nbins,
                policy="leafcompact", schedule=schedule,
                use_pallas_partition=use_pallas,
                partition_overlap=overlap, **kwargs)
        return shard_grow


def balanced_ownership(num_bins, num_shards: int):
    """Bin-count-balanced feature ownership (the reference re-balances
    ownership by bin count, feature_parallel_tree_learner.cpp:27-44):
    LPT greedy — features sorted by bin count, each assigned to the
    lightest shard with capacity.  Returns (own [S, Fs] i32 feature ids,
    ownmask [S, Fs] bool); padded slots point at feature 0 and are masked.
    """
    num_bins = np.asarray(num_bins)
    F = len(num_bins)
    Fs = -(-F // num_shards)
    order = np.argsort(-num_bins, kind="stable")
    loads = np.zeros(num_shards, np.int64)
    buckets = [[] for _ in range(num_shards)]
    for f in order:
        s = min((s for s in range(num_shards) if len(buckets[s]) < Fs),
                key=lambda s: (loads[s], s))
        buckets[s].append(int(f))
        loads[s] += int(num_bins[f])
    own = np.zeros((num_shards, Fs), np.int32)
    ownmask = np.zeros((num_shards, Fs), bool)
    for s, b in enumerate(buckets):
        own[s, :len(b)] = sorted(b)
        ownmask[s, :len(b)] = True
    return own, ownmask


def static_ownership(num_features: int, num_shards: int):
    """Contiguous-slice ownership (no balancing) — kept for the A/B in
    scripts/fp_ownership_bench.py."""
    Fs = -(-num_features // num_shards)
    own = np.minimum(np.arange(num_shards)[:, None] * Fs + np.arange(Fs),
                     num_features - 1).astype(np.int32)
    ownmask = (np.arange(num_shards)[:, None] * Fs
               + np.arange(Fs)) < num_features
    return own, ownmask


# Compiled feature-parallel k-iteration chunk programs, shared process-wide
_FP_CHUNK_PROGRAMS: dict = {}


class FeatureParallelLearner(_ParallelLearnerBase):
    """Feature ownership sharded, data replicated
    (feature_parallel_tree_learner.cpp).  Ownership is bin-count balanced
    like the reference (lines 27-44; ``balanced_ownership``) — the result
    is invariant to ownership, only load balance differs.  Both the
    per-iteration path and the fused k-iteration chunk program exist; the
    chunk runs the whole gradients → grow(SplitInfo allreduce) →
    score-update scan under shard_map with everything except feature
    ownership replicated."""

    ownership = staticmethod(balanced_ownership)

    def _ownership(self, gbdt, num_shards):
        # constant for the dataset's lifetime: compute/upload once (the
        # per-iteration path calls this every tree)
        cache = getattr(self, "_own_cache", None)
        if cache is not None and cache[0] == num_shards:
            return cache[1], cache[2]
        own, ownmask = type(self).ownership(
            np.asarray(gbdt.num_bins_device), num_shards)
        own, ownmask = jnp.asarray(own), jnp.asarray(ownmask)
        self._own_cache = (num_shards, own, ownmask)
        return own, ownmask

    def _shard_grow_fn(self, policy, kwargs, own, ownmask,
                       phase: str = "grow", loop_scale: int = 1):
        """Per-shard grow closure: slice owned features, allreduce the
        packed SplitInfo, apply splits on the replicated full matrix.
        ``phase``/``loop_scale`` label the SplitInfo-allreduce wire-
        metrics site (per split on the leaf-wise fori_loop, per traced
        level depth-wise; x chunk length on the fused path)."""
        loop = loop_scale * (1 if policy == "depthwise"
                             else kwargs["num_leaves"] - 1)

        def shard_grow(bins_full, grad_s, hess_s, mask_s, fmask, nbins):
            rank = jax.lax.axis_index(FEATURE_AXIS)
            own_s = own[rank]
            ownok = ownmask[rank]
            bins_own = jnp.take(bins_full, own_s, axis=0)
            nbins_own = jnp.take(nbins, own_s)
            fmask_own = fmask[own_s] & ownok

            schedule = SeamSchedule(split_finder=ownership_finder(
                own_s, FEATURE_AXIS,
                site="fp/splitinfo_allreduce", loop=loop, phase=phase))
            return grow_tree_unified(
                bins_own, grad_s, hess_s, mask_s, fmask_own, nbins_own,
                policy=policy, schedule=schedule,
                partition_bins=bins_full, **kwargs)
        return shard_grow

    def chunk_program(self, gbdt, obj_key, grad_fn, obj_params,
                      has_bag: bool, has_ff: bool,
                      train_metric_fns=(), valid_metric_fns=(),
                      n_valid: int = 0, health: bool = False, goss=None):
        """Fused k-iteration feature-parallel chunk (same contract as the
        data-parallel chunk_program / serial chunk program).  Rows are
        replicated, so metric evaluation needs no gathering — and neither
        does the health vector (every shard computes the identical
        full-row reductions)."""
        mesh = get_mesh(self.config.network_config.num_machines,
                        FEATURE_AXIS, getattr(self.config, 'device_type', ''))
        num_shards = mesh.shape[FEATURE_AXIS]
        num_class = gbdt.num_class
        lr = float(gbdt.gbdt_config.learning_rate)
        kwargs = self._grow_kwargs(gbdt)
        policy = "depthwise" if self._depthwise else "leafwise"
        max_nodes = max(_effective_num_leaves(self.tree_config) - 1, 1)
        health_fn = None
        if health:
            from ..health import make_health_fn
            health_fn = make_health_fn(
                self.tree_config.hist_dtype == "int8", None)
        # backend + device_type join the key like the DP/serial chunk
        # caches (graftlint R2): num_shards alone cannot distinguish two
        # same-sized meshes on different backends, and trace-time kernel
        # routing (ops/histogram._pallas_hist_ok, LGBM_TPU_NO_PALLAS
        # flips) bakes the backend into the program
        key = (obj_key, id(grad_fn), num_shards, num_class, lr,
               self._depthwise, tuple(sorted(kwargs.items())), has_bag,
               has_ff, bool(health), goss, jax.default_backend(),
               getattr(self.config, 'device_type', ''),
               tuple(id(f) for f in train_metric_fns),
               tuple(tuple(id(f) for f in fns) for fns in valid_metric_fns))
        prog = _FP_CHUNK_PROGRAMS.get(key)
        if prog is not None:
            return prog, num_shards

        lrf = jnp.float32(lr)
        # rows are replicated under feature ownership, so in-chunk GOSS
        # is the serial full-row draw (every shard computes the identical
        # selection from the identical gradients)
        from ..models.gbdt import make_goss_fn
        goss_fn = make_goss_fn(goss) if goss is not None else None

        def shard_chunk(score, bins, num_bins, own, ownmask, row_masks,
                        feat_masks, obj_params, train_mparams, valid_bins,
                        valid_scores, valid_mparams, goss_iters=None):
            from ..models.gbdt import make_chunk_body
            body = make_chunk_body(
                grad_fn=grad_fn, obj_params=obj_params, num_class=num_class,
                lrf=lrf,
                grow_fn=self._shard_grow_fn(
                    policy, kwargs, own, ownmask, phase="train_chunk",
                    loop_scale=int(row_masks.shape[0])),
                has_bag=has_bag, has_ff=has_ff, bins=bins,
                num_bins=num_bins, max_nodes=max_nodes,
                valid_bins=valid_bins, valid_mparams=valid_mparams,
                train_metric_fns=train_metric_fns,
                train_mparams=train_mparams,
                valid_metric_fns=valid_metric_fns, health_fn=health_fn,
                goss_fn=goss_fn)
            xs = ((row_masks, feat_masks) if goss_fn is None
                  else (row_masks, feat_masks, goss_iters))
            (score, vscores), (stacked, mvals, hvals) = jax.lax.scan(
                body, (score, tuple(valid_scores)), xs)
            return score, vscores, stacked, mvals, hvals

        from .. import costmodel
        prog = costmodel.instrument("chunk/fp", jax.jit(shard_map(
            shard_chunk, mesh=mesh,
            in_specs=(P(),) * (13 if goss is not None else 12),
            out_specs=(P(), tuple(P() for _ in range(n_valid)),
                       _tree_out_specs(None), P(), P()))),
            phase="train_chunk")
        _FP_CHUNK_PROGRAMS[key] = prog
        return prog, num_shards

    def chunk_args(self, gbdt, num_shards):
        """Extra leading inputs the FP chunk program takes after num_bins."""
        return self._ownership(gbdt, num_shards)

    def __call__(self, gbdt, bins, grad, hess, row_mask, feature_mask):
        mesh = get_mesh(self.config.network_config.num_machines, FEATURE_AXIS,
                        getattr(self.config, 'device_type', ''))
        num_shards = mesh.shape[FEATURE_AXIS]
        telemetry.count_route(
            "learner_fp", "learner/fp_" + ("depthwise" if self._depthwise
                                           else "leafwise"))

        if self._jitted is None:
            kwargs = self._grow_kwargs(gbdt)
            policy = "depthwise" if self._depthwise else "leafwise"

            def shard_fn(bins_full, grad_s, hess_s, mask_s, fmask, nbins,
                         own, ownmask):
                return self._shard_grow_fn(policy, kwargs, own, ownmask)(
                    bins_full, grad_s, hess_s, mask_s, fmask, nbins)

            from .. import costmodel
            self._jitted = costmodel.instrument("grow/fp", jax.jit(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(),) * 8,
                out_specs=_tree_out_specs(None))), phase="grow")

        own, ownmask = self._ownership(gbdt, num_shards)
        tree = self._jitted(bins, grad, hess, row_mask, feature_mask,
                            gbdt.num_bins_device, own, ownmask)
        return tree


def distributed_bin_finder(config):
    """Distributed bin finding (dataset.cpp:353-415).

    Each process computes BinMappers for a contiguous feature slice from the
    (identical) global sample and allgathers the results.  Single-process
    runs return None → local bin finding (identical output, the distribution
    is purely a speed optimization since every worker holds the same
    sample)."""
    if jax.process_count() == 1:
        return None

    def finder(sample: np.ndarray, max_bin: int):
        from jax.experimental import multihost_utils
        nproc = jax.process_count()
        rank = jax.process_index()
        F = sample.shape[1]
        step = -(-F // nproc)
        lo, hi = rank * step, min((rank + 1) * step, F)
        blobs = []
        for j in range(lo, hi):
            mapper = BinMapper()
            mapper.find_bin(sample[:, j], max_bin)
            blobs.append(mapper.to_bytes())
        # fixed-size padding like BinMapper::SizeForSpecificBin
        # (dataset.cpp:371-376) so the gather is a dense array
        max_len = 16 + 8 * (max_bin + 1)
        buf = np.zeros((step, max_len), dtype=np.uint8)
        for i, blob in enumerate(blobs):
            buf[i, :len(blob)] = np.frombuffer(blob, dtype=np.uint8)
        gathered = multihost_utils.process_allgather(buf)  # [nproc, step, max_len]
        mappers = []
        for j in range(F):
            r, i = divmod(j, step)
            mappers.append(BinMapper.from_bytes(gathered[r, i].tobytes()))
        return mappers

    return finder
