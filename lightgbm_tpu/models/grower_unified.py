"""The tree grower: ONE schedule-parameterized module, three growth
policies (ISSUE 9).

Until PR 9 the repo carried three grower modules — masked leaf-wise
(``grower.py``), level-batched depth-wise (``grower_depthwise.py``) and
compacted leaf-wise (``grower_leafcompact.py``) — that each re-implemented
the same parallel seams (histogram reduce, int-domain reduce, root-stat
reduce, owned-slice cache, split finder, partition-index translate) and
had to be patched in lockstep by every parallel-layer change (PRs 3/5/6).
This module collapses them: the growth POLICY (``leafwise`` /
``depthwise`` / ``leafcompact``) and a declarative :class:`SeamSchedule`
are parameters, the policy bodies are instances sharing one copy of the
seam plumbing, and every seam is telemetry-wrapped exactly once
(:func:`wrap_schedule`).

Growth policies (semantics unchanged from the pre-collapse modules,
pinned by tests/test_grower_unified.py's recorded digests):

- ``leafwise`` — the reference's strict best-first growth
  (serial_tree_learner.cpp:119-153) as a ``lax.fori_loop`` over
  ``num_leaves - 1`` splits; DataPartition is a masked ``[N]`` leaf-id
  vector, each split builds ONE smaller-child histogram and derives the
  sibling by parent − smaller (serial_tree_learner.cpp:262-283).
- ``depthwise`` — level-batched growth for MXU throughput: all leaves of
  a level histogram in one leaf-batched matmul pass
  (ops/histogram.histogram_leafbatch), levels unrolled in Python.  Split
  ORDER is by level (documented TPU-first trade); the num_leaves budget
  is honored best-first within each level.
- ``leafcompact`` — reference-parity leaf-wise growth at the reference's
  geometric-series cost: rows kept physically partitioned in an
  ``[F+9, P]`` plane pane (ops/compact.py), per-split histograms run
  over the smaller child's bucketed lane range only.

Seam schedule — the parallel learners' customization surface
(parallel/learners.py builds these; ``None`` fields mean serial):

- ``hist_reduce`` / ``int_hist_reduce``: per-histogram cross-shard
  reduction (f32 / int-domain) — psum for data-parallel, a feature-block
  psum_scatter under the reduce_scatter ownership schedule, an
  owned-block-slice + data-axis psum for the 2-D hybrid learner.
- ``stat_reduce`` / ``root_hist_reduce`` / ``own_slice``: root-init
  seams (replicated full-F root, owned-block cache).
- ``split_finder``: replacement for ops/split.find_best_split — the
  ownership learners wrap it with the packed-SplitInfo argmax allreduce
  and must return GLOBAL feature indices; the voting learner's finder
  additionally runs the top-k vote + voted-feature histogram exchange.
- ``hist_reduce_level`` / ``int_reduce_level``: the depthwise policy's
  level-granularity variants.
- ``hist_local``: voting mode — histogram caches stay LOCAL (the voted
  exchange lives inside ``split_finder``), so int8-derived root stats
  must go through ``stat_reduce``.
- partition-index translate: the canonical→storage feature map applied
  when splits are APPLIED (mixed-bin packing's c2p permutation) — shared
  here as :func:`partition_feature`, the one copy of what each grower
  used to re-derive.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.histogram import build_histogram, histogram_leafbatch
from ..ops.route_pallas import route_level_pallas, route_pallas_ok
from ..ops.split import SplitResult, find_best_split
from ..telemetry import phase_scope

GROW_POLICIES = ("leafwise", "depthwise", "leafcompact")

# out-of-bounds scatter index → mode="drop".  A plain int, NOT jnp.int32:
# creating a jax array at import time would initialize the XLA backend
# before jax.distributed.initialize can run (multi-process bootstrap).
BIG = 1 << 28


class TreeArrays(NamedTuple):
    """Fixed-shape device tree (mirrors tree.h:124-149)."""
    num_leaves: jax.Array       # i32 scalar
    split_feature: jax.Array    # [L-1] i32
    threshold_bin: jax.Array    # [L-1] i32
    split_gain: jax.Array       # [L-1] f32
    left_child: jax.Array       # [L-1] i32 (~leaf encoding)
    right_child: jax.Array      # [L-1] i32
    leaf_parent: jax.Array      # [L] i32
    leaf_value: jax.Array       # [L] f32
    leaf_count: jax.Array       # [L] i32
    leaf_ids: jax.Array         # [N] i32 — final row → leaf partition


class SeamSchedule(NamedTuple):
    """Declarative parallel-seam schedule (see module docstring).  A
    plain namedtuple of callables/flags: constructed per shard closure by
    the learners, never a jit static — the closures capture it."""
    hist_axis: Optional[str] = None
    hist_reduce: Optional[object] = None
    int_hist_reduce: Optional[object] = None
    stat_reduce: Optional[object] = None
    root_hist_reduce: Optional[object] = None
    own_slice: Optional[object] = None
    split_finder: Optional[object] = None
    # root candidate search: the leaf-wise policies run ONE root search
    # but trace the body finder inside the split fori_loop, so a finder
    # that carries collectives (voting) files its root exchange here at
    # a loop=1 executed-calls estimate instead of inheriting the body's
    # per-split loop factor (wire-metrics accuracy; values identical)
    root_split_finder: Optional[object] = None
    hist_reduce_level: Optional[object] = None
    int_reduce_level: Optional[object] = None
    hist_local: bool = False
    # TRACED [F] storage->canonical gather indices handed to every
    # histogram build (ops/histogram feat_gather): the block-local
    # mixed-bin layout's owned slice is built in PACKED order, and the
    # kernels gather it back to canonical order IN THE INT DOMAIN (before
    # dequantize/psum), so the cache, root stats, subtraction and split
    # search are all canonical and the downstream f32 graph is
    # shape-identical to the uniform layout's — packed-vs-uniform stays
    # bit-identical including argmax tie-breaks and XLA FMA-contraction
    # choices (ISSUE 12; learners derive it from the shard rank, so the
    # SPMD program is shard-uniform even though each block's permutation
    # differs)
    hist_feat_gather: Optional[object] = None


_SERIAL = SeamSchedule()

# seam field → telemetry site suffix; per-split loop marks the seams that
# run inside the leaf-wise/compact split fori_loop (traced once, executed
# once per split) — the depthwise level seams trace once PER LEVEL
_SEAM_SITES = (
    ("hist_reduce", "hist_reduce", True),
    ("int_hist_reduce", "int_hist_reduce", True),
    ("stat_reduce", "root_stats", False),
    ("root_hist_reduce", "root_hist", False),
    ("hist_reduce_level", "level_hist_reduce", False),
    ("int_reduce_level", "level_int_reduce", False),
)


def wrap_schedule(policy: str, schedule: Optional[SeamSchedule],
                  num_splits: int) -> SeamSchedule:
    """Wire-metrics hook point (ISSUE 5), applied ONCE for every policy:
    any seam not already labeled by the learner that built it
    (telemetry.collective_span passes wrapped fns through) gets a
    grower-generic ``<policy>/<seam>`` site here, so custom learners'
    collectives still show up in the interconnect block.  The wrappers
    call the seam unchanged — traced programs are bit-identical."""
    from .. import telemetry as _tl
    s = schedule if schedule is not None else _SERIAL
    per_split = policy != "depthwise"
    updates = {}
    for field, suffix, split_loop in _SEAM_SITES:
        fn = getattr(s, field)
        if fn is None:
            continue
        loop = num_splits if (split_loop and per_split) else 1
        updates[field] = _tl.collective_span(
            "%s/%s" % (policy, suffix), fn, kind="reduce",
            axis=s.hist_axis, loop=loop, phase="grow")
    return s._replace(**updates) if updates else s


def _is_int8(compute_dtype) -> bool:
    return str(compute_dtype).startswith("int8")


def _tree_quant_max(compute_dtype, grad, hess, row_mask, s):
    """The int8 routes' one scale a tree, as the keyword to hand every
    histogram pass of the tree (``ops/hist_pallas.quant_max_of``: a
    sibling derived by subtraction is only the histogram of its rows if
    they rounded alike in both passes); nothing for the float routes."""
    if not _is_int8(compute_dtype):
        return {}
    from ..ops.hist_pallas import quant_max_of
    with phase_scope("histogram"):
        return {"quant_max": quant_max_of(grad, hess, row_mask,
                                          s.hist_axis)}


def _patchable(module_name: str, attr: str, default):
    """Resolve a histogram entry through its historical compat module at
    trace time: tests monkeypatch ``grower.build_histogram`` /
    ``grower_depthwise.histogram_leafbatch`` (the established stub
    seams), and the collapse must not silently disconnect them."""
    import importlib
    try:
        mod = importlib.import_module("%s.%s" % (__package__, module_name))
        return getattr(mod, attr, default)
    except Exception:  # pragma: no cover - import cycle during bootstrap
        return default


def partition_feature(packing, feat):
    """The partition-index-translate seam, single-homed: canonical split
    feature → row index of the STORAGE-layout bin matrix (mixed-bin
    packing reorders rows into bin-width classes; split results stay
    canonical — io/binning.PackSpec)."""
    if packing is not None and len(packing.widths) > 1:
        return jnp.asarray(packing.c2p, jnp.int32)[feat]
    return feat


def _apply_hist_reduce(hist, s: SeamSchedule, compute_dtype):
    """The shared reduce rule: the quantized path reduces its INT
    accumulators internally over hist_axis (bit-exactness;
    ops/hist_pallas.quantize_values) — psum by default, the ownership
    feature-block scatter when int_hist_reduce is set — so the f32
    hist_reduce must not run again on top."""
    if s.hist_reduce is not None and not (
            _is_int8(compute_dtype) and s.hist_axis is not None):
        hist = s.hist_reduce(hist)
    return hist


def _root_stats_of(full_hist, s: SeamSchedule, compute_dtype, grad, hess,
                   row_mask):
    """Root stats, shared by the leaf-wise and compact policies.

    int8: derive from the histogram — the int accumulators are
    bit-identical across serial/data-parallel (scales pmax-synced, int32
    sums order-free) and any feature's bins sum to the same exact
    quantized totals, so this also holds under feature-parallel ownership
    slices.  Under an ownership schedule the stats must come from the
    replicated full-F root, not the owned block (a feature-padding
    shard's block is all zeros); under ``hist_local`` (voting) the local
    totals must still be stat_reduce'd to global.

    f32: root sums come from the gradient vectors, not from any one
    feature's histogram — per-feature f32 bin-order rounding would make
    the totals shard-dependent under feature ownership (the reference
    likewise computes root sums once from gradients,
    serial_tree_learner.cpp:178-198)."""
    if _is_int8(compute_dtype):
        root_stats = jnp.sum(full_hist[0], axis=0)
        if s.hist_local and s.stat_reduce is not None:
            root_stats = s.stat_reduce(root_stats)
        return root_stats
    maskf = row_mask.astype(jnp.float32)
    root_stats = jnp.stack([jnp.sum(grad * maskf), jnp.sum(hess * maskf),
                            jnp.sum(maskf)])
    if s.stat_reduce is not None:
        root_stats = s.stat_reduce(root_stats)
    return root_stats


def _root_hist_pair(hist_full_fn, hist_of_fn, s: SeamSchedule,
                    compute_dtype):
    """(full, cached-root) histograms, shared by leaf-wise and compact:
    under an ownership schedule (own_slice set) the ROOT is built
    replicated — full F, plain psum — so root stats are exact on every
    shard including feature-PADDING shards, then only the owned slice is
    cached.  ``hist_full_fn`` builds the unreduced full histogram;
    ``hist_of_fn`` the seam-reduced one."""
    if s.own_slice is not None:
        full = hist_full_fn()
        if s.root_hist_reduce is not None and not (
                _is_int8(compute_dtype) and s.hist_axis is not None):
            full = s.root_hist_reduce(full)
        return full, s.own_slice(full)
    if s.root_hist_reduce is not None and not (
            _is_int8(compute_dtype) and s.hist_axis is not None):
        # masked psum schedules: the ONE root exchange rides its own
        # root-loop-labeled site — letting it ride hist_reduce would file
        # it at the body's per-split executed-calls estimate and inflate
        # the wire-bytes series (same psum, values bit-identical)
        full = s.root_hist_reduce(hist_full_fn())
        return full, full
    full = hist_of_fn()
    return full, full


def _depth_gated(res: SplitResult, depth, max_depth: int) -> SplitResult:
    """depth-limited leaves cannot split (serial_tree_learner.cpp:240-249)"""
    if max_depth > 0:
        res = res._replace(gain=jnp.where(depth >= max_depth, -jnp.inf,
                                          res.gain))
    return res


# ===================================================================== API

_GROW_STATICS = ("policy", "num_leaves", "num_bins_max", "min_data_in_leaf",
                 "min_sum_hessian_in_leaf", "max_depth", "hist_backend",
                 "hist_chunk", "compute_dtype", "packing",
                 "partition_packing",
                 "use_pallas_partition", "partition_overlap", "interpret")


def grow_tree_unified(bins, grad, hess, row_mask, feature_mask, num_bins,
                      *, policy: str, num_leaves: int, num_bins_max: int,
                      min_data_in_leaf: int, min_sum_hessian_in_leaf: float,
                      max_depth: int = -1, hist_backend: str = "matmul",
                      hist_chunk: int = 0, compute_dtype=jnp.float32,
                      packing=None, partition_packing=None,
                      use_pallas_partition: bool = False,
                      partition_overlap: bool = True,
                      interpret: bool = False,
                      schedule: Optional[SeamSchedule] = None,
                      partition_bins=None):
    """Grow one tree (TreeLearner::Train) under any growth policy × seam
    schedule.  Not jitted; callers wrap it (the module-level jits below,
    the learners' shard closures, the chunk-program builders).

    Parameters
    ----------
    bins : [F, N] integer bin matrix (N may be the local row shard under
        shard_map; F may be an owned feature slice under feature
        ownership — ``partition_bins`` then carries the full matrix)
    grad, hess : [N] f32 gradients/hessians from the objective
    row_mask : [N] bool — bagging × validity mask; masked rows still get
        leaf ids (OOB score updates come free, unlike gbdt.cpp:159-165)
    feature_mask, num_bins : [F] feature_fraction mask / real bin counts
        (owned slices under feature ownership)
    policy : leafwise | depthwise | leafcompact (see module docstring)
    schedule : SeamSchedule — the parallel seams; None = serial
    partition_bins : [F_global, N] matrix used to APPLY splits when
        ``bins`` is only an owned feature slice; split_finder must then
        return GLOBAL feature indices
    hist_chunk : row-chunk length of the histogram scan; 0 = the
        policy's default (16384 leaf-wise/compact, 65536 depthwise)
    packing / partition_packing : mixed-bin layout specs.  ``packing``
        describes the layout of ``bins`` (the histogram passes);
        ``partition_packing`` (default: ``packing``) the layout of
        ``partition_bins`` — they differ under the block-local ownership
        layout (io/binning.BlockedPackSpec), where the owned slice uses
        the shard-uniform ``block_view`` while splits apply on the full
        blocked storage matrix via the GLOBAL canonical->storage map
    use_pallas_partition / partition_overlap / interpret : the compact
        policy's partition-kernel routing (ops/compact.partition_segment)
    """
    if policy not in GROW_POLICIES:
        raise ValueError("unknown grow policy %r" % (policy,))
    if hist_chunk <= 0:
        hist_chunk = 65536 if policy == "depthwise" else 16384
    s = wrap_schedule(policy, schedule, max(num_leaves - 1, 1))
    kwargs = dict(num_leaves=num_leaves, num_bins_max=num_bins_max,
                  min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
                  max_depth=max_depth, hist_chunk=hist_chunk,
                  compute_dtype=compute_dtype, packing=packing,
                  partition_packing=(partition_packing
                                     if partition_packing is not None
                                     else packing))
    if policy == "depthwise":
        return _grow_depthwise(bins, grad, hess, row_mask, feature_mask,
                               num_bins, s, partition_bins, **kwargs)
    if policy == "leafcompact":
        return _grow_leafcompact(bins, grad, hess, row_mask, feature_mask,
                                 num_bins, s, hist_backend=hist_backend,
                                 use_pallas_partition=use_pallas_partition,
                                 partition_overlap=partition_overlap,
                                 interpret=interpret, **kwargs)
    return _grow_leafwise(bins, grad, hess, row_mask, feature_mask,
                          num_bins, s, partition_bins,
                          hist_backend=hist_backend, **kwargs)


# ====================================================== leaf-wise policy

class _GrowState(NamedTuple):
    tree: TreeArrays
    hist_cache: jax.Array       # [L, F, B, 3]
    cand_gain: jax.Array        # [L]
    cand_feature: jax.Array     # [L]
    cand_threshold: jax.Array   # [L]
    cand_left_out: jax.Array    # [L]
    cand_right_out: jax.Array
    cand_left_cnt: jax.Array    # [L] i32
    cand_right_cnt: jax.Array
    cand_left_g: jax.Array
    cand_left_h: jax.Array
    cand_right_g: jax.Array
    cand_right_h: jax.Array
    leaf_sum_g: jax.Array       # [L]
    leaf_sum_h: jax.Array
    leaf_cnt: jax.Array         # [L] i32
    leaf_depth: jax.Array       # [L] i32
    done: jax.Array             # bool scalar


def _grow_leafwise(bins, grad, hess, row_mask, feature_mask, num_bins,
                   s: SeamSchedule, partition_bins, *, num_leaves: int,
                   num_bins_max: int, min_data_in_leaf: int,
                   min_sum_hessian_in_leaf: float, max_depth: int,
                   hist_backend: str, hist_chunk: int, compute_dtype,
                   packing, partition_packing=None):
    """Masked leaf-wise growth (the reference's TreeLearner::Train,
    serial_tree_learner.cpp:119-153): DataPartition's permuted index
    lists become a [N] leaf-id vector, the LRU histogram pool a dense
    [L, F, B, 3] cache carried through the split fori_loop, and the
    smaller-leaf + subtraction trick is kept per split."""
    F, N = bins.shape
    L = num_leaves
    B = num_bins_max
    f32 = jnp.float32
    finder = s.split_finder or find_best_split
    build_hist = _patchable("grower", "build_histogram", build_histogram)
    if partition_bins is None:
        partition_bins = bins
    _fg = ({"feat_gather": s.hist_feat_gather}
           if s.hist_feat_gather is not None else {})
    _fg.update(_tree_quant_max(compute_dtype, grad, hess, row_mask, s))

    def hist_of(mask, salt=0):
        hist = build_hist(bins, grad, hess, mask, B,
                               backend=hist_backend, chunk=hist_chunk,
                               compute_dtype=compute_dtype,
                               axis_name=s.hist_axis,
                               int_reduce=s.int_hist_reduce, salt=salt,
                               packing=packing, **_fg)
        return _apply_hist_reduce(hist, s, compute_dtype)

    def best_of(hist, sum_g, sum_h, cnt, depth, root=False):
        f = (s.root_split_finder or finder) if root else finder
        with phase_scope("split_find"):
            res = f(hist, sum_g, sum_h, cnt, num_bins, feature_mask,
                    float(min_data_in_leaf),
                    float(min_sum_hessian_in_leaf))
            return _depth_gated(res, depth, max_depth)

    # Device phases: every piece of device work of this policy
    # sits under one scope of telemetry.DEVICE_PHASES, unconditionally.
    # Inside the split loop the candidate tables (cand_*, leaf_*) are
    # split_find's, the [L, F, B, 3] cache and the sibling subtraction
    # histogram's, the leaf-id update row_route's, the node records
    # tree_pack's.

    # ---- root init (BeforeTrain, serial_tree_learner.cpp:155-236)
    full, root_hist = _root_hist_pair(
        lambda: build_hist(bins, grad, hess, row_mask, B,
                           backend=hist_backend, chunk=hist_chunk,
                           compute_dtype=compute_dtype,
                           axis_name=s.hist_axis, packing=packing,
                           **_fg),
        lambda: hist_of(row_mask), s, compute_dtype)
    with phase_scope("histogram"):
        root_stats = _root_stats_of(full, s, compute_dtype, grad, hess,
                                    row_mask)
    root_g, root_h, root_c = root_stats[0], root_stats[1], root_stats[2]
    root_best = best_of(root_hist, root_g, root_h, root_c,
                        jnp.asarray(1, jnp.int32), root=True)
    with phase_scope("tree_pack"):
        neg_inf = jnp.full((L,), -jnp.inf, dtype=f32)
        zeros_i = jnp.zeros((L,), dtype=jnp.int32)
        zeros_f = jnp.zeros((L,), dtype=f32)

        tree = TreeArrays(
            num_leaves=jnp.asarray(1, jnp.int32),
            split_feature=jnp.zeros((L - 1,), jnp.int32),
            threshold_bin=jnp.zeros((L - 1,), jnp.int32),
            split_gain=jnp.zeros((L - 1,), f32),
            left_child=jnp.zeros((L - 1,), jnp.int32),
            right_child=jnp.zeros((L - 1,), jnp.int32),
            leaf_parent=jnp.full((L,), -1, jnp.int32),
            leaf_value=zeros_f,
            leaf_count=zeros_i.at[0].set(root_c.astype(jnp.int32)),
            leaf_ids=jnp.zeros((N,), jnp.int32),
        )
        state = _GrowState(
            tree=tree,
            hist_cache=jnp.zeros((L,) + root_hist.shape,
                                 f32).at[0].set(root_hist),
            cand_gain=neg_inf.at[0].set(root_best.gain),
            cand_feature=zeros_i.at[0].set(root_best.feature),
            cand_threshold=zeros_i.at[0].set(root_best.threshold),
            cand_left_out=zeros_f.at[0].set(root_best.left_output),
            cand_right_out=zeros_f.at[0].set(root_best.right_output),
            cand_left_cnt=zeros_i.at[0].set(root_best.left_count),
            cand_right_cnt=zeros_i.at[0].set(root_best.right_count),
            cand_left_g=zeros_f.at[0].set(root_best.left_sum_grad),
            cand_left_h=zeros_f.at[0].set(root_best.left_sum_hess),
            cand_right_g=zeros_f.at[0].set(root_best.right_sum_grad),
            cand_right_h=zeros_f.at[0].set(root_best.right_sum_hess),
            leaf_sum_g=zeros_f.at[0].set(root_g),
            leaf_sum_h=zeros_f.at[0].set(root_h),
            leaf_cnt=zeros_i.at[0].set(root_c.astype(jnp.int32)),
            leaf_depth=zeros_i.at[0].set(1),
            done=jnp.asarray(False),
        )

    def body(_, state: _GrowState) -> _GrowState:
        # pick the best leaf to split (FindBestSplitsForLeaves argmax,
        # serial_tree_learner.cpp:140-147)
        with phase_scope("split_find"):
            best_leaf = jnp.argmax(state.cand_gain).astype(jnp.int32)
            best_gain = state.cand_gain[best_leaf]
            should_split = jnp.logical_and(~state.done, best_gain > 0.0)

        def do_split(state: _GrowState) -> _GrowState:
            tree = state.tree
            bl = best_leaf
            nl = tree.num_leaves
            with phase_scope("tree_pack"):
                node = nl - 1
            new_leaf = nl

            with phase_scope("split_find"):
                feat = state.cand_feature[bl]
                thr = state.cand_threshold[bl]
                lcnt = state.cand_left_cnt[bl]
                rcnt = state.cand_right_cnt[bl]
                left_is_smaller = lcnt <= rcnt
                small_leaf = jnp.where(left_is_smaller, bl, new_leaf)

            # --- record the node (Tree::Split, tree.cpp:50-83)
            with phase_scope("tree_pack"):
                p = tree.leaf_parent[bl]
                pp = jnp.maximum(p, 0)
                lc_at_p = jnp.where(
                    (p >= 0) & (tree.left_child[pp] == ~bl),
                    node, tree.left_child[pp])
                rc_at_p = jnp.where(
                    (p >= 0) & (tree.right_child[pp] == ~bl),
                    node, tree.right_child[pp])
                left_child = (tree.left_child.at[pp].set(lc_at_p)
                              .at[node].set(~bl))
                right_child = (tree.right_child.at[pp].set(rc_at_p)
                               .at[node].set(~new_leaf))

            # --- partition rows (DataPartition::Split as masked where,
            # data_partition.hpp:93-139), split feature translated through
            # the storage-layout map (partition-index-translate seam)
            with phase_scope("row_route"):
                pfeat = partition_feature(partition_packing, feat)
                fbin = jax.lax.dynamic_index_in_dim(
                    partition_bins, pfeat, axis=0,
                    keepdims=False).astype(jnp.int32)
                go_right = fbin > thr
                leaf_ids = jnp.where((tree.leaf_ids == bl) & go_right,
                                     new_leaf, tree.leaf_ids)
                small_mask = row_mask & (leaf_ids == small_leaf)

            # --- child histograms: build the smaller, subtract for the larger
            # (serial_tree_learner.cpp:262-283).
            # salt = the new leaf index: varies per split pass so the
            # stochastic-rounding bits decorrelate across passes
            small_hist = hist_of(small_mask, salt=new_leaf)
            with phase_scope("histogram"):
                parent_hist = state.hist_cache[bl]
                large_hist = parent_hist - small_hist
                lhist = jnp.where(left_is_smaller, small_hist, large_hist)
                rhist = jnp.where(left_is_smaller, large_hist, small_hist)

            # --- child stats
            with phase_scope("split_find"):
                lg, lh = state.cand_left_g[bl], state.cand_left_h[bl]
                rg, rh = state.cand_right_g[bl], state.cand_right_h[bl]
                lcf, rcf = lcnt.astype(f32), rcnt.astype(f32)
                depth = state.leaf_depth[bl] + 1

            # --- new candidate splits for both children.  Issued BEFORE
            # the [L, F, B, 3] cache scatter below: under an ownership
            # schedule the finder carries the packed-SplitInfo allgather,
            # and putting it first in program order lets XLA's async
            # collective scheduler overlap the wire latency with the
            # cache writeback's HBM traffic and the node bookkeeping that
            # dispatches the next split (ISSUE 9 overlap seam; pure
            # scheduling — the traced values are bit-identical)
            lbest = best_of(lhist, lg, lh, lcf, depth)
            rbest = best_of(rhist, rg, rh, rcf, depth)
            with phase_scope("histogram"):
                hist_cache = (state.hist_cache.at[bl].set(lhist)
                              .at[new_leaf].set(rhist))

            with phase_scope("tree_pack"):
                tree = tree._replace(
                    num_leaves=nl + 1,
                    split_feature=tree.split_feature.at[node].set(feat),
                    threshold_bin=tree.threshold_bin.at[node].set(thr),
                    split_gain=tree.split_gain.at[node].set(best_gain),
                    left_child=left_child,
                    right_child=right_child,
                    leaf_parent=tree.leaf_parent.at[bl].set(node)
                                                .at[new_leaf].set(node),
                    leaf_value=tree.leaf_value
                                   .at[bl].set(state.cand_left_out[bl])
                                   .at[new_leaf]
                                   .set(state.cand_right_out[bl]),
                    leaf_count=tree.leaf_count.at[bl].set(lcnt)
                                              .at[new_leaf].set(rcnt),
                    leaf_ids=leaf_ids,
                )
            def put(arr, left, right):
                return arr.at[bl].set(left).at[new_leaf].set(right)

            with phase_scope("split_find"):
                return state._replace(
                    tree=tree,
                    hist_cache=hist_cache,
                    cand_gain=put(state.cand_gain, lbest.gain, rbest.gain),
                    cand_feature=put(state.cand_feature, lbest.feature,
                                     rbest.feature),
                    cand_threshold=put(state.cand_threshold,
                                       lbest.threshold, rbest.threshold),
                    cand_left_out=put(state.cand_left_out,
                                      lbest.left_output, rbest.left_output),
                    cand_right_out=put(state.cand_right_out,
                                       lbest.right_output,
                                       rbest.right_output),
                    cand_left_cnt=put(state.cand_left_cnt, lbest.left_count,
                                      rbest.left_count),
                    cand_right_cnt=put(state.cand_right_cnt,
                                       lbest.right_count, rbest.right_count),
                    cand_left_g=put(state.cand_left_g, lbest.left_sum_grad,
                                    rbest.left_sum_grad),
                    cand_left_h=put(state.cand_left_h, lbest.left_sum_hess,
                                    rbest.left_sum_hess),
                    cand_right_g=put(state.cand_right_g,
                                     lbest.right_sum_grad,
                                     rbest.right_sum_grad),
                    cand_right_h=put(state.cand_right_h,
                                     lbest.right_sum_hess,
                                     rbest.right_sum_hess),
                    leaf_sum_g=put(state.leaf_sum_g, lg, rg),
                    leaf_sum_h=put(state.leaf_sum_h, lh, rh),
                    leaf_cnt=put(state.leaf_cnt, lcnt, rcnt),
                    leaf_depth=put(state.leaf_depth, depth, depth),
                )

        def no_split(state: _GrowState) -> _GrowState:
            return state._replace(done=jnp.asarray(True))

        # profiler alignment (ISSUE 2): the whole split body is labeled in
        # HLO metadata so profile_dir= traces group the per-split ops
        with jax.named_scope("leafwise_split"):
            return jax.lax.cond(should_split, do_split, no_split, state)

    return jax.lax.fori_loop(0, L - 1, body, state).tree


# ====================================================== depthwise policy

def _route_level_xla(partition_bins, slot_id, out_leaf, row_mask, feat_part,
                     threshold, chosen, right_leaf, small_is_right, *,
                     num_bins_max: int, num_leaves: int):
    """One level's row routing in XLA: (new slot id, new leaf id, the
    rows of every chosen slot's smaller child).  All per-slot attributes
    a row needs (split feature, threshold, chosen flag, new right-leaf
    id, smaller-child side) ride ONE [P, N] one-hot matmul instead of one
    pass per attribute: the slot-select one-hot is the expensive object
    (O(P·N) comparisons), so it is generated once and contracted against
    a packed [P, K] table."""
    f32 = jnp.float32
    i32 = jnp.int32
    P = chosen.shape[0]
    table = jnp.stack([feat_part.astype(f32),
                       threshold.astype(f32),
                       chosen.astype(f32),
                       right_leaf.astype(f32),
                       small_is_right.astype(f32)], axis=1)      # [P, 5]
    lsel = (slot_id[None, :] ==
            jnp.arange(P, dtype=i32)[:, None]).astype(f32)       # [P, N]
    # The table carries integer ids (feature, threshold, leaf).
    # Default TPU matmul precision truncates f32 operands to bf16,
    # which is EXACT for integers <= 256 — and exactly one lsel
    # entry matches per row, so there is no accumulation error
    # either.  Only configs with ids beyond 256 need the 6-pass
    # HIGHEST decomposition (measured 2.27 ms vs 0.72 ms per level
    # at 11M rows).  Feature ids are GLOBAL (split_finder returns
    # canonical ids even when ``bins`` is an owned slice), so the
    # guard must use the global width, not the sliced F.
    Fg = partition_bins.shape[0]
    ids_bf16_exact = max(Fg, num_bins_max, num_leaves) <= 256
    attr_prec = (None if ids_bf16_exact
                 else jax.lax.Precision.HIGHEST)
    attrs = jnp.einsum("pn,pk->kn", lsel, table,
                       precision=attr_prec,
                       preferred_element_type=jnp.float32)       # [5, N]
    feat_row = attrs[0].astype(i32)
    thr_row = attrs[1].astype(i32)
    in_chosen = attrs[2] > 0.5
    rl_row = attrs[3].astype(i32)
    small_right_row = attrs[4] > 0.5

    # the row's bin on its slot's split feature: an O(F·N) feature
    # one-hot avoids materializing the old [P, N] row gather, but
    # its cost grows with the dataset width — for wide datasets a
    # direct per-row gather is cheaper than F·N comparisons
    if Fg <= 128:
        fsel = (feat_row[None, :]
                == jnp.arange(Fg, dtype=i32)[:, None])
        # bins < 256 are bf16-exact and one fsel entry matches per
        # row
        row_bin = jnp.einsum(
            "fn,fn->n", fsel.astype(f32), partition_bins.astype(f32),
            precision=(None if num_bins_max <= 256
                       else jax.lax.Precision.HIGHEST)).astype(i32)
    else:
        row_bin = jnp.take_along_axis(
            partition_bins, feat_row[None, :], axis=0)[0].astype(i32)
    go_right = row_bin > thr_row
    out_leaf = jnp.where(in_chosen & go_right, rl_row, out_leaf)
    slot_id = (2 * slot_id
               + jnp.where(in_chosen, go_right.astype(i32), 0))
    sel = in_chosen & (go_right == small_right_row) & row_mask
    return slot_id, out_leaf, sel


def num_levels(num_leaves: int, max_depth: int = -1) -> int:
    """Number of split levels.  Matches the leaf-wise depth rule (a leaf
    at depth >= max_depth cannot split, root depth 1), so max_depth
    allows max_depth - 1 split levels."""
    d = max(1, math.ceil(math.log2(max(num_leaves, 2))))
    if max_depth > 0:
        d = min(d, max(max_depth - 1, 1))
    return d


def _grow_depthwise(bins, grad, hess, row_mask, feature_mask, num_bins,
                    s: SeamSchedule, partition_bins, *, num_leaves: int,
                    num_bins_max: int, min_data_in_leaf: int,
                    min_sum_hessian_in_leaf: float, max_depth: int,
                    hist_chunk: int, compute_dtype, packing,
                    partition_packing=None) -> TreeArrays:
    """Depth-wise (level-batched) growth — the TPU throughput path: the
    histograms of ALL leaves of a level build in ONE leaf-batched matmul
    pass (3·P value columns fill the MXU; 8 batched passes for a 255-leaf
    tree instead of 254 single-leaf passes), levels unrolled in Python
    with static [P = 2^d] slot shapes.  The smaller-child + subtraction
    trick is kept at level granularity.  Split-finding math is identical
    to leaf-wise; split ORDER is by level (documented TPU-first trade),
    the num_leaves budget honored best-first within each level."""
    F, N = bins.shape
    L = num_leaves
    D = num_levels(L, max_depth)
    B = num_bins_max
    f32 = jnp.float32
    i32 = jnp.int32

    from .. import telemetry

    maskf = row_mask.astype(f32)
    mind = float(min_data_in_leaf)
    minh = float(min_sum_hessian_in_leaf)
    leafbatch = _patchable("grower_depthwise", "histogram_leafbatch",
                           histogram_leafbatch)
    tree_scale = _tree_quant_max(compute_dtype, grad, hess, row_mask, s)

    def batch_hist_rows(b, g, h, col_id, col_ok, C, level=False, salt=0):
        # level passes may use the scatter schedule; the root pass always
        # reduces in full
        int_red = s.int_reduce_level if level else None
        # forward optional kwargs only when set: drop-in replacements
        # (histogram_leafbatch_segsum, test/profiling stubs) don't take
        # them
        extra = {"int_reduce": int_red} if int_red is not None else {}
        extra.update(tree_scale)
        if s.hist_feat_gather is not None:
            extra["feat_gather"] = s.hist_feat_gather
        if salt and compute_dtype == "int8_sr":
            extra["salt"] = salt
        out = leafbatch(b, g, h, col_id, col_ok, C, B,
                        chunk=hist_chunk,
                        compute_dtype=compute_dtype,
                        axis_name=s.hist_axis,
                        **({"packing": packing}
                           if packing is not None else {}),
                        **extra)
        # the quantized path reduces its INT accumulators internally over
        # hist_axis (bit-exactness); applying hist_reduce again would
        # double-count
        if _is_int8(compute_dtype) and s.hist_axis is not None:
            return out
        red = (s.hist_reduce_level or s.hist_reduce) if level \
            else s.hist_reduce
        if red is not None:
            out = red(out)
        return out

    def batch_hist(col_id, col_ok, C, level=False, salt=0):
        return batch_hist_rows(bins, grad, hess, col_id, col_ok, C,
                               level=level, salt=salt)

    vsplit = jax.vmap(s.split_finder or find_best_split,
                      in_axes=(0, 0, 0, 0, None, None, None, None))
    if partition_bins is None:
        partition_bins = bins
    route_kernel = route_pallas_ok(partition_bins.dtype, B)

    # ---- root (BeforeTrain: serial_tree_learner.cpp:155-236).
    # named_scope per level (ISSUE 2): profile_dir= Perfetto traces show
    # the unrolled level structure ("level0/histogram", ...) instead of a
    # flat op soup — unconditional, so it can't perturb program identity
    # Every piece of device work below also sits under one scope of
    # telemetry.DEVICE_PHASES, unconditionally, so a device trace
    # splits a level into histogram / split_find / row_route / tree_pack
    # with telemetry on or off.
    with jax.named_scope("level0"):
        hists = batch_hist(jnp.zeros((N,), i32), row_mask, 1)  # [1,F,B,3]
        with phase_scope("histogram"):
            root_stats = _root_stats_of(hists[0], s, compute_dtype, grad,
                                        hess, row_mask)
            if s.own_slice is not None:
                # ownership schedule: keep only this shard's contiguous
                # feature block from here on (root stats above came from
                # the full replicated histogram, so they stay
                # bit-identical to the psum schedule)
                hists = s.own_slice(hists)

    with phase_scope("split_find"):
        # per-slot level state (slot s at level d holds one candidate leaf)
        alive = jnp.ones((1,), bool)
        leaf_of = jnp.zeros((1,), i32)          # output leaf index per slot
        parent_node = jnp.full((1,), -1, i32)   # node owning the slot's leaf
        slot_g = root_stats[0][None]
        slot_h = root_stats[1][None]
        slot_c = root_stats[2][None]

    with phase_scope("row_route"):
        slot_id = jnp.zeros((N,), i32)          # row → level-local slot
        out_leaf = jnp.zeros((N,), i32)         # row → output leaf index

    with phase_scope("tree_pack"):
        # output tree arrays (static size L)
        leaf_value = jnp.zeros((L,), f32)
        leaf_count = jnp.zeros((L,), i32).at[0].set(
            root_stats[2].astype(i32))
        leaf_parent = jnp.full((L,), -1, i32)
        split_feature = jnp.zeros((max(L - 1, 1),), i32)
        threshold_bin = jnp.zeros((max(L - 1, 1),), i32)
        split_gain = jnp.zeros((max(L - 1, 1),), f32)
        left_child = jnp.zeros((max(L - 1, 1),), i32)
        right_child = jnp.zeros((max(L - 1, 1),), i32)

        n_nodes = jnp.asarray(0, i32)           # == num_leaves_cur - 1

    for d in range(D):
        P = 1 << d

        # ---- best split per slot (vmapped FindBestThreshold scan).  The
        # span wraps the CALL (not the vmapped body — a batching trace is
        # never "execution"), so eager runs (jax.disable_jit telemetry
        # profiling) attribute real split-search time.  The scope is its
        # own: under vmap the finder's inner scope reads
        # "vmap(split_find)", which no phase pattern matches
        with phase_scope("split_find"), telemetry.span("split_find") as _sp:
            res = _sp.fence(vsplit(hists, slot_g, slot_h, slot_c, num_bins,
                                   feature_mask, mind, minh))
            can = alive & (res.gain > 0.0) & jnp.isfinite(res.gain)

            # ---- budget: split the top-gain slots first (within-level
            # best-first, matching the leaf-wise selection rule at level
            # scope)
            budget = (L - 1) - n_nodes
            gains_m = jnp.where(can, res.gain, -jnp.inf)
            order = jnp.argsort(-gains_m)             # best slot first
            rank = jnp.argsort(order).astype(i32)     # slot → rank
            chosen = can & (rank < budget)
            n_chosen = jnp.sum(chosen.astype(i32))

            # ---- index assignment, in slot order (deterministic)
            csum = jnp.cumsum(chosen.astype(i32))
            node_of = n_nodes + csum - 1              # node per chosen slot
            right_leaf = (n_nodes + 1) + csum - 1     # new leaf per chosen
            bl = leaf_of

        with phase_scope("tree_pack"):
            nidx = jnp.where(chosen, node_of, BIG)
            blx = jnp.where(chosen, bl, BIG)
            rlx = jnp.where(chosen, right_leaf, BIG)

            # ---- node records (Tree::Split, tree.cpp:50-83)
            split_feature = split_feature.at[nidx].set(res.feature,
                                                       mode="drop")
            threshold_bin = threshold_bin.at[nidx].set(res.threshold,
                                                       mode="drop")
            split_gain = split_gain.at[nidx].set(res.gain, mode="drop")
            left_child = left_child.at[nidx].set(~bl, mode="drop")
            right_child = right_child.at[nidx].set(~right_leaf, mode="drop")

            # parent child-pointer fixup: slot parity says which side this
            # slot's leaf sits on in its parent node (even = left)
            pfix = jnp.where(chosen & (parent_node >= 0), parent_node, BIG)
            if d > 0:
                is_left = (jnp.arange(P, dtype=i32) % 2) == 0
                left_child = left_child.at[
                    jnp.where(is_left, pfix, BIG)].set(node_of, mode="drop")
                right_child = right_child.at[
                    jnp.where(is_left, BIG, pfix)].set(node_of, mode="drop")

            # ---- leaf records
            leaf_value = leaf_value.at[blx].set(res.left_output, mode="drop")
            leaf_value = leaf_value.at[rlx].set(res.right_output,
                                                mode="drop")
            leaf_count = leaf_count.at[blx].set(res.left_count, mode="drop")
            leaf_count = leaf_count.at[rlx].set(res.right_count, mode="drop")
            leaf_parent = leaf_parent.at[blx].set(node_of, mode="drop")
            leaf_parent = leaf_parent.at[rlx].set(node_of, mode="drop")

            n_nodes = n_nodes + n_chosen

        # ---- partition rows (DataPartition::Split as fused masked
        # passes): one streaming Pallas kernel a level on a TPU
        # (ops/route_pallas.py), the XLA formulation ``_route_level_xla``
        # everywhere else, the same integers either way.
        # The device name is "row_route": "partition" is the compacted
        # grower's stream-partition kernel.  The host span keeps its
        # canonical JSONL key.
        with phase_scope("row_route"), telemetry.span("partition") as _sp:
            small_is_right = res.right_count < res.left_count   # ties → left
            # mixed-bin packing stores the matrix rows in packed order;
            # the per-slot partition feature must address that layout
            # (the recorded split_feature above stays canonical)
            feat_part = partition_feature(partition_packing, res.feature)
            # counted per level, here, at trace time: the route is baked
            # into the program like the histogram kernel's
            telemetry.count("partition/route_pallas" if route_kernel
                            else "partition/route_xla")
            level = (partition_bins, slot_id, out_leaf, row_mask, feat_part,
                     res.threshold, chosen, right_leaf, small_is_right)
            slot_id, out_leaf, sel = (
                route_level_pallas(*level) if route_kernel
                else _route_level_xla(*level, num_bins_max=B, num_leaves=L))
            _sp.fence((out_leaf, slot_id))

        if d + 1 >= D:
            break

        # ---- next-level slot state (children of slot s at 2s / 2s+1)
        def interleave(a, b):
            return jnp.stack([a, b], axis=1).reshape(2 * P, *a.shape[1:])

        with phase_scope("split_find"):
            alive = interleave(chosen, chosen)
            leaf_of = interleave(bl, right_leaf)
            parent_node = interleave(node_of, node_of)
            slot_g = interleave(res.left_sum_grad, res.right_sum_grad)
            slot_h = interleave(res.left_sum_hess, res.right_sum_hess)
            slot_c = interleave(res.left_count.astype(f32),
                                res.right_count.astype(f32))

        # ---- level histogram: build ONLY the smaller child of every chosen
        # parent in one batched pass, derive the sibling by subtraction
        # Smaller-child choice from the SplitResult counts (integer-valued
        # f32 histogram sums; replicated under the data-parallel learner,
        # whose counts come from psum'd histograms).  Above 2^24 rows per
        # node the f32 rounding could mis-order near-equal children — that
        # only means the pass histograms the slightly larger child (the
        # sibling is still exact via subtraction), a perf non-event, so no
        # recount is needed at any scale.
        with phase_scope("row_route"):
            par_of_row = slot_id // 2
        # The masked full-N pass is the fastest smaller-child schedule
        # measured on v5e (1M and 11M rows): gathering the selected rows
        # into a compact N/2 buffer first (the masked-dense analog of the
        # reference's per-leaf index lists, data_partition.hpp) costs more
        # in cumsum/scatter/gather plumbing than the halved histogram pass
        # saves — see git history for the removed compaction path.
        with jax.named_scope("level%d" % (d + 1)):
            hist_small = batch_hist(par_of_row, sel, P, level=True,
                                    salt=d + 1)
            # the sibling subtraction and the slot interleave read and
            # write whole [P, F, B, 3] histograms: the histogram phase's
            with phase_scope("histogram"):
                hist_large = hists - hist_small
                flip = small_is_right[:, None, None, None]
                hists = interleave(
                    jnp.where(flip, hist_large, hist_small),
                    jnp.where(flip, hist_small, hist_large))

    with phase_scope("tree_pack"):
        num_leaves_final = n_nodes + 1
        return TreeArrays(
            num_leaves=num_leaves_final,
            split_feature=split_feature[:max(L - 1, 1)],
            threshold_bin=threshold_bin,
            split_gain=split_gain,
            left_child=left_child,
            right_child=right_child,
            leaf_parent=leaf_parent,
            leaf_value=leaf_value,
            leaf_count=leaf_count,
            leaf_ids=out_leaf,
        )


# ==================================================== leafcompact policy

class _CompactState(NamedTuple):
    tree: TreeArrays
    pane: jax.Array             # [2, rows, lanes] int8 — the partitioned
                                # plane pane's two sides (compact.pack_planes)
    seg_start: jax.Array        # [L] i32 — leaf -> lane range start
    seg_cnt: jax.Array          # [L] i32 — physical lane count
    seg_bucket: jax.Array       # [L] i32 — static width tier
    hist_cache: jax.Array       # [L, F, B, 3] (owned Fb block under an
                                # ownership schedule)
    cand_gain: jax.Array        # [L]
    cand_feature: jax.Array
    cand_threshold: jax.Array
    cand_left_out: jax.Array
    cand_right_out: jax.Array
    cand_left_cnt: jax.Array
    cand_right_cnt: jax.Array
    cand_left_g: jax.Array
    cand_left_h: jax.Array
    cand_right_g: jax.Array
    cand_right_h: jax.Array
    leaf_depth: jax.Array       # [L] i32
    done: jax.Array             # bool


def _run_if(pred, fn, operand):
    """``lax.cond(pred, fn, identity, operand)`` as a while loop of zero
    trips or one.  XLA updates a while loop's carry in place; a
    conditional's operand it copies whole before a branch may write to
    it, so a split under ``lax.cond`` / ``lax.switch`` copied the plane
    pane twice and the leaf histogram cache twice (809 MB and 1.56 GB
    each at 400,000 x 2,000; PERF.md section 6, PR 34) to change a
    range of the one and two rows of the other."""
    return jax.lax.while_loop(
        lambda c: c[0],
        lambda c: (jnp.asarray(False), fn(c[1])),
        (pred, operand))[1]


def _grow_leafcompact(bins, grad, hess, row_mask, feature_mask, num_bins,
                      s: SeamSchedule, *, num_leaves: int,
                      num_bins_max: int, min_data_in_leaf: int,
                      min_sum_hessian_in_leaf: float, max_depth: int,
                      hist_backend: str, hist_chunk: int, compute_dtype,
                      packing, partition_packing=None,
                      use_pallas_partition: bool,
                      partition_overlap: bool, interpret: bool):
    """Compacted leaf-wise growth — reference-parity split order at the
    reference's geometric-series histogram cost (~N·log L instead of
    N·(L-1)): every leaf's rows stay contiguous in a plane pane of two
    sides, [2, F+9 (padded), P (padded)] (bin rows + grad/hess bit-planes
    + validity; ops/compact.pack_planes).  A leaf's range lies on the side
    its depth's parity names; each split stably partitions the parent's
    lane range INSIDE the pane, read from the parent's side and written
    to the same lanes of the other (one aliased Pallas MXU
    selection-matmul call on TPU, the stable argsort oracle elsewhere:
    no slice out, no write-back), and histograms ONLY the
    physically-smaller child's bucketed range, sliced from the child's
    side, deriving the sibling by subtraction.  Ranges are handled at
    bucketed widths (ops/compact.bucket_table), one tier's branch a
    split; the histogram tier is pmax-synced over hist_axis so
    collectives inside the tier switch stay uniform across shards.
    Equivalence to the masked policy: structure-exact, values within the
    documented cross-program ulp budget (XLA CPU contracts the int8
    dequantize into split-dependent FMAs; see
    tests/test_leafcompact.py)."""
    from ..ops.compact import (BLOCK, bucket_table, pack_planes, pane_rows,
                               partition_segment, range_origin,
                               unpack_values)
    from .. import telemetry as _tl

    F, N = bins.shape
    R = pane_rows(F)            # plane-pane rows (ops/compact.pack_planes)
    L = num_leaves
    B = num_bins_max
    f32 = jnp.float32
    ppack = partition_packing if partition_packing is not None else packing
    c2p_arr = (jnp.asarray(ppack.c2p, jnp.int32)
               if ppack is not None and len(ppack.widths) > 1 else None)
    table = bucket_table(N, min_width=max(BLOCK, (-(-N // BLOCK) * BLOCK)
                                          >> 9))
    P = table[0]
    K = len(table)
    table_arr = jnp.asarray(table, jnp.int32)

    def bucket_of(x):
        return (jnp.sum(table_arr >= jnp.maximum(x, 1)) - 1).astype(
            jnp.int32)

    build_hist = _patchable("grower_leafcompact", "build_histogram",
                            build_histogram)
    _fg = ({"feat_gather": s.hist_feat_gather}
           if s.hist_feat_gather is not None else {})
    _fg.update(_tree_quant_max(compute_dtype, grad, hess, row_mask, s))

    def hist_of(hbins, hg, hh, hmask, salt=0, **extra):
        hist = build_hist(hbins, hg, hh, hmask, B,
                               backend=hist_backend, chunk=hist_chunk,
                               compute_dtype=compute_dtype,
                               axis_name=s.hist_axis,
                               int_reduce=s.int_hist_reduce, salt=salt,
                               packing=packing, **_fg, **extra)
        return _apply_hist_reduce(hist, s, compute_dtype)

    finder = s.split_finder or find_best_split

    def _finder(hist, sum_g, sum_h, cnt):
        return finder(hist, sum_g, sum_h, cnt, num_bins,
                      feature_mask, float(min_data_in_leaf),
                      float(min_sum_hessian_in_leaf))

    def best_of(hist, sum_g, sum_h, cnt, depth, root=False):
        f = (s.root_split_finder or finder) if root else finder
        with phase_scope("split_find"):
            if root:
                return _depth_gated(
                    f(hist, sum_g, sum_h, cnt, num_bins, feature_mask,
                      float(min_data_in_leaf),
                      float(min_sum_hessian_in_leaf)), depth, max_depth)
            return _depth_gated(_finder(hist, sum_g, sum_h, cnt), depth,
                                max_depth)

    def best_of_pair(lhist, rhist, lg, lh, lc, rg, rh, rc, depth):
        """Both children's candidate searches in ONE batched finder call
        (vmap over a [2, F, B, 3] stack): the finder's cumsum/argmax work
        is tiny, so per-call XLA overhead — paid 2x per split otherwise —
        is the cost that matters.  Elementwise math is identical to two
        single calls (both children share the same depth)."""
        # the scope is this call's own: under vmap the finder's inner
        # scope reads "vmap(split_find)", which no phase pattern matches
        with phase_scope("split_find"):
            both = _depth_gated(
                jax.vmap(_finder)(jnp.stack([lhist, rhist]),
                                  jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                                  jnp.stack([lc, rc])), depth, max_depth)
            lbest = jax.tree.map(lambda x: x[0], both)
            rbest = jax.tree.map(lambda x: x[1], both)
        return lbest, rbest

    # ---- root (BeforeTrain): full-data pass over the ORIGINAL arrays —
    # identical to the masked policy's root, so the two policies share
    # root histograms bit for bit
    full, root_hist = _root_hist_pair(
        lambda: build_hist(bins, grad, hess, row_mask, B,
                           backend=hist_backend, chunk=hist_chunk,
                           compute_dtype=compute_dtype,
                           axis_name=s.hist_axis, packing=packing, **_fg),
        lambda: hist_of(bins, grad, hess, row_mask), s, compute_dtype)
    # Device phases, as in the masked policy: the candidate and
    # segment tables are split_find's, the cache and the sibling
    # subtraction histogram's, the original-order leaf ids row_route's,
    # the pane (packing it, a split's mask and its kernel) partition's,
    # the node records tree_pack's.
    with phase_scope("histogram"):
        root_stats = _root_stats_of(full, s, compute_dtype, grad, hess,
                                    row_mask)
    root_g, root_h, root_c = root_stats[0], root_stats[1], root_stats[2]
    root_best = best_of(root_hist, root_g, root_h, root_c,
                        jnp.asarray(1, jnp.int32), root=True)
    with phase_scope("partition"):
        root_pane = pack_planes(bins, grad, hess, row_mask, P)

    neg_inf = jnp.full((L,), -jnp.inf, dtype=f32)
    zeros_i = jnp.zeros((L,), dtype=jnp.int32)
    zeros_f = jnp.zeros((L,), dtype=f32)

    with phase_scope("tree_pack"):
        tree = TreeArrays(
            num_leaves=jnp.asarray(1, jnp.int32),
            split_feature=jnp.zeros((L - 1,), jnp.int32),
            threshold_bin=jnp.zeros((L - 1,), jnp.int32),
            split_gain=jnp.zeros((L - 1,), f32),
            left_child=jnp.zeros((L - 1,), jnp.int32),
            right_child=jnp.zeros((L - 1,), jnp.int32),
            leaf_parent=jnp.full((L,), -1, jnp.int32),
            leaf_value=zeros_f,
            leaf_count=zeros_i.at[0].set(root_c.astype(jnp.int32)),
            leaf_ids=jnp.zeros((N,), jnp.int32),
        )
        state = _CompactState(
            tree=tree,
            pane=root_pane,
            seg_start=zeros_i,
            seg_cnt=zeros_i.at[0].set(N),
            seg_bucket=zeros_i.at[0].set(bucket_of(N)),
            # owned-block shape under an ownership schedule, full F
            # otherwise
            hist_cache=jnp.zeros((L,) + root_hist.shape, f32).at[0].set(
                root_hist),
            cand_gain=neg_inf.at[0].set(root_best.gain),
            cand_feature=zeros_i.at[0].set(root_best.feature),
            cand_threshold=zeros_i.at[0].set(root_best.threshold),
            cand_left_out=zeros_f.at[0].set(root_best.left_output),
            cand_right_out=zeros_f.at[0].set(root_best.right_output),
            cand_left_cnt=zeros_i.at[0].set(root_best.left_count),
            cand_right_cnt=zeros_i.at[0].set(root_best.right_count),
            cand_left_g=zeros_f.at[0].set(root_best.left_sum_grad),
            cand_left_h=zeros_f.at[0].set(root_best.left_sum_hess),
            cand_right_g=zeros_f.at[0].set(root_best.right_sum_grad),
            cand_right_h=zeros_f.at[0].set(root_best.right_sum_hess),
            leaf_depth=zeros_i.at[0].set(1),
            done=jnp.asarray(False),
        )

    def make_partition_branch(k: int):
        W = table[k]

        @phase_scope("partition")
        def branch(pane, side, start, cnt, feat, thr):
            # the one row of the parent's side that decides, over the
            # lanes the kernel will read; the range itself stays where
            # it lies
            cs, lanes = range_origin(pane, start, W)
            pfeat = feat if c2p_arr is None else c2p_arr[feat]
            fbin = jax.lax.dynamic_slice(
                pane, (side, pfeat, cs), (1, 1, lanes)).reshape(lanes)
            fbin = fbin.astype(jnp.int32) & 255   # int8 pane -> uint8 bin
            lane = cs + jnp.arange(lanes, dtype=jnp.int32)
            inseg = (lane >= start) & (lane < start + cnt)
            go_right = fbin > thr
            mask3 = jnp.where(inseg,
                              jnp.where(go_right, 0, 1), -1).astype(jnp.int8)
            plcnt = jnp.sum(inseg & ~go_right).astype(jnp.int32)
            pane2 = partition_segment(pane, mask3, side, start, cnt, plcnt,
                                      width=W,
                                      use_pallas=use_pallas_partition,
                                      overlap=partition_overlap,
                                      interpret=interpret)
            return pane2, plcnt

        return branch

    def make_hist_branch(k: int):
        W = table[k]

        @phase_scope("histogram")
        def branch(op):
            pane2, side2, sstart, scnt, salt = op
            cs2 = jnp.minimum(sstart, P - W)
            d2 = sstart - cs2
            # the two sides as one [2 * rows, lanes] array (a bitcast):
            # a two-dimensional slice fuses into the unpacking, where one
            # of [1, R, W] is cut out first and unpacked after
            stored = pane2.shape[1]
            hseg = jax.lax.dynamic_slice(
                pane2.reshape(2 * stored, pane2.shape[2]),
                (side2 * stored, cs2), (R, W))
            hbins, hg, hh, hvalid = unpack_values(hseg, F)
            lane2 = jnp.arange(W, dtype=jnp.int32)
            hmask = (lane2 >= d2) & (lane2 < d2 + scnt) & hvalid
            # a bucketed range is up to twice the leaf's rows wide: the
            # kernel may pass over the chunks outside [d2, d2 + scnt)
            return hist_of(hbins, hg, hh, hmask, salt=salt, skip_dead=True)

        return branch

    partition_branches = [make_partition_branch(k) for k in range(K)]
    hist_branches = [make_hist_branch(k) for k in range(K)]

    def body(_, state: _CompactState) -> _CompactState:
        with phase_scope("split_find"):
            best_leaf = jnp.argmax(state.cand_gain).astype(jnp.int32)
            best_gain = state.cand_gain[best_leaf]
            should_split = jnp.logical_and(~state.done, best_gain > 0.0)

        def do_split(state: _CompactState) -> _CompactState:
            tree = state.tree
            bl = best_leaf
            nl = tree.num_leaves
            with phase_scope("tree_pack"):
                node = nl - 1
            new_leaf = nl

            with phase_scope("split_find"):
                feat = state.cand_feature[bl]
                thr = state.cand_threshold[bl]
                start = state.seg_start[bl]
                cnt = state.seg_cnt[bl]

            # --- record the node (Tree::Split, tree.cpp:50-83)
            with phase_scope("tree_pack"):
                p = tree.leaf_parent[bl]
                pp = jnp.maximum(p, 0)
                lc_at_p = jnp.where(
                    (p >= 0) & (tree.left_child[pp] == ~bl),
                    node, tree.left_child[pp])
                rc_at_p = jnp.where(
                    (p >= 0) & (tree.right_child[pp] == ~bl),
                    node, tree.right_child[pp])
                left_child = (tree.left_child.at[pp].set(lc_at_p)
                              .at[node].set(~bl))
                right_child = (tree.right_child.at[pp].set(rc_at_p)
                               .at[node].set(~new_leaf))

            # --- original-order leaf ids (score updates need them; the
            # pane's permutation never leaves this function)
            with phase_scope("row_route"):
                ofeat = feat if c2p_arr is None else c2p_arr[feat]
                obin = jax.lax.dynamic_index_in_dim(
                    bins, ofeat, axis=0, keepdims=False).astype(jnp.int32)
                leaf_ids = jnp.where((tree.leaf_ids == bl) & (obin > thr),
                                     new_leaf, tree.leaf_ids)

            # --- partition the parent's lane range at ITS tier (local,
            # collective-free: shards may take different branches).  One
            # tier runs; each is a loop of its own so that the pane is
            # written in place (_run_if)
            with phase_scope("partition"):
                tier = state.seg_bucket[bl]
                # a leaf lies on the side its depth's parity names: the
                # root on side 0, a child on its parent's other side
                side = (state.leaf_depth[bl] - 1) & 1
                pane2, plcnt = state.pane, jnp.asarray(0, jnp.int32)
                for k, branch in enumerate(partition_branches):
                    pane2, plcnt = _run_if(
                        tier == k,
                        lambda c, branch=branch: branch(c[0], side, start,
                                                        cnt, feat, thr),
                        (pane2, plcnt))
                prcnt = cnt - plcnt

            # --- smaller-child histogram at the CHILD's own tier.  The
            # directly-built side is the VALID-smaller one, exactly like
            # the masked grower (same direct/subtracted f32 rounding);
            # its physical span picks the slice tier — pmax-synced across
            # shards so the collectives inside the branch line up
            with phase_scope("histogram"):
                lcnt = state.cand_left_cnt[bl]
                rcnt = state.cand_right_cnt[bl]
                left_small = lcnt <= rcnt
                scnt = jnp.where(left_small, plcnt, prcnt)
                sstart = jnp.where(left_small, start, start + plcnt)
                hk_span = scnt
                if s.hist_axis is not None:
                    # tier-selector sync: a scalar pmax per split — tiny
                    # on the wire but a full collective latency, so it
                    # belongs in the interconnect inventory
                    _tl.record_collective(
                        "leafcompact/tier_pmax", "pmax", s.hist_axis,
                        _tl._tree_nbytes(hk_span), loop=L - 1, phase="grow")
                    hk_span = jax.lax.pmax(hk_span, s.hist_axis)
                small_hist = jax.lax.switch(
                    bucket_of(hk_span), hist_branches,
                    (pane2, 1 - side, sstart, scnt, new_leaf))

                # the parent's row in a buffer of its own before the
                # cache is written: fused into the children's writes it
                # is a read of the cache at another row than the one
                # written, which XLA does not do in place (it copied the
                # whole cache, twice a split)
                parent_hist = jax.lax.optimization_barrier(
                    state.hist_cache[bl])
                large_hist = parent_hist - small_hist
                lhist = jnp.where(left_small, small_hist, large_hist)
                rhist = jnp.where(left_small, large_hist, small_hist)

            with phase_scope("split_find"):
                lg, lh = state.cand_left_g[bl], state.cand_left_h[bl]
                rg, rh = state.cand_right_g[bl], state.cand_right_h[bl]
                lcf, rcf = lcnt.astype(f32), rcnt.astype(f32)
                depth = state.leaf_depth[bl] + 1

            # finder before the cache scatter: the packed-SplitInfo
            # allgather overlaps the HBM writeback (ISSUE 9 overlap seam;
            # pure program order, bit-identical values)
            lbest, rbest = best_of_pair(lhist, rhist, lg, lh, lcf, rg, rh,
                                        rcf, depth)
            with phase_scope("histogram"):
                hist_cache = (state.hist_cache.at[bl].set(lhist)
                              .at[new_leaf].set(rhist))

            with phase_scope("tree_pack"):
                tree = tree._replace(
                    num_leaves=nl + 1,
                    split_feature=tree.split_feature.at[node].set(feat),
                    threshold_bin=tree.threshold_bin.at[node].set(thr),
                    split_gain=tree.split_gain.at[node].set(best_gain),
                    left_child=left_child,
                    right_child=right_child,
                    leaf_parent=tree.leaf_parent.at[bl].set(node)
                                                .at[new_leaf].set(node),
                    leaf_value=tree.leaf_value
                                   .at[bl].set(state.cand_left_out[bl])
                                   .at[new_leaf]
                                   .set(state.cand_right_out[bl]),
                    leaf_count=tree.leaf_count.at[bl].set(lcnt)
                                              .at[new_leaf].set(rcnt),
                    leaf_ids=leaf_ids,
                )

            def put(arr, left, right):
                return arr.at[bl].set(left).at[new_leaf].set(right)

            with phase_scope("split_find"):
                return state._replace(
                    tree=tree,
                    pane=pane2,
                    seg_start=state.seg_start.at[new_leaf].set(
                        start + plcnt),
                    seg_cnt=put(state.seg_cnt, plcnt, prcnt),
                    seg_bucket=put(state.seg_bucket, bucket_of(plcnt),
                                   bucket_of(prcnt)),
                    hist_cache=hist_cache,
                    cand_gain=put(state.cand_gain, lbest.gain, rbest.gain),
                    cand_feature=put(state.cand_feature, lbest.feature,
                                     rbest.feature),
                    cand_threshold=put(state.cand_threshold,
                                       lbest.threshold, rbest.threshold),
                    cand_left_out=put(state.cand_left_out,
                                      lbest.left_output, rbest.left_output),
                    cand_right_out=put(state.cand_right_out,
                                       lbest.right_output,
                                       rbest.right_output),
                    cand_left_cnt=put(state.cand_left_cnt, lbest.left_count,
                                      rbest.left_count),
                    cand_right_cnt=put(state.cand_right_cnt,
                                       lbest.right_count, rbest.right_count),
                    cand_left_g=put(state.cand_left_g, lbest.left_sum_grad,
                                    rbest.left_sum_grad),
                    cand_left_h=put(state.cand_left_h, lbest.left_sum_hess,
                                    rbest.left_sum_hess),
                    cand_right_g=put(state.cand_right_g,
                                     lbest.right_sum_grad,
                                     rbest.right_sum_grad),
                    cand_right_h=put(state.cand_right_h,
                                     lbest.right_sum_hess,
                                     rbest.right_sum_hess),
                    leaf_depth=put(state.leaf_depth, depth, depth),
                )

        # profiler alignment (ISSUE 2): label the compacted split body so
        # profile_dir= traces group its partition/histogram ops per split
        with jax.named_scope("leafcompact_split"):
            state = _run_if(should_split, do_split, state)
        with phase_scope("split_find"):
            return state._replace(done=state.done | ~should_split)

    return jax.lax.fori_loop(0, L - 1, body, state).tree


# ======================================================= jitted wrappers

# module-level jits shared across boosters, wrapped in the cost registry
# (lightgbm_tpu/costmodel.py): with telemetry armed, the compiled
# program's cost_analysis/compile seconds feed the roofline/compile
# blocks.  One jitted entry per policy under the HISTORICAL instrument
# names, so recorded roofline/compile trajectories stay comparable.
from .. import costmodel as _costmodel  # noqa: E402 (after jax imports)

_JIT_STATICS = tuple(k for k in _GROW_STATICS if k != "policy")


def _grow_tree_leafwise_fn(bins, grad, hess, row_mask, feature_mask,
                           num_bins, **kwargs) -> TreeArrays:
    return grow_tree_unified(bins, grad, hess, row_mask, feature_mask,
                             num_bins, policy="leafwise", **kwargs)


def _grow_tree_depthwise_fn(bins, grad, hess, row_mask, feature_mask,
                            num_bins, **kwargs) -> TreeArrays:
    return grow_tree_unified(bins, grad, hess, row_mask, feature_mask,
                             num_bins, policy="depthwise", **kwargs)


def _grow_tree_leafcompact_fn(bins, grad, hess, row_mask, feature_mask,
                              num_bins, **kwargs) -> TreeArrays:
    return grow_tree_unified(bins, grad, hess, row_mask, feature_mask,
                             num_bins, policy="leafcompact", **kwargs)


grow_tree = _costmodel.instrument(
    "grow/leafwise",
    jax.jit(_grow_tree_leafwise_fn, static_argnames=_JIT_STATICS),
    phase="grow")
grow_tree_depthwise_jit = _costmodel.instrument(
    "grow/depthwise",
    jax.jit(_grow_tree_depthwise_fn, static_argnames=_JIT_STATICS),
    phase="grow")
grow_tree_leafcompact = _costmodel.instrument(
    "grow/leafcompact",
    jax.jit(_grow_tree_leafcompact_fn, static_argnames=_JIT_STATICS),
    phase="grow")
