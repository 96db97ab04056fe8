"""Best-split search over histograms.

Vectorized re-design of FeatureHistogram::FindBestThreshold
(/root/reference/src/treelearner/feature_histogram.hpp:106-165): the
right-to-left scan becomes a cumulative sum over the bin axis plus a masked
argmax — embarrassingly parallel over features × thresholds on the VPU.

Parity-critical semantics preserved:
- threshold t means "bin <= t goes left"; candidate thresholds are
  0 .. num_bin-2 (the reference scans t = num_bins-1 .. 1 and stores t-1).
- kEpsilon hessian padding: the leaf total gets +2ε, each side +ε
  (feature_histogram.hpp:53, 113, 128).
- constraints: both sides need >= min_data_in_leaf rows and
  >= min_sum_hessian_in_leaf hessian mass (lines 123-131).
- a candidate must reach gain >= gain_shift (line 137); reported gain is
  ``best_gain - gain_shift`` (line 164).
- tie-breaks: within a feature the LARGER threshold wins (right-to-left scan
  updates only on strictly-greater, line 143); across features the SMALLER
  feature index wins (split_info.hpp:98-103).
- split gain g²/h, leaf output −g/h (lines 219-231; no L1/L2 terms in this
  reference snapshot).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import telemetry

K_EPSILON = 1e-15  # meta.h kEpsilon
NEG_INF = -jnp.inf


class SplitResult(NamedTuple):
    """Best split across features for one leaf (SplitInfo,
    split_info.hpp:17-54)."""
    gain: jax.Array          # f32 scalar; -inf when unsplittable
    feature: jax.Array       # i32 inner feature index
    threshold: jax.Array     # i32 bin threshold
    left_output: jax.Array   # f32
    right_output: jax.Array
    left_count: jax.Array    # i32; None from a histogram without counts
    right_count: jax.Array
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array  # raw (no epsilon)
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array


def find_best_split(hist: jax.Array, sum_grad: jax.Array, sum_hess: jax.Array,
                    num_data: jax.Array, num_bins: jax.Array,
                    feature_mask: jax.Array, min_data_in_leaf: float,
                    min_sum_hessian_in_leaf: float) -> SplitResult:
    """Find the best split over all features of one leaf.

    Parameters
    ----------
    hist : [F, B, 3] float32 (sum_grad, sum_hess, count), or [F, B, 2]
        (sum_grad, sum_hess): the search without counts, for
        ``min_data_in_leaf <= 1`` beside ``min_sum_hessian_in_leaf >
        K_EPSILON`` (the int8 level-wise routes).  ``num_data`` is then
        ``occupied``, [F, B] bool: the bins that hold a row of nonzero
        hessian code, known exactly from the integer sums.  A side with
        no such bin is refused, as the count test refuses an empty side
        and the hessian test one whose rows sum to 0: its float32 sums,
        the leaf's total less the other side's, are a rounding residue
        that could pass a small floor.  The result's counts are None
    sum_grad, sum_hess, num_data : leaf totals (raw, no epsilon)
    num_bins : [F] int32 — real bin count per feature (B is padded)
    feature_mask : [F] bool — feature_fraction sampling / ownership masks

    Mixed-bin invariant (ISSUE 6): under feature packing the histogram
    routes hand back CANONICAL feature order with narrow-class features
    zero-padded from their class width up to B — exactly the zeros the
    uniform pass puts there (no row carries a bin >= the feature's own
    num_bin), and the ``thresholds <= num_bins - 2`` validity mask below
    never admits the padding as a candidate.  This function therefore
    needs no packing awareness, and the across-feature argmax tie-break
    (smaller CANONICAL index wins) is identical packed or not.
    """
    with telemetry.span("split_find") as sp:
        return sp.fence(_find_best_split_impl(
            hist, sum_grad, sum_hess, num_data, num_bins, feature_mask,
            min_data_in_leaf, min_sum_hessian_in_leaf))


def _find_best_split_impl(hist, sum_grad, sum_hess, num_data, num_bins,
                          feature_mask, min_data_in_leaf,
                          min_sum_hessian_in_leaf) -> SplitResult:
    # unconditional named_scope: profile_dir= traces label these ops
    # "split_find" — the same key as the telemetry span/JSONL records —
    # with or without telemetry armed (ISSUE 2 profiler alignment)
    with jax.named_scope("split_find"):
        return _find_best_split_scoped(
            hist, sum_grad, sum_hess, num_data, num_bins, feature_mask,
            min_data_in_leaf, min_sum_hessian_in_leaf)


def _threshold_scan(hist, sum_grad, sum_hess, num_data, num_bins,
                    feature_mask, min_data_in_leaf,
                    min_sum_hessian_in_leaf):
    """Shared [F, B] threshold scan: cumulative left sums, the validity
    mask and the per-candidate gain score — the common core of the full
    best-split search and the voting learner's per-feature local gains.
    Returns (cg, ch, cc, score, gain_shift); cc is None for a histogram
    without counts, whose ``num_data`` is its bins' occupancy
    (``find_best_split``)."""
    F, B, stats = hist.shape
    counted = stats == 3
    eps = jnp.float32(K_EPSILON)

    cg = jnp.cumsum(hist[:, :, 0], axis=1)   # [F, B] left sums at threshold t
    ch = jnp.cumsum(hist[:, :, 1], axis=1)
    cc = jnp.cumsum(hist[:, :, 2], axis=1) if counted else None

    total_g = sum_grad.astype(jnp.float32)
    total_h = sum_hess.astype(jnp.float32)
    total_c = num_data.astype(jnp.float32) if counted else None

    # per threshold t (bin <= t left):
    left_g = cg
    left_h = ch + eps                        # raw_left + ε
    left_c = cc
    right_g = total_g - cg
    right_h = (total_h - ch) + eps           # raw_right + ε
    right_c = total_c - cc if counted else None

    thresholds = jnp.arange(B, dtype=jnp.int32)
    if counted:
        valid = ((right_c >= min_data_in_leaf)
                 & (left_c >= min_data_in_leaf)
                 & (right_h >= min_sum_hessian_in_leaf))
    else:
        # the first and last bins a row of nonzero hessian code sits in:
        # threshold t leaves one on each side where first <= t < last
        occupied = num_data
        first = jnp.min(jnp.where(occupied, thresholds, B), axis=1)
        last = jnp.max(jnp.where(occupied, thresholds, -1), axis=1)
        valid = ((thresholds[None, :] >= first[:, None])
                 & (thresholds[None, :] < last[:, None])
                 & (right_h >= min_sum_hessian_in_leaf))
    valid = (
        valid
        & (left_h >= min_sum_hessian_in_leaf)
        & (thresholds[None, :] <= (num_bins[:, None] - 2))
        & feature_mask[:, None]
    )

    gain_shift = _leaf_split_gain(total_g, total_h + 2 * eps)
    current_gain = (_leaf_split_gain(left_g, left_h)
                    + _leaf_split_gain(right_g, right_h))
    valid = valid & (current_gain >= gain_shift)
    score = jnp.where(valid, current_gain, NEG_INF)
    return cg, ch, cc, score, gain_shift


def per_feature_best_scores(hist, sum_grad, sum_hess, num_data, num_bins,
                            feature_mask, min_data_in_leaf,
                            min_sum_hessian_in_leaf) -> jax.Array:
    """[F] best (unshifted) split score per feature, -inf when a feature
    has no valid candidate — the voting learner's LOCAL gain vector
    (ISSUE 9; PV-tree / the reference's absent voting_parallel design):
    each shard proposes its top-k features by this score, and only the
    globally-voted features' histograms are exchanged."""
    _, _, _, score, _ = _threshold_scan(
        hist, sum_grad, sum_hess, num_data, num_bins, feature_mask,
        min_data_in_leaf, min_sum_hessian_in_leaf)
    return jnp.max(score, axis=1)


def _find_best_split_scoped(hist, sum_grad, sum_hess, num_data, num_bins,
                            feature_mask, min_data_in_leaf,
                            min_sum_hessian_in_leaf) -> SplitResult:
    F, B, stats = hist.shape
    counted = stats == 3
    eps = jnp.float32(K_EPSILON)
    total_g = sum_grad.astype(jnp.float32)
    total_h = sum_hess.astype(jnp.float32)
    total_c = num_data.astype(jnp.float32) if counted else None

    cg, ch, cc, score, gain_shift = _threshold_scan(
        hist, sum_grad, sum_hess, num_data, num_bins, feature_mask,
        min_data_in_leaf, min_sum_hessian_in_leaf)

    # within-feature argmax, larger threshold wins ties → argmax on the
    # reversed threshold axis
    rev = score[:, ::-1]
    best_t_rev = jnp.argmax(rev, axis=1)
    best_t = (B - 1) - best_t_rev                    # [F]
    best_score = jnp.take_along_axis(score, best_t[:, None], axis=1)[:, 0]

    # across features: smaller feature index wins ties (jnp.argmax returns
    # the first maximum)
    best_f = jnp.argmax(best_score).astype(jnp.int32)
    gain_raw = best_score[best_f]
    t = best_t[best_f].astype(jnp.int32)

    lg = cg[best_f, t]
    lh_raw = ch[best_f, t]
    lc = cc[best_f, t] if counted else None
    rg = total_g - lg
    rh_raw = total_h - lh_raw
    rc = total_c - lc if counted else None

    return SplitResult(
        gain=jnp.where(jnp.isfinite(gain_raw), gain_raw - gain_shift, NEG_INF),
        feature=best_f,
        threshold=t,
        left_output=_leaf_output(lg, lh_raw + eps),
        right_output=_leaf_output(rg, rh_raw + eps),
        left_count=lc.astype(jnp.int32) if counted else None,
        right_count=rc.astype(jnp.int32) if counted else None,
        left_sum_grad=lg,
        left_sum_hess=lh_raw,
        right_sum_grad=rg,
        right_sum_hess=rh_raw,
    )


def _leaf_split_gain(g, h):
    """g²/h (feature_histogram.hpp:219-221)."""
    return (g * g) / h


def _leaf_output(g, h):
    """−g/h (feature_histogram.hpp:229-231)."""
    return -g / h
