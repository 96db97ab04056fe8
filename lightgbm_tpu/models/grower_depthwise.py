"""Depth-wise grower — compat shim over ``models/grower_unified.py``.

The three grower modules were collapsed into ONE schedule-parameterized
grower (ISSUE 9); this module keeps the historical depth-wise entry
points (``grow_tree_depthwise`` with keyword seams, the module-level
``grow_tree_depthwise_jit``, ``num_levels``) plus the patchable
``histogram_leafbatch`` attribute, and nothing else (graftlint-proved
surface, pinned by tests/test_graftlint.py).  New code should import
from ``grower_unified`` directly.
"""
from __future__ import annotations

import jax.numpy as jnp

# patchable histogram seam: tests monkeypatch THIS attribute (the
# unified grower resolves it through this module at trace time)
from ..ops.histogram import histogram_leafbatch  # noqa: F401

from .grower_unified import (  # noqa: F401
    SeamSchedule, grow_tree_depthwise_jit, grow_tree_unified, num_levels)


def grow_tree_depthwise(bins, grad, hess, row_mask, feature_mask,
                        num_bins, *, num_leaves: int, num_bins_max: int,
                        min_data_in_leaf: int,
                        min_sum_hessian_in_leaf: float, max_depth: int = -1,
                        hist_chunk: int = 65536, hist_reduce=None,
                        stat_reduce=None, split_finder=None,
                        partition_bins=None, hist_axis=None,
                        compute_dtype=jnp.float32, packing=None,
                        hist_reduce_level=None, int_reduce_level=None,
                        own_slice=None):
    """Historical keyword-seam surface over
    ``grow_tree_unified(policy="depthwise")``; returns a
    ``grower_unified.TreeArrays``."""
    schedule = SeamSchedule(
        hist_axis=hist_axis, hist_reduce=hist_reduce,
        stat_reduce=stat_reduce, own_slice=own_slice,
        split_finder=split_finder, hist_reduce_level=hist_reduce_level,
        int_reduce_level=int_reduce_level)
    return grow_tree_unified(
        bins, grad, hess, row_mask, feature_mask, num_bins,
        policy="depthwise", num_leaves=num_leaves,
        num_bins_max=num_bins_max, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        max_depth=max_depth, hist_chunk=hist_chunk,
        compute_dtype=compute_dtype, packing=packing, schedule=schedule,
        partition_bins=partition_bins)
