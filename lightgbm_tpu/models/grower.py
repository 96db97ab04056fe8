"""Leaf-wise grower — compat shim over ``models/grower_unified.py``.

The three grower modules were collapsed into ONE schedule-parameterized
grower (ISSUE 9): growth policy (leafwise/depthwise/leafcompact) and a
declarative :class:`~.grower_unified.SeamSchedule` are parameters there;
this module keeps the historical leaf-wise entry points (``grow_tree``,
``grow_tree_impl`` with keyword seams) plus the patchable
``build_histogram`` attribute, and nothing else — the graftlint AST pass
(ISSUE 10) proved the old ``BIG``/``TreeArrays``/``_GrowState``
re-exports unreferenced outside ``grower_unified`` itself, and
tests/test_graftlint.py pins this surface so dead exports cannot regrow.
New code should import from ``grower_unified`` directly.
"""
from __future__ import annotations

import jax.numpy as jnp

# patchable histogram seam: tests/scripts monkeypatch THIS attribute
# (the unified grower resolves it through this module at trace time)
from ..ops.histogram import build_histogram  # noqa: F401

from .grower_unified import (  # noqa: F401
    SeamSchedule, grow_tree, grow_tree_unified)


def grow_tree_impl(bins, grad, hess, row_mask, feature_mask, num_bins, *,
                   num_leaves: int, num_bins_max: int,
                   min_data_in_leaf: int, min_sum_hessian_in_leaf: float,
                   max_depth: int = -1, hist_backend: str = "matmul",
                   hist_chunk: int = 16384, compute_dtype=jnp.float32,
                   packing=None,
                   hist_reduce=None, hist_axis=None, int_hist_reduce=None,
                   split_finder=None, partition_bins=None,
                   stat_reduce=None, own_slice=None, root_hist_reduce=None):
    """Historical keyword-seam surface over
    ``grow_tree_unified(policy="leafwise")`` — the individual seam kwargs
    assemble into one SeamSchedule."""
    schedule = SeamSchedule(
        hist_axis=hist_axis, hist_reduce=hist_reduce,
        int_hist_reduce=int_hist_reduce, stat_reduce=stat_reduce,
        root_hist_reduce=root_hist_reduce, own_slice=own_slice,
        split_finder=split_finder)
    return grow_tree_unified(
        bins, grad, hess, row_mask, feature_mask, num_bins,
        policy="leafwise", num_leaves=num_leaves,
        num_bins_max=num_bins_max, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        max_depth=max_depth, hist_backend=hist_backend,
        hist_chunk=hist_chunk, compute_dtype=compute_dtype,
        packing=packing, schedule=schedule, partition_bins=partition_bins)
