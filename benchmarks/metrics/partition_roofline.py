"""``partition_roofline``: the least time the chip could take to partition
one tree's rows over ``partition_ms_per_iter``.

The work is the algorithm's, counted from shapes, whatever implements it
(as ``harness/roofline.py`` counts the histogram's): a leaf-wise grower that
keeps every leaf's rows together moves, at each of ceil(log2(num_leaves))
levels, every row's ``features + 9`` pane bytes (bin codes, the gradient
pair's 8 bytes, the validity byte) once: read and written.  Memory binds;
the selection matmuls are an implementation's and are not counted."""
from __future__ import annotations

import math


def read(state):
    base = state.values.get("partition_ms_per_iter")
    if not base or not state.peaks:
        return None
    shape = state.shape
    levels = max(1, math.ceil(math.log2(max(shape["num_leaves"], 2))))
    moved = 2 * levels * shape["rows"] * (shape["features"] + 9)
    return 100.0 * (moved / state.peaks["hbm_bytes_per_s"]) / (base * 1e-3)
