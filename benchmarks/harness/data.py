"""Seeded Higgs-shaped table: every column continuous, binary label.

Copied from ``chip_smoke.make_table`` / ``bench.make_data(narrow_features=0)``
(the originals stay where they are; see PERF.md, Open questions) and changed
in two ways.  Rows are drawn in blocks, each block from its own stream of
the seed, a few blocks at a time on threads (NumPy's generators release the
interpreter lock), so the table costs seconds and not half a minute.  And
the table comes back column-major in float64, the layout and type
``Dataset.from_arrays`` converts to anyway: its per-column search then
reads contiguous memory and its conversion is no copy.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 19
THREADS = 8


def _stream(seed: int, index: int) -> np.random.Generator:
    # SeedSequence takes any non-negative integer, also one past 2**31
    return np.random.Generator(
        np.random.SFC64(np.random.SeedSequence([int(seed), int(index)])))


def make_table(rows: int, features: int, seed: int):
    """(x [rows, features] float64 column-major holding float32 values,
    y [rows] float32 in {0, 1})."""
    w = (_stream(seed, 0).standard_normal(features)
         / np.sqrt(features)).astype(np.float32)
    x = np.empty((rows, features), np.float64, order="F")
    y = np.empty((rows,), np.float32)

    def block(b: int) -> None:
        start = b * BLOCK_ROWS
        stop = min(start + BLOCK_ROWS, rows)
        rng = _stream(seed, b + 1)
        xb = rng.standard_normal((features, stop - start), dtype=np.float32)
        logits = (w @ xb + 0.5 * np.sin(xb[0] * 2.0) + 0.3 * xb[1] * xb[2])
        noise = rng.standard_normal(stop - start, dtype=np.float32)
        x[start:stop].T[...] = xb
        y[start:stop] = (logits + 0.5 * noise > 0)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(block, range(-(-rows // BLOCK_ROWS))))
    return x, y
