"""Seeded learning-to-rank table: queries of many documents, graded 0 - 4.

Two sources of randomness, kept apart so that every seed trains the same
ranking problem on fresh rows.

- The configuration's ``layout_key`` fixes the problem: the query lengths
  (log-normal about the configuration's mean, cut at its maximum, summing
  exactly to its rows), how many documents of each grade every query has
  (drawn from the configuration's grade mix), and the weights of the
  relevance function.  The pairwise work of lambdarank is a function of
  the query lengths alone, so it is the same in every run.
- ``--seed`` draws the rows: every feature value, and the noise added to
  the relevance.  Within each query the documents are ranked by noisy
  relevance and take the query's grades in that order, best first.

The table comes back as ``harness/data.py`` makes it: column-major float64
holding float32 values, drawn in blocks of rows on threads.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness.data import BLOCK_ROWS, THREADS, _stream


def query_lengths(rows: int, queries: int, sigma: float, longest: int,
                  key: int) -> np.ndarray:
    """[queries] int64 lengths summing to ``rows``: log-normal of shape
    ``sigma`` scaled to the mean rows / queries, each at least 1 and at
    most ``longest``."""
    z = _stream(key, 0).lognormal(0.0, sigma, queries)
    want = z * (rows / z.sum())
    sizes = np.clip(np.floor(want), 1, longest).astype(np.int64)
    # hand out what is left one document at a time, to the queries whose
    # share was cut most and that still have room (or take back from the
    # longest, should the floor of 1 have added)
    while sizes.sum() != rows:
        short = rows - int(sizes.sum())
        room = (sizes < longest) if short > 0 else (sizes > 1)
        order = np.argsort(-(want - sizes) * (1 if short > 0 else -1),
                           kind="stable")
        order = order[room[order]][:abs(short)]
        sizes[order] += 1 if short > 0 else -1
    return sizes


def grade_counts(sizes, mix, key: int) -> np.ndarray:
    """[queries, grades] documents of each grade a query has."""
    mix = np.asarray(mix, np.float64)
    return _stream(key, 1).multinomial(np.asarray(sizes, np.int64),
                                       mix / mix.sum())


def make_rank_table(conf: dict, seed: int, rows=None):
    """(x [rows, features] float64 column-major, label [rows] float32
    grades, boundaries [queries + 1] int64).  ``conf``: the
    configuration's ``rows``, ``features``, ``queries`` and ``layout``
    block; ``rows`` cuts the table for a rehearsal, the queries in
    proportion."""
    lay = conf["layout"]
    total = int(conf["rows"])
    n = int(rows or total)
    queries = max(1, round(int(conf["queries"]) * n / total))
    features = int(conf["features"])
    key = int(lay["layout_key"])
    sizes = query_lengths(n, queries, float(lay["length_sigma"]),
                          min(int(lay["longest"]), n), key)
    counts = grade_counts(sizes, lay["grade_mix"], key)
    boundaries = np.concatenate(([0], np.cumsum(sizes)))
    w = (_stream(key, 2).standard_normal(features)
         / np.sqrt(features)).astype(np.float32)
    x = np.empty((n, features), np.float64, order="F")
    rel = np.empty((n,), np.float32)

    def block(b: int) -> None:
        start = b * BLOCK_ROWS
        stop = min(start + BLOCK_ROWS, n)
        rng = _stream(seed, b + 1)
        xb = rng.standard_normal((features, stop - start), dtype=np.float32)
        noise = rng.standard_normal(stop - start, dtype=np.float32)
        x[start:stop].T[...] = xb
        rel[start:stop] = (w @ xb + 0.5 * np.sin(xb[0] * 2.0)
                           + 0.3 * xb[1] * xb[2] + 0.5 * noise)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(block, range(-(-n // BLOCK_ROWS))))
    # within each query, the most relevant documents take its best grades
    qid = np.repeat(np.arange(sizes.size), sizes)
    order = np.lexsort((-rel, qid))
    rank = np.arange(n) - np.repeat(boundaries[:-1], sizes)
    # a document's grade: how many of the query's grade thresholds (counts
    # of grade g and above, best first) its rank lies under
    above = np.cumsum(counts[:, ::-1], axis=1)[:, ::-1]      # [Q, G]
    grades = (rank[:, None] < above[qid][:, 1:]).sum(axis=1)
    label = np.empty(n, np.float32)
    label[order] = grades
    return x, label, boundaries
