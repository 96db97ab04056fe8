"""Plain reference for the gradients of lambdarank (NDCG).

NumPy and float64 only; imports nothing of the program.  One query at a
time, as rank_objective.hpp's ``GetGradientsForOneQuery`` of the snapshot
the repo was modelled on: the query's documents sorted by score, highest
first (ties keep document order); for every pair (high, low) with
label(high) > label(low),

    ΔNDCG = (gain(high) − gain(low)) · |disc(i) − disc(j)| / maxDCG@k

(disc(i) = 1 / log2(2 + i) at sorted position i), divided by
0.01 + |s(high) − s(low)| unless the query's best and worst scores are
equal; with σ(Δs) = 2 / (1 + exp(2 · sigmoid · Δs)) the pair adds
−σ·ΔNDCG to the high document's gradient and takes it from the low one's,
and adds 2·ΔNDCG·σ·(2 − σ) to both hessians.  The sigmoid is computed
exactly where the snapshot reads a table of it.  A query whose max DCG is 0
gets zeros.  Only the documents that can be the high one of a pair (a label
above the query's lowest) are walked against all the others.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = max(1, min(16, os.cpu_count() or 1))


def default_label_gain(n: int = 31) -> np.ndarray:
    """2^i − 1: LightGBM's default ``label_gain``."""
    return (np.left_shift(1, np.arange(n)) - 1).astype(np.float64)


def max_dcg(gains, k: int) -> float:
    """DCG@k of the query's gains in their best order."""
    top = np.sort(np.asarray(gains, np.float64))[::-1][:k]
    return float(np.sum(top / np.log2(2.0 + np.arange(top.size))))


def one_query(score, label, gain_table, k: int, sigmoid: float):
    """(grad, hess) of one query's documents, in their own order."""
    cnt = score.size
    grad = np.zeros(cnt, np.float64)
    hess = np.zeros(cnt, np.float64)
    best_dcg = max_dcg(gain_table[label], k)
    if cnt < 2 or best_dcg <= 0:
        return grad, hess
    order = np.argsort(-score, kind="stable")
    ss = score[order]
    ll = label[order]
    gg = gain_table[ll]
    disc = 1.0 / np.log2(2.0 + np.arange(cnt))
    high = np.flatnonzero(ll > ll.min())
    if high.size == 0:
        return grad, hess
    delta = ss[high, None] - ss[None, :]
    is_pair = ll[high, None] > ll[None, :]
    delta_ndcg = ((gg[high, None] - gg[None, :])
                  * np.abs(disc[high, None] - disc[None, :]) / best_dcg)
    if ss[0] != ss[cnt - 1]:
        delta_ndcg = delta_ndcg / (0.01 + np.abs(delta))
    with np.errstate(over="ignore"):
        sig = 2.0 / (1.0 + np.exp(2.0 * sigmoid * delta))
    lam = np.where(is_pair, -sig * delta_ndcg, 0.0)
    hes = np.where(is_pair, 2.0 * delta_ndcg * sig * (2.0 - sig), 0.0)
    lam_sorted = -lam.sum(axis=0)
    lam_sorted[high] += lam.sum(axis=1)
    hes_sorted = hes.sum(axis=0)
    hes_sorted[high] += hes.sum(axis=1)
    grad[order] = lam_sorted
    hess[order] = hes_sorted
    return grad, hess


def gradients(score, label, boundaries, label_gain=None, k: int = 20,
              sigmoid: float = 1.0):
    """(grad, hess) float64 of every document: ``score`` [N], ``label``
    [N] grades, ``boundaries`` [Q + 1] query starts.  Queries on threads
    (NumPy lets go of the interpreter inside a query's arrays)."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label).astype(np.int64)
    boundaries = np.asarray(boundaries, np.int64)
    gain_table = (default_label_gain() if label_gain is None
                  else np.asarray(label_gain, np.float64))
    grad = np.zeros(score.size, np.float64)
    hess = np.zeros(score.size, np.float64)

    def run(queries):
        for q in queries:
            lo, hi = boundaries[q], boundaries[q + 1]
            grad[lo:hi], hess[lo:hi] = one_query(
                score[lo:hi], label[lo:hi], gain_table, k, sigmoid)

    nq = boundaries.size - 1
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(run, [range(t, nq, THREADS) for t in range(THREADS)]))
    return grad, hess
