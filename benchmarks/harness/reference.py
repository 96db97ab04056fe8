"""Plain reference for histogram gradient boosting of binary logloss.

NumPy and float64 only; imports nothing of the program.  It follows
LightGBM's published rules (feature_histogram.hpp of the snapshot the repo
was modelled on): response and hessian of the logistic loss with labels in
{-1, +1}; a split candidate "bin <= t goes left" for t = 0 .. num_bin-2 is
valid when both sides keep ``min_data_in_leaf`` rows and
``min_sum_hessian_in_leaf`` hessian; its score is GL^2/HL + GR^2/HR; a leaf's
output is -G/H times the learning rate.

The reference does not grow a tree of its own to compare structure with: two
sound growers part ways at the first near-tie and never meet again.  It is
*teacher forced*, as a served model's reference is run over the served
tokens: given the score before a tree and the tree the program answered
with, it routes every row through that tree, builds every node's exact
histogram from the per-leaf histograms, and reads

- how much of the attainable gain the program's splits missed,
- how far each leaf value lies from -G/H of the rows that reached it, as a
  share of the value (the median leaf is compared) and as a gradient sum
  (the widest leaf is compared).

It bins nothing with the program's mappers either: ``bin_code_gap`` holds
the program's codes against equal-count bins of the reference's own.

With ``lower`` (one of ``LOWER``) ``judge_tree`` gives the same two readings
for the answer a grower in the next lower precision would have given at the
same nodes (the control).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

K_EPSILON = 1e-15
THREADS = 8


class TreeAnswer:
    """One tree as the program answered it (arrays copied out of the
    program's host model; bin space, leaf values already shrunk)."""

    def __init__(self, num_leaves, split_feature, threshold_bin, left_child,
                 right_child, leaf_value):
        n = int(num_leaves)
        self.num_leaves = n
        self.split_feature = np.asarray(split_feature, np.int64)[:n - 1]
        self.threshold_bin = np.asarray(threshold_bin, np.int64)[:n - 1]
        self.left_child = np.asarray(left_child, np.int64)[:n - 1]
        self.right_child = np.asarray(right_child, np.int64)[:n - 1]
        self.leaf_value = np.asarray(leaf_value, np.float64)[:n]


def logloss_gradients(score, label01, sigmoid=1.0):
    """(grad, hess) of binary logloss, float64."""
    ls = np.where(np.asarray(label01) > 0.5, 1.0, -1.0)
    s = np.asarray(score, np.float64)
    response = -2.0 * ls * sigmoid / (1.0 + np.exp(2.0 * ls * sigmoid * s))
    a = np.abs(response)
    return response, a * (2.0 * sigmoid - a)


def _route_block(tree: TreeAnswer, bins, cols):
    n = len(cols)
    node = np.zeros(n, np.int32)
    live = np.arange(n, dtype=np.int32)
    feature = tree.split_feature.astype(np.int32)
    threshold = tree.threshold_bin.astype(np.int32)
    left = tree.left_child.astype(np.int32)
    right = tree.right_child.astype(np.int32)
    for _ in range(tree.num_leaves):
        if live.size == 0:
            return ~node
        k = node[live]
        b = bins[feature[k], cols[live]]
        nxt = np.where(b > threshold[k], right[k], left[k])
        node[live] = nxt
        live = live[nxt >= 0]
    raise ValueError("tree has a cycle")


def route(tree: TreeAnswer, bins, rows=None):
    """Leaf index of each row (of ``rows``, or of all): pointer walk from
    the root, ``bin <= threshold`` goes left.  Blocks of rows on threads."""
    n = bins.shape[1] if rows is None else len(rows)
    if tree.num_leaves <= 1:
        return np.zeros(n, np.int32)
    cols = np.arange(n, dtype=np.int64) if rows is None \
        else np.asarray(rows, np.int64)
    step = max(1 << 16, -(-n // THREADS))
    blocks = [cols[i:i + step] for i in range(0, n, step)]
    with ThreadPoolExecutor(THREADS) as pool:
        return np.concatenate(list(pool.map(
            lambda c: _route_block(tree, bins, c), blocks)))


def leaf_histograms(bins, leaf, num_leaves, num_bins_max, grad, hess):
    """[L, F, B, 3] float64 (sum grad, sum hess, rows) per leaf, feature and
    bin: one pass over the table."""
    F = bins.shape[0]
    B = int(num_bins_max)
    size = num_leaves * B
    base = leaf.astype(np.int64) * B

    def one(f):
        key = base + bins[f]
        return (np.bincount(key, weights=grad, minlength=size),
                np.bincount(key, weights=hess, minlength=size),
                np.bincount(key, minlength=size).astype(np.float64))

    out = np.empty((num_leaves, F, B, 3), np.float64)
    with ThreadPoolExecutor(THREADS) as pool:
        for f, (g, h, c) in enumerate(pool.map(one, range(F))):
            out[:, f, :, 0] = g.reshape(num_leaves, B)
            out[:, f, :, 1] = h.reshape(num_leaves, B)
            out[:, f, :, 2] = c.reshape(num_leaves, B)
    return out


def node_histograms(tree: TreeAnswer, leaf_hist):
    """[L-1, F, B, 3]: a node's histogram is the sum of its leaves'.  Node k
    was made by the k-th split, so its children have larger indices."""
    n_nodes = tree.num_leaves - 1
    out = np.zeros((n_nodes,) + leaf_hist.shape[1:], np.float64)
    for k in range(n_nodes - 1, -1, -1):
        for c in (tree.left_child[k], tree.right_child[k]):
            out[k] += leaf_hist[~c] if c < 0 else out[c]
    return out


def split_scores(node_hist, num_bins, min_data_in_leaf,
                 min_sum_hessian_in_leaf):
    """(score [K, F, B], parent [K]): GL^2/HL + GR^2/HR of every candidate,
    -inf where the candidate is not allowed, and G^2/H of the node."""
    B = node_hist.shape[2]
    left = np.cumsum(node_hist, axis=2)
    total = left[:, :1, -1:, :]                       # [K, 1, 1, 3]
    right = total - left
    lg, lh, lc = left[..., 0], left[..., 1] + K_EPSILON, left[..., 2]
    rg, rh, rc = right[..., 0], right[..., 1] + K_EPSILON, right[..., 2]
    t = np.arange(B)[None, None, :]
    ok = ((lc >= min_data_in_leaf) & (rc >= min_data_in_leaf)
          & (lh >= min_sum_hessian_in_leaf) & (rh >= min_sum_hessian_in_leaf)
          & (t <= np.asarray(num_bins)[None, :, None] - 2))
    with np.errstate(divide="ignore", invalid="ignore"):
        score = lg * lg / lh + rg * rg / rh
    score = np.where(ok, score, -np.inf)
    tg, th = total[:, 0, 0, 0], total[:, 0, 0, 1] + 2 * K_EPSILON
    return score, tg * tg / th


def _shortfall(score, parent, feature, threshold):
    """Per node: (attainable gain, gain missed by the split given)."""
    k = np.arange(score.shape[0])
    best = score.reshape(score.shape[0], -1).max(axis=1)
    attainable = np.maximum(best - parent, 0.0)
    got = score[k, feature, threshold]
    allowed = np.isfinite(got)
    missed = np.where(allowed, best - np.where(allowed, got, 0.0),
                      attainable)
    return attainable, np.minimum(np.maximum(missed, 0.0), attainable)


def leaf_value_gaps(values, want):
    """|value - want| over max(|want|, median |want|), per leaf."""
    floor = np.median(np.abs(want))
    return np.abs(values - want) / np.maximum(np.abs(want), floor)


def leaf_sum_gaps(values, want, hess_sums, learning_rate, grad):
    """Per leaf: the gradient sum that would explain ``value - want``, in
    units of the root of the sum of squared gradients (what a sum of the
    tree's gradients with random signs comes to).  A share of the value says
    little of a leaf of a hundred rows; this says how many rows' worth of
    gradient a leaf's sums are off by, whatever its size."""
    return (np.abs(values - want) * (hess_sums + K_EPSILON) / learning_rate
            / np.sqrt(np.sum(np.square(grad))))


def _leaf_outputs(leaf_hist, learning_rate):
    tot = leaf_hist[:, 0, :, :].sum(axis=1)           # [L, 3]
    return -tot[:, 0] / (tot[:, 1] + K_EPSILON) * learning_rate


def judge_tree(tree: TreeAnswer, bins, num_bins, grad, hess, params,
               lower=None, faults=False, leaf=None):
    """Readings of one tree against the rows and gradients it was grown
    from.  ``params``: min_data_in_leaf, min_sum_hessian_in_leaf,
    learning_rate.  ``lower``: a function (grad, hess) -> (grad, hess) in the
    next lower precision; with it the control's readings come back too,
    under ``control`` as the program's under ``program``.  ``faults``: also
    read the half-batch fault (leaf values from every other row, the mean
    taken over those).  ``leaf``: the rows' leaves, where the caller has
    routed them already."""
    B = int(np.max(num_bins))
    if leaf is None:
        leaf = route(tree, bins)
    lh = leaf_histograms(bins, leaf, tree.num_leaves, B, grad, hess)
    nh = node_histograms(tree, lh)
    score, parent = split_scores(nh, num_bins, params["min_data_in_leaf"],
                                 params["min_sum_hessian_in_leaf"])
    attainable, missed = _shortfall(score, parent, tree.split_feature,
                                    tree.threshold_bin)
    lr = params["learning_rate"]
    want = _leaf_outputs(lh, lr)
    hess_sums = lh[:, 0, :, 1].sum(axis=1)

    def leaves(values):
        return {"leaf_value_gaps": leaf_value_gaps(values, want),
                "leaf_sum_gaps": leaf_sum_gaps(values, want, hess_sums, lr,
                                               grad)}

    program = dict(leaves(tree.leaf_value), missed=float(missed.sum()))
    out = {"attainable": float(attainable.sum()), "leaf": leaf,
           "program": program,
           "detail": _detail(tree, lh, score, attainable, missed, want,
                             program)}
    if lower is not None:
        g2, h2 = lower(grad, hess)
        lh2 = leaf_histograms(bins, leaf, tree.num_leaves, B, g2, h2)
        score2, _ = split_scores(node_histograms(tree, lh2), num_bins,
                                 params["min_data_in_leaf"],
                                 params["min_sum_hessian_in_leaf"])
        flat = score2.reshape(score2.shape[0], -1).argmax(axis=1)
        _, missed2 = _shortfall(score, parent, flat // score2.shape[2],
                                flat % score2.shape[2])
        out["control"] = dict(leaves(_leaf_outputs(lh2, lr)),
                              missed=float(missed2.sum()))
    if faults:
        half = np.arange(leaf.shape[0]) % 2 == 0
        g = np.bincount(leaf[half], weights=grad[half],
                        minlength=tree.num_leaves)
        h = np.bincount(leaf[half], weights=hess[half],
                        minlength=tree.num_leaves)
        out["fault_half_batch"] = leaves(-g / (h + K_EPSILON) * lr)
    return out


def _detail(tree, leaf_hist, score, attainable, missed, want, program):
    """The three worst leaves and nodes, for whoever reads a failed run."""
    tot = leaf_hist[:, 0, :, :].sum(axis=1)
    gaps, sum_gaps = program["leaf_value_gaps"], program["leaf_sum_gaps"]
    lines = []
    for l in np.argsort(-sum_gaps)[:3]:
        lines.append("leaf %d rows=%d G=%.6g H=%.6g want=%.6g got=%.6g "
                     "gap=%.3g sum_gap=%.3g"
                     % (l, tot[l, 2], tot[l, 0], tot[l, 1], want[l],
                        tree.leaf_value[l], gaps[l], sum_gaps[l]))
    flat = score.reshape(score.shape[0], -1).argmax(axis=1)
    for k in np.argsort(-missed)[:3]:
        lines.append("node %d split=(f%d,t%d) best=(f%d,t%d) attainable=%.6g"
                     " missed=%.3g" % (k, tree.split_feature[k],
                                       tree.threshold_bin[k],
                                       flat[k] // score.shape[2],
                                       flat[k] % score.shape[2],
                                       attainable[k], missed[k]))
    return lines


def replay_sum(trees, bins, rows):
    """Sum of the trees' leaf values over ``rows``, float64."""
    total = np.zeros(len(rows), np.float64)
    for t in trees:
        total += t.leaf_value[route(t, bins, rows)]
    return total


def bin_code_gap(values, codes, max_bin):
    """Widest distance, in bins, between the program's code of a value and
    the reference's own.  ``values`` [n, F] floats as the generator made
    them, ``codes`` [F, n] what the program binned them to.  The reference's
    code is the value's rank among the n in its column, cut into ``max_bin``
    bins of equal count: what a quantile binning of a continuous column
    comes to, whoever finds the edges."""
    n = values.shape[0]
    worst = 0
    for f in range(values.shape[1]):
        rank = np.empty(n, np.int64)
        rank[np.argsort(values[:, f], kind="stable")] = np.arange(n)
        own = rank * int(max_bin) // n
        worst = max(worst, int(np.max(np.abs(
            np.asarray(codes[f], np.int64) - own))))
    return float(worst)


# ------------------------------------------------------- lower precisions

def to_bfloat16(grad, hess):
    """Round to nearest even bfloat16 (8 bits of mantissa), back in
    float64."""
    def rnd(x):
        u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
        u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
        return u.astype(np.uint32).view(np.float32).astype(np.float64)
    return rnd(grad), rnd(hess)


def to_int(bits):
    """Symmetric integer quantisation to ``bits`` bits with one scale per
    tree (max |x| over the rows maps to the largest code), round to
    nearest."""
    top = float((1 << (bits - 1)) - 1)

    def lower(grad, hess):
        out = []
        for x in (grad, hess):
            s = max(float(np.max(np.abs(x))), 1e-30) / top
            out.append(np.clip(np.rint(x / s), -top, top) * s)
        return tuple(out)
    return lower


LOWER = {"bfloat16": to_bfloat16, "int4": to_int(4)}
