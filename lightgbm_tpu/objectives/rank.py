"""Lambdarank (NDCG) objective.

Re-design of LightGBM's src/objective/rank_objective.hpp:19-230.  The
reference loops queries with OpenMP and docs in O(q²) nested loops; the TPU
formulation computes each query's whole pairwise lambda matrix as one dense
[L, L] block.  Queries are grouped by length into a few static classes
(``query_layout``): a class of width L holds every query of at most L
documents, each padded to L, and runs as a ``lax.map`` over query blocks of
a bounded working set.  So a heavy tail of long queries costs each query
its own class's L², not the longest query's.  The layout is built once,
from the query lengths alone: the labels and the scores never shape the
program, and the same layout is the same work.  A query's documents lie in
consecutive rows, so it moves between the rows and its class slot as one
window, read and added as the whole 128-lane chunks it touches, one index
a chunk, with its lanes shifted into place.  The 1M-entry sigmoid
lookup table (rank_objective.hpp:179-192) is replaced by computing the
sigmoid exactly — a table is a CPU trick, the VPU computes exp faster than
it gathers.

Math parity (rank_objective.hpp:76-164):
- pairs (high, low) sorted by score desc; only label(high) > label(low);
- ΔNDCG = (gain_hi − gain_lo)·|disc_hi − disc_lo|·inv_max_dcg, regularized by
  /(0.01+|Δscore|) when best ≠ worst score;
- λ = −σ(Δs)·ΔNDCG accumulated ± on (high, low); hessian 2·ΔNDCG·σ(2−σ).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import telemetry
from ..utils import log

# pair slots one query block holds: bounds the [block, L, L] float32
# working set of a class to ~64 MB an array
PAIR_BUDGET = 1 << 24
# length classes a layout may have: each is one more map in the program
MAX_CLASSES = 12
# class widths below one 128-lane tile are powers of two, above it whole
# tiles
TILE = 128


class QueryLayout(NamedTuple):
    """The static shape of the pairwise work: per class ``(width, blocks,
    block)``, ``blocks * block`` query slots of ``width`` documents each.
    ``pairs`` is Σ q² over the real queries, what any formulation has to
    compute; ``pair_slots`` what the classes compute; ``docs`` Σ q."""
    classes: Tuple[Tuple[int, int, int], ...]
    pairs: int
    docs: int

    @property
    def pair_slots(self) -> int:
        return sum(b * k * w * w for w, b, k in self.classes)


def _needed_width(sizes: np.ndarray) -> np.ndarray:
    """The narrowest class width each query fits: a power of two up to 64,
    else whole 128-lane tiles."""
    sizes = np.maximum(np.asarray(sizes, np.int64), 1)
    pow2 = np.left_shift(1, np.ceil(np.log2(sizes)).astype(np.int64))
    return np.where(sizes <= TILE // 2, pow2, -(-sizes // TILE) * TILE)


def _class_widths(needed: np.ndarray) -> np.ndarray:
    """At most ``MAX_CLASSES`` widths that cover every query with the
    fewest pair slots: each query goes to the narrowest chosen width it
    fits, and a dynamic program over the distinct needed widths picks them
    (the widest is always one)."""
    widths, counts = np.unique(needed, return_counts=True)
    m = widths.size
    cum = np.concatenate(([0], np.cumsum(counts)))
    sq = widths.astype(np.float64) ** 2
    # cost[k][j]: least slots for the queries of widths[:j+1], k+1 classes,
    # the widest at widths[j]
    inf = float("inf")
    cost = np.full((MAX_CLASSES, m), inf)
    back = np.zeros((MAX_CLASSES, m), np.int64)
    cost[0] = cum[1:] * sq
    for k in range(1, MAX_CLASSES):
        for j in range(k, m):
            prev = cost[k - 1, :j] + (cum[j + 1] - cum[1:j + 1]) * sq[j]
            i = int(np.argmin(prev))
            cost[k, j], back[k, j] = prev[i], i
    k = int(np.argmin(cost[:, m - 1]))
    chosen, j = [m - 1], m - 1
    while k > 0:
        j = int(back[k, j])
        chosen.append(j)
        k -= 1
    return widths[np.asarray(chosen[::-1])]


def _blocks(n: int, width: int) -> Tuple[int, int]:
    """(blocks, block): the fewest query blocks of at most PAIR_BUDGET
    slots that hold ``n`` queries, as even as whole queries allow."""
    most = max(1, PAIR_BUDGET // (width * width))
    blocks = -(-n // most)
    return blocks, -(-n // blocks)


def query_layout(sizes) -> QueryLayout:
    """The length classes of queries of ``sizes`` documents."""
    sizes = np.asarray(sizes, np.int64)
    needed = _needed_width(sizes)
    widths = _class_widths(needed)
    classes = []
    for w, n in zip(widths, np.bincount(np.searchsorted(widths, needed),
                                        minlength=widths.size)):
        if n:
            classes.append((int(w),) + _blocks(int(n), int(w)))
    return QueryLayout(tuple(classes), int(np.sum(sizes * sizes)),
                       int(np.sum(sizes)))


def inverse_max_dcg(label, boundaries, label_gain, k: int) -> np.ndarray:
    """1 / max DCG@k per query (rank_objective.hpp:53-63), 0 where the max
    is 0: every query at once, the gains sorted within their query."""
    label = np.asarray(label).astype(np.int64)
    sizes = np.diff(np.asarray(boundaries, np.int64))
    qid = np.repeat(np.arange(sizes.size), sizes)
    gain = np.asarray(label_gain, np.float64)[label]
    order = np.lexsort((-gain, qid))
    rank = np.arange(label.size) - np.repeat(boundaries[:-1], sizes)
    top = rank < k
    discount = 1.0 / np.log2(2.0 + rank[top])
    max_dcg = np.bincount(qid[top], weights=gain[order][top] * discount,
                          minlength=sizes.size)
    with np.errstate(divide="ignore"):
        return np.where(max_dcg > 0, 1.0 / max_dcg, max_dcg).astype(
            np.float32)


class LambdarankNDCG:
    # per-query tables index the GLOBAL score vector, so the params are
    # not row-shardable — instead the data-parallel learner gathers the
    # score shards and computes the pairwise lambdas replicated, then
    # slices each shard's rows back out (needs_global_score protocol).
    # The reference distributes this per machine over its own queries
    # (rank_objective.hpp:68-192 under dataset.cpp:189-206 query-atomic
    # sharding); the replicated formulation trades S-fold redundant
    # O(sum q^2) VPU work — a small term next to histogram building — for
    # zero extra collectives beyond the all_gather the in-program metrics
    # already pay, and stays correct even when device-level row blocks cut
    # queries mid-way (only PROCESS shards are query-atomic).
    rows_aligned_params = False
    needs_global_score = True

    def __init__(self, config):
        self._sigmoid = float(config.sigmoid)
        if self._sigmoid <= 0.0:
            log.fatal("sigmoid param %f should greater than zero" % self._sigmoid)
        self.label_gain = np.asarray(config.label_gain, dtype=np.float32)
        self.optimize_pos_at = int(config.max_position)
        self.weights = None

    def init(self, metadata, num_data: int, rows=None) -> None:
        """``rows``: where document c of the metadata lives in the score
        vector (``num_data`` long), if not at c.  Each query's documents
        have to lie in consecutive rows: a query moves between the rows and
        its class slot as one window."""
        if metadata.query_boundaries is None:
            log.fatal("For lambdarank tasks, should have query information")
        label = np.asarray(metadata.label)
        boundaries = np.asarray(metadata.query_boundaries, np.int64)
        sizes = np.diff(boundaries)
        if rows is None:
            rows = np.arange(label.size, dtype=np.int64)
        # the row of each query's first document, and every other document
        # right after it
        first = rows[np.minimum(boundaries[:-1], max(rows.size - 1, 0))]
        if not np.array_equal(rows, np.repeat(first, sizes) + (
                np.arange(rows.size) - np.repeat(boundaries[:-1], sizes))):
            log.fatal("lambdarank needs each query's documents in "
                      "consecutive rows")
        self.num_data = num_data
        self.layout = query_layout(sizes)
        inv_max_dcg = inverse_max_dcg(label, boundaries, self.label_gain,
                                      self.optimize_pos_at)

        # each class: its queries in file order, padded to whole blocks by
        # empty queries whose window lies past the last row
        needed = _needed_width(sizes)
        widths = np.asarray([w for w, _, _ in self.layout.classes])
        of_class = np.searchsorted(widths, needed)
        gain = self.label_gain[label.astype(np.int64)]
        tables = []
        for c, (w, blocks, block) in enumerate(self.layout.classes):
            qs = np.flatnonzero(of_class == c)
            slots = blocks * block
            cnt = np.zeros(slots, np.int64)
            cnt[:qs.size] = sizes[qs]
            row = np.full(slots, num_data, np.int64)
            row[:qs.size] = first[qs]
            start = np.zeros(slots, np.int64)
            start[:qs.size] = boundaries[qs]
            valid = np.arange(w)[None, :] < cnt[:, None]
            doc = np.where(valid, start[:, None] + np.arange(w)[None, :], 0)
            imd = np.zeros(slots, np.float32)
            imd[:qs.size] = inv_max_dcg[qs]
            shape = (blocks, block, w)
            tables.append({
                "row": jnp.asarray(row.astype(np.int32).reshape(
                    blocks, block)),
                "count": jnp.asarray(cnt.astype(np.int32).reshape(
                    blocks, block)),
                "label": jnp.asarray(np.where(valid, label[doc], 0).astype(
                    np.int32).reshape(shape)),
                "gain": jnp.asarray(np.where(valid, gain[doc], 0.0).astype(
                    np.float32).reshape(shape)),
                "inv_max_dcg": jnp.asarray(imd.reshape(blocks, block))})
        self.tables = tuple(tables)
        self.discount = jnp.asarray(
            (1.0 / np.log2(2.0 + np.arange(max(widths)))).astype(np.float32))
        if metadata.weights is not None:
            w = np.zeros(num_data, np.float32)
            w[rows] = np.asarray(metadata.weights, np.float32)
            self.weights = jnp.asarray(w)

    def get_gradients(self, score: jax.Array):
        _, params, fn = self.chunk_spec()
        return _iteration_program(fn)(params, score)

    def globalize_layout(self, global_md, shard_layout,
                         num_padded: int) -> None:
        """Multi-process data parallel: rebuild the per-query tables over
        the GLOBAL rows in the padded-global coordinate system.

        ``global_md`` is the all-process metadata (Metadata.global_view:
        labels/query layout concatenated in process order — valid because
        row sharding is query-atomic, dataset.cpp:189-206);
        ``shard_layout`` maps compacted global row c of process p to padded
        position start_p + (c - c_p).  The tables then index the padded
        global score directly, and weights scatter into a padded vector so
        the lambda products line up."""
        pad_pos = np.concatenate(
            [start + np.arange(ln) for start, ln in shard_layout]
        ).astype(np.int64)
        self.init(global_md, num_padded, rows=pad_pos)

    def chunk_spec(self):
        # the layout is static (it shapes every class's block map); the
        # output length follows the score length at trace time, so one
        # callable serves both the true-row and shard-padded layouts
        key = ("lambdarank", self.num_data, self.layout,
               self.weights is not None)
        fn = _RANK_FNS.setdefault(key, functools.partial(
            _rank_gradients, layout=self.layout))
        return key, self.chunk_params(), fn

    def chunk_params(self):
        return {"tables": self.tables, "discount": self.discount,
                "sigmoid": jnp.float32(self._sigmoid),
                "weights": self.weights}

    @property
    def sigmoid(self) -> float:
        # ranking scores are used raw at predict time (rank_objective.hpp:194-199)
        return -1.0

    @property
    def num_class(self) -> int:
        return 1


# one callable per static key so the chunk trainer's program cache can use
# function identity (a fresh functools.partial per call would defeat it)
_RANK_FNS: dict = {}


@functools.lru_cache(maxsize=None)
def _iteration_program(fn):
    """The per-iteration path's gradient program: ``fn`` under the
    ``gradient`` phase, as the chunk body puts it."""
    def program(params, score):
        with telemetry.phase_scope("gradient"):
            return fn(params, score)
    return jax.jit(program)


def _rank_gradients(params, score, *, layout: QueryLayout):
    # nested inside the "gradient" phase: a device trace reads the
    # pairwise work by this name
    with jax.named_scope("gradient_lambdarank"):
        # trace-time counters, once a program: the real pairs and the
        # slots the classes compute for them
        telemetry.count("rank/pairs", layout.pairs)
        telemetry.count("rank/pair_slots", layout.pair_slots)
        telemetry.count("rank/classes", len(layout.classes))
        # documents moved by whole-query windows: rows to class slots and
        # back
        telemetry.count("rank/segment_docs", 2 * layout.docs)
        rows = score.shape[0]
        # rows as [chunks, 128] lanes: the chunks of a window of the widest
        # class from any query's first row, or from an empty query's at the
        # last row, lie inside them
        widest = max(w for w, _, _ in layout.classes)
        chunks = rows // TILE + -(-widest // TILE) + 1
        score = jnp.pad(score.astype(jnp.float32),
                        (0, chunks * TILE - rows)).reshape(chunks, TILE)
        moved = jnp.zeros((2, chunks, TILE), jnp.float32)
        for table in params["tables"]:
            pair = jax.lax.map(
                functools.partial(_query_block, score=score,
                                  discount=params["discount"],
                                  sigmoid=params["sigmoid"]),
                table)
            # each query's lambdas and hessians added over the chunks of
            # its rows; they overlap the next queries' rows by zeros only
            moved = _add_windows(moved, table["row"], pair)
        lambdas = moved[0].reshape(-1)[:rows]
        hessians = moved[1].reshape(-1)[:rows]
        if params["weights"] is not None:
            # single-process DP pads rows at the tail: they carry no
            # document
            weights = params["weights"]
            weights = jnp.pad(weights, (0, rows - weights.shape[0]))
            lambdas = lambdas * weights
            hessians = hessians * weights
        return lambdas, hessians


def _query_block(table, *, score, discount, sigmoid):
    """Pairwise lambdas and hessians of one block of ``[block, L]`` padded
    queries (rank_objective.hpp:76-156), each query's scores read as one
    window of its rows out of ``score`` laid out ``[chunks, 128]``, and its
    results given back as the whole chunks of those rows,
    ``[2, block, m + 1, 128]``, 0 past its documents."""
    count, label, gain = table["count"], table["label"], table["gain"]
    width = label.shape[-1]
    pos = jnp.arange(width, dtype=jnp.int32)
    valid = pos[None, :] < count[:, None]
    s = jnp.where(valid, _read_windows(score, table["row"], width), -jnp.inf)
    # score desc, stable; padding (-inf) sinks last
    neg, ll, gg, src = jax.lax.sort(
        (-s, label, gain, jnp.broadcast_to(pos, s.shape)), dimension=1,
        is_stable=True, num_keys=1)
    ss = jnp.where(valid, -neg, 0.0)      # valid sorts first: same mask
    best = ss[:, 0]
    # the last valid score, as the least: no gather
    worst = jnp.min(jnp.where(valid, ss, jnp.inf), axis=1)

    hi_s, lo_s = ss[:, :, None], ss[:, None, :]
    hi_l, lo_l = ll[:, :, None], ll[:, None, :]
    pair = (hi_l > lo_l) & valid[:, :, None] & valid[:, None, :]
    delta = hi_s - lo_s
    disc = discount[:width]
    paired_disc = jnp.abs(disc[:, None] - disc[None, :])
    delta_ndcg = ((gg[:, :, None] - gg[:, None, :]) * paired_disc
                  * table["inv_max_dcg"][:, None, None])
    delta_ndcg = jnp.where((hi_l != lo_l) & (best != worst)[:, None, None],
                           delta_ndcg / (0.01 + jnp.abs(delta)), delta_ndcg)
    sig = 2.0 / (1.0 + jnp.exp(2.0 * delta * sigmoid))
    p_hess = sig * (2.0 - sig)
    lam = jnp.where(pair, -sig * delta_ndcg, 0.0)
    hes = jnp.where(pair, 2.0 * delta_ndcg * p_hess, 0.0)

    lam_sorted = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
    hes_sorted = jnp.sum(hes, axis=2) + jnp.sum(hes, axis=1)
    # back to each query's document order
    _, lam_out, hes_out = jax.lax.sort((src, lam_sorted, hes_sorted),
                                       dimension=1, num_keys=1)
    return _chunk_windows(jnp.stack([lam_out, hes_out]), table["row"])


def _rotate_lanes(x, by, left: bool):
    """Each 128-lane row of ``x`` ``[..., 128]`` rotated by ``by`` lanes
    (shaped as ``x`` less its last axis), in seven static rotations of a
    power of two, each taken or not."""
    for bit in range(7):
        step = 1 << bit
        x = jnp.where(((by >> bit) & 1)[..., None] == 1,
                      jnp.roll(x, -step if left else step, axis=-1), x)
    return x


def _read_windows(score, row, width: int):
    """``[..., width]``: the scores of rows ``row .. row + width - 1`` out
    of ``score`` laid out ``[chunks, 128]``: the whole chunks the window
    touches, one index a chunk, each rotated left by the row's lane in its
    chunk and joined to the next at that lane."""
    m = -(-width // TILE)
    lane = row % TILE
    x = score[row[..., None] // TILE + jnp.arange(m + 1)]   # [..., m+1, 128]
    x = _rotate_lanes(x, lane[..., None], left=True)
    x = jnp.where(jnp.arange(TILE) < TILE - lane[..., None, None],
                  x[..., :-1, :], x[..., 1:, :])
    return x.reshape(row.shape + (m * TILE,))[..., :width]


def _chunk_windows(pair, row):
    """``[2, block, m + 1, 128]``: each query's ``[2, width]`` window of
    ``pair`` ``[2, block, width]`` as the whole chunks of the rows ``row ..``
    it lies on: cut into 128-lane chunks, each rotated right by the row's
    lane in its chunk and split at that lane between its own chunk and the
    next; 0 elsewhere."""
    width = pair.shape[-1]
    m = -(-width // TILE)
    lane = (row % TILE)[None, :, None, None]
    x = jnp.pad(pair, ((0, 0), (0, 0), (0, m * TILE - width)))
    x = _rotate_lanes(x.reshape(pair.shape[:2] + (m, TILE)), lane[..., 0],
                      left=False)
    x = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0)))
    return jnp.where(jnp.arange(TILE) >= lane, x[..., 1:, :], x[..., :-1, :])


def _add_windows(moved, row, chunks):
    """``moved`` ``[2, chunks, 128]`` plus each query's chunks of
    ``chunks`` ``[blocks, 2, block, m + 1, 128]``, from the chunk of its
    first row ``row`` ``[blocks, block]`` on, one index a chunk."""
    index = (row // TILE)[..., None] + jnp.arange(chunks.shape[-2])
    return jax.lax.scatter_add(
        moved, index[..., None], chunks,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 4), inserted_window_dims=(1,),
            scatter_dims_to_operand_dims=(1,)))
