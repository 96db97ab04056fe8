"""Lambdarank over length classes (``objectives/rank.py``).

The bucketed lambdas and hessians against a plain NumPy float64 statement
of rank_objective.hpp (``benchmarks/harness/lambdarank.py``, which imports
nothing of the program), the layout's rules, the claim that the pairwise
work is a function of the query lengths alone, and the benchmark's ``rank``
traffic kind run through its harness at a cut size.

Tolerance: the program sums each document's pair terms in float32, up to a
class width of them, where the reference sums in float64; a float32
rounding is 6e-8 of the sum, so 1e-5 of the query set's largest |lambda|
leaves room for a few hundred terms' roundings and no more.
"""
import argparse
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu import telemetry
from lightgbm_tpu.config import ObjectiveConfig
from lightgbm_tpu.metrics.dcg import DCGCalculator
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.objectives import rank
from lightgbm_tpu.utils import log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import lambdarank as reference  # noqa: E402
from harness.rank_data import grade_counts, query_lengths  # noqa: E402

TOL = 1e-5
CELL = "istella-rank-leafwise-f32.rank"


class _Meta:
    def __init__(self, label, boundaries, weights=None):
        self.label = np.asarray(label, np.float32)
        self.query_boundaries = np.asarray(boundaries, np.int64)
        self.weights = weights


def _objective(label, boundaries, weights=None):
    obj = create_objective("lambdarank", ObjectiveConfig())
    obj.init(_Meta(label, boundaries, weights), int(boundaries[-1]))
    return obj


def _heavy_tailed(seed, queries=60, extra=()):
    """Seeded log-normal query lengths with ``extra`` lengths in front."""
    rng = np.random.default_rng(seed)
    sizes = np.concatenate([np.asarray(extra, np.int64), np.clip(
        rng.lognormal(3.2, 0.9, queries).astype(np.int64), 1, 600)])
    return sizes, np.concatenate(([0], np.cumsum(sizes)))


# name -> (lengths put in front of a seeded heavy tail, grades, scores)
CASES = {
    # length-1 queries (no pair at all) and empty grades of both kinds
    "length_one": ((1, 1, 2), "mixed", "normal"),
    # a query at every class boundary and one past it
    "class_boundaries": ((64, 65, 128, 129, 256, 257), "mixed", "normal"),
    # every document of some queries the same grade: no pair, max DCG 0
    "all_equal_grades": ((40, 300), "equal_runs", "normal"),
    # scores tied within queries: order by document, no regularisation
    "tied_scores": ((50, 200), "mixed", "ties"),
    # the first iteration: every score 0
    "zero_scores": ((30,), "mixed", "zero"),
}


def _case(name, seed=11):
    extra, grades, scores = CASES[name]
    sizes, bounds = _heavy_tailed(seed, extra=extra)
    rng = np.random.default_rng(seed + 1)
    n = int(bounds[-1])
    label = rng.choice(5, n, p=[0.6, 0.15, 0.12, 0.08, 0.05])
    if grades == "equal_runs":
        label[bounds[0]:bounds[2]] = 2
        label[bounds[3]:bounds[4]] = 0
    score = rng.standard_normal(n).astype(np.float32)
    if scores == "ties":
        score = np.round(score * 2) / 2
    elif scores == "zero":
        score[:] = 0.0
    return label, bounds, score


def _close(got, want):
    scale = max(float(np.abs(want).max()), 1e-30)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=TOL * scale)


@pytest.mark.parametrize("name", sorted(CASES))
def test_bucketed_lambdas_equal_the_reference(name):
    label, bounds, score = _case(name)
    obj = _objective(label, bounds)
    grad, hess = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = reference.gradients(score, label, bounds)
    _close(grad, want_g)
    _close(hess, want_h)


@pytest.mark.parametrize("budget", [1 << 16, 3 * 64 * 64 + 1])
def test_query_blocks_that_do_not_divide_a_class(budget, monkeypatch):
    """A small working set cuts every class into several query blocks,
    the last padded with empty queries that give nothing."""
    monkeypatch.setattr(rank, "PAIR_BUDGET", budget)
    label, bounds, score = _case("class_boundaries", seed=5)
    obj = _objective(label, bounds)
    counts = {w: n for w, n in zip(*np.unique(
        rank._needed_width(np.diff(bounds)), return_counts=True))}
    assert any(b > 1 for _, b, _ in obj.layout.classes)
    assert any(b * k > counts.get(w, 0) for w, b, k in obj.layout.classes)
    grad, hess = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = reference.gradients(score, label, bounds)
    _close(grad, want_g)
    _close(hess, want_h)


def test_weights_scale_each_document():
    label, bounds, score = _case("class_boundaries", seed=7)
    w = np.random.default_rng(3).uniform(0.5, 2.0, label.size).astype(
        np.float32)
    obj = _objective(label, bounds, weights=w)
    grad, hess = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = reference.gradients(score, label, bounds)
    _close(grad, want_g * w)
    _close(hess, want_h * w)


def _per_document_block(table, *, score, discount, sigmoid):
    """One query block as the program computed it before queries moved as
    windows (the former ``rank._query_block``): every slot's score
    gathered by its own row index, sorted by score over the class width,
    the pair terms, the sort back to each query's document order."""
    count, label, gain = table["count"], table["label"], table["gain"]
    width = label.shape[-1]
    pos = jnp.arange(width, dtype=jnp.int32)
    valid = pos[None, :] < count[:, None]
    s = jnp.where(valid, score[table["rows"]], -jnp.inf)
    neg, ll, gg, src = jax.lax.sort(
        (-s, label, gain, jnp.broadcast_to(pos, s.shape)), dimension=1,
        is_stable=True, num_keys=1)
    ss = jnp.where(valid, -neg, 0.0)
    best = ss[:, 0]
    worst = jnp.take_along_axis(
        ss, jnp.maximum(count - 1, 0)[:, None], axis=1)[:, 0]
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
    _, lam_out, hes_out = jax.lax.sort((src, lam_sorted, hes_sorted),
                                       dimension=1, num_keys=1)
    return lam_out, hes_out


def _per_document(obj, score):
    """The lambdas and hessians as the program made them before queries
    moved as windows, kept as the oracle of the movement: the objective's
    own tables with every slot's row beside them, every row's lambda and
    hessian gathered by its own flat slot index (rows of no query read a
    zero past the last slot)."""
    _key, params, _fn = obj.chunk_spec()
    rows = score.shape[0]
    where = np.full(rows, -1, np.int64)
    tables, base = [], 0
    for table, (w, blocks, block) in zip(params["tables"],
                                         obj.layout.classes):
        row = np.asarray(table["row"])[..., None]
        valid = np.arange(w) < np.asarray(table["count"])[..., None]
        at = np.where(valid, row + np.arange(w), 0)
        where[at[valid]] = base + np.flatnonzero(valid)
        tables.append(dict(table, rows=jnp.asarray(at, jnp.int32)))
        base += blocks * block * w
    where = jnp.asarray(np.where(where < 0, base, where), jnp.int32)

    @jax.jit
    def program(params, tables, score, where):
        lams, hess = [], []
        for table in tables:
            lam, hes = jax.lax.map(functools.partial(
                _per_document_block, score=score,
                discount=params["discount"], sigmoid=params["sigmoid"]),
                table)
            lams.append(lam.reshape(-1))
            hess.append(hes.reshape(-1))
        zero = jnp.zeros((1,), jnp.float32)
        lam = jnp.concatenate(lams + [zero])[where]
        hes = jnp.concatenate(hess + [zero])[where]
        if params["weights"] is not None:
            lam, hes = lam * params["weights"], hes * params["weights"]
        return lam, hes
    return program(params, tuple(tables), jnp.asarray(score), where)


def _shard_padded(name):
    """A ``_case`` laid out as two processes' query-atomic shards in the
    padded-global rows ``globalize_layout`` builds: (objective, score over
    the padded rows, padding rows)."""
    label, bounds, score = _case(name)
    half = int(bounds[bounds.size // 2])
    shards = ((0, half), (half + 37, int(bounds[-1]) - half))
    num_padded = half + 37 + shards[1][1] + 11
    obj = create_objective("lambdarank", ObjectiveConfig())
    obj.globalize_layout(_Meta(label, bounds), shards, num_padded)
    pad_pos = np.concatenate([s + np.arange(n) for s, n in shards])
    padded = np.zeros(num_padded, np.float32)
    padded[pad_pos] = score
    padding = np.setdiff1d(np.arange(num_padded), pad_pos)
    padded[padding] = 7.0       # a shard's padding rows hold any score
    return obj, padded, padding


# name -> (lengths put in front of the seeded heavy tail, weighted)
MOVES = dict({name: None for name in CASES}, **{
    "one_document": ((1,), False),
    "class_widths": ((64, 128, 256, 384), False),
    "longest": ((4096,), False),
    "weighted": ((1, 128, 300), True),
})


@pytest.mark.parametrize("name", sorted(MOVES) + ["shard_padded"])
def test_the_movement_is_the_per_document_one(name):
    """Whole-query windows in and out give the per-document gathers'
    lambdas and hessians bit for bit: the same floats reach the pair
    terms, and a row gets its own slot's value plus exact zeros."""
    padding = np.zeros(0, np.int64)
    if name == "shard_padded":
        obj, score, padding = _shard_padded("class_boundaries")
    elif MOVES[name] is None:
        label, bounds, score = _case(name)
        obj = _objective(label, bounds)
    else:
        extra, weighted = MOVES[name]
        sizes, bounds = _heavy_tailed(13, queries=20, extra=extra)
        rng = np.random.default_rng(17)
        label = rng.integers(0, 5, bounds[-1])
        score = rng.standard_normal(bounds[-1]).astype(np.float32)
        weights = (rng.uniform(0.5, 2.0, bounds[-1]).astype(np.float32)
                   if weighted else None)
        obj = _objective(label, bounds, weights)
        if name == "class_widths":
            widths = {w for w, _, _ in obj.layout.classes}
            assert set(extra) <= widths, widths
    grad, hess = obj.get_gradients(jnp.asarray(score))
    want_g, want_h = _per_document(obj, score)
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(want_g))
    np.testing.assert_array_equal(np.asarray(hess), np.asarray(want_h))
    assert np.abs(np.asarray(grad)).max() > 0
    assert not np.asarray(grad)[padding].any()
    assert not np.asarray(hess)[padding].any()


def test_a_query_in_rows_apart_is_refused():
    """A query moves as one window of consecutive rows: ``rows`` that put
    two documents of one query apart fail at ``init``."""
    label, bounds, _ = _case("length_one")
    rows = np.arange(int(bounds[-1]))
    lo = int(bounds[2])                       # the query of two documents
    rows[[lo, lo + 1]] = rows[[lo + 1, lo]]
    obj = create_objective("lambdarank", ObjectiveConfig())
    with pytest.raises(log.LightGBMError, match="consecutive rows"):
        obj.init(_Meta(label, bounds), int(bounds[-1]), rows=rows)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_layout_rules(seed):
    sizes, _ = _heavy_tailed(seed, queries=400, extra=(1, 64, 128, 129))
    layout = rank.query_layout(sizes)
    widths = [w for w, _, _ in layout.classes]
    assert widths == sorted(widths) and len(widths) <= rank.MAX_CLASSES
    for w in widths:
        assert (w < rank.TILE and w & (w - 1) == 0) or w % rank.TILE == 0
    # every query fits a class, and each class's slots hold its queries
    assert max(widths) >= sizes.max()
    needed = rank._needed_width(sizes)
    of_class = np.searchsorted(widths, needed)
    for c, (w, blocks, block) in enumerate(layout.classes):
        n = int(np.sum(of_class == c))
        assert blocks * block >= n > (blocks - 1) * block
        assert block * w * w <= max(rank.PAIR_BUDGET, w * w)
    assert layout.pairs == int(np.sum(sizes ** 2)) <= layout.pair_slots


def test_inverse_max_dcg_is_the_per_query_statement():
    label, bounds, _ = _case("all_equal_grades")
    gain = ObjectiveConfig().label_gain
    dcg = DCGCalculator(np.asarray(gain, np.float32))
    want = []
    for q in range(bounds.size - 1):
        m = dcg.cal_max_dcg_at_k(20, label[bounds[q]:bounds[q + 1]])
        want.append(1.0 / m if m > 0 else m)
    got = rank.inverse_max_dcg(label, bounds, np.asarray(gain, np.float32),
                               20)
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               rtol=1e-6)


@pytest.mark.parametrize("draw", [1, 2])
def test_the_work_is_a_function_of_the_layout(draw):
    """Two label draws over one set of query lengths: the same slots, the
    same program text, the same counters."""
    sizes, bounds = _heavy_tailed(21, extra=(1, 129))
    texts, slots = [], []
    for labels_seed in (draw, draw + 100):
        label = np.random.default_rng(labels_seed).integers(0, 5,
                                                             bounds[-1])
        obj = _objective(label, bounds)
        key, params, fn = obj.chunk_spec()
        score = jnp.zeros((int(bounds[-1]),), jnp.float32)
        texts.append(rank._iteration_program(fn).lower(
            params, score).as_text())
        slots.append(obj.layout.pair_slots)
    assert slots[0] == slots[1] == rank.query_layout(sizes).pair_slots
    assert texts[0] == texts[1]


def test_counters_are_the_layouts_once_a_program():
    telemetry.reset()
    telemetry.enable(fence=False)
    try:
        jax.clear_caches()
        rank._iteration_program.cache_clear()
        rank._RANK_FNS.clear()
        label, bounds, score = _case("tied_scores", seed=31)
        obj = _objective(label, bounds)
        for _ in range(3):
            jax.block_until_ready(obj.get_gradients(jnp.asarray(score)))
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert counters["rank/pairs"] == obj.layout.pairs
    assert counters["rank/pair_slots"] == obj.layout.pair_slots
    assert counters["rank/classes"] == len(obj.layout.classes)


@pytest.mark.parametrize("layout", ["rows", "shard_padded"])
def test_segment_docs_are_counted_once_a_program(layout):
    """``rank/segment_docs``: every document moved as part of its query's
    window, in and out, counted once however often the program runs; a
    shard's padding rows are no documents."""
    if layout == "rows":
        label, bounds, score = _case("class_boundaries", seed=31)
        obj = _objective(label, bounds)
    else:
        obj, score, _padding = _shard_padded("class_boundaries")
        bounds = _case("class_boundaries")[1]
    telemetry.reset()
    telemetry.enable(fence=False)
    try:
        jax.clear_caches()
        rank._iteration_program.cache_clear()
        rank._RANK_FNS.clear()
        for _ in range(3):
            jax.block_until_ready(obj.get_gradients(jnp.asarray(score)))
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert counters["rank/segment_docs"] == 2 * int(bounds[-1])
    assert obj.layout.docs == int(bounds[-1])


def test_the_gradient_program_is_under_its_scope():
    label, bounds, _ = _case("zero_scores")
    obj = _objective(label, bounds)
    _key, params, fn = obj.chunk_spec()
    text = rank._iteration_program(fn).lower(
        params, jnp.zeros((int(bounds[-1]),), jnp.float32)).as_text(
            debug_info=True)
    assert "gradient/gradient_lambdarank/" in text


@pytest.mark.parametrize("seed", [3100000019, 4294967311])
def test_the_generator_fixes_the_layout_and_draws_the_rows(seed):
    """The configuration's key fixes the query lengths and every query's
    grade counts; the seed draws the rows and who takes which grade."""
    from harness.rank_data import make_rank_table
    conf = {"rows": 6000, "features": 6, "queries": 40,
            "layout": {"layout_key": 2016102101, "length_sigma": 0.8,
                       "longest": 700,
                       "grade_mix": [0.88, 0.04, 0.04, 0.025, 0.015]}}
    x1, y1, b1 = make_rank_table(conf, seed)
    x2, y2, b2 = make_rank_table(conf, seed + 1)
    np.testing.assert_array_equal(b1, b2)
    assert b1[-1] == 6000 and np.diff(b1).max() <= 700
    assert not np.array_equal(x1, x2) and not np.array_equal(y1, y2)
    for q in range(b1.size - 1):
        lo, hi = b1[q], b1[q + 1]
        np.testing.assert_array_equal(np.bincount(y1[lo:hi].astype(int),
                                                  minlength=5),
                                      np.bincount(y2[lo:hi].astype(int),
                                                  minlength=5))
    sizes = query_lengths(7325625, 23219, 0.8, 4096, 2016102101)
    assert sizes.sum() == 7325625 and sizes.max() <= 4096
    counts = grade_counts(sizes, [1, 1, 1, 1, 1], 2016102101)
    np.testing.assert_array_equal(counts.sum(axis=1), sizes)


@pytest.fixture(scope="module")
def rehearsal():
    """The ``rank`` traffic kind through the benchmark's harness on the
    CPU, cut to 4,096 rows in 100 queries and 32 columns, with
    ``--control 1``: set-up as the CLI builds the booster, one window
    slice of 8 per-tree turns on the compacted grower, the check against
    the NumPy reference."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import run as runner
    from lightgbm_tpu.utils import log
    real_load = runner.load_json

    def cut(*parts):
        loaded = real_load(*parts)
        if parts[0] == "configs":
            loaded = dict(loaded, rows=4096, queries=100, features=32)
        elif parts[0] == "cells":
            loaded = dict(loaded, params=dict(loaded["params"],
                                              warmup_slices=0))
        return loaded
    args = argparse.Namespace(workload=CELL, seed=3100000019, seconds=0.5,
                              trace=0, rows=4096, control=1)
    mp = pytest.MonkeyPatch()
    mp.setattr(runner, "load_json", cut)
    try:
        line, code = runner.execute(args, require_chip=False)
    finally:
        mp.undo()
        log.set_stream(None)
    assert code == 0
    return line


def test_the_rank_cell_is_correct_at_a_cut_size(rehearsal):
    assert rehearsal["failed"] == 0 and rehearsal["attempted"] == 8
    checks = rehearsal["checks"]
    for name in ("score_gap", "bin_code_gap", "split_gain_gap",
                 "leaf_value_gap", "leaf_sum_gap", "trees_short"):
        assert checks[name]["limit"] is not None
        assert checks[name]["value"] <= checks[name]["limit"], (
            name, checks[name])
    assert rehearsal["correct"] is True


@pytest.mark.parametrize("who", ["control", "half_batch", "state_unchanged"])
def test_the_control_and_the_faults_are_not_correct(rehearsal, who):
    if who == "control":
        assert rehearsal["control_correct"] is False
    else:
        assert rehearsal["faults_correct"][who] is False
