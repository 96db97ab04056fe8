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
