"""Whole trees past one int32 accumulator's rows (``hist_dtype=int8``).

``tests/test_hist_int8_ranges.py`` holds the accumulators against int64
sums.  Here the cap (``ops/hist_pallas.INT8_HIST_MAX_ROWS``) is patched
down under the routes a booster takes, and the trees are held against the
same booster's with the cap where it is: the serial level-wise route,
fused chunk and per iteration, serial leaf-wise growth, and the
data-parallel learners on four virtual devices, whose int reduction adds
every shard's accumulators, so that it is the rows of all shards together
that must not pass one accumulator's.  Float32 holds every sum of these
sizes exactly, so ranged and unranged histograms are the same numbers and
the trees the same trees.  ``tests/test_airline_cell.py`` runs the
benchmark's own harness, and its plain float64 reference, on the cell
this rule was built for.
"""
import numpy as np
import pytest

import jax

from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import hist_pallas

ROWS, COLUMNS, ITERS = 5000, 6, 3


@pytest.fixture(scope="module")
def table():
    rng = np.random.RandomState(17)
    x = rng.randn(ROWS, COLUMNS)
    x[:, 3] = 1.0                        # a constant column: one bin
    x[:, 4] = rng.rand(ROWS) < 0.002     # and one 99.8% in one bin
    y = ((x[:, 0] - x[:, 1] + 0.3 * rng.randn(ROWS)) > 0).astype(np.float32)
    return x, y


@pytest.fixture
def capped(monkeypatch):
    """patch(cap): the cap moved, and every program traced under another
    cap forgotten (the ranges are a trace-time shape)."""
    def forget():
        gbdt_mod._CHUNK_PROGRAMS.clear()
        jax.clear_caches()

    def patch(cap):
        monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", cap)
        forget()
    yield patch
    monkeypatch.undo()
    forget()


def _booster(table, **params):
    x, y = table
    base = {"objective": "binary", "num_leaves": "15", "max_bin": "32",
            "min_data_in_leaf": "5", "min_sum_hessian_in_leaf": "1",
            "learning_rate": "0.2", "hist_dtype": "int8",
            "hist_chunk": "256", "grow_policy": "depthwise"}
    cfg = OverallConfig()
    cfg.set(dict(base, **{k: str(v) for k, v in params.items()}),
            require_data=False)
    learner = None
    if params.get("tree_learner", "serial") != "serial":
        from lightgbm_tpu.parallel import create_parallel_learner
        learner = create_parallel_learner(cfg)
    booster = GBDT()
    booster.init(cfg.boosting_config,
                 Dataset.from_arrays(x, y, max_bin=32),
                 create_objective(cfg.objective_type, cfg.objective_config),
                 learner=learner)
    return booster


def _trees(booster, chunked):
    if chunked:
        assert booster.chunkable_for(False)
        booster.train_chunk(ITERS)
    else:
        for _ in range(ITERS):
            booster.train_one_iter(is_eval=False)
    assert len(booster.models) == ITERS
    return [(t.num_leaves, np.array(t.split_feature),
             np.array(t.threshold_bin), np.array(t.leaf_value))
            for t in booster.models], np.array(booster.score)


def _same(got, want):
    """The same trees: every split the same, the leaf values to the last
    place or two of float32 (the histograms are the same numbers, the
    program around them is another, and XLA fuses the split search's sums
    after the program it is given)."""
    (trees, score), (trees_w, score_w) = got, want
    for a, b in zip(trees, trees_w):
        assert a[0] == b[0] > 1
        np.testing.assert_array_equal(a[1], b[1])
        np.testing.assert_array_equal(a[2], b[2])
        np.testing.assert_allclose(a[3], b[3], rtol=1e-5, atol=1e-8)
    # a score is a sum of leaf values of either sign: one near 0 keeps
    # the values' last places, not its own
    np.testing.assert_allclose(score, score_w, rtol=1e-5, atol=1e-6)


def _ranges_counted(run):
    """(what ``run`` returned, hist/accum_ranges over its traces)."""
    from lightgbm_tpu import telemetry
    telemetry.reset()
    telemetry.enable(fence=False)
    try:
        out = run()
        return out, telemetry.snapshot()["counters"].get(
            "hist/accum_ranges", 0)
    finally:
        telemetry.disable()
        telemetry.reset()


@pytest.mark.parametrize("cap", [1000, 3000])
def test_levelwise_trees_are_the_same_ranged(table, capped, cap):
    """The fused chunk route and the per-iteration one, 5,000 rows in
    chunks of 256: seven ranges under a cap of 1,000 rows, two under
    3,000 (``_ranged_rows``)."""
    whole, counted = _ranges_counted(
        lambda: _trees(_booster(table), chunked=True))
    passes = counted                 # one range a pass: the count of passes
    assert passes >= 4
    whole_per_iteration = _trees(_booster(table), chunked=False)
    capped(cap)
    ranges = hist_pallas._ranged_rows(ROWS, 256)[0]
    assert ranges == {1000: 7, 3000: 2}[cap]
    ranged, counted = _ranges_counted(
        lambda: _trees(_booster(table), chunked=True))
    assert counted == passes * ranges
    _same(ranged, whole)
    _same(_trees(_booster(table), chunked=False), whole_per_iteration)


@pytest.mark.parametrize("compact", ["false", "true"])
def test_leafwise_trees_are_the_same_ranged(table, capped, compact):
    params = dict(grow_policy="leafwise", leafwise_compact=compact)
    whole = _trees(_booster(table, **params), chunked=False)
    capped(1000)
    _same(_trees(_booster(table, **params), chunked=False), whole)


@pytest.mark.parametrize("schedule", ["psum", "reduce_scatter"])
@pytest.mark.parametrize("cap,local_ranges", [(2000, 1), (600, 3)])
def test_data_parallel_int_reduction_is_ranged(table, capped, schedule, cap,
                                               local_ranges):
    """Four shards of 1,250 rows (1,280 as padded to chunks of 256).
    Under a cap of 2,000 rows each shard's accumulator is one range, and
    the int psum of four of them is not: the halves of the integer pair
    are reduced.  Under 600 a shard's own rows take three ranges.  Either
    way the trees are the unranged serial booster's, by both reduction
    schedules."""
    serial_whole = _trees(_booster(table), chunked=True)
    params = dict(tree_learner="data", num_machines=4, dp_schedule=schedule)
    dp_whole = _trees(_booster(table, **params), chunked=True)
    _same(dp_whole, serial_whole)
    dp_whole_per_iteration = _trees(_booster(table, **params), chunked=False)
    capped(cap)
    assert hist_pallas._ranged_rows(ROWS // 4, 256) == (
        local_ranges, 1280 if local_ranges == 1 else 1536, local_ranges > 1)
    assert hist_pallas.accum_ranges(1280 * 4, 256) > 1
    _same(_trees(_booster(table, **params), chunked=True), serial_whole)
    _same(_trees(_booster(table, **params), chunked=False),
          dp_whole_per_iteration)


def test_voting_and_feature_parallel_learners_range_too(table, capped):
    """The feature-parallel learner sums all rows on every shard; the
    voting learner's int8 histograms ride the data axis's int psum."""
    want = {}
    for learner in ("feature", "voting"):
        want[learner] = _trees(_booster(
            table, tree_learner=learner, num_machines=4), chunked=False)
    capped(1000)
    for learner in ("feature", "voting"):
        _same(_trees(_booster(table, tree_learner=learner, num_machines=4),
                     chunked=False), want[learner])
