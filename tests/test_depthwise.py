"""Depth-wise (level-batched) grower tests.

The depthwise policy (models/grower_depthwise.py) is the TPU throughput
path: identical split math to the leaf-wise grower, level-batched order.
Tests keep shapes tiny — the unrolled level program is expensive to compile
on CPU."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.grower import grow_tree
from lightgbm_tpu.models.grower_depthwise import grow_tree_depthwise, num_levels
from lightgbm_tpu.ops.histogram import histogram_leafbatch, histogram_segsum


@pytest.fixture(scope="module")
def small_problem():
    rng = np.random.RandomState(3)
    n, f = 800, 5
    x = rng.randn(n, f)
    y = ((x[:, 0] - x[:, 1] + 0.3 * rng.randn(n)) > 0).astype(np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=16)
    p = 0.5 * np.ones(n, np.float32)
    grad = jnp.asarray(p - y)
    hess = jnp.asarray(p * (1 - p))
    return dict(
        ds=ds, x=x, y=y,
        bins=jnp.asarray(ds.bins), grad=grad, hess=hess,
        row_mask=jnp.ones(n, bool), fmask=jnp.ones(f, bool),
        nbins=jnp.asarray([m.num_bin for m in ds.bin_mappers], jnp.int32))


def _grow(p, policy, num_leaves, row_mask=None, **kw):
    fn = grow_tree_depthwise if policy == "depthwise" else grow_tree
    return fn(p["bins"], p["grad"], p["hess"],
              p["row_mask"] if row_mask is None else row_mask,
              p["fmask"], p["nbins"], num_leaves=num_leaves, num_bins_max=16,
              min_data_in_leaf=10, min_sum_hessian_in_leaf=0.5, **kw)


def test_leafbatch_histogram_matches_segsum_oracle(small_problem):
    p = small_problem
    rng = np.random.RandomState(0)
    cid = jnp.asarray(rng.randint(0, 4, 800), jnp.int32)
    ok = jnp.asarray(rng.rand(800) < 0.7)
    got = histogram_leafbatch(p["bins"], p["grad"], p["hess"], cid, ok, 4, 16)
    for c in range(4):
        want = histogram_segsum(p["bins"], p["grad"], p["hess"],
                                ok & (cid == c), 16)
        np.testing.assert_allclose(np.asarray(got[c]), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_depthwise_tree_structure(small_problem):
    p = small_problem
    tree = _grow(p, "depthwise", 8)
    n = int(tree.num_leaves)
    assert 2 <= n <= 8
    counts = np.asarray(tree.leaf_count)[:n]
    assert counts.sum() == 800 and (counts >= 10).all()
    # every row's leaf value via the recorded partition equals a tree replay
    from lightgbm_tpu.ops.scoring import add_tree_score

    def pad(a, size):
        out = np.zeros(size, np.asarray(a).dtype)
        out[:min(len(np.asarray(a)), size)] = np.asarray(a)[:size]
        return jnp.asarray(out)

    lv = np.zeros(9, np.float32)
    lv[:n] = np.asarray(tree.leaf_value)[:n]
    replay = add_tree_score(
        p["bins"], jnp.zeros(800), pad(tree.split_feature, 7),
        pad(tree.threshold_bin, 7), pad(tree.left_child, 7),
        pad(tree.right_child, 7), jnp.asarray(lv), tree.num_leaves,
        max_nodes=7)
    by_ids = np.asarray(tree.leaf_value)[np.asarray(tree.leaf_ids)]
    np.testing.assert_allclose(np.asarray(replay), by_ids, atol=1e-6)


def test_depthwise_stump_matches_leafwise(small_problem):
    p = small_problem
    td = _grow(p, "depthwise", 2)
    tl = _grow(p, "leafwise", 2)
    assert int(td.split_feature[0]) == int(tl.split_feature[0])
    assert int(td.threshold_bin[0]) == int(tl.threshold_bin[0])
    np.testing.assert_allclose(np.asarray(td.leaf_value)[:2],
                               np.asarray(tl.leaf_value)[:2], rtol=1e-4)


def test_depthwise_respects_leaf_budget_and_bagging(small_problem):
    p = small_problem
    rng = np.random.RandomState(1)
    bag = jnp.asarray(rng.rand(800) < 0.6)
    tree = _grow(p, "depthwise", 6, row_mask=bag)
    n = int(tree.num_leaves)
    assert n <= 6
    counts = np.asarray(tree.leaf_count)[:n]
    assert counts.sum() == int(np.asarray(bag).sum())


def test_num_levels():
    assert num_levels(2) == 1
    assert num_levels(255) == 8
    assert num_levels(256) == 8
    assert num_levels(63) == 6
    # max_depth semantics match the leaf-wise rule: a leaf at depth >=
    # max_depth (root depth 1) cannot split → max_depth-1 split levels
    assert num_levels(255, max_depth=5) == 4
    assert num_levels(255, max_depth=2) == 1


def test_depthwise_data_parallel_matches_serial(small_problem):
    """Data-parallel depthwise over the 8-device CPU mesh grows the same
    tree as single-device depthwise (the reference's serial≡parallel
    invariant, data_parallel_tree_learner.cpp:237-243): in float32, and
    on the int8 route with two statistics a column, whose children's row
    counts each shard routes and the data axis sums (one psum a level),
    the same tree to the bit."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel import create_parallel_learner

    p = small_problem
    params = {"objective": "binary", "num_leaves": "8",
              "min_data_in_leaf": "10", "min_sum_hessian_in_leaf": "0.5",
              "learning_rate": "0.1", "grow_policy": "depthwise"}

    def grow(params):
        trees, counters = {}, {}
        for learner_kind, machines in (("serial", 1), ("data", 8),
                                       ("feature", 4)):
            cfg = OverallConfig()
            cfg.set(dict(params, tree_learner=learner_kind,
                         num_machines=str(machines)), require_data=False)
            ds = Dataset.from_arrays(p["x"], p["y"], max_bin=16)
            booster = GBDT()
            obj = create_objective(cfg.objective_type, cfg.objective_config)
            learner = (create_parallel_learner(cfg)
                       if learner_kind != "serial" else None)
            booster.init(cfg.boosting_config, ds, obj, learner=learner)
            telemetry.reset()
            telemetry.enable()
            try:
                booster.train_one_iter(is_eval=False)
                counters[learner_kind] = dict(
                    telemetry.snapshot()["counters"])
                counters[learner_kind]["sites"] = telemetry.collectives()
            finally:
                telemetry.disable()
                telemetry.reset()
            trees[learner_kind] = booster.models[0]
        return trees, counters

    trees, _ = grow(params)
    a = trees["serial"]
    for kind in ("data", "feature"):
        b = trees[kind]
        np.testing.assert_array_equal(a.split_feature, b.split_feature)
        np.testing.assert_array_equal(a.threshold_bin, b.threshold_bin)
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=1e-3)

    trees, counters = grow(dict(params, hist_dtype="int8",
                                min_data_in_leaf="1"))
    a = trees["serial"]
    assert a.num_leaves > 4
    for kind in ("serial", "data", "feature"):
        assert counters[kind]["hist/stats_2"] > 0, kind
        assert "hist/stats_3" not in counters[kind], kind
        b = trees[kind]
        assert b.to_string() == a.to_string(), kind
    # the rows are the data axis's: each level's right counts psum'd
    assert "depthwise/route_count_psum" in counters["data"]["sites"]
    assert "depthwise/route_count_psum" not in counters["feature"]["sites"]


def test_gbdt_trains_with_depthwise_policy(small_problem):
    p = small_problem
    ds = Dataset.from_arrays(p["x"], p["y"], max_bin=16)
    booster = lgb.train(
        {"objective": "binary", "num_leaves": 8, "min_data_in_leaf": 10,
         "min_sum_hessian_in_leaf": 0.5, "num_iterations": 8,
         "learning_rate": 0.2, "grow_policy": "depthwise"}, ds)
    prob = booster.predict(p["x"])
    acc = ((prob > 0.5).astype(np.float32) == p["y"]).mean()
    assert acc > 0.85


# ------------------------------------------------- two statistics a column

_GROWN = {}


def _grown(min_data_in_leaf, stats, num_leaves=63, minh=0.5,
           num_bins=16):
    """The level-wise int8 grower over the XLA int route, jitted once a
    (configuration, statistics) pair: ``hist_stats_of`` is patched while
    the program traces, to force the route it would not pick."""
    from unittest import mock
    from lightgbm_tpu.models import grower_unified
    key = (min_data_in_leaf, stats, num_leaves, minh, num_bins)
    if key not in _GROWN:
        kw = dict(compute_dtype="int8", num_leaves=num_leaves,
                  num_bins_max=num_bins, min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian_in_leaf=minh, max_depth=-1, packing=None)
        fn = jax.jit(lambda *a: grower_unified._grow_tree_depthwise_fn(
            *a, **kw))

        def grown(*a):
            with mock.patch.object(grower_unified, "hist_stats_of",
                                   lambda *_: stats):
                return fn(*a)
        _GROWN[key] = grown
    return _GROWN[key]


@pytest.mark.parametrize("holes", [False, True])
@pytest.mark.parametrize("min_data_in_leaf", [0, 1])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_two_statistics_grow_the_three_statistic_tree(seed, min_data_in_leaf,
                                                      holes):
    """Where ``min_data_in_leaf <= 1`` beside a hessian floor, the int8
    level-wise grower builds grad and hess alone a leaf column and takes
    each child's rows from the routing: every ``TreeArrays`` field,
    ``leaf_count`` and ``leaf_ids`` included, equals the tree grown with
    the count column, with every row in the tree and with a bag of
    holes in ``row_mask``."""
    from lightgbm_tpu.models.grower_unified import hist_stats_of
    assert hist_stats_of("int8", min_data_in_leaf, 0.5) == 2
    rng = np.random.RandomState(seed)
    n, f = 2000, 5
    x = rng.randn(n, f)
    bins = np.stack([np.clip((np.argsort(np.argsort(x[:, k])) * 16) // n,
                             0, 15) for k in range(f)]).astype(np.uint8)
    grad = (x[:, 0] * x[:, 1] + 0.5 * x[:, 2]
            + rng.randn(n)).astype(np.float32)
    hess = rng.uniform(0.05, 0.3, n).astype(np.float32)
    mask = rng.rand(n) < 0.7 if holes else np.ones(n, bool)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), jnp.ones((f,), bool),
            jnp.full((f,), 16, jnp.int32))
    two, three = (jax.tree.map(np.asarray,
                               _grown(min_data_in_leaf, stats)(*args))
                  for stats in (2, 3))
    for name, got, want in zip(two._fields, two, three):
        np.testing.assert_array_equal(got, want, name)
    n_leaves = int(two.num_leaves)
    assert n_leaves > 32
    assert two.leaf_count[:n_leaves].sum() == mask.sum()
    np.testing.assert_array_equal(
        two.leaf_count[:n_leaves],
        np.bincount(two.leaf_ids[mask], minlength=n_leaves))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_two_statistics_at_the_default_hessian_floor(seed):
    """LightGBM's default floor, ``min_sum_hessian_in_leaf=1e-3``, over
    30,000 rows of weight 10,000 (gradients and hessians 10,000 times a
    logloss's) in 255-leaf trees.  A sibling derived by float32
    subtraction carries a residue of its ancestors' bins in every bin it
    has no row of, above the floor at these sums, and a side of such
    bins alone passes it: the search that took every bin as occupied
    grows other trees on each of these three seeds.  The two-statistic
    tree refuses such a side where the count test does, and is the
    three-statistic tree field for field."""
    from lightgbm_tpu.models.grower_unified import hist_stats_of
    assert hist_stats_of("int8", 1, 1e-3) == 2
    rng = np.random.RandomState(seed)
    n, f = 30000, 5
    bins = rng.randint(0, 16, (f, n)).astype(np.uint8)
    y = ((bins[0] >= 8) ^ (bins[1] % 5 == 0)
         ^ (rng.rand(n) < 0.2)).astype(np.float32)
    grad = (1e4 * (0.5 - y)).astype(np.float32)
    hess = (1e4 * rng.uniform(0.1, 0.25, n)).astype(np.float32)
    mask = rng.rand(n) < 0.9
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), jnp.ones((f,), bool),
            jnp.full((f,), 16, jnp.int32))
    two, three = (jax.tree.map(np.asarray,
                               _grown(1, stats, 255, 1e-3)(*args))
                  for stats in (2, 3))
    for name, got, want in zip(two._fields, two, three):
        np.testing.assert_array_equal(got, want, name)
    assert int(two.num_leaves) > 128


@pytest.mark.parametrize("K", [2, 31, 64, 128, 200])
def test_row_flag_is_the_tables_entry(K):
    """The float level-wise routes pick each row's smaller-child flag out
    of a [2P] table of its new slot without a gather: ``_row_flag`` is
    ``flags[ids]`` for every id, across every word of the table."""
    from lightgbm_tpu.models.grower_unified import _row_flag
    rng = np.random.RandomState(K)
    flags = rng.rand(K) < 0.5
    ids = rng.randint(0, K, 5000).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(_row_flag(jnp.asarray(flags), jnp.asarray(ids))),
        flags[ids])


def test_three_statistics_where_the_count_can_refuse():
    """The rule reads the configuration alone: ``min_data_in_leaf=2``, a
    hessian floor of 0, or float histograms keep the count column, and a
    tree grown under the first counts ``hist/stats_3`` for every pass."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.models.grower_unified import hist_stats_of
    assert hist_stats_of("int8", 2, 100.0) == 3
    assert hist_stats_of("int8", 1, 0.0) == 3
    assert hist_stats_of("int8_sr", 0, 1.0) == 2
    assert hist_stats_of(jnp.float32, 1, 100.0) == 3
    assert hist_stats_of(jnp.bfloat16, 0, 100.0) == 3
    n, f = 1999, 5                      # shapes no other test traces
    S = jax.ShapeDtypeStruct
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    for min_data, minh, want in ((2, 1.0, 3), (1, 0.0, 3), (1, 1.0, 2)):
        telemetry.reset()
        telemetry.enable()
        try:
            jax.make_jaxpr(lambda *a: grow_tree_depthwise_jit(
                *a, compute_dtype="int8", num_leaves=15, num_bins_max=16,
                min_data_in_leaf=min_data, min_sum_hessian_in_leaf=minh,
                max_depth=-1, packing=None))(
                S((f, n), jnp.uint8), S((n,), jnp.float32),
                S((n,), jnp.float32), S((n,), jnp.bool_),
                S((f,), jnp.bool_), S((f,), jnp.int32))
            counters = telemetry.snapshot()["counters"]
        finally:
            telemetry.disable()
            telemetry.reset()
        assert counters["hist/stats_%d" % want] == 4, (min_data, minh)
        assert "hist/stats_%d" % (5 - want) not in counters
