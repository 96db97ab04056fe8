"""Differential tests: serial ≡ data-parallel ≡ feature-parallel trees on a
virtual 8-device CPU mesh — the reference's own invariant
(data_parallel_tree_learner.cpp: every worker ends each split with the
identical global best split), which SURVEY §4 recommends encoding as a test.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective


def _make_config(tree_learner, num_machines):
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "num_leaves": "15",
             "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1.0",
             "num_iterations": "5", "learning_rate": "0.2",
             "tree_learner": tree_learner,
             "num_machines": str(num_machines)}, require_data=False)
    return cfg


def _train_with(tree_learner, num_machines, x, y):
    cfg = _make_config(tree_learner, num_machines)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    booster = GBDT()
    objective = create_objective(cfg.objective_type, cfg.objective_config)
    learner = None
    if tree_learner != "serial":
        from lightgbm_tpu.parallel import create_parallel_learner
        learner = create_parallel_learner(cfg)
    booster.init(cfg.boosting_config, ds, objective, learner=learner)
    for _ in range(cfg.boosting_config.num_iterations):
        if booster.train_one_iter(is_eval=False):
            break
    return booster


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(21)
    n, f = 1600, 10
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.randn(n)) > 0).astype(np.float32)
    return x, y


def _tree_fingerprint(booster):
    out = []
    for t in booster.models:
        out.append((t.num_leaves, tuple(t.split_feature_real),
                    tuple(t.threshold_bin), tuple(np.round(t.leaf_value, 5))))
    return out


def test_requires_8_devices():
    assert len(jax.devices()) >= 8


def _assert_equivalent_to_serial(serial, parallel, x):
    """Parallel learners must reproduce serial trees up to f32 near-ties.

    Bitwise serial≡parallel equality is not achievable: reductions run in a
    different order (single-device sum vs psum of partials), so a split
    whose two candidates differ by < 1 ulp may resolve differently.  The
    reference has the same property (its guarantee is identical trees
    ACROSS WORKERS, which here holds by construction since the split search
    is replicated on reduced histograms).

    Tie-keyed comparison: splits are compared in order until the FIRST
    divergence per tree; a divergence is only acceptable when both sides'
    chosen gains agree to ~f32 reduction noise (a genuine near-tie —
    each learner picked ITS best, so if the decisions differ yet both
    maxima match, the candidates were tied).  Past the first divergence the
    partitions differ and structures are legitimately incomparable, so the
    remaining assertions are on predictions.
    """
    assert len(serial.models) == len(parallel.models)
    diverged = False
    for k, (ts, tp) in enumerate(zip(serial.models, parallel.models)):
        if diverged:
            # scores differ past the first divergence; later trees grow on
            # different residuals and are legitimately incomparable
            break
        n = min(ts.num_leaves, tp.num_leaves) - 1
        for i in range(n):
            same = (ts.split_feature_real[i] == tp.split_feature_real[i]
                    and ts.threshold_bin[i] == tp.threshold_bin[i])
            gs, gp = float(ts.split_gain[i]), float(tp.split_gain[i])
            tol = max(1e-4 * max(1.0, abs(gs), abs(gp)), 1e-3)
            if not same:
                # divergence must be a genuine near-tie, not a lost split
                assert abs(gs - gp) < tol, (
                    f"tree {k} split {i}: diverged with gain gap "
                    f"{gs} vs {gp} (not a near-tie)")
                diverged = True
                break
            # identical decision -> gains must agree to reduction noise too
            assert abs(gs - gp) < tol, (
                f"tree {k} split {i}: same split, gain {gs} vs {gp}")
        if not diverged:
            # identical prefix must mean identical size: a shorter parallel
            # tree with no near-tie divergence is a LOST split, not noise
            assert ts.num_leaves == tp.num_leaves, (
                f"tree {k}: identical split prefix but {ts.num_leaves} vs "
                f"{tp.num_leaves} leaves (lost splits)")
    diff = np.abs(serial.predict_raw(x) - parallel.predict_raw(x))
    # rows rerouted by a diverged near-tie split may shift; they must be few
    assert (diff > 1e-3).mean() < 0.05
    assert np.median(diff) < 1e-4


def test_data_parallel_matches_serial(data):
    x, y = data
    serial = _train_with("serial", 1, x, y)
    dp = _train_with("data", 8, x, y)
    _assert_equivalent_to_serial(serial, dp, x)


def test_feature_parallel_matches_serial(data):
    x, y = data
    serial = _train_with("serial", 1, x, y)
    fp = _train_with("feature", 8, x, y)
    _assert_equivalent_to_serial(serial, fp, x)


def test_feature_parallel_uneven_features(data):
    """F=10 not divisible by 8 shards — exercises the feature-padding path."""
    x, y = data
    fp = _train_with("feature", 8, x, y)
    # padded phantom features must never be chosen
    for t in fp.models:
        assert (np.asarray(t.split_feature_real) < x.shape[1]).all()


def test_data_parallel_uneven_rows(data):
    x, y = data
    # 1601 rows not divisible by 8
    x2 = np.concatenate([x, x[:1]])
    y2 = np.concatenate([y, y[:1]])
    serial = _train_with("serial", 1, x2, y2)
    dp = _train_with("data", 8, x2, y2)
    _assert_equivalent_to_serial(serial, dp, x2)


def test_data_parallel_chunked_eval_early_stop(synthetic_binary):
    """The data-parallel chunk evaluates metrics IN-PROGRAM (train metrics
    on the all_gathered global score — AUC's global sort included — and
    valid sets replicated per shard), so DP chunked runs early-stop with
    identical bookkeeping to the serial chunked path (VERDICT r1 #5;
    reference evaluates every iteration in parallel mode too,
    gbdt.cpp:225-259)."""
    from lightgbm_tpu.metrics import create_metric

    x, y = synthetic_binary
    xt, yt = x[:1500], y[:1500]
    rng = np.random.RandomState(0)
    xv = x[1500:]
    yv = rng.randint(0, 2, size=len(xv)).astype(np.float32)  # noise valid
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 30, "learning_rate": 0.4,
              "early_stopping_round": 3, "metric": "auc,binary_logloss",
              "grow_policy": "depthwise"}

    def make(tree_learner, machines):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, num_machines=machines)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        ds = Dataset.from_arrays(xt, yt, max_bin=32)
        dsv = Dataset.from_arrays(xv, yv, max_bin=32, reference=ds)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        tm = [m for m in (create_metric(t, cfg.metric_config)
                          for t in cfg.metric_types) if m is not None]
        b.init(cfg.boosting_config, ds, obj, tm, learner=learner)
        vm = [m for m in (create_metric(t, cfg.metric_config)
                          for t in cfg.metric_types) if m is not None]
        b.add_valid_dataset(dsv, vm)
        return b

    b_serial = make("serial", 1)
    assert b_serial.chunkable_for(True)
    b_serial.run_training(30, is_eval=True, chunk_size=5)

    b_dp = make("data", 8)
    assert b_dp.chunk_supported(True) and b_dp.chunkable_for(True)
    b_dp.run_training(30, is_eval=True, chunk_size=5)

    # identical early-stop iteration, model pop-back and best-score
    # bookkeeping; trees equal up to f32 psum near-ties (compare structure)
    assert b_serial.iter == b_dp.iter
    assert len(b_serial.models) == len(b_dp.models)
    np.testing.assert_array_equal(b_serial.best_iter[0], b_dp.best_iter[0])
    np.testing.assert_allclose(b_serial.best_score[0], b_dp.best_score[0],
                               rtol=1e-4)
    for t1, t2 in zip(b_serial.models, b_dp.models):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)


def test_feature_parallel_chunked_matches_serial(synthetic_binary):
    """The fused feature-parallel chunk program (ownership-sliced
    histograms + packed SplitInfo allreduce, everything else replicated)
    must reproduce the serial chunked trees exactly: every shard
    histograms its owned features over ALL rows, so per-feature sums are
    bit-identical to serial and the allreduce picks the identical global
    best (tie-break by smaller feature id preserved)."""
    x, y = synthetic_binary
    x, y = x[:1999], y[:1999]
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 4, "learning_rate": 0.2,
              "grow_policy": "depthwise",
              "bagging_fraction": 0.8, "bagging_freq": 2, "bagging_seed": 5}
    ds = Dataset.from_arrays(x, y, max_bin=32)

    def make(tree_learner, machines):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, num_machines=machines)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        return b

    b_serial = make("serial", 1)
    for _ in range(4):
        b_serial.train_one_iter(is_eval=False)

    b_fp = make("feature", 8)
    assert b_fp.chunk_supported(False) and b_fp.chunkable_for(False)
    stop = b_fp.train_chunk(4)
    assert not stop

    assert len(b_serial.models) == len(b_fp.models) == 4
    for t1, t2 in zip(b_serial.models, b_fp.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(b_serial.score),
                               np.asarray(b_fp.score),
                               rtol=1e-4, atol=1e-5)


def test_balanced_ownership_partition():
    """LPT bin-count balancing: every feature owned exactly once, loads
    within one max-feature of each other (feature_parallel_tree_learner
    .cpp:27-44 analog)."""
    from lightgbm_tpu.parallel.learners import balanced_ownership
    rng = np.random.RandomState(3)
    num_bins = rng.randint(2, 256, size=29)
    own, ownmask = balanced_ownership(num_bins, 8)
    owned = sorted(int(f) for f in own[ownmask])
    assert owned == list(range(29))
    loads = [int(num_bins[own[s][ownmask[s]]].sum()) for s in range(8)]
    assert max(loads) - min(loads) <= int(num_bins.max())


@pytest.mark.parametrize("grow_policy", ["leafwise", "depthwise"])
def test_data_parallel_chunked_matches_serial(synthetic_binary, grow_policy):
    """The fused data-parallel chunk program (shard_map over the whole
    k-iteration scan) must produce the same trees as serial training —
    rows sharded on a non-divisible N exercises the padding/valid_rows
    path."""
    x, y = synthetic_binary
    x, y = x[:1999], y[:1999]        # 1999 % 8 != 0 -> padding
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 4, "learning_rate": 0.2,
              "grow_policy": grow_policy,
              "bagging_fraction": 0.8, "bagging_freq": 2, "bagging_seed": 5}
    ds = Dataset.from_arrays(x, y, max_bin=32)

    def make(tree_learner, machines):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, num_machines=machines)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        return b

    b_serial = make("serial", 1)
    for _ in range(4):
        b_serial.train_one_iter(is_eval=False)

    b_dp = make("data", 8)
    assert b_dp.chunk_supported(False)
    if grow_policy == "depthwise":
        assert b_dp.chunkable_for(False)   # run_training would chunk
    stop = b_dp.train_chunk(4)
    assert not stop

    assert len(b_serial.models) == len(b_dp.models) == 4
    for t1, t2 in zip(b_serial.models, b_dp.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b_serial.score),
                               np.asarray(b_dp.score),
                               rtol=1e-3, atol=1e-4)


def test_data_parallel_chunked_lambdarank_matches_serial():
    """DP-chunked lambdarank: pairwise lambdas need whole queries, and
    device-level row blocks cut queries mid-way — so the chunk program
    gathers the score shards, computes the full lambda vector replicated,
    and slices each shard's rows (needs_global_score protocol; the
    reference's per-machine path is rank_objective.hpp:68-192).  Trees and
    the NDCG trajectory must match the serial per-iteration run."""
    rng = np.random.RandomState(17)
    nq, qsize = 40, 13          # 520 rows: NOT divisible by 8 (shard pad)
    n = nq * qsize
    x = rng.randn(n, 5)
    rel = np.clip((x[:, 0] + 0.3 * rng.randn(n)) * 1.2 + 1, 0, 3).round()
    boundaries = np.arange(0, n + 1, qsize)
    # row weights exercise the padded-weight path (the DP chunk's lambda
    # vector is shard-padded; weights must tail-pad to match)
    weights = (0.5 + rng.rand(n)).astype(np.float32)
    ds = Dataset.from_arrays(x, rel.astype(np.float32), max_bin=32,
                             weights=weights,
                             query_boundaries=boundaries)
    # int8 quantized histograms: scales are pmax-synced and the psum runs
    # in the int domain, so DP trees are BIT-identical to serial (f32
    # psum reduction order would otherwise show through lambdarank's
    # cancellation-heavy gradients)
    params = {"objective": "lambdarank", "num_leaves": 15,
              "min_data_in_leaf": 10, "min_sum_hessian_in_leaf": 1e-3,
              "num_iterations": 4, "learning_rate": 0.1,
              "grow_policy": "depthwise", "hist_dtype": "int8"}

    def make(tree_learner, machines):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, num_machines=machines)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        return b

    b_serial = make("serial", 1)
    for _ in range(4):
        b_serial.train_one_iter(is_eval=False)

    b_dp = make("data", 8)
    assert b_dp.chunk_supported(False) and b_dp.chunkable_for(False)
    stop = b_dp.train_chunk(4)
    assert not stop

    assert len(b_serial.models) == len(b_dp.models) == 4
    for t1, t2 in zip(b_serial.models, b_dp.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(b_serial.score)[:, :n],
                               np.asarray(b_dp.score)[:, :n],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hist_dtype", ["int8", "float32"])
def test_data_parallel_reduce_scatter_matches_psum(hist_dtype):
    """The reference's ReduceScatter ownership schedule
    (data_parallel_tree_learner.cpp:135-235) as psum_scatter + owned-block
    search + SplitInfo allreduce must produce the SAME trees as the
    full-psum schedule: bit-identical under int8 (the int accumulators are
    scattered in the int domain), and equal-structure within float
    tolerance under f32.  F=10 is deliberately not divisible by the
    8-shard mesh (feature padding path)."""
    rng = np.random.RandomState(23)
    n, f = 1999, 10
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.4 * rng.randn(n)) > 0).astype(int)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 4, "learning_rate": 0.2,
              "grow_policy": "depthwise", "hist_dtype": hist_dtype,
              "bagging_fraction": 0.8, "bagging_freq": 2, "bagging_seed": 5}

    def make(schedule):
        cfg = OverallConfig()
        p = dict(params, tree_learner="data", num_machines=8,
                 dp_schedule=schedule)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        from lightgbm_tpu.parallel import create_parallel_learner
        learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        assert b.chunk_supported(False)
        b.train_chunk(4)
        return b

    b_psum = make("psum")
    b_rs = make("reduce_scatter")
    assert len(b_psum.models) == len(b_rs.models) == 4
    for k, (t1, t2) in enumerate(zip(b_psum.models, b_rs.models)):
        assert t1.num_leaves == t2.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin,
                                      err_msg=f"tree {k}")
        if hist_dtype == "int8":
            # the int accumulators are identical by construction (int
            # sums are order-free), so the histograms agree bit-for-bit;
            # the f32 post-processing (dequantize/cumsum/outputs) is
            # compiled per schedule and XLA's fusion/FMA choices may
            # differ by a couple ulps — assert at ulp scale (1e-6, the
            # same cross-program budget the other schedule tests use;
            # this environment's XLA CPU measures up to ~5e-7)
            np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                       rtol=1e-6, atol=1e-9,
                                       err_msg=f"tree {k}")
        else:
            np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                       rtol=1e-5, atol=1e-7,
                                       err_msg=f"tree {k}")


@pytest.mark.parametrize("hist_dtype", ["int8", "float32"])
def test_data_parallel_leafwise_reduce_scatter(hist_dtype):
    """Leaf-wise growth under the reference's ReduceScatter ownership
    schedule — its ACTUAL N-machine mode
    (data_parallel_tree_learner.cpp:135-235 driving
    serial_tree_learner.cpp:119-153): per-split smaller-child histograms
    psum_scatter'd by feature block (int domain for int8), owned-feature
    search, packed SplitInfo allreduce.  Must match serial trees and the
    psum schedule.  F=10 is not divisible by the 8-shard mesh, so one
    shard owns only feature padding — the replicated-root-stat path."""
    rng = np.random.RandomState(29)
    n, f = 1999, 10
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.4 * rng.randn(n)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 4, "learning_rate": 0.2,
              "grow_policy": "leafwise", "hist_dtype": hist_dtype,
              "bagging_fraction": 0.8, "bagging_freq": 2, "bagging_seed": 5}

    def make(tree_learner, **extra):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, **extra)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        return b

    b_serial = make("serial")
    b_rs = make("data", num_machines=8, dp_schedule="reduce_scatter")
    b_psum = make("data", num_machines=8, dp_schedule="psum")

    for name, b in (("rs", b_rs), ("psum", b_psum)):
        assert len(b.models) == 4, name
        for k, (t1, t2) in enumerate(zip(b_serial.models, b.models)):
            assert t1.num_leaves == t2.num_leaves, f"{name} tree {k}"
            np.testing.assert_array_equal(
                t1.split_feature, t2.split_feature,
                err_msg=f"{name} tree {k}")
            np.testing.assert_array_equal(
                t1.threshold_bin, t2.threshold_bin,
                err_msg=f"{name} tree {k}")
            # int8: int accumulators identical by construction, only the
            # per-program f32 dequantize/search fusion may differ by an
            # ulp; f32: psum reduction order differs from the serial sum
            tol = dict(rtol=3e-7, atol=1e-9) if hist_dtype == "int8" \
                else dict(rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                       err_msg=f"{name} tree {k}", **tol)


@pytest.mark.parametrize("hist_dtype", ["int8", "float32"])
def test_data_parallel_leafwise_compact_schedules(hist_dtype):
    """The COMPACTED leaf-wise grower under BOTH data-parallel
    histogram-reduction schedules: serial ≡ compact-reduce_scatter ≡
    compact-psum trees.  The reduce_scatter path composes the reference's
    ownership schedule (feature-block psum_scatter — int domain for the
    quantized path — owned-slice hist cache + split search, packed
    SplitInfo allreduce) onto the compacted grower; there is no
    masked-grower fall-through anymore.  f32 asserts exact tree
    structure; int8 leaf values to 1 ulp (the int accumulators are
    order-free, only per-program f32 dequantize/search fusion differs).
    F=6 on the 8-shard mesh leaves two shards owning only feature
    padding — the replicated-root-stat path."""
    from lightgbm_tpu import telemetry
    rng = np.random.RandomState(31)
    n, f = 2999, 6                       # 2999 % 8 != 0 -> row padding
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(n)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3,
              "num_iterations": 4, "learning_rate": 0.1,
              "grow_policy": "leafwise", "hist_dtype": hist_dtype,
              "leafwise_compact": "true"}

    def make(tree_learner, **extra):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, **extra)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        return b

    b_serial = make("serial")
    telemetry.enable()
    try:
        b_rs = make("data", num_machines=8, dp_schedule="reduce_scatter")
        # the compacted grower actually ran under the ownership schedule
        # (the route counter is the runtime record of the fall-through's
        # absence)
        assert telemetry.counters().get("learner/dp_compact_rs", 0) > 0
    finally:
        telemetry.disable()
    b_psum = make("data", num_machines=8, dp_schedule="psum")

    for name, b in (("compact-rs", b_rs), ("compact-psum", b_psum)):
        assert len(b.models) == 4, name
        for k, (t1, t2) in enumerate(zip(b_serial.models, b.models)):
            assert t1.num_leaves == t2.num_leaves, f"{name} tree {k}"
            np.testing.assert_array_equal(
                t1.split_feature, t2.split_feature,
                err_msg=f"{name} tree {k}")
            np.testing.assert_array_equal(
                t1.threshold_bin, t2.threshold_bin,
                err_msg=f"{name} tree {k}")
            # int8: int-domain reductions are order-free — 1 ulp of
            # per-program f32 dequantize/search fusion is the only slack;
            # f32: psum reduction order differs from the serial sum
            # (same budget the other compact e2e tests use)
            tol = dict(rtol=1e-6, atol=1e-9) if hist_dtype == "int8" \
                else dict(rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                       err_msg=f"{name} tree {k}", **tol)
    # the two schedules agree with each other to the same budget
    for k, (t1, t2) in enumerate(zip(b_rs.models, b_psum.models)):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-6 if hist_dtype == "int8"
                                   else 1e-4,
                                   atol=1e-9 if hist_dtype == "int8"
                                   else 1e-6, err_msg=f"tree {k}")


def test_dp_schedule_auto_resolution(monkeypatch):
    """dp_schedule=auto follows the reference: psum on a single-process
    mesh, the ReduceScatter ownership schedule on true multi-process runs
    (the reference's N-machine mode IS that schedule)."""
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "tree_learner": "data",
             "num_machines": "8"}, require_data=False)
    assert cfg.boosting_config.tree_config.dp_schedule == "auto"
    from lightgbm_tpu.parallel.learners import DataParallelLearner
    learner = DataParallelLearner(cfg)
    assert learner._schedule() == "psum"          # process_count() == 1
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert learner._schedule() == "reduce_scatter"
    cfg2 = OverallConfig()
    cfg2.set({"objective": "binary", "tree_learner": "data",
              "num_machines": "8", "dp_schedule": "psum"},
             require_data=False)
    assert DataParallelLearner(cfg2)._schedule() == "psum"  # explicit wins
