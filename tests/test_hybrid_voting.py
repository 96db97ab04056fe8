"""Differential pins for the 2-D mesh learners (ISSUE 9):
serial ≡ data ≡ hybrid ≡ voting, every growth policy, per-iteration AND
fused-chunk paths, on the virtual 8-device CPU mesh.

The repo's standing equivalence bar (tests/test_parallel.py):

- **int8** histograms: the int-domain accumulators are order-free
  (pmax-synced scales, int32 sums), so parallel trees have the serial
  run's STRUCTURE exactly — hybrid and voting, all three growth
  policies, both dispatch paths.  Leaf values are float32 arithmetic on
  the dequantized sums, which each schedule's program orders and fuses
  its own way: across schedules they agree to 1e-6 relative (measured:
  5 float32 ulps on one leaf of voting's third leaf-wise tree, every
  other leaf equal), and the fused-chunk cells, which read equal on
  this compiler, stay pinned bitwise.
- **f32** histograms: reductions run in a different order (single-device
  sum vs psum of partials), so near-tied splits may legitimately resolve
  differently; equivalence is tie-keyed (identical splits up to genuine
  near-ties, values within reduction noise).

Voting exactness: the voted set covers the true best feature whenever
2·top_k >= the owned block width (the schedule then degenerates to a
full exchange of the block) — these pins run in that regime, so voting
is held to the same bar as hybrid, not just the PV-tree approximation
argument.
"""
import numpy as np
import jax
import pytest

from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.parallel import create_parallel_learner
from lightgbm_tpu.parallel.mesh import factor_machines

from test_parallel import _assert_equivalent_to_serial


# (grow_policy, leafwise_compact) cells of the policy matrix
POLICIES = [("leafwise", "false"), ("leafwise", "true"),
            ("depthwise", "false")]


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(11)
    n, f = 1200, 10
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.randn(n)) > 0).astype(
        np.float32)
    return x, y


def _make(tl, nm, x, y, extra=None):
    cfg = OverallConfig()
    # num_leaves=7: depthwise programs trace per level (3 levels vs 4 at
    # 15 leaves) and every cell compiles fresh shard_map programs on the
    # 8-device CPU platform — the bit-identity claims are leaf-count-
    # independent, so the smallest non-trivial tree keeps tier-1 time down
    p = {"objective": "binary", "num_leaves": "7",
         "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1.0",
         "learning_rate": "0.2", "tree_learner": tl,
         "num_machines": str(nm)}
    p.update(extra or {})
    cfg.set(p, require_data=False)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    b = GBDT()
    learner = None if tl == "serial" else create_parallel_learner(cfg)
    b.init(cfg.boosting_config, ds,
           create_objective(cfg.objective_type, cfg.objective_config),
           learner=learner)
    return b


def _train(tl, nm, x, y, extra=None, iters=3):
    b = _make(tl, nm, x, y, extra)
    for _ in range(iters):
        if b.train_one_iter(is_eval=False):
            break
    return b


_SERIAL_CACHE: dict = {}


def _serial(x, y, base):
    """Serial oracle boosters, trained once per (policy, compact,
    hist_dtype) for the whole module — every equivalence cell compares
    against the same 3-iteration serial run."""
    key = tuple(sorted(base.items()))
    if key not in _SERIAL_CACHE:
        _SERIAL_CACHE[key] = _train("serial", 1, x, y, base)
    return _SERIAL_CACHE[key]


def _assert_bit_identical(a, b, what):
    assert len(a.models) == len(b.models), what
    for k, (t1, t2) in enumerate(zip(a.models, b.models)):
        assert t1.num_leaves == t2.num_leaves, f"{what} tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature,
                                      err_msg=f"{what} tree {k}")
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin,
                                      err_msg=f"{what} tree {k}")
        np.testing.assert_array_equal(np.asarray(t1.leaf_value),
                                      np.asarray(t2.leaf_value),
                                      err_msg=f"{what} tree {k}")
    np.testing.assert_array_equal(np.asarray(a.score), np.asarray(b.score),
                                  err_msg=what)


def _assert_same_trees_across_schedules(a, b, what):
    """Structure exact; leaf values and scores to the cross-schedule
    float32 budget (module docstring)."""
    assert len(a.models) == len(b.models), what
    for k, (t1, t2) in enumerate(zip(a.models, b.models)):
        assert t1.num_leaves == t2.num_leaves, f"{what} tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature,
                                      err_msg=f"{what} tree {k}")
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin,
                                      err_msg=f"{what} tree {k}")
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-6, atol=0,
                                   err_msg=f"{what} tree {k}")
    np.testing.assert_allclose(np.asarray(a.score), np.asarray(b.score),
                               rtol=1e-6, atol=1e-7, err_msg=what)


def test_factor_machines():
    assert factor_machines(4) == (2, 2)
    assert factor_machines(8) == (4, 2)
    assert factor_machines(6) == (3, 2)
    assert factor_machines(7) == (7, 1)          # primes: pure DP
    assert factor_machines(8, feature_shards=4) == (2, 4)
    assert factor_machines(4, voting=True) == (4, 1)
    assert factor_machines(4, feature_shards=2, voting=True) == (2, 2)
    with pytest.raises(Exception):
        factor_machines(4, feature_shards=3)     # must divide


@pytest.mark.parametrize("tl,extra", [
    ("hybrid", {"feature_shards": "2"}),
    ("voting", {"top_k": "10"}),                 # 2k >= block width: exact
    # voting × explicit feature sharding composes the two restrictions —
    # pinned, but redundant with the two cells above for tier-1 time
    pytest.param("voting", {"feature_shards": "2", "top_k": "10"},
                 marks=pytest.mark.slow),
])
@pytest.mark.parametrize("policy,compact", POLICIES)
def test_int8_same_trees_per_iteration(data, tl, extra, policy, compact):
    """int8 histograms: hybrid/voting grow the serial run's trees for
    every growth policy (per-iteration path) — the same structure, leaf
    values within the cross-schedule budget."""
    x, y = data
    base = {"grow_policy": policy, "leafwise_compact": compact,
            "hist_dtype": "int8"}
    serial = _serial(x, y, base)
    e = dict(base)
    e.update(extra)
    par = _train(tl, 4, x, y, e)
    _assert_same_trees_across_schedules(
        serial, par, f"{tl} {policy} compact={compact}")


@pytest.mark.parametrize("tl,extra", [
    ("hybrid", {"feature_shards": "2"}),
    ("voting", {"top_k": "10"}),
])
@pytest.mark.parametrize("policy,compact,hd", [
    ("depthwise", "false", "int8"),
    # depthwise f32 chunk: pinned but redundant for tier-1 time — the
    # int8 cell above holds the depthwise chunk to the BITWISE bar and
    # the leafwise cell below covers the f32 chunk equivalence
    pytest.param("depthwise", "false", "float32",
                 marks=pytest.mark.slow),
    ("leafwise", "false", "float32"),
])
def test_fused_chunk_matches_serial(data, tl, extra, policy, compact, hd):
    """The fused k-iteration chunk program under the 2-D learners must
    reproduce the serial per-iteration trees (int8: bitwise; f32:
    near-tie equivalence — identical to the 1-D DP chunk bar)."""
    x, y = data
    base = {"grow_policy": policy, "leafwise_compact": compact,
            "hist_dtype": hd}
    serial = _serial(x, y, base)
    e = dict(base)
    e.update(extra)
    par = _make(tl, 4, x, y, e)
    par.train_chunk(3)
    if hd == "int8":
        _assert_bit_identical(serial, par, f"{tl} chunk {policy}")
    else:
        _assert_equivalent_to_serial(serial, par, x)


_F32_BASE = {"grow_policy": "leafwise", "leafwise_compact": "false",
             "hist_dtype": "float32"}


def test_hybrid_f32_equivalent_to_serial(data):
    x, y = data
    serial = _serial(x, y, _F32_BASE)
    hy = _train("hybrid", 4, x, y,
                dict(_F32_BASE, feature_shards="2"))
    _assert_equivalent_to_serial(serial, hy, x)


@pytest.mark.slow
def test_voting_f32_equivalent_to_serial(data):
    """Pinned, but rides the slow lane for tier-1 time: the leafwise f32
    fused-chunk cell above holds voting to the same f32 bar on every
    default run."""
    x, y = data
    serial = _serial(x, y, _F32_BASE)
    vo = _train("voting", 4, x, y, dict(_F32_BASE, top_k="10"))
    _assert_equivalent_to_serial(serial, vo, x)


def test_voting_small_topk_still_trains(data):
    """Below the exactness threshold (2·top_k < block width) voting is
    the PV-tree approximation: trees may differ from serial but training
    must stay healthy (every tree grows, predictions separate classes)."""
    x, y = data
    vo = _train("voting", 4, x, y, {"top_k": "2"}, iters=4)
    assert len(vo.models) == 4
    for t in vo.models:
        assert t.num_leaves > 1
    pred = vo.predict_raw(x)
    auc_ish = float(np.mean(pred[y > 0.5]) - np.mean(pred[y < 0.5]))
    assert auc_ish > 0.1


@pytest.mark.slow
def test_hybrid_uneven_rows_and_features(data):
    """Row padding (N % data_shards != 0) and feature-block padding
    (F % feature_shards != 0) both stay exact.  Slow lane (its 8-device
    4-feature-shard mesh compiles a one-off program set); the padding
    arithmetic itself is single-homed in _owned_block."""
    x, y = data
    x2, y2 = x[:1111], y[:1111]            # 1111 rows, 10 features, fs=2
    base = {"hist_dtype": "int8"}
    serial = _train("serial", 1, x2, y2, base)
    hy = _train("hybrid", 8, x2, y2,
                {"feature_shards": "4", "hist_dtype": "int8"})  # Fb=3 pads
    _assert_bit_identical(serial, hy, "hybrid uneven")
