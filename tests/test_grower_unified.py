"""Differential pin across the three growth policies (ISSUE 9).

The same dataset/config trained under every growth policy and both
histogram dtypes the cells and the CLI default use, asserting the
known-equal surfaces:

- masked leaf-wise == compacted leaf-wise: identical split STRUCTURE
  (features, thresholds, leaf counts), leaf values within the repo's
  cross-program budget — XLA CPU contracts the two growers' value math
  into different fusions, so float32 leaf values are NOT bitwise (this
  jaxlib: 4.4e-7 absolute on a leaf of 3.8e-4, 6.7e-6 relative on the
  larger ones); int8 reads equal;
- every policy's tree STRUCTURE is pinned: per tree the split features,
  threshold bins, child links and the leaf counts of the training rows,
  all integers, under one digest.  A seam applied twice, a reordered
  tie-break or a changed routing moves them; a compiler that fuses a
  float product differently does not, which is what the sha256 of the
  model TEXT pinned here before was failing on since the jaxlib moved
  (every leaf value's last digits are in that text).
"""
import hashlib

import numpy as np
import pytest

from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective


def _data():
    rng = np.random.RandomState(97)
    n, f = 1200, 8
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.6 * x[:, 1] + 0.25 * x[:, 2]
          + 0.3 * rng.randn(n)) > 0).astype(np.float32)
    return x, y


def _train(x, y, *, grow_policy, leafwise_compact="false",
           hist_dtype="float32", iters=4):
    cfg = OverallConfig()
    cfg.set({"objective": "binary", "num_leaves": "15",
             "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1.0",
             "learning_rate": "0.2", "grow_policy": grow_policy,
             "leafwise_compact": leafwise_compact,
             "hist_dtype": hist_dtype}, require_data=False)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    b = GBDT()
    b.init(cfg.boosting_config, ds,
           create_objective(cfg.objective_type, cfg.objective_config))
    for _ in range(iters):
        if b.train_one_iter(is_eval=False):
            break
    return b


def _structure_digest(booster, x) -> str:
    h = hashlib.sha256()
    for t in booster.models:
        leaf_count = np.bincount(t.leaf_index_by_replay(x),
                                 minlength=t.num_leaves)
        for ints in (t.split_feature, t.threshold_bin, t.left_child,
                     t.right_child, leaf_count):
            h.update(np.asarray(ints, np.int64).tobytes())
    return h.hexdigest()[:16]


# recorded on this tree; masked and compacted leaf-wise grow one structure
PINNED_STRUCTURE = {
    ("leafwise", "float32"): "31bb6b2e9571f039",
    ("leafwise_compact", "float32"): "31bb6b2e9571f039",
    ("depthwise", "float32"): "f69acab6bc3a5e4d",
    # int8: re-recorded by PR 36, one quantisation scale a tree
    # (ops/hist_pallas.quant_max_of) where every pass took its own
    ("leafwise", "int8"): "643a38052b8b0af7",
    ("leafwise_compact", "int8"): "643a38052b8b0af7",
    ("depthwise", "int8"): "7fce33bc69a39db6",
}
_POLICY_KW = {
    "leafwise": dict(grow_policy="leafwise"),
    "leafwise_compact": dict(grow_policy="leafwise",
                             leafwise_compact="true"),
    "depthwise": dict(grow_policy="depthwise"),
}


@pytest.fixture(scope="module")
def boosters():
    x, y = _data()
    return {(policy, dtype): _train(x, y, hist_dtype=dtype,
                                    **_POLICY_KW[policy])
            for policy, dtype in PINNED_STRUCTURE}


def test_all_policies_trained(boosters):
    for name, b in boosters.items():
        assert len(b.models) == 4, name
        for t in b.models:
            assert t.num_leaves > 1, name


def test_masked_equals_compact(boosters):
    """The compacted leaf-wise grower is the masked grower's split
    sequence with compacted data movement: identical split structure and
    leaf counts; leaf values/scores within the cross-program f32 budget
    (fusion dust, see module docstring — NOT bitwise on XLA CPU)."""
    a = boosters["leafwise", "float32"]
    b = boosters["leafwise_compact", "float32"]
    for k, (t1, t2) in enumerate(zip(a.models, b.models)):
        assert t1.num_leaves == t2.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-5, atol=5e-7, err_msg=f"tree {k}")
    np.testing.assert_allclose(np.asarray(a.score), np.asarray(b.score),
                               rtol=1e-5, atol=2e-6)


def test_masked_equals_compact_int8(boosters):
    """Same pin under int8 histograms: structure exact; leaf values
    within the documented cross-program 1-ulp budget (XLA CPU contracts
    the dequantize multiply into an FMA in some program contexts —
    grower_leafcompact module docstring)."""
    a = boosters["leafwise", "int8"]
    b = boosters["leafwise_compact", "int8"]
    for k, (t1, t2) in enumerate(zip(a.models, b.models)):
        assert t1.num_leaves == t2.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-6, atol=1e-9, err_msg=f"tree {k}")


@pytest.mark.parametrize("policy,hist_dtype", sorted(PINNED_STRUCTURE))
def test_tree_structure_pinned(boosters, policy, hist_dtype):
    """Every policy grows the trees it grew: the drift detector for the
    grower, in what no compiler moves."""
    got = _structure_digest(boosters[policy, hist_dtype], _data()[0])
    assert got == PINNED_STRUCTURE[policy, hist_dtype], (
        "%s %s grew other trees (structure digest %s)"
        % (policy, hist_dtype, got))
