"""Block-local mixed-bin packing on the 2-D ownership mesh (ISSUE 12).

Through PR 11 the hybrid/voting learners forced the uniform layout
(``needs_uniform_layout``): the global class-contiguous permutation and
contiguous feature-block ownership did not compose.  The block-local
layout (io/binning.BlockedPackSpec) computes the bin-width-class
permutation PER owned feature block — it never crosses a block boundary,
so packing commutes with ownership and the owned-block psum /
packed-SplitInfo allreduce ride unchanged.  Pinned here:

- plan rules: per-block-uniform class counts (the min across blocks),
  degenerate cases (a block without narrow features -> uniform layout),
  the block_view / global ranges / c2p contracts;
- packed-vs-uniform BIT-identity (trees, thresholds, leaf values,
  scores, model text, valid replay) under hybrid AND voting, int8 f32,
  per-iteration AND fused-chunk, on the (2,2) dryrun mesh.  int8 is
  robustly bitwise (the canonical reorder happens IN the int domain
  before dequantize — ops/hist_pallas feat_gather); f32 bitwise holds at
  the pinned schemas (XLA-CPU's dot reduction order is shape-dependent,
  the same property PR 6's serial f32 pins rely on);
- serial == packed-hybrid == packed-voting under int8 (the ISSUE 12
  acceptance row; bitwise at the pinned schema — like the PR 9 pins,
  int8 cross-schedule identity is exact where the root-stat bin-sums
  round identically, 1-ulp elsewhere).
"""
import numpy as np
import pytest

from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.binning import (BlockedPackSpec, NARROW_BINS,
                                     plan_feature_packing_blocked)
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.parallel.learners import create_parallel_learner


# --------------------------------------------------------------- plan rules

def test_blocked_plan_per_block_uniform_counts():
    # blocks of 4: narrow counts 2 and 1 -> uniform c_n = 1
    nb = np.array([5, 9, 255, 255,   7, 255, 255, 255])
    spec = plan_feature_packing_blocked(nb, 255, block=4)
    assert isinstance(spec, BlockedPackSpec)
    assert spec.counts == (1, 3)
    assert spec.block == 4
    # block 0 stores its first narrow feature (0) first; surplus narrow
    # feature 1 rides the wide segment in canonical order
    assert spec.perm == (0, 1, 2, 3, 4, 5, 6, 7)
    # global ranges interleave per block: (narrow, wide) x 2 blocks
    assert spec.ranges == ((0, 1, NARROW_BINS), (1, 3, 255),
                           (4, 1, NARROW_BINS), (5, 3, 255))
    # the shard-uniform block view: identity perm, per-block counts
    bv = spec.block_view
    assert bv.counts == (1, 3) and bv.perm == (0, 1, 2, 3)


def test_blocked_plan_permutes_within_blocks_only():
    nb = np.array([255, 5, 255, 9,   255, 255, 7, 255])
    spec = plan_feature_packing_blocked(nb, 255, block=4)
    assert spec.counts == (1, 3)
    # narrow-first WITHIN each block, remainder canonical; the
    # permutation never crosses the block boundary
    assert spec.perm == (1, 0, 2, 3, 6, 4, 5, 7)
    assert all(p // 4 == i // 4 for i, p in enumerate(spec.perm))
    # c2p inverts perm
    for f, p in enumerate(spec.c2p):
        assert spec.perm[p] == f


def test_blocked_plan_degenerates_without_narrow_in_a_block():
    # block 1 is all wide -> c_n = 0 -> uniform layout
    nb = np.array([5, 9, 255, 255,   255, 255, 255, 255])
    assert plan_feature_packing_blocked(nb, 255, block=4) is None
    # single class and env-style off behave like the global plan
    assert plan_feature_packing_blocked(
        np.array([5, 9, 7, 3]), 9, block=2) is None
    assert plan_feature_packing_blocked(nb, 255, block=4,
                                        mode="false") is None


def test_blocked_plan_refuses_all_padding_shard():
    # F=5 over 4 shards (block=2): shard 3 owns only ownership padding —
    # its clamped duplicate lanes would land a wide feature in the
    # narrow segment, so the plan refuses the mesh (uniform layout)
    nb = np.array([5, 255, 9, 255, 7])
    assert plan_feature_packing_blocked(nb, 255, block=2, shards=4) is None
    # the same feature set on 2 shards (block=3) packs fine
    assert plan_feature_packing_blocked(nb, 255, block=3,
                                        shards=2) is not None


def test_blocked_plan_partial_last_block():
    # F=6, block=4: the last block has 2 real features (1 narrow) ->
    # c_n = min(2, 1) = 1
    nb = np.array([5, 9, 255, 255,   7, 255])
    spec = plan_feature_packing_blocked(nb, 255, block=4)
    assert spec.counts == (1, 3)
    assert spec.ranges == ((0, 1, NARROW_BINS), (1, 3, 255),
                           (4, 1, NARROW_BINS), (5, 1, 255))
    assert sum(cnt for _, cnt, _ in spec.ranges) == 6


# ------------------------------------------------------------ training pins

def _mixed_xy(n, f, seed):
    rng = np.random.RandomState(seed)
    cols = [rng.randn(n) if j % 2 == 0
            else rng.randint(0, 4 + j, n).astype(float) for j in range(f)]
    x = np.stack(cols, axis=1)
    w = rng.randn(f)
    y = (((x - x.mean(0)) / (x.std(0) + 1e-9)) @ w
         + rng.randn(n) > 0).astype(np.float32)
    return x, y


@pytest.fixture(scope="module")
def mixed_ds():
    x, y = _mixed_xy(1500, 8, 3)
    ds = Dataset.from_arrays(x, y, max_bin=255)
    nb = ds.num_bins
    assert (nb <= NARROW_BINS).any() and (nb > NARROW_BINS).any()
    return ds


@pytest.fixture(scope="module")
def valid_ds():
    x, y = _mixed_xy(400, 8, 17)
    return Dataset.from_arrays(x, y, max_bin=255)


def _train(ds, tl, mixed, extra=None, iters=3, chunk=False, valid=None):
    p = {"objective": "binary", "num_leaves": "15", "min_data_in_leaf": "20",
         "min_sum_hessian_in_leaf": "1.0", "learning_rate": "0.1",
         "tree_learner": tl, "num_machines": "4", "mixed_bin": mixed}
    p.update(extra or {})
    cfg = OverallConfig()
    cfg.set(p, require_data=False)
    b = GBDT()
    learner = None if tl == "serial" else create_parallel_learner(cfg)
    b.init(cfg.boosting_config, ds,
           create_objective(cfg.objective_type, cfg.objective_config),
           learner=learner)
    if valid is not None:
        from lightgbm_tpu.metrics import create_metric
        b.add_valid_dataset(valid, [create_metric("auc", cfg.metric_config)])
    if chunk:
        b.train_chunk(iters)
        b.flush_pipeline()
    else:
        for _ in range(iters):
            if b.train_one_iter(is_eval=valid is not None):
                break
    return b


def _assert_bitwise(on, off, tag, model_text=False):
    assert on._pack_spec is not None, tag
    assert off._pack_spec is None, tag
    assert len(on.models) == len(off.models), tag
    for k, (t1, t2) in enumerate(zip(on.models, off.models)):
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature,
                                      err_msg=f"{tag} tree {k}")
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin,
                                      err_msg=f"{tag} tree {k}")
        np.testing.assert_array_equal(np.asarray(t1.leaf_value),
                                      np.asarray(t2.leaf_value),
                                      err_msg=f"{tag} tree {k}")
        np.testing.assert_array_equal(np.asarray(t1.threshold),
                                      np.asarray(t2.threshold),
                                      err_msg=f"{tag} tree {k}")
        if model_text:
            assert t1.to_string() == t2.to_string(), f"{tag} tree {k}"
    np.testing.assert_array_equal(np.asarray(on.score),
                                  np.asarray(off.score), err_msg=tag)
    for e1, e2 in zip(on.valid_datasets, off.valid_datasets):
        np.testing.assert_array_equal(np.asarray(e1["score"]),
                                      np.asarray(e2["score"]),
                                      err_msg=tag + " valid replay")


def test_hybrid_int8_packed_bit_identity(mixed_ds, valid_ds):
    # per-iteration leaf-wise, model text + scores + valid replay
    extra = {"feature_shards": "2", "hist_dtype": "int8",
             "grow_policy": "leafwise"}
    on = _train(mixed_ds, "hybrid", "true", extra, valid=valid_ds)
    off = _train(mixed_ds, "hybrid", "false", extra, valid=valid_ds)
    assert hasattr(on._pack_spec, "block")   # the BLOCK-LOCAL spec
    _assert_bitwise(on, off, "hybrid int8 leafwise", model_text=True)


def test_voting_int8_packed_bit_identity(mixed_ds):
    extra = {"feature_shards": "2", "top_k": "4", "hist_dtype": "int8",
             "grow_policy": "leafwise"}
    _assert_bitwise(_train(mixed_ds, "voting", "true", extra),
                    _train(mixed_ds, "voting", "false", extra),
                    "voting int8 leafwise")


def test_hybrid_int8_fused_chunk_packed_bit_identity(mixed_ds):
    extra = {"feature_shards": "2", "hist_dtype": "int8",
             "grow_policy": "depthwise"}
    _assert_bitwise(
        _train(mixed_ds, "hybrid", "true", extra, iters=3, chunk=True),
        _train(mixed_ds, "hybrid", "false", extra, iters=3, chunk=True),
        "hybrid int8 depthwise chunk")


def test_serial_equals_packed_hybrid_and_voting_int8():
    # the ISSUE 12 acceptance row: serial == hybrid == voting under int8
    # WITH block-local packing ON: the same structure, leaf values to the
    # cross-schedule float32 budget of tests/test_hybrid_voting.py (the
    # leaf sums are float sums of dequantized bins, taken in each
    # schedule's order; 6.0e-7 relative measured here).
    x, y = _mixed_xy(3000, 12, 3)
    ds = Dataset.from_arrays(x, y, max_bin=255)
    extra8 = {"hist_dtype": "int8", "grow_policy": "leafwise"}
    s = _train(ds, "serial", "false", extra8)
    h = _train(ds, "hybrid", "true", dict(extra8, feature_shards="2"))
    v = _train(ds, "voting", "true",
               dict(extra8, feature_shards="2", top_k="12"))
    assert h._pack_spec is not None and v._pack_spec is not None
    for tag, o in (("hybrid", h), ("voting", v)):
        assert len(s.models) == len(o.models)
        for k, (t1, t2) in enumerate(zip(s.models, o.models)):
            np.testing.assert_array_equal(
                t1.split_feature, t2.split_feature,
                err_msg=f"serial vs packed-{tag} tree {k}")
            np.testing.assert_array_equal(
                t1.threshold_bin, t2.threshold_bin,
                err_msg=f"serial vs packed-{tag} tree {k}")
            np.testing.assert_allclose(
                t1.leaf_value, t2.leaf_value, rtol=1e-6, atol=0,
                err_msg=f"serial vs packed-{tag} tree {k}")


def test_mixed_bin_true_warns_and_degenerates_on_narrowless_block(caplog):
    # fs=2 over 4 features: block 1 = two wide features -> no narrow ->
    # the blocked plan degenerates to the uniform layout with a warning
    rng = np.random.RandomState(0)
    n = 600
    x = np.stack([rng.randint(0, 5, n).astype(float), rng.randn(n),
                  rng.randn(n), rng.randn(n)], axis=1)
    y = ((x[:, 1] > 0)).astype(np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=255)
    b = _train(ds, "hybrid", "true",
               {"feature_shards": "2", "grow_policy": "leafwise"}, iters=1)
    assert b._pack_spec is None


@pytest.mark.slow
@pytest.mark.parametrize("tl,extra", [
    ("hybrid", {"feature_shards": "2"}),
    ("voting", {"feature_shards": "2", "top_k": "2"}),
])
def test_f32_packed_bit_identity(tl, extra):
    # f32 bitwise needs per-pass shapes where the XLA-CPU dot reduction
    # order coincides between the per-class and uniform passes (the same
    # shape-dependence PR 6's serial f32 pins live with): pinned at
    # n=5000 rows (2500 per data shard)
    x, y = _mixed_xy(5000, 8, 3)
    ds = Dataset.from_arrays(x, y, max_bin=255)
    e = dict(extra, hist_dtype="float32", grow_policy="leafwise")
    _assert_bitwise(_train(ds, tl, "true", e), _train(ds, tl, "false", e),
                    "%s f32 leafwise" % tl)


@pytest.mark.slow
@pytest.mark.parametrize("tl,extra", [
    ("hybrid", {"feature_shards": "2", "leafwise_compact": "true"}),
    ("voting", {"feature_shards": "2", "top_k": "4",
                "leafwise_compact": "true"}),
    ("hybrid", {"feature_shards": "4"}),
])
def test_packed_bit_identity_more_cells(mixed_ds, tl, extra):
    # compacted pane (full-F canonical assembly via the global blocked
    # ranges) and the fs=4 mesh factoring
    e = dict(extra, hist_dtype="int8", grow_policy="leafwise")
    _assert_bitwise(_train(mixed_ds, tl, "true", e),
                    _train(mixed_ds, tl, "false", e),
                    "%s int8 %s" % (tl, extra))
