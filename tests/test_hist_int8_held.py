"""The int histogram kernel's held one-hot
(``ops/hist_pallas.held_onehot``): the unfolded passes contract with the
one-hot as the operand the MXU holds and the live value rows streamed.
The pass turned round equals the streamed one bit for bit, in one block
and on the feature-block grid; the rule's table; its counter; and the
trees grown over either are the same text.  Moved whole out of
``tests/test_hist_int8.py``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


def _level_inputs(rng, F, N, B, num_cols):
    """int8 bins carrying uint8 codes up to B - 1, quantised levels and a
    leaf column (or -1, masked out) per row."""
    bins = rng.randint(0, B, (F, N)).astype(np.uint8)
    cid = rng.randint(-1, num_cols, N)
    vals = np.stack([rng.randint(-127, 128, N), rng.randint(0, 128, N),
                     np.ones(N, np.int64)]) * (cid >= 0)
    packed = np.concatenate([vals, cid[None]]).astype(np.int8)
    return jnp.asarray(bins.astype(np.int8)), jnp.asarray(packed), cid


@pytest.mark.parametrize("dtype", ["int8", "bf16"])
@pytest.mark.parametrize("F", [28, 100])
@pytest.mark.parametrize("B", [255, 256])
@pytest.mark.parametrize("num_cols,held", [(64, 192), (43, 160), (32, 96)])
def test_held_onehot_bit_identical(num_cols, held, B, F, dtype):
    """A pass turned round (the one-hot the held operand, the live value
    rows streamed, the accumulator transposed back and padded) sums every
    product into the cell it went to with the one-hot streamed: the same
    [F, B, lanes] int32 array, with uint8 codes >= 128 and masked rows,
    in one block (F = 28) and on the rotating feature-block grid (F = 100,
    the last block part padding)."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import (LANES, _hist_pallas_raw_fn,
                                              feature_grid, held_onehot)
    N, chunk = 1024, 512
    lanes = LANES if num_cols <= 42 else 192
    assert held_onehot(3, num_cols, B, lanes, dtype) == held
    fb, blocks = feature_grid(F, B, lanes, chunk, held)
    assert (blocks > 1) == (F > 28) and fb * blocks >= F
    bins, packed, cid = _level_inputs(
        np.random.RandomState(num_cols + B + F), F, N, B, num_cols)
    with pltpu.force_tpu_interpret_mode():
        streamed, turned = (np.asarray(_hist_pallas_raw_fn(
            bins, packed, B=B, chunk=chunk, dtype=dtype, lanes=lanes,
            held=rows)) for rows in (0, held))
    assert turned.shape == (F, B, lanes) and turned.dtype == np.int32
    np.testing.assert_array_equal(turned, streamed)
    assert int(turned[:, :, 2:3 * num_cols:3].sum()) == F * int(
        (cid >= 0).sum())
    assert not turned[:, :, 3 * num_cols:].any()


def test_held_onehot_rule():
    """Which passes turn round, and how many value rows they stream, from
    their static shapes: the integer modes where the live value rows (up
    to the 32-row tile) times the one-hot's tiles are fewer than the
    one-hot's rows times the value block's tiles.  Never float gradients,
    not 33-42 columns (128 rows against two tiles: no fewer), and not the
    64-bin classes of the mixed-bin layout, whose one-hot is half a
    tile."""
    from lightgbm_tpu.ops.hist_pallas import (feature_grid, held_onehot,
                                              rotating_feature_block)
    for dtype in ("int8", "bf16"):
        for B in (255, 256):
            assert [held_onehot(3, c, B, 128, dtype)
                    for c in (17, 21, 22, 32, 33, 42)] == [
                64, 64, 96, 96, 0, 0]
            assert [held_onehot(3, c, B, 192, dtype)
                    for c in (43, 53, 54, 64)] == [160, 160, 192, 192]
        assert [held_onehot(3, 64, B, 192, dtype)
                for B in (16, 64, 96, 128, 200)] == [0, 0, 0, 192, 192]
        assert [held_onehot(3, 32, B, 128, dtype)
                for B in (16, 64, 96, 128, 200)] == [0, 0, 0, 96, 96]
    assert not any(held_onehot(3, c, 255, 128, "int8")
                   for c in (1, 2, 4, 8, 16))          # hist_fold folds them
    for stats, c, lanes in ((3, 1, 128), (3, 32, 128), (3, 64, 192),
                            (5, 25, 128), (5, 38, 192)):
        assert held_onehot(stats, c, 256, lanes, "bf16v") == 0
    # the account of the rotating block follows the accumulator's layout:
    # [held, 256] cells a feature turned round, [256, 256] streamed
    assert rotating_feature_block(255, 192, 2048) == 24
    assert rotating_feature_block(255, 192, 2048, 192) == 32
    assert rotating_feature_block(255, 128, 2048) == 48
    assert feature_grid(2000, 255, 192, 2048, 192) == (32, 63)
    assert feature_grid(64, 255, 192, 2048, 192) == (64, 1)


@pytest.mark.parametrize("num_leaves,want", [(255, 2), (127, 1), (63, 0)])
def test_held_onehot_counter(monkeypatch, num_leaves, want):
    """hist/pallas_held_onehot, counted once a pass at trace time: a
    255-leaf level-wise tree has two unfolded passes that turn round
    (level 6, 32 leaf columns, and level 7, 64), a 127-leaf tree the
    first of them, a 63-leaf tree, every pass folded, none."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.reset()
    telemetry.enable()
    try:
        n, f = 4168 + num_leaves, 5       # shapes no other test traces
        S = jax.ShapeDtypeStruct
        jax.make_jaxpr(lambda *a: grow_tree_depthwise_jit(
            *a, compute_dtype="int8", num_leaves=num_leaves,
            num_bins_max=255, min_data_in_leaf=1,
            min_sum_hessian_in_leaf=1.0, max_depth=-1, packing=None))(
            S((f, n), jnp.uint8), S((n,), jnp.float32),
            S((n,), jnp.float32), S((n,), jnp.bool_), S((f,), jnp.bool_),
            S((f,), jnp.int32))
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    assert counters["hist/pallas_held_onehot"] == want
    assert counters["hist/pallas_int8"] == {255: 8, 127: 7, 63: 6}[num_leaves]


def test_held_onehot_same_trees(monkeypatch):
    """The grower over the turned-round pass and over the streamed one:
    the model text of three 255-leaf level-wise iterations is byte-equal
    (the kernel's ints being equal does not say so: the float histograms
    behind it must come out in the same layout)."""
    import lightgbm_tpu as lgb
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu import costmodel, telemetry
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models import gbdt as gbdt_mod
    from lightgbm_tpu.ops import hist_pallas
    rng = np.random.RandomState(31)
    x = rng.randn(3001, 5)                # a shape no other test trains
    y = ((x[:, 0] * x[:, 1] + 0.5 * x[:, 2] + 0.3 * rng.randn(3001)) > 0
         ).astype(np.float32)
    params = {"objective": "binary", "num_leaves": "255", "max_bin": "255",
              "min_data_in_leaf": "1", "min_sum_hessian_in_leaf": "0.01",
              "num_iterations": "3", "learning_rate": "0.2",
              "grow_policy": "depthwise", "hist_dtype": "int8"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rule = hist_pallas.held_onehot

    def train(turn):
        # nothing traced before may answer: not jax's caches, not the
        # booster's own table of chunk programs
        jax.clear_caches()
        monkeypatch.setattr(gbdt_mod, "_CHUNK_PROGRAMS", {})
        ruled = []
        monkeypatch.setattr(
            hist_pallas, "held_onehot",
            lambda *a: ruled.append(turn and rule(*a)) or ruled[-1])
        # Fence mode: every span waits for its values, so the grower's
        # program has ended before the loop dispatches the next
        # operation.  The interpreter's kernels call back into Python,
        # and a callback runs jax operations of its own (it iterates the
        # grid index, an Array); one that the host dispatches meanwhile
        # can wait on the program while the callback waits on it.  That
        # is where this test stood still in whole runs of PRs 31 to 33
        # (the stacks of PR 33's run: the main thread in
        # ``exact_table_lookup``'s slice, the callback in
        # ``interpret_pallas_call.get``).  The program is the same with
        # telemetry on (tests/test_trace_scopes.py)
        telemetry.enable(fence=True)
        # the cost registry would keep the first run's executable and
        # answer the second run with it
        costmodel.disable()
        try:
            with pltpu.force_tpu_interpret_mode():
                booster = lgb.train(params,
                                    Dataset.from_arrays(x, y, max_bin=255))
                # nothing of this run may still be on the device when
                # the next clear_caches() drops its programs
                jax.block_until_ready(booster.score)
                jax.effects_barrier()
        finally:
            telemetry.disable()
            telemetry.reset()
        return "\n".join(t.to_string() for t in booster.models), ruled

    text, ruled = train(True)
    # of the eight passes levels 6 and 7 were traced turned round, and
    # level 7 decided splits: every tree grew past 128 leaves
    assert set(ruled) == {0, 96, 192}
    assert all(int(t.split()[0]) > 128
               for t in text.split("num_leaves=")[1:])
    text_streamed, ruled = train(False)
    assert ruled and not any(ruled)
    jax.clear_caches()
    assert text == text_streamed
