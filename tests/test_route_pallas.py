"""The level-wise grower's row-routing kernel (ops/route_pallas.py).

On the CPU the kernel runs under the Pallas interpreter.  Three things
are held: its three outputs equal the XLA routing's
(``grower_unified._route_level_xla``) element for element over the
shapes the grid has to follow, the rows each slot sends right are the
count of the new slot ids over ``row_mask``, and a tree grown with the
kernel under the level loop is the tree grown with the XLA routing.  The
chip's own compile of it is ``tests/test_tpu_compile_programs.py``'s and
``tests/test_tpu_compile_wide.py``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.io.binning import PackSpec
from lightgbm_tpu.models import grower_unified
from lightgbm_tpu.ops import route_pallas

B, L = 255, 255


def _level(F, N, P, seed, packing=None):
    """One level's inputs: uint8 codes over the whole byte, rows spread
    over the P slots, a fifth of the rows masked out, a third of the slots
    not chosen, the last slot splitting on the last feature."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, 256, (F, N)).astype(np.uint8)
    assert (bins >= 128).any()
    slot_id = rng.randint(0, P, N).astype(np.int32)
    out_leaf = rng.randint(0, L, N).astype(np.int32)
    row_mask = rng.rand(N) < 0.8
    feature = rng.randint(0, F, P).astype(np.int32)
    feature[-1] = F - 1
    threshold = rng.randint(0, B, P).astype(np.int32)
    chosen = rng.rand(P) < 0.67
    chosen[-1] = True
    right_leaf = rng.randint(1, L, P).astype(np.int32)
    feat_part = grower_unified.partition_feature(packing,
                                                 jnp.asarray(feature))
    return [jnp.asarray(a) for a in (bins, slot_id, out_leaf, row_mask)] + [
        feat_part] + [jnp.asarray(a) for a in (threshold, chosen,
                                               right_leaf)]


def _both(args):
    want = jax.block_until_ready(grower_unified._route_level_xla(
        *args, num_bins_max=B, num_leaves=L))
    # one program, waited for: the interpreter's callbacks run jax
    # operations of their own, and an operation the test dispatches while
    # they are due can wait on them for good (tests/test_hist_int8_held.py,
    # ``test_held_onehot_same_trees``)
    with pltpu.force_tpu_interpret_mode():
        got = jax.block_until_ready(
            jax.jit(route_pallas.route_level_pallas)(*args))
    return got, want


def _assert_equal(got, want, args=None):
    for name, g, w in zip(("slot_id", "out_leaf", "right"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    if args is not None:
        _assert_counts(got, args)


def _assert_counts(got, args):
    """The rows each slot sent right are the rows of ``row_mask`` that
    took its odd new slot: a count of the new slot ids."""
    slot_new, right = np.asarray(got[0]), np.asarray(got[2])
    mask = np.asarray(args[3])
    P = args[6].shape[0]
    counts = np.bincount(slot_new[mask], minlength=2 * P)
    assert counts.shape == (2 * P,)
    np.testing.assert_array_equal(right, counts[1::2])
    assert right.dtype == np.int32


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks small enough that a test's few thousand rows and few hundred
    columns make several of each: 1,024 rows a chunk, 128 columns a
    block."""
    monkeypatch.setattr(route_pallas, "MAX_CHUNK", 1024)
    monkeypatch.setattr(route_pallas, "MAX_FEATURE_BLOCK", 128)


# N = 2,432: two whole chunks of 1,024 rows and a tail of 384
@pytest.mark.parametrize("P", [1, 2, 16, 64, 128])
@pytest.mark.parametrize("F,grid", [
    (28, (28, 1, 1024, 3)),
    # several feature blocks, the last one ragged (300 = 2 x 128 + 44)
    (300, (128, 3, 1024, 3)),
])
def test_kernel_equals_xla_routing(small_blocks, F, grid, P):
    N = 2432
    assert route_pallas.route_grid(F, N) == grid
    args = _level(F, N, P, seed=F + P)
    got, want = _both(args)
    _assert_equal(got, want, args)
    # the case says something: rows went both ways and some stayed
    slot_id, out_leaf, right = (np.asarray(a) for a in got)
    assert right.any() and right.sum() < np.asarray(args[3]).sum()
    assert (slot_id % 2 == 1).any() and (slot_id % 2 == 0).any()


@pytest.mark.parametrize("P", [1, 2, 16, 64, 128])
def test_kernel_equals_xla_routing_at_2000_columns(P):
    """The wide table's width under the kernel's own block rule (four
    blocks of 512 columns, the last of 464), a split on column 1,999: ids
    past what bf16 or a byte holds."""
    F, N = 2000, 1152
    assert route_pallas.route_grid(F, N) == (512, 4, 2048, 1)
    args = _level(F, N, P, seed=P)
    got, want = _both(args)
    _assert_equal(got, want, args)
    last = np.asarray(args[1]) == P - 1
    assert last.any() and int(args[4][P - 1]) == 1999
    assert np.asarray(got[0])[last].max() == 2 * (P - 1) + 1


@pytest.mark.parametrize("N", [1000, 128, 3000])
def test_rows_that_are_no_whole_number_of_128(small_blocks, N):
    args = _level(28, N, 16, seed=N)
    got, want = _both(args)
    _assert_equal(got, want, args)


@pytest.mark.parametrize("P", [1, 4, 16, 128])
@pytest.mark.parametrize("N", [2433, 5000])
def test_right_counts_are_the_new_slots_count(small_blocks, N, P):
    """The kernel's counts at a ragged N, whose tail chunk runs past the
    table (the rows it reads there are not counted), against
    ``np.bincount`` of the new slot ids over ``row_mask``: every slot's
    right child, and its left child as its rows less those."""
    args = _level(28, N, P, seed=N + P)
    with pltpu.force_tpu_interpret_mode():
        got = jax.block_until_ready(
            jax.jit(route_pallas.route_level_pallas)(*args))
    _assert_counts(got, args)
    slot, mask = np.asarray(args[1]), np.asarray(args[3])
    counts = np.bincount(np.asarray(got[0])[mask], minlength=2 * P)
    np.testing.assert_array_equal(
        np.bincount(slot[mask], minlength=P) - np.asarray(got[2]),
        counts[0::2])
    assert counts[1::2].any() and counts[0::2].any()


def test_more_than_128_slots(small_blocks):
    """A level of 512 slots (1,023 leaves): the table in slabs of 128."""
    args = _level(28, 2432, 512, seed=9)
    got, want = _both(args)
    _assert_equal(got, want, args)
    assert np.asarray(got[0]).max() > 2 * 384


def test_partition_packing_remap(small_blocks):
    """Mixed-bin packing: the slot's canonical feature is remapped to the
    table's packed row before either route sees it."""
    F = 28
    perm = tuple(np.random.RandomState(3).permutation(F).tolist())
    packing = PackSpec(widths=(64, 255), counts=(10, 18), perm=perm)
    args = _level(F, 2432, 16, seed=77, packing=packing)
    plain = _level(F, 2432, 16, seed=77)
    assert not np.array_equal(np.asarray(args[4]), np.asarray(plain[4]))
    got, want = _both(args)
    _assert_equal(got, want, args)


def test_nothing_chosen_moves_no_row(small_blocks):
    args = _level(28, 2432, 16, seed=5)
    args[6] = jnp.zeros_like(args[6])
    got, want = _both(args)
    _assert_equal(got, want, args)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  2 * np.asarray(args[1]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(args[2]))
    assert not np.asarray(got[2]).any()


def test_gate(monkeypatch):
    ok = route_pallas.route_pallas_ok
    assert not ok(jnp.uint8, 255)                      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ok(jnp.uint8, 255) and ok(jnp.int8, 256)
    assert not ok(jnp.uint16, 1024) and not ok(jnp.uint8, 257)
    monkeypatch.setenv("LGBM_TPU_NO_PALLAS", "1")
    assert not ok(jnp.uint8, 255)


# ------------------------------------------------------------ same trees

@pytest.mark.parametrize("compute_dtype", ["int8", "float32"])
@pytest.mark.parametrize("num_leaves", [63, 255])
def test_same_trees(monkeypatch, small_blocks, compute_dtype, num_leaves):
    """``TreeArrays``, ``leaf_ids`` included, of one level-wise tree with
    the kernel under the level loop and with the XLA routing; the
    histograms take the CPU's route in both."""
    from lightgbm_tpu import telemetry
    n, f = 6000 + num_leaves, 6
    rng = np.random.RandomState(num_leaves)
    x = rng.randn(n, f)
    bins = np.stack([np.clip((np.argsort(np.argsort(x[:, k])) * 255) // n,
                             0, 254) for k in range(f)]).astype(np.uint8)
    grad = (x[:, 0] * x[:, 1] + 0.5 * x[:, 2]
            + rng.randn(n)).astype(np.float32)
    hess = rng.uniform(0.5, 1.0, n).astype(np.float32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(rng.rand(n) < 0.9), jnp.ones((f,), bool),
            jnp.full((f,), 255, jnp.int32))
    kw = dict(compute_dtype=compute_dtype, num_leaves=num_leaves,
              num_bins_max=255, min_data_in_leaf=1,
              min_sum_hessian_in_leaf=1e-3, max_depth=-1, packing=None)

    def grow(kernel):
        monkeypatch.setattr(grower_unified, "route_pallas_ok",
                            lambda *a: kernel)
        telemetry.reset()
        telemetry.enable()
        try:
            # a wrapper of its own: no trace made before may answer
            with pltpu.force_tpu_interpret_mode():
                tree = jax.block_until_ready(jax.jit(
                    lambda *a: grower_unified._grow_tree_depthwise_fn(
                        *a, **kw))(*args))
                tree = jax.tree.map(np.asarray, tree)
            return tree, dict(telemetry.snapshot()["counters"])
        finally:
            telemetry.disable()
            telemetry.reset()

    levels = grower_unified.num_levels(num_leaves)
    tree, counters = grow(True)
    assert counters["partition/route_pallas"] == levels
    assert "partition/route_xla" not in counters
    tree_xla, counters = grow(False)
    assert counters["partition/route_xla"] == levels
    assert "partition/route_pallas" not in counters
    # the last level split too: more leaves than the one before can make
    assert int(tree.num_leaves) > 1 << (levels - 1)
    for name, got, want in zip(tree._fields, tree, tree_xla):
        np.testing.assert_array_equal(got, want, name)
