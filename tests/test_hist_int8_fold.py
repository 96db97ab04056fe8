"""The histogram kernel's bin fold (``ops/hist_pallas.hist_fold``): on
the narrow levels the low bits of the bin code move into the idle value
rows.  The folded integer pass equals the unfolded one and the XLA oracle
bit for bit at every fold its layout allows; the rule's table, the float
mode's ("bf16v", three and five statistics a column) beside the integer
modes'; the counters of a traced tree.  The folded float pass against the
unfolded one: ``tests/test_hist_float_pallas.py``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.hist_pallas import (INT8_HIST_MAX_ROWS,
                                          hist_pallas_leafbatch,
                                          hist_quant_xla, quantize_values)


# both sides of every fold boundary of ops/hist_pallas.hist_fold, the
# 128 -> 192 lane step (42 | 43) and the widest single pass
FOLD_COLS = (1, 2, 4, 5, 8, 10, 16, 17, 21, 32, 42, 43, 64)


@pytest.mark.parametrize("B", [256, 64])
@pytest.mark.parametrize("num_cols", FOLD_COLS)
def test_bin_fold_bit_identical(num_cols, B):
    """The bin fold (low bits of the bin code moved into the idle value
    rows) sums every product into the cell it went to before, in int32:
    the routed kernel equals the XLA oracle bit for bit, and the raw
    kernel at every fold its layout allows equals itself at fold 1 —
    with uint8 codes >= 128, a ragged last chunk and masked-out rows."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import (LANES, _hist_pallas_raw_fn,
                                              _hist_quant_xla_one,
                                              fold_options)
    rng = np.random.RandomState(100 * num_cols + B)
    F, N, chunk = 3, 2500, 1024
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, num_cols, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.8)
    if num_cols <= 42:
        via_xla = hist_quant_xla(bins, grad, hess, cid, ok, num_cols, B)
    else:
        # the oracle's wrapper splits at 42 columns and quantises each
        # group apart; the Pallas route takes up to 64 in one pass
        via_xla = _hist_quant_xla_one(bins, grad, hess, cid, ok, num_cols,
                                      B, chunk=65536, rng_bits=None)
    with pltpu.force_tpu_interpret_mode():
        via_pl = hist_pallas_leafbatch(bins, grad, hess, cid, ok, num_cols,
                                       B, chunk=chunk, dtype="int8")
    np.testing.assert_array_equal(np.asarray(via_xla), np.asarray(via_pl))
    assert float(via_pl[..., 2].sum()) == float(F * int(ok.sum()))

    packed, _ = quantize_values(grad, hess, ok, cid)
    pad = (-N) % chunk
    bins8 = jnp.pad(bins.astype(jnp.int8), ((0, 0), (0, pad)))
    packed = jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1)
    lanes = LANES if num_cols <= 42 else 192
    # every fold in int8, the deepest one in the bf16 level mode too
    runs = [(1, None, "int8")] + [
        (fold, gw, "int8") for fold, gw, _ in
        fold_options(3, num_cols, B, lanes)]
    runs.append(runs[-1][:2] + ("bf16",))
    with pltpu.force_tpu_interpret_mode():
        raw = [np.asarray(_hist_pallas_raw_fn(
            bins8, packed, B=B, chunk=chunk, dtype=dtype, lanes=lanes,
            fold=fold, gw=gw)) for fold, gw, dtype in runs]
    assert raw[0].shape == (F, B, lanes)
    for run, acc in zip(runs, raw):
        np.testing.assert_array_equal(acc, raw[0], err_msg=str(run))


def test_bin_fold_rule_and_counters(monkeypatch):
    """The rule's table at the cell's shapes, where it must not fold, and
    the hist/pallas_fold_<k> counters of one traced level-wise tree, with
    the two statistics a column of ``min_data_in_leaf <= 1`` and with the
    three of ``min_data_in_leaf=2``."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    from lightgbm_tpu.ops.hist_pallas import fold_options, hist_fold
    table = {1: (8, 3), 2: (8, 6), 3: (8, 9), 4: (4, 12), 5: (4, 16),
             8: (4, 24), 10: (4, 30), 11: (2, 36), 16: (2, 48),
             17: (1, None), 21: (1, None), 32: (1, None), 42: (1, None)}
    # three statistics a column: the integer modes, and float gradients
    # ("bf16v") riding single bf16 operands
    for num_cols, want in table.items():
        for B in (255, 256):
            assert hist_fold(3, num_cols, B, 128) == want, (num_cols, B)
    # the float32 pair's five statistics a column (g_hi, g_lo, h_hi, h_lo,
    # count), written out from fold_options: the leaf-wise pass of one
    # column builds 32 + 8 * 5 = 72 operand rows a feature where it built
    # 256, two columns 64 + 4 * 10 = 104; nine columns (gw 48) are the
    # last that save an eighth, and 192 lanes never fold
    rows5 = {(c, fold): rows for c in (1, 2)
             for fold, _gw, rows in fold_options(5, c, 256, 128)}
    assert (rows5[1, 8], rows5[2, 4]) == (72, 104)
    table5 = {1: (8, 5), 2: (4, 10), 3: (4, 16), 4: (4, 20), 5: (4, 26),
              8: (2, 40), 9: (2, 48), 10: (1, None), 25: (1, None)}
    for num_cols, want in table5.items():
        for B in (255, 256):
            assert hist_fold(5, num_cols, B, 128) == want, (num_cols, B)
    assert hist_fold(3, 43, 256, 192) == (1, None)
    assert hist_fold(5, 26, 256, 192) == (1, None)
    # a 64-bin class of the mixed-bin layout: the 32-row floor on the
    # one-hot holds it to fold 2, and only while that saves an eighth
    assert [hist_fold(3, c, 64, 128) for c in (1, 4, 5)] == [
        (2, 4), (2, 12), (1, None)]
    # two statistics a column (grad, hess): fold 8 holds four columns,
    # fold 4 sixteen, and 32 and 64 columns stay unfolded
    table2 = {1: (8, 2), 2: (8, 4), 4: (8, 8), 5: (4, 10), 8: (4, 16),
              16: (4, 32), 17: (2, 36), 24: (2, 48), 25: (1, None),
              32: (1, None), 64: (1, None)}
    for num_cols, want in table2.items():
        for B in (255, 256):
            assert hist_fold(2, num_cols, B, 128) == want, (num_cols, B)
    # the benchmark's tables have 254 bins: there 16 columns of two fold
    # by 2 (127 + 64 operand rows) and not by 4 (64 + 128)
    assert [hist_fold(2, c, 254, 128) for c in (1, 2, 4, 8, 16, 32)] == [
        (8, 2), (8, 4), (8, 8), (4, 16), (2, 32), (1, None)]

    # one 255-leaf level-wise tree traced on the TPU route (shapes no other
    # test traces: a cached trace would count nothing): eight passes of
    # 1, 1, 2, 4, 8, 16, 32 and 64 leaf columns
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def traced(n, min_data_in_leaf):
        telemetry.reset()
        telemetry.enable()
        try:
            f = 5
            S = jax.ShapeDtypeStruct
            jax.make_jaxpr(lambda *a: grow_tree_depthwise_jit(
                *a, compute_dtype="int8", num_leaves=255, num_bins_max=255,
                min_data_in_leaf=min_data_in_leaf,
                min_sum_hessian_in_leaf=1.0, max_depth=-1, packing=None))(
                S((f, n), jnp.uint8), S((n,), jnp.float32),
                S((n,), jnp.float32), S((n,), jnp.bool_),
                S((f,), jnp.bool_), S((f,), jnp.int32))
            counters = telemetry.snapshot()["counters"]
        finally:
            telemetry.disable()
            telemetry.reset()
        folds = {k: v for k, v in counters.items()
                 if k.startswith("hist/pallas_fold_")}
        assert sum(folds.values()) == counters["hist/pallas_int8"] == 8
        return folds, counters

    folds, counters = traced(4104, 1)
    assert folds == {"hist/pallas_fold_8": 4, "hist/pallas_fold_4": 2,
                     "hist/pallas_fold_1": 2}
    assert counters["hist/stats_2"] == 8 and "hist/stats_3" not in counters
    folds, counters = traced(4105, 2)
    assert folds == {"hist/pallas_fold_8": 3, "hist/pallas_fold_4": 2,
                     "hist/pallas_fold_2": 1, "hist/pallas_fold_1": 2}
    assert counters["hist/stats_3"] == 8 and "hist/stats_2" not in counters


# the level-wise tree's passes with two statistics a column: (fold,
# held) of each of 1, 2, 4, 8, 16, 32 and 64 leaf columns at 255 bins
TWO_STAT_PASSES = {1: (8, 0), 2: (8, 0), 4: (8, 0), 8: (4, 0), 16: (4, 0),
                   32: (1, 64), 64: (1, 0)}


@pytest.mark.parametrize("num_cols,F,cap", [
    (c, 3, None) for c in TWO_STAT_PASSES] + [
    # the feature-block grid (48 features a block at 128 lanes, 48 with
    # the one-hot held), and a pass cut into three accumulation ranges
    (32, 100, None), (64, 100, None), (4, 3, 1100), (64, 3, 1100)])
def test_two_statistics_are_the_first_two_of_three(monkeypatch, num_cols,
                                                   F, cap):
    """A level pass with two statistics a column (grad, hess) and slot
    targets: each column sums the rows whose new slot is its target, the
    smaller child of a parent that split, and its ``[F, B, 2]`` cells
    equal the first two statistics of the three-statistic pass over the
    same rows as leaf column and selection, bit for bit, at every fold
    and held shape of a 255-leaf tree's passes; the hessian's integer
    sums handed back beside them are the scatter-add oracle's; the XLA
    route reads the same; a parent that did not split sums nothing."""
    from lightgbm_tpu.ops.histogram import hist_quant_segsum
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import hist_pallas
    from lightgbm_tpu.ops.hist_pallas import (NO_TARGET, _ranged_rows,
                                              held_onehot, hist_fold,
                                              quant_max_of)
    if cap is not None:
        monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", cap)
        jax.clear_caches()
    B, N, chunk = 255, 3000, 512
    lanes = 128
    assert (hist_fold(2, num_cols, B, lanes)[0],
            held_onehot(2, num_cols, B, lanes, "int8")) == \
        TWO_STAT_PASSES[num_cols]
    assert _ranged_rows(N, chunk)[0] == (1 if cap is None else 3)
    rng = np.random.RandomState(num_cols + F)
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    ok = jnp.asarray(rng.rand(N) < 0.8)
    slot = jnp.asarray(rng.randint(0, 2 * num_cols, N).astype(np.int32))
    chosen = rng.rand(num_cols) < 0.75
    chosen[0] = num_cols == 1            # a parent that did not split
    small_right = rng.rand(num_cols) < 0.5
    targets = jnp.asarray(np.where(
        chosen, 2 * np.arange(num_cols) + small_right, NO_TARGET
    ).astype(np.int32))
    sel = ok & (slot == targets[slot // 2])
    scale = quant_max_of(grad, hess, ok)
    with pltpu.force_tpu_interpret_mode():
        three = hist_pallas_leafbatch(bins, grad, hess, slot // 2, sel,
                                      num_cols, B, chunk=chunk,
                                      quant_max=scale)
        two, hsum = hist_pallas_leafbatch(
            bins, grad, hess, slot, ok, num_cols, B, chunk=chunk,
            quant_max=scale, stats=2, targets=targets)
    assert two.shape == (num_cols, F, B, 2) and three.shape[-1] == 3
    assert hsum.shape == (num_cols, F, B) and hsum.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(two), np.asarray(three[..., :2]))
    xla = hist_quant_xla(bins, grad, hess, slot, ok, num_cols, B,
                         chunk=chunk, quant_max=scale, stats=2,
                         targets=targets)
    # the oracle sums in one accumulator, under the cap it was made for
    monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS",
                        INT8_HIST_MAX_ROWS)
    oracle = hist_quant_segsum(bins, grad, hess, slot, ok, num_cols, B,
                               quant_max=scale, stats=2, targets=targets)
    for got in (xla, oracle):
        np.testing.assert_array_equal(np.asarray(two), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(hsum), np.asarray(got[1]))
    assert np.asarray(two).any() and np.asarray(hsum).any()
    np.testing.assert_array_equal(np.asarray(hsum) != 0,
                                  np.asarray(two[..., 1]) != 0)
    assert num_cols == 1 or not np.asarray(two[0]).any()
    if cap is not None:
        jax.clear_caches()
