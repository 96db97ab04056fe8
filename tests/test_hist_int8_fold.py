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

from lightgbm_tpu.ops.hist_pallas import (hist_pallas_leafbatch,
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
    the hist/pallas_fold_<k> counters of one traced level-wise tree."""
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

    # one 255-leaf level-wise tree traced on the TPU route (shapes no other
    # test traces: a cached trace would count nothing): eight passes of
    # 1, 1, 2, 4, 8, 16, 32 and 64 leaf columns
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.reset()
    telemetry.enable()
    try:
        n, f = 4104, 5
        S = jax.ShapeDtypeStruct
        jax.make_jaxpr(lambda *a: grow_tree_depthwise_jit(
            *a, compute_dtype="int8", num_leaves=255, num_bins_max=255,
            min_data_in_leaf=1, min_sum_hessian_in_leaf=1.0, max_depth=-1,
            packing=None))(
            S((f, n), jnp.uint8), S((n,), jnp.float32),
            S((n,), jnp.float32), S((n,), jnp.bool_), S((f,), jnp.bool_),
            S((f,), jnp.int32))
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    folds = {k: v for k, v in counters.items()
             if k.startswith("hist/pallas_fold_")}
    assert folds == {"hist/pallas_fold_8": 3, "hist/pallas_fold_4": 2,
                     "hist/pallas_fold_2": 1, "hist/pallas_fold_1": 2}
    assert sum(folds.values()) == counters["hist/pallas_int8"] == 8
