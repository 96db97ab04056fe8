"""Quantized-gradient (int8) histogram path: XLA oracle ≡ Pallas kernel,
exact counts, and end-to-end training sanity.

The int8 path is the TPU throughput option (ops/hist_pallas.py): grad/hess
are rounded to 1/127 of their per-pass max and contracted on the int8 MXU.
The reference accumulates in double (bin.h:15-17); LightGBM's later
quantized-training work showed coarse gradient quantization preserves model
quality — these tests pin the machinery, scripts/auc_parity.py pins quality
at scale.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


from lightgbm_tpu.ops.histogram import histogram_leafbatch
from lightgbm_tpu.ops.hist_pallas import (hist_pallas_leafbatch,
                                          hist_quant_xla, quantize_values)


@pytest.fixture(scope="module")
def hist_inputs():
    rng = np.random.RandomState(3)
    F, N, B, C = 6, 5000, 32, 9
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.int8))
    grad = jnp.asarray((rng.randn(N) * 0.4).astype(np.float32))
    hess = jnp.asarray((rng.rand(N) * 0.25).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.85)
    return bins, grad, hess, cid, ok, F, N, B, C


def test_xla_quant_matches_pallas_interpret(hist_inputs):
    from jax.experimental.pallas import tpu as pltpu
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    via_xla = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        via_pl = hist_pallas_leafbatch(bins, grad, hess, cid, ok, C, B,
                                       chunk=1024, dtype="int8")
    np.testing.assert_array_equal(np.asarray(via_xla), np.asarray(via_pl))


def test_quantized_counts_exact_and_sums_close(hist_inputs):
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    exact = histogram_leafbatch(bins, grad, hess, cid, ok, C, B,
                                compute_dtype=jnp.float32)
    quant = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    np.testing.assert_array_equal(np.asarray(exact[..., 2]),
                                  np.asarray(quant[..., 2]))
    # per-cell error bounded by n_cell * scale/2 (round-to-nearest)
    gscale = float(jnp.max(jnp.abs(grad))) / 127.0
    counts = np.asarray(exact[..., 2])
    err = np.abs(np.asarray(exact[..., 0]) - np.asarray(quant[..., 0]))
    assert (err <= 0.5 * gscale * counts + 1e-5).all()


def test_dispatch_through_leafbatch(hist_inputs):
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    a = histogram_leafbatch(bins, grad, hess, cid, ok, C, B,
                            compute_dtype="int8")
    b = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uint8_bins_above_127_not_dropped():
    """Production max_bin=255 stores bins as uint8 with values up to 254;
    the Pallas kernel must mask the int8 sign-extension back off (a plain
    int8 cast wraps 200 -> -56 and silently drops the row)."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(9)
    F, N, B, C = 4, 3000, 255, 5
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.ones(N, bool)
    via_xla = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        via_pl = hist_pallas_leafbatch(bins, grad, hess, cid, ok, C, B,
                                       chunk=1024, dtype="int8")
    np.testing.assert_array_equal(np.asarray(via_xla), np.asarray(via_pl))
    # every row must land somewhere: total count == N per feature
    assert float(via_pl[..., 2].sum()) == float(N * F)


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

    vals, _ = quantize_values(grad, hess, ok)
    packed = jnp.concatenate(
        [vals, jnp.where(ok, cid, -1).astype(jnp.int8)[None]], axis=0)
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
    from lightgbm_tpu.ops.hist_pallas import hist_fold
    table = {1: (8, 3), 2: (8, 6), 3: (8, 9), 4: (4, 12), 5: (4, 16),
             8: (4, 24), 10: (4, 30), 11: (2, 36), 16: (2, 48),
             17: (1, None), 21: (1, None), 32: (1, None), 42: (1, None)}
    for num_cols, want in table.items():
        for B in (255, 256):
            for dtype in ("int8", "bf16"):
                assert hist_fold(3, num_cols, B, 128, dtype) == want, (
                    num_cols, B, dtype)
        # float gradients keep their summation shape
        assert hist_fold(3, num_cols, 256, 128, "bf16v") == (1, None)
        assert hist_fold(5, num_cols, 256, 128, "bf16v") == (1, None)
    assert hist_fold(3, 43, 256, 192, "int8") == (1, None)
    # a 64-bin class of the mixed-bin layout: the 32-row floor on the
    # one-hot holds it to fold 2, and only while that saves an eighth
    assert [hist_fold(3, c, 64, 128, "int8") for c in (1, 4, 5)] == [
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
        with pltpu.force_tpu_interpret_mode():
            booster = lgb.train(params,
                                Dataset.from_arrays(x, y, max_bin=255))
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


def test_wide_bins_int16_dispatch():
    """max_bin > 256 stores int16 bins; the int8 dispatch must route them
    through the XLA int formulation (the Pallas kernel's int8 bit-pattern
    trick only covers 8-bit bin ids) and still be exact."""
    rng = np.random.RandomState(5)
    F, N, B, C = 3, 2000, 300, 4
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.int16))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.ones(N, bool)
    a = histogram_leafbatch(bins, grad, hess, cid, ok, C, B,
                            compute_dtype="int8")
    b = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(a[..., 2].sum()) == float(N * F)


def test_stochastic_rounding_unbiased(hist_inputs):
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    key = jax.random.PRNGKey(0)
    bits = jax.random.bits(key, (2, N), jnp.uint32)
    vals, scale = quantize_values(grad, hess, ok, rng_bits=bits)
    # SR keeps values within 1 ulp and is mean-preserving to ~sqrt(N) noise
    g_deq = np.asarray(vals[0], np.float32) * float(scale[0])
    gm = np.asarray(grad) * np.asarray(ok, np.float32)
    assert np.abs(g_deq - gm).max() <= float(scale[0]) + 1e-7
    assert abs((g_deq - gm).sum()) < float(scale[0]) * np.sqrt(N) * 4


def test_train_multiclass_int8(synthetic_binary):
    """int8 histograms under the multiclass objective (per-class gradient
    slices quantize with their own per-pass scales)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.dataset import Dataset
    x, _ = synthetic_binary
    rng = np.random.RandomState(4)
    y = ((x[:, 0] > 0).astype(int) + (x[:, 1] > 0.5).astype(int)).astype(
        np.float32)  # 3 classes

    def train(hist_dtype):
        ds = Dataset.from_arrays(x, y, max_bin=64)
        params = {"objective": "multiclass", "num_class": "3",
                  "num_leaves": "15", "min_data_in_leaf": "20",
                  "min_sum_hessian_in_leaf": "1.0",
                  "num_iterations": "10", "learning_rate": "0.2",
                  "grow_policy": "depthwise", "hist_dtype": hist_dtype}
        booster = lgb.train(params, ds)
        p = booster.predict_multiclass(x)
        return float(np.mean(np.argmax(p, axis=1) != y))

    err_f32 = train("float32")
    err_int8 = train("int8")
    assert err_int8 <= err_f32 + 0.02, (err_f32, err_int8)


def test_train_depthwise_int8_quality(synthetic_binary):
    """End-to-end: int8 histograms must reach f32-comparable train error."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.dataset import Dataset
    x, y = synthetic_binary

    def train(hist_dtype):
        ds = Dataset.from_arrays(x, y, max_bin=64)
        params = {"objective": "binary", "num_leaves": "31",
                  "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1.0",
                  "num_iterations": "30", "learning_rate": "0.1",
                  "grow_policy": "depthwise", "hist_dtype": hist_dtype}
        booster = lgb.train(params, ds)
        p = booster.predict(x)
        return float(np.mean((p > 0.5) != (y > 0.5)))

    err_f32 = train("float32")
    err_int8 = train("int8")
    assert err_int8 <= err_f32 + 0.02, (err_f32, err_int8)


def test_int8_row_capacity_guard():
    """ADVICE r2 (medium): a histogram cell's int32 accumulator holds at
    most 2^31/127 rows (iteration-0 binary hessians all quantize to 127,
    and a single-bin feature concentrates every row into one cell) —
    beyond that the booster must refuse int8 loudly, not wrap silently."""
    from lightgbm_tpu.models.gbdt import (check_int8_row_capacity,
                                          INT8_HIST_MAX_ROWS)
    from lightgbm_tpu.utils.log import LightGBMError
    check_int8_row_capacity(INT8_HIST_MAX_ROWS)       # at the limit: fine
    check_int8_row_capacity(11_000_000)               # bench scale: fine
    with pytest.raises(LightGBMError):
        check_int8_row_capacity(INT8_HIST_MAX_ROWS + 1)


def test_stochastic_rounding_unbiased_and_deterministic():
    """quant_rounding=stochastic: value-keyed bits make rounding unbiased
    in expectation over many distinct values (mean quantization error well
    below the half-quantum bias a floor/ceil would give) and fully
    deterministic (same inputs -> same bits -> same ints)."""
    from lightgbm_tpu.ops.hist_pallas import quantize_values
    rng = np.random.RandomState(0)
    n = 200_000
    grad = rng.randn(n).astype(np.float32)
    hess = (0.1 + rng.rand(n)).astype(np.float32)
    ok = np.ones(n, bool)
    v1, s1 = quantize_values(jnp.asarray(grad), jnp.asarray(hess),
                             jnp.asarray(ok), stochastic=True, salt=7)
    v2, s2 = quantize_values(jnp.asarray(grad), jnp.asarray(hess),
                             jnp.asarray(ok), stochastic=True, salt=7)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))

    # unbiasedness: the mean signed quantization error of the SUM is tiny
    # relative to the one-ulp-per-row worst case
    gs = float(np.asarray(s1)[0])
    err = np.asarray(v1)[0].astype(np.float64) * gs - grad
    assert abs(err.mean()) < 0.02 * gs   # nearest-rounding is also ~0; the
    # distinguishing property is variance behavior, checked via the sum:
    assert abs(err.sum()) < 3 * gs * np.sqrt(n)

    # different salt -> different rounding realization (not a constant fn)
    v3, _ = quantize_values(jnp.asarray(grad), jnp.asarray(hess),
                            jnp.asarray(ok), stochastic=True, salt=8)
    assert (np.asarray(v3)[0] != np.asarray(v1)[0]).any()


def test_stochastic_int8_dp_bit_identical_to_serial():
    """The stochastic bits are keyed on the row's (grad, hess) VALUES, not
    its position — so serial and data-parallel programs quantize every
    physical row identically and the int8 bit-identity chain survives
    (both dp_schedule variants)."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(3)
    n, f = 1999, 8
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.4 * rng.randn(n)) > 0).astype(
        np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1.0,
              "num_iterations": 4, "learning_rate": 0.2,
              "grow_policy": "depthwise", "hist_dtype": "int8",
              "quant_rounding": "stochastic"}

    def make(tree_learner, machines, schedule="psum"):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner, num_machines=machines,
                 dp_schedule=schedule)
        cfg.set({k: str(v) for k, v in p.items()}, require_data=False)
        b = GBDT()
        obj = create_objective(cfg.objective_type, cfg.objective_config)
        learner = None
        if tree_learner != "serial":
            from lightgbm_tpu.parallel import create_parallel_learner
            learner = create_parallel_learner(cfg)
        b.init(cfg.boosting_config, ds, obj, learner=learner)
        return b

    bs = make("serial", 1)
    for _ in range(4):
        bs.train_one_iter(is_eval=False)
    for sched in ("psum", "reduce_scatter"):
        bd = make("data", 8, sched)
        bd.train_chunk(4)
        for k, (t1, t2) in enumerate(zip(bs.models, bd.models)):
            assert t1.num_leaves == t2.num_leaves, (sched, k)
            np.testing.assert_array_equal(t1.split_feature,
                                          t2.split_feature,
                                          err_msg=f"{sched} tree {k}")
            np.testing.assert_array_equal(t1.threshold_bin,
                                          t2.threshold_bin,
                                          err_msg=f"{sched} tree {k}")
