"""Quantized-gradient (int8) histogram path: XLA oracle ≡ Pallas kernel,
exact counts, and end-to-end training sanity.

The int8 path is the TPU throughput option (ops/hist_pallas.py): grad/hess
are rounded to 1/127 of their max over the tree's rows (a pass called
alone: over its own) and contracted on the int8 MXU.
The reference accumulates in double (bin.h:15-17); LightGBM's later
quantized-training work showed coarse gradient quantization preserves model
quality — these tests pin the machinery, scripts/auc_parity.py pins quality
at scale.

The kernel's two layout rules have files of their own, each a rule, its
counter and the bit-identity of the pass it re-shapes:
``tests/test_hist_int8_fold.py`` (the bin fold of the narrow levels) and
``tests/test_hist_int8_held.py`` (the held one-hot of the wide ones).
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
    vals, scale = quantize_values(grad, hess, ok, cid, rng_bits=bits)
    # SR keeps values within 1 ulp and is mean-preserving to ~sqrt(N) noise
    g_deq = np.asarray(vals[0], np.float32) * float(scale[0])
    gm = np.asarray(grad) * np.asarray(ok, np.float32)
    assert np.abs(g_deq - gm).max() <= float(scale[0]) + 1e-7
    assert abs((g_deq - gm).sum()) < float(scale[0]) * np.sqrt(N) * 4


def _numpy_mix32(x):
    with np.errstate(over="ignore"):       # uint32 products wrap
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x85EBCA6B)
        x = x ^ (x >> np.uint32(13))
        x = x * np.uint32(0xC2B2AE35)
        return x ^ (x >> np.uint32(16))


def _numpy_codes(grad, hess, ok, cid, rounding, bits, salt, quant_max):
    """The kernel's [4, N] operand stated plainly: rows 0-1
    clip(round(x / s), -127, 127) where ok else 0, row 2 ok, row 3 the
    column id where ok else -1; float32 arithmetic throughout."""
    f32 = np.float32
    okf = ok.astype(f32)
    if quant_max is None:
        quant_max = [np.max(np.abs(grad) * okf), np.max(np.abs(hess) * okf)]
    scales = [np.maximum(f32(m), f32(1e-30)) / f32(127.0) for m in quant_max]
    if rounding == "stochastic":
        def hashed(x, other, s):
            ix, io = x.view(np.uint32), other.view(np.uint32)
            with np.errstate(over="ignore"):
                key = _numpy_mix32(np.uint32(s) + np.uint32(0x9E3779B9))
            return _numpy_mix32(ix ^ _numpy_mix32(io) ^ key)
        bits = [hashed(grad, hess, salt), hashed(hess, grad, salt + 0x51ED)]
    rows = []
    for k, (x, s) in enumerate(zip((grad, hess), scales)):
        y = x / s
        if rounding == "nearest":
            q = np.rint(y)
        else:
            u = (bits[k] >> np.uint32(8)).astype(f32) * f32(1.0 / (1 << 24))
            q = np.floor(y + u)
        rows.append(np.where(ok, np.clip(q, -127, 127), 0))
    rows += [ok, np.where(ok, cid, -1)]
    return np.stack(rows).astype(np.int8), np.array(scales + [1.0], f32)


@pytest.mark.parametrize("n", [1000, 4099])
@pytest.mark.parametrize("rounding,scale", [
    ("nearest", "own"), ("nearest", "tree"), ("stochastic", "own"),
    ("rng_bits", "own")])
def test_operand_is_the_plain_statement_of_the_codes(n, rounding, scale):
    """``quantize_values`` makes the kernel's whole [4, N] int8 operand as
    one expression (PR 39); it is the plain NumPy statement of the codes
    bit for bit: ties at half steps, values past a tree's scale, masked
    rows, column ids -1 and N no multiple of 1,024."""
    rng = np.random.RandomState(n)
    grad = (rng.randn(n) * 4).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    # the pass's own scale 0.125 exactly, so that k + 0.5 quanta are ties
    grad = np.clip(grad, -15.875, 15.875)
    grad[0], hess[0] = 15.875, 1.0
    k = rng.randint(-126, 126, n // 4)
    grad[1:1 + n // 4] = (k + 0.5) * 0.125
    ok = rng.rand(n) < 0.8
    ok[0] = True
    cid = rng.randint(0, 64, n).astype(np.int32)
    cid[rng.rand(n) < 0.1] = -1
    quant_max = None if scale == "own" else np.array([9.0, 0.5], np.float32)
    bits = (rng.randint(0, 1 << 32, (2, n), dtype=np.uint64)
            .astype(np.uint32) if rounding == "rng_bits" else None)
    kw = dict(stochastic=rounding == "stochastic", salt=3,
              rng_bits=None if bits is None else jnp.asarray(bits),
              quant_max=None if quant_max is None
              else jnp.asarray(quant_max))
    codes, got_scale = jax.jit(lambda *a: quantize_values(*a, **kw))(
        *(jnp.asarray(a) for a in (grad, hess, ok, cid)))
    want, want_scale = _numpy_codes(grad, hess, ok, cid, rounding, bits, 3,
                                    quant_max)
    assert codes.shape == (4, n) and codes.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(codes), want)
    np.testing.assert_array_equal(np.asarray(got_scale), want_scale)
    if rounding == "nearest" and scale == "own":
        # the ties went to even
        assert (np.abs(want[0, 1:1 + n // 4]) % 2 == 0).all()
    if scale == "tree":
        # a tree's scale below the pass's own max saturates
        assert (np.abs(want[0]) == 127).sum() > n // 20


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
    """ADVICE r2 (medium), rewritten by PR 36: a histogram cell's int32
    accumulator holds at most 2^31/127 rows (iteration-0 binary hessians
    all quantize to 127, and a single-bin feature concentrates every row
    into one cell).  Past that the histogram routes cut the rows into
    ranges and build, with no refusal at GBDT.init; a route that sums
    every row into one accumulator must still refuse loudly, not wrap
    silently."""
    from lightgbm_tpu.ops.hist_pallas import (INT8_HIST_MAX_ROWS,
                                              _ranged_rows,
                                              check_int8_row_capacity)
    from lightgbm_tpu.ops.histogram import hist_quant_segsum
    from lightgbm_tpu.utils.log import LightGBMError
    check_int8_row_capacity(INT8_HIST_MAX_ROWS)       # at the limit: fine
    check_int8_row_capacity(11_000_000)               # bench scale: fine
    with pytest.raises(LightGBMError, match="one int32 accumulator"):
        check_int8_row_capacity(INT8_HIST_MAX_ROWS + 1)
    # traced, not run: the ranged route lowers past the cap, seven ranges
    # at the airline table's rows; the scatter-add oracle refuses there
    rows = 115_000_000
    args = (jax.ShapeDtypeStruct((2, rows), jnp.int8),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.bool_))
    out = jax.eval_shape(
        lambda *a: hist_quant_xla(*a, 1, 4, chunk=65536), *args)
    assert out.shape == (1, 2, 4, 3)
    assert _ranged_rows(rows, 65536)[0] == 7
    with pytest.raises(LightGBMError, match="hist_quant_segsum"):
        jax.eval_shape(lambda *a: hist_quant_segsum(*a, 1, 4), *args)
    jax.eval_shape(lambda *a: hist_quant_segsum(*a, 1, 4),
                   *jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                       tuple(1000 if d == rows else d for d in s.shape),
                       s.dtype), args))


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
                             jnp.asarray(ok), jnp.zeros(n, jnp.int32),
                             stochastic=True, salt=7)
    v2, s2 = quantize_values(jnp.asarray(grad), jnp.asarray(hess),
                             jnp.asarray(ok), jnp.zeros(n, jnp.int32),
                             stochastic=True, salt=7)
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
                            jnp.asarray(ok), jnp.zeros(n, jnp.int32),
                            stochastic=True, salt=8)
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


def test_one_scale_a_tree_makes_the_derived_sibling_exact():
    """``hists - hist_small`` is the larger child's histogram only if a
    row rounds to one code in both passes.  Rows that share a few values,
    as in a run's first trees, and a smaller child without the row of the
    largest gradient: with the tree's scale (``quant_max_of`` over all
    rows) the derived sibling is, code for code, the histogram a pass of
    its own builds; with a scale a pass (the parent's arithmetic, PERF.md
    section 6, PR 36) whole groups of rows flip and it is not."""
    from lightgbm_tpu.ops.hist_pallas import quant_max_of
    rng = np.random.RandomState(11)
    F, N, B = 3, 6000, 16
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.int8))
    values = np.array([-1.07, -0.93, -0.51, 0.22, 0.64, 0.98, 1.1],
                      np.float32)
    pick = rng.randint(0, len(values), N)
    grad = jnp.asarray(values[pick])
    hess = jnp.asarray(np.abs(values[pick]) * (2 - np.abs(values[pick])))
    everyone = jnp.ones((N,), bool)
    small = jnp.asarray((rng.rand(N) < 0.3) & (pick != len(values) - 1)
                        & (pick != 0))
    large = everyone & ~small
    cid = jnp.zeros((N,), jnp.int32)
    qmax = quant_max_of(grad, hess, everyone)
    scale = np.append(np.asarray(qmax) / 127.0, 1.0)

    def codes(ok, **kw):
        hist = hist_quant_xla(bins, grad, hess, cid, ok, 1, B, chunk=512,
                              **kw)[0]
        return np.rint(np.asarray(hist, np.float64) / scale).astype(np.int64)

    tree = dict(quant_max=qmax)
    np.testing.assert_array_equal(
        codes(everyone, **tree) - codes(small, **tree), codes(large, **tree))
    # a scale a pass: the smaller child's own max is another, its rows
    # round anew, and the difference stays in the derived sibling
    assert np.asarray(quant_max_of(grad, hess, small))[0] < float(qmax[0])
    assert np.any(codes(everyone) - codes(small) != codes(large, **tree))


@pytest.mark.parametrize("policy", ["depthwise", "leafwise"])
def test_growers_hand_every_pass_the_trees_scale(monkeypatch, policy):
    """Every int8 histogram pass of a tree gets the one ``quant_max``
    the grower took over the tree's rows; a float tree gets none."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.models import grower as grower_mod
    from lightgbm_tpu.models import grower_depthwise as gd_mod
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import histogram as hist_mod

    seen = []

    def spy(real):
        def call(*args, **kw):
            seen.append(kw.get("quant_max"))
            return real(*args, **kw)
        return call
    monkeypatch.setattr(gd_mod, "histogram_leafbatch",
                        spy(hist_mod.histogram_leafbatch))
    monkeypatch.setattr(grower_mod, "build_histogram",
                        spy(hist_mod.build_histogram))
    rng = np.random.RandomState(5)
    x = rng.randn(1500, 4)
    y = (x[:, 0] + 0.3 * rng.randn(1500) > 0).astype(np.float32)

    def passes(hist_dtype):
        del seen[:]
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "7", "max_bin": "16",
                 "min_data_in_leaf": "5", "hist_dtype": hist_dtype,
                 "grow_policy": policy, "leafwise_compact": "false"},
                require_data=False)
        booster = GBDT()
        booster.init(cfg.boosting_config,
                     Dataset.from_arrays(x, y, max_bin=16),
                     create_objective(cfg.objective_type,
                                      cfg.objective_config))
        booster.train_one_iter(is_eval=False)
        assert booster.models[0].num_leaves > 2
        return list(seen)

    int8 = passes("int8")
    assert len(int8) >= 2 and int8[0] is not None
    assert all(q is int8[0] for q in int8)
    assert passes("float32") and all(q is None for q in seen)
