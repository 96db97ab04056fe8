"""Float-gradient Pallas histogram path (ops/hist_pallas.py bf16v):
bf16 single-pass and f32x2 hi/lo variants vs the exact scatter oracle.

This is the round-3 mitigation for the environment's XLA einsum-lowering
regression (BASELINE.md): the hist_dtype=float32/bfloat16 paths route to a
hand-scheduled Pallas kernel on TPU.  These tests pin the kernel's math in
interpret mode; the dispatch itself is TPU-gated (histogram._pallas_hist_ok)
so the CPU einsum oracle below stays the reference.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


from lightgbm_tpu.ops.histogram import (histogram_leafbatch,
                                        histogram_leafbatch_segsum)
from lightgbm_tpu.ops.hist_pallas import hist_pallas_float_leafbatch


@pytest.fixture(scope="module")
def hist_inputs():
    rng = np.random.RandomState(7)
    F, N, B, C = 5, 4000, 32, 7
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.int8))
    grad = jnp.asarray((rng.randn(N) * 0.4).astype(np.float32))
    hess = jnp.asarray((rng.rand(N) * 0.25).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.85)
    return bins, grad, hess, cid, ok, F, N, B, C


def test_bf16_variant_matches_rounded_oracle(hist_inputs):
    """Single-pass bf16: equal to the exact oracle fed bf16-rounded
    grad/hess (to f32 accumulation-order noise), counts exact."""
    from jax.experimental.pallas import tpu as pltpu
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    g16 = grad.astype(jnp.bfloat16).astype(jnp.float32)
    h16 = hess.astype(jnp.bfloat16).astype(jnp.float32)
    want = histogram_leafbatch_segsum(bins, g16, h16, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        got = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=1024, precision="bf16")
    np.testing.assert_array_equal(np.asarray(want[..., 2]),
                                  np.asarray(got[..., 2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_f32x2_variant_near_exact(hist_inputs):
    """Two-pass hi/lo split recovers ~16 operand mantissa bits: per-cell
    error must sit far below the single-pass bf16 rounding floor."""
    from jax.experimental.pallas import tpu as pltpu
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    want = histogram_leafbatch_segsum(bins, grad, hess, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        got = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=1024, precision="f32x2")
        got_bf = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C,
                                             B, chunk=1024,
                                             precision="bf16")
    np.testing.assert_array_equal(np.asarray(want[..., 2]),
                                  np.asarray(got[..., 2]))
    w = np.asarray(want)
    err_x2 = np.abs(np.asarray(got) - w)[..., :2]
    err_bf = np.abs(np.asarray(got_bf) - w)[..., :2]
    # bound the hi/lo error by the operand split: |eps| <= 2^-16 per value,
    # so a cell of n rows with max |v| drifts <= n * maxv * 2^-16 (+ f32
    # accumulation noise)
    counts = w[..., 2:3][..., 0][..., None]
    maxv = max(float(jnp.max(jnp.abs(grad))), float(jnp.max(jnp.abs(hess))))
    bound = counts * maxv * 2.0**-15 + 1e-5
    assert (err_x2 <= bound).all()
    assert err_x2.sum() < 0.05 * err_bf.sum() + 1e-6


def test_wide_level_grouping(hist_inputs):
    """>64 columns split into groups; results must tile back exactly."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(11)
    F, N, B, C = 3, 2000, 16, 100
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.int8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.9)
    g16 = grad.astype(jnp.bfloat16).astype(jnp.float32)
    h16 = hess.astype(jnp.bfloat16).astype(jnp.float32)
    want = histogram_leafbatch_segsum(bins, g16, h16, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        got = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=512, precision="bf16")
    assert got.shape == (C, F, B, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_uint8_bins_above_127_not_dropped():
    """max_bin=255 bins ride as uint8 bit-patterns; the kernel must mask
    the int8 sign-extension back off (same guarantee as the int8 path)."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(13)
    F, N, B, C = 4, 3000, 255, 5
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.ones(N, bool)
    want = histogram_leafbatch_segsum(bins, grad, hess, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        got = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=1024, precision="f32x2")
    np.testing.assert_array_equal(np.asarray(want[..., 2]),
                                  np.asarray(got[..., 2]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-3)


def test_einsum_dispatch_unaffected_off_tpu(hist_inputs):
    """On the CPU backend _pallas_hist_ok is False, so the einsum branch
    still serves float dtypes (the differential-test oracle path)."""
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    assert jax.default_backend() != "tpu"
    a = histogram_leafbatch(bins, grad, hess, cid, ok, C, B,
                            compute_dtype=jnp.float32)
    b = histogram_leafbatch_segsum(bins, grad, hess, cid, ok, C, B)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-3)


def test_wide_dataset_feature_grid():
    """Datasets wider than one VMEM accumulator block (feature_block() =
    96 at B=256/lanes=128) ride the kernel's feature-block grid axis —
    int8 stays bit-identical to the XLA oracle, bf16v matches the rounded
    oracle; pad features are sliced off."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import (feature_block,
                                              hist_pallas_leafbatch,
                                              hist_quant_xla)
    rng = np.random.RandomState(17)
    F, N, B, C = 100, 1024, 256, 5
    assert F > feature_block(B, 128)
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(rng.rand(N).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, C, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.9)
    want_int = hist_quant_xla(bins, grad, hess, cid, ok, C, B)
    g16 = grad.astype(jnp.bfloat16).astype(jnp.float32)
    h16 = hess.astype(jnp.bfloat16).astype(jnp.float32)
    want_f = histogram_leafbatch_segsum(bins, g16, h16, cid, ok, C, B)
    with pltpu.force_tpu_interpret_mode():
        got_int = hist_pallas_leafbatch(bins, grad, hess, cid, ok, C, B,
                                        chunk=512, dtype="int8")
        got_f = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C,
                                            B, chunk=512,
                                            precision="bf16")
    np.testing.assert_array_equal(np.asarray(want_int), np.asarray(got_int))
    assert got_f.shape == (C, F, B, 3)
    np.testing.assert_allclose(np.asarray(got_f), np.asarray(want_f),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("skip_dead", [False, True])
@pytest.mark.parametrize("B", [255, 256])
@pytest.mark.parametrize("precision", ["bf16", "f32x1"])
@pytest.mark.parametrize("num_cols", [1, 2, 4])
def test_folded_float_pass_equals_the_unfolded(monkeypatch, num_cols,
                                               precision, B, skip_dead):
    """The float mode under the bin fold (``hist_fold``: three statistics
    a column at fold 8, 8, 4 for 1, 2, 4 columns, the float32 pair's five
    at 8, 4, 4) against the same pass with fold 1 forced: uint8 codes
    >= 128, a ragged last chunk, masked-out rows and, for ``skip_dead``, a
    tail of chunks with no live row.  Counts are exactly the oracle's and
    sums within the tolerance this file holds the unfolded kernel to.
    Under the interpreter the two are bit-equal as well: every cell's
    non-zero addends meet at the same places of the same contraction
    (whether the chip's MXU agrees is a chip run's to say: PERF.md
    section 6, PR 35)."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops import hist_pallas
    rng = np.random.RandomState(1000 * num_cols + B + skip_dead)
    F, N, chunk = 3, 3500, 1024
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray((rng.randn(N) * 0.4).astype(np.float32))
    hess = jnp.asarray((rng.rand(N) * 0.25).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, num_cols, N).astype(np.int32))
    # the bucketed range of the compacted grower: its rows in front, a
    # dead tail behind (the whole last chunk and more)
    live = N - 1500 if skip_dead else N
    ok = jnp.asarray((rng.rand(N) < 0.85) & (np.arange(N) < live))
    stats = 3 if precision == "bf16" else 5
    fold = hist_pallas.hist_fold(stats, num_cols, B, 128)[0]
    assert fold == {(3, 1): 8, (3, 2): 8, (3, 4): 4,
                    (5, 1): 8, (5, 2): 4, (5, 4): 4}[stats, num_cols]

    def run():
        telemetry.reset()
        telemetry.enable()
        try:
            with pltpu.force_tpu_interpret_mode():
                got = np.asarray(hist_pallas.hist_pallas_float_leafbatch(
                    bins, grad, hess, cid, ok, num_cols, B, chunk=chunk,
                    precision=precision, skip_dead=skip_dead))
            counters = telemetry.snapshot()["counters"]
        finally:
            telemetry.disable()
            telemetry.reset()
        return got, {k: v for k, v in counters.items()
                     if k.startswith("hist/pallas_fold_")}
    folded, counted = run()
    assert counted == {"hist/pallas_fold_%d" % fold: 1}
    monkeypatch.setattr(hist_pallas, "hist_fold", lambda *a: (1, None))
    unfolded, counted = run()
    assert counted == {"hist/pallas_fold_1": 1}
    assert folded.shape == (num_cols, F, B, 3)
    np.testing.assert_array_equal(folded, unfolded)
    if precision == "bf16":
        g = grad.astype(jnp.bfloat16).astype(jnp.float32)
        h = hess.astype(jnp.bfloat16).astype(jnp.float32)
        tol = dict(rtol=1e-5, atol=1e-4)
    else:
        g, h, tol = grad, hess, dict(rtol=1e-4, atol=1e-3)
    want = np.asarray(histogram_leafbatch_segsum(bins, g, h, cid, ok,
                                                 num_cols, B))
    np.testing.assert_array_equal(folded[..., 2], want[..., 2])
    np.testing.assert_allclose(folded, want, **tol)


@pytest.mark.parametrize("num_cols", [1, 2, 4])
def test_f32x1_bit_identical_to_f32x2_folded(num_cols):
    """The claim below where the passes fold (255 bins): one column folds
    both packings by 8, two and four columns fold the five statistics by 4
    and the three by 8 and 4.  The interpreter's dot gives a cell the same
    sum at either fold, so the packings stay bit-equal here whatever their
    folds; on the chip that rests on the folded accumulator equalling the
    unfolded one (PERF.md section 6, PR 35)."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(29 + num_cols)
    F, N, B = 3, 3000, 255
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    grad = jnp.asarray((rng.randn(N) * 0.4).astype(np.float32))
    hess = jnp.asarray((rng.rand(N) * 0.25).astype(np.float32))
    cid = jnp.asarray(rng.randint(0, num_cols, N).astype(np.int32))
    ok = jnp.asarray(rng.rand(N) < 0.85)
    with pltpu.force_tpu_interpret_mode():
        one, two = (np.asarray(hist_pallas_float_leafbatch(
            bins, grad, hess, cid, ok, num_cols, B, chunk=1024,
            precision=precision)) for precision in ("f32x1", "f32x2"))
    np.testing.assert_array_equal(one, two)


def test_f32x1_bit_identical_to_f32x2(hist_inputs):
    """The single-pass 5-stat packing accumulates the same per-lane f32
    partial sums as the two-pass variant — outputs must be bit-equal
    (including across the 38-column grouping boundary)."""
    from jax.experimental.pallas import tpu as pltpu
    bins, grad, hess, cid, ok, F, N, B, C = hist_inputs
    with pltpu.force_tpu_interpret_mode():
        one = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=1024, precision="f32x1")
        two = hist_pallas_float_leafbatch(bins, grad, hess, cid, ok, C, B,
                                          chunk=1024, precision="f32x2")
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))

    rng = np.random.RandomState(23)
    for C2 in (32, 50):
        # 32: the 192-lane single 5-stat pass (the depthwise depth-5
        # production route, 192 % 5 leaves 2 partial lanes);
        # 50: > 38, grouped into two 5-stat passes
        cid2 = jnp.asarray(rng.randint(0, C2, N).astype(np.int32))
        want = histogram_leafbatch_segsum(bins, grad, hess, cid2, ok,
                                          C2, B)
        with pltpu.force_tpu_interpret_mode():
            got = hist_pallas_float_leafbatch(bins, grad, hess, cid2, ok,
                                              C2, B, chunk=1024,
                                              precision="f32x1")
        assert got.shape == (C2, F, B, 3)
        np.testing.assert_array_equal(np.asarray(want[..., 2]),
                                      np.asarray(got[..., 2]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)
