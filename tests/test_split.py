"""Split-search tests against a literal NumPy port of the reference scan
(feature_histogram.hpp:106-165)."""
import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops.split import find_best_split, K_EPSILON


def _reference_scan(hist_f, num_bin, sum_g, sum_h_raw, num_data,
                    min_data, min_hess):
    """Literal port of FindBestThreshold for one feature."""
    sum_hessians = sum_h_raw + 2 * K_EPSILON
    best_gain = -np.inf
    best_threshold = num_bin
    sum_right_g = 0.0
    sum_right_h = K_EPSILON
    right_count = 0
    gain_shift = sum_g * sum_g / sum_hessians
    for t in range(num_bin - 1, 0, -1):
        sum_right_g += hist_f[t, 0]
        sum_right_h += hist_f[t, 1]
        right_count += hist_f[t, 2]
        if right_count < min_data or sum_right_h < min_hess:
            continue
        left_count = num_data - right_count
        if left_count < min_data:
            break
        sum_left_h = sum_hessians - sum_right_h
        if sum_left_h < min_hess:
            break
        sum_left_g = sum_g - sum_right_g
        gain = (sum_left_g ** 2 / sum_left_h + sum_right_g ** 2 / sum_right_h)
        if gain < gain_shift:
            continue
        if gain > best_gain:
            best_threshold = t - 1
            best_gain = gain
    return best_threshold, best_gain - gain_shift


def _run_case(seed, F=4, B=16, min_data=3, min_hess=1e-3):
    rng = np.random.RandomState(seed)
    hist = np.zeros((F, B, 3), dtype=np.float64)
    n = 500
    # one shared row population: every feature is a different binning of the
    # SAME rows, so per-feature histogram totals agree (as in real data)
    g = rng.randn(n)
    h = rng.rand(n) + 0.1
    for f in range(F):
        bins = rng.randint(0, B, size=n)
        for b_, g_, h_ in zip(bins, g, h):
            hist[f, b_] += [g_, h_, 1.0]
    sum_g = hist[0, :, 0].sum()
    sum_h = hist[0, :, 1].sum()
    num_data = hist[0, :, 2].sum()

    res = find_best_split(
        jnp.asarray(hist, jnp.float32), jnp.float32(sum_g),
        jnp.float32(sum_h), jnp.float32(num_data),
        jnp.full((F,), B, jnp.int32), jnp.ones((F,), bool),
        float(min_data), float(min_hess))

    # oracle: best across features, smaller feature wins ties
    best = (-np.inf, None, None)
    for f in range(F):
        t, gain = _reference_scan(hist[f], B, sum_g, sum_h, num_data,
                                  min_data, min_hess)
        if gain > best[0]:
            best = (gain, f, t)
    assert int(res.feature) == best[1], (int(res.feature), best)
    assert int(res.threshold) == best[2]
    np.testing.assert_allclose(float(res.gain), best[0], rtol=1e-4)


def test_split_matches_reference_scan():
    for seed in range(5):
        _run_case(seed)


def test_min_data_constraint_blocks_split():
    # all data in one bin → no valid split
    F, B = 2, 8
    hist = np.zeros((F, B, 3), dtype=np.float32)
    hist[:, 3] = [5.0, 10.0, 100.0]
    res = find_best_split(
        jnp.asarray(hist), jnp.float32(5.0), jnp.float32(10.0),
        jnp.float32(100.0), jnp.full((F,), B, jnp.int32),
        jnp.ones((F,), bool), 1.0, 1e-3)
    assert float(res.gain) == -np.inf


def test_feature_mask_respected():
    rng = np.random.RandomState(2)
    F, B = 3, 8
    hist = rng.rand(F, B, 3).astype(np.float32) * 10
    hist[:, :, 1] += 1
    sum_g = float(hist[0, :, 0].sum())
    sum_h = float(hist[0, :, 1].sum())
    cnt = float(hist[0, :, 2].sum())
    mask = jnp.asarray([False, True, False])
    res = find_best_split(
        jnp.asarray(hist), jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(cnt), jnp.full((F,), B, jnp.int32), mask, 0.0, 0.0)
    assert int(res.feature) == 1


def test_left_right_outputs_consistent():
    rng = np.random.RandomState(4)
    F, B = 2, 8
    hist = np.abs(rng.rand(F, B, 3)).astype(np.float32) * 5
    hist[:, :, 2] = np.round(hist[:, :, 2] * 10)
    sum_g = float(hist[0, :, 0].sum())
    sum_h = float(hist[0, :, 1].sum())
    cnt = float(hist[0, :, 2].sum())
    res = find_best_split(
        jnp.asarray(hist), jnp.float32(sum_g), jnp.float32(sum_h),
        jnp.float32(cnt), jnp.full((F,), B, jnp.int32),
        jnp.ones((F,), bool), 1.0, 1e-3)
    if np.isfinite(float(res.gain)):
        f, t = int(res.feature), int(res.threshold)
        lg = hist[f, :t + 1, 0].sum()
        lh = hist[f, :t + 1, 1].sum()
        np.testing.assert_allclose(float(res.left_sum_grad), lg, rtol=1e-4)
        np.testing.assert_allclose(float(res.left_output),
                                   -lg / (lh + K_EPSILON), rtol=1e-3)


def _counted_case(seed, F=4, B=16, n=400):
    """A [F, B, 3] histogram of exact counts over one row population,
    with bins that hold no row, and its leaf totals."""
    rng = np.random.RandomState(seed)
    hist = np.zeros((F, B, 3), dtype=np.float64)
    g = rng.randn(n)
    h = rng.rand(n) + 0.05
    for f in range(F):
        # a feature's rows in a few of its bins: empty sides at both ends
        lo, hi = sorted(rng.choice(B, 2, replace=False))
        bins = rng.randint(lo, hi + 1, size=n)
        for b_, g_, h_ in zip(bins, g, h):
            hist[f, b_] += [g_, h_, 1.0]
    return (jnp.asarray(hist, jnp.float32), jnp.float32(hist[0, :, 0].sum()),
            jnp.float32(hist[0, :, 1].sum()), jnp.float32(n))


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("min_data,min_hess", [(0.0, 1e-3), (1.0, 0.5)])
def test_search_without_counts_is_the_count_tests(seed, min_data, min_hess):
    """Where ``min_data_in_leaf <= 1`` and the floor is above K_EPSILON,
    the search over grad and hess alone, reading each bin's occupancy,
    finds the split the search with counts finds: every field of the
    result but the counts, bit for bit."""
    hist, sg, sh, n = _counted_case(seed)
    F, B = hist.shape[:2]
    args = (jnp.full((F,), B, jnp.int32), jnp.ones((F,), bool),
            min_data, min_hess)
    three = find_best_split(hist, sg, sh, n, *args)
    two = find_best_split(hist[..., :2], sg, sh, hist[..., 2] > 0, *args)
    assert two.left_count is None and two.right_count is None
    assert np.isfinite(float(three.gain))
    for name in three._fields:
        if not name.endswith("_count"):
            np.testing.assert_array_equal(np.asarray(getattr(two, name)),
                                          np.asarray(getattr(three, name)),
                                          name)


def test_search_without_counts_refuses_an_empty_side_of_residue():
    """A leaf whose rows sit in bins 0 - 4, every bin of the same grad to
    hess ratio, so that no real split gains; its totals carry a rounding
    residue of 0.01 hess and -0.003 grad, as a sibling derived by float32
    subtraction does.  A threshold of 4 or more leaves the right side no
    row and the residue, which passes a floor of 1e-3 and outgains every
    real split: the search reading occupancy refuses it, one that took
    every bin as occupied would not."""
    B = 8
    hist = np.zeros((1, B, 2), np.float32)
    hist[0, :5] = [-1.0, 1.0]
    occupied = jnp.asarray(hist[..., 1] > 0)
    args = (jnp.asarray(hist), jnp.float32(-5.003), jnp.float32(5.01))
    rest = (jnp.full((1,), B, jnp.int32), jnp.ones((1,), bool), 1.0, 1e-3)
    res = find_best_split(*args, occupied, *rest)
    assert (not np.isfinite(float(res.gain))) or int(res.threshold) < 4
    blind = find_best_split(*args, jnp.ones((1, B), bool), *rest)
    assert int(blind.threshold) >= 4 and float(blind.gain) > 1e-3
