"""BinMapper unit tests against hand-computed values (SURVEY §4 test plan b)."""
import numpy as np
import pytest

from lightgbm_tpu.io.binning import BinMapper


def test_distinct_values_path():
    # num distinct <= max_bin: boundaries are midpoints, last +inf
    values = np.array([1.0, 1.0, 2.0, 3.0, 3.0, 3.0])
    m = BinMapper()
    m.find_bin(values, max_bin=8)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound[:2], [1.5, 2.5])
    assert np.isinf(m.bin_upper_bound[2])
    assert not m.is_trivial
    # sparse rate = share of bin 0 (value 1.0 appears twice in 6 samples)
    assert m.sparse_rate == pytest.approx(2 / 6)


def test_value_to_bin_boundaries():
    m = BinMapper()
    m.find_bin(np.array([0.0, 1.0, 2.0]), max_bin=8)
    # boundaries [0.5, 1.5, inf]; value <= upper → that bin
    assert m.value_to_bin(0.0) == 0
    assert m.value_to_bin(0.5) == 0
    assert m.value_to_bin(0.50001) == 1
    assert m.value_to_bin(1.5) == 1
    assert m.value_to_bin(100.0) == 2
    np.testing.assert_array_equal(
        m.value_to_bin(np.array([0.0, 0.6, 3.0])), [0, 1, 2])


def test_trivial_feature():
    m = BinMapper()
    m.find_bin(np.full(100, 3.14), max_bin=8)
    assert m.num_bin == 1
    assert m.is_trivial


def test_hybrid_path_dedicated_bins():
    # one dominant value gets a dedicated bin when count > mean_bin_size
    values = np.concatenate([np.zeros(900), np.arange(1, 101)])
    m = BinMapper()
    m.find_bin(values, max_bin=10)
    assert m.num_bin <= 10
    assert m.num_bin > 1
    # zero must map to its own dedicated bin: nothing else shares it
    zero_bin = int(m.value_to_bin(0.0))
    others = m.value_to_bin(np.arange(1, 101).astype(float))
    assert not np.any(others == zero_bin)


def test_bins_are_monotonic():
    rng = np.random.RandomState(3)
    values = rng.randn(5000)
    m = BinMapper()
    m.find_bin(values, max_bin=32)
    bounds = m.bin_upper_bound
    assert np.all(np.diff(bounds[:-1]) > 0)
    # every value maps into [0, num_bin)
    bins = m.value_to_bin(values)
    assert bins.min() >= 0 and bins.max() < m.num_bin


def test_roundtrip_serialization():
    m = BinMapper()
    m.find_bin(np.random.RandomState(0).randn(1000), max_bin=16)
    m2 = BinMapper.from_bytes(m.to_bytes())
    assert m2.num_bin == m.num_bin
    assert m2.is_trivial == m.is_trivial
    np.testing.assert_allclose(m2.bin_upper_bound, m.bin_upper_bound)


def _reference_find_bin_bounds(values, max_bin, tie_perm=None):
    """Literal re-implementation of BinMapper::FindBin
    (/root/reference/src/io/bin.cpp:42-132) used as a test oracle, with
    one twist: ``tie_perm`` (a numpy RandomState) permutes equal-count
    groups after the count sort, simulating the reference's UNSTABLE
    std::sort in Common::SortForPair (common.h:362-381) under an
    adversarial implementation.  Returns the bin_upper_bound array."""
    values = np.asarray(values, dtype=np.float64)
    sample_size = values.size
    distinct_values, counts = np.unique(values, return_counts=True)
    distinct_values = list(distinct_values)
    counts = [int(c) for c in counts]
    num_values = len(distinct_values)
    assert num_values > max_bin, "oracle exercises the hybrid path only"

    mean_bin_size = sample_size / float(max_bin)
    rest_sample_cnt = sample_size
    bin_cnt = 0
    upper_bounds = [np.inf] * max_bin
    lower_bounds = [np.inf] * max_bin
    order = sorted(range(num_values), key=lambda i: -counts[i])
    if tie_perm is not None:
        # shuffle within equal-count runs: any such order is a legal
        # std::sort outcome
        i = 0
        while i < len(order):
            j = i
            while (j < len(order)
                   and counts[order[j]] == counts[order[i]]):
                j += 1
            seg = order[i:j]
            tie_perm.shuffle(seg)
            order[i:j] = seg
            i = j
    counts = [counts[i] for i in order]
    distinct_values = [distinct_values[i] for i in order]
    while bin_cnt < num_values and counts[bin_cnt] > mean_bin_size:
        upper_bounds[bin_cnt] = distinct_values[bin_cnt]
        lower_bounds[bin_cnt] = distinct_values[bin_cnt]
        rest_sample_cnt -= counts[bin_cnt]
        bin_cnt += 1
    if bin_cnt < max_bin:
        rest = sorted(range(bin_cnt, num_values),
                      key=lambda i: distinct_values[i])
        distinct_values[bin_cnt:] = [distinct_values[i] for i in rest]
        counts[bin_cnt:] = [counts[i] for i in rest]
        mean_bin_size = rest_sample_cnt / float(max_bin - bin_cnt)
        lower_bounds[bin_cnt] = distinct_values[bin_cnt]
        cur_cnt_inbin = 0
        for i in range(bin_cnt, num_values - 1):
            rest_sample_cnt -= counts[i]
            cur_cnt_inbin += counts[i]
            if cur_cnt_inbin >= mean_bin_size:
                upper_bounds[bin_cnt] = distinct_values[i]
                bin_cnt += 1
                lower_bounds[bin_cnt] = distinct_values[i + 1]
                if bin_cnt >= max_bin - 1:
                    break
                cur_cnt_inbin = 0
                mean_bin_size = rest_sample_cnt / float(max_bin - bin_cnt)
    order2 = sorted(range(max_bin), key=lambda i: lower_bounds[i])
    lower_bounds = [lower_bounds[i] for i in order2]
    upper_bounds = [upper_bounds[i] for i in order2]
    bounds = np.empty(bin_cnt, dtype=np.float64)
    for i in range(bin_cnt - 1):
        bounds[i] = (upper_bounds[i] + lower_bounds[i + 1]) / 2.0
    bounds[bin_cnt - 1] = np.inf
    return bounds


def _adversarial_tie_values():
    """Counts engineered to tie exactly AT and ABOVE the mean_bin_size
    boundary (VERDICT r2 weak #6): sample_size=1000, max_bin=10 →
    mean_bin_size=100.  Three values at count 150 (dedicated: > mean),
    four at exactly 100 (NOT dedicated: the reference's `>` is strict),
    thirty at count 5 filling the remainder."""
    vals = []
    for v, c in [(7.0, 150), (-3.0, 150), (11.0, 150),
                 (1.0, 100), (2.0, 100), (4.0, 100), (5.5, 100)]:
        vals += [v] * c
    for k in range(30):
        vals += [20.0 + 0.25 * k] * 5
    values = np.asarray(vals)
    assert values.size == 1000
    return values


def test_adversarial_count_ties_match_reference_oracle():
    """Bin bounds must be INVARIANT to the order of equal-count values —
    the property that makes our stable sort equivalent to the reference's
    unstable SortForPair (dedicated-bin membership is decided by a strict
    threshold over contiguous tie runs, and both the remainder and the
    final bins are re-sorted by value).  Checked against the bin.cpp
    oracle under 64 adversarial tie permutations."""
    values = _adversarial_tie_values()
    max_bin = 10
    m = BinMapper()
    m.find_bin(values, max_bin)
    ours = np.asarray(m.bin_upper_bound)

    base = _reference_find_bin_bounds(values, max_bin)
    np.testing.assert_array_equal(ours, base)
    rng = np.random.RandomState(0)
    for _ in range(64):
        permuted = _reference_find_bin_bounds(values, max_bin,
                                              tie_perm=rng)
        np.testing.assert_array_equal(base, permuted)


@pytest.mark.parametrize("order", ["C", "F"])
def test_binarize_in_row_blocks_on_threads_is_one_search_a_column(
        order, monkeypatch):
    """The table is quantized a block of rows of one column at a time, on
    threads; the codes are the per-column search's, whatever the blocks."""
    from lightgbm_tpu.io import dataset as dataset_mod
    rng = np.random.default_rng(4)
    x = np.asarray(rng.standard_normal((5003, 7)), order=order)
    x[:, 3] = np.round(x[:, 3])              # a few distinct values
    y = (x[:, 0] > 0).astype(np.float32)
    monkeypatch.setattr(dataset_mod, "BINARIZE_BLOCK_ROWS", 1000)
    ds = dataset_mod.Dataset.from_arrays(x, y, max_bin=63)
    assert ds.bins.shape == (len(ds.bin_mappers), 5003)
    for j_raw, j_inner in ds.used_feature_map.items():
        want = ds.bin_mappers[j_inner].value_to_bin(x[:, j_raw])
        np.testing.assert_array_equal(ds.bins[j_inner], want)
