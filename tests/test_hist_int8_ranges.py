"""The int8 histogram routes past one int32 accumulator's rows.

A cell of an int8 histogram sums levels of up to 127 in an int32, which
wraps past ``INT8_HIST_MAX_ROWS`` = 2^31 // 127 rows in one bin.  Every
route therefore cuts a longer table into ``accum_ranges`` row ranges, an
accumulator each, and adds them exactly as an integer pair
(``ops/hist_pallas.py``: ``accum_ranges``, ``range_sum``,
``pair_to_f32``).  Here the cap is patched down so that small tables take
one to seven ranges, and every accumulator is held against int64 NumPy
sums: bit for bit in the integer domain, and after the pair's one
rounding to float32 (exact below 2^24, which these sizes are, so "the
stated tolerance" of the small cases is 0).  One test runs at the true
scale, one row past the cap, where the parent's single accumulator wraps.
The whole-tree and data-parallel cases are in
``tests/test_hist_int8_ranged_trees.py``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.ops.hist_pallas import (
    INT8_HIST_MAX_ROWS, _quant_xla_acc, _ranged_rows, accum_ranges,
    hist_pallas_leafbatch, hist_pallas_raw,
    hist_quant_xla, pair_to_f32, quantize_values, range_sum)

F, B, C = 5, 32, 3
CHUNK = 256


def _table(n, seed=7, B=B):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    bins[0] = 3                      # a constant column: one bin, all rows
    grad = (rng.randn(n) * 0.4).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)          # every level 127
    cid = rng.randint(0, C, n).astype(np.int32)
    ok = rng.rand(n) < 0.9
    return tuple(jnp.asarray(a) for a in (bins, grad, hess, cid, ok))


def _int64_sums(bins, vals, cid, ok):
    """[F, B, C * 3] int64: the quantised levels summed cell by cell."""
    bins, vals, cid, ok = (np.asarray(a) for a in (bins, vals, cid, ok))
    want = np.zeros((F, B, C, 3), np.int64)
    rows = np.flatnonzero(ok)
    for f in range(F):
        for k in range(3):
            np.add.at(want[f, :, :, k],
                      (bins[f, rows].astype(np.int64), cid[rows]),
                      vals[k, rows].astype(np.int64))
    return want.reshape(F, B, C * 3)


def _pair_as_int64(hi, lo):
    return np.asarray(hi, np.int64) * 65536 + np.asarray(lo, np.int64)


def test_the_rule():
    assert INT8_HIST_MAX_ROWS == (1 << 31) // 127
    # every table the benchmark had before the airline cell: one range
    for rows in (1, 400_000, 10_502_144, INT8_HIST_MAX_ROWS):
        assert accum_ranges(rows) == 1
    assert accum_ranges(INT8_HIST_MAX_ROWS + 1) == 2
    assert accum_ranges(115_000_000) == 7
    # a range is whole chunks: 8,256 chunks of 2,048 rows at the most
    assert accum_ranges(8256 * 2048, 2048) == 1
    assert accum_ranges(8257 * 2048, 2048) == 2
    assert accum_ranges(115_001_344, 2048) == 7


@pytest.mark.parametrize("rows,cap,ranges,padded", [
    (5000, 6000, 1, 5120), (5000, 3000, 2, 5120), (5000, 2000, 3, 5376),
    (5000, 1000, 7, 5376), (5001, 1300, 4, 5120), (768, 1000, 1, 768)])
def test_ranges_are_balanced_whole_chunks(monkeypatch, rows, cap, ranges,
                                          padded):
    monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", cap)
    got = _ranged_rows(rows, CHUNK)
    assert got == (ranges, padded, ranges > 1)
    assert padded // ranges <= cap and padded % (ranges * CHUNK) == 0


@pytest.mark.parametrize("cap", [6000, 3000, 1000])
@pytest.mark.parametrize("rows", [5000, 4097])
def test_xla_int_route_ranged_is_the_int64_sum(monkeypatch, cap, rows):
    bins, grad, hess, cid, ok = _table(rows)
    whole = hist_quant_xla(bins, grad, hess, cid, ok, C, B, chunk=CHUNK)
    vals, scale = quantize_values(grad, hess, ok)
    monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", cap)
    ranges, padded, _ = _ranged_rows(rows, CHUNK)
    pad = padded - rows
    acc = _quant_xla_acc(
        jnp.pad(bins, ((0, 0), (0, pad))), jnp.pad(vals, ((0, 0), (0, pad))),
        jnp.pad(jnp.where(ok, cid, -1), (0, pad), constant_values=-1),
        B, C, CHUNK, ranges)
    want = _int64_sums(bins, vals, cid, ok)
    if ranges == 1:
        np.testing.assert_array_equal(np.asarray(acc), want)
    else:
        assert acc.shape == (ranges, F, B, C * 3)
        # no accumulator summed more rows than the cap allows
        assert np.abs(np.asarray(acc)).max() <= 127 * (padded // ranges)
        np.testing.assert_array_equal(_pair_as_int64(*range_sum(acc)), want)
    ranged = hist_quant_xla(bins, grad, hess, cid, ok, C, B, chunk=CHUNK)
    # float32 holds these sums exactly, so ranged == unranged bit for bit
    np.testing.assert_array_equal(np.asarray(ranged), np.asarray(whole))
    np.testing.assert_array_equal(
        np.asarray(ranged),
        want.reshape(F, B, C, 3).transpose(2, 0, 1, 3).astype(np.float32)
        * np.asarray(scale))


@pytest.mark.parametrize("cap,num_cols,B", [
    (6000, 3, B), (3000, 3, B), (1000, 3, B),
    # an unfolded pass with the one-hot held (96 value rows streamed)
    # and a one-column pass folded by 2: the same ranges on every layout
    (2000, 32, 255), (2000, 1, 64)])
def test_pallas_kernel_ranged_is_the_int64_sum(monkeypatch, cap, num_cols,
                                               B):
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import held_onehot, hist_fold
    rows = 5000
    bins, grad, hess, cid, ok = _table(rows, B=B)
    if num_cols != C:
        cid = jnp.asarray(np.random.RandomState(1).randint(
            0, num_cols, rows).astype(np.int32))
        assert (hist_fold(3, num_cols, B, 128)[0] > 1,
                held_onehot(3, num_cols, B, 128, "int8") > 0) == (
            num_cols == 1, num_cols == 32)
    # each interpreted kernel to its end before the next eager op: the
    # interpreter's callbacks run jax ops of their own on the CPU's
    # threads, and a main thread that dispatches beside them can wait on
    # them for good (it stood so once in a whole run of tier-1)
    done = jax.block_until_ready
    with pltpu.force_tpu_interpret_mode():
        whole = done(hist_pallas_leafbatch(
            bins, grad, hess, cid, ok, num_cols, B, chunk=CHUNK,
            dtype="int8"))
        monkeypatch.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", cap)
        ranged = done(hist_pallas_leafbatch(
            bins, grad, hess, cid, ok, num_cols, B, chunk=CHUNK,
            dtype="int8"))
        via_xla = hist_quant_xla(bins, grad, hess, cid, ok, num_cols, B,
                                 chunk=CHUNK)
        np.testing.assert_array_equal(np.asarray(ranged), np.asarray(whole))
        np.testing.assert_array_equal(np.asarray(ranged),
                                      np.asarray(via_xla))
        if num_cols != C:
            return
        # the kernel's own accumulators, range by range
        ranges, padded, _ = _ranged_rows(rows, CHUNK)
        vals, _ = quantize_values(grad, hess, ok)
        packed = jnp.concatenate(
            [vals, jnp.where(ok, cid, -1).astype(jnp.int8)[None]], axis=0)
        pad = padded - rows
        acc = done(hist_pallas_raw(
            jnp.pad(bins, ((0, 0), (0, pad))).astype(jnp.int8),
            jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1),
            B=B, chunk=CHUNK, dtype="int8", ranges=ranges))
    want = _int64_sums(bins, vals, cid, ok)
    if ranges == 1:
        assert acc.shape == (F, B, 128)
        np.testing.assert_array_equal(np.asarray(acc)[..., :C * 3], want)
    else:
        assert acc.shape == (ranges, F, B, 128)
        each = np.asarray(acc, np.int64)[..., :C * 3]
        assert np.abs(each).max() <= 127 * (padded // ranges)
        np.testing.assert_array_equal(each.sum(axis=0), want)
        np.testing.assert_array_equal(
            _pair_as_int64(*range_sum(acc))[..., :C * 3], want)


def test_pair_is_exact_and_rounds_once():
    rng = np.random.RandomState(11)
    acc = rng.randint(-(1 << 31), 1 << 31, (7, 4000), dtype=np.int64)
    acc[:, 0] = (1 << 31) - 1                    # seven full accumulators
    acc[:, 1] = -(1 << 31)
    want = acc.sum(axis=0)
    hi, lo = range_sum(jnp.asarray(acc.astype(np.int32)))
    np.testing.assert_array_equal(_pair_as_int64(hi, lo), want)
    # the float32 nearest the int64 sum: what numpy's own cast gives
    np.testing.assert_array_equal(np.asarray(pair_to_f32(hi, lo)),
                                  want.astype(np.float32))
    # pairs add like integers: two shards' halves summed, then carried
    np.testing.assert_array_equal(
        np.asarray(pair_to_f32(hi + hi, lo + lo)),
        (2 * want).astype(np.float32))


def test_one_row_past_the_cap_is_the_int64_sum():
    """The true wrap scale, and what fails on the parent's arithmetic:
    one constant column, every row's hessian level 127, 2,049 rows more
    than one int32 accumulator holds.  A single accumulator's sum is
    127 x rows = 2^31 + 260,215 and wraps negative; two ranges hold it."""
    rows = INT8_HIST_MAX_ROWS + 2049
    assert 127 * rows > (1 << 31) - 1
    bins = jnp.zeros((1, rows), jnp.int8)
    ones = jnp.ones((rows,), jnp.float32)
    hist = hist_quant_xla(bins, ones, ones, jnp.zeros((rows,), jnp.int32),
                          jnp.ones((rows,), bool), 1, 2)
    got = np.asarray(hist, np.float64)[0, 0]     # [B, 3]
    scale = 1.0 / 127.0
    want = np.array([[127.0 * rows * scale, 127.0 * rows * scale, rows],
                     [0.0, 0.0, 0.0]])
    # float32's one rounding of a sum of 2.1e9, and the scale's
    np.testing.assert_allclose(got, want, rtol=3e-7)
    assert (got[0] > 0).all()
    # and in the integer domain, exactly
    vals, _ = quantize_values(ones, ones, jnp.ones((rows,), bool))
    assert np.asarray(vals[:, :4]).tolist() == [[127] * 4, [127] * 4,
                                                [1] * 4]
    chunk = 65536
    ranges, padded, paired = _ranged_rows(rows, chunk)
    assert (ranges, paired) == (2, True)
    pad = padded - rows
    acc = _quant_xla_acc(
        jnp.pad(bins, ((0, 0), (0, pad))), jnp.pad(vals, ((0, 0), (0, pad))),
        jnp.pad(jnp.zeros((rows,), jnp.int32), (0, pad),
                constant_values=-1), 2, 1, chunk, ranges)
    np.testing.assert_array_equal(
        _pair_as_int64(*range_sum(acc))[0],
        np.array([[127 * rows, 127 * rows, rows], [0, 0, 0]], np.int64))
    # the parent's arithmetic, for the record: one int32 sum of the same
    assert int(np.asarray(acc).astype(np.int32).sum(
        axis=0, dtype=np.int32)[0, 0, 1]) < 0
