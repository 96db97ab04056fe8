"""The wide dense table: more columns than one VMEM-resident block of the
histogram kernel holds, so every pass runs the kernel's feature-block grid
(``hist_pallas.feature_grid``).

Two things are held here, on the CPU at a small size.  The kernel alone, in
interpret mode on multi-block shapes, equals exact integer histograms bit
for bit.  And the program, on the level-wise int8 route through
``GBDT.run_training`` with that kernel under it, agrees with the
benchmark's plain reference (``benchmarks/harness/reference.py``: NumPy,
float64, nothing of the program) under the limits of the benchmark's wide
cell, ``epsilon-levelwise-int8.train``, while the reference's answer in
int4 does not.  The cell's own size (400,000 x 2,000) runs on the chip;
``tests/test_tpu_compile.py`` compiles it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

CELL = "epsilon-levelwise-int8.train"
# what the three assertions on the program need and no more: columns past
# one feature block at either lane width (104: three blocks of 40 at 128
# lanes, four of 32 at 192), rows enough for 255-leaf trees the cell's
# limits can judge.  At 4,096 x 200 the fixture took 115 s alone and 150 s
# in a loaded run, most of it the interpreter's blocks; this takes 38 s.
ROWS, COLUMNS = 2048, 104


def _exact(bins, vals, cid, B, lanes):
    """[F, B, lanes] int64: every row's three levels added into the cell
    of its bin and leaf column, in integers."""
    F = bins.shape[0]
    live = cid >= 0
    out = np.zeros((F, B, lanes), np.int64)
    for f in range(F):
        key = bins[f, live].astype(np.int64) * lanes + cid[live] * 3
        for k in range(3):
            out[f] += np.bincount(key + k, weights=vals[k, live],
                                  minlength=B * lanes).astype(
                                      np.int64).reshape(B, lanes)
    return out


@pytest.mark.parametrize("F,lanes,num_cols,grid", [
    # 128 lanes unfolded, the one-hot held and 96 value rows streamed, 48
    # features a block: whole blocks, and a ragged last one
    (200, 128, 32, (40, 5)), (100, 128, 32, (40, 3)),
    # 128 lanes, the one-hot streamed (33-42 columns), 48 a block
    (200, 128, 40, (40, 5)),
    # the same grid under each fold of the bin code
    (100, 128, 1, (40, 3)), (200, 128, 4, (40, 5)), (100, 128, 16, (40, 3)),
    # 192 lanes (the 64-leaf level), the one-hot held: 32 features a block
    (200, 192, 64, (32, 7)), (100, 192, 64, (32, 4)),
])
def test_multi_block_kernel_equals_exact_histograms(F, lanes, num_cols,
                                                    grid):
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import (_hist_pallas_raw_fn,
                                              feature_grid, held_onehot,
                                              hist_fold)
    B, N, chunk = 255, 1024, 512
    fold, gw = hist_fold(3, num_cols, B, lanes)
    assert (fold > 1) == (num_cols <= 16)
    held = held_onehot(3, num_cols, B, lanes, "int8")
    assert (held > 0) == (num_cols in (32, 64))
    assert feature_grid(F, B, lanes, chunk, held) == grid
    assert grid[0] * grid[1] >= F and grid[1] > 1
    rng = np.random.RandomState(F + lanes + num_cols)
    bins = rng.randint(0, B, (F, N)).astype(np.uint8)     # codes >= 128 too
    vals = np.stack([rng.randint(-127, 128, N), rng.randint(0, 128, N),
                     np.ones(N, np.int64)])
    cid = rng.randint(-1, num_cols, N)                    # -1: masked rows
    vals = vals * (cid >= 0)
    packed = np.concatenate([vals, cid[None]]).astype(np.int8)
    with pltpu.force_tpu_interpret_mode():
        got = _hist_pallas_raw_fn(
            jnp.asarray(bins.astype(np.int8)), jnp.asarray(packed), B=B,
            chunk=chunk, dtype="int8", lanes=lanes, fold=fold, gw=gw,
            held=held)
    assert got.shape == (F, B, lanes) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got, np.int64),
                                  _exact(bins, vals, cid, B, lanes))


def test_feature_blocks_fit_the_scoped_vmem():
    """The account of the rotating block, in bytes as Mosaic lays the
    windows out: two buffers of the accumulator, of the bin rows and of
    the side-band, with room left for the kernel's temporaries."""
    from lightgbm_tpu.ops import hist_pallas as hp
    for B in (64, 100, 128, 200, 255, 256):
        for lanes in (128, 192):
            for chunk in (512, 2048):
                for held in (0, 64, 96, lanes):
                    fb = hp.rotating_feature_block(B, lanes, chunk, held)
                    assert fb >= 8 and fb % 8 == 0
                    rows, cols = (held, B) if held else (B, lanes)
                    windows = 2 * (fb * (-(-rows // 8) * 8)
                                   * (-(-cols // 128) * 128) * 4
                                   + fb * chunk + 32 * chunk)
                    assert windows <= (hp.VMEM_SCOPED_BYTES
                                       - hp.VMEM_TEMPORARIES_BYTES
                                       ) or fb == 8
    # what the benchmark's cells run: the narrow table is one block on
    # every pass, the wide one 42 blocks of 48 and, at 192 lanes, 84 of 24
    assert hp.feature_grid(28, 255, 128, 2048) == (28, 1)
    assert hp.feature_grid(28, 255, 192, 2048) == (28, 1)
    assert hp.feature_grid(64, 255, 192, 2048) == (64, 1)
    assert hp.feature_grid(96, 255, 128, 2048) == (96, 1)
    assert hp.feature_grid(2000, 255, 128, 2048) == (48, 42)
    assert hp.feature_grid(2000, 255, 192, 2048) == (24, 84)
    assert hp.feature_grid(2000, 255, 192, 2048, 192) == (32, 63)
    assert hp.feature_grid(2000, 255, 128, 2048, 96) == (48, 42)


# ------------------------------------------- the program and the reference

_WIDE_RUNS = {}


def _wide_run(min_data_in_leaf="1"):
    """One run of the benchmark's own harness on the wide cell cut to
    2,048 rows and 104 columns (``bench_cut.run_cut_cell``), with the
    cell's ``min_data_in_leaf`` of 1 or another laid over it; once a
    module and value."""
    from bench_cut import run_cut_cell
    if min_data_in_leaf not in _WIDE_RUNS:
        _WIDE_RUNS[min_data_in_leaf] = run_cut_cell(
            CELL, 3000000019, ROWS, COLUMNS,
            params=({"min_data_in_leaf": min_data_in_leaf}
                    if min_data_in_leaf != "1" else None))
    return _WIDE_RUNS[min_data_in_leaf]


@pytest.fixture(scope="module")
def wide_run():
    yield _wide_run()
    _WIDE_RUNS.clear()


# (statistics a leaf column, feature blocks a tree, passes that hold the
# one-hot): the cell's ``min_data_in_leaf=1`` builds two, grad and hess,
# and all eight passes are 128 lanes in 3 blocks of 40, the 64-leaf one
# too (128 value rows), which streams the one-hot; 2 keeps the count
# column, and the 64-leaf pass of 192 value rows is 192 lanes in 4
# blocks of 32 and holds it, as the 32-leaf pass does in both
@pytest.mark.parametrize("min_data_in_leaf,stats,blocks,held", [
    ("1", 2, 8 * 3, 1), ("2", 3, 7 * 3 + 4, 2)])
def test_wide_program_took_the_feature_block_grid(
        wide_run, min_data_in_leaf, stats, blocks, held):
    _line, counters = _wide_run(min_data_in_leaf)
    # one traced tree; no pass left the Pallas route
    passes = sum(v for k, v in counters.items()
                 if k.startswith("hist/pallas_fold_"))
    assert passes and passes % 8 == 0
    assert counters["hist/stats_%d" % stats] == passes
    assert "hist/stats_%d" % (5 - stats) not in counters
    assert counters["hist/pallas_fblocks"] == passes // 8 * blocks
    assert counters["hist/pallas_held_onehot"] == passes // 8 * held
    assert "hist/xla_int_kernel" not in counters
    # and every level's row routing took the kernel
    assert counters["partition/route_pallas"] == passes
    assert "partition/route_xla" not in counters


def test_wide_program_is_correct_by_the_cells_limits(wide_run):
    line, _counters = wide_run
    assert line["failed"] == 0 and line["attempted"] == 8
    checks = line["checks"]
    for name in ("score_gap", "bin_code_gap", "split_gain_gap",
                 "leaf_value_gap", "leaf_sum_gap", "trees_short"):
        assert checks[name]["limit"] is not None
        assert checks[name]["value"] <= checks[name]["limit"], (
            name, checks[name])
    assert line["correct"] is True


def test_wide_control_in_int4_is_not_correct(wide_run):
    line, _counters = wide_run
    assert line["control_correct"] is False
    assert any(c["limit"] is not None and c["value"] > c["limit"]
               for name, c in line["checks"].items()
               if name.startswith("control."))
    assert line["faults_correct"] == {"half_batch": False,
                                      "state_unchanged": False}
