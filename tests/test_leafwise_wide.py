"""Best-first growth over a wide table: the compacted grower with the
row-blocked partition kernel under it, and the program on the benchmark's
``epsilon-leafwise-f32`` configuration.

Held here, on the CPU at a small size:

- ``_grow_leafcompact`` (the Pallas partition kernel run by the
  interpreter, the pane past one block of the narrow tables' kernel and,
  in one case, past one row block) grows the tree a plain best-first
  grower grows: NumPy, float64, exact histograms, nothing of the program;
- the program on the cell's configuration through the benchmark's own
  harness (``benchmarks/run.py --rows``) is ``correct`` by the cell's
  limits, and the reference's answer in bfloat16 is not.

The cell's own size (400,000 x 2,000) runs on the chip;
``tests/test_tpu_compile_wide.py`` compiles it.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "epsilon-leafwise-f32.train"
K_EPSILON = 1e-15


# ------------------------------------------------- a plain best-first grower

def plain_best_first(bins, grad, hess, num_bin, num_leaves, min_data,
                     min_hess):
    """LightGBM's growth in float64, written out: every leaf keeps the best
    of its candidates "bin <= t goes left" (both sides ``min_data`` rows and
    ``min_hess`` hessian, t <= num_bin - 2, score GL^2/HL + GR^2/HR not
    under the leaf's own G^2/H; the larger threshold wins a tie within a
    column, the smaller column across columns), the leaf of the largest
    gain is split while that gain is positive (the lowest leaf wins a tie),
    the left child keeps the leaf's number and the right takes the next.
    Returns (splits [(leaf, feature, threshold, the leaf's rows)], leaf
    values, leaf counts)."""
    F, N = bins.shape
    g = grad.astype(np.float64)
    h = hess.astype(np.float64)

    def best(rows):
        G, H = g[rows].sum(), h[rows].sum()
        top = (-np.inf, 0, 0, None)
        for f in range(F):
            b = bins[f, rows]
            lg = np.cumsum(np.bincount(b, weights=g[rows], minlength=num_bin))
            lh = np.cumsum(np.bincount(b, weights=h[rows], minlength=num_bin))
            lc = np.cumsum(np.bincount(b, minlength=num_bin))
            rg, rh, rc = G - lg, H - lh, len(rows) - lc
            with np.errstate(divide="ignore", invalid="ignore"):
                score = (lg * lg / (lh + K_EPSILON)
                         + rg * rg / (rh + K_EPSILON))
            shift = G * G / (H + 2 * K_EPSILON)
            ok = ((lc >= min_data) & (rc >= min_data)
                  & (lh + K_EPSILON >= min_hess)
                  & (rh + K_EPSILON >= min_hess)
                  & (np.arange(num_bin) <= num_bin - 2) & (score >= shift))
            score = np.where(ok, score, -np.inf)
            t = num_bin - 1 - int(np.argmax(score[::-1]))
            if score[t] - shift > top[0] and np.isfinite(score[t]):
                top = (score[t] - shift, f, t,
                       (-lg[t] / (lh[t] + K_EPSILON),
                        -rg[t] / (rh[t] + K_EPSILON)))
        return top

    leaves = [np.arange(N)]
    cands = [best(leaves[0])]
    values = [0.0]
    splits = []
    while len(leaves) < num_leaves:
        leaf = int(np.argmax([c[0] for c in cands]))
        gain, f, t, outs = cands[leaf]
        if not gain > 0.0:
            break
        rows = leaves[leaf]
        right = bins[f, rows] > t
        splits.append((leaf, f, t, rows))
        leaves[leaf], values[leaf] = rows[~right], outs[0]
        leaves.append(rows[right])
        values.append(outs[1])
        cands[leaf] = best(leaves[leaf])
        cands.append(best(leaves[-1]))
    return splits, np.array(values), np.array([len(r) for r in leaves])


def _table(rows, columns, num_bin, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, columns)
    bins = np.empty((columns, rows), np.uint8)
    for f in range(columns):
        rank = np.argsort(np.argsort(x[:, f], kind="stable"))
        bins[f] = rank * num_bin // rows
    weights = rng.randn(columns) * (rng.rand(columns) < 0.2)
    margin = x @ weights / np.sqrt((weights ** 2).sum()) \
        + np.sin(2 * x[:, 0]) * x[:, 1]
    y = margin + 0.3 * rng.randn(rows) > 0
    score = 0.7 * rng.randn(rows)
    p = 1.0 / (1.0 + np.exp(-score))
    return bins, (p - y).astype(np.float32), (p * (1 - p)).astype(np.float32)


@pytest.mark.parametrize("rows,columns,leaves,overlap,grid,num_bin", [
    # the width tests/test_wide_table.py drives the harness at: past the
    # one block of the narrow tables' kernel, one block of the row-blocked
    (2048, 104, 63, True, (512, 128, 1), 64),
    # past one row block: two of 512 rows, the last one ragged, under
    # either schedule
    (1024, 1000, 15, True, (512, 512, 2), 64),
    (1024, 1000, 15, False, (512, 512, 2), 64),
    # the cell's 255 bins with the histograms the float kernel's, run by
    # the interpreter as a TPU would route them: five statistics of one
    # leaf column a pass, the bin code folded by 8 (``hist_fold``), dead
    # chunks skipped, three feature blocks of 40
    (2048, 104, 63, True, (512, 128, 1), 255),
])
def test_compact_grower_grows_the_plain_growers_tree(
        monkeypatch, rows, columns, leaves, overlap, grid, num_bin):
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.models.grower_unified import grow_tree_leafcompact
    from lightgbm_tpu.ops.compact import pane_rows, partition_grid
    assert partition_grid(pane_rows(columns)) == grid
    min_data, min_hess = 4, 1.0
    bins, grad, hess = _table(rows, columns, num_bin, seed=rows + columns)

    def grow():
        return grow_tree_leafcompact(
            jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(rows, bool), jnp.ones(columns, bool),
            jnp.full(columns, num_bin, jnp.int32), num_leaves=leaves,
            num_bins_max=num_bin, min_data_in_leaf=min_data,
            min_sum_hessian_in_leaf=min_hess, compute_dtype=jnp.float32,
            use_pallas_partition=True, partition_overlap=overlap,
            interpret=True)
    if num_bin == 255:
        from jax.experimental import pallas as pl
        from lightgbm_tpu.ops import compact, leafid_pallas
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # as bench_cut.run_cut_cell: the TPU interpreter's ANY, and no
        # view of a ref
        monkeypatch.setattr(compact, "PANE_SPACE", pl.ANY)
        monkeypatch.setattr(leafid_pallas, "WORDS_BY_VIEW", False)
        telemetry.reset()
        telemetry.enable(fence=True)
        try:
            with pltpu.force_tpu_interpret_mode():
                tree = jax.block_until_ready(grow())
            counters = telemetry.snapshot()["counters"]
        finally:
            telemetry.disable()
            telemetry.reset()
        # the root's pass and one a bucket width, every one folded
        assert counters["hist/pallas_f32"] == counters[
            "hist/pallas_fold_8"] >= 2
        assert "hist/pallas_fold_1" not in counters
        assert "hist/xla_einsum" not in counters
        # the leaf ids replayed after the split loop
        assert counters["partition/leafids_pallas"] == 1
    else:
        tree = grow()
    splits, values, counts = plain_best_first(
        bins, grad, hess, num_bin, leaves, min_data, min_hess)
    assert len(splits) == leaves - 1 == int(tree.num_leaves) - 1
    # the same order: node k is the k-th split, its left child the leaf it
    # split (or the later node that leaf became), its right the new leaf
    np.testing.assert_array_equal(np.asarray(tree.split_feature),
                                  [f for _, f, _, _ in splits])
    # and the same thresholds, or (float32 dust of the sibling subtraction
    # in a bin the leaf has no row in breaks the tie the other way) ones
    # that part the leaf's rows the same
    for (_, f, t, at), got in zip(splits, np.asarray(tree.threshold_bin)):
        between = (bins[f, at] > min(t, got)) & (bins[f, at] <= max(t, got))
        assert not between.any(), (f, t, got)
    parent = np.asarray(tree.leaf_parent)
    last_split_of = {}
    for k, (leaf, _, _, _) in enumerate(splits):
        last_split_of[leaf] = k
        last_split_of[k + 1] = k
    np.testing.assert_array_equal(
        parent, [last_split_of[l] for l in range(leaves)])
    np.testing.assert_array_equal(np.asarray(tree.leaf_count), counts)
    # float32 sums of a few thousand float32 gradients against float64
    np.testing.assert_allclose(np.asarray(tree.leaf_value), values,
                               rtol=2e-4, atol=1e-6)


# ------------------------------------------- the program and the reference

ROWS, COLUMNS = 2048, 104


@pytest.fixture(scope="module")
def leafwise_run():
    """One run of the benchmark's own harness on the cell cut to 2,048 rows
    and 104 columns, as ``tests/test_wide_table.py`` cuts the level-wise
    one: one slice of 8 per-tree turns on the compacted grower, the Pallas
    partition and float histogram kernels run by the interpreter."""
    from bench_cut import run_cut_cell
    return run_cut_cell(CELL, 3400000019, ROWS, COLUMNS, fence=True)


def test_leafwise_program_took_the_kernels(leafwise_run):
    _line, counters = leafwise_run
    # one bucket width at 2,048 rows: one partition kernel traced, of one
    # row block, and no argsort; the histograms are the float kernel's
    assert counters["partition/pallas"] >= 1
    assert counters["partition/pallas_rblocks"] == counters[
        "partition/pallas"]
    # every one of them reads and writes the pane itself (PR 37)
    assert counters["partition/in_pane"] == counters["partition/pallas"]
    assert "partition/xla" not in counters
    assert "partition/wide_f_fallback" not in counters
    # the leaf ids replayed once a tree program after its split loop
    assert counters["partition/leafids_pallas"] >= 1
    assert "partition/leafids_xla" not in counters
    assert counters["hist/pallas_f32"] >= 2
    assert "hist/xla_einsum" not in counters
    # every float pass (one leaf column of five statistics, 255 bins)
    # folds the bin code by 8
    assert counters["hist/pallas_fold_8"] == counters["hist/pallas_f32"]
    assert "hist/pallas_fold_1" not in counters


def test_leafwise_program_is_correct_by_the_cells_limits(leafwise_run):
    line, _counters = leafwise_run
    assert line["failed"] == 0 and line["attempted"] == 8
    checks = line["checks"]
    for name in ("score_gap", "bin_code_gap", "split_gain_gap",
                 "leaf_value_gap", "leaf_sum_gap", "trees_short"):
        assert checks[name]["limit"] is not None
        assert checks[name]["value"] <= checks[name]["limit"], (
            name, checks[name])
    assert line["correct"] is True


def test_leafwise_control_in_bfloat16_is_not_correct(leafwise_run):
    line, _counters = leafwise_run
    assert line["control_correct"] is False
    assert any(c["limit"] is not None and c["value"] > c["limit"]
               for name, c in line["checks"].items()
               if name.startswith("control."))
    assert line["faults_correct"] == {"half_batch": False,
                                      "state_unchanged": False}


# ------------------------------------------------- the cell's own metrics

def test_partition_metrics_read_what_is_there():
    """``partition_roofline`` is the algorithm's bytes from shapes over the
    measured scope time; both new readers give nothing, and do not raise,
    where the program has nothing for them (the parent commit has no
    row-block counter; a run off the chip has no peaks)."""
    import types
    added = [p for p in (os.path.join(ROOT, "benchmarks"),)
             if p not in sys.path]
    sys.path[:0] = added
    try:
        from harness import readers
        state = types.SimpleNamespace(
            values={}, counters={}, summary=None, traced=None,
            peaks={"hbm_bytes_per_s": 819e9},
            shape={"rows": 400_000, "features": 2_000, "num_leaves": 255})
        assert readers.read("partition_roofline", state) is None
        assert readers.read("partition_row_blocks", state) is None
        assert readers.read("partition_ms_per_iter", state) is None
        # 8 levels x 400,000 rows x 2,009 pane bytes, read and written:
        # 12.86 GB, 15.70 ms at 819 GB/s
        state.values["partition_ms_per_iter"] = 157.0
        share = readers.read("partition_roofline", state)
        assert abs(share - 100 * 15.70 / 157.0) < 0.01
        state.peaks = None
        assert readers.read("partition_roofline", state) is None
        state.counters["partition/pallas_rblocks"] = 27
        assert readers.read("partition_row_blocks", state) == 27
        # PR 37's two: the kernels' own time, by the name of the jitted
        # function their custom calls lie under, and the kernels that
        # partition inside the pane; nothing on a program that has neither
        assert readers.read("partition_in_pane_kernels", state) is None
        assert readers.read("partition_kernel_ms_per_iter", state) is None
        state.counters["partition/in_pane"] = 9
        assert readers.read("partition_in_pane_kernels", state) == 9
        seen = []
        state.traced_iterations = 8
        state.summary = types.SimpleNamespace(
            scoped_seconds=lambda rx: seen.append(rx) or 1.2)
        assert readers.read("partition_kernel_ms_per_iter", state) == 150.0
        (pattern,) = seen
        from lightgbm_tpu.ops import compact
        import re
        assert re.search(pattern, "leafcompact_split/while/body/partition/"
                         "jit(%s)/partition/pallas_call"
                         % compact._partition_in_pane_fn.__name__)
        assert not re.search(pattern, "partition/jit(_partition_segment_fn)"
                             "/partition/pallas_call")
    finally:
        for p in added:
            sys.path.remove(p)


def test_float_kernel_skips_dead_chunks_bit_for_bit():
    """Asked to (``skip_dead``, the compacted grower's range passes), the
    float kernel passes over a chunk in which no row is live (the tail
    of a bucketed range): the histogram of a range with dead chunks
    before and after its rows is, bit for bit, the histogram of the live
    chunks alone."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.hist_pallas import _hist_pallas_raw_fn
    F, B, chunk, stats = 16, 256, 512, 5
    rng = np.random.RandomState(7)
    live = slice(chunk, 3 * chunk)
    bins = rng.randint(0, 255, (F, 5 * chunk)).astype(np.uint8)
    packed = np.zeros((stats + 1, 5 * chunk), np.float32)
    packed[stats] = -1.0
    packed[:stats - 1, live] = rng.randn(stats - 1, 2 * chunk)
    packed[stats - 1, live] = 1.0
    packed[stats, live] = np.where(rng.rand(2 * chunk) < 0.8, 0.0, -1.0)
    packed[:stats, live] *= packed[stats, live] >= 0

    def hist(b, p, skip_dead):
        with pltpu.force_tpu_interpret_mode():
            return np.asarray(_hist_pallas_raw_fn(
                jnp.asarray(b.astype(np.int8)),
                jnp.asarray(p, jnp.bfloat16), B=B, chunk=chunk,
                dtype="bf16v", lanes=128, stats=stats,
                skip_dead=skip_dead))
    whole = hist(bins, packed, True)
    assert np.abs(whole[:, :, :stats]).sum() > 0
    np.testing.assert_array_equal(whole, hist(bins[:, live],
                                              packed[:, live], True))
    # and the kernel that sweeps every chunk, the level modes' own
    np.testing.assert_array_equal(whole, hist(bins, packed, False))
