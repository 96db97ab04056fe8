"""Compacted leaf-wise grower: streaming partition op + tree equivalence.

The compacted grower (models/grower_leafcompact.py) must grow EXACTLY the
trees of the masked grower (models/grower.py) — same structure, and
bit-identical values in the int8 mode whose arithmetic is order-free.  The
partition op itself is differentially tested: Pallas kernel (interpret
mode on CPU) vs the stable-argsort XLA oracle.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops.compact import (BLOCK, bucket_table, pack_planes,
                                      pane_layout, partition_segment,
                                      range_origin, unpack_values)


def _pane_case(rng, R, P, width, start, cnt, side, left=None):
    """A random two-sided pane of ``R`` plane rows over a root bucket of
    ``P`` lanes, and the mask of the range [start, start + cnt) in a
    bucket of ``width``: (pane, mask3, plcnt, what the call must
    return).  ``left``: every lane's direction, or None for a coin."""
    rows, lanes_total = pane_layout(R, P)
    pane = rng.randint(-128, 128, (2, rows, lanes_total)).astype(np.int8)
    cs, lanes = range_origin(pane, start, width)
    lane = int(cs) + np.arange(lanes)
    m = (rng.randint(0, 2, lanes) if left is None
         else np.full(lanes, left)).astype(np.int8)
    mask3 = np.where((lane >= start) & (lane < start + cnt), m,
                     -1).astype(np.int8)
    plcnt = int((mask3 == 1).sum())
    # the contract, in NumPy: the children on the other side, left rows
    # then right rows in their order, and every other byte of both sides
    # what it was
    want = pane.copy()
    inner = pane[side][:, start:start + cnt]
    went = mask3[start - int(cs):start - int(cs) + cnt]
    want[1 - side][:, start:start + plcnt] = inner[:, went == 1]
    want[1 - side][:, start + plcnt:start + cnt] = inner[:, went == 0]
    return pane, mask3, plcnt, want


def _partition(pane, mask3, side, start, cnt, plcnt, width, **kw):
    return np.asarray(partition_segment(
        jnp.asarray(pane), jnp.asarray(mask3), jnp.int32(side),
        jnp.int32(start), jnp.int32(cnt), jnp.int32(plcnt), width=width,
        **kw))


KERNELS = {"oracle": {},
           "serial": dict(use_pallas=True, interpret=True, overlap=False),
           "overlap": dict(use_pallas=True, interpret=True, overlap=True)}


@pytest.mark.parametrize("delta,cnt", [
    (0, 4096), (0, 4000), (100, 3000), (4095, 1), (0, 1), (123, 0),
])
def test_partition_kernel_matches_oracle(delta, cnt):
    """The cases the out-of-pane call had over a [11, 4096] range, now a
    bucket of 4,096 lanes that starts 2,048 lanes into side 1 of a root
    of 8,192."""
    rng = np.random.RandomState(delta + cnt)
    R, P, W = 11, 8192, 4096
    pane, mask3, plcnt, want = _pane_case(rng, R, P, W, 2048 + delta, cnt, 1)
    args = (pane, mask3, 1, 2048 + delta, cnt, plcnt, W)
    oracle = _partition(*args)
    kernel = _partition(*args, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(oracle, want)
    np.testing.assert_array_equal(oracle, kernel)


# pane heights past one row block (ops/compact.partition_grid): features,
# (lane block, row-block height, row blocks).  216 rows are one block of
# the row-blocked kernel, padded; 1,016 two, the last one ragged (504 of
# 512); 2,016 the wide cell's three, whole; 2,512 three with a ragged last
TALL_PANES = [(200, (512, 224, 1)), (1000, (512, 512, 2)),
              (2000, (512, 672, 3)), (2503, (512, 864, 3))]


@pytest.mark.parametrize("delta,cnt,features", [
    (0, 8192, None), (777, 6000, None), (2047, 4097, None),
    (100, 3000, None), (4095, 2049, None),
] + [(777, 6000, f) for f, _ in TALL_PANES]
  + [(0, 8192, 2000), (4095, 2049, 1000), (123, 0, 1000)])
def test_partition_dma_overlap_bit_identity(delta, cnt, features):
    """The overlapped-DMA kernel schedule (both window reads up front,
    left write-back under the right blend, VMEM-side merge of the fresh
    left lanes into the right window) must be BIT-identical to both the
    serialized schedule and the oracle.  A bucket of 8,192 lanes runs 5
    lane blocks (17 of the row-blocked kernel's), so the running offsets
    and the cross-block window overlaps (the lanes the merge exists for)
    are genuinely exercised.  The range starts 4,096 lanes into side 0 of
    a root of 16,384."""
    rng = np.random.RandomState(delta * 7 + cnt)
    R, P, W = 13, 16384, 8192
    if features is not None:
        # the row-blocked kernel: one-hots made at a lane block's first
        # row block land every row block's rows, 17 lane blocks of 512
        from lightgbm_tpu.ops.compact import pane_rows, partition_grid
        R = pane_rows(features)
        assert partition_grid(R) == dict(TALL_PANES)[features]
    start = 4096 + delta
    pane, mask3, plcnt, want = _pane_case(rng, R, P, W, start, cnt, 0)
    args = (pane, mask3, 0, start, cnt, plcnt, W)
    np.testing.assert_array_equal(_partition(*args), want)
    np.testing.assert_array_equal(_partition(*args, **KERNELS["serial"]),
                                  want)
    np.testing.assert_array_equal(_partition(*args, **KERNELS["overlap"]),
                                  want)


# (root lanes, bucket, start, cnt, every lane's direction or a coin)
IN_PANE_CASES = {
    "aligned": (8192, 4096, 2048, 3000, None),
    "unaligned": (8192, 4096, 2048 + 777, 3000, None),
    # the bucket clamped against the root's end, the range's last lane
    # the pane's last: the right window starts in the lane padding
    "ends_at_last_lane": (8192, 2048, 8192 - 1500, 1500, None),
    "last_lane_alone": (8192, 2048, 8191, 1, 0),
    "one_lane": (8192, 2048, 3000, 1, 1),
    "none_left": (8192, 4096, 2500, 3000, 0),
    "all_left": (8192, 4096, 2500, 3000, 1),
    "bucket_twice_the_range": (8192, 4096, 4000, 2048, None),
    "root": (4096, 4096, 0, 4096, None),
}


@pytest.mark.parametrize("features", [4, 100])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("case", sorted(IN_PANE_CASES))
@pytest.mark.parametrize("side", [0, 1])
def test_partition_in_pane_contract(side, case, kernel, features):
    """One contract for the oracle, the one-block kernels under both DMA
    schedules (4 columns) and the row-blocked kernel (100 columns: a pane
    past 88 rows): the children's lanes on the written side hold the
    stable partition; EVERY OTHER BYTE OF BOTH SIDES is what it was (what
    the ``where`` over the sliced-out range used to enforce), so the read
    side is untouched."""
    from lightgbm_tpu.ops.compact import pane_rows, partition_grid
    R = pane_rows(features)
    assert (partition_grid(R)[0] == BLOCK) == (features == 4)
    P, W, start, cnt, left = IN_PANE_CASES[case]
    rng = np.random.RandomState(len(case) * 31 + side)
    pane, mask3, plcnt, want = _pane_case(rng, R, P, W, start, cnt, side,
                                          left)
    assert plcnt == {None: plcnt, 0: 0, 1: cnt}[left]
    out = _partition(pane, mask3, side, start, cnt, plcnt, W,
                     **KERNELS[kernel])
    np.testing.assert_array_equal(out[side], pane[side])
    np.testing.assert_array_equal(out, want)


def test_partition_chain_reads_each_child_from_its_side():
    """Three splits in turn, as the grower makes them: the root (side 0
    to side 1), its right child (side 1 to side 0), that child's left
    child (side 0 to side 1).  Each range read from the side it was
    written to holds the rows a NumPy replay puts there; the same lanes
    of the wrong side hold a dead ancestor's."""
    rng = np.random.RandomState(17)
    R, N, P = 12, 5000, 6144
    rows = rng.randint(-128, 128, (R, N)).astype(np.int8)
    pane = np.zeros((2,) + pane_layout(R, P), np.int8)
    pane[0, :R, :N] = rows
    pane = jnp.asarray(pane)
    table = bucket_table(N)
    replay = rows.copy()          # columns in partitioned order
    start, cnt, side = 0, N, 0
    for by, take_left in enumerate((False, True, True)):
        width = min(w for w in table if w >= cnt)
        cs, lanes = range_origin(pane, start, width)
        lane = int(cs) + np.arange(lanes)
        inseg = (lane >= start) & (lane < start + cnt)
        go_left = np.zeros(lanes, bool)
        go_left[inseg] = replay[by, start:start + cnt] < 0
        mask3 = np.where(inseg, go_left, -1).astype(np.int8)
        plcnt = int(go_left.sum())
        assert 0 < plcnt < cnt
        pane = partition_segment(
            pane, jnp.asarray(mask3), jnp.int32(side), jnp.int32(start),
            jnp.int32(cnt), jnp.int32(plcnt), width=width, use_pallas=True,
            interpret=True)
        seg = replay[:, start:start + cnt]
        left = seg[by] < 0
        replay[:, start:start + cnt] = np.concatenate(
            [seg[:, left], seg[:, ~left]], axis=1)
        side = 1 - side
        got = np.asarray(pane)
        np.testing.assert_array_equal(got[side, :R, start:start + cnt],
                                      replay[:, start:start + cnt])
        assert not np.array_equal(got[1 - side, :R, start:start + cnt],
                                  replay[:, start:start + cnt])
        start, cnt = ((start, plcnt) if take_left
                      else (start + plcnt, cnt - plcnt))


@pytest.mark.parametrize("features,grid", [
    (28, (BLOCK, 40, 1)), (79, (BLOCK, 88, 1)), (80, (512, 96, 1)),
] + TALL_PANES + [(4000, (512, 832, 5))])
def test_partition_grid_fits_any_pane(monkeypatch, features, grid):
    """A pane of any height goes through the Pallas kernel: the grid
    (partition_grid) cuts what one block cannot hold into row blocks whose
    priced working set is under the budget, where the eligibility rule
    used to send the table to the argsort oracle.  88 rows or fewer are
    one block at the default lane block, the narrow tables' own program."""
    import jax
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops.compact import (PARTITION_VMEM_BUDGET, pane_rows,
                                          pallas_partition_ok,
                                          partition_grid,
                                          partition_vmem_bytes)
    R = pane_rows(features)
    lanes, rows, count = partition_grid(R)
    assert (lanes, rows, count) == grid
    one_block = partition_vmem_bytes(R) <= PARTITION_VMEM_BUDGET
    assert one_block == (lanes == BLOCK) == (features < 80)
    assert partition_vmem_bytes(
        rows, lanes, held=1 if one_block else 3) <= PARTITION_VMEM_BUDGET
    # whole row blocks of the int8 sublane tile cover the pane, the last
    # one alone ragged
    assert (count - 1) * rows < R <= count * rows
    assert one_block or rows % 32 == 0
    # the rule asks the backend and the hatch, and no width
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.enable()
    try:
        assert pallas_partition_ok() is True
        assert "partition/wide_f_fallback" not in telemetry.counters()
        assert "partition/wide_f_fallback" not in telemetry.COUNTER_FAMILIES
    finally:
        telemetry.disable()


def test_partition_row_blocks_are_counted():
    """``partition/pallas_rblocks``: the grid's row blocks, once a kernel
    traced, beside ``partition/pallas``; and ``partition/in_pane``, one
    a kernel that reads and writes the pane itself, which is every one."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.ops.compact import pane_rows
    rng = np.random.RandomState(11)
    telemetry.enable()
    try:
        assert "partition/in_pane" in telemetry.COUNTER_FAMILIES
        for features, blocks in ((4, 1), (1000, 2)):
            before = dict(telemetry.counters())
            # shapes no other test of a worker's chain traces: a counter
            # of trace time does not move when the trace is cached
            pane, mask3, plcnt, _ = _pane_case(
                rng, pane_rows(features), 6144, 2048, 5, 2000, 0)
            _partition(pane, mask3, 0, 5, 2000, plcnt, 2048,
                       use_pallas=True, interpret=True)
            after = telemetry.counters()

            def added(name):
                return after[name] - before.get(name, 0)
            assert added("partition/pallas") == 1
            assert added("partition/in_pane") == 1
            assert added("partition/pallas_rblocks") == blocks
        before = dict(telemetry.counters())
        _partition(pane, mask3, 0, 5, 2000, plcnt, 2048)
        after = telemetry.counters()
        assert after["partition/xla"] - before.get("partition/xla", 0) == 1
        assert after["partition/in_pane"] == before["partition/in_pane"]
    finally:
        telemetry.disable()


def test_partition_oracle_semantics():
    """Stable partition of the range's lanes onto the other side;
    everything else preserved byte for byte."""
    rng = np.random.RandomState(3)
    R, P, W, start, cnt = 5, 16384, 8192, 4096 + 777, 6000
    pane, mask3, plcnt, _ = _pane_case(rng, R, P, W, start, cnt, 1)
    out = _partition(pane, mask3, 1, start, cnt, plcnt, W)
    cs = int(range_origin(pane, start, W)[0])
    m = mask3[start - cs:start - cs + cnt]
    inner = pane[1][:, start:start + cnt]
    np.testing.assert_array_equal(out[0][:, start:start + plcnt],
                                  inner[:, m == 1])
    np.testing.assert_array_equal(out[0][:, start + plcnt:start + cnt],
                                  inner[:, m == 0])
    np.testing.assert_array_equal(out[0][:, :start], pane[0][:, :start])
    np.testing.assert_array_equal(out[0][:, start + cnt:],
                                  pane[0][:, start + cnt:])
    np.testing.assert_array_equal(out[1], pane[1])


def test_plane_pack_roundtrip():
    rng = np.random.RandomState(1)
    N, F = 1000, 4
    bins = rng.randint(0, 256, (F, N)).astype(np.uint8)
    grad = rng.randn(N).astype(np.float32) * 1e3
    hess = np.abs(rng.randn(N)).astype(np.float32) * 1e-3
    mask = rng.rand(N) < 0.7
    from lightgbm_tpu.ops.compact import pane_rows
    pane = pack_planes(jnp.asarray(bins), jnp.asarray(grad),
                       jnp.asarray(hess), jnp.asarray(mask), 2048)
    # two sides of whole row blocks and the root's bucket and two lane
    # blocks; the root on side 0, zeros (finite gradients) everywhere else
    assert pane.shape == (2,) + pane_layout(pane_rows(F), 2048) \
        == (2, 16, 2048 + 2 * BLOCK)
    assert pane_rows(F) % 8 == 0
    assert not np.asarray(pane[1]).any()
    assert not np.asarray(pane[0, :, N:]).any()
    assert not np.asarray(pane[0, F + 9:]).any()
    b, g, h, v = unpack_values(pane[0, :, :N], F)
    np.testing.assert_array_equal(np.asarray(b), bins)
    np.testing.assert_array_equal(np.asarray(g), grad)   # bit-exact planes
    np.testing.assert_array_equal(np.asarray(h), hess)
    np.testing.assert_array_equal(np.asarray(v), mask)


def test_bucket_table_invariants():
    for n in (1, 2048, 100_000, 1_000_000, 11_000_000):
        t = bucket_table(n)
        assert t[0] >= n and t[0] % BLOCK == 0
        for a, b in zip(t, t[1:]):
            assert b % BLOCK == 0 and b < a
            # a tier-k child (<= ceil(parent/2) rows) fits tier k+1
            assert b >= -(-a // 2) - BLOCK


def _grow_both(seed, *, compute_dtype, bagging, num_leaves=31, N=4000,
               F=5, B=32, min_data=20):
    from lightgbm_tpu.models.grower import grow_tree
    from lightgbm_tpu.models.grower_leafcompact import grow_tree_leafcompact

    rng = np.random.RandomState(seed)
    x = rng.randn(N, F)
    lo, hi = x.min(0), x.max(0)
    bins = np.clip((x - lo) / (hi - lo) * (B - 1), 0, B - 1)
    bins = bins.astype(np.uint8).T
    y = (x[:, 0] - x[:, 1] + 0.5 * np.sin(3 * x[:, 2])
         + 0.3 * rng.randn(N) > 0)
    pr = np.full(N, 0.5, np.float32)
    grad = (pr - y).astype(np.float32)
    hess = (pr * (1 - pr)).astype(np.float32)
    row_mask = np.ones(N, bool)
    if bagging:
        row_mask[rng.rand(N) < 0.4] = False
    kw = dict(num_leaves=num_leaves, num_bins_max=B,
              min_data_in_leaf=min_data, min_sum_hessian_in_leaf=1e-3,
              compute_dtype=compute_dtype)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(row_mask), jnp.asarray(np.ones(F, bool)),
            jnp.asarray(np.full(F, B, np.int32)))
    return grow_tree(*args, **kw), grow_tree_leafcompact(*args, **kw)


@pytest.mark.parametrize("bagging", [False, True])
@pytest.mark.parametrize("dtype", ["int8", "float32"])
def test_compact_grower_matches_masked_grower(dtype, bagging):
    dt = "int8" if dtype == "int8" else jnp.float32
    t1, t2 = _grow_both(11, compute_dtype=dt, bagging=bagging)
    assert int(t1.num_leaves) == int(t2.num_leaves) > 8
    for field in ("split_feature", "threshold_bin", "left_child",
                  "right_child", "leaf_count", "leaf_ids"):
        np.testing.assert_array_equal(np.asarray(getattr(t1, field)),
                                      np.asarray(getattr(t2, field)),
                                      err_msg=field)
    if dtype == "float32":
        # no trailing dequantize multiply -> nothing for XLA CPU's FMA
        # contraction to grab: bit-identical across the two programs
        np.testing.assert_array_equal(np.asarray(t1.leaf_value),
                                      np.asarray(t2.leaf_value))
    else:
        # XLA CPU contracts the MASKED grower's int8 dequantize multiply
        # into the subtraction as a single-rounding FMA (sub-ulp dust the
        # compacted program doesn't get; see grower_leafcompact.py) —
        # value-tolerant here, with the bitwise anchor provided by
        # test_compact_grower_matches_jitfree_replay
        np.testing.assert_allclose(np.asarray(t1.leaf_value),
                                   np.asarray(t2.leaf_value),
                                   rtol=1e-4, atol=1e-7)


def test_compact_grower_reads_each_child_from_its_side():
    """A leaf at an odd depth and one at an even depth are split in turn:
    the root (side 0 of the pane) writes its children to side 1, a child
    split from there writes to side 0, a grandchild back to side 1, and
    each smaller child's histogram is taken from the side it was written
    to.  A grower that read the wrong side would histogram a dead
    ancestor's rows in those lanes and still run; its counts and values
    would not be the masked grower's, which keeps no pane at all."""
    t1, t2 = _grow_both(29, compute_dtype=jnp.float32, bagging=False,
                        num_leaves=9)
    left, right = np.asarray(t2.left_child), np.asarray(t2.right_child)
    depth_of, split_depths = {0: 1}, set()
    for node in range(int(t2.num_leaves) - 1):
        split_depths.add(depth_of[node])
        for child in (left[node], right[node]):
            if child >= 0:
                depth_of[int(child)] = depth_of[node] + 1
    # leaves were split at depths of both parities, past the root's
    assert {1, 2, 3} <= split_depths, split_depths
    assert int(t1.num_leaves) == int(t2.num_leaves) == 9
    for field in ("split_feature", "threshold_bin", "left_child",
                  "right_child", "leaf_count", "leaf_ids", "leaf_value"):
        np.testing.assert_array_equal(np.asarray(getattr(t1, field)),
                                      np.asarray(getattr(t2, field)),
                                      err_msg=field)


def _manual_replay(bins, grad, hess, row_mask, num_bins, feature_mask, *,
                   num_leaves, num_bins_max, min_data, min_hess, dtype):
    """jit-free leaf-wise replay: the same library ops (build_histogram /
    find_best_split), dispatched one by one so no cross-op fusion can
    alter rounding.  The reference algorithm in ~30 lines
    (serial_tree_learner.cpp:119-153)."""
    from lightgbm_tpu.ops.histogram import build_histogram
    from lightgbm_tpu.ops.split import find_best_split

    N = bins.shape[1]
    bj, gj, hj = map(jnp.asarray, (bins, grad, hess))
    nb, fm = jnp.asarray(num_bins), jnp.asarray(feature_mask)
    leaf_ids = np.zeros(N, np.int32)
    hist, cand = {}, {}
    root = np.asarray(build_histogram(bj, gj, hj, jnp.asarray(row_mask),
                                      num_bins_max, compute_dtype=dtype))
    if dtype == "int8":
        st = root[0].sum(axis=0)
    else:
        st = np.array([(grad * row_mask).sum(), (hess * row_mask).sum(),
                       row_mask.sum()], np.float32)
    hist[0] = root
    cand[0] = find_best_split(jnp.asarray(root), *map(jnp.float32, st),
                              nb, fm, float(min_data), float(min_hess))
    values = np.zeros(num_leaves, np.float32)
    for split in range(num_leaves - 1):
        bl = max(cand, key=lambda k: float(cand[k].gain))
        best = cand[bl]
        if not float(best.gain) > 0:
            break
        new = split + 1
        feat, thr = int(best.feature), int(best.threshold)
        go_r = (bins[feat] > thr) & (leaf_ids == bl)
        leaf_ids[go_r] = new
        lcnt, rcnt = int(best.left_count), int(best.right_count)
        small = bl if lcnt <= rcnt else new
        sm = row_mask & (leaf_ids == small)
        sh = np.asarray(build_histogram(bj, gj, hj, jnp.asarray(sm),
                                        num_bins_max, compute_dtype=dtype,
                                        salt=new))
        large = hist[bl] - sh
        hist[bl], hist[new] = ((sh, large) if lcnt <= rcnt
                               else (large, sh))
        values[bl] = float(best.left_output)
        values[new] = float(best.right_output)
        for leaf, g_, h_, c_ in ((bl, best.left_sum_grad,
                                  best.left_sum_hess, lcnt),
                                 (new, best.right_sum_grad,
                                  best.right_sum_hess, rcnt)):
            cand[leaf] = find_best_split(
                jnp.asarray(hist[leaf]), jnp.float32(g_), jnp.float32(h_),
                jnp.float32(c_), nb, fm, float(min_data), float(min_hess))
    return leaf_ids, values


@pytest.mark.parametrize("dtype", ["int8", "float32"])
def test_compact_grower_matches_jitfree_replay(dtype):
    """The compacted grower reproduces a jit-free op-by-op replay of the
    reference algorithm BIT FOR BIT — the strongest equivalence anchor
    available on CPU (the masked grower deviates by FMA-contraction dust
    in the int8 mode; the replay and the compacted program do not)."""
    from lightgbm_tpu.models.grower_leafcompact import grow_tree_leafcompact

    rng = np.random.RandomState(23)
    N, F, B, L = 4000, 5, 32, 15
    x = rng.randn(N, F)
    lo, hi = x.min(0), x.max(0)
    bins = np.clip((x - lo) / (hi - lo) * (B - 1), 0, B - 1)
    bins = bins.astype(np.uint8).T
    y = (x[:, 0] - x[:, 1] + 0.3 * rng.randn(N) > 0)
    pr = np.full(N, 0.5, np.float32)
    grad = (pr - y).astype(np.float32)
    hess = (pr * (1 - pr)).astype(np.float32)
    row_mask = np.ones(N, bool)
    row_mask[rng.rand(N) < 0.3] = False
    nb = np.full(F, B, np.int32)
    fm = np.ones(F, bool)
    dt = "int8" if dtype == "int8" else jnp.float32

    tree = grow_tree_leafcompact(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(row_mask), jnp.asarray(fm), jnp.asarray(nb),
        num_leaves=L, num_bins_max=B, min_data_in_leaf=20,
        min_sum_hessian_in_leaf=1e-3, compute_dtype=dt)
    leaf_ids, values = _manual_replay(
        bins, grad, hess, row_mask, nb, fm, num_leaves=L, num_bins_max=B,
        min_data=20, min_hess=1e-3,
        dtype="int8" if dtype == "int8" else jnp.float32)
    np.testing.assert_array_equal(np.asarray(tree.leaf_ids), leaf_ids)
    nl = int(tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree.leaf_value)[:nl],
                                  values[:nl])


def test_compact_training_end_to_end():
    """Config-driven training with leafwise_compact=true reproduces the
    masked grower's boosting trajectory: identical tree structure every
    iteration, leaf values to reduction-order rounding (real-gradient
    [N]-sum reductions fuse differently across the two compiled programs
    on CPU — the bitwise anchor is test_compact_grower_matches_jitfree_
    replay)."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(5)
    N = 3000
    x = rng.randn(N, 6)
    y = ((x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(N)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=64)

    def run(compact):
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "15",
                 "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1e-3",
                 "learning_rate": "0.1", "num_iterations": "5",
                 "grow_policy": "leafwise", "hist_dtype": "float32",
                 "leafwise_compact": compact}, require_data=False)
        b = GBDT()
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config))
        for _ in range(5):
            b.train_one_iter(is_eval=False)
        return b

    b1, b2 = run("false"), run("true")
    assert len(b1.models) == len(b2.models) == 5
    for t1, t2 in zip(b1.models, b2.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b1.score),
                               np.asarray(b2.score), rtol=1e-3, atol=1e-5)


def test_compact_grower_max_depth():
    """The depth guard must block splits identically in both growers."""
    from lightgbm_tpu.models.grower import grow_tree
    from lightgbm_tpu.models.grower_leafcompact import grow_tree_leafcompact

    rng = np.random.RandomState(3)
    N, F, B = 3000, 5, 32
    x = rng.randn(N, F)
    lo, hi = x.min(0), x.max(0)
    bins = ((x - lo) / (hi - lo) * (B - 1)).astype(np.uint8).T
    y = (x[:, 0] + 0.5 * x[:, 1] > 0)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(N, 0.25, np.float32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(np.ones(N, bool)), jnp.asarray(np.ones(F, bool)),
            jnp.asarray(np.full(F, B, np.int32)))
    kw = dict(num_leaves=31, num_bins_max=B, min_data_in_leaf=10,
              min_sum_hessian_in_leaf=1e-3, max_depth=3,
              compute_dtype=jnp.float32)
    t1, t2 = grow_tree(*args, **kw), grow_tree_leafcompact(*args, **kw)
    assert int(t1.num_leaves) == int(t2.num_leaves) <= 4   # 2^(3-1)
    np.testing.assert_array_equal(np.asarray(t1.leaf_ids),
                                  np.asarray(t2.leaf_ids))
    np.testing.assert_array_equal(np.asarray(t1.leaf_value),
                                  np.asarray(t2.leaf_value))


def test_compact_training_multiclass():
    """Multiclass boosting (per-class interleaved trees) through the
    compacted grower matches the masked grower's structure/scores."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(8)
    N = 2400
    x = rng.randn(N, 5)
    y = (np.digitize(x[:, 0] + 0.3 * x[:, 1], [-0.5, 0.5])
         ).astype(np.float32)
    ds = Dataset.from_arrays(x, y, max_bin=32)

    def run(compact):
        cfg = OverallConfig()
        cfg.set({"objective": "multiclass", "num_class": "3",
                 "num_leaves": "7", "min_data_in_leaf": "20",
                 "min_sum_hessian_in_leaf": "1e-3",
                 "learning_rate": "0.1", "num_iterations": "3",
                 "grow_policy": "leafwise", "hist_dtype": "float32",
                 "leafwise_compact": compact}, require_data=False)
        b = GBDT()
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config))
        for _ in range(3):
            b.train_one_iter(is_eval=False)
        return b

    b1, b2 = run("false"), run("true")
    assert len(b1.models) == len(b2.models) == 9      # 3 classes x 3 iters
    for t1, t2 in zip(b1.models, b2.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-4, atol=1e-6)


def test_compact_grower_data_parallel_matches_serial():
    """The compacted grower under the data-parallel psum schedule: each
    shard keeps its LOCAL rows physically partitioned, per-split
    histograms are psum'd with a pmax-synced slice tier.  int8 trees
    must be bit-identical to the serial compacted run (int-domain
    reduction is order-free); rows not divisible by 8 exercises the
    shard padding path."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel import create_parallel_learner

    rng = np.random.RandomState(19)
    n = 2999                                # 2999 % 8 != 0
    x = rng.randn(n, 6)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(n)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)
    params = {"objective": "binary", "num_leaves": "15",
              "min_data_in_leaf": "20", "min_sum_hessian_in_leaf": "1e-3",
              "learning_rate": "0.1", "num_iterations": "4",
              "grow_policy": "leafwise", "hist_dtype": "int8",
              "leafwise_compact": "true", "dp_schedule": "psum"}

    def run(tree_learner, machines):
        cfg = OverallConfig()
        p = dict(params, tree_learner=tree_learner,
                 num_machines=str(machines))
        cfg.set(p, require_data=False)
        b = GBDT()
        learner = (create_parallel_learner(cfg)
                   if tree_learner != "serial" else None)
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config),
               learner=learner)
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        return b

    b_s, b_dp = run("serial", 1), run("data", 8)
    assert len(b_s.models) == len(b_dp.models) == 4
    for k, (t1, t2) in enumerate(zip(b_s.models, b_dp.models)):
        assert t1.num_leaves == t2.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin,
                                      err_msg=f"tree {k}")
        # int accumulators identical; per-program f32 dequantize/search
        # fusion may differ by a couple ulps (cross-program FMA story)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-6, atol=1e-9,
                                   err_msg=f"tree {k}")


def test_compact_chunk_path_matches_per_iteration():
    """Direct train_chunk calls (the CPU-test chunk seam) must ride the
    SAME compacted grower as the per-iteration path for the same
    config."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(21)
    n = 2000
    x = rng.randn(n, 5)
    y = ((x[:, 0] + 0.4 * x[:, 1] + 0.3 * rng.randn(n)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)

    def run(chunked):
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "15",
                 "min_data_in_leaf": "20",
                 "min_sum_hessian_in_leaf": "1e-3",
                 "learning_rate": "0.1", "num_iterations": "4",
                 "grow_policy": "leafwise", "hist_dtype": "int8",
                 "leafwise_compact": "true"}, require_data=False)
        b = GBDT()
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config))
        if chunked:
            b.train_chunk(4)
        else:
            for _ in range(4):
                b.train_one_iter(is_eval=False)
        return b

    b_it, b_ch = run(False), run(True)
    assert len(b_it.models) == len(b_ch.models) == 4
    for t1, t2 in zip(b_it.models, b_ch.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-5, atol=5e-7)   # two programs: fusion dust


def test_compact_training_bagging_feature_fraction():
    """Bagging + feature_fraction through the compacted grower: the RNG
    streams and masks are shared machinery, so trajectories must match
    the masked grower exactly in structure."""
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(13)
    n = 2500
    x = rng.randn(n, 8)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(n)) > 0)
    ds = Dataset.from_arrays(x, y.astype(np.float32), max_bin=32)

    def run(compact):
        cfg = OverallConfig()
        cfg.set({"objective": "binary", "num_leaves": "15",
                 "min_data_in_leaf": "20",
                 "min_sum_hessian_in_leaf": "1e-3",
                 "learning_rate": "0.1", "num_iterations": "4",
                 "bagging_fraction": "0.8", "bagging_freq": "2",
                 "bagging_seed": "7", "feature_fraction": "0.6",
                 "feature_fraction_seed": "3",
                 "grow_policy": "leafwise", "hist_dtype": "int8",
                 "leafwise_compact": compact}, require_data=False)
        b = GBDT()
        b.init(cfg.boosting_config, ds,
               create_objective(cfg.objective_type, cfg.objective_config))
        for _ in range(4):
            b.train_one_iter(is_eval=False)
        return b

    b1, b2 = run("false"), run("true")
    assert len(b1.models) == len(b2.models) == 4
    for t1, t2 in zip(b1.models, b2.models):
        assert t1.num_leaves == t2.num_leaves
        np.testing.assert_array_equal(t1.split_feature, t2.split_feature)
        np.testing.assert_array_equal(t1.threshold_bin, t2.threshold_bin)
        np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                   rtol=1e-4, atol=1e-6)
