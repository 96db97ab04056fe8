"""The benchmark's four-chip cell, ``airline-dp4-levelwise-int8.train_sharded``,
on the CPU.

115,000,000 x 13 rows over four chips are 28,750,000 a chip, two int32
accumulation ranges a histogram pass on each (``ops/hist_pallas.
accum_ranges``).  Here the benchmark's own harness runs the cell cut to
16,384 rows over four of the suite's host devices with the cap patched to
one chunk of 2,048: two ranges a pass on every shard, the data-parallel
chunk program that ``GBDT.init`` builds from the configuration's ``tree_learner=data num_machines=4
dp_schedule=reduce_scatter``, judged by the harness's plain float64
reference under the cell's own limits; and the Airline cell cut to the
same rows and seed on one device, whose splits the four shards make bit
for bit (and whose leaf values too under ``dp_schedule=psum``).  The routing stays on its CPU branch (the XLA histogram, the chunk
of the XLA scan cut to 2,048 rows as well): the chip's kernels under
``shard_map`` are compiled for four described chips in
``tests/test_tpu_compile_airline.py``.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax

from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.parallel import learners

CELL = "airline-dp4-levelwise-int8.train_sharded"
SERIAL = "airline-levelwise-int8.train"
ROWS, SEED, CAP = 16384, 4300000017, 2048
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE_FIELDS = ("split_feature", "threshold_bin", "threshold", "left_child",
               "right_child", "leaf_parent")


def _run(cell, **params):
    """(result line, route counters, the window's trees) of one harness
    run of ``cell`` cut to ROWS x 13, the cap at CAP rows, ``params``
    laid over the configuration's."""
    from bench_cut import run_cut_cell
    seen = {}

    def keep_trees(point, job=None, **_kw):
        if point == "answers":
            seen["trees"] = list(job.booster.models)
    mp = pytest.MonkeyPatch()
    mp.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", CAP)
    gbdt_mod._CHUNK_PROGRAMS.clear()
    learners._DP_CHUNK_PROGRAMS.clear()
    jax.clear_caches()
    try:
        line, counters = run_cut_cell(
            cell, SEED, ROWS, 13, fault=keep_trees, tpu_routes=False,
            params=dict(params, hist_chunk=str(CAP)))
    finally:
        mp.undo()
        gbdt_mod._CHUNK_PROGRAMS.clear()
        learners._DP_CHUNK_PROGRAMS.clear()
        jax.clear_caches()
    return line, counters, seen["trees"]


@pytest.fixture(scope="module")
def dp_run():
    return _run(CELL)


@pytest.fixture(scope="module")
def serial_run():
    return _run(SERIAL)


def test_each_shard_sums_its_passes_in_two_ranges(dp_run, serial_run):
    """4,096 rows a shard over a cap of one chunk of 2,048 are two ranges
    a pass; the one device's 16,384 rows are eight."""
    _line, counters, _trees = dp_run
    _line, serial_counters, _trees = serial_run
    passes = counters["hist/xla_int_kernel"]
    assert passes == serial_counters["hist/xla_int_kernel"] == 8
    # hist/accum_ranges is filed once a ranged reduction, nine a tree
    # here: the eight passes and the root's totals
    assert serial_counters["hist/accum_ranges"] == 8 * 9
    assert counters["hist/accum_ranges"] == 2 * 9


def test_the_collective_counters_are_the_payload_reckoned_by_hand(dp_run):
    """One tree of 255 leaves in 8 levels over 4 shards, 13 columns of up
    to 254 bins: the tree's int8 scale (a pmax of two float32), the root's
    integer pair psum'd over all 13 columns, the pair of every level after
    it reduce-scattered over the columns padded to 16 (blocks of 4), three
    value columns a smaller child, and the packed split of every slot of
    every level gathered from the four shards."""
    from lightgbm_tpu.ops.split import SplitResult
    _line, counters, _trees = dp_run
    B, stats, pair = 254, 3, 2
    root = pair * 13 * B * stats * 4
    smaller_children = sum(1 << (level - 1) for level in range(1, 8))
    levels = pair * 16 * B * stats * smaller_children * 4
    split_info = len(SplitResult._fields) * 4 * sum(
        1 << d for d in range(8))
    assert counters["collective/pmax_bytes_per_tree"] == 8
    assert counters["collective/psum_bytes_per_tree"] == root
    assert counters["collective/psum_scatter_bytes_per_tree"] == levels
    assert counters["collective/all_gather_bytes_per_tree"] == split_info
    assert counters["collective/bytes_per_tree"] == (
        8 + root + levels + split_info)
    # the pmax, the root, 2 x 8 level passes (the last level in two
    # halves of 64 children), 8 gathers of the split
    assert counters["collective/calls_per_tree"] == 1 + 1 + 16 + 8
    assert counters["collective/trees_per_iter"] == 1
    assert counters["collective/shards"] == 4
    assert counters["collective/schedule_reduce_scatter"] == 1
    assert "collective/schedule_psum" not in counters


def test_the_collective_roofline_reads_the_ring_bytes_over_the_time():
    """``collective_roofline`` on a synthetic reader state: per chip a ring
    of 4 sends 3/2 of an all-reduce's payload, 3/4 of a reduce-scatter's
    and 3 times an all-gather's part; at 200 GB/s a chip, over 2 ms."""
    import importlib.util
    path = os.path.join(ROOT, "benchmarks", "metrics",
                        "collective_roofline.py")
    spec = importlib.util.spec_from_file_location("collective_roofline",
                                                  path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)

    class State:
        values = {"collective_ms_per_iter": 2.0}
        counters = {"collective/psum_bytes_per_tree": 1_000_000,
                    "collective/pmax_bytes_per_tree": 8,
                    "collective/psum_scatter_bytes_per_tree": 8_000_000,
                    "collective/all_gather_bytes_per_tree": 10_000,
                    "collective/bytes_per_tree": 9_010_008,
                    "collective/trees_per_iter": 1,
                    "collective/shards": 4}
    moved = 1.5 * 1_000_008 + 0.75 * 8_000_000 + 3 * 10_000
    want = 100.0 * (moved / 200e9) / 2e-3
    assert metric.read(State()) == pytest.approx(want, rel=1e-12)
    assert 1.8 < want < 1.9
    State.values = {}
    assert metric.read(State()) is None


def test_the_cell_is_correct_by_its_limits_and_its_control_is_not(dp_run):
    line, _counters, _trees = dp_run
    assert line["failed"] == 0 and line["attempted"] == 8
    for name in ("score_gap", "bin_code_gap", "split_gain_gap",
                 "leaf_value_gap", "leaf_sum_gap", "trees_short"):
        check = line["checks"][name]
        assert check["limit"] is not None
        assert check["value"] <= check["limit"], (name, check)
    assert line["correct"] is True
    assert line["control_correct"] is False
    assert line["faults_correct"] == {"half_batch": False,
                                      "state_unchanged": False}


@pytest.fixture(scope="module")
def psum_run():
    return _run(CELL, dp_schedule="psum")


def test_the_psum_schedule_grows_the_serial_learners_trees_bit_for_bit(
        psum_run, serial_run):
    """The shards' integer pairs added exactly: under the all-reduce of
    whole histograms every split, gain and leaf value is the serial
    learner's to the last bit."""
    _l, counters, psum_trees = psum_run
    _l, _c, serial_trees = serial_run
    assert counters["collective/schedule_psum"] == 1
    assert len(psum_trees) == len(serial_trees) == 8
    for a, b in zip(psum_trees, serial_trees):
        assert a.num_leaves == b.num_leaves > 1
        for field in TREE_FIELDS + ("split_gain", "leaf_value"):
            np.testing.assert_array_equal(getattr(a, field),
                                          getattr(b, field), err_msg=field)


def test_the_cells_schedule_makes_the_serial_learners_splits(dp_run,
                                                            serial_run):
    """Under the reference's schedule, on this table and seed, every split
    is the serial learner's, bit for bit.  On the CPU the gains and leaf
    values agree with the serial learner's to float32's last places of the
    sums, not to the bit (a few leaves a tree after the first): the scatter
    program rounds the sibling it derives by subtraction otherwise, so at
    a near-tie a split could differ too.  On the chip both are the serial
    learner's to the bit."""
    _l, _c, serial_trees = serial_run
    _l, _c, dp_trees = dp_run
    assert len(dp_trees) == len(serial_trees) == 8
    for a, b in zip(dp_trees, serial_trees):
        assert a.num_leaves == b.num_leaves > 1
        for field in TREE_FIELDS:
            np.testing.assert_array_equal(getattr(a, field),
                                          getattr(b, field), err_msg=field)
        np.testing.assert_allclose(a.split_gain, b.split_gain, rtol=1e-3)
        # a leaf's value is -G/H * 0.1 with G the difference of a
        # float32 total and a prefix sum over its bins; an ulp of sums
        # of 10^3 - 10^4 is a few 1e-6 of a value
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=0,
                                   atol=1e-5)
    np.testing.assert_array_equal(dp_trees[0].leaf_value,
                                  serial_trees[0].leaf_value)


def test_train_sharded_reports_one_chips_rows(monkeypatch):
    """``shape.rows`` is what one chip holds: the readers set one chip's
    floor against a device time averaged over the chips."""
    for p in (os.path.join(ROOT, "benchmarks"), ROOT):
        monkeypatch.syspath_prepend(p)
    from traffic import train, train_sharded
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    monkeypatch.setattr(train, "run", lambda ctx: {
        "shape": {"rows": 115_000_000, "features": 13, "num_leaves": 255}})

    class Ctx:
        args = type("Args", (), {"workload": CELL})

    Ctx.bench = bench
    shape = train_sharded.run(Ctx())["shape"]
    assert shape == {"rows": 28_750_000, "features": 13, "num_leaves": 255,
                     "chips": 4}
    sys.modules.pop("traffic.train_sharded", None)
