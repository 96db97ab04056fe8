"""The benchmark's fourth cell, ``airline-levelwise-int8.train``, on the CPU.

115,000,000 x 13 rows are seven int32 accumulation ranges a histogram
pass (``ops/hist_pallas.accum_ranges``).  Here the benchmark's own
harness runs the cell cut to 28,672 rows with the cap patched to two
chunks of 2,048: seven ranges a pass all the same, the Pallas kernel
through the interpreter on its ranged grid, the fused chunk program as
``task=train`` builds it, judged by the harness's plain float64 reference
(``benchmarks/harness/reference.py``, teacher forced) under the cell's own
limits.  ``tests/test_hist_int8_ranges.py`` holds the accumulators against
int64 sums and ``tests/test_hist_int8_ranged_trees.py`` the trees against
the unranged program's.
"""
import pytest

import jax

from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops import hist_pallas

CELL = "airline-levelwise-int8.train"


@pytest.fixture(scope="module")
def airline_run():
    """One run of the benchmark's own harness on the airline cell cut to
    28,672 rows (``bench_cut.run_cut_cell``), the cap at 4,096 rows: seven
    ranges of two chunks of 2,048, as the cell's 115,000,000 rows are
    seven ranges of 8,022."""
    from bench_cut import run_cut_cell
    mp = pytest.MonkeyPatch()
    mp.setattr(hist_pallas, "INT8_HIST_MAX_ROWS", 4096)
    gbdt_mod._CHUNK_PROGRAMS.clear()
    jax.clear_caches()
    try:
        return run_cut_cell(CELL, 3600000011, 28672, 13)
    finally:
        mp.undo()
        gbdt_mod._CHUNK_PROGRAMS.clear()
        jax.clear_caches()


def test_airline_program_took_seven_ranges_a_pass(airline_run):
    _line, counters = airline_run
    passes = sum(v for k, v in counters.items()
                 if k.startswith("hist/pallas_fold_"))
    assert passes and passes % 8 == 0
    assert counters["hist/accum_ranges"] == 7 * passes
    # one block of 13 columns a pass, ranged or not
    assert counters["hist/pallas_fblocks"] == passes
    assert "hist/xla_int_kernel" not in counters
    assert counters["partition/route_pallas"] == passes


def test_airline_program_is_correct_by_the_cells_limits(airline_run):
    line, _counters = airline_run
    assert line["failed"] == 0 and line["attempted"] == 8
    checks = line["checks"]
    for name in ("score_gap", "bin_code_gap", "split_gain_gap",
                 "leaf_value_gap", "leaf_sum_gap", "trees_short"):
        assert checks[name]["limit"] is not None
        assert checks[name]["value"] <= checks[name]["limit"], (
            name, checks[name])
    assert line["correct"] is True


def test_airline_control_in_int4_is_not_correct(airline_run):
    line, _counters = airline_run
    assert line["control_correct"] is False
    assert any(c["limit"] is not None and c["value"] > c["limit"]
               for name, c in line["checks"].items()
               if name.startswith("control."))
    assert line["faults_correct"] == {"half_batch": False,
                                      "state_unchanged": False}


def test_the_cell_as_declared_is_seven_ranges_of_whole_chunks():
    """The files say what the docstring says: 115,000,000 x 13, nothing
    reduced, the level-wise int8 route; at that size a pass of the kernel
    (chunks of 2,048 rows) is seven ranges of 8,022 chunks, 3,392 rows of
    padding in all; and both metrics this cell brought list it."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry, = [c for c in bench["configs"]
              if c["name"] == "airline-levelwise-int8"]
    with open(os.path.join(root, entry["file"])) as fh:
        conf = json.load(fh)
    assert (conf["rows"], conf["features"]) == (115_000_000, 13)
    assert conf["reduced"] == entry["reduced"] == []
    assert conf["source"] == entry["source"] and len(entry["source"]) <= 200
    assert conf["params"]["hist_dtype"] == "int8"
    assert conf["params"]["grow_policy"] == "depthwise"
    assert hist_pallas._ranged_rows(conf["rows"], 2048) == (
        7, 7 * 8022 * 2048, True)
    assert 8022 * 2048 <= hist_pallas.INT8_HIST_MAX_ROWS
    assert hist_pallas.feature_grid(13, 255, 128, 2048, 0, 7) == (13, 1)
    assert hist_pallas.feature_grid(13, 255, 192, 2048, 192, 7) == (13, 1)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"hist_accum_ranges", "hist_range_sum_ms_per_iter",
            "hist_roofline", "hist_kernel_ms_per_iter", "train_step_mfu",
            "iters_per_chunk"} <= listed
    assert not {"partition_ms_per_iter", "partition_roofline",
                "partition_row_blocks"} & listed
