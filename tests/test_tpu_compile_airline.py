"""Compile, for a described v5e, the programs of the benchmark's long
table: ``airline-levelwise-int8``, 115,000,000 x 13, whose int8 histogram
passes sum in seven accumulation ranges (``ops/hist_pallas.accum_ranges``)
on the kernel's three-axis grid (``tests/test_tpu_compile.py`` holds the
rules these files keep).
"""
import re

import pytest

import jax
import jax.numpy as jnp

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, _captured_chunk_program, _check, _like, no_persistent_cache,
    one_chip, _pass_rules, _shape, _tiny_binary_dataset, topo)

AIRLINE_F, AIRLINE_N = 13, 7 * 8022 * 2048   # 115,000,000 rows as padded


@pytest.mark.parametrize("features,lanes,num_cols,grid", [
    # the airline cell's passes: one block of 13 columns, the window
    # rotating with the seven ranges; folded, unfolded with the one-hot
    # held, and the 64-leaf pass of 192 value rows
    (AIRLINE_F, 128, 1, (13, 1)), (AIRLINE_F, 128, 16, (13, 1)),
    (AIRLINE_F, 128, 32, (13, 1)), (AIRLINE_F, 192, 64, (13, 1)),
    # 49 - 96 columns are one block under one range (a constant window
    # has one buffer) and cannot be under several: two buffers of a
    # [96, 256, 128] accumulator are 25 MB.  The rotating account's block
    (96, 128, 1, (48, 2)), (64, 192, 64, (32, 2)),
])
def test_ranged_hist_kernel_compiles(one_chip, as_tpu, features, lanes,
                                     num_cols, grid):
    """The int8 kernel on its three-axis grid (feature blocks, ranges,
    chunks of a range) at the airline table's rows: seven accumulators
    of [F, B, lanes] int32, each zeroed at its range's first chunk."""
    from lightgbm_tpu.ops.hist_pallas import (_hist_pallas_raw_fn,
                                              _ranged_rows, feature_grid)
    assert _ranged_rows(115_000_000, 2048) == (7, AIRLINE_N, True)
    fold, gw, held = _pass_rules("int8", lanes, 3, num_cols)
    assert feature_grid(features, 256, lanes, 2048, held, 7) == grid

    def fresh(bins, packed):
        return _hist_pallas_raw_fn(bins, packed, B=256, chunk=2048,
                                   dtype="int8", lanes=lanes, fold=fold,
                                   gw=gw, held=held, ranges=7)
    compiled = jax.jit(fresh).lower(
        _shape(one_chip, (features, AIRLINE_N), jnp.int8),
        _shape(one_chip, (4, AIRLINE_N), jnp.int8)).compile()
    _check(compiled, custom_call=True)
    assert "s32[7,%d,256,%d]" % (features, lanes) in compiled.as_text()


def test_fused_chunk_program_compiles_at_the_airline_cell(
        one_chip, as_tpu, monkeypatch):
    """The program of the cell ``airline-levelwise-int8.train``: the
    configuration's own ``key=value`` pairs, 13 columns, its argument tree
    re-shaped to the 115,000,000 rows.  Seven accumulation ranges a pass
    (every histogram custom call hands on ``s32[7, 13, ...]``), and the
    size argument of the cell: the compiler counted 9.22 GB of
    temporaries and 3.22 GB of arguments, 80 and 28 bytes a row (PR 36),
    and a per-row float32 temporary too many would be another 0.46 GB of
    the 3.6 GB that are left of the chip."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "airline-levelwise-int8.json")) as fh:
        conf = json.load(fh)
    rows, columns = conf["rows"], conf["features"]
    assert (rows, columns) == (115_000_000, 13)
    n_tiny = 1000
    prog, seen = _captured_chunk_program(
        monkeypatch, conf["params"], _tiny_binary_dataset(n_tiny, columns),
        is_eval=False)
    args = _like(one_chip, seen, rows_from=n_tiny, rows_to=rows)
    compiled = prog.lower(*args).compile()
    ma = _check(compiled, custom_call=True)
    text = compiled.as_text()
    calls = re.findall(r"= (\S+) custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"[^\n]*'
                       r"_hist_pallas_raw_fn", text)
    assert len(calls) == 8 and all(c.startswith("s32[7,13,") for c in calls)
    assert "/histogram/range_sum/" in text
    per_row = (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / rows
    assert 95 < per_row < 116, (ma.temp_size_in_bytes,
                                ma.argument_size_in_bytes)
    # over the 4.00 GiB line on its bytes alone, and 5% of the chip free
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes > 4 << 30
    assert (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < 0.95 * 16_909_336_064
