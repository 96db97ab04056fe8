"""Compile, for a described v5e, the programs of the benchmark's long
table: ``airline-levelwise-int8``, 115,000,000 x 13, whose int8 histogram
passes sum in seven accumulation ranges (``ops/hist_pallas.accum_ranges``)
on the kernel's three-axis grid (``tests/test_tpu_compile.py`` holds the
rules these files keep).
"""
import re

import numpy as np

import pytest

import jax
import jax.numpy as jnp

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, _captured_chunk_program, _cell_config, _check, _like,
    no_persistent_cache, one_chip, _operand_producers, _pass_rules,
    _root_hist_layouts, _row_relayouts, _shape, _tiny_binary_dataset,
    topo)

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
    (every histogram custom call hands on ``s32[7, 13, ...]``), each
    pass's ``[3, N]`` int8 operand (grad and hess codes and the row's
    slot: ``min_data_in_leaf=1`` beside a hessian floor builds no count
    column) made by one fusion with no row relayout around it, the root's
    float histogram laid out as the three-statistic program lays out its
    own (``{2,1,3,0}``: another layout rounded the root's gains otherwise,
    PERF.md section 6, PR 44), and the size argument of the cell: the
    compiler counts 7.84 GB of temporaries and 3.22 GB of arguments, 68
    and 28 bytes a row, and a peak of 10.70 GB, 93 bytes a row (7.38 GB,
    64 bytes, and a peak of 11.16 GB with the count column and the
    level's column ids and row selection; 9.22 GB, 80 bytes, with the 28
    relayouts a tree before the operand was one fusion), and a per-row
    float32 temporary too many would be another 0.46 GB."""
    conf = _cell_config("airline-levelwise-int8")
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
    assert _row_relayouts(text, rows) == []
    producers = _operand_producers(text, rows, stats=2)
    assert len(producers) == 8 and all(
        "/histogram/" in line for _name, line in producers), producers
    assert _root_hist_layouts(text, columns) == {"{2,1,3,0:T(8,128)}"}
    per_row = (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / rows
    assert 92 < per_row < 101, (ma.temp_size_in_bytes,
                                ma.argument_size_in_bytes)
    assert ma.peak_memory_in_bytes / rows < 95, ma.peak_memory_in_bytes
    # over the 4.00 GiB line on its bytes alone, and 5% of the chip free
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes > 4 << 30
    assert (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes) < 0.95 * 16_909_336_064


def test_data_parallel_chunk_program_compiles_at_a_quarter_of_airline(
        topo, as_tpu, monkeypatch):
    """The program of the cell ``airline-dp4-levelwise-int8.train_sharded``
    for the four chips of a described v5e:2x2: the configuration's own
    ``key=value`` pairs, the learner ``GBDT.init`` builds from them, the
    argument tree re-shaped to 115,000,000 rows, 28,750,000 a chip.  Each
    chip's int8 passes sum in two accumulation ranges (every histogram
    custom call hands on ``s32[2, 13, ...]``); the program asks for the
    chips' integer pairs reduce-scattered by feature block, and the
    compiler for the chip makes each an all-reduce of the pair over the
    columns padded to 16, of which a chip then reads its block; and the
    size argument of the cell: a chip's temporaries and arguments (1.92
    and 0.83 GB) pass the 2.15 GB of the 12.5% line and leave 5% of the
    chip free."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import lightgbm_tpu as lgb
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel.learners import DataParallelLearner
    from tpu_described import _Captured
    conf = _cell_config("airline-dp4-levelwise-int8")
    rows, columns = conf["rows"], conf["features"]
    chips = int(conf["params"]["num_machines"])
    assert (rows, columns, chips) == (115_000_000, 13, 4)
    config = lgb.OverallConfig()
    config.set(conf["params"], require_data=False)
    n_tiny = 1000
    booster = lgb.GBDT()
    booster.init(config.boosting_config, _tiny_binary_dataset(n_tiny, columns),
                 create_objective(config.objective_type,
                                  config.objective_config))
    learner = booster._learner
    assert isinstance(learner, DataParallelLearner)
    assert learner._schedule() == "reduce_scatter"
    tpu_mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    seen = {}
    real_chunk_program = learner.chunk_program

    def capturing_chunk_program(*a, **kw):
        with monkeypatch.context() as m:
            m.setattr(learner, "_mesh", lambda: tpu_mesh)
            prog, num_shards = real_chunk_program(*a, **kw)

        def call(*args):
            seen["prog"], seen["args"] = prog, args
            raise _Captured
        return call, num_shards

    monkeypatch.setattr(learner, "chunk_program", capturing_chunk_program)
    with pytest.raises(_Captured):
        booster.train_chunk(8, is_eval=False)

    def conv(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        shape = tuple(rows if d == n_tiny else d for d in a.shape)
        spec = P(*("data" if d == n_tiny else None for d in a.shape))
        return jax.ShapeDtypeStruct(shape, a.dtype,
                                    sharding=NamedSharding(tpu_mesh, spec))

    lowered = seen["prog"].lower(*jax.tree.map(conv, seen["args"]))
    # the program asks for the reference's schedule: the seven level
    # exchanges of the integer pair reduce-scattered by feature block
    assert len(re.findall(r"stablehlo\.reduce_scatter", lowered.as_text())
               ) == 7 * 2
    compiled = lowered.compile()
    ma = _check(compiled, custom_call=True)
    text = compiled.as_text()
    calls = re.findall(r"= (\S+) custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"[^\n]*'
                       r"_hist_pallas_raw_fn", text)
    assert len(calls) == 8 and all(c.startswith("s32[2,13,") for c in calls)
    # ... which the compiler for the chip turns into an all-reduce of the
    # pair over the 13 columns padded to 16, each chip then reading its
    # block of four: one a level, the root's psum over 13 beside them
    pairs = re.findall(r"= \((s32\[\d+),\d+,\d+\][^ ]*, s32\[[\d,]+\][^ ]*\) "
                       r"all-reduce\(", text)
    assert sorted(pairs) == ["s32[13"] + ["s32[16"] * 7, pairs
    per_chip = ma.temp_size_in_bytes + ma.argument_size_in_bytes
    assert per_chip > 2.15e9, (ma.temp_size_in_bytes,
                               ma.argument_size_in_bytes)
    assert per_chip + ma.output_size_in_bytes < 0.95 * 16_909_336_064
