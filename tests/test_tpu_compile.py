"""Compile the TPU routes for a described v5e, without a chip.

The suite runs on the CPU, where every backend-keyed rule takes its CPU
branch — so the code a TPU user runs (Pallas histogram and partition
kernels, the compacted grower, the fused chunk program, the serving
program with donation, the ingest update program) would otherwise have
no test.  The TPU compiler is installed here and compiles for a topology
that is described, not attached: what it refuses (unaligned slices, VMEM
overflow, HBM overflow) costs no chip time.  A compile that passes is
not a chip run — ``chip_smoke.py`` is.

Shapes are the supported configurations at a real size: F=28, 255 bins,
255 leaves, N=2**20; and the wide table of the benchmark's second
configuration, F=2,000 at N=400,000, whose histogram passes run the
kernel's feature-block grid.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips if it cannot be;
nothing touches it at import, in ``skipif`` or in ``parametrize``
arguments; every compile runs in the test's own process with the
persistent cache off; backend-keyed routing is steered by monkeypatch,
not by a new option.

Where such tests may live: in this file (the kernels),
``tests/test_tpu_compile_programs.py`` (the narrow table's growers and
the programs the system builds), ``tests/test_tpu_compile_wide.py``
(the wide table's) and ``tests/test_tpu_compile_airline.py`` (the long
table's: the ranged kernel and its chunk program), which share
``tests/tpu_described.py`` and nothing else.  Each file is one xdist
worker's chain, so a new compile goes to the file of its table, and a
file that passes four minutes of a cold run is split again (ROADMAP
"Tests").  Several workers can describe the
topology at once only because the driver's command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (tried on this tree: three processes
described ``v5e:2x2`` and compiled side by side; without the variable the
second one is refused for the library's lock file).  The repository does
not set it.  Where it is missing the ``topo`` fixture fails the file by
name and does not skip it; one process (``-p no:xdist``) runs all four
files with no variable at all.
"""
import pytest

import jax
import jax.numpy as jnp

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, B, _check, F, _lower_kernel, _lower_partition, _mosaic_kernels,
    N, no_persistent_cache, one_chip, _pass_rules, _shape, topo, WIDE_F)


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("dtype,lanes,stats,num_cols", [
    ("int8", 128, 3, 32), ("int8", 192, 3, 64),
    # float gradients: one column folds by 8 like the integer modes, three
    # statistics and the float32 pair's five (the leaf-wise cell's pass,
    # a [32, 40] accumulator a feature); 38 columns of five at 192 lanes
    # keep the one-hot streamed and unfolded
    ("bf16v", 128, 3, 1), ("bf16v", 128, 5, 1), ("bf16v", 192, 5, 38),
    # the bin fold's shapes at the cell's levels (hist_fold: fold 8, 8,
    # 4, 4, 2 for 1, 2, 4, 8, 16 leaf columns), value blocks of 24 to 96
    # rows against one-hots of 32 to 128
    ("int8", 128, 3, 1), ("int8", 128, 3, 2), ("int8", 128, 3, 4),
    ("int8", 128, 3, 8), ("int8", 128, 3, 16), ("bf16", 128, 3, 1),
    # the 64-leaf pass in the bf16 level mode: turned round like "int8"
    ("bf16", 192, 3, 64),
])
def test_hist_kernel_compiles(one_chip, as_tpu, dtype, lanes, stats,
                              num_cols):
    from lightgbm_tpu.ops.hist_pallas import _hist_pallas_raw_fn
    packed_dtype = jnp.bfloat16 if dtype == "bf16v" else jnp.int8
    fold, gw, held = _pass_rules(dtype, lanes, stats, num_cols)
    assert (fold > 1) == (lanes == 128 and num_cols <= 16)
    # the integer modes' unfolded passes hold the one-hot and stream the
    # live value rows, 96 of 128 lanes and all 192; nothing else does
    assert held == (0 if dtype == "bf16v" or fold > 1 else 3 * num_cols)
    fn = jax.jit(_hist_pallas_raw_fn,
                 static_argnames=("B", "chunk", "dtype", "lanes", "stats",
                                  "fold", "gw", "held"))
    compiled = fn.lower(
        _shape(one_chip, (F, N), jnp.int8),
        _shape(one_chip, (stats + 1, N), packed_dtype),
        B=256, chunk=2048, dtype=dtype, lanes=lanes, stats=stats,
        fold=fold, gw=gw, held=held).compile()
    _check(compiled, custom_call=True)


@pytest.mark.parametrize("dtype,lanes,stats,num_cols,grid", [
    # the 64-leaf level, 192 lanes, the one-hot held: the accumulator is
    # [192, 256] cells a feature, 32 features a block
    ("int8", 192, 3, 64, (32, 63)), ("bf16", 192, 3, 64, (32, 63)),
    # 128 lanes: unfolded, the one-hot held and 96 live rows streamed
    # ([96, 256] cells a feature: 72 would fit, 48 are taken), and folded
    ("int8", 128, 3, 32, (48, 42)), ("int8", 128, 3, 1, (48, 42)),
    # float gradients, five statistics a column, the one-hot streamed:
    # [256, 192 -> 256] cells a feature, 24 a block (the compiler refused
    # 32 of those: 16.12 MiB of windows)
    ("bf16v", 192, 5, 38, (24, 84)),
    # the leaf-wise cell's pass, one column of five statistics folded by
    # 8: the unfolded pass's block (the fold's narrower accumulator is
    # not counted), 42 blocks of 48
    ("bf16v", 128, 5, 1, (48, 42)),
])
def test_hist_kernel_compiles_on_the_feature_block_grid(
        one_chip, as_tpu, dtype, lanes, stats, num_cols, grid):
    from lightgbm_tpu.ops.hist_pallas import feature_grid
    assert feature_grid(WIDE_F, 256, lanes, 2048,
                        _pass_rules(dtype, lanes, stats, num_cols)[2]) == grid
    compiled = _lower_kernel(one_chip, WIDE_F, dtype, lanes, stats,
                             num_cols).compile()
    _check(compiled, custom_call=True)


def test_narrow_kernels_lower_as_before_the_feature_block_repair(
        one_chip, as_tpu, monkeypatch):
    """What the rules of the grid and of the orientation leave alone.
    The VMEM account of the rotating block is not on the path of a table
    that fits one block: with the account put back to the rule it
    replaced (B * lanes * 4 bytes a feature in 6 MiB), every F=28 kernel
    of this file lowers to the same text.  And the held one-hot
    (``held_onehot``) is the integer modes' unfolded passes and no other:
    with the rule switched off every folded kernel and every "bf16v"
    kernel, folded or not, lowers to the same text, at F=28 and on the
    wide table, and the 32- and 64-column int8 passes do not."""
    from lightgbm_tpu.ops import hist_pallas
    shapes = [("int8", 128, 3, 32), ("int8", 192, 3, 64),
              ("bf16v", 128, 3, 1), ("bf16v", 192, 5, 38),
              ("int8", 128, 3, 1), ("int8", 128, 3, 2), ("int8", 128, 3, 4),
              ("int8", 128, 3, 8), ("int8", 128, 3, 16), ("bf16", 128, 3, 1),
              ("bf16v", 128, 5, 1)]
    turned = (0, 1)

    def before(b, lanes, _chunk, _held=0):
        fb = (6 << 20) // (b * lanes * 4)
        return max(8, fb - fb % 8)

    def texts(features):
        return [_lower_kernel(one_chip, features, *s).as_text()
                for s in shapes[:4 if features > F else None]]
    # all rounds lower from the same lines: the kernel is serialized with
    # the locations of its call stack, this test's frames among them
    rounds = []
    for account, rule in (
            (hist_pallas.rotating_feature_block, hist_pallas.held_onehot),
            (before, hist_pallas.held_onehot),
            (hist_pallas.rotating_feature_block, lambda *a: 0)):
        monkeypatch.setattr(hist_pallas, "rotating_feature_block", account)
        monkeypatch.setattr(hist_pallas, "held_onehot", rule)
        rounds.append((texts(F), texts(WIDE_F)))
    (narrow, wide), (narrow_was, wide_was), (narrow_off, wide_off) = rounds
    assert narrow == narrow_was
    # the old rule sized a block at 48 features of 128 lanes and 32 of
    # 192: what the account gives the held [96, 256] and [192, 256]
    # accumulators, and not the streamed [256, 192 -> 256] of "bf16v"
    assert wide[:3] == wide_was[:3] and wide[3] != wide_was[3]
    for got, off in ((narrow, narrow_off), (wide, wide_off)):
        assert [a == b for a, b in zip(got, off)] == [
            i not in turned for i in range(len(got))]


@pytest.mark.parametrize("overlap", [True, False])
def test_partition_kernel_compiles(one_chip, as_tpu, overlap):
    """The root's split at 2**20 rows of 28 columns: the one-block kernel
    reads the pane through a blocked operand offset by a prefetched
    scalar and writes it, aliased, through its windows."""
    compiled = _lower_partition(one_chip, F, N, N, overlap).compile()
    _check(compiled, custom_call=True)
    assert "output_to_operand_aliasing={{}: (2, {})}" in compiled.as_text()


@pytest.mark.parametrize("overlap,digest", [
    (True, "adf8b187faf1ab35"), (False, "5f913a79bb50370d")])
def test_narrow_partition_kernel_is_the_program_it_was(one_chip, as_tpu,
                                                       overlap, digest):
    """The F=28 kernel (a pane of one block, ``compact.partition_grid``)
    lowers, under either DMA schedule, to the Mosaic program that PR 37's
    commit lowered it to: the kernel that partitions a range inside the
    two-sided pane (side and first lane block prefetched, the windows at
    pane lanes).  The digests are that commit's, of the kernel's text
    without locations, taken in this container; until then they were
    those of 2b047c7, the commit before the row-block grid, whose
    out-of-pane kernel is gone.  A PR that does not mean to change the
    narrow tables' kernel leaves them as they are."""
    import hashlib
    from lightgbm_tpu.ops import compact
    R = compact.pane_rows(F)
    assert compact.partition_grid(R) == (compact.BLOCK, R, 1)
    (kernel,) = _mosaic_kernels(
        _lower_partition(one_chip, F, N, N, overlap).as_text())
    assert hashlib.sha256(kernel.encode()).hexdigest()[:16] == digest


# ------------------------------- accumulation ranges (ops/hist_pallas.py)

@pytest.mark.parametrize("features,shape,digest", [
    # the narrow cell's eight passes (seven shapes) and the wide cell's
    # largest two, the leaf-wise cell's folded float pass
    (F, ("int8", 128, 3, 1), "93a51d1b466040e1"),
    (F, ("int8", 128, 3, 2), "ef6d690b0dc71735"),
    (F, ("int8", 128, 3, 4), "4da1110c461f64a3"),
    (F, ("int8", 128, 3, 8), "a4aa1be5dd724cc9"),
    (F, ("int8", 128, 3, 16), "1cdefdef40ae6979"),
    (F, ("int8", 128, 3, 32), "3ae66f6404759ce5"),
    (F, ("int8", 192, 3, 64), "e48939e86d588acd"),
    (WIDE_F, ("int8", 128, 3, 32), "21e1d12aa024ca14"),
    (WIDE_F, ("int8", 192, 3, 64), "00db8c089905341e"),
    (WIDE_F, ("bf16v", 128, 5, 1), "d0801cd0a5de57fa"),
])
def test_one_range_kernels_are_the_programs_they_were(one_chip, as_tpu,
                                                      features, shape,
                                                      digest):
    """A table under ``INT8_HIST_MAX_ROWS`` rows is not on the path of the
    accumulation ranges: every pass of the three cells the benchmark had
    lowers to the Mosaic program that the commit before the ranges
    (0d73194) lowered it to, on the two-axis grid.  The digests are that
    commit's, of the kernel's text without locations, taken in this
    container."""
    import hashlib
    (kernel,) = _mosaic_kernels(
        _lower_kernel(one_chip, features, *shape).as_text())
    assert hashlib.sha256(kernel.encode()).hexdigest()[:16] == digest
