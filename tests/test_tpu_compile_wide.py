"""Compile, for a described v5e, the programs of the wide table: the
benchmark's second configuration, F=2,000 at N=400,000
(``tests/test_tpu_compile.py`` holds the rules these files keep).
"""
import math
import os
import re

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, _captured_chunk_program, _cell_size, _check, _grow_args,
    _GROW_KW, _like, _lower_partition, _lower_route_kernel,
    no_persistent_cache, one_chip, _range_passes, _tiny_binary_dataset,
    topo, _TracedCounters, _unlabelled, WIDE_F, WIDE_N)

import pytest

WIDE_N_PADDED = 401_408     # the cell's 400,000 rows in whole chunks


def test_grow_depthwise_int8_compiles_on_the_wide_table(one_chip, as_tpu):
    """Every pass of a 255-leaf level-wise tree over 2,000 columns: the
    parent of the feature-block repair was refused here for VMEM (the
    64-leaf pass, ``s32[2016,255,192]``)."""
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    compiled = grow_tree_depthwise_jit.lower(
        *_grow_args(one_chip, WIDE_N, WIDE_F), compute_dtype="int8",
        **_GROW_KW).compile()
    _cell_size(_check(compiled, custom_call=True))


@pytest.mark.parametrize("slots", [1, 128])
def test_route_kernel_compiles_at_the_wide_cell(one_chip, as_tpu, slots):
    """The level-wise row routing over 2,000 columns: the feature-block
    axis of the kernel's grid (four blocks of 512, the last ragged), the
    row's bin carried in VMEM scratch, at the root's level and the
    last."""
    from lightgbm_tpu.ops.route_pallas import route_grid
    assert route_grid(WIDE_F, WIDE_N_PADDED) == (512, 4, 4096, 98)
    compiled = _lower_route_kernel(one_chip, WIDE_F, WIDE_N_PADDED,
                                   slots).compile()
    assert "_route_kernel" in compiled.as_text()
    _check(compiled, custom_call=True)


def test_fused_chunk_program_compiles_on_the_wide_table(
        one_chip, as_tpu, monkeypatch):
    """The program of the cell ``epsilon-levelwise-int8.train``: the
    configuration's own ``key=value`` pairs, 2,000 columns, its argument
    tree re-shaped to the 400,000 rows."""
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "epsilon-levelwise-int8.json")) as fh:
        conf = json.load(fh)
    assert (conf["rows"], conf["features"]) == (WIDE_N, WIDE_F)
    n_tiny = 1000
    prog, seen = _captured_chunk_program(
        monkeypatch, conf["params"], _tiny_binary_dataset(n_tiny, WIDE_F),
        is_eval=False)
    args = _like(one_chip, seen, rows_from=n_tiny, rows_to=WIDE_N)
    _cell_size(_check(prog.lower(*args).compile(), custom_call=True))


# ------------------------------------- best-first growth (epsilon-leafwise-f32)

@pytest.mark.parametrize("overlap", [True, False])
def test_partition_kernel_compiles_on_the_wide_table(one_chip, as_tpu,
                                                     overlap):
    """The root's partition over 2,000 columns: a pane of 2,016 rows in
    three row blocks of 672 at 512 lanes, the one-hots held in VMEM for
    the row blocks of a lane block (``compact.partition_grid``).  One block
    of that pane is priced at 91 MiB."""
    from lightgbm_tpu.ops import compact
    R = compact.pane_rows(WIDE_F)
    assert compact.partition_vmem_bytes(R) > 90 << 20
    assert compact.partition_grid(R) == (512, 672, 3)
    assert compact.pane_layout(R, WIDE_N_PADDED) == (2016, 402_432)
    compiled = _lower_partition(one_chip, WIDE_F, WIDE_N_PADDED,
                                WIDE_N_PADDED, overlap).compile()
    _check(compiled, custom_call=True)
    assert "output_to_operand_aliasing={{}: (2, {})}" in compiled.as_text()


def test_grow_leafcompact_f32_compiles_on_the_wide_table(one_chip, as_tpu):
    """One tree of the cell ``epsilon-leafwise-f32.train``: the compacted
    grower, the row-blocked partition kernel at each of the nine bucket
    widths, the float histogram kernel on the feature-block grid with
    its bin code folded into the idle value lanes (``hist_fold``).  And
    what the compiled program must keep: the hi half of the float32
    gradient pair is a rounding XLA does not take for the identity (so the
    lo half carries something), and a split writes the pane and the leaf
    histogram cache in place (under ``lax.cond`` / ``lax.switch`` each was
    copied whole, twice a split).  Since PR 37 a split's range is
    partitioned inside the pane: every partition kernel reads and writes
    the pane itself, and XLA makes no pass over a range around it (the
    slice out, the ``where`` and the ``dynamic_update_slice`` back were
    164 of the cell's 314 ms of ``partition``)."""
    from lightgbm_tpu.models.grower_unified import grow_tree_leafcompact
    kw = dict(_GROW_KW, min_data_in_leaf=1, min_sum_hessian_in_leaf=100.0)
    with _TracedCounters() as traced:
        compiled = grow_tree_leafcompact.lower(
            *_grow_args(one_chip, WIDE_N, WIDE_F), use_pallas_partition=True,
            partition_overlap=True, **kw).compile()
    assert traced["partition/in_pane"] == traced["partition/pallas"] == 9
    assert traced["partition/pallas_rblocks"] == 27
    assert traced["partition/leafids_pallas"] == 1
    assert "partition/leafids_xla" not in traced
    _cell_size(_check(compiled, custom_call=True))
    text = _LEAFCOMPACT["text"] = compiled.as_text()
    assert _range_passes(text, WIDE_F) == []
    assert len(re.findall(
        r'"tpu_custom_call"[^\n]*/partition/jit\(_partition_in_pane_fn\)',
        text)) == 9
    rounded = re.findall(r"(%[\w.\-]+) = f32\[[^ ]* reduce-precision\("
                         r"(%[\w.\-]+)\), exponent_bits=8, mantissa_bits=7",
                         text)
    assert rounded
    # the lo half: x - hi(x), in the same fusion
    assert any(re.search(r"subtract\(%s, %s\)" % (re.escape(x),
                                                  re.escape(hi)), text)
               for hi, x in rounded)
    assert not re.search(r"= s8\[2,2016,402432\][^ ]* copy\(", text)
    assert not re.search(r"= f32\[255,2000,255,3\][^ ]* copy\(", text)
    # the float pass folds the bin code by 8: ten kernels (the root's pass
    # and nine bucket widths) of a [32, 40] accumulator a feature.  No
    # 128-lane accumulator, and nothing of its size either: beside the
    # leaf histogram cache no float32 buffer, as laid out, holds 64 MB
    # (the unfolded pass left four of 262 - 264 MB a pass, whatever the
    # leaf's size: the accumulator and XLA's views of five value lanes a
    # bin, the bins or the lanes padded to a tile)
    assert len(re.findall(r"= f32\[2016,32,40\][^ ]* custom-call\(",
                          text)) == 10
    assert not re.search(r"f32\[2016,25[56],128\]", text)
    large = {found.group(0) for found in _F32_BUFFER.finditer(text)
             if _bytes_as_laid_out(found) >= 64 << 20}
    assert large and all(name.startswith("f32[255,2000,255,3]")
                         for name in large), large


_LEAFCOMPACT = {}   # the tree program's compiled text, once a module


def _leafcompact_text(one_chip):
    if not _LEAFCOMPACT:
        from lightgbm_tpu.models.grower_unified import grow_tree_leafcompact
        kw = dict(_GROW_KW, min_data_in_leaf=1,
                  min_sum_hessian_in_leaf=100.0)
        _LEAFCOMPACT["text"] = grow_tree_leafcompact.lower(
            *_grow_args(one_chip, WIDE_N, WIDE_F), use_pallas_partition=True,
            partition_overlap=True, **kw).compile().as_text()
    return _LEAFCOMPACT["text"]


def test_the_compiler_put_nothing_of_a_panes_size_into_grow_leafcompact(
        one_chip, as_tpu):
    """The number form of "XLA put no copy in" (PR 37): of what the
    compiler inserted itself into one tree's program on the wide table
    (``compile.programs[].unscoped_ops`` of a ``metrics_out`` record), the
    largest result is a leaf's histogram row, far under one side of the
    pane; PR 34's parent copied the whole pane twice a split.  And every
    operation a trace of ``epsilon-leafwise-f32.train`` shows under no
    scope has its label."""
    from lightgbm_tpu import costmodel
    from lightgbm_tpu.ops import compact
    from lightgbm_tpu.telemetry import DEVICE_PHASES
    text = _leafcompact_text(one_chip)
    labels = costmodel.label_unscoped_ops(text)
    assert _unlabelled(text, labels) == []
    assert {found[0] for found in labels.values()} <= set(
        DEVICE_PHASES) | {costmodel.XLA}
    summary = costmodel._unscoped_summary(labels)
    rows, lanes = compact.pane_layout(compact.pane_rows(WIDE_F),
                                      WIDE_N_PADDED)
    assert (rows, lanes) == (2016, 402_432)
    opcode, nbytes = summary["xla_largest"]
    assert summary["xla"] > 10 and nbytes < rows * lanes // 16, summary
    # a row of the leaf histogram cache, prefetched for the subtraction
    assert nbytes == WIDE_F * 255 * 3 * 4, (opcode, nbytes)
    # the table's int8 view for the pane's packing, once a tree
    assert any(found[0] == "partition" and found[1] == "fusion"
               for found in labels.values())


def test_leaf_ids_are_replayed_once_after_the_split_loop(one_chip, as_tpu):
    """The original-order leaf ids of one tree on the wide table: no
    operation over the table's rows under ``row_route`` inside the split
    loop (the parent compared every row at each of 254 splits, and copied
    the ids into VMEM and back around it), and one call of the replay
    kernel after the loop, which reads the 8-bit table as it is laid out:
    no copy or conversion of it in front of the call."""
    text = _leafcompact_text(one_chip)
    rows = re.compile(r"\[(\d+,)*(%d|%d)\]" % (WIDE_N, WIDE_N_PADDED))
    in_loop = [line.strip()[:160] for line in text.splitlines()
               if "/while/body/" in line and "/row_route/" in line
               and rows.search(line.split(" = ", 1)[-1].split("(", 1)[0])]
    assert in_loop == [], in_loop
    calls = re.findall(r"= s32\[(\d+),128\]\S* custom-call\(([^)]*)\)"
                       r'[^\n]*"tpu_custom_call"[^\n]*op_name="([^"]*)"',
                       text)
    replay = [c for c in calls if "/row_route/jit(_replay_fn)" in c[2]]
    assert len(replay) == 1, calls
    tiles, operands, op_name = replay[0]
    assert int(tiles) * 128 == WIDE_N      # the ids as [N / 128, 128]
    assert "/while/" not in op_name
    table = operands.split(", ")[-1]
    assert re.search(r"%s = u8\[%d,%d\]\S* parameter\(" % (
        re.escape(table), WIDE_F, WIDE_N), text), table


_F32_BUFFER = re.compile(r"f32\[([\d,]+)\]\{([\d,]+):T\((\d+),(\d+)\)")


def _bytes_as_laid_out(found):
    """Bytes of a tiled float32 buffer of the compiled text: its two
    minor-most dimensions padded to whole ``T(rows, lanes)`` tiles."""
    dims = [int(d) for d in found.group(1).split(",")]
    minor_to_major = [int(d) for d in found.group(2).split(",")]
    for at, tile in zip(minor_to_major, (int(found.group(4)),
                                         int(found.group(3)))):
        dims[at] += (-dims[at]) % tile
    return 4 * math.prod(dims)
