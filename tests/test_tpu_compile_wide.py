"""Compile, for a described v5e, the programs of the wide table: the
benchmark's second configuration, F=2,000 at N=400,000
(``tests/test_tpu_compile.py`` holds the rules these files keep).
"""
import os

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, _captured_chunk_program, _cell_size, _check, _grow_args,
    _GROW_KW, _like, _lower_route_kernel, no_persistent_cache, one_chip,
    _tiny_binary_dataset, topo, WIDE_F, WIDE_N)

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
