"""Compile, for a described v5e, the lambdarank gradient program of the
benchmark's ranking cell: ``istella-rank-leafwise-f32``, 7,325,625
documents in 23,219 queries of up to 4,096, in twelve length classes
(``tests/test_tpu_compile.py`` holds the rules these files keep).
"""
import os
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp

from tpu_described import (  # noqa: F401 (fixtures)
    _cell_config, _like, _shape, no_persistent_cache, one_chip, topo)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# what the program compiled to before queries moved as windows: one index
# a slot in, one a row out
PARENT_TEMP, PARENT_ARGS = 56_886_784, 169_055_744


class _Meta:
    def __init__(self, label, boundaries):
        self.label, self.query_boundaries = label, boundaries
        self.weights = None


def _element_moves(text, most):
    """Lines of a compiled program that move single elements by an index
    each, more than ``most`` of them: a gather whose slices are one element
    and whose result is larger, and any scatter of one-element windows."""
    found = []
    for line in text.splitlines():
        op = re.search(r"= [a-z0-9]+\[([\d,]*)\]\S* gather\(", line)
        if op and re.search(r"slice_sizes=\{1(,1)*\}", line):
            if np.prod([int(d) for d in op.group(1).split(",") if d]) > most:
                found.append(line.strip()[:200])
        if " scatter(" in line and "update_window_dims={}" in line:
            found.append(line.strip()[:200])
    return found


def test_rank_gradient_program_moves_whole_queries(one_chip,
                                                   no_persistent_cache):
    """The per-tree gradient program at the cell's query layout: no
    gather or scatter of one index a document (the parent's two
    ``f32[7325625]`` gathers out and a ``[block, w]`` gather of slots a
    class block in), every class's windows read and added as whole
    128-lane chunks, and what the program holds on the device no more than
    the parent's."""
    from harness.rank_data import query_lengths
    from lightgbm_tpu.config import ObjectiveConfig
    from lightgbm_tpu.objectives import create_objective, rank
    conf = _cell_config("istella-rank-leafwise-f32")
    lay = conf["layout"]
    rows = conf["rows"]
    sizes = query_lengths(rows, conf["queries"], lay["length_sigma"],
                          lay["longest"], lay["layout_key"])
    bounds = np.concatenate(([0], np.cumsum(sizes)))
    label = np.random.default_rng(0).integers(0, 5, rows).astype(np.float32)
    obj = create_objective("lambdarank", ObjectiveConfig())
    obj.init(_Meta(label, bounds), rows)
    classes = obj.layout.classes
    assert len(classes) == 12 and obj.layout.docs == rows == 7_325_625
    _key, params, fn = obj.chunk_spec()
    compiled = rank._iteration_program(fn).lower(
        _like(one_chip, params),
        _shape(one_chip, (rows,), jnp.float32)).compile()
    text = compiled.as_text()
    most = max(block for _w, _b, block in classes)
    assert not _element_moves(text, most), _element_moves(text, most)[:4]
    assert not re.search(r"= f32\[%d\]\S* fusion\([^\n]*kind=kCustom[^\n]*"
                         r"/gather" % rows, text)
    # each class: a gather of whole chunks in its block loop, a
    # scatter-add of whole chunks after it
    assert len(re.findall(r" gather\([^\n]*slice_sizes=\{1,128\}",
                          text)) >= len(classes)
    assert len(re.findall(r" scatter\([^\n]*update_window_dims=\{\d+,\d+\}",
                          text)) == len(classes)
    # no row-long index table and no per-slot row table any more
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes <= PARENT_TEMP, ma.temp_size_in_bytes
    assert ma.argument_size_in_bytes < PARENT_ARGS, ma.argument_size_in_bytes
