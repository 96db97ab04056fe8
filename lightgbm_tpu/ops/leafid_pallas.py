"""Pallas TPU replay of a compacted tree's splits: every row's leaf id.

The compacted leaf-wise grower (models/grower_unified._grow_leafcompact)
grows a tree over a permuted pane whose order never leaves the function,
so the score update needs each row's leaf in the table's own order.  XLA
keeps those ids up to date at every split: a pass over all N rows that
reads the split's column of the [F, N] code table and the ids (101.6 ms a
tree at 7,325,625 x 220, 254 splits, on a v5e).  Nothing inside the loop
reads them, so this kernel makes them once, after it, from the tree's
split records: split n turned leaf ``leaf[n]``'s rows whose code on table
row ``row[n]`` exceeds ``threshold[n]`` into leaf n + 1, in order.

- the grid runs over chunks of rows, and reads each chunk's [fp, rows]
  block of the table once (fp: the columns in whole 8-bit tiles);
- the records and their number are prefetched scalars, and the loop over
  the splits has that number as its trip count, so a tree that stopped
  early runs the same program;
- the chunk's ids are carried across the splits in vector registers as a
  DENSE [rows / 128, 128] view (row r is element (r // 128, r % 128)) and
  written once;
- a split's codes are read by the split's column alone: the block viewed
  as int32 words holds four columns' codes a word (column f is byte f % 4
  of word row f // 4), and an 8-bit tile of 8 columns x 128 rows is two
  word rows, so the view as pairs of word rows, [fp / 8, 2, rows], moves
  no byte and the split's pair is an index of its leading axis.  Both
  rows of the pair are read and one kept (a dynamic index of the
  sublane within the pair does not compile), no compare against every
  column.

Measured on a v5e, the call alone: 4.9 ms at [220, 7,325,625] with 254
splits (the table's 1.64 GB take 2.0 ms at the HBM's peak) and 1.2 ms at
[2000, 400,000], against 103.5 and 7.6 ms for the 254 passes of the XLA
update (PERF.md section 6).  Integer logic throughout: the ids
equal the XLA update's element for element (tests/test_leafid_replay.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# bytes of one [fp, rows] block (the pipeline keeps two, inside the 16 MiB
# of scoped VMEM), and the most rows a chunk: a turn of the split loop
# carries the chunk's ids in rows / 1,024 vector registers and reads
# twice that many words; at [220, 7,325,625] chunks of 8,192 / 16,384 /
# 32,768 rows read 8.6 / 4.9 / 4.3 ms a call, the loop's own cost a turn
# spread over more rows
BLOCK_BYTES = 6 << 20
MAX_ROWS = 16384
# How a split's pair of word rows is read: through the int32 view of the
# block's ref, which moves no byte.  jax 0.9's TPU interpreter has no
# bitcast or reshape of a ref, so the tests that run a program's kernels
# under ``pltpu.force_tpu_interpret_mode`` patch this to False: the same
# words then come from a bitcast of the pair's eight 8-bit rows once
# loaded, which the TPU compiler lays out anew at every split (23.6 ms a
# call at [220, 7,325,625] on a v5e, where the view takes 4.9);
# ``interpret=True`` alone runs either
WORDS_BY_VIEW = True


def replay_grid(F: int, N: int):
    """(columns of the block, in whole 8-bit tiles; rows a chunk; chunks)
    of the replay over an [F, N] table, from its static shape: the chunk
    the largest multiple of 1,024 rows (8 sublanes of the dense id view)
    that keeps a block inside ``BLOCK_BYTES``, at most ``MAX_ROWS`` and no
    more than the table's rows need."""
    fp = F + (-F) % 8
    rows = BLOCK_BYTES // fp
    rows = max(1024, min(MAX_ROWS, rows - rows % 1024, N + (-N) % 1024))
    return fp, rows, -(-N // rows)


def _replay_kernel(count_ref, leaf_ref, row_ref, thr_ref, bins_ref, ids_out,
                   *, fp, rows, by_view):
    i32 = jnp.int32
    if by_view:
        pairs = bins_ref.bitcast(i32).reshape(fp // 8, 2, rows)

        def pair(f):
            return pairs[f // 8, 0:1, :], pairs[f // 8, 1:2, :]
    else:
        def pair(f):
            words = pltpu.bitcast(
                bins_ref[pl.ds(pl.multiple_of(8 * (f // 8), 8), 8), :], i32)
            return words[0:1], words[1:2]

    def split(n, ids):
        f = row_ref[n]
        word = jnp.where((f // 4) % 2 == 0, *pair(f)).reshape(ids.shape)
        code = (word >> (8 * (f % 4))) & 255
        return jnp.where((ids == leaf_ref[n]) & (code > thr_ref[n]), n + 1,
                         ids)

    ids_out[...] = jax.lax.fori_loop(0, count_ref[0], split,
                                     jnp.zeros(ids_out.shape, i32))


def _replay_fn(bins, count, leaf, row, threshold, interpret=False,
               by_view=True):
    """[N] int32 leaf id of every row of the [F, N] 8-bit table after the
    first ``count[0]`` splits of the records (``leaf``, ``row``,
    ``threshold``: [S] int32 each) are applied to a tree of one leaf, 0.
    The tail chunk's rows past N are read as they come and never
    written.  ``interpret``: Pallas's interpreter; ``by_view``:
    ``WORDS_BY_VIEW``."""
    F, N = bins.shape
    fp, rows, chunks = replay_grid(F, N)
    n128 = N + (-N) % LANES
    ids = pl.pallas_call(
        functools.partial(_replay_kernel, fp=fp, rows=rows,
                          by_view=by_view),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(chunks,),
            in_specs=[pl.BlockSpec((fp, rows), lambda i, *_: (0, i))],
            out_specs=pl.BlockSpec((rows // LANES, LANES),
                                   lambda i, *_: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((n128 // LANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(count, leaf, row, threshold, bins)
    return ids.reshape(n128)[:N]


# jitted: the custom call sits under ``row_route/jit(_replay_fn)`` in a
# trace, one call a tree
replay_pallas_raw = jax.jit(_replay_fn,
                            static_argnames=("interpret", "by_view"))


def replay_leaf_ids(bins, num_leaves, split_leaf, split_row, threshold,
                    interpret: bool = False):
    """The original-order leaf ids of a compacted tree of ``num_leaves``
    leaves: node n split leaf ``split_leaf[n]`` on table row
    ``split_row[n]`` (the partition row, after any packing's column map)
    at ``threshold[n]``, its right child leaf n + 1; the records int32."""
    count = jnp.reshape(num_leaves - 1, (1,)).astype(jnp.int32)
    return replay_pallas_raw(bins, count, split_leaf, split_row, threshold,
                             interpret=interpret, by_view=WORDS_BY_VIEW)
