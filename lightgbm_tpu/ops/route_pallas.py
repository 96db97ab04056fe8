"""Pallas TPU row-routing kernel of the level-wise grower.

After a level's splits are chosen every row has to learn its slot's
(split feature, threshold, chosen, new right leaf, smaller side), read
ONE of its own bin codes, compare, and take a new slot and leaf.  In XLA
that is a [P, N] one-hot contracted with the slot table, an [F, N]
one-hot (or a per-row gather) for the bin and a chain of selects
(models/grower_unified._grow_depthwise, the path every other backend
keeps).  This kernel streams the rows once a level instead:

- the per-row side-bands (slot id, leaf id, row mask; new slot, new leaf,
  the next histogram pass's row selection) travel as DENSE [N / 128, 128]
  int32 views of the [N] arrays (the same bytes in HBM), so every vector
  operation on them fills its 8 sublanes: row r is element
  (r // 128, r % 128);
- slot side: the level's table rides as two packed int32 words a slot
  (the partition feature; threshold | chosen | smaller-side | right
  leaf) and a row picks its slot's words by a LANE GATHER from the
  128-entry table (slots beyond 128 in slabs of 128): no one-hot, no
  matmul, exact by construction;
- feature side: per group of 128 rows the bin block [fb, 128] int8 is
  widened once and the row's own code kept by one compare and one select
  a vector register against the row's feature id broadcast over the
  sublanes, then reduced over the sublanes: no conversion of the table
  to float, no gather from HBM.  Tables wider than one block ride a
  feature-block grid axis (features minor), the row's code carried in
  VMEM scratch;
- the last feature block compares with the threshold and writes the new
  slot id, the new leaf id and the selection of the smaller child's rows.

Integer logic throughout: the outputs equal the XLA routing's element
for element (tests/test_route_pallas.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8        # sublanes of a 32-bit vector register
# bits of the packed second table word: threshold (8-bit bin codes),
# chosen, smaller-child-is-right, then the new right leaf
THR_BITS = 8
CHOSEN_BIT = THR_BITS
SMALL_BIT = CHOSEN_BIT + 1
LEAF_SHIFT = SMALL_BIT + 1
# bytes of one [fb, chunk] int8 bin block (the pipeline keeps two: 4 MiB
# of bin windows beside 2 MiB of side-bands at the most rows a chunk),
# and the most columns a block, whose 8-feature steps are unrolled.
# Measured on a v5e, the call alone (PERF.md section 6, PR 33): at
# [28, 10.5M] 8,192 / 32,768 / 65,536 rows a chunk read 2.17 / 1.92 /
# 1.86 ms (a grid step costs 0.35 us); at [2000, 401,408] 256 / 512 /
# 1,024 / 2,000 columns a block read 2.12 / 2.18 / 2.26 / 2.11 ms
BIN_BLOCK_BYTES = 2 << 20
MAX_CHUNK = 32768
MAX_FEATURE_BLOCK = 512


def route_grid(F: int, N: int):
    """(features a block, feature blocks, rows a chunk, row chunks) of one
    routing pass over an [F, N] table, from its static shape: the whole
    width in one block up to ``MAX_FEATURE_BLOCK`` columns, else balanced
    blocks of whole int8 sublane tiles; the chunk the largest multiple of
    1,024 rows (8 sublanes of the dense side-band view) that keeps a bin
    block inside ``BIN_BLOCK_BYTES``."""
    n_fblocks = -(-F // MAX_FEATURE_BLOCK)
    fb = F if n_fblocks == 1 else -(-F // n_fblocks)
    if n_fblocks > 1:
        fb += (-fb) % 32
        n_fblocks = -(-F // fb)
    rows = BIN_BLOCK_BYTES // (fb + (-fb) % 32)
    chunk = max(1024, min(MAX_CHUNK, rows - rows % 1024))
    chunk = min(chunk, N + (-N) % 1024)
    return fb, n_fblocks, chunk, -(-N // chunk)


def pack_table(feat_part, threshold, chosen, right_leaf, small_is_right):
    """The level's [2, P up to whole 128s] int32 slot table."""
    i32 = jnp.int32
    word = ((threshold.astype(i32) & ((1 << THR_BITS) - 1))
            | (chosen.astype(i32) << CHOSEN_BIT)
            | (small_is_right.astype(i32) << SMALL_BIT)
            | (right_leaf.astype(i32) << LEAF_SHIFT))
    table = jnp.stack([feat_part.astype(i32), word])
    P = table.shape[1]
    return jnp.pad(table, ((0, 0), (0, (-P) % LANES)))


def _route_kernel(table_ref, slot_ref, leaf_ref, mask_ref, bins_ref,
                  slot_out, leaf_out, sel_out, feat_scr, word_scr, bin_scr,
                  *, fb, n_fblocks, chunk, slabs):
    # grid = (row_chunks, feature_blocks), features minor: the side-band
    # windows stay put while the feature blocks of a chunk go by
    j = pl.program_id(1)
    i32 = jnp.int32
    groups = chunk // LANES

    @pl.when(j == 0)
    def _():
        slot = slot_ref[...]                            # [groups, 128]
        lane = slot & (LANES - 1)

        def look(word, s):
            # the slot's table word out of slab s, by a gather along lanes
            row = table_ref[word:word + 1, s * LANES:(s + 1) * LANES]
            return jnp.take_along_axis(
                jnp.broadcast_to(row, slot.shape), lane, axis=1,
                mode="promise_in_bounds")

        for word, scr in enumerate((feat_scr, word_scr)):
            mine = look(word, 0)
            for s in range(1, slabs):
                mine = jnp.where(slot // LANES == s, look(word, s), mine)
            scr[...] = mine
        bin_scr[...] = jnp.zeros_like(bin_scr)

    # feature side: the row's own code out of this block's fb rows, a
    # tile of 8 groups of 128 rows (one vector register of the dense
    # side-bands) a turn of a ROLLED loop: unrolled over a chunk's 256
    # groups the kernel took a second and a half to build, and a tree's
    # program holds one instance a level
    iota_f = jax.lax.broadcasted_iota(i32, (fb, LANES), 0) + j * fb
    sublane = jax.lax.broadcasted_iota(i32, (TILE, LANES), 0)

    def tile(t, carry):
        r0 = pl.multiple_of(t * TILE, TILE)
        feat = feat_scr[pl.ds(r0, TILE), :]                 # [8, 128]
        acc = jnp.zeros((TILE, LANES), i32)
        for k in range(TILE):
            c0 = pl.multiple_of((t * TILE + k) * LANES, LANES)
            x = bins_ref[:, pl.ds(c0, LANES)].astype(i32)   # [fb, 128]
            own = feat[k:k + 1, :] == iota_f
            mine = jnp.sum(jnp.where(own, x, 0), axis=0, keepdims=True)
            acc = jnp.where(sublane == k, mine, acc)
        bin_scr[pl.ds(r0, TILE), :] += acc
        return carry

    jax.lax.fori_loop(0, groups // TILE, tile, 0)

    @pl.when(j == n_fblocks - 1)
    def _():
        # codes ride as int8 bit-patterns: >= 128 wrapped negative
        row_bin = bin_scr[...] & 255
        word = word_scr[...]
        slot = slot_ref[...]
        thr = word & ((1 << THR_BITS) - 1)
        chosen = (word >> CHOSEN_BIT) & 1
        small_right = (word >> SMALL_BIT) & 1
        right_leaf = word >> LEAF_SHIFT
        go_right = (row_bin > thr).astype(i32)
        moved = chosen * go_right
        slot_out[...] = 2 * slot + moved
        leaf_out[...] = jnp.where(moved > 0, right_leaf, leaf_ref[...])
        sel_out[...] = (chosen * (go_right == small_right).astype(i32)
                        * mask_ref[...])


def _route_pallas_fn(bins, slot_id, out_leaf, row_mask, table):
    """(new slot id, new leaf id, smaller-child selection), each [N]
    int32, from the [F, N] 8-bit bin table, the [N] int32 side-bands
    (``row_mask`` as 0 / 1) and ``pack_table``'s slot table.  N must be a
    multiple of 128 (the dense side-band view); it need not be a multiple
    of the chunk: the tail block's rows past N are read as they come and
    never written."""
    F = bins.shape[0]
    N = slot_id.shape[0]
    assert N % LANES == 0 and 0 <= N - bins.shape[1] < LANES
    assert table.shape[1] % LANES == 0
    fb, n_fblocks, chunk, n_chunks = route_grid(F, N)
    groups = chunk // LANES
    dense = lambda a: a.reshape(N // LANES, LANES)          # noqa: E731
    side = pl.BlockSpec((groups, LANES), lambda i, j: (i, 0))
    kernel = functools.partial(
        _route_kernel, fb=fb, n_fblocks=n_fblocks, chunk=chunk,
        slabs=table.shape[1] // LANES)
    outs = pl.pallas_call(
        kernel,
        grid=(n_chunks, n_fblocks),
        in_specs=[
            pl.BlockSpec(table.shape, lambda i, j: (0, 0)),
            side, side, side,
            pl.BlockSpec((fb, chunk), lambda i, j: (j, i)),
        ],
        out_specs=[side, side, side],
        out_shape=[jax.ShapeDtypeStruct((N // LANES, LANES), jnp.int32)] * 3,
        scratch_shapes=[pltpu.VMEM((groups, LANES), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(table, dense(slot_id), dense(out_leaf), dense(row_mask),
      bins.astype(jnp.int8))
    return tuple(o.reshape(N) for o in outs)


# jitted: the levels of a tree whose slot tables pad to one width (all of
# them up to 128 slots) share ONE trace of the kernel.  Traced anew at
# each of a 255-leaf tree's eight levels the kernel added 5 s of trace
# time to the chunk program's build (PERF.md section 6, PR 33)
route_pallas_raw = jax.jit(_route_pallas_fn)


def route_pallas_ok(bins_dtype, num_bins_max: int) -> bool:
    """Eligibility of the routing kernel, the histogram kernels' rule
    (ops/histogram._pallas_hist_ok): a TPU backend and 8-bit bin codes,
    unless LGBM_TPU_NO_PALLAS=1, the hatch that covers every Pallas
    kernel.  The table's width, the level's slots and the row count are
    not part of it: the grid follows them (``route_grid``).  The caller
    counts the outcome once a level (``partition/route_pallas`` /
    ``partition/route_xla``)."""
    from .. import hatches
    if hatches.flag("LGBM_TPU_NO_PALLAS"):
        return False
    return (jax.default_backend() == "tpu" and num_bins_max <= 256
            and jnp.dtype(bins_dtype).itemsize == 1)


def route_level_pallas(partition_bins, slot_id, out_leaf, row_mask,
                       feat_part, threshold, chosen, right_leaf,
                       small_is_right):
    """One level's row routing on the kernel: what
    ``grower_unified._route_level_xla`` returns, element for element.
    Rows that are no whole number of 128 pad the [N] side-bands (never
    the bin table) and cut them back."""
    N = slot_id.shape[0]
    pad = (-N) % LANES
    side = [slot_id, out_leaf, row_mask.astype(jnp.int32)]
    if pad:
        side = [jnp.pad(a, (0, pad)) for a in side]
    slot_id, out_leaf, sel = route_pallas_raw(
        partition_bins, *side,
        pack_table(feat_part, threshold, chosen, right_leaf,
                   small_is_right))
    return slot_id[:N], out_leaf[:N], sel[:N] != 0
