"""Streaming stable row-partition — the compacted leaf-wise grower's core op.

The reference keeps every leaf's rows CONTIGUOUS in a permuted index array
and partitions the parent's range at each split
(/root/reference/src/io/data_partition.hpp:93-139); its histogram then
touches only the leaf's own rows (dense_bin.hpp:46-112 ConstructHistogram
over an ordered index list).  A TPU can't follow row indices (XLA lowers
small-table gathers to per-row scalar addressing — measured ~85 ms per [N]
f32 gather at 11M rows, PROFILE.md), so this module moves the DATA instead
of the indices: the [R, N] int8 plane matrix (bin rows + grad/hess
bit-planes + validity) is kept physically partitioned, and each split
stably partitions the parent's lane range in one streaming sweep.

The pane has TWO SIDES, [2, rows, lanes] (``pack_planes``), and a split
partitions its range INSIDE it: one Pallas call whose blocked operand is
the pane itself, offset to the range's first tile of 128 lanes by a
prefetched scalar, and whose output is the same storage
(``input_output_aliases``).  The kernel reads the parent's lanes where they lie on one side and lands
the children, through its read-modify-write windows at pane lanes, where
they will lie on the other.  Two sides, because the right stream runs
ahead of the read: right rows land at ``start + plcnt + ...``, lanes a
range partitioned onto itself would not have read yet.  Live leaves have
disjoint lane ranges whatever their side, and the lanes a split writes
last held an ancestor that is split and dead, so nothing live is
overwritten; a leaf's side is the parity of its depth, which the grower
carries anyway.  Nothing is sliced out of the pane around the kernel and
nothing written back (until PR 37 three XLA passes over a bucketed range
a split, more than half of the partition's time on the wide table).

The Pallas kernel (TPU): grid = (lane blocks,), sequential; BOTH streams
(left rows, then right rows) run inside each grid step, so one sweep over
the data compacts both sides.  Per block the lane compaction is pure MXU:
an exclusive prefix-sum of the selection mask via a strict-lower-
triangular int8 matmul, a one-hot selection matrix built by an iota
compare, and an int8 x int8 -> int32 selection matmul that moves whole
[R, block] panes (f32 grad/hess travel bit-exactly as 4 int8 planes).
Each stream's compacted lanes are DMA'd to the written side through a
read-modify-write window at a running lane offset carried in SMEM; the
window's blend keeps every lane outside the stream's fresh ones, so the
neighbours' bytes are rewritten with themselves.  By default the
per-block window DMAs are OVERLAPPED (both window reads
issue up front and the left write-back flies under the right blend): the
two streams' fresh lane ranges are always disjoint, but their
128-aligned RMW padding can overlap, so the right blend patches this
block's fresh left lanes in VMEM from a third selection matmul instead
of re-reading them through HBM — only the two write-backs stay ordered.
Cost per partitioned row: block x R int8 MACs (x1.5 with the overlap
patch) + ~3 bytes of HBM traffic per plane — ~0.6% of the histogram MACs
the compaction saves (PROFILE.md).

A pane too tall for one block's VMEM working set (past 88 rows, 79
columns) takes the same two schedules through ``_partition_kernel_rows``:
grid = (lane blocks, row blocks), the selection one-hots made once a lane
block — they depend on the mask alone — and kept in VMEM for its row
blocks (``partition_grid``).

The XLA oracle (CPU/tests): a stable argsort formulation of the same
contract (a slice of the read side, a sort, an update into the written
side) — the kernels are differentially tested against it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 2048  # partition lane block of a pane of one row block; the
              # kernel's VMEM working set (pane slices, the one-hot
              # selection matrices, the RMW window buffers and blend
              # temporaries) is priced by partition_vmem_bytes below,
              # and partition_grid cuts a taller pane into row blocks
              # that keep it under PARTITION_VMEM_BUDGET

TALL_BLOCK = 512  # lane block of a pane cut into row blocks: the stored
                  # one-hots are [block + 128, block] each, so a narrow
                  # lane block leaves the budget to the pane's rows


# VMEM ceiling for the partition kernel's working set.  Past it Mosaic
# fails to ALLOCATE, so the grid is sized here rather than discovered as
# a compile error.  12 MiB of the ~16 MiB/core leaves headroom for
# Mosaic's own spills; with the overlap schedule's temporary count the
# estimate admits one row block up to R≈88 (F≈79) at the default lane
# block.
PARTITION_VMEM_BUDGET = 12 << 20


# Where the pane lives as the kernels' aliased output.  HBM, not ANY: an
# ANY operand XLA may place in VMEM, where dynamic DMA lane offsets
# (128-aligned here) are disallowed.  jax 0.9's TPU interpreter keeps a
# kernel argument in the call's own buffer only where its space reads ANY
# (one declared HBM it copies into a kernel buffer of its own, which for
# an output aliased to its input starts uninitialised), so the tests that
# run a program's kernels under ``pltpu.force_tpu_interpret_mode`` patch
# this to ``pl.ANY``; ``interpret=True`` alone runs it as it stands.
PANE_SPACE = pltpu.HBM


def partition_vmem_bytes(rows: int, block: int = BLOCK,
                         held: int = 1) -> int:
    """Working-set estimate (bytes) of the partition kernel at a row
    block of ``rows`` pane rows: double-buffered input blocks, the
    matmul operand matrices, the RMW window buffers and the i32
    shifted/keep/blend temporaries.  Sized for the default OVERLAP
    schedule, whose right-blend merge keeps more [rows, win] i32
    temporaries live at once (merged/keep_lr/shifted_r/keep_r around the
    blend) than the serialized kernel's three.  ``held``: the one-hot
    selection matrices alive at once — one where each is built and
    spent in turn, three where the row-blocked kernel keeps them for
    every row block of a lane block."""
    win = block + 128
    return (2 * (rows + 1) * block  # pipelined seg+mask input blocks, int8
            + block * block         # strict-lower-triangular operand, int8
            + held * win * block    # one-hot selection matrices, int8
            + 2 * rows * win        # RMW window buffers, int8
            + 4 * 4 * rows * win)   # i32 temporaries live around the blend


def partition_grid(rows: int, block: int = BLOCK):
    """(lane block, row-block height, row blocks) of the partition
    kernel's grid for a pane of ``rows`` rows, in the manner of
    hist_pallas.feature_grid: a pane whose priced working set fits the
    budget is one row block at ``block`` lanes (the program every narrow
    table has always had); a taller one is cut into the fewest equal
    row blocks, a multiple of the int8 sublane tile (32) high, that fit
    at TALL_BLOCK lanes with the three one-hots held.  The selection
    depends on the mask alone, so every row block of a lane block lands
    its rows by the same one-hots."""
    if partition_vmem_bytes(rows, block) <= PARTITION_VMEM_BUDGET:
        return block, rows, 1
    lanes = min(block, TALL_BLOCK)
    fixed = partition_vmem_bytes(0, lanes, held=3)
    per_row = partition_vmem_bytes(1, lanes, held=3) - fixed
    most = (PARTITION_VMEM_BUDGET - fixed) // per_row // 32 * 32
    count = -(-rows // most)
    height = -(-rows // (count * 32)) * 32
    return lanes, height, -(-rows // height)


def pallas_partition_ok() -> bool:
    """Eligibility of the Pallas partition kernel: TPU default backend,
    unless LGBM_TPU_NO_PALLAS=1 — the escape hatch a mixed-backend
    process (TPU backend up, computation steered onto virtual CPU
    devices, e.g. __graft_entry__.dryrun_multichip) sets so kernels
    never land on a CPU mesh.  A pane of any height is eligible
    (partition_grid cuts it to fit VMEM).  Every outcome is counted
    (telemetry) — the runtime record of which partition route the
    process baked into its programs."""
    from .. import hatches, telemetry
    if hatches.flag("LGBM_TPU_NO_PALLAS"):
        # count_route: this rule is re-evaluated per tree by host code, so
        # counting per outcome CHANGE keeps the counter at per-decision
        # magnitude like the trace-time counters
        telemetry.count_route("partition_ok", "partition/env_no_pallas")
        return False
    ok = jax.default_backend() == "tpu"
    telemetry.count_route("partition_ok",
                          "partition/pallas_eligible" if ok
                          else "partition/pallas_ineligible")
    return ok


def _partition_kernel(scal_ref, mask_ref, seg_ref, out_ref, win_ref,
                      offs_ref, sem_ref, *, R, block):
    """Grid (nblocks,): both streams (left then right) per lane block.
    ``seg_ref`` is this step's lane block of the parent's side of the
    pane, ``out_ref`` the whole pane in HBM (the same buffer: the call
    aliases it), ``scal_ref`` the prefetched ``_scalars``.

    Mosaic requires dynamic DMA lane offsets to be 128-aligned, so each
    stream writes a read-modify-write WINDOW at the aligned-down offset:
    the compacted rows are shifted to their exact in-window position by a
    one-hot shift matmul, blended with the window's current content, and
    the whole aligned window written back.  Fully serialized DMAs keep
    the left write visible to the right read (their windows may
    overlap)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        offs_ref[0] = 0
        offs_ref[1] = 0

    written = 1 - scal_ref[1]
    start = scal_ref[2]
    plcnt = scal_ref[3]
    win = block + 128

    # mask3 lanes: 1 = left, 0 = right, -1 = outside the segment.  All
    # compares/arithmetic run wide (int32) — Mosaic has no 8-bit vector
    # math — and cast to int8 only at the MXU operands.
    m = mask_ref[...].astype(jnp.int32)                    # [1, block]
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (win, block), 0)
    lt = (iota_s < jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 1)).astype(jnp.int8)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (R, win), 1)
    pane = seg_ref[0]                                      # [R, block] int8

    for p in (0, 1):
        mi = (m == 1 - p).astype(jnp.int32)                # [1, block]
        used = jnp.sum(mi)
        # exclusive prefix sum over lanes as a strict-lower matmul
        pos = jax.lax.dot_general(
            mi.astype(jnp.int8), lt,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)              # [1, block]
        # compact + shift in ONE one-hot matmul: source lane s lands at
        # window lane pos[s] + shift
        base = start + p * plcnt + offs_ref[p]             # pane lane
        p0 = (base // 128) * 128                           # aligned window
        shift = base - p0
        sel = ((jnp.broadcast_to(pos, (win, block)) + shift == iota_t)
               & jnp.broadcast_to(mi == 1, (win, block))).astype(jnp.int8)
        shifted = jax.lax.dot_general(
            pane, sel, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)              # [R, win] i32
        # RMW: read the aligned window, blend lanes [shift, shift+used)
        dma_in = pltpu.make_async_copy(
            out_ref.at[written, :, pl.ds(p0, win)], win_ref, sem_ref)
        dma_in.start()
        dma_in.wait()
        keep = ((lane_w >= shift) & (lane_w < shift + used)).astype(
            jnp.int32)
        blended = (shifted * keep
                   + win_ref[...].astype(jnp.int32) * (1 - keep))
        win_ref[...] = blended.astype(jnp.int8)
        dma_out = pltpu.make_async_copy(
            win_ref, out_ref.at[written, :, pl.ds(p0, win)], sem_ref)
        dma_out.start()
        dma_out.wait()
        offs_ref[p] = offs_ref[p] + used


def _partition_kernel_overlap(scal_ref, mask_ref, seg_ref, out_ref,
                              winl_ref, winr_ref, offs_ref,
                              seml_ref, semr_ref, *, R, block):
    """Grid (nblocks,): both streams per lane block, window DMAs
    OVERLAPPED.

    The serialized kernel round-trips through HBM between the streams
    (in-L → out-L → in-R → out-R) because the right window's read must
    see the left window's write wherever their 128-aligned RMW paddings
    overlap.  Here both window READS issue up front (each sees pre-step
    HBM bytes) and overlap the selection matmuls; the left write-back
    flies under the right stream's compute; and the right blend patches
    this block's fresh left lanes VMEM-side — a third one-hot matmul
    places the SAME left rows at their right-window coordinates — so it
    never needs the post-left-write HBM state.  Only the two write-backs
    stay ordered (their paddings can carry differing bytes; the merged
    right window must win).  Bit-identical output to the serialized
    kernel by construction: the fresh lane ranges are disjoint and every
    patched byte equals what the HBM round-trip would have returned."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        offs_ref[0] = 0
        offs_ref[1] = 0

    written = 1 - scal_ref[1]
    start = scal_ref[2]
    plcnt = scal_ref[3]
    win = block + 128

    m = mask_ref[...].astype(jnp.int32)                    # [1, block]
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (win, block), 0)
    lt = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
          < jax.lax.broadcasted_iota(
              jnp.int32, (block, block), 1)).astype(jnp.int8)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (R, win), 1)
    pane = seg_ref[0]                                      # [R, block] int8

    base_l = start + offs_ref[0]                           # pane lanes
    base_r = start + plcnt + offs_ref[1]
    p0l = (base_l // 128) * 128
    p0r = (base_r // 128) * 128

    def window(p0):
        return out_ref.at[written, :, pl.ds(p0, win)]

    # both RMW window reads start immediately and fly under the matmuls;
    # neither depends on the other stream's write
    in_l = pltpu.make_async_copy(window(p0l), winl_ref, seml_ref)
    in_l.start()
    in_r = pltpu.make_async_copy(window(p0r), winr_ref, semr_ref)
    in_r.start()

    def stats(p):
        mi = (m == 1 - p).astype(jnp.int32)                # [1, block]
        used = jnp.sum(mi)
        pos = jax.lax.dot_general(
            mi.astype(jnp.int8), lt,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)              # [1, block]
        return mi, used, pos

    def place(mi, used, pos, shift):
        """Land stream rows at window lanes pos + shift (negative shifts
        simply match no lane: rows below the window never select)."""
        sel = ((jnp.broadcast_to(pos, (win, block)) + shift == iota_t)
               & jnp.broadcast_to(mi == 1, (win, block))).astype(jnp.int8)
        shifted = jax.lax.dot_general(
            pane, sel, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)              # [R, win] i32
        keep = ((lane_w >= shift) & (lane_w < shift + used)).astype(
            jnp.int32)
        return shifted, keep

    mi_l, used_l, pos_l = stats(0)
    mi_r, used_r, pos_r = stats(1)
    shifted_l, keep_l = place(mi_l, used_l, pos_l, base_l - p0l)
    # the SAME left rows at their RIGHT-window coordinates: the VMEM-side
    # merge operand for wherever [base_l, base_l+used_l) intersects the
    # right window (whose HBM read predates the left write)
    merged_l, keep_lr = place(mi_l, used_l, pos_l, base_l - p0r)
    shifted_r, keep_r = place(mi_r, used_r, pos_r, base_r - p0r)

    in_l.wait()
    blended_l = (shifted_l * keep_l
                 + winl_ref[...].astype(jnp.int32) * (1 - keep_l))
    winl_ref[...] = blended_l.astype(jnp.int8)
    # the right read may cover lanes the left write is about to touch:
    # it must have landed before that write starts
    in_r.wait()
    out_l = pltpu.make_async_copy(winl_ref, window(p0l), seml_ref)
    out_l.start()
    # right blend (overlapping the left write-back): right rows where
    # they land, this block's fresh left rows where THEY land, pre-step
    # HBM bytes everywhere else.  keep_r and keep_lr are disjoint — all
    # fresh left lanes precede start + plcnt <= base_r.
    patched = (merged_l * keep_lr
               + winr_ref[...].astype(jnp.int32) * (1 - keep_lr))
    blended_r = shifted_r * keep_r + patched * (1 - keep_r)
    winr_ref[...] = blended_r.astype(jnp.int8)
    # ordered write-backs: overlapping aligned paddings may carry
    # differing bytes (stale left-window tail vs merged right window) —
    # the right window's bytes must win
    out_l.wait()
    out_r = pltpu.make_async_copy(winr_ref, window(p0r), semr_ref)
    out_r.start()
    out_r.wait()

    offs_ref[0] = offs_ref[0] + used_l
    offs_ref[1] = offs_ref[1] + used_r


def _partition_kernel_rows(scal_ref, mask_ref, seg_ref, out_ref, sel_ref,
                           winl_ref, winr_ref, offs_ref, seml_ref,
                           semr_ref, *, rows, block, overlap):
    """Grid (lane blocks, row blocks), row blocks innermost: the pane of
    a wide table, ``rows`` pane rows at a time (partition_grid).

    What depends on the mask alone is done at a lane block's FIRST row
    block and kept for the others: the two prefix counts, the stream
    lengths (SMEM, beside the running offsets) and the one-hot selection
    matrices (VMEM: left rows at the left window's lanes, right rows at
    the right window's and, for the overlapped schedule, the left rows
    at the right window's).  Every row block then lands its rows by the
    same one-hots through its own row range of the same two windows, in
    the schedule of the one-block kernels above (``overlap`` or
    serialized); the offsets advance at the LAST row block.  Row blocks
    touch disjoint rows of the output, so the one-block kernels' ordering
    arguments hold within each row block as they stand."""
    j = pl.program_id(0)
    r = pl.program_id(1)

    @pl.when((j == 0) & (r == 0))
    def _():
        offs_ref[0] = 0
        offs_ref[1] = 0

    written = 1 - scal_ref[1]
    start = scal_ref[2]
    plcnt = scal_ref[3]
    win = block + 128
    base_l = start + offs_ref[0]                           # pane lanes
    base_r = start + plcnt + offs_ref[1]
    p0l = (base_l // 128) * 128
    p0r = (base_r // 128) * 128
    shift_l = base_l - p0l
    shift_r = base_r - p0r

    @pl.when(r == 0)
    def _():
        m = mask_ref[...].astype(jnp.int32)                # [1, block]
        iota_t = jax.lax.broadcasted_iota(jnp.int32, (win, block), 0)
        lt = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
              < jax.lax.broadcasted_iota(
                  jnp.int32, (block, block), 1)).astype(jnp.int8)

        def stats(p):
            mi = (m == 1 - p).astype(jnp.int32)
            pos = jax.lax.dot_general(
                mi.astype(jnp.int8), lt,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)          # [1, block]
            offs_ref[2 + p] = jnp.sum(mi)
            return mi, pos

        def onehot(mi, pos, shift):
            return ((jnp.broadcast_to(pos, (win, block)) + shift == iota_t)
                    & jnp.broadcast_to(mi == 1, (win, block))).astype(
                        jnp.int8)

        mi_l, pos_l = stats(0)
        mi_r, pos_r = stats(1)

        @pl.when(offs_ref[2] + offs_ref[3] > 0)
        def _():
            sel_ref[0] = onehot(mi_l, pos_l, shift_l)
            sel_ref[1] = onehot(mi_r, pos_r, shift_r)
            if overlap:
                sel_ref[2] = onehot(mi_l, pos_l, base_l - p0r)

    used_l = offs_ref[2]
    used_r = offs_ref[3]

    # a lane block with no lane of the segment lands nothing (the
    # bucketed range is up to twice the segment): its windows are left
    # alone, so a pass costs the segment's lanes and not its bucket's
    @pl.when(used_l + used_r > 0)
    def _():
        row0 = pl.multiple_of(r * rows, 32)
        lane_w = jax.lax.broadcasted_iota(jnp.int32, (rows, win), 1)
        pane = seg_ref[0]                                      # [rows, block]

        def place(k, shift, used):
            shifted = jax.lax.dot_general(
                pane, sel_ref[k], dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)              # [rows, win]
            keep = ((lane_w >= shift) & (lane_w < shift + used)).astype(
                jnp.int32)
            return shifted, keep

        def window(p0):
            return out_ref.at[written, pl.ds(row0, rows), pl.ds(p0, win)]

        if overlap:
            in_l = pltpu.make_async_copy(window(p0l), winl_ref, seml_ref)
            in_l.start()
            in_r = pltpu.make_async_copy(window(p0r), winr_ref, semr_ref)
            in_r.start()
            shifted_l, keep_l = place(0, shift_l, used_l)
            merged_l, keep_lr = place(2, base_l - p0r, used_l)
            shifted_r, keep_r = place(1, shift_r, used_r)
            in_l.wait()
            blended_l = (shifted_l * keep_l
                         + winl_ref[...].astype(jnp.int32) * (1 - keep_l))
            winl_ref[...] = blended_l.astype(jnp.int8)
            in_r.wait()
            out_l = pltpu.make_async_copy(winl_ref, window(p0l), seml_ref)
            out_l.start()
            patched = (merged_l * keep_lr
                       + winr_ref[...].astype(jnp.int32) * (1 - keep_lr))
            blended_r = shifted_r * keep_r + patched * (1 - keep_r)
            winr_ref[...] = blended_r.astype(jnp.int8)
            out_l.wait()
            out_r = pltpu.make_async_copy(winr_ref, window(p0r), semr_ref)
            out_r.start()
            out_r.wait()
        else:
            for k, p0, shift, used in ((0, p0l, shift_l, used_l),
                                       (1, p0r, shift_r, used_r)):
                shifted, keep = place(k, shift, used)
                dma_in = pltpu.make_async_copy(window(p0), winl_ref, seml_ref)
                dma_in.start()
                dma_in.wait()
                blended = (shifted * keep
                           + winl_ref[...].astype(jnp.int32) * (1 - keep))
                winl_ref[...] = blended.astype(jnp.int8)
                dma_out = pltpu.make_async_copy(winl_ref, window(p0), seml_ref)
                dma_out.start()
                dma_out.wait()

    @pl.when(r == pl.num_programs(1) - 1)
    def _():
        offs_ref[0] = offs_ref[0] + used_l
        offs_ref[1] = offs_ref[1] + used_r


def partition_overlap_on() -> bool:
    """Resolved DMA-overlap schedule bit (the
    LGBM_TPU_PARTITION_NO_OVERLAP=1 A/B hatch).  Resolved OUTSIDE every
    jit boundary — partition_segment's non-jitted wrapper reads it per
    call/trace, and the program-cache key builders (gbdt/learners)
    include it so a mid-process flip retraces instead of silently
    reusing the other schedule's kernel."""
    from .. import hatches
    return not hatches.flag("LGBM_TPU_PARTITION_NO_OVERLAP")


def pane_layout(rows: int, width: int, block: int = BLOCK):
    """(stored rows, stored lanes) of one side of the two-sided pane that
    holds ``rows`` plane rows over ``width`` lanes (``width`` a multiple of
    ``block``: the root's bucket).  The rows are padded to whole row
    blocks of the kernel's grid, so a ragged last block reads and writes
    rows that exist; the lanes by two lane blocks, so that the block
    after the widest range and a window (a lane block and 128) that
    starts at the last lane lie inside the array, and the array is whole
    blocks of the blocked read (the TPU interpreter pads an operand whose
    last block is ragged and hands the aliased output the padded
    shape)."""
    lanes, height, count = partition_grid(rows, block)
    return height * count, width + 2 * lanes


def range_origin(pane, start, width: int, block: int = BLOCK):
    """``(cs, lanes)``: the pane lane at which a split of the range that
    starts at ``start`` and lies in a bucket of ``width`` lanes is read,
    and how many lanes from there its mask covers.  ``cs`` is ``start``
    rounded down to 128 lanes (the tile a dynamic lane offset must keep:
    the blocked read is indexed by elements, not by whole lane blocks, so
    the range starts within a tile of its first block and does not
    straddle one lane block more than it has to) and clamped so that the
    bucket ends inside the root's; the mask is one lane block longer than
    the bucket, because a range that starts after ``cs`` may end after
    ``cs + width``."""
    lanes = partition_grid(pane.shape[1], block)[0]
    root_width = pane.shape[2] - 2 * lanes
    cs = jnp.minimum(start // 128 * 128, root_width - width)
    return cs.astype(jnp.int32), width + lanes


def partition_segment(pane, mask3, side, start, cnt, plcnt, *, width: int,
                      block: int = BLOCK, use_pallas: bool = False,
                      interpret: bool = False, overlap: bool = True):
    """Stable partition of a leaf's lane range, inside the pane.

    pane : [2, rows', lanes'] int8, two sides of plane rows (``pack_planes``)
    mask3 : [lanes] int8 over the pane lanes ``[cs, cs + lanes)`` of
        ``range_origin(pane, start, width)`` — 1 = goes left, 0 = goes
        right, -1 = outside the range
    side : i32 scalar, 0 or 1 — the side the range is read from; the
        children are written to the other
    start, cnt, plcnt : i32 scalars — the range's first pane lane, its lane
        count, and the number of mask3==1 lanes
    width : the range's bucket (static), a multiple of ``block``

    Returns the pane with, on side ``1 - side``, lanes [start, start+plcnt)
    holding the left rows in original relative order and [start+plcnt,
    start+cnt) the right rows.  Every other byte of both sides is what it
    was: the Pallas call aliases the pane to its output, reads the
    parent's lane blocks where they lie (a blocked read offset by the
    prefetched ``cs``) and lands the children through its read-modify-
    write windows where they will lie, so nothing is sliced out of the
    pane and nothing written back.  Two sides because the right stream
    runs ahead of the read: partitioned onto itself a range would
    overwrite lanes it has not read yet.

    ``overlap`` (Pallas path only): overlapped window DMAs (default; the
    serialized schedule remains as the A/B reference and the
    LGBM_TPU_PARTITION_NO_OVERLAP=1 escape hatch).  Both schedules are
    bit-identical — tests/test_leafcompact.py's regression proves it
    against the oracle.

    This wrapper is deliberately NOT jitted: the env hatch must resolve
    per call/trace, and a jitted body would bake the first resolution
    into the trace cache (jit-under-jit reuses the traced jaxpr without
    re-running the python body, so an env flip would be ignored even
    when the OUTER program retraces).
    """
    from .. import costmodel, telemetry
    if use_pallas:
        overlap = overlap and partition_overlap_on()
    telemetry.count("partition/pallas" if use_pallas else "partition/xla")
    if use_pallas:
        telemetry.count("partition/dma_overlap" if overlap
                        else "partition/dma_serial")
    if costmodel.enabled():
        # analytic per-pass cost (the Pallas kernel is a custom call XLA
        # cost analysis cannot see into): the range is read and written
        # once per partition pass — plus the selection matmuls' MACs
        # (R x W x lane-block one-hot contractions; 3 per block
        # overlapped, 2 serialized)
        R = pane.shape[1]
        lanes = partition_grid(R, block)[0]
        costmodel.note_traced_pass(
            "partition", ("pane", R, width, lanes, bool(use_pallas),
                          bool(overlap)),
            bytes_moved=2.0 * R * width,
            macs=float(R) * width * lanes * (3 if overlap else 2))
    with telemetry.span("partition") as sp:
        return sp.fence(_partition_in_pane_jit(
            pane, mask3, side, start, cnt, plcnt, width=width, block=block,
            use_pallas=use_pallas, interpret=interpret, overlap=overlap))


def _partition_in_pane_fn(pane, mask3, side, start, cnt, plcnt, *, width,
                          block, use_pallas, interpret, overlap):
    # unconditional named_scope: profile_dir= traces label the kernel /
    # oracle ops "partition", matching the telemetry span and JSONL phase
    # key whether or not telemetry is armed (ISSUE 2 profiler alignment)
    with jax.named_scope("partition"):
        cs, lanes = range_origin(pane, start, width, block)
        assert mask3.shape == (lanes,), (mask3.shape, lanes)
        if use_pallas:
            return _partition_call(pane, mask3, side, cs, start, plcnt,
                                   block, overlap, interpret)
        return _partition_oracle(pane, mask3, side, cs, start, cnt)


# jitted + wrapped in the cost registry: standalone (eager) partition
# calls — tests, micro-benchmarks — self-report compile seconds and
# memory analysis; under an outer trace the wrapper passes through.  The
# function's name is what the trace knows the kernels by: their custom
# calls lie under ``partition/jit(_partition_in_pane_fn)``
# (benchmarks/metrics/partition_kernel_ms_per_iter.json).
from .. import costmodel as _costmodel_mod  # noqa: E402

_partition_in_pane_jit = _costmodel_mod.instrument(
    "partition/kernel",
    jax.jit(_partition_in_pane_fn,
            static_argnames=("width", "block", "use_pallas", "interpret",
                             "overlap")),
    phase="partition")


def _partition_call(pane, mask3, side, cs, start, plcnt, block, overlap,
                    interpret):
    """One Pallas call over the pane itself: the pane is the blocked
    operand the parent's lanes are read through (``cs`` and ``side``
    prefetched into the index map) and, aliased, the HBM output the
    windows land in.  Which of the three kernels is ``partition_grid``'s
    to say, from the pane's rows."""
    from .. import telemetry
    lanes, rows, nrb = partition_grid(pane.shape[1], block)
    assert pane.shape[1] == rows * nrb, (pane.shape, rows, nrb)
    # trace-time, like hist/pallas_fblocks: row blocks of the grids of
    # the partition kernels traced (1 a kernel on a narrow table), and
    # the kernels that read and write the pane itself
    telemetry.count("partition/pallas_rblocks", nrb)
    telemetry.count("partition/in_pane")
    win = lanes + 128
    nblocks = mask3.shape[0] // lanes
    # prefetched: the first lane read in tiles of 128, the side read, the
    # range's first lane and the left stream's length
    scal = jnp.stack([cs // 128, side, start, plcnt]).astype(jnp.int32)
    tall = lanes != block or nrb > 1
    if tall:
        kernel = functools.partial(_partition_kernel_rows, rows=rows,
                                   block=lanes, overlap=overlap)
        grid = (nblocks, nrb)
    else:
        kernel = functools.partial(
            _partition_kernel_overlap if overlap else _partition_kernel,
            R=rows, block=lanes)
        grid = (nblocks,)

    def lane_block(j, *at):
        # grid indices, then the prefetched scalars: (r, s) or (s,); the
        # block's first element along each axis (pl.Element), so that the
        # read may start on any tile of 128 lanes
        *r, s = at
        return (s[1], (r[0] * rows if r else 0),
                pl.multiple_of(s[0] * 128 + j * lanes, 128))
    in_specs = [pl.BlockSpec((1, lanes), lambda j, *_: (0, j)),
                pl.BlockSpec((pl.Element(1), pl.Element(rows),
                              pl.Element(lanes)), lane_block)]
    # the held one-hots (row-blocked kernel), one RMW window a stream in
    # flight with its semaphore, the running offsets (and stream lengths)
    windows = 2 if overlap or tall else 1
    scratch = (
        [pltpu.VMEM((3 if overlap else 2, win, lanes), jnp.int8)] * tall
        + [pltpu.VMEM((rows, win), jnp.int8)] * windows
        + [pltpu.SMEM((4 if tall else 2,), jnp.int32)]
        + [pltpu.SemaphoreType.DMA(())] * windows)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=PANE_SPACE),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(pane.shape, jnp.int8),
        # operand 2 (after the scalars and the mask) is output 0: the
        # windows are read from and written to the pane's own storage
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
        interpret=interpret,
    )(scal, mask3[None, :], pane)


def _partition_oracle(pane, mask3, side, cs, start, cnt):
    """The XLA oracle of the same contract: stable sort by class (left 0,
    right 1, outside 2) puts left+right compacted at the FRONT of the
    sorted range; rolling by ``start - cs`` aligns them with the range's
    true position; lanes outside it keep the written side's bytes."""
    _, rows, _ = pane.shape
    lanes = mask3.shape[0]
    zero = jnp.int32(0)
    read = jax.lax.dynamic_slice(pane, (side, zero, cs), (1, rows, lanes))
    kept = jax.lax.dynamic_slice(pane, (1 - side, zero, cs),
                                 (1, rows, lanes))
    lane = cs + jnp.arange(lanes, dtype=jnp.int32)
    inseg = (lane >= start) & (lane < start + cnt)
    keys = jnp.where(mask3 == 1, 0, jnp.where(mask3 == 0, 1, 2))
    order = jnp.argsort(keys, stable=True)
    permuted = jnp.roll(jnp.take(read, order, axis=2), start - cs, axis=2)
    return jax.lax.dynamic_update_slice(
        pane, jnp.where(inseg[None, None, :], permuted, kept),
        (1 - side, zero, cs))


def pane_rows(num_features: int) -> int:
    """Plane-pane row count: F bin rows + 8 grad/hess bit-plane rows +
    validity, padded to the int8 sublane tile (Mosaic requires slices
    along the sublane dim to be 8-aligned)."""
    r = num_features + 9
    return -(-r // 8) * 8


def pack_planes(bins, grad, hess, row_mask, width: int,
                block: int = BLOCK) -> jax.Array:
    """The pane a tree starts from: ``[2, rows', lanes']`` int8
    (``pane_layout`` of ``pane_rows(F)`` rows over ``width`` lanes).  Side
    0 holds the root's planes: bin rows, grad/hess as 4 int8 bit-planes
    each (bit-exact f32 transport through the int8 selection matmul),
    validity; everything else is zeros, side 1 too.  A split reads its
    parent from one side and writes the children into the same lanes of
    the other, so side 1 is read where nothing was written yet only as a
    window's padding and as the masked lanes of a bucketed histogram
    range: zeros are finite gradients, what uninitialised memory need not
    be.  Written in place into the zeros (the table's rows once, the nine
    value rows once), not concatenated and padded: three passes over the
    pane's bytes fewer a tree."""
    F, N = bins.shape
    values = []
    for v in (grad, hess):
        u = jax.lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
        for k in range(4):
            values.append(jax.lax.bitcast_convert_type(
                ((u >> (8 * k)) & 0xFF).astype(jnp.uint8), jnp.int8))
    values.append(row_mask.astype(jnp.int8))
    pane = jnp.zeros((2,) + pane_layout(pane_rows(F), width, block),
                     jnp.int8)
    pane = pane.at[0, :F, :N].set(jax.lax.bitcast_convert_type(
        bins.astype(jnp.uint8), jnp.int8))
    return pane.at[0, F:F + 9, :N].set(jnp.stack(values))


def unpack_values(pane_slice, F: int):
    """(bins uint8 [F, W], grad f32 [W], hess f32 [W], valid bool [W])
    from a plane-pane slice."""
    bins = jax.lax.bitcast_convert_type(pane_slice[:F], jnp.uint8)

    def f32_of(rows):
        u = jnp.zeros(pane_slice.shape[1:], jnp.uint32)
        for k in range(4):
            b = jax.lax.bitcast_convert_type(rows[k], jnp.uint8)
            u = u | (b.astype(jnp.uint32) << (8 * k))
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    grad = f32_of(pane_slice[F:F + 4])
    hess = f32_of(pane_slice[F + 4:F + 8])
    valid = pane_slice[F + 8] == 1
    return bins, grad, hess, valid


def bucket_table(n: int, block: int = BLOCK, min_width: int = 0):
    """Descending static slice widths W_0 > W_1 > ... >= max(block,
    min_width): W_0 covers the root, each next is ceil(W/2) rounded up to a
    block multiple (so a physically-smaller child of a bucket-k parent
    always fits bucket k+1)."""
    w = -(-n // block) * block
    floor_w = max(block, -(-min_width // block) * block)
    table = [w]
    while table[-1] > floor_w:
        w = -(-(table[-1] // 2) // block) * block
        table.append(max(w, floor_w))
    return tuple(table)
