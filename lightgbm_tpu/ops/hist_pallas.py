"""Pallas TPU histogram kernel — the hot loop, hand-scheduled.

The XLA one-hot-einsum formulation (ops/histogram.py) runs at ~70% of MXU
peak and cannot use the int8 MXU path.  This kernel owns the schedule:

- grid over row-chunks; the [F, B, K] accumulator lives in VMEM across the
  whole grid (written back to HBM once), so HBM traffic is the int8 bin
  matrix + a packed int8 side-band — nothing else.  All row-aligned inputs
  are LANE-major or lane-packed: a [N, small] f32 buffer would be
  tile-padded to 128 lanes in HBM (128 bytes/row of traffic), so grad,
  hess, mask and column id travel as ONE packed [N, 4] int8 array;
- per feature, the bin one-hot [chunk, B] is generated in VMEM by an iota
  compare (never touches HBM) and contracted on the MXU
  (sublane-contracting dot_general) against the column-expanded value
  block [chunk, K].  A matmul pass costs the rows of the operand that
  STREAMS times the 128-wide tiles of the operand the MXU HOLDS, and only
  the held one is padded to whole tiles: with the one-hot streamed, 192
  value lanes (64 leaf columns) pay 256 rows x 2 tiles, a quarter of it
  on 64 lanes of zeros.  So the unfolded integer passes turn the product
  round where that is fewer units (``held_onehot``): the live value rows
  stream against the one-hot's two tiles, 192 x 2 at 64 columns and
  96 x 2 at 32, into the transposed accumulator, which the wrapper
  transposes back;
- a pass with few leaf columns FOLDS the bin code (``hist_fold``): the
  low log2(k) bits of the bin move out of the one-hot into k copies of
  the few live value rows, so the VPU builds ceil(B / k) + k * 3 * cols
  operand rows per feature instead of B (56 instead of 256 at the root)
  and the MXU contracts that much less.  Building those rows, not the
  matmul, is what an integer pass of up to 128 lanes costs; the int32
  sums land in the same cells, so the histograms are bit-identical at
  every fold.  The float mode folds by the same rule (PR 35): its
  unfolded one-column pass was MXU-bound on 256 one-hot rows against 128
  value lanes of which 5 carried sums; folded, every cell's addends meet
  at the same places of the same contraction, every other term an exact
  zero, and the f32 sums are the unfolded pass's bit for bit, on the
  CPU's interpreter and on a v5e alike (PERF.md section 6, PR 35);
- ``dtype="int8"`` is the quantized-gradient variant: stochastically /
  nearest-rounded int8 grad/hess, int8xint8->int32 MXU at 2x the bf16
  rate, exact int32 counts — modern LightGBM's quantized-training idea
  recast for a systolic array (the reference's double accumulators,
  bin.h:15-17, sit at the other end of this precision spectrum).

Layout contract: bins [F, N] int8, packed values [4, N] int8 (gq, hq, ok,
cid), output hist [C, F, B, 3] f32 after dequantization; or, for the
level-wise grower where the per-bin count can refuse no split, [3, N]
(gq, hq, the row's slot) beside each leaf column's target slot, output
[C, F, B, 2].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

# default value-operand width, one MXU tile: 42 leaf columns x 3 stats + 2.
# With the one-hot streamed a pass pays for B one-hot rows whatever part
# of the 128 lanes is live; hist_fold moves bin bits into the idle part
# when 16 columns or fewer are (9 of the float32 pair's five statistics),
# held_onehot streams the live part of an integer pass alone.
LANES = 128

# int8 histogram row ceiling: a histogram cell accumulates int8 values in
# an int32, and a cell's magnitude is bounded by 127 x rows-in-cell —
# saturated at iteration 0 of binary logloss, where hessians are uniform
# and every row quantizes to exactly 127; a constant (single-bin) feature
# then concentrates ALL rows into one cell.  More rows than 2^31/127 can
# therefore wrap ONE accumulator, so no accumulator of any route sums
# more: a longer table is cut into ``accum_ranges`` row ranges with an
# int32 accumulator each, combined exactly as an integer pair
# (``range_sum``), and an int-domain psum across shards, which sums every
# shard's accumulator into one int32 range, rides the same pair.  A route
# that does not range refuses (``check_int8_row_capacity``).
INT8_HIST_MAX_ROWS = (1 << 31) // 127


def accum_ranges(rows: int, chunk: int = 1) -> int:
    """THE rule: int32 accumulators a sum over ``rows`` rows takes,
    ceil(rows / INT8_HIST_MAX_ROWS) with a range held to whole chunks of
    ``chunk`` rows.  1 up to 16.9M rows: the program every table under
    the cap always ran."""
    per_range = INT8_HIST_MAX_ROWS // chunk * chunk
    assert per_range > 0, (INT8_HIST_MAX_ROWS, chunk)
    return max(1, -(-rows // per_range))


def check_int8_row_capacity(num_rows: int, route: str = "this route") -> None:
    """Refuse int8 histograms beyond ONE int32 accumulator's capacity on
    a route that does not cut its rows into ranges (silent wraparound
    would corrupt every split).  The histogram routes of
    ``histogram_leafbatch`` range (the Pallas kernel and the XLA int
    formulation, serial and under the data-parallel learners' int
    reductions) and never call this."""
    if accum_ranges(num_rows) > 1:
        from ..utils import log
        log.fatal(
            "hist_dtype=int8: %s sums all its rows into one int32 "
            "accumulator and supports at most %d rows (127 x rows can "
            "wrap past 2^31 when rows concentrate in one bin); got %d "
            "rows.  The histogram routes of task=train (Pallas and XLA "
            "int, serial and data-parallel) cut their rows into ranges "
            "and have no such limit"
            % (route, INT8_HIST_MAX_ROWS, num_rows))


def range_sum(acc):
    """[ranges, ...] int32 accumulators -> their exact sum as an integer
    pair (hi, lo), hi * 65536 + lo, each an int32 sum of the ranges'
    halves (not carried: lo may pass 65535, so pairs add, and psum, like
    plain integers, up to 32,768 ranges and shards in all)."""
    with jax.named_scope("range_sum"):
        return (jnp.sum(acc >> 16, axis=0),
                jnp.sum(acc & 0xFFFF, axis=0))


def pair_to_f32(hi, lo):
    """The float32 nearest hi * 65536 + lo: the carried high half is
    exact in float32 up to 2^40 (8.6 billion rows of 127), its product
    with 65536 exact, and the one addition rounds once.  A function of
    the sum alone: however the rows were cut into ranges and shards, the
    same float32."""
    with jax.named_scope("range_sum"):
        hi = hi + (lo >> 16)
        lo = lo & 0xFFFF
        return (hi.astype(jnp.float32) * jnp.float32(65536.0)
                + lo.astype(jnp.float32))


def _reduce_int(acc, keep, paired, int_reduce, axis_name, pallas,
                stats=3):
    """An int accumulator ([F, B, K], or [ranges, F, B, K] of a ranged
    pass) summed across its ranges and shards: float32
    [F, B, keep], the live value columns.  One range in all: the int32
    sum itself, reduced in the int domain as it always was, then cast.
    More: the integer pair, each half reduced by the same collective, so
    what a shard contributes is exact whatever the other shards hold and
    serial == data-parallel stays bit for bit.  ``stats=2``: beside it
    the hessian columns' integer sums modulo 2^32, int32 [F, B, keep / 2]
    (``hess_sums``)."""
    from .. import telemetry
    if paired and acc.ndim == 3:
        acc = acc[None]            # one range a shard, several shards
    halves = range_sum(acc[..., :keep]) if paired else (acc,)
    if int_reduce is not None:
        # ownership schedule: psum_scatter the INT accumulators by feature
        # block (feature axis 0) — still int-domain, still bit-exact
        halves = tuple(int_reduce(h) for h in halves)
    elif axis_name is not None:
        # reduce the INT accumulators across shards: dequantize-then-psum
        # would round (sum of 8 f32 products != int-sum x scale) and break
        # the bit-identical serial == data-parallel invariant
        telemetry.record_collective(
            "hist/int8_pallas_psum" if pallas else "hist/int8_xla_psum",
            "psum", axis_name, telemetry._tree_nbytes(halves))
        halves = tuple(jax.lax.psum(h, axis_name) for h in halves)
    hist = (pair_to_f32(*halves) if paired
            else halves[0][..., :keep].astype(jnp.float32))
    if stats == 3:
        return hist
    return hist, hess_sums(halves, keep, stats)


def hess_sums(halves, keep: int, stats: int):
    """The hessian columns' integer sums modulo 2^32 (int32 arithmetic
    wraps): ``hi * 65536 + lo`` of a pair, the int32 sum itself
    otherwise.  Exact where the float32 sums are not: a sibling derived
    by subtracting these is 0 exactly in a bin none of its rows holds,
    where the float32 difference keeps its ancestors' rounding."""
    h = [x[..., 1:keep:stats] for x in halves]
    return (h[0] << 16) + h[1] if len(h) == 2 else h[0]


def _dequantized(hist, B: int, C: int, stats: int, scale):
    """``_reduce_int``'s [F, B, C * stats] sums -> [C, F, B, stats]
    float32; with two statistics the pair (float32 [C, F, B, 2], the
    hessian's integer sums [C, F, B])."""
    if stats == 3:
        return hist.reshape(-1, B, C, 3).transpose(2, 0, 1, 3) * scale
    hist, hsum = hist
    return (hist.reshape(-1, B, C, 2).transpose(2, 0, 1, 3) * scale,
            hsum.reshape(-1, B, C).transpose(2, 0, 1))


def _ranged_rows(N: int, chunk: int, axis_name=None):
    """(ranges, rows as padded, paired) of one shard's pass over ``N``
    rows in chunks of ``chunk``: the ranges balanced, each of whole
    chunks; ``paired`` where the ranges, or the shards of the
    reduction's axis together, pass one accumulator's rows."""
    from .. import telemetry
    n_chunks = -(-N // chunk)
    ranges = accum_ranges(n_chunks * chunk, chunk)
    padded = ranges * -(-n_chunks // ranges) * chunk
    shards = 1 if axis_name is None else jax.lax.axis_size(axis_name)
    # counted per pass at trace time, like hist/pallas_fblocks
    telemetry.count("hist/accum_ranges", ranges)
    return ranges, padded, accum_ranges(padded * shards, chunk) > 1


def _hist_kernel(bins_ref, packed_ref, *refs, stats=3,
                 skip_dead=False, row_axis=1, **static):
    # grid = (feature_blocks, row_chunks), rows minor: each feature
    # block's accumulator lives in VMEM across its whole row sweep and is
    # written back to HBM once.  A ranged pass puts the ranges between
    # the two, (feature_blocks, ranges, row_chunks of a range): the
    # accumulator lives across ONE range's sweep and every range writes
    # its own.  ``refs`` is the output, behind the value rows' slot
    # targets where the pass has them
    *tgt_ref, out_ref = refs
    j = pl.program_id(row_axis)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    accumulate = functools.partial(_hist_accumulate, bins_ref, packed_ref,
                                   out_ref, tgt_ref=(tgt_ref or [None])[0],
                                   stats=stats, **static)
    if skip_dead:
        # a chunk with no live row (leaf id -1 throughout) adds zeros to
        # every cell.  The compacted grower hands the kernel a leaf's
        # range at a bucketed width, up to twice the leaf's rows: with
        # the dead chunks skipped a pass costs the leaf's rows and not
        # its bucket's (PERF.md section 6, PR 34)
        live = jnp.max(packed_ref[stats:stats + 1, :].astype(
            jnp.float32)) >= 0.0
        pl.when(live)(accumulate)
    else:
        accumulate()


def _hist_accumulate(bins_ref, packed_ref, out_ref, *, F, B, chunk, lanes,
                     compute_dtype, acc_dtype, stats=3, fold=1, gw=None,
                     held=0, tgt_ref=None):
    """One chunk of rows added into the feature block's accumulator."""
    # pure arithmetic (no jnp.where): Mosaic cannot relayout replicated
    # boolean vectors.  VPU math runs wide (8-bit vector arithmetic is
    # unsupported) and casts to compute_dtype only for the MXU operands.
    # Everything is LANE-major ([*, chunk]); the value block vL is built
    # TRANSPOSED [lanes, chunk] so the contraction is an NT-form matmul,
    # whichever of the two operands comes first.
    # ``stats`` values interleave per leaf column (3 = grad/hess/count;
    # 2 = grad/hess; 5 = the f32 single-pass hi/lo packing
    # g_hi,g_lo,h_hi,h_lo,count).  A row joins the value rows whose leaf
    # column its id names, or, with ``tgt_ref`` ([vrows, 1] int32), those
    # whose target its id equals.
    wide = jnp.int32 if compute_dtype == jnp.int8 else jnp.float32
    # bin fold (``fold`` > 1): bin = hi * fold + lo.  ``hi`` keeps a
    # one-hot of B / fold rows; ``lo`` picks one of ``fold`` groups of
    # ``gw`` value rows, so cell (hi, lo * gw + jj) of the product is cell
    # (hi * fold + lo, jj) of the unfolded histogram: the same products
    # summed into the same cell.  The VPU then builds B / fold + fold * gw
    # operand rows per feature where the unfolded kernel builds B, and
    # that build, not the MXU, sets the pace up to 128 lanes.
    vrows = held or (lanes if fold == 1 else fold * gw)
    jrow = jax.lax.broadcasted_iota(jnp.int32, (vrows, chunk), 0)
    if fold == 1:
        jj = jrow
    else:
        lo_j = jrow // gw
        jj = jrow - gw * lo_j
    leaf_j = jj // stats
    k_j = jj - stats * leaf_j
    # packed may be int8 (quantized levels) or bf16 (float values); both
    # convert exactly to ``wide`` (int levels <= 127, cid <= 191 — small
    # integers are exact in f32, so the cid equality compare is safe)
    packed = packed_ref[...].astype(wide)           # [stats + 1, chunk]
    terms = None
    for k in range(stats):
        vk = ((k_j == k).astype(wide)
              * jnp.broadcast_to(packed[k:k + 1, :], (vrows, chunk)))
        terms = vk if terms is None else terms + vk
    cidb = jnp.broadcast_to(packed[stats:stats + 1, :], (vrows, chunk))
    want = (leaf_j if tgt_ref is None
            else jnp.broadcast_to(tgt_ref[...], (vrows, chunk)))
    lmask = (cidb == want.astype(wide)).astype(wide)
    vL = terms * lmask                              # [vrows, chunk]
    if fold == 1:
        vLt = vL.astype(compute_dtype)

    shift = fold.bit_length() - 1
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (B // fold, chunk), 0)
    dn = (((1,), (1,)), ((), ()))                           # contract chunk
    for f in range(F):
        # bins ride as int8 bit-patterns; values >= 128 (uint8 source,
        # max_bin up to 256) wrap negative on the cast, so mask back
        # (int8-domain compares don't compile in Mosaic)
        brow = bins_ref[f:f + 1, :].astype(jnp.int32) & 255  # [1, chunk]
        if fold > 1:
            vLt = ((lo_j == (brow & (fold - 1))).astype(wide)
                   * vL).astype(compute_dtype)
            brow = brow >> shift
        oh = (iota_b == brow).astype(compute_dtype)     # [B/fold, chunk]
        # the first operand streams, the MXU holds the second.  Held, the
        # one-hot is B rows = whole 128-wide tiles and the ``held`` live
        # value rows stream into the transposed accumulator: the same
        # products into the same cells
        streamed, kept = (vLt, oh) if held else (oh, vLt)
        out_ref[f] += jax.lax.dot_general(
            streamed, kept, dimension_numbers=dn,
            preferred_element_type=acc_dtype)   # [B/fold, vrows] | [vrows, B]


def _hist_pallas_raw_fn(bins, packed, targets=None, *, B: int,
                        chunk: int = 2048, dtype: str = "int8",
                        lanes: int = LANES, stats: int = 3, fold: int = 1,
                        gw: int = None, held: int = 0,
                        skip_dead: bool = False, ranges: int = 1):
    """[F, B, lanes] accumulator from [F, N] bins and packed values
    ([F, B, gw] from a folded float pass; [ranges, F, B, lanes] from a
    ranged integer pass).

    Rows must be pre-padded to a multiple of ``chunk`` (pad cid with -1).
    packed is [stats + 1, N]: ``stats`` values per leaf column followed by
    the cid row (stats=3: grad, hess, ok; stats=2: grad, hess; stats=5:
    the f32 hi/lo packing g_hi, g_lo, h_hi, h_lo, ok).  A row joins leaf
    column ``cid``, or with ``targets`` ([C] int32, the integer modes) the
    column whose target equals its ``cid`` (``row_targets``).  Three
    dtype modes:
      "int8"  — packed int8 quantized levels, int8xint8->int32 MXU;
      "bf16"  — the SAME int8 levels riding bf16 operands (integers <= 127
                are bf16-exact), bit-identical histograms to "int8";
      "bf16v" — packed is BFLOAT16 carrying FLOAT grad/hess values
                (not quantized levels), f32 MXU accumulation.  This is the
                float-gradient variant: per-value bf16 precision instead of
                a shared int8 scale, and — being hand-scheduled — immune to
                XLA einsum-lowering regressions (BASELINE.md round 3).
    ``bins`` may carry uint8 bit-patterns (the kernel masks the
    sign-extension back off).  ``lanes`` widens the value operand past one
    MXU tile (192 holds 64 leaf columns in one pass over the data; as the
    operand the MXU holds they are two tiles, as the one it streams 192
    rows).  ``fold`` > 1 (a power of two, with ``gw`` >= the live
    stats * columns; ``hist_fold`` picks both) runs the bin-folded kernel
    on a [F, ceil(B / fold), fold * gw] accumulator and unfolds it.
    ``held`` > 0 (a multiple of 32 holding the live stats * columns;
    ``held_onehot`` picks it, for unfolded passes) turns the contraction
    round: ``held`` value rows stream against the one-hot, held as whole
    128-wide tiles, into a [F, held, B up to whole tiles] accumulator that
    is transposed back and zero-padded.  Either way the result is the same
    [F, B, lanes] array, bit for bit, for the integer-level modes;
    ``fold=1, held=0`` is the kernel as it always was.  "bf16v" folds and
    does not turn round, and a folded "bf16v" pass hands back the unfolded
    [F, B, gw] view, the live columns with no pad behind them (its one
    consumer, ``_hist_float_one``, takes the leading stats * columns of
    either shape): the same f32 sums bit for bit, measured on a v5e
    (``hist_fold``).
    ``skip_dead`` passes over a chunk with no live row (cid
    -1 throughout): the caller's to ask for, where its rows end in such
    chunks (the compacted grower's bucketed ranges); the same sums.
    ``ranges`` > 1 (``_ranged_rows`` picks it, for the integer modes past
    ``INT8_HIST_MAX_ROWS`` rows; N a multiple of ranges * chunk) cuts the
    row sweep into that many equal ranges on a grid axis of their own,
    each with its own int32 accumulator: the same kernel body, zeroed at
    a range's first chunk, and no copy of a row.  ``ranges=1`` is the
    two-axis grid as it always was.

    Wide datasets ride a FEATURE-BLOCK grid axis: each block of Fb
    features sweeps the rows in turn with its [Fb, B, lanes] accumulator
    resident in VMEM (the row side-band is re-read per block — F/Fb x a
    few MB of HBM, noise next to the matmuls).  Fb comes from
    ``rotating_feature_block``, which counts the two buffers of the
    rotating window as Mosaic lays them out, so every F lowers inside the
    16 MiB a kernel may hold: compiled for a described v5e at F = 2,000
    for every pass of a 255-leaf level-wise tree
    (tests/test_tpu_compile.py), with 255 bins 48 features a block at 128
    lanes and at 192 lanes 32 with the one-hot held, 24 with it streamed.
    ``feature_block`` and fewer features run as ONE block.
    """
    from .. import telemetry
    telemetry.count("hist/pallas_kernel_" + dtype)
    F, N = bins.shape
    assert N % (ranges * chunk) == 0 and packed.shape == (stats + 1, N)
    compute_dtype = jnp.int8 if dtype == "int8" else jnp.bfloat16
    acc_dtype = jnp.int32 if dtype == "int8" else jnp.float32
    if dtype == "bf16v":
        assert packed.dtype == jnp.bfloat16 and ranges == 1, packed.dtype
    fb, n_fblocks = feature_grid(F, B, lanes, chunk, held, ranges)
    if n_fblocks * fb > F:
        bins = jnp.pad(bins, ((0, n_fblocks * fb - F), (0, 0)))
    operands = [bins, packed]
    if targets is not None:
        assert dtype == "int8", dtype
        operands.append(row_targets(targets, stats, held or (
            lanes if fold == 1 else fold * gw), gw if fold > 1 else None))
    if held:
        assert dtype != "bf16v" and fold == 1
        assert stats <= held <= lanes and held % 32 == 0
        Bk = B + (-B) % LANES            # the held operand: whole tiles
        out_block = (fb, held, Bk)
    elif fold == 1:
        Bk = B
        out_block = (fb, B, lanes)
    else:
        assert fold & (fold - 1) == 0
        assert stats <= gw and fold * gw <= lanes
        Bh = -(-B // fold)
        Bk = Bh * fold
        out_block = (fb, Bh, fold * gw)
    kernel = functools.partial(
        _hist_kernel, F=fb, B=Bk, chunk=chunk,
        lanes=lanes, compute_dtype=compute_dtype, acc_dtype=acc_dtype,
        stats=stats, fold=fold, gw=gw, held=held,
        skip_dead=skip_dead, row_axis=1 if ranges == 1 else 2)
    out_shape = (n_fblocks * out_block[0],) + out_block[1:]
    if ranges == 1:
        grid = (n_fblocks, N // chunk)
        in_specs = [
            pl.BlockSpec((fb, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((stats + 1, chunk), lambda i, j: (0, j)),
        ]
        out_specs = pl.BlockSpec(out_block, lambda i, j: (i, 0, 0))
        const = lambda i, j: (0, 0)                 # noqa: E731
    else:
        per = N // chunk // ranges           # chunks of one range
        grid = (n_fblocks, ranges, per)
        in_specs = [
            pl.BlockSpec((fb, chunk), lambda i, r, j: (i, r * per + j)),
            pl.BlockSpec((stats + 1, chunk),
                         lambda i, r, j: (0, r * per + j)),
        ]
        out_specs = pl.BlockSpec((None,) + out_block,
                                 lambda i, r, j: (r, i, 0, 0))
        out_shape = (ranges,) + out_shape
        const = lambda i, r, j: (0, 0)              # noqa: E731
    if targets is not None:
        in_specs.append(pl.BlockSpec(operands[2].shape, const))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(out_shape, acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid)),
    )(*operands)
    if ranges > 1:
        # the views below are the one-range kernel's, over the feature
        # axis and behind it: the ranges ride stacked on that axis
        out = out.reshape((-1,) + out_shape[2:])
    if held or fold > 1:
        # the transpose of the held one-hot's [held, B], or the unfold,
        # cell (hi, lo * gw + jj) -> (hi * fold + lo, jj)
        out = (jnp.swapaxes(out, 1, 2) if held
               else out.reshape(-1, Bh * fold, gw))[:, :B]
        if dtype != "bf16v":
            # value columns zero-padded back to ``lanes``.  The layout
            # constraint hands the integer consumers what the plain
            # kernel's custom call would, a row-major array: without it
            # XLA lays the float histograms out after the narrow
            # accumulator and the split search's sums round in another
            # order (same ints, trees that differ in the last place of a
            # gain).  The pad itself fuses away.  The float consumer
            # takes the ``gw`` columns as they are: padded and pinned
            # they were 264 MB a pass at 2,000 features for XLA to pick
            # five lanes out of (PERF.md section 6, PR 35)
            out = with_layout_constraint(
                jnp.pad(out, ((0, 0), (0, 0), (0, lanes - out.shape[2]))),
                Layout(major_to_minor=(0, 1, 2)))
    if ranges > 1:
        out = out.reshape((ranges, -1) + out.shape[1:])[:, :F]
    else:
        out = out[:F]
    if dtype in ("int8", "bf16v"):
        return out                       # int32 / f32 accumulator as-is
    return out.astype(jnp.int32)


# jitted + wrapped in the cost registry: a STANDALONE (eager) call of the
# Pallas kernel — micro-benchmarks, tests — self-reports its compile cost
# and memory analysis; inside a traced grower program the wrapper passes
# straight through and the kernel inlines as before (cost analysis cannot
# see into the custom call either way — the analytic MAC counts ride
# costmodel.note_traced_pass from the histogram routing layer instead)
from .. import costmodel as _costmodel  # noqa: E402

hist_pallas_raw = _costmodel.instrument(
    "hist/pallas_raw",
    jax.jit(_hist_pallas_raw_fn,
            static_argnames=("B", "chunk", "dtype", "lanes", "stats", "fold",
                             "gw", "held", "skip_dead", "ranges")),
    phase="histogram")


# the target of a value row that sums no rows: row ids are slots >= 0, or
# -1 for a row outside the pass
NO_TARGET = -2


def row_targets(targets, stats: int, vrows: int, gw: int = None):
    """[vrows, 1] int32: the row id each value row of the kernel's
    operand sums, from ``targets`` [C], one a leaf column: value row r
    holds statistic ``jj % stats`` of column ``jj // stats``, ``jj`` = r,
    or r mod ``gw`` in a folded pass, and rows past the C columns take
    ``NO_TARGET``."""
    C = targets.shape[0]
    col = [r % (gw or vrows) // stats for r in range(vrows)]
    t = jnp.take(targets.astype(jnp.int32),
                 jnp.asarray([min(c, C - 1) for c in col], jnp.int32))
    live = jnp.asarray([c < C for c in col])
    return jnp.where(live, t, NO_TARGET).reshape(vrows, 1)


# what one kernel may hold of a v5e's VMEM (Mosaic's default scoped limit;
# the compiler refuses a kernel whose windows need more), and the part of
# it kept free of windows for the kernel's own temporaries: the value
# block and a one-hot in both widths, [256, chunk] rows at most
VMEM_SCOPED_BYTES = 16 << 20
VMEM_TEMPORARIES_BYTES = 2 << 20
# most features a block of a pass with the one-hot held, whatever fits:
# the kernel's feature loop is unrolled, and past the 48 a streamed
# 128-lane pass takes a longer one buys less than it costs to compile (on
# a v5e at [2000, 401,408], 96 value rows: 147.0 ms a pass and 15.7 s of
# compile at 72 a block, 148.2 ms and 9.8 s at 48; PERF.md, PR 31)
HELD_BLOCK_FEATURES = 48


def feature_block(B: int, lanes: int) -> int:
    """Most features that run as ONE block, the output window constant
    across the grid: the largest multiple of 8 (sublane tile) whose
    [Fb, B, lanes] int32/f32 accumulator is 12 MB or less.  Not a VMEM
    account (at 192 lanes the 64 features it gives are 16 MiB as laid
    out); it is the rule the single-block kernels were measured under,
    kept so that they stay the same programs: 64 features at 192 lanes
    and 96 at 128 compile for a v5e (255 and 256 bins).  Wider tables
    take ``rotating_feature_block``."""
    fb = (12 << 20) // (B * lanes * 4)
    return max(8, fb - fb % 8)


def held_onehot(stats: int, num_cols: int, B: int, lanes: int,
                dtype: str) -> int:
    """Value rows a pass STREAMS with the one-hot as the operand the MXU
    holds (the transposed accumulator), or 0 for the kernel as it always
    ran, the one-hot streamed and the value block held: every pass that
    ``hist_fold`` folds, and of the unfolded ones those below.  From the
    pass's static shapes: a matmul pass costs (rows streamed) x (128-wide
    tiles of the held operand), and only the held operand is padded to
    whole tiles.  Streamed one-hot: B rows (up to the 32-row int8 tile)
    x ceil(lanes / 128) tiles of value rows, two at 192 lanes whatever
    part of them is live.  Held one-hot: the live stats * num_cols value
    rows (up to the 32-row tile) x ceil(B / 128) tiles.  This takes the
    held one-hot where it is fewer units: at 255 bins 64 columns are
    192 x 2 against 256 x 2 and 32 columns 96 x 2 against 256 x 1, while
    33-42 columns (128 x 2) and a 64-bin class of the mixed-bin layout
    (192 x 1 against 64 x 2) keep the streamed one.  Measured on a v5e at
    [28, 10.5M], the kernel alone (PERF.md section 6, PR 31): 64 columns
    104.4 -> 78.5 ms, the dot alone 78.0 and the int8 peak's floor for
    384 units 73.5; 43 columns (160 rows) 104.4 -> 65.9; 32 columns,
    which the VPU's build of the one-hot bounds, 57.3 -> 53.6; 21 columns
    (64 rows) 57.3 -> 50.0; 42 columns (128 rows against two tiles) 57.3
    -> 57.6 had it turned, the 64-bin class 29.8 -> 41.7.  Only the
    integer-level modes, whose sums are exact and order-free: turned
    round, a "bf16v" cell's addends would meet in another operand's
    order, which nobody has held against the streamed one on a chip
    (PERF.md section 7, PR 31 (d)); its passes fold or stay as they
    were."""
    if dtype == "bf16v" or hist_fold(stats, num_cols, B, lanes)[0] > 1:
        return 0
    rows = stats * num_cols + (-stats * num_cols) % 32
    if rows * -(-B // LANES) < (B + (-B) % 32) * -(-lanes // LANES):
        return rows
    return 0


def feature_grid(F: int, B: int, lanes: int, chunk: int, held: int = 0,
                 ranges: int = 1):
    """(features per block, blocks) of one pass over F features."""
    if ranges == 1 and F <= feature_block(B, lanes):
        # single block: the output window is constant across the grid, so
        # Mosaic keeps ONE VMEM copy (the round-2 kernel ran exactly this
        # shape)
        return F, 1
    # multi-block: the output window rotates with grid axis i, which
    # Mosaic DOUBLE-BUFFERS; so it does with the ranges of a ranged
    # pass, whose one block is what the rotating account lets fit.
    # Blocks are balanced: with 48 a block
    # (B=256, lanes=128), 100 features run as 3 x 40 (20 pad) instead of
    # 48+48+48 (44 pad) — padded features cost full matmul passes
    rotating = rotating_feature_block(B, lanes, chunk, held)
    if ranges > 1 and F <= rotating:
        return F, 1
    n_fblocks = -(-F // rotating)
    fb = -(-F // n_fblocks)
    return fb + (-fb) % 8, n_fblocks          # sublane-tile multiple


def rotating_feature_block(B: int, lanes: int, chunk: int,
                           held: int = 0) -> int:
    """Most features a block when the table is wider than one block: the
    output window then rotates with the feature axis of the grid and
    Mosaic keeps TWO buffers of it, like of every operand.  Counted as
    laid out in VMEM (``T(8, 128)`` tiles of 4-byte cells: the
    accumulator's rows up to a multiple of 8 sublanes, its columns up to
    whole 128-lane tiles), per feature an accumulator and a [chunk] row
    of bin codes, twice each, beside the two buffers of the packed
    side-band (its stats + 1 rows fill one 32-sublane int8 tile, or two
    16-sublane bf16 ones) and the kernel's temporaries, under
    ``VMEM_SCOPED_BYTES``.  The accumulator is [B, lanes], 192 lanes laid
    out as 256, or with the one-hot held (``held_onehot``) [held, B], the
    ``held`` value rows that stream and 255 bins laid out as 256.  At 255
    bins and chunk 2048: 48 features at 128 lanes (12.4 MiB of windows);
    at 192 lanes 24 as [B, lanes] (12.2 MiB; the 32 that B * lanes * 4
    bytes a feature allowed were 16.12 MiB, which the TPU compiler
    refused at F = 300, 700 and 2,000); held, 32 features at 192 value
    rows (12.2 MiB again: 196,608 bytes a feature where the other layout
    takes 262,144) and, 72 fitting, ``HELD_BLOCK_FEATURES`` at 96.
    Raising the kernel's own
    ``vmem_limit_bytes`` instead buys 1% (on a v5e at [2000, 401,408],
    one-hot streamed: 293.1 ms a 192-lane pass at 24 a block, 290.2 at 32
    under 32 MiB, 353.1 at 64 under 48 MiB; PERF.md, PR 30).  The fold's
    narrower accumulator is not counted: a folded pass takes the unfolded
    block."""
    rows, cols = (held, B) if held else (B, lanes)
    acc = (rows + (-rows) % 8) * (cols + (-cols) % 128) * 4
    room = VMEM_SCOPED_BYTES - VMEM_TEMPORARIES_BYTES - 2 * 32 * chunk
    fb = room // (2 * (acc + chunk))
    if held:
        fb = min(fb, HELD_BLOCK_FEATURES)
    return max(8, fb - fb % 8)


def fold_options(stats: int, num_cols: int, B: int, lanes: int):
    """Every (fold, gw, operand rows per feature) the folded kernel's
    layout allows: at least one int8 sublane tile (32 rows) of one-hot,
    a value block of whole 8-row sublane groups (``gw`` is the live width
    stats * num_cols rounded up to a multiple of 8 / fold) that stays one
    MXU tile wide (fold * gw <= lanes)."""
    for fold in (2, 4, 8):
        hi_rows = -(-B // fold)
        step = 8 // fold
        gw = -(-stats * num_cols // step) * step
        if hi_rows >= 32 and fold * gw <= lanes:
            yield fold, gw, hi_rows + fold * gw


def hist_fold(stats: int, num_cols: int, B: int, lanes: int):
    """(fold, gw) of one histogram pass, from its static shapes.

    A pass with few leaf columns leaves most of the value operand's lanes
    idle, and up to 128 lanes the kernel's pace is the VPU's operand
    build (measured on a v5e at [28, 10.5M]: 3.6 ms + 0.20 ms per operand
    row built per feature and chunk, PERF.md section 6).  The fold moves
    the low log2(fold) bits of the bin code into ``fold`` groups of ``gw``
    value rows, so the kernel builds ceil(B / fold) + fold * gw rows per
    feature where fold 1, the unfolded kernel, builds B.  This picks the
    fold with the fewest rows (the larger on a tie: a smaller
    accumulator) if it saves an eighth of them or more, else fold 1.
    Every mode folds alike: the integer modes' sums are exact and
    order-free, and the float mode's ("bf16v": 3 statistics a column, or
    the float32 pair's 5, whose one-column pass folds by 8 into a
    [32, 40] accumulator a feature where it filled 5 lanes of
    [256, 128]) keep their order, because cell
    (hi, lo * gw + jj) of the folded product receives the products that
    cell (hi * fold + lo, jj) of the unfolded one receives, at the same
    positions of the same chunk-long contraction, every other term an
    exact zero, and chunks are added in the same grid order.  So it
    was on a v5e: folded and unfolded accumulators equal cell for cell
    at [28, 10,502,144] and [2000, 401,408], one column of five
    statistics, and at one, two and four columns of three and of five
    (``scripts/hist_kernel_bench.py --float-fold``; PERF.md section 6,
    PR 35), 100.6 -> 14.8 and 276.0 -> 39.6 ms a pass."""
    best, best_rows = (1, None), B - B // 8
    if lanes == LANES:
        for fold, gw, rows in fold_options(stats, num_cols, B, lanes):
            if rows <= best_rows:
                best, best_rows = (fold, gw), rows
    return best


def _mix32(x):
    """murmur3-style integer finalizer (public-domain mixing constants):
    a stateless uint32 hash good enough to decorrelate rounding noise."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def stochastic_bits(x, other, salt):
    """Deterministic per-element uniform bits for stochastic rounding,
    keyed on the (grad, hess) VALUE PAIR of the row and a per-use
    ``salt``.  Value-keyed means no row-position plumbing: the same
    physical row carries the same gradient bits in serial, sharded and
    multi-process programs alike — regardless of row position in the
    padded layouts — so the serial == distributed bit-identity of the
    int8 histograms survives, and the key varies per boosting iteration
    automatically because the gradients do.  Rows sharing the exact
    (grad, hess) pair round identically (iteration 0's uniform hessians
    are the worst case — but there grad/hess quantize near-exactly by
    construction of the max scale); from iteration 1 on the
    score fan-out makes the pairs effectively unique per row."""
    ix = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    io = jax.lax.bitcast_convert_type(other.astype(jnp.float32),
                                      jnp.uint32)
    return _mix32(ix ^ _mix32(io)
                  ^ _mix32(jnp.uint32(salt) + jnp.uint32(0x9E3779B9)))


def quant_max_of(grad, hess, row_ok, axis_name=None):
    """[2] f32: max |grad| and max |hess| over the participating rows,
    what the int8 scales are taken from.

    The scale must come from PARTICIPATING rows only: multi-process
    phantom padding rows can carry arbitrary score-residual gradients
    (their scores still accumulate leaf values) and would inflate the
    scale, collapsing quantization resolution and breaking the
    serial == distributed bit-identity.

    ``axis_name``: under shard_map, pmax over the data axis so every
    shard quantizes identically — int32 accumulation is then order-free,
    making data-parallel histograms BIT-identical to serial (the
    quantized analog of the reference's every-worker-identical-split
    invariant, data_parallel_tree_learner.cpp:237-243).

    The growers take it ONCE A TREE, over the tree's rows, and hand it to
    every pass (``quant_max``): a row then rounds to the same code in the
    root's pass, in its level's and in its leaf's, so a sibling derived
    by subtraction is the histogram a pass of its own would have built.
    With a scale a pass (PERF.md section 6, PR 36) every re-quantized row
    left its two roundings' difference in the derived histogram: in the
    first trees, where the rows share a few hundred values, whole leaves
    flip together, and a candidate with a handful of rows on one side
    saw sums of hundreds where the rows' own are under one."""
    okf = row_ok.astype(jnp.float32)
    m = jnp.stack([jnp.max(jnp.abs(grad) * okf),
                   jnp.max(jnp.abs(hess) * okf)])
    if axis_name is not None:
        from .. import telemetry
        telemetry.record_collective("hist/quant_scale_pmax", "pmax",
                                    axis_name, telemetry._tree_nbytes(m))
        m = jax.lax.pmax(m, axis_name)
    return m


def quantize_values(grad, hess, col_ok, col_id, rng_bits=None,
                    axis_name=None, stochastic=False, salt=0, quant_max=None,
                    stats=3):
    """The int8 kernels' value operand: ``[4, N]`` int8 codes of grad and
    hess under one global scale each, the count row (``col_ok``) and the
    row's leaf column (``col_id``, -1 where not ``col_ok``), with the
    scale ``[3]`` f32 that dequantizes the three sums (the count row is
    exact by construction); with ``stats=2`` ``[3, N]`` and ``[2]``, no
    count row.  The scale is the tree's (``quant_max``, from
    ``quant_max_of`` over the tree's rows) where the caller hands it, else
    the pass's own, over ``col_ok``'s rows (``axis_name`` as in
    ``quant_max_of``).

    Round-to-nearest by default; unbiased stochastic rounding
    (floor(y+u), u uniform in [0,1)) with ``stochastic=True`` — the
    uniform bits come from a deterministic value-keyed hash
    (``stochastic_bits``), or from explicit ``rng_bits`` [2, N] uint32.

    The rows are ONE elementwise expression over ``[stats + 1, N]``, the
    column id selected in the integer domain, narrowed by one convert: a
    1-D int8 row made apart and stacked after costs a compiled relayout
    loop a row wherever N is no whole number of tiles (PERF.md section 6,
    PR 39: 28 a tree, 140 ms at 115M rows).
    """
    if quant_max is None:
        quant_max = quant_max_of(grad, hess, col_ok, axis_name)
    gs = jnp.maximum(quant_max[0], 1e-30) / 127.0
    hs = jnp.maximum(quant_max[1], 1e-30) / 127.0
    row = jax.lax.broadcasted_iota(jnp.int32, (stats + 1, 1), 0)
    first = row == 0
    y = jnp.where(first, grad, hess) / jnp.where(first, gs, hs)
    if rng_bits is None and not stochastic:
        q = jnp.round(y)
    else:
        if rng_bits is None:
            rng_bits = (stochastic_bits(grad, hess, salt),
                        stochastic_bits(hess, grad, salt + 0x51ED))
        bits = jnp.where(first, rng_bits[0], rng_bits[1])
        u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
        q = jnp.floor(y + u)
    q = jnp.clip(q, -127, 127).astype(jnp.int32)
    cid = col_id.astype(jnp.int32)
    codes = jnp.where(row < 2, q,
                      jnp.where(row == 2, 1, cid) if stats == 3 else cid)
    codes = jnp.where(col_ok, codes, jnp.where(row == stats, -1, 0))
    return (codes.astype(jnp.int8),
            jnp.stack([gs, hs, jnp.float32(1.0)][:stats]))


def quant_saturation_count(grad, hess, axis_name=None):
    """Health gauge: how many grad/hess entries quantize to the ±127
    ceiling under quantize_values' max scale (|x| > 126.5·s with
    s = max|x|/127).  The scale construction pins the max row at 127 by
    design, so a handful of saturated rows is normal; a LARGE count means
    the magnitude distribution has collapsed onto the ceiling — iteration
    0's uniform hessians are the canonical case, and what fills an int32
    accumulator to the row where ``accum_ranges`` starts another.  Kept
    next to quantize_values so the two can never drift.

    Uses the finite global max per channel (the health monitor evaluates
    once per iteration over ALL rows).  A tree's passes quantize with
    the max over the tree's own rows (``quant_max_of``: the bag's, under
    bagging) ≤ this global max, so a tree whose max sits below the
    global one saturates MORE of its entries than the gauge counts —
    read the gauge as a floor, not a ceiling: nonzero means
    at-least-this-much concentration at the representable limit.
    ``axis_name``: pmax the scale across shards before counting, psum the
    count — every shard reports the identical global gauge."""
    f32 = jnp.float32
    total = jnp.zeros((), f32)
    if axis_name is not None:
        from .. import telemetry
        telemetry.record_collective("health/quant_sat_reduce", "psum",
                                    axis_name, 2 * 4)
    for x in (grad, hess):
        ax = jnp.where(jnp.isfinite(x), jnp.abs(x), 0.0)
        m = jnp.max(ax)
        if axis_name is not None:
            m = jax.lax.pmax(m, axis_name)
        sat = jnp.sum((ax * 127.0 > m * 126.5).astype(f32))
        total = total + (jax.lax.psum(sat, axis_name)
                         if axis_name is not None else sat)
    return total


def _grouped(fn, bins, grad, hess, col_id, col_ok, num_cols, B, *,
             group_width=42, targets=None, **kw):
    """Split levels wider than ``group_width`` columns into balanced
    groups (the same rule as ops/histogram.histogram_leafbatch: ceil-split
    so the last group is never a nearly-empty full pass).  42 = one
    128-lane MXU tile (XLA paths); the Pallas kernels take 64 (one pass
    of 192 value rows is cheaper than two passes over the data).

    ``targets`` ([num_cols] int32): column j sums the rows whose id is
    its target, one of the level's new slots 2j and 2j + 1, or none
    where the target is negative.  A group's ids and targets are taken
    relative to its first slot, so that they stay inside the int8 id row
    (slots up to 2 x 64 a group)."""
    if num_cols <= group_width:
        if targets is not None:
            kw["targets"] = targets
        return fn(bins, grad, hess, col_id, col_ok, num_cols, B, **kw)
    n_groups = -(-num_cols // group_width)
    width = -(-num_cols // n_groups)
    parts = []
    for base in range(0, num_cols, width):
        k = min(width, num_cols - base)
        lo, hi, ids = ((base, base + k, col_id - base) if targets is None
                       else (2 * base, 2 * (base + k), col_id - 2 * base))
        ok = col_ok & (col_id >= lo) & (col_id < hi)
        if targets is not None:
            t = targets[base:base + k]
            kw["targets"] = jnp.where(t < 0, NO_TARGET, t - lo)
        parts.append(fn(bins, grad, hess, ids, ok, k, B, **kw))
    return jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *parts)


def _class_acc_assemble(parts, packing, B: int, feat_axis: int = 0):
    """Per-class accumulators (packed feature order, feature axis 0, bin
    axis 1; both one further back behind the ranges of a ranged pass)
    -> ONE canonical-order accumulator padded to B bins.  Stays in
    the accumulator's own domain (int32 for the quantized kernels), so the
    ownership psum_scatter / cross-shard psum that follows operates on
    canonical contiguous feature blocks exactly as in the uniform path —
    the per-class passes ride the EXISTING reduction schedule unchanged.
    ONE implementation (ops/histogram._assemble_classes): the reassembly
    is the bit-identity-critical step, so every kernel route must share
    it."""
    from .histogram import _assemble_classes
    return _assemble_classes(parts, packing, B, feat_axis=feat_axis,
                             bin_axis=feat_axis + 1)


def _packing_on(packing) -> bool:
    from .histogram import _packing_active
    return _packing_active(packing)


def hist_pallas_leafbatch(bins, grad, hess, col_id, col_ok, num_cols: int,
                          num_bins_max: int, *, chunk: int = 2048,
                          dtype: str = "int8", rng_bits=None,
                          axis_name=None, int_reduce=None,
                          stochastic=False, salt=0, packing=None,
                          feat_gather=None, quant_max=None, stats=3,
                          targets=None):
    """Drop-in histogram_leafbatch equivalent on the Pallas kernel.

    ``bins`` is the usual [F, N] matrix (int8 or uint8).  The int32
    accumulator dequantizes to the usual [C, F, B, 3] f32.  Levels up to
    64 columns run as ONE pass (<=42 columns in a 128-lane accumulator,
    43-64 in a 192-lane one, cheaper than two passes over the data);
    wider levels split into 64-column groups.  Passes of 16 columns or
    fewer fold the bin code into the idle value rows (``hist_fold``); the
    unfolded ones of 17-32 and 43-64 columns hold the one-hot in the MXU
    and stream their live value rows (``held_onehot``); the accumulator
    handed on is [F, B, lanes] in every case.

    ``stats=2`` builds grad and hess alone, [C, F, B, 2]: two thirds of
    the value rows, so 64 columns fit one 128-lane tile and every rule
    above reads 2 x C live rows.  ``targets``: see ``_grouped``.

    ``packing`` (mixed-bin layout): one kernel launch per bin-width class
    — the narrow class's [Fc, 64, lanes] accumulator costs a quarter of
    the 255-wide pass in MXU/one-hot work — assembled back into ONE
    canonical int accumulator BEFORE the cross-shard reduction, so the
    int-domain bit-exactness chain and the DP ownership schedule are
    untouched."""
    from .. import telemetry
    # the device name comes from the unconditional named_scope; the span
    # is a host timer only and puts nothing into the program (a scope of
    # its own would make a traced run's kernel read "histogram/histogram"
    # and miss the timed run's compile-cache entry)
    with jax.named_scope("histogram"), telemetry.span("histogram") as sp:
        return sp.fence(_grouped(
            _hist_pallas_one, bins, grad, hess, col_id, col_ok,
            num_cols, num_bins_max, group_width=64, chunk=chunk,
            dtype=dtype, rng_bits=rng_bits, axis_name=axis_name,
            int_reduce=int_reduce, stochastic=stochastic, salt=salt,
            packing=packing, feat_gather=feat_gather,
            quant_max=quant_max, stats=stats, targets=targets))


def _hist_pallas_one(bins, grad, hess, col_id, col_ok, num_cols, B, *,
                     chunk, dtype, rng_bits, axis_name=None,
                     int_reduce=None, stochastic=False, salt=0,
                     packing=None, feat_gather=None, quant_max=None,
                     stats=3, targets=None):
    F, N = bins.shape
    lanes = LANES if stats * num_cols <= LANES else 192
    # ONE quantization for every class pass: the scale comes from the same
    # grad/hess/col_ok whatever the feature layout, so packed and uniform
    # passes quantize identically (bit-identity precondition)
    packed, scale = quantize_values(grad, hess, col_ok, col_id, rng_bits,
                                    axis_name=axis_name,
                                    stochastic=stochastic, salt=salt,
                                    quant_max=quant_max,
                                    stats=stats)  # [stats + 1, N] int8

    ranges, padded, paired = _ranged_rows(N, chunk, axis_name)
    pad = padded - N
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        packed = jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1)
    from .. import telemetry
    telemetry.count("hist/stats_" + str(stats))

    def launch(rows, width):
        fold, gw = hist_fold(stats, num_cols, width, lanes)
        held = held_onehot(stats, num_cols, width, lanes, dtype)
        # counted per pass, here: two passes of one shape share one trace
        # of the jitted kernel, so its own counters see them once
        telemetry.count("hist/pallas_fold_" + str(fold))
        telemetry.count("hist/pallas_held_onehot", int(held > 0))
        telemetry.count("hist/pallas_fblocks",
                        feature_grid(rows.shape[0], width, lanes, chunk,
                                     held, ranges)[1])
        # [F, width, lanes], behind its ranges where the pass is ranged
        return hist_pallas_raw(rows.astype(jnp.int8), packed, targets,
                               B=width, chunk=chunk, dtype=dtype,
                               lanes=lanes, stats=stats, fold=fold, gw=gw,
                               held=held, ranges=ranges)

    fa = int(ranges > 1)                 # the accumulator's feature axis
    if _packing_on(packing):
        telemetry.count("hist/mixedbin_pallas_int")
        parts = [launch(jax.lax.slice_in_dim(bins, start, start + cnt,
                                             axis=0), width)
                 for start, cnt, width in packing.ranges]
        acc = _class_acc_assemble(parts, packing, B, fa)     # [F, B, lanes]
    else:
        acc = launch(bins, B)
    if feat_gather is not None:
        # block-local packing's storage->canonical reorder, IN the int
        # domain and BEFORE the cross-shard reduction: the gather
        # commutes with the elementwise int psum, and the dequantized
        # f32 graph downstream is shape-identical to the uniform
        # layout's (XLA contraction choices cannot diverge — ISSUE 12)
        assert int_reduce is None, \
            "feat_gather does not compose with the ownership int scatter"
        acc = jnp.take(acc, feat_gather, axis=fa)
    hist = _reduce_int(acc, num_cols * stats, paired, int_reduce,
                       axis_name, pallas=True, stats=stats)
    return _dequantized(hist, B, num_cols, stats, scale)


def hist_pallas_float_leafbatch(bins, grad, hess, col_id, col_ok,
                                num_cols: int, num_bins_max: int, *,
                                chunk: int = 2048,
                                precision: str = "bf16", packing=None,
                                skip_dead: bool = False):
    """Float-gradient Pallas histogram — [C, F, B, 3] f32, same contract as
    histogram_leafbatch's einsum formulation but hand-scheduled (and so
    immune to the environment's XLA einsum-lowering regression, BASELINE.md
    round-3 addendum).

    precision="bf16"  (hist_dtype=bfloat16): grad/hess ride as single bf16
      operands — per-value exponents, ~8-bit mantissa, f32 accumulation.
      One pass over the data, the same MXU cost as the int-level kernel's
      bf16 mode.
    precision="f32" (hist_dtype=float32 on TPU): hi/lo bf16 split,
      g = bf16(g) + bf16(g - bf16(g)) — recovers ~16 mantissa bits of the
      f32 operand (vs 24 native; sums accumulate f32 either way, and the
      reference's doubles, bin.h:15-17, sit above both).  Levels up to 38
      columns run as ONE pass with FIVE stats per column (g_hi, g_lo,
      h_hi, h_lo, count — "f32x1"; 25 columns fill a 128-lane tile, 38
      fill 192): measured 2x faster than two 3-stat passes at 8 columns,
      1.4x at 25.  Wider levels run the SAME hi/lo split as TWO 3-stat
      passes over 64-column groups ("f32x2" — equal MXU units there, and
      fewer per-pass overheads than grouped 5-stat).  Both orderings
      accumulate identical per-lane f32 partial sums, so the choice is
      bit-invisible; "f32x1"/"f32x2" force one variant (A/B tests).

    A 128-lane pass of few columns folds the bin code into its idle
    lanes like the integer kernels' (``hist_fold``: up to 16 columns of
    three statistics, 9 of five; the leaf-wise growers' one-column pass
    by 8), the same f32 sums bit for bit.

    Counts are exact in every mode: ok rides as 1.0 (bf16-exact) and the
    lo lanes carry zeros.  ``skip_dead``: see ``_hist_pallas_raw_fn``.
    """
    if precision == "f32":
        precision = "f32x1" if num_cols <= 38 else "f32x2"
    with jax.named_scope("histogram"):
        if precision == "f32x1":
            return _grouped(_hist_float_one, bins, grad, hess, col_id,
                            col_ok, num_cols, num_bins_max, group_width=38,
                            chunk=chunk, precision=precision,
                            packing=packing, skip_dead=skip_dead)
        return _grouped(_hist_float_one, bins, grad, hess, col_id, col_ok,
                        num_cols, num_bins_max, group_width=64, chunk=chunk,
                        precision=precision, packing=packing,
                        skip_dead=skip_dead)


def _bf16_hi(x):
    """The float32 nearest ``x`` that bfloat16 holds exactly: the hi half
    of the hi/lo pair.  A rounding in its own right and not a pair of
    converts, which XLA, allowed excess precision (its default), takes
    for the identity: the lo half ``x - hi`` then carried nothing and the
    float32 histogram summed bfloat16 values (PERF.md section 6,
    PR 34)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _hist_float_one(bins, grad, hess, col_id, col_ok, num_cols, B, *,
                    chunk, precision, packing=None, skip_dead=False):
    from .. import telemetry
    if _packing_on(packing):
        # one kernel launch per bin-width class over the class's feature
        # rows; f32 accumulation is per row-chunk in fixed grid order, so
        # every canonical cell sums in exactly the uniform pass's order
        telemetry.count("hist/mixedbin_pallas_float")
        parts = []
        for start, cnt, width in packing.ranges:
            h = _hist_float_one(
                jax.lax.slice_in_dim(bins, start, start + cnt, axis=0),
                grad, hess, col_id, col_ok, num_cols, width,
                chunk=chunk, precision=precision,
                skip_dead=skip_dead)                     # [C, Fc, w, 3]
            if width < B:
                h = jnp.pad(h, ((0, 0), (0, 0), (0, B - width), (0, 0)))
            parts.append(h)
        packed_h = jnp.concatenate(parts, axis=1)
        return jnp.take(packed_h, jnp.asarray(packing.c2p, jnp.int32),
                        axis=1)
    F, N = bins.shape
    okf = col_ok.astype(jnp.float32)
    g = grad.astype(jnp.float32) * okf
    h = hess.astype(jnp.float32) * okf
    # cid rides the bf16 side-band: small integers (<= 64 after grouping)
    # are bf16-exact, and -1 never matches a lane's leaf id
    cidb = jnp.where(col_ok, col_id, -1).astype(jnp.bfloat16)
    bins8 = bins.astype(jnp.int8)
    pad = (-N) % chunk
    if pad:
        bins8 = jnp.pad(bins8, ((0, 0), (0, pad)))

    def run(vals, lanes):
        packed = jnp.stack([v.astype(jnp.bfloat16) for v in vals]
                           + [cidb], axis=0)
        if pad:
            packed = jnp.pad(packed, ((0, 0), (0, pad)),
                             constant_values=-1)
        fold, gw = hist_fold(len(vals), num_cols, B, lanes)
        # counted per pass, as the int launch counts its own
        telemetry.count("hist/pallas_fold_" + str(fold))
        telemetry.count("hist/pallas_fblocks",
                        feature_grid(F, B, lanes, chunk)[1])
        return hist_pallas_raw(bins8, packed, B=B, chunk=chunk,
                               dtype="bf16v", lanes=lanes,
                               stats=len(vals), fold=fold, gw=gw,
                               skip_dead=skip_dead)  # [F, B, gw | lanes]

    lanes3 = LANES if num_cols <= 42 else 192
    if precision == "bf16":
        acc = run([g, h, okf], lanes3)
    elif precision == "f32x1":
        g_hi, h_hi = _bf16_hi(g), _bf16_hi(h)
        lanes5 = LANES if num_cols <= 25 else 192
        acc5 = run([g_hi, g - g_hi, h_hi, h - h_hi, okf], lanes5)
        w = acc5[:, :, :num_cols * 5].reshape(F, B, num_cols, 5)
        hist = jnp.stack([w[..., 0] + w[..., 1], w[..., 2] + w[..., 3],
                          w[..., 4]], axis=-1)
        return hist.transpose(2, 0, 1, 3)
    elif precision == "f32x2":
        g_hi, h_hi = _bf16_hi(g), _bf16_hi(h)
        acc = (run([g_hi, h_hi, okf], lanes3)
               + run([g - g_hi, h - h_hi, jnp.zeros_like(okf)], lanes3))
    else:
        raise ValueError(f"unknown float-hist precision {precision!r}")
    hist = acc[:, :, :num_cols * 3]
    return hist.reshape(F, B, num_cols, 3).transpose(2, 0, 1, 3)


def hist_quant_xla(bins, grad, hess, col_id, col_ok, num_cols: int,
                   num_bins_max: int, *, chunk: int = 65536, rng_bits=None,
                   axis_name=None, int_reduce=None,
                   stochastic=False, salt=0, packing=None,
                   feat_gather=None, quant_max=None, stats=3, targets=None):
    """XLA reference of the SAME quantized-gradient math as the Pallas int8
    kernel (bit-identical output) — the CPU-testable oracle and the
    fallback on non-TPU backends.  ``packing``: per-class int accumulators
    assembled canonically before the cross-shard reduction, exactly like
    the Pallas route (int32 sums are order-free, so packed == uniform is
    bit-exact here by construction).  ``stats``, ``targets``: see
    ``hist_pallas_leafbatch``."""
    from .. import telemetry
    telemetry.count("hist/xla_int_kernel")
    with jax.named_scope("histogram"), telemetry.span("histogram") as sp:
        return sp.fence(_grouped(
            _hist_quant_xla_one, bins, grad, hess, col_id, col_ok,
            num_cols, num_bins_max, chunk=chunk, rng_bits=rng_bits,
            axis_name=axis_name, int_reduce=int_reduce,
            feat_gather=feat_gather,
            stochastic=stochastic, salt=salt, packing=packing,
            quant_max=quant_max, stats=stats, targets=targets))


def _quant_xla_acc(bins, packed, B: int, C: int, chunk: int,
                   ranges: int = 1, targets=None):
    """One class's raw [F, B, C*stats] int32 accumulator over the kernel's
    operand (``quantize_values``' [stats + 1, N] codes; rows pre-padded,
    with a column id of -1), or its [ranges, F, B, C*stats]: the chunks
    cut into that many equal ranges, each summed from zero."""
    F = bins.shape[0]
    N = bins.shape[1]
    stats = packed.shape[0] - 1
    n_chunks = N // chunk
    bins_c = bins.astype(jnp.int32).reshape(F, n_chunks,
                                            chunk).transpose(1, 0, 2)
    packed = packed.astype(jnp.int32)
    vals_c = packed[:stats].T.reshape(n_chunks, chunk, stats)
    cid_c = packed[stats].reshape(n_chunks, chunk)
    ib = jnp.arange(B, dtype=jnp.int32)
    ic = (jnp.arange(C, dtype=jnp.int32) if targets is None
          else targets.astype(jnp.int32))

    def body(carry, xs):
        bc, vc, cc = xs
        oh = (bc[:, :, None] == ib).astype(jnp.int32)
        lsel = (cc[:, None] == ic).astype(jnp.int32)
        vL = (lsel[:, :, None] * vc[:, None, :]).reshape(chunk, C * stats)
        out = jnp.einsum("fcb,ck->fbk", oh, vL,
                         preferred_element_type=jnp.int32)
        return carry + out, None

    init = jnp.zeros((F, B, C * stats), jnp.int32)
    if ranges == 1:
        hist, _ = jax.lax.scan(body, init, (bins_c, vals_c, cid_c))
        return hist
    _, hist = jax.lax.scan(
        lambda _, xs: (None, jax.lax.scan(body, init, xs)[0]), None,
        jax.tree.map(lambda x: x.reshape((ranges, -1) + x.shape[1:]),
                     (bins_c, vals_c, cid_c)))
    return hist


def _hist_quant_xla_one(bins, grad, hess, col_id, col_ok, num_cols, B, *,
                        chunk, rng_bits, axis_name=None, int_reduce=None,
                        stochastic=False, salt=0, packing=None,
                        feat_gather=None, quant_max=None, stats=3,
                        targets=None):
    from .. import telemetry
    F, N = bins.shape
    C = num_cols
    # don't pad a small input up to a full default chunk
    chunk = min(chunk, max(256, -(-N // 256) * 256))
    packed, scale = quantize_values(grad, hess, col_ok, col_id, rng_bits,
                                    axis_name=axis_name,
                                    stochastic=stochastic, salt=salt,
                                    quant_max=quant_max, stats=stats)
    telemetry.count("hist/stats_" + str(stats))
    ranges, padded, paired = _ranged_rows(N, chunk, axis_name)
    pad = padded - N
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        packed = jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1)

    fa = int(ranges > 1)                 # the accumulator's feature axis
    if _packing_on(packing):
        telemetry.count("hist/mixedbin_xla_int")
        parts = [_quant_xla_acc(
            jax.lax.slice_in_dim(bins, start, start + cnt, axis=0),
            packed, width, C, chunk, ranges, targets)
            for start, cnt, width in packing.ranges]
        hist = _class_acc_assemble(parts, packing, B, fa)  # [F, B, C*k] i32
    else:
        hist = _quant_xla_acc(bins, packed, B, C, chunk, ranges, targets)
    if feat_gather is not None:
        # storage->canonical reorder IN the int domain, before the
        # cross-shard psum (commutes elementwise) — see _hist_pallas_one
        assert int_reduce is None, \
            "feat_gather does not compose with the ownership int scatter"
        hist = jnp.take(hist, feat_gather, axis=fa)
    # int-domain feature scatter or cross-shard sum, then float32
    hist = _reduce_int(hist, C * stats, paired, int_reduce, axis_name,
                       pallas=False, stats=stats)
    return _dequantized(hist, B, C, stats, scale)
