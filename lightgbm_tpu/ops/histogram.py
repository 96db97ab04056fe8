"""Gradient/hessian histogram construction — the hottest kernel.

The reference's hottest loop is a CPU scatter-add over rows
(/root/reference/src/io/dense_bin.hpp:46-112, 4-way unrolled).  TPUs have no
fast scatter; the TPU-native formulation is a ONE-HOT × VALUES matmul on the
MXU:

    H[f*B + b, k] = Σ_rows  onehot(f*B + bin[f, row])[...]  ·  vals[row, k]

with ``vals = [grad, hess, 1] * mask``.  The one-hot is generated on the fly
per row-chunk (lax.scan) so it never lives in HBM at full size, and the
contraction runs over rows with fp32 accumulation (reference accumulates in
double, bin.h:15-17; fp32 + matmul tree-reduction is the deliberate TPU
precision choice).

A ``segment_sum`` backend exists for comparison/testing; matmul is default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import costmodel, hatches, telemetry

# transient one-hot working-set budget (bytes) for the chunked matmul
CHUNK_BYTE_BUDGET = 256 << 20
# virtual (pre-tiling) one-hot budget for the leaf-batched kernel
LEAFBATCH_VIRTUAL_BUDGET = 8 << 30


def _pallas_hist_ok(num_bins_max: int) -> bool:
    """THE Pallas-histogram eligibility rule, shared by the int8 and float
    dispatches: TPU backend and 8-bit bin ids (max_bin > 256 datasets
    carry int16 bins the kernel cannot ride).  Dataset WIDTH is not part
    of the rule: the kernel grids over VMEM-sized feature blocks
    (hist_pallas.feature_grid).  LGBM_TPU_HIST_EINSUM=1 forces the XLA
    formulation for ALL dtypes (A/B timing escape hatch).

    Every outcome is counted (telemetry): routing decisions are trace-time
    events baked into the compiled program, so these counters are the
    runtime record of which kernels the process's programs actually use."""
    if hatches.flag("LGBM_TPU_HIST_EINSUM"):
        telemetry.count("hist/env_force_einsum")
        return False
    # LGBM_TPU_NO_PALLAS covers EVERY Pallas kernel (partition, row
    # routing + these histogram kernels, ops/compact.pallas_partition_ok,
    # ops/route_pallas.route_pallas_ok) — the mixed-backend escape
    # hatch; HIST_EINSUM stays the A/B-timing hatch
    if hatches.flag("LGBM_TPU_NO_PALLAS"):
        telemetry.count("hist/env_no_pallas")
        return False
    ok = jax.default_backend() == "tpu" and num_bins_max <= 256
    telemetry.count("hist/pallas_eligible" if ok else "hist/pallas_ineligible")
    return ok


# ---------------------------------------------------------------------------
# Mixed-bin packing helpers (ISSUE 6).  A PackSpec (io/binning.py) says the
# [F, N] bin matrix is stored with features REORDERED into contiguous
# bin-width classes; every histogram route below then runs one pass per
# class at that class's width and reassembles the canonical feature order
# before anything downstream (split finding, subtraction caches, ownership
# scatters) sees the result.  Reassembly is zero-pad on the bin axis (a
# narrow feature's bins beyond its own num_bin are zero in the uniform
# pass too) + one gather on the feature axis — value-identical to the
# uniform single-pass histogram, cell for cell.


def _packing_active(packing) -> bool:
    return packing is not None and len(packing.widths) > 1


def _assemble_classes(parts, packing, B: int, feat_axis: int, bin_axis: int):
    """Concatenate per-class histograms (packed feature order) and gather
    back to canonical feature order.  ``parts[i]`` carries the class's
    features on ``feat_axis`` and ``widths[i]`` bins on ``bin_axis``."""
    padded = []
    for part, (_, _, width) in zip(parts, packing.ranges):
        if width < B:
            widths = [(0, 0)] * part.ndim
            widths[bin_axis] = (0, B - width)
            part = jnp.pad(part, widths)
        padded.append(part)
    packed = jnp.concatenate(padded, axis=feat_axis)
    c2p = jnp.asarray(packing.c2p, jnp.int32)
    return jnp.take(packed, c2p, axis=feat_axis)


def _unpack_bins(bins, packing):
    """[F, N] packed bin matrix -> canonical feature order (oracle paths:
    one F-row gather buys exact uniform-path semantics for free)."""
    return jnp.take(bins, jnp.asarray(packing.c2p, jnp.int32), axis=0)


def _einsum_chunk(chunk: int, F: int, B: int, itemsize: int, N: int) -> int:
    """The leaf-batched einsum's effective row-chunk resolution rule,
    factored out so the packed driver can pin every per-class pass to the
    UNIFORM pass's chunk boundaries: f32 per-cell sums accumulate across
    scan chunks, so identical chunking is what makes packed == uniform
    bit-identical on the XLA routes (a per-class budget would allow larger
    chunks — smaller F*B — and regroup the adds)."""
    budget_rows = max(LEAFBATCH_VIRTUAL_BUDGET // (F * B * itemsize), 256)
    chunk = min(chunk, -(-budget_rows // 256) * 256)
    return min(chunk, max(256, -(-N // 256) * 256))


def dense_pass_cost(N: int, F: int, B: int, num_cols: int,
                    int_levels: bool = False, stats: int = 3):
    """Analytic cost of ONE leaf-batched histogram pass — the dense
    one-hot-matmul MAC count PROFILE.md's roofline derives by hand
    (N x F x B x lanes per group; the MXU tile floor makes <=42 leaf
    columns cost 128 lanes, 43-64 ride a 192-lane operand; a pass of
    ``int_levels`` that hist_pallas.hist_fold folds contracts
    ceil(B / fold) one-hot rows against fold * gw value rows instead,
    and an unfolded one that hist_pallas.held_onehot turns round B
    one-hot rows against its live value rows alone)
    and the HBM
    bytes streamed (int8 bins + the packed per-row side-band, re-read
    once per group, + the per-group accumulator write-back).  Wider
    levels are modeled on the PALLAS grouping rule — balanced groups of
    <=64 columns (hist_pallas._grouped(group_width=64); the XLA einsum
    fallback groups by 42, but the analytic note exists for the Pallas
    routes cost analysis cannot see into).  ``stats`` value rows a
    column (2 for the int8 passes without the count).  Filed per traced
    pass via costmodel.note_traced_pass."""
    groups = 1 if num_cols <= 64 else -(-num_cols // 64)
    width = -(-num_cols // groups)
    lanes = 128.0 if stats * width <= 128 else 192.0
    cells = float(B) * lanes                 # accumulator cells a feature
    if int_levels and groups == 1:
        from .hist_pallas import held_onehot, hist_fold
        fold, gw = hist_fold(stats, num_cols, B, int(lanes))
        held = held_onehot(stats, num_cols, B, int(lanes), "int8")
        if fold > 1:
            cells = float(-(-B // fold)) * fold * gw
        elif held:
            cells = float(B) * held
    macs = float(N) * F * cells * groups
    bytes_moved = (groups * (float(N) * F + 4.0 * N)
                   + groups * float(F) * cells * 4.0)
    return macs, bytes_moved


def _note_hist_pass(bins, num_cols: int, num_bins_max: int,
                    compute_dtype, packing=None, stats: int = 3) -> None:
    """Analytic roofline note(s) for one leaf-batched pass.  Under mixed-bin
    packing the pass is really one pass PER bin-width class, so one note is
    filed per class (keyed ``binclass<width>``) — PROFILE.md's roofline rows
    then attribute narrow- and wide-class cost separately instead of
    pricing every feature at the uniform worst case."""
    if not costmodel.enabled():
        return
    F, N = bins.shape
    dt = getattr(compute_dtype, "__name__", None) or str(compute_dtype)
    int_levels = dt.startswith("int8")   # float gradients never fold
    if _packing_active(packing):
        for _, cnt, width in packing.ranges:
            macs, bytes_moved = dense_pass_cost(N, cnt, width, num_cols,
                                                int_levels, stats)
            costmodel.note_traced_pass(
                "histogram",
                ("pass", N, cnt, width, num_cols, dt,
                 "binclass%d" % width),
                macs=macs, bytes_moved=bytes_moved)
        return
    macs, bytes_moved = dense_pass_cost(N, F, num_bins_max, num_cols,
                                        int_levels, stats)
    costmodel.note_traced_pass(
        "histogram", ("pass", N, F, num_bins_max, num_cols, dt),
        macs=macs, bytes_moved=bytes_moved)


def _feat_take(hist, feat_gather, axis: int):
    """Apply the traced storage->canonical feature gather (block-local
    mixed-bin packing, ISSUE 12).  For float accumulators the placement
    is free — every cell is a finished sum — and for the quantized paths
    the gather runs IN THE INT DOMAIN inside the kernel drivers
    (ops/hist_pallas), so the dequantize->search f32 graph is
    shape-identical to the uniform layout's and XLA's FMA-contraction
    choices cannot diverge between the two programs."""
    if feat_gather is None:
        return hist
    return jnp.take(hist, feat_gather, axis=axis)


def histogram_matmul(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                     mask: jax.Array, num_bins_max: int,
                     chunk: int = 16384,
                     compute_dtype=jnp.float32, packing=None,
                     feat_gather=None) -> jax.Array:
    """Build per-feature histograms for the masked row subset.

    Parameters
    ----------
    bins : [F, N] integer bin matrix
    grad, hess : [N] float32
    mask : [N] bool/float — row inclusion (leaf membership × bagging)
    num_bins_max : static B (histogram width per feature)

    Returns
    -------
    hist : [F, B, 3] float32 — (sum_grad, sum_hess, count) per bin, matching
    HistogramBinEntry (bin.h:20-42).
    """
    telemetry.count("hist/xla_matmul")
    with telemetry.span("histogram") as sp:
        if _packing_active(packing):
            # one pass per bin-width class; the per-class chunk is pinned
            # to the UNIFORM pass's resolved chunk so the scan's per-cell
            # f32 accumulation groups identically (bit-identity)
            F = bins.shape[0]
            budget_rows = max(
                CHUNK_BYTE_BUDGET // (F * num_bins_max * 4), 256)
            eff_chunk = min(chunk, -(-budget_rows // 256) * 256)
            telemetry.count("hist/mixedbin_matmul")
            parts = []
            for start, cnt, width in packing.ranges:
                parts.append(_histogram_matmul_impl(
                    jax.lax.slice_in_dim(bins, start, start + cnt, axis=0),
                    grad, hess, mask, width, eff_chunk, compute_dtype))
            return sp.fence(_feat_take(_assemble_classes(
                parts, packing, num_bins_max, feat_axis=0, bin_axis=1),
                feat_gather, 0))
        return sp.fence(_feat_take(_histogram_matmul_impl(
            bins, grad, hess, mask, num_bins_max, chunk, compute_dtype),
            feat_gather, 0))


def _histogram_matmul_impl(bins, grad, hess, mask, num_bins_max, chunk,
                           compute_dtype) -> jax.Array:
    # the device name: an UNCONDITIONAL named_scope (telemetry.DEVICE_PHASES).
    # The telemetry span around the caller is a host timer and never
    # enters a scope, so telemetry on/off cannot change the traced
    # program's text or its compile-cache key
    with jax.named_scope("histogram"):
        return _histogram_matmul_scoped(bins, grad, hess, mask,
                                        num_bins_max, chunk, compute_dtype)


def _histogram_matmul_scoped(bins, grad, hess, mask, num_bins_max, chunk,
                             compute_dtype) -> jax.Array:
    F, N = bins.shape
    B = num_bins_max
    # bound the transient one-hot working set ([F, chunk, B] floats) by a
    # byte budget so wide datasets don't OOM; the chunk arg is a ceiling
    budget_rows = max(CHUNK_BYTE_BUDGET // (F * B * 4), 256)
    chunk = min(chunk, -(-budget_rows // 256) * 256)
    maskf = mask.astype(compute_dtype)
    vals = jnp.stack([grad.astype(compute_dtype) * maskf,
                      hess.astype(compute_dtype) * maskf,
                      maskf], axis=1)  # [N, 3]

    if N <= chunk:
        return _onehot_chunk(bins.astype(jnp.int32), vals, B, compute_dtype)

    pad = (-N) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    n_chunks = (N + pad) // chunk
    bins_c = bins.reshape(F, n_chunks, chunk).transpose(1, 0, 2)  # [n, F, C]
    vals_c = vals.reshape(n_chunks, chunk, 3)

    def body(carry, xs):
        b_chunk, v_chunk = xs
        carry = carry + _onehot_chunk(b_chunk.astype(jnp.int32), v_chunk, B,
                                      compute_dtype)
        return carry, None

    # the cross-chunk accumulator stays f32 regardless of compute_dtype:
    # only the matmul OPERANDS are lowered (counts in the thousands are not
    # representable in bf16)
    init = jnp.zeros((F, B, 3), dtype=jnp.float32)
    hist, _ = jax.lax.scan(body, init, (bins_c, vals_c))
    return hist


def _onehot_chunk(bins_chunk: jax.Array, vals_chunk: jax.Array, B: int,
                  compute_dtype) -> jax.Array:
    """One chunk: [F, C] bins + [C, 3] vals -> [F, B, 3] f32 partial
    histogram (operands in compute_dtype, accumulation always f32).

    The einsum contracts over rows; output layout [F*B, 3] keeps the large
    dimension on the MXU lane axis.
    """
    F, C = bins_chunk.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (F, C, B), 2)
    onehot = (bins_chunk[:, :, None] == iota).astype(compute_dtype)  # [F, C, B]
    # [3, C] @ [C, F*B] -> [3, F*B]
    flat = onehot.transpose(1, 0, 2).reshape(C, F * B)
    out = jnp.dot(vals_chunk.astype(compute_dtype).T, flat,
                  preferred_element_type=jnp.float32)  # [3, F*B]
    return out.reshape(3, F, B).transpose(1, 2, 0)


def histogram_leafbatch(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                        col_id: jax.Array, col_ok: jax.Array, num_cols: int,
                        num_bins_max: int, chunk: int = 65536,
                        compute_dtype=jnp.bfloat16,
                        axis_name=None, int_reduce=None,
                        salt=0, packing=None,
                        feat_gather=None,
                        skip_dead: bool = False,
                        quant_max=None, stats: int = 3,
                        targets=None) -> jax.Array:
    """Build histograms for MANY leaves in ONE matmul pass.

    The single-leaf one-hot matmul starves the MXU: the value operand has
    only 3 columns (grad/hess/count) of a 128-wide tile.  Batching C leaves
    widens it to 3·C columns, so one pass over the data builds C histograms
    for (measured) roughly the cost of one — the enabler for the depthwise
    grower, which needs all leaves of a tree level at once instead of the
    reference's one-leaf-at-a-time rebuild (serial_tree_learner.cpp:262-283).

    Parameters
    ----------
    bins : [F, N] integer bin matrix
    grad, hess : [N] f32
    col_id : [N] i32 — histogram column (leaf slot) per row
    col_ok : [N] bool — row participates (bagging mask ∧ slot-is-active)
    num_cols : static C — number of histogram columns

    Returns
    -------
    hist : [C, F, B, 3] f32

    ``packing`` (io/binning.PackSpec, static): mixed-bin layout — ``bins``
    is stored in packed (bin-width-class) feature order; every route below
    runs one pass per class at that class's width and returns the
    CANONICAL-order histogram, value-identical to the uniform pass.
    ``skip_dead``: the caller's rows end in stretches no row of which
    takes part (the compacted grower's bucketed ranges); the float Pallas
    kernel then passes over chunks that are dead throughout.  The same
    sums; the other routes take no notice.
    ``quant_max``: the int8 routes' scale, the tree's own
    (``hist_pallas.quant_max_of``) in place of this pass's.
    ``stats``, ``targets`` (the int8 routes): ``stats=2`` builds
    [C, F, B, 2], grad and hess without the count, and hands it back
    beside the hessian's integer sums, [C, F, B] int32 modulo 2^32
    (``hist_pallas.hess_sums``); with ``targets`` ([C] int32) column j
    sums the rows whose ``col_id`` equals ``targets[j]``
    (``hist_pallas._grouped``).
    """
    int8 = str(compute_dtype).startswith("int8")
    assert int8 or (stats == 3 and targets is None), compute_dtype
    if _packing_active(packing):
        telemetry.count("hist/mixedbin_leafbatch")
    _note_hist_pass(bins, num_cols, num_bins_max, compute_dtype,
                    packing=packing, stats=stats)
    if int8:
        # quantized-gradient path: Pallas int8-MXU kernel on TPU, the
        # bit-identical XLA formulation elsewhere (ops/hist_pallas.py).
        # The Pallas kernel carries bins as int8 bit-patterns, so bin ids
        # must fit 8 bits — max_bin > 256 datasets (int16 bins) take the
        # XLA int formulation instead.  "int8_sr" = unbiased stochastic
        # rounding (value-keyed deterministic bits).
        stochastic = compute_dtype == "int8_sr"
        from .hist_pallas import hist_pallas_leafbatch, hist_quant_xla
        if _pallas_hist_ok(num_bins_max):
            telemetry.count("hist/pallas_int8")
            with telemetry.span("histogram") as sp:
                return sp.fence(hist_pallas_leafbatch(
                    bins, grad, hess, col_id, col_ok, num_cols,
                    num_bins_max, axis_name=axis_name,
                    int_reduce=int_reduce, stochastic=stochastic,
                    salt=salt, packing=packing, feat_gather=feat_gather,
                    quant_max=quant_max, stats=stats, targets=targets))
        telemetry.count("hist/xla_int8")
        with telemetry.span("histogram") as sp:
            return sp.fence(hist_quant_xla(
                bins, grad, hess, col_id, col_ok, num_cols, num_bins_max,
                chunk=chunk, axis_name=axis_name, int_reduce=int_reduce,
                stochastic=stochastic, salt=salt, packing=packing,
                feat_gather=feat_gather, quant_max=quant_max, stats=stats,
                targets=targets))
    # float dtypes on TPU: hand-scheduled Pallas kernel with bf16 operands
    # (f32 rides a hi/lo operand split — one 5-stat pass for narrow
    # levels, two 3-stat passes wider).  This routes AROUND the XLA
    # one-hot-einsum lowering, whose fast path regressed ~27x in this
    # environment (BASELINE.md round-3 addendum) — and is the faster
    # schedule even on a healthy runtime.  Width is handled inside the
    # kernel (VMEM-sized feature-block grid); max_bin > 256 datasets
    # carry int16 bins and stay on the einsum.  axis_name is deliberately
    # NOT handled here: float reductions ride the caller's hist_reduce
    # hook, exactly like the einsum branch below.
    if _pallas_hist_ok(num_bins_max):
        from .hist_pallas import hist_pallas_float_leafbatch
        precision = ("bf16" if compute_dtype == jnp.bfloat16 else "f32")
        telemetry.count("hist/pallas_" + precision)
        with telemetry.span("histogram") as sp:
            return sp.fence(_feat_take(hist_pallas_float_leafbatch(
                bins, grad, hess, col_id, col_ok, num_cols, num_bins_max,
                precision=precision, packing=packing,
                skip_dead=skip_dead), feat_gather, 1))
    telemetry.count("hist/xla_einsum")
    with jax.named_scope("histogram"), telemetry.span("histogram") as sp:
        if _packing_active(packing):
            # per-class einsum passes at the uniform pass's resolved chunk
            # (identical scan grouping -> bit-identical f32 cells)
            eff_chunk = _einsum_chunk(chunk, bins.shape[0], num_bins_max,
                                      jnp.dtype(compute_dtype).itemsize,
                                      bins.shape[1])
            parts = []
            for start, cnt, width in packing.ranges:
                parts.append(_leafbatch_einsum(
                    jax.lax.slice_in_dim(bins, start, start + cnt, axis=0),
                    grad, hess, col_id, col_ok, num_cols, width,
                    chunk=eff_chunk, compute_dtype=compute_dtype))
            return sp.fence(_feat_take(_assemble_classes(
                parts, packing, num_bins_max, feat_axis=1, bin_axis=2),
                feat_gather, 1))
        return sp.fence(_feat_take(_leafbatch_einsum(
            bins, grad, hess, col_id, col_ok, num_cols, num_bins_max,
            chunk=chunk, compute_dtype=compute_dtype), feat_gather, 1))


def _leafbatch_einsum(bins, grad, hess, col_id, col_ok, num_cols: int,
                      num_bins_max: int, chunk: int = 65536,
                      compute_dtype=jnp.bfloat16) -> jax.Array:
    """The XLA one-hot-einsum leaf-batched formulation (CPU / testing
    oracle and the forced-fallback route)."""
    F, N = bins.shape
    B = num_bins_max
    # cap the pass at ONE 128-lane tile of the value operand (42 histogram
    # columns × 3): a C=64 pass costs ~2x what two 42-wide passes do on v5e
    # (the conv-lowered kernel's cost grows superlinearly past a tile), so
    # wide levels loop single-tile groups, balanced so the last group is
    # never a nearly-empty full-row pass (128 -> 4x32, not 42/42/42/2)
    if num_cols > 42:
        n_groups = -(-num_cols // 42)
        width = -(-num_cols // n_groups)
        parts = []
        for base in range(0, num_cols, width):
            k = min(width, num_cols - base)
            ok = col_ok & (col_id >= base) & (col_id < base + k)
            parts.append(_leafbatch_einsum(
                bins, grad, hess, col_id - base, ok, k, num_bins_max,
                chunk=chunk, compute_dtype=compute_dtype))
        return jnp.concatenate(parts, axis=0)
    # keep the value operand >= ~126 columns so the MXU tile is full even
    # for small levels (cols are zero-padded; wasted cols are free compared
    # to a starved tile)
    C = max(num_cols, 42)
    okf = col_ok.astype(jnp.float32)
    vals = jnp.stack([grad.astype(jnp.float32) * okf,
                      hess.astype(jnp.float32) * okf,
                      okf], axis=1)  # [N, 3]

    # big chunks amortize per-scan-iteration launch overhead; small inputs
    # use a single chunk of their own (padded) size.  XLA tiles the one-hot
    # einsum operand rather than materializing [F, chunk, B] (validated at
    # 7.5 GB virtual on a 16 GB chip), but clamp the virtual size anyway so
    # very wide datasets degrade to smaller chunks instead of risking OOM.
    chunk = _einsum_chunk(chunk, F, B, jnp.dtype(compute_dtype).itemsize, N)
    pad = (-N) % chunk
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        col_id = jnp.pad(col_id, (0, pad), constant_values=-1)
    n_chunks = (N + pad) // chunk
    bins_c = bins.astype(jnp.int32).reshape(F, n_chunks, chunk).transpose(1, 0, 2)
    vals_c = vals.astype(compute_dtype).reshape(n_chunks, chunk, 3)
    cid_c = col_id.astype(jnp.int32).reshape(n_chunks, chunk)
    ib = jnp.arange(B, dtype=jnp.int32)
    ic = jnp.arange(C, dtype=jnp.int32)

    def body(carry, xs):
        bc, vc, cc = xs
        oh = (bc[:, :, None] == ib).astype(compute_dtype)        # [F, C_rows, B]
        lsel = (cc[:, None] == ic).astype(compute_dtype)         # [C_rows, C]
        vL = (lsel[:, :, None] * vc[:, None, :]).reshape(chunk, C * 3)
        out = jnp.einsum("fcb,ck->fbk", oh, vL,
                         preferred_element_type=jnp.float32)     # [F, B, 3C]
        return carry + out, None

    init = jnp.zeros((F, B, C * 3), jnp.float32)
    # unroll: several chunks per loop iteration lets the scheduler overlap
    # the next chunk's HBM loads with the current chunk's compute
    hist, _ = jax.lax.scan(body, init, (bins_c, vals_c, cid_c),
                           unroll=min(4, n_chunks))
    hist = hist.reshape(F, B, C, 3).transpose(2, 0, 1, 3)        # [C, F, B, 3]
    return hist[:num_cols]


def histogram_leafbatch_segsum(bins, grad, hess, col_id, col_ok,
                               num_cols: int, num_bins_max: int,
                               chunk: int = 0, compute_dtype=None,
                               axis_name=None, int_reduce=None, salt=0,
                               packing=None, feat_gather=None):
    """Scatter-add leaf-batched histogram — CPU-fast oracle with the same
    [C, F, B, 3] contract as histogram_leafbatch (scatter beats the dense
    one-hot matmul off-TPU; summation ORDER differs, so f32 sums match the
    matmul only to reduction noise).  ``packing``: the oracle just
    un-permutes the packed bin matrix first — one F-row gather buys exact
    uniform-path semantics."""
    if _packing_active(packing):
        bins = _unpack_bins(bins, packing)
    F, N = bins.shape
    B = num_bins_max
    C = num_cols
    okf = col_ok.astype(jnp.float32)
    cid = jnp.where(col_ok, col_id, C).astype(jnp.int32)  # C = drop bucket
    ids = (cid[None, :] * F + jnp.arange(F, dtype=jnp.int32)[:, None]) * B \
        + bins.astype(jnp.int32)
    vals = jnp.stack([grad * okf, hess * okf, okf], axis=1)      # [N, 3]
    vals = jnp.broadcast_to(vals[None], (F, N, 3)).reshape(-1, 3)
    hist = jax.ops.segment_sum(vals, ids.reshape(-1),
                               num_segments=(C + 1) * F * B)
    return _feat_take(hist.reshape(C + 1, F, B, 3)[:C], feat_gather, 1)


def hist_quant_segsum(bins, grad, hess, col_id, col_ok, num_cols: int,
                      num_bins_max: int, chunk: int = 0, rng_bits=None,
                      compute_dtype=None, axis_name=None, int_reduce=None,
                      salt=0, packing=None, feat_gather=None,
                      quant_max=None, stats=3, targets=None):
    """Scatter-add variant of the quantized-gradient histogram — exact
    int32 accumulation, so it is bit-identical to hist_pallas/hist_quant_xla
    (ops/hist_pallas.py) at any summation order; the CPU-fast oracle for
    int8-path quality tests.  ``stats``, ``targets``: see
    ``histogram_leafbatch``."""
    from .hist_pallas import check_int8_row_capacity, quantize_values
    if _packing_active(packing):
        bins = _unpack_bins(bins, packing)
    F, N = bins.shape
    # one int32 segment sum over every row, and every shard's: not ranged
    check_int8_row_capacity(
        N * (1 if axis_name is None else jax.lax.axis_size(axis_name)),
        "the scatter-add oracle hist_quant_segsum")
    B = num_bins_max
    C = num_cols
    codes, scale = quantize_values(grad, hess, col_ok, col_id, rng_bits,
                                   axis_name=axis_name,
                                   stochastic=(compute_dtype == "int8_sr"),
                                   salt=salt, quant_max=quant_max,
                                   stats=stats)
    vals = codes[:stats]      # [stats, N] i8; its own column ids pass 127
    if targets is not None:
        # the column whose target the row's id is, else none
        hit = col_id[None, :] == targets[:, None]                # [C, N]
        col_id = jnp.where(jnp.any(hit, axis=0),
                           jnp.argmax(hit, axis=0), C)
    cid = jnp.where(col_ok, col_id, C).astype(jnp.int32)
    ids = (cid[None, :] * F + jnp.arange(F, dtype=jnp.int32)[:, None]) * B \
        + bins.astype(jnp.int32)
    v = jnp.broadcast_to(vals.T.astype(jnp.int32)[None],
                         (F, N, stats)).reshape(-1, stats)
    hist = jax.ops.segment_sum(v, ids.reshape(-1),
                               num_segments=(C + 1) * F * B)
    if axis_name is not None:
        from .. import telemetry
        telemetry.record_collective("hist/int8_segsum_psum", "psum",
                                    axis_name, telemetry._tree_nbytes(hist))
        hist = jax.lax.psum(hist, axis_name)   # int-domain cross-shard sum
    hist = _feat_take(hist.reshape(C + 1, F, B, stats)[:C], feat_gather, 1)
    if stats == 3:
        return hist.astype(jnp.float32) * scale
    return hist.astype(jnp.float32) * scale, hist[..., 1]


def histogram_segsum(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                     mask: jax.Array, num_bins_max: int,
                     packing=None, feat_gather=None) -> jax.Array:
    """Scatter-add backend (CPU-friendly, used by tests as an oracle)."""
    if _packing_active(packing):
        bins = _unpack_bins(bins, packing)
    F, N = bins.shape
    B = num_bins_max
    maskf = mask.astype(jnp.float32)
    ids = bins.astype(jnp.int32) + (jnp.arange(F, dtype=jnp.int32) * B)[:, None]
    ids = ids.reshape(-1)  # [F*N]
    vals = jnp.stack([grad * maskf, hess * maskf, maskf], axis=1)  # [N, 3]
    vals = jnp.broadcast_to(vals[None], (F, N, 3)).reshape(-1, 3)
    hist = jax.ops.segment_sum(vals, ids, num_segments=F * B)
    return _feat_take(hist.reshape(F, B, 3), feat_gather, 0)


def build_histogram(bins, grad, hess, mask, num_bins_max, *,
                    backend: str = "matmul", chunk: int = 16384,
                    compute_dtype=jnp.float32, axis_name=None,
                    int_reduce=None, salt=0, packing=None,
                    feat_gather=None, skip_dead: bool = False,
                    quant_max=None) -> jax.Array:
    """``int_reduce``: optional int-domain cross-shard reduction for the
    quantized path (feature axis 0) — the data-parallel reduce_scatter
    ownership schedule passes a psum_scatter here so the accumulators are
    scattered WITHOUT leaving the exact int domain.  ``packing``: static
    mixed-bin layout spec, ``skip_dead``: the mask ends in dead stretches,
    ``quant_max``: the tree's int8 scale (all three: see
    histogram_leafbatch)."""
    if str(compute_dtype).startswith("int8"):
        # single-leaf quantized pass == leaf-batched with one column
        N = bins.shape[1]
        cid = jnp.zeros((N,), jnp.int32)
        out = histogram_leafbatch(bins, grad, hess, cid, mask, 1,
                                  num_bins_max, chunk=chunk,
                                  compute_dtype=compute_dtype,
                                  axis_name=axis_name,
                                  int_reduce=int_reduce, salt=salt,
                                  packing=packing, feat_gather=feat_gather,
                                  quant_max=quant_max)
        return out[0]
    if backend == "matmul":
        if _pallas_hist_ok(num_bins_max):
            # single-leaf float pass on TPU: one-column leafbatch hits the
            # Pallas kernel (the leaf-wise f32 path rides the same einsum
            # the regression broke; MXU cost is identical either way — the
            # value tile is 128 lanes minimum)
            cid = jnp.zeros((bins.shape[1],), jnp.int32)
            out = histogram_leafbatch(bins, grad, hess, cid, mask, 1,
                                      num_bins_max, chunk=chunk,
                                      compute_dtype=compute_dtype,
                                      packing=packing,
                                      feat_gather=feat_gather,
                                      skip_dead=skip_dead)
            return out[0]
        return histogram_matmul(bins, grad, hess, mask, num_bins_max,
                                chunk=chunk, compute_dtype=compute_dtype,
                                packing=packing, feat_gather=feat_gather)
    if backend == "segsum":
        return histogram_segsum(bins, grad, hess, mask, num_bins_max,
                                packing=packing, feat_gather=feat_gather)
    raise ValueError(f"unknown histogram backend {backend!r}")
