"""Microbench histogram-pass formulations on the real TPU.

Variants (all build the same [C, F, B, 3]-shaped level histogram):
  bf16   : current histogram_leafbatch (one-hot x values, bf16 operands)
  int8   : quantized-gradient pass — values stochastically rounded to int8
           per column, one-hot generated int8, int8xint8->int32 MXU matmul,
           dequantized f32 result (modern LightGBM's quantized-training
           idea recast as an MXU matmul)

Usage: python scripts/hist_kernel_bench.py --rows 4000000 --cols 42

``--sweep-classes`` (ISSUE 6) instead runs the bin-width-class sweep: the
same leaf-batched pass at B=63, B=255, and the MIXED per-class schedule
(narrow features at 64 bins + wide at 255 via a PackSpec) so the packing
threshold (io/binning.NARROW_BINS) can be re-derived from measurement when
kernel economics change, instead of folklore.

``--float-fold`` (PR 35) times the raw float kernel ("bf16v", the float32
pair's five statistics of ONE leaf column: the leaf-wise pass) unfolded
against the fold ``hist_fold`` picks, at the benchmark's two tables,
[28, 10,502,144] and [2000, 401,408], and says whether the two
accumulators are bit-equal on this device (and at one, two and four
columns of three and five statistics at [28, 1,048,576]).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import histogram_leafbatch
from scripts.tpu_timeit import device_time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=4_000_000)
    p.add_argument("--features", type=int, default=28)
    p.add_argument("--bins", type=int, default=256)
    p.add_argument("--cols", type=int, default=42)
    p.add_argument("--chunk", type=int, default=65536)
    p.add_argument("--variants", default="bf16,int8")
    p.add_argument("--pallas-chunk", type=int, default=2048)
    p.add_argument("--sweep-classes", action="store_true",
                   help="bin-width-class sweep: 63-wide vs 255-wide vs "
                        "the mixed per-class schedule on the same rows "
                        "(re-derives the packing threshold from data)")
    p.add_argument("--narrow-frac", type=float, default=6 / 7,
                   help="fraction of features in the narrow class for "
                        "the mixed lane of --sweep-classes")
    p.add_argument("--float-fold", action="store_true",
                   help="the float kernel's one-column pass unfolded "
                        "against its bin fold, at both benchmark tables, "
                        "and whether the accumulators are bit-equal")
    args = p.parse_args()

    rng = np.random.RandomState(0)
    N, F, B, C = args.rows, args.features, args.bins, args.cols

    if args.sweep_classes:
        return sweep_classes(args, rng)
    if args.float_fold:
        return float_fold(args, rng)
    bins = jnp.asarray(rng.randint(0, B, size=(F, N), dtype=np.int32)
                       .astype(np.int8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32) * 0.3)
    hess = jnp.asarray(rng.rand(N).astype(np.float32) * 0.25)
    col_id = jnp.asarray(rng.randint(0, C, size=N).astype(np.int32))
    col_ok = jnp.asarray(rng.rand(N) < 0.9)

    per_pass_bytes = N * (F + 13)  # bins int8 + g/h f32 + colid i32 + ok
    for v in args.variants.split(","):
        if v == "bf16":
            op = lambda g, h: histogram_leafbatch(
                bins, g, h, col_id, col_ok, C, B, chunk=args.chunk)
        elif v == "int8":
            from lightgbm_tpu.ops.hist_pallas import hist_quant_xla
            op = lambda g, h: hist_quant_xla(
                bins, g, h, col_id, col_ok, C, B, chunk=args.chunk)
        elif v.startswith("pallas"):
            from lightgbm_tpu.ops.hist_pallas import hist_pallas_leafbatch
            dt = "int8" if v.endswith("int8") else "bfloat16"
            ck = args.pallas_chunk
            op = lambda g, h: hist_pallas_leafbatch(
                bins, g, h, col_id, col_ok, C, B, chunk=ck, dtype=dt)
        else:
            raise SystemExit(f"unknown variant {v}")
        t = device_time(op, grad, hess, key_arg=0, reps=(2, 6))
        gbps = per_pass_bytes / t / 1e9
        print(f"{v:6s} rows={N} C={C} chunk={args.chunk}: "
              f"{t*1e3:8.2f} ms/pass  ({gbps:6.1f} GB/s effective)")


def sweep_classes(args, rng):
    """63-wide vs 255-wide vs mixed per-class passes on identical rows.

    The mixed lane builds a real PackSpec (narrow features first, 64-wide
    class; wide features at 255) and calls histogram_leafbatch with it —
    the exact production schedule, so the printed ratio IS the headline
    histogram speedup a dataset with this narrow fraction can expect, and
    the 63-vs-255 lanes bound it from both sides."""
    from lightgbm_tpu.io.binning import PackSpec
    N, F, C = args.rows, args.features, args.cols
    n_narrow = max(1, min(F - 1, int(round(F * args.narrow_frac))))
    grad = jnp.asarray(rng.randn(N).astype(np.float32) * 0.3)
    hess = jnp.asarray(rng.rand(N).astype(np.float32) * 0.25)
    col_id = jnp.asarray(rng.randint(0, C, size=N).astype(np.int32))
    col_ok = jnp.asarray(rng.rand(N) < 0.9)
    per_pass_bytes = N * (F + 13)

    def bins_of(widths):
        return jnp.asarray(np.stack(
            [rng.randint(0, w, size=N) for w in widths]).astype(np.int8))

    lanes = [
        ("b63", bins_of([63] * F), 63, None),
        ("b255", bins_of([255] * F), 255, None),
        ("mixed", bins_of([64] * n_narrow + [255] * (F - n_narrow)), 255,
         PackSpec(widths=(64, 255), counts=(n_narrow, F - n_narrow),
                  perm=tuple(range(F)))),
    ]
    results = {}
    for name, bins, B, spec in lanes:
        op = lambda g, h, _b=bins, _B=B, _s=spec: histogram_leafbatch(
            _b, g, h, col_id, col_ok, C, _B, chunk=args.chunk,
            packing=_s)
        t = device_time(op, grad, hess, key_arg=0, reps=(2, 6))
        results[name] = t
        gbps = per_pass_bytes / t / 1e9
        print(f"{name:6s} rows={N} F={F} C={C}"
              f"{'' if spec is None else ' narrow=%d' % n_narrow}: "
              f"{t*1e3:8.2f} ms/pass  ({gbps:6.1f} GB/s effective)")
    print(f"mixed vs b255 speedup: {results['b255'] / results['mixed']:.2f}x"
          f"  (b63 bound: {results['b255'] / results['b63']:.2f}x)")
    return 0


def float_fold(args, rng):
    """The raw "bf16v" kernel at fold 1 and at the rule's fold: ms a pass
    where timed, and the folded accumulator against the unfolded one's
    live lanes, cell for cell."""
    from lightgbm_tpu.ops.hist_pallas import hist_fold, hist_pallas_raw
    B, chunk = 255, args.pallas_chunk           # the cells' bins

    def table(F, N, stats, cols):
        bins = jnp.asarray(rng.randint(0, B, size=(F, N), dtype=np.uint8)
                           .view(np.int8))
        cid = np.where(rng.rand(N) < 0.9, rng.randint(0, cols, N), -1)
        vals = rng.randn(stats, N).astype(np.float32) * 0.3 * (cid >= 0)
        vals[stats - 1] = cid >= 0              # the count row
        packed = jnp.asarray(np.concatenate([vals, cid[None]]),
                             jnp.bfloat16)
        return bins, packed

    def compare(F, N, stats, cols, timed):
        bins, packed = table(F, N, stats, cols)
        fold, gw = hist_fold(stats, cols, B, 128)
        runs = {}
        for k, g in ((1, None), (fold, gw)):
            # the table an argument and not a constant of the timed
            # program, which would carry its 800 MB
            op = lambda p, b, k=k, g=g: hist_pallas_raw(
                b, p, B=B, chunk=chunk, dtype="bf16v", lanes=128,
                stats=stats, fold=k, gw=g)
            ms = (device_time(op, packed, bins, key_arg=0, reps=(2, 6))
                  * 1e3 if timed else float("nan"))
            runs[k] = (ms, np.asarray(op(packed, bins)))
        (ms1, plain), (msk, folded) = runs[1], runs[fold]
        live = stats * cols
        equal = (np.array_equal(plain[:, :, :live], folded[:, :, :live])
                 and not plain[:, :, live:].any()
                 and not folded[:, :, live:].any())
        gap = np.abs(plain[:, :, :live] - folded[:, :, :live]).max()
        print(f"bf16v [{F}, {N}] stats={stats} cols={cols} B={B}: "
              f"fold 1 {ms1:8.2f} ms {plain.shape}, fold {fold} gw {gw} "
              f"{msk:8.2f} ms {folded.shape}, "
              f"{'BIT-EQUAL' if equal else 'NOT EQUAL, max gap %g' % gap}",
              flush=True)
        return equal

    ok = [compare(F, N, 5, 1, timed=True)
          for F, N in ((28, 10_502_144), (2000, 401_408))]
    ok += [compare(28, 1 << 20, stats, cols, timed=False)
           for stats in (3, 5) for cols in (1, 2, 4)]
    print("bf16v folded == unfolded on %s: %s" % (
        jax.devices()[0].device_kind, all(ok)))
    return 0


if __name__ == "__main__":
    main()
