"""The int8 histogram kernel past one int32 accumulator's rows, on the chip.

The benchmark's generator draws every column continuous, so no cell of
``airline-levelwise-int8.train`` holds more than 1/255 of the rows and no
accumulator of a run of the cell would wrap even unranged.  The real
airline table's ``Diverted`` column has two values, 99.8% of the rows in
one.  This script builds that table and runs iteration 0's histogram pass
over it through the Pallas kernel at the cell's own rows:

  column 0   constant: every row in one bin
  column 1   ``Diverted``-like: 99.8% of the rows in bin 0, the rest in 1

with binary logloss's first gradients (score 0: gradient -0.5 or +0.5 by
the label, hessian 0.25, so every hessian level is 127 and every gradient
level -127 or +127).  Every cell of the kernel's ranged accumulators, of
their exact integer pair and of the float32 histogram handed on is held
against int64 NumPy sums; what the same accumulators come to in ONE
int32, the arithmetic before the ranges, is printed beside them.

    chiprun -- python3 scripts/hist_ranges_check.py            # 115M rows
    JAX_PLATFORMS=cpu python scripts/hist_ranges_check.py \
        --rows 30000 --cap 4096                                # rehearsal

``--cap`` patches ``INT8_HIST_MAX_ROWS`` for a rehearsal off the chip
(the kernel runs through the interpreter there); it is not a setting of
the program.  ``--time`` also times the one-column and the 64-column pass
of the cell's table shape [13, rows], ranged, and in one range (whose sums
may wrap: the time is what is read).  The last line of standard output is
the result as JSON, also written to ``chiprun_out/hist_ranges_check.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import hist_pallas

B = 255
CHUNK = 2048


def table(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    bins = np.zeros((2, rows), np.uint8)
    bins[0] = 7
    bins[1] = rng.random(rows) < 0.002
    label = rng.random(rows) < 0.2            # a fifth of flights late
    grad = np.where(label, -0.5, 0.5).astype(np.float32)
    hess = np.full(rows, 0.25, np.float32)
    return bins, grad, hess


def int64_sums(bins, grad):
    """[2, B, 3] int64: levels +-127 and 127, and rows, cell by cell."""
    want = np.zeros((2, B, 3), np.int64)
    gq = np.where(grad > 0, 127, -127).astype(np.int64)
    for f in range(2):
        want[f, :, 0] = np.bincount(bins[f], weights=gq, minlength=B)
        want[f, :, 2] = np.bincount(bins[f], minlength=B)
        want[f, :, 1] = 127 * want[f, :, 2]
    return want


def check(rows: int, seed: int) -> dict:
    bins, grad, hess = table(rows, seed)
    want = int64_sums(bins, grad)
    ranges, padded, paired = hist_pallas._ranged_rows(rows, CHUNK)
    d_bins, d_grad, d_hess = (jnp.asarray(a) for a in (bins, grad, hess))
    ok = jnp.ones((rows,), bool)
    cid = jnp.zeros((rows,), jnp.int32)

    # the route as the grower calls it: float32 [1, 2, B, 3]
    hist = np.asarray(jax.jit(lambda b, g, h: hist_pallas.hist_pallas_leafbatch(
        b, g, h, cid, ok, 1, B, chunk=CHUNK, dtype="int8"))(
            d_bins, d_grad, d_hess), np.float64)[0]
    scale = np.array([0.5 / 127.0, 0.25 / 127.0, 1.0])
    want_f32 = want.astype(np.float32).astype(np.float64) * scale
    hist_rel = float(np.max(np.abs(hist - want_f32)
                            / np.maximum(np.abs(want_f32), 1.0)))

    # the kernel's own accumulators, range by range
    packed, _ = hist_pallas.quantize_values(d_grad, d_hess, ok, cid)
    pad = padded - rows
    fold, gw = hist_pallas.hist_fold(3, 1, B, 128)
    acc = hist_pallas.hist_pallas_raw(
        jnp.pad(d_bins, ((0, 0), (0, pad))).astype(jnp.int8),
        jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1),
        B=B, chunk=CHUNK, dtype="int8", fold=fold, gw=gw, ranges=ranges)
    acc = acc if ranges > 1 else acc[None]
    each = np.asarray(acc[..., :3], np.int64)             # [ranges, 2, B, 3]
    hi, lo = hist_pallas.range_sum(acc[..., :3])
    pair = np.asarray(hi, np.int64) * 65536 + np.asarray(lo, np.int64)
    one_int32 = each.astype(np.int32).sum(axis=0, dtype=np.int32)
    return {
        "rows": rows, "seed": seed, "ranges": ranges,
        "rows_a_range": padded // ranges, "paired": bool(paired),
        "cap": hist_pallas.INT8_HIST_MAX_ROWS,
        "cells_held": int(want.size),
        "largest_cell_int64": int(np.abs(want).max()),
        "largest_accumulator": int(np.abs(each).max()),
        "ranges_sum_equals_int64": bool((each.sum(axis=0) == want).all()),
        "pair_equals_int64": bool((pair == want).all()),
        "hist_max_rel_gap_to_rounded_int64": hist_rel,
        # the parent's arithmetic: the same rows in ONE int32
        "one_int32_cells_wrong": int((one_int32.astype(np.int64)
                                      != want).sum()),
        "one_int32_constant_column_hessian": int(one_int32[0, 7, 1]),
        "int64_constant_column_hessian": int(want[0, 7, 1]),
        "one_int32_diverted_bin0_hessian": int(one_int32[1, 0, 1]),
        "int64_diverted_bin0_hessian": int(want[1, 0, 1]),
    }


def time_passes(rows: int) -> dict:
    """ms a pass of the raw kernel at [13, rows], one and 64 leaf columns,
    in the ranges the rule gives and in one."""
    from scripts.tpu_timeit import device_time
    ranges, padded, _ = hist_pallas._ranged_rows(rows, CHUNK)
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B, (13, padded), dtype=np.uint8)
                       ).astype(jnp.int8)
    out = {}
    for cols, lanes in ((1, 128), (64, 192)):
        packed = jnp.asarray(np.concatenate([
            rng.integers(-127, 128, (2, padded), dtype=np.int8),
            np.ones((1, padded), np.int8),
            rng.integers(0, cols, (1, padded), dtype=np.int8)]))
        fold, gw = hist_pallas.hist_fold(3, cols, B, lanes)
        held = hist_pallas.held_onehot(3, cols, B, lanes, "int8")
        for r in sorted({ranges, 1}):
            op = lambda p, b, r=r: hist_pallas.hist_pallas_raw(
                b, p.astype(jnp.int8), B=B, chunk=CHUNK, dtype="int8",
                lanes=lanes, fold=fold, gw=gw, held=held, ranges=r)
            out["cols%d_ranges%d_ms" % (cols, r)] = 1e3 * device_time(
                op, packed.astype(jnp.float32), bins, reps=(1, 3))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=115_000_000)
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--cap", type=int, default=None,
                    help="rehearsal only: patch INT8_HIST_MAX_ROWS")
    ap.add_argument("--time", action="store_true")
    args = ap.parse_args()
    if args.cap is not None:
        hist_pallas.INT8_HIST_MAX_ROWS = args.cap
    device = jax.devices()[0]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind}}
    if device.platform == "tpu":
        result.update(check(args.rows, args.seed))
        if args.time:
            result["timing"] = time_passes(args.rows)
    else:
        from jax.experimental.pallas import tpu as pltpu
        with pltpu.force_tpu_interpret_mode():
            result.update(check(args.rows, args.seed))
    good = (result["ranges_sum_equals_int64"] and result["pair_equals_int64"]
            and result["hist_max_rel_gap_to_rounded_int64"] < 1e-6)
    result["matches_int64"] = bool(good)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hist_ranges_check.json"),
              "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
