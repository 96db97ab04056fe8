"""The partition of one range, alone on the chip: both DMA schedules of the
kernel that partitions a range inside the two-sided pane
(ops/compact.partition_segment since PR 37), and beside it the XLA passes
the out-of-pane call made around its kernel until then (slice the bucket
out, ``where`` against the kernel's output, ``dynamic_update_slice`` back),
at the same shape, so that the two can be told apart in a cell's
``partition_ms_per_iter``.

Each is timed as a loop inside ONE program whose carry is the pane: a
trip partitions the same range from the side the trip before wrote to, so
the pane is updated in place as the grower's loop updates it, nothing is
summed or perturbed around the call, and seconds a call are
(T(r2) - T(r1)) / (r2 - r1).

On a backend where the Pallas kernel is ineligible (CPU CI included) the
script times the XLA oracle of the same contract and says so, instead of
printing a fake A/B.

Usage: python scripts/partition_ab.py [--rows N] [--features F]
                                      [--start LANE] [--row-blocked 1]
Prints one JSON line.  ``--rows`` is the range's lane count (its bucket
is the rows in whole lane blocks), ``--start`` its first lane (the root's
bucket then covers start + rows).  ``--row-blocked 1`` also times the
row-blocked kernel (ops/compact._partition_kernel_rows) forced onto the
same pane, beside whichever kernel ``partition_grid`` picks for it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def loop_seconds(make_body, carry, reps=(2, 10)):
    """Seconds a trip of ``make_body()``'s loop body over ``carry``."""
    import jax

    def run(n):
        prog = jax.jit(lambda c: jax.lax.fori_loop(0, n, make_body(), c)
                       .ravel()[:8].astype("int32").sum())
        np.asarray(prog(carry))                       # compile + warm
        t0 = time.perf_counter()
        np.asarray(prog(carry))
        return time.perf_counter() - t0

    r1, r2 = reps
    t1 = run(r1)
    return (run(r2) - t1) / (r2 - r1)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1_000_000,
                   help="lanes of the range (bench scale: 1M)")
    p.add_argument("--features", type=int, default=28)
    p.add_argument("--start", type=int, default=0,
                   help="the range's first pane lane")
    p.add_argument("--left-frac", type=float, default=0.5)
    p.add_argument("--row-blocked", type=int, choices=(0, 1), default=0,
                   help="also time the row-blocked kernel on this pane")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import compact

    backend = jax.default_backend()
    eligible = backend == "tpu" and compact.pallas_partition_ok()
    R = compact.pane_rows(args.features)
    block = compact.BLOCK
    W = -(-args.rows // block) * block
    P = -(-(args.start + args.rows) // block) * block
    start, cnt = args.start, args.rows
    rng = np.random.RandomState(0)
    root = jnp.asarray(rng.randint(-128, 128, (R, P)), jnp.int8)

    def in_pane_ms(use_pallas: bool, overlap: bool) -> float:
        pane = jnp.zeros((2,) + compact.pane_layout(R, P), jnp.int8)
        pane = pane.at[0, :R, :P].set(root)
        cs, lanes = compact.range_origin(pane, start, W)
        lane = int(cs) + np.arange(lanes)
        mask3 = jnp.asarray(np.where(
            (lane >= start) & (lane < start + cnt),
            rng.rand(lanes) < args.left_frac, -1).astype(np.int8))
        plcnt = jnp.int32(int((np.asarray(mask3) == 1).sum()))

        def make_body():
            return lambda i, pane: compact._partition_in_pane_fn(
                pane, mask3, i & 1, jnp.int32(start), jnp.int32(cnt), plcnt,
                width=W, block=block, use_pallas=use_pallas,
                interpret=False, overlap=overlap)
        return loop_seconds(make_body, pane) * 1e3

    def round_trip_xla_ms() -> float:
        """What XLA did around the out-of-pane kernel, without the kernel:
        the bucket sliced out of a one-sided [R, P] pane at the range's
        (clamped, unaligned) lane, the ``where`` that kept the lanes
        outside the range against the kernel's output (here the slice
        itself behind a barrier), the update back."""
        def make_body():
            def body(i, pane):
                with jax.named_scope("partition"):
                    cs = jnp.minimum(jnp.int32(start) + 0 * i, P - W)
                    seg = jax.lax.dynamic_slice(pane, (jnp.int32(0), cs),
                                                (R, W))
                    out = jax.lax.optimization_barrier(seg)
                    lane = cs + jnp.arange(W, dtype=jnp.int32)
                    inseg = (lane >= start) & (lane < start + cnt)
                    new = jnp.where(inseg[None, :], out, seg)
                    return jax.lax.dynamic_update_slice(
                        pane, new, (jnp.int32(0), cs))
            return body
        return loop_seconds(make_body, root) * 1e3

    out = {
        "backend": backend,
        "device_kind": str(jax.local_devices()[0].device_kind),
        "pallas_eligible": bool(eligible),
        "rows": args.rows, "features": args.features, "start": start,
        "range_shape": [int(R), int(W)],
        "pane_shape": [2, *compact.pane_layout(R, P)],
    }
    out["round_trip_xla_ms"] = round(round_trip_xla_ms(), 3)
    if eligible:
        on = in_pane_ms(True, True)
        off = in_pane_ms(True, False)
        out["grid"] = list(compact.partition_grid(R))
        out["overlap_on_ms"] = round(on, 3)
        out["overlap_off_ms"] = round(off, 3)
        out["overlap_speedup"] = round(off / on, 4) if on > 0 else None
        if args.row_blocked:
            # the row-blocked kernel forced onto this pane (partition_grid
            # keeps a pane of 88 rows or fewer on the one-block kernels):
            # one row block where the pane is that low, TALL_BLOCK lanes
            rows = min(-(-R // 32) * 32, compact.partition_grid(2016)[1])
            forced = (compact.TALL_BLOCK, rows, -(-R // rows))
            compact.partition_grid = lambda *_a, **_k: forced
            out["rows_kernel_grid"] = list(forced)
            out["rows_kernel_overlap_on_ms"] = round(
                in_pane_ms(True, True), 3)
            out["rows_kernel_overlap_off_ms"] = round(
                in_pane_ms(True, False), 3)
    else:
        out["xla_oracle_ms"] = round(in_pane_ms(False, True), 3)
        out["note"] = (
            "Pallas partition ineligible on backend=%s — partition routes "
            "to the XLA oracle, where the DMA-overlap flag is a no-op; "
            "the overlap A/B needs a TPU round" % backend)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
