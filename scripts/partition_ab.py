"""Partition-window DMA-overlap A/B — the PROFILE.md "pending" number.

PR 3 made the overlapped window-DMA schedule the partition kernel's
default (ops/compact._partition_kernel_overlap) with
``LGBM_TPU_PARTITION_NO_OVERLAP=1`` as the serialized A/B hatch, but the
TPU measurement was never recorded.  This script runs that A/B through
scripts/tpu_timeit's carry-perturbed fori harness (honest on-device
seconds, no dispatch-only lies) at the bench pane shape.

On a backend where the Pallas kernel is ineligible (CPU CI included) the
overlap bit is a no-op — partition routes to the XLA oracle — so the
script reports the oracle timing and says exactly that, instead of
printing a fake A/B.

Usage: python scripts/partition_ab.py [--rows N] [--features F]
                                      [--row-blocked 1]
Prints one JSON line.  ``--row-blocked 1`` also times the row-blocked
kernel (ops/compact._partition_kernel_rows) forced onto the same pane,
beside whichever kernel ``partition_grid`` picks for it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1_000_000,
                   help="segment lanes (bench scale: 1M)")
    p.add_argument("--features", type=int, default=28)
    p.add_argument("--left-frac", type=float, default=0.5)
    p.add_argument("--row-blocked", type=int, choices=(0, 1), default=0,
                   help="also time the row-blocked kernel on this pane")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops import compact
    from tpu_timeit import device_time

    backend = jax.default_backend()
    eligible = backend == "tpu" and compact.pallas_partition_ok()
    R = compact.pane_rows(args.features)
    W = ((args.rows + compact.BLOCK - 1) // compact.BLOCK) * compact.BLOCK
    rng = np.random.RandomState(0)
    seg = jnp.asarray(rng.randint(-128, 128, (R, W)), jnp.int8)
    cnt = args.rows
    go_left = rng.rand(W) < args.left_frac
    mask3 = np.where(np.arange(W) < cnt,
                     go_left.astype(np.int8), np.int8(-1))
    plcnt = int(mask3[:cnt].sum())
    mask3 = jnp.asarray(mask3)
    delta = jnp.int32(0)

    def run(use_pallas: bool, overlap: bool) -> float:
        return device_time(
            lambda s, m: compact._partition_segment_impl(
                s, m, delta, jnp.int32(cnt), jnp.int32(plcnt),
                block=compact.BLOCK, use_pallas=use_pallas,
                interpret=False, overlap=overlap),
            seg, mask3)

    def run_rows(overlap: bool) -> float:
        # the row-blocked kernel forced onto this pane (partition_grid
        # keeps a pane of 88 rows or fewer on the one-block kernels): one
        # row block where the pane is that low, TALL_BLOCK lanes
        lanes = compact.TALL_BLOCK
        rows = min(-(-R // 32) * 32, compact.partition_grid(2016)[1])
        nrb = -(-R // rows)

        def op(s, m):
            with jax.named_scope("partition"):
                scal = jnp.stack([delta, jnp.int32(plcnt)])
                inseg = jnp.arange(W, dtype=jnp.int32) < cnt
                return jnp.where(inseg[None, :], compact._partition_rows_call(
                    s, m, scal, lanes, rows, nrb, overlap, False), s)
        return device_time(op, seg, mask3)

    out = {
        "backend": backend,
        "device_kind": str(jax.local_devices()[0].device_kind),
        "pallas_eligible": bool(eligible),
        "rows": args.rows, "features": args.features,
        "pane_shape": [int(R), int(W)],
    }
    if eligible:
        on = run(True, True)
        off = run(True, False)
        out["overlap_on_ms"] = round(on * 1e3, 3)
        out["overlap_off_ms"] = round(off * 1e3, 3)
        out["overlap_speedup"] = round(off / on, 4) if on > 0 else None
        out["grid"] = list(compact.partition_grid(R))
        if args.row_blocked:
            out["rows_kernel_overlap_on_ms"] = round(run_rows(True) * 1e3, 3)
            out["rows_kernel_overlap_off_ms"] = round(
                run_rows(False) * 1e3, 3)
    else:
        out["xla_oracle_ms"] = round(run(False, True) * 1e3, 3)
        out["note"] = (
            "Pallas partition ineligible on backend=%s — partition routes "
            "to the XLA oracle, where the DMA-overlap flag is a no-op; "
            "the overlap A/B needs a TPU round" % backend)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
