"""Per-phase cost attribution for the depthwise training iteration.

Two methodologies:

``--mode=stub`` (the original): bench-style A/B at full scale — the only
low-noise end-to-end ground truth on the chip.  Times the SAME
fused k-iteration chunk program in variants that stub one phase each, so
the phase cost falls out as a difference of end-to-end rates:

  full        : unmodified train_chunk
  nohist      : histogram_leafbatch replaced by a cheap data-dependent
                broadcast (keeps the program structure and all downstream
                consumers; removes the MXU one-hot passes)

``--mode=telemetry``: reads the telemetry subsystem's phase spans
(lightgbm_tpu/telemetry.py) instead of stubbing.  The fused program is
host-indivisible, so the span read runs ONE iteration eagerly
(jax.disable_jit + fence mode — every op executes and blocks as its own
dispatch) to attribute wall time to histogram / split_find / partition,
then scales those FRACTIONS onto the separately-measured jitted
sec/iter.  Eager dispatch overhead inflates the non-histogram tail, so
treat the stub difference as ground truth for absolutes and the span
fractions as the per-phase decomposition; ``--cross-check`` runs the
nohist stub variant too and prints both attributions side by side.

Usage: python scripts/profile_phases.py --rows 11000000 --iters 8
       python scripts/profile_phases.py --mode=telemetry --rows 200000
Prints one JSON line per variant.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(variant: str, args) -> float:
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu  # noqa: F401
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log
    from lightgbm_tpu.models import grower_depthwise
    from lightgbm_tpu.ops import histogram

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)

    if variant == "nohist":
        real = histogram.histogram_leafbatch

        def stub(bins, grad, hess, col_id, col_ok, num_cols, num_bins_max,
                 chunk=65536, compute_dtype=jnp.bfloat16, axis_name=None):
            F = bins.shape[0]
            # data-dependent (not constant-foldable), trivially cheap
            seed = (jnp.sum(grad[:8]) + col_id[0].astype(jnp.float32))
            return jnp.full((num_cols, F, num_bins_max, 3), 1.0,
                            jnp.float32) * (1.0 + 1e-12 * seed)

        grower_depthwise.histogram_leafbatch = stub

    from bench import make_data

    x, y = make_data(args.rows, args.features)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)

    cfg = OverallConfig()
    cfg.set({
        "objective": "binary", "num_leaves": str(args.leaves),
        "min_data_in_leaf": "100", "min_sum_hessian_in_leaf": "10.0",
        "learning_rate": "0.1", "grow_policy": "depthwise",
        "hist_dtype": args.hist_dtype,
        "num_iterations": str(2 * args.iters),
    }, require_data=False)

    booster = GBDT()
    booster.init(cfg.boosting_config, ds,
                 create_objective(cfg.objective_type, cfg.objective_config))
    booster.train_chunk(args.iters)
    jax.block_until_ready(booster.score)
    # perf_counter: monotonic (an NTP step would corrupt the rate)
    start = time.perf_counter()
    booster.train_chunk(args.iters)
    jax.block_until_ready(booster.score)
    elapsed = time.perf_counter() - start
    if variant == "nohist":
        grower_depthwise.histogram_leafbatch = real
    return args.iters / elapsed


def run_telemetry(args) -> dict:
    """Span-based attribution: jitted rate for the absolute sec/iter, one
    eager fenced iteration for the per-phase decomposition."""
    import jax
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.utils import log
    from bench import make_data

    log.set_stream(sys.stderr)
    log.set_level(log.WARNING)

    x, y = make_data(args.rows, args.features)
    ds = Dataset.from_arrays(x, y, max_bin=args.max_bin)
    cfg = OverallConfig()
    cfg.set({
        "objective": "binary", "num_leaves": str(args.leaves),
        "min_data_in_leaf": "100", "min_sum_hessian_in_leaf": "10.0",
        "learning_rate": "0.1", "grow_policy": "depthwise",
        "hist_dtype": args.hist_dtype,
        "num_iterations": str(2 * args.iters),
    }, require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds,
                 create_objective(cfg.objective_type, cfg.objective_config))

    # jitted end-to-end rate (the absolute scale the fractions map onto).
    # Telemetry armed for the jitted pass too (ISSUE 4): the cost registry
    # captures the chunk program's cost_analysis + compile seconds, and the
    # measured train_chunk span joins them into a roofline block
    telemetry.enable()
    telemetry.reset()
    booster.train_chunk(args.iters)
    jax.block_until_ready(booster.score)
    start = time.perf_counter()
    booster.train_chunk(args.iters)
    jax.block_until_ready(booster.score)
    sec_per_iter = (time.perf_counter() - start) / args.iters
    jit_snap = telemetry.snapshot()

    # one eager fenced iteration: every op span measures real execution
    # (reset clears the jitted pass's spans — the roofline block above is
    # already captured in jit_snap)
    telemetry.enable(fence=True)
    telemetry.reset()
    t0 = time.perf_counter()
    with jax.disable_jit():
        booster.train_one_iter(is_eval=False)
    eager_sec = time.perf_counter() - t0
    snap = telemetry.snapshot()
    telemetry.disable()

    pt = snap["phase_times"]
    phases = {k: pt.get(k, 0.0)
              for k in ("histogram", "split_find", "partition")}
    fractions = {k: round(v / eager_sec, 4) for k, v in phases.items()}
    out = {
        "mode": "telemetry", "rows": args.rows,
        "hist_dtype": args.hist_dtype,
        "iters_per_sec": round(1.0 / sec_per_iter, 4),
        "sec_per_iter": round(sec_per_iter, 4),
        "eager_sec": round(eager_sec, 4),
        "phase_times_eager": {k: round(v, 4) for k, v in pt.items()},
        "phase_fractions": fractions,
        "est_sec_per_iter": {k: round(f * sec_per_iter, 4)
                             for k, f in fractions.items()},
        "counters": dict(sorted(snap["counters"].items())),
    }
    # roofline/compile from the JITTED pass (ISSUE 4): attained rates over
    # the fused program's measured wall time, the compiled-program
    # inventory, and the analytic per-pass MAC notes
    if "roofline" in jit_snap:
        out["roofline"] = jit_snap["roofline"]
    if "compile" in jit_snap:
        out["compile"] = jit_snap["compile"]
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=11_000_000)
    p.add_argument("--features", type=int, default=28)
    p.add_argument("--leaves", type=int, default=255)
    p.add_argument("--max-bin", type=int, default=255)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--mode", default="stub", choices=["stub", "telemetry"])
    p.add_argument("--variant", default="full",
                   choices=["full", "nohist"])
    p.add_argument("--cross-check", action="store_true",
                   help="telemetry mode: also run the nohist stub variant "
                        "(subprocess) and report both histogram "
                        "attributions side by side")
    p.add_argument("--hist-dtype", default="float32",
                   choices=["float32", "bfloat16", "int8"])
    args = p.parse_args()
    if (args.mode == "telemetry" and args.cross_check
            and args.hist_dtype != "int8"):
        # two lanes, each a child run to its end before the next starts;
        # this parent stays off JAX (a chip belongs to one process at a
        # time — same rule as bench.py's orchestrator).  A lane that
        # fails fails the run.
        import subprocess
        shape = ["--rows", str(args.rows), "--features",
                 str(args.features), "--leaves", str(args.leaves),
                 "--max-bin", str(args.max_bin), "--iters",
                 str(args.iters), "--hist-dtype", args.hist_dtype]

        def lane(extra):
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__)] + shape + extra,
                stdout=subprocess.PIPE, text=True, timeout=3600,
                check=True)
            return json.loads(res.stdout.strip().splitlines()[-1])

        out = lane(["--mode", "telemetry"])
        sub = lane(["--mode", "stub", "--variant", "nohist"])
        out["cross_check"] = {
            "stub_hist_sec_per_iter": round(
                out["sec_per_iter"] - sub["sec_per_iter"], 4),
            "telemetry_hist_sec_per_iter":
                out["est_sec_per_iter"]["histogram"],
        }
        print(json.dumps(out))
        return
    if args.mode == "telemetry":
        print(json.dumps(run_telemetry(args)))
        return
    if args.variant == "nohist" and args.hist_dtype == "int8":
        # int8 derives root stats FROM the histogram (grower_depthwise);
        # a stubbed histogram would grow a structurally different tree and
        # the full-minus-nohist subtraction would compare two different
        # programs
        raise SystemExit("--variant nohist requires a float hist dtype "
                         "(int8 root stats are histogram-derived)")
    rate = run_variant(args.variant, args)
    print(json.dumps({"variant": args.variant, "mode": "stub",
                      "rows": args.rows,
                      "hist_dtype": args.hist_dtype,
                      "iters_per_sec": round(rate, 4),
                      "sec_per_iter": round(1.0 / rate, 4)}))


if __name__ == "__main__":
    main()
