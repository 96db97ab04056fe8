"""Training-health monitor: device-side numerical health, host-side policy.

The telemetry registry (telemetry.py, ISSUE 1) records what the HOST does —
phase wall times, kernel-route decisions.  This module watches what the
DEVICE PROGRAM computes: a NaN gradient, an Inf score, an int8 quantization
collapsing to the saturation ceiling, or a tree full of zero-gain splits all
degrade accuracy silently — nothing in the phase timers or route counters
moves.  The reference C++ had neither problem nor remedy (doubles on a CPU
fail loudly); quantized gradients on an accelerator need an instrument.

Design constraints (the same two that shaped telemetry.py):

1. **Never perturb training numerics.**  The health vector is computed FROM
   the training arrays (gradients, hessians, scores, tree arrays), never
   fed back into them.  On the per-iteration path it runs as separate tiny
   jitted programs over the already-materialized device arrays — the
   grower/chunk programs and their jit caches are untouched.  On the fused
   chunk path the vector is accumulated inside the scan (the only place the
   per-iteration values exist) as extra, independent reductions stacked
   next to the metric values; the score/tree math is byte-for-byte the same
   expression graph (tests/test_health.py locks score bit-identity in, on
   vs off).

2. **One host fetch per iteration.**  The per-iteration path dispatches the
   health programs asynchronously and starts their host copies alongside
   the model readback the boosting loop already pays; the chunk path reads
   the stacked [k, H] vector with the stacked trees.  No extra
   synchronization points, no effect on async dispatch.

The host-side :class:`HealthMonitor` assembles the device vector with
tree-derived counts (zero-gain splits, empty leaves, degenerate trees —
free from the model readback), applies the ``on_anomaly`` policy
(``warn`` / ``halt`` / ``record``), tracks eval-metric divergence (k
consecutive worsening iterations, ``health_divergence_rounds``), and mirrors
anomaly totals into telemetry counters so multi-process runs fold them into
the leader's summary through the existing cross-host aggregation
(parallel/learners.aggregate_telemetry).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy as np

from . import telemetry
from .utils import log

# Device health-vector layout: indices 0..5 are plain COUNTS (cross-shard
# psum), 6 the saturation gauge (already cross-shard global inside
# quant_saturation_count), 7 a WATERMARK (cross-shard pmax).  health_vector
# relies on this split; keep new plain counts before index 6.
HEALTH_VEC_KEYS = (
    "grad_nan", "grad_inf", "hess_nan", "hess_inf",
    "score_nan", "score_inf", "quant_sat",
    "score_max_abs",
)

# Tree-derived keys appended on host from the model readback.
TREE_HEALTH_KEYS = ("zero_gain_splits", "empty_leaves", "degenerate_trees")

# Keys whose nonzero value is an ANOMALY under the on_anomaly policy.
# quant_sat and zero_gain/empty-leaf counts are gauges, not faults: the int8
# max scale saturates its max row by construction, and zero-gain
# nodes appear in healthy late training.
ANOMALY_KEYS = ("grad_nan", "grad_inf", "hess_nan", "hess_inf",
                "score_nan", "score_inf")


class TrainingHealthError(log.LightGBMError):
    """Raised by ``on_anomaly=halt`` — a clean, catchable training stop
    (the CLI maps it to exit code 1 like every LightGBMError)."""


def health_vector(grad, hess, score, *, quantized: bool = False,
                  axis_name: Optional[str] = None):
    """[8] f32 device health vector over one iteration's arrays.

    grad/hess: [C, N] (or [N]) gradients/hessians; score: [C, N] raw
    scores AFTER this iteration's update.  ``quantized`` adds the int8
    saturation gauge (ops/hist_pallas.quant_saturation_count — rows whose
    magnitude quantizes to the ±127 ceiling under the max scale).
    ``axis_name``: under shard_map, counts are psum'd and the watermark
    pmax'd so every shard carries the identical global vector.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def count(pred):
        return jnp.sum(pred.astype(f32))

    counts = [count(jnp.isnan(grad)), count(jnp.isinf(grad)),
              count(jnp.isnan(hess)), count(jnp.isinf(hess)),
              count(jnp.isnan(score)), count(jnp.isinf(score))]
    if quantized:
        # quant_saturation_count is ALREADY cross-shard global (pmax'd
        # scale, psum'd count) — it must stay out of the psum below or
        # data-parallel runs would multiply it by the shard count
        from .ops.hist_pallas import quant_saturation_count
        qsat = quant_saturation_count(grad, hess, axis_name=axis_name)
    else:
        qsat = jnp.zeros((), f32)
    # watermark over FINITE scores only (a NaN would poison the max and
    # hide the magnitude trend that precedes overflow)
    finite = jnp.isfinite(score)
    smax = jnp.max(jnp.where(finite, jnp.abs(score), 0.0))
    vec_counts = jnp.stack(counts)
    if axis_name is not None:
        # wire-metrics coverage (ISSUE 5 / graftlint R1): tiny payloads,
        # but a full collective latency each — they belong in the
        # interconnect inventory like every other seam
        from . import telemetry
        telemetry.record_collective("health/vector_psum", "psum", axis_name,
                                    telemetry._tree_nbytes(vec_counts))
        telemetry.record_collective("health/score_pmax", "pmax", axis_name,
                                    telemetry._tree_nbytes(smax))
        vec_counts = jax.lax.psum(vec_counts, axis_name)
        smax = jax.lax.pmax(smax, axis_name)
    return jnp.concatenate([vec_counts, qsat[None], smax[None]])


@functools.lru_cache(maxsize=None)
def make_health_fn(quantized: bool, axis_name: Optional[str] = None):
    """Cached (grad, hess, score) -> [8] f32 closure for the fused chunk
    programs.  lru_cache keeps the closure identity stable so the chunk
    program caches (keyed on callable ids) hit across boosters."""
    def fn(grad, hess, score):
        return health_vector(grad, hess, score, quantized=quantized,
                             axis_name=axis_name)
    return fn


@functools.lru_cache(maxsize=None)
def _jitted_health(quantized: bool):
    """Per-iteration-path health program: one tiny jitted fn over the
    existing device arrays (grower programs and their caches untouched)."""
    import jax
    return jax.jit(functools.partial(health_vector, quantized=quantized))


def tree_health_counts(num_leaves: int, split_gain, leaf_count) -> dict:
    """Host-side tree health from an already-fetched TreeArrays: counts of
    zero/negative-gain recorded splits, empty leaves, and whether the tree
    is degenerate (unsplit root) — free with the model readback."""
    n = int(num_leaves)
    zero_gain = int(np.sum(np.asarray(split_gain)[:max(n - 1, 0)] <= 0.0))
    empty = int(np.sum(np.asarray(leaf_count)[:n] == 0)) if n > 1 else 0
    return {"zero_gain_splits": zero_gain, "empty_leaves": empty,
            "degenerate_trees": int(n <= 1)}


_told_auto_off = False


def resolve_enabled(health_setting: str) -> bool:
    """The ``health=`` resolution rule, single-homed: "auto" (default)
    follows the record SINK — a run with ``metrics_out=`` (or a library
    ``enable(jsonl_path)``) has somewhere to put the health blocks and
    turns the monitor on; "true"/"false" force it.  It does not follow
    the bare enabled flag: the monitor puts the
    health vector INTO the fused chunk program, so a run that arms
    telemetry only to read spans and counters — the benchmark's traced
    run — would train with another program than the timed run."""
    if health_setting == "true":
        return True
    if health_setting == "false":
        return False
    on = telemetry.sink_active()
    global _told_auto_off
    if telemetry.enabled() and not on and not _told_auto_off:
        # a library user who armed telemetry expecting the monitor (and
        # on_anomaly=halt with it) must hear, once, that it is not armed
        _told_auto_off = True
        log.info("telemetry is on without a metrics_out= sink: health=auto "
                 "leaves the training-health monitor off (health=true "
                 "turns it on)")
    return on


class HealthMonitor:
    """Per-booster health state: assembles iteration health blocks, applies
    the ``on_anomaly`` policy, tracks eval-metric divergence.

    The monitor never touches device state itself — GBDT hands it device
    vectors (or host numpy copies of them) and tree readbacks; everything
    here is host-side bookkeeping.
    """

    def __init__(self, on_anomaly: str = "warn",
                 divergence_rounds: int = 0, quantized: bool = False):
        self.on_anomaly = on_anomaly
        self.divergence_rounds = int(divergence_rounds)
        self.quantized = bool(quantized)
        self.totals: Dict[str, float] = {}
        self.anomalous_iterations = 0
        self._iter_tree: Dict[str, int] = {}
        self._warned: set = set()
        # eval divergence state: per "dataset/metric" key, the last value
        # and the current consecutive-worsening streak
        self._eval_last: Dict[str, float] = {}
        self._eval_streak: Dict[str, int] = {}
        self._pending_divergence: list = []

    # ------------------------------------------------------ device programs

    def grad_health_async(self, grad, hess, score):
        """Dispatch the health program and start its host copy; the result
        is fetched at finish_iteration, overlapping the link latency with
        the iteration's remaining device work."""
        vec = _jitted_health(self.quantized)(grad, hess, score)
        try:
            vec.copy_to_host_async()
        except Exception:
            pass
        return vec

    def chunk_health_fn(self, axis_name: Optional[str] = None):
        return make_health_fn(self.quantized, axis_name)

    # -------------------------------------------------------- accumulation

    def add_tree(self, num_leaves: int, split_gain, leaf_count) -> None:
        """Fold one tree's readback into the current iteration's counts."""
        for k, v in tree_health_counts(num_leaves, split_gain,
                                       leaf_count).items():
            self._iter_tree[k] = self._iter_tree.get(k, 0) + v

    def observe_eval(self, key: str, value: float,
                     bigger_better: bool) -> None:
        """Track one eval metric value; k consecutive worsening iterations
        (health_divergence_rounds) flag an ``eval_divergence`` anomaly."""
        if self.divergence_rounds <= 0:
            return
        last = self._eval_last.get(key)
        self._eval_last[key] = value
        if last is None:
            return
        if value != value:          # NaN metric: the most extreme
            worse = True            # divergence, not a streak reset
        elif last != last:
            worse = False           # recovery from NaN re-arms the streak
        else:
            worse = value < last if bigger_better else value > last
        streak = self._eval_streak.get(key, 0) + 1 if worse else 0
        self._eval_streak[key] = streak
        if streak >= self.divergence_rounds:
            self._pending_divergence.append(
                (key, streak, last, value))
            self._eval_streak[key] = 0   # re-arm, don't re-fire every iter

    # ------------------------------------------------------------- assembly

    def assemble(self, vec) -> dict:
        """Build the iteration's ``health`` block from the device vector
        (or None when the iteration produced no gradients) plus the
        accumulated tree counts.  Resets the per-iteration tree state."""
        block: Dict[str, float] = {}
        if vec is not None:
            vals = np.asarray(vec, np.float64)
            for i, k in enumerate(HEALTH_VEC_KEYS):
                block[k] = (float(vals[i]) if k == "score_max_abs"
                            else int(vals[i]))
        for k in TREE_HEALTH_KEYS:
            block[k] = self._iter_tree.get(k, 0)
        self._iter_tree = {}
        if self._pending_divergence:
            block["eval_divergence"] = [
                {"metric": k, "rounds": s,
                 "from": round(a, 6), "to": round(b, 6)}
                for k, s, a, b in self._pending_divergence]
        for k, v in block.items():
            if k == "eval_divergence":
                continue
            if k == "score_max_abs":
                self.totals[k] = max(self.totals.get(k, 0.0), v)
            else:
                self.totals[k] = self.totals.get(k, 0) + v
        return block

    def anomalies(self, block: dict) -> list:
        out = [k for k in ANOMALY_KEYS if block.get(k, 0)]
        out += ["eval_divergence:" + d["metric"]
                for d in block.get("eval_divergence", ())]
        return out

    def apply_policy(self, block: dict, iteration: int) -> None:
        """warn / halt / record on the iteration's anomalies.  Counters
        mirror every anomaly (``health/<kind>``) so cross-host aggregation
        and bench summaries see them regardless of policy."""
        found = self.anomalies(block)
        self._pending_divergence = []
        if not found:
            return
        self.anomalous_iterations += 1
        telemetry.count("health/anomalous_iterations")
        for kind in found:
            telemetry.count("health/" + kind.split(":")[0])
        detail = ", ".join(
            "%s=%s" % (k, block.get(k)) for k in ANOMALY_KEYS
            if block.get(k, 0))
        if block.get("eval_divergence"):
            detail = (detail + ("; " if detail else "")
                      + "eval divergence: " + ", ".join(
                          "%s (%d rounds)" % (d["metric"], d["rounds"])
                          for d in block["eval_divergence"]))
        if self.on_anomaly == "halt":
            log.error("training health anomaly at iteration %d (%s); "
                      "on_anomaly=halt — stopping" % (iteration, detail))
            raise TrainingHealthError(
                "training halted by health monitor at iteration %d: %s"
                % (iteration, detail))
        if self.on_anomaly == "warn":
            key = tuple(sorted(set(k.split(":")[0] for k in found)))
            if key not in self._warned:
                self._warned.add(key)
                log.warning("training health anomaly at iteration %d (%s); "
                            "recording every iteration, warning once per "
                            "anomaly kind (on_anomaly=warn)"
                            % (iteration, detail))

    def summary(self) -> dict:
        """Cumulative health totals (the end-of-run ``health`` summary
        block; bench.py attaches it to BENCH JSON lines)."""
        out = dict(self.totals)
        out["anomalous_iterations"] = self.anomalous_iterations
        return out
