"""Traffic kind ``rank``: one learning-to-rank training job, run for a
fixed time.

What a ``task=train objective=lambdarank`` user's process does after the
data is loaded: the booster the CLI would have built from the
configuration's ``key=value`` parameters over a table with query
boundaries, driven through ``GBDT.run_training`` in slices of a fixed
number of iterations until the window closes.  The table is made by
``harness/rank_data.py``: the query layout and the relevance function from
the configuration's ``layout`` block, the rows from the seed; it is binned
by the program's own ``Dataset.from_arrays`` (set-up).

The program's pairwise work is read from its own layout function
(``objectives.rank.query_layout``): a ``note rank layout`` line in every
run gives the real pairs and the slots computed for them.  A program
without that function cannot run this traffic, and stops at import.

Cell parameters (``cells/<cell>.json`` -> ``params``):
  slice_iters     iterations per ``run_training`` call (a multiple of 8)
  warmup_slices   slices run before the window; the first one compiles
  check_queries   whole queries, drawn from the seed, whose rows every
                  window tree is replayed over against the device's score
                  and whose bin codes are held against the reference's own
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lightgbm_tpu.objectives.rank import query_layout

from harness import lambdarank, reference
from harness.rank_data import make_rank_table
from traffic import train


class Job(train.Job):
    """The booster with its ranking table: what set-up builds, the window
    drives and the check reads (``train.Job``'s, over a table of
    queries)."""

    def __init__(self, ctx):
        from lightgbm_tpu import config as config_mod
        from lightgbm_tpu.io.dataset import Dataset
        from lightgbm_tpu.models.gbdt import GBDT
        from lightgbm_tpu.objectives import create_objective

        conf, cell = ctx.config, ctx.cell
        self.slice_iters = int(cell["params"]["slice_iters"])
        x, y, boundaries = make_rank_table(conf, ctx.seed, ctx.rows)
        ctx.mark("table")
        self.rows, self.features = x.shape
        self.boundaries = boundaries
        self.layout = query_layout(np.diff(boundaries))
        ctx.note("rank layout queries=%d pairs=%d pair_slots=%d fill=%.6f "
                 "classes=%s" % (boundaries.size - 1, self.layout.pairs,
                                 self.layout.pair_slots,
                                 self.layout.pairs / self.layout.pair_slots,
                                 list(self.layout.classes)))
        nq = boundaries.size - 1
        queries = np.sort(np.random.default_rng(ctx.seed).choice(
            nq, size=min(int(cell["params"]["check_queries"]), nq),
            replace=False))
        self.sample = np.concatenate([np.arange(boundaries[q],
                                                boundaries[q + 1])
                                      for q in queries])
        self.sample_values = x[self.sample]
        self.dataset = Dataset.from_arrays(
            x, y, max_bin=int(conf["params"]["max_bin"]),
            query_boundaries=boundaries)
        del x
        ctx.mark("binned")
        # the reference's inputs: the grades and query boundaries, the
        # sampled rows' values as the generator made them, and the binned
        # table, whose codes the check holds against the reference's own
        # binning of those rows
        self.bins = self.dataset.bins
        self.num_bins = np.asarray(self.dataset.num_bins)
        self.label = y
        params = config_mod.apply_aliases(
            config_mod.parse_argv(train._config_argv(conf)))
        overall = config_mod.OverallConfig()
        overall.set(params, require_data=False)
        self.overall = overall
        self.booster = GBDT()
        self.booster.init(
            overall.boosting_config, self.dataset,
            create_objective(overall.objective_type,
                             overall.objective_config))
        ctx.mark("booster")


def run(ctx) -> dict:
    """Set-up, window, check.  Returns the harness's result fields."""
    import jax

    job = Job(ctx)
    if ctx.fault is not None:
        ctx.fault("job", job=job)
    cell = ctx.cell["params"]
    for _ in range(int(cell["warmup_slices"])):
        job.slice()
    ctx.mark("warm")
    models_before = len(job.booster.models)
    score_before = job.score()

    ctx.window_opens()
    spans = []                     # (start, end) of each slice, host clock
    t0 = time.perf_counter()
    slowest = 0.0
    while True:
        traced = ctx.trace_slice(len(spans))
        s0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/slice"):
            job.slice()
        s1 = time.perf_counter()
        if traced:
            ctx.trace_stop(s0 - t0, s1 - t0, job.slice_iters)
        spans.append((s0 - t0, s1 - t0))
        slowest = max(slowest, s1 - s0)
        if (s1 - t0) + slowest > ctx.seconds:
            break
    window_s = spans[-1][1]
    ctx.window_closes()

    attempted = len(spans) * job.slice_iters
    score_after = job.score()
    trees = job.answers(models_before)
    tree_conf = job.overall.boosting_config.tree_config
    obj_conf = job.overall.objective_config
    params = {"min_data_in_leaf": float(tree_conf.min_data_in_leaf),
              "min_sum_hessian_in_leaf":
                  float(tree_conf.min_sum_hessian_in_leaf),
              "learning_rate":
                  float(job.overall.boosting_config.learning_rate),
              "sigmoid": float(obj_conf.sigmoid),
              "label_gain": np.asarray(obj_conf.label_gain, np.float64),
              "max_position": int(obj_conf.max_position),
              "max_bin": int(ctx.config["params"]["max_bin"])}
    ctx.read_memory_peak()
    ctx.read_counters()
    if ctx.fault is not None:
        ctx.fault("answers", job=job, trees=trees, score_after=score_after)
    table = (job.bins, job.num_bins, job.label, job.boundaries, job.sample,
             job.sample_values)
    job.free()

    readings = check(ctx, trees, table, score_before, score_after, params)
    return {
        "attempted": attempted,
        "failed": attempted - len(trees),
        "end_to_end": {"train_iters_per_s": attempted / window_s},
        "window_s": window_s,
        "slices": spans,
        "shape": {"rows": job.rows, "features": job.features,
                  "num_leaves": int(ctx.config["params"]["num_leaves"]),
                  "queries": int(job.boundaries.size - 1)},
        "readings": readings.pop("program"),
        "in_its_place": readings,
    }


def _replay_float32(score, trees, leaves, learning_rate) -> np.ndarray:
    """``score`` with the trees added as the program adds them: each leaf's
    value times the float32 learning rate, added in float32, one tree at
    a time (a one-leaf tree adds nothing).  The trees' values come back
    shrunk in float64; the float32 value before shrinking is recovered
    exactly, as it is the nearest float32 to their quotient."""
    lr = np.float32(learning_rate)
    out = np.array(score, np.float32)
    for tree, leaf in zip(trees, leaves):
        if tree.num_leaves > 1:
            raw = (tree.leaf_value / learning_rate).astype(np.float32)
            out += (raw * lr)[leaf]
    return out


def _bin_code_gap(values, codes, max_bin) -> float:
    """``reference.bin_code_gap``, its columns shared out to threads."""
    parts = np.array_split(np.arange(values.shape[1]), lambdarank.THREADS)
    with ThreadPoolExecutor(lambdarank.THREADS) as pool:
        return max(pool.map(lambda cols: reference.bin_code_gap(
            values[:, cols], codes[cols], max_bin), parts))


def check(ctx, trees, table, score_before, score_after, params) -> dict:
    """The numbers ``correct`` is decided by (PERF.md section 2), under
    ``program``: the first and the last tree of the window against the
    reference, teacher forced on the reference's own lambdarank gradients,
    every window tree replayed against the device's score over the sampled
    queries' rows, and the program's bin codes against the reference's.
    With ``--control 1`` the same numbers of what stands in the program's
    place, each under its own name: the control and two faults."""
    bins, num_bins, label, boundaries, sample, sample_values = table
    out = {"program": {}}

    def gradients(score):
        return lambdarank.gradients(
            score, label, boundaries, params["label_gain"],
            params["max_position"], params["sigmoid"])

    replay = reference.replay_sum(trees, bins, sample)
    out["program"]["score_gap"] = float(np.max(np.abs(
        score_after[sample].astype(np.float64)
        - (score_before[sample].astype(np.float64) + replay)))) \
        if trees else float("inf")
    out["program"]["bin_code_gap"] = _bin_code_gap(
        sample_values, bins[:, sample], params["max_bin"])
    lower = reference.LOWER[ctx.config["control_precision"]] \
        if ctx.control else None
    judged = []
    leaves = [reference.route(t, bins) for t in trees]
    if trees:
        grad, hess = gradients(score_before)
        ctx.note("first tree: root sum_hessian=%.6g sum_abs_gradient=%.6g"
                 % (hess.sum(), np.abs(grad).sum()))
        judged.append(reference.judge_tree(
            trees[0], bins, num_bins, grad, hess, params, lower=lower,
            faults=ctx.control, leaf=leaves[0]))
    if len(trees) > 1:
        # the device's own score before the last tree, to the bit: the
        # gradients depend on the order of each query's scores, ties
        # included, which a score taken back from the one after it
        # rounds away
        before_last = _replay_float32(score_before, trees[:-1], leaves[:-1],
                                      params["learning_rate"])
        after = _replay_float32(before_last, trees[-1:], leaves[-1:],
                                params["learning_rate"])
        ctx.note("replay: %d of %d rows differ from the device's score "
                 "after the window" % (int(np.sum(after != score_after)),
                                       after.size))
        judged.append(reference.judge_tree(
            trees[-1], bins, num_bins, *gradients(before_last), params,
            lower=lower, faults=ctx.control, leaf=leaves[-1]))
    for which, j in zip(("first tree", "last tree"), judged):
        for line in j["detail"]:
            ctx.note("%s: %s" % (which, line))
    attainable = sum(j["attainable"] for j in judged)
    for who in ("program", "control", "fault_half_batch"):
        if not judged or who not in judged[0]:
            continue
        mine = out.setdefault(who, {})
        if "missed" in judged[0][who]:
            mine["split_gain_gap"] = (
                sum(j[who]["missed"] for j in judged) / attainable
                if attainable > 0 else float("inf"))
        gaps = np.concatenate([j[who]["leaf_value_gaps"] for j in judged])
        mine["leaf_value_gap"] = float(np.median(gaps))
        mine["leaf_value_gap_widest"] = float(gaps.max())
        mine["leaf_sum_gap"] = float(max(j[who]["leaf_sum_gaps"].max()
                                         for j in judged))
    if ctx.control:
        # a step that leaves its state unchanged: the score after the
        # window is the score before it
        out["fault_state_unchanged"] = {"score_gap": float(
            np.max(np.abs(replay))) if trees else float("inf")}
    out["program"]["trees_short"] = float(
        sum(t.num_leaves <= 1 for t in trees))
    return out
