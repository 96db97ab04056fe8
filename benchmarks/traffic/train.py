"""Traffic kind ``train``: one training job, run for a fixed time.

What a ``task=train`` user's process does after the data is loaded: the
booster the CLI would have built from the configuration's ``key=value``
parameters, driven through ``GBDT.run_training`` in slices of a fixed number
of iterations until the window closes.  The table is made from the seed and
binned by the program's own ``Dataset.from_arrays`` (set-up).

Cell parameters (``cells/<cell>.json`` -> ``params``):
  slice_iters     iterations per ``run_training`` call (a multiple of 8)
  warmup_slices   slices run before the window; the first one compiles
  check_rows      rows, drawn from the seed, over which every window tree is
                  replayed against the device's score and the program's bin
                  codes are held against the reference's own
"""
from __future__ import annotations

import time

import numpy as np

from harness import reference
from harness.data import make_table


def _config_argv(config: dict) -> list:
    return ["%s=%s" % kv for kv in sorted(config["params"].items())]


class Job:
    """The booster with its table: what set-up builds, the window drives
    and the check reads.  One object, handed from one to the next."""

    def __init__(self, ctx):
        from lightgbm_tpu import config as config_mod
        from lightgbm_tpu.io.dataset import Dataset
        from lightgbm_tpu.models.gbdt import GBDT
        from lightgbm_tpu.objectives import create_objective

        conf, cell = ctx.config, ctx.cell
        self.rows = int(ctx.rows or conf["rows"])
        self.features = int(conf["features"])
        self.slice_iters = int(cell["params"]["slice_iters"])
        x, y = make_table(self.rows, self.features, ctx.seed)
        ctx.mark("table")
        self.sample = np.sort(np.random.default_rng(ctx.seed).choice(
            self.rows, size=min(int(cell["params"]["check_rows"]),
                                self.rows), replace=False))
        self.sample_values = x[self.sample]
        self.dataset = Dataset.from_arrays(
            x, y, max_bin=int(conf["params"]["max_bin"]))
        del x
        ctx.mark("binned")
        # the reference's inputs: the labels and the sampled rows' values as
        # the generator made them, and the binned table, whose codes the
        # check holds against the reference's own binning of those rows
        self.bins = self.dataset.bins
        self.num_bins = np.asarray(self.dataset.num_bins)
        self.label = y
        params = config_mod.apply_aliases(
            config_mod.parse_argv(_config_argv(conf)))
        overall = config_mod.OverallConfig()
        overall.set(params, require_data=False)
        self.overall = overall
        self.booster = GBDT()
        self.booster.init(
            overall.boosting_config, self.dataset,
            create_objective(overall.objective_type,
                             overall.objective_config))
        ctx.mark("booster")

    def slice(self) -> None:
        """One ``run_training`` call, to the end of its device work."""
        self.booster.run_training(self.slice_iters, is_eval=False)
        self.booster.score.block_until_ready()

    def score(self) -> np.ndarray:
        return np.array(self.booster.score[0], np.float32)

    def answers(self, start: int) -> list:
        """The trees the program read back since model ``start``."""
        return [reference.TreeAnswer(t.num_leaves, t.split_feature,
                                     t.threshold_bin, t.left_child,
                                     t.right_child, t.leaf_value)
                for t in self.booster.models[start:]]

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.booster = None
        self.dataset = None


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
    params = {"min_data_in_leaf": float(tree_conf.min_data_in_leaf),
              "min_sum_hessian_in_leaf":
                  float(tree_conf.min_sum_hessian_in_leaf),
              "learning_rate":
                  float(job.overall.boosting_config.learning_rate),
              "sigmoid": float(job.overall.objective_config.sigmoid),
              "max_bin": int(ctx.config["params"]["max_bin"])}
    ctx.read_memory_peak()
    ctx.read_counters()
    if ctx.fault is not None:
        ctx.fault("answers", job=job, trees=trees, score_after=score_after)
    table = (job.bins, job.num_bins, job.label, job.sample,
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
                  "num_leaves": int(ctx.config["params"]["num_leaves"])},
        "readings": readings.pop("program"),
        "in_its_place": readings,
    }


def check(ctx, trees, table, score_before, score_after, params) -> dict:
    """The numbers ``correct`` is decided by (PERF.md section 2), under
    ``program``: the first and the last tree of the window against the
    reference, teacher forced, every window tree replayed against the
    device's score, and the program's bin codes against the reference's.
    With ``--control 1`` the same numbers of what stands in the program's
    place, each under its own name: the control and two faults."""
    bins, num_bins, label, sample, sample_values = table
    out = {"program": {}}
    replay = reference.replay_sum(trees, bins, sample)
    out["program"]["score_gap"] = float(np.max(np.abs(
        score_after[sample].astype(np.float64)
        - (score_before[sample].astype(np.float64) + replay)))) \
        if trees else float("inf")
    out["program"]["bin_code_gap"] = reference.bin_code_gap(
        sample_values, bins[:, sample], params["max_bin"])
    lower = reference.LOWER[ctx.config["control_precision"]] \
        if ctx.control else None
    judged = []
    if trees:
        first = reference.judge_tree(
            trees[0], bins, num_bins,
            *reference.logloss_gradients(score_before, label,
                                         params["sigmoid"]),
            params, lower=lower, faults=ctx.control)
        judged.append(first)
    if len(trees) > 1:
        last = trees[-1]
        leaf = reference.route(last, bins)
        before_last = score_after.astype(np.float64) - last.leaf_value[leaf]
        judged.append(reference.judge_tree(
            last, bins, num_bins,
            *reference.logloss_gradients(before_last, label,
                                         params["sigmoid"]),
            params, lower=lower, faults=ctx.control, leaf=leaf))
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
        # of the shares of the value the median leaf is compared, steady
        # from seed to seed, and the widest only printed: it swings with
        # one small leaf's inherited noise.  The tail that is compared is
        # the widest gradient sum (PERF.md section 2)
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
