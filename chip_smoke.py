#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that lightgbm_tpu still starts on the chip.

One process drives the main path once through the entry point a user calls
(``lightgbm_tpu.cli.main``) at the full width of the one supported
configuration — Higgs shape: 28 dense numerical columns, ``max_bin=255``,
``num_leaves=255``, binary logloss — on a table generated from ``--seed``.
Rows are the only thing cut (1,000,000 of the README's 1M / 11M scales).

Default (one chip), three phases in this process:
  (a) ``task=train`` on the DEFAULT routes (leaf-wise, float32 histograms):
      on a TPU that is the compacted grower + Pallas partition + Pallas
      float histogram;
  (b) ``task=train grow_policy=depthwise hist_dtype=int8`` through the fused
      chunk program, 16 iterations = two chunks (a warm one after the one
      that compiles), loaded with ``ingest_workers=2`` (JAX-free children
      next to a live chip);
  (c) ``task=predict`` on the held-out file with (a)'s model through the
      compiled serving engine.
Checks (any failure -> non-zero exit, no result line): training metric
falls; (c)'s result file equals a host-only NumPy replay of the saved model
text; held-out AUC of (a) and (b) is near a scikit-learn anchor trained on
the same generated data; the route counters show the device routes ran.

``--chips 4`` runs ONLY (b) serially and (b) with ``tree_learner=data
num_machines=4`` and compares the two models, the mesh and the residency
of the bin matrix.  ``--rows`` rehearses the same phases small (e.g. under
``JAX_PLATFORMS=cpu``); without a TPU the script still exits non-zero and
prints no result line.

Output: one JSON object per line (observations, not metrics); the LAST line
is ``{"ok": true, "device": {...}}`` and is printed only on success.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

FEATURES = 28
MAX_BIN = 255
NUM_LEAVES = 255
LEARNING_RATE = 0.1
MIN_DATA_IN_LEAF = 100          # the CLI default, given to the anchor too
FULL_ROWS = 1_000_000           # the smaller of the README's two scales
ITERS_A = 6                     # per-iteration path: 1 compiling + 5 steady
ITERS_B = 16                    # two chunks of the default chunk_size=8

# (c) vs the host replay.  The result file holds "%.6f" text (<= 5e-7 of
# rounding); the engine sums float32 leaf values on the device where the
# replay sums them in float64 (a few 6e-8 ulps at |score| < 2), and the
# sigmoid's slope is <= 0.5.  2e-6 covers both with margin; a wrong leaf
# moves a row by >= 1e-3.
REPLAY_ATOL = 2e-6

# Held-out AUC vs scikit-learn's HistGradientBoostingClassifier (same
# iterations, leaves, bins, learning rate, min leaf size; an independent
# histogram GBDT).  The two differ in bin boundaries, leaf regularisation
# (min_sum_hessian_in_leaf=10 here), growth order for (b) (level-wise vs
# best-first) and int8 gradient quantisation for (b).  Seen so far: (a)
# 0.0005 and (b) 0.008 at 1M rows on the chip, 0.005 / 0.002 at 20k rows
# on the CPU (PR 24); a broken grower (wrong partition, dropped rows,
# garbage histograms) costs > 0.05.
AUC_TOL = 0.02

ZERO_COUNTERS = ("hist/pallas_ineligible", "hist/env_no_pallas",
                 "hist/env_force_einsum", "partition/pallas_ineligible",
                 "partition/route_xla",
                 "costmodel/aot_call_fallback", "costmodel/capture_failed")

FAILURES: list = []


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def check(ok: bool, what: str) -> bool:
    if not ok:
        FAILURES.append(what)
        print("CHECK FAILED: " + what, file=sys.stderr, flush=True)
    return bool(ok)


# ------------------------------------------------------------------ data

def make_table(rows: int, features: int, seed: int):
    """bench.make_data(narrow_features=0): every column continuous, so
    mixed_bin=auto resolves to the uniform layout and the 255-bin kernel
    class does the work.  Copied so no JAX import precedes the device
    check."""
    import numpy as np
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, features).astype(np.float32)
    w = rng.randn(features) / np.sqrt(features)
    logits = x @ w + 0.5 * np.sin(x[:, 0] * 2) + 0.3 * x[:, 1] * x[:, 2]
    y = (logits + rng.randn(rows) * 0.5 > 0).astype(np.int8)
    return x, y


def write_table(path: str, x, y) -> str:
    """Tab-separated text, label in column 0, shortest round-trip float32
    repr.  pyarrow's writer does 1M x 29 in seconds; pandas is the
    fallback."""
    try:
        import pyarrow as pa
        import pyarrow.csv as pacsv
        cols = [pa.array(y)] + [pa.array(x[:, j]) for j in range(x.shape[1])]
        table = pa.Table.from_arrays(
            cols, names=[str(i) for i in range(len(cols))])
        pacsv.write_csv(table, path, pacsv.WriteOptions(
            include_header=False, delimiter="\t"))
        return "pyarrow"
    except ImportError:
        import pandas as pd
        df = pd.DataFrame(x)
        df.insert(0, "y", y)
        df.to_csv(path, sep="\t", header=False, index=False)
        return "pandas"


def auc(y, score) -> float:
    import numpy as np
    from scipy.stats import rankdata
    y = np.asarray(y) > 0
    r = rankdata(score)
    n_pos, n_neg = int(y.sum()), int((~y).sum())
    return float((r[y].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


def sklearn_anchor(x, y, xv, yv, iters: int) -> float:
    from sklearn.ensemble import HistGradientBoostingClassifier
    clf = HistGradientBoostingClassifier(
        learning_rate=LEARNING_RATE, max_iter=iters,
        max_leaf_nodes=NUM_LEAVES, max_bins=MAX_BIN,
        min_samples_leaf=MIN_DATA_IN_LEAF, l2_regularization=0.0,
        early_stopping=False, random_state=0)
    clf.fit(x, y)
    return auc(yv, clf.decision_function(xv))


# ----------------------------------------------------------- host replay

def replay_model(model_path: str, features):
    """Host-only reference: Tree.from_string of every tree in the saved
    model TEXT, Tree.predict (NumPy) summed in float64.  Never touches the
    device.  Returns (raw_score, sigmoid_param, trees)."""
    import numpy as np
    from lightgbm_tpu.models.tree import Tree
    text = open(model_path).read()
    head = text.split("Tree=", 1)[0]
    sigmoid = float(re.search(r"^sigmoid=(\S+)", head, re.M).group(1))
    blocks = re.split(r"^Tree=\d+\n", text, flags=re.M)[1:]
    blocks[-1] = blocks[-1].split("feature importances:")[0]
    trees = [Tree.from_string(b) for b in blocks]
    raw = np.zeros(features.shape[0], dtype=np.float64)
    for t in trees:
        raw += t.predict(features)
    return raw, sigmoid, trees


# ------------------------------------------------------------ CLI driving

class LogTee:
    """The CLI's log stream: forwards to stderr (stdout is reserved for
    the JSON lines) and keeps (clock, line) so progress lines can be
    timed."""

    def __init__(self):
        self.lines: list = []

    def write(self, text: str) -> None:
        now = time.perf_counter()
        for line in text.splitlines():
            if line:
                self.lines.append((now, line))
        sys.stderr.write(text)

    def flush(self) -> None:
        sys.stderr.flush()

    def take(self) -> list:
        lines, self.lines = self.lines, []
        return lines


_PROGRESS = re.compile(
    r"([0-9.]+) seconds elapsed, finished (\d+) iteration")


class CacheCounts:
    """Persistent-compile-cache hits and misses, as JAX itself counts
    them (jax.monitoring events)."""

    def __init__(self):
        from jax import monitoring
        self.hits = 0
        self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"hits": self.hits, "misses": self.misses}
        self.hits = self.misses = 0
        return out


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cli(tag: str, argv: list, tee: LogTee, cache: CacheCounts) -> dict:
    """One cli.main call plus what it left behind: wall seconds, the
    progress lines' clocks, telemetry counters, compile records."""
    from lightgbm_tpu import cli, telemetry
    tee.take()
    t0 = time.perf_counter()
    rc = cli.main(argv)
    wall = time.perf_counter() - t0
    # cli.main disarms telemetry in its finally; snapshot() keeps the data
    snap = telemetry.snapshot()
    progress = [(float(m.group(1)), int(m.group(2)))
                for _t, line in tee.take()
                for m in [_PROGRESS.search(line)] if m]
    obs = {"phase": tag, "rc": rc, "wall_s": round(wall, 3),
           "progress": progress,
           "counters": {k: v for k, v in sorted(snap["counters"].items())
                        if k.split("/")[0] in ("hist", "partition",
                                               "costmodel", "jit", "serve",
                                               "ingest", "bagging",
                                               "lookup")},
           "compile_cache": cache.take(),
           "peak_bytes_in_use": peak_bytes()}
    comp = snap.get("compile") or {}
    obs["compile_s"] = {p["name"]: p.get("compile_seconds")
                        for p in comp.get("programs", [])
                        if isinstance(p, dict) and "name" in p}
    check(rc == 0, "%s: cli.main returned %r" % (tag, rc))
    return obs


def train_phase(tag: str, work: str, train_path: str, iters: int,
                extra: list, tee: LogTee, cache: CacheCounts):
    metrics_path = os.path.join(work, tag + ".metrics.jsonl")
    model_path = os.path.join(work, tag + ".model.txt")
    argv = ["task=train", "data=" + train_path, "objective=binary",
            "metric=binary_logloss", "is_training_metric=true",
            "metric_freq=1", "num_trees=%d" % iters,
            "num_leaves=%d" % NUM_LEAVES, "max_bin=%d" % MAX_BIN,
            "learning_rate=%g" % LEARNING_RATE,
            "output_model=" + model_path,
            # arms telemetry (route counters, memory gauges, cost capture)
            "metrics_out=" + metrics_path] + extra
    obs = run_cli(tag, argv, tee, cache)
    recs = []
    if os.path.exists(metrics_path):
        recs = [json.loads(line) for line in open(metrics_path)
                if line.strip()]
    it_recs = [r for r in recs if "iter" in r]
    losses = [list(r.get("eval_metrics", {}).values())[0][0]
              for r in it_recs if r.get("eval_metrics")]
    obs["train_logloss"] = [round(v, 6) for v in losses]
    check(len(losses) == iters,
          "%s: %d metric records for %d iterations"
          % (tag, len(losses), iters))
    check(len(losses) >= 2 and all(b < a for a, b in
                                   zip(losses, losses[1:])),
          "%s: training logloss does not fall every iteration: %s"
          % (tag, losses))
    # first call (compile + run) vs steady, from the CLI's own progress
    # lines (one per iteration on the per-iteration path, one per chunk
    # on the fused path)
    prog = obs["progress"]
    if prog:
        first_t, first_n = prog[0]
        obs["first_call_s"] = round(first_t, 3)
        obs["first_call_iters"] = first_n
        if len(prog) > 1:
            gaps = [b[0] - a[0] for a, b in zip(prog, prog[1:])]
            per_iter = sorted(g / max(b[1] - a[1], 1) for g, a, b
                              in zip(gaps, prog, prog[1:]))
            obs["steady_s_per_iter_median"] = round(
                per_iter[len(per_iter) // 2], 4)
            obs["longest_steady_dispatch_s"] = round(max(gaps), 3)
    # the longest single host-visible span in any record (a dispatch the
    # host waited on: model_readback blocks on the grow dispatch)
    longest = max(((v, k, r["iter"]) for r in it_recs
                   for k, v in r.get("phase_times", {}).items()),
                  default=None)
    if longest:
        obs["longest_span"] = {"seconds": longest[0], "phase": longest[1],
                               "iter": longest[2]}
    return obs, model_path


def check_routes(obs: dict, want_partition: bool) -> None:
    tag, c = obs["phase"], obs["counters"]
    check(any(v > 0 for k, v in c.items()
              if k.startswith("hist/pallas_kernel_")),
          "%s: no hist/pallas_kernel_* counter — the Pallas histogram "
          "kernels did not run (%s)" % (tag, c))
    zero = ZERO_COUNTERS + (("partition/xla",) if want_partition else ())
    for k in zero:
        check(c.get(k, 0) == 0, "%s: %s = %s, want 0" % (tag, k, c.get(k)))
    if want_partition:
        check(c.get("partition/pallas", 0) > 0,
              "%s: partition/pallas = 0 — the Pallas partition kernel "
              "did not run" % tag)


# --------------------------------------------------------------- phases

def one_chip(args, work: str, device: dict, tee, cache) -> None:
    import numpy as np
    import pandas as pd

    t0 = time.perf_counter()
    x, y = make_table(args.rows + args.valid_rows, FEATURES, args.seed)
    xt, yt = x[:args.rows], y[:args.rows]
    xv, yv = x[args.rows:], y[args.rows:]
    train_path = os.path.join(work, "higgs_shape.train.tsv")
    valid_path = os.path.join(work, "higgs_shape.valid.tsv")
    writer = write_table(train_path, xt, yt)
    write_table(valid_path, xv, yv)
    emit({"phase": "data", "rows": args.rows, "valid_rows": args.valid_rows,
          "columns": FEATURES, "max_bin": MAX_BIN, "num_leaves": NUM_LEAVES,
          "seed": args.seed, "writer": writer,
          "cut": ("rows only: %d of the README's 1M / 11M Higgs scales; "
                  "columns, bins and leaves are at full width" % args.rows),
          "train_bytes": os.path.getsize(train_path),
          "seconds": round(time.perf_counter() - t0, 2), "device": device})

    # (a) default routes
    obs_a, model_a = train_phase("a_leafwise_f32_default", work, train_path,
                                 ITERS_A, [], tee, cache)
    from lightgbm_tpu.io import parser as parser_mod
    from lightgbm_tpu.native import lib as native_lib
    obs_a["parser_tier_calls"] = dict(parser_mod.TIER_CALLS)
    obs_a["device"] = device
    check_routes(obs_a, want_partition=True)
    emit(obs_a)

    # (b) README headline route, fused chunk program, parallel ingest
    obs_b, model_b = train_phase(
        "b_depthwise_int8_chunk", work, train_path, ITERS_B,
        ["grow_policy=depthwise", "hist_dtype=int8", "ingest_workers=2"],
        tee, cache)
    obs_b["device"] = device
    check_routes(obs_b, want_partition=False)
    check(len(obs_b["progress"]) == 2,
          "b: expected two chunk dispatches, progress lines say %s"
          % obs_b["progress"])
    emit(obs_b)

    # (c) predict through the serving engine
    result_path = os.path.join(work, "predict_result.txt")
    obs_c = run_cli("c_predict_serving", [
        "task=predict", "data=" + valid_path, "input_model=" + model_a,
        "output_result=" + result_path,
        "metrics_out=" + os.path.join(work, "c.metrics.jsonl")], tee, cache)
    obs_c["device"] = device
    check(obs_c["counters"].get("costmodel/aot_call_fallback", 0) == 0,
          "c: costmodel/aot_call_fallback > 0")

    # the native helper must be the one built from this checkout's source
    so = native_lib.loaded_path()
    src = os.path.join(os.path.dirname(native_lib.__file__), "src",
                       "lgbm_native.cpp")
    tier = {"native_so": so, "parser_tier_calls": dict(parser_mod.TIER_CALLS)}
    if so is not None:
        tier["so_older_than_source"] = (os.path.getmtime(so)
                                        < os.path.getmtime(src))
        check(not tier["so_older_than_source"],
              "native .so is older than native/src/lgbm_native.cpp")
    check(so is not None and parser_mod.TIER_CALLS["native"] > 0
          and parser_mod.TIER_CALLS["exact"] == 0,
          "text parsing did not run on the native tier: %s" % tier)
    obs_c.update(tier)

    # replay: parse the held-out text the way any correct reader does
    feats = pd.read_csv(valid_path, sep="\t", header=None,
                        float_precision="round_trip").to_numpy()[:, 1:]
    raw_a, sig_a, trees_a = replay_model(model_a, feats)
    want = 1.0 / (1.0 + np.exp(-2.0 * sig_a * raw_a))
    got = (np.loadtxt(result_path) if os.path.exists(result_path)
           else np.zeros(0))
    ok_shape = check(got.shape == want.shape,
                     "c: result file has shape %s, want %s"
                     % (got.shape, want.shape))
    if ok_shape:
        err = float(np.max(np.abs(got - want)))
        obs_c["replay_max_abs_err"] = err
        obs_c["replay_atol"] = REPLAY_ATOL
        check(bool(np.all(np.isfinite(got))) and err <= REPLAY_ATOL,
              "c: result file differs from the host replay by %g > %g"
              % (err, REPLAY_ATOL))
    emit(obs_c)

    # held-out AUC vs an anchor independent of this code
    raw_b, _sig_b, trees_b = replay_model(model_b, feats)
    quality = {"phase": "quality", "auc_tol": AUC_TOL, "device": device}
    for tag, raw, trees, iters in (("a", raw_a, trees_a, ITERS_A),
                                   ("b", raw_b, trees_b, ITERS_B)):
        t0 = time.perf_counter()
        anchor = sklearn_anchor(xt, yt, xv, yv, iters)
        ours = auc(yv, raw)
        quality[tag] = {"auc": round(ours, 5),
                        "sklearn_auc": round(anchor, 5),
                        "trees": len(trees),
                        "max_leaves": max(t.num_leaves for t in trees),
                        "anchor_seconds": round(time.perf_counter() - t0, 2)}
        check(len(trees) == iters, "%s: %d trees saved, want %d"
              % (tag, len(trees), iters))
        check(abs(ours - anchor) <= AUC_TOL,
              "%s: held-out AUC %.5f vs scikit-learn %.5f, tolerance %g"
              % (tag, ours, anchor, AUC_TOL))
    emit(quality)


def four_chips(args, work: str, device: dict, tee, cache) -> None:
    """Serial (b) vs data-parallel (b) over a four-device mesh."""
    import numpy as np
    from lightgbm_tpu import cli
    from lightgbm_tpu.parallel import learners

    check(args.rows % 4 == 0, "--rows must divide by 4 for --chips 4")
    x, y = make_table(args.rows, FEATURES, args.seed)
    train_path = os.path.join(work, "higgs_shape.train.tsv")
    write_table(train_path, x, y)
    emit({"phase": "data", "rows": args.rows, "columns": FEATURES,
          "max_bin": MAX_BIN, "num_leaves": NUM_LEAVES, "seed": args.seed,
          "cut": "rows only (%d)" % args.rows, "device": device})

    route = ["grow_policy=depthwise", "hist_dtype=int8"]
    obs_s, model_s = train_phase("b_serial_one_device", work, train_path,
                                 ITERS_B, route, tee, cache)
    obs_s["device"] = device
    check_routes(obs_s, want_partition=False)
    emit(obs_s)

    kept = []

    class KeepApplication(cli.Application):
        """cli.main builds its Application internally; keep a handle so
        the booster's device state can be inspected afterwards."""

        def __init__(self, argv):
            super().__init__(argv)
            kept.append(self)

    real_application, cli.Application = cli.Application, KeepApplication
    try:
        obs_d, model_d = train_phase(
            "b_data_parallel_4", work, train_path, ITERS_B,
            route + ["tree_learner=data", "num_machines=4"], tee, cache)
    finally:
        cli.Application = real_application
    obs_d["device"] = device
    check_routes(obs_d, want_partition=False)

    booster = kept[-1].boosting if kept else None
    if check(booster is not None and booster._learner is not None,
             "data-parallel run left no learner to inspect"):
        mesh = booster._learner._mesh()
        mesh_devs = list(mesh.devices.flat)
        obs_d["mesh"] = {"size": len(mesh_devs),
                         "platforms": sorted({d.platform
                                              for d in mesh_devs})}
        check(len(mesh_devs) == 4 and len(set(mesh_devs)) == 4,
              "mesh has %d devices, asked for 4" % len(mesh_devs))
        check(args.rehearse or obs_d["mesh"]["platforms"] == ["tpu"],
              "mesh devices are %s, want tpu" % obs_d["mesh"]["platforms"])
        # the matrix the chunk program consumes (padded to the shard grid)
        bins = getattr(booster, "_dp_chunk_inputs",
                       (None, booster.bins_device))[1]
        shards = [(str(s.device), tuple(s.data.shape))
                  for s in bins.addressable_shards]
        obs_d["bins_shards"] = shards
        rows_on = [shape[-1] for _d, shape in shards]
        check(len({d for d, _s in shards}) == 4
              and all(abs(r - args.rows / 4) <= args.rows * 0.01
                      for r in rows_on),
              "bin matrix is not resident as ~N/4 rows on each of four "
              "devices: %s" % shards)
        texts = [compiled.as_text()
                 for prog in learners._DP_CHUNK_PROGRAMS.values()
                 for _rec, compiled in prog._cache.values()
                 if compiled is not None]
        found = sorted({op for t in texts for op in
                        ("all-reduce", "reduce-scatter", "all-gather",
                         "all-to-all", "collective-permute") if op in t})
        obs_d["collectives_in_compiled_text"] = found
        check(bool(found), "no collective in the compiled chunk/dp text "
              "(%d programs captured)" % len(texts))

    # the two models: same structure, leaf values within a tolerance
    _r, _s, trees_s = replay_model(model_s, np.zeros((1, FEATURES)))
    _r, _s, trees_d = replay_model(model_d, np.zeros((1, FEATURES)))
    cmp = {"phase": "serial_vs_data_parallel", "trees": len(trees_d),
           "device": device}
    check(len(trees_s) == len(trees_d) == ITERS_B,
          "tree counts differ: serial %d, data-parallel %d"
          % (len(trees_s), len(trees_d)))
    struct_diff, thr_diff, max_leaf_err, bit_equal = [], [], 0.0, True
    for k, (ts, td) in enumerate(zip(trees_s, trees_d)):
        if (ts.num_leaves != td.num_leaves or not np.array_equal(
                ts.split_feature_real, td.split_feature_real)):
            struct_diff.append(k)
            continue
        if not np.array_equal(ts.threshold, td.threshold):
            thr_diff.append(k)
        err = float(np.max(np.abs(ts.leaf_value - td.leaf_value)))
        max_leaf_err = max(max_leaf_err, err)
        bit_equal = bit_equal and err == 0.0
    cmp.update(trees_with_different_structure=struct_diff,
               trees_with_different_thresholds=thr_diff,
               max_abs_leaf_value_diff=max_leaf_err,
               leaf_values_bit_equal=bit_equal,
               # int8 histograms accumulate in int32 (order-free), so the
               # README claims bit equality; the dequantise + leaf-output
               # arithmetic is compiled into a different program under
               # shard_map and may fuse differently: allow 1e-6 absolute
               # (leaf values are O(0.1); float32 ulp there is 7e-9)
               leaf_value_atol=1e-6)
    check(not struct_diff and not thr_diff,
          "serial and data-parallel trees differ in structure: trees %s, "
          "thresholds: trees %s" % (struct_diff, thr_diff))
    check(max_leaf_err <= 1e-6,
          "leaf values differ by %g > 1e-6" % max_leaf_err)
    emit(obs_d)
    emit(cmp)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (default %d); giving it is how a "
                         "small rehearsal is asked for" % FULL_ROWS)
    ap.add_argument("--valid-rows", type=int, default=None,
                    help="held-out rows (default rows/10)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the serial vs data-parallel comparison")
    args = ap.parse_args()
    args.rehearse = args.rows is not None
    args.rows = args.rows or FULL_ROWS
    if args.valid_rows is None:
        args.valid_rows = max(args.rows // 10, 1000)

    # the package first: it places the compile cache before any compile
    import lightgbm_tpu
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu" and device["count"] == args.chips
    if not on_chip and not args.rehearse:
        # no accelerator (or not the asked number): no phase at the real
        # size, no result line
        print("chip_smoke: need %d TPU device(s), JAX reports %s"
              % (args.chips, device), file=sys.stderr)
        return 2

    from lightgbm_tpu import compile_cache
    from lightgbm_tpu.utils import log
    tee = LogTee()
    log.set_stream(tee)
    cache = CacheCounts()
    emit({"phase": "start", "device": device,
          "jax": jax.__version__,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "cache_placed_by": ("env " + compile_cache.CACHE_ENV
                              if os.environ.get(compile_cache.CACHE_ENV)
                              else "lightgbm_tpu.compile_cache"),
          "package": os.path.dirname(lightgbm_tpu.__file__)})

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            four_chips(args, work, device, tee, cache)
        else:
            one_chip(args, work, device, tee, cache)
    finally:
        log.set_stream(None)
        shutil.rmtree(work, ignore_errors=True)

    check(on_chip, "device check: need %d TPU device(s), JAX reports %s"
          % (args.chips, device))
    if FAILURES:
        print("chip_smoke FAILED (%d):\n  %s"
              % (len(FAILURES), "\n  ".join(FAILURES)), file=sys.stderr)
        return 1
    # the contract's line, keys in the contract's order, nothing else
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
