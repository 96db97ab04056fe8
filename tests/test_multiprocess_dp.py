"""True multi-PROCESS data-parallel training (the reference's N-machine
mode, data_parallel_tree_learner.cpp + linkers_socket.cpp).

Launches 2 OS processes, each with 4 virtual CPU devices, joined by
``jax.distributed.initialize`` into one 8-device job.  Each process loads
its own random row shard from the same CSV (dataset.cpp:172-216 semantics),
bin finding is distributed (feature slices + allgather), row-aligned state
is lifted to global mesh-sharded arrays (parallel/mesh.make_global_rows),
and the fused shard_map chunk program trains across both processes.

Asserts the reference's own invariant — every worker ends with the
IDENTICAL model — and serial equivalence of the distributed model.

The rule this file keeps: every process it starts goes through
``_run_pair`` — outputs to files, one deadline of PAIR_DEADLINE_S for the
pair and its serial baseline together, a failed rank takes its peer down,
a port that was taken is tried again.  No test waits on a process any
other way.
"""
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Standard JAX multihost practice: the launcher bootstraps
# jax.distributed BEFORE anything touches the backend (the in-cli
# init_distributed then sees an initialized client and skips).
WORKER = r"""
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
from lightgbm_tpu.parallel.mesh import init_distributed
init_distributed()
sys.argv = ["lightgbm_tpu"] + sys.argv[1:]
from lightgbm_tpu.cli import main
rc = main()
print("POST process_count:", jax.process_count(),
      "index:", jax.process_index(), "rc:", rc, flush=True)
sys.exit(rc)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _write_conf(path, data_csv, model_out, tree_learner, num_machines,
                grow_policy="depthwise", extra="", metric_freq=1000,
                num_iterations=8, objective="binary"):
    # hist_dtype=int8: quantization scales are pmax-synced across shards and
    # int32 accumulation is order-free, so the distributed histograms (and
    # therefore trees) are BIT-identical to serial — the strongest form of
    # the reference's every-worker-identical-model invariant.
    # dp_schedule is PINNED to psum: these tests assert exact tree
    # equality vs serial, which the ownership schedule does not promise
    # on near-tie data (an ulp in the owning shard's differently-compiled
    # search can flip a tie — see the lambdarank reduce_scatter
    # parametrization, which covers that schedule's multi-process path)
    with open(path, "w") as f:
        f.write(f"""task=train
data={data_csv}
objective={objective}
num_leaves=15
min_data_in_leaf=20
min_sum_hessian_in_leaf=1.0
num_iterations={num_iterations}
learning_rate=0.2
max_bin=32
metric_freq={metric_freq}
hist_dtype=int8
dp_schedule=psum
grow_policy={grow_policy}
tree_learner={tree_learner}
num_machines={num_machines}
output_model={model_out}
{extra}
""")


PAIR_DEADLINE_S = 180   # the slowest pair runs 37 s with a cold cache
PEER_GRACE_S = 5        # what a rank gets to end by itself once one has failed


def _spawn(conf, log, worker=WORKER, extra_env=None):
    env = dict(os.environ)
    env.pop("LGBM_TPU_COORDINATOR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    with open(log, "w") as fh:
        return subprocess.Popen(
            [sys.executable, "-c", worker, f"config={conf}"],
            env=env, stdout=fh, stderr=subprocess.STDOUT)


def _run_pair(tmp_path, confs, serial=None, check=True,
              workers=(WORKER, WORKER)):
    """Run the two ranks of one jax.distributed job, ``confs[rank]`` each,
    and beside them the one-process baseline ``serial`` if one is given.
    -> ([out_rank0, out_rank1], out_serial or None, return codes).

    Every process writes to a file under ``tmp_path``: a pipe that is
    read one rank after the other can fill and block its writer inside a
    collective the other rank waits in.  All of them share one deadline.
    With ``check`` a process that exits non-zero takes the others down
    PEER_GRACE_S later (a rank whose peer is gone waits in its collective
    until the coordination service gives up on it), and the assertion
    carries the tail of every output; without it, failing is what the
    caller expects and each process runs to its own end.  A coordinator
    that could not bind (another xdist worker took the port between
    ``_free_port`` and the bind) is run again on a fresh port."""
    for attempt in range(3):
        port = _free_port()
        logs = [str(tmp_path / f"out_r{rank}.{attempt}.log")
                for rank in range(2)]
        procs = [_spawn(confs[rank], logs[rank], workers[rank], {
            "LGBM_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "LGBM_TPU_NUM_PROCS": "2",
            "LGBM_TPU_PROC_ID": str(rank),
        }) for rank in range(2)]
        if serial is not None:
            logs.append(str(tmp_path / f"out_serial.{attempt}.log"))
            procs.append(_spawn(serial, logs[-1]))
        deadline = time.monotonic() + PAIR_DEADLINE_S
        grace_given = False
        try:
            while (any(p.poll() is None for p in procs)
                   and time.monotonic() < deadline):
                if (check and not grace_given
                        and any(p.poll() for p in procs)):
                    grace_given = True
                    deadline = min(deadline,
                                   time.monotonic() + PEER_GRACE_S)
                time.sleep(0.1)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                p.wait()
        outs = []
        for log in logs:
            with open(log, errors="replace") as fh:
                outs.append(fh.read())
        if "Failed to add port to server" not in outs[0]:
            break
    rcs = [p.returncode for p in procs]
    if check:
        names = ["rank 0", "rank 1", "serial"][:len(procs)]
        ok = all(rc == 0 for rc in rcs) and all(
            "POST process_count: 2" in out for out in outs[:2])
        assert ok, "\n".join(
            f"---- {name} (rc {rc}; -9: killed at the deadline or after "
            f"a peer failed):\n{out[-3000:]}"
            for name, rc, out in zip(names, rcs, outs))
    return outs[:2], (outs[2] if serial is not None else None), rcs[:2]


def _load_trees(model_path):
    from lightgbm_tpu.models.gbdt import GBDT
    return GBDT.from_model_file(model_path).models


def test_two_process_data_parallel_matches_serial(tmp_path):
    rng = np.random.RandomState(33)
    n, f = 1600, 8
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.randn(n)) > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")

    # ---- 2-process distributed run
    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2)
        confs.append(conf)

    # ---- serial baseline (same pipeline, one process)
    sconf = str(tmp_path / "train_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"), "serial", 1)
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    # reference invariant: every worker holds the identical model
    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"

    # distributed == serial trees: int8 histograms are bit-identical (see
    # _write_conf), so split decisions and leaf values must match exactly
    # (leaf values to f64-formatting noise of the text round-trip)
    trees_dp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    assert len(trees_dp) == len(trees_s) == 8
    for k, (td, ts) in enumerate(zip(trees_dp, trees_s)):
        assert td.num_leaves == ts.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {k}")
        np.testing.assert_allclose(td.leaf_value, ts.leaf_value,
                                   rtol=1e-6, atol=1e-8,
                                   err_msg=f"tree {k}")

    # the run actually exercised the distributed pieces
    assert "Finished train" in outs[0]


def test_two_process_bagging_workers_identical(tmp_path):
    """Multi-process bagging: each process bags its LOCAL shard (the
    reference's per-machine Bagging); the invariant is worker-identical
    models (trees are not serial-identical — the bagged subsets differ
    from a single-machine draw, as in the reference)."""
    rng = np.random.RandomState(7)
    n, f = 1600, 6
    x = rng.randn(n, f)
    y = ((x[:, 0] + 0.3 * rng.randn(n)) > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2,
                    extra="bagging_fraction=0.8\nbagging_freq=2\n"
                          "bagging_seed=9")
        confs.append(conf)
    outs, _, _ = _run_pair(tmp_path, confs)
    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged under bagging"
    assert m0.count("Tree=") == 8


def _parse_metric_lines(out):
    """-> {(iteration, metric_name): [values]} from the CLI log."""
    import re
    vals = {}
    for m in re.finditer(
            r"Iteration:(\d+), (.+?) : ([-\d.e+ ]+)\n", out):
        it, name, nums = int(m.group(1)), m.group(2), m.group(3)
        vals[(it, name)] = [float(v) for v in nums.split()]
    return vals


def _gen_valid_run(tmp_path, grow_policy, num_iterations, early_stop):
    """Shared harness: 2-process DP with a validation set + metrics
    (+ optional early stopping) vs the identical serial run.  The
    reference's N-machine mode evaluates metrics/early-stop every
    iteration exactly like serial (application.cpp:119-199 loads valid
    data per machine, gbdt.cpp:225-259 evaluates each iteration)."""
    rng = np.random.RandomState(11)
    n, nv, f = 1600, 400, 8

    def make(n_):
        x = rng.randn(n_, f)
        y = ((x[:, 0] - 0.5 * x[:, 1] + 0.6 * rng.randn(n_)) > 0).astype(int)
        return np.column_stack([y, x])
    csv = str(tmp_path / "train.csv")
    vcsv = str(tmp_path / "valid.csv")
    np.savetxt(csv, make(n), fmt="%.7g", delimiter=",")
    np.savetxt(vcsv, make(nv), fmt="%.7g", delimiter=",")

    extra = (f"valid_data={vcsv}\nmetric=binary_logloss,auc\n"
             "is_training_metric=true\n")
    if early_stop:
        extra += "early_stopping_round=3\n"

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2, grow_policy=grow_policy, extra=extra,
                    metric_freq=1, num_iterations=num_iterations)
        confs.append(conf)

    sconf = str(tmp_path / "train_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"),
                "serial", 1, grow_policy=grow_policy, extra=extra,
                metric_freq=1, num_iterations=num_iterations)
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)
    return outs, sout


def test_two_process_dp_eval_early_stop_matches_serial(tmp_path):
    """Chunked multi-process DP with valid set + logloss/AUC + early
    stopping: metric trajectory and the early-stop decision must match the
    serial run (train metrics run on the gathered global score — the
    trajectory is the serial one, not a per-machine local value)."""
    outs, sout = _gen_valid_run(tmp_path, "depthwise",
                                num_iterations=30, early_stop=True)
    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys(), (
        f"metric trajectories diverge:\nDP:{sorted(dp_vals)}\n"
        f"serial:{sorted(s_vals)}")
    assert len(dp_vals) > 0
    for key in s_vals:
        np.testing.assert_allclose(
            dp_vals[key], s_vals[key], rtol=2e-5, atol=1e-7,
            err_msg=f"metric {key}")

    # identical early-stopping decision (or identical full-length run):
    # same tree count on every worker and serially
    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    ms = open(tmp_path / "model_serial.txt").read()
    assert m0 == m1, "workers diverged"
    assert m0.count("Tree=") == ms.count("Tree=")
    es_dp = [l for l in outs[0].splitlines() if "Early stopping" in l]
    es_s = [l for l in sout.splitlines() if "Early stopping" in l]
    assert es_dp == es_s


def test_two_process_dp_eval_leafwise_periter(tmp_path):
    """Leaf-wise multi-process DP runs the per-iteration path: training
    metrics evaluate host-side on the gathered global score and valid
    scores update via tree replay — trajectory must still match serial."""
    outs, sout = _gen_valid_run(tmp_path, "leafwise",
                                num_iterations=8, early_stop=False)
    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys()
    assert len(dp_vals) > 0
    for key in s_vals:
        np.testing.assert_allclose(
            dp_vals[key], s_vals[key], rtol=2e-5, atol=1e-7,
            err_msg=f"metric {key}")


def _write_ranking_table(path, num_queries, seed, num_features=12):
    """Seeded stand-in for the reference's examples/lambdarank files when
    they are absent: ``path`` (tab-separated, grade 0-4 in column 0) plus
    ``path + ".query"`` (one query length per line).  Features are small
    integers, tie-dense like the reference set, and the grade follows a
    noisy linear score so NDCG can rise."""
    rng = np.random.RandomState(seed)
    lengths = rng.randint(5, 26, size=num_queries)
    n = int(lengths.sum())
    x = rng.randint(0, 12, size=(n, num_features)).astype(np.float64)
    w = np.random.RandomState(7).randn(num_features)
    score = (x - 5.5) @ w / np.sqrt(num_features) + 1.5 * rng.randn(n)
    grade = np.digitize(score, np.quantile(score, [0.5, 0.75, 0.9, 0.97]))
    np.savetxt(path, np.column_stack([grade, x]), fmt="%d", delimiter="\t")
    np.savetxt(path + ".query", lengths, fmt="%d")


@pytest.mark.parametrize("schedule,val_tol", [
    # psum: every shard dequantizes the identical full int histogram —
    # leaf values match serial to program-fusion ulps, every tree.
    # reduce_scatter (the auto default for true multi-process runs): the
    # owning shard's search is a differently-compiled program, so an
    # ulp-level gain difference can flip a near-tie split from tree 1 on
    # (this integer-featured ranking set is tie-dense) — tree 0 is still
    # asserted against serial, later trees via worker lockstep + quality
    ("psum", dict(rtol=1e-6, atol=1e-8)),
    ("reduce_scatter", dict(rtol=1e-3, atol=1e-6)),
])
def test_two_process_dp_lambdarank_matches_serial(tmp_path, schedule,
                                                  val_tol):
    """Distributed lambdarank (the reference's flagship parallel mode gap):
    query-atomic row sharding (dataset.cpp:189-206) + per-query tables
    rebuilt in padded-global coordinates (LambdarankNDCG.globalize_layout)
    + gathered-score lambdas in the DP chunk.  Trees must be identical on
    every worker AND match the serial run (int8 histograms are bit-exact
    across shardings); the NDCG trajectory must match serial."""
    train = str(tmp_path / "rank.train")
    test = str(tmp_path / "rank.test")
    ex = "/root/reference/examples/lambdarank"
    if os.path.isdir(ex):
        import shutil
        for f in ["rank.train", "rank.train.query", "rank.test",
                  "rank.test.query"]:
            shutil.copy(os.path.join(ex, f), tmp_path / f)
    else:
        _write_ranking_table(train, num_queries=200, seed=11)
        _write_ranking_table(test, num_queries=50, seed=12)
    # row weights: exercises the padded-global weight scatter
    # (globalize_layout's w[pad_pos]) and the weighted-lambda path
    nrows = sum(1 for _ in open(train))
    wrng = np.random.RandomState(3)
    np.savetxt(str(tmp_path / "rank.train.weight"),
               (0.5 + wrng.rand(nrows)).astype(np.float32), fmt="%.5f")

    extra = (f"objective=lambdarank\nvalid_data={test}\nmetric=ndcg\n"
             "is_training_metric=true\nndcg_at=1,3,5\n")

    def conf_for(path, model, learner, machines):
        # _write_conf hardcodes objective=binary; write a rank conf directly
        with open(path, "w") as f:
            f.write(f"""task=train
data={train}
num_leaves=15
min_data_in_leaf=10
min_sum_hessian_in_leaf=0.001
num_iterations=8
learning_rate=0.1
max_bin=32
metric_freq=1
hist_dtype=int8
dp_schedule={schedule}
grow_policy=depthwise
tree_learner={learner}
num_machines={machines}
output_model={model}
{extra}
""")

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"rank_r{rank}.conf")
        conf_for(conf, str(tmp_path / f"model_r{rank}.txt"), "data", 2)
        confs.append(conf)

    sconf = str(tmp_path / "rank_serial.conf")
    conf_for(sconf, str(tmp_path / "model_serial.txt"), "serial", 1)
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"

    trees_dp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    assert len(trees_dp) == len(trees_s) == 8
    # psum: every tree matches serial.  reduce_scatter: an ulp-level
    # tie-flip in the owning shard's differently-compiled search can
    # legitimately change a later tree's structure (the score cascade
    # makes everything after the first flip diverge) — but tree 0 sees
    # identical gradients, so it MUST still match, which is what catches
    # a garbage-tree regression
    ntrees_checked = 8 if schedule == "psum" else 1
    for k in range(ntrees_checked):
        td, ts = trees_dp[k], trees_s[k]
        assert td.num_leaves == ts.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {k}")
        np.testing.assert_allclose(td.leaf_value, ts.leaf_value,
                                   err_msg=f"tree {k}", **val_tol)

    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys()
    assert len(dp_vals) > 0
    # NDCG trajectory: psum matches serial to reduction ulps; under
    # reduce_scatter this integer-featured ranking set is near-tie-dense
    # and the owning shard's differently-compiled gain can flip a tie by
    # an ulp — a genuinely (equivalently-scoring) different tree, exactly
    # as the reference's own parallel mode diverges from ITS serial on
    # ties.  The guaranteed invariant is worker lockstep (m0 == m1,
    # asserted above) + serial-equivalent QUALITY
    mtol = (dict(rtol=2e-5, atol=1e-7) if schedule == "psum"
            else dict(rtol=2e-2, atol=2e-3))
    for key in s_vals:
        np.testing.assert_allclose(
            dp_vals[key], s_vals[key], err_msg=f"metric {key}", **mtol)


def test_two_process_feature_parallel_matches_serial(tmp_path):
    """Multi-process FEATURE parallel (feature_parallel_tree_learner.cpp
    on N machines): every process loads the FULL rows (the reference sets
    is_parallel_find_bin=false for FP — io/config.cpp:164-172) and the
    replicated-rows fused chunk runs over the global mesh.  Each feature's
    histogram is built by exactly one owner from the full rows, so trees
    must be identical on every worker AND identical to serial."""
    rng = np.random.RandomState(41)
    n, f = 1600, 8
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.6 * rng.randn(n)) > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    vcsv = str(tmp_path / "valid.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")
    xv = rng.randn(400, f)
    yv = ((xv[:, 0] - 0.5 * xv[:, 1] + 0.6 * rng.randn(400)) > 0).astype(int)
    np.savetxt(vcsv, np.column_stack([yv, xv]), fmt="%.7g", delimiter=",")
    extra = (f"valid_data={vcsv}\nmetric=binary_logloss,auc\n"
             "is_training_metric=true\n")

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "feature", 2, extra=extra, metric_freq=1)
        confs.append(conf)

    sconf = str(tmp_path / "train_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"),
                "serial", 1, extra=extra, metric_freq=1)
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"
    trees_fp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    assert len(trees_fp) == len(trees_s) == 8
    for k, (td, ts) in enumerate(zip(trees_fp, trees_s)):
        assert td.num_leaves == ts.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {k}")
    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys() and len(dp_vals) > 0
    for key in s_vals:
        np.testing.assert_allclose(dp_vals[key], s_vals[key],
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"metric {key}")


def test_two_process_feature_parallel_leafwise_fails_loudly(tmp_path):
    """Leaf-wise FP multi-process is unsupported — it must log.fatal with
    a clear message at init, not mis-train or fail obscurely."""
    rng = np.random.RandomState(5)
    n, f = 400, 4
    x = rng.randn(n, f)
    y = (x[:, 0] > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "feature", 2, grow_policy="leafwise")
        confs.append(conf)
    outs, _, rcs = _run_pair(tmp_path, confs, check=False)
    for rank, (rc, out) in enumerate(zip(rcs, outs)):
        assert rc != 0, f"rank {rank} unexpectedly succeeded"
        assert "multi-process feature-parallel training requires" in out


def test_run_pair_fails_fast_when_a_peer_dies(tmp_path):
    """Rank 1 joins the job and dies.  Rank 0, left in its first
    collective, is taken down with it, and the failure carries both
    outputs — in seconds, not at the deadline."""
    rng = np.random.RandomState(5)
    x = rng.randn(400, 4)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([(x[:, 0] > 0).astype(int), x]),
               fmt="%.7g", delimiter=",")
    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2)
        confs.append(conf)
    dying = WORKER.replace(
        "from lightgbm_tpu.cli import main",
        'print("rank 1 joined and dies", flush=True); os._exit(7)')
    assert dying != WORKER
    began = time.monotonic()
    with pytest.raises(AssertionError) as failure:
        _run_pair(tmp_path, confs, workers=(WORKER, dying))
    assert time.monotonic() - began < 30
    message = str(failure.value)
    assert "rank 0 (rc -9" in message and "rank 1 (rc 7" in message
    assert "rank 1 joined and dies" in message


def test_two_process_dp_multiclass_matches_serial(tmp_path):
    """Multi-process DP multiclass (k trees per iteration interleaved,
    gbdt.cpp:175-195): worker-identical AND serial-identical trees under
    int8, with multi_logloss evaluated on the gathered global score."""
    rng = np.random.RandomState(13)
    n, f, k = 1500, 6, 3
    x = rng.randn(n, f)
    y = (x[:, 0] + 0.5 * rng.randn(n) > 0.5).astype(int) + \
        (x[:, 1] + 0.5 * rng.randn(n) > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")
    extra = (f"num_class={k}\nmetric=multi_logloss\n"
             "is_training_metric=true\n")

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2, extra=extra, metric_freq=1,
                    objective="multiclass")
        confs.append(conf)

    sconf = str(tmp_path / "train_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"),
                "serial", 1, extra=extra, metric_freq=1,
                objective="multiclass")
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"
    trees_dp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    assert len(trees_dp) == len(trees_s) == 8 * k
    for i, (td, ts) in enumerate(zip(trees_dp, trees_s)):
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {i}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {i}")
    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys() and len(dp_vals) > 0
    for key in s_vals:
        np.testing.assert_allclose(dp_vals[key], s_vals[key],
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"metric {key}")


def test_two_process_dp_weighted_regression_matches_serial(tmp_path):
    """Multi-process DP L2 regression with row weights (a .weight side
    file, sharded with the rows): worker-identical, serial-identical
    trees; weighted l2 metric trajectory equal to serial."""
    rng = np.random.RandomState(29)
    n, f = 1600, 6
    x = rng.randn(n, f)
    y = x[:, 0] * 2.0 - x[:, 1] + 0.3 * rng.randn(n)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")
    np.savetxt(csv + ".weight", (0.5 + rng.rand(n)).astype(np.float32),
               fmt="%.5f")
    extra = "metric=l2\nis_training_metric=true\n"

    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"train_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2, extra=extra, metric_freq=1,
                    objective="regression")
        confs.append(conf)

    sconf = str(tmp_path / "train_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"),
                "serial", 1, extra=extra, metric_freq=1,
                objective="regression")
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"
    trees_dp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    assert len(trees_dp) == len(trees_s) == 8
    for i, (td, ts) in enumerate(zip(trees_dp, trees_s)):
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {i}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {i}")
    dp_vals = _parse_metric_lines(outs[0])
    s_vals = _parse_metric_lines(sout)
    assert dp_vals.keys() == s_vals.keys() and len(dp_vals) > 0
    for key in s_vals:
        np.testing.assert_allclose(dp_vals[key], s_vals[key],
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"metric {key}")


def test_two_process_dp_continued_training_from_reference_model(
        tmp_path, reference_binary):
    """Continued training (``input_model``) under TRUE multi-process data
    parallelism, seeded by a REFERENCE-WRITTEN model file — the
    reference's own N-machine continued-training shape
    (application.cpp:119-131 loading input_model + dataset.cpp:546-581
    init scores): 2-OS-process DP continued run must stay in worker
    lockstep and reproduce the serial continued run exactly (int8 +
    psum)."""
    rng = np.random.RandomState(44)
    n, f = 1600, 8
    x = rng.randn(n, f)
    y = ((x[:, 0] - 0.5 * x[:, 1] + 0.2 * rng.randn(n)) > 0).astype(int)
    csv = str(tmp_path / "train.csv")
    np.savetxt(csv, np.column_stack([y, x]), fmt="%.7g", delimiter=",")

    # 1) the reference binary trains the base model (3 trees)
    base_model = str(tmp_path / "ref_base_model.txt")
    with open(tmp_path / "ref_base.conf", "w") as fh:
        fh.write(f"""task=train
data={csv}
objective=binary
num_trees=3
num_leaves=15
min_data_in_leaf=20
min_sum_hessian_in_leaf=1.0
learning_rate=0.2
max_bin=32
output_model={base_model}
""")
    subprocess.run([reference_binary,
                    f"config={tmp_path / 'ref_base.conf'}"],
                   check=True, capture_output=True, text=True, timeout=60)
    assert os.path.exists(base_model)

    # 2) serial continued run: +5 trees on top of the reference model
    extra = f"input_model={base_model}\n"
    sconf = str(tmp_path / "cont_serial.conf")
    _write_conf(sconf, csv, str(tmp_path / "model_serial.txt"), "serial",
                1, num_iterations=5, extra=extra)

    # 3) 2-process DP continued run, same input model
    confs = []
    for rank in range(2):
        conf = str(tmp_path / f"cont_r{rank}.conf")
        _write_conf(conf, csv, str(tmp_path / f"model_r{rank}.txt"),
                    "data", 2, num_iterations=5, extra=extra)
        confs.append(conf)
    outs, sout, _ = _run_pair(tmp_path, confs, serial=sconf)

    m0 = open(tmp_path / "model_r0.txt").read()
    m1 = open(tmp_path / "model_r1.txt").read()
    assert m0 == m1, "workers diverged"

    trees_dp = _load_trees(str(tmp_path / "model_r0.txt"))
    trees_s = _load_trees(str(tmp_path / "model_serial.txt"))
    # 3 reference trees carried over + 5 continued
    assert len(trees_dp) == len(trees_s) == 8
    for k, (td, ts) in enumerate(zip(trees_dp, trees_s)):
        assert td.num_leaves == ts.num_leaves, f"tree {k}"
        np.testing.assert_array_equal(td.split_feature, ts.split_feature,
                                      err_msg=f"tree {k}")
        np.testing.assert_array_equal(td.threshold_bin, ts.threshold_bin,
                                      err_msg=f"tree {k}")
        np.testing.assert_allclose(td.leaf_value, ts.leaf_value,
                                   rtol=1e-6, atol=1e-8,
                                   err_msg=f"tree {k}")
