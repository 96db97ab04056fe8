"""Differential tests against the compiled reference binary.

The reference's own design guarantees deterministic trees for deterministic
configs (no bagging, feature_fraction=1), so the compiled reference binary
is an exact oracle for binning, split finding, leaf values, model-file
format and prediction (SURVEY §4: "a powerful differential-testing oracle
the original authors never encoded as a test").

What is (and isn't) asserted: the FIRST boosting iteration's trees must
match the reference exactly — same binning, histogram sums, split gains,
tie-breaks and leaf values.  Later trees are NOT compared structurally: the
reference accumulates histograms in double (bin.h:15-17) while the TPU
kernels accumulate f32 via matmul tree-reduction, so one near-tied gain can
legitimately pick a different feature and every subsequent tree cascades
(observed: tree 0 and 25/30 splits of tree 1 identical, then divergence).
Model-format interchangeability and end-metric parity are asserted instead.

The binary is built once per host into /tmp (the reference's CMake insists
on writing the executable into its own source dir, so the source tree is
copied to /tmp first; /root/reference itself is never touched).  Tests skip
if the toolchain or examples are unavailable.
"""
import os
import re
import shutil
import subprocess

import numpy as np
import pytest

REFERENCE = "/root/reference"


# reference_binary fixture lives in conftest.py (shared with
# test_auc_parity.py)


DET = ["feature_fraction=1.0", "bagging_fraction=1.0", "bagging_freq=0",
       "early_stopping_round=0"]

EXAMPLES = {
    "binary_classification": ("binary.train", "binary.test",
                              "binary.train.weight", "binary.test.weight",
                              "train.conf", "predict.conf"),
    "regression": ("regression.train", "regression.test",
                   "train.conf", "predict.conf"),
    "multiclass_classification": ("multiclass.train", "multiclass.test",
                                  "train.conf", "predict.conf"),
    "lambdarank": ("rank.train", "rank.test", "rank.train.query",
                   "rank.test.query", "train.conf", "predict.conf"),
}


def _parse_model_trees(path):
    """Parse a LightGBM text model into per-tree dicts (format of
    Tree::ToString, /root/reference/src/io/tree.cpp:111-136)."""
    trees = []
    cur = None
    for line in open(path):
        line = line.strip()
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif "=" in line and cur is not None:
            k, v = line.split("=", 1)
            cur[k] = v
    parsed = []
    for t in trees:
        d = {"num_leaves": int(t["num_leaves"])}
        for key in ("split_feature", "threshold", "leaf_value", "split_gain",
                    "left_child", "right_child"):
            if key in t and t[key]:
                vals = t[key].split()
                d[key] = (np.asarray(vals, dtype=float)
                          if key in ("threshold", "leaf_value", "split_gain")
                          else np.asarray(vals, dtype=int))
        parsed.append(d)
    return parsed


def _run_reference(binary, workdir, conf, extra):
    return subprocess.run([binary, f"config={conf}"] + extra, cwd=workdir,
                          check=True, capture_output=True, text=True,
                          timeout=120)


def _setup_example(tmp_path, task):
    src = os.path.join(REFERENCE, "examples", task)
    if not os.path.isdir(src):
        pytest.skip("reference examples not available")
    for f in EXAMPLES[task]:
        p = os.path.join(src, f)
        if os.path.exists(p):
            shutil.copy(p, tmp_path / f)
    return tmp_path


def _run_ours(tmp_path, monkeypatch, extra):
    from lightgbm_tpu.cli import Application
    monkeypatch.chdir(tmp_path)
    Application(["config=train.conf"] + extra).run()


def _assert_tree_equal(rt, tt, label, leaf_rtol=5e-4):
    __tracebackhide__ = True
    assert rt["num_leaves"] == tt["num_leaves"], f"{label} shape"
    np.testing.assert_array_equal(rt["split_feature"], tt["split_feature"],
                                  err_msg=f"{label} split features")
    np.testing.assert_allclose(rt["threshold"], tt["threshold"],
                               rtol=1e-6, atol=1e-12,
                               err_msg=f"{label} thresholds")
    np.testing.assert_array_equal(rt["left_child"], tt["left_child"],
                                  err_msg=f"{label} left children")
    np.testing.assert_array_equal(rt["right_child"], tt["right_child"],
                                  err_msg=f"{label} right children")
    np.testing.assert_allclose(rt["leaf_value"], tt["leaf_value"],
                               rtol=leaf_rtol, atol=1e-6,
                               err_msg=f"{label} leaf values")


def _assert_tree_prefix(rt, tt, label, min_prefix):
    """Exact agreement up to the first divergence, which must not occur
    before ``min_prefix`` splits.  A single near-tied gain flipped by the
    double-vs-f32 histogram accumulation legitimately changes every split
    after it (the tree's candidate set changes), so the provable property is
    a long exact prefix, not bitwise identity."""
    __tracebackhide__ = True
    assert rt["num_leaves"] == tt["num_leaves"], f"{label} shape"
    n = len(rt["split_feature"])
    same = ((rt["split_feature"] == tt["split_feature"])
            & np.isclose(rt["threshold"], tt["threshold"],
                         rtol=1e-6, atol=1e-12))
    div = int(np.argmin(same)) if not same.all() else n
    assert div >= min_prefix, (
        f"{label}: diverges at split {div} (< {min_prefix}); "
        f"features {rt['split_feature'][div]} vs {tt['split_feature'][div]}")


@pytest.mark.parametrize("task,extra,first_trees,min_prefix", [
    ("binary_classification", ["num_leaves=31", "min_data_in_leaf=50"], 1, 30),
    ("binary_classification", ["num_leaves=7", "min_data_in_leaf=20"], 1, 6),
    ("binary_classification", ["num_leaves=63", "min_data_in_leaf=100",
                               "min_sum_hessian_in_leaf=10.0"], 1, 62),
    ("regression", ["num_leaves=31", "min_data_in_leaf=50"], 1, 30),
    # multiclass: all 5 class trees of iteration 0 are first trees; the
    # uniform softmax start (p=1/5 everywhere) makes near-tied gains
    # common, so require a long exact prefix instead of full identity
    ("multiclass_classification", ["num_leaves=31", "min_data_in_leaf=50"],
     5, 15),
])
def test_first_iteration_trees_exact(reference_binary, tmp_path, monkeypatch,
                                     task, extra, first_trees, min_prefix):
    """First-iteration trees match the reference binary exactly (or to a
    long exact prefix where knife-edge ties exist): one shot validates
    binning, (weighted) gradients, histogram sums, gain formula, constraint
    handling, tie-breaking and leaf outputs for each objective."""
    _setup_example(tmp_path, task)
    cfg = DET + ["num_trees=2"] + extra
    _run_reference(reference_binary, tmp_path, "train.conf",
                   cfg + ["output_model=ref_model.txt"])
    _run_ours(tmp_path, monkeypatch, cfg + ["output_model=tpu_model.txt"])
    ref = _parse_model_trees(tmp_path / "ref_model.txt")
    tpu = _parse_model_trees(tmp_path / "tpu_model.txt")
    assert len(ref) == len(tpu)
    for i in range(first_trees):
        nsplits = len(ref[i]["split_feature"])
        if min_prefix >= nsplits:
            _assert_tree_equal(ref[i], tpu[i], f"{task} tree {i}")
        else:
            _assert_tree_prefix(ref[i], tpu[i], f"{task} tree {i}",
                                min_prefix)


def test_lambdarank_ndcg_parity(reference_binary, tmp_path, monkeypatch,
                                capfd):
    """Lambdarank cannot be compared tree-for-tree: the reference ranks
    tied scores with UNSTABLE std::sort (rank_objective.hpp:98-99), and at
    iteration 1 ALL scores are tied, so its own gradients depend on the
    sort implementation.  Learning quality (NDCG trajectory) is the
    comparable contract."""
    _setup_example(tmp_path, "lambdarank")
    cfg = DET + ["num_trees=20", "num_leaves=31", "min_data_in_leaf=50"]
    res = _run_reference(reference_binary, tmp_path, "train.conf",
                         cfg + ["output_model=ref_model.txt"])
    ref_ndcg = _metric_values(res.stdout.splitlines(), "NDCG@5")

    _run_ours(tmp_path, monkeypatch, cfg + ["output_model=tpu_model.txt"])
    out = capfd.readouterr()
    tpu_ndcg = _metric_values((out.out + out.err).splitlines(), "NDCG@5")

    ref_last = ref_ndcg[max(ref_ndcg)]
    tpu_last = tpu_ndcg[max(tpu_ndcg)]
    # one-sided: we must not rank meaningfully worse (being better is fine;
    # observed: 0.555 vs the reference's 0.522 on the example data)
    assert tpu_last >= ref_last - 0.02, (ref_last, tpu_last)


def test_model_format_interchangeable(reference_binary, tmp_path,
                                      monkeypatch):
    """Each side predicts with the OTHER side's model file and must
    reproduce the owner's predictions — the text model format and the
    prediction semantics are interchangeable."""
    _setup_example(tmp_path, "binary_classification")
    cfg = DET + ["num_trees=8", "num_leaves=31", "min_data_in_leaf=50"]
    _run_reference(reference_binary, tmp_path, "train.conf",
                   cfg + ["output_model=ref_model.txt"])
    _run_ours(tmp_path, monkeypatch, cfg + ["output_model=tpu_model.txt"])

    from lightgbm_tpu.cli import Application

    # reference predicts with our model vs us with our model
    _run_reference(reference_binary, tmp_path, "predict.conf",
                   ["input_model=tpu_model.txt",
                    "output_result=ref_on_tpu.txt"])
    Application(["config=predict.conf", "input_model=tpu_model.txt",
                 "output_result=tpu_on_tpu.txt"]).run()
    np.testing.assert_allclose(np.loadtxt(tmp_path / "ref_on_tpu.txt"),
                               np.loadtxt(tmp_path / "tpu_on_tpu.txt"),
                               rtol=1e-5, atol=1e-7)

    # we predict with the reference's model vs reference with its model
    _run_reference(reference_binary, tmp_path, "predict.conf",
                   ["input_model=ref_model.txt",
                    "output_result=ref_on_ref.txt"])
    Application(["config=predict.conf", "input_model=ref_model.txt",
                 "output_result=tpu_on_ref.txt"]).run()
    np.testing.assert_allclose(np.loadtxt(tmp_path / "ref_on_ref.txt"),
                               np.loadtxt(tmp_path / "tpu_on_ref.txt"),
                               rtol=1e-5, atol=1e-7)


def _metric_values(lines, metric_substr):
    out = {}
    for l in lines:
        m = re.search(r"Iteration:(\d+), ([^:]+) : ([0-9.eE+-]+)", l)
        if m and metric_substr in m.group(2):
            out[int(m.group(1))] = float(m.group(3))
    return out


def test_metric_parity(reference_binary, tmp_path, monkeypatch, capfd):
    """First-iteration metrics match tightly (identical trees); final
    metrics stay within a few percent despite structural divergence —
    learning quality parity."""
    _setup_example(tmp_path, "binary_classification")
    cfg = DET + ["num_trees=20", "num_leaves=31", "min_data_in_leaf=50"]
    res = _run_reference(reference_binary, tmp_path, "train.conf",
                         cfg + ["output_model=ref_model.txt"])
    ref_auc = _metric_values(res.stdout.splitlines(), "AUC")
    ref_ll = _metric_values(res.stdout.splitlines(), "log loss")

    _run_ours(tmp_path, monkeypatch, cfg + ["output_model=tpu_model.txt"])
    out = capfd.readouterr()
    lines = (out.out + out.err).splitlines()
    tpu_auc = _metric_values(lines, "AUC")
    tpu_ll = _metric_values(lines, "log loss")

    assert set(ref_auc) == set(tpu_auc) and len(ref_auc) >= 20
    # iteration 1: identical trees -> near-identical metrics
    assert abs(ref_auc[1] - tpu_auc[1]) < 1e-6
    assert abs(ref_ll[1] - tpu_ll[1]) < 1e-4
    # final iteration: parity within a few percent
    last = max(ref_auc)
    assert abs(ref_auc[last] - tpu_auc[last]) < 0.01
    assert abs(ref_ll[last] - tpu_ll[last]) / ref_ll[last] < 0.03


def test_depthwise_first_tree_split_set(reference_binary, tmp_path,
                                        monkeypatch):
    """grow_policy=depthwise on a full binary tree (num_leaves=4 = two full
    levels) finds the same split set and leaf values as the reference's
    leaf-wise order for the first tree."""
    _setup_example(tmp_path, "binary_classification")
    cfg = DET + ["num_trees=1", "num_leaves=4", "min_data_in_leaf=50"]
    _run_reference(reference_binary, tmp_path, "train.conf",
                   cfg + ["output_model=ref_model.txt"])
    _run_ours(tmp_path, monkeypatch,
              cfg + ["grow_policy=depthwise", "output_model=tpu_model.txt"])
    ref = _parse_model_trees(tmp_path / "ref_model.txt")
    tpu = _parse_model_trees(tmp_path / "tpu_model.txt")
    assert len(ref) == len(tpu) == 1
    rt, tt = ref[0], tpu[0]
    assert rt["num_leaves"] == tt["num_leaves"]
    # the leafbatch einsum rounds differently from the leafwise matmul, so
    # one near-tied gain may flip (observed: 1 of 3); require the majority
    # of the split set to agree and the root split to be identical
    assert rt["split_feature"][0] == tt["split_feature"][0]
    from collections import Counter
    cr = Counter(rt["split_feature"].tolist())
    ct = Counter(tt["split_feature"].tolist())
    n_common = sum((cr & ct).values())
    assert n_common >= len(rt["split_feature"]) - 1, (cr, ct)


def test_binning_count_ties_reference_sortforpair_defect(
        reference_binary, tmp_path, monkeypatch):
    """Adversarial count-tie binning (VERDICT r2 weak #6) — this probe
    surfaced a genuine REFERENCE DEFECT rather than a divergence bug on
    our side: Common::SortForPair (common.h:362-381) writes back
    ``keys[i] = arr[i]`` for i in [start, arr.size()) although ``arr`` is
    0-indexed from ``start``, so the remainder value sort in
    BinMapper::FindBin (bin.cpp:93, start=bin_cnt) DROPS the bin_cnt
    smallest remainder values and leaves a stale tail whose content
    depends on std::sort's unstable tie order.  On a feature with three
    dedicated (count>mean) values the reference therefore loses the
    boundaries around its smallest remainder values (verified against a
    harness linking the reference's own bin.cpp: bounds
    [1.25 6.25 9 15.5 22 inf] — 1.25 is midpoint(-3, 5.5) because values
    1, 2, 4 vanished).

    We implement the INTENDED algorithm (documented divergence,
    PARITY.md): bit-for-bit emulation is not even well-defined, since the
    stale tail varies with the C++ standard library's introsort.  This
    test pins both behaviors so any drift on either side is caught, and
    asserts our intended bins find a strictly better first split (the
    defect loses real split candidates)."""
    from tests.test_binning import _adversarial_tie_values
    rng = np.random.RandomState(77)
    f1 = _adversarial_tie_values().copy()
    rng.shuffle(f1)
    n = f1.size
    f2 = rng.randn(n)
    y = ((f1 > 5.0) ^ (rng.rand(n) < 0.15)).astype(int)
    np.savetxt(tmp_path / "ties.csv", np.column_stack([y, f1, f2]),
               fmt="%.7g", delimiter=",")
    conf = tmp_path / "train.conf"
    conf.write_text(
        "task=train\ndata=ties.csv\nobjective=binary\nnum_leaves=2\n"
        "min_data_in_leaf=20\nmax_bin=10\nnum_iterations=1\n"
        "learning_rate=0.1\nmetric_freq=100\n")

    _run_reference(reference_binary, tmp_path, "train.conf",
                   ["output_model=ref.txt"] + DET)
    _run_ours(tmp_path, monkeypatch, ["output_model=ours.txt"] + DET)

    rt = _parse_model_trees(tmp_path / "ref.txt")[0]
    tt = _parse_model_trees(tmp_path / "ours.txt")[0]
    # the reference's defect-lossy bins pick threshold 6.25 (it no longer
    # HAS a 4.75 boundary — midpoint of the dropped 4 and surviving 5.5)
    assert rt["split_feature"][0] == 0 and tt["split_feature"][0] == 0
    assert np.isclose(rt["threshold"][0], 6.25)
    # ours keeps the intended boundary and finds the strictly better
    # split the reference lost
    assert np.isclose(tt["threshold"][0], 4.75)
    assert tt["split_gain"][0] > rt["split_gain"][0] * 1.2

    # non-adversarial binning agreement is covered by the exact-tree
    # differential suite; this test only pins the defect feature


def test_reference_bin_cache_fallback(reference_binary, tmp_path,
                                      monkeypatch):
    """A reference-written <data>.bin next to the data file (the reference
    auto-loads it, dataset.cpp:653-898) must not break 'configs run
    unchanged': our loader now loads the reference cache NATIVELY
    (io/dataset._load_reference_binary, see test_reference_bin_cache.py
    for the format differentials) and leaves it untouched even under
    is_save_binary_file=true (VERDICT r2 missing #4)."""
    _setup_example(tmp_path, "binary_classification")
    # have the reference binary write its own cache
    _run_reference(reference_binary, tmp_path, "train.conf",
                   ["num_trees=1", "is_save_binary_file=true",
                    "output_model=ref.txt"] + DET)
    bin_path = tmp_path / "binary.train.bin"
    assert bin_path.exists()
    ref_cache = bin_path.read_bytes()

    _run_ours(tmp_path, monkeypatch,
              ["num_trees=2", "num_leaves=15",
               "is_save_binary_file=true", "output_model=ours.txt"] + DET)
    model = (tmp_path / "ours.txt").read_text()
    assert model.count("Tree=") == 2          # trained (from the cache)
    assert bin_path.read_bytes() == ref_cache  # cache left untouched
