"""AUC-parity quality gate vs the compiled reference binary (slow).

The north-star quality axis (BASELINE.md: "AUC parity with reference
LightGBM") as an automated test: 100 boosting iterations on 100k
Higgs-style rows, held-out AUC within 0.005 of the reference binary, for
the depthwise (headline), leafwise (reference-parity order) and
quantized-int8 configurations.

Split-finding math is identical to production; only the histogram
ACCUMULATION is routed through the scatter-add oracles
(histogram_leafbatch_segsum / hist_quant_segsum) because the dense one-hot
matmul is a TPU formulation that would take hours on the CPU CI mesh —
f32 sums differ from the matmul path only in reduction order, and the int8
path is bit-identical (int32 accumulation is order-free).
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRAIN_ROWS = 100_000
TEST_ROWS = 30_000
ITERS = 100
AUC_TOL = 0.005


def _auc(labels, scores):
    order = np.argsort(scores)
    ranks = np.empty(len(scores))
    sv = np.asarray(scores)[order]
    uniq, inv, counts = np.unique(sv, return_inverse=True,
                                  return_counts=True)
    start = np.zeros(len(uniq))
    start[1:] = np.cumsum(counts)[:-1]
    ranks[order] = (start + (counts + 1) / 2.0)[inv]
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


@pytest.fixture(scope="module")
def parity_data():
    from bench import make_data
    x, y = make_data(TRAIN_ROWS + TEST_ROWS, 28, seed=17)
    return (x[:TRAIN_ROWS], y[:TRAIN_ROWS],
            x[TRAIN_ROWS:], y[TRAIN_ROWS:])


CONF = {"objective": "binary", "learning_rate": "0.1", "num_leaves": "255",
        "max_bin": "255", "min_data_in_leaf": "100",
        "min_sum_hessian_in_leaf": "10.0"}


@pytest.fixture(scope="module")
def reference_auc(reference_binary, parity_data, tmp_path_factory):
    xtr, ytr, xte, yte = parity_data
    d = tmp_path_factory.mktemp("auc_parity")
    tr, te = str(d / "tr.csv"), str(d / "te.csv")
    np.savetxt(tr, np.column_stack([ytr, xtr]), fmt="%.7g", delimiter=",")
    np.savetxt(te, np.column_stack([yte, xte]), fmt="%.7g", delimiter=",")
    model = str(d / "model.txt")
    conf = str(d / "train.conf")
    with open(conf, "w") as f:
        f.write("task=train\n" + f"data={tr}\nnum_trees={ITERS}\n"
                + "".join(f"{k}={v}\n" for k, v in CONF.items())
                + f"metric_freq=1000\noutput_model={model}\n")
    subprocess.run([reference_binary, f"config={conf}"], check=True,
                   capture_output=True, text=True, timeout=180)
    pconf = str(d / "pred.conf")
    out = str(d / "pred.txt")
    with open(pconf, "w") as f:
        f.write(f"task=predict\ndata={te}\ninput_model={model}\n"
                f"output_result={out}\nis_sigmoid=false\n")
    subprocess.run([reference_binary, f"config={pconf}"], check=True,
                   capture_output=True, text=True, timeout=180)
    return _auc(yte, np.loadtxt(out))


def _train_ours(parity_data, grow_policy, hist_dtype, monkeypatch):
    import jax
    from lightgbm_tpu.config import OverallConfig
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.models import grower as grower_mod
    from lightgbm_tpu.models import grower_depthwise as gd_mod
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.ops import histogram as hist_mod

    # CPU-fast scatter-add accumulation (see module docstring)
    if hist_dtype == "int8":
        monkeypatch.setattr(gd_mod, "histogram_leafbatch",
                            hist_mod.hist_quant_segsum)
    elif hist_dtype == "bfloat16":
        # model the TPU float-gradient Pallas kernel's operand rounding
        # (ops/hist_pallas bf16v: grad/hess ride bf16, f32 accumulation;
        # order differs from the kernel like the f32 oracle does)
        import jax.numpy as jnp

        def bf16_seg(bins, grad, hess, cid, ok, C, B, **kw):
            g = grad.astype(jnp.bfloat16).astype(jnp.float32)
            h = hess.astype(jnp.bfloat16).astype(jnp.float32)
            return hist_mod.histogram_leafbatch_segsum(bins, g, h, cid,
                                                       ok, C, B)
        monkeypatch.setattr(gd_mod, "histogram_leafbatch", bf16_seg)
        # keep hist_dtype=float32 in the config below: the segsum stub
        # above carries the bf16 semantics, and the real bfloat16 config
        # value would re-route to the einsum with bf16 operands (slow on
        # the CPU mesh)
        hist_dtype = "float32"
    else:
        monkeypatch.setattr(gd_mod, "histogram_leafbatch",
                            hist_mod.histogram_leafbatch_segsum)

        def fast_build(bins, grad, hess, mask, num_bins_max, **kw):
            return hist_mod.histogram_segsum(bins, grad, hess, mask,
                                             num_bins_max)
        monkeypatch.setattr(grower_mod, "build_histogram", fast_build)

    xtr, ytr, xte, yte = parity_data
    ds = Dataset.from_arrays(xtr, ytr, max_bin=255)
    cfg = OverallConfig()
    cfg.set({**CONF, "num_iterations": str(ITERS),
             "grow_policy": grow_policy, "hist_dtype": hist_dtype},
            require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds,
                 create_objective(cfg.objective_type, cfg.objective_config))
    done = 0
    while done < ITERS:
        k = min(25, ITERS - done)
        booster.train_chunk(k)
        done += k
    jax.block_until_ready(booster.score)
    return _auc(yte, booster.predict_raw(xte))


@pytest.mark.slow
@pytest.mark.parametrize("grow_policy,hist_dtype", [
    ("depthwise", "float32"),
    ("leafwise", "float32"),
    ("depthwise", "int8"),
    ("depthwise", "bfloat16"),
])
def test_auc_parity_vs_reference(parity_data, reference_auc, grow_policy,
                                 hist_dtype, monkeypatch):
    ours = _train_ours(parity_data, grow_policy, hist_dtype, monkeypatch)
    assert ours >= reference_auc - AUC_TOL, (
        f"{grow_policy}/{hist_dtype}: AUC {ours:.6f} vs reference "
        f"{reference_auc:.6f} (tol {AUC_TOL})")
