"""Config system tests (aliases, conflicts, file parsing) —
/root/reference config.cpp parity."""
import os

import pytest

from lightgbm_tpu.config import (OverallConfig, apply_aliases, load_config,
                                 parse_config_file)
from lightgbm_tpu.utils.log import LightGBMError


def _set(params, **kw):
    cfg = OverallConfig()
    cfg.set(dict(params), require_data=kw.get("require_data", False))
    return cfg


def test_aliases():
    out = apply_aliases({"num_tree": "50", "sub_feature": "0.5",
                         "min_data": "10"})
    assert out["num_iterations"] == "50"
    assert out["feature_fraction"] == "0.5"
    assert out["min_data_in_leaf"] == "10"


def test_alias_does_not_override_canonical():
    out = apply_aliases({"num_tree": "50", "num_iterations": "99"})
    assert out["num_iterations"] == "99"


def test_defaults():
    cfg = _set({})
    assert cfg.boosting_config.num_iterations == 10
    assert cfg.boosting_config.learning_rate == 0.1
    assert cfg.boosting_config.tree_config.num_leaves == 127
    assert cfg.boosting_config.tree_config.min_data_in_leaf == 100
    assert cfg.io_config.max_bin == 256
    assert cfg.metric_config.eval_at == [1, 2, 3, 4, 5]
    assert cfg.objective_config.label_gain[2] == 3.0  # 2^2-1


def test_multiclass_conflict():
    with pytest.raises(LightGBMError):
        _set({"objective": "multiclass", "num_class": "1"})
    with pytest.raises(LightGBMError):
        _set({"objective": "binary", "num_class": "3"})
    with pytest.raises(LightGBMError):
        _set({"objective": "binary", "metric": "multi_logloss"})


def test_parallel_conflict_resolution():
    # serial forces num_machines=1 (config.cpp:164-167)
    cfg = _set({"tree_learner": "serial", "num_machines": "4"})
    assert cfg.network_config.num_machines == 1
    assert not cfg.is_parallel
    # data-parallel keeps machines and enables parallel bin finding
    cfg = _set({"tree_learner": "data", "num_machines": "4"})
    assert cfg.is_parallel
    assert cfg.is_parallel_find_bin


def test_hybrid_voting_learners_accepted():
    # the reference snapshot Fatals on tree_learner=voting
    # (config.cpp:311-313); ISSUE 9 realizes it, plus the 2-D hybrid
    # learner, with the mesh-factoring / vote-width knobs
    cfg = _set({"tree_learner": "voting", "num_machines": "2"})
    assert cfg.boosting_config.tree_learner == "voting"
    assert cfg.is_parallel
    assert cfg.boosting_config.tree_config.top_k == 20  # PV-tree default
    cfg = _set({"tree_learner": "hybrid", "num_machines": "4",
                "feature_shards": "2", "topk": "7"})
    assert cfg.boosting_config.tree_learner == "hybrid"
    assert cfg.boosting_config.tree_config.feature_shards == 2
    assert cfg.boosting_config.tree_config.top_k == 7  # topk alias
    with pytest.raises(LightGBMError):
        _set({"feature_shards": "-1"})
    with pytest.raises(LightGBMError):
        _set({"top_k": "0"})


def test_bad_values():
    with pytest.raises(LightGBMError):
        _set({"num_leaves": "1"})
    with pytest.raises(LightGBMError):
        _set({"learning_rate": "abc"})
    with pytest.raises(LightGBMError):
        _set({"bagging_fraction": "1.5"})
    with pytest.raises(LightGBMError):
        _set({"task": "explode"})


def test_config_file_and_argv_priority(tmp_path):
    conf = tmp_path / "t.conf"
    conf.write_text("# comment\nnum_trees = 77\nlearning_rate = 0.3  # tail\n"
                    "data = train.txt\n")
    params = parse_config_file(str(conf))
    assert params["num_trees"] == "77"
    assert params["learning_rate"] == "0.3"
    # argv wins over file (application.cpp:98)
    cfg = load_config([f"config={conf}", "num_trees=5"])
    assert cfg.boosting_config.num_iterations == 5


def test_metric_dedup():
    cfg = _set({"metric": "auc,auc,binary_logloss"})
    assert cfg.metric_types == ["auc", "binary_logloss"]


def test_verbosity_wires_log_level(capsys):
    """verbosity=3 (the ``verbosity`` alias included) must actually enable
    log.debug output at config/CLI startup — the reference's rule
    (config.cpp:59-70), single-homed in log.set_level_from_verbosity."""
    from lightgbm_tpu.utils import log
    old = log.get_level()
    try:
        _set({"verbosity": "3"})
        assert log.get_level() == log.DEBUG
        log.debug("debug-visible")
        assert "debug-visible" in capsys.readouterr().out
        _set({"verbose": "0"})
        assert log.get_level() == log.WARNING
        log.debug("debug-hidden")
        assert "debug-hidden" not in capsys.readouterr().out
        _set({"verbosity": "-1"})
        assert log.get_level() == log.FATAL
    finally:
        log.set_level(old)


def test_metrics_out_option(tmp_path):
    cfg = _set({"metrics_out": str(tmp_path / "m.jsonl"),
                "metrics_fence": "true"})
    assert cfg.io_config.metrics_out == str(tmp_path / "m.jsonl")
    assert cfg.io_config.metrics_fence is True
    assert _set({}).io_config.metrics_out == ""


def test_unknown_key_warns_and_trains_the_same(tmp_path, capsys):
    """A key nothing reads — here ``leafwise_segments``, a knob that was
    removed — is named in a warning and changes nothing: the model text
    is byte-equal to the run without it."""
    import numpy as np
    from lightgbm_tpu.io.dataset import Dataset
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.RandomState(5)
    x = rng.randn(3000, 6)
    y = (x[:, 0] + 0.4 * x[:, 1] > 0).astype(np.float64)
    ds = Dataset.from_arrays(x, y, max_bin=63)

    def train(extra):
        cfg = _set({"objective": "binary", "num_leaves": "15",
                    "num_iterations": "4", "min_data_in_leaf": "20",
                    **extra})
        booster = GBDT()
        booster.init(cfg.boosting_config, ds,
                     create_objective(cfg.objective_type,
                                      cfg.objective_config))
        for _ in range(4):
            booster.train_one_iter(is_eval=False)
        path = tmp_path / ("model_%d.txt" % len(extra))
        booster.save_model_to_file(True, str(path))
        return path.read_bytes()

    plain = train({})
    assert "Unknown parameter" not in capsys.readouterr().out
    assert train({"leafwise_segments": "4"}) == plain
    assert ("Unknown parameter leafwise_segments"
            in capsys.readouterr().out)
