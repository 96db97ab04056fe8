"""Training-health monitor tests (ISSUE 2): injected-NaN gradients raise
health events (and halt cleanly under on_anomaly=halt), both training paths
emit health/memory sink blocks, eval-divergence detection fires, and the
tier-1 invariant that the monitor never perturbs training numerics."""
import json

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu import health as health_mod
from lightgbm_tpu.health import HealthMonitor, TrainingHealthError
from lightgbm_tpu.config import OverallConfig
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models.gbdt import GBDT


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _data(n=1100, seed=5, features=6):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, features)
    y = (x[:, 0] + 0.5 * x[:, 1] + 0.1 * rng.randn(n) > 0).astype(np.float32)
    return x, y


BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
        "min_sum_hessian_in_leaf": 1.0, "learning_rate": 0.2}


class _NaNObjective:
    """Regression-like objective that poisons the first ``bad`` gradients
    with NaN from iteration ``start_iter`` on — the injected-fault fixture
    the health monitor must catch."""
    sigmoid = -1.0
    num_class = 1

    def __init__(self, bad=7, start_iter=0):
        self.bad = bad
        self.start_iter = start_iter
        self._calls = 0

    def init(self, metadata, num_data):
        self.label = jnp.asarray(np.asarray(metadata.label), jnp.float32)

    def get_gradients(self, score):
        grad = score - self.label
        if self._calls >= self.start_iter:
            grad = grad.at[:self.bad].set(jnp.nan)
        self._calls += 1
        return grad, jnp.ones_like(grad)


def _nan_booster(ds, on_anomaly, **extra):
    cfg = OverallConfig()
    cfg.set(dict({k: str(v) for k, v in BASE.items()},
                 objective="regression", health="true",
                 on_anomaly=on_anomaly, **extra), require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds, _NaNObjective())
    return booster


# ---------------------------------------------------------- injected faults

def test_nan_gradients_recorded_and_warn(tmp_path):
    """NaN gradients produce a nonzero grad_nan count in the sink records
    and in the cumulative summary; on_anomaly=warn keeps training alive
    (the NaN root histogram rejects every split, so training stops on the
    degenerate tree, not on the monitor)."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    path = str(tmp_path / "m.jsonl")
    telemetry.enable(path)
    booster = _nan_booster(ds, "warn")
    booster.run_training(3, False)
    telemetry.disable()
    recs = [json.loads(line) for line in open(path)]
    blocks = [r["health"] for r in recs if "iter" in r and "health" in r]
    assert blocks and blocks[0]["grad_nan"] == 7
    assert booster.health_summary()["grad_nan"] >= 7
    assert booster.health_summary()["anomalous_iterations"] >= 1


def test_on_anomaly_halt_stops_cleanly(tmp_path):
    """on_anomaly=halt raises TrainingHealthError (a LightGBMError: the
    CLI maps it to exit 1), naming the offending counts — and the record
    explaining the stop is already in the sink."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    path = str(tmp_path / "m.jsonl")
    telemetry.enable(path)
    booster = _nan_booster(ds, "halt")
    with pytest.raises(TrainingHealthError, match="grad_nan=7"):
        booster.run_training(3, False)
    telemetry.disable()
    from lightgbm_tpu.utils import log
    assert issubclass(TrainingHealthError, log.LightGBMError)
    recs = [json.loads(line) for line in open(path)]
    assert any(r.get("health", {}).get("grad_nan") == 7 for r in recs)


def test_on_anomaly_halt_mid_training():
    """Faults appearing mid-run (start_iter=2) halt at that iteration,
    keeping the clean iterations' trees."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    telemetry.enable()  # no sink: monitor alone must still halt
    cfg = OverallConfig()
    cfg.set(dict({k: str(v) for k, v in BASE.items()},
                 objective="regression", health="true",
                 on_anomaly="halt"), require_data=False)
    booster = GBDT()
    booster.init(cfg.boosting_config, ds, _NaNObjective(start_iter=2))
    with pytest.raises(TrainingHealthError):
        booster.run_training(5, False)
    assert len(booster.models) >= 2
    telemetry.disable()


def test_nan_in_chunked_path_detected(tmp_path):
    """The fused depthwise chunk accumulates the health vector in-program:
    NaN gradients surface with on_anomaly=halt on the chunk path too."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    telemetry.enable(str(tmp_path / "m.jsonl"))
    cfg = OverallConfig()
    cfg.set(dict({k: str(v) for k, v in BASE.items()},
                 objective="regression", health="true", on_anomaly="halt",
                 grow_policy="depthwise"), require_data=False)
    booster = GBDT()
    obj = _NaNObjective()

    # chunk_spec closing over the instance: NaN from iteration 0 in-scan
    def grad_fn(params, score):
        grad = score - params["label"]
        grad = grad.at[:7].set(jnp.nan)
        return grad, jnp.ones_like(grad)

    obj.chunk_spec = lambda: (("nan_test",),
                              {"label": obj.label}, grad_fn)
    booster.init(cfg.boosting_config, ds, obj)
    with pytest.raises(TrainingHealthError, match="grad_nan"):
        booster.train_chunk(4)
    telemetry.disable()


# ------------------------------------------------------------- sink schema

def test_health_memory_blocks_on_both_paths(tmp_path):
    """Acceptance: a CPU train with metrics_out= emits per-iteration
    records containing health and memory blocks — per-iteration leaf-wise
    AND fused depthwise chunk paths."""
    x, y = _data(n=1234)
    for tag, extra in (("leafwise", {"num_iterations": 3}),
                       ("depthwise", {"num_iterations": 8,
                                      "grow_policy": "depthwise"})):
        ds = Dataset.from_arrays(x, y, max_bin=32)
        path = str(tmp_path / (tag + ".jsonl"))
        lgb.train(dict(BASE, metrics_out=path, **extra), ds)
        telemetry.disable()
        recs = [json.loads(line) for line in open(path)]
        iter_recs = [r for r in recs if "iter" in r]
        assert len(iter_recs) == extra["num_iterations"], tag
        for rec in iter_recs:
            for key in (health_mod.HEALTH_VEC_KEYS
                        + health_mod.TREE_HEALTH_KEYS):
                assert key in rec["health"], (tag, key)
            assert rec["health"]["grad_nan"] == 0
            assert rec["memory"]["peak_bytes_in_use"] > 0
        # residency is filed once, before the first iteration record
        assert "residency" in recs[0]
        assert recs[0]["residency"]["num_rows"] == 1234


def test_health_off_means_no_blocks(tmp_path):
    """health=false with a sink: records carry NO health block (and no
    monitor runs), so the setting is a true kill switch."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    path = str(tmp_path / "m.jsonl")
    booster = lgb.train(dict(BASE, num_iterations=2, metrics_out=path,
                             health="false"), ds)
    telemetry.disable()
    assert booster.health_summary() is None
    recs = [json.loads(line) for line in open(path)]
    assert all("health" not in r for r in recs if "iter" in r)


# ------------------------------------------------------------- divergence

def test_eval_divergence_detection():
    """k consecutive worsening metric values flag an eval_divergence
    anomaly (unit-level: the monitor's streak logic, both directions)."""
    mon = HealthMonitor(on_anomaly="record", divergence_rounds=3)
    # bigger_better=False (loss): strictly increasing = worsening
    for v in (0.5, 0.6, 0.7):  # two worsenings after the first value
        mon.observe_eval("valid/loss", v, False)
    assert not mon._pending_divergence
    mon.observe_eval("valid/loss", 0.8, False)  # third consecutive
    block = mon.assemble(None)
    assert block["eval_divergence"][0]["metric"] == "valid/loss"
    assert block["eval_divergence"][0]["rounds"] == 3
    assert mon.anomalies(block) == ["eval_divergence:valid/loss"]
    # an improvement resets the streak (bigger_better=True: decreasing is
    # worsening; the bump to 0.75 arrives before the streak reaches 3)
    mon2 = HealthMonitor(on_anomaly="record", divergence_rounds=3)
    for v in (0.9, 0.8, 0.7, 0.75, 0.74, 0.73):
        mon2.observe_eval("t/auc", v, True)
    assert not mon2._pending_divergence


def test_divergence_halts_training(tmp_path):
    """End-to-end: a validation metric forced to worsen every iteration
    trips health_divergence_rounds under on_anomaly=halt."""
    x, y = _data(seed=11)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    # validate on ANTI-labels: every boosting iteration makes the valid
    # logloss strictly worse, a textbook divergence
    vs = Dataset.from_arrays(x[:400], 1.0 - y[:400], reference=ds)
    with pytest.raises(TrainingHealthError, match="eval divergence"):
        lgb.train(dict(BASE, num_iterations=12, metric="binary_logloss",
                       health="true", on_anomaly="halt",
                       health_divergence_rounds=3,
                       metrics_out=str(tmp_path / "m.jsonl")),
                  ds, valid_sets=[vs])
    telemetry.disable()


def test_divergence_halt_mid_chunk_leaves_consistent_state(tmp_path):
    """A halt raised inside the fused chunk loop must leave the booster
    exactly like an early stop at that iteration: surplus scan iterations
    rolled back, models/iter/score in agreement."""
    x, y = _data(seed=13)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    vs = Dataset.from_arrays(x[:400], 1.0 - y[:400], reference=ds)
    telemetry.enable(str(tmp_path / "m.jsonl"))
    cfg = OverallConfig()
    cfg.set(dict({k: str(v) for k, v in BASE.items()},
                 grow_policy="depthwise", metric="binary_logloss",
                 health="true", on_anomaly="halt",
                 health_divergence_rounds=3), require_data=False)
    booster = GBDT()
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.metrics import create_metric
    booster.init(cfg.boosting_config, ds,
                 create_objective("binary", cfg.objective_config))
    booster.add_valid_dataset(vs, [create_metric("binary_logloss",
                                                 cfg.metric_config)])
    with pytest.raises(TrainingHealthError, match="eval divergence"):
        booster.train_chunk(12, is_eval=True)
    telemetry.disable()
    # halted at the divergence iteration, state truncated there
    assert 0 < booster.iter < 12
    assert len(booster.models) == booster.iter
    # the rolled-back score matches replaying exactly the kept trees
    replay = np.zeros(ds.num_data)
    for tree in booster.models:
        replay += tree.predict(x)
    np.testing.assert_allclose(np.asarray(booster.score[0]), replay,
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- numerics non-perturbation

def test_scores_bit_identical_health_on_vs_off(tmp_path):
    """Tier-1 invariant: the monitor computes FROM training arrays, never
    into them — scores are bit-identical with health on vs off, telemetry
    armed both times, on both growth paths."""
    x, y = _data(seed=9)

    def scores(health, grow_policy):
        telemetry.disable()
        telemetry.reset()
        ds = Dataset.from_arrays(x, y, max_bin=32)
        booster = lgb.train(dict(BASE, num_iterations=4,
                                 grow_policy=grow_policy, health=health,
                                 metrics_out=str(tmp_path / "m.jsonl"),
                                 bagging_fraction=0.8, bagging_freq=1), ds)
        out = np.asarray(booster.score)
        telemetry.disable()
        return out

    for gp in ("leafwise", "depthwise"):
        np.testing.assert_array_equal(scores("false", gp),
                                      scores("true", gp))


# ------------------------------------------------------------------ config

def test_health_config_options():
    cfg = OverallConfig()
    cfg.set({"health": "true", "on_anomaly": "halt",
             "health_divergence_rounds": "4", "memory_stats": "false"},
            require_data=False)
    assert cfg.boosting_config.health == "true"
    assert cfg.boosting_config.on_anomaly == "halt"
    assert cfg.boosting_config.health_divergence_rounds == 4
    assert cfg.io_config.memory_stats == "false"
    # defaults
    d = OverallConfig()
    assert d.boosting_config.health == "auto"
    assert d.boosting_config.on_anomaly == "warn"
    assert d.io_config.memory_stats == "auto"
    from lightgbm_tpu.utils import log
    with pytest.raises(log.LightGBMError):
        OverallConfig().set({"on_anomaly": "explode"}, require_data=False)


@pytest.mark.parametrize("arm, want", [
    ("off", False), ("enable", False), ("enable_path", True)])
def test_health_auto_follows_the_record_sink(tmp_path, capsys, arm, want):
    """``health=auto`` is on with a ``metrics_out=`` sink and off under a
    bare ``telemetry.enable()``: the monitor adds its vector to the fused
    program, so arming the registry alone (the benchmark's traced run) must
    not change the program.  Said once when telemetry is on and the monitor
    stays off; ``true`` / ``false`` force it either way."""
    if arm == "enable":
        telemetry.enable()
    elif arm == "enable_path":
        telemetry.enable(str(tmp_path / "m.jsonl"))
    health_mod._told_auto_off = False
    assert health_mod.resolve_enabled("auto") is want
    assert health_mod.resolve_enabled("auto") is want
    said = capsys.readouterr()
    assert (said.out + said.err).count("health=auto leaves") \
        == (1 if arm == "enable" else 0)
    assert health_mod.resolve_enabled("true") is True
    assert health_mod.resolve_enabled("false") is False
    # and the booster follows: no monitor, so on_anomaly=halt is not armed
    x, y = _data()
    booster = GBDT()
    from lightgbm_tpu.objectives import create_objective
    cfg = OverallConfig()
    cfg.set({k: str(v) for k, v in BASE.items()}, require_data=False)
    booster.init(cfg.boosting_config, Dataset.from_arrays(x, y, max_bin=32),
                 create_objective(cfg.objective_type, cfg.objective_config))
    assert (booster._health_monitor is not None) is want


def test_quant_saturation_gauge():
    """int8 saturation gauge: uniform magnitudes all sit at the per-pass
    max → every entry saturates; a spread distribution saturates only the
    max row (per channel)."""
    from lightgbm_tpu.ops.hist_pallas import quant_saturation_count
    g = jnp.full((64,), 3.0)
    h = jnp.linspace(0.1, 1.0, 64)
    sat = float(quant_saturation_count(g, h))
    assert sat == 64 + 1  # all grads + the single max hessian
