"""Telemetry subsystem tests (ISSUE 1): route counters, span nesting,
zero-overhead disabled mode, the JSONL sink's per-iteration schema, and the
tier-1 invariant that instrumentation never perturbs training numerics."""
import json
import time

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.io.dataset import Dataset


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry is process-global state: every test starts disabled/zeroed
    and leaves nothing armed for the rest of the suite."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _data(n=1200, seed=0, features=6):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, features)
    y = (x[:, 0] + 0.5 * x[:, 1] + 0.1 * rng.randn(n) > 0).astype(np.float32)
    return x, y


BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
        "min_sum_hessian_in_leaf": 1.0, "learning_rate": 0.2}


# ----------------------------------------------------------------- counters

def test_counters_increment_on_forced_fallback(monkeypatch):
    """LGBM_TPU_NO_PALLAS=1 must leave a runtime record: the env-trip
    counter and the XLA fallback route counter both tick."""
    monkeypatch.setenv("LGBM_TPU_NO_PALLAS", "1")
    telemetry.enable()
    from lightgbm_tpu.ops.histogram import histogram_leafbatch
    bins = jnp.zeros((2, 16), jnp.uint8)
    g = jnp.ones((16,), jnp.float32)
    h = jnp.ones((16,), jnp.float32)
    cid = jnp.zeros((16,), jnp.int32)
    ok = jnp.ones((16,), bool)
    out = histogram_leafbatch(bins, g, h, cid, ok, 1, 4,
                              compute_dtype="int8")
    assert out.shape == (1, 2, 4, 3)
    c = telemetry.counters()
    assert c.get("hist/env_no_pallas", 0) >= 1
    assert c.get("hist/xla_int8", 0) >= 1
    # the partition eligibility rule trips the same hatch
    from lightgbm_tpu.ops.compact import pallas_partition_ok
    assert pallas_partition_ok() is False
    assert telemetry.counters().get("partition/env_no_pallas", 0) >= 1


def test_route_counters_float_fallback():
    telemetry.enable()
    from lightgbm_tpu.ops.histogram import histogram_leafbatch
    bins = jnp.zeros((2, 16), jnp.uint8)
    g = jnp.ones((16,), jnp.float32)
    h = jnp.ones((16,), jnp.float32)
    histogram_leafbatch(bins, g, h, jnp.zeros((16,), jnp.int32),
                        jnp.ones((16,), bool), 1, 4,
                        compute_dtype=jnp.float32)
    c = telemetry.counters()
    # CPU backend: Pallas ineligible, einsum fallback taken
    assert c.get("hist/xla_einsum", 0) >= 1
    assert c.get("hist/pallas_ineligible", 0) >= 1


# -------------------------------------------------------------------- spans

def test_spans_nest_correctly():
    telemetry.enable()
    with telemetry.span("outer"):
        time.sleep(0.002)
        with telemetry.span("inner"):
            time.sleep(0.002)
    snap = telemetry.snapshot()
    assert snap["phase_times"]["outer"] >= snap["phase_times"]["inner"] > 0
    assert snap["phase_counts"] == {"outer": 1, "inner": 1}
    # re-entrant same-name spans are suppressed (recursive helpers must
    # not double-count wall time under one name)
    with telemetry.span("outer"):
        with telemetry.span("outer"):
            time.sleep(0.001)
    assert telemetry.snapshot()["phase_counts"]["outer"] == 2
    # the stack unwinds on exceptions
    with pytest.raises(RuntimeError):
        with telemetry.span("boom"):
            raise RuntimeError("x")
    with telemetry.span("after"):
        pass
    assert "after" in telemetry.snapshot()["phase_times"]


def test_disabled_mode_records_nothing(tmp_path):
    assert not telemetry.enabled()
    with telemetry.span("phantom"):
        pass
    telemetry.count("phantom_counter")
    snap = telemetry.snapshot()
    assert snap["phase_times"] == {} and snap["counters"] == {}
    # a train without metrics_out writes no file and leaves no records
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    lgb.train(dict(BASE, num_iterations=2), ds)
    snap = telemetry.snapshot()
    assert snap["phase_times"] == {} and snap["counters"] == {}
    # nor does the fused chunk path, whose set-up and host-turn spans and
    # counters (ISSUE 27) are entered all the same: dataset_bin, find_bins,
    # binarize, booster_init, h2d, tree_build; bin/*, init/*, train/*, and
    # the compile listener's trace seconds
    ds = Dataset.from_arrays(x, y, max_bin=32)
    lgb.train(dict(BASE, num_iterations=8, grow_policy="depthwise"), ds)
    snap = telemetry.snapshot()
    assert snap["phase_times"] == {} and snap["counters"] == {}
    assert snap["trace_times"] == {} and snap["phase_counts"] == {}


# ------------------------------------------- set-up and host-turn spans

def _setup_and_train(n_iters=16):
    """What the benchmark's traffic does, at a toy size, with telemetry
    on: bin a table, build a booster, train in fused chunks of 8."""
    from lightgbm_tpu.objectives import create_objective
    telemetry.enable(fence=False)
    telemetry.reset()
    x, y = _data(n=3000, features=5)
    ds = Dataset.from_arrays(x, y, max_bin=32)
    config = lgb.OverallConfig()
    config.set({k: str(v) for k, v in dict(
        BASE, grow_policy="depthwise", hist_dtype="int8").items()},
        require_data=False)
    booster = lgb.GBDT()
    booster.init(config.boosting_config, ds,
                 create_objective(config.objective_type,
                                  config.objective_config))
    booster.run_training(n_iters, is_eval=False)
    snap = telemetry.snapshot()
    telemetry.disable()
    return booster, snap


def test_setup_spans_nest_and_sum():
    """A layer's self time is its span less its children (choosing-metrics
    guide, section 4): dataset_bin holds find_bins and binarize,
    booster_init holds h2d, and what is left over is not negative."""
    booster, snap = _setup_and_train()
    t, n = snap["phase_times"], snap["phase_counts"]
    for name in ("dataset_bin", "find_bins", "binarize", "booster_init",
                 "h2d", "train_chunk", "model_readback", "tree_build"):
        assert t[name] > 0, name
    assert t["dataset_bin"] >= t["find_bins"] + t["binarize"]
    # the children fill the parent: nothing else is timed under it
    assert t["dataset_bin"] - t["find_bins"] - t["binarize"] \
        < 0.05 * t["dataset_bin"] + 1e-3
    assert t["booster_init"] >= t["h2d"]
    assert n["find_bins"] == n["binarize"] == n["booster_init"] \
        == n["h2d"] == 1
    assert n["train_chunk"] == n["model_readback"] == 2
    assert n["tree_build"] == len(booster.models) == 16
    c = snap["counters"]
    assert c["bin/values"] == 3000 * 5
    assert c["bin/sample_rows"] == 3000
    assert c["init/h2d_bytes"] == 3000 * 5        # uint8 codes
    assert c["train/readback_bytes"] > 0
    # the compile listener keeps every stage of building a program
    for stage in ("jaxpr_trace", "lower"):
        assert snap["trace_times"][stage] > 0, stage


def test_train_counters_repeat_exactly():
    """``bin/values``, ``train/iterations`` and ``train/chunks`` are
    counts of work, not of time: two runs of one job read alike."""
    keys = ("bin/values", "bin/sample_rows", "init/h2d_bytes",
            "train/iterations", "train/chunks", "train/readback_bytes")
    first, second = [
        {k: _setup_and_train()[1]["counters"].get(k) for k in keys}
        for _ in range(2)]
    assert first == second
    assert first["train/iterations"] == 16 and first["train/chunks"] == 2


def test_file_loaders_get_the_binning_spans(tmp_path):
    """The spans live in the shared internals, not in from_arrays."""
    x, y = _data(n=500, features=4)
    path = tmp_path / "train.tsv"
    np.savetxt(path, np.column_stack([y, x]), delimiter="\t", fmt="%.6f")
    config = lgb.OverallConfig()
    config.set({"data": str(path), "max_bin": "16"}, require_data=False)
    telemetry.enable()
    telemetry.reset()
    Dataset.load_train(config.io_config)
    snap = telemetry.snapshot()
    t = snap["phase_times"]
    assert t["dataset_bin"] >= t["find_bins"] + t["binarize"] > 0
    assert snap["counters"]["bin/values"] == 500 * 4
    assert snap["counters"]["bin/sample_rows"] == 500


def test_compile_listener_separates_loads_from_compiles():
    """jax fires its backend-compile duration event around the
    persistent-cache lookup too: a lookup that hit is a load, kept under
    cache_load, and is not counted as a compile."""
    telemetry.enable()
    telemetry.reset()
    from jax import monitoring
    base = "/jax/core/compile/"
    monitoring.record_event_duration_secs(base + "jaxpr_trace_duration", 0.5)
    monitoring.record_event_duration_secs(
        base + "jaxpr_to_mlir_module_duration", 0.25)
    monitoring.record_event_duration_secs(
        base + "backend_compile_duration", 2.0)          # a true compile
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    monitoring.record_event_duration_secs(
        base + "backend_compile_duration", 0.125)        # the same lookup
    snap = telemetry.snapshot()
    assert snap["trace_times"] == {"jaxpr_trace": 0.5, "lower": 0.25,
                                   "backend_compile": 2.0,
                                   "cache_load": 0.125}
    assert snap["counters"] == {"jit/backend_compile": 1,
                                "jit/persistent_cache_miss": 1,
                                "jit/persistent_cache_hit": 1}


def test_nested_jaxpr_traces_are_counted_once(monkeypatch):
    """An inner jit traced inside an outer one reports first and lies
    inside the outer's interval: its seconds are the outer's too."""
    telemetry.enable()
    telemetry.reset()
    now = [100.0]
    monkeypatch.setattr(telemetry.time, "perf_counter", lambda: now[0])
    now[0] = 101.0
    telemetry._on_jaxpr_trace(0.25)      # inner: [100.75, 101.0]
    now[0] = 101.5
    telemetry._on_jaxpr_trace(0.25)      # inner: [101.25, 101.5]
    now[0] = 102.0
    telemetry._on_jaxpr_trace(1.5)       # outer: [100.5, 102.0]
    now[0] = 103.0
    telemetry._on_jaxpr_trace(0.5)       # a later, separate trace
    assert telemetry.snapshot()["trace_times"]["jaxpr_trace"] == 2.0


# --------------------------------------------------------------------- sink

def _check_record_schema(rec):
    assert isinstance(rec["iter"], int)
    for key in telemetry.CANONICAL_PHASES:
        assert key in rec["phase_times"]
    for v in rec["phase_times"].values():
        assert isinstance(v, (int, float)) and v >= 0
    assert isinstance(rec["counters"], dict)
    assert isinstance(rec["eval_metrics"], dict)
    # ISSUE 2: metrics_out= armed runs resolve health="auto" and
    # memory_stats="auto" ON — every record carries both blocks
    from lightgbm_tpu import health as health_mod
    for key in health_mod.HEALTH_VEC_KEYS + health_mod.TREE_HEALTH_KEYS:
        assert key in rec["health"], key
    assert rec["memory"]["peak_bytes_in_use"] >= 0
    assert rec["memory"]["source"] in ("device", "host_rss", "unavailable")


def test_jsonl_sink_per_iteration_schema(tmp_path):
    """3-iteration CPU train (per-iteration leaf-wise path): one
    schema-valid record per iteration plus the summary.

    Route counters fire at TRACE time, so the dataset shape must be unique
    to this test — a shape any earlier test already compiled would replay
    its cached program and record no new route decisions."""
    x, y = _data(n=1357, features=7)
    ds = Dataset.from_arrays(x, y, max_bin=48)
    path = str(tmp_path / "m.jsonl")
    lgb.train(dict(BASE, num_iterations=3, num_leaves=13,
                   metric="binary_logloss",
                   is_training_metric="true", metrics_out=path), ds)
    telemetry.disable()
    recs = [json.loads(line) for line in open(path)]
    iter_recs = [r for r in recs if "iter" in r]
    assert [r["iter"] for r in iter_recs] == [1, 2, 3]
    for rec in iter_recs:
        _check_record_schema(rec)
    # eval metrics ride the records
    assert any("training/" in k for r in iter_recs
               for k in r["eval_metrics"])
    # route counters are present and monotonic across records
    hist_counts = [sum(v for k, v in r["counters"].items()
                       if k.startswith("hist/")) for r in iter_recs]
    assert hist_counts[0] > 0
    assert hist_counts == sorted(hist_counts)
    assert recs[-1].get("summary") is True
    # ISSUE 2: the one-shot residency record precedes the iterations, and
    # the summary carries cumulative health + memory blocks
    residency = [r for r in recs if "residency" in r]
    assert residency and residency[0]["residency"]["bin_matrix_bytes"] > 0
    assert recs[-1]["health"]["anomalous_iterations"] == 0
    assert recs[-1]["memory"]["peak_bytes_in_use"] > 0


def test_jsonl_sink_chunked_one_record_per_iteration(tmp_path):
    """10-iteration depthwise CPU train rides the fused chunk path; the
    sink still gets exactly one record per iteration (amortized)."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    path = str(tmp_path / "m.jsonl")
    lgb.train(dict(BASE, num_iterations=10, grow_policy="depthwise",
                   metrics_out=path), ds)
    telemetry.disable()
    recs = [json.loads(line) for line in open(path)]
    iter_recs = [r for r in recs if "iter" in r]
    assert [r["iter"] for r in iter_recs] == list(range(1, 11))
    for rec in iter_recs:
        _check_record_schema(rec)
        assert rec["amortized_over"] >= 1


def test_sink_closed_after_train_no_leak(tmp_path):
    """A train() that armed the sink closes it: a later train() without
    metrics_out must not append records to the first run's file."""
    x, y = _data()
    ds = Dataset.from_arrays(x, y, max_bin=32)
    path = str(tmp_path / "m.jsonl")
    lgb.train(dict(BASE, num_iterations=2, metrics_out=path), ds)
    assert not telemetry.sink_active()
    n_lines = len(open(path).read().splitlines())
    ds2 = Dataset.from_arrays(x, y, max_bin=32)
    lgb.train(dict(BASE, num_iterations=2), ds2)
    assert len(open(path).read().splitlines()) == n_lines


# ------------------------------------------------------------ memory gauges

def test_memory_peak_rebaselines_across_reset():
    """The allocator's peak_bytes_in_use is monotonic over the PROCESS: a
    small run after a big one must not inherit the big run's peak, but
    growth past the post-reset baseline (a transient spike between
    samples) does count (white-box: stubs the device handle)."""
    class FakeDev:
        stats = {}

        def memory_stats(self):
            return dict(self.stats)

    dev = FakeDev()
    telemetry._mem_device = dev
    try:
        telemetry.reset()
        dev.stats = {"bytes_in_use": 9_000, "peak_bytes_in_use": 10_000}
        telemetry._mem_sample()
        assert telemetry.mem_peak_bytes() == 9_000
        telemetry.reset()   # fresh run: 10_000 lifetime peak is history
        dev.stats = {"bytes_in_use": 2_000, "peak_bytes_in_use": 10_000}
        telemetry._mem_sample()
        assert telemetry.mem_peak_bytes() == 2_000
        # allocator peak GREW past the baseline -> this run's spike
        dev.stats = {"bytes_in_use": 3_000, "peak_bytes_in_use": 11_000}
        telemetry._mem_sample()
        assert telemetry.mem_peak_bytes() == 11_000
    finally:
        telemetry._mem_device = None
        telemetry.reset()


# ---------------------------------------------------- numerics non-perturbation

def test_scores_identical_with_telemetry_on_vs_off(tmp_path):
    """Tier-1 invariant: instrumentation must not perturb numerics or jit
    caching — train_one_iter produces bit-identical scores either way."""
    x, y = _data(seed=3)
    params = dict(BASE, num_iterations=4, bagging_fraction=0.7,
                  bagging_freq=1)

    def scores(with_telemetry):
        if with_telemetry:
            telemetry.enable(str(tmp_path / "on.jsonl"), fence=True)
        else:
            telemetry.disable()
        telemetry.reset()
        ds = Dataset.from_arrays(x, y, max_bin=32)
        booster = lgb.train(params, ds)
        out = np.asarray(booster.score)
        telemetry.disable()
        return out

    off = scores(False)
    on = scores(True)
    np.testing.assert_array_equal(off, on)
