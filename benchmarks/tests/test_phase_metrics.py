"""The per-layer metrics of PR 27, on a trace of the program with its closed
set of device phases (``telemetry.DEVICE_PHASES``) recorded on a v5e:
``higgs-levelwise-int8.train`` cut to 65,536 rows with ``--rows``, one
traced slice of 8 iterations, beside PR 26's recording of the older
program (``test_trace_reduce.py``, untouched).  And the readers of
the program's own registry, which read nothing with telemetry off."""
import gzip
import os
import shutil
import types

import pytest

from harness import readers, trace_reduce
from harness.trace_reduce import TraceSummary

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "levelwise_int8_phases_65536rows.xplane.pb.gz")
ITERATIONS = 8
# read from the recording (my chip run, PR 27)
WINDOW_S = 0.046820715000000006
BUSY_S = 0.040067941
ROW_ROUTE = 0.102795625
SCORE_UPDATE = 0.015486250000000002
UNSCOPED = 1.28808025
HIST = 3.3154472500000005
SPLIT_FIND = 0.22647075


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return TraceSummary(trace_reduce.load(str(path)))


def state_of(summary=None, window_compiles=0):
    """What ``readers.State`` holds, without a run behind it."""
    return types.SimpleNamespace(
        summary=summary, traced=(0.0, 1.0, ITERATIONS),
        traced_iterations=ITERATIONS, window_compiles=window_compiles,
        counters={}, values={})


@pytest.fixture
def registry():
    """The program's telemetry registry, clean before and after."""
    from lightgbm_tpu import telemetry
    telemetry.disable()
    telemetry.reset()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def test_the_recording_is_the_new_program(summary):
    assert summary.window_s == pytest.approx(WINDOW_S, rel=1e-9)
    assert summary.busy_s == pytest.approx(BUSY_S, rel=1e-9)


@pytest.mark.parametrize("metric, ms_per_iter", [
    ("row_route_ms_per_iter", ROW_ROUTE), ("score_update_ms_per_iter",
                                           SCORE_UPDATE),
    ("unscoped_ms_per_iter", UNSCOPED),
    # the accepted metrics read the same recording
    ("hist_ms_per_iter", HIST), ("split_find_ms_per_iter", SPLIT_FIND)])
def test_trace_metrics_reduce_to_the_recorded_values(summary, metric,
                                                     ms_per_iter):
    got = readers.read(metric, state_of(summary))
    assert got == pytest.approx(ms_per_iter, rel=1e-6)


def test_every_busy_second_is_under_a_phase_or_in_the_remainder(summary):
    """The phases are disjoint (one pattern each, no operation under two)
    and with the remainder they add up to the busy time."""
    from lightgbm_tpu.telemetry import DEVICE_PHASES
    named = {p: summary.scoped_seconds(r"(^|/)%s(/|$)" % p)
             for p in DEVICE_PHASES}
    assert {p for p, s in named.items() if s} == {
        "histogram", "split_find", "row_route", "score_update", "tree_pack"}
    rest = readers.read("unscoped_ms_per_iter", state_of(summary))
    assert sum(s for s in named.values() if s) \
        + rest * 1e-3 * ITERATIONS == pytest.approx(summary.busy_s, rel=1e-6)
    # on this route XLA fuses the gradients into the first level's int8
    # quantisation, a multi-output fusion whose tuple root carries no
    # metadata: the scope is in the program (tests/test_trace_scopes.py)
    # and nothing of it reaches the trace, so the benchmark has no
    # gradient_ms_per_iter in this cell (PERF.md)
    assert named["gradient"] is None


def test_the_old_recording_has_no_closed_set_to_read(tmp_path):
    """PR 26's program scoped the row routing ``partition`` (and only
    with telemetry on): the new trace metrics read nothing there."""
    old = os.path.join(os.path.dirname(TRACE),
                       "levelwise_int8_65536rows.xplane.pb.gz")
    path = tmp_path / "old.xplane.pb"
    with gzip.open(old, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    state = state_of(TraceSummary(trace_reduce.load(str(path))))
    assert readers.read("row_route_ms_per_iter", state) is None
    assert readers.read("score_update_ms_per_iter", state) is None


def test_the_yardsticks_phases_are_the_programs():
    """``unscoped_ms_per_iter`` keeps its own list of the device phases:
    a phase that the program adds, drops or renames has to be an edit of
    the metric's file as well, where a reviewer sees it."""
    import importlib.util
    from lightgbm_tpu.telemetry import DEVICE_PHASES
    spec = importlib.util.spec_from_file_location(
        "unscoped", os.path.join(readers.METRICS, "unscoped_ms_per_iter.py"))
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    assert metric.PHASES == DEVICE_PHASES


REGISTRY_METRICS = (
    "host_turn_ms_per_iter", "iters_per_chunk", "readback_bytes_per_iter",
    "binning_s", "find_bins_s", "binarize_s", "binarize_mvalues_per_s",
    "booster_init_s", "h2d_gb_per_s", "compile_s", "cache_load_s")


@pytest.mark.parametrize("metric", REGISTRY_METRICS)
def test_registry_readers_read_nothing_with_telemetry_off(registry, metric):
    assert not registry.enabled()
    assert readers.read(metric, state_of()) is None
    # nor without a trace does the remainder
    assert readers.read("unscoped_ms_per_iter", state_of()) is None


def test_every_registry_metric_is_in_the_benchmark():
    import json
    with open(os.path.join(os.path.dirname(readers.METRICS), os.pardir,
                           "BENCHMARK.json")) as fh:
        listed = {m["name"] for m in json.load(fh)["per_layer"]}
    assert set(REGISTRY_METRICS) <= listed


def test_registry_readers_read_the_programs_spans_and_counters(registry):
    registry.enable()
    # a program without the spans and counters (the parent of PR 27)
    for metric in REGISTRY_METRICS:
        assert readers.read(metric, state_of()) is None
    for name in ("dataset_bin", "find_bins", "binarize", "booster_init",
                 "h2d", "model_readback", "tree_build"):
        with registry.span(name):
            pass
    for name, n in (("train/iterations", 16), ("train/chunks", 2),
                    ("train/readback_bytes", 4096), ("bin/values", 15000),
                    ("init/h2d_bytes", 15000)):
        registry.count(name, n)
    spans = registry.snapshot()["phase_times"]
    for metric, span in (("binning_s", "dataset_bin"),
                         ("find_bins_s", "find_bins"),
                         ("binarize_s", "binarize"),
                         ("booster_init_s", "booster_init")):
        assert readers.read(metric, state_of()) == spans[span]
    assert readers.read("host_turn_ms_per_iter", state_of()) == \
        pytest.approx((spans["model_readback"] + spans["tree_build"])
                      * 1e3 / 16)
    assert readers.read("iters_per_chunk", state_of()) == 8
    assert readers.read("readback_bytes_per_iter", state_of()) == 256
    assert readers.read("binarize_mvalues_per_s", state_of()) == \
        pytest.approx(15000 / spans["binarize"] / 1e6)
    assert readers.read("h2d_gb_per_s", state_of()) == \
        pytest.approx(15000 / spans["h2d"] / 1e9)


def test_compile_seconds_are_set_ups_or_nothing(registry):
    """Building and loading are read apart, and only where the whole
    process's seconds are set-up's: nothing built in the window, nothing
    built since the harness read the counters at its close."""
    registry.enable()
    from jax import monitoring

    def build(events):
        for event, seconds in events:
            monitoring.record_event_duration_secs(
                "/jax/core/compile/" + event, seconds)

    build((("jaxpr_trace_duration", 1.0),
           ("jaxpr_to_mlir_module_duration", 0.5),
           ("backend_compile_duration", 4.0)))
    # a second program, served by the persistent cache
    build((("jaxpr_trace_duration", 0.25),
           ("jaxpr_to_mlir_module_duration", 0.25)))
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    build((("cache_retrieval_time_sec", 0.125),
           ("backend_compile_duration", 0.125)))
    at_close = state_of()
    at_close.counters = dict(registry.snapshot()["counters"])
    assert at_close.counters["jit/backend_compile"] == 1
    assert readers.read("compile_s", at_close) == 6.0
    assert readers.read("cache_load_s", at_close) == 0.125
    # a program built inside the window
    in_window = state_of(window_compiles=1)
    in_window.counters = at_close.counters
    assert readers.read("compile_s", in_window) is None
    assert readers.read("cache_load_s", in_window) is None
    # a program built after the window closed (the check's, say)
    build((("jaxpr_trace_duration", 1.0),
           ("backend_compile_duration", 1.0)))
    assert readers.read("compile_s", at_close) is None
    assert readers.read("cache_load_s", at_close) is None
