"""The reduction from a trace to device seconds, on a trace recorded on a
v5e (PR 26: ``higgs-levelwise-int8.train`` cut to 65,536 rows with
``--rows``, one traced slice of 8 iterations)."""
import gzip
import os
import shutil

import pytest

from harness import trace_reduce
from harness.trace_reduce import Op, TraceSummary, _leaves

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "levelwise_int8_65536rows.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return TraceSummary(trace_reduce.load(str(path)))


def test_window_is_the_harness_span_and_busy_is_a_union(summary):
    assert summary.window_s == pytest.approx(0.046017336, rel=1e-9)
    assert summary.busy_s == pytest.approx(0.040031191, rel=1e-9)
    assert 0 < summary.busy_s < summary.window_s
    (ops,) = summary.planes.values()
    # leaves only: their summed seconds cannot pass the union by more than
    # rounding, so no loop body is counted with its loop
    assert sum(o.seconds for o in ops) == pytest.approx(summary.busy_s,
                                                        rel=1e-6)


@pytest.mark.parametrize("scope, seconds", [
    ("histogram", 0.025815176), ("split_find", 0.00156862),
    ("partition", 0.000703442)])
def test_scope_patterns_find_the_programs_phases(summary, scope, seconds):
    got = summary.scoped_seconds(r"(^|/)%s(/|$)" % scope)
    assert got == pytest.approx(seconds, rel=1e-6)


def test_a_pattern_that_matches_nothing_reads_nothing(summary):
    assert summary.scoped_seconds(r"(^|/)collective(/|$)") is None


def test_breakdown_names_are_the_traces_own(summary):
    top = summary.top_ops(10)
    assert len(top) == 10
    assert top[0][0].startswith("_hist_pallas_raw_fn")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = summary.idle_gaps(10)
    assert gaps and all(name.startswith("bench/") for name, _s in gaps)
    assert sum(s for _n, s in gaps) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)


def test_leaves_drop_the_loop_that_holds_them():
    ops = [Op("while", "", 0, 100), Op("a", "", 0, 40), Op("b", "", 50, 90),
           Op("after", "", 120, 130)]
    assert [o.name for o in _leaves(ops)] == ["a", "b", "after"]
