"""The split of ``unscoped_ms_per_iter`` by the program's own map
(``harness/hidden.py`` over ``lightgbm_tpu.costmodel.op_phases``), on the
recording ``test_phase_metrics.py`` reads, with a map made by hand: the
recorded program is PR 27's and has none of its own."""
import re

import pytest

from harness import hidden, readers
from test_phase_metrics import (  # noqa: F401 (fixtures)
    ITERATIONS, UNSCOPED, registry, state_of, summary)

NEW = ("histogram_hidden_ms_per_iter", "split_find_hidden_ms_per_iter",
       "partition_hidden_ms_per_iter", "xla_inserted_ms_per_iter",
       "unnamed_ms_per_iter", "row_route_hidden_ms_per_iter")


def unscoped_names(summary):
    scoped = hidden.unscoped_pattern()
    return sorted({op.name for ops in summary.planes.values() for op in ops
                   if not scoped.search(op.scope)
                   and not scoped.search(op.name)})


def hand_map(summary):
    """What a program might say of the recording's unscoped operations,
    by the look of their names; ``fusion.899`` in two programs that
    disagree, ``reverse.23`` in none."""
    chunk = {}
    for name in unscoped_names(summary):
        if name.startswith("reduce-window"):
            chunk[name] = ("split_find", "reduce-window", "f32[8,28,3]", 2688)
        elif name.startswith(("select_convert", "clamp_convert")):
            chunk[name] = ("histogram", "fusion", "s8[1,65536]", 65536)
        elif name.startswith("copy"):
            chunk[name] = ("xla", "copy", "f32[128,28,254]", 3641344)
        elif re.match(r"fusion\.8\d\d$", name):
            chunk[name] = ("row_route", "fusion", "pred[65536]", 65536)
        elif re.match(r"fusion\.9\d\d$", name):
            chunk[name] = ("tree_pack", "fusion", "s32[8,1,254]", 8128)
    assert "fusion.899" in chunk and "reverse.23" not in chunk
    return {"chunk/serial": chunk,
            "grow/depthwise": {"fusion.899": ("histogram", "fusion", "", 0)}}


@pytest.fixture
def mapped(summary, monkeypatch):
    from lightgbm_tpu import costmodel
    per_program = hand_map(summary)
    monkeypatch.setattr(costmodel, "op_phases",
                        lambda describe=False: per_program, raising=False)
    return per_program


def test_the_split_sums_to_the_unscoped_row(summary, mapped):
    state = state_of(summary)
    got = {name: readers.read(name, state) for name in NEW}
    assert all(value is not None for value in got.values())
    assert sum(got.values()) == pytest.approx(UNSCOPED, rel=1e-6)
    assert sum(got.values()) == pytest.approx(
        readers.read("unscoped_ms_per_iter", state), rel=1e-9)
    # the cumulative sums are most of this recording's row
    assert got["split_find_hidden_ms_per_iter"] > 0.8 * UNSCOPED
    assert got["histogram_hidden_ms_per_iter"] > 0
    assert got["xla_inserted_ms_per_iter"] > 0
    assert got["row_route_hidden_ms_per_iter"] > 0
    assert got["partition_hidden_ms_per_iter"] == 0


def test_unnamed_is_what_has_no_entry_no_metric_or_two_labels(summary,
                                                              mapped):
    found = hidden.split(state_of(summary))
    # a phase without a metric of its own, and what no program lists
    assert found["tree_pack"]["ms"] > 0 and found[None]["ms"] > 0
    assert "reverse.23" in found[None]["ops"]
    # a name two programs label differently is left out of both
    assert "fusion.899" in found[None]["ops"]
    assert "fusion.899" not in found["row_route"]["ops"]
    assert "histogram" in found and \
        "fusion.899" not in found["histogram"]["ops"]
    assert readers.read("unnamed_ms_per_iter", state_of(summary)) == \
        pytest.approx(found["tree_pack"]["ms"] + found[None]["ms"])
    # the compiler's own reads as what it is, not as copy.1730
    assert any(re.match(r"copy\.\d+ copy f32\[128,28,254\]$", what)
               for what in found["xla"]["ops"])


def test_flatten_keeps_a_name_two_programs_agree_on():
    flat = hidden.flatten({"a": {"x": "histogram", "y": "xla", "z": "xla"},
                           "b": {"x": "histogram", "y": "split_find"}})
    assert flat == {"x": ("histogram", "", "", 0), "z": ("xla", "", "", 0)}


@pytest.mark.parametrize("metric", NEW)
def test_the_new_readers_read_nothing_with_telemetry_off(registry, summary,
                                                         metric):
    """Nothing captured, no map: with a trace and without one."""
    assert not registry.enabled()
    assert readers.read(metric, state_of(summary)) is None
    assert readers.read(metric, state_of()) is None


@pytest.mark.parametrize("metric", NEW)
def test_the_new_readers_read_nothing_on_a_program_without_the_map(
        summary, monkeypatch, metric):
    """The parent of the PR that added ``op_phases``."""
    from lightgbm_tpu import costmodel
    monkeypatch.delattr(costmodel, "op_phases")
    assert readers.read(metric, state_of(summary)) is None


def test_the_new_metrics_are_in_the_benchmark_with_their_cells():
    import json
    import os
    with open(os.path.join(os.path.dirname(readers.METRICS), os.pardir,
                           "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["moves"] == "train_iters_per_s"
        assert listed[name]["source"] == "device_trace"
        assert listed[name]["workloads"] == (
            ["epsilon-leafwise-f32.train"]
            if name == "partition_hidden_ms_per_iter" else cells)
    assert set(hidden.GROUPS) | {hidden.UNNAMED} == set(NEW)
