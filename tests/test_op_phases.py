"""``costmodel.op_phases``: the phase of every operation a device trace
shows under no scope, from the compiled program's own text (ISSUE 38).
The resolver on a small hand-written module, one case per step of its
rule; and the map, its summary in ``compile_block`` and its laziness on a
program captured here on the CPU."""
import json

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu import costmodel, telemetry


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# what a scheduled module looks like in ``compiled.as_text()``: a quantise
# fusion with a tuple root in front of a histogram, a layout copy the
# compiler put between the histogram and a cumulative sum that lost its
# scope, the split search that reads it, and a loop whose body copies
HLO = r"""
HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[1024]{0}, f32[1024]{0})->f32[8,256]{1,0}}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.1 = f32[]{:T(128)} add(%a, %b), metadata={op_name="reduce_window_sum"}
}

%fused_quant (p0: f32[1024], p1: f32[1024]) -> (s8[1024], f32[1024]) {
  %p0 = f32[1024]{0:T(1024)} parameter(0)
  %p1 = f32[1024]{0:T(1024)} parameter(1)
  %mul.1 = f32[1024]{0:T(1024)} multiply(%p0, %p1), metadata={op_name="jit(f)/while/body/gradient/gradient_binary/mul" stack_frame_id=9}
  %round.1 = f32[1024]{0:T(1024)} round-nearest-even(%mul.1)
  %convert.1 = s8[1024]{0:T(1024)(4,1)} convert(%round.1), metadata={op_name="jit(f)/level0/histogram/convert_element_type" stack_frame_id=12}
  ROOT %tuple.1 = (s8[1024]{0:T(1024)(4,1)}, f32[1024]{0:T(1024)}) tuple(%convert.1, %mul.1)
}

%fused_quant_agree (p0.5: f32[1024]) -> (s8[1024], s8[1024]) {
  %p0.5 = f32[1024]{0:T(1024)} parameter(0)
  %clamp.5 = f32[1024]{0:T(1024)} negate(%p0.5), metadata={op_name="jit(f)/level1/histogram/jit(clip)/max"}
  %convert.5 = s8[1024]{0:T(1024)(4,1)} convert(%clamp.5)
  %convert.6 = s8[1024]{0:T(1024)(4,1)} convert(%clamp.5)
  ROOT %tuple.5 = (s8[1024]{0:T(1024)(4,1)}, s8[1024]{0:T(1024)(4,1)}) tuple(%convert.5, %convert.6)
}

%fused_hist (p0.2: s8[1024]) -> f32[8,256] {
  %p0.2 = s8[1024]{0:T(1024)(4,1)} parameter(0)
  %bitcast.2 = s8[8,128]{1,0:T(8,128)(4,1)} bitcast(%p0.2)
  %convert.2 = f32[8,128]{1,0:T(8,128)} convert(%bitcast.2), metadata={op_name="jit(f)/level0/histogram/convert_element_type"}
  %zero.2 = f32[]{:T(128)} constant(0)
  ROOT %pad.2 = f32[8,256]{1,0:T(8,128)} pad(%convert.2, %zero.2), padding=0_0x0_128, metadata={op_name="jit(f)/level0/histogram/pad"}
}

%wrapped_rw (p0.3: f32[8,256]) -> f32[8,256] {
  %p0.3 = f32[8,256]{0,1:T(8,128)} parameter(0)
  %zero.3 = f32[]{:T(128)} constant(0)
  ROOT %reduce-window.3 = f32[8,256]{0,1:T(8,128)} reduce-window(%p0.3, %zero.3), window={size=1x256 pad=0_0x255_0}, to_apply=%region_add
}

%fused_lost (p0.6: f32[8,256]) -> f32[8] {
  %p0.6 = f32[8,256]{1,0:T(8,128)} parameter(0)
  %zero.6 = f32[]{:T(128)} constant(0)
  ROOT %reduce.6 = f32[8]{0:T(128)} reduce(%p0.6, %zero.6), dimensions={1}, to_apply=%region_add, metadata={op_name="jit(f)/jit(clip)/reduce_sum"}
}

%fused_scan (p0.4: f32[8,256]) -> f32[8,256] {
  %p0.4 = f32[8,256]{0,1:T(8,128)} parameter(0)
  ROOT %sub.4 = f32[8,256]{1,0:T(8,128)} subtract(%p0.4, %p0.4), metadata={op_name="jit(f)/level0/split_find/vmap(split_find)/sub"}
}

%body (s: (s32[], f32[8,256])) -> (s32[], f32[8,256]) {
  %s = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%s), index=0
  %v = f32[8,256]{1,0:T(8,128)} get-tuple-element(%s), index=1
  %copy.30 = f32[8,256]{1,0:T(8,128)} copy(%v)
  ROOT %t = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) tuple(%i, %copy.30)
}

%cond (s.1: (s32[], f32[8,256])) -> pred[] {
  %s.1 = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%s.1), index=0
  %n.1 = s32[]{:T(128)} constant(8)
  ROOT %lt.1 = pred[]{:T(512)} compare(%i.1, %n.1), direction=LT
}

ENTRY %main.9 (x: f32[1024], y: f32[1024]) -> f32[8,256] {
  %x = f32[1024]{0:T(1024)} parameter(0), metadata={op_name="x"}
  %y = f32[1024]{0:T(1024)} parameter(1), metadata={op_name="y"}
  %fusion.1 = (s8[1024]{0:T(1024)(4,1)}, f32[1024]{0:T(1024)}) fusion(%x, %y), kind=kLoop, calls=%fused_quant, backend_config={"flag_configs":[]}
  %gte.0 = s8[1024]{0:T(1024)(4,1)} get-tuple-element(%fusion.1), index=0
  %fusion.5 = (s8[1024]{0:T(1024)(4,1)}, s8[1024]{0:T(1024)(4,1)}) fusion(%y), kind=kLoop, calls=%fused_quant_agree
  %hist.1 = f32[8,256]{1,0:T(8,128)} fusion(%gte.0), kind=kLoop, calls=%fused_hist, metadata={op_name="jit(f)/level0/histogram/pad" stack_frame_id=3}
  %copy.17 = f32[8,256]{0,1:T(8,128)} copy(%hist.1)
  %wrapped_reduce-window.1 = f32[8,256]{0,1:T(8,128)} fusion(%copy.17), kind=kLoop, calls=%wrapped_rw
  %reverse.1 = f32[8,256]{0,1:T(8,128)} reverse(%wrapped_reduce-window.1), dimensions={1}
  %scan.1 = f32[8,256]{1,0:T(8,128)} fusion(%reverse.1), kind=kLoop, calls=%fused_scan, metadata={op_name="jit(f)/level0/split_find/vmap(split_find)/sub"}
  %copy-start.2 = (f32[8,256]{1,0:T(8,128)S(1)}, f32[8,256]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%scan.1)
  %copy-done.2 = f32[8,256]{1,0:T(8,128)S(1)} copy-done(%copy-start.2)
  %zero.9 = s32[]{:T(128)} constant(0)
  %init.9 = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) tuple(%zero.9, %copy-done.2)
  %while.9 = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) while(%init.9), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  %lost.1 = f32[8]{0:T(128)} fusion(%x), kind=kLoop, calls=%fused_lost
  ROOT %out.9 = f32[8,256]{1,0:T(8,128)} get-tuple-element(%while.9), index=1
}
"""


def test_an_operation_with_its_own_phase_is_not_in_the_map():
    """Step (a): the trace already has it."""
    labels = costmodel.label_unscoped_ops(HLO)
    assert "hist.1" not in labels and "scan.1" not in labels
    # nor what no device runs as an operation, nor a fusion's inside
    assert not {"x", "gte.0", "init.9", "out.9", "zero.9", "mul.1",
                "reduce-window.3", "add.1"} & set(labels)


@pytest.mark.parametrize("name, label, opcode, result, nbytes", [
    # (b) a tuple root whose outputs disagree: the largest output's phase
    ("fusion.1", "gradient", "fusion", "(s8[1024], f32[1024])", 5120),
    # (b) through a tuple root and two converts that carry nothing
    ("fusion.5", "histogram", "fusion", "(s8[1024], s8[1024])", 2048),
    # (c) the compiler's own copy between a histogram and a split search
    # takes neither's name
    ("copy.17", "xla", "copy", "f32[8,256]", 8192),
    # (c) in a loop's body, and an asynchronous copy by its destination
    ("copy.30", "xla", "copy", "f32[8,256]", 8192),
    ("copy-start.2", "xla", "copy-start",
     "(f32[8,256], f32[8,256], u32[])", 8192),
    ("copy-done.2", "xla", "copy-done", "f32[8,256]", 8192),
    # (d) the decomposed cumulative sum: its user's phase, one step on, and
    # not its producer's, the histogram behind the copy
    ("wrapped_reduce-window.1", "split_find", "fusion", "f32[8,256]", 8192),
    ("reverse.1", "split_find", "reverse", "f32[8,256]", 8192),
    # (d) the program's own, but no neighbour names a phase
    ("lost.1", "xla", "fusion", "f32[8]", 32),
    ("lt.1", "xla", "compare", "pred[]", 1),
    # (d) a loop is an event of its own where it makes no trip: the phase
    # that made what it carries, four steps back through the compiler's copy
    ("while.9", "split_find", "while", "(s32[], f32[8,256])", 8196),
])
def test_the_rule_step_by_step(name, label, opcode, result, nbytes):
    assert costmodel.label_unscoped_ops(HLO)[name] == (
        label, opcode, result, nbytes)


def test_every_unscoped_operation_of_the_module_has_a_label():
    labels = costmodel.label_unscoped_ops(HLO)
    assert set(labels) == {
        "fusion.1", "fusion.5", "copy.17", "copy.30", "copy-start.2",
        "copy-done.2", "wrapped_reduce-window.1", "reverse.1", "lost.1",
        "lt.1", "while.9"}
    assert {found[0] for found in labels.values()} <= set(
        telemetry.DEVICE_PHASES) | {costmodel.XLA}
    assert costmodel._unscoped_summary(labels) == {
        "gradient": 1, "histogram": 1, "split_find": 3, "xla": 6,
        "xla_largest": ["copy", 8192]}
    assert costmodel.label_unscoped_ops("not a module") == {}


# ---------------------------------------------------- on a captured program

def _scoped_program():
    def f(g, h):
        with telemetry.phase_scope("histogram"):
            hist = jnp.stack([g, h]).reshape(2, 8, 128) * 2.0
        with telemetry.phase_scope("split_find"):
            left = jnp.cumsum(hist, axis=2)
            gain = left * left / (left + 1.0)
            return jnp.max(gain, axis=(0, 2))
    return costmodel.instrument("test/scoped", jax.jit(f), phase="grow")


def test_the_map_of_a_captured_program_is_made_when_asked_for():
    prog = _scoped_program()
    telemetry.enable()
    g = jnp.arange(1024, dtype=jnp.float32)
    want = jax.jit(lambda a, b: prog._fn(a, b))(g, g + 1)
    got = prog(g, g + 1)
    assert jnp.array_equal(got, want)
    rec = costmodel._records[-1]
    # captured, its text neither printed nor parsed
    assert rec["name"] == "test/scoped" and "_unscoped" not in rec
    phases = costmodel.op_phases()
    assert "_unscoped" in rec and list(phases) == ["test/scoped"]
    labels = phases["test/scoped"]
    assert set(labels.values()) <= set(telemetry.DEVICE_PHASES) | {"xla"}
    described = costmodel.op_phases(describe=True)["test/scoped"]
    assert {name: found[0] for name, found in described.items()} == labels
    # parsed once: the record keeps it
    kept = rec["_unscoped"]
    block = costmodel.compile_block()["programs"][-1]
    assert rec["_unscoped"] is kept
    counts = dict(block["unscoped_ops"])
    largest = counts.pop("xla_largest", None)
    assert sum(counts.values()) == len(labels)
    assert (largest is None) == ("xla" not in counts)
    # an operator's view and the serving tests' records stay plain data
    json.dumps(telemetry.snapshot()["compile"])
    json.dumps(costmodel.phase_program_records("grow"))
    # the call that follows runs the executable that was captured
    assert jnp.array_equal(prog(g, g + 1), want)


def test_the_decomposed_cumulative_sum_reads_split_find_here_too():
    """On the CPU the cumulative sum is a ``reduce-window`` that lost its
    scope path; the split search that reads it gives it the name."""
    prog = _scoped_program()
    telemetry.enable()
    g = jnp.arange(1024, dtype=jnp.float32)
    prog(g, g)
    text = costmodel._records[-1]["_compiled"].as_text()
    described = costmodel.op_phases(describe=True)["test/scoped"]
    windows = {name: found for name, found in described.items()
               if "reduce-window" in name}
    if "reduce-window" not in text:
        pytest.skip("this compiler kept the cumulative sum whole")
    assert windows and all(found[0] == "split_find"
                           for found in windows.values()), windows


def test_no_program_no_map():
    """Telemetry off: nothing is captured, so there is nothing to print;
    a record without an executable has no entry."""
    prog = _scoped_program()
    g = jnp.arange(1024, dtype=jnp.float32)
    prog(g, g)
    assert costmodel.op_phases() == {}
    broken = costmodel.instrument("test/broken", lambda x: x * 2)
    telemetry.enable()
    assert broken(3) == 6
    assert costmodel.op_phases() == {}
    assert "unscoped_ops" not in costmodel.compile_block()["programs"][0]


def test_two_records_of_one_name_are_kept_apart():
    telemetry.enable()
    g = jnp.arange(1024, dtype=jnp.float32)
    _scoped_program()(g, g)
    costmodel.instrument("test/scoped", jax.jit(lambda a: a[::-1] + 1))(g)
    assert list(costmodel.op_phases()) == ["test/scoped", "test/scoped#2"]


def test_a_metrics_out_run_records_and_reports_the_unscoped_operations(
        tmp_path, capsys):
    """What an operator sees: the summary record of a ``metrics_out`` run
    carries ``compile.programs[].unscoped_ops`` for the tree program, and
    ``scripts/telemetry_report.py`` prints it."""
    import os
    import sys
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.dataset import Dataset
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from scripts import telemetry_report

    rng = np.random.RandomState(0)
    x = rng.randn(1010, 5)
    y = (x[:, 0] + 0.1 * rng.randn(1010) > 0).astype(np.float32)
    path = str(tmp_path / "m.jsonl")
    lgb.train({"objective": "binary", "num_leaves": 7, "num_iterations": 2,
               "min_data_in_leaf": 20, "metrics_out": path},
              Dataset.from_arrays(x, y, max_bin=16))
    telemetry.disable()
    with open(path) as fh:
        summary = [json.loads(line) for line in fh][-1]
    assert summary["summary"]
    listed = [p for p in summary["compile"]["programs"]
              if "unscoped_ops" in p]
    assert listed, summary["compile"]["programs"]
    allowed = set(telemetry.DEVICE_PHASES) | {"xla", "xla_largest"}
    assert all(set(p["unscoped_ops"]) <= allowed for p in listed)
    assert telemetry_report.report(path) == 0
    out = capsys.readouterr().out
    assert "%s  under no phase: " % listed[0]["name"] in out
