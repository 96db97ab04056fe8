"""One program, traced or not (ISSUE 27).

(a) The fused chunk program and each of the three growers lower to the
    same text, debug info included, with telemetry on and off: a span is a
    host object and puts nothing into a traced program, and ``health=auto``
    follows the record sink, not the enabled flag.
(b) Every device phase a policy uses (``telemetry.DEVICE_PHASES``) is in
    the lowered text's metadata, and what the chunk program carries under
    no phase is loop plumbing, few enough to list here.

The lowered MLIR names an operation by its scope path *inside* its own
function; XLA prefixes the call site's path when it inlines the call, so
the census below walks the calls the same way.
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.io.dataset import Dataset
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.models import grower_unified
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.telemetry import DEVICE_PHASES

N, F, B, LEAVES = 1000, 6, 31, 7

# policy -> (booster parameters, the phases its chunk program must carry)
POLICIES = {
    "depthwise": (
        {"grow_policy": "depthwise", "hist_dtype": "int8"},
        {"gradient", "histogram", "split_find", "row_route",
         "score_update", "tree_pack", "eval"}),
    "leafwise": (
        {"grow_policy": "leafwise", "leafwise_compact": "false"},
        {"gradient", "histogram", "split_find", "row_route",
         "score_update", "tree_pack", "eval"}),
    "leafcompact": (
        {"grow_policy": "leafwise", "leafwise_compact": "true"},
        {"gradient", "histogram", "split_find", "row_route", "partition",
         "score_update", "tree_pack", "eval"}),
}

GROWERS = {
    "depthwise": grower_unified.grow_tree_depthwise_jit,
    "leafwise": grower_unified.grow_tree,
    "leafcompact": grower_unified.grow_tree_leafcompact,
}

# What may stand under no phase in a chunk program, as the path ends:
# the scan's own plumbing (its counter, the stacking of each iteration's
# outputs, carried constants), the slices that hand one class's arrays to
# the grower, the split loop's counter and the predicate of its cond.
PLUMBING = re.compile(
    r"(^jit\(chunk_fn\)/(while/(body|cond)/)?"
    r"(broadcast_in_dim|dynamic_update_slice|lt|add)$)"
    r"|(/closed_call/(slice|squeeze|broadcast_in_dim)$)"
    r"|(/level0/broadcast_in_dim$)"
    r"|(/while/(cond/lt|body/add)$)"
    r"|(/(leafwise|leafcompact)_split/convert_element_type$)")
PLUMBING_MOST = 64

PHASE = re.compile(r"(^|/)(%s)(/|$)" % "|".join(DEVICE_PHASES))
NOT_WORK = {"stablehlo.return", "func.return", "stablehlo.constant"}


class _Captured(Exception):
    pass


@pytest.fixture(autouse=True)
def _clean():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _dataset():
    rng = np.random.RandomState(5)
    x = rng.randn(N, F).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(N) > 0).astype(np.float32)
    return Dataset.from_arrays(x, y, max_bin=B)


def _chunk_lowered(policy: str, on: bool, monkeypatch) -> str:
    """The chunk program as GBDT.train_chunk itself builds it, lowered
    from a clean trace (no cached jaxpr of the other telemetry state)."""
    jax.clear_caches()
    gbdt_mod._CHUNK_PROGRAMS.clear()
    if on:
        telemetry.enable(fence=False)
    config = lgb.OverallConfig()
    config.set(dict({"objective": "binary", "num_leaves": str(LEAVES),
                     "max_bin": str(B), "min_data_in_leaf": "5"},
                    **POLICIES[policy][0]), require_data=False)
    booster = lgb.GBDT()
    booster.init(config.boosting_config, _dataset(),
                 create_objective(config.objective_type,
                                  config.objective_config))
    seen = {}
    real_get = gbdt_mod._get_chunk_program

    def capturing_get(*a, **kw):
        prog = real_get(*a, **kw)

        def call(*args):
            seen["prog"], seen["args"] = prog, args
            raise _Captured
        return call

    with monkeypatch.context() as m:
        m.setattr(gbdt_mod, "_get_chunk_program", capturing_get)
        with pytest.raises(_Captured):
            booster.train_chunk(2, is_eval=False)
    text = seen["prog"].lower(*seen["args"]).as_text(debug_info=True)
    telemetry.disable()
    telemetry.reset()
    return text


def _grower_lowered(policy: str, on: bool, ask_map: bool = False) -> str:
    """``ask_map``: the grower has been captured and run, and the phases of
    its unscoped operations asked for (``costmodel.op_phases``), before
    it is lowered."""
    jax.clear_caches()
    if on:
        telemetry.enable(fence=False)
    rng = np.random.RandomState(3)
    args = (jnp.asarray(rng.randint(0, B, (F, N)), jnp.uint8),
            jnp.asarray(rng.randn(N), jnp.float32),
            jnp.ones((N,), jnp.float32),
            jnp.ones((N,), jnp.bool_), jnp.ones((F,), jnp.bool_),
            jnp.full((F,), B, jnp.int32))
    kw = dict(num_leaves=LEAVES, num_bins_max=B, min_data_in_leaf=5,
              min_sum_hessian_in_leaf=1.0, max_depth=-1, packing=None)
    if ask_map:
        from lightgbm_tpu import costmodel
        jax.block_until_ready(GROWERS[policy](*args, **kw))
        labels = costmodel.op_phases()[GROWERS[policy].name]
        assert labels and set(labels.values()) <= set(DEVICE_PHASES) | {
            costmodel.XLA}
        # a trace of its own for the lowering below, from the same line of
        # this file as the other side's (locations are part of the text)
        jax.clear_caches()
    text = GROWERS[policy].lower(*args, **kw).as_text(debug_info=True)
    telemetry.disable()
    telemetry.reset()
    return text


def _functions(text: str) -> dict:
    """{function: [(operation, scope path, callee or None)]}."""
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    funcs, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"\s*func\.func (?:public |private )?@([\w.$-]+)\(",
                     line)
        if m:
            cur = funcs.setdefault(m.group(1), [])
            continue
        m = re.match(r'\s+(?:%[\w:#, ]+ = )?"?([a-z_]+\.[\w.]+|call)"?'
                     r"[ (].*loc\((#loc\d+)\)\s*$", line)
        if cur is None or not m:
            continue
        op, loc = m.groups()
        callee = (re.search(r"call @([\w.$-]+)", line).group(1)
                  if op in ("call", "func.call") else None)
        cur.append((op, names.get(loc, ""), callee))
    return funcs


def _operations(text: str) -> list:
    """[(operation, full scope path)] with every call walked into: the
    callee's operations take the call's path as prefix."""
    funcs, out = _functions(text), []

    def walk(name, prefix):
        for op, path, callee in funcs.get(name, ()):
            full = prefix + "/" + path if prefix else path
            if callee is not None:
                walk(callee, full)
            elif op not in NOT_WORK:
                out.append((op, full))

    walk("main", "")
    return out


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_chunk_program_lowers_alike_with_telemetry_on_and_off(
        policy, monkeypatch):
    off, on = [_chunk_lowered(policy, flag, monkeypatch)
               for flag in (False, True)]
    assert "stablehlo" in off
    assert on == off


@pytest.mark.parametrize("policy", sorted(GROWERS))
def test_grower_lowers_alike_with_telemetry_on_and_off(policy):
    off, on = [_grower_lowered(policy, flag) for flag in (False, True)]
    assert "stablehlo" in off
    assert on == off


def test_asking_for_the_map_of_unscoped_operations_changes_no_program():
    """``costmodel.op_phases`` reads the compiled text of a program that
    ran; what the grower lowers to afterwards is what it lowers to with
    telemetry off."""
    off, on = [_grower_lowered("depthwise", flag, ask_map=flag)
               for flag in (False, True)]     # one line: one location
    assert on == off


@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_device_phases_cover_the_chunk_program(policy, monkeypatch):
    ops = _operations(_chunk_lowered(policy, False, monkeypatch))
    assert len(ops) > 500
    found = collections.Counter()
    bare = []
    for op, path in ops:
        m = PHASE.search(path)
        if m:
            found[m.group(2)] += 1
        else:
            bare.append((op, path))
    assert set(found) == POLICIES[policy][1]
    stray = [(op, path) for op, path in bare if not PLUMBING.search(path)]
    assert not stray, stray[:10]
    assert len(bare) <= PLUMBING_MOST, len(bare)


def test_named_scopes_in_the_package_are_a_closed_set():
    """Every literal ``jax.named_scope`` / ``phase_scope`` name in the
    package is a device phase, an outer grouping scope, an objective's
    ``gradient_<objective>`` (nested inside ``gradient``), or the int8
    histograms' ``range_sum`` (nested inside ``histogram``, which every
    metric of that phase goes on reading; ``hist_range_sum_ms_per_iter``
    reads it alone)."""
    import glob
    import os
    root = os.path.dirname(os.path.abspath(lgb.__file__))
    outer = re.compile(r"^(level(%d|\d+)|leafwise_split|leafcompact_split"
                       r"|gradient_[a-z]+|range_sum)$")
    seen = set()
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as fh:
            source = fh.read()
        if path.endswith("telemetry.py"):
            # its docstrings quote the call; its one real use is phase_scope
            continue
        seen.update(re.findall(
            r'(?:named_scope|phase_scope)\(\s*"([^"]+)"', source))
    assert seen, "the census found no scope at all"
    odd = sorted(n for n in seen
                 if n not in DEVICE_PHASES and not outer.match(n))
    assert not odd, odd
    with pytest.raises(ValueError):
        telemetry.phase_scope("not_a_phase")


def test_route_kernel_is_under_row_route_traced_or_not(monkeypatch):
    """The level-wise grower with its routing kernel (on a TPU the route
    of every level; here the gate is steered and the kernel lowered by the
    interpreter): the same text with telemetry on and off, the kernel's
    operations under ``row_route`` and nowhere else."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(grower_unified, "route_pallas_ok", lambda *a: True)
    with pltpu.force_tpu_interpret_mode():
        off, on = [_grower_lowered("depthwise", flag)
                   for flag in (False, True)]
    assert on == off
    # the interpreter moves the kernel's blocks through io_callbacks, and
    # nothing else in this program has one
    kernel = [path for _op, path in _operations(off)
              if path.endswith("/io_callback")]
    assert len(kernel) > 3 * grower_unified.num_levels(LEAVES)
    assert all("/row_route/" in path for path in kernel), kernel[:5]


def test_kernel_locations_do_not_depend_on_telemetry(monkeypatch):
    """A Pallas kernel is serialised with its operations' source
    locations, the innermost user frames JAX keeps, and that is hashed
    into the compile-cache key.  Armed, the cost registry traces the chunk
    program one frame deeper than disarmed, and the routing kernel sits
    few enough frames under the chunk program for that frame to be among
    the ones kept: the traced run then compiled a program of its own.  So
    the frames JAX keeps at the kernel's call are the same with telemetry
    on and off."""
    from jax._src import source_info_util
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops import route_pallas
    kept = int(jax.config.jax_traceback_in_locations_limit)
    assert kept > 0
    seen = []
    real = route_pallas.route_pallas_raw

    def spy(*args, **kwargs):
        frames = list(source_info_util.user_frames(
            source_info_util.current().traceback))
        seen.append([(f.file_name, f.function_name, f.start_line)
                     for f in frames[1:kept + 1]])      # [0] is this spy
        return real(*args, **kwargs)

    monkeypatch.setattr(route_pallas, "route_pallas_raw", spy)
    monkeypatch.setattr(grower_unified, "route_pallas_ok", lambda *a: True)
    stacks = {}
    for on in (False, True):
        del seen[:]
        jax.clear_caches()
        gbdt_mod._CHUNK_PROGRAMS.clear()
        if on:
            telemetry.enable(fence=False)
        config = lgb.OverallConfig()
        config.set(dict({"objective": "binary", "num_leaves": str(LEAVES),
                         "max_bin": str(B), "min_data_in_leaf": "5"},
                        **POLICIES["depthwise"][0]), require_data=False)
        booster = lgb.GBDT()
        booster.init(config.boosting_config, _dataset(),
                     create_objective(config.objective_type,
                                      config.objective_config))
        with pltpu.force_tpu_interpret_mode():
            booster.train_chunk(2, is_eval=False)
            jax.block_until_ready(booster.score)
        telemetry.disable()
        telemetry.reset()
        assert len(seen) == grower_unified.num_levels(LEAVES)
        stacks[on] = seen[0]
    # the chunk program's own frame is among the kept ones: the case tests
    # what it says
    assert any(name.endswith(".chunk_fn") for _f, name, _l in stacks[False])
    assert stacks[True] == stacks[False]
