"""What the files that compile for a described v5e share: the topology
fixtures, the shapes of the supported configurations, and the helpers that
lower, capture and check a program.  ``tests/test_tpu_compile.py`` holds
the rules; this module holds no test.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

F, B, LEAVES, N = 28, 255, 255, 1 << 20
WIDE_F, WIDE_N = 2000, 400_000  # benchmarks/configs/epsilon-levelwise-int8
HBM_BYTES = 16 * 1000 ** 3      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        if "lockfile" in str(e):
            # a skip here would be a silent loss of every test of the file
            pytest.fail("another process holds the TPU library: the files "
                        "that compile for a described chip run side by "
                        "side only under ALLOW_MULTIPLE_LIBTPU_LOAD=1, as "
                        "the driver's command sets it; without it run "
                        "them in one process", pytrace=False)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch, no_persistent_cache):
    """Make the backend-keyed routing rules take their TPU branch while
    tracing (they ask jax.default_backend(), which still sees the CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def _like(one_chip, tree, rows_from=None, rows_to=None):
    """ShapeDtypeStructs for a pytree of arrays, on the described chip;
    every axis of length ``rows_from`` becomes ``rows_to``."""
    def conv(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        shape = tuple(rows_to if (rows_from and d == rows_from) else d
                      for d in a.shape)
        return _shape(one_chip, shape, a.dtype)
    return jax.tree.map(conv, tree)


def _check(compiled, custom_call: bool):
    """tpu_custom_call present where a Pallas route is expected, and the
    program fits one chip.  Returns memory_analysis()."""
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == custom_call, (
        "Pallas custom call %s in the compiled program"
        % ("missing" if custom_call else "unexpected"))
    ma = compiled.memory_analysis()
    total = ma.temp_size_in_bytes + ma.argument_size_in_bytes
    assert total < HBM_BYTES, (ma.temp_size_in_bytes,
                               ma.argument_size_in_bytes)
    return ma


def _grow_args(one_chip, n=N, f=F):
    return (_shape(one_chip, (f, n), jnp.uint8),       # bins
            _shape(one_chip, (n,), jnp.float32),       # grad
            _shape(one_chip, (n,), jnp.float32),       # hess
            _shape(one_chip, (n,), jnp.bool_),         # row_mask
            _shape(one_chip, (f,), jnp.bool_),         # feature_mask
            _shape(one_chip, (f,), jnp.int32))         # num_bins


def _cell_size(ma):
    """The size argument of the wide cell, pinned: what the compiler counts
    for the program is over the 2 GiB a new cell has to hold with the chip
    busy (it measured 3.24 GB of temporaries and 0.80 GB of arguments)."""
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes > 2 << 30, (
        ma.temp_size_in_bytes, ma.argument_size_in_bytes)


_GROW_KW = dict(num_leaves=LEAVES, num_bins_max=B, min_data_in_leaf=100,
                min_sum_hessian_in_leaf=10.0, max_depth=-1, packing=None)


def _pass_rules(dtype, lanes, stats, num_cols):
    """(fold, gw, held) as ``_hist_pallas_one`` picks them for a pass."""
    from lightgbm_tpu.ops.hist_pallas import held_onehot, hist_fold
    return (*hist_fold(stats, num_cols, 256, lanes),
            held_onehot(stats, num_cols, 256, lanes, dtype))


def _lower_kernel(one_chip, features, dtype, lanes, stats, num_cols,
                  targets=False):
    """The raw kernel, traced anew (a wrapper of its own, so that no
    cached trace answers) and lowered for the described chip; with
    ``targets``, a level pass's: each leaf column's slot a [num_cols]
    operand."""
    from lightgbm_tpu.ops.hist_pallas import _hist_pallas_raw_fn
    fold, gw, held = _pass_rules(dtype, lanes, stats, num_cols)

    def fresh(bins, packed, *tgt):
        return _hist_pallas_raw_fn(bins, packed, *tgt, B=256, chunk=2048,
                                   dtype=dtype, lanes=lanes, stats=stats,
                                   fold=fold, gw=gw, held=held)
    return jax.jit(fresh).lower(
        _shape(one_chip, (features, 2048 * 4), jnp.int8),
        _shape(one_chip, (stats + 1, 2048 * 4),
               jnp.bfloat16 if dtype == "bf16v" else jnp.int8),
        *([_shape(one_chip, (num_cols,), jnp.int32)] if targets else []))


def _lower_route_kernel(one_chip, features, rows, slots):
    """The level-wise grower's routing kernel at one level's shapes,
    traced anew and lowered for the described chip."""
    from lightgbm_tpu.ops.route_pallas import route_level_pallas
    per_slot = [_shape(one_chip, (slots,), dt)
                for dt in (jnp.int32, jnp.int32, jnp.bool_, jnp.int32)]
    return jax.jit(lambda *a: route_level_pallas(*a)).lower(
        _shape(one_chip, (features, rows), jnp.uint8),   # partition_bins
        _shape(one_chip, (rows,), jnp.int32),            # slot_id
        _shape(one_chip, (rows,), jnp.int32),            # out_leaf
        _shape(one_chip, (rows,), jnp.bool_),            # row_mask
        *per_slot)


def _lower_partition(one_chip, features, root_lanes, width, overlap):
    """One split's partition of a bucket of ``width`` lanes inside the
    two-sided pane of a table of ``features`` columns and ``root_lanes``
    padded rows, traced anew and lowered for the described chip."""
    from lightgbm_tpu.ops import compact
    pane = (2,) + compact.pane_layout(compact.pane_rows(features),
                                      root_lanes)
    lanes = width + compact.partition_grid(pane[1])[0]

    def fresh(pane, mask3, side, start, cnt, plcnt):
        return compact._partition_in_pane_fn(
            pane, mask3, side, start, cnt, plcnt, width=width,
            block=compact.BLOCK, use_pallas=True, interpret=False,
            overlap=overlap)
    scalar = _shape(one_chip, (), jnp.int32)
    return jax.jit(fresh, donate_argnums=0).lower(
        _shape(one_chip, pane, jnp.int8), _shape(one_chip, (lanes,), jnp.int8),
        scalar, scalar, scalar, scalar)


def _range_passes(text, features):
    """Lines of a compiled leaf-wise tree program in which XLA passes over
    a split's range or the pane: a ``copy``, ``select``, ``dynamic-slice``
    or ``dynamic-update-slice`` of a split's ``partition`` scope whose
    result has the pane's rows and a lane block or more (a split's mask
    is one row), and a ``copy`` of both sides under any scope or none.
    The histogram branch's own slice of the child's range is under
    ``histogram``, and the root's nine value rows are written into the
    pane once a tree, outside the split loop: neither is one of them."""
    import re
    from lightgbm_tpu.ops import compact
    R = compact.pane_rows(features)
    heights = (R, compact.pane_layout(R, compact.BLOCK)[0])
    found = []
    for line in text.splitlines():
        op = re.search(r"= s8\[([\d,]+)\][^ ]* (copy|select|dynamic-slice|"
                       r"dynamic-update-slice)\(", line)
        if not op:
            continue
        dims = [int(d) for d in op.group(1).split(",")]
        if (len(dims) < 2 or dims[-2] not in heights
                or dims[-1] < compact.TALL_BLOCK):
            continue
        both_sides = len(dims) == 3 and dims[0] == 2
        if (both_sides and op.group(2) == "copy") or re.search(
                r'op_name="[^"]*leafcompact_split/[^"]*/partition[/"]', line):
            found.append(line.strip()[:200])
    return found


def _unlabelled(text, labels):
    """Operations of a compiled program that ``costmodel.op_phases`` owes a
    label and ``labels`` (``costmodel.label_unscoped_ops(text)``) does not
    have: counted here on their own, line by line, from the entry
    computation and the computations its loops and conditionals name.
    An operation is owed one if the device runs it by itself and its own
    ``op_name`` holds no device phase."""
    import re
    from lightgbm_tpu.telemetry import DEVICE_PHASES
    phase = re.compile(r'op_name="([^"]*/)?(%s)[/"]' % "|".join(DEVICE_PHASES))
    no_operation = {"parameter", "constant", "tuple", "get-tuple-element",
                    "bitcast", "after-all"}
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    run, todo, missing = set(), ["ENTRY"], []
    while todo:
        name = todo.pop()
        if name in run:
            continue
        run.add(name)
        for line in bodies[name]:
            op = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = (?:\(.*?\)|\S+) "
                          r"([\w\-]+)\(", line)
            if not op:
                continue
            if op.group(2) in ("while", "conditional", "call"):
                # a loop of no trips is an event of its own in a trace
                todo += re.findall(
                    r"(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=%([\w.\-]+)", line)
                for listed in re.findall(r"branch_computations=\{([^}]*)\}",
                                         line):
                    todo += [c.strip(" %") for c in listed.split(",")]
            if (op.group(2) not in no_operation and not phase.search(line)
                    and op.group(1) not in labels):
                missing.append(line.strip()[:160])
    assert len(run) > 1, "no loop body was walked"
    return missing


def _cell_config(name):
    """A benchmark configuration's file (``benchmarks/configs``)."""
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            name + ".json")) as fh:
        return json.load(fh)


def _row_relayouts(text, rows):
    """Lines of a compiled program in which the compiler moves a 1-D int8
    row of ``rows`` into an ``s8[1,1,rows]``: the one-trip ``while`` that
    carries it and the ``broadcast`` that fills its target.  Where
    ``rows`` is no whole number of tiles, a row made apart and stacked
    into the histogram kernel's ``[4, N]`` operand cost one such loop
    (PERF.md section 6, PRs 38 and 39)."""
    import re
    row = "s8[1,1,%d]" % rows
    return [line.strip()[:200] for line in text.splitlines()
            if row in line and re.search(r" (while|broadcast)\(", line)]


def _operand_producers(text, rows, stats=3):
    """The fusions that make the int8 histogram kernel's
    ``[stats + 1, rows]`` operand, as ``(name, line)``: ``[4, rows]`` with
    the count row, ``[3, rows]`` for the level-wise passes without it."""
    import re
    return [(found.group(1), found.group(0)) for found in re.finditer(
        r"%%([\w.\-]+) = s8\[%d,%d\]\S* fusion\([^\n]*"
        % (stats + 1, rows), text)]


def _root_hist_layouts(text, features, bins=254, stats=2):
    """The layouts a compiled level-wise program gives its root's float32
    histogram, ``f32[1, features, bins, stats]``, memory space aside.
    The split search's sums over a leaf's bins round by its layout: the
    root's, laid out otherwise than the three-statistic program lays out
    its own, moved the last place of Airline's gains (PERF.md section 6,
    PR 44)."""
    import re
    return {re.sub(r"S\(\d+\)", "", found)
            for found in re.findall(r"f32\[1,%d,%d,%d\](\{[^}]*\})"
                                    % (features, bins, stats), text)}


class _TracedCounters(dict):
    """The telemetry counters a ``with`` block's traces added."""

    def __enter__(self):
        from lightgbm_tpu import telemetry
        telemetry.enable()
        self._before = dict(telemetry.counters())
        return self

    def __exit__(self, *exc):
        from lightgbm_tpu import telemetry
        self.update({name: count - self._before.get(name, 0)
                     for name, count in telemetry.counters().items()})
        telemetry.disable()


def _mosaic_kernels(lowered_text):
    """Every Pallas kernel of a lowered program as Mosaic MLIR text
    without locations.  The lowered text carries each kernel serialized
    with the file and line of every frame of its call stack, so an edit
    anywhere above a kernel in its file changes that text and not the
    kernel; this is what stays the same when the kernel does."""
    import base64
    import re
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    kernels = []
    for found in re.finditer(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                             lowered_text):
        context = ir.Context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(found.group(1)))
            kernels.append(module.operation.get_asm(
                enable_debug_info=False))
    return kernels


class _Captured(Exception):
    pass


def _tiny_binary_dataset(n, f=F):
    from lightgbm_tpu.io.dataset import Dataset
    rng = np.random.RandomState(5)
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return Dataset.from_arrays(x, y, max_bin=B)


def _captured_chunk_program(monkeypatch, params, dataset, is_eval):
    """(program, arguments) of the chunk of 8 iterations as
    GBDT.train_chunk itself builds and calls it: the call is intercepted
    at the program boundary."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import gbdt as gbdt_mod
    from lightgbm_tpu.metrics import create_metric
    from lightgbm_tpu.objectives import create_objective
    config = lgb.OverallConfig()
    config.set(params, require_data=False)
    booster = lgb.GBDT()
    booster.init(config.boosting_config, dataset,
                 create_objective(config.objective_type,
                                  config.objective_config),
                 [create_metric(t, config.metric_config)
                  for t in config.metric_types] if is_eval else [])
    assert booster.chunkable_for(is_eval)
    seen = {}
    real_get = gbdt_mod._get_chunk_program

    def capturing_get(*a, **kw):
        prog = real_get(*a, **kw)

        def call(*args):
            seen["prog"], seen["args"] = prog, args
            raise _Captured
        return call

    monkeypatch.setattr(gbdt_mod, "_get_chunk_program", capturing_get)
    with pytest.raises(_Captured):
        booster.train_chunk(8, is_eval=is_eval)
    return seen["prog"], seen["args"]
