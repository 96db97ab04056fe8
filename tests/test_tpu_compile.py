"""Compile the TPU routes for a described v5e, without a chip.

The suite runs on the CPU, where every backend-keyed rule takes its CPU
branch — so the code a TPU user runs (Pallas histogram and partition
kernels, the compacted grower, the fused chunk program, the serving
program with donation, the ingest update program) would otherwise have
no test.  The TPU compiler is installed here and compiles for a topology
that is described, not attached: what it refuses (unaligned slices, VMEM
overflow, HBM overflow) costs no chip time.  A compile that passes is
not a chip run — ``chip_smoke.py`` is.

Shapes are the supported configurations at a real size: F=28, 255 bins,
255 leaves, N=2**20; and the wide table of the benchmark's second
configuration, F=2,000 at N=400,000, whose histogram passes run the
kernel's feature-block grid.

Rules this file keeps (on-chip-measurement guide §2): the topology is
described inside a module-scoped fixture that skips if it cannot be;
nothing touches it at import, in ``skipif`` or in ``parametrize``
arguments; every compile runs in the test's own process with the
persistent cache off; backend-keyed routing is steered by monkeypatch,
not by a new option.  Keep all such tests in THIS file: one process holds
the TPU library at a time.
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


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("dtype,lanes,stats,num_cols", [
    ("int8", 128, 3, 32), ("int8", 192, 3, 64),
    ("bf16v", 128, 3, 1), ("bf16v", 192, 5, 38),
    # the bin fold's shapes at the cell's levels (hist_fold: fold 8, 8,
    # 4, 4, 2 for 1, 2, 4, 8, 16 leaf columns), value blocks of 24 to 96
    # rows against one-hots of 32 to 128
    ("int8", 128, 3, 1), ("int8", 128, 3, 2), ("int8", 128, 3, 4),
    ("int8", 128, 3, 8), ("int8", 128, 3, 16), ("bf16", 128, 3, 1),
    # the 64-leaf pass in the bf16 level mode: turned round like "int8"
    ("bf16", 192, 3, 64),
])
def test_hist_kernel_compiles(one_chip, as_tpu, dtype, lanes, stats,
                              num_cols):
    from lightgbm_tpu.ops.hist_pallas import _hist_pallas_raw_fn
    packed_dtype = jnp.bfloat16 if dtype == "bf16v" else jnp.int8
    fold, gw, held = _pass_rules(dtype, lanes, stats, num_cols)
    assert (fold > 1) == (dtype != "bf16v" and num_cols <= 16)
    # the integer modes' unfolded passes hold the one-hot and stream the
    # live value rows, 96 of 128 lanes and all 192; nothing else does
    assert held == (0 if dtype == "bf16v" or fold > 1 else 3 * num_cols)
    fn = jax.jit(_hist_pallas_raw_fn,
                 static_argnames=("B", "chunk", "dtype", "lanes", "stats",
                                  "fold", "gw", "held"))
    compiled = fn.lower(
        _shape(one_chip, (F, N), jnp.int8),
        _shape(one_chip, (stats + 1, N), packed_dtype),
        B=256, chunk=2048, dtype=dtype, lanes=lanes, stats=stats,
        fold=fold, gw=gw, held=held).compile()
    _check(compiled, custom_call=True)


def _pass_rules(dtype, lanes, stats, num_cols):
    """(fold, gw, held) as ``_hist_pallas_one`` picks them for a pass."""
    from lightgbm_tpu.ops.hist_pallas import held_onehot, hist_fold
    return (*hist_fold(stats, num_cols, 256, lanes, dtype),
            held_onehot(stats, num_cols, 256, lanes, dtype))


def _lower_kernel(one_chip, features, dtype, lanes, stats, num_cols):
    """The raw kernel, traced anew (a wrapper of its own, so that no
    cached trace answers) and lowered for the described chip."""
    from lightgbm_tpu.ops.hist_pallas import _hist_pallas_raw_fn
    fold, gw, held = _pass_rules(dtype, lanes, stats, num_cols)

    def fresh(bins, packed):
        return _hist_pallas_raw_fn(bins, packed, B=256, chunk=2048,
                                   dtype=dtype, lanes=lanes, stats=stats,
                                   fold=fold, gw=gw, held=held)
    return jax.jit(fresh).lower(
        _shape(one_chip, (features, 2048 * 4), jnp.int8),
        _shape(one_chip, (stats + 1, 2048 * 4),
               jnp.bfloat16 if dtype == "bf16v" else jnp.int8))


@pytest.mark.parametrize("dtype,lanes,stats,num_cols,grid", [
    # the 64-leaf level, 192 lanes, the one-hot held: the accumulator is
    # [192, 256] cells a feature, 32 features a block
    ("int8", 192, 3, 64, (32, 63)), ("bf16", 192, 3, 64, (32, 63)),
    # 128 lanes: unfolded, the one-hot held and 96 live rows streamed
    # ([96, 256] cells a feature: 72 would fit, 48 are taken), and folded
    ("int8", 128, 3, 32, (48, 42)), ("int8", 128, 3, 1, (48, 42)),
    # float gradients, five statistics a column, the one-hot streamed:
    # [256, 192 -> 256] cells a feature, 24 a block (the compiler refused
    # 32 of those: 16.12 MiB of windows)
    ("bf16v", 192, 5, 38, (24, 84)),
])
def test_hist_kernel_compiles_on_the_feature_block_grid(
        one_chip, as_tpu, dtype, lanes, stats, num_cols, grid):
    from lightgbm_tpu.ops.hist_pallas import feature_grid
    assert feature_grid(WIDE_F, 256, lanes, 2048,
                        _pass_rules(dtype, lanes, stats, num_cols)[2]) == grid
    compiled = _lower_kernel(one_chip, WIDE_F, dtype, lanes, stats,
                             num_cols).compile()
    _check(compiled, custom_call=True)


def test_narrow_kernels_lower_as_before_the_feature_block_repair(
        one_chip, as_tpu, monkeypatch):
    """What the rules of the grid and of the orientation leave alone.
    The VMEM account of the rotating block is not on the path of a table
    that fits one block: with the account put back to the rule it
    replaced (B * lanes * 4 bytes a feature in 6 MiB), every F=28 kernel
    of this file lowers to the same text.  And the held one-hot
    (``held_onehot``) is the integer modes' unfolded passes and no other:
    with the rule switched off every folded kernel and every "bf16v"
    kernel lowers to the same text, at F=28 and on the wide table, and
    the 32- and 64-column int8 passes do not."""
    from lightgbm_tpu.ops import hist_pallas
    shapes = [("int8", 128, 3, 32), ("int8", 192, 3, 64),
              ("bf16v", 128, 3, 1), ("bf16v", 192, 5, 38),
              ("int8", 128, 3, 1), ("int8", 128, 3, 2), ("int8", 128, 3, 4),
              ("int8", 128, 3, 8), ("int8", 128, 3, 16), ("bf16", 128, 3, 1)]
    turned = (0, 1)

    def before(b, lanes, _chunk, _held=0):
        fb = (6 << 20) // (b * lanes * 4)
        return max(8, fb - fb % 8)

    def texts(features):
        return [_lower_kernel(one_chip, features, *s).as_text()
                for s in shapes[:4 if features > F else None]]
    # all rounds lower from the same lines: the kernel is serialized with
    # the locations of its call stack, this test's frames among them
    rounds = []
    for account, rule in (
            (hist_pallas.rotating_feature_block, hist_pallas.held_onehot),
            (before, hist_pallas.held_onehot),
            (hist_pallas.rotating_feature_block, lambda *a: 0)):
        monkeypatch.setattr(hist_pallas, "rotating_feature_block", account)
        monkeypatch.setattr(hist_pallas, "held_onehot", rule)
        rounds.append((texts(F), texts(WIDE_F)))
    (narrow, wide), (narrow_was, wide_was), (narrow_off, wide_off) = rounds
    assert narrow == narrow_was
    # the old rule sized a block at 48 features of 128 lanes and 32 of
    # 192: what the account gives the held [96, 256] and [192, 256]
    # accumulators, and not the streamed [256, 192 -> 256] of "bf16v"
    assert wide[:3] == wide_was[:3] and wide[3] != wide_was[3]
    for got, off in ((narrow, narrow_off), (wide, wide_off)):
        assert [a == b for a, b in zip(got, off)] == [
            i not in turned for i in range(len(got))]


@pytest.mark.parametrize("overlap", [True, False])
def test_partition_kernel_compiles(one_chip, as_tpu, overlap):
    from lightgbm_tpu.ops import compact
    R = compact.pane_rows(F)
    fn = jax.jit(compact._partition_segment_fn,
                 static_argnames=("block", "use_pallas", "interpret",
                                  "overlap"))
    scalar = _shape(one_chip, (), jnp.int32)
    compiled = fn.lower(
        _shape(one_chip, (R, N), jnp.int8),            # seg pane
        _shape(one_chip, (N,), jnp.int8),              # mask3
        scalar, scalar, scalar,
        block=compact.BLOCK, use_pallas=True, interpret=False,
        overlap=overlap).compile()
    _check(compiled, custom_call=True)


# ------------------------------------------------------------- growers

def test_grow_depthwise_int8_compiles(one_chip, as_tpu):
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    compiled = grow_tree_depthwise_jit.lower(
        *_grow_args(one_chip), compute_dtype="int8", **_GROW_KW).compile()
    _check(compiled, custom_call=True)


def test_grow_depthwise_int8_compiles_on_the_wide_table(one_chip, as_tpu):
    """Every pass of a 255-leaf level-wise tree over 2,000 columns: the
    parent of the feature-block repair was refused here for VMEM (the
    64-leaf pass, ``s32[2016,255,192]``)."""
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    compiled = grow_tree_depthwise_jit.lower(
        *_grow_args(one_chip, WIDE_N, WIDE_F), compute_dtype="int8",
        **_GROW_KW).compile()
    _cell_size(_check(compiled, custom_call=True))


def test_grow_leafcompact_f32_compiles(one_chip, as_tpu):
    """The default route of task=train on a TPU: compacted grower, Pallas
    partition, Pallas float histogram."""
    from lightgbm_tpu.models.grower_unified import grow_tree_leafcompact
    from lightgbm_tpu.ops.compact import pallas_partition_ok
    assert pallas_partition_ok(F)
    compiled = grow_tree_leafcompact.lower(
        *_grow_args(one_chip), use_pallas_partition=True,
        partition_overlap=True, **_GROW_KW).compile()
    ma = _check(compiled, custom_call=True)
    # the route that holds an 11M-row table: well under 1 KB of temp/row
    assert ma.temp_size_in_bytes / N < 1024, ma.temp_size_in_bytes


def test_masked_leafwise_memory_per_row_is_pinned(one_chip, as_tpu):
    """The finding that keeps big tables off this route: the MASKED
    leaf-wise grower (leafwise_compact=false) needs ~2.7 KB of temp per
    row (2.81 GB at 1M rows) — ~31 GB at the 11M-row Higgs table, twice a
    v5e's HBM.  Only the compacted grower can hold that table; nobody
    should route it here on a chip.  If this drops below the bound the
    rule in gbdt.leafwise_compact_on deserves another look."""
    from lightgbm_tpu.models.grower_unified import grow_tree
    compiled = grow_tree.lower(*_grow_args(one_chip), **_GROW_KW).compile()
    per_row = compiled.memory_analysis().temp_size_in_bytes / N
    assert 1500 < per_row < 4000, per_row
    assert per_row * 11_000_000 > HBM_BYTES


# ----------------------------------------- programs built by the system

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


def test_fused_chunk_program_compiles(one_chip, as_tpu, monkeypatch):
    """chip_smoke phase (b): the depth-wise int8 chunk of 8 iterations,
    built by GBDT.train_chunk itself, its real argument tree re-shaped to
    N=2**20."""
    n_tiny = 1000                      # no other axis has this length
    prog, seen = _captured_chunk_program(
        monkeypatch,
        {"objective": "binary", "num_leaves": str(LEAVES),
         "max_bin": str(B), "grow_policy": "depthwise",
         "hist_dtype": "int8", "metric": "binary_logloss",
         "is_training_metric": "true"},
        _tiny_binary_dataset(n_tiny), is_eval=True)
    args = _like(one_chip, seen, rows_from=n_tiny, rows_to=N)
    _check(prog.lower(*args).compile(), custom_call=True)


def test_fused_chunk_program_compiles_on_the_wide_table(
        one_chip, as_tpu, monkeypatch):
    """The program of the cell ``epsilon-levelwise-int8.train``: the
    configuration's own ``key=value`` pairs, 2,000 columns, its argument
    tree re-shaped to the 400,000 rows."""
    import json
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "epsilon-levelwise-int8.json")) as fh:
        conf = json.load(fh)
    assert (conf["rows"], conf["features"]) == (WIDE_N, WIDE_F)
    n_tiny = 1000
    prog, seen = _captured_chunk_program(
        monkeypatch, conf["params"], _tiny_binary_dataset(n_tiny, WIDE_F),
        is_eval=False)
    args = _like(one_chip, seen, rows_from=n_tiny, rows_to=WIDE_N)
    _cell_size(_check(prog.lower(*args).compile(), custom_call=True))


def test_data_parallel_chunk_program_compiles_for_four_chips(
        topo, as_tpu, monkeypatch):
    """chip_smoke --chips 4: the same chunk under tree_learner=data on a
    (data,)=4 mesh of the described v5e:2x2 — collectives and the int8
    Pallas kernel in one program, N/4 rows of every row-aligned input on
    each chip."""
    import lightgbm_tpu as lgb
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.metrics import create_metric
    from lightgbm_tpu.objectives import create_objective
    from lightgbm_tpu.parallel import create_parallel_learner
    # the data-parallel program closes over the true row count (metric
    # slices, padding), so its shapes cannot be re-sized after the fact:
    # build the booster at the real N (placing 2**20 rows on the CPU
    # devices is cheap) and only the compile targets the described chips
    config = lgb.OverallConfig()
    config.set({"objective": "binary", "num_leaves": str(LEAVES),
                "max_bin": str(B), "grow_policy": "depthwise",
                "hist_dtype": "int8", "metric": "binary_logloss",
                "is_training_metric": "true", "tree_learner": "data",
                "num_machines": "4"}, require_data=False)
    learner = create_parallel_learner(config)
    booster = lgb.GBDT()
    booster.init(config.boosting_config, _tiny_binary_dataset(N),
                 create_objective(config.objective_type,
                                  config.objective_config),
                 [create_metric(t, config.metric_config)
                  for t in config.metric_types], learner=learner)
    tpu_mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    seen = {}
    real_chunk_program = learner.chunk_program

    def capturing_chunk_program(*a, **kw):
        # the shard_map is built over the described chips; everything
        # around it (placing the tiny inputs) keeps the CPU mesh
        with monkeypatch.context() as m:
            m.setattr(learner, "_mesh", lambda: tpu_mesh)
            prog, num_shards = real_chunk_program(*a, **kw)

        def call(*args):
            seen["prog"], seen["args"] = prog, args
            raise _Captured
        return call, num_shards

    monkeypatch.setattr(learner, "chunk_program", capturing_chunk_program)
    with pytest.raises(_Captured):
        booster.train_chunk(8, is_eval=True)

    def conv(a):
        a = np.asarray(a) if not hasattr(a, "shape") else a
        spec = P(*("data" if d == N else None for d in a.shape))
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(tpu_mesh, spec))

    compiled = seen["prog"].lower(*jax.tree.map(conv, seen["args"])).compile()
    ma = _check(compiled, custom_call=True)
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    # per chip: a quarter of the bin matrix, not all of it
    assert ma.argument_size_in_bytes < F * N, ma.argument_size_in_bytes


def _leafwise_tree(rng, leaves):
    """A random tree in the model's own encoding, grown like the trainer
    grows one: split k turns leaf l into node k with children ~l, ~(k+1)."""
    from lightgbm_tpu.models.tree import Tree
    n = leaves - 1
    left, right = np.zeros(n, np.int32), np.zeros(n, np.int32)
    leaf_parent = np.full(leaves, -1, np.int32)
    for k in range(n):
        leaf = rng.randint(0, k + 1)
        p = leaf_parent[leaf]
        if p >= 0:
            if left[p] == ~leaf:
                left[p] = k
            else:
                right[p] = k
        left[k], right[k] = ~leaf, ~(k + 1)
        leaf_parent[leaf] = leaf_parent[k + 1] = k
    feat = rng.randint(0, F, n)
    return Tree(leaves, feat, feat, rng.randint(0, B - 1, n),
                rng.randn(n), np.ones(n), left, right, leaf_parent,
                rng.randn(leaves) * 0.1)


def test_serving_program_compiles(one_chip, as_tpu):
    """chip_smoke phase (c): one bucketed breadth-first scoring program of
    serving.ServingEngine at 255 leaves, with the donation the engine
    resolves on a TPU."""
    from lightgbm_tpu.serving import FlatEnsemble, ServingEngine
    rng = np.random.RandomState(9)
    flat = FlatEnsemble.from_models(
        [_leafwise_tree(rng, LEAVES) for _ in range(16)], num_class=1)
    engine = ServingEngine(flat)
    assert engine.donate, "donation should resolve on for a TPU backend"
    bucket = engine.buckets[-1]
    codes = flat.encode(np.zeros((4, F)))
    t = _like(one_chip, engine._device_tables())
    compiled = engine._program("scores").lower(
        _shape(one_chip, (codes.shape[0], bucket), codes.dtype),
        t["sf"], t["tr"], t["lc"], t["rc"], t["lv"], t["root"], t["tc"],
        max_depth=flat.max_depth, num_class=flat.num_class).compile()
    _check(compiled, custom_call=False)


@pytest.mark.parametrize("chips", [1, 4])
def test_ingest_update_program_compiles(topo, as_tpu, chips):
    """io/streaming.DeviceRowWriter's donated update of the device-resident
    [F, N] bin matrix with one 200k-row chunk: a dynamic_update_slice on
    one chip; on four, each chip lands its own row block's part with no
    collective (left to the partitioner, the sharded update took 27 s per
    chunk on four v5e chips — PR 24)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.io.streaming import _update_program
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    placed = NamedSharding(mesh, P(None, "data") if chips > 1 else P())
    replicated = NamedSharding(mesh, P())
    compiled = _update_program(placed).lower(
        jax.ShapeDtypeStruct((F, N), jnp.uint8, sharding=placed),
        jax.ShapeDtypeStruct((F, 200_000), jnp.uint8, sharding=replicated),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)).compile()
    ma = _check(compiled, custom_call=False)
    text = compiled.as_text()
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "collective-permute"))
    # donated: updated in place (one padded copy of the local block on
    # four chips), never a second copy of the whole matrix per device
    assert ma.temp_size_in_bytes < F * N, ma.temp_size_in_bytes
