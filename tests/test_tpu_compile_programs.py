"""Compile, for a described v5e, the narrow table's growers and the
programs the system itself builds: F=28, 255 bins, 255 leaves, N=2**20
(``tests/test_tpu_compile.py`` holds the rules these files keep).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_described import (  # noqa: F401 (fixtures)
    as_tpu, B, _Captured, _captured_chunk_program, _cell_config, _check, F,
    _grow_args, _GROW_KW, HBM_BYTES, LEAVES, _like, _lower_route_kernel, N,
    no_persistent_cache, one_chip, _operand_producers, _range_passes,
    _root_hist_layouts, _row_relayouts, _shape, _tiny_binary_dataset, topo,
    _TracedCounters, _unlabelled)

NARROW_N = 10_502_144    # benchmarks/configs/higgs-levelwise-int8, padded


# ------------------------------------------------------------- growers

def test_grow_depthwise_int8_compiles(one_chip, as_tpu):
    from lightgbm_tpu.models.grower_unified import grow_tree_depthwise_jit
    compiled = grow_tree_depthwise_jit.lower(
        *_grow_args(one_chip), compute_dtype="int8", **_GROW_KW).compile()
    _check(compiled, custom_call=True)
    # the eight levels' row routing is the kernel's too, under the scope
    assert len(re.findall(
        r'"tpu_custom_call"[^\n]*/row_route/jit\(_route_pallas_fn\)',
        compiled.as_text())) == 8


@pytest.mark.parametrize("slots", [1, 128])
def test_route_kernel_compiles_at_the_narrow_cell(one_chip, as_tpu, slots):
    """The level-wise row routing of ``higgs-levelwise-int8.train`` at its
    own rows, the root's level and the last: one block of 28 columns,
    chunks of 32,768 rows, inside the VMEM a kernel may hold (the compiler
    refuses one that is not)."""
    from lightgbm_tpu.ops.route_pallas import route_grid
    assert route_grid(F, NARROW_N) == (28, 1, 32768, 321)
    compiled = _lower_route_kernel(one_chip, F, NARROW_N, slots).compile()
    assert "_route_kernel" in compiled.as_text()
    _check(compiled, custom_call=True)


def test_grow_leafcompact_f32_compiles(one_chip, as_tpu):
    """The default route of task=train on a TPU: compacted grower, Pallas
    partition, Pallas float histogram.  Every partition kernel (the
    one-block kernel, one a bucket width) reads and writes the pane
    itself, and XLA makes no pass over a split's range around it."""
    from lightgbm_tpu.models.grower_unified import grow_tree_leafcompact
    from lightgbm_tpu.ops.compact import pallas_partition_ok
    assert pallas_partition_ok()
    with _TracedCounters() as traced:
        compiled = grow_tree_leafcompact.lower(
            *_grow_args(one_chip), use_pallas_partition=True,
            partition_overlap=True, **_GROW_KW).compile()
    assert traced["partition/in_pane"] == traced["partition/pallas"] > 1
    assert traced["partition/pallas_rblocks"] == traced["partition/pallas"]
    ma = _check(compiled, custom_call=True)
    assert _range_passes(compiled.as_text(), F) == []
    # the route that holds an 11M-row table: well under 1 KB of temp/row
    assert ma.temp_size_in_bytes / N < 1024, ma.temp_size_in_bytes


def test_masked_leafwise_memory_per_row_is_pinned(one_chip, as_tpu):
    """What the MASKED leaf-wise grower (leafwise_compact=false) holds in
    temporaries at 1M rows: 0.40 GB, 383 bytes a row, since the float
    kernel folds its bin code (PR 35).  Until then it read 2.81 GB, taken
    for 2.7 KB a row and ~31 GB at the 11M-row Higgs table, the finding
    that kept big tables off this route; it was the leaf histogram cache,
    ``f32[255,28,255,3]`` laid out with its three statistics padded to
    128 lanes (936 MB a buffer), and not the rows.  Behind the folded
    kernel's narrow accumulator XLA lays the cache out bins-minor (133
    MB).  The route has not run on a chip since; if this leaves the band
    either way, the rule in gbdt.leafwise_compact_on and PERF.md
    section 7 (PR 35) deserve another look."""
    from lightgbm_tpu.models.grower_unified import grow_tree
    compiled = grow_tree.lower(*_grow_args(one_chip), **_GROW_KW).compile()
    per_row = compiled.memory_analysis().temp_size_in_bytes / N
    assert 250 < per_row < 600, per_row
    assert "f32[255,28,255,3]{3,2,1,0" not in compiled.as_text()


# ----------------------------------------- programs built by the system

_CHUNK = {}     # the compiled chunk program, once a module (a minute)


def _chunk_compiled(one_chip, monkeypatch):
    """chip_smoke phase (b): the depth-wise int8 chunk of 8 iterations,
    built by GBDT.train_chunk itself, its real argument tree re-shaped to
    N=2**20.  Call it under ``as_tpu``."""
    if not _CHUNK:
        n_tiny = 1000                  # no other axis has this length
        prog, seen = _captured_chunk_program(
            monkeypatch,
            {"objective": "binary", "num_leaves": str(LEAVES),
             "max_bin": str(B), "grow_policy": "depthwise",
             "hist_dtype": "int8", "metric": "binary_logloss",
             "is_training_metric": "true"},
            _tiny_binary_dataset(n_tiny), is_eval=True)
        args = _like(one_chip, seen, rows_from=n_tiny, rows_to=N)
        _CHUNK["compiled"] = prog.lower(*args).compile()
    return _CHUNK["compiled"]


def test_fused_chunk_program_compiles(one_chip, as_tpu, monkeypatch):
    _check(_chunk_compiled(one_chip, monkeypatch), custom_call=True)


def _chunk_labels(one_chip, monkeypatch):
    from lightgbm_tpu import costmodel
    text = _chunk_compiled(one_chip, monkeypatch).as_text()
    return text, costmodel.label_unscoped_ops(text)


def test_every_unscoped_operation_of_the_chunk_program_has_a_label(
        one_chip, as_tpu, monkeypatch):
    """What a device trace of the narrow cell shows under no scope
    (``unscoped_ms_per_iter``), the program names itself
    (``costmodel.op_phases``): no operation of the entry computation or the
    chunk's loop is left without a phase or ``xla``."""
    from lightgbm_tpu import costmodel
    from lightgbm_tpu.telemetry import DEVICE_PHASES
    text, labels = _chunk_labels(one_chip, monkeypatch)
    assert _unlabelled(text, labels) == []
    assert len(labels) > 300
    assert {found[0] for found in labels.values()} <= set(
        DEVICE_PHASES) | {costmodel.XLA}
    # the compiler's own are copies, prefetches and buffers, none as large
    # as the table
    summary = costmodel._unscoped_summary(labels)
    assert summary["xla"] > 100 and summary["xla_largest"][1] < F * N, summary


def test_quantise_fusions_of_the_chunk_program_read_histogram(
        one_chip, as_tpu, monkeypatch):
    """The int8 operand of every histogram kernel (``[4, N]``: the codes of
    a level's gradients and hessians, its count row and leaf columns) is
    one fusion a pass, and a trace puts it under ``histogram``: its own
    name holds the scope (PR 39).  Before, the rows came out of
    multi-output fusions with no metadata, which the program's map named
    ``histogram`` and ``gradient``."""
    text, labels = _chunk_labels(one_chip, monkeypatch)
    producers = _operand_producers(text, N)
    assert len(producers) == 8, producers
    for name, line in producers:
        assert "/histogram/" in line and name not in labels, line


def test_chunk_program_at_the_narrow_cell_relayouts_no_int8_row(
        one_chip, as_tpu, monkeypatch):
    """The program of ``higgs-levelwise-int8.train`` at its own
    10,500,000 rows, no whole number of the 1-D int8 layout's tiles.
    Each pass's ``[3, N]`` operand (no count row: ``min_data_in_leaf=0``
    beside a hessian floor) is one fusion under ``histogram``, and
    the compiler moves no int8 row: until PR 39 the rows were made apart
    and stacked, and 28 one-trip loops a tree carried each into an
    ``s8[1,1,N]`` (12.7 ms an iteration on the chip, PERF.md section 5);
    the temporaries were 1.136 GB, 0.976 with the count row, now 0.974.
    The root's float histogram is laid out as the three-statistic
    program lays out its own, row-major (PERF.md section 6, PR 44)."""
    conf = _cell_config("higgs-levelwise-int8")
    rows, columns = conf["rows"], conf["features"]
    assert (rows, columns) == (10_500_000, F)
    n_tiny = 1000
    prog, seen = _captured_chunk_program(
        monkeypatch, conf["params"], _tiny_binary_dataset(n_tiny, columns),
        is_eval=False)
    compiled = prog.lower(*_like(one_chip, seen, rows_from=n_tiny,
                                 rows_to=rows)).compile()
    ma = _check(compiled, custom_call=True)
    text = compiled.as_text()
    assert _row_relayouts(text, rows) == []
    producers = _operand_producers(text, rows, stats=2)
    assert len(producers) == 8 and all(
        "/histogram/" in line for _name, line in producers), producers
    assert _root_hist_layouts(text, columns) == {"{3,2,1,0:T(8,128)}"}
    assert 0.9e9 < ma.temp_size_in_bytes < 1.05e9, ma.temp_size_in_bytes


def test_decomposed_cumulative_sums_of_the_chunk_program_read_split_find(
        one_chip, as_tpu, monkeypatch):
    """The threshold scan's cumulative sums reach the device as bare
    ``pad``, ``reduce-window``, ``slice`` and ``reverse`` instructions
    with no metadata: each takes the name of the split search that reads
    it, not of the histogram it is computed from."""
    _text, labels = _chunk_labels(one_chip, monkeypatch)
    windows = {name: found for name, found in labels.items()
               if found[1] == "reduce-window" and found[2].startswith("f32")}
    assert len(windows) >= 8 * 3, windows
    assert {found[0] for found in windows.values()} == {"split_find"}, windows
    around = {found[0] for found in labels.values()
              if found[1] in ("pad", "reverse")}
    assert around == {"split_find"}, around


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
