"""lightgbm_tpu — a TPU-native gradient-boosted-decision-tree framework.

Brand-new JAX/XLA re-design of early LightGBM (reference at
/root/reference): histogram-based leaf-wise GBDT with serial,
feature-parallel and data-parallel tree learning — the compute path is
jitted XLA programs over a dense ``[features, rows]`` bin matrix in HBM, and
distribution is ``shard_map`` over a ``jax.sharding.Mesh`` with XLA
collectives instead of sockets/MPI.

Public surface:
- CLI: ``python -m lightgbm_tpu task=train config=train.conf`` (the
  reference's ``lightgbm`` executable surface; examples/ configs run
  unchanged).
- Python API: :class:`Dataset`, :func:`train`, :class:`GBDT`.
"""
from __future__ import annotations

import os

__version__ = "0.1.0"

# Exec'd parallel-parse workers (io/parallel_ingest.py) import this
# package but touch only the numpy parse stack: skip the JAX surface so
# worker startup is milliseconds, not a backend import.
_INGEST_WORKER = os.environ.get("LIGHTGBM_TPU_INGEST_WORKER") == "1"

# Persistent XLA compilation cache (compile_cache.py holds the placement
# rule).  Off when the process is pinned to the CPU: CPU AOT artifacts are
# host-feature-specific, and the test suite opts in for itself
# (tests/conftest.py).
if not _INGEST_WORKER:
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        from . import compile_cache
        compile_cache.configure()

    from . import telemetry
    from .config import OverallConfig, load_config
    from .io.dataset import Dataset
    from .models.gbdt import GBDT
    from .models.tree import Tree


def train(params: dict, train_set: Dataset, valid_sets=(), valid_names=None):
    """Convenience training entry for library users.

    ``params`` uses the reference's key=value names (aliases applied).
    """
    from .config import OverallConfig
    from .metrics import create_metric
    from .objectives import create_objective

    config = OverallConfig()
    config.set({k: str(v) for k, v in params.items()}, require_data=False)
    io = config.io_config
    mem_on = io.memory_stats_enabled()
    armed_telemetry = bool(io.metrics_out) or mem_on
    if armed_telemetry:
        telemetry.enable(io.metrics_out or None,
                         fence=io.metrics_fence, memory=mem_on)
        # fresh registry per armed run: a second train() in the same
        # process must not ship the first run's counters in its records
        telemetry.reset()
    booster = GBDT()
    objective = create_objective(config.objective_type,
                                 config.objective_config)
    train_metrics = []
    if config.boosting_config.is_provide_training_metric:
        train_metrics = [m for m in
                         (create_metric(t, config.metric_config)
                          for t in config.metric_types) if m is not None]
    learner = None
    if config.boosting_config.tree_learner != "serial":
        from .parallel import create_parallel_learner
        learner = create_parallel_learner(config)
    booster.init(config.boosting_config, train_set, objective, train_metrics,
                 learner=learner)
    for i, valid in enumerate(valid_sets):
        name = (valid_names[i] if valid_names else f"valid_{i + 1}")
        metrics = [m for m in (create_metric(t, config.metric_config)
                               for t in config.metric_types) if m is not None]
        booster.add_valid_dataset(valid, metrics, name=name)
    is_eval = bool(train_metrics) or bool(valid_sets)
    try:
        booster.run_training(config.boosting_config.num_iterations, is_eval)
    finally:
        if armed_telemetry:
            # this call armed the sink, so it closes it: a later train()
            # without metrics_out must not append records (and a later
            # fence-free run must not inherit fence mode).  snapshot()
            # still serves the accumulated data after disable
            telemetry.disable()
    return booster
