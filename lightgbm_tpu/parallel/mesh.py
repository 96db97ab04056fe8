"""Device mesh construction and multi-host bootstrap.

Replaces the reference's Linkers bootstrap
(/root/reference/src/network/linkers_socket.cpp:20-110: machine-list parse,
rank inference, TCP mesh) with jax.distributed + a 1-D
``jax.sharding.Mesh``.  A "machine" in the reference maps to a mesh slot
(one TPU device — or one device per host in multi-host runs); collective
traffic rides ICI/DCN via XLA instead of raw sockets.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh

from ..utils import log

DATA_AXIS = "data"
FEATURE_AXIS = "feature"
# serving-side tree-sharded ensembles (ISSUE 13): the [T, max_nodes] node
# tables shard along this axis, rows (codes) are replicated
TREE_AXIS = "tree"


def init_distributed(config=None) -> None:
    """Multi-host bootstrap (linkers_socket.cpp equivalent).

    Uses jax.distributed when coordinator env vars are present; single-host
    multi-device needs no bootstrap.  Must run before anything touches the
    XLA backend — so the already-initialized check reads the distributed
    client state directly instead of jax.process_count() (which would
    itself initialize the backend and make initialize() impossible).
    """
    from .. import hatches
    coordinator = hatches.raw("LGBM_TPU_COORDINATOR")
    if not coordinator:
        return
    # private probe — there is no public "is the distributed client up?"
    # API (a launcher may have initialized it before the CLI runs)
    from jax._src import distributed as _distributed
    if _distributed.global_state.client is not None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=hatches.int_value("LGBM_TPU_NUM_PROCS", 1),
        process_id=hatches.int_value("LGBM_TPU_PROC_ID", 0))
    clock_handshake()


def clock_handshake() -> float:
    """Cross-host clock-offset handshake (ISSUE 5), recorded at mesh
    setup: every process allgathers its ``time.time()`` sample and
    installs the leader-relative offset into telemetry, so per-process
    JSONL shard timestamps can be merged onto ONE job clock by
    scripts/timeline_report.py (cross-host skew attribution is
    meaningless on uncorrected clocks).

    The offset is accurate to ~one collective round-trip (the gathered
    samples are taken within the allgather's skew window); the RTT is
    recorded beside it as the error bar.  COLLECTIVE — every process of
    a multi-process job reaches init_distributed, which calls it.
    Single-process runs (and backends without multi-process collectives)
    record offset 0.  Returns the installed offset."""
    import time as _time
    from .. import telemetry
    if jax.process_count() <= 1:
        telemetry.set_clock_offset(0.0)
        return 0.0
    try:
        from jax.experimental import multihost_utils
        t0 = _time.perf_counter()
        gathered = np.asarray(multihost_utils.process_allgather(
            np.asarray(_time.time(), np.float64))).reshape(-1)
        rtt = _time.perf_counter() - t0
        offset = float(gathered[0] - gathered[jax.process_index()])
        telemetry.set_clock_offset(offset, rtt_s=rtt)
        return offset
    except Exception as e:  # pragma: no cover - backend capability gap
        log.warning("clock handshake unavailable (%s); shard timestamps "
                    "stay on local clocks" % e)
        telemetry.set_clock_offset(0.0)
        return 0.0


def get_mesh(num_machines: Optional[int] = None,
             axis_name: str = DATA_AXIS,
             device_type: str = "") -> Mesh:
    """1-D mesh over the first ``num_machines`` devices.

    ``device_type`` (config.py device_type: "cpu"/"tpu"/"gpu") selects the
    backend to draw mesh slots from in mixed-backend processes; empty means
    the default platform.

    Multi-process runs use EVERY device of the distributed job (a
    "machine" in the reference maps to a process; each contributes all its
    local devices as mesh slots — jax.devices() is globally ordered by
    process index, which make_global_rows relies on)."""
    devices = jax.devices(device_type) if device_type else jax.devices()
    if jax.process_count() > 1:
        return Mesh(np.array(devices), (axis_name,))
    if num_machines is None or num_machines <= 0:
        num_machines = len(devices)
    if num_machines > len(devices):
        log.warning(
            "num_machines=%d exceeds available devices (%d); shrinking "
            "world size to match (linkers_socket.cpp:106-109 behavior)"
            % (num_machines, len(devices)))
        num_machines = len(devices)
    return Mesh(np.array(devices[:num_machines]), (axis_name,))


def factor_machines(num_machines: int, feature_shards: int = 0,
                    voting: bool = False) -> "tuple[int, int]":
    """Factor ``num_machines`` into ``(data_shards, feature_shards)`` for
    the 2-D hybrid mesh (ISSUE 9).

    ``feature_shards > 0`` (the config knob) is honored exactly and must
    divide num_machines (loud error otherwise — a silent re-factor would
    change the wire bytes the perf gate tracks).  ``feature_shards == 0``
    resolves automatically:

    - hybrid: the largest divisor of num_machines that is <= sqrt(
      num_machines) — rows get at least as many shards as features (the
      histogram's row dimension is the one that grows with data), e.g.
      4 -> (2, 2), 8 -> (4, 2), 6 -> (3, 2), primes -> (n, 1).
    - voting: (num_machines, 1) — the reference's voting design is pure
      data-parallel (top-k votes over row shards); feature sharding
      composes only when asked for explicitly.

    A factoring with feature_shards == 1 degenerates to pure data
    parallelism on the ``data`` axis (documented fallback: hybrid then
    records the same wire bytes as tree_learner=data/psum)."""
    n = max(int(num_machines), 1)
    if feature_shards > 0:
        if n % feature_shards:
            log.fatal("feature_shards=%d does not divide num_machines=%d"
                      % (feature_shards, n))
        return n // feature_shards, feature_shards
    if voting:
        return n, 1
    fs = 1
    for d in range(2, int(n ** 0.5) + 1):
        if n % d == 0:
            fs = d
    return n // fs, fs


def get_mesh2d(num_machines: Optional[int] = None,
               feature_shards: int = 0, device_type: str = "",
               voting: bool = False) -> Mesh:
    """Explicit 2-D ``(data, feature)`` mesh over the first
    ``num_machines`` devices (ISSUE 9): rows shard over the ``data``
    axis, feature-block ownership lives on the ``feature`` axis, so the
    histogram reduce (psum over ``data`` restricted to owned blocks) and
    the SplitInfo allreduce (over ``feature``) ride different axes of
    one mesh — the hybrid data x feature plan the reference names but
    never implements (SURVEY.md "Voting-parallel: named but absent").

    Multi-process hybrid runs are not supported in this revision: the
    row-shard lift (make_global_rows) assumes the 1-D process-ordered
    mesh — fail loudly instead of training on a wrong layout."""
    if jax.process_count() > 1:
        log.fatal("tree_learner=hybrid/voting is single-process in this "
                  "revision (multi-process keeps the 1-D data mesh)")
    devices = jax.devices(device_type) if device_type else jax.devices()
    if num_machines is None or num_machines <= 0:
        num_machines = len(devices)
    if num_machines > len(devices):
        log.warning(
            "num_machines=%d exceeds available devices (%d); shrinking "
            "world size to match (linkers_socket.cpp:106-109 behavior)"
            % (num_machines, len(devices)))
        num_machines = len(devices)
    ds, fs = factor_machines(num_machines, feature_shards, voting=voting)
    grid = np.array(devices[:ds * fs]).reshape(ds, fs)
    return Mesh(grid, (DATA_AXIS, FEATURE_AXIS))


def get_serving_mesh(shards: int, device_type: str = "") -> Mesh:
    """1-D ``("tree",)`` mesh over the first ``shards`` devices for the
    tree-sharded serving engine (ISSUE 13): ``FlatEnsemble``'s
    [T, max_nodes] node tables shard contiguously along the tree axis —
    each device's HBM holds ONLY its tree block, which is what lifts the
    multi-GB-ensemble regime — while the codes batch is replicated.

    Loud error when ``shards`` exceeds the available devices: a silent
    shrink (the training meshes' linkers_socket behavior) would change
    the documented shard layout AND the serve/tree_* wire bytes the
    telemetry interconnect block prices, mid-deployment."""
    devices = jax.devices(device_type) if device_type else jax.devices()
    shards = int(shards)
    if shards < 1:
        log.fatal("serve_shards must be >= 1 to build a serving mesh "
                  "(got %d)" % shards)
    if shards > len(devices):
        log.fatal("serve_shards=%d exceeds available devices (%d) — the "
                  "tree-sharded engine never silently shrinks its mesh"
                  % (shards, len(devices)))
    return Mesh(np.array(devices[:shards]), (TREE_AXIS,))


def dataset_row_sharding(num_rows: int, shard_rows: bool = False,
                         num_machines: Optional[int] = None,
                         device_type: str = "",
                         parallel_consumer: bool = False):
    """Explicit placement for a streamed ``[F, N]`` bin matrix (ISSUE 8):
    a NamedSharding over the ``(data,)`` mesh axis.

    ``shard_rows=True`` (a single-process data-parallel consumer) shards
    the row axis across the CONSUMING LEARNER's mesh — ``get_mesh(
    num_machines)``, the exact device set the learner's jit(shard_map)
    programs run over — when the row count divides it (their bins
    in_spec is ``P(None, 'data')``, so the shards are picked up in
    place).  A non-dividing row count, or ``parallel_consumer=True``
    without ``shard_rows`` (the single-process feature-parallel
    learner), commits the matrix REPLICATED on that same learner mesh:
    a committed array's device set must equal the consuming program's,
    so a one-device placement would make the learner's multi-device
    shard_map raise "incompatible devices".  Only the serial consumer
    (neither flag) gets the one-device ``(data,)`` mesh — still an
    explicit placement, and numerically identical to the resident
    loader's default-device array (a multi-device input would let GSPMD
    repartition the serial grower's reductions and break
    bit-identity)."""
    from jax.sharding import NamedSharding, PartitionSpec
    if shard_rows or parallel_consumer:
        mesh = get_mesh(num_machines, DATA_AXIS, device_type)
        num_devices = int(mesh.devices.size)
        if (shard_rows and num_devices > 1 and num_rows > 0
                and num_rows % num_devices == 0):
            return NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))
        return NamedSharding(mesh, PartitionSpec())
    devices = jax.devices(device_type) if device_type else jax.devices()
    mesh = Mesh(np.array(devices[:1]), (DATA_AXIS,))
    return NamedSharding(mesh, PartitionSpec())


def get_rank() -> int:
    """Process rank for host-side data sharding (Network::rank)."""
    return jax.process_index()


def get_num_machines() -> int:
    return jax.process_count()


def global_row_layout(n_local: int):
    """Agree on a per-process padded row-block size for multi-host arrays.

    The reference's data-parallel mode gives each PROCESS an uneven random
    row shard (dataset.cpp:172-216); jax sharded arrays need equal
    per-device blocks, so every process pads its shard to the global max
    (rounded up to its local device count).  Returns (max_n, counts) with
    counts[p] = process p's true row count."""
    from jax.experimental import multihost_utils
    counts = multihost_utils.process_allgather(np.asarray(n_local))
    counts = np.atleast_1d(np.asarray(counts)).reshape(-1)
    d_local = jax.local_device_count()
    max_n = int(counts.max())
    max_n = -(-max_n // d_local) * d_local
    return max_n, counts


def make_global_rows(local, max_n: int, mesh: Mesh, row_axis: int = 0,
                     axis_name: str = DATA_AXIS):
    """One process's row shard -> the global row-sharded jax.Array.

    Pads ``local`` to ``max_n`` rows along ``row_axis`` and assembles the
    [P * max_n, ...] global array via
    ``jax.make_array_from_process_local_data`` — the glue between host
    shards and the shard_map programs (rows land on the owning process's
    devices; no cross-host transfer)."""
    from jax.sharding import NamedSharding, PartitionSpec
    local = np.asarray(local)
    pad = max_n - local.shape[row_axis]
    assert pad >= 0
    if pad:
        widths = [(0, 0)] * local.ndim
        widths[row_axis] = (0, pad)
        local = np.pad(local, widths)
    spec = [None] * local.ndim
    spec[row_axis] = axis_name
    sharding = NamedSharding(mesh, PartitionSpec(*spec))
    global_shape = list(local.shape)
    global_shape[row_axis] = max_n * jax.process_count()
    return jax.make_array_from_process_local_data(
        sharding, local, tuple(global_shape))


def gather_ragged_rows(local) -> np.ndarray:
    """Every process's host array, concatenated along axis 0 in process
    order — the host-side complement of make_global_rows for UNEVEN
    per-process lengths (row shards, per-query count vectors).  Used to
    rebuild GLOBAL metric metadata (labels/weights/query layout) on every
    process so distributed metric evaluation sees the same rows as a
    serial run (gbdt.cpp:225-259 evaluates every iteration in parallel
    mode too)."""
    from jax.experimental import multihost_utils
    local = np.asarray(local)
    lengths = np.asarray(multihost_utils.process_allgather(
        np.asarray(local.shape[0]))).reshape(-1)
    max_len = int(lengths.max())
    pad = max_len - local.shape[0]
    if pad:
        local = np.pad(local, [(0, pad)] + [(0, 0)] * (local.ndim - 1))
    full = np.asarray(multihost_utils.process_allgather(local))
    full = full.reshape((-1, max_len) + local.shape[1:])
    return np.concatenate([full[p, :int(lengths[p])]
                           for p in range(lengths.size)], axis=0)


def sync_up_by_min(value):
    """GlobalSyncUpByMin (application.cpp:275-302): align seeds/fractions to
    the global minimum across processes for deterministic distributed runs."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(np.asarray(value))
    return type(value)(np.min(gathered))
