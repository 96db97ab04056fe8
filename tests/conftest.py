"""Test configuration: force an 8-device virtual CPU platform so sharding
and parallel-learner tests run without TPU hardware (SURVEY.md §4).

jax.config.update is used besides the env var: it takes effect any time
before backend initialization, also where jax was imported earlier.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache for the suite: the tier-1 wall is
# compile-bound (the unrolled grower programs dominate), and the cache
# is content-addressed on the HLO — edited programs recompile, unchanged
# ones load hot.  lightgbm_tpu/compile_cache.py places it (the package
# itself leaves the cache off on the CPU; the suite opts in).
from lightgbm_tpu import compile_cache

compile_cache.configure(min_compile_secs=0.5)

import faulthandler
import shutil
import signal
import subprocess

import numpy as np
import pytest

REFERENCE_EXAMPLES = "/root/reference/examples"
REFERENCE_SRC = "/root/reference"
REFERENCE_BUILD = "/tmp/lightgbm_reference_build"
REFERENCE_BINARY = os.path.join(REFERENCE_BUILD, "lightgbm")


# The files that take over half a minute of a cold run, heaviest first
# (ROADMAP "Tests" has each one's seconds; a file that passes half a minute
# is added).  Under ``--dist loadfile`` a file is one worker's chain and the
# run's wall is the last chain's start plus its length.  xdist starts the
# files with the most tests first, which here are not the long ones (two
# full-size compiles are 110 s), so these are collected first, in this
# order, and xdist is told to keep the order it is given.
HEAVY_FIRST = (
    "test_tpu_compile_programs.py", "test_tpu_compile.py",
    "test_hist_int8_ranged_trees.py",
    "test_multiprocess_dp.py", "test_parallel.py", "test_hist_int8_held.py",
    "test_hist_int8_fold.py", "test_mixedbin.py", "test_wide_table.py",
    "test_tpu_compile_wide.py", "test_hybrid_voting.py", "test_gbdt.py",
    "test_streaming.py", "test_leafwise_wide.py", "test_leafcompact.py",
    "test_tpu_compile_airline.py", "test_airline_cell.py",
    "test_distributed_telemetry.py", "test_goss_chunk.py",
    "test_route_pallas.py", "test_depthwise.py", "test_hist_int8.py",
    "test_hist_int8_ranges.py",
    "test_graftlint.py",
    "test_mixedbin_hybrid.py", "test_hist_float_pallas.py",
    "test_elastic.py", "test_health.py", "test_costmodel.py",
    "test_serving.py", "test_grower_unified.py",
)


_STDERR_FD = 2


def pytest_configure(config):
    global _STDERR_FD
    _STDERR_FD = os.dup(2)      # before any test's capture takes fd 2
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(HEAVY_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


# twice the slowest test or fixture of a cold run (ROADMAP "Tests")
TEST_LIMIT_S = 300
TEST_LIMIT_GRACE_S = 30


@pytest.fixture(autouse=True)
def _test_time_limit(request):
    """A test that runs past TEST_LIMIT_S is failed by name, so a wait
    that never ends costs the run one test and not its clock.  The alarm
    reaches the test because xdist runs tests on its worker's main
    thread.  It cannot reach a main thread that waits inside one C call
    (``test_held_onehot_same_trees`` stood so under XLA for twenty
    minutes, twice).  For that case faulthandler's watchdog thread, which
    needs no interpreter, writes every thread's stack to the run's log
    TEST_LIMIT_GRACE_S later, so the log names the test and the call it
    stands in.  It does not end the process: under ``--dist loadfile``
    xdist hands a dead worker's file, the test that killed it included,
    to a new worker, again and again."""
    def expired(signum, frame):
        pytest.fail("%s ran past the %d s a test may take"
                    % (request.node.nodeid, TEST_LIMIT_S), pytrace=False)
    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    faulthandler.dump_traceback_later(TEST_LIMIT_S + TEST_LIMIT_GRACE_S,
                                      file=_STDERR_FD)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(autouse=True)
def _telemetry_leak_guard():
    """Telemetry is process-global state: a test that leaves the registry
    enabled (or a sink open) silently poisons every later test — route
    counters bleed across tests and sinks append foreign records.  Fail
    the offender, then clean up so the rest of the suite still runs on a
    clean registry.  Set up before (torn down after) per-test fixtures,
    so tests that disable telemetry in their own teardown pass."""
    from lightgbm_tpu import telemetry
    yield
    leaked_enabled = telemetry.enabled()
    leaked_sink = telemetry.sink_open()
    # ISSUE 5 surface: timeline/shard mode left on makes the next
    # metrics_out test write an unexpected shard file instead of its
    # configured path (an unmerged shard surviving the test)
    leaked_timeline = telemetry.timeline_enabled()
    # ISSUE 10 surface: graftlint's jaxpr layer arms telemetry in
    # trace-census mode (analysis.jaxpr_rules.begin_census) to record
    # the seam inventory while tracing; a test that leaves it armed
    # makes every later record_collective land in a foreign census AND
    # leaves telemetry enabled.  Check BEFORE the disable below (the
    # census teardown owns its own telemetry restore).
    from lightgbm_tpu.analysis import jaxpr_rules as _graftlint_census
    leaked_census = _graftlint_census.trace_census_active()
    if leaked_census:
        _graftlint_census.end_census()
    # ISSUE 15: every thread-owning subsystem (checkpoint writers, the
    # serving front, prefetch threads, the telemetry watchdog) and the
    # armed fault hatch register with ONE shared inventory
    # (lightgbm_tpu/lifecycle.py) — the guard reads it here instead of
    # hand-enumerating per module, and graftlint C1 gates that every new
    # thread spawn site keeps registering.  Read BEFORE the disable
    # below (disable() disarms — and deregisters — the watchdog).
    from lightgbm_tpu import faults as _faults  # noqa: F401 — importing
    # registers its armed-hatch probe; without this a test that set
    # LGBM_TPU_FAULT_AT without ever importing faults would slip past
    # the guard and SIGKILL a LATER test's training loop
    from lightgbm_tpu import tracing as _tracing  # noqa: F401 — same
    # deal for the flight recorder (ISSUE 16): importing registers the
    # trace-recorder probe, so a test that leaves the recorder armed —
    # a later test's serving/training events silently filing into a
    # foreign ring and foreign percentile sketches — fails here and is
    # disarmed by the probe's closer (which also flushes any configured
    # dump dir)
    from lightgbm_tpu import lifecycle as _lifecycle
    leaked_objects = _lifecycle.leaks()
    for _kind, _name, _closer in leaked_objects:
        try:
            _closer()
        except Exception:
            pass
    telemetry.disable()
    telemetry.reset()
    # ISSUE 9 surface: a test that enters ``with mesh:`` and leaks it
    # (an exception before __exit__, a kept generator) leaves a global
    # mesh context installed — later tests' jit'd reductions silently
    # become GSPMD-partitioned over it, breaking the serial growers'
    # bit-identity pins in ways that only reproduce under THIS test
    # order.  The learners never install a global mesh (shard_map takes
    # the mesh explicitly), so any non-default mesh here is a leak.
    leaked_mesh = None
    from jax._src import mesh as _mesh_lib
    env_mesh = _mesh_lib.thread_resources.env.physical_mesh
    if not env_mesh.empty:
        leaked_mesh = env_mesh
        _mesh_lib.thread_resources.env = _mesh_lib.EMPTY_ENV
    assert not (leaked_enabled or leaked_sink or leaked_timeline
                or leaked_census or leaked_objects
                or leaked_mesh is not None), (
        "test left %s — clean up (telemetry.disable() / end_census() / "
        "close()/disarm the leaked object / exit the mesh context, or "
        "use a fixture) so state cannot leak between tests"
        % ("live lifecycle registrations: %s"
           % ", ".join(sorted("%s(%s)" % (k, n)
                              for k, n, _c in leaked_objects))
           if leaked_objects
           else "telemetry in timeline/shard mode" if leaked_timeline
           else "graftlint trace-census armed" if leaked_census
           else "telemetry enabled with an open sink" if leaked_sink
           else "telemetry enabled" if leaked_enabled
           else "a global mesh context installed (%r)" % (leaked_mesh,)))


@pytest.fixture(scope="session")
def reference_binary():
    """Compile the reference from source once per session (differential
    oracle, SURVEY §4); skip when source/toolchain are unavailable."""
    if os.path.exists(REFERENCE_BINARY):
        return REFERENCE_BINARY
    if not os.path.isdir(os.path.join(REFERENCE_SRC, "src")):
        pytest.skip("reference source not available")
    if shutil.which("cmake") is None or shutil.which("make") is None:
        pytest.skip("no native toolchain")
    shutil.copytree(REFERENCE_SRC, REFERENCE_BUILD, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".git", "windows"))
    bdir = os.path.join(REFERENCE_BUILD, "build")
    os.makedirs(bdir, exist_ok=True)
    try:
        subprocess.run(["cmake", "..", "-DCMAKE_BUILD_TYPE=Release"],
                       cwd=bdir, check=True, capture_output=True,
                       timeout=60)
        subprocess.run(["make", f"-j{os.cpu_count()}"], cwd=bdir,
                       check=True, capture_output=True, timeout=180)
    except subprocess.CalledProcessError as e:  # pragma: no cover
        pytest.skip(f"reference build failed: {e.stderr[-500:]}")
    assert os.path.exists(REFERENCE_BINARY)
    return REFERENCE_BINARY


@pytest.fixture(scope="session")
def binary_example_paths():
    base = os.path.join(REFERENCE_EXAMPLES, "binary_classification")
    if not os.path.isdir(base):
        pytest.skip("reference examples not available")
    return {
        "train": os.path.join(base, "binary.train"),
        "test": os.path.join(base, "binary.test"),
        "train_conf": os.path.join(base, "train.conf"),
        "predict_conf": os.path.join(base, "predict.conf"),
    }


@pytest.fixture()
def synthetic_binary():
    """Small deterministic binary-classification dataset."""
    rng = np.random.RandomState(7)
    n, f = 2000, 12
    x = rng.randn(n, f)
    logits = x[:, 0] * 1.5 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logits + rng.randn(n) * 0.5 > 0).astype(np.float32)
    return x, y


@pytest.fixture()
def synthetic_regression():
    rng = np.random.RandomState(11)
    n, f = 1500, 8
    x = rng.randn(n, f)
    y = (2.0 * x[:, 0] - x[:, 1] + 0.3 * x[:, 2] ** 2
         + rng.randn(n) * 0.1).astype(np.float32)
    return x, y
