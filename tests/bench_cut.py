"""One run of the benchmark's own harness on a cell cut to a size the CPU
takes in a minute: what ``tests/test_wide_table.py`` and
``tests/test_leafwise_wide.py`` both drive.  This module holds no test.
"""
import argparse
import contextlib
import os
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cut_cell(cell, seed, rows, columns, fence=False, fault=None,
                 tpu_routes=True, params=None):
    """(result line, route counters) of ``benchmarks/run.py`` on ``cell``
    at ``rows`` x ``columns`` with ``--control 1``: its traffic kind builds
    the booster as the CLI does, drives ``run_training`` in slices of 8 and
    hands the trees, the scores and the binned table to the reference.  The
    routing is steered onto its TPU branch, the Pallas kernels run by the
    interpreter; the registry is on so that the route can be read back.

    ``fence``: the registry's spans wait for their device work.  The
    per-tree loop needs it here: the interpreter runs a kernel's steps as
    host callbacks that themselves dispatch JAX operations, and a host
    that goes on to its next eager operation while the tree's program is
    still in those callbacks can deadlock the CPU client (seen twice under
    six xdist workers, never alone).  The fused chunk program has no
    eager operation beside it.

    ``fault``: the harness's hook into the traffic kind
    (``run.Context.fault``), called with the job and, after the window,
    with its trees.

    ``tpu_routes=False`` leaves the routing on its CPU branch (the XLA
    histogram and routing, no interpreter): the TPU interpreter's kernels
    under ``shard_map`` wait on a barrier of all devices at every call,
    which the CPU client does not always reach (seen once in three runs
    of the four-device cell).  ``params``: ``key=value`` pairs laid over
    the configuration's."""
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.utils import log
    added = [p for p in (os.path.join(ROOT, "benchmarks"), ROOT)
             if p not in sys.path]
    sys.path[:0] = added
    import run as runner
    real_load = runner.load_json

    def cut(*parts):
        loaded = real_load(*parts)
        if parts[0] == "configs":
            loaded = dict(loaded, features=columns,
                          params=dict(loaded["params"], **(params or {})))
        elif parts[0] == "cells":
            # the first slice compiles inside the window; nothing judged
            # reads the clock
            loaded = dict(loaded, params=dict(loaded["params"],
                                              warmup_slices=0))
        return loaded
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=0, rows=rows, control=1)
    mp = pytest.MonkeyPatch()
    mp.setattr(runner, "load_json", cut)
    if tpu_routes:
        mp.setattr(jax, "default_backend", lambda: "tpu")
    # the TPU interpreter keeps the partition kernels' aliased pane in
    # the call's own buffer only as an ANY argument (compact.PANE_SPACE)
    from jax.experimental import pallas as pl
    from lightgbm_tpu.ops import compact
    mp.setattr(compact, "PANE_SPACE", pl.ANY)
    telemetry.reset()
    telemetry.enable(fence=fence)
    try:
        with (pltpu.force_tpu_interpret_mode() if tpu_routes
              else contextlib.nullcontext()):
            line, code = runner.execute(args, require_chip=False,
                                        fault=fault)
        counters = dict(telemetry.snapshot()["counters"])
    finally:
        telemetry.disable()
        telemetry.reset()
        mp.undo()
        log.set_stream(None)        # the runner sends the log to stderr
        for p in added:
            sys.path.remove(p)
    assert code == 0
    return line, counters
