"""Off the chip the runner reports nothing: with no ``--rows`` it stops at
once, with ``--rows`` it rehearses every phase and still prints no result
line and exits 2."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def run(*argv, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *argv],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell", CELLS)
def test_no_chip_no_result(cell):
    done = run("--workload", cell, "--seed", "3000000019", "--seconds", "1",
               "--trace", "0")
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_rehearsal_runs_every_phase_and_still_refuses():
    done = run("--workload", "higgs-levelwise-int8.train", "--seed",
               "3000000019", "--seconds", "1", "--trace", "0", "--rows",
               "4096")
    assert done.returncode == 2, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    assert "check score_gap" in done.stderr
    assert "correct=" in done.stderr
    assert "no result line" in done.stderr


def test_unknown_cell_is_refused():
    done = run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert done.returncode == 2 and done.stdout.strip() == ""
