"""``correct`` comes out false when it should: for the control (the
reference's answer in the next lower precision, put in the program's place)
and for each fault the cell can have, planted under a run that is otherwise
whole.  Small tables on the CPU; the readings the limits were set from are
chip runs at the cell's own size (PERF.md section 2)."""
import argparse

import numpy as np
import pytest

import run as runner

CELL = "higgs-levelwise-int8.train"
ROWS = 8192


def args(**kw):
    base = dict(workload=CELL, seed=3000000019, seconds=0.5, trace=0,
                rows=ROWS, control=0)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.fixture(scope="module")
def sound():
    line, code = runner.execute(args(control=1), require_chip=False)
    assert code == 0
    return line


def test_a_sound_run_is_correct(sound):
    # so that no fault below comes out not correct for nothing.  The limits
    # are the cell's, set at 10.5M rows; at 8k rows the quantisation noise
    # of a node is larger and some seeds read over them: this one does not
    assert sound["correct"] is True, sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] >= 8


def test_the_control_comes_out_not_correct(sound):
    # by the harness's own comparison, number by number against the cell's
    # limits, and not by a comparison of this test's
    assert sound["control_correct"] is False
    assert any(c["limit"] is not None and c["value"] > c["limit"]
               for name, c in sound["checks"].items()
               if name.startswith("control."))


def test_each_fault_read_beside_the_control_comes_out_not_correct(sound):
    assert sound["faults_correct"] == {"half_batch": False,
                                       "state_unchanged": False}


def state_unchanged(point, job=None, **_kw):
    """A step that returns its state unchanged: every slice grows its trees
    and then puts the score back."""
    if point != "job":
        return
    real = job.slice

    def slice_without_update():
        before = job.booster.score
        real()
        job.booster.score = before
    job.slice = slice_without_update


def half_batch(point, job=None, **_kw):
    """Half of the rows left out of every histogram, the leaf values taken
    over the rest: the program's own bagging switched on underneath."""
    if point != "job":
        return
    booster = job.booster
    booster.gbdt_config.bagging_fraction = 0.5
    booster.gbdt_config.bagging_freq = 1
    booster._use_bagging = True
    booster._bag_device = False


def altered_answer(point, trees=None, **_kw):
    """One leaf value altered where the model is read back."""
    if point != "answers":
        return
    trees[len(trees) // 2].leaf_value[1] += 0.05


def wild_leaf(point, job=None, trees=None, score_after=None, **_kw):
    """One leaf of the window's last tree sixteen times the median leaf
    away, in the tree and in the device's score alike: what wrong histogram
    sums leave behind (PERF.md, Open questions: the float32 route)."""
    if point != "answers":
        return
    from harness import reference
    last = trees[-1]
    leaf = reference.route(last, job.bins)
    small = int(np.argmin(np.bincount(leaf, minlength=last.num_leaves)))
    delta = 16 * float(np.median(np.abs(last.leaf_value)))
    last.leaf_value[small] += delta
    score_after[leaf == small] += delta


def altered_split(point, trees=None, **_kw):
    """One threshold of the window's first tree moved where it is read
    back."""
    if point != "answers":
        return
    trees[0].threshold_bin[0] = (trees[0].threshold_bin[0] + 40) % 200


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   altered_answer, altered_split, wild_leaf])
def test_a_broken_timed_path_is_not_correct(fault):
    line, code = runner.execute(args(), require_chip=False, fault=fault)
    assert code == 0
    assert line["correct"] is False, (fault.__name__, line["checks"])


def test_only_the_tail_number_sees_the_wild_leaf():
    line, _code = runner.execute(args(), require_chip=False, fault=wild_leaf)
    over = [name for name, c in line["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over == ["leaf_sum_gap"], line["checks"]


def test_codes_of_half_as_many_bins_are_not_correct():
    def coarse(point, job=None, **_kw):
        if point == "answers":
            job.bins //= 2
    line, _code = runner.execute(args(), require_chip=False, fault=coarse)
    c = line["checks"]["bin_code_gap"]
    assert c["value"] > c["limit"] and line["correct"] is False
