"""Traffic kind ``train_sharded``: the ``train`` kind's job, unchanged, on
a configuration whose learner shares the table's rows among the cell's
chips.

Its result differs in one field: ``shape.rows`` is the rows that ONE chip
holds, ceil(rows / chips), with ``chips`` from the cell's entry in
``BENCHMARK.json``, and ``shape.chips`` says how many.  The readers that set
one chip's floor against a device time averaged over the chips' planes
(``hist_roofline``, ``train_step_mfu``) then read the job over the four
chips' peaks, where the whole table's rows would read four times too high.
"""
from __future__ import annotations

from traffic import train


def run(ctx) -> dict:
    result = train.run(ctx)
    chips = int({w["name"]: w for w in ctx.bench["workloads"]}
                [ctx.args.workload]["chips"])
    result["shape"] = dict(result["shape"], chips=chips,
                           rows=-(-result["shape"]["rows"] // chips))
    return result
