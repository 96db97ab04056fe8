"""The least time one chip could take for one boosting iteration's work.

The work is the algorithm's, counted from shapes, whatever implements it
(PERF.md section 3): every one of ceil(log2(num_leaves)) tree levels has to
read each row's F bin codes (1 byte each) and its gradient pair (8 bytes)
once, and add each row's pair into F histogram cells (2 additions each).
The one-hot kernel's N*F*B*128 multiply-adds are an implementation's, not
the algorithm's, and are not counted.
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)
    if not isinstance(table.get(device_kind), dict):
        raise SystemExit("benchmarks: device kind %r is not in %s; add its "
                         "published peaks there" % (device_kind, PEAKS_FILE))
    return table[device_kind]


def iteration_work(rows: int, features: int, num_leaves: int) -> dict:
    levels = max(1, math.ceil(math.log2(max(num_leaves, 2))))
    return {"levels": levels,
            "bytes": levels * rows * (features + 8),
            "ops": levels * rows * features * 2}


def least_seconds(work: dict, peaks: dict) -> dict:
    """The roofline's floor and which side binds."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}
