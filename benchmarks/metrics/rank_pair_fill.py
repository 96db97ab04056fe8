"""``rank_pair_fill``: the share of the pair slots the lambdarank gradient
computes that are real pairs, from the program's two trace-time counters:
``rank/pairs`` (Σ q² over the queries) over ``rank/pair_slots`` (Σ over
the length classes of blocks · queries a block · width²).  A function of
the query lengths alone, so every seed of a cell reads the same.  A
program without the counters reads nothing."""
from __future__ import annotations


def read(state):
    pairs = state.counters.get("rank/pairs")
    slots = state.counters.get("rank/pair_slots")
    if not pairs or not slots:
        return None
    return pairs / slots
