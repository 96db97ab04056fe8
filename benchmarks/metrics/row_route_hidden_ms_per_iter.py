"""``row_route_hidden_ms_per_iter``: device time per traced iteration of the
operations under no scope that the program's own map
(``costmodel.op_phases``) gives to ``row_route``: the first fill of a
tree's per-row slot and leaf ids, a bare ``broadcast`` over every row."""
from harness import hidden


def read(state):
    return hidden.read(state, "row_route_hidden_ms_per_iter")
