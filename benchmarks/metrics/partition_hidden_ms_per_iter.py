"""``partition_hidden_ms_per_iter``: device time per traced iteration of the
operations under no scope that the program's own map
(``costmodel.op_phases``) gives to ``partition``: the table's int8 view for
the pane's packing, once a tree."""
from harness import hidden


def read(state):
    return hidden.read(state, "partition_hidden_ms_per_iter")
