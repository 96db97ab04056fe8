"""``split_find_hidden_ms_per_iter``: device time per traced iteration of
the operations under no scope that the program's own map
(``costmodel.op_phases``) gives to ``split_find``: the pieces of the
decomposed cumulative sums of the threshold scan."""
from harness import hidden


def read(state):
    return hidden.read(state, "split_find_hidden_ms_per_iter")
