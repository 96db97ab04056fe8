"""``histogram_hidden_ms_per_iter``: device time per traced iteration of the
operations under no scope that the program's own map
(``costmodel.op_phases``) gives to ``histogram`` or ``gradient``: the int8
quantise prologue of each pass and the gradients fused into the first."""
from harness import hidden


def read(state):
    return hidden.read(state, "histogram_hidden_ms_per_iter")
