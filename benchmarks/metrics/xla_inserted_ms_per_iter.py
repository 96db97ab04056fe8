"""``xla_inserted_ms_per_iter``: device time per traced iteration of the
operations under no scope that the program's own map
(``costmodel.op_phases``) labels ``xla``: what the compiler put in itself,
copies between two layouts or two memories and in front of a conditional."""
from harness import hidden


def read(state):
    return hidden.read(state, "xla_inserted_ms_per_iter")
