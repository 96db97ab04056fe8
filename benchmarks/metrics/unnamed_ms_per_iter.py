"""``unnamed_ms_per_iter``: device time per traced iteration of the
operations under no scope that the program's own map
(``costmodel.op_phases``) has no entry for, or gives to a phase that has no
``*_hidden_ms_per_iter`` of its own: what nobody could name.  With the
``*_hidden`` metrics and ``xla_inserted_ms_per_iter`` it is
``unscoped_ms_per_iter``."""
from harness import hidden


def read(state):
    return hidden.read(state, hidden.UNNAMED)
