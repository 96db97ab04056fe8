"""``iters_per_chunk``: the program's counter ``train/iterations`` over
``train/chunks``: iterations per dispatched program, whole process.  8 on
the fused route; a run that fell to the per-iteration path counts no
chunk and reads nothing."""
from harness import registry


def read(_state):
    return registry.over(registry.counter("train/iterations"),
                         registry.counter("train/chunks"))
