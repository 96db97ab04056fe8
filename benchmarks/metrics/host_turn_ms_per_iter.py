"""``host_turn_ms_per_iter``: host seconds of the program's spans
``model_readback`` + ``tree_build`` over the iterations the program itself
counted (``train/iterations``), whole process (warm-up slices included:
they run the same host turn)."""
from harness import registry


def read(_state):
    readback, build = (registry.span_s("model_readback"),
                       registry.span_s("tree_build"))
    if readback is None or build is None:
        return None
    return registry.over((readback + build) * 1e3,
                         registry.counter("train/iterations"))
