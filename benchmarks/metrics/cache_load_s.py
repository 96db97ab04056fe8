"""``cache_load_s``: seconds set-up spent loading programs from the
persistent compile cache (the listener's ``trace_times`` key
``cache_load``); 0 in a run that found nothing there and compiled
everything (``compile_s``).  Set-up's alone, or nothing, as ``compile_s``."""
from harness import registry


def read(state):
    return registry.build_seconds(state, ("cache_load",))
