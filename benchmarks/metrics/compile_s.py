"""``compile_s``: seconds set-up spent BUILDING programs, from the
program's one ``jax.monitoring`` listener: ``trace_times`` of
``jaxpr_trace`` + ``lower`` + ``backend_compile``.  Loads from the
persistent compile cache are ``cache_load_s``, beside it.

It is the TRACED run's set-up, as every per-layer metric is.  A traced
run finds in the cache what an earlier timed run of the same checkout
compiled (one program traced or not, PR 27), so where ``cache_load_s`` is
above 0 this reads the trace + lower of a warm start and a few small
compiles (7-8 s in the cell), not the cold compile inside a timed run's
``setup_s`` (30 s).  Set-up's alone, or nothing: ``registry.build_seconds``."""
from harness import registry


def read(state):
    return registry.build_seconds(
        state, ("jaxpr_trace", "lower", "backend_compile"))
