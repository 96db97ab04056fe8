"""``h2d_gb_per_s``: the program's counter ``init/h2d_bytes`` (the bin
table ``GBDT.init`` places) over its span ``h2d`` (the placement, waited
for), in GB a second.  The span's seconds are the bytes over this."""
from harness import registry


def read(_state):
    return registry.over(registry.counter("init/h2d_bytes"),
                         (registry.span_s("h2d") or 0.0) * 1e9)
