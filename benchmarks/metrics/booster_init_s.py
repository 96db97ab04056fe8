"""``booster_init_s``: seconds under the program's span ``booster_init``
(``GBDT.init``: score and metadata set-up, layout decisions, and the bin
table's placement on the device, which ``h2d_gb_per_s`` rates)."""
from harness import registry


def read(_state):
    return registry.span_s("booster_init")
