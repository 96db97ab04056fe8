"""``binning_s``: seconds under the program's span ``dataset_bin`` (the
row sample, ``find_bins_s`` and ``binarize_s``).  The traced run's own
set-up, like every metric that moves ``setup_s``."""
from harness import registry


def read(_state):
    return registry.span_s("dataset_bin")
