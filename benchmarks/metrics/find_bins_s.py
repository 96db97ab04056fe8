"""``find_bins_s``: seconds under the program's span ``find_bins``, a
child of ``dataset_bin``: the cut points of every column from the row
sample (``BinMapper.find_bin``)."""
from harness import registry


def read(_state):
    return registry.span_s("find_bins")
