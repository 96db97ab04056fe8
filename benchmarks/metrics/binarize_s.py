"""``binarize_s``: seconds under the program's span ``binarize``, a child
of ``dataset_bin``: one ``searchsorted`` per used column, on one thread."""
from harness import registry


def read(_state):
    return registry.span_s("binarize")
