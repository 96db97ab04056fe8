"""``binarize_mvalues_per_s``: the program's counter ``bin/values`` (rows x
used columns quantized) over its span ``binarize``, in millions a second:
``binarize_s`` by the work it did, so a table of another size reads alike."""
from harness import registry


def read(_state):
    return registry.over(registry.counter("bin/values"),
                         (registry.span_s("binarize") or 0.0) * 1e6)
