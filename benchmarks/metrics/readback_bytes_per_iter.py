"""``readback_bytes_per_iter``: the program's counter
``train/readback_bytes`` (device to host bytes of the model readback:
the stacked trees and in-program metric values) over ``train/iterations``."""
from harness import registry


def read(_state):
    return registry.over(registry.counter("train/readback_bytes"),
                         registry.counter("train/iterations"))
