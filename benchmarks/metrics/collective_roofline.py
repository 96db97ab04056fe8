"""``collective_roofline``: the least time the chips' interconnect could take
to move one iteration's collectives over ``collective_ms_per_iter``.

The bytes are what a ring over S chips sends from each chip for the payload
the program files at trace time, by kind (``collective/<kind>_bytes_per_tree``
times ``collective/trees_per_iter``, S from ``collective/shards``): an
all-reduce (``psum``, ``pmax``) 2(S - 1)/S of its payload, a reduce-scatter
(``psum_scatter``) (S - 1)/S of its payload, the whole operand before the
scatter, and an all-gather S - 1 times its payload, which is one chip's
part.  The interconnect moves ``ICI_BYTES_PER_S`` a chip: TPU v5e, the
benchmark's chip, 1,600 Gbps of inter-chip interconnect a chip (Google
Cloud documentation, "TPU v5e" system architecture page; written from
memory, no network).  ``harness/peaks.json`` holds the HBM and compute
peaks and not this one, so it lives here."""

ICI_BYTES_PER_S = 1600e9 / 8

RING = {
    "psum": lambda s: 2.0 * (s - 1) / s,
    "pmax": lambda s: 2.0 * (s - 1) / s,
    "psum_scatter": lambda s: (s - 1) / s,
    "all_gather": lambda s: float(s - 1),
}
PREFIX, SUFFIX = "collective/", "_bytes_per_tree"


def ring_bytes(bytes_by_kind: dict, shards: int):
    """Bytes one chip sends for ``{kind: payload bytes}`` over a ring of
    ``shards`` chips; None for a kind this file does not price."""
    if any(kind not in RING for kind in bytes_by_kind):
        return None
    return sum(RING[kind](shards) * nbytes
               for kind, nbytes in bytes_by_kind.items())


def read(state):
    base = state.values.get("collective_ms_per_iter")
    shards = state.counters.get("collective/shards")
    trees = state.counters.get("collective/trees_per_iter")
    by_kind = {name[len(PREFIX):-len(SUFFIX)]: value * (trees or 0)
               for name, value in state.counters.items()
               if name.startswith(PREFIX) and name.endswith(SUFFIX)
               and name != PREFIX + "bytes" + SUFFIX}
    if not base or not shards or not trees or not by_kind:
        return None
    moved = ring_bytes(by_kind, int(shards))
    if moved is None:
        return None
    return 100.0 * (moved / ICI_BYTES_PER_S) / (base * 1e-3)
