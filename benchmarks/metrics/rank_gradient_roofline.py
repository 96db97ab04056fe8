"""``rank_gradient_roofline``: the least time the chip could take for one
iteration's lambdarank gradients over ``rank_gradient_ms_per_iter``.

The work is the deployment's own pairs, whatever implements them: the
program's trace-time counter ``rank/pairs`` (Σ q² over the queries, every
ordered pair of a query's documents), not the slots a layout computes, so
a layout that wastes fewer slots reads higher.  A pair costs
``OPS_PER_PAIR`` operations, counting an exponential and a division as one
each: the score difference, its magnitude and the regularising 0.01 + |Δs|
(3), the gain difference, the discount difference and its magnitude (3),
ΔNDCG's two products and its division (3), the sigmoid 2 / (1 + exp(2σΔs))
(4), σ(2 − σ) (2), λ and the hessian term (3), the grade comparison (1),
and the four accumulations into the high and the low document's sums (4):
23.  A document costs ``BYTES_PER_DOC`` bytes of memory: its score and
grade read, its lambda and hessian written, 4 bytes each.  The floor is
the larger of the two at the chip's published peaks
(``harness/peaks.json``; the operations at its bfloat16 rate, which
elementwise float32 work on the vector unit does not reach)."""
from __future__ import annotations

OPS_PER_PAIR = 23
BYTES_PER_DOC = 16


def read(state):
    base = state.values.get("rank_gradient_ms_per_iter")
    pairs = state.counters.get("rank/pairs")
    if not base or not pairs or not state.peaks:
        return None
    least = max(pairs * OPS_PER_PAIR / state.peaks["bf16_flops_per_s"],
                state.shape["rows"] * BYTES_PER_DOC
                / state.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (base * 1e-3)
