"""Honest op timing of a single device op.

Dispatch-only timing lies (async), and per-call readback pays ~100ms RPC.
This harness chains R executions of an op inside ONE jitted fori_loop (each
iteration's input is perturbed by the carry so XLA cannot hoist the body),
reads back one scalar, and reports (T(R2) - T(R1)) / (R2 - R1).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def device_time(op, *args, reps=(2, 10), key_arg=0, readback=True):
    """Seconds per execution of op(*args), measured on-device.

    key_arg: index of a float array argument to perturb with the carry
    (keeps the loop body live across iterations).
    """

    def run(reps):
        @jax.jit
        def prog(eps, *a):
            def body(_, carry):
                a2 = list(a)
                a2[key_arg] = a2[key_arg] + (eps * carry).astype(
                    a2[key_arg].dtype)
                out = op(*a2)
                leaves = jax.tree_util.tree_leaves(out)
                s = sum(jnp.sum(l).astype(jnp.float32) for l in leaves)
                return carry + s * eps
            return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

        out = prog(jnp.float32(0.0), *args)   # compile+warm
        _ = np.asarray(out)
        t0 = time.perf_counter()
        out = prog(jnp.float32(0.0), *args)
        _ = np.asarray(out)
        return time.perf_counter() - t0

    r1, r2 = reps
    t1 = run(r1)
    t2 = run(r2)
    return (t2 - t1) / (r2 - r1)


if __name__ == "__main__":
    a = jnp.ones((8192, 8192), jnp.bfloat16)
    b = jnp.ones((8192, 8192), jnp.bfloat16)
    t = device_time(lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32),
                    a, b)
    print(f"8192^3 bf16 matmul: {t*1e3:.3f} ms -> {2*8192**3/t/1e12:.0f} TFLOP/s")
