"""Shared scan-amortized timing harness for on-chip microbenches.

A dispatched jit call has a fixed host cost, so a microbench amortizes
over a lax.scan of N iterations inside ONE jit. The scalar carry
is mixed into every operand (and cast back to the operand dtype — bf16+f32
promotes!) so XLA can neither hoist the op out of the loop nor DCE it, and
all outputs are consumed into the carry.

Caveat: wall-clock still includes the consume/shift overhead of every
iteration — treat absolute numbers as upper bounds and prefer
trace-based self-times (benchmarks/profile_step.py) for per-op
attribution.
"""

import time

import jax
import jax.numpy as jnp
from jax import lax


def timed(fn, *args, iters=50):
    """ms per iteration of fn(*args)."""

    @jax.jit
    def run(args):
        def body(c, _):
            out = fn(*[(a + c).astype(a.dtype) for a in args])
            return jnp.sum(out.astype(jnp.float32)) * 1e-9, None
        c, _ = lax.scan(body, jnp.float32(0), None, length=iters)
        return c

    r = run(args)
    float(r)
    t0 = time.perf_counter()
    r = run(args)
    float(r)
    return (time.perf_counter() - t0) / iters * 1e3


def timed_grad(fn, *args, iters=50):
    """ms per iteration of grad(sum(fn))(*args) wrt the first arg."""

    @jax.jit
    def run(args):
        def body(c, _):
            shifted = [(a + c).astype(a.dtype) for a in args]
            g = jax.grad(lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32)))(
                *shifted)
            return jnp.sum(g.astype(jnp.float32)) * 1e-9, None
        c, _ = lax.scan(body, jnp.float32(0), None, length=iters)
        return c

    r = run(args)
    float(r)
    t0 = time.perf_counter()
    r = run(args)
    float(r)
    return (time.perf_counter() - t0) / iters * 1e3
