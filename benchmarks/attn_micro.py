"""Microbench flash-attention variants on the real chip.

Times are amortized over a lax.scan inside one jit (see _timing.py) and
all outputs are consumed into the carry
so XLA cannot DCE or hoist anything.
"""

import functools
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from _timing import timed, timed_grad

B, H, T, D = 8, 12, 1024, 64


def main():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)

    from deepspeed_tpu.ops.pallas import flash_attention as fa

    flops_fwd = 4 * B * H * T * T * D / 2  # causal
    print(f"causal fwd ideal @197T: {flops_fwd/197e12*1e3:.3f} ms")

    # current default
    ms = timed(lambda q, k, v: fa.flash_attention(q, k, v, True), q, k, v)
    print(f"pallas fwd default (bq512 bk256): {ms:.3f} ms  "
          f"({flops_fwd/ms/1e9:.1f} TFLOPs)")

    for bq, bk in ((256, 256), (128, 128), (512, 512), (1024, 256),
                   (256, 512)):
        try:
            ms = timed(lambda q, k, v, bq=bq, bk=bk: fa.flash_attention(
                q, k, v, True, None, bq, bk), q, k, v)
            print(f"pallas fwd bq{bq} bk{bk}: {ms:.3f} ms")
        except Exception as e:
            print(f"pallas fwd bq{bq} bk{bk}: FAIL {type(e).__name__}")

    # XLA reference
    from deepspeed_tpu.ops.flash_attention import reference_attention
    ms = timed(lambda q, k, v: reference_attention(q, k, v, causal=True),
               q, k, v)
    print(f"xla reference fwd: {ms:.3f} ms")

    # grads
    ms = timed_grad(lambda q, k, v: fa.flash_attention(q, k, v, True),
                    q, k, v)
    print(f"pallas fwd+bwd (grad wrt q): {ms:.3f} ms")
    ms = timed_grad(lambda q, k, v: reference_attention(q, k, v, causal=True),
                    q, k, v)
    print(f"xla fwd+bwd (grad wrt q): {ms:.3f} ms")


if __name__ == "__main__":
    main()
