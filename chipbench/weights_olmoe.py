"""Seeded weights in ``deepspeed_tpu.models.olmoe.OLMoEModel``'s tree layout.

As ``weights.py`` for the GPT-2 tree: the benchmark draws the values from
``--seed`` and hands them to the program by overriding ``model.init``; the
reference (``reference_olmoe.py``) is given the same tree. Norm gains (the
q and k norms' too) are random around 1 and the router is random: ones or
zeros would hide a dropped norm or a router that is never read. The
router's scale makes the softmax over the experts uneven enough that the
top k are a real choice (logits of about unit spread).
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import engine_seed, seed_key      # noqa: F401


def table_rows(dims, vocab_multiple=128):
    return -(-dims["vocab"] // vocab_multiple) * vocab_multiple


def make(dims, key, positions=None, vocab_multiple=128):
    """float32 parameters in the program's tree layout, on the default
    device(s); jit it with ``out_shardings`` to make them sharded from birth."""
    d, l = dims["d_model"], dims["layers"]
    e, f = dims["experts"], dims["expert_ff"]
    std = 0.02
    proj_std = std / math.sqrt(2 * l)
    ks = iter(jax.random.split(key, 16))

    def n(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    blocks = {
        "ln1_scale": 1.0 + n((l, d), 0.1),
        "qkv_w": n((l, d, 3 * d), std),
        "q_norm_scale": 1.0 + n((l, d), 0.1),
        "k_norm_scale": 1.0 + n((l, d), 0.1),
        "attn_proj_w": n((l, d, d), proj_std),
        "ln2_scale": 1.0 + n((l, d), 0.1),
        "moe": {
            "gate": {"wg": n((l, d, e), 1.0 / math.sqrt(d))},
            "experts": {"w_gate": n((l, e, d, f), std),
                        "w_up": n((l, e, d, f), std),
                        "w_down": n((l, e, f, d), proj_std)},
        },
    }
    rows = table_rows(dims, vocab_multiple)
    return {
        "wte": n((rows, d), std),
        "blocks": blocks,
        "ln_f_scale": 1.0 + n((d,), 0.1),
        "lm_head": n((rows, d), std),
    }
