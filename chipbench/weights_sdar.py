"""Seeded weights in ``deepspeed_tpu.models.sdar.SDARModel``'s tree layout.

As ``weights_olmoe.py``: the benchmark draws the values from ``--seed`` and
hands them to the program by overriding ``model.init``; the reference
(``reference_sdar.py``) is given the same tree. Norm gains (each head's q and
k norms' too) are random around 1 and the router is random with logits of
about unit spread, so that the top 8 of 128 are a real choice. The row of
the embedding table at ``mask_token_id`` is a row like any other.
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
    h, hk, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    e, f = dims["experts"], dims["expert_ff"]
    std = 0.02
    proj_std = std / math.sqrt(2 * l)
    ks = iter(jax.random.split(key, 16))

    def n(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    blocks = {
        "ln1_scale": 1.0 + n((l, d), 0.1),
        "qkv_w": n((l, d, (h + 2 * hk) * hd), std),
        "q_norm_scale": 1.0 + n((l, hd), 0.1),
        "k_norm_scale": 1.0 + n((l, hd), 0.1),
        "attn_proj_w": n((l, h * hd, d), proj_std),
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
