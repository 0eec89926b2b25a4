"""Seeded weights, made by the benchmark and handed to the program.

The program's engines call ``model.init(PRNGKey(config seed))`` to make their
parameters; ``chipbench.model.build`` overrides that one method with ``make``
below and the job hands the engine ``engine_seed(--seed)`` as its config
seed, so the key reaches ``make`` as a run-time argument (one compiled
program serves every seed) and the system under test and the plain reference
(``reference.py``) both start from values the benchmark drew from ``--seed``:
the reference takes nothing the program has made. The tree layout (``wte``, ``wpe``, stacked
``blocks`` leaves) is the program's parameter interface and the only thing
taken from it.

Biases and LayerNorm gains are random too (a program that drops one would
pass on zeros and ones).
"""

import math

import jax
import jax.numpy as jnp


def engine_seed(seed: int) -> int:
    """Any non-negative whole number (seeds run past 2**31) folded into the
    31 bits an engine's config seed holds."""
    seed = int(seed)
    return (seed ^ ((seed >> 31) * 0x9E3779B1)) & 0x7FFFFFFF


def seed_key(seed: int):
    """The key an engine given ``engine_seed(seed)`` passes to ``init``."""
    return jax.random.PRNGKey(engine_seed(seed))


def table_rows(dims, vocab_multiple=128):
    return -(-dims["vocab"] // vocab_multiple) * vocab_multiple


def make(dims, key, positions=None, vocab_multiple=128):
    """float32 parameters in the program's tree layout, on the default
    device(s); jit it with ``out_shardings`` to make them sharded from birth."""
    d, l, ff = dims["d_model"], dims["layers"], dims["d_ff"]
    npos = (positions or dims["positions"]) + dims["pos_offset"]
    std = 0.02
    proj_std = std / math.sqrt(2 * l)
    ks = iter(jax.random.split(key, 16))

    def n(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    blocks = {
        "ln1_scale": 1.0 + n((l, d), 0.1), "ln1_bias": n((l, d), std),
        "qkv_w": n((l, d, 3 * d), std), "qkv_b": n((l, 3 * d), std),
        "attn_proj_w": n((l, d, d), proj_std), "attn_proj_b": n((l, d), std),
        "ln2_scale": 1.0 + n((l, d), 0.1), "ln2_bias": n((l, d), std),
        "mlp_fc_w": n((l, d, ff), std), "mlp_fc_b": n((l, ff), std),
        "mlp_proj_w": n((l, ff, d), proj_std), "mlp_proj_b": n((l, d), std),
    }
    return {
        "wte": n((table_rows(dims, vocab_multiple), d), std),
        "wpe": n((npos, d), std),
        "blocks": blocks,
        "ln_f_scale": 1.0 + n((d,), 0.1), "ln_f_bias": n((d,), std),
    }
