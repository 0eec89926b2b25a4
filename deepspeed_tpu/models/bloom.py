"""BLOOM family — ALiBi attention, embedding LayerNorm, no position table.

Capability match for the reference's BLOOM support (module_inject/
containers/bloom.py BLOOMLayerPolicy, model_implementations/transformers/
ds_bloom.py). The block structure is GPT-2's (fused qkv + gelu MLP), so the
TPU model subclasses the stacked-scan GPT2Model and overrides only the
family hooks: token embeddings are followed by a LayerNorm instead of a
position table, and attention logits get the ALiBi distance bias.

ALiBi here exploits softmax shift invariance: HF adds
``slope_h * (k - q)`` per row; a per-row constant shift leaves softmax
unchanged, so ``slope_h * k`` (key-position only) is equivalent and needs no
query-position dependence — one [1, H, 1, T] bias for both train and decode.
"""

import dataclasses
import math

import jax.numpy as jnp

from .gpt2 import GPT2Config, GPT2Model, _layer_norm


@dataclasses.dataclass(frozen=True)
class BloomConfig(GPT2Config):
    vocab_size: int = 250880
    activation: str = "gelu"


BLOOM_560M = BloomConfig(n_embd=1024, n_layer=24, n_head=16)
BLOOM_7B = BloomConfig(n_embd=4096, n_layer=30, n_head=32)


def alibi_slopes(n_heads: int):
    """Per-head ALiBi slopes (HF transformers build_alibi_tensor layout)."""
    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return pow2(n_heads)
    closest = 2 ** math.floor(math.log2(n_heads))
    return (pow2(closest) +
            pow2(2 * closest)[0::2][: n_heads - closest])


class BloomModel(GPT2Model):

    has_position_table = False

    def __init__(self, config: BloomConfig = BLOOM_560M):
        super().__init__(config)
        self._slopes = jnp.asarray(alibi_slopes(config.n_head),
                                   dtype=jnp.float32)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        params = super().init(rng)
        del params["wpe"]                       # ALiBi: no position table
        params["emb_ln_scale"] = jnp.ones((cfg.n_embd,))
        params["emb_ln_bias"] = jnp.zeros((cfg.n_embd,))
        return params

    # ------------------------------------------------- family hook overrides
    def _embed(self, params, input_ids, start_pos=0, positions=None):
        # ALiBi: per-row position shifts are softmax-invariant (row-constant
        # bias), so positions are ignored here too
        x = params["wte"].astype(self._compute_dtype(params))[input_ids]
        return _layer_norm(x, params["emb_ln_scale"], params["emb_ln_bias"],
                           self.config.layer_norm_epsilon)

    def _train_attn_bias(self, t):
        # [1, H, 1, t]: slope_h * key_position (row-shift-equivalent to HF's
        # slope_h * (k - q))
        return (self._slopes[None, :, None, None] *
                jnp.arange(t, dtype=jnp.float32)[None, None, None, :])

    def _decode_attn_bias(self, q_pos, k_pos):
        # [1, H, 1, max_len] (k_pos arrives [1, 1, 1, max_len])
        return self._slopes[None, :, None, None] * k_pos.astype(jnp.float32)
