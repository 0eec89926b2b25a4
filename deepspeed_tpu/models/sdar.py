"""SDAR — a routed LLaMA-shaped decoder that generates by diffusion over
blocks.

JetLM/SDAR-30B-A3B-Chat (``model_type`` sdar_moe; SDAR, arXiv:2510.06303).
The layer is the Qwen3-MoE one: RMSNorm, grouped-query attention (32 query
heads over 4 KV heads of ``head_dim`` 128, which is not ``n_embd / n_head``),
an RMSNorm with one gain of ``head_dim`` over EACH head's channels of q and
of k before the rotary (rotate-half, ``rope_theta`` 1e6), no biases; 128
SwiGLU experts of width 768 of which a token takes 8, softmax over all 128
in float32 and the picked 8 renormalised (``norm_topk_prob``), no shared
expert, nothing dropped; untied head.

What makes it SDAR is how it generates. The sequence is cut into blocks of
``block_length`` positions, and a position attends every position of its
OWN and of earlier blocks: ``k_pos // B <= q_pos // B`` (``_decode_attn_mask``
on the cache path, ``_train_attn_bias`` without a cache). A new block starts
as B ``[MASK]`` positions (``mask_token_id``, a row of the embedding table
like any other) and is denoised in place over a few forward passes, each
fixing the most confident of the still-masked positions from the logits AT
those positions (no shift: a masked position predicts its own token); when
none is masked one more pass writes the block's final keys and values. The
serving tick's pass over a pool is ``InferenceEngine.slot_block_dispatch``;
the schedule of passes is the scheduler's (``serving/scheduler.py``).

Built on ``OLMoEModel`` (the routed MLP, the expert leaves stacked
``[L, E, ...]``); with ``block_length`` 1 the mask is the causal one and
every program is ``LlamaModel``'s.
"""

import dataclasses

import jax.numpy as jnp

from .llama import _rms_norm
from .olmoe import OLMoEConfig, OLMoEModel


@dataclasses.dataclass(frozen=True)
class SDARConfig(OLMoEConfig):
    vocab_size: int = 151936
    n_positions: int = 32768
    n_embd: int = 2048
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128                 # not n_embd / n_head: 32 x 128 = 4096
    mlp_hidden: int = 768               # width of ONE expert
    num_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    rope_theta: float = 1000000.0
    layer_norm_epsilon: float = 1e-6
    #: positions a block holds; 1: plain next-token decoding
    block_length: int = 4
    #: the id whose embedding row stands at a position not yet denoised
    mask_token_id: int = 151669


SDAR_30B_A3B = SDARConfig()


class SDARModel(OLMoEModel):

    def __init__(self, config: SDARConfig = SDAR_30B_A3B):
        if config.block_length < 1:
            raise ValueError("block_length must be at least 1")
        if config.block_length > 1 and not \
                0 <= config.mask_token_id < config.vocab_size:
            raise ValueError(
                f"mask_token_id {config.mask_token_id} is no row of a "
                f"vocabulary of {config.vocab_size}")
        super().__init__(config)

    # ---------------------------------------------------------- the blocks
    @property
    def block_length(self) -> int:
        return self.config.block_length

    @property
    def mask_token_id(self) -> int:
        return self.config.mask_token_id

    @property
    def denoised_blocks(self):
        return ("k", "v") if self.block_length > 1 else ()

    @property
    def causal_attention(self) -> bool:
        return self.block_length == 1

    def _decode_attn_mask(self, q_pos, k_pos):
        b = self.block_length
        if b == 1:
            return super()._decode_attn_mask(q_pos, k_pos)
        return k_pos // b <= q_pos // b

    def _train_attn_bias(self, t):
        b = self.block_length
        if b == 1:
            return None
        pos = jnp.arange(t) // b
        return jnp.where(pos[None, :] <= pos[:, None], 0.0, -1e30)[None]

    @property
    def _rows_as_heads_from(self) -> int:
        # a block's B queries a slot stay on the zero-lane path of
        # ``_kv_attend``, as a decode step's one query does
        return max(2, self.block_length + 1)

    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """``k`` and ``v`` ``[L, S, max_len, 1, Hk * hd]``: one token's K
        (or V) of all four KV heads is ONE stored row of 512, as
        ``LFM2MoEModel.init_kv_cache`` stores its own and for its reason.
        Compiled for a v5e at 48 slots x 4096, the pass over blocks
        (4 queries a slot) against rows of ``(4, 128)`` is a dot batched
        over slots and row groups, for which the compiler copies each
        layer's slab (two of 201 MB a layer) first; over rows of 512, each
        query laid into its own head's lanes, it reads the slab where it
        lies (``_rows_as_heads_from``)."""
        cfg = self.config
        kv = (cfg.n_layer, batch_size, max_len, 1,
              self.kv_heads * cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}

    def decode_kernel_block(self, cache):
        # the kernel is told a length a slot and keeps the columns below
        # it: a block's queries keep columns past their own
        return None if self.block_length > 1 else \
            super().decode_kernel_block(cache)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        params = super().init(rng)
        blocks = params["blocks"]
        blocks["q_norm_scale"] = jnp.ones((cfg.n_layer, cfg.head_dim))
        blocks["k_norm_scale"] = jnp.ones((cfg.n_layer, cfg.head_dim))
        return params

    # ----------------------------------------------------------------- block
    def _qk_norm(self, q, k, p):
        """RMSNorm over each head's channels, one gain of ``head_dim``."""
        hd, eps = self.config.head_dim, self.config.layer_norm_epsilon

        def heads(x, gain):
            return _rms_norm(x.reshape(x.shape[:-1] + (-1, hd)), gain,
                             eps).reshape(x.shape)
        return heads(q, p["q_norm_scale"]), heads(k, p["k_norm_scale"])

    def flops_per_token(self, seq_len=None):
        """Active-parameter FLOPs of a training token (6 a parameter)."""
        cfg = self.config
        d, l, f = cfg.n_embd, cfg.n_layer, cfg.intermediate
        hd, hk = cfg.head_dim, cfg.kv_head_count
        block = l * (d * (2 * cfg.n_head + 2 * hk) * hd +
                     cfg.top_k * 3 * d * f + d * cfg.num_experts)
        flops = 6 * (block + cfg.padded_vocab * d)
        if seq_len:
            flops += 12 * l * cfg.n_head * hd * seq_len
        return flops
