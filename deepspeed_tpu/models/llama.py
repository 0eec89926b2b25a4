"""LLaMA family — RMSNorm + rotary + SwiGLU + GQA decoder.

Capability match for the reference's LLaMA-architecture support (the
reference serves it through module_inject auto-TP; DS-Chat trains LLaMA
variants). Same stacked-layer ``lax.scan`` design as models/gpt2.py — only
the family hooks differ: no position table (rotary inside attention),
RMSNorm without biases, SwiGLU MLP (gate/up/down), optional grouped-query
attention (n_kv_head < n_head), untied LM head.

Rotary follows the HF "rotate_half" convention (split halves, not
interleaved) so HF checkpoints inject without any weight permutation.
"""

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .gpt2 import GPT2Config, GPT2Model
from ..ops.seq_parallel import sp_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig(GPT2Config):
    vocab_size: int = 32000
    n_positions: int = 2048
    activation: str = "silu"
    n_kv_head: Optional[int] = None     # None => MHA
    rope_theta: float = 10000.0
    mlp_hidden: Optional[int] = None    # intermediate size; None => mlp_ratio*d
    sliding_window: Optional[int] = None  # Mistral windowed causal attention
    tie_word_embeddings: bool = False
    layer_norm_epsilon: float = 1e-5    # rms_norm eps

    @property
    def kv_head_count(self):
        return self.n_kv_head or self.n_head

    @property
    def intermediate(self):
        return self.mlp_hidden or self.mlp_ratio * self.n_embd


# presets matching Meta shapes
LLAMA_7B = LlamaConfig(n_embd=4096, n_layer=32, n_head=32, mlp_hidden=11008)
LLAMA_13B = LlamaConfig(n_embd=5120, n_layer=40, n_head=40, mlp_hidden=13824)
LLAMA2_70B = LlamaConfig(n_embd=8192, n_layer=80, n_head=64, n_kv_head=8,
                         mlp_hidden=28672, n_positions=4096)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def rope_cos_sin(pos, head_dim, theta, dtype):
    """cos/sin tables for HF rotate_half rotary. pos: [T] or [B, T] (may be
    traced). Returns cos/sin of shape pos.shape + (head_dim,) with the
    half-table duplicated."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    angles = pos.astype(jnp.float32)[..., None] * inv_freq
    emb = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def apply_rope(x, cos, sin):
    """x: [B, H, T, hd]; cos/sin: [T, hd] (shared) or [B, T, hd]
    (per-row positions). HF rotate_half convention."""
    if cos.ndim == 2:
        cos, sin = cos[None, None], sin[None, None]
    else:                               # [B, T, hd] -> [B, 1, T, hd]
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


class LlamaModel(GPT2Model):

    def __init__(self, config: LlamaConfig = LLAMA_7B):
        assert config.n_head % config.kv_head_count == 0, \
            "n_head must be a multiple of n_kv_head"
        super().__init__(config)

    @property
    def kv_heads(self) -> int:
        return self.config.kv_head_count

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        d, l, v, m = cfg.n_embd, cfg.n_layer, cfg.padded_vocab, cfg.intermediate
        hd, hk = cfg.head_dim, cfg.kv_head_count
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * l)
        keys = jax.random.split(rng, 8)

        def norm(key, shape, s):
            return jax.random.normal(key, shape, jnp.float32) * s

        blocks = {
            "ln1_scale": jnp.ones((l, d)),
            "qkv_w": norm(keys[0], (l, d, (cfg.n_head + 2 * hk) * hd), std),
            "attn_proj_w": norm(keys[1], (l, cfg.n_head * hd, d), proj_std),
            "ln2_scale": jnp.ones((l, d)),
            "gate_w": norm(keys[2], (l, d, m), std),
            "up_w": norm(keys[3], (l, d, m), std),
            "down_w": norm(keys[4], (l, m, d), proj_std),
        }
        params = {
            "wte": norm(keys[5], (v, d), std),
            "blocks": blocks,
            "ln_f_scale": jnp.ones((d,)),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = norm(keys[6], (v, d), std)
        return params

    # ------------------------------------------------- family hook overrides
    def _embed(self, params, input_ids, start_pos=0, positions=None):
        # rotary: positions enter through attention, not the embedding
        return params["wte"].astype(self._compute_dtype(params))[input_ids]

    def _final_norm(self, params, x):
        return _rms_norm(x, params["ln_f_scale"],
                         self.config.layer_norm_epsilon)

    def _unembed_weight(self, params, dtype):
        head = params.get("lm_head", params["wte"])
        return head.astype(dtype)

    def _decode_attn_mask(self, q_pos, k_pos):
        keep = k_pos <= q_pos
        if self.config.sliding_window is not None:
            keep &= (q_pos - k_pos) < self.config.sliding_window
        return keep

    # ----------------------------------------------------------------- block
    def _qk_norm(self, q, k, p):
        """Hook on the whole q and k projections ([B, T, H*hd]) before they
        are split into heads and rotated; OLMoE normalises them here."""
        return q, k

    def _attn_sublayer(self, x, p, rng, train, attn_fn=None, start_pos=0,
                       positions=None, extra=None):
        cfg = self.config
        b, t, d = x.shape
        h, hk, hd = cfg.n_head, cfg.kv_head_count, cfg.head_dim
        with jax.named_scope("qkv"):
            ln1 = _rms_norm(x, p["ln1_scale"], cfg.layer_norm_epsilon)
            qkv = ln1 @ p["qkv_w"].astype(ln1.dtype)
        q, k, v = jnp.split(qkv, [h * hd, (h + hk) * hd], axis=-1)
        q, k = self._qk_norm(q, k, p)
        q = q.reshape(b, t, h, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, hk, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, hk, hd).transpose(0, 2, 1, 3)
        pos = positions if positions is not None else start_pos + jnp.arange(t)
        cos, sin = rope_cos_sin(pos, hd, cfg.rope_theta, q.dtype)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if attn_fn is not None:
            attn = attn_fn(q, k, v)       # decode: cache stores hk-head k/v
        else:
            if hk != h:                   # GQA: repeat kv heads for the kernel
                k = jnp.repeat(k, h // hk, axis=1)
                v = jnp.repeat(v, h // hk, axis=1)
            # a family whose mask is not the causal one (``models/sdar.py``:
            # whole blocks) says so and hands the mask over as a bias
            attn = sp_attention(q, k, v, causal=self.causal_attention,
                                bias=self._train_attn_bias_ex(t, extra),
                                dropout_rate=cfg.dropout if train else 0.0,
                                dropout_rng=(jax.random.fold_in(rng, 3)
                                             if train and cfg.dropout > 0 and
                                             rng is not None else None),
                                impl=cfg.sp_attention,
                                backend=cfg.attn_backend,
                                window=cfg.sliding_window)
        with jax.named_scope("out_proj"):
            # heads of a width of their own: H * hd, not always D
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
            attn = attn @ p["attn_proj_w"].astype(attn.dtype)
            return x + self._dropout(attn, rng, train, 0)

    def _mlp_sublayer(self, x, p, rng, train):
        cfg = self.config
        ln2 = _rms_norm(x, p["ln2_scale"], cfg.layer_norm_epsilon)
        g = ln2 @ p["gate_w"].astype(ln2.dtype)
        u = ln2 @ p["up_w"].astype(ln2.dtype)
        out = (jax.nn.silu(g) * u) @ p["down_w"].astype(ln2.dtype)
        return x + self._dropout(out, rng, train, 1), jnp.float32(0.0)

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        return [
            (r"wte$", ("model", None)),
            (r"lm_head$", ("model", None)),
            (r"blocks/qkv_w$", ("pipe", None, "model")),
            (r"blocks/attn_proj_w$", ("pipe", "model", None)),
            (r"blocks/(gate_w|up_w)$", ("pipe", None, "model")),
            (r"blocks/down_w$", ("pipe", "model", None)),
            (r"blocks/", ("pipe",)),
        ]

    def flops_per_token(self, seq_len: Optional[int] = None):
        cfg = self.config
        d, l, m = cfg.n_embd, cfg.n_layer, cfg.intermediate
        hd, hk = cfg.head_dim, cfg.kv_head_count
        block = l * (d * (2 * cfg.n_head + 2 * hk) * hd + 3 * d * m)
        flops = 6 * (block + cfg.padded_vocab * d)  # one V×d head matmul
        if seq_len:
            flops += 12 * l * d * seq_len
        return flops
