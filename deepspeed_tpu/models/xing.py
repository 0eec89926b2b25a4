"""Xing4.0 — latent attention over a compressed cache, a residual of several
streams mixed by learned doubly-stochastic maps, routed experts with a
shared one behind leading dense layers.

XingChen-AGI/Xing4.0-29B-A4B (``model_type`` xing4_0). RMSNorm, no biases.
The residual state of a token is ``X`` in R^{n x C}, ``n = hc_mult``
streams (manifold-constrained hyper-connections, arXiv:2512.24880); the
embedding is put in every stream, and every sublayer F (attention, then
feed-forward) of a block has maps of its own, ``phi`` [nC, n(n + 2)],
``b`` [n(n + 2)] and three gates ``alpha``::

    x~ = RMSNorm(flatten(X); hc_eps)                  (no gain)
    [h_pre | h_post | h_res] = x~ phi                 split n | n | n*n
    H_pre  = sigmoid(a_pre h_pre + b_pre)             [n]
    H_post = 2 sigmoid(a_post h_post + b_post)        [n]
    M_0    = exp(clip(a_res mat(h_res) + b_res, -30, 30))        [n, n]
    M_t    = rows(cols(M_{t-1})), each divided by its sum + hc_eps,
             t = 1 ... hc_sinkhorn_iters;  H_res = M_last
    u = H_pre X;   y = F(RMSNorm(u; gain));   X <- H_res X + H_post^T y

and the streams are summed before the final norm. The maps are computed in
float32 (as a router is), the mix in float32 and stored in the streams'
type.

- attention (every layer): ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` ->
  H x (nope | rope); ``[c_kv | k_r] = u W_kva``, ``c_kv <- RMSNorm(c_kv)``;
  ``[k_nope | v] = c_kv W_kvb`` -> H x (nope | v); rotate-half RoPE with
  YaRN's frequencies on ``q_r`` and on the one ``k_r`` all heads share;
  scores ``(q_nope . k_nope + q_r . k_r) (nope + rope)^-1/2 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``(P v) W_o``.
- dense FFN (``l < first_k_dense_replace``): SwiGLU of ``mlp_hidden``.
- routed FFN: sigmoid scores in float32, CHOSEN by the top k of score +
  bias, WEIGHTED by the score alone, renormalised, times
  ``routed_scaling_factor``; SwiGLU experts, and ``num_shared_experts``
  more of the same width take every token with weight 1.
- one RMSNorm after the streams are summed; an untied head.

The multi-token-prediction block does not enter the model's own logits and
is not built.

Built on ``LlamaModel`` beside ``lfm2.py`` and ``kexaone.py``, whose
per-kind stacks and walk it shares (``KindStacks``): ``blocks = {"attn":
[L, ...], "dense": [Ld, ...], "moe": [Lm, ...]}``. What is its own:

- **The pool's only leaf is LATENT** (``latent_cache``, ``init_kv_cache``):
  ``latent`` ``[L, S, max_len, 1, w]``, a token's normalised ``c_kv`` and
  rotated ``k_r`` in whole vector rows (576 values stored as 640: 1,280
  bytes in bfloat16 where per-head K and V would be 20,480). The attention sublayer hands the cache
  that row, and ``GPT2Model._latent_attend`` attends it two ways: a block
  of tokens expands the lane to per-head keys and values; one token a slot
  is absorbed into the latent space and reads the slab where it lies.
- **The layers carry the streams** ``[n, S, T, C]`` (``_open_streams`` /
  ``_close_streams``), streams first: each is a plain ``[S, T, C]`` array
  in whole vector rows, and the mix is elementwise over them.
- **A share of the experts** (``experts_held``), as ``kexaone.py``.

Serving only (``train=True`` raises); ``verify_with_slots`` raises (the
block path would expand every slot's lane, and the family's own drafter is
the block that is not built).
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .gpt2 import GPT2Model, _einsum_f32, _latent_row_width
from .lfm2 import KindStacks
from .llama import LlamaConfig, LlamaModel, _rms_norm, apply_rope
from ..moe.experts import GatedExpertFFN
from ..moe.sharded_moe import MOELayer, TopKGate

LATENT = "latent_attention"


@dataclasses.dataclass(frozen=True)
class XingConfig(LlamaConfig):
    vocab_size: int = 131072
    n_positions: int = 262144
    n_embd: int = 3584
    n_layer: int = 40
    n_head: int = 32
    layer_norm_epsilon: float = 1e-6
    tie_word_embeddings: bool = False
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 64.0           # YaRN, ``rope_scaling``
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    mlp_hidden: int = 9216              # ``intermediate_size``: the dense FFN
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024   # width of ONE expert
    num_experts: int = 64               # what the router scores
    top_k: int = 4                      # ``num_experts_per_tok``
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    renorm_eps: float = 1e-20           # in the sum the picks are divided by
    hc_mult: int = 4                    # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0          # ``mhc_h_res_clamp_min/max``: -/+
    #: (offset, count): the experts this chip holds of ``num_experts``;
    #: ``None``: all of them
    experts_held: Optional[Tuple[int, int]] = None


XING4_29B_A4B = XingConfig()


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN: the
    fast ones as published, the slow ones divided by ``rope_factor``, a
    linear ramp between the two over the dimensions that turn
    ``rope_beta_fast`` ... ``rope_beta_slow`` times in the original
    positions."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra, inter = 1.0 / base ** pos, 1.0 / (cfg.rope_factor * base ** pos)

    def turns_at(n_rot):
        return dim * math.log(cfg.rope_original_positions /
                              (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


class XingModel(KindStacks, LlamaModel):
    routed_experts = True       # the cache forwards hand routing stats on
    latent_cache = ("latent",)
    op_stacks = {LATENT: "attn"}
    #: tokens a feed-forward takes at once (``KExaoneModel._ffn_chunk``)
    _ffn_chunk = 4096

    def __init__(self, config: XingConfig = XING4_29B_A4B):
        # not LlamaModel's: its heads are not n_embd / n_head wide
        GPT2Model.__init__(self, config)
        cfg = config
        self._index_layers((LATENT,) * cfg.n_layer,
                           cfg.first_k_dense_replace)
        held = tuple(cfg.experts_held or (0, cfg.num_experts))
        self.gate = TopKGate(cfg.n_embd, cfg.num_experts, cfg.top_k,
                             score="sigmoid", select_bias=True,
                             renorm_eps=cfg.renorm_eps,
                             scale=cfg.routed_scaling_factor)
        self.experts = GatedExpertFFN(
            cfg.n_embd, cfg.moe_intermediate_size, held[1],
            initializer_range=cfg.initializer_range)
        shared = GatedExpertFFN(
            cfg.n_embd, cfg.moe_intermediate_size * cfg.num_shared_experts,
            1, initializer_range=cfg.initializer_range) \
            if cfg.num_shared_experts else None
        self.moe = MOELayer(self.gate, self.experts, held=held,
                            shared=shared)
        self._inv_freq = yarn_inv_freq(cfg)
        m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        #: on cos and sin; 1 where ``mscale == mscale_all_dim``
        self._rope_scale = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / m
        self._score_scale = m * m / math.sqrt(
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        d, v, h, m = cfg.n_embd, cfg.padded_vocab, cfg.n_head, \
            cfg.intermediate
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        n = cfg.hc_mult
        l, ld, lm = cfg.n_layer, self.counts["dense"], self.counts["moe"]
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * l)
        keys = iter(jax.random.split(rng, 20))

        def norm(shape, s):
            return jax.random.normal(next(keys), shape, jnp.float32) * s

        def hyper(layers):
            # near the plain residual: the streams kept apart (b_res large
            # on the diagonal), read evenly, written with weight 1
            b_res = 8.0 * jnp.eye(n).reshape(-1) - 4.0
            b = jnp.concatenate([jnp.zeros(2 * n), b_res])
            return {"phi": norm((layers, n * d, n * (n + 2)), std),
                    "b": jnp.tile(b, (layers, 1)),
                    "alpha": jnp.ones((layers, 3))}

        blocks = {
            "attn": {
                "ln1_scale": jnp.ones((l, d)),
                "q_a_w": norm((l, d, cfg.q_lora_rank), std),
                "q_a_scale": jnp.ones((l, cfg.q_lora_rank)),
                "q_b_w": norm((l, cfg.q_lora_rank, h * qk), std),
                "kv_a_w": norm((l, d, cfg.kv_lora_rank +
                                cfg.qk_rope_head_dim), std),
                "kv_a_scale": jnp.ones((l, cfg.kv_lora_rank)),
                "kv_b_w": norm((l, cfg.kv_lora_rank, h * (
                    cfg.qk_nope_head_dim + cfg.v_head_dim)), std),
                "attn_proj_w": norm((l, h * cfg.v_head_dim, d), proj_std),
                "hc_attn": hyper(l)},
            "dense": {"ln2_scale": jnp.ones((ld, d)),
                      "gate_w": norm((ld, d, m), std),
                      "up_w": norm((ld, d, m), std),
                      "down_w": norm((ld, m, d), proj_std),
                      "hc_mlp": hyper(ld)},
            "moe": {"ln2_scale": jnp.ones((lm, d)),
                    "moe": jax.vmap(self.moe.init)(
                        jax.random.split(next(keys), lm)),
                    "hc_mlp": hyper(lm)},
        }
        return {"wte": norm((v, d), std), "lm_head": norm((v, d), std),
                "blocks": blocks, "ln_f_scale": jnp.ones((d,))}

    # ----------------------------------------------------- the residual path
    def _open_streams(self, x):
        """The embedding in every stream: ``[n, S, T, C]``."""
        return jnp.broadcast_to(x[None], (self.config.hc_mult,) + x.shape)

    def _close_streams(self, x):
        return x.sum(axis=0)

    def _hc_maps(self, xs, p):
        """A sublayer's three maps of the streams ``xs`` [n, S, T, C], in
        float32 with the tokens last (whole vector rows): ``(H_pre [n, S,
        T], H_post [n, S, T], H_res [n, n, S, T])``; ``H_res[i, j]`` is
        stream j's weight in new stream i, rows and columns summing to 1
        after the Sinkhorn steps (unrolled: elementwise, one fusion)."""
        cfg = self.config
        n, eps = cfg.hc_mult, cfg.hc_eps
        phi = p["phi"].astype(xs.dtype).reshape(n, xs.shape[-1], -1)
        # x~ phi = (x phi) / rms(x): the norm has no gain
        h = sum(_einsum_f32("stc,cm->mst", xs[j], phi[j]) for j in range(n))
        # the mean square as products of the streams with themselves: no
        # float32 copy of the streams is made for it
        square = sum(_einsum_f32("stc,stc->st", xs[j], xs[j])
                     for j in range(n)) / (n * xs.shape[-1])
        h = h * lax.rsqrt(square + eps)
        # each part of h times its own gate, plus its bias
        gates = jnp.repeat(p["alpha"].astype(jnp.float32),
                           np.array([n, n, n * n]),
                           total_repeat_length=n * (n + 2))
        h = h * gates[:, None, None] + \
            p["b"].astype(jnp.float32)[:, None, None]
        pre = jax.nn.sigmoid(h[:n])
        post = 2.0 * jax.nn.sigmoid(h[n:2 * n])
        res = jnp.exp(jnp.clip(h[2 * n:], -cfg.hc_res_clamp,
                               cfg.hc_res_clamp)).reshape((n, n) + h.shape[1:])
        for _ in range(cfg.hc_sinkhorn_iters):
            res = res / (res.sum(axis=0, keepdims=True) + eps)     # columns
            res = res / (res.sum(axis=1, keepdims=True) + eps)     # rows
        return pre, post, res

    def _hyper(self, xs, p, gain, sublayer):
        """One sublayer on the streams: ``xs <- H_res xs + H_post^T
        F(RMSNorm(H_pre xs))``. ``sublayer(u) -> (y, extra)``; returns
        ``(xs, extra)``."""
        n = self.config.hc_mult
        with jax.named_scope("hc_maps"):
            pre, post, res = self._hc_maps(xs, p)
            # a token's n (n + 2) weights side by side, [S, T, n (n + 2)]:
            # each then spreads over a row's lanes
            maps = jnp.moveaxis(jnp.concatenate(
                [pre, post, res.reshape((n * n,) + res.shape[2:])]), 0, -1)
        weight = lambda k: maps[..., k:k + 1]
        # every term widens its own stream: a float32 copy of the streams
        # that all of them shared was written out and read five times
        wide = lambda a: a.astype(jnp.float32)
        with jax.named_scope("hc_mix"):
            u = sum(weight(j) * wide(xs[j]) for j in range(n))
            u = _rms_norm(u.astype(xs.dtype), gain,
                          self.config.layer_norm_epsilon)
        y, extra = sublayer(u)
        with jax.named_scope("hc_mix"):
            xs = jnp.stack([
                (sum(weight((2 + i) * n + j) * wide(xs[j])
                     for j in range(n)) + weight(n + i) * wide(y)
                 ).astype(xs.dtype) for i in range(n)])
        return xs, extra

    # ----------------------------------------------------------------- block
    def _attention(self, u, p, attn_fn=None, start_pos=0, positions=None):
        """Latent attention of the normed mix ``u`` [S, T, C]: the
        sublayer's output, without a residual (``_hyper`` writes it
        back)."""
        cfg = self.config
        b, t, _ = u.shape
        h, nope, rope = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        eps = cfg.layer_norm_epsilon
        with jax.named_scope("qkv"):
            c_q = _rms_norm(u @ p["q_a_w"].astype(u.dtype), p["q_a_scale"],
                            eps)
            q = (c_q @ p["q_b_w"].astype(u.dtype)).reshape(
                b, t, h, nope + rope).transpose(0, 2, 1, 3)
            row = u @ p["kv_a_w"].astype(u.dtype)
            c_kv = _rms_norm(row[..., :cfg.kv_lora_rank], p["kv_a_scale"],
                             eps)
            pos = positions if positions is not None \
                else start_pos + jnp.arange(t)
            angles = pos.astype(jnp.float32)[..., None] * self._inv_freq
            angles = jnp.concatenate([angles, angles], axis=-1)
            cos = (jnp.cos(angles) * self._rope_scale).astype(u.dtype)
            sin = (jnp.sin(angles) * self._rope_scale).astype(u.dtype)
            q = jnp.concatenate(
                [q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)
            k_r = apply_rope(row[:, None, :, cfg.kv_lora_rank:], cos, sin)
            pad = _latent_row_width(cfg.kv_lora_rank + rope) - \
                cfg.kv_lora_rank - rope
            row = jnp.concatenate(
                [c_kv, k_r[:, 0], jnp.zeros((b, t, pad), u.dtype)], axis=-1)
        latent = (p["kv_b_w"].astype(u.dtype).reshape(
            cfg.kv_lora_rank, h, nope + cfg.v_head_dim), self._score_scale,
            rope)
        with jax.named_scope("attend_latent"):
            if attn_fn is not None:
                attn = attn_fn(q, row, None, latent=latent)
            else:       # no cache: the block's own rows are the lane
                q_pos = jnp.arange(t)[None, None, :, None]
                k_pos = jnp.arange(t)[None, None, None, :]
                attn = self._latent_attend(
                    q, q_pos, row, latent, self._query_block(t, h, t),
                    lambda at: k_pos <= at)
        with jax.named_scope("out_proj"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, -1)
            return attn @ p["attn_proj_w"].astype(attn.dtype)

    def _dense_ffn(self, x, p):
        g = x @ p["gate_w"].astype(x.dtype)
        u = x @ p["up_w"].astype(x.dtype)
        return (jax.nn.silu(g) * u) @ p["down_w"].astype(x.dtype), None

    def _layer(self, xs, layer, attn_fn=None, start_pos=0, positions=None):
        """One block of either path on the streams. Returns xs, or (xs,
        exp_counts) from a routed layer."""
        cfg, p = self.config, layer.p

        def attention(u):
            with jax.named_scope("attn"):
                return self._attention(u, p, attn_fn, start_pos,
                                       positions), None

        def dense(u):
            with jax.named_scope("dense_mlp"):
                return self._in_row_blocks(
                    lambda at, rows: self._dense_ffn(rows, p),
                    self._ffn_chunk, 1, u)

        def routed(u):
            with jax.named_scope("moe"):
                return self._in_row_blocks(
                    lambda at, rows: self.moe.apply_routed(
                        p["moe"], rows, renormalize=cfg.norm_topk_prob,
                        stacked=layer.stacked)[::2],
                    self._ffn_chunk, 1, u)

        xs, _ = self._hyper(xs, p["hc_attn"], p["ln1_scale"], attention)
        xs, counts = self._hyper(xs, p["hc_mlp"], p["ln2_scale"],
                                 dense if layer.ffn == "dense" else routed)
        return xs if counts is None else (xs, counts)

    def _block(self, x, layer_params, rng, train, extra=None):
        if train:
            raise NotImplementedError(
                "XingModel has no training path: its routed layers are "
                "dropless and hold a share of the experts "
                "(MOELayer.apply_routed), and a dropless routed backward "
                "pass with the experts over chips is ROADMAP B1's; serve "
                "it (train=False)")
        out = self._layer(x, layer_params)
        return (out[0] if isinstance(out, tuple) else out), jnp.float32(0.0)

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None):
        return self._layer(x, layer_params, attn_fn, start_pos, positions)

    # ------------------------------------------------------- decode protocol
    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """The pool's one leaf, ``latent`` ``[L, S, max_len, 1, w]``: a
        token's normalised ``c_kv`` (``kv_lora_rank``), the rotated key
        ``k_r`` all heads share (``qk_rope_head_dim``) and zeros up to whole
        vector rows (``_latent_row_width``: the published 576 values are
        4.5 rows of 128 lanes and are stored as 640), ONE stored row a token
        a layer as ``LFM2MoEModel.init_kv_cache``'s K and V are (the pool
        programs index a row's two axes)."""
        cfg = self.config
        return {"latent": jnp.zeros(
            (cfg.n_layer, batch_size, max_len, 1, _latent_row_width(
                cfg.kv_lora_rank + cfg.qk_rope_head_dim)), dtype)}

    def _decode_attn_mask(self, q_pos, k_pos):
        return k_pos <= q_pos

    def verify_with_slots(self, params, input_ids, cache, positions):
        raise NotImplementedError(
            "XingModel cannot verify a block of draft tokens: a block at a "
            "position of its own a slot would expand every slot's whole "
            "latent lane to per-head keys and values a step "
            "(_latent_attend's block path), and the family's own drafter, "
            "its multi-token-prediction block, is not built (ROADMAP B9)")

    def cache_partition_rules(self):
        """Slots over the dp axes; a latent row is every head's, whole."""
        return [(r"latent$", (None, ("data", "expert"), None, None, None))]

    def pipeline_spec(self):
        raise NotImplementedError(
            "XingModel has no pipeline protocol: its layers are of several "
            "kinds in per-kind stacks and carry several residual streams, "
            "and the compiled pipeline slices one stacked tree of like "
            "layers and hands one row a token across stages")

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """Per-kind stacks, the leading axis left whole. The heads' up
        projections and the output projection megatron-style, the two down
        projections (a latent is every head's) and the maps whole; the
        feed-forwards as ``kexaone.py``."""
        return [
            (r"wte$", ("model", None)),
            (r"lm_head$", ("model", None)),
            (r"blocks/attn/(q_b_w|kv_b_w)$", (None, None, "model")),
            (r"blocks/attn/attn_proj_w$", (None, "model", None)),
            (r"blocks/dense/(gate_w|up_w)$", (None, None, "model")),
            (r"blocks/dense/down_w$", (None, "model", None)),
            (r"blocks/moe/moe/experts/(w_gate|w_up|w_down)$",
             (None, "expert", None, None)),
            (r"blocks/moe/moe/shared/(w_gate|w_up)$", (None, None, "model")),
            (r"blocks/moe/moe/shared/w_down$", (None, "model", None)),
            (r"blocks/", (None,)),
        ]

    def flops_per_token(self, seq_len=None):
        """None, ``ModelSpec``'s "not counted": the family has no training
        path; serving's counts are the benchmark's
        (``chipbench/counts_xing.py``)."""
        return None
