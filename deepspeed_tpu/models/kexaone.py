"""K-EXAONE — window and full attention mixed, routed experts with a shared
one behind a leading dense layer.

LGAI-EXAONE/K-EXAONE-236B-A23B (``model_type`` exaone_moe). RMSNorm, no
biases; the family normalises each sublayer's OUTPUT (``exaone4``), not its
input; ``layer_types[l]`` picks block l's attention and
``l < first_k_dense_replace`` its feed-forward::

    h = x + RMSNorm(Attn_l(x))          y = h + RMSNorm(FFN_l(h))

- attention: grouped queries, RMSNorm over each head's channels of q and of
  k (one gain of ``head_dim`` each). ``sliding_attention``: rotate-half RoPE
  and a query at p sees the keys at ``p - sliding_window + 1 ... p``.
  ``full_attention``: causal, and no rotary embedding.
- dense FFN (the leading layers): SwiGLU of ``intermediate_size``.
- routed FFN: sigmoid scores in float32; the experts are CHOSEN by the top k
  of score + bias and WEIGHTED by the score alone, renormalised over the
  picks with 1e-20 in the sum, times ``routed_scaling_factor``; SwiGLU
  experts of ``moe_intermediate_size``; ``num_shared_experts`` more of the
  same width (one SwiGLU of their summed width) take every token with
  weight 1.
- one RMSNorm after the last block; an untied head.

The model's multi-token-prediction block (``num_nextn_predict_layers``)
does not enter its own logits and is not built.

Built on ``LlamaModel`` beside ``lfm2.py``, whose per-kind stacks and walk
it shares (``KindStacks``): ``blocks = {"window": [Lw, ...], "full":
[Lf, ...], "dense": [Ld, ...], "moe": [Lm, ...]}``. What is its own:

- **The pool has a cache shape per kind of attention** (``init_kv_cache``):
  ``k`` / ``v`` ``[Lf, S, max_len, 1, Hk * hd]`` for the full layers, and
  for the window layers the RINGS ``wk`` / ``wv`` ``[Lw, S, W, 1, Hk * hd]``
  (``window_rings``): position p in column ``p mod W``, whatever the lane's
  length (``GPT2Model._window_attend``). A ring is valid at the lane's END
  alone, as a recurrent state is.
- **A share of the experts** (``experts_held = (offset, count)``): the
  router scores all ``num_experts``; the layer holds ``count`` of them,
  computes what those give and leaves out the rest
  (``MOELayer(held=...)``): one chip's part under expert parallelism,
  without the exchange.
- A prefill's feed-forwards go in chunks of ``_ffn_chunk`` tokens
  (``GPT2Model._in_row_blocks``), so that the hidden rows of a long bucket
  (and the routed pairs' rows: top_k a token) are a chunk's.

Serving only (``train=True`` raises, as ``lfm2.py``); ``verify_with_slots``
raises (rows a rejected draft wrote have replaced ring columns still in the
window).
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .gpt2 import GPT2Model
from .lfm2 import KindStacks
from .llama import LlamaConfig, LlamaModel, _rms_norm, apply_rope, \
    rope_cos_sin
from ..moe.experts import GatedExpertFFN
from ..moe.sharded_moe import MOELayer, TopKGate
from ..ops.seq_parallel import sp_attention

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class KExaoneConfig(LlamaConfig):
    vocab_size: int = 153600
    n_positions: int = 262144
    n_embd: int = 6144
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128                 # not n_embd / n_head: 64 x 128 = 8192
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    mlp_hidden: int = 18432             # ``intermediate_size``: the dense FFN
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    sliding_window: int = 128
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 2048   # width of ONE expert
    num_experts: int = 128              # what the router scores
    top_k: int = 8                      # ``num_experts_per_tok``
    num_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    renorm_eps: float = 1e-20           # in the sum the picks are divided by
    #: (offset, count): the experts this chip holds of ``num_experts``;
    #: ``None``: all of them
    experts_held: Optional[Tuple[int, int]] = None


K_EXAONE_236B_A23B = KExaoneConfig()


class KExaoneModel(KindStacks, LlamaModel):
    routed_experts = True       # the cache forwards hand routing stats on
    window_rings = ("wk", "wv")
    op_stacks = {SLIDING: "window", FULL: "full"}
    #: tokens a feed-forward takes at once: at 4096 a routed layer's pair
    #: rows (8 a token) and their hidden rows are 1.3 GB, and the held
    #: experts' weights are read once a chunk (1.2 GB a layer)
    _ffn_chunk = 4096

    def __init__(self, config: KExaoneConfig = K_EXAONE_236B_A23B):
        # not LlamaModel's: its heads are not n_embd / n_head wide
        GPT2Model.__init__(self, config)
        cfg = config
        if cfg.n_head % cfg.kv_head_count:
            raise ValueError("n_head must be a multiple of n_kv_head")
        if FULL not in cfg.layer_types:
            raise ValueError("the pool's lanes are the full-attention "
                             "layers': layer_types names none")
        if cfg.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func {cfg.scoring_func!r}: the "
                             f"family's router scores with a sigmoid")
        self._index_layers(cfg.layer_types, cfg.first_k_dense_replace)
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

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        d, v = cfg.n_embd, cfg.padded_vocab
        hd, hk, m = cfg.head_dim, cfg.kv_head_count, cfg.intermediate
        n = self.counts
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * cfg.n_layer)
        keys = iter(jax.random.split(rng, 16))

        def norm(shape, s):
            return jax.random.normal(next(keys), shape, jnp.float32) * s

        def attention(l):
            return {"qkv_w": norm((l, d, (cfg.n_head + 2 * hk) * hd), std),
                    "q_norm_scale": jnp.ones((l, hd)),
                    "k_norm_scale": jnp.ones((l, hd)),
                    "attn_proj_w": norm((l, cfg.n_head * hd, d), proj_std),
                    "post_attn_scale": jnp.ones((l, d))}

        ld, lm = n["dense"], n["moe"]
        blocks = {
            "window": attention(n[SLIDING]),
            "full": attention(n[FULL]),
            "dense": {"gate_w": norm((ld, d, m), std),
                      "up_w": norm((ld, d, m), std),
                      "down_w": norm((ld, m, d), proj_std),
                      "post_mlp_scale": jnp.ones((ld, d))},
            "moe": {"post_mlp_scale": jnp.ones((lm, d)),
                    "moe": jax.vmap(self.moe.init)(
                        jax.random.split(next(keys), lm))},
        }
        return {"wte": norm((v, d), std), "lm_head": norm((v, d), std),
                "blocks": blocks, "ln_f_scale": jnp.ones((d,))}

    # ----------------------------------------------------------------- block
    def _attention(self, x, p, window, attn_fn=None, start_pos=0,
                   positions=None):
        """``x + RMSNorm(Attn(x))``; ``window``: a sliding layer (rotary,
        the band, its ring) or a full one (neither)."""
        cfg = self.config
        b, t, _ = x.shape
        h, hk, hd = cfg.n_head, cfg.kv_head_count, cfg.head_dim
        eps = cfg.layer_norm_epsilon
        with jax.named_scope("qkv"):
            qkv = x @ p["qkv_w"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [h * hd, (h + hk) * hd], axis=-1)
        q = _rms_norm(q.reshape(b, t, h, hd), p["q_norm_scale"], eps)
        k = _rms_norm(k.reshape(b, t, hk, hd), p["k_norm_scale"], eps)
        q, k = q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3)
        v = v.reshape(b, t, hk, hd).transpose(0, 2, 1, 3)
        if window:
            pos = positions if positions is not None \
                else start_pos + jnp.arange(t)
            cos, sin = rope_cos_sin(pos, hd, cfg.rope_theta, q.dtype)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        with jax.named_scope("attend_window" if window else "attend_full"):
            if attn_fn is not None:
                attn = attn_fn(q, k, v, ring=self.window_rings) if window \
                    else attn_fn(q, k, v)
            else:
                k = jnp.repeat(k, h // hk, axis=1)
                v = jnp.repeat(v, h // hk, axis=1)
                attn = sp_attention(
                    q, k, v, causal=True, impl=cfg.sp_attention,
                    backend=cfg.attn_backend,
                    window=cfg.sliding_window if window else None)
        with jax.named_scope("out_proj"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
            attn = attn @ p["attn_proj_w"].astype(attn.dtype)
            return x + _rms_norm(attn, p["post_attn_scale"], eps)

    def _dense_ffn(self, x, p):
        g = x @ p["gate_w"].astype(x.dtype)
        u = x @ p["up_w"].astype(x.dtype)
        return (jax.nn.silu(g) * u) @ p["down_w"].astype(x.dtype), None

    def _layer(self, x, layer, attn_fn=None, start_pos=0, positions=None):
        """One block of either path. Returns x, or (x, exp_counts) from a
        routed layer."""
        cfg, p = self.config, layer.p
        with jax.named_scope("attn"):
            x = self._attention(x, p, layer.op == SLIDING, attn_fn,
                                start_pos, positions)
        if layer.ffn == "dense":
            with jax.named_scope("dense_mlp"):
                y, counts = self._in_row_blocks(
                    lambda at, rows: self._dense_ffn(rows, p),
                    self._ffn_chunk, 1, x)
        else:
            with jax.named_scope("moe"):
                y, counts = self._in_row_blocks(
                    lambda at, rows: self.moe.apply_routed(
                        p["moe"], rows, renormalize=cfg.norm_topk_prob,
                        stacked=layer.stacked)[::2],
                    self._ffn_chunk, 1, x)
        x = x + _rms_norm(y, p["post_mlp_scale"], cfg.layer_norm_epsilon)
        return x if counts is None else (x, counts)

    def _block(self, x, layer_params, rng, train, extra=None):
        if train:
            raise NotImplementedError(
                "KExaoneModel has no training path: its routed layers are "
                "dropless (MOELayer.apply_routed) and a dropless routed "
                "backward pass with the experts over chips is ROADMAP B1's; "
                "serve it (train=False)")
        out = self._layer(x, layer_params)
        return (out[0] if isinstance(out, tuple) else out), jnp.float32(0.0)

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None):
        return self._layer(x, layer_params, attn_fn, start_pos, positions)

    # ------------------------------------------------------- decode protocol
    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """A cache shape per kind of attention layer: ``k`` and ``v`` over
        the FULL layers only, ``[Lf, S, max_len, 1, Hk * hd]`` (one stored
        row a token, as ``LFM2MoEModel.init_kv_cache`` says why), and the
        window layers' rings ``wk`` and ``wv`` ``[Lw, S, W, 1, Hk * hd]``:
        W columns a slot whatever its length, position p in column
        ``p mod W`` (``_window_attend``)."""
        cfg = self.config
        row = (1, self.kv_heads * cfg.head_dim)
        lane = (self.counts[FULL], batch_size, max_len) + row
        ring = (self.counts[SLIDING], batch_size, cfg.sliding_window) + row
        return {"k": jnp.zeros(lane, dtype), "v": jnp.zeros(lane, dtype),
                "wk": jnp.zeros(ring, dtype), "wv": jnp.zeros(ring, dtype)}

    def _decode_attn_mask(self, q_pos, k_pos):
        """Over a full-length lane: causal. ``sliding_window`` is the
        window layers', which go over their rings."""
        return k_pos <= q_pos

    def apply_with_cache(self, params, input_ids, cache, start_pos,
                         pad_counts=None, routing=False, lengths=None):
        if pad_counts is not None:
            raise NotImplementedError(
                "KExaoneModel takes no left-padded batch: a window layer's "
                "ring and band count positions from a row's first column "
                "(attention_mask in generate(); send rows of one length)")
        return super().apply_with_cache(params, input_ids, cache, start_pos,
                                        routing=routing, lengths=lengths)

    def verify_with_slots(self, params, input_ids, cache, positions):
        raise NotImplementedError(
            "KExaoneModel cannot verify a block of draft tokens: the rows a "
            "rejected draft wrote into a window layer's ring have replaced "
            "columns still in the window, and cannot be rolled back as the "
            "columns of a full-length lane are; ring snapshots are ROADMAP "
            "B8's")

    def pipeline_spec(self):
        raise NotImplementedError(
            "KExaoneModel has no pipeline protocol: its layers are of "
            "several kinds in per-kind stacks, and the compiled pipeline "
            "slices one stacked tree of like layers across stages")

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """Per-kind stacks: the leading axis is the kind's own layer count
        and is left whole; attention and the dense FFN megatron-style, the
        shared expert likewise, the routed experts over ``expert``, the
        router and the norms whole."""
        return [
            (r"wte$", ("model", None)),
            (r"lm_head$", ("model", None)),
            (r"blocks/(window|full)/qkv_w$", (None, None, "model")),
            (r"blocks/(window|full)/attn_proj_w$", (None, "model", None)),
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
        path, and ``LlamaModel``'s count is of a dense model."""
        return None
