"""LFM2-MoE — a hybrid stack: gated short convolutions among a few
attention layers, routed experts behind a leading dense layer.

LiquidAI/LFM2-24B-A2B (``model_type`` lfm2_moe). Pre-norm, RMSNorm, no
biases; ``layer_types[l]`` picks block l's operator and
``l < num_dense_layers`` its feed-forward::

    h = x + Op_l(RMSNorm(x))        y = h + FFN_l(RMSNorm(h))

- ``conv``: ``B, C, X = split3(u W_in)``; ``z = B * X``; a depthwise causal
  convolution of ``conv_L_cache`` taps over z, one filter a channel,
  ``c_t = sum_j w[:, j] z_{t-(K-1)+j}`` with ``z_{<0} = 0``;
  ``out = (C * c) W_out``. To go on from a prefix it needs the last K - 1
  rows of z and nothing else, whatever the prefix's length.
- ``full_attention``: grouped-query attention, RMSNorm over each head's
  channels of q and of k (one gain of ``head_dim`` each, shared by the
  heads), rotate-half RoPE.
- dense FFN (the leading layers): SwiGLU of ``intermediate_size``.
- routed FFN: sigmoid scores in float32; the experts are CHOSEN by the top k
  of score + bias (``use_expert_bias``) and WEIGHTED by the score alone,
  renormalised over the picks with 1e-6 in the sum, times
  ``routed_scaling_factor``; SwiGLU experts of ``moe_intermediate_size``,
  no shared expert, nothing dropped.
- one RMSNorm after the last block; the tied table is the head.

Built on ``LlamaModel`` as ``olmoe.py`` is: its attention sublayer (the q/k
norm hook per head here) and its SwiGLU, ``moe/`` for the routed layer. What
differs from every other family of ``models/`` is that the layers are not
alike, so there is no one stacked tree to scan:

- **Parameters in per-kind stacks**: ``blocks = {"conv": [Lc, ...], "attn":
  [La, ...], "dense": [Ld, ...], "moe": [Lm, ...]}``, experts
  ``[Lm, E, ...]``; a layer is (operator kind, its index in that stack,
  FFN kind, its index). The experts' matmul leaves are read whole as
  ``[Lm * E]`` groups at the routed layer's index (``MOELayer.take_whole``).
- **The pool has two kinds of state** (``init_kv_cache``): ``k`` and ``v``
  for the attention layers only, ``[La, S, max_len, 1, Hk * hd]`` (all of a
  token's KV heads in one stored row), and ``conv`` ``[Lc, S, K - 1, d]``,
  the conv layers' last K - 1 rows of z a slot (``recurrent_state``;
  ``GPT2Model._state_shift``).
- **The stack is walked** (``_scan_layers``) as leading layers, a
  ``lax.scan`` over the whole periods of the pattern with one period's
  layers unrolled in its body, and a tail that is no whole period
  (published: 2 dense + 9 x (attention, conv, conv, conv) + attention,
  conv); every leaf is indexed where it lies by its own kind's index.

Serving only: ``engine.forward`` (``hidden_states``, ``train=False``),
``apply_with_cache``, ``chunk_prefill_with_cache``, ``decode_with_slots``.
``train=True`` raises (dropless routed training is ROADMAP B1's), and
``verify_with_slots`` raises (a rejected draft cannot be rolled back out of
a state that exists only at the lane's end).
"""

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .llama import LlamaConfig, LlamaModel, _rms_norm
from ..moe.experts import GatedExpertFFN
from ..moe.sharded_moe import MOELayer, TopKGate

CONV, ATTN = "conv", "full_attention"
_PERIOD = (ATTN, CONV, CONV, CONV)


@dataclasses.dataclass(frozen=True)
class LFM2MoEConfig(LlamaConfig):
    vocab_size: int = 65536
    n_positions: int = 128000
    n_embd: int = 2048
    n_layer: int = 40
    n_head: int = 32
    n_kv_head: int = 8
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = True
    mlp_hidden: int = 11776             # ``intermediate_size``: the dense FFN
    layer_types: Tuple[str, ...] = (CONV, CONV) + _PERIOD * 9 + (ATTN, CONV)
    num_dense_layers: int = 2
    conv_L_cache: int = 3               # taps of the short convolution
    moe_intermediate_size: int = 1536   # width of ONE expert
    num_experts: int = 64
    top_k: int = 4                      # ``num_experts_per_tok``
    use_expert_bias: bool = True        # a bias [E] in the CHOICE of experts
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    renorm_eps: float = 1e-6            # in the sum the picks are divided by


LFM2_24B_A2B = LFM2MoEConfig()


def _take(tree, index):
    """Layer ``index`` (a Python int or a traced scalar) of every leaf of a
    stacked tree, read where it lies: what ``lax.scan`` does to its ``xs``."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), tree)


@jax.tree_util.register_pytree_node_class
class _Layer:
    """What ``_scan_layers`` hands ``_block`` / ``_decode_block`` as one
    layer's ``layer_params``: the kinds (static), this layer's slices of its
    operator's and its FFN's stacks, and for a routed layer the experts'
    whole leaves with its index among the routed layers."""

    def __init__(self, op, ffn, p, stacked=None):
        self.op, self.ffn, self.p, self.stacked = op, ffn, p, stacked

    def tree_flatten(self):
        return (self.p, self.stacked), (self.op, self.ffn)

    @classmethod
    def tree_unflatten(cls, kinds, children):
        return cls(*kinds, *children)


class KindStacks:
    """A stack whose layers are of several kinds, each kind's parameters in
    a stacked tree of its own: ``blocks[op_stacks[kind]]`` for the
    operators, ``blocks["dense"]`` and ``blocks["moe"]`` for the two
    feed-forwards (``self.moe``: the routed layer). What a family with such
    a stack shares (``models/lfm2.py``, ``models/kexaone.py``): which layer
    is which (``_index_layers``) and the walk (``_scan_layers``)."""

    #: {operator kind, as ``layer_types`` names it: its key in ``blocks``}
    op_stacks = {}

    def _index_layers(self, types, num_dense):
        """``self.layers``: layer l as (operator kind, index in its stack,
        FFN kind, index); ``self.counts``: layers of each kind; and the
        pattern's split for the walk."""
        types = tuple(types)
        n = self.config.n_layer
        if len(types) != n or set(types) - set(self.op_stacks):
            raise ValueError(
                f"layer_types must name n_layer={n} layers, each one of "
                f"{sorted(self.op_stacks)}; got {len(types)}: {types}")
        if not 0 <= num_dense <= n:
            raise ValueError(f"{num_dense} leading dense layers not in "
                             f"[0, {n}]")
        seen = dict.fromkeys((*self.op_stacks, "dense", "moe"), 0)
        self.layers = []
        for l, op in enumerate(types):
            ffn = "dense" if l < num_dense else "moe"
            self.layers.append((op, seen[op], ffn, seen[ffn]))
            seen[op] += 1
            seen[ffn] += 1
        self.counts = seen
        self.lead, self.period, self.repeats = self._split_pattern(
            types, num_dense)

    @staticmethod
    def _split_pattern(types, lead):
        """``(lead, period, repeats)``: after the ``lead`` leading layers
        (the dense ones, whose FFN is of another kind) the run of kinds
        that repeats at least twice and covers the most layers (the
        shortest of those that cover as many), and how many whole times;
        what is left after ``lead + period * repeats`` layers is the tail.
        ``repeats`` under 2 means there is nothing to scan."""
        rest = types[lead:]
        best = (lead, 0, 0)
        for period in range(1, len(rest) // 2 + 1):
            repeats = 1
            while rest[repeats * period:(repeats + 1) * period] == \
                    rest[:period]:
                repeats += 1
            if repeats >= 2 and period * repeats > best[1] * best[2]:
                best = (lead, period, repeats)
        return best

    def _scan_layers(self, body, carry, blocks, indexed=False, unroll=1):
        """Leading layers, a ``lax.scan`` over the whole periods with one
        period's layers unrolled in its body, the tail. Every stack is
        closed over whole and a layer's leaves are indexed where they lie
        by the layer's index in its own kind (a static slice of a stack
        handed to the scan as ``xs`` would be copied first). ``outs``: the
        routed layers' outputs stacked in layer order, or ``None`` where
        the body gives none."""
        moe, whole = self.moe.take_whole(blocks["moe"]["moe"])
        stacks = {kind: blocks[key] for kind, key in self.op_stacks.items()}
        stacks["dense"] = blocks["dense"]
        stacks["moe"] = {**blocks["moe"], "moe": moe}

        lead, period, repeats = self.lead, self.period, self.repeats
        first = self.layers[lead:lead + period]
        # how many layers of each kind one period holds: the stride of a
        # kind's index from one period to the next
        stride = {kind: sum(kind in (op, ffn) for op, _, ffn, _ in first)
                  for kind in stacks}

        def run(carry, layer, shift=0):
            """One layer; ``shift``: how many whole periods lie before it
            (traced, inside the scan)."""
            op, oi, ffn, fi = layer
            oi, fi = oi + shift * stride[op], fi + shift * stride[ffn]
            params = _Layer(op, ffn, {**_take(stacks[op], oi),
                                      **_take(stacks[ffn], fi)},
                            None if ffn != "moe" or whole is None
                            else (whole, fi))
            return body(carry, (params, oi, None) if indexed else params)

        outs = []

        def straight(carry, layers):
            for layer in layers:
                carry, out = run(carry, layer)
                if out is not None:
                    outs.append(out[None])
            return carry

        carry = straight(carry, self.layers[:lead])
        if repeats:
            def one_period(carry, r):
                got = []
                for layer in first:
                    carry, out = run(carry, layer, r)
                    got.append(out)
                return carry, None if got[0] is None else jnp.stack(got)

            carry, out = lax.scan(one_period, carry, jnp.arange(repeats))
            if out is not None:
                outs.append(out.reshape((-1,) + out.shape[2:]))
        carry = straight(carry, self.layers[lead + period * repeats:])
        return carry, jnp.concatenate(outs) if outs else None


class LFM2MoEModel(KindStacks, LlamaModel):
    routed_experts = True       # the cache forwards hand routing stats on
    recurrent_state = ("conv",)
    op_stacks = {CONV: "conv", ATTN: "attn"}

    def __init__(self, config: LFM2MoEConfig = LFM2_24B_A2B):
        super().__init__(config)
        cfg = config
        if ATTN not in cfg.layer_types:
            raise ValueError("the pool is sized by its attention layers: "
                             "layer_types names none")
        self._index_layers(cfg.layer_types, cfg.num_dense_layers)
        self.gate = TopKGate(cfg.n_embd, cfg.num_experts, cfg.top_k,
                             score="sigmoid",
                             select_bias=cfg.use_expert_bias,
                             renorm_eps=cfg.renorm_eps,
                             scale=cfg.routed_scaling_factor)
        self.experts = GatedExpertFFN(
            cfg.n_embd, cfg.moe_intermediate_size, cfg.num_experts,
            initializer_range=cfg.initializer_range)
        self.moe = MOELayer(self.gate, self.experts)

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        d, v = cfg.n_embd, cfg.padded_vocab
        hd, hk, m = cfg.head_dim, cfg.kv_head_count, cfg.intermediate
        n = self.counts
        std = cfg.initializer_range
        proj_std = std / math.sqrt(2 * cfg.n_layer)
        keys = iter(jax.random.split(rng, 12))

        def norm(shape, s):
            return jax.random.normal(next(keys), shape, jnp.float32) * s

        lc, la, ld, lm = n[CONV], n[ATTN], n["dense"], n["moe"]
        blocks = {
            "conv": {"ln1_scale": jnp.ones((lc, d)),
                     "in_w": norm((lc, d, 3 * d), std),
                     "conv_w": norm((lc, d, cfg.conv_L_cache),
                                    1.0 / math.sqrt(cfg.conv_L_cache)),
                     "out_w": norm((lc, d, d), proj_std)},
            "attn": {"ln1_scale": jnp.ones((la, d)),
                     "qkv_w": norm((la, d, (cfg.n_head + 2 * hk) * hd), std),
                     "q_norm_scale": jnp.ones((la, hd)),
                     "k_norm_scale": jnp.ones((la, hd)),
                     "attn_proj_w": norm((la, d, d), proj_std)},
            "dense": {"ln2_scale": jnp.ones((ld, d)),
                      "gate_w": norm((ld, d, m), std),
                      "up_w": norm((ld, d, m), std),
                      "down_w": norm((ld, m, d), proj_std)},
            "moe": {"ln2_scale": jnp.ones((lm, d)),
                    "moe": jax.vmap(self.moe.init)(
                        jax.random.split(next(keys), lm))},
        }
        params = {"wte": norm((v, d), std), "blocks": blocks,
                  "ln_f_scale": jnp.ones((d,))}
        if not cfg.tie_word_embeddings:
            params["lm_head"] = norm((v, d), std)
        return params

    # ----------------------------------------------------------------- block
    def _qk_norm(self, q, k, p):
        """RMSNorm over each head's channels, one gain of ``head_dim``."""
        cfg = self.config
        hd, eps = cfg.head_dim, cfg.layer_norm_epsilon

        def per_head(x, gain):
            heads = x.reshape(x.shape[:-1] + (-1, hd))
            return _rms_norm(heads, gain, eps).reshape(x.shape)

        return per_head(q, p["q_norm_scale"]), per_head(k, p["k_norm_scale"])

    def _conv_sublayer(self, x, p, state_fn=None):
        """``x + ((C * conv(B * X)) W_out)``. ``state_fn("conv", z)`` gives
        the K - 1 rows of z before this block and keeps the last K - 1
        real ones for the next call; without one (no cache) the block
        starts the sequence and the history is zero."""
        cfg = self.config
        t, taps = x.shape[1], cfg.conv_L_cache
        u = _rms_norm(x, p["ln1_scale"], cfg.layer_norm_epsilon)
        gate_b, gate_c, value = jnp.split(
            u @ p["in_w"].astype(u.dtype), 3, axis=-1)
        z = gate_b * value
        hist = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), z.dtype) \
            if state_fn is None else state_fn("conv", z).astype(z.dtype)
        seen = jnp.concatenate([hist, z], axis=1)        # [B, K - 1 + T, d]
        w = p["conv_w"].astype(z.dtype)                  # [d, K]
        conv = sum(w[:, j] * seen[:, j:j + t] for j in range(taps))
        return x + (gate_c * conv) @ p["out_w"].astype(z.dtype)

    def _routed_mlp(self, x, p, stacked):
        """(x + experts(RMSNorm(x)), exp_counts): every token routed."""
        cfg = self.config
        ln2 = _rms_norm(x, p["ln2_scale"], cfg.layer_norm_epsilon)
        y, _, counts = self.moe.apply_routed(
            p["moe"], ln2, renormalize=cfg.norm_topk_prob, stacked=stacked)
        return x + y, counts

    def _layer(self, x, layer, attn_fn=None, start_pos=0, positions=None,
               state_fn=None):
        """One block of either path. Returns x, or (x, exp_counts) from a
        routed layer."""
        p = layer.p
        if layer.op == CONV:
            with jax.named_scope("conv"):
                x = self._conv_sublayer(x, p, state_fn)
        else:
            with jax.named_scope("attn"):
                x = self._attn_sublayer(x, p, None, False, attn_fn=attn_fn,
                                        start_pos=start_pos,
                                        positions=positions)
        if layer.ffn == "dense":
            with jax.named_scope("dense_mlp"):
                return self._mlp_sublayer(x, p, None, False)[0]
        with jax.named_scope("moe"):
            return self._routed_mlp(x, p, layer.stacked)

    def _block(self, x, layer_params, rng, train, extra=None):
        if train:
            raise NotImplementedError(
                "LFM2MoEModel has no training path: its routed layers are "
                "dropless (MOELayer.apply_routed) and a dropless routed "
                "backward pass with the experts over chips is ROADMAP B1's; "
                "serve it (train=False)")
        out = self._layer(x, layer_params)
        return (out[0] if isinstance(out, tuple) else out), jnp.float32(0.0)

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None, state_fn=None):
        return self._layer(x, layer_params, attn_fn, start_pos, positions,
                           state_fn)

    # ------------------------------------------------------- decode protocol
    def init_kv_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        """The pool's two kinds of state: ``k`` and ``v`` over the
        ATTENTION layers only, token-major as every family's
        (``GPT2Model.init_kv_cache``), and ``conv`` ``[Lc, S, K - 1, d]``:
        each conv layer's last K - 1 rows of ``B * X`` a slot, whatever the
        slot's length. One token's K (or V) of ALL its KV heads is ONE
        stored row, ``[La, S, max_len, 1, Hk * hd]``: with grouped queries
        (four query heads a KV head) rows of ``(Hk * hd / 128, 128)``
        (``_kv_row_shape``) make the decode step's attention a dot batched
        over slots AND row groups, for which the chip's compiler re-lays
        each layer's whole slab in HBM first (201 MB of temporaries a
        layer at 48 slots x 4096, compiled for a described v5e); batched
        over slots alone it reads the slab where it lies.
        ``_kv_attend`` lays each query into its own head's lanes of a zero
        row, whatever the row's width."""
        cfg = self.config
        kv = (self.counts[ATTN], batch_size, max_len, 1,
              self.kv_heads * cfg.head_dim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((self.counts[CONV], batch_size,
                                   cfg.conv_L_cache - 1, cfg.n_embd), dtype)}

    def apply_with_cache(self, params, input_ids, cache, start_pos,
                         pad_counts=None, routing=False, lengths=None):
        if pad_counts is not None:
            raise NotImplementedError(
                "LFM2MoEModel takes no left-padded batch: a conv layer "
                "would read the padding into its first real tokens "
                "(attention_mask in generate(); send rows of one length)")
        return super().apply_with_cache(params, input_ids, cache, start_pos,
                                        routing=routing, lengths=lengths)

    def verify_with_slots(self, params, input_ids, cache, positions):
        raise NotImplementedError(
            "LFM2MoEModel cannot verify a block of draft tokens: the conv "
            "layers keep their state at the lane's end alone, so rejected "
            "rows cannot be rolled back column by column as K and V are; "
            "state snapshots are ROADMAP B8's")

    def cache_partition_rules(self):
        """The conv state before the base's ``(k|v)$``, which its name
        would match: slots over the dp axes, channels whole."""
        return [(r"^conv$", (None, ("data", "expert"), None, None))] + \
            super().cache_partition_rules()

    def pipeline_spec(self):
        raise NotImplementedError(
            "LFM2MoEModel has no pipeline protocol: its layers are of "
            "several kinds in per-kind stacks, and the compiled pipeline "
            "slices one stacked tree of like layers across stages")

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """Per-kind stacks: the leading axis is the kind's own layer count
        and is left whole; attention and the dense FFN megatron-style, the
        experts over ``expert``, the conv operator and the norms whole."""
        return [
            (r"wte$", ("model", None)),
            (r"lm_head$", ("model", None)),
            (r"blocks/attn/qkv_w$", (None, None, "model")),
            (r"blocks/attn/attn_proj_w$", (None, "model", None)),
            (r"blocks/dense/(gate_w|up_w)$", (None, None, "model")),
            (r"blocks/dense/down_w$", (None, "model", None)),
            (r"blocks/moe/moe/experts/(w_gate|w_up|w_down)$",
             (None, "expert", None, None)),
            (r"blocks/", (None,)),
        ]

    def flops_per_token(self, seq_len=None):
        """Active-parameter FLOPs of a training token (6 a parameter), as
        the other families count them; serving's counts are the
        benchmark's (``chipbench/counts_lfm2.py``)."""
        cfg = self.config
        d, n = cfg.n_embd, self.counts
        hd, hk = cfg.head_dim, cfg.kv_head_count
        block = n[CONV] * 4 * d * d + \
            n[ATTN] * (d * (cfg.n_head + 2 * hk) * hd + d * d) + \
            n["dense"] * 3 * d * cfg.intermediate + \
            n["moe"] * (cfg.top_k * 3 * d * cfg.moe_intermediate_size +
                        d * cfg.num_experts)
        flops = 6 * (block + cfg.padded_vocab * d)
        if seq_len:
            flops += 12 * n[ATTN] * d * seq_len
        return flops
