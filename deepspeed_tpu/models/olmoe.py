"""OLMoE — the LLaMA-shaped block with a routed mixture-of-experts MLP.

allenai/OLMoE-1B-7B (``model_type`` olmoe): RMSNorm, rotary (rotate-half),
multi-head attention whose q and k PROJECTIONS are RMS-normalised before
they are split into heads (one gain per projection channel), no biases, and
in place of the SwiGLU MLP ``num_experts`` SwiGLU experts of which every
token takes ``top_k`` — softmax over all experts in float32, the picked
probabilities NOT renormalised (``norm_topk_prob`` false), no shared
expert, no capacity: nothing is dropped. Untied output head.

Built on ``LlamaModel``: its attention sublayer with the q/k-norm hook, the
expert layer of ``moe/`` for the MLP. Expert leaves are stacked
``[L, E, ...]`` as in ``gpt2_moe.py`` (the layer axis scans, the expert axis
shards over ``expert``). Serving (``train=False``: ``engine.forward`` and
both cache forwards) runs ``MOELayer.apply_routed``; training keeps the
capacity-based ``MOELayer.apply`` with its load-balance loss.
"""

import dataclasses

import jax
import jax.numpy as jnp

from .llama import LlamaConfig, LlamaModel, _rms_norm
from ..moe.experts import GatedExpertFFN
from ..moe.sharded_moe import MOELayer, TopKGate


@dataclasses.dataclass(frozen=True)
class OLMoEConfig(LlamaConfig):
    vocab_size: int = 50304
    n_positions: int = 4096
    n_embd: int = 2048
    n_layer: int = 16
    n_head: int = 16
    mlp_hidden: int = 1024              # width of ONE expert
    num_experts: int = 64
    top_k: int = 8
    norm_topk_prob: bool = False        # renormalise the picked gates
    # training only (the capacity-based dispatch and its balance loss)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


OLMOE_1B_7B = OLMoEConfig()


class OLMoEModel(LlamaModel):
    routed_experts = True     # the cache forwards hand routing stats on

    def __init__(self, config: OLMoEConfig = OLMOE_1B_7B):
        super().__init__(config)
        cfg = config
        self.gate = TopKGate(cfg.n_embd, cfg.num_experts, cfg.top_k,
                             cfg.capacity_factor)
        self.experts = GatedExpertFFN(
            cfg.n_embd, cfg.intermediate, cfg.num_experts,
            initializer_range=cfg.initializer_range)
        self.moe = MOELayer(self.gate, self.experts)

    def aux_loss_weight(self):
        return self.config.aux_loss_weight

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        params = super().init(rng)
        blocks = params["blocks"]
        for k in ("gate_w", "up_w", "down_w"):      # dense MLP → experts
            del blocks[k]
        d = cfg.n_embd
        blocks["q_norm_scale"] = jnp.ones((cfg.n_layer, d))
        blocks["k_norm_scale"] = jnp.ones(
            (cfg.n_layer, cfg.kv_head_count * cfg.head_dim))
        moe_rngs = jax.random.split(jax.random.fold_in(rng, 1234), cfg.n_layer)
        blocks["moe"] = jax.vmap(self.moe.init)(moe_rngs)
        return params

    # ----------------------------------------------------------------- block
    def _qk_norm(self, q, k, p):
        eps = self.config.layer_norm_epsilon
        return (_rms_norm(q, p["q_norm_scale"], eps),
                _rms_norm(k, p["k_norm_scale"], eps))

    def _scan_split(self, blocks, cached):
        moe, whole = self.moe.take_whole(blocks["moe"])
        return {**blocks, "moe": moe}, whole

    def _routed_mlp(self, x, p, stacked=None):
        """(x + experts(RMSNorm(x)), exp_counts): every token routed."""
        cfg = self.config
        ln2 = _rms_norm(x, p["ln2_scale"], cfg.layer_norm_epsilon)
        y, _, counts = self.moe.apply_routed(
            p["moe"], ln2, renormalize=cfg.norm_topk_prob, stacked=stacked)
        return x + y, counts

    def _mlp_sublayer(self, x, p, rng, train, stacked=None):
        if not train:
            return self._routed_mlp(x, p, stacked)[0], jnp.float32(0.0)
        cfg = self.config
        ln2 = _rms_norm(x, p["ln2_scale"], cfg.layer_norm_epsilon)
        y, l_aux, _ = self.moe.apply(p["moe"], ln2, rng=rng, train=True)
        return x + self._dropout(y, rng, train, 1), l_aux

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None, stacked=None):
        """Returns (x, exp_counts): the cache forwards hand the counts on."""
        with jax.named_scope("attn"):
            x = self._attn_sublayer(x, layer_params, None, False,
                                    attn_fn=attn_fn, start_pos=start_pos,
                                    positions=positions, extra=extra)
        with jax.named_scope("moe"):
            return self._routed_mlp(x, layer_params, stacked)

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """Expert rules before the base class's first-match-wins 'blocks/'
        catch-all. Stacked [L, E, ...]: layer axis ('pipe') scans, expert
        axis shards."""
        base = [r for r in super().partition_rules()
                if "gate_w" not in r[0] and "down_w" not in r[0]]
        catchall = [r for r in base if r[0] == r"blocks/"]
        specific = [r for r in base if r[0] != r"blocks/"]
        moe_rules = [
            (r"blocks/moe/experts/(w_gate|w_up|w_down)$",
             ("pipe", "expert", None, None)),
        ]
        return specific + moe_rules + catchall

    def flops_per_token(self, seq_len=None):
        """Active-params FLOPs: attention + top_k experts + router + head."""
        cfg = self.config
        d, l, f = cfg.n_embd, cfg.n_layer, cfg.intermediate
        block = l * (4 * d * d + cfg.top_k * 3 * d * f + d * cfg.num_experts)
        flops = 6 * (block + cfg.padded_vocab * d)
        if seq_len:
            flops += 12 * l * d * seq_len
        return flops
