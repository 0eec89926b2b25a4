"""GPT-2 MoE — the flagship MoE training model.

Same stacked-layer/lax.scan design as models/gpt2.py, with each block's dense
MLP replaced by a mixture-of-experts FFN (reference pattern:
DeepSpeed-MoE models built from deepspeed/moe/layer.py ``MoE`` replacing the
transformer MLP). Expert leaves are stacked [L, E, ...] — the layer axis scans,
the expert axis shards over the ``expert`` mesh axis; the load-balance aux loss
accumulates in the scan carry and is added to the LM loss with
``aux_loss_weight``. Only the MLP sublayer differs from GPT2Model — attention,
embedding, loss, and the scan skeleton are inherited.
"""

import dataclasses

import jax
import jax.numpy as jnp

from .gpt2 import GPT2Config, GPT2Model, _layer_norm
from ..moe.experts import ExpertFFN
from ..moe.sharded_moe import TopKGate, MOELayer


@dataclasses.dataclass(frozen=True)
class GPT2MoEConfig(GPT2Config):
    num_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 1.25
    min_capacity: int = 4
    noisy_gate_policy: str = None
    drop_tokens: bool = True
    use_rts: bool = True
    aux_loss_weight: float = 0.01


class GPT2MoEModel(GPT2Model):
    routed_experts = True     # the cache forwards hand routing stats on

    def __init__(self, config: GPT2MoEConfig = GPT2MoEConfig()):
        super().__init__(config)
        cfg = config
        self.gate = TopKGate(cfg.n_embd, cfg.num_experts, cfg.top_k,
                             cfg.capacity_factor, cfg.eval_capacity_factor,
                             cfg.min_capacity, cfg.noisy_gate_policy,
                             cfg.drop_tokens, cfg.use_rts)
        self.experts = ExpertFFN(cfg.n_embd, 4 * cfg.n_embd, cfg.num_experts,
                                 initializer_range=cfg.initializer_range)
        self.moe = MOELayer(self.gate, self.experts)

    def aux_loss_weight(self):
        return self.config.aux_loss_weight

    # ------------------------------------------------------------------ init
    def init(self, rng):
        cfg = self.config
        params = super().init(rng)
        blocks = params["blocks"]
        # dense MLP → per-layer stacked MoE (gate + experts)
        for k in ("mlp_fc_w", "mlp_fc_b", "mlp_proj_w", "mlp_proj_b"):
            del blocks[k]
        moe_rngs = jax.random.split(jax.random.fold_in(rng, 1234), cfg.n_layer)
        blocks["moe"] = jax.vmap(self.moe.init)(moe_rngs)
        return params

    # ----------------------------------------------------------------- block
    def _mlp_sublayer(self, x, p, rng, train):
        cfg = self.config
        ln2 = _layer_norm(x, p["ln2_scale"], p["ln2_bias"],
                          cfg.layer_norm_epsilon)
        y, l_aux, _ = self.moe.apply(p["moe"], ln2, rng=rng, train=train)
        return x + self._dropout(y, rng, train, 1), l_aux

    def _scan_split(self, blocks, cached):
        if not cached:      # without a cache, eval is the capacity dispatch
            return blocks, None
        moe, whole = self.moe.take_whole(blocks["moe"])
        return {**blocks, "moe": moe}, whole

    def _decode_block(self, x, layer_params, attn_fn, start_pos,
                      positions=None, extra=None, stacked=None):
        """KV-cache decode block: attention from the base class, the MoE
        FFN routed and dropless (no capacity, no noise): the reference's
        MoE inference semantics (ops/transformer/inference/
        moe_inference.py:160) on the training gate/expert params. Returns
        (x, exp_counts)."""
        cfg = self.config
        p = layer_params
        with jax.named_scope("attn"):
            x = self._attn_sublayer(x, p, None, False, attn_fn=attn_fn,
                                    start_pos=start_pos, positions=positions,
                                    extra=extra)
        with jax.named_scope("moe"):
            ln2 = _layer_norm(x, p["ln2_scale"], p["ln2_bias"],
                              cfg.layer_norm_epsilon)
            y, _, counts = self.moe.apply_routed(p["moe"], ln2,
                                                 stacked=stacked)
        return x + y, counts

    # ------------------------------------------------------------- sharding
    def partition_rules(self):
        """Expert rules must precede the base class's first-match-wins
        'blocks/' catch-all, so specific rules are inserted and the
        catch-all stays last. Stacked [L, E, ...]: layer axis ('pipe')
        scans, expert axis shards."""
        base = [r for r in super().partition_rules() if "mlp" not in r[0]]
        catchall = [r for r in base if r[0] == r"blocks/"]
        specific = [r for r in base if r[0] != r"blocks/"]
        moe_rules = [
            (r"blocks/moe/experts/wi$", ("pipe", "expert", None, None)),
            (r"blocks/moe/experts/bi$", ("pipe", "expert", None)),
            (r"blocks/moe/experts/wo$", ("pipe", "expert", None, None)),
            (r"blocks/moe/experts/bo$", ("pipe", "expert", None)),
        ]
        return specific + moe_rules + catchall

    def flops_per_token(self, seq_len=None):
        """Active-params FLOPs: dense attention + top_k experts."""
        cfg = self.config
        d, l = cfg.n_embd, cfg.n_layer
        attn_params = 4 * l * d * d
        expert_params = cfg.top_k * 2 * cfg.mlp_ratio * l * d * d
        embed = cfg.padded_vocab * d + cfg.n_positions * d
        flops = 6 * (attn_params + expert_params + embed)
        if seq_len:
            flops += 12 * l * d * seq_len
        return flops
