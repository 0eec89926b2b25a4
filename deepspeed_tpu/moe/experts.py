"""Local experts.

Reference: deepspeed/moe/experts.py:10 ``Experts`` — a ModuleList of per-rank
expert FFNs run in a Python loop over chunks. TPU-native design: expert
parameters are stacked along a leading [E] axis (sharded over the ``expert``
mesh axis) and all experts run as ONE batched einsum — the MXU sees a single
large batched matmul instead of E small ones.

Two entries per expert class. ``apply`` takes expert-major ``[E, C, M]``
tokens (the capacity-based training dispatch). ``apply_grouped`` takes the
routed, dropless serving layout: ``[N, M]`` rows sorted by expert with
``group_sizes [E]`` rows each, and runs every matmul as one grouped matmul
(``jax.lax.ragged_dot``; on the TPU the compiler makes it one Mosaic kernel
whose FLOPs are the routed rows', not E times them).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax


class ExpertFFN:
    """Stacked per-expert 2-layer MLP: [E, M] → [E, F] → [E, M]."""

    def __init__(self, model_dim: int, ffn_dim: int, num_experts: int,
                 activation=None, initializer_range: float = 0.02):
        self.model_dim = model_dim
        self.ffn_dim = ffn_dim
        self.num_experts = num_experts
        self.activation = activation or (lambda x: jax.nn.gelu(x, approximate=True))
        self.initializer_range = initializer_range

    def init(self, rng):
        e, m, f = self.num_experts, self.model_dim, self.ffn_dim
        k1, k2 = jax.random.split(rng)
        std = self.initializer_range
        return {
            "wi": jax.random.normal(k1, (e, m, f), jnp.float32) * std,
            "bi": jnp.zeros((e, f)),
            "wo": jax.random.normal(k2, (e, f, m), jnp.float32) * std / math.sqrt(2),
            "bo": jnp.zeros((e, m)),
        }

    def apply(self, params, x, rng=None, train=True):
        """x: [E, C, M] expert-major tokens → [E, C, M]."""
        dt = x.dtype
        h = jnp.einsum("ecm,emf->ecf", x, params["wi"].astype(dt))
        h = h + params["bi"][:, None, :].astype(dt)
        h = self.activation(h)
        y = jnp.einsum("ecf,efm->ecm", h, params["wo"].astype(dt))
        return y + params["bo"][:, None, :].astype(dt)

    def apply_grouped(self, params, x, group_sizes, expert_ids):
        """x: [N, M] rows sorted by expert, ``group_sizes`` [E] rows per
        expert, ``expert_ids`` [N] each row's expert (for the biases)
        → [N, M]."""
        dt = x.dtype
        h = lax.ragged_dot(x, params["wi"].astype(dt), group_sizes)
        h = self.activation(h + params["bi"].astype(dt)[expert_ids])
        y = lax.ragged_dot(h, params["wo"].astype(dt), group_sizes)
        return y + params["bo"].astype(dt)[expert_ids]


class GatedExpertFFN:
    """Stacked per-expert gated (SwiGLU) MLP without biases:
    ``down(silu(gate(x)) * up(x))``, [E, M] → [E, F] → [E, M] — the expert
    of the LLaMA-shaped MoE families (OLMoE, Mixtral)."""

    def __init__(self, model_dim: int, ffn_dim: int, num_experts: int,
                 initializer_range: float = 0.02):
        self.model_dim = model_dim
        self.ffn_dim = ffn_dim
        self.num_experts = num_experts
        self.initializer_range = initializer_range

    def init(self, rng):
        e, m, f = self.num_experts, self.model_dim, self.ffn_dim
        k1, k2, k3 = jax.random.split(rng, 3)
        std = self.initializer_range
        return {
            "w_gate": jax.random.normal(k1, (e, m, f), jnp.float32) * std,
            "w_up": jax.random.normal(k2, (e, m, f), jnp.float32) * std,
            "w_down": jax.random.normal(k3, (e, f, m), jnp.float32) * std
            / math.sqrt(2),
        }

    def apply(self, params, x, rng=None, train=True):
        """x: [E, C, M] expert-major tokens → [E, C, M]."""
        dt = x.dtype
        g = jnp.einsum("ecm,emf->ecf", x, params["w_gate"].astype(dt))
        u = jnp.einsum("ecm,emf->ecf", x, params["w_up"].astype(dt))
        return jnp.einsum("ecf,efm->ecm", jax.nn.silu(g) * u,
                          params["w_down"].astype(dt))

    def apply_grouped(self, params, x, group_sizes, expert_ids=None):
        """x: [N, M] rows sorted by expert, ``group_sizes`` [E] → [N, M]."""
        dt = x.dtype
        g = lax.ragged_dot(x, params["w_gate"].astype(dt), group_sizes)
        u = lax.ragged_dot(x, params["w_up"].astype(dt), group_sizes)
        return lax.ragged_dot(jax.nn.silu(g) * u,
                              params["w_down"].astype(dt), group_sizes)
