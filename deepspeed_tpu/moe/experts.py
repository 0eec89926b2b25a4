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
whose FLOPs are the routed rows', not E times them). With ``layer`` given,
the matmul leaves are the STACKED ``[L, E, ...]`` leaves of all L layers and
are read where they lie (``_groups``). On one TPU the gated experts' three
products are ONE kernel of the repo's own where its shape rule takes them
(``_rows_kernel``; ``ops/pallas/grouped_matmul.py``), and ``grouped_matmuls``
counts how many of the traced products it took.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.constraints import active_mesh
from ..parallel.topology import EXPERT_AXIS

#: the grouped products traced in this process, and those of them that the
#: rows kernel took (three a ``GatedExpertFFN.apply_grouped`` call, two an
#: ``ExpertFFN``'s): what the serving engine's shut-down line reads
_TRACED = {"products": 0, "rows_kernel": 0}


def grouped_matmuls():
    """``(took the rows kernel, all)`` of the grouped products traced so
    far in this process; ``(0, 0)`` for a model without routed experts."""
    return _TRACED["rows_kernel"], _TRACED["products"]


def _groups(w, layer, dt):
    """A grouped matmul's right-hand side. ``w`` [E, K, N] is one layer's
    leaf. With ``layer`` given it is the stacked leaf [L, E, K, N] of all L
    layers, read where it lies: seen as L * E groups (a reshape of leading
    axes, no copy) of which ``_group_sizes`` gives only that layer's E any
    rows. The TPU kernel visits the row tiles that exist and fetches each
    one's weight block by group id, so empty groups are never visited; a
    layer's [E, K, N] sliced out of the stack first is a copy of all of it,
    since a slice can not fuse into the kernel's custom call (PERF.md,
    PR 34). Compute follows the parameters' type, so the cast is none; a
    real one would convert all L layers in every layer."""
    if layer is not None:
        w = w.reshape((-1,) + w.shape[2:])
    return w.astype(dt)


def _group_sizes(group_sizes, w, layer):
    """``group_sizes`` [E] for ``_groups(w, layer, .)``: placed at
    ``layer * E`` of a zero [L * E] vector where ``w`` is stacked, so the
    sorted rows keep their offsets."""
    if layer is None:
        return group_sizes
    l, e = w.shape[:2]
    return lax.dynamic_update_slice(
        jnp.zeros((l * e,), group_sizes.dtype), group_sizes, (layer * e,))


def _whole_tiles(x, sizes, expert_ids=None):
    """``x`` [N, M] with zero rows appended up to a multiple of 8, counted
    to the last group (their outputs are cut off again; a zero row reads a
    weight block and adds nothing). On the TPU ``lax.ragged_dot`` over a
    float32 row count that is NOT a multiple of 8 returns garbage: 1-7 and
    12 rows read 0.86-1.0 off a gather and einsum, 8, 16 and 32 read
    2.5e-7, at 64 and at 128 groups of [2048, 1536] (bf16 rows 1-7 are
    right; chip run, PERF.md, PR 36). Four picks a token of a one-slot
    pool are such a count. A count that is a multiple already (every
    serving cell's) is left as it is, and so is its program."""
    pad = -x.shape[0] % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        sizes = sizes.at[-1].add(pad)
        if expert_ids is not None:
            expert_ids = jnp.pad(expert_ids, (0, pad))
    return x, sizes, expert_ids


def _rows_kernel(x, leaves):
    """The row tile where the rows kernel takes the three
    products of a gated expert over rows ``x`` [N, K] and ``leaves``
    (gate, up, down; [E, ...] or stacked [L, E, ...]), else ``None``:
    they stay ``lax.ragged_dot``'s. Decided at trace time on what is seen
    here. No TPU (the CPU programs are what they were); a mesh whose
    ``expert`` axis shards the groups (the kernel is one device's); a leaf
    that is no plain array of the rows' type (an int8 ``QuantizedWeight``
    dequantises into a fresh buffer, and a cast would convert every
    layer's leaf in every layer); and what the kernel's own shape rule
    leaves out (``grouped_matmul.choose``, with the sweep it rests on).
    The kernel's module is imported here and nowhere else, so a process
    without routed experts on a TPU never loads it."""
    from ..parallel.topology import on_tpu
    mesh = active_mesh()
    if not on_tpu() or \
            (mesh is not None and mesh.shape.get(EXPERT_AXIS, 1) > 1) or \
            not all(isinstance(w, jax.Array) and w.dtype == x.dtype
                    for w in leaves):
        return None
    from ..ops.pallas.grouped_matmul import choose
    return choose(x.shape[0], x.shape[1], leaves[0].shape[-1], x.dtype,
                  "tpu")


class ExpertFFN:
    """Stacked per-expert 2-layer MLP: [E, M] → [E, F] → [E, M]."""

    matmul_leaves = ("wi", "wo")     # what apply_grouped can take stacked

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

    def apply_grouped(self, params, x, group_sizes, expert_ids, layer=None):
        """x: [N, M] rows sorted by expert, ``group_sizes`` [E] rows per
        expert, ``expert_ids`` [N] each row's expert (for the biases)
        → [N, M]. ``layer``: ``wi`` and ``wo`` are the stacked [L, E, ...]
        leaves and this is layer ``layer`` of them (the biases stay this
        layer's own [E, ...])."""
        dt, n = x.dtype, x.shape[0]
        _TRACED["products"] += 2
        x, sizes, expert_ids = _whole_tiles(
            x, _group_sizes(group_sizes, params["wi"], layer), expert_ids)
        h = lax.ragged_dot(x, _groups(params["wi"], layer, dt), sizes)
        h = self.activation(h + params["bi"].astype(dt)[expert_ids])
        y = lax.ragged_dot(h, _groups(params["wo"], layer, dt), sizes)
        y = y + params["bo"].astype(dt)[expert_ids]
        return y if y.shape[0] == n else y[:n]


class GatedExpertFFN:
    """Stacked per-expert gated (SwiGLU) MLP without biases:
    ``down(silu(gate(x)) * up(x))``, [E, M] → [E, F] → [E, M] — the expert
    of the LLaMA-shaped MoE families (OLMoE, Mixtral)."""

    matmul_leaves = ("w_gate", "w_up", "w_down")

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

    def apply_grouped(self, params, x, group_sizes, expert_ids=None,
                      layer=None):
        """x: [N, M] rows sorted by expert, ``group_sizes`` [E] → [N, M].
        ``layer``: the three leaves are the stacked [L, E, ...] leaves and
        this is layer ``layer`` of them."""
        dt, n = x.dtype, x.shape[0]
        leaves = [params[name] for name in self.matmul_leaves]
        _TRACED["products"] += 3
        tile = _rows_kernel(x, leaves)
        if tile is not None:
            from ..ops.pallas.grouped_matmul import gated_rows
            _TRACED["rows_kernel"] += 3
            # the stack as L * E groups (``_groups``), this layer's from l * E
            first = 0 if layer is None else layer * leaves[0].shape[1]
            return gated_rows(x, *(_groups(w, layer, dt) for w in leaves),
                              group_sizes, first, tile=tile)
        x, sizes, _ = _whole_tiles(
            x, _group_sizes(group_sizes, params["w_gate"], layer))
        g = lax.ragged_dot(x, _groups(params["w_gate"], layer, dt), sizes)
        u = lax.ragged_dot(x, _groups(params["w_up"], layer, dt), sizes)
        y = lax.ragged_dot(jax.nn.silu(g) * u,
                           _groups(params["w_down"], layer, dt), sizes)
        return y if y.shape[0] == n else y[:n]
