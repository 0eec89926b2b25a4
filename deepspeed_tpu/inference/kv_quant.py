"""Quantized KV slot pool — int8 cache lanes with per-column scales.

The serving slot pool (`serving/kv_slots.py`) is the HBM budget of a
decode replica: `[L, num_slots, max_model_len, H, hd]` in the model
dtype (token-major: `models/gpt2.py:init_kv_cache`), resident for the
process lifetime. Storing it int8 multiplies the
concurrent slots a replica can hold per HBM byte by ~3-4x (1 byte/value
plus one f32 scale per `hd` values, vs 4 for fp32), which is the
difference between 8 and 30 concurrent users per replica at the same
budget — the ZeRO++-style trade (arxiv 2306.10209) applied to KV state
instead of wire traffic, via the same `ops/quant_core` scale math.

Scale granularity is **per cache column** (one f32 scale per
`[layer, slot, position, head]`: the scales are the pool's leaves without
their trailing axis, `[L, num_slots, max_model_len, H]`; absmax over the
values of one stored row, which is one head's `hd`, or the `128 // hd`
heads that share a row where `hd` is under 128). Per-column scales are what make an *incrementally written*
quantized cache sound: prefill and decode touch whole columns, so a
write re-quantizes only the columns it produced, and the round-trip
`quantize(dequantize(q))` of every untouched column is exact (the absmax
element of a block quantizes to ±127 exactly, pinning the block's scale)
— repeated passes through the decode step never compound error on old
tokens. Each K/V value is quantized exactly once, when its column is
first written.

`QuantizedSlotPool` is a registered pytree whose first leaves mirror the
fp pool's leaf order (so shape probes like
``jax.tree.leaves(pool)[0].shape[1]`` keep meaning `num_slots`), and the
engine's slot programs (`inference/engine.py`) branch on its type at
trace time: decode dequantizes the pool inside the compiled step and
re-quantizes the updated pool on the way out; prefill and lane
copy/extract/insert touch only their lane's q/scale slices and never
materialize the full fp pool.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..ops.quant_core import INT8_QMAX, round_clip, symmetric_scale

__all__ = ["QuantizedSlotPool", "quantize_kv", "dequantize_kv",
           "quantize_pool", "dequantize_pool", "pool_nbytes"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedSlotPool:
    """int8 KV pool + per-column f32 scales.

    ``q``: the fp pool's tree with every leaf ``[..., hd]`` in int8;
    ``scales``: the same tree with the trailing ``hd`` axis dropped
    (one f32 scale per column). Flatten order puts ``q`` first so
    generic leaf-shape probes on the pool keep working.
    """
    q: Any
    scales: Any

    def tree_flatten(self):
        return (self.q, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scales = children
        return cls(q=q, scales=scales)


def quantize_kv(x):
    """One cache leaf ``[..., hd]`` -> (q int8 ``[..., hd]``,
    scales f32 ``[...]``) with per-column symmetric scales."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = symmetric_scale(absmax, INT8_QMAX)
    q = round_clip(xf / scale[..., None], -INT8_QMAX, INT8_QMAX, jnp.int8)
    return q, scale


def dequantize_kv(q, scales, dtype=jnp.float32):
    """(q, scales) -> float leaf of ``q.shape`` in ``dtype``."""
    return (q.astype(jnp.float32) * scales[..., None]).astype(dtype)


def quantize_pool(pool) -> QuantizedSlotPool:
    """fp pool tree -> QuantizedSlotPool (jit-safe)."""
    pairs = jax.tree.map(quantize_kv, pool)
    return QuantizedSlotPool(
        q=jax.tree.map(lambda p: p[0], pairs,
                       is_leaf=lambda t: isinstance(t, tuple)),
        scales=jax.tree.map(lambda p: p[1], pairs,
                            is_leaf=lambda t: isinstance(t, tuple)))


def dequantize_pool(pool: QuantizedSlotPool, dtype=jnp.float32):
    """QuantizedSlotPool -> fp pool tree in ``dtype`` (jit-safe)."""
    return jax.tree.map(lambda q, s: dequantize_kv(q, s, dtype),
                        pool.q, pool.scales)


def pool_nbytes(pool) -> int:
    """Resident bytes of a pool — fp tree or QuantizedSlotPool (q bytes +
    scale bytes). The capacity-per-HBM-byte comparison in
    benchmarks/serving.py --fleet reads this."""
    return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(pool))
