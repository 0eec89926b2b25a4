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
`quantize(dequantize(q))` of every untouched column gives its int8 values
back exactly (the absmax element of a block quantizes to ±127 exactly,
pinning the block's scale; the scale itself may come back one float32
ulp off, `(127 s) / 127`, which requantizes to the same int8 values:
`tests/unit/test_pool_donation.py`) — repeated passes through the decode
step never compound error on old tokens. Each K/V value is quantized
exactly once, when its column is first written.

`QuantizedSlotPool` is a registered pytree whose first leaves mirror the
fp pool's leaf order (so shape probes like
``jax.tree.leaves(pool)[0].shape[1]`` keep meaning `num_slots`). How a
pool is stored is decided in this module alone: the engine's slot
programs (`inference/engine.py`) go through the converters at the end of
it, which take either flavour and tell them apart by type at trace time.
Decode and verify see the whole pool as fp (`pool_to_fp`) and store it
back in the flavour it came in (`pool_from_fp`); prefill and lane
copy/extract/insert touch only their lane's q/scale slices (`read_lane`,
`write_lane`, `insert_lane`) and never materialize the full fp pool.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.quant_core import INT8_QMAX, round_clip, symmetric_scale

__all__ = ["QuantizedSlotPool", "quantize_kv", "dequantize_kv",
           "quantize_pool", "dequantize_pool", "pool_nbytes",
           "is_quantized_pool", "init_pool", "lane_slice", "lane_update",
           "read_lane", "write_lane", "insert_lane", "pool_to_fp",
           "pool_from_fp"]


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


# --------------------------------------------------------------------------
# either flavour: what the engine's pool programs call (all jit-safe)
# --------------------------------------------------------------------------

def is_quantized_pool(pool) -> bool:
    return isinstance(pool, QuantizedSlotPool)


def init_pool(model, num_slots, max_len, dtype, quantize=False):
    """``model``'s empty KV pool, stored fp in ``dtype`` or int8."""
    fp = model.init_kv_cache(num_slots, max_len, dtype=dtype)
    return quantize_pool(fp) if quantize else fp


def pool_to_fp(pool, dtype):
    """A whole pool (or lane) as an fp tree: an fp pool as it is, an int8
    pool dequantized into ``dtype``."""
    return dequantize_pool(pool, dtype) if is_quantized_pool(pool) else pool


def pool_from_fp(fp, like):
    """An fp tree stored in the flavour of ``like``. Re-quantizing a pool
    that ``pool_to_fp`` dequantized gives every column that was not
    written since its int8 values back (per-column scales), so old tokens
    never re-accumulate quantization error."""
    return quantize_pool(fp) if is_quantized_pool(like) else fp


def lane_slice(leaf, slot):
    """One slot's lane of a pool leaf (slot axis is 1): ``[d0, 1, ...]``."""
    start = (0, slot) + (0,) * (leaf.ndim - 2)
    sizes = (leaf.shape[0], 1) + leaf.shape[2:]
    return lax.dynamic_slice(leaf, start, sizes)


def lane_update(leaf, lane, slot):
    """Write a lane back into a pool leaf at slot ``slot``."""
    start = (0, slot) + (0,) * (leaf.ndim - 2)
    return lax.dynamic_update_slice(leaf, lane.astype(leaf.dtype), start)


def read_lane(pool, slot, dtype):
    """One slot's lane as an fp mini-cache ``[L, 1, max_len, H, hd]``
    (dequantizes just the lane of an int8 pool)."""
    lane = jax.tree.map(lambda leaf: lane_slice(leaf, slot), pool)
    return pool_to_fp(lane, dtype)


def insert_lane(pool, lane, slot, dtype=None):
    """A lane of either flavour into slot ``slot`` of a pool of either: a
    lane of the pool's own flavour is copied verbatim (q and scales, no
    requantization); an fp lane quantizes on the way into an int8 pool,
    an int8 lane dequantizes into ``dtype`` (read in that case alone) for
    an fp pool."""
    if is_quantized_pool(lane) != is_quantized_pool(pool):
        lane = pool_from_fp(pool_to_fp(lane, dtype), pool)
    return jax.tree.map(lambda pc, lc: lane_update(pc, lc, slot), pool, lane)


def write_lane(pool, mini, slot):
    """Write an fp mini-cache into slot ``slot`` (re-quantizes only this
    lane of an int8 pool — per-column scales keep the round-trip of
    untouched columns exact)."""
    return insert_lane(pool, mini, slot)
