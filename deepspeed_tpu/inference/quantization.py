"""Int8 weight-only quantized serving.

Capability match for the reference's int8 inference path
(module_inject/replace_module.py:140 ``GroupQuantizer`` quantizes fused
weights at injection time; csrc/transformer/inference/csrc/dequantize.cu:195
dequantizes inside the fused GEMMs). TPU-native re-design: a quantized
weight is a registered pytree node — int8 payload + per-group fp32 scales —
whose ``astype()`` IS the dequant. Model code already touches every matmul
weight through ``.astype(compute_dtype)`` (the mixed-precision contract), so
dequant lands exactly where the reference's kernel fusion puts it, and XLA
fuses the int8→bf16 multiply-by-scale into the consumer matmul's operand
pipeline. Memory wins: weights resident in HBM at ~half the bf16 bytes —
the decode path is weight-bandwidth-bound, so resident-int8 also lifts
tokens/s at small batch.

Grouping is along the LAST axis (per-row groups), which keeps the leading
layer axis of stacked [L, ...] leaves intact — ``lax.scan`` over layers
slices the q/scale leaves coherently, and tensor-parallel shardings on
non-last axes apply unchanged.
"""

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.logging import log_dist


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """int8 weight + per-group scales; ``astype`` dequantizes.

    q: int8, the original weight shape.
    scale: fp32, shape = q.shape[:-1] + (groups,).
    """

    def __init__(self, q, scale):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):  # reported dtype is the payload's
        return self.q.dtype

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes

    def astype(self, dt):
        """Dequantize: the serving matmuls call this in place of the usual
        bf16 cast (reference dequantize.cu:195 inside qkv/mlp GEMMs)."""
        group = self.q.shape[-1] // self.scale.shape[-1]
        w = self.q.astype(jnp.float32) * jnp.repeat(self.scale, group,
                                                    axis=-1)
        return w.astype(dt)


def is_quantized(x) -> bool:
    return isinstance(x, QuantizedWeight)


def quantize_leaf(w, group_size: int = 64, bits: int = 8) -> QuantizedWeight:
    """Symmetric per-group int8 quantization along the last axis
    (reference GroupQuantizer semantics, replace_module.py:140)."""
    assert bits == 8, "weight-only serving supports 8-bit payloads"
    last = w.shape[-1]
    gs = group_size if last % group_size == 0 else last
    groups = last // gs
    wg = w.astype(jnp.float32).reshape(*w.shape[:-1], groups, gs)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=-1), 1e-8) / qmax
    q = jnp.round(wg / scale[..., None]).clip(-qmax, qmax)
    return QuantizedWeight(q.reshape(w.shape).astype(jnp.int8), scale)


def _default_predicate(path, leaf) -> bool:
    """Quantize matmul-shaped floating weights of the transformer blocks —
    the reference GroupQuantizer scope (replace_module.py:140 quantizes
    fused layer weights, not embeddings/norms/biases). Stacked [L, ...]
    leaves make per-layer vectors LOOK 2-D, so the filter requires a real
    matrix (both trailing dims substantial) AND rejects norm/bias names.
    Also excluded: token/position embeddings (wte doubles as the logit
    head, the most quantization-sensitive matmul, and wpe is indexed with
    dynamic_slice before any dtype cast)."""
    if getattr(leaf, "ndim", 0) < 2:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    if min(leaf.shape[-1], leaf.shape[-2]) < 16:
        return False  # [L, d] norm/bias stacks, tiny projections
    names = [str(getattr(k, "key", k)) for k in path]
    last = names[-1] if names else ""
    if last.endswith(("_b", "bias", "scale", "norm", "gamma", "beta")):
        return False
    # ``hc_``: a widened residual's maps (models/xing.py), a few columns
    # that steer every stream of every sublayer, computed in float32
    skip = ("wpe", "wte", "embed", "position", "lm_head", "hc_")
    return not any(s in n for n in names for s in skip)


def quantize_tree(params, group_size: int = 64, bits: int = 8,
                  predicate=_default_predicate):
    """Quantize the selected leaves of a params pytree (jit-safe).
    Idempotent: already-quantized nodes pass through untouched."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: x if is_quantized(x)
        else quantize_leaf(x, group_size, bits) if predicate(kp, x) else x,
        params, is_leaf=lambda x: is_quantized(x))


def quantize_resident(params, shardings, group_size: int = 64, bits: int = 8,
                      predicate=_default_predicate, cast=None, programs=None):
    """``quantize_tree`` into the serving layout a leaf at a time, each
    selected leaf through a program of its own, so that the fp tree and
    the int8 tree are never both whole on the device. One program over
    the tree holds both, and a program that also MAKES the tree holds
    every large leaf in float32 across its two passes (absmax, then
    divide): 18 GB for a 10 GB model on a 16 GB chip; a stacked leaf goes
    through a slice of its leading (layer) axis at a time for the same
    reason (a 3.2 GB leaf of experts whole asked for 10.5 GB of
    temporaries; PERF.md, PR 36). Groups lie along the last axis, so the
    payload is ``quantize_tree``'s.

    ``cast=None``: ``params`` are the CALLER'S TO GIVE, on the device in
    the serving layout already (an engine's own init, a loaded
    checkpoint): each selected leaf is deleted once its program is
    dispatched (its bytes cannot be aliased to an int8 payload, so
    donating it frees nothing sooner) and the peak is the tree plus one
    payload; the other leaves are returned as they are. ``cast`` given
    (``recast``): ``params`` are anyone's, anywhere (a trainer's, the
    host's); every leaf goes through ``cast`` into its sharding, the
    selected ones on into int8 in the same program, and nothing of the
    caller's is touched. ``shardings``: ``quantized_shardings`` of the
    tree. ``programs``: a dict the caller keeps, so that a second call
    (a refresh every optimizer step) compiles nothing."""
    programs = {} if programs is None else programs
    consume = cast is None

    def one(kp, x, sh):
        selected = not is_quantized(x) and predicate(kp, x)
        if is_quantized(x) or (consume and not selected):
            return x
        key = (jax.tree_util.keystr(kp), consume)
        if key not in programs:
            def leaf(w):
                w = w if consume else cast(w)
                return quantize_leaf(w, group_size, bits) if selected else w
            programs[key] = jax.jit(
                (lambda w: jax.lax.map(leaf, w))
                if selected and x.ndim > 2 else leaf, out_shardings=sh)
        out = programs[key](x)
        if consume:
            x.delete()
        return out
    return jax.tree_util.tree_map_with_path(one, params, shardings,
                                            is_leaf=is_quantized)


def quantized_shardings(param_shardings, param_shapes,
                        predicate=_default_predicate):
    """Sharding tree matching ``quantize_tree``'s output structure: q keeps
    the weight's spec; scales replicate their (possibly non-divisible)
    group axis while keeping leading-axis sharding (tp/pp)."""
    def one(kp, sh, shape_leaf):
        if not predicate(kp, shape_leaf):
            return sh
        spec = tuple(sh.spec) if sh.spec else ()
        spec = spec + (None,) * (len(shape_leaf.shape) - len(spec))
        scale_spec = spec[:-1] + (None,)
        return QuantizedWeight(
            NamedSharding(sh.mesh, P(*spec)),
            NamedSharding(sh.mesh, P(*scale_spec)))
    return jax.tree_util.tree_map_with_path(one, param_shardings,
                                            param_shapes)


def tree_nbytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def describe(params) -> str:
    n_q = sum(1 for kp, x in
              jax.tree_util.tree_flatten_with_path(
                  params, is_leaf=is_quantized)[0] if is_quantized(x))
    return (f"int8 weight-only serving: {n_q} quantized weights, "
            f"{tree_nbytes(params) / 2**20:.1f} MiB resident")
