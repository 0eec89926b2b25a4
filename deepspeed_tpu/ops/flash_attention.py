"""Attention op with backend dispatch.

The TPU equivalent of the reference's fused attention kernels
(csrc/transformer/softmax_kernels.cu, csrc/transformer/inference softmax/
softmax_context): a Pallas flash-attention kernel on TPU (ops/pallas/
flash_attention.py), and an XLA reference path used on CPU (tests) and as the
numerics oracle. Loaded via FlashAttentionBuilder through the accelerator
op-builder seam.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def reference_attention(q, k, v, causal=True, mask=None, softmax_scale=None,
                        dropout_rate=0.0, dropout_rng=None, bias=None,
                        window=None):
    """Plain XLA attention. q,k,v: [B, H, T, D] (q may have Tq != Tk for
    decode). ``bias`` is an additive logits bias broadcastable to
    [B, H, Tq, Tk] (ALiBi). ``window`` (with causal) keeps only keys with
    q_pos - k_pos < window — Mistral sliding-window semantics. Numerics
    oracle for the Pallas kernel."""
    *_, t_q, d = q.shape
    t_k = k.shape[-2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / jnp.sqrt(d)
    if window is not None and not causal:
        raise ValueError("sliding window requires causal=True")
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        # offset so the last query attends to all keys (decode-friendly)
        q_pos = jnp.arange(t_q)[:, None] + (t_k - t_q)
        k_pos = jnp.arange(t_k)[None, :]
        causal_mask = q_pos >= k_pos
        if window is not None:
            causal_mask &= (q_pos - k_pos) < window
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def pallas_per_device(kernel, q, k, v, n_head, packed=False):
    """Run ``kernel(q, k, v, n_head_local)`` on each device's own block.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so under a mesh of more than one device
    the call is wrapped in ``shard_map``. Attention is independent per
    (batch row, head): the batch dim splits over the data axes and the
    head dim over 'seq' (Ulysses) and 'model', each only where it divides
    (a dim that does not divide is computed whole on every device). Axes
    an enclosing shard_map already made manual are left alone.
    ``packed``: q/k/v are [B, T, H*D], else [B, H, T, D]."""
    from ..parallel.constraints import active_mesh
    from ..parallel.topology import DP_AXES, MODEL_AXIS, SEQ_AXIS
    mesh = active_mesh()
    if mesh is None:
        return kernel(q, k, v, n_head)
    manual = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    auto = frozenset(mesh.axis_names) - manual
    if all(mesh.shape[a] == 1 for a in auto):
        return kernel(q, k, v, n_head)

    def pick(axes, n):
        axes = tuple(a for a in axes if a in auto and mesh.shape[a] > 1)
        size = math.prod(mesh.shape[a] for a in axes)
        return (axes, size) if axes and n % size == 0 else (None, 1)

    b_axes, _ = pick(DP_AXES, q.shape[0])
    h_axes, h_split = pick((SEQ_AXIS, MODEL_AXIS), n_head)
    spec = P(b_axes, None, h_axes) if packed else \
        P(b_axes, h_axes, None, None)
    return jax.shard_map(
        lambda q, k, v: kernel(q, k, v, n_head // h_split), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, axis_names=auto,
        check_vma=False)(q, k, v)


def flash_attention(q, k, v, causal=True, mask=None, softmax_scale=None,
                    dropout_rate=0.0, dropout_rng=None, backend="auto",
                    interpret=None, bias=None, window=None):
    """Dispatch: Pallas kernel on TPU, XLA reference elsewhere.

    backend="pallas" runs the Pallas kernel unconditionally and RAISES if the
    shape/features are unsupported — no silent degradation on the hot path.
    backend="xla" forces the reference path. "auto" picks Pallas only when
    running on TPU with a supported shape. On a TPU the kernel is compiled
    by Mosaic or the call fails; ``interpret=None`` selects interpreter mode
    only where the mesh's devices are not TPUs (CPU tests of the real
    kernel). ``bias`` (ALiBi etc.) currently routes to the XLA path."""
    from ..parallel.topology import on_tpu
    from .pallas import flash_attention as pallas_fa

    def pallas(interpret):
        return pallas_per_device(
            lambda q, k, v, _h: pallas_fa.flash_attention(
                q, k, v, causal, softmax_scale, None, None, interpret,
                window),
            q, k, v, q.shape[1])

    if backend == "pallas":
        if bias is not None or not pallas_fa.supported(
                q, k, causal=causal, mask=mask, dropout_rate=dropout_rate,
                window=window):
            raise ValueError(
                f"pallas flash attention does not support this call "
                f"(q={q.shape} k={k.shape} causal={causal} "
                f"mask={'yes' if mask is not None else 'no'} "
                f"bias={'yes' if bias is not None else 'no'} "
                f"window={window} "
                f"dropout={dropout_rate}); pass backend='xla' explicitly")
        return pallas(not on_tpu() if interpret is None else interpret)
    if backend == "auto" and on_tpu():
        if bias is None and pallas_fa.supported(q, k, causal=causal,
                                                mask=mask,
                                                dropout_rate=dropout_rate,
                                                window=window):
            return pallas(False)
        _warn_xla_fallback(q, bias)
    if backend not in ("auto", "xla"):
        raise ValueError(f"unknown attention backend {backend!r}")
    return reference_attention(q, k, v, causal=causal, mask=mask,
                               softmax_scale=softmax_scale,
                               dropout_rate=dropout_rate,
                               dropout_rng=dropout_rng, bias=bias,
                               window=window)


_warned_fallback = False


def _warn_xla_fallback(q, bias):
    """One-time visibility for the on-TPU XLA fallback: the dense path
    materializes [B, H, Tq, Tk] fp32 logits — a real memory/bandwidth cliff
    vs the Pallas kernel (why round-1 shipped at 16% MFU unnoticed)."""
    global _warned_fallback
    if _warned_fallback:
        return
    _warned_fallback = True
    from ..utils.logging import logger
    why = "attention bias (ALiBi)" if bias is not None else \
        f"unsupported shape {tuple(q.shape)}"
    logger.warning(
        f"flash_attention: falling back to the dense XLA path on TPU "
        f"({why} is not supported by the Pallas kernel); this "
        f"materializes full [B,H,Tq,Tk] fp32 attention logits")


def get_ops(backend: str):
    return SimpleNamespace(flash_attention=flash_attention,
                           reference_attention=reference_attention)
