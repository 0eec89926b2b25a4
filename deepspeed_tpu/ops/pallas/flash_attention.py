"""Pallas-TPU flash attention (forward + backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(reference csrc/transformer/softmax_kernels.cu and
csrc/transformer/ds_transformer_cuda.cpp:1037 fused layer): blocked
online-softmax attention that never materializes the [T, T] probability
matrix in HBM.

Design (not a port — shaped by the TPU memory hierarchy AND by profiling):
- (batch, head) pairs are folded: each grid step processes GH heads at once
  with batched ``dot_general``s. Round-2 profiling showed the per-grid-step
  overhead dominating at GPT-2 scale (B=8, H=12, T=1024, D=64: the fwd
  kernel ran in the SAME wall time for causal and non-causal, and for every
  block size — the per-step matmuls were ~1.4 us of MXU work against ~2.6 us
  of step overhead). Folding GH=4..8 heads per step cuts the grid 4-8x and
  makes each step's matmul [GH, BQ, D] x [GH, D, BK] — big enough to hide
  the overhead.
- grid = (BH/GH, num_q_blocks). Each program holds GH heads' Q block and
  their FULL K/V in VMEM and runs an online-softmax ``fori_loop`` over K/V
  blocks; K/V stay resident across the inner q-block grid dim.
- causal masking prunes the K/V loop at the diagonal (dynamic trip count).
- softmax statistics (m, l) are fp32 [GH, BQ, 1]; matmuls run on the MXU
  with ``preferred_element_type=f32``; inputs stay bf16.
- backward recomputes P from (q, k, lse) — flash-attention style — with two
  kernels: dq (grid over q blocks) and dk/dv (grid over k blocks), plus a
  cheap XLA precompute of delta = rowsum(dO * O).

The XLA reference path (ops/flash_attention.reference_attention) is the
numerics oracle; tests compare both fwd and grads (interpret mode on CPU).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_packed import ATTN_LSE_NAME, ATTN_OUT_NAME

NEG_INF = -1e30
# batched dims: [GH,M,D] x [GH,N,D] -> [GH,M,N] (contract last, batch first)
_BNT = (((2,), (2,)), ((0,), (0,)))
# batched dims: [GH,M,K] x [GH,K,N] -> [GH,M,N]
_BNN = (((2,), (1,)), ((0,), (0,)))
# batched dims: [GH,K,M] x [GH,K,N] -> [GH,M,N] (contract first non-batch)
_BTN = (((1,), (1,)), ((0,), (0,)))

# Pallas double-buffers grid-windowed inputs, and Mosaic needs stack room
# for fp32 temporaries — budget well under the 16M scoped-vmem limit (the
# train-step context proved tighter than a standalone call: GH=4 at
# T=1024/D=64 compiled alone but blew scoped vmem inside the fused step).
# Re-checked with libtpu 0.0.34 (PR 24): at the folds this budget picks,
# the whole GPT-2 350M train step (micro 8, T=1024) compiles for a v5e with
# these kernels in it, forward and fused backward.
# Env override for experiments: DSTPU_FLASH_VMEM_BUDGET (bytes).
import os as _os
_VMEM_BUDGET = int(_os.environ.get("DSTPU_FLASH_VMEM_BUDGET",
                                   3 * 1024 * 1024))


def _mask(s, q_off, k_off, gh, block_q, block_k, window):
    """Causal (+ optional sliding-window) keep-mask applied to one
    [GH, BQ, BK] logits block — shared by the resident and streamed
    fwd/dq/dkv kernels."""
    q_pos = q_off + lax.broadcasted_iota(jnp.int32, (gh, block_q, block_k), 1)
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (gh, block_q, block_k), 2)
    keep = q_pos >= k_pos
    if window is not None:
        keep &= (q_pos - k_pos) < window
    return jnp.where(keep, s, NEG_INF)


def _pick_blocks(t: int):
    """Largest preferred block sizes that divide t (t % 128 == 0 is already
    guaranteed by supported()/_resolve, so 128 always works). Env override
    for experiments: DSTPU_FLASH_BQ / DSTPU_FLASH_BK."""
    bq = next(b for b in (512, 256, 128) if t % b == 0)
    bk = next(b for b in (256, 128) if t % b == 0)
    bq = int(_os.environ.get("DSTPU_FLASH_BQ", bq))
    bk = int(_os.environ.get("DSTPU_FLASH_BK", bk))
    return min(t, bq), min(t, bk)


def _pick_gh(bh: int, t: int, d: int, bq: int, bk: int,
             itemsize: int = 2):
    """Largest head fold whose resident footprint fits the VMEM budget,
    or None when not even one head fits (the caller then takes the
    streamed kernels). ``itemsize`` is the q/k/v element size (2 for
    bf16, 4 for fp32 — fp32 inputs double the K/V, q/o and p
    footprints)."""
    for gh in (8, 4, 2, 1):
        if bh % gh:
            continue
        s_bytes = gh * bq * bk * (4 + itemsize)   # fp32 s + p copy
        kv_bytes = 2 * gh * t * d * itemsize
        qo_bytes = gh * bq * d * (2 * itemsize + 4)   # q, o, fp32 acc
        if s_bytes + kv_bytes + qo_bytes <= _VMEM_BUDGET:
            return gh
    return None


# From this K/V footprint up the resident kernels (full K/V per head in VMEM)
# give way to the streamed kernels (k-blocks as a grid dimension, online
# accumulators in scratch) — the long-context single-chip path.
# The bound is inclusive: at exactly 1 MiB (T=8192, D=64, bf16) the fused
# resident backward needs ~2x what Mosaic's scoped VMEM holds.
_RESIDENT_MAX_KV_BYTES = 1024 * 1024


def _streamed(t: int, d: int, itemsize: int) -> bool:
    return t * d * itemsize >= _RESIDENT_MAX_KV_BYTES


def _pick_gh_streamed(bh: int, d: int, bq: int, bk: int,
                      itemsize: int = 2) -> int:
    for gh in (8, 4, 2, 1):
        if bh % gh:
            continue
        s_bytes = gh * bq * bk * (4 + itemsize)
        kv_bytes = 2 * gh * bk * d * itemsize * 2  # double-buffered blocks
        qo_bytes = gh * bq * d * (2 * itemsize + 4 * 3)  # q, o, acc+m+l f32
        if s_bytes + kv_bytes + qo_bytes <= _VMEM_BUDGET:
            return gh
    # one head is the floor: its footprint does not grow with T, and it
    # compiles for the v5e at the widest shape supported() admits (d=256,
    # fp32 — a case of tests/unit/test_chip_compile.py)
    return 1


def supported(q, k, causal=True, mask=None, dropout_rate=0.0,
              window=None) -> bool:
    """Static shape/feature check for the Pallas path."""
    if mask is not None or dropout_rate > 0.0:
        return False
    if window is not None and (not causal or window <= 0):
        return False
    if q.ndim != 4 or q.shape[-2] != k.shape[-2]:
        return False
    if q.shape[1] != k.shape[1]:        # GQA callers repeat kv heads first
        return False
    t, d = q.shape[-2], q.shape[-1]
    # short sequences: full K/V resident per head; long sequences: streamed
    # k-block grid. Cap the total so one (b, h) pair stays addressable.
    if t > 128 * 1024:
        return False
    return t >= 128 and t % 128 == 0 and d % 8 == 0 and d <= 256


# --------------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                block_q, block_k, t_k, gh, window):
    q = q_ref[...]                               # [GH, BQ, D]
    q_off = pl.program_id(1) * block_q
    nk = pl.cdiv(q_off + block_q, block_k) if causal else t_k // block_k
    # sliding window: keys below q_off - window + 1 are dead for this q block
    # (window implies causal — enforced in _resolve)
    j0 = (jnp.maximum(q_off - window + 1, 0) // block_k
          if causal and window is not None else 0)

    def body(j, carry):
        acc, m, l = carry
        k_j = k_ref[:, pl.ds(j * block_k, block_k), :]   # [GH, BK, D]
        v_j = v_ref[:, pl.ds(j * block_k, block_k), :]
        s = lax.dot_general(q, k_j, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, j * block_k, gh, block_q, block_k, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            p.astype(v_j.dtype), v_j, _BNN, preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((gh, block_q, q.shape[-1]), jnp.float32)
    m0 = jnp.full((gh, block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((gh, block_q, 1), jnp.float32)
    acc, m, l = lax.fori_loop(j0, nk, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l)


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret, window=None):
    b, h, t, d = q.shape
    bh = b * h
    qf, kf, vf = (x.reshape(bh, t, d) for x in (q, k, v))
    gh = None if _streamed(t, d, q.dtype.itemsize) else (
        int(_os.environ.get("DSTPU_FLASH_GH_FWD", 0)) or
        _pick_gh(bh, t, d, block_q, block_k, q.dtype.itemsize))
    if gh is None:
        gh = _pick_gh_streamed(bh, d, block_q, block_k,
                               q.dtype.itemsize)
        out, lse = _fwd_streamed(qf, kf, vf, causal, scale, block_q, block_k,
                                 interpret, window, gh)
        return out.reshape(b, h, t, d), lse.reshape(b, h, t, 1)
    grid = (bh // gh, t // block_q)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, t_k=t, gh=gh,
                               window=window)
    flops = 4 * bh * t * t * d // (2 if causal else 1)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gh, block_q, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((gh, t, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((gh, t, d), lambda n, i: (n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gh, block_q, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((gh, block_q, 1), lambda n, i: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(flops),
            bytes_accessed=4 * bh * t * d * q.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t, 1)


# -------------------------------------------------------------------- backward

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc_ref, *,
                      causal, scale, block_q, block_k, t_q, gh, window):
    """One-pass backward: grid over k blocks (sequential), inner loop over
    q blocks. Computes s/p ONCE per (q, k) block pair and derives dv, dk
    (local accumulators) AND dq (f32 scratch [GH, T, D] persisting across
    the k-block grid dim — initialized at j==0, flushed at j==last).
    Versus the classic two-kernel (dq + dkv) split this saves two of seven
    dots, one of two exp sweeps, and a full re-fetch of q/do/lse/delta."""
    j = pl.program_id(1)
    nk = t_q // block_k
    k_off = j * block_k
    k_blk = k_ref[...]                           # [GH, BK, D]
    v_blk = v_ref[...]

    @pl.when(j == 0)
    def init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    nq = t_q // block_q
    start = k_off // block_q if causal else 0
    if causal and window is not None:
        nq = jnp.minimum(nq, pl.cdiv(k_off + block_k + window - 1, block_q))

    def body(i, carry):
        dk, dv = carry
        q_i = q_ref[:, pl.ds(i * block_q, block_q), :]
        do_i = do_ref[:, pl.ds(i * block_q, block_q), :]
        lse_i = lse_ref[:, pl.ds(i * block_q, block_q), :]
        delta_i = delta_ref[:, pl.ds(i * block_q, block_q), :]
        s = lax.dot_general(q_i, k_blk, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, i * block_q, k_off, gh, block_q, block_k, window)
        p = jnp.exp(s - lse_i)                   # [GH, BQ, BK]
        dv_new = dv + lax.dot_general(
            p.astype(do_i.dtype), do_i, _BTN,
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do_i, v_blk, _BNT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i) * scale          # [GH, BQ, BK]
        ds_lp = ds.astype(q_i.dtype)
        dk_new = dk + lax.dot_general(
            ds_lp, q_i, _BTN, preferred_element_type=jnp.float32)
        dq_acc_ref[:, pl.ds(i * block_q, block_q), :] += lax.dot_general(
            ds_lp, k_blk, _BNN, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    d = k_blk.shape[-1]
    dk0 = jnp.zeros((gh, block_k, d), jnp.float32)
    dv0 = jnp.zeros((gh, block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(start, nq, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == nk - 1)
    def flush():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _pick_gh_fused_bwd(bh: int, t: int, d: int, bq: int, bk: int,
                       itemsize: int = 2):
    """Head fold for the fused backward, or None when not even one head
    fits (the caller then takes the streamed kernels): q/do resident
    [GH,T,D] plus the f32 dq scratch dominate. Budget is 2x the fwd
    budget — calibrated on
    the real chip: gh=2 at (bh96, t1024, d64, bq512, bk256, bf16)
    compiles inside the fused train step (estimate 5.2M), gh=4 blows the
    16M scoped-vmem limit by 1.8M (estimate 12.6M)."""
    for gh in (8, 4, 2, 1):
        if bh % gh:
            continue
        resident = 2 * gh * t * d * itemsize * 2  # q, do (double-buffered)
        dq_bytes = gh * t * d * (4 + itemsize)    # f32 scratch + lp out
        kv_bytes = 2 * gh * bk * d * itemsize * 2
        tmp = gh * bq * bk * (4 + 4 + 2 * itemsize)  # s/p, dp/ds, p_lp+ds_lp
        if resident + dq_bytes + kv_bytes + tmp <= 2 * _VMEM_BUDGET:
            return gh
    return None


def _bwd_fused(qf, kf, vf, dof, lsef, deltaf, causal, scale, block_q,
               block_k, interpret, window, gh):
    bh, t, d = qf.shape
    flops = 4 * bh * t * t * d // (2 if causal else 1)
    q_full = pl.BlockSpec((gh, t, d), lambda n, j: (n, 0, 0))
    kv_blk = pl.BlockSpec((gh, block_k, d), lambda n, j: (n, j, 0))
    vec_full = pl.BlockSpec((gh, t, 1), lambda n, j: (n, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, t_q=t, gh=gh,
                          window=window),
        grid=(bh // gh, t // block_k),
        in_specs=[q_full, kv_blk, kv_blk, q_full, vec_full, vec_full],
        out_specs=[q_full, kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), kf.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), vf.dtype)],
        scratch_shapes=[pltpu.VMEM((gh, t, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(flops * 2.5),
            bytes_accessed=7 * bh * t * d * qf.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   causal, scale, block_q, block_k, t_k, gh, window):
    q = q_ref[...]                               # [GH, BQ, D]
    do = do_ref[...]
    lse = lse_ref[...]                           # [GH, BQ, 1]
    delta = delta_ref[...]
    q_off = pl.program_id(1) * block_q
    nk = pl.cdiv(q_off + block_q, block_k) if causal else t_k // block_k
    j0 = (jnp.maximum(q_off - window + 1, 0) // block_k
          if causal and window is not None else 0)

    def body(j, dq):
        k_j = k_ref[:, pl.ds(j * block_k, block_k), :]
        v_j = v_ref[:, pl.ds(j * block_k, block_k), :]
        s = lax.dot_general(q, k_j, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, j * block_k, gh, block_q, block_k, window)
        p = jnp.exp(s - lse)                     # [GH, BQ, BK]
        dp = lax.dot_general(do, v_j, _BNT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + lax.dot_general(ds.astype(k_j.dtype), k_j, _BNN,
                                    preferred_element_type=jnp.float32)

    dq = lax.fori_loop(j0, nk, body,
                       jnp.zeros((gh, q.shape[1], q.shape[-1]), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, causal, scale, block_q, block_k, t_q,
                    gh, window):
    k_blk = k_ref[...]                           # [GH, BK, D]
    v_blk = v_ref[...]
    k_off = pl.program_id(1) * block_k
    nq = t_q // block_q
    start = k_off // block_q if causal else 0
    # sliding window: queries at or beyond k_off + bk + window - 1 are dead
    if causal and window is not None:
        nq = jnp.minimum(nq, pl.cdiv(k_off + block_k + window - 1, block_q))

    def body(i, carry):
        dk, dv = carry
        q_i = q_ref[:, pl.ds(i * block_q, block_q), :]
        do_i = do_ref[:, pl.ds(i * block_q, block_q), :]
        lse_i = lse_ref[:, pl.ds(i * block_q, block_q), :]
        delta_i = delta_ref[:, pl.ds(i * block_q, block_q), :]
        s = lax.dot_general(q_i, k_blk, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, i * block_q, k_off, gh, block_q, block_k, window)
        p = jnp.exp(s - lse_i)                   # [GH, BQ, BK]
        dv_new = dv + lax.dot_general(
            p.astype(do_i.dtype), do_i, _BTN,
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do_i, v_blk, _BNT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i) * scale          # [GH, BQ, BK]
        dk_new = dk + lax.dot_general(
            ds.astype(q_i.dtype), q_i, _BTN,
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    d = k_blk.shape[-1]
    dk0 = jnp.zeros((gh, block_k, d), jnp.float32)
    dv0 = jnp.zeros((gh, block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(start, nq, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret,
         window=None):
    b, h, t, d = q.shape
    bh = b * h
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)      # [B, H, T, 1]
    qf, kf, vf, dof = (x.reshape(bh, t, d) for x in (q, k, v, do))
    lsef = lse.reshape(bh, t, 1)
    deltaf = delta.reshape(bh, t, 1)
    fused = _os.environ.get("DSTPU_FLASH_BWD", "fused") == "fused"
    if _streamed(t, d, q.dtype.itemsize):
        gh = None
    elif fused:
        gh = int(_os.environ.get("DSTPU_FLASH_GH_BWD", 0)) or \
            _pick_gh_fused_bwd(bh, t, d, block_q, block_k,
                               q.dtype.itemsize)
    else:
        gh = _pick_gh(bh, t, d, block_q, block_k, q.dtype.itemsize)
    if gh is None or fused:
        run = _bwd_streamed if gh is None else _bwd_fused
        gh = gh or _pick_gh_streamed(bh, d, block_q, block_k,
                                     q.dtype.itemsize)
        dq, dk, dv = run(qf, kf, vf, dof, lsef, deltaf, causal, scale,
                         block_q, block_k, interpret, window, gh)
        return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
                dv.reshape(b, h, t, d))

    blk_spec = pl.BlockSpec((gh, block_q, d), lambda n, i: (n, i, 0))
    full_spec = pl.BlockSpec((gh, t, d), lambda n, i: (n, 0, 0))
    vec_blk = pl.BlockSpec((gh, block_q, 1), lambda n, i: (n, i, 0))
    vec_full = pl.BlockSpec((gh, t, 1), lambda n, i: (n, 0, 0))
    flops = 4 * bh * t * t * d // (2 if causal else 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, t_k=t, gh=gh,
                          window=window),
        grid=(bh // gh, t // block_q),
        in_specs=[blk_spec, full_spec, full_spec, blk_spec,
                  vec_blk, vec_blk],
        out_specs=blk_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=int(flops * 1.5),
            bytes_accessed=5 * bh * t * d * q.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    kv_blk = pl.BlockSpec((gh, block_k, d), lambda n, j: (n, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, t_q=t, gh=gh,
                          window=window),
        grid=(bh // gh, t // block_k),
        in_specs=[full_spec, kv_blk, kv_blk, full_spec,
                  vec_full, vec_full],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), v.dtype)],
        cost_estimate=pl.CostEstimate(
            flops=int(flops * 2.5),
            bytes_accessed=6 * bh * t * d * q.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))




# ------------------------------------------------- streamed (long-T) kernels
# K/V blocks arrive via a THIRD grid dimension instead of residing whole in
# VMEM; online-softmax accumulators live in VMEM scratch that persists
# across the innermost grid dim. Dead blocks (causal/window) are skipped
# with pl.when — compute-free, though their DMA still runs.

def _fwd_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref,
                         acc_ref, m_ref, l_ref, *, causal, scale,
                         block_q, block_k, t_k, gh, window):
    j = pl.program_id(2)
    nkj = t_k // block_k
    q_off = pl.program_id(1) * block_q

    @pl.when(j == 0)
    def init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_off = j * block_k
    live = True
    if causal:
        live = k_off <= q_off + block_q - 1
    if causal and window is not None:
        live = live & (k_off + block_k - 1 >= q_off - window + 1)

    def compute():
        q = q_ref[...]
        k_j = k_ref[...]
        v_j = v_ref[...]
        s = lax.dot_general(q, k_j, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, k_off, gh, block_q, block_k, window)
        m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc * alpha + lax.dot_general(
            p.astype(v_j.dtype), v_j, _BNN, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if live is True:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(j == nkj - 1)
    def finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _fwd_streamed(qf, kf, vf, causal, scale, block_q, block_k, interpret,
                  window, gh):
    bh, t, d = qf.shape
    grid = (bh // gh, t // block_q, t // block_k)
    kernel = functools.partial(_fwd_kernel_streamed, causal=causal,
                               scale=scale, block_q=block_q, block_k=block_k,
                               t_k=t, gh=gh, window=window)
    flops = 4 * bh * t * t * d // (2 if causal else 1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gh, block_q, d), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((gh, block_k, d), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((gh, block_k, d), lambda n, i, j: (n, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gh, block_q, d), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((gh, block_q, 1), lambda n, i, j: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((gh, block_q, d), jnp.float32),
            pltpu.VMEM((gh, block_q, 1), jnp.float32),
            pltpu.VMEM((gh, block_q, 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(flops),
            bytes_accessed=(2 * bh * t * d + 2 * bh * t * t // block_q * d)
            * qf.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)


def _bwd_dq_kernel_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dq_acc_ref, *, causal, scale, block_q,
                            block_k, t_k, gh, window):
    j = pl.program_id(2)
    nkj = t_k // block_k
    q_off = pl.program_id(1) * block_q
    k_off = j * block_k

    @pl.when(j == 0)
    def init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = True
    if causal:
        live = k_off <= q_off + block_q - 1
    if causal and window is not None:
        live = live & (k_off + block_k - 1 >= q_off - window + 1)

    def compute():
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[...]
        delta = delta_ref[...]
        k_j = k_ref[...]
        v_j = v_ref[...]
        s = lax.dot_general(q, k_j, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, k_off, gh, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, v_j, _BNT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc_ref[...] = dq_acc_ref[...] + lax.dot_general(
            ds.astype(k_j.dtype), k_j, _BNN,
            preferred_element_type=jnp.float32)

    if live is True:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(j == nkj - 1)
    def finalize():
        dq_ref[...] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_streamed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                             dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                             causal, scale, block_q, block_k, t_q, gh,
                             window):
    i = pl.program_id(2)
    nqi = t_q // block_q
    k_off = pl.program_id(1) * block_k
    q_off = i * block_q

    @pl.when(i == 0)
    def init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    live = True
    if causal:
        live = q_off + block_q - 1 >= k_off
    if causal and window is not None:
        live = live & (q_off <= k_off + block_k - 1 + window - 1)

    def compute():
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        q_i = q_ref[...]
        do_i = do_ref[...]
        lse_i = lse_ref[...]
        delta_i = delta_ref[...]
        s = lax.dot_general(q_i, k_blk, _BNT,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _mask(s, q_off, k_off, gh, block_q, block_k, window)
        p = jnp.exp(s - lse_i)
        dv_acc_ref[...] = dv_acc_ref[...] + lax.dot_general(
            p.astype(do_i.dtype), do_i, _BTN,
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do_i, v_blk, _BNT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i) * scale
        dk_acc_ref[...] = dk_acc_ref[...] + lax.dot_general(
            ds.astype(q_i.dtype), q_i, _BTN,
            preferred_element_type=jnp.float32)

    if live is True:
        compute()
    else:
        pl.when(live)(compute)

    @pl.when(i == nqi - 1)
    def finalize():
        dk_ref[...] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd_streamed(qf, kf, vf, dof, lsef, deltaf, causal, scale, block_q,
                  block_k, interpret, window, gh):
    bh, t, d = qf.shape
    flops = 4 * bh * t * t * d // (2 if causal else 1)
    q_blk = pl.BlockSpec((gh, block_q, d), lambda n, i, j: (n, i, 0))
    kv_blk = pl.BlockSpec((gh, block_k, d), lambda n, i, j: (n, j, 0))
    vec_q = pl.BlockSpec((gh, block_q, 1), lambda n, i, j: (n, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_streamed, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, t_k=t, gh=gh,
                          window=window),
        grid=(bh // gh, t // block_q, t // block_k),
        in_specs=[q_blk, kv_blk, kv_blk, q_blk, vec_q, vec_q],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((gh, block_q, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(flops * 1.5),
            # K/V refetched once per q block
            bytes_accessed=(3 * bh * t * d +
                            2 * bh * t * (t // block_q) * d)
            * qf.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    # dkv: middle grid dim over k blocks, innermost over q blocks
    q_blk2 = pl.BlockSpec((gh, block_q, d), lambda n, j, i: (n, i, 0))
    kv_blk2 = pl.BlockSpec((gh, block_k, d), lambda n, j, i: (n, j, 0))
    vec_q2 = pl.BlockSpec((gh, block_q, 1), lambda n, j, i: (n, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_streamed, causal=causal,
                          scale=scale, block_q=block_q, block_k=block_k,
                          t_q=t, gh=gh, window=window),
        grid=(bh // gh, t // block_k, t // block_q),
        in_specs=[q_blk2, kv_blk2, kv_blk2, q_blk2, vec_q2, vec_q2],
        out_specs=[kv_blk2, kv_blk2],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), kf.dtype),
                   jax.ShapeDtypeStruct((bh, t, d), vf.dtype)],
        scratch_shapes=[pltpu.VMEM((gh, block_k, d), jnp.float32),
                        pltpu.VMEM((gh, block_k, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=int(flops * 2.5),
            # Q/dO/lse/delta refetched once per k block
            bytes_accessed=(4 * bh * t * d +
                            2 * bh * t * (t // block_k) * d)
            * qf.dtype.itemsize,
            transcendentals=bh * t * t // (2 if causal else 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)
    return dq, dk, dv


# ------------------------------------------------------------------ public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=True, softmax_scale=None,
                    block_q=None, block_k=None, interpret=False,
                    window=None):
    """Blocked flash attention. q,k,v: [B, H, T, D]; returns [B, H, T, D].
    ``window`` enables Mistral-style sliding-window causal attention."""
    out, _ = _flash_fwd(q, k, v, causal, softmax_scale, block_q, block_k,
                        interpret, window)
    return out


def _resolve(q, softmax_scale, block_q, block_k, causal=True, window=None):
    t, d = q.shape[-2], q.shape[-1]
    if window is not None and not causal:
        raise ValueError("sliding window requires causal=True")
    if t % 128 != 0:
        raise ValueError(
            f"pallas flash attention requires seq length divisible by 128, "
            f"got {t}; use the XLA backend for this shape")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    dq, dk = _pick_blocks(t)
    block_q, block_k = block_q or dq, block_k or dk
    if t % block_q or t % block_k:
        raise ValueError(f"block sizes ({block_q}, {block_k}) must divide "
                         f"sequence length {t}")
    return scale, block_q, block_k


def _flash_fwd(q, k, v, causal, softmax_scale, block_q, block_k, interpret,
               window=None):
    scale, bq, bk = _resolve(q, softmax_scale, block_q, block_k, causal,
                             window)
    out, lse = _fwd(q, k, v, causal, scale, bq, bk, interpret, window)
    out = checkpoint_name(out, ATTN_OUT_NAME)
    lse = checkpoint_name(lse, ATTN_LSE_NAME)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, softmax_scale, block_q, block_k, interpret, window,
               residuals, g):
    q, k, v, out, lse = residuals
    scale, bq, bk = _resolve(q, softmax_scale, block_q, block_k, causal,
                             window)
    dq, dk, dv = _bwd(q, k, v, out, lse, g, causal, scale, bq, bk, interpret,
                      window)
    return dq, dk, dv


flash_attention.defvjp(lambda q, k, v, c, s, bq, bk, it, w:
                       _flash_fwd(q, k, v, c, s, bq, bk, it, w),
                       _flash_bwd)
