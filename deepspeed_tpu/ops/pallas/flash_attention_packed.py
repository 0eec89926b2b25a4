"""Packed-layout Pallas flash attention: q/k/v/o in [B, T, H*D].

Round-3 profiling showed ~5 ms/micro of pure relayout copies in the 125M
step: the model computes qkv as [B, T, 3HD] (lane-aligned, matmul-native)
but the [B, H, T, D] kernel layout forces six head transposes (q/k/v fwd
+ mirrored bwd) and a duplicate save of the attention output. This kernel
keeps the tensors in the layout the surrounding matmuls already produce:

- arrays [B, T, H*D]; a grid step owns GH heads as a LANE SLICE of the
  feature dim (GH*D = 128 lanes for D=64) — blocks stay (sublane, 128·k)
  tiled, no relayout anywhere. Q, K, V (and O, dO) of a grid step are
  resident whole; the tiles are walked by loops INSIDE the kernel.
- per-head dots are unrolled over the GH static lane slices ([BQ, D] 2D
  matmuls — what Mosaic lowers batched dots to anyway), each stated as
  ``lax.dot_general`` dimension numbers (no ``.T`` is written).
- the tile loop does the work the causal mask leaves: ``_tile_plan`` splits
  the tiles a q tile (forward) or a k tile (backward) walks into those
  wholly kept (a body with no mask), those an edge of the mask crosses
  (the same body with the mask) and those wholly dropped (never run).
  Where the diagonal tile's place is static (square tiles, no window:
  ``_sub_tiled``) it is walked ``sub`` rows of k at a time, each against
  the q positions from its own start on, with the mask on the one square
  where they meet: large tiles for the interior, 128-blocks along the
  diagonal, 56% of the T x T scores at T = 1024 where 512-tiles ran 75%.
  ``_tile_counts`` is the record for a shape: tiles run, tiles masked,
  share of the T x T area computed.
- the softmax scale stays on the float32 scores: folded into q (exact at
  D=64, 2^-3) it measured +1.3% on the forward and -0.6% on the backward.
- both passes work on TRANSPOSED score blocks [k, q] (k·qᵀ): the running
  max / sum, lse and delta are ROW vectors that broadcast along sublanes
  (no lane reduce, no lane broadcast), the forward accumulates
  accᵀ = Vᵀ·Pᵀ from a V transposed once per grid step, dv and dk are plain
  matmuls and only dq contracts over the block's leading dim. lse is
  emitted [B*NG, T/128, 8, 128] f32 (positions in lanes, head h of the
  group in row h: 32 B a position and group where [B, T, NG*128] took 512);
  delta = rowsum(dO*O) is computed in the backward kernel itself, in the
  same layout, into VMEM scratch.
- backward fuses dq+dk+dv in one kernel (dq in f32 VMEM scratch across
  the k tiles).
- tile sizes are fixed by shape in ``_resolve`` from a sweep on the chip
  (``_TILES``); ``block=`` overrides them in BOTH passes (the tests' hook).

Reference counterpart: csrc/transformer softmax/attention kernels — but
the DESIGN here is driven by Mosaic tiling (8, 128) rules, not the CUDA
original. Parity oracle: ops/flash_attention.reference_attention
(tests/unit/test_pallas_flash_packed.py, interpret mode).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# What a forward kernel's two results are called under ``jax.checkpoint``:
# the layer remat's default policy keeps them by these names
# (runtime/activation_checkpointing/checkpointing.py), since no dot policy
# sees inside a ``pallas_call``. Outside a checkpoint a name is the identity.
ATTN_OUT_NAME = "flash_attn_out"
ATTN_LSE_NAME = "flash_attn_lse"
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def supported(t: int, d: int, n_head: int, causal: bool, window) -> bool:
    if d > _LANES or _LANES % d or t % 128:
        return False
    gh = _LANES // d
    if n_head % gh:
        return False
    if window is not None and (not causal or window <= 0):
        return False
    # the fused BACKWARD keeps q/k/v/o/do and its three outputs (bf16,
    # double-buffered: 16*2t*128 B) + the f32 dq scratch (4t*128) resident
    # per grid step: 4.6 KB a token, 18.9 MB at the cap (_params raises the
    # scoped-VMEM limit past 2048; longer T uses the streamed [B,H,T,D]
    # kernels instead).
    return t <= 4096


# ------------------------------------------------------------------ tile plan

def _lo(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _hi(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _tile_plan(off, width, step, t, causal, window, over_k):
    """Which tiles of size ``step`` the loop beside a fixed tile walks.

    The fixed tile starts at ``off`` (a Python int or a traced scalar) and
    is ``width`` long: a q tile walking k tiles (``over_k``, forward) or a
    k tile walking q tiles (backward). A score is kept where
    ``0 <= q - k`` (causal) ``< window``. Returns ``(lo, a, b, hi)``: tiles
    ``[lo, hi)`` hold a kept score and run; of them ``[a, b)`` hold kept
    scores only and run unmasked; ``[lo, a)`` and ``[b, hi)`` hold both
    kinds and run the mask. Static arithmetic on what the kernel has."""
    n = t // step
    if not causal:
        return 0, 0, n, n
    if over_k:   # q - k over tile j spans [off - (j+1)*step + 1, off + width - 1 - j*step]
        hi = (off + width - 1) // step + 1
        b = (off + 1) // step
        lo = a = 0
        if window is not None:
            lo = _hi(off - window + 1, 0) // step
            a = (_hi(off + width - window, 0) + step - 1) // step
    else:        # over tile i it spans [i*step - off - width + 1, (i+1)*step - 1 - off]
        lo = off // step
        a = (off + width - 1 + step - 1) // step
        hi = b = n
        if window is not None:
            hi = _lo(n, (off + width + window - 2) // step + 1)
            b = (off + window) // step
    a = _lo(_hi(a, lo), hi)
    b = _lo(_hi(b, a), hi)
    return lo, a, b, hi


def _sub_tiled(bq, bk, sub, causal, window):
    """The diagonal tile is walked in ``sub`` rows of k at a time, each
    against the q positions from its own start on: only where the tile's
    place on the diagonal is static (square tiles, no window)."""
    return causal and window is None and bq == bk and sub < bk


def _tile_counts(t, bq, bk, sub=None, causal=True, window=None):
    """The plan's record for a shape: (tiles run, tiles masked, share of the
    T x T score area that is computed) — per head and sequence, either
    pass. With ``sub`` the blocks of a diagonal tile that lie wholly above
    the diagonal are not computed, and the share says so."""
    run = masked = 0
    for i in range(t // bq):
        lo, a, b, hi = _tile_plan(i * bq, bq, bk, t, causal, window, True)
        run += hi - lo
        masked += (hi - lo) - (b - a)
    area = run * bq * bk
    if _sub_tiled(bq, bk, sub or bk, causal, window):
        n = bk // sub
        area -= (t // bq) * sub * sub * n * (n - 1) // 2
    return run, masked, area / (t * t)


def _walk(plan, body, carry):
    """Run ``body(masked)(tile, carry)`` over a plan's three stretches; a
    stretch that is statically empty is not built."""
    lo, a, b, hi = plan
    for s, e, masked in ((lo, a, True), (a, b, False), (b, hi, True)):
        if isinstance(s, int) and isinstance(e, int) and s >= e:
            continue
        carry = lax.fori_loop(s, e, body(masked), carry)
    return carry


def _rel(bq, bk, causal):
    """q - k inside a [k, q] tile whose corners coincide: q along the lanes,
    k along the sublanes."""
    if not causal:
        return None
    return (lax.broadcasted_iota(jnp.int32, (bk, bq), 1) -
            lax.broadcasted_iota(jnp.int32, (bk, bq), 0))


def _keep(rel, q_off, k_off, window):
    """The kept scores of the tile at (``q_off``, ``k_off``)."""
    shift = k_off - q_off
    keep = rel >= shift
    if window is not None:
        keep &= rel < shift + window
    return keep


def _chunks(n, fn):
    """``fn(base, c)`` for each of ``n`` 128-position chunks ``base + c``,
    eight to a basic block (the scheduler overlaps their transposes);
    ``base`` is a multiple of the group, so ``c`` keeps a chunk's static
    place inside a tile of up to 512."""
    g = math.gcd(n, 8)

    def group(o, _):
        for c in range(g):
            fn(o * g, c)
        return 0

    if n == g:
        group(0, 0)
    else:
        lax.fori_loop(0, n // g, group, 0)


def _rows_of(gh):
    return -(-gh // 8) * 8


def _head_rows(vals, rows):
    """Per-head [128, 1] columns (a value a position) → [rows, 128] with
    head h in row h and the positions along the lanes: the columns into the
    lanes of one [128, 128] tile (1-lane concats don't lower on Mosaic; a
    where over a full tile does), then one transpose."""
    lane = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    cols = jnp.zeros((_LANES, _LANES), jnp.float32)
    for h, v in enumerate(vals):
        cols = jnp.where(lane == h, v, cols)
    return cols.T[:rows]


def _row(ref, idx, first, count, h):
    """Head h's [1, count*128] row vector out of a [..., T/128, rows, 128]
    ref: ``count`` chunks from chunk ``first``."""
    return jnp.concatenate(
        [ref[idx + (first + c, slice(h, h + 1), slice(None))]
         for c in range(count)], axis=1)


def _specs(t, ng, rows):
    """A grid step's blocks: a sequence's [T, 128] lane slice of a packed
    array, and its [T/128, rows, 128] of lse."""
    return (pl.BlockSpec((1, t, _LANES), lambda n: (n // ng, 0, n % ng)),
            pl.BlockSpec((1, t // _LANES, rows, _LANES),
                         lambda n: (n, 0, 0, 0)))


def _params(t):
    """A grid step holds its sequence whole (double-buffered: 4.6 KB a
    token in the backward); past 2048 that is over Mosaic's default 16 MiB
    of scoped VMEM (a v5e has 128)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=48 * 2 ** 20 if t > 2048 else None)


# --------------------------------------------------------------------- forward

def _drop(s, keep):
    """Scores outside ``keep`` (which may cover only the leading lanes of
    ``s``: the rest are all kept) leave the softmax."""
    if keep is None:
        return s
    n = keep.shape[1]
    m = jnp.where(keep, s[:, :n], NEG_INF)
    return m if n == s.shape[1] else jnp.concatenate([m, s[:, n:]], axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, vt_ref, *, causal,
                scale, bq, bk, sub, t, gh, d, window):
    rows = lse_ref.shape[2]
    nc = bk // _LANES

    # V transposed once per grid step, a k tile to a leading index:
    # [T/BK, GH*D, BK], so that acc^T = V^T P^T is a plain matmul
    def v_chunk(base, c):
        vt_ref[(base + c) // nc, :,
               (c % nc) * _LANES:(c % nc + 1) * _LANES] = v_ref[
            0, pl.ds(pl.multiple_of((base + c) * _LANES, _LANES), _LANES),
            :].T

    _chunks(t // _LANES, v_chunk)
    rel = _rel(bq, bk, causal)

    def block(k_rows, vt_cols, q, keep, carry):
        """One [rows of k, lanes of q] block of scores into the running
        softmax of those q positions."""
        accs, ms, ls = carry
        new_accs, new_ms, new_ls = [], [], []
        for h in range(gh):
            sl = slice(h * d, (h + 1) * d)
            s = _drop(lax.dot_general(
                k_rows[:, sl], q[:, sl], _NT,
                preferred_element_type=jnp.float32) * scale, keep)
            m_new = jnp.maximum(ms[h], jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(ms[h] - m_new)
            p = jnp.exp(s - m_new)
            new_ls.append(ls[h] * alpha + jnp.sum(p, axis=0, keepdims=True))
            new_accs.append(accs[h] * alpha + jnp.dot(
                vt_cols[sl], p.astype(vt_cols.dtype),
                preferred_element_type=jnp.float32))
            new_ms.append(m_new)
        return new_accs, new_ms, new_ls

    def q_tile(i, _):
        q_off = pl.multiple_of(i * bq, bq)
        q = q_ref[0, pl.ds(q_off, bq), :]              # [BQ, GH*D]

        def body(masked):
            def step(j, carry):
                k_off = pl.multiple_of(j * bk, bk)
                keep = _keep(rel, q_off, k_off, window) if masked else None
                return block(k_ref[0, pl.ds(k_off, bk), :], vt_ref[j], q,
                             keep, carry)
            return step

        def diagonal(carry):
            """Tile i itself, ``sub`` rows of k at a time against the q
            positions from their start on; the mask on the square where
            they meet."""
            for r in range(0, bk, sub):
                part = block(
                    k_ref[0, pl.ds(q_off + r, sub), :],
                    vt_ref[i, :, r:r + sub], q[r:], rel[:sub, :sub] >= 0,
                    [[x[:, r:] for x in xs] for xs in carry])
                carry = [[jnp.concatenate([x[:, :r], y], axis=1) if r else y
                          for x, y in zip(xs, ys)]
                         for xs, ys in zip(carry, part)]
            return carry

        carry = ([jnp.zeros((d, bq), jnp.float32) for _ in range(gh)],
                 [jnp.full((1, bq), NEG_INF, jnp.float32) for _ in range(gh)],
                 [jnp.zeros((1, bq), jnp.float32) for _ in range(gh)])
        lo, a, b, hi = _tile_plan(q_off, bq, bk, t, causal, window, True)
        if _sub_tiled(bq, bk, sub, causal, window):
            carry = diagonal(_walk((lo, a, b, b), body, carry))
        else:
            carry = _walk((lo, a, b, hi), body, carry)
        accs, ms, ls = carry
        ls = [jnp.maximum(l, 1e-30) for l in ls]
        out_t = jnp.concatenate([a / l for a, l in zip(accs, ls)], axis=0)
        o_ref[0, pl.ds(q_off, bq), :] = out_t.T.astype(o_ref.dtype)
        lse = jnp.concatenate(
            [m + jnp.log(l) for m, l in zip(ms, ls)] +
            [jnp.zeros((rows - gh, bq), jnp.float32)] * (rows > gh), axis=0)
        for c in range(bq // _LANES):
            lse_ref[0, i * (bq // _LANES) + c] = \
                lse[:, c * _LANES:(c + 1) * _LANES]
        return 0

    lax.fori_loop(0, t // bq, q_tile, 0)


def _fwd(q, k, v, n_head, causal, scale, tiles, interpret, window):
    b, t, hd_total = q.shape
    d = hd_total // n_head
    gh = _LANES // d
    ng = n_head // gh
    rows = _rows_of(gh)
    bq, bk, sub = tiles
    scores = int(_tile_counts(t, *tiles, causal, window)[2] * t * t)

    full, lse_spec = _specs(t, ng, rows)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          bq=bq, bk=bk, sub=sub, t=t, gh=gh, d=d,
                          window=window),
        grid=(b * ng,),
        in_specs=[full, full, full],
        out_specs=[full, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, hd_total), q.dtype),
                   jax.ShapeDtypeStruct((b * ng, t // _LANES, rows, _LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t // bk, _LANES, bk), v.dtype)],
        cost_estimate=pl.CostEstimate(
            flops=4 * b * n_head * scores * d,
            bytes_accessed=4 * b * t * hd_total * q.dtype.itemsize,
            transcendentals=b * n_head * scores),
        compiler_params=_params(t),
        interpret=interpret,
    )(q, k, v)
    return out, lse


# -------------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_acc_ref, delta_ref, *, causal,
                scale, bq, bk, sub, t, gh, d, window):
    rows = lse_ref.shape[2]
    nc = bq // _LANES

    # delta = rowsum(dO * O) per head, laid out like lse
    def delta_chunk(base, c):
        sl = pl.ds(pl.multiple_of((base + c) * _LANES, _LANES), _LANES)
        prod = (do_ref[0, sl, :].astype(jnp.float32) *
                o_ref[0, sl, :].astype(jnp.float32))
        delta_ref[base + c] = _head_rows(
            [jnp.sum(prod[:, h * d:(h + 1) * d], axis=-1, keepdims=True)
             for h in range(gh)], rows)

    _chunks(t // _LANES, delta_chunk)
    dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
    rel = _rel(bq, bk, causal)

    def block(k_rows, v_rows, q, do, first, keep):
        """One [rows of k, lanes of q] block of scores: what it adds to
        those rows of dk and dv (per head) and to those positions of dq.
        ``first`` is the block's first 128-chunk of q positions."""
        count = q.shape[0] // _LANES
        dk_upds, dv_upds, dq_upds = [], [], []
        for h in range(gh):
            sl = slice(h * d, (h + 1) * d)
            qh, kh, vh, doh = q[:, sl], k_rows[:, sl], v_rows[:, sl], \
                do[:, sl]
            s = lax.dot_general(kh, qh, _NT,
                                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(_drop(s, keep) -
                        _row(lse_ref, (0,), first, count, h))
            dv_upds.append(jnp.dot(p.astype(doh.dtype), doh,
                                   preferred_element_type=jnp.float32))
            dp = lax.dot_general(vh, doh, _NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - _row(delta_ref, (), first, count, h)) * scale
            ds_lp = ds.astype(qh.dtype)
            dk_upds.append(jnp.dot(ds_lp, qh,
                                   preferred_element_type=jnp.float32))
            dq_upds.append(lax.dot_general(
                ds_lp, kh, _TN, preferred_element_type=jnp.float32))
        return dk_upds, dv_upds, jnp.concatenate(dq_upds, -1)

    def k_tile(j, _):
        k_off = pl.multiple_of(j * bk, bk)
        k_blk = k_ref[0, pl.ds(k_off, bk), :]          # [BK, GH*D]
        v_blk = v_ref[0, pl.ds(k_off, bk), :]

        def q_of(i):
            q_off = pl.multiple_of(i * bq, bq)
            return (q_off, q_ref[0, pl.ds(q_off, bq), :],
                    do_ref[0, pl.ds(q_off, bq), :])

        def body(masked):
            def step(i, carry):
                dks, dvs = carry
                q_off, q_i, do_i = q_of(i)
                keep = _keep(rel, q_off, k_off, window) if masked else None
                dk_upds, dv_upds, dq_upd = block(k_blk, v_blk, q_i, do_i,
                                                 i * nc, keep)
                dq_acc_ref[pl.ds(q_off, bq), :] += dq_upd
                return ([x + u for x, u in zip(dks, dk_upds)],
                        [x + u for x, u in zip(dvs, dv_upds)])
            return step

        def diagonal(carry):
            """Tile j itself, ``sub`` rows of k at a time against the q
            positions from their start on; the mask on the square where
            they meet."""
            dks, dvs = carry
            q_off, q_i, do_i = q_of(j)
            parts, dq_upd = [], None
            for r in range(0, bk, sub):
                dk_upds, dv_upds, dq_r = block(
                    k_blk[r:r + sub], v_blk[r:r + sub], q_i[r:], do_i[r:],
                    j * nc + r // _LANES, rel[:sub, :sub] >= 0)
                parts.append((dk_upds, dv_upds))
                dq_upd = dq_r if dq_upd is None else jnp.concatenate(
                    [dq_upd[:r], dq_upd[r:] + dq_r], axis=0)
            dq_acc_ref[pl.ds(q_off, bq), :] += dq_upd
            whole = lambda n, h: jnp.concatenate(
                [part[n][h] for part in parts], axis=0)
            return ([x + whole(0, h) for h, x in enumerate(dks)],
                    [x + whole(1, h) for h, x in enumerate(dvs)])

        carry = ([jnp.zeros((bk, d), jnp.float32) for _ in range(gh)],
                 [jnp.zeros((bk, d), jnp.float32) for _ in range(gh)])
        lo, a, b, hi = _tile_plan(k_off, bk, bq, t, causal, window, False)
        if _sub_tiled(bq, bk, sub, causal, window):
            carry = _walk((a, a, b, hi), body, diagonal(carry))
        else:
            carry = _walk((lo, a, b, hi), body, carry)
        dks, dvs = carry
        dk_ref[0, pl.ds(k_off, bk), :] = jnp.concatenate(
            dks, -1).astype(dk_ref.dtype)
        dv_ref[0, pl.ds(k_off, bk), :] = jnp.concatenate(
            dvs, -1).astype(dv_ref.dtype)
        return 0

    lax.fori_loop(0, t // bk, k_tile, 0)
    dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, n_head, causal, scale, tiles, interpret,
         window):
    b, t, hd_total = q.shape
    d = hd_total // n_head
    gh = _LANES // d
    ng = n_head // gh
    rows = _rows_of(gh)
    bq, bk, sub = tiles
    scores = int(_tile_counts(t, *tiles, causal, window)[2] * t * t)

    full, lse_spec = _specs(t, ng, rows)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          bq=bq, bk=bk, sub=sub, t=t, gh=gh, d=d,
                          window=window),
        grid=(b * ng,),
        in_specs=[full, full, full, full, full, lse_spec],
        out_specs=[full, full, full],
        out_shape=[jax.ShapeDtypeStruct((b, t, hd_total), q.dtype),
                   jax.ShapeDtypeStruct((b, t, hd_total), k.dtype),
                   jax.ShapeDtypeStruct((b, t, hd_total), v.dtype)],
        scratch_shapes=[pltpu.VMEM((t, _LANES), jnp.float32),
                        pltpu.VMEM((t // _LANES, rows, _LANES), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=10 * b * n_head * scores * d,
            bytes_accessed=8 * b * t * hd_total * q.dtype.itemsize,
            transcendentals=b * n_head * scores),
        compiler_params=_params(t),
        interpret=interpret,
    )(q, k, v, o, do, lse)
    return dq, dk, dv


# ------------------------------------------------------------------ public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def packed_flash_attention(q, k, v, n_head, causal=True, softmax_scale=None,
                           window=None, interpret=False, block=None):
    """Flash attention over packed [B, T, H*D] tensors. Returns the
    attention output in the SAME packed layout. ``block=(bq, bk[, sub])``
    sets the tiles of both passes (the tests' hook); left out, ``_resolve``
    picks them by shape."""
    out, _ = _pf_fwd(q, k, v, n_head, causal, softmax_scale, window,
                     interpret, block)
    return out


# (bq, bk, sub) measured on a v5e at [8, 1024, 16*64] and [4, 1024, 32*64]
# bf16, causal, and the best in BOTH passes (CHANGES.md, PR 37): largest
# first, the first whose tiles divide t wins. ``sub`` is how the diagonal
# tile is walked (_sub_tiled).
_TILES = ((512, 512, 128), (256, 256, 128), (128, 128, 128))


def _resolve(q, n_head, softmax_scale, window, block):
    """(scale, (bq, bk, sub)) for a shape, either pass. ``block`` is
    ``(bq, bk)`` or ``(bq, bk, sub)``; a size that does not divide what it
    tiles falls to one that does. A window takes no tile over 256: both its
    edges cross the tiles they meet, whose three loop bodies at 512 do not
    fit the scoped VMEM at T = 2048."""
    t, hd_total = q.shape[-2], q.shape[-1]
    d = hd_total // n_head
    if t % 128:
        raise ValueError(
            f"packed flash attention requires seq length divisible by 128, "
            f"got {t} (check supported() before calling)")
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if block is None:
        block = next(bb for bb in _TILES
                     if t % bb[0] == 0 and (window is None or bb[0] <= 256))
    bq, bk = (next(bb for bb in (want, 256, 128)
                   if t % bb == 0 and bb % _LANES == 0) for want in block[:2])
    sub = block[2] if len(block) > 2 else bk
    if bk % sub or sub % _LANES:
        sub = bk
    return scale, (bq, bk, sub)


def _pf_fwd(q, k, v, n_head, causal, softmax_scale, window, interpret,
            block):
    scale, tiles = _resolve(q, n_head, softmax_scale, window, block)
    out, lse = _fwd(q, k, v, n_head, causal, scale, tiles, interpret, window)
    out = checkpoint_name(out, ATTN_OUT_NAME)
    lse = checkpoint_name(lse, ATTN_LSE_NAME)
    return out, (q, k, v, out, lse)


def _pf_bwd(n_head, causal, softmax_scale, window, interpret, block,
            res, g):
    q, k, v, out, lse = res
    scale, tiles = _resolve(q, n_head, softmax_scale, window, block)
    return _bwd(q, k, v, out, lse, g, n_head, causal, scale, tiles,
                interpret, window)


packed_flash_attention.defvjp(_pf_fwd, _pf_bwd)
