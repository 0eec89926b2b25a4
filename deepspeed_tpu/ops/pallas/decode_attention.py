"""Decode attention over the slot pool: one query token a slot against the
live columns of that slot's lane, read where they lie.

The pool leaves are ``[L, S, max_len, G, W]`` (``GPT2Model.init_kv_cache``:
token-major, a token's keys of one layer one stored row ``(G, W)``, heads
narrower than 128 lanes packed ``W // hd`` to a row). The XLA attend
(``GPT2Model._kv_attend``) contracts over every column of every lane,
whatever is live; the compiler cannot be told the lengths. This kernel is:

- ``layer`` and ``lengths`` are scalar-prefetch operands; the leaves stay in
  HBM (``pl.ANY``) and are never copied, re-laid or aliased: the kernel only
  reads them.
- one invocation walks the slots, and for slot ``s`` the
  ``ceil(lengths[s] / bk)`` blocks of ``bk`` columns that hold a live
  token: a DMA of ``[bk, G, W]`` keys and one of values a block, double
  buffered, the next block (the next slot's first, at a lane's end) in
  flight while this one is computed. Nothing past a lane's last live block
  is fetched; the cost of a step is the live blocks', not the pool's.
- a block is seen as ``[bk * G, W]`` (no re-laying: G rows fill whole
  sublane tiles) and every query row is multiplied against all of it on
  the MXU; a row keeps the columns of its OWN stored row (``tok``: the
  column's token where the group matches, never otherwise) below its
  length, the rest are masked before the softmax and meet the values as
  exact zeros. Heads that share a stored row arrive as ``_kv_attend``'s
  zero-lane queries (``decode_attend`` lays them out and takes each head's
  own lanes of the result), so grouped KV heads are contracted per group
  and never repeated.
- the mathematics are ``_kv_attend``'s: scores in q's dtype scaled by
  ``1 / sqrt(hd)``, mask and softmax in float32 (here online across
  blocks), probabilities in q's dtype against the values, float32
  accumulation.

``block_columns`` fixes ``bk`` by shape from a sweep on the chip and is
what ``supported`` asks. Parity oracle: ``_kv_attend`` on the same pool
(tests/unit/test_decode_attention.py, interpret mode).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
#: a column no query row ever keeps: another stored row's
_NEVER = 1 << 30
#: columns a block holds. A sweep on the chip (PERF.md, PR 39) over 128 /
#: 256 / 512 at the serving cells' pools: the kernel runs at the DMA's rate
#: whatever the block (1.4-1.6 us a 128-column block of K and V of 16 x 128
#: rows, 690-750 GB/s of the bytes fetched), so the smallest block wins by
#: what it does not fetch: a free slot's one block, a lane's last
_BLOCK = 128


def _pallas():
    """Pallas, imported when a kernel is built and not before: the import
    costs a process a second, and ``block_columns`` is asked by every
    serving process, whatever its pool and its platform."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def block_columns(row_shape, max_len, dtype):
    """``bk``, the columns one fetched block holds for a pool of stored rows
    ``row_shape`` = ``(G, W)`` and ``max_len`` columns a lane, or ``None``
    where the kernel does not take the pool: rows of 128 lanes, ``G`` a
    multiple of 8 (a block is then whole sublane tiles seen as
    ``[bk * G, W]`` with no re-laying), a 16- or 32-bit float, and a lane
    of more than one block, or there is nothing to skip. Rows that hold ALL
    of a token's KV heads (``(1, W)``: LFM2, K-EXAONE) stay on the XLA
    attend, which reads such a slab where it lies: a block ``[bk, 1, W]``
    would pad its one sublane to a tile in VMEM (PERF.md section 7 has what
    the kernel read on such rows seen as ``[max_len, W]``, and why that
    waits)."""
    g, w = row_shape
    dtype = jnp.dtype(dtype)
    if w != _LANES or g % 8 or dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if max_len % _BLOCK or max_len <= _BLOCK:
        return None
    return _BLOCK


def supported(row_shape, max_len, dtype) -> bool:
    return block_columns(row_shape, max_len, dtype) is not None


def _kernel(layer_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            tok_ref, *, bk, groups, rows_per_group, scale):
    pl, pltpu = _pallas()
    n_slots, rows, w = q_ref.shape
    cols = bk * groups
    layer = layer_ref[0]

    def copies(s, i, buf):
        at = pl.multiple_of(i * bk, bk)
        return [pltpu.make_async_copy(hbm.at[layer, s, pl.ds(at, bk)],
                                      vmem.at[buf], sem.at[j, buf])
                for j, (hbm, vmem) in enumerate(((k_hbm, kbuf),
                                                 (v_hbm, vbuf)))]

    def start(s, i, buf):
        for c in copies(s, i, buf):
            c.start()

    # column c of a block is stored row c % G of token c // G
    c = lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    r = lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    tok_ref[...] = jnp.where(c % groups == r // rows_per_group, c // groups,
                             _NEVER)
    start(0, 0, 0)

    def per_slot(s, first):
        length = len_ref[s]
        n = (length + bk - 1) // bk
        q = q_ref[s]

        def per_block(i, carry):
            m, l, acc = carry
            buf = (first + i) % 2
            # the block after this one: this lane's next, or the next
            # lane's first
            last = i + 1 == n
            ns = jnp.where(last, s + 1, s)

            @pl.when(ns < n_slots)
            def _():
                start(ns, jnp.where(last, 0, i + 1), 1 - buf)

            for c in copies(s, i, buf):
                c.wait()
            k = kbuf[buf].reshape(cols, w)
            v = vbuf[buf].reshape(cols, w)
            sc = lax.dot_general(q, k, _NT,
                                 preferred_element_type=jnp.float32)
            sc = (sc.astype(q.dtype) * scale).astype(jnp.float32)
            sc = jnp.where(tok_ref[...] < length - i * bk, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        m, l, acc = lax.fori_loop(
            0, n, per_block,
            (jnp.full((rows, 1), NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, w), jnp.float32)))
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return (first + n) % 2

    lax.fori_loop(0, n_slots, per_slot, jnp.int32(0))


def _attend_rows(qz, k_pool, v_pool, layer, lengths, rows_per_group, hd, bk,
                 interpret):
    """``qz`` [S, R, W]: query rows, ``rows_per_group`` to a stored row of
    the pool in order, each with zeros outside its own head's lanes.
    Returns the attention over the live columns, [S, R, W] in qz's dtype."""
    pl, pltpu = _pallas()
    s, rows, w = qz.shape
    _, _, max_len, g, _ = k_pool.shape
    item = jnp.dtype(k_pool.dtype).itemsize
    need = 4 * bk * g * w * item + rows * bk * g * 4 + \
        2 * 2 * s * rows * w * jnp.dtype(qz.dtype).itemsize
    kernel = functools.partial(
        _kernel, bk=bk, groups=g, rows_per_group=rows_per_group,
        scale=1.0 / math.sqrt(hd))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, rows, w), qz.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((s, rows, w), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((s, rows, w), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, g, w), k_pool.dtype),
                pltpu.VMEM((2, bk, g, w), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, bk * g), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(need) + (16 << 20)),
        name="decode_attend",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      qz, k_pool, v_pool)


def decode_attend(q, k_pool, v_pool, layer, lengths, interpret=False,
                  block=None):
    """Attention of ``q`` [S, H, hd], one query token a slot, over columns
    ``< lengths[s]`` of lane ``s`` of layer ``layer`` of the pool leaves
    ``[L, S, max_len, G, W]``; returns [S, H, hd]. ``layer`` is a traced
    scalar, ``lengths`` [S], held to 1 .. ``max_len``: a free slot is at
    position 0 and every lane has a block in flight for it, and a parked
    lane that is full stands one past its last column (its write was
    dropped).
    ``block`` overrides the columns a block holds (the tests' hook and the
    sweep's; ``block_columns`` otherwise)."""
    s, h, hd = q.shape
    max_len, g, w = k_pool.shape[2:]
    bk = block or block_columns((g, w), max_len, k_pool.dtype)
    if not bk or max_len % bk or w != _LANES or g % 8:
        raise ValueError(
            f"decode_attend does not take a pool of rows {(g, w)} x "
            f"{max_len} columns of {k_pool.dtype} (block {bk})")
    pack = w // hd                   # KV heads to a stored row
    rep = h // (g * pack)            # query heads to a KV head
    lengths = jnp.clip(lengths, 1, max_len)
    if pack == 1:
        return _attend_rows(q, k_pool, v_pool, layer, lengths, rep, hd, bk,
                            interpret)
    # own[j, j']: head j of a row owns lane block j' (``_kv_attend``'s)
    own = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
    qz = (q.reshape(s, g, pack, rep, 1, hd) * own).reshape(s, h, w)
    out = _attend_rows(qz, k_pool, v_pool, layer, lengths, pack * rep, hd,
                       bk, interpret)
    return (out.reshape(s, g, pack, rep, pack, hd) * own).sum(axis=4) \
        .reshape(s, h, hd)
