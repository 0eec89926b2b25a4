"""The gated experts' three grouped matmuls as one kernel over the rows a
group holds: ``down(silu(gate(x)) * up(x))`` for rows sorted by expert.

``lax.ragged_dot`` becomes a library kernel tiled for 128-512 rows a group.
The routed serving programs hold a dozen (a decode step, a block pass) to a
few hundred (a prefill bucket), and the library's three kernels read 269
GB/s of the chip's 819 there (PERF.md section 6, PR 52). This kernel is:

- the work is the ``(group, row tile)`` pairs that hold a row, found from
  ``group_sizes`` outside the kernel (two cumulative sums and a comparison
  over E numbers) and handed in as scalar-prefetch operands; the grid walks
  them in order. A group without rows is in no pair and is never visited; the
  pairs past the last real one repeat it (no fetch, no arithmetic).
- the weight leaves are read where they lie, ``[G, K, F]`` and ``[G, F,
  M]`` with ``G`` the groups of ALL stacked layers and ``first_group``
  the layer's offset among them (as ``moe/experts.py:_groups``): a pair
  carries its group's index among all G, the block's index map reads it,
  and nothing is sliced, copied or re-laid. Consecutive
  pairs of one group keep the block index, so a touched expert's weights
  are fetched once for all of its rows (an expert whose matrices do not
  fit VMEM whole stays with ``lax.ragged_dot``: ``choose``).
- one pair computes ``[tile, K] x [K, F]`` twice (gate and up, which share
  the rows they read), ``silu(gate) * up`` and ``[tile, F] x [F, M]`` into
  a float32 accumulator: ``h`` never leaves VMEM. The roundings
  are the three ``ragged_dot``'s: each product accumulates in float32 and
  is rounded to the rows' type.
- a row tile several groups share is written once: each pair keeps the
  rows of its own group (``lo <= row < hi``) and what the pairs before it
  left in the tile. Rows of no group (past the sum of ``group_sizes``:
  the pairs of experts another chip holds) read zero where a tile is
  visited and are unspecified where none is, as with ``ragged_dot``.
- right for every row count: the rows are padded to whole tiles here (a
  pad of the activations, never of a weight) and cut again.

``choose`` is the shape rule; ``moe/experts.py:GatedExpertFFN.
apply_grouped`` asks it at trace time. Parity oracle: a gather and three
einsums (tests/unit/test_grouped_matmul.py, interpret mode).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: rows a tile holds. One layer stack's three products, ms, on the chip (my
#: chip run, PR 52, chiprun_out/micro52; PERF.md section 6 sets PR 51's
#: three-call kernel beside it; ``least`` is the larger of bytes / 819 GB/s
#: and FLOPs / 197 TFLOP/s; the prefill cases hold a mask over all rows):
#:
#: | case (groups of K x F, rows a group) | ragged_dot | tile 64 / 128 / 256 | F in blocks of 256 | least |
#: | c11dec (128 of 2048x768, ~12)        | 24.4 | 9.85 / 9.86 / 10.0 | 10.1 | 7.62 |
#: | c11pf512 / c11pf4096                 | 27.9 / 56.0 | 11.8 / 11.6 / 11.8; 28.0 / 27.6 / 27.8 | 12.6; 38.5 | 8.42; 9.42 |
#: | c3dec (64 of 2048x1024, ~3)          | 8.76 | 7.44 / 7.51 / - | 8.30 | 5.98 |
#: | c3pf512                              | 24.0 | 12.1 / 12.0 / 12.1 | 15.1 | 7.82 |
#: | c5dec (64 of 2048x1536, ~2.5)        | 10.4 | 9.11 / 9.13 / - | 9.26 | 7.35 |
#: | c5pf1024 / c5pf4096                  | 34.3 / 51.1 | 17.0 / 17.0 / 16.9; 29.8 / 29.3 / 29.4 | 19.8; 42.5 | 11.4; 12.6 |
#: | c7dec (16 held of 6144x2048, F by 512) | 6.32 | 5.56 / 5.56 / 5.58 | 5.41 | 3.96 |
#: | c9dec (8 held of 3584x1024)          | 2.86 | 2.53 / 2.59 / - | 2.57 | 1.45 |
#: | c9pf4096 (8 held, ~180 rows a group) | 16.8 | 16.1 / 15.9 / 15.9 | 19.3 | 2.37 |
#:
#: 64, 128 and 256 lie within 2% of each other everywhere (a tile of up to
#: 128 rows costs the MXU the same weight loads), so one tile: 128, or all
#: the rows where there are fewer. F is never cut: in blocks, a group's
#: weights are fetched again for every row tile it spans (the fourth
#: column, read on a build that could), so c7's shape stays on ragged_dot.
_ROW_TILE = 128
#: VMEM the two buffers of an expert's three matrices may take (the largest
#: a cell holds: 3584 x 1024, 44 MB)
_WEIGHT_VMEM = 48 << 20


def _pallas():
    """Pallas, imported when a kernel is built and not before (as
    ``decode_attention._pallas``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def choose(rows, k, f, dtype, platform):
    """The shape rule, a pure function of what ``apply_grouped`` sees at
    trace time: the row tile where this kernel takes the three products,
    ``None`` where ``lax.ragged_dot`` keeps them.

    - a platform that is no TPU: ``None`` (the CPU programs stay what they
      are; the tests run the kernel in interpret mode by calling it).
    - a type that is no 16- or 32-bit float, or widths that are no multiple
      of 128 lanes: ``None``.
    - an expert whose three matrices do not fit ``_WEIGHT_VMEM`` twice
      over: ``None``. With F cut into blocks a group's weights are fetched
      again for every row tile it spans; K-EXAONE's 6144 x 2048 (75 MB an
      expert) gained 12% alone so (the table's c7dec) and its cell paid
      2.7-3.5 s of start-up for it (PERF.md section 6, PR 52).
    - the row tile is ``_ROW_TILE`` (the table there), or the rows rounded
      up to whole sublane tiles where there are fewer."""
    dtype = jnp.dtype(dtype)
    if platform != "tpu" or dtype not in (jnp.bfloat16, jnp.float32):
        return None
    if k % 128 or f % 128 or rows < 1 or \
            6 * k * f * dtype.itemsize > _WEIGHT_VMEM:
        return None
    sub = 32 // dtype.itemsize          # rows of one (., 128) tile
    return min(_ROW_TILE, -(-rows // sub) * sub)


def _pairs(group_sizes, first_group, n_tiles, tile, n_pairs):
    """The ``(group, row tile)`` pairs that hold a row, in order, as four
    [n_pairs] vectors: the group's index among ALL groups of the leaves,
    the row tile, and the rows ``lo <= row < hi`` the group owns. The
    pairs past the last real one repeat its group and tile with no rows.
    Written in ``lax`` and with sums over a one-hot in place of gathers:
    a routed program traces this once a distinct shape at every start,
    and the ``jnp`` forms cost that trace three times as long."""
    sizes = group_sizes.astype(jnp.int32)
    e = sizes.shape[0]
    hi = lax.cumsum(sizes)
    lo = hi - sizes
    first = lax.div(lo, tile)
    tiles = lax.select(sizes > 0, lax.div(hi - 1, tile) - first + 1,
                       lax.full_like(sizes, 0))
    ends = lax.cumsum(tiles)            # pairs up to and with group g
    total = ends[e - 1]
    i = lax.iota(jnp.int32, n_pairs)
    at = jnp.minimum(i, jnp.maximum(total - 1, 0))
    # pair ``at`` belongs to the first group whose ``ends`` pass it
    before = (ends[None, :] <= at[:, None]).astype(jnp.int32)
    g = jnp.minimum(before.sum(1), e - 1)
    mine = (lax.iota(jnp.int32, e)[None, :] == g[:, None]).astype(jnp.int32)

    def of(v):                          # v[g], without a gather
        return (mine * v[None, :]).sum(1)
    t = jnp.minimum(jnp.maximum(of(first) + at - of(ends - tiles), 0),
                    n_tiles - 1)
    real = (i < total).astype(jnp.int32)
    return g + first_group, t, real * of(lo), real * of(hi)


def _kernel(gid_ref, tid_ref, lo_ref, hi_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref, *, tile):
    pl, _ = _pallas()
    i = pl.program_id(0)
    lo, hi, t = lo_ref[i], hi_ref[i], tid_ref[i]
    # the first pair of a tile finds nothing there to keep
    opened = jnp.logical_or(i == 0, tid_ref[jnp.maximum(i - 1, 0)] != t)

    @pl.when(jnp.logical_and(opened, hi <= lo))
    def _():                            # no group has a row at all
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(hi > lo)
    def _():
        x = x_ref[...]
        dt = x.dtype
        # float32 rows are multiplied as float32 (the library's kernel
        # does: 2.5e-7 off a gather and einsum), not in bfloat16 passes
        dot = functools.partial(
            jnp.dot, preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST if dt == jnp.float32 else None)
        g = dot(x, wg_ref[0]).astype(dt).astype(jnp.float32)
        u = dot(x, wu_ref[0]).astype(dt).astype(jnp.float32)
        h = (g * lax.logistic(g) * u).astype(dt)
        y = dot(h, wd_ref[0]).astype(dt)
        row = t * tile + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        kept = jnp.where(opened, jnp.zeros_like(y), o_ref[...])
        o_ref[...] = jnp.where((row >= lo) & (row < hi), y, kept)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def gated_rows(x, w_gate, w_up, w_down, group_sizes, first_group, *, tile,
               interpret=False):
    """``x`` [N, K] rows sorted by group, ``group_sizes`` [E] rows each;
    ``w_gate``, ``w_up`` [G, K, F] and ``w_down`` [G, F, M] hold group
    ``e``'s weights at ``first_group + e`` (a traced scalar; ``G >= E``).
    Returns ``(silu(x @ gate) * (x @ up)) @ down`` [N, M] in x's type.

    One ``jax.jit`` of stable identity: a program with many call sites of
    one shape (a layer loop's bodies) traces the kernel once a process and
    lowers it to Mosaic once a module, which is what a warm start pays for
    (PERF.md section 6, PR 52)."""
    pl, pltpu = _pallas()
    n, k = x.shape
    e = group_sizes.shape[0]
    f, m = w_down.shape[1:]
    pad = -n % tile
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    n_tiles = x.shape[0] // tile
    # every group after the first in a tile adds a pair, and no more
    # groups hold a row than there are rows
    n_pairs = n_tiles + min(e, n) - 1
    pairs = _pairs(group_sizes, jnp.asarray(first_group, jnp.int32),
                   n_tiles, tile, n_pairs)
    item = jnp.dtype(x.dtype).itemsize
    need = 6 * k * f * item + 2 * tile * (k + m) * item + \
        tile * (m + 3 * f) * 4
    rows_of = lambda i, g, t, *_: (t[i], 0)             # noqa: E731
    weights_of = lambda i, g, *_: (g[i], 0, 0)          # noqa: E731
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], m), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_pairs,),
            in_specs=[pl.BlockSpec((tile, k), rows_of),
                      pl.BlockSpec((1, k, f), weights_of),
                      pl.BlockSpec((1, k, f), weights_of),
                      pl.BlockSpec((1, f, m), weights_of)],
            out_specs=pl.BlockSpec((tile, m), rows_of)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(need) + (16 << 20)),
        name="ragged-dot-rows",
        interpret=interpret,
    )(*pairs, x, w_gate, w_up, w_down)
    return out[:n] if pad else out
