"""Packed-layout ([B, T, H*D]) flash attention vs the reference oracle —
fwd + grads, causal and windowed, interpret mode on CPU. Also checks the
model-level dispatch produces identical logits to the transpose path."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention_packed import (
    _resolve, _sub_tiled, _tile_counts, _tile_plan, packed_flash_attention,
    supported)

B, T, H, D = 2, 256, 4, 64


def _packed(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H * D)) * 0.3,
                             jnp.float32)
    return mk(), mk(), mk()


def _to_bhtd(x):
    return x.reshape(B, T, H, D).transpose(0, 2, 1, 3)


def _from_bhtd(x):
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


@pytest.mark.parametrize("window", [None, 96])
def test_forward_matches_reference(window):
    q, k, v = _packed()
    assert supported(T, D, H, True, window)
    got = packed_flash_attention(q, k, v, H, causal=True, window=window,
                                 interpret=True)
    want = _from_bhtd(reference_attention(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), causal=True, window=window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 96])
def test_grads_match_reference(window):
    q, k, v = _packed(seed=1)

    def f_packed(q, k, v):
        return jnp.sum(jnp.sin(packed_flash_attention(
            q, k, v, H, causal=True, window=window, interpret=True)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(_from_bhtd(reference_attention(
            _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), causal=True,
            window=window))))

    gp = jax.grad(f_packed, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.slow
def test_model_dispatch_matches_transpose_path():
    """GPT2Model with attn_backend='pallas' (packed path on CPU interpret)
    == the same weights through the [B,H,T,D] XLA attention path."""
    import dataclasses
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=256, n_layer=2,
                     n_head=4, pad_vocab_to_multiple=64,
                     attn_backend="pallas")
    model = GPT2Model(cfg)
    plain = GPT2Model(dataclasses.replace(cfg, attn_backend="xla"))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 256, (2, 128)), jnp.int32)

    assert model._packed_attn_ok(128, 64, 4)
    assert not plain._packed_attn_ok(128, 64, 4)
    np.testing.assert_allclose(
        np.asarray(model.logits(params, ids, train=False)),
        np.asarray(plain.logits(params, ids, train=False)),
        atol=2e-4, rtol=2e-4)

    # grads agree too (the custom-vjp backward)
    def loss(m):
        return lambda p: m.apply(p, {"input_ids": ids}, train=False)

    g1 = jax.grad(loss(model))(params)
    g0 = jax.grad(loss(plain))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=3e-4)


def _multi_tile_cases():
    """(t, block, window, d): 128 tiles in BOTH passes at T = 256, 384, 512
    (interior, diagonal and skipped tiles all present from 384 on), a window
    edge inside a tile (100) and on a tile boundary (128), bq != bk both
    ways, a second head size (128: one head a lane slice), and the
    diagonal tile walked in sub-blocks (a third size; the resolver's own
    choice at T = 512 with no ``block``)."""
    cases = [(t, (128, 128), w, 64)
             for t in (256, 384, 512) for w in (None, 100, 128)]
    cases += [(t, (128, 128), w, 128)
              for t, w in ((256, 128), (384, None), (512, 100))]
    cases += [(512, blk, w, 64) for blk in ((256, 128), (128, 256))
              for w in (None, 100)]
    cases += [(512, (256, 256, 128), None, 64), (768, (256, 256, 128), None, 64),
              (512, (256, 256, 128), None, 128), (512, (256, 256, 128), 100, 64),
              (512, None, None, 64)]
    return cases


@pytest.mark.parametrize(
    "t,block,window,d", _multi_tile_cases(),
    ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_multi_tile_blocks_match_reference(t, block, window, d):
    """Forward and all three gradients, float32 against the oracle, with
    the tiles forced small so that the online-softmax rescale, the dq
    scratch accumulated across k tiles, the unmasked interior body, the
    masked edge bodies and the skipped tiles all run (one row: B = 1, as
    ``eval_batch`` calls it)."""
    h = 256 // d
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(rng.standard_normal((1, t, h * d)) * 0.3,
                             jnp.float32)
    q, k, v = mk(), mk(), mk()
    assert supported(t, d, h, True, window)

    def attn(q, k, v):
        return packed_flash_attention(q, k, v, h, causal=True,
                                      window=window, interpret=True,
                                      block=block)

    def to4(x):
        return x.reshape(1, t, h, d).transpose(0, 2, 1, 3)

    def ref(q, k, v):
        return reference_attention(
            to4(q), to4(k), to4(v), causal=True,
            window=window).transpose(0, 2, 1, 3).reshape(1, t, h * d)

    np.testing.assert_allclose(np.asarray(attn(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    loss = lambda f: lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))
    gp = jax.grad(loss(attn), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)
    tiles = _resolve(q, h, None, window, block)[1]
    assert _sub_tiled(*tiles, True, window) == (
        (block is None or len(block) == 3) and window is None)


@pytest.mark.parametrize("t", [128, 256, 384, 512, 640, 1024, 1536, 2048])
def test_tile_plan_is_exact(t):
    """The plan against the mask itself: for every (bq, bk, window) the
    resolver can pick at this length, the tiles a q tile (forward) or a k
    tile (backward) runs are exactly those holding a kept score, the tiles
    it masks exactly those holding both kept and dropped scores, and
    ``_tile_counts`` adds them up."""
    pos = np.arange(t)
    diff = pos[:, None] - pos[None, :]                 # q - k
    tiles = [b for b in (128, 256, 512) if t % b == 0]
    for window in (None, 1, 100, 128, 129, 300, t):
        kept = diff >= 0
        if window is not None:
            kept &= diff < window
        for bq in tiles:
            for bk in tiles:
                by_tile = kept.reshape(t // bq, bq, t // bk, bk)
                some = by_tile.any(axis=(1, 3))        # [nq, nk]
                every = by_tile.all(axis=(1, 3))
                for over_k, fixed, width, step in ((True, t // bq, bq, bk),
                                                   (False, t // bk, bk, bq)):
                    for f in range(fixed):
                        lo, a, b, hi = _tile_plan(f * width, width, step, t,
                                                  True, window, over_k)
                        assert 0 <= lo <= a <= b <= hi <= t // step
                        ran = np.zeros(t // step, bool)
                        ran[lo:hi] = True
                        plain = np.zeros(t // step, bool)
                        plain[a:b] = True
                        line = (some[f], every[f]) if over_k else \
                            (some[:, f], every[:, f])
                        key = (window, bq, bk, over_k, f)
                        assert (ran == line[0]).all(), key
                        assert (plain == line[1]).all(), key
                run, masked, share = _tile_counts(t, bq, bk, window=window)
                assert run == some.sum() and masked == (some & ~every).sum()
                assert share == pytest.approx(run * bq * bk / t ** 2)
                # a diagonal tile walked ``sub`` rows of k at a time, each
                # against the q positions from its start on, computes every
                # kept score, and ``_tile_counts`` says how many in all
                for sub in (s for s in tiles if s < bk):
                    if not _sub_tiled(bq, bk, sub, True, window):
                        assert _tile_counts(t, bq, bk, sub,
                                            window=window) == (run, masked, share)
                        continue
                    done = (pos[:bq][:, None] >=
                            pos[:bk][None, :] // sub * sub)
                    assert (done | ~kept[:bq, :bk]).all()
                    diag = t // bq
                    assert _tile_counts(t, bq, bk, sub, window=window)[2] == \
                        pytest.approx(((run - diag) * bq * bk +
                                       diag * done.sum()) / t ** 2)
    n = t // tiles[-1]
    assert _tile_plan(0, tiles[-1], tiles[-1], t, False, None, True) == \
        (0, 0, n, n)


def test_resolver_picks_by_shape_and_honours_block():
    """No argument: the measured tiles for the shape. ``block=``: both
    passes take it (the backward used to ignore it)."""
    x = jnp.zeros((1, 1024, 256), jnp.float32)
    scale, (bq, bk, sub) = _resolve(x, 4, None, None, None)
    assert scale == 0.125 and 1024 % bq == 0 and 1024 % bk == 0
    assert bk % sub == 0 and sub % 128 == 0
    assert _resolve(x, 4, None, None, (128, 256))[1] == (128, 256, 256)
    assert _resolve(x, 4, None, None, (256, 256, 128))[1] == (256, 256, 128)
    # a tile that does not divide the length falls to one that does
    assert _resolve(x[:, :384], 4, None, None, (512, 256))[1] == \
        (128, 128, 128)
    # a window's edges cross what they meet: no tile over 256
    assert max(_resolve(x, 4, None, 300, None)[1]) <= 256
    # at T = 1024 less of the score area is computed than the 75% / 62.5%
    # of the parent's 512 and 256 squares, and fewer tiles are masked than run
    run, masked, share = _tile_counts(1024, bq, bk, sub)
    assert masked < run and share <= 0.625


def test_unsupported_seq_len_raises():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((1, 77, H * D)), jnp.float32)
    with pytest.raises(ValueError, match="divisible by 128"):
        packed_flash_attention(x, x, x, H, interpret=True)
