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
    packed_flash_attention, supported)

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


@pytest.mark.slow
@pytest.mark.parametrize("window", [None, 100])
def test_multi_tile_blocks_match_reference(window):
    """Force (128, 128) blocks at T=512 so the online-softmax rescale,
    the dq scratch accumulation across sequential k tiles, and windowed
    block skipping all run multi-tile (the default single-tile case
    would hide a broken alpha rescale entirely)."""
    rng = np.random.default_rng(7)
    t = 256
    mk = lambda: jnp.asarray(rng.standard_normal((1, t, H * D)) * 0.3,
                             jnp.float32)
    q, k, v = mk(), mk(), mk()

    def f_packed(q, k, v):
        return jnp.sum(jnp.sin(packed_flash_attention(
            q, k, v, H, causal=True, window=window, interpret=True,
            block=(128, 128))))

    def to4(x):
        return x.reshape(1, t, H, D).transpose(0, 2, 1, 3)

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(
            to4(q), to4(k), to4(v), causal=True,
            window=window).transpose(0, 2, 1, 3).reshape(1, t, H * D)))

    np.testing.assert_allclose(float(f_packed(q, k, v)),
                               float(f_ref(q, k, v)), rtol=1e-5)
    gp = jax.grad(f_packed, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_unsupported_seq_len_raises():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((1, 77, H * D)), jnp.float32)
    with pytest.raises(ValueError, match="divisible by 128"):
        packed_flash_attention(x, x, x, H, interpret=True)
