"""The decode-attention kernel (``ops/pallas/decode_attention.py``, ISSUE 39)
in interpret mode against ``GPT2Model._kv_attend`` on the same pool, and who
takes it.

The contract under test: slot ``s`` attends columns ``< lengths[s]`` of its
own lane of layer ``layer`` and nothing else, whatever lies in the dead
columns; the mathematics are ``_kv_attend``'s to bf16 rounding, and both are
``reference_attention``'s over the live columns gathered by hand; the cached
forward takes the kernel for a decode step of a family whose mask is plain
causal, on a TPU, on one device, over rows the kernel takes, and the XLA
attend everywhere else (``tests/unit/test_kv_pool.py`` runs that path on the
CPU, unedited).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.bloom import BloomConfig, BloomModel
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model, _kv_row_shape
from deepspeed_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from deepspeed_tpu.models.kexaone import KExaoneConfig, KExaoneModel
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.ops.flash_attention import reference_attention
from deepspeed_tpu.ops.pallas import decode_attention
from deepspeed_tpu.ops.pallas.decode_attention import (block_columns,
                                                       decode_attend,
                                                       supported)
from deepspeed_tpu.parallel import topology

BK, MAX_LEN, LAYERS = 16, 64, 3
#: (query heads, KV heads, head width): OPT's two heads of 64 to a stored
#: row of 128 lanes; OLMoE's sixteen heads of 128, one a row; a grouped-query
#: LLaMA, four query heads to each of eight KV heads. Rows of ALL KV heads
#: ((1, 512) LFM2, (1, 1024) K-EXAONE) the kernel does not take
LAYOUTS = {"opt_packed": (16, 16, 64), "olmoe": (16, 16, 128),
           "llama_gqa": (32, 8, 128)}
#: lengths a call holds, one a slot: position 0 (a free slot), one under /
#: at / one over a block's edge, the lane's last column, a full lane
LENGTHS = {"position_0": [1, 1, 1],
           "under_an_edge": [BK - 1, 2 * BK - 1, 3 * BK - 1],
           "at_an_edge": [BK, 2 * BK, 3 * BK],
           "over_an_edge": [BK + 1, 2 * BK + 1, 3 * BK + 1],
           "lane_end": [MAX_LEN - 1, MAX_LEN, MAX_LEN - 1],
           "mixed": [1, BK + 3, MAX_LEN, 7, BK, 2 * BK + 1]}


def _pool(layout, slots, dtype, seed=0):
    h, hk, hd = LAYOUTS[layout]
    g, w = _kv_row_shape(hk, hd)
    rng = np.random.RandomState(seed)
    shape = (LAYERS, slots, MAX_LEN, g, w)
    k = jnp.asarray(rng.randn(*shape), dtype)
    v = jnp.asarray(rng.randn(*shape), dtype)
    q = jnp.asarray(rng.randn(slots, h, hd), dtype)
    return q, k, v


def _xla(q, k, v, layer, lengths):
    keep = (jnp.arange(MAX_LEN)[None, :] < lengths[:, None])[:, None, None]
    return GPT2Model._kv_attend(q[:, :, None], k, v, layer, keep, None)[:, :, 0]


def _by_hand(q, k, v, layer, lengths, hk, hd):
    """``reference_attention`` over each slot's live columns, gathered out
    of a host copy of the pool, grouped KV heads repeated."""
    out = []
    for s, n in enumerate(np.asarray(lengths)):
        ks, vs = (np.asarray(x, np.float32)[layer, s, :n].reshape(n, hk, hd)
                  .transpose(1, 0, 2) for x in (k, v))
        rep = q.shape[1] // hk
        ks, vs = (jnp.asarray(np.repeat(x, rep, axis=0), q.dtype)[None]
                  for x in (ks, vs))
        out.append(reference_attention(q[s][None, :, None], ks, vs,
                                       causal=False)[0, :, 0])
    return jnp.stack(out)


@pytest.mark.parametrize("lengths", LENGTHS, ids=str)
@pytest.mark.parametrize("layout", LAYOUTS, ids=str)
def test_kernel_attends_the_live_columns_as_kv_attend_does(layout, lengths):
    """bf16 pool and queries: the kernel and ``_kv_attend`` agree to bf16
    rounding of values of order 1, and each is ``reference_attention`` over
    the live columns alone to its tolerance; the layer is the last."""
    h, hk, hd = LAYOUTS[layout]
    n = jnp.asarray(LENGTHS[lengths], jnp.int32)
    q, k, v = _pool(layout, len(LENGTHS[lengths]), jnp.bfloat16)
    layer = jnp.int32(LAYERS - 1)
    got = jax.jit(lambda *a: decode_attend(*a, interpret=True, block=BK))(
        q, k, v, layer, n)
    want = _xla(q, k, v, layer, n)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    hand = np.asarray(_by_hand(q, k, v, LAYERS - 1, n, hk, hd), np.float32)
    for out in (got, want):
        np.testing.assert_allclose(np.asarray(out, np.float32), hand,
                                   atol=3e-2)


@pytest.mark.parametrize("layer", (0, LAYERS - 1), ids=("first", "last"))
@pytest.mark.parametrize("layout", LAYOUTS, ids=str)
def test_kernel_reads_its_own_layer_and_nothing_dead(layout, layer):
    """float32, where the two paths agree to 1e-5: the layer asked for, the
    first and the last, and the dead columns never enter: poisoned with
    huge values (other layers, and every column at or past a lane's
    length) they change nothing."""
    n = jnp.asarray(LENGTHS["mixed"], jnp.int32)
    q, k, v = _pool(layout, len(LENGTHS["mixed"]), jnp.float32, seed=1)
    want = _xla(q, k, v, layer, n)
    dead = (jnp.arange(MAX_LEN)[None, :] >= n[:, None])[None, :, :, None, None]
    other = (jnp.arange(LAYERS) != layer)[:, None, None, None, None]
    k, v = (jnp.where(dead | other, 1e4, x) for x in (k, v))
    got = jax.jit(lambda *a: decode_attend(*a, interpret=True, block=BK))(
        q, k, v, jnp.int32(layer), n)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_a_length_past_the_lane_is_the_whole_lane():
    """A parked lane that is full stands at position ``max_len`` (its write
    was dropped): it attends the lane, and no block past it is fetched."""
    q, k, v = _pool("olmoe", 2, jnp.float32)
    n = jnp.asarray([MAX_LEN + 1, 5], jnp.int32)
    got = decode_attend(q, k, v, jnp.int32(1), n, interpret=True, block=BK)
    np.testing.assert_allclose(got, _xla(q, k, v, 1, n), atol=1e-5)


@pytest.mark.parametrize("row,max_len,dtype,takes", [
    ((16, 128), 1024, jnp.bfloat16, True),      # OPT 1.3B, OLMoE
    ((8, 128), 2048, jnp.bfloat16, True),       # eight KV heads of 128
    ((16, 128), 1024, jnp.float32, True),
    ((1, 512), 4096, jnp.bfloat16, False),      # LFM2: all KV heads one row
    ((1, 1024), 16384, jnp.bfloat16, False),    # K-EXAONE likewise
    ((4, 128), 4096, jnp.bfloat16, False),      # half a sublane tile
    ((16, 64), 1024, jnp.bfloat16, False),      # rows under 128 lanes
    ((16, 128), 128, jnp.bfloat16, False),      # a ring: one block
    ((16, 128), 1000, jnp.bfloat16, False),     # no whole blocks
    ((16, 128), 1024, jnp.int8, False),
], ids=lambda v: str(v).replace(" ", ""))
def test_supported_says_which_pools_the_kernel_takes(row, max_len, dtype,
                                                     takes):
    assert supported(row, max_len, dtype) is takes
    assert (block_columns(row, max_len, dtype) == 128) is takes


def test_a_pool_the_kernel_does_not_take_is_refused_by_name():
    q = jnp.zeros((2, 8, 64), jnp.bfloat16)
    pool = jnp.zeros((1, 2, 256, 4, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"rows \(4, 128\)"):
        decode_attend(q, pool, pool, 0, jnp.ones(2, jnp.int32),
                      interpret=True)


# ------------------------------------------------------------- who takes it

SLOTS, LANE = 3, 256


def _gpt2(**kw):
    # sixteen heads of 64: stored rows (8, 128), two heads a row
    return GPT2Model(GPT2Config(**{**dict(
        vocab_size=64, n_positions=LANE, n_embd=1024, n_layer=2, n_head=16,
        pad_vocab_to_multiple=1, dtype="float32"), **kw}))


@pytest.fixture
def spy(monkeypatch):
    """The program believes it will run on a TPU; the kernel, where the
    cached forward takes it, runs in interpret mode and is counted."""
    calls = []
    real = decode_attention.decode_attend

    def counted(q, k_pool, v_pool, layer, lengths):
        calls.append(q.shape)
        return real(q, k_pool, v_pool, layer, lengths, interpret=True)

    monkeypatch.setattr(topology, "on_tpu", lambda: True)
    monkeypatch.setattr(decode_attention, "decode_attend", counted)
    return calls


def _step(model, fn="decode_with_slots", t=1, positions=None, seed=0):
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    cache = jax.tree.map(
        lambda x: jnp.asarray(rng.randn(*x.shape) * 0.5, x.dtype),
        model.init_kv_cache(SLOTS, LANE, dtype=jnp.float32))
    ids = jnp.asarray(rng.randint(0, 64, (SLOTS, t)), jnp.int32)
    if positions is None:
        positions = jnp.asarray([0, 127, 200], jnp.int32)
    return getattr(model, fn)(params, ids, cache, positions)[:2]


def test_a_decode_step_on_a_tpu_takes_the_kernel_and_gives_the_same(spy):
    """``decode_with_slots`` over a pool of (8, 128) rows, slots at position
    0, at a block's last column and past it: one kernel call a layer body,
    and logits and pool equal to the XLA attend's."""
    model = _gpt2()
    assert model.decode_kernel_block(
        model.init_kv_cache(SLOTS, LANE, dtype=jnp.float32)) == 128
    logits, pool = _step(model)
    assert spy == [(SLOTS, 16, 64)]             # the layer scan's one body
    spy.clear()
    topology.on_tpu = lambda: False             # the fixture restores it
    want_logits, want_pool = _step(model)
    assert spy == []
    np.testing.assert_allclose(logits, want_logits, atol=2e-4, rtol=2e-4)
    for name in pool:       # the second layer's rows carry the first's sum
        np.testing.assert_allclose(pool[name], want_pool[name], atol=1e-5)


@pytest.mark.parametrize("case", ("several_tokens", "scalar_start", "bias",
                                  "layer_extras", "sliding_window", "ring",
                                  "short_lane"))
def test_everything_else_keeps_the_xla_attend(spy, case):
    """On a TPU, on one device: a block of several tokens a slot
    (``verify_with_slots``), a scalar start (the static ``generate()``
    path), ALiBi's bias, GPT-Neo's local layers, a sliding window in the
    mask, K-EXAONE (rings for its window layer, and ONE stored row a token
    in its full layer), and a lane of one block all stay on
    ``_kv_attend``."""
    if case == "several_tokens":
        _step(_gpt2(), "verify_with_slots", t=2)
    elif case == "scalar_start":
        _step(_gpt2(), "apply_with_cache", positions=jnp.int32(5))
    elif case == "bias":
        _step(BloomModel(BloomConfig(
            vocab_size=64, n_positions=LANE, n_embd=1024, n_layer=2,
            n_head=8, pad_vocab_to_multiple=1, dtype="float32")))
    elif case == "layer_extras":
        _step(GPTNeoModel(GPTNeoConfig(
            vocab_size=64, n_positions=LANE, n_embd=1024, n_layer=2,
            n_head=8, local_window=4, attention_layers=("global", "local"),
            pad_vocab_to_multiple=1, dtype="float32")))
    elif case == "sliding_window":
        model = LlamaModel(LlamaConfig(
            vocab_size=64, n_positions=LANE, n_embd=1024, n_layer=2,
            n_head=8, n_kv_head=8, mlp_hidden=64, sliding_window=32,
            pad_vocab_to_multiple=1, dtype="float32"))
        assert model.init_kv_cache(1, LANE)["k"].shape[3:] == (8, 128)
        _step(model)
    elif case == "ring":
        from deepspeed_tpu.models.kexaone import FULL, SLIDING
        model = KExaoneModel(KExaoneConfig(
            vocab_size=64, n_positions=LANE, n_embd=256, n_layer=2, n_head=4,
            n_kv_head=2, head_dim=64, mlp_hidden=64,
            moe_intermediate_size=32,
            num_experts=4, top_k=2, sliding_window=128,
            layer_types=(SLIDING, FULL), dtype="float32"))
        _step(model)
    else:
        model = _gpt2(n_positions=128)
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_kv_cache(SLOTS, 128, dtype=jnp.float32)
        model.decode_with_slots(params, jnp.zeros((SLOTS, 1), jnp.int32),
                                cache, jnp.asarray([0, 5, 127], jnp.int32))
    assert spy == []


def test_the_cpu_and_a_mesh_of_several_devices_keep_the_xla_attend(
        monkeypatch):
    """The same model and pool that take the kernel on one TPU: not on the
    CPU (tier-1's path), and not under a mesh of more than one device, where
    GSPMD could not partition the Mosaic call and the pool may be sharded
    over ``model``; a mesh of ONE device takes it."""
    model = _gpt2()
    cache = jax.eval_shape(
        lambda: model.init_kv_cache(SLOTS, LANE, dtype=jnp.bfloat16))
    assert model.decode_kernel_block(cache) is None             # the CPU
    monkeypatch.setattr(topology, "on_tpu", lambda: True)
    assert model.decode_kernel_block(cache) == 128
    devices = np.array(jax.devices())
    with jax.sharding.Mesh(devices[:2], ("model",)):
        assert model.decode_kernel_block(cache) is None
    with jax.sharding.Mesh(devices[:1], ("model",)):
        assert model.decode_kernel_block(cache) == 128
