"""The token-major KV pool and its one write (ISSUE 32).

The contract under test: the pool's leaves are ``[L, S, max_len, Hk, hd]``
(heads narrower than 128 lanes stored ``128 // hd`` to a row: the same bytes
in the same order), a cached step writes the new tokens' rows and nothing
else, every cached step takes the write and the read from one pair of
helpers, and the compiled decode step updates the donated pool in place.

The plain implementation here shares no attention or cache code with the
model: one slot at a time, one layer at a time, it gathers the lane's live
columns out of a host copy of the pool, appends the new token's K and V,
repeats grouped KV heads and takes a float32 softmax. It reads the model's
own mask and bias hooks (``_decode_attn_mask_ex``, ``_decode_attn_bias``) and
its block (``_decode_block``), which are not what this PR changes.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.kv_quant import pool_nbytes
from deepspeed_tpu.models.bloom import BloomConfig, BloomModel
from deepspeed_tpu.models.gpt2 import _kv_row_shape
from deepspeed_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
from deepspeed_tpu.models.opt import OPTConfig, OPTModel
from deepspeed_tpu.serving.fleet.handoff import KVHandoff

SLOTS, MAX_LEN = 4, 16
# rows at different positions in one call: the first column, the last one
POSITIONS = np.array([0, 5, MAX_LEN - 1, 9], np.int32)


def _models():
    """One of each kind the cached steps special-case: OPT with two heads
    of 64 to a stored row; grouped KV heads (4 query heads on 2 KV heads of
    32: four heads' worth of lanes to a row, two query heads each); OLMoE
    (routing returned); BLOOM (ALiBi bias); GPT-Neo (``_layer_extras``: a
    local window of 4 in its second layer)."""
    return {
        "opt": OPTModel(OPTConfig(
            vocab_size=96, n_positions=64, n_embd=128, n_layer=2, n_head=2,
            pad_vocab_to_multiple=1, dtype="float32")),
        "llama_gqa": LlamaModel(LlamaConfig(
            vocab_size=96, n_positions=64, n_embd=256, n_layer=2, n_head=8,
            n_kv_head=4, mlp_hidden=96, pad_vocab_to_multiple=1,
            dtype="float32")),
        "olmoe": OLMoEModel(OLMoEConfig(
            vocab_size=96, n_positions=64, n_embd=64, n_layer=2, n_head=2,
            mlp_hidden=32, num_experts=4, top_k=2, pad_vocab_to_multiple=1,
            dtype="float32")),
        "bloom": BloomModel(BloomConfig(
            vocab_size=96, n_positions=64, n_embd=64, n_layer=2, n_head=4,
            pad_vocab_to_multiple=1, dtype="float32")),
        "gpt_neo": GPTNeoModel(GPTNeoConfig(
            vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            local_window=4, attention_layers=("global", "local"),
            pad_vocab_to_multiple=1, dtype="float32")),
    }


MODELS = _models()
FAMILIES = tuple(MODELS)


def _random_pool(model, seed):
    """A pool whose every column holds something, so that 'bit-identical
    everywhere else' means something."""
    shapes = jax.eval_shape(lambda: model.init_kv_cache(SLOTS, MAX_LEN,
                                                        dtype=jnp.float32))
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {name: jax.random.normal(key, shapes[name].shape, jnp.float32)
            for name, key in zip(("k", "v"), keys)}


def _unpacked(model, leaf):
    """A host copy of a pool leaf as ``[L, S, max_len, Hk, hd]``: stored
    rows hold the same values in the same order."""
    leaf = np.array(leaf, copy=True)
    return leaf.reshape(leaf.shape[:3] + (model.kv_heads,
                                          model.config.head_dim))


def _plain_decode(model, params, toks, pool, positions):
    """(logits [S, V], the K and V rows each slot appended [L, S, Hk, hd])
    of one decode token a slot, computed plainly."""
    cfg = model.config
    hk, hd, rep = model.kv_heads, cfg.head_dim, cfg.n_head // model.kv_heads
    k_host, v_host = _unpacked(model, pool["k"]), _unpacked(model, pool["v"])
    extras = model._layer_extras()
    rows = {"k": np.zeros((cfg.n_layer, SLOTS, hk, hd), np.float32),
            "v": np.zeros((cfg.n_layer, SLOTS, hk, hd), np.float32)}
    logits = []
    for s in range(SLOTS):
        pos = int(positions[s])
        pos2d = jnp.full((1, 1), pos, jnp.int32)
        x = model._embed(params, jnp.asarray(toks[s]).reshape(1, 1),
                         positions=pos2d)
        q_pos = jnp.full((1, 1, 1, 1), pos, jnp.int32)
        k_pos = jnp.arange(pos + 1)[None, None, None, :]
        for layer in range(cfg.n_layer):
            lp = jax.tree.map(lambda a: a[layer], params["blocks"])
            extra = None if extras is None else extras[layer]

            def attn(q, k, v, layer=layer, extra=extra):
                # q [1, H, 1, hd]; k, v [1, Hk, 1, hd]
                rows["k"][layer, s] = np.asarray(k)[0, :, 0]
                rows["v"][layer, s] = np.asarray(v)[0, :, 0]
                live_k = np.concatenate(       # the live columns, gathered
                    [k_host[layer, s, :pos], rows["k"][layer, s][None]])
                live_v = np.concatenate(
                    [v_host[layer, s, :pos], rows["v"][layer, s][None]])
                kk = np.repeat(live_k.transpose(1, 0, 2), rep, axis=0)
                vv = np.repeat(live_v.transpose(1, 0, 2), rep, axis=0)
                scores = np.einsum("hd,hkd->hk", np.asarray(q)[0, :, 0],
                                   kk) / np.sqrt(hd)
                bias = model._decode_attn_bias(q_pos, k_pos)
                if bias is not None:
                    scores = scores + np.asarray(bias, np.float32)[0, :, 0]
                keep = np.asarray(model._decode_attn_mask_ex(
                    q_pos, k_pos, extra))[0, 0]
                scores = np.where(keep, scores, -1e30).astype(np.float32)
                probs = np.exp(scores - scores.max(-1, keepdims=True))
                probs = probs / probs.sum(-1, keepdims=True)
                return jnp.asarray(np.einsum("hk,hkd->hd", probs,
                                             vv))[None, :, None]

            out = model._decode_block(x, lp, attn, jnp.int32(0),
                                      positions=pos2d, extra=extra)
            x = out[0] if isinstance(out, tuple) else out
        x = model._final_norm(params, x)
        lg = x @ model._unembed_weight(params, x.dtype).T
        head_b = model._head_bias(params, lg.dtype)
        logits.append(np.asarray(lg if head_b is None else lg + head_b)[0, 0])
    return np.stack(logits), rows


@pytest.mark.parametrize("family", FAMILIES)
def test_decode_writes_one_row_a_slot_and_agrees_with_plain(family):
    model = MODELS[family]
    params = model.init(jax.random.PRNGKey(3))
    pool = _random_pool(model, 7)
    before = {n: _unpacked(model, pool[n]) for n in pool}
    toks = np.array([5, 17, 40, 63], np.int32)
    want, rows = _plain_decode(model, params, toks, pool, POSITIONS)
    got, new_pool, *stats = jax.jit(
        lambda p, c: model.decode_with_slots(
            p, toks[:, None], c, jnp.asarray(POSITIONS), routing=True))(
        params, pool)
    np.testing.assert_allclose(np.asarray(got)[:, 0], want, atol=2e-5,
                               rtol=2e-5)
    # routing is returned by the family that routes, and by no other
    assert (stats[0] is not None) == (family == "olmoe")
    for name in ("k", "v"):
        assert new_pool[name].shape == pool[name].shape
        after = _unpacked(model, new_pool[name])
        written = np.zeros(after.shape[:3], bool)
        for s, pos in enumerate(POSITIONS):
            written[:, s, pos] = True
            np.testing.assert_allclose(after[:, s, pos], rows[name][:, s],
                                       atol=1e-6, rtol=1e-6)
        # bit-identical everywhere else
        np.testing.assert_array_equal(after[~written], before[name][~written])


def test_stored_rows_fill_a_vector_row_or_hold_one_head():
    assert _kv_row_shape(32, 64) == (16, 128)      # OPT-1.3B: two to a row
    assert _kv_row_shape(16, 128) == (16, 128)     # OLMoE: one head a row
    assert _kv_row_shape(8, 256) == (8, 256)
    assert _kv_row_shape(4, 32) == (1, 128)
    assert _kv_row_shape(3, 64) == (3, 64)         # an odd head stays whole
    assert _kv_row_shape(12, 96) == (12, 96)       # 96 does not divide 128
    for family, (g, w) in (("opt", (1, 128)), ("llama_gqa", (1, 128)),
                           ("bloom", (4, 16))):
        leaf = jax.eval_shape(
            lambda m=MODELS[family]: m.init_kv_cache(SLOTS, MAX_LEN))["k"]
        assert leaf.shape == (2, SLOTS, MAX_LEN, g, w), family


@pytest.mark.parametrize("family", FAMILIES)
def test_verify_of_one_token_is_decode_bit_for_bit(family):
    model = MODELS[family]
    params = model.init(jax.random.PRNGKey(4))
    pool = _random_pool(model, 8)
    toks = jnp.asarray([[9], [2], [77], [31]], jnp.int32)
    pos = jnp.asarray(POSITIONS)
    dec = jax.jit(lambda p, c: model.decode_with_slots(p, toks, c, pos))(
        params, pool)
    ver = jax.jit(lambda p, c: model.verify_with_slots(p, toks, c, pos))(
        params, pool)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(ver)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("family", ("opt", "llama_gqa"))
def test_verify_block_drops_the_rows_that_overhang(family):
    """A block of 4 from column ``max_len - 2`` writes two rows and drops
    two; the lane's other columns, and the other slots' lanes outside their
    own blocks, keep their bits."""
    model = MODELS[family]
    params = model.init(jax.random.PRNGKey(5))
    pool = _random_pool(model, 9)
    before = {n: _unpacked(model, pool[n]) for n in pool}
    block = 4
    pos = np.array([MAX_LEN - 2, 3, 0, MAX_LEN - block], np.int32)
    ids = jnp.asarray(np.arange(SLOTS * block).reshape(SLOTS, block) % 90,
                      jnp.int32)
    logits, new_pool = jax.jit(lambda p, c: model.verify_with_slots(
        p, ids, c, jnp.asarray(pos)))(params, pool)
    assert logits.shape[:2] == (SLOTS, block)
    # the in-range positions' logits are those of single-token steps
    step_pool, step_logits = pool, []
    for j in range(2):
        lg, step_pool = jax.jit(lambda p, c, j=j: model.decode_with_slots(
            p, ids[:, j:j + 1], c, jnp.asarray(pos) + j))(params, step_pool)
        step_logits.append(np.asarray(lg)[:, 0])
    np.testing.assert_allclose(np.asarray(logits)[:, :2],
                               np.stack(step_logits, 1), atol=2e-5, rtol=2e-5)
    for name in ("k", "v"):
        after = _unpacked(model, new_pool[name])
        written = np.zeros(after.shape[:3], bool)
        for s in range(SLOTS):
            written[:, s, pos[s]:min(pos[s] + block, MAX_LEN)] = True
        assert written[:, 0].sum(axis=1).tolist() == [2, 2]
        np.testing.assert_array_equal(after[~written], before[name][~written])
        assert not np.array_equal(after[written], before[name][written])
        np.testing.assert_allclose(
            after[:, 0, MAX_LEN - 2:],
            _unpacked(model, step_pool[name])[:, 0, MAX_LEN - 2:],
            atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------- the engine
VOCAB = 96


@pytest.fixture(scope="module")
def engine():
    return deepspeed_tpu.init_inference(MODELS["opt"],
                                        config={"dtype": "float32"})


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,),
                                                dtype=np.int32)


def _decode(engine, pool, slot, tok, pos, steps):
    """``steps`` greedy tokens of slot ``slot`` (the other slots idle at
    column 0, as the scheduler runs them)."""
    slots = jax.tree.leaves(pool)[0].shape[1]
    out = []
    for _ in range(steps):
        toks = np.zeros(slots, np.int32)
        positions = np.zeros(slots, np.int32)
        toks[slot], positions[slot] = tok, pos
        pool, nxt = engine.slot_decode_step(pool, toks, positions,
                                            np.zeros(slots, np.float32))
        tok, pos = int(nxt[slot]), pos + 1
        out.append(tok)
    return pool, out


@pytest.mark.parametrize("quantized", (False, True), ids=("fp", "int8"))
@pytest.mark.parametrize("move", ("handoff", "copy_lane"))
def test_a_moved_lane_decodes_the_tokens_of_the_uninterrupted_run(
        engine, quantized, move):
    """prefill -> decode -> the lane moved (``slot_extract_lane`` -> a
    ``KVHandoff`` frame -> ``slot_insert_lane`` on a second pool, or
    ``slot_copy_lane`` inside the first) -> decode: the same tokens as
    prefill -> decode with nothing in between."""
    prompt = _prompt(7, 11)
    pool = engine.init_slot_pool(3, 32, quantize=quantized)
    pool, first = engine.slot_prefill(pool, 1, prompt)
    pool, head = _decode(engine, pool, 1, first, len(prompt), 3)
    straight = engine.init_slot_pool(3, 32, quantize=quantized)
    straight, first2 = engine.slot_prefill(straight, 1, prompt)
    _, want = _decode(engine, straight, 1, first2, len(prompt), 8)
    assert first == first2 and head == want[:3]
    kv_len = len(prompt) + 3
    if move == "handoff":
        frame = KVHandoff(prompt=prompt, first_token=first, kv_len=kv_len,
                          lane=engine.slot_extract_lane(pool, 1)).to_bytes()
        lane = KVHandoff.from_bytes(frame).lane
        for leaf in jax.tree.leaves(lane):      # a frame carries the shapes
            assert leaf.shape[:3] == (2, 1, 32)
        other = engine.init_slot_pool(2, 32, quantize=quantized)
        other, _ = engine.slot_prefill(other, 1, _prompt(5, 12))
        other = engine.slot_insert_lane(other, 0, lane)
        _, tail = _decode(engine, other, 0, head[-1], kv_len, 5)
    else:
        pool = engine.slot_copy_lane(pool, 1, 2)
        _, tail = _decode(engine, pool, 2, head[-1], kv_len, 5)
    assert head + tail == want


def test_compiled_decode_step_updates_the_donated_pool_in_place():
    """Ahead-of-time compile of ``slot_decode_step`` (CPU backend, four
    layers): both pool leaves of the output alias the donated input, and
    the program's temporaries are under half the pool's bytes. The parent's
    program (the pool scanned over as xs -> ys, ``jnp.where`` over each
    lane), compiled for the same model, pool and backend, keeps 5,856,416
    bytes of temporaries beside a pool of 4,194,304; this one 1,661,352,
    which are one layer's slabs (the CPU's dot wants its operand
    transposed) and grow with neither layers nor time (both read with this
    test's code, PR 32). On the chip the slabs are read where they lie:
    cell 2's ``jit_dec`` 6.58 GB -> 7.2 MB (PERF.md)."""
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    model = OPTModel(OPTConfig(
        vocab_size=VOCAB, n_positions=64, n_embd=128, n_layer=4, n_head=2,
        pad_vocab_to_multiple=1, dtype="float32"))
    engine = deepspeed_tpu.init_inference(model, config={"dtype": "float32"})
    slots, max_len = 8, 128
    pool = engine.init_slot_pool(slots, max_len)
    zi, zf = np.zeros(slots, np.int32), np.zeros(slots, np.float32)
    pool, _ = engine.slot_decode_step(pool, zi, zi, zf)
    fn = engine._slot_fns[("slot_decode", slots, max_len)]

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    vi = jax.ShapeDtypeStruct((slots,), jnp.int32)
    vf = jax.ShapeDtypeStruct((slots,), jnp.float32)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        shaped(engine.params), shaped(pool), vi, vi, vf, vi, vf, vi,
        vi, jax.ShapeDtypeStruct((slots,), jnp.bool_)).compile()
    first = len(jax.tree.leaves(engine.params))
    assert donated_params_from_hlo(compiled.as_text()) == {first, first + 1}
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_nbytes(pool) == 4194304
    assert mem.temp_size_in_bytes < pool_nbytes(pool) // 2
