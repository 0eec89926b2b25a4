"""SDAR (models/sdar.py): generation by diffusion over blocks through the
slot pool, beside the plain float32 reference (chipbench/reference_sdar.py)
at a size the CPU holds. The model's forward and its cached forwards under
the block mask are compared as logits; what ``ServingEngine`` streams is
compared with ``reference_sdar.generate`` id for id, with the pass of its
block at which each was fixed.

Tolerance: float32 system against float32 reference differ by summation
order only (``tests/unit/test_olmoe.py``): 2e-5 of the logits' RMS.
"""

import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import deepspeed_tpu                                               # noqa: E402
from chipbench import reference_sdar, weights_sdar                 # noqa: E402
from deepspeed_tpu.inference.speculative import (row_keys,         # noqa: E402
                                                 sample_rows)
from deepspeed_tpu.models.llama import LlamaModel                  # noqa: E402
from deepspeed_tpu.models.sdar import SDARConfig, SDARModel        # noqa: E402
from deepspeed_tpu.runtime.config_utils import ConfigError         # noqa: E402
from deepspeed_tpu.serving import SamplingParams, ServingEngine    # noqa: E402

F32_TOL = 2e-5
VOCAB, MASK, B, MAX_LEN = 250, 249, 4, 48


def dims_of(block=B):
    return {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2,
            "head_dim": 32, "experts": 8, "top_k": 2, "expert_ff": 32,
            "vocab": VOCAB, "positions": 64, "rope_theta": 1e6,
            "rms_eps": 1e-6, "norm_topk_prob": True, "block_length": block,
            "mask_token_id": MASK}


def seeded(key, block=B):
    """The benchmark's weights with tables large enough that the ids a
    block is given differ from position to position."""
    w = weights_sdar.make(dims_of(block), key, vocab_multiple=128)
    return {**w, "wte": w["wte"] * 20, "lm_head": w["lm_head"] * 5}


def tiny(block=B, dtype="float32"):
    class Seeded(SDARModel):
        def init(self, rng):
            return seeded(rng, block)
    return Seeded(SDARConfig(
        vocab_size=VOCAB, n_positions=64, n_embd=64, n_layer=2, n_head=4,
        n_kv_head=2, head_dim=32, mlp_hidden=32, num_experts=8, top_k=2,
        block_length=block, mask_token_id=MASK, dtype=dtype))


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.fixture(scope="module")
def engine():
    return deepspeed_tpu.init_inference(
        tiny(), config={"dtype": "float32", "max_tokens": 64, "seed": 3})


@pytest.fixture(scope="module")
def weights():
    return jax.jit(seeded)(jax.random.PRNGKey(3))


IDS = np.random.default_rng(1).integers(0, VOCAB, (2, 24), dtype=np.int32)


# ------------------------------------------------------- the model's forwards
def test_weights_tree_is_the_models_tree():
    model = SDARModel(tiny().config)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: weights_sdar.make(dims_of(), k),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, want)) == \
        jax.tree.leaves(jax.tree.map(lambda a: a.shape, got))
    assert model.block_length == B and model.denoised_blocks == ("k", "v")
    assert LlamaModel.block_length == 1 and not LlamaModel.denoised_blocks
    # a token's four KV heads are one stored row
    assert model.init_kv_cache(3, 16)["k"].shape == (2, 3, 16, 1, 64)


def test_apply_is_the_reference_under_the_block_mask(weights):
    got = tiny().logits(weights, jnp.asarray(IDS), train=False)[..., :VOCAB]
    want = np.stack([np.asarray(reference_sdar.logits(
        weights, row, dims_of()))[:, :VOCAB] for row in IDS])
    assert rel_rms(got, want) < F32_TOL
    # and the mask is the blocks': with a causal one the rows differ
    causal = np.asarray(reference_sdar.logits(
        weights, IDS[0], dims_of(block=1)))[:, :VOCAB]
    assert rel_rms(causal, want[0]) > 1e-2


def test_the_cached_forwards_are_the_reference(weights):
    """A prefill of whole blocks, then a pass over one block more a slot at
    a position of its own, some of it ``[MASK]``."""
    model, dims = tiny(), dims_of()
    cache = model.init_kv_cache(2, 32, dtype=jnp.float32)
    got, cache = model.apply_with_cache(weights, jnp.asarray(IDS[:, :16]),
                                        cache, jnp.int32(0))
    want = np.asarray(reference_sdar.logits(weights, IDS[0, :16], dims))
    assert rel_rms(got[0, :, :VOCAB], want[:, :VOCAB]) < F32_TOL
    flags = np.array([[0, 1, 1, 0], [1, 1, 1, 1]], bool)
    fed = np.where(flags, MASK, IDS[:, 16:20])
    got, cache = model.verify_with_slots(weights, jnp.asarray(fed), cache,
                                         jnp.asarray([16, 16]))
    for row in range(2):
        masked = np.concatenate([np.zeros(16, bool), flags[row]])
        want = np.asarray(reference_sdar.logits(
            weights, IDS[row, :20], dims, masked=masked))[16:, :VOCAB]
        assert rel_rms(got[row, :, :VOCAB], want) < F32_TOL


def test_block_length_one_is_the_llama_programs():
    """``block_length`` 1: the causal mask, the causal flag, no bias, the
    decode kernel's answer as the base gives it."""
    model = tiny(block=1)
    assert model.causal_attention and model._train_attn_bias(8) is None
    assert not model.denoised_blocks and model._rows_as_heads_from == 2
    q = np.arange(6)[None, None, :, None]
    k = np.arange(6)[None, None, None, :]
    np.testing.assert_array_equal(model._decode_attn_mask(q, k), k <= q)
    np.testing.assert_array_equal(tiny()._decode_attn_mask(q, k),
                                  k // B <= q // B)


# ------------------------------------------------------ through ServingEngine
def serve(engine, steps, prompts, depth=1, slots=3, sampling=None, **over):
    srv = ServingEngine(engine, {
        "num_slots": slots, "max_model_len": MAX_LEN, "max_queue": 64,
        "block_diffusion": {"denoising_steps": steps}, **over})
    if not depth:       # every pass read before the next is sent
        sched = srv.scheduler
        sched._decode_blocks = functools.partial(sched._decode_blocks,
                                                 pipelined=False)
    rids = [srv.submit(p, (sampling or {}).get(i) or SamplingParams(
        max_new_tokens=n, eos_token_id=eos))
        for i, (p, n, eos) in enumerate(prompts)]
    srv.run_until_idle()
    reqs = [srv.result(r) for r in rids]
    return srv, reqs


def expected(weights, prompt, max_new, steps, eos=None, choose=None):
    kw = {} if choose is None else {"choose": choose}
    ids, fixed, _ = reference_sdar.generate(
        weights, prompt, max_new, steps, dims_of(), eos=eos, pad_to=MAX_LEN,
        **kw)
    return list(ids), list(fixed)


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(0, MASK, n).astype(np.int32)


#: prompts of every ``len % 4``, ``max_new`` of every remainder (and one that
#: ends in its first block), more requests than slots so slots are reused
#: and several slots stand at different passes of their blocks in one tick
MIX = [(7, 9), (8, 4), (5, 6), (3, 11), (12, 1), (6, 2), (9, 7), (10, 5)]


@pytest.mark.parametrize("steps", (1, 2, 4))
@pytest.mark.parametrize("depth", (1, 0), ids=("pipelined", "read-first"))
def test_streams_are_the_published_loop(engine, weights, steps, depth):
    """Every request of the mix, id for id and pass for pass, whichever way
    the tick reads; the pipelined tick sends every pass but the first
    behind the one in flight and drops nothing (no EOS)."""
    prompts = [(prompt_of(n, i), new, None) for i, (n, new) in enumerate(MIX)]
    srv, reqs = serve(engine, steps, prompts, depth)
    for (prompt, new, _), req in zip(prompts, reqs):
        ids, fixed = expected(weights, prompt, new, steps)
        assert req.tokens == ids and req.fixed_pass == fixed
    m = srv.metrics
    assert srv.decode_executables() == 1 and srv.scheduler._flight is None
    assert m.block_tokens == sum(new for _, new in MIX) == m.tokens_out
    assert m.dropped_rows == 0
    assert m.pipelined_ticks == (m.decode_ticks - 1 if depth else 0)
    # what a pass of a slot yields: B tokens over steps + 1 passes, less
    # what max_new_tokens cut and the writing passes a last block skips
    assert m.block_cut == sum(-(len(p) + new) % B for p, new, _ in prompts)
    assert m.unmask_passes > m.write_passes > 0
    srv.shutdown()


@pytest.mark.parametrize("rem", range(B))
def test_a_prompt_of_every_remainder_alone(engine, weights, rem):
    """One request a pool, so the first block's passes follow from the
    prompt's remainder alone: ``rem`` tokens open it, the rest is masked."""
    prompt = prompt_of(8 + rem, 40 + rem)
    _, (req,) = serve(engine, 2, [(prompt, 7, None)], slots=1)
    ids, fixed = expected(weights, prompt, 7, 2)
    assert req.tokens == ids and req.fixed_pass == fixed
    # a first block with two or fewer masked positions takes one pass
    assert (max(fixed[:B - rem]) == 0) == (B - rem <= 2)


def test_a_prompt_shorter_than_a_block_prefills_nothing(engine, weights):
    prompt = prompt_of(3, 7)
    srv, (req,) = serve(engine, 2, [(prompt, 6, None)], slots=1)
    assert req.tokens == expected(weights, prompt, 6, 2)[0]
    assert engine.slot_executables("slot_prefill", 4, MAX_LEN) <= 1


@pytest.mark.parametrize("at", range(B))
def test_eos_inside_a_block_ends_the_request_there(engine, weights, at):
    """The id the reference gives at offset ``at`` of the second block, made
    the request's EOS: the stream ends with it, the rest of the block is
    dropped, and the pass in flight for the slot is computed and dropped."""
    prompt = prompt_of(8, 60 + at)
    free, _ = expected(weights, prompt, 12, 2)
    eos = free[B + at]
    ids, fixed = expected(weights, prompt, 12, 2, eos=eos)
    assert ids[-1] == eos and len(ids) <= B + at + 1
    srv, (req,) = serve(engine, 2, [(prompt, 12, eos)], slots=1)
    assert req.tokens == ids and req.fixed_pass == fixed
    assert srv.metrics.block_cut == -len(ids) % B
    assert srv.metrics.dropped_rows == 1


def test_a_prompt_may_hold_the_mask_id(engine, weights):
    """Masked-ness is a flag: a prompt token equal to ``mask_token_id`` is a
    token, in the prefilled blocks and in the first block alike."""
    prompt = prompt_of(10, 5)
    prompt[[2, 9]] = MASK
    _, (req,) = serve(engine, 2, [(prompt, 9, None)], slots=1)
    ids, fixed = expected(weights, prompt, 9, 2)
    assert req.tokens == ids and req.fixed_pass == fixed


def test_a_sampled_row_beside_greedy_ones(engine, weights):
    """One request at a temperature among greedy ones: the greedy streams
    are the reference's whatever rides beside them, and the sampled one is
    the reference's loop with the program's own draw at each position
    (``sample_rows`` keyed by the request's seed and the position), the
    confidence its probability at that temperature; pipelined or not."""
    temp, seed = 0.8, 11
    prompts = [(prompt_of(n, 70 + i), new, None)
               for i, (n, new) in enumerate([(7, 9), (9, 8), (6, 10)])]
    sampled = {1: SamplingParams(max_new_tokens=8, temperature=temp,
                                 seed=seed)}

    def choose(rows, positions):
        n = len(positions)
        ids = np.asarray(sample_rows(
            jnp.asarray(rows), jnp.full(n, temp), jnp.zeros(n, jnp.int32),
            jnp.ones(n), row_keys(jnp.full(n, seed, jnp.int32),
                                  jnp.asarray(positions, jnp.int32)), VOCAB))
        scaled = rows / temp
        conf = np.exp(scaled[np.arange(n), ids] - scaled.max(-1)) / \
            np.exp(scaled - scaled.max(-1, keepdims=True)).sum(-1)
        return ids, conf

    streams = []
    for depth in (1, 0):
        srv, reqs = serve(engine, 2, prompts, depth, sampling=sampled)
        streams.append([r.tokens for r in reqs])
        assert srv.metrics.sampled_ticks > 0
    assert streams[0] == streams[1]
    for i, (prompt, new, _) in enumerate(prompts):
        ids, _ = expected(weights, prompt, new, 2,
                          choose=choose if i == 1 else None)
        assert streams[0][i] == ids
    assert streams[0][1] != expected(weights, prompts[1][0], 8, 2)[0]


def test_costs_and_counters_take_tokens_and_columns(engine):
    """The cost plane splits a pass by the columns a row advanced and counts
    the tokens its block delivered; the tenant counters and the phase
    records count what was delivered, not rows."""
    from deepspeed_tpu.telemetry import get_tracer
    prompts = [(prompt_of(7, 1), 9, None), (prompt_of(8, 2), 4, None)]
    srv, reqs = serve(engine, 2, prompts, cost={"enabled": True})
    cost = srv.scheduler.cost
    assert [cost.record_for(r).tokens for r in reqs] == [9, 4]
    assert srv.metrics.tenant_stats["default"].tokens_out == 13
    names = {p[0] for p in get_tracer().phases()}
    assert {"serve/block_pass", "serve/block_write",
            "serve/kv_read"} <= names
    srv.shutdown()
    assert get_tracer().counter_value("serve/block_tokens") is None


# ------------------------------------------------------------ what is refused
@pytest.mark.parametrize("over,word", [
    ({"speculative": {"enabled": True, "k": 2}}, "speculative"),
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    ({"chunked_prefill": {"enabled": True, "chunk_tokens": 16}},
     "chunked_prefill"),
    ({"kv_quant": {"enabled": True}}, "kv_quant"),
    ({"role": "prefill"}, "role=prefill"),
    ({"block_diffusion": {"denoising_steps": 3}}, "does not divide"),
    ({"block_diffusion": {"denoising_steps": 8}}, "does not divide"),
    ({"max_model_len": 46}, "whole blocks"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_refused_at_validation_with_the_reason(engine, over, word):
    with pytest.raises(ConfigError, match=word) as err:
        ServingEngine(engine, {"num_slots": 2, "max_model_len": MAX_LEN,
                               **over})
    assert len(str(err.value)) > 60         # a reason, not a word


def test_no_key_chooses_a_remasking_rule(engine):
    """``low_confidence_static`` is the schedule, not an option: a dynamic
    rule's passes a block are known only once each is read."""
    with pytest.raises(ConfigError, match="unknown config key.*remasking"):
        ServingEngine(engine, {"num_slots": 2, "max_model_len": MAX_LEN,
                               "block_diffusion": {
                                   "remasking": "low_confidence_dynamic"}})


def test_a_family_without_blocks_refuses_the_block(engine):
    plain = deepspeed_tpu.init_inference(
        tiny(block=1), config={"dtype": "float32", "max_tokens": 64})
    with pytest.raises(ConfigError, match="block_length 1"):
        ServingEngine(plain, {"num_slots": 2, "max_model_len": MAX_LEN,
                              "block_diffusion": {"denoising_steps": 2}})
    with pytest.raises(ValueError, match="whole number of blocks"):
        pool = engine.init_slot_pool(1, MAX_LEN)
        engine.slot_prefill(pool, 0, np.zeros(6, np.int32))


def test_block_length_one_serves_what_llama_serves():
    """A family with ``block_length`` 1 takes the plain decode step (the
    program the serving tests pin) and streams ``generate()``'s tokens."""
    plain = deepspeed_tpu.init_inference(
        tiny(block=1), config={"dtype": "float32", "max_tokens": 64,
                               "seed": 3})
    srv = ServingEngine(plain, {"num_slots": 2, "max_model_len": MAX_LEN})
    prompt = prompt_of(7, 3)
    rid = srv.submit(prompt, SamplingParams(max_new_tokens=6))
    srv.run_until_idle()
    want = np.asarray(plain.generate(prompt[None], max_new_tokens=6))[0, 7:]
    assert srv.result(rid).tokens == list(want)
    assert srv.decode_executables() == 1 and srv.scheduler.block == 1
    assert plain.slot_executables("slot_decode", 2, MAX_LEN) == 1
    assert not any(k[0] == "slot_block" for k in plain._slot_fns)
    srv.shutdown()
