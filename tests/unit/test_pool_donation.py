"""KV-pool ownership tests (ISSUE 28).

The contract under test: every compiled program whose signature is pool
in, pool out CONSUMES its pool argument (``donate_argnums``), for the fp
and the int8 pool alike, so a lane write is an in-place
``dynamic_update_slice`` and never a second pool. After a call the pool
handed over is deleted, the pool returned holds every other slot's lane
bit for bit, and the written lane is what the program wrote before it
donated. A program that drops the alias (a sharding or layout mismatch
between input and output) fails here, not on the chip.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.engine import _next_pow2
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (RequestState, SamplingParams,
                                   ServingEngine)

VOCAB = 96
SLOTS, MAX_LEN = 4, 32
PROGRAMS = ("slot_prefill", "slot_suffix_prefill", "slot_chunk_prefill",
            "slot_copy_lane", "slot_insert_lane")


@pytest.fixture(scope="module")
def engine():
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=256,
                                 n_embd=32, n_layer=2, n_head=2,
                                 pad_vocab_to_multiple=1, dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,),
                                                dtype=np.int32)


def _host(tree):
    """A host COPY: on the CPU ``np.asarray`` is a view that pins the
    device buffer, and a pinned buffer cannot be donated."""
    return jax.tree.map(lambda leaf: np.array(leaf, copy=True), tree)


def _lane(tree, slot):
    """Slot ``slot``'s lane of a host pool, every leaf ``[L, 1, ...]``."""
    return jax.tree.map(lambda leaf: leaf[:, slot:slot + 1], tree)


def _assert_same(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) and la, what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _filled_pool(engine, quantized):
    """A pool whose every lane holds another prompt's K/V."""
    pool = engine.init_slot_pool(SLOTS, MAX_LEN, quantize=quantized)
    for slot in range(SLOTS):
        pool, _ = engine.slot_prefill(pool, slot, _prompt(5 + slot, slot))
    return pool


def _reference_lane(engine, quantized, tokens, start, before_lane):
    """The lane a prefill of ``tokens`` at column ``start`` has to leave,
    computed apart from the pool programs: ``apply_with_cache`` over a
    one-slot cache (fresh, or ``before_lane`` for a suffix), written
    through ``_write_lane`` into a fresh one-slot pool."""
    model = engine.module
    ids = np.zeros((1, _next_pow2(int(tokens.size))), np.int32)
    ids[0, :tokens.size] = tokens
    one = engine.init_slot_pool(1, MAX_LEN, quantize=quantized)

    @jax.jit
    def ref(params, ids, one, before_lane):
        mini = model.init_kv_cache(1, MAX_LEN, dtype=engine.dtype) \
            if before_lane is None else \
            engine._read_lane(before_lane, jnp.int32(0), quantized)
        _, mini = model.apply_with_cache(params, ids, mini,
                                         jnp.int32(start))
        return engine._write_lane(one, mini, jnp.int32(0), quantized)

    with engine.mesh:
        return _host(ref(engine.params, jnp.asarray(ids), one, before_lane))


@pytest.mark.parametrize("quantized", (False, True), ids=("fp", "q8"))
@pytest.mark.parametrize("program", PROGRAMS)
def test_pool_program_consumes_its_pool(engine, program, quantized):
    pool = _filled_pool(engine, quantized)
    before = _host(pool)
    handed_over = jax.tree.leaves(pool)
    target = 2
    if program == "slot_prefill":
        tokens = _prompt(11, 40)
        new, tok = engine.slot_prefill(pool, target, tokens)
        expected = _reference_lane(engine, quantized, tokens, 0, None)
    elif program == "slot_suffix_prefill":
        # slot 2 holds 7 prompt columns; extend it from column 7
        tokens = _prompt(6, 41)
        new, tok = engine.slot_suffix_prefill(pool, target, tokens, 7)
        expected = _reference_lane(engine, quantized, tokens, 7,
                                   _lane(before, target))
    elif program == "slot_chunk_prefill":
        # donated since it was written; held to the same rule here
        tokens = _prompt(8, 42)
        new = engine.slot_chunk_prefill(pool, target, tokens, 7)
        expected = _reference_lane(engine, quantized, tokens, 7,
                                   _lane(before, target))
    elif program == "slot_copy_lane":
        new = engine.slot_copy_lane(pool, 0, target)
        expected = _lane(before, 0)
    else:
        lane = _lane(before, 3)
        new = engine.slot_insert_lane(pool, target, lane)
        expected = lane
    # the alias took: every leaf handed over is gone, fp and q8 alike
    assert handed_over and all(leaf.is_deleted() for leaf in handed_over)
    if program in ("slot_prefill", "slot_suffix_prefill"):
        assert 0 <= tok < VOCAB
    after = _host(new)
    for slot in range(SLOTS):
        if slot != target:
            _assert_same(_lane(after, slot), _lane(before, slot),
                         f"{program}: slot {slot} changed")
    _assert_same(_lane(after, target), expected,
                 f"{program}: written lane")


@pytest.mark.parametrize("quantized", (False, True), ids=("fp", "q8"))
def test_refused_call_keeps_the_pool_and_a_consumed_pool_fails_loudly(
        engine, quantized):
    """Checks that raise before the dispatch leave the pool alive; a pool
    that a call consumed is refused by name, not by an XLA buffer error."""
    pool = _filled_pool(engine, quantized)
    before = _host(pool)
    with pytest.raises(ValueError, match="prompt length"):
        engine.slot_prefill(pool, 0, _prompt(MAX_LEN + 1, 1))
    with pytest.raises(ValueError, match="exceeds"):
        engine.slot_suffix_prefill(pool, 0, _prompt(8, 2), MAX_LEN - 4)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(pool))
    new = engine.slot_copy_lane(pool, 0, 1)
    _assert_same(_lane(_host(new), 3), _lane(before, 3), "slot 3")
    for call in (lambda: engine.slot_prefill(pool, 0, _prompt(4, 3)),
                 lambda: engine.slot_copy_lane(pool, 0, 1),
                 lambda: engine.slot_extract_lane(pool, 0),
                 lambda: engine.slot_decode_step(
                     pool, np.zeros(SLOTS, np.int32),
                     np.ones(SLOTS, np.int32),
                     np.zeros(SLOTS, np.float32))):
        with pytest.raises(RuntimeError, match="consumed"):
            call()


def test_serving_parity_through_prefix_hit_and_chunked_admission(engine):
    """One greedy stream per admission path that now donates — a chunked
    admission (``slot_chunk_prefill`` + the final ``slot_suffix_prefill``),
    a prefix hit under the chunk size (``slot_copy_lane`` +
    ``slot_suffix_prefill``) and a prefix hit that is chunked — each
    equal to ``generate()`` token for token."""
    shared = _prompt(40, 50)
    prompts = [np.concatenate([shared, _prompt(n, s)]).astype(np.int32)
               for n, s in ((37, 51), (9, 52), (50, 53))]
    srv = ServingEngine(engine, {
        "num_slots": 3, "max_model_len": 256, "max_queue": 8,
        "prefix_cache": {"enabled": True, "min_prefix_len": 8},
        "chunked_prefill": {"enabled": True, "chunk_tokens": 16}})
    for p in prompts:
        # one at a time: a finished prompt parks its lane before the next
        rid = srv.submit(p, SamplingParams(max_new_tokens=6))
        srv.run_until_idle()
        req = srv.result(rid)
        assert req.state is RequestState.FINISHED
        ref = np.asarray(engine.generate(p[None], max_new_tokens=6))[0]
        np.testing.assert_array_equal(req.output_ids, ref)
    assert srv.scheduler.prefix_cache.hits >= 2
    assert engine.slot_chunk_executables(3, 256, 16, quantized=False) == 1
    srv.shutdown()
