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

import inspect
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.engine import _next_pow2
from deepspeed_tpu.inference.kv_quant import read_lane, write_lane
from deepspeed_tpu.inference.speculative import sampling_arrays
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (RequestState, SamplingParams,
                                   ServingEngine)
from deepspeed_tpu.serving.config import DraftConfig

VOCAB = 96
SLOTS, MAX_LEN = 4, 32
# programs that write one lane; programs that write columns of every lane
LANE_PROGRAMS = ("slot_prefill", "slot_suffix_prefill",
                 "slot_chunk_prefill", "slot_copy_lane", "slot_insert_lane")
STEP_PROGRAMS = ("slot_decode_step", "slot_verify_step")
DRAFT_PROGRAMS = ("draft_prefill", "slot_draft_propose")    # fp pools only
K = 2       # draft tokens a speculative step proposes / verifies
# columns of every lane a step program writes (rejected ones are restored)
N_WRITTEN = {"slot_decode_step": 1, "slot_verify_step": K + 1,
             "slot_draft_propose": K + 1}


def _cases(programs):
    """(program, quantized) over both flavours where a program has both."""
    return [pytest.param(p, q, id=f"{p}-{'q8' if q else 'fp'}")
            for p in programs
            for q in ((False,) if p in DRAFT_PROGRAMS else (False, True))]


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
    """A pool whose every lane holds another prompt's K/V: slot ``s``
    holds ``5 + s`` columns."""
    pool = engine.init_slot_pool(SLOTS, MAX_LEN, quantize=quantized)
    for slot in range(SLOTS):
        pool, _ = engine.slot_prefill(pool, slot, _prompt(5 + slot, slot))
    return pool


@pytest.fixture(scope="module")
def draft(engine):
    return engine.init_draft(DraftConfig(mode="self", layers=1))


def _filled_draft_pool(engine, draft):
    dpool = engine.init_draft_pool(draft, SLOTS, MAX_LEN)
    for slot in range(SLOTS):
        dpool = engine.draft_prefill(draft, dpool, slot,
                                     _prompt(5 + slot, slot))
    return dpool


POSITIONS = np.arange(5, 5 + SLOTS, dtype=np.int32)    # each lane's next


def _step_inputs():
    """(toks, positions, temps) feeding every slot at its next column."""
    return (np.full(SLOTS, 7, np.int32), POSITIONS,
            np.zeros(SLOTS, np.float32))


def _assert_columns_kept(after, before, n_written, what, scale_ulps=0):
    """Every column outside ``[POSITIONS[s], POSITIONS[s] + n_written)`` of
    every slot ``s`` is bit-identical, in every leaf (column axis 2); the
    written ones are not all as they were. ``scale_ulps``: how far an int8
    pool's scales may move in the columns not written."""
    cols = np.arange(MAX_LEN)[None, :]
    written = (cols >= POSITIONS[:, None]) & \
        (cols < POSITIONS[:, None] + n_written)             # [S, C]
    changed = False
    for x, y in zip(jax.tree.leaves(after), jax.tree.leaves(before)):
        mask = written.reshape((1,) + written.shape + (1,) * (x.ndim - 3))
        x0, y0 = np.where(mask, 0, x), np.where(mask, 0, y)
        if scale_ulps and x.dtype == np.float32:
            np.testing.assert_array_max_ulp(x0, y0, maxulp=scale_ulps)
        else:
            np.testing.assert_array_equal(x0, y0, err_msg=what)
        changed |= bool(np.any(x != y))
    assert changed, f"{what}: nothing written"


def _reference_lane(engine, quantized, tokens, start, before_lane):
    """The lane a prefill of ``tokens`` at column ``start`` has to leave,
    computed apart from the pool programs: ``apply_with_cache`` over a
    one-slot cache (fresh, or ``before_lane`` for a suffix), written
    through ``write_lane`` into a fresh one-slot pool."""
    model = engine.module
    ids = np.zeros((1, _next_pow2(int(tokens.size))), np.int32)
    ids[0, :tokens.size] = tokens
    one = engine.init_slot_pool(1, MAX_LEN, quantize=quantized)

    @jax.jit
    def ref(params, ids, one, before_lane):
        mini = model.init_kv_cache(1, MAX_LEN, dtype=engine.dtype) \
            if before_lane is None else \
            read_lane(before_lane, jnp.int32(0), engine.dtype)
        _, mini = model.apply_with_cache(params, ids, mini,
                                         jnp.int32(start))
        return write_lane(one, mini, jnp.int32(0))

    with engine.mesh:
        return _host(ref(engine.params, jnp.asarray(ids), one, before_lane))


def _call(engine, draft, program, pool, slot=1):
    """One call of ``program`` at fixed shapes, writing lane ``slot`` where
    it writes one; the pool to go on with."""
    toks, positions, temps = _step_inputs()
    if program == "slot_prefill":
        return engine.slot_prefill(pool, slot, _prompt(11, 40))[0]
    if program == "slot_suffix_prefill":
        return engine.slot_suffix_prefill(pool, slot, _prompt(6, 41), 6)[0]
    if program == "slot_chunk_prefill":
        return engine.slot_chunk_prefill(pool, slot, _prompt(8, 42), 6)
    if program == "slot_copy_lane":
        return engine.slot_copy_lane(pool, 0, slot)
    if program == "slot_extract_lane":
        engine.slot_extract_lane(pool, slot)
        return pool                         # the one that keeps its pool
    if program == "slot_insert_lane":
        return engine.slot_insert_lane(pool, slot,
                                       engine.slot_extract_lane(pool, 3))
    if program == "slot_decode_step":
        return engine.slot_decode_step(pool, toks, positions, temps)[0]
    if program == "slot_verify_step":
        return engine.slot_verify_step(
            pool, toks, np.full((SLOTS, K), 9, np.int32), positions,
            temps)[0]
    if program == "draft_prefill":
        return engine.draft_prefill(draft, pool, slot, _prompt(11, 40))
    return engine.slot_draft_propose(
        draft, pool, toks, positions, temps, *sampling_arrays(SLOTS)[1:],
        K)[0]


@pytest.mark.parametrize("program,quantized", _cases(
    LANE_PROGRAMS + STEP_PROGRAMS + DRAFT_PROGRAMS))
def test_pool_program_consumes_its_pool(engine, draft, program, quantized):
    pool = _filled_draft_pool(engine, draft) if program in DRAFT_PROGRAMS \
        else _filled_pool(engine, quantized)
    before = _host(pool)
    handed_over = jax.tree.leaves(pool)
    target = 2
    expected = n_written = None
    scale_ulps = 0
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
    elif program == "slot_insert_lane":
        lane = _lane(before, 3)
        new = engine.slot_insert_lane(pool, target, lane)
        expected = lane
    else:
        # the step programs write columns of every lane. An int8 pool goes
        # through a decode step as fp and back whole: its int8 values come
        # back bit for bit, a scale may come back one ulp off
        # (``(127 * s) / 127`` in float32), which requantizes to the same
        # int8 values again. Verify restores what it rejects verbatim.
        new = _call(engine, draft, program, pool, target)
        n_written = N_WRITTEN.get(program)
        scale_ulps = int(quantized and program == "slot_decode_step")
    # the alias took: every leaf handed over is gone, fp and q8 alike
    assert handed_over and all(leaf.is_deleted() for leaf in handed_over)
    if program in ("slot_prefill", "slot_suffix_prefill"):
        assert 0 <= tok < VOCAB
    after = _host(new)
    if n_written is not None:
        _assert_columns_kept(after, before, n_written, program, scale_ulps)
        return
    for slot in range(SLOTS):
        if slot != target:
            _assert_same(_lane(after, slot), _lane(before, slot),
                         f"{program}: slot {slot} changed")
    if expected is not None:
        _assert_same(_lane(after, target), expected,
                     f"{program}: written lane")
    else:
        assert any(np.any(x != y) for x, y in zip(
            jax.tree.leaves(_lane(after, target)),
            jax.tree.leaves(_lane(before, target)))), "nothing written"


class _Ledger:
    """Stands in for the compile plane: keeps what the engine hands it,
    and the module name of the program as it is about to be called."""

    def __init__(self):
        self.seen = []      # (label, fn, names, module name)
        self._modules = {}

    def observe(self, label, fn, args, names=None, mesh=None):
        if fn not in self._modules:
            with mesh:
                text = fn.lower(*args).as_text()
            self._modules[fn] = re.search(r"module @(\w+)", text).group(1)
        self.seen.append((label, fn, tuple(names), self._modules[fn]))


_DECODE_ARGS = ("toks", "positions", "temps", "top_ks", "top_ps", "seeds")
_SAMPLE_ARGS = ("temperature", "top_k", "top_p", "seed")
# program -> (ledger label, body, the ledger's argument names)
BUILT = {
    "slot_prefill": ("slot_prefill", "pf",
                     ("params", "ids", "pool", "slot", "last_idx")
                     + _SAMPLE_ARGS),
    "slot_suffix_prefill": ("slot_suffix_prefill", "spf",
                            ("params", "ids", "pool", "slot", "start_pos",
                             "last_idx") + _SAMPLE_ARGS),
    "slot_chunk_prefill": ("slot_chunk_prefill", "cpf",
                           ("params", "ids", "pool", "slot", "start_pos")),
    "slot_copy_lane": ("slot_copy", "cp", ("pool", "src", "dst")),
    "slot_extract_lane": ("slot_extract", "ex", ("pool", "slot")),
    "slot_insert_lane": ("slot_insert", "ins", ("pool", "lane", "slot")),
    "slot_decode_step": ("slot_decode", "dec",
                         ("params", "pool") + _DECODE_ARGS
                         + ("prev", "from_host")),
    "slot_verify_step": ("slot_verify", "ver",
                         ("params", "pool", "toks", "draft_toks")
                         + _DECODE_ARGS[1:]),
    "draft_prefill": ("draft_prefill", "dpf",
                      ("draft_params", "ids", "draft_pool", "slot")),
    "slot_draft_propose": ("slot_draft", "prop",
                           ("draft_params", "draft_pool") + _DECODE_ARGS),
}


@pytest.mark.parametrize("program,quantized", _cases(BUILT))
def test_every_pool_program_is_built_one_way(engine, draft, program,
                                             quantized):
    """What ``InferenceEngine._pool_program`` decides, seen from outside,
    for every program it builds: a second call at the same shapes adds no
    executable, the key in ``_slot_fns`` ends in ``"q8"`` for an int8 pool
    and only then, the compile ledger is given the label and the body's
    own parameter names in order, and the module is ``jit_<body>`` (what a
    device trace calls it)."""
    label, body, names = BUILT[program]
    pool = _filled_draft_pool(engine, draft) if program in DRAFT_PROGRAMS \
        else _filled_pool(engine, quantized)
    ledger = engine.compile_plane = _Ledger()
    try:
        pool = _call(engine, draft, program, pool)
        _call(engine, draft, program, pool)
    finally:
        engine.compile_plane = None
    seen = [s for s in ledger.seen if s[0] == label]
    assert len(seen) == 2 and seen[0] == seen[1]
    _, fn, given, module = seen[0]
    assert given == names == tuple(
        inspect.signature(fn.__wrapped__).parameters)
    assert module == "jit_" + body == "jit_" + fn.__wrapped__.__name__
    key, = [k for k, v in engine._slot_fns.items() if v is fn]
    assert (key[-1] == "q8") == quantized
    dims = key[1:-1] if quantized else key[1:]
    assert engine.slot_executables(key[0], *dims, quantized=quantized) == 1
    assert engine.slot_executables(key[0], *dims) >= 1


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
    assert engine.slot_executables("slot_chunk", 3, 16, 256,
                                   quantized=False) == 1
    srv.shutdown()
