"""Phase records (telemetry/trace.py ``Tracer.phase`` / ``record_phase``):
the always-on ring beside the spans', what a serving tick and a train step
write into it, and the names of the jitted serving programs that the
benchmark's cell files read."""

import gc
import glob
import re
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import SamplingParams, ServingEngine
from deepspeed_tpu.telemetry import Tracer, get_tracer

VOCAB = 128


# ------------------------------------------------------------------ the ring

def test_phases_record_with_the_tracer_disabled():
    tr = Tracer(enabled=False)
    with tr.phase("serve/tick", 7) as outer:
        with tr.phase("serve/admit", 1, 2):
            pass
        outer.b = 3                       # payload may be set until exit
    assert tr.spans() == []               # no Span was made
    inner, tick = tr.phases()             # oldest first, by end stamp
    assert inner[0] == "serve/admit" and inner[3:] == (1, 2)
    assert tick[0] == "serve/tick" and tick[3:] == (7, 3)
    assert tick[1] <= inner[1] <= inner[2] <= tick[2]      # nested by stamps
    assert all(type(x) is int for x in tick[1:])
    assert tr.phases_total == 2 and tr.phases_dropped == 0


def test_phase_is_recorded_when_its_block_raises():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.phase("serve/admit"):
            raise ValueError("boom")
    assert [r[0] for r in tr.phases()] == ["serve/admit"]


def test_ring_keeps_its_size_and_counts_drops():
    tr = Tracer(phase_buffer_size=16)
    ring = tr._phase_ring
    for i in range(40):
        tr.record_phase("x", i, i + 1, i)
    assert tr._phase_ring is ring and len(ring) == 16      # never grows
    assert tr.phases_total == 40 and tr.phases_dropped == 24
    assert [r[3] for r in tr.phases()] == list(range(24, 40))
    tr.clear()
    assert tr.phases() == [] and tr.phases_dropped == 0


def test_plain_call_records_an_interval_that_outlived_its_frame():
    tr = Tracer()
    t0 = time.perf_counter_ns()
    with tr.phase("serve/tick"):
        pass
    tr.record_phase("serve/queue_wait", t0, time.perf_counter_ns(), 5, 11)
    tick, wait = tr.phases()
    assert wait == ("serve/queue_wait", t0, wait[2], 5, 11)
    assert wait[1] <= tick[1] and tick[2] <= wait[2]       # it spans the tick


def test_gc_is_watched_while_an_owner_is_open():
    tr = Tracer()
    a, b = object(), object()
    tr.watch_gc(a)
    tr.watch_gc(b)
    assert gc.callbacks.count(tr._on_gc) == 1
    try:
        gc.collect()                      # generation 2: always recorded
        rec = [r for r in tr.phases() if r[0] == "gc"]
        assert rec and rec[-1][3] == 2 and rec[-1][2] >= rec[-1][1]
        tr.unwatch_gc(a)
        assert tr._on_gc in gc.callbacks  # b is still open
    finally:
        tr.unwatch_gc(a)
        tr.unwatch_gc(b)
    assert tr._on_gc not in gc.callbacks
    n = tr.phases_total
    gc.collect()
    assert tr.phases_total == n


def test_phases_are_annotations_while_a_profile_is_taken(tmp_path):
    """In a ``jax.profiler`` trace each phase is a ``dstpu/<name>`` event on
    the host's track; with no trace on, none is made."""
    tr = Tracer()
    assert tr._profiler_annotation() is None
    with tr.phase("serve/before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tr._profiler_annotation() is jax.profiler.TraceAnnotation
        with tr.phase("serve/tick"):
            with tr.phase("serve/admit"):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("dstpu/")}
    assert names == {"dstpu/serve/tick", "dstpu/serve/admit"}
    assert [r[0] for r in tr.phases()] == \
        ["serve/before", "serve/admit", "serve/tick"]


# ------------------------------------------------------------ a serving tick

@pytest.fixture(scope="module")
def engine():
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=1,
                                 dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,), dtype=np.int32)


def _since(mark_ns):
    """The process-wide ring's records that began after a stamp (an index
    would be wrong once an earlier test file in this worker filled it)."""
    return [r for r in get_tracer().phases() if r[1] >= mark_ns]


def test_tick_with_one_prefill_holds_every_phase(engine, monkeypatch):
    tr = get_tracer()
    # the tracer is the process's: a test file that ran before this one in
    # the same worker may have left it enabled (tests/unit/test_costplane.py
    # does), and this test is about the records of a tracer that is off
    monkeypatch.setattr(tr, "enabled", False)
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    span_args = []
    real_span = tr.span
    monkeypatch.setattr(tr, "span", lambda name, **kw: (
        span_args.append((name, kw.get("args"))), real_span(name, **kw))[1])
    mark = time.perf_counter_ns()
    rid = srv.submit(_prompt(11), SamplingParams(max_new_tokens=3))
    srv.step()
    recs = [r for r in _since(mark) if r[0] != "gc"]
    tick = recs[-1]
    assert tick[0] == "serve/tick" and tick[3:] == (1, 1)  # tick 1, 1 active
    want = {"serve/admit", "serve/queue_wait", "serve/prefill_prep",
            "serve/prefill_dispatch", "serve/prefill_wait",
            "serve/first_token", "serve/decode_prep",
            "serve/decode_dispatch", "serve/decode_wait", "serve/kv_read",
            "serve/deliver", "serve/bookkeeping", "serve/tick"}
    assert {r[0] for r in recs} == want
    by = {}
    for r in recs:
        by.setdefault(r[0], []).append(r)
    # the tick found nothing in flight, so it sends TWO decode steps (the
    # scheduler's own prep, then the engine's prep and dispatch, each) and
    # waits on the first alone: 6 records of the admission, 6 of the two
    # dispatches, wait, kv_read, deliver, two bookkeepings, the tick
    assert len(recs) == 18
    assert [len(by[n]) for n in ("serve/decode_prep", "serve/decode_dispatch",
                                 "serve/decode_wait", "serve/kv_read",
                                 "serve/deliver")] == [4, 2, 1, 1, 1]
    prep, = by["serve/prefill_prep"]
    assert prep[3:] == (11, 16)                 # prompt tokens, pow2 bucket
    assert by["serve/prefill_dispatch"][0][3] == 16
    assert by["serve/first_token"][0][3] == rid
    assert by["serve/admit"][0][3:] == (1, 1)   # admitted, queue depth
    assert by["serve/queue_wait"][0][3:] == (rid, 11)
    # slots each step advances: the request goes on after the first
    assert [r[3] for r in by["serve/decode_prep"]] == [1, 0, 1, 0]
    assert by["serve/deliver"][0][3:] == (1, 0)
    # it dispatches before it waits: the second step is sent, fed by the
    # first on the device, before the first is read; then the delivery
    wait, = by["serve/decode_wait"]
    first, second = by["serve/decode_dispatch"]
    assert first[2] <= second[1] and second[2] <= wait[1]
    assert wait[2] <= by["serve/kv_read"][0][1] <= by["serve/deliver"][0][1]
    assert srv.scheduler._flight is not None    # the second, un-read
    assert len(srv.result(rid).tokens) == 2
    # every host phase nests in the tick, the prefill's in serve/admit
    host = [r for r in recs[:-1] if r[0] != "serve/queue_wait"]
    assert all(tick[1] <= r[1] <= r[2] <= tick[2] for r in host)
    admit, = by["serve/admit"]
    for name in ("serve/prefill_prep", "serve/prefill_dispatch",
                 "serve/prefill_wait", "serve/first_token"):
        assert admit[1] <= by[name][0][1] and by[name][0][2] <= admit[2]
    # innermost-phase self times add up to the tick
    from chipbench.trace import self_events
    pieces = self_events([(r[1], r[2], r[0]) for r in host + [tick]])
    assert sum(e - s for s, e, _ in pieces) == tick[2] - tick[1]
    # the old spans' args are not built while the tracer is off
    assert {"prefill", "decode_step"} <= {n for n, _ in span_args}
    assert all(a is None for n, a in span_args
               if n in ("prefill", "decode_step"))
    srv.shutdown()


def test_routed_model_tick_records_its_routing_and_a_dense_one_none(engine):
    """A model with routed experts (OLMoE) leaves one ``serve/moe_prefill``
    record a prefill and one ``serve/moe_decode`` a decode tick, payload
    (experts touched, largest count any expert got) summed over layers —
    what ``chipbench/layer_metrics/serve_moe.py`` reads; a dense model's
    tick (the fixture's) leaves neither."""
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    model = OLMoEModel(OLMoEConfig(
        vocab_size=VOCAB, n_positions=64, n_embd=64, n_layer=2, n_head=2,
        mlp_hidden=32, num_experts=8, top_k=2, dtype="float32"))
    moe = deepspeed_tpu.init_inference(model, config={"dtype": "float32"})
    names = {}
    for label, eng in (("routed", moe), ("dense", engine)):
        srv = ServingEngine(eng, {"num_slots": 2, "max_model_len": 64})
        mark = time.perf_counter_ns()
        srv.submit(_prompt(11), SamplingParams(max_new_tokens=3))
        srv.step()
        names[label] = _since(mark)
        srv.shutdown()
    assert not [r for r in names["dense"] if r[0].startswith("serve/moe_")]
    by = {}
    for r in names["routed"]:
        by.setdefault(r[0], []).append(r)
    prefill, = by["serve/moe_prefill"]
    decode, = by["serve/moe_decode"]
    # layers x (top_k .. experts) slots; the 16-token bucket's 32 picks
    assert 2 * 2 <= prefill[3] <= 2 * 8 and 2 * 4 <= prefill[4] <= 2 * 16
    # the tick routes both pool rows, the empty slot's dummy row too
    assert 2 * 2 <= decode[3] <= 2 * 4 and 2 * 1 <= decode[4] <= 2 * 2
    assert prefill[1] == prefill[2] and decode[1] == decode[2]   # instants
    tick, = by["serve/tick"]
    assert tick[1] <= prefill[1] <= decode[1] <= tick[2]
    wait, = by["serve/prefill_wait"]
    assert wait[2] <= prefill[1]        # taken after the token was read
    # a decode step's stats are read with ITS tokens: after the wait, which
    # comes after the next step's dispatch (the tick sends two, reads one)
    wait, = by["serve/decode_wait"]
    assert len(by["serve/decode_dispatch"]) == 2
    assert by["serve/decode_dispatch"][1][2] <= wait[1] <= wait[2] <= decode[1]


def test_queue_wait_spans_the_ticks_a_request_waited(engine):
    tr = get_tracer()
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 64})
    srv.submit(_prompt(5), SamplingParams(max_new_tokens=4))
    srv.step()                                  # the only slot is taken
    mark = time.perf_counter_ns()
    rid = srv.submit(_prompt(7, seed=1), SamplingParams(max_new_tokens=2))
    srv.step()
    srv.step()                                  # first request retires here
    assert not [r for r in _since(mark) if r[0] == "serve/queue_wait"]
    srv.step()                                  # admitted two ticks later
    recs = _since(mark)
    wait, = [r for r in recs if r[0] == "serve/queue_wait"]
    assert wait[3:] == (rid, 7)
    ticks = [r for r in recs if r[0] == "serve/tick"]
    assert len(ticks) == 3
    assert wait[1] <= ticks[0][1] and ticks[1][2] <= wait[2] <= ticks[2][2]
    assert [t[3] for t in ticks] == [2, 3, 4]   # tick numbers
    srv.shutdown()


def test_shutdown_stops_watching_gc(engine):
    tr = get_tracer()
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 64})
    assert tr._on_gc in gc.callbacks
    srv.shutdown()
    assert id(srv) not in tr._gc_owners


# --------------------------------------------------------------- a train step

def test_train_step_holds_its_four_phases():
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=32, n_embd=32,
                                 n_layer=1, n_head=2, pad_vocab_to_multiple=1))
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}, "steps_per_print": 0})
    tr = get_tracer()
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, VOCAB, (1, 8, 32))}
    eng.train_batch(batch=batch)
    mark = time.perf_counter_ns()
    eng.train_batch(batch=batch)
    recs = [r for r in _since(mark) if r[0] != "gc"]
    eng.close()
    assert id(eng) not in tr._gc_owners
    step = recs[-1]
    assert step[0] == "train/step" and step[3] == 1        # global step
    names = [r[0] for r in recs[:-1]]
    assert names == ["train/input", "train/dispatch", "train/post",
                     "train/readback", "train/post"]
    assert all(step[1] <= r[1] <= r[2] <= step[2] for r in recs[:-1])
    assert all(a[2] <= b[1] for a, b in zip(recs, recs[1:-1]))  # in order


# ------------------------------------------- names the benchmark's files read

def test_serving_program_names_are_what_the_cell_files_read(engine):
    """``chipbench/workloads/opt-1.3b.serve-chat.json`` finds the prefill and
    decode programs in a device trace by module name (``"modules"``:
    ``^jit_pf$``, ``^jit_dec$``); the names come from the inner functions of
    ``slot_prefill`` / ``slot_decode_step``. Renaming either makes
    ``prefill_share`` / ``decode_hbm_share`` read nothing."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "workloads",
                           "opt-1.3b.serve-chat.json")) as f:
        patterns = json.load(f)["modules"]
    pool = engine.init_slot_pool(2, 64)
    pool, _ = engine.slot_prefill(pool, 0, _prompt(5))
    pool, _ = engine.slot_decode_step(pool, np.zeros(2, np.int32),
                                      np.array([5, 0], np.int32),
                                      np.zeros(2, np.float32))
    pf = engine._slot_fns[("slot_prefill", 8, 64)]
    dec = engine._slot_fns[("slot_decode", 2, 64)]
    i32, f32 = jnp.int32(0), jnp.float32(0)
    vec = lambda dt: jnp.zeros(2, dt)
    with engine.mesh:
        pf_text = pf.lower(engine.params, jnp.zeros((1, 8), jnp.int32), pool,
                           i32, i32, f32, i32, f32, i32).as_text()
        dec_text = dec.lower(engine.params, pool, vec(jnp.int32),
                             vec(jnp.int32), vec(jnp.float32),
                             vec(jnp.int32), vec(jnp.float32),
                             vec(jnp.int32), vec(jnp.int32),
                             vec(jnp.bool_)).as_text()
    module = lambda text: re.search(r"module @(\w+)", text).group(1)
    assert module(pf_text) == "jit_pf"
    assert module(dec_text) == "jit_dec"
    assert re.search(patterns["prefill"], module(pf_text))
    assert re.search(patterns["decode"], module(dec_text))


def test_kv_read_says_what_the_decode_attention_read(engine):
    """``serve/kv_read``, an instant a decode tick: the columns of one layer
    the step's attention read, of the pool's. A tick on the CPU takes the
    XLA attend over the whole pool: all of them. Where the engine says its
    decode program takes the decode-attention kernel (here: told so, with
    blocks of 16 columns): every slot's live length in whole blocks and a
    free slot's one block."""
    assert engine.decode_kernel_block(2, 64) is None
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    mark = time.perf_counter_ns()
    srv.submit(_prompt(17), SamplingParams(max_new_tokens=3))
    srv.step()
    read, = [r for r in _since(mark) if r[0] == "serve/kv_read"]
    assert read[3:] == (2 * 64, 2 * 64) and read[1] == read[2]
    srv.scheduler._kv_read_block = 16
    mark = time.perf_counter_ns()
    srv.step()
    read, = [r for r in _since(mark) if r[0] == "serve/kv_read"]
    # the request stands at position 18 (17 prompt tokens and one decoded):
    # 19 live columns are two blocks; the free slot's position 0 is one
    assert read[3:] == (32 + 16, 2 * 64)
    srv.shutdown()


def test_the_phase_ring_is_resized_on_request_and_starts_empty():
    """``configure(phase_buffer_size=)``: what a process whose readers look
    back over more records than the default ring holds asks for."""
    tr = Tracer(phase_buffer_size=16)
    for i in range(40):
        tr.record_phase("serve/tick", i, i + 1)
    assert len(tr.phases()) == 16 and tr.phases_dropped == 24
    tr.configure(phase_buffer_size=64)
    assert tr.phases() == [] and tr.phases_total == 0
    for i in range(40):
        tr.record_phase("serve/tick", i, i + 1)
    assert len(tr.phases()) == 40 and tr.phases_dropped == 0
    tr.configure(phase_buffer_size=64)          # as it is: nothing cleared
    assert len(tr.phases()) == 40
