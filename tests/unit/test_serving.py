"""Continuous-batching serving tests (deepspeed_tpu/serving/).

The contract under test: admission order and slot multiplexing must be
invisible in the tokens — a greedily-served request is bitwise-identical to
a standalone generate() call — while the fused decode step compiles exactly
once per pool shape regardless of prompt-length mix.
"""

import csv
import os

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (QueueFull, RequestState, SamplingParams,
                                   ServingConfig, ServingEngine)

VOCAB = 128


@pytest.fixture(scope="module")
def engine():
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=1,
                                 dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (t,), dtype=np.int32) for t in lengths]


def test_greedy_token_parity_with_generate(engine):
    """Requests admitted at staggered ticks, with differing prompt lengths,
    produce bitwise the tokens a standalone generate() produces — and the
    decode hot path holds exactly ONE compiled executable afterwards."""
    srv = ServingEngine(engine, {"num_slots": 4, "max_model_len": 64})
    prompts = _prompts((5, 9, 3, 12, 7))
    rids = [srv.submit(p, SamplingParams(max_new_tokens=6))
            for p in prompts[:3]]
    srv.step()                       # stagger: admit/advance before the rest
    srv.step()
    rids += [srv.submit(p, SamplingParams(max_new_tokens=6))
             for p in prompts[3:]]
    srv.run_until_idle()
    for rid, p in zip(rids, prompts):
        req = srv.result(rid)
        assert req.state is RequestState.FINISHED
        ref = np.asarray(engine.generate(p[None], max_new_tokens=6))[0]
        np.testing.assert_array_equal(req.output_ids, ref)
    # compile-once: prompt buckets differed (4, 8, 16) yet the fused decode
    # step traced/compiled a single executable
    assert srv.decode_executables() == 1


def test_eos_retires_and_slot_is_reused(engine):
    """EOS retirement frees the slot; more requests than slots all finish
    through slot reuse; post-EOS tokens match generate()'s eos-fill."""
    prompts = _prompts((6, 6, 6, 6, 6), seed=1)
    # pick the first greedily-generated token of prompt 0 as the EOS id so
    # that request terminates at its very first token
    ref0 = np.asarray(engine.generate(prompts[0][None], max_new_tokens=1))[0]
    eos = int(ref0[-1])
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    sp = SamplingParams(max_new_tokens=5, eos_token_id=eos)
    rids = [srv.submit(p, sp) for p in prompts]
    srv.run_until_idle()
    pool = srv.scheduler.pool
    assert pool.free_count == 2                    # every slot returned
    assert pool.total_allocs == 5                  # 5 requests over 2 slots
    r0 = srv.result(rids[0])
    assert r0.state is RequestState.FINISHED
    assert r0.tokens == [eos]                      # retired at first token
    for rid, p in zip(rids, prompts):
        req = srv.result(rid)
        assert req.state is RequestState.FINISHED
        assert len(req.tokens) <= 5
        ref = np.asarray(engine.generate(p[None], max_new_tokens=5,
                                         eos_token_id=eos))[0]
        gen = ref[len(p):]
        # generate() fills positions after EOS with EOS; serving stops at it
        np.testing.assert_array_equal(np.asarray(req.tokens),
                                      gen[:len(req.tokens)])
        if len(req.tokens) < 5:
            assert req.tokens[-1] == eos
            assert (gen[len(req.tokens):] == eos).all()


# ------------------------------------------------- the one-step decode pipeline

def _generated(engine, prompt, n, eos=None):
    """``engine.generate``'s tokens after the prompt: the parent's order of
    work, one request at a time, nothing in flight."""
    out = np.asarray(engine.generate(prompt[None], max_new_tokens=n,
                                     eos_token_id=eos))[0]
    return out[len(prompt):].tolist()


def _ends_by_eos(engine, length, n, least=2):
    """(prompt, its ``n`` greedy tokens, k): a prompt of ``length`` whose
    token ``k`` (``least`` <= k < n - 1) equals none before it, so that as
    the EOS id it ends the request exactly there, short of
    ``max_new_tokens``. The tiny model repeats itself: most prompts have no
    such token, so seeds are tried in order."""
    for seed in range(64):
        prompt = _prompts((length,), seed=seed)[0]
        ref = _generated(engine, prompt, n)
        for k in range(least, n - 1):
            if ref[k] not in ref[:k]:
                return prompt, ref, k
    raise AssertionError("no prompt of that length ends by a fresh token")


def _pipeline_counts(srv):
    m = srv.metrics
    return m.decode_ticks, m.pipelined_ticks, m.dropped_rows


def test_pipelined_streams_are_generates_whatever_ends_a_request(engine):
    """A decode step is dispatched before the step before it is read, so
    an ending the host learns from a token (EOS) or between ticks (a
    deadline) comes one step late, and an admission joins a pipeline that
    is running. None of it shows in the tokens: two slots serve an EOS
    ending, a ``max_new_tokens`` ending, a deadline, a cancelled request,
    admissions in mid-flight and a slot bound again in the very tick its
    request timed out (the step in flight still holds the old request's
    row), each stream bitwise ``engine.generate``'s. The two gauges count
    what happened: every step but the first was sent behind another, and
    exactly the EOS ending's and the deadline's rows were dropped."""
    from deepspeed_tpu.telemetry import get_tracer
    now = [0.0]
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64},
                        clock=lambda: now[0])
    pb, pc, pd, pe, pf = _prompts((9, 3, 12, 4, 7), seed=11)
    pa, ref_a, k = _ends_by_eos(engine, 5, 6)
    eos = ref_a[k]
    ra = srv.submit(pa, SamplingParams(max_new_tokens=6, eos_token_id=eos))
    rb = srv.submit(pb, SamplingParams(max_new_tokens=4))
    rc = srv.submit(pc, SamplingParams(max_new_tokens=8, timeout_s=5))
    rd = srv.submit(pd, SamplingParams(max_new_tokens=5))
    re_ = srv.submit(pe, SamplingParams(max_new_tokens=5))
    pool = srv.scheduler.pool
    # one admission a tick: B and C join while A's steps are in flight
    while srv.result(rc).state is RequestState.QUEUED:
        srv.step()
    assert srv.result(ra).state is RequestState.FINISHED    # by EOS
    assert srv.cancel(re_) and not srv.cancel(rc)           # queued / running
    while srv.result(rb).state is not RequestState.FINISHED:
        srv.step()
    slot_c = pool.requests.index(srv.result(rc))
    assert srv.scheduler._flight is not None
    now[0] = 10.0                       # past C's deadline, a step in flight
    srv.step()
    assert srv.result(rc).state is RequestState.TIMEOUT
    assert pool.requests[slot_c] is srv.result(rd)          # the very slot
    rf = srv.submit(pf, SamplingParams(max_new_tokens=3))   # mid-flight
    srv.run_until_idle()
    assert srv.scheduler._flight is None and pool.free_count == 2
    assert srv.result(ra).tokens == ref_a[:k + 1]
    assert srv.result(rb).tokens == _generated(engine, pb, 4)
    got_c = srv.result(rc).tokens
    assert 1 <= len(got_c) < 8 and got_c == _generated(engine, pc, 8)[:len(got_c)]
    assert srv.result(rd).tokens == _generated(engine, pd, 5)
    assert srv.result(re_).state is RequestState.CANCELLED
    assert srv.result(re_).tokens == []
    assert srv.result(rf).tokens == _generated(engine, pf, 3)
    assert srv.decode_executables() == 1
    ticks, pipelined, dropped = _pipeline_counts(srv)
    assert pipelined == ticks - 1       # the pool was idle once: at the start
    assert dropped == 2                 # A's row after its EOS, C's at its deadline
    tr = get_tracer()
    assert tr.counter_value("serve/pipelined_ticks") == pipelined
    assert tr.counter_value("serve/dropped_rows") == dropped
    srv.shutdown()
    assert tr.counter_value("serve/pipelined_ticks") is None


def serve_past_a_deadline(engine, prompts):
    """Two slots serve four ``prompts``, one admission a tick: the second
    request times out with a step in flight, the third is bound to its slot
    in that very tick (the step in flight still holds the old request's
    row, and where the model keeps a recurrent state that row pushes into
    the slot's), the fourth joins in mid-flight. Every stream is
    ``engine.generate``'s; returns the metrics of the engine, shut down.
    For the model families' own files (``test_olmoe.py``, ``test_lfm2.py``)."""
    now = [0.0]
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64},
                        clock=lambda: now[0])
    first, late, third, fourth = prompts
    rids = [srv.submit(first, SamplingParams(max_new_tokens=9)),
            srv.submit(late, SamplingParams(max_new_tokens=9, timeout_s=5)),
            srv.submit(third, SamplingParams(max_new_tokens=4))]
    pool = srv.scheduler.pool
    for _ in range(3):
        srv.step()
    slot = pool.requests.index(srv.result(rids[1]))
    now[0] = 10.0
    srv.step()
    assert srv.result(rids[1]).state is RequestState.TIMEOUT
    assert pool.requests[slot] is srv.result(rids[2])
    rids.append(srv.submit(fourth, SamplingParams(max_new_tokens=5)))
    srv.run_until_idle()
    for rid, prompt, n in zip(rids, prompts, (9, 9, 4, 5)):
        got = srv.result(rid).tokens
        assert len(got) == n or rid == rids[1]
        assert got and got == _generated(engine, prompt, n)[:len(got)]
    assert srv.decode_executables() == 1 and srv.scheduler._flight is None
    srv.shutdown()
    return srv.metrics


def test_sampled_rows_ride_the_pipeline_and_nothing_is_dropped_without_eos(
        engine):
    """A sampled request's token is fed to the next step on the device like
    a greedy one's: its stream is what ``slot_prefill`` and then one
    ``slot_decode_step`` at a time, each read before the next is sent, give
    for the same ``(seed, position)`` keys. Where every request ends by
    ``max_new_tokens`` the scheduler knows each ending a step early: no row
    is computed for a request that has ended, and between two idle pools
    every step but the first is pipelined."""
    sp = SamplingParams(max_new_tokens=7, temperature=0.8, top_k=20, seed=5)
    hot, cold = _prompts((6, 10), seed=12)
    pool = engine.init_slot_pool(2, 64)
    pool, tok = engine.slot_prefill(pool, 0, hot, temperature=0.8, top_k=20,
                                    seed=5)
    want, pos = [tok], len(hot)
    while len(want) < 7:
        pool, nxt = engine.slot_decode_step(
            pool, np.array([want[-1], 0], np.int32),
            np.array([pos, 0], np.int32), np.array([0.8, 0], np.float32),
            top_ks=np.array([20, 0], np.int32), top_ps=np.ones(2, np.float32),
            seeds=np.array([5, 0], np.int32))
        want.append(int(nxt[0]))
        pos += 1
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    for _ in range(2):                  # two busy periods, idle between
        rh = srv.submit(hot, sp)
        rc = srv.submit(cold, SamplingParams(max_new_tokens=4))
        srv.run_until_idle()
        assert srv.result(rh).tokens == want
        assert srv.result(rc).tokens == _generated(engine, cold, 4)
    ticks, pipelined, dropped = _pipeline_counts(srv)
    assert dropped == 0 and pipelined == ticks - 2 and ticks >= 12
    assert srv.decode_executables() == 1
    srv.shutdown()


@pytest.mark.parametrize("how", ["run_until_idle", "drain", "shutdown"])
@pytest.mark.parametrize("ending", ["max_new_tokens", "eos"])
def test_a_step_is_in_flight_after_step_and_none_once_idle(engine, how,
                                                           ending):
    """``step()`` returns with the next decode step on the device, un-read;
    ``run_until_idle``, ``drain`` and ``shutdown`` return with none: an
    ending known ahead leaves nothing dispatched behind the last step, one
    learnt from the token leaves a step of dropped rows, which is let go."""
    prompt, ref, k = _ends_by_eos(engine, 6, 8)
    if ending == "max_new_tokens":
        k = 7
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    rid = srv.submit(prompt, SamplingParams(
        max_new_tokens=8, eos_token_id=ref[k] if ending == "eos" else None))
    srv.step()
    flight = srv.scheduler._flight
    assert flight is not None and [s for s, _ in flight.rows] == [0]
    assert len(srv.result(rid).tokens) == 2     # the prefill's and a step's
    getattr(srv, how)()
    assert srv.scheduler._flight is None
    assert srv.result(rid).tokens == ref[:k + 1]
    assert srv.metrics.dropped_rows == (ending == "eos")
    assert srv.scheduler.pool.free_count == 2
    if how != "shutdown":
        srv.shutdown()


@pytest.mark.parametrize("ending", ["max_new_tokens", "eos"])
def test_rows_past_an_ended_request_land_inside_its_lane(engine, ending,
                                                         monkeypatch):
    """A request that fills its lane to the last column but one (prompt +
    ``max_new_tokens`` = ``max_model_len``) ends while its neighbour goes
    on. Every step after its last carries a row for its parked slot — the
    dropped row of the step in flight at an EOS, then dummy rows — at the
    column one past what the lane holds, never past ``max_model_len - 1``:
    the columns it donated to the prefix cache stay bitwise what they were,
    the donation holds the DELIVERED length (the step in flight counted
    one more), and a later prompt that hits it streams what a cold prefill
    streams."""
    max_len = 32
    other = _prompts((4,), seed=14)[0]
    filler, ref, k = _ends_by_eos(engine, 24, 8, least=4)
    if ending == "max_new_tokens":
        k = 7
    sp = SamplingParams(max_new_tokens=8,
                        eos_token_id=ref[k] if ending == "eos" else None)
    srv = ServingEngine(engine, {       # a third slot: no lane is evicted
        "num_slots": 3, "max_model_len": max_len,
        "prefix_cache": {"enabled": True, "min_prefix_len": 8}})
    sched, inner = srv.scheduler, engine.slot_decode_dispatch
    fed_at = []
    monkeypatch.setattr(
        engine, "slot_decode_dispatch", lambda pool, toks, positions, *a,
        **kw: (fed_at.append(np.array(positions)),
               inner(pool, toks, positions, *a, **kw))[1])
    rf = srv.submit(filler, sp)
    ro = srv.submit(other, SamplingParams(max_new_tokens=24))
    while srv.result(rf).state is not RequestState.FINISHED:
        srv.step()
    assert srv.result(rf).tokens == ref[:k + 1]
    held = len(filler) + k              # every token but the last is in the lane
    entry, = sched.prefix_cache.entries.values()
    assert entry.kv_len == held == sched.pool.lengths[entry.slot]
    assert (held == max_len - 1) == (ending == "max_new_tokens")
    lane = {name: np.array(leaf[:, entry.slot, :held], copy=True)
            for name, leaf in sched.pool.cache.items()}
    before = len(fed_at)
    srv.run_until_idle()                # the neighbour's further steps
    assert len(fed_at) - before >= 8
    rows = np.stack(fed_at)[:, entry.slot]
    assert rows.max() == held <= max_len - 1
    assert (rows[before:] == held).all()    # one past what the lane holds
    for name, leaf in sched.pool.cache.items():
        np.testing.assert_array_equal(
            np.asarray(leaf[:, entry.slot, :held]), lane[name])
    assert srv.result(ro).tokens == _generated(engine, other, 24)
    # a later prompt over the donated lane: its first `held` tokens
    again = np.concatenate([srv.result(rf).output_ids[:held - 6],
                            _prompts((3,), seed=15)[0]]).astype(np.int32)
    rid = srv.submit(again, SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    assert sched.prefix_cache.hits == 1
    assert srv.result(rid).tokens == _generated(engine, again, 4)
    srv.shutdown()


def test_dispatch_arrays_clamp_a_row_into_the_lane(engine):
    """``SlotPool.dispatch_arrays``: a slot that takes no part in the step
    carries a greedy dummy row at the column one past what it holds, and
    that column is clamped to the lane's last whatever the registers say."""
    from deepspeed_tpu.serving.kv_slots import SlotPool
    pool = SlotPool(engine, 3, 16)
    pool.bind(0, object(), 9, 41, SamplingParams(temperature=0.5, seed=3))
    pool.bind(1, object(), 12, 42, SamplingParams(temperature=0.9, top_k=4))
    pool.set_length(2, 16)              # past the end: never in a valid run
    toks, pos, temps, top_ks, top_ps, seeds, from_host = \
        pool.dispatch_arrays([0], fed=())
    assert toks.tolist() == [41, 0, 0] and pos.tolist() == [9, 12, 15]
    assert temps.tolist() == [0.5, 0, 0] and top_ks.tolist() == [0, 0, 0]
    assert top_ps.tolist() == [1, 1, 1] and seeds.tolist() == [3, 0, 0]
    assert from_host.all()              # nothing in flight to feed from
    assert pool.dispatched.tolist() == [10, 12, 16]
    assert pool.lengths.tolist() == [9, 12, 16]
    # slot 0's token is in the step in flight; slot 1 was bound since
    _, pos, *_, from_host = pool.dispatch_arrays([0, 1], fed={0, 2})
    assert pos.tolist() == [10, 12, 15]
    assert from_host.tolist() == [False, True, True]
    for a in (toks, pos, top_ks, seeds):
        assert a.dtype == np.int32
    assert temps.dtype == top_ps.dtype == np.float32


def test_backpressure_queue_full(engine):
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 64,
                                 "max_queue": 2,
                                 "default_max_new_tokens": 4})
    prompts = _prompts((4, 4, 4), seed=2)
    srv.submit(prompts[0])
    srv.submit(prompts[1])
    with pytest.raises(QueueFull):
        srv.submit(prompts[2])
    assert srv.metrics.rejected == 1
    # backpressure is transient: a step drains a queue entry into the slot
    srv.step()
    rid = srv.submit(prompts[2], SamplingParams(max_new_tokens=2))
    srv.run_until_idle()
    assert srv.result(rid).state is RequestState.FINISHED


def test_deadline_timeout_fires(engine):
    now = [0.0]
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 64},
                        clock=lambda: now[0])
    long_req, short_req = _prompts((4, 4), seed=3)
    ra = srv.submit(long_req, SamplingParams(max_new_tokens=8, timeout_s=50))
    rb = srv.submit(short_req, SamplingParams(max_new_tokens=8, timeout_s=5))
    srv.step()                       # A admitted into the only slot; B queued
    assert srv.result(rb).state is RequestState.QUEUED
    now[0] = 10.0                    # past B's deadline, inside A's
    srv.step()
    assert srv.result(rb).state is RequestState.TIMEOUT
    assert srv.result(ra).state is RequestState.RUNNING
    now[0] = 60.0                    # past A's deadline while RUNNING
    srv.step()
    assert srv.result(ra).state is RequestState.TIMEOUT
    assert srv.scheduler.pool.free_count == 1      # slot reclaimed
    assert srv.metrics.timeouts == 2


def test_streaming_callback_and_drain(engine):
    seen = []
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    rid = srv.submit(_prompts((5,), seed=4)[0],
                     SamplingParams(max_new_tokens=4),
                     on_token=lambda req, tok: seen.append(tok))
    srv.drain()                      # graceful: finishes in-flight work
    req = srv.result(rid)
    assert req.state is RequestState.FINISHED
    assert seen == req.tokens and len(seen) == 4
    with pytest.raises(RuntimeError):
        srv.submit(_prompts((5,))[0])   # post-drain submits are rejected


def test_serving_metrics_reach_csv_sink(engine, tmp_path):
    """serving.monitor=True fans TTFT/queue-depth events through
    MonitorMaster's CSV sink; shutdown closes the handles."""
    cfg = ServingConfig.from_dict({
        "num_slots": 2, "max_model_len": 64, "monitor": True,
        "monitor_interval": 1,
        "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                        "job_name": "srv"}})
    srv = ServingEngine(engine, cfg)
    for p in _prompts((5, 7, 4), seed=5):
        srv.submit(p, SamplingParams(max_new_tokens=3))
    srv.shutdown()
    out = tmp_path / "srv"
    ttft = out / "serving_ttft_ms.csv"
    depth = out / "serving_queue_depth.csv"
    assert ttft.exists(), sorted(os.listdir(out))
    assert depth.exists(), sorted(os.listdir(out))
    with open(ttft) as f:
        rows = list(csv.reader(f))
    assert len(rows) == 3 and all(float(v) >= 0 for _, v in rows)
    # close() ran: the sink holds no open handles after shutdown
    assert srv.monitor.csv_monitor._files == {}


def test_submit_validation(engine):
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 16})
    with pytest.raises(ValueError):
        srv.submit(np.arange(12, dtype=np.int32),
                   SamplingParams(max_new_tokens=8))   # 12 + 8 > 16
    with pytest.raises(ValueError):
        srv.submit(np.asarray([], np.int32))
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0).validate()


@pytest.mark.slow
@pytest.mark.parametrize("family", ["llama", "bloom", "neo"])
def test_family_parity_through_serving(family):
    """Per-slot decode handles the family hook points: RoPE + GQA (llama),
    ALiBi bias (bloom), per-layer local/global attention extras (neo)."""
    if family == "llama":
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
        model = LlamaModel(LlamaConfig(
            vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            n_kv_head=2, pad_vocab_to_multiple=1, dtype="float32"))
    elif family == "bloom":
        from deepspeed_tpu.models.bloom import BloomConfig, BloomModel
        model = BloomModel(BloomConfig(
            vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            pad_vocab_to_multiple=1, dtype="float32"))
    else:
        from deepspeed_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
        model = GPTNeoModel(GPTNeoConfig(
            vocab_size=96, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            pad_vocab_to_multiple=1, dtype="float32"))
    eng = deepspeed_tpu.init_inference(model, config={"dtype": "float32"})
    srv = ServingEngine(eng, {"num_slots": 3, "max_model_len": 32})
    prompts = _prompts((4, 7, 5), seed=7)
    prompts = [p % 96 for p in prompts]
    rids = [srv.submit(p, SamplingParams(max_new_tokens=5)) for p in prompts]
    srv.run_until_idle()
    for rid, p in zip(rids, prompts):
        ref = np.asarray(eng.generate(p[None], max_new_tokens=5))[0]
        np.testing.assert_array_equal(srv.result(rid).output_ids, ref)


def test_compiled_program_cache_lru_eviction(engine):
    """Satellite: InferenceEngine._fns is LRU-capped by
    config.compiled_cache_size (slot-serving programs are exempt)."""
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=32,
                                 n_layer=1, n_head=2, pad_vocab_to_multiple=1,
                                 dtype="float32"))
    eng = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "compiled_cache_size": 2})
    ids = _prompts((4,), seed=6)[0][None]
    for t in (4, 6, 8):
        eng.forward(np.tile(ids[:, :1], (1, t)))
    assert len(eng._fns) == 2                      # oldest bucket evicted
    keys = list(eng._fns)
    assert ("fwd", (1, 4)) not in keys and ("fwd", (1, 8)) in keys
    # slot programs do not count against the cap
    pool = eng.init_slot_pool(2, 16)
    pool, tok = eng.slot_prefill(pool, 0, np.arange(4, dtype=np.int32))
    assert len(eng._fns) == 2 and len(eng._slot_fns) >= 2
    assert 0 <= tok < VOCAB


def test_latency_windows_bounded_memory():
    """Satellite: percentile sources are fixed-size sliding windows — a
    long-running replica's metrics memory stays O(slo.window), and the
    percentiles describe the RECENT samples, not the whole lifetime."""
    from deepspeed_tpu.serving.config import SLOConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(slo=SLOConfig.from_dict({"window": 32}))
    for i in range(10_000):
        m.record_ttft(1.0)             # 1000ms each, ancient history
    for _ in range(32):
        m.record_ttft(0.002)           # 2ms, the recent window
        m.record_decode_step(0.001, n_active=1)
    assert len(m.ttft_ms) == 32        # O(window), not O(requests)
    assert len(m.token_ms) == 32
    assert m.ttft_ms.maxlen == 32 and m.e2e_ms.maxlen == 32
    pct = m.percentiles()
    assert pct["ttft_ms"]["p99"] == pytest.approx(2.0)   # old 1000ms gone
    assert m.tokens_out == 10_000 + 64  # totals still lifetime-accurate
    m.close()


def test_slo_burn_rate_tracking():
    """Sliding-window SLO: violation rate vs the error budget. 10% of
    TTFTs over target at a p99 SLO = burning budget at 10x."""
    from deepspeed_tpu.serving.config import SLOConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry import get_tracer

    slo = SLOConfig.from_dict({"window": 100, "ttft_ms": 50.0,
                               "target": 0.99})
    m = ServingMetrics(slo=slo)
    for i in range(100):
        m.record_ttft(0.010 if i % 10 else 0.100)   # 10% violate 50ms
    status = m.slo_status()
    assert status["metrics"]["ttft_ms"]["violation_rate"] == \
        pytest.approx(0.10)
    assert status["burn_rate"] == pytest.approx(10.0)
    # gauges surface on tick (snapshot/Prometheus/statusz all read them)
    m.record_tick(queue_depth=0, slot_utilization=0.0)
    counters = get_tracer().counters()
    assert counters["serving/slo_burn_rate"][0] == pytest.approx(10.0)
    assert counters["serving/ttft_ms_p50"][0] == pytest.approx(10.0)
    m.close()
    assert "serving/slo_burn_rate" not in get_tracer().counters()


def test_slo_burn_decays_on_idle_replica():
    """PR-14 follow-up regression: with slo.decay_s the sliding windows
    age out by WALL CLOCK, so an idle replica's last_burn_rate and its
    dstpu_tenant_* burn gauges relax to 0 — while an active replica (its
    samples keep refreshing) keeps its live burn. Without decay the idle
    replica's window is frozen history and its burn reads as live
    forever."""
    from deepspeed_tpu.serving.config import SLOConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry import get_tracer

    clock = {"t": 1000.0}
    slo = SLOConfig.from_dict({"window": 64, "ttft_ms": 50.0,
                               "target": 0.99, "decay_s": 30.0})

    def violate(m, tenant):
        m.record_ttft(0.100, tenant=tenant)       # 100ms > 50ms target

    idle = ServingMetrics(slo=slo, monitor_interval=1,
                          clock=lambda: clock["t"])
    active = ServingMetrics(slo=slo, monitor_interval=1,
                            clock=lambda: clock["t"])
    for _ in range(16):
        violate(idle, "acme")
        violate(active, "acme")
    idle.record_tick(queue_depth=0, slot_utilization=0.0)
    active.record_tick(queue_depth=0, slot_utilization=0.0)
    assert idle.last_burn_rate == pytest.approx(100.0)
    assert active.last_burn_rate == pytest.approx(100.0)
    assert idle.tenant_status()["acme"]["burn_rate"] == \
        pytest.approx(100.0)

    # 31 idle seconds: the idle replica's samples age out; the active
    # replica keeps violating, so its window stays populated
    for _ in range(10):
        clock["t"] += 3.1
        violate(active, "acme")
    assert idle.last_burn_rate == 0.0            # relaxed on READ, no tick
    assert idle.tenant_status()["acme"]["burn_rate"] == 0.0
    assert idle.percentiles()["ttft_ms"]["n"] == 0
    assert get_tracer().counter_value("serving/slo_burn_rate") == 0.0
    assert active.last_burn_rate == pytest.approx(100.0)
    assert active.tenant_status()["acme"]["burn_rate"] == \
        pytest.approx(100.0)
    # the relaxed gauges belong to the idle producer and die with it
    idle.close()
    active.close()


def test_slo_no_decay_keeps_frozen_window():
    """The decay is opt-in: without decay_s an idle replica's burn stays
    at its last value (the pre-PR-15 behavior, unchanged)."""
    from deepspeed_tpu.serving.config import SLOConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics

    clock = {"t": 0.0}
    m = ServingMetrics(slo=SLOConfig.from_dict(
        {"window": 16, "ttft_ms": 50.0}), monitor_interval=1,
        clock=lambda: clock["t"])
    for _ in range(8):
        m.record_ttft(0.100)
    m.record_tick(queue_depth=0, slot_utilization=0.0)
    burn = m.last_burn_rate
    assert burn and burn > 0
    clock["t"] += 1e6
    assert m.last_burn_rate == burn
    m.close()
