"""Chunked prefill + multi-tenant scheduling tests (ISSUE 13).

The contracts under test:

- **bitwise token parity** — chunking a prompt's prefill across ticks is
  invisible in the tokens, on every admission path (plain, prefix-reuse,
  disaggregated handoff, speculative) and for both greedy and sampled
  streams (the first token still derives from ``(seed, position)`` only);
- **compile-once** — intermediate chunks share ONE compiled flavor per
  pow2 chunk bucket regardless of prompt length, and a 4k prompt never
  compiles (or runs) a monolithic prefill program;
- **stall-free decode** — co-resident requests advance every tick while
  a long prompt prefills, and no tick's wall time carries the monolithic
  prefill spike;
- **tenant isolation** — DRR admission honors weights, the router's
  token buckets reject over-rate tenants with a 429-style
  ``RateLimited``, failover replays preserve the tenant and restart
  chunk progress, and the ``prefill_chunk`` critical-path stage keeps
  the stage-sum == e2e identity exact.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (QueueFull, RateLimited, Request,
                                   RequestState, SamplingParams,
                                   ServingConfig, ServingEngine,
                                   TenantQueues, build_fleet)
from deepspeed_tpu.serving.config import ChunkedPrefillConfig, TenantConfig
from deepspeed_tpu.serving.fleet.handoff import KVHandoff
from deepspeed_tpu.telemetry.disttrace import TraceContext

VOCAB = 96


@pytest.fixture(scope="module")
def engine():
    """Mid-context engine for the parity/tenant tests."""
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=1024,
                                 n_embd=32, n_layer=2, n_head=2,
                                 pad_vocab_to_multiple=1, dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


@pytest.fixture(scope="module")
def engine4k():
    """Long-context engine for the injected-4k-prompt tests."""
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=4352,
                                 n_embd=32, n_layer=2, n_head=2,
                                 pad_vocab_to_multiple=1, dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,),
                                                dtype=np.int32)


def _serve(engine, cfg, submits):
    """Run [(prompt, SamplingParams)] to completion; returns token
    lists in submit order plus the ServingEngine (shut down)."""
    srv = ServingEngine(engine, cfg)
    rids = [srv.submit(p, sp) for p, sp in submits]
    srv.run_until_idle()
    toks = [list(srv.result(r).tokens) for r in rids]
    states = [srv.result(r).state for r in rids]
    srv.shutdown()
    assert all(s is RequestState.FINISHED for s in states), states
    return toks


CHUNKED = {"chunked_prefill": {"enabled": True, "chunk_tokens": 64}}


# ---------------------------------------------------------------- parity

def test_chunked_parity_greedy_and_sampled(engine):
    """Chunked vs monolithic prefill: bitwise token parity for greedy
    AND sampled streams, across differing prompt lengths (multiple
    intermediate chunks + differing final-suffix buckets)."""
    base = {"num_slots": 4, "max_model_len": 1024, "max_queue": 16}
    subs = [(_prompt(300, 1), SamplingParams(max_new_tokens=6)),
            (_prompt(500, 2), SamplingParams(max_new_tokens=6,
                                             temperature=0.8, top_k=10,
                                             seed=11)),
            (_prompt(12, 3), SamplingParams(max_new_tokens=6)),
            (_prompt(430, 4), SamplingParams(max_new_tokens=6,
                                             temperature=1.1, top_p=0.9,
                                             seed=5))]
    mono = _serve(engine, base, subs)
    chunked = _serve(engine, {**base, **CHUNKED}, subs)
    assert mono == chunked
    # the greedy stream is also bitwise generate()
    ref = np.asarray(engine.generate(subs[0][0][None],
                                     max_new_tokens=6))[0]
    assert mono[0] == list(ref[subs[0][0].size:])


def test_chunked_prefix_reuse_parity(engine):
    """Chunked admission composes with radix prefix reuse: only the
    unshared suffix is chunked, and the tokens still match monolithic
    serving without any cache."""
    shared = _prompt(200, 7)
    tails = [_prompt(150, 8), _prompt(260, 9), _prompt(40, 10)]
    prompts = [np.concatenate([shared, t]).astype(np.int32)
               for t in tails]
    subs = [(p, SamplingParams(max_new_tokens=5)) for p in prompts]
    mono = _serve(engine, {"num_slots": 4, "max_model_len": 1024,
                           "max_queue": 16}, subs)
    cfg = {"num_slots": 4, "max_model_len": 1024, "max_queue": 16,
           "prefix_cache": {"enabled": True, "min_prefix_len": 8},
           **CHUNKED}
    srv = ServingEngine(engine, cfg)
    # serialize so each finished prompt donates its lane before the next
    # admission — every later prompt takes the reuse path
    rids = []
    for p, sp in subs:
        rids.append(srv.submit(p, sp))
        srv.run_until_idle()
    toks = [list(srv.result(r).tokens) for r in rids]
    pc = srv.scheduler.prefix_cache
    assert pc.hits >= 2, "prefix cache never hit — reuse path untested"
    srv.shutdown()
    assert toks == mono


def test_chunked_handoff_parity_and_tenant(engine):
    """Disaggregated fleet with chunked prefill on the prefill replica:
    tokens match monolithic serving, the KVHandoff carries the tenant,
    and the decode side's per-tenant windows see it."""
    subs = [(_prompt(300, 21),
             SamplingParams(max_new_tokens=6, tenant="acme")),
            (_prompt(150, 22),
             SamplingParams(max_new_tokens=6, tenant="zen"))]
    mono = _serve(engine, {"num_slots": 4, "max_model_len": 1024,
                           "max_queue": 16}, subs)
    router = build_fleet(engine, {
        "num_slots": 4, "max_model_len": 1024, "max_queue": 16,
        **CHUNKED,
        "fleet": {"enabled": True, "replicas": 2, "prefill_replicas": 1,
                  "decode_replicas": 1, "heartbeat_timeout_s": 60.0}})
    fids = [router.submit(p, sp) for p, sp in subs]
    router.run_until_idle()
    toks = [list(router.result(f).tokens) for f in fids]
    assert toks == mono
    assert router.result(fids[0]).trace.tenant == "acme"
    decode = next(r for r in router.replicas.values()
                  if r.role == "decode")
    tstats = decode.engine.metrics.tenant_status()
    assert "acme" in tstats and "zen" in tstats
    table = router.tenant_summary()
    assert table["acme"]["completed"] >= 1
    # the aggregator's critical path grew the prefill_chunk stage and
    # the aligned-window sum-to-e2e identity still holds (the prefill
    # replica chunked; stage means must still sum to the e2e mean)
    summary = router.aggregator.critical_path_summary()
    assert "prefill_chunk" in summary["stages"]
    assert summary["stage_sum_ms_mean"] == pytest.approx(
        summary["e2e_ms_mean"], rel=0.05)
    router.shutdown()


def test_chunked_speculative_parity(engine):
    """Chunked prefill + speculative decode: the draft lane prefills at
    chunked-admission completion and the emitted stream stays bitwise
    the non-speculative, non-chunked stream."""
    subs = [(_prompt(200, 31), SamplingParams(max_new_tokens=10)),
            (_prompt(90, 32), SamplingParams(max_new_tokens=10))]
    mono = _serve(engine, {"num_slots": 2, "max_model_len": 1024,
                           "max_queue": 8}, subs)
    spec = _serve(engine, {"num_slots": 2, "max_model_len": 1024,
                           "max_queue": 8, **CHUNKED,
                           "speculative": {"enabled": True, "k": 2,
                                           "draft": {"mode": "self",
                                                     "layers": 1}}},
                  subs)
    assert spec == mono


# ---------------------------------------------------- compile-once / stall

def test_chunk_compile_once_per_pow2_flavor(engine):
    """Two long prompts of different lengths share ONE compiled chunk
    program (the chunk_tokens bucket); no monolithic prefill flavor for
    their full lengths exists."""
    subs = [(_prompt(300, 41), SamplingParams(max_new_tokens=2)),
            (_prompt(500, 42), SamplingParams(max_new_tokens=2))]
    before = set(engine._slot_fns)
    _serve(engine, {"num_slots": 4, "max_model_len": 1024,
                    "max_queue": 8, **CHUNKED}, subs)
    assert engine.slot_executables("slot_chunk", 4, 64, 1024) == 1
    # chunking compiled NO monolithic prefill flavor: every program the
    # run added stays at/below the chunk bucket (the engine fixture is
    # shared, so compare against the pre-run key set)
    new = set(engine._slot_fns) - before
    for key in new:
        if key[0] in ("slot_prefill", "slot_suffix", "slot_chunk"):
            bucket = key[2] if key[0] == "slot_chunk" else key[1]
            assert bucket <= 64, f"oversized prefill flavor {key}"


def test_4k_prompt_stall_free_ticks(engine4k):
    """The tentpole behavior, structurally: while a 4096-token prompt
    prefills in chunks, (a) a co-resident decoding request advances
    EVERY tick, (b) the prefill spreads over ~prompt/chunk ticks, and
    (c) no chunked tick's wall time reaches the monolithic admission
    tick's prefill spike."""
    chunk = 256
    cfg = {"num_slots": 2, "max_model_len": 4300, "max_queue": 8,
           "chunked_prefill": {"enabled": True, "chunk_tokens": chunk}}
    big = _prompt(4096, 51)
    small = _prompt(16, 52)

    # -- monolithic: measure the admission tick (the stall)
    srv = ServingEngine(engine4k, {"num_slots": 2, "max_model_len": 4300,
                                   "max_queue": 8})
    warm = srv.submit(big, SamplingParams(max_new_tokens=2))
    srv.run_until_idle()                      # compile the 4096 bucket
    assert srv.result(warm).done
    srv.submit(big, SamplingParams(max_new_tokens=2))
    t0 = time.perf_counter()
    srv.step()                                # whole 4k prefill, one tick
    mono_spike = time.perf_counter() - t0
    srv.run_until_idle()
    srv.shutdown()

    # -- chunked: small request decodes while the 4k prompt lands
    srv = ServingEngine(engine4k, cfg)
    warm = srv.submit(big, SamplingParams(max_new_tokens=2))
    srv.run_until_idle()                      # compile chunk + suffix
    assert srv.result(warm).done
    small_rid = srv.submit(small, SamplingParams(max_new_tokens=64))
    srv.step()                                # small admitted + decoding
    big_rid = srv.submit(big, SamplingParams(max_new_tokens=2))
    ticks = 0
    walls = []
    while srv.result(big_rid).state in (RequestState.QUEUED,
                                        RequestState.PREFILLING):
        before = len(srv.result(small_rid).tokens)
        t0 = time.perf_counter()
        srv.step()
        walls.append(time.perf_counter() - t0)
        ticks += 1
        # stall-free: the decoding request advanced THIS tick too
        assert len(srv.result(small_rid).tokens) == before + 1
        assert ticks < 64, "chunked prefill never completed"
    assert ticks >= 4096 // chunk - 1         # spread over many ticks
    assert srv.result(big_rid).state in (RequestState.RUNNING,
                                         RequestState.FINISHED)
    # no chunked tick carries the monolithic spike (the margin is wide —
    # one chunk is 1/16th of the monolithic prefill's work)
    assert max(walls) < mono_spike
    # and the chunk program for this pool compiled exactly once
    assert engine4k.slot_executables("slot_chunk", 2, chunk, 4300) == 1
    srv.run_until_idle()
    srv.shutdown()


def test_prefilling_request_expires_and_frees_slot(engine):
    """A PREFILLING request past its deadline times out mid-chunking and
    returns its slot."""
    clock = [0.0]
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 1024,
                                 "max_queue": 8, **CHUNKED},
                        clock=lambda: clock[0])
    rid = srv.submit(_prompt(400, 61),
                     SamplingParams(max_new_tokens=4, timeout_s=5.0))
    srv.step()                                 # first chunk lands
    assert srv.result(rid).state is RequestState.PREFILLING
    assert len(srv.scheduler.prefilling) == 1
    clock[0] = 10.0                            # past the deadline
    srv.step()
    assert srv.result(rid).state is RequestState.TIMEOUT
    assert not srv.scheduler.prefilling
    assert srv.scheduler.pool.free_count == 2
    srv.shutdown()


# ------------------------------------------------------------ tenant DRR

def _req(tenant, n_tokens, rid=0):
    return Request(request_id=rid, prompt=np.zeros((n_tokens,), np.int32),
                   sampling=SamplingParams(tenant=tenant),
                   max_new_tokens=1)


def test_drr_fairness_ratios():
    """Deficit round-robin grants admission tokens proportional to
    weights among backlogged tenants: weight 2:1:1 over equal-cost
    requests pops in a 2:1:1 ratio (within one round's slack)."""
    cfg = TenantConfig(enabled=True, default_weight=1.0,
                       weights={"a": 2.0}, quantum_tokens=32)
    cfg.validate()
    q = TenantQueues(cfg)
    for i in range(40):
        for t in ("a", "b", "c"):
            q.append(_req(t, 32, rid=i))
    served = {"a": 0, "b": 0, "c": 0}
    for _ in range(60):
        served[q.popleft().tenant] += 1
    assert served["a"] == pytest.approx(2 * served["b"], abs=2)
    assert served["b"] == pytest.approx(served["c"], abs=2)
    # whale prompts drain their deficit proportionally: a tenant with
    # 8x-longer prompts gets ~1/8th the POPS at equal weight
    q2 = TenantQueues(cfg)
    for i in range(40):
        q2.append(_req("whale", 256, rid=i))
        q2.append(_req("small", 32, rid=100 + i))
    pops = {"whale": 0, "small": 0}
    for _ in range(36):
        pops[q2.popleft().tenant] += 1
    assert pops["small"] >= 6 * pops["whale"]


def test_tenant_queue_preserves_fifo_when_disabled():
    """Without the tenants block, admission order is byte-for-byte the
    old single FIFO, whatever tenants the requests claim."""
    q = TenantQueues(None)
    reqs = [_req(t, 8, rid=i)
            for i, t in enumerate(("a", "b", "a", "c", "b"))]
    for r in reqs:
        q.append(r)
    assert not q.enabled
    assert [q.popleft().request_id for _ in range(5)] == [0, 1, 2, 3, 4]


def test_rate_limit_rejection_429(engine):
    """The router's per-tenant token bucket rejects over-budget submits
    with a 429-style RateLimited (a QueueFull subclass), counts the
    throttle per tenant, and leaves conforming tenants untouched."""
    router = build_fleet(engine, {
        "num_slots": 2, "max_model_len": 1024, "max_queue": 16,
        "tenants": {"enabled": True, "rates": {"whale": 50.0},
                    "burst_tokens": 80},
        "fleet": {"enabled": True, "replicas": 1,
                  "heartbeat_timeout_s": 60.0}})
    sp = SamplingParams(max_new_tokens=16, tenant="whale")
    router.submit(_prompt(60, 71), sp)          # 76 tokens: fits burst
    with pytest.raises(RateLimited) as exc:
        router.submit(_prompt(60, 72), sp)      # bucket is drained
    assert isinstance(exc.value, QueueFull)
    assert exc.value.status == 429
    assert exc.value.tenant == "whale"
    assert exc.value.retry_after_s > 0
    # an unlimited tenant (no rate configured, default 0 = unlimited)
    # passes while the whale is shedding
    router.submit(_prompt(60, 73),
                  SamplingParams(max_new_tokens=4, tenant="smol"))
    assert router.metrics.throttled == 1
    assert router.metrics.tenant_throttled == {"whale": 1}
    router.run_until_idle()
    router.shutdown()


def test_failover_preserves_tenant_and_restarts_chunks(engine):
    """Kill the replica serving a mid-prefill chunked request: the
    survivor replays it from scratch (chunk progress is replica-local),
    the tenant rides the trace into the replay, and the final tokens
    are bitwise the single-replica reference."""
    big = _prompt(400, 81)
    sp = SamplingParams(max_new_tokens=6, tenant="acme", seed=3,
                        temperature=0.7, top_k=8)
    ref = _serve(engine, {"num_slots": 2, "max_model_len": 1024,
                          "max_queue": 8}, [(big, sp)])[0]
    router = build_fleet(engine, {
        "num_slots": 2, "max_model_len": 1024, "max_queue": 8, **CHUNKED,
        "fleet": {"enabled": True, "replicas": 2,
                  "heartbeat_timeout_s": 60.0}})
    fid = router.submit(big, sp)
    router.step()
    router.step()                     # a couple of chunks have landed
    freq = router.result(fid)
    victim = freq.replica
    assert victim is not None
    vict_eng = router.replicas[victim].engine
    assert freq.request.state is RequestState.PREFILLING
    assert len(vict_eng.scheduler.prefilling) == 1
    router.kill(victim, reason="mid-prefill kill")
    router.run_until_idle()
    assert freq.state == "finished"
    assert list(freq.tokens) == ref    # replay, bitwise — sampled stream
    assert freq.trace.tenant == "acme"
    assert freq.trace.replays == 1
    # the survivor restarted chunk progress: its trace accumulated fresh
    # prefill_chunk marks AFTER the requeue
    labels = [m[0] for m in freq.trace.marks]
    assert "requeued" in labels
    assert "prefill_chunk" in labels[labels.index("requeued"):]
    router.shutdown()


# ---------------------------------------------------- trace / frame plumbing

def test_handoff_frame_and_trace_header_carry_tenant():
    ctx = TraceContext.mint(origin="router", tenant="acme")
    ctx2 = TraceContext.from_header(ctx.to_header())
    assert ctx2.tenant == "acme"
    assert ctx2.span_args().get("tenant") == "acme"
    lane = {"k": np.zeros((2, 1, 2, 8, 4), np.float32),
            "v": np.ones((2, 1, 2, 8, 4), np.float32)}
    h = KVHandoff(prompt=np.arange(5, dtype=np.int32), first_token=3,
                  kv_len=5, lane=lane, tenant="acme",
                  trace=ctx.to_header())
    h2 = KVHandoff.from_bytes(h.to_bytes())
    assert h2.tenant == "acme"
    assert h2.trace["tenant"] == "acme"


def test_prefill_chunk_stage_sums_to_e2e(engine):
    """The prefill_chunk critical-path stage exists and the per-request
    stage decomposition still sums to the trace e2e EXACTLY."""
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 1024,
                                 "max_queue": 8, **CHUNKED})
    rid = srv.submit(_prompt(300, 91), SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    ctx = srv.result(rid).trace
    path = ctx.critical_path()
    assert path.get("prefill_chunk", 0.0) > 0.0
    assert path.get("prefill", 0.0) > 0.0
    assert sum(path.values()) == pytest.approx(ctx.total_ms(), abs=1e-6)
    srv.shutdown()


def test_lazy_expiry_at_pop_and_sweep(engine):
    """Queued requests past their deadline finish as TIMEOUT at pop time
    (no per-tick full scan needed) and the low-frequency sweep clears
    the ones never popped."""
    clock = [0.0]
    srv = ServingEngine(engine, {"num_slots": 1, "max_model_len": 1024,
                                 "max_queue": 16},
                        clock=lambda: clock[0])
    # the slot is held by a long-running request, so the queue backs up
    run = srv.submit(_prompt(8, 95), SamplingParams(max_new_tokens=40))
    srv.step()
    dead = [srv.submit(_prompt(8, 96 + i),
                       SamplingParams(max_new_tokens=2, timeout_s=1.0))
            for i in range(3)]
    live = srv.submit(_prompt(8, 99), SamplingParams(max_new_tokens=2))
    clock[0] = 5.0                      # every deadline blown
    srv.run_until_idle()
    assert srv.result(run).state is RequestState.FINISHED
    for rid in dead:
        assert srv.result(rid).state is RequestState.TIMEOUT
    assert srv.result(live).state is RequestState.FINISHED
    assert srv.metrics.timeouts == 3
    srv.shutdown()


# ------------------------------------------------------------- validation

def test_config_validation():
    with pytest.raises(Exception):
        ChunkedPrefillConfig(enabled=True, chunk_tokens=100).validate()
    with pytest.raises(Exception):
        ChunkedPrefillConfig(enabled=True, chunk_tokens=8).validate()
    ChunkedPrefillConfig(enabled=True, chunk_tokens=128).validate()
    with pytest.raises(Exception):
        TenantConfig(enabled=True, weights={"a": -1}).validate()
    with pytest.raises(Exception):
        TenantConfig(enabled=True, quantum_tokens=0).validate()
    with pytest.raises(ValueError):
        SamplingParams(tenant="a/b").validate()
    with pytest.raises(ValueError):
        SamplingParams(tenant="").validate()
    with pytest.raises(Exception):
        ServingConfig.from_dict({"max_model_len": 64, "chunked_prefill":
                                 {"enabled": True, "chunk_tokens": 128}})
    cfg = ServingConfig.from_dict({
        "chunked_prefill": {"enabled": True, "chunk_tokens": 64},
        "tenants": {"enabled": True, "weights": {"a": 2.0},
                    "rates": {"a": 10.0}}})
    assert cfg.chunked_prefill.chunk_tokens == 64
    assert cfg.tenants.weight_of("a") == 2.0
    assert cfg.tenants.weight_of("b") == 1.0
    assert cfg.tenants.rate_of("b") == 0.0


def test_ds_tpu_serve_tenant_config_smoke(tmp_path):
    """ds_tpu_serve --config with the shipped multi-tenant JSON: the
    CLI boots a chunked + tenant-aware replica and serves prompts long
    enough to exercise the chunk path (statusz moved to an ephemeral
    port so the smoke never fights over :8080)."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "examples", "configs",
                           "serving_tenants.json")) as f:
        cfg = json.load(f)
    cfg["statusz"]["port"] = 0
    path = tmp_path / "serving_tenants.json"
    path.write_text(json.dumps(cfg))
    res = subprocess.run(
        [sys.executable, os.path.join(repo, "bin", "ds_tpu_serve"),
         "--cpu", "--config", str(path), "--max-len", "4352",
         "--requests", "3", "--rate", "50", "--prompt-len", "600",
         "--max-new", "6"],
        capture_output=True, text=True, cwd=repo, timeout=420)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    summary = json.loads(res.stdout[res.stdout.index("{"):])
    assert summary["completed"] == 3


def test_tenant_gauges_present_and_prometheus_series(engine):
    """dstpu_tenant_* gauges: present while serving, tenant= labeled in
    the Prometheus dump, and retracted on shutdown (the lifecycle lint
    in test_metrics_lifecycle.py covers the fleet-wide sweep)."""
    from deepspeed_tpu.telemetry import get_tracer, prometheus_dump
    tracer = get_tracer()
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 1024,
                                 "max_queue": 8, "monitor_interval": 1,
                                 "slo": {"ttft_ms": 10000.0},
                                 "tenants": {"enabled": True}})
    for tenant in ("acme", "zen"):
        srv.submit(_prompt(12, 101), SamplingParams(max_new_tokens=3,
                                                    tenant=tenant))
    srv.run_until_idle()
    counters = tracer.counters()
    assert "tenant/acme/ttft_ms_p99" in counters
    assert "tenant/zen/burn_rate" in counters
    dump = prometheus_dump(tracer)
    assert 'dstpu_tenant_ttft_ms_p99{tenant="acme"}' in dump
    srv.shutdown()
    dump = prometheus_dump(tracer)
    assert "dstpu_tenant_" not in dump
