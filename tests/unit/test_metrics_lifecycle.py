"""Gauge-lifecycle lint sweep (the leak class PRs 4 and 8 fixed by hand).

Every ``dstpu_*`` gauge family a producer registers in the shared
telemetry counter space must (a) carry an ``owner=`` so it is tied to a
closable producer, and (b) vanish from ``tracer.counters()`` — and
therefore from ``prometheus_dump()`` / ``/metrics`` — when that producer
shuts down. A closed engine's queue depth, a dead fleet's replica count,
or a disabled ledger's goodput fraction reading as *live* is a silent
dashboard lie.

The sweep exercises the real producers (training engine with sentinel +
flight recorder + goodput ledger; serving fleet with router metrics,
path gauges, SLO gauges, recorder) and then asserts, at the tracer
level, that every registered tag had an owner and that shutdown retracts
everything. New gauge families added without an owner fail here instead
of in a hand-audit five PRs later.
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import SamplingParams, build_fleet
from deepspeed_tpu.telemetry import configure_ledger, get_tracer

VOCAB = 96

#: tags allowed to live without an owner: none today. The monitor-sink
#: mirror is ownerless BY DESIGN but only re-writes tags its producing
#: engine already owns, so it never creates an orphan family.
OWNERLESS_ALLOWED: frozenset = frozenset()


@pytest.fixture
def tracer():
    tr = get_tracer()
    prev = tr.enabled
    tr.clear()
    tr.configure(enabled=True, buffer_size=4096)
    yield tr
    configure_ledger(enabled=False)
    tr.clear()
    tr.configure(enabled=prev)


def _assert_all_owned(tracer, context: str):
    orphans = [tag for tag in tracer._counters
               if tag not in tracer._counter_owners
               and tag not in OWNERLESS_ALLOWED]
    assert not orphans, (
        f"{context}: gauge families registered WITHOUT an owner= "
        f"(their values would outlive their producer): {sorted(orphans)}")


def test_training_engine_gauges_owned_and_released(tracer, tmp_path):
    model = GPT2Model(GPT2Config(vocab_size=64, n_positions=32, n_embd=32,
                                 n_layer=1, n_head=2,
                                 pad_vocab_to_multiple=8))
    import jax
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": jax.device_count() * 2,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "telemetry": {"enabled": True, "mfu": False},
        "flight_recorder": {"enabled": True,
                            "dir": str(tmp_path / "rec"),
                            "slow_step_factor": 1000.0},
        "resilience": {"sentinel_policy": "warn",
                       "handle_signals": False},
    })
    rng = np.random.default_rng(0)
    for _ in range(2):
        engine.train_batch(batch={"input_ids": rng.integers(
            0, 63, size=(1, engine.train_batch_size, 16),
            dtype=np.int32)})
    # a sentinel observation and a forced bundle register their gauges
    engine._sentinel.observe(float("nan"), 1.0, step=1)
    engine._recorder.trigger("manual", "lifecycle sweep", force=True)
    engine.save_checkpoint(tmp_path / "ckpt")
    assert "resilience/sentinel_bad_steps" in tracer.counters()
    assert "recorder/bundles" in tracer.counters()
    assert any(t.startswith("goodput/") for t in tracer.counters())
    _assert_all_owned(tracer, "training engine live")
    engine.close()
    configure_ledger(enabled=False)   # the ledger is process-global; a
                                      # disabled ledger retracts its mirror
    leftovers = {t for t in tracer.counters() if t not in OWNERLESS_ALLOWED}
    assert not leftovers, (
        f"gauges survived engine.close() + ledger disable as if live: "
        f"{sorted(leftovers)}")


def test_fleet_gauges_owned_and_released(tracer, tmp_path):
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=64,
                                 n_embd=64, n_layer=2, n_head=4,
                                 pad_vocab_to_multiple=1,
                                 dtype="float32"))
    inf = deepspeed_tpu.init_inference(model, config={"dtype": "float32"})
    router = build_fleet(inf, {
        "num_slots": 2, "max_model_len": 64,
        "slo": {"ttft_ms": 1.0, "window": 16},     # burn gauges populate
        "monitor_interval": 1,                     # tenant gauges emit
        "flight_recorder": {"enabled": True,
                            "dir": str(tmp_path / "fleet_rec")},
        "chunked_prefill": {"enabled": True, "chunk_tokens": 16},
        "cost": {"enabled": True},
        "tenants": {"enabled": True, "rates": {"whale": 1.0},
                    "burst_tokens": 24},
        "fleet": {"enabled": True, "replicas": 2,
                  "heartbeat_timeout_s": 60.0}})
    rng = np.random.default_rng(1)
    fids = [router.submit(rng.integers(0, VOCAB, (t,), dtype=np.int32),
                          SamplingParams(max_new_tokens=4,
                                         tenant=tenant))
            for t, tenant in ((5, "acme"), (40, "acme"), (6, "zen"))]
    # a throttled tenant registers its dstpu_tenant_throttled series
    from deepspeed_tpu.serving import RateLimited
    with pytest.raises(RateLimited):
        router.submit(rng.integers(0, VOCAB, (30,), dtype=np.int32),
                      SamplingParams(max_new_tokens=8, tenant="whale"))
    router.step()
    victim = next(router.result(f).replica for f in fids
                  if router.result(f).replica is not None)
    router.kill(victim)               # failover bundle + requeue gauges
    router.run_until_idle()
    counters = tracer.counters()
    assert any(t.startswith("fleet/") for t in counters)
    assert any(t.startswith("fleet/path_") for t in counters)
    assert any(t.startswith("serving/") for t in counters)
    # the tenant dimension: per-tenant SLO windows + router throttles
    # must register owned (and vanish below) like every other family
    assert any(t.startswith("tenant/acme/") for t in counters)
    assert "tenant/acme/prompt_tokens" in counters
    assert "tenant/acme/tokens_out" in counters
    # the dstpu_cost_* family (router cost fold) registers owned too
    assert "cost/acme/chip_ms" in counters
    assert "fleet/cost_serving_wall_ms" in counters
    assert "fleet/cost_overhead_ms" in counters
    assert "tenant/whale/throttled" in counters
    assert "fleet/throttled" in counters
    assert "recorder/bundles" in counters
    _assert_all_owned(tracer, "fleet live")
    # a live rollout registers the dstpu_rollout_* family the same way
    # (run LAST: its replace phase drains the original replicas, which
    # retracts their per-tenant windows)
    from deepspeed_tpu.serving import RolloutConfig
    ctl = router.start_rollout(
        inf.with_params(inf.params, inf.weights_version),
        config=RolloutConfig(canary_n=1, step_fraction=1.0, sustain_s=0.0))
    for _ in range(2000):
        router.step()
        if not ctl.active and not router._draining:
            break
    assert ctl.phase == "done", ctl.failure
    assert "rollout/shift_fraction" in tracer.counters()
    assert "rollout/version_skew" in tracer.counters()
    _assert_all_owned(tracer, "fleet live post-rollout")
    router.shutdown()
    configure_ledger(enabled=False)
    leftovers = {t for t in tracer.counters() if t not in OWNERLESS_ALLOWED}
    assert not leftovers, (
        f"gauges survived router.shutdown() as if live: "
        f"{sorted(leftovers)}")


def test_moe_gauges_owned_and_released(tracer):
    """ROADMAP item 3 seed: the dstpu_moe_* family (per-expert load +
    capacity-factor overflow, moe/sharded_moe.py MoeMetrics) follows the
    same owner/retraction contract as every other family — live with its
    producer, gone from /metrics after close(). The routing math is
    pinned too: a [E] count vector's imbalance and overflow fractions
    must match hand arithmetic."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import MoeMetrics
    from deepspeed_tpu.moe.sharded_moe import topk_gating
    from deepspeed_tpu.telemetry import prometheus_dump

    m = MoeMetrics(tracer=tracer)
    # real routing evidence: 16 tokens through a rigged 4-expert gate
    # where every token prefers expert 0 (logit margin), capacity 4
    logits = jnp.zeros((16, 4)).at[:, 0].set(5.0)
    _l_aux, _combine, _dispatch, exp_counts = topk_gating(
        logits, k=1, capacity_factor=1.0, min_capacity=4, use_rts=False,
        rng=jax.random.PRNGKey(0), train=False)
    out = m.record(np.asarray(exp_counts), capacity=4, step=1)
    # all 16 routed to expert 0: imbalance = 16/4 mean = 4x, 12 dropped
    assert out["expert_load_max"] == 16.0
    assert out["expert_load_mean"] == 4.0
    assert out["load_imbalance"] == pytest.approx(4.0)
    assert out["dropped_token_fraction"] == pytest.approx(12 / 16)
    assert out["overflow_tokens"] == 12.0 and out["overflow_steps"] == 1.0
    # balanced counts: imbalance 1.0, nothing dropped, counters hold
    out = m.record(np.full((4,), 4.0), capacity=4, step=2)
    assert out["load_imbalance"] == pytest.approx(1.0)
    assert out["dropped_token_fraction"] == 0.0
    assert out["overflow_tokens"] == 12.0
    assert m.summary()["records"] == 2
    # wire accounting: logical all-to-all payload E x C x M x itemsize
    # each direction — 4 * 4 * 8 * 4 = 512 bytes per step per leg
    wire = m.record_wire(capacity=4, num_experts=4, model_dim=8,
                         itemsize=4, step=2)
    assert wire["dispatch_bytes_total"] == 512.0
    assert wire["combine_bytes_total"] == 512.0
    assert wire["wire_bytes_per_step"] == 1024.0
    assert m.summary()["dispatch_bytes"] == 512
    dump = prometheus_dump(tracer)
    assert "dstpu_moe_dispatch_bytes_total 512.0" in dump
    assert "dstpu_moe_wire_bytes_per_step 1024.0" in dump
    assert "dstpu_moe_load_imbalance 1.0" in dump
    assert "dstpu_moe_dropped_token_fraction 0.0" in dump
    assert "dstpu_moe_overflow_tokens 12.0" in dump
    _assert_all_owned(tracer, "moe metrics live")
    m.close()
    dump = prometheus_dump(tracer)
    assert "dstpu_moe_" not in dump
    assert not [t for t in tracer.counters() if t.startswith("moe/")]


def test_prometheus_dump_reflects_retraction(tracer):
    """The exported text is the user-visible surface of the contract: a
    family present while live must be absent after its producer closes."""
    from deepspeed_tpu.serving.metrics import FleetMetrics
    from deepspeed_tpu.telemetry import prometheus_dump
    m = FleetMetrics(tracer=tracer)
    m.update(replicas=2, ready=2, pending=0)
    tracer.set_counter("fleet/path_prefill_ms_p50", 3.25, owner=m)
    assert "dstpu_fleet_path_prefill_ms_p50 3.25" in prometheus_dump(tracer)
    m.close()
    dump = prometheus_dump(tracer)
    assert "dstpu_fleet_path_prefill_ms_p50" not in dump
    assert "dstpu_fleet_ready_replicas" not in dump
