"""Rollout plane tests (serving/fleet/rollout.py).

Contracts under test: a same-version rollout must pass the bitwise
canary verify (the PR-12 determinism contract makes replay comparison
exact), complete through shift -> replace -> done, hand back a fleet of
exactly its original size at version skew 0, and never drop or
duplicate a streamed token; a rigged vNext (perturbed params at the
SAME version) must fail the canary, roll back automatically, leave the
replica set unchanged, and fire exactly ONE ``rollout_failed``
flight-recorder bundle embedding the canary diff and burn timeline; an
SLO burn breach mid-shift rolls back the same way; killing the canary
mid-verify aborts cleanly; a vPrev replica dying mid-rollout fails its
requests over with delivery exactly-once; ``start_rollout`` refuses
disaggregated fleets, disabled configs, and concurrent rollouts; the
``dstpu_rollout_*`` gauges and the ds_tpu_top panel ride along.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (RolloutConfig, SamplingParams,
                                   build_fleet)
from deepspeed_tpu.telemetry import get_tracer

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOCAB = 96


@pytest.fixture(scope="module")
def engine():
    model = GPT2Model(GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=1,
                                 dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


@pytest.fixture
def tracer():
    tr = get_tracer()
    prev = tr.enabled
    tr.clear()
    tr.configure(enabled=True, buffer_size=4096)
    yield tr
    tr.clear()
    tr.configure(enabled=prev)


def _prompts(lengths, seed=0, vocab=VOCAB):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (t,), dtype=np.int32) for t in lengths]


def _fleet_cfg(engine_cfg=None, **fleet):
    cfg = {"num_slots": 2, "max_model_len": 64}
    cfg.update(engine_cfg or {})
    fleet.setdefault("rollout", {"canary_n": 2, "step_fraction": 0.5,
                                 "sustain_s": 0.0})
    cfg["fleet"] = {"enabled": True, "heartbeat_timeout_s": 60.0, **fleet}
    return cfg


def _warm(router, n=3, seed=7, max_new=4):
    """Complete ``n`` requests so the canary has a replay set."""
    fids = [router.submit(p, SamplingParams(max_new_tokens=max_new))
            for p in _prompts((5, 8, 6, 9, 7)[:n], seed=seed)]
    router.run_until_idle()
    assert all(router.result(f).done for f in fids)
    return fids


def _run_rollout(router, ctl, max_steps=5000):
    """Drive the router until the rollout settles and drains finish."""
    for _ in range(max_steps):
        router.step()
        if not ctl.active and not router._draining:
            break
    assert not ctl.active, f"rollout still {ctl.phase} after {max_steps}"
    return ctl


def _live(router):
    return sorted(r.name for r in router.replicas.values() if not r.failed)


# ---------------------------------------------------------- happy path

def test_same_version_rollout_bitwise_canary_to_done(engine):
    """A same-version rollout: canary verdict bitwise_identical, phase
    walks standup -> canary -> shift -> replace -> done, the fleet hands
    back exactly its original size at skew 0, and requests streaming
    THROUGH the swap finish bitwise-correct with every position
    delivered exactly once."""
    router = build_fleet(engine, _fleet_cfg(replicas=2))
    _warm(router)
    before_n = len(_live(router))
    # two distinct lengths only: the generate() reference traces one
    # shape per (len, max_new) pair, shared with the failover test below
    prompts = _prompts((6, 9, 6, 9), seed=11)
    streamed = {i: [] for i in range(len(prompts))}
    fids = [router.submit(p, SamplingParams(max_new_tokens=8),
                          on_token=lambda r, t, i=i: streamed[i].append(t))
            for i, p in enumerate(prompts)]
    view = engine.with_params(engine.params, engine.weights_version)
    ctl = router.start_rollout(view)
    assert ctl.phase == "canary"          # standup already happened
    assert router.rollout_summary()["active"] is True
    _run_rollout(router, ctl)
    router.run_until_idle()
    assert ctl.phase == "done"
    assert ctl.canary_verdict == "bitwise_identical"
    assert all(rec.match for rec in ctl._records)
    assert router.metrics.rollouts == 1
    assert router.metrics.rollbacks == 0
    assert router.version_skew()["skew"] == 0
    # zero-downtime: same capacity back, all vNext members
    live = _live(router)
    assert len(live) == before_n
    assert set(live) == ctl._vnext
    for i, fid in enumerate(fids):
        fr = router.result(fid)
        assert fr.state == "finished", fr.failed_reason
        ref = np.asarray(
            engine.generate(prompts[i][None], max_new_tokens=8))[0]
        np.testing.assert_array_equal(fr.output_ids, ref)
        assert streamed[i] == list(ref[len(prompts[i]):])  # no dup/gap
    assert router.rollout_summary()["phase"] == "done"
    router.shutdown()


# ----------------------------------------------------------- rollback

def test_rigged_vnext_fails_canary_rolls_back_one_bundle(engine, tmp_path):
    """vNext params perturbed at the SAME version: the bitwise canary
    verify must catch it, roll back, leave the fleet untouched, and
    fire exactly one rollout_failed bundle with the canary diff and
    burn timeline embedded."""
    router = build_fleet(engine, _fleet_cfg(
        {"flight_recorder": {"enabled": True, "dir": str(tmp_path)}},
        replicas=2))
    _warm(router)
    before = _live(router)
    # a vNext that greedy decoding can tell apart: the final LayerNorm's
    # bias points at token 0's embedding, so every token comes out 0. (A
    # uniform ``x * 1.25 + 0.01`` scales and shifts every logit of this
    # tied-embedding model alike and left the two replayed requests'
    # tokens as they were: the rollout ended ``done``.)
    # Placed as the served bias is: a new layout would be a new program,
    # which the canary refuses before it compares a token.
    import jax
    bad = dict(engine.params)
    bad["ln_f_bias"] = jax.device_put(1000.0 * engine.params["wte"][0],
                                      engine.params["ln_f_bias"].sharding)
    ctl = router.start_rollout(
        engine.with_params(bad, engine.weights_version))
    _run_rollout(router, ctl)
    assert ctl.phase == "rolled_back"
    assert ctl.canary_verdict == "failed"
    assert "diverge" in ctl.failure
    assert router.metrics.rollbacks == 1
    assert router.metrics.canary_failures == 1
    assert router.metrics.rollouts == 0
    assert _live(router) == before         # fleet unchanged
    assert router.version_skew()["skew"] == 0
    bundles = [b for b in router.recorder.bundles()
               if b["kind"] == "rollout_failed"]
    assert len(bundles) == 1, router.recorder.bundles()
    with open(os.path.join(router.recorder.dir, bundles[0]["file"])) as f:
        doc = json.load(f)
    audit = doc["status"]["rollout"]
    assert audit["canary_verdict"] == "failed"
    assert audit["phase"] == "rolled_back"
    assert any(rec["match"] is False for rec in audit["canary"])
    assert "burn_timeline" in audit
    # the aborted rollout leaves the fleet fully serviceable
    _warm(router, n=2, seed=13)
    router.shutdown()


def test_burn_breach_mid_shift_rolls_back(engine):
    """The SLO gate: once the shift has begun, a burn rate over the
    ceiling rolls the rollout back and drains every replica it
    spawned."""
    router = build_fleet(engine, _fleet_cfg(replicas=2))
    _warm(router)
    before = _live(router)
    ctl = router.start_rollout(
        engine.with_params(engine.params, engine.weights_version))
    # breach the ceiling only once the shift is actually under way
    router._fleet_burn = lambda: 99.0 if ctl.fraction >= 0.5 else 0.0
    _run_rollout(router, ctl)
    assert ctl.phase == "rolled_back"
    assert "burn" in ctl.failure and "ceiling" in ctl.failure
    assert ctl.canary_verdict == "bitwise_identical"   # canary had passed
    assert ctl.fraction == 0.0             # traffic shifted back
    assert router.metrics.rollbacks == 1
    assert _live(router) == before
    router.shutdown()


def test_canary_killed_mid_verify_aborts_clean(engine):
    """Losing the canary replica during the replay is a gate breach,
    not a crash: clean rollback, fleet unchanged, still serving."""
    router = build_fleet(engine, _fleet_cfg(replicas=2))
    _warm(router, max_new=8)
    before = _live(router)
    ctl = router.start_rollout(
        engine.with_params(engine.params, engine.weights_version))
    assert ctl.phase == "canary"
    router.kill(ctl._canary_name)
    _run_rollout(router, ctl, max_steps=50)
    assert ctl.phase == "rolled_back"
    assert "canary replica lost" in ctl.failure
    assert router.metrics.rollbacks == 1
    assert _live(router) == before
    _warm(router, n=2, seed=17)            # fleet still serves
    router.shutdown()


# ----------------------------------------------------- failover overlap

def test_vprev_death_mid_rollout_fails_over_exactly_once(engine):
    """A vPrev replica dying while the rollout runs: its in-flight
    requests fail over (PR-8 path) and every streamed position is
    delivered exactly once; the rollout still completes and the fleet
    returns to its original size."""
    router = build_fleet(engine, _fleet_cfg(replicas=2))
    _warm(router)
    prompts = _prompts((6, 9, 6, 9), seed=31)
    streamed = {i: [] for i in range(len(prompts))}
    fids = [router.submit(p, SamplingParams(max_new_tokens=8),
                          on_token=lambda r, t, i=i: streamed[i].append(t))
            for i, p in enumerate(prompts)]
    for _ in range(3):                     # requests mid-stream
        router.step()
    ctl = router.start_rollout(
        engine.with_params(engine.params, engine.weights_version))
    victim = next(router.result(f).replica for f in fids
                  if router.result(f).replica is not None)
    assert victim not in ctl.spawned       # a vPrev member, mid-stream
    router.kill(victim)
    _run_rollout(router, ctl)
    router.run_until_idle()
    assert router.metrics.failovers == 1
    assert ctl.phase == "done"
    assert router.version_skew()["skew"] == 0
    assert len(_live(router)) == 2
    for i, fid in enumerate(fids):
        fr = router.result(fid)
        assert fr.state == "finished", fr.failed_reason
        ref = np.asarray(
            engine.generate(prompts[i][None], max_new_tokens=8))[0]
        np.testing.assert_array_equal(fr.output_ids, ref)
        assert streamed[i] == list(ref[len(prompts[i]):])  # exactly once
    router.shutdown()


# ------------------------------------------------------------- refusals

def test_start_rollout_refusals(engine):
    """Disaggregated fleets, disabled configs, and concurrent rollouts
    are refused up front — never half-started."""
    view = engine.with_params(engine.params, engine.weights_version)
    router = build_fleet(engine, _fleet_cfg(
        {"num_slots": 3}, replicas=2,
        prefill_replicas=1, decode_replicas=1))
    with pytest.raises(RuntimeError, match="unified"):
        router.start_rollout(view)
    router.shutdown()

    router = build_fleet(engine, _fleet_cfg(replicas=2))
    with pytest.raises(RuntimeError, match="refused"):
        router.start_rollout(view, config=RolloutConfig(enabled=False))
    ctl = router.start_rollout(view)
    with pytest.raises(RuntimeError, match="already in progress"):
        router.start_rollout(view)
    ctl.abort("test teardown")
    assert ctl.phase == "rolled_back"
    router.shutdown()


# ------------------------------------------------------ gauges / panel

def test_rollout_gauges_live_and_retract(engine, tracer):
    """dstpu_rollout_* are first-class Prometheus series while a
    rollout exists and vanish with the router."""
    from deepspeed_tpu.telemetry import prometheus_dump
    router = build_fleet(engine, _fleet_cfg(replicas=2))
    _warm(router)
    ctl = router.start_rollout(
        engine.with_params(engine.params, engine.weights_version))
    _run_rollout(router, ctl)
    assert ctl.phase == "done"
    dump = prometheus_dump(tracer)
    assert "dstpu_rollout_shift_fraction 1.0" in dump
    assert "dstpu_rollout_version_skew 0.0" in dump
    assert "dstpu_rollout_rollbacks 0.0" in dump
    assert 'tag="rollout' not in dump      # dedicated, not generic
    router.shutdown()
    assert not any(t.startswith("rollout/") for t in tracer.counters())


def test_ds_tpu_top_renders_rollout_panel_and_degrades(tmp_path):
    """The rollout panel renders phase/shift-bar/verdict and the
    per-replica version column from a snapshot; a snapshot without the
    section renders no panel."""
    snap = {"counters": {}, "goodput": None, "sections": {
        "fleet": {"replica_table": {
            "r0": {"role": "unified", "state": "READY", "queue_depth": 0,
                   "active_requests": 1, "weights_version": 2},
            "r1": {"role": "unified", "state": "READY", "queue_depth": 2,
                   "active_requests": 0, "weights_version": 1}}},
        "rollout": {"phase": "shift", "active": True, "target_version": 2,
                    "shift_fraction": 0.5, "canary": "r2", "canary_n": 4,
                    "canary_verdict": "bitwise_identical",
                    "vnext_replicas": ["r0"], "version_skew": 1,
                    "rollouts": 0, "rollbacks": 0}}}
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_tpu_top"),
         "--once", "--snapshot", str(path)],
        capture_output=True, text=True, timeout=30)
    assert out.returncode == 0, out.stderr
    assert "rollout" in out.stdout and "shift" in out.stdout
    assert "bitwise_identical" in out.stdout
    assert "v=2" in out.stdout and "v=1" in out.stdout
    # degradation: pre-rollout snapshot -> no panel, no version column
    snap["sections"].pop("rollout")
    for row in snap["sections"]["fleet"]["replica_table"].values():
        row.pop("weights_version")
    path.write_text(json.dumps(snap))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_tpu_top"),
         "--once", "--snapshot", str(path)],
        capture_output=True, text=True, timeout=30)
    assert out.returncode == 0, out.stderr
    assert "rollout" not in out.stdout and "v=" not in out.stdout


# ------------------------------------------------------------ CLI smoke

def test_ds_tpu_rollout_cli_smoke(tmp_path):
    """bin/ds_tpu_rollout drives a live tiny-model rollout end to end
    and exits 0 with phase done at version skew 0; --abort forces a
    rollback mid-shift and exits 0 only when it lands rolled_back.
    (Both legs run concurrently — each is a separate process whose cost
    is dominated by interpreter + compile startup.)"""
    done_json = tmp_path / "done.json"
    abort_json = tmp_path / "abort.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    base = [sys.executable, os.path.join(REPO, "bin", "ds_tpu_rollout"),
            "--cpu", "--model", "tiny", "--fleet", "2", "--requests", "4",
            "--rate", "100", "--prompt-len", "8", "--max-new", "3",
            "--canary-n", "1"]
    procs = [subprocess.Popen(base + extra, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for extra in (["--json", str(done_json)],
                           ["--abort", "--json", str(abort_json)])]
    for p in procs:
        _, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
    doc = json.loads(done_json.read_text())
    assert doc["rollout"]["phase"] == "done"
    assert doc["rollout"]["canary_verdict"] == "bitwise_identical"
    assert doc["version_skew"]["skew"] == 0
    assert doc["requests"]["finished"] == doc["requests"]["total"]
    doc = json.loads(abort_json.read_text())
    assert doc["rollout"]["phase"] == "rolled_back"
    assert doc["rollout"]["rollbacks"] == 1
    assert doc["requests"]["finished"] == doc["requests"]["total"]
