"""Telemetry overhead benchmark: tracer-on vs tracer-off step time.

Runs the same tiny-GPT2 `train_batch` loop five times — telemetry
disabled; enabled (spans + MFU counters + recompile watchdog + ring
buffer); enabled WITH the goodput ledger and the statusz server (an HTTP
thread parked on a live port); the full observability plane PLUS the
flight recorder (per-step ring records + trigger rules armed, no trigger
firing); and all of that PLUS the compile plane (per-step argument
fingerprints, the HBM role ledger, the overlap analyzer) — and writes
benchmarks/telemetry_overhead.json with median step times and the
relative overheads. Asserts every enabled mode costs < 2% of step time
(the low-overhead contract of deepspeed_tpu/telemetry/).

A sixth interleaved comparison, "dt", covers the serving plane: two
identical 2-replica fleets run the same request rounds, one with every
instrument dark, one with distributed tracing + fleet aggregation armed
(span stamping with trace args, per-request critical-path marks, the
router aggregator folding completed paths into dstpu_fleet_path_*
gauges, flight recorder recording every tick) — and asserts the armed
fleet's median decode tick stays < 2% slower.

An eighth interleaved comparison, "cost", isolates the cost plane: two
identical single-replica serving stacks run the same request rounds,
one with per-request chip-second attribution dark (``cost.enabled``
false — the scheduler holds ``None`` and every hook is one ``is None``
test), one with the CostLedger armed (per-tick weighted decode splits,
prefill charges, HBM residency, the overhead residual) — and asserts
the armed stack's median decode tick stays < 2% slower.

Both loops block on the loss every step, so the comparison isolates the
tracer's span machinery from the device sync it performs by design
(`sync_spans` would otherwise make the "on" loop LOOK slower merely by
measuring honestly).

Runs on CPU: JAX_PLATFORMS=cpu python benchmarks/telemetry_overhead.py
Knobs (env): TEL_STEPS, TEL_WARMUP, TEL_LAYERS, TEL_EMBD, TEL_SEQ,
TEL_THRESHOLD_PCT.
"""

import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu") or \
        os.environ.get("DSTPU_ACCELERATOR", "").lower() == "cpu":
    import importlib.util
    _spec = importlib.util.spec_from_file_location(
        "_dstpu_hermetic",
        os.path.join(REPO, "deepspeed_tpu", "utils", "hermetic.py"))
    _hermetic = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_hermetic)
    _hermetic.force_cpu()

import jax  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model  # noqa: E402
from deepspeed_tpu.telemetry import get_tracer  # noqa: E402

STEPS = int(os.environ.get("TEL_STEPS", 30))
WARMUP = int(os.environ.get("TEL_WARMUP", 5))
THRESHOLD_PCT = float(os.environ.get("TEL_THRESHOLD_PCT", 2.0))


def build_engine(telemetry_enabled: bool, full: bool = False,
                 recorder_dir: str = "", compile_plane: bool = False,
                 elastic: bool = False):
    model = GPT2Model(GPT2Config(
        vocab_size=256, n_positions=128,
        n_embd=int(os.environ.get("TEL_EMBD", 128)),
        n_layer=int(os.environ.get("TEL_LAYERS", 4)),
        n_head=4, pad_vocab_to_multiple=8))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": jax.device_count() * 2,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "telemetry": {"enabled": telemetry_enabled,
                      # measure span machinery, not the one-time step trace
                      # the MFU counter needs
                      "mfu": False,
                      # the ledger rides telemetry.enabled; the "on" loop
                      # isolates the tracer, the "full" loop adds it back
                      "goodput": full},
        # full mode: a live introspection server parked on an ephemeral
        # loopback port while the loop runs
        "statusz": {"enabled": full, "port": 0},
        # rec mode: the flight recorder ring + trigger rules, with the
        # slow-step threshold parked high so no trigger fires — the cost
        # under measurement is recording, not capture
        "flight_recorder": {"enabled": bool(recorder_dir),
                            "dir": recorder_dir or "unused",
                            "slow_step_factor": 1000.0},
        # cp mode: the compile/memory plane — per-step arg fingerprints,
        # the HBM role ledger, the overlap analyzer, at their default
        # cadences. Compile events only happen during warmup; what this
        # measures is the steady-state fingerprint + ledger cost.
        "compile_plane": {"enabled": compile_plane},
        # el mode: hostagg heartbeats EVERY step (worst-case cadence)
        # feeding a dark ElasticCoordinator — one gather + one dict
        # inspection per step when no host is missing
        "hostagg": {"enabled": elastic, "interval": 1},
        "elasticity": {"enabled": elastic,
                       "ignore_non_elastic_batch_info": True},
    })
    return engine


def _apply_mode(telemetry_enabled: bool, full: bool):
    """The tracer and the ledger are process-global; re-assert a mode
    before its block (the last-built engine's config would otherwise win
    for every engine)."""
    from deepspeed_tpu.telemetry import configure_ledger, get_tracer
    get_tracer().configure(enabled=telemetry_enabled)
    configure_ledger(enabled=full)


def run_block(engine, n_steps: int, collect=None):
    seq = int(os.environ.get("TEL_SEQ", 64))
    rng = np.random.default_rng(0)
    for _ in range(n_steps):
        batch = {"input_ids": rng.integers(
            0, 255, size=(1, engine.train_batch_size, seq), dtype=np.int32)}
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=batch)
        jax.block_until_ready(loss)      # every mode pays the sync
        dt = time.perf_counter() - t0
        if collect is not None:
            collect.append(dt)


def _dt_mode():
    """The "dt" comparison: identical serving fleets, observability dark
    vs distributed tracing + aggregation + flight recorder armed. The
    measured unit is the fused decode TICK (median over interleaved
    rounds), the serving analogue of the training modes' step — at a
    realistic tick size, like the training loop's ~20ms step, so the
    per-tick fixed cost of the armed plane is compared against real
    work, not against an artificially tiny model. Returns
    (off_ms_p50, dt_ms_p50, overhead_pct, requests)."""
    import tempfile
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serving import SamplingParams, build_fleet
    from deepspeed_tpu.telemetry import configure_ledger, get_tracer

    rounds = int(os.environ.get("TEL_DT_ROUNDS", 5))
    per_round = int(os.environ.get("TEL_DT_REQUESTS", 8))
    max_new = int(os.environ.get("TEL_DT_NEW", 48))
    model = GPT2Model(GPT2Config(
        vocab_size=256, n_positions=96,
        n_embd=int(os.environ.get("TEL_DT_EMBD", 256)),
        n_layer=int(os.environ.get("TEL_DT_LAYERS", 4)),
        n_head=4, pad_vocab_to_multiple=1, dtype="float32"))
    engine = ds.init_inference(model, config={"dtype": "float32"})
    rec_dir = tempfile.mkdtemp(prefix="dstpu_overhead_dt_")
    base = {"num_slots": per_round, "max_model_len": 96,
            "max_queue": per_round + 1,
            "max_prefills_per_tick": per_round}
    routers = {
        "off": build_fleet(engine, {
            **base, "telemetry": {"enabled": False},
            "fleet": {"enabled": True, "replicas": 2, "disttrace": False,
                      "heartbeat_timeout_s": 600.0}}),
        "dt": build_fleet(engine, {
            **base, "telemetry": {"enabled": True, "mfu": False},
            "flight_recorder": {"enabled": True, "dir": rec_dir,
                                "slow_step_factor": 1000.0},
            "fleet": {"enabled": True, "replicas": 2, "disttrace": True,
                      "heartbeat_timeout_s": 600.0}}),
    }
    modes = {"off": (False, False), "dt": (True, True)}
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, (12,), dtype=np.int32)
               for _ in range(per_round)]

    def run_round(router, ticks):
        fids = [router.submit(p, SamplingParams(max_new_tokens=max_new))
                for p in prompts]
        while True:
            t0 = time.perf_counter()
            n = router.step()
            if ticks is not None:
                ticks.append(time.perf_counter() - t0)
            if not n:
                break
        assert all(router.result(f).state == "finished" for f in fids)

    ticks = {name: [] for name in routers}
    for name, router in routers.items():          # compile + warmup
        _apply_mode(*modes[name])
        run_round(router, None)
    for _ in range(rounds):                        # interleaved rounds
        for name, router in routers.items():
            _apply_mode(*modes[name])
            run_round(router, ticks[name])
    _apply_mode(True, True)
    agg = routers["dt"].aggregator
    assert agg is not None and agg.observed >= rounds * per_round
    assert routers["off"].aggregator is None      # dark fleet built none
    assert agg.critical_path_summary()["stages"]["prefill"]["n"] > 0
    for router in routers.values():
        router.shutdown()
    configure_ledger(enabled=False)
    get_tracer().configure(enabled=False)
    off_ms = statistics.median(ticks["off"]) * 1e3
    dt_ms = statistics.median(ticks["dt"]) * 1e3
    return off_ms, dt_ms, 100.0 * (dt_ms - off_ms) / off_ms, \
        rounds * per_round


def _cost_mode():
    """The "cost" comparison: identical single-replica serving stacks,
    cost plane dark vs armed. The armed stack pays the per-tick
    attribution work — the weighted decode split over active slots, the
    HBM residency accrual, the overhead residual bookkeeping — on every
    fused decode tick; the dark stack's scheduler holds ``None``.
    Returns (dark_ms_p50, cost_ms_p50, overhead_pct, requests)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.serving import SamplingParams, ServingEngine

    rounds = int(os.environ.get("TEL_COST_ROUNDS", 5))
    per_round = int(os.environ.get("TEL_COST_REQUESTS", 8))
    max_new = int(os.environ.get("TEL_COST_NEW", 48))
    model = GPT2Model(GPT2Config(
        vocab_size=256, n_positions=96,
        n_embd=int(os.environ.get("TEL_COST_EMBD", 256)),
        n_layer=int(os.environ.get("TEL_COST_LAYERS", 4)),
        n_head=4, pad_vocab_to_multiple=1, dtype="float32"))
    engine = ds.init_inference(model, config={"dtype": "float32"})
    base = {"num_slots": per_round, "max_model_len": 96,
            "max_queue": per_round + 1,
            "max_prefills_per_tick": per_round,
            "telemetry": {"enabled": True, "mfu": False}}
    servers = {
        "dark": ServingEngine(engine, {**base,
                                       "cost": {"enabled": False}}),
        "cost": ServingEngine(engine, {**base,
                                       "cost": {"enabled": True}}),
    }
    assert servers["dark"].scheduler.cost is None
    assert servers["cost"].scheduler.cost is not None
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, (12,), dtype=np.int32)
               for _ in range(per_round)]
    sp = SamplingParams(max_new_tokens=max_new)

    def run_round(srv, ticks):
        for p in prompts:
            srv.submit(p, sp)
        while srv.queue_depth or srv.active_requests:
            t0 = time.perf_counter()
            srv.step()
            if ticks is not None:
                ticks.append(time.perf_counter() - t0)

    ticks = {name: [] for name in servers}
    for srv in servers.values():                   # compile + warmup
        run_round(srv, None)
    for _ in range(rounds):                        # interleaved rounds
        for name, srv in servers.items():
            run_round(srv, ticks[name])
    snap = servers["cost"].scheduler.cost.snapshot()
    # the armed ledger attributed every round and conserved wall-clock
    assert snap["tenants"]["default"]["tokens"] >= \
        rounds * per_round * max_new
    attributed_s = snap["attributed_ms"] / 1e3
    assert abs(attributed_s + snap["overhead_s"] -
               snap["serving_wall_s"]) <= 0.02 * snap["serving_wall_s"]
    for srv in servers.values():
        srv.shutdown()
    dark_ms = statistics.median(ticks["dark"]) * 1e3
    cost_ms = statistics.median(ticks["cost"]) * 1e3
    return dark_ms, cost_ms, 100.0 * (cost_ms - dark_ms) / dark_ms, \
        rounds * per_round


def main():
    import tempfile
    tracer = get_tracer()
    rec_dir = tempfile.mkdtemp(prefix="dstpu_overhead_rec_")
    cp_dir = tempfile.mkdtemp(prefix="dstpu_overhead_cp_")

    # one engine per mode; steps run in INTERLEAVED round-robin blocks so
    # machine drift (thermal, co-tenants) hits all modes equally —
    # sequential loops showed several % of drift, swamping the real cost
    modes = {"off": (False, False, "", False, False),
             "on": (True, False, "", False, False),
             "full": (True, True, "", False, False),
             "rec": (True, True, rec_dir, False, False),
             "cp": (True, True, cp_dir, True, False),
             "el": (True, True, "", False, True)}
    engines, times = {}, {name: [] for name in modes}
    for name, (tel, full, rdir, cp, el) in modes.items():
        engines[name] = build_engine(tel, full=full, recorder_dir=rdir,
                                     compile_plane=cp, elastic=el)
    assert engines["full"].statusz is not None and \
        engines["full"].statusz.port > 0
    assert engines["rec"]._recorder is not None
    assert engines["cp"]._compile_plane is not None and \
        engines["cp"]._hbm is not None
    assert engines["el"]._elastic is not None and \
        engines["el"]._hostagg is not None
    for name, (tel, full, _rdir, _cp, _el) in modes.items():  # warmup
        _apply_mode(tel, full)
        run_block(engines[name], WARMUP)

    block = max(1, STEPS // 7)
    done = 0
    while done < STEPS:
        n = min(block, STEPS - done)
        for name, (tel, full, _rdir, _cp, _el) in modes.items():
            _apply_mode(tel, full)
            run_block(engines[name], n, collect=times[name])
        done += n

    _apply_mode(True, True)
    assert len(tracer.spans()) > 0
    from deepspeed_tpu.telemetry.goodput import get_ledger
    assert get_ledger().snapshot()["buckets"]["productive_step"] > 0
    # the recorder recorded every step and — with no trigger firing —
    # wrote nothing to disk
    assert len(engines["rec"]._recorder._records) >= STEPS
    assert engines["rec"]._recorder.bundles() == []
    # the compile plane saw exactly the warmup compile, then went quiet
    cp_ledger = engines["cp"]._compile_plane
    assert cp_ledger.compiles >= 1 and cp_ledger.recompiles == 0
    # the dark coordinator aggregated every step and never latched
    el = engines["el"]
    assert el._hostagg.last is not None and not el._elastic.pending
    t_off, t_on = times["off"], times["on"]
    t_full, t_rec = times["full"], times["rec"]
    t_cp, t_el = times["cp"], times["el"]
    for engine in engines.values():
        engine.close()

    # dt mode: the serving plane with distributed tracing + aggregation
    # armed vs dark, interleaved the same way
    dt_off_ms, dt_ms, overhead_dt_pct, dt_requests = _dt_mode()

    # cost mode: the cost plane armed vs dark on the same serving
    # stack, interleaved the same way
    cost_off_ms, cost_ms, overhead_cost_pct, cost_requests = _cost_mode()

    off_ms = statistics.median(t_off) * 1e3
    on_ms = statistics.median(t_on) * 1e3
    full_ms = statistics.median(t_full) * 1e3
    rec_ms = statistics.median(t_rec) * 1e3
    cp_ms = statistics.median(t_cp) * 1e3
    el_ms = statistics.median(t_el) * 1e3
    overhead_pct = 100.0 * (on_ms - off_ms) / off_ms
    overhead_full_pct = 100.0 * (full_ms - off_ms) / off_ms
    overhead_rec_pct = 100.0 * (rec_ms - off_ms) / off_ms
    overhead_cp_pct = 100.0 * (cp_ms - off_ms) / off_ms
    overhead_el_pct = 100.0 * (el_ms - off_ms) / off_ms
    result = {
        "steps": STEPS,
        "step_ms_tracer_off_p50": round(off_ms, 4),
        "step_ms_tracer_on_p50": round(on_ms, 4),
        "step_ms_full_p50": round(full_ms, 4),
        "step_ms_recorder_p50": round(rec_ms, 4),
        "step_ms_compile_plane_p50": round(cp_ms, 4),
        "step_ms_tracer_off_mean": round(statistics.mean(t_off) * 1e3, 4),
        "step_ms_tracer_on_mean": round(statistics.mean(t_on) * 1e3, 4),
        "step_ms_full_mean": round(statistics.mean(t_full) * 1e3, 4),
        "step_ms_recorder_mean": round(statistics.mean(t_rec) * 1e3, 4),
        "step_ms_compile_plane_mean": round(statistics.mean(t_cp) * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "overhead_full_pct": round(overhead_full_pct, 3),
        "overhead_recorder_pct": round(overhead_rec_pct, 3),
        "overhead_compile_plane_pct": round(overhead_cp_pct, 3),
        "step_ms_elastic_p50": round(el_ms, 4),
        "overhead_elastic_pct": round(overhead_el_pct, 3),
        "serving_tick_ms_dark_p50": round(dt_off_ms, 4),
        "serving_tick_ms_disttrace_p50": round(dt_ms, 4),
        "overhead_disttrace_pct": round(overhead_dt_pct, 3),
        "disttrace_requests": dt_requests,
        "serving_tick_ms_cost_dark_p50": round(cost_off_ms, 4),
        "serving_tick_ms_cost_p50": round(cost_ms, 4),
        "overhead_cost_pct": round(overhead_cost_pct, 3),
        "cost_requests": cost_requests,
        "threshold_pct": THRESHOLD_PCT,
        "spans_recorded": len(tracer.spans()),
        "devices": jax.device_count(),
        "platform": jax.devices()[0].platform,
    }
    out = os.path.join(REPO, "benchmarks", "telemetry_overhead.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    assert overhead_pct < THRESHOLD_PCT, (
        f"telemetry overhead {overhead_pct:.2f}% exceeds the "
        f"{THRESHOLD_PCT}% budget")
    assert overhead_full_pct < THRESHOLD_PCT, (
        f"telemetry+ledger+statusz overhead {overhead_full_pct:.2f}% "
        f"exceeds the {THRESHOLD_PCT}% budget")
    assert overhead_rec_pct < THRESHOLD_PCT, (
        f"total observability overhead (tracer+ledger+statusz+flight "
        f"recorder) {overhead_rec_pct:.2f}% exceeds the "
        f"{THRESHOLD_PCT}% budget")
    assert overhead_cp_pct < THRESHOLD_PCT, (
        f"total observability overhead with the compile plane "
        f"(fingerprints + HBM ledger + overlap analyzer) "
        f"{overhead_cp_pct:.2f}% exceeds the {THRESHOLD_PCT}% budget")
    assert overhead_el_pct < THRESHOLD_PCT, (
        f"total observability overhead with per-step heartbeats + a "
        f"dark ElasticCoordinator {overhead_el_pct:.2f}% exceeds the "
        f"{THRESHOLD_PCT}% budget")
    assert overhead_dt_pct < THRESHOLD_PCT, (
        f"serving observability overhead with distributed tracing + "
        f"fleet aggregation armed {overhead_dt_pct:.2f}% exceeds the "
        f"{THRESHOLD_PCT}% budget")
    assert overhead_cost_pct < THRESHOLD_PCT, (
        f"cost-plane overhead (per-tick chip-second attribution + HBM "
        f"residency) {overhead_cost_pct:.2f}% exceeds the "
        f"{THRESHOLD_PCT}% budget")
    print(f"OK: tracer-on overhead {overhead_pct:.2f}%, + goodput "
          f"ledger + statusz server {overhead_full_pct:.2f}%, + flight "
          f"recorder {overhead_rec_pct:.2f}%, + compile plane "
          f"{overhead_cp_pct:.2f}%, + dark elastic coordinator "
          f"{overhead_el_pct:.2f}%, "
          f"serving fleet w/ distributed tracing {overhead_dt_pct:.2f}%, "
          f"cost plane {overhead_cost_pct:.2f}% — all < {THRESHOLD_PCT}%")


if __name__ == "__main__":
    main()
