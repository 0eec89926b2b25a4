"""Telemetry subsystem tests (deepspeed_tpu/telemetry/).

The contracts under test: spans nest and the ring buffer wraps without
growing; the Chrome trace-event export round-trips through JSON with valid
nesting and async request pairs; the recompile watchdog fires on a forced
shape change and ONLY then; comm spans carry byte/participant accounting;
serving requests leave a balanced queue→prefill→decode→complete span
lifecycle; a disabled tracer allocates no span objects; and the monitor
sink satellites (wandb batching, csv tag sanitization, timer mean)."""

import csv
import json
import os
from functools import partial

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu import comm as dist
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.telemetry import (chrome_trace, get_tracer,
                                     metrics_snapshot, prometheus_dump,
                                     write_chrome_trace)
from deepspeed_tpu.telemetry.trace import _NULL_SPAN, RecompileWatchdog

TINY = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                  n_head=4, pad_vocab_to_multiple=8)


@pytest.fixture
def tracer():
    """The global tracer, enabled and clean; restored after the test."""
    tr = get_tracer()
    prev_enabled, prev_sync = tr.enabled, tr.sync_spans
    tr.clear()
    tr.configure(enabled=True, buffer_size=4096, sync_spans=True)
    yield tr
    tr.clear()
    tr.configure(enabled=prev_enabled, sync_spans=prev_sync)


# ---------------------------------------------------------------- core tracer

def test_span_nesting_depth_and_order(tracer):
    with tracer.span("outer"):
        with tracer.span("mid"):
            with tracer.span("inner"):
                pass
        with tracer.span("mid2"):
            pass
    spans = {s.name: s for s in tracer.spans()}
    assert spans["outer"].depth == 0
    assert spans["mid"].depth == spans["mid2"].depth == 1
    assert spans["inner"].depth == 2
    # children close before parents -> recorded first
    names = [s.name for s in tracer.spans()]
    assert names.index("inner") < names.index("mid") < names.index("outer")
    # children are contained in the parent's interval
    out, inn = spans["outer"], spans["inner"]
    assert out.ts_us <= inn.ts_us
    assert inn.ts_us + inn.dur_us <= out.ts_us + out.dur_us + 1.0


def test_ring_buffer_wraparound(tracer):
    tracer.configure(buffer_size=16)
    for i in range(40):
        with tracer.span(f"s{i}"):
            pass
    spans = tracer.spans()
    assert len(spans) == 16          # never grows past capacity
    assert tracer.dropped == 24
    assert [s.name for s in spans] == [f"s{i}" for i in range(24, 40)]


def test_disabled_tracer_allocates_no_spans():
    tr = get_tracer()
    prev = tr.enabled
    tr.configure(enabled=False)
    try:
        before = len(tr.spans())
        a = tr.span("a")
        b = tr.span("b", cat="comm", args={"bytes": 1})
        # zero-cost contract: the SAME shared no-op object, not a new Span
        assert a is b is _NULL_SPAN
        with a as sp:
            sp.set(x=1)
            sp.sync_on(jnp.ones(1))
        tr.instant("i")
        tr.async_begin("r", 1)
        tr.async_end("r", 1)
        assert len(tr.spans()) == before
    finally:
        tr.configure(enabled=prev)


def test_counters_are_latest_value_gauges(tracer):
    tracer.set_counter("a", 1.0, 0)
    tracer.set_counter("a", 2.0, 1)
    tracer.set_counter("c", 3.0)
    assert tracer.counters()["a"] == (2.0, 1)
    assert tracer.counters()["c"] == (3.0, None)
    assert tracer.counter_value("a") == 2.0
    assert tracer.counter_value("missing", -1) == -1


# ------------------------------------------------------------- chrome export

def test_chrome_trace_round_trip(tracer, tmp_path):
    with tracer.span("parent"):
        with tracer.span("child", cat="train", args={"k": 1}):
            pass
    tracer.async_begin("request", 7, cat="serving")
    tracer.async_end("request", 7, cat="serving", args={"state": "finished"})
    path = str(tmp_path / "trace.json")
    write_chrome_trace(path, tracer)
    data = json.load(open(path))     # valid JSON round-trip
    evs = data["traceEvents"]
    x = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(x) == {"parent", "child"}
    for e in x.values():             # required trace-event fields
        assert {"ph", "ts", "dur", "pid", "tid", "cat"} <= set(e)
    # nesting survives export: child inside parent on the same tid
    assert x["child"]["tid"] == x["parent"]["tid"]
    assert x["parent"]["ts"] <= x["child"]["ts"]
    assert (x["child"]["ts"] + x["child"]["dur"] <=
            x["parent"]["ts"] + x["parent"]["dur"] + 1.0)
    b = [e for e in evs if e["ph"] == "b"]
    e_ = [e for e in evs if e["ph"] == "e"]
    assert len(b) == len(e_) == 1 and b[0]["id"] == e_[0]["id"]


def test_prometheus_dump_format(tracer):
    tracer.set_counter("serving/ttft_ms", 12.5)
    with tracer.span("fwd"):
        pass
    text = prometheus_dump(tracer)
    assert '# TYPE dstpu_metric gauge' in text
    assert 'dstpu_metric{tag="serving_ttft_ms"} 12.5' in text
    assert 'dstpu_span_count{name="fwd"} 1' in text
    # every sample line is "name{labels} value"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        assert len(line.rsplit(" ", 1)) == 2


# ---------------------------------------------------------------- comm spans

def test_comm_span_byte_accounting(tracer):
    from jax import shard_map
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P())
    def f(x):
        return dist.all_reduce(x, axis_name="data")

    x = jnp.ones((8, 4), jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x))[0], 8.0)
    spans = [s for s in tracer.spans() if s.cat == "comm"]
    assert len(spans) == 1           # recorded at trace time, once
    sp = spans[0]
    assert sp.args["op"] == "all_reduce"
    assert sp.args["bytes"] == 1 * 4 * 4   # per-shard payload [1, 4] f32
    assert sp.args["participants"] == 8
    assert sp.args["axis"] == "data"
    # and the snapshot's comm table aggregates it
    table = metrics_snapshot(tracer)["comm"]
    assert table["all_reduce"]["calls"] == 1
    assert table["all_reduce"]["bytes"] == 16
    # cached executions must not re-record
    f(x + 1)
    assert len([s for s in tracer.spans() if s.cat == "comm"]) == 1


# ------------------------------------------------------------ engine tracing

def _engine(config_over=None, seed=0):
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "telemetry": {"enabled": True, "peak_tflops_per_device": 1e-3},
    }
    cfg.update(config_over or {})
    model = GPT2Model(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine


def _batch(seqlen=16, gas=1, micro=8, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 255, size=(gas, micro, seqlen),
                                      dtype=np.int32)}


def test_train_batch_spans_and_step_counters(tracer):
    engine = _engine()
    for i in range(2):
        engine.train_batch(batch=_batch(seed=i))
    names = [s.name for s in tracer.spans()]
    assert names.count("train_batch") == 2
    assert names.count("dispatch") == 2
    counters = tracer.counters()
    assert "telemetry/step_time_ms" in counters
    assert counters["telemetry/step_time_ms"][0] > 0
    # MFU derived from the flops profiler (peak set tiny but nonzero)
    assert counters["telemetry/mfu"][0] > 0
    assert counters["telemetry/step_tflops"][0] > 0


def test_micro_api_nested_fwd_bwd_step_spans(tracer):
    engine = _engine()
    mb = {"input_ids": _batch()["input_ids"][0]}
    engine.forward(mb)
    engine.backward()
    metrics = engine.step()
    assert np.isfinite(float(metrics["grad_norm"]))
    spans = {s.name: s for s in tracer.spans()}
    assert {"fwd", "bwd", "step"} <= set(spans)
    # each phase carries a nested child span
    by_name = [s.name for s in tracer.spans()]
    assert "dispatch" in by_name       # inside fwd
    assert "accumulate" in by_name     # inside bwd
    assert "apply" in by_name          # inside step
    assert spans["fwd"].depth == 0
    assert {s.name: s.depth for s in tracer.spans()}["accumulate"] == 1


def test_recompile_watchdog_fires_on_shape_change(tracer):
    engine = _engine()
    engine.train_batch(batch=_batch(seqlen=16, seed=0))
    engine.train_batch(batch=_batch(seqlen=16, seed=1))
    # steady state: identical shapes, no recompile
    assert engine._watchdog.recompiles == 0
    assert "telemetry/recompiles" not in tracer.counters()
    # forced shape change -> new executable -> the watchdog fires
    engine.train_batch(batch=_batch(seqlen=8, seed=2))
    assert engine._watchdog.recompiles >= 1
    assert tracer.counters()["telemetry/recompiles"][0] >= 1
    assert any(s.name.startswith("recompile:") for s in tracer.spans())


def test_watchdog_handles_plain_functions():
    wd = RecompileWatchdog()
    assert wd.observe(lambda x: x) == 0   # no _cache_size: not watchable
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(2))
    assert wd.observe(f) == 0             # first sight = baseline
    f(jnp.ones(3))
    assert wd.observe(f) == 1
    assert wd.recompiles == 1


def test_export_interval_writes_files(tracer, tmp_path):
    trace_path = str(tmp_path / "t.json")
    snap_path = str(tmp_path / "s.json")
    engine = _engine({"telemetry": {
        "enabled": True, "export_interval": 2, "trace_output": trace_path,
        "snapshot_output": snap_path, "peak_tflops_per_device": 1e-3}})
    for i in range(2):
        engine.train_batch(batch=_batch(seed=i))
    assert os.path.exists(trace_path) and os.path.exists(snap_path)
    snap = json.load(open(snap_path))
    assert snap["global_steps"] == 2
    assert "train_batch" in snap["spans"]
    assert "telemetry/mfu" in snap["counters"]


# ---------------------------------------------------------- serving lifecycle

@pytest.fixture(scope="module")
def infer_engine():
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=1,
                                 dtype="float32"))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def test_serving_request_span_lifecycle(tracer, infer_engine):
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    srv = ServingEngine(infer_engine, {"num_slots": 2, "max_model_len": 64})
    rng = np.random.default_rng(0)
    rids = [srv.submit(rng.integers(0, 128, (4,), dtype=np.int32),
                       SamplingParams(max_new_tokens=3)) for _ in range(3)]
    srv.run_until_idle()
    spans = tracer.spans()
    for name in ("request", "request/queued", "request/decode"):
        begins = [s for s in spans if s.name == name and s.ph == "b"]
        ends = [s for s in spans if s.name == name and s.ph == "e"]
        assert len(begins) == len(ends) == 3, name
        assert sorted(s.aid for s in begins) == sorted(rids)
    done = {s.aid: s.args for s in spans
            if s.name == "request" and s.ph == "e"}
    for rid in rids:
        assert done[rid]["state"] == "finished"
        assert done[rid]["tokens"] == 3
        assert done[rid]["ttft_ms"] > 0
    # sync host spans for the device work
    assert any(s.name == "prefill" and s.args["prompt_len"] == 4
               for s in spans)
    assert any(s.name == "decode_step" for s in spans)


def test_serving_cancel_closes_spans(tracer, infer_engine):
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    srv = ServingEngine(infer_engine, {"num_slots": 1, "max_model_len": 64})
    rids = [srv.submit(np.ones(4, np.int32), SamplingParams(max_new_tokens=2))
            for _ in range(3)]
    assert srv.cancel(rids[-1])      # still queued: cancellable
    srv.run_until_idle()
    begins = sum(1 for s in tracer.spans()
                 if s.name == "request" and s.ph == "b")
    ends = sum(1 for s in tracer.spans()
               if s.name == "request" and s.ph == "e")
    assert begins == ends == 3       # cancelled request's span closed too


def test_serving_metrics_ride_telemetry_pipeline(tracer):
    from deepspeed_tpu.serving.metrics import ServingMetrics

    class FakeMonitor:
        def __init__(self):
            self.batches = []

        def write_events(self, events):
            self.batches.append(list(events))

    mon = FakeMonitor()
    m = ServingMetrics(monitor=mon, monitor_interval=1, tracer=tracer)
    m.record_tick(queue_depth=3, slot_utilization=0.5)
    m.record_ttft(0.010)
    # gauges visible in the snapshot BEFORE any flush — one gauge space
    assert tracer.counters()["serving/queue_depth"][0] == 3
    m.flush()
    flat = [e for b in mon.batches for e in b]
    assert ("serving/queue_depth", 3.0, 1) in flat
    assert any(t == "serving/ttft_ms" for t, _, _ in flat)
    m.flush()
    assert len([e for b in mon.batches for e in b]) == len(flat)  # drained


def test_serving_metrics_events_isolated_per_engine(tracer):
    """Two metrics instances in one process: a monitor-less engine's
    events must never surface in another engine's monitor (the event
    queue is per-instance, only the gauges are global)."""
    from deepspeed_tpu.serving.metrics import ServingMetrics

    class FakeMonitor:
        def __init__(self):
            self.batches = []

        def write_events(self, events):
            self.batches.append(list(events))

    orphan = ServingMetrics(monitor=None, monitor_interval=1, tracer=tracer)
    for _ in range(5):
        orphan.record_ttft(0.5)      # no monitor: nowhere to flush to
    mon = FakeMonitor()
    m = ServingMetrics(monitor=mon, monitor_interval=1, tracer=tracer)
    m.record_ttft(0.010)
    m.flush()
    flat = [e for b in mon.batches for e in b]
    assert flat == [("serving/ttft_ms", 10.0, 0)]   # none of orphan's 5
    # but the orphan's gauge is still globally visible
    assert tracer.counters()["serving/ttft_ms"][0] == 10.0


# ------------------------------------------------------- monitor sink fixes

class _SinkCfg:
    def __init__(self, **kw):
        self.enabled = True
        self.output_path = ""
        self.job_name = "job"
        self.project = self.group = self.team = None
        self.__dict__.update(kw)


def test_wandb_batches_same_step_tags():
    from deepspeed_tpu.monitor.monitor import WandbMonitor

    class FakeWandb:
        def __init__(self):
            self.calls = []

        def log(self, payload, step=None):
            self.calls.append((dict(payload), step))

    m = WandbMonitor(_SinkCfg(enabled=False))
    m._wandb = FakeWandb()
    m.write_events([("a", 1.0, 5), ("b", 2.0, 5), ("c", 3.0, 6),
                    ("d", 4.0, 5)])
    # ONE network call per step, not one per event
    assert len(m._wandb.calls) == 2
    assert m._wandb.calls[0] == ({"a": 1.0, "b": 2.0, "d": 4.0}, 5)
    assert m._wandb.calls[1] == ({"c": 3.0}, 6)


def test_csv_tag_sanitization_and_collision_guard(tmp_path):
    from deepspeed_tpu.monitor.monitor import CsvMonitor
    m = CsvMonitor(_SinkCfg(output_path=str(tmp_path)))
    hostile = ["Train/Samples/lr", "a b:c", "../../../etc/passwd",
               "t*q?<>|", "a b?c"]   # last two collide after sanitizing
    m.write_events([(t, 1.0, 0) for t in hostile])
    m.close()
    names = sorted(os.listdir(tmp_path / "job"))
    assert len(names) == len(hostile)          # collision guard: no merge
    for n in names:
        stem = n[:-len(".csv")]
        assert not set(stem) & set(' :*?<>|/'), n
        assert not stem.startswith("."), n     # no path climbing
    for n in names:                            # every file actually wrote
        rows = list(csv.reader(open(tmp_path / "job" / n)))
        assert rows == [["0", "1.0"]]


def test_csv_same_tag_reuses_file(tmp_path):
    from deepspeed_tpu.monitor.monitor import CsvMonitor
    m = CsvMonitor(_SinkCfg(output_path=str(tmp_path)))
    m.write_events([("x/y", 1.0, 0), ("x/y", 2.0, 1)])
    m.close()
    assert os.listdir(tmp_path / "job") == ["x_y.csv"]
    rows = list(csv.reader(open(tmp_path / "job" / "x_y.csv")))
    assert rows == [["0", "1.0"], ["1", "2.0"]]


def test_prometheus_monitor_sink(tmp_path, tracer):
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    class Cfg:
        tensorboard = _SinkCfg(enabled=False)
        wandb = _SinkCfg(enabled=False)
        csv_monitor = _SinkCfg(enabled=False)
        prometheus = _SinkCfg(output_path=str(tmp_path), job_name="run")

    master = MonitorMaster(Cfg())
    assert master.enabled            # the fourth sink alone enables it
    master.write_events([("loss", 0.5, 10)])
    master.close()
    text = open(tmp_path / "run.prom").read()
    assert 'dstpu_metric{tag="loss"} 0.5' in text
    # sink mirrors into gauges
    assert tracer.counters()["loss"] == (0.5, 10)


# ------------------------------------------------------------- timer fixes

def test_timer_mean_includes_in_flight(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod
    now = [0.0]
    monkeypatch.setattr(timer_mod.time, "perf_counter", lambda: now[0])
    t = timer_mod._Timer("t")
    assert t.mean() == 0.0           # never started: no ZeroDivision
    t.start()
    now[0] = 2.0
    # in-flight time counts, like elapsed()
    assert t.mean() == pytest.approx(2.0)
    t.stop()
    assert t.mean() == pytest.approx(2.0)
    t.start()
    now[0] = 6.0
    assert t.mean() == pytest.approx(3.0)    # (2 + 4) / 2


def test_throughput_timer_start_step_guard(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod
    now = [0.0]
    monkeypatch.setattr(timer_mod.time, "perf_counter", lambda: now[0])
    t = timer_mod.ThroughputTimer(batch_size=4, start_step=0,
                                  steps_per_output=0)
    t.start()
    now[0] = 2.0
    t.stop(global_step=True)         # first accumulated step (global=1)
    # exactly one 2s step of 4 samples: 2 samples/s (the old off-by-one
    # counted 2 steps here and reported double)
    assert t.avg_samples_per_sec() == pytest.approx(2.0)
    t.start()
    now[0] = 4.0
    t.stop(global_step=True)
    assert t.avg_samples_per_sec() == pytest.approx(2.0)


def test_throughput_timer_default_start_step_unchanged(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod
    now = [0.0]
    monkeypatch.setattr(timer_mod.time, "perf_counter", lambda: now[0])
    t = timer_mod.ThroughputTimer(batch_size=8, start_step=2,
                                  steps_per_output=0)
    for _ in range(2):               # warmup steps are excluded
        t.start()
        now[0] += 100.0
        t.stop(global_step=True)
    assert t.avg_samples_per_sec() == pytest.approx(8.0 / 100.0)
    t.start()
    now[0] += 1.0
    t.stop(global_step=True)
    assert t.avg_samples_per_sec() == pytest.approx(2 * 8.0 / 101.0)
