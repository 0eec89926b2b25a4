"""The program's phase records on the device trace's clock
(``chipbench/layer_metrics/_program_spans.py``): the alignment on made-up
spans and records, the idle table on the recorded slice of a real trace, and
traced rehearsals of both cells that print every metric the records feed."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as T                                # noqa: E402
from chipbench.layer_metrics import _program_spans as P         # noqa: E402

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "train_z1_slice.json.gz")
OFFSET_NS = 21_700_000_000_123      # the host clock's lead over the trace's
NS = 1_000_000_000


def made_up(n_before=5, n=40, n_after=7, tick_ns=125_000_000):
    """``n`` window ticks with spans 3 us around them, a tenth of them 28 ms
    longer (a prefill), ``n_before`` warm-up ticks, a 2 s gap (the profiler
    starting), the window, 3 s (it stopping), ``n_after`` drain ticks. Every
    tick holds an admit and a wait; returns (spans, records)."""
    spans, records, t = [], [], 0
    for i in range(n_before + n + n_after):
        if i == n_before:
            t += 2 * NS
        if i == n_before + n:
            t += 3 * NS
        dur = tick_ns + (28_000_000 if i % 10 == 3 else 0) + 137 * (i % 7)
        records.append(("serve/admit", t + 1_000, t + 9_000, 0, 0))
        records.append(("serve/decode_wait", t + 20_000, t + dur - 5_000, 0, 0))
        records.append(("serve/tick", t, t + dur, i + 1, 3))
        if n_before <= i < n_before + n:
            spans.append(((t - 3_000 - OFFSET_NS) / NS,
                          (t + dur + 3_000 - OFFSET_NS) / NS))
        t += dur + 40_000
    return spans, records


def test_alignment_recovers_the_offset_among_extra_records():
    spans, records = made_up()
    logged = []
    placed = P.place(spans, records, 0, "serve/tick", logged.append)
    assert placed is not None and len(placed.units) == len(spans) == 40
    assert [u[3] for u in placed.units] == list(range(6, 46))   # tick numbers
    assert placed.residual_s < 1e-9      # every tick inside its span
    for (s, e), u in zip(spans, placed.units):
        assert u[0] - s == pytest.approx(3e-6, abs=2e-7)
        assert e - u[1] == pytest.approx(3e-6, abs=2e-7)
    assert len(placed.phases) == len(records)
    assert "aligned at 5 of 52" in logged[0] and "phases_dropped 0" in logged[0]
    # the phases inside a unit, and self times that add up to it
    inside = placed.inside(placed.units[0])
    assert [p[2] for p in inside] == ["serve/admit", "serve/decode_wait"]
    split = placed.self_seconds(placed.units[0])
    assert sum(split.values()) == pytest.approx(
        placed.units[0][1] - placed.units[0][0], abs=1e-12)
    assert split["serve/admit"] == pytest.approx(8e-6, abs=1e-9)


def test_alignment_holds_where_every_tick_takes_the_same_time():
    """Equal ticks leave the shift to the gaps the profiler's start and stop
    make around the window."""
    spans, records = made_up(n_before=4, n=14, n_after=0)
    records = [(n, t0, t1 - (t1 - t0) % 1000 if n == "serve/tick" else t1,
                a, b) for n, t0, t1, a, b in records]
    placed = P.place(spans, records, 0, "serve/tick")
    assert [u[3] for u in placed.units] == list(range(5, 19))


@pytest.mark.parametrize("case", ["count", "drop", "disagree", "extra_unit"])
def test_alignment_refuses(case):
    spans, records = made_up()
    dropped = 0
    if case == "count":                   # fewer records than spans
        ticks = [r for r in records if r[0] == "serve/tick"]
        records = [r for r in records if r not in ticks[:20]]
    elif case == "drop":                  # the ring lost part of the window
        records = records[3 * 10:]
        dropped = 30
    elif case == "disagree":              # one tick 1 ms longer than its span
        i = records.index([r for r in records if r[0] == "serve/tick"][20])
        n, t0, t1, a, b = records[i]
        records[i] = (n, t0, t1 + 1_000_000, a, b)
    elif case == "extra_unit":            # a tick in the window without a span
        del spans[17]
    logged = []
    assert P.place(spans, records, dropped, "serve/tick", logged.append) is None
    assert logged and logged[-1].startswith("phase records:")


def test_a_drop_before_the_window_is_no_reason_to_refuse():
    spans, records = made_up()
    assert P.place(spans, records[3 * 2:], 6, "serve/tick") is not None


def test_idle_table_adds_up_to_the_idle_time_of_the_recorded_slice():
    """Device 0 of ``gpt2-medium.train-z1`` on a TPU v5e (PR 26) holds one
    whole ``train_batch`` span; made-up records put a ``train/step`` inside
    it with its phases, a ``gc`` inside the read-back over the span's longest
    idle gap (2.79 ms from 427.3 ms on), and steps before and after. Every
    idle moment lands under one name."""
    trace = T.load(SLICE)
    (s, e, _), = [x for x in trace.spans if x[2] == "train_batch"]
    ns = lambda sec: int(round(sec * NS)) + OFFSET_NS
    t0, t1 = ns(s) + 2_000, ns(e) - 2_000
    records = [
        ("train/step", t0 - 900_000_000, t0 - 470_000_000, 3, 0),
        ("train/input", t0 + 1_000, t0 + 300_000, 0, 0),
        ("train/dispatch", t0 + 400_000, t0 + 1_500_000, 0, 0),
        ("gc", t0 + 427_000_000, t0 + 429_500_000, 2, 51),
        ("train/readback", t0 + 1_600_000, t1 - 50_000, 0, 0),
        ("train/post", t1 - 40_000, t1 - 1_000, 0, 0),
        ("train/step", t0, t1, 4, 0),
        ("train/step", t1 + 600_000_000, t1 + 1_030_000_000, 5, 0)]
    placed = P.place([(s, e)], records, 0, "train/step")
    assert placed is not None and [u[3] for u in placed.units] == [4]
    idle = P.idle_by_phase(trace, P.idle_by_unit(trace, placed))
    total_idle = trace.window_s - trace.busy_s(0)
    assert sum(idle.values()) == pytest.approx(total_idle, abs=1e-9)
    assert set(idle) == {"outside", "train/step", "train/input",
                         "train/dispatch", "train/readback", "train/post",
                         "gc"}
    assert all(v >= 0 for v in idle.values())
    # under the span the benchmark's own table says the same, less the 4 us
    # between the span's ends and the step's
    assert sum(v for k, v in idle.items() if k != "outside") == \
        pytest.approx(trace.idle_by_span(0)["train_batch"], abs=1e-5)
    assert idle["gc"] == pytest.approx(0.429502 - 0.427318184, abs=1e-6)


def run(cell, seconds):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 9), "--seconds", seconds,
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, seconds", [("opt-1.3b.serve-chat", "3"),
                                           ("gpt2-medium.train-z1", "2")])
def test_traced_rehearsal_prints_every_metric_the_records_feed(cell, seconds):
    """Every per-layer metric of the cell whose source is the program's
    records is in the result line; the log holds the alignment (residual
    within the limit, nothing dropped), the idle table, which adds up to the
    window less the busy time, and the three slowest ticks or steps."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = set(P_METRICS[cell])
    assert mine <= {m["name"] for m in manifest["per_layer"]
                    if cell in m.get("workloads", [])}
    out, last = run(cell, seconds)
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert mine <= set(last["metrics"])
    for name in mine:
        assert last["metrics"][name]["value"] >= 0
    lines = out.splitlines()
    aligned, = [x for x in lines if "records aligned at" in x]
    assert "phases_dropped 0" in aligned
    residual_us = float(aligned.split("residual ")[1].split(" us")[0])
    assert residual_us <= P.TOLERANCE_S * 1e6
    table, = [x for x in lines if "idle seconds by program phase: " in x]
    idle = json.loads(table.split("phase: ", 1)[1])
    dev = last["device"]
    assert sum(idle.values()) == pytest.approx(
        dev["window_s"] - dev["busy_s"], rel=5e-3)
    assert len([x for x in lines if "] slowest " in x and "self ms by" in x]) == 3


P_METRICS = {
    "opt-1.3b.serve-chat": ["tick_host_ms", "queue_wait_ms",
                            "tick_prefill_ms", "prefill_pad_share",
                            "idle_prefill_ms", "idle_tick_ms"],
    "gpt2-medium.train-z1": ["step_host_ms", "idle_step_ms"]}
