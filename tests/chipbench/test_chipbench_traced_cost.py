"""What a traced run costs after its window: (a) the idle-overlap arithmetic
of the trace reduction against brute-force sums written here, on the recorded
slice of a real trace and on a made-up serving trace with nested spans and
phase records; (b) a trace of 1,000 ticks x 24 layers x 20 operations reduces
in seconds, under a time limit; (c) a traced rehearsal of each serving cell
closes its window at the cell's ``trace_ticks``, and the same rehearsal
untraced does not read the key."""

import contextlib
import functools
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace as T                                # noqa: E402
from chipbench.layer_metrics import _program_spans as P         # noqa: E402

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "train_z1_slice.json.gz")
OFFSET_NS = 21_700_000_000_123      # the host clock's lead over the trace's
REL = 1e-9


def serving_trace(n_ticks, layers, ops_a_layer, tick_s=0.02):
    """A made-up serving run on both clocks. Every tick is one ``step`` span
    around one ``serve/tick`` record with its phases; every fifth holds a
    prefill; between ticks a ``submit`` span inside a ``generator_sleep``
    one (nested). The device runs ``layers x ops_a_layer`` operations a tick
    with 1 us of idle time between them, from 0.6 ms after the tick begins;
    a ``while`` encloses the first half of them (nested: no idle time
    there); a prefill tick runs a second program first. Returns ``(trace,
    records)``."""
    ns = lambda s: int(round(s * 1e9)) + OFFSET_NS
    spans, records, ops, mods = [], [], [], []
    t = 1.0
    lo = t - 0.01
    for i in range(n_ticks):
        dur = tick_s * (1.0 + 0.1 * (i % 3))
        pf = 0.004 if i % 5 == 2 else 0.0
        spans.append((t - 3e-6, t + dur + pf + 3e-6, "step"))
        rec = lambda name, a, b, x=0, y=0: records.append(
            (name, ns(t + a), ns(t + b), x, y))
        rec("serve/admit", 5e-6, 40e-6, int(pf > 0), 0)
        if pf:
            rec("serve/prefill_prep", 50e-6, 900e-6, 300 + i, 512)
            rec("serve/prefill_dispatch", 900e-6, 1300e-6, 512)
            rec("serve/prefill_wait", 1300e-6, pf - 100e-6)
            rec("serve/first_token", pf - 100e-6, pf - 20e-6, i)
            ops.append((t + 1000e-6, t + pf - 300e-6, "fusion.pf"))
            mods.append((t + 1000e-6, t + pf - 300e-6, "jit_pf"))
        rec("serve/decode_prep", pf + 60e-6, pf + 400e-6, 3)
        rec("serve/decode_dispatch", pf + 400e-6, pf + 550e-6)
        rec("gc", pf + 565e-6, pf + 650e-6, 2, 17)     # inside the wait
        rec("serve/decode_wait", pf + 560e-6, pf + dur - 300e-6)
        rec("serve/deliver", pf + dur - 290e-6, pf + dur - 100e-6, 3, 0)
        rec("serve/bookkeeping", pf + dur - 90e-6, pf + dur - 10e-6)
        rec("serve/tick", 0.0, pf + dur, i + 1, 3)
        d0, d1 = t + pf + 600e-6, t + pf + dur - 1500e-6
        mods.append((d0, d1, "jit_dec"))
        n = layers * ops_a_layer
        each = (d1 - d0 - 100e-6) / n
        ops.append((d0, d0 + 50e-6 + n // 2 * each, "while.1"))
        for k in range(n):
            s = d0 + 50e-6 + k * each
            ops.append((s, s + each - 1e-6, f"fusion.{k % ops_a_layer}"))
        end = t + dur + pf
        spans.append((end + 5e-6, end + 30e-6, "generator_sleep"))
        spans.append((end + 10e-6, end + 20e-6, "submit"))
        t = end + 40e-6
    spans.append((lo, t + 0.01, "window"))
    trace = T.Trace([T.Device("/device:TPU:0", ops, mods)], sorted(spans))
    return trace, records


def brute_union_and_self(ops, lo, hi):
    """Busy seconds and ``{name: self seconds}`` inside ``[lo, hi)``, from
    every elementary segment between two neighbouring time points: busy
    where any operation covers it, given to the operation that started
    last."""
    s = np.array([x[0] for x in ops])
    e = np.array([x[1] for x in ops])
    names = [x[2] for x in ops]
    pts = np.unique(np.clip(np.concatenate([s, e, [lo, hi]]), lo, hi))
    busy, own = 0.0, {}
    for a, b in zip(pts[:-1], pts[1:]):
        mid = (a + b) / 2
        over = np.flatnonzero((s <= mid) & (e > mid))
        if len(over):
            busy += b - a
            last = over[s[over] == s[over].max()].max()
            own[names[last]] = own.get(names[last], 0.0) + (b - a)
    return busy, own


def brute_idle(ops, lo, hi):
    """The idle intervals of ``[lo, hi)`` by a plain merge."""
    cur, out = lo, []
    for s, e in sorted((max(a, lo), min(b, hi)) for a, b, _ in ops
                       if min(b, hi) > max(a, lo)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def brute_overlap(intervals, s, e):
    return sum(max(0.0, min(b, e) - max(a, s)) for a, b in intervals)


def close(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=REL, abs=1e-15), k


@pytest.fixture(scope="module")
def recorded():
    trace = T.load(SLICE)
    ops = trace.devices[0].ops
    trace.lo = min(s for s, _, _ in ops)
    trace.hi = max(e for _, e, _ in ops)
    return trace


@pytest.fixture(scope="module")
def made_up():
    trace, records = serving_trace(30, 2, 5)
    spans = [(s, e) for s, e, n in trace.spans if n == "step"]
    placed = P.place(spans, records, 0, "serve/tick")
    assert placed is not None and len(placed.units) == 30
    return trace, placed


@pytest.mark.parametrize("which", ["recorded", "made_up"])
def test_busy_and_self_seconds_against_every_elementary_segment(
        which, request):
    trace = request.getfixturevalue(which)
    trace = trace[0] if which == "made_up" else trace
    dev = trace.devices[0]
    busy, own = brute_union_and_self(dev.ops, trace.lo, trace.hi)
    assert trace.busy_s(0) == pytest.approx(busy, rel=REL)
    assert trace.busy_s() == trace.busy_s(0)         # one device: its own
    close(dev.op_self_seconds(trace.lo, trace.hi), own)
    assert dev.op_self_seconds(trace.lo, trace.hi) is \
        dev.op_self_seconds(trace.lo, trace.hi)      # kept, not made again
    # another window is another answer, not the kept one
    mid = (trace.lo + trace.hi) / 2
    half, own_half = brute_union_and_self(dev.ops, trace.lo, mid)
    assert T.total(dev.busy(trace.lo, mid)) == pytest.approx(half, rel=REL)
    close(dev.op_self_seconds(trace.lo, mid), own_half)


@pytest.mark.parametrize("which", ["recorded", "made_up"])
def test_idle_by_span_and_gaps_against_a_walk_over_every_idle_interval(
        which, request):
    trace = request.getfixturevalue(which)
    trace = trace[0] if which == "made_up" else trace
    idle = brute_idle(trace.devices[0].ops, trace.lo, trace.hi)
    got = trace.idle(0).intervals
    assert len(got) == len(idle)
    assert [t for x in got for t in x] == pytest.approx(
        [t for x in idle for t in x], rel=REL)
    pieces = T.self_events([x for x in trace.spans if x[2] != "window"])
    want = {"between_spans": sum(e - s for s, e in idle)}
    for s, e, name in pieces:
        c = brute_overlap(idle, s, e)
        if c:
            want[name] = want.get(name, 0.0) + c
            want["between_spans"] -= c
    close(trace.idle_by_span(0), want)
    if which == "made_up":
        assert {"step", "generator_sleep", "submit"} <= set(want)
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:10]:
        cover = {"between_spans": e - s}
        for ss, se, name in pieces:
            c = min(e, se) - max(s, ss)
            if c > 0:
                cover[name] = cover.get(name, 0.0) + c
                cover["between_spans"] -= c
        gaps.append((max(cover, key=cover.get), e - s))
    got = trace.gaps(0, top=10)
    assert [n for n, _ in got] == [n for n, _ in gaps]
    assert [v for _, v in got] == pytest.approx([v for _, v in gaps], rel=REL)


def test_idle_by_unit_against_a_walk_over_every_idle_interval(made_up):
    trace, placed = made_up
    idle = brute_idle(trace.devices[0].ops, trace.lo, trace.hi)
    per_unit = P.idle_by_unit(trace, placed)
    assert len(per_unit) == len(placed.units)
    for unit, got in zip(placed.units, per_unit):
        want = {}
        for s, e, name in placed.pieces(unit):
            c = brute_overlap(idle, s, e)
            if c:
                want[name] = want.get(name, 0.0) + c
        close(got, want)
    table = P.idle_by_phase(trace, per_unit)
    assert {"gc", "serve/decode_wait", "serve/prefill_wait", "serve/tick",
            "outside"} <= set(table)
    assert sum(table.values()) == pytest.approx(
        sum(e - s for s, e in idle), rel=REL)


def test_idle_by_unit_on_the_recorded_slice(recorded):
    """The one whole ``train_batch`` span of the slice as a unit with
    made-up phases inside it."""
    (s, e, _), = [x for x in recorded.spans if x[2] == "train_batch"]
    ns = lambda sec: int(round(sec * 1e9)) + OFFSET_NS
    t0, t1 = ns(s) + 2_000, ns(e) - 2_000
    records = [("train/input", t0 + 1_000, t0 + 300_000, 0, 0),
               ("train/dispatch", t0 + 400_000, t0 + 1_500_000, 0, 0),
               ("gc", t0 + 427_000_000, t0 + 429_500_000, 2, 51),
               ("train/readback", t0 + 1_600_000, t1 - 50_000, 0, 0),
               ("train/step", t0, t1, 4, 0)]
    placed = P.place([(s, e)], records, 0, "train/step")
    idle = brute_idle(recorded.devices[0].ops, recorded.lo, recorded.hi)
    (got,) = P.idle_by_unit(recorded, placed)
    want = {}
    for a, b, name in placed.pieces(placed.units[0]):
        c = brute_overlap(idle, a, b)
        if c:
            want[name] = want.get(name, 0.0) + c
    close(got, want)
    assert set(got) == {"train/step", "train/input", "train/dispatch",
                        "train/readback", "gc"}


def test_merged_intervals_answer_like_clip_and_total():
    m = T.Merged([(0.0, 1.0), (2.0, 3.0), (3.5, 4.0), (6.0, 9.0)])
    for s, e in [(0.5, 2.5), (1.0, 2.0), (-1.0, 10.0), (2.0, 3.0), (3.0, 3.5),
                 (8.0, 8.5), (9.0, 11.0), (4.0, 4.0), (5.0, 4.0)]:
        assert m.seconds(s, e) == T.total(T.clip(m.intervals, s, e)), (s, e)
    assert list(m.within(2.5, 3.75)) == [(2.0, 3.0), (3.5, 4.0)]
    assert list(m.within(4.0, 6.0)) == []


@contextlib.contextmanager
def time_limit(seconds):
    """A time limit on the body, by the alarm signal (no plug-in here)."""
    def ring(*_):
        raise TimeoutError(f"over {seconds} s")
    old = signal.signal(signal.SIGALRM, ring)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def test_a_thousand_ticks_reduce_in_seconds():
    """1,000 ticks x 24 layers x 20 operations (481,200 device events) with
    their phase records: everything a traced serving run asks of its trace,
    asked as often as a run asks it. The reduction that walked every idle
    interval for every piece took 18 s for 120 such ticks on this CPU and
    four times that for twice as many."""
    trace, records = serving_trace(1000, 24, 20)
    assert sum(len(d.ops) for d in trace.devices) == 1000 * 481 + 200
    t = time.perf_counter()
    with time_limit(60):
        spans = [(s, e) for s, e, n in trace.spans if n == "step"]
        placed = P.place(spans, records, 0, "serve/tick")
        assert len(placed.units) == 1000
        for _ in range(8):      # run.py and the readers, each anew
            busy = trace.busy_s(0)
            own = trace.devices[0].op_self_seconds(trace.lo, trace.hi)
        per_unit = P.idle_by_unit(trace, placed)
        table = P.idle_by_phase(trace, per_unit)
        by_span = trace.idle_by_span(0)
        gaps = trace.gaps(0, top=10)
    took = time.perf_counter() - t
    assert took < 60, took
    assert sum(own.values()) == pytest.approx(busy, rel=1e-9)
    assert sum(table.values()) == pytest.approx(trace.window_s - busy,
                                                rel=1e-9)
    assert sum(by_span.values()) == pytest.approx(trace.window_s - busy,
                                                  rel=1e-9)
    assert len(gaps) == 10 and gaps[0][0] == "between_spans"   # the window's ends


# ------------------------------------------- (c) the window bounded in ticks

@functools.lru_cache(maxsize=None)      # one run for every test that reads it
def rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 31), "--seconds", "4",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout.splitlines(), \
        json.loads(proc.stdout.strip().splitlines()[-1])


def cell_file(cell):
    with open(os.path.join(ROOT, "chipbench", "workloads", cell + ".json")) as f:
        return json.load(f)


SERVING = ["opt-1.3b.serve-chat", "olmoe-1b-7b.serve-chat-2k"]
#: read from the device plane's programs and kernels, which the CPU's
#: stand-in trace lacks: left out of a rehearsal's line, never reported as 0
NEED_A_CHIP = {"prefill_share", "decode_hbm_share", "moe_decode_hbm_share",
               "moe_ffn_share", "moe_prefill_roofline"}


@pytest.mark.parametrize("cell", SERVING)
def test_traced_rehearsal_closes_its_window_at_trace_ticks(cell):
    limit = cell_file(cell)["rehearse"]["cell"]["trace_ticks"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"] for m in json.load(f)["per_layer"]
                     if cell in m.get("workloads", [cell])}
    lines, last = rehearse(cell, 1)
    assert last["correct"] is True and last["failed"] == 0
    # every per-layer metric of the cell, on the shortened window
    assert per_layer - NEED_A_CHIP <= set(last["metrics"]) <= per_layer
    cost, = [x for x in lines if "] traced run: " in x]
    assert f"trace_ticks {limit} reached: True" in cost
    held = json.loads(cost.split("the spans ", 1)[1].split("; trace_ticks")[0])
    assert held["step"] == limit and held["window"] == 1
    for part in ("measure=", "stop_trace=", "load=", "check=", "readers=",
                 " device events"):
        assert part in cost
    aligned, = [x for x in lines if "records aligned at" in x]
    assert f" {limit} serve/tick records aligned" in aligned
    assert "phases_dropped 0" in aligned
    counts, = [x for x in lines if " ticks in the window of " in x]
    assert f" {limit} ticks in the window of " in counts
    # the window closed before its 4 s, and only requests due by then count
    assert last["device"]["window_s"] < 3.0
    sent = int(counts.split("] ")[1].split(" requests")[0])
    assert last["attempted"] == sent
    _, whole = rehearse(cell, 0)
    assert last["attempted"] < whole["attempted"]


@pytest.mark.parametrize("cell", SERVING)
def test_untraced_rehearsal_ignores_trace_ticks(cell):
    """All 4 s are measured and every request of them is attempted: what the
    generator makes for the cell's rehearsal traffic."""
    from chipbench.generators import openloop_lognormal
    from chipbench.model import load_json, merge
    c = cell_file(cell)
    traffic = merge(load_json("traffic", c["traffic"] + ".json"),
                    c["rehearse"]["traffic"])
    lines, last = rehearse(cell, 0)
    assert last["correct"] is True
    assert last["attempted"] == len(openloop_lognormal.generate(
        traffic, 2**31 + 31, 512, 4.0))
    counts, = [x for x in lines if " ticks in the window of " in x]
    ticks = int(counts.split(" gaps, ")[1].split(" ticks")[0])
    assert ticks > 2 * c["rehearse"]["cell"]["trace_ticks"]
    assert not [x for x in lines if "] traced run: " in x]


def test_serving_cells_state_their_trace_ticks():
    """At least a tenth over what the accepted program runs in 40 s (ledger,
    PR 29: 316 and 634-638 ticks), so today's traced runs are whole."""
    for cell, ticks in zip(SERVING, (316, 638)):
        c = cell_file(cell)
        assert c["trace_ticks"] >= 1.1 * ticks
        assert "trace_ticks" in c["assumed"]
