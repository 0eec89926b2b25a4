"""The program's own phase records, put on the device trace's clock.

The program (``deepspeed_tpu/telemetry/trace.py``) keeps a ring of phase
records ``(name, t0_ns, t1_ns, a, b)`` stamped with ``perf_counter_ns``:
``serve/tick`` with what a serving tick does inside it, ``train/step``
likewise, ``gc``. The benchmark's trace holds its own ``chipbench/step`` /
``chipbench/train_batch`` spans on the profiler's clock, each around exactly
one call of the program, so each brackets exactly one ``serve/tick`` /
``train/step`` record. ``place`` finds the run of records that the spans
bracket and the offset between the two clocks; the readers
(``serve_program.py``, ``train_program.py``) then give every idle moment of
the device to the innermost program phase open at that moment.

A program without phase records (the parent of the PR that added them) makes
``load`` return ``None``: the metrics are left out, nothing raises.

The leading underscore keeps ``run.py:readers`` from loading this file as a
reader module.
"""

import bisect
import json

import numpy as np

from chipbench import trace as T

TOLERANCE_S = 50e-6     # how far a record may lie outside the span around it
#: intervals that outlive a frame: they overlap ticks and are no host phase
INTERVALS = ("serve/queue_wait",)


class Placed:
    """``phases``: every record as ``(start_s, end_s, name, a, b)`` on the
    trace's clock, by start; ``units``: those the spans bracket (the window's
    ticks or steps); ``residual_s``: the farthest a unit lies outside its
    span."""

    def __init__(self, phases, units, residual_s):
        self.phases, self.units, self.residual_s = phases, units, residual_s
        self._starts = [p[0] for p in phases]

    def inside(self, unit):
        """The host phases that lie inside one unit, itself not among them."""
        lo = bisect.bisect_left(self._starts, unit[0])
        hi = bisect.bisect_right(self._starts, unit[1])
        return [p for p in self.phases[lo:hi]
                if p[1] <= unit[1] and p != unit and p[2] not in INTERVALS]

    def pieces(self, unit):
        """``(start_s, end_s, name)`` pieces of one unit, each under the
        innermost phase open then; they add up to the unit."""
        return T.self_events([p[:3] for p in [unit] + self.inside(unit)])

    def self_seconds(self, unit):
        """{phase name: seconds it was the innermost open phase} of one
        unit; adds up to the unit's length."""
        out = {}
        for s, e, name in self.pieces(unit):
            out[name] = out.get(name, 0.0) + e - s
        return out


def place(spans, records, dropped, unit, log=lambda msg: None):
    """``spans``: the trace's ``(start_s, end_s)`` around each call, in
    order; ``records``: ``Tracer.phases()``; ``dropped``: its
    ``phases_dropped``; ``unit``: the record name a span brackets. The ring
    also holds warm-up and drain, so the run of ``unit`` records is found by
    shape: the shift at which centres (up to one common offset, their median
    difference) and durations differ least from the spans', summed
    absolutely. The profiler's start and
    stop leave long gaps at both ends of the window, so a wrong shift costs
    whole gaps even where every tick takes the same time. ``None``, with the
    reason logged, unless every unit then lies inside its span to
    ``TOLERANCE_S``, no other unit overlaps the spans' range, and the ring
    still holds everything since the first unit began."""
    units = [r for r in records if r[0] == unit]
    n = len(spans)
    if not n or len(units) < n:
        log(f"phase records: {len(units)} {unit} records for {n} spans")
        return None
    base = units[0][1]
    sec = lambda ns: (ns - base) * 1e-9
    r0 = np.array([sec(r[1]) for r in units])
    r1 = np.array([sec(r[2]) for r in units])
    s0 = np.array([s for s, _ in spans])
    s1 = np.array([e for _, e in spans])
    best = None
    for k in range(len(units) - n + 1):
        shift = (s0 + s1 - (r0 + r1)[k:k + n]) / 2       # centre on centre
        off = float(np.median(shift))
        cost = float(np.abs(shift - off).sum() +
                     np.abs((s1 - s0) - (r1 - r0)[k:k + n]).sum())
        if best is None or cost < best[0]:
            best = (cost, k, off)
    _, k, off = best
    a0, a1 = r0[k:k + n] + off, r1[k:k + n] + off
    outside = float(max(0.0, (s0 - a0).max(), (a1 - s1).max()))
    log(f"phase records: {n} {unit} records aligned at {k} of "
        f"{len(units)}, clock offset {off:.6f} s, residual "
        f"{outside * 1e6:.1f} us, phases_dropped {dropped}")
    if outside > TOLERANCE_S:
        log(f"phase records: a {unit} record lies {outside * 1e6:.0f} us "
            f"outside its span (limit {TOLERANCE_S * 1e6:.0f})")
        return None
    if (k and r1[k - 1] + off > s0[0] + TOLERANCE_S) or \
            (k + n < len(units) and r0[k + n] + off < s1[-1] - TOLERANCE_S):
        log(f"phase records: more {unit} records than spans in the window")
        return None
    if dropped and records[0][2] > units[k][1]:
        log(f"phase records: the ring dropped records of the window "
            f"({dropped} overwritten)")
        return None
    on_trace = lambda r: (sec(r[1]) + off, sec(r[2]) + off, r[0], r[3], r[4])
    return Placed(sorted(on_trace(r) for r in records),
                  [on_trace(r) for r in units[k:k + n]], outside)


def seconds_of(phases, names):
    return sum(e - s for s, e, name, _, _ in phases if name in names)


def idle_by_unit(trace, placed, device=0):
    """One ``{phase name: idle seconds of the device under it}`` for each
    unit: every idle moment inside the unit goes to the innermost program
    phase open then."""
    idle = trace.idle(device)
    out = []
    for unit in placed.units:
        table = {}
        for s, e, name in placed.pieces(unit):
            c = idle.seconds(s, e)
            if c:
                table[name] = table.get(name, 0.0) + c
        out.append(table)
    return out


def add_up(tables):
    out = {}
    for table in tables:
        for name, v in table.items():
            out[name] = out.get(name, 0.0) + v
    return out


def idle_by_phase(trace, per_unit, device=0):
    """The tables of ``idle_by_unit`` added up, and ``outside``: the idle
    time under no unit (between two calls of the program). Adds up to the
    window less the device's busy time."""
    out = add_up(per_unit)
    out["outside"] = trace.window_s - trace.busy_s(device) - sum(out.values())
    return out


def load(ctx, trace, span, unit, kind=None):
    """``(placed, idle table)`` for a traced run, computed once a run and
    kept in ``ctx.state``; logs the alignment, the idle table (whole, and
    per unit for each kind of unit that ``kind(placed, unit)`` names) and the
    three slowest units with their phase split. ``None`` where the program
    keeps no phase records or they cannot be placed."""
    if "program_spans" not in ctx.state:
        ctx.state["program_spans"] = _load(ctx, trace, span, unit, kind)
    return ctx.state["program_spans"]


def _load(ctx, trace, span, unit, kind):
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    if trace is None or not hasattr(tracer, "phases"):
        ctx.log("phase records: the program keeps none")
        return None
    spans = [(s, e) for s, e, name in trace.spans if name == span]
    placed = place(spans, tracer.phases(), tracer.phases_dropped, unit,
                   ctx.log)
    if placed is None:
        return None
    per_unit = idle_by_unit(trace, placed)
    idle = idle_by_phase(trace, per_unit)
    ctx.log("idle seconds by program phase: " + json.dumps(idle))
    ms = lambda d, n=1: {k: round(v * 1e3 / n, 3)
                         for k, v in sorted(d.items())}
    kinds = {}
    for u, table in zip(placed.units, per_unit):
        if kind is not None:
            kinds.setdefault(kind(placed, u), []).append((u, table))
    for label, members in sorted(kinds.items()):
        n = len(members)
        mean = sum(u[1] - u[0] for u, _ in members) * 1e3 / n
        idle_each = ms(add_up(t for _, t in members), n)
        self_each = ms(add_up(placed.self_seconds(u) for u, _ in members), n)
        ctx.log(f"{n} {unit} {label}: mean {mean:.3f} ms, idle ms each by "
                f"phase {json.dumps(idle_each)}, self ms each by phase "
                f"{json.dumps(self_each)}")
    for u in sorted(placed.units, key=lambda u: u[0] - u[1])[:3]:
        gcs = [(round((e - s) * 1e3, 3), a, b)
               for s, e, name, a, b in placed.inside(u) if name == "gc"]
        ctx.log(f"slowest {unit}: {(u[1] - u[0]) * 1e3:.3f} ms at "
                f"{u[0] - trace.lo:.2f}s (a={u[3]} b={u[4]}), self ms by "
                f"phase {json.dumps(ms(placed.self_seconds(u)))}, gc "
                f"(ms, generation, collected) {gcs}")
    return placed, idle
