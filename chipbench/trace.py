"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per-device operation intervals, busy time as the UNION of those
intervals, self time by operation, XLA modules, collectives and the
benchmark's own host spans on the same clock.

A TPU's plane is ``/device:TPU:<n>`` (seen on the v5e, PR 26). Its line
``XLA Ops`` holds one event per HLO operation, named by the instruction's
whole text (kept here up to the `` = ``; a Pallas kernel keeps the marker
``tpu_custom_call``); a ``while`` encloses the operations of its body, so
durations nest and may not be added up. ``Async XLA Ops`` holds the spans of
asynchronous copies and collectives, from their start to their done;
``XLA Modules`` one event per executed program. Host spans are the ``chipbench/...`` ``TraceAnnotation`` events of
the host plane. On a platform without device planes (``--rehearse`` on the
CPU) host events that carry an ``hlo_op`` stat stand in, so the same code
runs; such a trace is never a chip result.

    python3 chipbench/trace.py <dir-or-file>     # what a trace holds, by hand
"""

import bisect
import glob
import gzip
import json
import os
import re
import sys

SPAN_PREFIX = "chipbench/"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` that no interval of merged ``b``
    covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


class Merged:
    """Merged, sorted intervals (or ``(start, end, ...)`` pieces that do not
    overlap) with their ends kept beside them, so that "which of them reach
    into ``[s, e)``" is a bisection and the few that do, not a walk over all.
    ``seconds`` adds what ``total(clip(intervals, s, e))`` adds, in the same
    order."""

    def __init__(self, intervals):
        self.intervals = intervals
        self._ends = [x[1] for x in intervals]

    def within(self, s, e):
        """The intervals that overlap ``[s, e)``, in order."""
        k = bisect.bisect_right(self._ends, s)
        while k < len(self.intervals) and self.intervals[k][0] < e:
            yield self.intervals[k]
            k += 1

    def seconds(self, s, e):
        return sum(min(x[1], e) - max(x[0], s)
                   for x in self.within(s, e)) if e > s else 0.0


def _kept(obj, key, make):
    """``make()`` the first time ``obj`` is asked for ``key``, the same
    result after that: a trace does not change once it is loaded, and every
    reader asks for its union, its self times and its idle list again."""
    if key not in obj._keep:
        obj._keep[key] = make()
    return obj._keep[key]


def self_events(events):
    """``(start, end, name)`` pieces in which ``name`` is the innermost
    running operation (the one that started last): nested events (a
    ``while`` and its body) are cut so that the pieces never overlap and add
    up to the union of all events."""
    evs = [x for x in events if x[1] > x[0]]
    points = []
    for i, (s, e, _) in enumerate(evs):
        points.append((s, 1, i))
        points.append((e, 0, i))
    points.sort()
    out, active, alive, prev = [], [], [False] * len(evs), 0.0
    for t, starts, i in points:
        while active and not alive[active[-1]]:
            active.pop()
        if active and t > prev:
            name = evs[active[-1]][2]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], t, name)
            else:
                out.append((prev, t, name))
        if starts:
            active.append(i)
        alive[i] = bool(starts)
        prev = t
    return out


class Device:
    def __init__(self, name, ops, modules, async_ops=()):
        self.name = name
        self.ops = ops              # [(start_s, end_s, op name)]
        self.modules = modules      # [(start_s, end_s, module name)]
        self.async_ops = list(async_ops)    # start-to-done spans
        self._keep = {}             # what was worked out of ``ops``, once

    def busy(self, lo, hi):
        """The union of the operations' intervals inside ``[lo, hi)``; the
        kept list, not a copy."""
        every = _kept(self, "union",
                      lambda: union((s, e) for s, e, _ in self.ops))
        return _kept(self, ("busy", lo, hi), lambda: clip(every, lo, hi))

    def op_self_seconds(self, lo, hi):
        """{operation name: seconds in which it was the innermost one}; the
        kept table, not a copy."""
        def add_up():
            out = {}
            for s, e, name in _kept(self, "self",
                                    lambda: self_events(self.ops)):
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    out[name] = out.get(name, 0.0) + d
            return out
        return _kept(self, ("self", lo, hi), add_up)

    def module_seconds(self, lo, hi):
        """{module name: (count, seconds)} of programs started in the window."""
        out = {}
        for s, e, name in self.modules:
            if lo <= s < hi:
                c, t = out.get(name, (0, 0.0))
                out[name] = (c + 1, t + e - s)
        return out


class Trace:
    def __init__(self, devices, spans):
        self.devices = devices      # [Device], by device number
        self.spans = spans          # [(start_s, end_s, name)] host spans
        self._keep = {}
        win = [x for x in spans if x[2] == "window"]
        if win:
            self.lo, self.hi = win[0][0], win[0][1]
        else:
            every = [x for d in devices for x in d.ops]
            self.lo = min((s for s, _, _ in every), default=0.0)
            self.hi = max((e for _, e, _ in every), default=0.0)

    @property
    def window_s(self):
        return self.hi - self.lo

    def busy_s(self, device=None):
        """Seconds in which an operation ran, averaged over the devices (or
        on one)."""
        devs = self.devices if device is None else [self.devices[device]]
        return _kept(self, ("busy_s", device, self.lo, self.hi), lambda: sum(
            total(d.busy(self.lo, self.hi)) for d in devs) / len(devs))

    def idle(self, device=0):
        """The moments of the window in which no operation ran on the
        device, as ``Merged`` intervals."""
        return _kept(self, ("idle", device, self.lo, self.hi), lambda: Merged(
            subtract([(self.lo, self.hi)],
                     self.devices[device].busy(self.lo, self.hi))))

    def _span_pieces(self):
        """The benchmark's host spans cut to their innermost one."""
        return _kept(self, "pieces", lambda: Merged(self_events(
            [x for x in self.spans if x[2] != "window"])))

    def gaps(self, device=0, top=10):
        """The longest idle gaps of a device inside the window, each named by
        the benchmark's host span that covers most of it."""
        idle, pieces = self.idle(device).intervals, self._span_pieces()
        out = []
        for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
            cover = {"between_spans": e - s}
            for ss, se, name in pieces.within(s, e):
                c = min(e, se) - max(s, ss)
                if c > 0:
                    cover[name] = cover.get(name, 0.0) + c
                    cover["between_spans"] -= c
            out.append((max(cover, key=cover.get), e - s))
        return out

    def idle_by_span(self, device=0):
        """{host span name: idle seconds of the device under it}: every idle
        moment goes to the innermost benchmark span open at that time."""
        idle = self.idle(device)
        out = {"between_spans": total(idle.intervals)}
        for s, e, name in self._span_pieces().intervals:
            c = idle.seconds(s, e)
            if c:
                out[name] = out.get(name, 0.0) + c
                out["between_spans"] -= c
        return out


def _xplane_file(path):
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return found[-1]
    return path


def _module_name(name):
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(text):
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``; a Pallas
    kernel keeps its custom-call target as a marker."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + " tpu_custom_call" if "tpu_custom_call" in text else name


def load(path, allow_host_ops=False):
    """Read a trace directory or file written by ``jax.profiler`` — or the
    small ``.json.gz`` form ``dump`` writes, which the tests keep."""
    if str(path).endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        return Trace([Device(d["name"], [tuple(x) for x in d["ops"]],
                             [tuple(x) for x in d["modules"]],
                             [tuple(x) for x in d.get("async_ops", [])])
                      for d in raw["devices"]],
                     [tuple(x) for x in raw["spans"]])
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_xplane_file(path))
    devices, spans, host_ops = [], [], []

    def ev(e, name=None):
        s = e.start_ns * 1e-9
        return (s, s + e.duration_ns * 1e-9, name or e.name)

    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            ops, mods, asyn = [], [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [ev(e, _op_name(e.name)) for e in line.events]
                elif line.name == "Async XLA Ops":
                    asyn = [ev(e, _op_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [ev(e, _module_name(e.name)) for e in line.events]
            devices.append((int(m.group(1)),
                            Device(plane.name, ops, mods, asyn)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(ev(e, e.name[len(SPAN_PREFIX):]))
                    elif allow_host_ops and "hlo_op" in dict(e.stats):
                        host_ops.append(ev(e))
    devices = [d for _, d in sorted(devices, key=lambda x: x[0])]
    if not devices and allow_host_ops:
        devices = [Device("/host:CPU (rehearsal)", host_ops, [])]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    return Trace(devices, sorted(spans))


def dump(trace, path, device=0, lo=None, hi=None):
    """Write one device's slice of a trace in the small form ``load`` reads."""
    lo = trace.lo if lo is None else lo
    hi = trace.hi if hi is None else hi
    d = trace.devices[device]
    keep = lambda xs: [x for x in xs if x[0] >= lo and x[1] <= hi]
    raw = {"devices": [{"name": d.name, "ops": keep(d.ops),
                        "modules": keep(d.modules),
                        "async_ops": keep(d.async_ops)}],
           "spans": keep(trace.spans)}
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)


def describe(path):
    """Print what a trace holds: planes, lines, counts, the commonest names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_xplane_file(path))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            tot = {}
            for e in evs:
                k = re.sub(r"[.\d]+$", "", _op_name(e.name))
                c, t = tot.get(k, (0, 0.0))
                tot[k] = (c + 1, t + e.duration_ns * 1e-9)
            top = sorted(tot.items(), key=lambda kv: -kv[1][1])[:25]
            print(f"  LINE {line.name!r}: {len(evs)} events, first at "
                  f"{evs[0].start_ns * 1e-9:.6f}s")
            for k, (c, t) in top:
                print(f"      {t:10.6f}s  x{c:<6d} {k}")
            e = evs[len(evs) // 2]
            print("      sample:", e.name[:200], e.start_ns, e.duration_ns)


if __name__ == "__main__":
    describe(sys.argv[1])
