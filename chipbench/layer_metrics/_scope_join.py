"""Device time under the program's own names: the device trace's operations
joined to the scope tables the program keeps of its compiled programs.

The program (``deepspeed_tpu/telemetry/trace.py``) notes every compiled
program at its first call and gives, when asked, ``Tracer.scope_tables()``:
``{module name: {program key: {instruction name: scope}}}``, read off the
``op_name`` metadata of each program's optimized HLO. A scope is the pass
(``forward``, ``remat``, ``backward``) and the ``named_scope`` words
(``attn``, ``kv_read``, ``mlp``, ``head``, ``sample``, ...) joined by ``/``;
``None`` where the instruction carries neither; behind a ``?`` where the
program's own names gave none and the table inferred one from the
instruction's neighbours (its time is added under the scope and counted
apart, ``Joined.inferred``). The trace names every event of "XLA Ops" by its
instruction, so:

- device 0's self pieces (``trace.self_events``: the innermost running
  operation at each moment; they add up to the busy time) each go to the
  program whose ``XLA Modules`` interval they start in;
- two buckets' prefill programs share the module name ``jit_pf`` and number
  their fusions differently: a ``jit_pf`` interval takes the bucket of the
  last ``serve/prefill_prep`` phase record before it (payload ``b``;
  ``_program_spans``, the shared clock), which is its key's second entry;
- the ``tpu_custom_call`` marker is stripped, the scope looked up, the
  seconds added up by module and scope.

A rehearsal on the CPU, whose host events stand in for the device's and
whose trace has no module intervals, takes them from the program's phase
records: a dispatch phase's start to the next one's. A trace of the chip
without module intervals is not joined at all.

``None`` from ``load`` without a trace and for a program that keeps no scope
tables (the parent of the PR that added them). The leading underscore keeps
``run.py:readers`` from loading this file as a reader module.
"""

import bisect
import json
import re
import time

from chipbench import trace as T
from chipbench.layer_metrics import serve_program, train_program

MARKER = " tpu_custom_call"
#: operations that enclose others: their own pieces are the loop's overhead
ENCLOSING = re.compile(r"^(while|conditional|call)(\.|$)")
#: a rehearsal's module intervals: the phase that dispatches each program
DISPATCHED = {"serve/prefill_dispatch": "jit_pf",
              "serve/decode_dispatch": "jit_dec",
              "train/dispatch": "jit_train_step"}


def module_intervals(trace, placed, rehearsal=False, device=0):
    """``(start_s, end_s, module)`` of the programs started in the window,
    by start: the device's own "XLA Modules". A rehearsal has none; its are
    what the program's phase records say was dispatched: from a dispatch
    phase's start to the next one's, whatever program that one sends (the
    CPU runs them in order, and a wait returns with the token, before the
    pool's last rows are written)."""
    if not rehearsal:
        return sorted(m for m in trace.devices[device].modules
                      if trace.lo <= m[0] < trace.hi)
    sent = sorted((p[0], DISPATCHED[p[2]]) for p in placed.phases
                  if p[2] in DISPATCHED and trace.lo <= p[0] < trace.hi) \
        if placed is not None else []
    ends = [s for s, _ in sent[1:]] + [trace.hi]
    return [(s, e, module) for (s, module), e in zip(sent, ends)]


class Joined:
    """``seconds``: {module: {scope or None: seconds}} of device 0's self
    pieces inside the window; ``calls``: {module: programs started that a
    table was found for} (``no_table``: those none was);
    ``busy``: {module: seconds its intervals were busy, by the union of
    the operations; of a rehearsal: of the pieces that started in them};
    ``unnamed``:
    {(module, instruction): seconds} that read ``None``, ``absent`` those
    of them the table does not list at all; ``inferred``: {module: seconds
    under a scope the table marks ``?``}; ``untabled``:
    seconds in modules without a table and outside every module;
    ``bucket_tokens``: the bucket tokens of the window's ``jit_pf`` calls;
    ``intervals`` and, beside each, ``tables_of``: the window's programs and
    the table each was looked up in (``None``: it has none)."""

    def __init__(self):
        self.seconds, self.calls, self.busy = {}, {}, {}
        self.unnamed, self.absent, self.untabled = {}, set(), 0.0
        self.inferred, self.no_table = {}, {}
        self.by_name = {}   # (module, instruction) -> (seconds, marked scope)
        self.bucket_tokens = 0
        self.intervals, self.tables_of = [], []

    def under(self, module, match):
        """Seconds of ``module`` under the scopes ``match(parts)`` takes,
        ``parts`` the scope split at ``/``."""
        return sum(v for scope, v in self.seconds.get(module, {}).items()
                   if scope is not None and match(scope.split("/")))


def _table_for(keyed, bucket):
    """The scope table of one module interval among the module's ``{key:
    table}``: its only one, or for a prefill the one whose key names the
    ``bucket`` (``("slot_prefill", bucket, max_len)``)."""
    if not keyed:
        return None
    if len(keyed) == 1:
        return next(iter(keyed.values()))
    return next((table for key, table in keyed.items()
                 if isinstance(key, tuple) and key[1:2] == (bucket,)), None)


def join(trace, tables, placed, log=lambda msg: None, rehearsal=False,
         device=0):
    """The window's ``Joined``; ``None`` where the trace of a chip shows no
    module interval to cut it by (never by the host's records there: they
    would redefine busy time and the metrics would not say so)."""
    dev = trace.devices[device]
    out = Joined()
    out.intervals = module_intervals(trace, placed, rehearsal, device)
    if not out.intervals:
        log("scope time: the trace shows no program started in the window "
            "(no \"XLA Modules\" event; in a rehearsal: no dispatch record "
            "laid on the trace's clock): nothing joined")
        return None
    preps = sorted(p for p in placed.phases if p[2] == "serve/prefill_prep") \
        if placed is not None else []
    prep_starts = [p[0] for p in preps]
    busy = T.Merged(dev.busy(trace.lo, trace.hi))
    for s, e, module in out.intervals:
        bucket = None
        if module == "jit_pf":      # the last prefill prepared before it
            at = bisect.bisect_right(prep_starts, s)
            bucket = preps[at - 1][4] if at else None
        out.tables_of.append(_table_for(tables.get(module), bucket))
        if out.tables_of[-1] is None:   # its time is ``untabled``, whole
            out.no_table[module] = out.no_table.get(module, 0) + 1
            continue
        out.calls[module] = out.calls.get(module, 0) + 1
        out.busy[module] = out.busy.get(module, 0.0) + \
            (0.0 if rehearsal else busy.seconds(s, e))
        out.bucket_tokens += bucket or 0
    starts = [m[0] for m in out.intervals]
    pieces = T._kept(dev, "self", lambda: T.self_events(dev.ops))
    for s, e, name in pieces:
        d = min(e, trace.hi) - max(s, trace.lo)
        if d <= 0:
            continue
        at = bisect.bisect_right(starts, s) - 1
        if at < 0 or s >= out.intervals[at][1] or out.tables_of[at] is None:
            out.untabled += d
            continue
        module = out.intervals[at][2]
        if rehearsal:               # busy is what started there
            out.busy[module] += d
        name = name[:-len(MARKER)] if name.endswith(MARKER) else name
        marked = scope = out.tables_of[at].get(name)
        if scope is None and name not in out.tables_of[at]:
            out.absent.add((module, name))
        elif scope is not None and scope[0] == "?":
            scope = scope[1:]
            out.inferred[module] = out.inferred.get(module, 0.0) + d
        by = out.seconds.setdefault(module, {})
        by[scope] = by.get(scope, 0.0) + d
        old = out.by_name.get((module, name), (0.0, marked))
        out.by_name[(module, name)] = (old[0] + d, marked)
        if scope is None:
            out.unnamed[(module, name)] = \
                out.unnamed.get((module, name), 0.0) + d
    _log(out, log)
    return out


def _log(out, log):
    for module, by in sorted(out.seconds.items()):
        n, total = out.calls[module], sum(by.values())
        top = sorted(by.items(), key=lambda kv: -kv[1])[:15]
        log(f"scope time {module}: {n} calls, {total / n * 1e3:.3f} ms a "
            f"call under a table, busy {out.busy[module] / n * 1e3:.3f} ms a "
            f"call, {100 * out.inferred.get(module, 0.0) / total:.2f}% of it "
            f"under a scope inferred from neighbours, "
            f"{100 * by.get(None, 0.0) / total:.2f}% unnamed; "
            f"ms a call and share by scope: " + json.dumps(
                [[str(k), round(v / n * 1e3, 4), round(100 * v / total, 2)]
                 for k, v in top]))
        ops = sorted(((v, name, scope) for (m, name), (v, scope)
                      in out.by_name.items() if m == module), reverse=True)
        log(f"scope time {module}: the longest instructions, ms a call: " +
            json.dumps([[name, str(scope), round(v / n * 1e3, 4)]
                        for v, name, scope in ops[:14]]))
    worst = sorted(out.unnamed.items(), key=lambda kv: -kv[1])[:5]
    log("scope time: the longest instructions that read None (*: not in "
        "the table at all): " + json.dumps(
        [[m, name + "*" * ((m, name) in out.absent), round(v, 6)]
         for (m, name), v in worst]) +
        f"; {out.untabled:.6f} s in programs without a table " +
        json.dumps(out.no_table) + " or outside every program; "
        f"{sum(out.inferred.values()):.6f} s under scopes inferred from "
        f"neighbours; {out.bucket_tokens} bucket tokens prefilled")


def load(ctx, trace):
    """The window's ``Joined``, computed once a run and kept in
    ``ctx.state``; ``None`` without a trace or where the program keeps no
    scope tables."""
    if "scope_time" not in ctx.state:
        ctx.state["scope_time"] = _load(ctx, trace)
    return ctx.state["scope_time"]


def _load(ctx, trace):
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    if trace is None or not hasattr(tracer, "scope_tables"):
        ctx.log("scope time: the program keeps no scope tables")
        return None
    t = time.perf_counter()
    tables = tracer.scope_tables()
    ctx.log("scope time: tables of " + json.dumps(
        {m: [str(k) for k in keyed] for m, keyed in tables.items()}) +
        f" taken in {time.perf_counter() - t:.2f} s")
    got = serve_program._loaded(ctx, trace) or \
        train_program._loaded(ctx, trace)
    return join(trace, tables, got[0] if got else None, ctx.log,
                rehearsal=bool(getattr(getattr(ctx, "args", None),
                                       "rehearse", False)))
