"""Serving and inference-engine layers, from the program's own phase records
(``serve/...`` on the tracer's phase ring) placed on the device trace's clock
by ``_program_spans``: where a tick's host time goes, how long a request
waited in the program's queue, what a prefill adds to its tick, how much of
a prefill bucket is padding, and which phase the device idled under. Each
returns ``None`` outside a serving cell, without a trace, or where the
program keeps no phase records."""

import statistics

from chipbench.layer_metrics import _program_spans as P

WAITS = ("serve/prefill_wait", "serve/decode_wait")
PREFILL = ("serve/prefill_prep", "serve/prefill_dispatch",
           "serve/prefill_wait", "serve/first_token")
#: a prefill's idle time is also what admission spent around it
PREFILL_IDLE = PREFILL + ("serve/admit",)


def _kind(placed, tick):
    held = any(p[2] == "serve/prefill_prep" for p in placed.inside(tick))
    return "with a prefill" if held else "decode-only"


def _loaded(ctx, trace):
    if trace is None or ctx.cell["job"] != "serve":
        return None
    return P.load(ctx, trace, "step", "serve/tick", _kind)


def _window(placed, trace, name):
    """The records of one name that ended inside the window."""
    return [p for p in placed.phases
            if p[2] == name and trace.lo <= p[1] <= trace.hi]


def tick_host_ms(ctx, record, trace):
    """Median over the window's ticks of the tick less its two waits for
    the device: what the host itself takes of a tick."""
    got = _loaded(ctx, trace)
    if got is None:
        return None
    placed, _ = got
    host = [u[1] - u[0] - P.seconds_of(placed.inside(u), WAITS)
            for u in placed.units]
    ctx.log(f"serve/tick median "
            f"{statistics.median(u[1] - u[0] for u in placed.units) * 1e3:.3f}"
            f" ms over {len(host)} ticks")
    return statistics.median(host) * 1e3


def queue_wait_ms(ctx, record, trace):
    """Median wait in the program's queue, ``enqueue`` to the moment
    ``_admit`` took the request, of requests admitted in the window."""
    got = _loaded(ctx, trace)
    waits = [e - s for s, e, *_ in
             _window(got[0], trace, "serve/queue_wait")] if got else []
    return statistics.median(waits) * 1e3 if waits else None


def tick_prefill_ms(ctx, record, trace):
    """Median, over the window's ticks that hold a prefill, of what the
    prefill adds to the tick: prep, dispatch, wait and first token."""
    got = _loaded(ctx, trace)
    if got is None:
        return None
    placed, _ = got
    adds = [P.seconds_of(placed.inside(u), PREFILL) for u in placed.units]
    adds = [x for x in adds if x > 0]
    return statistics.median(adds) * 1e3 if adds else None


def prefill_pad_share(ctx, record, trace):
    """Share of the window's prefill bucket tokens that were padding
    (payload of ``serve/prefill_prep``: prompt tokens, bucket tokens)."""
    got = _loaded(ctx, trace)
    preps = _window(got[0], trace, "serve/prefill_prep") if got else []
    bucket = sum(b for *_, b in preps)
    ctx.log(f"{len(preps)} prefills in the window, {bucket} bucket tokens")
    return 100.0 * sum(b - a for *_, a, b in preps) / bucket \
        if bucket else None


def _idle_ms(names, per):
    """Device-idle ms under the phases ``names`` picks, per record of
    ``per`` in the window."""
    def read(ctx, record, trace):
        got = _loaded(ctx, trace)
        if got is None:
            return None
        placed, idle = got
        n = len(_window(placed, trace, per))
        secs = sum(v for k, v in idle.items() if names(k))
        return secs * 1e3 / n if n else None
    return read


METRICS = {"tick_host_ms": tick_host_ms, "queue_wait_ms": queue_wait_ms,
           "tick_prefill_ms": tick_prefill_ms,
           "prefill_pad_share": prefill_pad_share,
           "idle_prefill_ms": _idle_ms(lambda k: k in PREFILL_IDLE,
                                       "serve/prefill_prep"),
           "idle_tick_ms": _idle_ms(
               lambda k: k not in PREFILL_IDLE and k != "outside",
               "serve/tick")}
