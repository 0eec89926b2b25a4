"""Serving layer, from the benchmark's own host clock around ``srv.step()``
and the ``on_token`` callbacks."""

import statistics


def tick_ms(ctx, record, trace):
    """Median time of ``srv.step()`` over ticks that had work."""
    return statistics.median(record["ticks"]) * 1e3 \
        if record.get("ticks") else None


def slot_occupancy(ctx, record, trace):
    """Mean over ticks of requests holding a slot / slots."""
    occ = record.get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None


def _field(name):
    return lambda ctx, record, trace: record.get(name)


METRICS = {"tick_ms": tick_ms, "slot_occupancy": slot_occupancy,
           "ttft_p95_ms": _field("ttft_p95_ms"),
           "ttft_p50_ms": _field("ttft_p50_ms"),
           "itl_p50_ms": _field("itl_p50_ms"),
           "generator_lag_ms": _field("generator_lag_ms")}
