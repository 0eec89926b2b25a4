"""Telemetry exporters: Chrome trace JSON, metrics snapshot, Prometheus.

Three views over one ``Tracer``:

- ``chrome_trace`` / ``write_chrome_trace`` — trace-event JSON loadable in
  Perfetto (ui.perfetto.dev) or chrome://tracing. Complete spans become
  ``ph="X"`` events (nesting falls out of ts/dur on a shared tid); async
  request spans become ``ph="b"/"e"`` pairs keyed by request id.
- ``metrics_snapshot`` / ``write_snapshot`` — JSON aggregates: per-span
  count/total/mean/max, the counter gauges (MFU, recompiles, memory
  high-water, serving gauges), and a per-collective table with payload
  bytes and derived algorithm/bus bandwidth (comm/logging.py formulas).
- ``prometheus_dump`` — the same gauges in Prometheus text exposition
  format, for scrape-by-file or pushgateway-style export. Also what the
  ``TelemetryMonitor`` sink writes.
"""

import json
import re
import time
from typing import Any, Dict, List, Optional

from .trace import Tracer, get_tracer


def _calc_bw(op, nbytes, dur_s, n):
    # deferred: comm/comm.py imports telemetry.trace, so a module-level
    # import of comm.logging here would be order-sensitive
    from ..comm.logging import calc_bw_log
    return calc_bw_log(op, nbytes, dur_s, n)

__all__ = ["chrome_trace", "write_chrome_trace", "chrome_trace_slice",
           "span_aggregates", "comm_table", "metrics_snapshot",
           "write_snapshot", "prometheus_dump"]


def _pid() -> int:
    try:
        import jax
        return jax.process_index()
    except Exception:
        return 0


def chrome_trace(tracer: Optional[Tracer] = None) -> Dict[str, Any]:
    """Trace-event JSON dict (Perfetto-loadable)."""
    tracer = tracer or get_tracer()
    pid = _pid()
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": f"deepspeed_tpu rank {pid}"},
    }]
    tids: List[int] = []
    for sp in tracer.spans():
        ev: Dict[str, Any] = {"name": sp.name, "cat": sp.cat, "ph": sp.ph,
                              "ts": sp.ts_us, "pid": pid, "tid": sp.tid}
        if sp.tid not in tids:
            tids.append(sp.tid)
        if sp.ph == "X":
            ev["dur"] = sp.dur_us
        if sp.ph in ("b", "e"):
            ev["id"] = format(sp.aid or 0, "x")
        if sp.ph == "i":
            ev["s"] = "t"      # thread-scoped instant
        args = dict(sp.args) if sp.args else {}
        if sp.cat == "comm" and sp.ph == "X":
            args.update(_bw_args(sp))
        if args:
            ev["args"] = args
        events.append(ev)
    # readable thread rows: raw thread idents are meaningless 15-digit
    # numbers in the Perfetto UI (the fleet-merged view re-labels lanes
    # per replica on top of this — telemetry/disttrace.py)
    for j, tid in enumerate(tids):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": f"thread {j}"}})
        events.append({"name": "thread_sort_index", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"sort_index": j}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": tracer.dropped}}


def write_chrome_trace(path: str, tracer: Optional[Tracer] = None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(tracer), f)
    return path


def chrome_trace_slice(tracer: Optional[Tracer] = None,
                       last_ms: Optional[float] = None) -> Dict[str, Any]:
    """Chrome trace JSON cut to the last ``last_ms`` milliseconds of span
    activity (span timestamps share the ``perf_counter_ns`` clock, so
    "now" is directly comparable). ``None`` = the full buffer. Shared by
    the statusz ``/trace`` endpoint and the flight-recorder bundles."""
    doc = chrome_trace(tracer)
    if last_ms is None:
        return doc
    cutoff = time.perf_counter_ns() / 1e3 - float(last_ms) * 1e3
    doc["traceEvents"] = [
        ev for ev in doc["traceEvents"]
        if ev["ph"] == "M" or
        ev.get("ts", 0) + ev.get("dur", 0) >= cutoff]
    return doc


def _bw_args(sp) -> Dict[str, float]:
    """Derived bandwidth for a comm span (GB/s, from measured duration —
    trace-time spans have ~0 duration and report 0). When the span carries
    ``wire_bytes`` (the dispatch's per-member link-byte model, compressed
    size when a codec ran) the bus bandwidth is wire_bytes ÷ duration
    directly; the analytic ring factors are only applied to legacy spans
    that lack it."""
    args = sp.args or {}
    nbytes = int(args.get("bytes", 0))
    n = int(args.get("participants", 0)) or 1
    dur_s = sp.dur_us / 1e6
    wire = args.get("wire_bytes")
    if wire is not None and dur_s > 0:
        return {"algbw_gbps": round(nbytes / dur_s / 1e9, 3),
                "busbw_gbps": round(int(wire) / dur_s / 1e9, 3)}
    algbw, busbw = _calc_bw(args.get("op", sp.name), nbytes, dur_s, n)
    return {"algbw_gbps": round(algbw, 3), "busbw_gbps": round(busbw, 3)}


def span_aggregates(tracer: Optional[Tracer] = None) -> Dict[str, Any]:
    """Per-name aggregates over complete spans: where did the time go."""
    tracer = tracer or get_tracer()
    out: Dict[str, Any] = {}
    for sp in tracer.spans():
        if sp.ph != "X":
            continue
        rec = out.setdefault(sp.name, {"count": 0, "total_ms": 0.0,
                                       "max_ms": 0.0})
        rec["count"] += 1
        rec["total_ms"] += sp.dur_us / 1e3
        rec["max_ms"] = max(rec["max_ms"], sp.dur_us / 1e3)
    for rec in out.values():
        rec["mean_ms"] = rec["total_ms"] / rec["count"]
        rec["total_ms"] = round(rec["total_ms"], 4)
        rec["mean_ms"] = round(rec["mean_ms"], 4)
        rec["max_ms"] = round(rec["max_ms"], 4)
    return out


def comm_table(tracer: Optional[Tracer] = None) -> Dict[str, Any]:
    """Per-collective totals: calls, payload bytes, derived bus bandwidth."""
    tracer = tracer or get_tracer()
    out: Dict[str, Any] = {}
    for sp in tracer.spans():
        if sp.cat != "comm" or sp.ph != "X":
            continue
        args = sp.args or {}
        op = args.get("op", sp.name)
        rec = out.setdefault(op, {"calls": 0, "bytes": 0, "wire_bytes": 0,
                                  "total_ms": 0.0,
                                  "participants": int(
                                      args.get("participants", 0))})
        rec["calls"] += 1
        rec["bytes"] += int(args.get("bytes", 0))
        rec["wire_bytes"] += int(args.get("wire_bytes", 0))
        pol = args.get("policy")
        if pol:
            rec["policy"] = pol
        rec["total_ms"] += sp.dur_us / 1e3
    for op, rec in out.items():
        dur_s = rec["total_ms"] / 1e3
        if rec["wire_bytes"] and dur_s > 0:
            # wire bytes come from the dispatch's link model (compressed
            # size when a codec ran): bus bw is wire ÷ time directly
            rec["algbw_gbps"] = round(rec["bytes"] / dur_s / 1e9, 3)
            rec["busbw_gbps"] = round(rec["wire_bytes"] / dur_s / 1e9, 3)
        else:
            algbw, busbw = _calc_bw(op, rec["bytes"], dur_s,
                                    max(rec["participants"], 1))
            rec["algbw_gbps"] = round(algbw, 3)
            rec["busbw_gbps"] = round(busbw, 3)
        rec["total_ms"] = round(rec["total_ms"], 4)
    return out


def metrics_snapshot(tracer: Optional[Tracer] = None,
                     extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One JSON document answering "where did this step's time go": span
    aggregates + gauges (MFU, recompiles, memory) + comm table."""
    tracer = tracer or get_tracer()
    counters = {tag: val for tag, (val, _step) in tracer.counters().items()}
    snap = {"spans": span_aggregates(tracer), "counters": counters,
            "comm": comm_table(tracer), "dropped_spans": tracer.dropped}
    from .goodput import get_ledger
    ledger = get_ledger()
    if ledger.enabled:
        snap["goodput"] = ledger.snapshot()
    if extra:
        snap.update(extra)
    return snap


def write_snapshot(path: str, tracer: Optional[Tracer] = None,
                   extra: Optional[Dict[str, Any]] = None) -> str:
    with open(path, "w") as f:
        json.dump(metrics_snapshot(tracer, extra=extra), f, indent=2,
                  default=str)
    return path


_PROM_NAME = re.compile(r"[^a-zA-Z0-9_:]")


def _prom(name: str) -> str:
    name = _PROM_NAME.sub("_", name)
    return name if not name[:1].isdigit() else "_" + name


def prometheus_dump(tracer: Optional[Tracer] = None,
                    prefix: str = "dstpu") -> str:
    """Prometheus text exposition of the gauges + span aggregates."""
    tracer = tracer or get_tracer()
    lines: List[str] = []
    host_lines: List[str] = []
    tenant_series: Dict[str, List[str]] = {}
    cost_series: Dict[str, List[str]] = {}
    lines.append(f"# TYPE {prefix}_metric gauge")
    for tag, (val, _step) in sorted(tracer.counters().items()):
        try:
            fval = float(val)
        except (TypeError, ValueError):
            continue
        if tag.startswith("host/"):
            # per-host aggregates (telemetry/hostagg.py) get dedicated
            # series — dashboards alert on dstpu_host_step_time_spread
            # without label-matching through the generic gauge
            name = _prom(tag[len("host/"):])
            host_lines.append(f"# TYPE {prefix}_host_{name} gauge")
            host_lines.append(f"{prefix}_host_{name} {fval}")
            continue
        if tag.startswith("mem/"):
            # HBM role attribution (telemetry/compileplane.py HBMLedger):
            # dedicated dstpu_mem_* series so a dashboard stacks
            # params/grads/optimizer/activations/kv_slots directly
            name = _prom(tag[len("mem/"):])
            host_lines.append(f"# TYPE {prefix}_mem_{name} gauge")
            host_lines.append(f"{prefix}_mem_{name} {fval}")
            continue
        if tag.startswith("fleet/"):
            # fleet router gauges (serving/metrics.py FleetMetrics):
            # dstpu_fleet_ready_replicas / _failovers / _kv_handoffs /
            # _prefix_cache_hit_rate as first-class alerting series
            name = _prom(tag[len("fleet/"):])
            host_lines.append(f"# TYPE {prefix}_fleet_{name} gauge")
            host_lines.append(f"{prefix}_fleet_{name} {fval}")
            continue
        if tag.startswith("tenant/"):
            # per-tenant SLO gauges (serving/metrics.py tenant windows,
            # router throttle counts): tenant/<name>/<metric> becomes a
            # tenant=-labeled dstpu_tenant_<metric> series — dashboards
            # rank tenants by burn rate / share with one query instead of
            # label-matching through the generic gauge
            tname, _, metric = tag[len("tenant/"):].partition("/")
            if metric:
                name = _prom(metric)
                tenant_series.setdefault(name, []).append(
                    f'{prefix}_tenant_{name}{{tenant="{_prom(tname)}"}} '
                    f"{fval}")
                continue
        if tag.startswith("cost/"):
            # cost-plane attribution (serving/metrics.py update_cost,
            # folded at the router from telemetry/costplane.py ledgers):
            # cost/<tenant>/<metric> becomes a tenant=-labeled
            # dstpu_cost_<metric> series — chargeback dashboards rank
            # tenants by chip-milliseconds / HBM-GiB-seconds with one
            # query instead of label-matching through the generic gauge
            tname, _, metric = tag[len("cost/"):].partition("/")
            if metric:
                name = _prom(metric)
                cost_series.setdefault(name, []).append(
                    f'{prefix}_cost_{name}{{tenant="{_prom(tname)}"}} '
                    f"{fval}")
                continue
        if tag.startswith("elastic/"):
            # elasticity gauges (elasticity/coordinator.py on the
            # training side, FleetMetrics.update_autoscale on the serving
            # side): dedicated dstpu_elastic_world_size / _hosts_missing /
            # _resizes / _live_replicas / _scale_ups series — a fleet
            # changing size is an alerting event, not a label lookup
            name = _prom(tag[len("elastic/"):])
            host_lines.append(f"# TYPE {prefix}_elastic_{name} gauge")
            host_lines.append(f"{prefix}_elastic_{name} {fval}")
            continue
        if tag.startswith("moe/"):
            # expert-parallel telemetry (moe/sharded_moe.py MoeMetrics):
            # dedicated dstpu_moe_load_imbalance / _dropped_token_fraction
            # / _overflow_tokens series — capacity-factor overflow is an
            # alerting target (dropped tokens are silent quality loss),
            # not a label-matched lookup
            name = _prom(tag[len("moe/"):])
            host_lines.append(f"# TYPE {prefix}_moe_{name} gauge")
            host_lines.append(f"{prefix}_moe_{name} {fval}")
            continue
        if tag.startswith("spec/"):
            # speculative-decode gauges (serving/metrics.py): dedicated
            # dstpu_spec_acceptance_ema / _tokens_per_tick / _draft_ms /
            # _verify_ms series — the acceptance floor is an alerting
            # target, not a label-matched lookup
            name = _prom(tag[len("spec/"):])
            host_lines.append(f"# TYPE {prefix}_spec_{name} gauge")
            host_lines.append(f"{prefix}_spec_{name} {fval}")
            continue
        if tag.startswith("rollout/"):
            # rollout plane gauges (serving/metrics.py update_rollout):
            # dedicated dstpu_rollout_shift_fraction / _version_skew /
            # _rollbacks series — a rollback is a paging event and
            # nonzero steady-state skew is a stuck rollout, not a
            # label-matched lookup
            name = _prom(tag[len("rollout/"):])
            host_lines.append(f"# TYPE {prefix}_rollout_{name} gauge")
            host_lines.append(f"{prefix}_rollout_{name} {fval}")
            continue
        lines.append(f'{prefix}_metric{{tag="{_prom(tag)}"}} {fval}')
    lines.extend(host_lines)
    for name in sorted(tenant_series):
        # one TYPE header per family, samples contiguous per the
        # exposition format (tenants vary only by label)
        lines.append(f"# TYPE {prefix}_tenant_{name} gauge")
        lines.extend(tenant_series[name])
    for name in sorted(cost_series):
        lines.append(f"# TYPE {prefix}_cost_{name} gauge")
        lines.extend(cost_series[name])
    aggs = span_aggregates(tracer)
    if aggs:
        lines.append(f"# TYPE {prefix}_span_ms_total counter")
        lines.append(f"# TYPE {prefix}_span_count counter")
        for name, rec in sorted(aggs.items()):
            lines.append(f'{prefix}_span_ms_total{{name="{_prom(name)}"}} '
                         f'{rec["total_ms"]}')
            lines.append(f'{prefix}_span_count{{name="{_prom(name)}"}} '
                         f'{rec["count"]}')
    from .goodput import get_ledger
    ledger = get_ledger()
    if ledger.enabled:
        snap = ledger.snapshot()
        lines.append(f"# TYPE {prefix}_goodput_seconds gauge")
        for bucket, secs in sorted(snap["buckets"].items()):
            lines.append(
                f'{prefix}_goodput_seconds{{bucket="{_prom(bucket)}"}} '
                f"{secs}")
        lines.append(f"# TYPE {prefix}_goodput_fraction gauge")
        lines.append(f"{prefix}_goodput_fraction "
                     f"{snap['goodput_fraction']}")
        lines.append(f"# TYPE {prefix}_wall_seconds gauge")
        lines.append(f"{prefix}_wall_seconds {snap['wall_s']}")
    lines.append(f"# TYPE {prefix}_dropped_spans gauge")
    lines.append(f"{prefix}_dropped_spans {tracer.dropped}")
    return "\n".join(lines) + "\n"
