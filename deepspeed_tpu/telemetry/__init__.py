"""deepspeed_tpu.telemetry — unified structured tracing & metrics.

Usage::

    from deepspeed_tpu.telemetry import get_tracer
    tr = get_tracer()
    tr.configure(enabled=True)
    with tr.span("fwd") as sp:
        loss = step(...)
        sp.sync_on(loss)          # honest timing under async dispatch
    from deepspeed_tpu.telemetry.export import write_chrome_trace
    write_chrome_trace("trace.json")   # load in ui.perfetto.dev

Training runs enable it via the ``"telemetry"`` config block
(runtime/config.py); serving via ``ServingConfig.telemetry``. See
docs/observability.md.
"""

from .trace import (Span, Tracer, RecompileWatchdog, get_tracer,
                    configure_tracer)
from .export import (chrome_trace, write_chrome_trace, chrome_trace_slice,
                     metrics_snapshot, write_snapshot, prometheus_dump,
                     span_aggregates, comm_table)
from .monitor_sink import TelemetryMonitor
from .goodput import GoodputLedger, get_ledger, configure_ledger
from .statusz import StatuszServer
from .flight_recorder import FlightRecorder
from .hostagg import HostAggregator
from .compileplane import (CompileLedger, HBMLedger, fingerprint_args,
                           diff_fingerprints)
from .overlap import OverlapAnalyzer, interval_overlap, overlap_from_events
from .disttrace import (TraceContext, FleetAggregator, merge_chrome_traces,
                        split_events_by_replica, CRITICAL_PATH_STAGES)
from .scorecard import (SCORECARD_KIND, INVARIANTS, check_invariants,
                        fold_scorecard, diff_scorecards, write_scorecard)

__all__ = ["Span", "Tracer", "RecompileWatchdog", "get_tracer",
           "configure_tracer", "chrome_trace", "write_chrome_trace",
           "chrome_trace_slice", "metrics_snapshot", "write_snapshot",
           "prometheus_dump", "span_aggregates", "comm_table",
           "TelemetryMonitor", "GoodputLedger", "get_ledger",
           "configure_ledger", "StatuszServer", "FlightRecorder",
           "HostAggregator", "CompileLedger", "HBMLedger",
           "fingerprint_args", "diff_fingerprints", "OverlapAnalyzer",
           "interval_overlap", "overlap_from_events",
           "TraceContext", "FleetAggregator", "merge_chrome_traces",
           "split_events_by_replica", "CRITICAL_PATH_STAGES",
           "SCORECARD_KIND", "INVARIANTS", "check_invariants",
           "fold_scorecard", "diff_scorecards", "write_scorecard"]
