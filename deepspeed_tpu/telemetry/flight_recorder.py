"""Flight recorder — anomaly-triggered postmortem bundles.

The tracer, goodput ledger, and statusz server can tell you *that* a step
was slow; by the time a human looks, the span ring has wrapped and the
moment is gone. The flight recorder is the capture layer: an always-on,
bounded in-memory ring of recent **step records** (step wall time,
goodput-bucket deltas, collective op/byte deltas, serving queue/SLO
state) plus a set of **trigger rules** that, when an anomaly fires, write
a self-contained **postmortem bundle** to disk while the evidence is
still in memory:

- ``slow_step``   — step wall time exceeded ``slow_step_factor`` × the
  EMA of recent steps (or the absolute ``slow_step_ms`` threshold).
  Compile/recompile steps are excluded from both the check and the EMA —
  they are separately attributed and would poison the baseline.
- ``recompile``   — the RecompileWatchdog saw jit-cache growth.
- ``sentinel``    — the training sentinel flagged a NaN loss / grad-norm
  spike (resilience/sentinel.py calls in).
- ``slo_burn``    — a serving replica's error-budget burn rate crossed
  ``slo_burn_threshold`` (edge-triggered by serving/engine.py).
- ``preemption``  — a preemption signal latched (always bypasses
  debounce: there may be no second chance to capture).
- ``straggler``   — the host aggregator (telemetry/hostagg.py) attributed
  the step time to one slow host.
- ``overlap_drop`` — a recompile produced a step program whose HLO
  static overlap fraction fell below ``compile_plane.overlap_floor``
  (telemetry/overlap.py: a schedule that silently de-overlapped).
- ``acceptance_drop`` — a serving replica's speculative-decode
  acceptance EMA fell below ``speculative.acceptance_floor``
  (edge-triggered by serving/engine.py after warmup: speculation that
  stopped paying for itself — draft drift, workload shift).
- ``manual``      — an explicit ``/debug/capture`` request.

A bundle is ONE JSON file (atomic tmp+rename write) containing the
last-N step records, the Perfetto trace slice around the trigger
(``trace_ms`` window), the goodput snapshot, the registered status
sections (config fingerprint, counters, checkpoint/rollback history),
the live tracer counters, and the XLA cost-analysis summary of the
active compiled executable. Retention is keep-last-``keep`` bundles, and
triggers are **debounced per kind** (``debounce_s``) so a pathological
run cannot fill the disk or capture in a loop — while one slow step, one
recompile, and one NaN arriving together still yield one bundle each.

Fully off by default: a disabled config means no recorder object, no
thread (the recorder never starts one — bundles are written inline at
trigger time, which is rare by construction), no directory, no files.
"""

import json
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from .goodput import get_ledger
from .trace import get_tracer

__all__ = ["FlightRecorder", "TRIGGER_KINDS"]

#: the trigger-rule vocabulary (bundle filenames carry the kind).
#: ``trial_best`` / ``trial_worst`` are fired once per measured autotuning
#: sweep (autotuning/measure.py) with the winning and losing trial's
#: goodput table, compile events, and score breakdown embedded — every
#: tuning decision stays auditable post-hoc.
TRIGGER_KINDS = ("slow_step", "recompile", "sentinel", "slo_burn",
                 "preemption", "straggler", "failover", "overlap_drop",
                 "acceptance_drop", "resize", "rollout_failed",
                 "trial_best", "trial_worst", "manual")


class FlightRecorder:
    """Bounded step-record ring + trigger rules + bundle writer."""

    def __init__(self, config=None, tracer=None, ledger=None,
                 clock=time.monotonic):
        def g(key, default):
            return getattr(config, key, default) if config is not None \
                else default

        self.tracer = tracer or get_tracer()
        self._ledger = ledger or get_ledger()
        self._clock = clock
        self.dir = str(g("dir", "flight_bundles"))
        self.keep = int(g("keep", 8))
        self.debounce_s = float(g("debounce_s", 30.0))
        self.slow_step_factor = float(g("slow_step_factor", 3.0))
        self.slow_step_ms = float(g("slow_step_ms", 0.0))
        self.warmup_steps = int(g("warmup_steps", 5))
        self.ema_alpha = float(g("ema_alpha", 0.2))
        self.trace_ms = float(g("trace_ms", 10_000.0))
        self.slo_burn_threshold = float(g("slo_burn_threshold", 2.0))
        self._records: "deque" = deque(maxlen=int(g("ring", 256)))
        #: name -> callable() -> dict; one bundle "status" section each
        self._providers: Dict[str, Callable[[], dict]] = {}
        self._cost_provider: Optional[Callable[[], dict]] = None
        self._compile_plane = None       # CompileLedger (attach_compile_plane)
        #: callable() -> [trace_id, ...]: the distributed-trace ids in
        #: flight on this member at capture time — what lets a router
        #: correlate same-trace bundles across replica bundle dirs
        self._trace_provider: Optional[Callable[[], list]] = None
        self._closed = False
        self.ema_ms = 0.0
        self._baseline_steps = 0       # records feeding the EMA
        self._last_goodput: Dict[str, float] = {}
        self._last_comm: Optional[Dict[str, int]] = None
        self._last_fire_at: Dict[str, float] = {}   # per-kind debounce
        self.trigger_counts: Dict[str, int] = {}
        self.suppressed = 0            # debounced (counted, not captured)
        self.last_fire: Optional[Dict[str, Any]] = None
        self._next_id = 1

    # ------------------------------------------------------------- registry
    def add_provider(self, name: str, provider: Callable[[], dict]):
        """Add a bundle status section (same shape as a statusz section:
        config fingerprint, counters, checkpoint history, ...)."""
        self._providers[name] = provider
        return self

    def set_cost_provider(self, provider: Callable[[], dict]):
        """Callable returning the XLA cost-analysis summary of the active
        compiled executable (the engine captures it when the MFU profiler
        traces the step fn)."""
        self._cost_provider = provider
        return self

    def attach_compile_plane(self, ledger):
        """Embed the compile ledger (telemetry/compileplane.py) in every
        bundle: fingerprints, recompile diffs, and per-event cost/memory
        summaries — a recompile bundle then names the exact argument
        whose shape changed instead of just counting the recompile."""
        self._compile_plane = ledger
        return self

    def set_trace_provider(self, provider: Callable[[], list]):
        """Callable returning the distributed trace ids currently in
        flight on this member (telemetry/disttrace.py); every bundle
        embeds them as ``in_flight_traces`` so cross-replica postmortems
        join on the request, not on wall-clock proximity."""
        self._trace_provider = provider
        return self

    # ------------------------------------------------------------ recording
    def record_step(self, step: int, dur_ms: float, compile: bool = False,
                    recompile: bool = False, slow_check: bool = True,
                    extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
        """Append one finished step/tick to the ring and run the slow-step
        rule. Returns the bundle path if the rule fired, else None."""
        record: Dict[str, Any] = {"step": int(step), "t": time.time(),
                                  "dur_ms": round(float(dur_ms), 3)}
        if compile:
            record["compile"] = True
        if recompile:
            record["recompile"] = True
        if self._ledger.enabled:
            totals = self._ledger.totals()
            deltas = {name: round(secs - self._last_goodput.get(name, 0.0), 6)
                      for name, secs in totals.items()
                      if secs - self._last_goodput.get(name, 0.0) > 1e-9}
            self._last_goodput = totals
            if deltas:
                record["goodput"] = deltas
        stats = self._comm_stats()
        if stats is not None:
            # diff every counter comm_stats exposes (ops + wire/logical/
            # inter-host/intra-host bytes) so the record shows the step's
            # actual link traffic, compressed size included
            prev = self._last_comm or {}
            self._last_comm = stats
            record["comm"] = {k: v - prev.get(k, 0)
                              for k, v in stats.items()}
        if extra:
            record.update(extra)
        self._records.append(record)

        baseline = not (compile or recompile)
        fired = None
        if slow_check and baseline and \
                self._baseline_steps >= self.warmup_steps and self.ema_ms > 0:
            slow = dur_ms > self.slow_step_factor * self.ema_ms or \
                (self.slow_step_ms > 0 and dur_ms > self.slow_step_ms)
            if slow:
                record["slow"] = True
                fired = self.trigger(
                    "slow_step",
                    f"step {step}: {dur_ms:.1f}ms vs EMA "
                    f"{self.ema_ms:.1f}ms "
                    f"(trigger {self.slow_step_factor:g}x)", step=step)
        if baseline:
            # the anomalous step still feeds the EMA (alpha-damped), so a
            # genuine regime change stops triggering after a few steps
            self.ema_ms = dur_ms if self._baseline_steps == 0 else \
                (1 - self.ema_alpha) * self.ema_ms + self.ema_alpha * dur_ms
            self._baseline_steps += 1
        return fired

    @staticmethod
    def _comm_stats() -> Optional[Dict[str, int]]:
        # deferred: comm.comm imports telemetry.trace; importing it here at
        # module level would be order-sensitive
        try:
            from ..comm.comm import comm_stats
            return comm_stats()
        except Exception:
            return None

    # ------------------------------------------------------------- triggers
    def trigger(self, kind: str, detail: str = "",
                step: Optional[int] = None,
                force: bool = False) -> Optional[str]:
        """Fire one trigger rule. Writes a bundle unless the per-kind
        debounce suppresses it (``force`` bypasses — preemption and
        explicit captures must not be dropped). Returns the bundle path
        or None when debounced."""
        self.trigger_counts[kind] = self.trigger_counts.get(kind, 0) + 1
        now = self._clock()
        last = self._last_fire_at.get(kind)
        if not force and last is not None and \
                now - last < self.debounce_s:
            self.suppressed += 1
            return None
        self._last_fire_at[kind] = now
        return self._write_bundle(kind, detail, step)

    # --------------------------------------------------------------- bundles
    def _write_bundle(self, kind: str, detail: str,
                      step: Optional[int]) -> str:
        from .export import chrome_trace_slice
        bid = self._next_id
        self._next_id += 1
        doc: Dict[str, Any] = {
            "id": bid,
            "kind": kind,
            "detail": detail,
            "step": step,
            "time": time.time(),
            "trigger_counts": dict(self.trigger_counts),
            "records": list(self._records),
            "trace": chrome_trace_slice(self.tracer, last_ms=self.trace_ms),
            "counters": {tag: val for tag, (val, _s)
                         in self.tracer.counters().items()},
            "status": {},
        }
        if self._trace_provider is not None:
            try:
                doc["in_flight_traces"] = list(self._trace_provider())
            except Exception as e:
                doc["in_flight_traces"] = []
                doc["trace_provider_error"] = str(e)
        if self._ledger.enabled:
            doc["goodput"] = self._ledger.snapshot()
        for name, provider in list(self._providers.items()):
            try:
                doc["status"][name] = provider()
            except Exception as e:   # a broken provider must not lose the
                doc["status"][name] = {"error": str(e)}   # whole bundle
        if self._cost_provider is not None:
            try:
                doc["cost"] = self._cost_provider()
            except Exception as e:
                doc["cost"] = {"error": str(e)}
        if self._compile_plane is not None:
            try:
                doc["compile_plane"] = self._compile_plane.bundle_section()
            except Exception as e:
                doc["compile_plane"] = {"error": str(e)}
        os.makedirs(self.dir, exist_ok=True)
        fname = f"bundle-{bid:06d}-{kind}.json"
        path = os.path.join(self.dir, fname)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, default=str)
        os.replace(tmp, path)          # a reader never sees a torn bundle
        self._retain()
        self.last_fire = {"id": bid, "kind": kind, "detail": detail,
                          "step": step, "time": doc["time"], "path": path}
        self.tracer.set_counter("recorder/bundles",
                                float(sum(self.trigger_counts.values())
                                      - self.suppressed), owner=self)
        self.tracer.instant(f"flight_recorder:{kind}", cat="warning",
                            args={"detail": detail, "bundle": fname})
        return path

    def _bundle_files(self) -> List[str]:
        try:
            names = os.listdir(self.dir)
        except OSError:
            return []
        return sorted(n for n in names
                      if n.startswith("bundle-") and n.endswith(".json"))

    def _retain(self):
        files = self._bundle_files()
        for name in files[:max(0, len(files) - self.keep)]:
            try:
                os.remove(os.path.join(self.dir, name))
            except OSError:
                pass
        # cross-replica postmortems (crossrep-NNNN.json, written into
        # this dir by FleetAggregator.cross_replica_postmortem) obey the
        # same keep — a soak with a failover every few seconds must not
        # grow the bundle dir without bound
        try:
            names = sorted(n for n in os.listdir(self.dir)
                           if n.startswith("crossrep-")
                           and n.endswith(".json"))
        except OSError:
            return
        for name in names[:max(0, len(names) - self.keep)]:
            try:
                os.remove(os.path.join(self.dir, name))
            except OSError:
                pass

    def bundles(self) -> List[Dict[str, Any]]:
        """On-disk bundle index (newest last): id, kind, file, bytes."""
        out = []
        for name in self._bundle_files():
            parts = name[len("bundle-"):-len(".json")].split("-", 1)
            try:
                bid = int(parts[0])
            except ValueError:
                continue
            path = os.path.join(self.dir, name)
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            out.append({"id": bid, "kind": parts[1] if len(parts) > 1
                        else "?", "file": name, "bytes": size})
        return out

    def read_bundle(self, bid: int) -> Optional[str]:
        """Raw JSON text of bundle ``bid`` (the /debug/bundle download)."""
        for entry in self.bundles():
            if entry["id"] == bid:
                try:
                    with open(os.path.join(self.dir, entry["file"])) as f:
                        return f.read()
                except OSError:
                    return None
        return None

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Retract this recorder's gauges from the shared counter space
        (the owning engine/router's shutdown path) — a closed member's
        bundle count must not linger in /metrics as if it were live.
        Bundles on disk are untouched. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.tracer.release_counters(self)

    # -------------------------------------------------------------- summary
    def summary(self) -> Dict[str, Any]:
        """The statusz/ds_tpu_top view: bundle count, last fire + age."""
        out: Dict[str, Any] = {
            "bundles": len(self._bundle_files()),
            "dir": self.dir,
            "triggers": dict(self.trigger_counts),
            "suppressed": self.suppressed,
            "ema_ms": round(self.ema_ms, 3),
            "records": len(self._records),
        }
        if self.last_fire is not None:
            last = dict(self.last_fire)
            last["age_s"] = round(max(0.0, time.time() - last["time"]), 1)
            out["last"] = last
        return out
