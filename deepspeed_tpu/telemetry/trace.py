"""Structured tracer — the substrate of deepspeed_tpu observability.

One per-process ``Tracer`` owns a fixed-capacity ring buffer of ``Span``
records plus a lightweight counter pipeline. Spans are host-side wall-time
intervals opened with ``tracer.span("fwd")`` context managers; under XLA's
async dispatch a raw host interval only measures *dispatch*, so spans carry
explicit sync points: ``sp.sync_on(outputs)`` blocks on the step's outputs
before the end timestamp is taken (the CUDA-event analogue of
utils/timer.py's ``stop(sync=True)``).

Four record kinds:

- complete spans (``ph='X'``): nested host intervals — fwd/bwd/step,
  dispatch, prefill, decode ticks. Nesting is depth-tracked per thread.
- async spans (``ph='b'``/``'e'``): intervals that outlive any one stack
  frame — a serving request's queue→prefill→decode→complete lifecycle,
  keyed by request id.
- counters: latest-value metrics (MFU, recompiles, queue depth, ...) in
  one process-wide gauge space — everything the training engine and the
  serving stack record lands here, so the metrics snapshot and Prometheus
  dump see it all. Monitor-EVENT fan-out stays per-producer: the engine
  and ``ServingMetrics`` buffer their own ``(tag, value, step)`` batches
  for ``MonitorMaster.write_events`` (a shared event queue would let two
  engines in one process drain each other's events).
- phase records: ``(name, t0_ns, t1_ns, a, b)`` tuples — where a serving
  tick and a train step spend their host time (``serve/...``,
  ``train/...``, ``gc``), in a fixed ring of their own. ALWAYS recorded,
  whatever ``enabled`` says, so a running process can be asked
  (``get_tracer().phases()``) without a restart; while a ``jax.profiler``
  trace is being taken each is also a ``dstpu/<name>`` annotation on the
  profiler's clock.

Beside the rings the tracer keeps a registry of the process's compiled
programs (``note_program``, at each program's first call) and, built when
asked, each one's scope table (``scope_tables``; telemetry/hlo_cost.py): what
joins a ``jax.profiler`` device trace's operations to the program's own
``named_scope`` words.

Disabled is the default and costs nothing: ``span()`` returns a shared
no-op singleton — no ``Span`` object is ever allocated (asserted by
tests/unit/test_telemetry.py). Counters and phase records stay live
regardless, since the monitor pipeline and the question "what was the
host doing in that gap" must work without tracing.

Exporters (Chrome trace JSON for Perfetto, metrics snapshot, Prometheus
text) live in telemetry/export.py; the ``MonitorMaster`` sink in
telemetry/monitor_sink.py.
"""

import gc
import itertools
import os
import threading
import time
import weakref
from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["Span", "Tracer", "RecompileWatchdog", "get_tracer",
           "configure_tracer", "avals_of"]

_NOSYNC = object()


def _default_sync():
    """Best-effort full-device sync for ``sync=True`` spans without an
    output to block on (accurate spans should prefer ``sync_on(value)``)."""
    try:
        import jax
        jax.effects_barrier()
    except Exception:
        pass


def avals_of(args):
    """A call's arguments as ``Tracer.note_program`` keeps them: every array
    as a ``ShapeDtypeStruct`` (its sharding where it is committed to one:
    an uncommitted array is lowered as the call lowers it, unspecified),
    anything else as it is. Holds no buffer."""
    import jax

    def one(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
            sharding=x.sharding if getattr(x, "committed", False) else None)
    return jax.tree.map(one, args)


def _block_on(value):
    try:
        import jax
        jax.block_until_ready(value)
    except Exception:
        pass


class Span:
    """One record in the ring buffer. Also its own context manager, so an
    enabled ``tracer.span(...)`` costs exactly one allocation."""

    __slots__ = ("name", "cat", "ts_us", "dur_us", "depth", "tid", "args",
                 "ph", "aid", "_tracer", "_sync", "_sync_val")

    def __init__(self, tracer, name: str, cat: str = "host",
                 args: Optional[Dict[str, Any]] = None, sync: bool = False,
                 ph: str = "X", aid: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.ph = ph
        self.aid = aid
        self.ts_us = 0.0
        self.dur_us = 0.0
        self.depth = 0
        self.tid = threading.get_ident()
        self._tracer = tracer
        self._sync = sync
        self._sync_val = _NOSYNC

    def sync_on(self, value):
        """Block on ``value`` (any pytree of jax arrays) at span exit before
        the end timestamp — the honest duration under async dispatch."""
        self._sync_val = value
        return value

    def set(self, **kwargs):
        """Attach/update args on an open span."""
        if self.args is None:
            self.args = {}
        self.args.update(kwargs)

    def __enter__(self):
        tr = self._tracer
        self.depth = tr._enter_depth()
        self.ts_us = time.perf_counter_ns() / 1e3
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._sync_val is not _NOSYNC:
            _block_on(self._sync_val)
        elif self._sync:
            _default_sync()
        self.dur_us = time.perf_counter_ns() / 1e3 - self.ts_us
        tr = self._tracer
        tr._exit_depth()
        # drop the references a retained record doesn't need
        self._sync_val = _NOSYNC
        tr._record(self)
        return False


class _NullSpan:
    """Shared no-op span: what a disabled tracer hands out. A singleton —
    the zero-cost-when-disabled contract is that no object is allocated."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def sync_on(self, value):
        return value

    def set(self, **kwargs):
        pass


_NULL_SPAN = _NullSpan()

#: one phase record: (name, t0_ns, t1_ns, a, b) — ``perf_counter_ns``
#: stamps and two ints whose meaning belongs to the name
PhaseRecord = Tuple[str, int, int, int, int]


class _Phase:
    """An open phase: what ``Tracer.phase`` hands out. ``a`` and ``b`` may
    be set until exit (a tick learns how many slots it left active only
    at its end). Lives for the ``with`` block; the ring keeps the tuple."""

    __slots__ = ("_tracer", "name", "a", "b", "_t0", "_annotation")

    def __init__(self, tracer, name: str, a: int, b: int):
        self._tracer = tracer
        self.name = name
        self.a = a
        self.b = b

    def __enter__(self):
        cls = self._tracer._profiler_annotation()
        self._annotation = None
        if cls is not None:
            self._annotation = cls("dstpu/" + self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tracer.record_phase(self.name, self._t0,
                                  time.perf_counter_ns(), self.a, self.b)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Per-process structured tracer: span ring buffer, counter pipeline
    and the always-on ring of phase records."""

    def __init__(self, buffer_size: int = 65536, enabled: bool = False,
                 phase_buffer_size: int = 32768):
        self.enabled = enabled
        self.sync_spans = True
        self._cap = max(16, int(buffer_size))
        self._ring: List[Optional[Span]] = [None] * self._cap
        self._head = 0          # next write index
        self._total = 0         # spans ever recorded (wraparound detector)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._counters: Dict[str, Any] = {}
        # gauge ownership: tag -> id(owner) for gauges registered by a
        # closable producer (an engine); release_counters(owner) drops the
        # tags that owner still holds, so /metrics and prometheus_dump
        # never report stale values from a closed engine. Last writer
        # wins: a tag two co-resident engines both write belongs to
        # whichever wrote it last, and only that one's close() removes it.
        self._counter_owners: Dict[str, int] = {}
        # phase records: a ring apart from the spans', written whether or
        # not ``enabled``. ``next()`` on the count hands every record its
        # own slot in one step that neither another thread nor a gc
        # callback firing between two bytecodes can split, so the hot
        # path takes no lock.
        self._phase_cap = max(16, int(phase_buffer_size))
        self._phase_ring: List[Optional[PhaseRecord]] = \
            [None] * self._phase_cap
        self._phase_seq = itertools.count()
        self._phase_total = 0
        self._annotation_cls = None     # jax.profiler.TraceAnnotation, lazily
        self._profiled_ns = 0       # when a phase last saw a profiler trace
        self._gc_owners: set = set()
        self._gc_t0 = 0
        # compiled programs by (module name, key): a weak reference to the
        # jitted function, the avals and mesh of its first call, when that
        # was, and its scope table once built. Nothing here keeps an engine
        # alive: a jitted body closes over its engine, hence the weak
        # reference; a table is a dict of strings.
        self._programs: Dict[Tuple[str, Any], list] = {}

    # ------------------------------------------------------------ configure
    def configure(self, config=None, **overrides):
        """Apply a ``TelemetryConfig`` (or kwargs): enabled, buffer_size,
        sync_spans. Resizing the buffer clears recorded spans. The kwarg
        ``phase_buffer_size`` resizes the phase ring and clears the
        recorded phases: a process whose readers look back over more than
        32,768 records asks for a longer ring before its first tick (a
        serving tick writes about twelve)."""
        kv = {}
        if config is not None:
            for k in ("enabled", "buffer_size", "sync_spans"):
                if hasattr(config, k):
                    kv[k] = getattr(config, k)
        kv.update(overrides)
        if "buffer_size" in kv and int(kv["buffer_size"]) != self._cap:
            with self._lock:
                self._cap = max(16, int(kv["buffer_size"]))
                self._ring = [None] * self._cap
                self._head = 0
                self._total = 0
        if "phase_buffer_size" in kv and \
                max(16, int(kv["phase_buffer_size"])) != self._phase_cap:
            # the writers take no lock: the ring is made before the
            # capacity that indexes it grows, and shrunk after it falls
            cap = max(16, int(kv["phase_buffer_size"]))
            self._phase_cap = min(cap, self._phase_cap)
            self._phase_ring = [None] * cap
            self._phase_cap = cap
            self._phase_seq = itertools.count()
            self._phase_total = 0
        if "sync_spans" in kv:
            self.sync_spans = bool(kv["sync_spans"])
        if "enabled" in kv:
            self.enabled = bool(kv["enabled"])
        return self

    # ----------------------------------------------------------------- spans
    def span(self, name: str, cat: str = "host",
             args: Optional[Dict[str, Any]] = None, sync: bool = False):
        """Open a nested wall-time span. ``sync=True`` fences the device at
        exit; for accuracy prefer ``sp.sync_on(step_outputs)``. Disabled
        tracer: returns the shared no-op singleton (no allocation)."""
        if not self.enabled:
            return _NULL_SPAN
        return Span(self, name, cat=cat, args=args,
                    sync=sync and self.sync_spans)

    def instant(self, name: str, cat: str = "host",
                args: Optional[Dict[str, Any]] = None):
        """Zero-duration marker event."""
        if not self.enabled:
            return
        sp = Span(self, name, cat=cat, args=args, ph="i")
        sp.ts_us = time.perf_counter_ns() / 1e3
        self._record(sp)

    def async_begin(self, name: str, aid: int, cat: str = "async",
                    args: Optional[Dict[str, Any]] = None):
        """Open one side of an async span (an interval that outlives the
        current stack frame, e.g. a serving request). Pair with
        ``async_end`` on the same (name, aid)."""
        if not self.enabled:
            return
        sp = Span(self, name, cat=cat, args=args, ph="b", aid=aid)
        sp.ts_us = time.perf_counter_ns() / 1e3
        self._record(sp)

    def async_end(self, name: str, aid: int, cat: str = "async",
                  args: Optional[Dict[str, Any]] = None):
        if not self.enabled:
            return
        sp = Span(self, name, cat=cat, args=args, ph="e", aid=aid)
        sp.ts_us = time.perf_counter_ns() / 1e3
        self._record(sp)

    def counter_track(self, name: str, values: Dict[str, float],
                      cat: str = "mem"):
        """Record a Chrome counter sample (``ph='C'``): Perfetto renders
        successive samples of the same ``name`` as a stacked counter
        track — the HBM ledger's waterline timeline rides this."""
        if not self.enabled:
            return
        sp = Span(self, name, cat=cat, args=dict(values), ph="C")
        sp.ts_us = time.perf_counter_ns() / 1e3
        self._record(sp)

    def _record(self, span: Span):
        with self._lock:
            self._ring[self._head] = span
            self._head = (self._head + 1) % self._cap
            self._total += 1

    def _enter_depth(self) -> int:
        d = getattr(self._tls, "depth", 0)
        self._tls.depth = d + 1
        return d

    def _exit_depth(self):
        self._tls.depth = max(0, getattr(self._tls, "depth", 1) - 1)

    def spans(self) -> List[Span]:
        """Recorded spans, oldest first (at most ``buffer_size``; older
        records are overwritten — the ring never grows)."""
        with self._lock:
            if self._total < self._cap:
                return [s for s in self._ring[:self._head] if s is not None]
            return ([s for s in self._ring[self._head:] if s is not None] +
                    [s for s in self._ring[:self._head] if s is not None])

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wraparound."""
        return max(0, self._total - self._cap)

    # ---------------------------------------------------------------- phases
    def phase(self, name: str, a: int = 0, b: int = 0) -> _Phase:
        """Open a nested host phase: ``with tracer.phase("serve/admit") as
        ph: ...; ph.a = admitted``. Recorded whether or not ``enabled``.
        Phases nest by their stamps alone — a reader gives each moment to
        the innermost phase open then — and one phase may be recorded in
        several pieces of the same name, which a reader adds up."""
        return _Phase(self, name, a, b)

    def record_phase(self, name: str, t0_ns: int, t1_ns: int,
                     a: int = 0, b: int = 0):
        """Record a finished interval from two ``time.perf_counter_ns()``
        stamps — the plain call for intervals that outlive a stack frame
        (a request's wait in the queue)."""
        i = next(self._phase_seq)
        self._phase_ring[i % self._phase_cap] = (name, t0_ns, t1_ns, a, b)
        # last writer wins: a racing writer can leave this one low until
        # the next record, never high
        self._phase_total = i + 1

    def phases(self) -> List[PhaseRecord]:
        """The recorded phases, oldest first by their end stamp (at most
        ``phase_buffer_size``; older ones are overwritten)."""
        recs = [r for r in list(self._phase_ring) if r is not None]
        recs.sort(key=lambda r: r[2])
        return recs

    @property
    def phases_total(self) -> int:
        """Phase records ever written (since the last ``clear``)."""
        return self._phase_total

    @property
    def phases_dropped(self) -> int:
        """Phase records overwritten by ring wraparound."""
        return max(0, self._phase_total - self._phase_cap)

    def _profiler_annotation(self):
        """``jax.profiler.TraceAnnotation`` while a profiler trace is being
        taken (one flag check), else ``None``."""
        cls = self._annotation_cls
        if cls is None:
            try:
                from jax.profiler import TraceAnnotation as cls
            except ImportError:
                cls = False
            self._annotation_cls = cls
        if cls and cls.is_enabled():
            self._profiled_ns = time.perf_counter_ns()
            return cls
        return None

    # -------------------------------------------------------------- programs
    def note_program(self, module: str, key: Any, fn, avals, mesh=None):
        """Register a compiled program at its first call: ``module`` is its
        name in a device trace (``jit_pf``, ``jit_dec``,
        ``jit_train_step``), ``key`` what tells two programs of one module
        name apart (the pool programs' key in ``_slot_fns``:
        ``("slot_prefill", bucket, max_len)``), ``fn`` the jitted function,
        ``avals`` its arguments as ``ShapeDtypeStruct``s with shardings and
        ``mesh`` the mesh it is called under. Lowers nothing: the caller
        marks ``fn`` noted and pays one attribute test a call after that.
        The registry holds ``fn`` weakly (its body closes over its engine);
        a program noted again under the same name and key (a second engine
        of the same shape) takes the entry over."""
        self._programs[(module, key)] = [weakref.ref(fn), avals, mesh,
                                         time.perf_counter_ns(), None]

    def scope_tables(self) -> Dict[str, Dict[Any, dict]]:
        """``{module name: {program key: {instruction name: scope}}}`` of
        every noted program (``hlo_cost.scope_table`` of its optimized HLO).
        A table not built yet is built now, from
        ``fn.lower(*avals).compile().as_text()``: the program the process
        already runs, so the persistent compile cache makes the compile a
        fetch, and its instructions carry the names a device trace of the
        process shows. A program whose function is gone and whose table was
        never built is forgotten. Never called on a call path: an operator
        or a trace's reader asks by hand (an engine that closes after a
        profiler trace leaves its own through ``keep_tables``)."""
        out: Dict[str, Dict[Any, dict]] = {}
        for (module, key), entry in list(self._programs.items()):
            if entry[4] is None and entry[0]() is None:
                del self._programs[(module, key)]
                continue
            if entry[4] is None:
                self._build_table(module, key, entry)
            out.setdefault(module, {})[key] = entry[4]
        return out

    def keep_tables(self):
        """An engine's last act as it closes: build now, while they can
        still be lowered, the tables of the programs noted before a phase
        of this tracer last saw a ``jax.profiler`` trace, so that they
        outlive the engine exactly where a device trace exists to join them
        to. Nothing in a process no profiler traced: that one never lowers
        a program twice. A failure is logged and not raised: it must not
        keep an engine from closing."""
        if not self._profiled_ns:
            return
        try:
            for (module, key), entry in list(self._programs.items()):
                if entry[4] is None and entry[0]() is not None \
                        and entry[3] <= self._profiled_ns:
                    self._build_table(module, key, entry)
        except Exception as e:      # noqa: BLE001
            from ..utils.logging import logger
            logger.warning(f"scope tables not kept: {type(e).__name__}: {e}")

    def _build_table(self, module, key, entry):
        from .hlo_cost import scope_table
        from ..utils.logging import logger
        fn, avals, mesh = entry[0](), entry[1], entry[2]
        t0 = time.perf_counter()
        with mesh if mesh is not None else nullcontext():
            text = fn.lower(*avals).compile().as_text()
        t1 = time.perf_counter()
        table = entry[4] = scope_table(text)
        unnamed = sum(v is None for v in table.values())
        inferred = sum(1 for v in table.values() if v and v[0] == "?")
        logger.info(
            f"scope table {module} {key}: {len(table)} instructions "
            f"({inferred} inferred, {unnamed} unnamed), compile "
            f"{t1 - t0:.2f} s, parse {time.perf_counter() - t1:.2f} s")
        # every program of an engine scans its layers under ``layers``: a
        # table without the word is another program's, handed over by a
        # compile cache whose key leaves the metadata out
        if module in ("jit_pf", "jit_dec", "jit_train_step") and not any(
                v and "layers" in v.split("/") for v in table.values()):
            logger.warning(
                f"scope table {module} {key} holds no 'layers': the "
                f"executable carries another program's names (a compile "
                f"cache keyed without metadata?); its scopes are not to "
                f"be trusted")

    def watch_gc(self, owner: Any):
        """Record garbage collections as ``gc`` phases (generation, objects
        collected) while any ``owner`` — an engine — is open: every
        generation-2 collection, and any that took over 1 ms."""
        if not self._gc_owners:
            gc.callbacks.append(self._on_gc)
        self._gc_owners.add(id(owner))

    def unwatch_gc(self, owner: Any):
        self._gc_owners.discard(id(owner))
        if not self._gc_owners and self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, when: str, info: Dict[str, int]):
        if when == "start":
            self._gc_t0 = time.perf_counter_ns()
            return
        t1 = time.perf_counter_ns()
        if info["generation"] == 2 or t1 - self._gc_t0 > 1_000_000:
            self.record_phase("gc", self._gc_t0, t1, info["generation"],
                              info["collected"])

    # -------------------------------------------------------------- counters
    def set_counter(self, tag: str, value: float, step: Optional[int] = None,
                    owner: Any = None):
        """Latest-value gauge update; works with tracing disabled (gauges
        must not depend on span recording). ``owner`` ties the tag to a
        closable producer for ``release_counters``."""
        self._counters[tag] = (value, step)
        if owner is not None:
            self._counter_owners[tag] = id(owner)
        # owner=None leaves any existing ownership standing: the
        # TelemetryMonitor sink mirrors an engine's own events back into
        # the gauge space ownerless, and that mirror must not strip the
        # engine's right to retract its tags at close()

    def release_counters(self, owner: Any):
        """Drop every gauge still owned by ``owner`` (engine close path):
        a closed engine's queue depth / step time must not linger in
        prometheus_dump() or /metrics as if it were live."""
        oid = id(owner)
        for tag in [t for t, o in self._counter_owners.items() if o == oid]:
            del self._counter_owners[tag]
            self._counters.pop(tag, None)

    def counters(self) -> Dict[str, Any]:
        return dict(self._counters)

    def counter_value(self, tag: str, default=None):
        """Latest value of one gauge (without its step), or ``default`` —
        the cheap single-tag read for per-tick consumers that must not pay
        for a full counters() copy."""
        val = self._counters.get(tag)
        return val[0] if val is not None else default

    # ------------------------------------------------------------------ misc
    def clear(self):
        with self._lock:
            self._ring = [None] * self._cap
            self._head = 0
            self._total = 0
        self._counters.clear()
        self._counter_owners.clear()
        self._phase_ring = [None] * self._phase_cap
        self._phase_seq = itertools.count()
        self._phase_total = 0


class RecompileWatchdog:
    """Counts jit cache growth per step (recompiles). A shape/dtype change
    that silently recompiles the train step is the #1 TPU perf cliff; this
    makes it a counter instead of a mystery.

    ``observe(fn)`` samples ``fn._cache_size()`` and returns how many NEW
    executables appeared since the last observation of that fn (0 on first
    sight — the initial compile is expected). Holds a reference to each
    watched fn so ids stay unique."""

    def __init__(self):
        self._watched: Dict[int, Any] = {}
        self.recompiles = 0

    def seen(self, fn) -> bool:
        """Whether ``fn`` has been observed before — False means the next
        call pays the initial compile (the goodput ledger's ``compile``
        bucket, distinct from a ``recompile``)."""
        return id(fn) in self._watched

    def observe(self, fn, tracer: Optional[Tracer] = None,
                label: str = "train_step", owner: Any = None) -> int:
        size_of = getattr(fn, "_cache_size", None)
        if size_of is None:
            return 0
        try:
            size = int(size_of())
        except Exception:
            return 0
        prev = self._watched.get(id(fn))
        self._watched[id(fn)] = (fn, size)
        if prev is None:
            return 0
        delta = max(0, size - prev[1])
        if delta:
            self.recompiles += delta
            if tracer is not None:
                # gauge-only: the caller owns monitor-event fan-out
                tracer.set_counter("telemetry/recompiles", self.recompiles,
                                   owner=owner)
                tracer.instant(f"recompile:{label}", cat="warning",
                               args={"new_executables": delta,
                                     "total": self.recompiles})
        return delta


_TRACER: Optional[Tracer] = None


def get_tracer() -> Tracer:
    """The process-global tracer (created disabled; ``DSTPU_TELEMETRY=1``
    enables it from the environment for script-level use)."""
    global _TRACER
    if _TRACER is None:
        _TRACER = Tracer(
            enabled=os.environ.get("DSTPU_TELEMETRY", "") in ("1", "true"))
    return _TRACER


def configure_tracer(config=None, **overrides) -> Tracer:
    """Configure the global tracer from a ``TelemetryConfig`` block
    (runtime/config.py) or kwargs; returns it."""
    return get_tracer().configure(config, **overrides)
