"""Compile/memory plane — compile ledger with recompile diffs + HBM ledger.

The recompile watchdog (telemetry/trace.py) counts recompiles; this
answers **why**. The ``CompileLedger`` records every compile event of a
watched jitted function — the argument fingerprint (per-leaf
shape/dtype/sharding, donation flags), the wall time of the step that
paid the compile, the XLA ``cost_analysis()`` FLOPs/bytes summary, and
the ``memory_analysis()`` argument/output/temp breakdown — and on a
recompile emits a **diff against the previous fingerprint of the same
function**::

    arg 3 (batch)['input_ids']: s32[1,8,16] -> s32[1,8,8]

The diff lands on ``/statusz`` (the ``compile_plane`` section and a
banner), in flight-recorder recompile bundles
(``FlightRecorder.attach_compile_plane``), and in the ``ds_tpu_top``
screen — the operator reads *which argument changed shape* instead of a
bare recompile count.

Event detection is fingerprint-driven: a changed signature IS the
recompile cause the diff names, and the jit cache size is sampled as a
backstop for recompiles with an unchanged signature (static-argument or
weak-type changes). ``observe()`` runs before the call on both paths:
the train step donates its inputs and every serving slot program that
returns a KV pool donates the one it is given, so fingerprints must be
taken while the arrays are alive (``fn.lower`` only reads avals and
never consumes donated buffers).

Analysis capture: ``cost_analysis`` comes from the *lowered* stage (no
backend compile — global-program FLOPs). ``memory_analysis`` needs a
compiled executable, so when ``compile_plane.memory_analysis`` is on the
ledger AOT-compiles the lowered module once per compile *event* — that
measures the isolated XLA compile wall time and yields the per-device
memory breakdown plus the optimized HLO (collectives + async-overlap
summary via telemetry/hlo_cost.py), at the cost of a second compile of
that event's program. Compile events are rare by construction; steady
state pays only the per-call fingerprint.

The ``HBMLedger`` is the memory half: live per-device bytes attributed
by role — params / grads / optimizer state / activations (executable
temps) / KV slot pool — from pytree accounting over each array's
addressable shards, exported as ``dstpu_mem_*`` gauges, a ``memory``
statusz section, and a Perfetto counter-track waterline in the span ring
(``Tracer.counter_track``).

Off ⇒ allocates nothing: no ``compile_plane`` config block means no
ledger object, no per-call fingerprints, no gauges (the PR 4/5 pattern).
"""

import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .hlo_cost import (collect_collectives, cost_summary,
                       hlo_overlap_summary, memory_summary)
from .trace import get_tracer

__all__ = ["CompileLedger", "HBMLedger", "fingerprint_args",
           "diff_fingerprints", "HBM_ROLES"]

#: jnp dtype name -> the short HLO spelling used in fingerprints/diffs
_DTYPE_SHORT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
                "float64": "f64", "int64": "s64", "int32": "s32",
                "int16": "s16", "int8": "s8", "uint64": "u64",
                "uint32": "u32", "uint16": "u16", "uint8": "u8",
                "bool": "pred", "float8_e4m3fn": "f8e4m3",
                "float8_e5m2": "f8e5m2"}


def _leaf_desc(x, donated: bool = False) -> str:
    """One leaf's fingerprint: ``f32[8,512]@(dp,-)`` — dtype, shape, and
    the NamedSharding partition spec when the array carries one (single-
    device arrays have no spec and print bare). Non-array leaves (static
    python scalars, None) fingerprint by repr."""
    shape = getattr(x, "shape", None)
    if shape is None:
        if x is None:
            return "None"
        if isinstance(x, (bool, int, float, complex)):
            # python scalars enter jit as weak-typed arrays: a VALUE
            # change doesn't recompile, so fingerprint the type only
            return f"py_{type(x).__name__}"
        return repr(x)
    dtype = str(getattr(x, "dtype", "?"))
    desc = (_DTYPE_SHORT.get(dtype, dtype) +
            "[" + ",".join(str(d) for d in shape) + "]")
    spec = getattr(getattr(x, "sharding", None), "spec", None)
    if spec is not None:
        desc += "@(" + ",".join(
            "-" if p is None else
            ("+".join(p) if isinstance(p, (tuple, list)) else str(p))
            for p in spec) + ")"
    if donated:
        desc += " donated"
    return desc


def fingerprint_args(args: Sequence[Any],
                     names: Optional[Sequence[str]] = None,
                     donated: Sequence[int] = ()) -> List[Tuple[str, str]]:
    """Fingerprint one call's arguments as ordered (key, descriptor)
    pairs, one per pytree leaf: key = ``arg 3 (batch)['input_ids']``,
    descriptor = ``s32[1,8,16]``. Pure host-side shape inspection — no
    device work, safe on donated buffers *before* the call."""
    import jax

    donated = set(donated)
    out: List[Tuple[str, str]] = []
    for i, arg in enumerate(args):
        label = f"arg {i}"
        if names is not None and i < len(names):
            label += f" ({names[i]})"
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        if not leaves:                    # None / empty subtree
            out.append((label, _leaf_desc(arg, i in donated)))
            continue
        for path, leaf in leaves:
            key = label + jax.tree_util.keystr(path)
            out.append((key, _leaf_desc(leaf, i in donated)))
    return out


def diff_fingerprints(old: Sequence[Tuple[str, str]],
                      new: Sequence[Tuple[str, str]]) -> List[str]:
    """Human-readable diff between two fingerprints: one line per
    changed/added/removed leaf, e.g. ``arg 3 (batch)['input_ids']:
    s32[1,8,16] -> s32[1,8,8]``."""
    old_map = dict(old)
    new_map = dict(new)
    out = []
    for key, desc in new:
        prev = old_map.get(key)
        if prev is None:
            out.append(f"{key}: added {desc}")
        elif prev != desc:
            out.append(f"{key}: {prev} -> {desc}")
    for key, desc in old:
        if key not in new_map:
            out.append(f"{key}: removed {desc}")
    return out


class CompileLedger:
    """Per-engine compile-event recorder: fingerprints, diffs, cost and
    memory analysis, bounded history."""

    def __init__(self, config=None, tracer=None, owner: Any = None):
        def g(key, default):
            return getattr(config, key, default) if config is not None \
                else default

        self.tracer = tracer or get_tracer()
        self._owner = owner
        self.memory_analysis = bool(g("memory_analysis", True))
        self._events: "deque" = deque(maxlen=int(g("history", 32)))
        #: label -> {"fn", "fp", "size"}; holds fn refs so identity is
        #: stable (the RecompileWatchdog pattern)
        self._state: Dict[str, Dict[str, Any]] = {}
        self.compiles = 0
        self.recompiles = 0
        self.analysis_compile_ms = 0.0   # total AOT-analysis compile time
        self.last_recompile: Optional[Dict[str, Any]] = None
        self._next_id = 1

    # ------------------------------------------------------------ observing
    @staticmethod
    def _cache_size(fn) -> int:
        size_of = getattr(fn, "_cache_size", None)
        if size_of is None:
            return 0
        try:
            return int(size_of())
        except Exception:
            return 0

    @staticmethod
    def _quick_sig(args) -> tuple:
        """Cheap structural signature: (shape, dtype, sharding) per leaf
        via one C-level flatten — the steady-state fast path. The full
        labeled fingerprint (string keys, donation flags) is only built
        when this differs, i.e. on compile events."""
        import jax
        return tuple(
            (getattr(leaf, "shape", None), getattr(leaf, "dtype", None),
             getattr(leaf, "sharding", None))
            if hasattr(leaf, "shape") else (None, None, type(leaf))
            for leaf in jax.tree.leaves(args))

    def observe(self, label: str, fn, args: Sequence[Any] = (),
                names: Optional[Sequence[str]] = None,
                donated: Sequence[int] = (), step: Optional[int] = None,
                mesh=None) -> Optional[Dict[str, Any]]:
        """Record a compile/recompile event for one call of ``fn`` under
        ``label``. Returns the event dict when this call's signature is
        new (first sight = ``compile``; changed fingerprint or jit-cache
        growth on the same fn = ``recompile``; a *different* fn object
        under the same label — e.g. a new static-argument bucket — is a
        fresh ``compile`` whose diff still names what changed), else
        None. Steady state costs one flatten + one tuple compare."""
        size = self._cache_size(fn)
        st = self._state.get(label)
        quick = self._quick_sig(args)
        if st is not None and st["fn"] is fn and quick == st["quick"] and \
                size <= st["size"]:
            return None                   # steady state: no strings built
        fp = fingerprint_args(args, names=names, donated=donated)
        # expected cache size after this call: observations run BEFORE the
        # call (donated inputs must be fingerprinted alive), so an event's
        # compile hasn't grown the cache yet — store size+1 so the next
        # steady-state call doesn't read its growth as a second recompile
        if st is None:
            ev = self._event("compile", label, fn, args, fp, None, step,
                             mesh)
            expected = size + 1
        elif st["fn"] is not fn:
            # a new jit wrapper under this label: a distinct program (new
            # ltd bucket, new prefill bucket) — first compile of that
            # program, with the cross-program diff attached
            diff = diff_fingerprints(st["fp"], fp) or \
                ["same argument signature (static-argument change "
                 "compiled a new program)"]
            ev = self._event("compile", label, fn, args, fp, diff, step,
                             mesh)
            expected = size + 1
        elif fp != st["fp"]:
            ev = self._event("recompile", label, fn, args, fp,
                             diff_fingerprints(st["fp"], fp), step, mesh)
            expected = size + 1
        elif size > st["size"]:
            # backstop, one call late by construction: the cache grew with
            # no signature change (static argument / weak-type / context)
            ev = self._event(
                "recompile", label, fn, args, fp,
                ["no argument signature change (static context or "
                 "weak-type change grew the jit cache)"], step, mesh)
            expected = size
        else:
            # quick-sig churn with an identical full fingerprint (fresh
            # but equal sharding objects): refresh the cheap key
            st["quick"] = quick
            st["size"] = max(st["size"], size)
            return None
        self._state[label] = {"fn": fn, "fp": fp, "quick": quick,
                              "size": expected}
        return ev

    def _event(self, kind: str, label: str, fn, args, fp, diff,
               step: Optional[int], mesh) -> Dict[str, Any]:
        ev: Dict[str, Any] = {
            "id": self._next_id,
            "kind": kind,
            "label": label,
            "step": step,
            "time": time.time(),
            "wall_ms": None,             # set by finish(): the step that
                                         # paid this compile
            "fingerprint": [f"{k}: {d}" for k, d in fp],
        }
        self._next_id += 1
        if diff:
            ev["diff"] = list(diff)
        self._analyze(ev, fn, args, mesh)
        self._events.append(ev)
        if kind == "recompile":
            self.recompiles += 1
            self.last_recompile = {
                "label": label, "step": step, "time": ev["time"],
                "diff": ev.get("diff", []),
            }
        else:
            self.compiles += 1
        tr = self.tracer
        tr.set_counter("compileplane/compiles", float(self.compiles),
                       owner=self._owner)
        tr.set_counter("compileplane/recompiles", float(self.recompiles),
                       owner=self._owner)
        tr.instant(f"compile_plane:{kind}", cat="warning",
                   args={"label": label,
                         "diff": "; ".join(ev.get("diff", []))[:512]})
        return ev

    def _analyze(self, ev: Dict[str, Any], fn, args, mesh):
        """Attach XLA's own accounting of the program this event compiled:
        cost_analysis from the lowered stage (global FLOPs/bytes, no
        backend compile), and — when ``memory_analysis`` is on — one AOT
        compile for the per-device memory breakdown, the measured compile
        wall time, and the optimized HLO's collective/overlap summary. A
        failed analysis annotates the event instead of losing it."""
        if not hasattr(fn, "lower"):
            return
        try:
            if mesh is not None:
                with mesh:
                    lowered = fn.lower(*args)
            else:
                lowered = fn.lower(*args)
            ev["cost"] = cost_summary(lowered.cost_analysis())
        except Exception as e:
            ev["analysis_error"] = str(e)
            return
        if not self.memory_analysis:
            return
        try:
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_ms = (time.perf_counter() - t0) * 1e3
            ev["compile_ms"] = round(compile_ms, 3)
            self.analysis_compile_ms += compile_ms
            self.tracer.set_counter("compileplane/last_compile_ms",
                                    round(compile_ms, 3),
                                    owner=self._owner)
            mem = memory_summary(compiled.memory_analysis())
            if mem:
                ev["memory"] = mem
            hlo = compiled.as_text()
            ev["collectives"] = collect_collectives(hlo)
            ev["overlap"] = hlo_overlap_summary(hlo)
            self.tracer.set_counter("overlap/hlo_async_fraction",
                                    ev["overlap"]["async_fraction"],
                                    owner=self._owner)
            self.tracer.set_counter(
                "overlap/hlo_static_fraction",
                ev["overlap"].get("static_overlap_fraction", 0.0),
                owner=self._owner)
        except Exception as e:
            ev["analysis_error"] = str(e)

    def finish(self, ev: Dict[str, Any], wall_ms: float):
        """Record the wall time of the step that paid this compile (the
        jit call's own compile+run, distinct from ``compile_ms``, the
        isolated AOT-analysis compile)."""
        ev["wall_ms"] = round(float(wall_ms), 3)

    # -------------------------------------------------------------- reading
    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def events_since(self, event_id: int) -> List[Dict[str, Any]]:
        """Events with id strictly greater than ``event_id`` — the
        measured-trial read API (autotuning/measure.py): a trial driver
        remembers the last id after warmup, and any event returned here
        during the measured window is a steady-state recompile (a hard
        disqualification). Pass 0 for the full history."""
        return [ev for ev in self._events if ev["id"] > event_id]

    @property
    def last_event_id(self) -> int:
        """Highest event id issued so far (0 before the first event)."""
        return self._next_id - 1

    def last_event(self, label: Optional[str] = None) \
            -> Optional[Dict[str, Any]]:
        for ev in reversed(self._events):
            if label is None or ev["label"] == label:
                return ev
        return None

    def step_flops(self, label: str, fn=None) -> float:
        """Global-program FLOPs of the executable currently active under
        ``label``, from the captured ``cost_analysis`` — the MFU-gauge
        fallback when the flops profiler is off. 0 when unknown or when
        ``fn`` is no longer the executable the cost was captured for."""
        st = self._state.get(label)
        if st is None or (fn is not None and st["fn"] is not fn):
            return 0.0
        ev = self.last_event(label)
        if ev is None:
            return 0.0
        return float((ev.get("cost") or {}).get("flops", 0.0))

    def summary(self) -> Dict[str, Any]:
        """The statusz section / ds_tpu_top view."""
        out: Dict[str, Any] = {
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            "functions": len(self._state),
            "events_kept": len(self._events),
        }
        if self.analysis_compile_ms:
            out["analysis_compile_ms"] = round(self.analysis_compile_ms, 1)
        last = self.last_event()
        if last is not None and last.get("cost"):
            flops = last["cost"].get("flops")
            if flops:
                out["last_step_gflops"] = round(flops / 1e9, 3)
        if last is not None and last.get("overlap"):
            out["hlo_async_fraction"] = last["overlap"]["async_fraction"]
            out["hlo_static_fraction"] = last["overlap"].get(
                "static_overlap_fraction", 0.0)
        lr = self.last_recompile
        if lr is not None:
            out["last_recompile"] = (
                f"{lr['label']} step {lr['step']}: " +
                "; ".join(lr["diff"][:4]) +
                (" …" if len(lr["diff"]) > 4 else ""))
            out["last_recompile_age_s"] = round(
                max(0.0, time.time() - lr["time"]), 1)
        return out

    def bundle_section(self) -> Dict[str, Any]:
        """What a flight-recorder bundle embeds: the summary plus the full
        event history (fingerprints, diffs, cost/memory summaries)."""
        return {"summary": self.summary(), "events": self.events()}


# ---------------------------------------------------------------- HBM ledger

#: the role vocabulary (stable schema for dashboards; ``other`` catches
#: caller-defined roles)
HBM_ROLES = ("params", "grads", "optimizer_state", "activations",
             "kv_slots", "other")


class HBMLedger:
    """Live per-device bytes by role. The peak-HBM gauge says *how much*;
    this says *what it is* — pytree accounting over addressable shards
    for the live trees (params/grads/optimizer state/KV slots), plus the
    active executable's temp allocation (``memory_analysis``) for
    activations. Gauges are ``mem/<role>_gib`` (Prometheus:
    ``dstpu_mem_<role>_gib``); every update also drops one Perfetto
    counter-track sample into the span ring — the waterline timeline."""

    def __init__(self, tracer=None, owner: Any = None):
        self.tracer = tracer or get_tracer()
        self._owner = owner
        self._bytes: Dict[str, int] = {}
        self.updates = 0

    def device_bytes(self, tree) -> int:
        """Per-device live bytes of a pytree: per-shard size from each
        array's sharding metadata (``shard_shape`` — replicated arrays
        count full size, that's what they occupy per device). Metadata
        only: no shard objects are materialized, so this is cheap enough
        for a per-N-steps cadence."""
        import math

        import jax

        total = 0
        for leaf in jax.tree.leaves(tree):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                continue
            itemsize = leaf.dtype.itemsize
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                try:
                    shape = sharding.shard_shape(tuple(shape))
                except Exception:
                    pass
            total += math.prod(shape) * itemsize
        return total

    def update(self, roles: Dict[str, float],
               peak_bytes: Optional[int] = None) -> Dict[str, Any]:
        """Set the current role attribution (bytes). Roles not in this
        update keep their last value; pass 0 to clear one. Mirrors the
        ``mem/*_gib`` gauges and emits the waterline counter-track
        sample. ``peak_bytes`` (the allocator high-water, when the
        backend reports one) yields the coverage ratio — how much of the
        high-water the roles explain."""
        self._bytes.update({k: int(v) for k, v in roles.items()})
        self.updates += 1
        tr = self.tracer
        gib = {}
        total = 0
        for role, nbytes in self._bytes.items():
            total += nbytes
            gib[role] = round(nbytes / 2**30, 6)
            tr.set_counter(f"mem/{role}_gib", gib[role], owner=self._owner)
        tr.set_counter("mem/total_gib", round(total / 2**30, 6),
                       owner=self._owner)
        out: Dict[str, Any] = {"total_bytes": total, "roles": dict(self._bytes)}
        if peak_bytes:
            out["peak_bytes"] = int(peak_bytes)
            out["coverage"] = round(total / peak_bytes, 4)
            tr.set_counter("mem/coverage", out["coverage"],
                           owner=self._owner)
        tr.counter_track("hbm_gib", gib)
        return out

    def summary(self) -> Dict[str, Any]:
        """The ``memory`` statusz section: role GiB plus the allocator's
        own numbers when the backend reports them (the CPU test backend
        does not)."""
        out: Dict[str, Any] = {}
        total = 0
        for role in HBM_ROLES:
            nbytes = self._bytes.get(role)
            if nbytes is not None:
                out[f"{role}_gib"] = round(nbytes / 2**30, 6)
                total += nbytes
        for role, nbytes in self._bytes.items():
            if role not in HBM_ROLES:
                out[f"{role}_gib"] = round(nbytes / 2**30, 6)
                total += nbytes
        out["total_gib"] = round(total / 2**30, 6)
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
        except Exception:
            stats = {}
        if stats.get("bytes_in_use"):
            out["in_use_gib"] = round(stats["bytes_in_use"] / 2**30, 6)
        if stats.get("peak_bytes_in_use"):
            out["peak_gib"] = round(stats["peak_bytes_in_use"] / 2**30, 6)
            if total:
                out["coverage"] = round(total / stats["peak_bytes_in_use"],
                                        4)
        return out
