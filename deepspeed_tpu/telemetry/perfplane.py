"""Perf plane — step/tick anatomy, roofline attribution, regression gate.

The compile plane says *what* compiled and *what it holds* (HBM roles,
collective counts); the goodput ledger says *how much* wall-clock was
productive; this module says **where a compiled program spends its
time**: every step/tick decomposes into named buckets — ``attn``,
``mlp`` (weight streaming rides the MLP/attn matmuls on a dense model),
``kv_read`` / ``kv_write`` (the KV-pool traffic ROADMAP item 2's paged
pool must beat), ``sample`` / ``verify`` (the decode tail), ``embed`` /
``head``, ``moe``, one ``coll_<op>`` bucket per collective kind, and
``other`` — by a stdlib-only per-op walk of the compiled HLO text
(:func:`anatomy_from_hlo`). Each instruction is classified by the
``jax.named_scope`` tokens XLA preserves in its ``op_name`` metadata
(the same scopes the flops profiler reads from jaxprs), priced under
an alpha-beta device model (compute = max(flops/peak, bytes/hbm_bw);
collectives = bytes/link_bw + latency, discounted by the module's
dependency-level ``static_overlap_fraction`` — so *de-overlapping a
schedule inflates the exposed ``coll_*`` ms even on CPU*). Runs in
tier-1 with no backend. Measured device time is not read here: the
reduction of a profiler trace is ``chipbench/trace.py`` (busy time as a
union of intervals, self time by operation).

:func:`reconcile_anatomy` is the roofline report: per bucket
arithmetic intensity, memory-bound flag against the device ridge, and
— against a measured ``{"buckets_ms": ...}`` dict a caller supplies —
predicted-vs-measured skew, the number STANDING CHIP DEBT says to
calibrate on hardware (ROADMAP item 5).

Sums are exact **by construction**: a program's ``total_ms`` is
*defined* as the float sum of its bucket ms values in sorted bucket
order, so the decomposition can never drift from its total (tested ±0
in tests/unit/test_perfplane.py).

The runtime half (:class:`PerfPlane`) hangs off the compile ledger:
every compile/recompile event with HLO text gets its anatomy attached,
``dstpu_anat_*`` gauges updated (owner lifecycle), a ``/statusz``
"anatomy" section, and — when a *recompile* shifts any bucket beyond
the configured band — an edge-triggered ``perf_regression`` flight
bundle, the perf twin of ``overlap_drop``.

The offline half is the regression gate: ``benchmarks/anatomy.py``
emits ``anatomy.json`` and ``bin/ds_tpu_perfdiff`` diffs it against the
checked-in baseline via :func:`diff_anatomy` (per-bucket noise bands,
hard gates, embedded invariants). Everything the CLI needs is importable
with zero third-party deps — ``hlo_cost.py`` is pulled in by file path
when the package is not importable, the ``ds_tpu_soakdiff`` pattern.
"""

import json
import math
import os
import re
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

try:
    from .hlo_cost import (COLLECTIVES, DTYPE_BYTES, _INSTR_RE, _PAT_SHAPE,
                           _parse_computations, collect_schedule_overlap)
except ImportError:      # file-path load (bin/ds_tpu_perfdiff, stdlib-only)
    import importlib.util as _ilu
    _spec = _ilu.spec_from_file_location(
        "_dstpu_hlo_cost",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "hlo_cost.py"))
    _hc = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_hc)
    COLLECTIVES, DTYPE_BYTES = _hc.COLLECTIVES, _hc.DTYPE_BYTES
    _INSTR_RE, _PAT_SHAPE = _hc._INSTR_RE, _hc._PAT_SHAPE
    _parse_computations = _hc._parse_computations
    collect_schedule_overlap = _hc.collect_schedule_overlap

__all__ = ["ANATOMY_KIND", "PHASE_BUCKETS", "DEVICE_MODEL",
           "anatomy_from_hlo",
           "reconcile_anatomy", "diff_anatomy", "format_diff",
           "check_anatomy_invariants", "write_anatomy", "PerfPlane"]

#: document kind pinned into anatomy.json (ds_tpu_perfdiff refuses to
#: baseline anything else)
ANATOMY_KIND = "dstpu_anatomy"

#: named-scope buckets in PRECEDENCE order: the first token found in an
#: op's scope stack wins, so ``.../attn/kv_write/...`` classifies as
#: kv_write (the inner, more specific scope), not attn. ``moe`` outranks
#: mlp because expert blocks nest a gate inside the mlp scope.
PHASE_BUCKETS = ("kv_write", "kv_read", "sample", "verify", "moe", "attn",
                 "mlp", "embed", "head")

#: token-boundary matchers (the flops profiler's `_PHASE_RE` trick:
#: "attn" must not match inside "attntmp")
_PHASE_RES = {p: re.compile(rf"(?<![A-Za-z0-9_]){p}(?![A-Za-z0-9_])")
              for p in PHASE_BUCKETS}

#: alpha-beta device model defaults — the same constants the PR-15
#: schedule cost model ships (autotuning/cost_model.ScheduleCostModel)
#: plus an HBM bandwidth term for the roofline ridge. All overridable
#: via ``perf_plane.device_model`` (and re-calibrated on chip with
#: ``calibrate_cost_model``: STANDING CHIP DEBT, ROADMAP item 5).
DEVICE_MODEL = {
    "peak_flops": 100e12,        # FLOP/s
    "hbm_bandwidth": 800e9,      # bytes/s
    "link_bandwidth": 40e9,      # bytes/s per link (collectives)
    "op_latency_s": 2e-6,        # per-collective dispatch latency
    "overlap_efficiency": 0.9,   # fraction of overlappable wire time the
                                 # latency-hiding executor actually hides
}

#: bookkeeping ops that move no HBM bytes of their own (or are priced
#: elsewhere): parameters/constants/tuple plumbing are free; ``while``
#: and ``conditional`` call-sites are priced through their bodies;
#: ``*-done``/``async-done`` halves carry the same payload their start
#: already counted.
_SKIP_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "async-done", "async-update", "copy-start", "copy-done",
))

_ENTRY_RE = re.compile(r"^ENTRY\s+(%?[\w.\-]+)", re.M)
#: XLA annotates wide tuples with /*index=N*/ comments whose '=' breaks
#: _INSTR_RE's tuple-result alternative — strip before matching
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_RESULT_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|\S+)\s+[\w\-]+\(")
_CALLS_RE = re.compile(r"calls=(%?[\w.\-]+)")
_BODY_RE = re.compile(r"body=(%?[\w.\-]+)")
_COND_RE = re.compile(r"condition=(%?[\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRUEFALSE_RE = re.compile(r"(?:true|false)_computation=(%?[\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=(%?[\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _shape_numel_bytes(dtype: str, dims: str) -> Tuple[int, int]:
    numel = math.prod([int(d) for d in dims.split(",") if d] or [1])
    return numel, numel * DTYPE_BYTES.get(dtype, 4)


def _line_bytes(text: str) -> int:
    return sum(_shape_numel_bytes(m.group(1), m.group(2))[1]
               for m in _PAT_SHAPE.finditer(text))


def _classify_scope(op_name: str) -> Optional[str]:
    """Highest-precedence phase token in a metadata scope stack."""
    for phase in PHASE_BUCKETS:
        if _PHASE_RES[phase].search(op_name):
            return phase
    return None


def _collective_base(op: str) -> Optional[str]:
    """'all-gather-start' / 'all-gather.3' / 'all-gather' -> 'all-gather'
    (None for non-collectives)."""
    for c in COLLECTIVES:
        if op == c or op.startswith(f"{c}-start") or \
                op.startswith(f"{c}."):
            return c
    if op.startswith("async-start"):
        return None     # handled by the caller via the line text
    return None


def _dot_flops(line: str, operands: str, result_numel: int) -> float:
    """2 * numel(result) * prod(lhs contracting dim sizes) — the shared
    contraction depth parsed from the printed ``lhs_contracting_dims``
    against the first (lhs) operand shape."""
    m = _LHS_CONTRACT_RE.search(line)
    lhs = _PAT_SHAPE.search(operands)
    if not m or not lhs:
        return 2.0 * result_numel
    dims = [int(d) for d in lhs.group(2).split(",") if d]
    depth = 1
    for idx in (int(i) for i in m.group(1).split(",") if i):
        if 0 <= idx < len(dims):
            depth *= dims[idx]
    return 2.0 * result_numel * depth


def _computation_multipliers(hlo_text: str,
                             comps: Dict[str, list]) -> Dict[str, float]:
    """Walk the call graph from ENTRY assigning each computation an
    execution multiplier: while bodies/conditions inherit the parent's
    multiplier times the printed ``known_trip_count`` (XLA prints it for
    rolled ``lax.scan`` loops; 1 when absent), conditional branches and
    ``call`` targets inherit it unchanged, fusion bodies stay at 0 —
    they are priced at their call site, where operand/result shapes
    approximate the fusion's real HBM traffic."""
    entry_m = _ENTRY_RE.search(hlo_text)
    entry = entry_m.group(1).lstrip("%") if entry_m else None
    mult: Dict[str, float] = {}
    names = {name.lstrip("%"): name for name in comps}
    if entry is None or entry not in names:
        # headerless fragment: treat every computation as entry-level
        return {name: 1.0 for name in comps}
    frontier = [(entry, 1.0)]
    while frontier:
        cname, m = frontier.pop()
        if mult.get(cname, 0.0) >= m:
            continue
        mult[cname] = m
        for line in comps.get(names.get(cname, cname), ()):
            if "/*" in line:
                line = _COMMENT_RE.sub("", line)
            bm = _BODY_RE.search(line)
            if bm:
                trip = _TRIP_RE.search(line)
                n = float(trip.group(1)) if trip else 1.0
                frontier.append((bm.group(1).lstrip("%"), m * n))
                cm = _COND_RE.search(line)
                if cm:
                    frontier.append((cm.group(1).lstrip("%"), m * n))
                continue
            br = _BRANCHES_RE.search(line)
            if br:
                for tok in re.findall(r"%?[\w.\-]+", br.group(1)):
                    frontier.append((tok.lstrip("%"), m))
                continue
            for tm in _TRUEFALSE_RE.finditer(line):
                frontier.append((tm.group(1).lstrip("%"), m))
            op_m = _INSTR_RE.match(line)
            if op_m and op_m.group(3) == "call":
                ta = _TO_APPLY_RE.search(line)
                if ta:
                    frontier.append((ta.group(1).lstrip("%"), m))
    return {name: mult.get(name.lstrip("%"), 0.0) for name in comps}


def _fusion_info(comps: Dict[str, list]) -> Dict[str, Dict[str, Any]]:
    """Per fusion body: the highest-precedence phase among its fused
    instructions' scope metadata, and the dot flops buried inside it
    (fusion call-site shapes carry the bytes; the body carries the
    math)."""
    out: Dict[str, Dict[str, Any]] = {}
    for cname, block in comps.items():
        best: Optional[str] = None
        flops = 0.0
        for line in block:
            if "/*" in line:
                line = _COMMENT_RE.sub("", line)
            om = _OP_NAME_RE.search(line)
            if om:
                phase = _classify_scope(om.group(1))
                if phase is not None and (
                        best is None or PHASE_BUCKETS.index(phase) <
                        PHASE_BUCKETS.index(best)):
                    best = phase
            im = _INSTR_RE.match(line)
            if im and im.group(3) == "dot":
                rm = _RESULT_RE.match(line)
                numel = 0
                if rm:
                    numel = sum(
                        _shape_numel_bytes(s.group(1), s.group(2))[0]
                        for s in _PAT_SHAPE.finditer(rm.group(1)))
                flops += _dot_flops(line, im.group(4), numel)
        out[cname.lstrip("%")] = {"phase": best, "dot_flops": flops}
    return out


def anatomy_from_hlo(hlo_text: str,
                     device_model: Optional[Dict[str, float]] = None
                     ) -> Dict[str, Any]:
    """Static anatomy of one compiled HLO module.

    Returns ``{"buckets": {name: {ms, flops, bytes, ops}}, "total_ms",
    "flops", "bytes", "static_overlap_fraction",
    "memory_bound_fraction", "device_model"}``. ``total_ms`` is the
    float sum of bucket ms in sorted bucket order — the decomposition
    sums to it exactly, by construction. ``host_gap`` is present at 0.0
    (only the measured path can see host time).
    """
    dm = dict(DEVICE_MODEL)
    dm.update(device_model or {})
    comps = _parse_computations(hlo_text)
    mults = _computation_multipliers(hlo_text, comps)
    fusions = _fusion_info(comps)
    overlap = collect_schedule_overlap(hlo_text)
    static_frac = float(overlap.get("static_overlap_fraction", 0.0))
    # exposed fraction of collective wire time after the latency-hiding
    # executor hides what the schedule makes hideable — the knob the
    # bucketed ZeRO exchange raises and a de-overlap regression drops
    exposed = 1.0 - dm["overlap_efficiency"] * static_frac

    buckets: Dict[str, Dict[str, float]] = {}

    def acc(name: str, ms: float, flops: float, nbytes: float,
            membound: bool):
        b = buckets.setdefault(name, {"ms": 0.0, "flops": 0.0,
                                      "bytes": 0.0, "ops": 0,
                                      "membound_ms": 0.0})
        b["ms"] += ms
        b["flops"] += flops
        b["bytes"] += nbytes
        b["ops"] += 1
        if membound:
            b["membound_ms"] += ms

    for cname, block in comps.items():
        mult = mults.get(cname, 0.0)
        if mult <= 0.0:
            continue
        for line in block:
            if "/*" in line:
                line = _COMMENT_RE.sub("", line)
            im = _INSTR_RE.match(line)
            if not im:
                continue
            op, operands = im.group(3), im.group(4)
            if op in _SKIP_OPS or op.split(".")[0] in _SKIP_OPS:
                continue
            rm = _RESULT_RE.match(line)
            result_text = rm.group(1) if rm else ""
            result_numel = sum(
                _shape_numel_bytes(s.group(1), s.group(2))[0]
                for s in _PAT_SHAPE.finditer(result_text))
            result_bytes = _line_bytes(result_text)
            operand_bytes = _line_bytes(operands)
            coll = _collective_base(op)
            if coll is None and op.startswith("async-start"):
                for c in COLLECTIVES:
                    if re.search(rf"\b{c}\b", line):
                        coll = c
                        break
            if coll is not None:
                if op.endswith("-done") or ".done" in op:
                    continue
                wire = max(result_bytes, operand_bytes)
                raw_ms = (wire / dm["link_bandwidth"] +
                          dm["op_latency_s"]) * 1e3
                acc(f"coll_{coll.replace('-', '_')}",
                    raw_ms * exposed * mult, 0.0, float(wire) * mult,
                    True)
                continue
            if op == "fusion" or op.startswith("fusion."):
                cm = _CALLS_RE.search(line)
                info = fusions.get(cm.group(1).lstrip("%"), {}) if cm \
                    else {}
                phase = info.get("phase")
                flops = float(info.get("dot_flops") or result_numel)
                if phase is None:
                    om = _OP_NAME_RE.search(line)
                    phase = _classify_scope(om.group(1)) if om else None
            else:
                om = _OP_NAME_RE.search(line)
                phase = _classify_scope(om.group(1)) if om else None
                if op == "dot":
                    flops = _dot_flops(line, operands, result_numel)
                elif op.startswith("reduce"):
                    flops = float(
                        sum(_shape_numel_bytes(s.group(1),
                                               s.group(2))[0]
                            for s in _PAT_SHAPE.finditer(operands)))
                else:
                    flops = float(result_numel)
            nbytes = float(operand_bytes + result_bytes)
            compute_ms = flops / dm["peak_flops"] * 1e3
            mem_ms = nbytes / dm["hbm_bandwidth"] * 1e3
            acc(phase or "other", max(compute_ms, mem_ms) * mult,
                flops * mult, nbytes * mult, mem_ms >= compute_ms)

    buckets.setdefault("host_gap", {"ms": 0.0, "flops": 0.0, "bytes": 0.0,
                                    "ops": 0, "membound_ms": 0.0})
    for b in buckets.values():
        b["ms"] = float(b["ms"])
        b["flops"] = float(b["flops"])
        b["bytes"] = float(b["bytes"])
    # THE sum-by-construction contract: total is DEFINED as the sorted
    # bucket sum, so `sum(buckets) == total` holds to the last ulp
    total_ms = float(sum(buckets[name]["ms"] for name in sorted(buckets)))
    membound = float(sum(b["membound_ms"] for b in buckets.values()))
    for b in buckets.values():
        del b["membound_ms"]
    return {
        "buckets": buckets,
        "total_ms": total_ms,
        "flops": float(sum(b["flops"] for b in buckets.values())),
        "bytes": float(sum(b["bytes"] for b in buckets.values())),
        "static_overlap_fraction": static_frac,
        "memory_bound_fraction":
            round(membound / total_ms, 6) if total_ms > 0 else 0.0,
        "device_model": dm,
    }


def reconcile_anatomy(static: Dict[str, Any],
                      measured: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
    """The roofline report: one row per bucket with arithmetic
    intensity (flops/byte), the memory-bound verdict against the device
    ridge (peak_flops / hbm_bandwidth), predicted ms, and — when a
    measured anatomy is supplied — measured ms and the
    predicted/measured skew the chip calibration pass pins down."""
    dm = static.get("device_model", DEVICE_MODEL)
    ridge = dm["peak_flops"] / dm["hbm_bandwidth"]
    meas = (measured or {}).get("buckets_ms", {})
    rows = []
    for name in sorted(static.get("buckets", {})):
        b = static["buckets"][name]
        intensity = (b["flops"] / b["bytes"]) if b["bytes"] else 0.0
        row = {
            "bucket": name,
            "flops": b["flops"],
            "bytes": b["bytes"],
            "arithmetic_intensity": round(intensity, 4),
            "memory_bound": intensity < ridge,
            "predicted_ms": round(b["ms"], 6),
        }
        if measured is not None:
            m_ms = float(meas.get(name, 0.0))
            row["measured_ms"] = m_ms
            row["skew"] = round(b["ms"] / m_ms, 4) if m_ms > 0 else None
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# the regression gate (stdlib-pure: bin/ds_tpu_perfdiff loads this file)
# ---------------------------------------------------------------------------

#: per-bucket noise bands. Static predictions are deterministic on an
#: unchanged tree, so the bands only absorb benign drift (an XLA fusion
#: decision moving ops between buckets) — a real regression (a
#: de-overlapped collective, bloated decode bytes) blows well past
#: them. Floors keep sub-ulp buckets from tripping ratio math.
DIFF_TOLERANCES = {
    "ms_ratio": 1.25,        # per-bucket ms <= 1.25x baseline
    "ms_floor": 0.01,        # ... ignoring buckets under 0.01 ms (the
                             # tiny-size pin keeps collective buckets in
                             # the tens of microseconds — the floor only
                             # mutes sub-noise epilogue buckets)
    "bytes_ratio": 1.10,     # per-bucket bytes <= 1.10x baseline
    "bytes_floor": 64 << 10,  # ... ignoring buckets under 64 KiB
    "total_ratio": 1.15,     # program total_ms <= 1.15x baseline
    "membound_band": 0.15,   # |memory_bound_fraction delta| <= 0.15
}


def check_anatomy_invariants(doc: Dict[str, Any]) -> Dict[str, Any]:
    """Fold-time invariants embedded in every anatomy.json: each
    program's bucket decomposition re-sums to its recorded total
    EXACTLY (the by-construction contract — any drift means the doc was
    hand-edited or the writer broke), and the decode KV-scaling
    evidence holds when both decode flavors are present."""
    out: Dict[str, Any] = {}
    bad = []
    for name, prog in sorted((doc.get("programs") or {}).items()):
        buckets = prog.get("buckets") or {}
        resum = float(sum(buckets[b]["ms"] for b in sorted(buckets)))
        if resum != float(prog.get("total_ms", -1.0)):
            bad.append(f"{name}: sum(buckets)={resum!r} != "
                       f"total_ms={prog.get('total_ms')!r}")
    out["sum_to_total"] = {"ok": not bad, "detail": "; ".join(bad) or
                           "every program re-sums exactly"}
    d1 = (doc.get("programs") or {}).get("decode_tick")
    d2 = (doc.get("programs") or {}).get("decode_tick_x2")
    if d1 and d2:
        b1 = float((d1.get("extras") or {}).get("kv_read_bytes_per_tick",
                                                0.0))
        b2 = float((d2.get("extras") or {}).get("kv_read_bytes_per_tick",
                                                0.0))
        ratio = (b2 / b1) if b1 > 0 else 0.0
        ok = 1.8 <= ratio <= 2.2
        out["kv_read_scales_with_max_len"] = {
            "ok": ok, "ratio": round(ratio, 4),
            "detail": f"dense-pool KV read bytes at 2x max_len: "
                      f"{ratio:.3f}x (expect ~2x — the number the paged "
                      f"pool must beat, ROADMAP item 2)"}
    return out


def diff_anatomy(base: Dict[str, Any], cand: Dict[str, Any],
                 tolerances: Optional[Dict[str, float]] = None
                 ) -> Tuple[List[Dict[str, Any]], bool]:
    """Compare a candidate anatomy.json against a baseline. Returns
    ``(rows, ok)``. Hard gates first: candidate kind, candidate's own
    embedded invariants (re-checked here — a doc whose buckets don't
    re-sum cannot pass), no baseline program missing from the
    candidate. Then per-program noise-banded comparisons that FAIL BY
    BUCKET NAME — the line a future PR reads when it silently
    de-overlaps a collective or bloats decode bytes."""
    tol = dict(DIFF_TOLERANCES)
    tol.update(tolerances or {})
    rows: List[Dict[str, Any]] = []

    def row(metric, b, c, t, ok, note=""):
        rows.append({"metric": metric, "baseline": b, "candidate": c,
                     "tolerance": t, "ok": bool(ok), "note": note})

    if cand.get("kind") != ANATOMY_KIND:
        row("kind", base.get("kind"), cand.get("kind"), ANATOMY_KIND,
            False, "candidate is not an anatomy doc")
        return rows, False
    for name, inv in sorted(check_anatomy_invariants(cand).items()):
        row(f"invariant:{name}", True, inv["ok"], "must hold", inv["ok"],
            "" if inv["ok"] else str(inv.get("detail")))

    base_progs = base.get("programs") or {}
    cand_progs = cand.get("programs") or {}
    for pname in sorted(base_progs):
        bp, cp = base_progs[pname], cand_progs.get(pname)
        if cp is None:
            row(f"{pname}", "present", None, "program must exist", False,
                "missing in candidate")
            continue
        bb = bp.get("buckets") or {}
        cb = cp.get("buckets") or {}
        for bucket in sorted(set(bb) | set(cb)):
            b_ms = float((bb.get(bucket) or {}).get("ms", 0.0))
            c_ms = float((cb.get(bucket) or {}).get("ms", 0.0))
            if max(b_ms, c_ms) < tol["ms_floor"]:
                continue                       # noise floor: skip row
            ok = c_ms <= max(b_ms * tol["ms_ratio"], tol["ms_floor"])
            row(f"{pname}.{bucket}.ms", round(b_ms, 4), round(c_ms, 4),
                f"<= {tol['ms_ratio']:g}x base", ok,
                "" if ok else "bucket regressed")
            b_by = float((bb.get(bucket) or {}).get("bytes", 0.0))
            c_by = float((cb.get(bucket) or {}).get("bytes", 0.0))
            if max(b_by, c_by) >= tol["bytes_floor"]:
                ok_b = c_by <= max(b_by * tol["bytes_ratio"],
                                   tol["bytes_floor"])
                row(f"{pname}.{bucket}.bytes", b_by, c_by,
                    f"<= {tol['bytes_ratio']:g}x base", ok_b,
                    "" if ok_b else "bucket bytes regressed")
        b_t = float(bp.get("total_ms", 0.0))
        c_t = float(cp.get("total_ms", 0.0))
        ok_t = b_t <= 0 or c_t <= b_t * tol["total_ratio"]
        row(f"{pname}.total_ms", round(b_t, 4), round(c_t, 4),
            f"<= {tol['total_ratio']:g}x base", ok_t)
        b_f = float(bp.get("memory_bound_fraction", 0.0))
        c_f = float(cp.get("memory_bound_fraction", 0.0))
        ok_f = abs(c_f - b_f) <= tol["membound_band"]
        row(f"{pname}.memory_bound_fraction", b_f, c_f,
            f"+/-{tol['membound_band']:g}", ok_f)
    return rows, all(r["ok"] for r in rows)


def format_diff(rows: List[Dict[str, Any]]) -> str:
    """The pass/fail table ds_tpu_perfdiff prints (soakdiff's format)."""
    def fmt(v):
        if isinstance(v, float):
            return f"{v:.4g}"
        return "-" if v is None else str(v)

    header = ("metric", "baseline", "candidate", "tolerance", "verdict")
    table = [header]
    for r in rows:
        verdict = "ok" if r["ok"] else "FAIL"
        if r["note"]:
            verdict += f"  ({r['note']})"
        table.append((r["metric"], fmt(r["baseline"]),
                      fmt(r["candidate"]), str(r["tolerance"]), verdict))
    widths = [max(len(row[i]) for row in table)
              for i in range(len(header) - 1)]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(cell.ljust(widths[j]) if j < len(widths)
                               else cell
                               for j, cell in enumerate(row)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths) + "  " +
                         "-" * 7)
    return "\n".join(lines)


def write_anatomy(doc: Dict[str, Any], path: str) -> str:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# runtime integration (rides the compile ledger)
# ---------------------------------------------------------------------------

class PerfPlane:
    """Per-engine anatomy engine: computes a static anatomy for every
    compile-ledger event that carries HLO text, exports ``anat/*``
    gauges (-> ``dstpu_anat_*``), serves the ``/statusz`` "anatomy"
    section + flight-bundle provider, and edge-triggers
    ``perf_regression`` when a *recompile* shifts any bucket beyond the
    configured band (first sight of a label never fires — the
    ``overlap_drop`` pattern)."""

    def __init__(self, config=None, tracer=None, owner: Any = None,
                 recorder=None):
        def g(key, default):
            return getattr(config, key, default) if config is not None \
                else default

        from .trace import get_tracer
        self.tracer = tracer or get_tracer()
        self._owner = owner if owner is not None else self
        self._recorder = recorder
        self.band = float(g("band", 0.25))
        self.band_floor_ms = float(g("band_floor_ms", 0.05))
        self.device_model = dict(DEVICE_MODEL)
        dm = g("device_model", None)
        if isinstance(dm, dict):
            self.device_model.update(dm)
        self._anatomies: Dict[str, Dict[str, Any]] = {}
        self._history: "deque" = deque(maxlen=int(g("history", 32)))
        self.programs_observed = 0
        self.regressions = 0
        self.last_regression: Optional[Dict[str, Any]] = None

    # ---------------------------------------------------------- observing
    def observe_program(self, label: str, hlo_text: str,
                        kind: str = "compile", step: Optional[int] = None,
                        event: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
        """Anatomize one compiled program. Attaches the anatomy to the
        ledger event (postmortem bundles embed it via
        ``attach_compile_plane``), refreshes the per-label gauges, and
        fires the ``perf_regression`` trigger on a banded bucket shift
        during a recompile."""
        anat = anatomy_from_hlo(hlo_text, self.device_model)
        if event is not None:
            event["anatomy"] = {
                "buckets": {name: round(b["ms"], 6)
                            for name, b in anat["buckets"].items()},
                "total_ms": anat["total_ms"],
                "memory_bound_fraction": anat["memory_bound_fraction"],
            }
        self.programs_observed += 1
        prev = self._anatomies.get(label)
        self._anatomies[label] = anat
        self._history.append({"label": label, "kind": kind, "step": step,
                              "time": time.time(),
                              "total_ms": anat["total_ms"]})
        tr = self.tracer
        tr.set_counter(f"anat/{label}/total_ms",
                       round(anat["total_ms"], 6), owner=self._owner)
        tr.set_counter(f"anat/{label}/memory_bound_fraction",
                       anat["memory_bound_fraction"], owner=self._owner)
        for name, b in anat["buckets"].items():
            if b["ms"] >= self.band_floor_ms or name.startswith("coll_"):
                tr.set_counter(f"anat/{label}/{name}_ms",
                               round(b["ms"], 6), owner=self._owner)
        if prev is not None and kind == "recompile":
            shifted = self._shifted_buckets(prev, anat)
            if shifted:
                self.regressions += 1
                detail = "; ".join(
                    f"{name}: {p:.3f}ms -> {c:.3f}ms" for name, p, c in
                    shifted[:6])
                self.last_regression = {"label": label, "step": step,
                                        "buckets": [s[0] for s in shifted],
                                        "detail": detail}
                tr.set_counter("anat/regressions",
                               float(self.regressions), owner=self._owner)
                tr.instant("perf_plane:regression", cat="warning",
                           args={"label": label, "detail": detail[:512]})
                if self._recorder is not None:
                    self._recorder.trigger(
                        "perf_regression",
                        f"recompile of {label} shifted bucket(s) beyond "
                        f"the {self.band:.0%} band: {detail}", step=step)
        return anat

    def _shifted_buckets(self, prev: Dict[str, Any], cur: Dict[str, Any]
                         ) -> List[Tuple[str, float, float]]:
        out = []
        names = set(prev["buckets"]) | set(cur["buckets"])
        for name in sorted(names):
            p = float((prev["buckets"].get(name) or {}).get("ms", 0.0))
            c = float((cur["buckets"].get(name) or {}).get("ms", 0.0))
            if abs(c - p) > max(self.band * p, self.band_floor_ms):
                out.append((name, p, c))
        return out

    # ------------------------------------------------------------ reading
    def anatomy(self, label: str) -> Optional[Dict[str, Any]]:
        return self._anatomies.get(label)

    def roofline(self, label: str,
                 measured: Optional[Dict[str, Any]] = None
                 ) -> Optional[List[Dict[str, Any]]]:
        anat = self._anatomies.get(label)
        return None if anat is None else reconcile_anatomy(anat, measured)

    def summary(self) -> Dict[str, Any]:
        """The /statusz "anatomy" section (ds_tpu_top renders the
        per-bucket bars from ``programs``)."""
        programs: Dict[str, Any] = {}
        for label, anat in self._anatomies.items():
            programs[label] = {
                "total_ms": round(anat["total_ms"], 4),
                "memory_bound_fraction": anat["memory_bound_fraction"],
                "buckets_ms": {
                    name: round(b["ms"], 4)
                    for name, b in sorted(anat["buckets"].items())
                    if b["ms"] > 0.0},
            }
        out: Dict[str, Any] = {
            "programs_observed": self.programs_observed,
            "regressions": self.regressions,
            "band": self.band,
            "programs": programs,
        }
        if self.last_regression is not None:
            out["last_regression"] = dict(self.last_regression)
        return out

    def bundle_section(self) -> Dict[str, Any]:
        """Flight-bundle provider: the full anatomy table at capture
        time (roofline rows included — a postmortem should not need a
        second run to see where time went)."""
        return {
            "summary": self.summary(),
            "rooflines": {label: reconcile_anatomy(anat)
                          for label, anat in self._anatomies.items()},
        }

    def close(self):
        """Retract every ``anat/*`` gauge. Standalone use only — when an
        engine owns the plane, ``engine.close()``'s counter release
        covers these (the owner is the engine, not this object)."""
        if self._owner is self:
            self.tracer.release_counters(self)
