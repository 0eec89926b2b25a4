"""Shared HLO cost core — one parser for every XLA-cost consumer.

Three consumers used to carry private copies of this logic:
``benchmarks/hlo_audit.py`` (the collective-schedule regression gate),
the flight recorder's "XLA cost summary" capture, and the compile ledger
(telemetry/compileplane.py). They now all read from here, so a change to
the HLO text format (an XLA upgrade renaming an op, a new async form) is
fixed in exactly one place — and the future schedule autotuner (ROADMAP
item 2/5) scores candidate plans with the same numbers the gate enforces.

Contents:

- ``collect_collectives(hlo_text)`` — {op: {count, bytes}} over a
  compiled module's *synchronous* collectives (single-result and
  tuple-result forms), payload bytes from the printed result shapes.
- ``collect_async(hlo_text)`` — per-op counts of collectives emitted in
  async start/done form (``all-gather-start`` … ``all-gather-done``, or
  the generic ``async-start`` wrapper) — the ops XLA's latency-hiding
  scheduler *can* overlap with compute.
- ``hlo_overlap_summary(hlo_text)`` — sync vs async collective counts
  and the ``async_fraction`` in [0, 1]: the static half of the
  collective-overlap instrument (telemetry/overlap.py layers the
  trace-measured half on top).
- ``collect_schedule_overlap(hlo_text)`` — the dependency-level overlap
  instrument for backends that never emit async start/done pairs (the
  CPU lowering): per collective, is there compute a latency-hiding
  executor could legally run between the collective's issue point and
  its first real consumer? Computed from ASAP dataflow levels, so it is
  robust to the printed schedule order — this is the number the bucketed
  ZeRO exchange (runtime/zero/overlap_schedule.py) exists to raise and
  the schedule autotuner (autotuning/schedule.py) scores.
- ``collect_replica_groups(hlo_text)`` — parsed ``replica_groups`` per
  collective instruction (explicit ``{{0,1},{2,3}}`` lists, the iota
  ``[G,S]<=[dims]T(perm)`` form, and the empty all-devices form), one
  record per op with the expanded group membership. The collective-
  safety auditors (analysis/hlo_audit_rules.py) consume this instead of
  re-regexing HLO text.
- ``module_num_partitions(hlo_text)`` — the module's declared partition
  count (``num_partitions=N`` header field), 0 when absent.
- ``cost_summary(raw)`` — normalize a ``cost_analysis()`` result
  (dict, or the list/tuple wrapping older jax returns) to a flat dict
  of floats with python-identifier keys.
- ``memory_summary(stats)`` — normalize a ``memory_analysis()``
  ``CompiledMemoryStats`` to a plain dict of the ``*_in_bytes`` fields.
- ``SCOPES`` — the one vocabulary of ``jax.named_scope`` words the models
  and the engines set, and ``scope_table(hlo_text)`` — {instruction name:
  scope} of a compiled program, read off the ``op_name`` metadata its
  optimized HLO keeps: what a device trace's "XLA Ops" events are joined
  to (``Tracer.scope_tables``, docs/observability.md).

This module is deliberately standalone — stdlib-only, no package
imports — so ``benchmarks/hlo_audit.py`` can load it by file path before
the deepspeed_tpu package (and its backend-touching ``__init__`` chain)
is imported, the same way it loads ``utils/hermetic.py``.
"""

import math
import re
from typing import Any, Dict, Optional

__all__ = ["DTYPE_BYTES", "COLLECTIVES", "SCOPES", "PASSES", "scope_words",
           "scope_table",
           "collect_collectives",
           "collect_async", "collect_schedule_overlap",
           "collect_replica_groups", "module_num_partitions",
           "hlo_overlap_summary", "cost_summary", "memory_summary"]

#: HLO shape-prefix dtype -> bytes per element (unknown dtypes assume 4)
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
               "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

#: the collective-op vocabulary the audit and the overlap analyzer track
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")

#: every word ``jax.named_scope`` is given under models/, moe/, the inference
#: engine and the train engine, outermost first where they nest
#: (tests/unit/test_scope_table.py walks the sources). ``layers`` is the
#: layer scan: what reads ``layers`` and no deeper word is the scan's own
#: slicing of the stacked leaves.
SCOPES = ("embed", "layers", "hc_maps", "hc_mix", "attn", "qkv", "out_proj",
          "kv_write", "kv_read", "attend_window", "attend_full",
          "attend_latent", "latent_up", "absorb", "mlp", "dense_mlp",
          "moe", "router", "moe_experts", "shared_expert", "conv", "head",
          "loss", "sample", "unmask", "verify", "optimizer")

#: the pass of a differentiated program an instruction belongs to, by how
#: autodiff wraps the name stack; put in front of the scope
PASSES = ("forward", "remat", "backward")

_PAT_SINGLE = re.compile(
    r"=\s*(\w+)\[([\d,]*)\]\S*\s+(" + "|".join(COLLECTIVES) + r")\(")
_PAT_TUPLE = re.compile(
    r"=\s*\(([^)]+)\)\s+(" + "|".join(COLLECTIVES) + r")\(")
_PAT_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    numel = math.prod([int(d) for d in dims.split(",") if d] or [1])
    return numel * DTYPE_BYTES.get(dtype, 4)


def collect_collectives(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """{op: {count, bytes}} over the compiled module (fusion-internal
    shapes included via the op's result shape). Synchronous forms only —
    async start/done pairs are ``collect_async``'s domain."""
    out: Dict[str, Dict[str, int]] = {}
    # single-result form only ('= f32[...] all-reduce('); tuple results
    # ('= (f32[...], ...) all-reduce(') are handled by _PAT_TUPLE below —
    # anchoring at '= <dtype>[' keeps the two disjoint
    for m in _PAT_SINGLE.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(dtype, dims)
    # tuple-result collectives (all-reduce of N tensors) print as
    # `(f32[...], f32[...]) all-reduce(` — catch those too
    for m in _PAT_TUPLE.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        for sm in _PAT_SHAPE.finditer(shapes):
            rec["bytes"] += _shape_bytes(sm.group(1), sm.group(2))
    return out


def collect_async(hlo_text: str) -> Dict[str, int]:
    """Per-op counts of collectives in async start/done form. XLA prints
    dedicated pairs for some ops (``all-gather-start(``) and wraps the
    rest in generic ``async-start`` instructions whose line names the
    wrapped op; both count."""
    out: Dict[str, int] = {}
    for op in COLLECTIVES:
        n = len(re.findall(rf"\b{op}-start\(", hlo_text))
        n += len(re.findall(rf"\basync-start[^\n]*\b{op}\b", hlo_text))
        if n:
            out[op] = n
    return out


_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)")
#: replica_groups in either printed form: explicit nested brace lists
#: ('{{0,1},{2,3}}', '{}' = all devices) or the iota shorthand
#: ('[G,S]<=[d0,d1,...]' with an optional 'T(perm)' transpose)
_RG_RE = re.compile(
    r"replica_groups=(\{(?:\{[\d,\s]*\}(?:,\s*)?)*\}|"
    r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_RG_LINE_RE = re.compile(
    r"(%?[\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(")
_IOTA_RE = re.compile(
    r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _expand_iota_groups(shape, dims, perm):
    """Expand HLO's iota replica-group shorthand ``[G,S]<=[dims]T(perm)``:
    device ids are ``transpose(arange(prod(dims)).reshape(dims), perm)``
    flattened, then chunked into G groups of S."""
    total = math.prod(dims)
    # row-major strides of the ORIGINAL dims layout
    strides = [1] * len(dims)
    for i in range(len(dims) - 2, -1, -1):
        strides[i] = strides[i + 1] * dims[i + 1]
    tdims = [dims[p] for p in perm]
    tstrides = [strides[p] for p in perm]
    flat = []
    for i in range(total):
        rem, off = i, 0
        for d, s in zip(reversed(tdims), reversed(tstrides)):
            off += (rem % d) * s
            rem //= d
        flat.append(off)
    group_size = shape[-1] if shape else total
    n_groups = max(1, total // max(1, group_size))
    return [flat[g * group_size:(g + 1) * group_size]
            for g in range(n_groups)]


def module_num_partitions(hlo_text: str) -> int:
    """The compiled module's declared partition count (0 when the header
    does not carry one)."""
    m = _NUM_PARTITIONS_RE.search(hlo_text)
    return int(m.group(1)) if m else 0


def collect_replica_groups(hlo_text: str):
    """One record per instruction carrying a ``replica_groups=`` field:
    ``{"name", "op", "groups", "form", "line"}``. ``groups`` is the
    expanded ``[[device ids], ...]`` membership — ``None`` for the empty
    form (``replica_groups={}``: every device in one group). ``form`` is
    ``"explicit"``, ``"iota"`` or ``"all"``. Shared by the HLO
    collective-safety auditors and the overlap analyzer so nobody
    re-regexes the module text."""
    out = []
    for lineno, line in enumerate(hlo_text.split("\n"), start=1):
        if "replica_groups=" not in line:
            continue
        rg = _RG_RE.search(line)
        if not rg:
            continue
        m = _RG_LINE_RE.search(line)
        name = m.group(1).lstrip("%") if m else f"line{lineno}"
        op = m.group(2) if m else ""
        body = rg.group(1)
        if body.startswith("["):
            im = _IOTA_RE.match(body)
            shape = [int(x) for x in im.group(1).split(",")]
            dims = [int(x) for x in im.group(2).split(",")]
            perm = ([int(x) for x in im.group(3).split(",")]
                    if im.group(3) else list(range(len(dims))))
            groups = _expand_iota_groups(shape, dims, perm)
            form = "iota"
        elif body == "{}":
            groups, form = None, "all"
        else:
            groups = [[int(x) for x in g.split(",") if x.strip()]
                      for g in re.findall(r"\{([\d,\s]*)\}", body[1:-1])]
            form = "explicit"
        out.append({"name": name, "op": op, "groups": groups,
                    "form": form, "line": lineno})
    return out


#: ops with matmul/reduction-class work — the compute a latency-hiding
#: executor can run under an in-flight collective. Elementwise and
#: data-movement ops are deliberately absent: they are memory-bound
#: epilogues that attach to their producers (a dequantize multiply or a
#: tanh fusion hides nothing by itself). A ``fusion`` counts only when
#: its fused computation body contains one of these.
_HEAVY_RE = re.compile(
    r"^(dot|convolution|custom-call|reduce|reduce-window|sort|while|"
    r"scatter|select-and-scatter|rng|rng-bit-generator|cholesky|"
    r"triangular-solve|fft)(\.|$)")

_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?(%?[\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)"
    r"\(([^)]*)\)")
_NAME_TOKEN_RE = re.compile(r"%[\w.\-]+")
_CALLS_RE = re.compile(r"calls=(%?[\w.\-]+)")
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*(?:\(|\s)")


def _parse_computations(hlo_text: str) -> Dict[str, list]:
    """{computation name: [instruction lines]} for every computation in
    an HLO module dump (ENTRY, while/cond bodies, fusion bodies)."""
    out: Dict[str, list] = {}
    block: list = []
    name = None
    depth = 0
    for line in hlo_text.split("\n"):
        stripped = line.strip()
        if depth == 0:
            if stripped.endswith("{") and "(" in stripped:
                m = _COMP_HEADER_RE.match(stripped)
                name = m.group(1) if m else f"_anon{len(out)}"
                depth = 1
                block = []
            continue
        depth += stripped.count("{") - stripped.count("}")
        if depth <= 0:
            if block:
                out[name] = block
            depth = 0
            continue
        if "=" in stripped:
            block.append(stripped)
    return out


def _instr_op(line: str) -> str:
    m = _INSTR_RE.match(line)
    return m.group(3) if m else ""


def _is_collective(op: str) -> bool:
    return any(op == c or op.startswith(f"{c}.") for c in COLLECTIVES)


def _count_between(sorted_levels, lo: int, hi: int) -> int:
    """Heavy ops with level strictly inside (lo, hi)."""
    import bisect
    if hi <= lo:
        return 0
    return bisect.bisect_left(sorted_levels, hi) - \
        bisect.bisect_right(sorted_levels, lo)


def collect_schedule_overlap(hlo_text: str) -> Dict[str, Any]:
    """Dependency-level static overlap of a compiled module's collectives.

    ASAP levels count the heavy ops (matmul/reduction class, see
    ``_HEAVY_RE``; fusions classified by their fused body) on each
    value's critical path. For each synchronous collective C the window
    runs from C's ready level to the minimum level of its first *real*
    consumer — a heavy op or another collective, traced through
    elementwise/movement ops (a dequantize epilogue does not end the
    window; the matmul that needs the data does). C is **overlappable**
    when a heavy op's level falls strictly inside that window: compute
    that is independent of C by construction (ancestors sit below the
    window, descendants at or above its end) which an async executor
    could run while C is on the wire. Collectives already emitted in
    async start/done form count as overlappable outright.

    A single fused whole-tree exchange scores 0 (every heavy op either
    feeds it or waits on it); a bucketed exchange issued in layer order
    scores (nb-1)/nb-ish — the metric ``benchmarks/overlap.py`` records
    as CPU evidence and the schedule autotuner scores."""
    comps = _parse_computations(hlo_text)
    # a fusion is heavy iff its fused body does real work
    heavy_fusion: Dict[str, bool] = {}
    for cname, block in comps.items():
        heavy_fusion[cname.lstrip("%")] = any(
            _HEAVY_RE.match(_instr_op(line)) for line in block)

    def is_heavy(op: str, line: str) -> bool:
        if _HEAVY_RE.match(op):
            return True
        if op == "fusion" or op.startswith("fusion."):
            m = _CALLS_RE.search(line)
            return bool(m) and heavy_fusion.get(m.group(1).lstrip("%"),
                                                False)
        return False

    total = 0
    overlappable = 0
    async_n = 0
    windows = []
    for cname, block in comps.items():
        if not any(_is_collective(_instr_op(l)) or "-start" in _instr_op(l)
                   for l in block):
            continue                     # no collectives: nothing to score
        names: list = []
        ops: list = []
        heavy: list = []
        operand_lists: list = []
        index: Dict[str, int] = {}
        for line in block:
            m = _INSTR_RE.match(line)
            if not m:
                names.append(None)
                ops.append("")
                heavy.append(False)
                operand_lists.append([])
                continue
            name, op, operands = m.group(2), m.group(3), m.group(4)
            names.append(name)
            ops.append(op)
            heavy.append(is_heavy(op, line))
            operand_lists.append(_NAME_TOKEN_RE.findall(operands))
            index[name] = len(names) - 1
        if not names:
            continue
        # ASAP heavy-op levels + a users index (producer idx -> consumers)
        asap = [0] * len(names)
        users: Dict[int, list] = {}
        for i, operands in enumerate(operand_lists):
            lvl = 0
            for tok in operands:
                j = index.get(tok)
                if j is None:
                    continue
                lvl = max(lvl, asap[j])
                users.setdefault(j, []).append(i)
            asap[i] = lvl + 1 if heavy[i] else lvl
        heavy_levels = sorted(asap[i] for i in range(len(names))
                              if heavy[i])
        max_level = max(asap) if asap else 0
        for i, op in enumerate(ops):
            is_async = any(op.startswith(f"{c}-start") for c in COLLECTIVES)
            if not is_async and not _is_collective(op):
                continue
            total += 1
            if is_async:
                async_n += 1
                overlappable += 1
                continue
            # first real consumer level, traced through light ops
            frontier = [i]
            seen = {i}
            consumer_lvl = None
            while frontier:
                j = frontier.pop()
                for k in users.get(j, ()):
                    if k in seen:
                        continue
                    seen.add(k)
                    if heavy[k] or _is_collective(ops[k]):
                        lvl = asap[k] if heavy[k] else asap[k] + 1
                        if consumer_lvl is None or lvl < consumer_lvl:
                            consumer_lvl = lvl
                    else:
                        frontier.append(k)
            if consumer_lvl is None:
                consumer_lvl = max_level + 1     # consumed by the output
            lo, hi = asap[i], consumer_lvl
            n_hidden = _count_between(heavy_levels, lo, hi)
            if n_hidden > 0:
                overlappable += 1
            windows.append({"op": op, "ready_level": lo,
                            "consumer_level": hi,
                            "compute_in_window": n_hidden})
    return {
        "collectives": total,
        "overlappable": overlappable,
        "async": async_n,
        "static_overlap_fraction":
            round(overlappable / total, 6) if total else 0.0,
        "windows": windows[:256],
    }


def hlo_overlap_summary(hlo_text: str) -> Dict[str, Any]:
    """The static overlap instrument: how much of the module's collective
    schedule is even *overlappable*. ``async_fraction`` is async ops over
    all collective ops, in [0, 1] — 0 on a fully synchronous schedule
    (the CPU backend), 1 when every collective has a start/done pair the
    latency-hiding scheduler can move compute between. The wall-clock
    half (did the overlap actually happen) comes from a device trace via
    telemetry/overlap.py."""
    sync = collect_collectives(hlo_text)
    async_ = collect_async(hlo_text)
    sched = collect_schedule_overlap(hlo_text)
    n_sync = sum(v["count"] for v in sync.values())
    n_async = sum(async_.values())
    total = n_sync + n_async
    return {
        "collectives": total,
        "sync": n_sync,
        "async": n_async,
        "async_fraction": round(n_async / total, 6) if total else 0.0,
        # the dependency-level instrument (collect_schedule_overlap):
        # collectives with hideable compute in their issue window — the
        # CPU-measurable half of the overlap story, and what the bucketed
        # ZeRO schedule raises on a backend with no async HLO forms
        "overlappable": sched["overlappable"],
        "static_overlap_fraction": sched["static_overlap_fraction"],
        "sync_bytes": sum(v["bytes"] for v in sync.values()),
        "per_op_sync": {op: v["count"] for op, v in sorted(sync.items())},
        "per_op_async": dict(sorted(async_.items())),
    }


def cost_summary(raw: Any) -> Dict[str, float]:
    """Normalize a ``cost_analysis()`` result to {identifier: float}.
    Handles the list/tuple wrapping of older jax versions, drops
    non-numeric values, and rewrites keys like ``"bytes accessed"`` to
    ``bytes_accessed`` (the per-operand ``bytes accessed0{}`` entries are
    dropped — consumers want module totals)."""
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not raw:
        return {}
    out: Dict[str, float] = {}
    for key, val in dict(raw).items():
        try:
            fval = float(val)
        except (TypeError, ValueError):
            continue
        name = re.sub(r"[^0-9a-zA-Z]+", "_", str(key)).strip("_")
        if re.search(r"\d", name):      # per-operand entries: skip
            continue
        out[name] = fval
    return out


def memory_summary(stats: Any) -> Optional[Dict[str, int]]:
    """``memory_analysis()`` CompiledMemoryStats -> plain dict of the
    per-device ``*_in_bytes`` fields (argument/output/temp/alias/
    generated_code, plus the host-memory variants when non-zero).
    Returns None when the backend reports nothing."""
    if stats is None:
        return None
    out: Dict[str, int] = {}
    for attr in dir(stats):
        if not attr.endswith("_size_in_bytes"):
            continue
        try:
            val = int(getattr(stats, attr))
        except (TypeError, ValueError):
            continue
        if attr.startswith("host_") and val == 0:
            continue                     # host fields are usually all-zero
        out[attr[:-len("_size_in_bytes")]] = val
    return out or None


#: token-bounded, as the flops profiler matches its phases: under autodiff
#: the stack's segments are wrapped ('jvp(attn)', 'transpose(jvp(mlp))'), and
#: a plain substring would take 'num_heads' for 'head'
_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_])(" + "|".join(SCOPES) + r")(?![A-Za-z0-9_])")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FUSION_RE = re.compile(r"\sfusion\(")
_APPLIED_RE = re.compile(
    r"(?:to_apply=|called_computations=\{)(%?[\w.\-]+)")
#: instructions the compiler emits no code for: never an event in a trace
_FREE_RE = re.compile(
    r"\s(parameter|constant|get-tuple-element|tuple|bitcast)\(")


def scope_words(name_stack: str) -> list:
    """The ``SCOPES`` words of a name stack or an ``op_name``, in order."""
    return _SCOPE_RE.findall(name_stack)


def _name_of(line: str) -> str:
    """``ROOT %fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = line.split(" = ", 1)[0]
    return (head[5:] if head.startswith("ROOT ") else head).lstrip("%")


def _scope_of(op_name: str) -> Optional[str]:
    """``jit(f)/transpose(jvp(layers))/while/body/attn/qkv/dot_general`` ->
    ``backward/layers/attn/qkv``: the pass the name stack shows, then its
    ``SCOPES`` words in order; ``None`` where it shows neither."""
    if "rematted_computation" in op_name:
        parts = ["remat"]
    elif "transpose(jvp(" in op_name:
        parts = ["backward"]
    elif "jvp(" in op_name:
        parts = ["forward"]
    else:
        parts = []
    parts += scope_words(op_name)
    return "/".join(parts) or None


def scope_table(hlo_text: str) -> Dict[str, Optional[str]]:
    """{instruction name: scope} of a compiled module's optimized HLO
    (``compiled.as_text()``), for every instruction of a computation that is
    neither a fusion's body nor applied an element by a reduce or a sort:
    the entry, ``while`` bodies and conditions, called computations. Those
    are what a device trace's "XLA Ops" line has events for, under these
    names; parameters, constants, tuples and bitcasts, for which the
    compiler emits no code, are left out. A fusion inside a fusion's body
    is listed too (the trace nests its event in the outer one's).

    The scope is the ``SCOPES`` words of the instruction's ``op_name``
    metadata in order, joined by ``/``, behind the pass where the name stack
    shows one (``PASSES``). An instruction whose own ``op_name`` holds no
    word (a fusion whose root carries no metadata) takes the commonest scope
    with a word among the instructions of the computation it ``calls=``,
    nested fusions included; a nested fusion with neither, that of the
    fusion around it. Those are the program's own names.

    What is left carries no name anywhere: what the compiler put in (a
    prefetch's ``copy-start``/``copy-done``, a re-laying ``copy``, the
    pieces a cumulative sum is expanded into) or renamed (the Mosaic kernel
    a ``ragged_dot`` becomes reads ``op_name="ragged-dot-none"``). Such an
    instruction is given what the instructions that use its result share
    (``_shared``), else what its operands share, and the scope is marked
    INFERRED by a leading ``?`` (``?layers/moe``): a reader adds its time
    under the scope and counts it apart. With neither it reads its pass
    alone, or ``None``."""
    comps = {name.lstrip("%"): block
             for name, block in _parse_computations(hlo_text).items()}

    def called(line):
        m = _CALLS_RE.search(line)
        return m.group(1).lstrip("%") if m else None

    # no events of their own: a fusion's body, and what a reduce, a sort, a
    # scatter or a custom call's top-k applies an element (``to_apply=``,
    # ``called_computations=``; a ``call`` runs its own)
    inner = {called(line) for block in comps.values() for line in block
             if _FUSION_RE.search(line)}
    inner |= {name.lstrip("%") for block in comps.values()
              for line in block if " call(" not in line
              for name in _APPLIED_RE.findall(line)}

    def worded(scope):
        return scope is not None and scope.lstrip("?") not in PASSES

    inside: Dict[str, Optional[str]] = {}

    def commonest(cname, seen=()):
        """The commonest worded scope among a called computation's
        instructions; the first met wins a tie."""
        if cname in inside:
            return inside[cname]
        votes: Dict[str, int] = {}
        for line in comps.get(cname, ()):
            scope = named(line, seen + (cname,))
            if worded(scope):
                votes[scope] = votes.get(scope, 0) + 1
        inside[cname] = max(votes, key=votes.get) if votes else None
        return inside[cname]

    def named(line, seen=()):
        """The scope the program's own names give an instruction: its
        ``op_name``'s (a merged instruction lists its sources' names: the
        first is whole), else its called computation's commonest."""
        m = _OP_NAME_RE.search(line)
        scope = _scope_of(m.group(1).split(";")[0]) if m else None
        if not worded(scope) and called(line) not in seen + (None,):
            scope = commonest(called(line), seen) or scope
        return scope

    table: Dict[str, Optional[str]] = {}
    bodies = []                 # (a listed fusion's body, the fusion's name)
    for cname, block in comps.items():
        if cname in inner:
            continue
        mine: Dict[str, Optional[str]] = {}
        free, operands = {}, {}
        for line in block:
            name = _name_of(line)
            mine[name] = named(line)
            kind = _FREE_RE.search(line)
            if kind:
                free[name] = kind.group(1)
            elif _FUSION_RE.search(line):
                bodies.append((called(line), name))
            operands[name] = [t.lstrip("%") for t in _NAME_TOKEN_RE.findall(
                line.split(" = ", 1)[1])]
        users: Dict[str, list] = {}
        for name, ops in operands.items():
            ops[:] = [o for o in ops if o in mine and o != name]
            for o in ops:
                users.setdefault(o, []).append(name)
        # the unnamed from their neighbours, until nothing moves: first from
        # what uses them alone, so that a chain of them is named from its
        # far end, and only what that leaves from their operands too. A
        # get-tuple-element or a bitcast hands a scope on either way; a
        # tuple only back to what it packs (a loop names what is packed for
        # it, its result names nothing); a parameter or a constant stands
        # for no scope
        for sides in ((users,), (users, operands)):
            moved = True
            while moved:
                moved = False
                for name, scope in mine.items():
                    if worded(scope) or free.get(name) in ("parameter",
                                                           "constant"):
                        continue
                    for side in sides:
                        if side is operands and free.get(name) == "tuple":
                            continue
                        near = _shared([mine[n].lstrip("?")
                                        for n in side.get(name, ())
                                        if worded(mine[n])])
                        # a forward instruction is not renamed by the
                        # backward ones that use it
                        if near and (not scope
                                     or near.split("/")[0] == scope):
                            mine[name], moved = "?" + near, True
                            break
        table.update((n, s) for n, s in mine.items() if n not in free)
    while bodies:
        cname, around = bodies.pop()
        for line in comps.get(cname, ()):
            if _FUSION_RE.search(line):
                scope = named(line)
                table[_name_of(line)] = scope if worded(scope) \
                    else table[around]
                bodies.append((called(line), _name_of(line)))
    return table


def _shared(scopes) -> Optional[str]:
    """What every one of ``scopes`` begins with, in whole words: the leading
    ``SCOPES`` words they have in common, behind the pass where each has
    the same one (``backward`` where some have that: a backward loop's body
    holds remat's forward beside it). ``None`` where they share no word."""
    split = [s.split("/") for s in scopes]
    passes = [p[0] for p in split if p[0] in PASSES]
    words = [p[1:] if p[0] in PASSES else p for p in split]
    lead = []
    for column in zip(*words):
        if len(set(column)) > 1:
            break
        lead.append(column[0])
    if not lead:
        return None
    if len(passes) == len(split) and (len(set(passes)) == 1
                                      or "backward" in passes):
        lead.insert(0, "backward" if "backward" in passes else passes[0])
    return "/".join(lead)
