"""ds_tpu_lint Plane A — auditors over REAL lowered/compiled artifacts.

The runtime discipline (one comm dispatch, explicit shard_map exchange
legs, optimization_barrier pin chains) is only as good as the programs
XLA actually emits. These rules read the artifacts themselves — the
compiled HLO text for collective structure, the lowered StableHLO for
argument donation — so a deadlock-shaped or HBM-doubling-shaped bug is
caught on the CPU lowering *before* it becomes a hang on real chips
(DeepCompile's premise: the compiled schedule is an analyzable
artifact; EQuARX's warning: quantized collective legs are where silent
group mismatches hide).

Rules (registry + docs in findings.py):

- HLO001 orphaned-async       — every ``*-start`` pairs with a done
- HLO002 replica-groups-partition — groups exactly partition devices
- HLO003 subaxis-inconsistency — same group shape ⇒ same partition
- HLO004 issue-order-divergence — identical collective issue order
  across per-device programs (static shard_map deadlock check)
- HLO005 undonated-buffer     — large state args must be donated
- HLO006 dispatch-conformance — every HLO collective kind reconciles
  with the comm dispatch's traced accounting

Inputs arrive as :class:`HloArtifact` records —
``analysis/artifacts.py`` lowers the repo's real programs (ZeRO-3
bucketed train step, ``decode_with_slots``, pipe step, MoE step) into
them, and tests feed synthetic seeded-violation fixtures.

Standalone-loadable like findings.py: ``bin/ds_tpu_lint`` file-path-
loads it (with hlo_cost registered under ``_dstpu_hlo_cost``) so saved
``.hlo`` files can be audited without jax.
"""

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

try:
    from .findings import Finding, make_key
    from ..telemetry.hlo_cost import (collect_async, collect_collectives,
                                      collect_replica_groups,
                                      module_num_partitions)
except ImportError:                    # loaded by file path (bin/ds_tpu_lint)
    from _dstpu_lint_findings import Finding, make_key  # type: ignore
    from _dstpu_hlo_cost import (collect_async,  # type: ignore
                                 collect_collectives,
                                 collect_replica_groups,
                                 module_num_partitions)

__all__ = ["HloArtifact", "run_hlo_audit", "collect_donation",
           "DISPATCH_ACCEPTS"]

#: HLO collective kind -> comm-dispatch op names whose traced presence
#: legitimizes it. Many-to-many because quantized/hierarchical dispatch
#: paths lower one logical op into several HLO kinds: a quantized
#: all_reduce is an RS+AG pair, the hierarchical reduce_scatter is a
#: chunk-permute + intra psum_scatter + inter all_to_all, and GSPMD
#: inserts its own all-reduces (loss/grad-norm) and collective-permutes
#: (resharding) alongside any explicitly dispatched exchange.
DISPATCH_ACCEPTS: Dict[str, Tuple[str, ...]] = {
    "all-reduce": ("all_reduce", "broadcast", "scatter", "reduce_scatter",
                   "all_gather"),
    "all-gather": ("all_gather", "all_reduce"),
    "reduce-scatter": ("reduce_scatter", "all_reduce"),
    "all-to-all": ("all_to_all", "reduce_scatter"),
    "collective-permute": ("ppermute", "reduce_scatter", "all_to_all"),
}

_ASYNC_KINDS = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")


@dataclass
class HloArtifact:
    """One lowered program under audit.

    ``hlo_texts``: compiled HLO module text(s) — one entry per device
    program (SPMD emits one; a list exercises the HLO004 cross-program
    order check). ``stablehlo``: the pre-compile lowering, whose
    ``func.func @main`` argument list carries donation attributes in
    flatten order — that is what lets HLO005 name the ROLE of an
    undonated buffer. ``arg_roles``: ``[(role, leaf_count), ...]`` in
    argument flatten order (role names follow the HBMLedger vocabulary:
    params / optimizer_state / kv_slots / batch / …). ``donatable_roles``:
    roles that are state-in/state-out for this program and therefore
    SHOULD be donated (a serving program's weights are read-only and
    exempt). ``traced_per_op``: comm dispatch per-op trace counts
    captured while this artifact was lowered (comm.comm_per_op_stats
    delta); None disables HLO006."""
    name: str
    hlo_texts: List[str] = field(default_factory=list)
    stablehlo: Optional[str] = None
    arg_roles: Optional[List[Tuple[str, int]]] = None
    donatable_roles: Set[str] = field(default_factory=set)
    traced_per_op: Optional[Dict[str, int]] = None
    comm_delta: Optional[Dict[str, int]] = None
    donation_min_bytes: int = 1 << 20
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def path(self) -> str:
        return f"hlo:{self.name}"


# ------------------------------------------------------------- donation

_MAIN_RE = re.compile(r"func\.func\s+(?:public\s+)?@main\((.*?)\)\s*->",
                      re.DOTALL)
# the attr dict may nest braces inside quoted strings ('mhlo.sharding =
# "{devices=[8,1]<=[8]}"') and, under Shardy, one level of bare ones
# ('sdy.sharding = #sdy.sharding<@mesh, [{}, {"data"}]>'). Every run is
# possessive: with plain quantifiers a dict this pattern cannot close
# backtracks exponentially (jax 0.9 signatures never returned).
_ATTR_RUN = r"[^{}\"]++|\"[^\"]*+\""
_ARG_RE = re.compile(
    r"%arg(\d+):\s*tensor<([^>]*)>\s*"
    r"(\{(?:" + _ATTR_RUN + r"|\{(?:" + _ATTR_RUN + r")*+\})*+\})?")
_MLIR_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "i64": 8,
                     "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
                     "i8": 1, "ui8": 1, "i1": 1, "f8E4M3FN": 1,
                     "f8E5M2": 1}
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")


def _alias_header_body(hlo_text: str) -> Optional[str]:
    """The balanced-brace body of the module header's
    ``input_output_alias={...}`` (entries nest ``{}`` twice, which a
    regex can't scan)."""
    key = "input_output_alias={"
    i = hlo_text.find(key)
    if i < 0:
        return None
    j = i + len(key)
    depth = 1
    while j < len(hlo_text) and depth:
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        j += 1
    return hlo_text[i + len(key):j - 1]


def _mlir_tensor_bytes(ty: str) -> int:
    parts = ty.split("x")
    dtype = parts[-1]
    dims = parts[:-1]
    n = 1
    for d in dims:
        if not d.isdigit():
            return 0                  # dynamic dim: size unknowable
        n *= int(d)
    return n * _MLIR_DTYPE_BYTES.get(dtype, 4)


def collect_donation(stablehlo: str) -> List[Dict[str, Any]]:
    """Per-argument donation records from a lowered StableHLO module:
    ``{"index", "type", "bytes", "donated"}`` in flatten order.
    Donation is the ``tf.aliasing_output`` / ``jax.buffer_donor``
    attribute jax stamps on donated arguments."""
    m = _MAIN_RE.search(stablehlo)
    sig = m.group(1) if m else stablehlo
    out = []
    for am in _ARG_RE.finditer(sig):
        attrs = am.group(3) or ""
        out.append({
            "index": int(am.group(1)),
            "type": am.group(2),
            "bytes": _mlir_tensor_bytes(am.group(2)),
            "donated": ("tf.aliasing_output" in attrs or
                        "jax.buffer_donor" in attrs),
        })
    return out


def donated_params_from_hlo(hlo_text: str) -> Set[int]:
    """Parameter numbers aliased to an output in a compiled module's
    ``input_output_alias`` header — the post-compile cross-check for
    the StableHLO donation attributes."""
    body = _alias_header_body(hlo_text)
    if body is None:
        return set()
    return {int(x) for x in _ALIAS_ENTRY_RE.findall(body)}


def _role_of(index: int, arg_roles) -> str:
    if not arg_roles:
        return "unknown"
    off = 0
    for role, count in arg_roles:
        if index < off + count:
            return role
        off += count
    return "unknown"


# ------------------------------------------------------------- the rules

def _audit_async(art: HloArtifact, findings: List[Finding]):
    for mi, hlo in enumerate(art.hlo_texts):
        for kind in _ASYNC_KINDS:
            starts = len(re.findall(rf"\b{kind}-start\(", hlo))
            dones = len(re.findall(rf"\b{kind}-done\(", hlo))
            if starts != dones:
                findings.append(Finding(
                    rule="HLO001", severity="error", path=art.path, line=0,
                    message=f"{kind}: {starts} start vs {dones} done in "
                            f"program {mi} — an in-flight collective is "
                            f"never completed (deadlock/leak shape)",
                    waiver_key=make_key("HLO001", art.name, kind)))
        g_start = len(re.findall(r"\basync-start\b", hlo))
        g_done = len(re.findall(r"\basync-done\b", hlo))
        if g_start != g_done:
            findings.append(Finding(
                rule="HLO001", severity="error", path=art.path, line=0,
                message=f"generic async-start/done mismatch "
                        f"({g_start} vs {g_done}) in program {mi}",
                waiver_key=make_key("HLO001", art.name, "async")))


def _check_partition(groups: List[List[int]], n_devices: int) -> str:
    """'' when ``groups`` exactly partition the device set, else the
    violation description."""
    sizes = {len(g) for g in groups}
    if len(sizes) > 1:
        return f"unequal group sizes {sorted(sizes)}"
    flat: List[int] = [d for g in groups for d in g]
    if len(set(flat)) != len(flat):
        dupes = sorted({d for d in flat if flat.count(d) > 1})
        return f"device(s) {dupes} appear in more than one group"
    expect = set(range(n_devices)) if n_devices else \
        set(range(max(flat) + 1)) if flat else set()
    missing = expect - set(flat)
    if missing:
        return (f"devices {sorted(missing)[:8]} participate in no group "
                f"(union must cover all {len(expect)} devices)")
    extra = set(flat) - expect
    if extra:
        return f"group members {sorted(extra)[:8]} exceed the device count"
    return ""


def _audit_replica_groups(art: HloArtifact, findings: List[Finding]):
    for mi, hlo in enumerate(art.hlo_texts):
        n_dev = module_num_partitions(hlo)
        recs = collect_replica_groups(hlo)
        # HLO002: each collective's groups partition the device set
        for rec in recs:
            if rec["groups"] is None:
                continue                 # empty form: all devices, trivially ok
            err = _check_partition(rec["groups"], n_dev)
            if err:
                findings.append(Finding(
                    rule="HLO002", severity="error", path=art.path,
                    line=rec["line"],
                    message=f"{rec['op']} %{rec['name']} replica_groups "
                            f"{rec['groups']}: {err}",
                    waiver_key=make_key("HLO002", art.name, rec["op"])))
        # HLO003: same group shape -> same partition everywhere
        by_shape: Dict[Tuple[int, int], Dict[str, Any]] = {}
        for rec in recs:
            if not rec["groups"]:
                continue
            shape = (len(rec["groups"]), len(rec["groups"][0]))
            canon = tuple(sorted(tuple(sorted(g)) for g in rec["groups"]))
            prev = by_shape.setdefault(shape, {"canon": canon, "rec": rec})
            if prev["canon"] != canon:
                findings.append(Finding(
                    rule="HLO003", severity="error", path=art.path,
                    line=rec["line"],
                    message=f"inconsistent {shape[0]}x{shape[1]} subaxis "
                            f"partition: %{prev['rec']['name']} uses "
                            f"{list(prev['canon'])[:4]} but %{rec['name']} "
                            f"uses {list(canon)[:4]} — hierarchical legs "
                            f"disagree on the (host, local) split",
                    waiver_key=make_key("HLO003", art.name,
                                        f"{shape[0]}x{shape[1]}")))


def _issue_order(hlo: str) -> List[Tuple[str, Any]]:
    """Ordered (op kind, canonical groups) sequence over the module —
    the thing every device must agree on for SPMD progress."""
    seq = []
    for rec in collect_replica_groups(hlo):
        base = re.sub(r"-start$|-done$", "", rec["op"])
        canon = None if rec["groups"] is None else \
            tuple(sorted(tuple(sorted(g)) for g in rec["groups"]))
        if rec["op"].endswith("-done"):
            continue
        seq.append((base, canon))
    return seq


def _audit_issue_order(art: HloArtifact, findings: List[Finding]):
    if len(art.hlo_texts) < 2:
        return
    ref = _issue_order(art.hlo_texts[0])
    for mi, hlo in enumerate(art.hlo_texts[1:], start=1):
        seq = _issue_order(hlo)
        if seq != ref:
            diverge = next((i for i, (a, b) in enumerate(zip(ref, seq))
                            if a != b), min(len(ref), len(seq)))
            a = ref[diverge][0] if diverge < len(ref) else "<end>"
            b = seq[diverge][0] if diverge < len(seq) else "<end>"
            findings.append(Finding(
                rule="HLO004", severity="error", path=art.path, line=0,
                message=f"collective issue order diverges between program "
                        f"0 and program {mi} at position {diverge}: "
                        f"{a} vs {b} — devices would enter different "
                        f"collectives first and deadlock",
                waiver_key=make_key("HLO004", art.name, f"program{mi}")))


def _audit_donation(art: HloArtifact, findings: List[Finding]):
    if not art.stablehlo:
        return
    args = collect_donation(art.stablehlo)
    # cross-check: the compiled module's input_output_alias should donate
    # at least the args StableHLO marked (XLA may add may-alias entries,
    # never drop requested ones silently — if it did, flag it)
    hlo_donated = donated_params_from_hlo(art.hlo_texts[0]) \
        if art.hlo_texts else None
    for a in args:
        role = _role_of(a["index"], art.arg_roles)
        if a["donated"] or a["bytes"] < art.donation_min_bytes:
            continue
        if art.donatable_roles and role not in art.donatable_roles:
            continue
        mib = a["bytes"] / 2**20
        findings.append(Finding(
            rule="HLO005", severity="error", path=art.path, line=0,
            message=f"arg {a['index']} ({role}, tensor<{a['type']}>, "
                    f"{mib:.1f} MiB) is not donated — input and output "
                    f"copies of this {role} buffer are live at once "
                    f"(HBMLedger would double-count the role)",
            waiver_key=make_key("HLO005", art.name,
                                f"{role}:{a['index']}")))
    if hlo_donated is not None and hlo_donated == set() and \
            any(a["donated"] for a in args):
        findings.append(Finding(
            rule="HLO005", severity="warning", path=art.path, line=0,
            message="StableHLO marks donated args but the compiled "
                    "module's input_output_alias is empty — XLA dropped "
                    "every donation (shape/sharding mismatch?)",
            waiver_key=make_key("HLO005", art.name, "alias-dropped")))


def _audit_dispatch(art: HloArtifact, findings: List[Finding]):
    if art.traced_per_op is None:
        return
    traced = {k: v for k, v in art.traced_per_op.items() if v}
    for mi, hlo in enumerate(art.hlo_texts):
        sync = collect_collectives(hlo)
        async_ = collect_async(hlo)
        kinds = set(sync) | set(async_)
        for kind in sorted(kinds):
            accepts = DISPATCH_ACCEPTS.get(kind, ())
            if any(traced.get(op) for op in accepts):
                continue
            count = sync.get(kind, {}).get("count", 0) + async_.get(kind, 0)
            findings.append(Finding(
                rule="HLO006", severity="error", path=art.path, line=0,
                message=f"{count} {kind} op(s) in the compiled module but "
                        f"the comm dispatch traced none of "
                        f"{list(accepts) or '(any)'} — these bytes bypass "
                        f"comm_stats() and every compression policy",
                waiver_key=make_key("HLO006", art.name, kind),
                meta={"hlo_count": count, "traced": traced}))


def run_hlo_audit(artifacts: Sequence[HloArtifact],
                  rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Plane A over a set of artifacts. Returns raw findings — the
    caller applies waivers."""
    active = set(rules) if rules else {"HLO001", "HLO002", "HLO003",
                                       "HLO004", "HLO005", "HLO006"}
    findings: List[Finding] = []
    for art in artifacts:
        if "HLO001" in active:
            _audit_async(art, findings)
        if {"HLO002", "HLO003"} & active:
            _audit_replica_groups(art, findings)
        if "HLO004" in active:
            _audit_issue_order(art, findings)
        if "HLO005" in active:
            _audit_donation(art, findings)
        if "HLO006" in active:
            _audit_dispatch(art, findings)
    return findings
