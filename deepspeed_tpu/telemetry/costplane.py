"""Cost plane — per-request / per-tenant chip-second & HBM attribution.

The goodput ledger (telemetry/goodput.py) answers "where did the wall
clock go" with exclusive buckets that sum to wall time by construction.
This module applies the same accounting discipline *per request*: every
second of serving wall-clock is split across the requests occupying it,
and whatever no request can claim lands in an explicit overhead
residual — so per-replica request costs + overhead **sum to serving
wall-clock by construction**, the invariant the soak scorecard checks.

Attribution rules (the contract ``tests/unit/test_costplane.py`` rigs):

- **Decode ticks** are divided over the active slots weighted by tokens
  emitted that tick. On the non-speculative path every slot emits one
  token, so the split is equal; on the speculative path accepted draft
  tokens credit their request and the draft/verify overhead is split
  pro-rata (one weighted split of the whole tick wall by emitted
  tokens achieves both).
- **Prefill** (inline, suffix after a radix hit, chunked, lane-copy,
  handoff insert) is charged whole to the owning request — prefill is
  never shared work.
- **Radix-cache hits** record *avoided* prefill cost as explicit
  savings: reused tokens x the EMA of observed per-token prefill cost.
  Savings are what the fleet did NOT pay, kept separate from chip_ms so
  costs still sum to wall; the scorecard cross-checks that the implied
  per-token savings rate never exceeds the paid rate by more than a
  small slack.
- **HBM byte-seconds** accrue per slot from the pool footprint
  (int8-aware: a quantized pool's q+scales bytes are what the device
  holds, the same bytes the PR-7 HBM ledger's ``kv_slots`` role counts)
  x residency, sampled every tick for every occupied slot (decoding or
  mid-chunked-prefill).
- **Overhead** is the tick residual: tick wall minus everything
  attributed. Idle ticks (no occupants) are pure overhead.

A per-request :class:`CostRecord` rides the request's ``TraceContext``
(``telemetry/disttrace.py``), so it crosses KV handoffs inside the frame
header and survives failover — a survivor replica's charges accumulate
into the SAME record, attributed by attempt number. Per-tenant totals
accumulate at charge time in each replica's :class:`CostLedger` and are
folded fleet-wide by the ``FleetRouter`` (``cost_summary``), which is
where the ``dstpu_cost_*`` Prometheus family, the ``/statusz`` costs
table, and the scorecard section come from.

Disabled (the default) allocates nothing: the scheduler holds ``None``
and every hook is a single ``is None`` test.
"""

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["CostRecord", "CostLedger", "tree_nbytes", "merge_cost_totals",
           "capacity_report"]

_GIB = 1024.0 ** 3

#: the per-tenant metrics a fold carries — the dstpu_cost_* family plus
#: the denominators the capacity report divides by
TENANT_COST_METRICS = ("chip_ms", "decode_ms", "prefill_ms", "hbm_gib_s",
                       "tokens", "prompt_tokens", "cache_savings_ms",
                       "cache_saved_tokens", "requests")


def tree_nbytes(tree) -> int:
    """Host-side logical bytes of an array pytree (no device sync):
    ``sum(leaf.size * leaf.dtype.itemsize)``. A quantized pool's int8 q
    + f32 scales leaves count at their real widths, so the figure is
    int8-aware by construction."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        total += int(size) * int(dtype.itemsize)
    return total


@dataclasses.dataclass
class CostRecord:
    """One request's accumulated cost, fleet-wide. Travels on the
    request's TraceContext: serialized into the KVHandoff frame header
    by ``to_dict`` and revived by ``from_dict`` on the decode side, and
    carried through failover by the router's persistent context — every
    attempt charges into the same record, keyed by attempt number."""
    request_id: Optional[int] = None
    tenant: str = "default"
    decode_ms: float = 0.0
    prefill_ms: float = 0.0
    hbm_gib_s: float = 0.0
    tokens: int = 0
    prompt_tokens: int = 0
    cache_savings_ms: float = 0.0
    cache_saved_tokens: int = 0
    #: chip_ms per attempt (0 = first): a failed-over request shows
    #: exactly what each attempt cost, including the abandoned one
    by_attempt: Dict[int, float] = dataclasses.field(default_factory=dict)
    #: the live attempt number (trace.replays), refreshed on every fetch
    attempt: int = 0

    @property
    def chip_ms(self) -> float:
        return self.decode_ms + self.prefill_ms

    def charge(self, ms: float, *, decode: bool):
        if decode:
            self.decode_ms += ms
        else:
            self.prefill_ms += ms
        self.by_attempt[self.attempt] = \
            self.by_attempt.get(self.attempt, 0.0) + ms

    def to_dict(self) -> dict:
        return {"request_id": self.request_id, "tenant": self.tenant,
                "decode_ms": self.decode_ms, "prefill_ms": self.prefill_ms,
                "hbm_gib_s": self.hbm_gib_s, "tokens": self.tokens,
                "prompt_tokens": self.prompt_tokens,
                "cache_savings_ms": self.cache_savings_ms,
                "cache_saved_tokens": self.cache_saved_tokens,
                "by_attempt": {str(k): v for k, v in self.by_attempt.items()},
                "attempt": self.attempt}

    @classmethod
    def from_dict(cls, d: dict) -> "CostRecord":
        rec = cls(request_id=d.get("request_id"),
                  tenant=d.get("tenant") or "default",
                  decode_ms=float(d.get("decode_ms", 0.0)),
                  prefill_ms=float(d.get("prefill_ms", 0.0)),
                  hbm_gib_s=float(d.get("hbm_gib_s", 0.0)),
                  tokens=int(d.get("tokens", 0)),
                  prompt_tokens=int(d.get("prompt_tokens", 0)),
                  cache_savings_ms=float(d.get("cache_savings_ms", 0.0)),
                  cache_saved_tokens=int(d.get("cache_saved_tokens", 0)),
                  attempt=int(d.get("attempt", 0)))
        rec.by_attempt = {int(k): float(v)
                          for k, v in (d.get("by_attempt") or {}).items()}
        return rec

    def summary(self) -> dict:
        out = self.to_dict()
        out["chip_ms"] = round(self.chip_ms, 3)
        return out


class _TenantCost:
    """One tenant's accumulated totals on one replica's ledger."""

    __slots__ = TENANT_COST_METRICS

    def __init__(self):
        self.chip_ms = 0.0
        self.decode_ms = 0.0
        self.prefill_ms = 0.0
        self.hbm_gib_s = 0.0
        self.tokens = 0
        self.prompt_tokens = 0
        self.cache_savings_ms = 0.0
        self.cache_saved_tokens = 0
        self.requests = 0

    def row(self) -> dict:
        return {"chip_ms": round(self.chip_ms, 3),
                "decode_ms": round(self.decode_ms, 3),
                "prefill_ms": round(self.prefill_ms, 3),
                "hbm_gib_s": round(self.hbm_gib_s, 9),
                "tokens": self.tokens,
                "prompt_tokens": self.prompt_tokens,
                "cache_savings_ms": round(self.cache_savings_ms, 3),
                "cache_saved_tokens": self.cache_saved_tokens,
                "requests": self.requests}


class CostLedger:
    """Per-replica cost accounting. The scheduler charges spans into it
    during every tick; ``end_tick`` closes the tick's books — HBM
    residency for the occupants, the overhead residual, the wall total.
    All charges use the scheduler's injected clock, so rigged tests can
    engineer exact splits."""

    def __init__(self, config=None, clock=None, slot_bytes: int = 0):
        self.enabled = bool(getattr(config, "enabled", True))
        self.clock = clock or time.monotonic
        self.ema_alpha = float(getattr(config, "ema_alpha", 0.25) or 0.25)
        self.track_hbm = bool(getattr(config, "hbm", True))
        self._tenant_cap = int(getattr(config, "max_tracked", 64) or 64)
        #: bytes one slot pins in HBM (pool + draft pool share, int8-
        #: aware) — set by the scheduler once the pools exist
        self.slot_bytes = int(slot_bytes)
        self._tenants: Dict[str, _TenantCost] = {}
        #: EMA of observed per-token prefill cost (ms/token): what a
        #: radix hit's avoided cost is priced at. None until the first
        #: real prefill — a hit before any paid prefill saves "0" (there
        #: is nothing honest to price it with).
        self.prefill_ms_per_token: Optional[float] = None
        self._max_ms_per_token = 0.0
        self.serving_wall_s = 0.0
        self.overhead_s = 0.0
        self.idle_ticks = 0
        self.ticks = 0
        self.spec_draft_ms = 0.0
        self.spec_verify_ms = 0.0
        self._tick_attr_s = 0.0     # seconds attributed this tick

    # ------------------------------------------------------------- records
    def record_for(self, req) -> CostRecord:
        """The request's CostRecord, minted on first touch and attached
        to its TraceContext (the carrier that survives handoff and
        failover). Requests without a trace keep the record on the
        Request object itself — replica-local, but never lost."""
        ctx = getattr(req, "trace", None)
        carrier = ctx if ctx is not None else req
        rec = getattr(carrier, "cost", None)
        if rec is None:
            rec = CostRecord(request_id=getattr(req, "request_id", None),
                             tenant=getattr(req, "tenant", None)
                             or "default",
                             prompt_tokens=int(
                                 getattr(req.prompt, "size", 0)))
            self._tenant(rec.tenant).requests += 1
            self._tenant(rec.tenant).prompt_tokens += rec.prompt_tokens
            carrier.cost = rec
        if ctx is not None:
            rec.attempt = int(getattr(ctx, "replays", 0) or 0)
        return rec

    def _tenant(self, name: str) -> _TenantCost:
        name = name or "default"
        t = self._tenants.get(name)
        if t is None:
            if len(self._tenants) >= self._tenant_cap and \
                    name != "__other__":
                return self._tenant("__other__")
            t = self._tenants[name] = _TenantCost()
        return t

    # ------------------------------------------------------------- charging
    def charge_decode(self, dt_s: float,
                      weighted: List[Tuple[CostRecord, int]],
                      tokens: Optional[List[int]] = None):
        """Split one decode tick's wall over its records, weighted by
        tokens emitted (equal on the non-speculative path, where every
        weight is 1). A pass over blocks (serving/scheduler.py
        ``_decode_blocks``) weighs a record by the columns its row advanced
        and hands in beside them the ``tokens`` its block delivered (none
        from most passes): those are what the records then count."""
        total_w = sum(max(0, w) for _r, w in weighted)
        if total_w <= 0 or dt_s <= 0:
            return
        self._tick_attr_s += dt_s
        for (rec, w), n in zip(weighted, tokens or [w for _r, w in weighted]):
            if w <= 0:
                continue
            ms = dt_s * 1e3 * w / total_w
            rec.charge(ms, decode=True)
            rec.tokens += n
            t = self._tenant(rec.tenant)
            t.decode_ms += ms
            t.chip_ms += ms
            t.tokens += n

    def charge_spec(self, dt_s: float, draft_s: float, verify_s: float,
                    weighted: List[Tuple[CostRecord, int]]):
        """One speculative tick: the whole tick wall (draft + verify +
        bookkeeping) splits over the emitted tokens, so accepted drafts
        credit their request and the draft/verify overhead lands
        pro-rata. The aggregate draft/verify walls are kept for the
        statusz table."""
        self.spec_draft_ms += draft_s * 1e3
        self.spec_verify_ms += verify_s * 1e3
        self.charge_decode(dt_s, weighted)

    def charge_prefill(self, rec: CostRecord, dt_s: float, tokens: int,
                       *, update_rate: bool = True):
        """Charge one prefill span (inline, suffix, chunk, lane-copy, or
        handoff insert) whole to its owning request. ``update_rate``
        feeds the per-token EMA that prices radix savings — lane copies
        and handoff inserts don't (their per-token cost is not prefill
        compute)."""
        if dt_s <= 0:
            return
        ms = dt_s * 1e3
        self._tick_attr_s += dt_s
        rec.charge(ms, decode=False)
        t = self._tenant(rec.tenant)
        t.prefill_ms += ms
        t.chip_ms += ms
        if update_rate and tokens > 0:
            rate = ms / tokens
            if self.prefill_ms_per_token is None:
                self.prefill_ms_per_token = rate
            else:
                self.prefill_ms_per_token += self.ema_alpha * (
                    rate - self.prefill_ms_per_token)
            self._max_ms_per_token = max(self._max_ms_per_token, rate)

    def note_cache_savings(self, rec: CostRecord, reused_tokens: int):
        """A radix hit avoided prefilling ``reused_tokens`` — record the
        avoided cost at the EMA per-token rate. Priced, never charged:
        savings are what the fleet did not pay."""
        if reused_tokens <= 0 or self.prefill_ms_per_token is None:
            return
        saved = reused_tokens * self.prefill_ms_per_token
        rec.cache_savings_ms += saved
        rec.cache_saved_tokens += reused_tokens
        t = self._tenant(rec.tenant)
        t.cache_savings_ms += saved
        t.cache_saved_tokens += reused_tokens

    # ----------------------------------------------------------------- tick
    def end_tick(self, wall_s: float, occupants: List[CostRecord]):
        """Close one tick: HBM residency for every occupied slot
        (footprint x tick wall), the overhead residual (wall minus
        attributed), and the wall total — conservation by construction."""
        if wall_s < 0:
            wall_s = 0.0
        self.ticks += 1
        self.serving_wall_s += wall_s
        self.overhead_s += max(0.0, wall_s - self._tick_attr_s)
        self._tick_attr_s = 0.0
        if not occupants:
            self.idle_ticks += 1
        elif self.track_hbm and self.slot_bytes > 0:
            gib_s = self.slot_bytes * wall_s / _GIB
            for rec in occupants:
                rec.hbm_gib_s += gib_s
                self._tenant(rec.tenant).hbm_gib_s += gib_s

    # -------------------------------------------------------------- folding
    def tenant_totals(self) -> Dict[str, dict]:
        return {name: t.row() for name, t in self._tenants.items()}

    def snapshot(self) -> dict:
        attributed_ms = sum(t.chip_ms for t in self._tenants.values())
        return {"enabled": self.enabled,
                "serving_wall_s": round(self.serving_wall_s, 6),
                "overhead_s": round(self.overhead_s, 6),
                "attributed_ms": round(attributed_ms, 3),
                "ticks": self.ticks,
                "idle_ticks": self.idle_ticks,
                "slot_bytes": self.slot_bytes,
                "prefill_ms_per_token":
                    None if self.prefill_ms_per_token is None
                    else round(self.prefill_ms_per_token, 6),
                "spec_draft_ms": round(self.spec_draft_ms, 3),
                "spec_verify_ms": round(self.spec_verify_ms, 3),
                "tenants": self.tenant_totals()}

    def reset(self):
        """Zero the fold state (tenant totals, wall, overhead) — the
        soak harness resets after warmup so the scorecard's conservation
        window matches the goodput window. Per-request records are
        untouched; in-flight requests re-register on their next charge."""
        self._tenants = {}
        self.serving_wall_s = 0.0
        self.overhead_s = 0.0
        self.idle_ticks = 0
        self.ticks = 0
        self.spec_draft_ms = 0.0
        self.spec_verify_ms = 0.0
        self._tick_attr_s = 0.0


def merge_cost_totals(into: Dict[str, Any], snap: dict):
    """Fold one replica's ``CostLedger.snapshot()`` into a fleet
    accumulator (the router's cost_summary, which also folds snapshots
    retained from failed/drained replicas)."""
    into["serving_wall_s"] = into.get("serving_wall_s", 0.0) + \
        float(snap.get("serving_wall_s", 0.0))
    into["overhead_s"] = into.get("overhead_s", 0.0) + \
        float(snap.get("overhead_s", 0.0))
    into["ticks"] = into.get("ticks", 0) + int(snap.get("ticks", 0))
    into["idle_ticks"] = into.get("idle_ticks", 0) + \
        int(snap.get("idle_ticks", 0))
    tenants = into.setdefault("tenants", {})
    for name, row in (snap.get("tenants") or {}).items():
        acc = tenants.setdefault(name, {m: 0 for m in TENANT_COST_METRICS})
        for metric in TENANT_COST_METRICS:
            acc[metric] = acc.get(metric, 0) + row.get(metric, 0)


def capacity_report(costs: dict, *, target_tokens_per_s: float = 0.0,
                    replicas: int = 0) -> dict:
    """Turn a cost fold into the capacity answer: tokens per chip-second
    per tenant, the fleet-effective rate (overhead included), and —
    given a target aggregate token rate for the SAME traffic mix — the
    projected replica count. ``replicas`` scales per-replica serving
    wall out of the fold's total chip-seconds; 0 derives nothing."""
    import math
    tenants = costs.get("tenants") or {}
    wall_s = float(costs.get("serving_wall_s", 0.0))
    total_tokens = sum(int(r.get("tokens", 0)) for r in tenants.values())
    rows = {}
    for name, r in sorted(tenants.items()):
        chip_s = float(r.get("chip_ms", 0.0)) / 1e3
        toks = int(r.get("tokens", 0))
        rows[name] = {
            "tokens": toks,
            "chip_s": round(chip_s, 6),
            "tokens_per_chip_s":
                round(toks / chip_s, 3) if chip_s > 0 else None,
            "hbm_gib_s": round(float(r.get("hbm_gib_s", 0.0)), 6),
            "cache_savings_ms":
                round(float(r.get("cache_savings_ms", 0.0)), 3),
            "cost_share": round(chip_s / wall_s, 4) if wall_s > 0 else None,
        }
    effective = total_tokens / wall_s if wall_s > 0 else 0.0
    out = {"tenants": rows,
           "total_tokens": total_tokens,
           "serving_wall_s": round(wall_s, 6),
           "overhead_s": round(float(costs.get("overhead_s", 0.0)), 6),
           "effective_tokens_per_chip_s": round(effective, 3)}
    if target_tokens_per_s > 0 and effective > 0:
        # chip-seconds demanded per wall second at the same mix; each
        # replica supplies ~1 chip-second per second of serving wall
        chips = target_tokens_per_s / effective
        out["target_tokens_per_s"] = target_tokens_per_s
        out["projected_replicas"] = max(1, math.ceil(chips))
        if replicas > 0:
            out["current_replicas"] = replicas
    return out
