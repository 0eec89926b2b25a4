"""Continuous-batching request scheduler.

Orca-style iteration-level scheduling on static XLA shapes: each ``tick()``
(1) expires requests past their deadline, (2) admits queued requests into
free slots — prefill writes the prompt's K/V into the slot's cache lane and
samples the request's FIRST token (so TTFT is one prefill away from
admission), and (3) advances every in-flight request by one token of ONE
fused decode step over all slots — the step dispatched a tick earlier,
read only after the next one has been dispatched behind it, fed on the
device (``_decode``: a pipeline one step deep, so the device does not wait
for the host between steps). Requests retire on EOS or max-tokens and their
slot returns to the free list for the next admission — no compiled shape
ever changes.
"""

import dataclasses
import enum
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

import numpy as np

from ..telemetry.trace import get_tracer
from ..utils.logging import logger
from .kv_slots import SlotPool
from .metrics import ServingMetrics


class QueueFull(RuntimeError):
    """Backpressure: the bounded admission queue is at capacity."""


class RateLimited(QueueFull):
    """429-style backpressure: the tenant's token bucket is empty. A
    subclass of QueueFull so existing retry-with-backoff handling works
    unchanged; ``tenant`` and ``retry_after_s`` let an API front-end
    surface a proper 429 with a Retry-After header."""

    def __init__(self, message: str, tenant: str = "default",
                 retry_after_s: float = 1.0):
        super().__init__(message)
        self.tenant = tenant
        self.retry_after_s = retry_after_s
        self.status = 429


class RequestState(enum.Enum):
    QUEUED = "queued"
    #: chunked prefill in progress: the request holds its slot across
    #: ticks while its prompt's K/V lands chunk by chunk, interleaved
    #: with everyone else's decode ticks (chunked_prefill config block)
    PREFILLING = "prefilling"
    RUNNING = "running"
    FINISHED = "finished"
    TIMEOUT = "timeout"
    CANCELLED = "cancelled"


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling controls: greedy (temperature 0, the
    default), or temperature / top-k / top-p sampling with a
    deterministic per-request ``seed`` — every sampled token's PRNG key
    derives only from ``(seed, cache position)``, so the stream is
    reproducible across ticks, slots, replicas, and failover replays
    (the router's delivered-position dedup depends on it). Beam search
    stays on the offline generate() path."""
    temperature: float = 0.0
    top_k: int = 0                         # 0 = off
    top_p: float = 1.0                     # 1.0 = off
    seed: int = 0
    max_new_tokens: Optional[int] = None   # None -> config default
    eos_token_id: Optional[int] = None
    timeout_s: Optional[float] = None      # None -> config default
    #: the tenant this request bills to: selects its DRR admission
    #: queue and weight, its router rate-limit bucket, and the
    #: dstpu_tenant_* SLO window its latencies land in. Carried on the
    #: KVHandoff frame and the TraceContext header, so disaggregation
    #: and failover never lose the billing identity.
    tenant: str = "default"

    def validate(self):
        if not self.tenant or not isinstance(self.tenant, str) or \
                "/" in self.tenant:
            raise ValueError(
                f"tenant must be a non-empty string without '/' "
                f"(it names a gauge tag segment), got {self.tenant!r}")
        if self.max_new_tokens is not None and self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.temperature < 0.0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.temperature <= 0.0 and (self.top_k or self.top_p < 1.0):
            raise ValueError(
                "top_k/top_p require temperature > 0 (temperature<=0 means "
                "greedy decoding, which would silently ignore them)")
        if self.timeout_s is not None and self.timeout_s <= 0:
            raise ValueError("timeout_s must be > 0")

    def to_dict(self) -> dict:
        """The replay-relevant fields — carried in the TraceContext
        header so a postmortem (or a cross-process survivor) can name
        the exact sampling law of the stream it is deduplicating."""
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p, "seed": self.seed,
                "tenant": self.tenant}


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray                     # int32 [T]
    sampling: SamplingParams
    max_new_tokens: int
    state: RequestState = RequestState.QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    on_token: Optional[Callable] = None    # on_token(request, token:int)
    submit_time: float = 0.0
    deadline: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    #: distributed trace context (telemetry/disttrace.py) — minted by the
    #: fleet router (or lazily at enqueue) and carried through every
    #: replica boundary this request crosses
    trace: Optional[object] = None
    #: chunked prefill progress: prompt tokens whose K/V is already in
    #: the slot lane (columns [0, prefill_pos) valid). Restarts from the
    #: reuse offset on a failover replay — progress is replica-local.
    prefill_pos: int = 0
    #: True once the request left the queue (its request/decode span is
    #: open) — a PREFILLING request that expires must close that span,
    #: not the queued one
    prefill_started: bool = False
    #: tick number of this request's last chunk (a freshly admitted
    #: chunked request must not take a second chunk in the same tick)
    prefill_tick: int = -1
    #: ``perf_counter_ns`` at enqueue: where this request's
    #: ``serve/queue_wait`` phase record starts (never the injectable clock)
    enqueue_ns: int = 0
    #: a family that generates by diffusion over blocks: beside each of
    #: ``tokens``, the pass of its block at which it was fixed
    fixed_pass: List[int] = dataclasses.field(default_factory=list)

    @property
    def tenant(self) -> str:
        return getattr(self.sampling, "tenant", None) or "default"

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.TIMEOUT,
                              RequestState.CANCELLED)

    @property
    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens."""
        return np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])


@dataclasses.dataclass
class _Flight:
    """A decode step that was dispatched and is not read yet."""
    out: object                 # its output, on the device
    rows: list                  # (slot, request) it advances
    positions: np.ndarray       # [S] the column each row was fed at
    sampled: bool               # some row's temperature is on
    pipelined: bool             # sent while another step was in flight
    #: a pass over blocks: {slot: (pass of its block, its last unmasking
    #: pass, its writing pass)} (``SlotPool.dispatch_block_arrays``)
    passes: Optional[dict] = None


class TenantQueues:
    """Admission queue with a tenant dimension: per-tenant FIFOs served
    by deficit round-robin (DRR), replacing the single global FIFO.

    With tenancy disabled (or only one tenant ever enqueues) this is
    byte-for-byte the old deque: strict arrival order. With
    ``tenants.enabled``, each tenant gets its own FIFO and ``popleft()``
    runs DRR over the backlogged tenants — every round-robin visit adds
    ``weight(tenant) * quantum_tokens`` to the tenant's deficit, and a
    request pops only when the deficit covers its admission cost (its
    prompt length, the prefill work the scheduler is about to buy it).
    Over any backlogged interval, admitted prefill tokens converge to the
    weight ratios — a whale tenant spraying 4k-token prompts drains its
    deficit 256x faster than a 16-token tenant and cannot starve it.

    The deque surface the rest of the stack uses is preserved:
    ``append`` / ``popleft`` / ``remove`` / ``len`` / ``iter`` / truth.
    """

    def __init__(self, config=None):
        self._cfg = config
        self.enabled = bool(getattr(config, "enabled", False))
        # tenant -> FIFO; insertion order gives a stable RR order
        self._queues: "OrderedDict[str, deque]" = OrderedDict()
        self._deficit: Dict[str, float] = {}
        self._rr: List[str] = []           # backlogged tenants, RR order
        self._rr_idx = 0
        self._fifo: "deque[Request]" = deque()   # disabled-mode fast path
        self._n = 0

    @staticmethod
    def _tenant_of(req) -> str:
        return getattr(req, "tenant", None) or "default"

    @staticmethod
    def _cost(req) -> float:
        """Admission cost in DRR currency: the prefill work this request
        buys on pop (its prompt tokens)."""
        return float(max(1, int(req.prompt.size)))

    def _quantum(self, tenant: str) -> float:
        cfg = self._cfg
        return cfg.weight_of(tenant) * float(cfg.quantum_tokens)

    # -------------------------------------------------------------- deque API
    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return self._n > 0

    def __iter__(self):
        if not self.enabled:
            return iter(self._fifo)
        import itertools
        return itertools.chain.from_iterable(self._queues.values())

    def append(self, req):
        self._n += 1
        if not self.enabled:
            self._fifo.append(req)
            return
        tenant = self._tenant_of(req)
        dq = self._queues.get(tenant)
        if dq is None:
            dq = self._queues[tenant] = deque()
        if not dq and tenant not in self._rr:
            self._rr.append(tenant)
        dq.append(req)

    def remove(self, req):
        """deque semantics: raises ValueError when absent."""
        if not self.enabled:
            self._fifo.remove(req)       # ValueError propagates
            self._n -= 1
            return
        dq = self._queues.get(self._tenant_of(req))
        if dq is None:
            raise ValueError("request not in queue")
        dq.remove(req)                   # ValueError propagates
        self._n -= 1
        if not dq:
            self._retire(self._tenant_of(req))

    def _retire(self, tenant: str):
        """Tenant went idle: drop it from the rotation and zero its
        deficit (classic DRR — an idle tenant must not bank credit)."""
        self._deficit[tenant] = 0.0
        if tenant in self._rr:
            idx = self._rr.index(tenant)
            self._rr.remove(tenant)
            if idx < self._rr_idx:
                self._rr_idx -= 1
            if self._rr:
                self._rr_idx %= len(self._rr)
            else:
                self._rr_idx = 0

    def popleft(self):
        """DRR pop: stays on the current tenant while its deficit covers
        the head request, else tops the next tenant up by its quantum and
        moves on. Terminates: every full rotation adds a positive quantum
        to each backlogged tenant and costs are bounded by the prompt
        length cap."""
        if self._n == 0:
            raise IndexError("pop from an empty TenantQueues")
        self._n -= 1
        if not self.enabled:
            return self._fifo.popleft()
        while True:
            tenant = self._rr[self._rr_idx % len(self._rr)]
            dq = self._queues[tenant]
            cost = self._cost(dq[0])
            if self._deficit.get(tenant, 0.0) >= cost:
                req = dq.popleft()
                self._deficit[tenant] -= cost
                if not dq:
                    self._retire(tenant)
                return req
            self._deficit[tenant] = \
                self._deficit.get(tenant, 0.0) + self._quantum(tenant)
            self._rr_idx = (self._rr_idx + 1) % len(self._rr)

    # ------------------------------------------------------------ inspection
    def depths(self) -> Dict[str, int]:
        """Per-tenant queue depth (statusz / metrics)."""
        if not self.enabled:
            return {"default": len(self._fifo)} if self._fifo else {}
        return {t: len(dq) for t, dq in self._queues.items() if dq}


class ContinuousBatchingScheduler:
    """Admission queue + slot pool + fused decode tick.

    Three roles share this loop (config.role): ``unified`` admits
    prompts, prefills, and decodes; ``prefill`` admits prompts, prefills,
    then extracts the slot lane into a KVHandoff for ``handoff_sink``
    instead of binding for decode; ``decode`` additionally drains a
    handoff queue — inserting received lanes into its own pool — and
    runs the token loop. With ``prefix_cache.enabled``, finished slots
    are donated to a radix cache and admissions that share a cached
    prefix take the lane-copy + suffix-prefill fast path.
    """

    def __init__(self, engine, config, metrics: ServingMetrics = None,
                 clock: Callable[[], float] = time.monotonic,
                 seed: int = 0,  # retained for API compat; sampling keys
                                 # now derive from per-REQUEST seeds only
                 handoff_sink: Optional[Callable] = None,
                 replica_name: Optional[str] = None):
        self.engine = engine
        self.config = config
        self.clock = clock
        self.role = getattr(config, "role", "unified")
        # lane identity for the merged fleet timeline: every span this
        # scheduler emits carries it, so the aggregator can partition the
        # shared span ring into per-replica Perfetto process lanes
        self.replica_name = replica_name or "serving"
        self.handoff_sink = handoff_sink
        self.metrics = metrics or ServingMetrics()
        quantize = bool(getattr(getattr(config, "kv_quant", None),
                                "enabled", False))
        module = getattr(engine, "module", None)
        # a family that generates by diffusion over blocks: the positions a
        # pass advances a slot by, and how many of them a pass fixes
        self.block = int(getattr(module, "block_length", 1))
        steps = getattr(getattr(config, "block_diffusion", None),
                        "denoising_steps", None)
        self.block_fix = self.block // (steps or self.block)
        self.pool = SlotPool(engine, config.num_slots, config.max_model_len,
                             quantize=quantize, block=self.block)
        # a model with window layers: the columns a ring keeps of a lane,
        # read off the pool's own leaf (``serve/kv_live``); 0 where every
        # attending layer keeps them all
        rings = getattr(module, "window_rings", ())
        leaves = getattr(self.pool.cache, "q", self.pool.cache)
        self._ring_window = int(leaves[rings[0]].shape[2]) if rings else 0
        # a pool whose lanes are not K and V of every layer (rings beside
        # them; a latent row a token) says what of it is live a decode tick
        self._kv_live = bool(rings or getattr(module, "latent_cache", ()))
        # the columns a block of the decode-attention kernel fetches, where
        # the decode program takes it; None where the step reads the whole
        # pool (``serve/kv_read``)
        ask = getattr(engine, "decode_kernel_block", None)
        self._kv_read_block = ask(config.num_slots, config.max_model_len) \
            if ask is not None else None
        # whether a whole prefill of so many tokens attends in the packed
        # flash kernel (``serve/kernel_prefills``)
        self._prefill_kernel = getattr(engine, "prefill_kernel", None)
        #: admission queue: per-tenant FIFOs + deficit round-robin when
        #: the tenants block is on, a plain FIFO otherwise (deque API)
        self.queue = TenantQueues(getattr(config, "tenants", None))
        #: (KVHandoff, Request) pairs awaiting a slot (decode/unified role)
        self.handoff_queue: "deque" = deque()
        #: chunked prefill in flight: slot -> PREFILLING Request, in
        #: admission order — each holds its slot across ticks while its
        #: prompt lands chunk by chunk (chunked_prefill config block)
        self.prefilling: "OrderedDict[int, Request]" = OrderedDict()
        self.chunked = getattr(config, "chunked_prefill", None)
        if not getattr(self.chunked, "enabled", False):
            self.chunked = None
        #: ticks between unconditional queue deadline sweeps — queued
        #: expiry is otherwise lazy (at pop time) plus EVENT-DRIVEN: the
        #: scheduler tracks the minimum queued deadline (O(1) per tick)
        #: and sweeps the moment the clock passes it, so deep per-tenant
        #: queues don't make every tick linear in total queued requests
        #: while timeouts still fire the tick they expire
        self.expire_sweep_interval = 64
        self._queue_min_deadline: Optional[float] = None
        self.prefix_cache = None
        pc_cfg = getattr(config, "prefix_cache", None)
        if getattr(pc_cfg, "enabled", False):
            from .fleet.prefix_cache import RadixPrefixCache
            self.prefix_cache = RadixPrefixCache(pc_cfg)
        # speculative decoding (inference/speculative.py): a draft model
        # plus a draft slot pool in lockstep with the target pool. Prefill
        # replicas never decode, so they skip the draft entirely.
        self.spec = None
        self.draft = None
        self.draft_cache = None
        spec_cfg = getattr(config, "speculative", None)
        if getattr(spec_cfg, "enabled", False) and self.role != "prefill":
            self.spec = spec_cfg
            self.draft = engine.init_draft(spec_cfg.draft)
            self.draft_cache = engine.init_draft_pool(
                self.draft, config.num_slots, config.max_model_len)
        # cost plane (telemetry/costplane.py): per-request / per-tenant
        # chip-second + HBM attribution. None when disabled — every hook
        # below is a single ``is None`` test, nothing allocated.
        self.cost = None
        cost_cfg = getattr(config, "cost", None)
        if getattr(cost_cfg, "enabled", False):
            from ..telemetry.costplane import CostLedger, tree_nbytes
            self.cost = CostLedger(cost_cfg, clock=clock)
            slot_bytes = self.pool.slot_nbytes()
            if self.draft_cache is not None:
                # the draft pool is per-slot KV state too — same residency
                slot_bytes += tree_nbytes(self.draft_cache) \
                    // max(1, config.num_slots)
            self.cost.slot_bytes = slot_bytes
        self._tick_no = 0
        #: the decode step dispatched and not read yet (``_decode``)
        self._flight: Optional[_Flight] = None
        # per-request async spans (queue → prefill → decode → complete)
        # land in the same trace as train/comm spans
        self.tracer = get_tracer()

    # -------------------------------------------------------------- enqueue
    def enqueue(self, request: Request):
        """Admission control: bounded queue -> QueueFull backpressure."""
        if len(self.queue) >= self.config.max_queue:
            self.metrics.record_reject()
            raise QueueFull(
                f"serving queue at capacity ({self.config.max_queue}); "
                f"retry with backoff")
        now = self.clock()
        request.submit_time = now
        request.enqueue_ns = time.perf_counter_ns()
        timeout = (request.sampling.timeout_s
                   if request.sampling.timeout_s is not None
                   else self.config.request_timeout_s)
        if timeout is not None:
            request.deadline = now + timeout
            if self._queue_min_deadline is None or \
                    request.deadline < self._queue_min_deadline:
                self._queue_min_deadline = request.deadline
        self.queue.append(request)
        if request.trace is None:
            from ..telemetry.disttrace import TraceContext
            request.trace = TraceContext.mint(origin=self.replica_name,
                                              tenant=request.tenant)
        ctx = request.trace
        if getattr(ctx, "tenant", None) is None:
            ctx.tenant = request.tenant
        if getattr(ctx, "sampling", None) is None:
            # the replay law rides the trace: a survivor (or a human in a
            # postmortem) can see the exact seed/temperature the dedup'd
            # stream was generated under
            ctx.sampling = request.sampling.to_dict()
        ctx.bind_span(request.request_id)
        ctx.hop(self.replica_name)
        ctx.mark("queued")
        tr = self.tracer
        tr.async_begin("request", request.request_id, cat="serving",
                       args={"prompt_len": int(request.prompt.size),
                             "max_new_tokens": request.max_new_tokens,
                             "replica": self.replica_name,
                             **ctx.span_args()})
        tr.async_begin("request/queued", request.request_id, cat="serving",
                       args={"replica": self.replica_name,
                             "trace_id": ctx.trace_id})
        self.metrics.record_submit(tenant=request.tenant,
                                   prompt_tokens=int(request.prompt.size))

    def enqueue_handoff(self, handoff, request: Request):
        """Admission control for the handoff path (decode role): the
        handoff queue shares ``max_queue`` with the prompt queue."""
        if len(self.handoff_queue) + len(self.queue) >= self.config.max_queue:
            self.metrics.record_reject()
            raise QueueFull(
                f"serving handoff queue at capacity "
                f"({self.config.max_queue}); retry with backoff")
        self.handoff_queue.append((handoff, request))
        ctx = request.trace
        if ctx is not None:
            ctx.hop(self.replica_name)
            ctx.mark("handoff_queued")
        self.tracer.async_begin(
            "request/handoff_queued", request.request_id, cat="serving",
            args={"kv_len": int(handoff.kv_len),
                  "source": handoff.source,
                  "replica": self.replica_name,
                  **(ctx.span_args() if ctx is not None else {})})

    # ----------------------------------------------------------------- tick
    def tick(self) -> int:
        """One scheduling iteration. Returns the number of requests still
        in flight (queued + prefilling + running) after the tick. With
        chunked prefill, each tick's prefill work is budgeted in units
        of ``chunk_tokens``: admissions (DRR-ordered, so a small
        tenant's short prompt goes first) spend the budget, then the
        OLDEST in-flight chunked prefill always advances one chunk —
        steady state under a long prompt is exactly one chunk + decode
        per tick, so a 4k-token prompt costs ~16 ticks of bounded work
        instead of one unbounded one, and every active slot still
        decodes every tick. Worst case (an admission landing the same
        tick as a chunk) is a small constant multiple of chunk_tokens,
        never the prompt length."""
        self._tick_no += 1
        now = self.clock()
        tr = self.tracer
        with tr.phase("serve/admit", 0, len(self.queue)) as admit:
            self._expire(now)
            self._admit_handoffs(now)
            budget = (self.chunked.chunk_tokens if self.chunked is not None
                      else None)
            budget, admit.a = self._admit(now, budget)
        self._advance_prefills(now, budget)
        self._decode()
        with tr.phase("serve/bookkeeping"):
            self.metrics.record_tick(len(self.queue), self.pool.utilization)
            if self.prefix_cache is not None:
                self.metrics.record_prefix_cache(self.prefix_cache)
            if self.cost is not None:
                # close the tick's books: HBM residency for every occupied
                # slot (decoding or mid-chunked-prefill), then the overhead
                # residual — tick wall minus everything attributed above —
                # so per-request costs + overhead sum to serving wall-clock
                # by construction
                occupants = [self.cost.record_for(self.pool.requests[s])
                             for s in self.pool.active_slots]
                occupants += [self.cost.record_for(r)
                              for r in self.prefilling.values()]
                self.cost.end_tick(self.clock() - now, occupants)
        return (len(self.queue) + len(self.handoff_queue) +
                len(self.pool.active_slots) + len(self.prefilling))

    def _alloc_slot(self) -> Optional[int]:
        """Claim a slot, evicting the LRU prefix-cache entry when the
        free list is dry — live admissions always outrank cached
        prefixes (pinned entries excepted)."""
        slot = self.pool.alloc()
        if slot is None and self.prefix_cache is not None:
            victim = self.prefix_cache.evict_lru()
            if victim is not None:
                self.pool.free(victim)
                slot = self.pool.alloc()
        return slot

    def _release_slot(self, slot: int, req: Request,
                      donate_seq=None):
        """Retire a slot: donate its lane to the prefix cache when it
        holds reusable K/V — a FINISHED request's full sequence, or the
        prompt a prefill-role scheduler just handed off — else return it
        to the free list."""
        cache = self.prefix_cache
        kv_len = int(self.pool.lengths[slot])
        if cache is not None and donate_seq is None and \
                req.state is RequestState.FINISHED:
            donate_seq = req.output_ids[:kv_len]
        if cache is not None and donate_seq is not None:
            accepted, evicted = cache.donate(slot, donate_seq, kv_len)
            if evicted is not None:
                self.pool.free(evicted)
            if accepted:
                self.pool.retire_to_cache(slot)
                return
        self.pool.free(slot)

    def _expire(self, now: float):
        """Deadline enforcement. Running and prefilling requests are
        checked every tick (O(slots)). The QUEUE is no longer rescanned
        every tick: expiry there is lazy at pop time (``_pop_live``)
        plus a sweep that runs only when the tracked minimum queued
        deadline has actually passed (event-driven — timeouts still
        fire the tick they expire) or on the low-frequency
        ``expire_sweep_interval`` backstop. A tick with nothing expired
        costs O(1) in queue length; the sweep itself recomputes the
        minimum, so a stale tracker only ever costs one extra scan."""
        for slot in self.pool.active_slots:
            req = self.pool.requests[slot]
            if req.deadline is not None and now > req.deadline:
                self._finish(req, RequestState.TIMEOUT, now)
                self.pool.free(slot)
        for slot in list(self.prefilling):
            req = self.prefilling[slot]
            if req.deadline is not None and now > req.deadline:
                del self.prefilling[slot]
                self._finish(req, RequestState.TIMEOUT, now)
                self.pool.free(slot)
        due = (self._queue_min_deadline is not None and
               now > self._queue_min_deadline)
        if not due and self._tick_no % self.expire_sweep_interval:
            return
        expired = []
        new_min = None
        for req in self.queue:
            if req.deadline is None:
                continue
            if now > req.deadline:
                expired.append(req)
            elif new_min is None or req.deadline < new_min:
                new_min = req.deadline
        self._queue_min_deadline = new_min
        for req in expired:
            try:
                self.queue.remove(req)
            except ValueError:
                continue
            self._finish(req, RequestState.TIMEOUT, now)

    def _pop_live(self, now: float) -> Optional[Request]:
        """Pop the next admissible request, finishing expired ones on
        the way out (the lazy half of deadline enforcement)."""
        while self.queue:
            req = self.queue.popleft()
            if req.deadline is not None and now > req.deadline:
                self._finish(req, RequestState.TIMEOUT, now)
                continue
            return req
        return None

    def _admit_handoffs(self, now: float):
        """Insert received KV lanes into free slots (decode/unified
        role): no prefill — the prompt's K/V arrives precomputed, only
        the lane insert and the bind happen here."""
        tr = self.tracer
        while self.handoff_queue:
            slot = self._alloc_slot()
            if slot is None:
                return
            handoff, req = self.handoff_queue.popleft()
            ctx = req.trace
            targs = ctx.span_args() if ctx is not None else {}
            tr.async_end("request/handoff_queued", req.request_id,
                         cat="serving")
            tr.async_begin("request/decode", req.request_id, cat="serving",
                           args={"slot": slot, "handoff": True,
                                 "replica": self.replica_name, **targs})
            t0 = self.clock()
            with tr.span("kv_handoff_in", cat="serving",
                         args={"request_id": req.request_id, "slot": slot,
                               "kv_len": int(handoff.kv_len),
                               "bytes": handoff.nbytes(),
                               "source": handoff.source,
                               "replica": self.replica_name, **targs}):
                self.pool.cache = self.engine.slot_insert_lane(
                    self.pool.cache, slot, handoff.lane)
            if self.cost is not None:
                # the lane insert is admission work owned by this request
                # (its per-token cost is transport, not prefill compute,
                # so it never feeds the savings-pricing EMA)
                self.cost.charge_prefill(
                    self.cost.record_for(req), self.clock() - t0,
                    int(handoff.kv_len), update_rate=False)
            if ctx is not None:
                ctx.mark("handoff_inserted")
            req.state = RequestState.RUNNING
            self.metrics.record_handoff_in()
            if self._should_finish(req, handoff.first_token):
                self._finish(req, RequestState.FINISHED, self.clock())
                self._release_slot(slot, req)
            else:
                self.pool.bind(slot, req, int(handoff.kv_len),
                               int(handoff.first_token), req.sampling)
                if self.spec is not None:
                    # the draft lane has no handoff: rebuild it from the
                    # prompt (the draft is the cheap side of the trade)
                    self.draft_cache = self.engine.draft_prefill(
                        self.draft, self.draft_cache, slot, req.prompt)

    def _advance_prefills(self, now: float, budget):
        """Advance in-flight chunked prefills, oldest first. The HEAD
        request always moves one chunk — a flood of small admissions can
        spend the whole budget, but it cannot starve a prefill already
        holding a slot — and younger ones follow only while budget
        remains (one chunk per tick in the steady state). A request
        whose final chunk lands completes its admission (first token
        sampled, slot bound for decode / handed off) in the same
        tick."""
        if not self.prefilling:
            return
        first = True
        for slot in list(self.prefilling):
            if not first and (budget is None or budget <= 0):
                break
            req = self.prefilling.get(slot)
            if req is None or req.prefill_tick == self._tick_no:
                continue                 # admitted (and chunked) this tick
            spent = self._chunk_step(slot, req)
            if budget is not None:
                budget -= spent
            first = False

    def _admit(self, now: float, budget=None):
        """Move queued requests into free slots (bounded per tick so
        admission bursts cannot starve in-flight decode). A prompt whose
        unshared suffix fits ``chunk_tokens`` (or everything, when
        chunking is off) prefills inline exactly as before; a longer one
        starts a CHUNKED admission — first chunk now, the rest
        interleaved with decode ticks — so no single tick ever runs an
        unbounded prefill. With a prefix cache, a prompt sharing a
        cached prefix admits via lane-copy + suffix/chunk prefill: only
        the unshared tail runs through the stack. A ``prefill``-role
        scheduler extracts the completed lane into a KVHandoff for
        ``handoff_sink`` instead of binding for decode. ``budget``
        (chunked mode) is the tick's prefill-token budget; each
        admission spends its actual prefill work against it, and the
        remainder is returned for the in-flight chunk advance, beside the
        number of requests admitted.
        Admissions run BEFORE the chunk advance so a DRR-favored small
        tenant's TTFT is one tick, not one whale prefill."""
        admitted = 0
        tr = self.tracer
        while self.queue and admitted < self.config.max_prefills_per_tick \
                and (budget is None or budget > 0):
            slot = self._alloc_slot()
            if slot is None:
                return budget, admitted
            req = self._pop_live(now)
            if req is None:
                self.pool.free(slot)
                return budget, admitted
            tr.record_phase("serve/queue_wait", req.enqueue_ns,
                            time.perf_counter_ns(), req.request_id,
                            int(req.prompt.size))
            ctx = req.trace
            if ctx is not None:
                ctx.mark("admitted")
            tr.async_end("request/queued", req.request_id, cat="serving")
            tr.async_begin("request/decode", req.request_id, cat="serving",
                           args={"slot": slot,
                                 "replica": self.replica_name,
                                 **(ctx.span_args() if ctx is not None
                                    else {})})
            req.prefill_started = True
            hit = None
            if self.prefix_cache is not None:
                hit = self.prefix_cache.lookup(req.prompt)
            # chunk only the UNSHARED suffix: a prefix hit may shrink a
            # whale prompt below the chunking threshold entirely
            suffix = int(req.prompt.size) - \
                (hit.matched if hit is not None else 0)
            if self.block > 1:
                spent = self._admit_blocks(slot, req)
            elif self.chunked is not None and \
                    suffix > self.chunked.chunk_tokens:
                spent = self._start_chunked(slot, req, hit)
            else:
                first = self._prefill_into(slot, req, hit)
                spent = suffix
                self._complete_admission(slot, req, first)
            if budget is not None:
                budget -= spent
            admitted += 1
        return budget, admitted

    def _start_chunked(self, slot: int, req: Request, hit) -> int:
        """Begin a chunked admission: optional prefix-reuse lane copy,
        then the first fixed-size chunk. The request holds its slot in
        PREFILLING state; ``_advance_prefills`` moves it forward on
        later ticks. Returns the prefill tokens spent now."""
        tr = self.tracer
        t = int(req.prompt.size)
        start = 0
        if hit is not None:
            start = min(int(hit.matched), t - 1)
            if start > 0:
                try:
                    t0 = self.clock()
                    with tr.span("prefix_reuse", cat="serving",
                                 args={"request_id": req.request_id,
                                       "slot": slot, "src_slot": hit.slot,
                                       "matched": hit.matched,
                                       "reused": start, "chunked": True,
                                       "suffix": t - start,
                                       "replica": self.replica_name,
                                       **(req.trace.span_args()
                                          if req.trace is not None
                                          else {})}):
                        self.pool.cache = self.engine.slot_copy_lane(
                            self.pool.cache, hit.slot, slot)
                    if self.cost is not None:
                        rec = self.cost.record_for(req)
                        self.cost.charge_prefill(rec, self.clock() - t0,
                                                 start, update_rate=False)
                        self.cost.note_cache_savings(rec, start)
                finally:
                    self.prefix_cache.release(hit, used_tokens=start)
            else:
                self.prefix_cache.release(hit, used_tokens=0)
        req.state = RequestState.PREFILLING
        req.prefill_pos = start
        # dummy decode writes for an unbound slot land at column
        # lengths[slot] — keep it one past the valid prefix so the next
        # chunk (which starts exactly there) overwrites the garbage
        self.pool.set_length(slot, start)
        self.prefilling[slot] = req
        return self._chunk_step(slot, req)

    def _chunk_step(self, slot: int, req: Request) -> int:
        """One chunk of prefill for a PREFILLING request. Intermediate
        chunks write exactly ``chunk_tokens`` of K/V through the
        sampling-free ``slot_chunk_prefill`` program (one compiled
        flavor); the FINAL chunk runs the pow2 suffix-prefill machinery,
        sampling the first token at the same ``(seed, position)`` key a
        monolithic prefill would use — bitwise token parity — and
        completes the admission. Returns prefill tokens spent."""
        tr = self.tracer
        t = int(req.prompt.size)
        p = int(req.prefill_pos)
        rem = t - p
        ctx = req.trace
        targs = ctx.span_args() if ctx is not None else {}
        req.prefill_tick = self._tick_no
        if rem > self.chunked.chunk_tokens:
            chunk = self.chunked.chunk_tokens
            t0 = self.clock()
            with tr.span("prefill_chunk", cat="serving",
                         args={"request_id": req.request_id, "slot": slot,
                               "start": p, "chunk": chunk,
                               "remaining": rem - chunk,
                               "replica": self.replica_name, **targs}):
                self.pool.cache = self.engine.slot_chunk_prefill(
                    self.pool.cache, slot, req.prompt[p:p + chunk], p)
            if self.cost is not None:
                self.cost.charge_prefill(self.cost.record_for(req),
                                         self.clock() - t0, chunk)
            req.prefill_pos = p + chunk
            self.pool.set_length(slot, req.prefill_pos)
            if ctx is not None:
                ctx.mark("prefill_chunk")
            return chunk
        # final chunk: suffix-prefill from an offset whose pow2 bucket
        # fits max_len (reuse_plan may back the offset off below
        # prefill_pos — those columns recompute to identical K/V)
        from .fleet.prefix_cache import reuse_plan
        offset, _sfx = reuse_plan(t, p, self.config.max_model_len)
        sp = req.sampling
        t0 = self.clock()
        with tr.span("prefill", cat="serving",
                     args={"request_id": req.request_id, "slot": slot,
                           "prompt_len": t, "chunked": True,
                           "suffix": t - offset,
                           "replica": self.replica_name, **targs}):
            self.pool.cache, first = self.engine.slot_suffix_prefill(
                self.pool.cache, slot, req.prompt[offset:], offset,
                temperature=sp.temperature, top_k=sp.top_k,
                top_p=sp.top_p, seed=sp.seed)
        self.metrics.record_prefill(False)      # over the chunks' columns
        if self.cost is not None:
            self.cost.charge_prefill(self.cost.record_for(req),
                                     self.clock() - t0, t - offset)
        self.prefilling.pop(slot, None)
        self._complete_admission(slot, req, int(first))
        return rem

    def _complete_admission(self, slot: int, req: Request, first: int):
        """Shared tail of every prefill path (inline or final chunk):
        record TTFT, deliver the first token, then bind for decode /
        hand off / finish."""
        with self.tracer.phase("serve/first_token", req.request_id):
            ctx = req.trace
            if ctx is not None:
                ctx.mark("first_token")
            t_first = self.clock()
            req.state = RequestState.RUNNING
            req.first_token_time = t_first
            self.metrics.record_ttft(t_first - req.submit_time,
                                     tenant=req.tenant)
            if self.cost is not None:
                # the first token is sampled BY the prefill: its cost is in
                # the prefill charge, but it still counts as an emitted
                # token, so tokens-per-chip-second sees every token
                rec = self.cost.record_for(req)
                rec.tokens += 1
                self.cost._tenant(rec.tenant).tokens += 1
            self._deliver(req, first)
            if self._should_finish(req, first):
                self._finish(req, RequestState.FINISHED, t_first)
                self._release_slot(slot, req)
            elif self.role == "prefill":
                self._hand_off(slot, req, first)
            else:
                self.pool.bind(slot, req, len(req.prompt), first,
                               req.sampling)
                if self.spec is not None:
                    self.draft_cache = self.engine.draft_prefill(
                        self.draft, self.draft_cache, slot, req.prompt)

    def _prefill_into(self, slot: int, req: Request, hit) -> int:
        """Full prefill, or the prefix-reuse fast path when the radix
        cache holds a shared prefix (``hit`` — looked up by the caller
        so the chunk-vs-inline decision sees the unshared suffix).
        Returns the first sampled token."""
        tr = self.tracer
        sp = req.sampling
        if hit is not None:
            from .fleet.prefix_cache import reuse_plan
            offset, _suffix = reuse_plan(int(req.prompt.size), hit.matched,
                                         self.config.max_model_len)
            if offset > 0:
                try:
                    t0 = self.clock()
                    with tr.span("prefix_reuse", cat="serving",
                                 args={"request_id": req.request_id,
                                       "slot": slot, "src_slot": hit.slot,
                                       "matched": hit.matched,
                                       "reused": offset,
                                       "suffix": int(req.prompt.size)
                                       - offset,
                                       "replica": self.replica_name,
                                       **(req.trace.span_args()
                                          if req.trace is not None
                                          else {})}):
                        self.pool.cache = self.engine.slot_copy_lane(
                            self.pool.cache, hit.slot, slot)
                        self.pool.cache, first = \
                            self.engine.slot_suffix_prefill(
                                self.pool.cache, slot, req.prompt[offset:],
                                offset,
                                temperature=sp.temperature, top_k=sp.top_k,
                                top_p=sp.top_p, seed=sp.seed)
                    self.metrics.record_prefill(False)  # columns below live
                    if self.cost is not None:
                        # the lane copy + suffix pass is what the request
                        # actually cost; the reused prefix is prefill the
                        # fleet did NOT pay — priced at the observed
                        # per-token EMA and recorded as savings
                        rec = self.cost.record_for(req)
                        self.cost.charge_prefill(
                            rec, self.clock() - t0,
                            int(req.prompt.size) - offset,
                            update_rate=False)
                        self.cost.note_cache_savings(rec, offset)
                    return first
                finally:
                    self.prefix_cache.release(hit, used_tokens=offset)
            self.prefix_cache.release(hit, used_tokens=0)
        t0 = self.clock()
        with tr.span("prefill", cat="serving",
                     args={"request_id": req.request_id, "slot": slot,
                           "prompt_len": int(req.prompt.size),
                           "replica": self.replica_name,
                           **(req.trace.span_args()
                              if req.trace is not None else {})}
                     if tr.enabled else None):
            # slot_prefill returns the first token as a python int —
            # already device-synced, so the span duration is honest
            self.pool.cache, first = self.engine.slot_prefill(
                self.pool.cache, slot, req.prompt,
                temperature=sp.temperature, top_k=sp.top_k,
                top_p=sp.top_p, seed=sp.seed)
        self._record_prefill(int(req.prompt.size))
        if self.cost is not None:
            self.cost.charge_prefill(self.cost.record_for(req),
                                     self.clock() - t0,
                                     int(req.prompt.size))
        return first

    def _admit_blocks(self, slot: int, req: Request) -> int:
        """Admission of a family that generates by diffusion over blocks:
        the prompt's WHOLE blocks are prefilled under the block mask (no
        token is sampled: a prompt shorter than a block prefills nothing),
        and the ``len % B`` tokens left over open the slot's first block
        beside masked positions. The first tokens come when that block's
        last unmasking pass is read (``_decode_blocks``), and TTFT with
        them. Returns the prefill tokens spent."""
        tr = self.tracer
        whole = int(req.prompt.size) // self.block * self.block
        if whole:
            t0 = self.clock()
            with tr.span("prefill", cat="serving",
                         args={"request_id": req.request_id, "slot": slot,
                               "prompt_len": int(req.prompt.size),
                               "replica": self.replica_name,
                               **(req.trace.span_args()
                                  if req.trace is not None else {})}
                         if tr.enabled else None):
                self.pool.cache, _ = self.engine.slot_prefill(
                    self.pool.cache, slot, req.prompt[:whole])
            self._record_prefill(whole)
            if self.cost is not None:
                self.cost.charge_prefill(self.cost.record_for(req),
                                         self.clock() - t0, whole)
        req.state = RequestState.RUNNING
        self.pool.bind_block(slot, req, whole, req.prompt[whole:],
                             int(req.prompt.size) + req.max_new_tokens,
                             req.sampling)
        return whole

    def _record_prefill(self, tokens: int):
        """A whole prefill of ``tokens`` was read: its routing stats, and
        whether its bucket's program attends in the flash kernel (the
        engine's answer, from the model's own rule)."""
        self._record_routing("serve/moe_prefill")
        ask = self._prefill_kernel
        self.metrics.record_prefill(
            ask is not None and ask(tokens, self.config.max_model_len))

    def _record_routing(self, name: str):
        """A model with routed experts: the program's last call read back
        (experts touched, largest count any expert got), summed over
        layers, with its tokens — kept as an instant phase record. A dense
        model's engine has nothing to take and nothing is recorded."""
        routing = self.engine.take_routing()
        if routing is not None:
            now = time.perf_counter_ns()
            self.tracer.record_phase(name, now, now, *routing)

    def _hand_off(self, slot: int, req: Request, first: int):
        """Prefill role: package the freshly prefilled lane as a
        KVHandoff, release the slot (donating to the prefix cache —
        prompt lanes are exactly what it wants), and deliver to the
        sink. The Request object travels WITH the handoff: the decode
        side keeps appending to the same token list and callbacks."""
        from .fleet.handoff import KVHandoff
        tr = self.tracer
        ctx = req.trace
        with tr.span("kv_handoff_out", cat="serving",
                     args={"request_id": req.request_id, "slot": slot,
                           "kv_len": int(req.prompt.size),
                           "replica": self.replica_name,
                           **(ctx.span_args() if ctx is not None else {})}):
            lane = self.engine.slot_extract_lane(self.pool.cache, slot)
        # the producing version rides both the trace and the frame: the
        # decode side refuses a lane from a different model mid-rollout
        version = int(getattr(self.engine, "weights_version", 0) or 0)
        if ctx is not None:
            ctx.weights_version = version
        handoff = KVHandoff(
            prompt=req.prompt, first_token=int(first),
            kv_len=int(req.prompt.size), lane=lane,
            temperature=req.sampling.temperature,
            top_k=req.sampling.top_k, top_p=req.sampling.top_p,
            seed=req.sampling.seed,
            max_new_tokens=req.max_new_tokens,
            eos_token_id=req.sampling.eos_token_id,
            request_id=req.request_id,
            tenant=req.tenant,
            trace=ctx.to_header() if ctx is not None else None,
            weights_version=version)
        if ctx is not None:
            ctx.mark("handoff_out")
        tr.async_end("request/decode", req.request_id, cat="serving",
                     args={"handed_off": True})
        # the lane was only written, never bound: park it in the prefix
        # cache (or free it) before the sink possibly re-enters us
        self.pool.set_length(slot, int(req.prompt.size))
        self._release_slot(slot, req, donate_seq=req.prompt)
        self.metrics.record_handoff_out()
        if self.handoff_sink is None:
            raise RuntimeError(
                "role=prefill needs a handoff_sink (router wiring) — "
                "a prefill replica has nowhere to send completed KV state")
        self.handoff_sink(handoff, req)

    def _decode(self):
        """One decode tick of a pipeline one step deep: the step that
        advances every slot that goes on is DISPATCHED, fed on the device
        by the step in flight, and only then is the step in flight waited
        on, read and delivered — so the device runs the next step while the
        host delivers this one, closes the tick, admits, and comes back.
        A tick that finds nothing in flight (the pool was idle) sends two.

        What a dispatch needs is known a step early: every slot in a step
        advances one column, and a request that ends by ``max_new_tokens``
        is left out of the step after its last. An ending learnt of from
        the token itself (EOS) or between ticks (a deadline) comes one step
        late: the request's row of the step already in flight is computed
        and dropped, its write one column past what the lane holds
        (``SlotPool.retire_to_cache``)."""
        active = self.pool.active_slots
        if not active:
            self._settle()
            return
        if self.spec is not None:
            # a free slot's temperature is 0: what the sampler will see
            self.metrics.record_decode_tick((self.pool.temps > 0).any())
            return self._decode_speculative(active)
        if self.block > 1:
            return self._decode_blocks(active)
        tr = self.tracer
        pool = self.pool
        t0 = self.clock()
        with tr.span("decode_step", cat="serving",
                     args={"n_active": len(active), "tick": self._tick_no,
                           "replica": self.replica_name}
                     if tr.enabled else None):
            flight = self._flight or self._dispatch(active)
            # the rows of the step in flight that are still their request's
            # (not timed out, not ended by the token before)
            rows = [(slot, req) for slot, req in flight.rows
                    if pool.requests[slot] is req]
            ahead = {slot for slot, _ in rows}
            going = [s for s in active
                     if len(pool.requests[s].tokens) + (s in ahead)
                     < pool.requests[s].max_new_tokens]
            self._flight = self._dispatch(going, flight, ahead) \
                if going else None
            nxt = self.engine.slot_decode_read(flight.out)
        self._record_routing("serve/moe_decode")
        positions = flight.positions
        if self._kv_live:
            # columns the step just read that hold a token of one of its
            # slots: over the full-length lanes, over the rings (0: none)
            live = positions[[slot for slot, _ in flight.rows]] + 1
            now = time.perf_counter_ns()
            tr.record_phase("serve/kv_live", now, now, int(live.sum()),
                            int(np.minimum(live, self._ring_window).sum()))
        # columns of one layer the step's attention just read, of the
        # pool's: with the kernel every slot's live length in whole blocks
        # (a free slot's one), with the XLA attend all of them
        bk, max_len = self._kv_read_block, self.config.max_model_len
        pool_cols = positions.size * max_len
        read = pool_cols if not bk else bk * int(
            ((np.minimum(positions + 1, max_len) + bk - 1) // bk).sum())
        now = time.perf_counter_ns()
        tr.record_phase("serve/kv_read", now, now, read, pool_cols)
        dt = self.clock() - t0
        self.metrics.record_decode_tick(flight.sampled, flight.pipelined)
        self.metrics.record_dropped_rows(len(flight.rows) - len(rows))
        self.metrics.record_decode_step(dt, len(rows))
        if self.cost is not None:
            # every delivered row emits exactly one token this tick: the
            # tick's decode wall splits equally (weight 1 each)
            self.cost.charge_decode(
                dt, [(self.cost.record_for(req), 1) for _, req in rows])
        now = self.clock()
        with tr.phase("serve/deliver", len(rows)) as deliver:
            for slot, req in rows:
                tok = int(nxt[slot])
                pool.lengths[slot] += 1     # fed token's K/V is in cache
                pool.pending[slot] = tok
                finishing = self._should_finish(req, tok, pending=1)
                if finishing and req.trace is not None:
                    # the token loop ends here; what follows (final
                    # delivery, bookkeeping) is the critical path's
                    # "stream" tail
                    req.trace.mark("decode_done")
                self._deliver(req, tok)
                self.metrics.record_tenant_tokens(req.tenant)
                if finishing:
                    self._finish(req, RequestState.FINISHED, now)
                    self._release_slot(slot, req)
                    deliver.b += 1
        if self._flight is not None and not pool.active_slots:
            self._settle()

    def _dispatch(self, slots, behind=None, fed=()):
        """Send the decode step that advances ``slots``. ``behind`` is the
        step in flight and ``fed`` the slots whose token it holds for this
        one, taken on the device; after an idle pool there is neither and
        every token is the host's. Returns the step un-read."""
        pool = self.pool
        with self.tracer.phase("serve/decode_prep", len(slots)):
            rows = [(slot, pool.requests[slot]) for slot in slots]
            toks, positions, temps, top_ks, top_ps, seeds, from_host = \
                pool.dispatch_arrays(slots, fed)
        pool.cache, out = self.engine.slot_decode_dispatch(
            pool.cache, toks, positions, temps, top_ks=top_ks,
            top_ps=top_ps, seeds=seeds,
            prev=None if behind is None else behind.out,
            from_host=from_host)
        return _Flight(out, rows, positions, bool((temps > 0).any()),
                       behind is not None)

    def _decode_blocks(self, active, pipelined: bool = True):
        """``_decode`` for a family that generates by diffusion over blocks
        (``models/sdar.py``): one PASS of the tick advances every slot that
        goes on by a pass of its block of B columns, and delivers nothing
        from most rows. The pipeline is ``_decode``'s, one pass deep: the
        next pass is sent, fed on the device by the one in flight, before
        that one is read. What a dispatch must know a pass early follows
        from the static schedule (``SlotPool.dispatch_block_arrays``):
        which pass of its block a slot is in, when it moves B columns on,
        and that a request whose last token lies in the block whose last
        unmasking pass was sent takes no further part (no writing pass). A
        block's tokens are delivered, in order, when its last unmasking
        pass is read; ``max_new_tokens`` or EOS may end a request inside a
        block, and the rest of it is dropped. An ending by EOS comes a
        pass late: the request's row of the pass in flight is computed and
        dropped. ``pipelined=False`` is the tests': every pass is read
        before the next is sent, and they hold the two streams bitwise
        equal."""
        tr, pool, b = self.tracer, self.pool, self.block
        t0 = self.clock()

        def going():
            return [s for s in pool.active_slots if not pool.block_done[s]]

        with tr.span("decode_step", cat="serving",
                     args={"n_active": len(active), "tick": self._tick_no,
                           "replica": self.replica_name}
                     if tr.enabled else None):
            flight = self._flight or self._dispatch_blocks(going())
            rows = [(slot, req) for slot, req in flight.rows
                    if pool.requests[slot] is req]
            behind = going() if pipelined else []
            self._flight = self._dispatch_blocks(
                behind, flight, {slot for slot, _ in rows}) \
                if behind else None
            ids, flags = self.engine.slot_block_read(flight.out)
        self._record_routing("serve/moe_decode")
        # columns of one layer the pass's attention read, of the pool's:
        # the XLA attend contracts over every column of every lane
        pool_cols = flight.positions.size * self.config.max_model_len
        now = time.perf_counter_ns()
        tr.record_phase("serve/kv_read", now, now, pool_cols, pool_cols)
        writing = sum(flight.passes[slot][2] for slot, _ in flight.rows)
        unmasking = len(flight.rows) - writing
        flagged = sum(int(pool.block_flags[slot].sum()) for slot, _ in rows
                      if not flight.passes[slot][2])
        tr.record_phase("serve/block_pass", now, now, unmasking, flagged)
        tr.record_phase("serve/block_write", now, now, writing,
                        len(flight.rows))
        dt = self.clock() - t0
        self.metrics.record_decode_tick(flight.sampled, flight.pipelined)
        self.metrics.record_dropped_rows(len(flight.rows) - len(rows))
        now = self.clock()
        delivered = firsts = cut = 0
        costs, tokens = [] if self.cost is not None else None, []
        with tr.phase("serve/deliver", len(rows)) as deliver:
            for slot, req in rows:
                sent, last, is_write = flight.passes[slot]
                gave = 0
                if is_write:
                    pool.next_block(slot)
                else:
                    fixed = pool.block_flags[slot] & ~flags[slot]
                    pool.block_fixed[slot][fixed] = sent
                    pool.block_ids[slot] = ids[slot]
                    pool.block_flags[slot] = flags[slot]
                if last:
                    gave, first = self._deliver_block(slot, req, now)
                    firsts += first
                    if req.done:
                        cut += b - max(int(req.prompt.size) -
                                       int(flight.positions[slot]), 0) - gave
                        self._release_slot(slot, req)
                        deliver.b += 1
                delivered += gave
                if costs is not None:
                    costs.append((self.cost.record_for(req), b))
                    tokens.append(gave)
        # a request's first token is counted with its TTFT
        self.metrics.record_decode_step(dt, delivered - firsts)
        self.metrics.record_block_pass(unmasking, writing, delivered, cut)
        if costs:
            # the wall by the columns a row advanced, the tokens as delivered
            self.cost.charge_decode(dt, costs, tokens)
        if self._flight is not None and not pool.active_slots:
            self._settle()

    def _deliver_block(self, slot: int, req: Request, now: float):
        """A slot's block has no masked position left: deliver its tokens
        in order (those past the prompt's, in a first block), the first of
        a request with its TTFT, up to ``max_new_tokens`` or EOS. Returns
        (the tokens delivered, whether the request's first is among them);
        the request is finished where one ended it."""
        pool = self.pool
        held = max(int(req.prompt.size) - int(pool.lengths[slot]), 0)
        gave, first = 0, req.first_token_time is None
        for j in range(held, self.block):
            tok = int(pool.block_ids[slot, j])
            if req.first_token_time is None:
                # no ``serve/first_token`` phase: that record is a part of
                # a prefill's cost, and this is ticks after the prefill
                if req.trace is not None:
                    req.trace.mark("first_token")
                req.first_token_time = now
                self.metrics.record_ttft(now - req.submit_time,
                                         tenant=req.tenant)
            finishing = self._should_finish(req, tok, pending=1)
            if finishing and req.trace is not None:
                req.trace.mark("decode_done")
            req.fixed_pass.append(int(pool.block_fixed[slot, j]))
            self._deliver(req, tok)
            gave += 1
            if finishing:
                self._finish(req, RequestState.FINISHED, now)
                break
        self.metrics.record_tenant_tokens(req.tenant, gave - first)
        return gave, int(first)

    def _dispatch_blocks(self, slots, behind=None, fed=()):
        """``_dispatch`` for a pass over blocks."""
        pool = self.pool
        with self.tracer.phase("serve/decode_prep", len(slots)):
            rows = [(slot, pool.requests[slot]) for slot in slots]
            (ids, flags, positions, temps, top_ks, top_ps, seeds,
             from_host), passes = pool.dispatch_block_arrays(
                slots, fed, self.block_fix)
        pool.cache, out = self.engine.slot_block_dispatch(
            pool.cache, ids, flags, positions, temps, top_ks=top_ks,
            top_ps=top_ps, seeds=seeds, fix=self.block_fix,
            prev=None if behind is None else behind.out,
            from_host=from_host)
        return _Flight(out, rows, positions, bool((temps > 0).any()),
                       behind is not None, passes)

    def _settle(self):
        """No slot is live: the step in flight, if there is one, holds
        only rows of requests that have ended. It is dropped un-read (the
        pool it returned is the pool), so ``run_until_idle``, ``drain`` and
        ``shutdown`` come back with nothing in flight."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self.metrics.record_dropped_rows(len(flight.rows))

    def _decode_speculative(self, active):
        """One speculative tick: the draft proposes k tokens per slot
        (one compiled scan), the target verifies all of them in one
        batched forward with in-step accept/rollback, and every active
        slot advances by its accepted prefix + 1 — between 1 and k+1
        tokens — with the emitted stream bitwise identical to the
        non-speculative path."""
        toks, positions, temps, top_ks, top_ps, seeds = \
            self.pool.decode_arrays()
        k = self.spec.k
        tr = self.tracer
        t0 = self.clock()
        with tr.span("draft_propose", cat="serving",
                     args={"n_active": len(active), "k": k,
                           "tick": self._tick_no,
                           "replica": self.replica_name}):
            self.draft_cache, draft_toks = self.engine.slot_draft_propose(
                self.draft, self.draft_cache, toks, positions, temps,
                top_ks, top_ps, seeds, k)
        t_draft = self.clock()
        # marks are consecutive: prev mark -> spec_verify_start buckets as
        # "decode" (draft + scheduling), spec_verify_start -> spec_verify
        # is the verify forward itself — stage sums still equal e2e exactly
        for slot in active:
            req = self.pool.requests[slot]
            if req.trace is not None:
                req.trace.mark("spec_verify_start")
        with tr.span("spec_verify", cat="serving",
                     args={"n_active": len(active), "k": k,
                           "tick": self._tick_no,
                           "replica": self.replica_name}):
            self.pool.cache, out_toks, accepts = self.engine.slot_verify_step(
                self.pool.cache, toks, draft_toks, positions, temps,
                top_ks, top_ps, seeds)
        t_verify = self.clock()
        for slot in active:
            req = self.pool.requests[slot]
            if req.trace is not None:
                req.trace.mark("spec_verify")
        now = self.clock()
        accepted_total = emitted_total = 0
        cost_pairs = [] if self.cost is not None else None
        for slot in active:
            req = self.pool.requests[slot]
            a = int(accepts[slot])
            p = int(self.pool.lengths[slot])
            delivered = 0
            finishing = False
            for j in range(a + 1):
                tok = int(out_toks[slot, j])
                finishing = self._should_finish(req, tok, pending=1)
                if finishing and req.trace is not None:
                    req.trace.mark("decode_done")
                self._deliver(req, tok)
                delivered += 1
                if finishing:
                    break
            # columns p..p+a hold the fed token + accepted drafts; the
            # final emitted token (the bonus / first mismatch) is the new
            # pending — its K/V is not in the cache yet
            self.pool.lengths[slot] = p + 1 + min(delivered, a)
            accepted_total += a
            emitted_total += delivered
            self.metrics.record_tenant_tokens(req.tenant, delivered)
            if cost_pairs is not None:
                cost_pairs.append((self.cost.record_for(req), delivered))
            if finishing:
                self._finish(req, RequestState.FINISHED, now)
                self._release_slot(slot, req)
            else:
                self.pool.pending[slot] = int(out_toks[slot, a])
        if cost_pairs is not None:
            # one weighted split of the whole tick wall by emitted
            # tokens: accepted drafts credit their request, and the
            # draft + verify overhead lands pro-rata in the same split
            self.cost.charge_spec(now - t0, draft_s=t_draft - t0,
                                  verify_s=t_verify - t_draft,
                                  weighted=cost_pairs)
        self.metrics.record_spec_tick(
            step_s=now - t0, n_active=len(active), k=k,
            accepted=accepted_total, emitted=emitted_total,
            draft_s=t_draft - t0, verify_s=t_verify - t_draft,
            ema_alpha=self.spec.ema_alpha)

    # -------------------------------------------------------------- helpers
    def _deliver(self, req: Request, tok: int):
        req.tokens.append(tok)
        if req.on_token is not None:
            try:
                req.on_token(req, tok)
            except Exception as e:   # user callback must not kill the loop
                logger.warning(
                    f"serving: on_token callback failed for request "
                    f"{req.request_id}: {e}")

    def _should_finish(self, req: Request, tok: int,
                       pending: int = 0) -> bool:
        """``pending`` counts tokens sampled but not yet appended — the
        decode loop asks BEFORE delivering, so the critical-path mark
        lands ahead of the final callback."""
        eos = req.sampling.eos_token_id
        return (len(req.tokens) + pending >= req.max_new_tokens or
                (eos is not None and tok == eos))

    def _finish(self, req: Request, state: RequestState, now: float):
        req.state = state
        req.finish_time = now
        if req.trace is not None:
            req.trace.mark("finished")
        tr = self.tracer
        if req.first_token_time is None and not req.prefill_started:
            # expired straight out of the queue: close the queued phase
            tr.async_end("request/queued", req.request_id, cat="serving")
        else:
            # admitted (incl. a PREFILLING request that expired before
            # its first token): the decode-phase span is the open one
            tr.async_end("request/decode", req.request_id, cat="serving")
        tr.async_end(
            "request", req.request_id, cat="serving",
            args={"state": state.value, "tokens": len(req.tokens),
                  "replica": self.replica_name,
                  "ttft_ms": None if req.first_token_time is None else
                  round((req.first_token_time - req.submit_time) * 1e3, 3),
                  **(req.trace.span_args()
                     if req.trace is not None else {})})
        if state is RequestState.TIMEOUT:
            self.metrics.record_timeout(tenant=req.tenant)
        elif state is RequestState.FINISHED:
            self.metrics.record_completion(req)
