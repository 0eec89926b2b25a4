"""ServingEngine — continuous-batching facade over InferenceEngine.

The online counterpart of ``InferenceEngine.generate()`` (one compiled
program per static batch): requests arrive one at a time via
``submit(prompt, ...) -> request_id``, are admitted into a fixed pool of
decode slots, and every ``step()`` advances ALL in-flight requests by one
token through a single compiled decode program. Per-token streaming runs
through ``on_token`` callbacks; robustness controls — bounded admission
queue with backpressure, per-request deadlines, graceful drain — are
first-class.

    engine = deepspeed_tpu.init_inference(model, config={...})
    srv = ServingEngine(engine, {"num_slots": 8, "max_model_len": 512})
    rid = srv.submit(prompt_ids, SamplingParams(max_new_tokens=32),
                     on_token=lambda req, tok: print(tok))
    srv.run_until_idle()
    print(srv.result(rid).output_ids)
    srv.shutdown()
"""

import time
from typing import Callable, Dict, Optional, Union

import numpy as np

from ..utils.logging import log_dist
from .config import ServingConfig
from .metrics import ServingMetrics
from .scheduler import (ContinuousBatchingScheduler, QueueFull, Request,
                        RequestState, SamplingParams)

__all__ = ["ServingEngine", "SamplingParams", "QueueFull", "RequestState"]


class ServingEngine:
    """Slot-based continuous-batching serving on top of InferenceEngine."""

    #: what cannot be served over each kind of state a model's pool may
    #: declare beside, or in place of, full-length K and V
    #: (``GPT2Model.recurrent_state``, ``window_rings``, ``latent_cache``):
    #: {kind: (what it is, {config block: why not})}. A latent lane keeps a
    #: row per token and IS valid up to any column: prefix cache, chunked
    #: prefill and an int8 pool run over it as over K and V
    #: (tests/unit/test_xing.py); what is refused is what it has no
    #: program for
    _LANE_END_FENCES = {
        "recurrent_state": (
            "keeps a recurrent state {names} beside K and V, which exists "
            "only at a lane's last token",
            {"prefix_cache": "a lane copied from another request holds the "
             "donor's state at the donor's end, not at the shared prefix",
             "speculative": "rejected draft rows cannot be rolled back out "
             "of it column by column",
             "kv_quant": "it is rewritten every token, and an int8 round "
             "trip a token would compound where a KV column is quantized "
             "once",
             "chunked_prefill": "the fused decode tick runs over every slot "
             "between two chunks, and the dummy row of a lane that is still "
             "prefilling is pushed into its state, where a dummy KV column "
             "is overwritten by the next chunk"}),
        "window_rings": (
            "keeps its window layers' keys and values in rings {names} of "
            "the last positions, which hold a lane as it stands at its last "
            "token",
            {"prefix_cache": "a lane copied from another request holds the "
             "donor's last positions, not those before the shared prefix",
             "speculative": "the rows a rejected draft wrote have replaced "
             "columns still in the window and cannot be rolled back",
             "chunked_prefill": "a prompt's last chunk may start below the "
             "column the chunks before it reached (reuse_plan), and the "
             "ring no longer holds the positions before that"}),
        "latent_cache": (
            "keeps a latent row a token {names} in place of K and V",
            {"speculative": "a block of draft tokens at a position of its "
             "own a slot would expand EVERY slot's whole lane to per-head "
             "keys and values a verify step (the block path of "
             "_latent_attend), and the family's own drafter, its "
             "multi-token-prediction block, is not built (ROADMAP B9)"}),
        "denoised_blocks": (
            "generates by diffusion over blocks: the columns of {names} past "
            "a lane's last whole block are provisional until the block's "
            "writing pass",
            {"speculative": "a block is denoised in place over passes of "
             "its own and nothing is rolled back; a draft of the next "
             "tokens has no place in it",
             "prefix_cache": "a shared prefix and a donated lane end where "
             "their tokens do, which is inside a block as often as not, and "
             "the keys of a block's first positions were computed seeing "
             "its last: rounding hits down to whole blocks is not built",
             "chunked_prefill": "a chunk that ended inside a block would "
             "leave its positions without the keys of the rest of their "
             "block; chunks of whole blocks are not built",
             "kv_quant": "every pass of a block rewrites its columns and "
             "the next pass attends them: an int8 round trip a pass would "
             "quantize keys that are written once more before they are "
             "final"}),
    }

    @classmethod
    def _fence_lane_end_state(cls, engine, config):
        """A model whose pool holds state that exists at a lane's END alone
        (a recurrent state; a window layer's ring: not a row per token)
        cannot be served with the mechanisms that lean on a KV lane being
        valid up to ANY column, nor with those that leave a lane half
        filled across decode ticks. Refused here, by the name of the block
        and of the state, before a pool is allocated; snapshots of such
        state are ROADMAP B8's. An int8 pool holds a ring as it holds a
        lane (a column is quantized once, when it is written). The kinds
        are what the MODEL declares, none is inferred from a leaf's name:
        a latent lane is fenced for speculation alone."""
        module = getattr(engine, "module", None)
        from ..runtime.config_utils import ConfigError
        for kind, (what, fences) in cls._LANE_END_FENCES.items():
            names = tuple(getattr(module, kind, ()))
            if not names:
                continue
            for block, reason in fences.items():
                if getattr(getattr(config, block, None), "enabled", False):
                    raise ConfigError(
                        f"{block}: {type(module).__name__} "
                        f"{what.format(names=names)}; {reason}")
        cls._fence_blocks(module, config)

    @staticmethod
    def _fence_blocks(module, config):
        """The ``block_diffusion`` block against the model's
        ``block_length``: a schedule the blocks cannot follow, and what a
        pool of blocks cannot hand over, are refused with the reason."""
        from ..runtime.config_utils import ConfigError
        b = getattr(module, "block_length", 1)
        steps = getattr(getattr(config, "block_diffusion", None),
                        "denoising_steps", None)
        name = type(module).__name__
        if b == 1:
            if steps is not None:
                raise ConfigError(
                    f"block_diffusion: {name} decodes a token a step "
                    f"(block_length 1); there is no block to denoise")
            return
        if steps is not None and (steps > b or b % steps):
            raise ConfigError(
                f"block_diffusion.denoising_steps={steps}: a pass fixes "
                f"block_length / denoising_steps positions, and {steps} "
                f"does not divide {name}'s block_length {b}")
        if config.max_model_len % b:
            raise ConfigError(
                f"max_model_len={config.max_model_len}: a lane of {name} "
                f"holds whole blocks of {b}, and its last block would end "
                f"past the lane")
        if getattr(config, "role", "unified") != "unified":
            raise ConfigError(
                f"role={config.role}: {name} generates by diffusion over "
                f"blocks, and a handed-off lane carries a first token and "
                f"a length, not a block half denoised and its flags")

    def __init__(self, engine, config: Union[ServingConfig, dict, None] = None,
                 clock: Callable[[], float] = time.monotonic, seed: int = 0,
                 handoff_sink: Optional[Callable] = None,
                 id_start: int = 0, id_stride: int = 1,
                 replica_name: Optional[str] = None):
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        else:
            config.validate()
        self.config = config
        self.engine = engine
        self._fence_lane_end_state(engine, config)
        # fleet lane identity: the name build_fleet gave this replica (or
        # "serving" standalone) — stamped on every span so the fleet
        # aggregator can split the shared span ring into per-replica lanes
        self.replica = replica_name or "serving"
        # fleet id spacing: replica i of N uses ids i, i+N, i+2N, ... so a
        # request's async trace spans stay unique when it migrates between
        # co-resident replicas (handoff, failover)
        self._id_start = int(id_start)
        self._id_stride = max(1, int(id_stride))
        self.monitor = None
        if config.monitor:
            from ..monitor.monitor import MonitorMaster
            self.monitor = MonitorMaster(config)
        from ..telemetry.trace import configure_tracer
        self.tracer = configure_tracer(config.telemetry) \
            if config.telemetry is not None else configure_tracer()
        self.tracer.watch_gc(self)      # until shutdown()
        from ..telemetry.goodput import configure_ledger, get_ledger
        tcfg = config.telemetry
        if tcfg is not None:
            # config wins, same contract as configure_tracer; without a
            # telemetry block the process-global ledger state stands (a
            # co-resident training engine may have enabled it)
            configure_ledger(enabled=bool(
                getattr(tcfg, "enabled", False) and
                getattr(tcfg, "goodput", True)))
        self._ledger = get_ledger()
        self.metrics = ServingMetrics(monitor=self.monitor,
                                      monitor_interval=config.monitor_interval,
                                      tracer=self.tracer, slo=config.slo,
                                      tenants=getattr(config, "tenants",
                                                      None))
        # flight recorder: per-tick records (queue depth, SLO burn) +
        # postmortem bundles on burn-rate spikes / preemption / explicit
        # /debug/capture; off by default = nothing allocated
        self._recorder = None
        self._last_burn = 0.0
        self._last_spec_ema = None
        if getattr(config.flight_recorder, "enabled", False):
            from ..telemetry.flight_recorder import FlightRecorder
            self._recorder = FlightRecorder(config.flight_recorder,
                                            tracer=self.tracer)
            self._recorder.add_provider("serving", self._statusz_section)
            # bundles embed the trace ids in flight on THIS replica, so
            # the router can correlate same-trace bundles across members
            self._recorder.set_trace_provider(self._traces_in_flight)
        # compile/memory plane (telemetry/compileplane.py): compile ledger
        # over the serving programs — each prefill bucket, the fused
        # decode step, pool init — plus the HBM role ledger attributing
        # per-device bytes to params vs the KV slot pool. Off by default
        # = nothing allocated, no per-call fingerprints.
        self._compile_plane = None
        self._hbm = None
        self._hbm_interval = 8
        cpcfg = getattr(config, "compile_plane", None)
        if getattr(cpcfg, "enabled", False):
            from ..telemetry.compileplane import CompileLedger, HBMLedger
            self._compile_plane = CompileLedger(cpcfg, tracer=self.tracer,
                                                owner=self)
            engine.compile_plane = self._compile_plane
            if cpcfg.hbm:
                self._hbm = HBMLedger(tracer=self.tracer, owner=self)
                self._hbm_interval = int(cpcfg.hbm_interval_steps)
            if self._recorder is not None:
                self._recorder.attach_compile_plane(self._compile_plane)
        self.statusz = None
        if getattr(config.statusz, "enabled", False):
            from ..telemetry.statusz import StatuszServer
            self.statusz = StatuszServer(config.statusz, tracer=self.tracer)
            self.statusz.register("serving", self._statusz_section)
            self.statusz.register_health("serving", self._health_check)
            if self._recorder is not None:
                self.statusz.attach_recorder(self._recorder)
            if self._compile_plane is not None:
                self.statusz.register("compile_plane",
                                      self._compile_plane.summary)
            if self._hbm is not None:
                self.statusz.register("memory", self._hbm.summary)
        self.scheduler = ContinuousBatchingScheduler(
            engine, config, metrics=self.metrics, clock=clock, seed=seed,
            handoff_sink=handoff_sink, replica_name=self.replica)
        if self.statusz is not None and self.scheduler.cost is not None:
            # standalone engines surface their own ledger; in a fleet the
            # router's fold is the authoritative per-tenant total
            self.statusz.register("costs", self._cost_section)
        self._requests: Dict[int, Request] = {}
        self._next_id = self._id_start
        self._draining = False
        self._preempt_drained = False
        self._preemption = None
        if config.resilience is not None and config.resilience.handle_signals:
            from ..resilience.preemption import PreemptionHandler
            self._preemption = PreemptionHandler.install()
        n_pos = getattr(getattr(engine.module, "config", None),
                        "n_positions", None)
        if n_pos is not None and config.max_model_len > n_pos:
            raise ValueError(
                f"serving.max_model_len={config.max_model_len} exceeds the "
                f"model's context length n_positions={n_pos}")
        log_dist(
            f"ServingEngine initialized: slots={config.num_slots} "
            f"max_model_len={config.max_model_len} "
            f"max_queue={config.max_queue}", ranks=[0])

    # ---------------------------------------------------------------- submit
    def submit(self, prompt, sampling: Optional[SamplingParams] = None,
               on_token: Optional[Callable] = None, trace=None) -> int:
        """Enqueue one request. Returns its request_id; raises ``QueueFull``
        when the bounded admission queue is at capacity (backpressure — the
        caller sheds load or retries with backoff) and ``RuntimeError``
        after shutdown/drain began. ``trace`` carries an existing
        distributed TraceContext (the fleet router's) — without one the
        scheduler mints a fresh per-request context at enqueue."""
        if self._draining:
            raise RuntimeError("ServingEngine is draining; submit rejected")
        sampling = sampling or SamplingParams()
        sampling.validate()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        max_new = (sampling.max_new_tokens
                   if sampling.max_new_tokens is not None
                   else self.config.default_max_new_tokens)
        if prompt.size + max_new > self.config.max_model_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds serving.max_model_len={self.config.max_model_len}")
        req = Request(request_id=self._next_id, prompt=prompt,
                      sampling=sampling, max_new_tokens=max_new,
                      on_token=on_token, trace=trace)
        self.scheduler.enqueue(req)     # raises QueueFull on backpressure
        self._requests[req.request_id] = req
        self._next_id += self._id_stride
        return req.request_id

    def submit_handoff(self, handoff, request: Optional[Request] = None,
                       on_token: Optional[Callable] = None) -> int:
        """Enqueue a completed prefill (serving/fleet/handoff.py) for
        decode in THIS replica's pool. With ``request`` (the router path)
        the same Request object continues — its token list, callbacks,
        and deadline travel with the KV state; without one (direct API
        use) a Request is reconstructed from the handoff's metadata and
        the already-sampled first token is delivered here. Raises
        ``QueueFull`` past ``max_queue`` (shared with the prompt queue)
        and ``ValueError`` when the handoff cannot fit this replica's
        pool."""
        if self._draining:
            raise RuntimeError("ServingEngine is draining; handoff rejected")
        kv_len = int(handoff.kv_len)
        max_new = (request.max_new_tokens if request is not None
                   else int(handoff.max_new_tokens))
        if kv_len + max_new > self.config.max_model_len:
            raise ValueError(
                f"handoff kv_len ({kv_len}) + max_new_tokens ({max_new}) "
                f"exceeds serving.max_model_len={self.config.max_model_len}")
        # version boundary check (rollout plane): a KV lane computed by a
        # different weights_version must never seed this replica's decode
        # — refuse it and re-prefill locally instead. None = pre-rollout
        # producer, accepted for compatibility.
        incoming = getattr(handoff, "weights_version", None)
        refused = incoming is not None and \
            int(incoming) != self.weights_version
        deliver_first = request is None
        if request is None:
            sampling = SamplingParams(
                temperature=handoff.temperature,
                top_k=int(getattr(handoff, "top_k", 0)),
                top_p=float(getattr(handoff, "top_p", 1.0)),
                seed=int(getattr(handoff, "seed", 0)),
                max_new_tokens=handoff.max_new_tokens,
                eos_token_id=handoff.eos_token_id,
                tenant=getattr(handoff, "tenant", None) or "default")
            trace = None
            if handoff.trace is not None:
                # a deserialized frame carries the producing side's trace
                # identity: decode continues the SAME trace (marks restart
                # in this process's clock domain)
                from ..telemetry.disttrace import TraceContext
                trace = TraceContext.from_header(handoff.trace)
            request = Request(
                request_id=self._next_id,
                prompt=np.asarray(handoff.prompt, np.int32).reshape(-1),
                sampling=sampling, max_new_tokens=handoff.max_new_tokens,
                on_token=on_token, trace=trace)
            self._next_id += self._id_stride
            request.submit_time = self.scheduler.clock()
            if not refused:
                self.tracer.async_begin(
                    "request", request.request_id, cat="serving",
                    args={"prompt_len": int(request.prompt.size),
                          "max_new_tokens": request.max_new_tokens,
                          "handoff": True, "replica": self.replica,
                          **(trace.span_args() if trace is not None
                             else {})})
        if refused:
            return self._refuse_handoff(handoff, request,
                                        fresh=deliver_first)
        self.scheduler.enqueue_handoff(handoff, request)   # QueueFull here
        self._requests[request.request_id] = request
        if deliver_first:
            request.state = RequestState.RUNNING
            request.first_token_time = self.scheduler.clock()
            request.tokens.append(int(handoff.first_token))
            if on_token is not None:
                try:
                    on_token(request, int(handoff.first_token))
                except Exception:
                    pass
        return request.request_id

    def _refuse_handoff(self, handoff, request: Request,
                        fresh: bool) -> int:
        """Refuse a KV lane from a different ``weights_version`` and
        re-prefill the request in THIS replica's pool instead. KV state
        computed by one model and read by another is silent corruption,
        and a mid-rollout fleet is exactly when producer and consumer
        versions differ. The (seed, cache position) sampling contract
        regenerates the SAME token stream from the local prefill, and
        the router's delivered-position dedup keeps client delivery
        exactly-once — the refusal costs one extra prompt pass, never
        correctness. ``fresh`` marks the direct-API path (the Request
        was just reconstructed here and has no open lifecycle span)."""
        if len(self.scheduler.queue) >= self.config.max_queue:
            # reject BEFORE mutating the request so the router can retry
            # the untouched handoff on another decode replica
            self.metrics.record_reject()
            raise QueueFull(
                f"serving queue at capacity ({self.config.max_queue}); "
                f"handoff refusal cannot re-prefill")
        producer = getattr(handoff, "weights_version", None)
        ctx = getattr(request, "trace", None)
        if ctx is not None:
            ctx.mark("handoff_refused")
        self.metrics.record_handoff_refused()
        with self.tracer.span(
                "handoff_refused", cat="serving",
                args={"request_id": request.request_id,
                      "producer_version": producer,
                      "local_version": self.weights_version,
                      "source": getattr(handoff, "source", None),
                      "replica": self.replica,
                      **(ctx.span_args() if ctx is not None else {})}):
            pass
        if not fresh:
            # the request already lived a prefill on the producing side:
            # close its open lifecycle span and reset to pre-admission
            # state — enqueue() below re-opens the span for the local
            # re-prefill, keeping the trace balanced
            self.tracer.async_end(
                "request", request.request_id, cat="serving",
                args={"handoff_refused": True,
                      "replica": self.replica})
            request.state = RequestState.QUEUED
            request.tokens.clear()
            request.prefill_pos = 0
            request.prefill_started = False
            request.first_token_time = None
        self.scheduler.enqueue(request)
        self._requests[request.request_id] = request
        log_dist(
            f"serving: KV handoff for request {request.request_id} "
            f"REFUSED (producer weights_version {producer} != local "
            f"{self.weights_version}); re-prefilling locally", ranks=[0])
        return request.request_id

    # ------------------------------------------------------------------ step
    def step(self) -> int:
        """One scheduler tick: expire deadlines, admit into free slots
        (prefill), one token for every active slot from one fused decode
        step. The step read is the one the tick before dispatched; this
        tick's own is dispatched first and left in flight for the next
        (``Scheduler._decode``), and nothing is left in flight once no
        request is (``run_until_idle``, ``drain``, ``shutdown``). Returns
        requests still in flight. On a preemption signal (SIGTERM/SIGINT
        or the ``preempt_signal`` fault) the tick becomes a clean drain:
        admissions stop, running slots complete, queued requests cancel."""
        if self._check_preemption():
            return 0
        rec = self._recorder
        t0 = time.perf_counter() if rec is not None else 0.0
        bucket = "serving_drain" if self._draining else "serving_step"
        tr = self.tracer
        with tr.phase("serve/tick") as tick:
            with self._ledger.track(bucket):
                in_flight = self.scheduler.tick()
            with tr.phase("serve/bookkeeping"):
                self.metrics.flush()
                if self._hbm is not None and \
                        self.metrics.ticks % self._hbm_interval == 0:
                    self._update_hbm()
                if rec is not None:
                    self._flight_record((time.perf_counter() - t0) * 1e3)
            tick.a, tick.b = self.metrics.ticks, self.active_requests
        return in_flight

    def _update_hbm(self):
        """HBM role ledger update: the serving replica's per-device bytes
        are the weights plus the slot-pool KV cache — the
        ``dstpu_mem_params_gib`` / ``dstpu_mem_kv_slots_gib`` gauges."""
        try:
            kv_bytes = self._hbm.device_bytes(self.scheduler.pool.cache)
            if self.scheduler.draft_cache is not None:
                # the draft pool is KV state too — it rides the same role
                kv_bytes += self._hbm.device_bytes(
                    self.scheduler.draft_cache)
            roles = {"params": self._hbm.device_bytes(self.engine.params),
                     "kv_slots": kv_bytes}
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
            self._hbm.update(roles,
                             peak_bytes=stats.get("peak_bytes_in_use"))
        except Exception as e:
            log_dist(f"compile plane: HBM ledger update failed: {e}",
                     ranks=[0])

    def _flight_record(self, dur_ms: float):
        """One scheduler tick into the flight recorder. Tick times swing
        legitimately (prefill vs decode), so the slow-step rule stays off;
        the serving trigger is the SLO error-budget burn rate crossing
        ``flight_recorder.slo_burn_threshold`` (edge-triggered — a burn
        that stays high fires once, not every tick)."""
        rec = self._recorder
        burn = self.metrics.last_burn_rate
        rec.record_step(self.metrics.ticks, dur_ms, slow_check=False,
                        extra={"queue_depth": self.queue_depth,
                               "active_requests": self.active_requests,
                               "draining": self._draining,
                               "slo_burn_rate": burn})
        if burn is not None:
            thresh = rec.slo_burn_threshold
            if burn > thresh and self._last_burn <= thresh:
                rec.trigger(
                    "slo_burn",
                    f"tick {self.metrics.ticks}: burn rate {burn:.2f} "
                    f"crossed {thresh:g} (queue {self.queue_depth}, "
                    f"{self.active_requests} active)")
            self._last_burn = burn
        spec = self.scheduler.spec
        ema = self.metrics.spec_acceptance_ema
        if spec is not None and ema is not None and \
                spec.acceptance_floor > 0 and \
                self.metrics.spec_ticks >= spec.warmup_ticks:
            # edge-triggered on the EMA dropping BELOW the floor:
            # speculation that stopped paying for itself (draft drift,
            # workload change) is an incident, not a steady alarm
            floor = spec.acceptance_floor
            prev = self._last_spec_ema
            if ema < floor and (prev is None or prev >= floor):
                tpt = self.metrics.spec_tokens_per_tick_ema or 0.0
                rec.trigger(
                    "acceptance_drop",
                    f"tick {self.metrics.ticks}: speculative acceptance "
                    f"EMA {ema:.3f} fell below floor {floor:g} "
                    f"(k={self.metrics.spec_k}, tokens/tick {tpt:.2f})")
            self._last_spec_ema = ema

    def _check_preemption(self) -> bool:
        if self._preemption is None or self._draining:
            return False
        from ..resilience.faults import fault
        if fault("preempt_signal"):
            self._preemption.signal()
        if not self._preemption.preempted:
            return False
        self._preempt_drained = True
        self.tracer.set_counter("resilience/preemptions", 1.0, owner=self)
        if self._recorder is not None:
            # capture before the drain rewrites queue/slot state; bypasses
            # debounce — there is no second chance after a preemption
            self._recorder.trigger(
                "preemption",
                f"serving drain on preemption signal "
                f"({self.active_requests} running, {self.queue_depth} "
                f"queued)", force=True)
        log_dist("serving: preemption signal received; draining "
                 f"({self.active_requests} running, {self.queue_depth} "
                 f"queued)", ranks=[0])
        with self.tracer.span("preempt_drain", cat="resilience"):
            with self._ledger.track("preemption"):
                self.drain(serve_queued=False)
        return True

    def run_until_idle(self, max_ticks: int = 100_000) -> int:
        """Tick until no request is queued or running. Returns ticks run."""
        for i in range(max_ticks):
            if self.step() == 0:
                return i + 1
        return max_ticks

    # --------------------------------------------------------------- results
    def result(self, request_id: int) -> Request:
        return self._requests[request_id]

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued request (running requests finish their course)."""
        req = self._requests.get(request_id)
        if req is None or req.state is not RequestState.QUEUED:
            return False
        try:
            self.scheduler.queue.remove(req)
        except ValueError:
            return False
        req.state = RequestState.CANCELLED
        req.finish_time = self.scheduler.clock()
        self._close_request_spans(req)
        return True

    # ------------------------------------------------------------- lifecycle
    def drain(self, serve_queued: bool = True, max_ticks: int = 100_000):
        """Graceful shutdown: stop admissions, finish in-flight work.
        ``serve_queued=False`` additionally cancels everything still
        queued (only running slots complete)."""
        self._draining = True
        if not serve_queued:
            while self.scheduler.queue:
                req = self.scheduler.queue.popleft()
                req.state = RequestState.CANCELLED
                req.finish_time = self.scheduler.clock()
                self._close_request_spans(req)
        ticks = self.run_until_idle(max_ticks=max_ticks)
        self.metrics.flush()
        return ticks

    def _close_request_spans(self, req):
        """Cancellation bypasses the scheduler's _finish: close the
        request's open async spans so the trace stays balanced."""
        self.tracer.async_end("request/queued", req.request_id,
                              cat="serving")
        self.tracer.async_end("request", req.request_id, cat="serving",
                              args={"state": req.state.value,
                                    "tokens": len(req.tokens)})

    def shutdown(self, serve_queued: bool = True):
        """Drain, flush metrics, close monitor sinks (releases the CSV
        file handles MonitorMaster holds), write the configured telemetry
        exports (telemetry.trace_output / snapshot_output), stop the
        statusz server, and retract this engine's gauges from the shared
        telemetry counter space."""
        self.drain(serve_queued=serve_queued)
        m = self.metrics
        log_dist(f"serving: shut down after {m.ticks} ticks: "
                 f"{m.decode_ticks} decode steps read, {m.pipelined_ticks} of "
                 f"them dispatched behind the one before, {m.sampled_ticks} "
                 f"sampled, {m.dropped_rows} rows dropped; "
                 f"{m.kernel_prefills} of {m.prefills} prefills attended in "
                 f"the flash kernel", ranks=[0])
        took, products = m.record_grouped_matmuls()
        if products:
            log_dist(f"serving: {took} of the programs' {products} grouped "
                     f"matmuls took the rows kernel", ranks=[0])
        if self.monitor is not None:
            self.monitor.close()
        tcfg = self.config.telemetry
        if tcfg is not None and getattr(tcfg, "enabled", False):
            from ..telemetry.export import (write_chrome_trace,
                                            write_snapshot)
            try:
                if tcfg.trace_output:
                    write_chrome_trace(tcfg.trace_output, self.tracer)
                if tcfg.snapshot_output:
                    write_snapshot(tcfg.snapshot_output, self.tracer,
                                   extra={"serving": self.metrics.summary()})
            except OSError as e:
                log_dist(f"serving telemetry export failed: {e}", ranks=[0])
        if self.statusz is not None:
            self.statusz.close()
        if self._recorder is not None:
            self._recorder.close()
        # gauge lifecycle: a closed engine's queue depth / TTFT must not
        # survive in prometheus_dump() or /metrics as if it were live
        self.metrics.close()
        if self._compile_plane is not None and \
                getattr(self.engine, "compile_plane", None) \
                is self._compile_plane:
            self.engine.compile_plane = None   # detach from the shared
                                               # InferenceEngine
        self.tracer.release_counters(self)
        self.tracer.unwatch_gc(self)
        # last: where a jax.profiler trace was taken, what each instruction
        # of the pool programs is for outlives the engine, with the tracer
        self.tracer.keep_tables()

    def _traces_in_flight(self):
        """Trace ids of every request still moving through THIS replica
        (queued, awaiting handoff insert, or decoding) — embedded in this
        replica's flight-recorder bundles for cross-replica correlation."""
        sched = self.scheduler
        reqs = list(sched.queue)
        reqs += [req for _h, req in list(sched.handoff_queue)]
        reqs += [sched.pool.requests[s] for s in sched.pool.active_slots]
        reqs += list(sched.prefilling.values())
        return sorted({req.trace.trace_id for req in reqs
                       if req is not None and req.trace is not None})

    # ------------------------------------------------------------- statusz
    def _health_check(self):
        """Load-balancer liveness: unhealthy the moment drain starts (or
        a preemption landed), so routing stops BEFORE in-flight work
        finishes — the window where new submits would be rejected."""
        if self._preempt_drained:
            return False, "preempted (drained)"
        if self._draining:
            return False, "draining"
        return True, "serving"

    def _statusz_section(self) -> dict:
        out = {
            "queue_depth": self.queue_depth,
            "active_requests": self.active_requests,
            "num_slots": self.config.num_slots,
            "slot_occupancy": round(
                self.active_requests / self.config.num_slots, 3),
            "submitted": self.metrics.submitted,
            "completed": self.metrics.completed,
            "rejected": self.metrics.rejected,
            "timeouts": self.metrics.timeouts,
            "tokens_out": self.metrics.tokens_out,
            "draining": self._draining,
            "weights_version": self.weights_version,
        }
        if self.config.role != "unified":
            out["role"] = self.config.role
        if self.metrics.handoffs_in or self.metrics.handoffs_out:
            out["kv_handoffs_in"] = self.metrics.handoffs_in
            out["kv_handoffs_out"] = self.metrics.handoffs_out
        if self.metrics.handoffs_refused:
            out["kv_handoffs_refused"] = self.metrics.handoffs_refused
        sched = self.scheduler
        if sched.chunked is not None:
            out["chunked_prefill"] = (
                f"chunk_tokens={sched.chunked.chunk_tokens} "
                f"prefilling={len(sched.prefilling)}")
        if sched.queue.enabled:
            depths = sched.queue.depths()
            if depths:
                out["tenant_queues"] = " ".join(
                    f"{t}={n}" for t, n in sorted(depths.items()))
        tstatus = self.metrics.tenant_status()
        if len(tstatus) > 1 or (tstatus and "default" not in tstatus):
            for tenant, row in sorted(tstatus.items()):
                out[f"tenant_{tenant}"] = (
                    f"share={row['token_share']} "
                    f"ttft_p99={row['ttft_ms_p99']}ms "
                    f"burn={row['burn_rate']} done={row['completed']}")
        pc = self.scheduler.prefix_cache
        if pc is not None:
            for k, v in pc.stats().items():
                out[f"prefix_{k}"] = v
        if sched.spec is not None:
            out["speculative"] = (f"k={sched.spec.k} "
                                  f"draft={sched.draft.describe}")
            m = self.metrics
            if m.spec_ticks:
                out["spec_acceptance_ema"] = round(
                    m.spec_acceptance_ema or 0.0, 4)
                out["spec_tokens_per_tick"] = round(
                    m.spec_tokens_per_tick_ema or 0.0, 3)
                out["spec_draft/verify_ms"] = \
                    f"{m.spec_draft_ms:.2f} / {m.spec_verify_ms:.2f}"
        for name, ps in self.metrics.percentiles().items():
            if ps["n"]:
                out[f"{name}_p50/p95/p99"] = \
                    f'{ps["p50"]} / {ps["p95"]} / {ps["p99"]}'
        slo = self.metrics.slo_status()
        if any(m.get("target_ms") is not None
               for m in slo["metrics"].values()):
            out["slo_burn_rate"] = slo["burn_rate"]
        return out

    def _cost_section(self) -> dict:
        """The standalone engine's /statusz ``costs`` section: this
        replica's cost-ledger snapshot (a fleet's router folds these
        instead). Empty when the cost plane is off."""
        cost = self.scheduler.cost
        return cost.snapshot() if cost is not None else {}

    # ------------------------------------------------------------- inspection
    @property
    def weights_version(self) -> int:
        """The checkpoint ``weights_version`` this replica serves (0 =
        unversioned: fresh init or a pre-rollout checkpoint). Reported
        on /statusz and compared across replicas — and across KV handoff
        frames — by the rollout plane."""
        return int(getattr(self.engine, "weights_version", 0) or 0)

    @property
    def preempted(self) -> bool:
        """True once a preemption signal triggered the clean drain."""
        return self._preempt_drained

    @property
    def queue_depth(self) -> int:
        return len(self.scheduler.queue)

    @property
    def active_requests(self) -> int:
        """Requests holding a slot: decoding OR mid-chunked-prefill (a
        PREFILLING request is active work, not queue depth)."""
        return (len(self.scheduler.pool.active_slots) +
                len(self.scheduler.prefilling))

    def decode_executables(self) -> int:
        """Compiled-executable count of the fused decode step (the
        compile-once contract: stays 1 across differing prompt lengths),
        for THIS engine's pool flavor (fp vs quantized). A family that
        generates by diffusion over blocks: of its pass over blocks."""
        sched = self.scheduler
        kind = ("slot_block", self.config.num_slots,
                self.config.max_model_len, sched.block_fix) \
            if sched.block > 1 else \
            ("slot_decode", self.config.num_slots, self.config.max_model_len)
        return self.engine.slot_executables(
            *kind, quantized=sched.pool.quantized)
