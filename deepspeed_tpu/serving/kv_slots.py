"""Slot-based KV-cache pool.

The TPU answer to GPU paged attention: instead of dynamically growing
per-request caches (vLLM-style block tables — pointer chasing XLA cannot
compile to a fixed program), the pool is ONE statically-shaped cache
``[L, num_slots, max_model_len, H, hd]`` allocated at startup (token-major:
one token's K or V of all heads is one row, so a decode tick writes
``num_slots`` rows a layer and nothing else). A request is
admitted by claiming a free slot (prefill overwrites the slot's whole lane),
advanced by the fused all-slot decode step, and retired by returning the
slot to the free list — no shape ever changes, so the decode step compiles
exactly once.

``SlotPool`` owns the device arrays plus the host-side per-slot registers
(length counter, pending token, temperature) that the scheduler feeds to
``InferenceEngine.slot_decode_dispatch`` each tick. The scheduler keeps one
decode step in flight, so a slot has two lengths while its step is out:
``lengths`` counts the columns whose token was DELIVERED (what a donated or
handed-off lane holds), ``dispatched`` the columns a step was sent for (where
the next step writes).

A family that generates by diffusion over blocks (``block`` > 1:
``models/sdar.py``) advances a slot B columns at a time, over several passes
of the tick a block: ``lengths`` is then the columns whose FINAL keys and
values are written (whole blocks), ``dispatched`` the column of the block the
next pass is sent for, and the ``block_*`` registers hold the block itself
(``bind_block``, ``dispatch_block_arrays``).
"""

from typing import Collection, List, Optional

import numpy as np


class SlotPool:
    """Fixed pool of decode slots over one static KV cache."""

    def __init__(self, engine, num_slots: int, max_model_len: int,
                 quantize: bool = False, block: int = 1):
        self.engine = engine
        #: positions a pass advances a slot by (``GPT2Model.block_length``)
        self.block = block
        if block > 1:
            # the block a slot is denoising, as the host last read it, and
            # which of its positions are still masked; the pass of the
            # block at which each position was fixed
            self.block_ids = np.zeros((num_slots, block), np.int32)
            self.block_flags = np.zeros((num_slots, block), bool)
            self.block_fixed = np.zeros((num_slots, block), np.int32)
            # of the block the next pass is SENT for: the passes sent, and
            # the masked positions it opened with (B but for a first block,
            # which the prompt's last tokens open)
            self.block_sent = np.zeros((num_slots,), np.int32)
            self.block_open = np.zeros((num_slots,), np.int32)
            # the column a request's last token lies before, and whether
            # the last pass it needs has been sent
            self.block_end = np.zeros((num_slots,), np.int32)
            self.block_done = np.zeros((num_slots,), bool)
        self.num_slots = num_slots
        self.max_model_len = max_model_len
        self.quantized = bool(quantize)
        self.cache = engine.init_slot_pool(num_slots, max_model_len,
                                           quantize=self.quantized)
        # host-side slot registers, mirrored into device arrays each tick
        self.lengths = np.zeros((num_slots,), np.int32)   # tokens in cache
        # ... counting the step in flight: ``lengths``, or one more
        self.dispatched = np.zeros((num_slots,), np.int32)
        self.pending = np.zeros((num_slots,), np.int32)   # next token to feed
        self.temps = np.zeros((num_slots,), np.float32)
        # per-request sampling registers: top-k / top-p truncation and the
        # request seed — sampling keys derive ONLY from (seed, position),
        # so a failover replay regenerates the identical stream
        self.top_ks = np.zeros((num_slots,), np.int32)
        self.top_ps = np.ones((num_slots,), np.float32)
        self.seeds = np.zeros((num_slots,), np.int32)
        self.requests: List[Optional[object]] = [None] * num_slots
        self._free = list(range(num_slots - 1, -1, -1))   # pop() -> slot 0 first
        #: slots parked in the prefix cache: not free, not active — their
        #: lanes stay resident as reusable prefixes until LRU eviction
        self.cached: set = set()
        self.total_allocs = 0

    # ------------------------------------------------------------ lifecycle
    def alloc(self) -> Optional[int]:
        """Claim a free slot, or None when the pool is saturated."""
        if not self._free:
            return None
        slot = self._free.pop()
        self.total_allocs += 1
        return slot

    def free(self, slot: int):
        """Retire a slot back to the free list (EOS / max-tokens /
        timeout / prefix-cache eviction). The lane's stale K/V needs no
        scrubbing: the next prefill overwrites the whole lane and the
        decode mask never looks past the new request's length."""
        if self.requests[slot] is None and slot in self._free:
            return
        self.requests[slot] = None
        self.set_length(slot, 0)
        self.pending[slot] = 0
        self.temps[slot] = 0.0
        self.top_ks[slot] = 0
        self.top_ps[slot] = 1.0
        self.seeds[slot] = 0
        self.cached.discard(slot)
        self._free.append(slot)

    def retire_to_cache(self, slot: int):
        """Park a finished request's slot in the prefix cache: detached
        from decode (no request, nothing pending) but NOT freed — the
        lane's K/V stays resident as a reusable prefix. ``lengths`` keeps
        the valid-column count; the per-tick dummy decode write for a
        parked slot lands at column ``lengths[slot]`` — one column past
        the cached content, exactly where a reusing request prefills or
        decodes first, so the cached prefix itself is never clobbered. So
        does the row of a step that was in flight when the request ended
        (by EOS: learnt of one step late), which is computed and dropped."""
        self.requests[slot] = None
        self.dispatched[slot] = self.lengths[slot]
        self.pending[slot] = 0
        self.temps[slot] = 0.0
        self.top_ks[slot] = 0
        self.top_ps[slot] = 1.0
        self.seeds[slot] = 0
        self.cached.add(slot)

    def bind(self, slot: int, request, length: int, first_token: int,
             sampling=None):
        """Attach an admitted request to its slot after prefill.
        ``sampling`` is the request's SamplingParams (or None for the
        greedy defaults) — its temperature/top-k/top-p/seed become this
        slot's per-tick registers."""
        self.requests[slot] = request
        self.set_length(slot, length)
        self.pending[slot] = first_token
        self.temps[slot] = getattr(sampling, "temperature", 0.0)
        self.top_ks[slot] = getattr(sampling, "top_k", 0)
        self.top_ps[slot] = getattr(sampling, "top_p", 1.0)
        self.seeds[slot] = getattr(sampling, "seed", 0)

    def bind_block(self, slot: int, request, start: int, held, end: int,
                   sampling=None):
        """``bind`` for a family that generates by blocks: the lane holds
        the final keys and values of columns below ``start`` (the prompt's
        whole blocks), the ``held`` tokens (the prompt's last
        ``len % B``) open the block at ``start`` beside masked positions,
        and the request's last token lies before column ``end``."""
        self.bind(slot, request, start, 0, sampling)
        held = np.asarray(held, np.int32)
        self.block_ids[slot] = 0
        self.block_ids[slot, :held.size] = held
        self.block_flags[slot] = np.arange(self.block) >= held.size
        self.block_fixed[slot] = 0
        self.block_sent[slot] = 0
        self.block_open[slot] = self.block - held.size
        self.block_end[slot] = end
        self.block_done[slot] = False

    def next_block(self, slot: int):
        """The writing pass of a slot's block was read: its columns are
        final, and the block the host holds is the next one, all masked."""
        self.lengths[slot] += self.block
        self.block_ids[slot] = 0
        self.block_flags[slot] = True
        self.block_fixed[slot] = 0

    def set_length(self, slot: int, length: int):
        """The valid columns of a slot no step is in flight for (free,
        parked, mid-prefill, just bound): a dummy row lands one past
        them."""
        self.lengths[slot] = self.dispatched[slot] = length

    # ------------------------------------------------------------ queries
    def slot_nbytes(self) -> int:
        """HBM bytes ONE slot pins in this pool: total pool footprint /
        num_slots, summed host-side over the cache pytree's leaves (no
        device sync). Int8-aware by construction — a quantized pool's
        leaves are the int8 q + f32 scales the device actually holds,
        the same bytes the HBM ledger's ``kv_slots`` role reports. The
        cost plane multiplies this by slot residency for per-request
        HBM-byte-seconds."""
        from ..telemetry.costplane import tree_nbytes
        return tree_nbytes(self.cache) // max(1, self.num_slots)

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> List[int]:
        return [s for s in range(self.num_slots)
                if self.requests[s] is not None]

    @property
    def utilization(self) -> float:
        return 1.0 - len(self._free) / self.num_slots

    def decode_arrays(self):
        """(toks, positions, temps, top_ks, top_ps, seeds) device-feed
        arrays for one fused decode/verify step. Free slots carry dummy
        values (token 0 at column 0, greedy); their lane writes land in a
        lane the next prefill fully overwrites, and their sampled tokens
        are dropped by the scheduler."""
        return (self.pending.copy(), self.lengths.copy(), self.temps.copy(),
                self.top_ks.copy(), self.top_ps.copy(), self.seeds.copy())

    def dispatch_arrays(self, slots: List[int], fed: Collection[int]):
        """``decode_arrays`` for the step the scheduler sends next, which
        advances ``slots`` alone, and ``from_host`` [S] behind them: the
        rows whose token is ``toks[s]``, all but the slots in ``fed``, whose
        token the step in flight holds on the device (none after an idle
        pool; a slot bound since that step was sent is not among them).
        Every slot that takes no part carries a dummy row (token 0, greedy)
        at the column one past what it holds, clamped into the lane: a
        bound slot left out because its request ends with the step in
        flight keeps its lane whole for ``_release_slot``. Counts the step
        as dispatched."""
        live = np.zeros((self.num_slots,), bool)
        live[slots] = True
        from_host = np.ones_like(live)
        from_host[[s for s in slots if s in fed]] = False
        positions = np.minimum(self.dispatched, self.max_model_len - 1)
        self.dispatched[slots] += 1
        return (np.where(live, self.pending, 0), positions,
                np.where(live, self.temps, np.float32(0)),
                np.where(live, self.top_ks, 0),
                np.where(live, self.top_ps, np.float32(1)),
                np.where(live, self.seeds, 0), from_host)

    def dispatch_block_arrays(self, slots: List[int], fed: Collection[int],
                              fix: int):
        """``dispatch_arrays`` for a pass over blocks: (ids [S, B], flags
        [S, B], positions, temps, top_ks, top_ps, seeds, from_host) and
        ``{slot: (pass of its block, its last unmasking pass, its writing
        pass)}`` for ``slots``. A pass fixes ``fix`` positions a slot, so a
        block that opened with m masked positions takes ``ceil(m / fix)``
        unmasking passes and one writing pass: which pass this is follows
        from how many were sent, never from the pass in flight. A row of
        ``fed`` takes its block from that pass on the device; the pass
        after a writing pass in flight opens the next block, all masked,
        from here; every other row takes the block as the host last read
        it. A slot that takes no part carries a dummy row (nothing flagged)
        at the block past what it holds, clamped into the lane. Counts the
        pass as dispatched: a writing pass moves the slot B columns on,
        and the last unmasking pass of the block that holds the request's
        last token ends its part (``block_done``: no writing pass)."""
        b = self.block
        live = np.zeros((self.num_slots,), bool)
        live[slots] = True
        ids = np.zeros((self.num_slots, b), np.int32)
        flags = np.zeros((self.num_slots, b), bool)
        from_host = np.ones_like(live)
        positions = np.minimum(self.dispatched, self.max_model_len - b)
        passes = {}
        for s in slots:
            sent = int(self.block_sent[s])
            unmasking = -(-int(self.block_open[s]) // fix)
            if s in fed and sent:
                from_host[s] = False
            elif s in fed:
                flags[s] = True
            else:
                ids[s], flags[s] = self.block_ids[s], self.block_flags[s]
            passes[s] = (sent, sent == unmasking - 1, sent == unmasking)
            if sent == unmasking:
                self.dispatched[s] += b
                self.block_sent[s], self.block_open[s] = 0, b
            else:
                self.block_sent[s] = sent + 1
                self.block_done[s] = sent == unmasking - 1 and \
                    positions[s] + b >= self.block_end[s]
        return (ids, flags, positions,
                np.where(live, self.temps, np.float32(0)),
                np.where(live, self.top_ks, 0),
                np.where(live, self.top_ps, np.float32(1)),
                np.where(live, self.seeds, 0), from_host), passes
