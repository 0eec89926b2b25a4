"""Sharded MoE: gating + dispatch/combine.

TPU-native re-design of the reference gating/dispatch layer
(deepspeed/moe/sharded_moe.py:179 ``top1gating``, :277 ``top2gating``, :420
``MOELayer`` with the ``_AllToAll`` autograd function at :90). The reference
dispatches tokens with an explicit NCCL all-to-all inside an autograd.Function;
here dispatch/combine are einsums against a one-hot dispatch tensor with
sharding constraints — expert tensors are sharded over the ``expert`` mesh
axis, token tensors over the data axes, and GSPMD lowers the resharding between
them to an ICI all-to-all (differentiable for free, no custom autograd).

Gating semantics follow the reference (which follows GShard):
  - top-1 / top-2 (generalized to top-k) with static per-expert capacity
    ``ceil(k * S / E * capacity_factor)`` clamped to ``min_capacity``
  - load-balance aux loss  l_aux = E * sum_e mean_s(gates[s,e]) * mean_s(mask[s,e])
  - noisy gating: 'Jitter' (input multiplied by uniform noise) and 'RSample'
    (logits + gaussian) policies
  - token dropping by intra-expert position (cumsum order), or
    ``drop_tokens=False`` → capacity = S (nothing dropped, more padding)
  - optional random token selection (``use_rts``) for drop fairness

KNOWN GAP (ROADMAP item 3, kept visible by ds_tpu_lint): the GSPMD
all-to-all behind the dispatch/combine einsums bypasses the
compression-aware comm dispatch — expert traffic gets no int8/fp8 wire
policy and no comm_stats() accounting. The HLO dispatch-conformance
auditor (HLO006) flags it on the ``moe_step`` artifact; the waiver in
``lint_waivers.json`` carries the tracking note and must be deleted
when dispatch/combine are routed through ``comm/comm.py`` under an
explicit ep shard_map.
"""

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.constraints import active_mesh, maybe_constraint
from ..parallel.topology import DATA_AXIS, EXPERT_AXIS


class MoeMetrics:
    """Owner-scoped ``dstpu_moe_*`` gauge family: per-expert load +
    capacity-factor overflow telemetry (the ROADMAP item 3 seed —
    expert-load imbalance is a goodput bucket waiting to exist, and the
    first step is measuring it).

    HOST-SIDE ONLY: ``record()`` takes the *concrete* ``exp_counts``
    vector a step returned (``np.asarray`` it after the step — never
    inside traced code, which the AST002 lint would flag) plus the
    static per-expert capacity, and mirrors:

    - ``moe/expert_load_max`` / ``moe/expert_load_mean`` — tokens routed
      to the hottest expert vs the mean (pre-capacity-drop counts);
    - ``moe/load_imbalance`` — max/mean ratio (1.0 = perfectly balanced;
      E = everything on one expert);
    - ``moe/dropped_token_fraction`` — routed tokens beyond capacity ÷
      routed tokens this record (the capacity-factor overflow rate);
    - ``moe/overflow_tokens`` / ``moe/overflow_steps`` — cumulative
      overflow counters;
    - ``moe/dispatch_bytes_total`` / ``moe/combine_bytes_total`` /
      ``moe/wire_bytes_per_step`` — the logical all-to-all payloads
      behind the dispatch/combine einsums (``record_wire``, computed
      host-side from static shapes: GSPMD emits the collective, so no
      comm-dispatch accounting sees it — this seed is the cost plane's
      handle on expert-parallel wire traffic until the einsums route
      through ``comm/comm.py``).

    Gauges carry ``owner=`` this instance and are retracted by
    ``close()`` — the PR-4 gauge-lifecycle contract
    (test_metrics_lifecycle.py enforces both)."""

    def __init__(self, tracer=None):
        from ..telemetry.trace import get_tracer
        self.tracer = tracer or get_tracer()
        self.records = 0
        self.overflow_tokens = 0
        self.overflow_steps = 0
        self.dispatch_bytes = 0
        self.combine_bytes = 0
        self.wire_records = 0
        self._closed = False

    def record(self, exp_counts, capacity: int,
               step: Optional[int] = None) -> Dict[str, float]:
        """Attribute one step's routing. ``exp_counts`` is [E] (or any
        leading dims summed away, e.g. [layers, E]) of tokens routed per
        expert BEFORE the capacity drop; ``capacity`` is the static slot
        count per expert the dispatch tensor enforced."""
        import numpy as np

        counts = np.asarray(exp_counts, dtype=np.float64)
        counts = counts.reshape(-1, counts.shape[-1]).sum(axis=0)
        routed = float(counts.sum())
        n_experts = max(1, counts.shape[0])
        mean = routed / n_experts
        dropped = float(np.maximum(counts - float(capacity), 0.0).sum()) \
            if capacity else 0.0
        self.records += 1
        if dropped > 0:
            self.overflow_tokens += int(dropped)
            self.overflow_steps += 1
        out = {
            "expert_load_max": float(counts.max()) if routed else 0.0,
            "expert_load_mean": mean,
            "load_imbalance":
                float(counts.max()) / mean if mean > 0 else 0.0,
            "dropped_token_fraction": dropped / routed if routed else 0.0,
            "overflow_tokens": float(self.overflow_tokens),
            "overflow_steps": float(self.overflow_steps),
        }
        for name, val in out.items():
            self.tracer.set_counter(f"moe/{name}", round(val, 6),
                                    step, owner=self)
        return out

    def record_wire(self, *, capacity: int, num_experts: int,
                    model_dim: int, itemsize: int = 4,
                    step: Optional[int] = None) -> Dict[str, float]:
        """Attribute one step's LOGICAL dispatch/combine wire traffic.
        Host-side arithmetic over static shapes — the dispatch einsum
        reshards [S, M] tokens into expert-major [E, C, M] (the
        all-to-all GSPMD emits) and combine moves the same [E, C, M]
        back, so each direction's payload is E x C x M x itemsize
        regardless of how many routed tokens actually filled the
        capacity slots (the collective moves the padded tensor)."""
        payload = int(num_experts) * int(capacity) * int(model_dim) \
            * int(itemsize)
        self.dispatch_bytes += payload
        self.combine_bytes += payload
        self.wire_records += 1
        out = {
            "dispatch_bytes_total": float(self.dispatch_bytes),
            "combine_bytes_total": float(self.combine_bytes),
            "wire_bytes_per_step": float(2 * payload),
        }
        for name, val in out.items():
            self.tracer.set_counter(f"moe/{name}", val, step, owner=self)
        return out

    def summary(self) -> Dict[str, Any]:
        """Statusz/bundle view of the cumulative overflow counters."""
        return {"records": self.records,
                "overflow_tokens": self.overflow_tokens,
                "overflow_steps": self.overflow_steps,
                "dispatch_bytes": self.dispatch_bytes,
                "combine_bytes": self.combine_bytes}

    def close(self):
        """Retract this family from the shared counter space — a closed
        MoE run's imbalance must not read as live. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.tracer.release_counters(self)


def _capacity(num_tokens: int, num_experts: int, k: int,
              capacity_factor: float, min_capacity: int,
              drop_tokens: bool) -> int:
    if not drop_tokens:
        return num_tokens
    cap = int(math.ceil(k * num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def topk_gating(logits: jnp.ndarray,
                k: int,
                capacity_factor: float,
                min_capacity: int = 4,
                drop_tokens: bool = True,
                use_rts: bool = True,
                rng: Optional[jax.Array] = None,
                train: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray, jnp.ndarray]:
    """Compute combine/dispatch tensors for top-k routing.

    logits: [S, E] raw gate logits.
    Returns (l_aux, combine [S,E,C] f32, dispatch [S,E,C] bool,
    exp_counts [E] i32 — tokens routed per expert before capacity drop).
    """
    s, e = logits.shape
    c = _capacity(s, e, k, capacity_factor, min_capacity, drop_tokens)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    combine = jnp.zeros((s, e, c), jnp.float32)
    dispatch = jnp.zeros((s, e, c), jnp.bool_)
    # running per-expert fill count, so choice 2 slots come after choice 1
    fill = jnp.zeros((e,), jnp.int32)
    # -inf-mask chosen experts on the LOGITS so later choices can never
    # re-select them (reference top2gating: logits_except1 masked_fill -inf;
    # zeroing softmax gates instead re-picks index 0 once gates underflow)
    masked_logits = logits.astype(jnp.float32)
    l_aux = jnp.float32(0.0)
    exp_counts = jnp.zeros((e,), jnp.int32)
    gate_sum = jnp.zeros((s,), jnp.float32)
    picks = []

    for choice in range(k):
        idx = jnp.argmax(masked_logits, axis=-1)                   # [S]
        mask = _one_hot(idx, e)                                    # [S, E]
        if choice == 0:
            # aux loss uses the FIRST-choice assignment (reference
            # top2gating computes it from mask1 only, sharded_moe.py:294)
            me = jnp.mean(gates, axis=0)
            ce = jnp.mean(mask, axis=0)
            l_aux = jnp.sum(me * ce) * e
        exp_counts = exp_counts + jnp.sum(mask, axis=0).astype(jnp.int32)

        if use_rts and train and rng is not None and drop_tokens:
            # random-token-selection: randomize drop priority instead of
            # favoring early positions (reference use_rts, sharded_moe.py:208);
            # salt offset keeps this stream disjoint from layer dropout keys
            prio = jax.random.uniform(jax.random.fold_in(rng, 1000 + choice), (s,))
            order = jnp.argsort(prio)
            inv = jnp.argsort(order)
            mask_sorted = mask[order]
            loc_sorted = jnp.cumsum(mask_sorted, axis=0) - mask_sorted
            locations = loc_sorted[inv]
        else:
            locations = jnp.cumsum(mask, axis=0) - mask            # [S, E]
        locations = locations + fill[None, :]
        fill = fill + jnp.sum(mask, axis=0).astype(jnp.int32)

        pos = jnp.sum(locations * mask, axis=-1).astype(jnp.int32)  # [S]
        keep = pos < c
        mask = mask * keep[:, None]
        gate_val = jnp.sum(gates * mask, axis=-1)                   # [S]
        picks.append((mask, pos, gate_val))
        gate_sum = gate_sum + gate_val
        # exclude chosen expert from the next round
        masked_logits = jnp.where(_one_hot(idx, e) > 0, -jnp.inf, masked_logits)

    # top-1 uses the raw gate probability as combine weight (reference
    # top1gating); for k>=2 the picked gates renormalize to sum to 1
    # (reference top2gating denom, sharded_moe.py:323)
    if k == 1:
        denom = jnp.ones_like(gate_sum)
    else:
        denom = jnp.maximum(gate_sum, jnp.finfo(jnp.float32).eps)
    for mask, pos, gate_val in picks:
        w = gate_val / denom                                        # [S]
        oh_pos = _one_hot(jnp.where(pos < c, pos, 0), c)            # [S, C]
        contrib = (w[:, None] * mask)[:, :, None] * oh_pos[:, None, :]
        combine = combine + contrib
        dispatch = dispatch | (contrib > 0)

    return l_aux, combine, dispatch, exp_counts


def topk_route(logits: jnp.ndarray, k: int,
               renormalize: Optional[bool] = None, score: str = "softmax",
               select_bias: Optional[jnp.ndarray] = None,
               renorm_eps: Optional[float] = None) -> Tuple[jnp.ndarray,
                                                            jnp.ndarray]:
    """Capacity-free top-k routing: every token keeps all its picks.
    ``logits`` [S, E] → (weights [S, k] f32, experts [S, k] i32, best
    first). The weights are the scores (float32, over all E: ``score``
    ``"softmax"``, or ``"sigmoid"``, each expert's own) at the picked
    experts; ``renormalize`` divides them by their sum over the k picks.
    ``None`` is the gate semantics of ``topk_gating`` (DeepSpeed's: raw
    probability for k = 1, renormalized for k >= 2); OLMoE
    (``norm_topk_prob`` false) passes ``False``. ``select_bias`` [E] enters
    the CHOICE and not the weight (the experts are the top k of score +
    bias, weighted by the score alone: the load-balancing bias of
    DeepSeek-V3's router, LFM2's ``use_expert_bias``); ``renorm_eps`` is
    added to the sum the picks are divided by (``None``: the sum is held
    over float32's epsilon instead)."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown router score {score!r}")
    with jax.named_scope("router"):
        logits = logits.astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        if select_bias is None:
            w, idx = lax.top_k(gates, k)
        else:
            _, idx = lax.top_k(gates + select_bias.astype(jnp.float32), k)
            w = jnp.take_along_axis(gates, idx, axis=-1)
        if renormalize is None:
            renormalize = k > 1
        if renormalize:
            total = jnp.sum(w, axis=-1, keepdims=True)
            w = w / (jnp.maximum(total, jnp.finfo(jnp.float32).eps)
                     if renorm_eps is None else total + renorm_eps)
        return w, idx.astype(jnp.int32)


class TopKGate:
    """Linear gate + top-k routing (reference ``TopKGate``,
    sharded_moe.py:377): holds the [M, E] projection and the routing
    hyperparameters. Functional: init/apply."""

    def __init__(self, model_dim: int, num_experts: int, k: int = 1,
                 capacity_factor: float = 1.0,
                 eval_capacity_factor: float = 1.0,
                 min_capacity: int = 4,
                 noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True,
                 use_rts: bool = True,
                 score: str = "softmax",
                 select_bias: bool = False,
                 renorm_eps: Optional[float] = None,
                 scale: float = 1.0):
        assert k >= 1
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.use_rts = use_rts
        # the routed serving path's (``MOELayer.apply_routed`` →
        # ``topk_route``): the score function, a ``bias`` [E] leaf that
        # enters the choice alone, the epsilon of the renormalisation and a
        # factor on the weights. The capacity-based ``apply`` is softmax.
        self.score = score
        self.select_bias = select_bias
        self.renorm_eps = renorm_eps
        self.scale = scale

    def init(self, rng):
        scale = 1.0 / math.sqrt(self.model_dim)
        params = {"wg": jax.random.uniform(
            rng, (self.model_dim, self.num_experts), jnp.float32,
            -scale, scale)}
        if self.select_bias:
            params["bias"] = jnp.zeros((self.num_experts,), jnp.float32)
        return params

    def apply(self, params, x, rng=None, train=True):
        """x: [S, M] → (l_aux, combine [S,E,C], dispatch [S,E,C], counts)."""
        inp = x.astype(jnp.float32)
        if train and self.noisy_gate_policy == "Jitter" and rng is not None:
            noise = jax.random.uniform(jax.random.fold_in(rng, 17),
                                       inp.shape, jnp.float32, 0.99, 1.01)
            inp = inp * noise
        logits = inp @ params["wg"]
        if train and self.noisy_gate_policy == "RSample" and rng is not None:
            logits = logits + jax.random.normal(
                jax.random.fold_in(rng, 19), logits.shape)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        return topk_gating(logits, self.k, cf,
                           min_capacity=self.min_capacity,
                           drop_tokens=self.drop_tokens,
                           use_rts=self.use_rts, rng=rng, train=train)


class MOELayer:
    """Dispatch → experts → combine (reference ``MOELayer``,
    sharded_moe.py:420).

    expert params carry a leading [E] dim sharded over the ``expert`` mesh
    axis; dispatch/combine einsums reshard tokens [S, ...] ↔ expert-major
    [E, C, ...] and GSPMD emits the all-to-all the reference performs
    explicitly (``_AllToAll.apply``, sharded_moe.py:90)."""

    def __init__(self, gate: TopKGate, experts, use_sharding_constraints=True,
                 held=None, shared=None):
        """``held = (offset, count)``: this layer holds experts ``offset ...
        offset + count - 1`` of the ``gate.num_experts`` the router scores
        (one chip's share under expert parallelism; ``experts`` is built for
        ``count``). ``apply_routed`` then routes over all of them and
        computes what its own give; what the absent ones would have added
        is left out, and nothing stands in for their chips. ``None``: all.
        ``shared``: an expert module of ONE expert that every token goes
        through with weight 1, beside the routed ones (``"shared"`` in the
        parameters; under expert parallelism every chip holds it, and its
        output is counted once). Both are ``apply_routed``'s alone: the
        capacity dispatch ``apply`` raises."""
        self.gate = gate
        self.experts = experts
        self.use_sharding_constraints = use_sharding_constraints
        if held is not None:
            offset, count = held
            if not (0 <= offset and count >= 1 and
                    offset + count <= gate.num_experts and
                    count == experts.num_experts):
                raise ValueError(
                    f"held={held}: experts offset ... offset + count - 1 "
                    f"must lie within the router's {gate.num_experts} and "
                    f"the expert module hold count={experts.num_experts}")
            if (offset, count) == (0, gate.num_experts):
                held = None
        self.held = held
        self.shared = shared

    def init(self, rng):
        gate_rng, exp_rng = jax.random.split(rng)
        params = {"gate": self.gate.init(gate_rng),
                  "experts": self.experts.init(exp_rng)}
        if self.shared is not None:
            params["shared"] = jax.tree.map(
                lambda a: a[0], self.shared.init(jax.random.fold_in(rng, 2)))
        return params

    def _apply_shared(self, params, xs):
        """The shared expert's output for rows ``xs`` [S, M]: its leaves
        are one expert's, without the [E] axis."""
        with jax.named_scope("shared_expert"):
            one = jax.tree.map(lambda a: a[None], params["shared"])
            return self.shared.apply(one, xs[None])[0]

    def apply(self, params, x, rng=None, train=True):
        """x: [..., M] (any leading dims) → (y [..., M], l_aux, exp_counts)."""
        if self.held is not None or self.shared is not None:
            raise NotImplementedError(
                "MOELayer.apply (the capacity dispatch) knows neither a "
                "share of the experts (held) nor a shared expert: both are "
                "apply_routed's")
        lead = x.shape[:-1]
        m = x.shape[-1]
        xs = x.reshape(-1, m)                                      # [S, M]
        l_aux, combine, dispatch, exp_counts = self.gate.apply(
            params["gate"], xs, rng=rng, train=train)

        # tokens → expert-major [E, C, M]; this einsum's output sharding
        # (expert axis) vs input sharding (data axes) is the all-to-all.
        expert_in = jnp.einsum("sec,sm->ecm",
                               dispatch.astype(x.dtype), xs)
        if self.use_sharding_constraints:
            expert_in = maybe_constraint(expert_in, EXPERT_AXIS, None, None)
        expert_out = self.experts.apply(params["experts"], expert_in,
                                        rng=rng, train=train)      # [E, C, M]
        if self.use_sharding_constraints:
            expert_out = maybe_constraint(expert_out, EXPERT_AXIS, None, None)
        y = jnp.einsum("sec,ecm->sm", combine.astype(x.dtype), expert_out)
        if self.use_sharding_constraints:
            y = maybe_constraint(y, (DATA_AXIS, EXPERT_AXIS), None)
        return y.reshape(*lead, m), l_aux, exp_counts

    def take_whole(self, stacked):
        """For a layer scan over L such layers, split their params
        (``stacked``: every leaf [L, ...]) into (what the scan slices a
        layer at a time, the experts' matmul leaves its body closes over
        whole and hands ``apply_routed`` as ``stacked=(whole, layer)``).
        Sliced by the scan, each [E, ...] leaf is copied out of the stack
        every layer of every call before the grouped matmul may read it:
        on the chip twice the time of the matmuls themselves (PERF.md, PR
        34). Decided on what is seen here, and ``(stacked, None)`` keeps
        the slice: a leaf that is no plain array (an int8
        ``QuantizedWeight`` dequantises into a fresh buffer anyway, and L
        layers' worth of it a layer would be L times the work), and a
        mesh whose ``expert`` axis shards the E of [L, E, ...], which the
        merged [L * E] axis can not carry."""
        names = self.experts.matmul_leaves
        experts = stacked["experts"]
        mesh = active_mesh()
        if (mesh is not None and mesh.shape.get(EXPERT_AXIS, 1) > 1) or \
                not all(isinstance(experts[n], jax.Array) for n in names):
            return stacked, None
        rest = {k: v for k, v in experts.items() if k not in names}
        return {**stacked, "experts": rest}, {n: experts[n] for n in names}

    def apply_routed(self, params, x, renormalize=None, stacked=None):
        """Routed, dropless serving path (the reference's MoE-inference
        semantics, reference ops/transformer/inference/moe_inference.py:160
        — route every token, drop nothing, no capacity): router matmul and
        score in float32, ``topk_route`` with the gate's score function,
        selection bias and epsilon, the token-expert pairs sorted
        by expert, the experts' matmuls as grouped matmuls over the groups
        (``experts.apply_grouped``), un-sorted and combined in the
        activations' type. Costs the routed FLOPs and holds no [S, E, C]
        tensor. Same return shape as apply(): l_aux is 0 (no load-balance
        objective when serving); exp_counts are the rows each expert this
        layer holds got ([E]; ``held``: [count]). ``stacked``: ``(whole,
        layer)`` from ``take_whole``; the experts' matmul leaves are then
        read from ``whole`` at ``layer`` and ``params`` holds the rest.

        With ``held``, the router still scores and picks among all E; a
        pair whose expert is not held sorts after every held one, belongs
        to no group of the grouped matmuls (which visit the rows of their
        groups and no others) and is combined as zero. The shared expert,
        where there is one, is added once a token after the combine."""
        lead = x.shape[:-1]
        m = x.shape[-1]
        xs = x.reshape(-1, m)                                      # [S, M]
        gate = self.gate
        k, e = gate.k, gate.num_experts
        logits = xs.astype(jnp.float32) @ \
            params["gate"]["wg"].astype(jnp.float32)
        w, idx = topk_route(logits, k, renormalize, gate.score,
                            params["gate"].get("bias"),
                            gate.renorm_eps)                       # [S, k]
        if gate.scale != 1.0:
            w = w * gate.scale
        flat = idx.reshape(-1)                                     # [S*k]
        if self.held is not None:
            offset, e = self.held
            flat = flat - offset        # its index among the held, or
            flat = jnp.where((flat >= 0) & (flat < e), flat, e)    # absent
        order = jnp.argsort(flat, stable=True)   # pair ids, by expert
        # an absent pair's index lies past the end and is dropped
        exp_counts = jnp.zeros((e,), jnp.int32).at[flat].add(1)
        experts, layer = params["experts"], None
        if stacked is not None:
            whole, layer = stacked
            experts = {**experts, **whole}
        with jax.named_scope("moe_experts"):
            expert_out = self.experts.apply_grouped(
                experts, xs[order // k], exp_counts, flat[order],
                layer=layer)                                       # [S*k, M]
        if self.held is not None:       # rows of no group: whatever lay there
            rows = jnp.arange(order.shape[0])[:, None] < exp_counts.sum()
            expert_out = jnp.where(rows, expert_out, 0)
        # un-sort: pair (s, j) sits at row inverse[s*k + j]
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        picked = expert_out[inverse].reshape(-1, k, m)
        y = jnp.einsum("skm,sk->sm", picked, w.astype(x.dtype))
        if self.shared is not None:
            y = y + self._apply_shared(params, xs)
        return y.reshape(*lead, m), jnp.float32(0.0), exp_counts
