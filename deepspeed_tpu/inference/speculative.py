"""Speculative decoding over the slot pool — draft runtimes + sampling.

The serving decode loop emits one token per compiled tick; speculation
turns each tick into ``accepted + 1`` tokens for roughly two dispatches:
a cheap DRAFT model proposes K tokens per slot (the whole K-step
autoregressive proposal is ONE compiled ``lax.scan`` —
``InferenceEngine.slot_draft_propose``), then the target model verifies
all K in ONE batched, statically-shaped forward
(``GPT2Model.verify_with_slots`` via ``slot_verify_step``), accepting
the longest matching prefix and rolling rejected KV columns back INSIDE
the compiled step.

**Verification is exact-match against the target's own deterministic
per-position sample.** Every emitted token — greedy or sampled — equals
what the non-speculative path would emit at that position, because both
paths sample with the same key, derived ONLY from ``(request seed,
cache column)`` (never tick or slot index). That buys three guarantees
the fleet already depends on:

- the token stream is **bitwise identical with speculation on or off**
  (the draft can only accelerate, never change, the output);
- a failover survivor **replays the identical stream** — the router's
  delivered-position dedup still yields every streamed position exactly
  once, now for sampled requests too;
- the draft maximizes acceptance by sampling with the SAME per-position
  key (a coupling: two similar distributions pushed through one uniform
  draw usually pick the same token).

The trade: at high temperature, exact-match acceptance is lower than
lossless rejection-sampling speculation. At/near greedy — the serving
common case — they coincide.

Draft flavors (``speculative.draft``):

- ``mode="self"`` — **self-speculative fallback**: the draft is the
  target's own first ``layers`` blocks (a zero-copy slice of the
  stacked ``blocks`` leaves) under the target's final norm + unembed —
  no second model has to fit HBM.
- ``mode="model"`` — a separate small GPT-2 config (own params; same
  vocab) for when a trained draft exists.
"""

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["DraftRuntime", "build_draft", "draft_key", "row_keys",
           "sample_rows"]


def draft_key(cfg) -> tuple:
    """Hashable identity of a draft config — the engine caches one
    DraftRuntime (params included) per distinct key, so N co-resident
    replicas with one shared InferenceEngine also share draft weights."""
    return ("self" if cfg.mode == "self" else "model",
            int(getattr(cfg, "layers", 0)), int(getattr(cfg, "n_layer", 0)),
            int(getattr(cfg, "n_embd", 0)), int(getattr(cfg, "n_head", 0)),
            int(getattr(cfg, "seed", 0)))


@dataclasses.dataclass
class DraftRuntime:
    """A draft model ready to propose: spec + params + shardings."""
    model: Any
    params: Any
    param_shardings: Any
    mode: str
    layers: int
    key: tuple

    @property
    def describe(self) -> str:
        cfg = self.model.config
        if self.mode == "self":
            return f"self(layers={self.layers})"
        return f"model({cfg.n_layer}L/{cfg.n_embd}d)"


def _draft_shardings(engine, model):
    from ..runtime.zero.partition import ZeroShardingPlanner
    rules = model.partition_rules() if hasattr(model, "partition_rules") \
        else []
    planner = ZeroShardingPlanner(engine.mesh_manager, stage=0, rules=rules)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return planner.param_shardings(shapes)


def build_draft(engine, cfg) -> DraftRuntime:
    """Build a DraftRuntime for ``engine`` from a DraftConfig-shaped
    object (``mode``/``layers``/``n_layer``/``n_embd``/``n_head``/
    ``seed``). ``self`` mode slices the target's stacked blocks —
    requires fp serving weights (weight-only int8 params have no layer
    axis to slice)."""
    target = engine.module
    tcfg = target.config
    mode = getattr(cfg, "mode", "self")
    if mode == "self":
        if getattr(engine, "_quant", None) is not None:
            raise ValueError(
                "self-speculative draft slices the target's stacked block "
                "leaves, which weight-only int8 serving params do not "
                "expose; serve fp weights or configure draft.mode='model'")
        layers = int(getattr(cfg, "layers", 0)) or max(1, tcfg.n_layer // 2)
        if not 1 <= layers <= tcfg.n_layer:
            raise ValueError(
                f"speculative.draft.layers={layers} outside "
                f"[1, {tcfg.n_layer}]")
        model = type(target)(dataclasses.replace(tcfg, n_layer=layers))
        shardings = _draft_shardings(engine, model)

        def slice_params(p):
            out = {k: v for k, v in p.items() if k != "blocks"}
            out["blocks"] = jax.tree.map(lambda leaf: leaf[:layers],
                                         p["blocks"])
            return out

        with engine.mesh:
            params = jax.jit(slice_params,
                             out_shardings=shardings)(engine.params)
        return DraftRuntime(model=model, params=params,
                            param_shardings=shardings, mode="self",
                            layers=layers, key=draft_key(cfg))
    if mode != "model":
        raise ValueError(f"speculative.draft.mode must be self|model, "
                         f"got {mode!r}")
    over = {}
    for name in ("n_layer", "n_embd", "n_head"):
        val = int(getattr(cfg, name, 0))
        if val:
            over[name] = val
    dcfg = dataclasses.replace(tcfg, **over)
    if dcfg.n_embd % dcfg.n_head:
        raise ValueError(
            f"draft n_embd={dcfg.n_embd} not divisible by "
            f"n_head={dcfg.n_head}")
    model = type(target)(dcfg)     # same family => same vocab/positions
    shardings = _draft_shardings(engine, model)
    rng = jax.random.PRNGKey(int(getattr(cfg, "seed", 0)))
    with engine.mesh:
        params = jax.jit(
            lambda r: jax.tree.map(engine._cast_leaf, model.init(r)),
            out_shardings=shardings)(rng)
    return DraftRuntime(model=model, params=params,
                        param_shardings=shardings, mode="model",
                        layers=dcfg.n_layer, key=draft_key(cfg))


# --------------------------------------------------------------------------
# deterministic per-request sampling
# --------------------------------------------------------------------------

def row_keys(seeds, cols):
    """One PRNG key per row, derived ONLY from ``(seed, cache column)``
    — the replay-determinism contract: a failover survivor (or the same
    request at a different tick/slot) regenerates the identical key for
    every token position. seeds [S] int32; cols [S] int32 -> [S] keys."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.PRNGKey(s), c))(
            seeds, cols)


def sample_rows(logits, temps, top_ks, top_ps, keys, vocab):
    """Per-row greedy / temperature / top-k / top-p sampling with
    per-row keys. logits [S, V_padded]; temps/top_ps f32 [S]; top_ks
    i32 [S] (0 = off); keys [S]. Greedy rows (temps <= 0) are fp32
    argmax over the real vocab — bitwise the ``generate()`` contract.
    Sampled rows follow HF's warper order: temperature, then top-k,
    then top-p on the top-k-renormalized distribution.

    The call computes only what some row of it asks for, decided on the
    device from ``temps`` / ``top_ks`` / ``top_ps``: nothing but the
    argmax where every row is greedy, and no sort of the vocabulary
    where no row truncates. Either way each row gets bitwise the token
    the long way gives it."""
    last = logits[:, :vocab].astype(jnp.float32)
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    v = last.shape[-1]

    def draw(masked):
        return jax.vmap(jax.random.categorical)(keys, masked)

    def truncated(scaled):
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            desc, jnp.clip(top_ks - 1, 0, v - 1)[:, None], axis=-1)
        k_on = (top_ks > 0)[:, None]
        masked = jnp.where(k_on & (scaled < kth), -jnp.inf, scaled)
        # top-p on the top-k survivors (exactly the first k sorted entries)
        eff_k = jnp.where(top_ks > 0, top_ks, v)
        desc = jnp.where(jnp.arange(v)[None, :] < eff_k[:, None], desc,
                         -jnp.inf)
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_ps[:, None]
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        p_on = (top_ps < 1.0)[:, None]
        return draw(jnp.where(p_on & (masked < thresh), -jnp.inf, masked))

    def sampled():
        scaled = last / jnp.maximum(temps, 1e-6)[:, None]
        # no row truncates: both masks are off, the draw is over `scaled`
        tok = lax.cond(jnp.any((top_ks > 0) | (top_ps < 1.0)),
                       truncated, draw, scaled).astype(jnp.int32)
        return jnp.where(temps <= 0.0, greedy, tok)

    return lax.cond(jnp.any(temps > 0.0), sampled, lambda: greedy)


def unmask_rows(logits, ids, flags, temps, top_ks, top_ps, keys, vocab,
                fix: int):
    """One denoising pass of a block of ``B`` positions a slot
    (``models/sdar.py``; the published ``low_confidence_static`` rule).
    logits [S, B, V_padded]; ids [S, B] int32 and flags [S, B] bool: the
    block as it stands and which of it is still masked; temps / top_ks /
    top_ps [S] and keys [S * B] (one a position). Every position draws an
    id as ``sample_rows`` draws it (greedy where ``temps <= 0``); its
    confidence is that id's probability under the soft-max of the row at
    the slot's temperature (1 where greedy; before any truncation). Of a
    slot's FLAGGED positions the ``fix`` most confident (all of them where
    fewer are flagged; ties to the lower position) take their id and drop
    their flag; no other position changes. Returns (ids, flags)."""
    s, b = ids.shape
    rep = lambda a: jnp.repeat(a, b)
    rows = logits.reshape(s * b, -1)
    x0 = sample_rows(rows, rep(temps), rep(top_ks), rep(top_ps), keys, vocab)
    last = rows[:, :vocab].astype(jnp.float32) / \
        jnp.where(temps > 0.0, temps, 1.0).repeat(b)[:, None]
    conf = jnp.exp(jnp.take_along_axis(last, x0[:, None], axis=-1)[:, 0] -
                   jax.nn.logsumexp(last, axis=-1)).reshape(s, b)
    _, idx = lax.top_k(jnp.where(flags, conf, -1.0), fix)
    take = jnp.zeros_like(flags).at[jnp.arange(s)[:, None], idx].set(True) \
        & flags
    return jnp.where(take, x0.reshape(s, b), ids), flags & ~take


def sampling_arrays(n: int):
    """Neutral per-slot sampling registers (greedy, no truncation):
    (temps f32, top_ks i32, top_ps f32, seeds i32)."""
    import numpy as np
    return (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
            np.ones((n,), np.float32), np.zeros((n,), np.int32))
