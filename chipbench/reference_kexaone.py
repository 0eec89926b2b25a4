"""Plain reference: K-EXAONE forward pass (LGAI-EXAONE/K-EXAONE-236B-A23B,
``model_type`` exaone_moe), as published, for ONE chip's share of it.
RMSNorm, no biases; the family normalises each sublayer's OUTPUT
(``exaone4``); block ``l`` of the stack:

    h = x + RMSNorm(Attn_l(x); g_a)         y = h + RMSNorm(FFN_l(h); g_f)

    Attn                 q, k, v = x Wq, x Wk, x Wv;  RMSNorm over each
                         HEAD's channels of q and of k (one gain of head_dim
                         each, shared by the heads)
      sliding_attention  rotate-half RoPE;  a query at p sees the keys at
      (layer_types[l])   p - window + 1 ... p
      full_attention     causal;  NO rotary embedding
                         softmax(q k^T / sqrt(head_dim)) v, eight query
                         heads a KV head;  times Wo
    FFN = dense          W2( silu(W1 h) * (W3 h) )      (l < dense_layers)
    FFN = routed         s = sigmoid_float32(h Wg) over ALL router_experts;
                         T = top_k(s + b);  w_e = s_e for e in T (WITHOUT b);
                         w = w / (sum_T w + 1e-20)      (norm_topk_prob)
                         w = w * routed_scaling_factor
                         out = sum_{e in T, e HELD} w_e E_e(h) + E_shared(h)
                         every expert a SwiGLU;  the shared one takes every
                         token with weight 1

    logits = W_head RMSNorm(y_last_layer)               the untied head

**The share.** The chip holds experts ``expert_offset ... expert_offset +
experts - 1`` of the ``router_experts`` the router scores, and ``vocab`` rows
of the vocabulary. The reference is given the same: the router scores and
picks among all of them, only the held experts' terms are added (what an
absent expert would have added is left out, here as in the program, and
nothing stands in for it), the shared expert is added once, and the head is
the ``vocab`` rows it is handed.

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``,
one sequence at a time, one jitted function a KIND of half-block, handed the
kind's whole stack and the layer's index within it and called layer by
layer in Python: no kernel, no cache, no ring, no batching, no sorting and
no gather of experts. EVERY held expert's FFN of every token is computed and
multiplied by its weight, which is zero outside the top k.

So that 13,312 positions fit beside the 7.4 GB of bf16 weights
``jobs/serve_arch.check`` makes, the work goes in blocks: attention one KV
head (its eight query heads) and ``Q_BLOCK`` queries at a time (a full
layer: against every key, 436 MB of scores; a window layer: against the
``window - 1 + Q_BLOCK`` keys that block can see); the feed-forwards
``ROW_BLOCK`` rows at a time; the experts one at a time, each cast to
float32 as it is used.

Departures, noted: (1) the weights arrive in the program's tree layout
(``weights_kexaone.make``): per-kind stacks, q, k, v fused in one ``qkv_w``
[d, (64 + 8 + 8) x 128] (split here in that order), experts stacked
[experts, ...]; the arithmetic is the published one. (2) The top k are
chosen by ``lax.top_k`` on the float32 scores plus bias; two that tie
exactly are taken lowest index first. (3) The multi-token-prediction block
does not enter the model's logits and is not here. (4) The norms on the
sublayers' outputs, the per-head q/k norm and the absence of a rotary
embedding in the full layers are the family's published ``exaone4``
modelling code, not keys of the config (``assumed`` in the configuration's
file).

``quant`` is the control's hook: a function applied to both operands of
every large matmul (the dense FFN, the three matmuls of every expert, the
shared one's), ``reference.fp8``. ``None`` is the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference_lfm2 import _f32, _layer, _mm, _rms, _rope, route

Q_BLOCK = 1024
ROW_BLOCK = 2048


def _in_blocks(fn, x, block):
    """``fn`` over the rows of ``x`` [T, ...] in blocks of ``block`` (the
    whole where that does not divide T)."""
    t = x.shape[0]
    if t <= block or t % block:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((t // block, block) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                              "eps", "window"))
def _attention(x, stack, at, heads, kv_heads, theta, eps, window):
    """x + RMSNorm(grouped-query attention of one sequence). ``window``:
    the sliding layer's (rotary, the band), or None (full: neither)."""
    p = _f32(_layer(stack, at))
    t = x.shape[0]
    hd = p["q_norm_scale"].shape[0]
    rep = heads // kv_heads
    q, k, v = jnp.split(x @ p["qkv_w"], [heads * hd, (heads + kv_heads) * hd],
                        axis=-1)
    q = _rms(q.reshape(t, heads, hd), p["q_norm_scale"], eps).transpose(1, 0, 2)
    k = _rms(k.reshape(t, kv_heads, hd), p["k_norm_scale"], eps
             ).transpose(1, 0, 2)
    v = v.reshape(t, kv_heads, hd).transpose(1, 0, 2)
    if window is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(kv_heads, rep, t, hd)
    block = Q_BLOCK if t > Q_BLOCK and t % Q_BLOCK == 0 else t
    # the keys a block of queries starting at q0 can see: all of them, or
    # positions q0 - (window - 1) ... q0 + block - 1 of a strip with
    # window - 1 rows of nothing before position 0
    reach = t if window is None else window - 1 + block
    lead = 0 if window is None else window - 1

    def group(xs):
        qg, kg, vg = xs                      # [rep, T, hd], [T, hd], [T, hd]
        kg = jnp.pad(kg, ((lead, 0), (0, 0)))
        vg = jnp.pad(vg, ((lead, 0), (0, 0)))

        def queries(b):
            q0 = b * block
            qb = jax.lax.dynamic_slice_in_dim(qg, q0, block, axis=1)
            first = 0 if window is None else q0     # strip row of key 0 seen
            kb = jax.lax.dynamic_slice_in_dim(kg, first, reach, axis=0)
            vb = jax.lax.dynamic_slice_in_dim(vg, first, reach, axis=0)
            q_pos = q0 + jnp.arange(block)[:, None]
            k_pos = first - lead + jnp.arange(reach)[None, :]
            keep = (k_pos <= q_pos) & (k_pos >= 0)
            if window is not None:
                keep &= q_pos - k_pos < window
            s = jnp.where(keep, qb @ kb.T / np.sqrt(hd), -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vb          # [rep, block, hd]

        o = jax.lax.map(queries, jnp.arange(t // block))
        return o.transpose(1, 0, 2, 3).reshape(rep, t, hd)

    o = jax.lax.map(group, (q, k, v))                      # [Hk, rep, T, hd]
    o = o.reshape(heads, t, hd).transpose(1, 0, 2).reshape(t, heads * hd)
    return x + _rms(o @ p["attn_proj_w"], p["post_attn_scale"], eps)


def _swiglu(u, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant),
               w_down, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense(x, stack, at, eps, quant):
    p = _f32(_layer(stack, at))
    y = _in_blocks(lambda u: _swiglu(u, p["gate_w"], p["up_w"], p["down_w"],
                                     quant), x, ROW_BLOCK)
    return x + _rms(y, p["post_mlp_scale"], eps)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renormalise", "eps", "renorm_eps", "scale", "offset", "quant"))
def _routed(x, stack, at, top_k, renormalise, eps, renorm_eps, scale, offset,
            quant):
    """x + RMSNorm(the held experts' FFNs times their weights, one expert
    at a time, + the shared expert's)."""
    p = _layer(stack, at)
    gate = _f32(p["moe"]["gate"])
    ex, shared = p["moe"]["experts"], _f32(p["moe"]["shared"])
    held = ex["w_gate"].shape[0]

    def rows(u):
        w = route(jax.nn.sigmoid(u @ gate["wg"]), gate["bias"], top_k,
                  renormalise, renorm_eps, scale)       # [T, router_experts]
        w = jax.lax.dynamic_slice_in_dim(w, offset, held, axis=1)

        def expert(acc, xs):
            w_gate, w_up, w_down, w_e = xs
            y = _swiglu(u, *_f32((w_gate, w_up, w_down)), quant)
            return acc + w_e[:, None] * y, None
        y, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                            (ex["w_gate"], ex["w_up"], ex["w_down"], w.T))
        return y + _swiglu(u, shared["w_gate"], shared["w_up"],
                           shared["w_down"], quant)

    y = _in_blocks(rows, x, ROW_BLOCK)
    return x + _rms(y, p["post_mlp_scale"].astype(jnp.float32), eps)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, table, gain, eps):
    return _rms(x, gain.astype(jnp.float32), eps) @ table.astype(jnp.float32).T


def logits(weights, ids, dims, quant=None):
    """[T, vocab rows] float32 logits of one sequence ``ids`` [T]."""
    eps = dims["rms_eps"]
    blocks = weights["blocks"]
    at = {"window": 0, "full": 0, "dense": 0, "moe": 0}

    def take(kind):
        at[kind] += 1
        return blocks[kind], at[kind] - 1

    with jax.default_matmul_precision("highest"):
        x = _embed(weights["wte"], jnp.asarray(ids, jnp.int32))
        for l, kind in enumerate(dims["layer_types"]):
            sliding = kind == "sliding_attention"
            x = _attention(x, *take("window" if sliding else "full"),
                           dims["heads"], dims["kv_heads"],
                           dims["rope_theta"], eps,
                           dims["window"] if sliding else None)
            if l < dims["dense_layers"]:
                x = _dense(x, *take("dense"), eps, quant)
            else:
                x = _routed(x, *take("moe"), dims["top_k"],
                            dims["norm_topk_prob"], eps, dims["renorm_eps"],
                            dims["routed_scaling_factor"],
                            dims["expert_offset"], quant)
        return _head(x, weights["lm_head"], weights["ln_f_scale"], eps)
