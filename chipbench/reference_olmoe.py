"""Plain reference: OLMoE forward pass (allenai/OLMoE-1B-7B, ``model_type``
olmoe), as published:

    n1 = RMSNorm(x)
    q, k, v = Wq n1, Wk n1, Wv n1
    q, k = RMSNorm_q(q), RMSNorm_k(k)       one gain per PROJECTION channel,
                                            over the whole projection, before
                                            the split into heads
    h = x + Wo Attn(RoPE(q), RoPE(k), v)    rotate-half RoPE, causal softmax
    n2 = RMSNorm(h)
    p = softmax_float32(Wr n2)              over all experts
    T = the top_k largest of p
    y = h + sum_{e in T} p_e Wdown_e( silu(Wgate_e n2) * (Wup_e n2) )
                                            p NOT renormalised over T unless
                                            ``norm_topk_prob``; every token
                                            routed, nothing dropped
    logits = Whead RMSNorm(y_last_layer)    untied head

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``,
one sequence at a time, one jitted layer called ``layers`` times: no kernel,
no cache, no batching, no sorting and no gather of experts — EVERY expert's
FFN of every token is computed and multiplied by its weight, which is zero
outside the top k. That is independent of the program's permutation code
and costs experts / top_k times the routed FLOPs, which a check outside the
timed window can pay.

Departures, noted: (1) the weights arrive in the program's tree layout
(``weights_olmoe.make``): q, k, v fused in one ``qkv_w`` [d, 3d] (split here
in that order), experts stacked [E, ...]; the arithmetic is the published
one. (2) The head multiplies by the table it is given (the vocabulary is a
multiple of 128 already: no padded rows). (3) The top k are chosen by
``lax.top_k`` on the float32 probabilities; two experts that tie exactly
are taken lowest index first, as the published ``torch.topk`` does not
promise either way.

``quant`` is the control's hook: a function applied to both operands of the
three matmuls of every expert (``reference.fp8``: what an expert layer in a
lower precision than the configuration states would compute). ``None`` is
the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: [H, T, hd]; rotate-half: the two halves of a head are the pair."""
    _, t, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def route(probs, top_k, renormalise):
    """[T, E] weights: ``probs`` at each row's ``top_k`` largest, 0 elsewhere."""
    _, idx = jax.lax.top_k(probs, top_k)
    keep = jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None],
                                    idx].set(1.0)
    w = probs * keep
    return w / w.sum(-1, keepdims=True) if renormalise else w


def _mm(a, b, quant):
    return a @ b if quant is None else quant(a) @ quant(b)


def _block(x, p, heads, top_k, theta, eps, renormalise, quant):
    """One block on one sequence. x: [T, d] float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t, d = x.shape
    hd = d // heads
    n1 = _rms(x, p["ln1_scale"], eps)
    q, k, v = jnp.split(n1 @ p["qkv_w"], 3, axis=-1)
    q, k = _rms(q, p["q_norm_scale"], eps), _rms(k, p["k_norm_scale"], eps)
    q, k, v = (a.reshape(t, heads, hd).transpose(1, 0, 2) for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    s = q @ jnp.swapaxes(k, -1, -2) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = (jax.nn.softmax(s, axis=-1) @ v).transpose(1, 0, 2).reshape(t, d)
    h = x + o @ p["attn_proj_w"]
    n2 = _rms(h, p["ln2_scale"], eps)
    moe = p["moe"]
    w = route(jax.nn.softmax(n2 @ moe["gate"]["wg"], axis=-1), top_k,
              renormalise)                                         # [T, E]

    def expert(acc, xs):
        w_gate, w_up, w_down, w_e = xs
        y = _mm(jax.nn.silu(_mm(n2, w_gate, quant)) * _mm(n2, w_up, quant),
                w_down, quant)
        return acc + w_e[:, None] * y, None
    ex = moe["experts"]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (ex["w_gate"], ex["w_up"], ex["w_down"], w.T))
    return h + y


_layer = jax.jit(_block, static_argnames=("heads", "top_k", "theta", "eps",
                                          "renormalise", "quant"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, head, gain, eps):
    return _rms(x, gain.astype(jnp.float32), eps) @ head.astype(jnp.float32).T


def logits(weights, ids, dims, quant=None):
    """[T, vocab rows] float32 logits of one sequence ``ids`` [T]."""
    with jax.default_matmul_precision("highest"):
        x = weights["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for i in range(dims["layers"]):
            p = jax.tree.map(lambda a: a[i], weights["blocks"])
            x = _layer(x, p, dims["heads"], dims["top_k"],
                       dims["rope_theta"], dims["rms_eps"],
                       dims["norm_topk_prob"], quant)
        return _head(x, weights["lm_head"], weights["ln_f_scale"],
                     dims["rms_eps"])
