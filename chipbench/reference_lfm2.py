"""Plain reference: LFM2-MoE forward pass (LiquidAI/LFM2-24B-A2B,
``model_type`` lfm2_moe), as published. Pre-norm, RMSNorm, no biases; block
``l`` of the stack, with ``u`` the normed input of each half:

    h = x + Op_l(RMSNorm(x))            y = h + FFN_l(RMSNorm(h))

    Op = conv            B, C, X = split3(u W_in);  z = B * X
      (layer_types[l])   c_t = sum_{j<K} w[:, j] z_{t-(K-1)+j},  z_{<0} = 0
                         (a depthwise causal convolution, K = conv_L_cache
                         taps, one filter a channel);  out = (C * c) W_out
    Op = full_attention  q, k, v = u Wq, u Wk, u Wv;  RMSNorm over each
                         HEAD's channels of q and of k (one gain of head_dim
                         each, shared by the heads);  rotate-half RoPE;
                         causal softmax(q k^T / sqrt(head_dim)) v, four
                         query heads a KV head;  times Wo
    FFN = dense          W2( silu(W1 u) * (W3 u) )      (l < num_dense_layers)
    FFN = routed         s = sigmoid_float32(u Wg);  T = top_k(s + b);
                         w_e = s_e for e in T (WITHOUT b);
                         w = w / (sum_T w + 1e-6)       (norm_topk_prob)
                         w = w * routed_scaling_factor
                         out = sum_{e in T} w_e Wdown_e( silu(Wgate_e u) *
                         (Wup_e u) );  no shared expert, nothing dropped

    logits = Wte RMSNorm(y_last_layer)                  the tied table

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``,
one sequence at a time, one jitted function a KIND of half-block, handed
the kind's whole stack and the layer's index within it and called layer by
layer in Python (five programs and the embedding's, whatever the depth: a
traced check from an empty compile cache pays for each): no kernel, no cache, no state, no batching, no
sorting and no gather of experts. EVERY expert's FFN of every token is
computed and multiplied by its weight, which is zero outside the top k:
independent of the program's permutation code, at experts / top_k times the
routed FLOPs, which a check outside the timed window can pay. The conv is
K shifted copies of z times the filter's columns, over the whole sequence.

So that 4,096 positions fit beside the 10.4 GB of bf16 weights
``jobs/serve_arch.check`` makes, the work goes in blocks: attention one KV
head (its four query heads) at a time, 268 MB of scores; the experts one at
a time, each cast to float32 as it is used (a layer's 64 at once are 2.4
GB); the head as it is (1.07 GB of logits).

Departures, noted: (1) the weights arrive in the program's tree layout
(``weights_lfm2.make``): per-kind stacks, q, k, v fused in one ``qkv_w``
[d, (32 + 8 + 8) x 64] (split here in that order), ``in_w`` [d, 3d] split in
the order B, C, X, filters ``conv_w`` [d, K] (the published Conv1d weight
[d, 1, K] without its middle axis), experts stacked [E, ...]; the
arithmetic is the published one. (2) The top k are chosen by ``lax.top_k``
on the float32 scores plus bias; two that tie exactly are taken lowest
index first, as the published ``torch.topk`` does not promise either way.
(3) The head multiplies by the table it is given (65,536 rows: no padding).

``quant`` is the control's hook: a function applied to both operands of
every large matmul (the conv operator's two projections, the dense FFN, the
three matmuls of every expert), ``reference.fp8``: what those layers in a
lower precision than the configuration states would compute. ``None`` is
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


def route(scores, bias, top_k, renormalise, eps=1e-6, scale=1.0):
    """[T, E] weights: ``scores`` at each row's ``top_k`` largest of
    ``scores + bias``, 0 elsewhere; renormalised over the picks with
    ``eps`` in the sum; times ``scale``."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    keep = jnp.zeros_like(scores).at[jnp.arange(scores.shape[0])[:, None],
                                     idx].set(1.0)
    w = scores * keep
    if renormalise:
        w = w / (w.sum(-1, keepdims=True) + eps)
    return w * scale


def _mm(a, b, quant):
    return a @ b if quant is None else quant(a) @ quant(b)


def _f32(p):
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _layer(stack, at):
    """Layer ``at`` of a kind's stacked tree, taken inside the jitted
    function: one program a kind, whatever the layer (a slice taken outside
    is a small program of its own for every leaf and every index)."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, at, 0, keepdims=False), stack)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _conv(x, stack, at, eps, quant):
    """x + the gated short convolution of one sequence. x: [T, d]."""
    p = _f32(_layer(stack, at))
    t = x.shape[0]
    taps = p["conv_w"].shape[1]
    u = _rms(x, p["ln1_scale"], eps)
    b, c, v = jnp.split(_mm(u, p["in_w"], quant), 3, axis=-1)
    z = b * v
    padded = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1])), z])
    conv = sum(p["conv_w"][:, j] * padded[j:j + t] for j in range(taps))
    return x + _mm(c * conv, p["out_w"], quant)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                              "eps"))
def _attention(x, stack, at, heads, kv_heads, theta, eps):
    """x + grouped-query attention of one sequence, one KV head's query
    heads at a time."""
    p = _f32(_layer(stack, at))
    t, d = x.shape
    hd = d // heads
    rep = heads // kv_heads
    u = _rms(x, p["ln1_scale"], eps)
    q, k, v = jnp.split(u @ p["qkv_w"], [heads * hd, (heads + kv_heads) * hd],
                        axis=-1)
    q = _rms(q.reshape(t, heads, hd), p["q_norm_scale"], eps)
    k = _rms(k.reshape(t, kv_heads, hd), p["k_norm_scale"], eps)
    q = _rope(q.transpose(1, 0, 2), theta).reshape(kv_heads, rep, t, hd)
    k = _rope(k.transpose(1, 0, 2), theta)
    v = v.reshape(t, kv_heads, hd).transpose(1, 0, 2)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def group(xs):
        qg, kg, vg = xs                      # [rep, T, hd], [T, hd], [T, hd]
        s = jnp.where(causal, qg @ kg.T / np.sqrt(hd), -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vg

    o = jax.lax.map(group, (q, k, v))                      # [Hk, rep, T, hd]
    o = o.reshape(heads, t, hd).transpose(1, 0, 2).reshape(t, d)
    return x + o @ p["attn_proj_w"]


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _dense(x, stack, at, eps, quant):
    p = _f32(_layer(stack, at))
    u = _rms(x, p["ln2_scale"], eps)
    hidden = jax.nn.silu(_mm(u, p["gate_w"], quant)) * _mm(u, p["up_w"], quant)
    return x + _mm(hidden, p["down_w"], quant)


@functools.partial(jax.jit, static_argnames=("top_k", "renormalise", "eps",
                                              "renorm_eps", "scale", "quant"))
def _routed(x, stack, at, top_k, renormalise, eps, renorm_eps, scale, quant):
    """x + every expert's FFN times its weight, one expert at a time."""
    p = _layer(stack, at)
    u = _rms(x, p["ln2_scale"].astype(jnp.float32), eps)
    gate = _f32(p["moe"]["gate"])
    w = route(jax.nn.sigmoid(u @ gate["wg"]), gate["bias"], top_k,
              renormalise, renorm_eps, scale)                      # [T, E]

    def expert(acc, xs):
        w_gate, w_up, w_down, w_e = xs
        w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
        y = _mm(jax.nn.silu(_mm(u, w_gate, quant)) * _mm(u, w_up, quant),
                w_down, quant)
        return acc + w_e[:, None] * y, None
    ex = p["moe"]["experts"]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (ex["w_gate"], ex["w_up"], ex["w_down"], w.T))
    return x + y


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
    at = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}

    def take(kind):
        at[kind] += 1
        return blocks[kind], at[kind] - 1

    with jax.default_matmul_precision("highest"):
        x = _embed(weights["wte"], jnp.asarray(ids, jnp.int32))
        for l, kind in enumerate(dims["layer_types"]):
            if kind == "conv":
                x = _conv(x, *take("conv"), eps, quant)
            else:
                x = _attention(x, *take("attn"), dims["heads"],
                               dims["kv_heads"], dims["rope_theta"], eps)
            if l < dims["dense_layers"]:
                x = _dense(x, *take("dense"), eps, quant)
            else:
                x = _routed(x, *take("moe"), dims["top_k"],
                            dims["norm_topk_prob"], eps, dims["renorm_eps"],
                            dims["routed_scaling_factor"], quant)
        return _head(x, weights["wte"], weights["ln_f_scale"], eps)
