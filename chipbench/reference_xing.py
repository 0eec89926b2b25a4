"""Plain reference: Xing4.0 forward pass (XingChen-AGI/Xing4.0-29B-A4B,
``model_type`` xing4_0), for ONE chip's share of it. RMSNorm, no biases.
A token's residual state is ``X`` in R^{n x C} (n = ``hc_mult`` = 4 streams;
manifold-constrained hyper-connections, arXiv:2512.24880); ``X_0`` is the
embedding in all n streams. Each sublayer F of a block (attention, then
feed-forward) has its own ``phi`` [nC, n(n + 2)], ``b`` [n(n + 2)] and gates
``a_pre, a_post, a_res``::

    x~ = RMSNorm(flatten(X); hc_eps)                     no gain
    [h_pre | h_post | h_res] = x~ phi                    split n | n | n*n
    H_pre  = sigmoid(a_pre h_pre + b_pre)                [n]
    H_post = 2 sigmoid(a_post h_post + b_post)           [n]
    M_0    = exp(clip(a_res mat(h_res) + b_res, -30, 30))   [n, n], row-major
    M_t    = rows(cols(M_{t-1})): every column, then every row, divided by
             (its sum + hc_eps);  t = 1 ... hc_sinkhorn_iters;  H_res = M_last
    u = H_pre X  [C];   y = F(RMSNorm(u; gain));   X <- H_res X + H_post^T y

    logits = W_head RMSNorm(sum of the streams of X_last)     the untied head

    Attn   c_q = RMSNorm(u W_qa);  q = c_q W_qb -> H x (nope | rope)
           [c_kv | k_r] = u W_kva;  c_kv <- RMSNorm(c_kv)
           [k_nope | v] = c_kv W_kvb -> H x (nope | v)
           rotate-half RoPE, YaRN frequencies, on q_r and on the ONE k_r all
           heads share
           scores (q_nope . k_nope + q_r . k_r) (nope + rope)^-1/2 m^2,
           m = 0.1 mscale_all_dim ln(factor) + 1;  causal softmax;  (P v) W_o
    FFN = dense    W2( silu(W1 u) * (W3 u) )            (l < dense_layers)
    FFN = routed   s = sigmoid_float32(u Wg) over ALL router_experts;
                   T = top_k(s + bias);  w_e = s_e for e in T (WITHOUT bias);
                   w = w / (sum_T w + renorm_eps);  w = w * routed_scaling_factor
                   out = sum_{e in T, e HELD} w_e E_e(u) + E_shared(u)

**The share**, as ``reference_kexaone.py``: the router scores and picks among
all ``router_experts``, only the held experts' terms (``expert_offset ...
expert_offset + experts - 1``) are added, the shared expert once, and the
head is the ``vocab`` rows it is handed; nothing stands in for the absent
chips.

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``,
one sequence at a time, the state as ``[T, n, C]``, one jitted function a
KIND of sublayer handed the kind's whole stack and the layer's index, called
layer by layer in Python: no cache, no latent absorbed into a query (keys and
values are expanded for every head, every time), no kernel, no batching, no
sorting and no gather of experts; the Sinkhorn steps are a loop. So that
8,192 positions fit beside the bf16 weights ``jobs/serve_arch.check`` makes,
attention goes one head and ``Q_BLOCK`` queries at a time, the feed-forwards
``ROW_BLOCK`` rows at a time, the experts one at a time.

Departures, noted: (1) the weights arrive in the program's tree layout
(``weights_xing.make``): per-kind stacks, a head's (nope | rope) and
(nope | v) parts side by side in ``q_b_w`` and ``kv_b_w``, experts stacked.
(2) Rotate-half RoPE where the family's code rotates interleaved pairs: a
fixed permutation of ``W_qb``'s and ``W_kva``'s rope columns, which random
weights do not see. (3) ``lax.top_k`` breaks an exact tie lowest index
first. (4) The multi-token-prediction block does not enter the logits and is
not here. (5) What the config's keys do not fix (the embedding copied into
every stream and the streams summed at the end, no gain in the flattened
norm, columns before rows, ``hc_eps`` in the Sinkhorn sums and the flattened
norm alone, gains on the two latent norms, ``mscale_all_dim`` squared into
the scores) is ``assumed`` in the configuration's file.

``quant`` is the control's hook: a function applied to both operands of
every large matmul (the five latent projections and ``W_o``, the dense FFN,
every expert's and the shared one's), ``reference.fp8``. ``None`` is the
reference.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference_kexaone import _in_blocks, _swiglu
from chipbench.reference_lfm2 import _f32, _layer, _mm, _rms, route

Q_BLOCK = 1024
ROW_BLOCK = 2048


def yarn_frequencies(dims):
    """The rope_dim / 2 rotary frequencies under YaRN, from the config's
    ``rope_scaling``: dimension i turns ``original positions x f_i / 2 pi``
    times; those that turn more than ``beta_fast`` times keep their
    frequency, those under ``beta_slow`` are divided by ``factor``, a linear
    ramp over the dimensions between."""
    dim, base = dims["rope_dim"], dims["rope_theta"]
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    plain = base ** -exponent

    def dimension_of(turns):
        return dim * math.log(dims["rope_original_positions"] /
                              (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dimension_of(dims["rope_beta_fast"])), 0)
    high = min(math.ceil(dimension_of(dims["rope_beta_slow"])), dim - 1)
    slowed = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                     0.0, 1.0)
    return plain * (1 - slowed) + plain / dims["rope_factor"] * slowed


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(x, freqs, scale):
    """x: [..., T, r]; rotate-half by position x frequency."""
    t, r = x.shape[-2:]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * \
        jnp.asarray(freqs, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * scale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * scale
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def maps(x, hc, iters, eps, clamp):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of the state
    ``x`` [T, n, C] under one sublayer's ``hc`` = {phi, b, alpha}."""
    t, n, _ = x.shape
    flat = x.reshape(t, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    h = flat @ hc["phi"]
    a, b = hc["alpha"], hc["b"]
    pre = jax.nn.sigmoid(a[0] * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * h[:, 2 * n:] + b[2 * n:], -clamp, clamp)
                ).reshape(t, n, n)

    def step(_, m):
        m = m / (m.sum(axis=1, keepdims=True) + eps)    # each column's sum
        return m / (m.sum(axis=2, keepdims=True) + eps)     # each row's

    return pre, post, jax.lax.fori_loop(0, iters, step, m)


def _hyper(x, hc, gain, eps, hyper, fn):
    """``H_res x + H_post^T fn(RMSNorm(H_pre x))``; ``hyper`` = (iters,
    hc_eps, clamp)."""
    pre, post, res = maps(x, hc, *hyper)
    y = fn(_rms(jnp.einsum("tn,tnc->tc", pre, x), gain, eps))
    return jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None]


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "kv_rank", "freqs", "rope_scale", "score_scale",
    "eps", "hyper", "quant"))
def _attention(x, stack, at, heads, nope, rope, kv_rank, freqs, rope_scale,
               score_scale, eps, hyper, quant):
    p = _f32(_layer(stack, at))

    def attend(u):
        t = u.shape[0]
        c_q = _rms(_mm(u, p["q_a_w"], quant), p["q_a_scale"], eps)
        q = _mm(c_q, p["q_b_w"], quant).reshape(t, heads, nope + rope)
        row = _mm(u, p["kv_a_w"], quant)
        c_kv = _rms(row[:, :kv_rank], p["kv_a_scale"], eps)
        kv = _mm(c_kv, p["kv_b_w"], quant).reshape(t, heads, -1)
        k_r = _rotate(row[:, kv_rank:], freqs, rope_scale)          # [T, r]
        q = q.transpose(1, 0, 2)                                 # [H, T, .]
        q_r = _rotate(q[..., nope:], freqs, rope_scale)
        kv = kv.transpose(1, 0, 2)
        block = Q_BLOCK if t > Q_BLOCK and t % Q_BLOCK == 0 else t

        def head(xs):
            q_n, q_r, k_n, v = xs

            def queries(b):
                q0 = b * block
                qn = jax.lax.dynamic_slice_in_dim(q_n, q0, block, axis=0)
                qr = jax.lax.dynamic_slice_in_dim(q_r, q0, block, axis=0)
                s = (qn @ k_n.T + qr @ k_r.T) * score_scale
                seen = jnp.arange(t)[None, :] <= q0 + jnp.arange(block)[:, None]
                return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1) @ v

            return jax.lax.map(queries, jnp.arange(t // block)
                               ).reshape(t, -1)

        o = jax.lax.map(head, (q[..., :nope], q_r, kv[..., :nope],
                               kv[..., nope:]))                  # [H, T, v]
        return _mm(o.transpose(1, 0, 2).reshape(t, -1), p["attn_proj_w"],
                   quant)

    return _hyper(x, p["hc_attn"], p["ln1_scale"], eps, hyper, attend)


@functools.partial(jax.jit, static_argnames=("eps", "hyper", "quant"))
def _dense(x, stack, at, eps, hyper, quant):
    p = _f32(_layer(stack, at))
    return _hyper(x, p["hc_mlp"], p["ln2_scale"], eps, hyper,
                  lambda u: _in_blocks(
                      lambda r: _swiglu(r, p["gate_w"], p["up_w"],
                                        p["down_w"], quant), u, ROW_BLOCK))


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renormalise", "eps", "renorm_eps", "scale", "offset", "hyper",
    "quant"))
def _routed(x, stack, at, top_k, renormalise, eps, renorm_eps, scale, offset,
            hyper, quant):
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

    return _hyper(x, _f32(p["hc_mlp"]), p["ln2_scale"].astype(jnp.float32),
                  eps, hyper, lambda u: _in_blocks(rows, u, ROW_BLOCK))


@functools.partial(jax.jit, static_argnames=("streams",))
def _embed(table, ids, streams):
    x = table[ids].astype(jnp.float32)
    return jnp.broadcast_to(x[:, None], (x.shape[0], streams, x.shape[1]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, table, gain, eps):
    return _rms(x.sum(axis=1), gain.astype(jnp.float32), eps) @ \
        table.astype(jnp.float32).T


def logits(weights, ids, dims, quant=None):
    """[T, vocab rows] float32 logits of one sequence ``ids`` [T]."""
    eps = dims["rms_eps"]
    blocks = weights["blocks"]
    hyper = (dims["hc_sinkhorn_iters"], dims["hc_eps"], dims["hc_clamp"])
    m = _mscale(dims["rope_factor"], dims["rope_mscale_all_dim"])
    rope_scale = _mscale(dims["rope_factor"], dims["rope_mscale"]) / m
    score_scale = m * m / math.sqrt(dims["nope_dim"] + dims["rope_dim"])
    freqs = tuple(float(f) for f in yarn_frequencies(dims))
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["wte"], jnp.asarray(ids, jnp.int32),
                   dims["streams"])
        for l in range(dims["dense_layers"] + dims["layers"]):
            x = _attention(x, blocks["attn"], l, dims["heads"],
                           dims["nope_dim"], dims["rope_dim"],
                           dims["kv_rank"], freqs, rope_scale, score_scale,
                           eps, hyper, quant)
            if l < dims["dense_layers"]:
                x = _dense(x, blocks["dense"], l, eps, hyper, quant)
            else:
                x = _routed(x, blocks["moe"], l - dims["dense_layers"],
                            dims["top_k"], dims["norm_topk_prob"], eps,
                            dims["renorm_eps"],
                            dims["routed_scaling_factor"],
                            dims["expert_offset"], hyper, quant)
        return _head(x, weights["lm_head"], weights["ln_f_scale"], eps)
