"""Plain reference: SDAR forward pass and its generation by diffusion over
blocks (JetLM/SDAR-30B-A3B-Chat, ``model_type`` sdar_moe; the published
``modeling_sdar_moe.py`` and ``generate.py``):

    n1 = RMSNorm(x)
    q, k, v = Wq n1, Wk n1, Wv n1           32 query heads over 4 KV heads
    q, k = RMSNorm_q(q), RMSNorm_k(k)       one gain of head_dim, over EACH
                                            head's 128 channels, before RoPE
    h = x + Wo Attn(RoPE(q), RoPE(k), v)    a query keeps every key of its
                                            own and of earlier blocks:
                                            k_pos // B <= q_pos // B
    n2 = RMSNorm(h)
    p = softmax_float32(Wr n2)              over all 128 experts
    T = the 8 largest of p, renormalised to sum 1 (``norm_topk_prob``)
    y = h + sum_{e in T} p_e Wdown_e( silu(Wgate_e n2) * (Wup_e n2) )
    logits = Whead RMSNorm(y_last_layer)    untied head; the row AT a
                                            position predicts that position

``generate``: the sequence's whole blocks of the prompt are context; the
``len % B`` prompt tokens left over open the first block beside ``[MASK]``
positions; a block is denoised over passes, each a whole forward of the
sequence so far (no cache), each fixing of the still-masked positions the
``n = B / steps`` whose chosen id is most probable
(``low_confidence_static``), until none is masked.

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``,
one sequence at a time, one jitted layer called ``layers`` times: no kernel,
no cache, no sorting and no gather of experts: EVERY expert's FFN of every
row is computed and multiplied by its weight, which is zero outside the top
k. The layer takes ROWS, each with a position and a row of a keep-mask over
the other rows, and the queries go through the scores a block of
``_QUERY_BLOCK`` at a time so that a sequence of thousands fits; a sequence
is rows 0..T-1 under the block mask. ``logits_two_stream`` hands the same
layer 2T rows, the clean sequence and beside it a NOISY copy (some
positions ``[MASK]``), a noisy row keeping the clean keys of earlier blocks
and the noisy keys of its own: the training mask of the SDAR paper, which
gives every block's logits at one state of its denoising in one forward
(tests/chipbench/test_chipbench_sdar.py holds it equal to block-by-block
forwards).

Departures from the published code, noted: (1) the weights arrive in the
program's tree layout (``weights_sdar.make``): q, k, v fused in one
``qkv_w`` (split here in that order), experts stacked [E, ...]; the
arithmetic is the published one, rotate-half RoPE as published. (2) The top
k are chosen by ``lax.top_k`` on the float32 probabilities, ties lowest
index first. (3) Whether a position is masked is a FLAG beside the ids and
is never read off the id: a prompt may hold ``mask_token_id``. (4) A pass
fixes ``min(n, still masked)`` positions and only masked ones; the
published ``torch.topk`` over confidences set to -inf elsewhere would, in a
first block that the prompt's remainder leaves fewer than ``n`` masked
positions, also overwrite a position that was never masked. (5) Greedy is
the arg-max of the float32 logits and the confidence the soft-max
probability of that id; ties in confidence go to the lower position.

``quant`` is the control's hook: a function applied to both operands of the
three matmuls of every expert. ``None`` is the reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: queries whose scores against every key are held at a time
_QUERY_BLOCK = 512


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, pos, theta):
    """x: [H, N, hd], row n at position ``pos[n]``; rotate-half."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def route(probs, top_k, renormalise):
    """[N, E] weights: ``probs`` at each row's ``top_k`` largest, 0 elsewhere."""
    _, idx = jax.lax.top_k(probs, top_k)
    keep = jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None],
                                    idx].set(1.0)
    w = probs * keep
    return w / w.sum(-1, keepdims=True) if renormalise else w


def _mm(a, b, quant):
    return a @ b if quant is None else quant(a) @ quant(b)


def _attend(q, k, v, keep):
    """q [H, N, hd] over k, v [Hk, N, hd] under ``keep`` [N, N], the queries
    a block at a time."""
    h, n, hd = q.shape
    rep = h // k.shape[0]
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)

    def some(qb, kb):
        s = qb @ jnp.swapaxes(k, -1, -2) / np.sqrt(hd)
        return jax.nn.softmax(jnp.where(kb, s, -jnp.inf), axis=-1) @ v

    blk = min(_QUERY_BLOCK, n)
    whole = n // blk * blk
    out = jax.lax.map(
        lambda xs: some(*xs),
        (q[:, :whole].reshape(h, -1, blk, hd).transpose(1, 0, 2, 3),
         keep[:whole].reshape(-1, blk, n)))
    out = out.transpose(1, 0, 2, 3).reshape(h, whole, hd)
    if whole < n:
        out = jnp.concatenate([out, some(q[:, whole:], keep[whole:])], axis=1)
    return out


def _block(x, pos, keep, p, heads, kv_heads, head_dim, top_k, theta, eps,
           renormalise, quant):
    """One layer on rows x [N, d] at positions ``pos`` under ``keep``."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    n = x.shape[0]
    n1 = _rms(x, p["ln1_scale"], eps)
    q, k, v = jnp.split(n1 @ p["qkv_w"], [heads * head_dim,
                                          (heads + kv_heads) * head_dim], -1)
    q = _rms(q.reshape(n, heads, head_dim), p["q_norm_scale"], eps)
    k = _rms(k.reshape(n, kv_heads, head_dim), p["k_norm_scale"], eps)
    q, k = _rope(q.transpose(1, 0, 2), pos, theta), \
        _rope(k.transpose(1, 0, 2), pos, theta)
    v = v.reshape(n, kv_heads, head_dim).transpose(1, 0, 2)
    o = _attend(q, k, v, keep).transpose(1, 0, 2).reshape(n, -1)
    h = x + o @ p["attn_proj_w"]
    n2 = _rms(h, p["ln2_scale"], eps)
    moe = p["moe"]
    w = route(jax.nn.softmax(n2 @ moe["gate"]["wg"], axis=-1), top_k,
              renormalise)                                         # [N, E]

    def expert(acc, xs):
        w_gate, w_up, w_down, w_e = xs
        y = _mm(jax.nn.silu(_mm(n2, w_gate, quant)) * _mm(n2, w_up, quant),
                w_down, quant)
        return acc + w_e[:, None] * y, None
    ex = moe["experts"]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (ex["w_gate"], ex["w_up"], ex["w_down"], w.T))
    return h + y


_layer = jax.jit(_block, static_argnames=(
    "heads", "kv_heads", "head_dim", "top_k", "theta", "eps", "renormalise",
    "quant"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, head, gain, eps):
    return _rms(x, gain.astype(jnp.float32), eps) @ head.astype(jnp.float32).T


def _forward(weights, x, pos, keep, dims, quant, last=None):
    """Logits of the rows (of the ``last`` of them alone, where given)."""
    with jax.default_matmul_precision("highest"):
        for i in range(dims["layers"]):
            p = jax.tree.map(lambda a: a[i], weights["blocks"])
            x = _layer(x, pos, keep, p, dims["heads"], dims["kv_heads"],
                       dims["head_dim"], dims["top_k"], dims["rope_theta"],
                       dims["rms_eps"], dims["norm_topk_prob"], quant)
        return _head(x if last is None else x[-last:], weights["lm_head"],
                     weights["ln_f_scale"], dims["rms_eps"])


def _embed(weights, ids, masked, dims):
    ids = jnp.asarray(ids, jnp.int32)
    if masked is not None:
        ids = jnp.where(jnp.asarray(masked, bool), dims["mask_token_id"], ids)
    return weights["wte"][ids].astype(jnp.float32)


def logits(weights, ids, dims, quant=None, masked=None):
    """[T, vocab rows] float32 logits of one sequence ``ids`` [T] under the
    block mask, the ``[MASK]`` row standing at the ``masked`` [T] positions
    (``None``: at none)."""
    t = len(ids)
    blk = jnp.arange(t) // dims["block_length"]
    return _forward(weights, _embed(weights, ids, masked, dims),
                    jnp.arange(t), blk[None, :] <= blk[:, None], dims, quant)


def logits_two_stream(weights, ids, masked, dims, quant=None):
    """[T, vocab rows]: the logits of a NOISY copy of ``ids`` (``[MASK]`` at
    ``masked``) whose every block sees the clean blocks before it and its
    own noisy positions: each block's pass over its own state, all in one
    forward of 2T rows."""
    t = len(ids)
    blk = jnp.arange(t) // dims["block_length"]
    before, own = blk[None, :] < blk[:, None], blk[None, :] == blk[:, None]
    keep = jnp.concatenate([
        jnp.concatenate([before | own, jnp.zeros((t, t), bool)], 1),
        jnp.concatenate([before, own], 1)], 0)
    x = jnp.concatenate([_embed(weights, ids, None, dims),
                         _embed(weights, ids, masked, dims)], 0)
    pos = jnp.concatenate([jnp.arange(t), jnp.arange(t)])
    return _forward(weights, x, pos, keep, dims, quant, last=t)


def greedy(rows, positions):
    """The default ``choose`` of ``generate``: each row's arg-max over the
    real vocabulary and its soft-max probability."""
    ids = rows.argmax(-1)
    top = rows.max(-1, keepdims=True)
    conf = 1.0 / np.exp(rows - top).sum(-1)
    return ids, conf


def fix(conf, flags, n):
    """The positions a pass fixes: of the flagged ones the ``n`` most
    confident (all of them where fewer are flagged), ties to the lower
    position."""
    order = np.argsort(-np.where(flags, conf, -np.inf), kind="stable")
    take = np.zeros_like(flags)
    take[order[:min(n, int(flags.sum()))]] = True
    return take


def generate(weights, prompt, max_new, steps, dims, eos=None, choose=greedy,
             pad_to=None):
    """The published loop, a whole forward a pass (of ``pad_to`` positions
    where given, the blocks past the current one padding that no position
    before them keeps: one compiled layer for every pass). Returns ``(ids,
    fixed_at, rows)``: the ``max_new`` generated ids (fewer where ``eos``
    came: it is the last), the pass of its block at which each was fixed,
    and the float32 logits row each was chosen from.
    ``choose(rows [B, vocab], positions [B]) -> (ids, confidences)``."""
    b, vocab = dims["block_length"], dims["vocab"]
    n = b // steps
    seq = [int(x) for x in prompt]
    start = len(seq) // b * b
    out, fixed_at, rows_of = [], [], []
    while True:
        ids = np.zeros(b, np.int64)
        flags = np.ones(b, bool)
        held = seq[start:]
        ids[:len(held)], flags[:len(held)] = held, False
        at, row = np.zeros(b, np.int64), [None] * b
        for p in range(steps):
            if not flags.any():
                break
            full = np.zeros(max(pad_to or 0, start + b), np.int32)
            mask = np.zeros(len(full), bool)
            full[:start], full[start:start + b] = seq[:start], ids
            mask[start:start + b] = flags
            lg = np.asarray(logits(weights, full, dims, masked=mask)
                            )[start:start + b, :vocab]
            x0, conf = choose(lg, start + np.arange(b))
            take = fix(np.asarray(conf), flags, n)
            ids[take], at[take] = np.asarray(x0)[take], p
            for j in np.flatnonzero(take):
                row[j] = lg[j]
            flags &= ~take
        for j in range(len(held), b):
            out.append(int(ids[j]))
            fixed_at.append(int(at[j]))
            rows_of.append(row[j])
            if len(out) == max_new or (eos is not None and out[-1] == eos):
                return np.asarray(out), np.asarray(fixed_at), \
                    np.stack(rows_of)
        seq = seq[:start] + [int(x) for x in ids]
        start += b
