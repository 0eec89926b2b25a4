"""Plain reference: GPT-2 / OPT forward pass, next-token loss and AdamW steps.

Straight ``jax.numpy`` in float32 with ``default_matmul_precision("highest")``
(on a TPU a float32 matmul is otherwise computed in bf16 passes): no kernel,
no cache, no batching, one sequence at a time, one jitted layer called
``layers`` times. Follows the published models: pre-LayerNorm decoder,
learned positions (OPT's are offset by 2), tanh-GELU (GPT-2 ``gelu_new``) or
ReLU (OPT) MLP, tied output head, mean next-token cross-entropy.

Departure, noted: the head multiplies by the table it is given. The program
pads the table to a multiple of 128 rows and lets the padded rows into its
softmax, so the benchmark hands both sides the padded table.

``quant`` is the control's hook: a function applied to both operands of
every matrix multiplication, in the backward pass too (``fp8``: what a later
PR in a lower precision than the configuration states would compute).
``None`` is the reference.

``train_losses`` follows the published AdamW (decoupled decay on every
parameter, bias-corrected moments, epsilon outside the root) in float32 from
the seeded weights: one sequence at a time, gradients summed, layers
recomputed in the backward pass, so that a 350M model's step takes 8.5 GB
of a 16 GB chip (the job frees its engine first).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def fp8(x):
    """Round to float8 e4m3 under one scale per tensor, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _qmm(a, b, quant):
    return quant(a) @ quant(b)


def _qmm_fwd(a, b, quant):
    return _qmm(a, b, quant), (a, b)


def _qmm_bwd(quant, saved, g):
    a, b = saved
    return quant(g) @ _t(quant(b)), _t(quant(a)) @ quant(g)


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def _mm(a, b, quant):
    """``a @ b``; under ``quant`` both operands of the product and of the two
    products of its backward pass are rounded first."""
    return a @ b if quant is None else _qmm(a, b, quant)


def _ln(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _act(x, name):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"reference has no activation {name!r}")


def _block(x, p, heads, act, eps, quant):
    """One pre-LN decoder block on one sequence. x: [T, d] float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t, d = x.shape
    hd = d // heads
    h = _ln(x, p["ln1_scale"], p["ln1_bias"], eps)
    qkv = _mm(h, p["qkv_w"], quant) + p["qkv_b"]
    q, k, v = (a.reshape(t, heads, hd).transpose(1, 0, 2)
               for a in jnp.split(qkv, 3, axis=-1))
    s = _mm(q, _t(k), quant) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = _mm(jax.nn.softmax(s, axis=-1), v, quant)
    o = o.transpose(1, 0, 2).reshape(t, d)
    x = x + _mm(o, p["attn_proj_w"], quant) + p["attn_proj_b"]
    h = _ln(x, p["ln2_scale"], p["ln2_bias"], eps)
    h = _act(_mm(h, p["mlp_fc_w"], quant) + p["mlp_fc_b"], act)
    return x + _mm(h, p["mlp_proj_w"], quant) + p["mlp_proj_b"]


_layer = jax.jit(_block, static_argnames=("heads", "act", "eps", "quant"))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, wte, scale, bias, eps, quant):
    x = _ln(x, scale.astype(jnp.float32), bias.astype(jnp.float32), eps)
    return _mm(x, wte.astype(jnp.float32).T, quant)


@functools.partial(jax.jit, static_argnames=("offset",))
def _embed(wte, wpe, ids, offset):
    pos = jnp.arange(ids.shape[0]) + offset
    return wte[ids].astype(jnp.float32) + wpe[pos].astype(jnp.float32)


def logits(weights, ids, dims, quant=None):
    """[T, table rows] float32 logits of one sequence ``ids`` [T]."""
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["wte"], weights["wpe"], jnp.asarray(ids, jnp.int32),
                   dims["pos_offset"])
        for i in range(dims["layers"]):
            p = jax.tree.map(lambda a: a[i], weights["blocks"])
            x = _layer(x, p, dims["heads"], dims["activation"],
                       dims["ln_eps"], quant)
        return _head(x, weights["wte"], weights["ln_f_scale"],
                     weights["ln_f_bias"], dims["ln_eps"], quant)


@jax.jit
def _nll_sum(lg, ids):
    lp = jax.nn.log_softmax(lg[:-1], axis=-1)
    return -jnp.take_along_axis(lp, ids[1:, None], axis=1).sum()


def loss(weights, batch_ids, dims, quant=None):
    """Mean next-token cross-entropy over every sequence of ``batch_ids``
    ([..., T], any leading shape), as one global training step sees it."""
    rows = np.asarray(batch_ids).reshape(-1, np.shape(batch_ids)[-1])
    total = 0.0
    for ids in rows:
        ids = jnp.asarray(ids, jnp.int32)
        total += float(_nll_sum(logits(weights, ids, dims, quant), ids))
    return total / (rows.shape[0] * (rows.shape[1] - 1))


def _sequence_nll(w, ids, dims, quant):
    """Summed next-token cross-entropy of one sequence, differentiable:
    the same arithmetic as ``logits`` with the layers under one scan."""
    x = w["wte"][ids] + w["wpe"][jnp.arange(ids.shape[0]) + dims["pos_offset"]]
    layer = jax.checkpoint(lambda x, p: (_block(
        x, p, dims["heads"], dims["activation"], dims["ln_eps"], quant), None))
    x, _ = jax.lax.scan(layer, x, w["blocks"])
    x = _ln(x, w["ln_f_scale"], w["ln_f_bias"], dims["ln_eps"])
    lp = jax.nn.log_softmax(_mm(x, w["wte"].T, quant)[:-1], axis=-1)
    return -jnp.take_along_axis(lp, ids[1:, None], axis=1).sum()


def _step(dims, opt, quant):
    b1, b2 = opt.get("betas", (0.9, 0.999))
    eps, lr, decay = opt.get("eps", 1e-8), opt["lr"], opt.get("weight_decay", 0.0)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, count, rows):
        """Mean loss of ``rows`` [n, T] on ``w``, and ``w`` after one AdamW
        step on that loss's gradient."""
        def add(carry, ids):
            nll, g = jax.value_and_grad(_sequence_nll)(w, ids, dims, quant)
            return (carry[0] + nll, jax.tree.map(jnp.add, carry[1], g)), None
        (nll, g), _ = jax.lax.scan(
            add, (0.0, jax.tree.map(jnp.zeros_like, w)), rows)
        tokens = rows.shape[0] * (rows.shape[1] - 1)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g / tokens, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * (g / tokens) ** 2, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        w = jax.tree.map(lambda w, m, v: w - lr * (
            (m / c1) / (jnp.sqrt(v / c2) + eps) + decay * w), w, m, v)
        return nll / tokens, w, m, v
    return step


def train_losses(weights, batches, dims, opt, quant=None):
    """The loss of each batch of ``batches`` ([..., T] token ids each, one
    global step) on the weights the steps before it left, under AdamW with
    ``opt`` (``lr``, ``weight_decay``, optionally ``betas``, ``eps``)."""
    step = _step(dims, opt, quant)
    w = jax.tree.map(lambda a: a.astype(jnp.float32) + 0, weights)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i, batch in enumerate(batches, 1):
            rows = jnp.asarray(batch, jnp.int32).reshape(-1, np.shape(batch)[-1])
            loss, w, m, v = step(w, m, v, jnp.float32(i), rows)
            losses.append(float(loss))
    return losses
