"""Seeded weights in ``deepspeed_tpu.models.xing.XingModel``'s tree layout.

As ``weights_kexaone.py`` for K-EXAONE's tree: the benchmark draws the values
from ``--seed`` and hands them to the program by overriding ``model.init``;
the reference (``reference_xing.py``) is given the same tree. Per-kind
stacks: ``blocks/attn`` ``[L, ...]`` (every layer's latent attention and the
maps of its attention sublayer, ``hc_attn``), ``blocks/dense`` ``[Ld, ...]``
and ``blocks/moe`` ``[Lm, ...]`` (the feed-forwards, each with the maps of
its sublayer, ``hc_mlp``; the HELD experts ``[Lm, experts, ...]``, the router
``[Lm, d, router_experts]``, the shared expert), in layer order within a
kind; the first ``dims["dense_layers"]`` layers are the dense ones.

Nothing at zero or one that a dropped term could hide behind:

- every norm gain (input norms, the two latent norms, the final one) random
  around 1;
- **the maps** (``phi`` [n d, n (n + 2)], ``b``, three gates ``alpha``): the
  flattened streams are normalised, so ``phi`` at ``HC_SPREAD / sqrt(n d)``
  gives pre-activations of spread ``HC_SPREAD`` (the n * n of ``H_res``:
  ``HC_RES_SPREAD``): the maps differ from token to token and none
  saturates; the gates lie in 0.3 ... 1; ``b_pre`` and ``b_post`` have unit
  spread, ``b_post`` about a mean of zero over a sublayer's streams: the
  streams are written with weights ``H_post`` = 2 sigmoid(.) that differ
  (0.24 ... 1.76) and whose SUM stays near n (drawn freely, the weight a
  sublayer's output has in the closing sum moved by the draw of four
  numbers, and how far a rounding early in the stack is carried to the
  logits went with it: ``logits_rel_rms_err`` read 0.028 ... 0.060 over
  ten seeds on the chip, the same program in int8 1.78 ... 2.01 times its
  own seed's reading and so 0.055 ... 0.106: no limit lay between the two;
  PERF.md, PR 46); ``b_res`` is random (``HC_RES_BIAS``) with ``HC_DIAGONAL`` added
  on the diagonal, so that after the Sinkhorn steps ``H_res`` keeps about
  half of a stream in place and mixes the rest: it is no identity (a model
  that skipped the mix would read as another), no uniform average and no
  permutation; a whole ROW of ``b_res`` also carries an offset of its own
  (``HC_ROW_OFFSET``): a scaling of rows that the converged map forgets and
  ONE Sinkhorn step does not (its map lies 12-40% from the converged one,
  3-7% without the offsets), so the number of steps is held by the
  comparison and not by a test alone. The spreads of the ``H_res`` part
  are what lets 20 steps
  reach rows AND columns that sum to 1 within 1e-5 for every token (of
  200,000 drawn; at unit spreads and a diagonal of 2 the slowest token's
  columns were 1.6e-2 off after 20 steps: a matrix near a permutation
  converges slowly);
- the latent projections at the spread of every other matrix but ``q_b_w``,
  drawn twice as wide: the scores then have a spread near 1 and the softmax
  is neither uniform nor one-hot, so a key one column off, or a score
  without its rotary term, moves the output;
- router, selection bias, held experts (sharing their layer's mean expert)
  and shared expert as ``weights_kexaone.py`` draws them, for its reasons,
  but the routed output matrices at a QUARTER of the shared expert's
  spread where K-EXAONE's are at half: a pick that rounding flips between
  a held and an absent expert adds or removes a whole term times
  ``routed_scaling_factor`` = 2 here (the sublayer's input
  is normalised here, so the router is drawn at ``1 / sqrt(d)``).
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import engine_seed, seed_key      # noqa: F401
from chipbench.weights_lfm2 import SHARED, table_rows    # noqa: F401

BIAS_SPREAD = 0.005
HC_SPREAD = 0.7
HC_RES_SPREAD = 0.3
HC_RES_BIAS = 0.5
HC_DIAGONAL = 1.0
HC_ROW_OFFSET = 1.5


def make(dims, key, positions=None, vocab_multiple=128):
    """float32 parameters in the program's tree layout, on the default
    device(s); jit it with ``out_shardings`` to make them sharded from birth."""
    d, e, f = dims["d_model"], dims["experts"], dims["expert_ff"]
    h, m, n = dims["heads"], dims["dense_ff"], dims["streams"]
    ld, lm = dims["dense_layers"], dims["layers"]
    l = ld + lm
    qk, c = dims["nope_dim"] + dims["rope_dim"], dims["kv_rank"]
    # 0.02 at the published width, and the same spread of every matmul's
    # OUTPUT at the rehearsal's
    std = 0.02 * math.sqrt(3584 / d)
    proj_std = std / math.sqrt(2 * l)
    ks = iter(jax.random.split(key, 64))

    def nrm(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    def experts(a, b, s):
        return math.sqrt(SHARED) * nrm((lm, 1, a, b), s) + \
            math.sqrt(1 - SHARED) * nrm((lm, e, a, b), s)

    def hyper(layers):
        b_res = nrm((layers, n, n), HC_RES_BIAS) + HC_DIAGONAL * jnp.eye(n) \
            + nrm((layers, n, 1), HC_ROW_OFFSET)
        spread = jnp.asarray([HC_SPREAD] * 2 * n + [HC_RES_SPREAD] * n * n)
        b_post = nrm((layers, n), 1.0)
        b_post = b_post - b_post.mean(axis=1, keepdims=True)
        return {"phi": nrm((layers, n * d, n * (n + 2)),
                           1.0 / math.sqrt(n * d)) * spread,
                "b": jnp.concatenate([nrm((layers, n), 1.0), b_post,
                                      b_res.reshape(layers, n * n)], axis=1),
                "alpha": jax.random.uniform(next(ks), (layers, 3),
                                            jnp.float32, 0.3, 1.0)}

    fs = f * dims["shared_experts"]
    blocks = {
        "attn": {
            "ln1_scale": 1.0 + nrm((l, d), 0.1),
            "q_a_w": nrm((l, d, dims["q_rank"]), std),
            "q_a_scale": 1.0 + nrm((l, dims["q_rank"]), 0.1),
            "q_b_w": nrm((l, dims["q_rank"], h * qk), 2 * std),
            "kv_a_w": nrm((l, d, c + dims["rope_dim"]), std),
            "kv_a_scale": 1.0 + nrm((l, c), 0.1),
            "kv_b_w": nrm((l, c, h * (dims["nope_dim"] + dims["v_dim"])),
                          std),
            "attn_proj_w": nrm((l, h * dims["v_dim"], d), proj_std),
            "hc_attn": hyper(l)},
        "dense": {"ln2_scale": 1.0 + nrm((ld, d), 0.1),
                  "gate_w": nrm((ld, d, m), std),
                  "up_w": nrm((ld, d, m), std),
                  "down_w": nrm((ld, m, d), proj_std),
                  "hc_mlp": hyper(ld)},
        "moe": {"ln2_scale": 1.0 + nrm((lm, d), 0.1),
                "hc_mlp": hyper(lm),
                "moe": {
                    "gate": {"wg": nrm((lm, d, dims["router_experts"]),
                                       1.0 / math.sqrt(d)),
                             "bias": nrm((lm, dims["router_experts"]),
                                         BIAS_SPREAD)},
                    "experts": {"w_gate": experts(d, f, std),
                                "w_up": experts(d, f, std),
                                "w_down": experts(f, d, proj_std / 4)},
                    "shared": {"w_gate": nrm((lm, d, fs), std),
                               "w_up": nrm((lm, d, fs), std),
                               "w_down": nrm((lm, fs, d), proj_std)}}},
    }
    rows = table_rows(dims, vocab_multiple)
    return {"wte": nrm((rows, d), 0.02), "lm_head": nrm((rows, d), 0.02),
            "blocks": blocks, "ln_f_scale": 1.0 + nrm((d,), 0.1)}
