"""Seeded weights in ``deepspeed_tpu.models.kexaone.KExaoneModel``'s tree layout.

As ``weights_lfm2.py`` for LFM2's tree: the benchmark draws the values from
``--seed`` and hands them to the program by overriding ``model.init``; the
reference (``reference_kexaone.py``) is given the same tree. The layout is
per KIND of layer (``blocks/window`` ``[Lw, ...]`` and ``blocks/full``
``[Lf, ...]``, the attention sublayers by kind; ``blocks/dense``
``[Ld, ...]``, ``blocks/moe`` ``[Lm, ...]`` with the HELD experts
``[Lm, experts, ...]``, the router ``[Lm, d, router_experts]`` and the shared
expert ``[Lm, d, f]``), in layer order within a kind; which layer is of
which kind is ``dims["layer_types"]`` and ``dims["dense_layers"]``.

Nothing at zero or one that a dropped term could hide behind: every norm gain
(the per-head q and k gains, the gains on the sublayers' outputs) is random
around 1; the router gives logits of about unit spread (its matrix is drawn
at half of 1 / sqrt(d): the residual it reads is not normalised and grows
with depth), so the sigmoid scores differ and do not saturate; the selection
bias is small and non-zero (spread ``BIAS_SPREAD``, about the gap between the
eighth and ninth largest of 128 scores, so it changes which experts are
chosen for a good share of the tokens while the weights stay the scores').
Both sizes are set so that the scores, not the bias, make the choice: with
saturated scores and a bias of 0.02 the bias decided it, the same few experts
took most rows of every request, and the share of the picks that fell on this
chip's sixteen moved 13% from seed to seed (std; 0.80-1.18 of the expected
``experts / router_experts``), and with it the decode program (15.7-17.2 ms;
my chip runs, PR 38). As drawn now the share still moves by the request
(0.90-1.12 of the expected over ten sequences of 512 tokens, by sequence
more than by seed: tokens of one sequence share what attention adds to their
residual, so they choose alike) and the decode program reads 17.5-17.9 ms.
The family normalises every sublayer's OUTPUT, so
each sublayer adds a row of about unit size to the residual: the embedding
is drawn at unit size too (at 0.02 the token itself would be a fiftieth of
what the first sublayer adds to it).

The held experts of a layer share their layer's mean expert
(``weights_lfm2.SHARED`` of every matrix's variance), for ``weights_lfm2.py``'s
reason: a pick that bf16 rounding flips between two HELD experts exchanges
two that compute nearly the same. A pick that flips between a held expert
and an absent one adds or removes a whole expert's term (about 2.5 / 8 of
the shared expert's weight, before the output norm), and nothing in the
weights can soften that: what the absent experts compute is not on this
chip. So the held experts' output matrices are drawn at half the shared
expert's spread: ``token_argmax_gap`` is a maximum over some 1,800 tokens
and reads the largest such flip (0.069-0.093 at the full spread, 0.017-0.061
at half; my chip runs, PR 38). The term is still held: left out altogether it
reads 0.150 in ``logits_rel_rms_err`` against a limit of 0.0225, and with the
layer taking its experts for the next chip's 0.158 (planted through the
job's own comparison on the chip; the cell file's ``assumed.check``). The
shared expert is a draw of its own.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import engine_seed, seed_key      # noqa: F401
from chipbench.weights_lfm2 import SHARED, table_rows    # noqa: F401

BIAS_SPREAD = 0.005


def kinds(dims):
    """How many layers there are of each kind: window attention, full
    attention, dense FFNs, routed FFNs."""
    types = dims["layer_types"]
    window = sum(t == "sliding_attention" for t in types)
    return window, len(types) - window, dims["dense_layers"], \
        len(types) - dims["dense_layers"]


def make(dims, key, positions=None, vocab_multiple=128):
    """float32 parameters in the program's tree layout, on the default
    device(s); jit it with ``out_shardings`` to make them sharded from birth."""
    d, e, f = dims["d_model"], dims["experts"], dims["expert_ff"]
    hd, m, h = dims["head_dim"], dims["dense_ff"], dims["heads"]
    lw, lf, ld, lm = kinds(dims)
    assert lm == dims["layers"], "dims.layers counts the ROUTED layers"
    # 0.02 at the published width, and the same spread of every matmul's
    # OUTPUT at the rehearsal's
    std = 0.02 * math.sqrt(6144 / d)
    proj_std = std / math.sqrt(2 * len(dims["layer_types"]))
    ks = iter(jax.random.split(key, 40))

    def n(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    def experts(a, b, s):
        return math.sqrt(SHARED) * n((lm, 1, a, b), s) + \
            math.sqrt(1 - SHARED) * n((lm, e, a, b), s)

    def attention(l):
        return {"qkv_w": n((l, d, (h + 2 * dims["kv_heads"]) * hd), std),
                "q_norm_scale": 1.0 + n((l, hd), 0.1),
                "k_norm_scale": 1.0 + n((l, hd), 0.1),
                "attn_proj_w": n((l, h * hd, d), proj_std),
                "post_attn_scale": 1.0 + n((l, d), 0.1)}

    fs = f * dims["shared_experts"]
    blocks = {
        "window": attention(lw),
        "full": attention(lf),
        "dense": {"gate_w": n((ld, d, m), std),
                  "up_w": n((ld, d, m), std),
                  "down_w": n((ld, m, d), proj_std),
                  "post_mlp_scale": 1.0 + n((ld, d), 0.1)},
        "moe": {"post_mlp_scale": 1.0 + n((lm, d), 0.1),
                "moe": {
                    "gate": {"wg": n((lm, d, dims["router_experts"]),
                                     0.5 / math.sqrt(d)),
                             "bias": n((lm, dims["router_experts"]),
                                       BIAS_SPREAD)},
                    "experts": {"w_gate": experts(d, f, std),
                                "w_up": experts(d, f, std),
                                "w_down": experts(f, d, proj_std / 2)},
                    "shared": {"w_gate": n((lm, d, fs), std),
                               "w_up": n((lm, d, fs), std),
                               "w_down": n((lm, fs, d), proj_std)}}},
    }
    rows = table_rows(dims, vocab_multiple)
    return {"wte": n((rows, d), 1.0), "lm_head": n((rows, d), 0.02),
            "blocks": blocks, "ln_f_scale": 1.0 + n((d,), 0.1)}
