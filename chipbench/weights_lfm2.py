"""Seeded weights in ``deepspeed_tpu.models.lfm2.LFM2MoEModel``'s tree layout.

As ``weights.py`` for the GPT-2 tree and ``weights_olmoe.py`` for OLMoE's:
the benchmark draws the values from ``--seed`` and hands them to the program
by overriding ``model.init``; the reference (``reference_lfm2.py``) is given
the same tree. The layout is per KIND of layer (``blocks/conv`` ``[Lc, ...]``,
``blocks/attn`` ``[La, ...]``, ``blocks/dense`` ``[Ld, ...]``, ``blocks/moe``
``[Lm, ...]`` with the experts ``[Lm, E, ...]``), in layer order within a
kind; which layer is of which kind is ``dims["layer_types"]`` and
``dims["dense_layers"]``.

Nothing at zero or one that a dropped term could hide behind: every norm
gain (the per-head q and k gains too) is random around 1; the router gives
logits of about unit spread, so the sigmoid scores differ; the expert bias
is drawn with a spread of 0.05, several times the gap between the fourth
and fifth largest score of 64, so that it changes which experts are chosen
for a good share of the tokens (``tests/chipbench/test_chipbench_lfm2.py``
holds that) while the weights stay the scores'; the conv filters are of
unit size over their taps.

The experts of a layer are NOT independent draws: each is its layer's mean
expert plus a part of its own that holds ``1 - SHARED`` of every matrix's
variance, as the experts of a trained layer share most of what they
compute. With independent experts the benchmark's comparison measured the
router's rounding and little else: four picks share a weight of 1, the gap
between the fourth and fifth largest of 64 biased scores is about an eighth
of the logits' spread whatever that spread is (a wider router widens gap
and rounding alike: tried), bf16 activations put an error of about a
hundredth of it on every logit, so about one (token, layer) in ten picks
another fourth expert than float32 does, and an independent one replaced a
quarter of the layer's output with something unrelated: ``engine.forward``
read 0.105-0.122 off the float32 reference on the chip where a dense model
reads 0.011, and the int8 control 1.4 times that (PERF.md section 6, PR
36). Now a pick that flips exchanges two experts whose outputs agree to
about 0.99, and the comparison reads the arithmetic again. What it still
cannot tell from rounding is WHICH expert of its layer a row went to; a row
that went to another layer's expert, to none, or through a wrong weight
reads as it did (each layer has a mean expert of its own), and on the CPU,
in float32, no pick flips and the unit tests hold the choice exactly.
"""

import math

import jax
import jax.numpy as jnp

from chipbench.weights import engine_seed, seed_key      # noqa: F401

BIAS_SPREAD = 0.05
#: the share of each expert matrix's variance that is its layer's mean
#: expert: outputs of two experts of a layer then agree to about SHARED**3
SHARED = 0.9967


def table_rows(dims, vocab_multiple=128):
    return -(-dims["vocab"] // vocab_multiple) * vocab_multiple


def kinds(dims):
    """How many layers there are of each kind: conv operators, attention
    operators, dense FFNs, routed FFNs."""
    types = dims["layer_types"]
    conv = sum(t == "conv" for t in types)
    return conv, len(types) - conv, dims["dense_layers"], \
        len(types) - dims["dense_layers"]


def make(dims, key, positions=None, vocab_multiple=128):
    """float32 parameters in the program's tree layout, on the default
    device(s); jit it with ``out_shardings`` to make them sharded from birth."""
    d, e, f = dims["d_model"], dims["experts"], dims["expert_ff"]
    hd, m, taps = dims["head_dim"], dims["dense_ff"], dims["conv_taps"]
    lc, la, ld, lm = kinds(dims)
    assert lm == dims["layers"], "dims.layers counts the ROUTED layers"
    # 0.02 at the published width, and the same spread of every matmul's
    # OUTPUT at the rehearsal's: with 0.02 at 128 channels the layers add
    # next to nothing to the embedding, and the tied head then answers
    # with the token it was fed, whatever the cache holds
    std = 0.02 * math.sqrt(2048 / d)
    proj_std = std / math.sqrt(2 * len(dims["layer_types"]))
    ks = iter(jax.random.split(key, 24))

    def n(shape, s):
        return jax.random.normal(next(ks), shape, jnp.float32) * s

    def experts(a, b, s):
        return math.sqrt(SHARED) * n((lm, 1, a, b), s) + \
            math.sqrt(1 - SHARED) * n((lm, e, a, b), s)

    qkv = (dims["heads"] + 2 * dims["kv_heads"]) * hd
    blocks = {
        "conv": {"ln1_scale": 1.0 + n((lc, d), 0.1),
                 "in_w": n((lc, d, 3 * d), std),
                 "conv_w": n((lc, d, taps), 1.0 / math.sqrt(taps)),
                 "out_w": n((lc, d, d), proj_std)},
        "attn": {"ln1_scale": 1.0 + n((la, d), 0.1),
                 "qkv_w": n((la, d, qkv), std),
                 "q_norm_scale": 1.0 + n((la, hd), 0.1),
                 "k_norm_scale": 1.0 + n((la, hd), 0.1),
                 "attn_proj_w": n((la, d, d), proj_std)},
        "dense": {"ln2_scale": 1.0 + n((ld, d), 0.1),
                  "gate_w": n((ld, d, m), std),
                  "up_w": n((ld, d, m), std),
                  "down_w": n((ld, m, d), proj_std)},
        "moe": {"ln2_scale": 1.0 + n((lm, d), 0.1),
                "moe": {
                    "gate": {"wg": n((lm, d, e), 1.0 / math.sqrt(d)),
                             "bias": n((lm, e), BIAS_SPREAD)},
                    # the four picks now add up, where four independent
                    # outputs added to half of one: half the spread keeps a
                    # routed layer's share of the residual (an eighth)
                    "experts": {"w_gate": experts(d, f, std),
                                "w_up": experts(d, f, std),
                                "w_down": experts(f, d, proj_std / 2)}}},
    }
    return {"wte": n((table_rows(dims, vocab_multiple), d), 0.02),
            "blocks": blocks,
            "ln_f_scale": 1.0 + n((d,), 0.1)}
