"""Int8 weight-only quantized serving (round-3 missing #2).

Reference anchors: module_inject/replace_module.py:140 ``GroupQuantizer``
(weights quantized at injection), csrc/transformer/inference/csrc/
dequantize.cu:195 (dequant inside the serving GEMMs). The quant config keys
were previously accepted-and-ignored; these tests pin the accepted=active
contract.
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.inference.quantization import (QuantizedWeight,
                                                  is_quantized,
                                                  quantize_leaf,
                                                  tree_nbytes)
from deepspeed_tpu.runtime.config_utils import ConfigError

TINY = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                  n_head=4, pad_vocab_to_multiple=8)


@pytest.fixture(scope="module")
def model_and_params():
    model = GPT2Model(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def make_engine(model, params, **cfg):
    cfg.setdefault("dtype", "int8")
    return InferenceEngine(model,
                           DeepSpeedInferenceConfig.from_dict(cfg),
                           params=params)


def test_quantize_leaf_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 128)) * 0.05
    qw = quantize_leaf(w, group_size=32)
    assert qw.q.dtype == jnp.int8 if (jnp := jax.numpy) else True
    deq = np.asarray(qw.astype(np.float32))
    err = np.abs(deq - np.asarray(w))
    # symmetric 8-bit grouped: error bounded by scale/2 = max|w|/254 per group
    assert err.max() <= np.abs(np.asarray(w)).max() / 127
    assert qw.nbytes < w.nbytes / 2.5  # int8 payload + f32 scales


def test_int8_logits_parity_and_memory(model_and_params):
    model, params = model_and_params
    e_bf = make_engine(model, params, dtype="bfloat16")
    e_q = make_engine(model, params, quant={"group_size": 32})
    n_q = sum(1 for x in jax.tree.leaves(e_q.params, is_leaf=is_quantized)
              if is_quantized(x))
    # exactly the 4 stacked matmul weights (qkv, attn_proj, mlp_fc,
    # mlp_proj); stacked [L, d] norm/bias leaves must NOT be quantized
    assert n_q == 4
    for name in ("ln1_scale", "ln1_bias", "qkv_b", "mlp_fc_b"):
        assert not is_quantized(e_q.params["blocks"][name]), name

    ids = (np.arange(32, dtype=np.int32).reshape(2, 16) * 7) % 255
    lb = np.asarray(e_bf(ids), np.float32)
    lq = np.asarray(e_q(ids), np.float32)
    assert np.abs(lb - lq).mean() < 0.05, "int8 logits diverge from bf16"
    assert (lb.argmax(-1) == lq.argmax(-1)).mean() > 0.95

    # the memory claim: quantized blocks at ~half the bf16 bytes
    assert tree_nbytes(e_q.params["blocks"]) < \
        0.75 * tree_nbytes(e_bf.params["blocks"])
    # embeddings stay full precision (GroupQuantizer scope)
    assert not is_quantized(e_q.params["wte"])


def test_int8_generate_matches_bf16_greedy(model_and_params):
    model, params = model_and_params
    e_bf = make_engine(model, params, dtype="bfloat16")
    e_q = make_engine(model, params, quant={"group_size": 32})
    prompt = (np.arange(16, dtype=np.int32).reshape(1, 16) * 3) % 255
    out_bf = np.asarray(e_bf.generate(prompt, max_new_tokens=8))
    out_q = np.asarray(e_q.generate(prompt, max_new_tokens=8))
    assert out_q.shape == out_bf.shape == (1, 24)
    # greedy decode on near-identical logits: require most tokens equal
    assert (out_bf[:, 16:] == out_q[:, 16:]).mean() >= 0.75


def test_int8_under_tensor_parallel(model_and_params):
    model, params = model_and_params
    eng = make_engine(model, params, quant={"group_size": 32},
                      tensor_parallel={"tp_size": 2})
    ids = np.arange(16, dtype=np.int32).reshape(1, 16) % 255
    logits = np.asarray(eng(ids), np.float32)
    assert np.all(np.isfinite(logits))
    ref = make_engine(model, params, quant={"group_size": 32})
    np.testing.assert_allclose(logits, np.asarray(ref(ids), np.float32),
                               atol=2e-2, rtol=0.1)


def test_int8_dtype_key_activates_quant():
    cfg = DeepSpeedInferenceConfig.from_dict({"dtype": "int8"})
    assert cfg.quant is not None and cfg.quant.enabled
    import jax.numpy as jnp
    assert cfg.dtype == jnp.bfloat16  # compute stays bf16


def test_int8_rejects_unsupported_bits():
    with pytest.raises(ConfigError, match="bits=8"):
        DeepSpeedInferenceConfig.from_dict(
            {"quant": {"enabled": True, "bits": 4}})


def test_recast_requantizes_fp_refresh(model_and_params):
    """The hybrid-engine refresh path: fp training params recast into the
    quantized serving layout (RLHF serving stays int8 across updates)."""
    model, params = model_and_params
    eng = make_engine(model, params, quant={"group_size": 32})
    fresh = jax.tree.map(lambda x: x * 1.0, params)
    re = eng.recast(fresh)
    assert any(is_quantized(x)
               for x in jax.tree.leaves(re, is_leaf=is_quantized))
    # the one leaf-at-a-time path (``quantize_resident`` with a cast): the
    # trainer's tree is left alone, a second refresh compiles nothing, and
    # leaves that are int8 already pass through
    assert not any(x.is_deleted() for x in jax.tree.leaves(fresh))
    programs = dict(eng._leaf_fns)
    again = eng.recast(eng.recast(fresh))
    assert eng._leaf_fns == programs
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(re)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quantize_resident_is_quantize_tree_a_leaf_at_a_time(model_and_params):
    """What the engine does to parameters that are on the device already
    (its own init, a loaded checkpoint): the payloads and scales of
    ``quantize_tree``, stacked leaves a layer slice at a time, and every
    leaf it quantized is consumed, so the tree is never held twice."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.quantization import quantize_tree
    model, params = model_and_params
    want = quantize_tree(params)
    fresh = jax.tree.map(jnp.array, params)         # the copy to consume
    got = make_engine(model, params)._quantize_resident(fresh)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # jitted against eager: a scale may differ in its last bit, and a
        # value on a rounding boundary with it
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6,
                                   atol=1 if a.dtype == jnp.int8 else 0)
    kept = jax.tree.leaves(jax.tree.map(
        lambda x, q: x.is_deleted() == isinstance(q, QuantizedWeight),
        fresh, got, is_leaf=lambda x: isinstance(x, QuantizedWeight)))
    assert all(kept) and any(isinstance(x, QuantizedWeight)
                             for x in jax.tree.leaves(
                                 got, is_leaf=lambda x: isinstance(
                                     x, QuantizedWeight)))
