"""LLaMA family tests: trains through the engine (ZeRO-3 + TP rules),
generates through the KV cache (GQA), rotary matches the HF rotate_half
convention via logits parity with a tiny HF LlamaForCausalLM."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

TINY = LlamaConfig(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                   n_head=4, n_kv_head=2, mlp_hidden=96,
                   pad_vocab_to_multiple=8)


def test_llama_trains_and_zero3():
    model = LlamaModel(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "steps_per_print": 0})
    rng = np.random.default_rng(0)
    losses = [float(engine.train_batch(batch={
        "input_ids": rng.integers(0, 255, (1, 8, 16), np.int32)}))
        for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # untied head + no position table
    assert "lm_head" in engine.param_shapes
    assert "wpe" not in engine.param_shapes


def test_llama_generates_with_gqa_cache():
    import jax
    model = LlamaModel(TINY)
    params = model.init(jax.random.PRNGKey(0))
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    eng = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
        {"dtype": "float32", "max_tokens": 64}), params=params)
    out = np.asarray(eng.generate(np.arange(8, dtype=np.int32)[None],
                                  max_new_tokens=4))
    assert out.shape == (1, 12)
    # cache carries n_kv_head (not n_head) heads
    cache = model.init_kv_cache(1, 16)
    assert cache["k"].shape[3] == TINY.n_kv_head


def test_llama_cache_matches_full_forward():
    """Prefill+decode logits == full forward logits (rotary offsets line
    up across the cache boundary)."""
    import jax
    import jax.numpy as jnp
    model = LlamaModel(TINY)
    params = model.init(jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, 255, (2, 10)).astype(np.int32)
    full = model.logits(params, jnp.asarray(ids), train=False)

    cache = model.init_kv_cache(2, 16, dtype=jnp.float32)
    pre, cache = model.apply_with_cache(params, jnp.asarray(ids[:, :7]),
                                        cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :7]),
                               atol=1e-4)
    for i in range(7, 10):
        step, cache = model.apply_with_cache(params, jnp.asarray(ids[:, i:i+1]),
                                             cache, i)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, i]), atol=1e-4)


def test_hf_llama_injection_logits_parity():
    transformers = pytest.importorskip("transformers")
    import torch
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(0).integers(0, 128, (2, 12)).astype(np.int64)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids)).logits.numpy()
    eng = deepspeed_tpu.init_inference(hf, {"dtype": "float32"})
    got = np.asarray(eng(ids.astype(np.int32)))
    np.testing.assert_allclose(got[..., :128], ref, atol=2e-3)


def test_mistral_sliding_window_cache_matches_full():
    """Windowed training forward == windowed decode through the cache."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    cfg = dataclasses.replace(TINY, sliding_window=6)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(3))
    ids = np.random.default_rng(4).integers(0, 255, (2, 12)).astype(np.int32)
    full = model.logits(params, jnp.asarray(ids), train=False)

    cache = model.init_kv_cache(2, 16, dtype=jnp.float32)
    pre, cache = model.apply_with_cache(params, jnp.asarray(ids[:, :8]),
                                        cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :8]),
                               atol=1e-4)
    for i in range(8, 12):
        step, cache = model.apply_with_cache(params,
                                             jnp.asarray(ids[:, i:i+1]),
                                             cache, i)
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, i]), atol=1e-4)


def test_hf_mistral_sliding_window_injection_parity():
    transformers = pytest.importorskip("transformers")
    import torch
    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8)
    hf = transformers.MistralForCausalLM(hf_cfg).eval()
    ids = np.random.default_rng(5).integers(0, 128, (2, 14)).astype(np.int64)
    with torch.no_grad():
        ref = hf(torch.from_numpy(ids)).logits.numpy()
    eng = deepspeed_tpu.init_inference(hf, {"dtype": "float32"})
    assert eng.module.config.sliding_window == 8
    got = np.asarray(eng(ids.astype(np.int32)))
    np.testing.assert_allclose(got[..., :128], ref, atol=2e-3)
