"""MoE serving: KV-cache decode parity, EP-sharded generation, and MoE
RLHF (hybrid engine train↔generate flip) — the reference's
DeepSpeedMoEInference capability (reference
ops/transformer/inference/moe_inference.py:160) on the TPU mesh."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel
from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

TINY = GPT2MoEConfig(vocab_size=256, n_positions=64, n_embd=32, n_layer=2,
                     n_head=4, num_experts=4, top_k=2,
                     pad_vocab_to_multiple=64)


def test_moe_decode_matches_dense_forward():
    """Cached prefill+decode logits == full forward of a no-drop model
    sharing the same params (the serving path routes every token, so the
    reference side must too — a drop_tokens=True reference would be
    seed-dependent)."""
    import dataclasses
    model = GPT2MoEModel(TINY)
    params = model.init(jax.random.PRNGKey(0))
    nodrop = GPT2MoEModel(dataclasses.replace(TINY, drop_tokens=False,
                                              use_rts=False))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 8)), jnp.int32)
    cache = model.init_kv_cache(2, 32, dtype=jnp.float32)
    logits, cache = model.apply_with_cache(params, prompt, cache, 0)
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    logits2, cache = model.apply_with_cache(params, tok, cache, 8)
    dense = nodrop.logits(params, jnp.concatenate([prompt, tok], -1),
                          train=False)
    np.testing.assert_allclose(np.asarray(logits2[:, -1]),
                               np.asarray(dense[:, -1]), atol=2e-4)


def test_routed_path_matches_the_dense_formula_and_the_nodrop_dispatch():
    """MOELayer.apply_routed (sorted pairs, grouped matmul) == every expert
    on every token times its top-k weight (what ``apply_dense`` computed,
    written out here as the formula) == the capacity dispatch path with
    drop_tokens=False (same gate weights, no capacity) — the serving
    path's numerics oracle."""
    from deepspeed_tpu.moe.sharded_moe import MOELayer, TopKGate, topk_route
    from deepspeed_tpu.moe.experts import ExpertFFN

    gate = TopKGate(16, 4, k=2, drop_tokens=False, use_rts=False)
    layer = MOELayer(gate, ExpertFFN(16, 32, 4),
                     use_sharding_constraints=False)
    params = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((10, 16)),
                    jnp.float32)
    y_nodrop, _, counts_n = layer.apply(params, x, train=False)
    y_routed, aux, counts_r = layer.apply_routed(params, x)
    w, idx = topk_route(x @ params["gate"]["wg"], 2)
    full = jnp.zeros((10, 4)).at[jnp.arange(10)[:, None], idx].set(w)
    every = layer.experts.apply(params["experts"],
                                jnp.broadcast_to(x[None], (4, 10, 16)))
    y_dense = jnp.einsum("se,esm->sm", full, every)
    np.testing.assert_allclose(np.asarray(y_routed), np.asarray(y_dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_routed), np.asarray(y_nodrop),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts_r),
                                  np.asarray(counts_n))
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    assert float(aux) == 0.0


def test_moe_generates_under_ep2():
    """A trained tiny MoE generates through InferenceEngine on an
    ep2 mesh (expert leaves sharded over 'expert')."""
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2MoEModel(TINY),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 2},
            "expert_parallel_size": 2,
            "steps_per_print": 0,
        })
    assert engine.mesh_manager.ep == 2
    rng = np.random.default_rng(0)
    for _ in range(3):
        engine.train_batch(batch={"input_ids": rng.integers(
            0, 256, (1, engine.dp_world_size * 2, 16), np.int32)})

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    icfg = DeepSpeedInferenceConfig.from_dict({"max_tokens": 64})
    ieng = InferenceEngine(engine.module, icfg, params=engine.params,
                           mesh_manager=engine.mesh_manager)
    # expert leaves really are EP-sharded in serving
    spec = ieng.params["blocks"]["moe"]["experts"]["wi"].sharding.spec
    assert "expert" in tuple(spec), spec
    prompt = rng.integers(0, 256, (4, 8)).astype(np.int32)
    out = np.asarray(ieng.generate(prompt, max_new_tokens=6,
                                   temperature=0.0))
    assert out.shape == (4, 14)
    np.testing.assert_array_equal(out[:, :8], prompt)
    assert ((out >= 0) & (out < 256)).all()


def test_moe_hybrid_engine_flip():
    """MoE RLHF: hybrid engine generates, trains, and generation follows
    the updated weights."""
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2MoEModel(TINY),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
            "expert_parallel_size": 2,
            "steps_per_print": 0,
            "hybrid_engine": {"enabled": True, "max_out_tokens": 64},
        })
    assert isinstance(engine, DeepSpeedHybridEngine)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 255, (2, 8)).astype(np.int32)
    out1 = np.asarray(engine.generate(prompt, max_new_tokens=6,
                                      temperature=0.0))
    assert out1.shape == (2, 14)
    for _ in range(8):
        engine.train_batch(batch={"input_ids": rng.integers(
            0, 255, (1, engine.dp_world_size, 16), np.int32)})
    out2 = np.asarray(engine.generate(prompt, max_new_tokens=6,
                                      temperature=0.0))
    assert not np.array_equal(out1, out2), \
        "MoE generation ignored the weight updates"
