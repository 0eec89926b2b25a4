"""Config system tests — mirrors the batch-triangle and subsystem-config
behavior of reference runtime/config.py (tests modeled on
tests/unit/runtime/test_ds_config_dict.py)."""

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.config_utils import ConfigError


def test_batch_triangle_full():
    cfg = DeepSpeedConfig(
        {"train_batch_size": 32, "train_micro_batch_size_per_gpu": 2,
         "gradient_accumulation_steps": 2}, world_size=8)
    assert cfg.train_batch_size == 32
    assert cfg.data_parallel_size == 8


def test_batch_triangle_solve_gas():
    cfg = DeepSpeedConfig({"train_batch_size": 32,
                           "train_micro_batch_size_per_gpu": 2}, world_size=8)
    assert cfg.gradient_accumulation_steps == 2


def test_batch_triangle_solve_micro():
    cfg = DeepSpeedConfig({"train_batch_size": 32,
                           "gradient_accumulation_steps": 2}, world_size=8)
    assert cfg.train_micro_batch_size_per_gpu == 2


def test_batch_triangle_solve_train():
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 2,
                           "gradient_accumulation_steps": 4}, world_size=8)
    assert cfg.train_batch_size == 64


def test_batch_triangle_mismatch_raises():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({"train_batch_size": 33, "train_micro_batch_size_per_gpu": 2,
                         "gradient_accumulation_steps": 2}, world_size=8)


def test_batch_triangle_missing_raises():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({}, world_size=8)


def test_fp16_bf16_exclusive():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "fp16": {"enabled": True},
                         "bf16": {"enabled": True}}, world_size=8)


def test_zero_config_parsing():
    cfg = DeepSpeedConfig(
        {"train_batch_size": 8,
         "zero_optimization": {"stage": 2, "reduce_bucket_size": 1000,
                               "offload_optimizer": {"device": "cpu"}}},
        world_size=8)
    assert cfg.zero_config.stage == 2
    assert cfg.zero_config.reduce_bucket_size == 1000
    assert cfg.zero_config.offload_optimizer.device == "cpu"
    assert cfg.zero_enabled


def test_zero_invalid_stage():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {"stage": 5}}, world_size=8)


def test_zero_legacy_cpu_offload_flag():
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "zero_optimization": {"stage": 2, "cpu_offload": True}},
                          world_size=8)
    assert cfg.zero_config.offload_optimizer.device == "cpu"


def test_parallel_sizes_reduce_dp():
    cfg = DeepSpeedConfig({"train_batch_size": 8, "tensor_parallel_size": 2},
                          world_size=8)
    assert cfg.data_parallel_size == 4


def test_zero23_pp_incompatible():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "pipeline_parallel_size": 2,
                         "zero_optimization": {"stage": 2}}, world_size=8)


def test_optimizer_scheduler_sections():
    cfg = DeepSpeedConfig(
        {"train_batch_size": 8,
         "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
         "scheduler": {"type": "WarmupLR",
                       "params": {"warmup_num_steps": 10}}}, world_size=8)
    assert cfg.optimizer.type == "adamw"
    assert cfg.scheduler.type == "WarmupLR"


def test_unknown_zero_key_raises():
    with pytest.raises(ConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {"stage": 1, "bogus_key": 1}},
                        world_size=8)


@pytest.mark.parametrize("block", ("no_such_plane", "plane_that_was_removed"))
def test_a_block_nobody_defines(block):
    """What a config block meets once its subsystem is deleted and no shim
    is left (ISSUE 33 removed one so): the strict ``ServingConfig`` refuses
    it by name; ``DeepSpeedConfig`` reads no top-level block it does not
    know and keeps no attribute for it."""
    from deepspeed_tpu.serving.config import ServingConfig
    with pytest.raises(ConfigError, match=f"unknown config key.*{block}"):
        ServingConfig.from_dict({"num_slots": 2, block: {"enabled": True}})
    cfg = DeepSpeedConfig({"train_batch_size": 8, block: {"enabled": True}},
                          world_size=8)
    assert not hasattr(cfg, block)


def test_zero_plus_plus_knobs_raise():
    """zero_quantized_weights/gradients post-date the reference version and
    have no wired path — accepted config must be active config."""
    with pytest.raises(ConfigError, match="1-bit"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {
                             "stage": 2, "zero_quantized_gradients": True}},
                        world_size=8)


def test_gradient_accumulation_dtype_validates_at_parse():
    """gradient_accumulation_dtype validates at config parse (no engine
    needed); junk values raise there."""
    with pytest.raises(ConfigError, match="gradient_accumulation_dtype"):
        DeepSpeedConfig({"train_batch_size": 8,
                         "gradient_accumulation_dtype": "int8"},
                        world_size=8)
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "gradient_accumulation_dtype": "bf16"},
                          world_size=8)
    assert cfg.gradient_accumulation_dtype == "bf16"


@pytest.mark.slow
def test_gradient_accumulation_dtype_trains_bf16():
    """bf16 accumulation is actually consumed by the engine and trains."""
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    tiny = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                      n_head=4, pad_vocab_to_multiple=8)
    base = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}, "steps_per_print": 0}
    e, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2Model(tiny),
        config=dict(base, gradient_accumulation_dtype="bf16"))
    import jax.numpy as jnp
    assert e._grad_acc_dtype == jnp.bfloat16
    rng = np.random.default_rng(0)
    loss = float(e.train_batch(batch={
        "input_ids": rng.integers(0, 255, (2, 8, 32), dtype=np.int32)}))
    assert np.isfinite(loss)
