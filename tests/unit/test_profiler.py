"""Flops profiler tests: analytic jaxpr counts vs hand-computed FLOPs,
scan trip-count handling, model profile sanity vs the 6N rule, and the
engine's profile_step hook (reference tests/unit/profiling)."""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.profiling.flops_profiler import (FlopsProfiler,
                                                    get_model_profile,
                                                    jaxpr_flops)

TINY = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                  n_head=4, pad_vocab_to_multiple=8)


def test_matmul_flops_exact():
    a = jnp.zeros((4, 8))
    b = jnp.zeros((8, 16))
    jaxpr = jax.make_jaxpr(lambda a, b: a @ b)(a, b)
    assert jaxpr_flops(jaxpr) == 2 * 4 * 16 * 8


def test_batched_matmul_flops():
    a = jnp.zeros((3, 4, 8))
    b = jnp.zeros((3, 8, 16))
    jaxpr = jax.make_jaxpr(
        lambda a, b: jnp.einsum("bij,bjk->bik", a, b))(a, b)
    assert jaxpr_flops(jaxpr) == 2 * 3 * 4 * 16 * 8


def test_scan_multiplies_by_length():
    w = jnp.zeros((5, 8, 8))
    x = jnp.zeros((8,))

    def f(w, x):
        def body(h, wi):
            return wi @ h, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    jaxpr = jax.make_jaxpr(f)(w, x)
    assert jaxpr_flops(jaxpr) == 5 * 2 * 8 * 8


def test_elementwise_and_breakdown():
    x = jnp.zeros((10, 10))
    jaxpr = jax.make_jaxpr(lambda x: jnp.tanh(x @ x) + 1.0)(x, )
    breakdown = {}
    total = jaxpr_flops(jaxpr, breakdown)
    assert breakdown["dot_general"] == 2 * 10 * 10 * 10
    assert breakdown["tanh"] == 100
    assert total >= breakdown["dot_general"] + 200


def test_model_profile_close_to_analytic_rule():
    model = GPT2Model(TINY)
    batch = {"input_ids": np.zeros((2, 32), np.int32)}
    prof = get_model_profile(model, batch)
    assert prof["params"] > 0
    # forward ≈ 2 * N * tokens (+attention); must be within sane bounds
    approx_fwd = 2 * prof["params"] * 2 * 32
    assert 0.5 * approx_fwd < prof["flops"] < 8 * approx_fwd, \
        (prof["flops"], approx_fwd)
    assert prof["per_primitive"]["dot_general"] > 0


def test_engine_profile_step_hook(tmp_path):
    out_file = str(tmp_path / "flops.txt")
    model = GPT2Model(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
        "flops_profiler": {"enabled": True, "profile_step": 1,
                           "output_file": out_file},
    })
    rng = np.random.default_rng(0)
    for _ in range(3):
        engine.train_batch(batch={"input_ids": rng.integers(
            0, 255, (1, 8, 16), np.int32)})
    assert os.path.isfile(out_file)
    text = open(out_file).read()
    assert "dot_general" in text and "flops" in text
    assert "latency" in text


def test_report_formatting():
    prof = {"flops": 3.2e12, "macs": 1.6e12, "xla_flops": None,
            "per_primitive": {"dot_general": 3e12, "tanh": 2e9}}
    text = FlopsProfiler().report(prof, params=125_000_000, latency_s=0.05)
    assert "3.20 T" in text
    assert "125.00 M" in text
    assert "64.00 T" in text  # 3.2e12/0.05 achieved FLOPS


# ------------------------- round-5: per-phase attribution (verdict #7)

def test_per_phase_attribution_gpt2():
    """The phase tree (reference profiler.py:239 module tree): embed/attn/
    mlp/head each get nonzero FLOPs, sum(phases) == total, and mlp:attn
    reflects the architecture (4x wider MLP dominates at short seq)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=8))
    prof = get_model_profile(model, {"input_ids": np.zeros((2, 32), np.int32)})
    phases = prof["per_phase"]
    for ph in ("attn", "mlp", "head"):
        assert phases.get(ph, 0) > 0, (ph, phases)
    assert sum(phases.values()) == prof["flops"]
    assert phases["mlp"] > phases["attn"] * 0.5


def test_phase_tree_in_report():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=8))
    prof = get_model_profile(model, {"input_ids": np.zeros((2, 32), np.int32)})
    text = FlopsProfiler().report(prof, latency_s=0.01)
    assert "model tree" in text
    assert "attn" in text and "mlp" in text and "head" in text
    assert "flops-proportional" in text  # honest wall label without a trace


def test_measured_wall_fractions_label():
    prof = {"flops": 100, "macs": 50, "xla_flops": None,
            "per_primitive": {"dot_general": 100},
            "per_phase": {"attn": 60, "mlp": 30, "embed": 10}}
    text = FlopsProfiler().report(prof, wall_fractions={"attn": 0.7,
                                                        "mlp": 0.3})
    assert "measured" in text and "70.0% wall" in text
    # a phase the trace didn't see must NOT print its flops share as wall
    embed_line = next(ln for ln in text.splitlines()
                      if ln.strip().startswith("embed"))
    assert "n/a" in embed_line


def test_model_shape_from_profile_feeds_autotuner():
    from deepspeed_tpu.autotuning.cost_model import (
        model_shape_from_profile, predict_throughput)
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=8))
    shape = model_shape_from_profile(
        model, {"input_ids": np.zeros((2, 32), np.int32)}, seq_len=32)
    assert shape.fwd_flops_per_sample and shape.fwd_flops_per_sample > 0
    assert shape.attn_fraction and 0 < shape.attn_fraction < 1
    with_attn = predict_throughput(shape, micro_bs=8, stage=2)
    import dataclasses as dc
    without = predict_throughput(dc.replace(shape, attn_fraction=None),
                                 micro_bs=8, stage=2)
    assert 0 < with_attn < without  # VPU-bound attention lowers the prior


def test_per_phase_attribution_survives_autodiff():
    """The engine profiles the TRAIN step (contains jax.grad): autodiff
    wraps name-stack segments as 'jvp(attn)'/'transpose(jvp(attn))', and
    attribution must still land on the phases, not 'other'."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, pad_vocab_to_multiple=8))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"input_ids": np.zeros((2, 32), np.int32)}

    def loss_and_grad(p, b):
        return jax.value_and_grad(
            lambda q: model.apply(q, b, rng=None, train=False))(p)

    prof = FlopsProfiler().profile(loss_and_grad, params, batch)
    phases = prof["per_phase"]
    for ph in ("attn", "mlp", "head"):
        assert phases.get(ph, 0) > 0, (ph, phases)
    assert phases.get("other", 0) < prof["flops"] * 0.5, phases
