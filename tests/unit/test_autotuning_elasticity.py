"""Autotuning + elasticity tests (reference tests/unit/elasticity,
tests/unit/autotuning)."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.elasticity import (ElasticityConfigError,
                                      ElasticityIncompatibleWorldSize,
                                      compute_elastic_config,
                                      get_valid_gpus)


# ---------------------------------------------------------------- elasticity
def _cfg(**over):
    block = {"enabled": True, "max_train_batch_size": 64,
             "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 16,
             "version": 0.1}
    block.update(over)
    return {"elasticity": block}


def test_get_valid_gpus():
    gpus = get_valid_gpus(batch_size=16, micro_batches=[2, 4],
                          min_gpus=1, max_gpus=16)
    # 16/2=8 micro-steps: g in divisors of 8; 16/4=4: divisors of 4
    assert gpus == [1, 2, 4, 8]
    assert get_valid_gpus(16, [2], 1, 16, allowed=[4, 8, 32]) == [4, 8]


def test_compute_elastic_config_v01():
    batch, gpus = compute_elastic_config(_cfg())
    assert batch <= 64
    for g in gpus:
        per = batch // g
        assert batch % g == 0
        assert any(per % m == 0 for m in (2, 4))


def test_world_size_validation_v01():
    batch, gpus, micro = compute_elastic_config(_cfg(), world_size=gpusafe())
    assert micro in (2, 4)
    with pytest.raises(ElasticityIncompatibleWorldSize):
        compute_elastic_config(_cfg(max_train_batch_size=8,
                                    micro_batch_sizes=[8]), world_size=3)


def gpusafe():
    batch, gpus = compute_elastic_config(_cfg())
    return gpus[0]


def test_compute_elastic_config_v02_scales_batch():
    b4, g4, m4 = compute_elastic_config(_cfg(version=0.2), world_size=4)
    b8, g8, m8 = compute_elastic_config(_cfg(version=0.2), world_size=8)
    assert g4 == [4] and g8 == [8]
    assert b8 >= b4  # batch grows with world size
    assert b4 % (m4 * 4) == 0 and b8 % (m8 * 8) == 0


def test_elasticity_errors():
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({"elasticity": {"enabled": False}})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config(_cfg(micro_batch_sizes=[0]))


def test_tpu_slice_restriction():
    batch, gpus = compute_elastic_config(
        _cfg(allowed_world_sizes=[1, 2, 4, 8]))
    assert set(gpus) <= {1, 2, 4, 8}


# ---------------------------------------------------------------- autotuner
def test_autotuner_picks_best_with_fake_runner(tmp_path):
    from deepspeed_tpu.autotuning import Autotuner

    def fake_runner(cfg):
        micro = cfg["train_micro_batch_size_per_gpu"]
        stage = cfg["zero_optimization"]["stage"]
        if micro > 8:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return micro * 10 - stage  # best: micro=8, stage=0

    tuner = Autotuner(
        model_factory=lambda: None,
        base_config={"optimizer": {"type": "adamw"},
                     "autotuning": {"enabled": True,
                                    "micro_batch_sizes": [2, 8, 16, 32],
                                    "zero_stages": [0, 1]}},
        runner=fake_runner, results_dir=str(tmp_path))
    best = tuner.tune()
    assert best["train_micro_batch_size_per_gpu"] == 8
    assert best["zero_optimization"]["stage"] == 0
    # OOM pruning: per stage, micro=16 fails ONCE and micro=32 is never
    # attempted (the infeasible floor skips it)
    attempts = [(e.config["train_micro_batch_size_per_gpu"],
                 e.config["zero_optimization"]["stage"])
                for e in tuner.experiments]
    for stage in (0, 1):
        assert attempts.count((16, stage)) == 1
        assert attempts.count((32, stage)) == 0
    results = json.load(open(tmp_path / "autotuning.json"))
    assert results["best"]["metric"] == 80  # micro=8, stage=0


def test_autotuner_real_engine_smoke():
    """Two tiny real trials through deepspeed_tpu.initialize."""
    import deepspeed_tpu
    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    rng = np.random.default_rng(0)

    def batch_factory(global_bs):
        return {"input_ids": rng.integers(0, 255, (1, global_bs, 16),
                                          np.int32)}

    tuner = Autotuner(
        model_factory=lambda: GPT2Model(GPT2Config(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2, n_head=4,
            pad_vocab_to_multiple=8)),
        base_config={
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "autotuning": {"enabled": True, "micro_batch_sizes": [1, 2],
                           "zero_stages": [0], "start_profile_step": 1,
                           "end_profile_step": 3}},
        batch_factory=batch_factory)
    best = tuner.tune()
    assert best["train_micro_batch_size_per_gpu"] in (1, 2)
    assert all(e.feasible for e in tuner.experiments)


def test_autotuner_all_fail_raises():
    from deepspeed_tpu.autotuning import Autotuner
    tuner = Autotuner(model_factory=lambda: None, base_config={
        "autotuning": {"micro_batch_sizes": [1], "zero_stages": [0]}},
        runner=lambda cfg: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="every trial failed"):
        tuner.tune()


def test_engine_elasticity_guard():
    """Reference engine.py:482-491: a batch config outside the elastic plan
    is rejected unless ignore_non_elastic_batch_info."""
    import deepspeed_tpu
    from deepspeed_tpu.elasticity import ElasticityConfigError
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import topology

    tiny = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=1,
                      n_head=4, pad_vocab_to_multiple=8)
    # plan for micro [2,4], max 48: a fixed batch valid at world size 8;
    # the configured batch 24 deliberately differs from it
    el = {"enabled": True, "max_train_batch_size": 48,
          "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 8,
          "allowed_world_sizes": [1, 2, 4, 8]}
    from deepspeed_tpu.elasticity import compute_elastic_config
    plan_batch, _, _ = compute_elastic_config({"elasticity": el},
                                              world_size=8)
    assert plan_batch != 24
    base = {"train_batch_size": 24,
            "train_micro_batch_size_per_gpu": 3,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 0, "elasticity": el}
    with pytest.raises(ElasticityConfigError, match="elastic plan"):
        deepspeed_tpu.initialize(model=GPT2Model(tiny), config=base)
    topology.reset_mesh()
    ok = dict(base, elasticity=dict(el, ignore_non_elastic_batch_info=True))
    engine, _, _, _ = deepspeed_tpu.initialize(model=GPT2Model(tiny),
                                               config=ok)
    assert engine.train_batch_size == 24


# ------------------------------------------------------- model-based tuner

def _shape_125m():
    from deepspeed_tpu.autotuning.cost_model import ModelShape
    return ModelShape(n_params=124_500_000, hidden=768, n_layer=12,
                      seq_len=1024)


def test_cost_model_memory_feasibility():
    """The analytic memory model must know 1.3B optimizer state does not
    fit one chip without offload, but does WITH offload (what
    benchmarks/baseline_ladder.py 1p3b relies on)."""
    from deepspeed_tpu.autotuning.cost_model import (ModelShape,
                                                     estimate_memory_bytes)
    big = ModelShape(n_params=1_313_000_000, hidden=2048, n_layer=24,
                     seq_len=1024)
    hbm = 15.75e9
    assert estimate_memory_bytes(big, 4, stage=2, dp=1) > hbm
    assert estimate_memory_bytes(big, 4, stage=2, dp=1,
                                 offload_optimizer=True, remat=True) < hbm
    # 125M fits easily
    assert estimate_memory_bytes(_shape_125m(), 8, stage=0) < hbm


def test_model_based_tuner_prunes_and_converges():
    """ModelBasedTuner must (a) pre-prune over-HBM configs without
    spending trials, (b) find the best config in FEWER trials than grid
    order on a synthetic objective."""
    from deepspeed_tpu.autotuning.cost_model import ModelShape
    from deepspeed_tpu.autotuning.tuner import (GridSearchTuner,
                                                ModelBasedTuner)

    shape = ModelShape(n_params=1_313_000_000, hidden=2048, n_layer=24,
                       seq_len=1024)
    micros = [1, 2, 4, 8, 16]
    stages = [0, 1, 2, 3]
    candidates = [(m, s) for s in stages for m in micros]

    # synthetic truth: throughput grows with micro then saturates;
    # stage 1 is the sweet spot; big micros at low stages OOM
    def truth(m, s):
        if m * (4 - s) > 20:
            return None                      # OOM region
        base = m / (1 + 0.12 * m)
        return base * {0: 1.0, 1: 1.04, 2: 0.97, 3: 0.9}[s]

    feasible = {c: truth(*c) for c in candidates if truth(*c) is not None}
    best_cand = max(feasible, key=feasible.get)

    def run(tuner, budget):
        seen = []
        for _ in range(budget):
            c = tuner.next()
            if c is None:
                break
            v = truth(*c)
            tuner.update(c, v, oom=v is None)
            seen.append((c, v))
        vals = [v for _, v in seen if v is not None]
        return seen, (max(vals) if vals else None)

    mb = ModelBasedTuner(list(candidates), shape=shape,
                         hbm_budget_bytes=15.75e9, dp=8)
    # at dp=8, ZeRO>=1 shards the 15.7GB optimizer state across chips;
    # stage 0 (replicated state) still cannot fit and is pre-pruned
    assert all(s >= 1 for (_, s) in mb.remaining), mb.remaining
    assert mb.pruned
    budget = 6
    _, best_mb = run(mb, budget)
    _, best_grid = run(GridSearchTuner(list(candidates)), budget)
    assert best_mb is not None
    # grid spends its budget on stage 0 (pruned region + small micros);
    # the model-based tuner starts in the feasible high-throughput zone
    assert best_grid is None or best_mb >= best_grid


def test_autotuner_uses_tuner_type():
    """Autotuner with tuner_type=model + a synthetic runner explores in
    prior order and returns the best config."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.autotuning.cost_model import ModelShape

    calls = []

    def runner(cfg):
        m = cfg["train_micro_batch_size_per_gpu"]
        s = cfg["zero_optimization"]["stage"]
        calls.append((m, s))
        if m >= 16 and s < 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return m / (1 + 0.1 * m) * (1.05 if s == 1 else 1.0)

    at = Autotuner(
        model_factory=lambda: None,
        base_config={"autotuning": {
            "enabled": True, "tuner_type": "model", "max_trials": 8,
            "micro_batch_sizes": [1, 4, 8, 16],
            "zero_stages": [0, 1, 2]}},
        runner=runner,
        model_shape=ModelShape(n_params=124_500_000, hidden=768,
                               n_layer=12, seq_len=1024))
    best = at.tune()
    assert best["train_micro_batch_size_per_gpu"] in (8, 16)
    assert len(calls) <= 8


def test_random_tuner_is_seeded_permutation():
    from deepspeed_tpu.autotuning.tuner import RandomTuner
    cands = [(m, s) for s in (0, 1) for m in (1, 2, 4)]
    t1 = RandomTuner(list(cands), seed=3)
    t2 = RandomTuner(list(cands), seed=3)
    assert t1.remaining == t2.remaining
    assert sorted(t1.remaining) == sorted(cands)
