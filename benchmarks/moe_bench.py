"""BASELINE.md config 5: MoE GPT (8 experts, top-2) training throughput
on one chip. Writes benchmarks/moe_top2.json.

Run on the real chip: python benchmarks/moe_bench.py
"""

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import device_peaks  # noqa: E402 — the one table of peaks


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel

    seq = int(os.environ.get("BENCH_SEQ", 1024))
    micro = int(os.environ.get("BENCH_BS", 8))
    gas = int(os.environ.get("BENCH_GAS", 16))
    steps = max(1, int(os.environ.get("BENCH_STEPS", 4)))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", 2)))

    # GPT-2-small width with 8 experts, top-2 (BASELINE #5); ~340M total
    # params, ~160M active per token
    cfg = GPT2MoEConfig(n_positions=seq, n_embd=768, n_layer=12, n_head=12,
                        num_experts=8, top_k=2, capacity_factor=1.25,
                        remat=False, attn_backend="auto")
    model = GPT2MoEModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 0,
    })
    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(0, 50256, (gas, micro, seq),
                                          dtype=np.int32)}

    for _ in range(3):
        loss = engine.train_batch(batch=batch())
    float(loss)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch=batch())
        float(loss)
        best = min(best, time.perf_counter() - t0)
    tok_s = steps * gas * micro * seq / best
    fpt = model.flops_per_token(seq)          # ACTIVE-param flops
    peak = device_peaks()["bf16_flops"]
    report = {
        "benchmark": "gpt2_moe_8e_top2_bf16_train",
        "model": "gpt2-small + 8 experts top-2",
        "zero_stage": 1, "experts": 8, "top_k": 2,
        "seq": seq, "micro_bs": micro, "gas": gas, "steps": steps,
        "tokens_per_sec": round(tok_s, 1),
        "achieved_active_tflops": round(tok_s * fpt / 1e12, 2),
        "active_mfu": round(tok_s * fpt / peak, 4),
        "final_loss": round(float(loss), 4),
        "note": ("single-chip measurement (ep=1: all experts resident; "
                 "the all-to-all is exercised by the ep2 CPU-mesh tests "
                 "and the multichip dryrun); MFU counts ACTIVE-param "
                 "FLOPs (top-2 of 8 experts)"),
    }
    with open(os.path.join(REPO, "benchmarks", "moe_top2.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
