"""BERT-Large MLM training throughput on one chip — the reference's
HEADLINE benchmark, reproduced on TPU.

The reference's fastest-BERT claim is BERT-Large at 64 TFLOPS on a V100
(docs/_posts/2020-05-28-fastest-bert-training.md:36-38, 0.512 MFU of the
V100's 125 TFLOPS peak), powered by its fused transformer CUDA kernels
(csrc/transformer/ds_transformer_cuda.cpp). This script trains the same
architecture (24 layers, 1024 hidden, seq 512, MLM objective) through the
deepspeed_tpu engine on one v5e chip and records achieved TFLOPS + MFU.
Writes benchmarks/bert_large.json.

Run on the real chip:  python benchmarks/bert_large.py
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REFERENCE_TFLOPS = 64.0          # reference headline on V100
REFERENCE_MFU = 64.0 / 125.0


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models.bert import BertConfig, BertModel, BERT_LARGE

    seq = int(os.environ.get("BERT_SEQ", 512))
    micro_bs = int(os.environ.get("BERT_BS", 8))
    gas = int(os.environ.get("BERT_GAS", 64))
    windows = int(os.environ.get("BERT_WINDOWS", 3))

    cfg = dataclasses.replace(BERT_LARGE, n_positions=seq,
                              attn_backend="auto")
    model = BertModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": micro_bs * gas,
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 0})

    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(5, cfg.vocab_size - 1,
                           (gas, micro_bs, seq)).astype(np.int32)
        mask = rng.random((gas, micro_bs, seq)) < 0.15
        return {"input_ids": np.where(mask, 3, ids).astype(np.int32),
                "labels": np.where(mask, ids, -100).astype(np.int32)}

    for _ in range(2):
        loss = engine.train_batch(batch=batch())
    float(loss)

    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=batch())
        float(loss)
        best = min(best, time.perf_counter() - t0)

    tokens_per_sec = gas * micro_bs * seq / best
    achieved = tokens_per_sec * model.flops_per_token(seq)
    from bench import device_peaks
    peak = device_peaks()["bf16_flops"]
    out = {
        "benchmark": "bert_large_mlm_bf16_train",
        "seq": seq, "micro_bs": micro_bs, "gas": gas,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "achieved_tflops": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        "reference_tflops_v100": REFERENCE_TFLOPS,
        "reference_mfu": round(REFERENCE_MFU, 4),
        "tflops_vs_reference": round(achieved / 1e12 / REFERENCE_TFLOPS, 2),
        "final_loss": round(float(loss), 4),
    }
    print(json.dumps(out))
    with open(os.path.join(REPO, "benchmarks", "bert_large.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
