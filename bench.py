"""Headline benchmark: GPT-2 125M training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline compares our MFU against the reference's headline training
efficiency (BERT-Large 64 TFLOPS on a 125-TFLOPS V100 = 0.512 MFU,
docs/_posts/2020-05-28-fastest-bert-training.md:36-38).
"""

import json
import os
import time

import numpy as np

REFERENCE_MFU = 64.0 / 125.0  # reference headline: BERT-Large on V100

# Published per-chip peaks, keyed by jax's ``device_kind``. A device that is
# not in the table is an error, not a default.
# "TPU v5 lite": Google Cloud documentation, "TPU v5e".
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197.0e12, "hbm_bytes_per_s": 819.0e9,
                    "hbm_bytes": 16.0e9},
}


def device_peaks():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on the chip; jax found platform "
            f"{dev.platform!r} ({dev.device_kind!r})")
    if dev.device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {dev.device_kind!r}; add it "
            f"to bench.PEAKS with its source")
    return PEAKS[dev.device_kind]


def main():
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model, GPT2_125M
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    import dataclasses

    peaks = device_peaks()      # fails here, before any work, off the chip
    enable_compile_cache()

    # micro 8 (fits the dense-loss path), gas 128 (8x128x1024 = a 1M-token
    # global batch, GPT-3-scale), one global step per timing window
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    micro_bs = int(os.environ.get("BENCH_BS", 8))
    steps = max(1, int(os.environ.get("BENCH_STEPS", 4)))
    gas = int(os.environ.get("BENCH_GAS", 128))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", 3)))
    warmup = 3

    # 125M fits comfortably: no remat (round-1 ran full recompute and paid
    # ~30% throughput for nothing). Attention: auto -> Pallas flash on TPU.
    cfg = dataclasses.replace(GPT2_125M, n_positions=seq, remat=False,
                              attn_backend="auto")
    model = GPT2Model(cfg)
    n_dev = len(deepspeed_tpu.parallel.topology.default_devices())

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": micro_bs * gas * n_dev,
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 0,
        })

    rng = np.random.default_rng(0)
    global_bs = micro_bs * engine.dp_world_size

    def batch():
        return {"input_ids": rng.integers(0, 50256, (gas, global_bs, seq),
                                          dtype=np.int32)}

    for _ in range(warmup):
        loss = engine.train_batch(batch=batch())
    jax.block_until_ready(engine.params)

    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch=batch())
        jax.block_until_ready(engine.params)
        dts.append(time.perf_counter() - t0)
    dt = float(np.median(dts))

    tokens_per_sec = steps * gas * global_bs * seq / dt
    flops_per_token = model.flops_per_token(seq)
    achieved = tokens_per_sec * flops_per_token
    peak = peaks["bf16_flops"] * engine.dp_world_size
    mfu = achieved / peak

    print(json.dumps({
        "metric": "gpt2_125m_bf16_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / REFERENCE_MFU, 4),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "detail": {
            "tokens_per_sec": round(tokens_per_sec, 1),
            "achieved_tflops": round(achieved / 1e12, 2),
            "seq": seq, "micro_bs": micro_bs, "steps": steps,
            "window_seconds": [round(x, 4) for x in dts],
            "final_loss": round(float(loss), 4),
            "devices": engine.dp_world_size,
        },
    }))


if __name__ == "__main__":
    main()
