#!/usr/bin/env python3
"""Runnable training example — one script, one JSON config per BASELINE
ladder rung (reference examples/ + docs/_tutorials/: a training script
driven by a ds_config JSON).

Every config under examples/configs/ works on the CPU mesh and on TPU
UNCHANGED — parallelism comes from the config (the engine builds the
device mesh from pipeline/tensor/expert/sequence_parallel_size), and
``--cpu`` only swaps the backend for an 8-device virtual CPU mesh.

    # smoke on any machine (no TPU needed)
    python examples/train.py --model gpt2-125m --cpu --steps 3 \
        --deepspeed_config examples/configs/gpt2_125m_zero0.json

    # the ladder rungs (drop --cpu on a TPU host)
    python examples/train.py --model gpt2-350m  --deepspeed_config examples/configs/gpt2_350m_zero1.json
    python examples/train.py --model gpt2-1.3b  --deepspeed_config examples/configs/gpt2_1p3b_zero3.json
    python examples/train.py --model gpt2-1.3b  --deepspeed_config examples/configs/gpt2_1p3b_zero2_offload.json
    python examples/train.py --model opt-125m   --deepspeed_config examples/configs/opt_pp4.json
    python examples/train.py --model gpt2-moe   --deepspeed_config examples/configs/moe_ep2.json

Data is the repo's own text, byte-tokenized (this environment has no
network egress); swap ``corpus_batches`` for your dataloader.
"""

import argparse
import dataclasses
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _force_cpu():
    from deepspeed_tpu.utils.hermetic import force_cpu
    force_cpu(device_count=8)


def build_model(name: str, seq: int, layers=None, vocab=256):
    """Ladder-rung presets on a byte vocabulary (the example trains on
    byte-tokenized text; pass your tokenizer's vocab for real runs)."""
    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2Model, GPT2_125M,
                                           GPT2_350M, GPT2_1_3B)
    if name.startswith("gpt2-moe"):
        from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel
        cfg = GPT2MoEConfig(vocab_size=vocab, n_positions=seq, n_embd=256,
                            n_layer=layers or 4, n_head=8,
                            pad_vocab_to_multiple=128, num_experts=4, top_k=2)
        return GPT2MoEModel(cfg)
    if name.startswith("opt"):
        from deepspeed_tpu.models.opt import OPTConfig, OPT_125M, OPTModel
        base = OPT_125M
        cfg = dataclasses.replace(base, vocab_size=vocab, n_positions=seq,
                                  pad_vocab_to_multiple=128,
                                  **({"n_layer": layers} if layers else {}))
        return OPTModel(cfg)
    base = {"gpt2-125m": GPT2_125M, "gpt2-350m": GPT2_350M,
            "gpt2-1.3b": GPT2_1_3B}[name]
    cfg = dataclasses.replace(base, vocab_size=vocab, n_positions=seq,
                              pad_vocab_to_multiple=128,
                              **({"n_layer": layers} if layers else {}))
    return GPT2Model(cfg)


def corpus_batches(gas, rows, seq, steps, seed=0):
    """Byte-tokenized batches from the repo's own text files."""
    import numpy as np
    chunks = []
    for pat in ("*.md", "docs/*.md", "deepspeed_tpu/**/*.py"):
        for path in sorted(glob.glob(os.path.join(REPO, pat),
                                     recursive=True))[:40]:
            try:
                with open(path, "rb") as f:
                    chunks.append(np.frombuffer(f.read(), np.uint8))
            except OSError:
                pass
    corpus = np.concatenate(chunks) if chunks else \
        np.random.default_rng(seed).integers(0, 256, 1 << 20).astype(np.uint8)
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        starts = rng.integers(0, len(corpus) - seq - 1, gas * rows)
        batch = np.stack([corpus[s:s + seq] for s in starts])
        yield {"input_ids": batch.reshape(gas, rows, seq).astype(np.int32)}


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="gpt2-125m",
                        choices=["gpt2-125m", "gpt2-350m", "gpt2-1.3b",
                                 "gpt2-moe", "opt-125m"])
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--seq", type=int, default=None,
                        help="sequence length (default: config hint or 1024)")
    parser.add_argument("--layers", type=int, default=None,
                        help="override layer count (cheap CI runs)")
    parser.add_argument("--cpu", action="store_true",
                        help="run on an 8-device virtual CPU mesh")
    parser.add_argument("--save", default=None,
                        help="checkpoint dir (saved at the end)")
    if "--cpu" in sys.argv:
        _force_cpu()
    import deepspeed_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    deepspeed_tpu.add_config_arguments(parser)
    args = parser.parse_args()
    if not args.deepspeed_config:
        parser.error("--deepspeed_config is required (see examples/configs/)")

    seq = args.seq or (256 if args.cpu else 1024)
    model = build_model(args.model, seq, layers=args.layers)
    engine, _, _, _ = deepspeed_tpu.initialize(args=args, model=model)

    gas = engine.gradient_accumulation_steps
    rows = engine.train_micro_batch_size_per_gpu * engine.dp_world_size
    losses = []
    for step, batch in enumerate(
            corpus_batches(gas, rows, seq, args.steps)):
        loss = engine.train_batch(batch=batch)
        losses.append(float(loss))
        print(f"step {step:4d}  loss {losses[-1]:.4f}")
    if args.save:
        engine.save_checkpoint(args.save)
        print(f"checkpoint saved -> {args.save}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
