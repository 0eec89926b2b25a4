"""Profile the GPT-2 125M fused train step on the real chip and print a
per-category device-time breakdown parsed straight from the xplane trace.

Usage (on the machine with the chip):
    python benchmarks/profile_step.py            # bs8 seq1024 gas8
    BENCH_BS=16 python benchmarks/profile_step.py

Categories are keyed on XLA op names: pallas flash kernels, dense fusions,
dynamic-update-slice stashes, loss/head ops, everything else.
"""

import dataclasses
import glob
import gzip
import json
import os
import sys
import tempfile
import time

import numpy as np

# sys.path[0] is benchmarks/; the repo root must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(trace_dir):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model, GPT2_125M

    seq = int(os.environ.get("BENCH_SEQ", 1024))
    micro_bs = int(os.environ.get("BENCH_BS", 8))
    gas = int(os.environ.get("BENCH_GAS", 8))
    remat_policy = os.environ.get("BENCH_REMAT") or None
    loss_chunking = os.environ.get("BENCH_LOSS", "auto")

    cfg = dataclasses.replace(
        GPT2_125M, n_positions=seq, remat=bool(remat_policy),
        remat_policy=remat_policy,
        attn_backend=os.environ.get("BENCH_ATTN", "auto"),
        loss_chunking=loss_chunking)
    model = GPT2Model(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": micro_bs * gas,
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": gas,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 0,
        })
    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(0, 50256, (gas, micro_bs, seq),
                                          dtype=np.int32)}

    for _ in range(3):
        loss = engine.train_batch(batch=batch())
    float(loss)

    t0 = time.perf_counter()
    loss = engine.train_batch(batch=batch())
    float(loss)
    wall = time.perf_counter() - t0

    jax.profiler.start_trace(trace_dir)
    loss = engine.train_batch(batch=batch())
    float(loss)
    jax.profiler.stop_trace()
    return wall, gas, micro_bs, seq


def categorize(name):
    n = name.lower()
    if "closed_call" in n or "custom-call" in n:
        return "pallas_attention"
    if "dynamic-update-slice" in n:
        return "stash_dus"
    if "dynamic-slice" in n:
        return "dyn_slice"
    if "convert" in n:
        return "convert"
    if "fusion" in n:
        return "fusion"
    if "copy" in n or "transpose" in n or "bitcast" in n:
        return "copy_transpose"
    if "all-reduce" in n or "reduce-scatter" in n or "all-gather" in n:
        return "collective"
    return "other"


def parse(trace_dir, n_micro):
    files = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not files:
        print("no trace found under", trace_dir)
        return
    path = max(files, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    pid_names = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
    tid_names = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
    ops = [e for e in events if e.get("ph") == "X" and
           tid_names.get((e["pid"], e["tid"])) == "XLA Ops"]
    # self time: events on the XLA Ops lane nest (while/call bodies overlap
    # their children) — subtract child durations via a stack sweep
    ops.sort(key=lambda e: (e["ts"], -e["dur"]))
    self_time, count = {}, {}
    stack = []
    for e in ops:
        ts, dur, name = e["ts"], e["dur"], e["name"]
        while stack and ts >= stack[-1][0] + stack[-1][1]:
            stack.pop()
        if stack:
            self_time[stack[-1][2]] = self_time.get(stack[-1][2], 0.0) - dur
        self_time[name] = self_time.get(name, 0.0) + dur
        count[name] = count.get(name, 0) + 1
        stack.append((ts, dur, name))
    total = sum(self_time.values())
    print(f"\n== device self-time {total/1e3:.1f} ms total, "
          f"{total/n_micro/1e3:.2f} ms/micro ==")
    by_cat = {}
    for n, d in self_time.items():
        by_cat[categorize(n)] = by_cat.get(categorize(n), 0.0) + d
    for c, d in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        print(f"  {c:18s} {d/n_micro/1e3:8.2f} ms/micro")
    print("\n== top 30 ops (self ms/micro) ==")
    for n, d in sorted(self_time.items(), key=lambda kv: -kv[1])[:30]:
        print(f"  {d/n_micro/1e3:8.3f}  x{count[n]//n_micro:<4d} {n[:100]}")


def main():
    trace_dir = os.environ.get("TRACE_DIR") or tempfile.mkdtemp(
        prefix="ds_tpu_trace_")
    wall, gas, bs, seq = run(trace_dir)
    print(f"wall per global step (gas={gas}, bs={bs}, seq={seq}): "
          f"{wall*1e3:.1f} ms = {wall*1e3/gas:.2f} ms/micro")
    parse(trace_dir, gas)
    print("trace dir:", trace_dir)


if __name__ == "__main__":
    main()
