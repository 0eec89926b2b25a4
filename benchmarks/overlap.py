"""Static compute–communication overlap: bucketed vs monolithic ZeRO.

CPU-runnable static evidence (no device trace has been read yet): compile the REAL ZeRO-3 train
step for the bench model under three schedules and record the
dependency-level static overlap fraction of each compiled program
(telemetry/hlo_cost.collect_schedule_overlap — for every collective, is
there compute a latency-hiding executor could run between its issue
point and its first real consumer?):

- ``monolithic`` — the whole exchange fused into one collective per
  direction (``overlap_schedule.overlap: false``): nothing can hide.
- ``bucketed``   — size-targeted layer-order buckets
  (runtime/zero/overlap_schedule.py): bucket k's gather rides under
  layers < k, bucket k's reduce-scatter under the backward of layers
  < k.
- ``gspmd``      — the default per-leaf GSPMD path, for context: max
  overlap surface, max op count (the other end of the tradeoff the
  autotuner prices).

Asserts bucketed > monolithic STRICTLY, records all three plus op
counts and wire bytes. Run (CPU):

    JAX_PLATFORMS=cpu python benchmarks/overlap.py

Writes benchmarks/overlap.json.
"""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "_dstpu_hermetic",
    os.path.join(REPO, "deepspeed_tpu", "utils", "hermetic.py"))
hermetic = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(hermetic)
hermetic.force_cpu(device_count=8)


def lower_case(name, extra, n_layer=8, n_embd=512, seq=128):
    """Build the bench engine under one schedule config and return the
    compiled train step's overlap/cost summary."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu import comm
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.telemetry.hlo_cost import (collect_collectives,
                                                  hlo_overlap_summary)

    topology.reset_mesh()
    model = GPT2Model(GPT2Config(
        vocab_size=512, n_positions=seq + 1, n_embd=n_embd,
        n_layer=n_layer, n_head=8, pad_vocab_to_multiple=128,
        scan_unroll=n_layer))
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": 0},
        "gradient_clipping": 1.0, "steps_per_print": 0,
    }
    config.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    gbs = 2 * engine.dp_world_size
    batch = engine._to_device_batch({"input_ids": rng.integers(
        0, 500, (1, gbs, seq), dtype=np.int32)})
    before = comm.comm_stats()
    with engine.mesh:
        lowered = engine._train_step_fn.lower(
            engine.params, engine.opt_state, engine.scaler_state, batch,
            jnp.float32(1e-3), jax.random.PRNGKey(0), None,
            jnp.float32(1.0))
        hlo = lowered.compile().as_text()
    after = comm.comm_stats()
    engine.close()
    summary = hlo_overlap_summary(hlo)
    colls = collect_collectives(hlo)
    out = {
        "static_overlap_fraction": summary["static_overlap_fraction"],
        "overlappable": summary["overlappable"],
        "collectives": summary["collectives"],
        "async_fraction": summary["async_fraction"],
        "hlo_sync_bytes": summary["sync_bytes"],
        "traced_wire_bytes": after["bytes"] - before["bytes"],
        "traced_ops": after["ops"] - before["ops"],
        "per_op": {k: v["count"] for k, v in sorted(colls.items())},
    }
    print(f"{name:12s} static overlap "
          f"{out['static_overlap_fraction']:.3f}  "
          f"({out['overlappable']}/{out['collectives']} collectives, "
          f"{out['hlo_sync_bytes'] / 2**20:.1f} MiB)", flush=True)
    return out


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--bucket-bytes", type=int, default=4 << 20)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--embd", type=int, default=512)
    ap.add_argument("--out", default=os.path.join(REPO, "benchmarks",
                                                  "overlap.json"))
    args = ap.parse_args()

    report = {
        "model": f"gpt2 {args.embd}d x {args.layers}L (scan unrolled), "
                 f"ZeRO-3 on dp8",
        "bucket_bytes": args.bucket_bytes,
        "monolithic": lower_case(
            "monolithic",
            {"overlap_schedule": {"enabled": True, "overlap": False}},
            n_layer=args.layers, n_embd=args.embd),
        "bucketed": lower_case(
            "bucketed",
            {"overlap_schedule": {"enabled": True,
                                  "bucket_bytes": args.bucket_bytes}},
            n_layer=args.layers, n_embd=args.embd),
        "gspmd": lower_case("gspmd", {}, n_layer=args.layers,
                            n_embd=args.embd),
    }
    mono = report["monolithic"]["static_overlap_fraction"]
    bucketed = report["bucketed"]["static_overlap_fraction"]
    report["delta"] = round(bucketed - mono, 6)

    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items()
                      if not isinstance(v, dict)}, indent=2))

    assert bucketed > mono, (
        f"bucketed schedule must raise the static overlap fraction: "
        f"bucketed {bucketed} vs monolithic {mono}")
    # the wire totals are schedule-invariant (honest accounting): the
    # bucketed exchange moves the same bytes in fewer, ordered ops
    assert (report["bucketed"]["traced_wire_bytes"] ==
            report["monolithic"]["traced_wire_bytes"]), report
    print(f"OVERLAP OK: bucketed {bucketed:.3f} > monolithic {mono:.3f} "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
