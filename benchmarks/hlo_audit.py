"""HLO collective audit: prove the ZeRO/TP/SP sharding designs lower to
the intended collectives.

The reference implements its communication schedule by hand (IPG-bucket
reduce-scatter in stage_1_and_2.py:894, coalesced allgather in
partition_parameters.py:874); here the schedule is GSPMD's, so the
verifiable artifact is the compiled HLO itself. This audit compiles the
REAL train step for each parallelism config on a virtual 8-device mesh and
records every collective op with its payload bytes — the "sharding is
right by construction" evidence that doesn't need hardware.

Run (CPU): JAX_PLATFORMS=cpu python benchmarks/hlo_audit.py
Writes benchmarks/hlo_audit.json.
"""

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import importlib.util  # noqa: E402

from deepspeed_tpu.utils.hermetic import force_cpu  # noqa: E402

force_cpu(device_count=8)

# the shared HLO cost core (telemetry/hlo_cost.py — stdlib-only, so the
# same file-path load works): one parser for this gate, the flight
# recorder's cost capture, and the compile ledger
_hc_spec = importlib.util.spec_from_file_location(
    "_dstpu_hlo_cost",
    os.path.join(REPO, "deepspeed_tpu", "telemetry", "hlo_cost.py"))
hlo_cost = importlib.util.module_from_spec(_hc_spec)
_hc_spec.loader.exec_module(hlo_cost)

#: behavior-identical alias — the collective parser now lives in the
#: shared core; tests and older callers keep the old name
_collect = hlo_cost.collect_collectives


def audit(name, mesh_kw, config_over, n_devices=8, with_flops=False):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import topology, initialize_mesh

    topology.reset_mesh()
    mm = initialize_mesh(devices=jax.devices("cpu")[:n_devices], **mesh_kw)
    if mesh_kw.get("ep", 1) > 1:
        from deepspeed_tpu.models.gpt2_moe import (GPT2MoEConfig,
                                                   GPT2MoEModel)
        cfg = GPT2MoEConfig(vocab_size=512, n_positions=256, n_embd=256,
                            n_layer=4, n_head=8, pad_vocab_to_multiple=128,
                            num_experts=2 * mesh_kw["ep"], top_k=1)
        model_cls = GPT2MoEModel
    else:
        cfg = GPT2Config(vocab_size=512, n_positions=256, n_embd=256,
                         n_layer=4, n_head=8, pad_vocab_to_multiple=128)
        model_cls = GPT2Model
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
        "steps_per_print": 0,
    }
    config.update(config_over)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model_cls(cfg),
                                               config=config,
                                               mesh_manager=mm)
    rng = np.random.default_rng(0)
    gbs = 2 * engine.dp_world_size
    batch = engine._to_device_batch({"input_ids": rng.integers(
        0, 500, (2, gbs, 128), dtype=np.int32)})
    with engine.mesh:
        lowered = engine._train_step_fn.lower(
            engine.params, engine.opt_state, engine.scaler_state, batch,
            jnp.float32(1e-3), jax.random.PRNGKey(0), None,
            jnp.float32(1.0))
        compiled = lowered.compile()
        hlo = compiled.as_text()
    stats = _collect(hlo)
    if with_flops:
        # Analytic roofline: compiled-step FLOPs from XLA's own cost model
        # vs total collective payload. bytes_per_gflop is the scale-free
        # number that catches an accidental resharding (dropping a grad
        # out-sharding ~doubles it) with no TPU in the loop.
        flops = float(hlo_cost.cost_summary(
            compiled.cost_analysis()).get("flops", 0.0))
        if not flops:
            print(f"WARNING: cost_analysis reported no flops — "
                  f"bytes/GFLOP roofline gate is DISABLED for {name}",
                  file=sys.stderr)
        total_bytes = sum(v["bytes"] for v in stats.values())
        stats = dict(stats)
        stats["_roofline"] = {
            "step_flops": flops,
            "collective_bytes": total_bytes,
            "bytes_per_gflop": (total_bytes / (flops / 1e9)) if flops else None,
        }
    # overlap column (ROADMAP item 2's before/after instrument): what
    # fraction of the schedule's collectives are emitted in async
    # start/done form — 0.0 on the fully synchronous CPU lowering, and
    # the number item 2 exists to raise on the TPU backend
    stats = dict(stats)
    stats["_overlap"] = hlo_cost.hlo_overlap_summary(hlo)
    shown = {k: v for k, v in stats.items() if not k.startswith("_")}
    line = (f"{name}: " + ", ".join(
        f"{op} x{v['count']} ({v['bytes']/2**20:.1f} MiB)"
        for op, v in sorted(shown.items())) if shown else f"{name}: none")
    print(line + f" | async overlap {stats['_overlap']['async_fraction']:.2f}")
    return stats


CASES = {
    # pure dp, ZeRO-0: grads MEAN over dp -> all-reduce, nothing else
    "dp8_zero0": ({"dp": 8}, {"zero_optimization": {"stage": 0}}),
    # ZeRO-2: grads land dp-SHARDED -> reduce-scatter; updated params
    # re-gather -> all-gather
    "dp8_zero2": ({"dp": 8}, {"zero_optimization": {"stage": 2}}),
    # ZeRO-3: params dp-sharded too -> all-gather in the layer scan
    # (fwd AND bwd), grads reduce-scatter
    "dp8_zero3": ({"dp": 8}, {"zero_optimization": {
        "stage": 3, "stage3_param_persistence_threshold": 0}}),
    # TP: per-layer partial sums -> all-reduce (or equivalent
    # reduce-scatter+all-gather pairs) inside every block
    "tp2_dp4_zero1": ({"tp": 2, "dp": 4},
                      {"tensor_parallel_size": 2,
                       "zero_optimization": {"stage": 1}}),
    # SP (Ulysses): head<->sequence all-to-all around attention
    "sp2_dp4_zero3": ({"sp": 2, "dp": 4},
                      {"sequence_parallel_size": 2,
                       "zero_optimization": {
                           "stage": 3,
                           "stage3_param_persistence_threshold": 0}}),
    # EP (MoE): expert-dispatch all-to-all in every MoE layer
    "ep2_dp4_zero2_moe": ({"ep": 2, "dp": 4},
                          {"expert_parallel_size": 2,
                           "zero_optimization": {"stage": 2}}),
}

BASELINE_PATH = os.path.join(REPO, "benchmarks", "hlo_audit_baseline.json")

# Gate tolerances (also used by tests/unit/test_hlo_gate.py). Counts are
# exact-ish (XLA may split/merge a collective across minor versions); bytes
# catch the silent killers — an accidental resharding roughly doubles
# gather traffic, far outside these bands.
COUNT_SLACK = 2
BYTES_RTOL = 0.25


def reduces(stats):
    """Backend note: the CPU SPMD lowering expresses reduce-scatter as
    all-reduce + dynamic-slice (no fused reduce-scatter HLO on this
    backend); the TPU backend emits the fused op from the SAME programs —
    so "grads reduce" is asserted as either form, while gather structure
    is backend-stable."""
    return "reduce-scatter" in stats or "all-reduce" in stats


def check_intent(report):
    """Design-intent assertions per strategy (shape of the collective
    schedule, independent of exact counts)."""
    a = report["dp8_zero0"]
    assert reduces(a), "zero0: dp grad mean must reduce"
    assert a.get("all-gather", {}).get("bytes", 0) < 2**20, \
        "zero0 should not gather params"
    z2 = report["dp8_zero2"]
    assert reduces(z2), "zero2: grads must reduce"
    assert z2.get("all-gather", {}).get("count", 0) >= 1, \
        "zero2: updated sharded params must re-gather"
    z3 = report["dp8_zero3"]
    assert reduces(z3), "zero3: grads must reduce"
    assert z3.get("all-gather", {}).get("count", 0) >= 2, \
        "zero3: param gathers must appear in the compiled step"
    tp = report["tp2_dp4_zero1"]
    assert reduces(tp), "tp: block partial sums must reduce"
    sp = report["sp2_dp4_zero3"]
    assert "all-to-all" in sp, "sp(Ulysses): head<->seq all-to-all missing"
    moe = report["ep2_dp4_zero2_moe"]
    assert "all-to-all" in moe, "moe(ep): expert-dispatch all-to-all missing"
    assert reduces(moe), "moe: grads must reduce"


def check_against_baseline(name, stats, baseline):
    """Tolerance comparison of one config's collectives vs the checked-in
    baseline. Returns a list of violation strings (empty = pass)."""
    problems = []
    base = baseline.get(name)
    if base is None:
        return [f"{name}: no baseline entry — regenerate {BASELINE_PATH}"]
    ops = {k for k in base if not k.startswith("_")} | \
          {k for k in stats if not k.startswith("_")}
    for op in sorted(ops):
        b = base.get(op, {"count": 0, "bytes": 0})
        s = stats.get(op, {"count": 0, "bytes": 0})
        if abs(s["count"] - b["count"]) > COUNT_SLACK:
            problems.append(
                f"{name}.{op}: count {s['count']} vs baseline {b['count']} "
                f"(slack {COUNT_SLACK})")
        denom = max(b["bytes"], 1)
        if abs(s["bytes"] - b["bytes"]) / denom > BYTES_RTOL and \
                abs(s["bytes"] - b["bytes"]) > 2**18:
            problems.append(
                f"{name}.{op}: bytes {s['bytes']} vs baseline {b['bytes']} "
                f"(rtol {BYTES_RTOL})")
    b_roof = (base.get("_roofline") or {}).get("bytes_per_gflop")
    s_roof = (stats.get("_roofline") or {}).get("bytes_per_gflop")
    if b_roof and s_roof and s_roof > b_roof * (1 + BYTES_RTOL):
        problems.append(
            f"{name}: bytes/GFLOP {s_roof:.0f} vs baseline {b_roof:.0f} — "
            f"collective traffic grew relative to compute")
    return problems


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite hlo_audit_baseline.json from this run "
                         "(do this deliberately, with the diff reviewed)")
    args = ap.parse_args()

    if not args.update_baseline and not os.path.exists(BASELINE_PATH):
        # fail fast, and never self-baseline silently: a gate that
        # baselines the very tree under test passes any regression
        print(f"ERROR: {BASELINE_PATH} missing — a gate run cannot "
              f"baseline itself. Re-run with --update-baseline "
              f"deliberately and review the diff.", file=sys.stderr)
        raise SystemExit(1)

    report = {}
    for name, (mesh_kw, over) in CASES.items():
        report[name] = audit(name, mesh_kw, over, with_flops=True)
    check_intent(report)
    report["_note"] = (
        "CPU SPMD lowers reduce-scatter as all-reduce+dynamic-slice; the "
        "TPU backend emits the fused op from the same programs")

    out = os.path.join(REPO, "benchmarks", "hlo_audit.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1)

    if args.update_baseline:
        with open(BASELINE_PATH, "w") as f:
            json.dump(report, f, indent=1)
        print(f"baseline written -> {BASELINE_PATH}")
    else:
        with open(BASELINE_PATH) as f:
            baseline = json.load(f)
        problems = []
        for name in CASES:
            problems += check_against_baseline(name, report[name], baseline)
        if problems:
            print("HLO AUDIT REGRESSIONS:\n  " + "\n  ".join(problems))
            raise SystemExit(1)
    print(f"HLO AUDIT OK -> {out}")


if __name__ == "__main__":
    main()
