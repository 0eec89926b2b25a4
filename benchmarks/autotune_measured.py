"""Measured-autotuning benchmark: the goodput-scored sweep vs every
hand-written config.

Runs the PR-15 measured-trials plane (autotuning/measure.py) on the
bench GPT-2 under a per-device HBM budget and proves the closed loop:

1. **The measured winner beats EVERY hand-written `examples/configs/`
   training config on measured goodput** (productive fraction × step
   TFLOPs on a sweep-constant FLOPs basis). Each hand config is mapped
   onto the bench geometry via ``point_from_config`` — its micro batch,
   ZeRO stage, offload mode, remat, and comm plan carried; topology
   (pp/ep), bf16, and scheduler knobs are normalized away (recorded in
   the output). Under the bench budget the micro-8 hand configs do not
   fit and are DISQUALIFIED (the reference autotuner's OOM pruning,
   driven by the HBM ledger instead of a crashed run); the qualified
   ones lose on measured goodput.
2. **Exactly one trial_best + one trial_worst bundle** per sweep, each
   embedding a score breakdown whose goodput window sums to the trial
   wall-clock within 1%.
3. **A second run is a pure cache hit** — 0 trials executed.
4. **Calibration**: the measured trials fit the ScheduleCostModel's
   alpha-beta terms; over the explicit-exchange plan ladder the
   calibrated ranking matches the measured ordering better than the
   static defaults (rank correlation asserted and reported).

Writes benchmarks/autotune_measured.json (snapshot-shaped: `ds_tpu_top
--snapshot autotune_measured.json` renders the tuning panel).

STANDING CHIP DEBT: this driver is chip-runnable by construction — no
CPU-only assumptions (the hermetic CPU shim only engages under
JAX_PLATFORMS=cpu, trial peaks prefer real allocator stats when the
backend reports them, and dims/budget are env knobs). It has not been
run on hardware yet; to calibrate alpha-beta from real profiles
AT_BUDGET_GIB must be re-based to the chip's HBM (the default
fits the CPU bench dims).

Runs on CPU: JAX_PLATFORMS=cpu python benchmarks/autotune_measured.py
Knobs (env): AT_EMBD, AT_LAYERS, AT_SEQ, AT_STEPS, AT_BUDGET_GIB,
             AT_GLOBAL_BATCH.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if os.environ.get("JAX_PLATFORMS", "").lower().startswith("cpu") or \
        os.environ.get("DSTPU_ACCELERATOR", "").lower() == "cpu":
    import importlib.util
    _spec = importlib.util.spec_from_file_location(
        "_dstpu_hermetic",
        os.path.join(REPO, "deepspeed_tpu", "utils", "hermetic.py"))
    _hermetic = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_hermetic)
    # the sweep's stage/offload/plan axes only differentiate with dp>1
    # (the fake-multichip mesh); on real chips the device count is the
    # hardware's own
    _hermetic.force_cpu(device_count=int(os.environ.get("AT_DEVICES", 8)))

import jax  # noqa: E402

from deepspeed_tpu.autotuning.cost_model import (  # noqa: E402
    ScheduleCostModel, rank_correlation)
from deepspeed_tpu.autotuning.measure import (  # noqa: E402
    AutotuneConfig, measure_schedule)
from deepspeed_tpu.autotuning.trials import (  # noqa: E402
    TrialPoint, point_from_config)
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model  # noqa: E402

EMBD = int(os.environ.get("AT_EMBD", 256))
LAYERS = int(os.environ.get("AT_LAYERS", 4))
SEQ = int(os.environ.get("AT_SEQ", 64))
STEPS = int(os.environ.get("AT_STEPS", 3))
#: per-device budget sized for the CPU bench dims: the micro-8
#: hand-written configs peak at >= 0.0599 GiB (z3) while every micro<=4
#: sweep point stays <= 0.0499 GiB — re-base on chip HBM for hardware
BUDGET_GIB = float(os.environ.get("AT_BUDGET_GIB", 0.055))

#: the hand-written training configs under comparison (serving_* files
#: configure replicas, not training runs)
HAND_CONFIGS = ("gpt2_125m_zero0", "gpt2_350m_zero1", "gpt2_1p3b_zero3",
                "gpt2_1p3b_zero2_offload", "moe_ep2", "opt_pp4",
                "elastic_training")

#: hand-config knobs the bench geometry cannot carry: recorded per row
NORMALIZED = ("pipeline_parallel_size", "expert_parallel_size", "bf16",
              "fp16", "scheduler", "elasticity", "hostagg", "resilience",
              "flight_recorder", "telemetry", "steps_per_print",
              "train_batch_size")


def main():
    dp = jax.device_count()
    global_batch = int(os.environ.get("AT_GLOBAL_BATCH", 8 * dp))
    cfg = GPT2Config(vocab_size=512, n_positions=SEQ + 1, n_embd=EMBD,
                     n_layer=LAYERS, n_head=8, pad_vocab_to_multiple=128,
                     scan_unroll=LAYERS)
    rng = np.random.default_rng(0)

    def model_factory():
        return GPT2Model(cfg)

    def batch_factory(gbs):
        toks = rng.integers(0, cfg.vocab_size - 2, (1, gbs, SEQ + 1))
        return {"input_ids": toks.astype(np.int32)}

    base_config = {
        "train_batch_size": global_batch,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 0,
    }

    # ---- hand-written rows: each examples/configs knob set mapped onto
    #      the bench geometry (micro/stage/offload/remat/plan carried)
    hand_points = {}
    for name in HAND_CONFIGS:
        path = os.path.join(REPO, "examples", "configs", f"{name}.json")
        with open(path) as f:
            doc = json.load(f)
        normalized = sorted(k for k in doc if k in NORMALIZED)
        point = point_from_config(doc, dp=dp, global_batch=global_batch)
        hand_points[name] = {"point": point, "key": point.key(),
                             "normalized": normalized}
        print(f"hand config {name:28s} -> {point.key()}"
              f"  (normalized: {', '.join(normalized) or '-'})")

    # ---- the swept space: micro ladder x offload (+ remat at the base
    #      micro), an explicit-exchange plan ladder at micro 4 for
    #      calibration, and every hand point
    points = []
    for micro in (1, 2, 4, 8):
        points.append(TrialPoint(micro_bs=micro))
        points.append(TrialPoint(micro_bs=micro, offload="cpu_pipelined"))
    points.append(TrialPoint(micro_bs=4, remat="full"))
    points.append(TrialPoint(micro_bs=2, remat="full"))
    plan_ladder = [TrialPoint(micro_bs=4, overlap=True, bucket_bytes=b)
                   for b in (256 << 10, 1 << 20, 4 << 20, 16 << 20)]
    points += plan_ladder
    for row in hand_points.values():
        if row["point"] not in points:
            points.append(row["point"])
    points = [p for p in points if p.feasible(dp, global_batch) is None]

    at = AutotuneConfig.from_dict({
        "steps": STEPS, "warmup_steps": 1,
        "hbm_budget_gib": BUDGET_GIB})

    out_dir = os.path.dirname(os.path.abspath(__file__))
    bundle_dir = tempfile.mkdtemp(prefix="autotune_bundles_")
    cache_dir = tempfile.mkdtemp(prefix="autotune_cache_")

    t0 = time.time()
    result = measure_schedule(model_factory, base_config, batch_factory,
                              points=points, autotune=at,
                              cache_dir=cache_dir, bundle_dir=bundle_dir)
    sweep_s = time.time() - t0
    table = result["table"]
    by_key = {e["key"]: e for e in table}
    winner_key = result["winner_key"]
    winner_score = result["score"]
    print(f"\nwinner {winner_key}  goodput score {winner_score:.4f}  "
          f"({result['trials_run']} trials, {sweep_s:.0f}s)")

    # ---- acceptance 1: the winner beats EVERY hand-written config
    hand_rows = {}
    for name, row in hand_points.items():
        e = by_key[row["key"]]
        hand_rows[name] = {
            "key": row["key"], "normalized": row["normalized"],
            "score": e["score"], "disqualified": e.get("disqualified"),
            "peak_hbm_gib": e.get("peak_hbm_gib"),
            "measured_step_s": e.get("measured_step_s"),
        }
        beaten = winner_score > e["score"]
        mark = "DQ " + e["disqualified"] if e.get("disqualified") else \
            f"score {e['score']:.4f}"
        print(f"  vs {name:28s} {mark:24s} "
              f"{'BEATEN' if beaten else 'NOT BEATEN'}")
        assert beaten, (
            f"winner {winner_key} ({winner_score:.4f}) does not beat "
            f"hand config {name} ({e['score']:.4f})")
        assert winner_key != row["key"], (
            f"winner IS the hand config {name} — tuning found nothing")

    # ---- acceptance 2: exactly one best + one worst bundle, breakdowns
    #      sum consistently with the goodput ledger (±1%)
    bundles = sorted(os.listdir(bundle_dir))
    best_bundles = [b for b in bundles if "trial_best" in b]
    worst_bundles = [b for b in bundles if "trial_worst" in b]
    assert len(best_bundles) == 1 and len(worst_bundles) == 1, bundles
    bundle_audit = {}
    for name in best_bundles + worst_bundles:
        with open(os.path.join(bundle_dir, name)) as f:
            doc = json.load(f)
        trial = doc["status"]["trial"]
        win = trial["score_breakdown"]["goodput_window"]
        total = sum(win["buckets"].values())
        err = abs(total - win["wall_s"]) / max(win["wall_s"], 1e-9)
        assert err < 0.01, (name, total, win["wall_s"])
        assert trial["compile_events"], name
        kind = "best" if "trial_best" in name else "worst"
        bundle_audit[kind] = {"file": name, "trial": trial["key"],
                              "window_sum_err": round(err, 6),
                              "score": trial["score"]}
    assert bundle_audit["best"]["trial"] == winner_key

    # ---- acceptance 3: the re-run is a pure cache hit
    t1 = time.time()
    rerun = measure_schedule(model_factory, base_config, batch_factory,
                             points=points, autotune=at,
                             cache_dir=cache_dir, bundle_dir=bundle_dir)
    rerun_s = time.time() - t1
    assert rerun["cached"] and rerun["trials_run"] == 0, (
        rerun.get("cached"), rerun.get("trials_run"))
    assert rerun["winner"] == result["winner"]
    assert sorted(os.listdir(bundle_dir)) == bundles   # no new bundles
    print(f"re-run: cache hit, 0 trials, {rerun_s:.1f}s")

    # ---- acceptance 4: calibrated model ranks the explicit plan ladder
    #      like the measurements, better than the static defaults
    ladder = [by_key[p.key()] for p in plan_ladder
              if p.key() in by_key and by_key[p.key()].get("flops")]
    meas = [e["measured_step_s"] for e in ladder]

    def model_rho(model):
        pred = [model.score(e["flops"], e["wire_bytes"],
                            e["hlo_collectives"],
                            e["static_overlap_fraction"]) for e in ladder]
        return rank_correlation(pred, meas)

    static_rho = model_rho(ScheduleCostModel())
    assert result.get("cost_model_calibrated"), "calibration did not run"
    calibrated = ScheduleCostModel.from_dict(result["cost_model"])
    cal_rho = model_rho(calibrated)
    print(f"plan-ladder rank correlation vs measured: "
          f"static {static_rho:.3f} -> calibrated {cal_rho:.3f}")
    # the static constants deterministically rank the 16 MiB plan (fewest
    # collectives) best, which every measurement contradicts — the
    # calibrated model must track the measured ordering instead
    assert cal_rho >= 0.5, cal_rho
    assert cal_rho > static_rho, (cal_rho, static_rho)
    coarse = max(ladder, key=lambda e: e["measured_step_s"])
    cal_scores = {e["key"]: calibrated.score(
        e["flops"], e["wire_bytes"], e["hlo_collectives"],
        e["static_overlap_fraction"]) for e in ladder}
    assert cal_scores[coarse["key"]] > min(cal_scores.values()), (
        "calibrated model calls the measured-slowest plan best")

    doc = {
        "bench": {"embd": EMBD, "layers": LAYERS, "seq": SEQ,
                  "steps": STEPS, "global_batch": global_batch, "dp": dp,
                  "hbm_budget_gib": BUDGET_GIB,
                  "platform": jax.devices()[0].platform,
                  "sweep_s": round(sweep_s, 1),
                  "rerun_s": round(rerun_s, 1)},
        "winner": {"key": winner_key, "score": round(winner_score, 4),
                   "point": result["winner"]},
        "hand_configs": hand_rows,
        "bundles": bundle_audit,
        "cache": {"second_run_cached": True, "second_run_trials": 0},
        "calibration": {
            "cost_model": result["cost_model"],
            "plan_ladder_rho_static": round(static_rho, 4),
            "plan_ladder_rho_calibrated": round(cal_rho, 4),
            "sweep_rho": result.get("rank_correlation"),
        },
        "table": [{k: e.get(k) for k in
                   ("key", "score", "productive_fraction", "step_tflops",
                    "measured_step_s", "peak_hbm_gib", "disqualified")}
                  for e in table],
        # snapshot-shaped: ds_tpu_top --snapshot renders the panel
        "sections": {"tuning": result.get("tuning") or {}},
        "counters": {},
    }
    out_path = os.path.join(out_dir, "autotune_measured.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"\nall acceptance checks passed -> {out_path}")


if __name__ == "__main__":
    main()
