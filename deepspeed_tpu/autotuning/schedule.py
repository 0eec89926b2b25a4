"""DeepCompile-style schedule autotuner for the bucketed ZeRO exchange.

ROADMAP items 2 + 5 meet here: the bucketed overlap schedule
(runtime/zero/overlap_schedule.py) exposes a plan space —
``(bucket_bytes, overlap on/off, compression policy)`` — and this module
searches it the DeepCompile way (arxiv 2504.09983): **lower the real
step program for every candidate plan and score the compiled HLO with a
cost model**, no hardware in the loop. Each trial builds a real engine
with the plan's config overrides, lowers+compiles ``train_batch`` on the
current backend (CPU works — no chip is needed), and reads:

- module FLOPs from XLA ``cost_analysis``,
- wire bytes / op counts from the comm dispatch's trace-time accounting
  (quantized plans are priced at their compressed wire size),
- the dependency-level static overlap fraction from
  ``telemetry/hlo_cost.collect_schedule_overlap``.

``ScheduleCostModel`` (autotuning/cost_model.py) folds those into
estimated seconds/step; the argmin plan wins. The winner is persisted
per ``(model, mesh, batch, stage)`` **fingerprint**: re-running with the
same fingerprint loads the cached winner without re-sweeping (pass
``force=True`` or delete the cache file to re-tune). ``bin/ds_tpu_tune``
is the CLI.
"""

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils.logging import log_dist, logger
from .cost_model import ScheduleCostModel

__all__ = ["SchedulePlan", "ScheduleTuner", "default_plans",
           "plan_from_config", "engine_fingerprint", "lower_and_measure",
           "tune_schedule", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.environ.get(
    "DSTPU_TUNE_CACHE",
    os.path.join(os.path.expanduser("~"), ".cache", "deepspeed_tpu",
                 "schedule"))


# ------------------------------------------------------------------- the plan

@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """One point of the schedule search space."""
    bucket_bytes: int = 4 << 20
    overlap: bool = True          # False = one fused bucket (monolithic)
    compression: str = "off"      # off | int8 | fp8_block (ZeRO policies)
    layer_chunking: bool = True

    def key(self) -> str:
        if not self.overlap:
            return f"monolithic/comp={self.compression}"
        chunk = "" if self.layer_chunking else "/whole-leaf"
        return (f"bucket={self.bucket_bytes >> 10}KiB/"
                f"comp={self.compression}{chunk}")

    def config_overrides(self) -> Dict[str, Any]:
        """The JSON blocks that make an engine run this plan."""
        over: Dict[str, Any] = {"overlap_schedule": {
            "enabled": True, "overlap": self.overlap,
            "bucket_bytes": int(self.bucket_bytes),
            "layer_chunking": self.layer_chunking}}
        if self.compression != "off":
            over["comm_compression"] = {
                "enabled": True, "all_gather": self.compression,
                "reduce_scatter": self.compression,
                "all_reduce": self.compression, "min_bytes": 0}
        return over

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SchedulePlan":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


def default_plans(bucket_sizes: Sequence[int] = (1 << 20, 4 << 20,
                                                 16 << 20),
                  compressions: Sequence[str] = ("off",),
                  ) -> List[SchedulePlan]:
    """The standard sweep: the monolithic schedule plus bucketed plans
    over a size ladder, per compression policy."""
    plans: List[SchedulePlan] = []
    for comp in compressions:
        plans.append(SchedulePlan(overlap=False, compression=comp))
        for b in bucket_sizes:
            plans.append(SchedulePlan(bucket_bytes=int(b),
                                      compression=comp))
    return plans


def plan_from_config(config: Dict[str, Any]) -> SchedulePlan:
    """The plan a hand-written config encodes (the comparison point for
    "the tuned plan beats the default"). A config without an
    ``overlap_schedule`` block is the monolithic schedule."""
    os_block = dict(config.get("overlap_schedule") or {})
    cc_block = dict(config.get("comm_compression") or {})
    comp = "off"
    if cc_block.get("enabled"):
        comp = cc_block.get("all_gather", "off")
        if comp == "fp32":
            comp = "off"
    if not os_block.get("enabled"):
        return SchedulePlan(overlap=False, compression=comp)
    return SchedulePlan(
        bucket_bytes=int(os_block.get("bucket_bytes", 4 << 20)),
        overlap=bool(os_block.get("overlap", True)),
        compression=comp,
        layer_chunking=bool(os_block.get("layer_chunking", True)))


# ------------------------------------------------------------ fingerprint

def engine_fingerprint(engine) -> str:
    """Stable id of what a schedule plan was tuned FOR: model family +
    dims, mesh shape, batch geometry, ZeRO stage, compute dtype. Same
    fingerprint => the cached winner applies; anything else re-sweeps."""
    cfg = getattr(engine.module, "config", None)
    model_desc = {
        "model": type(engine.module).__name__,
        "config": dataclasses.asdict(cfg)
        if dataclasses.is_dataclass(cfg) else str(cfg),
    }
    mm = engine.mesh_manager
    ident = {
        "model": model_desc,
        "mesh": {"pp": mm.pp, "dp": mm.dp, "tp": mm.tp, "sp": mm.sp,
                 "ep": mm.ep},
        "micro": engine.train_micro_batch_size_per_gpu,
        "gas": engine.gradient_accumulation_steps,
        "zero_stage": engine.zero_stage,
        "dtype": str(engine._compute_dtype or "float32"),
    }
    blob = json.dumps(ident, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ------------------------------------------------------------------ the trial

def lower_and_measure(engine, batch) -> Dict[str, float]:
    """Lower + compile the engine's real train step and return the cost
    inputs: flops (XLA cost_analysis), wire/logical bytes + traced op
    count (comm dispatch accounting across the trace), HLO collective
    count and static overlap fraction. Pure analysis — nothing
    executes."""
    import jax
    import jax.numpy as jnp

    from .. import comm
    from ..telemetry.hlo_cost import cost_summary, hlo_overlap_summary

    before = comm.comm_stats()
    t0 = time.perf_counter()
    with engine.mesh:
        lowered = engine._train_step_fn.lower(
            engine.params, engine.opt_state, engine.scaler_state,
            engine._to_device_batch(batch), jnp.float32(1e-3),
            jax.random.PRNGKey(0), None, jnp.float32(1.0))
        compiled = lowered.compile()
    after = comm.comm_stats()
    hlo = compiled.as_text()
    overlap = hlo_overlap_summary(hlo)
    flops = float(cost_summary(compiled.cost_analysis()).get("flops", 0.0))
    return {
        "flops": flops,
        "wire_bytes": after["bytes"] - before["bytes"],
        "logical_bytes": after["logical_bytes"] - before["logical_bytes"],
        "inter_host_bytes": (after["inter_host_bytes"] -
                             before["inter_host_bytes"]),
        "traced_ops": after["ops"] - before["ops"],
        "hlo_collectives": overlap["collectives"],
        "static_overlap_fraction": overlap["static_overlap_fraction"],
        "async_fraction": overlap["async_fraction"],
        "compile_s": round(time.perf_counter() - t0, 3),
    }


def _engine_trial(model_factory: Callable[[], Any],
                  base_config: Dict[str, Any],
                  batch_factory: Callable[[int], Any],
                  steps: int = 0) -> Callable[[SchedulePlan], Dict]:
    """Default trial runner: fresh engine per plan over a fresh mesh,
    lower+measure, optionally run ``steps`` real train steps for a
    measured wall-time column (0 = analysis only)."""

    def trial(plan: SchedulePlan) -> Dict[str, float]:
        import copy

        import deepspeed_tpu
        from ..parallel import topology

        cfg = copy.deepcopy(base_config)
        cfg.pop("autotuning", None)
        for key, block in plan.config_overrides().items():
            merged = dict(cfg.get(key) or {})
            merged.update(block)
            cfg[key] = merged
        topology.reset_mesh()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model_factory(), config=cfg)
        try:
            gbs = (engine.train_micro_batch_size_per_gpu *
                   engine.dp_world_size)
            batch = batch_factory(gbs)
            metrics = lower_and_measure(engine, batch)
            if steps > 0:
                loss = None
                for _ in range(steps):
                    loss = engine.train_batch(batch=batch)
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = engine.train_batch(batch=batch)
                float(loss)
                metrics["measured_step_s"] = round(
                    (time.perf_counter() - t0) / steps, 4)
                metrics["final_loss"] = float(loss)
        finally:
            engine.close()
        return metrics

    return trial


# ------------------------------------------------------------------ the tuner

class ScheduleTuner:
    """Sweep schedule plans, score with the cost model, persist the
    winner per fingerprint. ``trial_fn(plan) -> metrics`` is injectable
    (tests rig it); the stock one builds real engines."""

    def __init__(self, trial_fn: Callable[[SchedulePlan], Dict],
                 fingerprint: str,
                 plans: Optional[Sequence[SchedulePlan]] = None,
                 cost_model: Optional[ScheduleCostModel] = None,
                 cache_dir: Optional[str] = None):
        self.trial_fn = trial_fn
        self.fingerprint = fingerprint
        self.plans = list(plans) if plans is not None else default_plans()
        self.cost_model = cost_model or ScheduleCostModel()
        self.cache_dir = cache_dir or DEFAULT_CACHE_DIR
        self.swept = False            # did tune() actually run trials?

    @property
    def cache_path(self) -> str:
        return os.path.join(self.cache_dir, f"{self.fingerprint}.json")

    def _score(self, metrics: Dict[str, float]) -> float:
        return self.cost_model.score(
            flops=metrics.get("flops", 0.0),
            wire_bytes=metrics.get("wire_bytes", 0.0),
            n_collectives=metrics.get("hlo_collectives", 0.0),
            overlap_fraction=metrics.get("static_overlap_fraction", 0.0))

    def score_plan(self, plan: SchedulePlan) -> Dict[str, Any]:
        metrics = self.trial_fn(plan)
        return {"plan": plan.to_dict(), "key": plan.key(),
                "score_s": self._score(metrics), **metrics}

    def load_cached(self) -> Optional[Dict[str, Any]]:
        if not os.path.exists(self.cache_path):
            return None
        try:
            with open(self.cache_path) as f:
                result = json.load(f)
        except (OSError, ValueError) as e:
            logger.warning(f"schedule tuner: unreadable cache "
                           f"{self.cache_path}: {e}; re-sweeping")
            return None
        if result.get("fingerprint") != self.fingerprint:
            return None
        return result

    def tune(self, force: bool = False) -> Dict[str, Any]:
        """Cached winner when the fingerprint matches (no trials run),
        else the full sweep. The result carries the winner plan, its
        score, and the whole trial table."""
        self.swept = False
        if not force:
            cached = self.load_cached()
            if cached is not None:
                cached["cached"] = True
                log_dist(
                    f"schedule tuner: cache hit {self.cache_path} -> "
                    f"{SchedulePlan.from_dict(cached['winner']).key()}",
                    ranks=[0])
                return cached
        table: List[Dict[str, Any]] = []
        for plan in self.plans:
            entry = self.score_plan(plan)
            table.append(entry)
            log_dist(
                f"schedule tuner: {entry['key']:32s} "
                f"score {entry['score_s'] * 1e3:8.3f} ms/step  "
                f"overlap {entry.get('static_overlap_fraction', 0):.3f}  "
                f"collectives {entry.get('hlo_collectives', 0)}",
                ranks=[0])
        self.swept = True
        if not table:
            raise RuntimeError("schedule tuner: no plans to sweep")
        best = min(table, key=lambda e: e["score_s"])
        result = {
            "fingerprint": self.fingerprint,
            "winner": best["plan"],
            "winner_key": best["key"],
            "score_s": best["score_s"],
            "cost_model": self.cost_model.to_dict(),
            "table": table,
            "cached": False,
        }
        os.makedirs(self.cache_dir, exist_ok=True)
        tmp = self.cache_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f, indent=1)
        os.replace(tmp, self.cache_path)
        log_dist(f"schedule tuner: winner {best['key']} "
                 f"({best['score_s'] * 1e3:.3f} ms/step) -> "
                 f"{self.cache_path}", ranks=[0])
        return result


def tune_schedule(model_factory: Callable[[], Any],
                  base_config: Dict[str, Any],
                  batch_factory: Callable[[int], Any],
                  plans: Optional[Sequence[SchedulePlan]] = None,
                  cost_model: Optional[ScheduleCostModel] = None,
                  cache_dir: Optional[str] = None,
                  steps: int = 0,
                  force: bool = False) -> Dict[str, Any]:
    """End-to-end convenience: build one probe engine for the
    fingerprint, sweep (or load) the plan space, return the result dict
    (see :class:`ScheduleTuner`)."""
    import copy

    import deepspeed_tpu
    from ..parallel import topology

    topology.reset_mesh()
    probe, _, _, _ = deepspeed_tpu.initialize(
        model=model_factory(), config=copy.deepcopy(base_config))
    try:
        fingerprint = engine_fingerprint(probe)
    finally:
        probe.close()
    tuner = ScheduleTuner(
        _engine_trial(model_factory, base_config, batch_factory,
                      steps=steps),
        fingerprint, plans=plans, cost_model=cost_model,
        cache_dir=cache_dir)
    result = tuner.tune(force=force)
    result["swept"] = tuner.swept
    return result
