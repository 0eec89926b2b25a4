"""DeepSpeedEngine — the core training engine.

TPU-native re-design of the reference engine (deepspeed/runtime/engine.py:183
``DeepSpeedEngine``, 3.2k LoC). The torch engine wraps an nn.Module and
orchestrates hooks/buckets/streams by hand; here the engine owns a *state
pytree* (params, optimizer state, loss-scale state) plus ONE compiled train
step, and the ZeRO/precision/parallelism machinery is expressed as shardings
and pure functions inside that step:

  - forward/backward/step (reference engine.py:1634/1775/1971) are preserved
    as an API for reference-style user loops (micro-grad jit + accumulate +
    apply), while ``train_batch`` compiles the full
    gradient-accumulation × micro-step loop into a single XLA program
    (lax.scan over micro-batches) — the performant path.
  - ZeRO stages = sharding plans (runtime/zero/partition.py); stage-2's
    reduce-scatter happens because per-micro grads carry a dp-sharded
    sharding constraint; stage-3's gathers happen inside the model's layer
    scan; stage-1's optimizer-state sharding makes XLA allgather updated
    params after the (sharded) optimizer update — the all_gather_dp_groups
    step of stage_1_and_2.py:1738.
  - fp16 loss scaling runs inside the step (lax.cond skip), mirroring
    DynamicLossScaler + the overflow check collective (stage_1_and_2.py:1848).
"""

import json
import time
from collections import deque
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from ..accelerator import get_accelerator
from ..comm.logging import configure_comms_logger
from ..models.api import ModelSpec
from ..parallel.topology import initialize_mesh, default_devices
from ..telemetry.trace import (RecompileWatchdog, avals_of,
                               configure_tracer)
from ..utils.logging import logger, log_dist
from ..utils.timer import (SynchronizedWallClockTimer, ThroughputTimer,
                           FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER)
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import (LossScaleState, init_loss_scale_state,
                               grads_finite, update_loss_scale)
from .lr_schedules import get_lr_scheduler
from .optimizers import Optimizer, get_optimizer, wrap_client_optimizer
from .zero.partition import ZeroShardingPlanner

try:
    from ..monitor.monitor import MonitorMaster
except Exception:  # pragma: no cover
    MonitorMaster = None


def _cast_tree(tree, dtype):
    if dtype is None:
        return tree
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def _global_norm(tree):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


class DeepSpeedEngine:

    def __init__(self,
                 args=None,
                 model: ModelSpec = None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 collate_fn=None,
                 config=None,
                 mesh_manager=None,
                 dont_change_device=False):
        assert model is not None, "deepspeed_tpu.initialize requires a model"
        dist.init_distributed()

        if mesh_manager is not None:
            devices = list(mesh_manager.mesh.devices.flat)
        else:
            devices = default_devices()
        self._config = DeepSpeedConfig(config, mpu=mpu, world_size=len(devices))
        cfg = self._config

        if getattr(cfg, "sparse_gradients_enabled", False):
            # accepted = active: this build has no sparse grad path (XLA
            # embedding-gather grads are dense, and dense ICI all-reduce
            # beats allgather-based sparse reduction at TPU vocab scales —
            # runtime/sparse_tensor.py stays available as a host utility)
            from .config_utils import ConfigError
            raise ConfigError(
                "sparse_gradients is not supported on TPU; remove the key "
                "(gradients of embedding gathers are dense under XLA)")
        ep = cfg.expert_parallel_size
        if cfg.data_parallel_size % ep != 0:
            raise ValueError(f"ep={ep} must divide dp={cfg.data_parallel_size}")
        self.mesh_manager = mesh_manager or initialize_mesh(
            pp=cfg.pipeline_parallel_size,
            dp=cfg.data_parallel_size // ep,
            ep=ep,
            sp=cfg.sequence_parallel_size,
            tp=cfg.tensor_parallel_size,
            devices=devices)
        self.mesh = self.mesh_manager.mesh

        self.module = model
        self.training_dataloader = None
        self.client_lr_scheduler = lr_scheduler
        self.collate_fn = collate_fn
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0

        # ---- precision (reference engine dtype wiring, engine.py:1034) ----
        if cfg.fp16.enabled:
            self._compute_dtype = jnp.float16
        elif cfg.bf16.enabled:
            self._compute_dtype = jnp.bfloat16
        else:
            self._compute_dtype = None  # fp32 end-to-end
        self._dynamic_scale = cfg.fp16.enabled and cfg.fp16.dynamic_loss_scale
        # gradient_accumulation_dtype (reference data_types block;
        # validated at config parse): f32 default; bf16 halves the
        # accumulation buffer at ~3 digits of grad-sum precision
        gad = str(cfg.gradient_accumulation_dtype)
        self._grad_acc_dtype = jnp.bfloat16 if gad in ("bf16", "bfloat16") \
            else jnp.float32

        # ---- optimizer (engine.py:1157 _configure_optimizer) ----
        self.optimizer: Optional[Optimizer] = None
        self.lr_scheduler = None
        if optimizer is not None:
            self.optimizer = wrap_client_optimizer(optimizer)
            self._base_lr = 0.0
        elif cfg.optimizer is not None:
            self.optimizer = get_optimizer(cfg.optimizer.type, cfg.optimizer.params)
            self._base_lr = self.optimizer.defaults.get("lr", 1e-3)
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        elif cfg.scheduler is not None and cfg.scheduler.type:
            self.lr_scheduler = get_lr_scheduler(cfg.scheduler.type,
                                                 cfg.scheduler.params)

        # ---- ZeRO sharding plan ----
        zcfg = cfg.zero_config
        self.zero_stage = int(zcfg.stage)
        rules = model.partition_rules() if hasattr(model, "partition_rules") else []
        self.planner = ZeroShardingPlanner(
            self.mesh_manager, self.zero_stage, rules,
            persistence_threshold=zcfg.stage3_param_persistence_threshold
            if self.zero_stage >= 3 else 0)

        # ---- init params + optimizer state, sharded from birth
        #      (the zero.Init story, partition_parameters.py:601: params are
        #      created already-partitioned; no full copy ever materializes) ---
        rng = jax.random.PRNGKey(cfg.seed)
        param_shapes = jax.eval_shape(model.init, rng)
        self.param_shapes = param_shapes
        # frozen-leaf protocol (LoRA base freeze): the optimizer must not
        # touch these leaves at all — stop_gradient alone would still let
        # decoupled weight decay erode them
        self._frozen_mask = (model.frozen_param_mask(param_shapes)
                             if hasattr(model, "frozen_param_mask")
                             else None)
        self._pre_init_validate()
        self.param_shardings = self.planner.param_shardings(param_shapes)
        zoff = zcfg.offload_optimizer
        zpar = zcfg.offload_param
        self._offload = None
        self._param_runner = None
        offload_active = (zoff is not None and
                          getattr(zoff, "device", "none") != "none" and
                          self.optimizer is not None)
        if zpar is not None and getattr(zpar, "device", "none") != "none":
            # ZeRO-Infinity param offload: weights page through HBM layer
            # by layer; no full-size tree ever materializes on device
            # (runtime/zero/param_offload.py). Config validation guarantees
            # stage 3 + offload_optimizer here.
            from .zero.param_offload import ParamOffloadRunner
            self._param_runner = ParamOffloadRunner(self, rng)
            self._offload = self._param_runner.host_opt
            with self.mesh:
                self.params = self._param_runner.resident_params()
            self.opt_state = None
            self.opt_state_shardings = None
        else:
          with self.mesh:
            params_f32 = jax.jit(model.init,
                                 out_shardings=self.param_shardings)(rng)
            if offload_active:
                # ZeRO-Offload: fp32 masters + moments leave the device
                # (runtime/zero/offload.py); the device keeps only the
                # compute-dtype copy.
                from .zero.offload import HostOffloadOptimizer
                self._offload = HostOffloadOptimizer(
                    self.optimizer.name, self.optimizer.defaults, params_f32,
                    self.param_shardings, self._compute_dtype, zoff,
                    frozen_mask=self._frozen_mask)
                if self._compute_dtype is not None:
                    cast = jax.jit(
                        lambda p: _cast_tree(p, self._compute_dtype),
                        out_shardings=self.param_shardings, donate_argnums=0)
                    self.params = cast(params_f32)
                else:
                    self.params = params_f32
                self.opt_state = None
                self.opt_state_shardings = None
            else:
                self.params = params_f32
                if self.optimizer is not None:
                    opt_shapes = jax.eval_shape(self.optimizer.init,
                                                param_shapes)
                    self.opt_state_shardings = self.planner.opt_state_shardings(
                        opt_shapes, param_shapes)
                    self.opt_state = jax.jit(
                        self.optimizer.init,
                        out_shardings=self.opt_state_shardings)(self.params)
                else:
                    self.opt_state = None
                    self.opt_state_shardings = None
        # one-step-delayed optimizer exchange (offload_optimizer.pipeline_*)
        self._offload_pending = None
        self._offload_pipelined = (offload_active and
                                   self._param_runner is None and
                                   zoff is not None and
                                   getattr(zoff, "pipeline", False))
        self.grad_shardings = self.planner.grad_shardings(param_shapes)
        # replicated-from-birth scaler state: an uncommitted host pytree
        # here changes the step fn's input signature once the first step
        # returns committed arrays — one whole silent recompile at step 2
        # (found by the telemetry recompile watchdog)
        self.scaler_state = jax.device_put(
            init_loss_scale_state(cfg.fp16 if cfg.fp16.enabled else None),
            NamedSharding(self.mesh, P()))
        self._base_rng = jax.random.PRNGKey(cfg.seed + 1)

        # ---- elasticity guard (reference engine.py:482-491: the batch
        #      config must belong to the pre-computed elastic plan) ----
        el = (cfg._param_dict or {}).get("elasticity") or {}
        if el.get("enabled") and \
                not el.get("ignore_non_elastic_batch_info", False):
            # world size AND batch must belong to the pre-computed plan;
            # ignore_non_elastic_batch_info trusts the user's batch config
            # entirely (reference semantics)
            from ..elasticity import (ElasticityConfigError,
                                      compute_elastic_config)
            plan_batch, valid, micro = compute_elastic_config(
                cfg._param_dict, world_size=self.dp_world_size)
            if cfg.train_batch_size != plan_batch:
                raise ElasticityConfigError(
                    f"elasticity: config train_batch_size="
                    f"{cfg.train_batch_size} != elastic plan batch "
                    f"{plan_batch} for world size {self.dp_world_size}; "
                    f"set ignore_non_elastic_batch_info to override")
            log_dist(f"elasticity: plan batch={plan_batch} micro={micro} "
                     f"valid world sizes={valid}", ranks=[0])

        # ---- curriculum learning (engine.py:1673-1676 seqlen truncation;
        #      data_pipeline/curriculum_scheduler.py) ----
        self.curriculum_scheduler = None
        self.curriculum_seqlen = None
        self._curriculum_metric = "seqlen"
        cl = dict(cfg.curriculum_learning_legacy or {})
        de = dict(cfg.data_efficiency or {})
        if not cl.get("enabled"):
            ds = de.get("data_sampling", {})
            if de.get("enabled") and ds.get("enabled") and \
                    ds.get("curriculum_learning", {}).get("enabled"):
                cl = dict(ds["curriculum_learning"], enabled=True)
        if cl.get("enabled"):
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(cl)
            self._curriculum_config = cl
            self._curriculum_metric = cl.get("curriculum_metric",
                                             cl.get("curriculum_type",
                                                    "seqlen"))
            if self._curriculum_metric != "seqlen" and \
                    not cl.get("data_analysis_path"):
                logger.warning(
                    f"curriculum metric '{self._curriculum_metric}': no "
                    f"data_analysis_path configured — either run the "
                    f"offline DataAnalyzer (data_pipeline/data_analyzer.py) "
                    f"and set curriculum_learning.data_analysis_path, or "
                    f"wire a DeepSpeedDataSampler with metric_values "
                    f"through deepspeed_io(data_sampler=...)")

        # ---- progressive layer drop (reference engine.py:1667 injects
        #      theta into forward kwargs) ----
        self.progressive_layer_drop = None
        pld = dict(cfg.progressive_layer_drop or {})
        if pld.get("enabled"):
            self._require_fwd_kwarg("pld_theta", "progressive_layer_drop")
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=float(pld.get("theta", 0.5)),
                gamma=float(pld.get("gamma", 0.001)))

        # ---- random-LTD (reference data_routing/basic_layer.py:14 wraps
        #      layers; here the model's layer scan consumes ltd_keep) ----
        self.random_ltd_scheduler = None
        routing = dict(de.get("data_routing") or {})
        rl = dict(routing.get("random_ltd") or {})
        if de.get("enabled") and routing.get("enabled") and rl.get("enabled"):
            self._require_fwd_kwarg("ltd_keep", "random_ltd")
            from .data_pipeline.random_ltd import RandomLTDScheduler
            self.random_ltd_scheduler = RandomLTDScheduler(rl)

        # ---- MoQ (quantize_training): schedule-driven precision drop on
        #      the master weights, optionally gated by Hessian eigenvalues
        #      (reference engine.py:1995-2008) ----
        self.quantizer = None
        self.eigenvalue = None
        qt = dict((cfg._param_dict or {}).get("quantize_training") or {})
        if qt.get("enabled"):
            from .config_utils import ConfigError
            if self._offload is not None:
                raise ConfigError(
                    "quantize_training (MoQ) is not supported together with "
                    "ZeRO-Offload (masters live host-side)")
            from .quantize import Quantizer
            bits = dict(qt.get("quantize_bits") or {})
            sched = dict(qt.get("quantize_schedule") or {})
            algo = dict(qt.get("quantize_algo") or {})
            self.quantizer = Quantizer(
                q_target_bits=int(bits.get("target_bits", 8)),
                q_start_bits=int(bits.get("start_bits", 16)),
                q_period=int(sched.get("quantize_period", 100)),
                q_offset=int(sched.get("schedule_offset", 100)),
                q_groups=int(qt.get("quantize_groups", 1)),
                q_type=algo.get("q_type", "symmetric"),
                q_rounding=algo.get("rounding", "nearest"),
                q_verbose=bool(qt.get("quantize_verbose", False)))
            self._moq_modules = tuple(qt.get("modules", ("",)))
            eig = dict(qt.get("eigenvalue") or {})
            if eig.get("enabled"):
                from .eigenvalue import Eigenvalue
                self.eigenvalue = Eigenvalue(
                    verbose=bool(eig.get("verbose", False)),
                    max_iter=int(eig.get("max_iter", 20)),
                    tol=float(eig.get("tol", 1e-2)),
                    stability=float(eig.get("stability", 1e-6)))
        self._last_eig_batch = None
        self._last_modifiers = (None, None)

        # ---- activation checkpointing: JSON block -> remat policy on the
        #      model (reference checkpointing.py:789 configure()) ----
        if (cfg._param_dict or {}).get("activation_checkpointing") is not None:
            import dataclasses as _dc
            from .activation_checkpointing.checkpointing import configure
            pol = configure(deepspeed_config=cfg)
            if pol == "offload_dots":
                # XLA host-offload remat: single-accelerator scope today —
                # the SPMD partitioner rejects the placement annotation on
                # multi-device meshes, and the CPU test backend has no
                # lowering for it at all
                if devices[0].platform != "tpu":
                    logger.warning(
                        "cpu_checkpointing: host-offload remat has no CPU-"
                        "backend lowering; falling back to "
                        "dots_with_no_batch_dims_saveable for this run")
                    pol = "dots_with_no_batch_dims_saveable"
                elif len(devices) > 1:
                    from .config_utils import ConfigError
                    raise ConfigError(
                        "activation_checkpointing.cpu_checkpointing is "
                        "single-chip scope: XLA's SPMD partitioner cannot "
                        "yet shard host-offloaded remat residuals; drop "
                        "the flag or run on one chip")
            mcfg = getattr(self.module, "config", None)
            if mcfg is not None and hasattr(mcfg, "remat"):
                updates = {"remat": True}
                if hasattr(mcfg, "remat_policy"):
                    updates["remat_policy"] = pol
                if _dc.is_dataclass(mcfg):  # model configs are frozen
                    self.module.config = _dc.replace(mcfg, **updates)
                else:
                    for k, v in updates.items():
                        setattr(mcfg, k, v)

        # ---- dataloader (engine.deepspeed_io, engine.py:1542) ----
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # ---- observability ----
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=cfg.train_batch_size,
            steps_per_output=cfg.steps_per_print)  # 0 = never print
        configure_comms_logger(cfg.comms_logger)
        # structured tracer (telemetry/): fwd/bwd/step spans, comm spans,
        # MFU + recompile-watchdog counters; disabled = zero-cost no-ops
        self.tracer = configure_tracer(cfg.telemetry)
        self.tracer.watch_gc(self)      # until close()
        # goodput ledger (telemetry/goodput.py): wall-clock bucket
        # accounting — productive step vs compile/recompile/checkpoint/
        # sentinel/preemption/data-wait badput; rides telemetry.enabled
        from ..telemetry.goodput import configure_ledger
        self._ledger = configure_ledger(
            enabled=cfg.telemetry.enabled and cfg.telemetry.goodput)
        self._ledger_step_iv = None   # last step interval, for sentinel
                                      # reclassification in _post_step
        self._watchdog = RecompileWatchdog()
        self._step_flops: Dict[int, int] = {}   # id(step_fn) -> analytic flops
        self._step_cost: Dict[int, dict] = {}   # id(step_fn) -> cost summary
        self._last_fn_id = None                 # active compiled executable
        # flight recorder (telemetry/flight_recorder.py): bounded ring of
        # step records + anomaly-triggered postmortem bundles. Off by
        # default = no object, no directory, no thread.
        self._recorder = None
        if cfg.flight_recorder.enabled:
            from ..telemetry.flight_recorder import FlightRecorder
            self._recorder = FlightRecorder(cfg.flight_recorder,
                                            tracer=self.tracer)
            self._recorder.add_provider("training", self._statusz_section)
            self._recorder.set_cost_provider(self._xla_cost_summary)
        # cross-host straggler attribution (telemetry/hostagg.py): per-host
        # step-time/data-wait/heartbeat vector on a low-frequency gather
        self._hostagg = None
        self._last_data_wait_s = 0.0
        if cfg.hostagg.enabled:
            from ..telemetry.hostagg import HostAggregator
            self._hostagg = HostAggregator(cfg.hostagg, tracer=self.tracer,
                                           owner=self)
        # elastic coordinator (elasticity/coordinator.py): with the
        # elasticity block enabled, a hostagg heartbeat gap becomes
        # emergency-save + shrink-and-resume (ElasticResizeRequired)
        # instead of a hang in the next collective. Costs one dict
        # inspection per aggregation when nothing is wrong.
        self._elastic = None
        el_dict = (cfg._param_dict or {}).get("elasticity") or {}
        if el_dict.get("enabled") and self._hostagg is not None:
            from ..elasticity import ElasticCoordinator, ElasticityConfig
            el_cfg = ElasticityConfig(el_dict)
            if el_cfg.resize_on_heartbeat_gap:
                self._elastic = ElasticCoordinator(
                    self, el_cfg, recorder=self._recorder,
                    tracer=self.tracer)
        # compile/memory plane (telemetry/compileplane.py + overlap.py):
        # compile ledger with recompile diffs + cost/memory analysis, HBM
        # role ledger, collective-overlap analyzer. Off by default = no
        # objects, no per-call fingerprints, no gauges.
        self._compile_plane = None
        self._hbm = None
        self._overlap = None
        cpcfg = cfg.compile_plane
        if cpcfg.enabled:
            from ..telemetry.compileplane import CompileLedger, HBMLedger
            self._compile_plane = CompileLedger(cpcfg, tracer=self.tracer,
                                                owner=self)
            if cpcfg.hbm:
                self._hbm = HBMLedger(tracer=self.tracer, owner=self)
            if cpcfg.overlap:
                from ..telemetry.overlap import OverlapAnalyzer
                self._overlap = OverlapAnalyzer(
                    tracer=self.tracer, owner=self,
                    interval_steps=cpcfg.overlap_interval_steps,
                    window_ms=cpcfg.overlap_window_ms,
                    floor=cpcfg.overlap_floor, recorder=self._recorder)
            if self._recorder is not None:
                self._recorder.attach_compile_plane(self._compile_plane)
        # per-engine monitor-event buffer (bounded: survives a disabled
        # monitor without growing) — NOT the tracer's global queue, so two
        # engines in one process can't drain each other's events
        self._telemetry_events = deque(maxlen=256)
        self.monitor = None
        if MonitorMaster is not None:
            try:
                self.monitor = MonitorMaster(cfg)
            except Exception as e:
                logger.warning(f"monitor disabled: {e}")

        # ---- resilience (deepspeed_tpu/resilience/): training sentinel,
        #      preemption handling, auto-checkpoint cadence ----
        rcfg = cfg.resilience
        self._resilience = rcfg
        # skip/rollback also gate the optimizer update INSIDE the compiled
        # step (non-finite grads / grad-norm spikes take the lax.cond skip
        # branch), so a bad step never touches params or optimizer state
        self._sentinel_gate = rcfg.sentinel_policy in ("skip", "rollback")
        self._sentinel = None
        if rcfg.sentinel_policy != "off":
            from ..resilience.sentinel import TrainingSentinel
            self._sentinel = TrainingSentinel(rcfg, tracer=self.tracer,
                                              recorder=self._recorder,
                                              owner=self)
        self._preemption = None
        if rcfg.handle_signals:
            from ..resilience.preemption import PreemptionHandler
            self._preemption = PreemptionHandler.install()
        self._last_save_dir = None   # updated by save_checkpoint
        # recent checkpoint activity, shown on /statusz (appended by
        # runtime/checkpointing.py and the sentinel rollback path)
        self._ckpt_history = deque(maxlen=32)

        # ---- statusz introspection server (telemetry/statusz.py):
        #      /healthz /metrics /statusz /trace — opt-in, off = no thread
        self.statusz = None
        self._closed = False
        if cfg.statusz.enabled:
            from ..telemetry.statusz import StatuszServer
            self.statusz = StatuszServer(cfg.statusz, tracer=self.tracer)
            self.statusz.register("training", self._statusz_section)
            self.statusz.register_health("training", self._health_check)
            if self._recorder is not None:
                self.statusz.attach_recorder(self._recorder)
            if self._hostagg is not None:
                self.statusz.attach_hostagg(self._hostagg)
                # a host with a heartbeat gap is a pod problem: flip
                # /healthz so the operator's probe sees it
                self.statusz.register_health("hosts", self._hostagg.health)
            if self._elastic is not None:
                self.statusz.register("elasticity", self._elastic.summary)
            if self._compile_plane is not None:
                self.statusz.register("compile_plane",
                                      self._compile_plane.summary)
            if self._hbm is not None:
                self.statusz.register("memory", self._hbm.summary)
            if self._overlap is not None:
                self.statusz.register("overlap", self._overlap.summary)

        # ---- comm compression (comm/compression.py, docs/comm.md):
        #      quantized/hierarchical wire formats behind the collective
        #      dispatch. When a ZeRO-relevant policy is active the micro-
        #      gradient computation routes through the explicit shard_map
        #      exchange (runtime/zero/compressed_step.py) so param gathers
        #      and grad reduce-scatters genuinely move compressed bytes;
        #      with every policy "off" the GSPMD path is byte-identical
        #      to an uncompressed build.
        from ..comm.compression import configure_comm_compression
        configure_comm_compression(cfg.comm_compression)
        self._cc_zero_active = (cfg.comm_compression.zero_path_active and
                                self.mesh_manager.dp_world_size > 1)
        # ---- bucketed overlap schedule (runtime/zero/overlap_schedule.py,
        #      docs/comm.md): the explicit exchange additionally takes
        #      schedule ownership — size-targeted layer-order buckets
        #      through coalesced collectives, issued ahead of their first
        #      consuming layer. Composes with comm_compression through the
        #      same dispatch (quantized wire per bucket, per-leaf codec).
        self._sched_active = (cfg.overlap_schedule.enabled and
                              self.mesh_manager.dp_world_size > 1)
        self._compressed_grad_fns: Dict[Any, Any] = {}
        if self._cc_zero_active or self._sched_active:
            from .config_utils import ConfigError
            from .zero.compressed_step import explicit_scope_error
            feature = "overlap_schedule" if self._sched_active else \
                "comm_compression"
            err = explicit_scope_error(self, feature)
            if err:
                raise ConfigError(err)
        if self._sched_active:
            from .zero.overlap_schedule import build_schedule
            _, _, _, sched_info = build_schedule(self, cfg.overlap_schedule)
            self._sched_info = sched_info
            log_dist(
                "overlap_schedule: bucketed ZeRO exchange active "
                f"(overlap={cfg.overlap_schedule.overlap} "
                f"bucket_bytes={cfg.overlap_schedule.bucket_bytes} "
                f"gather_buckets={sched_info['gather_buckets']} "
                f"rs_buckets={sched_info['rs_buckets']} "
                f"layer_chunks={len(sched_info['layer_chunks'])})",
                ranks=[0])
        else:
            self._sched_info = None
        if self._cc_zero_active:
            log_dist(
                "comm_compression: explicit ZeRO exchange active "
                f"(all_gather={cfg.comm_compression.all_gather} "
                f"reduce_scatter={cfg.comm_compression.reduce_scatter} "
                f"all_reduce={cfg.comm_compression.all_reduce} "
                f"block={cfg.comm_compression.block_size} "
                f"hierarchical={cfg.comm_compression.hierarchical})",
                ranks=[0])

        self._grad_acc_buffer = None
        self._grad_acc_count = 0
        self._pending_batch = None
        self._pending_grads = None
        self._cached_fns: Dict[Any, Any] = {}
        self._compile_fns()

        # keys with reference semantics that XLA/GSPMD supersedes: say so
        # once instead of silently swallowing them
        for key, why in (
                ("prescale_gradients", "gradients accumulate/reduce in "
                 "fp32 here, so pre-division for fp16 reduce safety is "
                 "moot"),
                ("communication_data_type", "GSPMD picks collective dtypes "
                 "from the tensors at the insertion point"),
                ("disable_allgather", "XLA owns the gather/broadcast "
                 "choice under SPMD")):
            if (cfg._param_dict or {}).get(key) not in (None, False):
                log_dist(f"config '{key}' is superseded on TPU: {why}",
                         ranks=[0])
        if cfg.load_universal_checkpoint:
            log_dist("load_universal_checkpoint: checkpoints here are "
                     "universal by construction (global arrays reshard on "
                     "load); the flag is honored trivially", ranks=[0])
        if cfg.dump_state:
            log_dist(self._dump_state(), ranks=[0])

        n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(param_shapes))
        log_dist(
            f"DeepSpeedEngine initialized: params={n_params/1e6:.1f}M "
            f"zero_stage={self.zero_stage} mesh=pp{self.mesh_manager.pp}/"
            f"dp{self.mesh_manager.dp}/ep{self.mesh_manager.ep}/"
            f"sp{self.mesh_manager.sp}/tp{self.mesh_manager.tp} "
            f"dtype={self._compute_dtype or 'float32'} "
            f"batch={cfg.train_batch_size} (micro={cfg.train_micro_batch_size_per_gpu} "
            f"gas={cfg.gradient_accumulation_steps})", ranks=[0])

    def _pre_init_validate(self):
        """Hook for subclasses to validate model/mesh compatibility after
        param shapes are known but before params materialize."""

    def _require_fwd_kwarg(self, name: str, feature: str):
        """Accepted config = active config: a feature that needs the model's
        cooperation must raise, not silently no-op, when the model cannot
        honor it."""
        import inspect
        from .config_utils import ConfigError
        try:
            sig = inspect.signature(self.module.apply).parameters
        except (TypeError, ValueError):  # pragma: no cover - exotic models
            sig = {}
        accepts = name in sig or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in sig.values())
        if not accepts:
            raise ConfigError(
                f"config enables {feature} but "
                f"{type(self.module).__name__}.apply() does not accept "
                f"'{name}' — this model cannot honor the setting")

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------
    def _batch_sharding(self, leading_gas: bool):
        """Batch dim over dp axes; token dim over 'seq' when sp>1 (the
        sequence-parallel input sharding — tokens enter already split)."""
        base = self.mesh_manager.batch_spec(shard_seq=True)
        spec = P(None, *base) if leading_gas else base
        return NamedSharding(self.mesh, spec)

    def _micro_loss(self, params, mb, rng, train=True, precast=False,
                    pld_theta=None, ltd_keep=None):
        """Loss of one micro batch. ``precast=True`` means ``params`` is
        already in compute dtype (the train path hoists the cast out of the
        gas scan). pld_theta (traced) / ltd_keep (static) are the
        progressive-layer-drop and random-LTD forward kwargs."""
        pc = params if precast else _cast_tree(params, self._compute_dtype)
        kwargs = {}
        if pld_theta is not None:
            kwargs["pld_theta"] = pld_theta
        if ltd_keep is not None:
            kwargs["ltd_keep"] = ltd_keep
        out = self.module.apply(pc, mb, rng=rng, train=train, **kwargs)
        loss = out[0] if isinstance(out, tuple) else out
        return loss.astype(jnp.float32)

    def _clip_grads(self, grads):
        clip = self._config.gradient_clipping
        if not clip or clip <= 0:
            return grads, _global_norm(grads)
        norm = _global_norm(grads)
        factor = jnp.minimum(1.0, clip / (norm + 1e-6))
        return jax.tree.map(lambda g: g * factor, grads), norm

    def _apply_update(self, params, opt_state, scaler_state, grads, lr,
                      denom):
        """Unscale/average → clip → cond(update | skip) → scaler update.
        Returns ``applied`` alongside ``finite``: with the sentinel gating
        (resilience.sentinel_policy skip/rollback), non-finite grads and
        grad-norm spikes skip the update branch even outside fp16."""
        cfg = self._config
        inv = 1.0 / (denom * scaler_state.scale)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
        grads, grad_norm = self._clip_grads(grads)
        if cfg.fp16.enabled:
            finite = grads_finite(grads)
        else:
            finite = jnp.bool_(True)
        applied = finite
        if self._sentinel_gate:
            if not cfg.fp16.enabled:
                applied = grads_finite(grads)
            thresh = self._resilience.sentinel_grad_norm_threshold
            if thresh > 0:
                applied = applied & (grad_norm <= thresh)

        def do_update(args):
            p, s = args
            new_p, new_s = self.optimizer.update(grads, s, p, lr)
            if self._frozen_mask is not None:
                # static mask: XLA dead-code-eliminates frozen leaves' math
                new_p = jax.tree.map(
                    lambda frz, old, new: old if frz else new,
                    self._frozen_mask, p, new_p)
            return new_p, new_s

        def skip(args):
            return args

        new_params, new_opt = lax.cond(applied, do_update, skip,
                                       (params, opt_state))
        # the scaler reacts to fp16 overflow only — a sentinel skip must
        # not halve the loss scale
        new_scaler = update_loss_scale(
            scaler_state, finite, dynamic=self._dynamic_scale,
            scale_window=cfg.fp16.loss_scale_window,
            min_scale=cfg.fp16.min_loss_scale,
            max_hysteresis=cfg.fp16.hysteresis)
        return new_params, new_opt, new_scaler, finite, grad_norm, applied

    def _compressed_micro_grad(self, ltd_keep):
        """The shard_map'd explicit-ZeRO micro-gradient — bucketed
        overlap schedule (runtime/zero/overlap_schedule.py) when
        ``overlap_schedule`` is on, else the per-leaf compressed exchange
        (runtime/zero/compressed_step.py) — cached per random-LTD token
        budget like the jitted step fns."""
        if ltd_keep not in self._compressed_grad_fns:
            if self._sched_active:
                from .zero.overlap_schedule import make_bucketed_micro_grad
                fn = make_bucketed_micro_grad(self, ltd_keep)
            else:
                from .zero.compressed_step import make_compressed_micro_grad
                fn = make_compressed_micro_grad(self, ltd_keep)
            self._compressed_grad_fns[ltd_keep] = fn
        return self._compressed_grad_fns[ltd_keep]

    def _compile_fns(self):
        if self._param_runner is not None:
            # the param-offload runner owns its own per-stage jits; the
            # whole-tree step fns below would require full params on device
            self._train_step_fn = self._grad_step_fn = None
            self._micro_grad_fn = self._acc_fn = self._apply_fn = None
            self._eval_fn = None
            return
        mesh = self.mesh
        rep = NamedSharding(mesh, P())

        # --- shared gradient-accumulation body (scan over gas micros) ---
        # loss_mul is a traced scalar, 1.0 in normal operation; the
        # ``nan_loss`` fault point passes NaN so injected divergence flows
        # through the REAL path (NaN loss → NaN grads → sentinel gate)
        def accum_grads(params, scaler_state, batch, rng, pld_theta=None,
                        ltd_keep=None, loss_mul=None):
            gas = jax.tree.leaves(batch)[0].shape[0]
            scale = scaler_state.scale
            if loss_mul is not None:
                scale = scale * loss_mul

            # Cast the fp32 masters ONCE, outside the gas scan — grads wrt
            # the cast tree are identical to chaining through the cast's
            # vjp (bf16 grads either way, f32 accumulation either way), but
            # the ~6 bytes/param of cast traffic is paid once per global
            # step instead of once per micro step.
            pc = _cast_tree(params, self._compute_dtype)

            if self._cc_zero_active or self._sched_active:
                # explicit (policy-dispatched) ZeRO exchange: quantized
                # param gathers + hierarchical grad reduce-scatters run
                # through comm/ instead of GSPMD-inserted collectives;
                # bucketed + issue-ordered when overlap_schedule is on
                cfn = self._compressed_micro_grad(ltd_keep)

                def grad_fn(pc_, mb, r):
                    return cfn(pc_, mb, r, scale, pld_theta)
            else:
                def scaled_loss(pc_, mb, r):
                    return self._micro_loss(pc_, mb, r, precast=True,
                                            pld_theta=pld_theta,
                                            ltd_keep=ltd_keep) * scale

                grad_fn = jax.value_and_grad(scaled_loss)
            grad_specs = jax.tree.map(lambda s: s.spec, self.grad_shardings)

            if gas == 1:
                # fast path: no accumulation buffer round-trip through HBM
                lsum, gsum = grad_fn(pc,
                                     jax.tree.map(lambda x: x[0], batch),
                                     jax.random.fold_in(rng, 0))
                gsum = lax.with_sharding_constraint(
                    jax.tree.map(lambda g: g.astype(jnp.float32), gsum),
                    grad_specs)
            else:
                zeros = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, self._grad_acc_dtype),
                    self.param_shapes)

                def body(carry, xs):
                    gacc, lacc = carry
                    mb, i = xs
                    loss, g = grad_fn(pc, mb, jax.random.fold_in(rng, i))
                    g = jax.tree.map(
                        lambda a, b: a + b.astype(self._grad_acc_dtype),
                        gacc, g)
                    # pin ZeRO-2/3 reduce-scatter per micro-step
                    g = lax.with_sharding_constraint(g, grad_specs)
                    return (g, lacc + loss), None

                (gsum, lsum), _ = lax.scan(
                    body, (zeros, jnp.float32(0.0)),
                    (batch, jnp.arange(gas)))
            return lsum, gsum, gas

        # --- fused train_batch step: accumulate + in-jit optimizer update.
        # pld_theta is a traced arg (changes every step); ltd_keep is
        # STATIC — each reached token budget compiles once (the same
        # trade the seqlen curriculum makes), cached in _train_step_cache.
        def make_train_step(ltd_keep):
            def train_step(params, opt_state, scaler_state, batch, lr, rng,
                           pld_theta, loss_mul):
                lsum, gsum, gas = accum_grads(params, scaler_state, batch,
                                              rng, pld_theta, ltd_keep,
                                              loss_mul)
                with jax.named_scope("optimizer"):
                    new_params, new_opt, new_scaler, finite, grad_norm, \
                        applied = self._apply_update(
                            params, opt_state, scaler_state, gsum, lr,
                            denom=jnp.float32(gas))
                metrics = {
                    "loss": lsum / (gas * scaler_state.scale),
                    "grad_norm": grad_norm,
                    "loss_scale": scaler_state.scale,
                    "overflow": ~finite,
                    "applied": applied,
                }
                return new_params, new_opt, new_scaler, metrics

            return jax.jit(
                train_step,
                in_shardings=(self.param_shardings, self.opt_state_shardings,
                              None, self._batch_sharding(True), None, None,
                              None, None),
                out_shardings=(self.param_shardings,
                               self.opt_state_shardings, None, None),
                donate_argnums=(0, 1, 2))

        self._make_train_step = make_train_step
        self._train_step_cache = {}
        self._train_step_fn = make_train_step(None) \
            if self.optimizer is not None and self._offload is None else None

        # --- offload path: grads-only step; host SIMD Adam applies them ---
        def make_grad_step(ltd_keep):
            def grad_step(params, scaler_state, batch, rng, pld_theta,
                          loss_mul):
                lsum, gsum, gas = accum_grads(params, scaler_state, batch,
                                              rng, pld_theta, ltd_keep,
                                              loss_mul)
                return lsum / (gas * scaler_state.scale), gsum

            return jax.jit(
                grad_step,
                in_shardings=(self.param_shardings, None,
                              self._batch_sharding(True), None, None, None),
                out_shardings=(rep, self.grad_shardings))

        self._make_grad_step = make_grad_step
        self._grad_step_fn = make_grad_step(None) \
            if self._offload is not None else None

        # --- micro grad (forward/backward API path) ---
        def make_micro_grad(ltd_keep):
            def micro_grad(params, mb, rng, scale, pld_theta):
                if self._cc_zero_active or self._sched_active:
                    pc = _cast_tree(params, self._compute_dtype)
                    loss, g = self._compressed_micro_grad(ltd_keep)(
                        pc, mb, rng, scale, pld_theta)
                else:
                    def scaled_loss(p):
                        return self._micro_loss(p, mb, rng,
                                                pld_theta=pld_theta,
                                                ltd_keep=ltd_keep) * scale
                    loss, g = jax.value_and_grad(scaled_loss)(params)
                g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
                g = lax.with_sharding_constraint(
                    g, jax.tree.map(lambda s: s.spec, self.grad_shardings))
                return loss, g

            return jax.jit(
                micro_grad,
                in_shardings=(self.param_shardings,
                              self._batch_sharding(False), None, None, None),
                out_shardings=(rep, self.grad_shardings))

        self._make_micro_grad = make_micro_grad
        self._micro_grad_fn = make_micro_grad(None)

        def acc_grads(acc, g):
            return jax.tree.map(jnp.add, acc, g)

        self._acc_fn = jax.jit(acc_grads,
                               in_shardings=(self.grad_shardings,
                                             self.grad_shardings),
                               out_shardings=self.grad_shardings,
                               donate_argnums=(0,))

        def apply_step(params, opt_state, scaler_state, grads, lr, denom):
            new_params, new_opt, new_scaler, finite, grad_norm, applied = \
                self._apply_update(params, opt_state, scaler_state, grads, lr,
                                   denom)
            return new_params, new_opt, new_scaler, {
                "grad_norm": grad_norm, "overflow": ~finite,
                "applied": applied, "loss_scale": scaler_state.scale}

        self._apply_fn = jax.jit(
            apply_step,
            in_shardings=(self.param_shardings, self.opt_state_shardings,
                          None, self.grad_shardings, None, None),
            out_shardings=(self.param_shardings, self.opt_state_shardings,
                           None, None),
            donate_argnums=(0, 1, 2, 3)) \
            if self.optimizer is not None and self._offload is None else None

        # --- eval ---
        def eval_loss(params, mb):
            return self._micro_loss(params, mb, None, train=False)

        self._eval_fn = jax.jit(
            eval_loss,
            in_shardings=(self.param_shardings, self._batch_sharding(False)),
            out_shardings=rep)

    # ------------------------------------------------------------------
    # data
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route=None,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        cfg = self._config
        if batch_size is None:
            batch_size = cfg.train_micro_batch_size_per_gpu * self.dp_world_size
        if data_sampler is None and route in (None, "train"):
            data_sampler = self._maybe_curriculum_sampler(dataset, batch_size)
        return DeepSpeedDataLoader(dataset,
                                   batch_size=batch_size,
                                   collate_fn=collate_fn or self.collate_fn,
                                   drop_last=cfg.dataloader_drop_last,
                                   data_sampler=data_sampler,
                                   seed=cfg.seed)

    def _maybe_curriculum_sampler(self, dataset, batch_size):
        """Auto-build the curriculum data sampler when a non-seqlen metric
        is configured with an offline analysis directory
        (curriculum_learning.data_analysis_path — produced by
        data_pipeline/data_analyzer.py, the reference data_analyzer.py:20
        equivalent). Training route only; seqlen curricula keep the
        in-batch truncation path; iterable (non-Sized) datasets cannot be
        index-sampled and fall through to plain iteration."""
        cl = getattr(self, "_curriculum_config", None)
        if (not cl or self._curriculum_metric == "seqlen" or
                not cl.get("data_analysis_path") or
                not hasattr(dataset, "__len__")):
            return None
        from .data_pipeline.data_analyzer import load_metric_values
        from .data_pipeline.data_sampler import DeepSpeedDataSampler
        values = load_metric_values(cl["data_analysis_path"],
                                    self._curriculum_metric)
        if len(values) != len(dataset):
            raise ValueError(
                f"data_analysis_path metric map has {len(values)} entries "
                f"but the dataset has {len(dataset)} samples — re-run the "
                f"DataAnalyzer on this dataset")
        cfg = self._config
        sampler = DeepSpeedDataSampler(
            dataset,
            batch_size=batch_size,
            metric_values=values,
            curriculum_config=dict(cl),
            difficulty_type=cl.get("difficulty_type", "percentile"),
            # single-controller: each draw is the GLOBAL batch, rank 0 of 1
            dp_rank=0, dp_world=1,
            gradient_accumulation_steps=cfg.gradient_accumulation_steps,
            seed=cfg.seed)
        log_dist(f"curriculum sampler: metric="
                 f"'{self._curriculum_metric}' over "
                 f"{len(values)} analyzed samples", ranks=[0])
        return sampler

    # ------------------------------------------------------------------
    # reference-style API: forward / backward / step  (engine.py:1634+)
    # ------------------------------------------------------------------
    def forward(self, batch, train=True):
        """Compute the micro-batch loss. The grads for this batch are
        produced lazily in backward()."""
        if self._param_runner is not None:
            raise RuntimeError(
                "offload_param supports the train_batch()/eval_batch() API "
                "only (the forward/backward/step micro API would re-page "
                "every layer per call)")
        self.timers(FORWARD_GLOBAL_TIMER).start()
        tr = self.tracer
        g_iv = self._ledger.track("productive_step")
        with g_iv, tr.span("fwd", cat="train",
                           args={"micro_step": self.micro_steps}) as sp:
            batch = self._apply_curriculum(batch, min_ndim=2)
            self._pending_batch = self._to_device_batch(batch)
            rng = jax.random.fold_in(self._base_rng, self.micro_steps)
            scale = self.scaler_state.scale
            theta, keep = self._step_modifiers() if train else (None, None)
            fn = self._micro_grad_fn if keep is None else \
                self._train_step_cache.setdefault(
                    ("micro", keep), self._make_micro_grad(keep))
            cp_ev = self._observe_compile(
                "fwd", fn, (self.params, self._pending_batch, rng, scale,
                            theta),
                names=("params", "batch", "rng", "scale", "pld_theta"))
            t_cp = time.perf_counter() if cp_ev is not None else 0.0
            with tr.span("dispatch", cat="train"):
                with self.mesh:
                    loss, grads = fn(self.params, self._pending_batch, rng,
                                     scale, theta)
            if tr.sync_spans:
                sp.sync_on(loss)
        if cp_ev is not None:
            self._compile_plane.finish(
                cp_ev, (time.perf_counter() - t_cp) * 1e3)
        first_sight = not self._watchdog.seen(fn)
        if self._watchdog.observe(fn, tracer=tr, label="fwd", owner=self):
            g_iv.reclassify("recompile")
        elif first_sight:
            g_iv.reclassify("compile")
        self._pending_grads = grads
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss / scale

    def backward(self, loss=None, allreduce_gradients=True):
        """Accumulate the pending micro-batch gradients (the grad-hook +
        bucket path of stage_1_and_2.py:793 collapses to one jitted add)."""
        assert self._pending_grads is not None, "backward() without forward()"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        tr = self.tracer
        with self._ledger.track("productive_step"), \
             tr.span("bwd", cat="train",
                     args={"micro_step": self.micro_steps}) as sp:
            with tr.span("accumulate", cat="train"):
                with self.mesh:
                    if self._grad_acc_buffer is None:
                        self._grad_acc_buffer = self._pending_grads
                    else:
                        self._grad_acc_buffer = self._acc_fn(
                            self._grad_acc_buffer, self._pending_grads)
            if tr.sync_spans:
                sp.sync_on(self._grad_acc_buffer)
        self._grad_acc_count += 1
        self._pending_grads = None
        self.micro_steps += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()

    def is_gradient_accumulation_boundary(self):
        return self._grad_acc_count >= self._config.gradient_accumulation_steps

    def step(self):
        """Optimizer step at the accumulation boundary (engine.py:1971)."""
        assert self.optimizer is not None, "step() requires an optimizer"
        assert self._grad_acc_buffer is not None, "step() without backward()"
        self.timers(STEP_GLOBAL_TIMER).start()
        tr = self.tracer
        g_iv = self._ledger.track("productive_step")
        with g_iv, tr.span("step", cat="train",
                           args={"step": self.global_steps}) as sp:
            if self._offload is not None:
                with tr.span("host_opt_step", cat="train"):
                    metrics = self._offload_apply(
                        self._grad_acc_buffer,
                        denom=float(self._grad_acc_count))
            else:
                lr = jnp.float32(self.get_lr()[0])
                with tr.span("apply", cat="train"):
                    with self.mesh:
                        (self.params, self.opt_state, self.scaler_state,
                         metrics) = self._apply_fn(
                             self.params, self.opt_state, self.scaler_state,
                             self._grad_acc_buffer, lr,
                             jnp.float32(self._grad_acc_count))
                if tr.sync_spans:
                    sp.sync_on(metrics)
        self._grad_acc_buffer = None
        self._grad_acc_count = 0
        self._ledger_step_iv = g_iv   # _post_step may reclassify (sentinel)
        self._post_step(metrics)
        self.timers(STEP_GLOBAL_TIMER).stop()
        return metrics

    def _pipelined_offload_step(self, fn, batch, rng, theta, gas,
                                loss_mul=None):
        """One-step-delayed optimizer exchange (reference
        swap_tensor/pipelined_optimizer_swapper.py; round-3 weak #4): the
        grad step for THIS batch is dispatched async, then the host applies
        the PREVIOUS batch's grads (Adam on the masters) and uploads fresh
        params while the device computes. Params used by step N therefore
        reflect grads through step N-2 — the standard delayed-param-update
        staleness, opted into via offload_optimizer.pipeline_read/write."""
        if loss_mul is None:
            loss_mul = jnp.float32(1.0)
        with self.mesh:
            loss, gsum = fn(self.params, self.scaler_state, batch, rng,
                            theta, loss_mul)
        # start this step's grad d2h immediately so it lands during the
        # next step's host work
        for g in jax.tree.leaves(gsum):
            try:
                g.copy_to_host_async()
            except AttributeError:
                pass
        pend = self._offload_pending
        # the grads were produced under the CURRENT loss scale; by the time
        # they apply (next call) update_loss_scale may have moved it
        self._offload_pending = {"gsum": gsum, "denom": gas, "loss": loss,
                                 "scale": float(self.scaler_state.scale)}
        if pend is None:
            # first step: nothing to apply yet (params lag one step)
            return {"loss": loss, "grad_norm": 0.0, "overflow": False,
                    "loss_scale": float(self.scaler_state.scale),
                    "pipelined_skip": True}
        metrics = self._offload_apply(pend["gsum"], denom=pend["denom"],
                                      scale=pend["scale"])
        metrics["loss"] = pend["loss"]
        return metrics

    def _drain_offload_pipeline(self):
        """Apply any in-flight delayed grads (checkpoint/export/eval
        boundaries need the masters caught up)."""
        pend = getattr(self, "_offload_pending", None)
        if pend is None:
            return
        self._offload_pending = None
        self._offload_apply(pend["gsum"], denom=pend["denom"],
                            scale=pend["scale"])

    def _offload_apply(self, grads, denom, scale=None):
        """Host-side optimizer step (ZeRO-Offload): unscale/clip/step on the
        CPU SIMD path, refresh the device's compute-dtype params.
        ``scale``: the loss scale the grads were PRODUCED under (pipelined
        mode applies them one step later, when the live scale may differ)."""
        cfg = self._config
        if scale is None:
            scale = float(self.scaler_state.scale)
        lr = float(self.get_lr()[0])
        new_params, info = self._offload.step(
            grads, lr, unscale=1.0 / (denom * scale),
            clip=float(cfg.gradient_clipping or 0.0),
            check_finite=cfg.fp16.enabled)
        finite = not info["overflow"]
        if finite:
            self.params = new_params
        self.scaler_state = update_loss_scale(
            self.scaler_state, jnp.bool_(finite), dynamic=self._dynamic_scale,
            scale_window=cfg.fp16.loss_scale_window,
            min_scale=cfg.fp16.min_loss_scale,
            max_hysteresis=cfg.fp16.hysteresis)
        self._last_grad_norm = info["grad_norm"]
        return {"grad_norm": info["grad_norm"], "overflow": not finite,
                "loss_scale": scale}

    # ------------------------------------------------------------------
    # fused path: train_batch (the PipelineEngine-compatible entrypoint)
    # ------------------------------------------------------------------
    def train_batch(self, data_iter=None, batch=None):
        """Run one full global step (gas × micro) as one compiled program.
        The step's host time is on the tracer's phase ring: ``train/step``
        whole, and inside it ``train/input``, ``train/dispatch``,
        ``train/readback`` and ``train/post``."""
        with self.tracer.phase("train/step", self.global_steps):
            return self._train_batch(data_iter, batch)

    def _train_batch(self, data_iter, batch):
        assert self.optimizer is not None
        cfg = self._config
        self._check_preemption()
        if self._elastic is not None:
            # a latched heartbeat gap becomes emergency-save +
            # ElasticResizeRequired here, BEFORE the next collective
            # would hang on the dead host
            self._elastic.check()
        # flight recorder: the step record's wall time starts here so an
        # injected (or real) input-pipeline stall is part of the step the
        # operator sees — the record's goodput deltas attribute it
        rec = self._recorder
        t_rec = time.perf_counter() if (rec is not None or
                                        self._hostagg is not None) else 0.0
        if rec is not None:
            from ..resilience.faults import fault
            if fault("slow_step"):
                # deterministic slow-step injection: sleep well past the
                # k×EMA trigger whatever this machine's step time is
                time.sleep(0.05 + 5.0 * rec.ema_ms / 1e3)
        tr = self.tracer
        with tr.phase("train/input"):
            if batch is None:
                batch = self._next_gas_batch(data_iter)
            batch = self._apply_curriculum(batch)
            if self._param_runner is None:
                batch = self._to_device_batch(batch)
        if self._param_runner is not None:
            self.tput_timer.start()
            g_iv = self._ledger.track("productive_step")
            with g_iv:
                metrics = self._param_runner.train_batch(batch)
            self.micro_steps += cfg.gradient_accumulation_steps
            self._ledger_step_iv = g_iv
            if rec is not None or self._hostagg is not None:
                self._flight_record((time.perf_counter() - t_rec) * 1e3,
                                    False, False)
            self._post_step(metrics)
            self.tput_timer.stop(global_step=True)
            return metrics["loss"]
        self.tput_timer.start()
        rng = jax.random.fold_in(self._base_rng, self.global_steps)
        self._maybe_profile_flops(batch, rng)
        theta, keep = self._step_modifiers()
        loss_mul = self._loss_mul()
        if self.eigenvalue is not None:
            self._last_eig_batch = (jax.tree.map(lambda x: x[0], batch), rng)
        step_span = tr.span("train_batch", cat="train",
                            args={"step": self.global_steps}
                            if tr.enabled else None)
        g_iv = self._ledger.track("productive_step")
        fn = None
        cp_ev = None      # pending compile-ledger event (compile plane)
        t_cp = 0.0
        with g_iv, step_span as sp:
            if self._offload is not None:
                # denom = the batch's ACTUAL gas dim (accum_grads derives gas
                # the same way), not the config value — they can legitimately
                # differ
                gas = jax.tree.leaves(batch)[0].shape[0]
                fn = self._grad_step_fn if keep is None else \
                    self._train_step_cache.setdefault(
                        ("grad", keep), self._make_grad_step(keep))
                self._maybe_telemetry_flops(
                    fn, (self.params, self.scaler_state, batch, rng, theta,
                         loss_mul))
                cp_ev = self._observe_compile(
                    "train_batch", fn,
                    (self.params, self.scaler_state, batch, rng, theta,
                     loss_mul),
                    names=("params", "scaler_state", "batch", "rng",
                           "pld_theta", "loss_mul"))
                t_cp = time.perf_counter() if cp_ev is not None else 0.0
                if self._offload_pipelined:
                    metrics = self._pipelined_offload_step(fn, batch, rng,
                                                           theta, float(gas),
                                                           loss_mul)
                else:
                    with tr.span("dispatch", cat="train"):
                        with self.mesh:
                            loss, gsum = fn(self.params, self.scaler_state,
                                            batch, rng, theta, loss_mul)
                    with tr.span("host_opt_step", cat="train"):
                        metrics = self._offload_apply(gsum, denom=float(gas))
                    metrics["loss"] = loss
            else:
                lr = jnp.float32(self.get_lr()[0])
                fn = self._train_step_fn if keep is None else \
                    self._train_step_cache.setdefault(
                        ("train", keep), self._make_train_step(keep))
                self._maybe_telemetry_flops(
                    fn, (self.params, self.opt_state, self.scaler_state,
                         batch, lr, rng, theta, loss_mul))
                with tr.phase("train/dispatch"):
                    if not getattr(fn, "noted", False):     # its first call
                        fn.noted = True
                        tr.note_program(
                            "jit_train_step", ("train", keep), fn,
                            avals_of((self.params, self.opt_state,
                                      self.scaler_state, batch, lr, rng,
                                      theta, loss_mul)), self.mesh)
                    cp_ev = self._observe_compile(
                        "train_batch", fn,
                        (self.params, self.opt_state, self.scaler_state,
                         batch, lr, rng, theta, loss_mul),
                        names=("params", "opt_state", "scaler_state",
                               "batch", "lr", "rng", "pld_theta", "loss_mul"),
                        donated=(0, 1, 2))
                    t_cp = time.perf_counter() if cp_ev is not None else 0.0
                    with tr.span("dispatch", cat="train"), self.mesh:
                        (self.params, self.opt_state, self.scaler_state,
                         metrics) = fn(self.params, self.opt_state,
                                       self.scaler_state, batch, lr, rng,
                                       theta, loss_mul)
            if tr.sync_spans:
                sp.sync_on(metrics)
        if cp_ev is not None:
            # the wall time of the step that paid this compile event
            self._compile_plane.finish(
                cp_ev, (time.perf_counter() - t_cp) * 1e3)
            if self._overlap is not None and cp_ev.get("overlap"):
                # a recompile whose program de-overlapped the schedule
                # trips the overlap_floor -> flight-recorder trigger
                self._overlap.note_hlo(cp_ev["overlap"],
                                       kind=cp_ev.get("kind", "compile"),
                                       label=cp_ev.get("label", ""),
                                       step=cp_ev.get("step"))
        # goodput classification: a step that paid the initial XLA compile
        # or a watchdog-flagged recompile was not productive step time —
        # the first sight is read BEFORE _telemetry_step_end registers fn
        first_sight = fn is not None and not self._watchdog.seen(fn)
        rc_before = self._watchdog.recompiles
        with tr.phase("train/post"):
            self._telemetry_step_end(fn, step_span)
        if fn is not None and not tr.enabled and \
                (rec is not None or self._hostagg is not None):
            # the watchdog normally rides _telemetry_step_end; keep the
            # recompile trigger honest when only the recorder is on
            self._watchdog.observe(fn, label="train_batch")
        recompiled = self._watchdog.recompiles > rc_before
        if first_sight:
            g_iv.reclassify("compile")
        elif recompiled:
            g_iv.reclassify("recompile")
        self._last_fn_id = id(fn) if fn is not None else None
        self._ledger_step_iv = g_iv
        self.micro_steps += cfg.gradient_accumulation_steps
        if rec is not None or self._hostagg is not None:
            self._flight_record((time.perf_counter() - t_rec) * 1e3,
                                first_sight, recompiled)
        self._post_step(metrics)
        self.tput_timer.stop(global_step=True)
        return metrics["loss"]

    def eval_batch(self, batch):
        if self._param_runner is not None:
            return self._param_runner.eval_batch(batch)
        self._drain_offload_pipeline()
        batch = self._to_device_batch(batch)
        with self.mesh:
            return self._eval_fn(self.params, batch)

    def _apply_curriculum(self, batch, min_ndim: int = 3):
        """Seqlen curriculum: truncate the token axis to the current
        difficulty (reference engine.py:1673 curriculum_seqlen kwarg).
        Sliced host-side, so each reached difficulty compiles once.
        The token length comes from batch['input_ids'] (dict batches) and
        only token-shaped leaves ([gas, B, T] here, [B, T] on the micro
        path via min_ndim=2) are sliced — scalar-per-sample leaves like
        doc ids are left alone."""
        if self.curriculum_scheduler is None or \
                self._curriculum_metric != "seqlen":
            return batch
        if isinstance(batch, dict) and "input_ids" in batch:
            full = batch["input_ids"].shape[-1]
        else:
            cands = [x for x in jax.tree.leaves(batch)
                     if getattr(x, "ndim", 0) >= min_ndim]
            if not cands:
                return batch
            full = cands[0].shape[-1]
        seqlen = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)
        self.curriculum_seqlen = seqlen
        if seqlen >= full:
            return batch
        return jax.tree.map(
            lambda x: x[..., :seqlen] if getattr(x, "ndim", 0) >= min_ndim
            and x.shape[-1] == full else x, batch)

    def _maybe_profile_flops(self, batch, rng):
        """FlopsProfilerConfig hook: at profile_step, cost-analyze the
        compiled train step (reference engine wiring of FlopsProfiler,
        engine.py:1646-1664). Analysis only — the step fn donates its
        inputs, so the REAL step that follows provides the latency (the
        report is emitted from _post_step)."""
        fpcfg = self._config.flops_profiler
        if not fpcfg.enabled or self.global_steps != fpcfg.profile_step:
            return
        from ..profiling.flops_profiler import FlopsProfiler
        prof_fn = self._grad_step_fn if self._offload is not None \
            else self._train_step_fn
        if prof_fn is None:
            return
        lr = jnp.float32(self.get_lr()[0])
        one = jnp.float32(1.0)
        args = (self.params, self.scaler_state, batch, rng, None, one) \
            if self._offload is not None else \
            (self.params, self.opt_state, self.scaler_state, batch, lr, rng,
             None, one)
        profiler = FlopsProfiler(fpcfg)
        with self.mesh:
            prof = profiler.profile(prof_fn, *args)
        self._flops_profile = prof
        self._flops_profile_t0 = time.perf_counter()

    def _emit_flops_report(self, metrics):
        """Finish the profile started by _maybe_profile_flops: the step has
        run; block on its output for an honest latency, then report."""
        prof = getattr(self, "_flops_profile", None)
        t0 = getattr(self, "_flops_profile_t0", None)
        if prof is None or t0 is None:
            return
        self._flops_profile_t0 = None
        from ..profiling.flops_profiler import FlopsProfiler
        fpcfg = self._config.flops_profiler
        loss = metrics.get("loss")
        if hasattr(loss, "block_until_ready"):
            loss.block_until_ready()
        latency = time.perf_counter() - t0
        n_params = sum(int(np.prod(s.shape))
                       for s in jax.tree.leaves(self.param_shapes))
        report = FlopsProfiler(fpcfg).report(prof, params=n_params,
                                             latency_s=latency)
        log_dist("\n" + report, ranks=[0])
        if fpcfg.output_file and jax.process_index() == 0:
            with open(fpcfg.output_file, "w") as f:
                f.write(report + "\n")

    # ------------------------------------------------------------------
    # telemetry (telemetry/): MFU, recompile watchdog, memory high-water
    # ------------------------------------------------------------------
    def _maybe_telemetry_flops(self, fn, args):
        """Analytic FLOPs of the compiled step, once per step fn — the MFU
        numerator. Must run BEFORE the step call: the step donates its
        inputs, and tracing needs live avals."""
        tcfg = self._config.telemetry
        if not (self.tracer.enabled and tcfg.mfu) or fn is None or \
                id(fn) in self._step_flops:
            return
        try:
            from ..profiling.flops_profiler import FlopsProfiler
            with self.mesh:
                prof = FlopsProfiler().profile(fn, *args)
            self._step_flops[id(fn)] = int(prof["flops"])
            # cost evidence for flight-recorder bundles: what the active
            # compiled executable costs, per the analytic count AND XLA's
            # own cost analysis of the lowered program
            self._step_cost[id(fn)] = {
                "flops": int(prof["flops"]),
                "xla_flops": prof.get("xla_flops"),
                "per_phase": prof.get("per_phase"),
            }
        except Exception as e:
            logger.warning(f"telemetry: step flops profile failed: {e}")
            self._step_flops[id(fn)] = 0

    def _observe_compile(self, label, fn, args, names=None, donated=()):
        """Compile-ledger hook (telemetry/compileplane.py): fingerprint
        this call's arguments BEFORE the step runs (the step donates its
        inputs) and record a compile/recompile event — with the diff
        naming the changed argument — when the signature is new. No-op
        without the ``compile_plane`` config block."""
        cp = self._compile_plane
        if cp is None or fn is None:
            return None
        try:
            return cp.observe(label, fn, args, names=names, donated=donated,
                              step=self.global_steps, mesh=self.mesh)
        except Exception as e:   # observability must never fail the step
            logger.warning(f"compile plane: observe failed: {e}")
            return None

    def _update_hbm(self):
        """HBM role ledger update: per-device live bytes of the state
        trees plus the active executable's temp allocation — the
        ``dstpu_mem_*`` gauges and the Perfetto waterline sample."""
        hbm = self._hbm
        if hbm is None:
            return
        try:
            roles = {"params": hbm.device_bytes(self.params)}
            if self.opt_state is not None:
                roles["optimizer_state"] = hbm.device_bytes(self.opt_state)
            grads = 0
            if self._grad_acc_buffer is not None:
                grads += hbm.device_bytes(self._grad_acc_buffer)
            if self._pending_grads is not None:
                grads += hbm.device_bytes(self._pending_grads)
            roles["grads"] = grads
            # activations/temps: the compiled step's per-device temp
            # allocation from memory_analysis (grads and activations live
            # there inside the fused step); 0 when analysis is off
            ev = self._compile_plane.last_event("train_batch") \
                if self._compile_plane is not None else None
            mem = (ev or {}).get("memory") or {}
            roles["activations"] = int(mem.get("temp", 0))
            stats = jax.local_devices()[0].memory_stats() or {}
            hbm.update(roles, peak_bytes=stats.get("peak_bytes_in_use"))
        except Exception as e:
            logger.warning(f"compile plane: HBM ledger update failed: {e}")

    def _telemetry_step_end(self, fn, span):
        """Per-step gauges after the synced train_batch span: step time,
        MFU, live-memory high-water, recompile watchdog."""
        tr = self.tracer
        if not tr.enabled:
            return
        step = self.global_steps

        def gauge(tag, value):
            tr.set_counter(tag, value, step, owner=self)
            self._telemetry_events.append((tag, value, step))

        dur_s = span.dur_us / 1e6
        gauge("telemetry/step_time_ms", span.dur_us / 1e3)
        # recompile watchdog: a shape/dtype change that grew the jit cache
        # this step is a perf cliff — count it, don't guess
        if self._watchdog.observe(fn, tracer=tr, label="train_batch",
                                  owner=self):
            gauge("telemetry/recompiles", float(self._watchdog.recompiles))
        stats = jax.local_devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak:
            gauge("telemetry/peak_hbm_gib", peak / 2**30)
        flops = self._step_flops.get(id(fn), 0) if fn is not None else 0
        if not flops and fn is not None and self._compile_plane is not None:
            # MFU fallback: with the flops profiler off (telemetry.mfu
            # false, or a failed trace), derive step FLOPs from the
            # compile ledger's cost_analysis of the active executable so
            # telemetry/mfu keeps reporting instead of silently reading 0
            flops = int(self._compile_plane.step_flops("train_batch", fn))
            if flops:
                self._step_flops[id(fn)] = flops
        if flops and dur_s > 0:
            achieved = flops / dur_s
            gauge("telemetry/step_tflops", achieved / 1e12)
            peak_t = self._config.telemetry.peak_tflops_per_device
            if peak_t > 0:
                mfu = achieved / (peak_t * 1e12 * max(1, self.mesh.size))
                gauge("telemetry/mfu", mfu)

    def _export_telemetry(self):
        """Write the Chrome trace / metrics snapshot files (config:
        telemetry.trace_output / snapshot_output)."""
        tcfg = self._config.telemetry
        if jax.process_index() != 0:
            return
        from ..telemetry.export import write_chrome_trace, write_snapshot
        try:
            if tcfg.trace_output:
                write_chrome_trace(tcfg.trace_output, self.tracer)
            if tcfg.snapshot_output:
                write_snapshot(tcfg.snapshot_output, self.tracer,
                               extra={"global_steps": self.global_steps})
        except OSError as e:
            logger.warning(f"telemetry export failed: {e}")

    def _xla_cost_summary(self) -> dict:
        """Bundle section: the XLA cost-analysis summary of the compiled
        executable the last step ran (captured when the MFU profiler
        traced it), falling back to the compile ledger's cost capture
        when telemetry.mfu is off."""
        out = dict(self._step_cost.get(self._last_fn_id, {}))
        if not out and self._compile_plane is not None:
            ev = self._compile_plane.last_event("train_batch")
            if ev is not None and ev.get("cost"):
                out = {"flops": ev["cost"].get("flops"),
                       "xla_cost": ev["cost"],
                       "source": "compile_plane"}
        return out

    def _flight_record(self, dur_ms, compiled, recompiled):
        """Feed one finished step to the flight recorder (ring record,
        slow-step rule, recompile trigger) and the host aggregator
        (straggler attribution on its gather cadence)."""
        rec = self._recorder
        if rec is not None:
            rec.record_step(self.global_steps, dur_ms, compile=compiled,
                            recompile=recompiled)
            if recompiled:
                detail = (f"step {self.global_steps}: jit cache grew "
                          f"({self._watchdog.recompiles} recompiles total)")
                cp = self._compile_plane
                if cp is not None and cp.last_recompile is not None:
                    # name the cause, not just the count: the compile
                    # ledger's fingerprint diff of the changed argument
                    detail += " — " + "; ".join(
                        cp.last_recompile["diff"][:3])
                rec.trigger("recompile", detail, step=self.global_steps)
        agg = self._hostagg
        if agg is not None:
            dw_ms = 0.0
            if self._ledger.enabled:
                dw = self._ledger.totals().get("data_wait", 0.0)
                dw_ms = max(0.0, (dw - self._last_data_wait_s) * 1e3)
                self._last_data_wait_s = dw
            agg.update_local(dur_ms, data_wait_ms=dw_ms)
            res = agg.maybe_aggregate(self.global_steps + 1)
            if res and res.get("new_straggler") and rec is not None:
                rec.trigger(
                    "straggler",
                    f"host {res['straggler']} step time "
                    f"{res['max_ms']:.1f}ms vs median "
                    f"{res['median_ms']:.1f}ms ({res['spread']:.2f}x)",
                    step=self.global_steps)
            if res and self._elastic is not None:
                # latch only — the emergency save + ElasticResizeRequired
                # fire at the NEXT step boundary (train_batch calls
                # _elastic.check() beside _check_preemption), after
                # _post_step counted this completed step
                self._elastic.observe(res)

    def _next_gas_batch(self, data_iter):
        """Stack gas micro-batches from an iterator into [gas, ...] leaves.
        Time blocked on the input pipeline is ``data_wait`` badput."""
        gas = self._config.gradient_accumulation_steps
        with self._ledger.track("data_wait"):
            micros = [next(data_iter) for _ in range(gas)]
        return jax.tree.map(lambda *xs: np.stack(xs), *micros)

    def _to_device_batch(self, batch):
        return jax.tree.map(jnp.asarray, batch)

    # ------------------------------------------------------------------
    # resilience (resilience/): preemption, sentinel, fault injection
    # ------------------------------------------------------------------
    def _loss_mul(self):
        """Traced loss multiplier: 1.0 normally; NaN when the ``nan_loss``
        fault point fires, so injected divergence exercises the REAL
        NaN-loss path (grads go NaN inside the compiled step)."""
        from ..resilience.faults import fault
        if fault("nan_loss"):
            logger.warning(
                f"fault injection: nan_loss at step {self.global_steps}")
            return jnp.float32(np.nan)
        return jnp.float32(1.0)

    @property
    def preempted(self) -> bool:
        """True once a preemption signal (or injected ``preempt_signal``
        fault) has been observed; train_batch raises TrainingPreempted at
        its next call."""
        return self._preemption is not None and self._preemption.preempted

    def _check_preemption(self):
        """Step-boundary preemption check: on SIGTERM/SIGINT (or the
        ``preempt_signal`` fault), write an emergency checkpoint and raise
        ``TrainingPreempted`` BEFORE consuming the next batch — resume from
        the emergency checkpoint replays the identical trajectory."""
        if self._preemption is None:
            return
        from ..resilience.faults import fault
        from ..resilience.preemption import TrainingPreempted
        if fault("preempt_signal"):
            self._preemption.signal()
        if not self._preemption.preempted:
            return
        tr = self.tracer
        tr.set_counter("resilience/preemptions", 1.0, self.global_steps,
                       owner=self)
        if self._recorder is not None:
            # capture BEFORE the emergency save: there may be no second
            # chance, so the preemption trigger bypasses debounce
            self._recorder.trigger(
                "preemption",
                f"signal latched at step {self.global_steps}",
                step=self.global_steps, force=True)
        with tr.span("emergency_checkpoint", cat="resilience",
                     args={"step": self.global_steps}):
            # outermost-wins: the emergency save's IO counts as
            # 'preemption' badput, not 'checkpoint_save'
            with self._ledger.track("preemption"):
                ckpt_dir = self._emergency_checkpoint()
        where = f"at {ckpt_dir}" if ckpt_dir else \
            "NOT saved (no known checkpoint directory)"
        raise TrainingPreempted(
            f"preemption signal received; emergency checkpoint {where} "
            f"after step {self.global_steps}", checkpoint_dir=ckpt_dir)

    def _emergency_checkpoint(self):
        rcfg = self._resilience
        save_dir = (rcfg.emergency_checkpoint_dir or rcfg.autosave_dir or
                    self._last_save_dir)
        if save_dir is None:
            logger.warning(
                "preempted but no emergency_checkpoint_dir / autosave_dir "
                "configured and no prior save_checkpoint call; state lost")
            return None
        log_dist(f"preemption: writing emergency checkpoint to {save_dir}",
                 ranks=[0])
        return self.save_checkpoint(save_dir)

    def _sentinel_rollback(self):
        """Rollback policy: restore the last known checkpoint (emergency /
        autosave / last explicit save directory)."""
        from ..resilience.sentinel import SentinelError
        rcfg = self._resilience
        load_dir = (self._last_save_dir or rcfg.autosave_dir or
                    rcfg.emergency_checkpoint_dir)
        if load_dir is None:
            raise SentinelError(
                "sentinel rollback requested but no checkpoint exists: "
                "save one (or configure resilience.autosave_dir) before "
                "enabling sentinel_policy='rollback'")
        log_dist(f"sentinel: rolling back to last checkpoint in {load_dir} "
                 f"(rollback #{self._sentinel.rollbacks})", ranks=[0])
        with self.tracer.span("sentinel_rollback", cat="resilience"):
            # outermost-wins: the checkpoint load inside lands in the
            # ledger's 'sentinel' bucket, not 'checkpoint_load'
            with self._ledger.track("sentinel"):
                self.load_checkpoint(load_dir)
        self._ckpt_history.append(
            {"kind": "rollback", "dir": str(load_dir),
             "step": self.global_steps})

    def _observe_sentinel(self, metrics) -> str:
        """Host-side sentinel bookkeeping after a step: feeds this step's
        (loss, grad_norm) to the sentinel and returns its action ("ok",
        "warn", "skip", "rollback"). Under skip/rollback the in-step gate
        already withheld the bad update; this is the accounting half."""
        if self._sentinel is None:
            return "ok"
        loss = metrics.get("loss")
        gn = metrics.get("grad_norm")
        return self._sentinel.observe(
            float(loss) if loss is not None else 0.0,
            float(gn) if gn is not None else 0.0,
            step=self.global_steps)

    def _step_modifiers(self):
        """Per-step forward modifiers: (pld_theta traced scalar | None,
        ltd_keep static int | None). Stored for _post_step logging."""
        theta = None
        if self.progressive_layer_drop is not None:
            theta = jnp.float32(self.progressive_layer_drop.update_state(
                self.global_steps))
        keep = None
        if self.random_ltd_scheduler is not None:
            keep = int(self.random_ltd_scheduler.get_current_seq(
                self.global_steps))
        self._last_modifiers = (theta, keep)
        return theta, keep

    def _maybe_moq_step(self):
        """MoQ precision schedule (reference engine.py:1995-2008): at a
        potential switch boundary, optionally compute per-subtree Hessian
        eigenvalues to gate the drop, then project the masters through the
        new precision's fake-quant."""
        q = self.quantizer
        if q is None:
            return
        due = (q.current_bits > q.target_bits and
               self.global_steps >= q._next_switch)
        eigs = None
        if due and self.eigenvalue is not None and \
                self._last_eig_batch is not None:
            mb, rng = self._last_eig_batch
            def loss_fn(p):
                return self._micro_loss(p, mb, rng, train=False)
            with self.mesh:
                eigs = self.eigenvalue.compute_layer_eigenvalues(
                    loss_fn, self.params, rng)
        if not q.update(self.global_steps, eigs):
            return
        key = ("moq", q.current_bits)
        if key not in self._cached_fns:
            self._cached_fns[key] = jax.jit(
                lambda p, r: q.quantize(p, modules=self._moq_modules, rng=r),
                out_shardings=self.param_shardings, donate_argnums=0)
        with self.mesh:
            # disjoint from the per-step stream (which folds global_steps)
            moq_rng = jax.random.fold_in(self._base_rng,
                                         2**30 + self.global_steps)
            self.params = self._cached_fns[key](self.params, moq_rng)

    def _post_step(self, metrics):
        self._emit_flops_report(metrics)
        self.global_steps += 1
        self._maybe_moq_step()
        # compression scheduler (reference engine.py:1955): a technique
        # going live changes the traced program — recompile once
        sched = getattr(self.module, "compression_scheduler", None)
        if sched is not None and sched.step(self.global_steps):
            log_dist(f"compression schedule flipped at step "
                     f"{self.global_steps}; recompiling", ranks=[0])
            self._compile_fns()
        self.global_samples += self._config.train_batch_size
        # the step's first host read of its outputs: the host waits here
        # for the device
        with self.tracer.phase("train/readback"):
            overflow = bool(metrics.get("overflow", False))
        with self.tracer.phase("train/post"):
            self._post_step_books(metrics, overflow)

    def _post_step_books(self, metrics, overflow):
        sentinel_action = self._observe_sentinel(metrics)
        if sentinel_action in ("skip", "rollback") and \
                self._ledger_step_iv is not None:
            # the step's work was withheld/thrown away — its wall time is
            # sentinel badput, not productive training
            self._ledger_step_iv.reclassify("sentinel")
            self._ledger_step_iv = None
        if sentinel_action == "rollback":
            # restore the last checkpoint and stop accounting this step —
            # counters/lr below would mutate the just-restored state
            self._sentinel_rollback()
            return
        if overflow or sentinel_action == "skip":
            self.skipped_steps += 1
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.monitor is not None and self.monitor.enabled:
            events = [("Train/Samples/lr", self.get_lr()[0], self.global_samples)]
            if "loss" in metrics:
                events.append(("Train/Samples/train_loss",
                               float(metrics["loss"]), self.global_samples))
            if self._config.fp16.enabled:
                events.append(("Train/Samples/loss_scale",
                               float(metrics["loss_scale"]), self.global_samples))
            theta, keep = self._last_modifiers
            if theta is not None:
                events.append(("Train/Samples/pld_theta", float(theta),
                               self.global_samples))
            if keep is not None:
                events.append(("Train/Samples/random_ltd_effective_seq",
                               keep, self.global_samples))
            if self.quantizer is not None:
                events.append(("Train/Samples/moq_bits",
                               self.quantizer.current_bits,
                               self.global_samples))
            # one gauge space: every monitor event is mirrored into the
            # telemetry counters (snapshot/Prometheus see it), while the
            # event batch itself stays per-engine — same split serving
            # metrics use, so co-resident engines can't steal each other's
            # events
            events = [(tag, float(value), samples)
                      for tag, value, samples in events]
            for tag, value, samples in events:
                self.tracer.set_counter(tag, value, samples, owner=self)
            events.extend(self._telemetry_events)
            self._telemetry_events.clear()
            self.monitor.write_events(events)
        if (self._config.steps_per_print and
                self.global_steps % self._config.steps_per_print == 0):
            loss_txt = (f"loss={float(metrics['loss']):.4f} "
                        if "loss" in metrics else "")
            log_dist(f"step={self.global_steps} {loss_txt}"
                     f"lr={self.get_lr()[0]:.3e} "
                     f"skipped={self.skipped_steps}", ranks=[0])
        if self._config.wall_clock_breakdown and \
                self._config.steps_per_print and \
                self.global_steps % self._config.steps_per_print == 0:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER])
        if self._config.memory_breakdown and \
                self._config.steps_per_print and \
                self.global_steps % self._config.steps_per_print == 0:
            self._log_memory_breakdown()
        cpcfg = self._config.compile_plane
        if self._hbm is not None and \
                self.global_steps % cpcfg.hbm_interval_steps == 0:
            self._update_hbm()
        if self._overlap is not None:
            self._overlap.maybe_update(self.global_steps)
        tcfg = self._config.telemetry
        if tcfg.enabled and tcfg.export_interval and \
                self.global_steps % tcfg.export_interval == 0:
            self._export_telemetry()
        rcfg = self._resilience
        if rcfg.autosave_interval and \
                self.global_steps % rcfg.autosave_interval == 0:
            # periodic auto-checkpoint cadence (preemption insurance):
            # bounds steps-lost to autosave_interval
            with self.tracer.span("autosave", cat="resilience",
                                  args={"step": self.global_steps}):
                self.save_checkpoint(rcfg.autosave_dir)

    def _log_memory_breakdown(self):
        """memory_breakdown (reference see_memory_usage): per-device HBM
        in-use/peak from the runtime allocator; the CPU test backend
        reports no stats."""
        stats = jax.local_devices()[0].memory_stats() or {}
        if stats:
            log_dist(
                f"memory: in_use="
                f"{stats.get('bytes_in_use', 0) / 2**30:.2f}GiB "
                f"peak={stats.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB "
                f"limit={stats.get('bytes_limit', 0) / 2**30:.2f}GiB",
                ranks=[0])
        else:
            log_dist("memory: no allocator stats on this backend",
                     ranks=[0])

    # ------------------------------------------------------------------
    # introspection / properties (reference engine property surface)
    # ------------------------------------------------------------------
    def close(self, release_ledger: bool = False):
        """Release this engine's observability footprint: stop the statusz
        server (port + thread), close the monitor sinks, and retract this
        engine's gauges from the shared telemetry counter space — with two
        co-resident engines, prometheus_dump()//metrics must not keep
        reporting a closed engine's last step time as live. Idempotent;
        params/optimizer state are untouched (a closed engine can still
        train, it just stops being observable).

        ``release_ledger=True`` additionally disables the process-global
        goodput ledger and retracts its ``goodput/*`` gauge mirror — the
        trial-scoped lifecycle (autotuning/measure.py): back-to-back trial
        engines each re-enable the ledger from a fresh epoch, and a
        finished trial's bucket totals must not read as live between
        trials."""
        if self._closed:
            return
        self._closed = True
        if self.statusz is not None:
            self.statusz.close()
        if self.monitor is not None:
            self.monitor.close()
        if self._recorder is not None:
            self._recorder.close()
        self.tracer.release_counters(self)
        self.tracer.unwatch_gc(self)
        if release_ledger:
            from ..telemetry.goodput import configure_ledger
            configure_ledger(enabled=False)
        # last: where a jax.profiler trace was taken, what each instruction
        # of the step is for outlives the engine, with the tracer
        self.tracer.keep_tables()

    def scope_tables(self):
        """``{module name: {program key: {instruction name: scope}}}``: what
        each instruction of the compiled train step is for, by pass
        (``forward``, ``remat``, ``backward``) and by the ``named_scope``
        words of ``telemetry.hlo_cost.SCOPES`` — the join of a
        ``jax.profiler`` device trace to the program's own names
        (docs/observability.md, "Device time by scope"). ``Tracer
        .scope_tables`` by hand: builds the tables that are not built yet
        (one cached compile each) and returns every one the tracer holds."""
        return self.tracer.scope_tables()

    def _health_check(self):
        """Training liveness: unhealthy once a preemption signal latched
        (the engine is about to checkpoint and raise)."""
        if self.preempted:
            return False, "preempted"
        return True, f"training (step {self.global_steps})"

    def _statusz_section(self) -> dict:
        import hashlib
        cfg_bytes = json.dumps(self._config._param_dict, sort_keys=True,
                               default=str).encode()

        def gauge(tag):
            val = self.tracer.counter_value(tag)
            return round(val, 4) if val is not None else None

        out = {
            "config_fingerprint": hashlib.sha256(cfg_bytes).hexdigest()[:12],
            "global_steps": self.global_steps,
            "skipped_steps": self.skipped_steps,
            "global_samples": self.global_samples,
            "lr": self.get_lr()[0],
            "recompiles": self._watchdog.recompiles,
            "zero_stage": self.zero_stage,
            "mesh": f"pp{self.mesh_manager.pp}/dp{self.mesh_manager.dp}/"
                    f"ep{self.mesh_manager.ep}/sp{self.mesh_manager.sp}/"
                    f"tp{self.mesh_manager.tp}",
        }
        if self._sched_info is not None:
            out["overlap_schedule"] = self._sched_info
        for tag in ("telemetry/step_time_ms", "telemetry/mfu",
                    "telemetry/step_tflops", "telemetry/peak_hbm_gib"):
            val = gauge(tag)
            if val is not None:
                out[tag.split("/", 1)[1]] = val
        if self._ckpt_history:
            out["checkpoint_history"] = "; ".join(
                f"{e['kind']}@step{e['step']}:{e.get('tag', e.get('dir'))}"
                for e in list(self._ckpt_history)[-8:])
        if self._sentinel is not None:
            out["sentinel_bad_steps"] = self._sentinel.bad_steps
            out["sentinel_rollbacks"] = self._sentinel.rollbacks
        return out

    def _dump_state(self) -> str:
        """dump_state (reference engine dump): a one-shot engine summary
        for debugging config resolution."""
        cfg = self._config
        lines = ["engine state dump:"]
        for k in ("train_batch_size", "train_micro_batch_size_per_gpu",
                  "gradient_accumulation_steps", "gradient_clipping",
                  "steps_per_print"):
            lines.append(f"  {k} = {getattr(cfg, k)}")
        lines.append(f"  zero_stage = {self.zero_stage}")
        lines.append(f"  compute_dtype = {self._compute_dtype or 'float32'}")
        lines.append(f"  grad_accumulation_dtype = {self._grad_acc_dtype}")
        lines.append(f"  mesh = pp{self.mesh_manager.pp}/"
                     f"dp{self.mesh_manager.dp}/ep{self.mesh_manager.ep}/"
                     f"sp{self.mesh_manager.sp}/tp{self.mesh_manager.tp}")
        lines.append(f"  optimizer = "
                     f"{self.optimizer.name if self.optimizer else None} "
                     f"offload={'on' if self._offload else 'off'}")
        return "\n".join(lines)

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        return [self._base_lr]

    def get_global_grad_norm(self):
        return getattr(self, "_last_grad_norm", None)

    @property
    def cur_scale(self):
        return float(self.scaler_state.scale)

    @property
    def loss_scale(self):
        return self.cur_scale

    @property
    def dp_world_size(self):
        return self.mesh_manager.dp_world_size

    @property
    def mp_world_size(self):
        return self.mesh_manager.tp

    @property
    def train_batch_size(self):
        return self._config.train_batch_size

    @property
    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization(self):
        return self.zero_stage > 0

    def zero_optimization_stage(self):
        return self.zero_stage

    def fp16_enabled(self):
        return self._config.fp16.enabled

    def bfloat16_enabled(self):
        return self._config.bf16.enabled

    # ------------------------------------------------------------------
    # checkpointing — implemented in runtime/checkpointing.py, bound here
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, exclude_frozen_parameters=False):
        self._drain_offload_pipeline()
        from .checkpointing import save_checkpoint
        return save_checkpoint(self, save_dir, tag=tag,
                               client_state=client_state,
                               save_latest=save_latest)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        self._offload_pending = None  # in-flight delayed grads are stale
        from .checkpointing import load_checkpoint
        out = load_checkpoint(self, load_dir, tag=tag,
                              load_optimizer_states=load_optimizer_states,
                              load_lr_scheduler_states=load_lr_scheduler_states,
                              load_module_only=load_module_only)
        # resume the curriculum data sampler at the restored step (a fresh
        # sampler would restart the difficulty ramp AND replay the seeded
        # batch stream from step 0)
        sampler = getattr(self.training_dataloader, "data_sampler", None) \
            if self.training_dataloader is not None else None
        if sampler is not None and hasattr(sampler, "set_step"):
            sampler.set_step(self.global_steps)
        return out

    def get_fp32_params(self):
        """Gathered, fully-replicated fp32 params (the zero_to_fp32 path,
        utils/zero_to_fp32.py, as a live call). Under ZeRO-Offload the fp32
        masters live on the host — return those (device params are bf16)."""
        if self._offload is not None:
            self._drain_offload_pipeline()
            return self._offload.masters_tree()
        rep = jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                           self.param_shardings)
        with self.mesh:
            return jax.jit(lambda p: p, out_shardings=rep)(self.params)
