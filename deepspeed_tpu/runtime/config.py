"""DeepSpeedConfig — the cross-cutting config spine.

TPU-native re-design of the reference config system
(deepspeed/runtime/config.py:674 ``DeepSpeedConfig``): one JSON dict (or path)
parsed into typed per-subsystem models; the batch-size triangle
``train_batch_size = micro_batch_per_device × gradient_accumulation_steps ×
dp_world_size`` is auto-solved and validated exactly like the reference
(config.py:872-980).

Additions over the reference key set (TPU-first parallelism is config-driven
rather than delegated to a user mpu): ``tensor_parallel_size``,
``pipeline_parallel_size``, ``sequence_parallel_size``,
``expert_parallel_size`` select the device-mesh axis sizes; ``telemetry``
enables structured step/comm/serving tracing (``TelemetryConfig``) and
``prometheus`` adds the Prometheus-text monitor sink (docs/observability.md).
"""

import dataclasses
import json
import os
from typing import Any, Dict, Optional

from . import constants as C
from .config_utils import DeepSpeedConfigModel, ConfigError
from .zero.config import DeepSpeedZeroConfig
from ..utils.logging import logger


@dataclasses.dataclass
class FP16Config(DeepSpeedConfigModel):
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0
    fp16_master_weights_and_grads: bool = False

    @property
    def dynamic_loss_scale(self):
        return self.loss_scale == 0


@dataclasses.dataclass
class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    immediate_grad_update: bool = False


@dataclasses.dataclass
class OptimizerConfig(DeepSpeedConfigModel):
    type: str = "adamw"
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    legacy_fusion: bool = False

    def validate(self):
        self.type = self.type.lower()


@dataclasses.dataclass
class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference: runtime/activation_checkpointing/checkpointing.py:789 configure()."""
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


@dataclasses.dataclass
class CommsLoggerConfig(DeepSpeedConfigModel):
    """Reference: utils/comms_logging.py CommsLogger config."""
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class MonitorSinkConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"
    # tensorboard/wandb extras
    team: Optional[str] = None
    group: Optional[str] = None
    project: Optional[str] = None
    _ALLOW_EXTRA = True


@dataclasses.dataclass
class TelemetryConfig(DeepSpeedConfigModel):
    """The ``"telemetry"`` config block (deepspeed_tpu/telemetry/).

    Keys:

    - ``enabled``: turn on structured span tracing (off = zero-cost; the
      tracer hands out a shared no-op span, no allocation).
    - ``buffer_size``: span ring-buffer capacity; old spans are
      overwritten, never grown (low-overhead by construction).
    - ``sync_spans``: block on step outputs at span exit so durations are
      honest under XLA async dispatch (off = dispatch-only timings).
    - ``mfu``: derive model-FLOPs-utilization from the flops profiler's
      analytic step FLOPs (one extra trace of the step fn, once).
    - ``peak_tflops_per_device``: hardware peak for the MFU denominator;
      0 disables the MFU counter unless set.
    - ``trace_output`` / ``snapshot_output``: file paths for the Chrome
      trace-event JSON (Perfetto-loadable) and the metrics snapshot JSON.
    - ``export_interval``: write those files every N global steps
      (0 = only on demand via telemetry.export helpers).

    The Prometheus text dump is configured separately as a monitor sink —
    the top-level ``"prometheus"`` block (same shape as ``csv_monitor``).
    See docs/observability.md.
    """
    enabled: bool = False
    buffer_size: int = 65536
    sync_spans: bool = True
    mfu: bool = True
    peak_tflops_per_device: float = 0.0
    trace_output: Optional[str] = None
    snapshot_output: Optional[str] = None
    export_interval: int = 0
    #: goodput ledger (telemetry/goodput.py): wall-clock bucket accounting
    #: alongside the tracer; rides telemetry.enabled, opt out with false
    goodput: bool = True

    def validate(self):
        if self.buffer_size < 16:
            raise ConfigError("telemetry.buffer_size must be >= 16")
        if self.export_interval < 0:
            raise ConfigError("telemetry.export_interval must be >= 0")


@dataclasses.dataclass
class StatuszConfig(DeepSpeedConfigModel):
    """The ``"statusz"`` config block (telemetry/statusz.py): an opt-in
    live introspection HTTP server — ``/healthz`` (liveness, tied to
    drain/preemption state), ``/metrics`` (live Prometheus text),
    ``/statusz`` (human-readable status page, ``?format=json`` for
    machines), ``/trace?last_ms=N`` (Chrome trace slice). Disabled by
    default: no thread, no port. ``port: 0`` binds an ephemeral port
    (read it back from ``engine.statusz.port``)."""
    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0
    #: how many recent spans the /statusz page shows
    spans: int = 50

    def validate(self):
        if not (0 <= int(self.port) <= 65535):
            raise ConfigError("statusz.port must be in [0, 65535]")
        if self.spans < 1:
            raise ConfigError("statusz.spans must be >= 1")


@dataclasses.dataclass
class FlightRecorderConfig(DeepSpeedConfigModel):
    """The ``"flight_recorder"`` config block
    (telemetry/flight_recorder.py): an always-on bounded ring of recent
    step records plus anomaly-triggered postmortem bundles on disk.
    Disabled (the default) allocates nothing — no object, no directory,
    no thread.

    Trigger rules: step time over ``slow_step_factor`` × EMA (armed
    after ``warmup_steps`` baseline steps; ``slow_step_ms`` adds an
    absolute ceiling), recompile-watchdog events, sentinel NaN/grad-spike
    events, serving SLO burn rate over ``slo_burn_threshold``,
    preemption latch, hostagg straggler edges, and explicit
    ``/debug/capture`` requests. Bundles are keep-last-``keep`` with
    atomic writes and per-kind ``debounce_s`` so a pathological run
    cannot fill the disk or capture in a loop."""
    enabled: bool = False
    #: bundle output directory (created lazily at the first trigger)
    dir: str = "flight_bundles"
    #: step records kept in memory (each bundle embeds the full ring)
    ring: int = 256
    #: on-disk bundles kept (oldest deleted first)
    keep: int = 8
    #: min seconds between bundles of the SAME trigger kind
    debounce_s: float = 30.0
    slow_step_factor: float = 3.0
    #: absolute slow-step ceiling in ms; 0 disables the absolute rule
    slow_step_ms: float = 0.0
    warmup_steps: int = 5
    ema_alpha: float = 0.2
    #: trace-slice window embedded in each bundle, ms
    trace_ms: float = 10_000.0
    #: serving: SLO error-budget burn rate that triggers a capture
    slo_burn_threshold: float = 2.0

    def validate(self):
        if self.ring < 8:
            raise ConfigError("flight_recorder.ring must be >= 8")
        if self.keep < 1:
            raise ConfigError("flight_recorder.keep must be >= 1")
        if self.debounce_s < 0:
            raise ConfigError("flight_recorder.debounce_s must be >= 0")
        if self.slow_step_factor <= 1.0:
            raise ConfigError(
                "flight_recorder.slow_step_factor must be > 1")
        if not (0.0 < self.ema_alpha <= 1.0):
            raise ConfigError(
                "flight_recorder.ema_alpha must be in (0, 1]")
        if self.trace_ms <= 0:
            raise ConfigError("flight_recorder.trace_ms must be > 0")
        if self.warmup_steps < 1:
            raise ConfigError("flight_recorder.warmup_steps must be >= 1")


@dataclasses.dataclass
class HostAggConfig(DeepSpeedConfigModel):
    """The ``"hostagg"`` config block (telemetry/hostagg.py): cross-host
    straggler attribution. Every ``interval`` steps each host contributes
    a tiny metrics vector (step time, data-wait, heartbeat seqno) to a
    low-frequency all-gather; the aggregate exports ``dstpu_host_*``
    gauges, flags the slowest host as a straggler when max/median exceeds
    ``straggler_factor`` (a flight-recorder trigger), and reports a host
    whose seqno stalls for ``heartbeat_misses`` aggregations as a missing
    heartbeat (flips /healthz)."""
    enabled: bool = False
    interval: int = 10
    straggler_factor: float = 1.5
    heartbeat_misses: int = 3

    def validate(self):
        if self.interval < 1:
            raise ConfigError("hostagg.interval must be >= 1")
        if self.straggler_factor <= 1.0:
            raise ConfigError("hostagg.straggler_factor must be > 1")
        if self.heartbeat_misses < 1:
            raise ConfigError("hostagg.heartbeat_misses must be >= 1")


@dataclasses.dataclass
class CompilePlaneConfig(DeepSpeedConfigModel):
    """The ``"compile_plane"`` config block (telemetry/compileplane.py +
    telemetry/overlap.py): compile ledger with recompile diffs, HBM
    role ledger, and the collective-overlap analyzer. Disabled (the
    default) allocates nothing — no ledger objects, no per-call
    fingerprints, no gauges.

    - ``history``: compile events kept in memory (each carries the arg
      fingerprint, recompile diff, and cost/memory summaries).
    - ``memory_analysis``: AOT-compile each new executable once to
      capture ``memory_analysis()`` (per-device arg/output/temp bytes),
      the isolated compile wall time, and the optimized HLO's
      collective/async-overlap summary. Costs one extra XLA compile per
      compile *event* (steady state pays nothing); turn off on very
      large models where doubling each compile event is unacceptable.
    - ``hbm`` / ``hbm_interval_steps``: the HBM role ledger
      (``dstpu_mem_*`` gauges + Perfetto waterline) and its update
      cadence.
    - ``overlap`` / ``overlap_interval_steps`` / ``overlap_window_ms``:
      the trace-ring overlap gauge and its cadence/window.
    - ``overlap_floor``: minimum acceptable HLO-static overlap fraction
      per compiled step program. When a RECOMPILE produces a program
      whose static fraction falls below the floor, the flight recorder
      fires an ``overlap_drop`` bundle (a recompile that silently
      de-overlaps the schedule is a goodput regression the MFU gauge
      only shows as "slower"). 0 disables the check."""
    enabled: bool = False
    history: int = 32
    memory_analysis: bool = True
    hbm: bool = True
    hbm_interval_steps: int = 8
    overlap: bool = True
    overlap_interval_steps: int = 16
    overlap_window_ms: float = 30_000.0
    overlap_floor: float = 0.0

    def validate(self):
        if not 0.0 <= self.overlap_floor <= 1.0:
            raise ConfigError(
                "compile_plane.overlap_floor must be in [0, 1]")
        if self.history < 1:
            raise ConfigError("compile_plane.history must be >= 1")
        if self.hbm_interval_steps < 1:
            raise ConfigError(
                "compile_plane.hbm_interval_steps must be >= 1")
        if self.overlap_interval_steps < 1:
            raise ConfigError(
                "compile_plane.overlap_interval_steps must be >= 1")
        if self.overlap_window_ms <= 0:
            raise ConfigError(
                "compile_plane.overlap_window_ms must be > 0")


@dataclasses.dataclass
class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None
    recompute_fwd_factor: float = 0.0


@dataclasses.dataclass
class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: Dict[str, Any] = dataclasses.field(default_factory=dict)
    async_save: bool = False

    def validate(self):
        if str(self.tag_validation).lower() not in ("ignore", "warn", "fail"):
            raise ConfigError(f"checkpoint.tag_validation must be Ignore|Warn|Fail")


class DeepSpeedConfig:
    """Parse + validate the full config. Reference: runtime/config.py:674."""

    def __init__(self, config: Any, mpu=None, mesh_shape: Optional[Dict[str, int]] = None,
                 world_size: Optional[int] = None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise ConfigError(f"Config file not found: {config}")
            with open(config) as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        elif config is None:
            self._param_dict = {}
        else:
            raise ConfigError(
                f"Expected a dict or json path for config, got {type(config)}")

        pd = self._param_dict
        self.mpu = mpu

        # ---- parallel sizes (TPU mesh axes) ----
        self.tensor_parallel_size = int(pd.get(C.TENSOR_PARALLEL_SIZE, 1))
        self.pipeline_parallel_size = int(pd.get(C.PIPELINE_PARALLEL_SIZE, 1))
        self.sequence_parallel_size = int(pd.get(C.SEQUENCE_PARALLEL_SIZE, 1))
        self.expert_parallel_size = int(pd.get(C.EXPERT_PARALLEL_SIZE, 1))

        if world_size is None:
            try:
                import jax
                world_size = jax.device_count()
            except Exception:
                world_size = 1
        self.world_size = world_size
        model_parallel = (self.tensor_parallel_size * self.pipeline_parallel_size *
                          self.sequence_parallel_size)
        if world_size % model_parallel != 0:
            raise ConfigError(
                f"world size {world_size} not divisible by tp*pp*sp={model_parallel}")
        self.data_parallel_size = world_size // model_parallel

        # ---- batch triangle ----
        self.train_batch_size = pd.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = pd.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = pd.get(C.GRADIENT_ACCUMULATION_STEPS)
        self._configure_train_batch_size()

        # ---- subsystem models ----
        self.optimizer = (OptimizerConfig.from_dict(pd[C.OPTIMIZER])
                          if C.OPTIMIZER in pd else None)
        self.scheduler = (SchedulerConfig.from_dict(pd[C.SCHEDULER])
                          if C.SCHEDULER in pd else None)
        self.fp16 = FP16Config.from_dict(pd.get(C.FP16, {}))
        bf16_dict = pd.get(C.BFLOAT16, pd.get(C.BFLOAT16_OLD, {}))
        self.bf16 = BF16Config.from_dict(bf16_dict)
        if self.fp16.enabled and self.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        self.zero_config = DeepSpeedZeroConfig.from_dict(pd.get(C.ZERO_OPTIMIZATION, {}))
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(
            pd.get(C.ACTIVATION_CHECKPOINTING, {}))
        self.comms_logger = CommsLoggerConfig.from_dict(pd.get(C.COMMS_LOGGER, {}))
        # quantized/hierarchical collective policy (deepspeed_tpu/comm/
        # compression.py, docs/comm.md): per-collective off|fp32|int8|
        # fp8_block wire formats behind the comm dispatch
        from ..comm.compression import CommCompressionConfig
        self.comm_compression = CommCompressionConfig.from_dict(
            pd.get(C.COMM_COMPRESSION, {}))
        # bucketed compute-communication overlap for the ZeRO exchanges
        # (runtime/zero/overlap_schedule.py, docs/comm.md): size-targeted
        # layer-order buckets moved through coalesced collectives, issued
        # ahead of their consuming layers
        from .zero.overlap_schedule import OverlapScheduleConfig
        self.overlap_schedule = OverlapScheduleConfig.from_dict(
            pd.get(C.OVERLAP_SCHEDULE, {}))
        self.tensorboard = MonitorSinkConfig.from_dict(pd.get(C.TENSORBOARD, {}))
        self.wandb = MonitorSinkConfig.from_dict(pd.get(C.WANDB, {}))
        self.csv_monitor = MonitorSinkConfig.from_dict(pd.get(C.CSV_MONITOR, {}))
        self.prometheus = MonitorSinkConfig.from_dict(pd.get(C.PROMETHEUS, {}))
        self.telemetry = TelemetryConfig.from_dict(pd.get(C.TELEMETRY, {}))
        self.statusz = StatuszConfig.from_dict(pd.get(C.STATUSZ, {}))
        self.flight_recorder = FlightRecorderConfig.from_dict(
            pd.get(C.FLIGHT_RECORDER, {}))
        self.hostagg = HostAggConfig.from_dict(pd.get(C.HOSTAGG, {}))
        self.compile_plane = CompilePlaneConfig.from_dict(
            pd.get(C.COMPILE_PLANE, {}))
        self.flops_profiler = FlopsProfilerConfig.from_dict(pd.get(C.FLOPS_PROFILER, {}))
        self.checkpoint_config = CheckpointConfig.from_dict(pd.get(C.CHECKPOINT, {}))
        # fault tolerance: checkpoint integrity/fallback, preemption
        # handling, the training sentinel (deepspeed_tpu/resilience/)
        from ..resilience.config import ResilienceConfig
        self.resilience = ResilienceConfig.from_dict(pd.get(C.RESILIENCE, {}))

        # ---- scalars ----
        self.steps_per_print = pd.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.gradient_clipping = float(pd.get(C.GRADIENT_CLIPPING,
                                              C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients = pd.get(C.PRESCALE_GRADIENTS,
                                         C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = float(
            pd.get(C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT))
        self.sparse_gradients_enabled = pd.get(C.SPARSE_GRADIENTS,
                                               C.SPARSE_GRADIENTS_DEFAULT)
        self.communication_data_type = pd.get(C.COMMUNICATION_DATA_TYPE, None)
        self.gradient_accumulation_dtype = pd.get(C.GRADIENT_ACCUMULATION_DTYPE, None)
        if self.gradient_accumulation_dtype is not None and \
                str(self.gradient_accumulation_dtype) not in (
                    "fp32", "float32", "bf16", "bfloat16"):
            raise ConfigError(
                f"gradient_accumulation_dtype must be fp32|bf16, got "
                f"{self.gradient_accumulation_dtype}")
        self.wall_clock_breakdown = pd.get(C.WALL_CLOCK_BREAKDOWN,
                                           C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = pd.get(C.MEMORY_BREAKDOWN, False)
        self.dump_state = pd.get(C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.zero_allow_untested_optimizer = pd.get(C.ZERO_ALLOW_UNTESTED_OPTIMIZER, False)
        self.dataloader_drop_last = pd.get(C.DATALOADER_DROP_LAST,
                                           C.DATALOADER_DROP_LAST_DEFAULT)
        self.load_universal_checkpoint = pd.get(C.LOAD_UNIVERSAL_CHECKPOINT, False)
        self.disable_allgather = pd.get(C.DISABLE_ALLGATHER, False)
        self.seed = pd.get("seed", 42)
        self.elasticity = pd.get(C.ELASTICITY, {})
        self.autotuning = pd.get(C.AUTOTUNING, {})
        # measured-trials sweep parameters (autotuning/measure.py): the
        # engine carries the block; `ds_tpu_tune --measure` consumes it
        self.autotune = pd.get(C.AUTOTUNE, {})
        self.compression = pd.get(C.COMPRESSION_TRAINING, {})
        self.data_efficiency = pd.get(C.DATA_EFFICIENCY, {})
        self.curriculum_learning_legacy = pd.get(C.CURRICULUM_LEARNING_LEGACY, {})
        self.progressive_layer_drop = pd.get(C.PROGRESSIVE_LAYER_DROP, {})
        self.pipeline = pd.get(C.PIPELINE, {})
        self.monitor_config_enabled = (self.tensorboard.enabled or self.wandb.enabled
                                       or self.csv_monitor.enabled
                                       or self.prometheus.enabled)

        self._do_sanity_check()

    # -- batch triangle solver; mirrors reference semantics (config.py:872-980)
    def _configure_train_batch_size(self):
        train = self.train_batch_size
        micro = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        dp = self.data_parallel_size

        if all(v is not None for v in (train, micro, gas)):
            if train != micro * gas * dp:
                raise ConfigError(
                    f"Check batch related parameters. train_batch_size is not equal to "
                    f"micro_batch_per_gpu * gradient_acc_step * world_size "
                    f"{train} != {micro} * {gas} * {dp}")
        elif train is not None and micro is not None:
            gas = train // (micro * dp)
            if train % (micro * dp) != 0:
                raise ConfigError(
                    f"train_batch_size {train} not divisible by micro_batch*dp {micro * dp}")
        elif train is not None and gas is not None:
            micro = train // (gas * dp)
            if train % (gas * dp) != 0:
                raise ConfigError(
                    f"train_batch_size {train} not divisible by gas*dp {gas * dp}")
        elif micro is not None and gas is not None:
            train = micro * gas * dp
        elif train is not None:
            gas = 1
            micro = train // dp
            if train % dp != 0:
                raise ConfigError(f"train_batch_size {train} not divisible by dp {dp}")
        elif micro is not None:
            gas = 1
            train = micro * dp
        else:
            raise ConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu needs to be provided")

        if train <= 0 or micro <= 0 or gas <= 0:
            raise ConfigError(
                f"batch sizes must be positive: train={train} micro={micro} gas={gas}")
        self.train_batch_size = int(train)
        self.train_micro_batch_size_per_gpu = int(micro)
        self.gradient_accumulation_steps = int(gas)

    def _do_sanity_check(self):
        if self.zero_config.stage >= 2 and self.pipeline_parallel_size > 1:
            raise ConfigError(
                "ZeRO stage >= 2 is incompatible with pipeline parallelism "
                "(reference: engine.py:1414-1417)")

    # -- convenience mirrors of reference engine properties
    @property
    def zero_enabled(self):
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self):
        return self.zero_config.stage

    def print_config(self):
        logger.info(f"DeepSpeedConfig: {json.dumps(self._param_dict, indent=2, default=str)}")
