"""TPU accelerator (the analogue of accelerator/cuda_accelerator.py)."""

import jax
import jax.numpy as jnp

from .abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"
        self._seed = 42

    def _devices(self):
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                f"TPU accelerator selected but jax sees no TPU device "
                f"(devices: {jax.devices()})")
        return devs

    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index=None):
        devs = self._devices()
        return devs[device_index or 0]

    def device_count(self):
        return len(self._devices())

    def current_device(self):
        return self._devices()[0]

    def synchronize(self, device_index=None):
        # XLA async dispatch: block until all queued work is done.
        jax.block_until_ready(jax.device_put(0, self.device(device_index)))

    def manual_seed(self, seed):
        self._seed = seed

    def rng_key(self):
        return jax.random.PRNGKey(self._seed)

    def memory_stats(self, device_index=None):
        return dict(self.device(device_index).memory_stats() or {})

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    def default_dtype(self):
        return jnp.bfloat16

    def communication_backend_name(self):
        return self._communication_backend_name

    def range_push(self, msg):
        self._trace = jax.profiler.TraceAnnotation(msg)
        self._trace.__enter__()

    def range_pop(self):
        if getattr(self, "_trace", None) is not None:
            self._trace.__exit__(None, None, None)
            self._trace = None

    def create_op_builder(self, class_name):
        builder_cls = self.get_op_builder(class_name)
        return builder_cls() if builder_cls else None

    def get_op_builder(self, class_name):
        from ..ops.op_builder import get_builder_class
        return get_builder_class(class_name, backend="tpu")

    def on_accelerator(self, tensor):
        if not hasattr(tensor, "devices"):      # numpy / python scalars
            return False
        return any(d.platform != "cpu" for d in tensor.devices())
