"""Accelerator auto-detection + singleton.

Mirrors accelerator/real_accelerator.py:37 get_accelerator() /
:55 set_accelerator(): detection order is TPU → CPU, overridable via the
DSTPU_ACCELERATOR env var or set_accelerator().
"""

import os

_ACCELERATOR = None


def _detect():
    from .tpu_accelerator import TPU_Accelerator
    from .cpu_accelerator import CPU_Accelerator
    name = os.environ.get("DSTPU_ACCELERATOR")
    if name == "cpu":
        return CPU_Accelerator()
    import jax
    # an exception from jax.devices() propagates: a backend that cannot be
    # reached is an error, not a reason to carry on on the CPU
    has_tpu = any(d.platform != "cpu" for d in jax.devices())
    if name == "tpu" and not has_tpu:
        raise RuntimeError(
            "DSTPU_ACCELERATOR=tpu but jax sees no TPU device "
            f"(devices: {jax.devices()})")
    return TPU_Accelerator() if has_tpu else CPU_Accelerator()


def get_accelerator():
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _detect()
    return _ACCELERATOR


def set_accelerator(accel):
    global _ACCELERATOR
    _ACCELERATOR = accel
    return _ACCELERATOR
