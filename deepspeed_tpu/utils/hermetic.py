"""Plain CPU bootstrap for entry points that must not touch a chip.

Tests, the virtual-mesh benchmarks and the report CLIs run on the XLA CPU
backend. :func:`force_cpu` sets the three things that takes
(``JAX_PLATFORMS=cpu``, ``DSTPU_ACCELERATOR=cpu`` and, on request, the
virtual device count) and must run before the first jax backend
initialization — importing jax or this package first is fine.
"""

import os
import re


def force_cpu(device_count=None):
    """Pin this process (and its children) to the XLA CPU backend.

    device_count: if given, ensure XLA_FLAGS carries
    ``--xla_force_host_platform_device_count=<n>`` for the virtual mesh —
    a count already present in XLA_FLAGS wins (so
    ``XLA_FLAGS=...device_count=16 pytest ...`` reproduces a 16-device
    mesh in-process). Returns the jax module.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")
    if device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if not re.search(r"--xla_force_host_platform_device_count=\d+", flags):
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={device_count}"
            ).strip()

    import jax
    # the env var is read when jax is imported; cover the case where it
    # already was
    jax.config.update("jax_platforms", "cpu")
    return jax
