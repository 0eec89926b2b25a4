"""What ``deepspeed_tpu.utils.hermetic.force_cpu`` promises the CPU entry
points (tests/conftest.py, the ``--cpu`` CLIs)."""

import os

from deepspeed_tpu.utils.hermetic import force_cpu


def test_force_cpu_selects_cpu_platform(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("DSTPU_ACCELERATOR", raising=False)
    jax = force_cpu()
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["DSTPU_ACCELERATOR"] == "cpu"
    assert jax.config.jax_platforms == "cpu"
    assert {d.platform for d in jax.devices()} == {"cpu"}


def test_force_cpu_device_count_in_xla_flags_wins(monkeypatch):
    count = "--xla_force_host_platform_device_count="
    monkeypatch.setenv("XLA_FLAGS", f"--xla_cpu_foo=1 {count}16")
    force_cpu(device_count=8)
    assert os.environ["XLA_FLAGS"] == f"--xla_cpu_foo=1 {count}16"
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_foo=1")
    force_cpu(device_count=8)
    assert os.environ["XLA_FLAGS"] == f"--xla_cpu_foo=1 {count}8"
