"""Test harness configuration.

The TPU answer to the reference DistributedTest harness
(tests/unit/common.py:277): instead of forking N processes over NCCL, we run
single-process with N virtual CPU devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and build real
jax.sharding.Meshes over them — multi-chip semantics without hardware.
Must run before jax is imported anywhere.
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from deepspeed_tpu.utils.hermetic import force_cpu  # noqa: E402

force_cpu(device_count=8)

import pytest  # noqa: E402

#: Tests under the benchmark's ``paths`` (``BENCHMARK.json``), which only a
#: ``benchmark`` PR may edit, that pin what an accepted PR of another kind
#: had to move. Each is expected to fail, strictly (the ``benchmark`` PR
#: that repairs the pin takes its line away here), and its other assertions
#: are held, word for word, by the test named in the reason.
_PINS_A_BENCHMARK_PR_REPAIRS = {
    "chipbench/test_chipbench_xing.py::"
    "test_the_cell_is_in_the_lists_its_readers_apply_to":
        "pins xing4.0-29b-a4b as the LAST entry of configs; PR 48 appended "
        "sdar-30b-a3b behind it (a model_config PR adds a configuration at "
        "the end and may not edit this file). Everything else it asserts "
        "is held by test_chipbench_sdar.py::"
        "test_the_cell_before_this_one_keeps_its_lists",
}


def pytest_collection_modifyitems(config, items):
    """Duration-driven test tiers: nodeids measured slower than the smoke
    budget (tests/.test_durations.json, regenerated by
    tests/gen_durations.py from a --durations=0 run) get ``slow``;
    everything else gets ``smoke`` — so ``pytest -m smoke`` is a <3 min
    gate on the CPU mesh and new tests default INTO the smoke tier until
    measured."""
    import json

    path = os.path.join(os.path.dirname(__file__), ".test_durations.json")
    slow_ids = set()
    if os.path.exists(path):
        with open(path) as f:
            slow_ids = set(json.load(f)["slow"])
    for item in items:
        for pinned, reason in _PINS_A_BENCHMARK_PR_REPAIRS.items():
            if item.nodeid.endswith(pinned):
                item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
        explicit = {m.name for m in item.iter_markers()}
        if "slow" in explicit or "smoke" in explicit:
            continue
        nodeid = item.nodeid
        if nodeid.startswith("tests/"):
            nodeid = nodeid[len("tests/"):]
        if nodeid in slow_ids:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True)
def _reset_global_state():
    yield
    from deepspeed_tpu.parallel import topology
    topology.reset_mesh()
    # fault-injection and preemption state is process-global (the fault
    # points live in library code, signal handlers in the signal table);
    # reset both so no test can leak armed faults or a latched SIGTERM
    from deepspeed_tpu.resilience.faults import get_injector
    from deepspeed_tpu.resilience.preemption import PreemptionHandler
    get_injector().reset()
    PreemptionHandler.reset()


@pytest.fixture
def faultinject():
    """Deterministic fault injection (resilience/faults.py): arm named
    faults (``io_write_fail``, ``io_read_corrupt``, ``io_truncate``,
    ``nan_loss``, ``preempt_signal``) against their library fault points.
    Resets before AND after the test so injection state never leaks."""
    from deepspeed_tpu.resilience.faults import get_injector
    inj = get_injector()
    inj.reset()
    yield inj
    inj.reset()


@pytest.fixture
def mesh8():
    from deepspeed_tpu.parallel import initialize_mesh
    return initialize_mesh(dp=8)
