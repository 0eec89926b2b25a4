"""Train engine, from the program's own phase records (``train/...`` on the
tracer's phase ring) placed on the device trace's clock by
``_program_spans``: the host's share of a step, and the device's idle time
under the step's phases. Each returns ``None`` outside a training cell,
without a trace, or where the program keeps no phase records."""

import statistics

from chipbench.layer_metrics import _program_spans as P


def _loaded(ctx, trace):
    if trace is None or ctx.cell["job"] != "train":
        return None
    return P.load(ctx, trace, "train_batch", "train/step")


def step_host_ms(ctx, record, trace):
    """Median over the window's steps of ``train/step`` less its
    ``train/readback``, the wait for the device: what the host itself takes
    of a step."""
    got = _loaded(ctx, trace)
    if got is None:
        return None
    placed, _ = got
    return statistics.median(
        u[1] - u[0] - P.seconds_of(placed.inside(u), ("train/readback",))
        for u in placed.units) * 1e3


def idle_step_ms(ctx, record, trace):
    """Device-idle ms per step under the ``train/*`` phases (and a ``gc``
    inside one)."""
    got = _loaded(ctx, trace)
    if got is None:
        return None
    placed, idle = got
    secs = sum(v for k, v in idle.items() if k != "outside")
    return secs * 1e3 / len(placed.units)


METRICS = {"step_host_ms": step_host_ms, "idle_step_ms": idle_step_ms}
