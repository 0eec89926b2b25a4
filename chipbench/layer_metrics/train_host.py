"""Train engine: step time and model-FLOP utilisation, by the host's clock."""

import statistics

from chipbench import counts


def step_ms(ctx, record, trace):
    """Median time between the completions of successive steps."""
    stamps = record.get("step_stamps") or []
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return statistics.median(gaps) * 1e3 if gaps else None


def mfu(ctx, record, trace):
    """Required FLOP/token (``counts.train_flops_per_token``: matmul
    parameters with the unpadded head, causal attention, no recompute) x
    tokens/s of this run / (chips x the published bf16 peak)."""
    if "train_tokens_per_s" not in record:
        return None
    per_token = counts.train_flops_per_token(ctx.dims, ctx.traffic["seq"])
    peak = len(ctx.devices) * ctx.peak["bf16_flops_per_s"]
    return 100.0 * per_token * record["train_tokens_per_s"] / peak


METRICS = {"step_ms": step_ms, "mfu": mfu}
