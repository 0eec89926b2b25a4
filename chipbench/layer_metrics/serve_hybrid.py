"""The stack AROUND the expert matmuls of a hybrid serving cell (layers of
several kinds: short-convolution operators on a pooled recurrent state, a few
attention layers over their KV, a dense layer, the head and the sampler),
which ``moe_decode_hbm_share`` and ``moe_ffn_share`` together cannot tell
apart from the experts: the bytes a decode tick requires of it, from the
counts the configuration names (``ctx.counts.non_expert_decode_bytes``), over
the device time the decode program spends outside the grouped-matmul
operations (the ``pattern`` of the cell's ``moe_kernels``). It returns
``None`` without a trace, in a cell without ``moe_kernels``, and where the
configuration's counts have no such name (every other architecture)."""

import bisect
import statistics

from chipbench.layer_metrics.serve_moe import _kernel_ops, _module_seconds


def mixer_decode_hbm_share(ctx, record, trace):
    """(weights outside the experts + the live tokens' keys and values of
    the attention layers + the active slots' recurrent state read and
    written; means over the window's decode ticks) / HBM bandwidth, over
    the mean device time of one decode program LESS the operations matching
    ``moe_kernels.pattern`` inside it."""
    need_bytes = getattr(getattr(ctx, "counts", None),
                         "non_expert_decode_bytes", None)
    cell = ctx.cell
    if trace is None or need_bytes is None or "moe_kernels" not in cell \
            or "decode" not in cell.get("modules", {}) \
            or not record.get("live_tokens") or not record.get("occupancy"):
        return None
    runs, secs = _module_seconds(ctx, trace, "decode")
    if not runs:
        return None
    runs = sorted(runs)         # disjoint: one program at a time
    starts = [lo for lo, _ in runs]
    inside = 0.0
    for s, e in _kernel_ops(ctx, trace):
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and e <= runs[k][1]:
            inside += e - s
    around = (secs - inside) / len(runs)
    if around <= 0:
        return None
    live = statistics.fmean(record["live_tokens"])
    active = statistics.fmean(record["occupancy"]) * \
        cell["serving"]["num_slots"]
    need = need_bytes(ctx.dims, live, active, 2, record["vocab_rows"])
    ctx.log(f"decode program outside the expert matmuls {around * 1e3:.3f} "
            f"ms mean over {len(runs)} (expert matmuls "
            f"{inside / len(runs) * 1e3:.3f}); needs {need:.4e} B "
            f"({live:.0f} live tokens, {active:.1f} active slots)")
    return 100.0 * (need / ctx.peak["hbm_bytes_per_s"]) / around


METRICS = {"mixer_decode_hbm_share": mixer_decode_hbm_share}
