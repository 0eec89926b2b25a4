"""The routed expert layer of a serving cell: what the program's routing
counters say (the ``serve/moe_decode`` and ``serve/moe_prefill`` phase
records: experts touched and the largest count any expert got, summed over
layers, one record a call) and what the device trace says of the
grouped-matmul operations (named by the ``pattern`` of the cell's
``moe_kernels``). The counts come from the module the configuration names
(``ctx.counts``). Each returns ``None`` where its cell has no expert layer,
without a trace, or where the program records no routing (a dense model; the
parent of the PR that added the records)."""

import re
import statistics

from chipbench.layer_metrics import serve_program


def _loaded(ctx, trace):
    """The placed phase records of a traced serving cell with an expert
    layer (``serve_program``'s, loaded once a run), else ``None``."""
    if "moe_kernels" not in ctx.cell or getattr(ctx, "counts", None) is None:
        return None
    got = serve_program._loaded(ctx, trace)
    return got[0] if got else None


def _records(placed, trace, name):
    """``(touched, largest)`` of the records of one name inside the window."""
    return [(a, b) for s, _, n, a, b in placed.phases
            if n == name and trace.lo <= s <= trace.hi]


def _slots(ctx):
    return ctx.dims["layers"] * ctx.dims["experts"]


def moe_experts_touched(ctx, record, trace):
    """Mean over the window's decode ticks of the (layer, expert) slots that
    got a row, as a share of layers x experts. The program routes every row
    of the tick, the dummy rows of empty pool slots too."""
    placed = _loaded(ctx, trace)
    recs = _records(placed, trace, "serve/moe_decode") if placed else []
    if not recs:
        return None
    return 100.0 * statistics.fmean(a for a, _ in recs) / _slots(ctx)


def moe_load_skew(ctx, record, trace):
    """Mean over decode ticks of the largest count any one expert got (mean
    over layers) over the mean count (rows x top_k / experts)."""
    placed = _loaded(ctx, trace)
    recs = _records(placed, trace, "serve/moe_decode") if placed else []
    if not recs:
        return None
    dims = ctx.dims
    mean = ctx.cell["serving"]["num_slots"] * dims["top_k"] / dims["experts"]
    return statistics.fmean(b for _, b in recs) / dims["layers"] / mean


def _module_seconds(ctx, trace, key):
    pat = re.compile(ctx.cell["modules"][key])
    runs = [(s, e) for s, e, name in trace.devices[0].modules
            if pat.search(name) and trace.lo <= s < trace.hi]
    return runs, sum(e - s for s, e in runs)


def moe_decode_hbm_share(ctx, record, trace):
    """(weights outside the experts + the touched experts' weights + live
    KV bytes, means over the window's decode ticks) / HBM bandwidth, over
    the mean device time of one decode program."""
    placed = _loaded(ctx, trace)
    recs = _records(placed, trace, "serve/moe_decode") if placed else []
    if not recs or not record.get("live_tokens"):
        return None
    runs, secs = _module_seconds(ctx, trace, "decode")
    if not runs:
        return None
    touched = statistics.fmean(a for a, _ in recs)
    live = statistics.fmean(record["live_tokens"])
    need = ctx.counts.decode_bytes(ctx.dims, touched, live, 2,
                                   record["vocab_rows"])
    ctx.log(f"decode program {secs / len(runs) * 1e3:.3f} ms mean over "
            f"{len(runs)}; needs {need:.4e} B ({touched:.1f} expert slots "
            f"touched, {live:.0f} live tokens)")
    return 100.0 * (need / ctx.peak["hbm_bytes_per_s"]) / (secs / len(runs))


def _kernel_ops(ctx, trace):
    pat = re.compile(ctx.cell["moe_kernels"]["pattern"])
    return [(s, e) for s, e, name in trace.devices[0].ops
            if pat.search(name) and trace.lo <= s and e <= trace.hi]


def moe_ffn_share(ctx, record, trace):
    """Device time of the grouped-matmul operations over device-busy time."""
    if _loaded(ctx, trace) is None:
        return None
    ops, busy = _kernel_ops(ctx, trace), trace.busy_s(0)
    if not ops or busy <= 0:
        return None
    return 100.0 * sum(e - s for s, e in ops) / busy


def moe_prefill_roofline(ctx, record, trace):
    """Least time the expert matmuls of the window's prefills require (the
    larger of FLOPs / peak and bytes / bandwidth, from the prompts' tokens
    and the expert slots they touched: ``serve/prefill_prep`` and
    ``serve/moe_prefill``) over the device time of the grouped-matmul
    operations inside the prefill programs."""
    placed = _loaded(ctx, trace)
    if placed is None:
        return None
    runs, _ = _module_seconds(ctx, trace, "prefill")
    ops = _kernel_ops(ctx, trace)
    secs = sum(e - s for s, e in ops
               if any(lo <= s and e <= hi for lo, hi in runs))
    tokens = [a for s, _, n, a, _ in placed.phases
              if n == "serve/prefill_prep" and trace.lo <= s <= trace.hi]
    touched = _records(placed, trace, "serve/moe_prefill")
    if secs <= 0 or not tokens or len(tokens) != len(touched):
        return None
    flops = ctx.counts.expert_flops(ctx.dims, sum(tokens))
    io = sum(ctx.counts.expert_io_bytes(ctx.dims, t, a)
             for t, (a, _) in zip(tokens, touched))
    tf, tb = flops / ctx.peak["bf16_flops_per_s"], \
        io / ctx.peak["hbm_bytes_per_s"]
    ctx.log(f"{len(tokens)} prefills: expert matmuls {secs * 1e3:.3f} ms, "
            f"least {max(tf, tb) * 1e3:.3f} ms ({flops:.3e} FLOP {io:.3e} B, "
            f"bound by {'compute' if tf >= tb else 'memory'})")
    return 100.0 * max(tf, tb) / secs


METRICS = {"moe_experts_touched": moe_experts_touched,
           "moe_load_skew": moe_load_skew,
           "moe_decode_hbm_share": moe_decode_hbm_share,
           "moe_ffn_share": moe_ffn_share,
           "moe_prefill_roofline": moe_prefill_roofline}
