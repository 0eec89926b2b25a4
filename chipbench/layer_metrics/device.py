"""Readers of the device trace: idle share, the attention kernel's roofline
share, prefill's share and the decode step's share
of its memory roofline. Each returns ``None`` where its cell has nothing for
it to read."""

import re

from chipbench import counts


def _idle(kind):
    def read(ctx, record, trace):
        if trace is None or ctx.cell["job"] != kind:
            return None
        return 100.0 * (1.0 - trace.busy_s(0) / trace.window_s)
    return read


def attn_roofline(ctx, record, trace):
    """Least time the causal attention of the traced steps requires
    (``counts.attention_*`` over layers x sequences per device x steps;
    forward once, backward once, recompute not counted; the larger of
    FLOPs / peak and bytes / bandwidth) over the device time of the
    attention kernels (``pattern`` of the cell's ``attention_kernels``)."""
    spec = ctx.cell.get("attention_kernels")
    if trace is None or spec is None or not record.get("steps"):
        return None
    dev = trace.devices[0]
    pat = re.compile(spec["pattern"])
    secs = sum(t for name, t in dev.op_self_seconds(trace.lo, trace.hi).items()
               if pat.search(name))
    if secs <= 0:
        return None
    dims, t = ctx.dims, ctx.traffic
    seqs = t["gas"] * t["rows"] // len(ctx.devices) * dims["layers"]
    least = 0.0
    for backward in (False, True):
        f = counts.attention_flops(dims, t["seq"], backward)
        b = counts.attention_bytes(dims, t["seq"], backward)
        tf, tb = f / ctx.peak["bf16_flops_per_s"], b / ctx.peak["hbm_bytes_per_s"]
        least += seqs * max(tf, tb)
        ctx.log(f"attention {'backward' if backward else 'forward'}: "
                f"{f:.3e} FLOP {b:.3e} B per sequence-layer, bound by "
                f"{'compute' if tf >= tb else 'memory'}")
    steps = record["steps"]
    ctx.log(f"attention kernels {secs / steps * 1e3:.3f} ms/step, least "
            f"{least * 1e3:.3f} ms/step")
    return 100.0 * least * steps / secs


def _module_seconds(ctx, trace, key):
    pat = re.compile(ctx.cell["modules"][key])
    mods = trace.devices[0].module_seconds(trace.lo, trace.hi)
    n = sum(c for name, (c, _) in mods.items() if pat.search(name))
    secs = sum(t for name, (_, t) in mods.items() if pat.search(name))
    return n, secs


def prefill_share(ctx, record, trace):
    """Share of device-busy time spent in the prefill programs."""
    if trace is None or "prefill" not in ctx.cell.get("modules", {}):
        return None
    n, secs = _module_seconds(ctx, trace, "prefill")
    busy = trace.busy_s(0)
    return 100.0 * secs / busy if n and busy > 0 else None


def decode_hbm_share(ctx, record, trace):
    """(weight bytes + bytes of the live KV tokens, mean over decode ticks)
    / HBM bandwidth, over the mean device time of one decode program."""
    if trace is None or "decode" not in ctx.cell.get("modules", {}) \
            or not record.get("live_tokens"):
        return None
    n, secs = _module_seconds(ctx, trace, "decode")
    if not n:
        return None
    dims = ctx.dims
    live = sum(record["live_tokens"]) / len(record["live_tokens"])
    need = counts.weight_bytes(dims, 2, record["vocab_rows"]) + \
        live * counts.kv_bytes_per_token(dims, 2)
    ctx.log(f"decode program {secs / n * 1e3:.3f} ms mean over {n}; needs "
            f"{need:.4e} B ({live:.0f} live tokens)")
    return 100.0 * (need / ctx.peak["hbm_bytes_per_s"]) / (secs / n)


METRICS = {"device_idle.train": _idle("train"),
           "device_idle.serve": _idle("serve"),
           "attn_roofline": attn_roofline,
           "prefill_share": prefill_share,
           "decode_hbm_share": decode_hbm_share}
