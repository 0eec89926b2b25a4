"""A serving cell whose model keeps a LATENT cache and a widened residual
(``deepspeed_tpu/models/xing.py``): device time under the program's own
scope words ``attend_latent`` (with ``kv_write``, ``kv_read``, ``latent_up``
and ``absorb`` under it) and ``hc_maps`` / ``hc_mix`` (``_scope_join``),
against what the configuration's counts (``ctx.counts``) say the work
requires. Each returns ``None`` without a trace, for a program that keeps no
scope tables or has no such scope (every other architecture; the parent of
the PR that added them), where the counts module has no such name, and
where the cell ran no call of the program it reads.

Their entries for ``BENCHMARK.json`` are ``serve_latent.entries.json``
beside this file: a ``benchmark`` PR appends them with
``scope_time.entries.json``'s eleven (PERF.md section 7); until then they
are read under a manifest laid over a copy
(``tests/chipbench/test_chipbench_xing.py``)."""

import statistics

from chipbench.layer_metrics import _scope_join as J
from chipbench.layer_metrics import serve_program
from chipbench.layer_metrics.serve_moe import _records

ATTEND = {"attend_latent"}
HYPER = {"hc_maps", "hc_mix"}

_has = lambda words: lambda parts: bool(words & set(parts))


def _under(ctx, trace, module, words):
    """Seconds of ``module``'s device time under ``words`` and its calls,
    or ``None`` where the program has no such scope."""
    got = J.load(ctx, trace)
    if got is None or not got.calls.get(module) or module not in got.seconds:
        return None
    secs = got.under(module, _has(words))
    return (secs, got) if secs > 0 else None


def latent_attend_hbm_share(ctx, record, trace):
    """The decode attend's share of its roofline: the live tokens' latent
    bytes (``counts.live_kv_bytes`` of the window's ``serve/kv_live``
    records, mean over decode ticks) / HBM bandwidth, over ``jit_dec``'s
    device time under ``attend_latent`` a call. The XLA attend reads the
    whole slab (``kv_read_share`` 100%), so this reads the live share of
    the pool times the attend's own efficiency."""
    counts = getattr(ctx, "counts", None)
    found = _under(ctx, trace, "jit_dec", ATTEND) \
        if hasattr(counts, "live_kv_bytes") else None
    placed = serve_program._loaded(ctx, trace) if found else None
    recs = _records(placed[0], trace, "serve/kv_live") if placed else []
    if not recs:
        return None
    secs, got = found
    live = statistics.fmean(counts.live_kv_bytes(ctx.dims, a, b)
                            for a, b in recs)
    per_call = secs / got.calls["jit_dec"]
    ctx.log(f"jit_dec under attend_latent {per_call * 1e3:.3f} ms a call "
            f"over {got.calls['jit_dec']}; {live:.4e} live latent B")
    return 100.0 * (live / ctx.peak["hbm_bytes_per_s"]) / per_call


def latent_prefill_roofline(ctx, record, trace):
    """The expanded attend's share of its roofline: the operations the
    window's prefills require of it (``counts.prefill_attend_flops`` of each
    prompt's tokens, ``serve/prefill_prep``: the up-projection of the lane
    and causal attention with the masked half left out) / peak bf16, over
    ``jit_pf``'s device time under ``attend_latent``."""
    counts = getattr(ctx, "counts", None)
    found = _under(ctx, trace, "jit_pf", ATTEND) \
        if hasattr(counts, "prefill_attend_flops") else None
    placed = serve_program._loaded(ctx, trace) if found else None
    tokens = [a for a, _ in _records(placed[0], trace, "serve/prefill_prep")
              ] if placed else []
    if not tokens:
        return None
    secs, _ = found
    flops = sum(counts.prefill_attend_flops(ctx.dims, t) for t in tokens)
    ctx.log(f"{len(tokens)} prefills: jit_pf under attend_latent "
            f"{secs * 1e3:.3f} ms, requires {flops:.4e} FLOP")
    return 100.0 * (flops / ctx.peak["bf16_flops_per_s"]) / secs


def hc_decode_ms(ctx, record, trace):
    """``jit_dec``'s device time under ``hc_maps`` and ``hc_mix``, a call:
    what the widened residual costs a decode step."""
    found = _under(ctx, trace, "jit_dec", HYPER)
    if found is None:
        return None
    return found[0] / found[1].calls["jit_dec"] * 1e3


def hc_prefill_us_per_token(ctx, record, trace):
    """``jit_pf``'s device time under ``hc_maps`` and ``hc_mix``, a bucket
    token."""
    found = _under(ctx, trace, "jit_pf", HYPER)
    if found is None or not found[1].bucket_tokens:
        return None
    return found[0] / found[1].bucket_tokens * 1e6


METRICS = {"latent_attend_hbm_share": latent_attend_hbm_share,
           "latent_prefill_roofline": latent_prefill_roofline,
           "hc_decode_ms": hc_decode_ms,
           "hc_prefill_us_per_token": hc_prefill_us_per_token}
