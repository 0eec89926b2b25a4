"""A serving cell whose model generates by diffusion over blocks
(``deepspeed_tpu/models/sdar.py``): what the program's ``serve/block_pass``
and ``serve/block_write`` phase records say (one each a pass of the tick,
written when it is read: ``block_pass`` ``a`` the slots' rows that fixed
positions and ``b`` the positions still masked going in; ``block_write``
``a`` the rows that wrote a finished block's keys and values and ``b`` all
the pass's rows), what the job made of the stream (``jobs/serve_blocks.py``:
the periods between a request's blocks) and what the device trace says of
the pass's program (the cell's ``modules.decode``). Each returns ``None``
without a trace, outside such a cell, and for a program that records no
such phase (every other family; the parent of the PR that added them).

Their entries for ``BENCHMARK.json`` are ``serve_blocks.entries.json``
beside this file: a ``benchmark`` PR appends them with
``scope_time.entries.json``'s and ``serve_latent.entries.json``'s (PERF.md
section 7); until then they are read under a manifest laid over a copy
(``tests/chipbench/test_chipbench_sdar.py``)."""

import statistics

from chipbench.layer_metrics import serve_program
from chipbench.layer_metrics.serve_moe import _module_seconds, _records


def _passes(ctx, trace):
    """``(unmasking rows, writing rows, all rows)`` summed over the window's
    passes, or ``None``."""
    got = serve_program._loaded(ctx, trace)
    if not got:
        return None
    unmask = _records(got[0], trace, "serve/block_pass")
    write = _records(got[0], trace, "serve/block_write")
    if not unmask or not write:
        return None
    return sum(a for a, _ in unmask), sum(a for a, _ in write), \
        sum(b for _, b in write)


def tokens_per_pass(ctx, record, trace):
    """Tokens delivered in the window over the rows (a slot, a pass) its
    passes carried for a request: B over the passes a block takes, less
    what ``max_new_tokens`` cuts; 1 for a family that decodes a token a
    step."""
    rows = _passes(ctx, trace)
    if rows is None or not (rows[0] + rows[1]):
        return None
    return record["serve_tokens_per_s"] * record["window_s"] / \
        (rows[0] + rows[1])


def block_write_share(ctx, record, trace):
    """Device time of the writing passes over device-busy time: the share
    of the pass program's device time that its writing rows are of all its
    rows. What fusing a block's writing pass with the next block's first
    pass would take back."""
    rows = _passes(ctx, trace)
    if rows is None or not rows[2]:
        return None
    runs, secs = _module_seconds(ctx, trace, "decode")
    busy = trace.busy_s(0)
    if not runs or busy <= 0:
        return None
    return 100.0 * (rows[1] / rows[2]) * secs / busy


def block_period_ms(ctx, record, trace):
    """Median time between the deliveries of two blocks of one request in
    a row (the job's, host clock)."""
    return record.get("block_period_ms") if trace is not None else None


def block_prefill_share(ctx, record, trace):
    """Share of those periods in which a prefill was sent: ``itl_p95_ms``
    is their 80th percentile, so under 10% it lies on the plain period."""
    return record.get("block_prefill_share") if trace is not None else None


def block_pass_hbm_share(ctx, record, trace):
    """The pass program's share of its roofline: the bytes a pass requires
    (``counts.block_pass_bytes``: the weights outside the experts, the
    touched experts' once, the live keys and values, the rows' logits and
    expert rows) / HBM bandwidth, over the program's mean device time."""
    counts = getattr(ctx, "counts", None)
    rows = _passes(ctx, trace) if hasattr(counts, "block_pass_bytes") \
        else None
    got = serve_program._loaded(ctx, trace) if rows else None
    routed = _records(got[0], trace, "serve/moe_decode") if got else []
    if not routed or not record.get("live_tokens"):
        return None
    runs, secs = _module_seconds(ctx, trace, "decode")
    if not runs:
        return None
    touched = statistics.fmean(a for a, _ in routed)
    live = statistics.fmean(record["live_tokens"])
    per_pass = ctx.cell["serving"]["num_slots"] * ctx.dims["block_length"]
    need = counts.block_pass_bytes(ctx.dims, touched, live, per_pass, 2,
                                   record["vocab_rows"])
    ctx.log(f"block pass {secs / len(runs) * 1e3:.3f} ms mean over "
            f"{len(runs)}; needs {need:.4e} B ({touched:.1f} expert slots "
            f"touched, {live:.0f} live tokens, {per_pass} rows)")
    return 100.0 * (need / ctx.peak["hbm_bytes_per_s"]) / (secs / len(runs))


def moe_load_skew_blocks(ctx, record, trace):
    """``serve_moe.moe_load_skew`` for a pass over blocks: mean over the
    window's passes of the largest count any one expert got (mean over
    layers) over the mean count of a PASS's rows (slots x B x top_k /
    experts; the accepted reader divides by a decode tick's, a row a slot,
    and would read B times high here). Rows that share the ``[MASK]`` row,
    and the dummy rows of free slots, route alike: what a change of the
    grouped matmuls for few rows an expert has to see."""
    got = serve_program._loaded(ctx, trace)
    if not got or "block_length" not in ctx.dims or \
            not _records(got[0], trace, "serve/block_pass"):
        return None
    routed = _records(got[0], trace, "serve/moe_decode")
    if not routed:
        return None
    dims = ctx.dims
    mean = ctx.cell["serving"]["num_slots"] * dims["block_length"] * \
        dims["top_k"] / dims["experts"]
    return statistics.fmean(b for _, b in routed) / dims["layers"] / mean


METRICS = {"tokens_per_pass": tokens_per_pass,
           "block_write_share": block_write_share,
           "block_period_ms": block_period_ms,
           "block_prefill_share": block_prefill_share,
           "block_pass_hbm_share": block_pass_hbm_share,
           "moe_load_skew.blocks": moe_load_skew_blocks}
