"""A serving cell whose model keeps window layers' keys and values in rings
and holds a share of its experts: what the program's ``serve/kv_live``
phase record says (one a decode tick: the columns that hold a token of an
active slot, ``a`` over the full-length lanes and ``b`` over the rings) and
what its ``serve/moe_decode`` record says of the HELD experts' load, with
the counts of the module the configuration names (``ctx.counts``). Each
returns ``None`` without a trace, in a cell without ``moe_kernels``, where
the configuration's counts have no such names (every other architecture),
and where the program records no ``serve/kv_live`` (a model without rings;
the parent of the PR that added the record)."""

import statistics

from chipbench.layer_metrics.serve_moe import _loaded, _records


def kv_live_share(ctx, record, trace):
    """Mean over the window's decode ticks of the pool's bytes that hold a
    token of an active slot (``counts.live_kv_bytes`` of the tick's
    ``serve/kv_live``), as a share of the whole pool's
    (``counts.pool_bytes``): how much of the memory the pool pins is in
    use. The rings make the pool 3.3 GB here where every layer at full
    length would make it 16.1 GB."""
    counts = getattr(ctx, "counts", None)
    if not hasattr(counts, "live_kv_bytes") or \
            not hasattr(counts, "pool_bytes"):
        return None
    placed = _loaded(ctx, trace)
    recs = _records(placed, trace, "serve/kv_live") if placed else []
    if not recs:
        return None
    serving = ctx.cell["serving"]
    pool = counts.pool_bytes(ctx.dims, serving["num_slots"],
                             serving["max_model_len"])
    live = statistics.fmean(counts.live_kv_bytes(ctx.dims, a, b)
                            for a, b in recs)
    ctx.log(f"{len(recs)} serve/kv_live records: {live:.4e} live B of "
            f"{pool:.4e} pool B")
    return 100.0 * live / pool


def moe_share_skew(ctx, record, trace):
    """``moe_load_skew`` for a chip that holds a share of the experts: mean
    over decode ticks of the largest count any one HELD expert got (mean
    over layers) over the mean count an expert gets, rows x top_k over the
    ``router_experts`` the picks are spread over (not over the ``experts``
    held, which would read ``router_experts / experts`` times low)."""
    dims = getattr(ctx, "dims", None) or {}
    if "router_experts" not in dims:
        return None
    placed = _loaded(ctx, trace)
    recs = _records(placed, trace, "serve/moe_decode") if placed else []
    if not recs:
        return None
    mean = ctx.cell["serving"]["num_slots"] * dims["top_k"] / \
        dims["router_experts"]
    return statistics.fmean(b for _, b in recs) / dims["layers"] / mean


METRICS = {"kv_live_share": kv_live_share,
           "moe_share_skew": moe_share_skew}
