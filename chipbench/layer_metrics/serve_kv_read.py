"""How much of the KV pool a decode step reads: what the program's
``serve/kv_read`` phase record says (one a decode tick: ``a`` the columns of
one layer the step's attention read, ``b`` the pool's columns, slots x
``max_model_len``). Where the decode program took the decode-attention
kernel (``deepspeed_tpu/ops/pallas/decode_attention.py``) ``a`` is every
slot's live length in whole blocks; where it contracts over the whole pool
(the XLA attend: a CPU, a mesh, rows the kernel does not take) ``a`` is
``b``. ``None`` outside a serving cell, without a trace, and where the
program records no ``serve/kv_read`` (the parent of the PR that added the
kernel)."""

import statistics

from chipbench.layer_metrics import serve_program
from chipbench.layer_metrics.serve_moe import _records


def kv_read_share(ctx, record, trace):
    """Mean over the window's decode ticks of the columns the decode
    attention read, as a share of the pool's columns. The XLA attend reads
    them all (100%); the live tokens' own share is ``slot_occupancy`` times
    their mean length over ``max_model_len``."""
    got = serve_program._loaded(ctx, trace)
    recs = _records(got[0], trace, "serve/kv_read") if got else []
    if not recs:
        return None
    ctx.log(f"{len(recs)} serve/kv_read records: "
            f"{statistics.fmean(a for a, _ in recs):.0f} columns fetched a "
            f"layer of {recs[0][1]}")
    return statistics.fmean(100.0 * a / b for a, b in recs)


#: one quantity under the end-to-end metric each cell reports: a cell over
#: its knee reports tokens per second alone
METRICS = {"kv_read_share": kv_read_share,
           "kv_read_share.backlog": kv_read_share}
