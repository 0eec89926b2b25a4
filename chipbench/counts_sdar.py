"""Operations and bytes the SDAR configuration requires, from its sizes
alone (``dims`` is the ``dims`` block of its configuration file). The
expert layer is counted apart from the rest, as ``counts_olmoe.py`` counts
it: a pass of the tick must read the weights of the experts its rows TOUCH,
once a pass however many rows (B a slot) it carries, and a prefill's expert
matmuls are 6 * d * f FLOPs for each (token, chosen expert) pair. An expert,
a pair's rows and a token's keys and values are sized as OLMoE's are, and
those counts are ``counts_olmoe``'s. The heads are ``head_dim`` wide, which is
not ``d_model / heads``.
"""

from chipbench.counts_olmoe import (expert_bytes, expert_flops,   # noqa: F401
                                    expert_io_bytes, kv_bytes_per_token)


def layer_params_outside_experts(dims):
    """Parameters of one layer outside its experts: the fused q/k/v and
    the output projection, the router, two norm gains and the two head
    norms' gains."""
    d, hd = dims["d_model"], dims["head_dim"]
    qkv = d * (dims["heads"] + 2 * dims["kv_heads"]) * hd
    return qkv + dims["heads"] * hd * d + d * dims["experts"] + 2 * d + 2 * hd


def non_expert_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter outside the experts that a pass reads once:
    the layers' (``layer_params_outside_experts``), the final norm and the
    untied head. The embedding table is indexed, not read: a row a
    position, left out."""
    d = dims["d_model"]
    return (dims["layers"] * layer_params_outside_experts(dims) +
            (vocab_rows or dims["vocab"]) * d + d) * itemsize


def decode_bytes(dims, touched, live_tokens, itemsize=2, vocab_rows=None):
    """Bytes one pass of the tick requires: the weights outside the
    experts, the ``touched`` (layer, expert) slots' weights (summed over
    layers, as the program counts them; once a pass, whatever the rows)
    and the live tokens' keys and values."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        touched * expert_bytes(dims, itemsize) + \
        live_tokens * kv_bytes_per_token(dims, itemsize)


def block_pass_bytes(dims, touched, live_tokens, rows, itemsize=2,
                     vocab_rows=None):
    """``decode_bytes`` and what the pass's ``rows`` positions (slots x B)
    add of their own: the logits written and read back by the sampler in
    float32, and each (row, chosen expert) pair's rows through the three
    expert matmuls."""
    d, f = dims["d_model"], dims["expert_ff"]
    pairs = rows * dims["layers"] * dims["top_k"]
    return decode_bytes(dims, touched, live_tokens, itemsize, vocab_rows) + \
        2 * rows * (vocab_rows or dims["vocab"]) * 4 + \
        pairs * (3 * d + 4 * f) * itemsize


def total_params(dims, vocab_rows=None):
    """Every parameter held: both tables, the final norm, all experts."""
    rows = vocab_rows or dims["vocab"]
    d = dims["d_model"]
    return dims["layers"] * (
        layer_params_outside_experts(dims) +
        dims["experts"] * 3 * d * dims["expert_ff"]) + 2 * rows * d + d


def total_weight_bytes(dims, itemsize=2, vocab_rows=None):
    return total_params(dims, vocab_rows) * itemsize
