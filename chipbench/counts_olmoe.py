"""Operations and bytes the OLMoE configuration requires, from its sizes
alone (``dims`` is the ``dims`` block of its configuration file). The
expert layer is counted apart from the rest: a decode tick must read the
weights of the experts its rows TOUCH, not of all of them, and a prefill's
expert matmuls are 6 * d * f FLOPs for each (token, chosen expert) pair.
"""


def expert_bytes(dims, itemsize=2):
    """Bytes of ONE expert of ONE layer: gate, up and down matrices."""
    return 3 * dims["d_model"] * dims["expert_ff"] * itemsize


def non_expert_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter outside the experts that a decode step reads
    once: per layer the fused q/k/v and output projections, the router and
    the four norm gains; the final norm and the untied head. The embedding
    table is indexed, not read: a row per stream, left out."""
    d = dims["d_model"]
    qkv = d * (dims["heads"] + 2 * dims["kv_heads"]) * dims["head_dim"]
    per_layer = qkv + d * d + d * dims["experts"] + 2 * d + \
        (dims["heads"] + dims["kv_heads"]) * dims["head_dim"]
    return (dims["layers"] * per_layer +
            (vocab_rows or dims["vocab"]) * d + d) * itemsize


def kv_bytes_per_token(dims, itemsize=2):
    """Bytes of one token's keys and values over all layers."""
    return 2 * dims["layers"] * dims["kv_heads"] * dims["head_dim"] * itemsize


def decode_bytes(dims, touched, live_tokens, itemsize=2, vocab_rows=None):
    """Bytes one decode tick requires: the weights outside the experts, the
    ``touched`` (layer, expert) slots' weights (summed over layers, as the
    program counts them) and the live tokens' keys and values."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        touched * expert_bytes(dims, itemsize) + \
        live_tokens * kv_bytes_per_token(dims, itemsize)


def expert_flops(dims, tokens):
    """FLOPs the expert matmuls of ``tokens`` tokens require over all
    layers: top_k experts a token, three matmuls of 2 * d * f each."""
    return tokens * dims["layers"] * dims["top_k"] * \
        6 * dims["d_model"] * dims["expert_ff"]


def expert_io_bytes(dims, tokens, touched, itemsize=2):
    """Bytes the same matmuls must move over all layers: the ``touched``
    (layer, expert) slots' weights once, and for every (token, expert) pair
    its input row twice (gate, up), the two hidden rows written and read,
    and its output row."""
    d, f = dims["d_model"], dims["expert_ff"]
    pairs = tokens * dims["layers"] * dims["top_k"]
    return touched * expert_bytes(dims, itemsize) + \
        pairs * (3 * d + 4 * f) * itemsize


def total_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Every parameter as held on the device: both tables, all experts."""
    rows = vocab_rows or dims["vocab"]
    return non_expert_weight_bytes(dims, itemsize, rows) + \
        rows * dims["d_model"] * itemsize + \
        dims["layers"] * dims["experts"] * expert_bytes(dims, itemsize)
