"""Operations and bytes the LFM2-MoE configuration requires, from its sizes
alone (``dims`` is the ``dims`` block of its configuration file). The names
``layer_metrics/serve_moe.py`` calls (``decode_bytes``, ``expert_flops``,
``expert_io_bytes``, ``total_weight_bytes``) are ``counts_olmoe.py``'s, and
``non_expert_decode_bytes`` is what ``layer_metrics/serve_hybrid.py`` reads.

``dims["layers"]`` is the number of ROUTED layers (8 of the cut's 9):
``serve_moe.py`` multiplies it by ``experts`` for the (layer, expert) slots
and divides the program's summed counts by it. The stack around them is
``dims["layer_types"]`` (the operator of every layer, conv or
full_attention) and ``dims["dense_layers"]`` (the leading layers whose FFN
is dense); ``weights_lfm2.kinds`` counts them.
"""

from chipbench.weights_lfm2 import kinds


def expert_bytes(dims, itemsize=2):
    """Bytes of ONE expert of ONE layer: gate, up and down matrices."""
    return 3 * dims["d_model"] * dims["expert_ff"] * itemsize


def non_expert_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter outside the experts that a decode step reads
    once. A conv layer: the input projection [d, 3d], the filters [d, K],
    the output projection [d, d], its norm gain. An attention layer: the
    fused q/k/v and the output projections, its norm gain and the two
    per-head gains. A dense FFN: three matrices [d, dense_ff] and its norm
    gain; a routed one: the router [d, E], the selection bias [E] and its
    norm gain. The final norm, and the tied table read as the head (as the
    embedding it is indexed, a row per stream: left out)."""
    d, hd = dims["d_model"], dims["head_dim"]
    conv, attn, dense, routed = kinds(dims)
    qkv = d * (dims["heads"] + 2 * dims["kv_heads"]) * hd
    params = conv * (3 * d * d + d * dims["conv_taps"] + d * d + d) + \
        attn * (qkv + d * d + d + 2 * hd) + \
        dense * (3 * d * dims["dense_ff"] + d) + \
        routed * (d * dims["experts"] + dims["experts"] + d) + \
        d + (vocab_rows or dims["vocab"]) * d
    return params * itemsize


def kv_bytes_per_token(dims, itemsize=2):
    """Bytes of one token's keys and values: the ATTENTION layers alone."""
    return 2 * kinds(dims)[1] * dims["kv_heads"] * dims["head_dim"] * itemsize


def state_bytes_per_slot(dims, itemsize=2):
    """Bytes of one slot's recurrent state: K - 1 rows of d a conv layer,
    whatever the slot's length."""
    return kinds(dims)[0] * (dims["conv_taps"] - 1) * dims["d_model"] * itemsize


def non_expert_decode_bytes(dims, live_tokens, slots, itemsize=2,
                            vocab_rows=None):
    """Bytes one decode tick requires of everything around the expert
    matmuls: the weights outside the experts, the live tokens' keys and
    values of the attention layers, and ``slots`` slots' recurrent state
    read and written back."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        live_tokens * kv_bytes_per_token(dims, itemsize) + \
        2 * slots * state_bytes_per_slot(dims, itemsize)


def decode_bytes(dims, touched, live_tokens, itemsize=2, vocab_rows=None):
    """Bytes one decode tick requires: the weights outside the experts, the
    ``touched`` (layer, expert) slots' weights (summed over the routed
    layers, as the program counts them) and the live tokens' keys and
    values. The recurrent state (2.75 MB for 48 slots) needs the number of
    slots, which this name's callers do not give: left out here, counted in
    ``non_expert_decode_bytes``."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        touched * expert_bytes(dims, itemsize) + \
        live_tokens * kv_bytes_per_token(dims, itemsize)


def expert_flops(dims, tokens):
    """FLOPs the expert matmuls of ``tokens`` tokens require over the routed
    layers: top_k experts a token, three matmuls of 2 * d * f each."""
    return tokens * dims["layers"] * dims["top_k"] * \
        6 * dims["d_model"] * dims["expert_ff"]


def expert_io_bytes(dims, tokens, touched, itemsize=2):
    """Bytes the same matmuls must move over the routed layers: the
    ``touched`` (layer, expert) slots' weights once, and for every (token,
    expert) pair its input row twice (gate, up), the two hidden rows
    written and read, and its output row."""
    d, f = dims["d_model"], dims["expert_ff"]
    pairs = tokens * dims["layers"] * dims["top_k"]
    return touched * expert_bytes(dims, itemsize) + \
        pairs * (3 * d + 4 * f) * itemsize


def total_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Every parameter as held on the device: the one tied table (counted
    in ``non_expert_weight_bytes`` as the head), all experts."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        dims["layers"] * dims["experts"] * expert_bytes(dims, itemsize)
