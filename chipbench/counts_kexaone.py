"""Operations and bytes the K-EXAONE configuration requires of ONE chip's
share, from its sizes alone (``dims`` is the ``dims`` block of its
configuration file). The names ``layer_metrics/serve_moe.py`` calls
(``decode_bytes``, ``expert_flops``, ``expert_io_bytes``,
``total_weight_bytes``) are ``counts_olmoe.py``'s,
``non_expert_decode_bytes`` is what ``layer_metrics/serve_hybrid.py`` reads,
and ``live_kv_bytes`` / ``pool_bytes`` what ``layer_metrics/serve_window.py``
reads.

What a share of the experts asks of ``dims``: ``experts`` is the number HELD
here (16: what ``serve_moe.py`` multiplies by ``layers`` for the (layer,
expert) slots the program's counters count, and every byte count below),
``router_experts`` the number the router scores (128: its width, and what a
token's ``top_k`` picks are spread over), ``expert_offset`` the first held.
A token's picks fall on a held expert ``experts / router_experts`` of the
time: the expert matmuls of ``tokens`` tokens are those of ``tokens * top_k *
experts / router_experts`` pairs (expected; the program's counters say what
a run's were). ``dims["layers"]`` is the number of ROUTED layers, as
``counts_lfm2.py``'s.
"""

from chipbench.weights_kexaone import kinds


def expert_bytes(dims, itemsize=2):
    """Bytes of ONE expert of ONE layer: gate, up and down matrices."""
    return 3 * dims["d_model"] * dims["expert_ff"] * itemsize


def non_expert_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter outside the routed experts that a decode
    step reads once. An attention layer of either kind: the fused q/k/v
    [d, (H + 2 Hk) hd] and the output projection [H hd, d], the gain on its
    output and the two per-head gains. A dense FFN: three matrices
    [d, dense_ff] and the gain on its output; a routed one: the router
    [d, router_experts], the selection bias, the shared expert's three
    matrices and the gain. The final norm, and the untied head (the
    embedding is indexed, a row per stream: left out)."""
    d, hd, h = dims["d_model"], dims["head_dim"], dims["heads"]
    window, full, dense, routed = kinds(dims)
    attn = d * (h + 2 * dims["kv_heads"]) * hd + h * hd * d + d + 2 * hd
    shared = 3 * d * dims["expert_ff"] * dims["shared_experts"]
    params = (window + full) * attn + \
        dense * (3 * d * dims["dense_ff"] + d) + \
        routed * (d * dims["router_experts"] + dims["router_experts"] +
                  shared + d) + \
        d + (vocab_rows or dims["vocab"]) * d
    return params * itemsize


def kv_bytes_per_token(dims, itemsize=2):
    """Bytes of one token's keys and values that stay for the lane's
    length: the FULL layers alone."""
    return 2 * kinds(dims)[1] * dims["kv_heads"] * dims["head_dim"] * itemsize


def ring_bytes_per_column(dims, itemsize=2):
    """Bytes of one ring column (a position's keys and values) over the
    WINDOW layers."""
    return 2 * kinds(dims)[0] * dims["kv_heads"] * dims["head_dim"] * itemsize


def state_bytes_per_slot(dims, itemsize=2):
    """Bytes of one slot's rings: ``window`` columns a window layer,
    whatever the slot's length."""
    return dims["window"] * ring_bytes_per_column(dims, itemsize)


def live_kv_bytes(dims, lane_columns, ring_columns, itemsize=2):
    """Bytes of the pool that hold a token of an active slot:
    ``lane_columns`` columns over the full-length lanes and ``ring_columns``
    over the rings (the payload of ``serve/kv_live``)."""
    return lane_columns * kv_bytes_per_token(dims, itemsize) + \
        ring_columns * ring_bytes_per_column(dims, itemsize)


def pool_bytes(dims, slots, max_len, itemsize=2):
    """Bytes of the whole pool: ``max_len`` columns a slot over the full
    layers and a slot's rings."""
    return slots * (max_len * kv_bytes_per_token(dims, itemsize) +
                    state_bytes_per_slot(dims, itemsize))


def non_expert_decode_bytes(dims, live_tokens, slots, itemsize=2,
                            vocab_rows=None):
    """Bytes one decode tick requires of everything around the routed
    experts' matmuls: the weights outside them, the live tokens' keys and
    values of the full layers, and ``slots`` slots' rings read (one column
    of each is written: left out)."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        live_tokens * kv_bytes_per_token(dims, itemsize) + \
        slots * state_bytes_per_slot(dims, itemsize)


def decode_bytes(dims, touched, live_tokens, itemsize=2, vocab_rows=None):
    """Bytes one decode tick requires: the weights outside the routed
    experts, the ``touched`` (layer, held expert) slots' weights (summed
    over the routed layers, as the program counts them) and the live
    tokens' keys and values of the full layers. The rings need the number
    of slots, which this name's callers do not give: left out here, counted
    in ``non_expert_decode_bytes``."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        touched * expert_bytes(dims, itemsize) + \
        live_tokens * kv_bytes_per_token(dims, itemsize)


def held_pairs(dims, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on a held
    expert, over the routed layers: expected, with picks spread evenly."""
    return tokens * dims["layers"] * dims["top_k"] * \
        dims["experts"] / dims["router_experts"]


def expert_flops(dims, tokens):
    """FLOPs the HELD experts' matmuls of ``tokens`` tokens require over
    the routed layers: three matmuls of 2 * d * f for each expected pair
    (the shared expert is no grouped matmul and is not counted)."""
    return held_pairs(dims, tokens) * 6 * dims["d_model"] * dims["expert_ff"]


def expert_io_bytes(dims, tokens, touched, itemsize=2):
    """Bytes the same matmuls must move over the routed layers: the
    ``touched`` (layer, held expert) slots' weights once, and for every
    expected pair its input row twice (gate, up), the two hidden rows
    written and read, and its output row."""
    d, f = dims["d_model"], dims["expert_ff"]
    return touched * expert_bytes(dims, itemsize) + \
        held_pairs(dims, tokens) * (3 * d + 4 * f) * itemsize


def total_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Every parameter as held on the device: both tables (the head is
    counted in ``non_expert_weight_bytes``), the held experts."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        (vocab_rows or dims["vocab"]) * dims["d_model"] * itemsize + \
        dims["layers"] * dims["experts"] * expert_bytes(dims, itemsize)
