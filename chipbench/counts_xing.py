"""Operations and bytes the Xing4.0 configuration requires of ONE chip's
share, from its sizes alone (``dims`` is the ``dims`` block of its
configuration file). The names the accepted readers call are
``counts_kexaone.py``'s (``layer_metrics/serve_moe.py``: ``decode_bytes``,
``expert_flops``, ``expert_io_bytes``; ``serve_hybrid.py``:
``non_expert_decode_bytes``; ``serve_window.py``: ``live_kv_bytes``,
``pool_bytes``), with a share's meaning of ``dims`` (``experts`` HELD here,
``router_experts`` scored, ``layers`` the ROUTED layers). What is this
configuration's own, for ``layer_metrics/serve_latent.py``: the latent
cache's bytes a token, the widened residual's (mHC) operations and bytes,
and the attention operations of its two paths.
"""


def n_layers(dims):
    return dims["dense_layers"] + dims["layers"]


def expert_bytes(dims, itemsize=2):
    """Bytes of ONE expert of ONE layer: gate, up and down matrices."""
    return 3 * dims["d_model"] * dims["expert_ff"] * itemsize


def attention_params(dims):
    """One layer's latent attention: ``W_qa`` [d, q_rank] and its norm's
    gain, ``W_qb`` [q_rank, H (nope + rope)], ``W_kva`` [d, kv_rank + rope]
    and its gain, ``W_kvb`` [kv_rank, H (nope + v)], ``W_o`` [H v, d], the
    input norm's gain."""
    d, h = dims["d_model"], dims["heads"]
    q, c = dims["q_rank"], dims["kv_rank"]
    nope, rope, v = dims["nope_dim"], dims["rope_dim"], dims["v_dim"]
    return d * q + q + q * h * (nope + rope) + d * (c + rope) + c + \
        c * h * (nope + v) + h * v * d + d


def hc_params(dims):
    """One SUBLAYER's maps: ``phi`` [n d, n (n + 2)], ``b``, three gates."""
    n = dims["streams"]
    return n * dims["d_model"] * n * (n + 2) + n * (n + 2) + 3


def non_expert_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter outside the routed experts that a decode
    step reads once: every layer's attention and its two sublayers' maps; a
    dense FFN's three matrices and gain; a routed layer's router, selection
    bias, shared expert and gain; the final norm and the untied head (the
    embedding is indexed, a row per stream: left out)."""
    d = dims["d_model"]
    shared = 3 * d * dims["expert_ff"] * dims["shared_experts"]
    params = n_layers(dims) * (attention_params(dims) + 2 * hc_params(dims)) \
        + dims["dense_layers"] * (3 * d * dims["dense_ff"] + d) + \
        dims["layers"] * (d * dims["router_experts"] +
                          dims["router_experts"] + shared + d) + \
        d + (vocab_rows or dims["vocab"]) * d
    return params * itemsize


def kv_bytes_per_token(dims, itemsize=2):
    """Bytes of one token's latent rows over the layers: ``kv_rank + rope``
    values a layer (1,152 B in bfloat16 at the published 576; per-head keys
    and values would be H (nope + rope + v) values, 20,480 B)."""
    return n_layers(dims) * (dims["kv_rank"] + dims["rope_dim"]) * itemsize


def live_kv_bytes(dims, lane_columns, ring_columns=0, itemsize=2):
    """Bytes of the pool that hold a token of an active slot (the payload
    of ``serve/kv_live``: ``lane_columns``; a latent pool has no rings)."""
    return lane_columns * kv_bytes_per_token(dims, itemsize)


def pool_bytes(dims, slots, max_len, itemsize=2):
    return slots * max_len * kv_bytes_per_token(dims, itemsize)


def non_expert_decode_bytes(dims, live_tokens, slots, itemsize=2,
                            vocab_rows=None):
    """Bytes one decode tick requires of everything around the routed
    experts' matmuls: the weights outside them and the live tokens' latent
    rows (``slots``: no per-slot state here)."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        live_tokens * kv_bytes_per_token(dims, itemsize)


def decode_bytes(dims, touched, live_tokens, itemsize=2, vocab_rows=None):
    """Bytes one decode tick requires: the weights outside the routed
    experts, the ``touched`` (layer, held expert) slots' weights and the
    live tokens' latent rows."""
    return non_expert_decode_bytes(dims, live_tokens, 0, itemsize,
                                   vocab_rows) + \
        touched * expert_bytes(dims, itemsize)


def held_pairs(dims, tokens):
    """(token, expert) pairs of ``tokens`` tokens that fall on a held
    expert, over the routed layers: expected, with picks spread evenly."""
    return tokens * dims["layers"] * dims["top_k"] * \
        dims["experts"] / dims["router_experts"]


def expert_flops(dims, tokens):
    return held_pairs(dims, tokens) * 6 * dims["d_model"] * dims["expert_ff"]


def expert_io_bytes(dims, tokens, touched, itemsize=2):
    d, f = dims["d_model"], dims["expert_ff"]
    return touched * expert_bytes(dims, itemsize) + \
        held_pairs(dims, tokens) * (3 * d + 4 * f) * itemsize


def total_weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Every parameter as held on the device: both tables, the held
    experts."""
    return non_expert_weight_bytes(dims, itemsize, vocab_rows) + \
        (vocab_rows or dims["vocab"]) * dims["d_model"] * itemsize + \
        dims["layers"] * dims["experts"] * expert_bytes(dims, itemsize)


# ------------------------------------------------ the widened residual (mHC)

def hc_flops_per_token(dims):
    """Operations of a token's maps and mixes over the layers' 2 sublayers:
    the projection (2 n d n (n + 2)), the flattened norm (3 n d), the read
    ``H_pre X`` (2 n d) and the write ``H_res X + H_post^T y`` (2 n n d +
    2 n d); the Sinkhorn steps (4 n n an iteration) are noise beside
    them."""
    n, d = dims["streams"], dims["d_model"]
    sub = 2 * n * d * n * (n + 2) + 3 * n * d + 2 * n * d + \
        2 * n * n * d + 2 * n * d + 4 * n * n * dims["hc_sinkhorn_iters"]
    return 2 * n_layers(dims) * sub


def hc_bytes_per_token(dims, itemsize=2):
    """Bytes a token's streams move through the maps and mixes: a sublayer
    reads the n streams for its maps and read, reads them and the
    sublayer's output for the write, and writes n streams."""
    n, d = dims["streams"], dims["d_model"]
    return 2 * n_layers(dims) * (3 * n + 2) * d * itemsize


# ------------------------------------------------ attention, the two paths

def prefill_attend_flops(dims, tokens):
    """Operations the EXPANDED path requires of a prefill of ``tokens``
    tokens, over the layers: the up-projection of their latent rows to
    per-head keys and values (2 t kv_rank H (nope + v)) and causal
    attention with the masked half left out (t (t + 1) / 2 pairs a head,
    2 (nope + rope) a score and 2 v a weighted value)."""
    h = dims["heads"]
    nope, rope, v = dims["nope_dim"], dims["rope_dim"], dims["v_dim"]
    up = 2 * tokens * dims["kv_rank"] * h * (nope + v)
    pairs = tokens * (tokens + 1) // 2
    return n_layers(dims) * (up + pairs * h * 2 * (nope + rope + v))


def decode_attend_flops(dims, slots, live_tokens):
    """Operations the ABSORBED path requires of a decode tick, over the
    layers: a query a slot through the key half of ``W_kvb`` and the
    weighted latent through its value half (2 kv_rank H (nope + v) a slot),
    and a score and a weighted sum over each live token's latent row for
    every head (2 H (2 kv_rank + rope) a live token)."""
    h, c = dims["heads"], dims["kv_rank"]
    per_slot = 2 * c * h * (dims["nope_dim"] + dims["v_dim"])
    return n_layers(dims) * (slots * per_slot +
                             live_tokens * 2 * h * (2 * c + dims["rope_dim"]))
