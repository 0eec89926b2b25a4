"""Operations and bytes a configuration requires, from its sizes alone.

These are the benchmark's counts (``GPT2Model.flops_per_token`` in the
program counts the position table, the padded vocabulary and non-causal
attention; it is not used). ``dims`` is the ``dims`` block of a
configuration file.
"""


def matmul_params(dims):
    """Parameters that take part in a matrix multiplication: the blocks'
    four matrices and the (tied, unpadded) output head. No position table,
    no biases, no LayerNorm."""
    d, ff = dims["d_model"], dims["d_ff"]
    return dims["layers"] * (4 * d * d + 2 * d * ff) + dims["vocab"] * d


def train_flops_per_token(dims, seq):
    """Forward + backward FLOPs one trained token requires at sequence
    length ``seq``: 6 per matmul parameter, plus causal attention
    (QK^T and PV: 2 * 2 * seq * d per layer forward, halved by the mask,
    times 3 for forward + backward). Recomputation is not counted."""
    return 6 * matmul_params(dims) + 6 * dims["layers"] * dims["d_model"] * seq


def attention_flops(dims, seq, backward):
    """FLOPs the causal attention of ONE sequence in ONE layer requires:
    forward 2 matmuls (QK^T, PV), backward 4 (dV, dP, dQ, dK); each is
    2 * seq * seq * d, halved by the causal mask. The score recomputation a
    flash backward makes is not required work and is not counted."""
    return (4 if backward else 2) * seq * seq * dims["d_model"]


def attention_bytes(dims, seq, backward, itemsize=2):
    """Bytes the same call must move: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    return (8 if backward else 4) * seq * dims["d_model"] * itemsize


def weight_bytes(dims, itemsize=2, vocab_rows=None):
    """Bytes of every parameter a decode step reads once: the blocks'
    matrices and biases, LayerNorms and the tied table (read as the head)."""
    d, ff, l = dims["d_model"], dims["d_ff"], dims["layers"]
    per_layer = 4 * d * d + 2 * d * ff + 9 * d + ff
    return (l * per_layer + (vocab_rows or dims["vocab"]) * d + 2 * d) * itemsize


def kv_bytes_per_token(dims, itemsize=2):
    """Bytes of one token's keys and values over all layers."""
    return 2 * dims["layers"] * dims["d_model"] * itemsize
