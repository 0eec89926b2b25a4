"""Training batches: tokens with a Zipf unigram distribution, as text has
(uniform tokens leave nothing to learn below ln(vocab)). Every seed gives the
same shapes and the same amount of work; only the tokens differ."""

import numpy as np


def generate(params, seed, vocab, seconds):
    """A list of ``[gas, rows, seq]`` int32 batches, a new one for each step:
    ``warm_batches`` for set-up and ``batches_per_second * seconds`` for the
    window (the job cycles through them if it runs out)."""
    rng = np.random.default_rng(int(seed))
    n = int(params["warm_batches"] +
            np.ceil(params["batches_per_second"] * seconds))
    p = 1.0 / np.arange(1, vocab + 1) ** params["exponent"]
    shape = (n, params["gas"], params["rows"], params["seq"])
    ids = rng.choice(vocab, size=shape, p=p / p.sum()).astype(np.int32)
    return [{"input_ids": b} for b in ids]
