"""Open-loop request traffic: Poisson arrivals at a fixed rate, lognormal
prompt and output lengths, uniform token ids, nothing shared.

Every seed has the same number of requests (``round(rate * seconds)``), the
same multiset of prompt and output lengths (the quantiles of the two
distributions) and the same set of arrival times: a Poisson process of the
stated rate conditioned on its count, drawn once from ``SCHEDULE_SEED``. A
seed changes the order — which request arrives in which slot — and the token
ids. The order is shuffled only inside blocks of ``BLOCK`` requests of
neighbouring length: how many tokens a run can deliver inside its window
depends on whether the long requests come early or late, and a free shuffle
moved that by 6% from seed to seed on the chip (PR 26) while two runs of one
seed agreed to 0.4%.

``steady_start`` (optional) opens the window on a pool in use, as a stream
that has run for a long time leaves it: the same process is drawn over the
``history_s`` seconds before the window, and each of its requests that would
still be running when the window opens, at ``tick_ms`` a token, comes first
in the list with ``due`` under 0, the tokens it would have streamed by then
moved from its output into its prompt. That part is the same for every seed
but for the token ids.
"""

from statistics import NormalDist

import numpy as np


SCHEDULE_SEED = 26      # the one arrival schedule every seed shares
BLOCK = 4               # neighbours in length among which a seed shuffles


def _quantile_lengths(spec, n):
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _slots(base, rng):
    """``base`` maps the i-th shortest request to an arrival slot; the seed
    permutes the slots inside each block of ``BLOCK`` neighbours."""
    slot = base.copy()
    for b in range(0, len(base), BLOCK):
        slot[b:b + BLOCK] = rng.permutation(base[b:b + BLOCK])
    return slot


def _running(params, rate, vocab, rng):
    """The requests of the ``history_s`` seconds before the window that are
    still running when it opens: (prompt + tokens streamed so far, tokens
    left), oldest first."""
    start = params.get("steady_start")
    if not start:
        return []
    n = max(1, int(round(rate * start["history_s"])))
    fixed = np.random.default_rng(SCHEDULE_SEED + 1)
    age = np.sort(fixed.uniform(0.0, start["history_s"], n))[::-1]
    out_len = _quantile_lengths(params["output"], n)[fixed.permutation(n)]
    prompt_len = _quantile_lengths(params["prompt"], n)[fixed.permutation(n)]
    streamed = 1 + (age * 1e3 / start["tick_ms"]).astype(np.int64)
    return [{"due": -float(age[i]),
             "prompt": rng.integers(0, vocab, int(prompt_len[i] + streamed[i]),
                                    dtype=np.int32),
             "max_new": int(out_len[i] - streamed[i])}
            for i in range(n) if streamed[i] < out_len[i]]


def generate(params, seed, vocab, seconds, rate=None):
    """A list of requests sorted by ``due`` (seconds after the window opens;
    under 0: running already, see ``steady_start``):
    ``{"due", "prompt" (int32 ids), "max_new"}``."""
    rate = params["rate_per_s"] if rate is None else rate
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(SCHEDULE_SEED)
    due = np.sort(fixed.uniform(0.0, seconds, n))
    base_out, base_prompt = fixed.permutation(n), fixed.permutation(n)
    rng = np.random.default_rng(int(seed))
    out_len = np.empty(n, np.int64)
    prompt_len = np.empty(n, np.int64)
    out_len[_slots(base_out, rng)] = _quantile_lengths(params["output"], n)
    prompt_len[_slots(base_prompt, rng)] = \
        _quantile_lengths(params["prompt"], n)
    return _running(params, rate, vocab, rng) + [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompt_len[i]),
                                    dtype=np.int32),
             "max_new": int(out_len[i])} for i in range(n)]
