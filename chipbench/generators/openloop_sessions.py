"""Open-loop multi-turn chat sessions over a few shared system prompts: the
traffic of a chat product (a fixed system prompt, the conversation so far,
the new user turn), whose prompts share long prefixes with the lanes that
earlier requests left in the pool. It is what a prefix cache is for.

Parameters (a traffic file's keys):

- ``rate_per_s``: TURNS (requests) a second, nominal. Sessions start as a
  Poisson process of ``rate_per_s / mean turns`` a second from ``history_s``
  seconds before the window. The process is ONE fixed draw at unit rate,
  stretched to the rate asked for: another rate is the same sessions closer
  together, so what a window holds rises evenly with the rate.
- ``system_prompts``: ``{"count", "min", "max", "zipf"}``: lengths are the
  uniform quantiles of ``min ... max`` in a fixed shuffled order; a session
  takes prompt i with a probability proportional to ``(i + 1) ** -zipf``.
- ``turns``: ``{"min", "max"}``: turns a session, uniform; a session stops
  before the turn whose prompt and answer would pass ``max_model_len``.
- ``user``, ``answer``: ``{"median", "sigma", "min", "max"}``: lognormal
  lengths of a turn's user text and of its answer (the request's
  ``max_new``), quantiles of the distribution in a fixed shuffled order.
- ``think_s``: ``{"min", "max"}``, uniform. Turn k + 1 is due when turn k's
  answer has streamed at ``tick_ms`` a token from the moment turn k was DUE,
  plus the think time: fixed in advance, whatever the server does.
- ``ladder``: ``{"prefix", "min_suffix", "max_suffix"}``: see below.

Turn k's prompt is the system prompt, then for each earlier turn its user
text and a STAND-IN answer (seeded ids of the answer's length: what a client
sends back is fixed in advance, not what the server streamed), then user
text k. So turn k shares with turn k - 1's lane everything up to the end of
user text k - 1, and with every session of its system prompt that prompt.

The list opens on a pool in use (``due`` under 0; ``jobs/serve.py`` sends
those during set-up, in this order, and steps until each is admitted):

1. the ladder: one request of ``prefix`` tokens, then one for each power of
   two from ``min_suffix`` to ``max_suffix`` that shares the prefix and adds
   as many tokens of its own, two tokens of output each. Every suffix-prefill
   bucket a turn can run (the shortest user text to the longest history)
   and the lane copy are compiled in set-up, whatever a window reaches;
2. every turn that was due in the ``history_s`` seconds before the window,
   oldest first. One whose answer would have ended by then asks for
   ``DONOR_TOKENS`` tokens: its prefill and one decode step leave the lane in
   the prefix cache as the finished turn would have (what the cache matches
   of such a lane is its prompt: the stand-in answer is not the streamed
   one). One still streaming comes with the tokens streamed so far moved
   from its answer into its prompt.

What a seed changes: the token ids (the system prompts' too) and nothing
else. Sessions, lengths and due times are one fixed draw (``SCHEDULE_SEED``).
A seed that moved lengths among neighbours, as ``openloop_lognormal.py``
does, moved which suffix bucket a turn runs and which answers end inside the
window: 2.9% in the tokens delivered and 5.4% in the p95 gap over six seeds
on the chip (ledger and ``PERF.md``, PR 45), where a new cell may spread 1%.
"""

import numpy as np

from chipbench.generators.openloop_lognormal import _quantile_lengths

SCHEDULE_SEED = 46      # the one draw of sessions every seed and rate shares
POOL = 4096             # sessions and turns in the fixed draw
# the fewest tokens after which a request's lane is parked in the prefix
# cache: a request that ends on its prefill's token was never bound to its
# slot, and the scheduler frees such a lane (serving/scheduler.py)
DONOR_TOKENS = 2


def _fixed_draw(params):
    """Everything no seed and no rate changes, for ``POOL`` sessions."""
    fixed = np.random.default_rng(SCHEDULE_SEED)
    spec = params["system_prompts"]
    n = spec["count"]
    sys_len = np.rint(spec["min"] + (np.arange(n) + 0.5) / n *
                      (spec["max"] - spec["min"])).astype(np.int64)
    sys_len = sys_len[fixed.permutation(n)]
    p = (np.arange(n) + 1.0) ** -spec["zipf"]
    lo, hi = params["turns"]["min"], params["turns"]["max"]
    return {
        "sys_len": sys_len,
        "which": fixed.choice(n, size=POOL, p=p / p.sum()),
        "turns": fixed.integers(lo, hi + 1, POOL),
        "user": _quantile_lengths(params["user"], POOL)[
            fixed.permutation(POOL)],
        "answer": _quantile_lengths(params["answer"], POOL)[
            fixed.permutation(POOL)],
        "think": fixed.uniform(params["think_s"]["min"],
                               params["think_s"]["max"], POOL),
        "arrival": np.cumsum(fixed.exponential(1.0, POOL)),
    }


def sessions(params, seconds, rate):
    """The sessions that start before the window closes:
    ``{"system", "user": [lengths], "answer": [lengths], "due": [times]}``."""
    d = _fixed_draw(params)
    mean_turns = (params["turns"]["min"] + params["turns"]["max"]) / 2
    start = -params["history_s"] + d["arrival"] * mean_turns / rate
    if start[-1] < seconds:
        raise ValueError(f"the fixed draw of {POOL} sessions ends before the "
                         f"window does at {rate} turns a second")
    stream_s = params["tick_ms"] / 1e3
    out, at = [], 0
    for j in range(int(np.searchsorted(start, seconds))):
        k = int(d["turns"][j])
        pick = np.arange(at, at + k) % POOL
        at += k
        u, a, think = d["user"][pick], d["answer"][pick], d["think"][pick]
        # turn i's prompt: the system prompt, i earlier exchanges, user i
        prompt = d["sys_len"][d["which"][j]] + np.cumsum(u) + \
            np.concatenate([[0], np.cumsum(a)[:-1]])
        over = prompt + a > params["max_model_len"]
        keep = int(np.argmax(over)) if over.any() else k
        due = start[j] + np.concatenate(
            [[0.0], np.cumsum(a * stream_s + think)[:-1]])
        if keep:
            out.append({"system": int(d["which"][j]),
                        "user": u[:keep].tolist(), "answer": a[:keep].tolist(),
                        "due": due[:keep].tolist()})
    return out, d["sys_len"]


def _ladder(params, vocab, rng):
    spec = params["ladder"]
    shared = rng.integers(0, vocab, spec["prefix"], dtype=np.int32)
    steps = [np.zeros(0, np.int32)]
    b = spec["min_suffix"]
    while b <= spec["max_suffix"]:
        steps.append(rng.integers(0, vocab, b, dtype=np.int32))
        b *= 2
    first = -float(params["history_s"]) - len(steps)
    return [{"due": first + i, "prompt": np.concatenate([shared, own]),
             "max_new": DONOR_TOKENS, "ladder": True} for i, own in enumerate(steps)]


def generate(params, seed, vocab, seconds, rate=None):
    """A list of requests sorted by ``due`` (seconds after the window opens;
    under 0: sent in set-up, see above):
    ``{"due", "prompt" (int32 ids), "max_new"}``, and for a turn its
    ``session`` and ``turn``."""
    rate = params["rate_per_s"] if rate is None else rate
    drawn, sys_len = sessions(params, seconds, rate)
    rng = np.random.default_rng(int(seed))
    ids = lambda n: rng.integers(0, vocab, int(n), dtype=np.int32)
    system = [ids(n) for n in sys_len]
    stream_s = params["tick_ms"] / 1e3
    requests = []
    for j, s in enumerate(drawn):
        history = [system[s["system"]]]
        for k, due in enumerate(s["due"]):
            history.append(ids(s["user"][k]))
            answer = ids(s["answer"][k])
            if -params["history_s"] <= due < seconds:
                prompt, max_new = np.concatenate(history), s["answer"][k]
                if due < 0:
                    streamed = 1 + int(-due / stream_s)
                    if streamed + DONOR_TOKENS > max_new:   # ended by now
                        max_new = DONOR_TOKENS
                    else:
                        prompt = np.concatenate([prompt, answer[:streamed]])
                        max_new -= streamed
                requests.append({"due": float(due), "prompt": prompt,
                                 "max_new": int(max_new),
                                 "session": j, "turn": k})
            history.append(answer)
    requests.sort(key=lambda r: r["due"])
    return _ladder(params, vocab, rng) + requests
