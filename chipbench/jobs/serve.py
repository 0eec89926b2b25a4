"""Job runner for serving cells: ``init_inference`` → ``ServingEngine``.

One thread offers the load and drives the engine, as the program's own
``bin/ds_tpu_serve`` loop does: every request that has come due is submitted,
then ``srv.step()`` runs one tick. The loop is open: a request's clock starts
when it was DUE, whether or not the loop was free to send it then, and how
late the loop sent it is reported (``generator_lag_ms``). After the window no
request arrives; the drain that lets the window's requests finish is outside
the timed seconds. Where the traffic says so, the window opens on a pool
already in use: the requests a steady stream would have left running are sent
during set-up (``warm``).

A traced run of a cell whose file gives ``trace_ticks`` closes its window
after that many ticks where they come before ``seconds`` have passed, so that
what the trace costs to stop, load and reduce is the cell's and does not grow
with the speed of the program under test: the timed seconds end there, and a
request due later is never sent and enters no count. An untraced run does not
read the key.
"""

import time

import numpy as np

from chipbench import reference, weights
from chipbench.model import build, seeded_weights


def setup(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    cell, seed = ctx.cell, ctx.args.seed
    model, dims = build(ctx.config, cell.get("model_overrides"))
    ctx.dims = dims
    inference = dict(cell["inference"], seed=weights.engine_seed(seed))
    if ctx.args.control:
        inference["dtype"] = "int8"     # the program's own lower precision
    engine = deepspeed_tpu.init_inference(model, config=inference)
    srv = ServingEngine(engine, dict(cell["serving"]))
    ctx.state.update(
        model=model, engine=engine, srv=srv,
        requests=ctx.generator.generate(ctx.traffic, seed, dims["vocab"],
                                        ctx.args.seconds, rate=ctx.args.rate))


class _Stream:
    """What the one loop keeps about every request it has sent: the times and
    ids of its tokens, by the request's index in the generated list."""

    def __init__(self, ctx):
        self.ctx, self.srv, self.reqs = ctx, ctx.state["srv"], ctx.state["requests"]
        self.times, self.tokens = {}, {}    # index -> [token times], [ids]
        self.live = {}                      # index -> tokens in its cache lane
        self.index_of, self.refused = {}, set()

    def on_token(self, req, tok):
        i = self.index_of[req.request_id]
        self.times[i].append(time.perf_counter())
        self.tokens[i].append(int(tok))
        n = len(self.tokens[i])
        if n >= self.reqs[i]["max_new"]:
            self.live.pop(i, None)
        else:
            self.live[i] = len(self.reqs[i]["prompt"]) + n

    def submit(self, i):
        from deepspeed_tpu.serving import SamplingParams
        r = self.reqs[i]
        self.times[i], self.tokens[i] = [], []
        try:
            with self.ctx.span("submit"):
                rid = self.srv.submit(
                    r["prompt"], SamplingParams(max_new_tokens=r["max_new"]),
                    on_token=self.on_token)
            self.index_of[rid] = i
        except Exception as e:                     # refused: counts failed
            self.ctx.log(f"request {i} refused: {e!r}")
            self.refused.add(i)

    def complete(self, i):
        return i not in self.refused and \
            len(self.tokens.get(i, ())) == self.reqs[i]["max_new"]


def warm(ctx):
    """One request per prefill bucket the traffic can hit, and with them the
    pool and the decode tick; nothing else. Then the traffic's requests that
    were already running when the window opens (``due`` under 0) are sent and
    stepped until each holds a slot, so the window opens on a pool in use."""
    from deepspeed_tpu.serving import SamplingParams
    st = ctx.state
    srv = st["srv"]
    rng = np.random.default_rng(ctx.args.seed + 1)
    for n in ctx.cell["warm_prompt_lengths"]:
        srv.submit(rng.integers(0, ctx.dims["vocab"], n, dtype=np.int32),
                   SamplingParams(max_new_tokens=3))
        srv.run_until_idle()
    st["stream"] = stream = _Stream(ctx)
    running = [i for i, r in enumerate(st["requests"]) if r["due"] < 0]
    for i in running:
        stream.submit(i)
    while srv.queue_depth:
        srv.step()
    st["first_due"] = len(running)
    ctx.log(f"{len(running)} requests running as the window opens, "
            f"{sum(stream.live.values())} tokens in their lanes")


def measure(ctx, seconds):
    st = ctx.state
    srv, reqs, stream = st["srv"], st["requests"], st["stream"]
    slots = ctx.cell["serving"]["num_slots"]
    times, tokens = stream.times, stream.tokens
    lag, ticks, tick_at, occupancy, live_tokens = [], [], [], [], []
    backlog_mid = None
    clock = time.perf_counter
    # a traced window is bounded in ticks too; None is never a tick count
    tick_limit = ctx.cell.get("trace_ticks") if ctx.args.trace else None
    reached = None if tick_limit is None else False

    def unfinished(sent):
        return sum(1 for i in range(sent) if i not in stream.refused
                   and len(tokens[i]) < reqs[i]["max_new"])

    t0 = clock()
    nxt = st["first_due"]
    t_drain_end = t0 + seconds + ctx.cell["drain_seconds"]
    while True:
        now = clock()
        while nxt < len(reqs) and t0 + reqs[nxt]["due"] <= now:
            stream.submit(nxt)
            lag.append(clock() - (t0 + reqs[nxt]["due"]))
            nxt += 1
        if len(ticks) == tick_limit and now - t0 < seconds:
            seconds, reached = now - t0, True   # the window closes, below
            del reqs[nxt:]          # due later: never sent, in no count
            t_drain_end = now + ctx.cell["drain_seconds"]
        if backlog_mid is None and now - t0 >= seconds / 2:
            backlog_mid = unfinished(nxt)
        if now - t0 >= seconds and nxt >= len(reqs):
            ctx.end_window()        # the drain is outside the traced window
        if srv.queue_depth or srv.active_requests:
            live_tokens.append(sum(stream.live.values()))
            t1 = clock()
            with ctx.span("step"):
                srv.step()
            ticks.append(clock() - t1)
            tick_at.append(t1 - t0)
            occupancy.append(srv.active_requests / slots)
        elif nxt >= len(reqs):
            break
        else:
            with ctx.span("generator_sleep"):
                time.sleep(max(0.0, min(0.005, t0 + reqs[nxt]["due"] - clock())))
        if clock() > t_drain_end:
            ctx.log("drain limit reached with requests unfinished")
            break
    t_end = clock()
    t_close = t0 + seconds
    backlog_end = sum(
        1 for i in range(len(reqs)) if i not in stream.refused and
        (not stream.complete(i) or times[i][-1] > t_close))

    ttft, gaps, delivered = [], [], 0
    failed = sum(1 for i in range(len(reqs)) if not stream.complete(i))
    for i, r in enumerate(reqs):
        ts = times.get(i, [])
        delivered += sum(1 for t in ts if t0 < t <= t_close)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b > t0)
        if r["due"] >= 0:
            ttft.append(ts[0] - (t0 + r["due"]) if stream.complete(i) else None)
    worst = max([x for x in ttft if x is not None], default=t_end - t0)
    ttft = [worst if x is None else x for x in ttft]    # failed: the largest
    in_window = [k for k, t in enumerate(tick_at) if t < seconds]
    pct = lambda xs, q: float(np.percentile(xs, q)) * 1e3 if xs else None
    ctx.log(f"{len(reqs)} requests ({st['first_due']} running at the start), "
            f"{failed} failed, {delivered} tokens in the window, {len(gaps)} "
            f"gaps, {len(in_window)} ticks in the window of {len(ticks)}; "
            f"backlog mid/end {backlog_mid}/{backlog_end}; "
            f"drain {t_end - t_close:.2f}s")
    slow = sorted(range(len(ticks)), key=lambda k: -ticks[k])[:3]
    ctx.log("slowest ticks: " + ", ".join(
        f"{ticks[k] * 1e3:.0f} ms at {tick_at[k]:.1f}s with "
        f"{occupancy[k] * slots:.0f} active" for k in slow))
    ctx.log(f"ttft p50/p95 {pct(ttft, 50):.1f}/{pct(ttft, 95):.1f} ms over "
            f"{len(ttft)}  itl p50/p95 {pct(gaps, 50):.2f}/{pct(gaps, 95):.2f}"
            f" ms  generator lag p95 {pct(lag, 95):.2f} ms")
    return {"window_s": seconds, "trace_ticks_reached": reached,
            "serve_tokens_per_s": delivered / seconds,
            "ttft_p95_ms": pct(ttft, 95), "itl_p95_ms": pct(gaps, 95),
            "ttft_p50_ms": pct(ttft, 50), "itl_p50_ms": pct(gaps, 50),
            "generator_lag_ms": pct(lag, 95),
            "ticks": [ticks[k] for k in in_window],
            "occupancy": [occupancy[k] for k in in_window],
            "live_tokens": [live_tokens[k] for k in in_window
                            if live_tokens[k]],
            "vocab_rows": weights.table_rows(
                ctx.dims, st["model"].config.pad_vocab_to_multiple),
            "backlog_mid": backlog_mid, "backlog_end": backlog_end,
            "attempted": len(reqs), "failed": failed}


def check(ctx, record):
    """(a) ``engine.forward`` logits of seeded sequences against the plain
    reference's, as a relative RMS error: holds the precision. (b) every
    token streamed to a seeded sample of finished requests is the arg-max of
    the reference's teacher-forced logits over its own prefix, to a margin
    stated as a share of the row's largest |logit|: holds the cache path."""
    import jax.numpy as jnp
    st, lim = ctx.state, ctx.cell["check"]
    dims = ctx.dims
    vocab, pad_to = dims["vocab"], lim["reference_len"]
    rng = np.random.default_rng(ctx.args.seed + 2)
    # in the type the weights are served in; the reference upcasts them
    w = seeded_weights(st["model"], dims, ctx.args.seed,
                       jnp.dtype(ctx.cell["inference"]["dtype"]))

    def ref_logits(ids):
        padded = np.zeros(pad_to, np.int32)
        padded[:len(ids)] = ids
        return np.asarray(reference.logits(w, padded, dims))[:len(ids), :vocab]

    b, t = lim["logits_shape"]
    ids = rng.integers(0, vocab, (b, t), dtype=np.int32)
    got = np.asarray(st["engine"].forward(ids), np.float32)[..., :vocab]
    num = den = 0.0
    for row, g in zip(ids, got):
        ref = ref_logits(row)
        num += float(((g - ref) ** 2).sum())
        den += float((ref ** 2).sum())
    logits_err = float(np.sqrt(num / den))

    reqs, tokens = st["requests"], st["stream"].tokens
    done = [i for i in range(len(reqs)) if st["stream"].complete(i)]
    worst_gap = 0.0
    picks = rng.permutation(done)[:lim["token_requests"]]
    for i in picks:
        prompt, out = reqs[i]["prompt"], np.asarray(tokens[i], np.int32)
        seq = np.concatenate([prompt, out])
        rows = ref_logits(seq)[len(prompt) - 1:len(seq) - 1]
        chosen = rows[np.arange(len(out)), out]
        gap = (rows.max(-1) - chosen) / np.abs(rows).max(-1)
        worst_gap = max(worst_gap, float(gap.max()))
    ctx.log(f"token check on {len(picks)} finished requests of {len(done)}")
    return [("logits_rel_rms_err", logits_err, lim["logits_rel_rms_err"]),
            ("token_argmax_gap", worst_gap, lim["token_argmax_gap"]),
            ("requests_checked_short", lim["token_requests"] - len(picks)
             if len(done) >= lim["token_requests"] else 0, 0),
            ("failed_requests", record["failed"], 0)]


def close(ctx):
    ctx.state["srv"].shutdown()
    ctx.state.clear()
