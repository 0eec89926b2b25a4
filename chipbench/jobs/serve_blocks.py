"""Job runner for serving cells of a family that generates by diffusion over
blocks (``deepspeed_tpu/models/sdar.py``): a pass of the tick advances a
slot's block of B columns, most passes deliver nothing and a block's last
unmasking pass delivers its tokens at once.

``setup``, ``warm`` and ``close`` are ``jobs/serve_arch.py``'s and the loop
that offers the load is ``jobs/serve.py``'s, lent as they are. ``measure``
adds what the blocks make of a stream: the PERIODS (the time between the
deliveries of two blocks of one request in a row) and the share of them in
which a prefill was sent, which says where ``itl_p95_ms`` lies: three of
four gaps between a request's tokens are inside a block (about 0), the
fourth is a period, so the 95th percentile of the gaps is the 80th of the
periods, and it must lie on the plain period, away from those a prefill
lengthens. ``check`` is this file's:

(a) ``engine.forward`` logits of seeded sequences against the named
    reference's under the block mask, as a relative RMS error: holds the
    precision.
(b) what the timed run itself streamed to a seeded sample of finished
    requests, every whole block of it, at each unmasking pass: the request
    carries beside its ids the pass of its block at which each was fixed
    (``Request.fixed_pass``), which gives the block as it stood at the start
    of every pass (the ids fixed before it, ``[MASK]`` at the rest). The
    reference is given the final tokens before each block and the block as
    it stood (``reference.logits_two_stream``: one forward of the noisy
    copy beside the clean one gives every block of the request at one pass;
    tests/chipbench/test_chipbench_sdar.py holds it equal to block-by-block
    forwards), in blocks of queries so that it fits. Every id the program
    fixed at that pass must be the reference's arg-max at its position to a
    margin (the gap over the row's largest |logit|: ``token_argmax_gap``),
    and the positions it fixed must be the reference's most confident of
    those still masked, to a margin (the most confident one it left masked
    over the least confident one it fixed, less 1: ``confidence_margin``).
    Holds the cache path, the ``[MASK]`` row, the unmasking rule and the
    writing pass.
(c) the same two numbers over a PROBE: a few requests of one to three
    prompt tokens, served after the drain by the server and the programs
    the window ran. A request of the traffic opens on 128 keys or more,
    among which the three keys of its own block that a causal mask would
    hide move the logits by less than bfloat16 rounds them; a probe's first
    blocks are most of the keys their positions see. Holds the block mask
    of the pass.

The limits of (b) and (c) lie between the sound runs and faults planted in
the program and read through this check on the chip (a causal mask in the
pass, an id in the ``[MASK]`` row's place, the least confident fixed, a
writing pass left out: ``tests/chipbench/test_chipbench_sdar.py:planted``;
PERF.md section 2).
"""

import time

import numpy as np

from chipbench import weights
from chipbench.jobs import serve_arch
from chipbench.jobs.serve_arch import close, warm            # noqa: F401


def setup(ctx):
    """``jobs/serve_arch.py:setup``, and the program's ring of phase records
    at the length the cell asks for (``phase_buffer_size``): a pass writes
    about twelve records, and a window of blocks with the drain behind it
    is read afterwards."""
    serve_arch.setup(ctx)
    if "phase_buffer_size" in ctx.cell:
        from deepspeed_tpu.telemetry import configure_tracer
        configure_tracer(phase_buffer_size=ctx.cell["phase_buffer_size"])


def _periods(ctx, t0, seconds):
    """[(start, end)] of every period inside the window: the delivery times
    (seconds on the window's clock) of two blocks of one request in a row."""
    b = ctx.dims["block_length"]
    st = ctx.state
    out = []
    for i, ts in st["stream"].times.items():
        at = len(st["requests"][i]["prompt"]) + np.arange(len(ts))
        first = np.flatnonzero(np.diff(at // b, prepend=-1) > 0)
        marks = np.asarray(ts)[first] - t0
        out.extend((a, z) for a, z in zip(marks, marks[1:])
                   if a >= 0 and z <= seconds)
    return out


def measure(ctx, seconds):
    """``jobs/serve_arch.py:measure``, then the periods: their median, the
    share of them in which a prefill was sent, and the 80th percentile
    (where ``itl_p95_ms`` lies) beside the smallest period that holds a
    prefill."""
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    t0 = time.perf_counter()
    record = serve_arch.measure(ctx, seconds)
    periods = _periods(ctx, t0, record["window_s"])
    sent = sorted(s * 1e-9 - t0 for n, s, _, _, _ in (
        tracer.phases() if hasattr(tracer, "phases") else ())
        if n == "serve/prefill_dispatch")
    lengths = np.array([z - a for a, z in periods]) * 1e3
    held = np.array([np.searchsorted(sent, z) > np.searchsorted(sent, a)
                     for a, z in periods], bool)
    if len(lengths):
        record.update(
            block_period_ms=float(np.median(lengths)),
            block_prefill_share=100.0 * float(held.mean()))
        ctx.log(f"{len(lengths)} block periods: p50 "
                f"{np.median(lengths):.3f} p80 "
                f"{np.percentile(lengths, 80):.3f} p95 "
                f"{np.percentile(lengths, 95):.3f} ms; "
                f"{100.0 * held.mean():.2f}% hold a prefill"
                + (f" (the shortest of them {lengths[held].min():.3f} ms)"
                   if held.any() else ""))
    return record


def _fixed_at(prompt, out, fixed, block):
    """[T] over the whole blocks of ``prompt + out``: the pass of its block
    at which each position was fixed; -1 at the prompt's."""
    n = (len(prompt) + len(out)) // block * block
    at = np.full(n, -1)
    at[len(prompt):] = fixed[:n - len(prompt)]
    return at


def _row_stats(rows, ids, vocab):
    """Of logits ``rows`` [T, vocab rows] (on the device, where they stay):
    each row's gap from its arg-max to ``ids``' logit over its largest
    |logit|, and its arg-max's soft-max probability; two [T] host arrays."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(rows, ids):
        rows = rows[:, :vocab]
        top = rows.max(-1)
        chosen = jnp.take_along_axis(rows, ids[:, None], axis=-1)[:, 0]
        return (top - chosen) / jnp.abs(rows).max(-1), \
            1.0 / jnp.exp(rows - top[:, None]).sum(-1)
    gap, conf = stats(rows, jnp.asarray(ids, jnp.int32))
    return np.asarray(gap), np.asarray(conf)


def _probe(ctx, rng):
    """[(prompt, streamed ids, the pass each was fixed at)] of the probe's
    requests (the check's ``probe``: their prompts' lengths, under a block
    each so that no prefill program is asked for, and their ``max_new``),
    served together by the window's own server."""
    from deepspeed_tpu.serving import SamplingParams
    srv, probe = ctx.state["srv"], ctx.cell["check"]["probe"]
    sent = []
    for n in probe["prompt_lengths"]:
        prompt = rng.integers(0, ctx.dims["vocab"], n, dtype=np.int32)
        sent.append((prompt, srv.submit(prompt, SamplingParams(
            max_new_tokens=probe["max_new"]))))
    srv.run_until_idle()
    got = [(prompt, srv.result(rid)) for prompt, rid in sent]
    return [(prompt, np.asarray(r.tokens, np.int32), np.asarray(r.fixed_pass))
            for prompt, r in got]


def _block_numbers(reference, w, dims, steps, pad_to, prompt, out, fixed):
    """(largest gap, largest margin, block passes compared) of one request:
    every whole block of ``prompt + out`` at each unmasking pass against
    ``reference.logits_two_stream`` over ``pad_to`` positions."""
    vocab, block = dims["vocab"], dims["block_length"]
    fixed_at = _fixed_at(prompt, out, fixed, block)
    n = len(fixed_at)
    seq = np.zeros(pad_to, np.int32)
    seq[:n] = np.concatenate([prompt, out])[:n]
    worst_gap, worst_margin, blocks = 0.0, 0.0, 0
    for p in range(steps):
        # the block as it stood at pass p: masked what was fixed then or
        # later; ``now`` what that pass fixed
        masked, now = fixed_at >= p, fixed_at == p
        if not now.any():
            continue
        flags = np.zeros(pad_to, bool)
        flags[:n] = masked
        gap, conf = (a[:n] for a in _row_stats(
            reference.logits_two_stream(w, seq, flags, dims), seq, vocab))
        worst_gap = max(worst_gap, float(gap[now].max()))
        for at in range(0, n, block):
            took, left = now[at:at + block], (masked & ~now)[at:at + block]
            if took.any() and left.any():
                worst_margin = max(worst_margin, float(
                    conf[at:at + block][left].max() /
                    conf[at:at + block][took].min() - 1.0))
            blocks += bool(took.any())
    return worst_gap, worst_margin, blocks


def check(ctx, record):
    import jax
    import jax.numpy as jnp
    st, lim, dims = ctx.state, ctx.cell["check"], ctx.dims
    reference, maker = serve_arch._named(ctx, "reference"), \
        serve_arch._named(ctx, "weights")
    vocab = dims["vocab"]
    steps = ctx.cell["serving"]["block_diffusion"]["denoising_steps"]
    rng = np.random.default_rng(ctx.args.seed + 2)
    b, t = lim["logits_shape"]
    ids = rng.integers(0, vocab, (b, t), dtype=np.int32)
    got = np.asarray(st["engine"].forward(ids), np.float32)[..., :vocab]
    multiple = st["model"].config.pad_vocab_to_multiple
    reqs, stream, srv = st["requests"], st["stream"], st["srv"]
    done = [i for i in range(len(reqs)) if stream.complete(i)]
    picks = rng.permutation(done)[:lim["token_requests"]]
    rid_of = {i: rid for rid, i in stream.index_of.items()}
    streamed = [(reqs[i]["prompt"], np.asarray(stream.tokens[i], np.int32),
                 np.asarray(srv.result(rid_of[i]).fixed_pass)) for i in picks]
    probed = _probe(ctx, rng)
    del srv                     # the pool goes with the engine
    serve_arch._release_engine(ctx)
    # in the type the weights are served in; the reference upcasts them
    dtype = jnp.dtype(ctx.cell["inference"]["dtype"])
    w = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(dtype),
        maker.make(dims, key, vocab_multiple=multiple)))(
            weights.seed_key(ctx.args.seed))

    num = den = 0.0
    for row, g in zip(ids, got):
        ref = np.asarray(reference.logits(w, row, dims))[:, :vocab]
        num += float(((g - ref) ** 2).sum())
        den += float((ref ** 2).sum())
    logits_err = float(np.sqrt(num / den))

    worst = []
    for what, sample, pad_to in (
            (f"{len(picks)} finished requests of {len(done)}", streamed,
             lim["reference_len"]),
            ("the probe", probed, lim["probe"]["reference_len"])):
        read = [_block_numbers(reference, w, dims, steps, pad_to, *one)
                for one in sample]
        worst.append((max((r[0] for r in read), default=0.0),
                      max((r[1] for r in read), default=0.0)))
        ctx.log(f"block check on {what}: {sum(r[2] for r in read)} block "
                f"passes, token_argmax_gap {worst[-1][0]!r} "
                f"confidence_margin {worst[-1][1]!r}")
    del w
    return [("logits_rel_rms_err", logits_err, lim["logits_rel_rms_err"]),
            ("token_argmax_gap", max(g for g, _ in worst),
             lim["token_argmax_gap"]),
            ("confidence_margin", max(m for _, m in worst),
             lim["confidence_margin"]),
            ("requests_checked_short", lim["token_requests"] - len(picks)
             if len(done) >= lim["token_requests"] else 0, 0),
            ("failed_requests", record["failed"], 0)]
