"""Job runner for serving cells of another architecture than GPT-2's.

``jobs/serve.py`` imports GPT-2's weights maker and GPT-2's reference by
name. A configuration of another architecture names its own in its file:

    "benchmark": {"weights":   "<module with make(dims, key, ...)>",
                  "reference": "<module with logits(weights, ids, dims, quant)>",
                  "counts":    "<module the per-layer readers use>"}

and this runner takes them from there. The loop that offers the load,
drives the engine and times the tokens is ``jobs/serve.py``'s own
(``warm``, ``measure``), lent as it is, with one log line after it;
``setup`` and ``check`` are this file's. The check is ``serve.py``'s two numbers against the named
reference, in another order: the program's logits are read first, then the
engine, its weights and its pool are released, and only then are the
seeded weights made again for the reference — a model that fills the chip
leaves no room for a second copy beside the first.
"""

import gc
import importlib
import time

import numpy as np

from chipbench import weights
from chipbench.jobs import serve
from chipbench.jobs.serve import warm        # noqa: F401
from chipbench.model import by_name


def _named(ctx, part):
    return importlib.import_module(ctx.config["benchmark"][part])


def _build(ctx):
    """The program's model whose ``init(rng)`` is the named weights maker."""
    prog, dims = ctx.config["program"], ctx.config["dims"]
    cfg = by_name(prog["config"])(**{**prog["kwargs"],
                                     **(ctx.cell.get("model_overrides") or {})})
    cls, maker = by_name(prog["model"]), _named(ctx, "weights")
    multiple = cfg.pad_vocab_to_multiple

    class Seeded(cls):
        def init(self, rng):
            return maker.make(dims, rng, vocab_multiple=multiple)

    Seeded.__name__ = cls.__name__
    return Seeded(cfg), dims


def setup(ctx):
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    # the serving readers of ``layer_metrics/`` ask for the job "serve":
    # this is one, under another module's name
    ctx.cell = dict(ctx.cell, job="serve")
    cell, seed = ctx.cell, ctx.args.seed
    model, dims = _build(ctx)
    ctx.dims = dims
    ctx.counts = _named(ctx, "counts")
    inference = dict(cell["inference"], seed=weights.engine_seed(seed))
    if ctx.args.control:
        inference["dtype"] = "int8"     # the program's own lower precision
    engine = deepspeed_tpu.init_inference(model, config=inference)
    srv = ServingEngine(engine, dict(cell["serving"]))
    ctx.state.update(
        model=model, engine=engine, srv=srv,
        requests=ctx.generator.generate(ctx.traffic, seed, dims["vocab"],
                                        ctx.args.seconds, rate=ctx.args.rate))


def measure(ctx, seconds):
    """``jobs/serve.py:measure``, then one line every run, traced or not: the
    medians of the program's own phase records over the timed seconds, so a
    run whose ticks are all longer says whether the device's program
    (``decode_wait``) or the host's share took the time."""
    t0 = time.perf_counter_ns()
    record = serve.measure(ctx, seconds)
    _log_phase_medians(ctx, t0, t0 + int(record["window_s"] * 1e9))
    return record


def _log_phase_medians(ctx, lo_ns, hi_ns):
    from deepspeed_tpu.telemetry import get_tracer
    tracer = get_tracer()
    if not hasattr(tracer, "phases"):       # a program without the records
        return
    spent = {}
    for name, start_ns, end_ns, _, _ in tracer.phases():
        if lo_ns <= start_ns and end_ns <= hi_ns and end_ns > start_ns:
            spent.setdefault(name, []).append((end_ns - start_ns) * 1e-6)
    ctx.log(f"phase medians ms over {len(spent.get('serve/tick', ()))} "
            f"ticks: " + ", ".join(f"{k} {float(np.median(v)):.3f}"
                                   for k, v in sorted(spent.items())))


def _release_engine(ctx):
    """Shut the server down and drop the engine with its weights and pool."""
    st = ctx.state
    srv = st.pop("srv", None)
    if srv is not None:
        srv.shutdown()
    if "stream" in st:
        st["stream"].srv = None
    engine = st.pop("engine", None)
    if engine is not None:
        engine.params = None
        engine._slot_fns.clear()
        engine._fns.clear()
    del srv, engine
    gc.collect()
    in_use = (ctx.devices[0].memory_stats() or {}).get("bytes_in_use")
    ctx.log(f"engine released; bytes in use on device 0: {in_use}")


def check(ctx, record):
    """``jobs/serve.py:check``'s two numbers, against the named reference:
    (a) ``engine.forward`` logits of seeded sequences as a relative RMS
    error: holds the precision; (b) every token streamed to a seeded sample
    of finished requests against the reference's teacher-forced logits over
    its own prefix, as the gap to the row's arg-max over the row's largest
    |logit|: holds the cache path."""
    import jax
    import jax.numpy as jnp
    st, lim, dims = ctx.state, ctx.cell["check"], ctx.dims
    reference, maker = _named(ctx, "reference"), _named(ctx, "weights")
    vocab, pad_to = dims["vocab"], lim["reference_len"]
    rng = np.random.default_rng(ctx.args.seed + 2)
    b, t = lim["logits_shape"]
    ids = rng.integers(0, vocab, (b, t), dtype=np.int32)
    got = np.asarray(st["engine"].forward(ids), np.float32)[..., :vocab]
    multiple = st["model"].config.pad_vocab_to_multiple
    _release_engine(ctx)
    # in the type the weights are served in; the reference upcasts them
    dtype = jnp.dtype(ctx.cell["inference"]["dtype"])
    w = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(dtype),
        maker.make(dims, key, vocab_multiple=multiple)))(
            weights.seed_key(ctx.args.seed))

    def ref_logits(seq):
        padded = np.zeros(pad_to, np.int32)
        padded[:len(seq)] = seq
        return np.asarray(reference.logits(w, padded, dims))[:len(seq), :vocab]

    num = den = 0.0
    for row, g in zip(ids, got):
        ref = ref_logits(row)
        num += float(((g - ref) ** 2).sum())
        den += float((ref ** 2).sum())
    logits_err = float(np.sqrt(num / den))

    reqs, stream = st["requests"], st["stream"]
    done = [i for i in range(len(reqs)) if stream.complete(i)]
    worst_gap = 0.0
    picks = rng.permutation(done)[:lim["token_requests"]]
    for i in picks:
        prompt, out = reqs[i]["prompt"], np.asarray(stream.tokens[i], np.int32)
        seq = np.concatenate([prompt, out])
        rows = ref_logits(seq)[len(prompt) - 1:len(seq) - 1]
        chosen = rows[np.arange(len(out)), out]
        gap = (rows.max(-1) - chosen) / np.abs(rows).max(-1)
        worst_gap = max(worst_gap, float(gap.max()))
    del w
    ctx.log(f"token check on {len(picks)} finished requests of {len(done)}")
    return [("logits_rel_rms_err", logits_err, lim["logits_rel_rms_err"]),
            ("token_argmax_gap", worst_gap, lim["token_argmax_gap"]),
            ("requests_checked_short", lim["token_requests"] - len(picks)
             if len(done) >= lim["token_requests"] else 0, 0),
            ("failed_requests", record["failed"], 0)]


def close(ctx):
    _release_engine(ctx)
    ctx.state.clear()
