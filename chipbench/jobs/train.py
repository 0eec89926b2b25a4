"""Job runner for training cells: ``deepspeed_tpu.initialize`` → ``train_batch``.

The cell's file gives the engine's DeepSpeed config (``engine``), the
program's model-config overrides (``model_overrides``) and the data-parallel
width of the mesh; the traffic file gives the batch geometry. The program is
handed the model object and batches, nothing else.
"""

import time

import numpy as np

from chipbench import reference, weights
from chipbench.model import build, seeded_weights


def setup(ctx):
    import deepspeed_tpu
    cell, seed = ctx.cell, ctx.args.seed
    model, dims = build(ctx.config, cell.get("model_overrides"))
    mesh = deepspeed_tpu.parallel.initialize_mesh(dp=len(ctx.devices),
                                                  devices=ctx.devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh_manager=mesh,
        config=dict(cell["engine"], seed=weights.engine_seed(seed)))
    t = ctx.traffic
    st = ctx.state
    ctx.dims = dims
    st.update(engine=engine, model=model,
              tokens_per_step=t["gas"] * t["rows"] * t["seq"],
              batches=ctx.generator.generate(t, seed, dims["vocab"],
                                             ctx.args.seconds))


def _eval_groups(ctx):
    """``eval_groups`` micro-batches of ``eval_rows`` sequences of uniform
    tokens from the seed. Uniform, not Zipf: a few frequent targets would
    tie every group's error to the same few logits, and the groups' errors
    have to be independent for their RMS to be steady."""
    lim = ctx.cell["check"]
    rng = np.random.default_rng(ctx.args.seed + 3)
    return rng.integers(0, ctx.dims["vocab"], (
        lim["eval_groups"], lim["eval_rows"], ctx.traffic["seq"]),
        dtype=np.int32)


def warm(ctx):
    """On the seeded weights, before any update: the evaluation loss of a
    few small groups of sequences (``engine.eval_batch``), which ``check``
    compares one by one. Then the traffic's ``warm_batches`` training steps:
    the first compiles (or reads the cache); every one runs the train
    program the window runs, from the seeded weights on, and ``check`` sets
    their losses beside the reference's own AdamW steps."""
    import jax
    st = ctx.state
    st["eval_losses"] = [float(st["engine"].eval_batch({"input_ids": g}))
                         for g in _eval_groups(ctx)]
    n = ctx.traffic["warm_batches"]
    st["warm_losses"] = [float(st["engine"].train_batch(batch=b))
                         for b in st["batches"][:n]]
    jax.block_until_ready(st["engine"].params)
    st["next"] = n


def measure(ctx, seconds):
    """Steps for ``seconds``. The host waits for step i-1's loss before it
    sends step i+1, so at most two steps are ever queued, the window closes
    within a step of its length and every step leaves a host timestamp
    (today ``train_batch`` reads a value back itself and the wait is idle)."""
    import jax
    st = ctx.state
    engine, batches = st["engine"], st["batches"]
    losses, stamps = [], []
    i = st["next"]
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ctx.span("train_batch"):
            losses.append(engine.train_batch(batch=batches[i % len(batches)]))
        i += 1
        if len(losses) >= 2:
            with ctx.span("wait_loss"):
                jax.block_until_ready(losses[-2])
            stamps.append(time.perf_counter())
    with ctx.span("wait_params"):
        jax.block_until_ready(engine.params)
    elapsed = time.perf_counter() - t0
    steps = len(losses)
    losses = [float(x) for x in losses]
    gaps = np.diff(stamps) if len(stamps) > 1 else np.zeros(1)
    ctx.log(f"{steps} steps in {elapsed:.3f}s; losses first/last "
            f"{losses[0]:.4f}/{losses[-1]:.4f}; step gap median "
            f"{np.median(gaps) * 1e3:.1f} ms, slowest {gaps.max() * 1e3:.1f} "
            f"ms at step {int(gaps.argmax()) + 2}, "
            f"{int((gaps > 1.5 * np.median(gaps)).sum())} over 1.5x median")
    return {"train_tokens_per_s": steps * st["tokens_per_step"] / elapsed,
            "steps": steps, "elapsed_s": elapsed, "losses": losses,
            "step_stamps": stamps, "attempted": steps,
            "failed": int(sum(not np.isfinite(x) for x in losses))}


def _release_engine(ctx):
    """Drop the engine and everything it holds on the device: the reference's
    AdamW steps need the room."""
    import gc
    engine = ctx.state.pop("engine", None)
    if engine is not None:
        engine.close()
    del engine
    gc.collect()


def check(ctx, record):
    """Against the plain float32 reference from the same seed.

    ``step_loss_rel_rms_err`` holds the train program: the losses its first
    ``warm_batches`` steps returned, from the seeded weights on, against the
    reference's losses on the same batches under its own AdamW steps, as the
    RMS of the relative errors. A step's loss depends on the gradients and
    the optimizer step of every step before it: on the chip the loss falls
    by a tenth over those steps, so an update 0.1% off in size shows.
    ``eval_loss_rel_rms_err`` holds the forward pass on the seeded weights
    with independent errors (uniform tokens, one group at a time). Every
    loss of the run is finite."""
    import jax
    st, lim, dims = ctx.state, ctx.cell["check"], ctx.dims
    losses = st["warm_losses"] + record["losses"]
    _release_engine(ctx)
    warm = [b["input_ids"] for b in st["batches"][:len(st["warm_losses"])]]
    quant = reference.fp8 if ctx.args.control else None
    with jax.default_device(ctx.devices[0]):
        w = seeded_weights(st["model"], dims, ctx.args.seed)
        ref = reference.train_losses(w, warm, dims, _optimizer(ctx.cell))
        got = reference.train_losses(w, warm, dims, _optimizer(ctx.cell),
                                     quant) if quant else st["warm_losses"]
        errs = []
        for g, sys_loss in zip(_eval_groups(ctx), st["eval_losses"]):
            r = reference.loss(w, g, dims)
            c = reference.loss(w, g, dims, quant) if quant else sys_loss
            errs.append((c - r) / r)
        del w
    ctx.log("step losses " + " ".join(f"{x:.6f}" for x in got)
            + "; reference " + " ".join(f"{x:.6f}" for x in ref)
            + "; eval errors " + " ".join(f"{e:+.2e}" for e in errs))
    rms = lambda xs: float(np.sqrt(np.mean(np.square(xs))))
    step_rms = rms([(a - b) / b for a, b in zip(got, ref)])
    return [("step_loss_rel_rms_err", step_rms, lim["step_loss_rel_rms_err"]),
            ("eval_loss_rel_rms_err", rms(errs), lim["eval_loss_rel_rms_err"]),
            ("nonfinite_losses",
             int(sum(not np.isfinite(x) for x in losses)), 0)]


def _optimizer(cell):
    """The cell's optimizer as the reference reads it: AdamW only."""
    opt = cell["engine"]["optimizer"]
    if opt["type"].lower() != "adamw":
        raise SystemExit(f"chipbench: the reference has no {opt['type']} step")
    return opt["params"]


def close(ctx):
    ctx.state.clear()
