"""Job runner for training cells whose model state fits no single chip.

``jobs/train.py``'s set-up, warm-up and window, lent as they are (its mesh
is data-parallel over the cell's devices already). Only ``check`` differs:
``train.py`` makes the seeded float32 weights and the reference's AdamW
state on ``devices[0]``; a float32 reference of 1.3 B parameters with its
gradients and two moments is 21 GB, so here the weights are made SHARDED
over the cell's devices (``weights.make`` under ``jit`` with
``out_shardings``: each leaf split along its last axis that the device
count divides) and ``reference.train_losses`` runs on them unchanged: its
moments and gradients are made from the weights and follow their sharding.
The numbers compared and their meaning are ``train.py``'s.
"""

import numpy as np

from chipbench import reference, weights
from chipbench.jobs import train
from chipbench.jobs.train import close, measure, warm        # noqa: F401


def setup(ctx):
    # the training readers of ``layer_metrics/`` ask for the job "train":
    # this is one, under another module's name
    ctx.cell = dict(ctx.cell, job="train")
    train.setup(ctx)


def _sharded_weights(ctx):
    """The seeded float32 weights, each leaf split over the cell's devices
    along its last axis the device count divides (replicated if none)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg, dims = ctx.state["model"].config, ctx.dims
    n = len(ctx.devices)
    mesh = Mesh(np.array(ctx.devices), ("ref",))

    def make(key):
        return weights.make(dims, key, positions=cfg.n_positions,
                            vocab_multiple=cfg.pad_vocab_to_multiple)

    def split(leaf):
        axes = [a for a in range(leaf.ndim) if leaf.shape[a] % n == 0]
        spec = [None] * leaf.ndim
        if axes and n > 1:
            spec[axes[-1]] = "ref"
        return NamedSharding(mesh, P(*spec))
    key = weights.seed_key(ctx.args.seed)
    shardings = jax.tree.map(split, jax.eval_shape(make, key))
    return jax.jit(make, out_shardings=shardings)(key)


def check(ctx, record):
    """``jobs/train.py:check`` on sharded reference state."""
    st, lim, dims = ctx.state, ctx.cell["check"], ctx.dims
    losses = st["warm_losses"] + record["losses"]
    train._release_engine(ctx)
    warm_ids = [b["input_ids"] for b in st["batches"][:len(st["warm_losses"])]]
    quant = reference.fp8 if ctx.args.control else None
    opt = train._optimizer(ctx.cell)
    w = _sharded_weights(ctx)
    ref = reference.train_losses(w, warm_ids, dims, opt)
    got = reference.train_losses(w, warm_ids, dims, opt, quant) \
        if quant else st["warm_losses"]
    errs = []
    for g, sys_loss in zip(train._eval_groups(ctx), st["eval_losses"]):
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
