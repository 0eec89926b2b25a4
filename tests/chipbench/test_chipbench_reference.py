"""The plain reference against the system at a size the CPU holds, for both
configurations (GELU / no offset, ReLU / offset 2): full forward in float32;
prefill and decode through the slot pool against the reference's full
forward; and the controls — the same comparison with the system in a lower
precision than stated, the reference in fp8, another optimizer step than the
cell's — come out as not correct."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference, weights                  # noqa: E402
from chipbench.model import (build, load_json, merge,    # noqa: E402
                             seeded_weights as _seeded)

SEED = 2**31 + 17
# float32 system against float32 reference: summation order only
F32_TOL = 2e-5
CELL = {"gpt2-medium": "gpt2-medium.train-z1", "opt-1.3b": "opt-1.3b.serve-chat"}


def tiny(config_name, **overrides):
    cell = load_json("workloads", CELL[config_name] + ".json")
    config = merge(load_json("configs", config_name + ".json"),
                   cell["rehearse"]["config"])
    model, dims = build(config, overrides)
    return model, dims


ESEED = weights.engine_seed(SEED)


def seeded_weights(model, dims):
    return dict(_seeded(model, dims, SEED))


def rel_rms(got, want):
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


def forward_error(config_name, dtype):
    import deepspeed_tpu
    model, dims = tiny(config_name, dtype=dtype)
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": dtype, "max_tokens": 64, "seed": ESEED})
    ids = np.random.default_rng(1).integers(0, dims["vocab"], (2, 48),
                                            dtype=np.int32)
    got = np.asarray(engine.forward(ids), np.float32)[..., :dims["vocab"]]
    w = seeded_weights(model, dims)
    want = np.stack([np.asarray(reference.logits(w, row, dims))
                     [:, :dims["vocab"]] for row in ids])
    return rel_rms(got, want)


@pytest.mark.parametrize("config_name", ["gpt2-medium", "opt-1.3b"])
def test_forward_matches_the_reference_in_float32(config_name):
    assert forward_error(config_name, "float32") < F32_TOL


@pytest.mark.parametrize("config_name", ["gpt2-medium", "opt-1.3b"])
def test_forward_in_lower_precision_than_stated_fails(config_name):
    assert forward_error(config_name, "bfloat16") > 10 * F32_TOL


def served_gap(config_name, break_offset=False):
    """Largest shortfall of a streamed token's reference logit below the
    row's arg-max, as a share of the row's largest |logit|."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    model, dims = tiny(config_name, dtype="float32")
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "max_tokens": 64, "seed": ESEED})
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64,
                                 "max_queue": 8})
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, dims["vocab"], n, dtype=np.int32)
               for n in (5, 16, 23)]
    out = {}
    rids = [srv.submit(p, SamplingParams(max_new_tokens=9),
                       on_token=lambda r, t: out.setdefault(
                           r.request_id, []).append(int(t))) for p in prompts]
    srv.run_until_idle()
    srv.shutdown()
    w = seeded_weights(model, dims)
    if break_offset:                      # a cache that is one position off
        dims = {**dims, "pos_offset": dims["pos_offset"] + 1}
        w["wpe"] = np.concatenate([w["wpe"], w["wpe"][:1]])
    worst = 0.0
    for rid, p in zip(rids, prompts):
        toks = np.asarray(out[rid], np.int32)
        assert len(toks) == 9
        seq = np.concatenate([p, toks])
        rows = np.asarray(reference.logits(w, seq, dims))[
            len(p) - 1:len(seq) - 1, :dims["vocab"]]
        gap = (rows.max(-1) - rows[np.arange(9), toks]) / np.abs(rows).max(-1)
        worst = max(worst, float(gap.max()))
    return worst


@pytest.mark.parametrize("config_name", ["gpt2-medium", "opt-1.3b"])
def test_prefill_then_decode_through_the_slot_pool(config_name):
    assert served_gap(config_name) < 1e-4
    assert served_gap(config_name, break_offset=True) > 1e-2


def _engine_step_losses(cell, model, batches, **optimizer):
    import deepspeed_tpu
    import jax
    config = merge(cell["engine"], {"optimizer": {"params": optimizer}})
    mesh = deepspeed_tpu.parallel.initialize_mesh(dp=1, devices=jax.devices()[:1])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=dict(config, seed=ESEED), mesh_manager=mesh)
    return [float(engine.train_batch(batch={"input_ids": b})) for b in batches]


def _step_rms(got, ref):
    return float(np.sqrt(np.mean([((a - b) / b) ** 2 for a, b in zip(got, ref)])))


def test_train_steps_against_the_reference_adamw_and_its_controls():
    """The training cells compare the losses of the first steps with the
    reference's own AdamW steps. The bf16 system stays under the rehearsal's
    limit; the reference in fp8 (the step below the stated bf16, backward
    pass too) does not; nor does the system with another optimizer step than
    the cell states (here twice the cell's learning rate)."""
    cell = load_json("workloads", "gpt2-medium.train-z1.json")
    cell = merge(cell, cell["rehearse"]["cell"])
    limit = cell["check"]["step_loss_rel_rms_err"]
    opt = cell["engine"]["optimizer"]["params"]
    model, dims = tiny("gpt2-medium", **cell["model_overrides"])
    rng = np.random.default_rng(3)
    p = 1.0 / np.arange(1, dims["vocab"] + 1)
    batches = rng.choice(dims["vocab"], size=(4, 2, 2, 128),
                         p=p / p.sum()).astype(np.int32)
    w = seeded_weights(model, dims)
    ref = reference.train_losses(w, batches, dims, opt)
    got = _engine_step_losses(cell, model, batches)
    assert _step_rms(got, ref) < limit
    control = reference.train_losses(w, batches, dims, opt, quant=reference.fp8)
    assert _step_rms(control, ref) > limit
    other = _engine_step_losses(cell, model, batches, lr=2 * opt["lr"])
    assert _step_rms(other, ref) > limit


def test_reference_adamw_is_the_published_update():
    """One step on one small tensor by hand: decoupled decay, bias-corrected
    moments, epsilon outside the root."""
    import jax
    import jax.numpy as jnp
    dims = {"layers": 1, "d_model": 16, "heads": 2, "d_ff": 32, "vocab": 40,
            "positions": 8, "pos_offset": 0, "activation": "relu",
            "ln_eps": 1e-5}
    w = weights.make(dims, jax.random.PRNGKey(0), vocab_multiple=8)
    ids = np.arange(16, dtype=np.int32).reshape(2, 8)
    opt = {"lr": 1e-2, "weight_decay": 0.1, "betas": (0.9, 0.999), "eps": 1e-8}

    def mean_loss(w):
        with jax.default_matmul_precision("highest"):
            return sum(reference._sequence_nll(w, jnp.asarray(r), dims, None)
                       for r in ids) / (2 * 7)
    g = jax.grad(mean_loss)(w)
    # first step: m/(1-b1) = g and v/(1-b2) = g*g, so the update is g/(|g|+eps)
    stepped = jax.tree.map(lambda p, g: p - opt["lr"] * (
        g / (jnp.abs(g) + opt["eps"]) + opt["weight_decay"] * p), w, g)
    want = float(mean_loss(stepped))
    got = reference.train_losses(w, [ids, ids], dims, opt)
    assert abs(got[0] - float(mean_loss(w))) < 1e-5
    assert abs(got[1] - want) < 1e-5 and abs(got[1] - got[0]) > 1e-3
