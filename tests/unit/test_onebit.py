"""1-bit optimizer + compressed collective tests (reference tests/onebit):
sign/int8 collectives under shard_map vs the exact pmean oracle, the
warmup→compression state machine, and end-to-end engine training."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.compressed_collectives import (exact_allreduce_mean,
                                                      int8_allreduce,
                                                      onebit_allreduce)

TINY = GPT2Config(vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
                  n_head=4, pad_vocab_to_multiple=8)


def _mesh8():
    from deepspeed_tpu.parallel import initialize_mesh
    return initialize_mesh(dp=8).mesh


# ---------------------------------------------------- compressed collectives
def test_int8_allreduce_close_to_exact():
    mesh = _mesh8()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 64)), dtype=jnp.float32)

    fn = shard_map(lambda v: int8_allreduce(v.reshape(-1), "data"),
                   mesh=mesh, in_specs=P("data", None),
                   out_specs=P("data"))
    out = np.asarray(fn(x)).reshape(8, 64)[0]
    exact = np.mean(np.asarray(x), axis=0)
    # int8 two-leg quantization: ~1% of dynamic range
    assert np.max(np.abs(out - exact)) < 0.05 * np.max(np.abs(x))


def test_onebit_allreduce_error_feedback_converges():
    """Single-shot sign compression is coarse; with persistent error
    feedback the ACCUMULATED output tracks the accumulated exact mean —
    the property 1-bit Adam relies on."""
    mesh = _mesh8()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 64)), dtype=jnp.float32)

    def step(v, werr, serr):
        return onebit_allreduce(v.reshape(-1), werr, serr, "data")

    fn = shard_map(step, mesh=mesh,
                   in_specs=(P("data", None), P("data"), P("data")),
                   out_specs=(P("data"), P("data"), P("data")))
    werr = jnp.zeros((8 * 64,))
    serr = jnp.zeros((8 * 8,))
    acc = np.zeros(64)
    T = 30
    for _ in range(T):
        out, werr, serr = fn(x, werr, serr)
        acc += np.asarray(out).reshape(8, 64)[0]
    exact = np.mean(np.asarray(x), axis=0)
    err = np.abs(acc / T - exact).mean() / (np.abs(exact).mean() + 1e-9)
    assert err < 0.15, err  # time-averaged compressed mean ≈ exact mean


def test_onebit_allreduce_identical_on_all_members():
    mesh = _mesh8()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8, 64)), dtype=jnp.float32)
    fn = shard_map(
        lambda v, we, se: onebit_allreduce(v.reshape(-1), we, se, "data")[0],
        mesh=mesh, in_specs=(P("data", None), P("data"), P("data")),
        out_specs=P("data"))
    out = np.asarray(fn(x, jnp.zeros((8 * 64,)),
                        jnp.zeros((8 * 8,)))).reshape(8, 64)
    for r in range(1, 8):
        np.testing.assert_array_equal(out[0], out[r])


# ------------------------------------------------------ optimizer state machine
def test_onebit_adam_warmup_matches_adam_then_freezes_variance():
    from deepspeed_tpu.runtime.fp16.onebit.adam import scale_by_onebit_adam
    import optax
    tx = scale_by_onebit_adam(0.9, 0.999, 1e-8, freeze_step=2)
    ref = optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8)
    params = {"w": jnp.ones((16,))}
    g = {"w": jnp.full((16,), 0.3)}
    s = tx.init(params)
    rs = ref.init(params)
    for step in range(1, 3):  # warmup: exact Adam
        u, s = tx.update(g, s, params)
        ru, rs = ref.update(g, rs, params)
        np.testing.assert_allclose(u["w"], ru["w"], rtol=1e-5)
    nu_frozen = np.asarray(s.nu["w"]).copy()
    u3, s = tx.update(g, s, params)
    np.testing.assert_array_equal(s.nu["w"], nu_frozen)  # variance frozen
    # compressed updates are sign*scale: exactly 1 magnitude level
    mags = np.unique(np.round(np.abs(np.asarray(s.mu["w"])), 6))
    assert len(mags) == 1
    assert np.all(np.isfinite(np.asarray(u3["w"])))


def test_zeroone_adam_variance_refresh_interval_doubles():
    from deepspeed_tpu.runtime.fp16.onebit.zoadam import scale_by_zeroone_adam
    tx = scale_by_zeroone_adam(0.9, 0.999, 1e-8, var_freeze_step=2,
                               var_update_scaler=2)
    params = {"w": jnp.ones((8,))}
    s = tx.init(params)
    rng = np.random.default_rng(3)
    intervals = []
    for step in range(1, 12):
        g = {"w": jnp.asarray(rng.standard_normal(8), dtype=jnp.float32)}
        _, s = tx.update(g, s, params)
        intervals.append(int(s.var_interval))
    assert intervals[-1] > intervals[0]  # growing refresh interval
    assert int(s.count) == 11


def test_onebit_lamb_runs():
    from deepspeed_tpu.runtime.fp16.onebit.lamb import scale_by_onebit_lamb
    tx = scale_by_onebit_lamb(freeze_step=1)
    params = {"w": jnp.ones((8, 8))}
    s = tx.init(params)
    for _ in range(3):
        u, s = tx.update({"w": jnp.full((8, 8), 0.1)}, s, params)
    assert np.all(np.isfinite(np.asarray(u["w"])))


def test_onebit_lamb_trust_ratio_separates_it_from_adam():
    """What makes LAMB lamb (round-3 weak #7): the layer-wise trust ratio
    ||w||/||update|| scales each tensor's step with its parameter norm —
    identical grads on params of different scale produce proportionally
    different updates, unlike (onebit-)Adam whose update is
    norm-independent."""
    from deepspeed_tpu.runtime.fp16.onebit.adam import scale_by_onebit_adam
    from deepspeed_tpu.runtime.fp16.onebit.lamb import scale_by_onebit_lamb

    params = {"small": jnp.full((16, 16), 0.1),
              "big": jnp.full((16, 16), 10.0)}
    grads = {"small": jnp.full((16, 16), 0.01),
             "big": jnp.full((16, 16), 0.01)}

    lamb = scale_by_onebit_lamb(freeze_step=100)
    s = lamb.init(params)
    u, s = lamb.update(grads, s, params)
    r_lamb = (float(jnp.linalg.norm(u["big"])) /
              float(jnp.linalg.norm(u["small"])))
    assert r_lamb > 10, f"no trust-ratio scaling: ratio {r_lamb}"

    adam = scale_by_onebit_adam(freeze_step=100)
    sa = adam.init(params)
    ua, sa = adam.update(grads, sa, params)
    r_adam = (float(jnp.linalg.norm(ua["big"])) /
              float(jnp.linalg.norm(ua["small"])))
    assert abs(r_adam - 1.0) < 0.1, f"adam should be norm-independent: " \
                                    f"{r_adam}"


# ------------------------------------------------------------------- engine
@pytest.mark.parametrize("opt", ["OneBitAdam", "OneBitLamb", "ZeroOneAdam"])
def test_engine_trains_with_onebit_optimizers(opt):
    model = GPT2Model(TINY)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": opt,
                      "params": {"lr": 1e-3, "freeze_step": 2}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 0,
    })
    assert engine.optimizer.name == opt.lower()
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(5):  # crosses the freeze boundary at step 2
        batch = {"input_ids": rng.integers(0, 255, (1, 8, 16), np.int32)}
        losses.append(float(engine.train_batch(batch=batch)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
