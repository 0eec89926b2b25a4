"""Activation-checkpointing subsystem: JSON config → remat policy on the
model (the previously parsed-but-ignored ActivationCheckpointingConfig is
now consumed), Megatron-compatible checkpoint() surface."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac


def _engine(extra):
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4,
                                 pad_vocab_to_multiple=8))
    cfg = {
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0,
    }
    cfg.update(extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return engine, model


def test_config_turns_on_remat_and_trains():
    engine, model = _engine({"activation_checkpointing": {
        "partition_activations": True}})
    assert model.config.remat is True
    assert model.config.remat_policy == "nothing_saveable"
    loss = engine.train_batch(batch={"input_ids": np.zeros((1, 8, 16),
                                                           np.int32)})
    assert np.isfinite(float(loss))


def test_default_policy_keeps_dots():
    engine, model = _engine({"activation_checkpointing": {}})
    assert model.config.remat is True
    assert model.config.remat_policy == "dots_with_no_batch_dims_saveable"


def test_remat_matches_no_remat_loss():
    e1, _ = _engine({})
    e2, _ = _engine({"activation_checkpointing": {
        "partition_activations": True}})
    batch = {"input_ids": np.arange(128, dtype=np.int32).reshape(1, 8, 16)
             % 255}
    l1 = float(e1.train_batch(batch=batch))
    l2 = float(e2.train_batch(batch=batch))
    assert abs(l1 - l2) < 1e-5  # remat changes memory, not math


def test_cpu_checkpointing_policy_and_cpu_fallback():
    """Host-offloaded activations (reference checkpointing.py:461 CPU
    checkpointing): cpu_checkpointing=true maps to the XLA host-offload
    remat policy. The policy itself only lowers on real TPU (the CPU test
    backend has no annotate_device_placement implementation), so here the
    engine must FALL BACK with a warning and still train — the chip sweep
    validates the offload placement on hardware."""
    ac.configure(deepspeed_config=None, checkpoint_in_cpu=None)
    e2, model = _engine({"activation_checkpointing": {
        "cpu_checkpointing": True}})
    # config resolves to the offload policy...
    assert ac.current_policy_name() == "offload_dots"
    # ...but on the CPU backend the model runs the fallback policy
    assert model.config.remat_policy == "dots_with_no_batch_dims_saveable"
    e1, _ = _engine({})
    batch = {"input_ids": np.arange(128, dtype=np.int32).reshape(1, 8, 16)
             % 255}
    l1 = float(e1.train_batch(batch=batch))
    l2 = float(e2.train_batch(batch=batch))
    assert abs(l1 - l2) < 1e-5  # remat placement changes memory, not math


def test_offload_policy_lowers_standalone():
    """The offload policy itself is real (outside SPMD jit): grads through
    a scan rematerialized with host-offloaded dots match plain grads."""
    pol = ac.get_policy("offload_dots")

    def f(x, w, policy=None):
        def body(h, w_):
            return jnp.tanh(h @ w_), None
        fn = jax.checkpoint(body, policy=policy) if policy else body
        h, _ = jax.lax.scan(fn, x, w)
        return h.sum()

    x = jnp.ones((4, 16))
    w = jnp.full((3, 16, 16), 0.05)
    g_plain = jax.grad(f)(x, w)
    g_off = jax.jit(jax.grad(lambda a, b: f(a, b, pol)))(x, w)
    np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_off),
                               rtol=1e-6)


def test_checkpoint_function_surface():
    calls = []

    def fn(x):
        calls.append(1)
        return jnp.sin(x) @ x

    x = jnp.ones((8, 8))
    out = ac.checkpoint(fn, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.sin(x) @ x), atol=1e-6)
    wrapped = ac.checkpoint_wrapper(fn, policy="nothing_saveable")
    g = jax.grad(lambda x: jnp.sum(wrapped(x)))(x)
    assert np.all(np.isfinite(np.asarray(g)))


def test_unknown_policy_raises():
    with pytest.raises(ValueError):
        ac.get_policy("bogus_policy")


# ------------------------------------------------ the attention residuals
# The default policy keeps the attention kernel's output and lse (named in
# the kernels' forward): a checkpointed layer's backward runs the backward
# kernel alone, not the forward kernel a second time in front of it.

def _kernel_calls(jaxpr, primitive="pallas_call"):
    """How many ``primitive`` equations a jaxpr holds, sub-jaxprs (a scan's
    body, a checkpoint's, a shard_map's, a custom_vjp's) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub, primitive)
    return n


def _packed_model(policy):
    """A GPT2Model whose training layers take the packed Pallas kernel
    (interpret mode here) under ``jax.checkpoint`` with ``policy``."""
    model = GPT2Model(GPT2Config(
        vocab_size=256, n_positions=128, n_embd=128, n_layer=2, n_head=2, pad_vocab_to_multiple=128, attn_backend="pallas",
        remat=True, remat_policy=policy))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (4, 128)))
    return model, model.init(jax.random.PRNGKey(0)), {"input_ids": ids}


# what a layer's gradient runs: the forward kernel, the fused backward
# kernel, and under a policy that keeps no residual the forward once more
_CALLS = {"dots_with_no_batch_dims_saveable": 2, "dots_saveable": 2,
          "everything_saveable": 2, "nothing_saveable": 3}


@pytest.mark.parametrize("policy", sorted(_CALLS))
def test_layer_gradient_kernel_calls(policy):
    model, params, batch = _packed_model(policy)
    assert model._packed_attn_ok(128, 64, 2)
    grad = jax.make_jaxpr(jax.grad(
        lambda p: model.apply(p, batch, train=True)))(params)
    assert _kernel_calls(grad.jaxpr) == _CALLS[policy]


@pytest.mark.parametrize("policy", ("dots_with_no_batch_dims_saveable",
                                    "nothing_saveable"))
def test_layer_gradient_kernel_calls_per_device(policy):
    """The same count where the kernel runs inside ``pallas_per_device``'s
    ``shard_map`` (four devices, the batch split over them): the names are
    given inside the map and the residuals kept through it."""
    from deepspeed_tpu.parallel.topology import DeviceMeshManager
    mesh = DeviceMeshManager(dp=4, devices=jax.devices()[:4]).mesh
    model, params, batch = _packed_model(policy)
    with mesh:
        grad = jax.make_jaxpr(jax.grad(
            lambda p: model.apply(p, batch, train=True)))(params)
    assert _kernel_calls(grad.jaxpr, "shard_map") >= _CALLS[policy]
    assert _kernel_calls(grad.jaxpr) == _CALLS[policy]


@pytest.mark.parametrize("other", ("everything_saveable",
                                   "nothing_saveable"))
def test_kept_residuals_are_the_recomputed_values(other):
    """A kept residual is what the recompute would have produced: loss and
    gradients under the default policy equal, bit for bit, those of a layer
    that keeps everything and of one that recomputes everything."""
    def run(policy):
        model, params, batch = _packed_model(policy)
        return jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, batch, train=True)))(params)

    loss, grads = run(ac.DEFAULT_POLICY)
    loss_o, grads_o = run(other)
    assert np.asarray(loss).tobytes() == np.asarray(loss_o).tobytes()
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_o)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _streamed(q, k, v):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    b, t, hd = q.shape
    heads = lambda a: a.reshape(b, t, 2, hd // 2).transpose(0, 2, 1, 3)  # noqa: E731
    out = flash_attention(heads(q), heads(k), heads(v), True, None, None,
                          None, True, None)
    return out.transpose(0, 2, 1, 3).reshape(b, t, hd)


def _packed(q, k, v):
    from deepspeed_tpu.ops.pallas.flash_attention_packed import \
        packed_flash_attention
    return packed_flash_attention(q, k, v, 2, interpret=True)


@pytest.mark.parametrize("kernel", (_packed, _streamed),
                         ids=("packed", "streamed"))
def test_policy_means_one_thing_for_every_attention_kernel(kernel):
    """qkv -> attention -> out_proj under ``jax.checkpoint``: with either
    kernel the default policy's gradient runs as many kernel calls as
    keeping everything does, one fewer than keeping nothing."""
    x = jnp.ones((2, 128, 128), jnp.float32)
    w = jnp.full((128, 384), 0.01), jnp.full((128, 128), 0.01)

    def calls(policy):
        def layer(x, w_qkv, w_out):
            q, k, v = jnp.split(x @ w_qkv, 3, axis=-1)
            return jnp.sum(kernel(q, k, v) @ w_out)
        fn = jax.checkpoint(layer, policy=ac.get_policy(policy))
        return _kernel_calls(jax.make_jaxpr(
            jax.grad(fn, argnums=(1, 2)))(x, *w).jaxpr)

    kept = calls("everything_saveable")
    assert calls(ac.DEFAULT_POLICY) == calls("dots_saveable") == kept
    assert calls("nothing_saveable") == kept + 1


def _opcodes(hlo_text):
    """The opcode of every instruction of an HLO module's text, in order:
    what the program does, without names, ids or source lines."""
    from deepspeed_tpu.telemetry.hlo_cost import _INSTR_RE
    return [m.group(3) for m in map(_INSTR_RE.match, hlo_text.splitlines())
            if m]


def test_names_add_nothing_outside_a_checkpoint():
    """The serving prefill's guarantee: outside ``jax.checkpoint`` a named
    result is the result. ``packed_flash_attention`` holds one kernel call
    and compiles to the instructions of the bare kernel call (``_fwd``,
    which gives no names), no copy among them that the other lacks."""
    from deepspeed_tpu.ops.pallas import flash_attention_packed as fap
    x = jnp.ones((1, 128, 128), jnp.bfloat16)
    scale, tiles = fap._resolve(x, 2, None, None, None)

    def named(q, k, v):
        return fap.packed_flash_attention(q, k, v, 2, interpret=True)

    def bare(q, k, v):
        return fap._fwd(q, k, v, 2, True, scale, tiles, True, None)[0]

    jaxpr = jax.make_jaxpr(named)(x, x, x).jaxpr
    assert _kernel_calls(jaxpr) == 1 and _kernel_calls(jaxpr, "name") == 2
    assert _kernel_calls(jax.make_jaxpr(bare)(x, x, x).jaxpr, "name") == 0
    text = [jax.jit(f).lower(x, x, x).compile().as_text()
            for f in (named, bare)]
    assert _opcodes(text[0]) and _opcodes(text[0]) == _opcodes(text[1])
