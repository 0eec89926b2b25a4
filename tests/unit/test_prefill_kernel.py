"""A whole prefill attends its own keys in the packed flash kernel (ISSUE 58):
who takes it (``GPT2Model.prefill_kernel``, the one rule), that it gives
what ``_kv_attend`` over the lane gives, and the counter that says how often
it engaged.

The contract under test: a cached forward from a concrete column 0 computes
causal self-attention among the block's own tokens, which is what attention
over the lane is then (the columns below ``t`` hold what the call has just
written, the mask drops the rest); K and V go to the lane as ever; a
right-padded bucket needs no length. On the CPU the default programs are the
parent's; ``attn_backend="pallas"`` runs the kernel in interpret mode.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.bloom import BloomConfig, BloomModel
from deepspeed_tpu.models.gpt2 import GPT2Model
from deepspeed_tpu.models.gpt_neo import GPTNeoConfig, GPTNeoModel
from deepspeed_tpu.models.kexaone import KExaoneConfig, KExaoneModel
from deepspeed_tpu.models.lfm2 import LFM2MoEConfig, LFM2MoEModel
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
from deepspeed_tpu.models.opt import OPTConfig, OPTModel
from deepspeed_tpu.models.sdar import SDARConfig, SDARModel
from deepspeed_tpu.models.xing import XingConfig, XingModel
from deepspeed_tpu.parallel import topology

LANE, VOCAB = 512, 64


# ------------------------------------------------------------------ parity

def _opt(backend, **kw):
    # two heads of 64: one stored row of 128 lanes a token
    return OPTModel(OPTConfig(**{**dict(
        vocab_size=VOCAB, n_positions=LANE, n_embd=128, n_layer=2, n_head=2,
        pad_vocab_to_multiple=1, dtype="float32", attn_backend=backend),
        **kw}))


def _olmoe(backend):
    # two heads of 128, rotated before the cache, q and k normalised
    return OLMoEModel(OLMoEConfig(
        vocab_size=VOCAB, n_positions=LANE, n_embd=256, n_layer=2, n_head=2,
        mlp_hidden=64, num_experts=4, top_k=2, pad_vocab_to_multiple=1,
        dtype="float32", attn_backend=backend))


def _prefill_then_decode(model, params, ids, real, steps=8):
    """The whole prefill of ``ids`` [1, T] (``real`` of them the prompt, the
    rest right padding) as ``slot_prefill`` calls it, then ``steps`` greedy
    decode steps from the pool it left: (logits of the real rows, the pool
    after the prefill, the tokens)."""
    cache = model.init_kv_cache(1, LANE, dtype=jnp.float32)
    logits, cache = jax.jit(
        lambda p, i, c: model.apply_with_cache(p, i, c, 0))(params, ids,
                                                            cache)
    pool = jax.tree.map(np.array, cache)
    step = jax.jit(model.decode_with_slots)
    tok = jnp.argmax(logits[:, real - 1, :VOCAB], axis=-1).astype(jnp.int32)
    toks = [int(tok[0])]
    for i in range(steps):
        out, cache = step(params, tok[:, None], cache,
                          jnp.asarray([real + i], jnp.int32))
        tok = jnp.argmax(out[:, 0, :VOCAB], axis=-1).astype(jnp.int32)
        toks.append(int(tok[0]))
    return np.asarray(logits[0, :real]), pool, toks


@pytest.mark.parametrize("t,real", [(256, 256), (256, 200), (384, 384),
                                    (384, 300)],
                         ids=("256", "256_padded", "384", "384_padded"))
@pytest.mark.parametrize("build", (_opt, _olmoe), ids=("opt_64", "olmoe_128"))
def test_whole_prefill_in_the_kernel_is_the_attend_over_the_lane(
        build, t, real):
    """Heads of 64 (two to a stored row) and of 128 (rotary on), blocks of
    256 and 384 (tiles of 256 and of 128) in a lane of 512, whole and
    right-padded: the kernel's
    logits are the lane attend's to the tolerance ``test_decode_attention``
    holds the decode kernel to, the first layer's K and V are bitwise the
    same (the deeper ones carry the first's sum), and eight decode steps
    continued from either pool give the same tokens."""
    kernel, lane = build("pallas"), build("auto")
    params = lane.init(jax.random.PRNGKey(7))
    cache = jax.eval_shape(
        lambda: lane.init_kv_cache(1, LANE, dtype=jnp.float32))
    assert kernel.prefill_kernel(cache, t, 0, None, jnp.float32)
    assert not lane.prefill_kernel(cache, t, 0, None, jnp.float32)
    rng = np.random.RandomState(t + real)
    ids = np.zeros((1, t), np.int32)
    ids[0, :real] = rng.randint(0, VOCAB, real)
    got, got_pool, got_toks = _prefill_then_decode(kernel, params, ids, real)
    want, want_pool, want_toks = _prefill_then_decode(lane, params, ids, real)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        assert np.array_equal(got_pool[name][0], want_pool[name][0])
        np.testing.assert_allclose(got_pool[name][:, :, :real],
                                   want_pool[name][:, :, :real], atol=1e-5)
        assert not got_pool[name][:, :, t:].any()       # nothing past T
    assert got_toks == want_toks


@pytest.mark.parametrize("shape,heads,tiles", [
    ((8, 1024, 16 * 64), 16, (512, 512, 128)),      # cell 1's training step
    ((4, 1024, 32 * 64), 32, (512, 512, 128)),      # cell 4's
    ((1, 2048, 32 * 64), 32, (512, 512, 128)),      # cell 6's largest bucket
    ((1, 2048, 16 * 128), 16, (512, 512, 128)),     # cell 3's
    ((1, 256, 32 * 64), 32, (256, 256, 128)),       # the smallest it takes
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_the_kernel_resolves_the_tiles_it_was_swept_at(shape, heads, tiles):
    """The prefill calls the training forward's kernel and adds no tile of
    its own: T = 1,024 at the training cells' shapes resolves to ``_TILES``'
    first entry as before, and so do the prefill's buckets it divides."""
    from deepspeed_tpu.ops.pallas.flash_attention_packed import _resolve
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert _resolve(x, heads, None, None, None)[1] == tiles


def _engine(backend, **config):
    """An engine of ``_opt(backend)`` on ONE device (the tests' process has
    eight, and the rule refuses a mesh of several)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import initialize_mesh
    return InferenceEngine(
        _opt(backend), DeepSpeedInferenceConfig.from_dict(
            {"dtype": "float32", "max_tokens": LANE, **config}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))


# ---------------------------------------------------------------- the rule

def _lane(model, max_len, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: model.init_kv_cache(1, max_len, dtype=dtype))


def _opt_13b(**kw):
    return OPTModel(OPTConfig(**{**dict(
        n_positions=2048, n_embd=2048, n_layer=24, n_head=32,
        dtype="bfloat16"), **kw}))


def _olmoe_7b():
    return OLMoEModel(OLMoEConfig(dtype="bfloat16"))


def _llama(**kw):
    return LlamaModel(LlamaConfig(**{**dict(
        vocab_size=VOCAB, n_positions=LANE, n_embd=256, n_layer=2, n_head=2,
        n_kv_head=2, mlp_hidden=64, dtype="bfloat16"), **kw}))


def _traced(model, cache, t):
    """The rule's answer for a ``start`` that is an argument of the program
    (a suffix prefill, a chunk)."""
    said = []
    jax.eval_shape(lambda s: said.append(
        model.prefill_kernel(cache, t, s)) or s, jnp.int32(0))
    return said[0]


#: case -> (the model, the lane's columns, t, what else the call is given,
#: the answer): everything on one TPU unless the case says otherwise
RULE = {
    # the cells that take it, at their buckets
    "opt_1.3b_bucket_2048": (_opt_13b, 2048, 2048, {}, True),
    "opt_1.3b_bucket_1024": (_opt_13b, 2048, 1024, {}, True),
    "opt_1.3b_chat_bucket_256": (_opt_13b, 1024, 256, {}, True),
    "olmoe_bucket_512": (_olmoe_7b, 2048, 512, {}, True),
    "olmoe_bucket_2048": (_olmoe_7b, 2048, 2048, {}, True),
    "window_no_shorter_than_t": (
        lambda: _llama(sliding_window=256), LANE, 256, {}, True),
    "a_mesh_of_one_device": (_opt_13b, 2048, 1024, {"mesh": 1}, True),
    "pallas_backend_on_the_cpu": (
        lambda: _opt_13b(attn_backend="pallas"), 2048, 1024, {"tpu": False},
        True),
    # the call is no whole prefill
    "traced_start": (_opt_13b, 2048, 1024, {"start": "traced"}, False),
    "start_a_slot": (_opt_13b, 2048, 1024,
                     {"start": np.zeros(1, np.int32)}, False),
    "start_past_0": (_opt_13b, 2048, 1024, {"start": 128}, False),
    "pad_counts": (_opt_13b, 2048, 1024,
                   {"pad_counts": np.zeros(1, np.int32)}, False),
    "one_token": (_opt_13b, 2048, 1, {}, False),
    "bucket_128": (_opt_13b, 1024, 128, {}, False),
    # the family's mask or bias is its own
    "alibi": (lambda: BloomModel(BloomConfig(
        vocab_size=VOCAB, n_positions=LANE, n_embd=256, n_layer=2, n_head=4,
        dtype="bfloat16")), LANE, 256, {}, False),
    "gpt_neo_local_layers": (lambda: GPTNeoModel(GPTNeoConfig(
        vocab_size=VOCAB, n_positions=LANE, n_embd=256, n_layer=2, n_head=4,
        local_window=4, attention_layers=("global", "local"),
        dtype="bfloat16")), LANE, 256, {}, False),
    "window_shorter_than_t": (
        lambda: _llama(sliding_window=32), LANE, 256, {}, False),
    "sdar_blocks": (lambda: SDARModel(SDARConfig(
        vocab_size=VOCAB, n_positions=LANE, n_embd=256, n_layer=2, n_head=2,
        n_kv_head=2, head_dim=128, mlp_hidden=32, num_experts=4, top_k=2,
        block_length=4, mask_token_id=VOCAB - 1, dtype="bfloat16")),
        LANE, 256, {}, False),
    "sdar_30b": (lambda: SDARModel(SDARConfig(dtype="bfloat16")), 4096, 1024,
                 {}, False),
    # the pool or the heads are not the kernel's
    "grouped_heads_lfm2": (
        lambda: LFM2MoEModel(LFM2MoEConfig(dtype="bfloat16")), 4096, 1024,
        {}, False),
    "grouped_heads_k_exaone": (
        lambda: KExaoneModel(KExaoneConfig(dtype="bfloat16")), 16384, 2048,
        {}, False),
    "grouped_heads_llama": (lambda: _llama(n_head=4), LANE, 256, {}, False),
    "latent_leaf_xing": (
        lambda: XingModel(XingConfig(dtype="bfloat16")), 4224, 4096, {},
        False),
    "int8_pool": (_opt_13b, 2048, 1024, {"pool": jnp.int8}, False),
    "float32_pool": (_opt_13b, 2048, 1024, {"pool": jnp.float32}, False),
    "t_no_multiple_of_128": (_opt_13b, 2048, 320, {}, False),
    "t_past_4096": (lambda: _opt_13b(n_positions=8192), 8192, 8192, {},
                    False),
    "heads_of_80": (lambda: _opt_13b(n_embd=2560), 2048, 1024, {}, False),
    # where it would run
    "a_mesh_of_two_devices": (_opt_13b, 2048, 1024, {"mesh": 2}, False),
    "the_cpu_default": (_opt_13b, 2048, 1024, {"tpu": False}, False),
    "xla_backend_on_the_cpu": (
        lambda: _opt_13b(attn_backend="xla"), 2048, 1024, {"tpu": False},
        False),
}


@pytest.mark.parametrize("case", RULE, ids=str)
def test_the_one_rule_by_what_can_be_observed(monkeypatch, case):
    """``GPT2Model.prefill_kernel`` over the lane as ``slot_prefill`` builds
    it: OPT-1.3B and OLMoE at their cells' buckets accept; a start that is
    traced, a slot's own, or past 0, left padding, one token, a bucket of
    128 (``_flash_prefill_from``), ALiBi,
    GPT-Neo's local layers, a window shorter than the block, SDAR's blocks,
    grouped KV heads (LFM2, K-EXAONE, a LLaMA), a latent leaf (Xing), a
    pool that is not the compute dtype's, a length or a head width the
    kernel does not take, a mesh of two devices and the CPU default
    refuse."""
    build, max_len, t, given, want = RULE[case]
    model = build()
    monkeypatch.setattr(topology, "on_tpu",
                        lambda: given.get("tpu", True))
    cache = _lane(model, max_len, given.get("pool", jnp.bfloat16))
    start = given.get("start", 0)
    devices = np.array(jax.devices())
    mesh = jax.sharding.Mesh(devices[:given.get("mesh", 1)], ("model",))
    with mesh:
        if isinstance(start, str):
            got = _traced(model, cache, t)
        else:
            got = model.prefill_kernel(cache, t, start,
                                       given.get("pad_counts"), jnp.bfloat16)
    assert got is want


def test_cpu_default_slot_prefill_is_the_parents_program():
    """On the CPU with the default backend ``slot_prefill`` of an OPT traces
    to what it traced to before the kernel: no ``pallas_call``, and letter
    for letter the program of a process in which the rule always refuses;
    the same engine under ``attn_backend="pallas"`` holds one kernel a
    layer body."""
    def program(backend, bucket=256):
        engine = _engine(backend)
        pool = engine.init_slot_pool(2, LANE)
        pool, _ = engine.slot_prefill(pool, 0,
                                      np.zeros(bucket - 3, np.int32))
        fn = engine._slot_fns[("slot_prefill", bucket, LANE)]
        i32, f32 = jnp.int32(0), jnp.float32(0)
        # a new function a call: ``jax.jit`` keeps what it traced by it
        return engine, lambda: str(jax.make_jaxpr(
            lambda *a: fn.__wrapped__(*a))(
            engine.params, jnp.zeros((1, bucket), jnp.int32), pool, i32,
            i32, f32, i32, f32, i32))

    engine, trace = program("auto")
    assert not engine.prefill_kernel(250, LANE)
    text = trace()
    assert "pallas_call" not in text
    real = GPT2Model.prefill_kernel
    try:
        GPT2Model.prefill_kernel = lambda *a, **k: False
        assert trace() == text
    finally:
        GPT2Model.prefill_kernel = real
    engine, trace = program("pallas")
    assert engine.prefill_kernel(250, LANE)
    assert not engine.prefill_kernel(125, LANE)     # bucket 128
    assert trace().count("pallas_call") == 1


# -------------------------------------------------------------- the counter

def test_kernel_prefills_counter_and_served_tokens():
    """``serve/kernel_prefills`` of ``serve/prefills``: a served prompt of
    200 tokens (bucket 256: the kernel, in interpret mode) and one of 20
    (bucket 32: the lane attend) count 1 of 2, the shut-down line says so,
    both gauges go with the engine that owns them, and the tokens served
    through the kernel are those of the default engine."""
    import logging
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    from deepspeed_tpu.telemetry import get_tracer
    tr = get_tracer()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in (200, 20)]

    def serve(backend):
        srv = ServingEngine(_engine(backend, seed=11),
                            {"num_slots": 2, "max_model_len": LANE})
        lines = []
        handler = logging.Handler()
        handler.emit = lambda rec: lines.append(rec.getMessage())
        logger = logging.getLogger("DeepSpeedTPU")
        logger.addHandler(handler)
        try:
            rids = [srv.submit(p, SamplingParams(max_new_tokens=6))
                    for p in prompts]
            srv.run_until_idle()
            toks = [list(srv.result(r).tokens) for r in rids]
            read = (tr.counter_value("serve/kernel_prefills"),
                    tr.counter_value("serve/prefills"))
        finally:
            srv.shutdown()
            logger.removeHandler(handler)
        assert tr.counter_value("serve/prefills") is None
        assert tr.counter_value("serve/kernel_prefills") is None
        return toks, read, [ln for ln in lines if "prefills" in ln]

    toks, read, said = serve("pallas")
    assert read == (1, 2)
    assert any("1 of 2 prefills attended in the flash kernel" in ln
               for ln in said), said
    want, read, said = serve("auto")
    assert read == (0, 2)
    assert any("0 of 2 prefills" in ln for ln in said), said
    assert toks == want
