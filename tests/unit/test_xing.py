"""Xing4.0 (models/xing.py): a latent (MLA) cache with its two attention
paths, a residual of four streams mixed by Sinkhorn-normalised maps (mHC),
a share of the routed experts beside a shared one, served through the slot
pool beside the plain float32 reference (chipbench/reference_xing.py) at a
size the CPU holds. The pool's only leaf is the latent one; a block of
tokens expands it to per-head keys and values, one token a slot is absorbed
into it. Logits are compared, not tokens.

Tolerance: float32 system against float32 reference differ by summation
order only (``tests/unit/test_olmoe.py``): 2e-5 of the logits' RMS;
bfloat16 reads two hundred times over.
"""

import functools
import time
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference_xing, weights_xing                 # noqa: E402
from chipbench.model import load_json                              # noqa: E402
from deepspeed_tpu.models import xing                              # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Model                    # noqa: E402
from deepspeed_tpu.models.xing import XingConfig, XingModel        # noqa: E402

F32_TOL = 2e-5
HELD = (4, 8)           # experts 4 ... 11 of the router's 16
#: what the rehearsed cell holds the program to
REHEARSED = load_json("workloads", "xing4.0-29b-a4b.serve-docqa.json")[
    "rehearse"]["cell"]["check"]["logits_rel_rms_err"]


def dims_of(held=HELD, layers=3):
    return {"layers": layers, "dense_layers": 1, "d_model": 128, "heads": 4,
            "q_rank": 48, "kv_rank": 64, "nope_dim": 32, "rope_dim": 16,
            "v_dim": 32, "dense_ff": 256, "experts": held[1],
            "router_experts": 16, "expert_offset": held[0], "top_k": 4,
            "expert_ff": 64, "shared_experts": 1, "vocab": 512,
            "positions": 128, "rope_theta": 10000.0, "rope_factor": 4.0,
            "rope_original_positions": 16, "rope_beta_fast": 32.0,
            "rope_beta_slow": 1.0, "rope_mscale": 1.0,
            "rope_mscale_all_dim": 1.0, "rms_eps": 1e-6,
            "norm_topk_prob": True, "routed_scaling_factor": 2.0,
            "renorm_eps": 1e-20, "streams": 4, "hc_sinkhorn_iters": 20,
            "hc_eps": 1e-6, "hc_clamp": 30.0}


def tiny(dtype="float32", held=HELD, layers=3, **over):
    kw = dict(vocab_size=512, n_positions=128, n_embd=128,
              n_layer=layers + 1, n_head=4, q_lora_rank=48, kv_lora_rank=64,
              qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
              rope_factor=4.0, rope_original_positions=16, mlp_hidden=256,
              first_k_dense_replace=1, moe_intermediate_size=64,
              num_experts=16, experts_held=held, top_k=4, dtype=dtype)
    kw.update(over)
    model = XingModel(XingConfig(**kw))
    model._ffn_chunk = 16       # a prefill of 32 is two chunks
    return model


def seeded(dtype=jnp.float32, held=HELD, layers=3):
    w = weights_xing.make(dims_of(held, layers), jax.random.PRNGKey(46))
    return jax.tree.map(lambda a: a.astype(dtype), w)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


IDS = np.random.default_rng(1).integers(0, 512, (2, 48), dtype=np.int32)


def reference_logits(w, ids=IDS, held=HELD, layers=3):
    return np.stack([np.asarray(reference_xing.logits(
        w, row, dims_of(held, layers))) for row in ids])


def test_weights_tree_is_the_models_tree():
    model = tiny()
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: weights_xing.make(dims_of(), k),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(jax.tree.map(lambda a: a.shape, want)) == \
        jax.tree.leaves(jax.tree.map(lambda a: a.shape, got))
    # the eleven routed layers after the dense one are one scan
    assert (model.lead, model.period, model.repeats) == (1, 1, 3)
    assert model.lane_leaves == ("latent",) and not model.lane_end_state
    assert GPT2Model.lane_leaves.fget(GPT2Model()) == ("k", "v")


def test_yarn_frequencies_by_the_program_and_by_the_reference():
    """Fast dimensions as published, slow ones divided by the factor, at
    the published sizes and at the tiny ones."""
    for cfg, dims in ((XingConfig(), load_json(
            "configs", "xing4.0-29b-a4b.json")["dims"]),
            (tiny().config, dims_of())):
        ours = xing.yarn_inv_freq(cfg)
        np.testing.assert_allclose(
            ours, reference_xing.yarn_frequencies(dims), rtol=1e-6)
        plain = cfg.rope_theta ** -(np.arange(
            0, cfg.qk_rope_head_dim, 2) / cfg.qk_rope_head_dim)
        assert ours[0] == pytest.approx(plain[0])
        assert ours[-1] == pytest.approx(plain[-1] / cfg.rope_factor)
    assert XingModel(XingConfig(n_layer=2, experts_held=(0, 8))
                     )._score_scale == pytest.approx(
        (0.1 * np.log(64) + 1) ** 2 / np.sqrt(192))


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_forward_against_the_reference(dtype, ok):
    model, w = tiny(dtype), seeded()
    got = model.logits(jax.tree.map(lambda a: a.astype(dtype), w),
                       jnp.asarray(IDS), train=False)
    err = rel_rms(got, reference_logits(w))
    assert (err < F32_TOL) == ok and err < 0.05, err


def pool_logits(model, w, dtype, real=21, bucket=32):
    """A right-padded prefill of ``real`` tokens in a bucket, then decode
    steps one token a slot, through a pool of 2 slots x 64: the logits of
    every real position."""
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    cache = model.init_kv_cache(2, 64, dtype=dtype)
    ids = np.zeros((2, bucket), np.int32)
    ids[:, :real] = IDS[:, :real]
    logits, cache = jax.jit(model.apply_with_cache)(
        w, jnp.asarray(ids), cache, jnp.int32(0),
        lengths=jnp.full((2,), real))
    out = [logits[:, :real]]
    decode = jax.jit(model.decode_with_slots)
    for j in range(real, IDS.shape[1]):
        step, cache = decode(w, jnp.asarray(IDS[:, j:j + 1]), cache,
                             jnp.full((2,), j))
        out.append(step)
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_padded_prefill_then_decode_through_the_pool_against_the_reference(
        dtype, ok):
    model, w = tiny(dtype), seeded()
    model._attend_scores_bytes = 4 * 8 * 64 * 4    # a prefill: 4 query blocks
    err = rel_rms(pool_logits(model, w, jnp.dtype(dtype)),
                  reference_logits(w))
    assert (err < F32_TOL) == ok and err < 0.05, err


def test_the_absorbed_step_equals_the_expanded_block_on_the_same_cache():
    """One token a slot goes through the key half of ``W_kvb`` into the
    latent space (T = 1: absorbed); the same token as the second of a block
    of two attends expanded keys and values (T = 2). On one cache the two
    give the same logits, and the same latent row is written."""
    model, w = tiny(), seeded()
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    block = jax.jit(model.apply_with_cache)
    _, cache = block(w, jnp.asarray(IDS[:, :20]), cache, jnp.int32(0))
    absorbed, a = jax.jit(model.decode_with_slots)(
        w, jnp.asarray(IDS[:, 20:21]), cache, jnp.full((2,), 20))
    expanded, b = block(w, jnp.asarray(IDS[:, 19:21]), cache, jnp.int32(19))
    assert rel_rms(absorbed[:, 0], expanded[:, 1]) < F32_TOL
    assert rel_rms(a["latent"][:, :, :21], b["latent"][:, :, :21]) < F32_TOL
    assert a["latent"].shape == (4, 2, 64, 1, 128)   # 64 + 16 in whole rows
    assert not np.asarray(a["latent"][..., 80:]).any()


def test_the_maps_are_doubly_stochastic_and_differ_by_token():
    """``H_res`` after the 20 Sinkhorn steps: rows and columns sum to 1
    within 1e-4, for every token, and it is no identity, no uniform
    average and not the same for two tokens; the reference's own maps are
    the program's."""
    model, w = tiny(), seeded()
    xs = model._open_streams(w["wte"][IDS]) + \
        jax.random.normal(jax.random.PRNGKey(3), (4, 2, 48, 128)) * 0.02
    hc = jax.tree.map(lambda a: a[1], w["blocks"]["attn"]["hc_attn"])
    pre, post, res = model._hc_maps(xs, hc)
    assert res.shape == (4, 4, 2, 48)
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-4)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-4)
    res = np.asarray(res)
    assert np.abs(res[:, :, 0, 0] - np.eye(4)).max() > 0.2
    assert np.abs(res - 0.25).max() > 0.2
    assert np.abs(res[:, :, 0, 0] - res[:, :, 1, 7]).max() > 1e-3
    assert 0 < np.asarray(pre).min() and np.asarray(post).max() < 2
    want = reference_xing.maps(jnp.moveaxis(xs[:, 0], 0, 1), hc, 20, 1e-6,
                               30.0)
    for ours, theirs in zip((pre[:, 0].T, post[:, 0].T,
                             jnp.moveaxis(res[:, :, 0], -1, 0)), want):
        assert rel_rms(ours, theirs) < F32_TOL


# ----------------------------------------------------------- planted faults

def _identity_mix(model):
    keep = model._hc_maps

    def maps(xs, p):
        pre, post, res = keep(xs, p)
        eye = jnp.eye(4)[:, :, None, None]
        return pre, post, jnp.broadcast_to(eye, res.shape)
    model._hc_maps = maps


def _one_sinkhorn_step(model):
    model.config = model.config.__class__(**{
        **model.config.__dict__, "hc_sinkhorn_iters": 1})


def _no_rope_score(model):
    keep = model._latent_attend

    def attend(q, q_pos, slab, latent, block, mask):
        c = latent[0].shape[0]
        return keep(q, q_pos, slab.at[..., c:].set(0), latent, block, mask)
    model._latent_attend = attend


def _raw_latent_in_decode(model, monkeypatch):
    """A decode step that writes ``c_kv`` as it comes from ``W_kva``."""
    keep = xing._rms_norm

    def norm(x, scale, eps):
        decode = x.shape[1] == 1 and x.shape[-1] == model.config.kv_lora_rank
        return x if decode else keep(x, scale, eps)
    monkeypatch.setattr(xing, "_rms_norm", norm)


def _decode_one_column_late(model):
    keep = model.decode_with_slots

    def late(params, ids, cache, positions, routing=False):
        return keep(params, ids, cache, positions + 1, routing=routing)
    model.decode_with_slots = late


@pytest.mark.parametrize("fault", ["identity_mix", "one_sinkhorn_step",
                                   "no_rope_score", "raw_latent_in_decode",
                                   "decode_one_column_late"])
def test_a_planted_fault_reads_over_the_rehearsals_limit(fault, monkeypatch):
    """Each new mechanism left out or done wrong, in the program alone: the
    logits of a padded prefill and 27 decode steps then lie further from
    the reference than the rehearsed cell allows (0.045; the sound program
    in bfloat16 reads 0.02 there), where the sound program reads 1e-6."""
    model, w = tiny(), seeded()
    plant = globals()["_" + fault]
    plant(model, monkeypatch) if fault == "raw_latent_in_decode" \
        else plant(model)
    got, want = pool_logits(model, w, jnp.float32), reference_logits(w)
    err = rel_rms(got[:, 21:], want[:, 21:])        # the decode steps
    assert err > REHEARSED, (fault, err)


# ------------------------------------------------------------- the engine

@functools.lru_cache(maxsize=None)
def engine_of(dtype="float32"):
    """(engine serving the seeded weights in ``dtype``, the weights); one
    engine a type for the whole file: its programs compile once."""
    import deepspeed_tpu
    w = seeded()
    model = tiny()
    model.init = lambda rng: w
    return deepspeed_tpu.init_inference(
        model, config={"dtype": dtype, "max_tokens": 64}), w


def test_the_pools_only_leaf_is_latent_and_says_its_length():
    engine, _ = engine_of()
    pool = engine.init_slot_pool(3, 64)
    assert {k: v.shape for k, v in pool.items()} == {
        "latent": (4, 3, 64, 1, 128)}
    assert engine._pool_dims(pool) == (3, 64, False)
    assert engine._pool_dims(engine.init_slot_pool(3, 64, quantize=True)) \
        == (3, 64, True)
    assert not engine._recurrent    # a row per token: no real length needed
    assert engine.decode_kernel_block(3, 64) is None
    spent = pool
    pool, _ = engine.slot_prefill(pool, 0, IDS[0, :5])
    with pytest.raises(RuntimeError, match="consumed"):
        engine._pool_dims(spent)


def test_slot_prefill_and_decode_stream_the_references_tokens():
    engine, w = engine_of()
    want = reference_logits(w, IDS[:1])[0]
    top2 = np.sort(want, -1)[:, -2:]
    # positions whose arg-max no float32 rounding moves: nearly all
    clear = (top2[:, 1] - top2[:, 0]) > 1e-4 * np.abs(want).max()
    assert clear[12] and clear.mean() > 0.9
    pool = engine.init_slot_pool(3, 64)
    pool, tok = engine.slot_prefill(pool, 1, IDS[0, :13])
    assert tok == want[12].argmax()
    touched, largest = engine.take_routing()
    assert 0 < touched <= 3 * HELD[1]
    toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for j in range(13, 40):
        toks[1], pos[1] = IDS[0, j], j
        pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                            np.zeros(3, np.float32))
        assert nxt[1] == want[j].argmax() or not clear[j], j


def test_chunk_and_suffix_prefill_equal_one_prefill_over_the_latent_lane():
    """Chunked prefill over a latent lane WORKS: a whole chunk of 16 then a
    padded suffix of 5 leave the first token and the lane of one prefill of
    21 (a latent row a token is valid up to any column)."""
    engine, _ = engine_of()
    pool = engine.init_slot_pool(2, 64)
    pool, one = engine.slot_prefill(pool, 0, IDS[0, :21])
    pool = engine.slot_chunk_prefill(pool, 1, IDS[0, :16], 0)
    pool, two = engine.slot_suffix_prefill(pool, 1, IDS[0, 16:21], 16)
    assert one == two
    assert rel_rms(pool["latent"][:, 1, :21],
                   pool["latent"][:, 0, :21]) < F32_TOL


def test_a_copied_lane_goes_on_from_a_shared_prefix():
    """Prefix reuse over a latent lane WORKS: ``slot_copy_lane`` from a
    donor, then only the suffix past the shared 16 tokens, gives the first
    token and the lane of a whole prefill."""
    engine, _ = engine_of()
    other = np.concatenate([IDS[0, :16], IDS[1, 16:25]])
    pool = engine.init_slot_pool(3, 64)
    pool, _ = engine.slot_prefill(pool, 0, IDS[0, :30])        # the donor
    pool, whole = engine.slot_prefill(pool, 1, other)
    pool = engine.slot_copy_lane(pool, 0, 2)
    pool, reused = engine.slot_suffix_prefill(pool, 2, other[16:], 16)
    assert reused == whole
    assert rel_rms(pool["latent"][:, 2, :25],
                   pool["latent"][:, 1, :25]) < F32_TOL


def serve(engine, config, prompts, new=8):
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    out = {}
    srv = ServingEngine(engine, config)
    rids = [srv.submit(p, SamplingParams(max_new_tokens=new),
                       on_token=lambda r, t: out.setdefault(
                           r.request_id, []).append(int(t)))
            for p in prompts]
    srv.run_until_idle()
    srv.shutdown()
    return [out[r] for r in rids]


def test_the_server_streams_the_engines_tokens_and_records_the_live_latent():
    """Through ``ServingEngine`` (scheduler, pool programs, sampler): the
    tokens of ``generate``; every decode tick records ``serve/kv_live``
    (the active slots' columns, no rings) and ``serve/kv_read`` (the XLA
    attend reads every column)."""
    from deepspeed_tpu.telemetry import get_tracer
    engine, _ = engine_of()
    # one length: one prefill bucket, one ``generate`` program
    prompts = [IDS[0, :19], IDS[1, :19], IDS[0, 20:39]]
    config = {"num_slots": 3, "max_model_len": 64, "max_queue": 8}
    tracer = get_tracer()
    before, mark = tracer.phases_total, time.perf_counter_ns()
    got = serve(engine, config, prompts)
    # this test's own records: the process-wide ring also holds those of
    # whatever served before it in this worker, with pools of other sizes
    recs = {n: [(a, b) for name, t0, _, a, b in tracer.phases()
                if name == n and t0 >= mark]
            for n in ("serve/kv_live", "serve/kv_read", "serve/moe_decode")}
    assert tracer.phases_total > before and all(recs.values())
    assert all(a > 0 and b == 0 for a, b in recs["serve/kv_live"])
    assert all(a == b == 3 * 64 for a, b in recs["serve/kv_read"])
    for prompt, toks in zip(prompts, got):
        want = np.asarray(engine.generate(prompt[None], max_new_tokens=8))
        assert toks == want[0, len(prompt):].tolist()


@pytest.mark.parametrize("block", [
    {"prefix_cache": {"enabled": True}},
    {"chunked_prefill": {"enabled": True, "chunk_tokens": 16}},
    {"kv_quant": {"enabled": True}}],
    ids=["prefix_cache", "chunked_prefill", "kv_quant"])
def test_what_leans_on_a_lane_valid_at_any_column_is_served(block):
    """Not fenced by kind: a latent lane keeps a row per token. Prefix
    cache and chunked prefill stream the plain server's tokens; an int8
    pool the same first tokens and lengths."""
    engine, _ = engine_of()
    shared = IDS[0, :24]
    prompts = [np.concatenate([shared, IDS[1, :9]]),
               np.concatenate([shared, IDS[1, 20:27]]), IDS[1, :37]]
    config = {"num_slots": 3, "max_model_len": 64, "max_queue": 8}
    plain = serve(engine, config, prompts)
    other = serve(engine, {**config, **block}, prompts)
    if "kv_quant" in block:
        assert [len(x) for x in other] == [len(x) for x in plain]
        assert [x[0] for x in other] == [x[0] for x in plain]
    else:
        assert other == plain


def test_an_int8_round_trip_of_the_latent_lane_reads_to_its_rounding():
    from deepspeed_tpu.inference.kv_quant import pool_to_fp, quantize_pool
    model, w = tiny(), seeded()
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    _, cache = jax.jit(model.apply_with_cache)(
        w, jnp.asarray(IDS[:, :32]), cache, jnp.int32(0))
    decode = jax.jit(model.decode_with_slots)
    step = lambda c: decode(w, jnp.asarray(IDS[:, 32:33]), c,
                            jnp.full((2,), 32))[0]
    err = rel_rms(step(pool_to_fp(quantize_pool(cache), jnp.float32)),
                  step(cache))
    assert 0 < err < 0.05, err


def test_what_is_not_supported_says_so():
    from deepspeed_tpu.runtime.config_utils import ConfigError
    from deepspeed_tpu.serving import ServingEngine
    model, w = tiny(), seeded()
    with pytest.raises(NotImplementedError, match="ROADMAP B1"):
        model.apply(w, {"input_ids": jnp.asarray(IDS)}, train=True)
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="drafter"):
        model.verify_with_slots(w, jnp.asarray(IDS[:, :4]), cache,
                                jnp.array([0, 0]))
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.pipeline_spec()
    engine, _ = engine_of()
    with pytest.raises(ConfigError, match="speculative") as err:
        ServingEngine(engine, {
            "num_slots": 2, "max_model_len": 64,
            "speculative": {"enabled": True, "k": 2,
                            "draft": {"mode": "self", "layers": 1}}})
    assert "latent" in str(err.value) and "ROADMAP B9" in str(err.value)


def test_a_left_padded_batch_is_the_rows_alone():
    """``generate``'s left padding: positions count from a row's first real
    token and the padding is never a key."""
    model, w = tiny(), seeded()
    want = reference_logits(w, IDS[:1, :12])[0]
    ids = np.zeros((2, 16), np.int32)
    ids[0, 4:], ids[1] = IDS[0, :12], IDS[1, :16]
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    got, _ = jax.jit(model.apply_with_cache)(
        w, jnp.asarray(ids), cache, jnp.int32(0),
        pad_counts=jnp.array([4, 0]))
    assert rel_rms(got[0, 4:], want) < F32_TOL


def test_rules_cover_the_new_leaves():
    from deepspeed_tpu.models.api import match_rule, param_path_tree
    model = tiny()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths = jax.tree.leaves(param_path_tree(shapes))
    for path, leaf in zip(paths, jax.tree.leaves(shapes)):
        spec = match_rule(path, model.partition_rules())
        assert spec is None or len(spec) <= leaf.ndim, path
        assert spec is not None or path == "ln_f_scale", path
    rules = model.partition_rules()
    assert match_rule("blocks/attn/kv_b_w", rules)[2] == "model"
    assert match_rule("blocks/attn/kv_a_w", rules) == (None,)
    assert match_rule("blocks/attn/hc_attn/phi", rules) == (None,)
    cache = jax.eval_shape(lambda: model.init_kv_cache(2, 64))
    spec = match_rule("latent", model.cache_partition_rules())
    assert len(spec) == cache["latent"].ndim


def test_int8_weights_cover_the_large_leaves_and_leave_the_maps():
    """The program's own lower precision (the cell's control) quantizes the
    five latent projections and ``W_o``, the dense FFN, the held experts,
    the shared expert and the router; the maps of the widened residual,
    gains, bias and both tables stay."""
    from deepspeed_tpu.inference.quantization import (_default_predicate,
                                                      is_quantized)
    engine, _ = engine_of(dtype="int8")
    b = engine.params["blocks"]
    assert all(is_quantized(b["attn"][n]) for n in (
        "q_a_w", "q_b_w", "kv_a_w", "kv_b_w", "attn_proj_w"))
    assert all(is_quantized(b["dense"][n])
               for n in ("gate_w", "up_w", "down_w"))
    moe = b["moe"]["moe"]
    assert all(is_quantized(moe[part][n]) for part in ("experts", "shared")
               for n in ("w_gate", "w_up", "w_down"))
    assert is_quantized(moe["gate"]["wg"])
    for stack, key in (("attn", "hc_attn"), ("dense", "hc_mlp"),
                       ("moe", "hc_mlp")):
        assert not any(is_quantized(leaf) for leaf in b[stack][key].values())
    # at the published width too: [14336, 24] looks like a matrix
    path = tuple(jax.tree_util.DictKey(k)
                 for k in ("blocks", "attn", "hc_attn", "phi"))
    assert not _default_predicate(path, jnp.zeros((12, 14336, 24)))
    assert not is_quantized(moe["gate"]["bias"])
    assert not is_quantized(b["attn"]["kv_a_scale"])
    assert not is_quantized(engine.params["lm_head"])
    logits = np.asarray(engine.forward(IDS[:, :16]), np.float32)
    assert np.isfinite(logits).all()
    pool = engine.init_slot_pool(1, 64)
    pool, tok = engine.slot_prefill(pool, 0, IDS[0, :9])
    assert 0 <= tok < 512


# -------------------------------------------------------------- the share

def test_the_eight_shares_and_the_shared_expert_once_add_up():
    """A routed layer's output is the sum of what each chip's held experts
    give plus the shared expert ONCE: two shares of eight here, and what
    they leave out of each other's is exactly the other's."""
    w = seeded(held=(0, 16), layers=1)
    moe = jax.tree.map(lambda a: a[0], w["blocks"]["moe"]["moe"])
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 128))
    whole, _, counts = tiny(held=(0, 16), layers=1).moe.apply_routed(
        moe, u, renormalize=True)
    assert int(counts.sum()) == 2 * 24 * 4
    once = tiny().moe._apply_shared(moe, u.reshape(-1, 128)).reshape(u.shape)
    total = 0
    for offset in (0, 8):
        part = {**moe, "experts": jax.tree.map(
            lambda a: a[offset:offset + 8], moe["experts"])}
        y, _, got = tiny(held=(offset, 8), layers=1).moe.apply_routed(
            part, u, renormalize=True)
        np.testing.assert_array_equal(got, counts[offset:offset + 8])
        total = total + (y - once)
    assert rel_rms(total + once, whole) < F32_TOL
    assert rel_rms(once, whole) > 0.1           # the routed terms are there
