"""K-EXAONE (models/kexaone.py): window and full attention layers mixed,
a share of the routed experts beside a shared one, served through the slot
pool beside the plain float32 reference (chipbench/reference_kexaone.py) at
a size the CPU holds. The window layers keep their keys and values in RINGS
of ``sliding_window`` columns a slot (position p in column p mod W), the full
layers keep full-length lanes; a prefill is right-padded to a pow2 bucket and
told its real length; its attention goes in blocks of queries. Logits are
compared, not tokens.

Tolerance: float32 system against float32 reference differ by summation
order only (``tests/unit/test_olmoe.py``): 2e-5 of the logits' RMS;
bfloat16 reads two hundred times over.
"""

import os
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference_kexaone, weights_kexaone           # noqa: E402
from chipbench.reference_lfm2 import route                         # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Model                    # noqa: E402
from deepspeed_tpu.models.kexaone import (FULL, SLIDING,           # noqa: E402
                                          KExaoneConfig, KExaoneModel)
from deepspeed_tpu.moe.experts import GatedExpertFFN               # noqa: E402
from deepspeed_tpu.moe.sharded_moe import (MOELayer, TopKGate,     # noqa: E402
                                           topk_route)

F32_TOL = 2e-5
W = 8                   # the tiny window: 48 positions are six turns of it
#: the cut's pattern (a dense window layer, then one whole period, rotated)
#: and two whole periods, which the walk scans
PATTERNS = {"cut": (SLIDING, SLIDING, SLIDING, FULL, SLIDING),
            "periods": (SLIDING,) + (SLIDING, SLIDING, FULL, SLIDING) * 2}
HELD = (4, 8)           # experts 4 ... 11 of the router's 16


def dims_of(types, held=HELD):
    return {"layers": len(types) - 1, "dense_layers": 1,
            "layer_types": list(types), "window": W, "d_model": 128,
            "heads": 4, "kv_heads": 2, "head_dim": 32, "dense_ff": 256,
            "experts": held[1], "router_experts": 16,
            "expert_offset": held[0], "top_k": 4, "expert_ff": 64,
            "shared_experts": 1, "vocab": 512, "positions": 128,
            "rope_theta": 1000000.0, "rms_eps": 1e-5, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5, "renorm_eps": 1e-20}


def tiny(types=PATTERNS["cut"], dtype="float32", held=HELD, **over):
    kw = dict(vocab_size=512, n_positions=128, n_embd=128, n_layer=len(types),
              n_head=4, n_kv_head=2, head_dim=32, mlp_hidden=256,
              layer_types=tuple(types), sliding_window=W,
              moe_intermediate_size=64, num_experts=16, experts_held=held,
              top_k=4, dtype=dtype)
    kw.update(over)
    model = KExaoneModel(KExaoneConfig(**kw))
    model._ffn_chunk = 16       # a prefill of 32 is two chunks
    return model


def seeded(types=PATTERNS["cut"], dtype=jnp.float32, held=HELD):
    w = weights_kexaone.make(dims_of(types, held), jax.random.PRNGKey(38))
    return jax.tree.map(lambda a: a.astype(dtype), w)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


IDS = np.random.default_rng(1).integers(0, 512, (2, 48), dtype=np.int32)


def reference_logits(w, types, ids=IDS, held=HELD):
    return np.stack([np.asarray(reference_kexaone.logits(
        w, row, dims_of(types, held))) for row in ids])


def small_blocks(model, scores=4 * 4 * 8 * 64, window=8):
    """Query blocks of 8 at this size: the full layers' budget of scores
    (4 heads x 8 queries x 64 keys) and the window layers' block."""
    model._attend_scores_bytes, model._window_query_block = scores, window
    return model


# ------------------------------------------------------------ the stack

def test_weights_tree_is_the_models_tree():
    for types in PATTERNS.values():
        shapes = jax.eval_shape(tiny(types).init, jax.random.PRNGKey(0))
        made = jax.eval_shape(lambda k: weights_kexaone.make(dims_of(types), k),
                              jax.random.PRNGKey(0))
        assert jax.tree.map(lambda a: a.shape, shapes) == \
            jax.tree.map(lambda a: a.shape, made)
    experts = shapes["blocks"]["moe"]["moe"]
    assert experts["experts"]["w_gate"].shape == (8, 8, 128, 64)     # held
    assert experts["gate"]["wg"].shape == (8, 128, 16)      # the router: all
    assert experts["shared"]["w_down"].shape == (8, 64, 128)


@pytest.mark.parametrize("types, want", [
    (PATTERNS["cut"], (1, 1, 2)), (PATTERNS["periods"], (1, 4, 2)),
    ((SLIDING, SLIDING, SLIDING, FULL) * 12, (1, 4, 11))])
def test_the_pattern_is_split_into_lead_periods_and_tail(types, want):
    """The published 48 layers: the dense layer, eleven whole periods
    (rotated: sliding, sliding, full, sliding) and a tail of three."""
    assert KExaoneModel._split_pattern(tuple(types), 1) == want


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_forward_against_the_reference(pattern, dtype, ok):
    types = PATTERNS[pattern]
    model, w = tiny(types, dtype), seeded(types)
    got = model.logits(jax.tree.map(lambda a: a.astype(dtype), w), IDS,
                       train=False)
    err = rel_rms(got[..., :512], reference_logits(w, types))
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


def pool_logits(model, w, dtype, real=21, bucket=32):
    """Logits of IDS through the pool: a prefill of ``real`` tokens
    right-padded to ``bucket`` and told its length, then one decode step a
    token to the end: past ``real`` + 2 W, more than two turns of a ring."""
    cache = model.init_kv_cache(2, 64, dtype=dtype)
    ids = np.zeros((2, bucket), np.int32)
    ids[:, :real] = IDS[:, :real]
    out, cache = jax.jit(model.apply_with_cache)(
        w, ids, cache, jnp.int32(0), lengths=jnp.full((2,), real))
    rows = [out[:, :real]]
    step = jax.jit(model.decode_with_slots)
    for j in range(real, IDS.shape[1]):
        out, cache = step(w, IDS[:, j:j + 1], cache, jnp.full((2,), j))
        rows.append(out)
    return jnp.concatenate(rows, axis=1)[..., :512]


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_padded_prefill_then_decode_through_the_pool_against_the_reference(
        pattern, dtype, ok):
    """The prefill goes in blocks of 8 queries (full layers and window
    layers alike); the decode steps turn every ring more than twice."""
    types = PATTERNS[pattern]
    model, w = small_blocks(tiny(types, dtype)), seeded(types)
    assert IDS.shape[1] - 21 > 2 * W
    got = pool_logits(model, jax.tree.map(lambda a: a.astype(dtype), w),
                      jnp.dtype(dtype))
    err = rel_rms(got, reference_logits(w, types))
    assert (err < 2 * F32_TOL) if ok else (err > 10 * F32_TOL), err


def test_without_the_real_length_the_padding_enters_the_ring():
    model, w = tiny(), seeded()
    ids = np.zeros((2, 32), np.int32)
    ids[:, :21] = IDS[:, :21]
    rings = {}
    for name, lengths in (("told", jnp.full((2,), 21)), ("untold", None)):
        cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
        _, cache = model.apply_with_cache(w, ids, cache, jnp.int32(0),
                                          lengths=lengths)
        rings[name] = np.asarray(cache["wk"])
    assert np.abs(rings["told"] - rings["untold"]).max() > 1e-3


# --------------------------------------------------- the ring and the band

def banded_lane(model, q, k, v, start, lane_k, lane_v):
    """The same window layer over a FULL-LENGTH lane with the band mask:
    ``_kv_write`` at the tokens' own columns, ``_kv_attend`` over every
    column with ``0 <= q_pos - k_pos < W``."""
    t = q.shape[2]
    lane_k = GPT2Model._kv_write(lane_k, 0, k.transpose(0, 2, 1, 3), start)
    lane_v = GPT2Model._kv_write(lane_v, 0, v.transpose(0, 2, 1, 3), start)
    q_pos = (jnp.reshape(start, (-1, 1)) + jnp.arange(t))[:, None, :, None]
    k_pos = jnp.arange(lane_k.shape[2])[None, None, None, :]
    keep = (k_pos <= q_pos) & (q_pos - k_pos < W)
    return GPT2Model._kv_attend(q, lane_k, lane_v, 0, keep, None), \
        lane_k, lane_v


def test_a_ring_layer_equals_the_layer_over_a_full_lane_with_the_band_mask():
    """A prefill of 19 in a bucket of 32 (blocks of 8 queries), a block of
    8 more at column 19, then decode steps through three turns of the ring:
    at every step ``_window_attend`` over ``[1, S, W, ...]`` gives what the
    band mask gives over ``[1, S, 64, ...]``."""
    model = small_blocks(tiny())
    rng = np.random.default_rng(3)
    s, h, hk, hd = 2, 4, 2, 32

    def draw(t):
        return [jnp.asarray(rng.standard_normal((s, n, t, hd)), jnp.float32)
                for n in (h, hk, hk)]

    ring_k = ring_v = jnp.zeros((1, s, W, 1, hk * hd))
    lane_k = lane_v = jnp.zeros((1, s, 64, 1, hk * hd))
    q, k, v = draw(32)
    got, ring_k, ring_v = model._window_attend(
        q, k, v, ring_k, ring_v, 0, jnp.int32(0), jnp.full((s,), 19))
    want, lane_k, lane_v = banded_lane(model, q, k, v, jnp.int32(0),
                                       lane_k, lane_v)
    np.testing.assert_allclose(got[:, :, :19], want[:, :, :19], atol=1e-5)
    q, k, v = draw(8)           # a block at a non-zero start over the padding
    got, ring_k, ring_v = model._window_attend(
        q, k, v, ring_k, ring_v, 0, jnp.int32(19))
    want, lane_k, lane_v = banded_lane(model, q, k, v, jnp.int32(19),
                                       lane_k, lane_v)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for pos in range(27, 27 + 3 * W):
        q, k, v = draw(1)
        at = jnp.full((s,), pos)
        got, ring_k, ring_v = model._window_attend(q, k, v, ring_k, ring_v,
                                                   0, at)
        want, lane_k, lane_v = banded_lane(model, q, k, v, at, lane_k, lane_v)
        np.testing.assert_allclose(got, want, atol=1e-5, err_msg=str(pos))
        # position p lies in column p mod W
        np.testing.assert_array_equal(
            np.asarray(ring_k[0, :, pos % W]), np.asarray(lane_k[0, :, pos]))


def test_a_block_at_a_nonzero_start_equals_one_prefill():
    """16 tokens, then 16 more at column 16 (traced), then a decode step:
    the logits of one prefill of 32 and the same lane."""
    model, w = small_blocks(tiny()), seeded()
    fresh = lambda: model.init_kv_cache(2, 64, dtype=jnp.float32)
    one, whole = model.apply_with_cache(w, IDS[:, :32], fresh(), jnp.int32(0))
    _, cache = model.apply_with_cache(w, IDS[:, :16], fresh(), jnp.int32(0))
    two, cache = jax.jit(lambda c, at: model.apply_with_cache(
        w, IDS[:, 16:32], c, at))(cache, jnp.int32(16))
    assert rel_rms(two, one[:, 16:]) < F32_TOL
    for name in cache:
        assert rel_rms(cache[name], whole[name]) < F32_TOL, name


def test_row_blocks_cover_every_row_once():
    """``_in_row_blocks``: whole blocks through the map, the rest in a call
    of its own, outputs end to end, extras added up, ``at`` the first row
    of each block; no length goes in one piece but one that fits."""
    x = jnp.arange(2 * 29 * 3, dtype=jnp.float32).reshape(2, 29, 3)
    seen = []

    def fn(at, rows, pos):
        seen.append(rows.shape[1])
        return rows * 2 + (at + jnp.arange(rows.shape[1])[None, :, None]
                           - pos), \
            {"rows": jnp.float32(rows.shape[1]), "none": None}

    for block, pieces in ((8, [8, 5]), (29, [29]), (64, [29]), (1, [1])):
        del seen[:]
        out, extra = GPT2Model._in_row_blocks(
            fn, block, 1, x, jnp.arange(29)[None, :, None])
        assert seen == pieces       # traced once a shape
        assert np.array_equal(out, 2 * x) and out.shape == x.shape
        assert float(extra["rows"]) == 29 and extra["none"] is None


@pytest.mark.parametrize("length", [32, 29])
@pytest.mark.parametrize("family", ["gpt2", "olmoe", "lfm2", "kexaone",
                                    "whole_lane_heads"])
def test_blocked_prefill_attention_equals_unblocked(family, length):
    """Every family goes through ``_kv_attend``: a prefill of 32 in blocks
    of 8 queries gives the logits and the pool of the one-piece prefill,
    and so does one of 29 (three blocks and a rest of five; K-EXAONE's
    feed-forwards a chunk of 16 and a rest of 13): no length falls back to
    one piece. ``whole_lane_heads``: heads of 128 stored two to a row, which
    a prefill reads as heads and a decode step as rows."""
    if family == "gpt2":
        from deepspeed_tpu.models.gpt2 import GPT2Config
        make = lambda: GPT2Model(GPT2Config(
            vocab_size=512, n_positions=64, n_embd=128, n_layer=2, n_head=4))
    elif family == "olmoe":
        from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
        make = lambda: OLMoEModel(OLMoEConfig(
            vocab_size=512, n_positions=64, n_embd=128, n_layer=2, n_head=4,
            n_kv_head=4, mlp_hidden=64, num_experts=8, top_k=2))
    elif family == "lfm2":
        from deepspeed_tpu.models.lfm2 import ATTN, CONV, LFM2MoEConfig, \
            LFM2MoEModel
        make = lambda: LFM2MoEModel(LFM2MoEConfig(
            vocab_size=512, n_positions=64, n_embd=128, n_layer=3, n_head=4,
            n_kv_head=2, mlp_hidden=256, layer_types=(CONV, ATTN, CONV),
            num_dense_layers=1, moe_intermediate_size=64, num_experts=8,
            top_k=2))
    elif family == "kexaone":
        make = tiny
    else:
        make = lambda: tiny(n_embd=64, n_head=2, n_kv_head=2, head_dim=128)
    plain = make()
    w = jax.tree.map(lambda a: 3 * a, plain.init(jax.random.PRNGKey(2)))
    blocked = small_blocks(make(), scores=plain.config.n_head * 8 * 32 * 4)
    assert blocked._query_block(32, plain.config.n_head, 32) == 8
    assert plain._query_block(32, plain.config.n_head, 32) == 32
    outs = []
    for model in (plain, blocked):
        cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
        outs.append(jax.jit(model.apply_with_cache)(
            w, IDS[:, :length], cache, jnp.int32(0))[:2])
    assert rel_rms(outs[1][0], outs[0][0]) < F32_TOL
    for name in outs[0][1]:
        assert rel_rms(outs[1][1][name], outs[0][1][name]) < F32_TOL, name


def test_whole_lane_heads_prefill_as_heads_and_decode_as_rows():
    """Two KV heads of 128 in one stored row of 256: the prefill reads the
    rows as heads (no zero-padded queries), the decode step reads them as
    rows; both agree with the forward that keeps no cache."""
    model = tiny(n_embd=64, n_head=4, n_kv_head=2, head_dim=128)
    w = jax.tree.map(lambda a: 3 * a, model.init(jax.random.PRNGKey(2)))
    assert model.init_kv_cache(1, 64)["k"].shape == (1, 1, 64, 1, 256)
    want = model.logits(w, IDS, train=False)[..., :512]
    assert rel_rms(pool_logits(model, w, jnp.float32), want) < 2 * F32_TOL


# ---------------------------------------------------------- the engine

def engine_of(types=PATTERNS["cut"], dtype="float32"):
    """(engine serving the seeded weights in ``dtype``, the weights)."""
    import deepspeed_tpu
    w = seeded(types)
    model = tiny(types)
    model.init = lambda rng: w
    return deepspeed_tpu.init_inference(
        model, config={"dtype": dtype, "max_tokens": 64}), w


def test_the_pool_has_a_cache_shape_per_kind_of_layer():
    engine, _ = engine_of()
    pool = engine.init_slot_pool(3, 64)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (1, 3, 64, 1, 64), "v": (1, 3, 64, 1, 64),
        "wk": (4, 3, W, 1, 64), "wv": (4, 3, W, 1, 64)}
    assert engine._pool_dims(pool) == (3, 64, False)
    assert engine.module.lane_end_state == ("wk", "wv")
    assert engine._recurrent        # the prefills hand over the real length


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_slot_prefill_and_decode_stream_the_references_tokens(pattern):
    """``slot_prefill`` (bucket 16 for 13 tokens) then ``slot_decode_step``
    through the engine, four turns of the rings: every sampled token is the
    arg-max of the reference's full-sequence logits; the routing read back
    counts the HELD experts."""
    types = PATTERNS[pattern]
    engine, w = engine_of(types)
    want = reference_logits(w, types, IDS[:1])[0]
    top2 = np.sort(want, -1)[:, -2:]
    assert ((top2[:, 1] - top2[:, 0]) > 1e-4 * np.abs(want).max()).all()
    pool = engine.init_slot_pool(3, 64)
    pool, tok = engine.slot_prefill(pool, 1, IDS[0, :13])
    assert tok == want[12].argmax()
    routed = len(types) - 1
    touched, largest = engine.take_routing()
    assert touched <= routed * HELD[1]
    toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for j in range(13, 13 + 4 * W):
        toks[1], pos[1] = IDS[0, j], j
        pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                            np.zeros(3, np.float32))
        assert nxt[1] == want[j].argmax(), j
    touched, largest = engine.take_routing()
    assert touched <= routed * HELD[1] and largest <= 3 * routed


def test_chunk_and_suffix_prefill_equal_one_prefill_at_the_engine():
    """A whole chunk of 16 then a padded suffix of 5 (with no decode step
    between them) leave the first token and the lane of one prefill of 21:
    the rings hold positions 13 ... 20 either way."""
    engine, _ = engine_of()
    pool = engine.init_slot_pool(2, 64)
    pool, one = engine.slot_prefill(pool, 0, IDS[0, :21])
    pool = engine.slot_chunk_prefill(pool, 1, IDS[0, :16], 0)
    pool, two = engine.slot_suffix_prefill(pool, 1, IDS[0, 16:21], 16)
    assert one == two
    for name in ("wk", "wv"):
        assert rel_rms(pool[name][:, 1], pool[name][:, 0]) < F32_TOL, name
    for name in ("k", "v"):
        assert rel_rms(pool[name][:, 1, :21],
                       pool[name][:, 0, :21]) < F32_TOL, name
    with pytest.raises(ValueError, match="whole pow2 chunks"):
        engine.slot_chunk_prefill(pool, 1, IDS[0, :13], 0)


def test_what_is_not_supported_says_so():
    model, w = tiny(), seeded()
    with pytest.raises(NotImplementedError, match="ROADMAP B1"):
        model.apply(w, {"input_ids": jnp.asarray(IDS)}, train=True)
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="ring"):
        model.verify_with_slots(w, jnp.asarray(IDS[:, :4]), cache,
                                jnp.array([0, 0]))
    with pytest.raises(NotImplementedError, match="left-padded"):
        model.apply_with_cache(w, jnp.asarray(IDS[:, :8]), cache, 0,
                               pad_counts=jnp.array([0, 2]))
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.pipeline_spec()
    with pytest.raises(ValueError, match="layer_types"):
        tiny(n_layer=3)
    with pytest.raises(ValueError, match="held"):
        tiny(held=(12, 8))              # experts 12 ... 19 of 16


def test_generate_runs_the_cached_forward():
    engine, _ = engine_of()
    out = np.asarray(engine.generate(IDS[:1, :13], max_new_tokens=2 * W))
    pool = engine.init_slot_pool(1, 64)
    pool, tok = engine.slot_prefill(pool, 0, IDS[0, :13])
    got = [tok]
    for j in range(13, 13 + 2 * W - 1):
        pool, nxt = engine.slot_decode_step(
            pool, np.array([got[-1]], np.int32), np.array([j], np.int32),
            np.zeros(1, np.float32))
        got.append(int(nxt[0]))
    assert out[0, 13:].tolist() == got


def test_rules_cover_the_new_leaves():
    """Every parameter and every pool leaf meets a rule of its own rank."""
    from deepspeed_tpu.models.api import match_rule, param_path_tree
    model = tiny()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths = jax.tree.leaves(param_path_tree(shapes))
    for path, leaf in zip(paths, jax.tree.leaves(shapes)):
        spec = match_rule(path, model.partition_rules())
        assert spec is None or len(spec) <= leaf.ndim, path
        assert spec is not None or path == "ln_f_scale", path
    rules = model.partition_rules()
    assert match_rule("blocks/moe/moe/experts/w_up", rules)[1] == "expert"
    assert match_rule("blocks/moe/moe/shared/w_down", rules)[1] == "model"
    assert match_rule("blocks/window/qkv_w", rules)[2] == "model"
    cache = jax.eval_shape(lambda: model.init_kv_cache(2, 64))
    assert sorted(cache) == ["k", "v", "wk", "wv"]
    for name, leaf in cache.items():
        spec = match_rule(name, model.cache_partition_rules())
        assert len(spec) == leaf.ndim, name


def test_int8_weights_cover_the_new_large_leaves():
    """The program's own lower precision (the cell's control) quantizes the
    attention matrices of both kinds, the dense FFN, the held experts, the
    shared expert and the router; gains, bias and both tables stay."""
    from deepspeed_tpu.inference.quantization import is_quantized
    engine, _ = engine_of(dtype="int8")
    b = engine.params["blocks"]
    for kind, names in (("window", ("qkv_w", "attn_proj_w")),
                        ("full", ("qkv_w", "attn_proj_w")),
                        ("dense", ("gate_w", "up_w", "down_w"))):
        assert all(is_quantized(b[kind][n]) for n in names), kind
    moe = b["moe"]["moe"]
    assert all(is_quantized(moe[part][n]) for part in ("experts", "shared")
               for n in ("w_gate", "w_up", "w_down"))
    assert is_quantized(moe["gate"]["wg"])
    assert not is_quantized(moe["gate"]["bias"])
    assert not is_quantized(b["window"]["post_attn_scale"])
    assert not is_quantized(engine.params["wte"])
    assert not is_quantized(engine.params["lm_head"])
    logits = np.asarray(engine.forward(IDS[:, :16]), np.float32)
    assert np.isfinite(logits).all()
    pool = engine.init_slot_pool(1, 64)
    pool, tok = engine.slot_prefill(pool, 0, IDS[0, :9])
    assert 0 <= tok < 512


# ----------------------------------------------- the router and the share

def test_router_by_hand():
    """sigmoid scores; the bias enters the choice only; the picks are
    renormalised with 1e-20 in the sum, then times 2.5."""
    logits = jnp.log(jnp.asarray([[0.9, 0.5, 0.2, 0.8], [0.1, 0.2, 0.3, 0.4]])
                     / (1 - jnp.asarray([[0.9, 0.5, 0.2, 0.8],
                                         [0.1, 0.2, 0.3, 0.4]])))
    bias = jnp.asarray([0.0, 0.5, 0.0, 0.0])
    w, idx = topk_route(logits, 2, True, "sigmoid", bias, 1e-20)
    assert idx.tolist() == [[1, 0], [1, 3]]         # 0.5 + 0.5, 0.2 + 0.5
    np.testing.assert_allclose(
        2.5 * w, [[2.5 * 0.5 / 1.4, 2.5 * 0.9 / 1.4],
                  [2.5 * 0.2 / 0.6, 2.5 * 0.4 / 0.6]], rtol=1e-5)
    s = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(
        route(s, bias, 2, True, 1e-20, 2.5),
        [[2.5 * 0.9 / 1.4, 2.5 * 0.5 / 1.4, 0, 0],
         [0, 2.5 * 0.2 / 0.6, 0, 2.5 * 0.4 / 0.6]], rtol=1e-5)
    # all picked scores zero: the epsilon keeps the division finite
    w, _ = topk_route(jnp.full((1, 4), -200.0), 2, True, "sigmoid", None,
                      1e-20)
    assert np.isfinite(np.asarray(w)).all()


def share_layer(held, experts=16, d=32, f=16, k=4):
    gate = TopKGate(d, experts, k, score="sigmoid", select_bias=True,
                    renorm_eps=1e-20, scale=2.5)
    count = experts if held is None else held[1]
    return MOELayer(gate, GatedExpertFFN(d, f, count), held=held,
                    shared=GatedExpertFFN(d, f, 1))


def test_the_shares_add_up():
    """One routed layer of 16 experts, 4 a token: the outputs of the eight
    shares of two experts each, the shared expert counted once, sum to the
    uncut layer's, and that is the reference's arithmetic (every expert of
    every token times its weight, plus the shared expert). Each share's
    counts are its own experts' rows."""
    rng = jax.random.PRNGKey(5)
    whole = share_layer(None)
    p = whole.init(rng)
    p["gate"]["bias"] = 0.05 * jax.random.normal(rng, (16,))
    p = jax.tree.map(lambda a: 4 * a, p)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 32))
    full, _, counts = whole.apply_routed(p, x, renormalize=True)
    assert int(counts.sum()) == 40 * 4

    def swiglu(u, e):
        return (jax.nn.silu(u @ e["w_gate"]) * (u @ e["w_up"])) @ e["w_down"]

    shared = swiglu(x, p["shared"])
    weights = route(jax.nn.sigmoid(x @ p["gate"]["wg"]), p["gate"]["bias"],
                    4, True, 1e-20, 2.5)                             # [S, E]
    by_hand = shared + sum(
        weights[:, e:e + 1] * swiglu(x, jax.tree.map(lambda a: a[e],
                                                     p["experts"]))
        for e in range(16))
    np.testing.assert_allclose(full, by_hand, atol=2e-5)
    total = shared
    for i in range(8):
        held = (2 * i, 2)
        mine = dict(p, experts=jax.tree.map(lambda a: a[2 * i:2 * i + 2],
                                            p["experts"]))
        y, _, c = share_layer(held).apply_routed(mine, x, renormalize=True)
        np.testing.assert_array_equal(c, counts[2 * i:2 * i + 2])
        total = total + (y - shared)
    np.testing.assert_allclose(total, full, atol=2e-5)
    # a share that holds them all is the uncut layer, to the last bit
    assert share_layer((0, 16)).held is None


def test_an_absent_experts_rows_add_nothing_even_where_they_lie():
    """Rows of no group are masked after the grouped matmuls: NaN parked in
    the one weight block a stray row could read changes nothing."""
    layer = share_layer((4, 2))
    p = layer.init(jax.random.PRNGKey(7))
    x = jax.random.normal(jax.random.PRNGKey(8), (16, 32))
    y, _, counts = layer.apply_routed(p, x, renormalize=True)
    assert np.isfinite(np.asarray(y)).all() and int(counts.sum()) < 16 * 4


@pytest.mark.parametrize("held", [(4, 2), None])
def test_the_capacity_dispatch_refuses_a_share_and_a_shared_expert(held):
    """``MOELayer.apply`` knows neither: it says so, and no shape error of
    the dispatch says it for it. The family counts no training FLOPs."""
    layer = share_layer(held)            # both cases have a shared expert
    p = layer.init(jax.random.PRNGKey(7))
    with pytest.raises(NotImplementedError, match="held.*shared expert"):
        layer.apply(p, jnp.zeros((16, 32)), train=False)
    assert tiny().flops_per_token(128) is None


def test_the_seeded_bias_changes_some_choices_and_no_tie_hides():
    """``weights_kexaone.BIAS_SPREAD`` is several times the gap between the
    eighth and ninth score at the published width of the router."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((512, 128)) * 1.5, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(128) *
                       weights_kexaone.BIAS_SPREAD, jnp.float32)
    _, with_bias = topk_route(logits, 8, True, "sigmoid", bias, 1e-20)
    _, without = topk_route(logits, 8, True, "sigmoid", None, 1e-20)
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(with_bias), np.asarray(without))])
    assert 0.1 < moved < 0.95, moved


# ------------------------------------------------- fences and the records

@pytest.mark.parametrize("block, names", [
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    ({"speculative": {"enabled": True, "k": 2,
                      "draft": {"mode": "self", "layers": 1}}}, "speculative"),
    ({"chunked_prefill": {"enabled": True, "chunk_tokens": 16}},
     "chunked_prefill")])
def test_what_leans_on_a_lane_valid_at_any_column_is_fenced(block, names):
    """Raised at construction, before a pool is allocated, naming the
    mechanism and the model's rings."""
    from deepspeed_tpu.runtime.config_utils import ConfigError
    from deepspeed_tpu.serving import ServingEngine
    engine, _ = engine_of()
    config = {"num_slots": 2, "max_model_len": 64, **block}
    with pytest.raises(ConfigError, match=names) as err:
        ServingEngine(engine, config)
    assert "rings" in str(err.value) and "wk" in str(err.value)
    assert not engine._slot_fns             # nothing was built


def serve(engine, config, prompts, new=2 * W + 3):
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


def test_an_int8_pool_holds_the_rings_and_every_tick_records_kv_live():
    """``kv_quant`` is not fenced for rings (a ring column is quantized
    once, when it is written, as a lane's): requests through an int8 pool
    stream the fp pool's tokens but where rounding decides. The fp run
    records ``serve/kv_live`` each decode tick: the active slots' columns
    over the lanes, and over the rings at most W a slot."""
    from deepspeed_tpu.telemetry import get_tracer
    engine, _ = engine_of()
    prompts = [IDS[0, :5], IDS[1, :19], IDS[0, 20:33]]
    config = {"num_slots": 2, "max_model_len": 64, "max_queue": 8}
    tracer = get_tracer()
    before, mark = tracer.phases_total, time.perf_counter_ns()
    plain = serve(engine, config, prompts)
    # this test's own records: the process-wide ring also holds those of
    # whatever served before it in this worker, with pools of other sizes
    live = [(a, b) for name, t0, _, a, b in tracer.phases()
            if name == "serve/kv_live" and t0 >= mark]
    assert tracer.phases_total > before and live
    assert all(0 < b <= a and b <= 2 * W for a, b in live)
    assert max(a for a, _ in live) > 2 * W          # lanes longer than rings
    int8 = serve(engine, dict(config, kv_quant={"enabled": True}), prompts)
    assert [len(x) for x in int8] == [len(x) for x in plain]
    assert [x[0] for x in int8] == [x[0] for x in plain]    # the prefills'
    # a decode step over the int8 round trip of a lane and its rings reads
    # what it reads over the lane itself, to int8's rounding
    from deepspeed_tpu.inference.kv_quant import pool_to_fp, quantize_pool
    model, w = tiny(), seeded()
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    _, cache = model.apply_with_cache(w, IDS[:, :32], cache, jnp.int32(0))
    step = lambda c: model.decode_with_slots(
        w, IDS[:, 32:33], c, jnp.full((2,), 32))[0]
    err = rel_rms(step(pool_to_fp(quantize_pool(cache), jnp.float32)),
                  step(cache))
    assert 0 < err < 0.05, err


def test_a_prefilled_lane_crosses_a_handoff_frame_and_decodes_on():
    """A lane after its prefill holds its rings as they stand at its last
    token: through ``KVHandoff.to_bytes`` / ``from_bytes`` (every leaf with
    its shape) into another pool's slot, the decode goes on as in the pool
    it came from."""
    from deepspeed_tpu.serving.fleet.handoff import KVHandoff
    engine, _ = engine_of()
    a = engine.init_slot_pool(2, 64)
    a, first = engine.slot_prefill(a, 1, IDS[0, :13])
    lane = engine.slot_extract_lane(a, 1)
    assert sorted(lane) == ["k", "v", "wk", "wv"]
    frame = KVHandoff(prompt=IDS[0, :13], first_token=first, kv_len=13,
                      lane=lane, temperature=0.0, max_new_tokens=8,
                      eos_token_id=None, request_id=7, source="test")
    back = KVHandoff.from_bytes(frame.to_bytes())
    assert {k: v.shape for k, v in back.lane.items()} == \
        {k: v.shape for k, v in lane.items()}
    b = engine.init_slot_pool(3, 64)
    b = engine.slot_insert_lane(b, 2, jax.tree.map(jnp.asarray, back.lane))

    def decode(pool, slot, n):
        toks, pos = np.zeros(n, np.int32), np.zeros(n, np.int32)
        out, tok = [], first
        for j in range(13, 13 + 2 * W):
            toks[slot], pos[slot] = tok, j
            pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                                np.zeros(n, np.float32))
            tok = int(nxt[slot])
            out.append(tok)
        return out

    assert decode(a, 1, 2) == decode(b, 2, 3)
