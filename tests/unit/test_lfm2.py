"""LFM2-MoE (models/lfm2.py): a stack of layers of several kinds, served
through the slot pool beside the plain float32 reference
(chipbench/reference_lfm2.py) at a size the CPU holds. The conv layers keep
a recurrent state in the pool (the last K - 1 rows of ``B * X`` a slot), the
few attention layers keep K and V; a prefill is right-padded to a pow2
bucket and told its real length; the router is a sigmoid with a bias that
enters the choice and not the weight. Logits are compared, not tokens.

Tolerance: float32 system against float32 reference differ by summation
order only (``tests/unit/test_olmoe.py``): 2e-5 of the logits' RMS;
bfloat16 reads two hundred times over.
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference_lfm2, weights_lfm2                 # noqa: E402
from deepspeed_tpu.models.lfm2 import (ATTN, CONV, LFM2MoEConfig,  # noqa: E402
                                       LFM2MoEModel)
from deepspeed_tpu.moe.sharded_moe import topk_route               # noqa: E402

F32_TOL = 2e-5
PERIOD = (ATTN, CONV, CONV, CONV)
#: the cut's pattern (1 dense + 2 whole periods) and the published tail's
#: shape (a stack that ends ``attention, conv``: no whole period)
PATTERNS = {"periods": (CONV,) + PERIOD * 2,
            "tail": (CONV,) + PERIOD * 2 + (ATTN, CONV)}


def dims_of(types, dense=1):
    return {"layers": len(types) - dense, "dense_layers": dense,
            "layer_types": list(types), "conv_taps": 3, "d_model": 128,
            "heads": 4, "kv_heads": 2, "head_dim": 32, "dense_ff": 256,
            "experts": 8, "top_k": 2, "expert_ff": 64, "vocab": 512,
            "positions": 128, "rope_theta": 1000000.0, "rms_eps": 1e-5,
            "norm_topk_prob": True, "use_expert_bias": True,
            "routed_scaling_factor": 1.0, "renorm_eps": 1e-6}


def tiny(types=PATTERNS["periods"], dtype="float32", dense=1, **over):
    return LFM2MoEModel(LFM2MoEConfig(
        vocab_size=512, n_positions=128, n_embd=128, n_layer=len(types),
        n_head=4, n_kv_head=2, mlp_hidden=256, layer_types=tuple(types),
        num_dense_layers=dense, moe_intermediate_size=64, num_experts=8,
        top_k=2, dtype=dtype, **over))


def seeded(types=PATTERNS["periods"], dtype=jnp.float32, dense=1):
    w = weights_lfm2.make(dims_of(types, dense), jax.random.PRNGKey(36))
    return jax.tree.map(lambda a: a.astype(dtype), w)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


IDS = np.random.default_rng(1).integers(0, 512, (2, 48), dtype=np.int32)


def reference_logits(w, types, ids=IDS):
    return np.stack([np.asarray(reference_lfm2.logits(w, row, dims_of(types)))
                     for row in ids])


# ------------------------------------------------------------ the stack

def test_weights_tree_is_the_models_tree():
    for types in PATTERNS.values():
        shapes = jax.eval_shape(tiny(types).init, jax.random.PRNGKey(0))
        made = jax.eval_shape(lambda k: weights_lfm2.make(dims_of(types), k),
                              jax.random.PRNGKey(0))
        assert jax.tree.map(lambda a: a.shape, shapes) == \
            jax.tree.map(lambda a: a.shape, made)


@pytest.mark.parametrize("types, want", [
    (PATTERNS["periods"], (1, 4, 2)), (PATTERNS["tail"], (1, 4, 2)),
    ((CONV, CONV) + PERIOD * 9 + (ATTN, CONV), (2, 4, 9)),
    ((CONV, ATTN, CONV), (1, 0, 0))], ids=["cut", "tail", "published", "3"])
def test_the_pattern_is_split_into_lead_periods_and_tail(types, want):
    """Leading dense layers, the shortest run that repeats and how often;
    a stack with nothing to scan is walked layer by layer."""
    dense = 2 if len(types) == 40 else 1
    model = tiny(types, dense=dense)
    assert (model.lead, model.period, model.repeats) == want
    conv = sum(t == CONV for t in types)
    assert model.counts == {CONV: conv, ATTN: len(types) - conv,
                            "dense": dense, "moe": len(types) - dense}
    # every layer's index in its kind's stack, in layer order
    assert [l[1] for l in model.layers if l[0] == ATTN] == \
        list(range(len(types) - conv))
    assert [l[3] for l in model.layers if l[2] == "moe"] == \
        list(range(len(types) - dense))


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_forward_against_the_reference(pattern, dtype, ok):
    """``model.logits`` (what ``engine.forward`` runs): no cache, the conv
    starts from a zero history."""
    types = PATTERNS[pattern]
    w = seeded(types)
    got = tiny(types, dtype).logits(
        jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), w),
        jnp.asarray(IDS), train=False)
    err = rel_rms(got, reference_logits(w, types))
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


def pool_logits(model, w, dtype, real=37, bucket=64):
    """What ``slot_prefill`` then ``slot_decode_step`` compute: prefill
    ``real`` tokens right-padded to ``bucket`` into two lanes of a pool,
    told the real length, then decode the next ones a tick at a time
    (teacher-forced). [2, 48, V] logits."""
    cache = model.init_kv_cache(2, 64, dtype=dtype)
    ids = np.zeros((2, bucket), np.int32)
    ids[:, :real] = IDS[:, :real]
    out, cache = model.apply_with_cache(
        w, jnp.asarray(ids), cache, 0, lengths=jnp.array([real, real]))
    rows = [out[:, :real]]
    for t in range(real, 48):
        step, cache = model.decode_with_slots(
            w, jnp.asarray(IDS[:, t:t + 1]), cache, jnp.array([t, t]))
        rows.append(step)
    return jnp.concatenate(rows, axis=1)


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_padded_prefill_then_decode_through_the_pool_against_the_reference(
        pattern, dtype, ok):
    """The padded bucket's tail never enters the conv state: the decoded
    tokens' logits are the full-sequence reference's."""
    types = PATTERNS[pattern]
    w = seeded(types)
    dt = jnp.dtype(dtype)
    got = pool_logits(tiny(types, dtype),
                      jax.tree.map(lambda a: a.astype(dt), w), dt)
    want = reference_logits(w, types)
    err, err_decode = rel_rms(got, want), rel_rms(got[:, 37:], want[:, 37:])
    if ok:
        assert err < F32_TOL and err_decode < F32_TOL, (err, err_decode)
    else:
        assert err > 10 * F32_TOL and err_decode > 10 * F32_TOL


def test_without_the_real_length_the_padding_enters_the_state():
    """The control of the test above: the same padded bucket NOT told its
    length stores the state of the pad tail, and the first decoded token is
    far off."""
    types = PATTERNS["periods"]
    w, model = seeded(types), tiny(types)
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    ids = np.zeros((2, 64), np.int32)
    ids[:, :37] = IDS[:, :37]
    _, cache = model.apply_with_cache(w, jnp.asarray(ids), cache, 0)
    step, _ = model.decode_with_slots(w, jnp.asarray(IDS[:, 37:38]), cache,
                                      jnp.array([37, 37]))
    assert rel_rms(step, reference_logits(w, types)[:, 37:38]) > 1e-2


# ------------------------------------------------- the conv operator alone

def conv_reference(z, w):
    """c_t = sum_j w[:, j] z_{t-(K-1)+j}, z_{<0} = 0, by hand in numpy."""
    t, taps = z.shape[0], w.shape[1]
    out = np.zeros_like(z)
    for i in range(t):
        for j in range(taps):
            src = i - (taps - 1) + j
            if src >= 0:
                out[i] += w[:, j] * z[src]
    return out


def test_the_conv_operator_through_its_state_equals_the_whole_sequence():
    """One conv layer's operator: a padded prefill of 5 real tokens, then 6
    decode steps, each through ``_state_shift``, against the convolution of
    the whole sequence written out by hand."""
    from deepspeed_tpu.models.gpt2 import GPT2Model
    rng = np.random.default_rng(0)
    d, taps, total = 8, 3, 11
    model = tiny()
    p = {"ln1_scale": jnp.ones((d,)),
         "in_w": jnp.asarray(rng.normal(size=(d, 3 * d)), jnp.float32),
         "conv_w": jnp.asarray(rng.normal(size=(d, taps)), jnp.float32),
         "out_w": jnp.eye(d)}
    x = jnp.asarray(rng.normal(size=(1, total, d)), jnp.float32)
    whole = model._conv_sublayer(x, p) - x        # no state: one sequence
    u = np.asarray(x[0]) / np.sqrt(
        (np.asarray(x[0]) ** 2).mean(-1, keepdims=True) + 1e-5)
    b, c, v = np.split(u @ np.asarray(p["in_w"]), 3, axis=-1)
    by_hand = c * conv_reference(b * v, np.asarray(p["conv_w"]))
    np.testing.assert_allclose(np.asarray(whole[0]), by_hand, rtol=2e-4,
                               atol=2e-5)

    state = {"conv": jnp.zeros((2, 1, taps - 1, d))}     # two layers' leaf

    def through(block, lengths):
        def state_fn(name, rows):
            hist, state[name] = GPT2Model._state_shift(
                state[name], 1, rows, lengths)
            return hist
        return model._conv_sublayer(block, p, state_fn) - block

    padded = jnp.concatenate([x[:, :5], jnp.ones((1, 3, d))], axis=1)
    got = [through(padded, jnp.array([5]))[:, :5]]
    for t in range(5, total):
        got.append(through(x[:, t:t + 1], None))
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert not np.asarray(state["conv"][0]).any()    # the other layer's rows


@pytest.mark.parametrize("real", [1, 2, 5, 8])
def test_the_stored_state_is_that_of_the_last_real_token(real):
    """``_state_shift`` on a right-padded block of 8: what is kept is the
    last K - 1 rows up to the last real one (history fills in where the
    block is shorter than the state), the same as the block unpadded."""
    from deepspeed_tpu.models.gpt2 import GPT2Model
    rng = np.random.default_rng(real)
    hist = jnp.asarray(rng.normal(size=(1, 2, 2, 4)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(2, 8, 4)), jnp.float32)
    before, padded = GPT2Model._state_shift(hist, 0, rows,
                                            jnp.array([real, real]))
    _, plain = GPT2Model._state_shift(hist, 0, rows[:, :real])
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(before), np.asarray(hist[0]))
    want = np.concatenate([np.asarray(hist[0]), np.asarray(rows[:, :real])],
                          axis=1)[:, -2:]
    np.testing.assert_array_equal(np.asarray(padded[0]), want)


# --------------------------------------------------------------- the engine

def engine_of(types=PATTERNS["periods"], dtype="float32"):
    """(engine serving the seeded weights in ``dtype``, the weights)."""
    import deepspeed_tpu
    w = seeded(types)
    model = tiny(types)
    model.init = lambda rng: w
    return deepspeed_tpu.init_inference(
        model, config={"dtype": dtype, "max_tokens": 64}), w


def test_pool_dims_reads_a_kv_leaf_of_the_two_kind_pool():
    engine, _ = engine_of()
    pool = engine.init_slot_pool(3, 64)
    assert {k: v.shape for k, v in pool.items()} == {
        "k": (2, 3, 64, 1, 64), "v": (2, 3, 64, 1, 64),
        "conv": (7, 3, 2, 128)}
    assert sorted(pool)[0] == "conv"        # the leaf a blind probe reads
    assert engine._pool_dims(pool) == (3, 64, False)
    spent = pool
    pool, _ = engine.slot_prefill(pool, 0, IDS[0, :5])
    assert engine._pool_dims(pool) == (3, 64, False)
    with pytest.raises(RuntimeError, match="consumed"):
        engine._pool_dims(spent)            # donated to the prefill


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_slot_prefill_and_decode_stream_the_references_tokens(pattern):
    """``slot_prefill`` (bucket 16 for 13 tokens) then ``slot_decode_step``
    through the engine: every sampled token is the arg-max of the
    reference's full-sequence logits (float32; the margins are checked to
    be over the tolerance so no near-tie decides)."""
    types = PATTERNS[pattern]
    engine, w = engine_of(types)
    want = reference_logits(w, types, IDS[:1])[0]
    top2 = np.sort(want, -1)[:, -2:]
    assert ((top2[:, 1] - top2[:, 0]) > 1e-4 * np.abs(want).max()).all()
    pool = engine.init_slot_pool(3, 64)
    pool, tok = engine.slot_prefill(pool, 1, IDS[0, :13])
    assert tok == want[12].argmax()
    assert engine.take_routing() is not None
    toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
    for j in range(13, 30):
        toks[1], pos[1] = IDS[0, j], j
        pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                            np.zeros(3, np.float32))
        assert nxt[1] == want[j].argmax(), j
    touched, largest = engine.take_routing()
    routed = len(types) - 1                 # summed over the ROUTED layers
    assert routed <= touched <= routed * 8 and largest >= routed


def test_chunk_and_suffix_prefill_equal_one_prefill():
    """A prompt of 21 as one prefill, and as a whole chunk of 16 then a
    padded suffix of 5: the same first token and the same lane, the conv
    state too (the chunk leaves its state at its last token, the suffix
    goes on from it and stops at ITS last real token)."""
    engine, _ = engine_of()
    pool = engine.init_slot_pool(2, 64)
    pool, one = engine.slot_prefill(pool, 0, IDS[0, :21])
    pool = engine.slot_chunk_prefill(pool, 1, IDS[0, :16], 0)
    pool, two = engine.slot_suffix_prefill(pool, 1, IDS[0, 16:21], 16)
    assert one == two
    np.testing.assert_allclose(np.asarray(pool["conv"][:, 1]),
                               np.asarray(pool["conv"][:, 0]), rtol=1e-5,
                               atol=1e-6)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(pool[name][:, 1, :21]),
                                   np.asarray(pool[name][:, 0, :21]),
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="whole pow2 chunks"):
        engine.slot_chunk_prefill(pool, 1, IDS[0, :13], 0)


def test_what_is_not_supported_says_so():
    model, w = tiny(), seeded()
    with pytest.raises(NotImplementedError, match="ROADMAP B1"):
        model.apply(w, {"input_ids": jnp.asarray(IDS)}, train=True)
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="rolled back"):
        model.verify_with_slots(w, jnp.asarray(IDS[:, :4]), cache,
                                jnp.array([0, 0]))
    with pytest.raises(NotImplementedError, match="left-padded"):
        model.apply_with_cache(w, jnp.asarray(IDS[:, :8]), cache, 0,
                               pad_counts=jnp.array([0, 2]))
    with pytest.raises(NotImplementedError, match="pipeline"):
        model.pipeline_spec()
    with pytest.raises(ValueError, match="layer_types"):
        LFM2MoEModel(LFM2MoEConfig(n_layer=3, layer_types=(CONV, ATTN)))


def test_generate_runs_the_cached_forward():
    """``engine.generate`` (prefill at full length, then single steps at a
    scalar position) is the same cached forward: its greedy tokens are the
    slot path's."""
    engine, w = engine_of()
    out = np.asarray(engine.generate(IDS[:1, :13], max_new_tokens=6))
    pool = engine.init_slot_pool(1, 64)
    pool, tok = engine.slot_prefill(pool, 0, IDS[0, :13])
    got = [tok]
    for j in range(13, 18):
        pool, nxt = engine.slot_decode_step(
            pool, np.array([got[-1]], np.int32), np.array([j], np.int32),
            np.zeros(1, np.float32))
        got.append(int(nxt[0]))
    assert out[0, 13:].tolist() == got


def test_recurrent_streams_hold_through_the_decode_pipeline():
    """The scheduler keeps one decode step in flight: a request that times
    out leaves a row behind it that is computed and dropped, and it pushes
    into the slot's conv state like the dummy row of a free slot does. The
    request bound to that slot in the same tick prefills from nothing and
    streams ``generate()``'s tokens, as do the others."""
    from .test_serving import serve_past_a_deadline
    engine, _ = engine_of()
    m = serve_past_a_deadline(
        engine, [IDS[0, :7], IDS[1, :19], IDS[0, 20:31], IDS[1, 5:10]])
    assert m.dropped_rows == 1 and m.pipelined_ticks == m.decode_ticks - 1


def test_rules_cover_the_new_leaves():
    """Every parameter and every pool leaf meets a rule of its own rank."""
    from deepspeed_tpu.models.api import match_rule, param_path_tree
    model = tiny()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths = jax.tree.leaves(param_path_tree(shapes))
    for path, leaf in zip(paths, jax.tree.leaves(shapes)):
        spec = match_rule(path, model.partition_rules())
        assert spec is None or len(spec) <= leaf.ndim, path
        assert spec is not None or not path.startswith(("blocks/", "wte"))
    assert match_rule("blocks/moe/moe/experts/w_up",
                      model.partition_rules())[1] == "expert"
    cache = jax.eval_shape(lambda: model.init_kv_cache(2, 64))
    for name, leaf in cache.items():
        spec = match_rule(name, model.cache_partition_rules())
        assert len(spec) == leaf.ndim, name


def test_int8_weights_cover_the_new_large_leaves():
    """The program's own lower precision (the cell's control) quantizes the
    conv operator's projections, the dense FFN, the attention matrices and
    the experts; the filters, gains, bias and the tied table stay."""
    from deepspeed_tpu.inference.quantization import (_default_predicate,
                                                      is_quantized)
    from jax.tree_util import DictKey
    engine, _ = engine_of(dtype="int8")
    b = engine.params["blocks"]
    for kind, names in (("conv", ("in_w", "out_w")),
                        ("attn", ("qkv_w", "attn_proj_w")),
                        ("dense", ("gate_w", "up_w", "down_w"))):
        assert all(is_quantized(b[kind][n]) for n in names), kind
    assert all(is_quantized(b["moe"]["moe"]["experts"][n])
               for n in ("w_gate", "w_up", "w_down"))
    assert not is_quantized(b["conv"]["conv_w"])
    assert not is_quantized(b["moe"]["moe"]["gate"]["bias"])
    assert not is_quantized(engine.params["wte"])
    # the router is quantized at the published width (64 experts), not at 8
    path = tuple(DictKey(k) for k in ("blocks", "moe", "moe", "gate", "wg"))
    assert _default_predicate(path, jax.ShapeDtypeStruct((8, 2048, 64),
                                                         jnp.bfloat16))
    logits = np.asarray(engine.forward(IDS[:, :16]), np.float32)
    assert np.isfinite(logits).all()
    pool = engine.init_slot_pool(1, 64)
    pool, tok = engine.slot_prefill(pool, 0, IDS[0, :9])
    assert 0 <= tok < 512


# --------------------------------------------------------------- the router

def test_router_by_hand():
    """Sigmoid scores; the bias enters the CHOICE (expert 3 is picked over
    expert 0 because of it) and not the weight; the picks are divided by
    their sum plus 1e-6."""
    logits = jnp.asarray([[2.0, 0.0, -1.0, 1.5], [0.1, 0.2, 0.3, 0.4]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.2])
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    w, idx = topk_route(logits, 1, renormalize=False, score="sigmoid",
                        select_bias=bias)
    assert idx.tolist() == [[3], [3]]       # 0.8176 + 0.2 over 0.8808
    np.testing.assert_allclose(w[:, 0], s[:, 3], rtol=1e-6)
    w, idx = topk_route(logits, 2, renormalize=True, score="sigmoid",
                        select_bias=bias, renorm_eps=1e-6)
    assert idx.tolist() == [[3, 0], [3, 2]]
    for row, picks in enumerate(idx.tolist()):
        got = s[row, picks] / (s[row, picks].sum() + 1e-6)
        np.testing.assert_allclose(w[row], got, rtol=1e-6)
    assert float(w[0].sum()) < 1.0          # the epsilon is in the sum
    # without the bias the choice is the scores' own
    _, idx = topk_route(logits, 2, score="sigmoid")
    assert idx.tolist() == [[0, 3], [3, 2]]
    with pytest.raises(ValueError, match="router score"):
        topk_route(logits, 2, score="tanh")


@pytest.mark.parametrize("k, renormalize", [(1, None), (2, None), (8, False),
                                            (8, True)])
def test_the_softmax_router_is_what_it_was(k, renormalize):
    """OLMoE's and DeepSpeed's calls (no score, no bias, no epsilon given):
    the float32 softmax at the top k, renormalised over float32's epsilon
    where asked: bit for bit the formula it had."""
    logits = jnp.asarray(np.random.default_rng(k).normal(size=(33, 64)),
                         jnp.float32)
    w, idx = topk_route(logits, k, renormalize)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    want_w, want_idx = jax.lax.top_k(gates, k)
    if renormalize if renormalize is not None else k > 1:
        want_w = want_w / jnp.maximum(
            jnp.sum(want_w, axis=-1, keepdims=True),
            jnp.finfo(jnp.float32).eps)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))


def test_the_seeded_bias_changes_some_choices_and_no_tie_hides():
    """``weights_lfm2``'s bias moves the choice of experts for a good share
    of the tokens, and the seeded router's k-th and (k+1)-th biased scores
    are apart on every token of the comparison (layer 0's router on the
    embeddings' norm stands for all: same scale, same width)."""
    w = seeded()
    x = w["wte"][jnp.asarray(IDS.reshape(-1))]
    n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    gate = jax.tree.map(lambda a: a[0], w["blocks"]["moe"]["moe"]["gate"])
    s = jax.nn.sigmoid(n @ gate["wg"])
    _, plain = jax.lax.top_k(s, 2)
    _, biased = jax.lax.top_k(s + gate["bias"], 2)
    moved = np.mean(np.sort(plain, -1) != np.sort(biased, -1))
    assert 0.05 < moved < 0.95, moved
    top = np.sort(np.asarray(s + gate["bias"]), -1)[:, ::-1]
    assert (top[:, 1] - top[:, 2]).min() > 1e-6


# ------------------------------------------------- fences and the handoff

@pytest.mark.parametrize("block, names", [
    ({"prefix_cache": {"enabled": True}}, "prefix_cache"),
    ({"speculative": {"enabled": True, "k": 2,
                      "draft": {"mode": "self", "layers": 1}}}, "speculative"),
    ({"kv_quant": {"enabled": True}}, "kv_quant"),
    ({"chunked_prefill": {"enabled": True, "chunk_tokens": 16}},
     "chunked_prefill")])
def test_what_leans_on_a_lane_valid_at_any_column_is_fenced(block, names):
    """Raised at construction, before a pool is allocated, naming the
    mechanism and the model's recurrent state."""
    from deepspeed_tpu.runtime.config_utils import ConfigError
    from deepspeed_tpu.serving import ServingEngine
    engine, _ = engine_of()
    config = {"num_slots": 2, "max_model_len": 64, **block}
    with pytest.raises(ConfigError, match=names) as err:
        ServingEngine(engine, config)
    assert "recurrent state" in str(err.value) and "conv" in str(err.value)
    assert not engine._slot_fns             # nothing was built


def test_a_dense_model_takes_the_fenced_features():
    """The fence asks the model, not the feature: OPT with a prefix cache
    is served as before."""
    import deepspeed_tpu
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel
    from deepspeed_tpu.serving import ServingEngine
    engine = deepspeed_tpu.init_inference(
        OPTModel(OPTConfig(vocab_size=256, n_positions=64, n_embd=64,
                           n_layer=2, n_head=2)),
        config={"dtype": "float32", "max_tokens": 64})
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64,
                                 "prefix_cache": {"enabled": True}})
    srv.shutdown()


def test_a_chunk_at_column_zero_starts_from_nothing_on_a_used_lane():
    """What ``ServingEngine`` fences, shown at the engine: (a) a chunked
    prompt into a slot another request has LEFT, while a third decodes,
    equals one prefill, as long as no decode step falls between its chunks
    (a chunk at column 0 takes no state from the lane); (b) one decode step
    between the chunks pushes its dummy row into the half-filled lane's
    state and the lane is no longer the prompt's."""
    engine, _ = engine_of()
    pool = engine.init_slot_pool(3, 64)
    pool, want = engine.slot_prefill(pool, 0, IDS[0, :21])
    pool, _ = engine.slot_prefill(pool, 1, IDS[1, :30])      # the occupant
    pool, tok2 = engine.slot_prefill(pool, 2, IDS[1, 30:40])

    def step(pool, tok2, at):
        toks, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        toks[2], pos[2] = tok2, at
        pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                            np.zeros(3, np.float32))
        return pool, int(nxt[2])

    pool, tok2 = step(pool, tok2, 10)       # scribbles on lanes 0 and 1 too
    assert np.abs(np.asarray(pool["conv"][:, 1])).max() > 0
    pool = engine.slot_chunk_prefill(pool, 1, IDS[0, :16], 0)
    pool, got = engine.slot_suffix_prefill(pool, 1, IDS[0, 16:21], 16)
    sound = np.array(pool["conv"][:, 1], copy=True)
    pool, again = engine.slot_prefill(pool, 0, IDS[0, :21])
    assert got == want == again
    np.testing.assert_allclose(sound, np.asarray(pool["conv"][:, 0]),
                               rtol=1e-5, atol=1e-6)
    pool = engine.slot_chunk_prefill(pool, 1, IDS[0, :16], 0)
    pool, tok2 = step(pool, tok2, 11)
    pool, _ = engine.slot_suffix_prefill(pool, 1, IDS[0, 16:21], 16)
    assert np.abs(np.asarray(pool["conv"][:, 1]) - sound).max() > 1e-3


def test_a_prefilled_lane_crosses_a_handoff_frame_and_decodes_on():
    """A lane after its prefill holds the state of its last token: through
    ``KVHandoff.to_bytes`` / ``from_bytes`` (every leaf with its shape, the
    conv state's too) into another pool's slot, the decode goes on as in
    the pool it came from."""
    from deepspeed_tpu.serving.fleet.handoff import KVHandoff
    engine, _ = engine_of()
    a = engine.init_slot_pool(2, 64)
    a, first = engine.slot_prefill(a, 1, IDS[0, :13])
    lane = engine.slot_extract_lane(a, 1)
    assert sorted(lane) == ["conv", "k", "v"]
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
        for j in range(13, 19):
            toks[slot], pos[slot] = tok, j
            pool, nxt = engine.slot_decode_step(pool, toks, pos,
                                                np.zeros(n, np.float32))
            tok = int(nxt[slot])
            out.append(tok)
        return out

    assert decode(a, 1, 2) == decode(b, 2, 3)
