"""OLMoE (models/olmoe.py) and the routed, dropless expert layer
(moe/sharded_moe.py ``MOELayer.apply_routed``) against the plain float32
reference (chipbench/reference_olmoe.py) at a size the CPU holds: full
forward; prefill then decode through the slot pool against the reference's
full forward, LOGITS, not tokens; the same in a lower precision fails the
same tolerance; ``norm_topk_prob`` false against true; an expert that gets
no token beside one that gets all.

Tolerance. float32 system against float32 reference differ by summation
order only (the program adds a token's k expert outputs, the reference all E
with zero weights; fused q/k/v against three slices): 2e-5 of the logits'
RMS holds with a margin of 5 (readings 2e-6 .. 4e-6); bfloat16 reads 4e-3
and more, two hundred times over.

A router tie. A token whose k-th and (k+1)-th probabilities agree to
rounding may pick differently in the program than in the reference. In
bfloat16 on the chip at published widths (8 layers, 2 x 256 tokens, two
seeds; my chip run, PR 29) 0.55-0.60% of the 32,768 picks differ from the
float32 reference's, one pick in 4.4-4.7% of the (token, layer) pairs, the
swapped experts' probability 0.031 on average, and the whole comparison
still reads 0.008-0.0095 against a limit of 0.014 (PERF.md, section 2).
The comparison does not mask such tokens: one flipped pick replaces one
expert's output by another's, each scaled by that (equal, and for the k-th
of 64 small) probability, so its error is bounded by p_k * (|y_a| + |y_b|)
on that token, and it enters the relative RMS over all tokens like any
other error. In float32 at these sizes no pick flips: the seeded router's
k-th and (k+1)-th probabilities differ by more than 1e-6 on every token
(asserted below, so a flip can not hide behind the tolerance).
"""

import dataclasses
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference_olmoe, weights_olmoe              # noqa: E402
from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel    # noqa: E402
from deepspeed_tpu.moe.experts import ExpertFFN, GatedExpertFFN   # noqa: E402
from deepspeed_tpu.moe.sharded_moe import (MOELayer, TopKGate,    # noqa: E402
                                           topk_route)

F32_TOL = 2e-5
DIMS = {"layers": 2, "d_model": 128, "heads": 2, "kv_heads": 2,
        "head_dim": 64, "experts": 8, "top_k": 2, "expert_ff": 64,
        "vocab": 512, "positions": 128, "rope_theta": 10000.0,
        "rms_eps": 1e-5, "norm_topk_prob": False}


def tiny(dtype="float32", **over):
    cfg = OLMoEConfig(vocab_size=512, n_positions=128, n_embd=128, n_layer=2,
                      n_head=2, mlp_hidden=64, num_experts=8, top_k=2,
                      dtype=dtype, **over)
    return OLMoEModel(cfg)


def seeded(dtype=jnp.float32):
    w = weights_olmoe.make(DIMS, jax.random.PRNGKey(29))
    return jax.tree.map(lambda a: a.astype(dtype), w)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


IDS = np.random.default_rng(1).integers(0, 512, (2, 48), dtype=np.int32)


def reference_logits(w, dims=DIMS):
    return np.stack([np.asarray(reference_olmoe.logits(w, row, dims))
                     for row in IDS])


def test_weights_tree_is_the_models_tree():
    shapes = jax.eval_shape(tiny().init, jax.random.PRNGKey(0))
    made = jax.eval_shape(lambda k: weights_olmoe.make(DIMS, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, shapes) == \
        jax.tree.map(lambda a: a.shape, made)


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_forward_against_the_reference(dtype, ok):
    """``model.logits`` (what ``engine.forward`` runs), routed path."""
    w = seeded()
    got = tiny(dtype).logits(jax.tree.map(
        lambda a: a.astype(jnp.dtype(dtype)), w), jnp.asarray(IDS),
        train=False)
    err = rel_rms(got, reference_logits(w))
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


def pool_logits(model, w, dtype):
    """Prefill 40 tokens into two lanes of a slot pool, then decode the
    next 8 one tick at a time (teacher-forced): [2, 48, V] logits."""
    cache = model.init_kv_cache(2, 64, dtype=dtype)
    out, cache = model.apply_with_cache(w, jnp.asarray(IDS[:, :40]), cache, 0)
    rows = [out]
    for t in range(40, 48):
        step, cache = model.decode_with_slots(
            w, jnp.asarray(IDS[:, t:t + 1]), cache, jnp.array([t, t]))
        rows.append(step)
    return jnp.concatenate(rows, axis=1)


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_prefill_then_decode_through_the_pool_against_the_full_forward(dtype,
                                                                       ok):
    w = seeded()
    dt = jnp.dtype(dtype)
    got = pool_logits(tiny(dtype), jax.tree.map(lambda a: a.astype(dt), w), dt)
    want = reference_logits(w)
    err, err_decode = rel_rms(got, want), rel_rms(got[:, 40:], want[:, 40:])
    if ok:
        assert err < F32_TOL and err_decode < F32_TOL, (err, err_decode)
    else:
        assert err > 10 * F32_TOL and err_decode > 10 * F32_TOL


def test_no_router_tie_hides_behind_the_tolerance():
    """The seeded router's k-th and (k+1)-th probabilities are apart on
    every token of the comparison (layer 0's router on the embeddings'
    norm stands for both layers: same scale, same width)."""
    w = seeded()
    x = w["wte"][jnp.asarray(IDS.reshape(-1))]
    n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    p = jax.nn.softmax(n @ w["blocks"]["moe"]["gate"]["wg"][0], -1)
    top = np.sort(np.asarray(p), -1)[:, ::-1]
    assert (top[:, DIMS["top_k"] - 1] - top[:, DIMS["top_k"]]).min() > 1e-6


def test_norm_topk_prob_false_is_pinned_against_true():
    """OLMoE does not renormalise the picked probabilities; DeepSpeed's
    top-k does. Each program setting matches the reference of the same
    setting and misses the other by far more than the tolerance."""
    w = seeded()
    want = {flag: reference_logits(w, {**DIMS, "norm_topk_prob": flag})
            for flag in (False, True)}
    for flag in (False, True):
        got = tiny(norm_topk_prob=flag).logits(w, jnp.asarray(IDS),
                                               train=False)
        assert rel_rms(got, want[flag]) < F32_TOL
        assert rel_rms(got, want[not flag]) > 100 * F32_TOL


def dense_formula(layer, params, x, renormalize):
    """Every expert on every token, times its routing weight."""
    k, e = layer.gate.k, layer.gate.num_experts
    w, idx = topk_route(x @ params["gate"]["wg"], k, renormalize)
    full = jnp.zeros((x.shape[0], e)).at[
        jnp.arange(x.shape[0])[:, None], idx].set(w)
    every = layer.experts.apply(
        params["experts"], jnp.broadcast_to(x[None], (e,) + x.shape))
    return jnp.einsum("se,esm->sm", full, every)


@pytest.mark.parametrize("experts", [ExpertFFN, GatedExpertFFN])
def test_an_expert_with_no_token_and_one_with_all(experts):
    """Column 0 of the router is +1 and column 1 is -1 on inputs that are
    all positive: expert 0 is every token's first pick, expert 1 nobody's.
    The grouped matmul sees one group of S rows, one of 0."""
    layer = MOELayer(TopKGate(16, 8, k=2), experts(16, 32, 8))
    params = layer.init(jax.random.PRNGKey(3))
    wg = params["gate"]["wg"] * 0.1
    params["gate"]["wg"] = wg.at[:, 0].set(1.0).at[:, 1].set(-1.0)
    if "bi" in params["experts"]:       # biases that a wrong gather shows
        params["experts"]["bi"] = jax.random.normal(
            jax.random.PRNGKey(4), params["experts"]["bi"].shape) * 0.1
        params["experts"]["bo"] = jax.random.normal(
            jax.random.PRNGKey(5), params["experts"]["bo"].shape) * 0.1
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (12, 16))) + 0.5
    y, aux, counts = layer.apply_routed(params, x, renormalize=False)
    counts = np.asarray(counts)
    assert counts[0] == 12 and counts[1] == 0 and counts.sum() == 24
    assert float(aux) == 0.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        dense_formula(layer, params, x, False)), rtol=1e-5, atol=1e-6)


def test_routing_stats_ride_with_the_cache_forwards():
    """``routing=True``: (experts touched, largest count any expert got),
    summed over layers; a dense model returns None there."""
    model, w = tiny(), seeded()
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    _, cache, stats = model.apply_with_cache(
        w, jnp.asarray(IDS[:, :40]), cache, 0, routing=True)
    touched, largest = (int(v) for v in stats)
    assert 2 * 2 <= touched <= 2 * 8        # layers x (top_k .. experts)
    assert 2 * 20 <= largest <= 2 * 80      # layers x (80*2/8 .. 80 rows)
    _, _, stats = model.decode_with_slots(
        w, jnp.asarray(IDS[:, 40:41]), cache, jnp.array([40, 40]),
        routing=True)
    touched, largest = (int(v) for v in stats)
    assert 2 * 2 <= touched <= 2 * 4 and 2 * 1 <= largest <= 2 * 2
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
    dense = LlamaModel(LlamaConfig(vocab_size=512, n_embd=128, n_layer=2,
                                   n_head=2, mlp_hidden=64))
    dcache = dense.init_kv_cache(2, 64, dtype=jnp.float32)
    out = dense.apply_with_cache(dense.init(jax.random.PRNGKey(0)),
                                 jnp.asarray(IDS[:, :8]), dcache, 0,
                                 routing=True)
    assert len(out) == 3 and out[2] is None


def test_served_tokens_equal_generate_and_routing_is_taken_once():
    """``init_inference`` -> ``ServingEngine``: greedy tokens through the
    slot pool equal ``generate()``; the engine hands the routing stats of a
    prefill and of a decode tick over exactly once."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    model = tiny()
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "max_tokens": 64})
    srv = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    out = {}
    prompts = [IDS[0, :7], IDS[1, :19]]
    rids = [srv.submit(p, SamplingParams(max_new_tokens=5),
                       on_token=lambda r, t: out.setdefault(
                           r.request_id, []).append(int(t))) for p in prompts]
    srv.run_until_idle()
    srv.shutdown()
    for rid, p in zip(rids, prompts):
        want = np.asarray(engine.generate(p[None], max_new_tokens=5,
                                          temperature=0.0))[0, len(p):]
        assert out[rid] == want.tolist()
    assert engine.take_routing() is None        # the scheduler took them
    pool = engine.init_slot_pool(2, 64)
    pool, tok = engine.slot_prefill(pool, 0, prompts[0])
    assert isinstance(tok, int)
    touched, largest = engine.take_routing()
    assert 4 <= touched <= 16 and largest >= 2
    assert engine.take_routing() is None
    pool, nxt = engine.slot_decode_step(pool, np.zeros(2, np.int32),
                                        np.array([7, 0], np.int32),
                                        np.zeros(2, np.float32))
    assert nxt.shape == (2,) and engine.take_routing() is not None


def test_routed_streams_hold_through_the_decode_pipeline():
    """The scheduler keeps one decode step in flight (a request that times
    out leaves a row behind it that is computed and dropped; its slot is
    bound again under that step): a routed model's streams are still
    ``generate()``'s, its stats still ride one read-back a step, read with
    their own step's tokens, and ``serve/moe_decode`` is one record a step
    read."""
    import time
    import deepspeed_tpu
    from deepspeed_tpu.telemetry import get_tracer
    from .test_serving import serve_past_a_deadline
    engine = deepspeed_tpu.init_inference(
        tiny(), config={"dtype": "float32", "max_tokens": 64})
    mark = time.perf_counter_ns()
    m = serve_past_a_deadline(
        engine, [IDS[0, :7], IDS[1, :19], IDS[0, 20:31], IDS[1, 5:10]])
    assert m.dropped_rows == 1 and m.pipelined_ticks == m.decode_ticks - 1
    records = [r for r in get_tracer().phases()
               if r[1] >= mark and r[0] == "serve/moe_decode"]
    assert len(records) == m.decode_ticks
    assert engine.take_routing() is None        # each was taken, once


def test_int8_weights_cover_the_expert_leaves():
    """The program's own lower precision (the cell's control) quantizes the
    experts and the router, and the routed path runs on them."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.quantization import is_quantized
    engine = deepspeed_tpu.init_inference(
        tiny(), config={"dtype": "int8", "max_tokens": 64})
    moe = engine.params["blocks"]["moe"]
    assert all(is_quantized(moe["experts"][k])
               for k in ("w_gate", "w_up", "w_down"))
    assert not is_quantized(engine.params["lm_head"])
    # the router is quantized at the published width (64 experts), not at 8
    from deepspeed_tpu.inference.quantization import _default_predicate
    from jax.tree_util import DictKey
    path = tuple(DictKey(k) for k in ("blocks", "moe", "gate", "wg"))
    assert _default_predicate(path, jax.ShapeDtypeStruct((8, 2048, 64),
                                                         jnp.bfloat16))
    assert not is_quantized(moe["gate"]["wg"])
    logits = np.asarray(engine.forward(IDS[:, :16]), np.float32)
    assert np.isfinite(logits).all()


def test_training_keeps_the_capacity_path_and_its_aux_loss():
    model = tiny()
    params = model.init(jax.random.PRNGKey(0))
    loss = model.apply(params, {"input_ids": jnp.asarray(IDS)},
                       rng=jax.random.PRNGKey(1), train=True)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: model.apply(
        p, {"input_ids": jnp.asarray(IDS)}, rng=jax.random.PRNGKey(1),
        train=True))(params)
    g = grads["blocks"]["moe"]["experts"]["w_down"]
    assert float(jnp.abs(g).sum()) > 0


# --- the experts' stacked [L, E, ...] leaves read where they lie (PR 34) ---

def _rows(case, e, rng):
    """(group_sizes [E], rows) of three routings: spread, some experts
    empty, every row on one expert."""
    if case == "spread":
        sizes = rng.integers(1, 5, e)
    elif case == "empty experts":
        sizes = rng.integers(1, 5, e) * (np.arange(e) % 3 == 1)
    else:
        sizes = np.zeros(e, np.int64)
        sizes[e - 2] = 11
    return jnp.asarray(sizes, jnp.int32), int(sizes.sum())


@pytest.mark.parametrize("case", ["spread", "empty experts", "all on one"])
@pytest.mark.parametrize("experts", [ExpertFFN, GatedExpertFFN])
def test_stacked_leaves_at_a_layer_equal_that_layers_slice(experts, case):
    """``apply_grouped(..., layer=l)`` on the [L, E, ...] matmul leaves
    (biases this layer's own) equals the call on layer l's slices, every
    l: the groups of the other layers are empty and shift no row."""
    layers, e = 3, 8
    ffn = experts(16, 32, e)
    stacked = jax.vmap(ffn.init)(jax.random.split(jax.random.PRNGKey(7),
                                                  layers))
    for i, k in enumerate(("bi", "bo")):
        if k in stacked:                # biases that a wrong gather shows
            stacked[k] = jax.random.normal(jax.random.PRNGKey(8 + i),
                                           stacked[k].shape) * 0.1
    sizes, n = _rows(case, e, np.random.default_rng(3))
    ids = jnp.repeat(jnp.arange(e), sizes, total_repeat_length=n)
    x = jax.random.normal(jax.random.PRNGKey(9), (n, 16))
    for l in range(layers):
        sliced = jax.tree.map(lambda a: a[l], stacked)
        want = ffn.apply_grouped(sliced, x, sizes, ids)
        whole = {k: stacked[k] if k in ffn.matmul_leaves else v
                 for k, v in sliced.items()}
        got = jax.jit(lambda p, l: ffn.apply_grouped(p, x, sizes, ids,
                                                     layer=l))(whole, l)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(want).max()) > 0


@pytest.mark.parametrize("rows", [1, 4, 5, 8, 12])
@pytest.mark.parametrize("experts", [ExpertFFN, GatedExpertFFN])
def test_a_row_count_that_is_no_multiple_of_eight(experts, rows):
    """``apply_grouped`` appends zero rows up to a multiple of 8 and cuts
    them off again (``_whole_tiles``: the chip's kernel is wrong for a
    float32 count that is none; four picks of a one-slot pool): the rows
    that were there equal every expert's dense ``apply`` at the row's own
    expert, stacked leaves or one layer's."""
    e, m = 8, 16
    ffn = experts(m, 32, e)
    stacked = jax.vmap(ffn.init)(jax.random.split(jax.random.PRNGKey(7), 2))
    for i, k in enumerate(("bi", "bo")):
        if k in stacked:
            stacked[k] = jax.random.normal(jax.random.PRNGKey(8 + i),
                                           stacked[k].shape) * 0.1
    ids = jnp.sort(jax.random.randint(jax.random.PRNGKey(rows), (rows,), 0, e))
    sizes = jnp.bincount(ids, length=e).astype(jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(9), (rows, m))
    one = jax.tree.map(lambda a: a[1], stacked)
    want = ffn.apply(one, jnp.broadcast_to(x[None], (e, rows, m)),
                     train=False)[ids, jnp.arange(rows)]
    whole = {k: stacked[k] if k in ffn.matmul_leaves else v
             for k, v in one.items()}
    for params, layer in ((one, None), (whole, 1)):
        got = jax.jit(lambda p: ffn.apply_grouped(p, x, sizes, ids,
                                                  layer=layer))(params)
        assert got.shape == (rows, m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


@pytest.fixture
def grouped_calls(monkeypatch):
    """Every ``apply_grouped`` call made (traced) while the test runs:
    True where it was handed stacked leaves and a layer."""
    seen = []
    for cls in (ExpertFFN, GatedExpertFFN):
        def spy(self, params, x, sizes, ids=None, layer=None,
                _real=cls.apply_grouped):
            stacked = params[self.matmul_leaves[0]].ndim == 4
            assert stacked == (layer is not None)
            seen.append(stacked)
            return _real(self, params, x, sizes, ids, layer=layer)
        monkeypatch.setattr(cls, "apply_grouped", spy)
    return seen


def _gpt2_moe():
    from deepspeed_tpu.models.gpt2_moe import GPT2MoEConfig, GPT2MoEModel
    model = GPT2MoEModel(GPT2MoEConfig(
        vocab_size=512, n_positions=128, n_embd=32, n_layer=3, n_head=4,
        num_experts=4, top_k=2, pad_vocab_to_multiple=64))
    w = model.init(jax.random.PRNGKey(0))
    for i, k in enumerate(("bi", "bo")):
        w["blocks"]["moe"]["experts"][k] = jax.random.normal(
            jax.random.PRNGKey(20 + i),
            w["blocks"]["moe"]["experts"][k].shape) * 0.1
    return model, w


def _run(model, w, program):
    """(logits, routing stats or None) of one program of the model."""
    if program == "forward":            # what ``engine.forward`` runs
        return model.logits(w, jnp.asarray(IDS), train=False), None
    cache = model.init_kv_cache(2, 64, dtype=jnp.float32)
    out, cache, stats = model.apply_with_cache(
        w, jnp.asarray(IDS[:, :40]), cache, 0, routing=True)
    if program == "decode":
        out, cache, stats = model.decode_with_slots(
            w, jnp.asarray(IDS[:, 40:41]), cache, jnp.array([40, 40]),
            routing=True)
    return out, stats


@pytest.mark.parametrize("program", ["prefill", "decode", "forward"])
@pytest.mark.parametrize("family", ["olmoe", "gpt2_moe"])
def test_whole_leaves_give_the_logits_and_counts_the_slices_gave(
        family, program, grouped_calls, monkeypatch):
    """Both cache forwards and the uncached serving forward: the scan
    closes over the experts' matmul leaves whole, and logits and routing
    counts are those of the scan that sliced them (``take_whole`` made to
    keep the slice: the parent's program). GPT-2 MoE without a cache
    evaluates through the capacity dispatch, which no grouped matmul
    serves: untouched."""
    model, w = (tiny(), seeded()) if family == "olmoe" else _gpt2_moe()
    got, got_stats = _run(model, w, program)
    whole_calls = list(grouped_calls)
    del grouped_calls[:]
    monkeypatch.setattr(MOELayer, "take_whole", lambda self, p: (p, None))
    want, want_stats = _run(model, w, program)
    if family == "gpt2_moe" and program == "forward":
        assert whole_calls == [] and grouped_calls == []
    else:
        assert whole_calls and all(whole_calls)
        assert grouped_calls and not any(grouped_calls)
    assert rel_rms(got, want) < 1e-6
    if program != "forward":
        np.testing.assert_array_equal(np.asarray(got_stats),
                                      np.asarray(want_stats))


@pytest.mark.parametrize("keeps", ["int8", "expert 2"])
def test_the_slice_stays_for_int8_leaves_and_a_sharded_expert_axis(
        keeps, grouped_calls):
    """What ``take_whole`` sees decides, no option: a ``QuantizedWeight``
    leaf and a mesh whose ``expert`` axis is 2 keep the per-layer slice,
    and agree with the whole-leaf program on the same numbers (the int8
    engine's weights dequantised; the same weights on an ``expert`` 1
    mesh)."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.quantization import is_quantized
    from deepspeed_tpu.parallel import initialize_mesh
    fp = {"dtype": "float32", "max_tokens": 64}
    if keeps == "int8":
        kept = deepspeed_tpu.init_inference(
            tiny(), config={**fp, "quant": {"enabled": True}})
        assert is_quantized(
            kept.params["blocks"]["moe"]["experts"]["w_down"])
        w = jax.tree.map(
            lambda a: a.astype(jnp.float32) if is_quantized(a) else a,
            kept.params, is_leaf=is_quantized)
    else:
        w = seeded()
        kept = InferenceEngine(
            tiny(), DeepSpeedInferenceConfig.from_dict(fp), params=w,
            mesh_manager=initialize_mesh(dp=4, ep=2))
        spec = kept.params["blocks"]["moe"]["experts"]["w_down"].sharding.spec
        assert "expert" in tuple(spec), spec

    def drive(engine):
        logits = np.asarray(engine.forward(IDS[:, :16]))
        pool = engine.init_slot_pool(2, 64)
        pool, tok = engine.slot_prefill(pool, 0, IDS[0, :19])
        pool, nxt = engine.slot_decode_step(
            pool, np.array([tok, 0], np.int32), np.array([19, 0], np.int32),
            np.zeros(2, np.float32))
        return logits, tok, int(nxt[0]), engine.take_routing()

    got = drive(kept)
    assert grouped_calls and not any(grouped_calls)
    del grouped_calls[:]
    whole = InferenceEngine(tiny(), DeepSpeedInferenceConfig.from_dict(fp),
                            params=w)
    want = drive(whole)
    assert grouped_calls and all(grouped_calls)
    assert rel_rms(got[0], want[0]) < F32_TOL
    assert got[1:] == want[1:]
