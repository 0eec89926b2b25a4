"""What PR 36 adds to the benchmark, as new files beside the old (after
``test_chipbench_olmoe.py``, which does the same for PR 29): the LFM2-24B-A2B
configuration file against the published config, its counts by hand, its
reference against the system through ``jobs/serve_arch.py``'s own builder,
the traffic of both new cells against ISSUE 36's, and the
``mixer_decode_hbm_share`` reader on a made-up trace. The rehearsals of both
new cells are cases of
``test_chipbench_run.py::test_rehearsal_ends_in_one_result_line``, which
reads the manifest."""

import contextlib
import importlib
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_lfm2, weights                       # noqa: E402
from chipbench.generators import openloop_lognormal              # noqa: E402
from chipbench.jobs import serve_arch                            # noqa: E402
from chipbench.model import load_json, merge                     # noqa: E402
from chipbench.trace import Device, Trace                        # noqa: E402

CELL = "lfm2-24b-a2b.serve-agent-4k"
CARRIED = "opt-1.3b.serve-longprompt"
SEED = 2**31 + 36
ESEED = weights.engine_seed(SEED)
F32_TOL = 2e-5          # summation order only (tests/unit/test_lfm2.py)

PERIOD = ["full_attention", "conv", "conv", "conv"]
#: https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + PERIOD * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def config():
    return load_json("configs", "lfm2-24b-a2b.json")


def test_configuration_is_the_published_one_cut_in_depth_alone():
    cfg = config()
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "layer_types"}
    assert cfg["num_hidden_layers"] == 9 and cfg["num_dense_layers"] == 1
    # published layers 1-9: one leading (dense) conv layer, two whole periods
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:10] == \
        ["conv"] + PERIOD * 2
    assert len(PUBLISHED["layer_types"]) == 40
    assert [r.split()[0].rstrip(":") for r in cfg["reduced"]] == \
        ["num_hidden_layers", "num_dense_layers", "layer_types"]
    assert "40 -> 9" in cfg["reduced"][0] and "2 -> 1" in cfg["reduced"][1]
    man = load_json(os.pardir, "BENCHMARK.json")
    entry, = [c for c in man["configs"] if c["name"] == "lfm2-24b-a2b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    assert entry["source"] == cfg["source"]
    assert {"tie_word_embeddings", "chunk_order", "renorm_eps",
            "dims.layers"} <= set(cfg["assumed"])
    dims, kw = cfg["dims"], cfg["program"]["kwargs"]
    for dim, key, kwarg in (
            ("d_model", "hidden_size", "n_embd"),
            ("dense_ff", "intermediate_size", "mlp_hidden"),
            ("expert_ff", "moe_intermediate_size", "moe_intermediate_size"),
            ("heads", "num_attention_heads", "n_head"),
            ("kv_heads", "num_key_value_heads", "n_kv_head"),
            ("experts", "num_experts", "num_experts"),
            ("top_k", "num_experts_per_tok", "top_k"),
            ("dense_layers", "num_dense_layers", "num_dense_layers"),
            ("layer_types", "layer_types", "layer_types"),
            ("conv_taps", "conv_L_cache", "conv_L_cache"),
            ("vocab", "vocab_size", "vocab_size"),
            ("rms_eps", "norm_eps", "layer_norm_epsilon"),
            ("norm_topk_prob", "norm_topk_prob", "norm_topk_prob"),
            ("use_expert_bias", "use_expert_bias", "use_expert_bias"),
            ("routed_scaling_factor", "routed_scaling_factor",
             "routed_scaling_factor")):
        assert dims[dim] == cfg[key] == kw[kwarg], dim
    assert dims["rope_theta"] == kw["rope_theta"] == \
        cfg["rope_parameters"]["rope_theta"]
    assert kw["n_layer"] == cfg["num_hidden_layers"] == len(dims["layer_types"])
    # dims.layers counts the ROUTED layers: what serve_moe.py multiplies
    assert dims["layers"] == cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assert dims["head_dim"] * dims["heads"] == dims["d_model"]
    assert kw["tie_word_embeddings"] is True
    # the program's own defaults are the published model
    from deepspeed_tpu.models.lfm2 import LFM2_24B_A2B as full
    assert list(full.layer_types) == PUBLISHED["layer_types"]
    assert (full.n_layer, full.num_dense_layers, full.mlp_hidden,
            full.moe_intermediate_size, full.top_k, full.conv_L_cache) == \
        (40, 2, 11776, 1536, 4, 3)


def test_counts_of_the_configuration():
    dims = config()["dims"]
    gb = 1e9
    assert counts_lfm2.expert_bytes(dims) == 3 * 2048 * 1536 * 2 == 18874368
    # the reckoning of ISSUE 36 and of the configuration file, in GB
    conv = 7 * (2048 * 6144 + 2048 * 3 + 2048 * 2048 + 2048) * 2
    attn = 2 * (2048 * 48 * 64 + 2048 * 2048 + 2048 + 128) * 2
    dense = (3 * 2048 * 11776 + 2048) * 2
    routers = 8 * (2048 * 64 + 64 + 2048) * 2
    table = 65536 * 2048 * 2
    assert (round(conv / gb, 3), round(attn / gb, 3), round(dense / gb, 3),
            round(routers / gb, 3), round(table / gb, 3)) == \
        (0.235, 0.042, 0.145, 0.002, 0.268)
    outside = conv + attn + dense + routers + table + 2048 * 2
    assert counts_lfm2.non_expert_weight_bytes(dims) == outside
    assert counts_lfm2.total_weight_bytes(dims) == \
        outside + 8 * 64 * 18874368
    assert round(counts_lfm2.total_weight_bytes(dims) / gb, 2) == 10.36
    # K and V of the two attention layers only; the state of the seven conv
    assert counts_lfm2.kv_bytes_per_token(dims) == 2 * 2 * 8 * 64 * 2 == 4096
    assert counts_lfm2.state_bytes_per_slot(dims) == 7 * 2 * 2048 * 2
    assert counts_lfm2.non_expert_decode_bytes(dims, 1000, 40) == \
        outside + 1000 * 4096 + 2 * 40 * 57344
    # a decode tick that touches every expert reads every weight (the tied
    # table once, as the head); one that touches none what lies outside
    assert counts_lfm2.decode_bytes(dims, 8 * 64, 0) == \
        counts_lfm2.total_weight_bytes(dims)
    assert counts_lfm2.decode_bytes(dims, 0, 10) == outside + 10 * 4096
    assert counts_lfm2.expert_flops(dims, 1000) == \
        1000 * 8 * 4 * 6 * 2048 * 1536
    # the cell's prompts are bound by the experts' weights, its longest
    # bucket too (9.66 GB of them against 4 x 18.9 MFLOP a token a layer);
    # FLOPs bind past about 5,100 tokens
    for tokens, compute_bound in ((768, False), (4096, False), (8192, True)):
        f = counts_lfm2.expert_flops(dims, tokens) / 197e12
        b = counts_lfm2.expert_io_bytes(dims, tokens, 8 * 64) / 819e9
        assert (f > b) is compute_bound, tokens


def tiny():
    cell = load_json("workloads", CELL + ".json")
    cfg = merge(config(), cell["rehearse"]["config"])
    ctx = types.SimpleNamespace(config=cfg, cell={"model_overrides": None})
    return ctx, cfg["dims"]


def forward_error(dtype):
    import deepspeed_tpu
    ctx, dims = tiny()
    ctx.cell = {"model_overrides": {"dtype": dtype}}
    model, _ = serve_arch._build(ctx)
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": dtype, "max_tokens": 64, "seed": ESEED})
    ids = np.random.default_rng(1).integers(0, dims["vocab"], (2, 48),
                                            dtype=np.int32)
    got = np.asarray(engine.forward(ids), np.float32)[..., :dims["vocab"]]
    maker = importlib.import_module(ctx.config["benchmark"]["weights"])
    reference = importlib.import_module(ctx.config["benchmark"]["reference"])
    w = maker.make(dims, weights.seed_key(SEED))
    want = np.stack([np.asarray(reference.logits(w, row, dims))
                     for row in ids])
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_engine_forward_against_the_named_reference(dtype, ok):
    """The builder, weights maker and reference the configuration names, as
    ``serve_arch`` takes them (the rehearsal's stack: a dense conv layer,
    then two periods of attention, conv): the engine's own seed reproduces
    the reference's weights; a lower precision than stated fails."""
    err = forward_error(dtype)
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


@contextlib.contextmanager
def planted(fault):
    """The program with one fault of the cache path planted in it (``None``:
    as it is). ``column``: a decode step feeds its token one cache column
    late, so a column of the prefill's padding lies among the keys and every
    distance to the prompt is one too long. ``stale_state``: a decode step
    reads the conv state and does not write it, so every conv layer goes on
    seeing the prompt's last two rows. Used here at the rehearsal's size, and
    by the builder's chip runs at the cell's (PERF.md section 2)."""
    from deepspeed_tpu.models.gpt2 import GPT2Model
    decode, shift = GPT2Model.decode_with_slots, GPT2Model._state_shift

    def late(self, params, ids, cache, positions, **kw):
        return decode(self, params, ids, cache, positions + 1, **kw)

    def stale(state, layer, rows, lengths=None):
        hist, leaf = shift(state, layer, rows, lengths)
        return hist, (state if rows.shape[1] == 1 else leaf)

    if fault == "column":
        GPT2Model.decode_with_slots = late
    elif fault == "stale_state":
        GPT2Model._state_shift = staticmethod(stale)
    else:
        assert fault is None, fault
    try:
        yield
    finally:
        GPT2Model.decode_with_slots = decode
        GPT2Model._state_shift = staticmethod(shift)


def served_gap(fault):
    """``serve_arch.check``'s second number at the rehearsal's size in
    float32: requests through ``ServingEngine``, each streamed token's
    teacher-forced reference logit under the row's arg-max, over the row's
    largest |logit|; the largest over all tokens."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import SamplingParams, ServingEngine
    ctx, dims = tiny()
    ctx.cell = {"model_overrides": {"dtype": "float32"}}
    model, _ = serve_arch._build(ctx)
    maker = importlib.import_module(ctx.config["benchmark"]["weights"])
    reference = importlib.import_module(ctx.config["benchmark"]["reference"])
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, dims["vocab"], n, dtype=np.int32)
               for n in (5, 16, 23, 37)]
    out = {}
    with planted(fault):
        engine = deepspeed_tpu.init_inference(
            model, config={"dtype": "float32", "max_tokens": 64,
                           "seed": ESEED})
        srv = ServingEngine(engine, {"num_slots": 3, "max_model_len": 64,
                                     "max_queue": 8})
        rids = [srv.submit(p, SamplingParams(max_new_tokens=12),
                           on_token=lambda r, t: out.setdefault(
                               r.request_id, []).append(int(t)))
                for p in prompts]
        srv.run_until_idle()
        srv.shutdown()
    w = maker.make(dims, weights.seed_key(SEED))
    worst = 0.0
    for rid, p in zip(rids, prompts):
        toks = np.asarray(out[rid], np.int32)
        seq = np.concatenate([p, toks])
        rows = np.asarray(reference.logits(w, seq, dims))[
            len(p) - 1:len(seq) - 1, :dims["vocab"]]
        gap = (rows.max(-1) - rows[np.arange(len(toks)), toks]) / \
            np.abs(rows).max(-1)
        worst = max(worst, float(gap.max()))
    return worst


@pytest.mark.parametrize("fault, low, high", [
    (None, 0.0, 1e-4), ("column", 0.06, 2.01), ("stale_state", 0.06, 2.01)])
def test_token_gap_reads_the_cache_path(fault, low, high):
    """``token_argmax_gap`` through the system: nothing in float32 as the
    program is; with a cache column or a conv state one step off, 0.127
    and 1.96 here (a chosen token's logit can lie two largest |logit|
    under the arg-max), over twice the cell's limit at the least."""
    assert low <= served_gap(fault) < high


def test_reference_control_in_fp8_fails_the_same_tolerance():
    import jax
    from chipbench import reference, reference_lfm2, weights_lfm2
    _, dims = tiny()
    w = weights_lfm2.make(dims, jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    want = np.asarray(reference_lfm2.logits(w, ids, dims))
    got = np.asarray(reference_lfm2.logits(w, ids, dims, reference.fp8))
    err = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
    assert err > 100 * F32_TOL


def test_reference_routes_by_hand():
    """``route``: the scores at the top k of score + bias, the bias left
    out of the weight, renormalised with the epsilon in the sum."""
    import jax.numpy as jnp
    from chipbench.reference_lfm2 import route
    s = jnp.asarray([[0.9, 0.5, 0.2, 0.8], [0.1, 0.2, 0.3, 0.4]])
    bias = jnp.asarray([0.0, 0.5, 0.0, 0.0])
    np.testing.assert_allclose(
        route(s, bias, 2, False), [[0.9, 0.5, 0, 0], [0, 0.2, 0, 0.4]])
    np.testing.assert_allclose(
        route(s, bias, 2, True, eps=0.1, scale=2.0),
        [[1.8 / 1.5, 1.0 / 1.5, 0, 0], [0, 0.4 / 0.7, 0, 0.8 / 0.7]],
        rtol=1e-6)
    np.testing.assert_allclose(route(s, 0 * bias, 1, False),
                               [[0.9, 0, 0, 0], [0, 0, 0, 0.4]])


@pytest.mark.parametrize("cell_name, prompt, output, model_len", [
    (CELL, {"median": 768, "sigma": 0.9, "min": 64, "max": 3072},
     {"median": 160, "sigma": 0.7, "min": 16, "max": 768}, 4096),
    (CARRIED, {"median": 1280, "sigma": 0.3, "min": 768, "max": 1920},
     {"median": 32, "sigma": 0.5, "min": 16, "max": 64}, 2048)])
def test_traffic_is_the_issues(cell_name, prompt, output, model_len):
    cell = load_json("workloads", cell_name + ".json")
    t = load_json("traffic", cell["traffic"] + ".json")
    assert t["generator"] == "openloop_lognormal"
    assert t["prompt"] == prompt and t["output"] == output
    # CELL's band of its knee and where its p95 gap lies:
    # test_chipbench_placement.py (PR 57)
    assert cell_name == CELL or \
        abs(t["rate_per_s"] / t["knee"]["knee_per_s"] - 0.8) < 0.03
    assert cell["serving"]["max_model_len"] == model_len == \
        cell["inference"]["max_tokens"] == cell["check"]["reference_len"]
    assert cell["serving"]["num_slots"] % 4 == 0
    assert cell["trace_ticks"] == 640 and cell["chips"] == 1
    assert set(cell["serving"]) == {"num_slots", "max_model_len",
                                    "max_queue"}        # every feature off
    vocab = load_json("configs", cell["config"] + ".json")["dims"]["vocab"]
    reqs = openloop_lognormal.generate(t, SEED, vocab, 40.0)
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= model_len
    # every prefill bucket the traffic can reach is warmed, and no other
    pow2 = lambda n: min(1 << max(0, n - 1).bit_length(), model_len)
    warmed = [pow2(n) for n in cell["warm_prompt_lengths"]]
    reach = {pow2(n) for n in range(prompt["min"],
                                    prompt["max"] + output["max"] + 1)}
    assert sorted(warmed) == sorted(reach) and len(set(warmed)) == len(warmed)
    assert {pow2(len(r["prompt"])) for r in reqs} <= reach
    assert all(n + 3 <= model_len for n in cell["warm_prompt_lengths"])
    # a request that was running when the window opened ends inside history
    start = t["steady_start"]
    assert start["history_s"] * 1e3 >= output["max"] * start["tick_ms"] * 0.95
    assert any(r["due"] < 0 for r in reqs)


# ------------------------------------------- the mixer_decode_hbm_share reader

def made_up(ops, modules, live=(1000, 3000), occupancy=(0.25, 0.75),
            counts=counts_lfm2):
    from chipbench.layer_metrics import serve_hybrid
    ctx = types.SimpleNamespace(
        cell={"job": "serve", "moe_kernels": {"pattern": "ragged-dot"},
              "modules": {"prefill": "^jit_pf$", "decode": "^jit_dec$"},
              "serving": {"num_slots": 40}},
        dims=config()["dims"], counts=counts, log=lambda msg: None,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    trace = Trace([Device("/device:TPU:0", ops, modules)],
                  [(0.0, 0.2, "window")])
    record = {"live_tokens": list(live), "occupancy": list(occupancy),
              "vocab_rows": 65536}
    return serve_hybrid.METRICS["mixer_decode_hbm_share"], ctx, record, trace


OPS = [(0.000, 0.010, "ragged-dot-none.1 tpu_custom_call"),     # decode 1
       (0.010, 0.012, "ragged-dot-metadata"),
       (0.012, 0.040, "fusion.3"),
       (0.110, 0.122, "ragged-dot-none.2 tpu_custom_call"),     # the prefill
       (0.130, 0.144, "ragged-dot-none.1 tpu_custom_call"),     # decode 2
       (0.144, 0.170, "fusion.3")]
MODULES = [(0.0, 0.05, "jit_dec"), (0.105, 0.125, "jit_pf"),
           (0.125, 0.175, "jit_dec")]


def test_mixer_reader_on_a_made_up_trace():
    """Two decode programs of 50 ms each, 12 and 14 ms of them in the
    grouped matmuls (the prefill's are another module's): 37 ms a program
    outside them, against the bytes of 2000 live tokens and 20 slots."""
    read, ctx, record, trace = made_up(OPS, MODULES)
    need = counts_lfm2.non_expert_decode_bytes(ctx.dims, 2000, 20, 2, 65536)
    got = read(ctx, record, trace)
    assert got == pytest.approx(100 * need / 819e9 / 0.037)
    assert 0 < got < 100


def test_mixer_reader_returns_nothing_where_there_is_nothing_to_read():
    """Without a trace, in a cell without ``moe_kernels`` or a decode
    module, with another architecture's counts, without the window's live
    tokens, without a decode program in the window: the line leaves the
    metric out, nothing raises."""
    from chipbench import counts_olmoe
    read, ctx, record, trace = made_up(OPS, MODULES)
    assert read(ctx, record, None) is None
    assert read(ctx, dict(record, live_tokens=[]), trace) is None
    assert read(ctx, record, made_up(OPS, [(0.1, 0.12, "jit_pf")])[3]) is None
    other = made_up(OPS, MODULES, counts=counts_olmoe)
    assert other[0](other[1], other[2], other[3]) is None
    ctx.counts = None
    assert read(ctx, record, trace) is None
    ctx.counts = counts_lfm2
    del ctx.cell["moe_kernels"]
    assert read(ctx, record, trace) is None
