"""What PR 29 adds to the benchmark, as new files beside the old (the files
the benchmark already had may not be edited, so the new configuration's cases
of ``test_chipbench_reference.py`` and ``test_chipbench_counts.py`` live
here; the rehearsals of both new cells are cases of
``test_chipbench_run.py::test_rehearsal_ends_in_one_result_line``, which
reads the manifest): the OLMoE configuration file against the published
config, its counts, its reference against the system through
``jobs/serve_arch.py``'s own builder, the ``moe_*`` readers on a made-up
trace, the traffic file, and ``jobs/train_sharded.py``'s sharded weights."""

import importlib
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_olmoe, weights                     # noqa: E402
from chipbench.generators import openloop_lognormal             # noqa: E402
from chipbench.jobs import serve_arch                           # noqa: E402
from chipbench.model import load_json, merge                    # noqa: E402
from chipbench.trace import Device, Trace                       # noqa: E402

CELL = "olmoe-1b-7b.serve-chat-2k"
SEED = 2**31 + 29
ESEED = weights.engine_seed(SEED)
F32_TOL = 2e-5          # summation order only (tests/unit/test_olmoe.py)

#: https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct config.json
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def config():
    return load_json("configs", "olmoe-1b-7b.json")


def test_configuration_is_the_published_one_cut_in_depth_alone():
    cfg = config()
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 8
    assert cfg["reduced"] == ["num_hidden_layers 16 -> 8"]
    man = load_json(os.pardir, "BENCHMARK.json")
    entry, = [c for c in man["configs"] if c["name"] == "olmoe-1b-7b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    dims, kw = cfg["dims"], cfg["program"]["kwargs"]
    for dim, key, kwarg in (("d_model", "hidden_size", "n_embd"),
                            ("expert_ff", "intermediate_size", "mlp_hidden"),
                            ("heads", "num_attention_heads", "n_head"),
                            ("kv_heads", "num_key_value_heads", "n_kv_head"),
                            ("experts", "num_experts", "num_experts"),
                            ("top_k", "num_experts_per_tok", "top_k"),
                            ("layers", "num_hidden_layers", "n_layer"),
                            ("vocab", "vocab_size", "vocab_size"),
                            ("rope_theta", "rope_theta", "rope_theta"),
                            ("rms_eps", "rms_norm_eps", "layer_norm_epsilon"),
                            ("norm_topk_prob", "norm_topk_prob",
                             "norm_topk_prob")):
        assert dims[dim] == cfg[key] == kw[kwarg], dim
    assert dims["head_dim"] * dims["heads"] == dims["d_model"]
    assert kw["tie_word_embeddings"] is cfg["tie_word_embeddings"] is False


def test_counts_of_the_configuration():
    dims = config()["dims"]
    gb = 1e9
    assert counts_olmoe.expert_bytes(dims) == 3 * 2048 * 1024 * 2
    # the reckoning of the configuration file: 8 x 0.839 + 0.412 = 7.125 GB
    assert round(counts_olmoe.total_weight_bytes(dims) / gb, 3) == 7.125
    per_layer = (counts_olmoe.total_weight_bytes(dims) -
                 2 * 50304 * 2048 * 2 - 2048 * 2) / 8
    assert round(per_layer / gb, 3) == 0.839
    assert counts_olmoe.kv_bytes_per_token(dims) == 65536
    # a decode tick that touches every expert reads every weight but the
    # embedding table; one that touches none reads what lies outside them
    every = counts_olmoe.decode_bytes(dims, 8 * 64, 0)
    assert every == counts_olmoe.total_weight_bytes(dims) - 50304 * 2048 * 2
    assert counts_olmoe.decode_bytes(dims, 0, 10) == \
        counts_olmoe.non_expert_weight_bytes(dims) + 10 * 65536
    # ISSUE 29: tokens x 8 x 6 x 2048 x 1024 a layer
    assert counts_olmoe.expert_flops(dims, 1000) == \
        1000 * 8 * 8 * 6 * 2048 * 1024
    # a median prompt is bound by the experts' weights, not by FLOPs; a
    # prompt over 1925 tokens (two thirds of all experts' bytes a token
    # short of the ridge) by FLOPs
    for tokens, compute_bound in ((384, False), (1024, False), (4096, True)):
        f = counts_olmoe.expert_flops(dims, tokens) / 197e12
        b = counts_olmoe.expert_io_bytes(dims, tokens, 8 * 64) / 819e9
        assert (f > b) is compute_bound, tokens


def tiny():
    cell = load_json("workloads", CELL + ".json")
    cfg = merge(config(), cell["rehearse"]["config"])
    ctx = types.SimpleNamespace(config=cfg, cell={"model_overrides": None})
    return ctx, cfg["dims"]


def forward_error(dtype):
    import deepspeed_tpu
    import jax
    import jax.numpy as jnp
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
    ``serve_arch`` takes them: the engine's own seed reproduces the
    reference's weights; a lower precision than stated fails."""
    err = forward_error(dtype)
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


def test_reference_control_in_fp8_fails_the_same_tolerance():
    import jax
    from chipbench import reference, reference_olmoe, weights_olmoe
    _, dims = tiny()
    w = weights_olmoe.make(dims, jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    want = np.asarray(reference_olmoe.logits(w, ids, dims))
    got = np.asarray(reference_olmoe.logits(w, ids, dims, reference.fp8))
    err = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
    assert err > 100 * F32_TOL


def test_reference_routes_by_hand():
    """``route`` keeps the top k of each row's probabilities as they are
    (OLMoE) or renormalised over the k (``norm_topk_prob``)."""
    import jax.numpy as jnp
    from chipbench.reference_olmoe import route
    p = jnp.asarray([[0.1, 0.4, 0.2, 0.3], [0.7, 0.1, 0.1, 0.1]])
    np.testing.assert_allclose(route(p, 2, False),
                               [[0, 0.4, 0, 0.3], [0.7, 0.1, 0, 0]])
    np.testing.assert_allclose(route(p, 2, True),
                               [[0, 4 / 7, 0, 3 / 7], [0.875, 0.125, 0, 0]],
                               rtol=1e-6)


def test_traffic_is_the_issues():
    t = load_json("traffic", "serve-chat-2k.json")
    assert t["generator"] == "openloop_lognormal"
    assert t["prompt"] == {"median": 384, "sigma": 0.9, "min": 32, "max": 1536}
    assert t["output"] == {"median": 128, "sigma": 0.7, "min": 16, "max": 512}
    # the band of the knee: test_chipbench_placement.py (PR 57)
    cell = load_json("workloads", CELL + ".json")
    reqs = openloop_lognormal.generate(t, SEED, 50304, 40.0)
    longest = max(len(r["prompt"]) + r["max_new"] for r in reqs)
    assert longest <= cell["serving"]["max_model_len"] == 2048
    assert cell["serving"]["num_slots"] % 4 == 0
    # every prefill bucket the traffic can hit is warmed
    pow2 = lambda n: 1 << max(0, n - 1).bit_length()
    warmed = {min(pow2(n), 2048) for n in cell["warm_prompt_lengths"]}
    assert {min(pow2(len(r["prompt"])), 2048) for r in reqs} <= warmed
    # a request that was running when the window opened ends inside history
    start = t["steady_start"]
    assert start["history_s"] * 1e3 >= 512 * start["tick_ms"] * 0.95


# ------------------------------------------------- the moe_* readers

def made_up(records, ops, modules, live=(1000, 3000)):
    """A ctx, record and trace whose two ticks hold ``records``."""
    from chipbench.layer_metrics import _program_spans as P
    from chipbench.layer_metrics import serve_moe
    dims = config()["dims"]
    ticks = [(0.0, 0.1, "serve/tick", 1, 24), (0.1, 0.2, "serve/tick", 2, 24)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    ctx = types.SimpleNamespace(
        cell={"job": "serve", "moe_kernels": {"pattern": "ragged-dot"},
              "modules": {"prefill": "^jit_pf$", "decode": "^jit_dec$"},
              "serving": {"num_slots": 24}},
        dims=dims, counts=counts_olmoe, log=lambda msg: None,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        state={"program_spans": (placed, {})})
    trace = Trace([Device("/device:TPU:0", ops, modules)],
                  [(0.0, 0.2, "window")])
    record = {"live_tokens": list(live), "vocab_rows": 50304}
    return serve_moe.METRICS, ctx, record, trace


def test_moe_readers_on_a_made_up_trace():
    recs = [(0.05, 0.05, "serve/moe_decode", 400, 64),
            (0.15, 0.15, "serve/moe_decode", 440, 96),
            (0.11, 0.12, "serve/prefill_prep", 384, 512),
            (0.13, 0.13, "serve/moe_prefill", 512, 900)]
    ops = [(0.00, 0.01, "ragged-dot-none.1 tpu_custom_call"),      # decode
           (0.01, 0.05, "fusion.3"),
           (0.110, 0.122, "ragged-dot-none.2 tpu_custom_call"),    # prefill
           (0.13, 0.14, "ragged-dot-none.1 tpu_custom_call")]      # decode
    mods = [(0.0, 0.08, "jit_dec"), (0.105, 0.125, "jit_pf"),
            (0.125, 0.2, "jit_dec")]
    m, ctx, record, trace = made_up(recs, ops, mods)
    dims = ctx.dims
    assert m["moe_experts_touched"](ctx, record, trace) == \
        pytest.approx(100 * 420 / 512)
    # largest a layer (80 / 8 = 10) over the mean count (24 x 8 / 64 = 3)
    assert m["moe_load_skew"](ctx, record, trace) == pytest.approx(10 / 3)
    need = counts_olmoe.decode_bytes(dims, 420, 2000, 2, 50304)
    assert m["moe_decode_hbm_share"](ctx, record, trace) == \
        pytest.approx(100 * need / 819e9 / ((0.08 + 0.075) / 2))
    assert m["moe_ffn_share"](ctx, record, trace) == \
        pytest.approx(100 * 0.032 / 0.072)
    least = max(counts_olmoe.expert_flops(dims, 384) / 197e12,
                counts_olmoe.expert_io_bytes(dims, 384, 512) / 819e9)
    assert m["moe_prefill_roofline"](ctx, record, trace) == \
        pytest.approx(100 * least / 0.012)
    assert 0 < m["moe_prefill_roofline"](ctx, record, trace) < 100


def test_moe_readers_return_nothing_without_routing_records():
    """A dense model, or the parent of the PR that added the records: the
    line leaves the metrics out, nothing raises."""
    ops = [(0.0, 0.05, "fusion.3")]
    m, ctx, record, trace = made_up([], ops, [(0.0, 0.08, "jit_dec")])
    assert all(fn(ctx, record, trace) is None for fn in m.values())
    del ctx.cell["moe_kernels"]         # a cell without an expert layer
    assert all(fn(ctx, record, trace) is None for fn in m.values())
    assert all(fn(ctx, record, None) is None for fn in m.values())


def test_every_run_logs_the_medians_of_the_programs_phase_records():
    """``serve_arch.measure`` says where a run's ticks spent their time,
    traced or not: records inside the timed seconds only, instants left
    out."""
    from types import SimpleNamespace
    from chipbench.jobs import serve_arch
    from deepspeed_tpu.telemetry import get_tracer
    base, ms = 10 ** 17, 10 ** 6        # stamps no other test's records have
    tracer = get_tracer()
    for k, wait in enumerate((50, 54, 70)):
        t = base + k * 100 * ms
        tracer.record_phase("serve/decode_wait", t + ms, t + (1 + wait) * ms)
        tracer.record_phase("serve/moe_decode", t + 80 * ms, t + 80 * ms, 9, 3)
        tracer.record_phase("serve/tick", t, t + 90 * ms, k, 1)
    lines = []
    serve_arch._log_phase_medians(SimpleNamespace(log=lines.append),
                                  base, base + 200 * ms)
    assert lines == ["phase medians ms over 2 ticks: "
                     "serve/decode_wait 52.000, serve/tick 90.000"]


def test_sharded_reference_weights_are_the_seeded_ones_split_four_ways():
    """``jobs/train_sharded.py`` makes the reference's float32 weights split
    over the cell's devices; values are ``weights.make``'s."""
    import jax
    from chipbench.jobs import train_sharded
    from chipbench.model import build
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    cell = load_json("workloads", "opt-1.3b.train-z3-dp4.json")
    cfg = merge(load_json("configs", "opt-1.3b.json"),
                cell["rehearse"]["config"])
    model, dims = build(cfg)
    ctx = types.SimpleNamespace(
        state={"model": model}, dims=dims, devices=jax.devices()[:4],
        args=types.SimpleNamespace(seed=SEED))
    w = train_sharded._sharded_weights(ctx)
    plain = weights.make(dims, weights.seed_key(SEED),
                         positions=model.config.n_positions,
                         vocab_multiple=model.config.pad_vocab_to_multiple)
    for got, want in zip(jax.tree.leaves(w), jax.tree.leaves(plain)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)      # jitted against eager
        assert len(got.sharding.device_set) == 4
    qkv = w["blocks"]["qkv_w"]
    assert qkv.addressable_shards[0].data.shape[-1] == qkv.shape[-1] // 4
    assert cell["engine"]["zero_optimization"] == {
        "stage": 3, "stage3_param_persistence_threshold": 0}
    t = load_json("traffic", cell["traffic"] + ".json")
    assert cell["engine"]["train_batch_size"] == t["gas"] * t["rows"] == 32
    assert t["rows"] == 4 * cell["engine"]["train_micro_batch_size_per_gpu"]
