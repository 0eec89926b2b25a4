"""What PR 38 adds to the benchmark, as new files beside the old (after
``test_chipbench_lfm2.py``, which does the same for PR 36): the
K-EXAONE-236B-A23B configuration file against the published config, its
counts by hand, its reference against the system through
``jobs/serve_arch.py``'s own builder, the traffic of both new cells against
ISSUE 38's, the two readers of ``layer_metrics/serve_window.py`` on a
made-up trace, and the faults the builder planted on the chip to place the
limits of ``correct``. The rehearsals of both new cells are cases of
``test_chipbench_run.py::test_rehearsal_ends_in_one_result_line``, which
reads the manifest."""

import contextlib
import importlib
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_kexaone, weights                    # noqa: E402
from chipbench.jobs import serve_arch                            # noqa: E402
from chipbench.layer_metrics import serve_window                 # noqa: E402
from chipbench.model import load_json, merge                     # noqa: E402

CELL = "k-exaone-236b-a23b.serve-longdoc-16k"
CARRIED = "opt-1.3b.serve-backlog"
SEED = 2**31 + 38
ESEED = weights.engine_seed(SEED)
F32_TOL = 2e-5          # summation order only (tests/unit/test_kexaone.py)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}


def config():
    return load_json("configs", "k-exaone-236b-a23b.json")


@contextlib.contextmanager
def planted(fault):
    """The program with one fault planted in it (``None``: as it is).
    Of the cache path: ``column``: a decode step feeds its token one column
    late: its ring column and its lane column are the next one's, a column
    of the prefill's padding lies among the full layer's keys, and every
    rotated distance to the prompt is one too long. ``no_band``: a window
    layer's prefill attends every earlier key of the strip beside its block
    of queries, not the ``window`` before each query (the band mask is the
    only 2-D mask ``_kv_attend`` is handed). Of the share of the experts:
    ``no_routed``: the held experts' term is left out (what the grouped
    matmuls give counts as zero; the shared expert and the router stay).
    ``next_share``: the layer takes its experts for the NEXT chip's
    (``expert_offset`` + the number held: 16 at the cell's size): pairs
    routed to those are computed with this chip's weights, and its own are
    dropped. Used here at the rehearsal's size, and by the builder's chip
    runs at the cell's (PERF.md section 2)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Model
    from deepspeed_tpu.moe.experts import GatedExpertFFN
    from deepspeed_tpu.moe.sharded_moe import MOELayer
    decode, attend = GPT2Model.decode_with_slots, GPT2Model._kv_attend
    grouped, layer_init = GatedExpertFFN.apply_grouped, MOELayer.__init__

    def late(self, params, ids, cache, positions, **kw):
        return decode(self, params, ids, cache, positions + 1, **kw)

    def unbanded(q, k_pool, v_pool, layer, mask, bias):
        if getattr(mask, "ndim", 0) == 2:
            block, band = mask.shape
            apart = band - block + jnp.arange(block)[:, None] - \
                jnp.arange(band)[None, :]
            mask = (apart >= 0) & mask.any(axis=0)[None, :]
        return attend(q, k_pool, v_pool, layer, mask, bias)

    def nothing(self, *args, **kw):
        return 0 * grouped(self, *args, **kw)

    def next_share(self, gate, experts, *args, held=None, **kw):
        layer_init(self, gate, experts, *args,
                   held=held and (held[0] + held[1], held[1]), **kw)

    if fault == "column":
        GPT2Model.decode_with_slots = late
    elif fault == "no_band":
        GPT2Model._kv_attend = staticmethod(unbanded)
    elif fault == "no_routed":
        GatedExpertFFN.apply_grouped = nothing
    elif fault == "next_share":
        MOELayer.__init__ = next_share
    else:
        assert fault is None, fault
    try:
        yield
    finally:
        GPT2Model.decode_with_slots = decode
        GPT2Model._kv_attend = staticmethod(attend)
        GatedExpertFFN.apply_grouped = grouped
        MOELayer.__init__ = layer_init


# ------------------------------------------------- the configuration file

S, F = "sliding_attention", "full_attention"
#: https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": [S, S, S, F] * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": [F], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}


def test_published_is_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures beside the guides here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "K-EXAONE-236B-A23B" in line]
    assert rows[0]["config"] == PUBLISHED
    assert rows[0]["source_url"] == config()["source"]


def test_configuration_is_the_published_one_with_exactly_the_listed_cuts():
    cfg = config()
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "k-exaone-236b-a23b"][0]
    assert set(entry["reduced"]) == REDUCED and entry["source"] == cfg["source"]
    assert len(cfg["reduced"]) == len(REDUCED)
    # published layers 0-4: the dense layer and one whole period, rotated
    assert cfg["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cfg[key] == PUBLISHED[key][:5], key
    assert cfg["layer_types"][1:] == [S, S, F, S]
    assert (cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (16, 19200, 0)
    # the floors of a cut: four routed layers, 8 experts, an eighth of the rows
    assert cfg["mlp_layer_types"].count("sparse") == 4
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert set(cfg["assumed"]) >= {"sublayer_norms", "qk_norm",
                                   "rope_by_layer_kind", "router"}
    assert "eight chips" in cfg["deployment"]
    # what the program is built with is what the file says
    dims, kw = cfg["dims"], cfg["program"]["kwargs"]
    assert (dims["d_model"], dims["heads"], dims["kv_heads"],
            dims["head_dim"], dims["dense_ff"], dims["expert_ff"],
            dims["top_k"], dims["window"], dims["router_experts"],
            dims["shared_experts"]) == \
        (6144, 64, 8, 128, 18432, 2048, 8, 128, 128, 1)
    assert (dims["layers"], dims["experts"], dims["expert_offset"],
            dims["vocab"]) == (4, 16, 0, 19200)
    assert dims["layer_types"] == kw["layer_types"] == cfg["layer_types"]
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["mlp_hidden"], kw["moe_intermediate_size"], kw["top_k"],
            kw["sliding_window"], kw["num_experts"], kw["experts_held"],
            kw["vocab_size"], kw["n_layer"], kw["first_k_dense_replace"],
            kw["num_shared_experts"], kw["routed_scaling_factor"]) == \
        (6144, 64, 8, 128, 18432, 2048, 8, 128, 128, [0, 16], 19200, 5, 1,
         1, 2.5)
    from deepspeed_tpu.models.kexaone import K_EXAONE_236B_A23B as full
    assert list(full.layer_types) == PUBLISHED["layer_types"]
    assert (full.n_layer, full.n_embd, full.n_head, full.head_dim,
            full.num_experts, full.top_k, full.vocab_size) == \
        (48, 6144, 64, 128, 128, 8, 153600)


def test_counts_of_the_configuration():
    c, dims, gb = counts_kexaone, config()["dims"], 1e9
    assert c.expert_bytes(dims) == 3 * 6144 * 2048 * 2 == 75497472
    attn = (6144 * 80 * 128 + 8192 * 6144 + 6144 + 256) * 2
    dense = (3 * 6144 * 18432 + 6144) * 2
    routed = (6144 * 128 + 128 + 3 * 6144 * 2048 + 6144) * 2
    table = 19200 * 6144 * 2
    assert (round(attn / gb, 4), round(dense / gb, 4), round(routed / gb, 4),
            round(table / gb, 3)) == (0.2265, 0.6795, 0.0771, 0.236)
    outside = 5 * attn + dense + 4 * routed + table + 6144 * 2
    assert c.non_expert_weight_bytes(dims) == outside
    assert c.total_weight_bytes(dims) == outside + table + 4 * 16 * 75497472
    assert round(c.total_weight_bytes(dims) / gb, 2) == 7.42
    # the one full layer keeps a token's K and V; the four rings 128 columns
    assert c.kv_bytes_per_token(dims) == 2 * 8 * 128 * 2 == 4096
    assert c.ring_bytes_per_column(dims) == 4 * 4096
    assert c.state_bytes_per_slot(dims) == 4 * 128 * 4096 == 2097152
    assert c.pool_bytes(dims, 48, 16384) == 48 * (16384 * 4096 + 2097152)
    assert round(c.pool_bytes(dims, 48, 16384) / gb, 2) == 3.32
    # every layer at full length would be 16.1 GB
    assert round(48 * 16384 * 5 * 4096 / gb, 1) == 16.1
    assert c.live_kv_bytes(dims, 1000, 128) == 1000 * 4096 + 128 * 16384
    assert c.live_kv_bytes(dims, 48 * 16384, 48 * 128) == \
        c.pool_bytes(dims, 48, 16384)
    assert c.non_expert_decode_bytes(dims, 1000, 40) == \
        outside + 1000 * 4096 + 40 * 2097152
    assert c.decode_bytes(dims, 4 * 16, 0) == c.total_weight_bytes(dims) - table
    # a token's 8 picks of 128 fall on the 16 held once, over four layers
    assert c.held_pairs(dims, 1000) == 1000 * 4 * 8 * 16 / 128 == 4000
    assert c.expert_flops(dims, 1000) == 4000 * 6 * 6144 * 2048
    assert c.expert_io_bytes(dims, 1000, 64) == \
        64 * 75497472 + 4000 * (3 * 6144 + 4 * 2048) * 2
    # the held experts' weights bind the shorter buckets, FLOPs the longer
    for tokens, compute_bound in ((1024, False), (4096, False), (16384, True)):
        f = c.expert_flops(dims, tokens) / 197e12
        b = c.expert_io_bytes(dims, tokens, 64) / 819e9
        assert (f > b) is compute_bound, tokens


# -------------------------------------- the reference, through serve_arch

def tiny():
    cell = load_json("workloads", CELL + ".json")
    cfg = merge(config(), cell["rehearse"]["config"])
    ctx = types.SimpleNamespace(config=cfg, cell={"model_overrides": None})
    return ctx, cfg["dims"]


def forward_error(dtype, fault=None):
    import deepspeed_tpu
    ctx, dims = tiny()
    ctx.cell = {"model_overrides": {"dtype": dtype}}
    ids = np.random.default_rng(1).integers(0, dims["vocab"], (2, 48),
                                            dtype=np.int32)
    with planted(fault):
        model, _ = serve_arch._build(ctx)
        engine = deepspeed_tpu.init_inference(
            model, config={"dtype": dtype, "max_tokens": 64, "seed": ESEED})
        got = np.asarray(engine.forward(ids),
                         np.float32)[..., :dims["vocab"]]
    maker = importlib.import_module(ctx.config["benchmark"]["weights"])
    reference = importlib.import_module(ctx.config["benchmark"]["reference"])
    w = maker.make(dims, weights.seed_key(SEED))
    want = np.stack([np.asarray(reference.logits(w, row, dims))
                     for row in ids])
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.mark.parametrize("dtype, ok", [("float32", True), ("bfloat16", False)])
def test_engine_forward_against_the_named_reference(dtype, ok):
    """The builder, weights maker and reference the configuration names, as
    ``serve_arch`` takes them (the rehearsal's stack, a share of 4 of 16
    experts): the engine's own seed reproduces the reference's weights; a
    lower precision than stated fails."""
    err = forward_error(dtype)
    assert (err < F32_TOL) if ok else (err > 10 * F32_TOL), err


@pytest.mark.parametrize("fault", ["no_routed", "next_share"])
def test_forward_error_reads_the_share(fault):
    """``logits_rel_rms_err`` through the system in float32 (sound: under
    ``F32_TOL``, the test above): with the held experts' term left out, or
    the layer taking its experts for the next chip's, over twice the cell's
    limit at the least."""
    limit = load_json("workloads", CELL + ".json")["check"][
        "logits_rel_rms_err"]
    assert forward_error("float32", fault) > 2 * limit


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
        rids = [srv.submit(p, SamplingParams(max_new_tokens=20),
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
    (None, 0.0, 1e-4), ("column", 0.12, 2.01), ("no_band", 0.12, 2.01)])
def test_token_gap_reads_the_cache_path(fault, low, high):
    """``token_argmax_gap`` through the system: nothing in float32 as the
    program is; with a decode step one column late or a window layer's
    prefill without its band, over twice the cell's limit at the least."""
    limit = load_json("workloads", CELL + ".json")["check"]["token_argmax_gap"]
    gap = served_gap(fault)
    assert low <= gap < high, gap
    assert fault is None or gap > 2 * limit


def test_reference_control_in_fp8_fails_the_same_tolerance():
    import jax
    from chipbench import reference, reference_kexaone, weights_kexaone
    _, dims = tiny()
    w = weights_kexaone.make(dims, jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    want = np.asarray(reference_kexaone.logits(w, ids, dims))
    got = np.asarray(reference_kexaone.logits(w, ids, dims, reference.fp8))
    err = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
    assert err > 100 * F32_TOL


def test_the_reference_is_given_the_share_and_nothing_stands_in():
    """With other experts held the reference gives other logits; with all
    of them held (the uncut layer) others again: the absent experts' terms
    are left out, not made up."""
    import jax
    from chipbench import reference_kexaone, weights_kexaone
    _, dims = tiny()
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    uncut = dict(dims, experts=dims["router_experts"], expert_offset=0)
    w = weights_kexaone.make(uncut, jax.random.PRNGKey(1))
    whole = np.asarray(reference_kexaone.logits(w, ids, uncut))

    def share(offset):
        part = jax.tree.map(lambda a: a, w)
        ex = part["blocks"]["moe"]["moe"]["experts"]
        part["blocks"]["moe"]["moe"]["experts"] = {
            k: v[:, offset:offset + dims["experts"]] for k, v in ex.items()}
        return np.asarray(reference_kexaone.logits(
            part, ids, dict(dims, expert_offset=offset)))

    a, b = share(0), share(4)
    assert np.abs(a - b).max() > 1e-3 and np.abs(a - whole).max() > 1e-3


# ------------------------------------------------------------ the traffic

def test_the_long_document_traffic_is_the_issues():
    from chipbench.generators import openloop_lognormal
    cell = load_json("workloads", CELL + ".json")
    t = load_json("traffic", cell["traffic"] + ".json")
    prompt = {"median": 3072, "sigma": 0.7, "min": 1024, "max": 12288}
    output = {"median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    assert t["generator"] == "openloop_lognormal"
    assert t["prompt"] == prompt and t["output"] == output
    # the band of the knee: test_chipbench_placement.py (PR 57)
    assert cell["serving"]["max_model_len"] == 16384 == \
        cell["inference"]["max_tokens"]
    assert cell["serving"]["num_slots"] in (48, 40, 32)
    assert cell["trace_ticks"] == 640 and cell["chips"] == 1
    assert cell["job"] == "serve_arch"
    assert set(cell["serving"]) == {"num_slots", "max_model_len",
                                    "max_queue"}        # every feature off
    vocab = config()["dims"]["vocab"]
    reqs = openloop_lognormal.generate(t, SEED, vocab, 40.0)
    longest = max(len(r["prompt"]) + r["max_new"] for r in reqs)
    assert longest <= cell["check"]["reference_len"] <= 16384
    assert max(int(r["prompt"].max()) for r in reqs) < 19200
    # five prefill buckets, 1,024 ... 16,384, each warmed once
    pow2 = lambda n: min(1 << max(0, n - 1).bit_length(), 16384)
    warmed = [pow2(n) for n in cell["warm_prompt_lengths"]]
    assert warmed == [1024, 2048, 4096, 8192, 16384]
    assert {pow2(len(r["prompt"])) for r in reqs} <= set(warmed)
    assert all(n + 3 <= 16384 for n in cell["warm_prompt_lengths"])
    start = t["steady_start"]
    assert start["history_s"] * 1e3 >= output["max"] * start["tick_ms"] * 0.95
    assert any(r["due"] < 0 for r in reqs)


def test_the_backlog_traffic_is_the_chat_cells_above_its_knee():
    chat = load_json("workloads", "opt-1.3b.serve-chat.json")
    cell = load_json("workloads", CARRIED + ".json")
    same = lambda c: {k: v for k, v in c.items()
                      if k not in ("traffic", "why", "drain_seconds",
                                   "assumed")}
    assert same(cell) == same(chat)         # 28 slots x 1024, same buckets
    t = load_json("traffic", cell["traffic"] + ".json")
    base = load_json("traffic", chat["traffic"] + ".json")
    assert t["prompt"] == base["prompt"] == \
        {"median": 96, "sigma": 0.9, "min": 16, "max": 640}
    assert t["output"] == base["output"] == \
        {"median": 96, "sigma": 0.7, "min": 16, "max": 384}
    assert abs(t["rate_per_s"] / t["knee"]["knee_per_s"] - 1.5) < 0.05
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    reports = {m["name"] for m in man["end_to_end"]
               if CARRIED in m.get("workloads", [CARRIED])}
    assert reports == {"serve_tokens_per_s", "setup_s"}


def test_every_line_of_the_manifest_fits_its_200_characters():
    """The driver refuses the file before any run for a ``why``, ``layer``
    or ``source`` over 200 characters, a configuration's too (PR 38's first
    check was refused for this configuration's ``why`` of 206)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in man[group] for key in ("why", "layer", "source")
             if key in e]
    assert {(CELL.split(".")[0], "why"), (CELL, "why"), (CARRIED, "why")} <= \
        {(name, key) for name, key, _ in lines}
    bad = [(name, key, len(text)) for name, key, text in lines
           if not (1 <= len(text) <= 200 and text.isprintable())]
    assert not bad


# ------------------------------------------ the serve_window.py readers

def made_up(records, counts=counts_kexaone, dims=None):
    from chipbench.layer_metrics import _program_spans as P
    from chipbench.trace import Device, Trace
    ticks = [(0.0, 0.1, "serve/tick", 1, 24), (0.1, 0.2, "serve/tick", 2, 24)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    ctx = types.SimpleNamespace(
        cell={"job": "serve", "moe_kernels": {"pattern": "ragged-dot"},
              "modules": {"prefill": "^jit_pf$", "decode": "^jit_dec$"},
              "serving": {"num_slots": 48, "max_model_len": 16384}},
        dims=config()["dims"] if dims is None else dims, counts=counts,
        log=lambda msg: None,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        state={"program_spans": (placed, {})})
    trace = Trace([Device("/device:TPU:0", [], [])], [(0.0, 0.2, "window")])
    return ctx, {}, trace


RECORDS = [(0.05, 0.05, "serve/kv_live", 100000, 3000),
           (0.15, 0.15, "serve/kv_live", 140000, 3400),
           (0.05, 0.05, "serve/moe_decode", 60, 24),
           (0.15, 0.15, "serve/moe_decode", 62, 32),
           (0.30, 0.30, "serve/kv_live", 9, 9)]         # after the window


def test_window_readers_on_a_made_up_trace():
    """Two decode ticks: 120,000 lane columns and 3,200 ring columns live
    on average of a pool of 48 x 16,384; the largest count of a held
    expert 7 a layer against 48 x 8 / 128 = 3 rows an expert."""
    ctx, record, trace = made_up(RECORDS)
    live = 120000 * 4096 + 3200 * 16384
    assert serve_window.kv_live_share(ctx, record, trace) == pytest.approx(
        100 * live / (48 * (16384 * 4096 + 2097152)))
    assert serve_window.moe_share_skew(ctx, record, trace) == pytest.approx(
        (24 + 32) / 2 / 4 / 3.0)
    assert set(serve_window.METRICS) == {"kv_live_share", "moe_share_skew"}


def test_window_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a trace, with another architecture's counts or dims, in a
    cell without ``moe_kernels``, and for a program that records no
    ``serve/kv_live`` (a model without rings; the parent of this PR): the
    line leaves the metric out, nothing raises."""
    from chipbench import counts_lfm2
    ctx, record, trace = made_up(RECORDS)
    for read in serve_window.METRICS.values():
        assert read(ctx, record, None) is None
    other = made_up(RECORDS, counts=counts_lfm2,
                    dims=load_json("configs", "lfm2-24b-a2b.json")["dims"])
    for read in serve_window.METRICS.values():
        assert read(*other) is None
    silent = made_up([r for r in RECORDS if r[2] == "serve/moe_decode"])
    assert serve_window.kv_live_share(*silent) is None
    assert serve_window.moe_share_skew(*silent) is not None
    assert serve_window.moe_share_skew(*made_up([])) is None
    del ctx.cell["moe_kernels"]
    for read in serve_window.METRICS.values():
        assert read(ctx, record, trace) is None
