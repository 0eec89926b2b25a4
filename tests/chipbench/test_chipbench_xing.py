"""What PR 46 adds to the benchmark, as new files beside the old (after
``test_chipbench_kexaone.py``, which does the same for PR 38): the
Xing4.0-29B-A4B configuration file against the published config, its counts
by hand and against the tree the weights maker draws, its reference against
the system through ``jobs/serve_arch.py``'s own builder, the traffic of the
new cell against ISSUE 46's, the four readers of
``layer_metrics/serve_latent.py`` on made-up records, their entries
(``serve_latent.entries.json``) under a laid-over manifest, and the faults
the builder planted on the chip to place the limits of ``correct``. The
rehearsal of the new cell is a case of
``test_chipbench_run.py::test_rehearsal_ends_in_one_result_line``, which
reads the manifest."""

import contextlib
import dataclasses
import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import counts_xing, weights                       # noqa: E402
from chipbench.jobs import serve_arch                            # noqa: E402
from chipbench.layer_metrics import _scope_join as J             # noqa: E402
from chipbench.layer_metrics import serve_latent                 # noqa: E402
from chipbench.model import load_json, merge                     # noqa: E402

NAME = "xing4.0-29b-a4b"
CELL = NAME + ".serve-docqa"
SEED = 2**31 + 46
ESEED = weights.engine_seed(SEED)
F32_TOL = 2e-5          # summation order only (tests/unit/test_xing.py)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"}
FAULTS = ("identity_mix", "one_sinkhorn", "no_rope_score",
          "raw_latent_decode", "column")


def config():
    return load_json("configs", NAME + ".json")


@contextlib.contextmanager
def planted(fault):
    """The program with one fault planted in it (``None``: as it is). Of
    the widened residual: ``identity_mix``: ``H_res`` is the identity (the
    streams never mix; what a plain residual a stream would be);
    ``one_sinkhorn``: one Sinkhorn step where the configuration says 20.
    Of the latent cache's two paths: ``no_rope_score``: the rotary term of
    every score is dropped (the queries' rope part is zero);
    ``raw_latent_decode``: a decode step writes ``c_kv`` as it comes from
    ``W_kva``, without its norm, where a prefill writes it normalised;
    ``column``: a decode step feeds its token one column late (its latent
    row lands on the next column, a row of the prefill's padding lies among
    the keys, every rotated distance is one too long). Of the expert layer's
    share: ``routed_unscaled``: ``routed_scaling_factor`` left out (the held
    experts' term at half its weight beside the shared expert). Used here at the
    rehearsal's size, and by the builder's chip runs at the cell's (PERF.md
    section 2)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import xing
    from deepspeed_tpu.models.gpt2 import GPT2Model
    from deepspeed_tpu.models.xing import XingModel
    decode, attend = GPT2Model.decode_with_slots, GPT2Model._latent_attend
    maps, attention, norm = XingModel._hc_maps, XingModel._attention, \
        xing._rms_norm
    build = XingModel.__init__
    rank = []

    def unscaled(self, *args, **kw):
        build(self, *args, **kw)
        self.gate.scale = 1.0

    def late(self, params, ids, cache, positions, **kw):
        return decode(self, params, ids, cache, positions + 1, **kw)

    def unmixed(self, xs, p):
        pre, post, res = maps(self, xs, p)
        eye = jnp.eye(res.shape[0])[:, :, None, None]
        return pre, post, jnp.broadcast_to(eye, res.shape)

    def one_step(self, xs, p):
        keep = self.config
        self.config = dataclasses.replace(keep, hc_sinkhorn_iters=1)
        try:
            return maps(self, xs, p)
        finally:
            self.config = keep

    def unrotated(self, q, q_pos, slab, latent, block, keep):
        n = q.shape[-1] - latent[2]
        return attend(self, q.at[..., n:].set(0), q_pos, slab, latent, block,
                      keep)

    def noted(self, u, p, *args, **kw):
        rank[:] = [self.config.kv_lora_rank]
        return attention(self, u, p, *args, **kw)

    def raw(x, scale, eps):
        one_token = x.ndim == 3 and x.shape[1] == 1
        return x if one_token and [x.shape[-1]] == rank else \
            norm(x, scale, eps)

    if fault == "column":
        GPT2Model.decode_with_slots = late
    elif fault == "identity_mix":
        XingModel._hc_maps = unmixed
    elif fault == "one_sinkhorn":
        XingModel._hc_maps = one_step
    elif fault == "no_rope_score":
        GPT2Model._latent_attend = unrotated
    elif fault == "raw_latent_decode":
        XingModel._attention, xing._rms_norm = noted, raw
    elif fault == "routed_unscaled":
        XingModel.__init__ = unscaled
    else:
        assert fault is None, fault
    try:
        yield
    finally:
        GPT2Model.decode_with_slots = decode
        GPT2Model._latent_attend = attend
        XingModel._hc_maps, XingModel._attention = maps, attention
        XingModel.__init__ = build
        xing._rms_norm = norm


# ------------------------------------------------- the configuration file

#: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hc_eps": 1e-06, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "mhc_h_res_clamp_max": 30, "mhc_h_res_clamp_min": -30,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 768,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 131072}


def test_published_is_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures beside the guides here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Xing4.0-29B-A4B" in line]
    assert rows[0]["config"] == PUBLISHED
    assert rows[0]["source_url"] == config()["source"]


def test_configuration_is_the_published_one_with_exactly_the_listed_cuts():
    cfg = config()
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert differs == REDUCED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"] if c["name"] == NAME][0]
    assert set(entry["reduced"]) == REDUCED and entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert len(cfg["reduced"]) == len(REDUCED)
    # the floors of a cut: one dense layer and eleven routed ones (>= 4), 8
    # experts held (>= 8), an eighth of the rows; no width among the cuts
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["num_nextn_predict_layers"]) == \
        (12, 1, 8, 0)
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and
                   k != "vocab_size" for k in REDUCED)
    assert set(cfg["assumed"]) >= {"hyper_connections", "latent_attention",
                                   "router", "weights"}
    assert "eight chips" in cfg["deployment"]
    # what the program is built with is what the file says
    dims, kw = cfg["dims"], cfg["program"]["kwargs"]
    assert (dims["d_model"], dims["heads"], dims["q_rank"], dims["kv_rank"],
            dims["nope_dim"], dims["rope_dim"], dims["v_dim"],
            dims["dense_ff"], dims["expert_ff"], dims["top_k"],
            dims["router_experts"], dims["shared_experts"], dims["streams"],
            dims["hc_sinkhorn_iters"], dims["hc_eps"], dims["hc_clamp"]) == \
        (3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 64, 1, 4, 20, 1e-6,
         30.0)
    assert (dims["layers"], dims["dense_layers"], dims["experts"],
            dims["expert_offset"], dims["vocab"]) == (11, 1, 8, 0, 16384)
    assert (kw["n_embd"], kw["n_head"], kw["q_lora_rank"],
            kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"], kw["mlp_hidden"],
            kw["moe_intermediate_size"], kw["top_k"], kw["num_experts"],
            kw["experts_held"], kw["vocab_size"], kw["n_layer"],
            kw["first_k_dense_replace"], kw["num_shared_experts"],
            kw["routed_scaling_factor"], kw["hc_mult"],
            kw["hc_sinkhorn_iters"], kw["rope_factor"],
            kw["rope_original_positions"]) == \
        (3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 64, [0, 8], 16384,
         12, 1, 1, 2.0, 4, 20, 64.0, 4096)
    from deepspeed_tpu.models.xing import XING4_29B_A4B as full
    assert (full.n_layer, full.n_embd, full.n_head, full.q_lora_rank,
            full.kv_lora_rank, full.num_experts, full.top_k,
            full.vocab_size, full.first_k_dense_replace, full.hc_mult) == \
        (40, 3584, 32, 768, 512, 64, 4, 131072, 2, 4)


def test_counts_of_the_configuration():
    c, dims, m = counts_xing, config()["dims"], 1e6
    attn = c.attention_params(dims)
    assert round(attn / m, 2) == 28.41      # the five projections and W_o
    assert round(3584 * 768 / m, 2) == 2.75
    assert round(768 * 32 * 192 / m, 2) == 4.72
    assert round(3584 * 576 / m, 2) == 2.06
    assert round(512 * 32 * 256 / m, 2) == 4.19
    assert round(32 * 128 * 3584 / m, 2) == 14.68
    assert c.hc_params(dims) == 14336 * 24 + 24 + 3
    assert c.expert_bytes(dims) == 3 * 3584 * 1024 * 2 == 22020096
    table = 16384 * 3584
    outside = 12 * (attn + 2 * c.hc_params(dims)) + \
        (3 * 3584 * 9216 + 3584) + \
        11 * (3584 * 64 + 64 + 3 * 3584 * 1024 + 3584) + 3584 + table
    assert c.non_expert_weight_bytes(dims) == 2 * outside
    total = outside + table + 11 * 8 * 3 * 3584 * 1024
    assert c.total_weight_bytes(dims) == 2 * total
    assert round(total / 1e9, 3) == 1.658
    # a token's latent rows: 576 values a layer where per-head keys and
    # values would be 32 x (192 + 128)
    assert c.kv_bytes_per_token(dims) == 12 * 1152 == 13824
    assert 32 * (192 + 128) * 2 / 1152 == pytest.approx(17.8, abs=0.03)
    assert c.pool_bytes(dims, 48, 8192) == 48 * 8192 * 13824
    assert round(c.pool_bytes(dims, 48, 8192) / 1e9, 2) == 5.44
    assert c.live_kv_bytes(dims, 1000, 0) == 1000 * 13824
    assert c.non_expert_decode_bytes(dims, 1000, 48) == \
        2 * outside + 1000 * 13824
    assert c.decode_bytes(dims, 88, 0) == 2 * (total - table)
    # a token's 4 picks of 64 fall on the 8 held half a time a layer
    assert c.held_pairs(dims, 1000) == 1000 * 11 * 4 * 8 / 64 == 5500
    assert c.expert_flops(dims, 1000) == 5500 * 6 * 3584 * 1024
    assert c.expert_io_bytes(dims, 1000, 88) == \
        88 * 22020096 + 5500 * (3 * 3584 + 4 * 1024) * 2
    # ISSUE 46's reckoning of a bucket-8,192 prefill: the expanded attend
    # is two fifths of its operations
    attend = c.prefill_attend_flops(dims, 8192)
    assert attend == 12 * (2 * 8192 * 512 * 32 * 256 +
                           8192 * 8193 // 2 * 32 * 2 * 320)
    assert 9.0e12 < attend < 9.2e12     # 8.25 of them the attend itself
    assert c.decode_attend_flops(dims, 48, 100000) == 12 * (
        48 * 2 * 512 * 32 * 256 + 100000 * 2 * 32 * 1088)
    assert c.hc_bytes_per_token(dims) == 2 * 12 * 14 * 3584 * 2
    assert c.hc_flops_per_token(dims) > 2 * 12 * 2 * 14336 * 24


def test_counts_are_the_trees():
    """The parameters the counts name are the leaves the weights maker
    draws at the cell's sizes, to the last gain."""
    import jax
    maker = importlib.import_module(config()["benchmark"]["weights"])
    dims = config()["dims"]
    tree = jax.eval_shape(lambda k: maker.make(dims, k),
                          jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert tree["wte"].shape == (16384, 3584)
    assert size(tree) * 2 == counts_xing.total_weight_bytes(dims)
    experts = size(tree["blocks"]["moe"]["moe"]["experts"])
    assert experts * 2 == 11 * 8 * counts_xing.expert_bytes(dims)
    assert (size(tree) - experts - size(tree["wte"])) * 2 == \
        counts_xing.non_expert_weight_bytes(dims)
    hc = tree["blocks"]["attn"]["hc_attn"]
    assert size(hc) == 12 * counts_xing.hc_params(dims)
    attn = {k: v for k, v in tree["blocks"]["attn"].items() if k != "hc_attn"}
    assert size(attn) == 12 * counts_xing.attention_params(dims)


# -------------------------------------- the reference, through serve_arch

def tiny():
    cell = load_json("workloads", CELL + ".json")
    cfg = merge(config(), cell["rehearse"]["config"])
    ctx = types.SimpleNamespace(config=cfg, cell={"model_overrides": None})
    return ctx, cfg["dims"]


def _named(ctx):
    return (importlib.import_module(ctx.config["benchmark"]["weights"]),
            importlib.import_module(ctx.config["benchmark"]["reference"]))


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
    maker, reference = _named(ctx)
    w = maker.make(dims, weights.seed_key(SEED))
    want = np.stack([np.asarray(reference.logits(w, row, dims))
                     for row in ids])
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@pytest.mark.parametrize("dtype, fault, low, high", [
    ("float32", None, 0.0, F32_TOL), ("bfloat16", None, 10 * F32_TOL, 0.05),
    ("float32", "identity_mix", 0.045, 2.0),
    ("float32", "one_sinkhorn", 0.045, 2.0),
    ("float32", "no_rope_score", 0.045, 2.0),
    ("float32", "routed_unscaled", 0.045, 2.0)])
def test_engine_forward_against_the_named_reference(dtype, fault, low, high):
    """``logits_rel_rms_err`` with the builder, weights maker and reference
    the configuration names, as ``serve_arch`` takes them (the rehearsal's
    stack, a share of 4 of 16 experts): the engine's own seed reproduces
    the reference's weights; a lower precision than stated fails float32's
    tolerance; the streams unmixed, one Sinkhorn step of 20, a score
    without its rotary term, or the held experts' term without its factor
    of 2 read over the rehearsed cell's limit."""
    err = forward_error(dtype, fault)
    assert low <= err < high, err
    limit = load_json("workloads", CELL + ".json")["rehearse"]["cell"][
        "check"]["logits_rel_rms_err"]
    assert (fault is None) == (err < limit)


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
    maker, reference = _named(ctx)
    rng = np.random.default_rng(2)
    # one prefill bucket (32) and one padded length for the reference: three
    # programs to compile a case, not nine
    prompts = [rng.integers(0, dims["vocab"], n, dtype=np.int32)
               for n in (19, 23, 31)]
    new, pad_to = 12, 48
    out = {}
    with planted(fault):
        engine = deepspeed_tpu.init_inference(
            model, config={"dtype": "float32", "max_tokens": 64,
                           "seed": ESEED})
        srv = ServingEngine(engine, {"num_slots": 3, "max_model_len": 64,
                                     "max_queue": 8})
        rids = [srv.submit(p, SamplingParams(max_new_tokens=new),
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
        padded = np.zeros(pad_to, np.int32)
        padded[:len(seq)] = seq
        rows = np.asarray(reference.logits(w, padded, dims))[
            len(p) - 1:len(seq) - 1, :dims["vocab"]]
        gap = (rows.max(-1) - rows[np.arange(len(toks)), toks]) / \
            np.abs(rows).max(-1)
        worst = max(worst, float(gap.max()))
    return worst


@pytest.mark.parametrize("fault", [None, "column", "raw_latent_decode"])
def test_token_gap_reads_the_cache_path(fault):
    """``token_argmax_gap`` through the system: nothing in float32 as the
    program is; with a decode step one column late, or its latent row
    written without the norm a prefill gives it, over the cell's limit and
    the rehearsed cell's."""
    cell = load_json("workloads", CELL + ".json")
    gap = served_gap(fault)
    if fault is None:
        assert gap < 1e-4, gap
    else:
        assert gap > cell["check"]["token_argmax_gap"] and \
            gap > cell["rehearse"]["cell"]["check"]["token_argmax_gap"], gap


def test_reference_control_in_fp8_fails_the_same_tolerance():
    import jax
    from chipbench import reference, reference_xing, weights_xing
    _, dims = tiny()
    w = weights_xing.make(dims, jax.random.PRNGKey(1))
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    want = np.asarray(reference_xing.logits(w, ids, dims))
    got = np.asarray(reference_xing.logits(w, ids, dims, reference.fp8))
    err = float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))
    assert err > 100 * F32_TOL


def test_the_reference_is_given_the_share_and_nothing_stands_in():
    """With other experts held the reference gives other logits; with all
    of them held (the uncut layer) others again: the absent experts' terms
    are left out, not made up."""
    import jax
    from chipbench import reference_xing, weights_xing
    _, dims = tiny()
    ids = np.random.default_rng(2).integers(0, dims["vocab"], 32,
                                            dtype=np.int32)
    uncut = dict(dims, experts=dims["router_experts"], expert_offset=0)
    w = weights_xing.make(uncut, jax.random.PRNGKey(1))
    whole = np.asarray(reference_xing.logits(w, ids, uncut))

    def share(offset):
        part = jax.tree.map(lambda a: a, w)
        ex = part["blocks"]["moe"]["moe"]["experts"]
        part["blocks"]["moe"]["moe"]["experts"] = {
            k: v[:, offset:offset + dims["experts"]] for k, v in ex.items()}
        return np.asarray(reference_xing.logits(
            part, ids, dict(dims, expert_offset=offset)))

    a, b = share(0), share(4)
    assert np.abs(a - b).max() > 1e-3 and np.abs(a - whole).max() > 1e-3


# ------------------------------------------------------------ the traffic

def test_the_document_traffic_is_the_issues():
    """One prefill bucket (4,096: ISSUE 46's second size, taken because a
    bucket-8,192 prefill tick read 25 decode ticks and left under 8% of the
    gaps on prefill ticks at 0.8 of the knee; ``assumed`` in the cell's
    file) with ISSUE 46's clips, a lane that holds the longest request
    (3,968 + 256), short answers, nothing shared, the rate inside ISSUE
    46's band of the knee, slots by what the traffic keeps in flight, the
    placement readings kept for a re-sweep."""
    from chipbench.generators import openloop_lognormal
    cell = load_json("workloads", CELL + ".json")
    t = load_json("traffic", cell["traffic"] + ".json")
    assert t["generator"] == "openloop_lognormal"
    assert t["prompt"] == {"median": 3072, "sigma": 0.25, "min": 2176,
                           "max": 3968}
    assert t["output"] == {"median": 96, "sigma": 0.5, "min": 32, "max": 256}
    # the band of the knee: test_chipbench_placement.py (PR 57)
    placed = t["placement"]
    assert 0.08 <= placed["share_of_gaps_on_prefill_ticks"] <= 0.25
    assert abs(placed["gap_p97_ms"] / placed["gap_p93_ms"] - 1) < 0.03
    assert placed["gap_p93_ms"] <= placed["gap_p95_ms"] <= placed["gap_p97_ms"]
    assert cell["serving"]["max_model_len"] == 3968 + 256 == \
        cell["inference"]["max_tokens"]
    assert cell["serving"]["num_slots"] == 32       # ISSUE 46's 32-64
    assert cell["chips"] == 1 and cell["job"] == "serve_arch"
    assert set(cell["serving"]) == {"num_slots", "max_model_len",
                                    "max_queue"}        # every feature off
    assert cell["inference"]["dtype"] == "bfloat16"
    vocab = config()["dims"]["vocab"]
    reqs = openloop_lognormal.generate(t, SEED, vocab, 40.0)
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= \
        cell["serving"]["max_model_len"] <= cell["check"]["reference_len"]
    # the reference attends in blocks of 1,024 queries where they divide
    assert cell["check"]["reference_len"] % 1024 == 0
    assert max(int(r["prompt"].max()) for r in reqs) < 16384
    # ONE prefill bucket, warmed once
    pow2 = lambda n: 1 << max(0, n - 1).bit_length()
    assert {pow2(len(r["prompt"])) for r in reqs} == {4096} == \
        {pow2(n) for n in cell["warm_prompt_lengths"]}
    assert any(r["due"] < 0 for r in reqs)
    # what the device held, both ways, in the cell's file
    mem = cell["memory"]
    assert mem["memory_peak_bytes"] >= 0.25 * 16e9 <= \
        mem["memory_analysis_bytes"]


def test_the_cell_is_in_the_lists_its_readers_apply_to():
    """Every list K-EXAONE's cell is in (a share of the experts, a pool
    that says what of it is live), and ``mixer_decode_hbm_share``'s; not
    ``moe_load_skew``'s (it divides by the experts HELD). Every line of the
    manifest fits its 200 characters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    other = "k-exaone-236b-a23b.serve-longdoc-16k"
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            listed = m.get("workloads")
            if listed is not None and m["name"] != "kv_read_share":
                assert (CELL in listed) == (other in listed), m["name"]
                # appended after the cells the benchmark had
                assert CELL not in listed or \
                    listed.index(CELL) > listed.index(other)
    names = {m["name"] for m in man["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"moe_experts_touched", "moe_decode_hbm_share", "moe_ffn_share",
            "moe_prefill_roofline", "moe_share_skew", "kv_live_share",
            "kv_read_share", "mixer_decode_hbm_share", "tick_ms",
            "idle_tick_ms"} <= names and "moe_load_skew" not in names
    assert [w["name"] for w in man["workloads"]].index(CELL) == 8
    assert man["configs"][-1]["name"] == NAME
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in man[group] for key in ("why", "layer", "source")
             if key in e]
    assert {(NAME, "why"), (CELL, "why")} <= {(n, k) for n, k, _ in lines}
    assert not [(n, k, len(text)) for n, k, text in lines
                if not (1 <= len(text) <= 200 and text.isprintable())]


# ------------------------------------------ the serve_latent.py readers

def made_up(records, seconds, counts=counts_xing, calls=None):
    """Two ticks of a traced window, the program's records laid on its
    clock, and device seconds by scope as ``_scope_join`` would have
    joined them."""
    from chipbench.layer_metrics import _program_spans as P
    from chipbench.trace import Device, Trace
    ticks = [(0.0, 0.1, "serve/tick", 1, 24), (0.1, 0.2, "serve/tick", 2, 24)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    joined = J.Joined()
    joined.seconds = seconds
    joined.calls = {"jit_dec": 2, "jit_pf": 1} if calls is None else calls
    joined.bucket_tokens = 8192
    ctx = types.SimpleNamespace(
        cell={"job": "serve", "moe_kernels": {"pattern": "ragged-dot"},
              "modules": {"prefill": "^jit_pf$", "decode": "^jit_dec$"},
              "serving": {"num_slots": 48, "max_model_len": 8192}},
        dims=config()["dims"], counts=counts, log=lambda msg: None,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        state={"program_spans": (placed, {}), "scope_time": joined})
    trace = Trace([Device("/device:TPU:0", [], [])], [(0.0, 0.2, "window")])
    return ctx, {}, trace


RECORDS = [(0.05, 0.05, "serve/kv_live", 100000, 0),
           (0.15, 0.15, "serve/kv_live", 140000, 0),
           (0.12, 0.12, "serve/prefill_prep", 6000, 8192),
           (0.30, 0.30, "serve/kv_live", 9, 0)]         # after the window
SECONDS = {
    "jit_dec": {"layers/attn/attend_latent/kv_read": 0.010,
                "layers/attn/attend_latent/kv_read/absorb": 0.001,
                "layers/attn/attend_latent/kv_write": 0.001,
                "layers/hc_maps": 0.002, "layers/hc_mix": 0.001,
                "layers/attn/qkv": 0.004, None: 0.001},
    "jit_pf": {"layers/attn/attend_latent/kv_read": 0.090,
               "layers/attn/attend_latent/kv_read/latent_up": 0.010,
               "layers/hc_maps": 0.003, "layers/hc_mix": 0.005,
               "layers/moe": 0.050},
}


def test_latent_readers_on_made_up_records():
    """Two decode steps under ``attend_latent`` for 12 ms together, 120,000
    live columns on average; one prefill of 6,000 tokens under it for 0.1
    s; 3 ms and 8 ms under the two mHC scopes."""
    ctx, record, trace = made_up(RECORDS, SECONDS)
    read = serve_latent.METRICS
    assert read["latent_attend_hbm_share"](ctx, record, trace) == \
        pytest.approx(100 * (120000 * 13824 / 819e9) / 0.006)
    assert read["latent_prefill_roofline"](ctx, record, trace) == \
        pytest.approx(100 * counts_xing.prefill_attend_flops(
            ctx.dims, 6000) / 197e12 / 0.1)
    assert read["hc_decode_ms"](ctx, record, trace) == pytest.approx(1.5)
    assert read["hc_prefill_us_per_token"](ctx, record, trace) == \
        pytest.approx(0.008 / 8192 * 1e6)
    assert list(read) == [m["name"] for m in entries()]


def test_latent_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a trace, for a program that keeps no scope tables (the
    parent of this PR), for a program without the scopes (every other
    architecture), with another architecture's counts, and where the
    window ran no such program: the line leaves the metric out, nothing
    raises."""
    from chipbench import counts_kexaone
    ctx, record, trace = made_up(RECORDS, SECONDS)
    del ctx.state["scope_time"]
    for read in serve_latent.METRICS.values():
        assert read(ctx, record, None) is None
    ctx.state["scope_time"] = None
    for read in serve_latent.METRICS.values():
        assert read(ctx, record, trace) is None
    plain = {"jit_dec": {"layers/attn/kv_read": 0.01, "layers/mlp": 0.02},
             "jit_pf": {"layers/attn/kv_read": 0.1}}
    for read in serve_latent.METRICS.values():
        assert read(*made_up(RECORDS, plain)) is None
    other = made_up(RECORDS, SECONDS, counts=counts_kexaone)
    assert serve_latent.latent_prefill_roofline(*other) is None
    silent = made_up([r for r in RECORDS if r[2] != "serve/kv_live"], SECONDS)
    assert serve_latent.latent_attend_hbm_share(*silent) is None
    assert serve_latent.hc_decode_ms(*silent) is not None
    idle = made_up(RECORDS, SECONDS, calls={"jit_dec": 2})
    assert serve_latent.latent_prefill_roofline(*idle) is None
    assert serve_latent.hc_prefill_us_per_token(*idle) is None


# ---------------------------------------------------------------- manifest

def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries():
    """The four entries as they go at the END of ``per_layer``, after
    ``scope_time.entries.json``'s eleven: not in ``BENCHMARK.json`` yet, for
    that file's reason (``test_chipbench_scope_time.py:entries``)."""
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           "serve_latent.entries.json")) as f:
        return json.load(f)


def test_the_four_entries_each_with_a_reader_fit_the_manifest():
    man, mine = manifest(), entries()
    assert [m["name"] for m in mine] == list(serve_latent.METRICS)
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in man["end_to_end"]}
    layers = {m["layer"] for m in man["per_layer"]}
    had = {m["name"]: m for m in man["per_layer"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
        assert m["source"] == "device_trace" and m["layer"] in layers
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["name"])
        assert m["workloads"] == [CELL] and CELL in e2e[m["moves"]]
        assert had.get(m["name"], m) == m
        assert (m["unit"] == "%") == (m["better"] == "higher") == \
            m["name"].endswith(("_roofline", "_hbm_share"))
    # the accepted list keeps its length and its end (PR 39's pin)
    assert [m["name"] for m in man["per_layer"][-2:]] == \
        ["kv_read_share", "kv_read_share.backlog"] or \
        mine[0]["name"] in had


def test_a_traced_rehearsal_under_the_laid_over_manifest_reads_all_four(
        tmp_path):
    """A copy of ``chipbench/`` under a manifest that holds PR 42's eleven
    entries and these four at the end of ``per_layer``: the new cell's
    traced rehearsal reports every one of the four, the new scopes name
    the decode and prefill programs' time, and no share passes 100%."""
    from test_chipbench_scope_time import entries as eleven
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    man = manifest()
    have = {m["name"] for m in man["per_layer"]}
    for m in eleven():
        if m["moves"] != "train_tokens_per_s":
            m = dict(m, workloads=m["workloads"] + [CELL])
        man["per_layer"] += [m] if m["name"] not in have else []
    man["per_layer"] += [m for m in entries() if m["name"] not in have]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chipbench" / "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    got = last["metrics"]
    if "scope_unnamed_share.serve" not in got:
        pytest.skip("the host was too loaded to lay the program's records "
                    "on the trace's clock")
    assert last["correct"] is True
    assert set(serve_latent.METRICS) <= set(got), sorted(got)
    assert all(got[n]["value"] > 0 for n in serve_latent.METRICS)
    assert got["scope_unnamed_share.serve"]["value"] < 5.0
    lines = lambda module: " ".join(x for x in proc.stdout.splitlines()
                                    if "scope time " + module in x)
    for word in ("hc_maps", "hc_mix", "attend_latent", "absorb"):
        assert word in lines("jit_dec"), word
    assert "latent_up" in lines("jit_pf") and "hc_mix" in lines("jit_pf")
