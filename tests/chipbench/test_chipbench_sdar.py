"""The ``sdar-30b-a3b`` configuration and its cell
``sdar-30b-a3b.serve-reason-4k``: the file against the catalog's row, the
counts against the made weights, the two-stream reference against
block-by-block forwards, the cell rehearsed through ``run.py`` by name (and
failing its check under ``--control``, and under each fault of the pass
planted in the program), the traffic as ISSUE 48 gave it, and the readers of
``layer_metrics/serve_blocks.py`` on made-up records and under a manifest
laid over a copy.

``python tests/chipbench/test_chipbench_sdar.py <fault> <run.py's
arguments>`` runs the benchmark with one of ``FAULTS`` planted: the
builder's chip runs that place the limits of the cell's check (PERF.md
section 2), and the tests' rehearsals of them.
"""

import contextlib
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

from chipbench import counts_sdar, reference_sdar, weights_sdar     # noqa: E402
from chipbench.layer_metrics import (scope_time, serve_blocks,       # noqa: E402
                                     serve_latent)
from chipbench.model import load_json, merge                         # noqa: E402

NAME = "sdar-30b-a3b"
CELL = NAME + ".serve-reason-4k"
SEED = 2**31 + 48
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


#: the faults of the pass over blocks, each with the number of the job's
#: check that has to read it
FAULTS = {"causal": "token_argmax_gap", "real_ids": "token_argmax_gap",
          "least_confident": "confidence_margin",
          "unwritten": "token_argmax_gap"}


@contextlib.contextmanager
def planted(fault):
    """The program with one fault of the pass over blocks planted in it
    (``None``: as it is). ``causal``: the pass attends under
    ``k_pos <= q_pos`` (the prefill keeps the block mask: a position of a
    block being denoised does not see the positions behind it);
    ``real_ids``: a masked position embeds the id its register holds, not
    the ``[MASK]`` row; ``least_confident``: of the masked positions the
    ``fix`` LEAST confident are fixed; ``unwritten``: a block's writing
    pass is left out (its row lands on the columns of the next block, which
    that block's first pass overwrites), so its keys and values stay as its
    last unmasking pass wrote them, half of them a ``[MASK]`` row's. Used
    by the tests at the rehearsal's size and by the builder's chip runs at
    the cell's (PERF.md section 2)."""
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.inference import engine as inference
    from deepspeed_tpu.models.sdar import SDARModel
    from deepspeed_tpu.serving.kv_slots import SlotPool
    verify, mask_id = SDARModel.verify_with_slots, SDARModel.mask_token_id
    unmask, arrays = inference.unmask_rows, SlotPool.dispatch_block_arrays

    def causal(self, *args, **kw):
        block_mask = SDARModel._decode_attn_mask
        SDARModel._decode_attn_mask = lambda self, q_pos, k_pos: k_pos <= q_pos
        try:
            return verify(self, *args, **kw)
        finally:
            SDARModel._decode_attn_mask = block_mask

    def least(logits, ids, flags, temps, top_ks, top_ps, keys, vocab, fix):
        # every masked position's draw, then the ``fix`` least confident
        x0, _ = unmask(logits, ids, flags, temps, top_ks, top_ps, keys,
                       vocab, ids.shape[1])
        rows = logits[..., :vocab].astype(jnp.float32)
        conf = jnp.take_along_axis(
            rows - rows.max(-1, keepdims=True), x0[..., None], axis=-1)[..., 0]
        conf = conf - jnp.log(jnp.exp(
            rows - rows.max(-1, keepdims=True)).sum(-1))
        _, idx = lax.top_k(jnp.where(flags, -conf, -jnp.inf), fix)
        take = jnp.zeros_like(flags).at[
            jnp.arange(ids.shape[0])[:, None], idx].set(True) & flags
        return jnp.where(take, x0, ids), flags & ~take

    def unwritten(self, slots, fed, fix):
        (ids, flags, positions, *sampling, from_host), passes = arrays(
            self, slots, fed, fix)
        for slot, (_, _, writes) in passes.items():
            if writes:
                ids[slot], flags[slot], from_host[slot] = 0, False, True
                positions[slot] = min(positions[slot] + self.block,
                                      self.max_model_len - self.block)
        return (ids, flags, positions, *sampling, from_host), passes

    if fault == "causal":
        SDARModel.verify_with_slots = causal
    elif fault == "real_ids":
        # a masked position's register holds id 0
        SDARModel.mask_token_id = property(lambda self: 0)
    elif fault == "least_confident":
        inference.unmask_rows = least
    elif fault == "unwritten":
        SlotPool.dispatch_block_arrays = unwritten
    elif fault is not None:
        raise ValueError(fault)
    try:
        yield
    finally:
        SDARModel.verify_with_slots, SDARModel.mask_token_id = verify, mask_id
        inference.unmask_rows = unmask
        SlotPool.dispatch_block_arrays = arrays


def config():
    return load_json("configs", NAME + ".json")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def entries():
    """The six entries as they go at the END of ``per_layer``: not in
    ``BENCHMARK.json`` yet (``test_chipbench_scope_time.py:entries``)."""
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           "serve_blocks.entries.json")) as f:
        return json.load(f)


# ----------------------------------------------------------- configuration
def test_published_is_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog of architectures beside the guides here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "SDAR-30B-A3B-Chat" in line]
    assert rows[0]["config"] == PUBLISHED
    assert rows[0]["source_url"] == config()["source"]


def test_configuration_is_the_published_one_cut_in_depth_alone():
    cfg = config()
    assert {k for k, v in PUBLISHED.items() if cfg.get(k) != v} == \
        {"num_hidden_layers"}
    entry = [c for c in manifest()["configs"] if c["name"] == NAME][0]
    assert entry["reduced"] == ["num_hidden_layers"] and \
        entry["source"] == cfg["source"]
    assert entry["file"] == f"chipbench/configs/{NAME}.json"
    assert cfg["reduced"] == ["num_hidden_layers 48 -> 6"]
    assert cfg["num_hidden_layers"] == 6                # the floor is four
    assert set(cfg["assumed"]) >= {"block_length", "mask_token_id",
                                   "qk_norm", "head_dim", "weights"}
    assert "pipeline stages" in cfg["deployment"]
    dims, kw = cfg["dims"], cfg["program"]["kwargs"]
    assert (dims["d_model"], dims["heads"], dims["kv_heads"],
            dims["head_dim"], dims["experts"], dims["top_k"],
            dims["expert_ff"], dims["vocab"], dims["layers"],
            dims["block_length"], dims["mask_token_id"]) == \
        (2048, 32, 4, 128, 128, 8, 768, 151936, 6, 4, 151669)
    assert (kw["n_embd"], kw["n_head"], kw["n_kv_head"], kw["head_dim"],
            kw["num_experts"], kw["top_k"], kw["mlp_hidden"],
            kw["vocab_size"], kw["n_layer"], kw["block_length"],
            kw["mask_token_id"], kw["norm_topk_prob"]) == \
        (2048, 32, 4, 128, 128, 8, 768, 151936, 6, 4, 151669, True)
    from deepspeed_tpu.models.sdar import SDAR_30B_A3B as full
    assert (full.n_layer, full.n_embd, full.n_head, full.kv_head_count,
            full.head_dim, full.num_experts, full.top_k, full.intermediate,
            full.vocab_size, full.rope_theta, full.layer_norm_epsilon) == \
        (48, 2048, 32, 4, 128, 128, 8, 768, 151936, 1e6, 1e-6)


def test_counts_of_the_configuration():
    """ISSUE 48's arithmetic, from ``counts_sdar``: 623.1M a layer, 4.361B
    held, 30.5B whole; 12,288 B of keys and values a token."""
    dims = config()["dims"]
    layer = counts_sdar.layer_params_outside_experts(dims) + \
        128 * 3 * 2048 * 768
    assert round(layer / 1e6, 1) == 623.1
    assert round(counts_sdar.total_params(dims) / 1e9, 3) == 4.361
    assert round(counts_sdar.total_params(dict(dims, layers=48)) / 1e9,
                 1) == 30.5
    assert counts_sdar.total_weight_bytes(dims) == 8722111488
    assert counts_sdar.kv_bytes_per_token(dims) == 12288
    assert counts_sdar.expert_bytes(dims) == 3 * 2048 * 768 * 2
    # a pass reads every touched expert once, whatever its rows
    every = dims["layers"] * dims["experts"]
    assert counts_sdar.decode_bytes(dims, every, 0) == \
        counts_sdar.non_expert_weight_bytes(dims) + \
        every * counts_sdar.expert_bytes(dims)
    few, many = (counts_sdar.block_pass_bytes(dims, every, 1000, rows)
                 for rows in (48, 192))
    assert 0 < many - few < 0.05 * few
    assert "8.722 GB" in config()["memory"]


def tiny_dims():
    reh = load_json("workloads", CELL + ".json")["rehearse"]
    return merge(config(), reh["config"])["dims"]


def test_counts_are_the_trees():
    """``total_params`` is the leaf count of the made weights, at the
    rehearsal's sizes and (by shape alone) at the configuration's."""
    import jax
    for dims in (tiny_dims(), config()["dims"]):
        made = jax.eval_shape(lambda k, d=dims: weights_sdar.make(
            d, k, vocab_multiple=128), jax.random.PRNGKey(0))
        rows = weights_sdar.table_rows(dims)
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(made)) == \
            counts_sdar.total_params(dims, vocab_rows=rows)


# --------------------------------------------------------------- reference
def test_two_streams_are_block_by_block_forwards():
    """One forward of the noisy copy beside the clean one gives, at every
    block, the logits of a forward of the clean sequence before the block
    and the block as it stood."""
    import jax
    dims = tiny_dims()
    w = weights_sdar.make(dims, jax.random.PRNGKey(48))
    rng = np.random.default_rng(48)
    ids = rng.integers(0, dims["vocab"], 32).astype(np.int32)
    masked = rng.random(32) < 0.5
    masked[:4] = False                       # a block of context alone
    two = np.asarray(reference_sdar.logits_two_stream(w, ids, masked, dims))
    scale = np.abs(two).max()
    for at in range(0, 32, 4):
        flags = np.zeros(at + 4, bool)
        flags[at:] = masked[at:at + 4]
        one = np.asarray(reference_sdar.logits(
            w, ids[:at + 4], dims, masked=flags))[at:]
        assert np.abs(one - two[at:at + 4]).max() < 1e-5 * scale
    # and the clean sequence is not what the noisy blocks read
    clean = np.asarray(reference_sdar.logits(w, ids, dims))
    assert np.abs(clean - two)[masked].max() > 1e-2 * scale


def test_the_loop_fixes_the_most_confident_of_the_masked_only():
    conf = np.array([0.9, 0.2, 0.8, 0.8])
    flags = np.array([False, True, True, True])
    np.testing.assert_array_equal(
        reference_sdar.fix(conf, flags, 2), [False, False, True, True])
    np.testing.assert_array_equal(
        reference_sdar.fix(conf, np.array([1, 0, 0, 0], bool), 2),
        [True, False, False, False])


# --------------------------------------------------------------- rehearsal
def run(*flags, cwd=ROOT, script=None, fault=None):
    """``run.py`` (or ``script``), or this file with ``fault`` planted."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    script = [__file__, fault] if fault else \
        [script or os.path.join(ROOT, "chipbench", "run.py")]
    proc = subprocess.run(
        [sys.executable, *script, "--workload", CELL, "--seed", str(SEED), "--seconds", "2",
         "--rehearse", *flags],
        cwd=str(cwd), env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc.stdout, json.loads(proc.stdout.strip().splitlines()[-1])


def checks(stdout):
    return {m[1]: (float(m[2]), float(m[3]), m[4]) for m in re.finditer(
        r"check (\w+): (\S+) limit (\S+) (ok|NOT CORRECT)", stdout)}


@pytest.fixture(scope="module")
def rehearsed():
    return run("--trace", "0")


def test_the_cell_rehearses_by_name_and_is_correct(rehearsed):
    out, last = rehearsed
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    got = checks(out)
    assert set(got) >= {"logits_rel_rms_err", "token_argmax_gap",
                        "confidence_margin", "compiles_in_window"}
    assert all(word == "ok" for _, _, word in got.values())
    assert re.search(r"block check on 3 finished requests of \d+: "
                     r"[1-9]\d* block passes", out)
    assert re.search(r"\d+ block periods: p50", out)


def test_the_check_reads_the_control_apart(rehearsed):
    """The program's own lower precision (int8 weights) in its place: never
    correct, and the first number of the check reads it apart from the
    bfloat16 program on the same seed. (At the rehearsal's widths the loose
    limit holds both; at the cell's the limit lies between the two readings
    of the chip: PERF.md section 2.)"""
    out, last = run("--trace", "0", "--control")
    assert last["correct"] is False and "control run" in out
    plain, control = (checks(o)["logits_rel_rms_err"][0]
                      for o in (rehearsed[0], out))
    assert control > 1.3 * plain, (plain, control)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_of_the_pass_fails_the_check(rehearsed, fault):
    """Each of ``planted``'s faults, through the job's own check at the
    rehearsal's size and under the rehearsal's own limits (the tiny model's
    confidences lie within a tenth of each other, so the least confident
    fixed reads a margin of 0.10-0.14 here where the sound program reads
    under 0.01): the number that has to read the fault reads over its limit
    and well over the sound program's reading. What each reads at the
    cell's size on the chip is in the cell's ``check_placed`` (below) and
    PERF.md section 2."""
    out, last = run("--trace", "0", fault=fault)
    assert last["correct"] is False
    number = FAULTS[fault]
    value, limit, word = checks(out)[number]
    cell = load_json("workloads", CELL + ".json")
    assert word == "NOT CORRECT" and \
        limit == merge(cell, cell["rehearse"]["cell"])["check"][number]
    assert value > 1.5 * limit and \
        value > 5 * checks(rehearsed[0])[number][0], (value, limit)


def test_the_limits_lie_between_the_sound_runs_and_the_faults():
    """The cell's three limits against the chip's readings the cell file
    keeps (``check_placed``): room of a fifth or more on both sides (the
    int8 control reads only 1.5 times the largest sound ``logits_rel_rms_
    err``; the faults read 4 and 16 times the sound runs), and each fault
    that was planted is one of ``planted``'s."""
    cell = load_json("workloads", CELL + ".json")
    placed = cell["check_placed"]
    assert set(placed) == {"logits_rel_rms_err", "token_argmax_gap",
                           "confidence_margin"}
    for number, read in placed.items():
        limit = cell["check"][number]
        above = read.get("fault_smallest", read.get("control_smallest"))
        assert 1.2 * read["sound_largest"] <= limit <= above / 1.2, number
        assert read.get("fault", "causal") in FAULTS
    assert FAULTS[placed["token_argmax_gap"]["fault"]] == "token_argmax_gap"
    assert FAULTS[placed["confidence_margin"]["fault"]] == "confidence_margin"


# ------------------------------------------------------------- the traffic
def test_the_reasoning_traffic_is_the_issues():
    from chipbench.generators import openloop_lognormal
    cell = load_json("workloads", CELL + ".json")
    t = load_json("traffic", cell["traffic"] + ".json")
    assert t["generator"] == "openloop_lognormal"
    assert t["prompt"] == {"median": 256, "sigma": 0.8, "min": 128,
                           "max": 1024}
    assert t["output"] == {"median": 1536, "sigma": 0.5, "min": 512,
                           "max": 3072}
    assert 0.75 <= t["rate_per_s"] / t["knee"]["knee_per_s"] <= 0.85
    serving = cell["serving"]
    assert serving["max_model_len"] == 1024 + 3072 == \
        cell["inference"]["max_tokens"] == cell["check"]["reference_len"]
    assert serving["num_slots"] == 48 and cell["chips"] == 1
    # low_confidence_static is the one schedule served: no key chooses it
    assert serving["block_diffusion"] == {"denoising_steps": 2}
    assert cell["job"] == "serve_blocks"
    # the job asks the program's tracer for a ring that holds a window and
    # its drain (41,000 records); every other cell reads the default's
    assert cell["phase_buffer_size"] == 131072
    assert cell["check"]["probe"] == {
        "prompt_lengths": [1, 1, 2, 2, 3, 3], "max_new": 64,
        "reference_len": 128}
    assert cell["modules"] == {"prefill": "^jit_pf$", "decode": "^jit_blk$"}
    assert cell["inference"]["dtype"] == "bfloat16"
    assert len(cell["why"]) <= 200
    vocab = config()["dims"]["vocab"]
    reqs = openloop_lognormal.generate(t, SEED, vocab, 40.0)
    assert max(len(r["prompt"]) + r["max_new"] for r in reqs) <= 4096
    assert any(r["due"] < 0 for r in reqs)
    # the history covers the longest output at the traffic's own pace
    start = t["steady_start"]
    assert start["history_s"] * 1e3 >= 3072 * start["tick_ms"]
    # every bucket a request of the run can hit is warmed in set-up
    pow2 = lambda n: 1 << max(0, n - 1).bit_length()
    assert {pow2(len(r["prompt"]) // 4 * 4) for r in reqs} <= \
        {pow2(n) for n in cell["warm_prompt_lengths"]}
    mem = cell["memory"]
    assert mem["memory_peak_bytes"] >= 0.6 * mem["chip_bytes"]


def test_the_cell_is_in_the_lists_its_readers_apply_to():
    """Every list OLMoE's cell is in but ``moe_load_skew`` (its reader
    divides by the slots, and a pass routes four rows a slot), appended
    after the cells the benchmark had. Of the entries PR 57 appended, the
    scope-time ones do not list this cell yet (``decode_sample_ms`` reads
    ``jit_dec``, which a pass over blocks never calls: PERF.md section 7),
    and the six of ``serve_blocks.entries.json`` list it alone, as that
    file has them."""
    man = manifest()
    other = "olmoe-1b-7b.serve-chat-2k"
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            listed = m.get("workloads")
            if listed is None or m["name"] in scope_time.METRICS or \
                    m["name"] in serve_blocks.METRICS:
                continue
            assert (CELL in listed) == \
                (other in listed and m["name"] != "moe_load_skew"), m["name"]
            # appended after the cells the benchmark had
            assert CELL not in listed or \
                listed.index(CELL) > listed.index(other)
    # by their places counted from the front: entries are only appended,
    # so the next cell and configuration go behind these and move neither
    assert [w["name"] for w in man["workloads"]].index(CELL) == 9
    assert [c["name"] for c in man["configs"]].index(NAME) == 6
    assert [m for m in man["per_layer"]
            if m["name"] in serve_blocks.METRICS] == entries()
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in man[group] for key in ("why", "layer", "source")
             if key in e]
    assert not [(n, k, len(text)) for n, k, text in lines
                if not (1 <= len(text) <= 200 and text.isprintable())]


def test_the_cell_before_this_one_keeps_its_lists():
    """``test_chipbench_xing.py::test_the_cell_is_in_the_lists_its_readers_
    apply_to``, word for word but for one line: that test pins
    ``xing4.0-29b-a4b`` as the LAST configuration, which no longer holds
    once this PR's is appended, and its file is the accepted benchmark's
    (``tests/conftest.py`` expects it to fail until a ``benchmark`` PR
    repairs the pin; PERF.md section 7). Here the configuration is found
    by its place from the front."""
    man = manifest()
    name, cell = "xing4.0-29b-a4b", "xing4.0-29b-a4b.serve-docqa"
    other = "k-exaone-236b-a23b.serve-longdoc-16k"
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            listed = m.get("workloads")
            if listed is not None and m["name"] != "kv_read_share" and \
                    m["name"] not in serve_latent.METRICS:  # its own four
                assert (cell in listed) == (other in listed), m["name"]
                assert cell not in listed or \
                    listed.index(cell) > listed.index(other)
    names = {m["name"] for m in man["per_layer"]
             if cell in m.get("workloads", [])}
    assert {"moe_experts_touched", "moe_decode_hbm_share", "moe_ffn_share",
            "moe_prefill_roofline", "moe_share_skew", "kv_live_share",
            "kv_read_share", "mixer_decode_hbm_share", "tick_ms",
            "idle_tick_ms"} <= names and "moe_load_skew" not in names
    assert [w["name"] for w in man["workloads"]].index(cell) == 8
    assert [c["name"] for c in man["configs"]].index(name) == 5
    lines = [(e["name"], key, e[key])
             for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in man[group] for key in ("why", "layer", "source")
             if key in e]
    assert {(name, "why"), (cell, "why")} <= {(n, k) for n, k, _ in lines}


# ------------------------------------------------ the serve_blocks.py readers
def made_up(records, modules):
    from chipbench.layer_metrics import _program_spans as P
    from chipbench.trace import Device, Trace
    ticks = [(0.0, 0.1, "serve/tick", 1, 24), (0.1, 0.2, "serve/tick", 2, 24)]
    placed = P.Placed(sorted(ticks + records), ticks, 0.0)
    ctx = types.SimpleNamespace(
        cell={"job": "serve", "moe_kernels": {"pattern": "ragged-dot"},
              "modules": {"prefill": "^jit_pf$", "decode": "^jit_blk$"},
              "serving": {"num_slots": 48, "max_model_len": 4096}},
        dims=config()["dims"], counts=counts_sdar, log=lambda msg: None,
        peak={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        state={"program_spans": (placed, {})})
    ops = [(s, e, "fusion.1") for s, e, _ in modules]
    trace = Trace([Device("/device:TPU:0", ops, modules)],
                  [(0.0, 0.2, "window")])
    record = {"serve_tokens_per_s": 640.0, "window_s": 0.2,
              "live_tokens": [60000, 70000], "vocab_rows": 151936,
              "block_period_ms": 93.5, "block_prefill_share": 8.9}
    return ctx, record, trace


RECORDS = [(0.05, 0.05, "serve/block_pass", 30, 90),
           (0.05, 0.05, "serve/block_write", 10, 40),
           (0.05, 0.05, "serve/moe_decode", 700, 60),
           (0.15, 0.15, "serve/block_pass", 32, 100),
           (0.15, 0.15, "serve/block_write", 24, 56),
           (0.15, 0.15, "serve/moe_decode", 720, 60),
           (0.30, 0.30, "serve/block_write", 9, 9)]     # after the window
MODULES = [(0.02, 0.05, "jit_blk"), (0.06, 0.08, "jit_pf"),
           (0.12, 0.15, "jit_blk")]


def test_block_readers_on_made_up_records():
    args = made_up(RECORDS, MODULES)
    read = serve_blocks.METRICS
    # 128 tokens over 30 + 10 + 32 + 24 rows of requests
    assert read["tokens_per_pass"](*args) == pytest.approx(128 / 96)
    # 34 of 96 rows wrote; the pass program ran 0.06 of 0.08 busy seconds
    assert read["block_write_share"](*args) == pytest.approx(
        100 * 34 / 96 * 0.06 / 0.08)
    assert read["block_period_ms"](*args) == 93.5
    assert read["block_prefill_share"](*args) == 8.9
    need = counts_sdar.block_pass_bytes(args[0].dims, 710, 65000, 192, 2,
                                        151936)
    assert read["block_pass_hbm_share"](*args) == pytest.approx(
        100 * need / 819e9 / 0.03)
    assert read["block_pass_hbm_share"](*args) < 100
    # the largest count 60 over 6 layers, of a mean of 48 x 4 x 8 / 128 rows
    assert read["moe_load_skew.blocks"](*args) == pytest.approx(10 / 12)


def test_block_readers_return_nothing_where_there_is_nothing_to_read():
    """Without a trace; for a program that records no pass (every other
    family, the parent); for a counts module without the name."""
    with_trace = made_up(RECORDS, MODULES)
    for name, read in serve_blocks.METRICS.items():
        assert read(with_trace[0], with_trace[1], None) is None, name
    silent = made_up([r for r in RECORDS if "block" not in r[2]], MODULES)
    for name in ("tokens_per_pass", "block_write_share",
                 "block_pass_hbm_share", "moe_load_skew.blocks"):
        assert serve_blocks.METRICS[name](*silent) is None, name
    other = made_up(RECORDS, MODULES)
    other[0].counts = types.SimpleNamespace()
    assert serve_blocks.block_pass_hbm_share(*other) is None


def test_the_six_entries_each_with_a_reader_fit_the_manifest():
    man, mine = manifest(), entries()
    assert [m["name"] for m in mine] == list(serve_blocks.METRICS)
    e2e = {m["name"]: m["workloads"] for m in man["end_to_end"]
           if "workloads" in m}
    layers = {m["layer"] for m in man["per_layer"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
        assert m["layer"] in layers and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert m["workloads"] == [CELL] and CELL in e2e[m["moves"]]


def test_a_traced_rehearsal_under_the_laid_over_manifest_reads_the_blocks(
        tmp_path):
    """A copy of ``chipbench/`` under a manifest with the six entries at
    the end of ``per_layer``: the cell's traced rehearsal reports those the
    CPU has something to read for (the device's program intervals are the
    chip's), and a pass of a slot yields between 1 and 4/3 tokens."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    man = manifest()
    man["per_layer"] += entries()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    out, last = run("--trace", "1", cwd=tmp_path,
                    script=str(tmp_path / "chipbench" / "run.py"))
    got = last["metrics"]
    if "tick_host_ms" not in got:
        pytest.skip("the host was too loaded to lay the program's records "
                    "on the trace's clock")
    assert last["correct"] is True
    assert {"tokens_per_pass", "block_period_ms", "block_prefill_share",
            "moe_load_skew.blocks"} <= set(got), sorted(got)
    # the accepted reader would divide by a row a slot: B times high
    assert got["moe_load_skew.blocks"]["value"] >= 1.0
    assert 0.5 < got["tokens_per_pass"]["value"] <= 4 / 3 + 1e-9
    assert got["block_period_ms"]["value"] > 0
    assert 0 <= got["block_prefill_share"]["value"] <= 100
    assert "kv_read_share" in got and "moe_experts_touched" in got


if __name__ == "__main__":
    from chipbench import run as bench
    with planted(sys.argv.pop(1)):
        sys.exit(bench.main())
