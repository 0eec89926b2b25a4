"""chip_smoke.py — the standing proof that the train and serve paths run on
the chip, through the entry points a user calls, at published widths.

    python chip_smoke.py             # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 dp=4 and its
                                     # comparison, and no other phase

One process, no child processes; the script never chooses the platform. It
exits non-zero unless ``jax.devices()[0].platform == "tpu"``, and any phase
that raises ends it. The last line of stdout is the contract's one JSON
object; everything else is on earlier lines. Weights and tokens come from
``--seed``; nothing is downloaded or read from a record.

Times printed here are smoke output — one cold window each, compile next
to it — and not measurements; the benchmark (ROADMAP A0) makes those.

``--rehearse`` is the guide's first two rehearsals: the same control flow
at tiny sizes on whatever platform JAX was given from outside
(``JAX_PLATFORMS=cpu``, and ``XLA_FLAGS=--xla_force_host_platform_device_
count=4`` with ``--chips 4``). Its last line reports the platform it really
ran on, so it cannot pass for a chip run.
"""

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# bf16 carries 8 bits of mantissa; the attention kernels and their XLA
# reference round differently inside the softmax, so outputs and gradients
# agree to a few units of 2^-8 of the tensor's largest magnitude.
KERNEL_TOL = 2e-2
# a greedy token counts as right when its teacher-forced logit is within
# this share of the row's largest |logit| of the row's maximum (bf16
# near-ties break differently in differently shaped programs)
LOGIT_TOL = 2.0 ** -5
# AdamW learning rate of every training phase, with no warm-up schedule.
# The example configs' 3e-4 / 2e-4 come with 200-300 warm-up steps; without
# them Adam's first, sign-like steps overshoot on a 350M model and the loss
# rises by step 3 at any rate from 3e-5 up (chip runs of PR 24). At 3e-6 it
# falls at every step on both widths.
SMOKE_LR = 3e-6


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


# ------------------------------------------------------------------- device

def phase_device(args, cache_dir, counters):
    import jax
    from deepspeed_tpu.utils.compile_cache import cache_entries
    devs = jax.devices()
    d0 = devs[0]
    log("device", f"platform={d0.platform} kind={d0.device_kind!r} "
                  f"count={len(devs)}")
    vers = {m: importlib.metadata.version(m)
            for m in ("jax", "jaxlib", "libtpu", "numpy")}
    log("device", "versions " + " ".join(f"{k}={v}" for k, v in vers.items()))
    log("device", f"compile cache dir={cache_dir} "
                  f"entries_at_start={cache_entries(cache_dir)}")
    if d0.platform != "tpu" and not args.rehearse:
        raise SystemExit(
            f"chip_smoke: jax found no accelerator (platform="
            f"{d0.platform!r}); this script only passes on a TPU")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, jax sees {len(devs)}")
    stats = d0.memory_stats() or {}
    if stats:
        log("device", f"hbm bytes_limit={stats.get('bytes_limit')} "
                      f"bytes_in_use={stats.get('bytes_in_use')}")

    def listen(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            counters[event.rsplit("/", 1)[1]] += 1
    jax.monitoring.register_event_listener(listen)


# ------------------------------------------------------------------ kernels

def _close(name, got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite values"
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    if err > KERNEL_TOL * scale:
        raise AssertionError(
            f"{name}: max |diff| {err:.4g} > {KERNEL_TOL} x max|ref| "
            f"{scale:.4g}")
    return err / max(scale, 1e-30)


def _ref_by_heads(ref, q, k, v, ct, heads_per_call):
    """Reference output and gradients, a few heads at a time: dense fp32
    logits at T=8192 are 256 MiB per head."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, k, v, ct):
        out, vjp = jax.vjp(ref, q, k, v)
        return (out,) + vjp(ct)

    parts = [run(*(x[:, h:h + heads_per_call] for x in (q, k, v, ct)))
             for h in range(0, q.shape[1], heads_per_call)]
    return [jnp.concatenate(p, axis=1) for p in zip(*parts)]


def phase_kernels(args, sz):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.flash_attention import reference_attention
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        sparse_attention_pallas
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.flash_attention_packed import \
        packed_flash_attention
    from deepspeed_tpu.ops.sparse_attention_ops import (
        FixedSparsityConfig, layout_to_mask)
    from deepspeed_tpu.parallel.topology import on_tpu

    interpret = not on_tpu()      # Mosaic on the chip; only --rehearse is not
    assert args.rehearse or not interpret
    log("kernels", f"interpret={interpret}")
    key = jax.random.PRNGKey(args.seed)

    def qkvc(shape, i):
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        return [(jax.random.normal(kk, shape, jnp.float32) * 0.5
                 ).astype(jnp.bfloat16) for kk in ks]

    def check(name, fn, ref, shape, i, heads_per_call):
        q, k, v, ct = qkvc(shape, i)
        t0 = time.perf_counter()

        @jax.jit
        def run(q, k, v, ct):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(ct)

        got = jax.block_until_ready(run(q, k, v, ct))
        if not interpret:
            assert "tpu_custom_call" in run.lower(q, k, v, ct).compile(
                ).as_text(), f"{name}: no tpu_custom_call in the program"
        want = _ref_by_heads(ref, q, k, v, ct, heads_per_call)
        errs = [_close(f"{name}:{part}", g, w) for part, g, w in
                zip(("out", "dq", "dk", "dv"), got, want)]
        log("kernels", f"{name} {shape} ok  rel_err out/dq/dk/dv = " +
            "/".join(f"{e:.2e}" for e in errs) +
            f"  ({time.perf_counter() - t0:.1f}s with compile)")

    causal = lambda q, k, v: reference_attention(q, k, v, causal=True)
    b, h, t, d = sz["attn"]
    # [B,H,T,D] kernel at the train shape and at the resident/streamed
    # boundary (K/V of one head exactly 1 MiB)
    flash = lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                            interpret, None)
    check("flash", flash, causal, (b, h, t, d), 0, h)
    check("flash_boundary", flash, causal, sz["attn_boundary"], 1, 2)

    # packed [B,T,H*D] kernel — the layout the GPT-2 train step uses
    def to4(x):
        return x.reshape(x.shape[0], x.shape[1], h, d).transpose(0, 2, 1, 3)

    def packed_as4(q, k, v):
        # the kernel sees packed tensors; 4-D in and out so one reference
        # serves all three kernels
        pk = lambda x: x.transpose(0, 2, 1, 3).reshape(b, t, h * d)
        return to4(packed_flash_attention(pk(q), pk(k), pk(v), h,
                                          interpret=interpret))
    check("packed", packed_as4, causal, (b, h, t, d), 2, h)

    # block-sparse kernel on a causal Fixed layout
    block = sz["sparse_block"]
    layout = FixedSparsityConfig(
        num_heads=h, block=block, num_local_blocks=4, num_global_blocks=1,
        attention="unidirectional").make_layout(t)
    mask = jnp.asarray(layout_to_mask(layout, block))[None]
    sparse = lambda q, k, v: sparse_attention_pallas(
        q, k, v, layout, block, interpret=interpret)

    def sparse_ref(q, k, v):
        return reference_attention(q, k, v, causal=False, mask=mask)
    check("block_sparse", sparse, sparse_ref, (sz["sparse_batch"], h, t, d),
          3, h)


    # the experts' grouped matmul at a row count that is no multiple of 8:
    # four picks of one token, float32. lax.ragged_dot alone reads about 1
    # off there on the chip (logged, not asserted: it is the compiler's);
    # apply_grouped pads the rows and must be right (moe/experts.py)
    from jax import lax
    from deepspeed_tpu.moe.experts import GatedExpertFFN
    e, m, f = sz["grouped"]
    ffn = GatedExpertFFN(m, f, e)
    with jax.default_matmul_precision("highest"):
        w = ffn.init(jax.random.fold_in(key, 4))
        x = jax.random.normal(jax.random.fold_in(key, 5), (4, m), jnp.float32)
        ids = jnp.asarray([1, 1, e // 2, e - 1])
        counts = jnp.bincount(ids, length=e).astype(jnp.int32)
        want = jnp.einsum(
            "nf,nfm->nm", jax.nn.silu(
                jnp.einsum("nm,nmf->nf", x, w["w_gate"][ids])) *
            jnp.einsum("nm,nmf->nf", x, w["w_up"][ids]), w["w_down"][ids])
        got = jax.jit(ffn.apply_grouped)(w, x, counts)
        raw = jax.jit(lax.ragged_dot)(x, w["w_gate"], counts)
        raw_want = jnp.einsum("nm,nmf->nf", x, w["w_gate"][ids])
    raw_err = float(jnp.abs(raw - raw_want).max() / jnp.abs(raw_want).max())
    log("kernels", f"grouped matmul, 4 float32 rows over {e} groups of "
        f"[{m}, {f}] ok  rel_err {_close('grouped', got, want):.2e}  "
        f"(lax.ragged_dot alone on the same rows: {raw_err:.2e})")


# -------------------------------------------------------------------- train

def _fixed_batch(seed, gas, rows, seq, vocab):
    """Seeded tokens with a Zipf unigram distribution, as text has: uniform
    tokens would leave nothing to learn below ln(vocab), which the first
    step already reaches."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1)
    ids = rng.choice(vocab, size=(gas, rows, seq), p=p / p.sum())
    return {"input_ids": ids.astype(np.int32)}


def _engine(args, config_name, model_cfg, devices, micro, gas, stage=None):
    """A training engine over a dp mesh of ``devices`` with the geometry of
    an example config: its optimizer, precision and ZeRO stage (or
    ``stage``); global batch cut to micro x gas per device; SMOKE_LR in
    place of its warm-up schedule."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model
    with open(os.path.join(REPO, "examples", "configs", config_name)) as f:
        config = json.load(f)
    config.update(train_batch_size=micro * gas * len(devices),
                  train_micro_batch_size_per_gpu=micro,
                  steps_per_print=0, seed=args.seed)
    config.pop("scheduler")
    config["optimizer"]["params"]["lr"] = SMOKE_LR
    if stage is not None:
        config["zero_optimization"]["stage"] = stage
    mesh = deepspeed_tpu.parallel.initialize_mesh(dp=len(devices),
                                                  devices=devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2Model(model_cfg), config=config, mesh_manager=mesh)
    return engine


def _release():
    """After the caller has dropped its engine: forget the global mesh and
    collect, so the next phase starts with the chip's memory free."""
    from deepspeed_tpu.parallel.topology import reset_mesh
    reset_mesh()
    gc.collect()


def _on_chip():
    import jax
    return jax.devices()[0].platform == "tpu"


def _step_hlo(engine, batch):
    """Optimized HLO of the engine's compiled train step (the repo's own
    idiom: analysis/artifacts.py). With the persistent cache on this is a
    cache read, not a second compile."""
    import jax
    import jax.numpy as jnp
    args = (engine.params, engine.opt_state, engine.scaler_state,
            engine._to_device_batch(batch), jnp.float32(engine.get_lr()[0]),
            jax.random.PRNGKey(0), None, jnp.float32(1.0))
    with engine.mesh:
        return engine._train_step_fn.lower(*args).compile().as_text()


def _train(engine, batch, steps, phase, tokens_per_step):
    """``steps`` global steps on one fixed batch. Returns the losses; asserts
    they are finite and fall, and that the step compiled exactly once."""
    import jax
    losses = []
    t0 = time.perf_counter()
    losses.append(float(engine.train_batch(batch=batch)))
    compile_s = time.perf_counter() - t0
    fn = engine._train_step_fn
    engine._watchdog.observe(fn, label="train_batch")
    t1 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready(engine.params)
    window = time.perf_counter() - t1
    losses = [float(x) for x in losses]
    engine._watchdog.observe(fn, label="train_batch")
    log(phase, "losses " + " ".join(f"{x:.4f}" for x in losses))
    log(phase, f"smoke output, not a measurement: first step with compile "
               f"{compile_s:.1f}s; {steps - 1} steps in {window:.2f}s "
               f"({window / (steps - 1):.3f}s/step, "
               f"{tokens_per_step * (steps - 1) / window:.0f} tokens/s)")
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert all(x < losses[0] for x in losses[1:]) and \
        losses[-1] < losses[1], f"loss did not fall: {losses}"
    assert engine._watchdog.recompiles == 0, \
        f"train step recompiled {engine._watchdog.recompiles}x after step 1"
    return losses


def phase_train(args, sz):
    import jax

    # geometry of the example config (micro-batch 8, bf16, ZeRO-1, AdamW);
    # global batch cut to gas=2
    micro, gas, seq = sz["train_micro"], 2, sz["train_seq"]
    # chosen to fit 16 GB from the AOT compile's memory_analysis (args +
    # temp per device): remat + chunked loss 12.6 GB; remat alone 15.3 GB;
    # chunked loss alone 15.7 GB; neither 18.7 GB
    cfg = dataclasses.replace(sz["train_model"], n_positions=seq, remat=True,
                              loss_chunking="always", attn_backend="auto")
    log("train", f"model n_layer={cfg.n_layer} n_embd={cfg.n_embd} "
                 f"n_head={cfg.n_head} seq={seq} micro={micro} gas={gas} "
                 f"zero=1 bf16 lr={SMOKE_LR} remat={cfg.remat} "
                 f"loss_chunking={cfg.loss_chunking} (chosen to fit 16 GB)")
    engine = _engine(args, "gpt2_350m_zero1.json", cfg, jax.devices()[:1],
                     micro, gas)
    batch = _fixed_batch(args.seed, gas, micro, seq, cfg.vocab_size)
    _train(engine, batch, sz["train_steps"], "train", gas * micro * seq)
    if _on_chip():
        assert "tpu_custom_call" in _step_hlo(engine, batch), \
            "train step holds no tpu_custom_call: the dense XLA attention " \
            "path was taken, not the Pallas kernel"
        log("train", "compiled step contains tpu_custom_call")
    stats = jax.devices()[0].memory_stats() or {}
    log("train", f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    del engine
    _release()


# -------------------------------------------------------------------- serve

def _teacher_forced_ok(engine, seq_ids, n_prompt, n_pos, name):
    """Every generated token of ``seq_ids`` is the arg-max of the full
    forward pass over its own prefix, to LOGIT_TOL. Used only where the two
    serving programs broke a bf16 near-tie differently."""
    import jax.numpy as jnp
    ids = np.zeros((1, n_pos), np.int32)
    ids[0, :len(seq_ids)] = seq_ids
    logits = np.asarray(engine.forward(jnp.asarray(ids))[0], np.float32)
    vocab = engine.module.config.vocab_size
    rows = logits[n_prompt - 1:len(seq_ids) - 1, :vocab]
    chosen = rows[np.arange(len(rows)), seq_ids[n_prompt:]]
    gaps = (rows.max(-1) - chosen) / np.abs(rows).max(-1)
    if gaps.max() > LOGIT_TOL:
        i = int(gaps.argmax())
        raise AssertionError(
            f"{name}: new token {i} = {seq_ids[n_prompt + i]} is "
            f"{gaps[i]:.4f} of max|logit| below the arg-max (tolerance "
            f"{LOGIT_TOL})")
    return float(gaps.max())


def _serve_round(srv, engine, prompts, max_new, n_pos, label):
    from deepspeed_tpu.serving import RequestState, SamplingParams
    streamed, first = {}, {}
    t0 = time.perf_counter()

    def on_token(req, tok):
        streamed.setdefault(req.request_id, []).append(int(tok))
        first.setdefault(req.request_id, time.perf_counter())

    rids = [srv.submit(p, SamplingParams(max_new_tokens=max_new),
                       on_token=on_token) for p in prompts]
    srv.run_until_idle()
    wall = time.perf_counter() - t0
    ttft = [first[r] - t0 for r in rids]
    n_tok = sum(len(streamed[r]) for r in rids)
    log("serve", f"{label}: smoke output, not a measurement: "
                 f"{len(rids)} requests, {n_tok} tokens in {wall:.2f}s; "
                 f"time to first token " +
                 " ".join(f"{x:.3f}s" for x in ttft) +
                 f"; decode {(n_tok - len(rids)) / (wall - min(ttft)):.1f} "
                 f"tokens/s after the first token")
    exact = 0
    for rid, p in zip(rids, prompts):
        req = srv.result(rid)
        assert req.state is RequestState.FINISHED, (rid, req.state)
        got = np.asarray(streamed[rid], np.int32)
        assert len(got) == max_new, (rid, len(got))
        np.testing.assert_array_equal(np.asarray(req.output_ids)[len(p):],
                                      got)
        ref = np.asarray(engine.generate(p[None], max_new_tokens=max_new))[0]
        if np.array_equal(ref[len(p):], got):
            exact += 1
            continue
        at = int(np.argmax(ref[len(p):] != got))
        worst = max(
            _teacher_forced_ok(engine, np.concatenate([p, got]), len(p),
                               n_pos, f"served req{rid}"),
            _teacher_forced_ok(engine, ref, len(p), n_pos,
                               f"generate() req{rid}"))
        log("serve", f"{label}: req{rid} (prompt {len(p)}) parts from "
                     f"generate() at new token {at}; both sequences are "
                     f"arg-max of the teacher-forced logits to "
                     f"{worst:.4f} of max|logit| (tolerance {LOGIT_TOL})")
    log("serve", f"{label}: {exact}/{len(rids)} requests token-identical to "
                 f"generate()")
    return exact


def phase_serve(args, sz):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Model
    from deepspeed_tpu.serving import ServingEngine

    max_len, max_new = sz["serve_max_len"], sz["serve_new"]
    cfg = dataclasses.replace(sz["serve_model"], n_positions=max_len,
                              dtype="bfloat16")
    log("serve", f"model n_layer={cfg.n_layer} n_embd={cfg.n_embd} "
                 f"n_head={cfg.n_head} bf16; pool 8 slots x {max_len}; "
                 f"prompts {sz['serve_prompts']} + {max_new} new, greedy")
    engine = deepspeed_tpu.init_inference(
        GPT2Model(cfg), config={"dtype": "bfloat16", "seed": args.seed,
                                "max_tokens": max_len})
    srv = ServingEngine(engine, {"num_slots": 8, "max_model_len": max_len,
                                 "max_queue": 8})
    rng = np.random.default_rng(args.seed)
    draw = lambda: [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
                    for n in sz["serve_prompts"]]
    # round 1 pays every compile (pool, four prefill buckets, the decode
    # tick, four generate() programs); round 2 is the same shapes warm
    _serve_round(srv, engine, draw(), max_new, max_len, "round 1 (cold)")
    _serve_round(srv, engine, draw(), max_new, max_len, "round 2 (warm)")
    assert srv.decode_executables() == 1, srv.decode_executables()
    srv.shutdown()
    stats = jax.devices()[0].memory_stats() or {}
    log("serve", f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    del srv, engine
    _release()


# ---------------------------------------------------------------- four chips

def _assert_quartered(name, leaf, devices):
    shards = leaf.addressable_shards
    on = {s.device for s in shards}
    assert on == set(devices), \
        f"{name}: shards on {sorted(d.id for d in on)}, want all of " \
        f"{sorted(d.id for d in devices)}"
    for s in shards:
        share = s.data.size / leaf.size
        assert abs(share - 1 / len(devices)) < 0.01, \
            f"{name}: shard on device {s.device.id} is {share:.3f} of the leaf"
    return f"{name} {tuple(leaf.shape)} -> {len(shards)} shards of " \
           f"{tuple(shards[0].data.shape)}"


def phase_zero3_dp4(args, sz):
    import jax

    devices = jax.devices()[:4]
    seq, micro, gas = sz["train_seq"], sz["z3_micro"], 2
    steps = sz["z3_steps"]
    cfg = dataclasses.replace(sz["serve_model"], n_positions=seq, remat=True,
                              attn_backend="auto")
    log("zero3-dp4", f"model n_layer={cfg.n_layer} n_embd={cfg.n_embd} "
                     f"n_head={cfg.n_head} seq={seq} zero=3 bf16 dp=4 "
                     f"remat=True; cut from gpt2_1p3b_zero3.json: global "
                     f"batch 512 -> {micro * gas * 4} (micro {micro}/chip x "
                     f"gas {gas}), lr={SMOKE_LR} with no warm-up")
    engine = _engine(args, "gpt2_1p3b_zero3.json", cfg, devices, micro, gas)
    batch = _fixed_batch(args.seed, gas, micro * 4, seq, cfg.vocab_size)
    _train(engine, batch, steps, "zero3-dp4", gas * micro * 4 * seq)

    name, leaf = max(
        ((jax.tree_util.keystr(p), x) for p, x in
         jax.tree_util.tree_leaves_with_path(engine.params)),
        key=lambda kv: kv[1].size)
    log("zero3-dp4", "param " + _assert_quartered(name, leaf, devices))
    moments = [x for x in jax.tree.leaves(engine.opt_state)
               if getattr(x, "shape", None) == leaf.shape]
    assert moments, "no optimizer-state leaf of the large parameter's shape"
    for i, m in enumerate(moments):
        log("zero3-dp4", "optimizer state " +
            _assert_quartered(f"moment{i}{name}", m, devices))

    in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        in_use.append(stats.get("bytes_in_use"))
        log("zero3-dp4", f"device {d.id}: bytes_in_use="
                         f"{stats.get('bytes_in_use')} peak_bytes_in_use="
                         f"{stats.get('peak_bytes_in_use')}")
    if _on_chip():
        assert all(in_use), in_use
        spread = (max(in_use) - min(in_use)) / max(in_use)
        assert spread < 0.05, f"bytes in use differ by {spread:.1%}: {in_use}"
        log("zero3-dp4", f"bytes_in_use spread across devices {spread:.2%}")
    hlo = _step_hlo(engine, batch)
    ops = ["all-gather", "reduce-scatter"]
    if _on_chip():
        ops.append("tpu_custom_call")
    else:
        # the CPU compiler of the rehearsal leaves all-reduce + slice unfused
        ops[1] = "all-reduce"
    for op in ops:
        assert op in hlo, f"compiled ZeRO-3 step holds no {op}"
    log("zero3-dp4", "compiled step contains " + ", ".join(ops))
    del engine
    _release()


def phase_zero3_comparison(args, sz):
    import jax

    devices = jax.devices()[:4]
    seq, micro, gas = sz["train_seq"], sz["z3_micro"], 2
    steps = sz["z3_steps"]
    cfg = dataclasses.replace(sz["serve_model"], n_positions=seq, remat=True,
                              n_layer=min(4, sz["serve_model"].n_layer),
                              attn_backend="auto")
    rows = micro * gas * 4
    batch = _fixed_batch(args.seed, 1, rows, seq, cfg.vocab_size)["input_ids"]
    log("comparison", f"{cfg.n_layer} layers of the same widths, global "
                      f"batch {rows}: ZeRO-3 on 4 devices vs ZeRO-0 on 1")
    runs = {}
    for label, stage, devs, g in (("zero3 dp4", 3, devices, gas),
                                  ("zero0 dp1", 0, devices[:1], gas * 4)):
        engine = _engine(args, "gpt2_1p3b_zero3.json", cfg, devs, micro, g,
                         stage=stage)
        b = {"input_ids": batch.reshape(g, rows // g, seq)}
        runs[label] = _train(engine, b, steps, f"comparison {label}",
                             rows * seq)
        del engine
        _release()
    a, b = (np.asarray(v) for v in runs.values())
    diff = float(np.abs(a - b).max())
    log("comparison", f"max |loss difference| over {steps} steps {diff:.2e} "
                      f"(tolerance {sz['z3_loss_tol']})")
    assert diff <= sz["z3_loss_tol"], (runs, diff)


# --------------------------------------------------------------------- main

def sizes(rehearse):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2_1_3B, GPT2_350M
    if not rehearse:
        return dict(
            attn=(8, 16, 1024, 64), attn_boundary=(1, 12, 8192, 64),
            sparse_block=64, sparse_batch=2, grouped=(64, 2048, 1536),
            train_model=GPT2_350M, train_seq=1024, train_micro=8,
            train_steps=4,
            serve_model=GPT2_1_3B, serve_max_len=1024, serve_new=32,
            serve_prompts=(64, 128, 256, 512),
            z3_micro=4, z3_steps=3, z3_loss_tol=2e-3)
    tiny = GPT2Config(vocab_size=512, n_embd=128, n_layer=2, n_head=2,
                      pad_vocab_to_multiple=128)
    return dict(
        attn=(1, 2, 256, 64), attn_boundary=(1, 2, 256, 64),
        sparse_block=64, sparse_batch=1, grouped=(8, 64, 32),
        train_model=tiny, train_seq=128, train_micro=2,
        train_steps=4,
        serve_model=dataclasses.replace(tiny, n_layer=5),
        serve_max_len=128, serve_new=8, serve_prompts=(8, 16, 32, 64),
        z3_micro=2, z3_steps=3, z3_loss_tol=2e-3)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the ZeRO-3 dp=4 phase and its "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX was given "
                         "(CPU rehearsal; never a chip result)")
    args = ap.parse_args()
    t_start = time.perf_counter()

    import collections
    import jax
    from deepspeed_tpu.utils.compile_cache import (cache_entries,
                                                   enable_compile_cache)
    cache_dir = enable_compile_cache()
    counters = collections.Counter()
    phase_device(args, cache_dir, counters)
    sz = sizes(args.rehearse)
    phases = (phase_zero3_dp4, phase_zero3_comparison) if args.chips == 4 \
        else (phase_kernels, phase_train, phase_serve)
    for phase in phases:
        t0 = time.perf_counter()
        phase(args, sz)
        log("phase", f"{phase.__name__[6:]} passed in "
                     f"{time.perf_counter() - t0:.1f}s")
    log("device", f"compile cache dir={cache_dir} "
                  f"entries_at_end={cache_entries(cache_dir)} "
                  f"hits={counters['cache_hits']} "
                  f"misses={counters['cache_misses']}")
    log("phase", f"all passed in {time.perf_counter() - t_start:.1f}s "
                 f"(smoke output, not a measurement)")
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    sys.exit(main())
