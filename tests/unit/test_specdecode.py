"""Speculative + multi-token decoding over the slot pool (ISSUE 12).

The contract under test: speculation is an ACCELERATOR, never a
behavior change — the emitted stream is bitwise identical with
speculation on or off (greedy AND sampled, because verification is
exact-match against the target's deterministic per-position sample),
rollback restores rejected KV columns exactly (int8 lanes via the
untouched-column round-trip guarantee), each pow2-K verify flavor
compiles exactly once, and a failover survivor replays a SAMPLED
stream bit-for-bit so the router's delivered-position dedup stays
exactly-once.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (RequestState, SamplingParams,
                                   ServingEngine, build_fleet)
from deepspeed_tpu.serving.config import DraftConfig, SpeculativeConfig

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
VOCAB = 96

#: initializer_range is bumped so the tiny random model emits VARIED
#: greedy tokens (default init at this width degenerates to a constant
#: stream, which would vacuously pass every parity assertion)
MODEL_CFG = dict(vocab_size=VOCAB, n_positions=64, n_embd=64, n_layer=2,
                 n_head=4, pad_vocab_to_multiple=1, dtype="float32",
                 initializer_range=0.12)


@pytest.fixture(scope="module")
def engine():
    model = GPT2Model(GPT2Config(**MODEL_CFG))
    return deepspeed_tpu.init_inference(model, config={"dtype": "float32"})


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (t,), dtype=np.int32) for t in lengths]


def _spec_cfg(k=2, layers=1, **extra):
    cfg = {"num_slots": 4, "max_model_len": 64,
           "speculative": {"enabled": True, "k": k,
                           "draft": {"mode": "self", "layers": layers}}}
    cfg.update(extra)
    return cfg


# ------------------------------------------------------------------ parity

def test_bitwise_greedy_parity_speculation_off(engine):
    """The pre-speculation contract stands: spec disabled (the default
    config) serves bitwise what generate() produces."""
    srv = ServingEngine(engine, {"num_slots": 4, "max_model_len": 64})
    assert srv.scheduler.spec is None and srv.scheduler.draft is None
    prompts = _prompts((5, 9, 3), seed=11)
    rids = [srv.submit(p, SamplingParams(max_new_tokens=6)) for p in prompts]
    srv.run_until_idle()
    for rid, p in zip(rids, prompts):
        ref = np.asarray(engine.generate(p[None], max_new_tokens=6))[0]
        np.testing.assert_array_equal(srv.result(rid).output_ids, ref)


def test_bitwise_greedy_parity_speculation_on(engine):
    """Stronger than the ISSUE asks: speculation ON is ALSO bitwise —
    exact-match verification means the draft can only accelerate the
    stream, never alter it — across staggered admissions, slot reuse,
    and EOS retirement."""
    srv = ServingEngine(engine, _spec_cfg(k=2, layers=1))
    prompts = _prompts((5, 9, 3, 12, 7), seed=12)
    rids = [srv.submit(p, SamplingParams(max_new_tokens=8))
            for p in prompts[:3]]
    srv.step()
    srv.step()
    rids += [srv.submit(p, SamplingParams(max_new_tokens=8))
             for p in prompts[3:]]
    srv.run_until_idle()
    for rid, p in zip(rids, prompts):
        req = srv.result(rid)
        assert req.state is RequestState.FINISHED
        ref = np.asarray(engine.generate(p[None], max_new_tokens=8))[0]
        np.testing.assert_array_equal(req.output_ids, ref)
    # speculation actually ran and emitted multi-token ticks
    m = srv.metrics
    assert m.spec_ticks > 0 and m.spec_emitted > 0


def test_eos_respected_inside_accepted_block(engine):
    """A request whose EOS lands mid-accepted-block stops AT the EOS —
    tokens past it are discarded exactly like the non-speculative path."""
    prompts = _prompts((6,), seed=13)
    ref = np.asarray(engine.generate(prompts[0][None], max_new_tokens=8))[0]
    gen = ref[6:]
    eos = int(gen[2])                       # finish on the third token
    srv = ServingEngine(engine, _spec_cfg(k=4, layers=2))
    rid = srv.submit(prompts[0], SamplingParams(max_new_tokens=8,
                                                eos_token_id=eos))
    srv.run_until_idle()
    req = srv.result(rid)
    assert req.state is RequestState.FINISHED
    assert req.tokens[-1] == eos
    np.testing.assert_array_equal(np.asarray(req.tokens),
                                  gen[:len(req.tokens)])
    assert srv.scheduler.pool.free_count == 4      # slot reclaimed


# ------------------------------------------------- forced accept/rollback

def _seed_slot(engine, pool_slots, max_len, prompt, k):
    """(pool, ref, arrays) with the prompt prefilled into slot 0."""
    pool = engine.init_slot_pool(pool_slots, max_len)
    pool, first = engine.slot_prefill(pool, 0, prompt)
    n = pool_slots
    toks = np.zeros((n,), np.int32)
    pos = np.zeros((n,), np.int32)
    toks[0], pos[0] = first, len(prompt)
    temps = np.zeros((n,), np.float32)
    tk = np.zeros((n,), np.int32)
    tp = np.ones((n,), np.float32)
    sd = np.zeros((n,), np.int32)
    return pool, first, (toks, pos, temps, tk, tp, sd)


@pytest.mark.parametrize("force", ["full", "partial", "zero"])
def test_forced_acceptance_and_rollback_correctness(engine, force):
    """Accept/rollback at forced acceptance full/partial/zero: craft the
    draft block directly, verify the accept count, then CONTINUE greedy
    decoding through the rolled-back pool — the downstream stream only
    stays bitwise-correct if rollback restored rejected columns."""
    k = 4
    prompt = _prompts((6,), seed=21)[0]
    ref = np.asarray(engine.generate(prompt[None], max_new_tokens=12))[0][6:]
    pool, first, (toks, pos, temps, tk, tp, sd) = _seed_slot(
        engine, 2, 32, prompt, k)
    assert first == ref[0]
    good = ref[1:1 + k].astype(np.int32)       # exactly the greedy targets
    drafts = np.zeros((2, k), np.int32)
    if force == "full":
        drafts[0] = good
        expect_a = k
    elif force == "partial":
        drafts[0] = good
        drafts[0, 2] = (good[2] + 5) % VOCAB   # mismatch at offset 2
        expect_a = 2
    else:
        drafts[0] = (good + 7) % VOCAB
        expect_a = 0
    pool, tgt, acc = engine.slot_verify_step(pool, toks, drafts, pos, temps,
                                             tk, tp, sd)
    assert int(acc[0]) == expect_a
    emitted = [int(first)] + tgt[0, :expect_a + 1].tolist()
    assert emitted == ref[:len(emitted)].tolist()
    # continue with plain greedy decode through the (rolled-back) pool
    length = 6 + 1 + expect_a
    pending = emitted[-1]
    while len(emitted) < 12:
        toks[0], pos[0] = pending, length
        pool, nxt = engine.slot_decode_step(pool, toks, pos, temps)
        pending = int(nxt[0])
        emitted.append(pending)
        length += 1
    assert emitted == ref.tolist()


def test_int8_lane_rollback_exactness(engine):
    """int8 pools: a verify step with FULL rejection must leave every
    previously-written q/scale byte bit-identical (the untouched-column
    round-trip guarantee doing rollback duty) — only the fed token's
    column may change."""
    import jax
    k = 3
    prompt = _prompts((6,), seed=22)[0]
    pool = engine.init_slot_pool(2, 32, quantize=True)
    pool, first = engine.slot_prefill(pool, 0, prompt)
    before = jax.device_get(pool)
    n = 2
    toks = np.zeros((n,), np.int32)
    pos = np.zeros((n,), np.int32)
    toks[0], pos[0] = first, len(prompt)
    temps = np.zeros((n,), np.float32)
    drafts = np.full((n, k), 1, np.int32)
    # make every draft wrong: the greedy target at offset 0 is whatever
    # verify says — shift drafts off it afterwards via two passes
    pool2, tgt, acc = engine.slot_verify_step(pool, toks, drafts, pos, temps)
    if int(acc[0]) != 0:       # drafts accidentally matched: re-force
        drafts = (tgt[:, :k] + 11) % VOCAB
        pool2, tgt, acc = engine.slot_verify_step(pool2, toks, drafts, pos,
                                                  temps)
    assert int(acc[0]) == 0
    after = jax.device_get(pool2)
    col = len(prompt)          # the one column verify legitimately wrote
    # compare the REQUEST's lane (slot 0): free slots legitimately take
    # dummy scratch writes at their column 0, exactly like the
    # non-speculative decode step
    for tree_b, tree_a in ((before.q, after.q), (before.scales, after.scales)):
        for name in tree_b:
            b, a = tree_b[name][:, 0], tree_a[name][:, 0]  # [L, C, H(, hd)]
            mask = np.ones(b.shape, bool)
            mask[:, col] = False
            np.testing.assert_array_equal(b[mask], a[mask])


def test_int8_speculative_greedy_agreement(engine):
    """Quantized pool + speculation agrees with quantized non-spec
    serving bitwise (same dequant→compute→requant law, so exact-match
    verify keeps the streams identical)."""
    prompts = _prompts((5, 8), seed=23)
    outs = []
    for spec in (False, True):
        cfg = {"num_slots": 2, "max_model_len": 64,
               "kv_quant": {"enabled": True}}
        if spec:
            cfg["speculative"] = {"enabled": True, "k": 2,
                                  "draft": {"mode": "self", "layers": 1}}
        srv = ServingEngine(engine, cfg)
        rids = [srv.submit(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        srv.run_until_idle()
        outs.append([srv.result(r).tokens for r in rids])
    assert outs[0] == outs[1]


# ------------------------------------------------------- compile evidence

def test_pow2_k_buckets_compile_once(engine):
    """Compile-once evidence, both via the executable counter and the
    compile ledger: many ticks at one k flavor = ONE verify executable
    and zero recompile events; a second k flavor adds exactly one more
    compile."""
    from deepspeed_tpu.telemetry.compileplane import CompileLedger
    ledger = CompileLedger()
    engine.compile_plane = ledger
    try:
        prompts = _prompts((5, 9, 3, 12), seed=31)
        srv = ServingEngine(engine, _spec_cfg(k=2, layers=1))
        rids = [srv.submit(p, SamplingParams(max_new_tokens=10))
                for p in prompts]
        srv.run_until_idle()
        assert all(srv.result(r).state is RequestState.FINISHED
                   for r in rids)
        assert engine.slot_executables("slot_verify", 4, 64, 2) == 1
        ver_events = [e for e in ledger.events()
                      if e["label"] == "slot_verify"]
        assert len(ver_events) == 1 and ver_events[0]["kind"] == "compile"
        draft_events = [e for e in ledger.events()
                        if e["label"] == "slot_draft"]
        assert len(draft_events) == 1
        # a second pow2 flavor (k=4) is one more compile, not a recompile
        srv4 = ServingEngine(engine, _spec_cfg(k=4, layers=1))
        rid = srv4.submit(prompts[0], SamplingParams(max_new_tokens=6))
        srv4.run_until_idle()
        assert srv4.result(rid).state is RequestState.FINISHED
        assert engine.slot_executables("slot_verify", 4, 64, 4) == 1
        ver_events = [e for e in ledger.events()
                      if e["label"] == "slot_verify"]
        assert len(ver_events) == 2
        assert all(e["kind"] == "compile" for e in ver_events)
    finally:
        engine.compile_plane = None


def test_non_pow2_k_rejected():
    with pytest.raises(Exception, match="power of two"):
        SpeculativeConfig.from_dict({"enabled": True, "k": 3})


# ------------------------------------------------------- sampling + seeds

def test_sampling_determinism_per_seed(engine):
    """Same seed -> identical stream across separate serving engines,
    ticks, and slots; different seed -> different stream. Speculation
    on/off does not change a sampled stream either (the spec path
    samples with the same (seed, position) keys)."""
    prompt = _prompts((6,), seed=41)[0]
    sp = dict(max_new_tokens=10, temperature=0.8, top_k=25, top_p=0.9)

    def run(cfg, seed):
        srv = ServingEngine(engine, cfg)
        rid = srv.submit(prompt, SamplingParams(seed=seed, **sp))
        srv.run_until_idle()
        return srv.result(rid).tokens

    base = {"num_slots": 4, "max_model_len": 64}
    a = run(base, seed=7)
    b = run(base, seed=7)
    c = run(_spec_cfg(k=2, layers=1), seed=7)
    d = run(base, seed=8)
    assert a == b == c
    assert a != d
    assert len(set(a)) > 1          # actually sampling, not degenerate


def _straight_line_sample_rows(logits, temps, top_ks, top_ps, keys, vocab):
    """``sample_rows`` as it stood before it branched (PR 42's body, kept
    here to the letter): every call sorts the vocabulary, draws for every
    row, and picks per row at the end. The reference each branch of
    today's function has to equal bitwise."""
    import jax
    import jax.numpy as jnp
    last = logits[:, :vocab].astype(jnp.float32)
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    v = last.shape[-1]
    scaled = last / jnp.maximum(temps, 1e-6)[:, None]
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(desc, jnp.clip(top_ks - 1, 0, v - 1)[:, None],
                              axis=-1)
    k_on = (top_ks > 0)[:, None]
    masked = jnp.where(k_on & (scaled < kth), -jnp.inf, scaled)
    # top-p on the top-k survivors (exactly the first k sorted entries)
    eff_k = jnp.where(top_ks > 0, top_ks, v)
    desc = jnp.where(jnp.arange(v)[None, :] < eff_k[:, None], desc, -jnp.inf)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    p_on = (top_ps < 1.0)[:, None]
    masked = jnp.where(p_on & (masked < thresh), -jnp.inf, masked)
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


_ROWS = 12
#: (temps, top_ks, top_ps) of a call's rows, by what the rows ask for
_SAMPLER_CALLS = {
    "all_greedy": lambda r, i: (np.zeros(_ROWS), np.zeros(_ROWS),
                                np.ones(_ROWS)),
    # a greedy row may carry stale truncation registers: still greedy
    "greedy_with_registers": lambda r, i: (np.zeros(_ROWS),
                                           r.integers(0, 20, _ROWS),
                                           r.uniform(0.5, 1.0, _ROWS)),
    "sampled_no_truncation": lambda r, i: (r.uniform(0.3, 1.5, _ROWS),
                                           np.zeros(_ROWS), np.ones(_ROWS)),
    "sampled_top_k": lambda r, i: (r.uniform(0.3, 1.5, _ROWS),
                                   r.integers(1, 30, _ROWS), np.ones(_ROWS)),
    "sampled_top_p": lambda r, i: (r.uniform(0.3, 1.5, _ROWS),
                                   np.zeros(_ROWS),
                                   r.uniform(0.3, 0.99, _ROWS)),
    "sampled_top_k_and_top_p": lambda r, i: (r.uniform(0.3, 1.5, _ROWS),
                                             r.integers(1, 30, _ROWS),
                                             r.uniform(0.3, 0.99, _ROWS)),
    "mixed_greedy_and_plain": lambda r, i: (np.where(i % 3 == 0, 0.0, 0.8),
                                            np.zeros(_ROWS), np.ones(_ROWS)),
    "mixed_every_kind": lambda r, i: (np.where(i % 3 == 0, 0.0, 0.8),
                                      np.where(i % 2, 20, 0),
                                      np.where(i % 4 == 1, 0.9, 1.0)),
    "one_sampled_row": lambda r, i: (np.where(i == 5, 1.1, 0.0),
                                     np.zeros(_ROWS), np.ones(_ROWS)),
}


@pytest.mark.parametrize("rows", sorted(_SAMPLER_CALLS))
def test_sample_rows_bitwise_the_straight_line_function(rows):
    """Whatever a call's rows ask for (all greedy, all sampled with and
    without top-k / top-p, a mix), ``sample_rows`` returns bitwise what
    the straight-line function returns: the branches skip work, they
    change no token. bf16 logits over a padded vocabulary, as the engine
    hands them over."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.speculative import row_keys, sample_rows
    vocab, padded = 1000, 1024
    rng = np.random.default_rng(sorted(_SAMPLER_CALLS).index(rows))
    temps, top_ks, top_ps = (
        jnp.asarray(x, dt) for x, dt in zip(
            _SAMPLER_CALLS[rows](rng, np.arange(_ROWS)),
            (jnp.float32, jnp.int32, jnp.float32)))
    new = jax.jit(sample_rows, static_argnums=5)
    old = jax.jit(_straight_line_sample_rows, static_argnums=5)
    drawn = set()
    for _ in range(6):
        logits = jnp.asarray(rng.normal(0.0, 3.0, (_ROWS, padded)),
                             jnp.bfloat16)
        keys = row_keys(
            jnp.asarray(rng.integers(0, 1 << 30, _ROWS), jnp.int32),
            jnp.asarray(rng.integers(0, 2048, _ROWS), jnp.int32))
        got = np.asarray(new(logits, temps, top_ks, top_ps, keys, vocab))
        want = np.asarray(old(logits, temps, top_ks, top_ps, keys, vocab))
        assert got.dtype == want.dtype == np.int32
        np.testing.assert_array_equal(got, want)
        greedy = np.argmax(np.asarray(logits[:, :vocab], np.float32), -1)
        np.testing.assert_array_equal(got[np.asarray(temps) <= 0],
                                      greedy[np.asarray(temps) <= 0])
        drawn.update((got != greedy).nonzero()[0].tolist())
    # sampled rows really sample (not the arg-max every time), others never
    sampling = set(np.nonzero(np.asarray(temps) > 0)[0].tolist())
    assert drawn <= sampling and bool(drawn) == bool(sampling)


def _sorts(jaxpr, conds=0):
    """(how many ``cond`` branches deep) of every ``sort`` in a jaxpr,
    through whatever holds a jaxpr: a ``scan`` or a ``pjit`` body, a
    ``cond``'s branches."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield conds
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _sorts(
                        sub, conds + (eqn.primitive.name == "cond"))


def _program_jaxprs(engine, monkeypatch, call):
    """{program name: jaxpr} of the pool programs ``call`` runs, traced
    from the arguments the engine really hands each. (The program's own
    ``trace``: a second ``jit`` of its body would keep the engine alive in
    jax's caches past this module, and its tables with it.)"""
    seen = {}
    real = engine._pool_call

    def spy(fn, prep, *rest):
        args = prep()
        with engine.mesh:
            seen[fn.__name__] = fn.trace(*args).jaxpr.jaxpr
        return real(fn, lambda: args, *rest)

    monkeypatch.setattr(engine, "_pool_call", spy)
    call()
    return seen


def test_sample_rows_sorts_two_conds_deep():
    """The three levels of the sampler, in its jaxpr: no sort outside a
    ``cond``, and the sort two branches deep (some row samples AND some
    row truncates)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.speculative import row_keys, sample_rows
    z = jnp.zeros((4,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda lg, t, k, p, s: sample_rows(
        lg, t, k, p, row_keys(s, s), 96))(
        jnp.zeros((4, 96)), jnp.zeros((4,)), z, jnp.ones((4,)), z).jaxpr
    assert list(_sorts(jaxpr)) == [2]
    assert list(_sorts(jax.make_jaxpr(
        lambda lg, t, k, p, s: _straight_line_sample_rows(
            lg, t, k, p, row_keys(s, s), 96))(
        jnp.zeros((4, 96)), jnp.zeros((4,)), z, jnp.ones((4,)),
        z).jaxpr)) == [0]


@pytest.mark.parametrize("program", ["pf", "dec", "prop", "ver"])
def test_every_sampling_program_sorts_inside_a_cond_only(
        engine, monkeypatch, program):
    """The prefill, the decode step, the draft's proposal scan and the
    verify step each hold their sampler's sort inside ``cond`` branches
    and nowhere else: an all-greedy call runs no sort. In the verify step
    the sampler runs under a ``vmap`` over the block's positions; its
    predicates come from the un-batched ``temps`` / ``top_ks`` /
    ``top_ps``, so the ``cond`` survives the batching (a batched
    predicate would turn it into a ``select_n`` over both branches'
    results, and the sort would stand outside every ``cond``)."""
    k = 2
    prompt = _prompts((6,), seed=77)[0]
    draft = engine.init_draft(DraftConfig(mode="self", layers=1))

    def call():
        pool, _first, (toks, pos, temps, tk, tp, sd) = _seed_slot(
            engine, 2, 32, prompt, k)
        if program == "dec":
            engine.slot_decode_step(pool, toks, pos, temps, tk, tp, sd)
        elif program == "prop":
            dpool = engine.init_draft_pool(draft, 2, 32)
            engine.slot_draft_propose(draft, dpool, toks, pos, temps, tk,
                                      tp, sd, k)
        elif program == "ver":
            engine.slot_verify_step(pool, toks, np.zeros((2, k), np.int32),
                                    pos, temps, tk, tp, sd)

    depths = list(_sorts(_program_jaxprs(engine, monkeypatch, call)[program]))
    assert depths == [2], depths


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(top_k=5).validate()          # needs temperature
    with pytest.raises(ValueError):
        SamplingParams(temperature=1.0, top_p=0.0).validate()
    with pytest.raises(ValueError):
        SamplingParams(temperature=1.0, top_k=-1).validate()
    SamplingParams(temperature=1.0, top_k=5, top_p=0.9, seed=3).validate()


def test_sampled_failover_replay_bitwise_dedup(engine):
    """The PR 8 kill-mid-stream test, for SAMPLED requests: a failover
    survivor replays the identical seeded stream, the delivery adapter
    dedups by position, and the client sees every token exactly once —
    bitwise equal to an undisturbed single-replica run with the same
    seed."""
    prompts = _prompts((6, 8, 5, 7), seed=42)
    mk = lambda i: SamplingParams(max_new_tokens=8, temperature=0.9,  # noqa
                                  top_k=20, top_p=0.95, seed=100 + i)
    # reference: undisturbed single replica, same seeds
    ref_srv = ServingEngine(engine, {"num_slots": 4, "max_model_len": 64})
    ref_rids = [ref_srv.submit(p, mk(i)) for i, p in enumerate(prompts)]
    ref_srv.run_until_idle()
    refs = [ref_srv.result(r).tokens for r in ref_rids]

    router = build_fleet(engine, {
        "num_slots": 2, "max_model_len": 64,
        "fleet": {"enabled": True, "replicas": 2,
                  "heartbeat_timeout_s": 60.0}})
    streamed = {i: [] for i in range(len(prompts))}
    fids = [router.submit(p, mk(i),
                          on_token=lambda r, t, i=i: streamed[i].append(t))
            for i, p in enumerate(prompts)]
    for _ in range(3):
        router.step()
    victim = next(router.result(f).replica for f in fids
                  if router.result(f).replica is not None)
    router.kill(victim)
    router.run_until_idle()
    assert router.metrics.failovers == 1 and router.metrics.requeued >= 1
    for i, fid in enumerate(fids):
        fr = router.result(fid)
        assert fr.state == "finished", fr.failed_reason
        assert fr.tokens == refs[i]
        assert streamed[i] == refs[i]          # exactly once, no dup/gap
        assert (fr.trace.sampling or {}).get("seed") == 100 + i
    router.shutdown()


def test_handoff_frame_carries_sampling_law(engine):
    """KVHandoff to_bytes/from_bytes round-trips seed + top-k/top-p —
    and a disaggregated fleet serves a SAMPLED request bitwise equal to
    a unified replica with the same seed."""
    from deepspeed_tpu.serving import KVHandoff
    pool = engine.init_slot_pool(2, 32)
    prompt = _prompts((5,), seed=43)[0]
    pool, first = engine.slot_prefill(pool, 0, prompt)
    lane = engine.slot_extract_lane(pool, 0)
    h = KVHandoff(prompt=prompt, first_token=first, kv_len=5, lane=lane,
                  temperature=0.7, top_k=12, top_p=0.8, seed=99,
                  max_new_tokens=6)
    h2 = KVHandoff.from_bytes(h.to_bytes())
    assert (h2.temperature, h2.top_k, h2.top_p, h2.seed) == (0.7, 12, 0.8, 99)

    sp = SamplingParams(max_new_tokens=8, temperature=0.7, top_k=12,
                        top_p=0.8, seed=99)
    uni = ServingEngine(engine, {"num_slots": 2, "max_model_len": 64})
    rid = uni.submit(prompt, sp)
    uni.run_until_idle()
    ref = uni.result(rid).tokens

    router = build_fleet(engine, {
        "num_slots": 2, "max_model_len": 64,
        "fleet": {"enabled": True, "replicas": 2, "prefill_replicas": 1,
                  "decode_replicas": 1, "heartbeat_timeout_s": 60.0}})
    fid = router.submit(prompt, sp)
    router.run_until_idle()
    assert router.result(fid).state == "finished"
    assert router.result(fid).tokens == ref
    router.shutdown()


# -------------------------------------------------- self-spec + draft cfg

def test_self_speculative_full_depth_always_accepts(engine):
    """layers == n_layer makes the draft the target itself: acceptance
    is exactly 1.0 and every tick emits k+1 tokens — the degenerate
    upper bound that pins the accept-count arithmetic."""
    srv = ServingEngine(engine, _spec_cfg(k=2, layers=2, num_slots=2))
    prompt = _prompts((5,), seed=51)[0]
    rid = srv.submit(prompt, SamplingParams(max_new_tokens=9))
    srv.run_until_idle()
    ref = np.asarray(engine.generate(prompt[None], max_new_tokens=9))[0]
    np.testing.assert_array_equal(srv.result(rid).output_ids, ref)
    m = srv.metrics
    assert m.spec_acceptance_ema == pytest.approx(1.0)
    # 9 tokens: prefill emits 1, then 8/3-per-tick speculative ticks
    assert m.spec_ticks == 3 and m.spec_emitted == 8


def test_separate_draft_model_parity(engine):
    """mode='model' (separate random-init draft): terrible acceptance,
    identical stream — the draft never leaks into the output."""
    cfg = {"num_slots": 2, "max_model_len": 64,
           "speculative": {"enabled": True, "k": 2,
                           "draft": {"mode": "model", "n_layer": 1,
                                     "n_embd": 32, "n_head": 2}}}
    srv = ServingEngine(engine, cfg)
    assert srv.scheduler.draft.mode == "model"
    prompt = _prompts((6,), seed=52)[0]
    rid = srv.submit(prompt, SamplingParams(max_new_tokens=8))
    srv.run_until_idle()
    ref = np.asarray(engine.generate(prompt[None], max_new_tokens=8))[0]
    np.testing.assert_array_equal(srv.result(rid).output_ids, ref)


def test_draft_config_validation():
    with pytest.raises(Exception, match="self|model"):
        DraftConfig.from_dict({"mode": "eagle"})
    with pytest.raises(Exception, match="power of two"):
        SpeculativeConfig.from_dict({"k": 6})
    cfg = SpeculativeConfig.from_dict(
        {"enabled": True, "k": 4, "draft": {"mode": "self", "layers": 2}})
    assert cfg.draft.layers == 2


# ------------------------------------------------ telemetry + observability

def test_spec_gauges_dedicated_series_and_lifecycle(engine):
    """dstpu_spec_* is a first-class Prometheus series with the
    owner=/release lifecycle: live while the replica serves, gone after
    shutdown."""
    from deepspeed_tpu.telemetry import get_tracer
    from deepspeed_tpu.telemetry.export import prometheus_dump
    tr = get_tracer()
    tr.clear()
    tr.configure(enabled=True, buffer_size=4096)
    try:
        srv = ServingEngine(engine, _spec_cfg(k=2, layers=2, num_slots=2))
        rid = srv.submit(_prompts((5,), seed=61)[0],
                         SamplingParams(max_new_tokens=8))
        srv.run_until_idle()
        assert srv.result(rid).state is RequestState.FINISHED
        counters = tr.counters()
        assert "spec/acceptance_ema" in counters
        dump = prometheus_dump(tr)
        assert "dstpu_spec_acceptance_ema" in dump
        assert "dstpu_spec_tokens_per_tick" in dump
        # statusz section carries the acceptance numbers ds_tpu_top bars
        section = srv._statusz_section()
        assert "spec_acceptance_ema" in section
        assert section["speculative"].startswith("k=2")
        srv.shutdown()
        assert not any(t.startswith("spec/") for t in tr.counters())
    finally:
        tr.clear()
        tr.configure(enabled=False)


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_sampled_ticks_counter(engine, monkeypatch, speculative):
    """``serve/sampled_ticks`` of ``serve/decode_ticks``: how often a
    decode tick's sampler ran its sort-and-draw branch. Greedy requests
    leave it at 0 however many ticks run; one request at temperature 0.7
    makes exactly the steps that advance it count (its greedy neighbour's
    further ticks do not): on the speculative path the ticks it lives in,
    on the plain one, which keeps a step in flight, the three steps behind
    its prefill's token — it joins the step dispatched in the tick that
    admits it, which the next tick reads, so it lives one tick longer than
    it has steps. Both gauges go with the engine that owns them."""
    from deepspeed_tpu.telemetry import get_tracer
    tr = get_tracer()
    tr.clear()
    cfg = _spec_cfg(k=2, layers=2) if speculative else \
        {"num_slots": 4, "max_model_len": 64}
    srv = ServingEngine(engine, cfg)
    try:
        read = lambda tag: tr.counter_value(tag)            # noqa: E731
        prompts = _prompts((5, 7), seed=91)
        for p in prompts:
            srv.submit(p, SamplingParams(max_new_tokens=6))
        srv.run_until_idle()
        greedy_ticks = read("serve/decode_ticks")
        assert greedy_ticks >= 2 and read("serve/sampled_ticks") == 0
        assert greedy_ticks == srv.metrics.decode_ticks
        # a short sampled request beside a long greedy one
        long_rid = srv.submit(prompts[0], SamplingParams(max_new_tokens=24))
        hot_rid = srv.submit(prompts[1], SamplingParams(
            max_new_tokens=4, temperature=0.7, seed=3))
        lived = []              # per decode step: is the hot request bound?
        pool, decode = srv.scheduler.pool, srv.scheduler._decode
        monkeypatch.setattr(srv.scheduler, "_decode", lambda: (lived.append(
            any(r is not None and r.request_id == hot_rid
                for r in pool.requests)), decode())[1])
        srv.run_until_idle()
        assert srv.result(long_rid).state is RequestState.FINISHED
        hot_steps = sum(lived) if speculative else 4 - 1
        assert sum(lived) >= hot_steps >= 1
        assert read("serve/sampled_ticks") == hot_steps
        assert read("serve/decode_ticks") == greedy_ticks + len(lived)
        assert len(lived) > sum(lived)
    finally:
        srv.shutdown()
    assert read("serve/decode_ticks") is None
    assert read("serve/sampled_ticks") is None


def test_spec_verify_stage_sums_into_critical_path(engine):
    """The spec_verify stage exists in the critical path and the stage
    decomposition still sums to the trace e2e EXACTLY (mark intervals
    are consecutive by construction)."""
    srv = ServingEngine(engine, _spec_cfg(k=2, layers=1, num_slots=2))
    rid = srv.submit(_prompts((6,), seed=62)[0],
                     SamplingParams(max_new_tokens=8))
    srv.run_until_idle()
    req = srv.result(rid)
    ctx = req.trace
    path = ctx.critical_path()
    assert path.get("spec_verify", 0.0) > 0.0
    assert sum(path.values()) == pytest.approx(ctx.total_ms(), abs=1e-6)


def test_acceptance_drop_trigger_edge(engine, tmp_path):
    """A garbage separate-model draft drives acceptance ~0: the flight
    recorder fires exactly ONE acceptance_drop bundle (edge-triggered,
    post-warmup), not one per tick."""
    cfg = {"num_slots": 2, "max_model_len": 64,
           "speculative": {"enabled": True, "k": 4,
                           "acceptance_floor": 0.5, "warmup_ticks": 2,
                           "draft": {"mode": "model", "n_layer": 1,
                                     "n_embd": 32, "n_head": 2,
                                     "seed": 3}},
           "flight_recorder": {"enabled": True, "dir": str(tmp_path),
                               "debounce_s": 0.0}}
    srv = ServingEngine(engine, cfg)
    for p in _prompts((6, 6), seed=63):
        srv.submit(p, SamplingParams(max_new_tokens=16))
    srv.run_until_idle()
    assert srv.metrics.spec_acceptance_ema < 0.5
    bundles = [n for n in os.listdir(tmp_path)
               if n.startswith("bundle-") and "acceptance_drop" in n]
    assert len(bundles) == 1, sorted(os.listdir(tmp_path))
    with open(tmp_path / bundles[0]) as f:
        doc = json.load(f)
    assert doc["kind"] == "acceptance_drop"
    assert "acceptance" in doc["detail"]
    srv.shutdown()


# ------------------------------------------------------------- CLI smoke

def test_ds_tpu_serve_speculative_config_smoke():
    """ds_tpu_serve --config with the shipped speculative JSON: the CLI
    boots a speculative replica, serves real traffic, and reports the
    acceptance numbers in its summary."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_tpu_serve"),
         "--cpu", "--config",
         os.path.join(REPO, "examples", "configs", "serving_spec.json"),
         "--requests", "4", "--rate", "50", "--prompt-len", "8",
         "--max-new", "8"],
        capture_output=True, text=True, cwd=REPO, timeout=420)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    summary = json.loads(res.stdout[res.stdout.index("{"):])
    assert summary["completed"] == 4
    assert summary["speculative"]["ticks"] > 0
    assert 0.0 <= summary["speculative"]["acceptance_ema"] <= 1.0


@pytest.mark.slow
def test_speculative_benchmark_full_sweep():
    """The full --speculative benchmark (interleaved greedy-vs-spec
    blocks + parity + acceptance/speedup gates) — slow lane."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "serving.py"),
         "--speculative"],
        capture_output=True, text=True, cwd=REPO, timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    with open(os.path.join(REPO, "benchmarks", "serving_spec.json")) as f:
        report = json.load(f)
    assert report["speedup_tokens_per_s"] >= 2.0
    assert report["acceptance_ema"] >= 0.7
