"""Device time under the program's own names, the program's side: the one
vocabulary of ``named_scope`` words (``telemetry.hlo_cost.SCOPES``),
``scope_table`` over recorded HLO, the tracer's registry of compiled programs
(``note_program`` / ``scope_tables``) and what the engines do with it when
they close: nothing is lowered twice in a process no profiler traced, the
tables outlive an engine that was traced, and the registry pins no engine."""

import ast
import gc
import glob
import os
import re
import weakref

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import SamplingParams, ServingEngine
from deepspeed_tpu.telemetry import get_tracer
from deepspeed_tpu.telemetry import hlo_cost
from deepspeed_tpu.telemetry.hlo_cost import PASSES, SCOPES, scope_table

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ----------------------------------------------------- scope_table, recorded

#: cut from compiled programs (a v5e's and the CPU's), shapes shortened
HLO = '''
HloModule jit_step, is_scheduled=true

%fused_scores (p0: bf16[16,2048]) -> f32[16,2048] {
  %p0 = bf16[16,2048]{1,0} parameter(0)
  %convert.1 = f32[16,2048]{1,0} convert(%p0), metadata={op_name="jit(step)/layers/while/body/closed_call/attn/kv_read/convert_element_type"}
  %exp.1 = f32[16,2048]{1,0} exponential(%convert.1), metadata={op_name="jit(step)/layers/while/body/closed_call/attn/kv_read/exp"}
  ROOT %mul.1 = f32[16,2048]{1,0} multiply(%exp.1, %exp.1), metadata={op_name="jit(step)/layers/while/body/closed_call/mlp/mul"}
}

%fused_outer (q0: bf16[16,2048]) -> f32[16,2048] {
  %q0 = bf16[16,2048]{1,0} parameter(0)
  ROOT %fusion.9 = f32[16,2048]{1,0} fusion(%q0), kind=kLoop, calls=%fused_scores
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="jit(step)/layers/while/body/closed_call/mlp/reduce_sum"}
  %b = f32[] parameter(1)
  ROOT %add.7 = f32[] add(%a, %b), metadata={op_name="jit(step)/layers/while/body/closed_call/mlp/reduce_sum"}
}

%body (arg: (s32[], bf16[16,2048])) -> (s32[], bf16[16,2048]) {
  %arg = (s32[], bf16[16,2048]{1,0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%arg), index=0
  %get-tuple-element.2 = bf16[16,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %copy.4 = s32[] copy(%get-tuple-element.1)
  %fusion.3 = bf16[16,6144]{1,0:T(8,128)(2,1)} fusion(%get-tuple-element.2), kind=kOutput, calls=%fused_qkv, metadata={op_name="jit(step)/layers/while/body/closed_call/attn/qkv/dot_general;attn/qkv/add"}
  %fusion.4 = f32[16,2048]{1,0} fusion(%fusion.3), kind=kLoop, calls=%fused_outer
  %decode_attend.6 = bf16[16,2048]{1,0} custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layers/while/body/closed_call/attn/kv_read/decode_attend/pallas_call"}
  %fusion.5 = bf16[2048,8192]{1,0} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(step)/layers/while/body/squeeze"}
  ROOT %tuple.2 = (s32[], bf16[16,2048]{1,0}) tuple(%copy.4, %decode_attend.6)
}

%fused_qkv (r0: bf16[16,2048]) -> bf16[16,6144] {
  %r0 = bf16[16,2048]{1,0} parameter(0)
  ROOT %dot.1 = bf16[16,6144]{1,0} dot(%r0, %r0)
}

%fused_slice (s0: bf16[16,2048]) -> bf16[2048,8192] {
  %s0 = bf16[16,2048]{1,0} parameter(0)
  ROOT %broadcast.3 = bf16[2048,8192]{1,0} broadcast(%s0), dimensions={}
}

ENTRY %main (x: bf16[16,2048], w: f32[8]) -> (f32[16,50272], s32[16,50272]) {
  %x = bf16[16,2048]{1,0} parameter(0)
  %w = f32[8]{0} parameter(1)
  %constant.1 = s32[] constant(0)
  %tuple.1 = (s32[], bf16[16,2048]{1,0}) tuple(%constant.1, %x)
  %while.5 = (s32[], bf16[16,2048]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(step)/layers/while"}
  %get-tuple-element.9 = bf16[16,2048]{1,0} get-tuple-element(%while.5), index=1
  %fusion.84 = f32[16,50272]{1,0} fusion(%get-tuple-element.9), kind=kOutput, calls=%fused_head, metadata={op_name="jit(step)/head/dot_general"}
  %pad.1 = f32[16,50304]{1,0} pad(%fusion.84, %constant.1), padding=0_0x0_32
  %reduce-window.1 = f32[16,50304]{1,0} reduce-window(%pad.1, %constant.1), window={size=1x128}, to_apply=%region_add
  %iota.3 = s32[16,50272]{1,0} iota(), iota_dimension=1
  %sort.5 = (f32[16,50272]{1,0:T(8,128)(2,1)}, s32[16,50272]{1,0}) sort(%reduce-window.1, %iota.3), dimensions={1}, to_apply=%region_add, metadata={op_name="jit(step)/sample/jit(sort)/sort"}
  %fusion.20 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/jvp(layers)/while/body/closed_call/mlp/dot_general"}
  %fusion.21 = f32[8]{0} fusion(%fusion.20), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general"}
  %fusion.22 = f32[8]{0} fusion(%fusion.21), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mlp/transpose"}
  %fusion.23 = f32[8]{0} fusion(%fusion.22), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/transpose(jvp())/mul"}
  %fusion.24 = f32[8]{0} fusion(%fusion.23), kind=kLoop, calls=%fused_fwd, metadata={op_name="jit(step)/optimizer/cond/branch_1_fun/add"}
  %copy.30 = f32[8]{0} copy(%w), metadata={op_name="jit(step)/num_heads/embedding"}
  ROOT %tuple.9 = (f32[16,50272]{1,0}, s32[16,50272]{1,0}) tuple(%fusion.84, %iota.3)
}
'''


@pytest.fixture(scope="module")
def table():
    return scope_table(HLO)


@pytest.mark.parametrize("name, scope", [
    ("fusion.3", "layers/attn/qkv"),         # own name; a merged list's first
    ("decode_attend.6", "layers/attn/kv_read"),        # the Pallas call
    ("while.5", "layers"),
    ("fusion.5", "layers"),                  # the scan's slicing: no deeper
    ("fusion.84", "head"),
    ("sort.5", "sample"),                    # a tuple-shaped result
    ("fusion.20", "forward/layers/mlp"),
    ("fusion.21", "remat/layers/mlp"),
    ("fusion.22", "backward/layers/mlp"),
    ("fusion.24", "optimizer"),
    ("copy.30", None),                       # num_heads, embedding: no word
])
def test_scope_is_the_pass_and_the_words_of_the_own_name(table, name, scope):
    assert table[name] == scope


def test_a_fusion_without_metadata_takes_its_bodys_commonest_scope(table):
    """``fusion.4`` calls a body whose only instruction is a nested fusion;
    that one's body holds two ``kv_read`` instructions and one ``mlp``."""
    assert table["fusion.4"] == "layers/attn/kv_read"
    assert table["fusion.9"] == "layers/attn/kv_read"   # nested: listed too


@pytest.mark.parametrize("name, scope", [
    ("reduce-window.1", "?sample"),          # feeds the sampler's sort
    ("iota.3", "?sample"),
    ("pad.1", "?sample"),                    # feeds ``reduce-window.1``
    ("fusion.23", "?backward/layers/mlp"),   # a pass and no word: its operand
    ("copy.4", None),                        # packed for the loop's next turn
])
def test_what_the_compiler_put_in_is_inferred_and_marked(table, name, scope):
    """The pieces a cumulative sum is expanded into carry no metadata: they
    read what the instructions that use them share, else what their
    operands do, behind a ``?``: a name the program did not set. A backward
    instruction is not renamed by the optimizer's that uses it. What only a
    loop's result tuple uses stays unnamed."""
    assert table[name] == scope


def test_the_programs_own_names_carry_no_mark(table):
    own = {n: s for n, s in table.items() if s and not s.startswith("?")}
    assert {"fusion.3", "fusion.4", "fusion.9", "sort.5", "while.5"} <= \
        set(own)
    assert sum(s.startswith("?") for s in table.values() if s) == 4


def test_words_are_token_bounded_and_free_instructions_left_out(table):
    for free in ("x", "constant.1", "tuple.1", "get-tuple-element.9", "arg"):
        assert free not in table
    # a fusion's body and a reducer have no events of their own
    assert "exp.1" not in table and "add.7" not in table
    assert "dot.1" not in table


def test_one_vocabulary():
    from deepspeed_tpu.profiling import flops_profiler
    assert flops_profiler.PHASES is SCOPES
    assert len(SCOPES) == len(set(SCOPES)) < 32
    assert not set(SCOPES) & set(PASSES)


def _scope_literals(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                getattr(node.func, "attr", None) == "named_scope":
            arg = node.args[0]
            for leaf in ([arg.body, arg.orelse]
                         if isinstance(arg, ast.IfExp) else [arg]):
                assert isinstance(leaf, ast.Constant), (path, ast.dump(arg))
                yield leaf.value


def test_every_named_scope_literal_is_a_word_of_the_vocabulary():
    pkg = os.path.join(ROOT, "deepspeed_tpu")
    files = glob.glob(os.path.join(pkg, "models", "*.py")) + \
        glob.glob(os.path.join(pkg, "moe", "*.py")) + \
        [os.path.join(pkg, "inference", "engine.py"),
         os.path.join(pkg, "runtime", "engine.py")]
    found = {w for path in files for w in _scope_literals(path)}
    assert found <= set(SCOPES), found - set(SCOPES)
    # and every word is set somewhere
    assert set(SCOPES) <= found, set(SCOPES) - found


# ------------------------------------------------------ the serving engines

def _opt():
    return GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                n_layer=2, n_head=4,
                                pad_vocab_to_multiple=64))


def _olmoe():
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    return OLMoEModel(OLMoEConfig(vocab_size=512, n_positions=64, n_embd=128,
                                  n_layer=2, n_head=2, mlp_hidden=64,
                                  num_experts=8, top_k=2, dtype="float32"))


def _lfm2():
    from deepspeed_tpu.models.lfm2 import ATTN, CONV, LFM2MoEConfig, \
        LFM2MoEModel
    types = (CONV,) + (CONV, CONV, ATTN) * 2
    return LFM2MoEModel(LFM2MoEConfig(
        vocab_size=512, n_positions=64, n_embd=128, n_layer=len(types),
        n_head=4, n_kv_head=2, mlp_hidden=256, layer_types=types,
        num_dense_layers=1, moe_intermediate_size=64, num_experts=8, top_k=2,
        dtype="float32"))


def _kexaone():
    from deepspeed_tpu.models.kexaone import FULL, SLIDING, KExaoneConfig, \
        KExaoneModel
    types = (SLIDING,) + (SLIDING, SLIDING, FULL, SLIDING)
    return KExaoneModel(KExaoneConfig(
        vocab_size=512, n_positions=64, n_embd=128, n_layer=len(types),
        n_head=4, n_kv_head=2, head_dim=32, mlp_hidden=256,
        layer_types=types, sliding_window=8, moe_intermediate_size=64,
        num_experts=16, experts_held=(4, 8), top_k=4, dtype="float32"))


FAMILIES = {
    "opt": (_opt, {"embed", "layers", "attn", "qkv", "out_proj", "kv_write",
                   "kv_read", "mlp", "head", "sample"}),
    "olmoe": (_olmoe, {"embed", "layers", "attn", "qkv", "out_proj",
                       "kv_write", "kv_read", "moe", "router", "moe_experts",
                       "head", "sample"}),
    "lfm2": (_lfm2, {"embed", "layers", "attn", "conv", "qkv", "out_proj",
                     "kv_write", "kv_read", "dense_mlp", "moe", "router",
                     "moe_experts", "head", "sample"}),
    "kexaone": (_kexaone, {"embed", "layers", "attn", "qkv", "out_proj",
                           "attend_window", "attend_full", "kv_write",
                           "kv_read", "dense_mlp", "moe", "router",
                           "moe_experts", "shared_expert", "head", "sample"}),
}


#: one entry for every jaxpr lowered in this process (a compile, a fetch from
#: a cache): the count the benchmark holds its measured windows to
LOWERED = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **_kw: LOWERED.append(event) if event ==
    "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)


def _builds(monkeypatch):
    """The module names ``Tracer.scope_tables`` has built a table for."""
    built = []
    monkeypatch.setattr(hlo_cost, "scope_table", lambda text: (
        built.append(re.search(r"HloModule (\w+)", text).group(1)),
        scope_table(text))[1])
    return built


def _drive(engine, prefills=(5, 12, 20), steps=20, slots=4, max_len=64):
    pool = engine.init_slot_pool(slots, max_len)
    for slot, n in enumerate(prefills):
        pool, _ = engine.slot_prefill(pool, slot, np.arange(1, n + 1))
    pos = np.array(list(prefills) + [0] * (slots - len(prefills)), np.int32)
    zeros = np.zeros(slots, np.int32)
    for _ in range(steps):
        pool, _ = engine.slot_decode_step(pool, zeros, pos,
                                          np.zeros(slots, np.float32))
        pos = np.minimum(pos + 1, max_len - 1)
    return pool


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_program_of_a_family_gets_a_table(family, monkeypatch):
    """``jit_dec`` and each bucket's ``jit_pf`` get a table in which every
    word the family sets appears and few instructions read ``None``; until
    ``scope_tables()`` is asked nothing is lowered a second time."""
    build, words = FAMILIES[family]
    # an earlier module's engine that is only waiting for the collector is
    # forgotten; a live one's tables are built now, out of `built`'s sight
    gc.collect()
    get_tracer().scope_tables()
    engine = deepspeed_tpu.init_inference(build(),
                                          config={"dtype": "float32"})
    built = _builds(monkeypatch)
    _drive(engine)                  # every program's first call
    warm = len(LOWERED)
    _drive(engine)
    # 20 decode steps, 3 prefills: nothing lowered again, no table built
    assert len(LOWERED) == warm and built == []
    tables = engine.scope_tables()
    assert sorted(built) == ["jit_dec", "jit_init"] + ["jit_pf"] * 3
    assert len(LOWERED) <= warm + 5     # the lowerings jax kept are reused
    assert list(tables["jit_dec"]) == [("slot_decode", 4, 64)]
    assert sorted(tables["jit_pf"]) == [("slot_prefill", b, 64)
                                        for b in (8, 16, 32)]
    engine.scope_tables()
    assert len(built) == 5          # built once, kept
    for table in list(tables["jit_pf"].values()) + \
            list(tables["jit_dec"].values()):
        seen = {w for scope in table.values() if scope
                and not scope.startswith("?") for w in scope.split("/")}
        assert words <= seen, words - seen
        unnamed = [k for k, v in table.items() if v is None]
        assert len(unnamed) < 0.1 * len(table), unnamed


def _serve(engine, requests=3):
    srv = ServingEngine(engine, {"num_slots": 4, "max_model_len": 64})
    for n in range(requests):
        srv.submit(np.arange(1, 6 + 4 * n), SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    return srv


def test_shutdown_without_a_profiler_trace_builds_nothing(monkeypatch):
    engine = deepspeed_tpu.init_inference(_opt(), config={"dtype": "float32"})
    built = _builds(monkeypatch)
    srv = _serve(engine)
    before = len(LOWERED)
    srv.shutdown()
    assert built == [] and len(LOWERED) == before
    assert [k for k, entry in get_tracer()._programs.items()
            if entry[0]() in engine._slot_fns.values()
            and entry[4] is not None] == []


def test_a_table_that_cannot_be_built_does_not_stop_a_shutdown(
        tmp_path, monkeypatch):
    """``keep_tables`` is the last act of ``shutdown()`` and logs what goes
    wrong: the gauges are retracted and the sinks closed all the same."""
    engine = deepspeed_tpu.init_inference(_opt(), config={"dtype": "float32"})
    with jax.profiler.trace(str(tmp_path)):
        srv = _serve(engine)
    tracer = get_tracer()
    asked = []

    def fails(module, key, entry):
        asked.append(module)
        raise RuntimeError("no backend")
    monkeypatch.setattr(tracer, "_build_table", fails)
    srv.shutdown()
    assert len(asked) == 1              # it tried, once, and gave up
    assert id(srv) not in tracer._gc_owners
    monkeypatch.undo()
    assert "jit_dec" in engine.scope_tables()    # by hand, still there


def test_a_table_without_layers_is_logged_as_another_programs(monkeypatch):
    """An executable fetched from a cache whose key leaves the metadata out
    carries the names of the program that was compiled first: every
    program of an engine scans its layers under ``layers``, so a table
    without the word is reported, not read as 0.0."""
    from deepspeed_tpu.utils.logging import logger
    get_tracer().scope_tables()     # whatever earlier engines left unbuilt
    engine = deepspeed_tpu.init_inference(_opt(), config={"dtype": "float32"})
    _drive(engine, prefills=(5,), steps=1)
    warned = []
    monkeypatch.setattr(logger, "warning", warned.append)
    monkeypatch.setattr(hlo_cost, "scope_table", lambda text: {
        "fusion.1": "attn/kv_read", "fusion.2": "mlp", "copy.3": None})
    engine.scope_tables()
    assert sorted(w.split()[2] for w in warned if "holds no 'layers'" in w) \
        == ["jit_dec", "jit_pf"]


def test_tables_outlive_a_traced_engine_and_pin_nothing(tmp_path):
    """Under ``jax.profiler.trace`` a tick's phases see the profiler, so
    ``shutdown()`` leaves the tables with the tracer; the engine, dropped as
    the benchmark's jobs drop it, is collected with the registry filled."""
    engine = deepspeed_tpu.init_inference(_opt(), config={"dtype": "float32"})
    with jax.profiler.trace(str(tmp_path)):
        srv = _serve(engine)
    srv.shutdown()
    gone = weakref.ref(engine)
    keys = [fn.key for fn in engine._slot_fns.values()]
    engine.params = None
    engine._slot_fns.clear()
    del srv, engine
    gc.collect()
    assert gone() is None
    tables = get_tracer().scope_tables()
    assert ("slot_decode", 4, 64) in keys
    assert "sample" in set(tables["jit_dec"][("slot_decode", 4, 64)].values())
    assert any(key in tables["jit_pf"] for key in keys)


# ---------------------------------------------------------- the train engine

def _train_engine(**over):
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=64, n_embd=64,
                                 n_layer=2, n_head=4, remat=True,
                                 loss_chunking="always",
                                 pad_vocab_to_multiple=64))
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=dict({
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
        "zero_optimization": {"stage": 1}, "bf16": {"enabled": True}}, **over))
    return engine


def _steps(engine, n=2):
    rng = np.random.RandomState(0)
    for _ in range(n):
        engine.train_batch(batch={
            "input_ids": rng.randint(0, 256, size=(2, 8, 32))})


def test_train_step_table_holds_the_passes_and_the_optimizer(monkeypatch):
    engine = _train_engine()
    built = _builds(monkeypatch)
    _steps(engine)
    before = len(LOWERED)
    _steps(engine)
    engine.close()                  # no profiler trace: nothing is built
    assert built == [] and len(LOWERED) == before
    table = engine.scope_tables()["jit_train_step"][("train", None)]
    assert built == ["jit_train_step"]
    heads = {scope.split("/")[0] for scope in table.values() if scope}
    assert {"forward", "remat", "backward", "optimizer"} <= heads
    words = {w for scope in table.values() if scope for w in scope.split("/")}
    assert {"layers", "attn", "qkv", "mlp", "head", "loss"} <= words
    # what no scope names is the engine's own: the masters' cast, the
    # accumulators' zeros and sums, the micro-batch loop's counter
    unnamed = [k for k, v in table.items() if v is None]
    assert len(unnamed) < 0.15 * len(table), unnamed


def test_close_after_a_profiler_trace_keeps_the_step_table(tmp_path):
    engine = _train_engine()
    with jax.profiler.trace(str(tmp_path)):
        _steps(engine)
    engine.close()
    gone = weakref.ref(engine)
    del engine
    gc.collect()
    assert gone() is None
    table = get_tracer().scope_tables()["jit_train_step"][("train", None)]
    assert "optimizer" in set(table.values())


# ------------------------------------------- a scope is metadata and no more

def test_decode_program_is_the_same_text_with_and_without_the_new_scopes(
        monkeypatch):
    """``jit_dec``'s optimized HLO with every ``metadata={...}`` stripped is
    the same text when the words this PR added are set as when they are
    not: no instruction, no fusion decision, no module name moved."""
    new = {"layers", "qkv", "out_proj", "router", "loss", "optimizer"}

    def text(hide):
        scope = jax.named_scope
        if hide:
            import contextlib
            monkeypatch.setattr(
                jax, "named_scope", lambda name: contextlib.nullcontext()
                if name in new else scope(name))
        engine = deepspeed_tpu.init_inference(_opt(),
                                              config={"dtype": "float32"})
        _drive(engine, prefills=(5,), steps=1)
        fn = engine._slot_fns[("slot_decode", 4, 64)]
        _, avals, mesh, _, _ = get_tracer()._programs[("jit_dec", fn.key)]
        with mesh:
            out = fn.lower(*avals).compile().as_text()
        monkeypatch.undo()
        return re.sub(r", metadata=\{[^}]*\}", "", out)

    with_, without = text(False), text(True)
    assert "HloModule jit_dec" in with_
    strip = lambda t: re.sub(r"\n(FileNames|FunctionNames|FileLocations|"
                             r"StackFrames)\n(.+\n)*", "\n", t)
    assert strip(with_) == strip(without)


def test_compile_cache_key_holds_the_metadata(monkeypatch, tmp_path):
    """A scope is metadata, and jax leaves metadata out of the persistent
    cache's key unless told: a program that differs from a cached one in its
    scopes alone would be handed that one's executable, and its table would
    name the other program's code. ``enable_compile_cache`` tells it."""
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was)
