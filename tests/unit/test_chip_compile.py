"""Ahead-of-time compiles of the Pallas attention kernels, and of the slot
prefill over a pool of the serving cell's lane shape, for a described
TPU v5e (no chip attached): Mosaic and the TPU compiler accept what the
interpret-mode tests cannot see — tiling, scoped VMEM, HBM fit, whether a
donated pool stays aliased. Nothing runs; a compile that passes is not a
chip run.

The topology is described inside a module-scoped fixture (never at import):
only the xdist worker that is handed this file loads the TPU library.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.pallas.block_sparse_attention import \
    sparse_attention_pallas
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.flash_attention_packed import \
    packed_flash_attention
from deepspeed_tpu.ops.sparse_attention_ops import FixedSparsityConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip; the persistent compile cache is off
    while this module runs (an AOT entry cannot be read back without a
    chip, and the next compile would warn)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fed_back(vi):
    """The two arguments behind a decode step's six registers, as shapes
    placed like ``vi`` [slots]: ``prev`` (the output of the step before:
    its tokens, and where the model routes two stats behind them) and
    ``from_host``."""
    like = lambda shape, dtype: jax.ShapeDtypeStruct(    # noqa: E731
        shape, dtype, sharding=vi.sharding)
    return like((vi.shape[0] + 2,), jnp.int32), like(vi.shape, jnp.bool_)


def _compile(fn, one_chip, shape, dtype, grad=True):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    step = fn
    if grad:
        step = jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2))
    compiled = jax.jit(step).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b,t,h,d", [
    (8, 1024, 12, 64), (8, 1024, 16, 64), (4, 1024, 32, 64),
    (4, 1024, 16, 128),
    (1, 1024, 16, 64),      # eval_batch's single row
    (2, 256, 32, 64),       # the OPT cells' engine.forward check
    (1, 2048, 16, 64),      # the longest a grid step holds in 16 MiB
    (1, 4096, 16, 64),      # supported()'s cap: the raised VMEM limit
], ids=lambda v: str(v))
def test_packed_fwd_bwd(one_chip, b, t, h, d):
    """The GPT-2 125M / 350M / 1.3B train-step attention shapes and the
    two shapes the cells' checks run beside them, then the longest
    sequences ``supported()`` admits (no cell; the parent's kernel did not
    compile at 4096): Mosaic accepts every tile the resolver picks for
    them, forward and backward."""
    _compile(lambda q, k, v: packed_flash_attention(q, k, v, h),
             one_chip, (b, t, h * d), jnp.bfloat16)


def test_packed_window_fits(one_chip):
    """A window's two edges give the loop three bodies; at 512 tiles they
    overran the scoped VMEM at T = 2048 by 24 KB, so the resolver gives a
    window no tile over 256."""
    _compile(lambda q, k, v: packed_flash_attention(q, k, v, 16, window=512),
             one_chip, (1, 2048, 16 * 64), jnp.bfloat16)


def _kernel_instructions(compiled):
    return [line for line in compiled.as_text().splitlines()
            if " custom-call(" in line and "tpu_custom_call" in line]


@pytest.mark.parametrize("policy,kernels", [
    ("dots_with_no_batch_dims_saveable", 2), ("nothing_saveable", 3)])
def test_layer_remat_runs_the_forward_kernel_once(one_chip, policy, kernels):
    """qkv -> packed attention -> out_proj at GPT-2 350M's training shape,
    under ``jax.checkpoint`` as the layer scan's body is: the default policy
    keeps the kernel's named output and lse, so the gradient's program holds
    the forward and the fused backward kernel; a policy that keeps nothing
    holds the forward twice."""
    from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import \
        get_policy
    like = lambda *shape: jax.ShapeDtypeStruct(     # noqa: E731
        shape, jnp.bfloat16, sharding=one_chip)

    def layer(x, w_qkv, w_out):
        q, k, v = jnp.split(x @ w_qkv, 3, axis=-1)
        out = packed_flash_attention(q, k, v, 16) @ w_out
        return jnp.sum(out.astype(jnp.float32))

    step = jax.value_and_grad(          # the value keeps the forward alive
        jax.checkpoint(layer, policy=get_policy(policy)), argnums=(0, 1, 2))
    compiled = jax.jit(step).lower(like(8, 1024, 1024), like(1024, 3072),
                                   like(1024, 1024)).compile()
    assert len(_kernel_instructions(compiled)) == kernels


def test_named_residuals_cost_the_serving_prefill_nothing(one_chip):
    """Outside ``jax.checkpoint`` the names are the identity: bucket 2,048's
    whole-prefill attention compiles to the instructions of the bare kernel
    call, one kernel and no copy beside it."""
    from deepspeed_tpu.ops.pallas import flash_attention_packed as fap
    from deepspeed_tpu.telemetry.hlo_cost import _INSTR_RE
    x = jax.ShapeDtypeStruct((1, 2048, 32 * 64), jnp.bfloat16,
                             sharding=one_chip)
    scale, tiles = fap._resolve(x, 32, None, None, None)
    named = _compile(lambda q, k, v: packed_flash_attention(q, k, v, 32),
                     one_chip, x.shape, x.dtype, grad=False)
    bare = _compile(lambda q, k, v: fap._fwd(q, k, v, 32, True, scale, tiles,
                                             False, None)[0],
                    one_chip, x.shape, x.dtype, grad=False)
    opcodes = lambda c: [m.group(3) for m in map(      # noqa: E731
        _INSTR_RE.match, c.as_text().splitlines()) if m]
    assert len(_kernel_instructions(named)) == 1
    assert "copy" not in opcodes(named)
    assert opcodes(named) == opcodes(bare)


@pytest.mark.parametrize("shape,dtype", [
    ((8, 16, 1024, 64), jnp.bfloat16),
    ((1, 12, 4096, 64), jnp.bfloat16),
    # exactly 1 MiB of K/V per head: the resident/streamed boundary, where
    # the resident fused backward ran out of scoped VMEM
    ((1, 12, 8192, 64), jnp.bfloat16),
    ((1, 12, 32768, 64), jnp.bfloat16),
    # the widest head supported() admits, where the streamed kernels' head
    # fold bottoms out at 1
    ((1, 8, 8192, 256), jnp.float32),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v.__name__)
def test_flash_fwd_bwd(one_chip, shape, dtype):
    _compile(lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                             False, None),
             one_chip, shape, dtype)


@pytest.mark.parametrize("t", [1024, 8192])
def test_sliding_window_fwd(one_chip, t):
    """Resident (T=1024) and streamed (T=8192) windowed forward."""
    _compile(lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                             False, 256),
             one_chip, (1, 12, t, 64), jnp.bfloat16, grad=False)


@pytest.mark.parametrize("t", [1024, 4096])
def test_block_sparse_fwd_bwd(one_chip, t):
    """The layout ops/sparse_attention_ops.py builds for a causal Fixed
    config (local window of 4 blocks + 1 global block, block 64)."""
    h, block = 12, 64
    layout = FixedSparsityConfig(
        num_heads=h, block=block, num_local_blocks=4, num_global_blocks=1,
        attention="unidirectional").make_layout(t)
    assert np.asarray(layout).any()
    _compile(lambda q, k, v: sparse_attention_pallas(q, k, v, layout, block),
             one_chip, (2, h, t, 64), jnp.bfloat16)


@pytest.mark.parametrize("quantized", (False, True), ids=("fp", "q8"))
def test_slot_prefill_writes_its_lane_in_place(one_chip, quantized):
    """The chip's compiler keeps the alias the engine asks for
    (``donate_argnums``): over a 28-slot pool of ``opt-1.3b.serve-chat``'s
    lane shape (1024 columns x 32 heads x 64, stored as 16 rows of 128 a
    column, bf16 or int8 + scales; two layers) the whole pool is aliased to the output and the program's
    temporaries are a lane's size, not a pool's. XLA drops an alias
    silently where the layouts of input and output differ; that would show
    here as ``alias_size_in_bytes`` 0 and, on the chip, as a second pool
    copied per prefill (ISSUE 28)."""
    import deepspeed_tpu
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.kv_quant import pool_nbytes, quantize_pool
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel

    slots, max_len, bucket = 28, 1024, 128
    model = OPTModel(OPTConfig(vocab_size=512, n_positions=max_len,
                               n_embd=2048, n_layer=2, n_head=32,
                               dtype="bfloat16"))
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16", "max_tokens": max_len})
    # one call on a one-slot pool makes the engine build its program
    tiny = engine.init_slot_pool(1, max_len)
    if quantized:
        tiny = quantize_pool(tiny)
    tiny, _ = engine.slot_prefill(tiny, 0, np.zeros(1, np.int32))
    fn = engine._slot_fns[("slot_prefill", 1, max_len)
                          + (("q8",) if quantized else ())]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    i32, f32 = on_chip((), jnp.int32), on_chip((), jnp.float32)
    # pf(params, ids, pool, slot_idx, last_idx, temp, top_k, top_p, seed)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        params, on_chip((1, bucket), jnp.int32), pool, i32, i32, f32, i32,
        f32, i32).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert mem.temp_size_in_bytes < pool_nbytes(pool) // slots * 2
    first = len(jax.tree.leaves(params)) + 1
    assert donated_params_from_hlo(compiled.as_text()) == set(
        range(first, first + len(jax.tree.leaves(pool))))


def test_slot_decode_writes_its_rows_in_place(one_chip):
    """The decode step of ``opt-1.3b.serve-chat``'s pool (28 slots x 1024
    columns x 32 heads of 64; two layers), compiled for the chip: the
    donated pool is aliased to the output and carried through the layer
    loop in the layout it arrives in, so the program's temporaries stay
    under one lane's bytes (7.4 MB of 16.8; the parent's program, which
    rewrote every lane, kept 1.41 GB beside this 0.47 GB pool). A pool
    whose rows are narrower than 128 lanes fails here: the compiler then
    carries it padded to twice its size and copies all of it in and out of
    the loop (0.94 GB of temporaries: ``_kv_row_shape``)."""
    import deepspeed_tpu
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel

    slots, max_len = 28, 1024
    model = OPTModel(OPTConfig(vocab_size=512, n_positions=max_len,
                               n_embd=2048, n_layer=2, n_head=32,
                               dtype="bfloat16"))
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16", "max_tokens": max_len})
    tiny = engine.init_slot_pool(1, max_len)
    assert tiny["k"].shape == (2, 1, max_len, 16, 128)
    zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
    tiny, _ = engine.slot_decode_step(tiny, zi, zi, zf)
    fn = engine._slot_fns[("slot_decode", 1, max_len)]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    # dec(params, pool, toks, positions, temps, top_ks, top_ps, seeds,
    #     prev, from_host)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert mem.temp_size_in_bytes < pool_nbytes(pool) // slots
    first = len(jax.tree.leaves(params))
    assert donated_params_from_hlo(compiled.as_text()) == {first, first + 1}


@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_routed_decode_reads_expert_leaves_in_place(one_chip, program):
    """The slot decode step and a slot-prefill bucket (128) of an OLMoE of
    ``olmoe-1b-7b.serve-chat-2k``'s widths (hidden 2048, 64 experts of
    1024, 8 a token; two layers, 24 slots x 2048, small vocabulary),
    compiled for the chip: no instruction produces a layer's expert leaf
    (bf16 [64, 2048, 1024] or [64, 1024, 2048], 268 MB), the temporaries
    stay far under one, and the grouped matmuls are still the
    ``ragged-dot`` custom calls the cell's ``moe_kernels.pattern`` finds.

    How the layer loop may reach its expert leaves, 8 layers of three
    ``lax.ragged_dot`` over 192 rows at these sizes, compiled the same way
    (ISSUE 34):

    | the loop                                         | temporaries | copies |
    | ``lax.scan`` over the stacked [8, 64, ...] leaves | 268.6 MB    | ``dynamic-slice_bitcast_fusion`` x 3, each a whole leaf of the layer: 56% of the cell's busy time on the chip (ledger, PR 33) |
    | unrolled, static slices ``w[l]`` of the stack    | 5.9 GB      | every slice, up front |
    | the leaves whole, [L * E] groups, sizes at l * E | 0.59 MB     | none: the kernel's weight window is fetched by group id, and empty groups are never visited |

    The third is ``MOELayer.take_whole`` with ``apply_grouped(...,
    layer=l)``."""
    import re
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    from deepspeed_tpu.parallel import initialize_mesh

    slots, max_len, bucket = 24, 2048, 128
    model = OLMoEModel(OLMoEConfig(
        vocab_size=512, n_positions=max_len, n_embd=2048, n_layer=2,
        n_head=16, mlp_hidden=1024, num_experts=64, top_k=8,
        dtype="bfloat16"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # zeros on ONE host device: the program is built by one call on a
    # one-slot pool; 1.6 GB of normal draws, a copy for each of the rig's
    # eight devices, would buy nothing
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))
    leaf = engine.params["blocks"]["moe"]["experts"]["w_gate"]
    assert leaf.shape == (2, 64, 2048, 1024) and leaf.dtype == jnp.bfloat16
    tiny = engine.init_slot_pool(1, max_len)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = on_chip((), jnp.int32), on_chip((), jnp.float32)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    if program == "decode":
        zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
        engine.slot_decode_step(tiny, zi, zi, zf)
        fn = engine._slot_fns[("slot_decode", 1, max_len)]
        args = (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))
    else:
        engine.slot_prefill(tiny, 0, np.zeros(1, np.int32))
        fn = engine._slot_fns[("slot_prefill", 1, max_len)]
        args = (params, on_chip((1, bucket), jnp.int32), pool, i32, i32,
                f32, i32, f32, i32)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        *args).compile()
    text = compiled.as_text()
    # a prefill's own temporaries: its 2048-column mini cache (16.8 MB) and
    # 128 rows of attention scores; read 1.9 and 52.7 MB
    limit = (32 if program == "decode" else 64) * 2 ** 20
    assert compiled.memory_analysis().temp_size_in_bytes < limit
    copied = re.findall(
        r"^\s*(?:ROOT )?%?\S+ = bf16\[64,(?:2048,1024|1024,2048)\]\S* (\S+)\(",
        text, re.M)
    assert copied == [], copied
    assert len(re.findall(r"ragged-dot\S* = .*custom-call\(", text)) >= 3


@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_hybrid_stack_reads_every_leaf_in_place(one_chip, program):
    """The slot decode step and a slot-prefill bucket (128) of an LFM2-MoE
    of ``lfm2-24b-a2b.serve-agent-4k``'s widths (hidden 2048, 32 heads of
    64 over 8 KV heads, dense FFN 11776, 64 experts of 1536, 4 a token,
    conv of 3 taps; a dense conv layer, an attention layer and a routed
    conv layer; 48 slots x 4096, small vocabulary), compiled for the chip.
    The layers are of several kinds in per-kind stacks and are not one
    scanned tree (``models/lfm2.py:_scan_layers``), and the constraint is
    the compiled program: no instruction produces a layer's expert leaf
    (bf16 [64, 2048, 1536] or [64, 1536, 2048], 403 MB), every pool leaf
    (K and V of the ONE attention layer, a token's eight KV heads in one
    stored row of 512; the conv state of the two conv layers) is aliased
    to the output, the decode step's temporaries stay under 32 MB, and the
    grouped matmuls are the ``ragged-dot`` custom calls the cell's
    ``moe_kernels.pattern`` finds. (The cell's own nine layers, 40 slots
    and whole vocabulary read 16.8 MB for ``jit_dec``, compiled the same
    way; with K and V stored ``(4, 128)`` a row, as ``_kv_row_shape``
    gives, the scanned attention layers' slabs are re-laid in HBM, 201 MB
    of temporaries each: PERF.md, PR 36.)"""
    import re
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.models.lfm2 import (ATTN, CONV, LFM2MoEConfig,
                                           LFM2MoEModel)
    from deepspeed_tpu.parallel import initialize_mesh

    slots, max_len, bucket = 48, 4096, 128
    model = LFM2MoEModel(LFM2MoEConfig(
        vocab_size=512, n_positions=max_len, n_layer=3,
        layer_types=(CONV, ATTN, CONV), num_dense_layers=1,
        dtype="bfloat16"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # zeros on ONE host device (2.4 GB of experts): the program is built by
    # one call on a one-slot pool
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))
    leaf = engine.params["blocks"]["moe"]["moe"]["experts"]["w_gate"]
    assert leaf.shape == (2, 64, 2048, 1536) and leaf.dtype == jnp.bfloat16
    tiny = engine.init_slot_pool(1, max_len)
    assert {k: v.shape for k, v in tiny.items()} == {
        "k": (1, 1, max_len, 1, 512), "v": (1, 1, max_len, 1, 512),
        "conv": (2, 1, 2, 2048)}

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = on_chip((), jnp.int32), on_chip((), jnp.float32)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    if program == "decode":
        zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
        engine.slot_decode_step(tiny, zi, zi, zf)
        fn = engine._slot_fns[("slot_decode", 1, max_len)]
        args = (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))
        first = len(jax.tree.leaves(params))
    else:
        engine.slot_prefill(tiny, 0, np.zeros(1, np.int32))
        fn = engine._slot_fns[("slot_prefill", 1, max_len)]
        args = (params, on_chip((1, bucket), jnp.int32), pool, i32, i32,
                f32, i32, f32, i32)
        first = len(jax.tree.leaves(params)) + 1
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        *args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # a prefill's own temporaries: its 4096-column mini cache (8.4 MB) and
    # 128 rows of attention scores over it
    limit = (32 if program == "decode" else 96) * 2 ** 20
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert donated_params_from_hlo(text) == set(range(first, first + 3))
    copied = re.findall(
        r"^\s*(?:ROOT )?%?\S+ = bf16\[64,(?:2048,1536|1536,2048)\]\S* (\S+)\(",
        text, re.M)
    assert copied == [], copied
    assert len(re.findall(r"ragged-dot\S* = .*custom-call\(", text)) >= 3


@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_window_rings_are_written_and_read_in_place(one_chip, program):
    """The slot decode step and a slot-prefill bucket (2048: eight blocks of
    256 queries against their bands) of a K-EXAONE of
    ``k-exaone-236b-a23b.serve-longdoc-16k``'s widths (hidden 6144, 64 heads
    of 128 over 8 KV heads, 8 of 128 experts of 2048 held, 8 a token, a
    shared expert; a dense window layer, a routed full layer and a routed
    window layer; a narrow dense FFN and a small vocabulary), compiled for
    the chip at the cell's pool, 48 slots x 16,384. The pool has a cache
    shape per kind of layer: the full layer's lanes (1.6 GB a leaf) and the
    window layers' rings of 128 columns (25 MB a leaf). The constraint is
    the compiled program: every pool leaf is aliased to the output, no
    instruction copies a lane slab or a ring leaf, no instruction produces
    a layer's expert leaf, and the decode step's temporaries are its
    scores over the one full layer (48 x 64 x 16,384 in bfloat16, 101 MB)
    and little else. (The cell's own five layers read 107 MB for
    ``jit_dec`` and 2.1 GB for its bucket-16,384 prefill, compiled the same
    way: PERF.md, PR 38.)"""
    import re
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.models.kexaone import (FULL, SLIDING, KExaoneConfig,
                                              KExaoneModel)
    from deepspeed_tpu.parallel import initialize_mesh

    slots, max_len, bucket = 48, 16384, 2048
    model = KExaoneModel(KExaoneConfig(
        vocab_size=512, n_positions=max_len, n_layer=3, mlp_hidden=2048,
        layer_types=(SLIDING, FULL, SLIDING), experts_held=(0, 8),
        dtype="bfloat16"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))
    leaf = engine.params["blocks"]["moe"]["moe"]["experts"]["w_gate"]
    assert leaf.shape == (2, 8, 6144, 2048) and leaf.dtype == jnp.bfloat16
    tiny = engine.init_slot_pool(1, max_len)
    assert {k: v.shape for k, v in tiny.items()} == {
        "k": (1, 1, max_len, 1, 1024), "v": (1, 1, max_len, 1, 1024),
        "wk": (2, 1, 128, 1, 1024), "wv": (2, 1, 128, 1, 1024)}

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = on_chip((), jnp.int32), on_chip((), jnp.float32)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    if program == "decode":
        zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
        engine.slot_decode_step(tiny, zi, zi, zf)
        fn = engine._slot_fns[("slot_decode", 1, max_len)]
        args = (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))
        first = len(jax.tree.leaves(params))
        limit = 160 * 2 ** 20
    else:
        engine.slot_prefill(tiny, 0, np.zeros(1, np.int32))
        fn = engine._slot_fns[("slot_prefill", 1, max_len)]
        args = (params, on_chip((1, bucket), jnp.int32), pool, i32, i32,
                f32, i32, f32, i32)
        first = len(jax.tree.leaves(params)) + 1
        # scores [64, 2048, 2048] in float32 and as probabilities, the mini
        # cache of one lane (67 MB) and the rows of 2048 tokens
        limit = 2 * 2 ** 30
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        *args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert donated_params_from_hlo(text) == set(range(first, first + 4))
    copied = re.findall(
        r"^\s*(?:ROOT )?%?\S+ = bf16\[(?:48,16384,1024|1,48,16384,1,1024|"
        r"2,48,128,1024|2,48,128,1,1024|48,128,1024|8,6144,2048|8,2048,6144)"
        r"\]\S* (copy|transpose)\(", text, re.M)
    assert copied == [], copied
    assert len(re.findall(r"ragged-dot\S* = .*custom-call\(", text)) >= 3


@pytest.mark.parametrize("program", ("decode", "prefill"))
def test_latent_pool_is_written_and_attended_in_place(one_chip, program):
    """The slot decode step (absorbed: one token a slot against the latent
    slab where it lies) and a slot-prefill bucket (2048: the lane expanded
    to per-head keys and values, queries in blocks) of a Xing4.0 of
    ``xing4.0-29b-a4b.serve-docqa``'s widths (hidden 3584, 32 heads over a
    latent of 512 + 64, 8 of 64 experts of 1024 held, 4 a token, a shared
    expert, four residual streams; the dense layer and two routed ones; a
    small vocabulary), compiled for the chip at ISSUE 46's first pool, 48
    slots x 8,192 (the cell runs its second, 32 x 4,224: half the lane, the
    same programs). The pool's only leaf is the latent one, a token's 576 values
    stored as 640 (whole vector rows: 1,280 B a token a layer). The
    constraint is the compiled program: the leaf is aliased to the output,
    no instruction copies or transposes it or a layer's slab of it (with
    576-wide rows the decode step copied the whole pool in and out of its
    layer loop: 6.3 GB of temporaries at the cell's twelve layers), no
    layer's expert leaf is produced, and the Sinkhorn steps are unrolled
    into the maps' fusion: no loop of 20 trips is left. (Twelve layers,
    compiled the same way: 0.62 GB of temporaries for ``jit_dec`` and 1.39
    GB for the bucket-8,192 prefill at 48 x 8,192; the cell's 32 x 4,224:
    its file's ``memory``, and PERF.md, PR 46.)"""
    import re
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.models.xing import XingConfig, XingModel
    from deepspeed_tpu.parallel import initialize_mesh

    slots, max_len, bucket = 48, 8192, 2048
    model = XingModel(XingConfig(
        vocab_size=512, n_positions=max_len, n_layer=3,
        first_k_dense_replace=1, experts_held=(0, 8), dtype="bfloat16"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))
    leaf = engine.params["blocks"]["moe"]["moe"]["experts"]["w_gate"]
    assert leaf.shape == (2, 8, 3584, 1024) and leaf.dtype == jnp.bfloat16
    assert engine.params["blocks"]["attn"]["hc_attn"]["phi"].shape == \
        (3, 4 * 3584, 24)
    tiny = engine.init_slot_pool(1, max_len)
    assert {k: v.shape for k, v in tiny.items()} == {
        "latent": (3, 1, max_len, 1, 640)}

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = on_chip((), jnp.int32), on_chip((), jnp.float32)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    assert pool_nbytes(pool) == 3 * slots * max_len * 1280
    if program == "decode":
        zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
        engine.slot_decode_step(tiny, zi, zi, zf)
        fn = engine._slot_fns[("slot_decode", 1, max_len)]
        args = (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))
        first = len(jax.tree.leaves(params))
        # a layer's scores and probabilities [48, 32, 8192] in float32
        limit = 768 * 2 ** 20
    else:
        engine.slot_prefill(tiny, 0, np.zeros(1, np.int32))
        fn = engine._slot_fns[("slot_prefill", 1, max_len)]
        args = (params, on_chip((1, bucket), jnp.int32), pool, i32, i32,
                f32, i32, f32, i32)
        first = len(jax.tree.leaves(params)) + 1
        # a block of 1,024 queries' scores [32, 1024, 8192] in float32
        # (``_attend_scores_bytes``: 1 GB) and as probabilities; the lane
        # of 8,192 expanded to 32 heads' keys (192) and values (128): 168
        # MB; four streams of rows
        limit = 3 * 2 ** 30
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        *args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert donated_params_from_hlo(text) == {first}
    copied = re.findall(
        r"^\s*(?:ROOT )?%?\S+ = bf16\[(?:3,48,8192,1,640|48,8192,1,640|"
        r"48,8192,640|1,48,8192,1,640|8,3584,1024|8,1024,3584)"
        r"\]\S* (copy|transpose)\(", text, re.M)
    assert copied == [], copied
    assert len(re.findall(r"ragged-dot\S* = .*custom-call\(", text)) >= 3
    if program == "decode":
        # the layer scan and no other loop: the Sinkhorn steps are unrolled
        # (a prefill also loops over its query blocks, the heads of each
        # and the feed-forward's chunks)
        assert len(re.findall(r" while\(", text)) == 1


def _opt(max_len):
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel
    return OPTModel(OPTConfig(vocab_size=512, n_positions=max_len,
                              n_embd=2048, n_layer=2, n_head=32,
                              dtype="bfloat16"))


def _olmoe(max_len):
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    return OLMoEModel(OLMoEConfig(
        vocab_size=512, n_positions=max_len, n_embd=2048, n_layer=2,
        n_head=16, mlp_hidden=1024, num_experts=64, top_k=8,
        dtype="bfloat16"))


def _decode_program(model, max_len, tp=1):
    """The engine's slot decode program of ``model`` (zero weights, built by
    one call on a one-slot pool on the CPU, where it takes the XLA attend)
    and the shapes of its parameters and of that pool."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import initialize_mesh
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len,
             "tensor_parallel": {"tp_size": tp}}),
        mesh_manager=initialize_mesh(tp=tp, devices=jax.devices()[:tp]))
    tiny = engine.init_slot_pool(1, max_len)
    zi, zf = np.zeros(1, np.int32), np.zeros(1, np.float32)
    tiny, _ = engine.slot_decode_step(tiny, zi, zi, zf)
    return engine, engine._slot_fns[("slot_decode", 1, max_len)], tiny


def _decode_args(engine, tiny, slots, one_chip):
    """The shapes of ``_decode_program``'s arguments at a pool of ``slots``
    lanes, placed on the described chip."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    return (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))


@pytest.mark.parametrize("cell,build,slots,max_len", [
    ("opt-1.3b.serve-chat", _opt, 28, 1024),
    ("opt-1.3b.serve-longprompt", _opt, 24, 2048),
    ("olmoe-1b-7b.serve-chat-2k", _olmoe, 24, 2048),
], ids=lambda v: v if isinstance(v, str) else "")
def test_decode_step_takes_the_length_aware_kernel_in_place(
        one_chip, monkeypatch, cell, build, slots, max_len):
    """``jit_dec`` at the pools of the serving cells whose stored rows the
    decode-attention kernel takes (two layers of the cell's widths, small
    vocabulary), traced as on one TPU and compiled for the chip: a layer
    body holds ONE Mosaic call (``decode_attend``; a routed model's grouped
    matmuls are ``ragged-dot`` calls beside it), whose K and V operands are
    the pool leaves as ``kv_write`` left them, so every leaf stays aliased
    to the output and no slab is staged, re-laid or copied: the temporaries
    stay under HALF a lane's bytes (0.7 MB at serve-chat's pool where the
    XLA attend keeps 6.8 MB; a layout the custom call did not accept would
    show as a copy of a layer's slab, 112 to 400 MB, or of the pool)."""
    import re
    from deepspeed_tpu.analysis.hlo_audit_rules import donated_params_from_hlo
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.parallel import topology
    engine, fn, tiny = _decode_program(build(max_len), max_len)
    assert tiny["k"].shape[3:] == (16, 128)
    monkeypatch.setattr(topology, "on_tpu", lambda: True)

    params, pool, *rest = _decode_args(engine, tiny, slots, one_chip)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        params, pool, *rest).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    calls = re.findall(
        r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"", text, re.M)
    ours = [c for c in calls if c.startswith("decode_attend")]
    assert len(ours) == 1 and \
        [c for c in calls if "ragged-dot" not in c] == ours, calls
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    lane = pool["k"].size * 2 // (pool["k"].shape[0] * slots)
    assert mem.temp_size_in_bytes < lane // 2, mem.temp_size_in_bytes
    first = len(jax.tree.leaves(params))
    assert donated_params_from_hlo(text) == set(
        range(first, first + len(pool)))
    copied = re.findall(
        rf"^\s*(?:ROOT )?%?\S+ = bf16\[(?:\d,)?{slots},{max_len},"
        r"(?:16,128|2048)\]\S* (copy|transpose)\(", text, re.M)
    assert copied == [], copied


def test_decode_step_over_a_mesh_compiles_on_the_xla_attend(
        topo, one_chip, monkeypatch):
    """The same program with the pool's heads sharded four ways over
    ``model`` (serve-chat's pool, tensor parallel 4), compiled for the
    described 2x2 mesh: GSPMD cannot partition a Mosaic kernel, so under a
    mesh of more than one device the cached forward keeps the XLA attend; no
    ``tpu_custom_call`` is in the program, and it compiles."""
    from jax.sharding import Mesh, NamedSharding
    from deepspeed_tpu.parallel import topology
    slots, max_len = 28, 1024
    engine, fn, tiny = _decode_program(_opt(max_len), max_len, tp=4)
    monkeypatch.setattr(topology, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(engine.mesh.devices.shape),
                engine.mesh.axis_names)

    def moved(sh):
        return None if sh is None else NamedSharding(mesh, sh.spec)

    def like(x, sh, lead=None):
        shape = x.shape if lead is None else (x.shape[0], lead) + x.shape[2:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=moved(sh))

    pool_sh = engine._pool_shardings(slots, max_len)
    assert pool_sh["k"].spec[3] == "model"
    params = jax.tree.map(like, engine.params, engine.param_shardings)
    pool = jax.tree.map(lambda x, sh: like(x, sh, slots), tiny, pool_sh)
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    vi = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep)
    vf = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=rep)
    with mesh:
        compiled = jax.jit(
            fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums,
            out_shardings=(jax.tree.map(moved, pool_sh), None)).lower(
            params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text         # the row-parallel matmuls' sum


@pytest.fixture(scope="module")
def longprompt_programs():
    """``opt-1.3b.serve-longprompt``'s widths and vocabulary at two layers:
    the engine with its decode program and bucket 2,048's prefill, each
    called once on a one-slot pool on the CPU, and the optimized HLO of
    those compiled for the chip so far (``_longprompt_text``)."""
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel
    max_len, bucket = 2048, 2048
    model = OPTModel(OPTConfig(vocab_size=50272, n_positions=max_len,
                               n_embd=2048, n_layer=2, n_head=32,
                               dtype="bfloat16"))
    engine, dec, tiny = _decode_program(model, max_len)
    tiny, _ = engine.slot_prefill(tiny, 0, np.zeros(bucket - 3, np.int32))
    return engine, tiny, {
        "jit_dec": dec,
        "jit_pf": engine._slot_fns[("slot_prefill", bucket, max_len)]}, {}


def _longprompt_text(one_chip, monkeypatch, programs, program):
    """The optimized HLO of ``program`` (``jit_dec``, or bucket 2,048's
    ``jit_pf``) at ``opt-1.3b.serve-longprompt``'s pool (24 slots x 2,048),
    traced as on one TPU and compiled for the chip; once a module."""
    from deepspeed_tpu.parallel import topology
    slots, bucket = 24, 2048
    engine, tiny, fns, texts = programs
    fn = fns[program]
    if program not in texts:
        monkeypatch.setattr(topology, "on_tpu", lambda: True)

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype),
                              engine.params)
        pool = jax.tree.map(
            lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype),
            tiny)
        vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
        si, sf = on_chip((), jnp.int32), on_chip((), jnp.float32)
        if program == "jit_dec":
            args = (params, pool, vi, vi, vf, vi, vf, vi, *_fed_back(vi))
        else:
            args = (params, on_chip((1, bucket), jnp.int32), pool, si, si,
                    sf, si, sf, si)
        assert fn.__name__ == program[4:]
        compiled = jax.jit(
            fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
            *args).compile()
        texts[program] = compiled.as_text()
        texts[program, "temp"] = compiled.memory_analysis().temp_size_in_bytes
    return texts[program]


@pytest.mark.parametrize("program", ["jit_dec", "jit_pf"])
def test_scope_table_names_every_large_instruction(
        one_chip, monkeypatch, longprompt_programs, program):
    """``jit_dec`` and bucket 2,048's ``jit_pf`` at
    ``opt-1.3b.serve-longprompt``'s pool (24 slots x 2,048, the cell's
    vocabulary, two layers), compiled for the chip: in the scope table of
    the optimized HLO (``telemetry.hlo_cost.scope_table``) no instruction
    with 1 MB of output or more reads ``None``, the ``[bucket, vocabulary]``
    matmul reads ``head``, the prefill's packed flash kernel and the decode
    kernel ``kv_read``, and the sampler's sort ``sample``. (Two layers: the
    compiler unrolls the scan, and the names hold all the same.)"""
    import re
    from deepspeed_tpu.telemetry.hlo_cost import DTYPE_BYTES, scope_table
    text = _longprompt_text(one_chip, monkeypatch, longprompt_programs,
                            program)
    table = scope_table(text)

    def out_bytes(name):
        m = re.search(rf"^\s*(?:ROOT )?%?{re.escape(name)} = (\w+)\[([\d,]*)\]",
                      text, re.M)
        if m is None:                   # a tuple-shaped result: not one array
            return 0
        return DTYPE_BYTES.get(m.group(1), 4) * int(np.prod(
            [int(d) for d in m.group(2).split(",") if d] or [1]))

    # (what nothing uses has no neighbour to be named by: at this depth the
    # compiler prefetches the position table a second time and drops it)
    large = {n: s for n, s in table.items() if out_bytes(n) >= 1 << 20
             and re.search(rf"\(.*%{re.escape(n)}[,)]", text)}
    assert len(large) > 8 and \
        [n for n, s in large.items() if s is None] == []
    # the program's own names alone (an ``op_name``, the called
    # computation's) leave some of them unnamed: prefetched slices and
    # copies, the lane's zero fill. Those read a scope inferred from what
    # uses them, and say so
    assert [n for n, s in large.items() if s.startswith("?")]
    unnamed = [n for n, s in table.items() if s is None]
    assert len(unnamed) < 0.1 * len(table), unnamed

    def shaped(pattern):
        return {table[n] for n in re.findall(
            rf"^\s*(?:ROOT )?%?(\S+) = {pattern}", text, re.M) if n in table}

    assert shaped(r"\(f32\[\d+,50272\].* sort\(") == {"sample"}
    if program == "jit_pf":
        assert shaped(r"bf16\[2048,50304\]\S* fusion\(") == {"head"}
        assert shaped(r"\(bf16\[1,2048,2048\]\S*, f32\[16,16,8,128\]\S*\) "
                      r"custom-call\(") == {"layers/attn/kv_read"}
    else:
        assert shaped(r"bf16\[24,32,128\]\S* custom-call\(") == \
            {"layers/attn/kv_read"}


def test_whole_prefill_attends_in_the_packed_kernel(
        one_chip, monkeypatch, longprompt_programs):
    """Bucket 2,048's ``jit_pf`` of ``opt-1.3b.serve-longprompt`` (24 slots
    x 2,048, two layers of the cell's widths), traced as on one TPU and
    compiled for the chip: the attention of the bucket's tokens is ONE
    Mosaic call, the training forward's packed flash kernel over q, k, v
    ``[1, 2048, 32 * 64]`` as the qkv matmul gave them (no transpose or
    copy feeds it, none takes its output), filed under
    ``layers/attn/kv_read``; no instruction holds a head's scores of
    2,048 x 2,048 in any type, and the program's temporaries are under the
    512 MB that the float32 scores ``[32, 2048, 2048]`` of the lane attend
    alone take (241 MB against 1,117 MB for the same program on
    ``_kv_attend``, compiled the same way: PERF.md, PR 58)."""
    import re
    from deepspeed_tpu.telemetry.hlo_cost import scope_table
    programs = longprompt_programs
    text = _longprompt_text(one_chip, monkeypatch, programs, "jit_pf")
    table = scope_table(text)
    calls = re.findall(
        r"^\s*(?:ROOT )?%?(\S+) = (.*?) custom-call\((.*?)\), "
        r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    assert len(calls) == 1, calls
    name, out, operands = calls[0]
    assert table[name] == "layers/attn/kv_read"
    assert re.match(r"\(bf16\[1,2048,2048\]\{2,1,0\S*, f32\[16,16,8,128\]",
                    out), out
    # q, k, v come as the projections left them and the output goes as it is
    fed = [o.strip().lstrip("%") for o in operands.split(",")]
    assert len(fed) == 3
    made = {n: kind for n, kind in re.findall(
        r"^\s*(?:ROOT )?%?(\S+) = \S+ (\S+?)\(", text, re.M)}
    assert [made.get(n) for n in fed if made.get(n) in ("copy", "transpose")
            ] == [], [(n, made.get(n)) for n in fed]
    # (``[1, 2048, 2048]`` is the bucket's tokens by the model's width,
    # ``[2, 2048, 2048]`` the two layers' output projections)
    scores = [m for m in re.findall(
        r"^\s*(?:ROOT )?%?\S+ = \w+\[((?:\d+,)+)2048,2048\]", text, re.M)
        if np.prod([int(d) for d in m.split(",") if d]) > 2]
    assert scores == [], scores
    temp = programs[3]["jit_pf", "temp"]
    assert temp < 512 * 2 ** 20, temp


def _whole_prefill_kernels(model, t, max_len, one_chip, **kw):
    """The names of the Mosaic kernels in a whole prefill of ``t`` tokens
    into an empty lane of ``max_len`` (``apply_with_cache`` from column 0,
    as ``slot_prefill`` calls it), lowered for the described chip at the
    model's own widths: abstract weights, nothing is built or compiled."""
    import re

    def on(x):
        floating = jnp.issubdtype(x.dtype, jnp.floating)
        return jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if floating else x.dtype,
            sharding=one_chip)

    params = jax.tree.map(on, jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0)))
    lane = jax.tree.map(on, jax.eval_shape(
        lambda: model.init_kv_cache(1, max_len, dtype=jnp.bfloat16)))
    ids = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda p, i, c: model.apply_with_cache(
        p, i, c, 0, **kw)).lower(params, ids, lane).as_text()
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


@pytest.mark.parametrize("family", ("opt", "olmoe", "lfm2", "k-exaone",
                                    "xing", "sdar"))
def test_which_family_prefills_in_the_packed_kernel(
        one_chip, monkeypatch, family):
    """The whole prefill of each served family at its cell's widths, lane
    and largest bucket, traced as on one TPU: OPT-1.3B's and OLMoE's hold
    the packed flash kernel (``_fwd_kernel``); LFM2's and K-EXAONE's
    (grouped KV heads), Xing's (a latent leaf) and SDAR's (blocks that see
    ahead, grouped heads) hold no packed-attention call, only the experts'
    rows kernel where they route."""
    from deepspeed_tpu.models.kexaone import KExaoneConfig, KExaoneModel
    from deepspeed_tpu.models.lfm2 import LFM2MoEConfig, LFM2MoEModel
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    from deepspeed_tpu.models.opt import OPTConfig, OPTModel
    from deepspeed_tpu.models.sdar import SDARConfig, SDARModel
    from deepspeed_tpu.models.xing import XingConfig, XingModel
    from deepspeed_tpu.parallel import topology
    monkeypatch.setattr(topology, "on_tpu", lambda: True)
    bf16 = dict(dtype="bfloat16")
    # (the model, the bucket, the lane, what a recurrent state is told)
    model, t, max_len, more = {
        "opt": (OPTModel(OPTConfig(n_positions=2048, n_embd=2048, n_layer=24,
                                   n_head=32, **bf16)), 2048, 2048, {}),
        "olmoe": (OLMoEModel(OLMoEConfig(**bf16)), 2048, 2048, {}),
        "lfm2": (LFM2MoEModel(LFM2MoEConfig(**bf16)), 4096, 4096,
                 {"lengths": jnp.asarray([3000], jnp.int32)}),
        "k-exaone": (KExaoneModel(KExaoneConfig(**bf16)), 2048, 16384, {}),
        "xing": (XingModel(XingConfig(**bf16)), 4096, 4224, {}),
        "sdar": (SDARModel(SDARConfig(**bf16)), 1024, 4096, {}),
    }[family]
    kernels = _whole_prefill_kernels(model, t, max_len, one_chip, **more)
    if family in ("opt", "olmoe"):
        assert "_fwd_kernel" in kernels, kernels
    else:
        assert kernels <= {"ragged-dot-rows"}, kernels


@pytest.mark.parametrize("program", ["jit_dec", "jit_pf"])
def test_sampler_sorts_inside_a_conditional_only(
        one_chip, monkeypatch, longprompt_programs, program):
    """The same two programs, compiled for the chip: the compiler keeps
    the sampler's two ``cond``s as ``conditional`` instructions (it turns
    neither into a select that would run both sides), every ``sort`` of
    the program lies in a computation that only a conditional's branch
    reaches, so a call whose rows are all greedy sorts nothing, and the
    scope table still names the sort, the draw and the conditionals
    ``sample``."""
    import re
    from deepspeed_tpu.telemetry.hlo_cost import (_parse_computations,
                                                  scope_table)
    text = _longprompt_text(one_chip, monkeypatch, longprompt_programs,
                            program)
    comps = {name.lstrip("%"): block
             for name, block in _parse_computations(text).items()}
    refs = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
    groups = re.compile(r"(?:branch_computations|called_computations)="
                        r"\{([^}]*)\}")

    def callees(line):
        return refs.findall(line) + [
            n.strip().lstrip("%") for g in groups.findall(line)
            for n in g.split(",")]

    conds = [line for block in comps.values() for line in block
             if " conditional(" in line]
    assert len(conds) == 2, conds       # some row samples; some truncates
    under, todo = set(), [c for line in conds for c in callees(line)]
    while todo:
        name = todo.pop()
        if name not in under:
            under.add(name)
            todo += [c for line in comps[name] for c in callees(line)]
    sorting = {name for name, block in comps.items()
               if any(re.search(r"\ssort\(", line) for line in block)}
    assert sorting and sorting <= under, sorting - under
    # reached from a branch ONLY: nothing outside the branches calls them
    outside = {c for name, block in comps.items() if name not in under
               for line in block if " conditional(" not in line
               for c in callees(line)}
    assert not (sorting & outside)
    table = scope_table(text)
    named = {n: s for n, s in table.items()
             if re.match(r"(sort|conditional)[.\d]*$", n)}
    assert len(named) >= 3 and \
        {s.lstrip("?") for s in named.values()} == {"sample"}, named


def _block_pass_program(one_chip, slots=48, max_len=4096, b=4, fix=2):
    """The engine's pass over blocks of an SDAR of ``sdar-30b-a3b.serve-
    reason-4k``'s widths (one layer, a small vocabulary, zero weights;
    built by one call on a one-slot pool on the CPU), the shapes of its
    arguments at the cell's pool on the described chip, and that pool's."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models.sdar import SDARConfig, SDARModel
    from deepspeed_tpu.parallel import initialize_mesh

    model = SDARModel(SDARConfig(vocab_size=512, n_positions=max_len,
                                 n_layer=1, mask_token_id=500,
                                 dtype="bfloat16"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    model.init = lambda rng: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "bfloat16", "max_tokens": max_len}),
        mesh_manager=initialize_mesh(dp=1, devices=jax.devices()[:1]))
    tiny = engine.init_slot_pool(1, max_len)
    assert tiny["k"].shape == (1, 1, max_len, 1, 512)
    z = np.zeros((1, b), np.int32)
    tiny, _ = engine.slot_block_dispatch(
        tiny, z, z > 0, np.zeros(1, np.int32), np.zeros(1, np.float32),
        fix=fix)
    fn = engine._slot_fns[("slot_block", 1, max_len, fix)]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda x: on_chip(x.shape, x.dtype), engine.params)
    pool = jax.tree.map(
        lambda x: on_chip((x.shape[0], slots) + x.shape[2:], x.dtype), tiny)
    vi, vf = on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32)
    # blk(params, pool, ids, flags, positions, temps, top_ks, top_ps,
    #     seeds, prev, from_host)
    return fn, (params, pool, on_chip((slots, b), jnp.int32),
                on_chip((slots, b), jnp.bool_), vi, vf, vi, vf, vi,
                on_chip((slots * 2 * b + 2,), jnp.int32),
                on_chip((slots,), jnp.bool_)), pool


def test_block_pass_reads_the_slab_where_it_lies(one_chip):
    """The pass over blocks of ``sdar-30b-a3b.serve-reason-4k``'s pool (48
    slots x 4,096 columns, a token's four KV heads of 128 in ONE stored row
    of 512; the cell's widths, one layer, a small vocabulary), compiled for
    the chip: the donated pool is aliased to the output, no instruction
    copies a layer's slab (bf16 [1, 48, 4096, 512], 201 MB: with rows of
    ``(4, 128)``, or with a block's four queries seen as a prefill's, the
    compiler copies K's and V's each pass, ``SDARModel.init_kv_cache``),
    the grouped matmuls are the ``ragged-dot`` custom calls, and the
    unmasking is named in the scope table."""
    import re
    from deepspeed_tpu.inference.kv_quant import pool_nbytes
    from deepspeed_tpu.telemetry.hlo_cost import scope_table

    fn, args, pool = _block_pass_program(one_chip)
    compiled = jax.jit(
        fn.__wrapped__, donate_argnums=fn._jit_info.donate_argnums).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_nbytes(pool)
    assert mem.temp_size_in_bytes < 256 * 2 ** 20
    text = compiled.as_text()
    copied = re.findall(
        r"^\s*(?:ROOT )?%?\S+ = bf16\[1,48,4096,512\]\S* (copy|transpose)\(",
        text, re.M)
    assert copied == [], copied
    assert len(re.findall(r"ragged-dot\S* = .*custom-call\(", text)) >= 3
    assert "unmask" in {s.lstrip("?").split("/")[-1]
                        for s in scope_table(text).values() if s}


def _rows_kernels(text):
    """(bodies, call sites) of the experts' rows kernel in a lowered
    program's text: the Mosaic calls that carry its name, and the calls
    of the one ``jax.jit`` that wraps it."""
    import re
    return (len(re.findall(r'kernel_name = "ragged-dot-rows"', text)),
            len(re.findall(r"call @gated_rows", text)))


def test_rows_kernel_is_lowered_once_a_shape(one_chip):
    """What a warm start pays for the experts' kernel: Pallas lowers a
    ``pallas_call`` to Mosaic while the program is LOWERED, before the
    persistent cache is asked, so every start pays it whatever the cache
    holds (PERF.md section 6, PR 52). Two layer loops that call
    ``gated_rows`` on leaves of one shape hold ONE kernel body behind two
    call sites; a third loop over leaves of another depth adds one."""
    from jax import lax
    from deepspeed_tpu.ops.pallas.grouped_matmul import choose, gated_rows
    rows, k, f, e = 192, 2048, 1024, 64
    tile = choose(rows, k, f, jnp.bfloat16, "tpu")

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def leaves(layers):
        return [on_chip((layers * e, k, f)), on_chip((layers * e, k, f)),
                on_chip((layers * e, f, k))]

    def program(x, sizes, *stacks):
        for at in range(0, len(stacks), 3):
            def body(x, layer, ws=stacks[at:at + 3]):
                return x + gated_rows(x, *ws, sizes, layer * e,
                                      tile=tile), None
            x, _ = lax.scan(body, x, jnp.arange(stacks[at].shape[0] // e))
        return x

    args = (on_chip((rows, k)), on_chip((e,), jnp.int32))
    two = jax.jit(program).lower(*args, *leaves(2), *leaves(2))
    assert _rows_kernels(two.as_text()) == (1, 2)
    three = jax.jit(program).lower(*args, *leaves(2), *leaves(2), *leaves(3))
    assert _rows_kernels(three.as_text()) == (2, 3)
    assert "ragged-dot-rows" in two.compile().as_text()


@pytest.mark.parametrize("family", ("olmoe", "sdar"))
def test_routed_step_holds_one_rows_kernel_a_layer_body(
        one_chip, monkeypatch, family):
    """The decode step of an OLMoE of ``olmoe-1b-7b.serve-chat-2k``'s
    widths and the pass over blocks of an SDAR of ``sdar-30b-a3b.serve-
    reason-4k``'s, traced as on one TPU: the layer loop's body calls the
    rows kernel ONCE for its three products (one body, one call site; the
    parent's three ``ragged_dot`` are gone), so a routed program lowers
    one Mosaic kernel a distinct layer loop at every start."""
    from deepspeed_tpu.parallel import topology
    if family == "olmoe":
        slots, max_len = 24, 2048
        engine, fn, tiny = _decode_program(_olmoe(max_len), max_len)
        args = _decode_args(engine, tiny, slots, one_chip)
    else:
        fn, args, _ = _block_pass_program(one_chip)
    on_cpu = jax.jit(fn.__wrapped__).lower(*args).as_text()
    assert _rows_kernels(on_cpu) == (0, 0)
    assert on_cpu.count("ragged_dot") >= 3
    monkeypatch.setattr(topology, "on_tpu", lambda: True)
    # a new function: ``jax.jit`` keeps what it traced by the function
    on_tpu = jax.jit(lambda *a: fn.__wrapped__(*a)).lower(*args).as_text()
    assert _rows_kernels(on_tpu) == (1, 1)
    assert "ragged_dot" not in on_tpu
