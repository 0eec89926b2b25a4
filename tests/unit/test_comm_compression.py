"""Quantized + hierarchical collectives behind the comm dispatch.

Covers the `comm_compression` acceptance surface on the 8-device CPU mesh:
  - blockwise codec round-trip error BOUNDS (property-style over dtypes /
    shapes / block sizes — not just "close", provably within scale/2),
  - the bitwise escape hatch: policy off ⇒ the dispatch traces programs
    byte-identical to raw jax.lax, and an engine configured with the block
    disabled/all-off trains bit-identically to one without the block,
  - quantized collective semantics vs their exact counterparts,
  - the hierarchical (intra-host f32 / inter-host quantized) reduce-scatter,
  - honest wire-byte accounting (ring factors, scatter's own op name,
    inter/intra-host split),
  - the ZeRO-3 regression: one train step with compression on vs off moves
    >= 3x fewer inter-host wire bytes at matched loss.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm.compression import (CommCompressionConfig,
                                            configure_comm_compression,
                                            reset_comm_compression)
from deepspeed_tpu.ops.quant_core import (FP8_DTYPE, FP8_QMAX, INT8_QMAX,
                                          block_count, dequantize_blockwise,
                                          quantize_blockwise, wire_nbytes)
from deepspeed_tpu.parallel import initialize_mesh
from deepspeed_tpu.parallel.topology import hierarchical_axis_groups
from deepspeed_tpu.runtime.config_utils import ConfigError


@pytest.fixture(autouse=True)
def _clean_compression():
    reset_comm_compression()
    dist.reset_comm_stats()
    yield
    reset_comm_compression()


@pytest.fixture
def mesh(mesh8):
    return mesh8.mesh


def _smap(mesh, fn, in_spec, out_spec):
    try:
        return shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                         check_vma=False)
    except TypeError:  # older jax spelling
        return shard_map(fn, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                         check_rep=False)


def _enable(**over):
    cfg = {"enabled": True, "all_gather": "int8", "reduce_scatter": "int8",
           "all_reduce": "int8", "all_to_all": "int8", "broadcast": "int8",
           "devices_per_host": 2, "min_bytes": 0}
    cfg.update(over)
    return configure_comm_compression(cfg)


# ------------------------------------------------------------- codec bounds

WIRES = ["int8"] + (["fp8_block"] if FP8_DTYPE is not None else [])


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,block", [
    ((1024,), 256), ((64, 32), 64), ((8, 128), 1024),  # block == size
    ((100,), 7),                                       # indivisible -> 1 blk
    ((512,), 1),                                       # degenerate block
])
def test_roundtrip_error_bound(wire, dtype, shape, block):
    """Per element |x - dq(q(x))| <= the codec's analytic bound from the
    BLOCK's absmax: scale/2 for int8 (half a rounding step), half-ulp
    relative (2^-4) for fp8 e4m3."""
    # crc32, not hash(): str hashes change with every process, and one
    # draw in a few dozen lands a bf16 input on the bound
    rng = np.random.default_rng(
        zlib.crc32(repr((wire, shape, block)).encode()))
    x = jnp.asarray((rng.normal(size=shape) *
                     rng.lognormal(size=shape)).astype("float32")).astype(dtype)
    q, scales = quantize_blockwise(x, block, wire)
    assert q.shape == x.shape
    nb = block_count(x.size, block)
    assert scales.shape == (nb,)
    xf = np.asarray(x, np.float32).reshape(nb, -1)
    back = np.asarray(dequantize_blockwise(q, scales)).reshape(nb, -1)
    absmax = np.abs(xf).max(axis=1, keepdims=True)
    if wire == "int8":
        bound = absmax / INT8_QMAX / 2 + 1e-7
    else:
        bound = np.abs(xf) * 2.0 ** -4 + absmax / FP8_QMAX + 1e-7
    assert (np.abs(back - xf) <= bound).all(), \
        np.max(np.abs(back - xf) - bound)


@pytest.mark.parametrize("wire", WIRES)
def test_roundtrip_zero_and_constant_blocks(wire):
    z = jnp.zeros((512,), jnp.float32)
    q, s = quantize_blockwise(z, 128, wire)
    np.testing.assert_array_equal(np.asarray(dequantize_blockwise(q, s)), 0.0)
    c = jnp.full((512,), -3.25, jnp.float32)
    q, s = quantize_blockwise(c, 128, wire)
    np.testing.assert_allclose(np.asarray(dequantize_blockwise(q, s)), -3.25,
                               rtol=1e-2)


def test_wire_nbytes_model():
    # 1 byte/value + 4 bytes/block of scales; indivisible -> one scale
    assert wire_nbytes(1024, 256) == 1024 + 4 * 4
    assert wire_nbytes(1000, 256) == 1000 + 4
    assert wire_nbytes(64, None) == 64 + 4


# ---------------------------------------------------- bitwise escape hatch

def test_policy_off_is_bitwise_identical_hlo(mesh):
    """The tentpole's escape hatch: with every policy off (the default),
    the dispatch wrappers lower to byte-identical programs as raw lax."""
    x = jnp.ones((8, 64), jnp.float32)

    def lowered(body):
        f = _smap(mesh, body, P("data"), P())
        return jax.jit(f).lower(x).as_text()

    pairs = [
        (lambda v: dist.all_gather(v, axis_name="data"),
         lambda v: lax.all_gather(v, "data", axis=0, tiled=True)),
        (lambda v: dist.all_reduce(v, axis_name="data"),
         lambda v: lax.psum(v, "data")),
        (lambda v: dist.reduce_scatter(
            dist.all_gather(v, axis_name="data"), axis_name="data"),
         lambda v: lax.psum_scatter(
             lax.all_gather(v, "data", axis=0, tiled=True), "data",
             scatter_dimension=0, tiled=True)),
        (lambda v: dist.broadcast(v, src=2, axis_name="data"),
         lambda v: lax.psum(
             jnp.where(lax.axis_index("data") == 2, v, jnp.zeros_like(v)),
             "data")),
        (lambda v: dist.all_to_all(jnp.sum(v) + jnp.zeros((8, 8)),
                                   axis_name="data", split_axis=1,
                                   concat_axis=1),
         lambda v: lax.all_to_all(jnp.sum(v) + jnp.zeros((8, 8)), "data",
                                  split_axis=1, concat_axis=1, tiled=True)),
    ]
    for wrapped, raw in pairs:
        assert lowered(wrapped) == lowered(raw)
    # and an ENABLED config whose per-op policies are all off is the same
    _enable(all_gather="off", reduce_scatter="off", all_reduce="off",
            all_to_all="off", broadcast="off")
    for wrapped, raw in pairs:
        assert lowered(wrapped) == lowered(raw)


def test_disallowed_axis_and_min_bytes_stay_uncompressed(mesh):
    _enable(allowed_axes=["model"])  # data collectives must not compress
    x = jnp.arange(8.0 * 64).reshape(8, 64)
    f = _smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
              P("data"), P())
    g = jax.jit(f)
    reset_comm_compression()
    h = jax.jit(_smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
                      P("data"), P()))
    assert g.lower(x).as_text() == h.lower(x).as_text()
    # min_bytes floor: tiny payloads keep full precision even when allowed
    _enable(min_bytes=10**9)
    f2 = jax.jit(_smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
                       P("data"), P()))
    assert f2.lower(x).as_text() == h.lower(x).as_text()


# ------------------------------------------------- quantized collectives

def test_quantized_all_gather_matches_exact(mesh):
    _enable()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
    f = _smap(mesh, lambda v: dist.all_gather(v, axis_name="data", axis=0),
              P("data"), P())
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.asarray(x), atol=np.abs(x).max() / 100)


@pytest.mark.parametrize("devices_per_host", [0, 2, 4])
def test_quantized_reduce_scatter_matches_exact(mesh, devices_per_host):
    """Flat (devices_per_host=0 on one host) AND hierarchical splits: the
    quantized reduce-scatter matches psum_scatter within codec error."""
    _enable(devices_per_host=devices_per_host)
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
    f = _smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data",
                                                  axis=0),
              P(None, None), P("data", None))
    out = np.asarray(f(y))
    # every member contributes the same full tensor -> sum = 8x, member i
    # holds rows [2i, 2i+2)
    np.testing.assert_allclose(out, 8 * np.asarray(y),
                               atol=8 * np.abs(y).max() / 60)


def test_hierarchical_rs_quantizes_after_intra_reduction(mesh):
    """The hierarchical path quantizes HOST-REDUCED partials: its error
    must stay within the codec bound of the 2-member-summed blocks (it
    would be ~L times larger if each member quantized pre-reduction)."""
    _enable(devices_per_host=2)
    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    f = _smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data",
                                                  axis=0),
              P(None, None), P("data", None))
    out = np.asarray(f(y))
    exact = 8 * np.asarray(y)
    # intra (x2) then quantized inter exchange of 4 host partials: the
    # inter leg rounds 4 values of magnitude ~2|y|: bound 4 * (2*absmax/127)
    bound = 4 * 2 * np.abs(y).max() / INT8_QMAX + 1e-5
    assert np.abs(out - exact).max() <= bound


def test_quantized_all_reduce_and_broadcast_and_a2a(mesh):
    _enable()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
    ar = _smap(mesh, lambda v: dist.all_reduce(v, op=dist.ReduceOp.AVG,
                                               axis_name="data"),
               P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(ar(x)),
                               np.tile(np.asarray(x).mean(0), (8, 1)),
                               atol=np.abs(x).max() / 30)
    bc = _smap(mesh, lambda v: dist.broadcast(v, src=5, axis_name="data"),
               P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(bc(x)),
                               np.tile(np.asarray(x)[5], (8, 1)),
                               atol=np.abs(x).max() / 100)
    a2a = _smap(mesh, lambda v: dist.all_to_all(v, axis_name="data",
                                                split_axis=1, concat_axis=1),
                P("data", None), P("data", None))
    reset_comm_compression()
    exact = _smap(mesh, lambda v: dist.all_to_all(v, axis_name="data",
                                                  split_axis=1,
                                                  concat_axis=1),
                  P("data", None), P("data", None))
    ex = np.asarray(exact(x))
    _enable()
    np.testing.assert_allclose(np.asarray(a2a(x)), ex,
                               atol=np.abs(x).max() / 100)


@pytest.mark.skipif(FP8_DTYPE is None, reason="no fp8 in this jaxlib")
def test_fp8_block_collectives(mesh):
    _enable(all_gather="fp8_block", broadcast="fp8_block")
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
    f = _smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
              P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x),
                               atol=np.abs(x).max() / 12)
    bc = _smap(mesh, lambda v: dist.broadcast(v, src=1, axis_name="data"),
               P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(bc(x)),
                               np.tile(np.asarray(x)[1], (8, 1)),
                               atol=np.abs(x).max() / 12)


# ----------------------------------------------------- wire-byte accounting

def test_wire_byte_model_flat_ops(mesh):
    """Wire accounting models per-member ring traffic: all_gather ships
    (n-1) shard copies, reduce_scatter (n-1)/n of the input, broadcast
    pays the full masked-psum ring (~2x), scatter accounts under its OWN
    name instead of inheriting a broadcast entry."""
    n, d = 8, 64
    x = jnp.ones((n, d), jnp.float32)
    shard_bytes = d * 4

    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
                  P("data"), P())).lower(x)
    assert dist.comm_stats()["bytes"] == (n - 1) * shard_bytes

    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data"),
                  P(None, None), P("data", None))).lower(x)
    full = n * d * 4
    assert dist.comm_stats()["bytes"] == (n - 1) * full // n

    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.all_reduce(v, axis_name="data"),
                  P("data"), P("data"))).lower(x)
    assert dist.comm_stats()["bytes"] == 2 * (n - 1) * shard_bytes // n

    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.broadcast(v, axis_name="data"),
                  P("data"), P("data"))).lower(x)
    assert dist.comm_stats()["bytes"] == 2 * (n - 1) * shard_bytes // n

    from deepspeed_tpu.comm import get_comms_logger
    cl = get_comms_logger()
    cl.enabled = True
    cl.reset()
    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.scatter(
        dist.gather(v, axis_name="data"), src=0, axis_name="data"),
        P("data"), P("data"))).lower(x)
    stats = dist.comm_stats()
    # gather(=all_gather) + scatter, each accounted once under its own op
    assert stats["ops"] == 2
    assert "scatter" in cl.comms_dict and "broadcast" not in cl.comms_dict
    cl.enabled = False
    cl.reset()


def test_inter_host_split_and_compression_ratio(mesh):
    """With 2 members/host, 4 of the 8 ring links cross hosts -> half the
    flat wire bytes are inter-host; the hierarchical quantized RS puts
    ONLY its (compressed) inter leg there."""
    n, d = 8, 2048
    x = jnp.ones((n, d), jnp.float32)
    _enable(all_gather="off", reduce_scatter="off", all_reduce="off",
            all_to_all="off", broadcast="off")   # accounting only
    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data"),
                  P(None, None), P("data", None))).lower(x)
    flat = dist.comm_stats()
    assert flat["inter_host_bytes"] * 2 == flat["bytes"]

    _enable(devices_per_host=2)
    dist.reset_comm_stats()
    jax.jit(_smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data"),
                  P(None, None), P("data", None))).lower(x)
    hier = dist.comm_stats()
    size = n * d
    intra = (2 - 1) * (size // 2) * 4
    inter = (4 - 1) * wire_nbytes(size // 8, 256)
    assert hier["bytes"] == intra + inter
    assert hier["inter_host_bytes"] == inter
    assert flat["inter_host_bytes"] / hier["inter_host_bytes"] > 3


def test_hierarchical_axis_groups_shapes():
    intra, inter = hierarchical_axis_groups(8, 2)
    assert intra == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert inter == [[0, 2, 4, 6], [1, 3, 5, 7]]
    assert hierarchical_axis_groups(8, 1) == (None, None)
    assert hierarchical_axis_groups(8, 8) == (None, None)
    assert hierarchical_axis_groups(8, 3) == (None, None)


def test_config_validation():
    with pytest.raises(ConfigError, match="must be one of"):
        CommCompressionConfig.from_dict({"all_gather": "int4"})
    with pytest.raises(ConfigError, match="block_size"):
        CommCompressionConfig.from_dict({"block_size": 0})
    cfg = CommCompressionConfig.from_dict(
        {"enabled": True, "reduce_scatter": "int8"})
    assert cfg.zero_path_active
    assert not CommCompressionConfig.from_dict(
        {"enabled": True, "all_to_all": "int8"}).zero_path_active
    assert not CommCompressionConfig.from_dict(
        {"reduce_scatter": "int8"}).zero_path_active   # master switch off


# --------------------------------------------------------- engine (ZeRO-3)

def _train_zero3(cc, steps=2, seed=7, stage=3, gas=1):
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import topology
    topology.reset_mesh()
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=33, n_embd=64,
                                 n_layer=2, n_head=4,
                                 pad_vocab_to_multiple=8))
    config = {
        "train_batch_size": 16 * gas, "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": 0},
        "gradient_clipping": 1.0, "steps_per_print": 0}
    if cc is not None:
        config["comm_compression"] = cc
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rng = np.random.default_rng(seed)
    before = dist.comm_stats()
    losses = []
    for _ in range(steps):
        toks = rng.integers(0, 255, (16 * gas, 33)).astype(np.int32)
        batch = {"input_ids": toks.reshape(gas, 16, 33)}
        losses.append(float(engine.train_batch(batch=batch)))
    after = dist.comm_stats()
    params = jax.tree.leaves(jax.tree.map(np.asarray, engine.params))
    engine.close()
    return losses, {k: after[k] - before[k] for k in after}, params


def test_zero3_compression_regression():
    """THE acceptance test: one ZeRO-3 step with int8+hierarchical
    compression moves >= 3x fewer inter-host wire bytes than the same
    step uncompressed (measured through the same explicit-dispatch
    instrumentation, fp32 policies), at matched loss."""
    base_losses, base_stats, _ = _train_zero3(
        {"enabled": True, "all_gather": "fp32", "reduce_scatter": "fp32",
         "all_reduce": "fp32", "devices_per_host": 2})
    q_losses, q_stats, _ = _train_zero3(
        {"enabled": True, "all_gather": "int8", "reduce_scatter": "int8",
         "all_reduce": "int8", "devices_per_host": 2, "min_bytes": 0})
    assert base_stats["inter_host_bytes"] > 0
    ratio = base_stats["inter_host_bytes"] / q_stats["inter_host_bytes"]
    assert ratio >= 3.0, (base_stats, q_stats)
    assert q_stats["bytes"] < base_stats["bytes"]
    # matched loss: same data, same init -> curves agree within the int8
    # codec's effect on a 2-layer model
    for a, b in zip(base_losses, q_losses):
        assert abs(a - b) / abs(a) < 0.01, (base_losses, q_losses)


def test_zero3_policy_off_is_bitwise_identical():
    """Escape-hatch at the engine level: no block, enabled:false, and
    enabled-with-all-off-policies produce IDENTICAL parameters bit for
    bit (same GSPMD program)."""
    _, _, p_none = _train_zero3(None)
    _, _, p_disabled = _train_zero3({"enabled": False})
    _, _, p_off = _train_zero3({"enabled": True, "all_gather": "off",
                                "reduce_scatter": "off"})
    for a, b, c in zip(p_none, p_disabled, p_off):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_zero3_compressed_with_accumulation_learns():
    """gas > 1: the compressed micro-grad lives inside the accumulation
    scan; losses stay finite and match the uncompressed run closely."""
    base, _, _ = _train_zero3(
        {"enabled": True, "all_gather": "fp32", "reduce_scatter": "fp32"},
        steps=2, gas=2)
    q, _, _ = _train_zero3(
        {"enabled": True, "all_gather": "int8", "reduce_scatter": "int8",
         "min_bytes": 0}, steps=2, gas=2)
    assert all(np.isfinite(base)) and all(np.isfinite(q))
    for a, b in zip(base, q):
        assert abs(a - b) / abs(a) < 0.01


def test_compression_scope_rejects_model_parallel():
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel import topology
    topology.reset_mesh()
    model = GPT2Model(GPT2Config(vocab_size=256, n_positions=33, n_embd=64,
                                 n_layer=2, n_head=4,
                                 pad_vocab_to_multiple=8))
    with pytest.raises(ConfigError, match="pure data parallelism"):
        deepspeed_tpu.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "tensor_parallel_size": 2,
            "zero_optimization": {"stage": 2},
            "comm_compression": {"enabled": True, "reduce_scatter": "int8"},
        })
