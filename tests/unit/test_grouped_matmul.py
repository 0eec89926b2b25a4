"""The gated experts' rows kernel (``ops/pallas/grouped_matmul.py``) in
Pallas interpret mode against a gather and three einsums, and the choice
between it and ``lax.ragged_dot`` (``moe/experts.py:_rows_kernel``): the
shape rule as a table, the CPU programs unchanged, a dense engine that
never loads the kernel's module, the counter of the serving engine."""

import logging
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.moe import experts
from deepspeed_tpu.moe.experts import GatedExpertFFN
from deepspeed_tpu.ops.pallas import grouped_matmul
from deepspeed_tpu.ops.pallas.grouped_matmul import choose, gated_rows

K, F = 128, 256


def _leaves(rng, groups, dtype):
    return [jnp.asarray(rng.randn(groups, *shape) * 0.1, dtype)
            for shape in ((K, F), (K, F), (F, K))]


def _plain(x, leaves, sizes, first=0):
    """Gather each row's expert and contract, in numpy: the three products
    with the roundings of three ``ragged_dot`` (float32 sums, rounded to
    x's type)."""
    ids = np.repeat(np.arange(len(sizes)), sizes) + first
    dt = x.dtype                # numpy's too: ml_dtypes holds bfloat16
    rows = np.asarray(x[:len(ids)], np.float32)
    wg, wu, wd = (np.asarray(w, np.float32)[ids] for w in leaves)

    def rounded(a):
        return a.astype(dt).astype(np.float32)
    g = rounded(np.einsum("nk,nkf->nf", rows, wg))
    u = rounded(np.einsum("nk,nkf->nf", rows, wu))
    h = rounded(g / (1 + np.exp(-g)) * u)
    return np.einsum("nf,nfm->nm", h, wd).astype(dt)


def _close(got, want, dtype):
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# name, type, rows, sizes of the groups (a sum under the rows: the rest
# belong to no group, a share's absent experts), tile, first group, groups
# of the leaves. The shapes of the routed path in float32, two of
# them again in bfloat16; every count that is no multiple of 8 and every
# tile the rule can return in both.
_F32, _BF16 = jnp.float32, jnp.bfloat16
_CASES = [
    ("empty groups", _F32, 24, [3, 0, 5, 1, 0, 0, 7, 8], 16, 0, 8),
    ("empty groups", _BF16, 24, [3, 0, 5, 1, 0, 0, 7, 8], 16, 0, 8),
    ("no group has a row", _F32, 12, [0, 0, 0, 0], 16, 0, 4),
    ("one row a group", _F32, 8, [1] * 8, 16, 0, 8),
    ("count no multiple of the tile", _F32, 73, [40, 0, 3, 30], 32, 0, 4),
    ("a group over three tiles", _F32, 50, [2, 45, 3], 16, 0, 3),
    ("two groups end on a tile's edge", _F32, 48, [16, 0, 16, 9], 16, 0, 4),
    ("groups from an offset in the stack", _F32, 18,
     [3, 0, 5, 1, 0, 0, 7, 2], 16, 8, 24),
    ("rows of no group at the end", _F32, 40, [5, 9, 0, 4], 16, 4, 12),
    ("rows of no group at the end", _BF16, 40, [5, 9, 0, 4], 16, 4, 12),
    ("no row of the share's experts", _F32, 40, [0, 0], 16, 2, 4),
] + [(f"{n} rows", dt, n, None, None, 0, 4)
     for n in (1, 2, 3, 4, 5, 6, 7, 12, 13) for dt in (_F32, _BF16)] \
  + [(f"row tile {t}", dt, 2 * t + 5, None, t, 0, 4)
     for t, dt in ((8, _F32), (16, _F32), (16, _BF16), (32, _F32),
                   (64, _BF16), (128, _F32), (128, _BF16))]


@pytest.mark.parametrize(
    "name,dtype,rows,sizes,tile,first,groups", _CASES,
    ids=[f"{c[0]}-{jnp.dtype(c[1]).name}" for c in _CASES])
def test_rows_kernel_is_the_plain_product(name, dtype, rows, sizes, tile,
                                          first, groups):
    """``gated_rows`` in interpret mode reads what a gather and three
    einsums read, for every row of a group; a row of no group in a tile
    that was visited reads zero."""
    rng = np.random.RandomState(len(name) + rows)
    if sizes is None:       # rows cut into four groups at random
        cut = np.sort(rng.randint(0, rows + 1, 3))
        sizes = np.diff(np.concatenate([[0], cut, [rows]]))
    if tile is None:        # the rule's own tile for so few rows
        tile = choose(rows, K, F, dtype, "tpu")
    sizes = np.asarray(sizes, np.int32)
    x = jnp.asarray(rng.randn(rows, K), dtype)
    leaves = _leaves(rng, groups, dtype)
    args = (x, *leaves, jnp.asarray(sizes), first)
    # the interpreter's program, compiled without the CPU backend's
    # optimisations: two thirds of a case's time otherwise
    got = gated_rows.lower(*args, tile=tile, interpret=True).compile(
        compiler_options={"xla_backend_optimization_level": 0,
                          "xla_llvm_disable_expensive_passes": True})(*args)
    assert got.shape == (rows, K) and got.dtype == dtype
    held = int(sizes.sum())
    _close(got[:held], _plain(x, leaves, sizes, first), dtype)
    visited = -(-held // tile) * tile if held else tile
    assert not np.asarray(got[held:visited], np.float32).any()


@pytest.mark.parametrize("layer", (0, 2))
def test_apply_grouped_hands_the_stack_where_it_lies(monkeypatch, layer):
    """``apply_grouped(..., layer=l)`` where the rule takes the kernel:
    the stacked [L, E, ...] leaves go in as L * E groups from ``l * E``,
    and the result is what the ``ragged_dot`` path reads on layer l."""
    rng = np.random.RandomState(layer)
    n_layers, e, rows = 3, 4, 22
    ffn = GatedExpertFFN(K, F, e)
    stacked = dict(zip(ffn.matmul_leaves, (
        w.reshape(n_layers, e, *w.shape[1:])
        for w in _leaves(rng, n_layers * e, jnp.float32))))
    sizes = jnp.asarray([5, 0, 13, 4], jnp.int32)
    x = jnp.asarray(rng.randn(rows, K), jnp.float32)
    want = ffn.apply_grouped(stacked, x, sizes, layer=layer)
    before = experts.grouped_matmuls()

    monkeypatch.setattr(experts, "_rows_kernel", lambda x, leaves: 16)
    monkeypatch.setattr(
        grouped_matmul, "gated_rows",
        lambda *a, **kw: gated_rows(*a, interpret=True, **kw))
    got = jax.jit(lambda p, l: ffn.apply_grouped(p, x, sizes, layer=l))(
        stacked, layer)
    _close(got, want, jnp.float32)
    took, products = experts.grouped_matmuls()
    assert (took - before[0], products - before[1]) == (3, 3)


# what the sweep's cases give (PERF.md section 6, PR 52): rows = tokens x
# picks, K, F -> the row tile, or None where ``lax.ragged_dot`` keeps the
# products: cell 7's expert does not fit VMEM whole; cell 9's prefill, a
# wash alone, goes with its cell's decode step (section 6 has the pair)
_RULE = [
    ("c11dec", 192 * 8, 2048, 768, 128),
    ("c11pf512", 512 * 8, 2048, 768, 128),
    ("c11pf4096", 4096 * 8, 2048, 768, 128),
    ("c3dec", 24 * 8, 2048, 1024, 128),
    ("c3pf512", 512 * 8, 2048, 1024, 128),
    ("c5dec", 40 * 4, 2048, 1536, 128),
    ("c5pf1024", 1024 * 4, 2048, 1536, 128),
    ("c5pf4096", 4096 * 4, 2048, 1536, 128),
    ("c7dec", 48 * 8, 6144, 2048, None),
    ("c7pf4096", 4096 * 8, 6144, 2048, None),
    ("c9dec", 32 * 4, 3584, 1024, 128),
    ("c9pf4096", 4096 * 4, 3584, 1024, 128),
    ("one slot's four picks", 4, 2048, 1536, 16),
]


@pytest.mark.parametrize("name,rows,k,f,want", _RULE,
                         ids=[c[0] for c in _RULE])
def test_shape_rule(name, rows, k, f, want):
    assert choose(rows, k, f, jnp.bfloat16, "tpu") == want
    assert choose(rows, k, f, jnp.bfloat16, "cpu") is None


def test_shape_rule_leaves_out_what_the_kernel_does_not_take():
    assert choose(4, 2048, 768, jnp.float32, "tpu") == 8
    assert choose(192, 2048, 768, jnp.int8, "tpu") is None
    assert choose(192, 2048, 100, jnp.bfloat16, "tpu") is None     # lanes
    assert choose(192, 100, 768, jnp.bfloat16, "tpu") is None
    assert choose(0, 2048, 768, jnp.bfloat16, "tpu") is None
    # float32 doubles an expert's bytes: 3584 x 1024 no longer fits
    assert choose(192, 3584, 1024, jnp.float32, "tpu") is None


def test_cpu_program_is_three_ragged_dots():
    """On the CPU ``apply_grouped`` traces to what it traced to before the
    kernel: three ``ragged_dot`` and no ``pallas_call``, letter for letter
    the program of a process where the rule is never asked."""
    ffn = GatedExpertFFN(K, F, 4)
    params = ffn.init(jax.random.PRNGKey(0))
    x = jnp.ones((16, K), jnp.float32)
    sizes = jnp.asarray([4, 4, 4, 4], jnp.int32)
    before = experts.grouped_matmuls()
    text = str(jax.make_jaxpr(
        lambda p: ffn.apply_grouped(p, x, sizes))(params))
    assert len(re.findall(r"= ragged_dot_general\[", text)) == 3 and \
        "pallas_call" not in text
    took, products = experts.grouped_matmuls()
    assert (took - before[0], products - before[1]) == (0, 3)
    real = experts._rows_kernel
    try:
        experts._rows_kernel = lambda *a: None
        assert text == str(jax.make_jaxpr(
            lambda p: ffn.apply_grouped(p, x, sizes))(params))
    finally:
        experts._rows_kernel = real


def _serve(model):
    """Serve one request on the CPU and shut down; the log's lines (the
    package's logger hands nothing up to the root, so a handler of its
    own reads them)."""
    from deepspeed_tpu.utils.logging import logger
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.serving.engine import SamplingParams, ServingEngine
    engine = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
        {"dtype": "float32", "max_tokens": 32}))
    serving = ServingEngine(engine, {"num_slots": 2, "max_model_len": 32})
    lines = []
    reader = logging.Handler()
    reader.emit = lambda record: lines.append(record.getMessage())
    logger.addHandler(reader)
    try:
        rid = serving.submit(np.arange(1, 5),
                             SamplingParams(max_new_tokens=3))
        serving.run_until_idle()
        assert len(serving.result(rid).tokens) == 3
        serving.shutdown()
    finally:
        logger.removeHandler(reader)
    return serving, lines


def test_dense_engine_never_loads_the_kernel_and_says_nothing():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    name = "deepspeed_tpu.ops.pallas.grouped_matmul"
    held = sys.modules.pop(name)
    try:
        serving, lines = _serve(GPT2Model(GPT2Config(
            vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
            pad_vocab_to_multiple=64)))
        assert name not in sys.modules
    finally:
        sys.modules[name] = held
    assert serving.metrics.grouped_matmuls == (0, 0)
    assert any("shut down after" in line for line in lines)
    assert not any("grouped matmuls" in line for line in lines)


def test_routed_engine_counts_its_grouped_matmuls():
    """On the CPU no product takes the kernel: "0 of the programs' M"."""
    from deepspeed_tpu.models.olmoe import OLMoEConfig, OLMoEModel
    serving, lines = _serve(OLMoEModel(OLMoEConfig(
        vocab_size=64, n_positions=32, n_embd=32, n_layer=2, n_head=2,
        mlp_hidden=32, num_experts=4, top_k=2, dtype="float32")))
    said = [m for line in lines for m in re.findall(
        r"(\d+) of the programs' (\d+) grouped matmuls took the rows kernel",
        line)]
    took, products = serving.metrics.grouped_matmuls
    assert said == [(str(took), str(products))]
    # a prefill bucket and the decode step, three products a layer body
    assert took == 0 and products >= 6 and products % 3 == 0
