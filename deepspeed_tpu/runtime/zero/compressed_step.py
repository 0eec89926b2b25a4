"""Explicit (compression-aware) ZeRO exchange — the shard_map micro-grad.

The default ZeRO path is pure GSPMD: sharding constraints make XLA insert
the stage-3 param all-gathers and stage-2/3 grad reduce-scatters, which is
optimal but leaves the wire format out of our hands — GSPMD collectives
always move the compute dtype. When a ``comm_compression`` policy is
active, the engine swaps the micro-gradient computation for this module's
``shard_map`` over the data axis, where the SAME exchanges run through the
comm dispatch (comm/comm.py) and can therefore quantize:

  1. stage-3 param shards are gathered explicitly with
     :func:`comm.all_gather` — blockwise int8/fp8 wire under policy
     (ZeRO++ qwZ),
  2. the model runs locally on the (host-)full params and the local
     micro-batch shard,
  3. gradients are exchanged explicitly: dp-sharded leaves via
     :func:`comm.reduce_scatter` (hierarchical intra-host-f32 /
     inter-host-quantized under policy — ZeRO++ qgZ), replicated leaves
     via :func:`comm.all_reduce`.

Semantics match the GSPMD path's per-micro gradients (global-mean loss,
AVG reduction) up to quantization error and float reduction order; the
``comm_compression`` "off" policies keep the GSPMD path untouched — that
is the bitwise escape hatch.

Scope (validated by the engine): pp = tp = sp = ep = 1 — the compressed
exchange owns the WHOLE mesh minus the data axis, so model/pipeline/
sequence sharding must be off. This is the ZeRO++ deployment shape: pure
data-parallel ZeRO across many hosts.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ... import comm
from ...parallel.topology import DATA_AXIS


def _shard_map_norep(fn, mesh, in_specs, out_specs):
    """shard_map with the replication check disabled (outputs are made
    consistent by explicit collectives, which the checker cannot see
    through)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _dp_dim(spec) -> int:
    """Index of the dim a PartitionSpec shards over the data axis, -1 if
    replicated w.r.t. data."""
    for i, s in enumerate(spec):
        if s == DATA_AXIS or (isinstance(s, (tuple, list)) and
                              DATA_AXIS in s):
            return i
    return -1


def explicit_scope_error(engine, feature: str) -> Optional[str]:
    """Why an explicit (shard_map) ZeRO exchange cannot run under this
    config, or None. The engine raises this at init — accepted config =
    active config. ``feature`` names the block that asked for the path
    (``comm_compression`` or ``overlap_schedule``)."""
    mm = engine.mesh_manager
    if mm.pp > 1 or mm.tp > 1 or mm.sp > 1 or mm.ep > 1:
        return (f"{feature}: the explicit ZeRO exchange supports "
                "pure data parallelism only (pp=tp=sp=ep=1); got "
                f"pp={mm.pp} tp={mm.tp} sp={mm.sp} ep={mm.ep}. Disable "
                "the block or drop the model-parallel axes")
    if engine._offload is not None or engine._param_runner is not None:
        return (f"{feature}: not supported together with "
                "ZeRO-Offload / param offload (the offload runners own "
                "their own step functions)")
    return None


def compression_scope_error(cfg, engine) -> Optional[str]:
    del cfg
    return explicit_scope_error(engine, "comm_compression")


def make_compressed_micro_grad(engine, ltd_keep=None):
    """Build ``grad_fn(pc, mb, rng, scale, pld_theta) -> (loss, grads)``:
    the shard_map'd micro-gradient with explicit (policy-dispatched) ZeRO
    collectives. ``pc`` is the compute-dtype param tree; the returned loss
    is the scaled global-mean micro loss, grads are global-mean grads laid
    out per ``engine.grad_shardings`` — exactly the GSPMD path's contract,
    so the gradient-accumulation scan and optimizer update are unchanged.
    """
    mm = engine.mesh_manager
    mesh = mm.mesh
    param_specs = jax.tree.map(lambda s: s.spec, engine.param_shardings)
    grad_specs = jax.tree.map(lambda s: s.spec, engine.grad_shardings)
    # dp-sharded dim per leaf (static): which dim to gather/scatter
    gather_dims = jax.tree.map(lambda s: _dp_dim(s.spec),
                               engine.param_shardings)
    scatter_dims = jax.tree.map(lambda s: _dp_dim(s.spec),
                                engine.grad_shardings)
    batch_spec = mm.batch_spec(shard_seq=False)
    # pld_theta is a traced scalar iff progressive layer drop is configured
    # (static per engine); None cannot cross the shard_map boundary as an
    # input, so the arity is fixed here
    with_pld = engine.progressive_layer_drop is not None

    def body(pc, mb, rng, scale, pld_theta):
        # decorrelate per-shard dropout/noise (the GSPMD path draws one
        # global mask; lossy mode trades that for locality)
        r = None if rng is None else jax.random.fold_in(
            rng, lax.axis_index(DATA_AXIS))

        # 1. explicit stage-3 param gather — quantized wire under policy
        def gather_leaf(d, x):
            if d < 0:
                return x
            return comm.all_gather(x, axis_name=DATA_AXIS, axis=d)

        full = jax.tree.map(gather_leaf, gather_dims, pc)

        def scaled_loss(p):
            return engine._micro_loss(p, mb, r, precast=True,
                                      pld_theta=pld_theta,
                                      ltd_keep=ltd_keep) * scale

        loss, g = jax.value_and_grad(scaled_loss)(full)

        # 2. explicit grad exchange: AVG over dp (local losses are means
        #    over the local batch shard; averaging the shard-grads equals
        #    the global-mean gradient)
        def reduce_leaf(d, gl):
            if d < 0:
                return comm.all_reduce(gl, op=comm.ReduceOp.AVG,
                                       axis_name=DATA_AXIS)
            return comm.reduce_scatter(gl, axis_name=DATA_AXIS, axis=d,
                                       op=comm.ReduceOp.AVG)

        g = jax.tree.map(reduce_leaf, scatter_dims, g)
        loss = comm.all_reduce(loss, op=comm.ReduceOp.AVG,
                               axis_name=DATA_AXIS)
        return loss, g

    if with_pld:
        smap = _shard_map_norep(
            body, mesh,
            in_specs=(param_specs, batch_spec, P(), P(), P()),
            out_specs=(P(), grad_specs))
        return smap
    inner = _shard_map_norep(
        lambda pc, mb, rng, scale: body(pc, mb, rng, scale, None),
        mesh,
        in_specs=(param_specs, batch_spec, P(), P()),
        out_specs=(P(), grad_specs))

    def without_pld(pc, mb, rng, scale, pld_theta=None):
        del pld_theta
        return inner(pc, mb, rng, scale)

    return without_pld
