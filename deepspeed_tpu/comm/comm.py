"""deepspeed_tpu.comm — the communication layer.

TPU-native re-design of the reference comm wrapper (deepspeed/comm/comm.py:
torch.distributed-compatible API over NCCL). On TPU there are two distinct
planes, and this module covers both:

1. **Host/control plane** — process bootstrap and eager cross-host ops:
   ``init_distributed`` → ``jax.distributed.initialize`` (the reference's
   rendezvous, comm.py:526), ``get_rank``/``get_world_size`` →
   process indices, ``barrier``/``broadcast_obj`` via multihost utils.

2. **Device/compute plane** — collectives *inside* compiled programs:
   thin named wrappers over ``jax.lax`` collectives (psum/all_gather/
   psum_scatter/all_to_all/ppermute) for use under ``shard_map``. Each wrapper
   routes through ``timed_op`` so the CommsLogger records op/size/participants
   exactly like the reference's @timed_op (comm.py:104) — at trace time, since
   XLA owns execution scheduling.

The reference's capability fallbacks (reduce_scatter_fn → allgather+reduce,
comm.py:239) are unnecessary: XLA provides every primitive on every backend.
"""

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.logging import logger
# submodule import (not the telemetry package) — keeps the
# comm <-> telemetry.export import graph acyclic
from ..telemetry.trace import get_tracer
from .logging import get_comms_logger

_INITIALIZED = False


class ReduceOp:
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "prod"


# --------------------------------------------------------------------------
# Host/control plane
# --------------------------------------------------------------------------

def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1):
    """Bootstrap multi-host JAX. Mirrors deepspeed.init_distributed
    (comm.py:526) including env-based discovery (comm.py:591-689): honors
    the launcher's WORLD_SIZE/RANK/MASTER_ADDR/MASTER_PORT, plus OMPI_* and
    SLURM_* variables.

    Single-process (the common TPU dev loop and the CI fake-multichip mode)
    is a no-op: jax already sees its local devices.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return

    env = os.environ
    nprocs = world_size if world_size > 0 else int(
        env.get("DSTPU_NUM_PROCESSES",
                env.get("WORLD_SIZE", env.get("OMPI_COMM_WORLD_SIZE",
                                              env.get("SLURM_NTASKS", "1")))))
    proc_id = rank if rank >= 0 else int(
        env.get("RANK", env.get("OMPI_COMM_WORLD_RANK", env.get("SLURM_PROCID", "0"))))

    # do NOT touch jax.devices()/process_count() before initialize — that
    # would initialize the XLA backend and make jax.distributed.initialize
    # raise (it must run first in the process)
    if nprocs > 1 and not jax.distributed.is_initialized():
        coordinator = init_method
        if coordinator is None:
            addr = env.get("MASTER_ADDR", "127.0.0.1")
            port = env.get("MASTER_PORT", str(distributed_port))
            coordinator = f"{addr}:{port}"
        if env.get("JAX_PLATFORMS", "").startswith("cpu") or \
                env.get("DSTPU_ACCELERATOR") == "cpu":
            # multi-process CPU backend needs cross-host collectives
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if verbose:
            logger.info(
                f"Initializing jax.distributed: coordinator={coordinator} "
                f"rank={proc_id} world={nprocs}")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=nprocs,
                                   process_id=proc_id)
    _INITIALIZED = True


def _dist_state():
    """The jax.distributed global state (None outside multi-process runs).
    Private import: the barrier and object broadcast below need the
    coordination-service client (key-value store, wait_at_barrier), which
    jax 0.9 exposes nowhere public."""
    from jax._src import distributed
    if distributed.global_state.client is not None:
        return distributed.global_state
    return None


def is_initialized():
    return _INITIALIZED or _dist_state() is not None


def get_rank(group=None) -> int:
    gs = _dist_state()
    return gs.process_id if gs is not None else jax.process_index()


def get_world_size(group=None) -> int:
    gs = _dist_state()
    return gs.num_processes if gs is not None else jax.process_count()


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", 0))


_barrier_count = 0


def barrier(group=None, timeout_ms: int = 600_000):
    """Cross-process barrier over the coordination service (GRPC) — no
    device collective, so it works on any backend mix. Falls back to the
    device-collective sync when the runtime is multi-process without a
    jax.distributed client (e.g. an externally-bootstrapped TPU pod)."""
    global _barrier_count
    gs = _dist_state()
    if gs is not None and gs.num_processes > 1:
        _barrier_count += 1
        gs.client.wait_at_barrier(f"dstpu_barrier_{_barrier_count}",
                                  timeout_ms)
    elif jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("deepspeed_tpu.barrier")


def broadcast_object(obj, src: int = 0):
    """Host-level object broadcast via the coordination service key-value
    store (reference p2p pickled-object sends, pipe/p2p.py:100). The entry
    is deleted after every rank has read it (no coordinator KV leak)."""
    global _barrier_count
    gs = _dist_state()
    if gs is None or gs.num_processes <= 1:
        if gs is None and jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return multihost_utils.broadcast_one_to_all(
                obj, is_source=jax.process_index() == src)
        return obj
    import base64
    import pickle
    _barrier_count += 1
    key = f"dstpu_bcast_{_barrier_count}"
    if gs.process_id == src:
        payload = base64.b64encode(pickle.dumps(obj)).decode("ascii")
        gs.client.key_value_set(key, payload)
        out = obj
    else:
        payload = gs.client.blocking_key_value_get(key, 600_000)
        out = pickle.loads(base64.b64decode(payload))
    gs.client.wait_at_barrier(f"{key}_done", 600_000)
    if gs.process_id == src:
        try:
            gs.client.key_value_delete(key)
        except Exception:
            pass  # older jaxlib without delete: entry persists, job still OK
    return out


def destroy_process_group():
    global _INITIALIZED
    if jax.process_count() > 1:
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    _INITIALIZED = False


# --------------------------------------------------------------------------
# Device/compute plane — collectives for use inside shard_map.
#
# Every wrapper routes through ONE compression-aware dispatch point: the
# policy from comm/compression.py (the `comm_compression` config block)
# decides per call whether to trace the plain lax op (policy "off" — the
# bitwise escape hatch: byte-identical programs to an uncompressed build),
# the full-precision explicit path ("fp32"), or the blockwise-quantized
# wire implementations in comm/quantized.py ("int8"/"fp8_block").
# Accounting records WIRE bytes per op — what a ring implementation puts on
# each member's links, compressed size when a codec ran — split into
# intra-host and inter-host traffic when the (host, local) layout is known.
# --------------------------------------------------------------------------

from .compression import get_comm_compression

_SUMLIKE = (ReduceOp.SUM, ReduceOp.AVG)


def _size_bytes(x):
    try:
        return x.size * x.dtype.itemsize
    except Exception:
        return 0


def _participants(axis_name) -> int:
    """Static axis size at trace time (psum of a python 1 folds to a
    constant — no HLO is emitted); 0 when the axis is unbound
    (eager/host context)."""
    try:
        return int(lax.psum(1, axis_name))
    except Exception:
        return 0


# Baseline per-member ring wire-byte model, from the logical payload bytes:
# all_gather's input is the SHARD (it ships n-1 copies of it); reduce_
# scatter/all_to_all move (n-1)/n of the full input per member; all_reduce
# = reduce-scatter + all-gather = 2(n-1)/n; broadcast lowers to a masked
# psum (see broadcast()) so it pays the full all-reduce ring, ~2x an
# optimal broadcast; scatter lowers to broadcast + local slice and
# inherits its wire cost under its own op name.
_BASE_WIRE = {
    "all_reduce": lambda nb, n: 2 * (n - 1) * nb // n,
    "all_gather": lambda nb, n: (n - 1) * nb,
    "reduce_scatter": lambda nb, n: (n - 1) * nb // n,
    "all_to_all": lambda nb, n: (n - 1) * nb // n,
    "broadcast": lambda nb, n: 2 * (n - 1) * nb // n,
    "scatter": lambda nb, n: 2 * (n - 1) * nb // n,
    "ppermute": lambda nb, n: nb,
}


def _base_wire(op: str, logical: int, n: int) -> int:
    if n <= 1:
        # unbound axis (host context) or single member: nothing crosses a
        # link for n==1; keep the logical size for n==0 so eager callers
        # still see their payload accounted
        return logical if n == 0 else 0
    return _BASE_WIRE[op](logical, n)


# cumulative collective accounting, maintained unconditionally — a few
# integer adds at trace time. The flight recorder diffs this per step
# record to show how much collective traffic the anomalous step carried,
# without scanning the span ring.
_COMM_OPS = 0
_COMM_WIRE_BYTES = 0
_COMM_LOGICAL_BYTES = 0
_COMM_INTER_BYTES = 0
_COMM_INTRA_BYTES = 0
_COMM_PER_OP: dict = {}


def comm_stats():
    """Cumulative collective accounting traced through the wrappers.

    ``bytes`` is WIRE bytes (per-member link traffic, compressed size when
    a quantized policy ran); ``logical_bytes`` is the uncompressed payload
    the caller handed in; ``inter_host_bytes``/``intra_host_bytes`` split
    the wire traffic by link scope when the (host, local) layout is known
    (comm_compression.devices_per_host, else the process-local device
    count)."""
    return {"ops": _COMM_OPS, "bytes": _COMM_WIRE_BYTES,
            "logical_bytes": _COMM_LOGICAL_BYTES,
            "inter_host_bytes": _COMM_INTER_BYTES,
            "intra_host_bytes": _COMM_INTRA_BYTES}


def comm_per_op_stats():
    """Per-op traced collective counts ({op name: count}). Kept apart
    from :func:`comm_stats` — whose flat numeric dict the flight
    recorder diffs per step record — so the dispatch-conformance
    auditor (analysis/hlo_audit_rules.py HLO006) can reconcile a
    compiled module's collective kinds against what the dispatch
    actually traced."""
    return dict(_COMM_PER_OP)


def reset_comm_stats():
    global _COMM_OPS, _COMM_WIRE_BYTES, _COMM_LOGICAL_BYTES
    global _COMM_INTER_BYTES, _COMM_INTRA_BYTES
    _COMM_OPS = _COMM_WIRE_BYTES = _COMM_LOGICAL_BYTES = 0
    _COMM_INTER_BYTES = _COMM_INTRA_BYTES = 0
    _COMM_PER_OP.clear()


def _split_inter(wire: int, n: int) -> int:
    """Inter-host share of a FLAT collective's wire bytes: with L members
    per host laid out host-major, H = n/L of the n ring links cross hosts,
    and every ring link carries the same traffic — so H/n of the bytes are
    inter-host. 0 when the axis fits on one host (or layout unknown)."""
    if n <= 1:
        return 0
    local = get_comm_compression().local_members(n)
    if not local:
        return 0
    return wire * (n // local) // n


def _account(op, logical, wire, n, axis_name, inter=None):
    """Record one traced collective into the cumulative counters + comms
    logger. ``inter``: explicit inter-host wire bytes (hierarchical ops
    know their legs); default = the flat ring-link model."""
    global _COMM_OPS, _COMM_WIRE_BYTES, _COMM_LOGICAL_BYTES
    global _COMM_INTER_BYTES, _COMM_INTRA_BYTES
    if inter is None:
        inter = _split_inter(wire, n)
    _COMM_OPS += 1
    _COMM_WIRE_BYTES += wire
    _COMM_LOGICAL_BYTES += logical
    _COMM_INTER_BYTES += inter
    _COMM_INTRA_BYTES += wire - inter
    _COMM_PER_OP[op] = _COMM_PER_OP.get(op, 0) + 1
    cl = get_comms_logger()
    if cl is not None and cl.enabled:
        cl.append(op, wire, str(axis_name))
    return inter


def _comm_span(name, logical, wire, axis_name, participants, policy="off"):
    """Telemetry span for one collective: op kind, logical payload bytes,
    wire bytes, mesh axis, participant count, active compression policy
    (bus bandwidth is derived at export time from WIRE bytes ÷ measured
    duration). Collectives inside compiled programs are spanned at TRACE
    time — XLA owns execution scheduling, so the per-execution wall time
    of a fused collective is only visible to ``jax.profiler``; these spans
    give per-op byte/shape accounting and trace-position instead."""
    tracer = get_tracer()
    if not tracer.enabled:
        return tracer.span(name)     # the shared no-op singleton
    args = {"op": name, "bytes": logical, "wire_bytes": wire,
            "axis": str(axis_name), "participants": participants}
    if policy != "off":
        args["policy"] = policy
    return tracer.span(name, cat="comm", args=args)


def _dispatch(op, x, axis_name, quantizable=True):
    """The single dispatch decision: (policy, participants, logical bytes).
    policy "off" means: trace the plain lax op (bitwise escape hatch)."""
    logical = _size_bytes(x)
    n = _participants(axis_name)
    cc = get_comm_compression()
    policy = cc.policy_for(op, axis_name, logical) if (quantizable and
                                                       n > 1) else "off"
    return cc, policy, n, logical


def all_reduce(x, op: str = ReduceOp.SUM, axis_name="data"):
    """lax.psum/pmax/pmin over a mesh axis. [COLLECTIVE]"""
    cc, policy, n, logical = _dispatch(
        "all_reduce", x, axis_name, quantizable=op in _SUMLIKE)
    if policy in ("int8", "fp8_block") and x.size % n == 0:
        from .quantized import (quantized_all_reduce,
                                quantized_all_reduce_wire_bytes)
        wire = quantized_all_reduce_wire_bytes(x.size, n, cc.block_size)
        _account("all_reduce", logical, wire, n, axis_name)
        with _comm_span("all_reduce", logical, wire, axis_name, n, policy):
            return quantized_all_reduce(x, axis_name, n, cc.block_size,
                                        policy, avg=op == ReduceOp.AVG)
    wire = _base_wire("all_reduce", logical, n)
    _account("all_reduce", logical, wire, n, axis_name)
    with _comm_span("all_reduce", logical, wire, axis_name, n):
        if op == ReduceOp.SUM:
            return lax.psum(x, axis_name)
        if op == ReduceOp.AVG:
            return lax.pmean(x, axis_name)
        if op == ReduceOp.MAX:
            return lax.pmax(x, axis_name)
        if op == ReduceOp.MIN:
            return lax.pmin(x, axis_name)
        if op == ReduceOp.PRODUCT:
            # EXACT product via all_gather + prod (an exp(psum(log)) trick
            # NaNs on x<=0 and loses integer precision past 2^24). PRODUCT
            # reduces are rare and small; the O(world) gather is the honest
            # primitive.
            return jnp.prod(lax.all_gather(x, axis_name), axis=0)
    raise ValueError(f"Unsupported reduce op {op}")


def all_gather(x, axis_name="data", axis: int = 0, tiled: bool = True):
    """Gather shards along `axis` from every member of the mesh axis.
    Policy int8/fp8_block ships the shard blockwise-quantized (the ZeRO-3
    param-gather wire, ZeRO++ qwZ)."""
    cc, policy, n, logical = _dispatch(
        "all_gather", x, axis_name, quantizable=tiled)
    if policy in ("int8", "fp8_block"):
        from .quantized import (quantized_all_gather,
                                quantized_all_gather_wire_bytes)
        wire = quantized_all_gather_wire_bytes(x.size, n, cc.block_size)
        _account("all_gather", logical, wire, n, axis_name)
        with _comm_span("all_gather", logical, wire, axis_name, n, policy):
            return quantized_all_gather(x, axis_name, axis, n,
                                        cc.block_size, policy)
    wire = _base_wire("all_gather", logical, n)
    _account("all_gather", logical, wire, n, axis_name)
    with _comm_span("all_gather", logical, wire, axis_name, n):
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name="data", axis: int = 0, op: str = ReduceOp.SUM):
    """psum_scatter: the ZeRO-2/3 gradient primitive
    (reference runtime/comm/coalesced_collectives.py:29).

    Policy int8/fp8_block quantizes the exchange; with
    ``comm_compression.hierarchical`` and a known (host, local) layout it
    becomes the two-level ZeRO++ qgZ path — full-precision reduce inside
    each host, quantized exchange across hosts — so only the compressed
    payload crosses the inter-host links."""
    cc, policy, n, logical = _dispatch(
        "reduce_scatter", x, axis_name, quantizable=op in _SUMLIKE)
    if policy in ("int8", "fp8_block") and x.shape[axis] % n == 0:
        from .quantized import (
            hierarchical_reduce_scatter,
            hierarchical_reduce_scatter_wire_bytes,
            quantized_reduce_scatter, quantized_reduce_scatter_wire_bytes)
        from ..parallel.topology import hierarchical_axis_groups
        avg = op == ReduceOp.AVG
        local = cc.local_members(n) if cc.hierarchical else 0
        if local:
            intra_g, inter_g = hierarchical_axis_groups(n, local)
            intra_b, inter_b = hierarchical_reduce_scatter_wire_bytes(
                x.size, n, local, cc.block_size, x.dtype.itemsize)
            wire = intra_b + inter_b
            _account("reduce_scatter", logical, wire, n, axis_name,
                     inter=inter_b)
            with _comm_span("reduce_scatter", logical, wire, axis_name, n,
                            policy):
                return hierarchical_reduce_scatter(
                    x, axis_name, axis, n, local, intra_g, inter_g,
                    cc.block_size, policy, avg)
        wire = quantized_reduce_scatter_wire_bytes(x.size, n, cc.block_size)
        _account("reduce_scatter", logical, wire, n, axis_name)
        with _comm_span("reduce_scatter", logical, wire, axis_name, n,
                        policy):
            return quantized_reduce_scatter(x, axis_name, axis, n,
                                            cc.block_size, policy, avg)
    wire = _base_wire("reduce_scatter", logical, n)
    _account("reduce_scatter", logical, wire, n, axis_name)
    with _comm_span("reduce_scatter", logical, wire, axis_name, n):
        out = lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                               tiled=True)
        if op == ReduceOp.AVG:
            out = out / axis_size(axis_name)
        return out


# ---------------------------------------------------------------- coalesced
# Bucketed forms for the overlap schedule (runtime/zero/overlap_schedule.py,
# reference runtime/comm/coalesced_collectives.py): a BUCKET of leaves moves
# in ONE collective. Accounting stays honest by construction — one op is
# recorded whose logical/wire bytes are the SUMS of the per-leaf models, so
# N buckets and N leaves log identical byte totals and differ only in the
# op count (the delta the flight recorder diffs between schedules). Under a
# quantized policy every leaf is encoded with exactly the per-leaf codec
# (same blocks, same scales) and only wire payloads are concatenated, so
# the dequantized values are bitwise identical to the per-leaf collectives.

def all_gather_coalesced(xs: Sequence, axis_name="data",
                         axes: Optional[Sequence[int]] = None):
    """Gather a bucket of shards in one collective; returns the per-leaf
    gathered tensors (each = ``all_gather(x, axis_name, axis)``)."""
    xs = list(xs)
    axes = [0] * len(xs) if axes is None else list(axes)
    logical = sum(_size_bytes(x) for x in xs)
    n = _participants(axis_name)
    cc = get_comm_compression()
    policy = cc.policy_for("all_gather", axis_name, logical) if n > 1 \
        else "off"
    if policy in ("int8", "fp8_block"):
        from .quantized import (quantized_all_gather_coalesced,
                                quantized_all_gather_coalesced_wire_bytes)
        wire = quantized_all_gather_coalesced_wire_bytes(
            [x.size for x in xs], n, cc.block_size)
        _account("all_gather", logical, wire, n, axis_name)
        with _comm_span("all_gather", logical, wire, axis_name, n, policy):
            return quantized_all_gather_coalesced(xs, axis_name, axes, n,
                                                  cc.block_size, policy)
    wire = sum(_base_wire("all_gather", _size_bytes(x), n) for x in xs)
    _account("all_gather", logical, wire, n, axis_name)
    with _comm_span("all_gather", logical, wire, axis_name, n):
        if n <= 1:
            return [lax.all_gather(x, axis_name, axis=a, tiled=True)
                    for x, a in zip(xs, axes)]
        flat = jnp.concatenate([x.reshape(-1) for x in xs])
        g = lax.all_gather(flat, axis_name)          # [n, total]
        outs = []
        off = 0
        for x, axis in zip(xs, axes):
            seg = g[:, off:off + x.size].reshape((n,) + x.shape)
            off += x.size
            out = jnp.moveaxis(seg, 0, axis)
            shape = list(x.shape)
            shape[axis] *= n
            outs.append(out.reshape(shape))
        return outs


def reduce_scatter_coalesced(xs: Sequence, axis_name="data",
                             axes: Optional[Sequence[int]] = None,
                             op: str = ReduceOp.SUM):
    """Reduce-scatter a bucket of full-size tensors in one collective;
    returns the per-leaf reduced shards (each =
    ``reduce_scatter(x, axis_name, axis, op)``)."""
    xs = list(xs)
    axes = [0] * len(xs) if axes is None else list(axes)
    logical = sum(_size_bytes(x) for x in xs)
    n = _participants(axis_name)
    cc = get_comm_compression()
    policy = cc.policy_for("reduce_scatter", axis_name, logical) \
        if (op in _SUMLIKE and n > 1) else "off"
    if policy in ("int8", "fp8_block") and \
            all(x.shape[a] % n == 0 for x, a in zip(xs, axes)):
        from .quantized import (
            hierarchical_reduce_scatter_coalesced,
            hierarchical_reduce_scatter_coalesced_wire_bytes,
            quantized_reduce_scatter_coalesced,
            quantized_reduce_scatter_coalesced_wire_bytes)
        from ..parallel.topology import hierarchical_axis_groups
        avg = op == ReduceOp.AVG
        sizes = [x.size for x in xs]
        local = cc.local_members(n) if cc.hierarchical else 0
        if local:
            intra_g, inter_g = hierarchical_axis_groups(n, local)
            intra_b, inter_b = \
                hierarchical_reduce_scatter_coalesced_wire_bytes(
                    sizes, n, local, cc.block_size, xs[0].dtype.itemsize)
            wire = intra_b + inter_b
            _account("reduce_scatter", logical, wire, n, axis_name,
                     inter=inter_b)
            with _comm_span("reduce_scatter", logical, wire, axis_name, n,
                            policy):
                return hierarchical_reduce_scatter_coalesced(
                    xs, axis_name, axes, n, local, intra_g, inter_g,
                    cc.block_size, policy, avg)
        wire = quantized_reduce_scatter_coalesced_wire_bytes(
            sizes, n, cc.block_size)
        _account("reduce_scatter", logical, wire, n, axis_name)
        with _comm_span("reduce_scatter", logical, wire, axis_name, n,
                        policy):
            return quantized_reduce_scatter_coalesced(
                xs, axis_name, axes, n, cc.block_size, policy, avg)
    wire = sum(_base_wire("reduce_scatter", _size_bytes(x), n) for x in xs)
    _account("reduce_scatter", logical, wire, n, axis_name)
    with _comm_span("reduce_scatter", logical, wire, axis_name, n):
        if n <= 1:
            outs = [lax.psum_scatter(x, axis_name, scatter_dimension=a,
                                     tiled=True) for x, a in zip(xs, axes)]
        else:
            rows = jnp.concatenate(
                [jnp.moveaxis(x, a, 0).reshape(n, -1)
                 for x, a in zip(xs, axes)], axis=1)       # [n, total//n]
            red = lax.psum_scatter(rows.reshape(-1), axis_name,
                                   scatter_dimension=0, tiled=True)
            outs = []
            off = 0
            for x, a in zip(xs, axes):
                sz = x.size // n
                rest = tuple(s for i, s in enumerate(x.shape) if i != a)
                seg = red[off:off + sz].reshape((x.shape[a] // n,) + rest)
                off += sz
                outs.append(jnp.moveaxis(seg, 0, a))
        if op == ReduceOp.AVG:
            outs = [o / axis_size(axis_name) for o in outs]
        return outs


def all_to_all(x, axis_name="expert", split_axis: int = 0, concat_axis: int = 0):
    """MoE dispatch/combine primitive (reference sharded_moe.py:90 _AllToAll)."""
    cc, policy, n, logical = _dispatch("all_to_all", x, axis_name)
    if policy in ("int8", "fp8_block") and x.shape[split_axis] % n == 0:
        from .quantized import (quantized_all_to_all,
                                quantized_all_to_all_wire_bytes)
        wire = quantized_all_to_all_wire_bytes(x.size, n, cc.block_size)
        _account("all_to_all", logical, wire, n, axis_name)
        with _comm_span("all_to_all", logical, wire, axis_name, n, policy):
            return quantized_all_to_all(x, axis_name, split_axis,
                                        concat_axis, n, cc.block_size,
                                        policy)
    wire = _base_wire("all_to_all", logical, n)
    _account("all_to_all", logical, wire, n, axis_name)
    with _comm_span("all_to_all", logical, wire, axis_name, n):
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def _broadcast_impl(x, src, axis_name, op_label):
    """Shared broadcast lowering (broadcast + scatter account under their
    own op names but put the same masked-psum ring on the wire)."""
    cc, policy, n, logical = _dispatch("broadcast", x, axis_name)
    if policy in ("int8", "fp8_block"):
        from .quantized import (quantized_broadcast,
                                quantized_broadcast_wire_bytes)
        wire = quantized_broadcast_wire_bytes(x.size, n, cc.block_size)
        _account(op_label, logical, wire, n, axis_name)
        with _comm_span(op_label, logical, wire, axis_name, n, policy):
            return quantized_broadcast(x, src, axis_name, n, cc.block_size,
                                       policy)
    wire = _base_wire("broadcast", logical, n)
    _account(op_label, logical, wire, n, axis_name)
    with _comm_span(op_label, logical, wire, axis_name, n):
        idx = lax.axis_index(axis_name)
        # where, not multiply: non-src members may hold NaN/inf placeholders
        # (torch broadcast ignores their buffers entirely)
        return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)),
                        axis_name)


def broadcast(x, src: int = 0, axis_name="data"):
    """src's value on every member, as psum of the masked value.

    XLA exposes no one-to-many collective inside SPMD programs (ppermute
    requires unique sources), so broadcast = all-reduce of a one-hot
    contribution. Cost: a ring all-reduce moves ~2·N per link regardless of
    world size — about 2x an optimal broadcast and CONSTANT in world size,
    which is why this is also how GSPMD itself materializes broadcasts."""
    return _broadcast_impl(x, src, axis_name, "broadcast")


def ppermute(x, perm: Sequence, axis_name="pipe"):
    """Point-to-point ring/pipeline exchange (reference pipe/p2p.py).
    Never compressed: pipeline activations are latency-bound single hops."""
    logical = _size_bytes(x)
    n = _participants(axis_name)
    wire = _base_wire("ppermute", logical, n)
    _account("ppermute", logical, wire, n, axis_name)
    with _comm_span("ppermute", logical, wire, axis_name, n):
        return lax.ppermute(x, axis_name, perm=perm)


def send_recv_next(x, axis_name="pipe"):
    """Shift +1 along axis (stage i → stage i+1), wrapping."""
    n = int(axis_size(axis_name))
    return ppermute(x, [(i, (i + 1) % n) for i in range(n)], axis_name)


def send_recv_prev(x, axis_name="pipe"):
    n = int(axis_size(axis_name))
    return ppermute(x, [(i, (i - 1) % n) for i in range(n)], axis_name)


# --------------------------------------------------------------------------
# Reference-name compatibility surface (deepspeed.comm parity). torch's
# in/out-tensor contracts are functional under XLA (return the result);
# rank-rooted ops are SUPERSETS — every member gets the root's result,
# which costs the same as the rooted op on a ring and is how GSPMD itself
# lowers them.
# --------------------------------------------------------------------------

def all_gather_into_tensor(x, axis_name="data", axis: int = 0):
    """reference comm.py all_gather_into_tensor (functional: returns the
    gathered tensor instead of writing into an output buffer)."""
    return all_gather(x, axis_name, axis=axis)


# reference allgather_fn dispatches to all_gather_into_tensor when the
# backend has it; XLA always does
allgather_fn = all_gather_into_tensor


def reduce_scatter_tensor(x, axis_name="data", axis: int = 0,
                          op: str = ReduceOp.SUM):
    return reduce_scatter(x, axis_name, axis=axis, op=op)


reduce_scatter_fn = reduce_scatter_tensor


def all_to_all_single(x, axis_name="expert", split_axis: int = 0,
                      concat_axis: int = 0):
    return all_to_all(x, axis_name, split_axis=split_axis,
                      concat_axis=concat_axis)


def reduce(x, dst: int = 0, axis_name="data", op: str = ReduceOp.SUM):
    """Rooted reduce; under SPMD every member receives the result (torch
    leaves non-dst outputs undefined — this is a superset)."""
    del dst
    return all_reduce(x, axis_name=axis_name, op=op)


def gather(x, dst: int = 0, axis_name="data", axis: int = 0):
    """Rooted gather; superset semantics (all members get the result)."""
    del dst
    return all_gather(x, axis_name, axis=axis)


def scatter(x, src: int = 0, axis_name="data", axis: int = 0):
    """Member i receives src's i-th shard along ``axis``. Non-src members'
    inputs are fully ignored (broadcast uses where-masking, so NaN/inf
    placeholders are fine). Accounted once, under its OWN op name with the
    broadcast lowering's wire cost (it used to inherit a "broadcast" entry
    at the full-tensor count, which hid its real identity from the
    before/after compression ratios)."""
    full = _broadcast_impl(x, src, axis_name, "scatter")
    n = _participants(axis_name)
    if full.shape[axis] % n:
        raise ValueError(f"scatter: dim {axis} ({full.shape[axis]}) must "
                         f"divide by axis size {n}")
    chunk = full.shape[axis] // n
    return lax.dynamic_slice_in_dim(full, lax.axis_index(axis_name) * chunk,
                                    chunk, axis)


def new_group(ranks):
    """Reference new_group returns a torch process group. XLA collectives
    are mesh-axis-scoped instead: build the mesh with the axes you need
    (parallel/topology.initialize_mesh) and pass the axis name to the
    collectives. The returned rank list works as the ``group`` argument of
    :func:`get_global_rank`."""
    logger.info("comm.new_group: XLA collectives are mesh-axis-scoped; "
                "use initialize_mesh axes for device collectives. "
                "Returning the rank list for host-plane rank mapping.")
    return list(ranks)


def get_global_rank(group=None, group_rank: int = 0) -> int:
    """Map a group-local rank to a global rank (reference comm.py
    get_global_rank). ``group``: a rank list from :func:`new_group`, or
    None for the world group."""
    if group is None:
        return group_rank
    return list(group)[group_rank]


def monitored_barrier(group=None, timeout=None, wait_all_ranks=False):
    """Barrier with logging (reference monitored_barrier; the hang
    diagnostics live in the launcher's failure detection here).
    ``timeout``: datetime.timedelta or seconds, forwarded to the barrier."""
    del group, wait_all_ranks
    logger.info(f"monitored_barrier enter (rank {get_rank()})")
    if timeout is None:
        barrier()
    else:
        seconds = timeout.total_seconds() if hasattr(
            timeout, "total_seconds") else float(timeout)
        barrier(timeout_ms=int(seconds * 1000))
    logger.info(f"monitored_barrier exit (rank {get_rank()})")


def _no_host_p2p(name, alternative):
    raise ValueError(
        f"comm.{name} is not supported on TPU: XLA owns collective "
        f"scheduling inside compiled programs, so host-driven "
        f"point-to-point has no mapping. Use {alternative} inside the "
        f"compiled step (see runtime/pipe/engine.py for the pipeline "
        f"exchange pattern).")


def isend(tensor, dst, **kw):
    _no_host_p2p("isend", "comm.ppermute / send_recv_next")


def irecv(tensor, src, **kw):
    _no_host_p2p("irecv", "comm.ppermute / send_recv_prev")


def send(tensor, dst, **kw):
    _no_host_p2p("send", "comm.ppermute / send_recv_next")


def recv(tensor, src, **kw):
    _no_host_p2p("recv", "comm.ppermute / send_recv_prev")


def has_all_gather_into_tensor() -> bool:
    return True


def has_reduce_scatter_tensor() -> bool:
    return True


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def axis_size(axis_name):
    # psum of a python 1 folds to the static axis size at trace time
    # (lax.axis_size only exists in newer jax releases)
    return lax.psum(1, axis_name)


def log_summary():
    cl = get_comms_logger()
    if cl is not None:
        cl.log_summary()
