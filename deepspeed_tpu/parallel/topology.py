"""Process/device topology over a JAX device mesh.

TPU-native re-design of the reference topology layer
(deepspeed/runtime/pipe/topology.py:12 ``ProcessTopology``, :251
``PipelineParallelGrid``; deepspeed/utils/groups.py). Where the reference
builds NCCL process groups from a cartesian rank grid, here the grid IS a
``jax.sharding.Mesh`` with named axes, and "process groups" are mesh-axis
subsets consumed by pjit/shard_map — XLA lowers collectives onto ICI/DCN.

Canonical axis order (outer → inner, chosen so that the innermost axes map to
the fastest ICI links and the data axes are contiguous for ZeRO sharding):

    ('pipe', 'data', 'expert', 'seq', 'model')

- ``data`` × ``expert`` together form the reference's data-parallel world
  (groups.py:108: ep_size divides dp_world; expert-dp = dp/ep).
- ZeRO shards optimizer state / grads / params over ('data', 'expert').
- MoE all-to-all dispatch runs over 'expert'.
- Sequence parallelism (ring attention / Ulysses) runs over 'seq'.
- Tensor parallelism runs over 'model' (innermost → fastest ICI).
"""

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
MESH_AXES = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)

# The composite data-parallel sharding axes used by ZeRO.
DP_AXES = (DATA_AXIS, EXPERT_AXIS)


def hierarchical_axis_groups(axis_size: int, devices_per_host: int):
    """Split a flat mesh axis into (host, local) subaxes for two-level
    collectives (the ZeRO++ hierarchical exchange, comm/quantized.py).

    Returns ``(intra_groups, inter_groups)`` as ``axis_index_groups`` lists
    for the ``jax.lax`` collectives: intra groups hold the ``devices_per_
    host`` consecutive members that share a host (host-major member order —
    exactly how ``initialize_mesh`` lays out ``jax.devices()``, which is
    process-major); inter groups hold the members at the same local offset
    across hosts. ``(None, None)`` when no meaningful split exists
    (devices_per_host <= 1, >= axis_size, or not a divisor)."""
    local = int(devices_per_host)
    if local <= 1 or local >= axis_size or axis_size % local:
        return None, None
    hosts = axis_size // local
    intra = [[h * local + l for l in range(local)] for h in range(hosts)]
    inter = [[h * local + l for h in range(hosts)] for l in range(local)]
    return intra, inter


def default_devices():
    """Device list for mesh construction: the default backend's devices, or
    the (virtual) CPU devices when DSTPU_ACCELERATOR=cpu asks for them on a
    host whose default backend is the TPU."""
    import os
    if os.environ.get("DSTPU_ACCELERATOR") == "cpu":
        return jax.devices("cpu")
    return jax.devices()


def on_tpu() -> bool:
    """Will a computation traced now run on a TPU? The one place that asks:
    the devices the active mesh was built from, or, outside a mesh context,
    the devices a mesh would be built from (``default_devices``)."""
    from .constraints import active_mesh
    mesh = active_mesh()
    devices = mesh.devices.flat if mesh is not None else default_devices()
    return devices[0].platform == "tpu"


class ProcessTopology:
    """Named-axis cartesian topology; API shaped after the reference
    ProcessTopology (topology.py:12) but backed by numpy index math over
    device ids rather than rank lists + NCCL groups."""

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        assert len(axes) == len(dims)
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)
        self._grid = np.arange(int(np.prod(self.dims))).reshape(self.dims)

    def get_rank(self, **coords) -> int:
        idx = tuple(coords[a] for a in self.axes)
        return int(self._grid[idx])

    def get_coord(self, rank: int):
        pos = np.argwhere(self._grid == rank)[0]
        return dict(zip(self.axes, (int(p) for p in pos)))

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_axis_names(self) -> List[str]:
        return list(self.axes)

    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """All groups of ranks that vary only along `axis`
        (reference topology.py:131)."""
        ax = self.axes.index(axis)
        moved = np.moveaxis(self._grid, ax, -1).reshape(-1, self.dims[ax])
        return [list(map(int, row)) for row in moved]

    def filter_match(self, **coords) -> List[int]:
        ranks = []
        for r in range(self.world_size()):
            c = self.get_coord(r)
            if all(c[k] == v for k, v in coords.items()):
                ranks.append(r)
        return ranks


class DeviceMeshManager:
    """Owns the global ``jax.sharding.Mesh`` and the named-sharding helpers.

    The single place the rest of the framework asks "how is X sharded".
    Replaces reference groups.py globals (_WORLD_GROUP/_EXPERT_PARALLEL_GROUP/
    ...) with mesh-axis bookkeeping.
    """

    def __init__(self,
                 pp: int = 1,
                 dp: Optional[int] = None,
                 ep: int = 1,
                 sp: int = 1,
                 tp: int = 1,
                 devices=None):
        devices = devices if devices is not None else default_devices()
        n = len(devices)
        fixed = pp * ep * sp * tp
        if dp is None:
            if n % fixed != 0:
                raise ValueError(
                    f"{n} devices not divisible by pp*ep*sp*tp={fixed}")
            dp = n // fixed
        total = pp * dp * ep * sp * tp
        if total != n:
            raise ValueError(
                f"mesh {pp}x{dp}x{ep}x{sp}x{tp}={total} != device count {n}")
        self.topology = ProcessTopology(MESH_AXES, (pp, dp, ep, sp, tp))
        dev_array = np.asarray(devices).reshape(pp, dp, ep, sp, tp)
        self.mesh = Mesh(dev_array, MESH_AXES)
        self.pp, self.dp, self.ep, self.sp, self.tp = pp, dp, ep, sp, tp

    # ---- sizes ----
    @property
    def dp_world_size(self) -> int:
        """Full data-parallel degree (data × expert), reference groups.py."""
        return self.dp * self.ep

    def axis_size(self, axis: str) -> int:
        return self.topology.get_dim(axis)

    # ---- shardings ----
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_spec(self, shard_seq: bool = True) -> P:
        """Batch dim over the dp axes; sequence dim over 'seq' if enabled."""
        if self.sp > 1 and shard_seq:
            return P(DP_AXES, SEQ_AXIS)
        return P(DP_AXES)

    def batch_sharding(self, shard_seq: bool = True) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(shard_seq))

    def data_host_groups(self, devices_per_host: int = 0):
        """(intra, inter) ``axis_index_groups`` splitting the 'data' axis
        into (host, local) subaxes for hierarchical collectives;
        ``devices_per_host`` 0 = this process's local device count."""
        if devices_per_host == 0:
            devices_per_host = jax.local_device_count()
        return hierarchical_axis_groups(self.axis_size(DATA_AXIS),
                                        devices_per_host)

    def __enter__(self):
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)


_MESH_MANAGER: Optional[DeviceMeshManager] = None


def initialize_mesh(pp=1, dp=None, ep=1, sp=1, tp=1, devices=None) -> DeviceMeshManager:
    """Create (or replace) the global mesh. Analogue of groups.initialize
    (deepspeed/utils/groups.py:46)."""
    global _MESH_MANAGER
    _MESH_MANAGER = DeviceMeshManager(pp=pp, dp=dp, ep=ep, sp=sp, tp=tp, devices=devices)
    return _MESH_MANAGER


def get_mesh_manager() -> DeviceMeshManager:
    global _MESH_MANAGER
    if _MESH_MANAGER is None:
        _MESH_MANAGER = DeviceMeshManager()
    return _MESH_MANAGER


def reset_mesh():
    global _MESH_MANAGER
    _MESH_MANAGER = None
