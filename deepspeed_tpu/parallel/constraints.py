"""Sharding-constraint helpers.

``maybe_constraint`` applies ``lax.with_sharding_constraint`` only when a mesh
context is active AND the named axes exist in it — so model code can annotate
intent unconditionally (the GSPMD analogue of the reference's explicit
collectives) and still run un-meshed (single-device tests, numerics oracles).
Axes of size 1 are kept (no-op for XLA, zero cost).
"""

from jax import lax
# private: jax 0.9 has no public reader for the legacy ``with mesh:`` context
# this framework uses (jax.sharding.get_mesh sees only jax.set_mesh)
from jax._src.mesh import thread_resources
from jax.sharding import PartitionSpec as P


def active_mesh():
    """The context mesh, or None."""
    mesh = thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


def _filter_spec(spec: P, axis_names) -> P:
    out = []
    for s in spec:
        if s is None:
            out.append(None)
        elif isinstance(s, (tuple, list)):
            kept = tuple(a for a in s if a in axis_names)
            out.append(kept if kept else None)
        else:
            out.append(s if s in axis_names else None)
    return P(*out)


def maybe_constraint(x, *spec):
    """with_sharding_constraint(x, P(*spec)) if a mesh is active, else x."""
    mesh = active_mesh()
    if mesh is None:
        return x
    return lax.with_sharding_constraint(x, _filter_spec(P(*spec),
                                                        set(mesh.axis_names)))
