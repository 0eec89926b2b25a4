"""Collective wrapper tests over an 8-device CPU mesh — the "distributed
tests without a cluster" pattern (SURVEY §4 implication)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel import initialize_mesh


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


def test_all_reduce_sum(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.all_reduce(v, axis_name="data"),
              P("data"), P("data"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))


def test_all_reduce_max(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.all_reduce(v, op=dist.ReduceOp.MAX,
                                              axis_name="data"),
              P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, 7.0))


def test_all_gather(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.all_gather(v, axis_name="data"),
              P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x)), np.arange(8.0))


def test_reduce_scatter(mesh):
    x = jnp.ones((8, 8))
    f = _smap(mesh, lambda v: dist.reduce_scatter(v, axis_name="data"),
              P(None, None), P("data", None))
    out = f(x)
    assert out.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(out), 8 * np.ones((8, 8)))


def test_all_to_all(mesh):
    # each member holds a row of 8 elems; all_to_all transposes ownership
    x = jnp.arange(64.0).reshape(8, 8)
    f = _smap(mesh, lambda v: dist.all_to_all(v, axis_name="data",
                                              split_axis=1, concat_axis=1),
              P("data", None), P("data", None))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.arange(64.0).reshape(8, 8).T)


def test_broadcast(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.broadcast(v, src=3, axis_name="data"),
              P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, 3.0))


def test_ppermute_shift(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.send_recv_next(v, axis_name="data"),
              P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(f(x)), np.roll(np.arange(8.0), 1))


def test_host_api():
    assert dist.get_rank() == 0
    assert dist.get_world_size() == 1
    dist.barrier()
    assert dist.broadcast_object({"a": 1}) == {"a": 1}


def test_comms_logger_records(mesh):
    from deepspeed_tpu.comm import get_comms_logger
    cl = get_comms_logger()
    cl.enabled = True
    cl.reset()
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.all_reduce(v, axis_name="data"),
              P("data"), P("data"))
    f(x)
    assert "all_reduce" in cl.comms_dict
    cl.enabled = False


# ------------------- reference-name compatibility surface (round 5)

def test_compat_gather_scatter_reduce(mesh):
    x = jnp.arange(8.0)
    # gather: every member holds the full tensor (superset of rooted)
    g = _smap(mesh, lambda v: dist.gather(v, dst=0, axis_name="data"),
              P("data"), P())
    np.testing.assert_allclose(np.asarray(g(x))[:8], np.arange(8.0))
    # scatter: member i gets src's shard i == original sharding round-trip
    s = _smap(mesh, lambda v: dist.scatter(
        dist.gather(v, axis_name="data"), src=0, axis_name="data"),
        P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(s(x)), np.asarray(x))
    # reduce: superset of rooted reduce (everyone gets the sum)
    r = _smap(mesh, lambda v: dist.reduce(v, dst=0, axis_name="data"),
              P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(r(x)), np.full(8, x.sum()))


def test_compat_tensor_aliases(mesh):
    x = jnp.arange(8.0)
    f = _smap(mesh, lambda v: dist.all_gather_into_tensor(
        v, axis_name="data"), P("data"), P())
    np.testing.assert_allclose(np.asarray(f(x))[:8], np.arange(8.0))
    rs = _smap(mesh, lambda v: dist.reduce_scatter_tensor(
        v, axis_name="data"), P(), P("data"))
    out = rs(jnp.ones(8))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))
    assert dist.has_all_gather_into_tensor()
    assert dist.has_reduce_scatter_tensor()
    assert dist.allgather_fn is dist.all_gather_into_tensor


def test_compat_group_rank_mapping():
    grp = dist.new_group([3, 5, 7])
    assert dist.get_global_rank(grp, 1) == 5
    assert dist.get_global_rank(None, 2) == 2


def test_host_p2p_raises_with_guidance():
    for name in ("isend", "irecv", "send", "recv"):
        with pytest.raises(ValueError, match="ppermute"):
            getattr(dist, name)(jnp.zeros(2), 0)


def test_scatter_ignores_nan_placeholders(mesh):
    """Non-src members may pass NaN placeholders (torch semantics)."""
    def body(v):
        idx = dist.axis_index("data")
        src_val = jnp.arange(8.0)
        placeholder = jnp.full((8,), jnp.nan)
        x = jnp.where(idx == 0, src_val, placeholder)
        return dist.scatter(x, src=0, axis_name="data")
    f = _smap(mesh, body, P("data"), P("data"))
    out = np.asarray(f(jnp.zeros(8)))
    assert np.isfinite(out).all(), out
    np.testing.assert_allclose(out, np.arange(8.0))
