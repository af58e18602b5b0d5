"""The ``repro.utils.compat`` mesh/shard_map wrappers: the defaults they
fill in (all-Auto mesh axes, ``check_vma=False``) and that meshes and
shard_maps built through them run end to end."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

import repro.utils.compat as compat


def test_axis_type_has_modes():
    """Every axis is Auto unless the caller names other modes."""
    n = jax.device_count()
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    mesh = compat.make_mesh((n,), ("data",),
                            axis_types=(AxisType.Explicit,))
    assert tuple(mesh.axis_types) == (AxisType.Explicit,)


def test_make_mesh_builds_real_mesh():
    n = jax.device_count()
    mesh = compat.make_mesh((n,), ("data",))
    assert tuple(mesh.axis_names) == ("data",)
    mesh = compat.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
    assert tuple(mesh.axis_names) == ("data",)


def test_make_mesh_devices_kwarg():
    devs = jax.devices()
    mesh = compat.make_mesh((len(devs),), ("data",), devices=devs)
    assert mesh.devices.size == len(devs)


def test_shard_map_executes():
    n = jax.device_count()
    mesh = compat.make_mesh((n,), ("data",))
    P = jax.sharding.PartitionSpec
    f = compat.shard_map(lambda x: x * 2.0, mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"))
    x = jnp.arange(n * 2, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x)), np.arange(n * 2) * 2.0)


def test_axis_size_inside_shard_map():
    n = jax.device_count()
    mesh = compat.make_mesh((n,), ("data",))
    P = jax.sharding.PartitionSpec
    f = compat.shard_map(lambda x: x * jax.lax.axis_size("data"),
                         mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), axis_names=("data",))
    x = jnp.ones((n,), jnp.float32)
    np.testing.assert_allclose(np.asarray(f(x)), np.full((n,), float(n)))
