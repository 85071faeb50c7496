"""Fixture: version-gated JAX surfaces used directly (jax-version-gated)."""
from jax.experimental import shard_map as sm
import jax


def build(devices):
    mesh = jax.make_mesh((2,), ("data",))
    axis_kind = jax.sharding.AxisType
    mapped = sm
    m2 = jax.sharding.Mesh(devices, ("data",), axis_types=(axis_kind,))
    return mesh, mapped, m2
