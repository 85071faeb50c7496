"""One home for the JAX surfaces that changed across releases.

Written for the installed JAX, 0.9.0 (see docs/distributed.md). The
surfaces below moved or were renamed in earlier releases, so no other
module may reference them directly — enforced symbol-by-symbol by
tests/test_compat.py::test_no_version_gated_jax_symbols_outside_compat:

  * mesh construction — ``jax.make_mesh`` with explicit ``axis_types``
    (``jax.sharding.AxisType``); the repo assumes Auto axes throughout
  * ``jax.shard_map`` and its replication-check kwarg ``check_vma``

The rest are thin shared helpers (pytree ops, typed PRNG keys, jaxpr source
provenance, profiler annotations, the persistent compilation cache) kept
here so an upgrade touches one file.
"""
from __future__ import annotations

import os

import jax

__all__ = [
    "make_mesh",
    "shard_map",
    "ensure_host_devices",
    "enable_compilation_cache",
    "prng_key",
    "key_dtype",
    "tree_map",
    "tree_leaves",
    "tree_flatten",
    "tree_unflatten",
    "tree_structure",
    "tree_map_with_path",
    "tree_flatten_with_path",
    "register_dataclass",
    "user_frames",
    "named_scope",
    "trace_annotation",
]


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------


def make_mesh(shape, axes, *, devices=None):
    """Build a ``jax.sharding.Mesh`` whose axes are all Auto (the GSPMD
    behaviour the whole repo assumes)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def ensure_host_devices(n: int) -> None:
    """Request ``n`` fake host-platform XLA devices.

    Must run before the JAX backend initializes (i.e. before any computation
    or device query). A no-op when a device count is already forced — callers
    that layer (conftest forces 8 for the suite; dryrun asks for 512) get the
    outermost request, and should check ``jax.device_count()`` for what they
    actually received.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


# <checkout>/.jax_cache (gitignored): src/repro/compat.py -> checkout root
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here. Otherwise the cache lives at :data:`CACHE_DIR`, one
    fixed directory inside the checkout: the directory is part of the cache
    key, so a path made from a temp name, a pid or the time would never hit.
    JAX's own minimum compile time (about 1 s) decides what is cached.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default: our
    bodies mix psum/psum_scatter over axis subsets in ways the checker
    rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check)


# ---------------------------------------------------------------------------
# PRNG keys
# ---------------------------------------------------------------------------


def prng_key(seed: int) -> jax.Array:
    """Typed PRNG key."""
    return jax.random.key(seed)


def key_dtype():
    """dtype of a step key — for ShapeDtypeStructs fed to ``jit.lower``."""
    return prng_key(0).dtype


def user_frames(source_info):
    """User-code (file_name, start_line) frames of one jaxpr equation.

    ``eqn.source_info`` provenance lives in ``jax._src.source_info_util``,
    which is internal; every consumer (the sketch-coverage analyzer) goes
    through here.
    """
    from jax._src import source_info_util as siu

    return [(f.file_name, f.start_line)
            for f in siu.user_frames(source_info.traceback)]


# ---------------------------------------------------------------------------
# profiler / naming annotations (consumed by repro.obs.tracing)
# ---------------------------------------------------------------------------


def named_scope(name: str):
    """``jax.named_scope``: a tracing-time op-naming aid (HLO / jaxpr dumps)."""
    return jax.named_scope(name)


def trace_annotation(name: str):
    """``jax.profiler.TraceAnnotation``: host-side spans opened through it
    appear on the timeline of a ``jax.profiler`` capture (negligible cost
    outside an active profiling session)."""
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# pytree ops
# ---------------------------------------------------------------------------

tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure
tree_map_with_path = jax.tree_util.tree_map_with_path
tree_flatten_with_path = jax.tree_util.tree_flatten_with_path
register_dataclass = jax.tree_util.register_dataclass
