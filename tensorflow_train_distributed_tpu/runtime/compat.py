"""The jax sharding entry points the package uses, under one import.

There is one installation (jax 0.9): these are direct re-exports, kept
in one module so call sites read ``compat.shard_map`` / ``compat.set_mesh``
the same way everywhere.
"""

import jax
from jax import set_mesh, shard_map  # noqa: F401  (re-exports)
from jax.lax import axis_size  # noqa: F401  (re-export)

get_abstract_mesh = jax.sharding.get_abstract_mesh


def abstract_mesh(axis_sizes, axis_names):
    """``jax.sharding.AbstractMesh`` from parallel size/name sequences."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))
