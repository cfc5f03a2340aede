"""Seeded weights, made on the device in one jitted call, in the type
they are used in.

The benchmark makes the weights and hands them to the program; the
plain reference reads the same arrays.  Shapes come from
``jax.eval_shape`` of the model's own ``init`` (nothing is allocated),
values from ``--seed`` by the rule below, so no float32 tree of a
bf16-served model ever exists:

- ``scale`` (RMSNorm)          -> ones
- ``bias``                     -> normal, std 0.02
- ``embedding``                -> normal, std 1 (the residual stream
                                  starts at unit scale)
- any ``kernel`` [.., in, out] -> normal, std 1/sqrt(in)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, "key", None) or getattr(entry, "name", None)
        if isinstance(key, str) and key != "value":
            return key
    raise ValueError(f"unnamed leaf at {path}")


_CHUNK_ELEMS = 1 << 27      # largest piece generated at once


def _normal(key, shape, dtype, std: float):
    """``std * N(0, 1)`` in ``dtype``, generated in pieces along the
    leading axis so the generator's transients stay small beside the
    finished leaf (a stacked 7B-width FFN kernel is ~1e9 elements)."""
    size = math.prod(shape)
    lead = shape[0] if shape else 1
    pieces = next((n for n in range(1, lead + 1)
                   if lead % n == 0 and size // n <= _CHUNK_ELEMS), lead)
    if pieces <= 1:
        return (jax.random.normal(key, shape, jnp.float32) * std
                ).astype(dtype)
    sub = (lead // pieces,) + tuple(shape[1:])
    out = jax.lax.map(
        lambda k: (jax.random.normal(k, sub, jnp.float32) * std
                   ).astype(dtype),
        jax.random.split(key, pieces))
    return out.reshape(shape)


def _fill(key, name: str, shape, dtype):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "bias":
        return _normal(key, shape, dtype, 0.02)
    if name == "embedding":
        return _normal(key, shape, dtype, 1.0)
    if name == "kernel":
        return _normal(key, shape, dtype, 1.0 / math.sqrt(shape[-2]))
    raise ValueError(f"no fill rule for a leaf named {name!r}")


def make_params(shapes, seed: int, dtype):
    """A tree like ``shapes`` (plain dicts of ``ShapeDtypeStruct``),
    filled from ``seed`` in ``dtype`` by one jitted program.  Large
    leaves are generated in pieces (``_normal``), so the peak is the
    finished tree plus about a megabyte (compiler's figure at 7B
    widths: 8.7 GB of output, 1.1 MB of temporaries)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_fill(k, n, s.shape, dtype)
                  for k, n, (_, s) in zip(keys, names, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build)
    # A seed may exceed 32 signed bits; fold it into two words.
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return fn(key)


def plain_shapes(boxed):
    """``jax.eval_shape`` of a flax ``init`` -> plain nested dicts of
    ``ShapeDtypeStruct`` (partitioning boxes removed)."""
    import flax

    return flax.core.unfreeze(flax.core.meta.unbox(boxed))
