"""Random weights from the run's seed, made on the device in one jitted call.

The program states the tree it takes (names, shapes, dtypes) through
``jax.eval_shape`` of its own ``init``; the values are the benchmark's, so
the reference (``bench/reference.py``) reads weights the program did not
make.  Every matrix is normal with standard deviation ``fan_in ** -0.5``,
the embedding table unit normal, and norm scales ``1 + 0.1 * normal`` so a
norm that is skipped or misapplied shows.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    hi, lo = np.random.SeedSequence(int(seed) % 2 ** 63).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def _path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _leaf(key, name: str, shape, dtype):
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif leaf == "table":
        x = jax.random.normal(key, shape, jnp.float32)
    elif "router" in name:
        x = jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
    elif len(shape) >= 2:
        x = jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5
    else:
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def make_params(shapes, seed: int):
    """Weights with the structure of ``shapes`` (a tree of
    ``ShapeDtypeStruct``), drawn from ``seed`` on the default device."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_name(p) for p, _ in flat]

    def make(key):
        out = []
        for name, (_, s) in zip(names, flat):
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            out.append(_leaf(k, name, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(seed_key(seed))
