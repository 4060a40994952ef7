"""Seeded weights, made by the benchmark and not by the program.

The model family's ``layout`` lists every leaf by path with its shape and
kind of initialisation; one leaf's values depend on (seed, path) alone.  The
program gets them in the type it serves them in, and the reference gets the
same values in float32, so both start from identical weights.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchlib.traffic import seed_key

SCALE = 0.02


def _leaf(key, shape, kind):
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "alog":            # Mamba's A_log: log of Uniform[1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * SCALE
    raise ValueError(f"unknown initialisation {kind!r}")


def weight_key(seed: int):
    return seed_key(seed, 3)


def make(layout: dict, base, dtype):
    """``{path: array}`` in ``dtype`` from ``weight_key(seed)`` (traceable;
    call it under jit with the key as an argument)."""
    return {path: _leaf(jax.random.fold_in(base, zlib.crc32(path.encode())),
                        shape, kind).astype(dtype)
            for path, (shape, kind) in layout.items()}


def path_of(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in keypath)


def to_tree(flat: dict, abstract):
    """Put ``{path: array}`` into the program's parameter tree, whose leaves
    must be exactly the layout's paths with the same shapes."""
    leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
    paths = [path_of(p) for p, _ in leaves]
    if sorted(paths) != sorted(flat):
        raise ValueError(f"program leaves {sorted(paths)} differ from the "
                         f"reference layout {sorted(flat)}")
    for (p, a), path in zip(leaves, paths):
        if tuple(a.shape) != tuple(flat[path].shape):
            raise ValueError(f"{path}: program shape {a.shape} differs from "
                             f"the reference layout {flat[path].shape}")
    return jax.tree_util.tree_unflatten(jax.tree.structure(abstract),
                                        [flat[p] for p in paths])


def from_tree(tree) -> dict:
    return {path_of(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
