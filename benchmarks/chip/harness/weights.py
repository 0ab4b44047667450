"""Weights made from the seed, on the device, in one jitted call.

Each leaf is drawn by its path: the same seed and path give the same
values whether the leaf goes to the program or to the plain reference.
How a leaf is drawn (its rule) belongs to the configuration's reference
file (``init_rule(path, shape, config)``).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def leaf_paths(tree) -> dict:
    """{"a/b/0/c": leaf} for every leaf of a pytree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_str(p): x for p, x in flat}


def draw(key, path: str, shape, dtype, rule) -> jax.Array:
    """One leaf by its rule: ("normal", std) | ("ones",) | ("zeros",) |
    ("a_log", lo, hi): log of U(lo, hi) | ("dt_bias", lo, hi): inverse
    softplus of a step size drawn log-uniformly in [lo, hi] (Mamba-2's
    initialisation of A and dt)."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    kind = rule[0]
    if kind == "normal":
        x = rule[1] * jax.random.normal(k, shape, jnp.float32)
    elif kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, rule[1], rule[2]))
    elif kind == "dt_bias":
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(rule[2]) - math.log(rule[1])) + math.log(rule[1]))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init rule {rule!r} for {path}")
    return x.astype(dtype)


def make(shapes, key, rule_fn, config, out_shardings=None):
    """Every leaf of ``shapes`` (a pytree of ShapeDtypeStruct) drawn in one
    jitted call, placed by ``out_shardings`` when given."""

    def gen(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: draw(key, path_str(p), s.shape, s.dtype,
                              rule_fn(path_str(p), s.shape, config)),
            shapes)

    return jax.jit(gen, out_shardings=out_shardings)(key)
