"""Random weights from the seed, made on the device in one jitted call.

The configuration file lists ``init`` rules, first match wins, each
``[leaf name, distribution, a, b]`` matched against the last key of a
leaf's path:

* ``normal a``      -> a * N(0, 1)
* ``fan_in``        -> N(0, 1) / sqrt(shape[-2])
* ``uniform a b``   -> U(a, b)
* ``one_plus a``    -> 1 + a * N(0, 1)

The tree's structure (names and shapes) is the serving checkpoint layout;
the values are the benchmark's own, so the plain reference and the program
read the same weights and neither made them.
"""
from __future__ import annotations

import fnmatch
import math

import jax
import jax.numpy as jnp


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _rule(rules, name):
    for r in rules:
        if fnmatch.fnmatchcase(name, r[0]):
            return r
    raise KeyError(f"no init rule matches leaf {name!r}")


def make(shapes, rules, key):
    """``shapes``: a pytree of ShapeDtypeStruct -> the same tree of arrays."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, max(len(leaves), 1))
        out = []
        for k, (path, s) in zip(keys, leaves):
            kind, *args = _rule(rules, leaf_name(path))[1:]
            if kind == "normal":
                v = args[0] * jax.random.normal(k, s.shape, jnp.float32)
            elif kind == "fan_in":
                v = jax.random.normal(k, s.shape, jnp.float32) / math.sqrt(
                    s.shape[-2])
            elif kind == "uniform":
                v = jax.random.uniform(k, s.shape, jnp.float32, args[0],
                                       args[1])
            elif kind == "one_plus":
                v = 1.0 + args[0] * jax.random.normal(k, s.shape, jnp.float32)
            else:
                raise ValueError(f"unknown init {kind!r}")
            out.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key)


def key_of(seed: int, stream: int):
    """A raw uint32[2] PRNG key from the whole seed (any width) and a
    stream number."""
    seed = int(seed)
    hi = (seed >> 32) & 0xFFFFFFFF
    lo = seed & 0xFFFFFFFF
    return jax.random.fold_in(jnp.asarray([hi, lo], jnp.uint32), stream)
