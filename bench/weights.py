"""Seeded weights for a benchmark run, made by the benchmark and not by the
program under test.

The program's parameter tree gives only the names, shapes and dtypes
(`jax.eval_shape` of its init). Every leaf is drawn from its own key,
folded from the run seed and the leaf's path, and every stacked layer from
a key folded once more with its layer index. So one jitted call makes the
whole tree on the device, and the reference can draw any single layer
again, alone, and get the same values. Stacked layers, and the rows of a
large matrix in chunks, are drawn one after another (`lax.map`), so that
drawing needs little memory beside the weights themselves.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def leaf_kind(name: str) -> str:
    last = name.split("/")[-2:]
    if last[-1] == "scale":
        return "scale"
    if last[-1] in ("bias", "b", "bq", "bk", "bv"):
        return "bias"
    if last[0] in ("tok_embed", "lm_head", "pos_embed"):
        return "embed"
    return "matrix"


def draw(key, shape, dtype, kind):
    """One leaf (or one layer of a stacked leaf)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        x = 1.0 + 0.1 * z
    elif kind in ("bias", "embed"):
        x = 0.02 * z
    else:
        x = z / jnp.sqrt(jnp.float32(shape[-2]))
    return x.astype(dtype)


def leaf_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


CHUNK_ELEMS = 1 << 24     # larger unstacked leaves are drawn in 16 chunks


def draw_leaf(key, shape, dtype, kind):
    """An unstacked leaf: in 16 row chunks when it is large."""
    n = 16
    size = 1
    for s in shape:
        size *= s
    if size < CHUNK_ELEMS or len(shape) < 2 or shape[0] % n:
        return draw(key, shape, dtype, kind)
    rows = (shape[0] // n,) + tuple(shape[1:])
    part = jax.lax.map(lambda i: draw(jax.random.fold_in(key, i), rows,
                                      dtype, kind), jnp.arange(n))
    return part.reshape(shape)


def layer_leaf(key, name, shape, dtype, layer):
    """Layer `layer` of the stacked leaf `name` (shape without the layer
    axis), as `make` draws it from `seed_key(seed)`."""
    k = jax.random.fold_in(leaf_key(key, name), layer)
    return draw(k, shape, dtype, leaf_kind(name))


def unstacked_leaf(key, name, shape, dtype):
    """The unstacked leaf `name`, as `make` draws it from `seed_key(seed)`."""
    return draw_leaf(leaf_key(key, name), shape, dtype, leaf_kind(name))


def make(shapes, seed, *, stacked=("layers",)):
    """Weights for the tree of ShapeDtypeStructs `shapes`, in one jitted
    call. Leaves under a `stacked` top-level key carry the layer axis
    first."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    names = [leaf_name(p) for p, _ in flat]

    def build(key):
        out = []
        for name, (_, sd) in zip(names, flat):
            k, kind = leaf_key(key, name), leaf_kind(name)
            if name.split("/")[0] in stacked:
                out.append(jax.lax.map(lambda l, k=k, sd=sd, kind=kind: draw(
                    jax.random.fold_in(k, l), sd.shape[1:], sd.dtype, kind),
                    jnp.arange(sd.shape[0])))
            else:
                out.append(draw_leaf(k, sd.shape, sd.dtype, kind))
        return out

    leaves = jax.jit(build)(seed_key(seed))
    return jax.tree_util.tree_unflatten(tree, leaves)
