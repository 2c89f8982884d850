"""Seeded bf16 weights, drawn on the device.

Every leaf is N(0, 0.02) drawn in float32 and rounded once to bfloat16, the
type the model is served in.  A leaf's key comes from the seed and the
leaf's path in the parameter tree (``"['blocks']['layer0']['mixer']['wq']"``);
a leaf of the scanned block stack (leading axis = layer) draws each layer
from ``fold_in(path_key, layer)``.  So the reference, which names its own
weights by the same paths, redraws one layer at a time and gets the very
same bf16 values without holding the model or importing the program.
"""
from __future__ import annotations

import zlib
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

SCALE = 0.02


def path_key(seed: int, path: str):
    """The key of one leaf: any non-negative seed (more than 32 bits
    allowed) and a stable hash of the leaf's path."""
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(path.encode()))


def _one(key, shape: Tuple[int, ...]):
    return (jax.random.normal(key, shape, jnp.float32) * SCALE
            ).astype(jnp.bfloat16)


def _stacked(key, shape: Tuple[int, ...]):
    keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(
        jnp.arange(shape[0]))
    return jax.vmap(lambda k: _one(k, shape[1:]))(keys)


@partial(jax.jit, static_argnums=(1,))
def _draw_all(keys, plan):
    return [(_stacked if stacked else _one)(k, shape)
            for k, (shape, stacked) in zip(keys, plan)]


def draw_params(cfg, seed: int):
    """The program's whole bf16 parameter tree for ``cfg``, in one jitted
    call.  Leaves under ``['blocks']`` are the scanned layer stack."""
    from repro.models import init_params

    shapes, _ = init_params(cfg, abstract=True, dtype=jnp.bfloat16)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    plan = tuple((tuple(s.shape), p.startswith("['blocks']"))
                 for p, (_, s) in zip(paths, flat))
    keys = jnp.stack([path_key(seed, p) for p in paths])
    return jax.tree_util.tree_unflatten(treedef, _draw_all(keys, plan))


@partial(jax.jit, static_argnums=(1,))
def _draw_leaf(key, shape: Tuple[int, ...]):
    return _one(key, shape)


@partial(jax.jit, static_argnums=(1,))
def _draw_layer(key, shape: Tuple[int, ...], layer):
    return _one(jax.random.fold_in(key, layer), shape)


def draw_leaf(seed: int, path: str, shape: Tuple[int, ...], layer: int = -1):
    """One leaf of ``shape`` (for a stacked leaf: one layer, ``shape``
    without the layer axis) as bf16, equal bit for bit to that part of
    :func:`draw_params`."""
    key = path_key(seed, path)
    if layer < 0:
        return _draw_leaf(key, tuple(shape))
    return _draw_layer(key, tuple(shape), layer)
