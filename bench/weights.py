"""Random weights from the seed, made on the device in one jitted call.

A model module (``bench/models/<name>.py``) gives the pytree's layout as a
tree of shapes, the layout the program reads, built from the configuration's
sizes alone, so the reference and the program read the same arrays and
neither made them. Each leaf is drawn from its own key, the seed's key
folded with the crc32 of the leaf's path. Matrices are bfloat16 (the
published checkpoints store bfloat16); ConSmax's per-head beta and gamma
stay float32.

Distributions: an embedding ``table`` N(0, 1/d) (tied logits of order 1),
projections N(0, 1/fan_in), biases N(0, 0.05^2), RMSNorm gains 1 + N(0,
0.1^2) stored zero-centred, beta ~ U[0.5, 2.5] and gamma = 100 (the paper's
init).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf(key, path: str, shape: tuple):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "beta":
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 2.5)
    if name == "gamma":
        return jnp.full(shape, 100.0, jnp.float32)
    if name == "scale":
        std = 0.1
    elif name == "b":
        std = 0.05
    elif name == "table":
        std = shape[-1] ** -0.5
    elif path.endswith("o/w"):              # (n, h, dk, d): fan-in h * dk
        std = (shape[1] * shape[2]) ** -0.5
    else:                                   # (n, d_in, ...)
        std = shape[1] ** -0.5
    # drawn in bfloat16 directly: no float32 copy of a matrix is ever held
    return jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)


def _build(key, shapes, prefix=""):
    if isinstance(shapes, tuple):
        return _leaf(key, prefix, shapes)
    return {k: _build(key, v, f"{prefix}/{k}" if prefix else k)
            for k, v in shapes.items()}


def make(shapes: dict, seed_word: int):
    """The whole pytree of ``shapes`` from one key, in one jitted call on
    the default device. The key is the hardware generator's (``rbg``): the
    same seed gives the same weights on the same backend, without
    threefry's cost over billions of elements."""
    fn = jax.jit(lambda k: _build(k, shapes))
    return fn(jax.random.key(seed_word, impl="rbg"))
