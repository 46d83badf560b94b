"""Seeded weights, drawn identically for the engine and the reference.

Every leaf is a function of (seed, leaf name, layer index) and nothing
else, computed with integer arithmetic and power-of-two scales only, so
the values do not depend on how XLA fuses the draw: the engine's stacked
tree (one jitted call, `lax.map` over layers) and the reference's
layer-at-a-time draws give the same bits.

Kinds:
  matrix  bf16 weight: a triangular 17-bit integer times 2**-e, with e
          chosen so the standard deviation is near 1/sqrt(fan_in); the
          value keeps more bits than bf16, so bf16 rounds it and an int8
          grid does not hold it exactly.
  embed   the same draw with standard deviation near 0.02.
  norm    RMSNorm scale in [0.75, 1.25], steps of 1/64.
  int8    a servable int8 weight: values triangular in [-127, 127] and a
          per-output-channel f32 scale (64 + k) / 64 * 2**-e, k in
          [-16, 16]: `values * scale` is exact in f32.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int) -> jax.Array:
    """A threefry key for any non-negative seed below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def leaf_key(root: jax.Array, name: str, layer) -> jax.Array:
    k = jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def _bits(key, shape):
    return jax.random.bits(key, shape, jnp.uint32)


def _exp2(e: int) -> float:
    return float(2.0 ** -e)


def matrix(key, shape, dtype=jnp.bfloat16, std: float | None = None):
    """(…, fan_in, fan_out) weight, see the module docstring."""
    b = _bits(key, shape)
    tri = ((b & 0xFFFF).astype(jnp.int32)
           + (b >> 16).astype(jnp.int32) - 0xFFFF)        # [-65535, 65535]
    tri_std = 65536 / math.sqrt(6)
    target = std if std is not None else 1 / math.sqrt(shape[-2])
    e = round(math.log2(tri_std / target))
    return (tri.astype(jnp.float32) * _exp2(e)).astype(dtype)


def norm(key, shape, dtype=jnp.bfloat16):
    k = (_bits(key, shape) % 33).astype(jnp.int32) - 16
    return (1.0 + k.astype(jnp.float32) / 64).astype(dtype)


def int8(key, shape):
    """(values int8 (…, in, out), scale f32 (…, 1, out))."""
    kv, ks = jax.random.split(key)
    b = _bits(kv, shape)
    vals = ((b & 0x7F).astype(jnp.int32)
            + ((b >> 8) & 0x7F).astype(jnp.int32) - 127).astype(jnp.int8)
    sshape = shape[:-2] + (1, shape[-1])
    k = (_bits(ks, sshape) % 33).astype(jnp.int32) - 16
    e = round(math.log2((127 / math.sqrt(6)) * math.sqrt(shape[-2])))
    scale = (64 + k).astype(jnp.float32) * _exp2(e + 6)
    return vals, scale


def draw(root, name: str, layer: int, shape, kind: str,
         dtype=jnp.bfloat16):
    """One leaf (one layer of a stacked leaf) by name; see `kind`s above."""
    key = leaf_key(root, name, layer)
    if kind == "matrix":
        return matrix(key, shape, dtype)
    if kind == "embed":
        return matrix(key, shape, dtype, std=0.02)
    if kind == "norm":
        return norm(key, shape, dtype)
    if kind == "int8":
        return int8(key, shape)
    raise ValueError(f"unknown weight kind {kind!r}")


def stacked(root, name: str, rows: int, shape, kind: str,
            dtype=jnp.bfloat16, draw_fn=draw, stride: int = 1,
            offset: int = 0):
    """`draw_fn` (by default `draw`) for layers offset, offset + stride,
    ... (`rows` of them), stacked on a leading axis; inside a jit this
    holds one layer's temporaries at a time."""
    layers = jnp.arange(rows, dtype=jnp.uint32)
    # stride 1 and offset 0 leave a bare iota: a one-entry layer pattern
    # draws with the same program as a plain range of layers
    if stride != 1:
        layers = layers * stride
    if offset:
        layers = layers + offset
    return jax.lax.map(
        lambda i: draw_fn(root, name, i, shape, kind, dtype), layers)
