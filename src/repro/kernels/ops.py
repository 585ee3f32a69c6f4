"""Jit'd public wrappers around the Pallas quantization kernels.

Handles: pytree flatten -> (M, 128) tile padding -> kernel -> unflatten.
``interpret=None`` resolves by platform (``sq.resolve_interpret``): compiled
Mosaic kernels on a TPU, the Pallas interpreter elsewhere.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import stochastic_quant as sq

Pytree = Any
LANES = sq.LANES


def pad_to_tiles(flat: jax.Array, block_m: int = sq.BLOCK_M) -> tuple[jax.Array, int]:
    """1-D -> (M, 128) with M a multiple of block_m. Returns (tiled, orig_len)."""
    n = flat.shape[0]
    tile = block_m * LANES
    padded = ((n + tile - 1) // tile) * tile
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, LANES), n


def flatten_pytree(tree: Pytree) -> tuple[jax.Array, Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    shapes = [(l.shape, l.dtype) for l in leaves]
    return flat, (treedef, shapes)


def unflatten_pytree(flat: jax.Array, meta) -> Pytree:
    treedef, shapes = meta
    leaves = []
    off = 0
    for shape, dtype in shapes:
        size = 1
        for d in shape:
            size *= d
        leaves.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnames=("q_bits", "interpret"))
def quantize_flat(
    key: jax.Array, flat: jax.Array, q_bits: int, *, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """1-D fp32 -> (idx u8 (M,128), signs u8, scale fp32). Stochastic.
    The caller keeps the original length (``flat.shape[0]``) for unpadding."""
    tiled, _ = pad_to_tiles(flat)
    scale = jnp.max(jnp.abs(flat))
    rbits = jax.random.bits(key, tiled.shape, jnp.uint32)
    idx, signs = sq.quantize(tiled, rbits, scale, q_bits, interpret=interpret)
    return idx, signs, scale


@functools.partial(jax.jit, static_argnames=("q_bits", "n", "interpret"))
def dequantize_flat(
    idx: jax.Array, signs: jax.Array, scale: jax.Array, q_bits: int, n: int,
    *, interpret: bool | None = None,
) -> jax.Array:
    out = sq.dequantize(idx, signs, scale, q_bits, interpret=interpret)
    return out.reshape(-1)[:n]


def quantize_pytree_kernel(
    key: jax.Array, tree: Pytree, q_bits: int, *, interpret: bool | None = None
) -> tuple[Pytree, jax.Array]:
    """Drop-in replacement for repro.core.quantization.quantize_pytree that
    routes through the Pallas kernels (quantize -> wire -> dequantize)."""
    flat, meta = flatten_pytree(tree)
    n = flat.shape[0]
    idx, signs, scale = quantize_flat(key, flat, q_bits, interpret=interpret)
    deq = dequantize_flat(idx, signs, scale, q_bits, n, interpret=interpret)
    return unflatten_pytree(deq, meta), scale


@functools.partial(jax.jit, static_argnames=("q_bits", "interpret"))
def aggregate_uploads(
    idx: jax.Array, signs: jax.Array, scales: jax.Array, weights: jax.Array,
    q_bits, *, interpret: bool | None = None,
) -> jax.Array:
    """Server-side fused dequant + weighted sum (paper eq. 2).
    idx/signs: (K, M, 128); returns (M*128,) fp32 flat aggregate."""
    out = sq.aggregate(idx, signs, scales, weights, q_bits, interpret=interpret)
    return out.reshape(-1)
