"""Pallas TPU kernels: stochastic quantize / dequantize / fused aggregate.

TPU adaptation of the paper's eq. 4 quantizer (Sec. II-B):
  * the model vector is tiled (M, 128) — lane dim 128 matches the VPU;
  * blocks of (BLOCK_M, 128) live in VMEM; the fp32 range scalar rides in
    SMEM as a whole (1, 1) array, and the aggregate's per-client
    coefficients as one whole (Kp,) SMEM row;
  * stochastic rounding consumes pre-generated uint32 entropy (kept as an
    explicit input so the kernel is deterministic and oracle-testable);
  * magnitude indexes store as uint8 (q <= 8 covers the paper's operating
    regime, Fig. 5) and signs as a separate uint8 plane — exactly the
    paper's wire format ``Z*q + Z + 32`` bits, so the aggregation kernel
    (eq. 2) can consume the packed uplink directly;
  * Mosaic has no 8-bit compares and no direct uint <-> float casts, so
    every u8/u32 load, compare and store goes through int32 (exact for
    these ranges: results are bit-identical to the direct casts).

``interpret=None`` (the default) resolves by platform through
:func:`resolve_interpret`: compiled Mosaic kernels on a TPU, the Pallas
interpreter elsewhere (CPU tests).

The fused aggregate kernel folds K clients' dequantize + weighted sum:
out = sum_k w_k * sign_k * idx_k * (scale_k / levels_k). The client axis
is a grid dimension (BLOCK_K clients per step, partial sum carried in VMEM
across the k grid steps via output-block revisiting), so one kernel covers
any K — from the paper's C = 8 uplink to a full 1024-client fleet —
with constant VMEM footprint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.profile import scope as _profile_scope

BLOCK_M = 256
LANES = 128


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret`` wins; None means interpret mode off a TPU
    and compiled kernels on one."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _to_f32(v: jax.Array) -> jax.Array:
    """Unsigned plane / entropy word -> fp32 through int32 (the values
    here are < 2^24, so the result is exactly the direct cast)."""
    return v.astype(jnp.int32).astype(jnp.float32)


def _quant_kernel(x_ref, rbits_ref, scale_ref, idx_ref, sign_ref, *, q_bits: int):
    levels = jnp.float32(2.0**q_bits - 1.0)
    scale = scale_ref[0, 0]
    safe = jnp.where(scale > 0, scale, 1.0)
    x = x_ref[...].astype(jnp.float32)
    scaled = jnp.minimum(jnp.abs(x) * (levels / safe), levels)
    lower = jnp.floor(scaled)
    frac = scaled - lower
    u = _to_f32(rbits_ref[...] >> jnp.uint32(8)) * jnp.float32(2.0**-24)
    idx = jnp.minimum(lower + (u < frac).astype(jnp.float32), levels)
    idx_ref[...] = idx.astype(jnp.int32).astype(jnp.uint8)
    sign_ref[...] = (x < 0).astype(jnp.int32).astype(jnp.uint8)


def quantize(
    x: jax.Array, rbits: jax.Array, scale: jax.Array, q_bits: int,
    *, interpret: bool | None = None, block_m: int = BLOCK_M,
) -> tuple[jax.Array, jax.Array]:
    """x, rbits: (M, 128); scale: () fp32. Returns (idx u8, signs u8)."""
    # same wire-format bound as core.quantization.quantize_indices: the u8
    # index plane holds levels up to 2^8 - 1, a larger static q would
    # silently wrap the magnitude index
    if not 1 <= int(q_bits) <= 8:
        raise ValueError(
            f"quantize: q_bits={q_bits} does not fit the uint8 index plane "
            "(max level 2^q - 1 needs 1 <= q <= 8)"
        )
    m, lanes = x.shape
    assert lanes == LANES and m % block_m == 0, (x.shape, block_m)
    grid = (m // block_m,)
    kernel = functools.partial(_quant_kernel, q_bits=q_bits)
    with _profile_scope("pallas_quantize"):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=[
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((m, LANES), jnp.uint8),
                jax.ShapeDtypeStruct((m, LANES), jnp.uint8),
            ],
            interpret=resolve_interpret(interpret),
        )(x, rbits, scale.reshape(1, 1))


def _dequant_kernel(idx_ref, sign_ref, scale_ref, out_ref, *, q_bits: int):
    levels = jnp.float32(2.0**q_bits - 1.0)
    scale = scale_ref[0, 0]
    # range sanity: a corrupted index plane (bit flips on the wire) must
    # dequantize into [-scale, scale], never scale * 255 / levels — clamp to
    # the level count. A no-op for every index a quantizer can emit.
    mag = jnp.minimum(_to_f32(idx_ref[...]), levels) * (scale / levels)
    out_ref[...] = jnp.where(sign_ref[...].astype(jnp.int32) > 0, -mag, mag)


def dequantize(
    idx: jax.Array, signs: jax.Array, scale: jax.Array, q_bits: int,
    *, interpret: bool | None = None, block_m: int = BLOCK_M,
) -> jax.Array:
    m, lanes = idx.shape
    assert lanes == LANES, (
        f"dequantize expects lane-tiled (M, {LANES}) input, got idx {idx.shape}"
    )
    assert m % block_m == 0, (
        f"dequantize: M={m} must be a multiple of block_m={block_m}"
    )
    assert signs.shape == idx.shape, (
        f"dequantize: signs {signs.shape} must match idx {idx.shape}"
    )
    kernel = functools.partial(_dequant_kernel, q_bits=q_bits)
    with _profile_scope("pallas_dequantize"):
        return pl.pallas_call(
            kernel,
            grid=(m // block_m,),
            in_specs=[
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
                pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, LANES), jnp.float32),
            interpret=resolve_interpret(interpret),
        )(idx, signs, scale.reshape(1, 1))


def plane_in_range(idx: jax.Array, q_bits: jax.Array) -> jax.Array:
    """Per-client wire-plane range screen: ``max(idx) <= 2^q - 1``.

    ``idx`` is (K, ...) index planes (any trailing layout), ``q_bits`` a
    scalar or (K,) per-client level (traced ok). A valid quantizer output
    always passes; an out-of-range index means the plane was corrupted in
    flight (sim fault injection, or a real wire) and the slot should be
    screened out of the aggregate rather than clamped silently. Note the
    check is vacuous at q = 8 for a u8 plane (every byte is a legal index)
    — pair it with a sign-plane check and a finite-range check, as
    ``repro.sim.engine.screen_slots`` does.
    """
    qf = jnp.maximum(jnp.asarray(q_bits), 1).astype(jnp.float32)
    levels = 2.0**qf - 1.0
    flat = idx.reshape(idx.shape[0], -1).astype(jnp.float32)
    return jnp.max(flat, axis=1) <= levels


def _aggregate_kernel(idx_ref, sign_ref, coef_ref, out_ref, *, block_k: int):
    """coef[k] = weights[k] * scales[k] / levels[k] precomputed on host —
    the kernel is a pure weighted magnitude sum.

    The client axis is a grid dimension: grid = (m_blocks, k_blocks) with k
    minor, so for each output tile the partial sum stays resident in VMEM
    while the k steps stream BLOCK_K clients' planes at a time through it
    (output-block revisiting). Any K works with constant VMEM footprint —
    no static unroll of the whole fleet. The (Kp,) coefficient row sits
    whole in SMEM; step kb reads its clients' scalars at kb*block_k + j.
    """
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    acc = out_ref[...]
    for j in range(block_k):  # static unroll of the TILE only
        mag = _to_f32(idx_ref[j])
        val = jnp.where(sign_ref[j].astype(jnp.int32) > 0, -mag, mag)
        acc = acc + coef_ref[kb * block_k + j] * val
    out_ref[...] = acc


BLOCK_K = 8


def aggregate(
    idx: jax.Array,      # (K, M, 128) uint8
    signs: jax.Array,    # (K, M, 128) uint8
    scales: jax.Array,   # (K,) fp32
    weights: jax.Array,  # (K,) fp32
    q_bits,              # int or (K,) array of per-client levels
    *, interpret: bool | None = None, block_m: int = BLOCK_M,
    block_k: int = BLOCK_K,
) -> jax.Array:
    """Fused dequantize + eq.-2 weighted sum over K wire payloads.

    K and M are padded internally (zero-coefficient clients / zero rows), so
    any active-set size and any lane-tiled length work; the output keeps the
    caller's (M, 128) shape.
    """
    k, m, lanes = idx.shape
    assert lanes == LANES, (
        f"aggregate expects lane-tiled (K, M, {LANES}) input, got idx {idx.shape}"
    )
    assert signs.shape == idx.shape, (
        f"aggregate: signs {signs.shape} must match idx {idx.shape}"
    )
    scales = jnp.asarray(scales, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    assert scales.shape == (k,), (
        f"aggregate: scales must be one fp32 range per client, shape ({k},), "
        f"got {scales.shape}"
    )
    assert weights.shape == (k,), (
        f"aggregate: weights must be one eq.-2 weight per client, shape ({k},), "
        f"got {weights.shape}"
    )
    qb_in = jnp.asarray(q_bits)
    assert qb_in.ndim == 0 or qb_in.shape == (k,), (
        f"aggregate: q_bits must be a scalar or per-client ({k},), "
        f"got shape {qb_in.shape}"
    )
    qb = jnp.broadcast_to(qb_in.astype(jnp.float32), (k,))
    levels = 2.0**qb - 1.0
    coef = (weights * scales / levels).astype(jnp.float32)

    k_pad = (-k) % block_k
    m_pad = (-m) % block_m
    if k_pad or m_pad:
        idx = jnp.pad(idx, ((0, k_pad), (0, m_pad), (0, 0)))
        signs = jnp.pad(signs, ((0, k_pad), (0, m_pad), (0, 0)))
        coef = jnp.pad(coef, (0, k_pad))  # zero coef: padding contributes 0
    kp, mp = k + k_pad, m + m_pad

    kernel = functools.partial(_aggregate_kernel, block_k=block_k)
    with _profile_scope("pallas_aggregate"):
        out = pl.pallas_call(
            kernel,
            grid=(mp // block_m, kp // block_k),
            in_specs=[
                pl.BlockSpec((block_k, block_m, LANES),
                             lambda i, kb: (kb, i, 0)),
                pl.BlockSpec((block_k, block_m, LANES),
                             lambda i, kb: (kb, i, 0)),
                # the whole coefficient row in SMEM: a (1, block_k) VMEM
                # window breaks Mosaic's (8, 128) block rule for most K
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((block_m, LANES), lambda i, kb: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((mp, LANES), jnp.float32),
            interpret=resolve_interpret(interpret),
        )(idx, signs, coef)
    return out[:m]
