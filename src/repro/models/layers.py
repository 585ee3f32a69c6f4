"""Shared neural-net layers: norms, RoPE, GQA attention, SwiGLU.

Conventions:
  * params are plain nested dicts of jnp arrays, fp32 masters;
  * forward casts to ``cfg.activation_dtype`` (bf16 by default) and keeps
    softmax/normalization accumulations in fp32;
  * attention comes in three flavours:
      - ``dense_attention``: full (S x S) scores; fine for short seq;
      - ``chunked_attention``: online-softmax over KV chunks, O(S*chunk)
        memory — the production path for 32k prefill (TPU-native flash
        adaptation: block sizes picked for VMEM, not SM occupancy);
      - ``decode_attention``: one query against a (ring-buffer) KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.activations import shard_act
from repro.kernels import flash_attention as _flash
from repro.kernels.flash_attention import kv_block_range  # noqa: F401 (re-export)

# ----------------------------------------------------------------- init

def dense_init(key: jax.Array, shape: tuple[int, ...], scale: float = 0.02) -> jax.Array:
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32))


def embed_init(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    return dense_init(key, shape, scale=1.0 / (shape[-1] ** 0.5))


# ----------------------------------------------------------------- norms

def rmsnorm_params(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dtype)


def layernorm_params(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return out.astype(dtype)


# ----------------------------------------------------------------- RoPE

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                  # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention

def attention_params(key: jax.Array, d: int, n_heads: int, n_kv: int, hd: int) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, n_heads, hd)),
        "wk": dense_init(k2, (d, n_kv, hd)),
        "wv": dense_init(k3, (d, n_kv, hd)),
        "wo": dense_init(k4, (n_heads, hd, d)),
    }


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) by repeating each KV head G times."""
    b, s, n_kv, hd = k.shape
    g = n_heads // n_kv
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=2)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0,
    q_positions: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-materialization attention. q: (B,S,H,hd), k/v: (B,T,KV,hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scale = hd ** -0.5
    scores = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    qp = q_positions if q_positions is not None else jnp.arange(s)
    kp = k_positions if k_positions is not None else jnp.arange(t)
    mask = jnp.ones((s, t), bool)
    if causal:
        mask = mask & (kp[None, :] <= qp[:, None])
    if window:
        mask = mask & (kp[None, :] > qp[:, None] - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def chunked_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    chunk: int, causal: bool = True, window: int = 0,
    causal_skip: bool = False,
) -> jax.Array:
    """Online-softmax attention, O(S * chunk) live memory.

    ``causal_skip``: unroll the query-chunk loop in python and scan each
    query chunk only over its causal KV prefix — removes the ~2x wasted
    masked compute of the rectangular baseline (a §Perf optimization).
    """
    b, s, h, hd = q.shape
    assert s % chunk == 0, (s, chunk)
    nq = s // chunk
    kvh = k.shape[2]
    scale = hd ** -0.5
    # K/V stay in their KV heads here; each chunk is expanded to H heads
    # inside kv_step, so GQA live memory is O(chunk * H), not O(S * H).
    kc = k.reshape(b, nq, chunk, kvh, hd)
    vc = v.reshape(b, nq, chunk, kvh, hd)
    qc = q.reshape(b, nq, chunk, h, hd)

    def q_chunk_body(
        qi: int, q_blk: jax.Array, kv_lo: int, kv_hi: int
    ) -> jax.Array:
        """Process one query chunk against kv chunks [kv_lo, kv_hi)."""
        q_pos = qi * chunk + jnp.arange(chunk)

        def kv_step(carry, kj):
            m, l, acc = carry
            k_blk = _expand_kv(
                jax.lax.dynamic_index_in_dim(kc, kj, axis=1, keepdims=False), h
            )
            v_blk = _expand_kv(
                jax.lax.dynamic_index_in_dim(vc, kj, axis=1, keepdims=False), h
            )
            k_pos = kj * chunk + jnp.arange(chunk)
            sc = jnp.einsum(
                "bshd,bthd->bhst", q_blk, k_blk, preferred_element_type=jnp.float32
            ) * scale
            mask = jnp.ones((chunk, chunk), bool)
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            if window:
                mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
            sc = jnp.where(mask[None, None], sc, -1e30)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhst,bthd->bhsd", p, v_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, chunk), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), jnp.arange(kv_lo, kv_hi)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 2, 1, 3)  # (b, chunk, h, hd)

    if causal_skip and (causal or window):
        # scan only the KV chunks with any visible (q, k) pair: chunks
        # past the causal diagonal AND chunks entirely left of the
        # sliding-window start are never visited (kv_block_range is the
        # single source of truth for this geometry — shared with the
        # flash kernels and the masked-compute-count test).
        outs = [
            q_chunk_body(
                qi,
                qc[:, qi],
                *kv_block_range(
                    qi, block_q=chunk, block_k=chunk, nk=nq,
                    causal=causal, window=window,
                ),
            )
            for qi in range(nq)
        ]
        return jnp.concatenate(outs, axis=1).astype(q.dtype)

    def outer(qi):
        return q_chunk_body(qi, jax.lax.dynamic_index_in_dim(qc, qi, 1, False), 0, nq)

    out = jax.lax.map(outer, jnp.arange(nq))  # (nq, b, chunk, h, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    block_q: int = 512, block_k: int = 512,
    causal: bool = True, window: int = 0,
    impl: str = "xla", interpret: bool | None = None,
) -> jax.Array:
    """Blockwise flash attention (see ``kernels/flash_attention.py``).

    Same signature family as ``chunked_attention`` but never touches an
    (S x T) score tensor and never expands K/V to H heads: GQA grouping
    and causal/window block skipping happen inside the block schedule.
    ``impl='pallas'`` selects the TPU kernel (interpret-mode off-TPU),
    ``impl='xla'`` its executable twin. S/T must divide block_q/block_k —
    the model dispatch falls back to dense/chunked otherwise.
    """
    return _flash.flash_attention(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        window=window, impl=impl, interpret=interpret,
    )


def decode_attention(
    q: jax.Array,            # (B, 1, H, hd) — already RoPE'd at abs position
    k_cache: jax.Array,      # (B, Lc, KV, hd) — RoPE'd at write time
    v_cache: jax.Array,      # (B, Lc, KV, hd)
    slot_positions: jax.Array,  # (Lc,) absolute positions, -1 = empty
) -> jax.Array:
    b, _one, h, hd = q.shape
    k = _expand_kv(k_cache, h)
    v = _expand_kv(v_cache, h)
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bthd->bhqt", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    valid = slot_positions >= 0
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bthd->bqhd", probs, v)


# ----------------------------------------------------------------- MLP

def swiglu_params(key: jax.Array, d: int, f: int, n_layers: int = 1) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d, f)),
        "wu": dense_init(k2, (d, f)),
        "wd": dense_init(k3, (f, d), scale=0.02 / max(1.0, (2 * n_layers) ** 0.5)),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    dtype = x.dtype
    g = shard_act(jnp.einsum("bsd,df->bsf", x, params["wg"].astype(dtype)), "bsf")
    u = shard_act(jnp.einsum("bsd,df->bsf", x, params["wu"].astype(dtype)), "bsf")
    out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params["wd"].astype(dtype))
    return shard_act(out, "btd")


# ----------------------------------------------------------------- embedding

def embedding_params(key: jax.Array, vocab: int, d: int) -> dict:
    return {"table": embed_init(key, (vocab, d))}


def embed(params: dict, tokens: jax.Array, dtype) -> jax.Array:
    return params["table"].astype(dtype)[tokens]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in fp32 for a stable softmax-cross-entropy."""
    return jnp.einsum(
        "bsd,vd->bsv", x, params["table"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
