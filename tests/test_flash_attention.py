"""Flash-attention kernel family vs the dense oracle.

Three-way parity (Pallas interpret == XLA twin == ref) across
causal x window x GQA, block-skip geometry against brute force, the
model-level dispatch (flash config == chunked config, non-divisible
shapes fall back), and the ring variant on 8 forced host devices.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels.ref import flash_attention_ref
from repro.models import layers

TOL = dict(rtol=2e-5, atol=2e-5)  # fp32 accumulation everywhere


def _qkv(key, b, s, h, kv, hd, dtype=jnp.float32):
    kq, kk, kv_ = jax.random.split(key, 3)
    q = (0.3 * jax.random.normal(kq, (b, s, h, hd))).astype(dtype)
    k = (0.3 * jax.random.normal(kk, (b, s, kv, hd))).astype(dtype)
    v = (0.3 * jax.random.normal(kv_, (b, s, kv, hd))).astype(dtype)
    return q, k, v


# ------------------------------------------------------------ parity

@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 96), (False, 0), (False, 40),
])
@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (4, 1)])
def test_three_way_parity(causal, window, h, kv):
    q, k, v = _qkv(jax.random.PRNGKey(hash((causal, window, h, kv)) % 2**31),
                   2, 256, h, kv, 32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    xla = fa.flash_attention_xla(
        q, k, v, block_q=128, block_k=128, causal=causal, window=window
    )
    pal = fa.flash_attention_pallas(
        q, k, v, block_q=128, block_k=128, causal=causal, window=window,
        interpret=True,
    )
    np.testing.assert_allclose(xla, ref, **TOL)
    np.testing.assert_allclose(pal, ref, **TOL)


def test_parity_uneven_blocks_and_lse():
    # block_q != block_k, diagonal straddles block boundaries
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 384, 4, 2, 16)
    ref, ref_lse = flash_attention_ref(q, k, v, causal=True, with_lse=True)
    xla, xla_lse = fa.flash_attention_xla(
        q, k, v, block_q=128, block_k=64, causal=True, with_lse=True
    )
    pal, pal_lse = fa.flash_attention_pallas(
        q, k, v, block_q=128, block_k=64, causal=True, interpret=True,
        with_lse=True,
    )
    np.testing.assert_allclose(xla, ref, **TOL)
    np.testing.assert_allclose(pal, ref, **TOL)
    np.testing.assert_allclose(xla_lse, ref_lse, **TOL)
    np.testing.assert_allclose(pal_lse, ref_lse, **TOL)


def test_all_masked_blocks_skipped_and_correct():
    # window=64 over 512 tokens in 128-blocks: most KV blocks are fully
    # masked for most q blocks; some (q, k) block pairs are entirely
    # skipped, boundary rows inside visited blocks are partially masked.
    q, k, v = _qkv(jax.random.PRNGKey(11), 1, 512, 4, 2, 32)
    total = fa.visited_block_counts(
        4, block_q=128, block_k=128, nk=4, causal=True, window=64
    )
    assert total < 4 * (4 + 1) // 2  # strictly fewer than causal-only
    ref = flash_attention_ref(q, k, v, causal=True, window=64)
    xla = fa.flash_attention_xla(
        q, k, v, block_q=128, block_k=128, causal=True, window=64
    )
    pal = fa.flash_attention_pallas(
        q, k, v, block_q=128, block_k=128, causal=True, window=64,
        interpret=True,
    )
    np.testing.assert_allclose(xla, ref, **TOL)
    np.testing.assert_allclose(pal, ref, **TOL)


def test_bf16_inputs_fp32_accumulation():
    q, k, v = _qkv(jax.random.PRNGKey(13), 1, 256, 4, 2, 32, jnp.bfloat16)
    ref = flash_attention_ref(q, k, v, causal=True)
    out = fa.flash_attention_xla(q, k, v, block_q=128, block_k=128, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), rtol=2e-2, atol=2e-2
    )


# ------------------------------------------------------------ block geometry

def _brute_visited(qi, kj, *, block_q, block_k, causal, window):
    qp = np.arange(qi * block_q, (qi + 1) * block_q)
    kp = np.arange(kj * block_k, (kj + 1) * block_k)
    vis = np.ones((block_q, block_k), bool)
    if causal:
        vis &= kp[None, :] <= qp[:, None]
    if window:
        vis &= kp[None, :] > qp[:, None] - window
    return bool(vis.any())


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 64), (64, 128)])
@pytest.mark.parametrize("causal,window", [
    (True, 0), (True, 100), (True, 64), (False, 90),
])
def test_kv_block_range_matches_brute_force(block_q, block_k, causal, window):
    s = 512
    nq, nk = s // block_q, s // block_k
    for qi in range(nq):
        lo, hi = fa.kv_block_range(
            qi, block_q=block_q, block_k=block_k, nk=nk,
            causal=causal, window=window,
        )
        expect = [
            kj for kj in range(nk)
            if _brute_visited(qi, kj, block_q=block_q, block_k=block_k,
                              causal=causal, window=window)
        ]
        assert list(range(lo, hi)) == expect, (qi, lo, hi, expect)


def test_chunked_window_skip_compute_count_and_parity():
    # Satellite: causal_skip with window>0 must not scan chunks entirely
    # left of the window start. kv_block_range is the exact schedule the
    # skip path executes, so the count assertion IS the compute count.
    s, chunk, window = 2048, 128, 300
    nq = s // chunk
    visited = fa.visited_block_counts(
        nq, block_q=chunk, block_k=chunk, nk=nq, causal=True, window=window
    )
    causal_only = nq * (nq + 1) // 2
    # each q chunk sees at most ceil(window/chunk)+1 kv chunks
    per_q_cap = window // chunk + 2
    assert visited < causal_only
    assert visited <= nq * per_q_cap
    q, k, v = _qkv(jax.random.PRNGKey(17), 1, s, 4, 2, 16)
    full = layers.chunked_attention(
        q, k, v, chunk=chunk, causal=True, window=window, causal_skip=False
    )
    skip = layers.chunked_attention(
        q, k, v, chunk=chunk, causal=True, window=window, causal_skip=True
    )
    np.testing.assert_allclose(skip, full, **TOL)


def test_chunked_gqa_per_block_expansion_matches_dense():
    # Satellite: K/V stay in KV heads until each chunk is expanded inside
    # kv_step; numerics must still match the dense path exactly.
    q, k, v = _qkv(jax.random.PRNGKey(19), 2, 512, 8, 2, 16)
    dense = layers.dense_attention(q, k, v, causal=True, window=200)
    chunked = layers.chunked_attention(q, k, v, chunk=128, causal=True,
                                       window=200)
    np.testing.assert_allclose(chunked, dense, **TOL)


# ------------------------------------------------------------ model dispatch

def _tiny_cfg(**kw):
    from repro.models.config import ModelConfig

    base = dict(name="t", family="dense", n_layers=1, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab=64, chunk_size=128)
    base.update(kw)
    return ModelConfig(**base)


def _attn_out(cfg, s, key=0):
    from repro.models import model as model_mod

    p = model_mod.init_params(cfg, jax.random.PRNGKey(key))["layers"]
    lp = jax.tree.map(lambda a: a[0], p)["attn"]
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(key + 1), (1, s, cfg.d_model))
    o, _, _ = model_mod._self_attention(
        cfg, lp, x.astype(jnp.float32), causal=True, positions=jnp.arange(s)
    )
    return o


def test_model_dispatch_flash_matches_chunked():
    # s > DENSE_ATTN_MAX_SEQ and divisible: flash config must match the
    # chunked config bit-for-bit-ish (same fp32 online softmax).
    o_ch = _attn_out(_tiny_cfg(), 2560)
    o_fl = _attn_out(_tiny_cfg(attn_impl="flash"), 2560)
    np.testing.assert_allclose(o_fl, o_ch, **TOL)


def test_model_dispatch_flash_nondivisible_falls_back():
    # 2509 % 128 != 0: both configs take the dense fallback, identically.
    o_ch = _attn_out(_tiny_cfg(), 2509)
    o_fl = _attn_out(_tiny_cfg(attn_impl="flash"), 2509)
    np.testing.assert_allclose(o_fl, o_ch, rtol=0, atol=0)


def test_model_dispatch_flash_sliding_window():
    o_ch = _attn_out(_tiny_cfg(sliding_window=384), 2560)
    o_fl = _attn_out(_tiny_cfg(sliding_window=384, attn_impl="flash"), 2560)
    np.testing.assert_allclose(o_fl, o_ch, **TOL)


# ------------------------------------------------------------ ring

def test_merge_partials_equals_monolithic():
    # Splitting the keys into shards and merging partials must reproduce
    # single-pass flash — the exact invariant the ring rotation relies on.
    q, k, v = _qkv(jax.random.PRNGKey(23), 1, 256, 4, 2, 32)
    parts = []
    n = 4
    s_loc = 256 // n
    for i in range(n):
        sl = slice(i * s_loc, (i + 1) * s_loc)
        parts.append(fa._xla_partials(
            q, k[:, sl], v[:, sl], block_q=64, block_k=64, causal=True,
            window=0, q_offset=0, k_offset=i * s_loc,
        ))
    # fold in rotated order (as each device would: own shard first)
    acc = parts[2]
    for j in (3, 0, 1):
        acc = fa.merge_partials(acc, parts[j])
    out = (acc[0] / jnp.maximum(acc[2], 1e-30)[..., None]).astype(q.dtype)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, **TOL)


_RING_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from repro.kernels.flash_attention import ring_flash_attention, flash_attention_xla
from repro.kernels.ref import flash_attention_ref

mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
spec = P(None, "seq", None, None)
key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = 0.3 * jax.random.normal(kq, (1, 1024, 4, 32))
k = 0.3 * jax.random.normal(kk, (1, 1024, 2, 32))
v = 0.3 * jax.random.normal(kv, (1, 1024, 2, 32))
for window in (0, 200):
    fn = partial(ring_flash_attention, axis_name="seq", axis_size=8,
                 block_q=64, block_k=64, causal=True, window=window)
    ring = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))(q, k, v)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    err = float(jnp.max(jnp.abs(ring - ref)))
    assert err < 2e-5, (window, err)
    print("window", window, "err", err)
print("RING-OK")
"""


def test_ring_flash_subprocess_8_devices():
    """Full ppermute path on 8 forced host devices (subprocess because
    jax locks the device count at first init): ring over a seq-sharded
    1024-token input must match the dense oracle, causal and windowed."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=os.path.join(root, "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _RING_SCRIPT],
        capture_output=True, text=True, timeout=540, env=env, cwd=root,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "RING-OK" in proc.stdout


# ------------------------------------------------------------ hlo gate

def test_no_s2_scores_detects_dense_and_passes_flash():
    from repro.dist.hlo_analysis import no_s2_scores

    s = 2048
    q, k, v = _qkv(jax.random.PRNGKey(29), 1, s, 2, 1, 64)
    dense_hlo = jax.jit(
        lambda a, b, c: layers.dense_attention(a, b, c, causal=True)
    ).lower(q, k, v).compile().as_text()
    flash_hlo = jax.jit(
        lambda a, b, c: layers.flash_attention(
            a, b, c, block_q=256, block_k=256, causal=True
        )
    ).lower(q, k, v).compile().as_text()
    assert no_s2_scores(dense_hlo, s), "dense lowering must trip the gate"
    assert no_s2_scores(flash_hlo, s) == []


def test_no_s2_scores_sharded_unit():
    from repro.dist.hlo_analysis import no_s2_scores

    # synthetic per-device HLO: a (S/2, S) f32 tensor on a seq=2 mesh
    hlo = "ENTRY %e () -> f32[1] {\n  %x = f32[1024,2048]{1,0} dot()\n}"
    assert no_s2_scores(hlo, 2048, shards=2)
    assert no_s2_scores(hlo, 2048, shards=1) == []  # one full-length dim only
