"""Blockwise flash attention: Pallas TPU kernel, XLA twin, ring variant.

Three implementations of ONE block schedule (same math, same masking,
same online-softmax bookkeeping), kept in lockstep by the parity tests
against ``ref.flash_attention_ref``:

``flash_attention_pallas``
    The TPU kernel. Grid ``(batch*heads, q_blocks, kv_blocks)`` with the
    KV axis minor, so for each (head, q-block) the ``m``/``l``/``acc``
    partials stay resident in VMEM across the KV steps while the KV
    blocks stream through — the same output-block-revisiting recipe as
    ``stochastic_quant._aggregate_kernel``. GQA is folded into the K/V
    BlockSpec index maps (query head ``h`` reads KV head ``h // g``), so
    the full (B, T, H, hd) expanded K/V of ``_expand_kv`` is never
    materialized. Causal / sliding-window masking is decided at BLOCK
    level first: a fully-masked KV block is predicated out with
    ``pl.when`` (no compute is issued for it), and only diagonal /
    window-edge blocks pay the elementwise mask.

``flash_attention_xla``
    The same block schedule in plain jnp (python q-block loop, lax.scan
    over the visited KV range) — the executable path on the CPU
    container and the lowering path for the dry-run gates. Supports
    *traced* ``q_offset``/``k_offset`` so the ring variant can reuse it
    per shard; with static offsets the fully-masked KV blocks are
    sliced out of the scan range entirely (never visited).

``ring_flash_attention``
    Sequence-parallel flash for use inside ``shard_map``: every device
    keeps its local Q shard, and the K/V shards rotate around the
    ``seq`` mesh axis via ``lax.ppermute`` (neighbor-local transfers
    only — no all-gather of the KV window). Per-step partials
    ``(acc, m, l)`` merge by the standard logsumexp combine, so the
    result is bit-comparable to single-device flash up to fp32
    reassociation.

Online-softmax invariants (every implementation):
  m_new = max(m, rowmax(s));  p = exp(s - m_new) masked to 0
  corr  = exp(m - m_new);     l_new = l * corr + rowsum(p)
  acc_new = acc * corr + p @ v;  out = acc / max(l, eps)
A fully-masked row keeps (m, l, acc) = (-1e30, 0, 0) — the masked
``p`` (not just masked scores) is what makes that exact, because
``exp(-1e30 - (-1e30)) = 1`` would otherwise poison ``l``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.stochastic_quant import resolve_interpret
from repro.obs.profile import scope as _profile_scope

NEG_INF = -1e30  # finite, matching dense_attention (no inf - inf NaNs)
DEFAULT_BLOCK = 512


# ------------------------------------------------------------ block ranges

def kv_block_range(
    qi: int, *, block_q: int, block_k: int, nk: int,
    causal: bool, window: int, q_offset: int = 0, k_offset: int = 0,
) -> tuple[int, int]:
    """Half-open KV-block range ``[lo, hi)`` visible to q-block ``qi``.

    Static-offset form of the masking geometry shared by every
    implementation (and by ``layers.chunked_attention``'s skip path):
    a KV block is visited iff it contains ANY (q, k) pair with
    ``k <= q`` (causal) and ``k > q - window`` (window > 0). Also the
    unit under test for the masked-compute-count satellite.
    """
    q_first = q_offset + qi * block_q
    q_last = q_first + block_q - 1
    lo, hi = 0, nk
    if causal:
        # last visible k position is q_last
        hi = min(nk, (q_last - k_offset) // block_k + 1)
    if window:
        # first visible k position is q_first - window + 1
        lo = max(0, (q_first - window + 1 - k_offset) // block_k)
    return (lo, max(lo, hi))


def visited_block_counts(
    nq: int, *, block_q: int, block_k: int, nk: int,
    causal: bool, window: int,
) -> int:
    """Total KV blocks visited across all q blocks (test/bench helper)."""
    return sum(
        hi - lo
        for lo, hi in (
            kv_block_range(qi, block_q=block_q, block_k=block_k, nk=nk,
                           causal=causal, window=window)
            for qi in range(nq)
        )
    )


# ------------------------------------------------------------ Pallas kernel

def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
    block_q: int, block_k: int, nk: int, causal: bool, window: int,
    scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    q_first = i * block_q
    q_last = q_first + block_q - 1
    k_first = j * block_k
    k_last = k_first + block_k - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    # Block-level skip: a KV block with no visible (q, k) pair issues no
    # compute at all (the diagonal/window-edge blocks pay the mask).
    visit = jnp.bool_(True)
    if causal:
        visit = visit & (k_first <= q_last)
    if window:
        visit = visit & (k_last > q_first - window)

    @pl.when(visit)
    def _step():
        q = q_ref[0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # (bq, bk)
        # elementwise mask (only diagonal/window-edge blocks actually
        # mix masked and unmasked pairs, but the predicate depends on
        # program_id, so the where() runs on every visited block — cheap
        # next to the two matmuls)
        if causal or window:
            q_pos = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask = mask & (k_pos <= q_pos)
            if window:
                mask = mask & (k_pos > q_pos - window)
            s = jnp.where(mask, s, NEG_INF)
        else:
            mask = None
        m_prev = m_ref[0]                           # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)             # see module docstring
        corr = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * corr + p.sum(axis=1)
        o_ref[0] = o_ref[0] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[0] = m_new

    # Final KV step for this q block: normalize in place. With causal
    # masking the diagonal block IS the last visited one, so rows never
    # see another contribution after the divide.
    if causal:
        j_hi = jnp.minimum(nk - 1, (i * block_q + block_q - 1) // block_k)
    else:
        j_hi = nk - 1

    @pl.when(j == j_hi)
    def _finalize():
        l = l_ref[0]
        o_ref[0] = o_ref[0] / jnp.maximum(l, 1e-30)[:, None]


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
    causal: bool = True, window: int = 0,
    interpret: bool | None = None, with_lse: bool = False,
):
    """q: (B, S, H, hd); k/v: (B, T, KV, hd); H a multiple of KV.

    Returns (B, S, H, hd) in q.dtype (plus fp32 lse (B, S, H) when
    ``with_lse``). S/T must divide block_q/block_k — callers fall back
    to ``chunked_attention`` for non-divisible shapes (model dispatch).
    """
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    g = h // kvh
    nq, nk = s // block_q, t // block_k
    scale = hd ** -0.5

    # head-major flattening: program b' = batch * H + head
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, t, hd)

    def kv_row(bh):
        # GQA inside the kernel: query head bh%H reads KV head (bh%H)//g
        return (bh // h) * kvh + (bh % h) // g

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, window=window, scale=scale,
    )
    with _profile_scope("pallas_flash_attention"):
        o, m, l = pl.pallas_call(
            kernel,
            grid=(b * h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec(
                    (1, block_k, hd), lambda bh, i, j: (kv_row(bh), j, 0)
                ),
                pl.BlockSpec(
                    (1, block_k, hd), lambda bh, i, j: (kv_row(bh), j, 0)
                ),
            ],
            out_specs=[
                # index maps ignore j: the output block is revisited
                # across the KV steps (partials resident in VMEM)
                pl.BlockSpec((1, block_q, hd), lambda bh, i, j: (bh, i, 0)),
                pl.BlockSpec((1, block_q), lambda bh, i, j: (bh, i)),
                pl.BlockSpec((1, block_q), lambda bh, i, j: (bh, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s, hd), jnp.float32),
                jax.ShapeDtypeStruct((b * h, s), jnp.float32),
                jax.ShapeDtypeStruct((b * h, s), jnp.float32),
            ],
            interpret=resolve_interpret(interpret),
        )(qf, kf, vf)
    out = o.reshape(b, h, s, hd).transpose(0, 2, 1, 3).astype(q.dtype)
    if with_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(b, h, s)
        return out, lse.transpose(0, 2, 1)
    return out


# ------------------------------------------------------------ XLA twin

def _paired_causal_partials(q, k, v, *, block):
    """Causal-only fast path for ``_xla_partials``: fold the triangle of
    visited blocks into uniform rectangles.

    With ``block_q == block_k`` the causal schedule visits blocks
    ``0..qi`` for q-block ``qi`` — q rows ``r`` and ``nq-1-r`` together
    own exactly ``nq-1`` interior (fully-visible, maskless) blocks plus
    their two diagonal blocks. So the whole triangle runs as ONE
    ``lax.map`` over nq/2 row pairs — a single compiled body instead of
    nq python-unrolled scans (whose per-loop overhead was costing more
    than the masking it saved) — with a ``lax.cond`` routing each of the
    nq-1 interior steps to whichever row of the pair still has blocks
    left, and the two diagonal blocks as direct masked steps (they share
    one relative-position mask). Returns the same unnormalized
    ``(acc, m, l)`` contract as ``_xla_partials``.
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    nq = s // block
    scale = hd ** -0.5

    # GQA by ROW FOLDING, not repetition: the g query heads sharing a KV
    # head become g*block rows of one (b, kvh)-batched matmul against
    # the un-expanded K/V block — zero K/V copies per step and a larger
    # (better-blocked) matmul. Row r of a folded q block is query head
    # gi = r // block at position r % block.
    qt = (q.reshape(b, nq, block, kvh, g, hd)
           .transpose(0, 1, 3, 4, 2, 5)
           .reshape(b, nq, kvh, g * block, hd))
    kt = k.reshape(b, nq, block, kvh, hd).transpose(0, 3, 1, 2, 4)
    vt = v.reshape(b, nq, block, kvh, hd).transpose(0, 3, 1, 2, 4)
    diag_mask = jnp.tile(
        jnp.arange(block)[None, :] <= jnp.arange(block)[:, None], (g, 1))

    def _step(q_blk, kj, state, mask=None):
        m, l, acc = state                            # (b, kvh, g*bq[, hd])
        k_blk = jax.lax.dynamic_index_in_dim(kt, kj, 2, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vt, kj, 2, keepdims=False)
        sc = jnp.einsum(
            "bKsd,bKtd->bKst", q_blk, k_blk,
            preferred_element_type=jnp.float32,
        ) * scale                                    # (b, kvh, g*bq, bk)
        if mask is not None:
            sc = jnp.where(mask[None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        if mask is not None:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        return (
            m_new,
            l * corr + p.sum(axis=-1),
            acc * corr[..., None] + jnp.einsum(
                "bKst,bKtd->bKsd", p, v_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ),
        )

    def pair_body(i_lo):
        i_hi = nq - 1 - i_lo
        q_lo = jax.lax.dynamic_index_in_dim(qt, i_lo, 1, keepdims=False)
        q_hi = jax.lax.dynamic_index_in_dim(qt, i_hi, 1, keepdims=False)
        zero = (
            jnp.full((b, kvh, g * block), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g * block), jnp.float32),
            jnp.zeros((b, kvh, g * block, hd), jnp.float32),
        )

        def interior(carry, t):
            lo_state, hi_state = carry
            return jax.lax.cond(
                t < i_lo,
                lambda: (_step(q_lo, t, lo_state), hi_state),
                lambda: (lo_state, _step(q_hi, t - i_lo, hi_state)),
            ), None

        (lo_state, hi_state), _ = jax.lax.scan(
            interior, (zero, zero), jnp.arange(nq - 1))
        lo_state = _step(q_lo, i_lo, lo_state, mask=diag_mask)
        hi_state = _step(q_hi, i_hi, hi_state, mask=diag_mask)
        return lo_state, hi_state

    lo, hi = jax.lax.map(pair_body, jnp.arange(nq // 2))

    def assemble(lo_leaf, hi_leaf):
        # map element i handled q rows i and nq-1-i: lo rows ascend from
        # 0, hi rows descend from nq-1; then unfold g*block rows back to
        # (head, position)
        y = jnp.concatenate([lo_leaf, jnp.flip(hi_leaf, axis=0)], axis=0)
        hd_tail = y.shape[4:]                        # () or (hd,)
        y = y.reshape((nq, b, kvh, g, block) + hd_tail)
        perm = (1, 0, 4, 2, 3) + tuple(5 + i for i in range(len(hd_tail)))
        y = y.transpose(*perm)                       # (b, nq, bq, kvh, g[, hd])
        return y.reshape((b, s, h) + hd_tail)

    m = assemble(lo[0], hi[0])
    l = assemble(lo[1], hi[1])
    acc = assemble(lo[2], hi[2])
    return acc, m, l


def _xla_partials(
    q, k, v, *, block_q, block_k, causal, window, q_offset, k_offset,
):
    """Blockwise online softmax with GQA row folding.

    Returns unnormalized ``(acc (b,s,h,hd) f32, m (b,s,h), l (b,s,h))``
    so ring shards can merge. Offsets may be python ints (static — the
    masked KV blocks are sliced out of the scan range) or traced
    scalars (ring — every block is scanned, masking handles the rest).

    GQA is handled by *row folding*, not K/V expansion: the g query
    heads sharing a KV head are folded into the matmul's row dimension
    (q block shaped ``(b, kvh, g*block_q, hd)``), so every kv_step is a
    plain ``bKsd,bKtd->bKst`` batched matmul against the un-expanded
    ``(b, kvh, block_k, hd)`` K/V block — zero copies per step and g-x
    larger (better-shaped) matmuls. Elementwise masks are tiled
    ``(g, 1)`` to cover the folded rows. The all-at-once grouped
    ``bsKgd,btKd->bKgst`` alternative measured ~3x slower on the CPU
    backend because the 5-D contraction re-transposes Q inside every
    KV step; per-block ``jnp.repeat`` expansion costs two copies per
    step and measured ~15-20% slower than folding at 32k.
    """
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    g = h // kvh
    nq, nk = s // block_q, t // block_k
    scale = hd ** -0.5
    static_offsets = isinstance(q_offset, int) and isinstance(k_offset, int)

    if (static_offsets and causal and not window and q_offset == 0
            and k_offset == 0 and s == t and block_q == block_k
            and nq >= 2 and nq % 2 == 0):
        return _paired_causal_partials(q, k, v, block=block_q)

    # same row-folded GQA layout as _paired_causal_partials: the g query
    # heads sharing a KV head become g*block_q rows of one (b, kvh)-
    # batched matmul against the un-expanded K/V block (zero copies per
    # step), and K/V are transposed head-major ONCE outside the loops so
    # every kv_step is a pure batched matmul
    qf = (q.reshape(b, nq, block_q, kvh, g, hd)
           .transpose(0, 1, 3, 4, 2, 5)
           .reshape(b, nq, kvh, g * block_q, hd))
    kt = k.reshape(b, nk, block_k, kvh, hd).transpose(0, 3, 1, 2, 4)
    vt = v.reshape(b, nk, block_k, kvh, hd).transpose(0, 3, 1, 2, 4)

    def q_block(qi):
        q_blk = qf[:, qi]                            # (b, kvh, g*bq, hd)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def make_step(masked):
            # ``masked=False`` is the interior fast path: a block fully
            # visible to every q row skips the elementwise mask (and its
            # two where()s) entirely — under causal masking that is all
            # but the diagonal block of each q row.
            def kv_step(carry, kj):
                m, l, acc = carry
                k_blk = jax.lax.dynamic_index_in_dim(kt, kj, 2, keepdims=False)
                v_blk = jax.lax.dynamic_index_in_dim(vt, kj, 2, keepdims=False)
                sc = jnp.einsum(
                    "bKsd,bKtd->bKst", q_blk, k_blk,
                    preferred_element_type=jnp.float32,
                ) * scale                            # (b, kvh, g*bq, bk)
                if masked:
                    k_pos = k_offset + kj * block_k + jnp.arange(block_k)
                    mask = jnp.ones((block_q, block_k), bool)
                    if causal:
                        mask = mask & (k_pos[None, :] <= q_pos[:, None])
                    if window:
                        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
                    mask = jnp.tile(mask, (g, 1))    # (g*bq, bk)
                    sc = jnp.where(mask[None, None], sc, NEG_INF)
                m_new = jnp.maximum(m, sc.max(axis=-1))
                p = jnp.exp(sc - m_new[..., None])
                if masked:
                    p = jnp.where(mask[None, None], p, 0.0)
                corr = jnp.exp(m - m_new)
                l_new = l * corr + p.sum(axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bKst,bKtd->bKsd", p, v_blk.astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                )
                return (m_new, l_new, acc_new), None
            return kv_step

        m0 = jnp.full((b, kvh, g * block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g * block_q), jnp.float32)
        a0 = jnp.zeros((b, kvh, g * block_q, hd), jnp.float32)
        carry = (m0, l0, a0)
        if static_offsets:
            lo, hi = kv_block_range(
                qi, block_q=block_q, block_k=block_k, nk=nk,
                causal=causal, window=window,
                q_offset=q_offset, k_offset=k_offset,
            )
            q_first = q_offset + qi * block_q
            q_last = q_first + block_q - 1

            def is_full(kj):
                k_first = k_offset + kj * block_k
                k_last = k_first + block_k - 1
                return ((not causal or k_last <= q_first)
                        and (not window or k_first > q_last - window))

            full = [kj for kj in range(lo, hi) if is_full(kj)]
            edge = [kj for kj in range(lo, hi) if not is_full(kj)]
            if full:
                carry, _ = jax.lax.scan(
                    make_step(False), carry, jnp.asarray(full))
            if edge:
                carry, _ = jax.lax.scan(
                    make_step(causal or window > 0), carry,
                    jnp.asarray(edge))
        else:
            carry, _ = jax.lax.scan(
                make_step(causal or window > 0), carry, jnp.arange(nk))
        return carry

    parts = [q_block(qi) for qi in range(nq)]

    def stitch(xs):
        # nq x (b, kvh, g*bq[, hd]) -> (b, s, h[, hd]); row r of the
        # folded axis is head g_i = r // bq at position r % bq
        y = jnp.stack(xs, axis=1)                    # (b, nq, kvh, g*bq[, hd])
        hd_tail = y.shape[4:]                        # () or (hd,)
        y = y.reshape((b, nq, kvh, g, block_q) + hd_tail)
        perm = (0, 1, 4, 2, 3) + tuple(5 + i for i in range(len(hd_tail)))
        y = y.transpose(*perm)                       # (b, nq, bq, kvh, g[, hd])
        return y.reshape((b, s, h) + hd_tail)

    m = stitch([p[0] for p in parts])
    l = stitch([p[1] for p in parts])
    acc = stitch([p[2] for p in parts])
    return acc, m, l


def flash_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
    causal: bool = True, window: int = 0,
    q_offset=0, k_offset=0, with_lse: bool = False,
):
    """Executable twin of the Pallas kernel (same schedule, same math)."""
    acc, m, l = _xla_partials(
        q, k, v, block_q=block_q, block_k=block_k, causal=causal,
        window=window, q_offset=q_offset, k_offset=k_offset,
    )
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    if with_lse:
        return out, m + jnp.log(jnp.maximum(l, 1e-30))
    return out


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
    causal: bool = True, window: int = 0, impl: str = "xla",
    interpret: bool | None = None, with_lse: bool = False,
):
    """Dispatch: ``impl='pallas'`` (TPU kernel; interpret-mode off-TPU)
    or ``impl='xla'`` (blockwise twin — the default off-TPU)."""
    if impl == "pallas":
        return flash_attention_pallas(
            q, k, v, block_q=block_q, block_k=block_k, causal=causal,
            window=window, interpret=interpret, with_lse=with_lse,
        )
    if impl == "xla":
        return flash_attention_xla(
            q, k, v, block_q=block_q, block_k=block_k, causal=causal,
            window=window, with_lse=with_lse,
        )
    raise ValueError(f"flash_attention impl {impl!r} not in ('pallas', 'xla')")


# ------------------------------------------------------------ ring variant

def merge_partials(a, b):
    """Logsumexp combine of two unnormalized flash partials
    ``(acc, m, l)`` over the SAME queries, disjoint keys. Associative
    and commutative up to fp32 rounding; an empty contribution
    ``(0, -1e30, 0)`` is the identity."""
    acc_a, m_a, l_a = a
    acc_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    return (
        acc_a * ca[..., None] + acc_b * cb[..., None],
        m,
        l_a * ca + l_b * cb,
    )


def ring_flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    axis_name: str, axis_size: int,
    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
    causal: bool = True, window: int = 0, shard_id: jax.Array | None = None,
) -> jax.Array:
    """Sequence-parallel flash attention over the ``axis_name`` mesh axis.

    Call INSIDE ``shard_map`` with q/k/v sharded on their sequence dim:
    every argument here is the device-local shard (B, S_loc, H|KV, hd).
    The K/V shards rotate one neighbor per step via ``lax.ppermute``
    (``axis_size`` steps total), so no device ever holds more than one
    remote KV shard and nothing is all-gathered. Positions are global:
    shard ``d`` owns queries ``[d*S_loc, (d+1)*S_loc)``.

    All devices run all ``axis_size`` steps in SPMD lockstep — a step
    whose KV shard is entirely in a device's causal future contributes
    the identity partial (masked to zero), which keeps the merge exact;
    load-rebalancing (striped layouts) is future work, see the kernels
    README.
    """
    s_loc = q.shape[1]
    # ``shard_id``: this device's index on the ring axis. Default is
    # ``lax.axis_index``, correct under a fully-manual shard_map; under a
    # PARTIAL-auto shard_map the caller must pass it explicitly (a
    # P(axis)-sharded iota slice), because axis_index there lowers to a
    # PartitionId op the SPMD partitioner rejects.
    idx = jax.lax.axis_index(axis_name) if shard_id is None else shard_id
    q_off = idx * s_loc
    perm = [(d, (d + 1) % axis_size) for d in range(axis_size)]

    state = None
    k_cur, v_cur = k, v
    for step in range(axis_size):
        src = (idx - step) % axis_size   # origin shard of the current K/V
        part = _xla_partials(
            q, k_cur, v_cur, block_q=block_q, block_k=block_k,
            causal=causal, window=window,
            q_offset=q_off, k_offset=src * s_loc,
        )
        state = part if state is None else merge_partials(state, part)
        if step != axis_size - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    acc, _m, l = state
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
