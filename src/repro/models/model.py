"""Model factory: init / train-forward / prefill / decode for all families.

Layout decisions (MaxText-style, chosen for the multi-pod dry-run):
  * layers are stacked with a leading L axis and driven by ``lax.scan``
    (+ ``jax.checkpoint`` on the body) so the HLO stays small and remat
    is uniform;
  * params are fp32 masters, cast to ``cfg.activation_dtype`` at use;
  * the LM head / embedding are vocab-sharded by the launcher, and the
    cross-entropy is computed in sequence chunks so full (B,S,V) logits
    are never materialized;
  * decode uses a ring-buffer KV cache (window-bounded when
    ``cfg.sliding_window`` is set) with RoPE applied at write time.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.activations import current_activation_plan, shard_act
from repro.kernels import flash_attention as _flash
from repro.models import layers, mamba2, moe, rwkv6
from repro.models.config import ModelConfig

Params = dict
CE_CHUNK = 1024
DENSE_ATTN_MAX_SEQ = 2048  # above this, use the chunked online-softmax path


# =====================================================================
# init
# =====================================================================

def _dense_layer_params(cfg: ModelConfig, key: jax.Array) -> dict:
    ka, km = jax.random.split(key)
    p = {
        "ln1": layers.rmsnorm_params(cfg.d_model),
        "ln2": layers.rmsnorm_params(cfg.d_model),
        "attn": layers.attention_params(
            ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        ),
    }
    if cfg.family == "moe":
        p["moe"] = moe.moe_params(km, cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_layers)
    else:
        p["mlp"] = layers.swiglu_params(km, cfg.d_model, cfg.d_ff, cfg.n_layers)
    return p


def _rwkv_layer_params(cfg: ModelConfig, key: jax.Array) -> dict:
    kt, kc = jax.random.split(key)
    return {
        "ln1": layers.rmsnorm_params(cfg.d_model),
        "ln2": layers.rmsnorm_params(cfg.d_model),
        "tm": rwkv6.time_mix_params(kt, cfg.d_model, cfg.rwkv_heads, cfg.n_layers),
        "cm": rwkv6.channel_mix_params(kc, cfg.d_model, cfg.d_ff, cfg.n_layers),
    }


def _mamba_layer_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return {
        "ln": layers.rmsnorm_params(cfg.d_model),
        "mamba": mamba2.mamba2_params(
            key, cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim, cfg.n_layers
        ),
    }


def _encdec_enc_layer_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return _dense_layer_params(cfg, key)


def _encdec_dec_layer_params(cfg: ModelConfig, key: jax.Array) -> dict:
    ka, kx, km = jax.random.split(key, 3)
    return {
        "ln1": layers.rmsnorm_params(cfg.d_model),
        "ln_x": layers.rmsnorm_params(cfg.d_model),
        "ln2": layers.rmsnorm_params(cfg.d_model),
        "attn": layers.attention_params(
            ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        ),
        "xattn": layers.attention_params(
            kx, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
        ),
        "mlp": layers.swiglu_params(km, cfg.d_model, cfg.d_ff, cfg.n_layers),
    }


def _stack_layers(layer_fn, cfg: ModelConfig, key: jax.Array, n: int) -> dict:
    keys = jax.random.split(key, n)
    return jax.vmap(functools.partial(layer_fn, cfg))(keys)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    k_emb, k_layers, k_head, k_extra = jax.random.split(key, 4)
    params: Params = {
        "embed": layers.embedding_params(k_emb, cfg.vocab, cfg.d_model),
        "final_norm": layers.rmsnorm_params(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.embedding_params(k_head, cfg.vocab, cfg.d_model)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        params["layers"] = _stack_layers(_dense_layer_params, cfg, k_layers, cfg.n_layers)
    elif fam == "ssm":
        params["layers"] = _stack_layers(_rwkv_layer_params, cfg, k_layers, cfg.n_layers)
    elif fam == "hybrid":
        params["layers"] = _stack_layers(_mamba_layer_params, cfg, k_layers, cfg.n_layers)
        ksa, ksm = jax.random.split(k_extra)
        params["shared_attn"] = {
            "ln": layers.rmsnorm_params(cfg.d_model),
            "ln2": layers.rmsnorm_params(cfg.d_model),
            "attn": layers.attention_params(
                ksa, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
            ),
            "mlp": layers.swiglu_params(ksm, cfg.d_model, cfg.d_ff, cfg.n_layers),
        }
    elif fam == "encdec":
        ke, kd = jax.random.split(k_layers)
        params["enc_layers"] = _stack_layers(
            _encdec_enc_layer_params, cfg, ke, cfg.n_enc_layers
        )
        params["layers"] = _stack_layers(_encdec_dec_layer_params, cfg, kd, cfg.n_layers)
        params["enc_norm"] = layers.rmsnorm_params(cfg.d_model)
    else:
        raise ValueError(f"unknown family {fam}")
    if fam == "vlm":
        params["vis_proj"] = {
            "w": layers.dense_init(k_extra, (cfg.d_model, cfg.d_model))
        }
    return params


def abstract_params(cfg: ModelConfig, key: jax.Array | None = None) -> Params:
    """ShapeDtypeStruct pytree of the params (no allocation, for dry-runs)."""
    k = jax.random.PRNGKey(0) if key is None else key
    return jax.eval_shape(lambda: init_params(cfg, k))


# =====================================================================
# attention block helpers
# =====================================================================

def _flash_dispatch(
    cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool, window: int,
) -> jax.Array:
    """``attn_impl="flash"`` path: single-device blockwise flash, or the
    ring variant when the active plan shards the sequence dim.

    The ring decision is static (mesh topology, shape divisibility): on
    a seq>1 activation mesh each device keeps its Q shard and the K/V
    shards rotate via ``lax.ppermute`` inside ``shard_map`` — the
    remaining mesh axes stay ``auto`` so the heads/batch shardings from
    ``shard_act`` keep propagating through the body.
    """
    b, s, h, _hd = q.shape
    kvh = k.shape[2]
    blk = cfg.chunk_size  # dispatch already guarantees s % chunk_size == 0
    plan = current_activation_plan()
    if plan is not None:
        ent = plan.resolve(s, "seq")
        if isinstance(ent, str):
            n = plan.axis_size(ent)
            if n > 1 and s % (n * blk) == 0:
                # Fully-manual shard_map (the form the --require-flash
                # dry-run gate validated; see kernels/README.md), so every
                # mesh axis gets an explicit spec:
                #   * heads ride the model axis only when BOTH the query
                #     and KV head counts divide it — contiguous head
                #     blocks then align with whole GQA groups, keeping
                #     the in-kernel head->kv mapping local;
                #   * batch follows the plan's progressive dp rule;
                #   * axes in no spec carry replicated data (check_vma
                #     off: the body is deterministic per shard).
                msz = plan.axis_size("model")
                heads_ent = (
                    "model"
                    if msz > 1 and h % msz == 0 and kvh % msz == 0
                    else None
                )
                used = frozenset(x for x in (ent, heads_ent) if x)
                b_ent = plan.resolve(b, "batch", used=used)
                spec = P(b_ent, ent, heads_ent, None)

                def ring_body(qs, ks, vs, ids):
                    # ids: P(seq)-sharded iota — each shard reads its own
                    # ring index (lax.axis_index lowers to a PartitionId
                    # op XLA rejects in these nested-manual bodies)
                    return _flash.ring_flash_attention(
                        qs, ks, vs, axis_name=ent, axis_size=n,
                        block_q=blk, block_k=blk, causal=causal,
                        window=window, shard_id=ids[0],
                    )

                return jax.shard_map(
                    ring_body, mesh=plan.mesh,
                    in_specs=(spec, spec, spec, P(ent)), out_specs=spec,
                    check_vma=False,
                )(q, k, v, jnp.arange(n, dtype=jnp.int32))
    return layers.flash_attention(
        q, k, v, block_q=blk, block_k=blk, causal=causal, window=window
    )


def _self_attention(
    cfg: ModelConfig, p: dict, x: jax.Array, *,
    causal: bool, positions: jax.Array, causal_skip: bool = False,
    window_override: Optional[int] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out, k_rope, v) — k/v for optional cache building."""
    dtype = x.dtype
    window = cfg.sliding_window if window_override is None else window_override
    q = shard_act(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype)), "bshd")
    k = shard_act(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dtype)), "bshd")
    v = shard_act(jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dtype)), "bshd")
    q = shard_act(layers.apply_rope(q, positions, cfg.rope_theta), "bshd")
    k = shard_act(layers.apply_rope(k, positions, cfg.rope_theta), "bshd")
    s = x.shape[1]
    if s <= DENSE_ATTN_MAX_SEQ or s % cfg.chunk_size != 0:
        o = layers.dense_attention(q, k, v, causal=causal, window=window)
    elif cfg.attn_impl == "flash":
        o = _flash_dispatch(cfg, q, k, v, causal=causal, window=window)
    else:
        o = layers.chunked_attention(
            q, k, v, chunk=cfg.chunk_size, causal=causal, window=window,
            causal_skip=causal_skip,
        )
    out = shard_act(jnp.einsum("bshk,hkd->bsd", shard_act(o, "bshd"), p["wo"].astype(dtype)), "btd")
    return out, k, v


def _dense_block(cfg: ModelConfig, p: dict, x: jax.Array, *,
                 causal_skip: bool = False) -> tuple[jax.Array, dict]:
    s = x.shape[1]
    positions = jnp.arange(s)
    h, _, _ = _self_attention(
        cfg, p["attn"], layers.rmsnorm(p["ln1"], x, cfg.norm_eps),
        causal=True, positions=positions, causal_skip=causal_skip,
    )
    x = x + h
    aux: dict = {}
    y = layers.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = moe.moe_apply(
            p["moe"], y, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
        )
        # named for the save_moe_out remat policy: saving this (B,S,D)
        # tensor keeps the backward from re-running the expert matmuls and
        # their partial-sum all-reduces (the dominant collective for grok;
        # EXPERIMENTS.md §Perf grok iteration 1).
        from jax.ad_checkpoint import checkpoint_name
        m = checkpoint_name(m, "moe_out")
    else:
        m = layers.swiglu(p["mlp"], y)
    return x + m, aux


def _rwkv_block(cfg: ModelConfig, p: dict, x: jax.Array, x_tm, x_cm, s0):
    h, tm_carry, s_new = rwkv6.time_mix_apply(
        p["tm"], layers.rmsnorm(p["ln1"], x, cfg.norm_eps), x_tm, s0,
        cfg.rwkv_heads, chunked=x.shape[1] % 64 == 0 and x.shape[1] > 1,
    )
    x = x + h
    c, cm_carry = rwkv6.channel_mix_apply(
        p["cm"], layers.rmsnorm(p["ln2"], x, cfg.norm_eps), x_cm
    )
    return x + c, tm_carry, cm_carry, s_new


def _mamba_block(cfg: ModelConfig, p: dict, x: jax.Array, state=None):
    h, new_state = mamba2.mamba2_apply(
        p["mamba"], layers.rmsnorm(p["ln"], x, cfg.norm_eps),
        d_inner=cfg.d_inner, d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
        state=state, chunk=min(cfg.chunk_size, 128),
        chunked=x.shape[1] % min(cfg.chunk_size, 128) == 0 and x.shape[1] > 1,
    )
    return x + h, new_state


# =====================================================================
# train forward (per family)
# =====================================================================

def _scan(body, x, stacked, remat: bool = True, remat_policy: str = "full"):
    if remat and remat_policy == "save_moe_out":
        pol = jax.checkpoint_policies.save_only_these_names("moe_out")
        f = jax.checkpoint(body, policy=pol)
    elif remat:
        f = jax.checkpoint(body)
    else:
        f = body
    x, aux = jax.lax.scan(f, x, stacked)
    return x, aux


def _forward_dense(cfg: ModelConfig, params: Params, x: jax.Array, *,
                   causal_skip: bool = False, remat: bool = True,
                   remat_policy: str = "full") -> tuple[jax.Array, dict]:
    def body(h, layer_p):
        h, aux = _dense_block(cfg, layer_p, h, causal_skip=causal_skip)
        return h, aux

    x, auxs = _scan(body, x, params["layers"], remat, remat_policy)
    aux = {k: jnp.mean(v) for k, v in auxs.items()} if auxs else {}
    return x, aux


def _forward_rwkv(cfg: ModelConfig, params: Params, x: jax.Array, *,
                  remat: bool = True) -> tuple[jax.Array, dict]:
    b = x.shape[0]
    hN = cfg.rwkv_heads
    hd = cfg.d_model // hN

    def body(h, layer_p):
        x_prev = jnp.zeros((b, cfg.d_model), h.dtype)
        s0 = jnp.zeros((b, hN, hd, hd), jnp.float32)
        h, _, _, _ = _rwkv_block(cfg, layer_p, h, x_prev, x_prev, s0)
        return h, None

    x, _ = _scan(body, x, params["layers"], remat)
    return x, {}


def _forward_hybrid(cfg: ModelConfig, params: Params, x: jax.Array, *,
                    causal_skip: bool = False, remat: bool = True) -> tuple[jax.Array, dict]:
    n_super = cfg.n_layers // cfg.attn_every
    stacked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_super, cfg.attn_every) + a.shape[1:]),
        params["layers"],
    )
    shared = params["shared_attn"]
    s = x.shape[1]
    positions = jnp.arange(s)
    # Shared attention is window-bounded so hybrid long-context stays O(w).
    window = cfg.sliding_window or 4096

    def super_body(h, super_p):
        def inner(hh, layer_p):
            hh, _ = _mamba_block(cfg, layer_p, hh)
            return hh, None

        h, _ = jax.lax.scan(inner, h, super_p)
        a, _, _ = _self_attention(
            cfg, shared["attn"], layers.rmsnorm(shared["ln"], h, cfg.norm_eps),
            causal=True, positions=positions, causal_skip=causal_skip,
            window_override=window,
        )
        h = h + a
        m = layers.swiglu(shared["mlp"], layers.rmsnorm(shared["ln2"], h, cfg.norm_eps))
        return h + m, None

    x, _ = _scan(super_body, x, stacked, remat)
    return x, {}


def _forward_encoder(cfg: ModelConfig, params: Params, src: jax.Array, *,
                     remat: bool = True) -> jax.Array:
    positions = jnp.arange(src.shape[1])

    def body(h, layer_p):
        a, _, _ = _self_attention(
            cfg, layer_p["attn"], layers.rmsnorm(layer_p["ln1"], h, cfg.norm_eps),
            causal=False, positions=positions,
        )
        h = h + a
        m = layers.swiglu(layer_p["mlp"], layers.rmsnorm(layer_p["ln2"], h, cfg.norm_eps))
        return h + m, None

    src, _ = _scan(body, src, params["enc_layers"], remat)
    return layers.rmsnorm(params["enc_norm"], src, cfg.norm_eps)


def _cross_attention(cfg: ModelConfig, p: dict, x: jax.Array, mem_k, mem_v) -> jax.Array:
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dtype))
    o = layers.dense_attention(q, mem_k, mem_v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dtype))


def _forward_encdec(cfg: ModelConfig, params: Params, src: jax.Array,
                    tgt: jax.Array, *, remat: bool = True) -> tuple[jax.Array, dict]:
    mem = _forward_encoder(cfg, params, src, remat=remat)
    positions = jnp.arange(tgt.shape[1])

    def body(h, layer_p):
        a, _, _ = _self_attention(
            cfg, layer_p["attn"], layers.rmsnorm(layer_p["ln1"], h, cfg.norm_eps),
            causal=True, positions=positions,
        )
        h = h + a
        dtype = h.dtype
        xp = layer_p["xattn"]
        mk = jnp.einsum("bsd,dhk->bshk", mem, xp["wk"].astype(dtype))
        mv = jnp.einsum("bsd,dhk->bshk", mem, xp["wv"].astype(dtype))
        c = _cross_attention(
            cfg, xp, layers.rmsnorm(layer_p["ln_x"], h, cfg.norm_eps), mk, mv
        )
        h = h + c
        m = layers.swiglu(layer_p["mlp"], layers.rmsnorm(layer_p["ln2"], h, cfg.norm_eps))
        return h + m, None

    tgt, _ = _scan(body, tgt, params["layers"], remat)
    return tgt, {}


# =====================================================================
# loss
# =====================================================================

def _chunked_ce(
    cfg: ModelConfig, params: Params, h: jax.Array, labels: jax.Array,
    mask: jax.Array, ce_chunk: int = CE_CHUNK,
) -> jax.Array:
    """Cross-entropy without materializing (B, S, V): scan over S chunks."""
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    b, s, d = h.shape
    chunk = min(ce_chunk, s)
    if s % chunk:
        chunk = s
    nc = s // chunk
    hc = h.reshape(b, nc, chunk, d)
    lc = labels.reshape(b, nc, chunk)
    mc = mask.reshape(b, nc, chunk)

    def body(carry, inp):
        hh, ll, mm = inp
        logits = layers.unembed(head, hh)              # (B, chunk, V) fp32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mm
        return (carry[0] + nll.sum(), carry[1] + mm.sum()), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    xs = (
        jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0), jnp.moveaxis(mc, 1, 0)
    )
    (total, denom), _ = jax.lax.scan(jax.checkpoint(body), init, xs)
    return total / jnp.maximum(denom, 1.0)


def forward_train(
    cfg: ModelConfig, params: Params, batch: dict, *,
    causal_skip: bool = False, remat: bool = True, remat_policy: str = "full",
) -> tuple[jax.Array, dict]:
    """Returns (loss, metrics). Batch layout per family — see repro.data."""
    dtype = cfg.activation_dtype
    fam = cfg.family
    if fam == "encdec":
        src = batch["src_embeds"].astype(dtype)
        tgt = layers.embed(params["embed"], batch["tokens"], dtype)
        h, aux = _forward_encdec(cfg, params, src, tgt, remat=remat)
    else:
        x = shard_act(layers.embed(
            params["embed"], shard_act(batch["tokens"], "bt"), dtype
        ), "btd")
        if fam == "vlm":
            vis = batch["vis_embeds"].astype(dtype)
            vis = jnp.einsum("bnd,de->bne", vis, params["vis_proj"]["w"].astype(dtype))
            x = jnp.concatenate([vis, x], axis=1)
        if fam in ("dense", "moe", "vlm"):
            h, aux = _forward_dense(cfg, params, x, causal_skip=causal_skip,
                                    remat=remat, remat_policy=remat_policy)
        elif fam == "ssm":
            h, aux = _forward_rwkv(cfg, params, x, remat=remat)
        elif fam == "hybrid":
            h, aux = _forward_hybrid(cfg, params, x, causal_skip=causal_skip, remat=remat)
        else:
            raise ValueError(fam)
        if fam == "vlm":
            h = h[:, batch["vis_embeds"].shape[1]:, :]
    h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    loss = _chunked_ce(cfg, params, h, batch["labels"], batch["mask"].astype(jnp.float32))
    metrics = {"loss": loss}
    if aux:
        loss = loss + 0.01 * aux.get("lb_loss", 0.0) + 1e-3 * aux.get("z_loss", 0.0)
        metrics.update(aux)
    return loss, metrics


def forward_logits(cfg: ModelConfig, params: Params, batch: dict) -> jax.Array:
    """Last-position logits (used by prefill benchmarks and tests)."""
    dtype = cfg.activation_dtype
    fam = cfg.family
    if fam == "encdec":
        src = batch["src_embeds"].astype(dtype)
        tgt = layers.embed(params["embed"], batch["tokens"], dtype)
        h, _ = _forward_encdec(cfg, params, src, tgt, remat=False)
    else:
        x = shard_act(layers.embed(
            params["embed"], shard_act(batch["tokens"], "bt"), dtype
        ), "btd")
        if fam == "vlm":
            vis = batch["vis_embeds"].astype(dtype)
            vis = jnp.einsum("bnd,de->bne", vis, params["vis_proj"]["w"].astype(dtype))
            x = jnp.concatenate([vis, x], axis=1)
        if fam in ("dense", "moe", "vlm"):
            h, _ = _forward_dense(cfg, params, x, remat=False)
        elif fam == "ssm":
            h, _ = _forward_rwkv(cfg, params, x, remat=False)
        elif fam == "hybrid":
            h, _ = _forward_hybrid(cfg, params, x, remat=False)
        else:
            raise ValueError(fam)
    h = layers.rmsnorm(params["final_norm"], h[:, -1:, :], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return layers.unembed(head, h)[:, 0, :]
