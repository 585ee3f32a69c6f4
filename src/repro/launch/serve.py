"""Serving launcher: batched greedy decode with the ring-buffer cache.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2_7b \
        --batch 4 --context 96 --new-tokens 32 [--ckpt-dir DIR]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2_7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--context", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.ckpt import load_checkpoint
    from repro.configs import get_reduced
    from repro.dist import sharding as shd
    from repro.dist.plan import make_plan
    from repro.launch.mesh import make_host_mesh
    from repro.models import decode_step, init_params
    from repro.models.decode import encode, init_cache, prefill
    from repro.obs.profile import span

    cfg = get_reduced(args.arch)
    # serve-mode plan: tensor parallelism only, params replicated over the
    # data axes (a no-op placement on the 1x1 host mesh)
    plan = make_plan(make_host_mesh(), mode="serve")
    key = jax.random.PRNGKey(args.seed)
    if args.ckpt_dir:
        params, meta = load_checkpoint(args.ckpt_dir)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        print(f"restored step {meta['step']}")
    else:
        params = init_params(cfg, key)
    params = jax.device_put(params, plan.named(shd.param_specs(plan, params)))

    rng = np.random.default_rng(args.seed)
    b = args.batch
    total = args.context + args.new_tokens
    ctx = jnp.asarray(rng.integers(0, cfg.vocab, (b, args.context)), jnp.int32)

    if cfg.family == "encdec":
        cache = init_cache(cfg, b, total)
        cache = encode(cfg, params, cache, jnp.asarray(
            rng.normal(size=(b, args.context, cfg.d_model)), jnp.float32))
        tokens = jnp.zeros((b,), jnp.int32)
    else:
        logits, cache = prefill(cfg, params, {"tokens": ctx}, total)
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)

    # Donating the cache and the token buffer lets XLA update both in place
    # instead of re-allocating them every token; the greedy argmax and the
    # buffer write live inside the jitted step so the loop issues exactly
    # one dispatch per token.
    def _step(p, c, tok, buf, i):
        logits, c = decode_step(cfg, p, c, tok)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return c, nxt, buf.at[:, i].set(nxt)

    step = jax.jit(_step, donate_argnums=(1, 3))
    out_buf = jnp.zeros((b, args.new_tokens + 1), jnp.int32).at[:, 0].set(tokens)
    with span("serve.decode", tokens=args.new_tokens) as decode:
        for i in range(args.new_tokens):
            cache, tokens, out_buf = step(params, cache, tokens, out_buf,
                                          jnp.int32(i + 1))
        jax.block_until_ready(tokens)
    dt = decode.seconds
    print(f"{args.new_tokens} tokens x {b} requests in {dt:.2f}s "
          f"({args.new_tokens * b / dt:.1f} tok/s)")
    gen = np.asarray(out_buf)
    for r in range(b):
        print(f"req{r}: {list(gen[r][:16])}")


if __name__ == "__main__":
    main()
