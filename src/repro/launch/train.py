"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi_6b --reduced \
        --steps 100 --batch 8 --seq 128 [--ckpt-dir /tmp/ckpt] [--fl-interval 10]

On the CPU container this trains the REDUCED variant on the host mesh;
on a real slice drop --reduced and it uses make_production_mesh() with
the full FSDP+TP shardings. --fl-interval N inserts the paper's quantized
federated aggregation every N steps (2 virtual clients on the host mesh;
clients = pods on the multi-pod mesh).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest COMPLETE checkpoint in "
                         "--ckpt-dir (params restored, optimizer state "
                         "re-initialized, data stream fast-forwarded); "
                         "starts fresh if the directory has none")
    ap.add_argument("--fl-interval", type=int, default=0)
    ap.add_argument("--fl-q", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="JSONL run-ledger path (default: $REPRO_LEDGER)")
    ap.add_argument("--xprof", default=None, metavar="DIR",
                    help="profiler capture of the steady-state steps "
                         "(starts after step 0, so compile is excluded)")
    args = ap.parse_args()

    from repro.ckpt import save_checkpoint
    from repro.configs import get_config, get_reduced
    from repro.core.quantization import quantize_pytree
    from repro.dist import sharding as shd
    from repro.dist.activations import activation_mesh
    from repro.dist.plan import make_plan
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.obs import default_ledger, maybe_trace, span
    from repro.optim import adamw

    ledger = default_ledger(args.ledger)
    ledger.run_header(
        name=f"train[{args.arch}]", entry="launch.train", arch=args.arch,
        reduced=bool(args.reduced), steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, fl_interval=args.fl_interval,
        fl_q=args.fl_q, seed=args.seed,
    )

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_host_mesh() if args.reduced else make_production_mesh()
    plan = make_plan(mesh)
    opt = adamw(args.lr)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    start_step = 0
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir")
        from repro.ckpt import latest_step, load_checkpoint

        last = latest_step(args.ckpt_dir)
        if last is None:
            print(f"--resume: no complete checkpoint in {args.ckpt_dir}; "
                  "starting fresh", flush=True)
        else:
            # load_checkpoint validates the sidecar (keys/shapes/dtypes)
            # and raises CheckpointError rather than resuming from a
            # half-written or mismatched step
            tree, meta = load_checkpoint(args.ckpt_dir, last)
            params = jax.tree_util.tree_map(
                lambda ref, arr: jnp.asarray(arr, ref.dtype), params, tree
            )
            start_step = int(meta["step"])
            ledger.write("resume", step=start_step, action="load",
                         dir=str(args.ckpt_dir))
            print(f"resumed from step {start_step} ({args.ckpt_dir})",
                  flush=True)
    opt_state = opt.init(params)
    # place params/optimizer through the logical-axis plan (a no-op on the
    # 1x1 host mesh; FSDP+TP placement on a real slice)
    pspecs = plan.named(shd.param_specs(plan, params))
    params = jax.device_put(params, pspecs)
    opt_state = jax.device_put(
        opt_state, plan.named(shd.make_opt_specs(mesh, opt_state, pspecs))
    )
    step_fn, _ = make_train_step(cfg, mesh, opt)
    step = jax.jit(step_fn, donate_argnums=(0, 1))

    rng = np.random.default_rng(args.seed)
    b, s = args.batch, args.seq
    import contextlib
    prof = contextlib.ExitStack()
    # fast-forward the data stream (and the fl key schedule) over the
    # already-trained steps so a resumed run sees the same batch a fresh
    # run would at the same step index
    for i in range(start_step):
        rng.integers(0, cfg.vocab, (b, s))
        if cfg.family == "encdec":
            rng.normal(size=(b, s, cfg.d_model))
        if cfg.family == "vlm":
            rng.normal(size=(b, cfg.n_vis_tokens, cfg.d_model))
        if args.fl_interval and (i + 1) % args.fl_interval == 0:
            key, _, _ = jax.random.split(key, 3)
    metrics = None
    with span("train.loop", steps=args.steps) as loop:
        for i in range(start_step, args.steps):
            toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
            batch = {"tokens": toks, "labels": toks, "mask": jnp.ones((b, s))}
            if cfg.family == "encdec":
                batch["src_embeds"] = jnp.asarray(
                    rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
            if cfg.family == "vlm":
                batch["vis_embeds"] = jnp.asarray(
                    rng.normal(size=(b, cfg.n_vis_tokens, cfg.d_model)),
                    jnp.float32)
            params, opt_state, metrics = step(params, opt_state, batch)
            if i == start_step:
                jax.block_until_ready(metrics["loss"])
                ledger.timing("first_step", loop.seconds,
                              entry="launch.train", note="includes compile")
                if args.xprof:  # steady state only: compile is done
                    prof.enter_context(maybe_trace(args.xprof))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({loop.seconds/(i-start_step+1):.2f}s/step)",
                      flush=True)
            if args.fl_interval and (i + 1) % args.fl_interval == 0:
                # paper eq. 2 on 2 virtual clients: quantize + weighted-average
                key, k1, k2 = jax.random.split(key, 3)
                q1, t1 = quantize_pytree(k1, params, args.fl_q)
                q2, t2 = quantize_pytree(k2, params, args.fl_q)
                params = jax.tree_util.tree_map(
                    lambda a, c: (0.5 * a.astype(jnp.float32)
                                  + 0.5 * c.astype(jnp.float32)
                                  ).astype(a.dtype),
                    q1, q2,
                )
                print(f"  fl sync @ step {i+1}: q={args.fl_q} "
                      f"theta_max={float(t1):.3f}", flush=True)
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                path = save_checkpoint(args.ckpt_dir, i + 1, params,
                                       extra={"loss": float(metrics["loss"])})
                print(f"  saved {path}", flush=True)
    prof.close()
    if metrics is None:
        print(f"nothing to do: resumed step {start_step} >= --steps "
              f"{args.steps}", flush=True)
        return
    ledger.timing("train_loop", loop.seconds, entry="launch.train",
                  steps=args.steps,
                  final_loss=float(metrics["loss"]))


if __name__ == "__main__":
    main()
