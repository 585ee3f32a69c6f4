"""Run the full (arch x shape x mesh) dry-run sweep as subprocesses.

Each combo runs in a fresh process (jax locks the 512-device XLA flag at
first init, and isolation keeps one OOM/compile failure from killing the
sweep). Appends JSONL records to benchmarks/results/dryrun.jsonl.

Meshes:
  single  16x16        (256 chips, data x model)
  multi   2x16x16      (512 chips, pod x data x model)
  seq4d   1x4x2x16     (128 chips, pod x data x seq x model) — sequence
          and expert parallelism active through the logical-axis plan;
          train/prefill shapes only. GQA archs additionally gate on
          "no full-seq replicated intermediates", and expert-divisible
          MoE archs gate on "dispatch lowers to all-to-alls".

``--wire-ratio`` runs the pod-scale per-arch federated-round wire
accounting instead (ROADMAP pod-scale item, second half): every arch is
lowered in both wire modes on the 2x16x16 mesh and the inter-pod byte
ratio lands as a JSONL row in benchmarks/results/wire_ratio.jsonl.

Usage:
  PYTHONPATH=src python benchmarks/dryrun_sweep.py \
      [--mesh single|multi|seq4d|both|all] [--arch A ...] [--shape S ...] \
      [--fl-round] [--wire-ratio] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ARCHS = [
    "llama3_8b", "seamless_m4t_large_v2", "grok_1_314b", "internvl2_26b",
    "rwkv6_7b", "phi3_medium_14b", "yi_6b", "starcoder2_7b", "zamba2_7b",
    "granite_moe_1b_a400m",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.obs import default_ledger  # noqa: E402 — needs the src path

SEQ4D_SHAPE = "1x4x2x16"            # pod x data x seq x model
SEQ4D_SHAPES = ["train_4k", "prefill_32k"]   # seq axis is a train/prefill story
# GQA archs whose attention window gathers stay below the full-seq
# threshold — gated on seq-sharded activations (see launch/dryrun.py).
# granite's prefill KV-cache write (f32, KV*hd = d_model/2) sits exactly
# on the threshold, so it gates on the train shape only.
SEQ_GATED = {
    "llama3_8b": {"train_4k", "prefill_32k"},
    "granite_moe_1b_a400m": {"train_4k"},
}
# MoE archs whose expert count divides the 16-wide model axis — gated on
# the dispatch lowering to all-to-alls
A2A_GATED = {
    "granite_moe_1b_a400m": {"train_4k", "prefill_32k"},
}

MESHES = {
    "single": {"label": "16x16", "args": []},
    "multi": {"label": "2x16x16", "args": ["--multi-pod"]},
    "seq4d": {"label": SEQ4D_SHAPE, "args": ["--mesh-shape", SEQ4D_SHAPE]},
}


def run_combo(arch: str, shape: str, mesh: str, out: str,
              fl_round: bool = False, timeout: int = 3600) -> dict:
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--out", out,
        *MESHES[mesh]["args"],
    ]
    if fl_round:
        cmd.append("--fl-round")
    if mesh == "seq4d":
        if shape in SEQ_GATED.get(arch, ()):
            cmd.append("--require-seq-sharded")
        if shape in A2A_GATED.get(arch, ()):
            cmd.append("--require-alltoall")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, env=env,
        )
        ok = proc.returncode == 0
        err = "" if ok else proc.stdout[-800:] + proc.stderr[-800:]
    except subprocess.TimeoutExpired:
        ok, err = False, f"timeout after {timeout}s"
    return {
        "arch": arch, "shape": shape, "mesh": MESHES[mesh]["label"],
        "fl_round": fl_round, "ok": ok,
        "wall_s": round(time.time() - t0, 1), "err": err,
    }


def run_wire_ratio(arch: str, out: str, timeout: int = 3600,
                   downlink: str = "off") -> dict:
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", "train_512", "--wire-ratio",
        "--downlink", downlink, "--out", out,
    ]
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    t0 = time.time()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout, env=env,
        )
        ok = proc.returncode == 0
        err = "" if ok else proc.stdout[-800:] + proc.stderr[-800:]
        ratio = None
        if ok:
            try:  # stdout is exactly one pretty-printed JSON record
                ratio = json.loads(proc.stdout).get("inter_pod_ratio")
            except ValueError:
                ratio = None
    except subprocess.TimeoutExpired:
        ok, err, ratio = False, f"timeout after {timeout}s", None
    return {
        "arch": arch, "ok": ok, "ratio": ratio,
        "wall_s": round(time.time() - t0, 1), "err": err,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi", "seq4d", "both", "all"],
                    default="both")
    ap.add_argument("--arch", nargs="*", default=ARCHS)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--fl-round", action="store_true",
                    help="also lower the federated round (multi-pod only)")
    ap.add_argument("--wire-ratio", action="store_true",
                    help="per-arch fl-round inter-pod wire-ratio sweep "
                         "instead of the lower+compile matrix")
    ap.add_argument("--downlink", default="off",
                    choices=("off", "quant", "delta"),
                    help="broadcast mode threaded into the wire-ratio "
                         "rounds (both lowered wire modes)")
    ap.add_argument("--max-ratio", type=float, default=None,
                    help="fail the wire-ratio sweep if any arch's "
                         "inter-pod ratio is >= this bound (CI gate)")
    ap.add_argument("--out", default=os.path.join(ROOT, "benchmarks", "results", "dryrun.jsonl"))
    ap.add_argument("--wire-out", default=os.path.join(
        ROOT, "benchmarks", "results", "wire_ratio.jsonl"))
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()

    # sweep-level ledger: one record event per combo (the subprocesses
    # inherit REPRO_LEDGER through env and add their own hlo/record rows)
    led = default_ledger()

    if args.wire_ratio:
        os.makedirs(os.path.dirname(args.wire_out), exist_ok=True)
        print(f"wire-ratio sweep: {len(args.arch)} archs -> {args.wire_out}",
              flush=True)
        led.run_header(name="dryrun_sweep[wire_ratio]", entry="dryrun_sweep",
                       n_archs=len(args.arch), downlink=args.downlink)
        n_ok = 0
        over = []
        for i, a in enumerate(args.arch):
            r = run_wire_ratio(a, args.wire_out, timeout=args.timeout,
                               downlink=args.downlink)
            n_ok += r["ok"]
            if (args.max_ratio is not None
                    and (r["ratio"] is None or r["ratio"] >= args.max_ratio)):
                over.append((a, r["ratio"]))
            led.record("wire_ratio_sweep", r)
            print(
                f"[{i+1}/{len(args.arch)}] {a} ok={r['ok']} "
                f"ratio={r['ratio']} {r['wall_s']}s {r['err'][:160]}",
                flush=True,
            )
        print(f"done: {n_ok}/{len(args.arch)} ok", flush=True)
        if over:
            print(f"wire-ratio gate FAILED (>= {args.max_ratio}): {over}",
                  flush=True)
        return 0 if n_ok == len(args.arch) and not over else 1

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    meshes = {
        "single": ["single"], "multi": ["multi"], "seq4d": ["seq4d"],
        "both": ["single", "multi"], "all": ["single", "multi", "seq4d"],
    }[args.mesh]
    combos = []
    for m in meshes:
        shapes = args.shape or (SEQ4D_SHAPES if m == "seq4d" else SHAPES)
        combos += [(a, s, m) for a in args.arch for s in shapes]
    print(f"sweep: {len(combos)} combos -> {args.out}", flush=True)
    led.run_header(name=f"dryrun_sweep[{args.mesh}]", entry="dryrun_sweep",
                   n_combos=len(combos))
    n_ok = 0
    for i, (a, s, m) in enumerate(combos):
        r = run_combo(a, s, m, args.out, timeout=args.timeout)
        n_ok += r["ok"]
        led.record("dryrun_sweep", r)
        print(
            f"[{i+1}/{len(combos)}] {a} {s} {m} "
            f"ok={r['ok']} {r['wall_s']}s {r['err'][:160]}", flush=True,
        )
    if args.fl_round:
        for a in args.arch:
            r = run_combo(a, "train_4k", "multi", args.out, fl_round=True,
                          timeout=args.timeout)
            led.record("dryrun_sweep", r)
            print(f"[fl_round] {a} ok={r['ok']} {r['wall_s']}s {r['err'][:160]}", flush=True)
    print(f"done: {n_ok}/{len(combos)} ok", flush=True)
    return 0 if n_ok == len(combos) else 1


if __name__ == "__main__":
    sys.exit(main())
