"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  fig2   — V trade-off (energy vs accuracy)            [paper Fig. 2]
  fig3   — FEMNIST-proxy accuracy/energy vs baselines  [paper Fig. 3]
  fig4   — CIFAR-proxy accuracy/energy vs baselines    [paper Fig. 4]
  fig5   — quantization level vs rounds / dataset size [paper Fig. 5]
  kernels— Pallas quant/dequant/aggregate microbench   [Table I payload path]
  flash  — chunked vs flash vs ring attention matrix   [ISSUE 10 long-context]
  sim    — compiled fleet simulator rounds/sec         [repro.sim scan path]
  roofline — per (arch x shape) dry-run terms          [§Roofline]

Full-scale variants (paper-size rounds/tasks) are available by calling the
functions in benchmarks.fl_benchmarks directly; this entrypoint sizes
everything to finish on the CPU container.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


_WIRE_RATIO_SCRIPT = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax, numpy as np
from repro.configs import get_config
from repro.dist.hlo_analysis import inter_axis_bytes, pod_partition_map
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models.config import InputShape

cfg = get_config("llama3_8b")
mesh = make_production_mesh(multi_pod=True)
pods = pod_partition_map(mesh)
shape = InputShape("train_small", 512, 64, "train")
downlink = os.environ.get("BENCH_DOWNLINK", "off")
out = {"downlink": downlink}
for packed in (False, True):
    hlo = steps.lower_fl_round(cfg, mesh, shape, wire_packed=packed,
                               downlink=downlink).compile().as_text()
    r = inter_axis_bytes(hlo, pods)
    mode = "packed" if packed else "fp32"
    out[mode] = r["inter_bytes"]
    out[mode + "_unattr"] = r["unattributed_bytes"]
print("WIRE_RATIO " + json.dumps(out))
"""


def bench_wire_ratio(timeout: int = 1800, downlink: str = "quant") -> list[tuple]:
    """ROADMAP pod-scale item (first half): lower the federated round on
    the 2x16x16 mesh in both wire modes and record the inter-pod byte
    ratio (uint8 wire / fp32 payload) via ``inter_axis_bytes``. Runs in a
    subprocess because the 512-device XLA flag must precede jax init.
    Asserts the packed wire stays under 0.3x — the paper's
    ``(Zq + Z + 32)``-bit format at q <= 8 with bit-packed signs is
    analytically ~0.28x of fp32.

    ``downlink`` ('off'/'quant'/'delta') threads the broadcast leg into
    both lowered rounds, so the gate holds for the full round-trip wire
    discipline (default 'quant', matching the CI leg).
    """
    import json as _json
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a 512-host-device dry run: CPU only, never the parent's accelerator
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               BENCH_DOWNLINK=downlink, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _WIRE_RATIO_SCRIPT],
            capture_output=True, text=True, timeout=timeout, env=env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return [("flround_wire_ratio[2x16x16]", 0.0,
                 f"FAILED:timeout_after_{timeout}s")]
    line = next(
        (l for l in proc.stdout.splitlines() if l.startswith("WIRE_RATIO ")),
        None,
    )
    if proc.returncode != 0 or line is None:
        return [("flround_wire_ratio[2x16x16]", 0.0,
                 f"FAILED:{proc.stderr[-200:]}")]
    res = _json.loads(line[len("WIRE_RATIO "):])
    # a parse failure that dumps the uplink into unattributed_bytes (or
    # zeroes the denominator) must fail loudly, not pass vacuously
    assert res["fp32"] > 0 and res["packed"] > 0, res
    assert max(res["fp32_unattr"], res["packed_unattr"]) < 0.1 * res["fp32"], (
        f"replica-group attribution degraded: {res}"
    )
    ratio = res["packed"] / res["fp32"]
    assert ratio < 0.3, (
        f"inter-pod wire ratio regressed: {ratio:.3f} >= 0.3 "
        f"(packed={res['packed']:.0f}B fp32={res['fp32']:.0f}B)"
    )
    return [(
        f"flround_wire_ratio[llama3_8b,2x16x16,downlink={downlink}]", 0.0,
        f"inter_pod_ratio={ratio:.4f};u8_bytes={res['packed']:.0f}"
        f";fp32_bytes={res['fp32']:.0f};assert=lt0.3",
    )]


_MOE_A2A_SCRIPT = """
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.configs import get_config
from repro.dist.hlo_analysis import (
    inter_axis_bytes, pod_partition_map, weighted_collectives,
)
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models.config import INPUT_SHAPES
from repro.optim import adamw

cfg = get_config("granite_moe_1b_a400m")
mesh = make_production_mesh(shape=(2, 8, 2, 16))   # pod x data x seq x model
hlo = steps.lower_train_step(
    cfg, mesh, INPUT_SHAPES["train_512"], adamw(3e-4)
).compile().as_text()
coll = weighted_collectives(hlo)
split = inter_axis_bytes(hlo, pod_partition_map(mesh))
print("MOE_A2A " + json.dumps({
    "count": coll["counts"].get("all-to-all", 0),
    "bytes": coll["bytes"].get("all-to-all", 0.0),
    "intra_bytes": split["intra_by_kind"].get("all-to-all", 0.0),
    "inter_bytes": split["inter_by_kind"].get("all-to-all", 0.0),
}))
"""


def bench_moe_alltoall(timeout: int = 1800) -> list[tuple]:
    """ROADMAP expert-parallel item: on the 4D (pod, data, seq, model)
    mesh the MoE dispatch must lower to all-to-alls over the expert axis
    (granite 32e on the 16-wide model axis), and — because the model axis
    is innermost in the device order — that dispatch traffic must stay
    intra-pod (the inter-pod links carry the FL uplink, not expert
    routing). Runs in a subprocess for the 512-device XLA flag."""
    import json as _json
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _MOE_A2A_SCRIPT],
            capture_output=True, text=True, timeout=timeout, env=env, cwd=root,
        )
    except subprocess.TimeoutExpired:
        return [("moe_alltoall[granite,2x8x2x16]", 0.0,
                 f"FAILED:timeout_after_{timeout}s")]
    line = next(
        (l for l in proc.stdout.splitlines() if l.startswith("MOE_A2A ")), None,
    )
    if proc.returncode != 0 or line is None:
        return [("moe_alltoall[granite,2x8x2x16]", 0.0,
                 f"FAILED:{proc.stderr[-200:]}")]
    res = _json.loads(line[len("MOE_A2A "):])
    assert res["count"] > 0, f"no all-to-all in the expert-sharded MoE: {res}"
    # the expert dispatch rides the model axis (innermost, intra-pod); a
    # small residue of batch-dim resharding over (pod, data) may cross
    # pods, but it must stay noise next to the dispatch traffic
    inter_frac = res["inter_bytes"] / max(res["bytes"], 1.0)
    assert inter_frac < 0.01, (
        f"expert dispatch leaked onto the inter-pod links: {res}"
    )
    return [(
        "moe_alltoall[granite_moe_1b_a400m,2x8x2x16]", 0.0,
        f"a2a_ops={res['count']};a2a_bytes={res['bytes']:.0f}"
        f";intra_pod_bytes={res['intra_bytes']:.0f}"
        f";inter_frac={inter_frac:.4f};assert=lt0.01",
    )]


def bench_kernels(ledger=None) -> list[tuple]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.obs import default_ledger, timed_phase

    led = ledger if ledger is not None else default_ledger()
    rows = []
    key = jax.random.PRNGKey(0)
    flat = jax.random.normal(key, (1 << 20,))  # 1M params
    for q in (2, 4, 8):
        warm = lambda: jax.block_until_ready(ops.quantize_flat(key, flat, q))
        n = 5
        with timed_phase("kernel_quantize", led, warmup=warm, q=q, n=n) as t:
            for _ in range(n):
                jax.block_until_ready(ops.quantize_flat(key, flat, q))
        us = t.seconds / n * 1e6
        # wire size vs fp32 baseline (paper eq. 5)
        ratio = (flat.size * q + flat.size + 32) / (flat.size * 32)
        rows.append((f"kernel_quantize[q={q},Z=1M]", us, f"wire_ratio={ratio:.3f}"))
    idx, signs, scale = ops.quantize_flat(key, flat, 4)
    k = 8
    idxs = jnp.broadcast_to(idx, (k,) + idx.shape)
    sgns = jnp.broadcast_to(signs, (k,) + signs.shape)
    scales = jnp.full((k,), scale)
    w = jnp.full((k,), 1.0 / k)
    agg = lambda: jax.block_until_ready(
        ops.aggregate_uploads(idxs, sgns, scales, w, 4)
    )
    with timed_phase("kernel_aggregate", led, warmup=agg, k=k, n=3) as t:
        for _ in range(3):
            agg()
    rows.append((
        f"kernel_aggregate[K={k},Z=1M]", t.seconds / 3 * 1e6,
        "fused=dequant+weighted_sum",
    ))
    return rows


def main() -> None:
    import argparse

    from benchmarks import fl_benchmarks as flb
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import default_ledger, maybe_trace

    ap = argparse.ArgumentParser()
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="JSONL run-ledger path (default: $REPRO_LEDGER)")
    ap.add_argument("--xprof", default=None, metavar="DIR",
                    help="capture a profiler trace of the kernel microbench")
    args = ap.parse_args()
    enable_compile_cache()
    ledger = default_ledger(args.ledger)
    ledger.run_header(name="benchmarks.run", entry="run.main")

    t_start = time.time()
    print("name,us_per_call,derived", flush=True)

    def emit(rows):
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)

    # Child processes first: the attention children execute on the
    # device, and a chip belongs to one process at a time, so this parent
    # touches no backend until they have exited (the dry-run children pin
    # the CPU platform themselves). Attention rows are CPU-sized cells;
    # the full matrix incl. the 128k cell and the 500k ring record is
    #   PYTHONPATH=src python benchmarks/attn_benchmarks.py --json
    from benchmarks import attn_benchmarks as attnb

    emit(attnb.bench_flash_attention(quick=True, record_json=False))
    emit(bench_wire_ratio())
    emit(bench_moe_alltoall())

    from benchmarks import sim_benchmarks as simb

    with maybe_trace(args.xprof):
        emit(bench_kernels(ledger=ledger))
    # CPU-sized fleet rows; the 1024-client scale run is
    #   PYTHONPATH=src python benchmarks/sim_benchmarks.py --clients 1024
    # (add --policy=ga for the compiled Algorithm-1 population search;
    # --json records the rows into BENCH_sim.json)
    emit(simb.bench_fleet_scale(u=64, n_rounds=10, batch_size=8,
                                n_channels=8))
    emit(simb.bench_fleet_scale(u=32, n_rounds=4, batch_size=8, policy="ga",
                                n_channels=8, ga_generations=8,
                                ga_population=12))
    # QCCF vs compiled baselines at matched accuracy (CPU-sized; the
    # paper-scale U=1024 comparison is
    #   PYTHONPATH=src python benchmarks/sim_benchmarks.py --baseline \
    #       --scenario cellfree_a4 --clients 1024 --rounds 20 --json
    # which also records rows into BENCH_sim.json)
    emit(simb.bench_baseline_energy(u=64, n_rounds=10, batch_size=8,
                                    n_channels=8, scenario="single_bs"))
    emit(simb.bench_sim_vs_object(u=8, n_rounds=10))
    emit(flb.bench_v_tradeoff(task="tiny", n_rounds=10))
    emit(flb.bench_task("femnist", betas=(300.0,), n_rounds=6))
    emit(flb.bench_task("tiny", betas=(150.0, 300.0), n_rounds=12))
    emit(flb.bench_quant_levels(task="femnist", n_rounds=8))

    try:
        from benchmarks.roofline import bench_rooflines

        emit(bench_rooflines())
    except FileNotFoundError:
        emit([("roofline", 0.0, "dryrun.jsonl missing (run dryrun_sweep)")])

    print(f"# total wall: {time.time() - t_start:.1f}s", flush=True)


if __name__ == "__main__":
    main()
