"""Attention-path benchmarks: chunked vs flash vs ring (ISSUE 10).

Each executed combo runs in its OWN subprocess so peak RSS is
attributable to that (impl, seq) pair — on the CPU container there is no
device memory_stats(), so ``ru_maxrss`` is the peak-memory proxy; the
jax runtime + inputs baseline is constant across impls at a given seq,
so the *delta* between impls is the score/expanded-KV materialization.

Row protocol (appended to BENCH_sim.json via ``write_bench_json``):

  {"name": "attn[<impl>,S=<seq>,H=<h>,KV=<kv>,w=<window>]",
   "bench": "flash_attention", "phase": "pre_pr10_baseline" | "pr10",
   "impl", "seq", "heads", "kv_heads", "head_dim", "chunk", "window",
   "us_per_call", "tokens_per_s", "peak_rss_mb", ...}

The pre-PR chunked rows are recorded FIRST (``--record-baseline``,
before the flash kernel lands) so the >= 2x tokens/s acceptance at 32k
is measured against a committed baseline, not asserted after the fact.
Because subprocess-to-subprocess machine drift (±15-20% on a shared
container) rivals the measured gaps, each cell also records an
``attn[flash_vs_chunked,...,interleaved]`` row: one subprocess
alternates the two jitted impls iteration by iteration, so drift
cancels in the ratio — the >= 2x gate reads that row.

Head counts shrink with seq so the single-core container finishes each
matrix cell in ~seconds-to-minutes (the FLOP count per cell stays
roughly constant); the counts ride in every row so comparisons are
always within a cell, never across seq lengths.

The 500k ring row is lower+compile only (execution is a TPU job): an
8-way ``seq`` mesh, ring flash via ``lax.ppermute``, with per-device
peak from ``memory_analysis()`` plus the ``no_s2_scores`` HLO gate and
the collective-permute count (neighbor-local transfers only).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from benchmarks.sim_benchmarks import write_bench_json  # noqa: E402


_EXEC_SCRIPT = r"""
import json, os, resource, time
import jax, jax.numpy as jnp
from repro.models import layers

impl = os.environ["ATTN_IMPL"]
S = int(os.environ["ATTN_S"]); H = int(os.environ["ATTN_H"])
KV = int(os.environ["ATTN_KV"]); HD = int(os.environ["ATTN_HD"])
CHUNK = int(os.environ["ATTN_CHUNK"]); W = int(os.environ["ATTN_W"])
ITERS = int(os.environ["ATTN_ITERS"])
B = 1
kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
q = 0.3 * jax.random.normal(kq, (B, S, H, HD), jnp.float32)
k = 0.3 * jax.random.normal(kk, (B, S, KV, HD), jnp.float32)
v = jax.random.normal(kv_, (B, S, KV, HD), jnp.float32)
if impl == "chunked":
    fn = lambda q, k, v: layers.chunked_attention(
        q, k, v, chunk=CHUNK, causal=True, window=W)
elif impl == "chunked_skip":
    fn = lambda q, k, v: layers.chunked_attention(
        q, k, v, chunk=CHUNK, causal=True, window=W, causal_skip=True)
elif impl == "flash":
    fn = lambda q, k, v: layers.flash_attention(
        q, k, v, block_q=CHUNK, block_k=CHUNK, causal=True, window=W)
elif impl == "dense":
    fn = lambda q, k, v: layers.dense_attention(
        q, k, v, causal=True, window=W)
else:
    raise SystemExit("unknown impl " + impl)
f = jax.jit(fn)
t0 = time.time(); jax.block_until_ready(f(q, k, v)); warm_s = time.time() - t0
t0 = time.time()
for _ in range(ITERS):
    jax.block_until_ready(f(q, k, v))
dt = (time.time() - t0) / ITERS
print("ATTN_BENCH " + json.dumps({
    "impl": impl, "seq": S, "heads": H, "kv_heads": KV, "head_dim": HD,
    "chunk": CHUNK, "window": W, "iters": ITERS,
    "us_per_call": dt * 1e6, "tokens_per_s": B * S / dt,
    "warm_s": round(warm_s, 2),
    "peak_rss_mb":
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
}))
"""


_PAIRED_SCRIPT = r"""
import json, os, time
import jax, jax.numpy as jnp
from repro.models import layers

S = int(os.environ["ATTN_S"]); H = int(os.environ["ATTN_H"])
KV = int(os.environ["ATTN_KV"]); HD = int(os.environ["ATTN_HD"])
CHUNK = int(os.environ["ATTN_CHUNK"]); W = int(os.environ["ATTN_W"])
ITERS = int(os.environ["ATTN_ITERS"])
B = 1
kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
q = 0.3 * jax.random.normal(kq, (B, S, H, HD), jnp.float32)
k = 0.3 * jax.random.normal(kk, (B, S, KV, HD), jnp.float32)
v = jax.random.normal(kv_, (B, S, KV, HD), jnp.float32)
base = jax.jit(lambda q, k, v: layers.chunked_attention(
    q, k, v, chunk=CHUNK, causal=True, window=W))
fl = jax.jit(lambda q, k, v: layers.flash_attention(
    q, k, v, block_q=CHUNK, block_k=CHUNK, causal=True, window=W))
jax.block_until_ready(base(q, k, v))
jax.block_until_ready(fl(q, k, v))
bt, ft = [], []
for _ in range(ITERS):
    t0 = time.time(); jax.block_until_ready(base(q, k, v))
    bt.append(time.time() - t0)
    t0 = time.time(); jax.block_until_ready(fl(q, k, v))
    ft.append(time.time() - t0)
b_dt = sum(bt) / ITERS; f_dt = sum(ft) / ITERS
print("ATTN_PAIR " + json.dumps({
    "impl": "flash_vs_chunked", "seq": S, "heads": H, "kv_heads": KV,
    "head_dim": HD, "chunk": CHUNK, "window": W, "iters": ITERS,
    "chunked_tokens_per_s": B * S / b_dt,
    "flash_tokens_per_s": B * S / f_dt,
    "speedup_vs_chunked": round(b_dt / f_dt, 3),
}))
"""


_RING_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
import numpy as np
from repro.kernels.flash_attention import ring_flash_attention
from repro.dist.hlo_analysis import no_s2_scores, weighted_collectives

S = int(os.environ.get("ATTN_S", "524288")); B, H, KV, HD = 1, 1, 1, 64
BLK = int(os.environ.get("ATTN_CHUNK", "512"))
mesh = Mesh(np.array(jax.devices()).reshape(8), ("seq",))
n_sh = 8
spec = P(None, "seq", None, None)

def attn(q, k, v):
    return ring_flash_attention(
        q, k, v, axis_name="seq", axis_size=n_sh, causal=True,
        block_q=BLK, block_k=BLK)

f = jax.jit(jax.shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False))
args = [jax.ShapeDtypeStruct((B, S, H, HD), jnp.float32),
        jax.ShapeDtypeStruct((B, S, KV, HD), jnp.float32),
        jax.ShapeDtypeStruct((B, S, KV, HD), jnp.float32)]
import time
t0 = time.time(); lowered = f.lower(*args); lower_s = time.time() - t0
t0 = time.time(); compiled = lowered.compile(); compile_s = time.time() - t0
hlo = compiled.as_text()
mem = compiled.memory_analysis()
offenders = no_s2_scores(hlo, S // n_sh)
coll = weighted_collectives(hlo)
print("RING_BENCH " + json.dumps({
    "seq": S, "n_shards": n_sh, "block": BLK,
    "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
    "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
    "arg_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
    "s2_offenders": len(offenders),
    "collective_permute_ops":
        coll["counts"].get("collective-permute", 0),
    "allgather_ops": coll["counts"].get("all-gather", 0),
    "collective_permute_bytes":
        coll["bytes"].get("collective-permute", 0.0),
}))
"""


def _subprocess_json(script: str, tag: str, env_extra: dict, timeout: int):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), **env_extra)
    env.pop("XLA_FLAGS", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"timeout_after_{timeout}s"}
    line = next(
        (l for l in proc.stdout.splitlines() if l.startswith(tag + " ")), None
    )
    if proc.returncode != 0 or line is None:
        return {"error": (proc.stderr or "no output")[-300:]}
    return json.loads(line[len(tag) + 1:])


def _run_exec(impl: str, s: int, h: int, kv: int, *, hd: int = 64,
              chunk: int = 512, window: int = 0, iters: int = 1,
              timeout: int = 900) -> dict:
    return _subprocess_json(
        _EXEC_SCRIPT, "ATTN_BENCH",
        {
            "ATTN_IMPL": impl, "ATTN_S": str(s), "ATTN_H": str(h),
            "ATTN_KV": str(kv), "ATTN_HD": str(hd),
            "ATTN_CHUNK": str(chunk), "ATTN_W": str(window),
            "ATTN_ITERS": str(iters),
        },
        timeout,
    )


# (seq, heads, kv_heads, iters): FLOPs/cell stay ~constant as seq grows.
EXEC_MATRIX = (
    (4_096, 4, 1, 3),
    (32_768, 2, 1, 3),
    (131_072, 1, 1, 1),
)


def _run_pair(s: int, h: int, kv: int, *, hd: int = 64, chunk: int = 512,
              window: int = 0, iters: int = 2, timeout: int = 1800) -> dict:
    return _subprocess_json(
        _PAIRED_SCRIPT, "ATTN_PAIR",
        {
            "ATTN_S": str(s), "ATTN_H": str(h), "ATTN_KV": str(kv),
            "ATTN_HD": str(hd), "ATTN_CHUNK": str(chunk),
            "ATTN_W": str(window), "ATTN_ITERS": str(iters),
        },
        timeout,
    )


def bench_attention_impls(
    impls: tuple = ("chunked", "chunked_skip", "flash"),
    *, quick: bool = False, phase: str = "pr10", json_rows: list | None = None,
) -> list[tuple]:
    """Executed tokens/s + peak-RSS matrix. Unavailable impls (flash
    before the kernel lands) are skipped silently — that is what makes
    the same harness usable for the pre-PR baseline record.

    Each (impl, seq) runs in its own subprocess so peak RSS is
    attributable, but the subprocess-to-subprocess machine drift on a
    shared container (±15-20% run to run) is comparable to the gaps
    being measured — so each cell additionally records a
    ``flash_vs_chunked`` row from ONE subprocess that alternates the two
    jitted impls iteration by iteration. Drift hits both sides of that
    ratio equally; it is the acceptance record for the >= 2x bar."""
    from repro.models import layers

    have = [i for i in impls
            if i != "flash" or hasattr(layers, "flash_attention")]
    matrix = EXEC_MATRIX[:2] if quick else EXEC_MATRIX
    rows: list[tuple] = []
    by_key: dict = {}
    for s, h, kv, iters in matrix:
        for impl in have:
            r = _run_exec(impl, s, h, kv, iters=iters)
            name = f"attn[{impl},S={s},H={h},KV={kv},w=0]"
            if "error" in r:
                rows.append((name, 0.0, f"FAILED:{r['error']}"))
                continue
            by_key[(impl, s)] = r
            derived = (
                f"tokens_per_s={r['tokens_per_s']:.0f}"
                f";peak_rss_mb={r['peak_rss_mb']:.0f}"
            )
            base = by_key.get(("chunked", s))
            if impl != "chunked" and base:
                speed = r["tokens_per_s"] / base["tokens_per_s"]
                derived += f";speedup_vs_chunked={speed:.2f}x"
                r["speedup_vs_chunked"] = round(speed, 3)
            rows.append((name, r["us_per_call"], derived))
            if json_rows is not None:
                json_rows.append({
                    "name": name, "bench": "flash_attention", "phase": phase,
                    **{k: v for k, v in r.items()},
                })
        if "flash" in have:
            pr = _run_pair(s, h, kv, iters=max(iters, 2))
            name = f"attn[flash_vs_chunked,S={s},H={h},KV={kv},interleaved]"
            if "error" in pr:
                rows.append((name, 0.0, f"FAILED:{pr['error']}"))
                continue
            rows.append((name, 0.0, (
                f"chunked={pr['chunked_tokens_per_s']:.0f}"
                f";flash={pr['flash_tokens_per_s']:.0f}"
                f";speedup_vs_chunked={pr['speedup_vs_chunked']:.2f}x"
            )))
            if json_rows is not None:
                json_rows.append({
                    "name": name, "bench": "flash_attention", "phase": phase,
                    **{k: v for k, v in pr.items()},
                })
    return rows


def bench_ring_500k(*, seq: int = 524_288, block: int = 4096,
                    timeout: int = 1800, phase: str = "pr10",
                    json_rows: list | None = None) -> list[tuple]:
    """Lower+compile the ring variant at 500k on an 8-way seq mesh (no
    execution — that is a TPU job): per-device temp bytes, the
    no_s2_scores gate, and the ppermute count are the record. ``block``
    is larger than the executed cells' 512 to keep the per-shard q-block
    unroll (S/8/block scans x 8 ring steps) tractable to trace."""
    r = _subprocess_json(_RING_SCRIPT, "RING_BENCH",
                         {"ATTN_S": str(seq), "ATTN_CHUNK": str(block),
                          "JAX_PLATFORMS": "cpu"},
                         timeout)
    name = f"attn[ring_flash,S={seq},seq_mesh=8,lower_only]"
    if "error" in r:
        return [(name, 0.0, f"FAILED:{r['error']}")]
    assert r["s2_offenders"] == 0, (
        f"ring flash at {seq} still carries an S^2-sized per-device "
        f"tensor: {r}"
    )
    assert r["collective_permute_ops"] > 0 and r["allgather_ops"] == 0, (
        f"ring must move K/V by neighbor ppermute, not gather: {r}"
    )
    derived = (
        f"compile_s={r['compile_s']};temp_mb_per_device="
        f"{(r['temp_bytes_per_device'] or 0) / 1e6:.0f}"
        f";ppermute_ops={r['collective_permute_ops']}"
        f";allgather_ops=0;s2_offenders=0"
    )
    if json_rows is not None:
        json_rows.append({
            "name": name, "bench": "flash_attention", "phase": phase, **r,
        })
    return [(name, 0.0, derived)]


def bench_flash_attention(*, quick: bool = False, record_json: bool = True,
                          phase: str = "pr10") -> list[tuple]:
    """run.py entry: the executed impl matrix + the 500k ring record.
    At 32k flash must show >= 2x tokens/s over the default (rectangular)
    chunked path — the ISSUE 10 acceptance bar, gated on the
    drift-cancelled interleaved row."""
    json_rows: list = []
    rows = bench_attention_impls(quick=quick, phase=phase,
                                 json_rows=json_rows)
    rows += bench_ring_500k(phase=phase, json_rows=json_rows)
    if record_json and json_rows:
        write_bench_json(json_rows)
    pair32 = next((r for r in json_rows
                   if r.get("impl") == "flash_vs_chunked"
                   and r.get("seq") == 32_768), None)
    if pair32 is not None and "speedup_vs_chunked" in pair32:
        assert pair32["speedup_vs_chunked"] >= 2.0, (
            "flash at 32k must be >= 2x chunked tokens/s (interleaved "
            f"measurement), got {pair32['speedup_vs_chunked']}x"
        )
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--record-baseline", action="store_true",
                    help="pre-PR record: chunked-only rows tagged "
                         "phase=pre_pr10_baseline (run BEFORE the flash "
                         "kernel lands)")
    ap.add_argument("--quick", action="store_true",
                    help="skip the 128k cell (CI-sized run)")
    ap.add_argument("--no-ring", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="append rows to BENCH_sim.json")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="also write the rows as JSON lines (CI artifact)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()  # the device-side children inherit it
    print("name,us_per_call,derived", flush=True)
    json_rows: list = []
    if args.record_baseline:
        rows = bench_attention_impls(
            ("chunked", "chunked_skip"), quick=args.quick,
            phase="pre_pr10_baseline", json_rows=json_rows,
        )
    else:
        rows = bench_attention_impls(quick=args.quick, json_rows=json_rows)
        if not args.no_ring:
            rows += bench_ring_500k(json_rows=json_rows)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    if args.json and json_rows:
        write_bench_json(json_rows)
        print(f"# {len(json_rows)} rows -> BENCH_sim.json", flush=True)
    if args.jsonl and json_rows:
        os.makedirs(os.path.dirname(args.jsonl) or ".", exist_ok=True)
        with open(args.jsonl, "a") as f:
            for row in json_rows:
                f.write(json.dumps(row, default=str) + "\n")
        print(f"# {len(json_rows)} rows -> {args.jsonl}", flush=True)


if __name__ == "__main__":
    main()
