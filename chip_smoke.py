#!/usr/bin/env python3
"""Chip smoke test: the QCCF fleet scan at FEMNIST width on a TPU.

Drives the repo's main path once through its public entry point
(``repro.sim.build_sim`` -> ``FleetSim.run_compiled``) at the paper's
FEMNIST width (Z = 246590, U = 1024 clients, C = 8 channels, synthetic
data and random initial weights made from ``--seed``) and checks what
comes out:

  kernel   the compiled Pallas aggregate at K = 8, M = 1984 vs the dense
           oracle ``kernels.ref.aggregate_ref`` (rtol 1e-5);
  greedy   3 rounds of the compiled greedy + KKT policy, with eval;
  ga       2 rounds of the in-scan genetic search (Algorithm 1).

Every phase prints its device kind, setup / compile / run seconds
(compilation is timed apart from execution) and the device's peak bytes
in use. Any failed check raises, so the exit code is non-zero; the last
line of a passing run is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

  python chip_smoke.py [--seed N]       # one chip, the phases above
  python chip_smoke.py --chips 4        # only: unsharded scan on device 0
                                        # vs the same scan sharded over a
                                        # 4-device mesh (shard_clients)

There is no CPU fallback: the script exits non-zero, printing no result,
when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ref as kref  # noqa: E402
from repro.kernels import stochastic_quant as sq  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.sim import build_sim, engine  # noqa: E402

TASK = "femnist"
FEMNIST_Z = 246590  # CNN parameters, paper Table I
SCENARIO = "single_bs"
N_CLIENTS = 1024
N_CHANNELS = 8
BLOCK_M = 64
GREEDY_ROUNDS = 3
GA_ROUNDS = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def kernel_phase(seed: int) -> None:
    """Compiled fused dequant + weighted sum vs the dense oracle."""
    k = N_CHANNELS
    m = engine._pad_len(FEMNIST_Z, BLOCK_M) // sq.LANES
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.randint(keys[0], (k,), 1, 9)
    levels = (2 ** q - 1)[:, None, None]
    idx = (jax.random.randint(keys[1], (k, m, sq.LANES), 0, 256)
           % (levels + 1)).astype(jnp.uint8)
    signs = jax.random.randint(keys[2], (k, m, sq.LANES), 0, 2).astype(
        jnp.uint8)
    scales = jax.random.uniform(keys[3], (k,), minval=0.1, maxval=2.0)
    weights = jax.random.dirichlet(keys[4], jnp.ones((k,)))
    fn = jax.jit(lambda *a: sq.aggregate(*a, block_m=BLOCK_M))
    t0 = time.perf_counter()
    compiled = fn.lower(idx, signs, scales, weights, q).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    t0 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(
        compiled(idx, signs, scales, weights, q)))
    run_s = time.perf_counter() - t0
    with jax.default_matmul_precision("float32"):
        want = np.asarray(kref.aggregate_ref(idx, signs, scales, weights, q))
    err = float(np.max(np.abs(out - want)))
    log(f"kernel: aggregate K={k} M={m} compile_s={compile_s:.3f} "
        f"run_s={run_s:.4f} tpu_custom_call={has_kernel} max_abs_err={err:.3e}")
    check(has_kernel, "aggregate did not compile to a Mosaic kernel")
    check(out.shape == (m, sq.LANES), f"aggregate shape {out.shape}")
    np.testing.assert_allclose(out, want, rtol=1e-5,
                               atol=1e-5 * float(np.max(np.abs(want))))


def build(policy: str, seed: int):
    t0 = time.perf_counter()
    sim = build_sim(TASK, scenario=SCENARIO, n_clients=N_CLIENTS,
                    n_channels=N_CHANNELS, seed=seed, policy_mode=policy,
                    block_m=BLOCK_M)
    jax.block_until_ready(sim.fleet.x)
    setup_s = time.perf_counter() - t0
    log(f"{policy}: setup_s={setup_s:.2f} U={sim.fleet.n_clients} "
        f"C={sim.channel.params.n_channels} z={sim.z} "
        f"fleet_x={tuple(sim.fleet.x.shape)} fleet_bytes={sim.fleet.x.nbytes}")
    return sim


def check_result(res, n_rounds: int, u: int, c: int, q_cap: int,
                 with_eval: bool) -> None:
    check(res.energy.shape == (n_rounds,), f"energy shape {res.energy.shape}")
    check(res.q_levels.shape == (n_rounds, u), f"q shape {res.q_levels.shape}")
    check(np.all(np.isfinite(res.energy)) and np.all(res.energy >= 0),
          f"energy not finite and non-negative: {res.energy}")
    check(np.all(np.isfinite(res.loss)), f"loss not finite: {res.loss}")
    check(np.all((res.accuracy >= 0) & (res.accuracy <= 1)),
          f"accuracy outside [0, 1]: {res.accuracy}")
    if with_eval:
        check(np.all(res.loss > 0), f"eval loss not positive: {res.loss}")
    check(np.all((res.n_scheduled >= 0) & (res.n_scheduled <= c)),
          f"n_scheduled outside [0, {c}]: {res.n_scheduled}")
    check(np.array_equal(res.energy > 0, res.n_scheduled > 0),
          "energy spent in a round with nobody scheduled, or none spent "
          "in a round with a schedule")
    sched = res.q_levels > 0
    check(np.array_equal(sched.sum(axis=1), res.n_scheduled),
          "q_levels and n_scheduled disagree")
    q = res.q_levels[sched]
    check(np.all((q >= 1) & (q <= q_cap)), f"q outside [1, {q_cap}]: {q}")
    check(np.all(res.rates[sched] > 0) and np.all(res.rates[~sched] == 0),
          "assigned rates do not match the schedule")
    check(np.all(np.isfinite(res.lambda1)) and np.all(np.isfinite(res.lambda2)),
          "Lyapunov queues not finite")


def scan_phase(sim, n_rounds: int, *, with_eval: bool = True):
    """AOT-compile the scan (timed alone, text checked for the Mosaic
    kernel), then run it through ``run_compiled`` twice: both calls reuse
    that executable (``FleetSim.lower`` and ``run_compiled`` share one
    jitted scan), and the second must repeat the first bit for bit."""
    t0 = time.perf_counter()
    compiled = sim.lower(n_rounds, with_eval=with_eval).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    del compiled
    t0 = time.perf_counter()
    res = sim.run_compiled(n_rounds, with_eval=with_eval)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = sim.run_compiled(n_rounds, with_eval=with_eval)
    run_s = time.perf_counter() - t0
    log(f"{sim.policy_mode}: rounds={n_rounds} compile_s={compile_s:.2f} "
        f"first_call_s={first_s:.2f} run_s={run_s:.3f} "
        f"tpu_custom_call={has_kernel} "
        f"temp_bytes={getattr(mem, 'temp_size_in_bytes', None)} "
        f"arg_bytes={getattr(mem, 'argument_size_in_bytes', None)} "
        f"peak_bytes_in_use={peak_bytes()}")
    for n in range(n_rounds):
        log(f"{sim.policy_mode}: round={n} energy={res.energy[n]!r} "
            f"accuracy={res.accuracy[n]!r} loss={res.loss[n]!r} "
            f"n_scheduled={int(res.n_scheduled[n])} "
            f"q={sorted(res.q_levels[n][res.q_levels[n] > 0].tolist())}")
    check(has_kernel, "the compiled scan holds no Mosaic kernel "
                      "(aggregate ran interpreted)")
    check_result(res, n_rounds, sim.fleet.n_clients,
                 sim.channel.params.n_channels, sim.q_cap, with_eval)
    for field in ("energy", "accuracy", "loss", "q_levels", "lambda2"):
        check(np.array_equal(getattr(res, field), getattr(again, field)),
              f"second run of the same scan differs in {field}")
    return res


def sharded_parity_phase(seed: int, n_devices: int) -> None:
    """The same scan unsharded on device 0, then with the client axis
    sharded over an n-device mesh; compared with the tolerances of the
    shard-parity test (tests/test_sim_engine.py)."""
    from jax.sharding import Mesh

    devices = jax.devices()
    check(len(devices) >= n_devices,
          f"need {n_devices} devices, found {len(devices)}")
    sim = build("greedy", seed)
    t0 = time.perf_counter()
    base = sim.run_compiled(GREEDY_ROUNDS)
    base_s = time.perf_counter() - t0
    mesh = Mesh(np.array(devices[:n_devices]), ("data",))
    sim.shard_clients(mesh, axis="data")
    check(len(sim.fleet.x.sharding.device_set) == n_devices,
          f"fleet placed on {sim.fleet.x.sharding}")
    t0 = time.perf_counter()
    res = sim.run_compiled(GREEDY_ROUNDS)
    sharded_s = time.perf_counter() - t0
    check_result(res, GREEDY_ROUNDS, sim.fleet.n_clients,
                 sim.channel.params.n_channels, sim.q_cap, True)
    np.testing.assert_array_equal(res.q_levels, base.q_levels)
    np.testing.assert_array_equal(res.n_scheduled, base.n_scheduled)
    np.testing.assert_allclose(res.energy, base.energy, rtol=1e-6)
    np.testing.assert_allclose(res.rates, base.rates, rtol=1e-6)
    np.testing.assert_allclose(res.lambda2, base.lambda2, rtol=1e-5, atol=1e-9)
    log(f"sharded: mesh={n_devices} unsharded_first_call_s={base_s:.2f} "
        f"sharded_first_call_s={sharded_s:.2f} "
        f"max_abs_d_energy={float(np.max(np.abs(res.energy - base.energy))):.3e} "
        f"max_abs_d_accuracy="
        f"{float(np.max(np.abs(res.accuracy - base.accuracy))):.3e} "
        f"peak_bytes_in_use={peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-unsharded scan parity")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); "
              "this script does not fall back", file=sys.stderr)
        return 2
    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache()}")

    if args.chips == 4:
        sharded_parity_phase(args.seed, 4)
    else:
        kernel_phase(args.seed)
        sim = build("greedy", args.seed)
        scan_phase(sim, GREEDY_ROUNDS)
        del sim
        gc.collect()
        sim = build("compiled-ga", args.seed)
        scan_phase(sim, GA_ROUNDS)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
