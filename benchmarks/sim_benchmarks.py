"""Fleet-scale benchmarks for the compiled simulator (repro.sim).

The headline entry runs U = 1024 clients, C = 8 uplink channels (the
paper's C << U regime) for >= 20 QCCF rounds through the single jitted
``lax.scan`` — one compile, no per-client Python objects, and per-round
work compacted to the S = min(U, C) scheduled slots — and reports
rounds/sec with compile time split out:

    PYTHONPATH=src python benchmarks/sim_benchmarks.py --clients 1024 --rounds 20

``--policy=ga`` swaps the greedy fast path for the fully compiled GA
(``repro.sim.search``) — the whole Algorithm 1 population search runs inside
the same one-compile scan; the four paper baselines (``no_quant``,
``channel_allocate``, ``principle``, ``same_size``) are also valid
``--policy`` values and run as traced decision functions in the same scan.
``--scenario`` selects a registered scenario preset (``single_bs``,
``cellfree_a4``, ``noniid_a01`` — see ``repro.sim.scenario``); ``--baseline``
runs the QCCF-vs-baselines energy/accuracy comparison on one scenario
(``bench_baseline_energy``). ``--dry-run`` traces + lowers the full scan
without executing (the CI manual-dispatch job uses this: lowering success is
the gate, no CPU burn). ``--outage-p/--outage-corr/--fade-p/--corrupt-p/
--nan-p`` build a ``FaultSpec`` and run the scan with in-scan fault
injection + the graceful-degradation screen; ``--fault-overhead`` runs the
clean-vs-faulty pair and records the rounds/s overhead of the fault
machinery (budget <= 10%) plus energy-to-matched-accuracy. ``--json`` appends machine-readable rows to
``BENCH_sim.json`` at the repo root (rounds/sec, compile_s, U, C, policy,
scenario, aggregator) so the perf trajectory across PRs stays recorded.

Telemetry (``repro.obs``): ``--telemetry`` builds the sim with the in-scan
metric taps on (still one compile — the taps ride the scan as extra ys);
``--ledger PATH`` (or the ``REPRO_LEDGER`` env var) streams the run header,
per-round rows, and phase timings to the structured JSONL ledger; ``--xprof
DIR`` captures a profiler trace of ONLY the steady-state rounds (compile
excluded), attributed to the named scopes (``pallas_quantize``,
``fleet_local_sgd``, ``kkt_solve``, ...).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

BENCH_JSON = os.path.join(ROOT, "BENCH_sim.json")

# benchmark-CLI spelling -> engine policy_mode (baselines pass through)
_POLICY_MODES = {"greedy": "greedy", "ga": "compiled-ga"}
BENCH_POLICIES = ("greedy", "ga", "no_quant", "channel_allocate",
                  "principle", "same_size")


def bench_fleet_scale(
    u: int = 1024,
    n_rounds: int = 20,
    task: str = "tiny",
    n_channels: int | None = 8,
    mu: float = 100.0,
    beta: float = 20.0,
    batch_size: int = 8,
    seed: int = 0,
    dry_run: bool = False,
    with_eval: bool = False,
    policy: str = "greedy",       # see BENCH_POLICIES
    scenario: str | None = None,  # registered preset name, None = legacy
    ga_generations: int = 30,
    ga_population: int = 32,
    json_rows: list | None = None,
    telemetry: bool = False,
    ledger=None,
    xprof: str | None = None,
    downlink: str = "off",
    faults=None,
) -> list[tuple]:
    """U-client QCCF rounds in one compiled scan; rows are run.py-style CSV.

    ``n_channels`` defaults to the paper's sparse uplink (C = 8); pass
    ``None`` for the dense C = U layout. ``scenario`` picks a registered
    preset (topology + heterogeneity + Lyapunov constants travel as one
    pytree through ``build_sim``); ``policy`` can be the greedy fast path,
    the compiled GA, or any traced baseline. When ``json_rows`` is a list,
    a machine-readable record is appended per executed config.

    ``telemetry`` turns the in-scan metric taps on; ``ledger`` (an
    ``repro.obs.Ledger``, default = ``REPRO_LEDGER`` resolution) receives
    the run header, phase timings, and — with telemetry — per-round rows;
    ``xprof`` captures a profiler trace of the steady-state rounds only.
    """
    import jax
    from repro.core.genetic import GAConfig
    from repro.obs import (MetricsConfig, default_ledger, maybe_trace,
                           metrics_to_dict, timed_phase)
    from repro.sim import build_sim

    assert policy in BENCH_POLICIES, policy
    policy_mode = _POLICY_MODES.get(policy, policy)
    ga_config = GAConfig(
        generations=ga_generations, population=ga_population,
        repair_infeasible=True,
    )
    c = u if n_channels is None else int(n_channels)
    scen = scenario or "single_bs"
    tag = f"U={u},C={c},{task},{scen},{policy}"
    if downlink != "off":
        tag += f",dl={downlink}"
    if faults is not None and faults.enabled:
        tag += f",faults=p{faults.outage_p:g}"
    led = ledger if ledger is not None else default_ledger()
    tele = MetricsConfig(enabled=True) if telemetry else None
    rows = []
    with timed_phase("build", led, tag=tag) as t_build:
        sim = build_sim(
            task, scenario=scenario, n_clients=u, n_channels=c, mu=mu,
            beta=beta, seed=seed, batch_size=batch_size, n_test=256,
            policy_mode=policy_mode, ga_config=ga_config, telemetry=tele,
            downlink=downlink, faults=faults,
        )
    led.run_header(
        name=f"sim_fleet[{tag}]", entry="bench_fleet_scale",
        policy=policy_mode, scenario=scen, u=u, c=c, rounds=n_rounds,
        seed=seed, telemetry=bool(telemetry), downlink=downlink,
    )
    rows.append((
        f"sim_build[{tag}]", t_build.seconds * 1e6,
        f"z={sim.z};n_max={sim.fleet.n_max};policy={policy_mode}"
        f";A={sim.channel.n_aps};assoc={sim.channel.association}",
    ))

    keys, ridx = sim._scan_xs(n_rounds)
    carry = sim._init_carry()
    with timed_phase("lower", led, tag=tag, rounds=n_rounds) as t_lower:
        lowered = sim._scan_fn(with_eval).lower(sim.data(), sim._dyn, carry,
                                                keys, ridx)
    hlo_bytes = len(lowered.as_text())
    led.hlo_event(f"sim_lower[{tag}]", {"hlo_bytes": hlo_bytes},
                  rounds=n_rounds)
    rows.append((f"sim_lower[{tag},rounds={n_rounds}]",
                 t_lower.seconds * 1e6, f"hlo_bytes={hlo_bytes}"))
    if dry_run:
        rows.append((f"sim_dryrun[{tag},rounds={n_rounds}]",
                     0.0, "lowered=ok"))
        return rows

    with timed_phase("compile", led, tag=tag, rounds=n_rounds) as t_compile:
        compiled = lowered.compile()
    rows.append((f"sim_compile[{tag},rounds={n_rounds}]",
                 t_compile.seconds * 1e6, "one_compile"))

    with maybe_trace(xprof):
        with timed_phase("run", led, tag=tag, rounds=n_rounds) as t_run:
            (flat, *_), out = compiled(sim.data(), sim._dyn, carry, keys,
                                       ridx)
            jax.block_until_ready(flat)
    run_s = t_run.seconds
    import numpy as np

    n_sched = np.asarray(out["n_scheduled"])
    qs = np.asarray(out["q_levels"])
    mean_q = float(qs[qs > 0].mean()) if (qs > 0).any() else 0.0
    if led.enabled:
        tapped = ({k: np.asarray(v)
                   for k, v in metrics_to_dict(out["metrics"]).items()}
                  if "metrics" in out else {})
        energy = np.asarray(out["energy"])
        for n in range(n_rounds):
            led.round_row(
                n, energy=float(energy[n]), n_scheduled=int(n_sched[n]),
                **{k: float(v[n]) for k, v in tapped.items()},
            )
    rows.append((
        f"sim_fleet[{tag},rounds={n_rounds}]",
        run_s / n_rounds * 1e6,
        f"rounds_per_s={n_rounds / run_s:.3f};mean_sched={n_sched.mean():.1f}"
        f";mean_q={mean_q:.2f};energy_J={float(np.asarray(out['energy']).sum()):.5f}",
    ))
    if json_rows is not None:
        json_rows.append({
            "name": f"sim_fleet[{tag},rounds={n_rounds}]",
            "engine": "active-set-compaction",
            "u": u, "c": c, "rounds": n_rounds, "policy": policy_mode,
            "scenario": scen, "downlink": downlink,
            "aggregator": "pallas-tiled",
            "rounds_per_s": round(n_rounds / run_s, 5),
            "compile_s": round(t_compile.seconds, 3),
            "lower_s": round(t_lower.seconds, 3),
            "run_s": round(run_s, 3),
            "mean_sched": round(float(n_sched.mean()), 2),
            "mean_q": round(mean_q, 3),
        })
        if with_eval:
            # trajectory fields for the downlink-on vs -off parity check
            json_rows[-1]["final_acc"] = round(
                float(np.asarray(out["accuracy"])[-1]), 5)
            json_rows[-1]["final_loss"] = round(
                float(np.asarray(out["loss"])[-1]), 5)
            json_rows[-1]["cum_energy_J"] = round(
                float(np.asarray(out["energy"]).sum()), 6)
    return rows


def bench_baseline_energy(
    u: int = 1024,
    n_rounds: int = 20,
    scenario: str = "single_bs",
    policies: tuple = ("greedy", "no_quant", "channel_allocate", "principle"),
    task: str = "tiny",
    n_channels: int = 8,
    mu: float = 100.0,
    beta: float = 20.0,
    batch_size: int = 8,
    seed: int = 0,
    target_acc: float | None = None,
    ga_generations: int = 8,
    ga_population: int = 12,
    json_rows: list | None = None,
    telemetry: bool = False,
    ledger=None,
) -> list[tuple]:
    """QCCF vs the paper's baselines on ONE scenario, one compile per policy.

    Every policy sees the same scenario pytree, seed, and per-round key
    schedule, so channel draws / client drops / minibatches are identical —
    the only difference is the decision function traced into the scan.
    Records cumulative uplink+compute energy, final accuracy, mean
    realized quantization level, and rounds/energy-to-target-accuracy
    (target defaults to the worst final accuracy across policies, i.e. a
    level every policy reaches — the paper's "matched accuracy" comparison
    of Figs. 3/4). ``telemetry``/``ledger`` thread straight into
    ``build_sim`` — ``run_compiled`` then writes the run header and
    per-round rows itself.
    """
    import numpy as np
    from repro.core.genetic import GAConfig
    from repro.obs import MetricsConfig, default_ledger
    from repro.sim import build_sim

    ga_config = GAConfig(generations=ga_generations, population=ga_population,
                         repair_infeasible=True)
    led = ledger if ledger is not None else default_ledger()
    tele = MetricsConfig(enabled=True) if telemetry else None
    rows = []
    results: dict = {}
    for pol in policies:
        assert pol in BENCH_POLICIES, pol
        sim = build_sim(
            task, scenario=scenario, n_clients=u, n_channels=n_channels,
            mu=mu, beta=beta, seed=seed, batch_size=batch_size, n_test=256,
            policy_mode=_POLICY_MODES.get(pol, pol), ga_config=ga_config,
            telemetry=tele, ledger=led,
        )
        t0 = time.time()
        res = sim.run_compiled(n_rounds, with_eval=True)
        run_s = time.time() - t0
        qs = np.asarray(res.q_levels)
        mean_q = float(qs[qs > 0].mean()) if (qs > 0).any() else 0.0
        results[pol] = (
            np.asarray(res.energy, dtype=np.float64),
            np.asarray(res.accuracy, dtype=np.float64),
            run_s,
            mean_q,
        )

    if target_acc is None:
        target_acc = min(float(acc[-1]) for _, acc, _, _ in results.values())

    for pol, (energy, acc, run_s, mean_q) in results.items():
        cum_e = np.cumsum(energy)
        hit = np.nonzero(acc >= target_acc)[0]
        r_hit = int(hit[0]) + 1 if hit.size else -1
        e_hit = float(cum_e[hit[0]]) if hit.size else float(cum_e[-1])
        rows.append((
            f"sim_baseline[{scenario},{pol},U={u},rounds={n_rounds}]",
            run_s / n_rounds * 1e6,
            f"cum_energy_J={float(cum_e[-1]):.5f};final_acc={float(acc[-1]):.4f}"
            f";target_acc={target_acc:.4f};rounds_to_target={r_hit}"
            f";energy_to_target_J={e_hit:.5f};mean_q={mean_q:.2f}",
        ))
        if json_rows is not None:
            json_rows.append({
                "name": f"sim_baseline[{scenario},{pol},U={u},rounds={n_rounds}]",
                "bench": "baseline_energy",
                "scenario": scenario, "policy": pol,
                "u": u, "c": n_channels, "rounds": n_rounds,
                "cum_energy_J": round(float(cum_e[-1]), 6),
                "final_acc": round(float(acc[-1]), 5),
                "target_acc": round(float(target_acc), 5),
                "rounds_to_target": r_hit,
                "energy_to_target_J": round(e_hit, 6),
                "mean_q": round(mean_q, 3),
            })
    return rows


def bench_fault_overhead(
    u: int = 1024,
    n_rounds: int = 20,
    outage_p: float = 0.1,
    task: str = "tiny",
    n_channels: int = 8,
    mu: float = 100.0,
    beta: float = 20.0,
    batch_size: int = 8,
    seed: int = 0,
    json_rows: list | None = None,
    ledger=None,
) -> list[tuple]:
    """Clean vs faults-on run of the SAME task/seed/key schedule: the
    rounds/s cost of the in-scan fault machinery (injection draws + the
    per-slot screen + realized Lyapunov feedback; budget <= 10% at the
    U = 1024 fleet scale) and the energy-to-matched-accuracy price of a
    ``outage_p`` correlated outage process (the fleet spends energy on
    rounds whose uploads partially never land). Compile time is excluded
    from both timings (lower+compile split out, as bench_fleet_scale)."""
    import jax
    import numpy as np
    from repro.obs import default_ledger, timed_phase
    from repro.sim import build_sim
    from repro.sim.scenario import FaultSpec

    led = ledger if ledger is not None else default_ledger()
    spec = FaultSpec(outage_p=outage_p, outage_corr=0.5)
    rows = []
    results: dict = {}
    for label, faults in (("clean", None), ("faulty", spec)):
        tag = f"U={u},C={n_channels},{task},{label}"
        sim = build_sim(
            task, n_clients=u, n_channels=n_channels, mu=mu, beta=beta,
            seed=seed, batch_size=batch_size, n_test=256, faults=faults,
        )
        keys, ridx = sim._scan_xs(n_rounds)
        carry = sim._init_carry()
        compiled = sim._scan_fn(True).lower(
            sim.data(), sim._dyn, carry, keys, ridx).compile()
        with timed_phase("run", led, tag=tag, rounds=n_rounds) as t_run:
            (flat, *_), out = compiled(sim.data(), sim._dyn, carry, keys,
                                       ridx)
            jax.block_until_ready(flat)
        results[label] = (
            t_run.seconds,
            np.asarray(out["energy"], np.float64),
            np.asarray(out["accuracy"], np.float64),
        )

    target_acc = min(float(acc[-1]) for _, _, acc in results.values())
    clean_s = results["clean"][0]
    for label, (run_s, energy, acc) in results.items():
        cum_e = np.cumsum(energy)
        hit = np.nonzero(acc >= target_acc)[0]
        r_hit = int(hit[0]) + 1 if hit.size else -1
        e_hit = float(cum_e[hit[0]]) if hit.size else float(cum_e[-1])
        overhead = run_s / clean_s - 1.0
        rows.append((
            f"sim_faults[{label},U={u},rounds={n_rounds},p={outage_p:g}]",
            run_s / n_rounds * 1e6,
            f"rounds_per_s={n_rounds / run_s:.3f}"
            f";overhead_vs_clean={overhead * 100:.1f}%"
            f";cum_energy_J={float(cum_e[-1]):.5f}"
            f";final_acc={float(acc[-1]):.4f};target_acc={target_acc:.4f}"
            f";rounds_to_target={r_hit};energy_to_target_J={e_hit:.5f}",
        ))
        if json_rows is not None:
            json_rows.append({
                "name": f"sim_faults[{label},U={u},rounds={n_rounds},"
                        f"p={outage_p:g}]",
                "bench": "fault_overhead",
                "u": u, "c": n_channels, "rounds": n_rounds,
                "outage_p": (0.0 if label == "clean" else outage_p),
                "rounds_per_s": round(n_rounds / run_s, 5),
                "overhead_vs_clean_pct": round(overhead * 100, 2),
                "cum_energy_J": round(float(cum_e[-1]), 6),
                "final_acc": round(float(acc[-1]), 5),
                "target_acc": round(float(target_acc), 5),
                "rounds_to_target": r_hit,
                "energy_to_target_J": round(e_hit, 6),
            })
    return rows


def bench_sim_vs_object(u: int = 8, n_rounds: int = 10) -> list[tuple]:
    """Small-scale sanity row: compiled engine vs the object-based loop
    running the same greedy-KKT policy (see tests/test_sim_parity.py)."""
    from repro.fl.experiment import build_experiment
    from repro.sim import build_sim
    from repro.sim.policy import HostFastPolicy

    sim = build_sim("tiny", n_clients=u, seed=0, n_test=256)
    t0 = time.time()
    res = sim.run_compiled(n_rounds, with_eval=False)
    sim_s = time.time() - t0  # includes the one compile

    exp = build_experiment("qccf", task="tiny", n_clients=u, n_channels=u, seed=0)
    exp.policy = HostFastPolicy(sim.sysp, sim.eps1, sim.eps2, sim.v_weight, q_cap=8)
    exp.eval_fn(exp.params)
    t0 = time.time()
    exp.run(n_rounds, eval_every=n_rounds)
    obj_s = time.time() - t0
    return [(
        f"sim_vs_object[U={u},rounds={n_rounds}]",
        sim_s / n_rounds * 1e6,
        f"object_us_per_round={obj_s / n_rounds * 1e6:.0f}"
        f";mean_sched={res.n_scheduled.mean():.1f}",
    )]


def write_bench_json(new_rows: list[dict], path: str = BENCH_JSON) -> None:
    """Append executed-config records to the JSON perf trajectory file."""
    doc = {"rows": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc["rows"].extend(new_rows)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=1024)
    ap.add_argument("--channels", type=int, default=8,
                    help="uplink channels C (paper regime C << U); "
                         "0 means C = U (dense)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--task", default="tiny")
    ap.add_argument("--mu", type=float, default=100.0)
    ap.add_argument("--beta", type=float, default=20.0)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--eval", action="store_true")
    ap.add_argument("--policy", choices=list(BENCH_POLICIES), default="greedy",
                    help="ga = full Algorithm 1 (compiled GA) inside the scan;"
                         " no_quant/channel_allocate/principle/same_size are"
                         " the paper's baselines as traced decision functions")
    ap.add_argument("--scenario", default=None,
                    help="registered scenario preset (single_bs, cellfree_a4,"
                         " noniid_a01); default = legacy single-BS build")
    ap.add_argument("--baseline", action="store_true",
                    help="run the QCCF-vs-baselines energy comparison on"
                         " --scenario instead of the scaling bench")
    ap.add_argument("--target-acc", type=float, default=None,
                    help="matched-accuracy level for --baseline (default:"
                         " worst final accuracy across policies)")
    ap.add_argument("--ga-generations", type=int, default=30)
    ap.add_argument("--ga-population", type=int, default=32)
    ap.add_argument("--json", action="store_true",
                    help=f"append machine-readable rows to {BENCH_JSON}")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the in-scan metric taps (repro.obs) — "
                         "still one compile")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="JSONL run-ledger path (default: $REPRO_LEDGER)")
    ap.add_argument("--xprof", default=None, metavar="DIR",
                    help="capture a profiler trace of the steady-state "
                         "rounds into DIR")
    ap.add_argument("--downlink", default="off",
                    choices=("off", "quant", "delta"),
                    help="quantized server->client broadcast mode for the "
                         "scaling bench (BENCH_sim downlink-on rows)")
    ap.add_argument("--outage-p", type=float, default=0.0,
                    help="client outage probability (fault injection)")
    ap.add_argument("--outage-corr", type=float, default=0.0,
                    help="Markov outage correlation (0 = i.i.d.)")
    ap.add_argument("--fade-p", type=float, default=0.0,
                    help="deep-fade probability (realized-rate faults)")
    ap.add_argument("--corrupt-p", type=float, default=0.0,
                    help="per-slot wire corruption probability")
    ap.add_argument("--nan-p", type=float, default=0.0,
                    help="NaN/Inf gradient-burst probability")
    ap.add_argument("--fault-overhead", action="store_true",
                    help="run the clean-vs-faulty overhead bench (rounds/s "
                         "cost of the fault machinery + energy-to-target "
                         "under --outage-p outages) instead of the "
                         "scaling bench")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import default_ledger
    enable_compile_cache()
    ledger = default_ledger(args.ledger)
    print("name,us_per_call,derived", flush=True)
    json_rows: list | None = [] if args.json else None
    faults = None
    if any((args.outage_p, args.fade_p, args.corrupt_p, args.nan_p)):
        from repro.sim.scenario import FaultSpec
        faults = FaultSpec(outage_p=args.outage_p,
                           outage_corr=args.outage_corr,
                           fade_p=args.fade_p, corrupt_p=args.corrupt_p,
                           nan_p=args.nan_p)
    if args.fault_overhead:
        rows = bench_fault_overhead(
            u=args.clients, n_rounds=args.rounds,
            outage_p=args.outage_p or 0.1, task=args.task,
            n_channels=(args.clients if args.channels == 0 else args.channels),
            mu=args.mu, beta=args.beta, batch_size=args.batch_size,
            seed=args.seed, json_rows=json_rows, ledger=ledger,
        )
    elif args.baseline:
        rows = bench_baseline_energy(
            u=args.clients, n_rounds=args.rounds,
            scenario=args.scenario or "single_bs", task=args.task,
            n_channels=(args.clients if args.channels == 0 else args.channels),
            mu=args.mu, beta=args.beta, batch_size=args.batch_size,
            seed=args.seed, target_acc=args.target_acc,
            ga_generations=args.ga_generations,
            ga_population=args.ga_population, json_rows=json_rows,
            telemetry=args.telemetry, ledger=ledger,
        )
    else:
        rows = bench_fleet_scale(
            u=args.clients, n_rounds=args.rounds, task=args.task,
            n_channels=(None if args.channels == 0 else args.channels),
            mu=args.mu, beta=args.beta, batch_size=args.batch_size,
            seed=args.seed, dry_run=args.dry_run, with_eval=args.eval,
            policy=args.policy, scenario=args.scenario,
            ga_generations=args.ga_generations,
            ga_population=args.ga_population, json_rows=json_rows,
            telemetry=args.telemetry, ledger=ledger, xprof=args.xprof,
            downlink=args.downlink, faults=faults,
        )
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    if json_rows:
        write_bench_json(json_rows)
        print(f"# wrote {len(json_rows)} row(s) -> {BENCH_JSON}", flush=True)


if __name__ == "__main__":
    main()
