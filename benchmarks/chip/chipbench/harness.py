"""One run of one cell: set-up, a measured window of back-to-back
experiments of the compiled fleet scan, the check against the plain
reference, and the result line.

An experiment is one ``FleetSim.run_compiled(rounds, with_eval=...)``
call, which returns its per-round outputs on the host. Every experiment
of a run uses the same keys, so each must repeat the warm-up's outputs
bit for bit and pass the structural checks; one that does not is
``failed``. After the window the program's state is freed and the
reference follows the first rounds from the seed alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from chipbench import checks, manifest, reference, tracing

HOST_SPANS = ("window", "experiment", "check")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read (``metrics/<name>.py``)."""

    cell: manifest.Cell
    spans: dict            # set-up host spans, seconds
    trace: Optional[tracing.Trace]
    window: tuple          # (start_ns, end_ns) of the traced window
    window_s: float
    busy_s: float
    rounds: int            # rounds run in the traced window
    scheduled: np.ndarray  # clients scheduled in each of those rounds
    peaks: dict
    memory: dict = dataclasses.field(default_factory=dict)  # scan's bytes


MAX_SEED_DRAWS = 100_000


def program_seed(cfg: dict, seed: int) -> int:
    """The seed the program and the reference are given for ``--seed``:
    the first of a stream of candidates drawn from ``--seed`` whose
    fleet's largest client holds exactly ``data.largest_client`` samples.
    The stacked fleet is padded to its largest client, so this gives
    every seed the same fleet shape and bytes: the same compiled scan
    (found in the persistent cache) and the same work per round."""
    want = cfg["data"]["largest_client"]
    rng = np.random.default_rng(seed % 2**64)
    for _ in range(MAX_SEED_DRAWS):
        cand = int(rng.integers(1, 2**31 - 1))
        if reference.client_sizes(cfg, cand).max() == want:
            return cand
    raise ValueError(f"no fleet with largest client {want} in "
                     f"{MAX_SEED_DRAWS} draws from seed {seed}")


def _sim_kwargs(cell: manifest.Cell, seed: int) -> dict:
    from repro.core.genetic import GAConfig

    cfg, tr = cell.config, cell.traffic
    d, t, ly = cfg["data"], cfg["training"], cfg["lyapunov"]
    kw = dict(
        scenario=cfg["scenario"], n_clients=cfg["n_clients"],
        n_channels=cfg["n_channels"], mu=d["mu"], beta=d["beta"],
        alpha_dirichlet=d["alpha_dirichlet"], n_test=d["n_test"],
        v_weight=ly["v_weight"], target_q=ly["target_q"], q_cap=ly["q_cap"],
        lr=t["lr"], batch_size=t["batch_size"], block_m=cfg["block_m"],
        seed=seed, policy_mode=tr["policy"])
    if tr.get("ga"):
        kw["ga_config"] = GAConfig(**tr["ga"])
    return kw


def _check_stated(sim, cfg: dict) -> None:
    """The program runs what the configuration states, or no result."""
    stated = {k: cfg["system"][k] for k in cfg["system"]}
    have = {k: getattr(sim.sysp, k) for k in stated}
    if have != stated or sim.z != cfg["model"]["z"] \
            or sim.sysp.tau != cfg["training"]["tau"]:
        raise ValueError(f"program runs z={sim.z} {have}, the configuration "
                         f"states z={cfg['model']['z']} {stated}")


def configure_cache() -> None:
    """Keep every compiled program in the persistent cache, the small
    ones of set-up and of the reference too, so that only a checkout's
    first run compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(require_tpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def run_cell(mf: manifest.Manifest, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_tpu: bool = True) -> dict:
    """The whole run of cell ``name``; returns the result object, its
    ``checks`` last. ``t_start`` is the process start on the
    ``time.perf_counter`` clock."""
    import jax

    from repro.sim import build_sim

    cell = mf.cell(name)
    device = device_info(require_tpu, cell.chips)
    cfg, tr = cell.config, cell.traffic
    seed = program_seed(cfg, seed)
    print(f"program seed {seed}", file=sys.stderr)
    n_rounds, with_eval = tr["rounds_per_experiment"], tr["with_eval"]
    u, c, q_cap = cfg["n_clients"], cfg["n_channels"], cfg["lyapunov"]["q_cap"]
    spans = {}

    t0 = time.perf_counter()
    sim = build_sim(cfg["task"], **_sim_kwargs(cell, seed))
    jax.block_until_ready(sim.data())
    spans["host_build"] = time.perf_counter() - t0
    _check_stated(sim, cfg)
    t0 = time.perf_counter()
    compiled = sim.lower(n_rounds, with_eval=with_eval).compile()
    spans["compile"] = time.perf_counter() - t0
    hlo = tracing.HloIndex.of(compiled.as_text()) if trace else None
    memory = _memory_analysis(compiled)
    del compiled
    warm = sim.run_compiled(n_rounds, with_eval=with_eval)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    attempted = failed = 0
    why = []
    last = warm
    scheduled = []
    with (jax.profiler.trace(trace_dir) if trace else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation("window"):
            t_w = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("experiment"):
                    res = sim.run_compiled(n_rounds, with_eval=with_eval)
                with jax.profiler.TraceAnnotation("check"):
                    bad = checks.structural_faults(res, n_rounds, u, c, q_cap)
                    if not checks.same_result(res, warm):
                        bad.append("outputs differ from the warm-up's")
                attempted += 1
                failed += bool(bad)
                why += bad
                last = res
                scheduled.append(res.n_scheduled)
                if time.perf_counter() - t_w >= seconds or (
                        trace and attempted >= tr["trace_experiments"]):
                    break
            window_s = time.perf_counter() - t_w
    peak = _peak_bytes()
    prog = {k: np.asarray(getattr(last, k)) for k in checks.OUTPUTS}
    del sim, warm, last, res
    gc.collect()

    if trace:
        metrics, extra = _layer_metrics(
            mf, cell, spans, trace_dir, hlo, attempted * n_rounds,
            np.concatenate(scheduled), device["kind"], memory)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(extra["device"])
    else:
        values = {"rounds_per_s": attempted * n_rounds / window_s,
                  "setup_s": setup_s, "peak_hbm_gb": peak / 1e9}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        extra = {}
    device["memory_peak_bytes"] = peak

    follow = prog if tr["policy"] == "compiled-ga" else None
    ref = reference.Reference(cfg, tr, seed, follow=follow).run(
        tr["reference_rounds"])
    numbers = checks.compare(prog, ref)
    correct = failed == 0 and checks.within(numbers, cell.limits)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if "breakdown" in extra:
        out["breakdown"] = extra["breakdown"]
    out["checks"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                     for k in checks.NUMBERS}
    out["checks"]["failed_experiments"] = {"value": failed, "limit": 0}
    if why:
        print("failed experiments: " + "; ".join(sorted(set(why))),
              file=sys.stderr)
    return out


def _memory_analysis(compiled) -> dict:
    """Argument, output, alias and temp bytes of a compiled program, as
    the compiler's buffer assignment gives them ({} where it gives none)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {k: int(getattr(ma, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp")}


def _layer_metrics(mf, cell, spans, trace_dir, hlo, rounds, scheduled, kind,
                   memory):
    if not hlo.scopes:
        raise RuntimeError("the compiled scan's HLO text names no scopes")
    tr = tracing.load(tracing.find_xplane(trace_dir), HOST_SPANS, hlo)
    win = tr.span("window")
    if win is None:
        raise RuntimeError("the trace holds no 'window' host span")
    lo, hi = win.start_ns, win.end_ns
    busy = tracing.busy_ns(tr, lo, hi)
    if busy <= 0:
        raise RuntimeError("no device operation in the traced window")
    ctx = LayerContext(
        cell=cell, spans=spans, trace=tr, window=(lo, hi),
        window_s=(hi - lo) / 1e9, busy_s=busy / 1e9, rounds=rounds,
        scheduled=scheduled,
        peaks=manifest.load_peaks(kind, mf.bench_dir / "peaks.json"),
        memory=memory)
    metrics = {}
    for m in cell.per_layer:
        v = mf.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    gaps = sorted(tracing.idle_gaps(tr, lo, hi), key=lambda g: g[0] - g[1])
    breakdown = {
        "device_ops": tracing.top_ops(tr, lo, hi),
        "idle_gaps": [[tracing.host_span_at(tr, (a + b) / 2, HOST_SPANS),
                       (b - a) / 1e9] for a, b in gaps[:10]],
    }
    return metrics, {"device": {"busy_s": ctx.busy_s,
                                "window_s": ctx.window_s},
                     "breakdown": breakdown}
