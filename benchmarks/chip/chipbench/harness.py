"""One run of one cell: set-up, a measured window of back-to-back
experiments of the cell's program, the check against the plain
reference, and the result line.

What the program is belongs to the configuration's runner
(``runners/<name>.py``, see ``manifest.Cell.runner``): ``build(cell,
seed)`` returns the built program, which compiles its experiment, runs
one and returns its result on the host, lists a result's faults against
the warm-up's, counts its useful FLOPs and hands over the outputs the
check needs; ``check(cell, seed, outputs)`` returns the runner's
``NUMBERS``. An experiment with a fault is ``failed``. The check runs
after the program's state is freed.
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

from chipbench import manifest, tracing

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
    useful_flops: int      # the runner's useful FLOPs of those rounds
    peaks: dict
    memory: dict = dataclasses.field(default_factory=dict)  # program's bytes


def within(numbers: dict, limits: dict) -> bool:
    """Every number at or under its limit (a NaN never is)."""
    return all(numbers[k] <= limits[k] for k in limits)


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

    cell = mf.cell(name)
    device = device_info(require_tpu, cell.chips)
    runner = cell.runner
    spans = {}

    t0 = time.perf_counter()
    prog = runner.build(cell, seed)
    spans["host_build"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = prog.compile()
    spans["compile"] = time.perf_counter() - t0
    hlo = tracing.HloIndex.of(compiled.as_text()) if trace else None
    memory = _memory_analysis(compiled)
    del compiled
    warm = prog.experiment()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    attempted = failed = useful = 0
    traced = cell.traffic["trace_experiments"]
    why = []
    last = warm
    with (jax.profiler.trace(trace_dir) if trace else contextlib.nullcontext()):
        with jax.profiler.TraceAnnotation("window"):
            t_w = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("experiment"):
                    res = prog.experiment()
                with jax.profiler.TraceAnnotation("check"):
                    bad = prog.faults(res, warm)
                attempted += 1
                failed += bool(bad)
                why += bad
                last = res
                useful += prog.useful_flops(res)
                if time.perf_counter() - t_w >= seconds or (
                        trace and attempted >= traced):
                    break
            window_s = time.perf_counter() - t_w
    peak = _peak_bytes()
    rounds = attempted * prog.rounds
    outputs = prog.outputs(last)
    del prog, warm, last, res
    gc.collect()

    if trace:
        metrics, extra = _layer_metrics(
            mf, cell, spans, trace_dir, hlo, rounds, useful, device["kind"],
            memory)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(extra["device"])
    else:
        values = {"rounds_per_s": rounds / window_s,
                  "setup_s": setup_s, "peak_hbm_gb": peak / 1e9}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        extra = {}
    device["memory_peak_bytes"] = peak

    numbers = runner.check(cell, seed, outputs)
    correct = failed == 0 and within(numbers, cell.limits)
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if "breakdown" in extra:
        out["breakdown"] = extra["breakdown"]
    out["checks"] = {k: {"value": numbers[k], "limit": cell.limits[k]}
                     for k in runner.NUMBERS}
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


def _layer_metrics(mf, cell, spans, trace_dir, hlo, rounds, useful, kind,
                   memory):
    if not hlo.scopes:
        raise RuntimeError("the compiled program's HLO text names no scopes")
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
        useful_flops=useful,
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
