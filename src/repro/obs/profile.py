"""Profiler hooks: named scopes for the trace, annotations for the host,
and the opt-in ``--xprof DIR`` capture the benchmark CLIs share.

``scope`` wraps traced regions (Pallas kernels, the local-SGD vmap, the
KKT solve) in ``jax.named_scope`` — pure metadata riding the jaxpr's
source locations, so profiles attribute device time to paper steps.
Named scopes do NOT change the lowered StableHLO text (locations are
debug info), which the telemetry-off byte-identity gate in
``tests/test_obs.py`` relies on.

``annotate`` is the host-side ``jax.profiler.TraceAnnotation`` for
per-round phases of object-loop runs; it only costs anything while a
trace is being captured.

``maybe_trace`` gates ``jax.profiler.trace`` on a directory argument so
CLIs can expose ``--xprof DIR`` without branching: None is a no-op
context. Capture it around the steady-state region only (after compile),
so the profile shows round execution, not tracing. A requested trace
that cannot be started or written raises: a run asked for a profile
must not exit 0 without one.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax


def scope(name: str):
    """Traced-region name for profiles: ``with scope("kkt_solve"): ...``"""
    return jax.named_scope(name)


def annotate(name: str):
    """Host-side profiler annotation (active only during a capture)."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — profiling must never fail a run
        return contextlib.nullcontext()


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str]) -> Iterator[None]:
    """``jax.profiler.trace(dir)`` when a directory is given, else no-op.

    A profiler that fails to start or stop raises (``RuntimeError``
    naming the directory, chained to the cause).
    """
    if not trace_dir:
        yield
        return
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception as e:  # noqa: BLE001 — re-raised with the directory
        raise RuntimeError(f"xprof capture to {trace_dir!r} failed to "
                           f"start: {e}") from e
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — re-raised with the directory
            raise RuntimeError(f"xprof capture to {trace_dir!r} failed to "
                               f"stop: {e}") from e
        print(f"# xprof trace written to {trace_dir}", flush=True)
