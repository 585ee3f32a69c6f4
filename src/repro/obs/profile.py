"""Profiler hooks: named scopes for the device, spans for the host, a
recorder for spans and compile counts, and the opt-in ``--xprof DIR``
capture the benchmark CLIs share.

``scope`` wraps traced regions in ``jax.named_scope``: pure metadata
riding the jaxpr's source locations, found in a chip trace through the
``op_name`` of each compiled instruction. Named scopes do NOT change the
lowered StableHLO text (locations are debug info), which the
telemetry-off byte-identity gate in ``tests/test_obs.py`` relies on.
:data:`ROUND_SCOPES` names the layers of one round of the fleet scan
(``FleetSim._round_body``): every op of the scan body sits under one.

``span(name, **ids)`` is the one host-side timing primitive. It always
enters ``jax.profiler.TraceAnnotation(name, **ids)``, a host event on
the clock the profiler shares with the device planes, which costs next
to nothing when no capture runs, and reads ``time.perf_counter_ns``
on entry and exit (``.seconds``). While a recorder is installed
(``with recording() as log:``) it also appends a :class:`SpanRecord` to
``log.spans``; with none installed it keeps nothing. A span is also a
decorator: ``@span("sim.build")``.

While a recorder is installed, JAX's own monitoring events are counted
into ``log.counts`` (:data:`COUNTED_EVENTS`): jaxpr traces, backend
compiles (a persistent-cache load included), and persistent-cache hits
and misses. A run that traces or compiles nothing counts nothing.

Timing ``build_sim`` with no profiler and no benchmark::

    from repro.obs.profile import recording
    from repro.sim import build_sim

    with recording() as log:
        sim = build_sim("tiny", n_clients=16, seed=0)
        sim.run_compiled(5)
    for s in log.spans:
        print(s.parent, s.name, s.ids, (s.end_ns - s.start_ns) / 1e9)
    print(log.seconds("sim.data"), log.counts)

``maybe_trace`` gates ``jax.profiler.trace`` on a directory argument so
CLIs can expose ``--xprof DIR`` without branching: None is a no-op
context. Capture it around the steady-state region only (after compile),
so the profile shows round execution, not tracing. A requested trace
that cannot be started or written raises: a run asked for a profile
must not exit 0 without one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Iterator, NamedTuple, Optional

import jax

# The layers of one round of the fleet scan, in the order the round runs
# them (see FleetSim._round_body); kkt_solve nests inside decision.
ROUND_SCOPES = ("channel_draw", "decision", "slot_gather", "fleet_local_sgd",
                "quantize_wire", "pallas_aggregate", "downlink",
                "server_update", "eval", "telemetry")

# JAX monitoring event -> key of Recording.counts
COUNTED_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "backend_compiles",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def scope(name: str):
    """Traced-region name for profiles: ``with scope("kkt_solve"): ...``"""
    return jax.named_scope(name)


class SpanRecord(NamedTuple):
    name: str
    start_ns: int   # time.perf_counter_ns()
    end_ns: int
    parent: Optional[str]  # name of the enclosing span, None at the top
    ids: dict


@dataclasses.dataclass
class Recording:
    """What :func:`recording` keeps: every span closed and every counted
    JAX event while it was installed."""

    spans: list = dataclasses.field(default_factory=list)
    counts: dict = dataclasses.field(
        default_factory=lambda: dict.fromkeys(COUNTED_EVENTS.values(), 0))
    _open: list = dataclasses.field(default_factory=list, repr=False)

    def seconds(self, name: str) -> float:
        """Total seconds of the spans called ``name``."""
        return sum(s.end_ns - s.start_ns for s in self.spans
                   if s.name == name) / 1e9


_RECORDER: Optional[Recording] = None
_LISTENING = False


class span(contextlib.ContextDecorator):
    """``with span("sim.fetch", run=3) as s: ...``; ``s.seconds`` is the
    time so far inside the block, and its duration after it."""

    def __init__(self, name: str, **ids) -> None:
        self.name = name
        self.ids = ids
        self._end = None

    def _recreate_cm(self):
        # a decorated function enters a fresh span on every call
        return type(self)(self.name, **self.ids)

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        self._log = _RECORDER
        if self._log is not None:
            self._log._open.append(self.name)
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._end = time.perf_counter_ns()
        log = self._log
        if log is not None:
            log._open.pop()
            log.spans.append(SpanRecord(
                self.name, self._start, self._end,
                log._open[-1] if log._open else None, self.ids))
        self._ann.__exit__(*exc)

    @property
    def seconds(self) -> float:
        end = time.perf_counter_ns() if self._end is None else self._end
        return (end - self._start) / 1e9


def _count(event: str, *_args, **_kwargs) -> None:
    log = _RECORDER
    if log is not None and event in COUNTED_EVENTS:
        log.counts[COUNTED_EVENTS[event]] += 1


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Install a :class:`Recording` for the block (the previous one, if
    any, is restored after it)."""
    global _RECORDER, _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_listener(_count)
        jax.monitoring.register_event_duration_secs_listener(_count)
        _LISTENING = True
    prev, _RECORDER = _RECORDER, Recording()
    try:
        yield _RECORDER
    finally:
        _RECORDER = prev


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
