"""The structured run ledger: one versioned JSONL schema for every entry
point (``FleetSim.run*``, the benchmark drivers, ``launch.train``,
``launch.dryrun``).

A ledger file is a sequence of JSON objects, one per line, each carrying
the common envelope ``{schema, event, run_id, ts}`` plus the per-kind
payload fields below. ``schema`` is :data:`LEDGER_SCHEMA_VERSION`;
readers reject events from a different major version instead of
mis-parsing them.

Event kinds
-----------

``run_header``  one per run: run name, entry point, scenario pytree hash,
                fleet shape / policy / mesh, git rev, jax version.
``round``       one per FL round: the ``RoundRecord`` columns plus (when
                telemetry is on) the ``RoundMetrics`` fields.
``timing``      one per timed phase (``timed_phase``): phase name and
                seconds, with warmup excluded by construction.
``hlo``         HLO byte attribution: the ``inter_axis_bytes`` /
                ``loop_summary`` / ``weighted_collectives`` output of a
                lowered program, folded into the ledger instead of
                bespoke dicts.
``record``      a free-form record from a sweep (e.g. one
                ``launch.dryrun`` combo) — payload is preserved as-is
                under ``"payload"``.
``resume``      one per segmented-scan checkpoint boundary: the step
                (next round index) and whether the carry was saved
                (``action="save"``) or restored (``action="load"``).

Telemetry must never kill a run: a failed append is retried once (the
transient-NFS / fd-exhaustion case) and then the ledger degrades to the
null sink with a single ``RuntimeWarning`` — the experiment keeps its
results, it just loses its log.

``Ledger(None)`` is the null sink (every write is a no-op), so call sites
never branch on "is telemetry configured". ``default_ledger()`` reads the
``REPRO_LEDGER`` environment variable — the one knob CI and local runs
share (see ``scripts/tier1.sh``).
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import math
import os
import subprocess
import time
from typing import Any, Callable, Iterator, Optional

from repro.obs.profile import span

LEDGER_SCHEMA_VERSION = 1
REPRO_LEDGER_ENV = "REPRO_LEDGER"

# event kind -> required payload fields (beyond the common envelope)
EVENT_FIELDS: dict[str, tuple[str, ...]] = {
    "run_header": ("name", "entry"),
    "round": ("round",),
    "timing": ("phase", "seconds"),
    "hlo": ("source", "payload"),
    "record": ("source", "payload"),
    "resume": ("step", "action"),
}
_ENVELOPE = ("schema", "event", "run_id", "ts")


def _sanitize(obj: Any) -> Any:
    """JSON-ready copy: numpy scalars -> python, NaN/inf -> None (strict
    JSON has no NaN literal, and a null metric reads as 'not defined this
    round' — e.g. corr_q_d with < 2 scheduled clients)."""
    if hasattr(obj, "item") and not hasattr(obj, "__len__"):
        obj = obj.item()
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {str(k): _sanitize(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize(v) for v in obj]
    if hasattr(obj, "tolist"):  # numpy arrays
        return _sanitize(obj.tolist())
    return obj


def validate_event(ev: dict) -> dict:
    """Raise ``ValueError`` unless ``ev`` is a well-formed ledger event of
    this schema version; returns the event for chaining."""
    for k in _ENVELOPE:
        if k not in ev:
            raise ValueError(f"ledger event missing envelope field {k!r}: {ev}")
    if ev["schema"] != LEDGER_SCHEMA_VERSION:
        raise ValueError(
            f"ledger schema {ev['schema']!r} != {LEDGER_SCHEMA_VERSION}"
        )
    kind = ev["event"]
    if kind not in EVENT_FIELDS:
        raise ValueError(f"unknown ledger event kind {kind!r}")
    missing = [k for k in EVENT_FIELDS[kind] if k not in ev]
    if missing:
        raise ValueError(f"ledger {kind!r} event missing {missing}: {ev}")
    if not isinstance(ev["ts"], (int, float)):
        raise ValueError(f"ledger ts must be numeric: {ev['ts']!r}")
    return ev


def read_ledger(path: str) -> list[dict]:
    """Load + validate every event of a ledger file (schema-checked)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(validate_event(json.loads(line)))
    return events


def git_rev(root: Optional[str] = None) -> Optional[str]:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=5,
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — headers degrade, never fail a run
        return None


def pytree_hash(tree: Any) -> str:
    """Stable content hash of a pytree (scenario fingerprint for run
    headers): sha256 over the treedef repr and every leaf's bytes."""
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()[:16]


class Ledger:
    """Append-per-write JSONL sink. ``Ledger(None)`` is the null sink."""

    def __init__(self, path: Optional[str], run_id: Optional[str] = None):
        self.path = path or None
        if run_id is None:
            run_id = f"{int(time.time() * 1e3):x}-{os.getpid()}"
        self.run_id = run_id

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def write(self, event: str, **fields: Any) -> Optional[dict]:
        if not self.enabled:
            return None
        ev = {
            "schema": LEDGER_SCHEMA_VERSION, "event": event,
            "run_id": self.run_id, "ts": time.time(),
            **_sanitize(fields),
        }
        validate_event(ev)
        line = json.dumps(ev) + "\n"
        # Telemetry must never kill a run: retry a failed append once (a
        # transient OSError — NFS hiccup, fd exhaustion), then degrade to
        # the null sink with one warning instead of raising into the
        # experiment. Malformed events above still raise — that is a
        # caller bug, not an I/O fault.
        try:
            self._append(line)
        except OSError:
            time.sleep(0.05)
            try:
                self._append(line)
            except OSError as e:
                import warnings

                warnings.warn(
                    f"ledger write to {self.path!r} failed twice ({e}); "
                    "disabling ledger for the rest of this run",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self.path = None
                return None
        return ev

    def _append(self, line: str) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(line)

    # ------------------------------------------------ typed conveniences

    def run_header(self, name: str, entry: str, **meta: Any) -> Optional[dict]:
        """One per run: who/what/where. ``meta`` carries scenario_hash,
        policy, u/c, mesh/plan labels, etc.; git rev and jax version are
        stamped here so every ledger is self-describing."""
        try:
            import jax
            jax_version = jax.__version__
        except Exception:  # noqa: BLE001
            jax_version = None
        return self.write(
            "run_header", name=name, entry=entry, git_rev=git_rev(),
            jax_version=jax_version, **meta,
        )

    def round_row(self, round: int, **metrics: Any) -> Optional[dict]:
        return self.write("round", round=int(round), **metrics)

    def timing(self, phase: str, seconds: float, **meta: Any) -> Optional[dict]:
        return self.write("timing", phase=phase, seconds=float(seconds), **meta)

    def hlo_event(self, source: str, payload: dict, **meta: Any) -> Optional[dict]:
        return self.write("hlo", source=source, payload=payload, **meta)

    def record(self, source: str, payload: dict, **meta: Any) -> Optional[dict]:
        return self.write("record", source=source, payload=payload, **meta)


def default_ledger(path: Optional[str] = None) -> Ledger:
    """The common ``--ledger PATH`` / ``REPRO_LEDGER`` resolution every
    CLI shares: an explicit path wins, else the environment variable,
    else the null sink."""
    return Ledger(path or os.environ.get(REPRO_LEDGER_ENV) or None)


@contextlib.contextmanager
def timed_phase(
    name: str,
    ledger: Optional[Ledger] = None,
    warmup: Optional[Callable[[], Any]] = None,
    **meta: Any,
) -> Iterator[span]:
    """The one timing block the benchmark drivers share.

    Runs ``warmup`` (jit pre-compiles etc.) BEFORE the clock starts, so
    the measured region never includes one-time costs; times the block
    as a :class:`repro.obs.profile.span` named ``name`` (so the phase also
    appears in profiler traces and recordings) and yields it, its
    ``.seconds`` the phase's duration after the block; and emits a ledger
    ``timing`` event when a ledger is given.

        with timed_phase("run", ledger, warmup=warm) as t:
            do_work()
        print(t.seconds)
    """
    if warmup is not None:
        warmup()
    t = span(name, **meta)
    try:
        with t:
            yield t
    finally:
        if ledger is not None:
            ledger.timing(name, t.seconds, **meta)
