"""repro.obs — the fleet telemetry layer.

Three legs, one subsystem (see README.md):

* **in-scan metric taps** (``metrics``): a ``RoundMetrics`` pytree the
  engine emits as extra ``lax.scan`` ys, gated by a static
  ``MetricsConfig`` so ``telemetry=off`` lowers to the byte-identical
  scan;
* **structured run ledger** (``ledger``): the versioned JSONL sink every
  entry point writes through — run headers, per-round metric rows,
  compile/lower/run timings (``timed_phase``), and HLO byte-attribution
  events — plus ``report`` to render a run summary from a ledger file;
* **profiler hooks** (``profile``): ``jax.named_scope`` on the round's
  layers and the hot kernels (``ROUND_SCOPES``), ``span`` (the one host
  timing primitive, a ``jax.profiler.TraceAnnotation``), ``recording``
  (spans and JAX compile counts kept in memory), and an opt-in
  ``--xprof DIR`` trace capture on the benchmark CLIs.
"""
from repro.obs.ledger import (  # noqa: F401
    LEDGER_SCHEMA_VERSION, Ledger, default_ledger, pytree_hash, read_ledger,
    timed_phase, validate_event,
)
from repro.obs.metrics import (  # noqa: F401
    METRIC_FIELDS, METRICS_OFF, MetricsConfig, RoundMetrics,
    decision_metrics, decision_metrics_host, metrics_to_dict,
)
from repro.obs.profile import (  # noqa: F401
    ROUND_SCOPES, maybe_trace, recording, scope, span,
)
