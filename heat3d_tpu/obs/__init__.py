"""Unified telemetry: run ledger, metrics registry, named-span tracing —
plus the :mod:`~heat3d_tpu.obs.perf` layer that judges what they record
(profile capture, roofline attribution, the perf-regression gate,
multihost ledger merge; docs/OBSERVABILITY.md §5).

Three instruments, one package (see docs/OBSERVABILITY.md):

- :mod:`~heat3d_tpu.obs.ledger` — append-only JSONL event stream (spans +
  points, run-id/generation/process tagging) written by every entry point.
- :mod:`~heat3d_tpu.obs.metrics` — counters/gauges/histograms with a
  Prometheus-textfile/JSON exporter and a final per-run summary record.
- :mod:`~heat3d_tpu.obs.trace` — spans on the profiler's clock (each
  ``span`` is also a ``TraceAnnotation`` and a record in an always-on
  in-process ring, :func:`spans`) and ``jax.named_scope`` brackets, so
  profiler traces attribute host and device time to *our* phases.

Library code uses the module-level conveniences; with nothing configured
a span costs its annotation and ring record, an event nothing::

    from heat3d_tpu import obs

    obs.get().event("fault_injected", kind_="backend-loss", step=8)
    with obs.get().span("chunk", steps=4) as sp:
        ...
    obs.REGISTRY.counter("retries_total").inc()
    with obs.named_phase("halo_exchange"):
        ...  # traced code
"""

from heat3d_tpu.obs.ledger import (  # noqa: F401
    ENV_LEDGER,
    NULL,
    Ledger,
    NullLedger,
    activate,
    deactivate,
    get,
)
from heat3d_tpu.obs.metrics import (  # noqa: F401
    ENV_METRICS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    export_at_exit,
)
from heat3d_tpu.obs.trace import named_phase, scoped, spans  # noqa: F401
