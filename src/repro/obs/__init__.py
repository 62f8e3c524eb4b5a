"""``repro.obs`` — solve-pipeline observability (DESIGN.md §9).

- :mod:`repro.obs.trace` — nestable span/event tracer with a
  guaranteed no-op disabled path, JSONL + Chrome-trace (Perfetto)
  export.
- :mod:`repro.obs.metrics` — labeled counters/gauges/histograms; the
  registry :class:`~repro.solvers.driver.SolveReport` counters are
  derived from, plus the report/trace cross-checks.

Span and event names are documented in docs/observability.md; the docs
CI gate (``tools/check_docs.py``) keeps that taxonomy complete.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    SERVICE_REPORT_PAIRS,
    SHARD_BYTE_PAIRS,
    TRACE_REPORT_PAIRS,
    check_report_consistency,
    check_trace_report,
)
from repro.obs.trace import (  # noqa: F401
    NULL_SPAN,
    NULL_TRACER,
    NullTracer,
    Tracer,
    from_jsonl,
)
