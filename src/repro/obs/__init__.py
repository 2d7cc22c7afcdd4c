"""Solve-wide observability: span tracer + unified metrics registry.

The paper's whole argument is I/O accounting — Table 3's 145 TB read /
4 TB written, and the §3.4.2 claim that SEM-SpMM hides SSD reads behind
compute. This package puts every layer's counters and timings on ONE
timeline:

  trace     nestable `span("operator.matmat")` context managers with a
            thread-safe in-process collector and a JSONL exporter; while
            the JAX profiler collects, every span is also a `TraceMe` in
            the profiler's trace, on the device trace's clock;
  metrics   pull-based registry snapshotting the existing counter objects
            (`IOStats`, `PageCache`, `Prefetcher`, `WriteBehind`)
            uniformly, plus derived gauges (hit rate, overlap fraction,
            bytes/pass, write-behind backlog);
  progress  per-restart convergence events + an ETA estimator from
            restart-over-restart residual decay, fed through the solver
            `callback` seam;
  report    `python -m repro.obs.report TRACE` renders a human solve
            report; `--validate` gates the schema for CI.

Entry point: `core.solve(op, nev, method=..., trace=...)` installs a
tracer for the solve's duration and emits the full timeline with zero
solver-code changes. With no tracer and the profiler off every
instrumentation point is a no-op guard (a module-global None check and
the profiler's `is_enabled()`), not a dropped feature.
"""
from repro.obs.trace import (NULL_SPAN, SCHEMA, Span, Tracer, active, event,
                             span, tracing)
from repro.obs.metrics import (MetricsRegistry, delta, derive, gauges,
                               snapshot_counters, snapshot_store)
from repro.obs.progress import ConvergenceTracker

__all__ = [
    "NULL_SPAN", "SCHEMA", "Span", "Tracer", "active", "event", "span",
    "tracing",
    "MetricsRegistry", "delta", "derive", "gauges", "snapshot_counters",
    "snapshot_store",
    "ConvergenceTracker",
]
