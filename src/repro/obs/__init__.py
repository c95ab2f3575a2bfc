"""`repro.obs` — unified tracing / metrics / benchmark-measurement layer.

Three pieces, one import:

* :mod:`repro.obs.trace` — hierarchical host-boundary spans with JSONL and
  Chrome-trace (Perfetto) export, gated by the registry-validated
  ``REPRO_TRACE`` knob (no-op when off).
* :mod:`repro.obs.metrics` — counters / gauges for solver telemetry, plus the process event bus that the XLA compile
  listener (``repro.analysis.retrace``) publishes into.
* :mod:`repro.obs.bench` — the single copy of the benchmark timing /
  memory helpers every ``benchmarks/figN`` driver shares.

``python -m repro.obs report`` summarizes saved trace JSONL;
``python -m repro.obs smoke`` runs a traced toy solve and validates the
Chrome-trace schema (the CI obs-smoke lane).

Import discipline: this package imports only the stdlib and ``repro.env``
— never jax/numpy — so instrumented modules pay nothing extra at import
and the CLI works on machines without the solver stack.
"""

from __future__ import annotations

from .bench import (
    Timer,
    count_compiles,
    perf_record,
    ru_maxrss_mb,
    timed,
    timed_peak,
)
from .metrics import (
    Counter,
    Gauge,
    counter,
    emit,
    gauge,
    reset_metrics,
    snapshot,
    subscribe,
    unsubscribe,
)
from .trace import (
    Span,
    TRACE_OUT,
    chrome_trace_events,
    counter_event,
    get_events,
    get_spans,
    instant,
    reset_trace,
    set_trace,
    span,
    trace_enabled,
    validate_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)

__all__ = [
    "Counter",
    "Gauge",
    "Span",
    "TRACE_OUT",
    "Timer",
    "chrome_trace_events",
    "count_compiles",
    "counter",
    "counter_event",
    "emit",
    "gauge",
    "get_events",
    "get_spans",
    "hist",
    "instant",
    "perf_record",
    "reset_metrics",
    "reset_trace",
    "ru_maxrss_mb",
    "set_trace",
    "snapshot",
    "span",
    "subscribe",
    "timed",
    "timed_peak",
    "trace_enabled",
    "unsubscribe",
    "validate_chrome_trace",
    "write_chrome_trace",
    "write_jsonl",
]
