"""Observability: span tracer, unified metrics registry, exporters.

The lag the paper studies is born somewhere concrete — admission wait,
prefill stall, a speculation rollback, an in-flight weight swap.  This
package makes that visible on a live run:

* ``tracer``   — ring-buffered span/instant/counter collector with
                 monotonic clocks; ``NULL_TRACER`` makes every
                 instrumentation point free when tracing is off, and
                 ``mirrored`` puts its sync spans into ``jax.profiler``
                 captures as ``TraceAnnotation``s, beside the device's
                 work.
* ``registry`` — one ``MetricsRegistry`` that ``ServeStats``,
                 ``RuntimeQueueStats`` and the trainers register into;
                 one ``snapshot()`` feeds telemetry, launchers and
                 benchmarks alike.
* ``perfetto`` — Chrome/Perfetto ``trace_event`` JSON + JSONL export.

``benchmarks/trace_report.py`` turns an exported trace into the
lag-attribution report (time-in-state per request, lag-at-emission
histogram, swap-to-first-stale-token latency).
"""
from repro.obs.perfetto import (
    events_to_trace_json,
    export_perfetto,
    export_trace_jsonl,
    load_trace_events,
)
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.tracer import (
    NULL_TRACER,
    ProfilerTracer,
    Span,
    TraceEvent,
    Tracer,
    make_tracer,
    mirrored,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "ProfilerTracer",
    "Span",
    "TraceEvent",
    "Tracer",
    "events_to_trace_json",
    "export_perfetto",
    "export_trace_jsonl",
    "load_trace_events",
    "make_tracer",
    "mirrored",
]
