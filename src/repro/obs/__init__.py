"""repro.obs — span tracing, Perfetto export, and session metrics.

See ``src/repro/obs/README.md`` for the span taxonomy and metrics
naming conventions, and ``examples/quickstart.py`` for the two-line
"observe your join" recipe::

    from repro.obs import trace_session
    with trace_session() as tracer:
        index.self_join(epsilon=eps)
    tracer.export("join.trace.json")      # open in ui.perfetto.dev
    print(tracer.analysis().summary())
"""
from repro.obs.tracer import (
    NOOP_SPAN,
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
    trace_session,
)
from repro.obs.compiles import compile_counts
from repro.obs.export import (
    TraceAnalysis,
    export_chrome_trace,
    validate_chrome_trace,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_bounds,
)
from repro.obs.live import (
    Alert,
    LiveCalibrator,
    LiveObserver,
    RollupWindow,
    Slo,
    SloMonitor,
    TimeSeries,
    default_serving_slos,
    merge_live_sections,
)
from repro.obs.webhook import WebhookSink

__all__ = [
    "NOOP_SPAN",
    "Tracer",
    "get_tracer",
    "enable_tracing",
    "disable_tracing",
    "trace_session",
    "compile_counts",
    "TraceAnalysis",
    "export_chrome_trace",
    "validate_chrome_trace",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "log_bounds",
    "TimeSeries",
    "RollupWindow",
    "Slo",
    "SloMonitor",
    "Alert",
    "LiveCalibrator",
    "LiveObserver",
    "default_serving_slos",
    "merge_live_sections",
    "WebhookSink",
]
