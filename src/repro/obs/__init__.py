"""Observability spine: structured spans and metrics.

One tracer/metrics layer shared by the fused engine, the fleet and the
serving stack (`telemetry`).
"""
from .telemetry import (  # noqa: F401
    MetricsRegistry,
    Tracer,
    compile_spans,
    default_clock,
    get_metrics,
    get_tracer,
    render_prometheus,
    set_tracer,
)
