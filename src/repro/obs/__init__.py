"""Observability spine: structured spans, metrics, search history.

One tracer/metrics layer shared by the fused engine, the fleet and the
serving stack (`telemetry`), plus the npz-backed search-history store
(`history`) that the learned-seeding ROADMAP item will train on.
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
from .history import HistoryRecorder  # noqa: F401
