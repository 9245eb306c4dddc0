"""Unified observability layer: metrics hub, stage tracing, exact histograms.

The measurement substrate the ROADMAP items report through: a typed
:class:`~repro.obs.hub.MetricsHub` (exact wrap-safe counters, JSONL sink,
snapshot/delta), a :class:`~repro.obs.tracing.Tracer` (named stage spans,
Chrome-trace export, optional ``jax.profiler`` annotation), and
:class:`~repro.obs.hist.FixedHistogram` (deterministic log-bucket latency
percentiles).  ``python -m repro.obs.report <run.jsonl>`` renders a run.
"""
from repro.obs.hist import FixedHistogram, log_bounds
from repro.obs.hub import ExactCounter, Gauge, MetricsHub
from repro.obs.tracing import NULL_TRACER, STEP_SCOPES, Tracer

__all__ = [
    "ExactCounter",
    "FixedHistogram",
    "Gauge",
    "MetricsHub",
    "NULL_TRACER",
    "STEP_SCOPES",
    "Tracer",
    "log_bounds",
]
