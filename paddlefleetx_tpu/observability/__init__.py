"""Structured telemetry: metrics registry, crash-surviving flight
recorder, FLOPs/MFU accounting, device-memory sampling and labeled
trace annotations.

The training loop's numbers (step time, h2d wait, HBM watermark, MFU,
goodput) and its dispatch decisions (attention path, mp-linear
lowering) are first-class, machine-readable outputs here — not
grep-able log lines plus out-of-band scripts. See
``docs/observability.md`` for the events.jsonl schema and counter
names.
"""

from . import export, metrics, server, timeline
from .flops import (
    PEAK_FLOPS_BY_KIND, causal_attn_flops, model_flops_per_token,
    peak_flops,
)
from .histogram import LogHistogram
from .memory import device_memory_stats, format_bytes
from .metrics import MetricsRegistry, get_registry
from .recorder import FlightRecorder, read_events, read_tail
from .server import MetricsServer
from .spans import NULL_SPAN, Span, Tracer
from .timeline import ThreadTimeline, get_timeline

# ``observability.trace`` (host phases as profiler annotations) imports
# jax and is imported where it is used, not here: a launcher's parent
# takes ``timeline`` from this package and must stay off jax.

__all__ = [
    "FlightRecorder", "LogHistogram", "MetricsRegistry",
    "MetricsServer", "NULL_SPAN", "PEAK_FLOPS_BY_KIND", "Span",
    "ThreadTimeline", "Tracer", "causal_attn_flops",
    "device_memory_stats", "export", "format_bytes", "get_registry",
    "get_timeline", "metrics", "model_flops_per_token", "peak_flops",
    "read_events", "read_tail", "server", "timeline",
]
