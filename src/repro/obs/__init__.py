"""Unified observability layer: metrics registry, request tracing,
engine instrumentation, and NVFP4 quantization-health probes.

Dependency-free by design (stdlib, jax's profiler annotations for host
spans, and the repo's own jax surface in the probe); see
docs/CONVENTIONS.md §6 for the instrumentation boundary rule.
"""

from repro.obs.instrumentation import (NULL, Instrumentation,
                                       STAT_FLOAT_KEYS, STAT_INT_KEYS,
                                       STAT_KEYS, legacy_stats_dict)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry)
from repro.obs.tracing import RequestTrace, Span, TraceSink

__all__ = [
    "NULL", "Instrumentation", "STAT_FLOAT_KEYS", "STAT_INT_KEYS",
    "STAT_KEYS", "legacy_stats_dict", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "default_registry", "RequestTrace", "Span",
    "TraceSink",
]
