"""Observability for the serving stack: tracing, metrics, pod snapshots.

Import surface is deliberately flat — instrumented modules do
``from repro.obs import TRACER, metrics`` and nothing else.  This
package imports nothing from ``repro.serve``/``repro.tune``/
``repro.kernels`` (they import *us*), and defers every jax import, so
it is safe at any layer including ``launch.multihost`` pre-bootstrap.
"""
from .trace import (TRACER, Span, Tracer, disable_tracing, enable_tracing,
                    export_chrome_trace, get_tracer, merge_chrome_traces,
                    request_coverage, tracing_enabled, watch_compiles)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry, note_static_fallback, warn_once)
from .quality import (CRITICAL, LEVELS, OK, SHADOW, WARN, AlertMachine,
                      ShadowScorer, get_shadow)
from .slo import MONITOR, SLO, SLOMonitor, get_monitor
from .server import ObsServer, validate_exposition
from .pod import (local_snapshot, merge_pod_trace, pod_quality_report,
                  pod_snapshot)

__all__ = [
    "TRACER", "Span", "Tracer", "enable_tracing", "disable_tracing",
    "tracing_enabled", "get_tracer", "export_chrome_trace",
    "merge_chrome_traces", "request_coverage", "watch_compiles",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "warn_once", "note_static_fallback",
    "SHADOW", "ShadowScorer", "AlertMachine", "get_shadow",
    "OK", "WARN", "CRITICAL", "LEVELS",
    "MONITOR", "SLO", "SLOMonitor", "get_monitor",
    "ObsServer", "validate_exposition",
    "local_snapshot", "pod_snapshot", "merge_pod_trace",
    "pod_quality_report",
]
