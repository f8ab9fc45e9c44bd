"""Serving observability: metrics registry + request lifecycle tracing.

Zero-dependency (stdlib-only) quantitative evidence for the serving path —
the counters/gauges/histograms behind ``GET /metrics`` (Prometheus text
format) and the per-request JSONL traces behind ``--trace-out``. The
ROADMAP's north star is serving heavy traffic "as fast as the hardware
allows"; this package is how that claim gets numbers instead of vibes
(TTFT, per-token latency, queue wait, lane occupancy, prefix-cache hits).

All hooks are no-ops when the registry is disabled (``DLLAMA_OBS=0`` or
``get_registry().disable()``); an enabled histogram observation is an O(1)
bucket increment under a short lock.

PR 4 adds the ENGINE-level substrate below the request metrics: the
flight recorder (``recorder.py``, a bounded ring of structured engine
events with postmortem dumps), device memory telemetry (``device.py``,
``device.memory_stats()`` vs the analytic ``memory_report``), and
compiled-step cost analysis (``cost.py``, XLA flops/bytes vs the HBM
roofline) — all surfaced by the API server's ``/v1/debug/*`` endpoints.

PR 7 adds the third rung: span timeline tracing (``spans.py``, Chrome-
trace exports + per-request millisecond accounting behind
``/v1/debug/timeline`` and ``--timeline-out``), sliding-window SLO
attainment / goodput (``slo.py``, ``dllama_slo_*`` gauges +
``/v1/debug/slo``), and the engine watchdog (``watchdog.py``, stall
detection with auto-postmortem and a degraded ``/v1/health``).

PR 9 makes the registry continuously *watchable* in-process: a sampler
thread snapshots every counter/gauge/histogram-quantile into a bounded
two-tier time-series store (``timeseries.py``, ``/v1/debug/series``),
rolling-baseline EWMA anomaly rules over those series feed
``/v1/health``'s degraded status (``anomaly.py``), and a zero-dependency
single-file live dashboard renders the lot (``dashboard.py``,
``GET /dashboard``).
"""

from .cost import (
    extract_cost,
    hbm_peak_bytes_per_s,
    print_roofline_report,
    roofline_fraction,
    roofline_report,
    weight_bytes_per_token,
)
from .device import (
    compare_with_analytic,
    device_memory_stats,
    sample_device_memory,
)
from .anomaly import (
    AnomalyMonitor,
    AnomalyRule,
    EwmaBaseline,
    build_default_rules,
)
from .dashboard import DASHBOARD_HTML, render_dashboard
from .metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    DEFAULT_TOKEN_BUCKETS_S,
    MetricsRegistry,
    get_registry,
)
from .recorder import FlightRecorder, get_recorder
from .slo import SloTracker
from .spans import SpanTracker, get_span_tracker
from .timeseries import MetricsSampler, SeriesStore, resolve_series_knobs
from .trace import NULL_SPAN, RequestSpan, Tracer
from .watchdog import EngineWatchdog, resolve_watchdog_knobs

__all__ = [
    "DEFAULT_LATENCY_BUCKETS_S",
    "DEFAULT_TOKEN_BUCKETS_S",
    "MetricsRegistry",
    "get_registry",
    "FlightRecorder",
    "get_recorder",
    "device_memory_stats",
    "sample_device_memory",
    "compare_with_analytic",
    "extract_cost",
    "hbm_peak_bytes_per_s",
    "roofline_fraction",
    "roofline_report",
    "print_roofline_report",
    "weight_bytes_per_token",
    "NULL_SPAN",
    "RequestSpan",
    "Tracer",
    "SpanTracker",
    "get_span_tracker",
    "SloTracker",
    "EngineWatchdog",
    "resolve_watchdog_knobs",
    "SeriesStore",
    "MetricsSampler",
    "resolve_series_knobs",
    "AnomalyMonitor",
    "AnomalyRule",
    "EwmaBaseline",
    "build_default_rules",
    "DASHBOARD_HTML",
    "render_dashboard",
]
