"""Engine observability: request-lifecycle tracing, profiler spans around
the stages of an engine tick, latency metrics, and export surfaces (JSONL /
Chrome trace / Prometheus text / JSON).

Contract (locked by tests/test_obs.py): observability is FREE and
INVARIANT — a :class:`TraceRecorder` threaded through
``DecodeEngine(trace=...)`` reads only host mirrors the scheduler
already maintains, so tracing on vs. off leaves token streams bitwise
identical, ``compile_counts()`` unchanged, and adds zero device
fetches. The profiler spans (:func:`span`) hold to the same contract:
they carry host ints only and add no fetch, sync or dispatch, so a run
under ``jax.profiler`` serves the same tokens. See docs/observability.md.
"""
from repro.obs.metrics import (SECONDS_BUCKETS, TICK_BUCKETS, Counter,
                               Gauge, Histogram, MetricsRegistry,
                               engine_metrics, latency_metrics,
                               lifecycle_latencies, parse_prometheus,
                               percentile)
from repro.obs.trace import (AUX_EVENTS, EVENT_NAMES, LIFECYCLE_EVENTS,
                             TraceEvent, TraceRecorder, monotonic, span)

__all__ = [
    "AUX_EVENTS", "Counter", "EVENT_NAMES", "Gauge", "Histogram",
    "LIFECYCLE_EVENTS", "MetricsRegistry", "SECONDS_BUCKETS",
    "TICK_BUCKETS", "TraceEvent", "TraceRecorder", "engine_metrics",
    "latency_metrics", "lifecycle_latencies", "monotonic",
    "parse_prometheus", "percentile", "span",
]
