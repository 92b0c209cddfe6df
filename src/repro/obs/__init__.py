"""Fleet telemetry: metrics registry, timing spans, engine instrumentation.

See API.md "Observability".  `MetricsRegistry` (zero-dep counters /
gauges / histograms -> dict snapshot / Prometheus text), `SpanRecorder`
(nesting ``span("segment"|"prepare"|"round"|"flush"|"host_sync"|
"records"|"eval"|"checkpoint"|"compile")`` timing trees with
`block_until_ready` fencing), and
`EngineObs` (the bundle `DeviceScaleEngine.set_obs` / the serve stack
publish through, emitting schema-versioned records into a run dir's
``metrics.jsonl``).  Recorder spans, and the engines' own spans with
or without a bundle, are also `jax.profiler` annotations named
``fl.<span>`` (`annotate`); `compiles` counts every lowering and backend
compile of the process.
"""
from .metrics import (DEFAULT_BUCKETS, METRICS_SCHEMA, Metric,
                      MetricsRegistry, load_metrics_file,
                      merge_snapshot_records, snapshot_record)
from .spans import SPAN_SCHEMA, Span, SpanRecorder, annotate, fence
from .instrument import EVENT_SCHEMA, EngineObs
from . import compiles

__all__ = [
    "DEFAULT_BUCKETS", "METRICS_SCHEMA", "Metric", "MetricsRegistry",
    "load_metrics_file", "merge_snapshot_records", "snapshot_record",
    "SPAN_SCHEMA", "Span", "SpanRecorder", "annotate", "fence",
    "EVENT_SCHEMA", "EngineObs", "compiles",
]
