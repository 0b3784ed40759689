"""Observability: labeled metrics + hierarchical spans for the whole stack.

The reference repo's only observability is coarse wall-clock CSV columns
(SURVEY §5.1), and until round 6 this repo's was a flat name→total span
accumulator (``utils/tracing.py``).  This package makes *where device time
goes* a first-class subsystem:

* :mod:`consensus_tpu.obs.metrics` — a thread-safe registry of labeled
  counters, gauges, and log-bucketed histograms with a JSON ``snapshot()``
  and Prometheus text exposition (``to_prometheus()``);
* :mod:`consensus_tpu.obs.spans` — hierarchical (parent/child) spans that
  supersede the flat ``Tracer`` while keeping ``get_tracer()`` /
  ``timing.json`` backward compatible;
* :mod:`consensus_tpu.obs.backends` — the shared instrument set backends
  record into: padding efficiency (useful vs. allocated tokens per
  row/width bucket), first sightings of a padded bucket, host↔device
  transfer timings, and the process's compile record (JAX's own compile
  events by jitted function and stage).

Artifacts: ``experiment.py`` snapshots the registry delta + span tree into
``run_dir/metrics.json`` (and the cumulative process registry into
``run_dir/metrics.prom``); ``cli/run_sweep.py`` aggregates cells into one
sweep-level snapshot; ``bench.py`` reports ``padding_efficiency`` and
``bucket_recompiles`` in its ``extra`` field.  Metric names and label
conventions: docs/ARCHITECTURE.md §Observability.
"""

from consensus_tpu.obs.backends import (
    BackendInstruments,
    bucket_recompiles,
    padding_efficiency,
)
from consensus_tpu.obs.metrics import (
    DEFAULT_COUNT_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    Registry,
    diff_snapshots,
    exponential_buckets,
    get_registry,
    merge_snapshots,
    prometheus_text,
)
from consensus_tpu.obs.sketch import (
    QuantileSketch,
    federate_snapshot,
    quantile_from_series,
)
from consensus_tpu.obs.slo import SLOEngine, SLOSpec
from consensus_tpu.obs.welfare import (
    ServeTelemetry,
    WelfareDriftDetector,
    get_welfare_sink,
    set_welfare_sink,
)
from consensus_tpu.obs.spans import SpanTracer, diff_span_paths, get_span_tracer
from consensus_tpu.obs.trace import (
    FlightRecorder,
    IterationLedger,
    RollingWindow,
    TraceContext,
    TraceStore,
    get_flight_recorder,
    get_trace_store,
    trace_current,
    use_trace,
)

__all__ = [
    "BackendInstruments",
    "DEFAULT_COUNT_BUCKETS",
    "DEFAULT_TIME_BUCKETS",
    "FlightRecorder",
    "IterationLedger",
    "QuantileSketch",
    "Registry",
    "RollingWindow",
    "SLOEngine",
    "SLOSpec",
    "ServeTelemetry",
    "SpanTracer",
    "TraceContext",
    "TraceStore",
    "WelfareDriftDetector",
    "bucket_recompiles",
    "diff_snapshots",
    "diff_span_paths",
    "exponential_buckets",
    "federate_snapshot",
    "get_flight_recorder",
    "get_registry",
    "get_span_tracer",
    "get_trace_store",
    "get_welfare_sink",
    "merge_snapshots",
    "padding_efficiency",
    "prometheus_text",
    "quantile_from_series",
    "set_welfare_sink",
    "trace_current",
    "use_trace",
]
