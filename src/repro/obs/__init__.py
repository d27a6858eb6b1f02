"""Telemetry plane: metrics registry, host spans, accuracy SLO probes.

The serving stack's observability layer, host-side:

  * `registry`  — scoped counters / gauges (with high-water marks) /
    fixed-log2-bucket histograms behind one `MetricsRegistry`, snapshot-able
    to a plain JSON dict (what checkpoint manifest v5 persists) and
    mergeable across shards (`merge_snapshots`, the host half of
    `core.sharded.merged_metrics`).
  * `trace`     — `span(name, **counts)`: a `jax.profiler.TraceAnnotation`
    named `cms.<name>` around a layer boundary of the hot path.  It lands
    in the profiler's own trace, on the device ops' clock, only while a
    profiler session records; a span never blocks, reads back or
    dispatches, so it is always on.  `cpu=True` adds `cpu_ns`, the
    calling thread's CPU time inside the span.
  * `export`    — Prometheus text exposition for registry snapshots (what
    `launch/serve_counts.py --metrics-out` writes and the bench job
    uploads as an artifact).
  * `probes`    — `AccuracyProbe`: a deterministic hash-sampled exact
    shadow counter (bounded memory) whose `are_by_decile` turns the
    paper's ARE-by-frequency-decile evaluation into tracked runtime
    metrics, CI-gated by `benchmarks/check_regression.py`.
"""
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                merge_snapshots)
from repro.obs.trace import recording, span
from repro.obs.export import to_prometheus, write_prometheus
from repro.obs.probes import AccuracyProbe

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "merge_snapshots",
    "recording", "span",
    "to_prometheus", "write_prometheus",
    "AccuracyProbe",
]
