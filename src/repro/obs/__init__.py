"""Cluster observability: lock-free tracing, a compile counter and typed
metrics.

* ``repro.obs.trace`` — per-thread span tracer with two sinks.  Its
  ring buffers keep every span on the host's ``perf_counter`` clock and
  export Chrome-trace/Perfetto JSON (open a ``--trace`` artifact in
  ``ui.perfetto.dev``; ``run_cluster`` returns a call's spans in
  ``stats_out["spans"]``).  While ``jax.profiler`` records, each span is
  also a TraceMe on the profiler's own clock, on the host plane of the
  same ``.xplane.pb`` as the device operations.  Disabled it costs one
  module-attribute read per call site; enabled it never takes a lock on
  the hot path.
* ``repro.obs.compiles`` — a ``jax.monitoring`` listener counting the
  process's tracing, lowering, compiling and compile-cache loads
  (``run_cluster`` reports it in ``stats_out["compile"]``).
* ``repro.obs.metrics`` — counters / gauges / fixed-bucket histograms
  (staleness, gap, drained-batch k, mailbox depth, per-shard busy
  time) with a background ``SnapshotPublisher`` that samples gauges
  off the hot path and mirrors them onto Perfetto counter tracks.

Wired through the threaded cluster (``repro.cluster``), the
discrete-event engine (``repro.core.engine`` — comparable metrics, no
spans: virtual time has no wall-clock spans to show), the cluster CLI
(``--trace`` / ``--metrics-out``) and ``benchmarks/bench_cluster.py``
(per-phase profiles + staleness histograms).
"""
from . import compiles, trace
from .metrics import (DEPTH_EDGES, DRAIN_K_EDGES, GAP_EDGES,
                      STALENESS_EDGES, Counter, Gauge, Histogram,
                      MetricsRegistry, SnapshotPublisher,
                      history_observer, serve_instruments)
from .trace import validate_chrome_trace

compiles.install()      # the process-wide compile counter starts here

__all__ = [
    "trace", "compiles", "validate_chrome_trace", "MetricsRegistry",
    "Counter", "Gauge", "Histogram", "SnapshotPublisher", "history_observer",
    "serve_instruments", "STALENESS_EDGES", "GAP_EDGES", "DRAIN_K_EDGES",
    "DEPTH_EDGES",
]
