"""Unified telemetry: structured tracing, metrics, structured logging.

Three zero-dependency pillars shared by every layer of the stack
(search engine, campaign runner, fleet workers/supervisor, recommend
server):

* :mod:`repro.obs.trace`   — ``Span``/``trace()`` crash-safe JSONL span
  logs (one ``trace.jsonl`` per process, Chrome/Perfetto-exportable via
  ``python -m repro.obs.export``), ``phase()`` hot-path timers that feed
  registry histograms, and ``watch_gc()`` for GC pauses; each is also a
  ``repro.<name>`` annotation on a JAX profile being taken;
* :mod:`repro.obs.metrics` — ``MetricsRegistry`` counters / gauges /
  fixed-bucket histograms with deterministic aggregation and a
  Prometheus text rendering (the serve ``/metrics`` surface and the
  lease-piggybacked live fleet view);
* :mod:`repro.obs.log`     — JSONL structured logger carrying
  ``(worker, batch_id, cell_id)`` context, with a plain-text mirror.

Everything here READS clocks and counters but never touches an RNG
stream or checkpoint content: searches with telemetry on are bitwise
identical to telemetry off (test-enforced in ``tests/test_obs.py``), and
``benchmarks/bench_obs`` gates the vec-engine overhead below 5%.
"""
from repro.obs.metrics import (MetricsRegistry, global_registry,
                               merge_snapshots, render_prometheus,
                               snapshot_value)
from repro.obs.trace import (Tracer, current_tracer, install_tracer,
                             span, tracing_disabled)

__all__ = [
    "MetricsRegistry", "global_registry", "merge_snapshots",
    "render_prometheus", "snapshot_value", "Tracer", "current_tracer",
    "install_tracer", "span", "tracing_disabled",
]
