"""Telemetry subsystem: unified metrics registry, structured request
tracing, and exporters.

The one observability surface for the whole stack (``docs/
observability.rst``). Three modules:

- :mod:`~libskylark_tpu.telemetry.metrics` — a thread-safe
  process-wide registry of labeled counters/gauges/histograms, plus
  **collector adapters** that re-home the pre-existing stats blocks
  (``engine.stats()``, ``serve_stats()``, resilience fault log,
  WebHDFS reconnects) so every number the system
  already tracks appears once, under one schema, via
  :func:`snapshot`.
- :mod:`~libskylark_tpu.telemetry.trace` — ``with telemetry.span(...)``
  with contextvar parent/child linkage, explicit cross-thread
  :class:`SpanContext` handoff (a request id attached at
  ``MicrobatchExecutor.submit`` survives into the flush thread and the
  bisection-isolation retries), and mirroring of every span into
  ``jax.profiler.TraceAnnotation``.
- :mod:`~libskylark_tpu.telemetry.export` — JSONL span/metric sink
  under ``SKYLARK_TELEMETRY_DIR`` with a background flusher that also
  runs synchronously on the resilience preemption teardown, and the
  Prometheus text renderer :func:`prometheus_text`.

Enablement: ``SKYLARK_TELEMETRY=1`` (record, in-memory only),
``SKYLARK_TELEMETRY_DIR=<dir>`` (record + JSONL export), or
:func:`set_enabled`. Spans (not counters) are also recorded while a
``jax.profiler`` session is tracing the process. Disabled cost is one
branch per record, two per span — cheap enough that the
timing-sensitive tier-1 tests run with it off.
"""

from __future__ import annotations

# first, and standard library only: its import watch and its compile
# listener time everything below (and everything after this package)
from libskylark_tpu.telemetry import setup

setup.start()

from libskylark_tpu.base import env as _env
from libskylark_tpu.telemetry.metrics import (
    DEFAULT_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry, counter,
    enabled, gauge, histogram, register_collector, registry, set_enabled,
    snapshot,
)
from libskylark_tpu.telemetry.trace import (
    Span, SpanContext, add_event, add_sink, apply_periods, attach,
    clear_finished, current_span, finished_spans, get_context,
    new_request_id, span, stage_seconds,
)
from libskylark_tpu.telemetry.export import (
    JsonlExporter, get_exporter, install_exporter, prometheus_text,
    shutdown_exporter,
)

setup.bind_counters()

# Auto-install the JSONL exporter when the environment asks for it —
# first telemetry import (the engine pulls this package) wires the
# whole export path with zero host code.
if _env.TELEMETRY_DIR.get():
    install_exporter()

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram", "JsonlExporter",
    "MetricsRegistry", "Span", "SpanContext", "add_event", "add_sink",
    "apply_periods", "attach", "clear_finished", "counter", "current_span",
    "enabled", "finished_spans", "gauge", "get_context", "get_exporter",
    "histogram", "install_exporter", "new_request_id", "prometheus_text",
    "register_collector", "registry", "set_enabled", "setup",
    "shutdown_exporter", "snapshot", "span", "stage_seconds",
]
