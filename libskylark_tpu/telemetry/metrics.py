"""Process-wide metrics registry: labeled counters, gauges, histograms.

The r8/r9 rounds grew observability piecemeal — ``engine.stats()``,
``serve_stats()``, the resilience ``fired()`` log, WebHDFS reconnect
counting — each with a private schema and no
common export path. This registry is the one schema they all surface
through: subsystems either **record directly** (a
:class:`Counter`/:class:`Gauge`/:class:`Histogram` created once at
module import) or **register a collector** (a zero-argument callable
re-homing an existing stats block at snapshot time, so numbers the
system already tracks appear exactly once instead of being counted
twice). :func:`snapshot` returns everything under one document;
:func:`libskylark_tpu.telemetry.prometheus_text` renders the same data
in Prometheus text exposition format.

Cost discipline (the tier-1 timing-sensitive tests run with telemetry
off): a disabled ``inc``/``set``/``observe`` is **one attribute read
and one branch** — no lock, no dict lookup, no allocation. Collectors
run only at snapshot time and are *always* consulted (they read
counters the host subsystems maintain anyway), so a disabled-mode
snapshot still carries the unified engine/serve/resilience numbers —
which is what lets ``bench.py`` embed a snapshot in every benchmarks
record without turning telemetry on.

Enablement: ``SKYLARK_TELEMETRY=1`` or ``SKYLARK_TELEMETRY_DIR=<dir>``
(the latter also installs the JSONL exporter —
:mod:`libskylark_tpu.telemetry.export`), or :func:`set_enabled`
programmatically.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

from libskylark_tpu.base import env as _env
from libskylark_tpu.base import locks as _locks

# ---------------------------------------------------------------------------
# enablement: one module-level bool, read without a lock on the hot path
# ---------------------------------------------------------------------------

_ENABLED: Optional[bool] = None


def enabled() -> bool:
    """Whether telemetry recording is on (``SKYLARK_TELEMETRY=1`` /
    ``SKYLARK_TELEMETRY_DIR`` set / :func:`set_enabled`)."""
    global _ENABLED
    if _ENABLED is None:
        _ENABLED = (bool(_env.TELEMETRY.get())
                    or bool(_env.TELEMETRY_DIR.get()))
    return _ENABLED


def set_enabled(on: bool) -> None:
    """Programmatic switch (overrides the environment gate)."""
    global _ENABLED
    _ENABLED = bool(on)


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

#: Default histogram bucket bounds (seconds-flavored: compile times,
#: flush latencies). A fixed, shared vector keeps every histogram
#: mergeable and the record path allocation-free.
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0,
)


def _label_key(labels: dict) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Metric:
    """Common base: name, help text, a lock-guarded per-label store."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "",  # noqa: A002 - prom idiom
                 registry: "Optional[MetricsRegistry]" = None):
        self.name = name
        self.help = help
        self._lock = _locks.make_lock("telemetry.metric")
        self._values: Dict[Tuple, float] = {}
        self._registry = registry

    def _base_doc(self) -> dict:
        return {"type": self.kind, "help": self.help}

    def to_dict(self) -> dict:
        with self._lock:
            doc = self._base_doc()
            doc["values"] = [
                {"labels": dict(k), "value": v}
                for k, v in sorted(self._values.items())
            ]
        return doc

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


class LifetimeCounter:
    """Process-lifetime event totals that survive their owning object.

    Collectors report *live* objects only (routers, autoscalers live
    in WeakSets), so a snapshot taken after an episode's object is
    gone would silently drop its events; subsystems keep one of these
    at module level and fold :meth:`snapshot` into their collector
    block. Always on (the counted event dwarfs the bump), never
    reset."""

    __slots__ = ("_lock", "_values")

    def __init__(self, site: str, kinds: Sequence[str] = ()):
        self._lock = _locks.make_lock(site)
        # pre-seeded kinds always appear in the snapshot, zero or not
        # — consumers (benchmark records) key off their presence
        self._values: Dict[str, int] = {k: 0 for k in kinds}

    def inc(self, kind: str, n: int = 1) -> None:
        with self._lock:
            self._values[kind] = self._values.get(kind, 0) + n

    def get(self, kind: str) -> int:
        with self._lock:
            return self._values.get(kind, 0)

    def snapshot(self, prefix: str = "lifetime_") -> Dict[str, int]:
        with self._lock:
            return {prefix + k: v for k, v in sorted(self._values.items())}


class Counter(Metric):
    """Monotonically increasing count. ``inc()`` is the only mutator."""

    kind = "counter"

    def inc(self, n: float = 1, **labels) -> None:
        if not enabled():
            return
        self.inc_always(n, **labels)

    def inc_always(self, n: float = 1, **labels) -> None:
        """Record regardless of the global gate — for adapters counting
        events a host subsystem already pays for (e.g. a WebHDFS
        reconnect: the reconnect itself dwarfs the counter bump)."""
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0) + n

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0)


class Gauge(Metric):
    """A value that goes up and down (queue depth, last objective)."""

    kind = "gauge"

    def set(self, v: float, **labels) -> None:
        if not enabled():
            return
        self.set_always(v, **labels)

    def set_always(self, v: float, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(v)

    def add(self, n: float = 1, **labels) -> None:
        if not enabled():
            return
        k = _label_key(labels)
        with self._lock:
            self._values[k] = self._values.get(k, 0) + n

    def value(self, **labels) -> Optional[float]:
        with self._lock:
            return self._values.get(_label_key(labels))


class Histogram(Metric):
    """Fixed-bucket histogram: cumulative bucket counts + sum + count
    per label set (the Prometheus classic-histogram layout)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "",  # noqa: A002
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 registry: "Optional[MetricsRegistry]" = None):
        super().__init__(name, help, registry)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        # per-label-key: [bucket counts..., +Inf count], sum
        self._hist: Dict[Tuple, list] = {}

    def observe(self, v: float, **labels) -> None:
        if not enabled():
            return
        self.observe_always(v, **labels)

    def observe_always(self, v: float, **labels) -> None:
        v = float(v)
        k = _label_key(labels)
        with self._lock:
            cell = self._hist.get(k)
            if cell is None:
                cell = self._hist[k] = [[0] * (len(self.buckets) + 1), 0.0]
            counts, _ = cell
            for i, b in enumerate(self.buckets):
                if v <= b:
                    counts[i] += 1
                    break
            else:
                counts[-1] += 1
            cell[1] += v

    def to_dict(self) -> dict:
        with self._lock:
            doc = self._base_doc()
            doc["buckets"] = list(self.buckets)
            doc["values"] = [
                {"labels": dict(k),
                 "counts": list(counts),
                 "count": sum(counts),
                 "sum": total}
                for k, (counts, total) in sorted(self._hist.items())
            ]
        return doc

    def reset(self) -> None:
        with self._lock:
            self._hist.clear()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class MetricsRegistry:
    """Get-or-create store of instruments plus named collectors.

    Instruments are created once (idempotent by name — a second
    ``counter("x")`` returns the first) and live for the process;
    collectors are ``name -> zero-arg callable`` returning a JSON-able
    dict, consulted at :meth:`snapshot` time. A collector that raises
    contributes an ``{"error": ...}`` block instead of failing the
    snapshot — telemetry must never be a failure mode.
    """

    def __init__(self):
        self._lock = _locks.make_lock("telemetry.registry")
        self._metrics: Dict[str, Metric] = {}
        self._collectors: Dict[str, Callable[[], dict]] = {}

    def _get_or_create(self, cls, name: str, help: str,  # noqa: A002
                       **kw) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, registry=self,
                                              **kw)
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:  # noqa: A002
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:  # noqa: A002
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "",  # noqa: A002
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def register_collector(self, name: str,
                           fn: Callable[[], dict]) -> None:
        """Adapter seam: re-home an existing stats block (engine cache
        counters, serve executor stats, ...) under the unified snapshot
        without double-counting. Idempotent per name (latest wins, so a
        test can stub one out)."""
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name: str) -> None:
        with self._lock:
            self._collectors.pop(name, None)

    def metrics(self) -> Dict[str, Metric]:
        with self._lock:
            return dict(self._metrics)

    def snapshot(self) -> dict:
        """The whole registry as one JSON-able document: direct
        instruments under ``"metrics"``, adapter blocks under
        ``"collectors"``."""
        with self._lock:
            metrics = dict(self._metrics)
            collectors = dict(self._collectors)
        doc: dict = {
            "enabled": enabled(),
            "metrics": {name: m.to_dict()
                        for name, m in sorted(metrics.items())},
            "collectors": {},
        }
        for name, fn in sorted(collectors.items()):
            try:
                doc["collectors"][name] = fn()
            except Exception as e:  # noqa: BLE001 — snapshot never fails
                doc["collectors"][name] = {"error": repr(e)}
        return doc

    def reset(self) -> None:
        """Zero every instrument's values (tests). Instruments and
        collectors stay registered — module-level handles must survive
        a reset."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m.reset()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-global registry every wired subsystem records to."""
    return _REGISTRY


# module-level conveniences bound to the global registry


def counter(name: str, help: str = "") -> Counter:  # noqa: A002
    return _REGISTRY.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:  # noqa: A002
    return _REGISTRY.gauge(name, help)


def histogram(name: str, help: str = "",  # noqa: A002
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return _REGISTRY.histogram(name, help, buckets)


def register_collector(name: str, fn: Callable[[], dict]) -> None:
    _REGISTRY.register_collector(name, fn)


def snapshot() -> dict:
    return _REGISTRY.snapshot()


__all__ = [
    "Counter", "DEFAULT_BUCKETS", "Gauge", "Histogram",
    "LifetimeCounter", "Metric", "MetricsRegistry", "counter",
    "enabled", "gauge", "histogram", "register_collector", "registry",
    "set_enabled", "snapshot",
]
