"""Structured request tracing: spans, contextvar linkage, cross-thread
handoff, and a ``jax.profiler.TraceAnnotation`` mirror.

A **span** is one named, timed unit of work with parent/child linkage::

    with telemetry.span("serve.flush", attrs={"capacity": 8}) as sp:
        ...  # children opened inside nest under sp automatically

Linkage is :mod:`contextvars`-based, so nesting works across the async
boundaries jax cares about within one thread. Threads do **not**
inherit context — which is correct for the serve layer (a flush worker
must not accidentally parent under whatever the submitting thread was
doing) — so crossing a thread is *explicit*: capture
:func:`get_context` where the request is born, hand the
:class:`SpanContext` over with the work item, and :func:`attach` it in
the executing thread (or pass it as ``parent=`` to the next span).
``MicrobatchExecutor.submit`` does exactly this: the request id minted
at submit rides the queued request into the flush thread and every
bisection-isolation retry.

Every real span also enters a ``jax.profiler.TraceAnnotation`` with its
name, so host-side spans line up with the device timeline under
``jax.profiler.trace`` — the bridge that makes per-stage device
timelines first-class (FlashSketch's argument: sketch-kernel perf work
is only trustworthy with them).

The gate: a real span opens when telemetry is enabled
(:func:`~libskylark_tpu.telemetry.metrics.enabled`), **or while a
``jax.profiler`` session is recording** — whoever traces the process
gets the program's spans on the profiler's own clock without setting
anything — or under ``force=True`` (the
:class:`~libskylark_tpu.utility.timer.PhaseTimer` shim, whose
``SKYLARK_TPU_PROFILE`` phase timers keep their own enablement). A span
that is not forced is also skipped while jax is tracing a function: it
would time the tracing, not the work.

Cost discipline: a disabled :func:`span` is two branches returning a
shared no-op context manager — no allocation, no contextvar write.

Finished spans go to the bounded in-memory ring (:func:`finished_spans`
— tests, debugging; :func:`stage_seconds` reads a parent's stages from
it) and to every registered sink (:func:`add_sink`; the JSONL exporter
in :mod:`libskylark_tpu.telemetry.export` is one).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
import time
from collections import deque
from typing import Callable, Iterator, Optional

import jax.profiler as _jax_profiler

try:  # True outside any jit/scan/shard_map trace of this thread
    from jax._src.core import trace_state_clean as _not_tracing
except ImportError:  # pragma: no cover - moved by a jax upgrade: spans
    def _not_tracing() -> bool:  # then also open under a trace, which
        return True              # test_apply_under_jit_opens_no_span flags

from libskylark_tpu.base import locks as _locks
from libskylark_tpu.telemetry import metrics as _metrics

# ---------------------------------------------------------------------------
# ids
# ---------------------------------------------------------------------------

_ids = itertools.count(1)
# full pid + 32 random bits, drawn ONCE: ids stay cheap per span (no
# urandom syscall on the hot path) yet unique across the processes that
# share one SKYLARK_TELEMETRY_DIR — a truncated pid would collide for
# pids congruent mod the truncation under Linux's large pid_max
_ID_PREFIX = f"{os.getpid():x}-{os.urandom(4).hex()}"


def _new_id() -> str:
    return f"{_ID_PREFIX}-{next(_ids):08x}"


def new_request_id() -> str:
    """Mint a request id (the serve layer calls this at submit when the
    caller didn't provide one)."""
    return f"req-{_new_id()}"


# ---------------------------------------------------------------------------
# span + context
# ---------------------------------------------------------------------------


class SpanContext:
    """The portable identity of a span: what crosses threads/processes.
    Carries the trace id, the span id (the future parent), and the
    request id baggage the serve pipeline threads end to end."""

    __slots__ = ("trace_id", "span_id", "request_id")

    def __init__(self, trace_id: str, span_id: str,
                 request_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.request_id = request_id

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"SpanContext(trace={self.trace_id}, span={self.span_id}, "
                f"request={self.request_id})")


class Span:
    """One in-flight (then finished) traced operation."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "request_id",
                 "attrs", "events", "t_wall", "t_start_ns", "t_end_ns",
                 "status", "error", "thread")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 request_id: Optional[str], attrs: Optional[dict]):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.request_id = request_id
        self.attrs = dict(attrs) if attrs else {}
        self.events: list = []
        self.t_wall = time.time()      # for the JSONL export only
        # start and end on ONE clock (perf_counter_ns), so a parent's
        # self time is exact arithmetic over the ring
        self.t_start_ns: Optional[int] = None
        self.t_end_ns: Optional[int] = None
        self.status = "ok"
        self.error: Optional[str] = None
        self.thread = threading.current_thread().name

    @property
    def duration_s(self) -> Optional[float]:
        if self.t_end_ns is None:
            return None
        return (self.t_end_ns - self.t_start_ns) * 1e-9

    def set_attr(self, key: str, value) -> None:
        self.attrs[key] = value

    def add_event(self, name: str, attrs: Optional[dict] = None) -> None:
        self.events.append({"name": name, "t": time.time(),
                            "attrs": dict(attrs) if attrs else {}})

    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.request_id)

    def to_dict(self) -> dict:
        doc = {
            "kind": "span",
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_wall": round(self.t_wall, 6),
            "duration_s": (round(self.duration_s, 9)
                           if self.duration_s is not None else None),
            "status": self.status,
            "thread": self.thread,
        }
        if self.request_id is not None:
            doc["request_id"] = self.request_id
        if self.attrs:
            doc["attrs"] = self.attrs
        if self.events:
            doc["events"] = self.events
        if self.error is not None:
            doc["error"] = self.error
        return doc


# the active span (or attached remote SpanContext) for this context
_CURRENT: "contextvars.ContextVar[Optional[object]]" = \
    contextvars.ContextVar("skylark_telemetry_span", default=None)

# room for a whole traced window of the benchmark's fastest-turning cell:
# cwt_sparse_apply finishes ≈ 430 applies of six spans each in its 10 s
# (PERF.md PR 31; 42 before), and a wrapped ring costs the run its span
# metrics (``stage_seconds`` gives None)
_FINISHED: "deque[Span]" = deque(maxlen=16384)
_SINKS: "list[Callable[[Span], None]]" = []
_SINK_LOCK = _locks.make_lock("telemetry.sink")

_span_count = _metrics.counter(
    "telemetry.spans", "Finished telemetry spans, by name and status")


def current_span() -> Optional[Span]:
    cur = _CURRENT.get()
    return cur if isinstance(cur, Span) else None


def get_context() -> Optional[SpanContext]:
    """The calling context's span identity, for explicit cross-thread
    handoff (``None`` outside any span)."""
    cur = _CURRENT.get()
    if isinstance(cur, Span):
        return cur.context()
    if isinstance(cur, SpanContext):
        return cur
    return None


@contextlib.contextmanager
def attach(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Adopt a :class:`SpanContext` captured in another thread: spans
    opened inside the block parent under it (and inherit its request
    id). ``attach(None)`` is a no-op block."""
    if ctx is None:
        yield
        return
    token = _CURRENT.set(ctx)
    try:
        yield
    finally:
        _CURRENT.reset(token)


# True exactly while a ``jax.profiler`` session records (a static method
# of jaxlib's TraceMe; tens of nanoseconds)
_session_recording = _jax_profiler.TraceAnnotation.is_enabled


class _NoopSpanCm:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpanCm()


class _SpanCm:
    """Real-span context manager (class, not @contextmanager: the
    serve submit path opens one per request and the generator protocol
    costs ~2x a plain __enter__/__exit__ pair)."""

    __slots__ = ("span", "_token", "_ann")

    def __init__(self, name: str, attrs: Optional[dict],
                 parent: Optional[SpanContext],
                 request_id: Optional[str]):
        cur = _CURRENT.get()
        if parent is None and cur is not None:
            parent = cur.context() if isinstance(cur, Span) else cur
        if parent is not None:
            trace_id = parent.trace_id
            parent_id = parent.span_id
            if request_id is None:
                request_id = parent.request_id
        else:
            trace_id = _new_id()
            parent_id = None
        self.span = Span(name, trace_id, parent_id, request_id, attrs)
        self._token = None
        self._ann = None

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self.span)
        self._ann = _jax_profiler.TraceAnnotation(self.span.name)
        self._ann.__enter__()
        self.span.t_start_ns = time.perf_counter_ns()
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self.span
        s.t_end_ns = time.perf_counter_ns()
        try:
            self._ann.__exit__(exc_type, exc, tb)
        except Exception:  # pragma: no cover - profiler teardown
            pass
        if exc is not None:
            s.status = "error"
            s.error = repr(exc)
        _CURRENT.reset(self._token)
        _finish(s)
        return False


def span(name: str, attrs: Optional[dict] = None, *,
         parent: Optional[SpanContext] = None,
         request_id: Optional[str] = None,
         force: bool = False):
    """Open a span (context manager yielding the :class:`Span`, or
    ``None`` when the gate is shut: telemetry disabled, no
    ``jax.profiler`` session recording, and ``force`` not set — or jax
    tracing a function around the call, where the span would time the
    tracing).

    ``parent`` overrides the ambient contextvar parent (cross-thread
    handoff); ``request_id`` pins the id explicitly (else inherited
    from the parent); ``force`` opens a real span regardless of the
    gate (the PhaseTimer shim's hook — phase timers keep their own
    ``SKYLARK_TPU_PROFILE`` enablement)."""
    if not force and not (
            (_metrics.enabled() or _session_recording())
            and _not_tracing()):
        return _NOOP
    return _SpanCm(name, attrs, parent, request_id)


def add_event(name: str, attrs: Optional[dict] = None) -> None:
    """Append an event to the current span (no-op outside one, or
    disabled) — e.g. a resilience retry attempt recording itself on
    whatever span is executing."""
    cur = current_span()
    if cur is not None:
        cur.add_event(name, attrs)


# ---------------------------------------------------------------------------
# finished-span fanout
# ---------------------------------------------------------------------------


def _finish(s: Span) -> None:
    _FINISHED.append(s)
    _span_count.inc_always(name=s.name, status=s.status)
    with _SINK_LOCK:
        sinks = list(_SINKS)
    for sink in sinks:
        try:
            sink(s)
        except Exception:  # noqa: BLE001 — a sink must never fail work
            pass


def add_sink(fn: Callable[[Span], None]) -> Callable[[], None]:
    """Register a finished-span consumer; returns the unregister
    callable."""
    with _SINK_LOCK:
        _SINKS.append(fn)

    def unregister() -> None:
        with _SINK_LOCK:
            try:
                _SINKS.remove(fn)
            except ValueError:
                pass

    return unregister


def finished_spans(n: Optional[int] = None) -> list:
    """The most recent finished spans (bounded ring; tests/debug)."""
    spans = list(_FINISHED)
    return spans if n is None else spans[-n:]


def clear_finished() -> None:
    _FINISHED.clear()


def stage_seconds(root_name: str, last: Optional[int] = None
                  ) -> Optional[list]:
    """The stages of the last ``last`` finished spans named
    ``root_name`` (all of them when ``None``), oldest first: for each
    its ``total_s``, its ``self_s`` (total minus what its direct
    children cover) and ``children`` — the summed seconds of its
    direct children by name.

    ``None`` when the ring may have dropped a span from the oldest of
    them on (the window is then not whole, and a reader must leave its
    number out rather than report a wrong one)."""
    spans = list(_FINISHED)
    roots = [s for s in spans if s.name == root_name]
    if last is not None:
        roots = roots[-last:] if last > 0 else []
    if not roots:
        return []
    if not _ring_is_whole(spans, roots, last):
        return None
    by_id = {r.span_id: (r, {}, []) for r in roots}
    for s in spans:
        if s.parent_id in by_id:
            root, by_name, covered = by_id[s.parent_id]
            by_name[s.name] = by_name.get(s.name, 0.0) + s.duration_s
            covered.append((max(s.t_start_ns, root.t_start_ns),
                            min(s.t_end_ns, root.t_end_ns)))
    stages = []
    for root, by_name, covered in by_id.values():
        end, inside = 0, 0
        for a, b in sorted(covered):    # union: children that ran in
            a = max(a, end)             # other threads may overlap
            if b > a:
                inside += b - a
                end = b
        stages.append({"total_s": root.duration_s,
                       "self_s": root.duration_s - inside * 1e-9,
                       "children": by_name})
    return stages


def _ring_is_whole(spans: list, roots: list, last: Optional[int]) -> bool:
    """False when the ring may have dropped a span from the oldest of
    ``roots`` on: a full ring has (or may have) dropped spans, all of
    which ended before its oldest did — whole only if that was before
    the oldest root began, and no asked-for root is among them."""
    return not (len(spans) == _FINISHED.maxlen and (
        (last is not None and len(roots) < last)
        or spans[0].t_end_ns >= roots[0].t_start_ns))


def _split_by_innermost(inside: list, lo: int, hi: int) -> dict:
    """[lo, hi) in nanoseconds by the name of the innermost of the spans
    ``inside`` open at each instant; what none of them covers goes to
    ``None``. The values add up to ``hi - lo`` exactly (integers)."""
    by_name: dict = {}
    cursor = lo
    stack: list = []     # (end, name) of the open spans, outermost first

    def credit(until: int) -> None:
        nonlocal cursor
        until = min(until, hi)
        if until > cursor:
            name = stack[-1][1] if stack else None
            by_name[name] = by_name.get(name, 0) + until - cursor
            cursor = until

    for s in sorted(inside, key=lambda s: (s.t_start_ns, -s.t_end_ns)):
        if s.t_start_ns >= hi:
            break
        while stack and stack[-1][0] <= s.t_start_ns:
            credit(stack[-1][0])
            stack.pop()
        credit(s.t_start_ns)
        stack.append((s.t_end_ns, s.name))
    while stack:
        credit(stack[-1][0])
        stack.pop()
    credit(hi)
    return by_name


def apply_periods(root_name: str, last: Optional[int] = None
                  ) -> Optional[list]:
    """The periods of a closed blocking loop, read off the ring: for the
    last ``last`` finished spans named ``root_name`` (all when ``None``)
    of the newest one's thread, oldest first and without the newest
    (it has no successor), a dict each of

    - ``period_s``: the next root's start minus this root's start;
    - ``before_s``: the root's start to the start of its **handover**
      span (``names.HANDOVER``: the first ``engine.execute`` among its
      descendants — same ``trace_id``, same thread, inside its
      interval —, else its first ``sketch.dispatch``): the program's own
      Python ahead of the call that hands the compiled program to the
      runtime. Nothing is in flight when such a loop's operation begins,
      so the device is idle for all of it;
    - ``before_by_name``: that interval by the name of the innermost
      span open at each instant (the own time of the handover's
      ancestors ahead of the call among them), and ``before_self_s``:
      what only the root covers — together exactly ``before_s``;
    - ``call_s``: the handover span's duration, ``handover``: its name;
    - ``handovers``: how many spans of that name the root holds (one
      runtime hand-off an operation is the rule).

    A root that holds no handover span (an eager composition that opens
    none) has ``handovers`` 0 and ``None`` for the other entries but
    ``period_s``. ``None`` for the whole list where
    :func:`stage_seconds` gives ``None`` (a wrapped ring). All stamps
    are ``perf_counter_ns`` of one process."""
    from libskylark_tpu.telemetry.names import HANDOVER

    spans = list(_FINISHED)
    roots = [s for s in spans if s.name == root_name]
    if roots:
        thread = roots[-1].thread
        roots = [s for s in roots if s.thread == thread]
    if last is not None:
        roots = roots[-last:] if last > 0 else []
    if not roots:
        return []
    if not _ring_is_whole(spans, roots, last):
        return None
    roots.sort(key=lambda s: s.t_start_ns)
    by_trace: dict = {}
    wanted = {r.trace_id for r in roots}
    for s in spans:
        if s.trace_id in wanted and s.thread == thread:
            by_trace.setdefault(s.trace_id, []).append(s)
    periods = []
    for root, successor in zip(roots, roots[1:]):
        inside = [s for s in by_trace[root.trace_id]
                  if s is not root and s.t_start_ns >= root.t_start_ns
                  and s.t_end_ns <= root.t_end_ns]
        period = {"period_s": (successor.t_start_ns - root.t_start_ns) * 1e-9,
                  "before_s": None, "before_by_name": None,
                  "before_self_s": None, "call_s": None, "handover": None,
                  "handovers": 0}
        for name in HANDOVER:
            calls = [s for s in inside if s.name == name]
            if calls:
                first = min(calls, key=lambda s: s.t_start_ns)
                parts = _split_by_innermost(
                    inside, root.t_start_ns, first.t_start_ns)
                period.update(
                    before_s=(first.t_start_ns - root.t_start_ns) * 1e-9,
                    before_self_s=parts.pop(None, 0) * 1e-9,
                    before_by_name={k: v * 1e-9 for k, v in parts.items()},
                    call_s=first.duration_s, handover=name,
                    handovers=len(calls))
                break
        periods.append(period)
    return periods


__all__ = [
    "Span", "SpanContext", "add_event", "add_sink", "apply_periods",
    "attach", "clear_finished", "current_span", "finished_spans",
    "get_context", "new_request_id", "span", "stage_seconds",
]
