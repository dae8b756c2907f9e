"""Where did start-up go: one record of what a process paid before its
first useful call, on the spans' clock.

Set-up is an end-to-end metric of the benchmark (``setup_s``) and nothing
inside the program timed it: the gated ``engine.*`` spans are shut until
somebody traces or enables telemetry, and most of set-up happens before
that. This module keeps a bounded list of ``Record``s — ``(phase, name,
t_start_ns, t_end_ns, self_ns)`` on ``time.perf_counter_ns``, the clock
``Span.t_start_ns`` uses — that is **always on**: set-up work happens once
a module and once a shape, never once an apply, so there is no gate, no
environment variable and no exporter of its own. Phases and their sources:

``import``
    ``name`` is the module. A finder put first on ``sys.meta_path`` times
    (through the spec it hands back, not through a frame of its own) the
    ``exec_module`` of every ``libskylark_tpu.*`` module and of every other
    module whose first import happens directly inside one of them
    (``scipy`` through ``sketch/qrft.py``, ``jax.experimental.pallas``
    through ``sketch/pallas_dense.py``; what such a module imports in turn
    is its own). The package's own record starts at the stamp on the first
    line of ``libskylark_tpu/__init__.py``. ``self_ns`` leaves out the
    watched imports inside, so the records add up to wall time. A module
    the caller imported before the package (``import jax``) is the
    caller's, and has no record.
``trace``, ``lower``, ``backend_compile``
    ``name`` is jax's ``fun_name`` (``jit(f)`` read as ``f``), from one
    ``jax.monitoring`` duration listener: every jit of the process, the
    eager ``jnp`` programs and ``engine.compiled``'s ``.lower()`` /
    ``.compile()`` among them. The end is the listener's clock reading,
    the start that minus the duration; ``self_ns`` leaves out the records
    of the same phase and thread inside (a jit traced inside another's
    trace).
``cache_load``
    the persistent compilation cache's retrieval, named after the
    ``backend_compile`` record it lies inside: a detail of that record,
    never added to it.

:func:`seconds` and :func:`summary` give the **union** of a phase's
intervals, so nothing nested or concurrent is counted twice. The counters
``setup.seconds{phase}`` and ``setup.events{phase}`` (``self_ns`` summed,
always on) carry the same through ``telemetry.snapshot()``, the JSONL
exporter and ``prometheus_text()``.

Imported first of all by ``libskylark_tpu/telemetry/__init__.py``, ahead of
everything it should see: nothing but the standard library at module level.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import Counter, deque, namedtuple
from importlib.machinery import ModuleSpec
from typing import Iterable, Optional

PACKAGE = "libskylark_tpu"
PHASES = ("import", "trace", "lower", "backend_compile", "cache_load")

Record = namedtuple("Record", "phase name t_start_ns t_end_ns self_ns")

# a library process has tens of records; a benchmark cell whose operands and
# check are eager jnp has 1.2-2.4 thousand, nearly all of them ``trace``
# records of a few microseconds (PERF.md PR 37). Bounded all the same, and
# what fell off the old end is counted: sums over a list that has dropped
# records are sums of its tail. Appends are atomic and readers copy, as with
# telemetry/trace.py's ring of finished spans
_RECORDS: "deque[Record]" = deque(maxlen=8192)
_dropped = 0


class _Local(threading.local):
    """What one thread has open."""

    def __init__(self):
        self.frames = []        # imports being executed, outermost first
        self.package = None     # the frame of the package itself
        self.cache_load = None  # a cache load waiting for its compile's name
        self.ended = {}         # phase -> intervals no later record is around


_LOCAL = _Local()
_counters = None    # (setup.seconds, setup.events), once bind_counters() ran

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def _record(phase: str, name: str, t_start_ns: int, t_end_ns: int,
            self_ns: int) -> None:
    global _dropped
    record = Record(phase, name, t_start_ns, t_end_ns, self_ns)
    if len(_RECORDS) == _RECORDS.maxlen:
        _dropped += 1
    _RECORDS.append(record)
    if _counters is not None:
        _count(record)


def _count(record: Record) -> None:
    seconds, events = _counters
    seconds.inc_always(record.self_ns * 1e-9, phase=record.phase)
    events.inc_always(phase=record.phase)


def bind_counters() -> None:
    """Create the two counters and bring them up to the records so far
    (``telemetry/__init__.py``, once the registry is imported: the first
    records are older than it)."""
    global _counters
    if _counters is not None:
        return
    from libskylark_tpu.telemetry import metrics as _metrics

    _counters = (
        _metrics.counter(
            "setup.seconds", "Set-up seconds (imports, jit traces, "
            "lowerings, backend compiles, cache loads), by phase"),
        _metrics.counter(
            "setup.events", "Set-up records, by phase"))
    for record in tuple(_RECORDS):
        _count(record)


# ---------------------------------------------------------------------------
# imports
# ---------------------------------------------------------------------------


class _Frame:
    __slots__ = ("name", "t_start_ns", "inside_ns")

    def __init__(self, name: str, t_start_ns: int):
        self.name = name
        self.t_start_ns = t_start_ns
        self.inside_ns = 0      # watched imports that ran inside this one


def _ours(name: str) -> bool:
    return name == PACKAGE or name.startswith(PACKAGE + ".")


def _enter(name: str, t_start_ns: Optional[int] = None) -> _Frame:
    frame = _Frame(name, t_start_ns or time.perf_counter_ns())
    _LOCAL.frames.append(frame)
    return frame


def _leave(frame: _Frame) -> None:
    t_end_ns = time.perf_counter_ns()
    frames = _LOCAL.frames
    if frame in frames:     # and whatever an exception left above it
        del frames[frames.index(frame):]
    total_ns = t_end_ns - frame.t_start_ns
    if frames:
        frames[-1].inside_ns += total_ns
    _record("import", frame.name, frame.t_start_ns, t_end_ns,
            total_ns - frame.inside_ns)


class _TimedSpec(ModuleSpec):
    """A module's spec for the length of its import. importlib sets
    ``spec._initializing`` just before ``exec_module`` and clears it just
    after: the two clock readings, taken from calls that have returned
    before the module's code runs and after it has — no frame of this file
    stands under an import. (It matters: CPython 3.12 keeps frames in
    chunks, and a regex compiled a few frames deeper ran into a chunk
    border on every call; a wrapped ``exec_module`` cost
    ``numpy.f2py.crackfortran`` 0.41 s on the chip host, PERF.md PR 37.)"""

    @property
    def _initializing(self):
        return self.__dict__.get("_initializing", False)

    @_initializing.setter
    def _initializing(self, value):
        self.__dict__["_initializing"] = value
        if value:
            self.__dict__["_timed"] = _enter(self.name)
            return
        frame = self.__dict__.pop("_timed", None)
        self.__class__ = ModuleSpec     # plain again: later imports of
        if frame is not None:           # the module read no property
            _leave(frame)


class _ImportWatch:
    """First on ``sys.meta_path``; finds nothing itself. For a module it
    should time it asks the finders after it, and times the spec they
    give."""

    def find_spec(self, fullname, path=None, target=None):
        if not _ours(fullname):
            frames = _LOCAL.frames
            if not frames or not _ours(frames[-1].name):
                return None
        for finder in sys.meta_path:
            find_spec = getattr(finder, "find_spec", None)
            if finder is self or find_spec is None:
                continue
            spec = find_spec(fullname, path, target)
            if spec is not None:
                if (type(spec) is ModuleSpec
                        and hasattr(spec.loader, "exec_module")):
                    spec.__class__ = _TimedSpec
                return spec
        return None


_WATCH = _ImportWatch()


def start() -> None:
    """Put the finder first on ``sys.meta_path``, open the record of the
    package itself at the stamp its first line took, and register the one
    ``jax.monitoring`` listener — in that order: a first ``import jax``
    made here is the package's, and gets its record
    (``telemetry/__init__.py``, ahead of its own imports; once)."""
    if _WATCH in sys.meta_path:
        return
    sys.meta_path.insert(0, _WATCH)
    _LOCAL.package = _enter(
        PACKAGE, getattr(sys.modules.get(PACKAGE), "_T0_NS", None))
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)


def package_imported() -> None:
    """Close the record of the package itself (the last line of
    ``libskylark_tpu/__init__.py``)."""
    frame, _LOCAL.package = _LOCAL.package, None
    if frame is not None:
        _leave(frame)


# ---------------------------------------------------------------------------
# traces, lowerings, compiles, cache loads
# ---------------------------------------------------------------------------


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    phase = _JAX_EVENTS.get(event)
    if phase is None:
        return
    t_end_ns = time.perf_counter_ns()
    t_start_ns = t_end_ns - int(duration_secs * 1e9)
    if phase == "cache_load":   # named when its backend_compile ends
        _LOCAL.cache_load = (t_start_ns, t_end_ns)
        return
    name = str(kwargs.get("fun_name", ""))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    if phase == "backend_compile":
        load, _LOCAL.cache_load = _LOCAL.cache_load, None
        if load is not None:
            _record("cache_load", name, *load, load[1] - load[0])
    # listeners hear an inner record before the one around it: what began
    # after this one did, on this thread, ran inside it
    ended = _LOCAL.ended.setdefault(phase, deque(maxlen=_RECORDS.maxlen))
    inside_ns = 0
    while ended and ended[-1][0] >= t_start_ns:
        start, end = ended.pop()
        inside_ns += end - start
    ended.append((t_start_ns, t_end_ns))
    _record(phase, name, t_start_ns, t_end_ns,
            t_end_ns - t_start_ns - inside_ns)


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------


def records(until_ns: Optional[int] = None) -> list:
    """The records so far, oldest first; with ``until_ns`` those that
    ended before it."""
    return [r for r in tuple(_RECORDS)
            if until_ns is None or r.t_end_ns <= until_ns]


def dropped() -> int:
    """How many of the oldest records the bounded list has let go (the
    imports first): above 0, ``records()`` and what is summed from it are
    no longer the whole process; the two counters still are."""
    return _dropped


def _union_s(kept: list, phases: Iterable[str]) -> float:
    phases = tuple(phases)
    covered, reach = 0, 0
    for start, end in sorted((r.t_start_ns, r.t_end_ns)
                             for r in kept if r.phase in phases):
        start = max(start, reach)
        if end > start:
            covered += end - start
            reach = end
    return covered * 1e-9


def seconds(phases: Iterable[str], until_ns: Optional[int] = None) -> float:
    """Seconds covered by the union of the intervals of ``phases``'
    records (those that ended before ``until_ns``)."""
    return _union_s(records(until_ns), phases)


def summary(until_ns: Optional[int] = None, top: int = 10) -> dict:
    """``{"seconds": {phase: union seconds}, "events": {phase: count},
    "total_s": union of every phase but the cache loads, "largest":
    [{"phase", "name", "self_s"}, ...], "dropped": records the bounded list
    let go}`` — the ``top`` largest records by their own seconds — over
    the records that ended before ``until_ns`` (all of them when
    ``None``)."""
    kept = records(until_ns)
    largest = sorted(kept, key=lambda r: r.self_ns, reverse=True)[:top]
    events = Counter(r.phase for r in kept)
    return {
        "seconds": {p: _union_s(kept, (p,)) for p in PHASES},
        "events": {p: events[p] for p in PHASES},
        "total_s": _union_s(kept, PHASES[:-1]),
        "largest": [{"phase": r.phase, "name": r.name,
                     "self_s": r.self_ns * 1e-9} for r in largest],
        "dropped": _dropped,
    }


__all__ = ["PHASES", "Record", "dropped", "records", "seconds", "summary"]
