"""``engine.compiled`` — whole-solver compilation with an explicit cache.

``compiled(fn, ...)`` wraps a pure solver pipeline in ``jax.jit`` with
explicit static arguments and (opt-in) buffer donation, AOT-compiles it
(``lower().compile()``) and serves the executable from an in-process
LRU (:mod:`libskylark_tpu.engine.cache`). The cache key is explicit —
nothing is left to jit's implicit closure identity, so two *different*
transform objects with the same (seed, counter) share one executable.
The key is a function of the program and its arguments alone:

    (solver name, code-version hash, static args, key_fn extras,
     abstract shapes/dtypes, sharding/mesh fingerprint, donation,
     solver-precision regime, backend)

The AOT discipline buys a hard property: an entry can never silently
recompile — ``jax.stages.Compiled`` raises on a signature mismatch
instead of re-tracing — so the engine's miss counter is exactly the
process's solver-compile counter, which the recompile-guard tests and
the CI jit-leak gate rely on.

Donation: callers opt in per-site (``donate_argnums``) and globally
(``SKYLARK_ENGINE_DONATE=1`` flips :func:`donation_enabled`, which the
solver entry points consult via :func:`maybe_donate`). Donated operands
are consumed — the caller's array is invalidated on every backend,
including CPU. The tier-1 default is off because the public solvers
take *user* operands (docs/performance.rst, "donation caveats").

Cross-process reuse has two tiers (docs/performance, "Persistent AOT
artifacts & warmup packs"):

- ``SKYLARK_AOT_DIR=<dir>`` — the **artifact store**
  (:mod:`libskylark_tpu.engine.aot`): every AOT compile is serialized
  under a digest of this exact cache key; a later process *loads
  instead of compiling* (zero tracing, zero backend compile), with
  compat probing and fall-back-to-compile on any deserialize failure,
  and a per-key file lock extending the single-flight discipline
  across processes — N racing cold replicas perform one compile
  fleet-wide.
- ``SKYLARK_EXEC_CACHE_DIR=<dir>`` — jax's persistent *compilation*
  cache (tracing still paid, HLO-keyed), wired at first engine
  compile. Deprecated as an artifact-store alias: when set without
  ``SKYLARK_AOT_DIR``, artifacts additionally land in ``<dir>/aot``
  with a one-time ``DeprecationWarning``.
"""

from __future__ import annotations

import functools
import hashlib
import inspect
import json
import os
import time
import warnings
from typing import Callable, Optional, Sequence

import jax

from libskylark_tpu import telemetry as _telemetry
from libskylark_tpu.base import env as _env
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.engine import aot as _aot
from libskylark_tpu.engine.cache import CacheEntry, EngineStats, ExecutableCache
from libskylark_tpu.resilience import faults as _faults

# ---------------------------------------------------------------------------
# global cache + policy switches
# ---------------------------------------------------------------------------


def _cache_size() -> int:
    return _env.EXEC_CACHE_SIZE.get()


_CACHE = ExecutableCache(maxsize=_cache_size())

# telemetry re-homing (docs/observability): the cache's own counters are
# the authoritative compile/hit/miss source — the collector snapshots
# them instead of double-counting on the hot path. Only the cold compile
# (already seconds-scale) opens a span + histogram observation.
_COMPILE_HIST = _telemetry.histogram(
    "engine.compile_seconds",
    "Wall time of cold XLA compiles through the executable cache")
_LOAD_HIST = _telemetry.histogram(
    "engine.load_seconds",
    "Wall time of persisted-AOT-artifact loads (deserialize instead "
    "of compile) through the executable cache")
_PERSIST_FAIL = _telemetry.counter(
    "engine.persistent_cache_failures",
    "enable_persistent_cache attempts that failed (jax persistent "
    "compilation cache could not be wired)")

# one warning per (reason) per process for unusable AOT artifacts —
# the counter carries the volume, the warning carries the diagnosis
_aot_warned: set = set()


def _lifetime_rollup() -> EngineStats:
    """The reset-proof rollup (current window included) — ONE
    implementation for both the telemetry snapshot and the
    ``dump_stats`` artifact the CI jit-leak gate reads, so the two
    views can never desynchronize."""
    lifetime = EngineStats()
    lifetime.merge(_CACHE.lifetime)
    lifetime.merge(_CACHE.stats)
    return lifetime


def _telemetry_engine_block() -> dict:
    return {"stats": _CACHE.stats.to_dict(),
            "lifetime": _lifetime_rollup().to_dict(),
            "cache_entries": len(_CACHE)}


_telemetry.register_collector("engine", _telemetry_engine_block)


def cache() -> ExecutableCache:
    """The process-global executable cache."""
    return _CACHE


def stats() -> EngineStats:
    """Global engine counters (hits/misses/recompiles/compile time)."""
    return _CACHE.stats


def reset() -> None:
    """Drop every executable and zero the counters (tests/benches)."""
    _CACHE.reset()


def donation_enabled() -> bool:
    """Whether solver entry points donate their operands
    (``SKYLARK_ENGINE_DONATE=1``). Off by default: donation invalidates
    the caller's arrays (on every backend, CPU included)."""
    return _env.ENGINE_DONATE.get()


def maybe_donate(argnums: Sequence[int]) -> tuple[int, ...]:
    """``argnums`` when donation is enabled, else ``()`` — the one-line
    policy the solver entry points use for their donate_argnums."""
    return tuple(argnums) if donation_enabled() else ()


# ---------------------------------------------------------------------------
# persistent (cross-process) compilation cache wiring
# ---------------------------------------------------------------------------

_persistent_wired = False


def enable_persistent_cache(path: Optional[str] = None) -> bool:
    """Wire jax's persistent compilation cache at ``path`` (or
    ``SKYLARK_EXEC_CACHE_DIR``). Returns whether a cache directory is in
    effect. Never raises — the persistent cache is an optimization, not
    a failure mode.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: jax reads that variable itself, and this function — the
    only place in the tree that sets the directory — leaves it alone
    (the directory is part of the cache key; a second location never
    hits)."""
    global _persistent_wired
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _persistent_wired = True
        return True
    path = path or _env.EXEC_CACHE_DIR.raw()
    if not path or path.strip().lower() in ("0", "off", "no", "false"):
        return False
    try:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax memoizes a "cache disabled" decision at the first
        # compile; dropping it makes the next compile re-read the
        # config — without this, wiring after any eager op (key
        # fold_in, a warm-up) is silently a no-op
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        # below jax's 1.0 s default: solver pipeline executables
        # backend-compile in well under a second on CPU hosts yet are
        # exactly the artifacts worth persisting for the serve-many
        # processes
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.1)
        _persistent_wired = True
        return True
    except Exception as e:  # noqa: BLE001 — optimization, not failure
        # observable, not silent (r13 satellite): one warning plus an
        # always-on counter, so "the persistent cache never engaged"
        # shows up in telemetry instead of as a mystery cold fleet
        _PERSIST_FAIL.inc_always(reason=type(e).__name__)
        warnings.warn(
            f"jax persistent compilation cache could not be wired at "
            f"{path!r}: {e!r} — continuing without it",
            RuntimeWarning, stacklevel=2)
        return False


def _maybe_wire_persistent() -> None:
    global _persistent_wired
    if not _persistent_wired and _env.EXEC_CACHE_DIR.is_set():
        _persistent_wired = True  # one attempt per process
        enable_persistent_cache()


# ---------------------------------------------------------------------------
# cache-key components
# ---------------------------------------------------------------------------

_code_hashes: dict[str, str] = {}


def _file_hash(path: str) -> str:
    h = _code_hashes.get(path)
    if h is None:
        try:
            with open(path, "rb") as fh:
                h = hashlib.sha256(fh.read()).hexdigest()[:16]
        except OSError:
            h = "unreadable"
        _code_hashes[path] = h
    return h


def code_version(fn: Callable) -> str:
    """Code-version component of the cache key: a hash over the wrapped
    solver's defining module plus the engine's own sources, so editing
    either invalidates persisted executables keyed on it (the
    cross-process analog of "recompile after a code change")."""
    paths = [__file__, os.path.join(os.path.dirname(__file__), "cache.py")]
    try:
        src = inspect.getsourcefile(fn)
        if src:
            paths.append(src)
    except TypeError:
        pass
    return "-".join(_file_hash(p) for p in paths)


def digest(obj) -> str:
    """Stable identity of a closed-over collaborator (sketch transform,
    kernel, params block) for ``key_fn`` extras: the hash of its JSON
    serialization when it has one (``to_json`` — transforms serialize
    their (seed, counter) creation context, kernels their
    hyperparameters), else its ``repr``. Two transform *objects* with
    the same serialization are the same pure function of the input —
    and share one executable."""
    try:
        doc = obj.to_json()
    except AttributeError:
        doc = repr(obj)
    return hashlib.sha256(str(doc).encode()).hexdigest()[:16]


def _precision_fingerprint() -> tuple:
    from libskylark_tpu.base import precision

    try:
        ambient = precision.ambient_matmul_precision()
    except Exception:
        ambient = None
    return (precision.get_solver_precision(), str(ambient))


def _aval_key(x) -> tuple:
    shape = tuple(getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    return (shape, dtype)


def _sharding_key(x) -> str:
    try:
        return str(x.sharding)
    except Exception:
        return "unsharded"


# ---------------------------------------------------------------------------
# the wrapper
# ---------------------------------------------------------------------------


class CompiledFn:
    """A solver pipeline bound to the executable cache. Call it like the
    wrapped function; statics go by keyword (``static_argnames``),
    everything positional is a traced array."""

    def __init__(self, fn: Callable, *, static_argnames: Sequence[str] = (),
                 donate_argnums: Sequence[int] = (),
                 donate: str = "explicit",
                 key_fn: Optional[Callable] = None,
                 name: Optional[str] = None):
        if donate not in ("explicit", "auto"):
            raise ValueError(f"donate must be 'explicit' or 'auto', "
                             f"got {donate!r}")
        self._fn = fn
        self._static_argnames = tuple(static_argnames)
        self._donate_argnums = tuple(donate_argnums)
        self._donate_mode = donate
        self._key_fn = key_fn
        self.name = name or getattr(fn, "__qualname__", repr(fn))
        self.stats = EngineStats()
        # per-wrapper counters are bumped from serve worker threads too;
        # bare += on a dataclass field is a read-modify-write race
        self._stats_lock = _locks.make_lock("engine.fn_stats")
        self._code_version = None
        functools.update_wrapper(self, fn)

    # -- key --

    def _effective_donate(self) -> tuple[int, ...]:
        """``donate="auto"`` sites (the public solver entry points)
        donate only when the user opted in (SKYLARK_ENGINE_DONATE=1);
        "explicit" sites always honor their argnums. The effective
        tuple is part of the cache key — flipping the opt-in mid-
        process keys a fresh executable rather than mis-serving one
        with the wrong aliasing contract."""
        if self._donate_mode == "auto" and not donation_enabled():
            return ()
        return self._donate_argnums

    def _key(self, args, statics, kwargs, donate_argnums) -> tuple:
        if self._code_version is None:
            self._code_version = code_version(self._fn)
        extra = self._key_fn(*args, **kwargs) if self._key_fn else ()
        return (
            self.name,
            self._code_version,
            statics,
            extra,
            tuple(_aval_key(a) for a in args),
            tuple(_sharding_key(a) for a in args),
            donate_argnums,
            _precision_fingerprint(),
            jax.default_backend(),
        )

    # -- cold-key materialization: AOT load > single-flight compile --

    def _aot_load_entry(self, key) -> Optional[CacheEntry]:
        """Deserialize the key's persisted artifact into a cache entry
        (None on plain miss). An artifact that exists but is unusable
        — compat mismatch, torn file, deserialize failure — counts an
        ``aot_load_failures``, warns once per reason, and returns None
        so the caller compiles fresh."""
        try:
            got = _aot.load(key)
        except _aot.AotLoadError as e:
            with self._stats_lock:
                self.stats.aot_load_failures += 1
            _CACHE.note_aot_load_failure()
            if e.reason not in _aot_warned:
                _aot_warned.add(e.reason)
                warnings.warn(
                    f"persisted AOT artifact for {self.name!r} is "
                    f"unusable ({e}); recompiling", RuntimeWarning,
                    stacklevel=3)
            return None
        if got is None:
            return None
        executable, _header, dt = got
        _LOAD_HIST.observe_always(dt, name=self.name)
        with self._stats_lock:
            self.stats.aot_loads += 1
            self.stats.load_seconds += dt
        _CACHE.note_aot_load(dt)
        return CacheEntry(executable=executable, name=self.name,
                          compile_seconds=0.0, loaded=True)

    def _materialize(self, key, args, kwargs, donate_argnums) -> CacheEntry:
        """Resolve one cold key, owning the in-process single-flight:
        load the persisted artifact if the store has it; otherwise take
        the cross-process file lock (so N racing cold *processes*
        produce one compile fleet-wide — a lock wait usually ends with
        the winner's artifact ready to load), and only then compile —
        serializing the result back into the store for the next
        process. The caller aborts the in-process single-flight on any
        raise; the file lock is released here either way."""
        lock = None
        try:
            if _aot.enabled():
                had_artifact = os.path.exists(
                    _aot.artifact_path(_aot.key_digest(key)))
                entry = self._aot_load_entry(key)
                if entry is not None:
                    _CACHE.insert(key, entry)
                    return entry
                lock = _aot.lock_for(key)
                if (lock.acquire(timeout=_aot.lock_timeout())
                        and not had_artifact):
                    # the wait may have spanned a peer's compile+save:
                    # re-probe before compiling ourselves. Skip it when
                    # an artifact was already present and judged
                    # unusable — re-reading the same bytes would only
                    # double-count the failure
                    entry = self._aot_load_entry(key)
                    if entry is not None:
                        _CACHE.insert(key, entry)
                        return entry
                # acquire timeout: compile anyway (liveness) but skip
                # the save — we are not the elected single writer
            entry = self._backend_compile(key, args, kwargs,
                                          donate_argnums)
            if lock is not None and lock.held:
                _aot.save(key, entry.executable, name=self.name,
                          compile_seconds=entry.compile_seconds)
            _CACHE.insert(key, entry)
            return entry
        finally:
            if lock is not None:
                lock.release()

    def _backend_compile(self, key, args, kwargs,
                         donate_argnums) -> CacheEntry:
        t0 = time.perf_counter()
        # chaos seam: a compile-path fault takes the same abort
        # route as a real XLA failure, so injection exercises
        # the single-flight waiter-release contract too
        with _telemetry.span("engine.compile",
                             attrs={"name": self.name}):
            _faults.check("engine.compile", detail=self.name)
            jitted = jax.jit(
                self._fn,
                static_argnames=self._static_argnames or None,
                donate_argnums=donate_argnums or None,
            )
            # apart: tracing + lowering is paid by every process, the
            # backend compile only where the persistent cache misses
            with _telemetry.span("engine.lower",
                                 attrs={"name": self.name}):
                lowered = jitted.lower(*args, **kwargs)
            with _telemetry.span("engine.backend_compile",
                                 attrs={"name": self.name}):
                executable = lowered.compile()
        dt = time.perf_counter() - t0
        # always recorded: compiles are seconds-scale (the
        # histogram bump is noise) and the bench snapshot embeds
        # compile-time data even with telemetry off
        _COMPILE_HIST.observe_always(dt, name=self.name)
        with self._stats_lock:
            self.stats.compiles += 1
            self.stats.compile_seconds += dt
        _CACHE.note_compile()
        return CacheEntry(executable=executable, name=self.name,
                          compile_seconds=dt)

    # -- call --

    def __call__(self, *args, **kwargs):
        with _telemetry.span("engine.call",
                             attrs={"name": self.name}) as sp:
            return self._call(sp, args, kwargs)

    def _call(self, sp, args, kwargs):
        import jax.numpy as jnp

        statics = tuple(
            (k, kwargs[k]) for k in self._static_argnames if k in kwargs
        )
        unknown = set(kwargs) - set(self._static_argnames)
        if unknown:
            raise TypeError(
                f"engine.compiled({self.name}): dynamic arguments must be "
                f"positional; got keyword {sorted(unknown)!r}")
        args = tuple(
            a if isinstance(a, jax.Array) else jnp.asarray(a) for a in args
        )
        donate_argnums = self._effective_donate()
        with _telemetry.span("engine.lookup"):
            key = self._key(args, statics, kwargs, donate_argnums)
            # single-flight: on a cold key exactly one thread
            # materializes (AOT artifact load, else compile) while
            # concurrent callers of the same key block in acquire()
            entry = _CACHE.acquire(key)
        if sp is not None:
            sp.set_attr("hit", entry is not None)
        if entry is None:
            with self._stats_lock:
                self.stats.misses += 1
            _maybe_wire_persistent()
            try:
                entry = self._materialize(key, args, kwargs,
                                          donate_argnums)
            except BaseException:
                _CACHE.abort(key)
                raise
        else:
            with self._stats_lock:
                self.stats.hits += 1
        with _telemetry.span("engine.execute"):
            t0 = time.perf_counter()
            out = entry.executable(*args)
            dt = time.perf_counter() - t0  # dispatch wall; async past this
        with self._stats_lock:
            self.stats.executions += 1
            self.stats.execute_seconds += dt
        _CACHE.note_execution(entry, dt)
        return out


def compiled(fn: Optional[Callable] = None, *,
             static_argnames: Sequence[str] = (),
             donate_argnums: Sequence[int] = (),
             donate: str = "explicit",
             key_fn: Optional[Callable] = None,
             name: Optional[str] = None):
    """Wrap ``fn`` (usable as a decorator) in the donation-aware
    executable cache. See the module docstring for key anatomy."""
    if fn is None:
        return functools.partial(
            compiled, static_argnames=static_argnames,
            donate_argnums=donate_argnums, donate=donate, key_fn=key_fn,
            name=name)
    return CompiledFn(fn, static_argnames=static_argnames,
                      donate_argnums=donate_argnums, donate=donate,
                      key_fn=key_fn, name=name)


# ---------------------------------------------------------------------------
# stats dump (CI jit-leak gate)
# ---------------------------------------------------------------------------


def dump_stats(path: str) -> None:
    """Write global counters + per-entry snapshot as JSON, atomically
    (temp file + ``os.replace`` — the CI jit-leak gate reads this at
    process exit and must never see a torn artifact). ``lifetime`` is
    the reset-proof rollup (current window included) — what the gate
    keys off; ``telemetry`` is the unified registry snapshot
    (docs/observability) so the artifact carries the serve/resilience/
    io counters alongside the engine's own."""
    doc = {"stats": _CACHE.stats.to_dict(),
           "lifetime": _lifetime_rollup().to_dict(),
           "entries": _CACHE.snapshot(),
           "cache_size": len(_CACHE)}
    try:
        from libskylark_tpu.engine.serve import serve_stats

        doc["serve"] = serve_stats()
    except Exception:
        pass
    try:
        doc["telemetry"] = _telemetry.snapshot()
    except Exception:
        pass
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)


def _install_stats_dump() -> None:
    path = _env.ENGINE_STATS_DUMP.get()
    if not path:
        return
    import atexit

    atexit.register(lambda: _try_dump(path))


def _try_dump(path: str) -> None:
    try:
        dump_stats(path)
    except Exception:
        pass


_install_stats_dump()
