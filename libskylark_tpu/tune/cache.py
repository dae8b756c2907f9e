"""Persistent on-disk plan cache: certified winners survive the process.

Chip time is scarce; a run that measures a best plan must leave it
somewhere the next process can serve from. The cache is one JSON
document, schema-versioned, keyed by :meth:`Workload.key`:

.. code-block:: json

    {"schema": 1,
     "entries": {
       "tpu_v5_lite|dense_rowwise|normal|float32|8192x8192x1024": {
         "plan": {"backend": "pallas", "m_tile": 512,
                  "precision": "bf16x3"},
         "source": "measured",
         "value": 86.269, "unit": "GB/s",
         "recorded": "2026-07-31T03:23:42+00:00"}}}

``source``: "measured" (a live window timed it — authoritative;
:meth:`record_measurement` only replaces a measured entry with a BETTER
measured value) or "ranked" (offline cost-model winner — any
measurement replaces it).

Location: ``SKYLARK_PLAN_CACHE`` env (a path; ``0``/``off`` disables
persistence entirely), defaulting to ``benchmarks/plan_cache.json`` in
the repo tree when that directory exists (certified plans ride the
repo like the other benchmark artifacts), else
``~/.cache/libskylark_tpu/plan_cache.json``. Schema mismatches load as
EMPTY and never save over the newer file (a downgrade must not destroy
a newer cache); unreadable/corrupt files load empty too — the cache is
an optimization and must never be a failure mode.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from libskylark_tpu.base import env as _env
from libskylark_tpu.base import locks as _locks
from libskylark_tpu.telemetry import metrics as _metrics
from libskylark_tpu.tune.plans import Plan, Workload

SCHEMA = 1

# Unified-registry adapter (docs/observability): the dispatchers'
# cache-consultation outcomes, previously untracked. Always counted —
# a lookup happens once per dispatch (host-side key build dwarfs it)
# and the benchmarks snapshot carries tune counters even with
# telemetry off.
_LOOKUPS = _metrics.counter(
    "tune.plan_cache_lookups",
    "Plan-cache consultations by the sketch-apply dispatchers, "
    "by outcome (hit / miss / malformed)")


def _utcnow() -> str:
    import datetime

    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


def default_cache_path() -> Optional[str]:
    """Resolved cache location, or None when persistence is disabled
    (SKYLARK_PLAN_CACHE=0/off/empty)."""
    if _env.PLAN_CACHE.is_set():
        # set: the parsed value (an off-word parses to None — disabled)
        return _env.PLAN_CACHE.get()
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    repo_bench = os.path.join(here, "benchmarks")
    if os.path.isdir(repo_bench):
        return os.path.join(repo_bench, "plan_cache.json")
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "libskylark_tpu", "plan_cache.json")


class PlanCache:
    """In-memory view of the JSON cache document. Thread-safe for the
    dispatch path (lookup) and the bench feedback path (record+save)."""

    def __init__(self, path: Optional[str] = None,
                 entries: Optional[dict] = None):
        self.path = path
        self.entries: dict[str, dict] = dict(entries or {})
        self._lock = _locks.make_lock("tune.plan_cache")
        self._fingerprint: Optional[str] = None
        self.load_error: Optional[str] = None

    # -- persistence --

    @classmethod
    def load(cls, path: Optional[str]) -> "PlanCache":
        cache = cls(path)
        if path is None:
            return cache
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            return cache
        except Exception as e:  # corrupt file: serve empty, keep file
            cache.load_error = f"{type(e).__name__}: {e}"
            return cache
        if doc.get("schema") != SCHEMA:
            cache.load_error = (f"schema {doc.get('schema')!r} != "
                                f"{SCHEMA} (newer build?) — ignoring")
            return cache
        entries = doc.get("entries")
        if isinstance(entries, dict):
            cache.entries = entries
        return cache

    @staticmethod
    def _prefer(mine: dict, theirs: dict) -> dict:
        """Merge rule for one key present in memory AND on disk (another
        process wrote between our load and save): measured beats
        ranked; among measured with comparable units, the better value
        wins; ties keep ours."""
        m_meas = mine.get("source") == "measured"
        t_meas = theirs.get("source") == "measured"
        if m_meas != t_meas:
            return mine if m_meas else theirs
        mv, tv = mine.get("value"), theirs.get("value")
        if (isinstance(mv, (int, float)) and isinstance(tv, (int, float))
                and mine.get("unit") == theirs.get("unit")
                and tv > mv):
            return theirs
        return mine

    def save(self, path: Optional[str] = None) -> bool:
        """Atomic write (tmp + replace), sorted keys for stable diffs.
        The on-disk document is RE-READ and merged under an advisory
        file lock first: two processes certifying different workloads
        in one window (the bench-A/B-in-separate-processes pattern)
        must not lose each other's winners to a stale-snapshot
        rewrite. Returns False (without writing) when persistence is
        disabled or the on-disk document has a different schema (never
        clobber a newer cache)."""
        path = path or self.path
        if path is None:
            return False
        with self._lock:
            try:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            except OSError:
                return False
            lock_fh = None
            try:
                try:  # advisory lock; best-effort where flock exists
                    import fcntl

                    lock_fh = open(f"{path}.lock", "w")
                    fcntl.flock(lock_fh, fcntl.LOCK_EX)
                except Exception:
                    if lock_fh is not None:  # opened but flock failed
                        lock_fh.close()      # (e.g. ENOLCK on NFS)
                    lock_fh = None
                try:
                    with open(path) as fh:
                        disk = json.load(fh)
                    if disk.get("schema") != SCHEMA:
                        return False
                    for key, ent in (disk.get("entries") or {}).items():
                        if key not in self.entries:
                            self.entries[key] = ent
                        else:
                            self.entries[key] = self._prefer(
                                self.entries[key], ent)
                    self._fingerprint = None  # merge may have changed plans
                except Exception:
                    pass  # absent or unreadable: safe to (re)create
                doc = {"schema": SCHEMA, "entries": self.entries}
                tmp = f"{path}.tmp.{os.getpid()}"
                try:
                    with open(tmp, "w") as fh:
                        json.dump(doc, fh, indent=1, sort_keys=True)
                        fh.write("\n")
                    os.replace(tmp, path)
                except OSError:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    return False
                return True
            finally:
                if lock_fh is not None:
                    lock_fh.close()

    # -- lookup / record --

    def fingerprint(self) -> str:
        """Content hash over the *plans* in the cache (not the
        measurement metadata): the static-key component the solver
        engine (libskylark_tpu/engine) folds into every executable key.
        Hashing only the plan part means re-recording a better
        measurement of the SAME plan leaves every executable valid,
        while editing a cached plan invalidates the engine-served
        pipelines (conservatively: the fingerprint is global, so an
        unrelated-workload plan write also recompiles — over-
        invalidation is a wasted compile, a stale serve would be a
        wrong dispatch).

        Memoized — this sits on the engine's per-call key path — and
        invalidated by :meth:`put` (which every write funnels through).
        Code that mutates ``entries`` directly must call
        :meth:`invalidate_fingerprint`."""
        with self._lock:
            if self._fingerprint is not None:
                return self._fingerprint
            plans = {k: ent.get("plan") for k, ent in
                     sorted(self.entries.items())}
            doc = json.dumps(plans, sort_keys=True, default=str)
            import hashlib

            self._fingerprint = hashlib.sha256(
                doc.encode()).hexdigest()[:16]
            return self._fingerprint

    def invalidate_fingerprint(self) -> None:
        with self._lock:
            self._fingerprint = None

    def lookup(self, w: Workload) -> Optional[Plan]:
        ent = self.entries.get(w.key())
        if not ent:
            _LOOKUPS.inc_always(outcome="miss")
            return None
        try:
            plan = Plan.from_dict(ent["plan"])
        except Exception:
            _LOOKUPS.inc_always(outcome="malformed")
            return None  # malformed entry: heuristic fallback
        _LOOKUPS.inc_always(outcome="hit")
        return plan

    def entry(self, w: Workload) -> Optional[dict]:
        return self.entries.get(w.key())

    def put(self, w: Workload, plan: Plan, *, source: str = "ranked",
            value: Optional[float] = None, unit: Optional[str] = None,
            extra: Optional[dict] = None) -> dict:
        ent = {"plan": plan.to_dict(), "source": source,
               "recorded": _utcnow()}
        if value is not None:
            ent["value"] = float(value)
            ent["unit"] = unit or "GB/s"
        if extra:
            ent.update(extra)
        with self._lock:
            self.entries[w.key()] = ent
            self._fingerprint = None
        return ent

    def record_measurement(self, w: Workload, plan: Plan, value: float,
                           unit: str = "GB/s",
                           extra: Optional[dict] = None) -> bool:
        """Feed one measured result back. A measured entry is only
        replaced by a BETTER measured value (higher, for throughput
        units); ranked entries always yield to measurements. Returns
        whether the cache changed."""
        cur = self.entries.get(w.key())
        if (cur and cur.get("source") == "measured"
                and isinstance(cur.get("value"), (int, float))
                and cur.get("unit", unit) == unit
                and float(value) <= float(cur["value"])):
            return False
        self.put(w, plan, source="measured", value=value, unit=unit,
                 extra=extra)
        return True


# -- process-global cache used by the dispatchers --

_global: Optional[PlanCache] = None
_global_lock = _locks.make_lock("tune.global_cache")


def get_cache() -> PlanCache:
    """The process-global cache, lazily loaded from
    :func:`default_cache_path`."""
    global _global
    with _global_lock:
        if _global is None:
            _global = PlanCache.load(default_cache_path())
        return _global


def set_cache(cache: Optional[PlanCache]) -> Optional[PlanCache]:
    """Inject a cache (tests; also the reload seam after an external
    process rewrote the file). Returns the previous cache. Pass None to
    drop back to lazy-load-from-disk."""
    global _global
    with _global_lock:
        prev, _global = _global, cache
        return prev


def _telemetry_block() -> dict:
    """Snapshot adapter: the ALREADY-LOADED global cache's shape (no
    lazy disk load at snapshot time — a snapshot must not have side
    effects)."""
    with _global_lock:
        c = _global
    if c is None:
        return {"loaded": False}
    return {"loaded": True, "entries": len(c.entries),
            "load_error": c.load_error}


_metrics.register_collector("tune.plan_cache", _telemetry_block)
